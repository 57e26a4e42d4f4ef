"""The correctness control of a configuration, on the chip.

For each seed, the reference in the nearest precision below the one the
configuration states (three bfloat16 passes for float32 at highest) is
put in the program's place: its logits over the seed's image pool, a
batch at a time at the configuration's batch, go through the harness's
own check (``harness.check``), as a run's answers do.

    python3 benchmarks/onchip/tools/control.py --config mnist_cnn \\
        --seeds 11 12 13

prints one JSON line per seed with what the check compared and the
``correct`` it decides, and exits nonzero if the control reads correct
on any seed: then the limit cannot tell the two precisions apart.
"""
import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import harness  # noqa: E402
import numpy as np  # noqa: E402


def control_checks(fam, cfg: dict, seed: int, pool_size: int) -> dict:
    weights, images = fam.materialize(cfg, harness.seed_words(seed),
                                      pool_size)
    pool = np.asarray(images)
    served = harness.reference_logits(fam, cfg, weights, pool, "high")
    return harness.check(fam, cfg, weights, pool, served,
                         np.arange(len(pool)), n_unanswered=0)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args()
    import jax
    cfg = harness.load_config(args.config)
    fam = harness.load_family(cfg)
    pool_size = harness.load_traffic("offline")["pool"]
    passed = []
    for seed in args.seeds:
        checks = control_checks(fam, cfg, seed, pool_size)
        correct = harness.is_correct(checks)
        if correct:
            passed.append(seed)
        print(json.dumps({"config": args.config, "seed": seed,
                          "device": jax.devices()[0].device_kind,
                          "correct": correct, "checks": checks}),
              flush=True)
    if passed:
        raise SystemExit(f"the control reads correct on seeds {passed}")


if __name__ == "__main__":
    main()
