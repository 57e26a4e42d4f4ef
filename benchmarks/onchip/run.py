"""Run one cell of the on-chip benchmark and print its result line.

    python3 benchmarks/onchip/run.py --workload mnist_cnn.offline \\
        --seed 7 --seconds 10 --trace 0

from the repository root, on a machine that holds the chips the cell
asks for. The last line of stdout is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics,
or its per-layer metrics with ``--trace 1``), ``device``, with
``--trace 1`` a ``breakdown``, and last ``checks``: each number the
correctness check compared, beside its limit. Without a TPU, with fewer
chips than the cell asks for, or on a device missing from ``peaks.json``
it exits nonzero and prints no result.
"""
import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import harness  # noqa: E402


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = harness.resolve(args.workload)
    import jax
    import repro.launch.serve  # noqa: F401  (the system under test)
    devices = jax.devices()
    t_init = harness.clock()
    dev = devices[0]
    if dev.platform != "tpu":
        raise SystemExit(f"needs a TPU; JAX found {dev.platform} "
                         f"({dev.device_kind})")
    if len(devices) < cell.spec["chips"]:
        raise SystemExit(f"{cell.name} needs {cell.spec['chips']} chips; "
                         f"JAX found {len(devices)}")
    peaks = json.loads((HERE / "peaks.json").read_text())["devices"]
    if dev.device_kind not in peaks:
        raise SystemExit(f"device {dev.device_kind!r} is not in peaks.json")
    result = harness.run(cell, seed=args.seed, seconds=args.seconds,
                         trace=bool(args.trace),
                         marks={"start": T_START, "init": t_init},
                         peak=peaks[dev.device_kind], device=dev)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
