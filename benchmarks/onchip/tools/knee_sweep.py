"""Find a configuration's latency limit and knee on the chip, once.

1. The closed loop of ``traffic/offline.json`` (full buckets) for
   ``--step-seconds`` gives the full-bucket ``engine_step_ms``; the
   latency limit ``slo_ms`` is five times that.
2. Open-loop Poisson traffic, through the bucket ladder and the top-up
   hold under that limit, at each rate of ``--fractions`` times the
   closed loop's images/s, for ``--seconds`` each, ``--repeat`` times.
   Each window is judged whole: its p95 over every request due in it,
   and the share of those requests answered inside it (a backlog that
   grows through the window leaves it short). A rate meets the limit
   when the median window keeps p95 under ``slo_ms`` and answers at
   least ``KEPT_UP`` of its requests, so that one host stall in one
   window does not decide it. The knee is the highest rate that, with
   every rate below it, meets the limit.

Set-up and windows are the harness's own (``harness.setup`` and
``harness.measure``); one open-loop stack serves every rate in turn.

    python3 benchmarks/onchip/tools/knee_sweep.py --config mnist_cnn

prints one JSON line per window and per rate, and a last line with
``slo_ms`` and ``knee_per_s``; the configuration file keeps them as
plain numbers.
"""
import argparse
import dataclasses
import json
import statistics
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import harness  # noqa: E402

KEPT_UP = 0.99      # share of a window's requests answered inside it


def meets_limit(windows: list[dict], slo_ms: float) -> bool:
    """A rate's verdict from its windows, each judged whole: the median
    window's p95 under the limit, and the median window's requests
    answered inside it to at least ``KEPT_UP``."""
    return (statistics.median(w["p95_ms"] for w in windows) <= slo_ms
            and statistics.median(w["kept_up"] for w in windows)
            >= KEPT_UP)


def knee_fraction(verdicts: dict[float, bool]) -> float | None:
    """The highest fraction that, with every fraction below it, met the
    limit."""
    met = None
    for frac in sorted(verdicts):
        if not verdicts[frac]:
            break
        met = frac
    return met


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--step-seconds", type=float, default=4.0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--repeat", type=int, default=3)
    ap.add_argument("--fractions", type=float, nargs="+",
                    default=[0.3, 0.4, 0.5, 0.6, 0.7, 0.8, 0.9, 1.0])
    args = ap.parse_args()
    cfg = harness.load_config(args.config)
    fam = harness.load_family(cfg)

    closed = harness.Cell(f"{args.config}.closed", {"chips": 1}, cfg,
                          harness.load_traffic("offline"), fam, {})
    _, pool, engine, frontend = harness.setup(closed, args.seed, {})
    got = harness.measure(closed, engine, frontend, pool, args.step_seconds,
                          None, harness.no_span)
    step_ms = 1e3 * got.engine["wall_s"] / got.engine["steps"]
    closed_rate = got.done_in_window / args.step_seconds
    slo_ms = 5 * step_ms
    print(json.dumps({"config": args.config, "engine_step_ms": step_ms,
                      "closed_images_per_s": closed_rate,
                      "slo_ms": slo_ms}), flush=True)
    del engine, frontend

    poisson = {"loop": "open", "buckets": "auto", "pool": len(pool),
               "states": [{"rate_x_knee": 1.0, "mean_dwell_s": None}]}
    opened = harness.Cell(f"{args.config}.sweep", {"chips": 1},
                          dict(cfg, slo_ms=slo_ms), poisson, fam, {})
    _, pool, engine, frontend = harness.setup(opened, args.seed, {})
    verdicts = {}
    for frac in sorted(args.fractions):
        rate = frac * closed_rate
        cell = dataclasses.replace(opened, config=dict(opened.config,
                                                       knee_per_s=rate))
        windows = []
        for r in range(args.repeat):
            due = harness.open_due(cell, args.seconds,
                                   args.seed + 1000 * r)
            got = harness.measure(cell, engine, frontend, pool,
                                  args.seconds, due, harness.no_span)
            lat = harness.latencies_s(got.win)
            w = {"fraction": frac, "rate_per_s": rate,
                 "requests": len(lat),
                 "p50_ms": 1e3 * harness.percentile(lat, 50),
                 "p95_ms": 1e3 * harness.percentile(lat, 95),
                 "kept_up": got.done_in_window / max(1, len(lat)),
                 "pad_fraction": got.engine["pad_lanes"] / max(
                     1, got.engine["lane_steps"] + got.engine["pad_lanes"]),
                 "full_gc": len(got.gc_pauses),
                 "full_gc_longest_s": max(got.gc_pauses, default=0.0)}
            windows.append(w)
            print(json.dumps(w), flush=True)
        verdicts[frac] = meets_limit(windows, slo_ms)
        print(json.dumps({"fraction": frac, "rate_per_s": rate,
                          "meets_limit": verdicts[frac]}), flush=True)
    frac = knee_fraction(verdicts)
    knee = None if frac is None else frac * closed_rate
    print(json.dumps({"config": args.config, "slo_ms": slo_ms,
                      "closed_images_per_s": closed_rate,
                      "knee_per_s": knee}), flush=True)


if __name__ == "__main__":
    main()
