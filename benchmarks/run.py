"""Run every paper-table benchmark. Prints ``name,us_per_call,derived``.

``--tuning-cache PATH`` makes the run consume and extend a persisted
tuned-tile table (repro.ops.tiling.TuningCache, versioned JSON): entries
load before any benchmark compiles — op_sweep winners and plan bind-time
autotuning from earlier runs steer this one — and everything measured
here is saved back (merged) at the end.
"""
from __future__ import annotations

import argparse
import os
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tuning-cache", default=None, metavar="PATH",
                    help="persisted tuned-tile table: load before the "
                         "benchmarks, save (merged) after")
    args = ap.parse_args()

    from repro.ops import TUNING_CACHE
    if args.tuning_cache and os.path.exists(args.tuning_cache):
        n = TUNING_CACHE.load(args.tuning_cache)
        print(f"# tuning cache: loaded {n} entries from {args.tuning_cache}")

    print("name,us_per_call,derived")
    from benchmarks import (addtree_resources, batch_sweep, cnn_table,
                            gops_table, op_sweep, pipeline_sweep,
                            plan_boot, roofline_table, serve_slo,
                            serve_throughput, shard_sweep, window_pipeline)
    for mod in (cnn_table, addtree_resources, window_pipeline, op_sweep,
                pipeline_sweep, shard_sweep, batch_sweep,
                gops_table, roofline_table, serve_throughput, serve_slo,
                plan_boot):
        try:
            mod.run()
        except Exception:
            print(f"{mod.__name__},0.0,ERROR")
            traceback.print_exc()

    if args.tuning_cache:
        TUNING_CACHE.save(args.tuning_cache)
        print(f"# tuning cache: saved {len(TUNING_CACHE)} entries to "
              f"{args.tuning_cache}")


if __name__ == "__main__":
    main()
