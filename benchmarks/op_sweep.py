"""Design-space sweep over kernel tile parameters (DESIGN.md §7, §10).

For each registered Pallas-backed op family this measures a candidate grid
per (shape, dtype), reports each point, and writes the winner into the
repro.ops tuning cache — the software analogue of the FPGA design-space
exploration step in the accelerator surveys (arXiv:1806.01683 §"design
space"): the datapath is fixed, the *mapping* is tuned offline. The conv
sweep routes through ``repro.ops.autotune`` (coordinate descent over
rb/pb × mb × bb — the same search ``ExecutionPlan.bind(autotune)`` runs),
so the persisted table is exactly what serving consumes.

``run()`` (benchmarks/run.py) populates the in-process cache and emits CSV.
Standalone use can persist the result and feed it back to any later run:

    PYTHONPATH=src:. python benchmarks/op_sweep.py --out tuning_cache.json
    REPRO_TUNING_CACHE=tuning_cache.json PYTHONPATH=src:. python ...

(or ``--tuning-cache tuning_cache.json`` on ``launch/serve.py`` /
``benchmarks/run.py``, which also saves back what they measure).
"""
from __future__ import annotations

import argparse
import functools

import jax
import jax.numpy as jnp

from benchmarks.common import emit, time_fn
from repro.kernels.addtree.ops import tree_reduce_sum
from repro.kernels.qmatmul.ops import qmatmul
from repro.ops import TUNING_CACHE, ExecPolicy
from repro.ops.autotune import tune_conv2d, tune_fused_conv_block
from repro.ops.tiling import legal_qmatmul_tiles

# (B, N, H, W, M, kh, kw, sh, sw) — the paper's two conv layers + a wide one
CONV_CASES = [
    (8, 1, 28, 28, 15, 3, 3, 1, 1),
    (8, 15, 13, 13, 20, 6, 6, 1, 1),
    (2, 8, 32, 32, 64, 3, 3, 1, 1),
]
TREE_CASES = [(509, 144), (1024, 37)]          # prime R on purpose
TREE_RB = (32, 64, 128, 256)
QMM_CASES = [(128, 256, 128), (96, 144, 80)]   # (M, K, N)
QMM_BLOCKS = (32, 64, 128)


def _sweep_conv() -> None:
    """Conv + fused-conv candidate search via the measured autotuner
    (every probed point is emitted; the winner lands in the cache)."""
    for case in CONV_CASES:
        b, n, h, w, m, kh, kw, sh, sw = case
        x = jax.random.normal(jax.random.PRNGKey(0), (b, n, h, w))
        wt = jax.random.normal(jax.random.PRNGKey(1), (m, n, kh, kw))
        tag = "x".join(map(str, case))

        def point(op, probes):
            def on_point(tiles, us):
                lbl = "_".join(f"{k}{v}" for k, v in sorted(tiles.items()))
                probes[tuple(sorted(tiles.items()))] = us
                emit(f"op_sweep/{op}/{tag}/{lbl}", us)
            return on_point

        def best_row(op, best, probes):
            emit(f"op_sweep/{op}/{tag}/best",
                 probes[tuple(sorted(best.items()))],
                 ";".join(f"{k}={v}" for k, v in sorted(best.items())))

        probes: dict = {}
        best = tune_conv2d(x, wt, stride=(sh, sw),
                           on_point=point("conv2d", probes))
        best_row("conv2d", best, probes)
        ho, wo = (h - kh) // sh + 1, (w - kw) // sw + 1
        if ho % 2 == 0 and wo % 2 == 0:     # fused kernel: even dims only
            probes = {}
            best = tune_fused_conv_block(
                x, wt, stride=(sh, sw),
                on_point=point("fused_conv_block", probes))
            best_row("fused_conv_block", best, probes)


def _sweep_tree() -> None:
    for r, eta in TREE_CASES:
        x = jax.random.normal(jax.random.PRNGKey(eta), (r, eta))
        best, best_us = None, float("inf")
        for rb in TREE_RB:
            us = time_fn(functools.partial(tree_reduce_sum, rb=rb), x)
            emit(f"op_sweep/tree_reduce_sum/{r}x{eta}/rb{rb}", us)
            if us < best_us:
                best, best_us = {"rb": rb}, us
        TUNING_CACHE.put("tree_reduce_sum", (r, eta), x.dtype, best)
        emit(f"op_sweep/tree_reduce_sum/{r}x{eta}/best", best_us,
             f"rb={best['rb']}")


def _sweep_qmatmul() -> None:
    for m, k, n in QMM_CASES:
        xc = jax.random.randint(jax.random.PRNGKey(0), (m, k), -127, 128,
                                jnp.int8)
        wc = jax.random.randint(jax.random.PRNGKey(1), (k, n), -127, 128,
                                jnp.int8)
        xs = jnp.full((m, 1), 0.01, jnp.float32)
        ws = jnp.full((1, n), 0.02, jnp.float32)
        best, best_us = None, float("inf")
        # label + cache the tiles that actually execute: the wrapper clamps
        # each requested block to a legal divisor of its dim, so two
        # requested caps can collapse to the same real tile — dedupe
        tiles = sorted({tuple(legal_qmatmul_tiles(
            m, n, k, {"bm": c, "bn": c, "bk": c}).values())
            for c in QMM_BLOCKS})
        for bm, bn, bk in tiles:
            pol = ExecPolicy(tiling={"bm": bm, "bn": bn, "bk": bk})
            us = time_fn(functools.partial(qmatmul, policy=pol),
                         xc, wc, xs, ws)
            emit(f"op_sweep/qmatmul/{m}x{k}x{n}/bm{bm}_bn{bn}_bk{bk}", us)
            if us < best_us:
                best, best_us = {"bm": bm, "bn": bn, "bk": bk}, us
        TUNING_CACHE.put("qmatmul", (m, k, n), xc.dtype, best)
        emit(f"op_sweep/qmatmul/{m}x{k}x{n}/best", best_us,
             f"bm={best['bm']};bn={best['bn']};bk={best['bk']}")


def run() -> None:
    _sweep_conv()
    _sweep_tree()
    _sweep_qmatmul()
    emit("op_sweep/cache_entries", float(len(TUNING_CACHE)))


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=None,
                    help="write the tuned tile table to this JSON path")
    args = ap.parse_args()
    print("name,us_per_call,derived")
    run()
    if args.out:
        TUNING_CACHE.save(args.out)
        print(f"# saved {len(TUNING_CACHE)} entries to {args.out}")
