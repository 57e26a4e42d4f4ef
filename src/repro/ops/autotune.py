"""Measured autotuning: candidate-grid search over kernel tile parameters.

The paper's accelerator wins by *sizing* its parallel hardware to the layer
at hand (multiplication-addition tree width, window buffer depth, §III.B);
the surveys (arXiv:1806.01683, arXiv:1712.08934) call the same step
design-space exploration and identify it — together with weight-reuse-
maximizing loop order — as the dominant throughput lever. This module is
that step for the TPU kernels (DESIGN.md §10): for one concrete
(op, shape, dtype, platform) call it times real launches over a small
candidate grid and writes the winner into the shared ``TUNING_CACHE``
(repro.ops.tiling), where every later call of the same signature picks it
up ahead of the analytic heuristic.

Search strategy is coordinate descent, one axis at a time in impact order
(``bb`` — the batch block, the weight-reuse knob — then the row block,
then the channel block), starting from the analytic heuristic. The
heuristic point is always measured, and a candidate must beat the
incumbent by ``MIN_GAIN`` (5%) to displace it — without that hysteresis
the search chases scheduler noise and "wins" that do not reproduce (on
CPU interpret runs, where tile choice barely moves wall time, nearly
every winner correctly stays at the heuristic).

Entry points:

  * ``ensure_tuned(op, *args, **kwargs)`` — cache hit or run the search.
    Called by the kernel wrappers under ``ExecPolicy(autotune=True)`` for
    concrete (untraced) calls, and by ``ExecutionPlan.bind`` when the plan
    was compiled with ``autotune=True`` (the winners are then baked into
    the BoundPlan so the serve hot path never re-tunes).
  * ``resolved_backend(op, *args, policy=..., **kwargs)`` — which backend
    dispatch would pick; tuning is skipped when it is not ``"pallas"``
    (tile parameters only bind there — on CPU auto-dispatch lands on XLA
    and there is nothing to tune).

Persistence rides on ``TuningCache.save/load`` (versioned JSON, corrupt or
unknown-version files fall back to heuristics): ``--tuning-cache`` on
``launch/serve.py`` and ``benchmarks/run.py``, or ``REPRO_TUNING_CACHE``.
"""
from __future__ import annotations

import time
from typing import Callable, Mapping

import jax

from repro.ops.policy import ExecPolicy, current_policy
from repro.ops.tiling import (LANE, SUBLANE, TUNING_CACHE,
                              VMEM_BUDGET_BYTES, choose_conv_blocks,
                              choose_fused_blocks, choose_qmatmul_blocks,
                              conv_layout, conv_signature, lane_vmem_bytes,
                              legal_block, legal_qmatmul_tiles,
                              patch_vmem_bytes, window_vmem_bytes)

__all__ = ["ensure_tuned", "tune_conv2d", "tune_fused_conv_block",
           "tune_qmatmul", "resolved_backend",
           "heuristic_tiles", "TUNE_WARMUP", "TUNE_ITERS", "MIN_GAIN"]

# best-of timing per candidate: min over ITERS after WARMUP compile calls.
# Module-level so tests and smoke runs can shrink them.
TUNE_WARMUP = 1
TUNE_ITERS = 3
# a candidate must be at least this much faster than the incumbent to win
# (hysteresis against measurement noise; the heuristic is the incumbent)
MIN_GAIN = 0.05

# candidate values per axis (clamped/deduped against the actual dims)
BATCH_BLOCKS = (1, 2, 4, 8, 16)
ROW_BLOCKS = (1, 2, 4, 8)
CHANNEL_CAPS = (32, 64, 128)
# output-channel blocks of the channels-on-lanes conv: whole lane tiles
LANE_CHANNEL_CAPS = (128, 256)
QMM_CAPS = (32, 64, 128, 256)


def _measure(fn: Callable, *args, warmup: int | None = None,
             iters: int | None = None) -> float:
    """Minimum wall time of ``fn(*args)`` in microseconds (the floor is
    the right estimate for single-digit-ms launches — scheduler noise
    dominates the median at this scale)."""
    warmup = TUNE_WARMUP if warmup is None else warmup
    iters = TUNE_ITERS if iters is None else iters
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    best = float("inf")
    for _ in range(max(iters, 1)):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best * 1e6


def _layout(op: str, x_shape, w_shape, stride) -> str:
    """The window conv layout the call runs (``"slab"`` for the fused
    block, which always runs the slab)."""
    if op != "conv2d":
        return "slab"
    return conv_layout(x_shape[1], (x_shape[3] - w_shape[3]) // stride[1]
                       + 1, w_shape[2], w_shape[3])


def _axis_candidates(op: str, x_shape, w_shape, stride,
                     heuristic: Mapping[str, int]) -> dict[str, list[int]]:
    """Per-axis candidate values for the conv families, heuristic point
    included, clamped to valid ranges and legal blocks, deduped. The
    channels-on-lanes conv takes whole lane tiles for ``mb``."""
    bsz, _, h, _ = x_shape
    m, _, kh, _ = w_shape
    ho = (h - kh) // stride[0] + 1
    axes: dict[str, list[int]] = {}
    bbs = {b for b in BATCH_BLOCKS if b <= bsz} | {heuristic["bb"]}
    axes["bb"] = sorted(bbs)
    if op == "fused_conv_block":
        po = max(ho // 2, 1)
        pbs = {p for p in ROW_BLOCKS if p <= po} | {heuristic["pb"], po}
        axes["pb"] = sorted(pbs)
    else:
        rbs = {r for r in ROW_BLOCKS if r <= ho} | {heuristic["rb"], ho}
        axes["rb"] = sorted(rbs)
    if _layout(op, x_shape, w_shape, stride) == "lanes":
        mbs = {legal_block(m, cap, LANE) for cap in LANE_CHANNEL_CAPS}
    else:
        mbs = {legal_block(m, cap, SUBLANE) for cap in CHANNEL_CAPS}
    mbs.add(heuristic["mb"])
    axes["mb"] = sorted(mbs)
    return axes


def _window_fits(op: str, x_shape, w_shape, stride, itemsize: int
                 ) -> Callable[[Mapping[str, int]], bool]:
    """Whether a candidate's grid step fits the VMEM budget — the tuner
    only ever launches tiles the compiler would accept."""
    _, n, _, w = x_shape
    _, _, kh, kw = w_shape
    layout = _layout(op, x_shape, w_shape, stride)
    if layout != "slab":
        vmem = lane_vmem_bytes if layout == "lanes" else patch_vmem_bytes
        return lambda t: vmem(
            n, w, kh, kw, stride, t["mb"], t["rb"], t["bb"],
            itemsize) <= VMEM_BUDGET_BYTES
    pooled = op == "fused_conv_block"
    rows = "pb" if pooled else "rb"
    return lambda t: window_vmem_bytes(
        n, w, kh, kw, stride, t["mb"], t[rows], t["bb"], itemsize,
        pooled=pooled) <= VMEM_BUDGET_BYTES


def _descend(axes: dict[str, list[int]], start: dict[str, int],
             launch: Callable[..., Callable], *,
             fits: Callable[[Mapping[str, int]], bool] | None = None,
             on_point: Callable[[dict, float], None] | None = None
             ) -> dict[str, int]:
    """Coordinate descent: sweep each axis in insertion order holding the
    others at the current best. A candidate displaces the incumbent only
    when it measures at least ``MIN_GAIN`` faster — the heuristic start
    point survives noise-level "wins". ``launch(**tiles)`` returns a
    zero-arg timed callable; candidates ``fits`` rejects are skipped."""
    measured: dict[tuple, float] = {}

    def probe(cand: dict[str, int]) -> float:
        key = tuple(sorted(cand.items()))
        if key not in measured:
            us = _measure(launch(**cand))
            measured[key] = us
            if on_point is not None:
                on_point(dict(cand), us)
        return measured[key]

    best = dict(start)
    best_us = probe(best)
    for axis, values in axes.items():
        for v in values:
            cand = {**best, axis: v}
            if fits is not None and not fits(cand):
                continue
            us = probe(cand)
            if us < best_us * (1.0 - MIN_GAIN):
                best, best_us = cand, us
    return best


def _no_autotune(policy: ExecPolicy | None) -> ExecPolicy:
    pol = policy if policy is not None else current_policy()
    # the search itself must not recurse into ensure_tuned, and explicit
    # candidate tiles must win over any policy/cache tiling
    return pol.with_options(autotune=False, tiling=())


def resolved_backend(op: str, *args, policy: ExecPolicy | None = None,
                     **kwargs) -> str | None:
    """The backend the registry would dispatch this call to (None when no
    backend accepts it)."""
    from repro.ops.registry import REGISTRY
    pol = policy if policy is not None else current_policy()
    if pol.backend is not None:
        try:
            if REGISTRY.lookup(op, pol.backend).accepts(*args, **kwargs):
                return pol.backend
        except Exception:
            return None
    capable = REGISTRY.supported_backends(op, *args, **kwargs)
    return capable[0] if capable else None


# ------------------------------------------------------------- tuners

def tune_conv2d(x, w, b=None, *, stride=(1, 1),
                policy: ExecPolicy | None = None,
                on_point=None) -> dict[str, int]:
    """Measure (rb, mb, bb) candidates for the window-stationary conv
    kernel on this concrete call; cache and return the winner."""
    from repro.kernels.conv_window.ops import conv2d_window
    pol = _no_autotune(policy)
    heur = choose_conv_blocks(x.shape[1], x.shape[2], x.shape[3], w.shape[0],
                              w.shape[2], w.shape[3], tuple(stride),
                              x.dtype.itemsize, batch=x.shape[0])
    axes = _axis_candidates("conv2d", x.shape, w.shape, tuple(stride), heur)

    def launch(**tiles):
        return lambda: conv2d_window(x, w, b, stride=tuple(stride),
                                     policy=pol, **tiles)

    fits = _window_fits("conv2d", x.shape, w.shape, tuple(stride),
                        x.dtype.itemsize)
    best = _descend(axes, heur, launch, fits=fits, on_point=on_point)
    sig = conv_signature(x.shape, w.shape, tuple(stride))
    TUNING_CACHE.put("conv2d", sig, x.dtype, best)
    return best


def tune_fused_conv_block(x, w, b=None, *, stride=(1, 1), scale=None,
                          policy: ExecPolicy | None = None,
                          on_point=None) -> dict[str, int]:
    """Measure (pb, mb, bb) candidates for the fused conv+relu+pool kernel
    on this concrete call; cache and return the winner. ``scale`` exercises
    the int8 requant epilogue when the caller runs quantized."""
    from repro.kernels.fused_cwp.ops import fused_conv_window
    pol = _no_autotune(policy)
    heur = choose_fused_blocks(x.shape[1], x.shape[2], x.shape[3],
                               w.shape[0], w.shape[2], w.shape[3],
                               tuple(stride), x.dtype.itemsize)
    axes = _axis_candidates("fused_conv_block", x.shape, w.shape,
                            tuple(stride), heur)

    def launch(**tiles):
        return lambda: fused_conv_window(x, w, b, stride=tuple(stride),
                                         scale=scale, policy=pol, **tiles)

    fits = _window_fits("fused_conv_block", x.shape, w.shape,
                        tuple(stride), x.dtype.itemsize)
    best = _descend(axes, heur, launch, fits=fits, on_point=on_point)
    sig = conv_signature(x.shape, w.shape, tuple(stride))
    TUNING_CACHE.put("fused_conv_block", sig, x.dtype, best)
    return best


def tune_qmatmul(x_codes, w_codes, x_scale, w_scale, *,
                 policy: ExecPolicy | None = None,
                 on_point=None) -> dict[str, int]:
    """Measure (bm, bn, bk) candidates for the blocked int8 GEMM; cache
    and return the winner. The kernel never pads, so candidate caps clamp
    to legal blocks of each dim (duplicates deduped by the axis candidate
    sets)."""
    from repro.kernels.qmatmul.ops import qmatmul
    pol = _no_autotune(policy)
    m, k = x_codes.shape
    _, n = w_codes.shape
    heur = choose_qmatmul_blocks(m, n, k)
    legal = [legal_qmatmul_tiles(m, n, k, {"bm": c, "bn": c, "bk": c})
             for c in QMM_CAPS]
    axes = {kk: sorted({t[kk] for t in legal} | {heur[kk]})
            for kk in ("bm", "bn", "bk")}

    def launch(**tiles):
        pol_t = pol.with_options(
            tiling={f"qmatmul.{kk}": vv for kk, vv in tiles.items()})
        return lambda: qmatmul(x_codes, w_codes, x_scale, w_scale,
                               policy=pol_t)

    best = _descend(axes, heur, launch, on_point=on_point)
    TUNING_CACHE.put("qmatmul", (m, k, n), x_codes.dtype, best)
    return best


_TUNERS = {"conv2d": tune_conv2d, "fused_conv_block": tune_fused_conv_block,
           "qmatmul": tune_qmatmul}


def heuristic_tiles(op: str, *args, **kwargs) -> dict[str, int] | None:
    """The tiles a heuristic-only call of this signature resolves to
    (wrapper clamps included) — callers compare a tuned winner against
    this to tell a real move from "the heuristic won" (in which case a
    heuristic-tiled program is already identical and nothing needs
    baking)."""
    if op == "qmatmul":
        m, k = args[0].shape
        n = args[1].shape[1]
        return choose_qmatmul_blocks(m, n, k)
    if op not in ("conv2d", "fused_conv_block"):
        return None
    x, w = args[0], args[1]
    stride = tuple(kwargs.get("stride", (1, 1)))
    dims = (x.shape[1], x.shape[2], x.shape[3], w.shape[0], w.shape[2],
            w.shape[3], stride, x.dtype.itemsize)
    heur = (choose_fused_blocks(*dims) if op == "fused_conv_block"
            else choose_conv_blocks(*dims, batch=x.shape[0]))
    heur["bb"] = max(1, min(heur["bb"], x.shape[0]))
    return heur


def _sig_of(op: str, args, kwargs) -> tuple:
    if op == "qmatmul":
        m, k = args[0].shape
        return (m, k, args[1].shape[1])
    return conv_signature(args[0].shape, args[1].shape,
                          tuple(kwargs.get("stride", (1, 1))))


def ensure_tuned(op: str, *args, policy: ExecPolicy | None = None,
                 **kwargs) -> dict[str, int] | None:
    """Return the tuned tiles for this concrete call, measuring them on a
    cache miss. Returns None (and measures nothing) when the op family is
    unknown to the tuner or dispatch would not land on the pallas backend
    (tile parameters only bind there)."""
    tuner = _TUNERS.get(op)
    if tuner is None:
        return None
    hit = TUNING_CACHE.get(op, _sig_of(op, args, kwargs), args[0].dtype)
    if hit is not None:
        return hit
    if resolved_backend(op, *args, policy=policy, **kwargs) != "pallas":
        return None
    return tuner(*args, policy=policy, **kwargs)
