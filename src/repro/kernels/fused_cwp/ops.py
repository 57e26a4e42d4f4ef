"""jit'd public wrapper for the fused conv+bias+relu+pool kernel.

Same conventions as kernels/conv_window/ops.py: the pooled-row count is
padded to the block size when ragged (by extending the input with dead
rows and slicing the pooled result), the batch is padded to the batch
block ``bb`` with dead images (sliced off the output), and tile sizes
resolve through the shared policy/tiling layer (DESIGN.md §7): explicit
kwargs > ``ExecPolicy.tiling`` > tuning cache > VMEM-budget heuristic. Under ``ExecPolicy.autotune`` a concrete (untraced) call with
no cache entry first runs the measured candidate search
(repro.ops.autotune) and the winner lands in the cache (DESIGN.md §10).

Registered as the ``pallas`` backend of the ``fused_conv_block`` op family
(repro.ops); its capability predicate requires even conv output dims (the
2×2/2 pool consumes rows in pairs — odd sizes route to the ref/xla
backends, which apply the explicit ``odd`` handling of core.window).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.fused_cwp.kernel import fused_cwp_pallas
from repro.ops.policy import ExecPolicy, current_policy
from repro.ops.tiling import (SUBLANE, choose_fused_blocks, conv_signature,
                              legal_block, tile_params)


@functools.partial(jax.jit, static_argnames=("stride", "interpret", "pb",
                                             "mb", "bb", "name"))
def _fused_cwp_jit(x: jax.Array, w: jax.Array, b: jax.Array | None,
                   scale: jax.Array | None, *,
                   stride: tuple[int, int], interpret: bool,
                   pb: int, mb: int, bb: int, name: str) -> jax.Array:
    bsz, h = x.shape[0], x.shape[2]
    m, kh = w.shape[0], w.shape[2]
    sh = stride[0]
    po = ((h - kh) // sh + 1) // 2

    # pad Po to a multiple of pb with dead input rows; the tail block pools
    # windows over the pad and the result is sliced off
    pad_pool = (-po) % pb
    if pad_pool:
        x = jnp.pad(x, ((0, 0), (0, 0), (0, pad_pool * 2 * sh), (0, 0)))
    # pad B to a multiple of bb with dead images, sliced off the output
    pad_b = (-bsz) % bb
    if pad_b:
        x = jnp.pad(x, ((0, pad_b), (0, 0), (0, 0), (0, 0)))

    bias = jnp.zeros((m,), x.dtype) if b is None else b
    # ×1.0 on the accumulator is exact, so the unquantized path is
    # bit-identical to the pre-epilogue kernel
    s = jnp.ones((m,), jnp.float32) if scale is None else scale
    out = fused_cwp_pallas(x, w, s, bias, stride=stride, pb=pb, mb=mb,
                           bb=bb, interpret=interpret, name=name)
    return out[:bsz, :, :po, :]


def fused_conv_window(x: jax.Array, w: jax.Array, b: jax.Array | None = None,
                      *, stride: tuple[int, int] = (1, 1),
                      odd: str = "raise",
                      scale: jax.Array | None = None,
                      interpret: bool | None = None,
                      pb: int | None = None, mb: int | None = None,
                      bb: int | None = None,
                      stage: str | None = None,
                      policy: ExecPolicy | None = None) -> jax.Array:
    """Fused conv+[requant]+bias+relu+2×2 pool. x: (B,N,H,W), w:
    (M,N,Kh,Kw) -> (B,M,Ho/2,Wo/2). ``scale`` (M,) is the int8 requant
    epilogue applied to the accumulator before bias/relu. ``bb`` batches
    images per grid step (one weight-tile DMA per BB images). Requires
    even conv output dims (``odd`` modes other than even inputs are served
    by the ref/xla backends). The kernel is named ``fused_cwp.<stage>``
    (``fused_cwp`` without a plan stage): the name of its device events."""
    pol = policy if policy is not None else current_policy()
    if interpret is None:
        interpret = pol.resolve_interpret()

    n, h, wdt = x.shape[1], x.shape[2], x.shape[3]
    m, kh, kw = w.shape[0], w.shape[2], w.shape[3]
    sh, sw = stride
    ho = (h - kh) // sh + 1
    wo = (wdt - kw) // sw + 1
    if ho % 2 or wo % 2:
        raise ValueError(
            f"fused kernel needs even conv output dims, got {ho}x{wo}")
    defaults = choose_fused_blocks(n, h, wdt, m, kh, kw, tuple(stride),
                                   x.dtype.itemsize)
    sig = conv_signature(x.shape, w.shape, stride)
    if (pol.autotune and pb is None and mb is None and bb is None
            and not isinstance(x, jax.core.Tracer)):
        from repro.ops.autotune import ensure_tuned  # lazy: cycle
        ensure_tuned("fused_conv_block", x, w, b, stride=tuple(stride),
                     scale=scale, policy=pol)
    tiles = tile_params("fused_conv_block", sig, x.dtype, defaults,
                        pol.tile_overrides)
    if pb is not None:
        tiles["pb"] = pb
    if mb is not None:
        tiles["mb"] = mb
    if bb is not None:
        tiles["bb"] = bb
    # mb must divide M and obey the block rule; pb and bb are free — ragged Po
    # and B are padded
    tiles["mb"] = legal_block(m, tiles["mb"], SUBLANE)
    tiles["pb"] = max(1, tiles["pb"])
    tiles["bb"] = max(1, min(tiles["bb"], x.shape[0]))
    return _fused_cwp_jit(x, w, b, scale, stride=tuple(stride),
                          interpret=interpret, pb=tiles["pb"],
                          mb=tiles["mb"], bb=tiles["bb"],
                          name=f"fused_cwp.{stage}" if stage else "fused_cwp")
