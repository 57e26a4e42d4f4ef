"""Fused conv+bias+relu+pool Pallas TPU kernel — the deep pipeline between
layers (DESIGN.md §8), batch-blocked (DESIGN.md §10).

This extends the window-stationary conv kernel (kernels/conv_window) by one
pipeline stage: each grid step computes a block of **pooled** output rows,
so the pre-pool activation exists only as VREG/VMEM temporaries inside the
step. Mapping of the paper's §III.B structure:

  FPGA                          TPU (this kernel)
  ----                          -----------------
  window buffer streams rows    the input slab covers 2·PB conv rows
    into conv                     (``band_input_rows(2·PB, Kh, sh)``
                                  input rows, halo overlap with the
                                  next block)
  conv → relu wired directly    the MXU accumulators are requantized,
                                  biased and relu'd in VREGs, never
                                  written back
  2×2 pooling consumes the      each pooled row is the elementwise max of
    conv stream in place          four (MB, Wo/2) accumulators: two conv
                                  rows × {even, odd} output columns — the
                                  (PB, Wo/2) pooled tile is the only thing
                                  DMA'd back to HBM

The even/odd column split comes from the slab layout (conv_window's
``slab_layout`` with 2·stride_w column phases): both column groups are
contiguous lane slices, so pooling needs no strided lane access.

HBM traffic per block: input slab + weight tile + *pooled* output tile —
the (MB, 2·PB, Wo) activation that the unfused path round-trips is gone,
a 4×(+relu) output-traffic reduction on top of the window reuse.

**Batch blocking**: each grid step carries BB images, so the weight tile
is DMA'd once per (pi, mi) *block of images* instead of once per image —
weight HBM traffic drops ~BB×. The step loops over (image, pooled row)
pairs, so every image runs the *same* contraction as the BB=1 kernel and
the output is bitwise identical for any BB (pinned by
tests/test_autotune.py). BB is a measured autotuner
candidate (repro.ops.autotune), not a heuristic default.

Grid: (B/BB, Po/PB, M/MB) with Po = Ho/2 pooled rows. Constraints
(enforced by the wrapper/predicate): Ho and Wo even (2×2/2 pool, VALID),
PB divides Po and BB divides B after ragged padding, MB divides M and is
a multiple of 8 or all of M.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.quantize import requant_epilogue
from repro.core.window import band_input_rows
from repro.kernels.conv_window.kernel import (conv_row, slab_layout,
                                              slab_spec, tap_weights)


def _fused_cwp_kernel(x_ref, w_ref, s_ref, b_ref, o_ref, *,
                      kh: int, kw: int, stride: tuple[int, int],
                      pb: int, wq: int, bb: int):
    """One grid step: BB images × PB pooled rows (slab -> taps -> MXU ->
    ×scale -> +bias -> 2×2 max -> relu), one weight-tile DMA.

    x_ref: (BB, rows_in, 2·sw, N, W/(2·sw))  slab, rows_in = (2·pb−1)·sh+kh
    w_ref: (Kh, MB, Kw·N)                    per-kernel-row weight tile
    s_ref: (MB, 1)   requant scale tile (1.0 when not quantized — an
                     exact no-op multiply on the accumulator)
    b_ref: (MB, 1)   bias tile
    o_ref: (BB, PB, MB, Wo/2)                pooled output tile

    The scale is the int8 requant epilogue: operands arrive as integer
    codes, the MXU contraction accumulates them exactly, and sx·sw[m]
    dequantizes each accumulator in VREGs. relu after the max equals the
    max of relus exactly (both monotone), so the order saves three relus.
    """
    scale = s_ref[...]
    bias = b_ref[...]

    def pooled_row(i, carry):
        img, p = i // pb, i % pb
        pooled = None
        for r in (2 * p, 2 * p + 1):
            for group in (0, 1):
                acc = conv_row(x_ref, w_ref, img, r, kh=kh, kw=kw,
                               stride=stride, groups=2, group=group,
                               width=wq)
                y = requant_epilogue(acc, scale, bias)
                pooled = y if pooled is None else jnp.maximum(pooled, y)
        o_ref[img, p] = jnp.maximum(pooled, 0.0).astype(o_ref.dtype)
        return carry

    jax.lax.fori_loop(0, bb * pb, pooled_row, 0)


def fused_cwp_pallas(x: jax.Array, w: jax.Array, s: jax.Array,
                     b: jax.Array, *, stride: tuple[int, int],
                     pb: int, mb: int, bb: int = 1,
                     interpret: bool, name: str) -> jax.Array:
    """Launch. x: (B, N, H, W); w: (M, N, Kh, Kw); s: (M,) requant scales
    (ones when unquantized); b: (M,) bias.

    pb: pooled output rows per block; mb: output channels per block; bb:
    images per grid step (weight reuse; the winner is measured, see
    repro.ops.autotune). Returns (B, M, Po, Wo/2) in x.dtype; requires
    even Ho/Wo, pb | Po, mb | M, bb | B (the wrapper pads/clamps).
    ``name`` names the kernel's HLO instruction, and so its device events.
    """
    bsz, n, h, wdt = x.shape
    m, n2, kh, kw = w.shape
    assert n == n2, (x.shape, w.shape)
    sh, sw = stride
    ho = (h - kh) // sh + 1
    wo = (wdt - kw) // sw + 1
    assert ho % 2 == 0 and wo % 2 == 0, (ho, wo)
    po, wq = ho // 2, wo // 2
    assert po % pb == 0 and m % mb == 0, (po, pb, m, mb)
    assert bsz % bb == 0, (bsz, bb)
    xs = slab_layout(x, 2 * sw)
    kernel = functools.partial(_fused_cwp_kernel, kh=kh, kw=kw,
                               stride=stride, pb=pb, wq=wq, bb=bb)
    out = pl.pallas_call(
        kernel,
        grid=(bsz // bb, po // pb, m // mb),
        in_specs=[
            slab_spec(bb, band_input_rows(2 * pb, kh, sh), 2 * sw, n,
                      xs.shape[-1], 2 * pb * sh),
            pl.BlockSpec((kh, mb, kw * n), lambda bi, pi, mi: (0, mi, 0)),
            pl.BlockSpec((mb, 1), lambda bi, pi, mi: (mi, 0)),
            pl.BlockSpec((mb, 1), lambda bi, pi, mi: (mi, 0)),
        ],
        out_specs=pl.BlockSpec((bb, pb, mb, wq),
                               lambda bi, pi, mi: (bi, pi, mi, 0)),
        out_shape=jax.ShapeDtypeStruct((bsz, po, m, wq), x.dtype),
        interpret=interpret,
        name=name,
    )(xs, tap_weights(w).astype(x.dtype),
      s.reshape(m, 1).astype(jnp.float32), b.reshape(m, 1).astype(x.dtype))
    return out.transpose(0, 2, 1, 3)
