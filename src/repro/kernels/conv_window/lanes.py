"""Window-stationary Pallas TPU conv2d with the channels on the MXU's lanes.

The layout ``ops.tiling.conv_layout`` gives a conv whose channels fill
more of a 128-lane pass than its output width does (and at least half of
one): 52 of ResNet-50's 53 convs. The paper's structure (DESIGN.md §2
row C3) maps so:

  FPGA                         TPU (this kernel)
  ----                         -----------------
  SHIFT_BUFFER (K-1)×(W-K)     the input *slab* (B, H/sh, sh·sw, W/sw, N):
    holds W-K trailing pixels    a halo'd stripe of BB images x rows_in rows,
    of the previous K-1 rows     width on sublanes, channels on lanes, DMA'd
                                 HBM->VMEM once per grid step
  WINDOW_BUFFER K×K regs       the Kh·Kw taps: tap (i, j) of every output
    one window per clock         pixel of the block is one contiguous
                                 (BB, RB, Wo_pad, N) slice, merged for free
                                 into (BB·RB·Wo_pad, N) rows
  K² DSP multipliers +         one (BB·RB·Wo_pad, Kw·N)·(Kw·N, MB) MXU
    odd-even addition tree       contraction per kernel row (one per tap
                                 where N is not a multiple of 128),
                                 accumulated in fp32, the bias added once:
                                 images x rows x width stream through the
                                 systolic array, channels fill its lanes
  M parallel kernel banks      the Cout grid axis, outermost: each weight
                                 tile crosses HBM once per call
  N-channel parallel units     Cin on the lanes of each tap

Rows lead, and rows and columns both split into stride phases (input
pixel (r·sh + a, c·sw + e) sits at row r, phase a·sw + e, column c), so
every tap at any stride is a contiguous slice on every axis and the body
uses no strided or gathered vector access. A 1x1 conv subsamples its
input first and keeps one phase. Wo_pad is Wo rounded up to the sublane
tile, so (RB, Wo_pad, ·) -> (RB·Wo_pad, ·) merges sublanes without a
relayout (Wo = 7 and 14 included); the pad columns are sliced off. The
output is ``(B, Ho, Wo_pad, M)``; the wrapper (ops.py) transposes NCHW
in and out around the call.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.conv_window.kernel import PRECISION
from repro.ops.tiling import LANE, SUBLANE


def lane_slab(x: jax.Array, stride: tuple[int, int], rows: int,
              cols: int) -> jax.Array:
    """(B, N, H, W) -> (B, rows, sh·sw, cols, N): channels on lanes,
    width on sublanes, and both the rows and the columns split into
    phases — input pixel (r·sh + a, c·sw + e) sits at row r, phase
    a·sw + e, column c. A tap at any stride is then a contiguous slice
    on every axis. The input is cropped or zero-padded to rows·sh by
    cols·sw; no valid output reads the pad."""
    sh, sw = stride
    bsz, n = x.shape[:2]
    x = x[:, :, :rows * sh, :cols * sw]
    x = jnp.pad(x, ((0, 0), (0, 0), (0, rows * sh - x.shape[2]),
                    (0, cols * sw - x.shape[3])))
    return (x.reshape(bsz, n, rows, sh, cols, sw)
            .transpose(0, 2, 3, 5, 4, 1)
            .reshape(bsz, rows, sh * sw, cols, n))


def _conv_lanes_kernel(x_ref, w_ref, b_ref, o_ref, *, kh: int, kw: int,
                       stride: tuple[int, int], concat: bool):
    """One grid step: BB images x RB output rows x MB output channels.

    x_ref: (BB, RB + (Kh-1)//sh, sh·sw, Wq, N)   halo'd lane slab
    w_ref: (Kh, Kw·N, MB) if ``concat`` else (Kh·Kw, N, MB)
    b_ref: (1, MB)
    o_ref: (BB, RB, Wo_pad, MB)

    Tap (i, j) of every output pixel in the block is one contiguous
    (BB, RB, Wo_pad, N) slice, merged into (BB·RB·Wo_pad, N) rows: the
    images, rows and columns of the block all stream through one
    contraction per kernel row, channels on the lanes. Where N fills
    whole lane tiles a kernel row's Kw taps concatenate on lanes into
    one (rows, Kw·N) operand; else each tap contracts on its own.
    """
    sh, sw = stride
    bb, rb, wo, mb = o_ref.shape
    n = x_ref.shape[-1]
    rows = bb * rb * wo

    def tap(i, j):
        r0, c0 = i // sh, j // sw
        return x_ref[:, r0:r0 + rb, (i % sh) * sw + j % sw,
                     c0:c0 + wo, :].reshape(rows, n)

    def contract(a, w):
        return jax.lax.dot_general(
            a, w, dimension_numbers=(((1,), (0,)), ((), ())),
            precision=PRECISION, preferred_element_type=jnp.float32)

    acc = None
    for i in range(kh):
        if concat:
            parts = [contract(jnp.concatenate(
                [tap(i, j) for j in range(kw)], axis=1), w_ref[i])]
        else:
            parts = [contract(tap(i, j), w_ref[i * kw + j])
                     for j in range(kw)]
        for part in parts:
            acc = part if acc is None else acc + part
    o_ref[...] = (acc + b_ref[...]).reshape(bb, rb, wo, mb) \
        .astype(o_ref.dtype)


def conv2d_lanes_pallas(x: jax.Array, w: jax.Array, b: jax.Array, *,
                        stride: tuple[int, int], rb: int, mb: int,
                        bb: int = 1, interpret: bool,
                        name: str = "conv_window") -> jax.Array:
    """Launch the channels-on-lanes kernel; the same contract as
    ``conv2d_window_pallas`` (x: (B, N, H, W); w: (M, N, Kh, Kw); b:
    (M,); rb | Ho, mb | M, bb | B; returns (B, M, Ho, Wo) in x.dtype).

    The grid (M/MB, B/BB, Ho/RB) keeps the output-channel axis outermost,
    so each weight tile crosses HBM once per call. A 1x1 conv's stride is
    taken by subsampling the input here. The output width is padded to
    Wo_pad, a multiple of the sublane tile, and the pad sliced off.
    """
    bsz, n, h, wdt = x.shape
    m, n2, kh, kw = w.shape
    assert n == n2, (x.shape, w.shape)
    sh, sw = stride
    ho = (h - kh) // sh + 1
    wo = (wdt - kw) // sw + 1
    assert ho % rb == 0 and m % mb == 0, (ho, rb, m, mb)
    assert bsz % bb == 0, (bsz, bb)
    if kh == kw == 1:
        x, stride = x[:, :, ::sh, ::sw], (1, 1)
        sh, sw = stride
    sub = SUBLANE * 4 // x.dtype.itemsize
    wo_pad = -(-wo // sub) * sub
    xs = lane_slab(x, stride, ho + (kh - 1) // sh, wo_pad + (kw - 1) // sw)
    concat = n % LANE == 0
    wt = w.transpose(2, 3, 1, 0)                       # (Kh, Kw, N, M)
    wt = wt.reshape(kh, kw * n, m) if concat else wt.reshape(kh * kw, n, m)
    kernel = functools.partial(_conv_lanes_kernel, kh=kh, kw=kw,
                               stride=stride, concat=concat)
    rows_in = rb + (kh - 1) // sh
    out = pl.pallas_call(
        kernel,
        grid=(m // mb, bsz // bb, ho // rb),
        in_specs=[
            pl.BlockSpec(
                (pl.Element(bb), pl.Element(rows_in), pl.Element(sh * sw),
                 pl.Element(xs.shape[3]), pl.Element(n)),
                lambda mi, bi, ri: (bi * bb, ri * rb, 0, 0, 0)),
            pl.BlockSpec((wt.shape[0], wt.shape[1], mb),
                         lambda mi, bi, ri: (0, 0, mi)),
            pl.BlockSpec((1, mb), lambda mi, bi, ri: (0, mi)),
        ],
        out_specs=pl.BlockSpec((bb, rb, wo_pad, mb),
                               lambda mi, bi, ri: (bi, ri, 0, mi)),
        out_shape=jax.ShapeDtypeStruct((bsz, ho, wo_pad, m), x.dtype),
        interpret=interpret,
        name=name,
    )(xs, wt.astype(x.dtype), b.reshape(1, m).astype(x.dtype))
    return out[:, :, :wo].transpose(0, 3, 1, 2)
