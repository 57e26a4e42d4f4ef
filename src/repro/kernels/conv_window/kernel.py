"""Window-stationary Pallas TPU conv2d — the paper's window buffer on VMEM.

Two operand layouts (DESIGN.md §2 row C3, §3); ``ops.tiling.conv_layout``
picks one from the shape. Channels on the MXU's lanes (``lanes.py``)
where min(N, 128) > min(Wo, 128) and N >= 64: 52 of ResNet-50's 53
convs. The output width on the lanes (this module) elsewhere: the 7x7
stem, the paper CNN's convs, and ``kernels/fused_cwp``, which shares
this module's slab helpers. Width on lanes maps the paper's structure so:

  FPGA                         TPU (this module's kernel)
  ----                         --------------------------
  SHIFT_BUFFER (K-1)×(W-K)     the input *slab*: a (rows_in × W) full-width
    holds W-K trailing pixels    stripe of the image, DMA'd HBM->VMEM once
    of the previous K-1 rows     per (row-block, batch) grid step
  WINDOW_BUFFER K×K regs       the Kh·Kw taps: per output row, each tap is
    one window per clock         a lane-shifted (N, Wo) view of one slab
                                 row; a kernel row's Kw taps stack on
                                 sublanes into a (Kw·N, Wo) operand
  K² DSP multipliers +         one (MB, Kw·N)·(Kw·N, Wo) MXU contraction
    odd-even addition tree       per kernel row, accumulated in fp32
  M parallel kernel banks      the Cout grid axis (output-channel parallel)

Layout (DESIGN.md §8): the slab is ``(B, H, P, N, W/P)`` — rows on a
leading axis, channels on sublanes, width on lanes, and the columns split
into P = stride_w interleaved *phases* (column c sits in phase c % P at
lane c // P). A row is then a dynamic index on an untiled axis and every
tap, strided or not, is a contiguous lane slice, so the kernel body uses
no strided or gathered vector access. The output is ``(B, Ho, M, Wo)``:
its last two block dims are always (MB, Wo), so the row block RB is free
of the 8×128 tiling rule and only MB must be a multiple of 8 or all of M.
With Wo of 7-56 on the lanes most of each MXU pass is empty, which is
why wide convs take the channels-on-lanes kernel. The wrappers transpose
NCHW in and out around the call.

Reuse invariant preserved: each input element crosses HBM->VMEM once per
row block, in a slab of ``band_input_rows(RB, Kh, sh)`` rows (halo rows
of adjacent blocks excepted: ``halo_rows(Kh, sh)``, amortized to
(Kh−s)/(RB·s) per block, the overlap the paper's SHIFT_BUFFER absorbs),
through element offsets on every axis (``pl.Element``), double-buffered
against the previous step's MXU work. This grid is the repo's one row
tiling (DESIGN.md §13). Grid: (B/BB, Ho/RB, M/MB); block sizes come from
ops.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.window import band_input_rows

# fp32 operands contract at full fp32 precision on the MXU; int8 codes
# (integer-valued fp32, |code| <= 127) are exact at any precision
PRECISION = jax.lax.Precision.HIGHEST


def slab_layout(x: jax.Array, phases: int) -> jax.Array:
    """(B, N, H, W) -> (B, H, P, N, ⌈W/P⌉): rows leading, column c in
    phase c % P at lane c // P (zero columns pad W to a multiple of P;
    no valid output ever reads them)."""
    bsz, n, h, w = x.shape
    wp = -(-w // phases)
    x = jnp.pad(x, ((0, 0), (0, 0), (0, 0), (0, wp * phases - w)))
    return x.reshape(bsz, n, h, wp, phases).transpose(0, 2, 4, 1, 3)


def tap_weights(w: jax.Array) -> jax.Array:
    """(M, N, Kh, Kw) -> (Kh, M, Kw·N): one (M, Kw·N) matrix per kernel
    row, features ordered (tap, channel) like ``conv_row``'s operand."""
    m, n, kh, kw = w.shape
    return w.transpose(2, 0, 3, 1).reshape(kh, m, kw * n)


def slab_spec(bb: int, rows_in: int, phases: int, n: int, wp: int,
              row_step: int) -> pl.BlockSpec:
    """The halo'd input slab: ``rows_in`` rows starting every
    ``row_step`` rows, so consecutive row blocks overlap by the halo
    exactly like adjacent line-buffer windows. Every axis is
    element-indexed (Pallas TPU does not mix element and blocked axes)."""
    return pl.BlockSpec(
        (pl.Element(bb), pl.Element(rows_in), pl.Element(phases),
         pl.Element(n), pl.Element(wp)),
        lambda bi, ri, mi: (bi * bb, ri * row_step, 0, 0, 0))


def conv_row(x_ref, w_ref, img, row, *, kh: int, kw: int,
             stride: tuple[int, int], groups: int, group: int,
             width: int) -> jax.Array:
    """fp32 (MB, width) accumulator of conv output row ``row`` (local to
    the slab), restricted to the output columns q·groups + group.

    The slab holds P = groups·stride_w column phases, so output column
    (q·groups + group) at tap j reads input column P·q + group·sw + j:
    phase e % P, lane offset e // P with e = group·sw + j — one
    contiguous lane slice per tap. ``groups=2`` splits even from odd
    output columns, which is how the fused kernel pools columns without
    a strided lane access. Each kernel row's Kw taps stack on sublanes
    into one (Kw·N, width) operand: one MXU contraction per kernel row."""
    sh, sw = stride
    phases = groups * sw
    acc = None
    for i in range(kh):
        rows = [x_ref[img, row * sh + i, ph] for ph in range(phases)]
        taps = []
        for j in range(kw):
            e = group * sw + j
            off = e // phases
            taps.append(rows[e % phases][:, off:off + width])  # (N, width)
        part = jax.lax.dot_general(
            w_ref[i], jnp.concatenate(taps, axis=0),
            dimension_numbers=(((1,), (0,)), ((), ())),
            precision=PRECISION, preferred_element_type=jnp.float32)
        acc = part if acc is None else acc + part
    return acc


def _conv_window_kernel(x_ref, w_ref, b_ref, o_ref, *,
                        kh: int, kw: int, stride: tuple[int, int],
                        rb: int, wo: int, bb: int):
    """One grid step: BB images × RB output rows, one weight-tile DMA.

    x_ref: (BB, rows_in, sw, N, W/sw)  slab, rows_in = (rb-1)*sh + kh
    w_ref: (Kh, MB, Kw*N)              per-kernel-row weight tile
    b_ref: (MB, 1)                     bias tile
    o_ref: (BB, RB, MB, Wo)            output tile

    One loop over (image, row) pairs: each image runs the *same*
    contraction as the BB=1 kernel (bitwise-identical output per image for
    any BB) while the weight tile crosses HBM once per BB images.
    """
    bias = b_ref[...]

    def row(i, carry):
        img, r = i // rb, i % rb
        acc = conv_row(x_ref, w_ref, img, r, kh=kh, kw=kw, stride=stride,
                       groups=1, group=0, width=wo)
        o_ref[img, r] = (acc + bias).astype(o_ref.dtype)
        return carry

    jax.lax.fori_loop(0, bb * rb, row, 0)


def conv2d_window_pallas(x: jax.Array, w: jax.Array, b: jax.Array, *,
                         stride: tuple[int, int], rb: int, mb: int,
                         bb: int = 1, interpret: bool,
                         name: str = "conv_window") -> jax.Array:
    """Launch the kernel. x: (B, N, H, W); w: (M, N, Kh, Kw); b: (M,).

    rb: output rows per block; mb: output channels per block; bb: images
    per grid step (weight reuse — a measured autotuner candidate,
    DESIGN.md §10). Requires rb | Ho, mb | M, bb | B (the wrapper pads).
    Returns (B, M, Ho, Wo) in x.dtype. ``name`` names the kernel's HLO
    instruction, and so its device events.
    """
    bsz, n, h, wdt = x.shape
    m, n2, kh, kw = w.shape
    assert n == n2, (x.shape, w.shape)
    sh, sw = stride
    ho = (h - kh) // sh + 1
    wo = (wdt - kw) // sw + 1
    assert ho % rb == 0 and m % mb == 0, (ho, rb, m, mb)
    assert bsz % bb == 0, (bsz, bb)
    xs = slab_layout(x, sw)
    kernel = functools.partial(_conv_window_kernel, kh=kh, kw=kw,
                               stride=stride, rb=rb, wo=wo, bb=bb)
    out = pl.pallas_call(
        kernel,
        grid=(bsz // bb, ho // rb, m // mb),
        in_specs=[
            slab_spec(bb, band_input_rows(rb, kh, sh), sw, n, xs.shape[-1],
                      rb * sh),
            pl.BlockSpec((kh, mb, kw * n), lambda bi, ri, mi: (0, mi, 0)),
            pl.BlockSpec((mb, 1), lambda bi, ri, mi: (mi, 0)),
        ],
        out_specs=pl.BlockSpec((bb, rb, mb, wo),
                               lambda bi, ri, mi: (bi, ri, mi, 0)),
        out_shape=jax.ShapeDtypeStruct((bsz, ho, m, wo), x.dtype),
        interpret=interpret,
        name=name,
    )(xs, tap_weights(w).astype(x.dtype), b.reshape(m, 1).astype(x.dtype))
    return out.transpose(0, 2, 1, 3)
