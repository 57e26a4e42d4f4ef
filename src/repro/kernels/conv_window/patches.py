"""Window-stationary Pallas TPU conv2d over whole-window patches.

The layout ``ops.tiling.conv_layout`` gives a conv whose kernel row of
taps (Kw·N) fills under a quarter of the MXU's depth while its whole
window (Kh·Kw·N) fills at least one pass: ResNet-50's 7x7x3 stem. Each
output pixel's Kh·Kw·N window is assembled in VMEM and contracted at
once, the paper's whole K×K window feeding one multiply-add tree
(§III.B). The paper's structure (DESIGN.md §2 row C3) maps so:

  FPGA                         TPU (this kernel)
  ----                         -----------------
  SHIFT_BUFFER (K-1)×(W-K)     the input *slab* (B, H, P·Np, W/P): a
    holds W-K trailing pixels    halo'd stripe of BB images x rows_in rows,
    of the previous K-1 rows     column phases and channels on sublanes,
                                 width on lanes, DMA'd HBM->VMEM once per
                                 grid step
  WINDOW_BUFFER K×K regs       the patch: per pair of output rows, each of
    one window per clock         the Kh + sh slab rows their windows span
                                 gives ⌈Kw/P⌉ lane-shifted (P·Np, Wo)
                                 views, one whole sublane tile each,
                                 stacked into a (K, Wo) operand,
                                 K = (Kh + sh)·⌈Kw/P⌉·P·Np
  K² DSP multipliers +         one (2·MB, K)·(K, Wo) MXU contraction per
    odd-even addition tree       pair of output rows, accumulated in fp32,
                                 the bias added once
  M parallel kernel banks      MB output channels of each of the pair's
                                 rows on the contraction's 2·MB rows (the
                                 Cout grid axis beyond them)

The slab splits the columns into P = stride_w phases (column c·P + e at
lane c, sublane e·Np + ch) and pads the channels to Np, so that P·Np
fills whole sublane tiles: a lane shift o of one slab row then holds the
taps j = o·P + e of every channel at once, and every piece of the patch
is one aligned tile row. Taps j >= Kw and channels >= N meet zero
weights. Two output rows (``PATCH_ROWS``) share one patch: the weights
(``stacked_weights``) hold each row's window, shifted down sh slab rows
for the second, so one contraction fills 2·MB rows of the MXU (128 for
the stem's 64 channels) and each patch loaded into it serves both rows. A single-kernel probe on
a TPU v5e put the stem at 0.36 ms a call this way, 0.40 ms with one row
a contraction and 0.37 ms with the pixels streamed and the channels on
the lanes (PERF.md §6). The output is ``(B, Ho, M, Wo)``; the wrapper
(ops.py) transposes NCHW in and out around the call.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.window import band_input_rows
from repro.kernels.conv_window.kernel import PRECISION
from repro.ops.tiling import PATCH_ROWS, patch_channels


def patch_slab(x: jax.Array, phases: int, np_: int, cols: int
               ) -> jax.Array:
    """(B, N, H, W) -> (B, H, P·Np, cols): rows leading, input column
    c·P + e of channel ch at sublane e·Np + ch, lane c. Channels pad to
    Np and columns to cols·P (or crop to it) with zeros."""
    bsz, n, h, w = x.shape
    x = x[..., :cols * phases]
    x = jnp.pad(x, ((0, 0), (0, np_ - n), (0, 0),
                    (0, cols * phases - x.shape[-1])))
    return (x.reshape(bsz, np_, h, cols, phases)
            .transpose(0, 2, 4, 1, 3)
            .reshape(bsz, h, phases * np_, cols))


def patch_weights(w: jax.Array, phases: int, np_: int) -> jax.Array:
    """(M, N, Kh, Kw) -> (M, Kh·F), F = ⌈Kw/P⌉·P·Np features a slab row,
    ordered (kernel row i, lane shift o, phase e, channel ch) like
    ``window_patch``'s rows; tap j = o·P + e, zero where j >= Kw or
    ch >= N."""
    m, n, kh, kw = w.shape
    offs = -(-kw // phases)
    w = jnp.pad(w, ((0, 0), (0, np_ - n), (0, 0), (0, offs * phases - kw)))
    return (w.reshape(m, np_, kh, offs, phases)
            .transpose(0, 2, 3, 4, 1)
            .reshape(m, kh * offs * phases * np_))


def stacked_weights(w: jax.Array, kh: int, sh: int, mb: int) -> jax.Array:
    """(M, Kh·F) -> (G·M, ((G-1)·sh + Kh)·F), G = ``PATCH_ROWS``: output
    row q of a group reads the window shifted down q·sh slab rows, zero
    elsewhere. Rows are ordered (channel block, q, channel in block), so
    the grid's block of G·MB rows holds every row of the group for its
    MB channels."""
    m, k = w.shape
    f = k // kh
    span = (PATCH_ROWS - 1) * sh * f
    rows = [jnp.pad(w, ((0, 0), (q * sh * f, span - q * sh * f)))
            for q in range(PATCH_ROWS)]
    return (jnp.stack(rows).reshape(PATCH_ROWS, m // mb, mb, k + span)
            .transpose(1, 0, 2, 3).reshape(PATCH_ROWS * m, k + span))


def window_patch(x_ref, img, row, *, rows: int, sh: int, offs: int,
                 width: int) -> jax.Array:
    """The (rows·F, width) patch whose first slab row is output row
    ``row``'s (local to the slab): each of ``rows`` slab rows gives
    ``offs`` lane-shifted views (P·Np, width), stacked on sublanes."""
    pieces = []
    for i in range(rows):
        line = x_ref[img, row * sh + i]                 # (P·Np, cols)
        pieces += [line[:, o:o + width] for o in range(offs)]
    return jnp.concatenate(pieces, axis=0)


def _conv_patches_kernel(x_ref, w_ref, b_ref, o_ref, *, kh: int, sh: int,
                         offs: int, rb: int, bb: int):
    """One grid step: BB images x RB output rows x MB output channels.

    x_ref: (BB, rows_in, P·Np, cols)  halo'd slab, rows_in = (rb-1)·sh + kh
    w_ref: (G·MB, K)                  stacked whole-window weight tile
    b_ref: (MB, 1)                    bias tile
    o_ref: (BB, RB, MB, Wo)           output tile

    One loop over (image, group of G = ``PATCH_ROWS`` rows), each one
    contraction of the group's patch: every group runs the same dot
    whatever the blocks. RB is a multiple of G.
    """
    bias = b_ref[...]
    mb, wo = o_ref.shape[-2:]
    groups = rb // PATCH_ROWS

    def group(t, carry):
        img, r = t // groups, t % groups * PATCH_ROWS
        patch = window_patch(x_ref, img, r, rows=(PATCH_ROWS - 1) * sh + kh,
                             sh=sh, offs=offs, width=wo)
        acc = jax.lax.dot_general(
            w_ref[...], patch, dimension_numbers=(((1,), (0,)), ((), ())),
            precision=PRECISION, preferred_element_type=jnp.float32)
        for q in range(PATCH_ROWS):
            o_ref[img, r + q] = (acc[q * mb:(q + 1) * mb] + bias) \
                .astype(o_ref.dtype)
        return carry

    jax.lax.fori_loop(0, bb * groups, group, 0)


def conv2d_patches_pallas(x: jax.Array, w: jax.Array, b: jax.Array, *,
                          stride: tuple[int, int], rb: int, mb: int,
                          bb: int = 1, interpret: bool,
                          name: str = "conv_window") -> jax.Array:
    """Launch the whole-window kernel; the same contract as
    ``conv2d_window_pallas`` (x: (B, N, H, W); w: (M, N, Kh, Kw); b:
    (M,); rb | Ho, mb | M, bb | B; returns (B, M, Ho, Wo) in x.dtype),
    and RB a multiple of ``PATCH_ROWS``. Grid (B/BB, Ho/RB, M/MB)."""
    bsz, n, h, wdt = x.shape
    m, n2, kh, kw = w.shape
    assert n == n2, (x.shape, w.shape)
    sh, sw = stride
    ho = (h - kh) // sh + 1
    wo = (wdt - kw) // sw + 1
    assert ho % rb == 0 and m % mb == 0, (ho, rb, m, mb)
    assert bsz % bb == 0 and rb % PATCH_ROWS == 0, (bsz, bb, rb)
    np_ = patch_channels(n, sw, x.dtype.itemsize)
    offs = -(-kw // sw)
    xs = patch_slab(x, sw, np_, wo + offs - 1)
    wt = stacked_weights(patch_weights(w, sw, np_), kh, sh, mb)
    kernel = functools.partial(_conv_patches_kernel, kh=kh, sh=sh,
                               offs=offs, rb=rb, bb=bb)
    out = pl.pallas_call(
        kernel,
        grid=(bsz // bb, ho // rb, m // mb),
        in_specs=[
            pl.BlockSpec(
                (pl.Element(bb), pl.Element(band_input_rows(rb, kh, sh)),
                 pl.Element(xs.shape[2]), pl.Element(xs.shape[3])),
                lambda bi, ri, mi: (bi * bb, ri * rb * sh, 0, 0)),
            pl.BlockSpec((PATCH_ROWS * mb, wt.shape[1]),
                         lambda bi, ri, mi: (mi, 0)),
            pl.BlockSpec((mb, 1), lambda bi, ri, mi: (mi, 0)),
        ],
        out_specs=pl.BlockSpec((bb, rb, mb, wo),
                               lambda bi, ri, mi: (bi, ri, mi, 0)),
        out_shape=jax.ShapeDtypeStruct((bsz, ho, m, wo), x.dtype),
        interpret=interpret,
        name=name,
    )(xs, wt.astype(x.dtype), b.reshape(m, 1).astype(x.dtype))
    return out.transpose(0, 2, 1, 3)
