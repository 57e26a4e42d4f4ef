"""Convolution-window pipeline — paper §III.B.2 (C3).

Three artifacts live here:

1. The *laws* of the paper's window buffer — output sizes (Eq. 1–2), the
   fill latency ``T_u = (K-1)·W + K - 1`` (Fig. 8) and the ``(K-1)/K``
   adjacent-window data-reuse ratio (Fig. 6) — as plain functions used by
   tests and benchmarks.

2. ``LineBufferSim`` — a cycle-accurate software model of the paper's
   WINDOW_BUFFER (K×K) + SHIFT_BUFFER ((K-1)×(W-K)) register structure,
   following the five parallel per-cycle steps of §III.B.2 verbatim. It
   exists to *validate the paper's claims exactly* (one window per cycle
   after T_u; window at cycle K·W is x_(W0); window at cycle H·W is
   x_(H0·W0)). It is NOT the TPU execution path.

3. ``extract_windows`` / ``conv2d_ref`` / ``conv2d_im2col`` — the JAX
   formulations. ``conv2d_ref`` computes convolution in the paper's
   dataflow order (intra-kernel multiply -> odd-even addition tree ->
   input-channel reduction -> bias, Eq. 3–8). ``conv2d_im2col`` is the
   MXU-shaped production formulation the Pallas kernel implements
   (windows become the contracting operand of a matmul).

Layouts follow the paper: input (B, N, H, W), weight (M, N, Hk, Wk),
output (B, M, Ho, Wo).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.addtree import pairwise_sum

__all__ = [
    "conv_output_size",
    "pool_output_size",
    "fill_latency",
    "reuse_ratio",
    "halo_rows",
    "band_input_rows",
    "LineBufferSim",
    "extract_windows",
    "conv2d_ref",
    "conv2d_im2col",
    "maxpool2",
    "pad_spatial",
]


def conv_output_size(in_size: int, k: int, stride: int) -> int:
    """Paper Eq. (1)/(2): floor((H - Hk)/Hs) + 1 over the VALID window
    (the paper's accelerator does not pad); a padded conv passes its
    padded size H + 2·pad."""
    if in_size < k:
        raise ValueError(f"input {in_size} smaller than kernel {k}")
    return (in_size - k) // stride + 1


def pool_output_size(in_size: int, odd: str = "raise") -> int:
    """Output size of a 2×2/stride-2 VALID pool (paper Eq. 1–2 with K=S=2).

    Eq. (1)/(2) give floor((H-2)/2)+1 = floor(H/2): an odd trailing
    row/column contributes no window and is *dropped*. That silent drop is
    made explicit here: ``odd`` is ``"raise"`` (default — odd inputs are a
    sizing bug), ``"drop"`` (the Eq. 1–2 floor), or ``"pad"`` (extend with
    -inf to the next even size, i.e. ceil(H/2))."""
    if odd not in ("raise", "drop", "pad"):
        raise ValueError(f"odd mode {odd!r}; expected raise|drop|pad")
    if in_size % 2 and odd == "raise":
        raise ValueError(
            f"2x2/2 maxpool over an odd size {in_size} drops the last "
            f"row/column (paper Eq. 1-2 floor); pass odd='drop' to accept "
            f"that or odd='pad' to keep a ceil-sized output")
    if in_size % 2 and odd == "pad":
        return (in_size + 1) // 2
    return in_size // 2


def maxpool2(x: jax.Array, *, odd: str = "raise") -> jax.Array:
    """2×2 max pool, stride 2, NCHW — the paper's pooling layers.

    Odd feature-map sizes are handled per ``odd`` (see
    ``pool_output_size``): the old behavior silently dropped the last
    row/column; now that is an explicit choice. Duck-typed graph hook:
    a ``TracedArray`` (repro.graph.trace) records a MaxPool2 node instead
    of computing."""
    hook = getattr(x, "graph_maxpool2", None)
    if hook is not None:
        return hook(odd=odd)
    h, w = x.shape[-2], x.shape[-1]
    # validate (and raise) before any padding
    pool_output_size(h, odd), pool_output_size(w, odd)
    if odd == "pad" and (h % 2 or w % 2):
        pad = [(0, 0)] * (x.ndim - 2) + [(0, h % 2), (0, w % 2)]
        x = jnp.pad(x, pad, constant_values=-jnp.inf)
    return jax.lax.reduce_window(
        x, -jnp.inf, jax.lax.max, (1, 1, 2, 2), (1, 1, 2, 2), "VALID")


def pad_spatial(x: jax.Array, padding: tuple[int, int]) -> jax.Array:
    """Zero-pad the last two (H, W) axes of ``x`` by ``padding`` = (ph,
    pw) on each side: a padded conv is this, then the VALID conv."""
    ph, pw = padding
    if not (ph or pw):
        return x
    return jnp.pad(x, [(0, 0)] * (x.ndim - 2) + [(ph, ph), (pw, pw)])


def fill_latency(k: int, w: int, kw: int | None = None) -> int:
    """Paper Fig. 8: invalid/fill cycles T_u = (K-1)·W + K - 1.

    Generalized to a non-square Kh×Kw window (``k`` rows, ``kw`` cols,
    default square): T_u = (Kh-1)·W + Kw - 1 — Kh-1 full rows must be
    resident plus Kw-1 pixels of the current row. The Kh-1 resident rows
    are exactly the stride-1 halo of the kernels' row blocks
    (``halo_rows(kh, 1)``)."""
    kw = k if kw is None else kw
    return (k - 1) * w + kw - 1


def reuse_ratio(k: int) -> float:
    """Paper Fig. 6: fraction of data shared between horizontally adjacent
    windows = (K-1)/K."""
    return (k - 1) / k


def halo_rows(kh: int, sh: int = 1) -> int:
    """Input rows shared by vertically adjacent row blocks: kh - sh
    (clamped at 0 — stride ≥ kernel means no reuse). At stride 1 this is
    the paper's K-1 resident shift-buffer rows, and
    ``halo_rows(k, 1) / k == reuse_ratio(k)``."""
    return max(kh - sh, 0)


def band_input_rows(rb: int, kh: int, sh: int = 1) -> int:
    """Input rows a block of ``rb`` conv-output rows reads: (rb-1)·sh + kh,
    the height of the halo'd slab each grid step of the window kernels
    DMA's (DESIGN.md §13) — the vertical form of the line buffer's
    fill+stream span (``band_input_rows(1, k, 1) == k``; one more output
    row adds ``sh`` rows, the same marginal cost as one more line-buffer
    step down)."""
    if rb < 1:
        raise ValueError(f"band needs >= 1 output rows, got {rb}")
    return (rb - 1) * sh + kh


class LineBufferSim:
    """Cycle-accurate model of the paper's window cache (Fig. 7).

    Registers:
      WB: K rows × K cols.   Stream enters WB[K-1][0] (paper: "row K, col 1");
          every row shifts right each cycle (col 0 -> col K-1).
      SB: (K-1) rows × (W-K) cols, also right-shifting. The value exiting
          WB row r (r >= 1) at col K-1 enters SB[r-1][0] (paper step 3); the
          value exiting SB row j at col W-K-1 enters WB[j][0] (paper step 5).
      If W == K the shift buffer is empty and WB row exits feed the row above
      directly.

    Because WB shifts right, the *newest* pixel of each row sits at col 0 —
    the window readout therefore reverses columns to recover image order
    (a wiring choice, zero cost in hardware; the paper's figures elide it).

    The five steps of §III.B.2 happen in parallel: each cycle computes all
    reads from the *previous* cycle's register values.

    ``k`` may be a (Kh, Kw) pair for non-square windows: WB becomes
    Kh×Kw, SB becomes (Kh-1)×(W-Kw), and T_u = (Kh-1)·W + Kw - 1 — the
    reference model for the halo accounting of the kernels' row blocks
    (``halo_rows``, ``band_input_rows``, DESIGN.md §13).
    """

    def __init__(self, k: int | tuple[int, int], w: int):
        kh, kw = (k, k) if isinstance(k, int) else k
        if kh < 1 or kw < 1 or w < kw:
            raise ValueError(f"need Kh >= 1 and 1 <= Kw <= W, "
                             f"got Kh={kh} Kw={kw} W={w}")
        self.k = k                        # as given (int for square windows)
        self.kh, self.kw, self.w = kh, kw, w
        self.wb = np.full((kh, kw), np.nan)
        self.sb = np.full((max(kh - 1, 0), max(w - kw, 0)), np.nan)
        self.cycle = 0  # number of pixels streamed so far

    def step(self, value: float) -> None:
        """Stream one pixel (row-major image order). One clock cycle."""
        kh, kw, w = self.kh, self.kw, self.w
        wb_old, sb_old = self.wb.copy(), self.sb.copy()
        # (2) WINDOW_BUFFER right shift
        self.wb[:, 1:] = wb_old[:, :-1]
        # (3)+(4) exits of WB rows 1..Kh-1 enter SHIFT_BUFFER, which shifts
        if kh > 1:
            if w > kw:
                self.sb[:, 1:] = sb_old[:, :-1]
                self.sb[:, 0] = wb_old[1:, kw - 1]
                # (5) SHIFT_BUFFER exits feed WB rows 0..Kh-2, col 0
                self.wb[:kh - 1, 0] = sb_old[:, w - kw - 1]
            else:  # W == Kw: no shift buffer, exits feed the row above
                self.wb[:kh - 1, 0] = wb_old[1:, kw - 1]
        # (1) new datum enters the bottom row, col 0
        self.wb[kh - 1, 0] = value
        self.cycle += 1

    @property
    def window(self) -> np.ndarray:
        """Current Kh×Kw window in image orientation (columns
        un-reversed)."""
        return self.wb[:, ::-1].copy()

    def window_valid(self) -> bool:
        """True when WB holds a complete in-image window (Fig. 8's valid
        region): past the fill latency and not wrapping a row boundary."""
        t = self.cycle
        if t <= fill_latency(self.kh, self.w, self.kw):
            return False
        col = (t - 1) % self.w + 1  # 1-indexed column of the newest pixel
        return col >= self.kw

    def run(self, image: np.ndarray,
            stride: tuple[int, int] = (1, 1)):
        """Stream a full (H, W) image; yield (cycle, row, col, window) for
        every valid window, in paper order x_(1) … x_(H0·W0).

        ``stride`` keeps the dataflow untouched — the buffers shift every
        cycle regardless (the hardware cannot skip pixels) — and simply
        gates the *readout* to the VALID-conv stride grid: windows whose
        top-left corner (row, col) has row % sh == 0 and col % sw == 0.
        That is how the paper's machine realizes Eq. (1)-(2) strides: same
        fill latency, fewer valid readouts."""
        h, w = image.shape
        sh, sw = stride
        assert w == self.w
        for i in range(h):
            for j in range(w):
                self.step(float(image[i, j]))
                if self.window_valid():
                    # newest pixel (i, j) is the window's bottom-right corner
                    r, c = i - self.kh + 1, j - self.kw + 1
                    if r % sh == 0 and c % sw == 0:
                        yield self.cycle, r, c, self.window


def extract_windows(x: jax.Array, k: tuple[int, int],
                    stride: tuple[int, int]) -> jax.Array:
    """All convolution windows of ``x`` (B, N, H, W) -> (B, Ho, Wo, N·Kh·Kw).

    This is the dense-tensor statement of what the line buffer produces one
    entry per cycle: the feature dim is ordered (N, Kh, Kw) to match the
    paper's Eq. (3) reduction order. Implemented with
    ``lax.conv_general_dilated_patches`` (a gather, no FLOPs).
    """
    kh, kw = k
    patches = jax.lax.conv_general_dilated_patches(
        x, filter_shape=(kh, kw), window_strides=stride, padding="VALID",
        dimension_numbers=("NCHW", "OIHW", "NCHW"))
    # patches: (B, N*Kh*Kw, Ho, Wo) with feature order (N, Kh, Kw)
    return jnp.moveaxis(patches, 1, -1)


@partial(jax.jit, static_argnames=("stride",))
def conv2d_ref(x: jax.Array, w: jax.Array, b: jax.Array | None = None,
               stride: tuple[int, int] = (1, 1)) -> jax.Array:
    """Paper-dataflow convolution oracle (Eq. 3–8).

    x: (B, N, H, W); w: (M, N, Kh, Kw); b: (M,) or None -> (B, M, Ho, Wo).

    Dataflow = the paper's: for every window, K²·N fully-parallel multiplies
    (C1 intra-kernel + input-channel parallel), then the odd-even addition
    tree over all N·Kh·Kw products (C2; NO padding to a power of two), then
    the bias. Output channels are vectorized (C1 output-channel parallel).
    Accurate but memory-hungry — tests/small shapes only.
    """
    m, n, kh, kw = w.shape
    win = extract_windows(x, (kh, kw), stride)          # (B,Ho,Wo,N·Kh·Kw)
    prod = win[:, :, :, None, :] * w.reshape(m, n * kh * kw)  # (B,Ho,Wo,M,η)
    out = pairwise_sum(prod, axis=-1)                   # odd-even tree, η=N·K²
    if b is not None:
        out = out + b
    return jnp.moveaxis(out, -1, 1)                     # (B, M, Ho, Wo)


@partial(jax.jit, static_argnames=("stride",))
def conv2d_im2col(x: jax.Array, w: jax.Array, b: jax.Array | None = None,
                  stride: tuple[int, int] = (1, 1)) -> jax.Array:
    """MXU-shaped formulation: windows as matmul operand.

    Same value as ``conv2d_ref``; this is the layout the Pallas kernel
    (kernels/conv_window) realizes tile-by-tile in VMEM. The systolic array
    performs the multiply-add tree of Eq. (9) in hardware.
    """
    m, n, kh, kw = w.shape
    win = extract_windows(x, (kh, kw), stride)          # (B,Ho,Wo,η)
    # HIGHEST: fp32 operands contract fp32-accurately on a TPU too (its
    # default is one bf16 pass), like the Pallas conv kernels
    out = jnp.einsum("bhwe,me->bmhw", win, w.reshape(m, n * kh * kw),
                     precision=jax.lax.Precision.HIGHEST,
                     preferred_element_type=jnp.float32).astype(x.dtype)
    if b is not None:
        out = out + b[None, :, None, None].astype(out.dtype)
    return out
