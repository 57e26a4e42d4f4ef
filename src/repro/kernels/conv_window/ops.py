"""jit'd public wrapper for the window-stationary conv kernel.

Pads the output-row count to the row block and the batch to the batch
block when ragged (the kernel launchers own the slab/tap layouts), picks
the operand layout from the shape (``ops.tiling.conv_layout``: channels
on lanes where they fill more of a pass than the output width does and
at least half of it; whole-window patches where a kernel row's taps fill
under a quarter of the MXU's depth and the window at least one pass;
else the width-on-lanes slab), and exposes a single ``conv2d_window``
entry point registered as the ``pallas`` backend of the ``conv2d`` op
family (repro.ops).

Block sizes and interpret mode come from the shared policy/tiling layer
(DESIGN.md §7): explicit kwargs > ``ExecPolicy.tiling`` overrides > the
tuning cache > the VMEM-budget heuristic in ``repro.ops.tiling``; interpret
defaults to auto-detection (interpret only off-TPU).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.artifact.warmup import note_layout
from repro.kernels.conv_window.kernel import conv2d_window_pallas
from repro.kernels.conv_window.lanes import conv2d_lanes_pallas
from repro.kernels.conv_window.patches import conv2d_patches_pallas
from repro.ops.policy import ExecPolicy, current_policy
from repro.ops.tiling import (LANE, PATCH_ROWS, SUBLANE,
                              choose_conv_blocks, conv_layout,
                              conv_signature, legal_block, tile_params)


@functools.partial(jax.jit, static_argnames=("stride", "interpret", "rb",
                                             "mb", "bb", "name", "layout"))
def _conv2d_window_jit(x: jax.Array, w: jax.Array, b: jax.Array | None, *,
                       stride: tuple[int, int], interpret: bool,
                       rb: int, mb: int, bb: int, name: str,
                       layout: str) -> jax.Array:
    bsz, h = x.shape[0], x.shape[2]
    m, kh = w.shape[0], w.shape[2]
    sh = stride[0]
    ho = (h - kh) // sh + 1

    # pad Ho to a multiple of rb by extending the input with dead rows —
    # the tail block computes windows over the pad and the result is sliced
    # off. (Rows, not a power-of-two pad: the odd-even rule again.)
    pad_rows = (-ho) % rb
    if pad_rows:
        x = jnp.pad(x, ((0, 0), (0, 0), (0, pad_rows * sh), (0, 0)))
    # pad B to a multiple of bb with dead images, sliced off the output
    pad_b = (-bsz) % bb
    if pad_b:
        x = jnp.pad(x, ((0, pad_b), (0, 0), (0, 0), (0, 0)))

    bias = jnp.zeros((m,), x.dtype) if b is None else b
    launch = {"lanes": conv2d_lanes_pallas,
              "patches": conv2d_patches_pallas,
              "slab": conv2d_window_pallas}[layout]
    out = launch(x, w, bias, stride=stride, rb=rb, mb=mb, bb=bb,
                 interpret=interpret, name=name)
    return out[:bsz, :, :ho, :]


def conv2d_window(x: jax.Array, w: jax.Array, b: jax.Array | None = None,
                  *, stride: tuple[int, int] = (1, 1),
                  interpret: bool | None = None,
                  rb: int | None = None, mb: int | None = None,
                  bb: int | None = None,
                  stage: str | None = None,
                  policy: ExecPolicy | None = None) -> jax.Array:
    """Window-stationary conv2d. x: (B,N,H,W), w: (M,N,Kh,Kw) -> (B,M,Ho,Wo).

    VALID padding, like the paper's accelerator (``repro.ops.conv2d``
    pads the input first for a padded conv). ``interpret=None``
    auto-detects (kernel body interpreted everywhere but TPU);
    ``rb``/``mb``/``bb`` override the resolved tile sizes (``bb`` = images
    per grid step, one weight-tile DMA per BB images). The kernel is
    named ``conv_window.<stage>`` (``conv_window`` without a plan stage):
    the name of its device events. The layout it takes is noted in the
    ambient boot report (``repro.artifact.warmup.note_layout``).
    """
    pol = policy if policy is not None else current_policy()
    if interpret is None:
        interpret = pol.resolve_interpret()

    n, h, wdt = x.shape[1], x.shape[2], x.shape[3]
    m, kh, kw = w.shape[0], w.shape[2], w.shape[3]
    layout = conv_layout(n, (wdt - kw) // stride[1] + 1, kh, kw)
    defaults = choose_conv_blocks(n, h, wdt, m, kh, kw, tuple(stride),
                                  x.dtype.itemsize, batch=x.shape[0])
    sig = conv_signature(x.shape, w.shape, stride)
    if (pol.autotune and rb is None and mb is None and bb is None
            and not isinstance(x, jax.core.Tracer)):
        from repro.ops.autotune import ensure_tuned  # lazy: cycle
        ensure_tuned("conv2d", x, w, b, stride=tuple(stride), policy=pol)
    tiles = tile_params("conv2d", sig, x.dtype, defaults, pol.tile_overrides)
    if rb is not None:
        tiles["rb"] = rb
    if mb is not None:
        tiles["mb"] = mb
    if bb is not None:
        tiles["bb"] = bb
    # mb must divide M and obey the block rule (on lanes in the lane
    # layout, on sublanes in the others); rb and bb are free — ragged Ho
    # and B are padded — but for whole-window patches rb is a multiple of
    # the rows one contraction covers
    tiles["mb"] = legal_block(m, tiles["mb"],
                              LANE if layout == "lanes" else SUBLANE)
    step = PATCH_ROWS if layout == "patches" else 1
    tiles["rb"] = max(1, -(-tiles["rb"] // step)) * step
    tiles["bb"] = max(1, min(tiles["bb"], x.shape[0]))
    name = f"conv_window.{stage}" if stage else "conv_window"
    note_layout(name, layout)
    return _conv2d_window_jit(x, w, b, stride=tuple(stride),
                              interpret=interpret,
                              rb=tiles["rb"], mb=tiles["mb"],
                              bb=tiles["bb"], name=name, layout=layout)
