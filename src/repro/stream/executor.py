"""Streaming executors: run one conv/fused stage as halo-overlapped bands.

``stream_conv2d`` / ``stream_fused_conv_block`` mirror the
``repro.ops.conv2d`` / ``fused_conv_block`` entry points exactly — same
operand convention (floats, or QTensors, or pre-split codes + ``scale``),
same quantization discipline, same registry dispatch — but the spatial
loop over output rows is outside the kernel: each band slices
``band_input_rows`` input rows (adjacent bands overlapping on the halo)
and dispatches the *untiled* op on the slice, so the resident working set
is ``band_working_set`` bytes regardless of H.

Bitwise equality with the untiled entry points (pinned by
``tests/test_stream.py`` across quant modes × kernel families × K ×
stride) holds because every step that could differ is hoisted out of the
band loop:

  * operand quantization (``_conv_quant_operands``) runs ONCE on the full
    image — the int8 per-tensor activation scale sees all of H, so each
    band slices exact integer codes rather than re-quantizing;
  * the per-channel requant epilogue and the qformat output snap are
    elementwise, so applying them per band equals applying them untiled;
  * the conv itself is windowed VALID: a band's output element is the
    same η-length dot product either way.

Tile height resolves through the standard machinery
(``repro.ops.tiling.tile_params``) under the op names ``stream_conv2d`` /
``stream_fused_conv_block`` with the single axis ``th`` — so plan-baked
overrides (``"stream_conv2d.th"``), tuning-cache rows written by
``repro.ops.autotune.tune_stream_*``, and the ``SpatialTiling`` spec's
budget-derived default compose in the usual override > cache > heuristic
order.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.quantize import QTensor, conv_epilogue
from repro.ops.policy import ExecPolicy, current_policy
from repro.ops.registry import dispatch
from repro.ops.tiling import conv_signature, tile_params
from repro.stream.tiling import SpatialTiling, conv_bands, pooled_bands

__all__ = ["stream_conv2d", "stream_fused_conv_block", "resolve_tile_rows"]


def _arr(x):
    """The dense array behind a (possibly quantized) activation."""
    return x.codes if isinstance(x, QTensor) else x


def resolve_tile_rows(op: str, x, w, stride, tiling: SpatialTiling,
                      policy: ExecPolicy) -> int:
    """Tile height for this concrete call: SpatialTiling's budget-derived
    default, refined by a tuning-cache row for (op, conv signature,
    dtype, platform), overridden by policy tiling (bind-time autotune
    bakes ``"<op>.th"`` here)."""
    sig = conv_signature(_arr(x).shape, _arr(w).shape, tuple(stride))
    th = tile_params(op, sig, _arr(x).dtype, {"th": tiling.tile_rows},
                     policy.tile_overrides)["th"]
    return max(int(th), 1)


def stream_conv2d(x, w, b=None, *, stride=(1, 1), scale=None,
                  tiling: SpatialTiling,
                  policy: ExecPolicy | None = None) -> jax.Array:
    """Halo-banded ``repro.ops.conv2d``: (B, N, H, W) · (M, N, Kh, Kw) ->
    (B, M, Ho, Wo), bitwise-equal to the untiled entry point."""
    from repro.ops.impls import _conv_quant_operands, split_requant
    pol = policy if policy is not None else current_policy()
    x, w, b = _conv_quant_operands(pol, x, w, b)
    x, w, s = split_requant(x, w)
    if scale is None:
        scale = s
    kh = w.shape[2]
    sh, _ = stride
    ho = (x.shape[2] - kh) // sh + 1
    th = resolve_tile_rows("stream_conv2d", x, w, stride, tiling, pol)
    outs = []
    for _, _, in_lo, in_hi in conv_bands(ho, th, kh, sh):
        xb = x[:, :, in_lo:in_hi, :]
        out = dispatch("conv2d", xb, w, None if scale is not None else b,
                       stride=tuple(stride), policy=pol)
        if scale is not None:
            out = conv_epilogue(out, scale, b)
        if pol.quant == "qformat":
            out = pol.qformat.quantize(out)
        outs.append(out)
    return outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=2)


def stream_fused_conv_block(x, w, b=None, *, stride=(1, 1), odd="raise",
                            scale=None, tiling: SpatialTiling,
                            stage: str | None = None,
                            policy: ExecPolicy | None = None) -> jax.Array:
    """Halo-banded ``repro.ops.fused_conv_block``: bands count *pooled*
    rows (even conv-row cuts — no 2×2 pool window ever straddles bands;
    only the image's own ragged last rows see the ``odd`` mode, exactly
    as untiled). Bitwise-equal to the untiled entry point. Every band's
    call carries the plan ``stage`` it serves."""
    from repro.core.window import pool_output_size
    from repro.ops.impls import _conv_quant_operands, split_requant
    pol = policy if policy is not None else current_policy()
    x, w, b = _conv_quant_operands(pol, x, w, b)
    x, w, s = split_requant(x, w)
    if scale is None:
        scale = s
    kh = w.shape[2]
    sh, _ = stride
    h = x.shape[2]
    ho = (h - kh) // sh + 1
    po = pool_output_size(ho, odd)
    th = resolve_tile_rows("stream_fused_conv_block", x, w, stride,
                           tiling, pol)
    outs = []
    for _, _, in_lo, in_hi in pooled_bands(po, th, kh, sh, h):
        xb = x[:, :, in_lo:in_hi, :]
        out = dispatch("fused_conv_block", xb, w, b, stride=tuple(stride),
                       odd=odd, scale=scale, stage=stage, policy=pol)
        if pol.quant == "qformat":
            out = pol.qformat.quantize(out)
        outs.append(out)
    return outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=2)
