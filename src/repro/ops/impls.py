"""Backend registrations + the public op entry points (DESIGN.md §7).

Five op families, three backend flavors:

  op               ref (oracle)          xla (jnp/lax)        pallas (kernel)
  ---------------  --------------------  -------------------  ----------------
  conv2d           paper-dataflow        im2col einsum        window-stationary
                   (windows → odd-even   (MXU form)           kernel
                   tree)                                      (kernels/conv_window)
  fused_conv_block unfused ref chain     im2col+relu+pool     fused conv window
                   (conv2d_ref → relu    chain                pipeline
                   → maxpool2, verbatim)                      (kernels/fused_cwp)
  tree_reduce_sum  odd-even pairwise     jnp.sum              addtree kernel
  qmatmul          int32-exact dot       int32-exact dot      blocked int8 GEMM
  causal_conv1d    stacked-window        shifted adds         —
                   einsum

Priorities make auto-selection match the platform: the Pallas kernels are
strongly preferred on TPU and a last resort elsewhere (interpret mode is a
correctness tool, not a fast path), so CPU auto-dispatch lands on the XLA
formulations — exactly the old hardcoded defaults, now derived instead of
scattered.

Quantization (paper C4) is applied here, once, per ``ExecPolicy.quant``:
``qformat`` snaps operands and results to the Qm.n lattice; ``int8`` runs
convs on integer codes with a per-output-channel requant **epilogue**
(scale × accumulator + bias, after the reduction — inside the fused
kernel's pipeline for ``fused_conv_block``) and the real int8 datapath
(``qmatmul``/``qdense``) for dense layers.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.quantize import QTensor, conv_epilogue, quantize_int8
from repro.core.window import (conv2d_im2col, conv2d_ref, maxpool2,
                               pad_spatial)
from repro.core.addtree import pairwise_sum
from repro.ops.policy import ExecPolicy, current_policy
from repro.ops.registry import dispatch, register

__all__ = ["conv2d", "fused_conv_block", "tree_reduce_sum", "qmatmul",
           "qdense", "causal_conv1d", "dense", "quantize_conv_int8",
           "split_requant"]


# ---------------------------------------------------------------- conv2d

@register("conv2d", "ref", priority=1)
def _conv2d_ref(x, w, b=None, *, stride=(1, 1), stage=None, policy=None):
    return conv2d_ref(x, w, b, stride)


@register("conv2d", "xla", priority=10)
def _conv2d_xla(x, w, b=None, *, stride=(1, 1), stage=None, policy=None):
    return conv2d_im2col(x, w, b, stride)


def _conv2d_pallas_ok(x, w, b=None, *, stride=(1, 1), **_) -> bool:
    return (x.ndim == 4 and w.ndim == 4 and x.shape[1] == w.shape[1]
            and x.shape[2] >= w.shape[2] and x.shape[3] >= w.shape[3])


@register("conv2d", "pallas", priority={"tpu": 30, "*": 5},
          supports=_conv2d_pallas_ok)
def _conv2d_pallas(x, w, b=None, *, stride=(1, 1), stage=None, policy=None):
    from repro.kernels.conv_window.ops import conv2d_window  # lazy: pallas
    return conv2d_window(x, w, b, stride=stride, stage=stage, policy=policy)


def _conv_quant_operands(pol: ExecPolicy, x, w, b):
    """Quantize conv operands per the policy (paper C4), shared by the
    ``conv2d`` and ``fused_conv_block`` entry points."""
    if pol.quant == "qformat":
        # Paper-exact fixed point: weights, activations and (implicitly via
        # the lattice) the products all live on the Qm.n grid; accumulation
        # is exact because Q8.8*Q8.8 products fit fp32 integers.
        q = pol.qformat
        return q.quantize(x), q.quantize(w), \
            (None if b is None else q.quantize(b))
    if pol.quant == "int8":
        # int8 weights per output channel, activations per-tensor — kept as
        # QTensors so the conv runs on integer codes and the dequant happens
        # ONCE, per output channel, in the requant epilogue (instead of
        # dequantizing both full operand tensors up front).
        return quantize_conv_int8(x, w) + (b,)
    return x, w, b


def quantize_conv_int8(x, w) -> tuple[QTensor, QTensor]:
    """The int8 conv operand quantization: per-tensor activation QTensor +
    per-output-channel weight QTensor (codes kept in the conv's (M, N, Kh,
    Kw) layout, scale flattened to (M,)). Shared by the eager entry points
    here and the graph compiler's quant-lowering pass (repro.graph)."""
    m = w.shape[0]
    wq = quantize_int8(w.reshape(m, -1), axis=-1)
    xq = quantize_int8(x, axis=None)
    return xq, QTensor(wq.codes.reshape(w.shape), wq.scale.reshape(-1))


def split_requant(x, w):
    """Split int8 QTensor conv operands into (x_codes, w_codes, scale).

    The codes come back as integer-valued float32 arrays (the MXU/VPU
    contraction over η = N·Kh·Kw int8·int8 products is exact in fp32:
    |Σ| ≤ η·127² < 2²⁴ for every conv in this repo) and ``scale`` is the
    per-output-channel requant factor sx·sw with shape (M,), to be applied
    to the accumulator — *after* the reduction, *before* the bias — by the
    backend epilogue. Non-QTensor operands pass through with scale None.
    """
    if not (isinstance(x, QTensor) or isinstance(w, QTensor)):
        return x, w, None
    if not (isinstance(x, QTensor) and isinstance(w, QTensor)):
        raise TypeError(
            "int8 conv needs BOTH operands quantized: got "
            f"x={type(x).__name__}, w={type(w).__name__}")
    scale = (x.scale * w.scale).reshape(-1).astype(jnp.float32)
    return (x.codes.astype(jnp.float32), w.codes.astype(jnp.float32), scale)


def conv2d(x: jax.Array, w: jax.Array, b: jax.Array | None = None, *,
           stride: tuple[int, int] = (1, 1),
           padding: tuple[int, int] = (0, 0), stage: str | None = None,
           policy: ExecPolicy | None = None) -> jax.Array:
    """x: (B, N, H, W) · w: (M, N, Kh, Kw) -> (B, M, Ho, Wo). ``padding``
    = (ph, pw) zero rows/columns on each side (default VALID); the input
    is padded here and every backend runs the VALID conv of the padded
    input. ``stage`` names the plan stage (``s<i>``) the call serves; the
    pallas backend names its kernel ``conv_window.<stage>`` after it.

    Backend and quantization come from ``policy`` (or the active
    ``use_policy`` context). This is the single conv entry point — the
    per-call-site ``path=`` strings it replaces live only in the
    ``core.conv`` deprecation shim.

    Under ``quant="int8"`` (or when called directly with QTensor operands,
    as the compiled plans do) the backend contracts integer codes and the
    per-channel requant scale + bias are applied as an epilogue on the
    small accumulator — the paper's post-accumulate number-format step.
    """
    pol = policy if policy is not None else current_policy()
    x, w, b = _conv_quant_operands(pol, x, w, b)
    x, w, scale = split_requant(x, w)
    out = dispatch("conv2d", pad_spatial(x, padding), w,
                   None if scale is not None else b, stride=stride,
                   stage=stage, policy=pol)
    if scale is not None:
        out = conv_epilogue(out, scale, b)
    if pol.quant == "qformat":
        out = pol.qformat.quantize(out)
    return out


# ------------------------------------------------------ fused_conv_block

@register("fused_conv_block", "ref", priority=1)
def _fused_ref(x, w, b=None, *, stride=(1, 1), odd="raise", scale=None,
               stage=None, policy=None):
    from repro.kernels.fused_cwp.ref import fused_conv_block_ref
    return fused_conv_block_ref(x, w, b, stride, odd, scale=scale)


@register("fused_conv_block", "xla", priority=10)
def _fused_xla(x, w, b=None, *, stride=(1, 1), odd="raise", scale=None,
               stage=None, policy=None):
    out = conv2d_im2col(x, w, None if scale is not None else b, stride)
    if scale is not None:
        out = conv_epilogue(out, scale, b)
    return maxpool2(jax.nn.relu(out), odd=odd)


def _fused_pallas_ok(x, w, b=None, *, stride=(1, 1), odd="raise", **_):
    if not _conv2d_pallas_ok(x, w, b, stride=stride):
        return False
    ho = (x.shape[2] - w.shape[2]) // stride[0] + 1
    wo = (x.shape[3] - w.shape[3]) // stride[1] + 1
    # the fused kernel pools rows/cols in pairs; odd conv outputs take the
    # ref/xla backends (which apply the explicit core.window odd handling)
    return ho % 2 == 0 and wo % 2 == 0 and ho >= 2 and wo >= 2


@register("fused_conv_block", "pallas", priority={"tpu": 30, "*": 5},
          supports=_fused_pallas_ok)
def _fused_pallas(x, w, b=None, *, stride=(1, 1), odd="raise", scale=None,
                  stage=None, policy=None):
    from repro.kernels.fused_cwp.ops import fused_conv_window  # lazy: pallas
    return fused_conv_window(x, w, b, stride=stride, odd=odd, scale=scale,
                             stage=stage, policy=policy)


def fused_conv_block(x: jax.Array, w: jax.Array, b: jax.Array | None = None,
                     *, stride: tuple[int, int] = (1, 1), odd: str = "raise",
                     stage: str | None = None,
                     policy: ExecPolicy | None = None) -> jax.Array:
    """conv + bias + relu + 2×2/2 maxpool as ONE op: (B, N, H, W) ·
    (M, N, Kh, Kw) -> (B, M, Ho/2, Wo/2) (odd dims per ``odd``).

    The paper's deep pipeline between layers (§III.B, DESIGN.md §8): the
    pre-pool activation never materializes in HBM on the pallas backend.
    Quantization matches ``conv2d`` exactly; under ``qformat`` the output
    snap commutes with relu/max (both monotone and 0-preserving), so
    fused output == eager ``maxpool2(relu(conv2d(...)))`` bit-for-bit per
    backend. Under ``int8`` (or with QTensor operands) the requant scale
    rides INTO the backend as the ``scale`` epilogue operand — it must be
    applied before the in-pipeline bias/relu/pool, so unlike ``conv2d``
    it cannot be an outer wrapper here. ``stage`` names the plan stage
    (``s<i>``) the call serves; the pallas backend names its kernel
    ``fused_cwp.<stage>`` after it, so a device trace tells stages apart.
    """
    pol = policy if policy is not None else current_policy()
    x, w, b = _conv_quant_operands(pol, x, w, b)
    x, w, scale = split_requant(x, w)
    out = dispatch("fused_conv_block", x, w, b, stride=stride, odd=odd,
                   scale=scale, stage=stage, policy=pol)
    if pol.quant == "qformat":
        out = pol.qformat.quantize(out)
    return out


# ------------------------------------------------------- tree_reduce_sum

@register("tree_reduce_sum", "ref", priority=1)
def _tree_ref(x, *, policy=None):
    return pairwise_sum(x, axis=-1)


@register("tree_reduce_sum", "xla", priority=10)
def _tree_xla(x, *, policy=None):
    return jnp.sum(x, axis=-1)


@register("tree_reduce_sum", "pallas", priority={"tpu": 30, "*": 5},
          supports=lambda x, **_: x.ndim == 2)
def _tree_pallas(x, *, policy=None):
    from repro.kernels.addtree.ops import tree_reduce_sum as tree_kernel
    return tree_kernel(x, policy=policy)


def tree_reduce_sum(x: jax.Array, *,
                    policy: ExecPolicy | None = None) -> jax.Array:
    """(R, η) -> (R,): odd-even pairwise tree sum along the last axis."""
    return dispatch("tree_reduce_sum", x, policy=policy)


# --------------------------------------------------------------- qmatmul

def _int_dot(x_codes, w_codes, x_scale, w_scale, out_dtype):
    acc = jax.lax.dot_general(
        x_codes.astype(jnp.int32), w_codes.astype(jnp.int32),
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)
    return (acc.astype(jnp.float32) * x_scale * w_scale).astype(out_dtype)


@register("qmatmul", "ref", priority=1)
def _qmatmul_ref(x_codes, w_codes, x_scale, w_scale, *,
                 out_dtype=jnp.float32, policy=None):
    from repro.kernels.qmatmul.ref import qmatmul_ref
    return qmatmul_ref(x_codes, w_codes, x_scale, w_scale, out_dtype)


@register("qmatmul", "xla", priority=10)
def _qmatmul_xla(x_codes, w_codes, x_scale, w_scale, *,
                 out_dtype=jnp.float32, policy=None):
    # the XLA formulation is the int32-accumulating dot itself — what the
    # MXU int8 path lowers to without explicit blocking
    return _int_dot(x_codes, w_codes, x_scale, w_scale, out_dtype)


@register("qmatmul", "pallas", priority={"tpu": 30, "*": 5},
          supports=lambda xc, wc, xs, ws, **_: xc.ndim == 2 and wc.ndim == 2)
def _qmatmul_pallas(x_codes, w_codes, x_scale, w_scale, *,
                    out_dtype=jnp.float32, policy=None):
    from repro.kernels.qmatmul.ops import qmatmul as qmatmul_kernel
    return qmatmul_kernel(x_codes, w_codes, x_scale, w_scale,
                          out_dtype=out_dtype, policy=policy)


def qmatmul(x_codes: jax.Array, w_codes: jax.Array,
            x_scale: jax.Array, w_scale: jax.Array, *,
            out_dtype=jnp.float32,
            policy: ExecPolicy | None = None) -> jax.Array:
    """(M,K) int8 · (K,N) int8 -> (M,N). Scales: x (M,1)|scalar, w (1,N)|scalar."""
    return dispatch("qmatmul", x_codes, w_codes, x_scale, w_scale,
                    out_dtype=out_dtype, policy=policy)


def qdense(x: jax.Array, wq: QTensor, out_dtype=None, *,
           policy: ExecPolicy | None = None) -> jax.Array:
    """fp (…, K) · int8 (K, N) -> fp (…, N): per-token activation quant,
    per-output-channel weight scales — the deployment matmul for quantized
    serving (paper Tab. III '16 bit fixed' row, int8 on TPU)."""
    out_dtype = out_dtype or x.dtype
    lead = x.shape[:-1]
    k = x.shape[-1]
    x2 = x.reshape(-1, k)
    xq = quantize_int8(x2, axis=-1)             # per-row (per-token) scale
    out = qmatmul(xq.codes, wq.codes, xq.scale, wq.scale,
                  out_dtype=out_dtype, policy=policy)
    return out.reshape(*lead, -1)


# --------------------------------------------------------- causal_conv1d

@register("causal_conv1d", "ref", priority=1)
def _causal_conv1d_ref(x, w, b=None, *, policy=None):
    """Oracle: materialize every K-deep window, one einsum (B,T,K,C)."""
    k, c = w.shape
    t = x.shape[1]
    pad = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    win = jnp.stack([pad[:, i:i + t, :] for i in range(k)], axis=2)
    y = jnp.einsum("btkc,kc->btc", win, w)
    return y if b is None else y + b


@register("causal_conv1d", "xla", priority=10)
def _causal_conv1d_xla(x, w, b=None, *, policy=None):
    """K shifted adds (the unrolled window walk); XLA fuses to one pass."""
    k, c = w.shape
    pad = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    t = x.shape[1]
    out = jnp.zeros_like(x)
    for i in range(k):  # K is tiny (2–4); static unroll
        out = out + pad[:, i:i + t, :] * w[i]
    return out if b is None else out + b


def causal_conv1d(x: jax.Array, w: jax.Array, b: jax.Array | None = None, *,
                  policy: ExecPolicy | None = None) -> jax.Array:
    """Depthwise causal 1-D conv — the 1-D window pipeline (DESIGN.md §5).

    x: (B, T, C), w: (K, C) -> (B, T, C); y[t] = Σ_k w[k]·x[t-K+1+k] + b.
    Left-padded so every output sees exactly K (zero-extended) samples,
    matching Mamba's conv1d.
    """
    assert x.shape[-1] == w.shape[-1], (x.shape, w.shape)
    return dispatch("causal_conv1d", x, w, b, policy=policy)


# ----------------------------------------------------------------- dense

def dense(x: jax.Array, w: jax.Array, b: jax.Array | None = None, *,
          policy: ExecPolicy | None = None) -> jax.Array:
    """Policy-aware dense matmul: fp (…, K) · (K, N) -> (…, N).

    Under ``quant="int8"`` the contraction runs on the real int8 datapath
    (per-output-channel weight scales, per-token activation scales, int32
    accumulation via the ``qmatmul`` family); ``"qformat"`` snaps operands
    and result to the Qm.n lattice; ``"none"`` is a plain einsum at HIGHEST
    precision. This is how model layers (``models/layers.py`` MLPs) pick up quantized serving
    from one ``use_policy`` block instead of threading flags.
    """
    pol = policy if policy is not None else current_policy()
    if pol.quant == "int8":
        if w.ndim != 2:
            # never silently degrade a requested datapath (the registry's
            # no-silent-fallback rule): batched/stacked weights have no
            # int8 path here yet
            raise ValueError(
                f"dense under quant='int8' needs a 2-D weight, got "
                f"{w.shape}; reshape or drop to quant='none'")
        wq = quantize_int8(w, axis=0)           # (1, N) per-out-channel
        out = qdense(x, wq, out_dtype=x.dtype, policy=pol)
        return out if b is None else out + b
    if pol.quant == "qformat":
        # keep the whole affine op on the Qm.n lattice, bias included —
        # same discipline as conv2d's qformat path
        q = pol.qformat
        out = q.quantize(jnp.einsum("...d,df->...f", q.quantize(x),
                                    q.quantize(w),
                                    precision=jax.lax.Precision.HIGHEST))
        return out if b is None else q.quantize(out + q.quantize(b))
    # fp32-accurate on a TPU too (its default is one bf16 pass), like the
    # conv kernels; bf16 operands are unaffected
    out = jnp.einsum("...d,df->...f", x, w,
                     precision=jax.lax.Precision.HIGHEST)
    return out if b is None else out + b
