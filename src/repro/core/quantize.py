"""Fixed-point / integer quantization — paper C4 ("16 bit fixed" in Tab. III).

Two layers:

1. ``QFormat`` — a faithful simulator of the paper's Qm.n fixed-point
   arithmetic (default Q8.8 = 16-bit: 1 sign + 7 integer + 8 fraction).
   Values are held as float but snapped to the fixed-point lattice with
   saturation, exactly what the FPGA datapath computes. Used to validate
   "16-bit fixed point preserves MNIST accuracy" (examples/train_mnist_cnn).

2. int8 symmetric per-channel quantization — the TPU-idiomatic deployment
   path (TPU has int8 MXU throughput, no 16-bit integer path; see DESIGN.md
   §2). Produces the operands consumed by kernels/qmatmul. Also reused for
   int8 KV-cache quantization in repro.serve.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

__all__ = ["QFormat", "QTensor", "quantize_int8", "dequantize_int8",
           "fake_quant_int8", "quantize_tree", "requant_epilogue",
           "conv_epilogue"]


@dataclass(frozen=True)
class QFormat:
    """Qm.n two's-complement fixed point with saturation.

    ``int_bits`` includes the sign bit (paper-style Q8.8: int_bits=8,
    frac_bits=8, total 16). ``quantize`` rounds-to-nearest onto the lattice
    of step 2**-frac_bits and saturates to [-2**(m-1), 2**(m-1) - step].
    """

    int_bits: int = 8
    frac_bits: int = 8

    @property
    def total_bits(self) -> int:
        return self.int_bits + self.frac_bits

    @property
    def step(self) -> float:
        return 2.0 ** -self.frac_bits

    @property
    def max_val(self) -> float:
        return 2.0 ** (self.int_bits - 1) - self.step

    @property
    def min_val(self) -> float:
        return -(2.0 ** (self.int_bits - 1))

    def quantize(self, x: jax.Array) -> jax.Array:
        """Snap to the fixed-point lattice (round-half-to-even, saturate)."""
        scaled = jnp.round(x.astype(jnp.float32) / self.step)
        lo = self.min_val / self.step
        hi = self.max_val / self.step
        return jnp.clip(scaled, lo, hi) * self.step

    def quantize_int(self, x: jax.Array) -> jax.Array:
        """Integer codes (int32 container) for hardware-exact arithmetic."""
        scaled = jnp.round(x.astype(jnp.float32) / self.step)
        lo = self.min_val / self.step
        hi = self.max_val / self.step
        return jnp.clip(scaled, lo, hi).astype(jnp.int32)

    def dequantize_int(self, codes: jax.Array) -> jax.Array:
        return codes.astype(jnp.float32) * self.step


class QTensor(NamedTuple):
    """int8 codes + per-channel fp32 scales. ``values = codes * scale``
    with ``scale`` broadcast along ``axis`` (kept as metadata by caller)."""

    codes: jax.Array   # int8
    scale: jax.Array   # fp32, shape broadcastable against codes


def _absmax(x: jax.Array, axis: int | None) -> jax.Array:
    if axis is None:
        return jnp.max(jnp.abs(x))
    return jnp.max(jnp.abs(x), axis=axis, keepdims=True)


@partial(jax.jit, static_argnames=("axis",))
def quantize_int8(x: jax.Array, axis: int | None = -1) -> QTensor:
    """Symmetric int8 quantization with per-channel scale over ``axis``
    reduced away (i.e. one scale per slice along the other dims).

    axis=None -> per-tensor. Scale = absmax / 127, zero-point = 0 (symmetric,
    like the paper's signed fixed point).
    """
    amax = _absmax(x.astype(jnp.float32), axis)
    scale = jnp.maximum(amax, 1e-8) / 127.0
    codes = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -127, 127)
    return QTensor(codes.astype(jnp.int8), scale)


def dequantize_int8(q: QTensor, dtype=jnp.float32) -> jax.Array:
    return (q.codes.astype(jnp.float32) * q.scale).astype(dtype)


@partial(jax.jit, static_argnames=("axis",))
def fake_quant_int8(x: jax.Array, axis: int | None = -1) -> jax.Array:
    """Quantize-dequantize with a straight-through gradient — used for
    quantization-aware training of the paper CNN."""

    @jax.custom_vjp
    def _fq(v):
        return dequantize_int8(quantize_int8(v, axis), v.dtype)

    def _fwd(v):
        return _fq(v), None

    def _bwd(_, g):
        return (g,)

    _fq.defvjp(_fwd, _bwd)
    return _fq(x)


def requant_epilogue(acc: jax.Array, scale: jax.Array,
                     b: jax.Array | None = None) -> jax.Array:
    """Dequantize an integer conv accumulator: ``acc·scale [+ b]``.

    ``scale``/``b`` must be pre-broadcast to ``acc``'s layout by the
    caller. Compilers may contract the multiply-add into one FMA — the
    installed XLA:CPU does inside a fusion, and a compiled Pallas TPU
    kernel may — so backends agree on this step to one rounding, not bit
    for bit; the integer accumulation before it is exact everywhere
    (DESIGN.md §8 states the parity that holds).
    """
    out = acc * scale
    return out if b is None else out + b


def conv_epilogue(out: jax.Array, scale: jax.Array | None,
                  b: jax.Array | None = None) -> jax.Array:
    """``requant_epilogue`` broadcast for NCHW conv outputs: ``scale``
    (M,)|None per output channel, then bias (M,)|None cast to the output
    dtype. This is THE post-reduction arithmetic — every consumer
    (``repro.ops`` conv2d / fused xla backend, the fused ref oracle, the
    channel-parallel schedules) must call it rather than re-spelling the
    broadcasts, or the fused-vs-unfused and sharded-vs-unsharded parity
    guarantees silently decay into per-call-site conventions."""
    if scale is not None:
        return requant_epilogue(
            out, scale[None, :, None, None],
            None if b is None else b[None, :, None, None].astype(out.dtype))
    if b is not None:
        out = out + b[None, :, None, None].astype(out.dtype)
    return out


def quantize_tree(params, axis: int | None = -1, min_size: int = 16):
    """Quantize every float array leaf of a pytree to int8 QTensors.

    Small leaves (biases, norms, scalars: fewer than ``min_size`` elements
    or ndim < 2) stay in float — matching deployment practice and the
    paper's keeping of accumulators at full width.
    """

    def _leaf(x):
        if (hasattr(x, "dtype") and jnp.issubdtype(x.dtype, jnp.floating)
                and x.ndim >= 2 and x.size >= min_size):
            return quantize_int8(x, axis)
        return x

    return jax.tree_util.tree_map(_leaf, params)
