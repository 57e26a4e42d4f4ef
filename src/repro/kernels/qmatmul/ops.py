"""jit'd wrapper: the int8 GEMM kernel as the ``qmatmul`` pallas backend.

``qmatmul`` consumes pre-quantized operands (int8 codes + scales, the
QTensor layout from core.quantize). ``qdense`` is the kernel-flavored
convenience path (fp activations in, int8 weights, fp out); the
policy-routed equivalent lives in ``repro.ops.qdense``.

Block sizes come from the shared tiling layer (legal divisors of the
MXU-native 128 caps — the int8 GEMM does not pad); interpret mode
auto-detects via ExecPolicy (interpret only off-TPU).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.quantize import QTensor, quantize_int8
from repro.kernels.qmatmul.kernel import qmatmul_pallas
from repro.ops.policy import ExecPolicy, current_policy
from repro.ops.tiling import (choose_qmatmul_blocks, legal_qmatmul_tiles,
                              tile_params)


@functools.partial(jax.jit,
                   static_argnames=("bm", "bn", "bk", "out_dtype",
                                    "interpret"))
def _qmatmul_jit(x_codes, w_codes, xs, ws, *, bm, bn, bk, out_dtype,
                 interpret):
    return qmatmul_pallas(x_codes, w_codes, xs, ws, bm=bm, bn=bn, bk=bk,
                          out_dtype=out_dtype, interpret=interpret)


def qmatmul(x_codes: jax.Array, w_codes: jax.Array,
            x_scale: jax.Array, w_scale: jax.Array,
            out_dtype=jnp.float32, interpret: bool | None = None, *,
            policy: ExecPolicy | None = None) -> jax.Array:
    """(M,K) int8 · (K,N) int8 -> (M,N). Scales: x (M,1)|scalar, w (1,N)|scalar."""
    pol = policy if policy is not None else current_policy()
    if interpret is None:
        interpret = pol.resolve_interpret()
    m, k = x_codes.shape
    _, n = w_codes.shape
    xs = jnp.broadcast_to(jnp.asarray(x_scale, jnp.float32), (m, 1)) \
        if jnp.ndim(x_scale) < 2 else x_scale.astype(jnp.float32)
    ws = jnp.broadcast_to(jnp.asarray(w_scale, jnp.float32), (1, n)) \
        if jnp.ndim(w_scale) < 2 else w_scale.astype(jnp.float32)
    tiles = tile_params("qmatmul", (m, k, n), x_codes.dtype,
                        choose_qmatmul_blocks(m, n, k), pol.tile_overrides)
    # grid blocks must divide their dims exactly (the kernel never pads)
    # and obey the block rule
    return _qmatmul_jit(x_codes, w_codes, xs, ws,
                        **legal_qmatmul_tiles(m, n, k, tiles),
                        out_dtype=out_dtype, interpret=interpret)


def qdense(x: jax.Array, wq: QTensor, out_dtype=None,
           interpret: bool | None = None, *,
           policy: ExecPolicy | None = None) -> jax.Array:
    """fp (…, K) · int8 (K, N) -> fp (…, N), pinned to the Pallas kernel.

    Thin alias of ``repro.ops.qdense`` (the one quantized-dense
    implementation) with ``backend="pallas"`` forced — this module is the
    kernel-flavored entry point; use ``repro.ops.qdense`` for
    policy-routed dispatch."""
    from repro.ops.impls import qdense as _qdense
    pol = policy if policy is not None else current_policy()
    pol = pol.with_options(
        backend="pallas",
        interpret=pol.interpret if interpret is None else interpret)
    return _qdense(x, wq, out_dtype, policy=pol)
