"""Gradient compression for the cross-pod (DCN) reduce.

Within a pod, gradient all-reduce rides ICI and stays fp32. Across pods the
DCN link is the scarce resource; two standard compressors are provided:

  * ``bf16``  — cast-before-reduce (2× traffic cut, no state);
  * ``int8``  — per-tensor symmetric int8 with ERROR FEEDBACK: the
    quantization residual is carried into the next step, making the
    compression unbiased over time (Seide et al. / 1-bit SGD lineage).

``cross_pod_grad_reduce`` is the shard_map building block: gradients enter
pod-local (already reduced over 'data'), are compressed, psum'd over
'pod', decompressed and averaged. Error-feedback state is carried per
parameter. Used by make_train_step via ``compression=`` when a 'pod' axis
exists; validated in tests/test_compression.py (convergence + unbiasedness).
"""
from __future__ import annotations

from functools import partial
from typing import Literal

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

__all__ = ["compress_decompress", "error_feedback_compress",
           "cross_pod_grad_reduce", "init_ef_state"]


def _int8_quant(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    amax = jnp.max(jnp.abs(x))
    scale = jnp.maximum(amax, 1e-12) / 127.0
    return jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8), scale


def compress_decompress(x: jax.Array, mode: Literal["bf16", "int8"]
                        ) -> jax.Array:
    """Round-trip through the compressed representation (what the wire
    carries)."""
    if mode == "bf16":
        return x.astype(jnp.bfloat16).astype(x.dtype)
    codes, scale = _int8_quant(x.astype(jnp.float32))
    return (codes.astype(jnp.float32) * scale).astype(x.dtype)


def init_ef_state(params) -> dict:
    return jax.tree_util.tree_map(
        lambda p: jnp.zeros(p.shape, jnp.float32), params)


def error_feedback_compress(grad: jax.Array, ef: jax.Array,
                            mode: Literal["bf16", "int8"]
                            ) -> tuple[jax.Array, jax.Array]:
    """(compressed(grad + ef), new_ef). The residual re-enters next step."""
    g = grad.astype(jnp.float32) + ef
    sent = compress_decompress(g, mode)
    return sent, g - sent


def cross_pod_grad_reduce(grads, ef_state, *, mesh: Mesh,
                          mode: Literal["none", "bf16", "int8"] = "bf16"):
    """Compress -> psum over 'pod' -> average. grads are pod-local means.

    Returns (reduced_grads, new_ef_state). With mode="none" this is a plain
    pod all-reduce (the baseline).
    """
    if "pod" not in mesh.axis_names:
        return grads, ef_state
    n_pods = dict(mesh.shape)["pod"]
    if n_pods == 1 or mode == "none":
        return grads, ef_state

    def one(g, ef):
        def local(gl, efl):
            if mode == "bf16":
                sent = gl.astype(jnp.bfloat16)
                red = jax.lax.psum(sent, "pod").astype(jnp.float32) / n_pods
                return red, efl
            sent, new_ef = error_feedback_compress(gl, efl, mode)
            red = jax.lax.psum(sent, "pod") / n_pods
            return red.astype(gl.dtype), new_ef

        # gradients/ef are already sharded like the params; shard_map over
        # every mesh axis with their existing layout is handled by pjit at
        # the boundary — here we only need the pod collective, so run
        # replicated-in/replicated-out over the pod axis alone.
        return shard_map(
            local, mesh=mesh,
            in_specs=(P(), P()), out_specs=(P(), P()),
            check_vma=False)(g, ef)

    flat_g, tree = jax.tree_util.tree_flatten(grads)
    flat_e = jax.tree_util.tree_leaves(ef_state)
    out_g, out_e = [], []
    for g, e in zip(flat_g, flat_e):
        rg, re = one(g, e)
        out_g.append(rg)
        out_e.append(re)
    return (jax.tree_util.tree_unflatten(tree, out_g),
            jax.tree_util.tree_unflatten(tree, out_e))
