"""Blocked int8×int8→int32 GEMM with per-channel scale epilogue.

The TPU-idiomatic realization of the paper's 16-bit fixed-point datapath
(DESIGN.md §2, row C4): the MXU has a native int8 path at 2× bf16
throughput (394 TOPS on v5e); accumulation is int32 (lossless, like the
paper's full-width accumulators), and the Qm.n rescale becomes a fp32
per-row × per-column scale in the epilogue.

Grid (⌈M/bm⌉, ⌈N/bn⌉, ⌈K/bk⌉), K innermost so each (m, n) output tile's
int32 accumulator lives in a VMEM scratch across the K steps; the epilogue
(scale multiply + cast) fires on the last K step only. Block shapes are
multiples of the 32×128 int8 tile where the problem allows — never padded
to powers of two (C2 rule).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _qmatmul_kernel(x_ref, w_ref, xs_ref, ws_ref, o_ref, acc_ref, *,
                    k_steps: int):
    """x: (bm, bk) i8; w: (bk, bn) i8; xs: (bm, 1) f32; ws: (1, bn) f32;
    o: (bm, bn) f32; acc scratch: (bm, bn) i32."""
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # integer products are exact at any precision; an explicit DEFAULT
    # keeps an ambient fp32 matmul-precision setting (which Mosaic rejects
    # for int8 operands) from reaching the kernel
    acc_ref[...] += jax.lax.dot_general(
        x_ref[...], w_ref[...],
        dimension_numbers=(((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.DEFAULT,
        preferred_element_type=jnp.int32,
    )

    @pl.when(ki == k_steps - 1)
    def _epilogue():
        o_ref[...] = (acc_ref[...].astype(jnp.float32)
                      * xs_ref[...] * ws_ref[...]).astype(o_ref.dtype)


def qmatmul_pallas(x_codes: jax.Array, w_codes: jax.Array,
                   x_scale: jax.Array, w_scale: jax.Array, *,
                   bm: int, bn: int, bk: int, out_dtype=jnp.float32,
                   interpret: bool) -> jax.Array:
    m, k = x_codes.shape
    k2, n = w_codes.shape
    assert k == k2 and m % bm == 0 and n % bn == 0 and k % bk == 0
    k_steps = k // bk
    grid = (m // bm, n // bn, k_steps)

    return pl.pallas_call(
        functools.partial(_qmatmul_kernel, k_steps=k_steps),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda mi, ni, ki: (mi, ki)),
            pl.BlockSpec((bk, bn), lambda mi, ni, ki: (ki, ni)),
            pl.BlockSpec((bm, 1), lambda mi, ni, ki: (mi, 0)),
            pl.BlockSpec((1, bn), lambda mi, ni, ki: (0, ni)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda mi, ni, ki: (mi, ni)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        interpret=interpret,
    )(x_codes, w_codes, x_scale, w_scale)
