"""Pallas kernel for the paper's odd-even addition tree (§III.B.1, Fig. 5).

Reduces (R, η) -> (R, 1) for arbitrary η with a statically-unrolled
⌈log2 η⌉-level tree — the level widths go η, ⌈η/2⌉, … 1, exactly the
paper's construction (odd leftover forwarded, never zero-padded to a
power of two). On the VPU each level is one vectorized add over the row
block; the depth (and therefore the dependency chain) matches the classic
tree, the *work* is η−1 adds instead of 2^⌈log2 η⌉−1.

Pairing: a level of width n with s = ⌈n/2⌉ adds element k to element
k + s for k < ⌊n/2⌋ and forwards element s−1 when n is odd — the pairs of
the paper's tree taken across the two halves instead of side by side, so
a level is one lane rotation by s, one add and one select over the whole
block (the TPU has no stride-2 lane access). The wrapper pads η to whole
lane tiles; pad lanes are never selected into a level's live prefix.

Rows are tiled over the grid; η stays in-block (the tree is a cross-lane
reduction — for the η values this system meets, η = N·Kh·Kw ≤ a few
thousand, one block of η lanes fits VMEM trivially).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _addtree_kernel(x_ref, o_ref, *, eta: int):
    x = x_ref[...]                      # (rb, lanes), live prefix of eta
    lanes = x.shape[1]
    col = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    n = eta
    while n > 1:                        # statically unrolled levels
        s = (n + 1) // 2
        partner = pltpu.roll(x, lanes - s, 1)   # partner[k] = x[k + s]
        x = jnp.where(col < n // 2, x + partner, x)
        n = s
    o_ref[...] = x[:, :1].astype(o_ref.dtype)


def tree_reduce_sum_pallas(x: jax.Array, *, rb: int,
                           interpret: bool) -> jax.Array:
    """(R, η) -> (R, 1). rb divides R."""
    r, eta = x.shape
    assert r % rb == 0, (r, rb)
    lanes = -(-eta // 128) * 128
    x = jnp.pad(x, ((0, 0), (0, lanes - eta)))
    return pl.pallas_call(
        functools.partial(_addtree_kernel, eta=eta),
        grid=(r // rb,),
        in_specs=[pl.BlockSpec((rb, lanes), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((rb, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((r, 1), x.dtype),
        interpret=interpret,
    )(x)
