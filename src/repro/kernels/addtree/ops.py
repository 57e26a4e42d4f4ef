"""jit'd wrapper for the odd-even addition-tree reduction kernel.

Registered as the ``pallas`` backend of the ``tree_reduce_sum`` op family
(repro.ops). The row block comes from the shared tiling layer; a ragged or
prime row count R is padded up to a multiple of rb with zero rows and
sliced back — the same pad-and-slice treatment conv_window applies to
ragged Ho, instead of the old divisor search that degenerated to rb=1
(one grid step per row) whenever R was prime.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.addtree.kernel import tree_reduce_sum_pallas
from repro.ops.policy import ExecPolicy, current_policy
from repro.ops.tiling import SUBLANE, choose_tree_rows, tile_params


@functools.partial(jax.jit, static_argnames=("rb", "interpret"))
def _tree_reduce_sum_jit(x: jax.Array, *, rb: int,
                         interpret: bool) -> jax.Array:
    r = x.shape[0]
    pad = (-r) % rb
    if pad:                      # zero rows reduce to zero; sliced off below
        x = jnp.pad(x, ((0, pad), (0, 0)))
    out = tree_reduce_sum_pallas(x, rb=rb, interpret=interpret)
    return out[:r, 0]


def tree_reduce_sum(x: jax.Array, interpret: bool | None = None, *,
                    rb: int | None = None,
                    policy: ExecPolicy | None = None) -> jax.Array:
    """(R, η) -> (R,): odd-even pairwise tree sum along the last axis.

    ``interpret=None`` auto-detects (interpret only off-TPU); ``rb``
    overrides the resolved row block.
    """
    pol = policy if policy is not None else current_policy()
    if interpret is None:
        interpret = pol.resolve_interpret()
    r, eta = x.shape
    tiles = tile_params("tree_reduce_sum", (r, eta), x.dtype,
                        choose_tree_rows(r), pol.tile_overrides)
    if rb is not None:
        tiles["rb"] = rb
    # a row block short of R sits on sublanes: whole sublane tiles only
    rb = min(r, -(-max(1, tiles["rb"]) // SUBLANE) * SUBLANE)
    return _tree_reduce_sum_jit(x, rb=rb, interpret=interpret)
