"""Mixture-of-Experts with capacity-bounded top-k routing (GShard-style).

Dispatch is scatter-based (no (T, E, C) one-hot tensor): each (token, k)
assignment computes its position-within-expert by a cumulative count, drops
past capacity, and scatters features into an (E·C, D) buffer. Compiled
FLOPs are therefore ∝ E·C·D·F = active-expert compute (what the roofline's
MODEL_FLOPS/HLO_FLOPs ratio expects), not all-expert compute.

Experts are sharded over the ``model`` mesh axis (EP). Under pjit, the
scatter/gather across the token and expert shardings lowers to the dispatch
collectives; the shard_map all-to-all variant is a §Perf iteration.

Supports shared (always-on) experts (llama4-scout) and top-k renorm (dbrx).
An auxiliary load-balance loss (Switch-style) is returned for training.
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp

import numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.models.common import ACTIVATIONS, dense_init
from repro.sharding.logical import A, ShardingCtx, shard

__all__ = ["MoEConfig", "moe_init", "moe_axes", "moe_apply"]


@dataclass(frozen=True)
class MoEConfig:
    d_model: int
    d_ff: int                 # per-expert hidden size
    n_experts: int
    top_k: int
    capacity_factor: float = 1.25
    n_shared: int = 0         # always-on shared experts (llama4: 1)
    act: str = "silu"
    gated: bool = True
    router_aux_weight: float = 0.01


def moe_init(key: jax.Array, cfg: MoEConfig) -> dict:
    ks = jax.random.split(key, 6)
    e, d, f = cfg.n_experts, cfg.d_model, cfg.d_ff
    p = {
        "router": dense_init(ks[0], (d, e), d),
        "wi": dense_init(ks[1], (e, d, f), d),
        "wo": dense_init(ks[2], (e, f, d), f),
    }
    if cfg.gated:
        p["wg"] = dense_init(ks[3], (e, d, f), d)
    if cfg.n_shared:
        p["shared_wi"] = dense_init(ks[4], (d, cfg.n_shared * f), d)
        p["shared_wo"] = dense_init(ks[5], (cfg.n_shared * f, d),
                                    cfg.n_shared * f)
        if cfg.gated:
            p["shared_wg"] = dense_init(ks[4], (d, cfg.n_shared * f), d)
    return p


def moe_axes(cfg: MoEConfig) -> dict:
    ax = {
        "router": A("embed", None),
        "wi": A("expert", "embed", "mlp"),
        "wo": A("expert", "mlp", "embed"),
    }
    if cfg.gated:
        ax["wg"] = A("expert", "embed", "mlp")
    if cfg.n_shared:
        ax["shared_wi"] = A("embed", "mlp")
        ax["shared_wo"] = A("mlp", "embed")
        if cfg.gated:
            ax["shared_wg"] = A("embed", "mlp")
    return ax


def _capacity(tokens: int, cfg: MoEConfig) -> int:
    c = math.ceil(tokens * cfg.top_k * cfg.capacity_factor / cfg.n_experts)
    return max(8, -(-c // 8) * 8)  # multiple of 8 (sublane), never pow2-padded


def moe_apply(params: dict, x: jax.Array, cfg: MoEConfig,
              ctx: ShardingCtx | None) -> tuple[jax.Array, jax.Array]:
    """x: (B, S, D) -> (out (B, S, D), aux_loss scalar fp32).

    Dispatches to the shard_map expert-parallel path when a mesh with a
    'model' axis that divides n_experts is available (the production path),
    else runs the local reference implementation below.
    """
    if (ctx is not None and ctx.mesh is not None
            and "model" in ctx.mesh.axis_names):
        n_model = dict(zip(ctx.mesh.axis_names,
                           ctx.mesh.devices.shape))["model"]
        if cfg.n_experts % n_model == 0:
            return _moe_apply_ep(params, x, cfg, ctx, n_model)
    return _moe_apply_local(params, x, cfg, ctx)


def _moe_apply_ep(params: dict, x: jax.Array, cfg: MoEConfig,
                  ctx: ShardingCtx, n_model: int
                  ) -> tuple[jax.Array, jax.Array]:
    """Expert-parallel MoE via shard_map — zero all-to-all by construction.

    Activations between layers are replicated over the 'model' axis (the
    standard TP layout), so every model rank already holds every local
    token: rank j selects the tokens routed to ITS E/n experts, runs them
    (capacity per (expert, data-shard) group — GShard group semantics),
    and a single psum over 'model' combines — the same collective cost as
    one row-parallel TP matmul. The shared expert's F dim is sharded over
    'model' and its partial output rides the same psum for free.

    This exists because the pjit scatter/gather formulation of EP dispatch
    makes the SPMD partitioner materialize replicated (T·k, D) token
    buffers — ~50 GB/device at dbrx train shapes (measured in the dry-run;
    see EXPERIMENTS.md §Perf).
    """
    mesh = ctx.mesh
    e, k = cfg.n_experts, cfg.top_k
    e_l = e // n_model
    act = ACTIVATIONS[cfg.act]
    sizes = dict(mesh.shape)
    dp_axes: tuple = ()
    for cand in (("pod", "data"), ("data",), ("pod",)):
        if all(a in mesh.axis_names for a in cand):
            prod = 1
            for a in cand:
                prod *= sizes[a]
            if prod > 1 and x.shape[0] % prod == 0:
                dp_axes = cand
                break
    bspec = dp_axes if dp_axes else None

    def local(xl, router, wi, wg, wo, sh_wi, sh_wg, sh_wo):
        bl, s, d = xl.shape
        t = bl * s
        cap = _capacity(s * bl, cfg)
        j = jax.lax.axis_index("model")

        logits = jnp.einsum("bsd,de->bse", xl.astype(jnp.float32),
                            router.astype(jnp.float32))
        probs = jax.nn.softmax(logits, axis=-1)
        top_w, top_e = jax.lax.top_k(probs, k)            # (B,S,k)
        top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)
        assign = jax.nn.one_hot(top_e[..., 0], e, dtype=jnp.float32)
        aux = e * jnp.mean(assign.mean((0, 1)) * probs.mean((0, 1))) \
            * cfg.router_aux_weight
        if dp_axes:
            # per-data-shard estimator averaged across shards (mean of
            # per-shard products — GShard computes aux per group likewise;
            # differs from the exact global statistic at O(1/shards) level)
            aux = jax.lax.pmean(aux, dp_axes)

        flat_e = top_e.reshape(t * k)
        local_e = flat_e - j * e_l
        owned = (local_e >= 0) & (local_e < e_l)
        le = jnp.where(owned, local_e, e_l)               # drop row e_l
        onehot = jax.nn.one_hot(le, e_l + 1, dtype=jnp.int32)
        pos = (jnp.cumsum(onehot, axis=0) * onehot).sum(-1) - 1
        keep = owned & (pos < cap)
        pos_c = jnp.where(keep, pos, cap)
        le_c = jnp.where(keep, le, e_l)

        xt = xl.reshape(t, d)
        src = jnp.repeat(jnp.arange(t), k)
        buf = jnp.zeros((e_l + 1, cap + 1, d), xl.dtype)
        buf = buf.at[le_c, pos_c].set(xt[src])
        buf = buf[:e_l, :cap, :]

        hid = jnp.einsum("ecd,edf->ecf", buf, wi.astype(xl.dtype))
        if cfg.gated:
            hid = act(jnp.einsum("ecd,edf->ecf", buf,
                                 wg.astype(xl.dtype))) * hid
        else:
            hid = act(hid)
        y = jnp.einsum("ecf,efd->ecd", hid, wo.astype(xl.dtype))
        y = jnp.pad(y, ((0, 1), (0, 1), (0, 0)))
        gathered = y[le_c, pos_c]                         # (t·k, D)
        w = (top_w.reshape(t * k) * keep).astype(xl.dtype)
        out = (gathered * w[:, None]).reshape(t, k, d).sum(1)

        if cfg.n_shared:                                  # F sharded: partial
            sh = jnp.einsum("td,df->tf", xt, sh_wi.astype(xl.dtype))
            if cfg.gated:
                sh = act(jnp.einsum("td,df->tf", xt,
                                    sh_wg.astype(xl.dtype))) * sh
            else:
                sh = act(sh)
            out = out + jnp.einsum("tf,fd->td", sh, sh_wo.astype(xl.dtype))

        out = jax.lax.psum(out, "model")
        return out.reshape(bl, s, d), aux

    zero = jnp.zeros((), x.dtype)
    # cast to the compute dtype BEFORE the shard_map boundary so the FSDP
    # all-gather of expert weights moves bf16, not fp32 — halves both the
    # gather buffers (the dbrx train cell over-budget) and the traffic.
    cast = lambda t: t.astype(x.dtype)
    args = (x, params["router"], cast(params["wi"]),
            cast(params["wg"]) if cfg.gated else zero, cast(params["wo"]),
            cast(params["shared_wi"]) if cfg.n_shared else zero,
            cast(params["shared_wg"]) if (cfg.n_shared and cfg.gated)
            else zero,
            cast(params["shared_wo"]) if cfg.n_shared else zero)
    in_specs = (P(bspec, None, None), P(None, None),
                P("model", None, None), P("model", None, None) if cfg.gated
                else P(), P("model", None, None),
                P(None, "model") if cfg.n_shared else P(),
                P(None, "model") if (cfg.n_shared and cfg.gated) else P(),
                P("model", None) if cfg.n_shared else P())
    out, aux = shard_map(local, mesh=mesh, in_specs=in_specs,
                         out_specs=(P(bspec, None, None), P()),
                         check_vma=False)(*args)
    return shard(out, ctx, "batch", "act_seq", "act_embed"), aux


def _moe_apply_local(params: dict, x: jax.Array, cfg: MoEConfig,
                     ctx: ShardingCtx | None) -> tuple[jax.Array, jax.Array]:
    """Reference (single-host) path.

    GShard-style GROUP-WISE dispatch: each batch row is a dispatch group
    with its own capacity C = ceil(S·k·cf/E). All cumulative counts,
    scatters and gathers act within a row.
    """
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    cap = _capacity(s, cfg)
    act = ACTIVATIONS[cfg.act]

    # --- routing (fp32) ---
    logits = jnp.einsum("bsd,de->bse", x.astype(jnp.float32),
                        params["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_e = jax.lax.top_k(probs, k)                # (B, S, k)
    top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)

    # Switch aux loss: E * Σ_e (token fraction_e × mean prob_e)
    assign = jax.nn.one_hot(top_e[..., 0], e, dtype=jnp.float32)
    aux = e * jnp.mean(assign.mean((0, 1)) * probs.mean((0, 1))) \
        * cfg.router_aux_weight

    # --- group-local dispatch: position-within-(row, expert) ---
    flat_e = top_e.reshape(b, s * k)                      # (B, S·k)
    onehot = jax.nn.one_hot(flat_e, e, dtype=jnp.int32)   # (B, S·k, E)
    pos = (jnp.cumsum(onehot, axis=1) * onehot).sum(-1) - 1
    keep = pos < cap
    pos_c = jnp.where(keep, pos, cap)                     # drop slot: col C
    src = jnp.repeat(jnp.arange(s), k)                    # within-row token

    buf = jnp.zeros((b, e, cap + 1, d), x.dtype)
    buf = shard(buf, ctx, "batch", "act_expert", None, None)
    brow = jnp.broadcast_to(jnp.arange(b)[:, None], (b, s * k))
    buf = buf.at[brow, flat_e, pos_c].set(x[:, src, :].reshape(b, s * k, d))
    buf = buf[:, :, :cap, :]
    buf = shard(buf, ctx, "batch", "act_expert", None, None)

    # --- expert FFN (B, E, C, D), experts sharded on 'model' (EP) ---
    hid = jnp.einsum("becd,edf->becf", buf, params["wi"].astype(x.dtype))
    if cfg.gated:
        gate = jnp.einsum("becd,edf->becf", buf,
                          params["wg"].astype(x.dtype))
        hid = act(gate) * hid
    else:
        hid = act(hid)
    hid = shard(hid, ctx, "batch", "act_expert", None, None)
    y = jnp.einsum("becf,efd->becd", hid, params["wo"].astype(x.dtype))

    # --- combine: row-local gather + routing weights ---
    y = jnp.pad(y, ((0, 0), (0, 0), (0, 1), (0, 0)))      # drop slot row
    gathered = y[brow, flat_e, pos_c]                     # (B, S·k, D)
    w = (top_w.reshape(b, s * k) * keep).astype(x.dtype)
    out = (gathered * w[..., None]).reshape(b, s, k, d).sum(axis=2)

    # --- shared experts (always-on) ---
    if cfg.n_shared:
        sh = jnp.einsum("bsd,df->bsf", x, params["shared_wi"].astype(x.dtype))
        if cfg.gated:
            sg = jnp.einsum("bsd,df->bsf", x,
                            params["shared_wg"].astype(x.dtype))
            sh = act(sg) * sh
        else:
            sh = act(sh)
        sh = shard(sh, ctx, "batch", "act_seq", "act_mlp")
        out = out + jnp.einsum("bsf,fd->bsd", sh,
                               params["shared_wo"].astype(x.dtype))

    return shard(out, ctx, "batch", "act_seq", "act_embed"), aux
