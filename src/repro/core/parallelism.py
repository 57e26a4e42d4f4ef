"""Channel-parallel convolution schedules — paper §III.A (C1), Eq. (6)/(7).

The paper derives two ways to parallelize the conv reduction across
"compute units"; on a TPU mesh the compute units are chips and the two
schedules become two sharding+collective patterns over the ``model`` axis:

* OUTPUT-channel parallel (paper Eq. 6 / method 1): the M output channels
  are split across devices. Weights are sharded on M, every device sees the
  full input window stream, no collective is needed in the conv itself.
  This is classic tensor parallelism of the "column-parallel" kind.

* INPUT-channel parallel (paper Eq. 7–8 / method 2, Fig. 3): the N input
  channels are split; each device computes the partial sums
  ``Ô_n = [a_1n … a_Mn]`` for its channel slice, and the per-device partials
  are combined with one all-reduce — the paper's M accumulators realized in
  space instead of time (N sequential accumulations). "Row-parallel" tensor
  parallelism; the bias is added once after the reduce.

* BOTH (DESIGN.md §15): the paper's §III.A architecture composes the two
  simultaneously — the ``model`` axis factors into an ``icp × ocp``
  sub-grid (``stage_mesh``), each device owning an (M/ocp, N/icp) weight
  block. The reduce then runs over the *icp sub-groups only*, so the
  collective shrinks as ocp grows and neither channel dimension has to
  cover the whole mesh by itself — which is exactly what breaks the
  one-axis mesh-4 falloff.

All modes compose with batch sharding over ``data`` orthogonally.
``shard_map`` keeps the collective explicit (the reduce *is* Fig. 3),
rather than relying on pjit inference.

The Eq. 7 reduction itself is ``ring_all_reduce``: a double-buffered
``ppermute`` ring instead of a blocking ``psum``. Each step permutes the
*received* buffer while the accumulate hangs off a separate dependency
chain, so the next hop's communication can overlap the current add (and,
inside a larger program, the next stage's compute) — a blocking psum
serializes all of it. The ring reassociates the partial sum exactly like
psum does, so the bitwise-parity methodology of tests/test_shard_plan
(lattice data, exact int8 codes) applies unchanged.

Two op families get schedules here:

* ``conv2d_channel_parallel`` — the bare conv (+ optional int8 requant
  ``scale``, applied with the bias after the reduction is complete:
  post-reduce for ICP/BOTH, per-shard for OCP);
* ``fused_conv_block_channel_parallel`` — the deep-pipelined
  conv+requant+bias+relu+pool stage of the graph compiler (DESIGN.md §9).
  Under OCP the whole fused stage (one Pallas kernel on TPU) runs
  per-shard. Under ICP/BOTH only the conv produces *partials*; the Eq. 7
  ring reduce completes the accumulation and the requant/bias/relu/pool
  epilogue runs on the combined result — scale and bias after a partial
  sum would be wrong, which is why the reduce sits between the conv and
  the epilogue.

This module is the single sanctioned home of ``shard_map``-over-conv
(enforced by the ``shard-map-conv`` lint rule, DESIGN.md §14); the graph
compiler routes sharded plan stages here, never hand-rolls its own
collective.
"""
from __future__ import annotations

import enum
import functools

import jax
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.quantize import conv_epilogue
from repro.core.window import maxpool2

__all__ = ["ChannelParallelism", "conv2d_channel_parallel",
           "fused_conv_block_channel_parallel", "ring_all_reduce",
           "stage_mesh"]


class ChannelParallelism(enum.Enum):
    NONE = "none"
    OUTPUT = "output"   # paper Eq. (6): shard M, no collective
    INPUT = "input"     # paper Eq. (7): shard N, one ring reduce
    BOTH = "both"       # §III.A composed: icp × ocp sub-grid


def _axis_size(mesh: Mesh, axis: str) -> int:
    return mesh.shape[axis]


def ring_all_reduce(part: jax.Array, axis: str, size: int) -> jax.Array:
    """Eq. 7 all-reduce as a double-buffered ``ppermute`` ring.

    Each of the ``size - 1`` steps rotates the *communication* buffer one
    hop around the ring while the accumulator adds the previously received
    shard — the permute chain (`buf`) and the accumulate chain (`acc`) are
    independent dependency chains, so XLA can issue hop k+1's transfer
    while hop k's add (and surrounding stage compute) executes. A blocking
    ``psum`` fuses both into one synchronizing collective.

    Every device adds the same ``size`` shards (its own plus each
    neighbor's, in ring order), so the result equals ``psum`` up to
    floating-point reassociation — and exactly, on the lattice/int8 data
    the parity tests use, or at ``size == 2`` where a+b has one ordering.
    """
    if size <= 1:
        return part
    perm = [(j, (j + 1) % size) for j in range(size)]
    acc = part
    buf = part
    for _ in range(size - 1):
        buf = jax.lax.ppermute(buf, axis, perm)
        acc = acc + buf
    return acc


@functools.lru_cache(maxsize=None)
def stage_mesh(mesh: Mesh, icp: int, ocp: int,
               model_axis: str = "model") -> Mesh:
    """Factor ``mesh``'s model axis into an (ocp, icp) sub-grid.

    Returns a mesh over the *same* devices whose ``model_axis`` is
    replaced by two axes ``("ocp", "icp")`` with icp fastest-varying, so
    the icp ring reduce runs between model-axis neighbors. Other axes
    (``data``) are preserved in place. Mesh is hashable, so the rebuild
    is cached per (mesh, split).
    """
    names = list(mesh.axis_names)
    pos = names.index(model_axis)
    devs = np.moveaxis(mesh.devices, pos, -1)
    lead = devs.shape[:-1]
    devs = devs.reshape(*lead, ocp, icp)
    new_names = [n for n in names if n != model_axis] + ["ocp", "icp"]
    # moveaxis put the non-model axes first in their original order
    return Mesh(devs, tuple(new_names))


def _validate(x, w, mesh: Mesh, mode: ChannelParallelism,
              model_axis: str, data_axis: str | None,
              icp: int = 0, ocp: int = 0) -> str | None:
    """Static shape/mesh checks with actionable errors (instead of the
    shard_map partition failure the raw specs would produce). Returns the
    resolved batch spec (``data_axis`` or None)."""
    if x.ndim != 4 or w.ndim != 4 or x.shape[1] != w.shape[1]:
        raise ValueError(
            f"channel-parallel conv needs x (B,N,H,W) and w (M,N,Kh,Kw) "
            f"with matching N; got x {x.shape}, w {w.shape}")
    if model_axis not in mesh.axis_names:
        raise ValueError(f"mesh {dict(mesh.shape)} has no "
                         f"{model_axis!r} axis")
    msize = _axis_size(mesh, model_axis)
    m, n = w.shape[0], w.shape[1]
    if mode == ChannelParallelism.OUTPUT and m % msize:
        raise ValueError(
            f"OUTPUT-channel parallelism (paper Eq. 6) shards the M={m} "
            f"output channels over {model_axis}={msize} devices, but "
            f"{m} % {msize} != 0; pick a divisible channel count, a "
            f"smaller mesh, or INPUT mode")
    if mode == ChannelParallelism.INPUT and n % msize:
        raise ValueError(
            f"INPUT-channel parallelism (paper Eq. 7) shards the N={n} "
            f"input channels over {model_axis}={msize} devices, but "
            f"{n} % {msize} != 0; pick a divisible channel count, a "
            f"smaller mesh, or OUTPUT mode")
    if mode == ChannelParallelism.BOTH:
        ki, ko = max(icp, 1), max(ocp, 1)
        if ki * ko != msize:
            raise ValueError(
                f"BOTH-channel parallelism factors the {model_axis!r} "
                f"axis ({msize} devices) into icp×ocp, but "
                f"{ki}×{ko} = {ki * ko} != {msize}")
        if n % ki:
            raise ValueError(
                f"BOTH-channel parallelism (paper Eq. 7 side) shards the "
                f"N={n} input channels over icp={ki} groups, but "
                f"{n} % {ki} != 0; pick divisible factors")
        if m % ko:
            raise ValueError(
                f"BOTH-channel parallelism (paper Eq. 6 side) shards the "
                f"M={m} output channels over ocp={ko} groups, but "
                f"{m} % {ko} != 0; pick divisible factors")
    batch_spec = data_axis if data_axis in mesh.axis_names else None
    if batch_spec is not None:
        dsize = _axis_size(mesh, batch_spec)
        if x.shape[0] % dsize:
            raise ValueError(
                f"batch {x.shape[0]} does not divide the {batch_spec!r} "
                f"axis ({dsize} devices); pad the batch or pass "
                f"data_axis=None to replicate it")
    return batch_spec


def _conv(x, w, b, stride, policy):
    """Per-shard conv through the repro.ops registry (lazy import: core is
    imported *by* the ops package). The active ExecPolicy picks the local
    backend — auto lands on the XLA im2col form, the schedule's MXU shape."""
    from repro.ops.registry import dispatch
    return dispatch("conv2d", x, w, b, stride=stride, policy=policy)


def _operands(x, w, b, scale, x_spec, w_spec, v_spec):
    """shard_map plumbing for the optional bias/scale operands (None
    cannot cross a shard_map boundary): the (in_specs, args) to launch
    with — ``v_spec`` covers both vector operands — and an ``unpack``
    turning the local body's ``*rest`` back into (bias, scale)."""
    in_specs = [x_spec, w_spec]
    args = [x, w]
    have_b, have_s = b is not None, scale is not None
    for operand in (b, scale):
        if operand is not None:
            in_specs.append(v_spec)
            args.append(operand)

    def unpack(rest):
        return (rest[0] if have_b else None,
                rest[have_b] if have_s else None)

    return tuple(in_specs), args, unpack


def conv2d_channel_parallel(
    x: jax.Array,
    w: jax.Array,
    b: jax.Array | None,
    *,
    mesh: Mesh,
    mode: ChannelParallelism,
    stride: tuple[int, int] = (1, 1),
    scale: jax.Array | None = None,
    model_axis: str = "model",
    data_axis: str | None = "data",
    icp: int = 0,
    ocp: int = 0,
    policy=None,
) -> jax.Array:
    """Distributed conv2d under the selected channel-parallel schedule.

    x: (B, N, H, W), w: (M, N, Kh, Kw), b: (M,)|None -> (B, M, Ho, Wo).
    Batch is sharded over ``data_axis`` when given; channels per ``mode``.
    ``scale`` (M,) is the int8 requant epilogue factor (codes-in,
    dequantized-out — see repro.ops.split_requant); under INPUT/BOTH mode
    it is applied after the ring reduce, with the bias, exactly once.
    ``icp``/``ocp`` factor the model axis for BOTH mode (ignored
    otherwise).
    """
    stride = tuple(stride)
    if mode == ChannelParallelism.NONE:
        if scale is not None:
            return conv_epilogue(_conv(x, w, None, stride, policy),
                                 scale, b)
        return _conv(x, w, b, stride, policy)

    batch_spec = _validate(x, w, mesh, mode, model_axis, data_axis,
                           icp, ocp)

    if mode == ChannelParallelism.OUTPUT:
        # shard M on model; replicate x over model; concat along M implicit.
        # bias/scale shard with their output channels — per-shard epilogue.
        in_specs, args, unpack = _operands(
            x, w, b, scale, P(batch_spec, None, None, None),
            P(model_axis, None, None, None), P(model_axis))

        def local(xl, wl, *rest):
            bl, sl = unpack(rest)
            if sl is not None:
                return conv_epilogue(_conv(xl, wl, None, stride, policy),
                                     sl, bl)
            return _conv(xl, wl, bl, stride, policy)

        return shard_map(
            local, mesh=mesh, in_specs=in_specs,
            out_specs=P(batch_spec, model_axis, None, None),
            check_vma=False)(*args)

    if mode == ChannelParallelism.INPUT:
        # shard N on model; each device computes partial O over its channel
        # slice; one ring reduce combines (paper Fig. 3); requant scale and
        # bias join once, post-reduce, when the accumulation is complete.
        msize = _axis_size(mesh, model_axis)
        in_specs, args, unpack = _operands(
            x, w, b, scale, P(batch_spec, model_axis, None, None),
            P(None, model_axis, None, None), P(None))

        def local(xl, wl, *rest):
            bl, sl = unpack(rest)
            part = _conv(xl, wl, None, stride, policy)
            return conv_epilogue(ring_all_reduce(part, model_axis, msize),
                                 sl, bl)

        return shard_map(
            local, mesh=mesh, in_specs=in_specs,
            out_specs=P(batch_spec, None, None, None),
            check_vma=False)(*args)

    if mode == ChannelParallelism.BOTH:
        # §III.A composed: the model axis factors into an (ocp, icp)
        # sub-grid. x shards N over "icp" groups, w blocks over both,
        # bias/scale shard with their output channels over "ocp". The
        # ring reduce runs over the icp sub-axis only — ocp groups never
        # communicate — and the output concatenates M over "ocp".
        ki, ko = max(icp, 1), max(ocp, 1)
        smesh = stage_mesh(mesh, ki, ko, model_axis)
        in_specs, args, unpack = _operands(
            x, w, b, scale, P(batch_spec, "icp", None, None),
            P("ocp", "icp", None, None), P("ocp"))

        def local(xl, wl, *rest):
            bl, sl = unpack(rest)
            part = _conv(xl, wl, None, stride, policy)
            return conv_epilogue(ring_all_reduce(part, "icp", ki), sl, bl)

        return shard_map(
            local, mesh=smesh, in_specs=in_specs,
            out_specs=P(batch_spec, "ocp", None, None),
            check_vma=False)(*args)

    raise ValueError(f"unknown mode {mode}")


def fused_conv_block_channel_parallel(
    x: jax.Array,
    w: jax.Array,
    b: jax.Array | None,
    *,
    mesh: Mesh,
    mode: ChannelParallelism,
    stride: tuple[int, int] = (1, 1),
    odd: str = "raise",
    scale: jax.Array | None = None,
    model_axis: str = "model",
    data_axis: str | None = "data",
    icp: int = 0,
    ocp: int = 0,
    policy=None,
) -> jax.Array:
    """The fused conv+requant+bias+relu+pool stage, channel-parallel.

    x: (B, N, H, W), w: (M, N, Kh, Kw) -> (B, M, Ho/2, Wo/2).

    OUTPUT mode runs the whole fused stage per M-shard (each device owns
    its output channels end to end — on TPU that is the fused_cwp kernel
    per shard). INPUT/BOTH modes cannot: relu/pool do not commute with
    the sum over input channels, so the per-device conv produces
    *partials*, the Eq. 7 ring reduce completes the accumulation, and the
    epilogue (requant scale → bias → relu → 2×2/2 pool) runs on the
    combined result — replicated over the reduce axis, which costs
    nothing measurable (the epilogue is elementwise on the
    already-reduced tile). Under BOTH the epilogue still runs per
    M-shard: each ocp group owns its output channels end to end.
    """
    from repro.ops.registry import dispatch
    stride = tuple(stride)
    if mode == ChannelParallelism.NONE:
        return dispatch("fused_conv_block", x, w, b, stride=stride, odd=odd,
                        scale=scale, policy=policy)

    batch_spec = _validate(x, w, mesh, mode, model_axis, data_axis,
                           icp, ocp)

    if mode == ChannelParallelism.OUTPUT:
        in_specs, args, unpack = _operands(
            x, w, b, scale, P(batch_spec, None, None, None),
            P(model_axis, None, None, None), P(model_axis))

        def local(xl, wl, *rest):
            bl, sl = unpack(rest)
            return dispatch("fused_conv_block", xl, wl, bl, stride=stride,
                            odd=odd, scale=sl, policy=policy)

        return shard_map(
            local, mesh=mesh, in_specs=in_specs,
            out_specs=P(batch_spec, model_axis, None, None),
            check_vma=False)(*args)

    if mode == ChannelParallelism.INPUT:
        msize = _axis_size(mesh, model_axis)
        in_specs, args, unpack = _operands(
            x, w, b, scale, P(batch_spec, model_axis, None, None),
            P(None, model_axis, None, None), P(None))

        def local(xl, wl, *rest):
            bl, sl = unpack(rest)
            part = _conv(xl, wl, None, stride, policy)
            # Eq. 7: ONE all-reduce, overlapped (ring)
            full = ring_all_reduce(part, model_axis, msize)
            return maxpool2(jax.nn.relu(conv_epilogue(full, sl, bl)),
                            odd=odd)

        return shard_map(
            local, mesh=mesh, in_specs=in_specs,
            out_specs=P(batch_spec, None, None, None),
            check_vma=False)(*args)

    if mode == ChannelParallelism.BOTH:
        ki, ko = max(icp, 1), max(ocp, 1)
        smesh = stage_mesh(mesh, ki, ko, model_axis)
        in_specs, args, unpack = _operands(
            x, w, b, scale, P(batch_spec, "icp", None, None),
            P("ocp", "icp", None, None), P("ocp"))

        def local(xl, wl, *rest):
            bl, sl = unpack(rest)
            part = _conv(xl, wl, None, stride, policy)
            full = ring_all_reduce(part, "icp", ki)
            return maxpool2(jax.nn.relu(conv_epilogue(full, sl, bl)),
                            odd=odd)

        return shard_map(
            local, mesh=smesh, in_specs=in_specs,
            out_specs=P(batch_spec, "ocp", None, None),
            check_vma=False)(*args)

    raise ValueError(f"unknown mode {mode}")
