"""ExecutionPlan: the static, deep-pipelined execution of a compiled graph.

``compile_model(model, ...)`` (re-exported as ``PaperCNN.compile``) runs
trace → passes → plan. The resulting ``ExecutionPlan`` is the software
analogue of the paper's synthesized accelerator:

  * **static** — node list, shapes, fusion decisions and quantization
    points are fixed at compile time; calling it is pure data movement
    through a known pipeline (and therefore cleanly ``jax.jit``-able);
  * **registry-dispatched** — every compute stage goes through the
    ``repro.ops`` registry under the ambient ``ExecPolicy`` (backend
    preference, interpret mode, tiling), so one plan runs on every
    registered backend of the platform;
  * **quant-baked** — the quantization mode is part of the artifact (like
    a bitstream's number format). The lowered graph carries explicit
    QuantizeNodes and all conv stages execute with ``quant="none"``;
    asking the plan to run under a *different* ambient quant raises
    instead of silently recompiling.

``plan.bind(params)`` folds the constant (weight) quantize nodes and the
batch-norm folds (``BatchNormFoldNode``) once and returns a ``BoundPlan``
— per-batch calls then skip weight requantization and batch norm
entirely, the constant folding of DESIGN.md §8.

Compiling with ``autotune=True`` makes the plan **measured** (DESIGN.md
§10): ``bind`` runs the candidate-grid search of ``repro.ops.autotune``
once per conv/fused/dense stage (cache hits — including entries loaded
from a persisted tuning-cache file — skip the measurement) and bakes the
winning tile parameters into the BoundPlan as per-stage ``ExecPolicy``
tiling overrides, so the serve hot path never re-tunes and never even
consults the cache.

Compiling with ``mesh=`` makes the plan **sharded** (DESIGN.md §9/§15):
the placement pass stamps a ``ShardingSpec`` on every conv stage (the
paper-§III.A icp × ocp split per layer, from an arithmetic-intensity
cost model), execution routes those stages through the
explicit-collective schedules in ``core.parallelism``, batches scatter
over the ``data`` axis on entry, and ``bind`` additionally
``device_put``s every stage's weight operands under their placement —
OCP weights land M-sharded, ICP weights N-sharded, composed splits
blocked over both — so the per-batch call starts from resident shards,
the way a bitstream's weight ROMs are flashed per compute unit before
traffic arrives.
"""
from __future__ import annotations

from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.artifact.warmup import phase
from repro.core.quantize import QFormat, QTensor, quantize_int8
from repro.core.window import maxpool2, pad_spatial
from repro.graph.ir import (AddNode, BatchNormFoldNode, Conv2DNode,
                            DenseNode, FlattenNode, FusedConvBlockNode,
                            GlobalAvgPoolNode, Graph, InputNode,
                            MaxPool2Node, MaxPoolNode, QuantizeNode,
                            ReluNode)
from repro.graph.passes import default_passes, place_channel_parallel
from repro.graph.trace import add, global_avg_pool, max_pool, trace
from repro.ops.policy import ExecPolicy, current_policy

__all__ = ["ExecutionPlan", "BoundPlan", "compile_model"]


def _apply_quantize(node: QuantizeNode, val, q: QFormat):
    """int8 kinds produce QTensors (codes + scale), NOT fake-quant floats:
    the conv entry points contract the codes and apply sx·sw as a
    per-output-channel requant epilogue (inside the fused kernel's
    pipeline), so the dequant multiply never touches the full operand
    tensors — the weight half of it is constant-folded by ``bind``."""
    if node.kind == "qformat":
        return q.quantize(val)
    if node.kind == "int8_act":
        return quantize_int8(val, axis=None)
    if node.kind == "int8_conv_weight":
        m = val.shape[0]
        t = quantize_int8(val.reshape(m, -1), axis=-1)
        return QTensor(t.codes.reshape(val.shape), t.scale.reshape(-1))
    raise ValueError(f"unknown quantize kind {node.kind!r}")


def _fold_batch_norm(node: BatchNormFoldNode, params):
    """The conv weight (``part="w"``) or bias (``"b"``) with its batch
    norm folded in: w·s and β + (b − μ)·s, s = γ/√(σ² + ε)."""
    scale = node.gamma.fetch(params) * jax.lax.rsqrt(
        node.var.fetch(params) + node.eps)
    if node.part == "w":
        return node.w.fetch(params) * scale[:, None, None, None]
    b = 0.0 if node.b is None else node.b.fetch(params)
    return node.beta.fetch(params) + (b - node.mean.fetch(params)) * scale


@dataclass(frozen=True)
class ExecutionPlan:
    """A compiled graph + its baked quantization (and, when compiled with
    ``mesh=``, its channel-parallel placement), executable as
    ``plan(params, images)``."""

    graph: Graph
    quant: str = "none"
    qformat: QFormat = field(default_factory=QFormat)
    compile_policy: ExecPolicy | None = None
    mesh: Mesh | None = None
    # measured tile selection at bind time (DESIGN.md §10)
    autotune: bool = False

    # ---------- policy resolution ----------
    def _base_policy(self, policy: ExecPolicy | None) -> ExecPolicy:
        pol = policy
        if pol is None:
            pol = self.compile_policy
        if pol is None:
            pol = current_policy()
        if pol.quant not in ("none", self.quant):
            raise ValueError(
                f"plan was compiled for quant={self.quant!r} but is being "
                f"run under quant={pol.quant!r}; recompile with "
                f".compile(policy=...) for a different number format")
        # quantization is explicit graph structure now — compute stages
        # run quant-free; dense keeps its mode (per-token int8 scales are
        # dynamic and live in ops.dense)
        return pol.with_options(quant="none")

    # ---------- execution ----------
    @staticmethod
    def _stage_policy(base: ExecPolicy, tiles: dict | None) -> ExecPolicy:
        """The per-stage policy: baked (bind-time autotuned) tile
        parameters ride as namespaced tiling overrides, which win over
        the tuning cache and the heuristics in ``tile_params``."""
        if not tiles:
            return base
        return base.with_options(tiling={**base.tile_overrides, **tiles})

    def __call__(self, params, x, *, policy: ExecPolicy | None = None,
                 _folded: dict | None = None, _placed: dict | None = None,
                 _tuned: dict | None = None):
        from repro.ops import conv2d, dense, fused_conv_block
        base = self._base_policy(policy)
        dense_pol = base.with_options(quant=self.quant, qformat=self.qformat)
        env: dict[int, jax.Array] = {}
        folded = _folded or {}
        placed = _placed or {}
        tuned = _tuned or {}

        def _weight(node, idx, attr):
            """Weight operand: pre-placed by a mesh-aware ``bind`` when
            available; else through the lowered graph's quantize node
            (possibly pre-folded); else read from the ParamRef."""
            if (node.id, attr) in placed:
                return placed[(node.id, attr)]
            if len(node.inputs) > idx:
                return env[node.inputs[idx]]
            ref = getattr(node, attr)
            return None if ref is None else ref.fetch(params)

        def _conv_stage(node, fused: bool, sid: str):
            xin = env[node.inputs[0]]
            wv = _weight(node, 1, "w")
            bv = _weight(node, 2, "b")
            spec = node.sharding
            if self.mesh is None or spec is None or spec.mode == "none":
                # single-device, or pure data parallel over the mesh
                pol = self._stage_policy(base, tuned.get(node.id))

                def stage(xl, wl, bl):
                    if fused:
                        return fused_conv_block(xl, wl, bl,
                                                stride=node.stride,
                                                odd=node.odd, policy=pol,
                                                stage=sid)
                    return conv2d(xl, wl, bl, stride=node.stride,
                                  padding=node.padding, stage=sid,
                                  policy=pol)

                return self._batch_parallel(stage, xin, wv, bv)
            from repro.core.parallelism import (
                ChannelParallelism, conv2d_channel_parallel,
                fused_conv_block_channel_parallel)
            from repro.ops.impls import split_requant
            x_arr, w_arr, scale = split_requant(xin, wv)
            x_arr = pad_spatial(x_arr, getattr(node, "padding", (0, 0)))
            mode = ChannelParallelism(spec.mode)
            ki, ko = spec.split(self.mesh.shape["model"])
            daxis = "data" if spec.data else None
            if fused:
                return fused_conv_block_channel_parallel(
                    x_arr, w_arr, bv, mesh=self.mesh, mode=mode,
                    stride=node.stride, odd=node.odd, scale=scale,
                    data_axis=daxis, icp=ki, ocp=ko, policy=base)
            return conv2d_channel_parallel(
                x_arr, w_arr, bv, mesh=self.mesh, mode=mode,
                stride=node.stride, scale=scale, data_axis=daxis,
                icp=ki, ocp=ko, policy=base)

        def _node(node, sid: str):
            if isinstance(node, InputNode):
                return self._scatter(x)
            if isinstance(node, QuantizeNode):
                if node.id in folded:
                    return folded[node.id]
                val = (node.ref.fetch(params) if node.constant
                       else env[node.inputs[0]])
                return _apply_quantize(node, val, self.qformat)
            if isinstance(node, BatchNormFoldNode):
                if node.id in folded:
                    return folded[node.id]
                return _fold_batch_norm(node, params)
            if isinstance(node, (Conv2DNode, FusedConvBlockNode)):
                return _conv_stage(
                    node, isinstance(node, FusedConvBlockNode), sid)
            if isinstance(node, ReluNode):
                return jax.nn.relu(env[node.inputs[0]])
            if isinstance(node, MaxPool2Node):
                return maxpool2(env[node.inputs[0]], odd=node.odd)
            if isinstance(node, MaxPoolNode):
                return max_pool(env[node.inputs[0]], node.window,
                                node.stride, node.padding)
            if isinstance(node, AddNode):
                return add(env[node.inputs[0]], env[node.inputs[1]])
            if isinstance(node, GlobalAvgPoolNode):
                # the conv->fc boundary, as FlattenNode: gather the
                # channel axis of a sharded activation
                return self._gather(global_avg_pool(env[node.inputs[0]]))
            if isinstance(node, FlattenNode):
                v = self._gather(env[node.inputs[0]])
                return v.reshape(v.shape[0], -1)
            if isinstance(node, DenseNode):
                wq = folded.get(node.id)
                b = _weight(node, 2, "b")
                if wq is not None:
                    # bind pre-quantized this dense weight: run the real
                    # int8 datapath directly (== ops.dense under int8)
                    from repro.ops import qdense
                    pol = self._stage_policy(base, tuned.get(node.id))
                    out = self._batch_parallel(
                        lambda xl, wl: qdense(xl, wl, out_dtype=xl.dtype,
                                              policy=pol),
                        env[node.inputs[0]], wq)
                    return out if b is None else out + b
                pol = self._stage_policy(dense_pol, tuned.get(node.id))
                return self._batch_parallel(
                    lambda xl, wl, bl: dense(xl, wl, bl, policy=pol),
                    env[node.inputs[0]], _weight(node, 1, "w"), b)
            raise TypeError(f"no executor for node {node.pretty()}")

        # stage i (``self.stages()[i]``) runs under the scope s<i>.<op>,
        # which the compiled program's op metadata carries
        for i, node in enumerate(self.graph):
            with jax.named_scope(f"s{i}.{node.op}"):
                env[node.id] = _node(node, f"s{i}")
        return env[self.graph.output_id]

    def _scatter(self, x):
        """Place the serving batch along the ``data`` axis on entry
        (DESIGN.md §15): the front-end's bucketed batches split across
        the data dimension of the mesh before the first stage runs, so
        data-parallel replicas work on disjoint batch slices instead of
        every device repeating the full batch. Batches that don't divide
        the axis stay as-is (the schedules replicate them, exactly as
        before)."""
        if self.mesh is None or "data" not in self.mesh.axis_names:
            return x
        if x.ndim < 1 or x.shape[0] % self.mesh.shape["data"]:
            return x
        sh = NamedSharding(self.mesh, P("data", *[None] * (x.ndim - 1)))
        if isinstance(x, jax.core.Tracer):
            return jax.lax.with_sharding_constraint(x, sh)
        return jax.device_put(x, sh)

    def _batch_parallel(self, fn, x, *consts):
        """``fn(x, *consts)`` with the batch split over the mesh's
        ``data`` axis by a shard_map, ``consts`` (weights, None allowed)
        replicated. A Pallas kernel is a one-device program that XLA
        cannot partition, so on a data-parallel mesh the plan hands each
        device its batch slice itself; without a data axis wider than
        one, or when the batch does not divide it, this is plain
        ``fn(x, *consts)``."""
        data = self.mesh.shape.get("data", 1) if self.mesh is not None \
            else 1
        lead = x.codes if isinstance(x, QTensor) else x
        if data == 1 or lead.shape[0] % data:
            return fn(x, *consts)
        from jax import shard_map
        batch = P("data", *[None] * (lead.ndim - 1))
        present = [c for c in consts if c is not None]

        def local(xl, *kept):
            it = iter(kept)
            return fn(xl, *[None if c is None else next(it) for c in consts])

        return shard_map(
            local, mesh=self.mesh,
            in_specs=(QTensor(batch, P()) if isinstance(x, QTensor)
                      else batch, *[P()] * len(present)),
            out_specs=P("data"), check_vma=False)(x, *present)

    def _gather(self, v):
        """Collect a (possibly channel-sharded) activation at the conv→fc
        boundary: an axis-aware all-gather that moves ONLY the model
        (channel) axis — the batch dim *keeps* its ``data`` sharding, so
        the gather's per-device traffic is the model-axis shards of the
        local batch slice, never the whole batch. This is the paper's
        accelerator DMA-ing the final feature map out of the conv
        pipeline — and it pins the dense tail to the exact same program
        the unsharded plan runs (replicated over model), so a sharded
        plan stays bitwise-comparable end to end."""
        if self.mesh is None:
            return v
        batch = "data" if "data" in self.mesh.axis_names else None
        sh = NamedSharding(self.mesh, P(batch, *[None] * (v.ndim - 1)))
        if isinstance(v, jax.core.Tracer):
            return jax.lax.with_sharding_constraint(v, sh)
        return jax.device_put(v, sh)

    # ---------- constant folding + placement ----------
    def _shard_weight(self, node, folded: dict, placed: dict,
                      params) -> None:
        """Pin one sharded conv stage's weight-side operands to their mesh
        placement (the one-time flash of the per-unit weight ROMs):
        OCP shards w/b (and the int8 weight scale) on M over ``model``,
        ICP shards w on N and replicates b. Lowered (quantized) operands
        are placed in-place in ``folded``; unlowered ones go to ``placed``
        keyed by (node id, attr)."""
        spec = node.sharding
        if spec is None or spec.mode == "none":
            return
        if spec.mode == "both":
            # composed split: weights block over the (ocp, icp) sub-grid
            # of the stage mesh; bias/scale shard with their M over ocp
            from repro.core.parallelism import stage_mesh
            ki, ko = spec.split(self.mesh.shape["model"])
            mesh = stage_mesh(self.mesh, ki, ko, "model")
            wspec = P("ocp", "icp", None, None)
            vspec = P("ocp")
        else:
            mesh = self.mesh
            ocp = spec.mode == "output"
            wspec = P("model", None, None, None) if ocp \
                else P(None, "model", None, None)
            vspec = P("model") if ocp else P(None)

        def put(val, part):
            sh = NamedSharding(mesh, part)
            if isinstance(val, QTensor):      # int8: codes + per-M scales
                return jax.device_put(val, QTensor(
                    sh, NamedSharding(mesh, vspec)))
            return jax.device_put(val, sh)

        if len(node.inputs) > 1:              # quantize-lowered weight
            folded[node.inputs[1]] = put(folded[node.inputs[1]], wspec)
        else:
            placed[(node.id, "w")] = put(node.w.fetch(params), wspec)
        if len(node.inputs) > 2:              # qformat-lowered bias
            folded[node.inputs[2]] = put(folded[node.inputs[2]], vspec)
        elif node.b is not None:
            placed[(node.id, "b")] = put(node.b.fetch(params), vspec)

    def _stage_calls(self, params, folded: dict):
        """Yield (node, op, args, kwargs) for every tunable stage — the
        concrete calling convention the autotuner measures: a
        representative activation built from the graph's static specs,
        the real bound weights (quantization included; int8 stages get
        codes-as-f32 plus the requant-epilogue scale operand)."""
        import numpy as np
        from repro.graph.passes import stage_input_spec, tunable_stages
        from repro.ops.impls import split_requant
        rng = np.random.RandomState(0)
        for node in tunable_stages(self.graph):
            spec = stage_input_spec(self.graph, node)
            shape = spec.shape
            ph, pw = getattr(node, "padding", (0, 0))
            if ph or pw:    # the kernel runs VALID over the padded input
                bsz, n, h, w = shape
                shape = (bsz, n, h + 2 * ph, w + 2 * pw)
            x = jnp.asarray(rng.standard_normal(shape), spec.dtype)
            if isinstance(node, (Conv2DNode, FusedConvBlockNode)):
                fused = isinstance(node, FusedConvBlockNode)
                op = "fused_conv_block" if fused else "conv2d"
                wv = (folded[node.inputs[1]] if len(node.inputs) > 1
                      else node.w.fetch(params))
                bv = (folded.get(node.inputs[2])
                      if len(node.inputs) > 2 else
                      (None if node.b is None else node.b.fetch(params)))
                scale = None
                if isinstance(wv, QTensor):
                    _, w_arr, scale = split_requant(
                        QTensor(x.astype(jnp.float32), jnp.float32(1.0)), wv)
                else:
                    w_arr = wv
                kw = dict(stride=node.stride)
                if fused:
                    kw["scale"] = scale     # the in-kernel requant epilogue
                yield node, op, (x, w_arr, bv), kw
            else:                           # DenseNode
                wq = folded.get(node.id)
                if wq is None:              # fp dense is a plain einsum —
                    continue                # nothing to tune
                xq = quantize_int8(x.reshape(x.shape[0], -1), axis=-1)
                yield node, "qmatmul", (xq.codes, wq.codes,
                                        xq.scale, wq.scale), {}

    def _autotune_stages(self, params, folded: dict,
                         policy: ExecPolicy | None = None
                         ) -> dict[int, dict]:
        """Measure tile winners for every tunable stage (DESIGN.md §10).

        Per stage: run ``ensure_tuned`` on the stage's concrete calling
        convention — a tuning-cache hit skips the measurement, a miss
        times the candidate grid — and return {node id: namespaced tiling
        overrides} for baking. ``policy`` is the *bind* policy (the one
        the bound plan will execute under): stages whose dispatch under
        it would not land on the pallas backend tune nothing
        (``ensure_tuned`` returns None) — tiles only bind there. A winner
        that IS the heuristic point bakes nothing either — the default
        resolution already produces that exact program.
        """
        from repro.ops.autotune import ensure_tuned, heuristic_tiles
        base = self._base_policy(policy)
        tuned: dict[int, dict] = {}
        for node, op, args, kw in self._stage_calls(params, folded):
            best = ensure_tuned(op, *args, policy=base, **kw)
            if best and best != heuristic_tiles(op, *args, **kw):
                tuned[node.id] = {f"{op}.{k}": v for k, v in best.items()}
        return tuned

    def pin_heuristic_tiles(self, params, folded: dict | None = None
                            ) -> int:
        """Winner-validation hook (DESIGN.md §10): overwrite every
        tunable stage's tuning-cache entry with the analytic heuristic
        point. Callers use this when a plan-level A/B shows the op-level
        winners regressing end to end (``benchmarks/pipeline_sweep.py``);
        re-binding afterwards bakes nothing and later runs keep the
        incumbent instead of re-chasing the same noise. Pass an existing
        ``BoundPlan.folded`` to skip re-folding the weight quantization.
        Returns how many stage entries were pinned."""
        from repro.ops.autotune import heuristic_tiles
        from repro.ops.tiling import TUNING_CACHE
        pinned = 0
        if folded is None:
            folded = self._fold_constants(params)
        for node, op, args, kw in self._stage_calls(params, folded):
            heur = heuristic_tiles(op, *args, **kw)
            if heur is None:
                continue
            if op == "qmatmul":
                m, k = args[0].shape
                sig = (m, k, args[1].shape[1])
            else:
                from repro.ops.tiling import conv_signature
                sig = conv_signature(args[0].shape, args[1].shape,
                                     tuple(kw.get("stride", (1, 1))))
            TUNING_CACHE.put(op, sig, args[0].dtype, heur)
            pinned += 1
        return pinned

    def _fold_constants(self, params) -> dict:
        """The constant fold of ``bind``: every constant QuantizeNode and
        every batch-norm fold (phase ``fold``), plus each dense layer's
        QTensor under int8."""
        with phase("fold"):
            folded = {
                node.id: _apply_quantize(node, node.ref.fetch(params),
                                         self.qformat)
                for node in self.graph
                if isinstance(node, QuantizeNode) and node.constant}
            folded.update({
                node.id: _fold_batch_norm(node, params)
                for node in self.graph
                if isinstance(node, BatchNormFoldNode)})
        if self.quant == "int8":
            for node in self.graph:
                if isinstance(node, DenseNode):
                    folded[node.id] = quantize_int8(node.w.fetch(params),
                                                    axis=0)
        return folded

    def bind(self, params, *, policy: ExecPolicy | None = None,
             verify: bool = True) -> "BoundPlan":
        """Fold weight quantization against ``params`` now: every
        constant QuantizeNode (conv weights/biases), plus — under int8 —
        each dense layer's per-output-channel QTensor, so per-batch calls
        skip weight requantization entirely (only the per-token activation
        scales stay dynamic). On a mesh-compiled plan the folded/fetched
        conv weights are additionally ``device_put`` under their
        ShardingSpec, so binding is a one-time placement and per-batch
        calls start from resident shards. On an ``autotune=True`` plan the
        measured tile winners are baked in here too — the per-batch call
        runs on tuned tiles without ever touching the tuner or the cache.

        ``verify=True`` (the default) re-runs the static verifier
        (DESIGN.md §14) over the bound plan, adding the bound-level
        checks: folded QTensor codes/scale shapes match their stages,
        every fingerprint input serializes. Read-only — the BoundPlan
        is identical with or without it."""
        folded = self._fold_constants(params)
        tuned: dict = {}
        if self.autotune:
            with phase("tune"):
                tuned = self._autotune_stages(params, folded, policy=policy)
        placed = self._place_weights(params, folded)
        bound = BoundPlan(plan=self, params=params, folded=folded,
                          policy=policy, placed=placed, tuned=tuned)
        if verify:
            from repro.analysis.verifier import verify_plan
            verify_plan(bound)
        return bound

    def _place_weights(self, params, folded: dict) -> dict:
        """The mesh half of ``bind``: ``device_put`` every sharded conv
        stage's weight operands under their ShardingSpec. Pure data
        movement over an already-placed graph — the artifact loader
        (DESIGN.md §12) re-runs this on restored payloads without ever
        re-running the placement *pass*."""
        placed: dict = {}
        if self.mesh is not None:
            for node in self.graph:
                if isinstance(node, (Conv2DNode, FusedConvBlockNode)):
                    self._shard_weight(node, folded, placed, params)
        return placed

    # ---------- persistence (DESIGN.md §12) ----------
    def save(self, params, path, *, policy: ExecPolicy | None = None,
             input_shapes=None, aot: bool = True) -> str:
        """``bind`` against ``params`` and persist the result as a plan
        artifact (``repro.artifact.store.save_plan``): manifest + weight/
        QTensor payloads + AOT-compiled executables. Returns the content
        fingerprint. ``PaperCNN.compile(...).save(params, path)`` is the
        one-line export; ``BoundPlan.load(path)`` is the matching
        zero-derivation import."""
        return self.bind(params, policy=policy).save(
            path, input_shapes=input_shapes, aot=aot)

    # ---------- introspection ----------
    def stages(self) -> list[str]:
        return [n.pretty() for n in self.graph]

    def num_fused(self) -> int:
        return sum(isinstance(n, FusedConvBlockNode) for n in self.graph)

    def num_sharded(self) -> int:
        return sum(getattr(n, "sharding", None) is not None
                   and n.sharding.mode != "none" for n in self.graph)

    def pretty(self) -> str:
        mesh = "" if self.mesh is None else \
            f", mesh={dict(self.mesh.shape)}"
        head = (f"ExecutionPlan(quant={self.quant}, "
                f"{len(self.graph)} nodes, {self.num_fused()} fused{mesh})")
        return head + "\n" + self.graph.pretty()


@dataclass(frozen=True)
class BoundPlan:
    """An ExecutionPlan closed over one params pytree with weight
    quantization pre-folded (and, on a mesh plan, weights pre-sharded;
    on an autotuned plan, measured tile winners pre-baked) — call as
    ``bound(images)``."""

    plan: ExecutionPlan
    params: object
    folded: dict
    policy: ExecPolicy | None = None
    placed: dict = field(default_factory=dict)
    # {node id: namespaced tiling overrides} measured at bind time
    tuned: dict = field(default_factory=dict)

    def __call__(self, x, *, policy: ExecPolicy | None = None):
        return self.plan(self.params, x,
                         policy=policy if policy is not None else self.policy,
                         _folded=self.folded, _placed=self.placed,
                         _tuned=self.tuned)

    # ---------- persistence (DESIGN.md §12) ----------
    def fingerprint(self) -> str:
        """Content fingerprint over graph IR + quant + placement + baked
        tiles + policies + mesh shape + weights + versions."""
        from repro.artifact.fingerprint import plan_fingerprint
        return plan_fingerprint(self.plan, params=self.params,
                                tuned=self.tuned, bind_policy=self.policy)

    def save(self, path, *, input_shapes=None, aot: bool = True) -> str:
        """Persist as a versioned plan artifact; returns the content
        fingerprint. See ``repro.artifact.store.save_plan``."""
        from repro.artifact.store import save_plan
        return save_plan(self, path, input_shapes=input_shapes, aot=aot)

    @classmethod
    def load(cls, path, *, params=None) -> "BoundPlan":
        """Reconstruct a bound plan from an artifact — no re-trace, no
        passes, no re-placement, no re-tuning. ``params`` (optional)
        asserts the artifact matches the caller's weights. Raises
        ``repro.artifact.ArtifactError`` when the artifact is unusable
        (serving paths use ``PlanStore.load`` for warn-and-fall-back)."""
        from repro.artifact.store import load_plan
        return load_plan(path, params=params).bound


def compile_model(model, input_shape: tuple[int, ...] | None = None, *,
                  policy: ExecPolicy | None = None, fuse: bool = True,
                  mesh: Mesh | None = None, autotune: bool = False,
                  dtype: str = "float32",
                  verify: bool = True) -> ExecutionPlan:
    """trace → passes → plan for any model whose forward routes through
    the hooked functional layer (DESIGN.md §8).

    The quantization mode is resolved now (explicit ``policy`` >
    model-config policy > ambient ``use_policy``) and baked into the
    plan; backend/interpret/tiling stay dynamic through the registry.

    ``mesh`` (with a ``model`` axis, optionally a ``data`` axis) runs the
    channel-parallel placement pass (DESIGN.md §9/§15) and bakes the mesh
    into the plan: an icp × ocp model-axis split per conv stage from the
    stage's arithmetic intensity (pure ICP, pure OCP, composed, or
    replicated when nothing divides), overridable via
    ``ExecPolicy.channel_parallel``; batches scatter over ``data``.

    ``autotune=True`` (or ``ExecPolicy.autotune``) defers to DESIGN.md
    §10: ``plan.bind`` measures tile candidates per stage (tuning-cache
    hits skip the measurement) and bakes the winners into the BoundPlan.

    ``verify=True`` (the default) runs the static plan verifier
    (``repro.analysis.verify_plan``, DESIGN.md §14) over the finished
    plan — shape/dtype flow, quant invariants, sharding legality,
    artifact coherence — raising ``PlanVerificationError``
    with named violations. Verification is read-only: verified and
    unverified compiles produce byte-identical plans.
    """
    if input_shape is None:
        input_shape = model.input_shape()
    pol = policy
    if pol is None:
        cfg_pol = getattr(model, "cfg", None)
        exec_pol = getattr(cfg_pol, "exec_policy", None)
        pol = exec_pol() if callable(exec_pol) else None
    quant_pol = pol if pol is not None else current_policy()
    with phase("trace"):
        graph = trace(model, tuple(input_shape), dtype)
    with phase("fuse"):
        graph = default_passes(graph, quant=quant_pol.quant,
                               qformat=quant_pol.qformat, fuse=fuse)
    if mesh is not None:
        if "model" not in mesh.axis_names:
            raise ValueError(
                f"mesh {dict(mesh.shape)} has no 'model' axis; channel "
                f"parallelism (paper §III.A) shards over 'model' and "
                f"batches over 'data'")
        with phase("place"):
            graph = place_channel_parallel(
                graph, mesh.shape["model"],
                override=quant_pol.channel_parallel,
                data="data" in mesh.axis_names)
    plan = ExecutionPlan(graph=graph, quant=quant_pol.quant,
                         qformat=quant_pol.qformat, compile_policy=pol,
                         mesh=mesh,
                         autotune=autotune or quant_pol.autotune)
    if verify:
        from repro.analysis.verifier import verify_plan
        verify_plan(plan)
    return plan
