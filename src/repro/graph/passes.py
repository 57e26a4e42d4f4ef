"""Graph passes: fusion, quantization lowering, dead-quantize elimination.

The pass pipeline turns the traced layer-by-layer graph into the paper's
deep pipeline (DESIGN.md §8):

  0. ``fold_batch_norm`` — every Conv2D → BatchNorm pair becomes the conv
     alone, its weight and bias read from two constant
     ``BatchNormFoldNode``s that ``ExecutionPlan.bind`` computes once
     (w·γ/√(σ²+ε) and β + (b − μ)·γ/√(σ²+ε)), so no batch norm runs per
     batch.

  1. ``fuse_conv_blocks`` — every single-consumer Conv2D → Relu → MaxPool2
     chain collapses into one ``FusedConvBlockNode``, executed by the
     ``fused_conv_block`` op family (conv window pipeline + bias + relu +
     2×2 pool in one kernel; the pre-pool activation never round-trips
     HBM — §III.B's between-stage streaming, lifted between layers).

  2. ``lower_quant`` — makes the plan's quantization mode *explicit* as
     QuantizeNodes so downstream ops run with ``quant="none"``:
     weights get per-ref quantize nodes marked ``constant`` (foldable once
     by ``ExecutionPlan.bind`` — the scale constant-folding), activations
     get per-edge quantize nodes, and qformat conv/fused outputs get the
     paper's post-accumulate lattice snap. Dense nodes keep their quant in
     the executor (the int8 dense path needs per-token dynamic scales);
     their *weight* QTensor still folds, in ``bind`` rather than as a
     graph node.

  3. ``eliminate_dead_quantize`` — the Qm.n snap is idempotent and
     commutes with relu/maxpool/flatten (monotone, 0-preserving), so an
     activation quantize whose producer chain is provably lattice-valued
     is dead and is removed. This is why the fused pipeline quantizes once
     per block instead of twice per layer boundary.

  4. ``place_channel_parallel`` (mesh compiles only, DESIGN.md §9/§15) —
     stamps the paper's §III.A parallelism choice on every conv stage as
     a ``ShardingSpec``: the model axis factors per stage into an
     ``icp × ocp`` split chosen by an arithmetic-intensity cost model
     (``_split_cost``) — pure OCP (Eq. 6), pure ICP (Eq. 7), a composed
     2-D split, or replicated when nothing divides — overridable through
     ``ExecPolicy.channel_parallel``.

Every pass is ``Graph -> Graph`` and re-validates; numerics after the full
pipeline match the eager model exactly (bitwise per backend) — pinned by
``tests/test_graph.py`` (and, for placed graphs, ``tests/test_shard_plan``).
"""
from __future__ import annotations

from dataclasses import replace

from repro.core.quantize import QFormat
from repro.graph.ir import (BatchNormFoldNode, BatchNormNode, Conv2DNode,
                            DenseNode, FlattenNode, FusedConvBlockNode,
                            Graph, MaxPool2Node, Node, QuantizeNode,
                            ReluNode, ShardingSpec, TensorSpec)

__all__ = ["fold_batch_norm", "fuse_conv_blocks", "lower_quant",
           "eliminate_dead_quantize",
           "place_channel_parallel", "default_passes", "tunable_stages",
           "stage_input_spec", "stage_arith_intensity"]


def _single_consumer(graph: Graph, nid: int) -> Node | None:
    cons = graph.consumers(nid)
    return cons[0] if len(cons) == 1 and graph.output_id != nid else None


def fold_batch_norm(graph: Graph) -> Graph:
    """Conv2D → BatchNorm (the conv's only consumer) ⇒ the conv, reading
    its weight and bias from two constant ``BatchNormFoldNode``s placed
    just before it; the conv takes the batch norm's id, so its consumers
    stay wired. A batch norm after anything else raises: the plan runs
    none per batch."""
    folds = {}                           # conv id -> its BatchNormNode
    for node in graph:
        if not isinstance(node, BatchNormNode):
            continue
        conv = graph.node(node.inputs[0])
        if not (isinstance(conv, Conv2DNode)
                and _single_consumer(graph, conv.id) is node):
            raise ValueError(
                f"%{node.id} batch_norm follows %{conv.id} {conv.op}: only "
                f"a batch norm that is a conv's one consumer folds")
        folds[conv.id] = node
    if not folds:
        return graph
    nid = graph.next_id()
    out: list[Node] = []
    for node in graph:
        if isinstance(node, BatchNormNode):
            continue
        bn = folds.get(node.id)
        if bn is None:
            out.append(node)
            continue
        refs = dict(w=node.w, b=node.b, gamma=bn.gamma, beta=bn.beta,
                    mean=bn.mean, var=bn.var, eps=bn.eps)
        fw = BatchNormFoldNode(id=nid, inputs=(), part="w",
                               out=TensorSpec(node.w.shape, node.w.dtype),
                               **refs)
        fb = BatchNormFoldNode(id=nid + 1, inputs=(), part="b",
                               out=TensorSpec(bn.gamma.shape, bn.gamma.dtype),
                               **refs)
        nid += 2
        out += [fw, fb, replace(node, id=bn.id,
                                inputs=(node.inputs[0], fw.id, fb.id))]
    # a folded conv takes its batch norm's (later) id at the conv's place,
    # which is still before every consumer of either
    return replace(graph, nodes=tuple(out)).validate()


def fuse_conv_blocks(graph: Graph) -> Graph:
    """Conv2D → Relu → MaxPool2 (linear, single-consumer) ⇒ one
    FusedConvBlockNode carrying the pool's id (so downstream inputs and
    the graph output stay valid). A padded conv stays unfused: the fused
    kernel pools a VALID conv."""
    fused: list[Node] = []
    skip: set[int] = set()
    for node in graph:
        if node.id in skip:
            continue
        if isinstance(node, Conv2DNode) and node.padding == (0, 0):
            r = _single_consumer(graph, node.id)
            if isinstance(r, ReluNode):
                p = _single_consumer(graph, r.id)
                if isinstance(p, MaxPool2Node):
                    fused.append(FusedConvBlockNode(
                        id=p.id, inputs=node.inputs, out=p.out,
                        w=node.w, b=node.b, stride=node.stride, odd=p.odd))
                    skip.update({r.id, p.id})
                    continue
        fused.append(node)
    # creation order kept nodes topologically sorted; the fused node uses
    # the pool's (later) id but sits at the conv's position, which is
    # still before every consumer
    return replace(graph, nodes=tuple(fused)).validate()


def _quantize_node(nid: int, src: int, spec: TensorSpec, kind: str,
                   q: QFormat, constant: bool = False,
                   ref=None) -> QuantizeNode:
    return QuantizeNode(id=nid, inputs=(src,), out=spec, kind=kind,
                        int_bits=q.int_bits, frac_bits=q.frac_bits,
                        constant=constant, ref=ref)


def lower_quant(graph: Graph, quant: str,
                qformat: QFormat | None = None) -> Graph:
    """Insert explicit QuantizeNodes per ``quant`` mode.

    Replicates exactly what ``repro.ops.conv2d`` / ``fused_conv_block``
    do internally under a quantized ExecPolicy — but as graph structure,
    so weight quantization becomes a foldable constant and redundant
    activation snaps become visible to DQE.
    """
    if quant == "none":
        return graph
    if quant not in ("qformat", "int8"):
        raise ValueError(f"unknown quant mode {quant!r}")
    q = qformat or QFormat()
    nodes: list[Node] = []
    nid = graph.next_id()
    rewired: dict[int, int] = {}      # producer id -> quantized-value id

    def _wref(w, kind):
        nonlocal nid
        node = replace(_quantize_node(nid, -1, TensorSpec(w.shape, w.dtype),
                                      kind, q, constant=True, ref=w),
                       inputs=())
        nodes.append(node)
        nid += 1
        return node.id

    for node in graph:
        inputs = tuple(rewired.get(i, i) for i in node.inputs)
        if isinstance(node, (Conv2DNode, FusedConvBlockNode)):
            if len(node.inputs) > 1:
                raise ValueError(
                    f"quant={quant!r}: %{node.id} {node.op} reads a folded "
                    f"batch norm, which is not lowered to {quant} yet; "
                    f"compile it with quant='none'")
            # activation quantize on the conv input edge
            act_kind = "qformat" if quant == "qformat" else "int8_act"
            src = inputs[0]
            src_spec = graph.node(node.inputs[0]).out
            aq = _quantize_node(nid, src, src_spec, act_kind, q)
            nodes.append(aq)
            nid += 1
            wkind = ("qformat" if quant == "qformat" else "int8_conv_weight")
            wq = _wref(node.w, wkind)
            bq = None
            if node.b is not None and quant == "qformat":
                bq = _wref(node.b, "qformat")
            # weight refs are rebound to quantize-node ids at execution
            # time via `inputs`; keep the ref fields for introspection
            lowered = replace(node, inputs=(aq.id, wq) +
                              (() if bq is None else (bq,)))
            nodes.append(lowered)
            if quant == "qformat":
                oq = _quantize_node(nid, node.id, node.out, "qformat", q)
                nodes.append(oq)
                nid += 1
                rewired[node.id] = oq.id
        else:
            nodes.append(replace(node, inputs=inputs))
    out = rewired.get(graph.output_id, graph.output_id)
    return replace(graph, nodes=tuple(nodes), output_id=out).validate()


def _lattice_valued(graph: Graph, nid: int, q: QuantizeNode) -> bool:
    """True if %nid provably lies on q's Qm.n lattice: produced by an
    equal-format qformat quantize, or by a lattice-preserving op (relu,
    maxpool, flatten) over lattice values."""
    node = graph.node(nid)
    if isinstance(node, QuantizeNode):
        return (node.kind == "qformat" and node.int_bits == q.int_bits
                and node.frac_bits == q.frac_bits)
    if isinstance(node, (ReluNode, MaxPool2Node, FlattenNode)):
        return _lattice_valued(graph, node.inputs[0], q)
    return False


def eliminate_dead_quantize(graph: Graph) -> Graph:
    """Remove idempotent activation quantizes (qformat over already-
    lattice values). Weight (constant) quantizes and int8 activation
    quantizes are never dead (int8 scales are data-dependent)."""
    changed = True
    while changed:
        changed = False
        for node in graph:
            if (isinstance(node, QuantizeNode) and not node.constant
                    and node.kind == "qformat" and node.inputs
                    and _lattice_valued(graph, node.inputs[0], node)):
                graph = replace(
                    graph,
                    nodes=tuple(n for n in graph if n.id != node.id))
                graph = graph.replace_input(node.id, node.inputs[0])
                changed = True
                break
    return graph.validate()


# Modeled fixed cost of one ppermute ring hop (collective launch + sync),
# expressed in element-traffic units so it adds directly to the byte terms
# of ``_split_cost``. It is what makes the model prefer a short ring over
# a long one when the per-hop payload is small — the measured mesh-4 ICP
# falloff of BENCH_shard.json, as a constant.
_HOP_OVERHEAD = 4096.0


def _split_cost(m: int, n: int, kh: int, kw: int, ho: int, wo: int,
                ki: int, ko: int) -> float:
    """Per-device cost model of an (icp=ki, ocp=ko) channel split —
    the stage's arithmetic intensity turned into a placement score.

    Terms (element units, per device):

      * compute — (M/ko)·(N/ki)·Kh·Kw·Ho·Wo MACs; both factors shrink it.
      * window  — the im2col/window stream each device reads:
        (N/ki)·Kh·Kw·Ho·Wo. Only the ICP factor shrinks it — under OCP
        every device streams the *full* input (Eq. 6 replicates x).
      * reduce  — the ICP ring: ki−1 hops, each moving the whole
        (M/ko)·Ho·Wo partial buffer, plus a fixed per-hop overhead.
        Only exists when ki > 1; shrinks as ko grows — the 2-D win.

    Low-arithmetic-intensity stages (small M, big windows) land on ICP;
    wide-M stages on OCP; in between, a mixed split keeps the ring short
    while still dividing the window stream.
    """
    spatial = ho * wo
    compute = (m / ko) * (n / ki) * kh * kw * spatial
    window = (n / ki) * kh * kw * spatial
    reduce_ = (ki - 1) * ((m / ko) * spatial + _HOP_OVERHEAD)
    return compute + window + reduce_


def _pick_split(m: int, n: int, kh: int, kw: int, ho: int, wo: int,
                model_size: int) -> tuple[int, int]:
    """Choose the (icp, ocp) factorization of the model axis for one
    stage: the feasible (ki | N, ko | M, ki·ko = mesh) split of minimum
    modeled cost. ``(1, 1)`` — pure data parallelism — is always
    feasible, so auto-placement never produces an invalid plan; it only
    wins when no divisible split is cheaper than staying replicated.
    """
    best, best_cost = (1, 1), _split_cost(m, n, kh, kw, ho, wo, 1, 1)
    for ki in range(1, model_size + 1):
        if model_size % ki:
            continue
        ko = model_size // ki
        if n % ki or m % ko:
            continue
        cost = _split_cost(m, n, kh, kw, ho, wo, ki, ko)
        if cost < best_cost:
            best, best_cost = (ki, ko), cost
    return best


def _split_mode(ki: int, ko: int) -> str:
    if ki > 1 and ko > 1:
        return "both"
    if ki > 1:
        return "input"
    if ko > 1:
        return "output"
    return "none"


def _conv_hw(graph: Graph, node: Node) -> tuple[int, int]:
    """The stage's PRE-pool conv output spatial extent (the reduce buffer
    size — a fused block's ``out`` is already pooled)."""
    h, w = stage_input_spec(graph, node).shape[2:]
    kh, kw = node.w.shape[2], node.w.shape[3]
    sh, sw = node.stride
    ph, pw = getattr(node, "padding", (0, 0))
    return (h + 2 * ph - kh) // sh + 1, (w + 2 * pw - kw) // sw + 1


def stage_arith_intensity(graph: Graph) -> list[dict]:
    """Per-conv-stage arithmetic intensity (MACs per element moved) and
    the placement the cost model derives from it — recorded into
    shard_sweep's JSON so the benchmark explains its own placements."""
    out = []
    for node in graph:
        if not isinstance(node, (Conv2DNode, FusedConvBlockNode)):
            continue
        m, n = node.w.shape[0], node.w.shape[1]
        kh, kw = node.w.shape[2], node.w.shape[3]
        ho, wo = _conv_hw(graph, node)
        macs = m * n * kh * kw * ho * wo
        moved = n * ho * wo * kh * kw + m * n * kh * kw + m * ho * wo
        spec = getattr(node, "sharding", None)
        out.append({
            "node": node.id, "op": node.op,
            "m": m, "n": n, "k": [kh, kw], "conv_hw": [ho, wo],
            "macs": macs, "elements_moved": moved,
            "intensity": round(macs / moved, 3),
            "placement": None if spec is None else str(spec),
        })
    return out


def place_channel_parallel(graph: Graph, model_size: int, *,
                           override: str | None = None,
                           data: bool = True) -> Graph:
    """Attach a ``ShardingSpec`` to every conv / fused-conv stage.

    ``model_size`` is the mesh's ``model``-axis extent. Auto placement
    factors that axis per stage into an ``icp × ocp`` split chosen by the
    ``_split_cost`` arithmetic-intensity model (DESIGN.md §15) — pure
    ICP, pure OCP, a genuine 2-D split, or pure data parallelism when no
    channel dim divides. ``override`` (ExecPolicy.channel_parallel:
    "input" | "output" | "none") forces the whole axis onto one 1-D
    schedule; a stage whose channels the forced schedule cannot shard
    (e.g. ICP on a 1-channel input layer) stays **replicated** — never
    silently the other schedule — with the decision visible in
    ``plan.pretty()`` / ``num_sharded()``. An override that applies to
    *no* stage raises (asking a whole network for an impossible schedule
    is a configuration bug, like an ExecPolicy backend no op registers).
    ``data`` opts the batch dim into ``data``-axis sharding (orthogonal
    to the mode).
    """
    placed: list[Node] = []
    forced_hits = 0
    conv_stages = 0
    for node in graph:
        if not isinstance(node, (Conv2DNode, FusedConvBlockNode)):
            placed.append(node)
            continue
        conv_stages += 1
        m, n = node.w.shape[0], node.w.shape[1]
        if override is None:
            ho, wo = _conv_hw(graph, node)
            ki, ko = _pick_split(m, n, node.w.shape[2], node.w.shape[3],
                                 ho, wo, model_size)
            mode = _split_mode(ki, ko)
        else:
            dim = m if override == "output" else n
            mode = override if (override == "none"
                                or dim % model_size == 0) else "none"
            forced_hits += mode == override != "none"
            ki, ko = ((model_size, 1) if mode == "input" else
                      (1, model_size) if mode == "output" else (1, 1))
        placed.append(replace(node, sharding=ShardingSpec(
            mode=mode, data=data,
            icp=ki if mode != "none" else 0,
            ocp=ko if mode != "none" else 0)))
    if override not in (None, "none") and conv_stages and not forced_hits:
        raise ValueError(
            f"channel_parallel={override!r} applies to none of the "
            f"{conv_stages} conv stages: no layer's "
            f"{'M' if override == 'output' else 'N'} divides the model "
            f"axis ({model_size} devices); use divisible channel counts "
            f"or drop the override for per-layer auto-placement")
    return replace(graph, nodes=tuple(placed)).validate()


def tunable_stages(graph: Graph) -> list[Node]:
    """The stages a measured autotuner can size (DESIGN.md §10): conv,
    fused conv block, and dense nodes, in execution order. Channel-sharded
    stages are excluded — their per-device shapes live inside shard_map,
    where tiles resolve through the tuning cache by (per-shard) signature
    rather than through plan-baked overrides."""
    out = []
    for node in graph:
        if isinstance(node, (Conv2DNode, FusedConvBlockNode)):
            spec = node.sharding
            if spec is None or spec.mode == "none":
                out.append(node)
        elif isinstance(node, DenseNode):
            out.append(node)
    return out


def stage_input_spec(graph: Graph, node: Node) -> TensorSpec:
    """The *float-level* activation spec feeding ``node``: quantize nodes
    are transparent (an int8_act QuantizeNode re-emits its input's spec —
    the executed QTensor's codes keep that shape, and the kernels contract
    codes as float32)."""
    src = graph.node(node.inputs[0])
    while isinstance(src, QuantizeNode) and src.inputs:
        src = graph.node(src.inputs[0])
    return src.out


def default_passes(graph: Graph, quant: str = "none",
                   qformat: QFormat | None = None,
                   fuse: bool = True) -> Graph:
    """The standard pipeline: fold batch norm → fuse → lower quant → DQE
    (the fold runs with ``fuse=False`` too: no batch norm runs per
    batch)."""
    graph = fold_batch_norm(graph)
    if fuse:
        graph = fuse_conv_blocks(graph)
    graph = lower_quant(graph, quant, qformat)
    return eliminate_dead_quantize(graph)
