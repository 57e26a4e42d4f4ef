"""Typed op-graph IR for the fusion graph compiler (DESIGN.md §8).

The paper's accelerator is a *static* machine: every layer's shapes, every
buffer depth, every datapath width is fixed at synthesis time, and the
deep pipeline (window buffer → mult-add tree → pooling) exists precisely
because the whole network structure is known up front. This module is that
synthesis-time view of a model: a small, fully-typed operator graph with
static shapes, produced by ``repro.graph.trace`` and consumed by the pass
pipeline (``repro.graph.passes``) and the plan executor
(``repro.graph.plan``).

Nodes are frozen dataclasses carrying

  * ``id``      — a stable integer (creation order; passes keep ids stable
                  where possible so dumps diff cleanly),
  * ``inputs``  — ids of producing nodes,
  * ``out``     — a static ``TensorSpec`` (shape + dtype). The leading
                  (batch) dim is the *example* batch used at trace time;
                  execution is batch-polymorphic and only trailing dims
                  are structural.

Parameters are ``ParamRef``s — paths into the model's params pytree, not
values — so one compiled plan serves any weights of the right shapes,
exactly like a bitstream serves any weight ROM contents.
"""
from __future__ import annotations

from dataclasses import dataclass, field, replace
from typing import Iterator

__all__ = ["TensorSpec", "ParamRef", "ShardingSpec", "Node", "InputNode",
           "Conv2DNode", "ReluNode", "MaxPool2Node", "FlattenNode",
           "DenseNode", "QuantizeNode", "FusedConvBlockNode", "AddNode",
           "MaxPoolNode", "GlobalAvgPoolNode", "BatchNormNode",
           "BatchNormFoldNode", "Graph"]


@dataclass(frozen=True)
class TensorSpec:
    """Static shape + dtype of one value in the graph."""

    shape: tuple[int, ...]
    dtype: str = "float32"

    def __str__(self) -> str:
        return f"{self.dtype}[{','.join(map(str, self.shape))}]"


@dataclass(frozen=True)
class ParamRef:
    """A path into the params pytree, e.g. ``("conv1", "w")``."""

    path: tuple[str, ...]
    shape: tuple[int, ...]
    dtype: str = "float32"

    def fetch(self, params):
        leaf = params
        for key in self.path:
            leaf = leaf[key]
        return leaf

    def __str__(self) -> str:
        return "/".join(self.path)


@dataclass(frozen=True)
class ShardingSpec:
    """Placement of one conv stage on a 2-D device mesh (DESIGN.md §9/§15).

    ``mode`` is the paper's §III.A channel-parallelism choice, in
    ``ChannelParallelism`` value spelling:

      * ``"output"`` — Eq. 6 / OCP: weights (and bias/requant scale)
        sharded on M over the ``model`` axis, no collective;
      * ``"input"``  — Eq. 7 / ICP: input channels sharded on N, one
        ring reduce combines the per-device partial accumulations;
      * ``"both"``   — the paper's composed §III.A design point: the
        ``model`` axis factors into an ``icp × ocp`` sub-grid, each
        device owning an (N/icp, M/ocp) weight block — the reduce runs
        over the (smaller) icp groups only;
      * ``"none"``   — replicated compute (data parallelism only).

    ``icp``/``ocp`` are the model-axis factors backing that choice
    (``icp * ocp`` must equal the model-axis extent). ``0`` means
    "derive from mode" — the pre-2-D encoding, where ``input`` meant
    the whole axis is ICP and ``output`` the whole axis is OCP; the
    placement pass always writes them explicitly now. Use ``split()``
    to resolve either form against a mesh.

    ``data`` opts the stage's batch dim into sharding over the ``data``
    axis (composes orthogonally with every channel mode). Set by the
    ``place_channel_parallel`` pass; ``None`` on a node means the graph
    was never placed and the stage executes single-device.
    """

    mode: str = "none"
    data: bool = True
    icp: int = 0
    ocp: int = 0

    def __post_init__(self):
        if self.mode not in ("none", "input", "output", "both"):
            raise ValueError(f"unknown sharding mode {self.mode!r}; "
                             "expected none|input|output|both")
        if self.icp < 0 or self.ocp < 0:
            raise ValueError(f"negative sharding factors "
                             f"icp={self.icp} ocp={self.ocp}")

    def split(self, model_size: int) -> tuple[int, int]:
        """Resolve the (icp, ocp) group sizes against a mesh's model-axis
        extent. Explicit factors win; legacy 1-D specs (factors unset)
        derive the whole axis from ``mode``."""
        if self.icp or self.ocp:
            return (max(self.icp, 1), max(self.ocp, 1))
        if self.mode == "input":
            return (model_size, 1)
        if self.mode == "output":
            return (1, model_size)
        return (1, 1)

    def __str__(self) -> str:
        if self.mode == "none":
            return "none"
        if self.mode == "both":
            return f"icp{self.icp}xocp{self.ocp}"
        return {"input": "icp", "output": "ocp"}[self.mode]


@dataclass(frozen=True)
class Node:
    """Base node: subclasses add op-specific static attributes."""

    id: int
    inputs: tuple[int, ...]
    out: TensorSpec

    @property
    def op(self) -> str:
        name = type(self).__name__
        if name.endswith("Node"):
            name = name[:-4]
        return getattr(self, "_opname", name.lower())

    def describe(self) -> str:
        return ""

    def pretty(self) -> str:
        args = ", ".join(f"%{i}" for i in self.inputs)
        extra = self.describe()
        extra = f" {extra}" if extra else ""
        return f"%{self.id} = {self.op}({args}){extra} -> {self.out}"


@dataclass(frozen=True)
class InputNode(Node):
    pass


@dataclass(frozen=True)
class Conv2DNode(Node):
    """conv2d + bias (paper C1/C3), weights by reference. ``padding`` =
    (ph, pw) zero rows/columns on each side of the input; (0, 0) is the
    paper's VALID conv. A conv whose batch norm was folded
    (``passes.fold_batch_norm``) reads its weight and bias from the
    ``BatchNormFoldNode``s at ``inputs[1:]``."""

    w: ParamRef = None
    b: ParamRef | None = None
    stride: tuple[int, int] = (1, 1)
    sharding: ShardingSpec | None = None
    padding: tuple[int, int] = (0, 0)

    def describe(self) -> str:
        shard = "" if self.sharding is None else f" shard={self.sharding}"
        pad = "" if self.padding == (0, 0) else \
            f" p={self.padding[0]}x{self.padding[1]}"
        return (f"w={self.w} k={self.w.shape[2]}x{self.w.shape[3]} "
                f"s={self.stride[0]}x{self.stride[1]}" + pad
                + ("" if self.b is None else f" b={self.b}") + shard)


@dataclass(frozen=True)
class ReluNode(Node):
    pass


@dataclass(frozen=True)
class MaxPool2Node(Node):
    """2×2/stride-2 max pool; ``odd`` per core.window.pool_output_size."""

    odd: str = "raise"

    def describe(self) -> str:
        return f"odd={self.odd}"


@dataclass(frozen=True)
class FlattenNode(Node):
    """(B, …) -> (B, prod(…)) — the conv→fc boundary."""


@dataclass(frozen=True)
class DenseNode(Node):
    """x @ w + b through the policy-aware ``repro.ops.dense``."""

    w: ParamRef = None
    b: ParamRef | None = None

    def describe(self) -> str:
        return f"w={self.w}" + ("" if self.b is None else f" b={self.b}")


@dataclass(frozen=True)
class QuantizeNode(Node):
    """An explicit quantization point, inserted by the lowering pass.

    ``kind``:
      * ``qformat``          — snap to the Qm.n lattice (paper C4);
      * ``int8_conv_weight`` — per-output-channel symmetric int8
                               fake-quant of a (M, N, Kh, Kw) conv weight;
      * ``int8_act``         — per-tensor int8 fake-quant of an activation.

    Dense weights get no QuantizeNode: the int8 dense path needs the real
    QTensor datapath (per-token activation scales + qmatmul), so its
    weight quantization folds in ``ExecutionPlan.bind`` instead.

    ``constant`` marks weight quantizations: their input is a ParamRef
    subgraph, so ``ExecutionPlan.bind`` folds them once instead of
    recomputing per batch (the scale constant-folding of DESIGN.md §8).
    """

    kind: str = "qformat"
    int_bits: int = 8
    frac_bits: int = 8
    constant: bool = False
    ref: ParamRef | None = None       # set when quantizing a weight directly

    def describe(self) -> str:
        fmt = (f" Q{self.int_bits}.{self.frac_bits}"
               if self.kind == "qformat" else "")
        src = f" ref={self.ref}" if self.ref is not None else ""
        return f"kind={self.kind}{fmt}{src}" + \
            (" const" if self.constant else "")


@dataclass(frozen=True)
class FusedConvBlockNode(Node):
    """conv + bias + relu + 2×2/2 maxpool as ONE stage — the paper's deep
    pipeline between layers (§III.B, Fig. 6/8): the pre-pool activation
    never exists as a whole tensor."""

    _opname = "fused_conv_block"

    w: ParamRef = None
    b: ParamRef | None = None
    stride: tuple[int, int] = (1, 1)
    odd: str = "raise"
    sharding: ShardingSpec | None = None

    def describe(self) -> str:
        shard = "" if self.sharding is None else f" shard={self.sharding}"
        return (f"w={self.w} k={self.w.shape[2]}x{self.w.shape[3]} "
                f"s={self.stride[0]}x{self.stride[1]} odd={self.odd}"
                + shard)


@dataclass(frozen=True)
class AddNode(Node):
    """Elementwise sum of two same-shaped values: the residual add where
    a block's branch meets its shortcut (the graph's fan-in)."""


@dataclass(frozen=True)
class MaxPoolNode(Node):
    """``window``×``window`` max pool, stride ``stride``, ``padding``
    rows/columns of -inf on each side (ResNet's 3×3/2 pad-1 stem pool;
    the paper's 2×2/2 pool is ``MaxPool2Node``)."""

    _opname = "max_pool"

    window: int = 3
    stride: int = 2
    padding: int = 1

    def describe(self) -> str:
        return f"k={self.window} s={self.stride} p={self.padding}"


@dataclass(frozen=True)
class GlobalAvgPoolNode(Node):
    """(B, C, H, W) -> (B, C): the mean of each channel's map."""

    _opname = "global_avg_pool"


@dataclass(frozen=True)
class BatchNormNode(Node):
    """Inference batch norm over channels (axis 1): (x - mean) ·
    gamma / sqrt(var + eps) + beta, from the running statistics. Traced
    only: ``passes.fold_batch_norm`` folds it into the conv before it."""

    _opname = "batch_norm"

    gamma: ParamRef = None
    beta: ParamRef = None
    mean: ParamRef = None
    var: ParamRef = None
    eps: float = 1e-5

    def describe(self) -> str:
        return f"gamma={self.gamma} eps={self.eps:g}"


@dataclass(frozen=True)
class BatchNormFoldNode(Node):
    """A constant (no inputs): the weight (``part="w"``) or the bias
    (``part="b"``) of conv weight ``w`` and bias ``b`` (None: zero) with
    the batch norm after it folded in. ``ExecutionPlan.bind`` computes it
    once, like a constant quantize, so no batch norm runs per batch."""

    _opname = "bn_fold"

    part: str = "w"
    w: ParamRef = None
    b: ParamRef | None = None
    gamma: ParamRef = None
    beta: ParamRef = None
    mean: ParamRef = None
    var: ParamRef = None
    eps: float = 1e-5

    def describe(self) -> str:
        return f"{self.part} of w={self.w} gamma={self.gamma}"


@dataclass(frozen=True)
class Graph:
    """An ordered (topological) operator graph with one input and one
    output; a value may feed several nodes (fan-out) and an ``AddNode``
    reads two (fan-in). Passes are Graph -> Graph; nodes are
    immutable."""

    nodes: tuple[Node, ...]
    input_id: int = 0
    output_id: int = 0

    def __post_init__(self):
        object.__setattr__(self, "nodes", tuple(self.nodes))

    def __iter__(self) -> Iterator[Node]:
        return iter(self.nodes)

    def __len__(self) -> int:
        return len(self.nodes)

    def node(self, nid: int) -> Node:
        for n in self.nodes:
            if n.id == nid:
                return n
        raise KeyError(f"no node %{nid} in graph")

    def consumers(self, nid: int) -> list[Node]:
        return [n for n in self.nodes if nid in n.inputs]

    def ops(self) -> list[str]:
        return [n.op for n in self.nodes]

    def next_id(self) -> int:
        return max(n.id for n in self.nodes) + 1

    def validate(self) -> "Graph":
        """Check topological order, id uniqueness, input/output wiring."""
        seen: set[int] = set()
        for n in self.nodes:
            if n.id in seen:
                raise ValueError(f"duplicate node id %{n.id}")
            for i in n.inputs:
                if i not in seen:
                    raise ValueError(
                        f"%{n.id} ({n.op}) consumes %{i} before definition")
            seen.add(n.id)
        if self.input_id not in seen or self.output_id not in seen:
            raise ValueError("input/output id not in graph")
        return self

    def pretty(self) -> str:
        return "\n".join(n.pretty() for n in self.nodes)

    # ---------- rewrite helpers for passes ----------
    def replace_input(self, old: int, new: int) -> "Graph":
        """Rewire every consumer of %old to read %new (used when a pass
        deletes %old)."""
        nodes = tuple(
            replace(n, inputs=tuple(new if i == old else i
                                    for i in n.inputs))
            for n in self.nodes)
        out = new if self.output_id == old else self.output_id
        return replace(self, nodes=nodes, output_id=out)
