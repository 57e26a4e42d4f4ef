"""Tracer: lift a core.conv-based model into the repro.graph IR.

``trace(model, input_shape)`` runs the model's ``forward`` once with a
``TracedArray`` in place of the image batch and a params pytree of
``ParamRef`` leaves (built shape-only via ``jax.eval_shape`` — no weights
are materialized). The repo's functional layer is duck-type hooked:

  * ``core.conv.conv2d_apply``   checks for ``graph_conv2d`` on its input,
  * ``core.window.maxpool2``     checks for ``graph_maxpool2``,
  * the ``relu`` / ``flatten`` / ``dense`` / ``batch_norm`` / ``add`` /
    ``max_pool`` / ``global_avg_pool`` wrappers below record nodes for a
    ``TracedArray`` and compute for real arrays — so one ``forward`` body
    is both the eager model and the graph program (DESIGN.md §8). A
    value used twice (a residual block's input) fans out; ``add`` fans
    in.

Shape inference happens during tracing (conv/pool output sizes via the
paper's Eq. 1–2 helpers), so a model whose sizing is inconsistent — e.g. a
2×2 pool over an odd feature map under ``odd="raise"`` — fails at *compile*
time, like an FPGA design failing synthesis rather than misbehaving on
silicon.
"""
from __future__ import annotations

from dataclasses import dataclass, field

import jax
import numpy as np

from repro.core.window import conv_output_size, pool_output_size
from repro.graph.ir import (AddNode, BatchNormNode, Conv2DNode, DenseNode,
                            FlattenNode, GlobalAvgPoolNode, Graph,
                            InputNode, MaxPool2Node, MaxPoolNode, Node,
                            ParamRef, ReluNode, TensorSpec)

__all__ = ["TracedArray", "GraphBuilder", "param_refs", "trace",
           "relu", "flatten", "dense", "batch_norm", "add", "max_pool",
           "global_avg_pool"]


@dataclass
class GraphBuilder:
    """Accumulates nodes in creation (= topological) order."""

    nodes: list[Node] = field(default_factory=list)

    def add(self, cls, inputs: tuple[int, ...], out: TensorSpec,
            **attrs) -> "TracedArray":
        node = cls(id=len(self.nodes), inputs=inputs, out=out, **attrs)
        self.nodes.append(node)
        return TracedArray(self, node.id, out)

    def input(self, spec: TensorSpec) -> "TracedArray":
        return self.add(InputNode, (), spec)

    def finish(self, output: "TracedArray") -> Graph:
        return Graph(nodes=tuple(self.nodes), input_id=0,
                     output_id=output.node_id).validate()


@dataclass
class TracedArray:
    """The symbolic value flowing through ``forward`` during tracing.

    Carries only a static ``TensorSpec``; the ``graph_*`` methods are the
    duck-typed hooks the functional layer dispatches on.
    """

    builder: GraphBuilder
    node_id: int
    spec: TensorSpec

    @property
    def shape(self) -> tuple[int, ...]:
        return self.spec.shape

    @property
    def ndim(self) -> int:
        return len(self.spec.shape)

    @property
    def dtype(self) -> str:
        return self.spec.dtype

    def _emit(self, cls, out_shape: tuple[int, ...], **attrs):
        return self.builder.add(cls, (self.node_id,),
                                TensorSpec(tuple(out_shape), self.dtype),
                                **attrs)

    # ---------- hooks the functional layer dispatches on ----------
    def graph_conv2d(self, params: dict, cfg) -> "TracedArray":
        w: ParamRef = params["w"]
        b: ParamRef | None = params.get("b")
        bsz, n, h, wd = self.shape
        m, n2, kh, kw = w.shape
        if n != n2:
            raise ValueError(f"conv2d: input has {n} channels, weight "
                             f"{w} expects {n2}")
        ph, pw = cfg.padding
        ho = conv_output_size(h + 2 * ph, kh, cfg.stride[0])
        wo = conv_output_size(wd + 2 * pw, kw, cfg.stride[1])
        return self._emit(Conv2DNode, (bsz, m, ho, wo), w=w, b=b,
                          stride=tuple(cfg.stride), padding=(ph, pw))

    def graph_maxpool2(self, *, odd: str = "raise") -> "TracedArray":
        bsz, c, h, w = self.shape
        out = (bsz, c, pool_output_size(h, odd), pool_output_size(w, odd))
        return self._emit(MaxPool2Node, out, odd=odd)

    def graph_relu(self) -> "TracedArray":
        return self._emit(ReluNode, self.shape)

    def graph_flatten(self) -> "TracedArray":
        bsz = self.shape[0]
        return self._emit(FlattenNode,
                          (bsz, int(np.prod(self.shape[1:]))))

    def graph_dense(self, w: ParamRef,
                    b: ParamRef | None = None) -> "TracedArray":
        k, n = w.shape
        if self.shape[-1] != k:
            raise ValueError(f"dense: input dim {self.shape[-1]} vs "
                             f"weight {w} dim {k}")
        return self._emit(DenseNode, (*self.shape[:-1], n), w=w, b=b)

    def graph_batch_norm(self, params: dict, eps: float) -> "TracedArray":
        if params["gamma"].shape != (self.shape[1],):
            raise ValueError(f"batch_norm: {self.shape[1]} channels, "
                             f"gamma {params['gamma']} is "
                             f"{params['gamma'].shape}")
        return self._emit(BatchNormNode, self.shape, gamma=params["gamma"],
                          beta=params["beta"], mean=params["mean"],
                          var=params["var"], eps=float(eps))

    def graph_add(self, other: "TracedArray") -> "TracedArray":
        if other.builder is not self.builder or other.spec != self.spec:
            raise ValueError(f"add: {self.spec} and {other.spec} are not "
                             f"two values of one graph with one shape")
        return self.builder.add(AddNode, (self.node_id, other.node_id),
                                self.spec)

    def graph_max_pool(self, window: int, stride: int,
                       padding: int) -> "TracedArray":
        bsz, c, h, w = self.shape
        ho = conv_output_size(h + 2 * padding, window, stride)
        wo = conv_output_size(w + 2 * padding, window, stride)
        return self._emit(MaxPoolNode, (bsz, c, ho, wo), window=window,
                          stride=stride, padding=padding)

    def graph_global_avg_pool(self) -> "TracedArray":
        return self._emit(GlobalAvgPoolNode, self.shape[:2])


# ------------------------------------------------------ functional layer
# Trace-aware wrappers shared by eager execution and tracing. conv2d and
# maxpool2 are hooked at their core definitions (core.conv / core.window);
# these three cover the glue that previously lived inline in model code.

def relu(x):
    """jax.nn.relu, or a Relu node when tracing."""
    hook = getattr(x, "graph_relu", None)
    return hook() if hook is not None else jax.nn.relu(x)


def flatten(x):
    """(B, …) -> (B, -1), or a Flatten node when tracing."""
    hook = getattr(x, "graph_flatten", None)
    return hook() if hook is not None else x.reshape(x.shape[0], -1)


def dense(x, w, b=None, *, policy=None):
    """Policy-aware dense (repro.ops.dense), or a Dense node when
    tracing."""
    hook = getattr(x, "graph_dense", None)
    if hook is not None:
        return hook(w, b)
    from repro.ops import dense as op
    return op(x, w, b, policy=policy)


def batch_norm(x, params: dict, *, eps: float):
    """Inference batch norm over channels (axis 1) from the running
    statistics in ``params`` (gamma, beta, mean, var), or a BatchNorm node
    when tracing (the compiled plan folds it into the conv before it)."""
    hook = getattr(x, "graph_batch_norm", None)
    if hook is not None:
        return hook(params, eps)
    scale = params["gamma"] * jax.lax.rsqrt(params["var"] + eps)
    shift = params["beta"] - params["mean"] * scale
    return x * scale[:, None, None] + shift[:, None, None]


def add(x, y):
    """x + y, or an Add node (fan-in) when tracing."""
    hook = getattr(x, "graph_add", None)
    return hook(y) if hook is not None else x + y


def max_pool(x, window: int, stride: int, padding: int):
    """NCHW max pool over ``window``×``window`` windows at ``stride``,
    the borders padded with -inf, or a MaxPool node when tracing."""
    hook = getattr(x, "graph_max_pool", None)
    if hook is not None:
        return hook(window, stride, padding)
    pad = ((0, 0), (0, 0), (padding, padding), (padding, padding))
    return jax.lax.reduce_window(x, -np.inf, jax.lax.max,
                                 (1, 1, window, window),
                                 (1, 1, stride, stride), pad)


def global_avg_pool(x):
    """(B, C, H, W) -> (B, C) channel means, or a GlobalAvgPool node when
    tracing."""
    hook = getattr(x, "graph_global_avg_pool", None)
    return hook() if hook is not None else x.mean(axis=(2, 3))


# ---------------------------------------------------------------- trace

def _key_name(entry) -> str:
    for attr in ("key", "idx", "name"):
        if hasattr(entry, attr):
            return str(getattr(entry, attr))
    return str(entry)


def param_refs(model) -> dict:
    """The model's params pytree with every leaf replaced by a ParamRef
    (shape-only: ``jax.eval_shape`` never touches device memory)."""
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: ParamRef(
            path=tuple(_key_name(p) for p in path),
            shape=tuple(leaf.shape), dtype=str(leaf.dtype)),
        shapes)


def trace(model, input_shape: tuple[int, ...],
          dtype: str = "float32") -> Graph:
    """Lift ``model.forward`` into a Graph.

    ``input_shape`` is an example (B, C, H, W); the traced batch dim is
    informational — execution is batch-polymorphic.
    """
    refs = param_refs(model)
    builder = GraphBuilder()
    x = builder.input(TensorSpec(tuple(input_shape), dtype))
    out = model.forward(refs, x)
    if not isinstance(out, TracedArray):
        raise TypeError(
            f"{type(model).__name__}.forward returned {type(out).__name__} "
            f"under tracing — its ops must route through the hooked "
            f"functional layer (conv2d_apply, maxpool2, relu, flatten, "
            f"dense, batch_norm, add, max_pool, global_avg_pool)")
    return builder.finish(out)
