"""Graph IR ↔ JSON codec for plan artifacts (DESIGN.md §12).

The artifact store persists a *compiled* graph — fusion, quantization
lowering, and channel-parallel placement already applied — so a replica
reconstructs its ``ExecutionPlan`` by decoding nodes, never by re-running
trace or the pass pipeline. The encoding is canonical (sorted keys, no
float formatting, ids kept verbatim) so the same document doubles as the
fingerprint payload: two plans hash equal iff their decoded graphs are
equal (``Graph`` is a frozen dataclass, so equality is structural).

Every node type carries exactly its dataclass fields; an unknown ``op``
on decode raises ``ValueError`` — the store maps that to the
schema-mismatch arm of the fallback ladder (a newer build wrote a node
kind this build cannot execute).
"""
from __future__ import annotations

import dataclasses

from repro.graph.ir import (AddNode, BatchNormFoldNode, BatchNormNode,
                            Conv2DNode, DenseNode, FlattenNode,
                            FusedConvBlockNode, GlobalAvgPoolNode, Graph,
                            InputNode, MaxPool2Node, MaxPoolNode, ParamRef,
                            QuantizeNode, ReluNode, ShardingSpec, TensorSpec)

__all__ = ["graph_to_doc", "graph_from_doc"]

_NODE_TYPES = {
    "input": InputNode,
    "conv2d": Conv2DNode,
    "relu": ReluNode,
    "maxpool2": MaxPool2Node,
    "flatten": FlattenNode,
    "dense": DenseNode,
    "quantize": QuantizeNode,
    "fused_conv_block": FusedConvBlockNode,
    "add": AddNode,
    "max_pool": MaxPoolNode,
    "global_avg_pool": GlobalAvgPoolNode,
    "batch_norm": BatchNormNode,
    "bn_fold": BatchNormFoldNode,
}
# node kinds whose attributes are plain numbers, strings and ParamRefs:
# each attribute is stored under its field name
_PLAIN = (AddNode, MaxPoolNode, GlobalAvgPoolNode, BatchNormNode,
          BatchNormFoldNode)


def _attrs(cls) -> list[str]:
    return [f.name for f in dataclasses.fields(cls)
            if f.name not in ("id", "inputs", "out")]


def _spec_doc(spec: TensorSpec) -> dict:
    return {"shape": list(spec.shape), "dtype": spec.dtype}


def _spec_from(doc: dict) -> TensorSpec:
    return TensorSpec(shape=tuple(doc["shape"]), dtype=doc["dtype"])


def _ref_doc(ref: ParamRef | None) -> dict | None:
    if ref is None:
        return None
    return {"path": list(ref.path), "shape": list(ref.shape),
            "dtype": ref.dtype}


def _ref_from(doc: dict | None) -> ParamRef | None:
    if doc is None:
        return None
    return ParamRef(path=tuple(doc["path"]), shape=tuple(doc["shape"]),
                    dtype=doc["dtype"])


def _shard_doc(spec: ShardingSpec | None) -> dict | None:
    if spec is None:
        return None
    return {"mode": spec.mode, "data": bool(spec.data),
            "icp": int(spec.icp), "ocp": int(spec.ocp)}


def _shard_from(doc: dict | None) -> ShardingSpec | None:
    if doc is None:
        return None
    # icp/ocp absent in pre-§15 artifacts: 0 = derive from mode
    return ShardingSpec(mode=doc["mode"], data=bool(doc["data"]),
                        icp=int(doc.get("icp", 0)),
                        ocp=int(doc.get("ocp", 0)))


def _node_doc(node) -> dict:
    doc = {"op": node.op, "id": int(node.id),
           "inputs": [int(i) for i in node.inputs],
           "out": _spec_doc(node.out)}
    if isinstance(node, (Conv2DNode, FusedConvBlockNode)):
        doc.update(w=_ref_doc(node.w), b=_ref_doc(node.b),
                   stride=list(node.stride),
                   sharding=_shard_doc(node.sharding))
        if isinstance(node, FusedConvBlockNode):
            doc["odd"] = node.odd
        elif node.padding != (0, 0):    # VALID convs keep their old doc
            doc["padding"] = list(node.padding)
    elif isinstance(node, _PLAIN):
        for name in _attrs(type(node)):
            val = getattr(node, name)
            doc[name] = _ref_doc(val) if isinstance(val, ParamRef) or \
                val is None else val
    elif isinstance(node, MaxPool2Node):
        doc["odd"] = node.odd
    elif isinstance(node, DenseNode):
        doc.update(w=_ref_doc(node.w), b=_ref_doc(node.b))
    elif isinstance(node, QuantizeNode):
        doc.update(kind=node.kind, int_bits=int(node.int_bits),
                   frac_bits=int(node.frac_bits),
                   constant=bool(node.constant), ref=_ref_doc(node.ref))
    return doc


def _node_from(doc: dict):
    cls = _NODE_TYPES.get(doc.get("op"))
    if cls is None:
        raise ValueError(f"unknown graph node op {doc.get('op')!r} "
                         f"(artifact written by a newer build?)")
    kw = dict(id=int(doc["id"]), inputs=tuple(doc["inputs"]),
              out=_spec_from(doc["out"]))
    if cls in (Conv2DNode, FusedConvBlockNode):
        kw.update(w=_ref_from(doc["w"]), b=_ref_from(doc["b"]),
                  stride=tuple(doc["stride"]),
                  sharding=_shard_from(doc.get("sharding")))
        if cls is FusedConvBlockNode:
            kw["odd"] = doc["odd"]
        else:
            kw["padding"] = tuple(doc.get("padding", (0, 0)))
    elif cls in _PLAIN:
        for name in _attrs(cls):
            val = doc[name]
            kw[name] = _ref_from(val) if isinstance(val, dict) or \
                val is None else val
    elif cls is MaxPool2Node:
        kw["odd"] = doc["odd"]
    elif cls is DenseNode:
        kw.update(w=_ref_from(doc["w"]), b=_ref_from(doc["b"]))
    elif cls is QuantizeNode:
        kw.update(kind=doc["kind"], int_bits=int(doc["int_bits"]),
                  frac_bits=int(doc["frac_bits"]),
                  constant=bool(doc["constant"]),
                  ref=_ref_from(doc.get("ref")))
    return cls(**kw)


def graph_to_doc(graph: Graph) -> dict:
    """Canonical JSON-able document for a (possibly lowered/placed)
    graph."""
    return {"input_id": int(graph.input_id),
            "output_id": int(graph.output_id),
            "nodes": [_node_doc(n) for n in graph]}


def graph_from_doc(doc: dict) -> Graph:
    """Decode and re-validate; raises ``ValueError``/``KeyError`` on any
    structural problem (callers map that to the fallback ladder)."""
    return Graph(nodes=tuple(_node_from(n) for n in doc["nodes"]),
                 input_id=int(doc["input_id"]),
                 output_id=int(doc["output_id"])).validate()
