"""Content fingerprints for compiled plans (DESIGN.md §12).

A plan artifact is only safe to reuse if *everything* that shaped the
compiled program is part of its identity. The fingerprint is a sha256
over a canonical JSON document covering

  * the compiled graph IR (fusion, quantization lowering, and
    ShardingSpec placement included — ``ir_codec.graph_to_doc``),
  * the baked quantization mode + ``QFormat`` lattice,
  * the ExecPolicy essentials (compile policy and bind policy: backend,
    quant, tiling overrides, channel_parallel, interpret, autotune),
  * the mesh shape (axis names × sizes) or None,
  * the bind-time tuned tiles (``BoundPlan.tuned``),
  * the weight content (a digest over every params leaf: path, dtype,
    shape, raw bytes),
  * the artifact schema version and the jax/repro versions.

Changing any of these — retrained weights, a different quant mode, new
autotuned tiles, another mesh — yields a distinct fingerprint, so a
replica can never silently serve a stale artifact
(``tests/test_artifact.py`` pins this). The document is deterministic
(sorted keys, integer ids from the tracer's creation order, no floats
except tile integers), so the same model + policy + mesh fingerprints
identically across processes and hosts.
"""
from __future__ import annotations

import hashlib
import json

import jax
import numpy as np

from repro.artifact.ir_codec import graph_to_doc
from repro.core.quantize import QFormat
from repro.ops.policy import ExecPolicy

__all__ = ["SCHEMA_VERSION", "REPRO_PLAN_VERSION", "params_digest",
           "policy_to_doc", "policy_from_doc", "mesh_shape_doc",
           "fingerprint_doc", "plan_fingerprint"]

# version of the on-disk artifact schema (manifest layout + payload
# naming). Bumped when the format changes; loaders refuse other versions
# and the caller falls back to the fresh pipeline.
# v2: conv and fused-conv nodes no longer carry a ``tiling`` key.
SCHEMA_VERSION = 2

# version of the *semantics* a plan encodes (executor calling
# conventions, pass meanings). Part of the fingerprint so a plan written
# by an incompatible build never matches.
# v2: 2-D (icp x ocp) placement + ring-reduce collectives + data-axis
# batch scatter (DESIGN.md §15) changed the sharded executor's program.
REPRO_PLAN_VERSION = 2


def params_digest(params) -> str:
    """sha256 over every leaf of a params pytree: key path, dtype, shape,
    raw bytes — sorted by path so dict ordering never leaks in."""
    leaves = []
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        key = "/".join(_path_str(p) for p in path)
        leaves.append((key, np.asarray(jax.device_get(leaf))))
    h = hashlib.sha256()
    for key, arr in sorted(leaves):
        h.update(key.encode())
        h.update(str(arr.dtype).encode())
        h.update(str(arr.shape).encode())
        h.update(np.ascontiguousarray(arr).tobytes())
    return h.hexdigest()


def _path_str(p) -> str:
    if hasattr(p, "key"):
        return str(p.key)
    if hasattr(p, "idx"):
        return str(p.idx)
    return str(p)


def policy_to_doc(policy: ExecPolicy | None) -> dict | None:
    if policy is None:
        return None
    return {
        "backend": policy.backend,
        "quant": policy.quant,
        "qformat": [policy.qformat.int_bits, policy.qformat.frac_bits],
        "interpret": policy.interpret,
        "tiling": [[k, int(v)] for k, v in policy.tiling],
        "channel_parallel": policy.channel_parallel,
        "autotune": bool(policy.autotune),
    }


def policy_from_doc(doc: dict | None) -> ExecPolicy | None:
    if doc is None:
        return None
    return ExecPolicy(
        backend=doc["backend"], quant=doc["quant"],
        qformat=QFormat(*doc["qformat"]), interpret=doc["interpret"],
        tiling=tuple((k, int(v)) for k, v in doc["tiling"]),
        channel_parallel=doc["channel_parallel"],
        autotune=bool(doc["autotune"]))


def mesh_shape_doc(mesh) -> list | None:
    """Mesh identity = (axis name, size) pairs in axis order. Device ids
    are deliberately NOT part of it: an artifact restores onto any host
    with enough devices (like the elastic checkpoint restore)."""
    if mesh is None:
        return None
    return [[name, int(size)] for name, size in
            zip(mesh.axis_names, mesh.devices.shape)]


def fingerprint_doc(plan, *, params=None, tuned=None,
                    bind_policy=None) -> dict:
    """The canonical identity document for one (optionally bound) plan."""
    return {
        "repro_plan_version": REPRO_PLAN_VERSION,
        "jax_version": jax.__version__,
        "graph": graph_to_doc(plan.graph),
        "quant": plan.quant,
        "qformat": [plan.qformat.int_bits, plan.qformat.frac_bits],
        "compile_policy": policy_to_doc(plan.compile_policy),
        "bind_policy": policy_to_doc(bind_policy),
        "mesh": mesh_shape_doc(plan.mesh),
        "tuned": {str(int(k)): {kk: int(vv) for kk, vv in sorted(v.items())}
                  for k, v in sorted((tuned or {}).items())},
        "params_digest": None if params is None else params_digest(params),
    }


def plan_fingerprint(plan, *, params=None, tuned=None,
                     bind_policy=None) -> str:
    """sha256 hex of the canonical identity document. Works on an
    ``ExecutionPlan`` (pass ``params``/``tuned`` explicitly) or via
    ``BoundPlan.fingerprint()`` which supplies its own."""
    doc = fingerprint_doc(plan, params=params, tuned=tuned,
                          bind_policy=bind_policy)
    blob = json.dumps(doc, sort_keys=True, separators=(",", ":"))
    return hashlib.sha256(blob.encode()).hexdigest()
