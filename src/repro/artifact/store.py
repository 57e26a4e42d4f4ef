"""The plan artifact store: persist compiled plans + AOT executables
(DESIGN.md §12).

The paper's datapath is a *synthesis artifact*: the expensive design
work (structure, number format, placement, tile sizing) happens once,
and every deployed board just flashes the result. This module gives the
software pipeline the same property — the third persistence layer after
the tuning cache (§10) and checkpoints (§4), and the one that makes
horizontal scale cheap: a replica boots by **reading**, not deriving.

On-disk artifact (a directory, written atomically via tmp + rename):

    manifest.json   schema version, content fingerprint, graph IR doc,
                    quant/QFormat, ExecPolicy docs, mesh shape, baked
                    tuned tiles, tuning-cache rows for the plan's
                    stages, params digest, payload + AOT indexes
    payloads.npz    params pytree leaves + the bind-folded weight
                    quantization (QTensor codes/scales, qformat arrays)
    aot/<i>.bin     serialized XLA executables, one per compiled input
                    shape (jax AOT ``lower().compile()`` at save time)

``load_plan`` reconstructs a ``BoundPlan`` without re-tracing,
re-running passes, re-placing, or re-tuning: the graph decodes from the
manifest, folded weights come off disk, mesh placement is re-derived as
pure ``device_put``s (the one-time weight-ROM flash), and executables
deserialize instead of compiling.

Fallback ladder (every rung warns, no rung crashes the boot):

  1. full hit       — plan + folded weights + AOT executable restored;
  2. AOT miss       — backend/jax/device mismatch or missing shape:
                      keep the restored plan, compile from IR;
  3. artifact miss  — schema version mismatch, corrupt manifest/payload,
                      fingerprint mismatch, stale params: ``PlanStore``
                      returns None and the caller runs the fresh
                      trace → fuse → place → tune → compile pipeline.
"""
from __future__ import annotations

import json
import os
import pathlib
import tempfile
import warnings
from dataclasses import dataclass, field

import jax
import numpy as np

from repro.artifact import warmup
from repro.artifact.aot import (AOTMismatchError, aot_compile,
                                cache_executable, cached_executable,
                                deserialize_compiled, executable_key,
                                serialize_compiled)
from repro.artifact.fingerprint import (SCHEMA_VERSION, fingerprint_doc,
                                        mesh_shape_doc, params_digest,
                                        plan_fingerprint, policy_from_doc,
                                        policy_to_doc)
from repro.artifact.ir_codec import graph_from_doc, graph_to_doc
from repro.core.quantize import QFormat, QTensor

__all__ = ["ArtifactError", "ArtifactStaleError", "PlanArtifact",
           "save_plan", "load_plan", "PlanStore", "compile_program",
           "MANIFEST", "PAYLOADS"]

MANIFEST = "manifest.json"
PAYLOADS = "payloads.npz"


class ArtifactError(RuntimeError):
    """Artifact unusable (corrupt, unknown schema, wrong environment) —
    callers warn and fall back to the fresh compile pipeline."""


class ArtifactStaleError(ArtifactError):
    """Artifact is internally consistent but does not match the serving
    state (different weights) — reuse would silently serve stale math."""


# ---------------------------------------------------------------------------
# payload (de)flattening

def _flatten_params(params) -> dict[str, np.ndarray]:
    flat = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        keys = []
        for p in path:
            if not hasattr(p, "key"):
                raise ArtifactError(
                    f"plan artifacts require a dict-keyed params pytree; "
                    f"got path entry {p!r}")
            keys.append(str(p.key))
        flat["/".join(keys)] = np.asarray(jax.device_get(leaf))
    return flat


def _unflatten_params(flat: dict[str, np.ndarray]) -> dict:
    params: dict = {}
    for key, arr in flat.items():
        node = params
        parts = key.split("/")
        for k in parts[:-1]:
            node = node.setdefault(k, {})
        node[parts[-1]] = jax.numpy.asarray(arr)
    return params


def _payload_arrays(params, folded) -> tuple[dict, dict]:
    """-> ({npz key: array}, folded-kind index {node id: kind})."""
    arrays = {f"params/{k}": v for k, v in _flatten_params(params).items()}
    kinds: dict[str, str] = {}
    for nid, val in folded.items():
        if isinstance(val, QTensor):
            kinds[str(int(nid))] = "qtensor"
            arrays[f"folded/{int(nid)}.codes"] = np.asarray(
                jax.device_get(val.codes))
            arrays[f"folded/{int(nid)}.scale"] = np.asarray(
                jax.device_get(val.scale))
        else:
            kinds[str(int(nid))] = "array"
            arrays[f"folded/{int(nid)}.array"] = np.asarray(
                jax.device_get(val))
    return arrays, kinds


def _load_payloads(path: pathlib.Path, kinds: dict) -> tuple[dict, dict]:
    with np.load(path, allow_pickle=False) as data:
        raw = {k: data[k] for k in data.files}
    params = _unflatten_params(
        {k[len("params/"):]: v for k, v in raw.items()
         if k.startswith("params/")})
    folded: dict = {}
    for nid_s, kind in kinds.items():
        nid = int(nid_s)
        if kind == "qtensor":
            folded[nid] = QTensor(
                jax.numpy.asarray(raw[f"folded/{nid}.codes"]),
                jax.numpy.asarray(raw[f"folded/{nid}.scale"]))
        elif kind == "array":
            folded[nid] = jax.numpy.asarray(raw[f"folded/{nid}.array"])
        else:
            raise ArtifactError(f"unknown folded payload kind {kind!r}")
    return params, folded


def _rebuild_mesh(doc):
    if doc is None:
        return None
    names = tuple(name for name, _ in doc)
    sizes = tuple(int(size) for _, size in doc)
    need = int(np.prod(sizes))
    devs = jax.devices()
    if len(devs) < need:
        raise ArtifactError(
            f"plan was compiled for mesh {dict(doc)} ({need} devices) but "
            f"this process has {len(devs)}")
    from jax.sharding import Mesh
    return Mesh(np.asarray(devs[:need]).reshape(sizes), names)


# ---------------------------------------------------------------------------
# tuning-cache interop (DESIGN.md §10 ↔ §12)

def _stage_signatures(bound) -> list[tuple[str, tuple, object]]:
    """(op, shape signature, dtype) per tunable stage — the tuning-cache
    keys the plan's kernels resolve through."""
    from repro.ops.tiling import conv_signature
    sigs = []
    for _, op, args, kw in bound.plan._stage_calls(bound.params,
                                                   bound.folded):
        if op == "qmatmul":
            m, k = args[0].shape
            sigs.append((op, (int(m), int(k), int(args[1].shape[1])),
                         args[0].dtype))
        else:
            sigs.append((op, conv_signature(
                args[0].shape, args[1].shape,
                tuple(kw.get("stride", (1, 1)))), args[0].dtype))
    return sigs


def _export_stage_rows(bound) -> list[dict]:
    """Snapshot the TUNING_CACHE entries covering this plan's stages so a
    replica that has to compile from IR (AOT miss) still resolves the
    measured tiles instead of re-tuning or falling to heuristics."""
    from repro.ops.tiling import TUNING_CACHE
    rows, seen = [], set()
    for op, sig, dtype in _stage_signatures(bound):
        hit = TUNING_CACHE.get(op, sig, dtype)
        key = TUNING_CACHE.key(op, sig, dtype)
        if hit and key not in seen:
            seen.add(key)
            rows.append({"op": op, "shape": list(key[1]), "dtype": key[2],
                         "platform": key[3], "params": hit})
    return rows


def _batch_sharding(plan, input_shape):
    """The data-axis input placement AOT programs are lowered with
    (DESIGN.md §15): batches split over ``data`` when the plan's mesh has
    that axis and the static batch divides it, else None (replicated —
    the pre-2-D behavior). Must agree with ``ExecutionPlan._scatter`` so
    a restored executable accepts the batches the engine places."""
    mesh = getattr(plan, "mesh", None)
    if mesh is None or "data" not in mesh.axis_names:
        return None
    if not input_shape or input_shape[0] % mesh.shape["data"]:
        return None
    from jax.sharding import NamedSharding, PartitionSpec as P
    return NamedSharding(
        mesh, P("data", *[None] * (len(input_shape) - 1)))


def compile_program(bound, input_shape, dtype="float32"):
    """AOT-compile ``bound`` for one batch shape as the function
    ``vision_b<batch>``, lowered with the data-axis input placement. jit
    names the module after the function (``jit_vision_b<batch>``), so a
    profiler trace tells the bucket programs apart."""
    def program(x):
        return bound(x)

    program.__name__ = program.__qualname__ = f"vision_b{input_shape[0]}"
    return aot_compile(program, input_shape, dtype,
                       sharding=_batch_sharding(bound.plan, input_shape))


# ---------------------------------------------------------------------------
# save

def save_plan(bound, path, *, input_shapes=None, aot: bool = True) -> str:
    """Persist a ``BoundPlan`` as a versioned artifact directory; returns
    the content fingerprint.

    ``input_shapes``: the static input shapes to AOT-compile executables
    for (default: the traced input shape). ``aot=False`` skips the
    executable payloads — the artifact then boots via compile-from-IR
    (still no trace/fuse/place/tune).
    """
    plan = bound.plan
    path = pathlib.Path(path)
    path.parent.mkdir(parents=True, exist_ok=True)
    if input_shapes is None:
        input_shapes = (plan.graph.node(plan.graph.input_id).out.shape,)

    fp = plan_fingerprint(plan, params=bound.params, tuned=bound.tuned,
                          bind_policy=bound.policy)
    arrays, folded_kinds = _payload_arrays(bound.params, bound.folded)

    aot_index: dict[str, str] = {}
    aot_blobs: list[bytes] = []
    if aot:
        for shape in input_shapes:
            compiled = compile_program(bound, shape)
            blob = serialize_compiled(compiled)
            if blob is None:        # backend can't serialize: IR-only
                aot_index.clear()
                aot_blobs.clear()
                break
            key = _aot_key(shape)
            aot_index[key] = f"aot/{len(aot_blobs)}.bin"
            aot_blobs.append(blob)
            # the save-time compile is also the process's warm program
            cache_executable(executable_key(fp, shape), compiled)

    manifest = {
        "schema_version": SCHEMA_VERSION,
        "fingerprint": fp,
        "platform": jax.default_backend(),
        "jax_version": jax.__version__,
        "quant": plan.quant,
        "qformat": [plan.qformat.int_bits, plan.qformat.frac_bits],
        "compile_policy": policy_to_doc(plan.compile_policy),
        "bind_policy": policy_to_doc(bound.policy),
        "mesh": mesh_shape_doc(plan.mesh),
        "graph": graph_to_doc(plan.graph),
        "tuned": {str(int(k)): {kk: int(vv) for kk, vv in v.items()}
                  for k, v in bound.tuned.items()},
        "tuning_cache": _export_stage_rows(bound),
        "params_digest": params_digest(bound.params),
        "folded": folded_kinds,
        "aot": aot_index,
    }

    tmp = pathlib.Path(tempfile.mkdtemp(dir=path.parent, prefix=".tmp_"))
    try:
        np.savez(tmp / PAYLOADS, **arrays)
        # np.savez may append .npz — normalize
        if not (tmp / PAYLOADS).exists():       # pragma: no cover
            os.replace(tmp / (PAYLOADS + ".npz"), tmp / PAYLOADS)
        if aot_blobs:
            (tmp / "aot").mkdir()
            for i, blob in enumerate(aot_blobs):
                (tmp / "aot" / f"{i}.bin").write_bytes(blob)
        (tmp / MANIFEST).write_text(json.dumps(manifest, indent=1,
                                               sort_keys=True) + "\n")
        if path.exists():
            import shutil
            shutil.rmtree(path)
        os.replace(tmp, path)
    finally:
        if tmp.exists():
            import shutil
            shutil.rmtree(tmp, ignore_errors=True)
    return fp


def _aot_key(shape, dtype="float32") -> str:
    return "x".join(str(int(s)) for s in shape) + "|" + str(dtype)


# ---------------------------------------------------------------------------
# load

@dataclass
class PlanArtifact:
    """A loaded artifact: the reconstructed ``BoundPlan`` plus access to
    its AOT executables (with the compile-from-IR fallback)."""

    bound: object
    fingerprint: str
    manifest: dict
    path: pathlib.Path
    _from_aot: dict = field(default_factory=dict)

    def executable(self, input_shape, dtype="float32"):
        """The restored AOT executable for one input shape, or None
        (missing shape / environment mismatch — warned)."""
        key = executable_key(self.fingerprint, input_shape, dtype)
        hit = cached_executable(key)
        if hit is not None:
            return hit
        entry = self.manifest.get("aot", {}).get(_aot_key(input_shape,
                                                          dtype))
        if entry is None:
            return None
        try:
            blob = (self.path / entry).read_bytes()
            compiled = deserialize_compiled(blob)
        except (OSError, AOTMismatchError) as e:
            warnings.warn(
                f"plan artifact {self.path}: AOT executable for shape "
                f"{tuple(input_shape)} not restorable ({e}); compiling "
                f"from plan IR instead", stacklevel=2)
            return None
        cache_executable(key, compiled)
        self._from_aot[tuple(input_shape)] = True
        return compiled

    def program(self, input_shape, dtype="float32"):
        """A ready-to-dispatch program for ``input_shape``: the restored
        executable when possible, else jit-compiled from the plan IR
        (rung 2 of the fallback ladder) — timed under the ``compile``
        warmup phase either way it lands there."""
        exe = self.executable(input_shape, dtype)
        if exe is not None:
            return exe
        bound = self.bound
        with warmup.phase("compile"):
            compiled = compile_program(bound, input_shape, dtype)
        cache_executable(
            executable_key(self.fingerprint, input_shape, dtype), compiled)
        return compiled

    def restored_aot(self, input_shape) -> bool:
        return bool(self._from_aot.get(tuple(input_shape)))


def load_plan(path, *, params=None) -> PlanArtifact:
    """Reconstruct a ``BoundPlan`` from an artifact directory — no
    tracing, no passes, no placement pass, no tuning.

    ``params``: when given (a serving replica holding its own weights),
    their digest must match the artifact's; a mismatch raises
    ``ArtifactStaleError`` — stale plans are never silently served. The
    returned bound plan always uses the artifact's own (identical)
    payload weights.

    Raises ``ArtifactError`` on any corruption / schema / environment
    problem; ``PlanStore.load`` wraps this with the warn-and-fall-back
    behavior serving wants.
    """
    from repro.graph.plan import BoundPlan, ExecutionPlan

    path = pathlib.Path(path)
    with warmup.phase("artifact"):
        try:
            manifest = json.loads((path / MANIFEST).read_text())
        except FileNotFoundError as e:
            raise ArtifactError(f"no plan artifact at {path}: {e}") from e
        except json.JSONDecodeError as e:
            raise ArtifactError(
                f"plan artifact {path}: corrupt manifest ({e})") from e
        if not isinstance(manifest, dict):
            raise ArtifactError(f"plan artifact {path}: manifest is not "
                                f"an object")
        version = manifest.get("schema_version")
        if version != SCHEMA_VERSION:
            raise ArtifactError(
                f"plan artifact {path}: schema version {version!r} "
                f"(this build reads {SCHEMA_VERSION})")
        try:
            graph = graph_from_doc(manifest["graph"])
            qformat = QFormat(*manifest["qformat"])
            plan = ExecutionPlan(
                graph=graph, quant=manifest["quant"], qformat=qformat,
                compile_policy=policy_from_doc(manifest["compile_policy"]),
                mesh=_rebuild_mesh(manifest["mesh"]), autotune=False)
            bind_policy = policy_from_doc(manifest["bind_policy"])
            tuned = {int(k): {kk: int(vv) for kk, vv in v.items()}
                     for k, v in manifest.get("tuned", {}).items()}
            loaded_params, folded = _load_payloads(path / PAYLOADS,
                                                   manifest.get("folded",
                                                                {}))
        except ArtifactError:
            raise
        except Exception as e:
            raise ArtifactError(
                f"plan artifact {path}: malformed content "
                f"({type(e).__name__}: {e})") from e

        # integrity: the recomputed identity must match what was stamped
        fp = plan_fingerprint(plan, params=loaded_params, tuned=tuned,
                              bind_policy=bind_policy)
        if fp != manifest.get("fingerprint"):
            raise ArtifactError(
                f"plan artifact {path}: content fingerprint mismatch "
                f"(payloads edited, or written by an incompatible "
                f"jax/repro build)")
        if params is not None and \
                params_digest(params) != manifest.get("params_digest"):
            raise ArtifactStaleError(
                f"plan artifact {path}: weights differ from the serving "
                f"params — refusing to serve a stale plan")

        # measured tiles for any compile-from-IR rung (and for eager
        # calls sharing these shapes): merge, never overwrite fresher
        # local measurements
        from repro.ops.tiling import TUNING_CACHE
        TUNING_CACHE.merge_rows(manifest.get("tuning_cache", ()),
                                keep_existing=True)

        placed = plan._place_weights(loaded_params, folded)
        bound = BoundPlan(plan=plan, params=loaded_params, folded=folded,
                          policy=bind_policy, placed=placed, tuned=tuned)

        # static verification (DESIGN.md §14): a manifest can pass the
        # fingerprint check and still describe an illegal plan (written
        # by a buggy or adversarial producer with a recomputed
        # fingerprint) — re-derive every invariant before serving it
        from repro.analysis.verifier import PlanVerificationError, \
            verify_plan
        try:
            verify_plan(bound)
        except PlanVerificationError as e:
            raise ArtifactError(
                f"plan artifact {path}: failed static verification — "
                + "; ".join(v.render() for v in e.violations)) from e
    return PlanArtifact(bound=bound, fingerprint=fp, manifest=manifest,
                        path=path)


# ---------------------------------------------------------------------------
# the store: named artifacts for serving

class PlanStore:
    """A directory of named plan artifacts (``<root>/<name>/``) with the
    warn-and-fall-back load the serving layer wants: ``load`` returns
    ``None`` on *any* artifact problem (after warning) so the caller runs
    the fresh pipeline — a bad artifact can degrade boot latency, never
    availability or correctness."""

    def __init__(self, root):
        self.root = pathlib.Path(root)

    def path(self, name: str) -> pathlib.Path:
        return self.root / name

    def has(self, name: str) -> bool:
        return (self.path(name) / MANIFEST).exists()

    def names(self) -> list[str]:
        if not self.root.exists():
            return []
        return sorted(p.parent.name
                      for p in self.root.glob(f"*/{MANIFEST}"))

    def save(self, name: str, bound, *, input_shapes=None,
             aot: bool = True) -> str:
        return save_plan(bound, self.path(name),
                         input_shapes=input_shapes, aot=aot)

    def load(self, name: str, *, params=None) -> PlanArtifact | None:
        try:
            return load_plan(self.path(name), params=params)
        except ArtifactError as e:
            warnings.warn(
                f"plan store: artifact {name!r} unusable, falling back "
                f"to fresh compile ({e})", stacklevel=2)
            return None
