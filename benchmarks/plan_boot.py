"""Replica cold-boot benchmark: time from process start to first served
response, fresh pipeline vs plan artifact store (DESIGN.md §12).

The serving deltas elsewhere in this repo measure steady state; this one
measures the part an autoscaler feels — how long a NEW replica takes
before it answers its first request. Three boot modes, each a **child
process** (cold caches are the whole point; in-process "reboots" would
reuse traced jaxprs and the executable cache):

* ``fresh``        — full pipeline: trace → fuse → place → tune →
                     XLA compile per bucket.
* ``artifact``     — bound plans restored from a store saved WITHOUT
                     AOT executables: zero trace/fuse/place/tune, but
                     each bucket still pays ``jit().lower().compile()``.
* ``artifact_aot`` — full hit: plans AND serialized executables restore;
                     boot is deserialization + first dispatch only.

The donor replica that saves the stores is a child too: a chip belongs to
one process, so the parent never touches JAX and each child gets the
device to itself. Each boot child reports its warmup phase breakdown
(repro.artifact.warmup) and a digest of its first response's logits —
the three modes must be bitwise-identical (same weights, same plan, same
program), which the schema check asserts. The trajectory lands in ``BENCH_boot.json``; the
acceptance bar is artifact_aot ≥ 2× faster to first response than fresh.
"""
from __future__ import annotations

import argparse
import hashlib
import json
import os
import pathlib
import subprocess
import sys
import tempfile
import time

from benchmarks.common import emit

BENCH_JSON = pathlib.Path(__file__).resolve().parent.parent \
    / "BENCH_boot.json"
REPO = pathlib.Path(__file__).resolve().parent.parent

BATCH = 4
MODES = ("fresh", "artifact", "artifact_aot")
REQUIRED_KEYS = ("boot_to_first_response_ms", "phases_ms", "calls",
                 "zero_compile", "plan_source", "logits_sha256")


# ---------- child: one measured boot ----------

def _child(mode: str, store: str, buckets: str) -> None:
    """Boot a replica, serve one request, print a JSON report. Imports
    happen before the clock starts — we measure the serving stack's
    boot work, not Python import time."""
    import jax
    import numpy as np

    from repro.artifact.warmup import PHASES, collect_warmup
    from repro.models.cnn import PaperCNN, PaperCNNConfig
    from repro.serve import VisionEngine, VisionEngineConfig

    # setup before the clock starts: XLA platform init is replica
    # overhead no plan artifact can save, and a real replica reads its
    # weights from a checkpoint — synthesizing them with model.init here
    # is benchmark scaffolding, identical across modes either way
    jax.block_until_ready(jax.numpy.zeros(()) + 0)
    model = PaperCNN(PaperCNNConfig())
    params = model.init(jax.random.PRNGKey(0))

    t0 = time.perf_counter()
    with collect_warmup() as boot:
        engine = VisionEngine(
            model, params,
            VisionEngineConfig(batch=BATCH,
                               buckets="auto" if buckets == "auto" else None,
                               artifact_dir=store or None))
    rng = np.random.RandomState(0)
    uid = engine.submit(rng.randn(*model.input_shape()[1:])
                        .astype(np.float32))
    results = engine.run()
    elapsed = time.perf_counter() - t0

    logits = np.asarray(results[uid]["logits"], np.float32)
    print(json.dumps({
        "mode": mode,
        "platform": jax.default_backend(),
        "boot_to_first_response_ms": round(elapsed * 1e3, 3),
        "phases_ms": {p: round(boot.phase_s(p) * 1e3, 3) for p in PHASES},
        "calls": {p: boot.phase_calls(p) for p in PHASES},
        "zero_compile": boot.zero_compile(),
        "plan_source": {str(b): s
                        for b, s in sorted(engine.plan_source.items())},
        "logits_sha256": hashlib.sha256(logits.tobytes()).hexdigest(),
    }))


def _run_child(*args: str) -> str:
    """Run this module in a child process; returns its stdout."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [str(REPO / "src"), str(REPO)]
        + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
    proc = subprocess.run(
        [sys.executable, "-m", "benchmarks.plan_boot", *args],
        cwd=REPO, env=env, capture_output=True, text=True, check=True)
    return proc.stdout


def _report(stdout: str) -> dict:
    # the report is the last stdout line; anything above is boot chatter
    return json.loads(stdout.strip().splitlines()[-1])


def _store_dirs(tmp: pathlib.Path) -> dict[str, str]:
    return {"artifact": str(tmp / "store_noaot"),
            "artifact_aot": str(tmp / "store_aot"), "fresh": ""}


# ---------- donor child: save the stores ----------

def _save_stores(tmp: pathlib.Path, buckets: str) -> None:
    """One donor replica saves the bucket ladder twice: with AOT
    executables (the full-hit store) and without (isolates how much of
    the win is skipping derivation vs skipping XLA compile)."""
    import jax

    from repro.models.cnn import PaperCNN, PaperCNNConfig
    from repro.serve import VisionEngine, VisionEngineConfig

    model = PaperCNN(PaperCNNConfig())
    params = model.init(jax.random.PRNGKey(0))
    engine = VisionEngine(
        model, params,
        VisionEngineConfig(batch=BATCH,
                           buckets="auto" if buckets == "auto" else None))
    from repro.artifact.store import PlanStore
    for mode in ("artifact", "artifact_aot"):
        store = PlanStore(_store_dirs(tmp)[mode])
        for bucket in engine.buckets:
            shape = (bucket, *model.input_shape()[1:])
            store.save(engine.bucket_name(bucket), engine.bound(bucket),
                       input_shapes=[shape], aot=mode == "artifact_aot")


# ---------- parent (never touches JAX): measure the three modes ----------

def bench_point(*, smoke: bool = False) -> dict:
    buckets = "fixed" if smoke else "auto"
    with tempfile.TemporaryDirectory() as tmp:
        _run_child("--save-stores", tmp, "--buckets", buckets)
        stores = _store_dirs(pathlib.Path(tmp))
        reports = {m: _report(_run_child("--child", m, "--store",
                                         stores[m], "--buckets", buckets))
                   for m in MODES}
    fresh_ms = reports["fresh"]["boot_to_first_response_ms"]
    for mode in MODES:
        rec = reports[mode]
        ms = rec["boot_to_first_response_ms"]
        rec["speedup_vs_fresh"] = round(fresh_ms / ms, 3) if ms else 0.0
        emit(f"plan_boot/{mode}", ms * 1e3,
             f"speedup={rec['speedup_vs_fresh']:.2f}x "
             f"zero_compile={rec['zero_compile']} "
             f"compile_ms={rec['phases_ms']['compile']:.0f} "
             f"artifact_ms={rec['phases_ms']['artifact']:.0f}")
    return {
        "bench": "plan_boot",
        "schema": 1,
        "smoke": smoke,
        "platform": reports["fresh"]["platform"],
        "batch": BATCH,
        "buckets": buckets,
        "modes": reports,
    }


def check_schema(point: dict) -> None:
    """Assert the BENCH_boot.json point shape (the check.sh smoke gate)."""
    for mode in MODES:
        assert mode in point["modes"], f"missing mode {mode!r}"
        rec = point["modes"][mode]
        missing = [k for k in REQUIRED_KEYS if k not in rec]
        assert not missing, f"{mode} missing keys: {missing}"
    shas = {point["modes"][m]["logits_sha256"] for m in MODES}
    assert len(shas) == 1, \
        f"first responses diverge across boot modes: {shas}"
    for mode in ("artifact", "artifact_aot"):
        rec = point["modes"][mode]
        assert rec["zero_compile"], \
            f"{mode} boot ran derivation phases: {rec['calls']}"


def write_point(point: dict, path: pathlib.Path = BENCH_JSON) -> None:
    """Append to the trajectory file (one JSON list, like the other
    BENCH_*.json records)."""
    history = []
    if path.exists():
        try:
            history = json.loads(path.read_text())
        except json.JSONDecodeError:
            history = []
    history.append(point)
    path.write_text(json.dumps(history, indent=1) + "\n")


def run() -> None:
    point = bench_point()
    check_schema(point)
    write_point(point)


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--child", choices=MODES, default=None,
                    help="internal: run one measured boot and print JSON")
    ap.add_argument("--store", default="",
                    help="internal: artifact store dir for the child")
    ap.add_argument("--save-stores", default=None, metavar="DIR",
                    help="internal: donor child saving the stores in DIR")
    ap.add_argument("--buckets", default="auto",
                    choices=("auto", "fixed"))
    ap.add_argument("--smoke", action="store_true",
                    help="single-bucket ladder for CI; asserts the schema")
    ap.add_argument("--no-json", action="store_true",
                    help="skip the BENCH_boot.json trajectory write")
    ap.add_argument("--out", default=None, metavar="PATH",
                    help="write the trajectory to PATH instead of "
                         "BENCH_boot.json")
    args = ap.parse_args()
    if args.child:
        _child(args.child, args.store, args.buckets)
        sys.exit(0)
    if args.save_stores:
        _save_stores(pathlib.Path(args.save_stores), args.buckets)
        sys.exit(0)
    print("name,us_per_call,derived")
    point = bench_point(smoke=args.smoke)
    check_schema(point)
    if not args.no_json:
        write_point(point, pathlib.Path(args.out) if args.out
                    else BENCH_JSON)
