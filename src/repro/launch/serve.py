"""Production serving launcher: ``--arch <id>`` behind the unified
serving front-end (repro.serve.frontend, DESIGN.md §11) — request-level
intake with deadlines over the continuous-batching engine (DESIGN.md §6)
or, for CNN-family archs, the bucketed vision engine (DESIGN.md §8).
``--reduced`` runs a small same-family config on CPU.

A synthetic workload (``--requests`` with mixed prompt/decode lengths) is
submitted through the front-end with an optional ``--slo-ms`` deadline
budget; the report shows sustained occupancy, throughput, and the SLO
view (p50/p95/p99 latency, goodput, deadline-miss rate) from the unified
``ServeStats``. ``--max-queue`` bounds intake — submits beyond it are
refused with the typed ``QueueFullError`` and reported as rejected.
``--profile-dir DIR`` records a profiler trace of the whole run into
``DIR`` (TensorBoard's profile plugin or ``jax.profiler.ProfileData``
read it): the boot phases (``boot.*`` spans) and every serving step
(``frontend.step`` > ``vision.step`` > ``vision.place``/``launch``/
``fetch``/``deliver``) on the host, beside the device's operations.
"""
from __future__ import annotations

import argparse
import os
import pathlib

import jax
import numpy as np

# fixed in-checkout location of JAX's persistent compilation cache when
# JAX_COMPILATION_CACHE_DIR is not set (the path is part of the cache key,
# so it must not move between runs)
DEFAULT_COMPILE_CACHE = pathlib.Path(__file__).resolve().parents[3] / \
    ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache for this process and
    return its directory. ``JAX_COMPILATION_CACHE_DIR``, when set, is left
    to JAX (it reads the variable itself); otherwise the cache lives at
    ``DEFAULT_COMPILE_CACHE``. Called by entry points only — importing
    ``repro`` never touches the cache."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_COMPILE_CACHE))
    return str(DEFAULT_COMPILE_CACHE)


def _load_tuning_cache(path) -> None:
    """``--tuning-cache`` load half: merge a persisted tuned-tile table
    (benchmarks/op_sweep.py --out, or a previous --tuning-cache run) into
    the process cache before any plan compiles. A missing file is fine —
    first runs start empty; corrupt/unknown-version files warn and fall
    back to heuristics inside ``TuningCache.load``."""
    import os

    from repro.ops import TUNING_CACHE
    if not path:
        return
    if not os.path.exists(path):
        print(f"tuning cache: {path} not found (starting empty)")
        return
    n = TUNING_CACHE.load(path)
    print(f"tuning cache: loaded {n} entries from {path}")


def _save_tuning_cache(path) -> None:
    """``--tuning-cache`` save half: persist everything measured this
    process (bind-time autotuning included) for the next one."""
    from repro.ops import TUNING_CACHE
    if not path:
        return
    TUNING_CACHE.save(path)
    print(f"tuning cache: saved {len(TUNING_CACHE)} entries to {path}")


def _frontend(adapter, clock, *, max_queue: int, slo_ms: float | None):
    from repro.serve import Frontend, FrontendConfig
    slo_s = slo_ms / 1e3 if slo_ms else None
    return Frontend(adapter, FrontendConfig(max_queue=max_queue,
                                            slo_s=slo_s), clock)


def build_vision_server(model, params, *, capacity: int, mesh=None,
                        fixed_batch: bool = False, autotune: bool = False,
                        artifact_dir: str | None = None,
                        max_queue: int = 64, slo_ms: float | None = None):
    """The vision serving stack this launcher runs: a ``VisionEngine``
    over bucketed compiled plans (every ladder bucket compiled or
    artifact-loaded here, at construction) behind the front-end.
    Returns ``(engine, frontend, boot)`` with ``boot`` the time-to-ready
    ``WarmupReport``. Dispatch binds at trace time, so the ambient
    ``use_policy`` around this call is the policy the buckets serve
    under (``chip_smoke.py`` pins the pallas backend that way)."""
    from repro.artifact.warmup import collect_warmup
    from repro.serve import (MonotonicClock, VisionAdapter, VisionEngine,
                             VisionEngineConfig)
    clock = MonotonicClock()
    with collect_warmup() as boot:
        # prewarm (on by default) compiles/loads EVERY ladder bucket here
        engine = VisionEngine(
            model, params,
            VisionEngineConfig(batch=capacity, mesh=mesh,
                               buckets=None if fixed_batch else "auto",
                               autotune=autotune,
                               artifact_dir=artifact_dir),
            clock=clock)
    frontend = _frontend(VisionAdapter(engine), clock, max_queue=max_queue,
                         slo_ms=slo_ms)
    return engine, frontend, boot


def _submit_all(frontend, payloads, **options) -> int:
    """Submit everything; a full queue sheds (typed, counted) instead of
    hanging — the launcher's workload is open-loop."""
    from repro.serve import QueueFullError
    shed = 0
    for p in payloads:
        try:
            frontend.submit(p, **options)
        except QueueFullError:
            shed += 1
    return shed


def _print_slo(stats, args) -> None:
    slo = f"{args.slo_ms:.0f}ms" if args.slo_ms else "none"
    print(f"SLO (budget {slo}): p50={stats.p50_s * 1e3:.1f}ms "
          f"p95={stats.p95_s * 1e3:.1f}ms p99={stats.p99_s * 1e3:.1f}ms | "
          f"goodput {stats.goodput_rps:.2f} req/s | "
          f"deadline misses {stats.deadline_misses}/{stats.completed} "
          f"({stats.miss_rate:.0%}) | rejected at intake {stats.rejected}")


def _serve_vision(spec, model, args) -> None:
    """Micro-batched image serving through bucketed compiled plans behind
    the front-end. An explicit ``--mesh`` (e.g. ``1x2``: data×model)
    compiles the plans channel-parallel (DESIGN.md §9); ``auto`` keeps
    the vision path single-device — the CNN is small enough that sharding
    is an explicit operator choice, not a default. ``--autotune`` measures
    tile winners at bind time (or takes them from ``--tuning-cache``) and
    bakes them into the served plans (DESIGN.md §10).

    ``--plan-artifact DIR`` boots the bucket ladder from a plan artifact
    store (DESIGN.md §12): zero trace/fuse/place/tune work when every
    bucket hits, fresh-pipeline fallback (with a warning) otherwise.
    ``--save-plan DIR`` writes the ladder back out for the next replica;
    ``--warmup-report`` prints the per-phase time-to-ready breakdown
    either way."""
    from repro.launch.train import build_mesh

    mesh = None if args.mesh == "auto" else build_mesh(args.mesh)
    params = model.init(jax.random.PRNGKey(0))
    engine, frontend, boot = build_vision_server(
        model, params, capacity=args.capacity, mesh=mesh,
        fixed_batch=args.fixed_batch, autotune=args.autotune,
        artifact_dir=args.plan_artifact,
        max_queue=args.max_queue or max(args.requests, 64),
        slo_ms=args.slo_ms)
    clock = frontend.clock
    plan = engine.plan
    sharded = "" if mesh is None else (
        f", {plan.num_sharded()} sharded stages over "
        f"mesh={dict(mesh.shape)}")
    tuned = ""
    if args.autotune:
        baked = engine.bound(args.capacity).tuned
        tuned = f", {len(baked)} autotuned stages"
    print(f"arch={args.arch} vision path: compiled plan with "
          f"{plan.num_fused()} fused conv blocks, quant={plan.quant}"
          f"{sharded}{tuned}, batch buckets {list(engine.buckets)}")
    if args.warmup_report:
        print(boot.pretty())
    if args.plan_artifact:
        srcs = ", ".join(f"{b}:{s}"
                         for b, s in sorted(engine.plan_source.items()))
        print(f"plan artifacts: {srcs}")
        status = ("OK (trace/fuse/place/tune phases all 0)"
                  if boot.zero_compile() else
                  "DEGRADED (fresh pipeline ran for some buckets)")
        print(f"zero-derivation boot: {status}")
    if args.save_plan:
        fps = engine.save_artifacts(args.save_plan)
        for name, fp in sorted(fps.items()):
            print(f"saved plan artifact {args.save_plan}/{name} "
                  f"fingerprint={fp[:16]}")

    rng = np.random.RandomState(1)
    shape = model.input_shape()[1:]
    shed = _submit_all(frontend,
                       (rng.randn(*shape).astype(np.float32)
                        for _ in range(args.requests)))

    t0 = clock.now()
    results = frontend.run_until_drained()
    wall = clock.now() - t0

    s = engine.stats
    print(f"served {len(results)} images in {wall:.2f}s "
          f"({s.images_per_s:.1f} img/s) over {s.steps} bucket-shaped "
          f"batches (max {args.capacity})")
    print(f"lane utilization {s.lane_utilization:.0%} "
          f"({s.lane_steps} real + {s.pad_lanes} pad lanes), "
          f"pad_fraction={s.pad_fraction:.2f}, overlapped fetches "
          f"{s.overlapped}/{s.steps}")
    _print_slo(s, args)
    if shed:
        print(f"shed {shed} submissions at intake (queue full)")
    if results:
        sample = results[min(results)]
        print(f"sample prediction (request {min(results)}): "
              f"label={sample['label']}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--capacity", type=int, default=4,
                    help="KV slots (max in-flight sequences)")
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--decode-steps", type=int, default=32)
    ap.add_argument("--max-seq", type=int, default=0,
                    help="per-slot budget (default prompt+decode)")
    ap.add_argument("--kv-quant", choices=("none", "int8"), default="none")
    ap.add_argument("--mesh", default="auto")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--slo-ms", type=float, default=None,
                    help="per-request latency budget; completions past it "
                         "count as deadline misses in the SLO report")
    ap.add_argument("--max-queue", type=int, default=0,
                    help="front-end intake bound (0 = fit the workload); "
                         "submits beyond it are refused, not queued")
    ap.add_argument("--tuning-cache", default=None, metavar="PATH",
                    help="persisted tuned-tile table: load before "
                         "compiling, save (merged) after serving")
    ap.add_argument("--autotune", action="store_true",
                    help="measure tile winners at plan bind time and bake "
                         "them into the served plans (vision path)")
    ap.add_argument("--fixed-batch", action="store_true",
                    help="serve every micro-batch at the full --capacity "
                         "shape (disable bucketed batch plans)")
    ap.add_argument("--plan-artifact", default=None, metavar="DIR",
                    help="boot bucket plans from a plan artifact store "
                         "(zero trace/fuse/place/tune on full hit; "
                         "misses fall back to the fresh pipeline)")
    ap.add_argument("--save-plan", default=None, metavar="DIR",
                    help="after boot, save every bucket plan (+ AOT "
                         "executables) into DIR for the next replica")
    ap.add_argument("--warmup-report", action="store_true",
                    help="print the time-to-ready phase breakdown "
                         "(trace/fuse/place/tune/compile/artifact/"
                         "first_dispatch)")
    ap.add_argument("--profile-dir", default=None, metavar="DIR",
                    help="record a profiler trace of the run (boot phases "
                         "and serving spans) into DIR")
    args = ap.parse_args()
    if args.profile_dir is None:
        _serve(args)
        return
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0    # the program's spans, no call tracer
    with jax.profiler.trace(args.profile_dir, profiler_options=opts):
        _serve(args)
    print(f"profile: {args.profile_dir}")


def _serve(args) -> None:
    """Serve ``args.arch`` as the command line asks."""
    from repro.configs.registry import get_arch
    from repro.launch.train import build_mesh, reduced_config
    from repro.serve import (Engine, EngineConfig, LMAdapter,
                             MonotonicClock)
    from repro.sharding.logical import DEFAULT_RULES, ShardingCtx

    print(f"compile cache: {enable_compile_cache()}")
    _load_tuning_cache(args.tuning_cache)
    spec = get_arch(args.arch)
    model = spec.model()
    if spec.family == "cnn":
        _serve_vision(spec, model, args)
        _save_tuning_cache(args.tuning_cache)
        return
    if args.reduced:
        model = reduced_config(model)
    mesh = build_mesh(args.mesh)
    rules = DEFAULT_RULES
    if spec.rule_overrides:
        rules = rules.with_overrides(**spec.rule_overrides)
    ctx = ShardingCtx(mesh, rules)

    clock = MonotonicClock()
    params = model.init(jax.random.PRNGKey(0))
    max_seq = args.max_seq or (args.prompt_len + args.decode_steps)
    engine = Engine(model, params,
                    EngineConfig(capacity=args.capacity, max_seq=max_seq,
                                 kv_quant=args.kv_quant),
                    ctx, clock=clock)
    frontend = _frontend(LMAdapter(engine), clock,
                         max_queue=args.max_queue or max(args.requests, 64),
                         slo_ms=args.slo_ms)

    # mixed-length synthetic workload: jittered prompts, fixed budget
    rng = np.random.RandomState(1)
    lens = rng.choice([args.prompt_len // 2, args.prompt_len],
                      size=args.requests)
    shed = _submit_all(frontend,
                       (rng.randint(0, model.cfg.vocab, size=int(plen))
                        for plen in lens),
                       max_new_tokens=args.decode_steps)

    t0 = clock.now()
    results = frontend.run_until_drained()
    wall = clock.now() - t0
    finished = list(results.values())

    s = engine.stats
    total_tokens = s.prefill_tokens + s.decode_tokens
    print(f"arch={args.arch} capacity={args.capacity} "
          f"kv_quant={args.kv_quant} kv_bytes={engine.kv.nbytes():,}")
    print(f"served {len(finished)} requests in {wall:.2f}s "
          f"({len(finished) / wall:.2f} req/s)")
    print(f"engine steps {s.steps} | mean occupancy "
          f"{engine.scheduler.stats.mean_occupancy():.2f}/{args.capacity} "
          f"| decode lane utilization {s.decode_utilization:.0%}")
    print(f"tokens: {s.prefill_tokens} prefill + {s.decode_tokens} decode "
          f"= {total_tokens} ({total_tokens / wall:.1f} tok/s)")
    _print_slo(s, args)
    if shed:
        print(f"shed {shed} submissions at intake (queue full)")
    served = [r for r in finished if r.generated]
    if served:
        r0 = served[0]
        print(f"sample continuation (request {r0.uid}):", r0.generated[:10])
    rejected = len(finished) - len(served)
    if rejected:
        print(f"rejected {rejected} requests (prompt > max_seq {max_seq})")
    _save_tuning_cache(args.tuning_cache)


if __name__ == "__main__":
    main()
