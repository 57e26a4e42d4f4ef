"""Fused-plan vs layer-by-layer latency sweep (DESIGN.md §8).

The paper's Tab. II argument, lifted between layers: the deep pipeline
(conv → relu → pool with no intermediate feature-map round-trip) should be
no slower than the layer-by-layer chain anywhere and pull ahead as batch
(and therefore intermediate-tensor traffic) grows. We time, per quant mode
and batch size:

  * ``eager``  — ``PaperCNN.forward`` (conv2d_apply → relu → maxpool2 per
    layer, each op materializing its output),
  * ``plan``   — ``PaperCNN.compile()``'s fused ExecutionPlan, ``bind``-ed
    so weight quantization is constant-folded out of the timed region,

and report GOPS = flops_per_image × batch / time for both, plus the
speedup. A ``BENCH_pipeline.json`` trajectory point (fused vs unfused
GOPS at the reference batch) is appended so later PRs can track the
fusion speedup over time.

On CPU the Pallas fused kernel runs in interpret mode, so the registry
auto-selects the XLA backends — the comparison is then compiled-plan
structure vs eager op chain under the same backend, and the reproduced
claim is the *shape* of the curve, not TPU microseconds.

The **tuned-vs-heuristic** columns (DESIGN.md §10) time the same fused
plan twice on the backend where tile parameters actually bind (pallas):
once with the analytic heuristic tiles (tuning cache masked off) and once
compiled with ``autotune=True`` — bind measures the candidate grid and
bakes the winners. ``tuned_speedup = heuristic / tuned``; both runs are
bitwise-identical in output (tiles never change numerics), so the ratio
is pure scheduling. When the measured winner IS the heuristic point the
two plans are the same program and the speedup is reported as exactly 1.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import time

import jax

from benchmarks.common import emit
from repro.models.cnn import PaperCNN, PaperCNNConfig
from repro.ops import ExecPolicy, TUNING_CACHE, use_policy

BATCHES = [1, 8, 32, 128]
QUANTS = ("none", "qformat", "int8")
REFERENCE_BATCH = 8                     # the trajectory-point batch
BENCH_JSON = pathlib.Path(__file__).resolve().parent.parent \
    / "BENCH_pipeline.json"


def _best_us(fn, *args, warmup: int = 3, iters: int = 25) -> float:
    """Minimum wall time in microseconds. The fused-vs-eager programs are
    near-identical single-digit-ms CPU workloads, where the *floor* is the
    meaningful latency estimate — the median is dominated by scheduler
    noise at this scale (benchmarks/common.time_fn serves the larger
    workloads)."""
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    best = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best * 1e6


def sweep(batches=BATCHES, quants=QUANTS, *, img_size=28, warmup=3,
          iters=25):
    """-> rows [{quant, batch, eager_us, plan_us, gops_eager, gops_plan,
    speedup}]. ``img_size`` scales the workload past MNIST; the kernels'
    row-block grid tiles each stage's rows (DESIGN.md §13)."""
    key = jax.random.PRNGKey(0)
    cfg = PaperCNNConfig(img_size=img_size)
    flops1 = cfg.flops_per_image()
    model = PaperCNN(cfg)
    params = model.init(key)
    rows = []
    for quant in quants:
        pol = ExecPolicy(quant=quant)
        plan = model.compile(policy=pol)
        bound = plan.bind(params)
        plan_fwd = jax.jit(lambda x: bound(x))
        eager_fwd = jax.jit(lambda p, x: model.forward(p, x))

        for b in batches:
            x = jax.random.normal(key, model.input_shape(b))
            with use_policy(pol):
                t_eager = _best_us(eager_fwd, params, x,
                                   warmup=warmup, iters=iters)
            t_plan = _best_us(plan_fwd, x, warmup=warmup, iters=iters)
            row = {
                "quant": quant, "batch": b,
                "eager_us": t_eager, "plan_us": t_plan,
                "gops_eager": flops1 * b / t_eager / 1e3,
                "gops_plan": flops1 * b / t_plan / 1e3,
                "speedup": t_eager / t_plan,
            }
            rows.append(row)
            emit(f"pipeline/{quant}/batch{b}/eager", t_eager,
                 f"GOPS={row['gops_eager']:.2f}")
            emit(f"pipeline/{quant}/batch{b}/plan", t_plan,
                 f"GOPS={row['gops_plan']:.2f};"
                 f"fused_speedup={row['speedup']:.2f}x;"
                 f"fused_blocks={plan.num_fused()}")
    return rows


def _best_us_interleaved(fa, fb, *args, warmup: int = 3,
                         iters: int = 25) -> tuple[float, float]:
    """Two callables timed alternately (min wall time each, µs): the A/B
    calls ride the same load drift, so their *ratio* is far more stable
    than two back-to-back ``_best_us`` runs on a noisy host."""
    for _ in range(warmup):
        jax.block_until_ready(fa(*args))
        jax.block_until_ready(fb(*args))
    best_a = best_b = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fa(*args))
        best_a = min(best_a, time.perf_counter() - t0)
        t0 = time.perf_counter()
        jax.block_until_ready(fb(*args))
        best_b = min(best_b, time.perf_counter() - t0)
    return best_a * 1e6, best_b * 1e6


def tuned_vs_heuristic(quants=QUANTS, *, img_size=28, warmup=3,
                       iters=25) -> dict:
    """Time the fused plan at the reference batch on the pallas backend
    with heuristic vs bind-time-autotuned tiles (DESIGN.md §10).

    -> {quant: {heur_us, tuned_us, gops_heur, gops_tuned, tuned_speedup,
    tiles, validation}}. The heuristic plan masks the tuning cache
    (snapshot/clear/restore) so winners measured by an earlier benchmark
    in the same process cannot leak into the baseline; the autotuned plan
    then tunes through the cache as serving would (hits skip the
    measurement). The two plans are timed interleaved, and the timing IS
    the autotuner's plan-level **winner validation**: op-level winners
    that fail to beat the heuristic plan end to end are rejected —
    ``pin_heuristic_tiles`` writes the incumbent back into the cache (so
    serving and later runs keep it instead of re-chasing noise) and the
    validated configuration is the heuristic program itself
    (``tuned_speedup`` exactly 1, ``validation: "reverted"``). The same
    holds when the search never left the heuristic (``"heuristic"``);
    a surviving winner reports its measured ratio (``"kept"``).
    """
    key = jax.random.PRNGKey(0)
    cfg = PaperCNNConfig(img_size=img_size)
    flops1 = cfg.flops_per_image()
    model = PaperCNN(cfg)
    params = model.init(key)
    x = jax.random.normal(key, model.input_shape(REFERENCE_BATCH))
    out = {}
    for quant in quants:
        pol = ExecPolicy(quant=quant, backend="pallas")
        saved = TUNING_CACHE.snapshot()
        TUNING_CACHE.clear()            # heuristic tiles, nothing tuned
        bound_h = model.compile(policy=pol,
                                batch=REFERENCE_BATCH).bind(params)
        fn_h = jax.jit(lambda xx: bound_h(xx))
        jax.block_until_ready(fn_h(x))  # trace under the masked cache
        TUNING_CACHE.restore(saved)
        plan_t = model.compile(policy=pol, batch=REFERENCE_BATCH,
                               autotune=True)
        bound_t = plan_t.bind(params)   # measures (or cache-hits) winners
        if bound_t.tuned:
            fn_t = jax.jit(lambda xx: bound_t(xx))
            t_h, t_t = _best_us_interleaved(fn_h, fn_t, x,
                                            warmup=warmup, iters=iters)
            if t_t < t_h:
                validation = "kept"
            else:                       # winner regressed end to end:
                plan_t.pin_heuristic_tiles(params, bound_t.folded)
                bound_t = plan_t.bind(params)        # bakes nothing now
                t_t, validation = t_h, "reverted"
        else:                           # winner == heuristic everywhere:
            t_h = _best_us(fn_h, x, warmup=warmup, iters=iters)
            t_t = t_h                   # same program, ratio is pure noise
            validation = "heuristic"
        row = {
            "heur_us": t_h, "tuned_us": t_t,
            "gops_heur": flops1 * REFERENCE_BATCH / t_h / 1e3,
            "gops_tuned": flops1 * REFERENCE_BATCH / t_t / 1e3,
            "tuned_speedup": t_h / t_t,
            "tiles": {str(k): v for k, v in sorted(bound_t.tuned.items())},
            "validation": validation,
        }
        out[quant] = row
        emit(f"pipeline/{quant}/batch{REFERENCE_BATCH}/tuned", t_t,
             f"GOPS={row['gops_tuned']:.2f};"
             f"tuned_speedup={row['tuned_speedup']:.2f}x;"
             f"heur_us={t_h:.0f};tuned_stages={len(bound_t.tuned)};"
             f"validation={validation}")
    return out


def trajectory_point(rows, path=BENCH_JSON, tuned=None) -> dict:
    """Append the reference-batch fused/unfused (and tuned-vs-heuristic)
    GOPS to the trajectory file (one JSON list; later PRs extend it)."""
    ref = [r for r in rows if r["batch"] == REFERENCE_BATCH] or rows
    modes = {r["quant"]: {"gops_unfused": round(r["gops_eager"], 3),
                          "gops_fused": round(r["gops_plan"], 3),
                          "fused_speedup": round(r["speedup"], 3)}
             for r in ref}
    for quant, t in (tuned or {}).items():
        if quant in modes:
            modes[quant].update(
                gops_heur_tiles=round(t["gops_heur"], 3),
                gops_tuned_tiles=round(t["gops_tuned"], 3),
                tuned_speedup=round(t["tuned_speedup"], 3),
                tuned_validation=t["validation"],
                tuned_tiles={k: dict(v) for k, v in t["tiles"].items()})
    point = {
        "bench": "pipeline_sweep",
        "reference_batch": ref[0]["batch"],
        "platform": jax.default_backend(),
        "modes": modes,
    }
    if tuned:
        point["note"] = (
            "fused/unfused columns run the registry's auto backend (XLA "
            "on CPU, where tile parameters do not bind — their ratio "
            "there is program structure + measurement noise, which is "
            "what the earlier sub-1.0 none-mode fused_speedup points "
            "were); the *_tiles columns isolate the tile lever on the "
            "pallas backend, heuristic vs measured-autotuned (DESIGN.md "
            "§10), timed interleaved as the tuner's plan-level winner "
            "validation. tuned_speedup==1.0 means the validated "
            "configuration IS the heuristic program: either the search "
            "never left the heuristic point (tuned_validation="
            "'heuristic'; hysteresis — a candidate must measure >5% "
            "faster to displace the incumbent) or the op-level winner "
            "failed end-to-end validation and was reverted "
            "(tuned_validation='reverted', incumbent pinned in the "
            "cache); 'kept' winners report their measured ratio")
    history = []
    if path.exists():
        try:
            history = json.loads(path.read_text())
        except json.JSONDecodeError:
            history = []
    history.append(point)
    path.write_text(json.dumps(history, indent=1) + "\n")
    return point


def _summary(rows, wrote_json: bool) -> None:
    worst = min((r["speedup"] for r in rows
                 if r["batch"] >= REFERENCE_BATCH), default=1.0)
    tail = f";trajectory={BENCH_JSON.name}" if wrote_json else ""
    emit("pipeline/summary", 0.0,
         f"min_speedup_at_batch>={REFERENCE_BATCH}={worst:.2f}x{tail}")


def run() -> None:
    rows = sweep()
    tuned = tuned_vs_heuristic()
    trajectory_point(rows, tuned=tuned)
    _summary(rows, wrote_json=True)


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sweep for CI: 2 batches, fewer iters, no "
                         "tuned-vs-heuristic timing")
    ap.add_argument("--no-json", action="store_true",
                    help="skip the BENCH_pipeline.json trajectory write")
    ap.add_argument("--img-size", type=int, default=28,
                    help="input resolution (the kernels' row blocks tile "
                         "each stage's rows, DESIGN.md §13)")
    args = ap.parse_args()
    print("name,us_per_call,derived")
    if args.smoke:
        rows = sweep(batches=[1, 8], img_size=args.img_size,
                     warmup=2, iters=8)
        tuned = None
    else:
        rows = sweep(img_size=args.img_size)
        tuned = tuned_vs_heuristic(img_size=args.img_size)
    if args.img_size != 28:
        args.no_json = True             # trajectory tracks the paper shape
    if not args.no_json:
        trajectory_point(rows, tuned=tuned)
    _summary(rows, wrote_json=not args.no_json)
