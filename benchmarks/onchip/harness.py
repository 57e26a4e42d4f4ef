"""One cell of the on-chip benchmark: set-up, the measured window, the
check against the reference, and the result line.

The cell, its configuration, its traffic and its metrics are found by
name: the cell in ``BENCHMARK.json``, the configuration in the file that
``configs`` names, the traffic in ``traffic/<name>.json``, the model
family in ``families/<family>.py`` and each metric in
``metrics/<metric>.py`` (``read(run) -> float | None``; ``None`` leaves
the metric out of the line).

The system under test is the vision serving stack that
``launch/serve.py:build_vision_server`` builds, every conv stage a
compiled Pallas kernel: the window drives it through ``Frontend.submit``
and ``Frontend.step`` only, and takes each answer out of
``Frontend.results`` as it arrives, as a client takes its reply. Every
request is timed from the moment it was due, by this module's own clock.
"""
from __future__ import annotations

import contextlib
import gc
import importlib.util
import json
import math
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(ROOT / "src"))

import arrivals  # noqa: E402
import trace_reduce  # noqa: E402

POLL_S = 0.0005        # re-check a held partial bucket this often
DRAIN_LIMIT_S = 60.0   # answers still missing this long after the window
                       # never come
clock = time.monotonic  # the program's MonotonicClock reads the same clock


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        f"onchip_{path.parent.name}_{path.stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    name: str
    spec: dict            # the workload entry of BENCHMARK.json
    config: dict
    traffic: dict
    family: object        # families/<family>.py
    metrics: dict         # 0 (end to end) / 1 (per layer) -> [(spec, module)]


def manifest(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def load_config(name: str, root: Path = ROOT) -> dict:
    """The configuration file that ``BENCHMARK.json`` names ``name``."""
    files = {c["name"]: c["file"] for c in manifest(root)["configs"]}
    return json.loads((root / files[name]).read_text())


def load_traffic(name: str, root: Path = ROOT) -> dict:
    return json.loads((root / HERE.relative_to(ROOT) / "traffic"
                       / f"{name}.json").read_text())


def load_family(config: dict, root: Path = ROOT):
    return load_module(root / HERE.relative_to(ROOT) / "families"
                       / f"{config['family']}.py")


def resolve(workload: str, root: Path = ROOT) -> Cell:
    """The cell named ``workload`` with everything it names, loaded."""
    man = manifest(root)
    cells = {w["name"]: w for w in man["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; BENCHMARK.json "
                         f"has {sorted(cells)}")
    spec = cells[workload]
    config = load_config(spec["config"], root)
    here = root / HERE.relative_to(ROOT)
    metrics = {}
    for trace, key in ((0, "end_to_end"), (1, "per_layer")):
        metrics[trace] = [
            (m, load_module(here / "metrics" / f"{m['name']}.py"))
            for m in man[key]
            if workload in m.get("workloads", [workload])]
    return Cell(workload, spec, config, load_traffic(spec["traffic"], root),
                load_family(config, root), metrics)


@dataclass
class Run:
    """What one run measured: the records every metric reads. The
    per-request arrays hold every request due in the window, in the
    order sent; a time not known (never dispatched, never answered) is
    NaN."""
    cell: Cell
    seconds: float                      # the measured window
    setup_s: float
    due: np.ndarray                     # when each request was due
    dispatch: np.ndarray                # when the front-end dispatched it
    finish: np.ndarray                  # when its answer arrived
    done_in_window: int                 # answers that arrived in it
    engine: dict                        # engine counters over the window
    stages: list[dict]                  # conv stages of the configuration
    flops_per_image: int
    peak: dict
    compiles_in_window: int = 0
    trace: trace_reduce.Trace | None = None


def seed_words(seed: int) -> np.ndarray:
    """Any whole number up to 64 bits as two 32-bit words, so that seeds
    past 2**32 stay apart."""
    if not 0 <= seed < 2**64:
        raise ValueError(f"seed {seed} is not a whole number below 2**64")
    return np.array([seed & 0xFFFFFFFF, seed >> 32], np.uint32)


def enable_compile_cache() -> str:
    """The program's persistent compile cache (``JAX_COMPILATION_CACHE_DIR``
    if set, else a fixed directory in the checkout), with every program
    cached however small or quick to compile."""
    import jax
    from repro.launch.serve import enable_compile_cache as enable
    path = enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCounter:
    """Counts XLA compilations (persistent-cache loads included) inside
    its ``with`` block."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.count = 0

    def _event(self, name, *args, **kwargs):
        if name == self.EVENT:
            self.count += 1

    def __enter__(self):
        import jax.monitoring
        jax.monitoring.register_event_duration_secs_listener(self._event)
        return self

    def __exit__(self, *exc):
        import jax.monitoring
        jax.monitoring.unregister_event_duration_listener(self._event)


class GcPauses:
    """Times Python's full (oldest-generation) collections inside its
    ``with`` block: the host pauses the program pays for the objects it
    keeps."""

    def __init__(self):
        self.pauses: list[float] = []
        self._t = 0.0

    def _event(self, phase, info):
        if info["generation"] != 2:
            return
        if phase == "start":
            self._t = time.perf_counter()
        else:
            self.pauses.append(time.perf_counter() - self._t)

    def __enter__(self):
        gc.callbacks.append(self._event)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self._event)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def _profile_options():
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0        # host spans, no function tracer
    return opts


# ------------------------------------------------------------- set-up

def setup(cell: Cell, seed: int, marks: dict):
    """Weights, image pool and the serving stack, every bucket of the
    cell's ladder compiled (or loaded from the cache) and served once."""
    from repro.launch.serve import build_vision_server
    from repro.ops import ExecPolicy, use_policy
    cfg, fam = cell.config, cell.family
    marks["cache"] = enable_compile_cache()
    weights, images = fam.materialize(cfg, seed_words(seed),
                                      cell.traffic["pool"])
    pool = np.asarray(images)             # requests arrive from the host
    marks["inputs"] = clock()
    model = fam.build_program(cfg)
    with use_policy(ExecPolicy(backend="pallas", quant=cfg["quant"])):
        engine, frontend, _ = build_vision_server(
            model, fam.program_params(cfg, weights), capacity=cfg["batch"],
            fixed_batch=cell.traffic["buckets"] == "full",
            max_queue=cfg["max_queue"], slo_ms=cfg["slo_ms"])
    marks["buckets"] = dict(engine.ready_s)
    marks["build"] = clock()
    for b in engine.buckets:              # one served step per bucket
        for i in range(b):
            frontend.submit(pool[i % len(pool)])
        frontend.run_until_drained()
    frontend.results.clear()              # the warm answers, taken
    marks["warm"] = clock()
    return weights, pool, engine, frontend


# ------------------------------------------------------------- window

class Window:
    """The requests of one window, recorded as flat lists of numbers (no
    object per request for Python's collector to scan): for each, in the
    order sent, its pool image, due time, and once known its dispatch and
    answer times. Answers are taken out of the front-end as they arrive,
    so the cost of ``collect`` grows with the answers, not the backlog."""

    def __init__(self, frontend, pool, span):
        self.frontend, self.pool, self.span = frontend, pool, span
        self.image: list[int] = []
        self.due: list[float] = []
        self.dispatch: list[float] = []
        self.finish: list[float] = []
        self.accepted: list[bool] = []
        self.index: dict[int, int] = {}   # front-end id -> request, while
                                          # outstanding
        self.answered: list[int] = []     # request of each answer
        self.logits: list[np.ndarray] = []

    def send(self, image: int, due: float) -> None:
        from repro.serve import QueueFullError
        n = len(self.due)
        self.image.append(image)
        self.due.append(due)
        self.dispatch.append(math.nan)
        self.finish.append(math.nan)
        with self.span("submit"):
            try:
                rid = self.frontend.submit(self.pool[image])
            except QueueFullError:
                self.accepted.append(False)
                return
        self.accepted.append(True)
        self.index[rid] = n

    def collect(self) -> int:
        """Take the answers that arrived since the last call; returns how
        many."""
        now = clock()
        results = self.frontend.results
        arrived = list(results)
        for rid in arrived:
            n = self.index.pop(rid)
            self.finish[n] = now
            self.dispatch[n] = self.frontend.requests[rid].dispatch_t
            self.answered.append(n)
            self.logits.append(results.pop(rid)["logits"])
        return len(arrived)

    def step(self, flush: bool = False) -> bool:
        with self.span("frontend_step"):
            ran = self.frontend.step(flush=flush)
        self.collect()
        return ran


def drive_closed(win: Window, seconds: float, callers: int) -> float:
    """``callers`` callers, each sending its next image when its answer
    arrives. Returns the window's end."""
    t0 = clock()
    t_end = t0 + seconds
    for _ in range(callers):
        win.send(len(win.due) % len(win.pool), t0)
    while True:
        before = len(win.answered)
        win.step()
        now = clock()
        if now >= t_end:
            return t_end
        for _ in range(len(win.answered) - before):
            win.send(len(win.due) % len(win.pool), now)


def drive_open(win: Window, due: np.ndarray, seconds: float) -> float:
    """Send each image at its due time, whatever is outstanding. Returns
    the window's end."""
    t0 = clock()
    t_end = t0 + seconds
    i = 0

    def send_due(until):
        nonlocal i
        while i < len(due) and t0 + due[i] <= until:
            win.send(i % len(win.pool), t0 + due[i])
            i += 1

    while True:
        now = clock()
        if now >= t_end:
            send_due(t_end)               # late, but due in the window
            return t_end
        send_due(now)
        if not win.step():
            wait = (t0 + due[i] if i < len(due) else t_end) - clock()
            if len(win.frontend.core):    # a partial bucket is held
                wait = min(wait, POLL_S)
            if wait > 0:
                with win.span("wait_arrival"):
                    time.sleep(wait)


def drain(win: Window) -> None:
    """Serve what is left after the window, taking each answer."""
    limit = clock() + DRAIN_LIMIT_S
    while win.frontend.has_work() and clock() < limit:
        win.step(flush=True)


def _engine_counters(engine) -> dict:
    s = engine.stats
    return {"steps": s.steps, "lane_steps": s.lane_steps,
            "pad_lanes": s.pad_lanes, "wall_s": s.wall_s}


@dataclass
class Measured:
    """One measured window: its requests, when it closed, and what the
    engine, the compiler and Python's collector did inside it."""
    win: Window
    t_end: float
    done_in_window: int                 # answers that arrived in it
    engine: dict                        # engine counters over the window
    compiles: int
    gc_pauses: list[float]


def open_due(cell: Cell, seconds: float, seed: int) -> np.ndarray:
    """The due times of an open-loop cell's window, from the seed."""
    return arrivals.open_schedule(cell.traffic["states"],
                                  cell.config["knee_per_s"], seconds,
                                  np.random.default_rng(seed))


def measure(cell: Cell, engine, frontend, pool, seconds: float,
            due: np.ndarray | None, span, trace_dir: str | None = None
            ) -> Measured:
    """Drive one window of the cell's traffic through the front-end, then
    serve what is left. ``due`` is the open loop's schedule; with
    ``trace_dir`` the profiler records the window there."""
    import jax
    win = Window(frontend, pool, span)
    # what set-up left behind is never collected again, so a collection
    # in the window scans only the objects made in it
    gc.collect()
    gc.freeze()
    if trace_dir:
        jax.profiler.start_trace(trace_dir,
                                 profiler_options=_profile_options())
    before = _engine_counters(engine)
    with CompileCounter() as compiles, GcPauses() as pauses, \
            span("window"):
        if cell.traffic["loop"] == "closed":
            t_end = drive_closed(win, seconds, cell.traffic["callers"])
        else:
            t_end = drive_open(win, due, seconds)
    after = _engine_counters(engine)
    if trace_dir:
        jax.profiler.stop_trace()
    done_in_window = int(np.sum(np.array(win.finish) <= t_end))
    drain(win)
    gc.unfreeze()
    return Measured(win, t_end, done_in_window,
                    {k: after[k] - before[k] for k in after},
                    compiles.count, pauses.pauses)


def no_span(name: str):
    return contextlib.nullcontext()


# ------------------------------------------------------------ the run

def run(cell: Cell, *, seed: int, seconds: float, trace: bool,
        marks: dict, peak: dict, device) -> dict:
    """One run of ``cell``: returns the result line's object. ``marks``
    holds the clock readings at process start (``start``) and once JAX
    has found its devices (``init``)."""
    import jax
    cfg, fam = cell.config, cell.family
    weights, pool, engine, frontend = setup(cell, seed, marks)
    due = open_due(cell, seconds, seed) \
        if cell.traffic["loop"] == "open" else None
    setup_s = clock() - marks["start"]

    trace_dir = tempfile.mkdtemp(prefix="onchip_trace_") if trace else None
    span = jax.profiler.TraceAnnotation if trace else no_span
    got = measure(cell, engine, frontend, pool, seconds, due, span,
                  trace_dir)
    win = got.win
    finish = np.array(win.finish)
    memory_peak = (device.memory_stats() or {}).get("peak_bytes_in_use")
    del engine, frontend
    gc.collect()

    run_rec = Run(cell, seconds, setup_s, np.array(win.due),
                  np.array(win.dispatch), finish, got.done_in_window,
                  got.engine, fam.stages(cfg), fam.flops_per_image(cfg),
                  peak, got.compiles)
    if trace:
        run_rec.trace = trace_reduce.load(
            trace_reduce.newest_xplane(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)

    t_ref = clock()
    accepted = np.array(win.accepted)
    checks = check(fam, cfg, weights, pool,
                   np.stack(win.logits) if win.logits else np.zeros((0,)),
                   np.array(win.image)[win.answered],
                   n_unanswered=int(np.sum(accepted & np.isnan(finish))))
    marks["reference_s"] = clock() - t_ref
    report(marks, setup_s, run_rec, got.gc_pauses)

    metrics = {}
    for m, mod in cell.metrics[int(trace)]:
        value = mod.read(run_rec)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    dev = {"platform": device.platform, "kind": device.device_kind,
           "count": jax.device_count(), "memory_peak_bytes": memory_peak}
    out = {"correct": is_correct(checks),
           "attempted": len(finish),
           "failed": int(np.sum(np.isnan(finish))),
           "metrics": metrics, "device": dev}
    if trace:
        tr = run_rec.trace
        dev["busy_s"] = trace_reduce.busy_s(tr)
        dev["window_s"] = trace_reduce.window_s(tr)
        out["breakdown"] = {"device_ops": trace_reduce.top_ops(tr),
                            "idle_gaps": trace_reduce.idle_gaps(tr)}
    out["checks"] = checks
    for name, c in checks.items():
        log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    return out


def report(marks: dict, setup_s: float, run_rec: Run,
           gc_pauses: list[float]) -> None:
    """The run's diagnostics, on stderr: set-up by phase, the reference's
    time, compilations and Python's full collections in the window, and
    how late requests were dispatched."""
    t = marks["start"]
    log(f"setup_s {setup_s:.3f}: init {marks['init'] - t:.3f}, weights "
        f"and images {marks['inputs'] - marks['init']:.3f}, build "
        f"{marks['build'] - marks['inputs']:.3f} (bucket ready s "
        f"{ {b: round(s, 3) for b, s in marks['buckets'].items()} }), "
        f"warm {marks['warm'] - marks['build']:.3f}; compile cache "
        f"{marks['cache']}")
    log(f"reference and check {marks['reference_s']:.3f} s (not in setup)")
    log(f"compiles in window: {run_rec.compiles_in_window}")
    log(f"full gc collections in window: {len(gc_pauses)}, "
        f"{sum(gc_pauses):.3f} s in all, longest "
        f"{max(gc_pauses, default=0.0):.3f} s")
    lag = run_rec.dispatch - run_rec.due
    lag = lag[~np.isnan(lag)]
    e = run_rec.engine
    log(f"window: {len(run_rec.due)} requests, {run_rec.done_in_window} "
        f"answered in it, {e['steps']} engine steps, {e['lane_steps']} "
        f"lanes + {e['pad_lanes']} pad; due to dispatch median "
        f"{1e3 * float(np.median(lag)) if lag.size else math.nan:.3f} ms")


# ---------------------------------------------------------- correctness

def reference_logits(fam, cfg: dict, weights, pool: np.ndarray,
                     passes: str = "highest") -> np.ndarray:
    """The plain reference over the pool, a batch at a time."""
    import jax
    fwd = jax.jit(fam.forward, static_argnames="passes")
    b = cfg["batch"]
    n = len(pool)
    padded = np.concatenate([pool, np.zeros((-n % b, *pool.shape[1:]),
                                            pool.dtype)])
    return np.concatenate([np.asarray(fwd(weights, padded[i:i + b],
                                          passes=passes))
                           for i in range(0, len(padded), b)])[:n]


def logit_err(served: np.ndarray, want: np.ndarray) -> float:
    """Largest |served - reference| over every logit, relative to the
    largest |reference| logit of the pool."""
    if served.size == 0:
        return 0.0
    return float(np.abs(served - want).max() / np.abs(want).max())


def check(fam, cfg: dict, weights, pool, served, image_idx,
          n_unanswered: int) -> dict:
    """The numbers ``correct`` compares, each beside its limit."""
    ref = reference_logits(fam, cfg, weights, pool)
    err = logit_err(served, ref[image_idx]) if len(image_idx) else 0.0
    if not np.isfinite(err) or (served.size and
                                not np.isfinite(served).all()):
        err = float("inf")
    return {"logit_err": {"value": err, "limit": cfg["logit_err_limit"]},
            "unanswered": {"value": n_unanswered, "limit": 0}}


def is_correct(checks: dict) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())


# ------------------------------------------------------ metric helpers

def percentile(values, q: float) -> float:
    """Nearest-rank percentile, q in [0, 100] (as ``serve/stats.py``)."""
    ordered = sorted(values)
    rank = max(1, -(-len(ordered) * q // 100))
    return ordered[int(rank) - 1]


def latencies_s(run) -> np.ndarray:
    """Due time to answer of every request due in the window (of a
    ``Run`` or a ``Window``); a request never answered (refused, or
    lost) counts as infinitely late."""
    finish, due = np.asarray(run.finish), np.asarray(run.due)
    return np.where(np.isnan(finish), np.inf, finish - due)
