"""Vision serving: bucketed micro-batch image inference over compiled plans.

The LM engine (repro.serve.engine, DESIGN.md §6) keeps ONE compiled decode
program and scales throughput with occupancy. This is the same argument
for the paper's own workload — image classification: requests are
micro-batched into a **fixed** batch shape and pushed through the fused
``ExecutionPlan`` from the graph compiler (repro.graph, DESIGN.md §8), so
there is a small static set of compiled programs regardless of queue
depth, and the deep pipeline inside the plan (fused conv blocks) does the
per-image work without HBM round-trips between conv/relu/pool.

``VisionEngineConfig.buckets`` adds **bucketed batch plans**: instead of
padding every short batch to the one full compiled shape (paying dead pad
lanes), the engine keeps a plan cache keyed by padded batch bucket (e.g.
1/2/4/8 for ``batch=8``) and serves each micro-batch through the smallest
bucket that fits — short tails stop paying full-batch pad lanes. The
whole ladder **pre-warms at boot** (``VisionEngineConfig.prewarm``,
default on): a bucket that compiled lazily on its first short batch used
to spike that request's p99 by a whole XLA compile; now every bucket's
program exists before traffic arrives. ``VisionStats.pad_fraction`` makes
the bucketing win visible (surfaced by ``benchmarks/serve_throughput.py``).

``VisionEngineConfig.artifact_dir`` points the ladder at a **plan
artifact store** (repro.artifact, DESIGN.md §12): each bucket first
tries ``<dir>/bucket_<b>`` — a hit restores the bound plan (weights,
folded quantization, baked tiles) and its AOT-compiled executable with
zero trace/fuse/place/tune work, a stale or corrupt artifact warns and
falls back to the fresh pipeline. ``save_artifacts()`` writes the
ladder back out, which is what ``launch/serve.py --save-plan`` calls.

The plan is ``bind``-ed to the params at engine construction: weight
quantization (int8 scales, Qm.n snapping) is folded once — the serving
analogue of flashing the bitstream before traffic arrives. With
``VisionEngineConfig.mesh`` the plan is additionally compiled
channel-parallel (an icp × ocp split per conv stage, DESIGN.md §9/§15),
the bind places each stage's weights shard-resident, and serving
batches scatter over the mesh's ``data`` axis before dispatch. With
``VisionEngineConfig.autotune`` each bucket's bind measures tile
candidates (or takes them from a persisted tuning cache) and bakes the
winners into the bound plan (DESIGN.md §10) — serving traffic never
re-tunes.

``VisionEngine.step`` keeps at most **two buckets in flight** when more
work is certain: with a full bucket queued behind the bucket a step
answers, the step launches that one *ahead* (its copy back started for
when the device finishes, ``copy_to_host_async``) before it fetches, and
the next step answers it, so the transfer back of bucket *n* overlaps
the stacking, input transfer and launch of bucket *n+1*. A lone bucket
is fetched in the step that launched it, as before. The front-end's
``VisionAdapter`` hands the engine a second bucket only when it is full.
"""
from __future__ import annotations

from collections import deque
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from repro.ops import ExecPolicy
from repro.serve.clock import Clock, MonotonicClock
from repro.serve.stats import ServeStats
from repro.spans import span

__all__ = ["VisionEngineConfig", "VisionStats", "VisionEngine"]


@dataclass(frozen=True)
class VisionEngineConfig:
    batch: int = 8                    # the largest compiled batch shape
    # None follows the normal compile() precedence (model-config policy,
    # then ambient use_policy); set to pin a serving policy explicitly
    policy: ExecPolicy | None = None
    fuse: bool = True                 # compile with conv-block fusion
    # device mesh for a channel-parallel plan (DESIGN.md §9): compile
    # with ICP/OCP placement and bind weights shard-resident. None
    # serves single-device.
    mesh: object | None = None
    # bucketed batch plans: None serves every micro-batch at the one
    # ``batch`` shape (the pre-bucketing behavior); "auto" compiles
    # power-of-two buckets up to ``batch``; an explicit tuple pins the
    # bucket ladder (must include ``batch``). On a mesh with a ``data``
    # axis, buckets that don't divide it are dropped.
    buckets: tuple[int, ...] | str | None = None
    # measured tile selection at bind time (DESIGN.md §10)
    autotune: bool = False
    # compile (or artifact-load) EVERY ladder bucket at construction so
    # no request ever pays a one-time compile in its latency (the lazy
    # first-short-batch compile used to spike p99 per bucket)
    prewarm: bool = True
    # plan artifact store directory (DESIGN.md §12): bucket plans load
    # from ``<dir>/bucket_<b>`` when present (zero-derivation boot) and
    # ``save_artifacts()`` writes them back. None disables the store.
    artifact_dir: str | None = None


@dataclass
class VisionStats(ServeStats):
    """Vision view of the unified ``ServeStats`` (DESIGN.md §11):
    ``items`` counts real images served (each occupying one lane, so
    ``lane_steps == items``); ``pad_lanes`` counts dead batch-padding
    lanes. Issued = real + pad: a short final batch still computes its
    pad lanes, but they must never count as served work. The derived
    occupancy views (``lane_utilization``, ``pad_fraction``) live on the
    base class; the pre-§11 names survive as aliases. ``overlapped``
    counts the buckets whose answers were fetched in a later step than
    their launch (``VisionEngine.step``)."""

    overlapped: int = 0

    @property
    def images(self) -> int:
        return self.items

    @property
    def images_per_s(self) -> float:
        return self.items_per_s


class VisionEngine:
    """Micro-batching classifier over ``model.compile()``.

    The model must expose ``compile(policy=..., fuse=..., batch=...)``
    and ``input_shape(batch)`` (PaperCNN does). Short batches pad to the
    smallest compiled bucket that fits (the full ``batch`` shape when
    bucketing is off) and the pad lanes are discarded host-side — a
    bounded set of XLA programs, occupancy-scaled throughput.
    """

    def __init__(self, model, params,
                 config: VisionEngineConfig = VisionEngineConfig(),
                 clock: Clock | None = None):
        self.model = model
        self.config = config
        self.clock = clock if clock is not None else MonotonicClock()
        self._params = params
        mesh = config.mesh
        self._data_div = 1
        if mesh is not None and "data" in mesh.axis_names:
            self._data_div = mesh.shape["data"]
            if config.batch % self._data_div:
                raise ValueError(
                    f"batch {config.batch} does not divide the mesh's data "
                    f"axis ({self._data_div} devices); the compiled batch "
                    f"shape is sharded over it — pick a divisible batch")
        self.buckets = self._resolve_buckets(config)
        self._steps: dict[int, object] = {}     # bucket -> AOT executable
        self._bounds: dict[int, object] = {}    # bucket -> BoundPlan
        # bucket -> "artifact+aot" | "artifact" | "fresh" (boot telemetry)
        self.plan_source: dict[int, str] = {}
        # bucket -> seconds from compile (or artifact load) start to the
        # warm first dispatch (boot telemetry)
        self.ready_s: dict[int, float] = {}
        self._store = None
        if config.artifact_dir is not None:
            from repro.artifact.store import PlanStore
            self._store = PlanStore(config.artifact_dir)
        self.plan = self._compile_bucket(config.batch)
        if config.prewarm:
            # every ladder bucket gets its program before traffic arrives
            # (from the artifact store when available)
            self.warm()
        self.stats = VisionStats()
        self._queue: deque[tuple[int, np.ndarray]] = deque()
        self.results: dict[int, dict] = {}
        self._uid = 0
        # the bucket launched ahead, answered by the next step:
        # (uids, device logits), or None
        self._ahead: tuple[list[int], object] | None = None

    def _resolve_buckets(self, config: VisionEngineConfig
                         ) -> tuple[int, ...]:
        if config.buckets is None:
            return (config.batch,)
        if config.buckets == "auto":
            ladder = []
            b = 1
            while b < config.batch:
                ladder.append(b)
                b *= 2
            ladder.append(config.batch)
        else:
            ladder = sorted(set(int(b) for b in config.buckets))
            if not ladder or ladder[-1] != config.batch:
                raise ValueError(
                    f"buckets {config.buckets} must include the full "
                    f"batch {config.batch} (it serves saturated traffic)")
        return tuple(b for b in ladder
                     if b % self._data_div == 0) or (config.batch,)

    @staticmethod
    def bucket_name(bucket: int) -> str:
        """Artifact name of one bucket plan inside the store."""
        return f"bucket_{bucket}"

    def _compile_bucket(self, bucket: int):
        """Produce the ready program for one padded batch shape.

        With an artifact store: restore the bound plan (and, when the
        backend/versions match, the AOT executable) — zero trace/fuse/
        place/tune work; any artifact problem warns and falls through to
        the fresh pipeline. Without (or on fallback): compile + bind,
        then AOT-lower the program explicitly (``jit().lower().compile()``)
        so compile time is its own warmup phase. Either way the warm
        dispatch runs here, outside any timed serving step —
        ``VisionStats.wall_s`` measures serving only."""
        from repro.artifact.warmup import phase
        t0 = self.clock.now()
        shape = (bucket, *self.model.input_shape()[1:])
        bound = exe = None
        source = "fresh"
        if self._store is not None:
            art = self._store.load(self.bucket_name(bucket),
                                   params=self._params)
            if art is not None:
                bound = art.bound
                exe = art.executable(shape)
                source = "artifact+aot" if exe is not None else "artifact"
        if bound is None:
            plan = self.model.compile(policy=self.config.policy,
                                      fuse=self.config.fuse, batch=bucket,
                                      mesh=self.config.mesh,
                                      autotune=self.config.autotune)
            bound = plan.bind(self._params)
        if exe is None:
            from repro.artifact.store import compile_program
            with phase("compile"):
                exe = compile_program(bound, shape)
        self._bounds[bucket] = bound
        self._steps[bucket] = exe
        self.plan_source[bucket] = source
        warm = jnp.zeros(shape, jnp.float32)
        with phase("first_dispatch"):
            jax.block_until_ready(exe(warm))
        self.ready_s[bucket] = self.clock.now() - t0
        return bound.plan

    def executable(self, bucket: int):
        """The compiled (AOT) program serving ``bucket`` — its
        ``as_text()`` shows what runs on the device."""
        return self._steps[bucket]

    def bound(self, bucket: int):
        """The ``BoundPlan`` behind ``bucket`` (folded, placed weights)."""
        return self._bounds[bucket]

    def save_artifacts(self, directory=None) -> dict[str, str]:
        """Persist every compiled bucket plan (+ its AOT executable) into
        the store at ``directory`` (default: the configured
        ``artifact_dir``) — what ``launch/serve.py --save-plan`` calls.
        Returns {artifact name: fingerprint}."""
        from repro.artifact.store import PlanStore
        if directory is None and self._store is not None:
            store = self._store
        elif directory is not None:
            store = PlanStore(directory)
        else:
            raise ValueError("no artifact directory: pass one or set "
                             "VisionEngineConfig.artifact_dir")
        out = {}
        for bucket, bound in sorted(self._bounds.items()):
            shape = (bucket, *self.model.input_shape()[1:])
            name = self.bucket_name(bucket)
            out[name] = store.save(name, bound, input_shapes=[shape])
        return out

    def _bucket_for(self, k: int) -> int:
        for b in self.buckets:
            if b >= k:
                return b
        return self.buckets[-1]

    def _place_batch(self, batch):
        """Scatter a bucket-shaped batch over the mesh's ``data`` axis
        before dispatch (DESIGN.md §15): every bucket is a multiple of
        the data extent (``_resolve_buckets`` guarantees it), so replicas
        work on disjoint batch slices and the AOT program — lowered with
        this exact input sharding — never reshards on entry."""
        mesh = self.config.mesh
        if mesh is None or "data" not in getattr(mesh, "axis_names", ()):
            return jnp.asarray(batch)
        from jax.sharding import NamedSharding, PartitionSpec as P
        sh = NamedSharding(mesh, P("data", *[None] * (batch.ndim - 1)))
        return jax.device_put(jnp.asarray(batch), sh)

    def warm(self) -> None:
        """Make every ladder bucket's program exist now (from artifacts
        when available). Runs at construction by default
        (``config.prewarm``): a one-time compile must never land in a
        request's latency — the old lazy first-short-batch compile
        spiked p99 by a whole XLA compile per bucket."""
        for b in self.buckets:
            if b not in self._steps:
                self._compile_bucket(b)

    # ---------- request intake ----------
    def submit(self, image) -> int:
        """Queue one (C, H, W) image; returns its request id."""
        img = np.asarray(image, np.float32)
        want = self.model.input_shape()[1:]
        if img.shape != tuple(want):
            raise ValueError(f"image shape {img.shape} != model input "
                             f"{tuple(want)}")
        uid = self._uid
        self._uid += 1
        self._queue.append((uid, img))
        return uid

    # ---------- driving ----------
    def step(self) -> int:
        """Serve the queue's next bucket-shaped batch; returns how many
        real images this step launched.

        Each launched bucket is a span ``vision.step`` (``bucket``,
        ``lanes``) over ``place`` (stack, pad, put on the device) and
        ``launch`` (the executable call); ``fetch`` (wait for and copy
        back the logits) and ``deliver`` answer a bucket inside the last
        ``vision.step`` of the engine step. A lone bucket is answered in
        the step that launched it. When more work is certain (a full
        bucket queued behind the bucket this step answers, or any bucket
        queued behind one an earlier step launched ahead) the next
        bucket is launched ahead first, its copy back started
        (``copy_to_host_async``), and left in flight: the step's
        ``fetch`` then takes the previous bucket, and the next step
        answers the one launched ahead, its ``fetch`` marked
        ``overlapped=1`` (``VisionStats.overlapped``). At most two
        buckets are in flight. A step with nothing queued takes the
        bucket in flight outside any ``vision.step``; its time still
        counts in ``wall_s``."""
        due, self._ahead = self._ahead, None  # launched by an earlier step
        late = due is not None
        if not late and not self._queue:
            return 0
        # the step's first launch; a second one is full, compiled at boot
        bucket = self._bucket_for(len(self._queue))
        if self._queue and bucket not in self._steps:
            self._compile_bucket(bucket)    # one-time, outside the timing
        t0 = self.clock.now()
        lanes = 0
        if not late:
            uids, imgs, bucket = self._pop_bucket()
            with span("vision.step", bucket=bucket, lanes=len(uids)):
                due = (uids, self._launch(imgs, bucket, ahead=False))
                if len(self._queue) < self.config.batch:
                    self._take(*due)        # a lone bucket: answer it now
                    due = None
            lanes += len(uids)
        if due is not None and self._queue:
            uids, imgs, bucket = self._pop_bucket()
            with span("vision.step", bucket=bucket, lanes=len(uids)):
                self._ahead = (uids, self._launch(imgs, bucket, ahead=True))
                self._take(*due, overlapped=late)
            lanes += len(uids)
        elif due is not None:
            self._take(*due, overlapped=True)
        self.stats.wall_s += self.clock.now() - t0
        return lanes

    def _pop_bucket(self) -> tuple[list[int], list[np.ndarray], int]:
        """Up to ``batch`` queued requests and the bucket that fits them."""
        uids, imgs = [], []
        while self._queue and len(uids) < self.config.batch:
            uid, img = self._queue.popleft()
            uids.append(uid)
            imgs.append(img)
        return uids, imgs, self._bucket_for(len(uids))

    def _launch(self, imgs: list[np.ndarray], bucket: int, ahead: bool):
        """Place and launch ``imgs`` as one ``bucket``; returns the device
        logits. A bucket launched ``ahead`` starts its copy back as soon
        as the device finishes."""
        with span("vision.place"):
            batch = np.stack(imgs)
            if len(imgs) < bucket:      # pad to the bucket shape
                pad = np.zeros((bucket - len(imgs), *batch.shape[1:]),
                               np.float32)
                batch = np.concatenate([batch, pad])
            placed = self._place_batch(batch)
        with span("vision.launch"):
            out = self._steps[bucket](placed)
            if ahead:
                out.copy_to_host_async()
        self.stats.steps += 1
        self.stats.items += len(imgs)               # real images served
        self.stats.lane_steps += len(imgs)          # real work only
        self.stats.pad_lanes += bucket - len(imgs)  # issued, not served
        return out

    def _take(self, uids: list[int], out, overlapped: bool = False) -> None:
        """Copy one launched bucket's logits back and answer its lanes."""
        meta = {"overlapped": 1} if overlapped else {}
        with span("vision.fetch", **meta):
            logits = np.asarray(jax.device_get(out))
        with span("vision.deliver"):
            for i, uid in enumerate(uids):
                self.results[uid] = {"label": int(logits[i].argmax()),
                                     "logits": logits[i]}
        if overlapped:
            self.stats.overlapped += 1

    def run(self) -> dict[int, dict]:
        """Drain the queue; returns {uid: {"label", "logits"}}."""
        while self.has_work():
            self.step()
        return self.results

    def has_work(self) -> bool:
        """Requests queued, or a launched bucket not yet answered."""
        return bool(self._queue) or self._ahead is not None

    def unanswered(self) -> int:
        """Requests queued or in flight: submitted, not yet answered."""
        return len(self._queue) + (len(self._ahead[0]) if self._ahead
                                   else 0)
