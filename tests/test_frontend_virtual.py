"""Deterministic virtual-time regressions for the serving front-end
(DESIGN.md §11).

Everything here runs under ``VirtualClock``: time advances only when the
scheduler charges it (``FrontendConfig.step_cost_s``), so every latency,
deadline miss, and percentile below is an exact hand-computable value —
no ``time.sleep`` anywhere, no tolerance windows, no flakes. The stub
``SimAdapter``/``BucketSimAdapter`` stand in for the engines so these
tests pin the *scheduling* layer alone; ``tests/test_frontend_real.py``
runs the same front-end over the real engines.
"""
import math

import numpy as np
import pytest

from repro.serve import (Frontend, FrontendConfig, MonotonicClock,
                         OpenLoopDriver, QueueFullError, ServeStats,
                         VirtualClock, percentile)
from repro.serve.queue import RequestQueue
from repro.serve.request import Request


class SimAdapter:
    """Lane-based stub engine: ``capacity`` lanes; an injected request
    occupies one lane for ``options["steps"]`` engine steps (default 1).
    ``inject`` hard-asserts the occupancy invariant the property suite
    leans on, and can refuse the first ``refuse_first`` calls with the
    typed ``QueueFullError`` to exercise evict-to-queue."""

    kind = "sim"
    forms_buckets = False

    def __init__(self, capacity: int, refuse_first: int = 0):
        self.capacity = capacity
        self.stats = ServeStats()
        self.lanes: dict[int, int] = {}          # rid -> steps remaining
        self.injected: list[int] = []            # rids, in inject order
        self.max_occupancy = 0
        self._refuse = refuse_first
        self._done: list[tuple[int, object]] = []

    @property
    def preferred_batch(self) -> int:
        return self.capacity

    def free_lanes(self) -> int:
        return self.capacity - len(self.lanes)

    def inject(self, req) -> None:
        if self._refuse > 0:
            self._refuse -= 1
            raise QueueFullError(len(self.lanes), self.capacity)
        assert len(self.lanes) < self.capacity, \
            "invariant violated: inject into a full engine"
        self.lanes[req.rid] = int(req.options.get("steps", 1))
        self.injected.append(req.rid)
        self.max_occupancy = max(self.max_occupancy, len(self.lanes))

    def step(self) -> None:
        active = len(self.lanes)
        self.stats.steps += 1
        self.stats.items += active
        self.stats.lane_steps += active
        self.stats.pad_lanes += self.capacity - active
        for rid in list(self.lanes):
            self.lanes[rid] -= 1
            if self.lanes[rid] <= 0:
                del self.lanes[rid]
                self._done.append((rid, f"result-{rid}"))

    def drain(self):
        out, self._done = self._done, []
        return out

    def has_inflight(self) -> bool:
        return bool(self.lanes)


class BucketSimAdapter:
    """Bucket-forming stub (the vision shape): every step forms one fresh
    batch of up to ``batch`` injected requests, serves it in one step,
    and pays pad lanes for the unfilled remainder — the workload the
    top-up policy exists for."""

    kind = "sim-bucket"
    forms_buckets = True

    def __init__(self, batch: int):
        self.batch = batch
        self.stats = ServeStats()
        self._pending: list[int] = []
        self._done: list[tuple[int, object]] = []

    @property
    def preferred_batch(self) -> int:
        return self.batch

    def free_lanes(self) -> int:
        return self.batch

    def inject(self, req) -> None:
        self._pending.append(req.rid)

    def step(self) -> None:
        if not self._pending:
            return
        served, self._pending = (self._pending[:self.batch],
                                 self._pending[self.batch:])
        self.stats.steps += 1
        self.stats.items += len(served)
        self.stats.lane_steps += len(served)
        self.stats.pad_lanes += self.batch - len(served)
        self._done.extend((rid, rid) for rid in served)

    def drain(self):
        out, self._done = self._done, []
        return out

    def has_inflight(self) -> bool:
        return bool(self._pending)


def _frontend(adapter, *, max_queue=64, slo_s=None, topup=True,
              step_cost_s=0.01):
    clock = VirtualClock()
    fe = Frontend(adapter,
                  FrontendConfig(max_queue=max_queue, slo_s=slo_s,
                                 topup=topup, step_cost_s=step_cost_s),
                  clock)
    return fe, clock


class TestVirtualClock:
    def test_starts_at_zero_and_advances(self):
        c = VirtualClock()
        assert c.now() == 0.0
        c.advance(1.5)
        assert c.now() == 1.5

    def test_sleep_is_advance(self):
        c = VirtualClock()
        c.sleep(0.25)
        c.sleep(0.25)
        assert c.now() == 0.5

    def test_negative_advance_rejected(self):
        c = VirtualClock()
        with pytest.raises(ValueError):
            c.advance(-0.1)

    def test_monotonic_clock_ignores_nonpositive_sleep(self):
        # MonotonicClock.sleep(<=0) must be a no-op, not an error — the
        # open-loop driver computes sleep gaps that can round to zero
        c = MonotonicClock()
        t0 = c.now()
        c.sleep(0.0)
        c.sleep(-1.0)
        assert c.now() >= t0


class TestPercentile:
    def test_nearest_rank_exact(self):
        vals = [0.01, 0.02, 0.03, 0.04]
        assert percentile(vals, 50) == 0.02
        assert percentile(vals, 95) == 0.04
        assert percentile(vals, 25) == 0.01
        assert percentile(vals, 100) == 0.04

    def test_zero_percentile_is_min(self):
        assert percentile([3.0, 1.0, 2.0], 0) == 1.0

    def test_empty_is_zero(self):
        assert percentile([], 99) == 0.0

    def test_out_of_range_raises(self):
        with pytest.raises(ValueError):
            percentile([1.0], 101)
        with pytest.raises(ValueError):
            percentile([1.0], -1)


class TestBackpressure:
    def test_frontend_queue_full_is_typed_not_a_hang(self):
        fe, _ = _frontend(SimAdapter(2), max_queue=2)
        fe.submit("a")
        fe.submit("b")
        with pytest.raises(QueueFullError) as ei:
            fe.submit("c")
        assert ei.value.size == 2 and ei.value.maxlen == 2
        assert fe.stats.submitted == 2
        assert fe.stats.rejected == 1
        # the two accepted requests still complete normally
        results = fe.run_until_drained()
        assert fe.stats.completed == 2 and len(results) == 2

    def test_engine_queue_full_is_typed(self):
        # the LM engine's internal admission queue raises the same typed
        # error (EngineConfig.max_queue routes here)
        q = RequestQueue(maxlen=1)
        q.add(Request(uid=0, prompt=np.zeros(2, np.int32), max_new_tokens=1))
        with pytest.raises(QueueFullError):
            q.add(Request(uid=1, prompt=np.zeros(2, np.int32),
                          max_new_tokens=1))

    def test_queue_bound_must_be_positive(self):
        with pytest.raises(ValueError):
            RequestQueue(maxlen=0)
        with pytest.raises(ValueError):
            Frontend(SimAdapter(1), FrontendConfig(max_queue=0),
                     VirtualClock())

    def test_evict_to_queue_not_drop(self):
        # the adapter refuses rid 0's injection (engine-side
        # backpressure); it must come back and complete, not vanish
        sim = SimAdapter(2, refuse_first=1)
        fe, _ = _frontend(sim)
        fe.submit("a", steps=1)
        fe.submit("b", steps=1)
        results = fe.run_until_drained()
        assert sorted(results) == [0, 1]
        assert fe.stats.completed == 2
        # rid 0 was evicted to the queue and injected on the next round
        assert sim.injected == [1, 0]


class TestDeadlineTrace:
    def test_hand_computed_miss_accounting(self):
        """capacity=1, 3 requests of 2 steps each at 0.01s/step, SLO 30ms:
        completions at exactly 0.02 / 0.04 / 0.06 — one hit, two misses."""
        fe, _ = _frontend(SimAdapter(1), slo_s=0.03)
        for name in ("a", "b", "c"):
            fe.submit(name, steps=2)
        fe.run_until_drained()
        s = fe.stats
        assert s.latencies == pytest.approx([0.02, 0.04, 0.06])
        assert s.completed == 3
        assert s.deadline_misses == 2
        assert s.miss_rate == pytest.approx(2 / 3)
        # nearest-rank percentiles over the exact trace
        assert s.p50_s == pytest.approx(0.04)
        assert s.p95_s == pytest.approx(0.06)
        assert s.p99_s == pytest.approx(0.06)
        # goodput window: first submit (t=0) to last completion (t=0.06)
        assert s.span_s == pytest.approx(0.06)
        assert s.goodput_rps == pytest.approx(1 / 0.06)

    def test_per_request_slo_overrides_config(self):
        fe, _ = _frontend(SimAdapter(1), slo_s=10.0)
        fe.submit("tight", slo_s=0.005, steps=1)   # will finish at 0.01
        fe.submit("loose", steps=1)                # config budget: 10s
        fe.run_until_drained()
        assert fe.stats.deadline_misses == 1

    def test_no_slo_means_no_misses(self):
        fe, _ = _frontend(SimAdapter(1))
        for i in range(4):
            fe.submit(i, steps=3)
        fe.run_until_drained()
        assert fe.stats.deadline_misses == 0
        assert fe.stats.miss_rate == 0.0

    def test_late_requests_served_not_dropped(self):
        # a request past its deadline is still served and counted as a
        # miss — the queue never silently sheds accepted work
        fe, _ = _frontend(SimAdapter(1), slo_s=0.001)
        fe.submit("a", steps=5)
        results = fe.run_until_drained()
        assert results[0] == "result-0"
        assert fe.stats.completed == 1
        assert fe.stats.deadline_misses == 1


class TestEdfOrdering:
    def test_tighter_deadline_dispatches_first(self):
        sim = SimAdapter(1)
        fe, _ = _frontend(sim)
        fe.submit("loose", slo_s=10.0, steps=1)   # rid 0
        fe.submit("tight", slo_s=0.1, steps=1)    # rid 1
        fe.run_until_drained()
        assert sim.injected == [1, 0]

    def test_fcfs_among_equal_deadlines(self):
        sim = SimAdapter(1)
        fe, clock = _frontend(sim, slo_s=None)    # all deadlines == inf
        for i in range(5):
            fe.submit(i, steps=1)
        fe.run_until_drained()
        assert sim.injected == [0, 1, 2, 3, 4]

    def test_requeue_preserves_dispatch_order(self):
        sim = SimAdapter(2, refuse_first=1)
        fe, _ = _frontend(sim)
        for i in range(4):
            fe.submit(i, steps=1)
        fe.run_until_drained()
        # rid 0's refused injection went back with its original seq, so
        # it still dispatches before every not-yet-picked rid
        assert sim.injected.index(0) < sim.injected.index(2)
        assert sim.injected.index(0) < sim.injected.index(3)
        assert sorted(sim.injected) == [0, 1, 2, 3]


class TestTopUpPolicy:
    @staticmethod
    def _staggered(topup: bool):
        fe, clock = _frontend(BucketSimAdapter(4), slo_s=1.0, topup=topup)
        arrivals = [(0.000, "a", {}), (0.005, "b", {}),
                    (0.010, "c", {}), (0.015, "d", {})]
        driver = OpenLoopDriver(fe, arrivals)
        driver.run(max_steps=100)
        return fe.stats

    def test_topup_beats_always_open_new_bucket(self):
        """Scripted staggered arrivals into a batch-4 bucket former: the
        top-up policy holds the partial bucket (deadlines afford it) and
        serves one full batch; the greedy policy opens a bucket per
        arrival wave and pays pad lanes for each."""
        held = self._staggered(topup=True)
        greedy = self._staggered(topup=False)
        assert held.completed == greedy.completed == 4
        assert held.steps < greedy.steps
        assert held.pad_lanes < greedy.pad_lanes
        assert held.lane_utilization > greedy.lane_utilization
        assert held.goodput_rps >= greedy.goodput_rps

    def test_topup_exact_trace(self):
        # with top-up: all four arrivals coalesce into ONE full bucket
        s = self._staggered(topup=True)
        assert s.steps == 1
        assert s.pad_lanes == 0
        assert s.latencies == [pytest.approx(0.025), pytest.approx(0.020),
                               pytest.approx(0.015), pytest.approx(0.010)]

    def test_deadline_pressure_forces_partial_dispatch(self):
        # flush=False: more arrivals may come, so only the deadline
        # decides. A patient request is held for top-up; an urgent one
        # (slack < 2x the step estimate) dispatches as a partial bucket.
        patient, _ = _frontend(BucketSimAdapter(4), slo_s=1.0, topup=True)
        patient.submit("can-wait")
        assert patient.step(flush=False) is False     # held
        assert patient.has_work()

        urgent, _ = _frontend(BucketSimAdapter(4), slo_s=0.015, topup=True)
        urgent.submit("cannot")
        assert urgent.step(flush=False) is True       # dispatched now
        assert urgent.stats.completed == 1
        assert urgent.stats.deadline_misses == 0
        assert urgent.stats.latencies == [pytest.approx(0.01)]

    def test_hold_ended_by_top_up_is_counted(self):
        # step cost 1/8 s; a lone request is held at t=0, three more
        # arrive at t=1/4 and fill the bucket, which then dispatches
        fe, clock = _frontend(BucketSimAdapter(4), slo_s=100.0,
                              step_cost_s=0.125)
        fe.submit("a")
        assert fe.step(flush=False) is False          # held from t=0
        clock.advance(0.125)
        assert fe.step(flush=False) is False          # still held
        clock.advance(0.125)
        for name in "bcd":
            fe.submit(name)
        assert fe.step(flush=False) is True           # full: dispatched
        assert (fe.stats.hold_s, fe.stats.holds) == (0.25, 1)
        assert fe.stats.pad_lanes == 0
        # a full bucket at once is never held, so the counters stay
        for name in "efgh":
            fe.submit(name)
        assert fe.step(flush=False) is True
        assert (fe.stats.hold_s, fe.stats.holds) == (0.25, 1)

    def test_hold_ended_by_deadline_is_counted(self):
        # deadline 1/2 s, step estimate 1/8 s: held while the slack is
        # over 2 x 1/8 s, i.e. at t=0 and t=1/8; dispatched at t=1/4
        fe, clock = _frontend(BucketSimAdapter(4), slo_s=0.5,
                              step_cost_s=0.125)
        fe.submit("a")
        assert fe.step(flush=False) is False
        clock.advance(0.125)
        assert fe.step(flush=False) is False
        clock.advance(0.125)
        assert fe.step(flush=False) is True           # partial bucket
        assert (fe.stats.hold_s, fe.stats.holds) == (0.25, 1)
        assert fe.stats.pad_lanes == 3
        assert fe.requests[0].dispatch_t == 0.25

    def test_flush_dispatches_partial_bucket(self):
        # closed-loop (flush=True default): a partial bucket never holds
        fe, _ = _frontend(BucketSimAdapter(4), slo_s=100.0, topup=True)
        fe.submit("a")
        fe.run_until_drained()
        assert fe.stats.completed == 1
        assert fe.stats.steps == 1
        assert fe.stats.pad_lanes == 3
        assert (fe.stats.hold_s, fe.stats.holds) == (0.0, 0)


class TestFrontendLoop:
    def test_stalled_adapter_raises_not_spins(self):
        class Stalled(SimAdapter):
            def free_lanes(self):
                return 0

        fe, _ = _frontend(Stalled(1))
        fe.submit("stuck")
        with pytest.raises(RuntimeError, match="stalled"):
            fe.run_until_drained(max_steps=10)

    def test_results_keyed_by_rid(self):
        fe, _ = _frontend(SimAdapter(2))
        rids = [fe.submit(c, steps=1) for c in "abc"]
        results = fe.run_until_drained()
        assert sorted(results) == sorted(rids) == [0, 1, 2]
        assert results[1] == "result-1"

    def test_wall_s_accumulates_virtual_step_cost(self):
        fe, clock = _frontend(SimAdapter(1))
        fe.submit("a", steps=3)
        fe.run_until_drained()
        assert fe.stats.steps == 3
        assert fe.stats.wall_s == pytest.approx(0.03)
        assert clock.now() == pytest.approx(0.03)
        assert fe.stats.items_per_s == pytest.approx(3 / 0.03)


class TestOpenLoopDriver:
    @staticmethod
    def _run_once(seed: int):
        rng = np.random.RandomState(seed)
        times = np.cumsum(rng.exponential(0.01, size=12))
        arrivals = [(float(t), i, {"steps": int(rng.randint(1, 4))})
                    for i, t in enumerate(times)]
        fe, _ = _frontend(SimAdapter(2), slo_s=0.05)
        driver = OpenLoopDriver(fe, arrivals)
        driver.run(max_steps=500)
        return fe.stats

    def test_same_seed_identical_stats(self):
        a, b = self._run_once(7), self._run_once(7)
        assert a.latencies == b.latencies          # bitwise, not approx
        assert (a.steps, a.items, a.pad_lanes) == \
            (b.steps, b.items, b.pad_lanes)
        assert (a.completed, a.deadline_misses) == \
            (b.completed, b.deadline_misses)
        assert a.goodput_rps == b.goodput_rps

    def test_all_arrivals_accounted(self):
        s = self._run_once(3)
        assert s.submitted == 12
        assert s.completed == 12
        assert s.rejected == 0

    def test_shed_arrivals_are_counted_rejections(self):
        fe, _ = _frontend(SimAdapter(1), max_queue=1)
        arrivals = [(0.0, i, {"steps": 4}) for i in range(4)]
        driver = OpenLoopDriver(fe, arrivals)
        driver.run(max_steps=200)
        # the burst lands before any dispatch: one accepted, three refused
        # at intake (typed) and shed by the open-loop driver (no retry)
        assert fe.stats.rejected == len(driver.shed) == 3
        assert fe.stats.submitted == fe.stats.completed == 1
