"""Two buckets in flight in ``VisionEngine.step`` (DESIGN.md §11).

With a full bucket queued behind the bucket a step answers, the step
launches that next bucket ahead before it fetches, and the next step
answers it; a lone bucket is fetched in the step that launched it. The
front-end injects a second bucket only when it is full. The answers, the
lane counters and the step count are those of the synchronous path; only
when each answer is taken moves.
"""
import jax
import numpy as np
import pytest

from repro.models.cnn import PaperCNN, PaperCNNConfig
from repro.serve import (Frontend, FrontendConfig, ServeStats,
                         VirtualClock, VisionAdapter, VisionEngine,
                         VisionEngineConfig)

BATCH = 8


@pytest.fixture(scope="module")
def model_params():
    model = PaperCNN(PaperCNNConfig())
    return model, model.init(jax.random.PRNGKey(0))


def _engine(model_params, clock=None):
    model, params = model_params
    return VisionEngine(model, params,
                        VisionEngineConfig(batch=BATCH, buckets="auto"),
                        clock=clock)


def _images(model, n, seed=0):
    rng = np.random.RandomState(seed)
    return [rng.randn(*model.input_shape()[1:]).astype(np.float32)
            for _ in range(n)]


def _serve_synchronously(engine, images):
    """One bucket at a time, nothing queued behind it: every bucket is
    fetched in the step that launched it. Returns logits in image order."""
    uids = []
    for i in range(0, len(images), BATCH):
        uids += [engine.submit(img) for img in images[i:i + BATCH]]
        engine.step()
        assert not engine.has_work()
    return [engine.results[u]["logits"] for u in uids]


def _count_inflight(engine):
    """Wrap every bucket executable and ``_take``: returns a dict whose
    ``most`` is the most buckets launched and not yet fetched, seen at
    any launch."""
    seen = {"launched": 0, "taken": 0, "most": 0}
    for b, exe in list(engine._steps.items()):
        def launch(x, exe=exe):
            seen["launched"] += 1
            seen["most"] = max(seen["most"],
                               seen["launched"] - seen["taken"])
            return exe(x)
        engine._steps[b] = launch
    take = engine._take

    def counted_take(*args, **kwargs):
        seen["taken"] += 1
        return take(*args, **kwargs)
    engine._take = counted_take
    return seen


@pytest.mark.parametrize("n, overlapped", [(16, 1), (17, 2), (24, 2)])
def test_queued_buckets_overlap_with_synchronous_answers(model_params, n,
                                                         overlapped):
    model, _ = model_params
    images = _images(model, n)
    sync = _engine(model_params)
    want = _serve_synchronously(sync, images)
    assert sync.stats.overlapped == 0

    engine = _engine(model_params)
    inflight = _count_inflight(engine)
    uids = [engine.submit(img) for img in images]
    results = engine.run()
    assert sorted(results) == uids
    for uid, logits in zip(uids, want):     # each under its own uid
        np.testing.assert_array_equal(results[uid]["logits"], logits)
        assert results[uid]["label"] == int(logits.argmax())
    assert not engine.has_work()
    assert inflight["most"] == 2
    assert inflight["launched"] == inflight["taken"] == engine.stats.steps
    for name in ("steps", "items", "lane_steps", "pad_lanes"):
        assert getattr(engine.stats, name) == getattr(sync.stats, name), \
            name
    assert engine.stats.overlapped == overlapped


def test_overlapped_counts_buckets_answered_in_a_later_step(model_params):
    """Driven step by step: a bucket counts when its answers arrive in a
    later step than its launch, and only then."""
    model, _ = model_params
    engine = _engine(model_params)
    n = 3 * BATCH + 5
    for img in _images(model, n):
        engine.submit(img)
    launched_in, answered_in = {}, {}
    k = 0
    while engine.has_work():
        first = len(launched_in)
        for uid in range(first, first + engine.step()):
            launched_in[uid] = k
        for uid in engine.results:
            answered_in.setdefault(uid, k)
        assert len(launched_in) - len(answered_in) <= BATCH
        k += 1
    buckets = [range(i, min(i + BATCH, n)) for i in range(0, n, BATCH)]
    late = sum(answered_in[b[0]] > launched_in[b[-1]] for b in buckets)
    assert late == engine.stats.overlapped == 3


def test_a_lone_bucket_is_answered_in_its_own_step(model_params):
    """Fewer than a full bucket queued behind it: the synchronous path,
    directly and through the front-end."""
    model, _ = model_params
    engine = _engine(model_params)
    uids = [engine.submit(img) for img in _images(model, 2 * BATCH - 1)]
    assert engine.step() == BATCH
    assert all(u in engine.results for u in uids[:BATCH])
    assert engine.step() == BATCH - 1
    assert all(u in engine.results for u in uids)
    assert not engine.has_work()
    assert engine.stats.overlapped == 0

    engine = _engine(model_params)
    fe = Frontend(VisionAdapter(engine), FrontendConfig(max_queue=64))
    rids = [fe.submit(img) for img in _images(model, 2 * BATCH - 1)]
    assert fe.step() is True                 # 7 stay queued behind it
    assert sorted(fe.results) == rids[:BATCH]
    fe.run_until_drained()
    assert sorted(fe.results) == rids
    assert engine.stats.overlapped == 0


def test_frontend_drains_every_answer(model_params):
    """A backlog the engine sees only through the front-end's count: the
    buckets overlap, and the drain delivers every answer once."""
    model, _ = model_params
    engine = _engine(model_params)
    fe = Frontend(VisionAdapter(engine), FrontendConfig(max_queue=64))
    images = _images(model, 2 * BATCH + 3)
    rids = [fe.submit(img) for img in images]
    results = fe.run_until_drained()
    assert sorted(results) == rids
    assert not fe.has_work() and not engine.has_work()
    assert not engine.results                 # every answer drained
    assert fe.stats.completed == len(rids)
    assert engine.stats.steps == 3 and engine.stats.overlapped == 2
    want = _serve_synchronously(_engine(model_params), images)
    for rid, logits in zip(rids, want):
        np.testing.assert_array_equal(results[rid]["logits"], logits)


def test_a_topup_hold_still_delivers_the_bucket_in_flight(model_params):
    model, _ = model_params
    clock = VirtualClock()
    engine = _engine(model_params, clock)
    fe = Frontend(VisionAdapter(engine),
                  FrontendConfig(max_queue=64, slo_s=10.0,
                                 step_cost_s=0.01), clock)
    rids = [fe.submit(img) for img in _images(model, 2 * BATCH + 3)]
    assert fe.step(flush=False)              # two full buckets go: the
    assert sorted(fe.results) == rids[:BATCH]    # first is answered, the
    assert engine.has_work()                     # second stays in flight
    assert fe.step(flush=False)              # 3 queued: held, and the
    assert sorted(fe.results) == rids[:2 * BATCH]    # second arrives
    assert len(fe.core) == 3 and not engine.has_work()
    assert fe.step(flush=False) is False     # still held, nothing in flight
    fe.run_until_drained()
    assert sorted(fe.results) == rids
    assert engine.stats.overlapped == 1 and fe.stats.holds == 1


class _PipeSim:
    """Bucket-forming stub of the vision engine's two buckets in flight,
    on a virtual clock: with a bucket queued behind the one a step
    answers, the step launches it ahead and the next step answers it. A
    step that answers its own bucket and leaves nothing in flight costs
    ``ROUND_TRIP``, any other step half that."""

    ROUND_TRIP = 0.002
    forms_buckets = True
    preferred_batch = 4

    def __init__(self, clock):
        self.clock = clock
        self.stats = ServeStats()
        self._pending, self._ahead, self._done = [], [], []

    def free_lanes(self) -> int:
        return 2 * self.preferred_batch - len(self._pending) \
            - len(self._ahead)

    def inject(self, req) -> None:
        self._pending.append(req.rid)

    def step(self) -> None:
        n = self.preferred_batch
        due, self._ahead = self._ahead, []
        late = bool(due)
        if not late:
            due, self._pending = self._pending[:n], self._pending[n:]
        if self._pending:
            self._ahead, self._pending = self._pending[:n], self._pending[n:]
        self._done += due
        self.clock.advance(self.ROUND_TRIP / 2 if late or self._ahead
                           else self.ROUND_TRIP)

    def drain(self):
        out, self._done = [(r, r) for r in self._done], []
        return out

    def has_inflight(self) -> bool:
        return bool(self._pending or self._ahead)


def test_step_estimate_is_a_buckets_dispatch_to_answer():
    """The top-up hold's estimate learns only from steps that answer all
    they dispatched: a step that leaves a bucket in flight, or only
    answers an earlier one, is shorter than a bucket's dispatch-to-answer
    time and would pull it down."""
    clock = VirtualClock()
    fe = Frontend(_PipeSim(clock), FrontendConfig(max_queue=64), clock)
    for i in range(10):
        fe.submit(i)
    fe.run_until_drained()                   # no step answers all it sent
    assert sorted(fe.results) == list(range(10))
    assert fe._step_est is None
    fe.submit(10)
    fe.run_until_drained()                   # one lone bucket
    assert fe._step_est == _PipeSim.ROUND_TRIP
