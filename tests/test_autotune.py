"""Measured autotuner + batch-blocked kernels (DESIGN.md §10).

Pins the four contracts of the tuning subsystem:

  * persistence — versioned JSON roundtrip with platform-scoped keys;
    corrupt / unknown-version / legacy files fall back to heuristics with
    a warning, never an exception;
  * numerics — tile parameters (including the batch block ``bb``) never
    change results: autotuned == heuristic-tiled bitwise, BB>1 == BB=1
    bitwise, across quant modes and for both kernel families;
  * plumbing — a cache entry actually steers the kernel launch, and a
    plan compiled with ``autotune=True`` bakes per-stage winners into the
    BoundPlan (with output bitwise-equal to the untuned plan);
  * scoping — tuning only happens where tiles bind (the pallas backend)
    and entries measured on another platform are invisible here.
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.ops.autotune as autotune
from repro.kernels.conv_window.ops import conv2d_window
from repro.kernels.fused_cwp.ops import fused_conv_window
from repro.ops import (ExecPolicy, TUNING_CACHE, TuningCache, ensure_tuned,
                       fused_conv_block, use_policy)
from repro.ops.tiling import SCHEMA_VERSION, conv_signature, tile_params

KEY = jax.random.PRNGKey(0)
X = jax.random.normal(KEY, (5, 3, 12, 12))
W = jax.random.normal(jax.random.PRNGKey(1), (8, 3, 3, 3))
B = jax.random.normal(jax.random.PRNGKey(2), (8,))


@pytest.fixture(autouse=True)
def _isolated_cache(monkeypatch):
    """Each test sees an empty global cache (and cheap tuner timing);
    whatever it measures is discarded afterwards."""
    saved = TUNING_CACHE.snapshot()
    TUNING_CACHE.clear()
    monkeypatch.setattr(autotune, "TUNE_WARMUP", 0)
    monkeypatch.setattr(autotune, "TUNE_ITERS", 1)
    yield
    TUNING_CACHE.restore(saved)


# ---------------------------------------------------------- persistence

class TestPersistence:
    def test_roundtrip(self, tmp_path):
        cache = TuningCache()
        cache.put("fused_conv_block", (5, 3, 12, 12, 8, 3, 3, 1, 1),
                  jnp.float32, {"pb": 2, "mb": 8, "bb": 4})
        cache.put("qmatmul", (64, 32, 16), jnp.int8,
                  {"bm": 64, "bn": 16, "bk": 32})
        path = tmp_path / "cache.json"
        cache.save(path)
        doc = json.loads(path.read_text())
        assert doc["version"] == SCHEMA_VERSION
        assert all("platform" in row for row in doc["entries"])

        fresh = TuningCache()
        assert fresh.load(path) == 2
        assert fresh.get("fused_conv_block",
                         (5, 3, 12, 12, 8, 3, 3, 1, 1),
                         jnp.float32) == {"pb": 2, "mb": 8, "bb": 4}
        assert fresh.get("qmatmul", (64, 32, 16), jnp.int8) == \
            {"bm": 64, "bn": 16, "bk": 32}

    def test_corrupt_file_warns_and_loads_nothing(self, tmp_path):
        path = tmp_path / "corrupt.json"
        path.write_text("{not json at all")
        cache = TuningCache()
        with pytest.warns(UserWarning, match="corrupt"):
            assert cache.load(path) == 0
        assert len(cache) == 0

    def test_unknown_version_warns_and_loads_nothing(self, tmp_path):
        path = tmp_path / "future.json"
        path.write_text(json.dumps({"version": SCHEMA_VERSION + 999,
                                    "entries": [{"op": "conv2d"}]}))
        cache = TuningCache()
        with pytest.warns(UserWarning, match="unknown schema version"):
            assert cache.load(path) == 0
        assert len(cache) == 0

    def test_legacy_list_format_still_loads(self, tmp_path):
        path = tmp_path / "legacy.json"
        path.write_text(json.dumps([
            {"op": "tree_reduce_sum", "shape": [509, 144],
             "dtype": "float32", "params": {"rb": 64}}]))
        cache = TuningCache()
        assert cache.load(path) == 1
        # platform-less rows key under the current platform
        assert cache.get("tree_reduce_sum", (509, 144),
                         jnp.float32) == {"rb": 64}

    def test_stale_prebatch_conv_rows_are_skipped(self, tmp_path):
        """PR-2-era conv entries (8-element, batch-less signatures) can
        never match a lookup now — they must not count as loaded."""
        path = tmp_path / "legacy.json"
        path.write_text(json.dumps([
            {"op": "conv2d", "shape": [1, 28, 28, 15, 3, 3, 1, 1],
             "dtype": "float32", "params": {"rb": 2}}]))
        cache = TuningCache()
        with pytest.warns(UserWarning, match="pre-batch signature"):
            assert cache.load(path) == 0
        assert len(cache) == 0

    def test_heuristics_survive_corrupt_cache(self, tmp_path):
        """A corrupt cache file must not change what the wrapper runs:
        tile resolution falls straight through to the heuristics."""
        path = tmp_path / "corrupt.json"
        path.write_text("]")
        with pytest.warns(UserWarning):
            TUNING_CACHE.load(path)
        ref = fused_conv_window(X, W, B)           # heuristic tiles
        np.testing.assert_array_equal(np.asarray(ref),
                                      np.asarray(fused_conv_window(X, W, B)))


# ---------------------------------------------------- cache key scoping

class TestCacheScoping:
    def test_platform_scoped_entries(self):
        sig = conv_signature(X.shape, W.shape, (1, 1))
        TUNING_CACHE.put("conv2d", sig, X.dtype, {"rb": 7}, platform="tpu")
        # measured-on-TPU tiles are invisible on this (CPU) platform
        assert TUNING_CACHE.get("conv2d", sig, X.dtype) is None
        got = tile_params("conv2d", sig, X.dtype, {"rb": 1, "mb": 8, "bb": 1})
        assert got["rb"] == 1

    def test_cache_entry_steers_the_launch(self, monkeypatch):
        """A tuned entry must actually reach the kernel launch (every
        cached value off the heuristic point: M=16 defaults to mb=16,
        and mb=8 is a legal block — a multiple of the 8-row tile)."""
        import repro.kernels.fused_cwp.ops as fops
        seen = {}
        real = fops._fused_cwp_jit

        def spy(*args, **kwargs):
            seen.update(kwargs)
            return real(*args, **kwargs)

        monkeypatch.setattr(fops, "_fused_cwp_jit", spy)
        w16 = jax.random.normal(jax.random.PRNGKey(1), (16, 3, 3, 3))
        sig = conv_signature(X.shape, w16.shape, (1, 1))
        TUNING_CACHE.put("fused_conv_block", sig, X.dtype,
                         {"pb": 2, "mb": 8, "bb": 5})
        fused_conv_window(X, w16, None)
        assert (seen["pb"], seen["mb"], seen["bb"]) == (2, 8, 5)

    def test_illegal_cached_channel_block_is_legalized(self, monkeypatch):
        """A cached mb that breaks the block rule (not a multiple of 8,
        not all of M) is clamped to a legal block, never launched."""
        import repro.kernels.fused_cwp.ops as fops
        seen = {}
        real = fops._fused_cwp_jit

        def spy(*args, **kwargs):
            seen.update(kwargs)
            return real(*args, **kwargs)

        monkeypatch.setattr(fops, "_fused_cwp_jit", spy)
        sig = conv_signature(X.shape, W.shape, (1, 1))
        TUNING_CACHE.put("fused_conv_block", sig, X.dtype,
                         {"pb": 2, "mb": 4, "bb": 5})
        fused_conv_window(X, W, B)
        assert (seen["pb"], seen["mb"], seen["bb"]) == (2, 8, 5)


# ------------------------------------------------------------- numerics

QUANT_POLICIES = [
    ExecPolicy(backend="pallas", quant="none"),
    ExecPolicy(backend="pallas", quant="qformat"),
    ExecPolicy(backend="pallas", quant="int8"),
]


class TestBatchBlockParity:
    @pytest.mark.parametrize("pol", QUANT_POLICIES,
                             ids=[p.quant for p in QUANT_POLICIES])
    @pytest.mark.parametrize("bb", [2, 4, 5])
    def test_fused_bb_bitwise_equals_bb1(self, pol, bb):
        """The batch-blocked fused pipeline is a pure scheduling change:
        BB>1 output is bitwise-identical to BB=1 in every quant mode
        (each image's contraction is the same static program)."""
        ref = fused_conv_block(X, W, B, policy=pol.with_options(
            tiling={"fused_conv_block.bb": 1}))
        out = fused_conv_block(X, W, B, policy=pol.with_options(
            tiling={"fused_conv_block.bb": bb}))
        np.testing.assert_array_equal(np.asarray(ref), np.asarray(out))

    @pytest.mark.parametrize("bb", [2, 3, 5])
    def test_conv_window_bb_bitwise_equals_bb1(self, bb):
        ref = conv2d_window(X, W, B, bb=1)
        out = conv2d_window(X, W, B, bb=bb)
        np.testing.assert_array_equal(np.asarray(ref), np.asarray(out))

    def test_bb_beyond_batch_clamps(self):
        out = fused_conv_window(X, W, B, bb=64)
        np.testing.assert_array_equal(
            np.asarray(fused_conv_window(X, W, B, bb=1)), np.asarray(out))


class TestAutotune:
    def test_autotuned_bitwise_equals_heuristic(self):
        """The measured winner never changes numerics — only time."""
        ref = fused_conv_window(X, W, B)           # heuristic tiles
        pol = ExecPolicy(backend="pallas", autotune=True)
        best = ensure_tuned("fused_conv_block", X, W, B, stride=(1, 1),
                            policy=pol)
        assert best is not None and {"pb", "mb", "bb"} <= set(best)
        sig = conv_signature(X.shape, W.shape, (1, 1))
        assert TUNING_CACHE.get("fused_conv_block", sig, X.dtype) == best
        out = fused_conv_window(X, W, B)           # now runs tuned tiles
        np.testing.assert_array_equal(np.asarray(ref), np.asarray(out))

    def test_wrapper_tunes_on_first_concrete_call(self):
        with use_policy(ExecPolicy(backend="pallas", autotune=True)):
            fused_conv_window(X, W, B)
        sig = conv_signature(X.shape, W.shape, (1, 1))
        assert TUNING_CACHE.get("fused_conv_block", sig, X.dtype) is not None

    def test_non_pallas_dispatch_tunes_nothing(self):
        # CPU auto-dispatch resolves to xla, where tiles don't bind
        assert ensure_tuned("conv2d", X, W, None, stride=(1, 1)) is None
        assert len(TUNING_CACHE) == 0


class TestPlanAutotune:
    # the two fused-stage signatures of the batch-4 MNIST plan
    SIG1 = (4, 1, 28, 28, 15, 3, 3, 1, 1)
    SIG2 = (4, 15, 13, 13, 20, 6, 6, 1, 1)

    @pytest.mark.parametrize("quant", ["none", "int8"])
    def test_bind_bakes_cached_winners_and_keeps_numerics(self, quant):
        """Tuned tiles from the cache (here: seeded, as a persisted
        op_sweep table would) are baked into the BoundPlan per stage,
        and never change the plan's output."""
        from repro.models.cnn import PaperCNN, PaperCNNConfig
        model = PaperCNN(PaperCNNConfig())
        params = model.init(KEY)
        x = jax.random.normal(jax.random.PRNGKey(3), (4, 1, 28, 28))
        pol = ExecPolicy(quant=quant, backend="pallas")
        ref = model.compile(policy=pol, batch=4).bind(params)(x)
        # non-heuristic winners, as a measured run on other hardware
        # might produce them
        TUNING_CACHE.put("fused_conv_block", self.SIG1, jnp.float32,
                         {"pb": 2, "mb": 5, "bb": 4})
        TUNING_CACHE.put("fused_conv_block", self.SIG2, jnp.float32,
                         {"pb": 1, "mb": 10, "bb": 2})
        TUNING_CACHE.put("qmatmul", (4, 320, 10), jnp.int8,
                         {"bm": 2, "bn": 5, "bk": 64})
        bound = model.compile(policy=pol, batch=4,
                              autotune=True).bind(params)
        # both fused stages baked; int8 adds the dense qmatmul stage
        assert len(bound.tuned) == (3 if quant == "int8" else 2)
        baked = {k: v for tiles in bound.tuned.values()
                 for k, v in tiles.items()}
        assert baked["fused_conv_block.bb"] in (2, 4)
        if quant == "int8":
            assert baked["qmatmul.bk"] == 64
        np.testing.assert_array_equal(np.asarray(ref),
                                      np.asarray(bound(x)))

    def test_bind_measures_on_cache_miss(self):
        """An empty cache means bind really measures: every tunable stage
        gains a cache entry, and tuning never changes the output (a
        heuristic-equal winner bakes nothing — same program either way)."""
        from repro.models.cnn import PaperCNN, PaperCNNConfig
        model = PaperCNN(PaperCNNConfig())
        params = model.init(KEY)
        x = jax.random.normal(jax.random.PRNGKey(3), (4, 1, 28, 28))
        pol = ExecPolicy(quant="none", backend="pallas")
        ref = model.compile(policy=pol, batch=4).bind(params)(x)
        assert len(TUNING_CACHE) == 0
        bound = model.compile(policy=pol, batch=4,
                              autotune=True).bind(params)
        assert TUNING_CACHE.get("fused_conv_block", self.SIG1,
                                jnp.float32) is not None
        assert TUNING_CACHE.get("fused_conv_block", self.SIG2,
                                jnp.float32) is not None
        np.testing.assert_array_equal(np.asarray(ref),
                                      np.asarray(bound(x)))

    def test_pin_heuristic_tiles_reverts_bad_winners(self):
        """Plan-level winner validation: pinning writes the heuristic
        point over a regressing cache entry, after which bind bakes
        nothing (the plan is the heuristic program again)."""
        from repro.models.cnn import PaperCNN, PaperCNNConfig
        model = PaperCNN(PaperCNNConfig())
        params = model.init(KEY)
        TUNING_CACHE.put("fused_conv_block", self.SIG1, jnp.float32,
                         {"pb": 1, "mb": 3, "bb": 4})   # a "bad" winner
        plan = model.compile(policy=ExecPolicy(backend="pallas"),
                             batch=4, autotune=True)
        assert plan.bind(params).tuned          # baked the bad winner
        assert plan.pin_heuristic_tiles(params) == 2
        hit = TUNING_CACHE.get("fused_conv_block", self.SIG1, jnp.float32)
        assert hit == {"pb": 13, "mb": 15, "bb": 1}     # the heuristic
        assert plan.bind(params).tuned == {}

    def test_persisted_cache_skips_measurement(self, tmp_path,
                                               monkeypatch):
        """The serve scenario: winners persisted by one process are
        loaded by a later bind, which then re-measures nothing."""
        from repro.models.cnn import PaperCNN, PaperCNNConfig
        model = PaperCNN(PaperCNNConfig())
        params = model.init(KEY)
        pol = ExecPolicy(backend="pallas")
        plan = model.compile(policy=pol, batch=2, autotune=True)
        plan.bind(params)
        assert len(TUNING_CACHE) >= 2   # both fused stages measured
        path = tmp_path / "tuned.json"
        TUNING_CACHE.save(path)

        TUNING_CACHE.clear()
        assert TUNING_CACHE.load(path) >= 2
        calls = []
        monkeypatch.setattr(autotune, "_measure",
                            lambda *a, **k: calls.append(1) or 1.0)
        plan.bind(params)               # every stage cache-hits
        assert not calls, "persisted winners must skip re-measurement"
