"""Streaming spatial tiler (repro.stream, DESIGN.md §13).

Pins the subsystem's four contracts:

  * halo math — bands partition the output, adjacent input ranges overlap
    by exactly ``halo_rows``, pooled bands cut only at even conv rows, and
    the streamed-row total is untiled + (n_bands-1)·halo (the line-buffer
    law lifted to tiles);
  * numerics — streamed == untiled **bitwise**, across quant modes ×
    kernel families × K × stride × ragged-last-band heights, eager and
    plan-level;
  * placement — ``place_spatial_tiling`` stamps exactly the over-budget
    unsharded stages (MNIST stays untiled at the default budget, so
    existing plans and fingerprints are unchanged), and the stamped
    tiling is part of the plan's content identity (a plan saved untiled
    never silently serves tiled);
  * tuning — the tile height is a real autotuner axis: candidates are
    visible, a measured non-heuristic winner lands in the cache, and
    plans bake it like any other tile parameter.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.ops.autotune as autotune
from repro.artifact import load_plan
from repro.artifact.fingerprint import plan_fingerprint
from repro.models.cnn import PaperCNN, PaperCNNConfig
from repro.models.vgg import VGGStyleCNN, VGGStyleCNNConfig
from repro.ops import (ExecPolicy, TUNING_CACHE, conv2d, fused_conv_block,
                       use_policy)
from repro.ops.tiling import conv_signature
from repro.stream import (STREAM_VMEM_BUDGET_BYTES, SpatialTiling,
                          band_working_set, choose_tile_rows, conv_bands,
                          halo_rows, place_spatial_tiling, pooled_bands,
                          stream_conv2d, stream_fused_conv_block,
                          streamed_input_rows, tiling_from_doc,
                          tiling_to_doc)
from repro.stream.executor import resolve_tile_rows

KEY = jax.random.PRNGKey(0)
QUANTS = ("none", "qformat", "int8")


def _lattice(key, shape, frac=6, maxcode=31):
    """Small integer multiples of 2^-frac. Float convs of different
    shapes (a band vs the whole frame) reduce in different orders on
    XLA:CPU, so quant='none' is bitwise only when the arithmetic is
    exact: on this lattice every product and partial sum of these convs
    is representable in fp32. qformat and int8 quantize their operands
    onto exact grids themselves and keep random data."""
    c = jax.random.randint(key, shape, -maxcode, maxcode + 1)
    return c.astype(jnp.float32) * (2.0 ** -frac)


def _lattice_tree(tree):
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    return jax.tree_util.tree_unflatten(treedef, [
        _lattice(jax.random.PRNGKey(i + 100), leaf.shape)
        for i, leaf in enumerate(leaves)])


def _data(quant, key, shape):
    return (_lattice(key, shape) if quant == "none"
            else jax.random.normal(key, shape))


@pytest.fixture(autouse=True)
def _isolated_cache(monkeypatch):
    saved = TUNING_CACHE.snapshot()
    TUNING_CACHE.clear()
    monkeypatch.setattr(autotune, "TUNE_WARMUP", 0)
    monkeypatch.setattr(autotune, "TUNE_ITERS", 1)
    yield
    TUNING_CACHE.restore(saved)


# ---------------------------------------------------------- halo math

class TestHaloMath:
    @pytest.mark.parametrize("ho,tile,kh,sh", [(26, 7, 3, 1), (8, 3, 6, 1),
                                               (13, 4, 3, 2), (5, 5, 5, 1),
                                               (9, 1, 2, 1)])
    def test_conv_bands_partition_and_overlap(self, ho, tile, kh, sh):
        bands = conv_bands(ho, tile, kh, sh)
        # output ranges partition [0, ho)
        assert bands[0][0] == 0 and bands[-1][1] == ho
        for (a, b, _, _), (c, d, _, _) in zip(bands, bands[1:]):
            assert b == c
        # each band reads (rb-1)·sh + kh rows; adjacent bands overlap on
        # exactly the halo
        for lo, hi, in_lo, in_hi in bands:
            assert in_hi - in_lo == (hi - lo - 1) * sh + kh
        for (_, _, _, hi0), (_, _, lo1, _) in zip(bands, bands[1:]):
            assert hi0 - lo1 == halo_rows(kh, sh)

    def test_streamed_rows_identity(self):
        for ho, tile, kh, sh in [(26, 7, 3, 1), (8, 3, 6, 1), (13, 4, 3, 2)]:
            nbands = -(-ho // tile)
            assert streamed_input_rows(ho, tile, kh, sh) == \
                (ho - 1) * sh + kh + (nbands - 1) * halo_rows(kh, sh)

    @pytest.mark.parametrize("po,tile,kh,sh,h", [(13, 2, 3, 1, 28),
                                                 (4, 3, 6, 1, 13),
                                                 (5, 2, 5, 1, 15),
                                                 (3, 2, 3, 2, 13)])
    def test_pooled_bands_cut_even_conv_rows(self, po, tile, kh, sh, h):
        bands = pooled_bands(po, tile, kh, sh, h)
        assert bands[0][0] == 0 and bands[-1][1] == po
        for p0, p1, in_lo, in_hi in bands:
            assert in_lo == 2 * p0 * sh          # even conv-row cut: no
            assert in_lo % 2 == 0 or sh > 1      # pool window straddles
            assert in_hi <= h
        for (_, p1a, _, _), (p0b, _, _, _) in zip(bands, bands[1:]):
            assert p1a == p0b

    def test_choose_tile_rows_fits_budget(self):
        n, h, w, m, kh, kw = 3, 224, 224, 8, 5, 5
        tr = choose_tile_rows(n, h, w, m, kh, kw, (1, 1), 4, pooled=True,
                              budget=STREAM_VMEM_BUDGET_BYTES)
        assert 1 <= tr <= (h - kh + 1) // 2
        assert band_working_set(n, w, m, w - kw + 1, tr, kh, 1, 4,
                                pooled=True) <= STREAM_VMEM_BUDGET_BYTES
        # a budget smaller than any band still streams: 1-row floor
        assert choose_tile_rows(n, h, w, m, kh, kw, (1, 1), 4,
                                pooled=True, budget=1) == 1
        # band working set is H-independent (the fixed-VMEM claim)
        assert band_working_set(n, w, m, w - kw + 1, tr, kh, 1, 4,
                                pooled=True) == \
            band_working_set(n, w, m, w - kw + 1, tr, kh, 1, 4, pooled=True)

    def test_spec_validation_and_doc_roundtrip(self):
        with pytest.raises(ValueError, match="tile_rows"):
            SpatialTiling(tile_rows=0, halo=2)
        with pytest.raises(ValueError, match="halo"):
            SpatialTiling(tile_rows=2, halo=-1)
        spec = SpatialTiling(tile_rows=7, halo=4, pooled=True,
                             budget_bytes=50_000)
        assert tiling_from_doc(tiling_to_doc(spec)) == spec
        assert tiling_to_doc(None) is None and tiling_from_doc(None) is None


# ------------------------------------------------------ bitwise equality

def _conv_case(quant, k, s, h, backend=None):
    pol = ExecPolicy(quant=quant, **({"backend": backend} if backend else {}))
    x = _data(quant, KEY, (2, 3, h, h + 2))
    w = _data(quant, jax.random.PRNGKey(1), (4, 3, k, k))
    b = _data(quant, jax.random.PRNGKey(2), (4,))
    tiling = SpatialTiling(tile_rows=2, halo=halo_rows(k, s))
    got = stream_conv2d(x, w, b, stride=(s, s), tiling=tiling, policy=pol)
    want = conv2d(x, w, b, stride=(s, s), policy=pol)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


class TestBitwiseConv:
    """stream_conv2d == conv2d bitwise: quant × K × stride; the K=5 cases
    leave a ragged last band (ho = 9 and 5 against tile_rows = 2)."""

    @pytest.mark.parametrize("quant", QUANTS)
    @pytest.mark.parametrize("k,s,h", [(3, 1, 13), (3, 2, 13), (5, 1, 13),
                                       (5, 2, 13), (3, 1, 14)])
    def test_sweep(self, quant, k, s, h):
        _conv_case(quant, k, s, h)

    @pytest.mark.parametrize("quant", QUANTS)
    def test_pallas_backend(self, quant):
        """The windowed-kernel family (interpret-mode on CPU)."""
        _conv_case(quant, 3, 1, 13, backend="pallas")

    def test_ambient_policy_applies(self):
        x = jax.random.normal(KEY, (1, 2, 11, 11))
        w = jax.random.normal(jax.random.PRNGKey(1), (3, 2, 3, 3))
        tiling = SpatialTiling(tile_rows=4, halo=2)
        with use_policy(ExecPolicy(quant="qformat")):
            got = stream_conv2d(x, w, None, tiling=tiling)
            want = conv2d(x, w, None)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def _fused_case(quant, k, s, h, backend=None, tile=2):
    pol = ExecPolicy(quant=quant, **({"backend": backend} if backend else {}))
    x = _data(quant, KEY, (2, 3, h, h))
    w = _data(quant, jax.random.PRNGKey(1), (4, 3, k, k))
    b = _data(quant, jax.random.PRNGKey(2), (4,))
    tiling = SpatialTiling(tile_rows=tile, halo=halo_rows(k, s), pooled=True)
    got = stream_fused_conv_block(x, w, b, stride=(s, s), odd="drop",
                                  tiling=tiling, policy=pol)
    want = fused_conv_block(x, w, b, stride=(s, s), odd="drop", policy=pol)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


class TestBitwiseFused:
    """stream_fused_conv_block == fused_conv_block bitwise — pooled bands
    (even conv-row cuts), ragged last bands, odd='drop' trailing rows."""

    @pytest.mark.parametrize("quant", QUANTS)
    @pytest.mark.parametrize("k,s,h", [(3, 1, 13), (3, 2, 13), (5, 1, 13),
                                       (5, 2, 15), (3, 1, 16)])
    def test_sweep(self, quant, k, s, h):
        _fused_case(quant, k, s, h)

    @pytest.mark.parametrize("quant", QUANTS)
    def test_pallas_backend(self, quant):
        """The fused window kernel needs even conv maps: 14→12→6."""
        _fused_case(quant, 3, 1, 14, backend="pallas")

    def test_single_band_passthrough(self):
        """A tile covering the whole image is the untiled call."""
        _fused_case("none", 3, 1, 9, tile=64)


# ------------------------------------------------------------ placement

class TestPlacement:
    def test_mnist_stays_untiled_at_default_budget(self):
        plan = PaperCNN(PaperCNNConfig()).compile()
        assert [n.id for n in plan.graph
                if getattr(n, "tiling", None)] == []

    def test_vgg224_tiles_early_blocks(self):
        plan = VGGStyleCNN(VGGStyleCNNConfig()).compile()
        tiled = [n for n in plan.graph if getattr(n, "tiling", None)]
        assert len(tiled) == 2               # blocks 0 and 1 exceed 1 MiB
        for n in tiled:
            t = n.tiling
            assert t.pooled and t.tile_rows >= 1
            assert t.halo == n.w.shape[2] - n.stride[0]
            assert t.budget_bytes == STREAM_VMEM_BUDGET_BYTES

    def test_budget_knob(self):
        model = VGGStyleCNN(VGGStyleCNNConfig(img_size=64))
        untiled = model.compile(stream_budget=1 << 40)
        assert not [n for n in untiled.graph if getattr(n, "tiling", None)]
        tiled = model.compile(stream_budget=50_000)
        assert [n for n in tiled.graph if getattr(n, "tiling", None)]

    def test_pass_is_idempotent_and_skips_fitting_stages(self):
        plan = VGGStyleCNN(VGGStyleCNNConfig(img_size=64)).compile(
            stream_budget=50_000)
        g2 = place_spatial_tiling(plan.graph, budget_bytes=50_000)
        assert [tiling_to_doc(getattr(n, "tiling", None)) for n in g2] == \
            [tiling_to_doc(getattr(n, "tiling", None)) for n in plan.graph]


# ------------------------------------------------------ plan-level parity

class TestPlanParity:
    @pytest.mark.parametrize("quant", QUANTS)
    def test_paper_cnn_tiled_plan_bitwise(self, quant):
        model = PaperCNN(PaperCNNConfig())
        params = model.init(KEY)
        if quant == "none":
            params = _lattice_tree(params)
        x = _data(quant, jax.random.PRNGKey(3), (2, 1, 28, 28))
        pol = ExecPolicy(quant=quant)
        tiled_plan = model.compile(pol, batch=2, stream_budget=10_000)
        assert [n for n in tiled_plan.graph if getattr(n, "tiling", None)]
        want = model.compile(pol, batch=2)(params, x)    # untiled: default
        got = tiled_plan.bind(params)(x)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_vgg_multiblock_ragged_bitwise(self):
        """Multi-block plan at a height where bands go ragged (quant
        none, so on lattice data — see ``_lattice``)."""
        model = VGGStyleCNN(VGGStyleCNNConfig(img_size=48))
        params = _lattice_tree(model.init(KEY))
        x = _lattice(jax.random.PRNGKey(3), model.input_shape(2))
        tiled = model.compile(batch=2, stream_budget=40_000)
        assert [n for n in tiled.graph if getattr(n, "tiling", None)]
        want = model.compile(batch=2, stream_budget=1 << 40)(params, x)
        np.testing.assert_array_equal(
            np.asarray(tiled.bind(params)(x)), np.asarray(want))


# -------------------------------------------------- fingerprint identity

class TestFingerprint:
    def test_tiling_changes_plan_identity(self):
        model = PaperCNN(PaperCNNConfig())
        untiled = model.compile()
        tiled = model.compile(stream_budget=10_000)
        assert plan_fingerprint(untiled) != plan_fingerprint(tiled)
        # and different tile budgets are different identities too
        assert plan_fingerprint(model.compile(stream_budget=5_000)) != \
            plan_fingerprint(tiled)

    def test_artifact_roundtrip_preserves_tiling(self, tmp_path):
        """A saved streamed plan restores streamed — same tiling doc,
        bitwise-same output (the stale-artifact guarantee: tiling is part
        of content identity, not a load-time re-derivation)."""
        model = PaperCNN(PaperCNNConfig())
        params = model.init(KEY)
        bound = model.compile(batch=2, stream_budget=10_000).bind(params)
        x = jax.random.normal(jax.random.PRNGKey(3), (2, 1, 28, 28))
        want = np.asarray(bound(x))
        bound.save(tmp_path / "streamed", input_shapes=[tuple(x.shape)])
        art = load_plan(tmp_path / "streamed", params=params)
        docs = [tiling_to_doc(getattr(n, "tiling", None))
                for n in art.bound.plan.graph]
        assert docs == [tiling_to_doc(getattr(n, "tiling", None))
                        for n in bound.plan.graph]
        assert any(d is not None for d in docs)
        got = np.asarray(art.program(tuple(x.shape))(jnp.asarray(x)))
        np.testing.assert_array_equal(got, want)


# ------------------------------------------------------------- autotune

class TestStreamAutotune:
    def _stage(self):
        x = jax.random.normal(KEY, (1, 3, 14, 14))
        w = jax.random.normal(jax.random.PRNGKey(1), (4, 3, 3, 3))
        b = jax.random.normal(jax.random.PRNGKey(2), (4,))
        tiling = SpatialTiling(tile_rows=2, halo=2, pooled=True)
        return x, w, b, tiling

    def test_tile_height_axis_visible(self, monkeypatch):
        """The tuner really sweeps th: on_point sees >1 distinct value."""
        monkeypatch.setattr(autotune, "_measure", lambda *a, **k: 1.0)
        x, w, b, tiling = self._stage()
        seen = []
        autotune.tune_stream_fused_conv_block(
            x, w, b, odd="drop", tiling=tiling,
            policy=ExecPolicy(backend="pallas"),
            on_point=lambda tiles, us: seen.append(tiles["th"]))
        assert len(set(seen)) > 1
        assert tiling.tile_rows in seen         # heuristic is a candidate

    def test_non_heuristic_winner_lands_in_cache(self, monkeypatch):
        """Scripted timings: a candidate off the heuristic point wins by
        >MIN_GAIN and the cache row records the non-heuristic height."""
        x, w, b, tiling = self._stage()
        # po = 6; axis = sorted({4<=6} | {2, 3, 6}) = [2, 3, 4, 6];
        # probe order: start {th:2}, then 3, 4, 6 ({th:2} memoized)
        times = iter([100.0, 10.0, 120.0, 90.0])
        monkeypatch.setattr(autotune, "_measure",
                            lambda *a, **k: next(times))
        best = autotune.tune_stream_fused_conv_block(
            x, w, b, odd="drop", tiling=tiling,
            policy=ExecPolicy(backend="pallas"))
        assert best == {"th": 3} != {"th": tiling.tile_rows}
        sig = conv_signature(x.shape, w.shape, (1, 1))
        assert TUNING_CACHE.get("stream_fused_conv_block", sig,
                                x.dtype) == {"th": 3}

    def test_cache_row_steers_executor(self):
        """A tuning-cache row overrides the SpatialTiling heuristic, and
        a policy (plan-baked) override beats both — all bitwise."""
        x, w, b, tiling = self._stage()
        pol = ExecPolicy()
        sig = conv_signature(x.shape, w.shape, (1, 1))
        assert resolve_tile_rows("stream_fused_conv_block", x, w, (1, 1),
                                 tiling, pol) == tiling.tile_rows
        TUNING_CACHE.put("stream_fused_conv_block", sig, x.dtype, {"th": 5})
        assert resolve_tile_rows("stream_fused_conv_block", x, w, (1, 1),
                                 tiling, pol) == 5
        baked = pol.with_options(
            tiling={"stream_fused_conv_block.th": 3})
        assert resolve_tile_rows("stream_fused_conv_block", x, w, (1, 1),
                                 tiling, baked) == 3
        want = fused_conv_block(x, w, b, odd="drop", policy=pol)
        for p in (pol, baked):
            got = stream_fused_conv_block(x, w, b, odd="drop",
                                          tiling=tiling, policy=p)
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_plan_bakes_cached_stream_winner(self):
        """bind(autotune=True) on a streamed plan: a cached non-heuristic
        th bakes into BoundPlan.tuned under the stream op's namespace and
        the tuned program stays bitwise-equal."""
        model = PaperCNN(PaperCNNConfig())
        params = model.init(KEY)
        x = jax.random.normal(jax.random.PRNGKey(3), (2, 1, 28, 28))
        pol = ExecPolicy(backend="pallas")
        plain = model.compile(pol, batch=2, stream_budget=10_000)
        want = plain.bind(params)(x)
        # seed non-heuristic winners for both streamed fused stages
        TUNING_CACHE.put("stream_fused_conv_block",
                         (2, 1, 28, 28, 15, 3, 3, 1, 1), jnp.float32,
                         {"th": 5})
        TUNING_CACHE.put("stream_fused_conv_block",
                         (2, 15, 13, 13, 20, 6, 6, 1, 1), jnp.float32,
                         {"th": 2})
        tuned_plan = model.compile(pol, batch=2, stream_budget=10_000,
                                   autotune=True)
        bound = tuned_plan.bind(params)
        baked = {k: v for tiles in bound.tuned.values()
                 for k, v in tiles.items()}
        assert baked.get("stream_fused_conv_block.th") in (5, 2)
        np.testing.assert_array_equal(np.asarray(bound(x)),
                                      np.asarray(want))
