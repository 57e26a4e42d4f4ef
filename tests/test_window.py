"""Paper §III.B.2: window pipeline — cycle-exact line-buffer law +
conv-oracle equivalence against jax.lax (independent second oracle)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis_compat import given, settings, st

from repro.core.window import (LineBufferSim, band_input_rows,
                               conv2d_im2col, conv2d_ref, conv_output_size,
                               extract_windows, fill_latency, halo_rows,
                               maxpool2, pool_output_size, reuse_ratio)


class TestLaws:
    def test_output_size_eq_1_2(self):
        """Paper Eq. (1)/(2) with the worked example: 5x5 input, 3x3 kernel,
        stride 2 -> 2x2 output."""
        assert conv_output_size(5, 3, 2) == 2
        assert conv_output_size(28, 3, 1) == 26
        assert conv_output_size(13, 6, 1) == 8

    def test_fill_latency_law(self):
        """T_u = (K-1)W + K - 1 (Fig. 8)."""
        assert fill_latency(3, 8) == 2 * 8 + 2
        assert fill_latency(6, 13) == 5 * 13 + 5

    def test_reuse_ratio(self):
        """(K-1)/K shared data between adjacent windows (Fig. 6)."""
        assert reuse_ratio(3) == pytest.approx(2 / 3)
        assert reuse_ratio(12) == pytest.approx(11 / 12)


class TestLineBufferSim:
    @pytest.mark.parametrize("k,w,h", [(3, 8, 6), (2, 5, 4), (3, 3, 5),
                                       (4, 10, 7), (6, 13, 13)])
    def test_cycle_exact(self, k, w, h):
        img = np.arange(h * w, dtype=np.float32).reshape(h, w)
        sim = LineBufferSim(k, w)
        wins = list(sim.run(img))
        ho, wo = h - k + 1, w - k + 1
        # II=1: exactly one valid window per valid cycle, Ho*Wo total
        assert len(wins) == ho * wo
        # first valid window appears the cycle after T_u
        assert wins[0][0] == fill_latency(k, w) + 1
        # every window content is exact
        for cyc, i, j, win in wins:
            np.testing.assert_array_equal(win, img[i:i + k, j:j + k])
        # paper's landmarks: cycle K*W holds x_(W0); cycle H*W holds the last
        bycycle = {c: (i, j) for c, i, j, _ in wins}
        assert bycycle[k * w] == (0, wo - 1)
        assert bycycle[h * w] == (ho - 1, wo - 1)

    def test_storage_sizes(self):
        """WINDOW_BUFFER K×K + SHIFT_BUFFER (K-1)×(W-K) — Fig. 7."""
        sim = LineBufferSim(3, 10)
        assert sim.wb.shape == (3, 3)
        assert sim.sb.shape == (2, 7)


def _check_linebuffer_laws(k: int, w: int, h: int) -> None:
    """One property check: fill latency T_u, II=1 window count, landmark
    cycles, window contents, and the (K-1)/K reuse ratio."""
    img = np.arange(h * w, dtype=np.float32).reshape(h, w)
    sim = LineBufferSim(k, w)
    wins = list(sim.run(img))
    ho, wo = h - k + 1, w - k + 1
    assert len(wins) == ho * wo
    assert wins[0][0] == fill_latency(k, w) + 1
    assert reuse_ratio(k) == pytest.approx((k - 1) / k)
    for cyc, i, j, win in wins:
        np.testing.assert_array_equal(win, img[i:i + k, j:j + k])
    bycycle = {c: (i, j) for c, i, j, _ in wins}
    assert bycycle[k * w] == (0, wo - 1)          # x_(W0) at cycle K·W
    assert bycycle[h * w] == (ho - 1, wo - 1)     # last window at H·W


class TestLineBufferProperties:
    """Property sweep of the T_u law and reuse ratio over K ∈ {1..7},
    including the degenerate K=1 (no shift buffer, T_u=0, reuse 0) and
    the K == W edge (window spans the full row; SHIFT_BUFFER is empty
    and WB row exits feed the row above directly)."""

    @pytest.mark.parametrize("k", range(1, 8))
    def test_sweep_k_1_to_7(self, k):
        for w in (k, k + 1, k + 5):               # k == w is the edge case
            _check_linebuffer_laws(k, w, h=k + 3)

    def test_k_equals_w_storage(self):
        sim = LineBufferSim(4, 4)
        assert sim.sb.size == 0                   # no shift buffer at K==W
        _check_linebuffer_laws(4, 4, 9)

    @given(st.integers(1, 7), st.data())
    @settings(max_examples=40, deadline=None)
    def test_hypothesis_laws(self, k, data):
        w = data.draw(st.integers(k, k + 8))
        h = data.draw(st.integers(k, k + 6))
        _check_linebuffer_laws(k, w, h)


def _check_strided_laws(kh: int, kw: int, w: int, h: int,
                        sh: int, sw: int) -> None:
    """Strided / non-square property check: the buffers shift every cycle
    (same dataflow, same T_u), the readout hits exactly the Eq. (1)-(2)
    stride grid, and every window content is exact."""
    img = np.arange(h * w, dtype=np.float32).reshape(h, w)
    sim = LineBufferSim((kh, kw), w)
    wins = list(sim.run(img, stride=(sh, sw)))
    ho, wo = (h - kh) // sh + 1, (w - kw) // sw + 1
    assert len(wins) == ho * wo
    # stride gates readout only: first valid window still lands the
    # cycle after T_u = (Kh-1)·W + Kw - 1 (top-left corner (0,0) is
    # always on the stride grid)
    assert wins[0][0] == fill_latency(kh, w, kw) + 1
    for cyc, r, c, win in wins:
        assert r % sh == 0 and c % sw == 0
        np.testing.assert_array_equal(win, img[r:r + kh, c:c + kw])
    # readout positions are exactly the VALID-conv grid
    assert [(r, c) for _, r, c, _ in wins] == \
        [(r * sh, c * sw) for r in range(ho) for c in range(wo)]


class TestLineBufferStrideNonSquare:
    """§III.B.2 generalized: stride > 1 (readout gating, same fill
    latency) and non-square Kh×Kw windows — the reference model for the
    halo accounting of the kernels' row blocks (DESIGN.md §13)."""

    @pytest.mark.parametrize("kh,kw,w,h,sh,sw",
                             [(3, 3, 9, 7, 2, 2),     # square, strided
                              (3, 3, 11, 9, 2, 1),
                              (6, 6, 13, 13, 2, 2),   # paper conv2, s=2
                              (2, 5, 11, 8, 1, 1),    # wide window
                              (5, 2, 7, 9, 1, 1),     # tall window
                              (4, 3, 10, 10, 3, 2),   # mixed strides
                              (1, 3, 8, 5, 2, 2)])    # single-row window
    def test_sweep(self, kh, kw, w, h, sh, sw):
        _check_strided_laws(kh, kw, w, h, sh, sw)

    def test_non_square_storage(self):
        """WB Kh×Kw + SB (Kh-1)×(W-Kw) — Fig. 7 with a non-square window."""
        sim = LineBufferSim((2, 5), 9)
        assert sim.wb.shape == (2, 5)
        assert sim.sb.shape == (1, 4)
        assert fill_latency(2, 9, 5) == 1 * 9 + 4

    @given(st.integers(1, 5), st.integers(1, 5), st.integers(1, 3),
           st.integers(1, 3), st.data())
    @settings(max_examples=40, deadline=None)
    def test_hypothesis_strided(self, kh, kw, sh, sw, data):
        w = data.draw(st.integers(kw, kw + 7))
        h = data.draw(st.integers(kh, kh + 6))
        _check_strided_laws(kh, kw, w, h, sh, sw)

    def test_halo_accounting_matches_line_buffer(self):
        """The row blocks' halo IS the line buffer's resident-row count: at
        stride 1, halo_rows(k) == K-1 shift-buffer rows, and
        halo_rows(k)/k equals the paper's (K-1)/K reuse ratio; the fill
        latency is exactly those resident rows plus the Kw-1 lead-in."""
        for k in range(1, 8):
            assert halo_rows(k, 1) == k - 1
            assert halo_rows(k, 1) / k == pytest.approx(reuse_ratio(k))
        for kh, kw, w in [(3, 3, 8), (4, 2, 9), (2, 5, 11), (6, 6, 13)]:
            assert fill_latency(kh, w, kw) == halo_rows(kh, 1) * w + kw - 1

    def test_band_rows_are_line_buffer_spans(self):
        """A 1-row block reads exactly Kh rows (the window) and each extra
        output row costs sh more — the vertical form of the line buffer's
        fill+stream law."""
        for kh, sh in [(3, 1), (3, 2), (5, 2), (6, 1)]:
            assert band_input_rows(1, kh, sh) == kh
            assert band_input_rows(4, kh, sh) - \
                band_input_rows(3, kh, sh) == sh


class TestMaxPool2:
    def test_even_matches_reduce_window(self):
        x = jax.random.normal(jax.random.PRNGKey(0), (2, 3, 8, 6))
        want = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max,
                                     (1, 1, 2, 2), (1, 1, 2, 2), "VALID")
        np.testing.assert_array_equal(np.asarray(maxpool2(x)),
                                      np.asarray(want))

    def test_odd_raises_by_default(self):
        """The old _maxpool2 silently dropped the last row/column on odd
        maps; that is an explicit error now (paper Eq. 1–2 sizing)."""
        x = jnp.zeros((1, 2, 5, 4))
        with pytest.raises(ValueError, match="odd"):
            maxpool2(x)
        with pytest.raises(ValueError, match="odd"):
            maxpool2(jnp.zeros((1, 2, 4, 7)))

    def test_odd_drop_matches_eq_1_2_floor(self):
        x = jnp.arange(1 * 1 * 5 * 5, dtype=jnp.float32).reshape(1, 1, 5, 5)
        out = maxpool2(x, odd="drop")
        assert out.shape == (1, 1, 2, 2)          # floor(5/2), Eq. 1–2
        assert pool_output_size(5, "drop") == 2
        # the dropped row/col never influences the output
        np.testing.assert_array_equal(
            np.asarray(out), np.asarray(maxpool2(x[:, :, :4, :4])))

    def test_odd_pad_keeps_ceil_and_values(self):
        x = jnp.arange(1 * 1 * 5 * 5, dtype=jnp.float32).reshape(1, 1, 5, 5)
        out = maxpool2(x, odd="pad")
        assert out.shape == (1, 1, 3, 3)          # ceil(5/2)
        assert pool_output_size(5, "pad") == 3
        # -inf padding: the ragged edge pools to the real maxima
        np.testing.assert_array_equal(np.asarray(out[0, 0, -1]),
                                      np.asarray(x[0, 0, -1, [1, 3, 4]]))

    def test_unknown_mode_rejected(self):
        with pytest.raises(ValueError, match="odd mode"):
            maxpool2(jnp.zeros((1, 1, 4, 4)), odd="truncate")


class TestConvOracles:
    def _lax(self, x, w, b, s):
        out = jax.lax.conv_general_dilated(
            x, w, s, "VALID", dimension_numbers=("NCHW", "OIHW", "NCHW"))
        return out if b is None else out + b[None, :, None, None]

    @pytest.mark.parametrize(
        "b,n,h,w,m,kh,kw,sh,sw",
        [(1, 1, 5, 5, 1, 3, 3, 2, 2),       # the paper's worked example
         (2, 3, 11, 9, 5, 3, 3, 1, 1),
         (2, 15, 13, 13, 20, 6, 6, 1, 1),   # paper conv2 shape
         (1, 4, 9, 12, 7, 2, 5, 1, 2)])
    def test_ref_and_im2col_vs_lax(self, b, n, h, w, m, kh, kw, sh, sw):
        key = jax.random.PRNGKey(b * 7 + n)
        x = jax.random.normal(key, (b, n, h, w))
        wt = jax.random.normal(jax.random.PRNGKey(1), (m, n, kh, kw))
        bias = jax.random.normal(jax.random.PRNGKey(2), (m,))
        want = self._lax(x, wt, bias, (sh, sw))
        np.testing.assert_allclose(conv2d_ref(x, wt, bias, (sh, sw)), want,
                                   rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(conv2d_im2col(x, wt, bias, (sh, sw)),
                                   want, rtol=1e-4, atol=1e-4)

    @given(st.integers(1, 4), st.integers(1, 3), st.integers(2, 4),
           st.integers(1, 2), st.data())
    @settings(max_examples=25, deadline=None)
    def test_hypothesis_shapes(self, b, n, k, s, data):
        h = data.draw(st.integers(k, k + 6))
        w = data.draw(st.integers(k, k + 6))
        m = data.draw(st.integers(1, 4))
        x = jax.random.normal(jax.random.PRNGKey(h * 31 + w), (b, n, h, w))
        wt = jax.random.normal(jax.random.PRNGKey(3), (m, n, k, k))
        want = self._lax(x, wt, None, (s, s))
        np.testing.assert_allclose(conv2d_im2col(x, wt, None, (s, s)), want,
                                   rtol=1e-4, atol=1e-4)

    def test_windows_match_manual(self):
        x = jnp.arange(2 * 1 * 4 * 5, dtype=jnp.float32).reshape(2, 1, 4, 5)
        win = extract_windows(x, (2, 2), (1, 1))
        assert win.shape == (2, 3, 4, 4)
        np.testing.assert_array_equal(
            np.asarray(win[0, 0, 0]),
            np.asarray([x[0, 0, 0, 0], x[0, 0, 0, 1],
                        x[0, 0, 1, 0], x[0, 0, 1, 1]]))

    def test_grad_flows(self):
        x = jax.random.normal(jax.random.PRNGKey(0), (1, 2, 6, 6))
        w = jax.random.normal(jax.random.PRNGKey(1), (3, 2, 3, 3))
        g = jax.grad(lambda w_: conv2d_im2col(x, w_, None).sum())(w)
        assert np.isfinite(np.asarray(g)).all()
