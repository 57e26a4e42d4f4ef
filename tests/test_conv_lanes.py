"""The channels-on-lanes and whole-window layouts of the window conv
kernel (kernels/conv_window, DESIGN.md §2-§3).

Parity in interpret mode at every ResNet-50 stage kind of
``test_tpu_compile.RESNET_STAGES`` — the same kernel size, stride and
map size, fewer channels — against the paper-dataflow oracle and
``lax.conv_general_dilated`` at HIGHEST (the stem runs the whole-window
patches, every other kind the lanes); the shape rule that picks the
layout; the VMEM reckoning of the chosen blocks; the autotuner's axes;
and the boot report's record of the layout each kernel took.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from test_tpu_compile import RESNET_STAGES, STAGES

from repro.artifact.warmup import collect_warmup
from repro.kernels.conv_window.ops import conv2d_window
from repro.kernels.conv_window.ref import conv2d_window_ref
from repro.ops.autotune import _axis_candidates, _window_fits
from repro.ops.tiling import (SUBLANE, VMEM_BUDGET_BYTES,
                              choose_conv_blocks, conv_layout,
                              lane_vmem_bytes, patch_vmem_bytes,
                              window_vmem_bytes)

# (B, N, H, W, M, K, stride) per RESNET_STAGES kind: the same H, W, K and
# stride, channels cut so the oracle's B·Ho·Wo·M·N·K² products stay small.
# N stays above Wo wherever ResNet-50 puts channels on lanes: 64 (per-tap
# contractions) on the 56x56 maps, 128 (taps concatenated on lanes)
# elsewhere; Wo = 7 and 14 are not multiples of the sublane tile.
REDUCED = {
    "stem": (1, 3, 230, 230, 8, 7, 2),
    "s1_3x3": (1, 64, 58, 58, 8, 3, 1),
    "s1_1x1": (1, 128, 56, 56, 8, 1, 1),
    "s2_3x3": (2, 128, 30, 30, 8, 3, 1),
    "s2_1x1": (2, 128, 28, 28, 64, 1, 1),
    "s2_3x3_stride2": (2, 128, 58, 58, 8, 3, 2),
    "s2_proj": (2, 128, 56, 56, 64, 1, 2),
    "s3_3x3": (2, 128, 16, 16, 32, 3, 1),
    "s3_1x1": (2, 128, 14, 14, 256, 1, 1),
    "s3_3x3_stride2": (2, 128, 30, 30, 32, 3, 2),
    "s3_proj": (2, 128, 28, 28, 256, 1, 2),
    "s4_3x3": (2, 128, 9, 9, 128, 3, 1),
    "s4_1x1": (2, 128, 7, 7, 256, 1, 1),
    "s4_3x3_stride2": (2, 128, 16, 16, 128, 3, 2),
    "s4_proj": (2, 128, 14, 14, 256, 1, 2),
}


def _out_width(w, k, s):
    return (w - k) // s + 1


def test_reduced_cases_keep_resnet50_geometry():
    assert REDUCED.keys() == RESNET_STAGES.keys()
    for name, (_, n, h, w, _, k, s) in REDUCED.items():
        _, n50, h50, w50, _, k50, s50 = RESNET_STAGES[name]
        assert (h, w, k, s) == (h50, w50, k50, s50), name
        assert conv_layout(n, _out_width(w, k, s), k, k) == \
            conv_layout(n50, _out_width(w50, k50, s50), k50, k50), name


@pytest.mark.parametrize("stage", sorted(REDUCED))
def test_parity_at_resnet50_stage_kinds(stage):
    """Heuristic blocks, and small ones (a ragged row block with halo'd
    neighbours, one image a step, 128-lane channel blocks), against both
    oracles at HIGHEST."""
    b, n, h, w, m, k, s = REDUCED[stage]
    x = jax.random.normal(jax.random.PRNGKey(h + k), (b, n, h, w))
    wt = jax.random.normal(jax.random.PRNGKey(1), (m, n, k, k)) \
        / np.sqrt(n * k * k)
    bias = jax.random.normal(jax.random.PRNGKey(2), (m,))
    oracle = conv2d_window_ref(x, wt, bias, stride=(s, s))
    xla = jax.lax.conv_general_dilated(
        x, wt, (s, s), "VALID", precision=jax.lax.Precision.HIGHEST) \
        + bias[None, :, None, None]
    np.testing.assert_allclose(oracle, xla, rtol=1e-5, atol=1e-5)
    small = dict(rb=3, bb=1, mb=128 if m % 128 == 0 else m)
    for tiles in ({}, small):
        got = conv2d_window(x, wt, bias, stride=(s, s), **tiles)
        assert got.shape == xla.shape
        np.testing.assert_allclose(got, oracle, rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(got, xla, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("stage", sorted(RESNET_STAGES))
def test_layout_rule(stage):
    """Channels go on the lanes wherever min(N, 128) > min(Wo, 128) and
    N >= 64: every ResNet-50 conv but the stem (N = 3, Wo = 112), whose
    kernel row of taps (Kw·N = 21) fills under a quarter of the MXU's
    depth and whose window (147) more than one pass: it takes the
    whole-window patches."""
    _, n, _, w, _, k, s = RESNET_STAGES[stage]
    want = "patches" if stage == "stem" else "lanes"
    assert conv_layout(n, _out_width(w, k, s), k, k) == want


@pytest.mark.parametrize("stage", sorted(STAGES))
def test_layout_rule_keeps_narrow_channels_on_the_slab(stage):
    """Under 64 channels the width stays on the lanes, even where the
    channels outnumber it (the paper CNN's conv2: N = 15, Wo = 8;
    highres_cnn's last block: N = 32, Wo = 24), so an unfused conv sums
    as ``fused_cwp`` does."""
    _, n, _, w, _, k = STAGES[stage]
    assert conv_layout(n, _out_width(w, k, 1), k, k) == "slab"
    assert conv_layout(64, 8, 3, 3) == "lanes"
    assert conv_layout(63, 8, 3, 3) == "slab"


@pytest.mark.parametrize("n, k, want", [
    (3, 7, "patches"),      # the stem: Kw·N = 21, window 147
    (4, 7, "patches"),      # Kw·N = 28, window 196
    (3, 5, "slab"),         # window 75: under one pass
    (4, 8, "slab"),         # Kw·N = 32: a quarter of the depth already
    (1, 12, "patches"),     # Kw·N = 12, window 144
    (1, 11, "slab")])       # window 121
def test_patch_rule_thresholds(n, k, want):
    """Whole-window patches where Kw·N < 32 and Kh·Kw·N >= 128, at any
    output width that keeps the channels off the lanes."""
    for wo in (8, 112, 224):
        assert conv_layout(n, wo, k, k) == want


@pytest.mark.parametrize("stage", sorted(RESNET_STAGES))
def test_chosen_blocks_fit_vmem(stage):
    """The heuristic blocks of each ResNet-50 conv, at the served batch,
    fit ``VMEM_BUDGET_BYTES`` under the reckoning of their layout; a lane
    kernel's channel block is whole lane tiles or all of M."""
    b, n, h, w, m, k, s = RESNET_STAGES[stage]
    t = choose_conv_blocks(n, h, w, m, k, k, (s, s), 4, batch=b)
    layout = conv_layout(n, _out_width(w, k, s), k, k)
    if layout == "lanes":
        assert t["mb"] == m or (m % t["mb"] == 0 and t["mb"] % 128 == 0)
        used = lane_vmem_bytes(n, w, k, k, (s, s), t["mb"], t["rb"],
                               t["bb"], 4)
    elif layout == "patches":
        assert t["mb"] == m or (m % t["mb"] == 0
                                and t["mb"] % SUBLANE == 0)
        used = patch_vmem_bytes(n, w, k, k, (s, s), t["mb"], t["rb"],
                                t["bb"], 4)
    else:
        used = window_vmem_bytes(n, w, k, k, (s, s), t["mb"], t["rb"],
                                 t["bb"], 4, pooled=False)
    assert used <= VMEM_BUDGET_BYTES
    assert 1 <= t["bb"] <= b


def test_lane_vmem_rounds_to_tiles():
    """Wo_pad rounds to the 8-sublane tile: a 7-wide output costs what an
    8-wide one does, a 9-wide one more."""
    args = dict(kh=3, kw=3, stride=(1, 1), mb=128, rows=7, bb=2,
                itemsize=4)
    seven, eight, nine = (lane_vmem_bytes(512, wo + 2, **args)
                          for wo in (7, 8, 9))
    assert seven == eight < nine


def test_autotuner_axes_follow_the_layout():
    """A lane conv's channel-block candidates are whole lane tiles, and
    its fit test is the lane reckoning."""
    x_shape, w_shape = (8, 512, 9, 9), (512, 512, 3, 3)
    heur = choose_conv_blocks(512, 9, 9, 512, 3, 3, (1, 1), 4, batch=8)
    axes = _axis_candidates("conv2d", x_shape, w_shape, (1, 1), heur)
    assert all(mb % 128 == 0 for mb in axes["mb"])
    fits = _window_fits("conv2d", x_shape, w_shape, (1, 1), 4)
    assert fits(heur)
    assert not fits({**heur, "mb": 512, "bb": 8})


def test_autotuner_fits_patches_with_their_reckoning():
    """The stem's channel-block candidates are sublane blocks, and its
    fit test is the whole-window reckoning: the heuristic blocks fit,
    a whole bucket in one step does not."""
    b, n, h, w, m, k, s = RESNET_STAGES["stem"]
    x_shape, w_shape = (b, n, h, w), (m, n, k, k)
    heur = choose_conv_blocks(n, h, w, m, k, k, (s, s), 4, batch=b)
    axes = _axis_candidates("conv2d", x_shape, w_shape, (s, s), heur)
    assert all(m % mb == 0 and mb % SUBLANE == 0 for mb in axes["mb"])
    fits = _window_fits("conv2d", x_shape, w_shape, (s, s), 4)
    assert fits(heur)
    assert not fits({**heur, "rb": _out_width(h, k, s), "bb": b})


def test_boot_report_counts_layouts():
    x = jnp.ones((2, 128, 9, 9))
    w = jnp.ones((16, 128, 3, 3))
    with collect_warmup() as report:
        conv2d_window(x, w, stage="s1")
        conv2d_window(jnp.ones((1, 3, 12, 12)), jnp.ones((4, 3, 3, 3)),
                      stage="s0")
        conv2d_window(jnp.ones((1, 3, 21, 21)), jnp.ones((8, 3, 7, 7)),
                      stride=(2, 2), stage="s2")
    assert report.layouts == {"conv_window.s1": "lanes",
                              "conv_window.s0": "slab",
                              "conv_window.s2": "patches"}
    assert "conv_window layouts: lanes 1, patches 1, slab 1" in \
        report.pretty()


@pytest.mark.parametrize("arch, want", [
    ("resnet50", "conv_window layouts: lanes 52, patches 1"),
    ("mnist_cnn", None)])
def test_served_plan_layout_counts(arch, want):
    """Tracing a served bucket plan on the pallas backend records one
    layout per window-conv stage: ResNet-50's stem takes the whole-window
    patches, its other 52 convs the lanes; the paper CNN runs no window
    conv."""
    from repro.configs.registry import get_arch
    from repro.ops import ExecPolicy
    model = get_arch(arch).model()
    plan = model.compile(policy=ExecPolicy(backend="pallas"), batch=8)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    images = jax.ShapeDtypeStruct(model.input_shape(8), jnp.float32)
    with collect_warmup() as report:
        jax.eval_shape(lambda p, xb: plan(p, xb), params, images)
    lines = [ln for ln in report.pretty().splitlines()
             if " layouts: " in ln]
    assert lines == ([] if want is None else [want])
