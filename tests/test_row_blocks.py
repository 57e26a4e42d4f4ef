"""The kernels' row-block grid, the repo's one row tiling (DESIGN.md §13):
``conv_window`` here, ``fused_cwp`` in ``test_row_blocks_fused.py``.

``conv_window`` and ``fused_cwp`` run a grid of row blocks, each reading
a halo'd slab of ``band_input_rows`` input rows (adjacent blocks share
``halo_rows``). The block height is a scheduling choice only: every case
here runs the kernel (interpret mode on the CPU) with small row blocks,
ragged last blocks included, and asserts the output bitwise equal to the
one-block kernel's. Blocks are forced through ``ExecPolicy.tiling``, the
route plan-baked and cached tiles take.

The slab kernels (width on lanes, and the fused block built on them)
compute each output row with the same contraction whatever the block, so
they are held bitwise on random data. The lane kernel contracts a whole
block's rows in one dot, whose size XLA:CPU may sum in another order, so
its float32 cases use data on which every product and partial sum is
exact (``_lattice``); then any order gives the same bits. The
whole-window patch kernel's cases use the lattice too, in every quant
mode.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.cnn import PaperCNN, PaperCNNConfig
from repro.ops import ExecPolicy, conv2d
from repro.ops.tiling import conv_layout

KEY = jax.random.PRNGKey(0)
QUANTS = ("none", "qformat", "int8")
# (k, s, h) of the slab conv cases; x is (2, 3, h, h + 2)
CONV_SHAPES = [(3, 1, 13), (3, 2, 13), (5, 1, 13), (5, 2, 13), (3, 1, 14)]
# ResNet-like lane-layout convs, reduced: (B, N, H, W, M, k, stride, pad)
LANE_SHAPES = {
    "1x1": (2, 64, 8, 8, 64, 1, 1, 0),
    "1x1_stride2": (2, 64, 9, 9, 128, 1, 2, 0),
    "3x3_same": (2, 64, 8, 8, 64, 3, 1, 1),
    "3x3_stride2": (2, 128, 9, 9, 128, 3, 2, 1),
    "3x3_ragged": (2, 64, 9, 9, 64, 3, 1, 0),
}
# stem-like whole-window convs, reduced: (B, N, H, W, M, k, stride, pad)
PATCH_SHAPES = {
    "7x7_stride2_same": (2, 3, 18, 16, 8, 7, 2, 3),
    "7x7_ragged": (2, 3, 17, 15, 16, 7, 1, 0),
    "5x5x6_stride2": (2, 6, 19, 19, 8, 5, 2, 0),
}


def _lattice(key, shape, frac=6, maxcode=31):
    """Integer multiples of 2^-frac up to maxcode·2^-frac: on this grid
    the products and partial sums of these convs fit fp32 exactly."""
    c = jax.random.randint(key, shape, -maxcode, maxcode + 1)
    return c.astype(jnp.float32) * (2.0 ** -frac)


def _operands(shape_x, m, k, n=3, data=jax.random.normal):
    x = data(KEY, shape_x)
    w = data(jax.random.PRNGKey(1), (m, n, k, k))
    b = data(jax.random.PRNGKey(2), (m,))
    return x, w, b


def _out_rows(h, k, s, pad=0):
    return (h + 2 * pad - k) // s + 1


def _pallas(quant="none", **tiling):
    return ExecPolicy(backend="pallas", quant=quant, tiling=tiling)


# ------------------------------------------------ conv_window, slab layout

@pytest.mark.parametrize("rb", [2, 3])
@pytest.mark.parametrize("k,s,h", CONV_SHAPES)
@pytest.mark.parametrize("quant", QUANTS)
def test_conv_slab_row_blocks_bitwise(quant, k, s, h, rb):
    x, w, b = _operands((2, 3, h, h + 2), 4, k)
    ho = _out_rows(h, k, s)
    assert conv_layout(3, _out_rows(h + 2, k, s), k, k) == "slab"
    assert rb < ho
    got = conv2d(x, w, b, stride=(s, s),
                 policy=_pallas(quant, **{"conv2d.rb": rb}))
    want = conv2d(x, w, b, stride=(s, s),
                  policy=_pallas(quant, **{"conv2d.rb": ho}))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ----------------------------------------------- conv_window, lane layout

@pytest.mark.parametrize("rb", [2, 3])
@pytest.mark.parametrize("shape", sorted(LANE_SHAPES))
def test_conv_lanes_row_blocks_bitwise(shape, rb):
    """The kernel ``resnet50`` serves: 1x1, strided, padded and ragged
    row blocks against one block, float32."""
    bsz, n, h, wd, m, k, s, pad = LANE_SHAPES[shape]
    x, w, b = _operands((bsz, n, h, wd), m, k, n=n, data=_lattice)
    ho = _out_rows(h, k, s, pad)
    assert conv_layout(n, _out_rows(wd, k, s, pad), k, k) == "lanes"
    assert rb < ho
    kw = dict(stride=(s, s), padding=(pad, pad))
    got = conv2d(x, w, b, policy=_pallas(**{"conv2d.rb": rb}), **kw)
    want = conv2d(x, w, b, policy=_pallas(**{"conv2d.rb": ho}), **kw)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# -------------------------------------------- conv_window, whole windows

@pytest.mark.parametrize("rb", [2, 3])
@pytest.mark.parametrize("shape", sorted(PATCH_SHAPES))
@pytest.mark.parametrize("quant", QUANTS)
def test_conv_patches_row_blocks_bitwise(quant, shape, rb):
    """The layout ``resnet50``'s stem takes: strided, padded and ragged
    row blocks against one block, in every quant mode."""
    bsz, n, h, wd, m, k, s, pad = PATCH_SHAPES[shape]
    x, w, b = _operands((bsz, n, h, wd), m, k, n=n, data=_lattice)
    ho = _out_rows(h, k, s, pad)
    assert conv_layout(n, _out_rows(wd, k, s, pad), k, k) == "patches"
    assert rb < ho
    kw = dict(stride=(s, s), padding=(pad, pad))
    got = conv2d(x, w, b, policy=_pallas(quant, **{"conv2d.rb": rb}), **kw)
    want = conv2d(x, w, b, policy=_pallas(quant, **{"conv2d.rb": ho}),
                  **kw)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ------------------------------------------------------- the policy route

@pytest.mark.parametrize("fuse,tiling", [
    (True, {"fused_conv_block.pb": 2}), (False, {"conv2d.rb": 3})],
    ids=["fused", "conv"])
@pytest.mark.parametrize("quant", QUANTS)
def test_paper_cnn_small_blocks_bitwise(quant, fuse, tiling):
    """Small blocks forced on a compiled plan through its policy give the
    default plan's output, bit for bit (the heuristic takes each stage
    whole: pb 13 and 4 fused, rb 26 and 8 unfused)."""
    model = PaperCNN(PaperCNNConfig())
    params = model.init(KEY)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 1, 28, 28))
    want = model.compile(_pallas(quant), fuse=fuse, batch=2).bind(params)(x)
    plan = model.compile(_pallas(quant, **tiling), fuse=fuse, batch=2)
    got = plan.bind(params)(x)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
