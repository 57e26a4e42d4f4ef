"""The ``fused_cwp`` kernel's pooled row blocks (DESIGN.md §13), its
VMEM budget at ``highres_cnn``'s 224x224 stages, and a multi-block plan.
Each block height is a scheduling choice only: see ``test_row_blocks.py``.
"""
import jax
import numpy as np
import pytest

from repro.configs.registry import get_arch
from repro.graph.ir import FusedConvBlockNode
from repro.graph.passes import stage_input_spec
from repro.models.vgg import VGGStyleCNN, VGGStyleCNNConfig
from repro.ops import ExecPolicy, fused_conv_block
from repro.ops.tiling import (VMEM_BUDGET_BYTES, choose_fused_blocks,
                              window_vmem_bytes)
from test_row_blocks import KEY, QUANTS, _lattice, _operands, _out_rows, \
    _pallas

# (k, s, h) of the fused cases, every conv map even; x is (2, 3, h, h)
FUSED_SHAPES = [(3, 1, 14), (5, 1, 14), (3, 2, 13), (5, 2, 15), (3, 1, 16)]


@pytest.mark.parametrize("pb", [1, 2])
@pytest.mark.parametrize("k,s,h", FUSED_SHAPES)
@pytest.mark.parametrize("quant", QUANTS)
def test_fused_pooled_blocks_bitwise(quant, k, s, h, pb):
    """Blocks of ``pb`` pooled rows cut only at even conv rows, so no
    2x2 pool window straddles a block boundary."""
    x, w, b = _operands((2, 3, h, h), 4, k)
    po = _out_rows(h, k, s) // 2
    assert _out_rows(h, k, s) % 2 == 0 and pb < po
    got = fused_conv_block(x, w, b, stride=(s, s),
                           policy=_pallas(quant,
                                          **{"fused_conv_block.pb": pb}))
    want = fused_conv_block(x, w, b, stride=(s, s),
                            policy=_pallas(quant,
                                           **{"fused_conv_block.pb": po}))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ----------------------------------------------------------------- budget

@pytest.fixture(scope="module")
def highres_stages():
    """(B, N, H, W, M, K, stride) of each fused stage of the batch-8
    ``highres_cnn`` plan."""
    plan = get_arch("highres_cnn").model().compile(batch=8)
    return [(*stage_input_spec(plan.graph, node).shape, node.w.shape[0],
             node.w.shape[2], node.stride)
            for node in plan.graph if isinstance(node, FusedConvBlockNode)]


@pytest.mark.parametrize("stage", range(4))
def test_fused_blocks_fit_vmem(highres_stages, stage):
    """The heuristic blocks of each ``highres_cnn`` fused stage fit
    ``VMEM_BUDGET_BYTES``: the kernel grid alone bounds a 224x224
    stage's working set."""
    assert len(highres_stages) == 4
    _, n, h, w, m, k, stride = highres_stages[stage]
    t = choose_fused_blocks(n, h, w, m, k, k, stride, 4)
    po = _out_rows(h, k, stride[0]) // 2
    assert 1 <= t["pb"] <= po
    assert window_vmem_bytes(n, w, k, k, stride, t["mb"], t["pb"], t["bb"],
                             4, pooled=True) <= VMEM_BUDGET_BYTES


# ----------------------------------------------------- a multi-block plan

def test_vgg_multiblock_ragged_bitwise():
    """Four fused blocks on the pallas backend, in blocks of two pooled
    rows and two images of a batch of three (both ragged), against the
    xla backend. Integer data keeps every conv sum exact, so the two
    backends' summation orders give the same bits."""
    model = VGGStyleCNN(VGGStyleCNNConfig(img_size=48))
    leaves, treedef = jax.tree_util.tree_flatten(model.init(KEY))
    ints = jax.tree_util.tree_unflatten(treedef, [
        _lattice(jax.random.PRNGKey(10 + i), leaf.shape, frac=0, maxcode=1)
        for i, leaf in enumerate(leaves)])
    x = _lattice(jax.random.PRNGKey(3), model.input_shape(3), frac=0,
                 maxcode=1)
    tiled = model.compile(_pallas(**{"fused_conv_block.pb": 2,
                                     "fused_conv_block.bb": 2}), batch=3)
    want = model.compile(ExecPolicy(backend="xla"), batch=3)(ints, x)
    np.testing.assert_array_equal(np.asarray(tiled.bind(ints)(x)),
                                  np.asarray(want))
