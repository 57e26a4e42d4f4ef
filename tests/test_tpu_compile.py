"""Compile the main path's Pallas kernels for a described TPU v5e chip.

No chip is needed: the TPU compiler is installed here and compiles for a
topology that is described, not attached. Each case compiles one kernel
call at a shape the served plans issue — the fused conv block and the
plain window conv at every conv stage of ``highres_cnn`` (224²) and
``mnist_cnn``, the window conv at ``resnet50``'s stage
shapes (padded, strided, 1x1), the int8 GEMM at both fc shapes, and the
addition tree — with interpret mode off, and asserts that the executable
holds a Mosaic kernel (``tpu_custom_call``); every served bucket plan
(batch 1, 2, 4, 8; quant none and int8) compiles whole for one chip, as
does ``resnet50``'s bucket-8 plan, and the 2×2-mesh plans for four
described chips. What the compiler refuses
here (block shapes off the 8×128 tiling, vector ops Mosaic cannot lower,
too much VMEM) would otherwise first show up at serve time on the chip.

The topology is described inside a module fixture, never at import:
only one process at a time may load the TPU library, and every test
worker imports this file. Keep every such compile in this one file.
"""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.addtree.ops import tree_reduce_sum
from repro.kernels.conv_window.ops import conv2d_window
from repro.kernels.fused_cwp.ops import fused_conv_window
from repro.kernels.qmatmul.ops import qmatmul

# (B, N, H, W, M, K) of every conv-stage kernel call the served plans
# make at batch 8: each stage is one call over its whole frame.
STAGES = {
    "highres_b0": (8, 3, 224, 224, 8, 5),
    "highres_b1": (8, 8, 110, 110, 16, 3),
    "highres_b2": (8, 16, 54, 54, 32, 3),
    "highres_b3": (8, 32, 26, 26, 32, 3),
    "mnist_conv1": (8, 1, 28, 28, 15, 3),
    "mnist_conv2": (8, 15, 13, 13, 20, 6),
}
# (B, N, H, W, M, K, stride) of ResNet-50's conv kernel calls at batch 8,
# the input already padded (the kernel runs VALID over it): the 7x7/2
# stem, per stage a 3x3 and a 1x1, and from stage 2 the strided 3x3 and
# the strided 1x1 projection
RESNET_STAGES = {
    "stem": (8, 3, 230, 230, 64, 7, 2),
    "s1_3x3": (8, 64, 58, 58, 64, 3, 1),
    "s1_1x1": (8, 256, 56, 56, 64, 1, 1),
    "s2_3x3": (8, 128, 30, 30, 128, 3, 1),
    "s2_1x1": (8, 128, 28, 28, 512, 1, 1),
    "s2_3x3_stride2": (8, 128, 58, 58, 128, 3, 2),
    "s2_proj": (8, 256, 56, 56, 512, 1, 2),
    "s3_3x3": (8, 256, 16, 16, 256, 3, 1),
    "s3_1x1": (8, 1024, 14, 14, 256, 1, 1),
    "s3_3x3_stride2": (8, 256, 30, 30, 256, 3, 2),
    "s3_proj": (8, 512, 28, 28, 1024, 1, 2),
    "s4_3x3": (8, 512, 9, 9, 512, 3, 1),
    "s4_1x1": (8, 2048, 7, 7, 512, 1, 1),
    "s4_3x3_stride2": (8, 512, 16, 16, 512, 3, 2),
    "s4_proj": (8, 1024, 14, 14, 2048, 1, 2),
}
# (M, K, N) of the int8 fc GEMM: highres_cnn, mnist_cnn
GEMMS = {"highres_fc": (8, 4608, 10), "mnist_fc": (8, 320, 10)}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip is written to a persistent cache but
    # cannot be read back without one; keep these compiles out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


def _compiled_text(fn, one_chip, *specs) -> str:
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
            for shape, dtype in specs]
    return jax.jit(fn).lower(*args).compile().as_text()


def _conv_specs(stage):
    b, n, h, w, m, k = STAGES[stage]
    return ((b, n, h, w), jnp.float32), ((m, n, k, k), jnp.float32), \
        ((m,), jnp.float32)


@pytest.mark.parametrize("stage", sorted(STAGES))
def test_fused_cwp_compiles(one_chip, stage):
    m = STAGES[stage][4]
    text = _compiled_text(
        lambda x, w, b, s: fused_conv_window(x, w, b, scale=s,
                                             interpret=False),
        one_chip, *_conv_specs(stage), ((m,), jnp.float32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("stage", sorted(STAGES))
def test_conv_window_compiles(one_chip, stage):
    text = _compiled_text(
        lambda x, w, b: conv2d_window(x, w, b, interpret=False),
        one_chip, *_conv_specs(stage))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("stage", sorted(RESNET_STAGES))
def test_conv_window_compiles_at_resnet50_shapes(one_chip, stage):
    b, n, h, w, m, k, s = RESNET_STAGES[stage]
    text = _compiled_text(
        lambda x, wt, bias: conv2d_window(x, wt, bias, stride=(s, s),
                                          interpret=False),
        one_chip, ((b, n, h, w), jnp.float32), ((m, n, k, k), jnp.float32),
        ((m,), jnp.float32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("gemm", sorted(GEMMS))
def test_qmatmul_compiles(one_chip, gemm):
    m, k, n = GEMMS[gemm]
    text = _compiled_text(
        lambda xc, wc, xs, ws: qmatmul(xc, wc, xs, ws, interpret=False),
        one_chip, ((m, k), jnp.int8), ((k, n), jnp.int8),
        ((m, 1), jnp.float32), ((1, n), jnp.float32))
    assert "tpu_custom_call" in text


def test_addtree_compiles(one_chip):
    text = _compiled_text(lambda x: tree_reduce_sum(x, interpret=False),
                          one_chip, ((1024, 75), jnp.float32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("bucket", [1, 2, 4, 8])
@pytest.mark.parametrize("quant", ["none", "int8"])
@pytest.mark.parametrize("arch", ["highres_cnn", "mnist_cnn"])
def test_served_bucket_plan_compiles(one_chip, arch, quant, bucket):
    """Each bucket of the batch-8 ladder ``VisionEngine`` serves, as the
    bound plan it compiles: every conv stage and,
    under int8, the fc GEMM is a Mosaic kernel."""
    from repro.configs.registry import get_arch
    from repro.ops import ExecPolicy
    model = get_arch(arch).model()
    plan = model.compile(policy=ExecPolicy(backend="pallas", quant=quant,
                                           interpret=False), batch=bucket)
    bound = plan.bind(model.init(jax.random.PRNGKey(0)))
    text = _compiled_text(lambda x: bound(x), one_chip,
                          (model.input_shape(bucket), jnp.float32))
    assert text.count("tpu_custom_call") >= \
        plan.num_fused() + (quant == "int8")


@pytest.mark.parametrize("arch", ["highres_cnn", "mnist_cnn"])
def test_served_kernels_carry_their_stage_names(one_chip, arch):
    """Each fused stage's kernel is the instruction ``fused_cwp.s<i>``
    (``plan.stages()[i]``), which names its events
    in a device trace."""
    from repro.configs.registry import get_arch
    from repro.graph.ir import FusedConvBlockNode
    from repro.ops import ExecPolicy
    model = get_arch(arch).model()
    plan = model.compile(policy=ExecPolicy(backend="pallas",
                                           interpret=False), batch=8)
    bound = plan.bind(model.init(jax.random.PRNGKey(0)))
    text = _compiled_text(lambda x: bound(x), one_chip,
                          (model.input_shape(8), jnp.float32))
    kernels = re.findall(r"^\s*%(\S+) = .*tpu_custom_call", text, re.M)
    fused = [i for i, node in enumerate(plan.graph)
             if isinstance(node, FusedConvBlockNode)]
    assert {re.sub(r"\.\d+$", "", k) for k in kernels} == \
        {f"fused_cwp.s{i}" for i in fused}


def test_resnet50_bucket_plan_compiles_with_every_conv_a_kernel(one_chip):
    """The bucket-8 plan ``resnet50.offline`` serves, weights bound: each
    of the 53 convs is the instruction ``conv_window.s<i>``
    (``plan.stages()[i]``); batch norm is folded, so nothing else is a
    kernel."""
    from repro.configs.registry import get_arch
    from repro.graph.ir import Conv2DNode
    from repro.ops import ExecPolicy
    model = get_arch("resnet50").model()
    plan = model.compile(policy=ExecPolicy(backend="pallas",
                                           interpret=False), batch=8)
    bound = plan.bind(model.init(jax.random.PRNGKey(0)))
    text = _compiled_text(lambda x: bound(x), one_chip,
                          (model.input_shape(8), jnp.float32))
    kernels = re.findall(r"^\s*%(\S+) = .*tpu_custom_call", text, re.M)
    convs = [i for i, node in enumerate(plan.graph)
             if isinstance(node, Conv2DNode)]
    assert len(convs) == 53
    assert sorted(re.sub(r"\.\d+$", "", k) for k in kernels) == \
        sorted(f"conv_window.s{i}" for i in convs)


@pytest.mark.parametrize("quant", ["none", "int8"])
@pytest.mark.parametrize("arch", ["highres_cnn", "mnist_cnn"])
def test_sharded_plan_compiles_on_four_chips(topo, one_chip, arch, quant):
    """The 2×2 (data × model) plan that ``launch/serve.py --mesh 2x2``
    serves: every Pallas call must sit inside a shard_map, since XLA
    cannot partition a Mosaic kernel (mnist's conv1 stays unsharded, and
    the int8 fc GEMM runs per data shard)."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.configs.registry import get_arch
    from repro.ops import ExecPolicy
    mesh = Mesh(np.asarray(topo.devices).reshape(2, 2), ("data", "model"))
    model = get_arch(arch).model()
    plan = model.compile(policy=ExecPolicy(backend="pallas", quant=quant,
                                           interpret=False),
                         batch=8, mesh=mesh)
    replicated = NamedSharding(mesh, P())
    params = jax.tree_util.tree_map(
        lambda leaf: jax.ShapeDtypeStruct(leaf.shape, leaf.dtype,
                                          sharding=replicated),
        jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    images = jax.ShapeDtypeStruct(model.input_shape(8), jnp.float32,
                                  sharding=NamedSharding(mesh, P("data")))
    text = jax.jit(lambda p, x: plan(p, x)).lower(params, images) \
        .compile().as_text()
    assert text.count("tpu_custom_call") >= plan.num_fused()


@pytest.mark.parametrize("arch", ["highres_cnn", "mnist_cnn"])
def test_stage_table_covers_served_plans(arch):
    """STAGES lists every conv kernel call of the served batch-8 plans,
    so the compiles above track the models."""
    from repro.configs.registry import get_arch
    from repro.graph.ir import FusedConvBlockNode
    from repro.graph.passes import stage_input_spec
    plan = get_arch(arch).model().compile(batch=8)
    calls = set()
    for node in plan.graph:
        if not isinstance(node, FusedConvBlockNode):
            continue
        b, n, h, w = stage_input_spec(plan.graph, node).shape
        m, _, k, _ = node.w.shape
        calls.add((b, n, h, w, m, k))
    assert calls and calls <= set(STAGES.values())
