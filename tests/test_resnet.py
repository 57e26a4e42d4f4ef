"""ResNet-50 v1.5 through the graph compiler (DESIGN.md §8): padded and
strided convs, batch norm folded at bind, the residual fan-out/fan-in,
the 3x3/2 max pool and global average pooling — each checked against a
plain float32 reference written here, at a tiny size on the CPU.

The reference (``reference_forward``) is a straightforward ``jax.numpy``
forward pass under ``jax.default_matmul_precision("highest")``:
``lax.conv_general_dilated`` with explicit padding, batch norm written
out from the running statistics, ``reduce_window`` for the max pool and
a mean for global average pooling. It follows the published description
(arXiv:1512.03385 Table 1, stride 2 on the 3x3 as in v1.5) with these
departures, all shared with the program: batch norm in inference form
(running statistics, eps 1e-5), the fc carries a bias (as torchvision's
model), and ``TINY`` cuts the widths by 16, the depth to one bottleneck
a stage, the input to 32x32 and the classes to 10.
"""
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.artifact.ir_codec import graph_from_doc, graph_to_doc
from repro.analysis.verifier import verify_plan
from repro.core.conv import Conv2DConfig, conv2d_apply
from repro.graph import compile_model, trace
from repro.graph.ir import (AddNode, BatchNormFoldNode, BatchNormNode,
                            Conv2DNode, GlobalAvgPoolNode, MaxPoolNode)
from repro.graph.passes import fold_batch_norm, lower_quant
from repro.graph.trace import (batch_norm, global_avg_pool, max_pool, relu)
from repro.models.resnet import ResNet, ResNetConfig
from repro.ops import ExecPolicy, conv2d

TINY = ResNetConfig(name="resnet_tiny", img_size=32, stem_width=4,
                    widths=(4, 8, 16, 32), depths=(1, 1, 1, 1),
                    n_classes=10)
# Max |program - reference| over the logits, relative to max |reference|.
# Both sides contract float32 at full precision; only summation order
# differs (im2col einsum or per-row kernel taps against XLA's conv, batch
# norm folded into the weights against written out), ~1e-7 relative per
# layer over 18 layers (read: 3e-7). 1e-5 leaves ~30x room and still
# fails a bf16 contraction (~4e-3) or a wrong pad, stride or fold.
TOL = 1e-5


def _ref_conv(x, w, stride, pad):
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), [(pad, pad), (pad, pad)],
        dimension_numbers=("NCHW", "OIHW", "NCHW"),
        precision=jax.lax.Precision.HIGHEST)


def _ref_bn(x, bn, eps):
    c = lambda v: v[None, :, None, None]       # noqa: E731
    return (x - c(bn["mean"])) / jnp.sqrt(c(bn["var"]) + eps) \
        * c(bn["gamma"]) + c(bn["beta"])


def _ref_max_pool(x):
    return jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 1, 3, 3),
                                 (1, 1, 2, 2),
                                 ((0, 0), (0, 0), (1, 1), (1, 1)))


def reference_forward(cfg: ResNetConfig, params: dict, x):
    """The plain float32 ResNet v1.5 forward (see the module docstring)."""
    eps = cfg.bn_eps

    def conv_bn(x, p, bn, stride, pad):
        return _ref_bn(_ref_conv(x, p["w"], stride, pad), bn, eps)

    with jax.default_matmul_precision("highest"):
        x = jax.nn.relu(conv_bn(x, params["stem"], params["stem_bn"], 2, 3))
        x = _ref_max_pool(x)
        for s, depth in enumerate(cfg.depths):
            for j in range(depth):
                p = params[f"layer{s + 1}_{j}"]
                stride = 2 if s > 0 and j == 0 else 1
                y = jax.nn.relu(conv_bn(x, p["conv1"], p["conv1_bn"], 1, 0))
                y = jax.nn.relu(conv_bn(y, p["conv2"], p["conv2_bn"],
                                        stride, 1))
                y = conv_bn(y, p["conv3"], p["conv3_bn"], 1, 0)
                short = x if j else conv_bn(x, p["proj"], p["proj_bn"],
                                            stride, 0)
                x = jax.nn.relu(y + short)
        x = x.mean(axis=(2, 3))
        return x @ params["fc_w"] + params["fc_b"]


def _seeded_bn(path, leaf):
    """Batch-norm statistics away from the identity, so the fold is
    checked (init gives gamma 1, beta 0, mean 0, var 1)."""
    name = str(path[-1].key)
    key = jax.random.fold_in(jax.random.PRNGKey(7),
                             zlib.crc32(str(path).encode()))
    if name == "var":
        return jax.random.uniform(key, leaf.shape, minval=0.5, maxval=2.0)
    if name in ("mean", "beta"):
        return 0.1 * jax.random.normal(key, leaf.shape)
    if name == "gamma":
        return jax.random.uniform(key, leaf.shape, minval=0.5, maxval=1.5)
    return leaf


@pytest.fixture(scope="module")
def tiny():
    model = ResNet(TINY)
    params = jax.tree_util.tree_map_with_path(
        _seeded_bn, model.init(jax.random.PRNGKey(0)))
    images = jax.random.normal(jax.random.PRNGKey(1), (2, 3, 32, 32))
    want = jax.jit(reference_forward, static_argnums=0)(TINY, params, images)
    return model, params, images, want


def _rel_err(got, want) -> float:
    return float(jnp.abs(got - want).max() / jnp.abs(want).max())


# ------------------------------------------------------------ whole model

def test_resnet50_counts_are_the_published_ones():
    cfg = ResNetConfig()
    assert len(cfg.convs()) == 53
    assert cfg.flops_per_image() == 8_178_368_512
    assert cfg.param_count() == 25_557_032
    shapes = jax.eval_shape(ResNet(cfg).init, jax.random.PRNGKey(0))
    assert sum(a.size for a in jax.tree.leaves(shapes)) == 25_610_152


@pytest.mark.parametrize("path", ["eager", "xla", "pallas"])
def test_tiny_resnet_matches_the_reference(tiny, path):
    """The eager model, and the compiled plan bound to the weights under
    the ``xla`` and ``pallas`` (interpreted) backends."""
    model, params, images, want = tiny
    if path == "eager":
        with jax.default_matmul_precision("highest"):
            got = model.forward(params, images)
    else:
        plan = compile_model(model, model.input_shape(2),
                             policy=ExecPolicy(backend=path))
        got = plan.bind(params)(images)
    assert _rel_err(got, want) < TOL


def test_tiny_resnet_serves_through_the_vision_engine(tiny):
    from repro.launch.serve import build_vision_server
    from repro.ops import use_policy
    model, params, images, want = tiny
    with use_policy(ExecPolicy(backend="xla")):
        engine, frontend, boot = build_vision_server(
            model, params, capacity=2, fixed_batch=True)
    for img in np.asarray(images):
        frontend.submit(img)
    res = frontend.run_until_drained()
    got = np.stack([res[r]["logits"] for r in sorted(res)])
    assert _rel_err(got, want) < TOL
    assert boot.phase_calls("fold") == 1      # one bind, no BN per batch


def test_plan_folds_every_batch_norm_at_bind(tiny):
    model, params, images, _ = tiny
    plan = compile_model(model, model.input_shape(2),
                         policy=ExecPolicy(backend="xla"))
    graph = plan.graph
    assert not any(isinstance(n, BatchNormNode) for n in graph)
    convs = [n for n in graph if isinstance(n, Conv2DNode)]
    folds = [n for n in graph if isinstance(n, BatchNormFoldNode)]
    assert len(convs) == len(TINY.convs()) and len(folds) == 2 * len(convs)
    assert all(isinstance(graph.node(i), BatchNormFoldNode)
               for c in convs for i in c.inputs[1:])
    bound = plan.bind(params)
    assert {n.id for n in folds} <= set(bound.folded)
    # the per-batch program reads the folded constants: no rsqrt left
    hlo = jax.jit(lambda x: bound(x)).lower(images).as_text()
    assert "rsqrt" not in hlo


def test_residual_graph_validates_roundtrips_and_verifies(tiny):
    """The fan-out (a block input read by conv1 and the shortcut) and
    fan-in (the add) survive ``validate``, the artifact codec and the
    verifier; a tampered add is a named violation."""
    model = tiny[0]
    plan = compile_model(model, model.input_shape(2))
    graph = plan.graph.validate()
    adds = [n for n in graph if isinstance(n, AddNode)]
    assert len(adds) == len(TINY.depths) and all(len(a.inputs) == 2
                                                 for a in adds)
    fanout = [n for n in graph if len(graph.consumers(n.id)) > 1]
    assert fanout
    assert [n.op for n in graph if isinstance(
        n, (MaxPoolNode, GlobalAvgPoolNode))] == ["max_pool",
                                                  "global_avg_pool"]
    assert graph_from_doc(graph_to_doc(graph)) == graph
    assert verify_plan(plan) == []

    from dataclasses import replace
    bad = replace(adds[0], inputs=(adds[0].inputs[0], graph.input_id))
    broken = replace(plan, graph=replace(graph, nodes=tuple(
        bad if n.id == bad.id else n for n in graph)))
    codes = {v.code for v in verify_plan(broken, raise_on_violation=False)}
    assert "shape-flow" in codes


def test_each_new_stage_kind_runs_under_its_scope(tiny):
    """The program's ops carry the scope ``s<i>.<op>`` of every conv,
    add, max pool and global average pool stage (``plan.stages()[i]``),
    so a device trace can name them (read before XLA fuses the pool
    into the fc on a CPU)."""
    model, params, images, _ = tiny
    plan = compile_model(model, model.input_shape(2),
                         policy=ExecPolicy(backend="xla"))
    bound = plan.bind(params)
    text = jax.jit(lambda x: bound(x)).lower(images).as_text(
        debug_info=True)
    kinds = ("conv2d", "add", "max_pool", "global_avg_pool")
    scoped = [f"/s{i}.{n.op}/" for i, n in enumerate(plan.graph)
              if n.op in kinds]
    assert len(scoped) == len(TINY.convs()) + len(TINY.depths) + 2
    assert all(scope in text for scope in scoped)


def test_residual_plan_roundtrips_through_the_artifact_store(tiny, tmp_path):
    from repro.graph.plan import BoundPlan
    model, params, images, want = tiny
    plan = compile_model(model, model.input_shape(2),
                         policy=ExecPolicy(backend="xla"))
    plan.bind(params).save(tmp_path / "plan", aot=False)
    loaded = BoundPlan.load(tmp_path / "plan")
    assert loaded.plan.graph == plan.graph
    assert _rel_err(loaded(images), want) < TOL


def test_quantized_batch_norm_fold_is_refused(tiny):
    graph = fold_batch_norm(trace(tiny[0], (1, 3, 32, 32)))
    with pytest.raises(ValueError, match="folded batch norm"):
        lower_quant(graph, "int8")


def test_batch_norm_after_a_non_conv_is_refused():
    class ReluThenNorm:
        def input_shape(self, batch=1):
            return (batch, 2, 4, 4)

        def init(self, key):
            return {"bn": {k: jnp.ones((2,))
                           for k in ("gamma", "beta", "mean", "var")}}

        def forward(self, params, x):
            return global_avg_pool(batch_norm(relu(x), params["bn"],
                                              eps=1e-5))

    with pytest.raises(ValueError, match="batch_norm follows"):
        compile_model(ReluThenNorm())


# --------------------------------------------------------------- per node

@pytest.mark.parametrize("backend", ["xla", "pallas"])
@pytest.mark.parametrize("k,stride", [(7, 2), (3, 1), (3, 2), (1, 1),
                                      (1, 2)])
def test_padded_strided_conv_matches_lax(backend, k, stride):
    """``repro.ops.conv2d`` with SAME-style padding k // 2 at ResNet's
    kernel sizes and strides, against ``lax.conv_general_dilated``."""
    kx, kw = jax.random.split(jax.random.PRNGKey(k * 10 + stride))
    x = jax.random.normal(kx, (2, 5, 15, 14))
    w = jax.random.normal(kw, (8, 5, k, k))
    got = conv2d(x, w, stride=(stride, stride), padding=(k // 2, k // 2),
                 policy=ExecPolicy(backend=backend))
    want = _ref_conv(x, w, stride, k // 2)
    assert got.shape == want.shape
    assert _rel_err(got, want) < TOL


def test_max_pool_3x3_stride2_pad1_matches_a_loop():
    x = np.asarray(jax.random.normal(jax.random.PRNGKey(3), (2, 3, 9, 8)))
    got = np.asarray(max_pool(jnp.asarray(x), 3, 2, 1))
    pad = np.pad(x, ((0, 0), (0, 0), (1, 1), (1, 1)),
                 constant_values=-np.inf)
    want = np.array([[[[pad[b, c, 2 * i:2 * i + 3, 2 * j:2 * j + 3].max()
                        for j in range(4)] for i in range(5)]
                      for c in range(3)] for b in range(2)])
    np.testing.assert_array_equal(got, want)


def test_global_avg_pool_is_the_channel_mean():
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 3, 7, 7))
    np.testing.assert_allclose(global_avg_pool(x),
                               np.asarray(x).mean(axis=(2, 3)), rtol=1e-6)


@pytest.mark.parametrize("bias", [False, True])
def test_batch_norm_fold_matches_batch_norm_written_out(bias):
    """conv -> BN compiled (the fold at bind) against the conv and the
    batch norm written out, with and without a conv bias."""
    cfg = Conv2DConfig(3, 6, (3, 3), (2, 2), use_bias=bias, padding=(1, 1))
    keys = jax.random.split(jax.random.PRNGKey(5), 6)
    bn = {"gamma": jax.random.uniform(keys[0], (6,), minval=0.5, maxval=2),
          "beta": jax.random.normal(keys[1], (6,)),
          "mean": jax.random.normal(keys[2], (6,)),
          "var": jax.random.uniform(keys[3], (6,), minval=0.1, maxval=3)}
    conv = {"w": jax.random.normal(keys[4], (6, 3, 3, 3))}
    if bias:
        conv["b"] = jax.random.normal(keys[5], (6,))

    class ConvNorm:
        def input_shape(self, batch=1):
            return (batch, 3, 9, 9)

        def init(self, key):
            return {"conv": conv, "bn": bn}

        def forward(self, params, x):
            return batch_norm(conv2d_apply(params["conv"], x, cfg),
                              params["bn"], eps=1e-5)

    model = ConvNorm()
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 3, 9, 9))
    plan = compile_model(model, policy=ExecPolicy(backend="xla"))
    got = plan.bind(model.init(None))(x)
    y = _ref_conv(x, conv["w"], 2, 1)
    if bias:
        y = y + conv["b"][None, :, None, None]
    assert _rel_err(got, _ref_bn(y, bn, 1e-5)) < TOL


def test_no_cnn_arch_is_in_the_lm_grid():
    """``ARCH_IDS`` feeds the LM shape sweeps (``launch/dryrun.py --arch
    all``, ``test_models_smoke.py``); a CNN there would enter them."""
    from repro.configs.registry import ARCH_IDS, _MODULES, get_arch
    cnns = {a for a in _MODULES if get_arch(a).family == "cnn"}
    assert {"mnist_cnn", "highres_cnn", "resnet50"} <= cnns
    assert not cnns & set(ARCH_IDS)
