"""Sharded execution plans (DESIGN.md §9): the channel-parallel placement
pass, the mesh-aware plan executor, the core schedules' edge cases, and
the VisionEngine pad-lane stats fix.

Multi-device cases run in subprocess children (the host-platform device
override must be set before jax initializes, as in test_distributed).

Bitwise parity methodology: the parity children build "lattice" params
and images — small integer multiples of 2^-6 with the absmax pinned to
127/64 — so every conv product and partial sum is exactly representable
in fp32 and every int8 scale is a power of two. Reassociating the
reduction (which is exactly what ICP's psum and OCP's matmul re-blocking
do) then cannot change a single bit, so sharded == unsharded must hold
EXACTLY, per backend, for all three quant modes. Under int8 the codes
are ≤127 by construction, so the integer accumulation is exact for any
data — pinned separately with random inputs.
"""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

REPO_SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run(code: str, devices: int = 4) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = REPO_SRC + os.pathsep + env.get("PYTHONPATH", "")
    res = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, timeout=900,
                         env=env)
    assert res.returncode == 0, res.stdout + "\n" + res.stderr
    return res.stdout


PREAMBLE = """
import jax, jax.numpy as jnp, numpy as np
jax.config.update("jax_default_matmul_precision", "float32")
from jax.sharding import Mesh
from repro.models.cnn import PaperCNN, PaperCNNConfig
from repro.ops import ExecPolicy

def lattice(key, shape, frac=6, maxcode=31):
    c = jax.random.randint(key, shape, -maxcode, maxcode + 1)
    v = c.astype(jnp.float32) * (2.0 ** -frac)
    flat = v.reshape(-1).at[0].set(127 * 2.0 ** -frac)  # exact int8 scale
    return flat.reshape(shape)

def lattice_tree(tree):
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    return jax.tree_util.tree_unflatten(treedef, [
        lattice(jax.random.PRNGKey(i + 100), l.shape)
        for i, l in enumerate(leaves)])

# conv1: M=16, N=1 -> OCP everywhere; conv2: M=8, N=16 -> ICP at mesh 2,
# the composed icp2 x ocp2 split at mesh 4
CFG = PaperCNNConfig(conv1_c=16, conv2_c=8)
MODEL = PaperCNN(CFG)
PARAMS = lattice_tree(MODEL.init(jax.random.PRNGKey(0)))
X = lattice(jax.random.PRNGKey(9), (4, 1, 28, 28))

def mesh_of(k, data=1):
    devs = np.asarray(jax.devices()[: k * data]).reshape(data, k)
    return Mesh(devs, ("data", "model"))
"""


class TestShardedPlanParity:
    def test_bitwise_vs_unsharded_all_quants_meshes_schedules(self):
        """ICP == OCP == auto == unsharded, bitwise, for all three quant
        modes on ref and xla at mesh sizes 1/2/4 (forced 4-device CPU)."""
        _run(PREAMBLE + """
for quant in ("none", "qformat", "int8"):
    for backend in ("ref", "xla"):
        pol = ExecPolicy(quant=quant, backend=backend)
        want = np.asarray(MODEL.compile(policy=pol).bind(PARAMS)(X))
        for k in (1, 2, 4):
            for cp in (None, "icp", "ocp"):
                sp = MODEL.compile(
                    policy=pol.with_options(channel_parallel=cp),
                    mesh=mesh_of(k))
                got = np.asarray(sp.bind(PARAMS)(X))
                assert np.array_equal(got, want), \\
                    (quant, backend, k, cp, np.abs(got - want).max())
        # auto placement must actually exercise BOTH schedules
        auto = MODEL.compile(policy=pol, mesh=mesh_of(2))
        modes = {n.sharding.mode for n in auto.graph
                 if getattr(n, "sharding", None) is not None}
        assert {"output", "input"} <= modes, modes
print("OK")
""")

    def test_pallas_backend_and_data_axis_sharding(self):
        """The pallas (interpret) backend through a sharded plan, and
        batch sharding over the data axis composed with both schedules.

        Under int8 the requant epilogue ``acc·s + b`` may be contracted
        into one FMA in one program and rounded twice in the other (the
        installed XLA:CPU fuses it, DESIGN.md §8); with zero biases both
        roundings agree, so sharded == unsharded holds bit for bit by
        construction and the exact integer ring reduce stays pinned."""
        _run(PREAMBLE + """
NO_BIAS = {k: ({**v, "b": jnp.zeros_like(v["b"])} if isinstance(v, dict)
               else jnp.zeros_like(v) if k == "fc_b" else v)
           for k, v in PARAMS.items()}
for quant, params in (("none", PARAMS), ("int8", NO_BIAS)):
    pol = ExecPolicy(quant=quant, backend="pallas")
    want = np.asarray(MODEL.compile(policy=pol).bind(params)(X))
    got = np.asarray(MODEL.compile(policy=pol, mesh=mesh_of(2))
                     .bind(params)(X))
    assert np.array_equal(got, want), (quant, np.abs(got - want).max())
# data x model = 2 x 2: batch 4 shards over data, channels over model
pol = ExecPolicy(quant="int8")
want = np.asarray(MODEL.compile(policy=pol).bind(PARAMS)(X))
got = np.asarray(MODEL.compile(policy=pol, mesh=mesh_of(2, data=2))
                 .bind(PARAMS)(X))
assert np.array_equal(got, want), np.abs(got - want).max()
print("OK")
""")

    def test_int8_bitwise_with_random_data_and_jit(self):
        """int8 parity needs no lattice data: the codes are ≤127 ints, so
        the sharded reduction is exact for ANY input. Also pins the
        jitted (serving) path against the eager sharded plan."""
        _run(PREAMBLE + """
params = MODEL.init(jax.random.PRNGKey(3))
x = jax.random.normal(jax.random.PRNGKey(4), (4, 1, 28, 28))
pol = ExecPolicy(quant="int8")
want = np.asarray(MODEL.compile(policy=pol).bind(params)(x))
bound = MODEL.compile(policy=pol, mesh=mesh_of(4)).bind(params)
assert np.array_equal(np.asarray(bound(x)), want)
got_jit = np.asarray(jax.jit(lambda v: bound(v))(x))
assert np.array_equal(got_jit, np.asarray(bound(x)))
print("OK")
""")

    def test_unfused_sharded_plan_and_float_closeness(self):
        """fuse=False routes sharded Conv2D nodes (not fused blocks)
        through the schedules; random-data sharded quant=none stays
        allclose to the unsharded plan (reassociation only)."""
        _run(PREAMBLE + """
params = MODEL.init(jax.random.PRNGKey(3))
x = jax.random.normal(jax.random.PRNGKey(4), (4, 1, 28, 28))
plain = MODEL.compile(fuse=False)
assert plain.num_fused() == 0
want = np.asarray(plain.bind(params)(x))
sharded = MODEL.compile(fuse=False, mesh=mesh_of(4))
assert sharded.num_sharded() == 2
got = np.asarray(sharded.bind(params)(x))
np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
print("OK")
""")

    def test_bind_places_weights_on_mesh(self):
        """bind() on a mesh plan leaves the weight shards resident: OCP
        weights sharded on M over 'model', ICP weights on N."""
        _run(PREAMBLE + """
from jax.sharding import NamedSharding, PartitionSpec as P
mesh = mesh_of(2)
plan = MODEL.compile(mesh=mesh)                       # quant none
bound = plan.bind(PARAMS)
specs = {}
for (nid, attr), val in bound.placed.items():
    node = plan.graph.node(nid)
    specs[(node.sharding.mode, attr)] = val.sharding.spec
assert specs[("output", "w")] == P("model", None, None, None)
assert specs[("output", "b")] == P("model")
assert specs[("input", "w")] == P(None, "model", None, None)
# int8: the folded weight QTensor is placed (codes sharded, scale too)
plan8 = MODEL.compile(policy=ExecPolicy(quant="int8"), mesh=mesh)
b8 = plan8.bind(PARAMS)
from repro.core.quantize import QTensor
qts = [v for v in b8.folded.values() if isinstance(v, QTensor)]
assert any(v.codes.sharding.spec == P("model", None, None, None)
           for v in qts)
print("OK")
""")


class TestChannelParallelConvEdges:
    """The core schedules (paper Eq. 6/7) beyond what the plan exercises:
    stride, missing bias, requant scale, and the clear-error contract."""

    def test_stride_bias_and_scale_edges(self):
        _run(PREAMBLE + """
from repro.core.parallelism import (ChannelParallelism,
                                    conv2d_channel_parallel,
                                    fused_conv_block_channel_parallel)
from repro.core.window import conv2d_im2col
mesh = mesh_of(4)
key = jax.random.PRNGKey(0)
x = jax.random.normal(key, (4, 8, 13, 13))
w = jax.random.normal(jax.random.PRNGKey(1), (8, 8, 3, 3))
b = jax.random.normal(jax.random.PRNGKey(2), (8,))
for mode in (ChannelParallelism.OUTPUT, ChannelParallelism.INPUT):
    # stride 2
    want = conv2d_im2col(x, w, b, (2, 2))
    got = conv2d_channel_parallel(x, w, b, mesh=mesh, mode=mode,
                                  stride=(2, 2))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-5, err_msg=str(mode))
    # b=None: no bias is added anywhere (exactly once when present)
    want0 = conv2d_im2col(x, w, None, (1, 1))
    got0 = conv2d_channel_parallel(x, w, None, mesh=mesh, mode=mode)
    np.testing.assert_allclose(np.asarray(got0), np.asarray(want0),
                               rtol=1e-4, atol=1e-5, err_msg=str(mode))
    # int8 requant scale: applied once, post-reduction, pre-bias
    from repro.ops import conv2d, quantize_conv_int8, split_requant
    xq, wq = quantize_conv_int8(x, w)
    cx, cw, scale = split_requant(xq, wq)
    want8 = conv2d(xq, wq, b)
    got8 = conv2d_channel_parallel(cx, cw, b, mesh=mesh, mode=mode,
                                   scale=scale)
    assert np.array_equal(np.asarray(got8), np.asarray(want8)), mode
# fused block: stride 2 + b=None under ICP (psum before relu/pool)
from repro.core.window import maxpool2
xf = jax.random.normal(key, (2, 8, 13, 9))
want = maxpool2(jax.nn.relu(conv2d_im2col(xf, w, None, (2, 2))),
                odd="drop")
got = fused_conv_block_channel_parallel(
    xf, w, None, mesh=mesh, mode=ChannelParallelism.INPUT,
    stride=(2, 2), odd="drop")
np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                           rtol=1e-4, atol=1e-5)
print("OK")
""")

    def test_clear_errors_not_cryptic_shard_map_failures(self):
        _run(PREAMBLE + """
from repro.core.parallelism import (ChannelParallelism,
                                    conv2d_channel_parallel)
mesh = mesh_of(4)
x = jax.random.normal(jax.random.PRNGKey(0), (4, 6, 9, 9))
w = jax.random.normal(jax.random.PRNGKey(1), (10, 6, 3, 3))
def expect(mode, xx, ww, frag, **kw):
    try:
        conv2d_channel_parallel(xx, ww, None, mesh=mesh, mode=mode, **kw)
    except ValueError as e:
        assert frag in str(e), (frag, str(e))
    else:
        raise AssertionError(f"no error for {mode} {frag}")
# M=10 does not divide 4 devices
expect(ChannelParallelism.OUTPUT, x, w, "OUTPUT-channel parallelism")
# N=6 does not divide 4 devices
expect(ChannelParallelism.INPUT, x, w, "INPUT-channel parallelism")
# batch 3 does not divide a 2-wide data axis
m22 = mesh_of(2, data=2)
x3 = jax.random.normal(jax.random.PRNGKey(2), (3, 6, 9, 9))
w8 = jax.random.normal(jax.random.PRNGKey(3), (8, 6, 3, 3))
try:
    conv2d_channel_parallel(x3, w8, None, mesh=m22,
                            mode=ChannelParallelism.OUTPUT)
except ValueError as e:
    assert "does not divide" in str(e) and "data" in str(e)
else:
    raise AssertionError("no batch-divisibility error")
# rank/channel mismatch
expect(ChannelParallelism.OUTPUT, x,
       jax.random.normal(jax.random.PRNGKey(4), (8, 5, 3, 3)),
       "matching N")
print("OK")
""")

    def test_vision_engine_serves_on_mesh(self):
        _run(PREAMBLE + """
from repro.serve.vision import VisionEngine, VisionEngineConfig
params = MODEL.init(jax.random.PRNGKey(0))
mesh = mesh_of(2, data=2)
eng = VisionEngine(MODEL, params,
                   VisionEngineConfig(batch=4, mesh=mesh))
assert eng.plan.num_sharded() == 2
rng = np.random.RandomState(0)
imgs = [rng.randn(1, 28, 28).astype(np.float32) for _ in range(6)]
uids = [eng.submit(im) for im in imgs]
results = eng.run()
want = np.asarray(MODEL.forward(params, jnp.asarray(np.stack(imgs))))
assert [results[u]["label"] for u in uids] == \\
    [int(w.argmax()) for w in want]
# batch that cannot shard over the data axis fails at construction
try:
    VisionEngine(MODEL, params, VisionEngineConfig(batch=3, mesh=mesh))
except ValueError as e:
    assert "does not divide" in str(e)
else:
    raise AssertionError("no batch-divisibility error")
print("OK")
""")


class TestPlacementPass:
    """Pure-graph placement logic — no devices needed."""

    def _graph(self, conv1_c=16, conv2_c=8):
        from repro.graph import fuse_conv_blocks, trace
        from repro.models.cnn import PaperCNN, PaperCNNConfig
        m = PaperCNN(PaperCNNConfig(conv1_c=conv1_c, conv2_c=conv2_c))
        return fuse_conv_blocks(trace(m, m.input_shape()))

    @staticmethod
    def _modes(graph):
        return [n.sharding.mode for n in graph
                if getattr(n, "sharding", None) is not None]

    def test_auto_rule_ocp_when_m_wide_else_icp(self):
        from repro.graph import place_channel_parallel
        # conv1 (M=16, N=1): N is unsplittable -> OCP; conv2 (M=8, N=16):
        # ICP halves the window stream for an 8x8-buffer ring -> ICP
        g = place_channel_parallel(self._graph(), 2)
        assert self._modes(g) == ["output", "input"]
        # widen conv2's M until the ring payload (M x 8x8 partials)
        # outweighs the window-stream halving -> cost model flips to OCP
        g = place_channel_parallel(self._graph(conv2_c=256), 2)
        assert self._modes(g) == ["output", "output"]

    def test_auto_rule_2d_split_at_mesh4(self):
        """At mesh=4 the model axis factors: conv1 (N=1) stays pure OCP,
        conv2 (M=8, N=16) lands on the composed icp2 x ocp2 split — the
        ring stays short while the window stream still halves."""
        from repro.graph import place_channel_parallel
        g = place_channel_parallel(self._graph(), 4)
        assert self._modes(g) == ["output", "both"]
        specs = [n.sharding for n in g
                 if getattr(n, "sharding", None) is not None]
        assert (specs[0].icp, specs[0].ocp) == (1, 4)
        assert (specs[1].icp, specs[1].ocp) == (2, 2)
        assert str(specs[1]) == "icp2xocp2"

    def test_auto_rule_pure_data_when_nothing_divides(self):
        """Channels (15, 20) at mesh 8: conv2 can shard neither N=15 nor
        M=20 by 8, and no mixed factorization divides both — the stage
        falls back to pure data parallelism, never an invalid plan."""
        from repro.graph import place_channel_parallel
        g = place_channel_parallel(self._graph(15, 20), 8)
        assert self._modes(g) == ["none", "none"]
        for n in g:
            if getattr(n, "sharding", None) is not None:
                assert n.sharding.split(8) == (1, 1)
                assert n.sharding.data

    def test_auto_rule_falls_through_on_divisibility(self):
        from repro.graph import place_channel_parallel
        # paper channels (15, 20) at mesh 2: conv1 prefers OCP but
        # 15 % 2 != 0 and N=1 -> replicated; conv2 prefers ICP (20<30)
        # but 15 % 2 != 0 -> falls through to OCP (20 % 2 == 0)
        g = place_channel_parallel(self._graph(15, 20), 2)
        assert self._modes(g) == ["none", "output"]

    def test_forced_override_partial_and_impossible(self):
        from repro.graph import place_channel_parallel
        # forced ICP: conv1 (N=1) stays replicated, never flips to OCP
        g = place_channel_parallel(self._graph(), 2, override="input")
        assert self._modes(g) == ["none", "input"]
        # forced ICP at mesh 32: applies nowhere -> configuration error
        with pytest.raises(ValueError, match="applies to none"):
            place_channel_parallel(self._graph(), 32, override="input")

    def test_sharding_spec_survives_quant_lowering(self):
        from repro.graph import lower_quant, place_channel_parallel
        g = place_channel_parallel(self._graph(), 2)
        g = lower_quant(g, "int8")
        assert self._modes(g) == ["output", "input"]

    def test_policy_channel_parallel_aliases_and_validation(self):
        from repro.ops import ExecPolicy
        assert ExecPolicy(channel_parallel="icp").channel_parallel \
            == "input"
        assert ExecPolicy(channel_parallel="ocp").channel_parallel \
            == "output"
        assert ExecPolicy(channel_parallel="none").channel_parallel \
            == "none"
        with pytest.raises(ValueError, match="channel_parallel"):
            ExecPolicy(channel_parallel="diagonal")

    def test_compile_requires_model_axis(self):
        import jax
        import numpy as np
        from jax.sharding import Mesh
        from repro.models.cnn import PaperCNN, PaperCNNConfig
        mesh = Mesh(np.asarray(jax.devices()[:1]).reshape(1,), ("data",))
        with pytest.raises(ValueError, match="model"):
            PaperCNN(PaperCNNConfig()).compile(mesh=mesh)


class TestVisionPadLaneStats:
    """The pad-lane accounting fix: dead lanes issued to fill the
    compiled batch shape must not count as served work."""

    def test_short_final_batch_counts_real_lanes_only(self):
        import jax
        from repro.models.cnn import PaperCNN, PaperCNNConfig
        from repro.serve.vision import VisionEngine, VisionEngineConfig
        model = PaperCNN(PaperCNNConfig())
        params = model.init(jax.random.PRNGKey(0))
        eng = VisionEngine(model, params, VisionEngineConfig(batch=4))
        rng = np.random.RandomState(0)
        for _ in range(6):
            eng.submit(rng.randn(1, 28, 28).astype(np.float32))
        eng.run()
        s = eng.stats
        assert s.steps == 2 and s.images == 6
        assert s.lane_steps == 6          # real work only
        assert s.pad_lanes == 2           # issued to fill the shape
        assert s.lane_utilization == pytest.approx(6 / 8)

    def test_full_batches_have_no_pad_lanes(self):
        import jax
        from repro.models.cnn import PaperCNN, PaperCNNConfig
        from repro.serve.vision import VisionEngine, VisionEngineConfig
        model = PaperCNN(PaperCNNConfig())
        params = model.init(jax.random.PRNGKey(0))
        eng = VisionEngine(model, params, VisionEngineConfig(batch=2))
        rng = np.random.RandomState(0)
        for _ in range(4):
            eng.submit(rng.randn(1, 28, 28).astype(np.float32))
        eng.run()
        assert eng.stats.pad_lanes == 0
        assert eng.stats.lane_steps == 4
        assert eng.stats.lane_utilization == 1.0
