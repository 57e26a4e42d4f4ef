"""The ``resnet`` family of the on-chip benchmark, on the CPU: its
counts are the program's own, it refuses a file that is not what the
program runs, and at a tiny size the program (Pallas kernels in
interpret mode) passes ``harness.check`` while the control (the
reference in three bfloat16 passes) fails it.
"""
import sys
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE / "tools"))

import control  # noqa: E402
import harness  # noqa: E402

FAM = harness.load_module(HERE / "families" / "resnet.py")
CFG = harness.load_config("resnet50")
# widths / 16, one bottleneck a stage, 32x32 input: both strides and a
# projection shortcut on every stage, as at full size
TINY = dict(CFG, name="resnet_tiny", input=[3, 32, 32], stem_width=4,
            widths=[4, 8, 16, 32], depths=[1, 1, 1, 1], n_classes=10,
            batch=4)


def test_stage_ops_sum_to_the_models_own_count():
    from repro.models.resnet import ResNetConfig
    prog = ResNetConfig()
    assert FAM.flops_per_image(CFG) == prog.flops_per_image() \
        == 8_178_368_512
    # conv by conv: the program's names, channels, kernels, strides and
    # input sizes, in the program's order
    assert [(st["name"], st["n"], st["m"], st["k"], st["s"], st["h"])
            for st in FAM.stages(CFG)] == [
        (name, c.in_channels, c.out_channels, c.kernel[0], c.stride[0], h)
        for name, c, h in prog.convs()]


def test_stage_bytes_count_padded_input_output_and_weights_once():
    stem = FAM.stages(CFG)[0]          # 3x224x224, pad 3 -> 64x112x112
    assert (stem["ho"], stem["p"]) == (112, 3)
    assert FAM.stage_bytes(stem, 8) == 4 * (
        8 * (3 * 230 * 230 + 64 * 112 * 112) + 64 * 3 * 7 * 7 + 64)


@pytest.mark.parametrize("key,value", [("widths", [64, 128, 256, 1024]),
                                       ("depths", [3, 4, 6, 2]),
                                       ("expansion", 2)])
def test_program_refuses_a_file_that_is_not_what_it_runs(key, value):
    with pytest.raises(ValueError, match="parameters"):
        FAM.build_program(dict(CFG, **{key: value}))
    FAM.build_program(CFG)


def tiny_program(cfg):
    """The program's ResNet at the tiny file's sizes, checked as
    ``build_program`` checks the registry's."""
    import jax
    import jax.numpy as jnp
    from repro.models.resnet import ResNet, ResNetConfig
    model = ResNet(ResNetConfig(
        img_size=cfg["input"][1], stem_width=cfg["stem_width"],
        widths=tuple(cfg["widths"]), depths=tuple(cfg["depths"]),
        expansion=cfg["expansion"], n_classes=cfg["n_classes"]))
    structs = jax.tree.map(lambda s: jax.ShapeDtypeStruct(s, jnp.float32),
                           FAM.weight_shapes(cfg), is_leaf=FAM._is_shape)
    assert jax.tree.map(lambda a: a.shape,
                        FAM.program_params(cfg, structs)) == \
        jax.tree.map(lambda a: a.shape,
                     jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    return model


@pytest.mark.parametrize("seed", [1, 2**31 + 77])
def test_program_passes_and_control_fails_the_check(seed):
    """Both through ``harness.check``, as a run's answers go."""
    import jax
    from repro.ops import ExecPolicy, use_policy
    weights, images = FAM.materialize(TINY, harness.seed_words(seed),
                                      TINY["batch"])
    pool = np.asarray(images)
    with use_policy(ExecPolicy(backend="pallas")):
        bound = tiny_program(TINY).compile(batch=len(pool)).bind(
            FAM.program_params(TINY, weights))
        served = np.asarray(jax.jit(lambda x: bound(x))(pool))
    every = np.arange(len(pool))
    got = harness.check(FAM, TINY, weights, pool, served, every, 0)
    assert harness.is_correct(got), got
    ctl = control.control_checks(FAM, TINY, seed, TINY["batch"])
    assert not harness.is_correct(ctl), ctl
    assert ctl["logit_err"]["value"] > got["logit_err"]["value"]
