"""The conv-chain family: VALID conv -> bias -> ReLU -> 2x2/2 max-pool
blocks, then one dense layer, on NCHW float32 images.

The paper's Tab. I CNN (``mnist_cnn``) belongs to it. From a
configuration file alone this module gives what a cell needs:

* ``stages`` and ``stage_ops``/``stage_bytes``: each conv stage's
  operations and minimal HBM bytes, from its shapes;
* ``materialize``: weights and an image pool made on the device from
  the seed, in one jitted call;
* ``forward``: the plain reference, written here from the layer
  equations. It imports nothing of the program;
* ``build_program``/``program_params``: the program's model from the
  repo's registry, checked against the file, and the benchmark's weights
  in the program's parameter layout.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

POOL = 2            # 2x2 max-pool, stride 2
BIAS_STD = 0.1      # nonzero biases, so the bias path is checked too


def stages(cfg: dict) -> list[dict]:
    """One dict per conv block: channels ``n``->``m``, kernel ``k``,
    input ``h``x``w``, conv output ``ho``x``wo``, pooled ``hp``x``wp``."""
    c, h, w = cfg["input"]
    out = []
    for m, k in cfg["blocks"]:
        ho, wo = h - k + 1, w - k + 1
        if ho % POOL or wo % POOL:
            raise ValueError(f"{cfg['name']}: pre-pool map {ho}x{wo} is odd")
        out.append({"n": c, "m": m, "k": k, "h": h, "w": w, "ho": ho,
                    "wo": wo, "hp": ho // POOL, "wp": wo // POOL})
        c, h, w = m, ho // POOL, wo // POOL
    return out


def fc_in(cfg: dict) -> int:
    last = stages(cfg)[-1]
    return last["m"] * last["hp"] * last["wp"]


def stage_ops(st: dict) -> int:
    """Multiply-adds x 2 of one image's conv (bias, ReLU and pool are
    not counted, as the model's own FLOP counts do not count them)."""
    return 2 * st["m"] * st["n"] * st["k"] ** 2 * st["ho"] * st["wo"]


def stage_bytes(st: dict, images: int, itemsize: int = 4) -> int:
    """Least HBM traffic of one fused call over ``images`` images: each
    input read once, the pooled output written once, the weights and
    bias read once per call."""
    act = st["n"] * st["h"] * st["w"] + st["m"] * st["hp"] * st["wp"]
    wts = st["m"] * st["n"] * st["k"] ** 2 + st["m"]
    return itemsize * (images * act + wts)


def flops_per_image(cfg: dict) -> int:
    return (sum(stage_ops(st) for st in stages(cfg))
            + 2 * fc_in(cfg) * cfg["n_classes"])


def weight_shapes(cfg: dict) -> list[tuple[tuple, tuple]]:
    """(weight, bias) shapes: OIHW per conv block, then (fc_in, classes)."""
    out = [((st["m"], st["n"], st["k"], st["k"]), (st["m"],))
           for st in stages(cfg)]
    return out + [((fc_in(cfg), cfg["n_classes"]), (cfg["n_classes"],))]


def _weights(cfg: dict, key) -> list[tuple[jax.Array, jax.Array]]:
    """He-scaled normal weights and small normal biases."""
    shapes = weight_shapes(cfg)
    out = []
    for (ws, bs), k in zip(shapes, jax.random.split(key, len(shapes))):
        kw, kb = jax.random.split(k)
        fan_in = ws[1] * ws[2] * ws[3] if len(ws) == 4 else ws[0]
        out.append((jax.random.normal(kw, ws) * fan_in ** -0.5,
                    BIAS_STD * jax.random.normal(kb, bs)))
    return out


def materialize(cfg: dict, seed_words, n_images: int):
    """``(weights, images)`` on the device from the seed's two 32-bit
    words, in one jitted call: the same seed gives the same of both."""
    def make(words):
        key = jax.random.fold_in(jax.random.PRNGKey(words[0]), words[1])
        k_weights, k_images = jax.random.split(key)
        return (_weights(cfg, k_weights),
                jax.random.normal(k_images, (n_images, *cfg["input"])))
    return jax.jit(make)(jnp.asarray(seed_words, jnp.uint32))


# ------------------------------------------------------------ reference

def _split_bf16(x):
    """``x = hi + lo`` to about 16 bits: ``hi`` is ``x`` rounded to its
    top 16 bits by integer arithmetic on its bits (a compiler may fold a
    float32 -> bfloat16 -> float32 round trip away, but not this), ``lo``
    the rest rounded to bfloat16."""
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    hi = jax.lax.bitcast_convert_type(
        (bits + jnp.uint32(0x8000)) & jnp.uint32(0xFFFF0000), jnp.float32)
    return hi.astype(jnp.bfloat16), (x - hi).astype(jnp.bfloat16)


def _three_pass(op, a, b):
    """``op`` on float32 operands in three bfloat16 passes (hi*hi +
    hi*lo + lo*hi, float32 accumulation): what a TPU's ``high``
    precision computes, written out so that every backend computes it."""
    ah, al = _split_bf16(a)
    bh, bl = _split_bf16(b)
    return op(ah, bh) + op(ah, bl) + op(al, bh)


def _conv(x, w, passes: str):
    def op(a, b, precision=None):
        return jax.lax.conv_general_dilated(
            a, b, (1, 1), "VALID", dimension_numbers=("NCHW", "OIHW", "NCHW"),
            precision=precision, preferred_element_type=jnp.float32)
    if passes == "highest":
        return op(x, w, jax.lax.Precision.HIGHEST)
    return _three_pass(op, x, w)


def _matmul(x, w, passes: str):
    def op(a, b, precision=None):
        return jnp.matmul(a, b, precision=precision,
                          preferred_element_type=jnp.float32)
    if passes == "highest":
        return op(x, w, jax.lax.Precision.HIGHEST)
    return _three_pass(op, x, w)


def forward(weights, images, passes: str = "highest"):
    """Logits of a (B, C, H, W) batch. ``passes="highest"`` is float32
    at full precision; ``"high"`` is the three-pass bfloat16 control."""
    if passes not in ("highest", "high"):
        raise ValueError(f"passes must be 'highest' or 'high', got {passes}")
    x = images
    for w, b in weights[:-1]:
        x = jax.nn.relu(_conv(x, w, passes) + b[None, :, None, None])
        x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max,
                                  (1, 1, POOL, POOL), (1, 1, POOL, POOL),
                                  "VALID")
    w, b = weights[-1]
    return _matmul(x.reshape(x.shape[0], -1), w, passes) + b


# -------------------------------------------------------------- program

def build_program(cfg: dict):
    """The program's model for ``cfg['arch']``, refused unless its input
    and parameter shapes are those of the file."""
    from repro.configs.registry import get_arch
    model = get_arch(cfg["arch"]).model()
    if tuple(model.input_shape(1)[1:]) != tuple(cfg["input"]):
        raise ValueError(f"{cfg['arch']}: program input "
                         f"{model.input_shape(1)[1:]} != file {cfg['input']}")
    want = jax.tree.map(lambda a: a.shape, program_params(
        cfg, [(jax.ShapeDtypeStruct(ws, jnp.float32),
               jax.ShapeDtypeStruct(bs, jnp.float32))
              for ws, bs in weight_shapes(cfg)]))
    got = jax.tree.map(lambda a: a.shape,
                       jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    if got != want:
        raise ValueError(f"{cfg['arch']}: program parameters {got} != "
                         f"file {want}")
    return model


def program_params(cfg: dict, weights) -> dict:
    """The benchmark's weights in the program's parameter tree."""
    params = {name: {"w": w, "b": b}
              for name, (w, b) in zip(cfg["conv_params"], weights[:-1])}
    params["fc_w"], params["fc_b"] = weights[-1]
    return params
