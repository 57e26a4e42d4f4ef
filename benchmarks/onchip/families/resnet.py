"""The ResNet family: ResNet v1.5 with bottleneck blocks on NCHW float32
images (He et al., arXiv:1512.03385, Table 1; the stride 2 of a
downsampling block on its 3x3 conv, as torchvision's ``resnet50``).

``resnet50`` belongs to it. From a configuration file alone this module
gives what a cell needs:

* ``stages`` and ``stage_ops``/``stage_bytes``: each conv's operations
  and HBM bytes, from its padded and strided shapes;
* ``materialize``: weights, batch-norm statistics and an image pool made
  on the device from the seed, in one jitted call;
* ``forward``: the plain reference, written here from the layer
  equations (batch norm written out, not folded). It imports nothing of
  the program;
* ``build_program``/``program_params``: the program's model from the
  repo's registry, checked against the file, and the benchmark's weights
  in the program's parameter layout.

The weights are ``{"stem": conv, "blocks": [{"conv1", "conv2", "conv3",
and on a stage's first block "proj": conv}, ...], "fc": {"w", "b"}}``
with each conv ``{"w", "gamma", "beta", "mean", "var"}``: ``forward``
reads the net's wiring from that structure (a block with ``proj`` after
the first block downsamples; every k x k conv pads k // 2).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

BN = ("gamma", "beta", "mean", "var")
EPS = 1e-5                 # batch norm's epsilon, the program's too


def _blocks(cfg: dict):
    """(stage, block, width, stride) of every bottleneck, in order."""
    for s, (width, depth) in enumerate(zip(cfg["widths"], cfg["depths"])):
        for j in range(depth):
            yield s, j, width, 2 if s > 0 and j == 0 else 1


def stages(cfg: dict) -> list[dict]:
    """One dict per conv, in execution order: its program parameter
    ``name``, channels ``n``->``m``, kernel ``k``, stride ``s``, padding
    ``p``, input ``h``x``w`` and output ``ho``x``wo``."""
    def conv(name, n, m, k, s, h):
        p = k // 2
        ho = (h + 2 * p - k) // s + 1
        return {"name": name, "n": n, "m": m, "k": k, "s": s, "p": p,
                "h": h, "w": h, "ho": ho, "wo": ho}
    c, h, _ = cfg["input"]
    out = [conv("stem", c, cfg["stem_width"], 7, 2, h)]
    h = (out[0]["ho"] + 2 - 3) // 2 + 1           # 3x3/2 max pool, pad 1
    n = cfg["stem_width"]
    for s, j, width, stride in _blocks(cfg):
        name = f"layer{s + 1}_{j}"
        m = width * cfg["expansion"]
        mid = conv(f"{name}/conv2", width, width, 3, stride, h)
        out += [conv(f"{name}/conv1", n, width, 1, 1, h), mid,
                conv(f"{name}/conv3", width, m, 1, 1, mid["ho"])]
        if j == 0:
            out.append(conv(f"{name}/proj", n, m, 1, stride, h))
        h, n = mid["ho"], m
    return out


def fc_in(cfg: dict) -> int:
    return cfg["widths"][-1] * cfg["expansion"]


def stage_ops(st: dict) -> int:
    """Multiply-adds x 2 of one image's conv (batch norm, ReLU, pools
    and adds are not counted, as the model's own FLOP count does not
    count them)."""
    return 2 * st["m"] * st["n"] * st["k"] ** 2 * st["ho"] * st["wo"]


def stage_bytes(st: dict, images: int, itemsize: int = 4) -> int:
    """HBM traffic of one conv call over ``images`` images: the padded
    input read once, the output written once, the weights and the folded
    bias read once per call."""
    hp, wp = st["h"] + 2 * st["p"], st["w"] + 2 * st["p"]
    act = st["n"] * hp * wp + st["m"] * st["ho"] * st["wo"]
    wts = st["m"] * st["n"] * st["k"] ** 2 + st["m"]
    return itemsize * (images * act + wts)


def flops_per_image(cfg: dict) -> int:
    return (sum(stage_ops(st) for st in stages(cfg))
            + 2 * fc_in(cfg) * cfg["n_classes"])


def weight_shapes(cfg: dict) -> dict:
    """The weights' structure (module docstring) with shapes as leaves."""
    def conv(st):
        m = st["m"]
        return {"w": (m, st["n"], st["k"], st["k"]),
                **{key: (m,) for key in BN}}
    convs = {st["name"]: conv(st) for st in stages(cfg)}
    blocks = []
    for s, j, _, _ in _blocks(cfg):
        name = f"layer{s + 1}_{j}"
        parts = ("conv1", "conv2", "conv3") + (("proj",) if j == 0 else ())
        blocks.append({part: convs[f"{name}/{part}"] for part in parts})
    return {"stem": convs["stem"], "blocks": blocks,
            "fc": {"w": (fc_in(cfg), cfg["n_classes"]),
                   "b": (cfg["n_classes"],)}}


def _is_shape(x) -> bool:
    return isinstance(x, tuple) and all(isinstance(d, int) for d in x)


# Weight and statistics distributions (the configuration's ``assumed``):
# He-normal convs keep a ReLU chain's second moment; the running
# statistics sit near what such a conv puts out (mean near 0, variance
# near 1) so each batch norm passes on about unit scale; the last batch
# norm of each block scales its branch by gamma ~ U(0.2, 0.5), as
# trained ResNets' do, so the residual stream grows slowly over the 16
# blocks instead of doubling.
GAMMA = (0.8, 1.2)
GAMMA_LAST = (0.2, 0.5)
VAR = (0.8, 1.2)
SHIFT_STD = 0.05           # beta and running mean
FC_BIAS_STD = 0.1


def _scaled(path, shape, z):
    """A leaf from a standard draw ``z`` (normal, or uniform on [0, 1)
    for gamma and the running variance)."""
    name = path[-1].key
    if name == "w" and len(shape) == 4:           # a conv: He-normal
        return z * (2.0 / (shape[1] * shape[2] * shape[3])) ** 0.5
    if name == "w":                               # the fc
        return z * shape[0] ** -0.5
    if name == "b":
        return FC_BIAS_STD * z
    if name in ("gamma", "var"):
        last = name == "gamma" and any(
            getattr(p, "key", None) == "conv3" for p in path)
        lo, hi = GAMMA_LAST if last else GAMMA if name == "gamma" else VAR
        return lo + (hi - lo) * z
    return SHIFT_STD * z                          # beta, running mean


def _weights(cfg: dict, key) -> dict:
    """Every leaf sliced from one normal and one uniform draw (two RNG
    calls, so the set-up compiles in seconds, not one per leaf)."""
    leaves, tree = jax.tree_util.tree_flatten_with_path(
        weight_shapes(cfg), is_leaf=_is_shape)
    uniform = [path[-1].key in ("gamma", "var") for path, _ in leaves]
    sizes = [math.prod(shape) for _, shape in leaves]
    k_normal, k_uniform = jax.random.split(key)
    draws = {
        False: jax.random.normal(k_normal, (sum(
            n for n, u in zip(sizes, uniform) if not u),)),
        True: jax.random.uniform(k_uniform, (sum(
            n for n, u in zip(sizes, uniform) if u),))}
    at = {False: 0, True: 0}
    out = []
    for (path, shape), u, n in zip(leaves, uniform, sizes):
        z = draws[u][at[u]:at[u] + n].reshape(shape)
        at[u] += n
        out.append(_scaled(path, shape, z))
    return jax.tree_util.tree_unflatten(tree, out)


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


def _contract(op, a, b, passes: str):
    if passes == "highest":
        return op(a, b, jax.lax.Precision.HIGHEST)
    return _three_pass(op, a, b)


def _conv_bn(x, c: dict, stride: int, passes: str, eps: float):
    """conv (padding k // 2) then batch norm from the running
    statistics, written out."""
    pad = c["w"].shape[2] // 2

    def op(a, b, precision=None):
        return jax.lax.conv_general_dilated(
            a, b, (stride, stride), [(pad, pad), (pad, pad)],
            dimension_numbers=("NCHW", "OIHW", "NCHW"),
            precision=precision, preferred_element_type=jnp.float32)
    y = _contract(op, x, c["w"], passes)

    def ch(v):
        return v[None, :, None, None]
    return (y - ch(c["mean"])) / jnp.sqrt(ch(c["var"]) + eps) \
        * ch(c["gamma"]) + ch(c["beta"])


def forward(weights, images, passes: str = "highest"):
    """Logits of a (B, C, H, W) batch. ``passes="highest"`` is float32
    at full precision; ``"high"`` is the three-pass bfloat16 control."""
    if passes not in ("highest", "high"):
        raise ValueError(f"passes must be 'highest' or 'high', got {passes}")
    eps = EPS
    x = jax.nn.relu(_conv_bn(images, weights["stem"], 2, passes, eps))
    x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 1, 3, 3),
                              (1, 1, 2, 2), ((0, 0), (0, 0), (1, 1), (1, 1)))
    for i, blk in enumerate(weights["blocks"]):
        stride = 2 if "proj" in blk and i > 0 else 1
        y = jax.nn.relu(_conv_bn(x, blk["conv1"], 1, passes, eps))
        y = jax.nn.relu(_conv_bn(y, blk["conv2"], stride, passes, eps))
        y = _conv_bn(y, blk["conv3"], 1, passes, eps)
        short = _conv_bn(x, blk["proj"], stride, passes, eps) \
            if "proj" in blk else x
        x = jax.nn.relu(y + short)
    x = x.mean(axis=(2, 3))

    def matmul(a, b, precision=None):
        return jnp.matmul(a, b, precision=precision,
                          preferred_element_type=jnp.float32)
    return _contract(matmul, x, weights["fc"]["w"], passes) \
        + weights["fc"]["b"]


# -------------------------------------------------------------- program

def build_program(cfg: dict):
    """The program's model for ``cfg['arch']``, refused unless its input
    and parameter shapes are those of the file."""
    from repro.configs.registry import get_arch
    model = get_arch(cfg["arch"]).model()
    if model.cfg.bn_eps != EPS:
        raise ValueError(f"{cfg['arch']}: program batch norm eps "
                         f"{model.cfg.bn_eps} != {EPS}")
    if tuple(model.input_shape(1)[1:]) != tuple(cfg["input"]):
        raise ValueError(f"{cfg['arch']}: program input "
                         f"{model.input_shape(1)[1:]} != file {cfg['input']}")
    structs = jax.tree.map(lambda s: jax.ShapeDtypeStruct(s, jnp.float32),
                           weight_shapes(cfg), is_leaf=_is_shape)
    want = jax.tree.map(lambda a: a.shape, program_params(cfg, structs))
    got = jax.tree.map(lambda a: a.shape,
                       jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    if got != want:
        raise ValueError(f"{cfg['arch']}: program parameters differ from "
                         f"the file's")
    return model


def program_params(cfg: dict, weights) -> dict:
    """The benchmark's weights in the program's parameter tree: conv
    ``<name>`` -> ``{"w"}`` and its batch norm ``<name>_bn``, where a
    block's convs nest under ``layer<stage>_<block>``."""
    def put(tree, path, leaf):
        *outer, last = path.split("/")
        for key in outer:
            tree = tree.setdefault(key, {})
        tree[last] = leaf

    params: dict = {}
    put(params, "stem", {"w": weights["stem"]["w"]})
    put(params, "stem_bn", {k: weights["stem"][k] for k in BN})
    for (s, j, _, _), blk in zip(_blocks(cfg), weights["blocks"]):
        for part, c in blk.items():
            name = f"layer{s + 1}_{j}/{part}"
            put(params, name, {"w": c["w"]})
            put(params, f"{name}_bn", {k: c[k] for k in BN})
    params["fc_w"], params["fc_b"] = weights["fc"]["w"], weights["fc"]["b"]
    return params
