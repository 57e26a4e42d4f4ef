"""ResNet v1.5 with bottleneck blocks — the published conv backbone.

He et al., "Deep Residual Learning for Image Recognition"
(arXiv:1512.03385), Table 1; "v1.5" puts each downsampling block's
stride 2 on its 3x3 conv instead of its first 1x1 (torchvision's
``resnet50``, MLPerf Inference's image-classification model):

  stem     7x7/2 conv pad 3 -> BN -> ReLU -> 3x3/2 max pool pad 1
  stage s  ``depths[s]`` bottlenecks of width ``widths[s]``:
           1x1 -> BN -> ReLU -> 3x3 (stride) pad 1 -> BN -> ReLU ->
           1x1 to ``expansion`` x width -> BN, plus the shortcut (the
           block input, or on a stage's first block a 1x1 conv with the
           stride -> BN), then add -> ReLU
  head     global average pool -> fc to ``n_classes``

Convs carry no bias; batch norm is part of the parameters (gamma, beta,
running mean and variance) and the compiled plan folds it into the conv
before it at bind. Every op goes through the hooked functional layer,
so one ``forward`` is the eager model and the graph the plan compiles.
Implements the model protocol of ``PaperCNN``/``VGGStyleCNN``
(``input_shape`` / ``init`` / ``forward`` / ``compile`` / ``loss``).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import TYPE_CHECKING

import jax
import jax.numpy as jnp

from repro.core.conv import Conv2DConfig, conv2d_apply, conv2d_init
from repro.graph.trace import (add, batch_norm, dense, global_avg_pool,
                               max_pool, relu)
from repro.models.common import dense_init
from repro.ops import ExecPolicy

if TYPE_CHECKING:
    from repro.graph.plan import ExecutionPlan

__all__ = ["ResNetConfig", "ResNet"]


@dataclass(frozen=True)
class ResNetConfig:
    """Defaults are ResNet-50 v1.5 at 224x224 (Table 1, 50-layer)."""

    name: str = "resnet50"
    in_channels: int = 3
    img_size: int = 224
    stem_width: int = 64
    widths: tuple[int, ...] = (64, 128, 256, 512)
    depths: tuple[int, ...] = (3, 4, 6, 3)
    expansion: int = 4
    n_classes: int = 1000
    bn_eps: float = 1e-5
    policy: ExecPolicy | None = None

    def exec_policy(self) -> ExecPolicy | None:
        return self.policy

    def convs(self) -> list[tuple[str, Conv2DConfig, int]]:
        """(parameter name, conv, input size) of every conv in execution
        order: the stem, then per block ``conv1``-``conv3`` and, on a
        stage's first block, ``proj``. Each conv's batch norm is
        ``<name>_bn``."""
        def conv(n, m, k, s):
            return Conv2DConfig(n, m, (k, k), (s, s), use_bias=False,
                                padding=(k // 2, k // 2), policy=self.policy)
        stem = conv(self.in_channels, self.stem_width, 7, 2)
        out = [("stem", stem, self.img_size)]
        h = (stem.out_size(self.img_size, self.img_size)[0] - 1) // 2 + 1
        n = self.stem_width
        for s, (width, depth) in enumerate(zip(self.widths, self.depths)):
            m = width * self.expansion
            for j in range(depth):
                stride = 2 if s > 0 and j == 0 else 1
                name = f"layer{s + 1}_{j}"
                mid = conv(width, width, 3, stride)
                ho = mid.out_size(h, h)[0]
                out += [(f"{name}/conv1", conv(n, width, 1, 1), h),
                        (f"{name}/conv2", mid, h),
                        (f"{name}/conv3", conv(width, m, 1, 1), ho)]
                if j == 0:
                    out.append((f"{name}/proj", conv(n, m, 1, stride), h))
                h, n = ho, m
        return out

    def fc_in(self) -> int:
        return self.widths[-1] * self.expansion

    def flops_per_image(self) -> int:
        """Analytic MACs x 2 of every conv and the fc (batch norm,
        ReLU, pools and adds are not counted)."""
        total = 0
        for _, c, h in self.convs():
            ho = c.out_size(h, h)[0]
            total += 2 * c.out_channels * c.in_channels * c.kernel[0] ** 2 \
                * ho * ho
        return total + 2 * self.fc_in() * self.n_classes

    def param_count(self) -> int:
        """Conv weights, BN gamma and beta, fc weight and bias (the BN
        running statistics are not parameters)."""
        total = sum(c.out_channels * c.in_channels * c.kernel[0] ** 2
                    + 2 * c.out_channels for _, c, _ in self.convs())
        return total + self.fc_in() * self.n_classes + self.n_classes

    active_param_count = param_count


def _bn_init(m: int) -> dict:
    return {"gamma": jnp.ones((m,)), "beta": jnp.zeros((m,)),
            "mean": jnp.zeros((m,)), "var": jnp.ones((m,))}


def _put(tree: dict, path: str, leaf) -> None:
    *outer, last = path.split("/")
    for key in outer:
        tree = tree.setdefault(key, {})
    tree[last] = leaf


def _get(tree: dict, path: str):
    for key in path.split("/"):
        tree = tree[key]
    return tree


class ResNet:
    def __init__(self, cfg: ResNetConfig):
        self.cfg = cfg

    def input_shape(self, batch: int = 1) -> tuple[int, int, int, int]:
        cfg = self.cfg
        return (batch, cfg.in_channels, cfg.img_size, cfg.img_size)

    def init(self, key: jax.Array) -> dict:
        """He-normal conv weights, identity batch norms (gamma 1, beta
        0, running mean 0, variance 1), fc as ``dense_init``."""
        convs = self.cfg.convs()
        keys = jax.random.split(key, len(convs) + 1)
        params: dict = {}
        for (name, c, _), k in zip(convs, keys):
            _put(params, name, conv2d_init(k, c))
            _put(params, f"{name}_bn", _bn_init(c.out_channels))
        fc_in = self.cfg.fc_in()
        params["fc_w"] = dense_init(keys[-1], (fc_in, self.cfg.n_classes),
                                    fc_in)
        params["fc_b"] = jnp.zeros((self.cfg.n_classes,))
        return params

    def _conv_bn(self, params, x, name: str, c: Conv2DConfig):
        x = conv2d_apply(_get(params, name), x, c)
        return batch_norm(x, _get(params, f"{name}_bn"), eps=self.cfg.bn_eps)

    def forward(self, params: dict, images: jax.Array) -> jax.Array:
        """(B, C, H, W) -> logits (B, n_classes)."""
        convs = {name: c for name, c, _ in self.cfg.convs()}
        x = relu(self._conv_bn(params, images, "stem", convs["stem"]))
        x = max_pool(x, 3, 2, 1)
        for s, depth in enumerate(self.cfg.depths):
            for j in range(depth):
                name = f"layer{s + 1}_{j}"
                y = x
                for part in ("conv1", "conv2"):
                    y = relu(self._conv_bn(params, y, f"{name}/{part}",
                                           convs[f"{name}/{part}"]))
                y = self._conv_bn(params, y, f"{name}/conv3",
                                  convs[f"{name}/conv3"])
                short = x if j else self._conv_bn(params, x, f"{name}/proj",
                                                  convs[f"{name}/proj"])
                x = relu(add(y, short))
        x = global_avg_pool(x)
        return dense(x, params["fc_w"], params["fc_b"],
                     policy=self.cfg.exec_policy())

    def compile(self, policy: ExecPolicy | None = None, *,
                fuse: bool = True, batch: int = 1, mesh=None,
                autotune: bool = False,
                verify: bool = True) -> "ExecutionPlan":
        """Same contract as ``PaperCNN.compile`` (DESIGN.md §8-§10):
        trace -> batch-norm fold -> quant lowering -> placement.
        No stage is fused (no conv is followed by a 2x2 pool); each conv
        is one ``conv_window`` call, its rows tiled by the kernel's
        row-block grid (DESIGN.md §13)."""
        from repro.graph.plan import compile_model
        return compile_model(self, self.input_shape(batch), policy=policy,
                             fuse=fuse, mesh=mesh, autotune=autotune,
                             verify=verify)

    def loss(self, params: dict, batch: dict, ctx=None
             ) -> tuple[jax.Array, dict]:
        logits = self.forward(params, batch["images"])
        labels = batch["labels"]
        logp = jax.nn.log_softmax(logits.astype(jnp.float32))
        nll = -jnp.take_along_axis(logp, labels[:, None], axis=-1).mean()
        acc = (logits.argmax(-1) == labels).mean()
        return nll, {"ce": nll, "accuracy": acc}
