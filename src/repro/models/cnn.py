"""The paper's CNN (Tab. I) built on core.conv — the accelerator's workload.

Structure (MNIST 28×28×1, VALID padding, as in the paper):
  conv1: 3×3 × 15, stride 1   -> (15, 26, 26)    params 150 (+bias in paper count)
  relu + maxpool 2×2 stride 2 -> (15, 13, 13)
  conv2: 6×6 × 20, stride 1   -> (20, 8, 8)      params 10,820 (15·6·6·20 + 20)
  relu + maxpool 2×2 stride 2 -> (20, 4, 4)
  fc:    320 -> 10            params 3,210
Total 14,180 params — matching the paper's Tab. I per-layer counts.

Execution is an ``ExecPolicy`` (repro.ops, DESIGN.md §7): backend
``ref`` (paper-dataflow oracle) | ``xla`` (MXU im2col form) | ``pallas``
(window-stationary kernel) | auto, and quantization ``none`` | ``qformat``
(paper-exact Q8.8) | ``int8``. The legacy ``path=``/``quant=`` string
fields still work via the core.conv deprecation shim.

``forward`` routes through the trace-aware functional layer
(core.conv.conv2d_apply, core.window.maxpool2, graph.trace relu/flatten/
dense), so the same body is both the eager model and the program that
``PaperCNN.compile()`` lifts into a fused, static ``ExecutionPlan``
(repro.graph, DESIGN.md §8).
"""
from __future__ import annotations

from dataclasses import dataclass, replace
from typing import TYPE_CHECKING, Literal

import jax
import jax.numpy as jnp

from repro.core.conv import Conv2DConfig, conv2d_apply, conv2d_init
from repro.core.quantize import QFormat
from repro.core.window import maxpool2
from repro.graph.trace import dense, flatten, relu
from repro.models.common import dense_init
from repro.ops import ExecPolicy
from repro.sharding.logical import A

if TYPE_CHECKING:
    from repro.graph.plan import ExecutionPlan

__all__ = ["PaperCNNConfig", "PaperCNN"]


@dataclass(frozen=True)
class PaperCNNConfig:
    name: str = "mnist_cnn"
    in_channels: int = 1
    img_size: int = 28
    conv1_k: int = 3
    conv1_c: int = 15
    conv2_k: int = 6
    conv2_c: int = 20
    n_classes: int = 10
    # legacy string spellings (deprecated — prefer ``policy``)
    path: Literal["ref", "im2col", "kernel"] | None = None
    quant: Literal["none", "qformat", "int8"] = "none"
    policy: ExecPolicy | None = None

    @property
    def conv1_cfg(self) -> Conv2DConfig:
        return Conv2DConfig(self.in_channels, self.conv1_c,
                            (self.conv1_k, self.conv1_k), (1, 1),
                            path=self.path, quant=self.quant,
                            qformat=QFormat(), policy=self.policy)

    @property
    def conv2_cfg(self) -> Conv2DConfig:
        return Conv2DConfig(self.conv1_c, self.conv2_c,
                            (self.conv2_k, self.conv2_k), (1, 1),
                            path=self.path, quant=self.quant,
                            qformat=QFormat(), policy=self.policy)

    def exec_policy(self) -> ExecPolicy | None:
        """The model-wide ExecPolicy (same resolution as Conv2DConfig:
        explicit ``policy`` wins, legacy strings map through the shim,
        neither → None and the ambient ``use_policy`` applies)."""
        return self.conv1_cfg.exec_policy()

    def feature_sizes(self) -> tuple[int, int, int]:
        """(post-pool1, post-pool2, flattened fc input)."""
        s1 = (self.img_size - self.conv1_k + 1) // 2
        s2 = (s1 - self.conv2_k + 1) // 2
        return s1, s2, s2 * s2 * self.conv2_c


    def flops_per_image(self) -> int:
        """Analytic MACs×2 for Tab. III-style GOPS accounting."""
        o1 = self.img_size - self.conv1_k + 1
        f1 = 2 * self.conv1_c * self.in_channels * self.conv1_k ** 2 * o1 * o1
        s1 = o1 // 2
        o2 = s1 - self.conv2_k + 1
        f2 = 2 * self.conv2_c * self.conv1_c * self.conv2_k ** 2 * o2 * o2
        _, _, fc_in = self.feature_sizes()
        f3 = 2 * fc_in * self.n_classes
        return f1 + f2 + f3

    def param_count(self) -> int:
        c1 = self.in_channels * self.conv1_k ** 2 * self.conv1_c + self.conv1_c
        c2 = self.conv1_c * self.conv2_k ** 2 * self.conv2_c + self.conv2_c
        fc = self.feature_sizes()[2] * self.n_classes + self.n_classes
        return c1 + c2 + fc

    active_param_count = param_count


class PaperCNN:
    def __init__(self, cfg: PaperCNNConfig):
        self.cfg = cfg

    def input_shape(self, batch: int = 1) -> tuple[int, int, int, int]:
        cfg = self.cfg
        return (batch, cfg.in_channels, cfg.img_size, cfg.img_size)

    def init(self, key: jax.Array) -> dict:
        cfg = self.cfg
        k1, k2, k3 = jax.random.split(key, 3)
        _, _, fc_in = cfg.feature_sizes()
        return {
            "conv1": conv2d_init(k1, cfg.conv1_cfg),
            "conv2": conv2d_init(k2, cfg.conv2_cfg),
            "fc_w": dense_init(k3, (fc_in, cfg.n_classes), fc_in),
            "fc_b": jnp.zeros((cfg.n_classes,)),
        }

    def axes(self) -> dict:
        return {
            "conv1": {"w": A("conv_out", "conv_in", None, None),
                      "b": A("conv_out")},
            "conv2": {"w": A("conv_out", "conv_in", None, None),
                      "b": A("conv_out")},
            "fc_w": A(None, None), "fc_b": A(None),
        }

    def forward(self, params: dict, images: jax.Array) -> jax.Array:
        """images: (B, C, H, W) -> logits (B, n_classes).

        Every op is trace-aware: with real arrays this is the eager
        model; with a ``TracedArray`` it records the repro.graph IR. The
        pools see even maps for the paper's sizes (26, 8); odd sizes now
        raise at the pool instead of silently dropping a row/column.
        """
        cfg = self.cfg
        x = conv2d_apply(params["conv1"], images, cfg.conv1_cfg)
        x = maxpool2(relu(x))
        x = conv2d_apply(params["conv2"], x, cfg.conv2_cfg)
        x = maxpool2(relu(x))
        x = flatten(x)
        return dense(x, params["fc_w"], params["fc_b"],
                     policy=cfg.exec_policy())

    def compile(self, policy: ExecPolicy | None = None, *,
                fuse: bool = True, batch: int = 1,
                mesh=None, autotune: bool = False,
                verify: bool = True) -> "ExecutionPlan":
        """Lift this model into a fused, static ``ExecutionPlan``
        (repro.graph, DESIGN.md §8): trace → conv+relu+pool fusion →
        quantization lowering → DQE. Quant mode resolves now (``policy``
        > config policy > ambient ``use_policy``); backend selection
        stays dynamic through the op registry at call time.

        ``mesh`` (jax.sharding.Mesh with a ``model`` axis) additionally
        runs the channel-parallel placement pass (DESIGN.md §9): each
        conv stage gets the paper's ICP or OCP schedule from its channel
        counts (override via ``ExecPolicy.channel_parallel``) and
        ``plan.bind`` places the weights shard-resident.

        ``autotune=True`` makes ``plan.bind`` measure tile candidates per
        conv/fused/dense stage (DESIGN.md §10) and bake the winners into
        the BoundPlan — serving then runs on measured tiles with no
        re-tuning on the hot path."""
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

