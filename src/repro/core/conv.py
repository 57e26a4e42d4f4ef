"""Conv modules built on the paper's window pipeline (C1+C2+C3+C4 composed).

``Conv2D``: the accelerator's conv layer. Execution is delegated to the
``repro.ops`` registry (DESIGN.md §7): ``Conv2DConfig.policy`` carries an
``ExecPolicy`` (backend = ``ref`` paper-dataflow oracle | ``xla`` MXU-shaped
im2col | ``pallas`` window-stationary kernel; quant = ``none`` | ``qformat``
Q8.8 | ``int8``), and ``conv2d_apply`` is one registry call.

**Deprecation shim**: the legacy ``Conv2DConfig(path=..., quant=...)``
string spelling still works — ``path`` maps through
``repro.ops.compat.policy_from_legacy`` (``ref``→``ref``,
``im2col``→``xla``, ``kernel``→``pallas``) with a DeprecationWarning. This
file is the only sanctioned home of that mapping outside ``repro.ops``
(enforced by the ``string-dispatch`` lint rule, DESIGN.md §14).

``CausalConv1D``: the 1-D window pipeline used by Mamba2/RWKV token-shift
(DESIGN.md §5) — ``causal_conv1d`` is re-exported from the op registry;
its decode-time ``step`` keeps a (K-1)-deep ring state — literally the
paper's WINDOW_BUFFER holding the last K-1 samples.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import TYPE_CHECKING, Literal

import jax
import jax.numpy as jnp

from repro.core.quantize import QFormat
from repro.core.window import conv_output_size

if TYPE_CHECKING:                     # repro.ops imports resolve lazily at
    from repro.ops.policy import ExecPolicy  # call time: core is imported
                                      # *by* the ops package (no cycle)

__all__ = ["Conv2DConfig", "conv2d_init", "conv2d_apply",
           "causal_conv1d", "causal_conv1d_step"]


@dataclass(frozen=True)
class Conv2DConfig:
    in_channels: int
    out_channels: int
    kernel: tuple[int, int] = (3, 3)
    stride: tuple[int, int] = (1, 1)
    use_bias: bool = True
    # zero rows/columns added on each side of the input (top and bottom,
    # left and right): (0, 0) is the paper's VALID conv, (k // 2, k // 2)
    # SAME for an odd kernel k
    padding: tuple[int, int] = (0, 0)
    # legacy string spellings (deprecated — prefer ``policy``)
    path: Literal["ref", "im2col", "kernel"] | None = None
    quant: Literal["none", "qformat", "int8"] = "none"
    qformat: QFormat = field(default_factory=QFormat)
    policy: ExecPolicy | None = None

    def exec_policy(self) -> "ExecPolicy | None":
        """The effective ExecPolicy for this config.

        Explicit ``policy`` wins (conflicting legacy fields raise); legacy
        ``path``/``quant`` strings map through the compat shim. With neither
        set, returns None — the op registry then resolves the ambient
        ``use_policy(...)`` context, so a default-configured model follows
        the surrounding policy block."""
        legacy = self.path is not None or self.quant != "none"
        if self.policy is not None:
            if legacy:
                raise ValueError(
                    f"Conv2DConfig got policy={self.policy} AND legacy "
                    f"path={self.path!r}/quant={self.quant!r}; set the "
                    f"quant/backend on the ExecPolicy instead")
            return self.policy
        if not legacy:
            return None               # defer to the ambient use_policy(...)
        from repro.ops import policy_from_legacy
        return policy_from_legacy(self.path, self.quant, self.qformat)

    def out_size(self, h: int, w: int) -> tuple[int, int]:
        ph, pw = self.padding
        return (conv_output_size(h + 2 * ph, self.kernel[0], self.stride[0]),
                conv_output_size(w + 2 * pw, self.kernel[1], self.stride[1]))


def conv2d_init(key: jax.Array, cfg: Conv2DConfig, dtype=jnp.float32) -> dict:
    kh, kw = cfg.kernel
    fan_in = cfg.in_channels * kh * kw
    wkey, _ = jax.random.split(key)
    w = jax.random.normal(wkey, (cfg.out_channels, cfg.in_channels, kh, kw),
                          dtype) * jnp.asarray(fan_in, dtype) ** -0.5
    params = {"w": w}
    if cfg.use_bias:
        params["b"] = jnp.zeros((cfg.out_channels,), dtype)
    return params


def conv2d_apply(params: dict, x: jax.Array, cfg: Conv2DConfig) -> jax.Array:
    """x: (B, N, H, W) -> (B, M, Ho, Wo) under the configured ExecPolicy.

    Duck-typed graph hook: when ``x`` is a ``TracedArray``
    (repro.graph.trace) this records a Conv2D node in the graph under
    construction instead of computing — how any core.conv-based model
    becomes liftable into the repro.graph IR (DESIGN.md §8)."""
    hook = getattr(x, "graph_conv2d", None)
    if hook is not None:
        return hook(params, cfg)
    from repro.ops import conv2d
    return conv2d(x, params["w"], params.get("b"), stride=cfg.stride,
                  padding=cfg.padding, policy=cfg.exec_policy())


def causal_conv1d(x: jax.Array, w: jax.Array, b: jax.Array | None = None, *,
                  policy: "ExecPolicy | None" = None) -> jax.Array:
    """Compat re-export of ``repro.ops.causal_conv1d`` (the 1-D window
    pipeline, DESIGN.md §5)."""
    from repro.ops import causal_conv1d as op
    return op(x, w, b, policy=policy)


def causal_conv1d_step(x_t: jax.Array, state: jax.Array, w: jax.Array,
                       b: jax.Array | None = None
                       ) -> tuple[jax.Array, jax.Array]:
    """Single-token decode step with the (K-1)-deep window state.

    x_t: (B, C); state: (B, K-1, C) holding the previous K-1 inputs
    (oldest first). Returns (y_t, new_state). This ring update is the
    paper's WINDOW_BUFFER shift (step 2 of §III.B.2) in one dimension.
    """
    k = w.shape[0]
    window = jnp.concatenate([state, x_t[:, None, :]], axis=1)  # (B, K, C)
    y = jnp.einsum("bkc,kc->bc", window, w)
    if b is not None:
        y = y + b
    new_state = window[:, 1:, :] if k > 1 else state
    return y, new_state
