"""Smoke test of the vision serving path on a TPU, kernels compiled.

Serves two models through the stack ``launch/serve.py`` builds
(``build_vision_server``: a ``VisionEngine`` over bucketed compiled plans
behind the ``Frontend``), with the op registry pinned to the ``pallas``
backend so every conv stage runs a compiled Pallas kernel (a kernel that
cannot take a call raises; nothing falls back):

  * ``highres_cnn``, a VGG-style stack that is not a published config:
    224x224x3, batch 8, bucket ladder 1/2/4/8 — each block is one
    ``fused_cwp`` call, its rows tiled by the kernel's row-block grid;
  * ``mnist_cnn``, the paper's CNN (Tab. I);
  * ``resnet50``, ResNet-50 v1.5 at 224x224x3: padded, strided and 1x1
    convs, batch norm folded at bind, residual adds.

Each model is served in quant ``none`` and ``int8`` (``resnet50``, whose
folded batch norm has no int8 lowering, in ``none`` only) on 16 images
drawn from ``--seed``, submitted in waves of 8, 4, 2, 1, 1 so that every
bucket of the ladder serves. Every bucket executable must contain
``tpu_custom_call`` (kernels compiled, not interpreted), and the served
logits must match a plain float32 forward at ``highest`` matmul
precision — the model's ``ref`` backend (``xla`` for ``resnet50``, whose
paper-dataflow ``ref`` convs would hold every window's products at
once) — within the tolerances below.

``--mesh 2x2`` runs only the sharded path (four chips): ``highres_cnn``
compiled channel-parallel over a data x model mesh, checked for weights
and batches resident on all four devices, and its logits compared with
the one-chip unsharded plan in the same process.

Usage, from the repository root on a TPU host:

    python chip_smoke.py                 # one chip
    python chip_smoke.py --arch resnet50 # one chip, one model
    python chip_smoke.py --mesh 2x2      # four chips

The last line of stdout is ``{"ok": true, "device": {...}}``; any failed
check raises and the script exits nonzero without printing it. Without a
TPU it exits nonzero before serving anything.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

N_IMAGES = 16
WAVES = (8, 4, 2, 1, 1)     # full bucket, then tails: buckets 8, 4, 2, 1
BATCH = 8

# Max |served - reference| over all logits, relative to max |reference|.
#  * none: the kernels and the fc einsum contract float32 on the MXU at
#    HIGHEST precision (fp32-accurate passes); what remains is summation
#    order — per-tap partial sums vs the reference's odd-even tree —
#    about 1e-6 relative through five layers. 1e-4 leaves 100x headroom
#    and still fails a single-pass bf16 contraction (~4e-3 relative).
#  * int8: five quantized layers (per-tensor activation and per-channel
#    weight scales, step absmax/127) each add ~1% relative noise; they
#    compound to a few percent of the logit scale. 0.1 bounds that with
#    margin and still fails a wrong scale or a dropped requant epilogue.
# The mesh phase compares two runs of the same quantized program, so only
# reduction order (ring reduce vs one contraction) separates them: 1e-4
# under none; under int8 the codes accumulate exactly and a 1-ulp requant
# difference can move one downstream code by one step, far below 1e-2.
TOL_VS_REF = {"none": 1e-4, "int8": 0.1}
TOL_SHARDED = {"none": 1e-4, "int8": 1e-2}
ARCHS = ("highres_cnn", "mnist_cnn", "resnet50")
QUANTS = {"resnet50": ("none",)}               # others: none and int8
REFERENCE_BACKEND = {"resnet50": "xla"}        # others: ref


def require_tpu():
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU; JAX found "
                         f"{dev.platform} ({dev.device_kind})")
    return dev


def seeded_images(model, seed: int):
    import numpy as np
    rng = np.random.RandomState(seed)
    return rng.standard_normal(
        (N_IMAGES, *model.input_shape()[1:])).astype(np.float32)


def reference_logits(model, params, images, backend: str = "ref"):
    """A plain float32 forward at highest matmul precision through
    ``backend``: ``ref`` (paper-dataflow conv oracle, dense einsum) or
    ``xla`` (im2col einsum)."""
    import jax
    import numpy as np
    from repro.ops import ExecPolicy, use_policy
    with use_policy(ExecPolicy(backend=backend)), \
            jax.default_matmul_precision("highest"):
        return np.asarray(jax.jit(model.forward)(params, images))


def serve(frontend, images):
    """Submit ``images`` in ``WAVES``, draining after each; logits in
    submission order."""
    import numpy as np
    it = iter(images)
    for n in WAVES:
        for _ in range(n):
            frontend.submit(next(it))
        frontend.run_until_drained()
    res = frontend.results
    return np.stack([res[rid]["logits"] for rid in sorted(res)])


def compare(name: str, got, want, tol: float) -> None:
    """Fail unless every logit is finite, the max abs error is within
    ``tol`` of the reference's scale, and labels agree wherever the
    reference's top-2 margin exceeds twice that error budget."""
    import numpy as np
    scale = float(np.abs(want).max())
    err = float(np.abs(got - want).max())
    top2 = np.sort(want, axis=-1)[:, -2:]
    decisive = (top2[:, 1] - top2[:, 0]) > 2 * tol * scale
    agree = got.argmax(-1) == want.argmax(-1)
    print(f"{name}: max abs err {err:.3e} = {err / scale:.3e} x max|ref| "
          f"{scale:.3e} (tol {tol:g}); labels agree {int(agree.sum())}/"
          f"{len(agree)} ({int(decisive.sum())} decisive)", flush=True)
    if not np.isfinite(got).all():
        raise AssertionError(f"{name}: non-finite logits")
    if err > tol * scale:
        raise AssertionError(f"{name}: error {err:.3e} beyond tolerance "
                             f"{tol:g} x {scale:.3e}")
    if not agree[decisive].all():
        raise AssertionError(f"{name}: labels differ on decisive images")


def stage_backends(plan) -> list[str]:
    """What each compute stage of a plan served under the pinned pallas
    policy runs: conv stages the Pallas kernels (registry dispatch raises
    rather than leave pallas), dense the int8 Pallas GEMM under int8 and
    a plain XLA einsum otherwise."""
    from repro.graph.ir import Conv2DNode, DenseNode, FusedConvBlockNode
    out = []
    for node in plan.graph:
        if isinstance(node, FusedConvBlockNode):
            kind = "pallas fused_cwp"
        elif isinstance(node, Conv2DNode):
            kind = "pallas conv_window"
        elif isinstance(node, DenseNode):
            kind = ("pallas qmatmul" if plan.quant == "int8"
                    else "xla einsum")
        else:
            continue
        out.append(f"%{node.id} {node.op} -> {kind}")
    return out


def check_kernels(engine, quant: str) -> None:
    """Every bucket executable holds compiled Pallas kernels: at least one
    per conv stage, plus the int8 GEMM under int8."""
    from repro.graph.ir import Conv2DNode, FusedConvBlockNode
    want = sum(isinstance(n, (Conv2DNode, FusedConvBlockNode))
               for n in engine.plan.graph) + (quant == "int8")
    for bucket in engine.buckets:
        n = engine.executable(bucket).as_text().count("tpu_custom_call")
        print(f"  bucket {bucket}: {n} tpu_custom_call, ready in "
              f"{engine.ready_s[bucket]:.2f} s", flush=True)
        if n < want:
            raise AssertionError(f"bucket {bucket}: {n} compiled kernels, "
                                 f"expected at least {want}")


def one_chip(arch: str, seed: int) -> None:
    import jax
    from repro.configs.registry import get_arch
    from repro.launch.serve import build_vision_server
    from repro.ops import ExecPolicy, use_policy

    model = get_arch(arch).model()
    params = model.init(jax.random.PRNGKey(seed))
    images = seeded_images(model, seed)
    want = reference_logits(model, params, images,
                            REFERENCE_BACKEND.get(arch, "ref"))
    for quant in QUANTS.get(arch, ("none", "int8")):
        with use_policy(ExecPolicy(backend="pallas", quant=quant)):
            engine, frontend, _ = build_vision_server(model, params,
                                                      capacity=BATCH)
        print(f"{arch} quant={quant} input {model.input_shape(BATCH)} "
              f"buckets {list(engine.buckets)}", flush=True)
        for line in stage_backends(engine.plan):
            print(f"  {line}")
        check_kernels(engine, quant)
        got = serve(frontend, images)
        s = engine.stats
        if (s.steps, s.pad_lanes) != (len(WAVES), 0):
            raise AssertionError(f"expected {len(WAVES)} exact-bucket "
                                 f"steps, got {s.steps} with "
                                 f"{s.pad_lanes} pad lanes")
        compare(f"  {arch} {quant} vs f32 ref", got, want,
                TOL_VS_REF[quant])


def check_placement(engine, mesh) -> None:
    """Weights of every sharded stage and every bucket's input batch span
    all of the mesh's devices; nothing is left on one device."""
    import jax
    everywhere = set(mesh.devices.flat)
    bound = engine.bound(BATCH)
    leaves = jax.tree_util.tree_leaves(bound.placed)
    for node in bound.plan.graph:
        spec = getattr(node, "sharding", None)
        if spec is not None and spec.mode != "none" and len(node.inputs) > 1:
            leaves += jax.tree_util.tree_leaves(bound.folded[node.inputs[1]])
    if not leaves:
        raise AssertionError("no weights were placed on the mesh")
    for leaf in leaves:
        if set(leaf.sharding.device_set) != everywhere:
            raise AssertionError(f"weight {leaf.shape} on "
                                 f"{leaf.sharding.device_set}")
    for bucket in engine.buckets:
        (batch,), _ = engine.executable(bucket).input_shardings
        if set(batch.device_set) != everywhere:
            raise AssertionError(f"bucket {bucket} batch on "
                                 f"{batch.device_set}")
    print(f"  placement: {len(leaves)} weight arrays and every bucket's "
          f"batch span {len(everywhere)} devices", flush=True)


def sharded(spec: str, seed: int) -> None:
    import jax
    from repro.configs.registry import get_arch
    from repro.launch.serve import build_vision_server
    from repro.launch.train import build_mesh
    from repro.ops import ExecPolicy, use_policy

    mesh = build_mesh(spec)
    model = get_arch("highres_cnn").model()
    params = model.init(jax.random.PRNGKey(seed))
    images = seeded_images(model, seed)
    for quant in ("none", "int8"):
        # the same waves through an unsharded one-chip server: int8
        # activation scales are per batch, so both sides must see the
        # same batches (a zero pad lane leaves the scale unchanged)
        with use_policy(ExecPolicy(backend="pallas", quant=quant)):
            engine, frontend, _ = build_vision_server(
                model, params, capacity=BATCH, mesh=mesh)
            _, single, _ = build_vision_server(model, params,
                                               capacity=BATCH)
        plan = engine.plan
        print(f"highres_cnn quant={quant} mesh {dict(mesh.shape)}: "
              f"{plan.num_sharded()} sharded stages, buckets "
              f"{list(engine.buckets)}", flush=True)
        for node in plan.graph:
            if getattr(node, "sharding", None) is not None:
                print(f"  %{node.id} {node.op} {node.sharding}")
        if plan.num_sharded() == 0:
            raise AssertionError("mesh plan shards no stage")
        check_placement(engine, mesh)
        check_kernels(engine, quant)
        compare(f"  sharded {quant} vs one-chip plan",
                serve(frontend, images), serve(single, images),
                TOL_SHARDED[quant])


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--mesh", default=None, metavar="DxM",
                    help="run only the sharded path on a data x model "
                         "mesh, e.g. 2x2 (four chips)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--arch", choices=ARCHS, action="append",
                    help="serve only this model on one chip (repeatable; "
                         "default: every model)")
    args = ap.parse_args()

    dev = require_tpu()
    import jax
    from repro.launch.serve import enable_compile_cache
    print(f"jax {jax.__version__}, device {dev.device_kind} x "
          f"{len(jax.devices())}", flush=True)
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    if args.mesh:
        sharded(args.mesh, args.seed)
    else:
        for arch in args.arch or ARCHS:
            one_chip(arch, args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
