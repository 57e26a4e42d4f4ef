"""The program's spans (``repro.spans``) and stable names, on the CPU.

A profiler session records the spans on the host plane: each serving
step is ``frontend.step`` > ``vision.step`` > place, launch, fetch and
deliver, in that order, and ``launch/serve.py --profile-dir`` records
the boot phases (``boot.*``) beside them. Each bucket's executable is
the module ``jit_vision_b<bucket>``, and its ops carry the plan's stage
scopes ``s<i>.<op>``.
"""
import glob
import os
import re
import sys
import warnings

import jax
import numpy as np
import pytest

from repro.models.cnn import PaperCNN, PaperCNNConfig
from repro.serve import (Frontend, FrontendConfig, VisionAdapter,
                         VisionEngine, VisionEngineConfig)

CHILDREN = ("vision.place", "vision.launch", "vision.fetch",
            "vision.deliver")


def _host_spans(log_dir):
    """The ``<layer>.<part>`` events of the host planes of the newest
    trace under ``log_dir``: [(name, start, end, stats)] by start."""
    from jax.profiler import ProfileData
    path = max(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                         recursive=True), key=os.path.getmtime)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if not re.fullmatch(r"[a-z]+\.[a-z_]+", ev.name):
                    continue
                with warnings.catch_warnings():  # jaxlib's stats type
                    warnings.simplefilter("ignore")  # warns when iterated
                    stats = dict(ev.stats)
                out.append((ev.name, ev.start_ns,
                            ev.start_ns + ev.duration_ns, stats))
    return sorted(out, key=lambda sp: (sp[1], -sp[2]))


def _inside(inner, outer) -> bool:
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def _stack(batch=4):
    model = PaperCNN(PaperCNNConfig())
    engine = VisionEngine(model, model.init(jax.random.PRNGKey(0)),
                          VisionEngineConfig(batch=batch, buckets="auto"))
    return model, engine, Frontend(VisionAdapter(engine),
                                   FrontendConfig(max_queue=64))


def test_serving_steps_nest_their_spans(tmp_path):
    model, engine, fe = _stack()
    rng = np.random.RandomState(0)
    jax.profiler.start_trace(str(tmp_path))
    try:
        for _ in range(6):                  # a bucket of 4, then of 2
            fe.submit(rng.randn(*model.input_shape()[1:])
                      .astype(np.float32))
        fe.run_until_drained()
    finally:
        jax.profiler.stop_trace()
    spans = _host_spans(str(tmp_path))
    by = {name: [sp for sp in spans if sp[0] == name]
          for name in ("frontend.step", "vision.step", *CHILDREN)}
    assert len(by["vision.step"]) == engine.stats.steps == 2
    assert [(sp[3]["bucket"], sp[3]["lanes"]) for sp in by["vision.step"]] \
        == [(4, 4), (2, 2)]
    for step in by["vision.step"]:
        assert sum(_inside(step, f) for f in by["frontend.step"]) == 1
        kids = [next(sp for sp in by[name] if _inside(sp, step))
                for name in CHILDREN]
        for a, b in zip(kids, kids[1:]):    # in order, one after another
            assert a[2] <= b[1]
    assert all(sum(_inside(sp, step) for step in by["vision.step"]) == 1
               for name in CHILDREN for sp in by[name])


def test_overlapped_fetches_nest_and_carry_their_mark(tmp_path):
    """16 queued at batch 8: the first step launches both buckets and
    fetches the first after the second's launch; the second step launches
    nothing and fetches the second, marked ``overlapped=1``. Each
    launched bucket is one ``vision.step``, and every fetch lies under a
    ``vision.step`` or a ``frontend.step``."""
    model, engine, fe = _stack(batch=8)
    rng = np.random.RandomState(0)
    jax.profiler.start_trace(str(tmp_path))
    try:
        for _ in range(16):
            fe.submit(rng.randn(*model.input_shape()[1:])
                      .astype(np.float32))
        fe.run_until_drained()
    finally:
        jax.profiler.stop_trace()
    spans = _host_spans(str(tmp_path))
    by = {name: [sp for sp in spans if sp[0] == name]
          for name in ("frontend.step", "vision.step", *CHILDREN)}
    assert len(by["vision.step"]) == engine.stats.steps == 2
    assert len(fe.results) == 16
    for fetch in by["vision.fetch"]:
        assert any(_inside(fetch, sp)
                   for sp in by["vision.step"] + by["frontend.step"])
    first, late = by["vision.fetch"]
    assert "overlapped" not in first[3]
    assert late[3]["overlapped"] == 1 == engine.stats.overlapped
    second = by["vision.step"][1]
    launch = next(sp for sp in by["vision.launch"] if _inside(sp, second))
    assert _inside(first, second) and launch[2] <= first[1]
    assert not any(_inside(late, sp) for sp in by["vision.step"])


def test_bucket_executables_are_named_and_scoped():
    """Each bucket's program is the module ``jit_vision_b<bucket>``, and
    the compiled text carries the scope ``s<i>.<op>`` of each plan stage
    that computes (``plan.stages()[i]``)."""
    _, engine, _ = _stack()
    stages = engine.plan.stages()
    for b in engine.buckets:
        text = engine.executable(b).as_text()
        assert text.startswith(f"HloModule jit_vision_b{b},")
        for i, stage in enumerate(stages):
            op = re.match(r"%\d+ = (\w+)\(", stage).group(1)
            if op in ("fused_conv_block", "dense"):
                assert f"/s{i}.{op}/" in text


def test_profile_dir_records_boot_and_serving(tmp_path, monkeypatch,
                                              capsys):
    from repro.launch import serve
    # the test process keeps its own compile cache settings
    monkeypatch.setattr(serve, "enable_compile_cache", lambda: "off")
    monkeypatch.setattr(sys, "argv", [
        "serve", "--arch", "mnist_cnn", "--requests", "5",
        "--profile-dir", str(tmp_path)])
    serve.main()
    assert f"profile: {tmp_path}" in capsys.readouterr().out
    names = {sp[0] for sp in _host_spans(str(tmp_path))}
    assert {"boot.trace", "boot.fuse", "boot.compile",
            "boot.first_dispatch", "frontend.step", "vision.step",
            *CHILDREN} <= names


@pytest.mark.parametrize("name", ["compile", "first_dispatch", "fold"])
def test_a_warmup_phase_is_a_boot_span(tmp_path, name):
    from repro.artifact.warmup import collect_warmup, phase
    jax.profiler.start_trace(str(tmp_path))
    try:
        with collect_warmup() as report:
            with phase(name):
                pass
    finally:
        jax.profiler.stop_trace()
    assert report.phase_calls(name) == 1
    assert [sp[0] for sp in _host_spans(str(tmp_path))] == [f"boot.{name}"]
