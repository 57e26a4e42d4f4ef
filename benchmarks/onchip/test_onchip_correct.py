"""The check that decides ``correct``, on the CPU at sizes a test run
holds (Pallas kernels in interpret mode).

* The program agrees with the plain reference within each
  configuration's limit, and the control (the reference in three
  bfloat16 passes, the precision below float32 at highest) fails it.
* A whole run of a cell, with only the harness's look for a chip
  skipped, reads ``correct`` true; with the timed path broken
  underneath it reads false: an answer altered where it is produced,
  answers swapped between the lanes of a batch, an answer that never
  comes.
* Without a TPU, on a device missing from the peak table, or without
  the program beside the benchmark, ``run.py`` exits nonzero and prints
  no result.
"""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE / "tools"))

import control  # noqa: E402
import harness  # noqa: E402

PEAKS = json.loads((HERE / "peaks.json").read_text())["devices"]
FAM = harness.load_module(HERE / "families" / "conv_chain.py")


def program_logits(cfg, weights, images):
    """The program's compiled plan (Pallas backend) at ``cfg``'s sizes."""
    import jax
    from repro.ops import ExecPolicy, use_policy
    model = FAM.build_program(cfg)
    with use_policy(ExecPolicy(backend="pallas", quant=cfg["quant"])):
        bound = model.compile(batch=len(images)).bind(
            FAM.program_params(cfg, weights))
        return np.asarray(jax.jit(lambda x: bound(x))(images))


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_program_passes_and_control_fails_the_check(seed):
    """Both through ``harness.check``, as a run's answers go."""
    cfg = harness.load_config("mnist_cnn")
    weights, images = FAM.materialize(cfg, harness.seed_words(seed),
                                      cfg["batch"])
    pool = np.asarray(images)
    every = np.arange(len(pool))
    got = harness.check(FAM, cfg, weights, pool,
                        program_logits(cfg, weights, pool), every, 0)
    assert harness.is_correct(got), got
    ctl = control.control_checks(FAM, cfg, seed, cfg["batch"])
    assert not harness.is_correct(ctl), ctl
    assert ctl["logit_err"]["value"] > got["logit_err"]["value"]


# ------------------------------------------------- a whole run, broken

def _new_results(step):
    def wrapped(self):
        before = set(self.results)
        n = step(self)
        return n, [u for u in self.results if u not in before]
    return wrapped


def altered(step):
    def f(self):
        n, new = _new_results(step)(self)
        logits = np.array(self.results[new[0]]["logits"])
        logits[0] += 1e-3 * np.abs(logits).max()
        self.results[new[0]]["logits"] = logits
        return n
    return f


def swapped(step):
    def f(self):
        n, new = _new_results(step)(self)
        if len(new) > 1:
            first = self.results[new[0]]
            for a, b in zip(new, new[1:]):
                self.results[a] = self.results[b]
            self.results[new[-1]] = first
        return n
    return f


def dropped(step):
    def f(self):
        n, new = _new_results(step)(self)
        del self.results[new[0]]
        return n
    return f


@pytest.fixture
def cpu_run(monkeypatch):
    """A whole run of a cell on this host's CPU: everything of
    ``run.py`` but its look for a chip."""
    import jax
    monkeypatch.setattr(harness, "enable_compile_cache", lambda: "off")

    def go(workload, seed=2**31 + 5, seconds=0.3):
        cell = harness.resolve(workload)
        return harness.run(cell, seed=seed, seconds=seconds, trace=False,
                           marks={"start": harness.clock(),
                                  "init": harness.clock()},
                           peak=PEAKS["TPU v5 lite"],
                           device=jax.devices()[0])
    return go


@pytest.mark.parametrize("fault", [None, altered, swapped, dropped])
def test_a_broken_timed_path_reads_not_correct(cpu_run, monkeypatch,
                                               fault):
    from repro.serve.vision import VisionEngine
    if fault is not None:
        monkeypatch.setattr(VisionEngine, "step", fault(VisionEngine.step))
    out = cpu_run("mnist_cnn.offline")
    assert out["attempted"] > 16
    assert list(out)[-1] == "checks"
    assert set(out["metrics"]) == {"images_per_s", "setup_s"}
    assert out["correct"] is (fault is None), out["checks"]


def test_an_open_loop_run_reads_correct(cpu_run):
    out = cpu_run("mnist_cnn.burst", seconds=0.5)
    assert out["correct"] and out["failed"] == 0, out
    assert set(out["metrics"]) == {"latency_p50_ms", "latency_p95_ms",
                                   "setup_s"}


def _run_py(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "benchmarks/onchip/run.py", "--workload",
         "mnist_cnn.offline", "--seed", "1", "--seconds", "1"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_refuses_without_a_tpu():
    res = _run_py(ROOT)
    assert res.returncode != 0 and res.stdout.strip() == ""
    assert "needs a TPU" in res.stderr


def test_run_refuses_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(HERE, tmp_path / "benchmarks" / "onchip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    res = _run_py(tmp_path)
    assert res.returncode != 0 and res.stdout.strip() == ""
    assert "No module named 'repro'" in res.stderr


def test_run_refuses_a_device_missing_from_the_peak_table(monkeypatch,
                                                          capsys):
    import jax

    class Chip:
        platform = "tpu"
        device_kind = "TPU v99"

    run_py = harness.load_module(HERE / "run.py")
    monkeypatch.setattr(jax, "devices", lambda *a: [Chip()])
    with pytest.raises(SystemExit, match="peaks.json"):
        run_py.main(["--workload", "mnist_cnn.offline", "--seed", "1",
                     "--seconds", "1"])
    assert capsys.readouterr().out == ""
