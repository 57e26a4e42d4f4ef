"""The benchmark's files resolve, by name alone, and its yardsticks hold.

Runs on the CPU: no chip, no compile of the served path.
"""
import json
import shutil
import sys
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(ROOT))

import arrivals  # noqa: E402
import harness  # noqa: E402

sys.path.insert(0, str(HERE / "tools"))
import knee_sweep  # noqa: E402

MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in MANIFEST["workloads"]]


@pytest.mark.parametrize("name", CELLS)
def test_every_cell_resolves_to_its_files(name):
    cell = harness.resolve(name)
    assert cell.config["name"] == cell.spec["config"]
    assert cell.traffic["loop"] in ("closed", "open")
    assert cell.traffic["buckets"] in ("full", "auto")
    for trace in (0, 1):
        assert cell.metrics[trace], f"{name} reports no metric at {trace}"
        for spec, mod in cell.metrics[trace]:
            assert callable(mod.read), spec["name"]
    e2e = {m["name"] for m, _ in cell.metrics[0]}
    assert "setup_s" in e2e and len(e2e) >= 2
    for m, _ in cell.metrics[1]:
        assert m["moves"] in e2e, (name, m["name"])
    assert cell.spec["chips"] == 1


def test_manifest_names_only_what_exists():
    for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"]:
        assert set(m.get("workloads", CELLS)) <= set(CELLS), m["name"]
        assert (HERE / "metrics" / f"{m['name']}.py").is_file()
    for c in MANIFEST["configs"]:
        cfg = harness.load_config(c["name"])
        assert (HERE / "families" / f"{cfg['family']}.py").is_file()
        assert cfg["reduced"] == c["reduced"]
        assert any(w["config"] == c["name"] for w in MANIFEST["workloads"])


def test_a_new_cell_needs_only_new_files(tmp_path):
    """A cell, a traffic mix and a per-layer metric added as files (and
    entries in the manifest) resolve with no harness file edited."""
    shutil.copytree(HERE, tmp_path / "benchmarks" / "onchip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    here = tmp_path / "benchmarks" / "onchip"
    (here / "traffic" / "trickle.json").write_text(json.dumps(
        {"loop": "open", "buckets": "auto", "pool": 8,
         "states": [{"rate_x_knee": 0.1, "mean_dwell_s": None}]}))
    (here / "metrics" / "answered.py").write_text(
        "def read(run):\n    return float(len(run.requests))\n")
    manifest = json.loads(json.dumps(MANIFEST))
    manifest["workloads"].append(
        {"name": "mnist_cnn.trickle", "config": "mnist_cnn",
         "traffic": "trickle", "chips": 1, "why": "a light open loop"})
    manifest["per_layer"].append(
        {"name": "answered", "unit": "requests", "better": "higher",
         "source": "host_clock", "layer": "front-end intake",
         "moves": "latency_p95_ms", "workloads": ["mnist_cnn.trickle"]})
    manifest["end_to_end"][2]["workloads"].append("mnist_cnn.trickle")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(manifest))
    cell = harness.resolve("mnist_cnn.trickle", root=tmp_path)
    assert cell.traffic["states"][0]["rate_x_knee"] == 0.1
    assert [m["name"] for m, _ in cell.metrics[1]] == ["answered"]
    assert {m["name"] for m, _ in cell.metrics[0]} == {
        "latency_p95_ms", "setup_s"}


def test_stage_ops_sum_to_the_models_own_counts():
    from benchmarks.cnn_table import table
    from repro.models.cnn import PaperCNNConfig
    from repro.models.vgg import VGGStyleCNNConfig
    fam = harness.load_module(HERE / "families" / "conv_chain.py")
    vgg = VGGStyleCNNConfig()
    hr = {"name": "vgg", "input": [vgg.in_channels, vgg.img_size,
                                   vgg.img_size],
          "blocks": [list(b) for b in vgg.blocks], "n_classes": vgg.n_classes}
    mn = harness.load_config("mnist_cnn")
    assert fam.flops_per_image(hr) == vgg.flops_per_image()
    assert [fam.stage_ops(st) for st in fam.stages(hr)] == [
        58_080_000, 26_873_856, 24_920_064, 10_616_832]
    paper = PaperCNNConfig()
    assert fam.flops_per_image(mn) == paper.flops_per_image()
    tab1 = table(paper)          # rows: conv1, pool1, conv2, pool2, fc
    assert [fam.stage_ops(st) for st in fam.stages(mn)] == [
        tab1[0][2], tab1[2][2]]
    assert 2 * fam.fc_in(mn) * mn["n_classes"] == tab1[4][2]


def test_stage_bytes_count_input_output_and_weights_once():
    fam = harness.load_module(HERE / "families" / "conv_chain.py")
    first = fam.stages(harness.load_config("mnist_cnn"))[0]   # 1x28x28 -> 15x13x13
    assert fam.stage_bytes(first, 8) == 4 * (8 * (28 * 28 + 15 * 13 * 13)
                                             + 15 * 9 + 15)


def test_program_refuses_a_file_that_is_not_what_it_runs():
    fam = harness.load_module(HERE / "families" / "conv_chain.py")
    cfg = dict(harness.load_config("mnist_cnn"), blocks=[[15, 3], [24, 6]])
    with pytest.raises(ValueError, match="parameters"):
        fam.build_program(cfg)
    fam.build_program(harness.load_config("mnist_cnn"))


@pytest.mark.parametrize("seed", [1, 2**31 + 77])
def test_open_schedule_offers_the_same_work_for_every_seed(seed):
    burst = [{"rate_x_knee": 0.3, "mean_dwell_s": 0.9},
             {"rate_x_knee": 1.5, "mean_dwell_s": 0.1}]
    base = arrivals.open_schedule(burst, 1000.0, 10.0,
                                  np.random.default_rng(0))
    due = arrivals.open_schedule(burst, 1000.0, 10.0,
                                 np.random.default_rng(seed))
    assert len(due) == len(base) == pytest.approx(4200, abs=2)
    assert not np.array_equal(due, base)
    assert (np.diff(due) >= 0).all() and due[0] >= 0 and due[-1] < 10.0
    poisson = [{"rate_x_knee": 0.8, "mean_dwell_s": None}]
    a = arrivals.open_schedule(poisson, 100.0, 10.0,
                               np.random.default_rng(seed))
    b = arrivals.open_schedule(poisson, 100.0, 10.0,
                               np.random.default_rng(seed + 1))
    assert len(a) == len(b) == 800 and not np.array_equal(a, b)
    for t in (a, b):           # the same gaps, in another order
        assert np.allclose(np.sort(np.diff(np.append(t, 10.0))),
                           arrivals.exp_quantiles(800, 10.0))


def test_large_seeds_give_other_weights_and_images():
    fam = harness.load_module(HERE / "families" / "conv_chain.py")
    cfg = harness.load_config("mnist_cnn")
    seen = set()
    for seed in (5, 5 + 2**32, 2**33 + 5, 5):
        weights, images = fam.materialize(cfg, harness.seed_words(seed), 2)
        seen.add((float(weights[0][0].sum()), float(images.sum())))
    assert len(seen) == 3        # the repeated seed gives the same again


def test_percentile_is_nearest_rank():
    assert harness.percentile([3.0, 1.0, 2.0, 4.0], 50) == 2.0
    assert harness.percentile(list(range(1, 101)), 95) == 95
    assert harness.percentile([1.0, float("inf")], 95) == float("inf")


def test_the_knee_judges_whole_windows_by_their_median():
    def win(p95_ms, kept_up=1.0):
        return {"p95_ms": p95_ms, "kept_up": kept_up}
    # one stalled window of three does not decide a rate
    assert knee_sweep.meets_limit([win(5.0), win(40.0), win(6.0)], 7.6)
    assert not knee_sweep.meets_limit([win(5.0), win(8.0), win(9.0)], 7.6)
    # nor does a short close; a backlog that grows all through does
    assert knee_sweep.meets_limit(
        [win(5.0, 0.97), win(5.0), win(5.0)], 7.6)
    assert not knee_sweep.meets_limit(
        [win(5.0, 0.95), win(5.0, 0.9), win(5.0)], 7.6)
    assert knee_sweep.knee_fraction(
        {0.3: True, 0.5: True, 0.6: False, 0.7: True}) == 0.5
    assert knee_sweep.knee_fraction({0.3: False, 0.5: True}) is None
