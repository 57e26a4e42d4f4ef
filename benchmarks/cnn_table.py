"""Tab. I reproduction: the paper CNN's structure, parameters, FLOPs.

Parameterized over ``img_size`` (the same table at high resolution shows
each layer's activation footprint); the paper's Tab. I numbers are
asserted only at the default 28×28.
"""
from __future__ import annotations

import argparse

from benchmarks.common import emit
from repro.models.cnn import PaperCNNConfig


def table(cfg: PaperCNNConfig) -> list[tuple[str, int, int]]:
    """(layer, params, flops) rows, computed from the config — the same
    analytic counts ``flops_per_image`` totals."""
    o1 = cfg.img_size - cfg.conv1_k + 1
    s1 = o1 // 2
    o2 = s1 - cfg.conv2_k + 1
    _, _, fc_in = cfg.feature_sizes()
    k1, k2 = cfg.conv1_k, cfg.conv2_k
    return [
        (f"conv1 {k1}x{k1}x{cfg.conv1_c} s1",
         cfg.in_channels * k1 * k1 * cfg.conv1_c + cfg.conv1_c,
         2 * cfg.conv1_c * cfg.in_channels * k1 * k1 * o1 * o1),
        ("pool1 2x2 s2", 0, 0),
        (f"conv2 {k2}x{k2}x{cfg.conv2_c} s1",
         cfg.conv1_c * k2 * k2 * cfg.conv2_c + cfg.conv2_c,
         2 * cfg.conv2_c * cfg.conv1_c * k2 * k2 * o2 * o2),
        ("pool2 2x2 s2", 0, 0),
        (f"fc {fc_in}->{cfg.n_classes}",
         fc_in * cfg.n_classes + cfg.n_classes,
         2 * fc_in * cfg.n_classes),
    ]


def run(img_size: int = 28) -> None:
    cfg = PaperCNNConfig(img_size=img_size)
    rows = table(cfg)
    total_p = sum(p for _, p, _ in rows)
    total_f = sum(f for _, _, f in rows)
    if img_size == 28:
        # paper Tab. I: 150 / 10,820 / 3,210
        assert rows[0][1] == 150 and rows[2][1] == 10820 \
            and rows[4][1] == 3210
    for name, p, f in rows:
        emit(f"tab1/{name}", 0.0, f"params={p};flops={f}")
    emit("tab1/total", 0.0,
         f"params={total_p};flops_per_image={total_f};"
         f"matches_paper_tab1={img_size == 28}")
    assert total_p == cfg.param_count()
    assert total_f == cfg.flops_per_image()


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--img-size", type=int, default=28,
                    help="input resolution (paper Tab. I asserts at 28)")
    args = ap.parse_args()
    print("name,us_per_call,derived")
    run(img_size=args.img_size)
