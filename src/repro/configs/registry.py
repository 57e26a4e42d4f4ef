"""Arch registry: ``--arch <id>`` resolution for launch/dryrun/train/serve."""
from __future__ import annotations

import importlib

from repro.configs.base import SHAPES, ArchSpec

_MODULES = {
    "dbrx-132b": "repro.configs.dbrx_132b",
    "llama4-scout-17b-a16e": "repro.configs.llama4_scout_17b_a16e",
    "qwen1.5-0.5b": "repro.configs.qwen15_05b",
    "command-r-35b": "repro.configs.command_r_35b",
    "qwen3-14b": "repro.configs.qwen3_14b",
    "gemma2-2b": "repro.configs.gemma2_2b",
    "internvl2-26b": "repro.configs.internvl2_26b",
    "seamless-m4t-medium": "repro.configs.seamless_m4t_medium",
    "zamba2-7b": "repro.configs.zamba2_7b",
    "rwkv6-1.6b": "repro.configs.rwkv6_16b",
    "mnist_cnn": "repro.configs.mnist_cnn",
    "highres_cnn": "repro.configs.highres_cnn",
    "resnet50": "repro.configs.resnet50",
}

# the vision workloads (family "cnn") are servable via --arch but outside
# the assigned LM shape-grid pool
CNN_IDS = ("mnist_cnn", "highres_cnn", "resnet50")
ARCH_IDS = [a for a in _MODULES if a not in CNN_IDS]
SHAPE_IDS = list(SHAPES)


def get_arch(arch_id: str) -> ArchSpec:
    if arch_id not in _MODULES:
        raise KeyError(f"unknown arch {arch_id!r}; known: {sorted(_MODULES)}")
    return importlib.import_module(_MODULES[arch_id]).ARCH
