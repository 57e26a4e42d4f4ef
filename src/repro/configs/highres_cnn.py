"""Multi-block VGG-style CNN at 224×224: a smoke for the window kernels
at a large frame, not a published configuration and not a benchmark cell.
Its stages (8,3,5,5) at 224² and (16,8,3,3) at 110² each run as one
``fused_cwp`` call whose row-block grid sizes every step to the VMEM
budget (DESIGN.md §13). Served by ``chip_smoke.py`` and ``launch/serve.py
--arch highres_cnn``.
"""
from repro.configs.base import ArchSpec
from repro.models.vgg import VGGStyleCNN, VGGStyleCNNConfig

CONFIG = VGGStyleCNNConfig()

ARCH = ArchSpec(
    arch_id="highres_cnn", family="cnn",
    build=lambda: VGGStyleCNN(CONFIG),
    source="VGG-style stack (survey arXiv:1806.01683 §streaming dataflow)",
    notes="224x224x3; conv5x5x8 + 3 conv3x3 blocks (each fused conv+relu+"
          "pool) -> fc10; each block one fused_cwp call, its rows tiled "
          "by the kernel's row-block grid; not a published config.",
)
