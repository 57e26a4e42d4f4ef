"""ResNet-50 v1.5 at 224x224x3, float32: the published conv backbone
served through the vision engine (DESIGN.md §8).

Not part of the assigned LM shape-grid pool; served by ``launch/serve.py
--arch resnet50``, ``chip_smoke.py`` and the on-chip benchmark's
``resnet50.offline`` cell.
"""
from repro.configs.base import ArchSpec
from repro.models.resnet import ResNet, ResNetConfig

CONFIG = ResNetConfig()

ARCH = ArchSpec(
    arch_id="resnet50", family="cnn",
    build=lambda: ResNet(CONFIG),
    source="arXiv:1512.03385 Table 1 (50-layer); v1.5 stride placement as "
           "torchvision resnet50 / MLPerf Inference",
    notes="224x224x3; 7x7/2 stem, 3x3/2 pool, bottlenecks [3,4,6,3] of "
          "widths 64-512 x4, global average pool, fc 2048->1000; batch "
          "norm folded into the convs at bind; 25.6M parameters.",
)
