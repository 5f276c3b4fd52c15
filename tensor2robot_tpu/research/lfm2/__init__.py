"""LFM2-style hybrid backbone: gated short convolutions beside attention,
a sigmoid router with a balancing bias, leading dense layers."""

from tensor2robot_tpu.research.lfm2.lfm2_model import (  # noqa: F401
    LFM2Model,
    LFM2Net,
)
