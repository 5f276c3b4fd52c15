"""Xing4.0-style backbone: latent attention with values narrower than its
keys, four residual streams mixed by Sinkhorn maps, a shared expert beside a
scaled sigmoid router."""

from tensor2robot_tpu.research.xing.xing_model import (  # noqa: F401
    XingModel,
    XingNet,
)
