"""GLM-4.7-Flash-style backbone: latent attention at 256/256 with plain
rotary positions, a shared expert beside a scaled sigmoid router, and one
multi-token-prediction module."""

from tensor2robot_tpu.research.glm.glm_model import (  # noqa: F401
    GlmModel,
    GlmNet,
)
