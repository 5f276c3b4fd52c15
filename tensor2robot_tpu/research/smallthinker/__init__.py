from tensor2robot_tpu.research.smallthinker.smallthinker_model import (
    SmallThinkerModel,
    SmallThinkerNet,
)
