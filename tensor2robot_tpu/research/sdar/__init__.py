from tensor2robot_tpu.research.sdar.sdar_model import (
    SDARModel,
    SDARNet,
    corrupt,
    sequence_key,
)
