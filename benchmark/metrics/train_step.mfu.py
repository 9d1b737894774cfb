"""Reader of the per-layer metric `train_step.mfu`: FLOPs the step needs a token (flops.py) x tokens/s over chips x the bf16 peak (%)."""

import layer_math


def read(x):
    return layer_math.train_mfu(x)
