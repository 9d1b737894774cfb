"""Reader of the per-layer metric `serve_step.mfu`: forward FLOPs of every token processed, prefill and decode (flops.py), a second over the bf16 peak (%)."""

import layer_math


def read(x):
    return layer_math.serve_mfu(x)
