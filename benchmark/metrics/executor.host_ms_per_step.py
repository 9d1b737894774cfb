"""Reader of the per-layer metric `executor.host_ms_per_step`: window time minus device busy time, over the window's steps (ms)."""

import layer_math


def read(x):
    return layer_math.host_ms_per_step(x, "steps")
