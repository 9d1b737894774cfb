"""Reader of the per-layer metric `engine.host_ms_per_step_serve`: window time minus device busy time, over the engine's ragged steps (ms)."""

import layer_math


def read(x):
    return layer_math.host_ms_per_step(x, "ragged_steps")
