"""Reader of the per-layer metric `serve_step.device_ms_p50`: device time of one run of the ragged step program, median (ms)."""

import layer_math


def read(x):
    return layer_math.step_device_ms_p50(x)
