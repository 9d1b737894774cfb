"""Reader of the per-layer metric `executor.host_ms_per_step_p95`: per step, host wall time minus that step's device time, 95th percentile (ms)."""

import layer_math


def read(x):
    return layer_math.host_ms_per_step_p95(x)
