"""Reader of the per-layer metric `device.idle_share_serve`: 1 - union of device operation intervals over the window (%)."""

import layer_math


def read(x):
    return layer_math.idle_share(x)
