"""Reader of the per-layer metric `device.peak_hbm_share_train`: peak bytes of the fullest chip over its HBM (%)."""

import layer_math


def read(x):
    return layer_math.peak_hbm_share(x)
