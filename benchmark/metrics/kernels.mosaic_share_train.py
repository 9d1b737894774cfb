"""Reader of the per-layer metric `kernels.mosaic_share_train`: device time in Mosaic (Pallas) custom calls over device busy time (%)."""

import layer_math


def read(x):
    return layer_math.mosaic_share(x)
