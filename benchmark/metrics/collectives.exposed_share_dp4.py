"""Reader of the per-layer metric `collectives.exposed_share_dp4`: device time in collectives that no other op of the same chip overlaps, over the traced window (%)."""

import layer_math


def read(x):
    t = x["trace"]
    if t.get("exposed_collective_s") is None:
        return None
    return layer_math.pct(t["exposed_collective_s"], t["window_s"])
