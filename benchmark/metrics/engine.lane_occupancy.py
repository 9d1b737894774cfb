"""Reader of the per-layer metric `engine.lane_occupancy`: decode_active_lane_steps_total over decode_capacity_lane_steps_total, over the window (%)."""

import layer_math


def read(x):
    return layer_math.pct(x["raw"].get("active_lane_steps"), x["raw"].get("capacity_lane_steps"))
