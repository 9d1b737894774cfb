"""Reader of the per-layer metric `moe.expert_load_max_over_mean`: the busiest held expert's assignments over the mean expert's, over every (layer, expert) since the warm-up, as read at the close of the window; 1 if routing is even."""


def read(x):
    g = x["raw"].get("gauges", {})
    mean = g.get("moe_expert_load_mean")
    return g["moe_expert_load_max"] / mean if mean else None
