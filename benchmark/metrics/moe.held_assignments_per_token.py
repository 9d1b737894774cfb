"""Reader of the per-layer metric `moe.held_assignments_per_token`: (token, expert) pairs that landed on held experts over tokens routed (valid tokens x expert layers), over the window; top_k x held / router width if routing is even."""


def read(x):
    c = x["raw"].get("counters", {})
    routed = c.get("moe_tokens_routed_total")
    return c["moe_held_assignments_total"] / routed if routed else None
