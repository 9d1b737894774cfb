"""Reader of the per-layer metric `engine.fetch_ready_idle_share_serve`: the device's idle seconds that `idle_gaps` gives to the span `generation/fetch_ready` (the wait for a finished serving step inside `generation/fetch`), over the traced window (%)."""

SPAN = "generation/fetch_ready"


def read(x):
    t = x["trace"]
    idle = sum(s for name, s in t["idle_gaps"] if name == SPAN)
    return 100.0 * idle / t["window_s"]
