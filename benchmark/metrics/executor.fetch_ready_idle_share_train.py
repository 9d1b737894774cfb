"""Reader of the per-layer metric `executor.fetch_ready_idle_share_train`: the device's idle seconds that `idle_gaps` gives to the span `executor/fetch_ready` (the wait for a finished train step inside `executor/fetch`), over the traced window (%)."""

SPAN = "executor/fetch_ready"


def read(x):
    t = x["trace"]
    idle = sum(s for name, s in t["idle_gaps"] if name == SPAN)
    return 100.0 * idle / t["window_s"]
