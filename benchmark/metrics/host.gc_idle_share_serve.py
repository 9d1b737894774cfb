"""Reader of the per-layer metric `host.gc_idle_share_serve`: the device's idle seconds that `idle_gaps` gives to the span `executor/gc` (a collection of generation 1 or 2 by Python's cyclic collector), over the traced window (%)."""

SPAN = "executor/gc"


def read(x):
    t = x["trace"]
    idle = sum(s for name, s in t["idle_gaps"] if name == SPAN)
    return 100.0 * idle / t["window_s"]
