"""Reader of the per-layer metric `kv.window_resident_share`: pages resident in a window layer's pool over what the same sequences would hold there unwindowed (the pages they hold in a full layer), as read at the close of the window (%)."""


def read(x):
    g = x["raw"].get("gauges", {})
    full = g.get("kv_pages_resident_full")
    return 100.0 * g["kv_pages_resident_window"] / full if full else None
