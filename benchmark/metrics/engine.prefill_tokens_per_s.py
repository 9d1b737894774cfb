"""Reader of the per-layer metric `engine.prefill_tokens_per_s`: prefill_tokens_total over the window (tokens/s)."""


def read(x):
    return (x["raw"]["prefill_tokens"] / x["raw"]["window_s"] if x["raw"].get("prefill_tokens") else None)
