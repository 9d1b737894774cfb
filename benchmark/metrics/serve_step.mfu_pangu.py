"""Reader of the per-layer metric `serve_step.mfu_pangu`: the FLOPs the window's work is due (flops_openpangu.py: layers on tokens processed, held experts on the pairs that landed on them, the head on tokens emitted, absorbed latent attention on every key every valid query token attended), a second over the bf16 peak (%)."""

import flops_openpangu
import layer_math


def read(x):
    raw = x["raw"]
    counters = raw.get("counters", {})
    pairs = counters.get("moe_held_assignments_total")
    if (not raw.get("tokens_processed") or pairs is None
            or "attn_latent_key_rows_total" not in counters):
        return None
    total = flops_openpangu.forward_flops(
        x["config"], raw["tokens_processed"], raw["tokens_emitted"], pairs,
        counters)
    return layer_math.pct(total / raw["window_s"],
                          x["chips"] * x["peaks"]["bf16_flops_per_s"])
