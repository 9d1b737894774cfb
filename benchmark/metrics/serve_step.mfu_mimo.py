"""Reader of the per-layer metric `serve_step.mfu_mimo`: the FLOPs the window's work is due (flops_mimo.py: layers on tokens processed, held experts on the pairs that landed on them, the head on tokens emitted, full attention at cached length, window attention at the window), a second over the bf16 peak (%)."""

import flops_mimo
import layer_math


def read(x):
    raw = x["raw"]
    pairs = raw.get("counters", {}).get("moe_held_assignments_total")
    if not raw.get("tokens_processed") or pairs is None:
        return None
    total = flops_mimo.forward_flops(
        x["config"], raw["tokens_processed"], raw["tokens_emitted"],
        raw["context_sum"], pairs)
    return layer_math.pct(total / raw["window_s"],
                          x["chips"] * x["peaks"]["bf16_flops_per_s"])
