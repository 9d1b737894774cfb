"""Reader of the per-layer metric `serve_step.hbm_share_pangu`: the bytes a step must move (flops_openpangu.py: stated weights touched, the latent pages the attention kernel walks) over the median device time of a step at the HBM's peak rate (%)."""

import os

import flops_openpangu
import harness
import layer_math


def read(x):
    raw, cfg = x["raw"], x["config"]
    step_ms = layer_math.step_device_ms_p50(x)
    counters = raw.get("counters", {})
    if (not step_ms or not raw.get("ragged_steps")
            or "attn_latent_pages_total" not in counters):
        return None
    reference = harness.load_module(os.path.join(
        harness.HERE, "models", cfg["model"] + "_reference.py"))
    due = flops_openpangu.step_bytes(cfg, reference.spec(cfg), counters,
                                     raw["ragged_steps"],
                                     raw["tokens_processed"])
    return layer_math.pct(due, step_ms * 1e-3 * x["peaks"]["hbm_bytes_per_s"])
