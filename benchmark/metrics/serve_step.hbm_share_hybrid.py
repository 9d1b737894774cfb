"""Reader of the per-layer metric `serve_step.hbm_share_hybrid`: the bytes a step must move (flops_hybrid.py: stated weights read, recurrent state read and written, live K and V) over the median device time of a step at the HBM's peak rate (%)."""

import os

import flops_hybrid
import harness
import layer_math


def read(x):
    raw, cfg = x["raw"], x["config"]
    step_ms = layer_math.step_device_ms_p50(x)
    state = raw.get("gauges", {}).get("recurrent_state_bytes")
    if not step_ms or not state or not raw.get("ragged_steps"):
        return None
    reference = harness.load_module(os.path.join(
        harness.HERE, "models", cfg["model"] + "_reference.py"))
    due = flops_hybrid.step_bytes(cfg, reference.spec(cfg), state,
                                  raw["context_sum"] / raw["ragged_steps"])
    return layer_math.pct(due, step_ms * 1e-3 * x["peaks"]["hbm_bytes_per_s"])
