"""Arithmetic the per-layer readers share. A reader gets one dict:
trace (reduce.summarize's output), raw (what the kind's window
returned), peaks, config, traffic, chips, memory_peak_bytes."""

import statistics

import flops
import reduce


def pct(num, den):
    return None if not den or num is None else 100.0 * num / den


def host_ms_per_step(x, steps_key):
    steps = x["raw"].get(steps_key)
    t = x["trace"]
    return None if not steps else (t["window_s"] - t["busy_s"]) * 1e3 / steps


def step_device_ms_p50(x):
    v = x["trace"]["step_device_s"]
    return statistics.median(v) * 1e3 if v else None


def host_ms_per_step_p95(x):
    """Per step: host wall time of the step minus that step's device
    time, 95th percentile. None unless the trace holds exactly the
    window's steps."""
    ends = x["raw"].get("step_ends") or []
    dev = x["trace"]["step_device_s"]
    if not ends or len(dev) != len(ends):
        return None
    walls = [b - a for a, b in zip([0.0] + ends[:-1], ends)]
    return reduce.quantile([(w - d) * 1e3 for w, d in zip(walls, dev)], 0.95)


def mosaic_share(x):
    t = x["trace"]
    return pct(t["mosaic_s"], t["busy_s"]) if t["mosaic_s"] else None


def idle_share(x):
    t = x["trace"]
    return pct(t["window_s"] - t["busy_s"], t["window_s"])


def peak_hbm_share(x):
    return pct(x["memory_peak_bytes"], x["peaks"]["hbm_bytes"])


def train_mfu(x):
    raw = x["raw"]
    per_token = flops.bert_train_flops_per_token(x["config"],
                                                 x["traffic"]["seq_len"])
    rate = raw["tokens"] / raw["window_s"]
    return pct(per_token * rate, x["chips"] * x["peaks"]["bf16_flops_per_s"])


def serve_mfu(x):
    raw = x["raw"]
    if not raw.get("tokens_processed"):
        return None
    total = flops.gpt_forward_flops(x["config"], raw["tokens_processed"],
                                    raw["tokens_emitted"], raw["context_sum"])
    return pct(total / raw["window_s"],
               x["chips"] * x["peaks"]["bf16_flops_per_s"])
