"""Summarize a jax-profiler trace (bench.py's PT_BENCH_TRACE_DIR) into
a dispatch-vs-compute breakdown.

Usage:
    python tools/trace_summary.py <trace dir> [out.json]

Walks every `*.trace.json.gz` (perfetto/chrome-trace export) under the
directory and reports, per trace: wall span, busy time and top ops per
device lane, and the busy fraction — the direct answer to "is the gap
dispatch overhead or slow kernels". Keeps only aggregates, so the
committed artifact is a few KB while raw traces can be gigabytes.

Reference precedent for per-op timing discipline:
/root/reference/paddle/fluid/operators/benchmark/op_tester.cc:1 (its
op-level profile tables); here the compiled-program timeline replaces
per-op timers.
"""

import gzip
import json
import os
import sys
from collections import defaultdict


def summarize_trace(path, top=25):
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rt") as f:
        data = json.load(f)
    events = data.get("traceEvents", [])
    # pid -> process name (device lanes look like "/device:TPU:0" or
    # "TPU:0 (pid n)"; host threads are python/runtime lanes)
    pid_names = {}
    for e in events:
        if e.get("ph") == "M" and e.get("name") == "process_name":
            pid_names[e["pid"]] = e.get("args", {}).get("name", "")
    lanes = defaultdict(lambda: {"busy_us": 0.0, "ops": defaultdict(float),
                                 "t0": None, "t1": None})
    for e in events:
        if e.get("ph") != "X" or "dur" not in e:
            continue
        lane = pid_names.get(e.get("pid"), str(e.get("pid")))
        L = lanes[lane]
        ts, dur = float(e.get("ts", 0)), float(e["dur"])
        L["busy_us"] += dur
        L["ops"][e.get("name", "?")] += dur
        L["t0"] = ts if L["t0"] is None else min(L["t0"], ts)
        L["t1"] = (ts + dur if L["t1"] is None
                   else max(L["t1"], ts + dur))
    out = {}
    for lane, L in lanes.items():
        span = (L["t1"] - L["t0"]) if L["t0"] is not None else 0.0
        ops = sorted(L["ops"].items(), key=lambda kv: -kv[1])[:top]
        out[lane] = {
            "span_ms": round(span / 1e3, 3),
            "busy_ms": round(L["busy_us"] / 1e3, 3),
            "busy_frac": round(L["busy_us"] / span, 4) if span else None,
            "top_ops_ms": {k: round(v / 1e3, 3) for k, v in ops},
        }
    return out


def main(root, out_path=None):
    traces = []
    for dirpath, _, files in os.walk(root):
        for fn in files:
            if fn.endswith(".trace.json.gz") or fn.endswith(".trace.json"):
                traces.append(os.path.join(dirpath, fn))
    if not traces:
        print(f"no traces under {root}", file=sys.stderr)
        return 1
    report = {}
    for t in sorted(traces):
        rel = os.path.relpath(t, root)
        try:
            report[rel] = summarize_trace(t)
        except Exception as e:  # noqa: BLE001 — summarize what we can
            report[rel] = {"error": f"{type(e).__name__}: {e}"}
    text = json.dumps(report, indent=1)
    if out_path:
        with open(out_path, "w") as f:
            f.write(text)
    print(text[:4000])
    return 0


if __name__ == "__main__":
    if len(sys.argv) < 2:
        sys.exit("usage: trace_summary.py <trace dir> [out.json]")
    sys.exit(main(sys.argv[1], sys.argv[2] if len(sys.argv) > 2 else None))
