"""From a profiler trace to numbers: device busy time as a union of
intervals, idle gaps attributed to the host span that covers them,
time in Mosaic (Pallas) kernels, exposed collective time, the device
time of each program run.

A trace is read into a neutral form first, so the arithmetic can be
checked on a hand-made fixture:
  {"devices": {plane: {"ops": [(name, start_ns, dur_ns)],
                       "modules": [(name, start_ns, dur_ns)],
                       "async": [(name, start_ns, dur_ns)]}},
   "host": [(name, start_ns, dur_ns)]}
"""

import gzip
import json
import re

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute", "collective-broadcast")
MOSAIC = "tpu_custom_call"
SHORT_PAUSE_NS = 50_000


def load_xplane(path):
    """An .xplane.pb as jax's profiler wrote it: device planes are
    '/device:TPU:<n>' with the lines 'XLA Ops' (one event an operation,
    named by its HLO text), 'XLA Modules' (one event a program run) and
    'Async XLA Ops'; host spans are the '/host:CPU' plane's events."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    trace = {"devices": {}, "host": []}
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            dev = {"ops": [], "modules": [], "async": []}
            for line in plane.lines:
                key = {"XLA Ops": "ops", "XLA Modules": "modules",
                       "Async XLA Ops": "async"}.get(line.name)
                if key:
                    dev[key] = [(e.name, e.start_ns, e.duration_ns)
                                for e in line.events]
            trace["devices"][plane.name] = dev
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                trace["host"] += [(e.name, e.start_ns, e.duration_ns)
                                  for e in line.events
                                  if e.name.startswith("bench/")
                                  or e.name.startswith("executor/")
                                  or e.name.startswith("generation/")]
    return trace


def load_perfetto(path):
    """A perfetto/chrome JSON trace (plain or .gz): complete events
    ('ph': 'X', ts and dur in microseconds). A process named
    '/device:TPU:<n>' is a device, its threads the lines; every other
    process is the host."""
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rt") as f:
        events = json.load(f)["traceEvents"]
    procs, threads = {}, {}
    for e in events:
        if e.get("ph") == "M" and e.get("name") == "process_name":
            procs[e["pid"]] = e["args"]["name"]
        if e.get("ph") == "M" and e.get("name") == "thread_name":
            threads[(e["pid"], e["tid"])] = e["args"]["name"]
    trace = {"devices": {}, "host": []}
    for e in events:
        if e.get("ph") != "X":
            continue
        row = (e["name"], e["ts"] * 1000.0, e["dur"] * 1000.0)
        proc = procs.get(e["pid"], "")
        if proc.startswith("/device:TPU:"):
            dev = trace["devices"].setdefault(
                proc, {"ops": [], "modules": [], "async": []})
            key = {"XLA Ops": "ops", "XLA Modules": "modules",
                   "Async XLA Ops": "async"}.get(
                       threads.get((e["pid"], e["tid"])))
            if key:
                dev[key].append(row)
        else:
            trace["host"].append(row)
    return trace


def union(intervals):
    """Merge (start, end) intervals; returns the merged list, sorted."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def total(merged):
    return sum(e - s for s, e in merged)


def subtract(a, b):
    """Parts of merged intervals `a` that no interval of merged `b`
    covers."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append([cur, b[k][0]])
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append([cur, e])
    return out


def short_name(hlo):
    """'%fusion.12 = f32[24,512]{...} fusion(...)' -> 'fusion f32[24,512]'.
    A Mosaic custom call is named by what it runs, as far as the text
    says."""
    m = re.match(r"%?([\w\-]+?)(?:\.\d+)* = (\(?[a-z0-9]+\[[\d,]*\])?", hlo)
    if not m:
        return hlo[:60]
    name, shape = m.group(1), m.group(2) or ""
    if MOSAIC in hlo:
        k = re.search(r'kernel_name[=:]\s*\\?"?([\w.\-]+)', hlo)
        name = "mosaic:" + (k.group(1) if k else name)
    return (name + " " + shape.lstrip("(")).strip()


def is_collective(hlo):
    m = re.search(r"\)?\s([a-z\-]+)\(", hlo.split(" = ", 1)[-1])
    op = m.group(1) if m else ""
    return any(op.startswith(c) for c in COLLECTIVES)


def window_of(trace):
    """The traced window: the 'bench/window' host span, else first
    device event to last."""
    for name, s, d in trace["host"]:
        if name == "bench/window":
            return s, s + d
    starts = [s for dev in trace["devices"].values() for _, s, _ in dev["ops"]]
    ends = [s + d for dev in trace["devices"].values()
            for _, s, d in dev["ops"]]
    return min(starts), max(ends)


def clip(rows, lo, hi):
    return [(n, max(s, lo), min(s + d, hi)) for n, s, d in rows
            if s + d > lo and s < hi]


def summarize(trace, n_devices=None, module_filter=None):
    """Numbers over the traced window, averaged over the devices.
    `module_filter`: substring a program run's name must hold to count
    as a step (None: the program that ran most time)."""
    lo, hi = window_of(trace)
    window_ns = hi - lo
    devices = sorted(trace["devices"])
    if n_devices:
        devices = devices[:n_devices]
    busy = mosaic = exposed = coll = 0.0
    op_time, step_ns = {}, []
    first_gaps = None
    for i, name in enumerate(devices):
        dev = trace["devices"][name]
        ops = clip(dev["ops"], lo, hi)
        merged = union((s, e) for _, s, e in ops)
        busy += total(merged)
        mosaic += sum(e - s for n, s, e in ops if MOSAIC in n)
        coll_iv = union((s, e) for n, s, e in ops + clip(dev["async"], lo, hi)
                        if is_collective(n))
        other = union((s, e) for n, s, e in ops if not is_collective(n))
        coll += total(coll_iv)
        exposed += total(subtract(coll_iv, other))
        if i == 0:
            for n, s, e in ops:
                key = short_name(n)
                op_time[key] = op_time.get(key, 0.0) + (e - s)
            mods = clip(dev["modules"], lo, hi)
            if mods:
                if module_filter is None:
                    by = {}
                    for n, s, e in mods:
                        by[n] = by.get(n, 0.0) + (e - s)
                    module_filter = max(by, key=by.get)
                # whole runs only: one cut by the window's edge is no step
                step_ns = [d for n, s, d in dev["modules"]
                           if module_filter in n and s >= lo and s + d <= hi]
            first_gaps = subtract([[lo, hi]], merged)
    n = max(len(devices), 1)
    return {
        "window_s": window_ns / 1e9,
        "busy_s": busy / n / 1e9,
        "mosaic_s": mosaic / n / 1e9,
        "collective_s": coll / n / 1e9,
        "exposed_collective_s": exposed / n / 1e9,
        "step_device_s": [v / 1e9 for v in step_ns],
        "step_module": module_filter,
        "top_ops": [[k, v / 1e9] for k, v in
                    sorted(op_time.items(), key=lambda kv: -kv[1])[:10]],
        "idle_gaps": attribute_gaps(first_gaps or [], trace["host"]),
    }


def attribute_gaps(gaps, host):
    """Idle seconds by what the host was doing: each gap goes to the
    innermost (shortest) host span that covers its midpoint; pauses
    under 50 us between operations are pooled under one name."""
    spans = sorted(((s, s + d, n) for n, s, d in host), key=lambda r: r[1] - r[0])
    by = {}
    for s, e in gaps:
        if e - s < SHORT_PAUSE_NS:
            key = "pauses_under_50_us_between_operations"
        else:
            mid = (s + e) / 2
            key = next((n for a, b, n in spans if a <= mid <= b),
                       "no_host_span")
        by[key] = by.get(key, 0.0) + (e - s)
    return [[k, v / 1e9] for k, v in sorted(by.items(), key=lambda kv: -kv[1])]


def quantile(values, q):
    """Linear-interpolated quantile of a list (q in 0..1)."""
    v = sorted(values)
    if not v:
        return None
    pos = q * (len(v) - 1)
    i = int(pos)
    frac = pos - i
    return v[i] if i + 1 >= len(v) else v[i] * (1 - frac) + v[i + 1] * frac
