"""The harness: finds a cell's files by the names in BENCHMARK.json,
drives its traffic kind, and prints the result line. General code only:
what belongs to one configuration, traffic mix, kind or per-layer metric
lives in a file of its own (README.md says where)."""

import argparse
import glob
import importlib.util
import json
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
TRACE_DIR = os.path.join(ROOT, ".bench_trace")


class NoChip(SystemExit):
    pass


def load_json(path):
    with open(path) as f:
        return json.load(f)


def load_module(path, name=None):
    """Import a file by path: metric names hold dots, so readers and
    kinds are found as files, not as package attributes."""
    name = name or "bench_" + os.path.basename(path).replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not os.path.exists(path):
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_peaks(device_kind):
    table = load_json(os.path.join(HERE, "peaks.json"))
    for row in table["devices"]:
        if row["device_kind"] == device_kind:
            return row
    raise KeyError(f"device_kind {device_kind!r} is not in benchmark/peaks.json:"
                   " add its published peaks with their source")


class Cell:
    """One entry of `workloads`, with its configuration and traffic."""

    def __init__(self, bench, name):
        cells = {w["name"]: w for w in bench["workloads"]}
        if name not in cells:
            raise SystemExit(f"no workload {name!r} in BENCHMARK.json; have "
                             f"{sorted(cells)}")
        self.bench = bench
        self.spec = cells[name]
        self.name = name
        self.chips = int(self.spec["chips"])
        cfg = {c["name"]: c for c in bench["configs"]}[self.spec["config"]]
        self.config = load_json(os.path.join(ROOT, cfg["file"]))
        self.traffic = load_json(
            os.path.join(HERE, "traffic", self.spec["traffic"] + ".json"))
        self.end_to_end = [m for m in bench["end_to_end"]
                           if name in m.get("workloads", [name])]
        self.per_layer = [m for m in bench["per_layer"]
                          if name in m.get("workloads", [name])]

    def kind(self):
        return load_module(os.path.join(HERE, "kinds",
                                        self.traffic["kind"] + ".py"))


class Ctx:
    """What a kind is given. `clock` is the one host clock of the run."""

    def __init__(self, cell, seed, seconds, trace, devices, t0):
        self.cell = cell
        self.config = cell.config
        self.traffic = cell.traffic
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.trace = bool(trace)
        self.devices = devices
        self.t0 = t0
        self.clock = time.perf_counter
        self.notes = {}

    @property
    def window_seconds(self):
        """A traced window is cut to the traffic file's trace_seconds:
        traces are large and tracing slows the host."""
        if self.trace:
            return min(self.seconds,
                       float(self.traffic.get("trace_seconds", self.seconds)))
        return self.seconds


class CompileCounter:
    """Compile requests jax makes (jit cache misses, whether XLA then
    compiles or the persistent cache answers), from jax's own events."""

    def __init__(self):
        import jax

        self.requests = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(self._on)

    def _on(self, event, *_a, **_k):
        if event == "/jax/core/compile/backend_compile_duration":
            self.requests += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def require_devices(chips):
    """The chips the cell asks for, on a TPU, or no result at all."""
    import jax

    try:
        devs = jax.devices()
    except RuntimeError as e:
        raise NoChip(f"benchmark: jax found no accelerator: {e}")
    if devs[0].platform != "tpu":
        raise NoChip(f"benchmark: needs a TPU, jax found {devs[0].platform!r}")
    if len(devs) < chips:
        raise NoChip(f"benchmark: the cell asks for {chips} chips, jax "
                     f"found {len(devs)}")
    return devs[:chips]


def memory_peak(devices):
    """Peak bytes on the fullest chip as the allocator counts them."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


def executable_bytes(bound):
    """Bytes a bound step's executable needs while it runs, by XLA's
    own accounting (arguments + outputs - aliased + temporaries): the
    allocator's peak counter leaves the temporaries out (PERF.md,
    layer `device`)."""
    mem = bound.aot_compiled().memory_analysis()
    return int(mem.argument_size_in_bytes + mem.output_size_in_bytes
               - mem.alias_size_in_bytes + mem.temp_size_in_bytes)


def start_trace():
    import jax

    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0      # host spans come from TraceAnnotation
    opts.host_tracer_level = 2
    jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)


def stop_trace():
    import jax

    jax.profiler.stop_trace()
    paths = glob.glob(os.path.join(TRACE_DIR, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not paths:
        raise RuntimeError("the profiler wrote no .xplane.pb")
    return paths[0]


def span(name):
    """A host span on the profiler's clock, around a call into a layer."""
    import jax

    return jax.profiler.TraceAnnotation(name)


def read_per_layer(cell, inputs):
    """Each per-layer metric through its own reader; a reader that
    finds nothing to read returns None and the metric is left out."""
    out = {}
    for m in cell.per_layer:
        reader = load_module(os.path.join(HERE, "metrics", m["name"] + ".py"))
        value = reader.read(inputs)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def judge(checks):
    """checks: [(name, value, limit)], value <= limit passes. A value
    that is not a number (a reference that gave none) fails."""
    ok = True
    for _name, value, limit in checks:
        ok = ok and value is not None and value == value and value <= limit
    return ok


def checks_dict(checks):
    return {name: {"value": value, "limit": limit}
            for name, value, limit in checks}


def drive(ctx):
    """One run after the chips were found: set-up, the window, the
    program's state freed, the comparison, the result. Tests call this
    at tiny sizes on the CPU (ctx.trace off)."""
    cell, devices = ctx.cell, ctx.devices
    compiles = CompileCounter()
    kind = cell.kind().Kind(ctx)

    kind.setup()
    setup_s = time.perf_counter() - ctx.t0
    if ctx.trace:
        start_trace()
    c0 = compiles.requests
    raw = kind.window()      # the kind puts the span bench/window around it
    compiles_in_window = compiles.requests - c0
    xplane = stop_trace() if ctx.trace else None
    ctx.notes["allocator_peak_bytes"] = memory_peak(devices)
    peak = max(ctx.notes["allocator_peak_bytes"],
               int(raw.get("memory_peak_bytes", 0)))
    kind.release()
    t_check = time.perf_counter()
    checks = list(kind.check())
    checks.append(("compiles_in_window", compiles_in_window, 0))

    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": peak}
    result = {"correct": judge(checks), "attempted": int(raw["attempted"]),
              "failed": int(raw["failed"])}
    if ctx.trace:
        from reduce import load_xplane, summarize

        summary = summarize(load_xplane(xplane), n_devices=len(devices))
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        inputs = {"trace": summary, "raw": raw, "peaks": ctx.peaks,
                  "config": ctx.config, "traffic": ctx.traffic,
                  "chips": cell.chips, "memory_peak_bytes": peak}
        result["metrics"] = read_per_layer(cell, inputs)
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = summary["window_s"]
        result["breakdown"] = {"device_ops": summary["top_ops"][:10],
                               "idle_gaps": summary["idle_gaps"][:10]}
    else:
        values = dict(raw["end_to_end"], setup_s=setup_s)
        result["metrics"] = {m["name"]: {"value": float(values[m["name"]]),
                                         "unit": m["unit"]}
                             for m in cell.end_to_end}
    result["device"] = device
    result["notes"] = dict(ctx.notes, compile_requests=compiles.requests,
                           compile_cache_hits=compiles.cache_hits,
                           setup_s=setup_s, window_s=raw.get("window_s"),
                           check_s=time.perf_counter() - t_check)
    result["checks"] = checks_dict(checks)
    for name, value, limit in checks:
        sys.stderr.write(f"check {name}: {value!r} limit {limit!r}\n")
    sys.stderr.flush()
    return result


def open_cell(workload, seed, seconds, trace, t0):
    """The cell's Ctx on this machine's chips."""
    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell = Cell(bench, workload)
    if not os.path.isdir(os.path.join(ROOT, "paddle_tpu")):
        raise NoChip("benchmark: the system under test (paddle_tpu/) is "
                     "not in this directory")
    # the compile cache: where the environment says, else one fixed
    # path inside the checkout; the program takes the same variable
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(ROOT, ".jax_cache"))
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    devices = require_devices(cell.chips)
    from paddle_tpu.runtime import dispatch

    dispatch.ensure_persistent_cache()
    ctx = Ctx(cell, seed, seconds, trace, devices, t0)
    ctx.peaks = load_peaks(devices[0].device_kind)
    return ctx


def run(argv, t0):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    ctx = open_cell(args.workload, args.seed, args.seconds, args.trace, t0)
    print(json.dumps(drive(ctx)), flush=True)
    return 0
