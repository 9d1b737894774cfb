#!/usr/bin/env python
"""Cost-model autotuner: tune every performance knob the stack has
grown, write the winners as a per-executable-fingerprint profile that
``flags.apply_autotune_profile()`` (auto-invoked at Executor/engine
construction) consumes — a second run of the same workload comes up
pre-tuned with zero hand-set flags.

Two stages, per the loop/tensor-abstraction direction
(arXiv:2304.12576 — blocking parameters derived from a cost model,
not guessed):

  1. COST MODEL — one instrumented baseline run with
     ``observability_xla_analysis`` on yields the executable's
     flops/bytes-accessed/argument-bytes gauges plus the program's own
     state-byte accounting. Knobs whose effect is structural are
     derived from these, no sweep needed:
       * ``collective_bucket_mb`` — bucket the DP gradient all-reduce
         so ~TARGET_BUCKETS buckets cover the gradient bytes (enough
         buckets to overlap backward, big enough to amortize
         per-collective latency);
       * ``serving_max_batch_size`` — the measured step is
         bandwidth-bound (low arithmetic intensity) -> larger batches
         amortize the weight streaming; compute-bound -> keep the
         workload batch;
       * ``generation_chunk_tokens`` / ``generation_prefill_buckets``
         — chunk sizing from the same intensity signal, bucket ladder
         from the workload's sequence extent.
  2. MEASURED SWEEP — ``dispatch_pipeline_depth`` (the knob whose
     effect is a host/device timing race) is swept live: N steps per
     candidate through the REAL ``run_pipelined`` path, scored by
     median step wall-ms. Knobs this workload cannot measure (e.g.
     ``reader_prefetch_depth`` — no GeneratorLoader in the loop) are
     deliberately NOT written to the profile.

The profile lands under the ``autotune_dir`` flag — ``.autotune/`` in
the checkout when the flag names no directory; a later run consumes it
only when ITS ``autotune_dir`` flag names that directory — keyed by
``runtime.dispatch
.program_fingerprint`` of the TRAIN program — content-derived, so a
fresh process building the same workload computes the same key and
finds its profile. Scope note: the serving/generation knobs in a
tool-produced profile take effect when the TRAIN profile is applied
(flags are process-wide, so engines constructed in that process read
the tuned values); the ServingEngine/GenerationEngine construction
seams additionally consume profiles saved under the PREDICTOR
program's fingerprint (``flags.save_autotune_profile(fp, ...)`` — the
per-model serving-profile hook; an end-to-end serving sweep that
writes those is ROADMAP item 5's open leg).

``--smoke`` is the CI gate: tune the built-in workload, then spawn TWO
fresh measurement processes — default flags vs profile-applied — and
require (a) the profile measurably changed the flags and (b) the tuned
run's ``paddle_step_wall_ms_p50`` is no worse than the default run's
(x NOISE_MARGIN, CPU-CI jitter headroom). Artifact JSON mirrors the
other bench tools.

Run:  python tools/autotune.py --smoke --out autotune_smoke.json
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path.insert(0, REPO)

os.environ.setdefault("JAX_PLATFORMS", "cpu")

# gradient all-reduce bucketing target: enough buckets that the first
# reduce becomes data-ready mid-backward, few enough that each bucket
# amortizes its collective launch (PR-9 measured 2-8 buckets as the
# overlap sweet spot on the CI models)
TARGET_BUCKETS = 4
# the tuned re-run must be no SLOWER than default; CPU CI timing noise
# gets this much headroom (the sweep picks by median of many steps, so
# a genuine regression still trips it)
NOISE_MARGIN = 1.25


# -- the parameterized workload ----------------------------------------------


def build_workload(fluid, hidden=64, classes=8, in_dim=32):
    """A small but real train step: 2-layer MLP + softmax-xent + Adam
    with global-norm clip (so the fused-optimizer clip seam is part of
    what gets tuned/fingerprinted). Deterministic names via the
    unique_name guard -> the program fingerprint is stable across
    processes."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = fluid.layers.data("x", [in_dim])
        y = fluid.layers.data("y", [1], dtype="int64")
        h = fluid.layers.fc(x, hidden, act="relu",
                            param_attr=fluid.ParamAttr(name="at_w1"),
                            bias_attr=fluid.ParamAttr(name="at_b1"))
        h = fluid.layers.fc(h, hidden, act="relu",
                            param_attr=fluid.ParamAttr(name="at_w2"),
                            bias_attr=fluid.ParamAttr(name="at_b2"))
        logits = fluid.layers.fc(h, classes,
                                 param_attr=fluid.ParamAttr(name="at_w3"),
                                 bias_attr=fluid.ParamAttr(name="at_b3"))
        loss = fluid.layers.mean(
            fluid.layers.softmax_with_cross_entropy(logits, y))
        fluid.optimizer.Adam(
            1e-3, grad_clip=fluid.clip.GradientClipByGlobalNorm(1.0)
        ).minimize(loss)
    return main, startup, loss


def feed_stream(steps, batch=32, in_dim=32, classes=8, host_work=True):
    """Per-step host-side batch synthesis — the input-pipeline cost the
    async dispatch pipeline exists to hide; without it every
    pipeline-depth candidate measures identical."""
    import numpy as np

    rng = np.random.RandomState(0)
    for _ in range(steps):
        x = rng.rand(batch, in_dim).astype("float32")
        if host_work:
            # a little real normalization work per batch (decode stand-in)
            x = (x - x.mean(axis=1, keepdims=True)) / (
                x.std(axis=1, keepdims=True) + 1e-6)
        yield {"x": x,
               "y": (rng.rand(batch, 1) * classes).astype("int64")}


def measure_pipelined(fluid, exe, main, loss, scope, steps, batch=32):
    """Median per-step wall-ms through Executor.run_pipelined (depth
    from the live flag — the seam being tuned)."""
    times = []
    with fluid.scope_guard(scope):
        t_prev = None
        for _ in exe.run_pipelined(main, feeds=feed_stream(steps, batch),
                                   fetch_list=[loss], scope=scope):
            t = time.perf_counter()
            if t_prev is not None:
                times.append((t - t_prev) * 1e3)
            t_prev = t
    # drop the head (bind/compile transients survive even after warmup)
    tail = times[2:] if len(times) > 6 else times
    return statistics.median(tail) if tail else 0.0


# -- cost model ---------------------------------------------------------------


def _state_bytes(main):
    # one f32 gradient per trainable param — the payload the DP
    # all-reduce moves (grads are f32 here regardless of param dtype)
    total = 0
    for p in main.all_parameters():
        n = 1
        for d in p.shape:
            n *= max(int(d), 1)
        total += n * 4
    return total


def _dtype_itemsize(dtype) -> int:
    import numpy as np

    try:
        return int(np.dtype(str(dtype)).itemsize)
    except (TypeError, ValueError):
        return 1  # fp8 family: 1 byte (ml_dtypes normally registers it)


def weight_stream_bytes(program) -> int:
    """DTYPE-AWARE bytes of the program's persistable weights — what a
    serving step actually streams from HBM. A quantized program
    (paddle_tpu.quantize rewrite) counts its int8/fp8 buffers at
    1 byte/element plus the fp32 scale planes, NOT the pre-rewrite
    fp32 sizes — assuming 4 bytes everywhere would over-estimate a
    quantized engine's weight traffic (and with it mis-classify its
    arithmetic intensity) by the dequant factor."""
    total = 0
    for v in program.global_block().vars.values():
        if not getattr(v, "persistable", False) or not v.shape:
            continue
        if any(d is None or int(d) < 0 for d in v.shape):
            continue
        n = 1
        for d in v.shape:
            n *= int(d)
        total += n * _dtype_itemsize(v.dtype)
    return total


def _quantized_weight_elems(program) -> int:
    """Total elements of weights consumed through the quantized matmul
    ops — the tensors whose CPU-reference lowering materializes an
    fp32 dequantized copy that inflates XLA's bytes_accessed."""
    gb = program.global_block()
    names = set()
    for op in gb.ops:
        if op.type in ("quantized_matmul", "quantized_fc"):
            names.update(op.inputs.get("QWeight", ()))
    total = 0
    for n in names:
        if gb.has_var(n) and gb.var(n).shape:
            k = 1
            for d in gb.var(n).shape:
                k *= max(int(d), 1)
            total += k
    return total


def _xla_gauges():
    """The observability_xla_analysis compile-time gauges of the TRAIN
    step. Several executables register gauges in one process (the
    startup/init program compiles first); the train step is identified
    as the executable label with the most flops, and every family is
    read from THAT label — mixing families across executables would
    hand the cost model a nonsense intensity."""
    from paddle_tpu import observability

    inst = observability.snapshot().get("instruments", {})
    families = ("paddle_xla_flops", "paddle_xla_bytes_accessed",
                "paddle_xla_argument_bytes", "paddle_xla_temp_bytes")
    by_label = {}
    for fam in families:
        for label, v in inst.get(fam, {}).get("values", {}).items():
            by_label.setdefault(label, {})[fam] = float(v)
    if not by_label:
        return {}
    best = max(by_label, key=lambda l: by_label[l].get(
        "paddle_xla_flops", by_label[l].get(
            "paddle_xla_bytes_accessed", 0.0)))
    return dict(by_label[best], executable_label=best)


def derive_cost_model_flags(main, xla, batch, seq_extent=None):
    """Structural knobs from the cost model — each entry records its
    rationale next to the chosen value so the profile is auditable."""
    grad_bytes = _state_bytes(main)  # one grad per param, same dtype
    grad_mb = grad_bytes / 2**20
    bucket_mb = max(grad_mb / TARGET_BUCKETS, 0.001)
    # round to a tidy value; tiny models still get a nonzero cap so
    # the planner engages and the collective seam is exercised
    bucket_mb = round(bucket_mb, 3) if bucket_mb < 1 else round(bucket_mb)

    flops = xla.get("paddle_xla_flops", 0.0)
    bytes_acc = xla.get("paddle_xla_bytes_accessed", 0.0)
    # quantized programs (paddle_tpu.quantize): the gauges may have
    # been captured on the CPU-reference lowering, whose dequantize
    # materializes an fp32 copy of every quantized weight — on TPU the
    # dequant stays in registers, so the weight stream is the int8/fp8
    # bytes. Swap the fp32-equivalent weight traffic for the true
    # quantized bytes before classifying intensity, or a quantized
    # engine's serving batch / generation chunk knobs would be derived
    # from weight bytes it no longer moves.
    q_elems = _quantized_weight_elems(main)
    w_stream = weight_stream_bytes(main)
    if q_elems and bytes_acc:
        bytes_acc = max(bytes_acc - 4.0 * q_elems, float(w_stream))
    intensity = (flops / bytes_acc) if bytes_acc else 0.0
    # bandwidth-bound (< ~4 flops/byte): bigger serving batches / decode
    # chunks amortize the weight streaming; compute-bound: keep them
    # tight so latency stays low
    bandwidth_bound = intensity < 4.0
    serving_batch = int(batch * (2 if bandwidth_bound else 1))
    chunk_tokens = 32 if bandwidth_bound else 16

    ladder = []
    ext = int(seq_extent or 512)
    b = 16
    while b < ext:
        ladder.append(b)
        b *= 2
    ladder.append(ext)

    flags = {
        "collective_bucket_mb": str(bucket_mb),
        "serving_max_batch_size": serving_batch,
        "generation_chunk_tokens": chunk_tokens,
        "generation_prefill_buckets": ",".join(str(x) for x in ladder),
    }
    rationale = {
        "grad_mb": round(grad_mb, 4),
        "target_buckets": TARGET_BUCKETS,
        "arithmetic_intensity_flops_per_byte": round(intensity, 3),
        "bandwidth_bound": bandwidth_bound,
        "weight_stream_bytes": int(w_stream),
        "quantized_weight_elems": int(q_elems),
        "bytes_accessed_effective": float(bytes_acc),
        "xla": xla,
    }
    return flags, rationale


# -- the tuner ----------------------------------------------------------------


def tune(steps=32, batch=32, smoke=False):
    import paddle_tpu as fluid
    from paddle_tpu import flags as pflags
    from paddle_tpu import observability
    from paddle_tpu.runtime.dispatch import program_fingerprint

    # the tuner measures DEFAULTS — a stale profile auto-applying
    # itself mid-measurement would tune against its own output
    fluid.set_flags({"autotune_apply": False,
                     "observability_xla_analysis": True})

    main, startup, loss = build_workload(fluid)
    fingerprint = program_fingerprint(main)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
    # warmup (compile) outside every timed window
    with fluid.scope_guard(scope):
        for feed in feed_stream(2, batch):
            exe.run(main, feed=feed, fetch_list=[loss], scope=scope)

    report = {"fingerprint": fingerprint, "steps_per_candidate": steps}

    # baseline at default flags
    baseline_ms = measure_pipelined(fluid, exe, main, loss, scope, steps,
                                    batch)
    report["baseline_ms_p50"] = round(baseline_ms, 4)

    # stage 1: cost model from the compile-time analysis gauges
    xla = _xla_gauges()
    cm_flags, rationale = derive_cost_model_flags(main, xla, batch)
    report["cost_model"] = {"flags": cm_flags, "rationale": rationale}

    # stage 2: measured sweep of the host/device-race knobs
    depth_candidates = (1, 2, 3) if smoke else (1, 2, 3, 4, 6)
    sweep = {}
    best_depth, best_ms = None, None
    for d in depth_candidates:
        fluid.set_flags({"dispatch_pipeline_depth": d})
        ms = measure_pipelined(fluid, exe, main, loss, scope, steps, batch)
        sweep[str(d)] = round(ms, 4)
        # strictly-better wins; ties prefer the shallower pipeline
        # (each slot pins a batch of device memory)
        if best_ms is None or ms < best_ms * 0.98:
            best_depth, best_ms = d, ms
    report["depth_sweep_ms"] = sweep
    report["tuned_ms_p50"] = round(best_ms, 4)

    tuned_flags = dict(cm_flags)
    tuned_flags["dispatch_pipeline_depth"] = best_depth
    # NOT written: reader_prefetch_depth — this workload feeds through
    # run_pipelined, not a GeneratorLoader, so no candidate value was
    # ever measured; shipping an untested knob as if evidence-backed
    # is exactly what this tool exists to end

    hidden = observability.snapshot().get("collected", {}).get(
        "paddle_step_overlap_hidden_fraction", {}).get("_")
    evidence = {
        "baseline_ms_p50": report["baseline_ms_p50"],
        "tuned_ms_p50": report["tuned_ms_p50"],
        "depth_sweep_ms": sweep,
        "cost_model": rationale,
        "overlap_hidden_fraction": hidden,
        "backend": "cpu" if smoke else None,
    }
    path = pflags.save_autotune_profile(fingerprint, tuned_flags, evidence)
    report["profile_path"] = path
    report["tuned_flags"] = tuned_flags
    return report, fingerprint


# -- fresh-process measurement (the smoke gate's two arms) -------------------


def measure_one(mode: str, steps: int, batch=32):
    """Fresh-process arm: 'default' runs the workload on default
    flags; 'tuned' applies the profile via the real
    apply_autotune_profile seam first (and proves the flags changed).
    Prints one JSON line: the paddle_step_* median + what applied."""
    import paddle_tpu as fluid
    from paddle_tpu import flags as pflags
    from paddle_tpu import observability
    from paddle_tpu.runtime.dispatch import program_fingerprint

    fluid.set_flags({"autotune_apply": False})  # explicit seam below
    main, startup, loss = build_workload(fluid)
    fingerprint = program_fingerprint(main)
    applied = {}
    if mode == "tuned":
        defaults = {n: pflags.flag(n) for n in (
            "dispatch_pipeline_depth", "collective_bucket_mb",
            "serving_max_batch_size", "generation_chunk_tokens")}
        applied = pflags.apply_autotune_profile(fingerprint)
        if not applied:
            print(json.dumps({"error": "profile applied no flags",
                              "fingerprint": fingerprint}))
            return 1
        if all(pflags.flag(n) == v for n, v in defaults.items()):
            print(json.dumps({"error": "flags did not change",
                              "fingerprint": fingerprint}))
            return 1
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        for feed in feed_stream(2, batch):
            exe.run(main, feed=feed, fetch_list=[loss], scope=scope)
    own_ms = measure_pipelined(fluid, exe, main, loss, scope, steps, batch)
    snap = observability.snapshot().get("collected", {})
    out = {
        "mode": mode,
        "fingerprint": fingerprint,
        "applied": applied,
        "own_ms_p50": round(own_ms, 4),
        "paddle_step_wall_ms_p50": snap.get(
            "paddle_step_wall_ms_p50", {}).get("_"),
        "paddle_step_total": snap.get("paddle_step_total", {}).get("_"),
    }
    print("PT_AUTOTUNE_RESULT " + json.dumps(out))
    return 0


def _spawn_measure(mode: str, steps: int, autotune_dir: str,
                   repeats: int = 3):
    """Fresh-process measurement arm, best-of-N: a single ~0.2 ms-step
    median sample swings >2x run to run on a shared CI box, so the
    gate compares the MIN of `repeats` independent process medians —
    the classic noise-robust estimator for 'how fast can this config
    actually go'."""
    env = dict(os.environ)
    env["FLAGS_autotune_dir"] = autotune_dir
    env.setdefault("JAX_PLATFORMS", "cpu")
    best = None
    samples = []
    for _ in range(repeats):
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__),
             "--measure-one", mode, "--steps", str(steps)],
            env=env, capture_output=True, text=True, timeout=900)
        result = None
        for line in proc.stdout.splitlines():
            if line.startswith("PT_AUTOTUNE_RESULT "):
                result = json.loads(line[len("PT_AUTOTUNE_RESULT "):])
        if result is None:
            raise RuntimeError(
                f"measure-one {mode} produced no result "
                f"(rc={proc.returncode}):\n"
                f"{proc.stdout[-2000:]}\n{proc.stderr[-2000:]}")
        ms = result.get("paddle_step_wall_ms_p50") or result["own_ms_p50"]
        samples.append(ms)
        if best is None or ms < (best.get("paddle_step_wall_ms_p50")
                                 or best["own_ms_p50"]):
            best = result
    best["samples_ms_p50"] = samples
    return best


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="CI mode: tune the built-in workload, gate the "
                         "fresh-process profiled re-run")
    ap.add_argument("--steps", type=int, default=None,
                    help="steps per sweep candidate")
    ap.add_argument("--out", default=None, help="artifact JSON path")
    ap.add_argument("--measure-one", choices=("default", "tuned"),
                    default=None, help=argparse.SUPPRESS)
    args = ap.parse_args()

    if args.measure_one:
        return measure_one(args.measure_one, args.steps or 24)

    from paddle_tpu import flags as pflags

    if not pflags.autotune_dir():
        pflags.set_flags({"autotune_dir": os.path.join(REPO, ".autotune")})
    steps = args.steps or (24 if args.smoke else 48)
    t0 = time.time()
    report, fingerprint = tune(steps=steps, smoke=args.smoke)
    gates = {}
    ok = True

    if args.smoke:
        adir = pflags.autotune_dir()
        default_run = _spawn_measure("default", steps, adir)
        tuned_run = _spawn_measure("tuned", steps, adir)
        report["fresh_process"] = {"default": default_run,
                                   "tuned": tuned_run}
        # gate 1: the fresh process consumed the profile and its flags
        # measurably changed
        gates["profile_applied_flags"] = bool(tuned_run.get("applied"))
        ok &= gates["profile_applied_flags"]
        # gate 2: the profiled re-run's paddle_step_* median is no
        # worse than the default-flags run (x noise margin)
        d = default_run.get("paddle_step_wall_ms_p50") or \
            default_run["own_ms_p50"]
        t = tuned_run.get("paddle_step_wall_ms_p50") or \
            tuned_run["own_ms_p50"]
        gates["tuned_no_slower"] = bool(t <= d * NOISE_MARGIN)
        gates["default_ms_p50"] = d
        gates["tuned_ms_p50"] = t
        ok &= gates["tuned_no_slower"]

    report["gates"] = gates
    report["ok"] = bool(ok)
    report["wall_s"] = round(time.time() - t0, 1)
    out = json.dumps(report, indent=1, sort_keys=True)
    print(out)
    if args.out:
        with open(args.out, "w") as f:
            f.write(out + "\n")
    if not ok:
        print("[autotune] GATE FAILED: " + json.dumps(gates),
              file=sys.stderr)
        return 1
    print(f"[autotune] OK: profile {report['profile_path']} "
          f"(fingerprint {fingerprint[:12]}...)", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
