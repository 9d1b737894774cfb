#!/usr/bin/env python
"""Dispatch microbench: Executor.run steps/sec on a tiny MLP (CPU).

Measures the python hot path, NOT the model: the MLP is deliberately
small enough that per-step dispatch overhead dominates, so the number
tracks the cost of everything between the user's `exe.run(...)` and the
XLA executable. Three loops, jit-compile excluded (warmup first):

  fast    — Executor.run with the BoundStep dispatch cache (default)
  legacy  — pre-dispatch-cache emulation: fast path off, donation
            forced on (the old executor donated on CPU), so every step
            rebuilds the cache key, re-normalizes the feed, re-walks
            the scope — the pre-PR per-step work
  floor   — the raw jitted step function called directly: the number
            python dispatch can never beat

Also proves the cross-executor compile cache: a SECOND Executor runs
the same program and must report jit_compiles == 0.

Second benchmark — the async host/device pipeline: a deliberately
HOST-FEED-BOUND step (the input pipeline materializes + casts a
multi-MB float64 batch per step, the model is a medium matmul stack)
driven twice over identical feeds:

  sync     — the classic loop: feed generation, normalization and the
             H2D put sit on the critical path between device steps
  overlap  — Executor.run_pipelined: the same work on the dedicated
             feeder thread, double-buffered, while the device runs
             step N (runtime.dispatch.BoundStep.run_pipelined)

Reports steps/s both ways plus the paddle_step_overlap_* accounting
(host feed ms per step, how much of it the consumer waited for, the
hidden fraction). CI gates overlap_speedup >= --min-overlap-speedup
(default 1.3) — the proof that host work actually hides behind the
device step.

Prints one JSON object; --out FILE also writes it to disk. --smoke
shrinks the loops for CI (the JSON is uploaded as an artifact so the
perf trajectory accumulates per commit). Exit code 1 if the fast loop
is slower than legacy (a dispatch regression) or the overlap gate
fails.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

os.environ.setdefault("JAX_PLATFORMS", "cpu")


def build_mlp(fluid):
    """Tiny MLP: 2 hidden fc layers, SGD. Small on purpose — see
    module docstring."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = fluid.layers.data("x", [16])
        y = fluid.layers.data("y", [1], dtype="int64")
        h = fluid.layers.fc(x, 16, act="relu")
        loss = fluid.layers.mean(
            fluid.layers.softmax_with_cross_entropy(
                fluid.layers.fc(h, 10), y))
        fluid.optimizer.SGD(0.1).minimize(loss)
    return main, startup, loss


def time_loop(fn, steps):
    t0 = time.perf_counter()
    for _ in range(steps):
        fn()
    return time.perf_counter() - t0


def build_feed_bound(fluid, width):
    """Host-feed-bound step: the input pipeline cost (float64
    materialize + cast) rivals the device matmuls. The data layer is
    batch-agnostic; the feed picks the batch size."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = fluid.layers.data("x", [width])
        h = fluid.layers.fc(x, width, act="relu")
        out = fluid.layers.mean(fluid.layers.fc(h, 16))
    return main, startup, out


def overlap_bench(fluid, steps, batch=64, width=2048, io_wait_s=0.006):
    """Sync vs pipelined loop over IDENTICAL host-heavy feed streams;
    returns the overlap report dict.

    The feed stream models a real input pipeline: a blocking read
    stage (``io_wait_s`` of disk/decode latency — time the CPU is
    idle) followed by CPU work materializing a fresh float64 batch.
    On the CPU smoke runner the jitted "device" step shares cores
    with the feeder, so the CPU share of the feed cannot physically
    be hidden there — the I/O share can, and is, which is what the
    gate measures. On a real TPU both shares hide."""
    import numpy as np

    from paddle_tpu.observability.registry import overlap_telemetry

    main, startup, out = build_feed_bound(fluid, width)

    def feeds(n):
        # representative host input pipeline per step: blocking read
        # wait, then materialize a fresh float64 batch (the
        # np.asarray/pad/cast work the ISSUE's s_per_step_dispatch
        # accounting blames) — the BoundStep plan casts it to float32
        rng = np.random.RandomState(7)
        for _ in range(n):
            time.sleep(io_wait_s)
            yield {"x": rng.rand(batch, width)}

    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        warm = 4
        # warm both paths (compile + first-call excluded)
        for f in feeds(warm):
            exe.run(main, feed=f, fetch_list=[out])
        for _ in exe.run_pipelined(main, feeds(warm), [out]):
            pass

        t0 = time.perf_counter()
        for f in feeds(steps):
            exe.run(main, feed=f, fetch_list=[out])
        sync_s = time.perf_counter() - t0

        before = overlap_telemetry().snapshot()
        t0 = time.perf_counter()
        for _ in exe.run_pipelined(main, feeds(steps), [out]):
            pass
        async_s = time.perf_counter() - t0
        after = overlap_telemetry().snapshot()

    n = max(1, after["steps"] - before["steps"])
    feed_ms = after["feed_ms_sum"] - before["feed_ms_sum"]
    wait_ms = after["wait_ms_sum"] - before["wait_ms_sum"]
    return {
        "model": f"mlp[{width}-{width}-16] batch={batch} float64 feed",
        "io_wait_ms_per_step": round(io_wait_s * 1e3, 3),
        "steps": steps,
        "sync_steps_per_sec": round(steps / sync_s, 1),
        "async_steps_per_sec": round(steps / async_s, 1),
        "overlap_speedup": round(sync_s / async_s, 2),
        # s_per_step_dispatch accounting: host feed work per step, the
        # part of it the consumer actually waited for, and the hidden
        # fraction (1.0 = all host feed work ran under the device step)
        "feed_ms_per_step": round(feed_ms / n, 3),
        "wait_ms_per_step": round(wait_ms / n, 3),
        "hidden_fraction": round(
            1.0 - (min(wait_ms, feed_ms) / feed_ms) if feed_ms > 0 else 0.0,
            4),
    }


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--steps", type=int, default=2000)
    ap.add_argument("--warmup", type=int, default=50)
    ap.add_argument("--repeats", type=int, default=3,
                    help="take the best of N timed loops (noise guard)")
    ap.add_argument("--smoke", action="store_true",
                    help="CI mode: short loops")
    ap.add_argument("--overlap-steps", type=int, default=60,
                    help="steps per overlap timing loop")
    ap.add_argument("--min-overlap-speedup", type=float, default=1.3,
                    help="CI gate: pipelined vs sync on the "
                         "host-feed-bound step")
    ap.add_argument("--out", default=None, help="also write JSON here")
    args = ap.parse_args()
    if args.smoke:
        args.steps = min(args.steps, 300)
        args.repeats = min(args.repeats, 2)
        args.overlap_steps = min(args.overlap_steps, 40)

    import numpy as np

    import paddle_tpu as fluid
    from paddle_tpu.runtime import dispatch as _dispatch

    main_prog, startup, loss = build_mlp(fluid)
    feed = {"x": np.random.RandomState(0).rand(8, 16).astype("float32"),
            "y": np.zeros((8, 1), "int64")}
    scope = fluid.Scope()
    result = {"model": "mlp[16-16-10] batch=8", "steps": args.steps}

    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)

        def one():
            exe.run(main_prog, feed=feed, fetch_list=[loss],
                    return_numpy=False)

        for _ in range(args.warmup):
            one()

        # fast path
        dt = min(time_loop(one, args.steps) for _ in range(args.repeats))
        result["steps_per_sec"] = round(args.steps / dt, 1)
        result["us_per_step"] = round(dt / args.steps * 1e6, 1)

        # floor: the raw jitted step fn, state threaded by hand
        compiled = next(b for b in exe._cache.values() if b.fetch_names)
        bound = next(b for b in exe._bound.values()
                     if b.compiled is compiled)
        ordered = [norm(feed[n]) for n, norm in bound.feed_plan]
        state = list(bound.state_vals)
        wpos = {n: i for i, n in enumerate(compiled.written_names)}
        sidx = [wpos.get(n) for n in compiled.state_names]
        base = bound.base_key
        box = {"i": 0, "state": state}

        def floor_step():
            box["i"] += 1
            outs = compiled.fn(base, np.int32(box["i"]), *ordered,
                               *box["state"])
            ns = outs[len(compiled.fetch_names):]
            box["state"] = [ns[w] if w is not None else old
                            for w, old in zip(sidx, box["state"])]

        floor_step()
        dt = min(time_loop(floor_step, args.steps)
                 for _ in range(args.repeats))
        result["floor_steps_per_sec"] = round(args.steps / dt, 1)

        # legacy: pre-dispatch-cache emulation on a FRESH executor so
        # its compile counters and caches don't pollute the fast ones
        legacy_exe = fluid.Executor(fluid.CPUPlace())
        legacy_exe.fast_dispatch = False
        legacy_exe._force_donation = True  # the pre-PR executor donated

        def legacy_one():
            legacy_exe.run(main_prog, feed=feed, fetch_list=[loss],
                           return_numpy=False)

        for _ in range(max(5, args.warmup // 4)):
            legacy_one()
        dt = min(time_loop(legacy_one, args.steps)
                 for _ in range(args.repeats))
        result["legacy_steps_per_sec"] = round(args.steps / dt, 1)
        result["speedup_vs_legacy"] = round(
            result["steps_per_sec"] / result["legacy_steps_per_sec"], 2)

        # cross-executor compile sharing: a second executor, same
        # program — must compile NOTHING new
        exe2 = fluid.Executor(fluid.CPUPlace())
        exe2.run(main_prog, feed=feed, fetch_list=[loss],
                 return_numpy=False)
        st2 = exe2.cache_stats()
        result["second_executor_jit_compiles"] = st2["jit_compiles"]
        result["second_executor_shared_cache_hits"] = st2["shared_cache_hits"]

        st = exe.cache_stats()
        result["cache_stats"] = {
            k: st[k] for k in ("bound_hits", "bound_misses", "jit_compiles",
                               "shared_cache_hits", "compile_time_s")
        }
        result["persistent_cache_dir"] = st["process"]["persistent_cache_dir"]

    # -- async host/device pipeline: sync vs overlapped feed -----------
    result["overlap"] = overlap_bench(fluid, args.overlap_steps)

    out = json.dumps(result, indent=2, sort_keys=True)
    print(out)
    if args.out:
        with open(args.out, "w") as f:
            f.write(out + "\n")
    rc = 0
    if result["speedup_vs_legacy"] < 1.0:
        sys.stderr.write("[dispatch_bench] REGRESSION: fast dispatch is "
                         "slower than the legacy path\n")
        rc = 1
    if result["overlap"]["overlap_speedup"] < args.min_overlap_speedup:
        sys.stderr.write(
            "[dispatch_bench] REGRESSION: async feed pipeline "
            f"{result['overlap']['overlap_speedup']}x < "
            f"{args.min_overlap_speedup}x on the host-feed-bound step\n")
        rc = 1
    return rc


if __name__ == "__main__":
    sys.exit(main())
