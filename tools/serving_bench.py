#!/usr/bin/env python
"""Serving microbench: closed-loop latency/throughput through the
ServingEngine on a tiny model (CPU).

Measures the serving layer, NOT the model: C closed-loop clients each
fire single-row requests back to back through the dynamic batcher, so
the numbers track coalescing + queueing + dispatch overhead. Reported:

  direct      — requests issued one-at-a-time through a bare
                Predictor.run: the unbatched single-caller baseline
                `examples/serve_bucketed.py`-style loops pay
  closed_loop — requests/sec + latency quantiles with C closed-loop
                clients (each waits for its response before the next
                request): the latency-bounded regime, where the batch
                timeout is the price of coalescing
  burst       — all requests submitted as futures up front, then
                awaited: the throughput-bounded regime, where full
                batches amortize per-call dispatch (this is the number
                that must beat `direct`)
  fifo_vs_slo — the SAME bursty deadline-bound overload through the
                bare FIFO engine and through the traffic tier
                (paddle_tpu.traffic): deadline-goodput both ways plus
                the gain (tools/traffic_replay.py owns the full
                scenario suite; this is its headline number riding the
                serving trajectory artifact)

Prints one JSON object (same contract as tools/dispatch_bench.py);
--out FILE also writes it to disk; --smoke shrinks the load for CI
(the JSON is uploaded as an artifact so the serving trajectory
accumulates per commit). Exit code 1 if any request errored or the
engine never coalesced (occupancy stuck at 1 with concurrent clients —
the subsystem's whole point lost).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

os.environ.setdefault("JAX_PLATFORMS", "cpu")


def export_model(fluid, path):
    """Tiny MLP classifier; single-row requests make batching visible."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = fluid.layers.data("x", [16])
        h = fluid.layers.fc(x, 32, act="relu")
        out = fluid.layers.fc(h, 10, act="softmax")
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        fluid.io.save_inference_model(path, ["x"], [out], exe, main)


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--requests", type=int, default=2000,
                    help="total requests per measured loop")
    ap.add_argument("--clients", type=int, default=8,
                    help="closed-loop client threads")
    ap.add_argument("--max-batch", type=int, default=16)
    ap.add_argument("--batch-timeout-ms", type=float, default=2.0)
    ap.add_argument("--workers", type=int, default=2)
    ap.add_argument("--smoke", action="store_true",
                    help="CI mode: short loops")
    ap.add_argument("--out", default=None, help="also write JSON here")
    args = ap.parse_args()
    if args.smoke:
        args.requests = min(args.requests, 400)
        args.clients = min(args.clients, 4)

    import numpy as np

    import paddle_tpu as fluid
    from paddle_tpu.inference import Config, create_predictor
    from paddle_tpu.serving import ServingEngine

    model_dir = tempfile.mkdtemp(prefix="pt_serving_bench_")
    export_model(fluid, model_dir)
    # batch bucketing pins the compiled-shape set: any coalesced batch
    # pads up to a power-of-two bucket, and the warmup below compiles
    # every bucket OUTSIDE the timed loops (one stray in-loop XLA
    # compile would swamp a 100ms microbench)
    buckets = []
    b = 1
    while b < args.max_batch:
        buckets.append(b)
        b *= 2
    buckets.append(args.max_batch)
    cfg = Config(model_dir)
    cfg.enable_shape_bucketing(batch_buckets=tuple(buckets))
    pred = create_predictor(cfg)

    rng = np.random.RandomState(0)
    xs = [rng.rand(1, 16).astype("float32") for _ in range(32)]
    for b in buckets:  # compile every batch bucket before timing
        pred.run([rng.rand(b, 16).astype("float32")])

    result = {
        "model": "mlp[16-32-10] single-row requests",
        "requests": args.requests,
        "clients": args.clients,
        "max_batch_size": args.max_batch,
        "batch_timeout_ms": args.batch_timeout_ms,
        "num_workers": args.workers,
    }

    # direct single-caller baseline (what callers do without the engine)
    n_direct = args.requests
    t0 = time.perf_counter()
    for i in range(n_direct):
        pred.run([xs[i % len(xs)]])
    dt = time.perf_counter() - t0
    result["direct_req_per_sec"] = round(n_direct / dt, 1)

    # engine: C closed-loop clients
    engine = ServingEngine(pred, max_batch_size=args.max_batch,
                           batch_timeout_ms=args.batch_timeout_ms,
                           queue_capacity=max(256, args.requests),
                           num_workers=args.workers)
    per_client = args.requests // args.clients
    errors = []
    barrier = threading.Barrier(args.clients + 1)

    def client(cid):
        try:
            barrier.wait(timeout=60)
            for i in range(per_client):
                engine.predict({"x": xs[(cid + i) % len(xs)]}, timeout=120)
        except Exception as e:  # noqa: BLE001
            errors.append(repr(e))

    threads = [threading.Thread(target=client, args=(c,), daemon=True)
               for c in range(args.clients)]
    for t in threads:
        t.start()
    barrier.wait(timeout=60)
    t0 = time.perf_counter()
    for t in threads:
        t.join(timeout=600)
    dt = time.perf_counter() - t0
    hung = sum(t.is_alive() for t in threads)
    snap = engine.metrics.snapshot()
    engine.close(drain=True)

    served = args.clients * per_client
    result["closed_loop_req_per_sec"] = round(served / dt, 1)
    result["latency_ms"] = {k: snap["latency_ms"][k]
                            for k in ("p50", "p95", "p99", "mean", "max")}
    result["queue_wait_ms_p95"] = snap["queue_wait_ms"]["p95"]
    result["batch_occupancy"] = snap["batch_occupancy"]
    result["batch_fill"] = snap["batch_fill"]
    result["batches_total"] = snap["batches_total"]

    # burst: submit everything up front, await all — full batches
    # amortize per-call dispatch, so this must beat `direct`
    burst_engine = ServingEngine(pred, max_batch_size=args.max_batch,
                                 batch_timeout_ms=args.batch_timeout_ms,
                                 queue_capacity=max(256, args.requests),
                                 num_workers=args.workers)
    t0 = time.perf_counter()
    futs = [burst_engine.submit({"x": xs[i % len(xs)]})
            for i in range(args.requests)]
    for f in futs:
        try:
            f.result(timeout=600)
        except Exception as e:  # noqa: BLE001
            errors.append(repr(e))
    dt = time.perf_counter() - t0
    burst_snap = burst_engine.metrics.snapshot()
    burst_engine.close(drain=True)
    result["burst_req_per_sec"] = round(args.requests / dt, 1)
    result["burst_speedup_vs_direct"] = round(
        result["burst_req_per_sec"] / result["direct_req_per_sec"], 2)
    result["burst_batch_occupancy"] = burst_snap["batch_occupancy"]

    # FIFO vs SLO-aware goodput under deadline-bound overload: the
    # traffic tier must convert the same offered load into MORE
    # responses that meet their deadlines (sheds are free, late
    # completions are not)
    sys.path.insert(0, HERE)
    import traffic_replay

    overload_spec = {
        "rate": result["burst_req_per_sec"] * 1.5,
        "burst_rate": result["burst_req_per_sec"] * 4.0,
        "duration_s": 2.0 if args.smoke else 5.0,
        "max_batch": args.max_batch, "workers": args.workers,
        "queue_capacity": 512,
        "deadline_ms": {"interactive": 80.0, "batch": 300.0,
                        "best_effort": 300.0},
    }
    cmp_r = traffic_replay.run_overload_comparison(pred, overload_spec)
    result["fifo_vs_slo"] = {
        "fifo_goodput": cmp_r["fifo"]["goodput"],
        "slo_goodput": cmp_r["slo"]["goodput"],
        "goodput_gain": cmp_r["goodput_gain"],
        "shed_before_batch_ok": cmp_r["slo"].get("shed_before_batch_ok"),
    }

    result["errors"] = len(errors) + hung
    if errors:
        result["first_error"] = errors[0]

    out = json.dumps(result, indent=2, sort_keys=True)
    print(out)
    if args.out:
        with open(args.out, "w") as f:
            f.write(out + "\n")
    if result["errors"]:
        sys.stderr.write("[serving_bench] FAILURES: requests errored or "
                         "hung\n")
        return 1
    if args.clients > 1 and snap["batch_occupancy"]["max"] <= 1:
        sys.stderr.write("[serving_bench] REGRESSION: concurrent clients "
                         "never coalesced into one batch\n")
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
