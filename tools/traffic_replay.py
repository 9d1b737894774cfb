#!/usr/bin/env python
"""Traffic-replay harness: prove the traffic tier under realistic load.

A serving stack is not proven by uniform closed-loop benches — real
traffic is bursty (Poisson with Markov-modulated burst states),
heavy-tailed (request sizes drawn from a lognormal), multi-tenant
(quota'd shares), and hostile (clients that stop reading mid-stream).
This tool replays exactly those shapes, from declarative scenario
specs, against the REAL stack (tiny MLP / tiny LM on CPU — the layer
under test is admission/scheduling, not the model), and gates the
properties ISSUE 10 promises:

  bursty_overload   SLO-aware scheduling beats the PR-3 FIFO on
                    deadline-goodput by >= 1.5x under overload, and
                    every shed request consumed ZERO batch slots
                    (engine_submitted + shed == offered, exactly).
  priority_mix      under saturating mixed load, interactive latency
                    HOLDS (p99 <= 3x its uncontended p99, or — where
                    GIL jitter stretches absolute tails — deadline
                    goodput >= 0.9), its sheds stay ~zero while
                    `best_effort` absorbs the shedding, and aging
                    keeps `batch` from starving (completions > 0).
  mixed_tenant      token-bucket quotas hold each tenant's admit rate
                    within 10% of its configured share under 2x
                    saturation.
  slow_client       a /v1/generate client that stops reading is
                    cancelled by the write-stall timeout: KV pages
                    freed BEFORE the generation would have finished,
                    decode work saved, batcher never stalled (a
                    healthy concurrent stream completes meanwhile).
                    ``shared_prefix: true`` in the spec runs the same
                    regression with the radix cache on and the healthy
                    client SHARING the stalled client's prefix — the
                    cancel must route through the refcounted release
                    and leave the sibling's pages intact.
  shared_prefix     the radix-cache gates: N tenants x M requests over
                    K common prefixes (heavy-tail suffixes). Warm TTFT
                    <= 0.3x cold TTFT, >= 2x peak resident sequences
                    at the same fixed page pool with sharing on vs
                    off, emitted tokens identical on-vs-off (greedy),
                    zero leaked pages after drain, and the
                    paddle_generation_radix_* gauge family populated.
  disagg            shared-prefix flood against a split prefill/decode
                    topology (paddle_tpu.disagg) vs a co-located
                    oracle: greedy tokens identical, every request
                    handed off with its KV pages streamed through the
                    page store, both tiers visible in phase health,
                    zero leaked pages after drain.
  mixed_adapter     N tenants x M LoRA adapters multiplexed through
                    ONE ragged engine (paddle_tpu.adapters): every
                    adapter's greedy output token-identical to a
                    dedicated single-adapter oracle engine, base rows
                    bitwise-stable alongside, then an upload/evict
                    churn loop (LRU evictions under a full pool) that
                    must leave ZERO leaked pool bytes and the
                    paddle_adapter_* gauge family populated.
  rolling_restart   WorkerPool.rolling_restart under live closed-loop
                    load: zero failed in-flight requests, replacement
                    workers warm-start from the persistent compile
                    cache (zero new cache entries — no recompile on
                    the hot signature).

--smoke runs every scenario at CI scale (~seconds each) and exits 1
on any gate failure; --scale N multiplies durations/rates toward the
millions-of-requests regime (the harness is open-loop and O(1) per
request, so scale is bounded by wall clock, not memory). Prints one
JSON object; --out FILE also writes it (CI uploads the artifact, so
the goodput trajectory accumulates per commit).
"""

from __future__ import annotations

import argparse
import json
import os
import re
import socket
import sys
import tempfile
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, HERE)

os.environ.setdefault("JAX_PLATFORMS", "cpu")


# -- arrival processes -------------------------------------------------------


class Arrivals:
    """Inter-arrival generator. Poisson at ``rate``; with
    ``burst_rate`` set, a 2-state Markov-modulated Poisson process:
    exponential holding times in a calm state (``rate``) and a burst
    state (``burst_rate``) — the bursty shape a diurnal + retry-storm
    front end actually sees."""

    def __init__(self, rng, rate: float, burst_rate: float = 0.0,
                 mean_calm_s: float = 1.0, mean_burst_s: float = 0.3):
        self.rng = rng
        self.rate = float(rate)
        self.burst_rate = float(burst_rate)
        self.mean_calm_s = mean_calm_s
        self.mean_burst_s = mean_burst_s
        self._in_burst = False
        self._state_left = rng.exponential(mean_calm_s)

    def next_gap(self) -> float:
        r = self.rate
        if self.burst_rate > 0:
            if self._state_left <= 0:
                self._in_burst = not self._in_burst
                self._state_left = self.rng.exponential(
                    self.mean_burst_s if self._in_burst else self.mean_calm_s)
            if self._in_burst:
                r = self.burst_rate
        gap = float(self.rng.exponential(1.0 / r))
        self._state_left -= gap
        return gap


# -- accounting --------------------------------------------------------------


class Tally:
    """Per-class offered/shed/good accounting for one replay leg."""

    def __init__(self):
        from paddle_tpu.serving.metrics import StreamingHistogram

        self.lock = threading.Lock()
        self.offered = {}
        self.shed = {}
        self.completed = {}
        self.good = {}
        self.lat = {}
        self.pending = 0
        self._hist_cls = StreamingHistogram

    def on_offer(self, cls):
        with self.lock:
            self.offered[cls] = self.offered.get(cls, 0) + 1
            self.pending += 1

    def on_shed(self, cls):
        with self.lock:
            self.shed[cls] = self.shed.get(cls, 0) + 1
            self.pending -= 1

    def on_done(self, cls, t0, deadline, err, shed=False):
        now = time.monotonic()
        with self.lock:
            if shed:
                self.shed[cls] = self.shed.get(cls, 0) + 1
            else:
                self.completed[cls] = self.completed.get(cls, 0) + 1
                if err is None and (deadline is None or now <= deadline):
                    self.good[cls] = self.good.get(cls, 0) + 1
                self.lat.setdefault(cls, self._hist_cls()).record(
                    (now - t0) * 1e3)
            self.pending -= 1

    def wait_drained(self, timeout: float) -> bool:
        t_end = time.monotonic() + timeout
        while time.monotonic() < t_end:
            with self.lock:
                if self.pending <= 0:
                    return True
            time.sleep(0.05)
        return False

    def snapshot(self):
        with self.lock:
            tot_off = sum(self.offered.values())
            tot_good = sum(self.good.values())
            return {
                "offered": dict(self.offered),
                "shed": dict(self.shed),
                "completed": dict(self.completed),
                "good": dict(self.good),
                "goodput": round(tot_good / tot_off, 4) if tot_off else 0.0,
                "latency_ms": {c: {k: h.snapshot()[k]
                                   for k in ("p50", "p99", "count")}
                               for c, h in self.lat.items()},
            }


def _make_plan(rng, spec, class_rates, buckets=(1, 2, 4, 8)):
    """Pregenerate every arrival (inter-arrival gap from the
    Poisson/MMPP process, class drawn by rate share, heavy-tail
    lognormal row count mapped onto the bucket ladder) OUTSIDE the
    timed loop — the driver must be O(1) per request or the harness
    measures its own RNG instead of the stack."""
    import numpy as np

    total = sum(class_rates.values())
    arr = Arrivals(rng, total, spec.get("burst_rate", 0.0))
    n = max(10, int(total * spec["duration_s"] * 1.5))
    gaps = [arr.next_gap() for _ in range(n)]
    classes = sorted(class_rates)
    weights = np.asarray([class_rates[c] / total for c in classes])
    idx = rng.choice(len(classes), size=n, p=weights)
    rows = np.clip(rng.lognormal(0.0, 0.8, size=n),
                   1, buckets[-1]).astype(int)
    pool = {b: np.asarray(rng.rand(b, 16), np.float32) for b in buckets}
    feeds = []
    for r in rows:
        b = next(b for b in buckets if r <= b)
        feeds.append(pool[b][:int(r)])
    return gaps, [classes[i] for i in idx], feeds


def _drive_plan(plan, duration_s, submit_one):
    """Open-loop arrival driver: submissions never block (futures +
    callbacks do the accounting), so offered load is independent of
    service capacity — the definition of an overload test."""
    gaps, classes, feeds = plan
    t_end = time.monotonic() + duration_s
    t_next = time.monotonic()
    i = 0
    while i < len(gaps):
        now = time.monotonic()
        if now >= t_end:
            break
        while t_next <= now and i < len(gaps):
            submit_one(classes[i], feeds[i])
            t_next += gaps[i]
            i += 1
        time.sleep(min(0.002, max(0.0, t_next - now)))
    return i


# -- model + stack -----------------------------------------------------------


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


def build_predict_stack(tmp_dir, max_batch=8, buckets=(1, 2, 4, 8)):
    """Tiny MLP predictor with batch bucketing, every bucket warmed
    (compiles outside any measured loop; warmup also populates the
    paddle_step_* quantiles the SLO estimator reads)."""
    import numpy as np

    import paddle_tpu as fluid
    from paddle_tpu.inference import Config, create_predictor

    model_dir = os.path.join(tmp_dir, "mlp")
    export_model(fluid, model_dir)
    cfg = Config(model_dir)
    cfg.enable_shape_bucketing(batch_buckets=tuple(buckets))
    pred = create_predictor(cfg)
    rng = np.random.RandomState(0)
    for b in buckets:
        pred.run([rng.rand(b, 16).astype("float32")])
    return model_dir, pred


def measure_capacity(pred, max_batch=8, workers=2, n=300):
    """Burst-drain throughput of a bare engine: the offered-rate
    anchor, so overload factors mean the same thing on a fast laptop
    and a loaded CI runner."""
    import numpy as np

    from paddle_tpu.serving import ServingEngine

    eng = ServingEngine(pred, max_batch_size=max_batch, batch_timeout_ms=2,
                        queue_capacity=max(512, n), num_workers=workers)
    x = np.zeros((1, 16), np.float32)
    t0 = time.monotonic()
    futs = [eng.submit({"x": x}) for _ in range(n)]
    for f in futs:
        f.result(timeout=120)
    rps = n / (time.monotonic() - t0)
    eng.close(drain=True)
    return rps


def measure_traffic_capacity(pred, max_batch=8, workers=2, n=400):
    """Burst-drain throughput THROUGH the traffic controller — the
    rate anchor for scenarios that stress the scheduling layer itself
    (the bare-engine number is 2-4x higher and would turn a
    'saturating' flood into a pure GIL-contention test)."""
    import numpy as np

    from paddle_tpu.serving import ServingEngine
    from paddle_tpu.traffic import TrafficConfig, TrafficController

    eng = ServingEngine(pred, max_batch_size=max_batch, batch_timeout_ms=2,
                        queue_capacity=max(512, n), num_workers=workers)
    ctl = TrafficController(eng, config=TrafficConfig.from_flags(
        queue_capacity=max(512, n)))
    x = np.zeros((1, 16), np.float32)
    t0 = time.monotonic()
    tickets = [ctl.submit({"x": x}) for _ in range(n)]
    for t in tickets:
        t.result(timeout=120)
    rps = n / (time.monotonic() - t0)
    ctl.close(drain=True)
    eng.close(drain=True)
    return rps


# -- scenario: FIFO vs SLO under bursty overload -----------------------------


def run_overload_comparison(pred, spec):
    """The headline gate: the same bursty, heavy-tail, deadline-bound
    overload through (a) the PR-3 bare-FIFO engine and (b) the
    traffic controller. Reports deadline-goodput both ways and the
    shed-before-batch invariant."""
    import numpy as np

    from paddle_tpu.serving import Overloaded, ServingEngine
    from paddle_tpu.traffic import (TrafficConfig, TrafficController,
                                    TrafficShed)

    results = {}
    deadlines = spec["deadline_ms"]
    buckets = spec.get("buckets", (1, 2, 4, 8))

    class_rates = {"interactive": spec["rate"] * 0.3,
                   "batch": spec["rate"] * 0.4,
                   "best_effort": spec["rate"] * 0.3}
    for leg in ("fifo", "slo"):
        rng = np.random.RandomState(spec.get("seed", 7))
        plan = _make_plan(rng, spec, class_rates, buckets)
        tally = Tally()
        engine = ServingEngine(
            pred, max_batch_size=spec["max_batch"], batch_timeout_ms=5,
            queue_capacity=spec["queue_capacity"],
            num_workers=spec["workers"])
        ctl = None
        if leg == "slo":
            ctl = TrafficController(engine, config=TrafficConfig.from_flags(
                queue_capacity=spec["queue_capacity"],
                aging_ms=spec.get("aging_ms", 200.0)))

        def submit_one(cls, feed, ctl=ctl, engine=engine, tally=tally):
            dl_ms = deadlines[cls]
            t0 = time.monotonic()
            deadline = t0 + dl_ms / 1e3
            tally.on_offer(cls)
            try:
                if ctl is not None:
                    t = ctl.submit({"x": feed}, tenant="replay",
                                   priority=cls, deadline_ms=dl_ms)
                else:
                    t = engine.submit({"x": feed}, deadline_ms=dl_ms)
            except (TrafficShed, Overloaded):
                tally.on_shed(cls)
                return
            t.add_done_callback(
                lambda fut, cls=cls, t0=t0, deadline=deadline:
                tally.on_done(cls, t0, deadline,
                              fut.exception(timeout=0),
                              shed=isinstance(fut.exception(timeout=0),
                                              TrafficShed)))

        offered = _drive_plan(plan, spec["duration_s"], submit_one)
        tally.wait_drained(spec["duration_s"] + 20)
        snap = engine.metrics.snapshot()
        r = tally.snapshot()
        r["offered_total"] = offered
        r["engine_submitted"] = snap["requests_total"]
        r["engine_batches"] = snap["batches_total"]
        if ctl is not None:
            r["traffic"] = {
                k: ctl.stats()[k]
                for k in ("shed", "deadline_miss_ratio", "drain_rate_rps",
                          "aged_total", "retry_after_last_s")}
            shed_total = sum(r["shed"].values())
            # the shed-before-batch invariant, exact: every offered
            # request either reached the engine or was shed — never both
            r["shed_before_batch_ok"] = (
                r["engine_submitted"] + shed_total == offered)
            ctl.close(drain=False)
        engine.close(drain=False, timeout=10)
        results[leg] = r

    fifo_good = results["fifo"]["goodput"]
    slo_good = results["slo"]["goodput"]
    results["goodput_gain"] = round(slo_good / fifo_good, 2) if fifo_good \
        else float("inf") if slo_good else 0.0
    return results


# -- scenario: priority semantics under saturation ---------------------------


def run_priority_mix(pred, spec):
    """The priority-semantics proof. Phase 1: interactive traffic
    alone at its normal rate (the UNCONTENDED p99 baseline). Phase 2:
    the SAME interactive rate plus a saturating flood of batch +
    best_effort on top. The contract: interactive latency holds
    (p99 <= 3x uncontended) and its sheds stay ~zero — the flood is
    absorbed by best_effort — while aging still feeds batch
    completions (no starvation under strict priority)."""
    import numpy as np

    from paddle_tpu.serving import ServingEngine
    from paddle_tpu.traffic import (TrafficConfig, TrafficController,
                                    TrafficShed)

    out = {}
    buckets = spec.get("buckets", (1, 2, 4, 8))
    for phase in ("uncontended", "overload"):
        rng = np.random.RandomState(spec.get("seed", 11))
        tally = Tally()
        engine = ServingEngine(
            pred, max_batch_size=spec["max_batch"], batch_timeout_ms=2,
            queue_capacity=spec["queue_capacity"],
            num_workers=spec["workers"])
        # HALF a batch per worker in flight: the engine's own FIFO
        # stays shallow, so a dispatched interactive request waits at
        # most about one batch-time behind lower-class work —
        # ordering decisions live in the traffic layer, not in a deep
        # engine queue (the latency/throughput knob a latency-tier
        # deployment turns)
        ctl = TrafficController(engine, config=TrafficConfig.from_flags(
            queue_capacity=spec["queue_capacity"],
            aging_ms=spec.get("aging_ms", 150.0),
            max_inflight=spec["max_batch"] * spec["workers"] // 2))
        rates = {"interactive": spec["interactive_rate"]}
        if phase == "overload":
            rates["batch"] = spec["batch_rate"]
            rates["best_effort"] = spec["best_effort_rate"]
        plan = _make_plan(
            rng, {"duration_s": spec["duration_s"],
                  "burst_rate": (spec.get("burst_rate", 0.0)
                                 if phase == "overload" else 0.0)},
            rates, buckets)

        def submit_one(cls, feed, ctl=ctl, tally=tally):
            dl_ms = spec["deadline_ms"][cls]
            t0 = time.monotonic()
            deadline = t0 + dl_ms / 1e3
            tally.on_offer(cls)
            try:
                t = ctl.submit({"x": feed}, tenant="replay", priority=cls,
                               deadline_ms=dl_ms)
            except TrafficShed:
                tally.on_shed(cls)
                return
            t.add_done_callback(
                lambda fut, cls=cls, t0=t0, deadline=deadline:
                tally.on_done(cls, t0, deadline,
                              fut.exception(timeout=0),
                              shed=isinstance(fut.exception(timeout=0),
                                              TrafficShed)))

        _drive_plan(plan, spec["duration_s"], submit_one)
        tally.wait_drained(spec["duration_s"] + 20)
        r = tally.snapshot()
        r["aged_total"] = ctl.stats()["aged_total"]
        ctl.close(drain=False)
        engine.close(drain=False, timeout=10)
        out[phase] = r

    unc = out["uncontended"]["latency_ms"].get("interactive", {})
    ovl = out["overload"]["latency_ms"].get("interactive", {})
    out["interactive_p99_uncontended_ms"] = unc.get("p99", 0.0)
    out["interactive_p99_overload_ms"] = ovl.get("p99", 0.0)
    # the baseline is floored at 15ms: on a contended CPU CI box the
    # uncontended p99 of a few hundred samples swings 5-60ms on
    # scheduler jitter alone, and a lucky 5ms baseline would fail the
    # 3x bound on noise, not on scheduling policy (a TPU deployment
    # replays at scale where the floor is irrelevant)
    out["interactive_p99_floor_ms"] = 15.0
    out["interactive_p99_ratio"] = (
        round(ovl["p99"] / max(unc["p99"], 15.0), 2)
        if unc.get("p99") and ovl.get("p99") else 0.0)
    # the operational form of the same promise: under the flood,
    # interactive requests still MEET THEIR DEADLINE (the latency gate
    # passes on either expression — the ratio on idle boxes, the
    # deadline-goodput wherever single-process GIL jitter stretches
    # absolute tails)
    ov = out["overload"]
    out["interactive_goodput"] = round(
        ov["good"].get("interactive", 0)
        / max(1, ov["offered"].get("interactive", 1)), 4)
    ov = out["overload"]
    out["interactive_shed_fraction"] = round(
        ov["shed"].get("interactive", 0)
        / max(1, ov["offered"].get("interactive", 1)), 4)
    out["best_effort_shed_fraction"] = round(
        ov["shed"].get("best_effort", 0)
        / max(1, ov["offered"].get("best_effort", 1)), 4)
    out["batch_completed"] = ov["completed"].get("batch", 0)
    return out


# -- scenario: tenant quotas -------------------------------------------------


def run_mixed_tenant(pred, spec):
    """Every tenant offers 2x its quota; admitted rates must land
    within 10% of the configured shares (token buckets, not luck)."""
    import numpy as np

    from paddle_tpu.serving import ServingEngine
    from paddle_tpu.traffic import (TenantSpec, TrafficConfig,
                                    TrafficController, TrafficShed)

    rng = np.random.RandomState(spec.get("seed", 23))
    tenants = spec["tenants"]            # name -> rate share (req/s)
    specs = {name: TenantSpec(name, rate=r, burst=max(1.0, r * 0.05))
             for name, r in tenants.items()}
    engine = ServingEngine(pred, max_batch_size=spec["max_batch"],
                           batch_timeout_ms=5,
                           queue_capacity=spec["queue_capacity"],
                           num_workers=spec["workers"])
    ctl = TrafficController(engine, config=TrafficConfig.from_flags(
        queue_capacity=spec["queue_capacity"], tenants=specs))
    tally = Tally()
    admitted = {name: 0 for name in tenants}
    # offered = 2x each tenant's quota: every tenant individually
    # saturates its own bucket (the plan's "classes" are the tenants)
    plan = _make_plan(rng, {"duration_s": spec["duration_s"]},
                      {n: 2.0 * r for n, r in tenants.items()},
                      spec.get("buckets", (1, 2, 4, 8)))

    def submit_one(tenant, feed):
        tally.on_offer(tenant)
        try:
            t = ctl.submit({"x": feed}, tenant=tenant, priority="batch")
        except TrafficShed:
            tally.on_shed(tenant)
            return
        admitted[tenant] += 1
        t.add_done_callback(lambda fut, tenant=tenant:
                            tally.on_done(tenant, time.monotonic(), None,
                                          fut.exception(timeout=0)))

    t0 = time.monotonic()
    _drive_plan(plan, spec["duration_s"], submit_one)
    elapsed = time.monotonic() - t0
    tally.wait_drained(spec["duration_s"] + 20)
    r = tally.snapshot()
    r["admit_rates"] = {}
    r["share_errors"] = {}
    for name in sorted(tenants):
        admit_rate = admitted[name] / elapsed
        r["admit_rates"][name] = round(admit_rate, 2)
        r["share_errors"][name] = round(
            abs(admit_rate - tenants[name]) / tenants[name], 4)
    r["max_share_error"] = max(r["share_errors"].values())
    ctl.close(drain=False)
    engine.close(drain=False, timeout=10)
    return r


# -- scenario: slow client over HTTP ----------------------------------------


def _lm_cfg():
    from paddle_tpu.generation.model import GPTConfig

    return GPTConfig(vocab_size=89, hidden_size=32, num_layers=2,
                     num_heads=4, ffn_size=64, max_position=1024,
                     hidden_dropout=0.0, attention_dropout=0.0)


def _build_lm_stack(tmp_dir, kv_dtype="float32", **gen_kw):
    import paddle_tpu as fluid
    from paddle_tpu.generation import GenerationEngine
    from paddle_tpu.generation.model import build_lm_program
    from paddle_tpu.inference import Config, create_predictor

    cfg = _lm_cfg()
    d = os.path.join(tmp_dir, "lm")
    if not os.path.isdir(d):
        main, startup, _feeds, fetches = build_lm_program(cfg, 32)
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            exe = fluid.Executor(fluid.TPUPlace())
            exe.run(startup)
            fluid.io.save_inference_model(d, ["tokens"],
                                          [fetches["logits"]], exe, main)
    pred = create_predictor(Config(d))
    kw = dict(page_size=16, num_pages=192, max_decode_batch=4,
              prefill_buckets=(16,), kv_dtype=kv_dtype, warmup=False)
    kw.update(gen_kw)
    gen = GenerationEngine(pred, cfg, **kw)
    return pred, gen


def run_slow_client(tmp_dir, spec):
    """One client streams /v1/generate and stops reading; one healthy
    client streams alongside. Gates: the stalled sequence is CANCELLED
    early (decode work saved, KV pages freed), and the healthy stream
    finishes normally — the batcher never stalled. ``spec["kv_dtype"]
    = "int8"`` runs the same regression over QUANTIZED pages — a
    stalled socket must free int8 pages + scale planes at the next
    step boundary exactly like fp32 ones. ``spec["shared_prefix"] =
    True`` turns the radix cache on and gives the healthy client the
    STALLED client's prompt prefix: the write-stall cancel must go
    through the refcounted release — the sibling keeps decoding over
    the shared pages, nothing leaks, and check_integrity stays
    green."""
    from paddle_tpu.serving import ServingEngine, ServingServer

    shared = bool(spec.get("shared_prefix"))
    pred, gen = _build_lm_stack(
        tmp_dir, kv_dtype=spec.get("kv_dtype", "float32"),
        **({"prefix_cache": True} if shared else {}))
    engine = ServingEngine(pred, num_workers=1)
    server = ServingServer(engine, generation_engine=gen,
                           stream_write_timeout_s=spec["stall_timeout_s"],
                           sndbuf=4096)
    max_new = spec["max_new_tokens"]
    result = {"max_new_tokens": max_new}
    # shared-prefix mode: both prompts open with the same two FULL
    # pages (page_size 16), so the healthy sibling attaches the
    # stalled client's published prefix by reference
    prefix = [(i % 83) + 1 for i in range(32)] if shared else []
    stall_prompt = prefix + [3, 5, 7]
    healthy_prompt = prefix + [2, 4] if shared else [2, 4]
    try:
        # stalled client: raw socket, tiny receive buffer, reads ~1KB
        # then stops forever
        s = socket.socket()
        s.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF, 2048)
        s.connect((server.host, server.port))
        body = json.dumps({"tokens": stall_prompt,
                           "max_new_tokens": max_new,
                           "stream": True}).encode()
        s.sendall(b"POST /v1/generate HTTP/1.1\r\n"
                  b"Host: x\r\nContent-Type: application/json\r\n"
                  + f"Content-Length: {len(body)}\r\n\r\n".encode() + body)
        s.recv(1024)   # headers + first tokens, then stall

        # healthy client in parallel (proves the engine loop and other
        # handler threads never stall behind the stuck writer)
        healthy_tokens = []

        def healthy():
            import http.client

            conn = http.client.HTTPConnection(server.host, server.port,
                                              timeout=60)
            b = json.dumps({"tokens": healthy_prompt, "max_new_tokens": 8,
                            "stream": False}).encode()
            conn.request("POST", "/v1/generate", b)
            resp = conn.getresponse()
            healthy_tokens.extend(json.loads(resp.read()).get("tokens", []))
            conn.close()

        if shared:
            # recv() above can return on headers alone, mid-prefill —
            # wait for the stalled sequence to publish BOTH prefix
            # pages so the sibling attaches the full shared run
            t_pub = time.monotonic() + 10
            while (time.monotonic() < t_pub
                   and gen.prefix_probe(healthy_prompt) < 32):
                time.sleep(0.01)
        ht = threading.Thread(target=healthy, daemon=True)
        ht.start()
        ht.join(60)

        # wait for the stall timeout to fire and the cancel to land
        t_end = time.monotonic() + spec["stall_timeout_s"] + 30
        while time.monotonic() < t_end:
            st = gen.stats()
            if st["cancelled_total"] >= 1 and st["cache"]["active_seqs"] == 0:
                break
            time.sleep(0.1)
        st = gen.stats()
        if shared:
            # the sibling-intact proof: sharing actually engaged, the
            # refcounted release left the trie/refcounts coherent, and
            # flushing the trie accounts for every page
            result["prefix_hit_tokens"] = st["radix"][
                "prefix_hit_tokens_total"]
            gen.cache.check_integrity()
            gen.cache.drop_trie()
            gen.cache.check_integrity()
            st = gen.stats()
        result.update({
            "cancelled_total": st["cancelled_total"],
            "active_seqs_after": st["cache"]["active_seqs"],
            "pages_in_use_after": st["cache"]["pages_in_use"],
            "tokens_decoded": st["decode_tokens_total"],
            "healthy_tokens": len(healthy_tokens),
            # early cancel = decode work SAVED vs letting it run out
            "decode_saved_fraction": round(
                1.0 - st["decode_tokens_total"] / max(1, max_new), 4),
        })
        s.close()
    finally:
        server.close()
        gen.close(drain=False)
        engine.close(drain=False)
    result["ok"] = (result.get("cancelled_total", 0) >= 1
                    and result.get("active_seqs_after", 1) == 0
                    and result.get("pages_in_use_after", 1) == 0
                    and result.get("healthy_tokens", 0) > 0
                    and result.get("tokens_decoded", max_new) < max_new
                    and (not shared
                         or result.get("prefix_hit_tokens", 0) >= 32))
    return result


# -- scenario: shared-prefix fleet (radix KV cache) --------------------------


def run_shared_prefix(tmp_dir, spec):
    """N tenants x M requests over K common prompt prefixes with
    heavy-tail suffixes — the system-prompt fleet. Radix cache ON must
    (1) serve warm requests with TTFT <= 0.3x a cold prefill of the
    same prompt (only the unmatched suffix prefills), (2) hold >= 2x
    the concurrently-resident sequences of the OFF engine at the SAME
    page pool (shared prefix pages are charged once), (3) emit
    token-identical greedy output to a cold engine, and (4) leak zero
    pages after drain + trie flush, with ``check_integrity`` green."""
    import random
    import statistics

    ps = 16
    pref_len = int(spec.get("prefix_tokens", 128))
    max_new = int(spec.get("max_new_tokens", 16))
    geom = dict(page_size=ps,
                num_pages=int(spec.get("num_pages", 34)),
                max_decode_batch=int(spec.get("max_decode_batch", 8)))
    rng = random.Random(1234)

    def make_prefix(k):
        return [(i * 7 + k * 13) % 83 + 1 for i in range(pref_len)]

    def make_suffix():
        # heavy tail: mostly a couple of tokens, the odd long one
        n = rng.choice([2, 2, 3, 3, 4, 4, 5, 6, 14])
        return [rng.randrange(1, 84) for _ in range(n)]

    def timed(gen, prompt):
        t0 = time.monotonic()
        stream = gen.submit(prompt, max_new, eos_id=None)
        toks = stream.result(300)
        return (stream.first_token_at - t0) * 1e3, toks

    def burst(gen, prompts):
        # peak concurrently-RESIDENT sequences (admitted, holding KV
        # pages), sampled while the whole fleet is in flight
        peak = [0]
        stop = threading.Event()

        def sampler():
            while not stop.is_set():
                peak[0] = max(peak[0],
                              gen.stats()["cache"]["active_seqs"])
                time.sleep(0.002)

        th = threading.Thread(target=sampler, daemon=True)
        th.start()
        streams = [gen.submit(p, max_new, eos_id=None) for p in prompts]
        toks = [s.result(300) for s in streams]
        stop.set()
        th.join(5)
        return peak[0], toks

    # -- radix ON -------------------------------------------------------------
    pred_on, gen_on = _build_lm_stack(tmp_dir, prefix_cache=True, **geom)
    ttft_pairs = []
    try:
        # absorb the one-time executable compile OFF the clock, then
        # flush the throwaway's published pages
        gen_on.generate(make_prefix(999) + [1, 2], 2, eos_id=None,
                        timeout=300)
        gen_on.cache.drop_trie()

        # TTFT: per fresh prefix, one COLD request publishes it, then
        # warm siblings prefill only their suffix. Trie flushed
        # between prefixes so every cold sample is truly cold.
        colds, warms = [], []
        for k in range(int(spec.get("ttft_prefixes", 3))):
            pre = make_prefix(100 + k)
            for i in range(1 + int(spec.get("warm_per_prefix", 2))):
                prompt = pre + make_suffix()
                ms, toks = timed(gen_on, prompt)
                (colds if i == 0 else warms).append(ms)
                ttft_pairs.append((prompt, toks))
            gen_on.cache.drop_trie()

        # resident-fleet burst: K prefixes x (tenants x M) requests,
        # interleaved like independent tenants would arrive
        burst_prompts = [make_prefix(k) + make_suffix()
                         for k in range(int(spec.get("num_prefixes", 2)))
                         for _ in range(int(spec.get("tenants", 4))
                                        * int(spec.get(
                                            "requests_per_tenant", 2)))]
        rng.shuffle(burst_prompts)
        peak_on, toks_on = burst(gen_on, burst_prompts)

        radix = gen_on.stats()["radix"]
        gen_on.cache.check_integrity()
        gen_on.cache.drop_trie()
        gen_on.cache.check_integrity()
        pages_after_on = gen_on.stats()["cache"]["pages_in_use"]
    finally:
        gen_on.close(drain=False)

    # -- radix OFF: same pool, same prompts -----------------------------------
    pred_off, gen_off = _build_lm_stack(tmp_dir, **geom)
    try:
        identical = all(
            list(gen_off.generate(p, max_new, eos_id=None, timeout=300))
            == list(t) for p, t in ttft_pairs)
        peak_off, toks_off = burst(gen_off, burst_prompts)
        identical = identical and all(
            list(a) == list(b) for a, b in zip(toks_on, toks_off))
        pages_after_off = gen_off.stats()["cache"]["pages_in_use"]
    finally:
        gen_off.close(drain=False)

    cold_ms = statistics.median(colds)
    warm_ms = statistics.median(warms)
    return {
        "prefix_tokens": pref_len, "max_new_tokens": max_new,
        "usable_pages": geom["num_pages"] - 1,
        "requests_burst": len(burst_prompts),
        "cold_ttft_ms": round(cold_ms, 2),
        "warm_ttft_ms": round(warm_ms, 2),
        "warm_over_cold": round(warm_ms / max(cold_ms, 1e-9), 4),
        "peak_resident_on": peak_on,
        "peak_resident_off": peak_off,
        "tokens_identical": bool(identical),
        "prefix_hit_tokens": radix["prefix_hit_tokens_total"],
        "prefix_hits": radix["prefix_hits_total"],
        "prefix_lookups": radix["prefix_lookups_total"],
        "hit_rate": radix["prefix_hit_rate"],
        "cow_forks": radix["cow_forks_total"],
        "leaf_evictions": radix["leaf_evictions_total"],
        "pages_in_use_after_on": pages_after_on,
        "pages_in_use_after_off": pages_after_off,
    }


# -- scenario: disaggregated prefill/decode ----------------------------------


# a minimal stdlib metrics stub: a SEPARATE python process serving a
# fixed /metrics exposition — stands in for a remote worker so the
# fleet-merge gate covers real multi-process scraping without paying
# three jax imports
_METRICS_STUB = r"""
import sys
from http.server import BaseHTTPRequestHandler, HTTPServer

text = sys.argv[1].encode()


class H(BaseHTTPRequestHandler):
    def do_GET(self):
        body = text if self.path == "/metrics" else b"{}"
        self.send_response(200 if self.path == "/metrics" else 404)
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def log_message(self, *a):
        pass


srv = HTTPServer(("127.0.0.1", 0), H)
print(srv.server_address[1], flush=True)
srv.serve_forever()
"""


def _spawn_metrics_stub(text):
    import subprocess

    proc = subprocess.Popen([sys.executable, "-c", _METRICS_STUB, text],
                            stdout=subprocess.PIPE, text=True)
    port = int(proc.stdout.readline())
    return proc, port


def run_disagg(tmp_dir, spec):
    """Shared-prefix flood replayed against a split prefill/decode
    topology (paddle_tpu.disagg) over a REAL TCP page-store wire
    (PageStoreServer + one PageStoreClient per worker), with a
    co-located engine as the token-identity oracle and fleet
    observability wired end to end. Gates: (1) every split request
    emits greedy tokens IDENTICAL to the co-located engine's, (2)
    every request went through a handoff and its pages shipped over
    the store (handoffs == requests, pages pulled > 0), (3) the phase
    health fragment exposes both tiers, (4) drain leaves zero pages on
    every engine with ``check_integrity`` green, (5) one traced HTTP
    /v1/generate yields ONE connected trace (zero orphan spans)
    covering the router hop, the disagg handoff (prefill + decode
    phases) and the page-store wire — assembled via
    ``/v1/admin/trace/<id>`` and renderable with process lanes, and
    (6) ``/metrics/fleet`` merges the router plus >=3 live worker
    processes with ``{worker=,phase=}`` labels and exports
    ``paddle_slo_*`` gauges."""
    import random
    import urllib.request

    import paddle_tpu as fluid
    from paddle_tpu.disagg import (DecodeWorker, DisaggService,
                                   PrefillWorker)
    from paddle_tpu.disagg.pagestore import (PageStoreClient,
                                             PageStoreServer)
    from paddle_tpu.inference import Config, create_predictor
    from paddle_tpu.observability import (FleetAggregator, SLOMonitor,
                                          assemble_trace, propagate,
                                          tracing)
    from paddle_tpu.serving import ServingEngine, ServingServer
    from paddle_tpu.tools_timeline import to_chrome_trace

    cfg = _lm_cfg()
    pref_len = int(spec.get("prefix_tokens", 64))
    max_new = int(spec.get("max_new_tokens", 12))
    rng = random.Random(4321)
    prompts = []
    for k in range(int(spec.get("num_prefixes", 2))):
        pre = [(i * 7 + k * 13) % 83 + 1 for i in range(pref_len)]
        for _ in range(int(spec.get("requests_per_prefix", 4))):
            n = rng.choice([2, 3, 3, 4, 5, 9])
            prompts.append(pre + [rng.randrange(1, 84) for _ in range(n)])
    rng.shuffle(prompts)

    # co-located oracle: one engine does prefill AND decode
    pred, gen = _build_lm_stack(tmp_dir, prefix_cache=True)
    try:
        oracle = [list(gen.generate(p, max_new, eos_id=None, timeout=300))
                  for p in prompts]
    finally:
        gen.close(drain=False)

    fluid.set_flags({"observability_tracing": True,
                     "observability_flight_capacity": 4096})
    # split topology: one prefill worker + one decode worker, each
    # with its OWN client connection to a TCP page-store server — the
    # trace-context field in the wire framing is exercised for real
    d = os.path.join(tmp_dir, "lm")
    store_srv = PageStoreServer(page_size=16)
    kw = dict(page_size=16, num_pages=192, max_decode_batch=4,
              chunk_tokens=16, warmup=False)
    pf = PrefillWorker(
        create_predictor(Config(d)), cfg,
        PageStoreClient(store_srv.host, store_srv.port, page_size=16),
        **kw)
    dw = DecodeWorker(
        create_predictor(Config(d)), cfg,
        PageStoreClient(store_srv.host, store_srv.port, page_size=16),
        **kw)
    svc = DisaggService(prefill=[pf], decode=[dw])
    stubs = []
    server = eng = None
    try:
        streams = [svc.submit(p, max_new_tokens=max_new, eos_id=None)
                   for p in prompts]
        toks = [list(s.result(timeout=300)) for s in streams]

        # -- cross-process trace: one traced HTTP request ------------------
        eng = ServingEngine(pred, num_workers=1)
        server = ServingServer(eng, generation_engine=svc)
        client_ctx = tracing.SpanContext(tracing._new_id(),
                                         tracing._new_id())
        req = urllib.request.Request(
            f"{server.address}/v1/generate",
            data=json.dumps({"tokens": prompts[0],
                             "max_new_tokens": max_new,
                             "eos_id": None, "stream": True}).encode(),
            headers={"Content-Type": "application/json",
                     **propagate.inject(client_ctx)})
        with urllib.request.urlopen(req, timeout=300) as resp:
            lines = [json.loads(ln) for ln in resp if ln.strip()]
        http_toks = [ln["token"] for ln in lines if "token" in ln]
        first, tail = lines[0], lines[-1]
        trace_echoed = (first.get("trace_id") == client_ctx.trace_id
                        and tail.get("trace_id") == client_ctx.trace_id)
        assembled = assemble_trace(client_ctx.trace_id, [server.address])
        span_names = {s.get("name") for s in assembled["spans"]}
        orphans = propagate.orphan_spans(
            assembled["spans"], known_parents=(client_ctx.span_id,))
        chrome = to_chrome_trace([
            {"name": s["name"], "ts": s["ts"], "dur": s["dur"],
             "tid": s.get("tid", 0), "pid": s.get("pid", 0),
             "args": {k: v for k, v in s.items()
                      if k not in ("kind", "t", "name", "ts", "dur",
                                   "tid", "pid")}}
            for s in assembled["spans"]])
        lanes = {e.get("pid") for e in chrome["traceEvents"]
                 if e.get("ph") == "X"}
        arrows = sum(1 for e in chrome["traceEvents"]
                     if e.get("ph") == "s")

        # -- fleet merge: router + 3 REAL worker processes ------------------
        for i, (worker, phase) in enumerate(
                (("prefill-0", "prefill"), ("decode-0", "decode"),
                 ("decode-1", "decode"))):
            text = (
                f'paddle_traffic_completed_total{{cls="interactive"}} '
                f'{100 + i}\n'
                f'paddle_traffic_deadline_miss_total{{cls="interactive"}} '
                f'{i}\n'
                f'paddle_generation_ttft_ms_p99 {40.0 + i}\n')
            proc, port = _spawn_metrics_stub(text)
            stubs.append((proc, port, worker, phase))
        agg = FleetAggregator(slo=SLOMonitor(), timeout_s=2.0)
        agg.add_endpoint(server.address, worker="router", phase="disagg")
        for _proc, port, worker, phase in stubs:
            agg.add_endpoint(f"http://127.0.0.1:{port}", worker=worker,
                             phase=phase)
        server._httpd.RequestHandlerClass.fleet = agg
        agg.scrape()   # two scrapes: the SLO window needs two samples
        with urllib.request.urlopen(f"{server.address}/metrics/fleet",
                                    timeout=30) as r:
            fleet_text = r.read().decode()
        fleet_workers = {m.group(1) for m in re.finditer(
            r'worker="([^"]+)"', fleet_text)}
        m = re.search(r"^paddle_fleet_live (\d+)", fleet_text, re.M)
        live = int(m.group(1)) if m else 0

        stats = svc.stats_numeric()
        phases = {h["phase"] for h in svc.phase_health()}
    finally:
        fluid.set_flags({"observability_tracing": False,
                         "observability_flight_capacity": 512})
        if server is not None:
            server.close()
        if eng is not None:
            eng.close()
        svc.close(drain=True)
        store_srv.close()
        for proc, *_rest in stubs:
            proc.terminate()
    leaked = 0
    for w in svc._prefill + svc._decode:
        w.engine.cache.check_integrity()
        leaked += int(w.engine.stats()["cache"]["pages_in_use"])

    identical = all(a == b for a, b in zip(toks, oracle))
    return {
        "requests": len(prompts),
        "prefix_tokens": pref_len,
        "max_new_tokens": max_new,
        "tokens_identical": bool(identical),
        "http_tokens_identical": bool(http_toks == oracle[0]),
        "handoffs": int(stats["handoffs_total"]),
        "handoff_failures": int(stats["handoff_failures_total"]),
        "pages_shipped": int(stats["pages_shipped_total"]),
        "pages_pulled": int(stats["pages_pulled_total"]),
        "store_hit_rate": stats["store_hit_rate"],
        "wire_ratio": stats.get("wire_ratio", 0.0),
        "phases": sorted(phases),
        "leaked_pages": leaked,
        # trace completeness (acceptance: ONE connected trace spanning
        # router -> handoff -> page-store wire -> decode)
        "trace_id_echoed": bool(trace_echoed),
        "trace_spans": len(assembled["spans"]),
        "trace_span_names": sorted(span_names),
        "trace_orphans": len(orphans),
        "trace_roles_covered": bool(
            {"serving/http_generate", "disagg/handoff",
             "disagg/prefill_phase",
             "disagg/decode_submit"} <= span_names
            and any(n.startswith("pagestore/") for n in span_names)),
        "timeline_lanes": len(lanes),
        "timeline_flow_arrows": int(arrows),
        # fleet merge (acceptance: >=3 live processes, worker/phase
        # labels, paddle_slo_* gauges)
        "fleet_workers": sorted(fleet_workers),
        "fleet_processes_merged": 1 + len(stubs),
        "fleet_live": int(live),
        "fleet_has_slo_gauges": "paddle_slo_error_budget_burn"
                                in fleet_text,
        "fleet_has_phase_labels": 'phase="prefill"' in fleet_text
                                  and 'phase="decode"' in fleet_text,
    }


# -- scenario: multi-adapter multiplexing ------------------------------------


def run_mixed_adapter(tmp_dir, spec):
    """N tenants x M LoRA adapters through ONE ragged engine. Gates:
    (1) every adapter's greedy output in the MIXED batch is
    token-identical to a dedicated single-adapter oracle engine (same
    checkpoint, only that adapter resident), (2) base-only rows served
    alongside are identical to a no-adapter engine, (3) an
    upload/evict churn loop over a deliberately small pool (LRU
    evictions engaged) leaves zero leaked pool bytes, and (4) the
    paddle_adapter_* gauge family is populated."""
    import numpy as np

    import paddle_tpu as fluid

    n_adapters = int(spec.get("adapters", 8))
    max_new = int(spec.get("max_new_tokens", 6))
    rng = np.random.RandomState(spec.get("seed", 5))
    prompt = [int(t) for t in rng.randint(1, 84, 10)]

    def with_store(slots):
        fluid.set_flags({"adapter_pool_max_bytes": 1,
                         "adapter_slots_per_bucket": int(slots)})
        try:
            return _build_lm_stack(tmp_dir, max_decode_batch=n_adapters + 1,
                                   chunk_tokens=16)
        finally:
            fluid.set_flags({"adapter_pool_max_bytes": 0,
                             "adapter_slots_per_bucket": 0})

    # base oracle: plain engine, no adapters
    _pred0, gen0 = _build_lm_stack(tmp_dir, max_decode_batch=n_adapters + 1,
                                   chunk_tokens=16)
    try:
        base_tokens = list(gen0.generate(prompt, max_new, eos_id=None,
                                         timeout=300))
    finally:
        gen0.close(drain=False)

    _pred, gen = with_store(slots=n_adapters + 2)
    result = {"adapters": n_adapters, "max_new_tokens": max_new}
    try:
        store = gen.adapter_store
        targets = sorted(store.targets)
        factors = {}
        for i in range(n_adapters):
            r = 8 if i % 2 == 0 else 16
            fac = {}
            for t in targets[: 1 + (i % 3)]:
                K, N = store.targets[t]
                fac[t] = (rng.randn(K, r).astype(np.float32) * 0.05,
                          rng.randn(r, N).astype(np.float32) * 0.05)
            factors[f"ad{i}"] = (fac, 2.0 * r)
            store.upload(f"ad{i}", fac, alpha=2.0 * r,
                         tenant=f"tenant{i % max(1, spec.get('tenants', 3))}")

        # the mixed micro-batch: every adapter + one base row at once
        streams = [gen.submit(prompt, max_new, eos_id=None,
                              adapter=f"ad{i}") for i in range(n_adapters)]
        streams.append(gen.submit(prompt, max_new, eos_id=None))
        mixed = [list(s.result(300)) for s in streams]
        result["base_row_identical"] = mixed[-1] == base_tokens
        result["adapters_diverge_from_base"] = sum(
            mixed[i] != base_tokens for i in range(n_adapters))

        # per-adapter oracle: dedicated engine, ONLY that adapter
        identical = True
        for i in range(n_adapters):
            _po, oracle = with_store(slots=3)
            try:
                fac, alpha = factors[f"ad{i}"]
                oracle.adapter_store.upload(f"ad{i}", fac, alpha=alpha)
                solo = list(oracle.generate(prompt, max_new, eos_id=None,
                                            adapter=f"ad{i}", timeout=300))
            finally:
                oracle.close(drain=False)
            if solo != mixed[i]:
                identical = False
        result["tokens_identical"] = identical

        # churn: a pool with room for 2 adapters per bucket cycles
        # through 3x that many uploads — LRU evictions must engage and
        # every byte must come back
        churn_rounds = int(spec.get("churn_rounds", 8))
        for j in range(churn_rounds):
            fac = {targets[0]: (rng.randn(*(
                store.targets[targets[0]][0], 8)).astype(np.float32) * 0.05,
                rng.randn(8, store.targets[targets[0]][1]).astype(
                    np.float32) * 0.05)}
            store.upload(f"churn{j}", fac)
            gen.generate(prompt, 2, eos_id=None, adapter=f"churn{j}",
                         timeout=300)
        stats = store.stats_numeric()
        for row in store.resident():
            store.evict(row["id"])
        result.update({
            "uploads_total": int(stats["uploads_total"]),
            "lru_evictions_total": int(stats["lru_evictions_total"]),
            "leaked_pool_bytes": int(store.used_bytes()),
            "gauges_populated": stats["uploads_total"] >= n_adapters,
        })
    finally:
        gen.close(drain=False)
    return result


# -- scenario: rolling restart under live load -------------------------------


def run_rolling_restart(tmp_dir, model_dir, spec):
    """WorkerPool under closed-loop load while every worker is
    replaced. Gates: zero failed in-flight requests (connect retries
    are allowed — that is normal LB behavior; an ACCEPTED request must
    never fail), and replacement workers add zero persistent-cache
    entries (warm start, no recompile)."""
    import http.client

    import numpy as np

    from paddle_tpu.traffic import WorkerPool

    pool = WorkerPool(
        model_dir, num_workers=spec["workers"], batch_buckets=[1, 4],
        warmup_shapes={"x": [1, 16]},
        engine_kwargs={"max_batch_size": 4, "batch_timeout_ms": 2,
                       "num_workers": 1},
        use_reuseport=spec.get("use_reuseport"))
    cache_dir = pool.workers[0].info["persistent_cache_dir"]
    x = np.zeros((1, 16), np.float32).tolist()
    body = json.dumps({"inputs": {"x": x}}).encode()
    stop = threading.Event()
    counts = {"ok": 0, "shed": 0, "failed": 0, "connect_retry": 0}
    lock = threading.Lock()

    def client():
        while not stop.is_set():
            # fresh connection per request (Connection: close): the
            # accepted-request failure accounting stays exact. A
            # connection the kernel accepted into a closing listener's
            # backlog dies with NO response bytes — that is the
            # connection-level race every load balancer retries
            # (idempotent request, no response started), NOT a dropped
            # in-flight request; it retries here and is counted. A
            # request whose RESPONSE was severed mid-body is the real
            # failure the drain protocol must never produce.
            status = None
            for _attempt in range(5):
                conn = http.client.HTTPConnection(
                    pool.host, pool.port, timeout=30)
                try:
                    conn.request("POST", "/v1/predict", body,
                                 {"Connection": "close"})
                    resp = conn.getresponse()
                except (http.client.BadStatusLine, ConnectionError,
                        socket.timeout, OSError):
                    # no status line ever arrived: safe retry
                    conn.close()
                    with lock:
                        counts["connect_retry"] += 1
                    time.sleep(0.02)
                    continue
                try:
                    resp.read()
                    status = resp.status
                except Exception:  # noqa: BLE001 — severed MID-response
                    status = -1
                conn.close()
                break
            with lock:
                if status == 200:
                    counts["ok"] += 1
                elif status in (503, 429):
                    counts["shed"] += 1
                else:
                    counts["failed"] += 1

    threads = [threading.Thread(target=client, daemon=True)
               for _ in range(spec["clients"])]
    result = {}
    try:
        for t in threads:
            t.start()
        time.sleep(1.0)                       # steady state before restart
        files_before = len(os.listdir(cache_dir))
        t0 = time.monotonic()
        report = pool.rolling_restart()
        restart_s = time.monotonic() - t0
        time.sleep(1.0)                       # steady state after
        files_after = len(os.listdir(cache_dir))
        stop.set()
        for t in threads:
            t.join(30)
        cold = [i["warmup_ms"] for i in report["cold"]]
        warm = [i["warmup_ms"] for i in report["replacements"]]
        result = {
            "counts": counts,
            "restart_s": round(restart_s, 2),
            "cold_warmup_ms": cold,
            "warm_warmup_ms": warm,
            "warm_ratio": round(sum(warm) / sum(cold), 3) if sum(cold) else 0,
            "cache_entries_before": files_before,
            "cache_entries_after": files_after,
            "drained": report["drained"],
            "reuseport": pool.use_reuseport,
        }
        result["ok"] = (counts["failed"] == 0 and counts["ok"] > 0
                        and files_after == files_before)
    finally:
        stop.set()
        pool.close()
    return result


# -- main --------------------------------------------------------------------


def main():
    ap = argparse.ArgumentParser(
        description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--smoke", action="store_true", help="CI scale + gates")
    ap.add_argument("--scale", type=float, default=1.0,
                    help="multiply durations/rates (toward the "
                         "millions-of-requests regime)")
    ap.add_argument("--scenario", default="all",
                    choices=["all", "bursty_overload", "priority_mix",
                             "mixed_tenant", "slow_client",
                             "shared_prefix", "disagg", "mixed_adapter",
                             "rolling_restart"])
    ap.add_argument("--out", default=None, help="also write JSON here")
    args = ap.parse_args()

    tmp = tempfile.mkdtemp(prefix="pt_traffic_replay_")
    result = {"smoke": bool(args.smoke), "scale": args.scale}
    gates = {}

    need_pred = args.scenario in ("all", "bursty_overload", "priority_mix",
                                  "mixed_tenant", "rolling_restart")
    model_dir = pred = None
    capacity = 0.0
    if need_pred:
        model_dir, pred = build_predict_stack(tmp)
        capacity = measure_capacity(pred)
        result["capacity_rps"] = round(capacity, 1)

    dur = (3.0 if args.smoke else 10.0) * args.scale

    if args.scenario in ("all", "bursty_overload"):
        spec = {
            "rate": capacity * 2.0, "burst_rate": capacity * 6.0,
            "duration_s": dur, "max_batch": 8, "workers": 2,
            "queue_capacity": 512,
            "deadline_ms": {"interactive": 80.0, "batch": 300.0,
                            "best_effort": 300.0},
        }
        result["bursty_overload"] = run_overload_comparison(pred, spec)
        r = result["bursty_overload"]
        gates["goodput_gain_ge_1.5"] = r["goodput_gain"] >= 1.5
        gates["shed_before_batch"] = bool(
            r["slo"].get("shed_before_batch_ok"))

    if args.scenario in ("all", "priority_mix"):
        tcap = measure_traffic_capacity(pred)
        result["traffic_capacity_rps"] = round(tcap, 1)
        spec = {
            # interactive runs at the SAME modest rate in both phases
            # (it is the tenant whose latency the SLO protects); the
            # overload phase floods batch + best_effort ON TOP until
            # the TRAFFIC LAYER saturates (anchored on through-the-
            # controller capacity — anchoring on the bare engine's
            # burst rate would just measure GIL contention from the
            # submission spam, not the scheduler under test)
            "interactive_rate": 250.0,
            "batch_rate": min(tcap * 0.8, 2000.0),
            "best_effort_rate": min(tcap * 1.2, 3000.0),
            "burst_rate": min(tcap * 3.0, 8000.0),
            "duration_s": dur, "max_batch": 8, "workers": 2,
            "queue_capacity": 256, "aging_ms": 150.0,
            "deadline_ms": {"interactive": 100.0, "batch": 1000.0,
                            "best_effort": 500.0},
        }
        def _priority_gates(r):
            return {
                "interactive_latency_holds": (
                    0 < r["interactive_p99_ratio"] <= 3.0
                    or r["interactive_goodput"] >= 0.9),
                "interactive_sheds_near_zero":
                    r["interactive_shed_fraction"] <= 0.10,
                "best_effort_absorbs_shedding":
                    r["best_effort_shed_fraction"]
                    >= max(0.2, r["interactive_shed_fraction"]),
                "batch_not_starved": r["batch_completed"] > 0,
            }

        result["priority_mix"] = run_priority_mix(pred, spec)
        g = _priority_gates(result["priority_mix"])
        if not all(g.values()):
            # latency-bound gates on a shared CPU runner: one retry
            # absorbs a noisy-neighbor window (both attempts reported)
            result["priority_mix_first_attempt"] = result["priority_mix"]
            result["priority_mix"] = run_priority_mix(pred, spec)
            g = _priority_gates(result["priority_mix"])
        gates.update(g)

    if args.scenario in ("all", "mixed_tenant"):
        # quotas sum WELL below system throughput: the property under
        # test is that the token buckets hold each tenant to its
        # configured share when the tenant itself over-offers (2x) —
        # not downstream backpressure (bursty_overload covers that)
        spec = {
            "duration_s": dur, "max_batch": 8, "workers": 2,
            "queue_capacity": 512,
            "tenants": {"alice": 200.0, "bob": 100.0, "carol": 50.0},
        }
        result["mixed_tenant"] = run_mixed_tenant(pred, spec)
        gates["tenant_shares_within_10pct"] = (
            result["mixed_tenant"]["max_share_error"] <= 0.10)

    if args.scenario in ("all", "slow_client"):
        spec = {"stall_timeout_s": 0.8, "max_new_tokens": 900}
        result["slow_client"] = run_slow_client(tmp, spec)
        gates["slow_client_cancelled_and_freed"] = bool(
            result["slow_client"]["ok"])

    if args.scenario in ("all", "shared_prefix"):
        spec = {
            "prefix_tokens": 128, "num_prefixes": 2, "tenants": 4,
            "requests_per_tenant": 2, "max_new_tokens": 16,
            "num_pages": 34, "max_decode_batch": 8,
            "ttft_prefixes": 3, "warm_per_prefix": 2,
        }
        result["shared_prefix"] = run_shared_prefix(tmp, spec)
        r = result["shared_prefix"]
        gates["radix_warm_ttft_le_0.3x_cold"] = r["warm_over_cold"] <= 0.3
        gates["radix_resident_ge_2x_on_vs_off"] = (
            r["peak_resident_off"] > 0
            and r["peak_resident_on"] >= 2 * r["peak_resident_off"])
        gates["radix_tokens_identical_on_vs_off"] = bool(
            r["tokens_identical"])
        gates["radix_gauges_populated"] = (
            r["prefix_hits"] > 0
            and r["prefix_hit_tokens"] >= spec["prefix_tokens"])
        gates["radix_zero_leaked_pages"] = (
            r["pages_in_use_after_on"] == 0
            and r["pages_in_use_after_off"] == 0)
        # cancel-under-sharing: a stalled sibling's write-timeout
        # cancel goes through the refcounted release — the healthy
        # sibling decoding over the SAME prefix pages is untouched
        result["slow_client_shared"] = run_slow_client(
            tmp, {"stall_timeout_s": 0.8, "max_new_tokens": 900,
                  "shared_prefix": True})
        gates["slow_client_shared_sibling_intact"] = bool(
            result["slow_client_shared"]["ok"])

    if args.scenario in ("all", "disagg"):
        spec = {
            "prefix_tokens": 64, "num_prefixes": 2,
            "requests_per_prefix": 4, "max_new_tokens": 12,
        }
        result["disagg"] = run_disagg(tmp, spec)
        r = result["disagg"]
        gates["disagg_tokens_identical"] = bool(r["tokens_identical"])
        # the flood plus the one traced HTTP request each hand off
        gates["disagg_every_request_handed_off"] = (
            r["handoffs"] == r["requests"] + 1
            and r["handoff_failures"] == 0)
        gates["disagg_pages_streamed"] = (
            r["pages_shipped"] > 0 and r["pages_pulled"] > 0)
        gates["disagg_phases_exposed"] = (
            r["phases"] == ["decode", "prefill"])
        gates["disagg_zero_leaked_pages"] = r["leaked_pages"] == 0
        # ONE connected trace spans router -> handoff -> prefill ->
        # page-store wire -> decode submit, the trace id is echoed on
        # the stream, and the timeline renders with flow arrows
        gates["disagg_trace_connected"] = (
            r["trace_id_echoed"] and r["http_tokens_identical"]
            and r["trace_orphans"] == 0 and r["trace_roles_covered"]
            and r["timeline_flow_arrows"] > 0)
        # /metrics/fleet on the router merges >=3 live worker
        # processes with worker/phase labels + paddle_slo_* gauges
        gates["disagg_fleet_merged"] = (
            r["fleet_live"] >= 4 and len(r["fleet_workers"]) >= 4
            and r["fleet_has_slo_gauges"]
            and r["fleet_has_phase_labels"])

    if args.scenario in ("all", "mixed_adapter"):
        spec = {"adapters": 8, "tenants": 3, "max_new_tokens": 6,
                "churn_rounds": 8}
        result["mixed_adapter"] = run_mixed_adapter(tmp, spec)
        r = result["mixed_adapter"]
        gates["adapter_tokens_identical_vs_oracle"] = bool(
            r["tokens_identical"])
        gates["adapter_base_row_identical"] = bool(r["base_row_identical"])
        gates["adapter_zero_leaked_pool_bytes"] = (
            r["leaked_pool_bytes"] == 0)
        gates["adapter_gauges_populated"] = bool(r["gauges_populated"])

    if args.scenario in ("all", "rolling_restart"):
        spec = {"workers": 2, "clients": 4}
        result["rolling_restart"] = run_rolling_restart(tmp, model_dir, spec)
        gates["rolling_restart_zero_failed"] = bool(
            result["rolling_restart"]["ok"])

    result["gates"] = gates
    result["pass"] = all(gates.values()) if gates else False
    out = json.dumps(result, indent=2, sort_keys=True, default=str)
    print(out)
    if args.out:
        with open(args.out, "w") as f:
            f.write(out + "\n")
    if gates and not result["pass"]:
        failing = [k for k, v in gates.items() if not v]
        sys.stderr.write(f"[traffic_replay] GATES FAILED: {failing}\n")
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
