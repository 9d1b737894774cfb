"""paddle_tpu.observability: unified registry, trace spans, flight
recorder.

Covers the PR's acceptance criteria directly:
* one scrape (``observability.snapshot()`` / prometheus text) exposes
  serving + dispatch-cache + executor + supervisor + reader families;
* N-thread concurrent span emission, with a snapshotting reader racing
  the writers, loses and duplicates ZERO events;
* an injected ``nan@N`` and an injected ``hang@N`` (faults.py under
  the Supervisor) each produce a parseable flight-recorder JSON dump
  holding the spans and step-metric samples leading up to the fault;
* timeline rendering emits thread-name metadata and cross-thread flow
  arrows for parented spans.
"""

import json
import os
import sys
import threading

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import observability, profiler, resilience
from paddle_tpu.observability import flight, tracing
from paddle_tpu.observability.registry import MetricsRegistry
from paddle_tpu.tools_timeline import to_chrome_trace

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(HERE, "tools"))

import chaos_train  # noqa: E402  (the resilience test model zoo)


@pytest.fixture()
def obs_flags():
    """Flip observability flags for a test and ALWAYS restore them —
    they are process-global and the rest of the suite runs with the
    defaults."""
    saved = {k: fluid.flags.flag(k) for k in (
        "observability_metrics", "observability_tracing",
        "observability_flight", "observability_flight_capacity",
        "observability_dump_dir")}

    def set_flags(**kw):
        fluid.set_flags(kw)

    yield set_flags
    fluid.set_flags(saved)


# -- registry ---------------------------------------------------------------


def test_registry_instruments_and_exporters():
    reg = MetricsRegistry()
    c = reg.counter("t_requests_total", "requests")
    c.inc()
    c.inc(2)
    g = reg.gauge("t_depth")
    g.set(7)
    g.labels(lane="b").set(3)
    h = reg.histogram("t_latency_ms")
    for v in (1.0, 2.0, 100.0):
        h.observe(v)

    # idempotent: same name -> same family; kind mismatch rejected
    assert reg.counter("t_requests_total") is c
    with pytest.raises(ValueError):
        reg.gauge("t_requests_total")

    text = reg.to_prometheus_text()
    assert "# TYPE t_requests_total counter" in text
    assert "t_requests_total 3" in text
    assert 't_depth{lane="b"} 3' in text
    assert "t_latency_ms_count 3" in text
    assert 't_latency_ms{quantile="0.5"}' in text

    snap = reg.snapshot()
    json.dumps(snap)  # JSON-clean is part of the contract
    assert snap["instruments"]["t_requests_total"]["values"]["_"] == 3
    assert snap["instruments"]["t_latency_ms"]["values"]["_"]["count"] == 3


def test_registry_collector_survives_bad_collector():
    reg = MetricsRegistry()

    def bad():
        raise RuntimeError("scrape-time failure")

    reg.register_collector("bad", bad)
    reg.register_collector("good", lambda: {"t_ok_total": 1})
    text = reg.to_prometheus_text()
    assert "t_ok_total 1" in text  # the bad collector vanished, not the scrape
    reg.unregister_collector("good")
    assert "t_ok_total" not in reg.to_prometheus_text()


def test_unified_snapshot_exposes_all_subsystem_families(tmp_path):
    """THE acceptance test: serving + dispatch + executor + supervisor
    + reader families visible through the single registry after each
    subsystem merely exists/ran."""
    from paddle_tpu.serving.metrics import ServingMetrics

    sm = ServingMetrics()          # serving family source (self-registers)
    sm.inc("requests_total")
    loader = fluid.DataLoader.from_generator(capacity=4)  # reader source

    main, startup, loss = chaos_train.build_model()
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.TPUPlace())
        exe.run(startup)
        ck = str(tmp_path / "ck")
        sup = resilience.Supervisor(
            exe, main, checkpoint_dir=ck, feed_fn=chaos_train.feed_fn,
            fetch_list=[loss],
            policy=resilience.CheckpointPolicy(ck, every_steps=0,
                                               keep_last=2))
        sup.run_loop(2, resume=False, final_checkpoint=False)

    text = observability.to_prometheus_text()
    for family in (
        "paddle_serving_requests_total",       # serving
        "paddle_dispatch_jit_compiles",        # dispatch/compile caches
        "paddle_executor_bound_hits",          # executor
        "paddle_resilience_steps_completed",   # supervisor
        "paddle_reader_queue_depth",           # reader
        "paddle_step_total",                   # step telemetry
        "paddle_compile_total",                # compile counter
        "paddle_build_info",                   # build stamp
    ):
        assert family in text, f"{family} missing from unified scrape"

    snap = observability.snapshot()
    json.dumps(snap)
    assert "paddle_resilience_steps_completed" in snap["collected"]
    del loader, sm


# -- tracing ----------------------------------------------------------------


def test_span_parentage_and_cross_thread_attach(obs_flags):
    obs_flags(observability_tracing=True, observability_flight=True)
    flight.clear()
    with tracing.span("outer") as outer:
        assert tracing.current() == outer
        with tracing.span("inner") as inner:
            assert inner.trace_id == outer.trace_id

    handoff = {}

    def worker():
        with tracing.attach(outer):
            with tracing.span("worker_side") as ctx:
                handoff["ctx"] = ctx

    t = threading.Thread(target=worker)
    t.start()
    t.join()
    assert handoff["ctx"].trace_id == outer.trace_id

    spans = {e["name"]: e for e in flight.entries() if e["kind"] == "span"}
    assert spans["inner"]["parent_id"] == outer.span_id
    assert spans["worker_side"]["parent_id"] == outer.span_id
    assert spans["inner"]["trace_id"] == spans["worker_side"]["trace_id"]


def test_span_disabled_is_plain_record_event(obs_flags):
    obs_flags(observability_tracing=False)
    with profiler.host_trace():
        with tracing.span("plain_event") as ctx:
            assert ctx is None
    evs = [e for e in profiler.host_events() if e["name"] == "plain_event"]
    assert len(evs) == 1 and "args" not in evs[0]


def test_concurrent_span_emission_loses_and_duplicates_nothing(obs_flags):
    """N writer threads, K spans each, with a reader thread snapshotting
    the host-event log and flight ring THROUGHOUT: afterwards exactly
    N*K events, all span ids distinct."""
    n_threads, k = 8, 150
    obs_flags(observability_tracing=True, observability_flight=True,
              observability_flight_capacity=2 * n_threads * k)
    flight.clear()
    stop = threading.Event()
    snap_errors = []

    def reader():
        while not stop.is_set():
            try:
                profiler.host_events()
                flight.entries()
            except Exception as e:  # noqa: BLE001 — torn snapshot
                snap_errors.append(e)

    def writer(i):
        for j in range(k):
            with tracing.span(f"w{i}", {"j": j}):
                pass

    with profiler.host_trace():
        rt = threading.Thread(target=reader)
        rt.start()
        threads = [threading.Thread(target=writer, args=(i,))
                   for i in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        stop.set()
        rt.join()
        events = [e for e in profiler.host_events()
                  if e["name"].startswith("w")]
    assert not snap_errors
    assert len(events) == n_threads * k  # zero lost, zero duplicated
    ids = [e["args"]["span_id"] for e in events]
    assert len(set(ids)) == len(ids)
    ring_spans = [e for e in flight.entries() if e["kind"] == "span"]
    assert len(ring_spans) == n_threads * k
    assert len({e["span_id"] for e in ring_spans}) == n_threads * k


# -- flight recorder --------------------------------------------------------


def test_flight_ring_is_bounded(obs_flags):
    obs_flags(observability_flight=True, observability_flight_capacity=32)
    flight.clear()
    for i in range(500):
        flight.note("event", i=i)
    ent = flight.entries()
    assert len(ent) == 32
    assert ent[-1]["i"] == 499 and ent[0]["i"] == 468  # newest kept
    # out-of-range capacity clamps (to >=16) and keeps appending
    obs_flags(observability_flight_capacity=4)
    for i in range(40):
        flight.note("event", i=i)
    assert len(flight.entries()) == 16


def test_span_args_cannot_collide_with_recorder_keys(obs_flags):
    """User span args using the recorder's own entry keys (name/ts/
    dur/tid/...) must not blow up the traced code path."""
    obs_flags(observability_tracing=True, observability_flight=True)
    flight.clear()
    with tracing.span("collide", {"name": "user-name", "dur": 7,
                                  "step": 3}):
        pass
    (entry,) = [e for e in flight.entries() if e["kind"] == "span"]
    assert entry["name"] == "collide"       # recorder's key wins
    assert entry["step"] == 3               # non-colliding args kept


def _supervised(tmp_path, obs_flags, fault, **sup_kw):
    obs_flags(observability_tracing=True, observability_flight=True,
              observability_dump_dir=str(tmp_path / "dumps"))
    flight.clear()
    main, startup, loss = chaos_train.build_model()
    scope = fluid.Scope()
    ck = str(tmp_path / "ck")
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.TPUPlace())
        exe.run(startup)
        sup = resilience.Supervisor(
            exe, main, checkpoint_dir=ck, feed_fn=chaos_train.feed_fn,
            fetch_list=[loss],
            policy=resilience.CheckpointPolicy(ck, every_steps=3,
                                               keep_last=2),
            fault_injector=resilience.FaultInjector(fault), **sup_kw)
        stats = sup.run_loop(8)
    return stats


def _check_dump(path, reason):
    assert path and os.path.exists(path)
    with open(path) as f:
        dump = json.load(f)          # parseable is part of the criterion
    assert dump["reason"] == reason
    kinds = {e["kind"] for e in dump["entries"]}
    # the spans and metric samples leading up to the fault
    assert "span" in kinds, kinds
    assert "step" in kinds, kinds
    assert any(e["kind"] == "span" and e["name"] == "resilience/step"
               for e in dump["entries"])
    assert "metrics" in dump and "instruments" in dump["metrics"]
    return dump


def test_flight_dump_on_injected_nan(tmp_path, obs_flags):
    stats = _supervised(tmp_path, obs_flags, "nan@5")
    assert stats["nan_events"] == 1 and stats["rollbacks"] == 1
    assert len(stats["flight_dumps"]) == 1
    dump = _check_dump(stats["flight_dumps"][0], "nan_rollback")
    assert any(e["kind"] == "event" and e.get("what") == "nan_loss"
               for e in dump["entries"])
    # training still completed after the rollback
    assert stats["steps_completed"] > 8 - 5


def test_flight_dump_on_injected_hang(tmp_path, obs_flags):
    stats = _supervised(tmp_path, obs_flags, "hang@4:2.0",
                        watchdog_timeout_s=0.4)
    assert stats["watchdog_fires"] == 1
    assert stats["flight_dumps"], "watchdog fire must dump"
    dump = _check_dump(stats["flight_dumps"][0], "watchdog_hang")
    assert any(e["kind"] == "event" and e.get("what") == "watchdog_fire"
               for e in dump["entries"])
    assert stats["steps_completed"] == 8  # retry recovered the step


def test_flight_dump_on_escaping_exception(tmp_path, obs_flags):
    obs_flags(observability_flight=True,
              observability_dump_dir=str(tmp_path / "dumps"))
    main, startup, loss = chaos_train.build_model()
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.TPUPlace())
        exe.run(startup)
        sup = resilience.Supervisor(
            exe, main, checkpoint_dir=str(tmp_path / "ck"),
            feed_fn=chaos_train.feed_fn, fetch_list=[loss],
            max_retries=0,
            fault_injector=resilience.FaultInjector("raise@2"))
        with pytest.raises(resilience.InjectedFault):
            sup.run_loop(5)
    assert sup.stats()["flight_dumps"]
    with open(sup.stats()["flight_dumps"][-1]) as f:
        dump = json.load(f)
    assert dump["reason"] == "exception:InjectedFault"


def test_flight_dump_survives_bad_dump_dir(obs_flags):
    obs_flags(observability_dump_dir="/proc/definitely/not/writable")
    assert flight.dump("unwritable") is None  # no raise out of a crash path


# -- timeline rendering -----------------------------------------------------


def test_timeline_thread_names_and_flow_arrows(obs_flags):
    obs_flags(observability_tracing=True)
    ctx_holder = {}
    with profiler.host_trace():
        with tracing.span("submit_side") as ctx:
            ctx_holder["ctx"] = ctx

        def worker():
            with tracing.span("worker_side", parent=ctx_holder["ctx"]):
                pass

        t = threading.Thread(target=worker, name="obs-test-worker")
        t.start()
        t.join()
        events = profiler.host_events()

    trace = to_chrome_trace(events)
    evs = trace["traceEvents"]
    meta = [e for e in evs if e.get("ph") == "M"
            and e.get("name") == "thread_name"]
    assert any(e["args"]["name"] == "obs-test-worker" for e in meta)

    starts = [e for e in evs if e.get("ph") == "s"]
    finishes = [e for e in evs if e.get("ph") == "f"]
    assert len(starts) == 1 and len(finishes) == 1  # one cross-thread arrow
    assert starts[0]["id"] == finishes[0]["id"]
    assert starts[0]["tid"] != finishes[0]["tid"]
    # same-thread nesting produced no arrow: both spans exist as X events
    xs = {e["name"] for e in evs if e.get("ph") == "X"}
    assert {"submit_side", "worker_side"} <= xs


def test_stable_tids_registered_with_names():
    tid = profiler.thread_tid()
    assert profiler.thread_tid() == tid  # stable within the thread
    names = profiler.thread_names()
    assert names[tid] == threading.current_thread().name


def test_xla_analysis_gauges(obs_flags):
    """observability_xla_analysis surfaces per-executable memory/cost
    accounting through the dispatch cache as labeled gauges."""
    saved = fluid.flags.flag("observability_xla_analysis")
    fluid.set_flags({"observability_xla_analysis": True})
    try:
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup), fluid.unique_name.guard():
            x = fluid.layers.data("x", [4])
            loss = fluid.layers.mean(fluid.layers.fc(x, 2))
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            exe = fluid.Executor(fluid.TPUPlace())
            exe.run(startup)
            exe.run(main, feed={"x": np.ones((2, 4), "float32")},
                    fetch_list=[loss])
    finally:
        fluid.set_flags({"observability_xla_analysis": saved})
    text = observability.to_prometheus_text()
    assert "paddle_xla_" in text  # at least one analysis family
    assert 'executable="' in text  # labeled by executable tag


# -- serving integration ----------------------------------------------------


def test_serving_request_spans_flow_into_batch_execute(obs_flags):
    """submit (caller thread) -> batch_execute (worker thread) carries
    trace parentage, so the timeline shows the handoff."""
    pytest.importorskip("jax")
    from paddle_tpu.inference import Config, create_predictor
    from paddle_tpu.serving import ServingEngine
    import tempfile

    d = tempfile.mkdtemp(prefix="obs_srv_")
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = fluid.layers.data("x", [6])
        out = fluid.layers.fc(x, 3)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        fluid.io.save_inference_model(d, ["x"], [out], exe, main)
    pred = create_predictor(Config(d))
    # warm once: the FIRST call of an executable is the compile path
    # (a compile event, not a traced step) — the span assertion below
    # is about the steady-state hot path
    pred.run([np.ones((1, 6), "float32")])

    obs_flags(observability_tracing=True, observability_flight=True)
    flight.clear()
    eng = ServingEngine(pred, max_batch_size=4, batch_timeout_ms=5)
    try:
        xv = np.ones((1, 6), "float32")
        eng.predict({"x": xv})
    finally:
        eng.close()
    spans = [e for e in flight.entries() if e["kind"] == "span"]
    by_name = {}
    for s in spans:
        by_name.setdefault(s["name"].split("[")[0], s)
    submit = by_name.get("serving/submit")
    execute = by_name.get("serving/batch_execute")
    assert submit and execute
    assert execute["trace_id"] == submit["trace_id"]
    assert execute["parent_id"] == submit["span_id"]
    # the jit step under the worker joined the same trace
    step = by_name.get("executor/step")
    assert step is not None and step["trace_id"] == submit["trace_id"]


# -- the generation loop's run-ahead counters ---------------------------------


def test_generation_overlap_counters_in_stats_and_scrape(tmp_path):
    """What the ragged loop counts about running one step ahead of the
    device is in ``engine.stats()`` and in the one scrape, as
    ``paddle_generation_*`` beside the ``loop_*`` phase counters."""
    from paddle_tpu.generation import GenerationEngine
    from paddle_tpu.generation.engine import DRAIN_REASONS
    from paddle_tpu.generation.model import GPTConfig, build_lm_program
    from paddle_tpu.inference import Config, create_predictor

    cfg = GPTConfig(vocab_size=97, hidden_size=32, num_layers=2, num_heads=4,
                    ffn_size=64, max_position=64, hidden_dropout=0.0,
                    attention_dropout=0.0)
    main, startup, _feeds, fetches = build_lm_program(cfg, 48)
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.TPUPlace())
        exe.run(startup)
        fluid.io.save_inference_model(str(tmp_path), ["tokens"],
                                      [fetches["logits"]], exe, main)
    names = (["steps_dispatched_ahead_total", "device_carried_tokens_total",
              "discarded_rows_total", "admit_holds_total",
              "admit_hold_us_total"]
             + [f"inflight_drains_{r}_total" for r in DRAIN_REASONS])
    with GenerationEngine(create_predictor(Config(str(tmp_path))), cfg,
                          page_size=4, num_pages=32, max_decode_batch=2,
                          chunk_tokens=6) as eng:
        eng.generate(np.asarray([5, 9, 2, 7], np.int64), max_new_tokens=8,
                     eos_id=None, timeout=300)
        stats = eng.stats()
        text = observability.to_prometheus_text()
    for name in names + ["loop_step_us_total", "loop_emit_us_total"]:
        assert name in stats, name
        assert f"paddle_generation_{name}{{" in text, name
    # seven of the eight steps were dispatched behind their predecessor,
    # each with its decode token still on the device
    assert stats["ragged_steps_total"] == 8
    assert stats["steps_dispatched_ahead_total"] == 7
    assert stats["device_carried_tokens_total"] == 7
    assert stats["discarded_rows_total"] == 0
