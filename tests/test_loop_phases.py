"""The two host loops measured from inside (ISSUE 26): the ragged engine's
eight loop phases and one `Executor.run`'s four spans are opened whatever
`observability_tracing` says, under constant names, and each engine phase
counts its wall time into `loop_<phase>_us_total` at the span's boundary."""

import re
import threading
import time

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import observability, profiler
from paddle_tpu.generation import DraftModel, GenerationEngine
from paddle_tpu.generation.engine import LOOP_PHASES
from paddle_tpu.generation.model import GPTConfig, build_lm_program
from paddle_tpu.inference import Config, create_predictor
from paddle_tpu.observability import flight

CFG = GPTConfig(vocab_size=97, hidden_size=32, num_layers=2, num_heads=4,
                ffn_size=64, max_position=64, hidden_dropout=0.0,
                attention_dropout=0.0)
SEQ = 48
PHASES = ["generation/" + p for p in LOOP_PHASES]
COUNTERS = [f"loop_{p}_us_total" for p in LOOP_PHASES]
# what one iteration of the loop looks like, by the phases' initials: a
# step is dispatched (assemble, bind, step) before its predecessor's
# tokens are emitted, and the last step of a burst is read by an
# iteration that dispatches nothing
ITERATION = re.compile(r"(w?a(gd?(mb)?se?)?)+")
INITIAL = {"generation/wait": "w", "generation/admit": "a",
           "generation/grow": "g", "generation/draft": "d",
           "generation/assemble": "m", "generation/bind": "b",
           "generation/step": "s", "generation/emit": "e"}


@pytest.fixture(scope="module")
def predictor(tmp_path_factory):
    d = str(tmp_path_factory.mktemp("loop_phases_lm"))
    main, startup, _feeds, fetches = build_lm_program(CFG, SEQ)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.TPUPlace())
        exe.run(startup)
        fluid.io.save_inference_model(d, ["tokens"], [fetches["logits"]],
                                      exe, main)
    return create_predictor(Config(d))


def _engine(predictor, **kw):
    return GenerationEngine(predictor, CFG, page_size=4, num_pages=64,
                            max_decode_batch=4, chunk_tokens=6, **kw)


def _prompt(n, seed=0):
    return np.random.RandomState(seed).randint(
        1, CFG.vocab_size, n).astype(np.int64)


def _loop_events(events):
    """The loop thread's phase events, by start time."""
    tids = {e["tid"] for e in events if e["name"] == "generation/step"}
    assert len(tids) == 1
    (tid,) = tids
    return sorted((e for e in events
                   if e["tid"] == tid and e["name"] in INITIAL),
                  key=lambda e: e["ts"])


def _run_traced(predictor, **kw):
    """Two requests, one after the other (so the loop is starved in
    between and `generation/wait` closes inside the session), with
    observability_tracing off. Returns (host events, ragged steps)."""
    with _engine(predictor, **kw) as eng:
        with profiler.host_trace():
            s0 = eng.stats()["ragged_steps_total"]
            for seed in (1, 2):
                eng.generate(_prompt(9, seed), max_new_tokens=5, timeout=300)
                # the result is out before the emit phase closes; the
                # gauges are set after it, a moment before the loop
                # finds itself starved
                deadline = time.monotonic() + 30
                while (eng.stats()["active_seqs"]
                       and time.monotonic() < deadline):
                    time.sleep(0.001)
                time.sleep(0.05)
            steps = eng.stats()["ragged_steps_total"] - s0
            events = profiler.host_events()
    return events, steps


def test_phase_spans_without_the_flag(predictor):
    assert not fluid.get_flags("observability_tracing")[
        "observability_tracing"]
    events, steps = _run_traced(predictor)
    names = [e["name"] for e in events]
    assert steps >= 8
    # one step phase a dispatched step, and one more a request: the
    # iteration that only waits for the last step's tokens
    assert names.count("generation/step") == steps + 2
    assert names.count("generation/bind") == steps
    assert set(PHASES) - {"generation/draft"} <= set(names)
    assert "generation/draft" not in names       # no draft model
    assert not [n for n in names if "[" in n]
    assert "generation/submit" not in names      # request tracing: flag only
    loop = _loop_events(events)
    # in order: every iteration is [wait] admit [grow [draft] [assemble
    # bind] step [emit]]
    assert ITERATION.fullmatch("".join(INITIAL[e["name"]] for e in loop))
    # and one at a time (time.time() pairs: allow a microsecond)
    for a, b in zip(loop, loop[1:]):
        assert a["ts"] + a["dur"] <= b["ts"] + 1e-6, (a, b)
    # the dispatch and the wait for the tokens lie inside the step phase
    step_iv = [(e["ts"], e["ts"] + e["dur"]) for e in loop
               if e["name"] == "generation/step"]
    for child in ("executor/feed", "executor/step", "generation/fetch"):
        inner = [e for e in events if e["name"] == child]
        assert len(inner) >= steps - 1           # the first call compiles
        for e in inner:
            assert any(lo - 1e-6 <= e["ts"] and e["ts"] + e["dur"] <= hi + 1e-6
                       for lo, hi in step_iv), e


class _OnesDraft(DraftModel):
    def propose(self, contexts, k):
        return [np.full(k, 1, np.int64) for _ in contexts]


def test_draft_phase_only_with_speculative_rows(predictor):
    events, steps = _run_traced(predictor, spec_tokens=2, draft=_OnesDraft())
    names = [e["name"] for e in events]
    assert 0 < names.count("generation/draft") <= steps
    assert ITERATION.fullmatch(
        "".join(INITIAL[e["name"]] for e in _loop_events(events)))


def test_loop_counters_partition_the_loop_threads_time(predictor):
    """Read on the loop thread itself (an `on_token` callback runs inside
    the emit phase): between two such readings the phases' counters grew
    by the wall time that passed, so nothing is left between phases. The
    client's callback is slow (3 ms, counted under emit), as a real step
    is: at this toy size a whole step takes 0.7 ms, of which opening and
    closing eight phases is some percent by itself."""
    marks = []
    with _engine(predictor) as eng:
        def on_token(_tok):
            snap = eng.metrics.snapshot()
            marks.append((time.perf_counter_ns(),
                          [snap[c] for c in COUNTERS]))
            time.sleep(0.003)

        eng.submit(_prompt(4), max_new_tokens=56,
                   on_token=on_token).result(timeout=300)
        stats = eng.stats()
        text = observability.to_prometheus_text()
    assert len(marks) == 56
    for (_t0, a), (_t1, b) in zip(marks, marks[1:]):
        assert all(y >= x for x, y in zip(a, b))            # monotone
    (t0, c0), (t1, c1) = marks[2], marks[-1]   # past the compiling step
    wall_us = (t1 - t0) / 1e3
    counted_us = sum(c1) - sum(c0)
    assert abs(counted_us - wall_us) <= 0.05 * wall_us, (counted_us, wall_us)
    for c in COUNTERS:
        assert c in stats
        assert f"paddle_generation_{c}" in text
    assert stats["loop_step_us_total"] > 0 and stats["loop_draft_us_total"] == 0


def test_flag_on_keeps_parentage_and_flow(predictor):
    fluid.set_flags({"observability_tracing": True})
    try:
        flight.clear()
        with _engine(predictor) as eng:
            eng.generate(_prompt(9, 3), max_new_tokens=4, timeout=300)
        spans = [e for e in flight.entries() if e.get("kind") == "span"]
    finally:
        fluid.set_flags({"observability_tracing": False})
    by_name = {}
    for s in spans:
        by_name.setdefault(s["name"], []).append(s)
    assert not [n for n in by_name if "[" in n]
    (submit,) = by_name["generation/submit"]
    steps = by_name["generation/step"]
    # every step that carried the request points back at its submit
    # span; the last iteration dispatches nothing and only reads the
    # step in flight
    assert [s["n"] for s in steps] == [1] * (len(steps) - 1) + [0]
    assert all(submit["span_id"] in s["flow_from"] for s in steps[:-1])
    assert all(s["new_tokens"] >= 1 for s in steps[:-1])
    # the jitted call is the step span's child, in its trace
    step_ids = {s["span_id"]: s["trace_id"] for s in steps}
    assert by_name["executor/step"]
    for c in by_name["executor/step"]:
        assert step_ids[c["parent_id"]] == c["trace_id"]
    # the flag buys identity for the spans it always did, and nothing
    # else: the other phases stay on the profiler's clock alone, one
    # flight-ring entry a step and not a dozen
    assert set(by_name) <= {"generation/submit", "generation/step",
                            "executor/step"}
    assert all("step" in s and s["tag"] == "generation/ragged_step"
               for s in by_name["executor/step"])


def test_executor_run_spans_without_the_flag():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = fluid.layers.data("x", [6])
        out = fluid.layers.fc(x, 3)
    scope = fluid.Scope()
    feed = {"x": np.ones((2, 6), "float32")}
    seen = []
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        exe.run(main, feed=feed, fetch_list=[out])      # compiles, binds
        tid = threading.get_ident()
        with profiler.host_trace():
            exe.run(main, feed=feed, fetch_list=[out])
            seen = [e for e in profiler.host_events()
                    if e["name"].startswith("executor/")]
    assert threading.get_ident() == tid
    assert [e["name"] for e in seen] == [
        "executor/bind", "executor/feed", "executor/step", "executor/fetch"]
    for a, b in zip(seen, seen[1:]):
        assert a["ts"] + a["dur"] <= b["ts"] + 1e-6
