"""The host's stalls on the profiler's clock: the cyclic collector's
range and counters, the wait for a finished step inside each fetch, the
state write-back and the first call's compile. Every range is opened
whatever `observability_tracing` says, under a constant name, and is
read here from the host-event log of a `profiler.host_trace()` session.
CPU, tiny widths: no time here is a device time."""

import gc

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import observability, profiler
from paddle_tpu.generation import GenerationEngine
from paddle_tpu.generation.model import GPTConfig, build_lm_program
from paddle_tpu.inference import Config, create_predictor
from paddle_tpu.observability import tracing

CFG = GPTConfig(vocab_size=97, hidden_size=32, num_layers=2, num_heads=4,
                ffn_size=64, max_position=64, hidden_dropout=0.0,
                attention_dropout=0.0)
SEQ = 48


def _gc_counts(generation):
    snap = observability.snapshot()["collected"]
    key = f'{{generation="{generation}"}}'
    return (snap["paddle_gc_collections_total"][key],
            snap["paddle_gc_pause_seconds_total"][key])


def _named(events, name):
    return [e for e in events if e["name"] == name]


def _inside(inner, outer):
    return (outer["ts"] - 1e-6 <= inner["ts"] and inner["ts"] + inner["dur"]
            <= outer["ts"] + outer["dur"] + 1e-6)


# -- the cyclic collector ---------------------------------------------------


@pytest.mark.parametrize("generation", [1, 2])
def test_a_collection_of_generation_1_or_2_is_a_range_and_counts(generation):
    n0, s0 = _gc_counts(generation)
    with profiler.host_trace():
        gc.collect(generation)
        events = _named(profiler.host_events(), "executor/gc")
    n1, s1 = _gc_counts(generation)
    mine = [e for e in events if e["args"]["generation"] == generation]
    assert len(mine) == 1
    assert mine[0]["dur"] > 0
    assert n1 >= n0 + 1 and s1 > s0


def test_a_generation_0_collection_counts_and_opens_no_range():
    n0, s0 = _gc_counts(0)
    with profiler.host_trace():
        gc.collect(0)
        events = _named(profiler.host_events(), "executor/gc")
    n1, s1 = _gc_counts(0)
    assert not [e for e in events if e["args"]["generation"] == 0]
    assert n1 >= n0 + 1 and s1 >= s0
    text = observability.to_prometheus_text()
    for family in ("paddle_gc_collections_total",
                   "paddle_gc_pause_seconds_total"):
        for g in range(3):
            assert f'{family}{{generation="{g}"}}' in text


def test_installing_the_hook_twice_registers_one_callback():
    tracing.install_gc_hook()
    tracing.install_gc_hook()
    assert gc.callbacks.count(tracing._on_gc) == 1


def test_a_collection_under_the_event_log_lock_does_not_deadlock():
    """An allocation made while the event log's lock is held can start
    a collection on the same thread, whose hook then logs its range."""
    with profiler.host_trace():
        with profiler._events_lock:
            gc.collect(1)
        events = _named(profiler.host_events(), "executor/gc")
    assert [e for e in events if e["args"]["generation"] == 1]


# -- Executor.run -----------------------------------------------------------


def _sgd_program(inputs=6, width=3):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = fluid.layers.data("x", [inputs])
        loss = fluid.layers.mean(fluid.layers.fc(x, width))
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    return main, startup, loss


def test_fetch_waits_for_the_step_then_copies_and_writeback_comes_between():
    main, startup, loss = _sgd_program()
    feed = {"x": np.ones((2, 6), "float32")}
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        (first,) = exe.run(main, feed=feed, fetch_list=[loss])   # compiles
        with profiler.host_trace():
            (second,) = exe.run(main, feed=feed, fetch_list=[loss])
            events = [e for e in profiler.host_events()
                      if e["name"].startswith("executor/")
                      and e["name"] != "executor/gc"]
    assert isinstance(second, np.ndarray) and second != first  # SGD moved
    assert [e["name"] for e in events] == [
        "executor/bind", "executor/feed", "executor/step",
        "executor/writeback", "executor/fetch_ready", "executor/fetch"]
    step, writeback, ready, fetch = events[2:]
    assert step["ts"] + step["dur"] <= writeback["ts"] + 1e-6
    assert writeback["ts"] + writeback["dur"] <= fetch["ts"] + 1e-6
    assert _inside(ready, fetch)


def test_a_first_call_is_the_range_executor_compile_with_its_tag():
    # a width no other program of the process has: its block is compiled
    # here, not found in the process's shared cache
    main, startup, loss = _sgd_program(inputs=13, width=11)
    feed = {"x": np.ones((2, 13), "float32")}
    compiles = len(profiler.compile_events())
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        with profiler.host_trace():
            exe.run(main, feed=feed, fetch_list=[loss])
            events = profiler.host_events()
    (compile_,) = _named(events, "executor/compile")
    assert compile_["args"]["tag"]
    assert not [e for e in events if e["name"].startswith("jit_compile")]
    # the compile's history and counter stay; its range is logged once
    assert len(profiler.compile_events()) > compiles
    assert profiler.compile_events()[-1]["name"] == compile_["args"]["tag"]
    assert not _named(events, "executor/step")   # a compile is no step


# -- GenerationEngine -------------------------------------------------------


@pytest.fixture(scope="module")
def predictor(tmp_path_factory):
    d = str(tmp_path_factory.mktemp("host_stalls_lm"))
    main, startup, _feeds, fetches = build_lm_program(CFG, SEQ)
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.TPUPlace())
        exe.run(startup)
        fluid.io.save_inference_model(d, ["tokens"], [fetches["logits"]],
                                      exe, main)
    return create_predictor(Config(d))


def _greedy(predictor, prompt, n):
    toks, out = [int(t) for t in prompt], []
    for _ in range(n):
        arr = np.zeros((1, SEQ), np.int64)
        arr[0, :len(toks)] = toks
        (logits,) = predictor.run([arr])
        toks.append(int(np.argmax(logits[0, len(toks) - 1])))
        out.append(toks[-1])
    return out


def test_an_engine_step_waits_for_its_tokens_inside_the_fetch(predictor):
    prompt = np.random.RandomState(7).randint(1, CFG.vocab_size, 9)
    with GenerationEngine(predictor, CFG, page_size=4, num_pages=64,
                          max_decode_batch=4, chunk_tokens=6) as eng:
        with profiler.host_trace():
            got = eng.generate(prompt.astype(np.int64), max_new_tokens=6,
                               timeout=300)
            steps = eng.stats()["ragged_steps_total"]
            events = profiler.host_events()
    assert got == _greedy(predictor, prompt, 6)
    fetches = _named(events, "generation/fetch")
    ready = _named(events, "generation/fetch_ready")
    assert len(ready) == len(fetches) >= steps - 1
    for r in ready:
        assert sum(_inside(r, f) for f in fetches) == 1
