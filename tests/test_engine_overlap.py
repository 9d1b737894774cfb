"""The ragged step loop runs one step ahead of the device (ISSUE 33):
step N+1 is dispatched before step N's tokens are read, a decode row's
input token goes from one step's output to the next step's input on the
device, and whatever needs token values on the host drains the step in
flight first.

The anchor is token-stream equality with the serial order (dispatch,
read, emit, then the next dispatch), which a private attribute of the
engine forces: greedy decoding is what it was by construction, and the
counters say that the overlap engaged. CPU, tiny widths: no time here
is a device time."""

import functools
import importlib.util
import os
import threading
import time

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.generation import DraftModel, GenerationEngine
from paddle_tpu.generation.engine import DRAIN_REASONS
from paddle_tpu.generation.model import GPTConfig, build_lm_program
from paddle_tpu.inference import Config, create_predictor
from paddle_tpu.runtime.dispatch import BoundStep
from paddle_tpu.serving import ServingError

CFG = GPTConfig(vocab_size=97, hidden_size=32, num_layers=2, num_heads=4,
                ffn_size=64, max_position=64, hidden_dropout=0.0,
                attention_dropout=0.0)
SEQ = 48
HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def lm_dir(tmp_path_factory):
    d = str(tmp_path_factory.mktemp("overlap_lm"))
    main, startup, _feeds, fetches = build_lm_program(CFG, SEQ)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.TPUPlace())
        exe.run(startup)
        fluid.io.save_inference_model(d, ["tokens"], [fetches["logits"]],
                                      exe, main)
    return d


@pytest.fixture(scope="module")
def predictor(lm_dir):
    return create_predictor(Config(lm_dir))


@pytest.fixture(scope="module")
def hybrid():
    """tests/test_hybrid.py's recipe for a tiny hybrid decoder: its
    config, weights and reference, loaded by path."""
    spec = importlib.util.spec_from_file_location(
        "_hybrid_recipe", os.path.join(HERE, "test_hybrid.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def hybrid_predictor(hybrid, tmp_path_factory):
    from paddle_tpu.models.hybrid import build_hybrid_lm_program

    d = str(tmp_path_factory.mktemp("overlap_hybrid_lm"))
    main, _startup, _feeds, fetches = build_hybrid_lm_program(
        hybrid.HCFG, hybrid.SEQ)
    with fluid.scope_guard(hybrid._scope_with(hybrid.WEIGHTS)):
        exe = fluid.Executor(fluid.TPUPlace())
        fluid.io.save_inference_model(d, ["tokens"], [fetches["logits"]],
                                      exe, main)
    return create_predictor(Config(d))


def _engine(pred, ahead=True, config=CFG, **kw):
    """An engine over ``pred``; ``ahead=False`` forces the serial order
    before the loop starts."""
    args = dict(page_size=4, num_pages=64, max_decode_batch=4,
                chunk_tokens=6)
    args.update(kw)
    eng = GenerationEngine(pred, config, start=False, **args)
    eng._run_ahead = ahead
    return eng.start()


def _prompts(n, lo, hi, seed):
    rng = np.random.RandomState(seed)
    return [rng.randint(1, CFG.vocab_size, rng.randint(lo, hi))
            .astype(np.int64) for _ in range(n)]


def _serve(eng, prompts, max_new=12, **kw):
    streams = [eng.submit(p, max_new_tokens=max_new, **kw) for p in prompts]
    return [s.result(timeout=600) for s in streams]


def _drains(st):
    return {r: st[f"inflight_drains_{r}_total"] for r in DRAIN_REASONS}


# -- token-stream equality: running ahead against the serial order ---------------


def _plain(pred, ahead):
    with _engine(pred, ahead) as eng:
        got = _serve(eng, _prompts(6, 2, 6, 1))
    # the counters are read after the close: a request's result is out
    # before the loop has read the step behind it
    return got, eng.stats()


def _chunked_prefill(pred, ahead):
    with _engine(pred, ahead) as eng:
        got = _serve(eng, _prompts(6, 14, 31, 2), max_new=10)
    return got, eng.stats()


def _eos_mid_answer(pred, ahead):
    prompts = _prompts(5, 3, 9, 3)
    with _engine(pred, ahead=False) as ref:
        full = _serve(ref, prompts, max_new=14)
    # each request's own sixth token ends it, wherever it first occurs
    eos = [toks[5] for toks in full]
    with _engine(pred, ahead) as eng:
        streams = [eng.submit(p, max_new_tokens=14, eos_id=e)
                   for p, e in zip(prompts, eos)]
        got = [s.result(timeout=600) for s in streams]
    st = eng.stats()
    for toks, want, e, s in zip(got, full, eos, streams):
        assert toks == want[:want.index(e) + 1]     # nothing past EOS
        assert s.finish_reason == "eos"
    if ahead:
        # the row computed after each EOS was never emitted
        assert st["discarded_rows_total"] == len(prompts)
    return got, st


def _length_finishes(pred, ahead):
    """``max_new`` of 1 (the prompt's final chunk samples the only
    token), an answer that ends with the position window, and ordinary
    lengths beside them."""
    prompts = _prompts(4, 3, 8, 4)
    prompts.append(_prompts(1, 52, 53, 5)[0])       # 52 + 12 = max_position
    with _engine(pred, ahead) as eng:
        streams = [eng.submit(p, max_new_tokens=n)
                   for p, n in zip(prompts, (1, 2, 7, 12, 12))]
        got = [s.result(timeout=600) for s in streams]
        assert [s.finish_reason for s in streams] == ["length"] * 5
    st = eng.stats()
    assert [len(t) for t in got] == [1, 2, 7, 12, 12]
    assert st["discarded_rows_total"] == 0      # known early: no row wasted
    return got, st


def _prefix_cache(pred, ahead):
    head = _prompts(1, 12, 13, 6)[0]
    prompts = [np.concatenate([head, t]) for t in _prompts(5, 2, 7, 7)]
    with _engine(pred, ahead, prefix_cache=True) as eng:
        got = [eng.generate(prompts[0], max_new_tokens=9, timeout=600)]
        got += _serve(eng, prompts[1:], max_new=9)
        eng.cache.check_integrity()
    st = eng.stats()
    assert st["radix"]["prefix_hits_total"] >= 4           # publish lags a step at most
    return got, st


def _adapter_rows(pred, ahead):
    fluid.set_flags({"adapter_pool_max_bytes": 1,
                     "adapter_slots_per_bucket": 4})
    try:
        eng = _engine(pred, ahead)
    finally:
        fluid.set_flags({"adapter_pool_max_bytes": 0,
                         "adapter_slots_per_bucket": 0})
    rng = np.random.RandomState(11)
    with eng:
        store = eng.adapter_store
        for i, rank in enumerate((8, 16)):
            t = sorted(store.targets)[i]
            K, N = store.targets[t]
            store.upload(f"ad{i}", {t: (
                rng.randn(K, rank).astype("float32") * 0.05,
                rng.randn(rank, N).astype("float32") * 0.05)},
                alpha=2.0 * rank)
        prompts = _prompts(5, 3, 10, 8)
        streams = [eng.submit(p, max_new_tokens=9, adapter=a) for p, a in
                   zip(prompts, ("ad0", "ad1", None, "ad0", "ad1"))]
        got = [s.result(timeout=600) for s in streams]
    return got, eng.stats()


def _int8_pools(pred, ahead):
    with _engine(pred, ahead, kv_dtype="int8") as eng:
        got = _serve(eng, _prompts(5, 5, 16, 9))
    return got, eng.stats()


CASES = {"plain_decode": _plain, "chunked_prefill": _chunked_prefill,
         "eos_mid_answer": _eos_mid_answer, "length_finishes": _length_finishes,
         "prefix_cache": _prefix_cache, "adapter_rows": _adapter_rows,
         "int8_pools": _int8_pools}


@pytest.mark.parametrize("case", sorted(CASES) + ["recurrent_state"])
def test_running_ahead_serves_the_serial_orders_tokens(case, predictor,
                                                       request):
    if case == "recurrent_state":
        serve = functools.partial(
            _recurrent_state, request.getfixturevalue("hybrid"),
            request.getfixturevalue("hybrid_predictor"))
    else:
        serve = functools.partial(CASES[case], predictor)
    want, serial = serve(False)
    got, ahead = serve(True)
    assert got == want
    # the serial order never has a step in flight at a dispatch
    assert serial["steps_dispatched_ahead_total"] == 0
    assert serial["device_carried_tokens_total"] == 0
    assert not any(_drains(serial).values())
    # running ahead engaged on all but each burst's first step, carried
    # the decode tokens on the device, and nothing forced a drain
    steps = ahead["ragged_steps_total"]
    assert 0.5 * steps < ahead["steps_dispatched_ahead_total"] < steps
    assert ahead["device_carried_tokens_total"] > 0
    assert not any(_drains(ahead).values())
    # what is left in the pool is the trie's
    assert ahead["cache"]["pages_in_use"] == ahead["radix"]["trie_pages"]


def _recurrent_state(hybrid, pred, ahead):
    """7 requests over 3 lanes: a lane is reused two steps after its
    predecessor's last token was dispatched, and starts from a reset
    state (beside test_hybrid's reset test, which holds the logits)."""
    prompts = hybrid._prompts(7, seed=4)
    args = dict(config=hybrid.HCFG, mode="ragged", page_size=4, num_pages=40,
                max_decode_batch=3, chunk_tokens=4, prefix_cache=False)
    with _engine(pred, ahead, **args) as eng:
        got = _serve(eng, prompts, max_new=6)
    st = eng.stats()
    assert st["state_lane_resets_total"] == 7
    assert st["moe_tokens_routed_total"] == sum(
        len(p) + 5 for p in prompts) * 4
    if ahead:
        assert got[-1] == hybrid._greedy_by_reference(prompts[-1], 6)
    return got, st


# -- what drains the step in flight -------------------------------------------------


class _OnesDraft(DraftModel):
    def propose(self, contexts, k):
        return [np.full(k, 1, np.int64) for _ in contexts]


def test_an_engine_with_a_draft_never_runs_ahead(predictor):
    prompts = _prompts(4, 3, 14, 21)
    with _engine(predictor, ahead=False) as ref:
        want = _serve(ref, prompts)
    with _engine(predictor, spec_tokens=2, draft=_OnesDraft()) as eng:
        got = _serve(eng, prompts)
    st = eng.stats()
    assert got == want
    assert st["steps_dispatched_ahead_total"] == 0
    assert st["device_carried_tokens_total"] == 0
    assert st["inflight_drains_draft_total"] == st["ragged_steps_total"] > 0
    assert st["spec_rounds_total"] > 0


def test_pool_dry_eviction_drains_first_and_the_victim_resumes(predictor):
    prompts = _prompts(4, 8, 14, 7)
    with _engine(predictor, ahead=False, max_decode_batch=3) as ref:
        want = _serve(ref, prompts, max_new=18)
    with _engine(predictor, num_pages=16, max_decode_batch=3) as eng:
        got = _serve(eng, prompts, max_new=18)
        eng.cache.check_integrity()
    st = eng.stats()
    assert got == want
    assert st["evicted_total"] >= 1
    # every eviction found a step in flight and read it out first
    assert st["inflight_drains_evict_total"] >= st["evicted_total"]
    assert st["steps_dispatched_ahead_total"] > 0
    assert st["cache"]["pages_in_use"] == 0


def test_swap_base_lands_with_no_step_in_flight(predictor):
    p = _prompts(1, 5, 6, 31)[0]
    with _engine(predictor, ahead=False) as ref:
        want = ref.generate(p, max_new_tokens=40, timeout=600)
    name = "gpt_head.b"
    weights = {name: np.asarray(predictor._scope.find_var(name))}
    seen = []
    with _engine(predictor) as eng:
        apply = eng._apply_swap
        eng._apply_swap = lambda *a: (seen.append(eng._inflight), apply(*a))
        started = threading.Event()
        s = eng.submit(p, max_new_tokens=40, on_token=lambda _t: started.set())
        assert started.wait(600)
        assert eng.swap_base(weights, version="same") == "same"
        assert s.result(timeout=600) == want
    st = eng.stats()
    assert seen == [None]
    assert st["model_swaps"] == 1
    assert st["inflight_drains_swap_total"] == 1    # one was in flight


def _cancel_after(n):
    """on_token that cancels its own stream at the n-th token: on the
    loop thread, inside emit(N), with step N+1 already dispatched."""
    box = {}

    def on_token(_tok):
        box["n"] = box.get("n", 0) + 1
        if box["n"] == n:
            box["stream"].cancel()
    return box, on_token


def test_cancel_and_deadline_with_a_step_in_flight(predictor):
    pa, pb, pc = _prompts(3, 4, 9, 41)
    with _engine(predictor, ahead=False) as ref:
        wa, wb, wc = _serve(ref, [pa, pb, pc], max_new=30)
    with _engine(predictor) as eng:
        box, on_token = _cancel_after(7)
        box["stream"] = sa = eng.submit(pa, max_new_tokens=30,
                                        on_token=on_token)
        sb = eng.submit(pb, max_new_tokens=30)
        # the row in flight at the cancel was computed and dropped
        assert sa.result(timeout=600) == wa[:7]
        assert sa.finish_reason == "cancelled"
        assert sb.result(timeout=600) == wb
        mid = eng.stats()
        assert mid["discarded_rows_total"] >= 1
        assert mid["cancelled_total"] == 1
        # a deadline that passes mid-answer: a prefix, then the lane serves on
        slow = threading.Event()
        sc = eng.submit(pc, max_new_tokens=30, deadline_ms=150,
                        on_token=lambda _t: slow.wait(0.02))
        got = sc.result(timeout=600)
        assert sc.finish_reason == "deadline"
        assert 0 < len(got) < 30 and got == wc[:len(got)]
        assert eng.generate(pc, max_new_tokens=30, timeout=600) == wc
        eng.cache.check_integrity()
        assert eng.stats()["cache"]["pages_in_use"] == 0


@pytest.mark.parametrize("drain", [True, False])
def test_close_with_a_step_in_flight_finishes_every_stream(predictor, drain):
    prompts = _prompts(6, 3, 12, 51)
    with _engine(predictor, ahead=False) as ref:
        want = _serve(ref, prompts, max_new=20)
    eng = _engine(predictor, max_decode_batch=3)
    started = threading.Event()
    streams = [eng.submit(p, max_new_tokens=20,
                          on_token=lambda _t: started.set())
               for p in prompts]
    assert started.wait(600)
    eng.close(drain=drain)
    assert all(s.done() for s in streams)
    assert eng._inflight is None and not eng._by_slot
    for s, w in zip(streams, want):
        if drain:
            assert s.finish_reason == "length" and s.tokens == w
        else:
            assert s.finish_reason in ("length", "closed")
            assert s.tokens == w[:len(s.tokens)]
    assert eng.cache.stats()["pages_in_use"] == 0
    if drain:
        assert not any(_drains(eng.stats()).values())


class _Poisoned:
    """What the host finds when the device failed under a step."""

    def __array__(self, *a, **k):
        raise RuntimeError("device failed under the step")


@pytest.mark.parametrize("where", ["dispatch", "fetch"])
def test_a_step_that_raises_fails_its_rows_and_the_engine_serves_on(
        predictor, where):
    prompts = _prompts(3, 3, 9, 61)
    with _engine(predictor, ahead=False) as ref:
        want = _serve(ref, prompts, max_new=16)
    with _engine(predictor) as eng:
        calls = [0]
        if where == "dispatch":
            dispatch = eng._dispatch

            def flaky(bound, feed):
                calls[0] += 1
                if calls[0] == 6:
                    raise RuntimeError("the dispatch failed")
                return dispatch(bound, feed)
            eng._dispatch = flaky
        else:
            ahead = eng._dispatch_ahead

            def flaky(*a):
                step = ahead(*a)
                calls[0] += 1
                if calls[0] == 6:
                    step.tokens = _Poisoned()
                return step
            eng._dispatch_ahead = flaky
        streams = [eng.submit(p, max_new_tokens=16) for p in prompts]
        for s, w in zip(streams, want):
            with pytest.raises(ServingError, match="step execution failed"):
                s.result(timeout=600)
            assert s.finish_reason == "error"
            assert s.tokens == w[:len(s.tokens)] and len(s.tokens) < 16
        assert eng._inflight is None and not eng._by_slot
        st = eng.stats()
        # a step was in flight when the other failed: read out, its rows
        # (the failed sequences') dropped
        assert st["inflight_drains_error_total"] == 1
        assert st["cache"]["pages_in_use"] == 0
        # and the loop serves on, from pools that are alive
        assert _serve(eng, prompts, max_new=16) == want
        assert eng.cache.pools_alive()


# -- the invariants, by counters ------------------------------------------------------


def test_never_more_than_one_step_ahead_and_dispatches_hold_the_pool_lock(
        predictor, monkeypatch):
    prompts = _prompts(9, 3, 20, 71)
    with _engine(predictor, max_decode_batch=3) as eng:
        outstanding, seen, unlocked = [0], [], []
        run, emit = BoundStep.run, eng._emit_step

        def counted_run(self, feed, return_numpy):
            if self.compiled.tag == "generation/ragged_step":
                outstanding[0] += 1
                seen.append(outstanding[0])
                if not eng.cache.pools_locked().locked():
                    unlocked.append(len(seen))
            return run(self, feed, return_numpy)

        def counted_emit(step, tokens):
            outstanding[0] -= 1
            return emit(step, tokens)

        monkeypatch.setattr(BoundStep, "run", counted_run)
        eng._emit_step = counted_emit
        _serve(eng, prompts, max_new=10)
    st = eng.stats()
    # at a dispatch: the new step and at most the one before it
    assert max(seen) == 2 and not unlocked
    assert st["steps_dispatched_ahead_total"] == seen.count(2)
    assert st["ragged_steps_total"] == len(seen)


def _slow_device(eng, seconds):
    """Make the wait for a step's tokens last ``seconds``, as a device
    step far longer than the loop's own work does on the chip."""
    fetch = eng._fetch

    def slow(step):
        time.sleep(seconds)
        return fetch(step)
    eng._fetch = slow


def test_with_a_queue_waiting_the_loop_never_holds(predictor):
    samples = []
    with _engine(predictor, max_decode_batch=2) as eng:
        _slow_device(eng, 0.02)

        def on_token(_tok):
            samples.append((eng.queue_depth(),
                            eng.metrics.snapshot()["admit_holds_total"]))
        streams = [eng.submit(p, max_new_tokens=6, on_token=on_token)
                   for p in _prompts(8, 3, 9, 81)]
        for s in streams:
            s.result(timeout=600)
    waiting = [holds for depth, holds in samples if depth > 0]
    assert len(waiting) > 10 and max(waiting) == 0


def test_a_successor_submitted_within_the_hold_joins_the_next_step(predictor):
    """Clients = lanes: when a request ends, its client's next one is a
    moment away. The loop waits for it (the device is busy with the step
    in flight), so it is admitted into the very next dispatched step
    rather than one later."""
    pa, pb, pc = _prompts(3, 3, 6, 91)
    with _engine(predictor, max_decode_batch=2) as eng:
        _slow_device(eng, 0.04)
        rows_of, ahead = [], eng._dispatch_ahead

        def recorded(bound, feed, rows, carry):
            rows_of.append({id(req.stream) for _, req, _ in rows})
            return ahead(bound, feed, rows, carry)
        eng._dispatch_ahead = recorded
        long = eng.submit(pa, max_new_tokens=40)
        first = eng.submit(pb, max_new_tokens=8)
        first.result(timeout=600)
        time.sleep(0.002)               # the client's thread, a moment late
        successor = eng.submit(pc, max_new_tokens=4)
        successor.result(timeout=600)
        long.result(timeout=600)
    st = eng.stats()
    last_of_first = max(i for i, r in enumerate(rows_of) if id(first) in r)
    first_of_successor = min(i for i, r in enumerate(rows_of)
                             if id(successor) in r)
    # step N carried the first request's last row; N+1 was dispatched
    # before N's tokens were read; the successor is in N+2
    assert first_of_successor == last_of_first + 2
    assert st["admit_holds_total"] >= 1
    assert st["admit_hold_us_total"] >= 1000
    assert st["queue_wait_ms"]["max"] < 0.04 * 1e3
