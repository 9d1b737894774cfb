"""paddle_tpu.generation: paged KV cache, paged-attention kernel,
continuous-batching engine, streamed /v1/generate.

The correctness anchor throughout: GREEDY continuous-batching decode
must produce EXACTLY the tokens a naive re-prefill decode produces
from the same weights — through slot churn, eviction/resume, and HTTP.
"""

import http.client
import json
import threading
import time

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import generation
from paddle_tpu.generation import (CacheGeometry, GenerationEngine,
                                   PagedKVCache, PagePoolExhausted)
from paddle_tpu.generation.model import (GPTConfig, build_decode_program,
                                         build_lm_program,
                                         build_prefill_program)
from paddle_tpu.inference import Config, create_predictor
from paddle_tpu.serving import DeadlineExceeded, Overloaded, ServingEngine, ServingServer


# -- fixtures: one tiny LM + predictor per module (compile once) ------------

CFG = GPTConfig(vocab_size=97, hidden_size=32, num_layers=2, num_heads=4,
                ffn_size=64, max_position=64, hidden_dropout=0.0,
                attention_dropout=0.0)
SEQ = 48


@pytest.fixture(scope="module")
def lm_dir(tmp_path_factory):
    d = str(tmp_path_factory.mktemp("gen_lm"))
    main, startup, _feeds, fetches = build_lm_program(CFG, SEQ)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.TPUPlace())
        exe.run(startup)
        fluid.io.save_inference_model(d, ["tokens"],
                                      [fetches["logits"]], exe, main)
    return d


@pytest.fixture(scope="module")
def predictor(lm_dir):
    return create_predictor(Config(lm_dir))


@pytest.fixture(scope="module")
def oracle(predictor):
    """Naive greedy re-prefill decode through the stock LM program."""
    def _decode(prompt, n, eos=None):
        toks = list(int(t) for t in prompt)
        out = []
        for _ in range(n):
            arr = np.zeros((1, SEQ), np.int64)
            arr[0, :len(toks)] = toks
            (logits,) = predictor.run([arr])
            t = int(np.argmax(logits[0, len(toks) - 1]))
            toks.append(t)
            out.append(t)
            if eos is not None and t == eos:
                break
        return out
    return _decode


def _prompts(n, lo=3, hi=12, seed=0):
    rng = np.random.RandomState(seed)
    return [rng.randint(1, CFG.vocab_size, rng.randint(lo, hi))
            .astype(np.int64) for _ in range(n)]


# -- PagedKVCache unit tests -------------------------------------------------


def _cache(num_pages=8, page_size=4, max_seqs=3, maxp=4):
    return PagedKVCache(2, 4, 8, num_pages=num_pages, page_size=page_size,
                        max_seqs=max_seqs, max_pages_per_seq=maxp)


def test_kvcache_alloc_free_reuse():
    c = _cache()
    s0 = c.allocate_slot(7)     # 2 pages
    s1 = c.allocate_slot(4)     # 1 page
    assert c.free_pages() == 7 - 3
    used_pages = set(c.block_tables[s0][:2]) | {c.block_tables[s1][0]}
    assert 0 not in used_pages and len(used_pages) == 3
    c.check_integrity()
    c.release(s0)
    assert c.free_pages() == 6
    # free-list reuse: the released pages are handed out again
    s2 = c.allocate_slot(8)     # 2 pages
    assert set(c.block_tables[s2][:2]) <= used_pages | set(range(1, 8))
    c.check_integrity()
    assert {s0, s2} & {s1} == set()   # s1 untouched throughout
    assert int(c.block_tables[s1][0]) in used_pages


def test_kvcache_exhaustion_raises():
    c = _cache(num_pages=4, max_seqs=4)   # 3 usable pages
    c.allocate_slot(8)                    # 2 pages
    with pytest.raises(PagePoolExhausted):
        c.allocate_slot(8)                # needs 2, only 1 free
    c.allocate_slot(4)                    # 1 page fits
    with pytest.raises(PagePoolExhausted):
        c.allocate_slot(1)
    c.check_integrity()


def test_kvcache_ensure_capacity_and_eviction():
    c = _cache(num_pages=5, max_seqs=2)   # 4 usable
    s0 = c.allocate_slot(4)               # 1 page
    s1 = c.allocate_slot(9)               # 3 pages -> pool dry
    c.lengths[s0] = 4
    with pytest.raises(PagePoolExhausted):
        c.ensure_capacity(s0, 5)          # needs page 2, none free
    c.evict(s1)
    assert c.stats()["evictions_total"] == 1
    c.ensure_capacity(s0, 5)              # now succeeds
    assert c.pages_needed(5) == 2
    c.check_integrity()
    # the evicted slot is reusable and its table row was reset to junk
    assert not c.is_active(s1)
    assert int(c.block_tables[s1].sum()) == 0


def test_kvcache_never_fits_check():
    c = _cache(num_pages=4, maxp=2, page_size=4)
    assert c.can_fit_ever(8)
    assert not c.can_fit_ever(9)          # > max_pages_per_seq window
    assert not c.can_fit_ever(1000)


# -- paged-attention kernel vs dense oracle ---------------------------------


def test_paged_attention_matches_dense():
    import jax.numpy as jnp

    from paddle_tpu.kernels.paged_attention import (kv_cache_write,
                                                    paged_attention)

    rng = np.random.RandomState(1)
    B, H, D, P, ps, maxp = 3, 4, 8, 16, 4, 4
    kp = jnp.zeros((H, P, ps, D), jnp.float32)
    vp = jnp.zeros((H, P, ps, D), jnp.float32)
    lens = np.array([5, 9, 1], np.int32)
    tables = np.zeros((B, maxp), np.int32)
    nxt = 1
    for b in range(B):
        for i in range(-(-int(lens[b]) // ps)):
            tables[b, i] = nxt
            nxt += 1
    S = 12
    k_new = rng.randn(B, S, H, D).astype(np.float32)
    v_new = rng.randn(B, S, H, D).astype(np.float32)
    kp, vp = kv_cache_write(kp, vp, jnp.asarray(k_new), jnp.asarray(v_new),
                            jnp.asarray(tables), jnp.zeros(B, jnp.int32),
                            jnp.asarray(lens))
    q = rng.randn(B, H, D).astype(np.float32)
    out = np.asarray(paged_attention(jnp.asarray(q), kp, vp,
                                     jnp.asarray(lens), jnp.asarray(tables)))
    for b in range(B):
        L = int(lens[b])
        s = np.einsum("hd,lhd->hl", q[b] / np.sqrt(D), k_new[b, :L])
        p = np.exp(s - s.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        np.testing.assert_allclose(
            out[b], np.einsum("hl,lhd->hd", p, v_new[b, :L]),
            rtol=1e-5, atol=1e-5)
    # length-0 rows are defined as zeros, never NaN
    z = np.asarray(paged_attention(jnp.asarray(q), kp, vp,
                                   jnp.zeros(B, jnp.int32),
                                   jnp.asarray(tables)))
    assert np.all(np.isfinite(z)) and np.allclose(z, 0.0)


def test_junk_page_isolation():
    """Invalid rows (idle lanes, batch padding) write to page 0 and
    MUST NOT touch any allocated page."""
    import jax.numpy as jnp

    from paddle_tpu.kernels.paged_attention import kv_cache_write

    H, P, ps, D = 2, 6, 4, 4
    kp = jnp.zeros((H, P, ps, D), jnp.float32)
    vp = jnp.zeros((H, P, ps, D), jnp.float32)
    tables = np.array([[1, 2], [3, 4]], np.int32)
    k_new = np.ones((2, 1, H, D), np.float32)
    kp2, _ = kv_cache_write(kp, vp, jnp.asarray(k_new),
                            jnp.asarray(k_new), jnp.asarray(tables),
                            jnp.zeros(2, jnp.int32),
                            jnp.asarray([0, 0], np.int32))  # all invalid
    assert np.allclose(np.asarray(kp2)[:, 1:], 0.0)          # pages intact


# -- proglint: the new ops are first-class ----------------------------------


def test_generation_programs_pass_proglint():
    from paddle_tpu.analysis import analyze_program

    geom = CacheGeometry(num_pages=32, page_size=4, max_pages_per_seq=16)
    for prog, fetches in (build_decode_program(CFG, geom),
                          build_prefill_program(CFG, 16, geom)):
        rep = analyze_program(prog,
                              fetch_names=[v.name for v in fetches])
        assert rep.ok, [d.format() for d in rep.diagnostics]
        assert not rep.diagnostics, [d.format() for d in rep.diagnostics]
        # the satellite contract: no lint_suppress escape hatch
        for blk in prog.blocks:
            for op in blk.ops:
                assert "lint_suppress" not in (op.attrs or {})


def test_registry_knows_paged_ops():
    from paddle_tpu.core.registry import has_op

    assert has_op("paged_attention")
    assert has_op("kv_cache_write")


# -- engine correctness ------------------------------------------------------


def _engine(predictor, **kw):
    kw.setdefault("page_size", 4)
    kw.setdefault("num_pages", 64)
    kw.setdefault("max_decode_batch", 4)
    kw.setdefault("prefill_buckets", (8, 16, 32))
    return GenerationEngine(predictor, CFG, **kw)


def test_continuous_equals_naive_greedy(predictor, oracle):
    """THE acceptance test: concurrent continuous-batching decode ==
    per-request naive re-prefill decode, token for token, through slot
    join/leave churn (5 requests on 4 lanes, different lengths)."""
    with _engine(predictor) as eng:
        prompts = _prompts(5)
        new = [3, 6, 4, 7, 5]
        streams = [eng.submit(p, max_new_tokens=n)
                   for p, n in zip(prompts, new)]
        results = [s.result(timeout=300) for s in streams]
    for p, n, got in zip(prompts, new, results):
        assert got == oracle(p, n), (list(p), n)
    snap = eng.stats()
    assert snap["responses_total"] == 5
    assert snap["decode_steps_total"] >= max(new) - 1
    assert snap["cache"]["pages_in_use"] == 0    # all pages returned


def test_streaming_first_token_before_completion(predictor):
    """Streamed tokens arrive DURING generation: after the first token
    is yielded, the request must not be finished yet (max_new is large
    enough that decode is still running)."""
    with _engine(predictor) as eng:
        stream = eng.submit(_prompts(1)[0], max_new_tokens=12)
        it = iter(stream)
        first = next(it)
        assert isinstance(first, int)
        assert not stream.done(), \
            "first token must stream out before generation completes"
        rest = list(it)
        assert stream.done()
        assert [first] + rest == stream.tokens
        assert len(rest) == 11
        assert stream.finish_reason == "length"


def test_eos_stops_early(predictor, oracle):
    p = _prompts(1, seed=3)[0]
    # pick the oracle's 2nd generated token as the EOS id
    want = oracle(p, 8)
    eos = want[2]
    with _engine(predictor) as eng:
        got = eng.generate(p, max_new_tokens=8, eos_id=eos)
        st = eng.stats()
    assert got == want[:3]          # eos token included, then stop
    assert st["responses_total"] == 1


def test_overloaded_before_prefill_on_pool_exhaustion(predictor):
    """Satellite: a request the pool can NEVER hold is rejected with
    Overloaded at submit — before any prefill work happens."""
    with _engine(predictor, num_pages=4) as eng:   # 3 usable pages = 12 toks
        with pytest.raises(Overloaded):
            eng.submit(np.arange(1, 9, dtype=np.int64), max_new_tokens=8)
        assert eng.stats()["prefill_batches_total"] == 0
        # a fitting request still serves
        assert len(eng.generate([5, 6, 7], max_new_tokens=3,
                                timeout=300)) == 3


def test_queue_overload(predictor):
    with _engine(predictor, queue_capacity=2, start=False) as eng:
        eng.submit([1, 2, 3], max_new_tokens=2)
        eng.submit([1, 2, 3], max_new_tokens=2)
        with pytest.raises(Overloaded):
            eng.submit([1, 2, 3], max_new_tokens=2)


def test_deadline_in_queue(predictor):
    with _engine(predictor, start=False) as eng:
        s = eng.submit([1, 2, 3], max_new_tokens=2, deadline_ms=5)
        time.sleep(0.05)
        eng.start()
        with pytest.raises(DeadlineExceeded):
            s.result(timeout=60)
        assert s.finish_reason == "deadline"


def test_cancel_stream(predictor):
    with _engine(predictor) as eng:
        s = eng.submit(_prompts(1)[0], max_new_tokens=40)
        it = iter(s)
        next(it)
        assert s.cancel()
        t0 = time.time()
        while not s.done() and time.time() - t0 < 60:
            time.sleep(0.01)
        assert s.finish_reason == "cancelled"
        # pages come back
        t0 = time.time()
        while eng.stats()["cache"]["pages_in_use"] and time.time() - t0 < 60:
            time.sleep(0.01)
        assert eng.stats()["cache"]["pages_in_use"] == 0


def test_eviction_resume_correctness(predictor, oracle):
    """Pool pressure mid-decode evicts the youngest sequence; its
    request re-queues and resumes via re-prefill — and STILL produces
    exactly the oracle tokens. Block tables stay consistent throughout
    (check_integrity after every completion)."""
    # 15 usable pages of 4 tokens; 3 lanes x (prompt ~10 + 24 new)
    # cannot all fit -> guaranteed evictions
    with _engine(predictor, num_pages=16, max_decode_batch=3) as eng:
        prompts = _prompts(3, lo=8, hi=12, seed=7)
        streams = [eng.submit(p, max_new_tokens=24) for p in prompts]
        results = [s.result(timeout=600) for s in streams]
        st = eng.stats()
        eng.cache.check_integrity()
    assert st["evicted_total"] >= 1, "test must actually exercise eviction"
    for p, got in zip(prompts, results):
        assert got == oracle(p, 24), list(p)
    assert st["cache"]["pages_in_use"] == 0


def test_block_table_integrity_under_join_leave(predictor, oracle):
    """Concurrent join/leave churn: staggered submissions with varied
    lengths; every result matches its oracle and the page accounting
    balances at the end."""
    with _engine(predictor, num_pages=32) as eng:
        prompts = _prompts(10, seed=11)
        lens = [2, 5, 3, 7, 4, 6, 2, 8, 3, 5]
        streams = []

        def submitter(i):
            time.sleep(0.002 * i)
            streams.append((i, eng.submit(prompts[i],
                                          max_new_tokens=lens[i])))

        threads = [threading.Thread(target=submitter, args=(i,))
                   for i in range(10)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        results = {i: s.result(timeout=600) for i, s in streams}
        eng.cache.check_integrity()
        st = eng.stats()
    for i in range(10):
        assert results[i] == oracle(prompts[i], lens[i]), i
    assert st["cache"]["pages_in_use"] == 0
    assert st["responses_total"] == 10


def test_drain_close(predictor):
    with _engine(predictor) as eng:
        s = eng.submit(_prompts(1)[0], max_new_tokens=6)
        eng.close(drain=True)
        assert len(s.result(timeout=300)) == 6    # drain finishes actives
        with pytest.raises(Exception):
            eng.submit([1], max_new_tokens=1)     # admission closed


def test_decode_is_one_bound_dispatch(predictor):
    """The per-step hot path (tentpole acceptance): the RAGGED engine
    holds exactly ONE BoundStep for its whole life — prefill chunks,
    decode rows and mixed batches all reuse it; no new executables,
    no new bound entries, no prefill-bucket ladder."""
    with _engine(predictor) as eng:
        assert eng.mode == "ragged"
        eng.generate(_prompts(1)[0], max_new_tokens=4, timeout=300)
        bound = eng._ragged_bound
        assert bound is not None
        assert eng._decode_bound is None and not eng._prefill_progs
        compiles_before = eng._exe.cache_stats()["jit_compiles"]
        eng.generate(_prompts(1, seed=5)[0], max_new_tokens=6, timeout=300)
        assert eng._ragged_bound is bound
        compiles_after = eng._exe.cache_stats()["jit_compiles"]
        # prefill AND decode of a fresh request: zero new executables
        assert compiles_after == compiles_before


def test_metrics_join_unified_registry(predictor):
    from paddle_tpu import observability

    with _engine(predictor) as eng:
        eng.generate(_prompts(1)[0], max_new_tokens=3, timeout=300)
        text = observability.to_prometheus_text()
    assert "paddle_generation_requests_total" in text
    assert "paddle_generation_cache_page_utilization" in text
    assert "paddle_generation_ttft_ms_p50" in text
    assert "paddle_generation_decode_occupancy" in text
    snap = eng.stats()
    assert snap["ttft_ms"]["count"] >= 1
    assert snap["decode_tokens_per_s"] > 0


def test_decode_steps_join_request_trace(predictor):
    """Tentpole contract: with tracing on, ragged steps carry
    flow_from arrows back to the request's submit span (prefill
    chunks, decode and verify rows all live in the SAME step spans)."""
    from paddle_tpu.observability import flight

    fluid.set_flags({"observability_tracing": True})
    try:
        flight.clear()
        with _engine(predictor) as eng:
            eng.generate(_prompts(1, seed=17)[0], max_new_tokens=4,
                         timeout=300)
        evs = [e for e in flight.entries()
               if "generation" in str(e.get("name", ""))]
        names = {e["name"] for e in evs}
        assert "generation/step" in names
        subs = [e for e in evs if e["name"] == "generation/submit"]
        steps = [e for e in evs if e["name"] == "generation/step"]
        assert subs and steps
        sub_ids = {s["span_id"] for s in subs}
        assert any(set(e.get("flow_from") or []) & sub_ids for e in steps)
    finally:
        fluid.set_flags({"observability_tracing": False})


# -- HTTP /v1/generate -------------------------------------------------------


def test_http_generate_streams_before_done(predictor, oracle):
    serve = ServingEngine(predictor, start=False)
    with _engine(predictor) as eng:
        srv = ServingServer(serve, generation_engine=eng)
        try:
            p = _prompts(1, seed=13)[0]
            want = oracle(p, 10)
            conn = http.client.HTTPConnection(srv.host, srv.port,
                                              timeout=300)
            conn.request("POST", "/v1/generate", json.dumps(
                {"tokens": [int(t) for t in p], "max_new_tokens": 10}),
                {"Content-Type": "application/json"})
            resp = conn.getresponse()
            assert resp.status == 200
            assert resp.getheader("Content-Type") == "application/x-ndjson"
            lines = []
            first_line = json.loads(resp.readline())
            # acceptance criterion: the FIRST token arrives while the
            # engine is still generating this request
            assert first_line["token"] == want[0]
            assert not eng._closed
            lines.append(first_line)
            for raw in resp:
                if raw.strip():
                    lines.append(json.loads(raw))
            conn.close()
            assert lines[-1]["done"] and lines[-1]["finish_reason"] == "length"
            got = [ln["token"] for ln in lines[:-1]]
            assert got == want
        finally:
            srv.close()
            serve.close()


def test_http_generate_nonstream_and_errors(predictor):
    serve = ServingEngine(predictor, start=False)
    with _engine(predictor) as eng:
        srv = ServingServer(serve, generation_engine=eng)
        try:
            conn = http.client.HTTPConnection(srv.host, srv.port,
                                              timeout=300)
            conn.request("POST", "/v1/generate", json.dumps(
                {"tokens": [3, 4, 5], "max_new_tokens": 4,
                 "stream": False}))
            r = conn.getresponse()
            body = json.loads(r.read())
            assert r.status == 200 and len(body["tokens"]) == 4
            # malformed: empty tokens
            conn.request("POST", "/v1/generate",
                         json.dumps({"tokens": []}))
            r = conn.getresponse()
            assert r.status == 400
            r.read()
            # malformed: non-numeric deadline
            conn.request("POST", "/v1/generate", json.dumps(
                {"tokens": [1], "deadline_ms": "soon"}))
            r = conn.getresponse()
            assert r.status == 400
            r.read()
            conn.close()
        finally:
            srv.close()
            serve.close()


def test_http_generate_404_without_engine(predictor):
    serve = ServingEngine(predictor, start=False)
    srv = ServingServer(serve)
    try:
        conn = http.client.HTTPConnection(srv.host, srv.port, timeout=60)
        conn.request("POST", "/v1/generate",
                     json.dumps({"tokens": [1, 2]}))
        r = conn.getresponse()
        assert r.status == 404
        r.read()
        conn.close()
    finally:
        srv.close()
        serve.close()
