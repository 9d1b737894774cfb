"""paddle_tpu.ragged: the mixed prefill+decode executable, speculative
decoding, and int8-quantized KV pages (ISSUE 13).

Correctness anchors:
  * kernel — ragged_paged_attention vs a numpy dense oracle, f32 AND
    bf16, with prefill chunks, decode rows and len-0 rows side by side
    in ONE batch (len-0 defined 0, never NaN);
  * engine — the ragged engine is token-identical to BOTH the naive
    re-prefill oracle and the retained two-lane engine, through churn
    and eviction/resume;
  * speculative decoding — greedy-identical whatever the draft
    proposes (full-replica, truncated, or garbage drafts);
  * int8 KV — >= 2x resident sequences at the fp32 byte budget, and
    the quantized kernel within the blockwise error bound;
  * ONE BoundStep — the engine's whole life runs through a single
    generation-tagged dispatch object.
"""

import http.client
import importlib
import json
import threading
import time

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import generation
from paddle_tpu.generation import (CacheGeometry, DraftModel,
                                   GenerationEngine, HostDraft,
                                   PagedKVCache)
from paddle_tpu.generation.model import (GPTConfig,
                                         build_lm_program,
                                         build_ragged_step_program)
from paddle_tpu.inference import Config, create_predictor
from paddle_tpu.serving import ServingEngine, ServingServer

CFG = GPTConfig(vocab_size=97, hidden_size=32, num_layers=2, num_heads=4,
                ffn_size=64, max_position=64, hidden_dropout=0.0,
                attention_dropout=0.0)
SEQ = 48


@pytest.fixture(scope="module")
def lm_dir(tmp_path_factory):
    d = str(tmp_path_factory.mktemp("ragged_lm"))
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
    def _decode(prompt, n):
        toks = list(int(t) for t in prompt)
        out = []
        for _ in range(n):
            arr = np.zeros((1, SEQ), np.int64)
            arr[0, :len(toks)] = toks
            (logits,) = predictor.run([arr])
            t = int(np.argmax(logits[0, len(toks) - 1]))
            toks.append(t)
            out.append(t)
        return out
    return _decode


def _prompts(n, lo=3, hi=12, seed=0):
    rng = np.random.RandomState(seed)
    return [rng.randint(1, CFG.vocab_size, rng.randint(lo, hi))
            .astype(np.int64) for _ in range(n)]


# -- kernel vs dense oracle --------------------------------------------------


def _mixed_batch(dt, seed=1):
    """One ragged batch holding a prefill chunk (start 0), a decode
    row over a 6-token prefix, a mid-prompt chunk, and a len-0 idle
    lane — the four row kinds one engine step mixes."""
    import jax.numpy as jnp

    from paddle_tpu.kernels.paged_attention import kv_cache_write

    rng = np.random.RandomState(seed)
    B, C, H, D, P, ps, maxp = 4, 5, 4, 8, 24, 4, 5
    kp = jnp.zeros((H, P, ps, D), dt)
    vp = jnp.zeros((H, P, ps, D), dt)
    tables = np.zeros((B, maxp), np.int32)
    tables[0, :2] = [1, 2]
    tables[1, :2] = [3, 4]
    tables[2, :4] = [5, 6, 7, 8]
    starts = np.array([0, 6, 9, 0], np.int32)
    nvalid = np.array([5, 1, 3, 0], np.int32)
    # prefixes already in the pool: row 1 has 6 tokens, row 2 has 9
    pre = {1: rng.randn(1, 6, H, D).astype(np.float32),
           2: rng.randn(1, 9, H, D).astype(np.float32)}
    prev = {}
    for b, kv in pre.items():
        vv = rng.randn(*kv.shape).astype(np.float32)
        prev[b] = (kv, vv)
        kp, vp = kv_cache_write(
            kp, vp, jnp.asarray(kv, dt), jnp.asarray(vv, dt),
            jnp.asarray(tables[b:b + 1]), jnp.zeros(1, jnp.int32),
            jnp.asarray([kv.shape[1]], np.int32))
    k_new = rng.randn(B, C, H, D).astype(np.float32)
    v_new = rng.randn(B, C, H, D).astype(np.float32)
    kp, vp = kv_cache_write(kp, vp, jnp.asarray(k_new, dt),
                            jnp.asarray(v_new, dt), jnp.asarray(tables),
                            jnp.asarray(starts), jnp.asarray(nvalid))
    q = rng.randn(B, C, H, D).astype(np.float32)
    return (q, kp, vp, starts, nvalid, tables, k_new, v_new, prev, D)


def _dense_row(q, keys, vals, D):
    s = np.einsum("hd,lhd->hl", q / np.sqrt(D), keys)
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    return np.einsum("hl,lhd->hd", p, vals)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_ragged_kernel_vs_dense_oracle(dtype):
    import jax.numpy as jnp

    from paddle_tpu.kernels.ragged_paged_attention import (
        ragged_paged_attention)

    dt = jnp.float32 if dtype == "float32" else jnp.bfloat16
    (q, kp, vp, starts, nvalid, tables, k_new, v_new, prev, D) = \
        _mixed_batch(dt)
    out = np.asarray(ragged_paged_attention(
        jnp.asarray(q, dt), kp, vp, jnp.asarray(starts),
        jnp.asarray(nvalid), jnp.asarray(tables))).astype(np.float32)
    tol = dict(rtol=1e-5, atol=1e-5) if dtype == "float32" \
        else dict(rtol=0.0, atol=0.05)
    for b in range(len(starts)):
        pre_k, pre_v = prev.get(b, (np.zeros((1, 0, *q.shape[2:]),
                                             np.float32),) * 2)
        for j in range(int(nvalid[b])):
            keys = np.concatenate([pre_k[0], k_new[b, :j + 1]], 0)
            vals = np.concatenate([pre_v[0], v_new[b, :j + 1]], 0)
            if dtype == "bfloat16":   # the pool rounds K/V to bf16
                keys = keys.astype(jnp.bfloat16).astype(np.float32)
                vals = vals.astype(jnp.bfloat16).astype(np.float32)
            np.testing.assert_allclose(
                out[b, j], _dense_row(q[b, j], keys, vals, D), **tol)
        # rows past num_valid — and the whole len-0 idle lane — are
        # DEFINED zero, never NaN
        assert np.all(np.isfinite(out[b]))
        assert np.allclose(out[b, int(nvalid[b]):], 0.0)


def test_ragged_kernel_interpret_matches_reference(monkeypatch):
    """The Pallas kernel body (interpreter mode) agrees with the
    pure-JAX reference on the same mixed batch — the CPU-CI proof the
    TPU lowering computes the oracle's numbers."""
    import jax.numpy as jnp

    from paddle_tpu.kernels.ragged_paged_attention import (
        ragged_paged_attention)

    (q, kp, vp, starts, nvalid, tables, *_rest) = _mixed_batch(jnp.float32)
    ref = np.asarray(ragged_paged_attention(
        jnp.asarray(q), kp, vp, jnp.asarray(starts),
        jnp.asarray(nvalid), jnp.asarray(tables)))
    monkeypatch.setenv("PADDLE_TPU_KERNEL_INTERPRET", "1")
    pal = np.asarray(ragged_paged_attention(
        jnp.asarray(q), kp, vp, jnp.asarray(starts),
        jnp.asarray(nvalid), jnp.asarray(tables)))
    np.testing.assert_allclose(pal, ref, rtol=1e-5, atol=1e-5)


def test_quantized_kernel_error_bound_and_junk_isolation():
    """int8 pages: the quantized ragged attention stays within the
    kernels/quant.py blockwise bound of the fp32 result; invalid rows
    write only the junk page + its scale plane."""
    import jax.numpy as jnp

    from paddle_tpu.kernels.quant import blockwise_error_bound
    from paddle_tpu.kernels.ragged_paged_attention import (
        quantized_kv_cache_write, ragged_paged_attention)

    (q, kp, vp, starts, nvalid, tables, k_new, v_new, prev, D) = \
        _mixed_batch(jnp.float32)
    ref = np.asarray(ragged_paged_attention(
        jnp.asarray(q), kp, vp, jnp.asarray(starts),
        jnp.asarray(nvalid), jnp.asarray(tables)))
    H, P, ps, _ = kp.shape
    kq = jnp.zeros((H, P, ps, D), jnp.int8)
    vq = jnp.zeros((H, P, ps, D), jnp.int8)
    ks = jnp.ones((H, P, ps), jnp.float32)
    vs = jnp.ones((H, P, ps), jnp.float32)
    for b, (pk, pv) in prev.items():
        kq, vq, ks, vs = quantized_kv_cache_write(
            kq, vq, ks, vs, jnp.asarray(pk), jnp.asarray(pv),
            jnp.asarray(tables[b:b + 1]), jnp.zeros(1, jnp.int32),
            jnp.asarray([pk.shape[1]], np.int32))
    kq, vq, ks, vs = quantized_kv_cache_write(
        kq, vq, ks, vs, jnp.asarray(k_new), jnp.asarray(v_new),
        jnp.asarray(tables), jnp.asarray(starts), jnp.asarray(nvalid))
    out = np.asarray(ragged_paged_attention(
        jnp.asarray(q), kq, vq, jnp.asarray(starts),
        jnp.asarray(nvalid), jnp.asarray(tables),
        k_scales=ks, v_scales=vs))
    # attention output is a convex combination of dequantized V rows
    # perturbed by quantized-K score shifts: a loose but principled
    # bound is a few multiples of the worst per-row quantization step
    bound = 8 * max(blockwise_error_bound(k_new, D),
                    blockwise_error_bound(v_new, D))
    assert np.abs(out - ref).max() <= bound
    # junk isolation: an all-invalid write touches only page 0
    kq2 = jnp.zeros((H, P, ps, D), jnp.int8)
    ks2 = jnp.ones((H, P, ps), jnp.float32)
    kq2b, _vq2, ks2b, _vs2 = quantized_kv_cache_write(
        kq2, kq2, ks2, ks2, jnp.asarray(k_new), jnp.asarray(v_new),
        jnp.asarray(tables), jnp.asarray(starts),
        jnp.zeros(len(starts), np.int32))
    assert np.all(np.asarray(kq2b)[:, 1:] == 0)
    assert np.allclose(np.asarray(ks2b)[:, 1:], 1.0)


def _pool_batch(case):
    """A ragged batch drawn straight into the pool (no kv_cache_write):
    every lane's live pages hold random K/V, the rest of the pool and
    every table entry past a lane's live pages whatever ``case`` says.
    Shapes are small but real enough for the interpreter to walk the
    kernel's blocks: 2 pages a block (the test shrinks the kernel's
    VMEM budget), so 40 keys span three blocks."""
    rng = np.random.RandomState(7)
    ps, D, KVH, P, maxp = 8, 16, 2, 40, 12
    H, C = KVH * case.get("group", 1), case.get("C", 4)
    starts = np.asarray(case["starts"], np.int32)
    nvalid = np.asarray(case["nvalid"], np.int32)
    B = len(starts)
    k = rng.randn(KVH, P, ps, D).astype(np.float32)
    v = rng.randn(KVH, P, ps, D).astype(np.float32)
    dead = case.get("dead_page", 0)
    if case.get("nan_dead"):
        k[:, dead] = np.nan
        v[:, dead] = np.nan
    tables = np.full((B, maxp), dead, np.int32)
    free = [p for p in rng.permutation(np.arange(1, P)) if p != dead]
    for b in range(B):
        need = -(-int(starts[b] + nvalid[b]) // ps) if nvalid[b] else 0
        tables[b, :need] = [free.pop() for _ in range(need)]
    q = rng.randn(B, C, H, D).astype(np.float32)
    return q, k, v, starts, nvalid, tables


_BLOCK_CASES = {
    # 40 keys = 5 pages = three blocks of two pages
    "three_blocks": dict(starts=[36, 0], nvalid=[4, 4]),
    # 21 keys end inside page 2 and inside block 1; 9 inside page 1
    "mid_block_mid_page": dict(starts=[18, 8], nvalid=[3, 1]),
    # decode only: C = 1 pads to the 8-row tile
    "decode_only": dict(C=1, starts=[33, 5, 16], nvalid=[1, 1, 1]),
    # two query heads a KV head
    "grouped_query": dict(group=2, starts=[20, 0], nvalid=[4, 3]),
    "int8_pages": dict(int8=True, starts=[36, 3], nvalid=[4, 2]),
    # entries past the live pages name a page full of NaN: never used
    "nan_dead_entries": dict(nan_dead=True, dead_page=9,
                             starts=[17, 0, 30], nvalid=[4, 0, 2]),
    "all_idle": dict(starts=[0, 12, 0], nvalid=[0, 0, 0]),
}


@pytest.mark.parametrize("case", sorted(_BLOCK_CASES))
def test_ragged_kernel_blocks_interpret(case, monkeypatch):
    """The kernel body (interpreter mode) walking a lane's live keys in
    blocks of several pages, against _reference_ragged and the dense
    oracle: contexts of three blocks, ends inside a block and a page,
    C = 1, grouped queries, int8 pages, dead table entries, idle
    lanes."""
    import jax.numpy as jnp

    from paddle_tpu.kernels import quant

    # by module path: the package rebinds the name to the function
    rpa = importlib.import_module(
        "paddle_tpu.kernels.ragged_paged_attention")
    spec = _BLOCK_CASES[case]
    q, k, v, starts, nvalid, tables = _pool_batch(spec)
    KVH, P, ps, D = k.shape
    # K and V of two pages, two buffers each (head dim padded to a
    # lane tile): blocks of two pages
    monkeypatch.setattr(rpa, "_BLOCK_VMEM_BYTES",
                        2 * 2 * 2 * KVH * ps * 128 * 4)
    assert rpa._block_pages(KVH, ps, 128, 4, 8, tables.shape[1]) == 2
    scales = {}
    kp, vp = jnp.asarray(k), jnp.asarray(v)
    if spec.get("int8"):
        (kp, ks), (vp, vs) = (quant.blockwise_quantize(
            jnp.asarray(a).reshape(-1, D)) for a in (k, v))
        kp, vp = kp.reshape(k.shape), vp.reshape(v.shape)
        scales = dict(k_scales=ks.reshape(KVH, P, ps),
                      v_scales=vs.reshape(KVH, P, ps))
        # what the pool holds is the oracle's K/V
        k = np.asarray(quant.blockwise_dequantize(
            kp.reshape(-1, D).astype(jnp.float32), ks)).reshape(k.shape)
        v = np.asarray(quant.blockwise_dequantize(
            vp.reshape(-1, D).astype(jnp.float32), vs)).reshape(v.shape)
    args = (jnp.asarray(q), kp, vp, jnp.asarray(starts),
            jnp.asarray(nvalid), jnp.asarray(tables))
    monkeypatch.setenv("PADDLE_TPU_KERNEL_INTERPRET", "1")
    out = np.asarray(rpa.ragged_paged_attention(*args, **scales))
    assert np.all(np.isfinite(out))
    group = q.shape[2] // KVH
    for b in range(len(starts)):
        total = int(starts[b] + nvalid[b])
        pages = tables[b, :-(-total // ps)]
        keys = k[:, pages].reshape(KVH, -1, D).repeat(group, 0)
        vals = v[:, pages].reshape(KVH, -1, D).repeat(group, 0)
        for j in range(int(nvalid[b])):
            n = int(starts[b]) + j + 1
            np.testing.assert_allclose(
                out[b, j], _dense_row(q[b, j], keys[:, :n].swapaxes(0, 1),
                                      vals[:, :n].swapaxes(0, 1), D),
                rtol=2e-5, atol=2e-5)
        assert np.all(out[b, int(nvalid[b]):] == 0.0)
    if case in ("mid_block_mid_page", "nan_dead_entries"):
        # the TPU interpreter starts every buffer as NaN and runs the
        # copies and semaphores as such: what a block's dead tail
        # leaves unwritten in VMEM must not reach the output either
        from jax.experimental.pallas import tpu as pltpu

        tpu = rpa._ragged_pallas(*args, 1.0 / np.sqrt(D), None, None,
                                 pltpu.InterpretParams())
        np.testing.assert_allclose(np.asarray(tpu), out, rtol=1e-6,
                                   atol=1e-6)
    if not spec.get("nan_dead"):    # the reference gathers dead entries too
        ref = np.asarray(rpa._reference_ragged(
            *args, 1.0 / np.sqrt(D), scales.get("k_scales"),
            scales.get("v_scales")))
        np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)


def test_ragged_kernel_grid_ignores_table_width():
    """The work no longer follows the table: the same live pages under
    block tables 8 and 128 entries wide trace to the same grid, and no
    grid dimension is the table's width."""
    import jax
    import jax.numpy as jnp

    rpa = importlib.import_module(
        "paddle_tpu.kernels.ragged_paged_attention")
    B, C, H, D, P, ps = 3, 16, 4, 128, 160, 16

    def grid(maxp):
        args = (jnp.zeros((B, C, H, D)), jnp.zeros((H, P, ps, D)),
                jnp.zeros((H, P, ps, D)), jnp.full((B,), 40, jnp.int32),
                jnp.ones((B,), jnp.int32), jnp.zeros((B, maxp), jnp.int32))
        jaxpr = jax.make_jaxpr(
            lambda *a: rpa._ragged_pallas(*a, 0.1, None, None, False))(*args)
        (inner,) = jaxpr.jaxpr.eqns          # _ragged_pallas is jitted
        (call,) = [e for e in inner.params["jaxpr"].jaxpr.eqns
                   if e.primitive.name == "pallas_call"]
        return tuple(call.params["grid_mapping"].grid)

    assert grid(8) == grid(128)
    assert 8 not in grid(8) and 128 not in grid(128)


# -- proglint + registry -----------------------------------------------------


def test_ragged_programs_pass_proglint():
    from paddle_tpu.analysis import analyze_program

    geom = CacheGeometry(num_pages=32, page_size=4, max_pages_per_seq=16)
    for kv_dtype in ("float32", "int8"):
        prog, fetches = build_ragged_step_program(CFG, geom, 8, kv_dtype)
        rep = analyze_program(prog,
                              fetch_names=[v.name for v in fetches])
        assert rep.ok, [d.format() for d in rep.diagnostics]
        assert not rep.diagnostics, [d.format() for d in rep.diagnostics]
        # the satellite contract: no lint_suppress escape hatch
        for blk in prog.blocks:
            for op in blk.ops:
                assert "lint_suppress" not in (op.attrs or {})


def test_registry_knows_ragged_ops():
    from paddle_tpu.core.registry import has_op

    assert has_op("ragged_paged_attention")
    assert has_op("ragged_paged_attention_q")
    assert has_op("kv_cache_write_q")


# -- engine: ragged vs two-lane vs oracle ------------------------------------


def _engine(predictor, **kw):
    kw.setdefault("page_size", 4)
    kw.setdefault("num_pages", 64)
    kw.setdefault("max_decode_batch", 4)
    kw.setdefault("chunk_tokens", 6)
    return GenerationEngine(predictor, CFG, **kw)


def test_ragged_equals_two_lane_through_churn_eviction(predictor, oracle):
    """THE collapse proof: the one-executable ragged engine emits
    exactly the two-lane engine's tokens (== the naive oracle's)
    through slot churn, pool-pressure eviction and resume — prompts
    longer than the chunk exercise chunked prefill on the way."""
    prompts = _prompts(4, lo=8, hi=14, seed=7)
    outs = {}
    for mode in ("ragged", "two_lane"):
        kw = dict(num_pages=16, max_decode_batch=3, mode=mode)
        if mode == "two_lane":
            kw["prefill_buckets"] = (8, 16, 32)
            kw.pop("chunk_tokens", None)
        with _engine(predictor, **kw) as eng:
            streams = [eng.submit(p, max_new_tokens=18) for p in prompts]
            outs[mode] = [s.result(timeout=600) for s in streams]
            st = eng.stats()
            eng.cache.check_integrity()
        assert st["evicted_total"] >= 1, (mode, "must exercise eviction")
        assert st["cache"]["pages_in_use"] == 0
    assert outs["ragged"] == outs["two_lane"]
    for p, got in zip(prompts, outs["ragged"]):
        assert got == oracle(p, 18), list(p)


def test_chunked_prefill_token_identity(predictor, oracle):
    """A prompt much longer than the chunk prefills across several
    steps and still emits oracle-identical tokens with an intact
    page pool."""
    p = _prompts(1, lo=30, hi=40, seed=9)[0]
    with _engine(predictor, chunk_tokens=4) as eng:
        got = eng.generate(p, max_new_tokens=8, timeout=600)
        st = eng.stats()
    assert got == oracle(p, 8)
    assert st["prefill_chunks_total"] >= -(-int(p.size) // 4)
    assert st["cache"]["pages_in_use"] == 0


def test_one_bound_step_per_step(predictor):
    """Satellite assertion: the engine's whole life — mixed prefill +
    decode + a second request — flows through EXACTLY ONE
    generation-tagged BoundStep, and steps == bound dispatches."""
    from paddle_tpu.runtime import dispatch as rt_dispatch

    before = set(id(b) for b in rt_dispatch.live_bound_steps())
    with _engine(predictor) as eng:
        eng.generate(_prompts(1, seed=21)[0], max_new_tokens=5,
                     timeout=600)
        eng.generate(_prompts(1, seed=22)[0], max_new_tokens=4,
                     timeout=600)
        new = [b for b in rt_dispatch.live_bound_steps()
               if id(b) not in before]
        st = eng.stats()
    assert eng._ragged_bound is not None
    # the engine's ENTIRE life minted exactly one new dispatch object
    assert [b.audit_info()["tag"] for b in new] == \
        ["generation/ragged_step"]
    assert new[0] is eng._ragged_bound
    assert st["ragged_steps_total"] == st["decode_steps_total"]
    assert not eng._prefill_progs and eng._decode_bound is None


def test_attention_page_counters_match_a_hand_count(predictor):
    """attn_live_pages_total counts the pages the step's lanes hold
    (what the kernel walks), attn_table_pages_total the block tables'
    width: one 9-token prompt, 3 new tokens, pages of 4, chunks of 6 on
    4 lanes is four steps over 6, 9, 10 and 11 keys."""
    with _engine(predictor) as eng:
        eng.generate(_prompts(1, lo=9, hi=10, seed=5)[0], max_new_tokens=3,
                     timeout=600)
        st = eng.stats()
        maxp = eng.geom.max_pages_per_seq
    assert st["ragged_steps_total"] == 4
    assert st["attn_live_pages_total"] == 2 + 3 + 3 + 3
    assert st["attn_table_pages_total"] == 4 * 4 * maxp


# -- speculative decoding ----------------------------------------------------


class _GarbageDraft(DraftModel):
    """Adversarial draft: confidently wrong proposals."""

    def propose(self, contexts, k):
        return [np.full(k, 1, np.int64) for _ in contexts]


def test_spec_decode_greedy_equivalence(predictor, oracle):
    """Speculative decoding with a full-replica draft: tokens are
    EXACTLY the plain greedy tokens, and drafts are actually being
    accepted (the speedup mechanism is live, not vacuous)."""
    draft = HostDraft.from_predictor(predictor, CFG)
    prompts = _prompts(3, seed=31)
    with _engine(predictor, spec_tokens=3, draft=draft,
                 chunk_tokens=8) as eng:
        streams = [eng.submit(p, max_new_tokens=10) for p in prompts]
        res = [s.result(timeout=600) for s in streams]
        st = eng.stats()
    for p, got in zip(prompts, res):
        assert got == oracle(p, 10), list(p)
    assert st["spec_proposed_total"] > 0
    assert st["spec_accepted_total"] > 0
    assert st["spec_acceptance_rate"] > 0.5
    assert streams[0].accepted_draft_tokens > 0
    assert streams[0].verified_tokens == 10


def test_spec_decode_garbage_draft_still_greedy(predictor, oracle):
    """Correctness never depends on the draft: an always-wrong draft
    costs acceptance (0) but the emitted stream is still exactly
    greedy."""
    prompts = _prompts(2, seed=37)
    with _engine(predictor, spec_tokens=3, draft=_GarbageDraft(),
                 chunk_tokens=8) as eng:
        res = [eng.generate(p, max_new_tokens=8, timeout=600)
               for p in prompts]
        st = eng.stats()
    for p, got in zip(prompts, res):
        assert got == oracle(p, 8), list(p)
    assert st["spec_proposed_total"] > 0
    assert st["spec_accepted_total"] == 0


@pytest.mark.slow  # eviction-pressure + HTTP round trip; slow-tests CI job
def test_spec_decode_through_eviction_and_http(predictor, oracle):
    """Spec decode under pool pressure (evict/resume) AND through the
    streamed HTTP endpoint stays greedy-identical, with the usage
    fragment reporting accepted-draft vs verified counts."""
    draft = HostDraft.from_predictor(predictor, CFG)
    prompts = _prompts(3, lo=8, hi=12, seed=41)
    with _engine(predictor, num_pages=16, max_decode_batch=3,
                 spec_tokens=3, draft=draft, chunk_tokens=8) as eng:
        serve = ServingEngine(predictor, start=False)
        srv = ServingServer(serve, generation_engine=eng)
        try:
            streams = [eng.submit(p, max_new_tokens=16) for p in prompts]
            res = [s.result(timeout=600) for s in streams]
            st = eng.stats()
            assert st["evicted_total"] >= 1
            for p, got in zip(prompts, res):
                assert got == oracle(p, 16), list(p)
            # HTTP: stream + usage fragment
            p = _prompts(1, seed=43)[0]
            want = oracle(p, 6)
            conn = http.client.HTTPConnection(srv.host, srv.port,
                                              timeout=300)
            conn.request("POST", "/v1/generate", json.dumps(
                {"tokens": [int(t) for t in p], "max_new_tokens": 6,
                 "stream": True}))
            resp = conn.getresponse()
            lines = [json.loads(x) for x in resp if x.strip()]
            conn.close()
            got = [ln["token"] for ln in lines[:-1]]
            tail = lines[-1]
            assert got == want
            assert tail["done"] and "usage" in tail
            assert tail["usage"]["verified_tokens"] == 6
            assert tail["usage"]["prompt_tokens"] == int(p.size)
            assert 0 <= tail["usage"]["accepted_draft_tokens"] <= 6
        finally:
            srv.close()
            serve.close()


def test_http_usage_fragment_nonstream(predictor):
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
            conn.close()
            assert r.status == 200
            u = body["usage"]
            assert u["prompt_tokens"] == 3
            assert u["completion_tokens"] == 4
            assert u["verified_tokens"] == 4
            assert u["accepted_draft_tokens"] == 0   # spec off
        finally:
            srv.close()
            serve.close()


# -- int8 KV pages -----------------------------------------------------------


def test_int8_capacity_arithmetic():
    """The ~2x-resident-sequences claim as deterministic arithmetic:
    at any fp32 pool byte budget, int8 pages (scales included) hold
    >= 2x the sequences."""
    for head_dim in (8, 64, 128):
        f32 = PagedKVCache.page_bytes(4, head_dim, 16, "float32")
        i8 = PagedKVCache.page_bytes(4, head_dim, 16, "int8")
        assert f32 / i8 >= 2.0, (head_dim, f32, i8)
    # and on a live pool
    c = PagedKVCache(2, 4, 8, num_pages=8, page_size=4, max_seqs=2,
                     max_pages_per_seq=4, dtype="int8")
    assert c.quantized and c.pool_bytes() < 8 * 2 * \
        PagedKVCache.page_bytes(4, 8, 4, "float32")
    assert c.stats()["pool_bytes"] == c.pool_bytes()


def test_int8_engine_generates_and_frees_pages(predictor, oracle):
    """The int8 engine serves requests over quantized pages (scale
    planes swap through set_buffers) and returns every page; at this
    tiny scale greedy tokens match fp32 exactly."""
    p = _prompts(1, seed=47)[0]
    with _engine(predictor, kv_dtype="int8") as eng:
        got = eng.generate(p, max_new_tokens=6, timeout=600)
        st = eng.stats()
        eng.cache.check_integrity()
    assert got == oracle(p, 6)
    assert st["cache"]["pages_in_use"] == 0
    assert eng.cache.quantized


@pytest.mark.slow  # builds a tiny LM + HTTP stack; slow-tests CI job
def test_stalled_socket_frees_quantized_pages():
    """Regression (ISSUE 13 satellite): a stalled /v1/generate client
    over the INT8 engine is cancelled and its quantized pages + scale
    planes free at the next step boundary."""
    import os
    import sys
    import tempfile

    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "tools"))
    import traffic_replay

    res = traffic_replay.run_slow_client(
        tempfile.mkdtemp(prefix="pt_slow_client_int8_"),
        {"stall_timeout_s": 0.8, "max_new_tokens": 900,
         "kv_dtype": "int8"})
    assert res["cancelled_total"] >= 1, res
    assert res["active_seqs_after"] == 0, res
    assert res["pages_in_use_after"] == 0, res
    assert res["healthy_tokens"] > 0, res
    assert res["tokens_decoded"] < res["max_new_tokens"], res


# -- draft contract ----------------------------------------------------------


def test_host_draft_contract(predictor):
    """HostDraft: batched proposals respect k and the position
    window; a truncated-layer draft still satisfies the protocol."""
    full = HostDraft.from_predictor(predictor, CFG)
    small = HostDraft.from_predictor(predictor, CFG, num_layers=1)
    ctxs = [np.arange(1, 6, dtype=np.int64),
            np.arange(1, 10, dtype=np.int64)]
    for d in (full, small):
        out = d.propose(ctxs, 3)
        assert len(out) == 2
        assert all(len(o) <= 3 for o in out)
        assert all(0 <= int(t) < CFG.vocab_size for o in out for t in o)
    # near the window edge the draft must not propose past it
    edge = np.ones(CFG.max_position - 2, np.int64)
    out = full.propose([edge], 5)
    assert len(out[0]) <= 1
