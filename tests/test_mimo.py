"""MiMo-V2 decoder serving (ISSUE 35): window layers with a learned sink
beside full-attention layers (different KV-head counts, keys wider than
values), a page store that recycles window pages, partial rotary
positions, a sigmoid router with a selection bias, through the ragged
GenerationEngine.

Everything is checked against the plain reference the benchmark ships
(benchmark/models/mimo_reference.py: float32, one causal pass, attention
in blocks), at tiny widths on the CPU with float32 weights, so the
tolerances are float32 round-off (1e-5 of a logit of order one); 2e-4
leaves room for the longest chain and would not pass a window off by one,
a dropped sink, a rotation of the wrong width or a bias that weighs,
which are errors of 1e-2 and more (the last test plants each).
"""

import importlib.util
import os
import sys

import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.generation import GenerationEngine
from paddle_tpu.generation.kvcache import (PagedKVCache, WindowKind,
                                           window_ring_pages)
from paddle_tpu.generation.model import (CacheGeometry,
                                         build_mimo_step_program)
from paddle_tpu.inference import Config, create_predictor
from paddle_tpu.kernels.ragged_paged_attention import (
    _ragged_pallas, _reference_ragged, split_kv_cache_write)
from paddle_tpu.models.mimo import build_mimo_lm_program
from paddle_tpu.ops.moe import topk_moe

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")


def _load(path, name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(BENCH, path, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load("models", "mimo_reference")
prog = _load("models", "mimo_program")
CFG = _load("tests", "tiny_mimo").CONFIG
MCFG = prog.mimo_config(CFG)
ENG = CFG["engine"]
TOL = 2e-4


def _weights(cfg, seed=0):
    """Seeded float32 weights by the reference's spec, the sink and the
    selection bias placed as the configuration says."""
    rng = np.random.RandomState(seed)
    out = {}
    for name, shape, init in ref.spec(cfg):
        w = (cfg["initializer_range"] * rng.randn(*shape)
             if init == "normal" else np.full(shape, float(init == "ones")))
        out[name] = jnp.asarray(w, jnp.float32)
    return out


WEIGHTS = _weights(CFG)
PLACED = {k: ref.placed(CFG, k, v) for k, v in WEIGHTS.items()}


def _ref_logits(tokens, fault=None):
    t = np.zeros(-(-len(tokens) // 16) * 16, np.int32)
    t[:len(tokens)] = tokens
    return np.asarray(ref.logits_at(CFG, "highest", WEIGHTS, t,
                                    np.arange(len(tokens)), fault=fault))


# -- (a) the kernel: window x sink x split keys, rows that straddle the window ----


def _dense_attention(q, k, v, window, sink, scale):
    """One sequence, every position: q [T, H, D], k [T, KVH, D], v [T,
    KVH, Dv], by the definition."""
    T, H, _ = q.shape
    g = H // k.shape[1]
    k, v = np.repeat(k, g, 1), np.repeat(v, g, 1)
    s = np.einsum("thd,shd->hts", q * scale, k)
    i, j = np.arange(T)[:, None], np.arange(T)[None, :]
    live = j <= i
    if window:
        live &= j > i - window
    s = np.where(live[None], s, -1e30)
    if sink is not None:
        s = np.concatenate(
            [s, np.broadcast_to(sink[:, None, None], (H, T, 1))], -1)
    p = np.exp(s - s.max(-1, keepdims=True))
    p = (p / p.sum(-1, keepdims=True))[..., :T]
    return np.einsum("hts,shd->thd", p, v)


def _paged_case(window, sink, split, seed=0):
    """Three lanes (a prefill chunk whose rows straddle the window's
    edge, a decode row, an idle lane) written chunk by chunk through the
    cache write, window pages recycled as the engine's cache would."""
    rng = np.random.RandomState(seed)
    H, KVH, Dv, ps, C, B, P = 16, 2, 16, 16, 4, 3, 40
    D = 24 if split else 16
    W = window_ring_pages(window, C, ps) if window else 8
    lens, nv = [70, 37, 0], [4, 1, 0]
    T = [a + b for a, b in zip(lens, nv)]
    qs = [rng.randn(t, H, D).astype("f") for t in T]
    ks = [rng.randn(t, KVH, D).astype("f") for t in T]
    vs = [rng.randn(t, KVH, Dv).astype("f") for t in T]
    kp = jnp.zeros((KVH, P, ps + D - Dv, Dv), jnp.float32)
    vp = jnp.zeros((KVH, P, ps, Dv), jnp.float32)
    tables = np.zeros((B, W), np.int32)
    pos, owned, fresh = [0] * B, [{} for _ in range(B)], 1
    while any(pos[b] < T[b] for b in range(B)):
        kn = np.zeros((B, C, KVH, D), "f")
        vn = np.zeros((B, C, KVH, Dv), "f")
        st, n = np.zeros(B, np.int32), np.zeros(B, np.int32)
        for b in range(B):
            c = min(C, T[b] - pos[b])
            if c <= 0:
                continue
            kn[b, :c], vn[b, :c] = ks[b][pos[b]:pos[b] + c], vs[b][pos[b]:pos[b] + c]
            st[b], n[b] = pos[b], c
            for page in range(pos[b] // ps, (pos[b] + c - 1) // ps + 1):
                if page in owned[b]:
                    continue
                if window:
                    keep = max(pos[b] - window + 1, 0) // ps
                    for old in [o for o in owned[b] if o < keep]:
                        del owned[b][old]
                    assert len(owned[b]) < W
                owned[b][page], fresh = fresh, fresh + 1
                tables[b, page % W if window else page] = owned[b][page]
            pos[b] += c
        kp, vp = split_kv_cache_write(
            kp, vp, jnp.asarray(kn), jnp.asarray(vn), jnp.asarray(tables),
            jnp.asarray(st), jnp.asarray(n), ring=bool(window))
    snk = rng.randn(H).astype("f") if sink else None
    scale = 1.0 / np.sqrt(D)
    q, want = np.zeros((B, C, H, D), "f"), np.zeros((B, C, H, Dv), "f")
    for b in range(B):
        if nv[b]:
            q[b, :nv[b]] = qs[b][lens[b]:]
            want[b, :nv[b]] = _dense_attention(
                qs[b], ks[b], vs[b], window, snk, scale)[lens[b]:]
    args = (jnp.asarray(q), kp, vp, jnp.asarray(lens, jnp.int32),
            jnp.asarray(nv, jnp.int32), jnp.asarray(tables))
    return args, scale, None if snk is None else jnp.asarray(snk), want


@pytest.mark.parametrize("split", [False, True], ids=["k16", "k24v16"])
@pytest.mark.parametrize("sink", [False, True], ids=["nosink", "sink"])
@pytest.mark.parametrize("window", [None, 20, 16], ids=["full", "w20", "w16"])
def test_ragged_kernel_window_sink_split_keys(window, sink, split):
    """The Pallas kernel under the interpreter, the reference path and
    the definition agree; the chunk of lane 0 starts at 70 with a window
    of 20: its first row reaches back to key 51 (page 3), its last to 54,
    over pages the ring has turned. Sixteen query heads on two KV heads:
    with ``lean_decode`` lane 1 (one token, 8 rows) takes the lean path
    and lane 0 (4 tokens, 32 rows) the whole chunk's."""
    args, scale, snk, want = _paged_case(window, sink, split)
    got_ref = np.asarray(_reference_ragged(*args, scale, None, None,
                                           window=window, sink=snk))
    np.testing.assert_allclose(got_ref, want, atol=2e-6)
    for stored, lean in ((False, False), (True, False), (False, True)):
        got = np.asarray(_ragged_pallas(
            *args, scale, None, None, interpret=True, window=window,
            sink=snk, stored_products=stored, lean_decode=lean))
        # stored_products rounds q, k, v and the probabilities to bfloat16
        np.testing.assert_allclose(got, want, atol=3e-2 if stored else 2e-6)
    assert not want[2].any() and not got[2].any()       # the idle lane


# -- (b) the router: sigmoid scores, a bias that ranks and does not weigh ---------


def _moe_case(seed=5, T=12, d=32, E=8, f=16):
    rng = np.random.RandomState(seed)
    r = lambda *s: jnp.asarray(0.3 * rng.randn(*s), jnp.float32)  # noqa: E731
    return (np.asarray(r(T, d)), {"router.w": r(d, E), "router.bias": r(E),
                                  "experts_in.w": r(E, d, 2 * f),
                                  "experts_out.w": r(E, f, d)})


def _moe_cfg(held, first):
    return dict(CFG, n_routed_experts=held, num_experts_per_tok=3,
                deployment={"router_experts": 8, "first_expert": first})


def _run_moe(x, p, held, first):
    out, loads = topk_moe(
        jnp.asarray(x), jnp.ones(x.shape[0], bool), p["router.w"],
        p["experts_in.w"][first:first + held],
        p["experts_out.w"][first:first + held], top_k=3, num_experts=8,
        first_expert=first, block_rows=8, score_func="sigmoid",
        select_bias=p["router.bias"])
    return np.asarray(out), np.asarray(loads)


@pytest.mark.parametrize("held,first", [(8, 0), (4, 4), (3, 2)])
def test_sigmoid_router_equals_the_reference(held, first):
    x, p = _moe_case()
    share = dict(p, **{k: p[k][first:first + held]
                       for k in ("experts_in.w", "experts_out.w")})
    want = np.asarray(ref.moe(_moe_cfg(held, first), ref.products("highest"),
                              share, jnp.asarray(x)))
    got, loads = _run_moe(x, p, held, first)
    np.testing.assert_allclose(got, want, atol=TOL)
    assert loads.shape == (held,) and 0 < loads.sum() <= 3 * 12
    # the bias used as a weight is another layer
    wrong = np.asarray(ref.moe(_moe_cfg(held, first), ref.products("highest"),
                               share, jnp.asarray(x), fault="bias_weighs"))
    assert np.abs(wrong - want).max() > 100 * TOL


def test_the_sixteenth_shares_add_up_to_the_uncut_layer():
    """Guide section 4: the chip's share is right if the shares add up.
    The expert parts of the shares of one layer, each computed over the
    same router, sum to the uncut layer; what every chip computes alike
    (the router, the attention, the norms) is in no share's sum and is
    counted once."""
    x, p = _moe_case(seed=9)
    whole, n_all = _run_moe(x, p, 8, 0)
    parts = [_run_moe(x, p, 2, first) for first in range(0, 8, 2)]
    np.testing.assert_allclose(sum(o for o, _n in parts), whole, atol=TOL)
    np.testing.assert_array_equal(
        np.concatenate([n for _o, n in parts]), n_all)
    assert n_all.sum() == 3 * 12        # dropless: every pair is somewhere


# -- (c) the programs against one full forward of the reference, at the logits ----


def _scope_with(weights):
    scope = fluid.Scope()
    for k, v in weights.items():
        scope.set_var(k, jnp.asarray(v))
    return scope


def test_export_program_equals_the_reference():
    seq = 48
    main, _startup, _feeds, fetches = build_mimo_lm_program(MCFG, seq)
    toks = np.random.RandomState(2).randint(1, 89, (2, seq)).astype(np.int64)
    exe = fluid.Executor(fluid.TPUPlace())
    (out,) = exe.run(main, feed={"tokens": toks},
                     fetch_list=[fetches["logits"]],
                     scope=_scope_with(PLACED))
    for b in range(2):
        np.testing.assert_allclose(out[b], _ref_logits(toks[b]), atol=TOL)


def _cache(lanes, scope=None):
    ring = window_ring_pages(MCFG.window, ENG["chunk_tokens"],
                             ENG["page_size"])
    return PagedKVCache(
        len(MCFG.layers_of("full")), MCFG.num_kv_heads, MCFG.v_dim,
        k_dim=MCFG.k_dim, num_pages=ENG["num_pages"],
        page_size=ENG["page_size"], max_seqs=lanes, max_pages_per_seq=8,
        window=WindowKind(len(MCFG.layers_of("window")),
                          MCFG.window_kv_heads, MCFG.window, ring),
        state=MCFG.state_shapes(lanes), scope=scope)


@pytest.mark.parametrize("decode_after", [40, 0])
def test_step_program_through_both_pools_equals_one_forward(decode_after):
    """Chunks of 4 then a token a step (40), and a token a step from the
    start (0: every position is judged), five windows long: through the
    full pages, the window layers' ring as the cache turns it, the
    rotary positions and the sink, against one full forward. The cache's
    invariants hold after every step, and a lane never holds more window
    pages than its ring."""
    rng = np.random.RandomState(11)
    plan = {0: rng.randint(1, 89, 101), 2: rng.randint(1, 89, 57)}
    lanes, chunk = 3, ENG["chunk_tokens"]
    scope = _scope_with(PLACED)
    cache = _cache(lanes, scope.new_scope())
    cache.reset_buffers()
    geom = CacheGeometry(
        num_pages=ENG["num_pages"], page_size=ENG["page_size"],
        max_pages_per_seq=8, window_num_pages=cache.window_num_pages,
        window_pages_per_seq=cache.window.pages_per_seq)
    main, fetches = build_mimo_step_program(MCFG, geom, chunk)
    logits_var = [op for op in main.global_block().ops
                  if op.type == "linear_stored"][-1].outputs["Out"][0]
    exe = fluid.Executor(fluid.TPUPlace())
    slot = {r: cache.allocate_slot(len(seq)) for r, seq in plan.items()}
    done, got = {r: 0 for r in plan}, {r: {} for r in plan}
    while any(done[r] < len(plan[r]) for r in plan):
        toks = np.zeros((lanes, chunk), np.int64)
        ids = np.zeros((lanes, chunk), np.int64)
        nv, pos = np.zeros(lanes, np.int32), np.zeros(lanes, np.int64)
        for r, seq in plan.items():
            c = min(chunk if done[r] < decode_after else 1,
                    len(seq) - done[r])
            s = slot[r]
            toks[s, :c] = seq[done[r]:done[r] + c]
            ids[s, :c] = done[r] + np.arange(c)
            nv[s], pos[s] = c, done[r]
            cache.window_step(s, done[r], c)
        cache.check_integrity()
        assert max(len(h) for h in cache._wpages_of) <= \
            cache.window.pages_per_seq
        feed = {"gen_tokens": toks, "gen_pos_ids": ids,
                "gen_positions": pos, "gen_num_valid": nv,
                "gen_block_tables": cache.block_tables.copy(),
                "gen_block_tables_window": cache.window_tables.copy(),
                **cache.state}
        outs = exe.run(main, feed=feed, fetch_list=fetches + [logits_var],
                       scope=cache.scope, return_numpy=False)
        cache.set_state(outs[1:-1])
        logits = np.asarray(outs[-1])
        for r in plan:
            if nv[slot[r]]:
                done[r] += int(nv[slot[r]])
                got[r][done[r] - 1] = logits[slot[r], 0]
    assert cache.stats()["window_pages_recycled_total"] >= 5 + 2
    for r, seq in plan.items():
        want = _ref_logits(seq)
        assert len(got[r]) == (len(seq) if not decode_after
                               else 10 + len(seq) - 40)
        for at, logits in got[r].items():
            np.testing.assert_allclose(logits, want[at], atol=TOL)
    loads = np.asarray(cache.state["gen_state_moe_loads"])
    assert loads.shape == (3, 4) and 0 < loads.sum() <= 3 * 3 * (101 + 57)


# -- (d) the engine -----------------------------------------------------------------


@pytest.fixture(scope="module")
def predictor(tmp_path_factory):
    d = str(tmp_path_factory.mktemp("mimo_lm"))
    main, _startup, _feeds, fetches = build_mimo_lm_program(MCFG, 16)
    exe = fluid.Executor(fluid.TPUPlace())
    fluid.io.save_inference_model(d, ["tokens"], [fetches["logits"]], exe,
                                  main, program_only=True)
    np.savez(os.path.join(d, "__params__.npz"))
    pred = create_predictor(Config(d))
    for k, v in PLACED.items():
        pred._scope.set_var(k, v)
    return pred


def _engine(predictor, **kw):
    args = dict(mode="ragged", page_size=ENG["page_size"],
                num_pages=ENG["num_pages"], max_decode_batch=ENG["lanes"],
                chunk_tokens=ENG["chunk_tokens"], prefix_cache=False)
    args.update(kw)
    return GenerationEngine(predictor, MCFG, **args)


def test_engine_serves_the_reference_and_recycles_window_pages(predictor):
    """Five requests on three lanes (two lanes are reused), prompts of
    33-90 tokens in chunks of 4, answers past the window of 20: every
    served token is the one the reference puts first (a gap of float32
    round-off), the cache's invariants hold at every token, and a lane
    never holds more than its ring of window pages."""
    eng = _engine(predictor)
    rng = np.random.default_rng(0)
    held = []

    def audit(_tok):
        eng.cache.check_integrity()
        held.append(max(len(h) for h in eng.cache._wpages_of))

    streams = []
    for p, n in ((70, 30), (40, 24), (90, 26), (55, 20), (33, 40)):
        prompt = rng.integers(1, 89, p, dtype=np.int64)
        streams.append((prompt, eng.submit(
            prompt, max_new_tokens=n, eos_id=None, on_token=audit)))
    pairs = [(p, np.asarray(s.result(timeout=600), np.int64))
             for p, s in streams]
    eng.close()
    eng.cache.check_integrity()
    gaps = ref.served_gaps(CFG, WEIGHTS, pairs, 144, 40)
    assert len(gaps) == 140 and max(gaps) < TOL
    st = eng.stats()
    ring = eng.cache.window.pages_per_seq
    assert max(held) <= ring == 3
    assert st["kv_window_pages_recycled_total"] >= 5 * 3
    assert st["kv_pages_resident_window"] == st["kv_pages_resident_full"] == 0
    assert 0 < st["attn_live_pages_window_total"] < \
        st["attn_live_pages_full_total"] == st["attn_live_pages_total"]
    assert st["moe_tokens_routed_total"] % 3 == 0       # 3 expert layers
    assert 0 < st["moe_held_assignments_total"] <= \
        3 * st["moe_tokens_routed_total"]
    assert st["evicted_total"] == 0


def test_engine_evicted_sequence_resumes_identically(predictor):
    """A full pool too small for three long sequences: the youngest is
    preempted, gives both kinds of page back, and resumes to the same
    tokens."""
    rng = np.random.default_rng(3)
    prompts = [rng.integers(1, 89, 30, dtype=np.int64) for _ in range(3)]
    calm = _engine(predictor)
    want = [calm.submit(p, max_new_tokens=40, eos_id=None).result(timeout=600)
            for p in prompts]
    calm.close()
    eng = _engine(predictor, num_pages=12)      # 11 pages: 3 x 5 needed
    streams = [eng.submit(p, max_new_tokens=40, eos_id=None)
               for p in prompts]
    got = [s.result(timeout=600) for s in streams]
    eng.close()
    eng.cache.check_integrity()
    assert got == want
    assert eng.stats()["evicted_total"] >= 1


@pytest.mark.parametrize("kwargs,needs", [
    (dict(prefix_cache=True), "window's last pages"),
    (dict(spec_tokens=2, draft=object()), "recycling held back"),
    (dict(page_store=object()), "window pages on the wire"),
    (dict(mode="two_lane"), "ragged engine"),
    (dict(kv_dtype="int8"), "int8 scale planes"),
])
def test_engine_refuses_what_window_layers_cannot_serve(predictor, kwargs,
                                                        needs):
    with pytest.raises(ValueError, match=needs):
        _engine(predictor, start=False, **kwargs)


# -- (e) each planted fault is far over the tolerance ---------------------------------


@pytest.mark.parametrize("fault", ref.FAULTS)
def test_planted_fault_moves_the_logits(fault):
    toks = np.random.RandomState(4).randint(1, 89, 64)
    assert np.abs(_ref_logits(toks, fault)
                  - _ref_logits(toks)).max() > 100 * TOL


# -- (f) the expert kernel: the same served tokens on both paths ----------------------


def test_engine_serves_the_same_tokens_through_the_expert_kernel(
        tmp_path, monkeypatch):
    """Width 128 with experts of 128 and a window of 4 lanes x 4 tokens,
    where kernels/moe_ffn.py's tiles fit: the engine under the
    interpreter (the three expert layers through ``moe_grouped_ffn``,
    gauge ``moe_kernel_layers`` 3) serves the tokens of the
    ``ragged_dot`` path (gauge 0) and counts the same loads."""
    from paddle_tpu.runtime import dispatch

    cfg = dict(CFG, hidden_size=128, moe_intermediate_size=128, vocab_size=97)
    mcfg = prog.mimo_config(cfg)
    main, _startup, _feeds, fetches = build_mimo_lm_program(mcfg, 16)
    fluid.io.save_inference_model(
        str(tmp_path), ["tokens"], [fetches["logits"]],
        fluid.Executor(fluid.TPUPlace()), main, program_only=True)
    np.savez(os.path.join(str(tmp_path), "__params__.npz"))
    weights = {k: ref.placed(cfg, k, v)
               for k, v in _weights(cfg, seed=5).items()}
    rng = np.random.default_rng(1)
    prompts = [rng.integers(1, 97, n, dtype=np.int64) for n in (23, 9, 30)]
    seen = {}
    for path in ("kernel", "ragged_dot"):
        if path == "kernel":
            monkeypatch.setenv("PADDLE_TPU_KERNEL_INTERPRET", "1")
        else:
            monkeypatch.delenv("PADDLE_TPU_KERNEL_INTERPRET")
        dispatch._SHARED_CACHE.clear()      # compiled by program content
        pred = create_predictor(Config(str(tmp_path)))
        for k, v in weights.items():
            pred._scope.set_var(k, v)
        eng = GenerationEngine(
            pred, mcfg, mode="ragged", page_size=ENG["page_size"],
            num_pages=ENG["num_pages"], max_decode_batch=4,
            chunk_tokens=4, prefix_cache=False)
        streams = [eng.submit(p, max_new_tokens=6, eos_id=None)
                   for p in prompts]
        tokens = [s.result(timeout=600) for s in streams]
        eng.close()
        st = eng.stats()
        seen[path] = (tokens, st["moe_held_assignments_total"],
                      st["moe_kernel_layers"])
    dispatch._SHARED_CACHE.clear()
    assert seen["kernel"][:2] == seen["ragged_dot"][:2]
    assert (seen["kernel"][2], seen["ragged_dot"][2]) == (3, 0)
