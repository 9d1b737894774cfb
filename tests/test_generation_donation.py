"""The page pools are state that the generation steps donate and rewrite
in place (ISSUE 30).

  * program — every builder declares its pools as rewritten state: the
    bound step donates all of them and misses none, and in its optimized
    HLO each pool is aliased onto the output of its OWN layer's cache
    write, with no copy of a pool left;
  * kernel — the page-wise cache write equals a numpy oracle of rows;
  * engine — token streams with donation forced equal those without it
    (eviction, the prefix cache, int8 pools, speculative rows, two_lane),
    a reader on another thread never meets a donated array, two engines
    over one predictor keep separate pools, and pools lost with a failed
    step are replaced.

CPU only: the executor skips donation there unless forced
(`_force_donation`), and XLA's CPU backend honours it.
"""

import functools
import re
import threading

import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.disagg import HostPageStore
from paddle_tpu.generation import GenerationEngine, HostDraft
from paddle_tpu.generation.kvcache import PagedKVCache, pool_names
from paddle_tpu.generation.model import (CacheGeometry, GPTConfig,
                                         HybridConfig, build_decode_program,
                                         build_hybrid_step_program,
                                         build_lm_program,
                                         build_prefill_program,
                                         build_ragged_step_program)
from paddle_tpu.inference import Config, create_predictor
from paddle_tpu.kernels.paged_attention import kv_cache_write
from paddle_tpu.kernels.ragged_paged_attention import (
    quantized_kv_cache_write)

CFG = GPTConfig(vocab_size=97, hidden_size=32, num_layers=3, num_heads=4,
                ffn_size=64, max_position=64, hidden_dropout=0.0,
                attention_dropout=0.0)
HCFG = HybridConfig(
    vocab_size=97, hidden_size=32,
    layer_types=("mamba", "attention", "mamba", "attention"), num_heads=4,
    num_kv_heads=2, mamba_heads=8, mamba_head_dim=8, mamba_state=16,
    moe_experts=8, moe_top_k=3, moe_expert_size=16, shared_size=24,
    max_position=64, mamba_chunk=8, moe_held=4)
GEOM = CacheGeometry(num_pages=24, page_size=4, max_pages_per_seq=16)
LANES, CHUNK, SEQ = 3, 6, 48


# -- (i), (ii) what each builder's step donates, and what XLA made of it -------


def _ragged_feed(chunk=CHUNK):
    return {"gen_tokens": np.zeros((LANES, chunk), np.int64),
            "gen_pos_ids": np.zeros((LANES, chunk), np.int64),
            "gen_positions": np.zeros(LANES, np.int64),
            "gen_num_valid": np.zeros(LANES, np.int32),
            "gen_block_tables": np.zeros((LANES, GEOM.max_pages_per_seq),
                                         np.int32)}


def _case(name):
    """(program, fetches, feed, pool names by layer kind, pool shape)"""
    heads, hd = CFG.num_heads, CFG.hidden_size // CFG.num_heads
    if name in ("ragged", "ragged_int8"):
        int8 = name == "ragged_int8"
        prog, fetches = build_ragged_step_program(
            CFG, GEOM, CHUNK, "int8" if int8 else "float32")
        return prog, fetches, _ragged_feed(), pool_names(3, int8), (heads, hd)
    if name == "hybrid":
        prog, fetches = build_hybrid_step_program(HCFG, GEOM, CHUNK)
        feed = _ragged_feed()
        feed.update({n: np.zeros(shp, dt) for n, (shp, dt)
                     in HCFG.state_shapes(LANES).items()})
        return (prog, fetches, feed, pool_names(2),
                (HCFG.num_kv_heads, HCFG.head_dim))
    feed = _ragged_feed(1)
    del feed["gen_pos_ids"]
    if name == "decode":
        prog, fetches = build_decode_program(CFG, GEOM)
        feed["gen_tokens"] = np.zeros((LANES, 1), np.int64)
        feed["gen_attend_lens"] = np.ones(LANES, np.int32)
    else:
        prog, fetches = build_prefill_program(CFG, 16, GEOM)
        feed["gen_tokens"] = np.zeros((LANES, 16), np.int64)
        feed["gen_last_index"] = np.zeros(LANES, np.int64)
    return prog, fetches, feed, pool_names(3), (heads, hd)


CASES = ("ragged", "ragged_int8", "hybrid", "decode", "prefill")


@functools.lru_cache(maxsize=None)
def _bound(name):
    """The step of `name`'s program, bound (nothing runs) against a
    scope of zeros for everything the program keeps there."""
    prog, fetches, feed, names, (kvh, hd) = _case(name)
    scope = fluid.Scope()
    for var in prog.global_block().vars.values():
        if var.persistable:
            scope.set_var(var.name, jnp.zeros([int(d) for d in var.shape],
                                              str(var.dtype)))
    exe = fluid.Executor(fluid.TPUPlace())
    exe._force_donation = True
    pools = [n for kind in names for n in kind]
    shape = (kvh, GEOM.num_pages, GEOM.page_size, hd)
    return exe.bind(prog, feed, fetches, scope=scope), pools, shape


@pytest.mark.parametrize("name", CASES)
def test_every_pool_is_donated_and_none_missed(name):
    bound, pools, shape = _bound(name)
    info = bound.audit_info()
    assert len(pools) == {"ragged_int8": 12, "hybrid": 4}.get(name, 6)
    assert sorted(info["donated"]) == sorted(pools)
    assert info["donation_missed"] == []
    assert info["donation_skip_reason"] is None
    item = 1 if name == "ragged_int8" else 4
    want = 2 * (len(pools) // (4 if name == "ragged_int8" else 2)) * (
        int(np.prod(shape)) * item
        + (int(np.prod(shape[:3])) * 4 if name == "ragged_int8" else 0))
    assert info["donated_bytes"] == want
    # nothing of the pools is fed or fetched
    assert not set(pools) & set(bound.compiled.feed_names)
    assert not set(pools) & set(bound.compiled.fetch_names)


@pytest.mark.parametrize("name", CASES)
def test_each_pool_is_aliased_onto_its_own_output_and_not_copied(name):
    """jax pairs donated inputs with outputs of one shape first come,
    first served, and all pools have one shape: the order of state
    arguments (first use) and of written outputs (first write) has to
    agree, or XLA aliases a pool onto another layer's output and copies."""
    bound, pools, shape = _bound(name)
    text = bound.aot_compiled().as_text()
    from paddle_tpu.runtime.dispatch import hlo_donation_aliases

    aliases = hlo_donation_aliases(bound.compiled, text)
    assert aliases == {n: n for n in pools}
    assert bound.donation_aliases() == aliases
    dims = ",".join(map(str, shape))
    flat = ",".join(map(str, (shape[0] * shape[1],) + shape[2:]))
    copies = [ln.strip() for ln in text.splitlines()
              if re.search(r"= \w+\[(%s|%s)\]\S* copy\(" % (dims, flat), ln)]
    assert not copies, copies[:3]


# -- the page-wise write against a numpy oracle of rows -------------------------


@pytest.mark.parametrize("S,ps", [(1, 4), (6, 4), (16, 16), (5, 8), (12, 4)])
@pytest.mark.parametrize("quantized", [False, True])
def test_page_wise_write_equals_row_oracle(S, ps, quantized):
    """Windows that start mid-page, span several pages, are partly or
    wholly invalid: every valid row lands in its slot, nothing else of
    any page but the junk page changes."""
    rng = np.random.RandomState(S * 31 + ps)
    KVH, P, D, B = 2, 40, 8, 4
    maxp = 9
    tables = 1 + np.arange(B)[:, None] * maxp + np.arange(maxp)[None, :]
    pos = rng.randint(0, 3 * ps, B)
    nv = np.array([S, max(S - 1, 0), 0, min(S, 1)])
    k_new, v_new = (rng.randn(B, S, KVH, D).astype(np.float32)
                    for _ in range(2))
    k0, v0 = (rng.randn(KVH, P, ps, D).astype(np.float32) for _ in range(2))
    args = [jnp.asarray(a) for a in (k_new, v_new, tables.astype(np.int32),
                                     pos.astype(np.int32),
                                     nv.astype(np.int32))]
    if quantized:
        from paddle_tpu.kernels.quant import blockwise_quantize

        def quantize(new):      # a scale a row, whatever the rows' order
            q, sc = blockwise_quantize(jnp.asarray(new.reshape(-1, D)))
            return (np.asarray(q).reshape(new.shape),
                    np.asarray(sc).reshape(new.shape[:3]))

        held = [(k0 * 20).astype(np.int8), (v0 * 20).astype(np.int8),
                np.abs(k0[..., 0]), np.abs(v0[..., 0])]
        got = quantized_kv_cache_write(*map(jnp.asarray, held), *args)
        (kq, ksc), (vq, vsc) = quantize(k_new), quantize(v_new)
        want = list(zip((h.copy() for h in held), (kq, vq, ksc, vsc)))
    else:
        got = kv_cache_write(jnp.asarray(k0), jnp.asarray(v0), *args)
        want = [(k0.copy(), k_new), (v0.copy(), v_new)]
    for (pool, new), out in zip(want, got):
        for b in range(B):
            for j in range(int(nv[b])):
                at = int(pos[b]) + j
                pool[:, tables[b, at // ps], at % ps] = new[b, j]
        np.testing.assert_array_equal(np.asarray(out)[:, 1:], pool[:, 1:])


# -- the engine -----------------------------------------------------------------


@pytest.fixture(scope="module")
def lm_dir(tmp_path_factory):
    d = str(tmp_path_factory.mktemp("donation_lm"))
    main, startup, _feeds, fetches = build_lm_program(CFG, SEQ)
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.TPUPlace())
        exe.run(startup)
        fluid.io.save_inference_model(d, ["tokens"], [fetches["logits"]],
                                      exe, main)
    return d


@pytest.fixture(scope="module")
def forced(lm_dir):
    pred = create_predictor(Config(lm_dir))
    pred._exe._force_donation = True
    return pred


@pytest.fixture(scope="module")
def plain(lm_dir):
    pred = create_predictor(Config(lm_dir))
    pred._exe.disable_donation = True
    return pred


def _engine(predictor, **kw):
    kw.setdefault("page_size", 4)
    kw.setdefault("num_pages", 64)
    kw.setdefault("max_decode_batch", 4)
    if kw.get("mode") != "two_lane":
        kw.setdefault("chunk_tokens", 6)
    return GenerationEngine(predictor, CFG, **kw)


def _prompts(n, lo, hi, seed, prefix=()):
    rng = np.random.RandomState(seed)
    return [np.concatenate([np.asarray(prefix, np.int64), rng.randint(
        1, CFG.vocab_size, rng.randint(lo, hi)).astype(np.int64)])
        for _ in range(n)]


SHARED = list(range(1, 17))         # four full pages every prompt starts with
SCENARIOS = {
    # name: (engine kwargs, prompts, new tokens, what the run must show)
    "eviction": (dict(num_pages=16, max_decode_batch=3),
                 _prompts(4, 8, 14, 7), 18,
                 lambda st: st["evicted_total"] >= 1),
    "prefix_cache": (dict(prefix_cache=True, max_decode_batch=2),
                     _prompts(4, 3, 8, 3, SHARED), 6,
                     lambda st: st["radix"]["prefix_hits_total"] >= 1),
    "int8": (dict(kv_dtype="int8"), _prompts(3, 5, 20, 5), 8,
             lambda st: st["step_donated_bytes"] in (0, 3 * 2 * (
                 4 * 64 * 4 * 8 + 4 * 64 * 4 * 4))),
    "speculative": (dict(spec_tokens=3, chunk_tokens=8), _prompts(3, 3, 12, 31),
                    10, lambda st: st["spec_accepted_total"] > 0),
    "two_lane": (dict(mode="two_lane", prefill_buckets=(8, 16, 32)),
                 _prompts(3, 4, 14, 11), 7,
                 lambda st: st["prefill_batches_total"] >= 1),
}


def _serve(predictor, name):
    kw, prompts, n_new, shows = SCENARIOS[name]
    kw = dict(kw)
    if "spec_tokens" in kw:
        kw["draft"] = HostDraft.from_predictor(predictor, CFG)
    with _engine(predictor, **kw) as eng:
        streams = [eng.submit(p, max_new_tokens=n_new) for p in prompts]
        out = [s.result(timeout=600) for s in streams]
        st = eng.stats()
        eng.cache.check_integrity()
    assert shows(st), (name, st)
    assert st["cache"]["pages_in_use"] == 0 or kw.get("prefix_cache")
    return out, st


@pytest.mark.parametrize("name", sorted(SCENARIOS))
def test_tokens_with_donation_forced_equal_those_without(name, forced, plain):
    got, st = _serve(forced, name)
    want, st_plain = _serve(plain, name)
    assert got == want
    assert all(len(t) == SCENARIOS[name][2] for t in got)
    assert st["step_donated_bytes"] > 0
    assert "donation_skip_reason" not in st
    assert st_plain["step_donated_bytes"] == 0
    assert st_plain["donation_skip_reason"] == "disable_donation"


def test_engine_on_a_cpu_reports_zero_donated_bytes_and_why(lm_dir):
    with _engine(create_predictor(Config(lm_dir))) as eng:
        eng.generate(_prompts(1, 5, 6, 1)[0], max_new_tokens=2, timeout=600)
        st = eng.stats()
    assert st["step_donated_bytes"] == 0
    assert st["donation_skip_reason"] == "cpu"


def test_spill_run_from_another_thread_never_sees_a_deleted_array(forced):
    """`spill_run` is documented safe from any thread. A step deletes
    the pools it is donated: the reader dispatches its gathers under the
    lock the step's dispatch holds, so it reads before or after, and the
    full pages it exports read the same every time."""
    store = HostPageStore(page_size=4)
    warm = _prompts(1, 3, 4, 41, SHARED)[0]
    with _engine(forced, prefix_cache=True, page_store=store) as eng:
        eng.generate(warm, max_new_tokens=2, timeout=600)
        n0, k0, v0, _, _ = eng.cache.export_run(warm)
        assert n0 == 4
        errors, spilled, stop = [], [], threading.Event()

        def reader():
            try:
                while not stop.is_set():
                    spilled.append(eng.spill_run(warm))
                    n, k, v, _, _ = eng.cache.export_run(warm)
                    assert n == n0
                    np.testing.assert_array_equal(k, k0)
                    np.testing.assert_array_equal(v, v0)
            except BaseException as e:  # noqa: BLE001 — reported below
                errors.append(e)

        t = threading.Thread(target=reader)
        t.start()
        try:
            streams = [eng.submit(p, max_new_tokens=24)
                       for p in _prompts(6, 4, 12, 43, SHARED)]
            out = [s.result(timeout=600) for s in streams]
        finally:
            stop.set()
            t.join(timeout=60)
        steps = eng.stats()["ragged_steps_total"]
    assert not errors, errors[:1]
    assert steps >= 24 and len(spilled) >= 1 and set(spilled) == {4}
    assert all(len(t) == 24 for t in out)


def test_a_step_deletes_the_arrays_it_was_donated(forced):
    """What makes the test above mean something: under forced donation a
    pool array read before a step is deleted by it, and the cache hands
    out the live one."""
    with _engine(forced) as eng:
        before = eng.cache.k_pages + eng.cache.v_pages
        eng.generate(_prompts(1, 5, 6, 2)[0], max_new_tokens=2, timeout=600)
        after = eng.cache.k_pages + eng.cache.v_pages
        assert all(a.is_deleted() for a in before)
        assert not any(a.is_deleted() for a in after)
        assert eng.cache.pools_alive()


def test_two_engines_over_one_predictor_keep_separate_pools(forced, plain):
    pa, pb = _prompts(2, 6, 12, 51)
    with _engine(plain) as ref:
        want = [ref.generate(p, max_new_tokens=8, timeout=600)
                for p in (pa, pb)]
    with _engine(forced) as a, _engine(forced) as b:
        sa = a.submit(pa, max_new_tokens=8)
        sb = b.submit(pb, max_new_tokens=8)
        assert [sa.result(timeout=600), sb.result(timeout=600)] == want
        assert a.cache.scope is not b.cache.scope
        assert a.cache.scope.parent is b.cache.scope.parent is forced._scope
        ka, kb = a.cache.k_pages, b.cache.k_pages
        assert not any(x is y for x, y in zip(ka, kb))
        assert not np.array_equal(np.asarray(ka[0]), np.asarray(kb[0]))
        # one compiled executable, a bound step and its state each
        assert a._ragged_bound is not b._ragged_bound
        assert a._ragged_bound.compiled is b._ragged_bound.compiled
    # the pools are the engines' own: the shared scope holds none
    names = [n for kind in pool_names(CFG.num_layers) for n in kind]
    assert not any(n in forced._scope.vars for n in names)
    assert all(forced._scope.find_var(n) is None for n in names)


def test_pools_lost_with_a_failed_step_are_replaced(forced, plain):
    p = _prompts(1, 6, 9, 61)[0]
    with _engine(plain) as ref:
        want = ref.generate(p, max_new_tokens=6, timeout=600)
    eng = _engine(forced, prefix_cache=True, start=False)
    try:
        for a in eng.cache.k_pages + eng.cache.v_pages:
            a.delete()          # what a step that failed on the device leaves
        assert not eng.cache.pools_alive()
        eng._recover_pools()
        assert eng.cache.pools_alive() and eng.cache.trie_pages() == 0
        eng.start()
        assert eng.generate(p, max_new_tokens=6, timeout=600) == want
    finally:
        eng.close()


def test_cache_alone_owns_a_scope_and_puts_buffers_where_steps_read():
    cache = PagedKVCache(2, 2, 8, num_pages=8, page_size=4, max_seqs=2,
                         max_pages_per_seq=4, dtype="int8")
    names = pool_names(2, True)
    assert not cache.scope.vars                 # lazy: nothing allocated yet
    k = cache.k_pages
    assert [cache.scope.vars[n] for n in names[0]] == k
    assert cache.k_scales[0].shape == (2, 8, 4) and k[0].dtype == jnp.int8
    # the k and v scale planes are arrays of their own (a buffer can be
    # donated once)
    assert not any(x is y for x, y in zip(cache.k_scales, cache.v_scales))
    gen = cache.scope.generation
    fresh = [[jnp.ones_like(a) for a in kind]
             for kind in (cache.k_pages, cache.v_pages, cache.k_scales,
                          cache.v_scales)]
    cache.set_buffers(*fresh)
    assert cache.scope.generation == gen + 1    # bound steps re-resolve
    assert cache.v_pages[1] is fresh[1][1]
    assert cache.scope.find_var(names[3][0]) is fresh[3][0]
    with pytest.raises(ValueError):
        cache.set_buffers(fresh[0], fresh[1])   # int8 needs its scales


# -- tools/donation_audit.py: the generation phase holds the pools to it ---------


def test_audit_flags_a_generation_step_that_leaves_a_pool_out():
    import importlib.util
    import os

    spec = importlib.util.spec_from_file_location(
        "donation_audit", os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "tools", "donation_audit.py"))
    audit = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(audit)
    pools = ["gen_k_pages_0", "gen_v_pages_0"]
    rows = [{"tag": tag, "pools": pools, "donated": list(pools)}
            for tag in ("generation/ragged_step", "generation/prefill[16]",
                        "generation/decode")]
    assert audit.generation_pools_check(rows) == []
    # the static plans of --check-static are held to the same
    static = [{"tag": r["tag"], "pools": pools, "static_donatable": pools}
              for r in rows]
    assert audit.generation_pools_check(static) == []
    rows[0]["donated"] = pools[:1]          # a fed pool is never donated
    rows[2]["pools"] = []                   # a program that declares none
    got = audit.generation_pools_check(rows[:1] + rows[2:])
    assert len(got) == 3, got
    assert "gen_v_pages_0" in got[1] and "generation/prefill" in got[0]
    assert "declares none" in got[2]
