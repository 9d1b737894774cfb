"""paddle_tpu.adapters: batched LoRA multiplexing + hot base swap
(ISSUE 19).

Correctness anchors:
  * kernel — batched_lora_delta (interpret-mode Pallas) vs the pure-JAX
    reference vs the dense-merge oracle (f32/bf16), tile-unaligned
    shapes, the Mosaic rank-geometry guard;
  * store — slot-0 zero-adapter invariant, refcounted evict-under-load
    (AdapterInUse while pinned), LRU + tenant-quota eviction, zero
    leaked pool bytes;
  * rewrite — idempotent repoint, strict proglint on the rewritten
    program, base numerics bitwise-unchanged with zero adapters,
    quantized-base composition;
  * serving — a mixed-adapter micro-batch token-identical to dedicated
    per-adapter engines on the ragged engine, and a hot base swap
    under live submissions with zero drops, the SAME bound executable
    and no new persistent-compile-cache entries.
"""

import os

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import adapters
from paddle_tpu.adapters import (
    AdapterInUse,
    AdapterMissing,
    AdapterQuotaExceeded,
    AdapterStore,
    rewrite_for_lora,
)
from paddle_tpu.adapters.store import SLOTS_FEED, scale_var_name
from paddle_tpu.kernels import lora

# -- kernel vs oracle --------------------------------------------------------


def _pools(rng, S, K, r, N):
    a = rng.randn(S, K, r).astype("float32") * 0.1
    b = rng.randn(S, r, N).astype("float32") * 0.1
    a[0] = 0.0
    b[0] = 0.0
    sc = rng.rand(S).astype("float32")
    sc[0] = 0.0
    return a, b, sc


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_delta_matches_dense_merge(dtype):
    """The batched delta == per-row matmul against the DENSE-MERGED
    weight (W + scale_s * A_s @ B_s), the oracle a LoRA-merging
    deployment would serve."""
    import jax.numpy as jnp

    rng = np.random.RandomState(0)
    S, K, r, N, M = 5, 24, 8, 17, 6
    a, b, sc = _pools(rng, S, K, r, N)
    slots = np.array([0, 1, 2, 3, 4, 1], np.int32)
    x = rng.randn(M, K).astype("float32")
    xj = jnp.asarray(x).astype(dtype)
    got = np.asarray(
        lora.batched_lora_delta(xj, jnp.asarray(a), jnp.asarray(b),
                                jnp.asarray(sc), jnp.asarray(slots)),
        np.float32)
    want = np.stack([x[m].astype(np.float32)
                     @ (sc[s] * a[s] @ b[s]) for m, s in enumerate(slots)])
    tol = 5e-5 if dtype == "float32" else 0.05
    assert np.abs(got - want).max() <= tol * max(np.abs(want).max(), 1.0)
    # slot-0 rows are EXACTLY zero, not approximately
    assert np.all(got[0] == 0.0)


@pytest.mark.parametrize("shape", [(6, 24, 8, 16), (16, 128, 16, 128),
                                   (3, 70, 8, 33)])
def test_interpret_pallas_matches_reference(shape):
    """The real kernel body (interpreter mode) against the reference
    gather path — including M/K/N all tile-unaligned."""
    import jax.numpy as jnp

    M, K, r, N = shape
    rng = np.random.RandomState(1)
    S = 4
    a, b, sc = _pools(rng, S, K, r, N)
    slots = rng.randint(0, S, M).astype(np.int32)
    x = jnp.asarray(rng.randn(M, K).astype("float32"))
    pal = np.asarray(lora._lora_delta_pallas(
        x, jnp.asarray(a), jnp.asarray(b), jnp.asarray(sc),
        jnp.asarray(slots), interpret=True), np.float32)
    ref = np.asarray(lora._reference_lora_delta(
        x, jnp.asarray(a), jnp.asarray(b), jnp.asarray(sc),
        jnp.asarray(slots)), np.float32)
    assert np.abs(pal - ref).max() <= 1e-4 * max(np.abs(ref).max(), 1.0)


def test_rank_geometry_guard():
    """A non-8-multiple bucket rank cannot tile on Mosaic: the guard
    names the geometry (PTL091/092 share this exact message); the
    interpreter executes it fine (tile-unaligned ranks keep the
    reference numerics on CPU CI)."""
    import jax.numpy as jnp

    assert lora.lora_rank_geometry_issue(8) is None
    assert lora.lora_rank_geometry_issue(16) is None
    assert "multiple of 8" in lora.lora_rank_geometry_issue(12)
    rng = np.random.RandomState(2)
    a, b, sc = _pools(rng, 3, 32, 12, 16)
    slots = np.array([0, 1, 2, 1], np.int32)
    x = jnp.asarray(rng.randn(4, 32).astype("float32"))
    with pytest.raises(ValueError, match="multiple of 8"):
        lora._lora_delta_pallas(x, jnp.asarray(a), jnp.asarray(b),
                                jnp.asarray(sc), jnp.asarray(slots),
                                interpret=False)
    out = lora._lora_delta_pallas(x, jnp.asarray(a), jnp.asarray(b),
                                  jnp.asarray(sc), jnp.asarray(slots),
                                  interpret=True)
    ref = lora._reference_lora_delta(x, jnp.asarray(a), jnp.asarray(b),
                                     jnp.asarray(sc), jnp.asarray(slots))
    assert np.abs(np.asarray(out) - np.asarray(ref)).max() <= 1e-4


def test_registry_knows_lora_ops():
    from paddle_tpu.core.registry import get_op_def, registered_ops

    assert "batched_lora_matmul" in registered_ops()
    assert "batched_lora_fc" in registered_ops()
    d = get_op_def("batched_lora_matmul")
    assert d.stop_gradient
    assert "A" in d.no_grad_slots and "Slots" in d.no_grad_slots


# -- the store ---------------------------------------------------------------

TARGETS = {"w1": (16, 24), "w2": (24, 16)}


def test_store_slot0_reserved_and_upload_shapes():
    st = AdapterStore(TARGETS, rank_buckets=(8, 16), slots_per_bucket=3)
    rng = np.random.RandomState(0)
    row = st.upload("a1", {"w1": (rng.randn(16, 8).astype("float32"),
                                  rng.randn(8, 24).astype("float32"))},
                    alpha=16.0)
    assert row["slot"] >= 1  # slot 0 is the zero adapter, never taken
    assert row["rank"] == 8 and row["rank_bucket"] == 8
    assert st.is_resident("a1") and not st.is_resident("nope")
    # rank 9 rounds UP into the 16 bucket, zero-padded
    row2 = st.upload("a2", {"w2": (rng.randn(24, 9).astype("float32"),
                                   rng.randn(9, 16).astype("float32"))})
    assert row2["rank"] == 9 and row2["rank_bucket"] == 16
    with pytest.raises(adapters.AdapterError, match="rank"):
        st.upload("a3", {"w1": (np.zeros((16, 20), "float32"),
                                np.zeros((20, 24), "float32"))})
    with pytest.raises(adapters.AdapterError, match="unknown target"):
        st.upload("a4", {"bogus": (np.zeros((4, 8), "float32"),
                                   np.zeros((8, 4), "float32"))})


def test_evict_under_load_refcount_integrity():
    """The evict-under-load contract: a pinned adapter refuses evict
    (AdapterInUse), force-evict works for teardown, release unpins,
    and the pool ends with zero leaked bytes."""
    st = AdapterStore(TARGETS, rank_buckets=(8,), slots_per_bucket=4)
    rng = np.random.RandomState(1)
    for i in range(2):
        st.upload(f"a{i}", {"w1": (rng.randn(16, 8).astype("float32"),
                                   rng.randn(8, 24).astype("float32"))})
    st.acquire("a0")
    st.acquire("a0")
    with pytest.raises(AdapterInUse):
        st.evict("a0")
    assert st.is_resident("a0")  # refused evict left it resident
    st.release("a0")
    with pytest.raises(AdapterInUse):
        st.evict("a0")           # still one in-flight row
    st.release("a0")
    st.evict("a0")               # idle now: clean evict
    assert not st.is_resident("a0")
    with pytest.raises(AdapterMissing):
        st.acquire("a0")
    # force-evict tears down a pinned adapter (the slot zeroes)
    st.acquire("a1")
    st.evict("a1", force=True)
    assert not st.is_resident("a1")
    assert st.used_bytes() == 0
    s = st.stats_numeric()
    assert s["evict_refusals_total"] >= 2
    assert s["active_refs"] == 0 or s["resident"] == 0


def test_lru_and_tenant_quota_eviction():
    st = AdapterStore(TARGETS, rank_buckets=(8,), slots_per_bucket=2,
                      tenant_quota=2)
    rng = np.random.RandomState(2)

    def up(aid, tenant=None):
        return st.upload(aid, {"w1": (rng.randn(16, 8).astype("float32"),
                                      rng.randn(8, 24).astype("float32"))},
                         tenant=tenant)

    up("a0")
    up("a1")  # bucket full (2 usable slots + the zero slot)
    up("a2")  # LRU-evicts a0
    assert not st.is_resident("a0") and st.is_resident("a2")
    assert st.stats_numeric()["lru_evictions_total"] >= 1
    # tenant quota: the third upload self-evicts the tenant's LRU idle
    st2 = AdapterStore(TARGETS, rank_buckets=(8,), slots_per_bucket=8,
                       tenant_quota=2)
    st2.upload("t0", {"w1": (rng.randn(16, 8).astype("float32"),
                             rng.randn(8, 24).astype("float32"))},
               tenant="alice")
    st2.upload("t1", {"w1": (rng.randn(16, 8).astype("float32"),
                             rng.randn(8, 24).astype("float32"))},
               tenant="alice")
    st2.upload("t2", {"w1": (rng.randn(16, 8).astype("float32"),
                             rng.randn(8, 24).astype("float32"))},
               tenant="alice")
    assert not st2.is_resident("t0")
    assert st2.stats_numeric()["quota_evictions_total"] >= 1
    # every resident pinned -> quota raises instead of evicting
    st2.acquire("t1")
    st2.acquire("t2")
    with pytest.raises(AdapterQuotaExceeded):
        st2.upload("t3", {"w1": (rng.randn(16, 8).astype("float32"),
                                 rng.randn(8, 24).astype("float32"))},
                   tenant="alice")


# -- the rewrite -------------------------------------------------------------


def _mlp_program():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = fluid.layers.data("x", [16])
        h = fluid.layers.fc(x, 32, act="relu")
        out = fluid.layers.fc(h, 8)
    return main, startup, out


def test_rewrite_idempotent_base_identity_and_proglint():
    """Repointed ops, zero-adapter rows bitwise-identical to the fp32
    original, second rewrite a no-op, strict proglint clean."""
    from paddle_tpu.analysis import validate_for_run

    main, startup, out = _mlp_program()
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.TPUPlace())
        exe.run(startup)
        feed = {"x": np.random.RandomState(0).rand(4, 16).astype("float32")}
        (ref,) = exe.run(main, feed=feed, fetch_list=[out])
        store = AdapterStore.for_program(main, slots_per_bucket=3)
        store.attach(scope)
        rep1 = rewrite_for_lora(main, store)
        rep2 = rewrite_for_lora(main, store)
        assert rep1.n_repointed == 2 and rep2.n_repointed == 0
        assert any("already" in (r["reason"] or "") for r in rep2.rows)
        types = [op.type for op in main.global_block().ops]
        assert "mul" not in types and types.count("batched_lora_fc") == 2
        slots = np.zeros((4, store.n_buckets), np.int32)
        (base,) = exe.run(main, feed=dict(feed, **{SLOTS_FEED: slots}),
                          fetch_list=[out])
        # the zero adapter is bitwise identity, not approximate
        np.testing.assert_array_equal(base, ref)
        validate_for_run(main, fetch_names=[out.name],
                         feed_names=["x", SLOTS_FEED], mode="strict",
                         label="lora")

        # a real adapter on one row: dense-merge oracle agreement
        rng = np.random.RandomState(3)
        t0 = sorted(store.targets)[0]
        K, N = store.targets[t0]
        A = rng.randn(K, 8).astype("float32") * 0.1
        B = rng.randn(8, N).astype("float32") * 0.1
        row = store.upload("ad", {t0: (A, B)}, alpha=16.0)
        slots2 = np.zeros((4, store.n_buckets), np.int32)
        slots2[2, row["rank_bucket"] == np.array(store.rank_buckets)] = \
            row["slot"]
        (got,) = exe.run(main, feed=dict(feed, **{SLOTS_FEED: slots2}),
                         fetch_list=[out])
        np.testing.assert_array_equal(got[[0, 1, 3]], ref[[0, 1, 3]])
        assert np.abs(got[2] - ref[2]).max() > 0  # the delta applied


def test_quantized_base_composition():
    """LoRA over an int8 base: the rewrite repoints quantized_fc ops,
    base rows keep the quantized numerics bitwise, and the delta
    applies on top of the dequantized product."""
    from paddle_tpu import quantize

    main, startup, out = _mlp_program()
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.TPUPlace())
        exe.run(startup)
        feed = {"x": np.random.RandomState(0).rand(4, 16).astype("float32")}
        quantize.rewrite_for_inference(main, scope, "int8")
        (qref,) = exe.run(main, feed=feed, fetch_list=[out])
        store = AdapterStore.for_program(main, slots_per_bucket=3)
        store.attach(scope)
        rep = rewrite_for_lora(main, store)
        assert rep.n_repointed == 2
        assert all(r["base_kind"] == "int8" for r in rep.rows
                   if r["action"] == "repointed")
        slots = np.zeros((4, store.n_buckets), np.int32)
        (base,) = exe.run(main, feed=dict(feed, **{SLOTS_FEED: slots}),
                          fetch_list=[out])
        np.testing.assert_array_equal(base, qref)
        rng = np.random.RandomState(4)
        t0 = sorted(store.targets)[0]
        K, N = store.targets[t0]
        row = store.upload("ad", {t0: (rng.randn(K, 8).astype("float32"),
                                       rng.randn(8, N).astype("float32"))})
        slots[:, list(store.rank_buckets).index(row["rank_bucket"])] = \
            row["slot"]
        (got,) = exe.run(main, feed=dict(feed, **{SLOTS_FEED: slots}),
                         fetch_list=[out])
        assert np.abs(got - qref).max() > 0
        store.evict("ad")
        (back,) = exe.run(main, feed=dict(feed, **{
            SLOTS_FEED: np.zeros((4, store.n_buckets), np.int32)}),
            fetch_list=[out])
        np.testing.assert_array_equal(back, qref)


def test_constraint_pass_covers_lora_geometry(monkeypatch):
    """distlint kernel-geometry coverage: a rank-12 bucket is PTL092
    (lost kernel) by default and PTL091 (error) under FORCE_PALLAS —
    no silent reference fallback in an AOT-validated deployment."""
    from paddle_tpu.analysis import analyze_program

    main, startup, out = _mlp_program()
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.TPUPlace())
        exe.run(startup)
        store = AdapterStore.for_program(main, rank_buckets=(12,),
                                         slots_per_bucket=3)
        store.attach(scope)
        rewrite_for_lora(main, store)
    rep = analyze_program(main, fetch_names=[out.name],
                          feed_names=["x", SLOTS_FEED], label="lora12")
    assert any(d.code == "PTL092" for d in rep.warnings)
    monkeypatch.setenv("PADDLE_TPU_FORCE_PALLAS", "1")
    rep2 = analyze_program(main, fetch_names=[out.name],
                           feed_names=["x", SLOTS_FEED], label="lora12f")
    assert any(d.code == "PTL091" for d in rep2.errors)

    # well-formed geometry (8/16 buckets): clean under both regimes
    main2, startup2, out2 = _mlp_program()
    scope2 = fluid.Scope()
    with fluid.scope_guard(scope2):
        exe2 = fluid.Executor(fluid.TPUPlace())
        exe2.run(startup2)
        store2 = AdapterStore.for_program(main2, slots_per_bucket=3)
        store2.attach(scope2)
        rewrite_for_lora(main2, store2)
    rep3 = analyze_program(main2, fetch_names=[out2.name],
                           feed_names=["x", SLOTS_FEED], label="lora816")
    assert not rep3.errors
    assert not any(d.code.startswith("PTL09") for d in rep3.warnings)


# -- end to end: the ragged engine -------------------------------------------

CFG = None
SEQ = 40


def _gpt_cfg():
    from paddle_tpu.generation.model import GPTConfig

    global CFG
    if CFG is None:
        CFG = GPTConfig(vocab_size=97, hidden_size=32, num_layers=2,
                        num_heads=4, ffn_size=64, max_position=64,
                        hidden_dropout=0.0, attention_dropout=0.0)
    return CFG


@pytest.fixture(scope="module")
def lm_dir(tmp_path_factory):
    from paddle_tpu.generation.model import build_lm_program

    cfg = _gpt_cfg()
    d = str(tmp_path_factory.mktemp("adapter_lm"))
    main, startup, _feeds, fetches = build_lm_program(cfg, SEQ)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.TPUPlace())
        exe.run(startup)
        fluid.io.save_inference_model(d, ["tokens"],
                                      [fetches["logits"]], exe, main)
    return d


def _adapter_engine(lm_dir, lanes, slots=8):
    from paddle_tpu.generation import GenerationEngine
    from paddle_tpu.inference import Config, create_predictor

    fluid.set_flags({"adapter_pool_max_bytes": 1,
                     "adapter_slots_per_bucket": slots})
    try:
        pred = create_predictor(Config(lm_dir))
        return GenerationEngine(pred, _gpt_cfg(), page_size=4,
                                num_pages=64, max_decode_batch=lanes,
                                chunk_tokens=6)
    finally:
        fluid.set_flags({"adapter_pool_max_bytes": 0,
                         "adapter_slots_per_bucket": 0})


def _upload(store, rng, aid, rank, n_targets=2):
    ts = sorted(store.targets)[:n_targets]
    fac = {}
    for t in ts:
        K, N = store.targets[t]
        fac[t] = (rng.randn(K, rank).astype("float32") * 0.05,
                  rng.randn(rank, N).astype("float32") * 0.05)
    return store.upload(aid, fac, alpha=2.0 * rank)


def test_mixed_adapter_batch_matches_sequential(lm_dir):
    """THE multiplexing proof at test scale: 4 distinct adapters + a
    base row submitted together through ONE ragged executable are
    token-identical to per-adapter sequential runs on dedicated
    engines."""
    rng = np.random.RandomState(7)
    prompt = np.asarray([3, 11, 5, 2, 17, 8], np.int64)
    eng = _adapter_engine(lm_dir, lanes=5)
    try:
        for i in range(4):
            _upload(eng.adapter_store, rng, f"ad{i}",
                    8 if i % 2 == 0 else 16, n_targets=1 + i % 3)
        streams = [eng.submit(prompt, max_new_tokens=10,
                              adapter=f"ad{i}") for i in range(4)]
        streams.append(eng.submit(prompt, max_new_tokens=10))
        mixed = [s.result(timeout=600) for s in streams]
        with pytest.raises(AdapterMissing):
            eng.submit(prompt, max_new_tokens=2, adapter="ghost")
        frag = eng.models_fragment()
        assert len(frag["adapters"]) == 4
        assert frag["base"]["version"] == "base"
    finally:
        eng.close(drain=True)

    # base row == a no-adapter engine's output
    from paddle_tpu.generation import GenerationEngine
    from paddle_tpu.inference import Config, create_predictor

    beng = GenerationEngine(create_predictor(Config(lm_dir)), _gpt_cfg(),
                            page_size=4, num_pages=64, max_decode_batch=2,
                            chunk_tokens=6)
    try:
        assert mixed[4] == beng.generate(prompt, max_new_tokens=10,
                                         timeout=600)
    finally:
        beng.close(drain=True)

    # each adapter row == a dedicated single-adapter engine
    for i in range(4):
        rng2 = np.random.RandomState(7)
        solo = _adapter_engine(lm_dir, lanes=2, slots=3)
        try:
            for j in range(i + 1):  # same rng draw order as the upload loop
                _upload(solo.adapter_store if j == i else
                        _shadow_store(solo), rng2, f"ad{j}",
                        8 if j % 2 == 0 else 16, n_targets=1 + j % 3)
            out = solo.generate(prompt, max_new_tokens=10,
                                adapter=f"ad{i}", timeout=600)
        finally:
            solo.close(drain=True)
        assert out == mixed[i], f"ad{i} diverged from dedicated engine"


def _shadow_store(eng):
    """A throwaway store with the same target table, used only to burn
    rng draws so adapter i's factors match the mixed-batch upload."""
    return AdapterStore({t: kn for t, kn in eng.adapter_store.targets.items()},
                        slots_per_bucket=3)


def test_hot_swap_zero_drop_same_executable(lm_dir):
    """Hot base swap under live submissions: zero failed requests, the
    SAME BoundStep object (no rebind, no recompile), no new persistent
    compile-cache entries, and post-swap tokens actually change."""
    import threading

    from paddle_tpu.runtime.dispatch import persistent_cache_dir

    rng = np.random.RandomState(9)
    prompt = np.asarray([2, 9, 4, 11, 6], np.int64)
    eng = _adapter_engine(lm_dir, lanes=3)
    try:
        _upload(eng.adapter_store, rng, "ad0", 8)
        before = eng.generate(prompt, max_new_tokens=8, timeout=600)
        bound = eng._ragged_bound
        cache = persistent_cache_dir()
        n_before = (len(os.listdir(cache))
                    if cache and os.path.isdir(cache) else 0)
        new_w = {}
        for t, (K, N) in eng.adapter_store.targets.items():
            cur = np.asarray(eng._scope.find_var(t))
            new_w[t] = cur + rng.randn(K, N).astype("float32") * 0.02
        failures, done, stop = [], [], threading.Event()

        def pump():
            i = 0
            while not stop.is_set():
                try:
                    s = eng.submit(prompt, max_new_tokens=3,
                                   adapter="ad0" if i % 2 else None)
                    s.result(timeout=300)
                    done.append(1)
                except Exception as e:  # noqa: BLE001
                    failures.append(repr(e))
                i += 1

        th = threading.Thread(target=pump, daemon=True)
        th.start()
        label = eng.swap_base(new_w, version="v2")
        stop.set()
        th.join(60)
        assert label == "v2" and eng.model_version == "v2"
        assert eng.model_swaps == 1
        assert failures == [] and len(done) >= 1
        assert eng._ragged_bound is bound  # same executable, no rebind
        n_after = (len(os.listdir(cache))
                   if cache and os.path.isdir(cache) else 0)
        assert n_after == n_before  # zero new compile-cache entries
        after = eng.generate(prompt, max_new_tokens=8, timeout=600)
        assert after != before  # the new weights actually serve
        # signature mismatch is refused loudly, not applied silently
        with pytest.raises(ValueError, match="signature-identical"):
            eng.swap_base({"dec0_qkv.w": np.zeros((3, 3), "float32")})
    finally:
        eng.close(drain=True)


@pytest.mark.slow
def test_engine_releases_refcounts_on_completion(lm_dir):
    """submit pins the adapter for the request's lifetime; terminal
    states (including completion) release it so evict works."""
    rng = np.random.RandomState(5)
    eng = _adapter_engine(lm_dir, lanes=2)
    try:
        _upload(eng.adapter_store, rng, "ad0", 8)
        out = eng.generate(np.asarray([4, 8, 15], np.int64),
                           max_new_tokens=4, adapter="ad0", timeout=600)
        assert len(out) == 4
        eng.adapter_store.evict("ad0")  # no lingering refcount
        assert not eng.adapter_store.is_resident("ad0")
        assert eng.adapter_store.used_bytes() == 0
    finally:
        eng.close(drain=True)
