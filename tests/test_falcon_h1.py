"""Falcon-H1 serving (ISSUE 38): every layer runs a Mamba-2 mixer and
grouped-query attention side by side on one normed input (`parallel`),
then a dense SwiGLU (`dense`), with rotary positions, a head size that
is not hidden / heads, an untied head and the muP multipliers, through
the hybrid decoder, its step program and the ragged GenerationEngine.

Checked at the LOGITS against the plain reference the benchmark ships
(benchmark/models/falcon_h1_reference.py: float32, a token-by-token
recurrence, one causal pass), at the benchmark's tiny preset
(benchmark/tests/tiny_falcon.py: a 2-group Mamba, head_dim 16 on a
hidden size of 48 and 8 heads, every multiplier as published) on the CPU with
float32 weights. The published head multiplier (1/128) makes logits of
order 1e-2, so the tolerance is 1e-6: float32 round-off of a chunked
scan against a sequential one is ~1e-8 there, a wrong multiplier,
branch or state 1e-4 and more.
"""

import dataclasses
import importlib.util
import os

import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.generation import GenerationEngine
from paddle_tpu.generation.kvcache import pool_names
from paddle_tpu.generation.model import (CacheGeometry,
                                         build_hybrid_step_program)
from paddle_tpu.inference import Config, create_predictor
from paddle_tpu.models.hybrid import HybridConfig, build_hybrid_lm_program

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")


def _load(path):
    spec = importlib.util.spec_from_file_location(
        os.path.basename(path)[:-3], path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load(os.path.join(BENCH, "models", "falcon_h1_reference.py"))
prog = _load(os.path.join(BENCH, "models", "falcon_h1_program.py"))
CFG = _load(os.path.join(BENCH, "tests", "tiny_falcon.py")).CONFIG
HCFG = prog.hybrid_config(CFG)
TOL = 1e-6
SEQ = 24
V = CFG["vocab_size"]


def _weights(cfg=CFG, seed=0):
    """Seeded float32 weights as the benchmark draws them: normal at
    initializer_range, the structural vectors ones or zeros, then the
    leaves the configuration places (dt_bias, A_log, the conv)."""
    rng = np.random.RandomState(seed)
    out = {}
    for name, shape, init in ref.spec(cfg):
        if init == "normal":
            w = cfg["initializer_range"] * rng.randn(*shape)
        else:
            w = np.full(shape, float(init == "ones"))
        out[name] = np.asarray(ref.placed(cfg, name, jnp.asarray(
            w, jnp.float32)))
    # norms off their trivial values, so a wrong use shows
    for name in out:
        if name.endswith(("ln1.scale", "ln2.scale", "norm.scale", "d")):
            out[name] = (out[name] + 0.3 * rng.randn(
                *out[name].shape)).astype(np.float32)
    return out


WEIGHTS = _weights()


def _ref_logits(tokens, cfg=CFG, weights=WEIGHTS, fault=None):
    """The reference on the weights as the program holds them: they are
    placed already, so the reference's own placement is switched off."""
    t = np.asarray(tokens, np.int32)
    p = {k: jnp.asarray(v) for k, v in weights.items()}
    return np.asarray(_unplaced(ref.logits_at)(
        cfg, "highest", p, t, np.arange(t.size), fault=fault))


def _unplaced(fn):
    def call(*a, **kw):
        keep = ref.placed
        ref.placed = lambda _cfg, _name, value: value
        try:
            return fn(*a, **kw)
        finally:
            ref.placed = keep
    return call


def _scope_with(weights):
    scope = fluid.Scope()
    for k, v in weights.items():
        scope.set_var(k, jnp.asarray(v))
    return scope


def _export_logits(hcfg, toks, weights=WEIGHTS):
    main, _startup, _feeds, fetches = build_hybrid_lm_program(
        hcfg, toks.shape[1])
    exe = fluid.Executor(fluid.TPUPlace())
    (out,) = exe.run(main, feed={"tokens": toks},
                     fetch_list=[fetches["logits"]],
                     scope=_scope_with(weights))
    return np.asarray(out)


TOKS = np.random.RandomState(2).randint(1, V, (2, SEQ)).astype(np.int64)


# -- the configuration ---------------------------------------------------------------


def test_config_is_parallel_dense_with_its_own_head_dim():
    assert HCFG.layer_types == ("parallel",) * 3
    assert HCFG.attention_layers == HCFG.mamba_layers == (0, 1, 2)
    assert HCFG.expert_layers == ()
    assert HCFG.head_dim == 16 != HCFG.hidden_size // HCFG.num_heads
    # a state a layer, no expert load counts
    assert sorted(HCFG.state_shapes(3)) == sorted(
        f"gen_state_{k}_{i}" for i in range(3) for k in ("ssm", "conv"))
    with pytest.raises(ValueError, match="unknown kinds"):
        dataclasses.replace(HCFG, layer_types=("both",) * 3)
    with pytest.raises(ValueError, match="one kind a layer"):
        dataclasses.replace(HCFG, ffn_types=("dense",))


# -- (a) the exported full forward ---------------------------------------------------


def test_export_program_equals_the_reference():
    out = _export_logits(HCFG, TOKS)
    for b in range(2):
        want = _ref_logits(TOKS[b])
        assert np.abs(want).max() > 100 * TOL
        np.testing.assert_allclose(out[b], want, atol=TOL)


def test_parallel_layer_is_its_two_branches_summed():
    """One layer: what the parallel layer adds to the residual stream is
    what the Mamba-only layer adds plus what the attention-only layer
    adds, all three read at the input of the layer's second norm."""
    one = dataclasses.replace(HCFG, layer_types=("parallel",),
                              ffn_types=("dense",))
    w = {k: v for k, v in WEIGHTS.items()
         if not k.startswith(("hyb1_", "hyb2_"))}
    exe = fluid.Executor(fluid.TPUPlace())

    def added(cfg):
        main, _s, _f, _fetches = build_hybrid_lm_program(cfg, SEQ)
        ops = main.global_block().ops
        h0 = [op for op in ops if op.type == "scale"][0].output("Out")[0]
        h1 = [op for op in ops if op.type == "rms_norm"][1].input("X")[0]
        a, b = exe.run(main, feed={"tokens": TOKS}, fetch_list=[h1, h0],
                       scope=_scope_with(w))
        return np.asarray(a) - np.asarray(b)

    both = added(one)
    mamba = added(dataclasses.replace(one, layer_types=("mamba",)))
    attn = added(dataclasses.replace(one, layer_types=("attention",)))
    assert np.abs(mamba).max() > 1e-2 and np.abs(attn).max() > 1e-2
    np.testing.assert_allclose(both, mamba + attn, atol=1e-6)


MULTIPLIERS = [
    ("embedding_multiplier", 1.0), ("ssm_in_multiplier", 1.0),
    ("ssm_multipliers", (1.0,) * 5), ("ssm_out_multiplier", 1.0),
    ("attention_in_multiplier", 0.5), ("attention_out_multiplier", 1.0),
    ("key_multiplier", 1.0), ("mlp_multipliers", (1.0, 1.0)),
    ("logits_scaling", 1.0)]


@pytest.mark.parametrize("field,value", MULTIPLIERS,
                         ids=[f for f, _v in MULTIPLIERS])
def test_each_multiplier_changes_the_logits_when_set_to_one(field, value):
    """Each published multiplier (attention_in's is 1: set to 0.5) moves
    the logits when it is changed, and the program with it changed is
    the reference with it changed: wired, and wired to the right place."""
    key, ref_value = ((("lm_head_multiplier", 1.0 / value))
                      if field == "logits_scaling" else (field, value))
    changed = _export_logits(dataclasses.replace(HCFG, **{field: value}),
                             TOKS[:1])[0]
    assert np.abs(changed - _export_logits(HCFG, TOKS[:1])[0]).max() > 1e-4
    want = _ref_logits(TOKS[0], cfg=dict(CFG, **{key: ref_value}))
    # a head multiplier of 1 makes logits 128 times larger: relative too
    np.testing.assert_allclose(changed, want, rtol=1e-5, atol=TOL)


def test_reference_faults_move_the_logits():
    want = _ref_logits(TOKS[0])
    for fault in ref.FAULTS:
        assert np.abs(_ref_logits(TOKS[0], fault=fault) - want).max() > 1e-4


# -- (b) the step program: chunked prefill, then decode through pages and state -------


def _drive_step(plan, lanes, chunk, decode_after, stale=None):
    """Feed `plan` ({lane: tokens}) through the step program: chunks of
    `chunk` tokens until `decode_after` tokens are in, then a token a
    step. Returns, per lane, {position: logits there}. `stale`: a state
    the lanes start from instead of zeros (a lane's predecessor's)."""
    geom = CacheGeometry(num_pages=40, page_size=8, max_pages_per_seq=8)
    main, fetches = build_hybrid_step_program(HCFG, geom, chunk)
    logits_var = [op for op in main.global_block().ops
                  if op.type == "scale"][-1].outputs["Out"][0]
    exe = fluid.Executor(fluid.TPUPlace())
    scope = _scope_with(WEIGHTS)
    state = stale or {
        n: jnp.zeros([lanes if s == -1 else s for s in shp], dt)
        for n, (shp, dt) in HCFG.state_shapes(-1).items()}
    for names in pool_names(len(HCFG.attention_layers))[:2]:
        for name in names:
            scope.set_var(name, jnp.zeros(
                (HCFG.num_kv_heads, 40, 8, HCFG.head_dim)))
    tables = np.zeros((lanes, 8), np.int32)
    for r in range(lanes):
        tables[r] = 1 + r * 8 + np.arange(8)
    done = {r: 0 for r in plan}
    got = {r: {} for r in plan}
    while any(done[r] < len(plan[r]) for r in plan):
        toks = np.zeros((lanes, chunk), np.int64)
        pos_ids = np.zeros((lanes, chunk), np.int64)
        nv = np.zeros(lanes, np.int32)
        pos = np.zeros(lanes, np.int64)
        for r, seq in plan.items():
            c = min(chunk if done[r] < decode_after else 1,
                    len(seq) - done[r])
            toks[r, :c] = seq[done[r]:done[r] + c]
            pos_ids[r] = done[r] + np.arange(chunk)
            nv[r], pos[r] = c, done[r]
        feed = {"gen_tokens": toks, "gen_pos_ids": pos_ids,
                "gen_positions": pos, "gen_num_valid": nv,
                "gen_block_tables": tables, **state}
        outs = exe.run(main, feed=feed, fetch_list=fetches + [logits_var],
                       scope=scope, return_numpy=False)
        state = dict(zip(state, outs[1:-1]))
        logits = np.asarray(outs[-1])                      # [lanes, 1, V]
        for r in plan:
            if nv[r]:
                done[r] += int(nv[r])
                got[r][done[r] - 1] = logits[r, 0]
    return got, state


@pytest.mark.parametrize("decode_after", [12, 0])
def test_step_program_chunked_prefill_then_decode_equals_one_forward(
        decode_after):
    rng = np.random.RandomState(11)
    plan = {0: rng.randint(1, V, 19), 2: rng.randint(1, V, 14)}  # lane 1 idle
    got, _state = _drive_step(plan, lanes=3, chunk=4,
                              decode_after=decode_after)
    for r, seq in plan.items():
        want = _ref_logits(seq)
        for at, logits in got[r].items():
            np.testing.assert_allclose(logits, want[at], atol=TOL)


def test_a_row_at_position_zero_reads_no_stale_state():
    """Lanes that arrive holding another sequence's state (what a lane
    keeps from its predecessor) give the same logits as fresh lanes: a
    row at position 0 drops it inside the step. The stale state is
    large enough to matter: planted past the reset, it moves them."""
    rng = np.random.RandomState(5)
    first = {0: rng.randint(1, V, 30), 1: rng.randint(1, V, 30)}
    _got, stale = _drive_step(first, lanes=2, chunk=4, decode_after=30)
    plan = {0: rng.randint(1, V, 9), 1: rng.randint(1, V, 11)}
    got, _ = _drive_step(plan, lanes=2, chunk=4, decode_after=4,
                         stale=dict(stale))
    for r, seq in plan.items():
        want = _ref_logits(seq)
        for at, logits in got[r].items():
            np.testing.assert_allclose(logits, want[at], atol=TOL)
    from paddle_tpu.ops import ssm
    from paddle_tpu.runtime import dispatch

    mixer = ssm.mamba2_mixer
    dispatch._SHARED_CACHE.clear()
    ssm.mamba2_mixer = lambda x, nv, pos, *a, **kw: mixer(x, nv, pos + 1,
                                                          *a, **kw)
    try:
        kept, _ = _drive_step(plan, lanes=2, chunk=4, decode_after=4,
                              stale=dict(stale))
    finally:
        ssm.mamba2_mixer = mixer
        dispatch._SHARED_CACHE.clear()
    moved = max(np.abs(kept[r][at] - got[r][at]).max()
                for r in plan for at in got[r])
    assert moved > 10 * TOL


# -- (c) the engine ------------------------------------------------------------------


@pytest.fixture(scope="module")
def predictor(tmp_path_factory):
    d = str(tmp_path_factory.mktemp("falcon_lm"))
    main, _startup, _feeds, fetches = build_hybrid_lm_program(HCFG, SEQ)
    with fluid.scope_guard(_scope_with(WEIGHTS)):
        exe = fluid.Executor(fluid.TPUPlace())
        fluid.io.save_inference_model(d, ["tokens"], [fetches["logits"]],
                                      exe, main)
    return create_predictor(Config(d))


def _engine(predictor, **kw):
    args = dict(mode="ragged", page_size=8, num_pages=40,
                max_decode_batch=3, chunk_tokens=4, prefix_cache=False)
    args.update(kw)
    return GenerationEngine(predictor, HCFG, **args)


def _greedy_by_reference(prompt, n):
    toks = [int(t) for t in prompt]
    for _ in range(n):
        toks.append(int(np.argmax(_ref_logits(toks)[-1])))
    return toks[len(prompt):]


def test_engine_serves_the_reference_tokens_and_reuses_lanes(predictor):
    """7 requests over 3 lanes: four of them take a lane another held,
    and read neither its pages nor its state."""
    rng = np.random.RandomState(4)
    prompts = [rng.randint(1, V, rng.randint(5, 20)).astype(np.int64)
               for _ in range(7)]
    want = [_greedy_by_reference(p, 6) for p in prompts]
    with _engine(predictor) as eng:
        streams = [eng.submit(p, max_new_tokens=6) for p in prompts]
        got = [s.result(timeout=300) for s in streams]
        st = eng.stats()
    assert got == want
    assert st["state_lane_resets_total"] == 7
    # 3 layers, each a Mamba mixer: SSM [4, 8, 16] and conv 3 x 96 a lane
    assert st["recurrent_state_bytes"] == 3 * 3 * (4 * 8 * 16 + 3 * 96) * 4
    assert "moe_held_assignments_total" not in st
    # the pools are the layers with attention: all three, head_dim 16
    assert len(eng.cache.k_pages) == 3
    assert eng.cache.k_pages[0].shape == (2, 40, 8, 16)


def test_engine_pools_take_the_configs_head_dim(predictor):
    """The cache's head size is the config's, not hidden / heads (6
    here): a pool of the wrong width would not be the step program's
    declared pool."""
    with _engine(predictor) as eng:
        assert eng.cache.head_dim == HCFG.head_dim == 16
        assert eng.generate(np.arange(1, 9), max_new_tokens=2) == \
            _greedy_by_reference(np.arange(1, 9), 2)


def test_engine_counts_the_pages_of_lean_lanes(predictor):
    """``attn_lean_pages_total`` is the pages walked by the lanes whose
    rows (valid tokens x the query group, 8 / 2 = 4) fit the kernel's
    lean rows, counted over every step the engine assembled: decode
    lanes and the short last chunks of prompts, not the full chunks."""
    from paddle_tpu.kernels.ragged_paged_attention import lean_decode_rows

    group = HCFG.num_heads // HCFG.num_kv_heads
    rows = lean_decode_rows(group, 4, stored_products=False)
    feeds = []
    eng = _engine(predictor, start=False)
    assemble = eng._assemble

    def seen(lanes):
        feed, rows_, carry = assemble(lanes)
        feeds.append((feed["gen_positions"].copy(),
                      feed["gen_num_valid"].copy()))
        return feed, rows_, carry

    eng._assemble = seen
    rng = np.random.RandomState(5)
    with eng.start():
        for s in [eng.submit(rng.randint(1, V, n).astype(np.int64),
                             max_new_tokens=5) for n in (6, 13, 9, 17)]:
            s.result(timeout=300)
    st = eng.stats()
    want = sum(int(-(-(p + n) // 8)) for pos, nv in feeds
               for p, n in zip(pos, nv) if 0 < n * group <= rows)
    assert eng._lean_tokens == 2
    assert st["attn_lean_pages_total"] == want
    assert 0 < want < st["attn_live_pages_total"]


def _serve_counting_live_rows(predictor, prompts, n):
    """Serve ``prompts`` greedily; returns (tokens, stats after the
    close, the valid tokens of every step assembled)."""
    live = []
    eng = _engine(predictor, start=False)
    assemble = eng._assemble

    def seen(lanes):
        feed, rows, carry = assemble(lanes)
        live.append(int(feed["gen_num_valid"].sum()))
        return feed, rows, carry

    eng._assemble = seen
    with eng.start():
        streams = [eng.submit(p, max_new_tokens=n) for p in prompts]
        tokens = [s.result(timeout=300) for s in streams]
    return tokens, eng.stats(), live


def test_engine_serves_the_same_tokens_on_the_live_rows(predictor,
                                                        monkeypatch):
    """The dense SwiGLU on the live rows alone: the tiny widths are under
    ``live_ffn_rows``'s rule, so the rule is made to say R = half the
    window (6 of 3 lanes x 4 tokens) for every layer. The engine then
    serves the tokens of the whole-window path and of the reference
    (padding rows now read 0 where they read noise: nothing reads them),
    and counts a narrow step for each step with at most 6 valid tokens,
    over both branches."""
    from paddle_tpu.ops import decoder
    from paddle_tpu.runtime import dispatch

    prompts = [np.random.RandomState(s).randint(1, V, n).astype(np.int64)
               for s, n in ((11, 6), (12, 13), (13, 9), (14, 17), (15, 5))]
    dispatch._SHARED_CACHE.clear()      # compiled by program content
    whole, st_whole, _ = _serve_counting_live_rows(predictor, prompts, 6)
    monkeypatch.setattr(decoder, "live_ffn_rows",
                        lambda rows, d, f, itemsize: rows // 2)
    dispatch._SHARED_CACHE.clear()
    try:
        got, st, live = _serve_counting_live_rows(predictor, prompts, 6)
    finally:
        monkeypatch.undo()
        dispatch._SHARED_CACHE.clear()
    assert got == whole == [_greedy_by_reference(p, 6) for p in prompts]
    assert (st["ffn_narrow_layers"], st_whole["ffn_narrow_layers"]) == (3, 0)
    assert st_whole["ffn_narrow_steps_total"] == 0
    assert st["ragged_steps_total"] == len(live)
    assert st["ffn_live_rows_total"] == sum(live)
    assert st["ffn_narrow_steps_total"] == sum(n <= 6 for n in live)
    assert 0 < st["ffn_narrow_steps_total"] < st["ragged_steps_total"]


@pytest.mark.parametrize("kwargs,needs", [
    (dict(prefix_cache=True), "snapshots"),
    (dict(spec_tokens=2, draft=object()), "rollback"),
    (dict(page_store=object()), "on the wire"),
])
def test_engine_refuses_what_recurrent_state_cannot_serve(predictor, kwargs,
                                                         needs):
    with pytest.raises(ValueError, match=needs):
        _engine(predictor, start=False, **kwargs)


def test_hybrid_config_defaults_are_granites():
    """Every option Falcon-H1 added defaults to what the granite config
    had: no rotary, a tied head, hidden / heads, experts everywhere, no
    muP multiplier."""
    g = HybridConfig(vocab_size=97, hidden_size=32, layer_types=(
        "mamba", "attention"), num_heads=4, num_kv_heads=2, mamba_heads=8,
        mamba_head_dim=8, mamba_state=16, moe_experts=8, moe_top_k=2,
        moe_expert_size=16, shared_size=24, max_position=64)
    assert g.head_dim == 8 and g.rope_base is None and g.tie_word_embeddings
    assert g.ffn_kinds == ("experts",) * 2 and g.expert_layers == (0, 1)
    assert g.attention_layers == (1,) and g.mamba_layers == (0,)
    assert "gen_state_moe_loads" in g.state_shapes(3)
