"""Hybrid decoder serving (ISSUE 29): Mamba-2 layers with a per-lane
recurrent state beside the paged KV cache, dropless top-k experts as a
chip's share, grouped-query attention, through the ragged
GenerationEngine.

Everything is checked at the LOGITS against the plain reference the
benchmark ships (benchmark/models/granite_hybrid_reference.py: float32,
sequential recurrence, one causal pass), at tiny widths on the CPU with
float32 weights, so the tolerances are float32 round-off: the program
and the reference order their sums differently (a chunked scan against
a token-by-token recurrence, grouped products against a dense gate
matrix), which is worth 1e-5 of a logit of order one; 2e-4 leaves room
for the longest chain (ten tokens through four layers) and would not
pass a wrong state, a dropped expert or a wrong scale, which are errors
of 1e-2 and more.
"""

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
from paddle_tpu.models.hybrid import build_hybrid_lm_program
from paddle_tpu.ops.moe import topk_moe
from paddle_tpu.ops.ssm import mamba2_mixer

MODELS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark", "models")


def _load(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(MODELS, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load("granite_hybrid_reference")
prog = _load("granite_hybrid_program")

TOL = 2e-4
# one period shaped mamba, mamba, attention, mamba; the published
# multipliers; 4 of 8 experts held unless a test says otherwise
CFG = {
    "hidden_size": 32, "num_attention_heads": 4, "num_key_value_heads": 2,
    "num_hidden_layers": 4,
    "layer_types": ["mamba", "mamba", "attention", "mamba", "mamba"],
    "mamba_n_heads": 8, "mamba_d_head": 8, "mamba_d_state": 16,
    "mamba_n_groups": 1, "mamba_d_conv": 4, "mamba_expand": 2,
    "mamba_chunk_size": 8, "intermediate_size": 16,
    "shared_intermediate_size": 24, "num_experts_per_tok": 3,
    "num_local_experts": 4, "vocab_size": 97,
    "embedding_multiplier": 12, "residual_multiplier": 0.22,
    "attention_multiplier": 0.125, "logits_scaling": 16,
    "rms_norm_eps": 1e-5, "initializer_range": 0.1,
    "storage_dtype": "float32", "state_dtype": "float32",
    "deployment": {"router_experts": 8, "first_expert": 0},
    "engine": {"max_position": 64, "lanes": 3, "chunk_tokens": 4,
               "page_size": 4, "num_pages": 40, "kv_dtype": "float32",
               "state_dtype": "float32"},
}
HCFG = prog.hybrid_config(CFG)
SEQ = 24


def _weights(cfg, seed=0):
    rng = np.random.RandomState(seed)
    out = {}
    for name, shape, init in ref.spec(cfg):
        if init == "normal":
            out[name] = (cfg["initializer_range"]
                         * rng.randn(*shape)).astype(np.float32)
        else:
            out[name] = np.full(shape, float(init == "ones"), np.float32)
    # structural vectors off their trivial values, so a wrong use shows
    for name in out:
        if name.endswith(("dt_bias", "a_log", "mamba_d", "conv.b")):
            out[name] = (out[name] + 0.3 * rng.randn(
                *out[name].shape)).astype(np.float32)
    return out


WEIGHTS = _weights(CFG)


def _ref_logits(tokens, cfg=CFG, weights=WEIGHTS):
    t = np.asarray(tokens, np.int32)
    p = {k: jnp.asarray(v) for k, v in weights.items()}
    return np.asarray(ref.logits_at(cfg, "highest", p, t, np.arange(t.size)))


# -- (a) the mixer: one call == ragged chunks through the carried state --------


def _mixer_params(rng, d=32, H=8, P=8, N=16, G=1, K=4):
    d_in, ch = H * P, H * P + 2 * G * N
    r = lambda *s: jnp.asarray(0.3 * rng.randn(*s), jnp.float32)  # noqa: E731
    return dict(w_in=r(d, d_in + ch + H), conv_w=r(ch, K), conv_b=r(ch),
                dt_bias=r(H), a_log=r(H), d_skip=1 + r(H), norm_w=1 + r(d_in),
                w_out=r(d_in, d))


def _ref_mixer(p, u, **dims):
    names = {"w_in": "mamba_in.w", "conv_w": "mamba_conv.w",
             "conv_b": "mamba_conv.b", "dt_bias": "mamba_dt_bias",
             "a_log": "mamba_a_log", "d_skip": "mamba_d",
             "norm_w": "mamba_norm.scale", "w_out": "mamba_out.w"}
    return np.asarray(ref.mamba_mixer(
        dict(CFG, **dims), ref.products("highest"),
        {names[k]: v for k, v in p.items()}, jnp.asarray(u)))


def test_mamba2_mixer_ragged_chunks_equal_one_pass_and_the_reference():
    rng = np.random.RandomState(3)
    p = _mixer_params(rng)
    kw = dict(num_heads=8, head_dim=8, num_groups=1, state_size=16,
              chunk_size=4, eps=1e-5)
    lens = [11, 7, 0, 5]            # row 2 idle throughout
    seqs = [rng.randn(n, 32).astype(np.float32) for n in lens]
    want = [_ref_mixer(p, s) if len(s) else s for s in seqs]
    # one call over the whole (padded) window, zero state: the scan path
    C = 12
    x = np.zeros((4, C, 32), np.float32)
    for r, s in enumerate(seqs):
        x[r, :len(s)] = s
    full, _, _ = mamba2_mixer(jnp.asarray(x), jnp.asarray(lens), jnp.zeros(
        4, jnp.int32), None, None, **p, **kw)
    for r, s in enumerate(seqs):
        np.testing.assert_allclose(np.asarray(full)[r, :len(s)], want[r],
                                   atol=TOL)
    # ragged chunks of 4 through the carried state; row 3 restarts at
    # position 0 after its first sequence with a second one
    again = rng.randn(6, 32).astype(np.float32)
    want_again = _ref_mixer(p, again)
    ssm = jnp.asarray(rng.randn(4, 8, 8, 16), jnp.float32)   # junk: a
    conv = jnp.asarray(rng.randn(4, 3, 96), jnp.float32)     # reused lane
    done = [0, 0, 0, 0]
    got = [[] for _ in lens]
    plan = [(s, n) for s, n in zip(seqs, lens)]
    plan[3] = (np.concatenate([seqs[3], again]), 11)
    for _ in range(4):
        xs = np.zeros((4, 4, 32), np.float32)
        nv = np.zeros(4, np.int32)
        pos = np.zeros(4, np.int32)
        for r, (s, n) in enumerate(plan):
            cut = n
            if r == 3 and done[r] < 5:
                cut = 5             # never a chunk across two sequences
            c = min(4, cut - done[r])
            xs[r, :c] = s[done[r]:done[r] + c]
            nv[r] = c
            pos[r] = done[r] - (5 if r == 3 and done[r] >= 5 else 0)
        before = (np.asarray(ssm), np.asarray(conv))
        y, ssm, conv = mamba2_mixer(jnp.asarray(xs), jnp.asarray(nv),
                                    jnp.asarray(pos), ssm, conv, **p, **kw)
        for r in range(4):
            got[r].append(np.asarray(y)[r, :nv[r]])
            done[r] += int(nv[r])
            if nv[r] == 0:          # an idle row touches neither state
                np.testing.assert_array_equal(np.asarray(ssm)[r], before[0][r])
                np.testing.assert_array_equal(np.asarray(conv)[r], before[1][r])
    assert done == [11, 7, 0, 11]
    for r in (0, 1):
        np.testing.assert_allclose(np.concatenate(got[r]), want[r], atol=TOL)
    out3 = np.concatenate(got[3])
    np.testing.assert_allclose(out3[:5], want[3], atol=TOL)
    np.testing.assert_allclose(out3[5:], want_again, atol=TOL)


@pytest.mark.parametrize("R,H,P,N,G,T", [
    (3, 8, 16, 128, 1, 8),      # one block of 8 heads
    (2, 8, 32, 128, 2, 16),     # two groups, a block each
    (2, 64, 64, 128, 1, 16),    # the cell's head shape: blocks of 16 heads
])
def test_state_step_kernel_equals_its_reference(monkeypatch, R, H, P, N, G, T):
    """The Pallas body under the interpreter against the plain einsums:
    both sum in float32, in another order (1e-5 of values of order ten).
    Row 0 arrives with a decay of 0 and a state of 1e30: dropped by the
    multiplication, not by a select."""
    from paddle_tpu.kernels import mamba2_state as m

    rng = np.random.RandomState(R + H)
    ssm = jnp.asarray(rng.randn(R, H, P, N), jnp.float32).at[0].set(1e30)
    c, b = (jnp.asarray(rng.randn(R, G, T, N), jnp.float32) for _ in "cb")
    xw = jnp.asarray(rng.randn(R, T, H * P), jnp.float32)
    decay = jnp.asarray(rng.rand(R, H), jnp.float32).at[0].set(0.0)
    want_y, want_s = m._reference(ssm, c, b, xw, decay)
    monkeypatch.setenv("PADDLE_TPU_KERNEL_INTERPRET", "1")
    assert m._head_block(H // G, P, N) is not None
    y, s = m.state_step(ssm, c, b, xw, decay)
    np.testing.assert_allclose(np.asarray(s)[1:], np.asarray(want_s)[1:],
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(y)[1:], np.asarray(want_y)[1:],
                               atol=1e-4, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(s)[0], np.asarray(want_s)[0],
                               atol=1e-4)
    assert np.all(np.isfinite(np.asarray(s)))


def test_mamba2_mixer_through_the_kernel_equals_the_reference(monkeypatch):
    """The mixer at a state of 128 (where the kernel's tiles fit) with
    the kernel's body interpreted: chunks of 8 through the carried
    state against the token-by-token reference."""
    monkeypatch.setenv("PADDLE_TPU_KERNEL_INTERPRET", "1")
    rng = np.random.RandomState(21)
    # 8 heads of 16 = 128 rows, a whole lane tile: the kernel's path
    p = _mixer_params(rng, H=8, P=16, N=128)
    kw = dict(num_heads=8, head_dim=16, num_groups=1, state_size=128,
              chunk_size=8, eps=1e-5)
    seq = rng.randn(19, 32).astype(np.float32)
    want = _ref_mixer(p, seq, mamba_n_heads=8, mamba_d_head=16,
                      mamba_d_state=128, mamba_expand=4)
    ssm = jnp.asarray(rng.randn(2, 8, 16, 128), jnp.float32)    # a used lane
    conv = jnp.asarray(rng.randn(2, 3, 8 * 16 + 256), jnp.float32)
    got, done = [], 0
    while done < 19:
        c = min(8, 19 - done)
        xs = np.zeros((2, 8, 32), np.float32)
        xs[1, :c] = seq[done:done + c]
        y, ssm, conv = mamba2_mixer(
            jnp.asarray(xs), jnp.asarray([0, c], jnp.int32),
            jnp.asarray([0, done], jnp.int32), ssm, conv, **p, **kw)
        got.append(np.asarray(y)[1, :c])
        done += c
    np.testing.assert_allclose(np.concatenate(got), want, atol=TOL)


# -- (b), (c) the experts ---------------------------------------------------------


def _moe_case(seed=5, T=12, d=32, E=8, f=16):
    rng = np.random.RandomState(seed)
    x = rng.randn(T, d).astype(np.float32)
    p = {"router.w": rng.randn(d, E).astype(np.float32),
         "experts_in.w": 0.3 * rng.randn(E, d, 2 * f).astype(np.float32),
         "experts_out.w": 0.3 * rng.randn(E, f, d).astype(np.float32)}
    # ties impossible: the third and fourth logits of every token differ
    logits = np.sort(x @ p["router.w"], axis=-1)
    assert np.min(logits[:, -3] - logits[:, -4]) > 1e-3
    return x, p


def _ref_moe(x, p, held, first, experts=8):
    cfg = dict(CFG, num_local_experts=held,
               deployment={"router_experts": experts, "first_expert": first})
    share = {"router.w": p["router.w"],
             "experts_in.w": p["experts_in.w"][first:first + held],
             "experts_out.w": p["experts_out.w"][first:first + held]}
    return np.asarray(ref.moe(cfg, ref.products("highest"),
                              {k: jnp.asarray(v) for k, v in share.items()},
                              jnp.asarray(x)))


def _run_moe(x, p, valid, held, first, loads=None, block_rows=8):
    out, loads = topk_moe(
        jnp.asarray(x), jnp.asarray(valid), jnp.asarray(p["router.w"]),
        jnp.asarray(p["experts_in.w"][first:first + held]),
        jnp.asarray(p["experts_out.w"][first:first + held]), loads,
        top_k=3, num_experts=8, first_expert=first, block_rows=block_rows)
    return np.asarray(out), np.asarray(loads)


@pytest.mark.parametrize("held,first", [(8, 0), (4, 0), (4, 4), (3, 2)])
def test_topk_moe_equals_the_reference_and_padding_takes_no_expert(held, first):
    x, p = _moe_case()
    valid = np.ones(12, bool)
    valid[[2, 7, 8]] = False
    out, loads = _run_moe(x, p, valid, held, first)
    want = _ref_moe(x, p, held, first)
    np.testing.assert_allclose(out[valid], want[valid], atol=TOL)
    assert np.all(out[~valid] == 0.0)
    # hand count: each valid token's top 3, those that fall in the share
    top = np.argsort(-(x @ p["router.w"]), axis=-1)[:, :3]
    count = np.zeros(held, np.int64)
    for t in np.flatnonzero(valid):
        for e in top[t]:
            if first <= e < first + held:
                count[e - first] += 1
    np.testing.assert_array_equal(loads, count)
    assert loads.sum() <= 3 * valid.sum()


def test_two_shares_and_the_shared_expert_once_equal_the_uncut_layer():
    """Guide section 4: the chip's share is right if the shares add up.
    Experts 0-3 and 4-7 on the same tokens, summed, are the uncut
    eight-expert layer; the shared expert belongs to neither share and
    is counted once."""
    x, p = _moe_case(seed=9)
    valid = np.ones(12, bool)
    lo, n_lo = _run_moe(x, p, valid, 4, 0)
    hi, n_hi = _run_moe(x, p, valid, 4, 4)
    whole, n_all = _run_moe(x, p, valid, 8, 0)
    np.testing.assert_allclose(lo + hi, whole, atol=TOL)
    np.testing.assert_allclose(whole, _ref_moe(x, p, 8, 0), atol=TOL)
    np.testing.assert_array_equal(np.concatenate([n_lo, n_hi]), n_all)
    assert n_all.sum() == 3 * 12        # dropless: every pair is somewhere
    rng = np.random.RandomState(1)
    w_in = jnp.asarray(0.3 * rng.randn(32, 48), jnp.float32)
    w_out = jnp.asarray(0.3 * rng.randn(24, 32), jnp.float32)
    shared = np.asarray(ref.gated_ffn(ref.products("highest"),
                                      jnp.asarray(x), w_in, w_out))
    from paddle_tpu.ops.decoder import gated_silu_ffn

    np.testing.assert_allclose(
        np.asarray(gated_silu_ffn(jnp.asarray(x), w_in, w_out)), shared,
        atol=TOL)
    np.testing.assert_allclose((lo + shared) + hi, whole + shared, atol=TOL)


# -- (d) the step program: pages + state == one full forward --------------------


def _scope_with(weights):
    scope = fluid.Scope()
    for k, v in weights.items():
        scope.set_var(k, jnp.asarray(v))
    return scope


def _drive_step(plan, lanes, chunk, decode_after):
    """Feed `plan` ({lane: tokens}) through the step program: lane r gets
    chunks of `chunk` tokens until `decode_after` tokens are in, then a
    token a step (lanes of different lengths run out at different
    steps). The program's head runs on each row's last valid position:
    returns, per lane, {position: logits there} and the final loads.
    The logits are read through an extra fetch of the same run (the
    head's scaled output)."""
    geom = CacheGeometry(num_pages=40, page_size=4, max_pages_per_seq=16)
    main, fetches = build_hybrid_step_program(HCFG, geom, chunk)
    logits_var = [op for op in main.global_block().ops
                  if op.type == "scale"][-1].outputs["Out"][0]
    exe = fluid.Executor(fluid.TPUPlace())
    scope = _scope_with(WEIGHTS)
    state = {n: jnp.zeros([lanes if s == -1 else s for s in shp], dt)
             for n, (shp, dt) in HCFG.state_shapes(-1).items()}
    # the page pools are the step's own state: it finds them in the
    # scope, rewrites them and the executor stores them back
    for names in pool_names(len(HCFG.attention_layers))[:2]:
        for name in names:
            scope.set_var(name, jnp.zeros(
                (HCFG.num_kv_heads, 40, 4, HCFG.head_dim)))
    tables = np.zeros((lanes, 16), np.int32)
    for r in range(lanes):
        tables[r] = 1 + r * 12 + np.arange(16) % 12
    done = {r: 0 for r in plan}
    got = {r: {} for r in plan}
    while any(done[r] < len(plan[r]) for r in plan):
        toks = np.zeros((lanes, chunk), np.int64)
        nv = np.zeros(lanes, np.int32)
        pos = np.zeros(lanes, np.int64)
        for r, seq in plan.items():
            c = min(chunk if done[r] < decode_after else 1,
                    len(seq) - done[r])
            toks[r, :c] = seq[done[r]:done[r] + c]
            nv[r], pos[r] = c, done[r]
        feed = {"gen_tokens": toks, "gen_pos_ids": np.zeros_like(toks),
                "gen_positions": pos, "gen_num_valid": nv,
                "gen_block_tables": tables, **state}
        outs = exe.run(main, feed=feed, fetch_list=fetches + [logits_var],
                       scope=scope, return_numpy=False)
        state = dict(zip(state, outs[1:-1]))
        logits = np.asarray(outs[-1])                      # [lanes, 1, V]
        tokens = np.asarray(outs[0]).reshape(lanes, chunk)
        for r in plan:
            if nv[r]:
                done[r] += int(nv[r])
                got[r][done[r] - 1] = logits[r, 0]
                assert np.all(tokens[r] == np.argmax(logits[r, 0]))
    return got, np.asarray(state["gen_state_moe_loads"])


@pytest.mark.parametrize("decode_after", [8, 0])
def test_step_program_chunked_prefill_then_decode_equals_one_forward(
        decode_after):
    """Chunks of 4 then a token a step (8), and a token a step from the
    start (0: every position ends a row, so every position is judged),
    through pages and state, against one full forward."""
    rng = np.random.RandomState(11)
    plan = {0: rng.randint(1, 97, 13), 2: rng.randint(1, 97, 9)}  # lane 1 idle
    got, loads = _drive_step(plan, lanes=3, chunk=4, decode_after=decode_after)
    for r, seq in plan.items():
        want = _ref_logits(seq)
        assert sorted(got[r]) == ([3, 7] + list(range(8, len(seq)))
                                  if decode_after else list(range(len(seq))))
        for at, logits in got[r].items():
            np.testing.assert_allclose(logits, want[at], atol=TOL)
    # every valid token was routed in every layer; dropless: the pairs
    # held are at most all of them
    assert loads.shape == (4, 4)
    assert 0 < loads.sum() <= 3 * 4 * (13 + 9)


def test_export_program_equals_the_reference():
    main, startup, _feeds, fetches = build_hybrid_lm_program(HCFG, SEQ)
    toks = np.random.RandomState(2).randint(1, 97, (2, SEQ)).astype(np.int64)
    exe = fluid.Executor(fluid.TPUPlace())
    (out,) = exe.run(main, feed={"tokens": toks},
                     fetch_list=[fetches["logits"]],
                     scope=_scope_with(WEIGHTS))
    for b in range(2):
        np.testing.assert_allclose(out[b], _ref_logits(toks[b]), atol=TOL)


# -- (e) the engine ---------------------------------------------------------------


@pytest.fixture(scope="module")
def predictor(tmp_path_factory):
    d = str(tmp_path_factory.mktemp("hybrid_lm"))
    main, _startup, _feeds, fetches = build_hybrid_lm_program(HCFG, SEQ)
    scope = _scope_with(WEIGHTS)
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.TPUPlace())
        fluid.io.save_inference_model(d, ["tokens"], [fetches["logits"]],
                                      exe, main)
    return create_predictor(Config(d))


def _engine(predictor, **kw):
    args = dict(mode="ragged", page_size=4, num_pages=40,
                max_decode_batch=3, chunk_tokens=4, prefix_cache=False)
    args.update(kw)
    return GenerationEngine(predictor, HCFG, **args)


def _greedy_by_reference(prompt, n):
    toks = [int(t) for t in prompt]
    for _ in range(n):
        toks.append(int(np.argmax(_ref_logits(toks)[-1])))
    return toks[len(prompt):]


def _prompts(n, seed, lo=5, hi=14):
    rng = np.random.RandomState(seed)
    return [rng.randint(1, 97, rng.randint(lo, hi)).astype(np.int64)
            for _ in range(n)]


def test_engine_serves_the_reference_tokens_and_reuses_lanes(predictor):
    prompts = _prompts(7, seed=4)       # 7 requests over 3 lanes: reuse
    want = [_greedy_by_reference(p, 6) for p in prompts]
    with _engine(predictor) as eng:
        streams = [eng.submit(p, max_new_tokens=6) for p in prompts]
        got = [s.result(timeout=300) for s in streams]
        st = eng.stats()
    assert got == want
    # a fresh engine gives a reused lane's request the same tokens
    with _engine(predictor) as fresh:
        assert fresh.generate(prompts[-1], max_new_tokens=6) == want[-1]
    # counters against a hand count: every prompt and every fed-back
    # token went through all 4 expert layers once
    routed = sum(len(p) + 5 for p in prompts) * 4
    assert st["moe_tokens_routed_total"] == routed
    assert st["state_lane_resets_total"] == 7
    assert 0 < st["moe_held_assignments_total"] <= 3 * routed
    assert st["moe_expert_load_max"] >= st["moe_expert_load_mean"] > 0
    lane = 3 * (8 * 8 * 16 + 3 * 96) * 4        # 3 Mamba layers, float32
    assert st["recurrent_state_bytes"] == 3 * lane + 4 * 4 * 4


def test_engine_evicted_sequence_resumes_identically(predictor):
    """A dry pool evicts the youngest sequence, which re-prefills from
    prompt + generated: right for a recurrent layer too, because the
    state restarts from zero at position 0."""
    prompts = _prompts(3, seed=8, lo=10, hi=14)
    want = [_greedy_by_reference(p, 10) for p in prompts]
    with _engine(predictor, num_pages=14) as eng:     # 13 usable pages
        streams = [eng.submit(p, max_new_tokens=10) for p in prompts]
        got = [s.result(timeout=300) for s in streams]
        st = eng.stats()
    assert got == want
    assert st["evicted_total"] >= 1
    assert st["state_lane_resets_total"] == 3 + st["evicted_total"]


@pytest.mark.parametrize("kwargs,needs", [
    (dict(prefix_cache=True), "snapshots"),
    (dict(spec_tokens=2, draft=object()), "rollback"),
    (dict(page_store=object()), "on the wire"),
    (dict(mode="two_lane"), "ragged engine"),
])
def test_engine_refuses_what_recurrent_state_cannot_serve(predictor, kwargs,
                                                         needs):
    with pytest.raises(ValueError, match=needs):
        _engine(predictor, start=False, **kwargs)


# -- (f) the GPT path is untouched --------------------------------------------------


def test_gpt_engine_step_feeds_and_fetches_are_the_parents(tmp_path):
    """The dense decoder's ragged step takes and gives exactly what it
    did before recurrent state existed, in the program and in the dict
    the engine assembles each step: 5 scheduler feeds in, tokens out,
    no `gen_state_*`; 2 pools a layer are its state, neither fed nor
    fetched."""
    from paddle_tpu.generation.model import GPTConfig, build_lm_program

    cfg = GPTConfig(vocab_size=97, hidden_size=32, num_layers=2, num_heads=4,
                    ffn_size=64, max_position=64, hidden_dropout=0.0,
                    attention_dropout=0.0)
    main, startup, _feeds, fetches = build_lm_program(cfg, SEQ)
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.TPUPlace())
        exe.run(startup)
        fluid.io.save_inference_model(str(tmp_path), ["tokens"],
                                      [fetches["logits"]], exe, main)
    want = sorted(["gen_tokens", "gen_pos_ids", "gen_positions",
                   "gen_num_valid", "gen_block_tables"])
    seen = []
    with GenerationEngine(create_predictor(Config(str(tmp_path))), cfg,
                          mode="ragged", page_size=4, num_pages=16,
                          max_decode_batch=2, chunk_tokens=4,
                          prefix_cache=False) as eng:
        bind = eng._bind_ragged
        eng._bind_ragged = lambda feed: seen.append(sorted(feed)) or bind(feed)
        assert len(eng.generate(np.arange(1, 7), max_new_tokens=3)) == 3
        assert eng._state_names == () and eng.cache.state == {}
        assert len(eng._ragged_fetches) == 1
        assert eng._ragged_bound.compiled.donatable_names == [
            f"gen_{kv}_pages_{i}" for i in range(2) for kv in "kv"]
        st = eng.stats()
    assert seen and all(names == want for names in seen)
    assert "moe_held_assignments_total" not in st
    assert st["moe_tokens_routed_total"] == st["state_lane_resets_total"] == 0


# -- (g) the expert kernel: the same served tokens on both paths -------------------


def test_engine_serves_the_same_tokens_through_the_expert_kernel(
        tmp_path, monkeypatch):
    """Width 128 with experts of 128 and a window of 4 lanes x 4 tokens,
    where kernels/moe_ffn.py's tiles fit: the engine under the
    interpreter (every expert layer through ``moe_grouped_ffn``, gauge
    ``moe_kernel_layers`` 4) serves the tokens of the ``ragged_dot``
    path (gauge 0) and counts the same loads."""
    from paddle_tpu.runtime import dispatch

    cfg = dict(CFG, hidden_size=128, intermediate_size=128, mamba_d_head=32,
               vocab_size=101)
    hcfg = prog.hybrid_config(cfg)
    main, _startup, _feeds, fetches = build_hybrid_lm_program(hcfg, SEQ)
    with fluid.scope_guard(_scope_with(_weights(cfg, seed=3))):
        fluid.io.save_inference_model(
            str(tmp_path), ["tokens"], [fetches["logits"]],
            fluid.Executor(fluid.TPUPlace()), main)
    prompts = _prompts(5, seed=6)
    seen = {}
    for path in ("kernel", "ragged_dot"):
        if path == "kernel":
            monkeypatch.setenv("PADDLE_TPU_KERNEL_INTERPRET", "1")
        else:
            monkeypatch.delenv("PADDLE_TPU_KERNEL_INTERPRET")
        dispatch._SHARED_CACHE.clear()      # compiled by program content
        with GenerationEngine(
                create_predictor(Config(str(tmp_path))), hcfg, mode="ragged",
                page_size=4, num_pages=40, max_decode_batch=4,
                chunk_tokens=4, prefix_cache=False) as eng:
            streams = [eng.submit(p, max_new_tokens=5) for p in prompts]
            tokens = [s.result(timeout=300) for s in streams]
        st = eng.stats()
        seen[path] = (tokens, st["moe_held_assignments_total"],
                      st["moe_kernel_layers"])
    dispatch._SHARED_CACHE.clear()
    assert seen["kernel"][:2] == seen["ragged_dot"][:2]
    assert (seen["kernel"][2], seen["ragged_dot"][2]) == (4, 0)
