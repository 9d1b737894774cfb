"""openPangu-Ultra-MoE serving: latent attention (MLA) over a paged cache
of latents with no V pool, read by one absorbed kernel for prefill and
decode, sandwich norms, a leading dense layer, then routed experts
(sigmoid router, selection bias, routed_scaling_factor) beside a shared
expert, through the ragged GenerationEngine.

Everything is checked against the plain reference the benchmark ships
(benchmark/models/openpangu_reference.py: float32, one causal pass,
latent attention in its published expanded form), at tiny widths on the
CPU with float32 weights, so the tolerances are float32 round-off (1e-5
of a logit of order one); 2e-4 leaves room for the longest chain and
would not pass any planted fault (a dropped rope key, an unnormed
latent, no post norms, routed scale 1, no shared expert), which move
logits by 1 and more.
"""

import importlib.util
import os

import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.generation import GenerationEngine
from paddle_tpu.generation.kvcache import PagedKVCache
from paddle_tpu.generation.model import (CacheGeometry,
                                         build_mimo_step_program)
from paddle_tpu.inference import Config, create_predictor
from paddle_tpu.models.mimo import build_mimo_lm_program
from paddle_tpu.ops.decoder import gated_silu_ffn
from paddle_tpu.ops.moe import topk_moe

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")


def _load(path, name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(BENCH, path, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load("models", "openpangu_reference")
prog = _load("models", "openpangu_program")
CFG = _load("tests", "tiny_openpangu").CONFIG
MCFG = prog.openpangu_config(CFG)
ENG = CFG["engine"]
TOL = 2e-4


def _weights(cfg, seed=0):
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


def _scope_with(weights):
    scope = fluid.Scope()
    for k, v in weights.items():
        scope.set_var(k, jnp.asarray(v))
    return scope


# -- (a) the programs against one full forward of the reference --------------------


def test_export_program_equals_the_reference():
    """The full causal forward (latent attention expanded, as published)
    and the reference agree at every position."""
    seq = 48
    main, _startup, _feeds, fetches = build_mimo_lm_program(MCFG, seq)
    toks = np.random.RandomState(2).randint(1, 89, (2, seq)).astype(np.int64)
    (out,) = fluid.Executor(fluid.TPUPlace()).run(
        main, feed={"tokens": toks}, fetch_list=[fetches["logits"]],
        scope=_scope_with(PLACED))
    for b in range(2):
        np.testing.assert_allclose(out[b], _ref_logits(toks[b]), atol=TOL)


def test_step_program_pages_latents_alone():
    """A latent layer's one pool: [1, pages, page_size + rope, kv_rank],
    no V pool, in the program and in the cache."""
    geom = CacheGeometry(num_pages=ENG["num_pages"],
                         page_size=ENG["page_size"], max_pages_per_seq=8)
    main, _fetches = build_mimo_step_program(MCFG, geom, 4)
    pools = {v.name: tuple(v.shape) for v in main.list_vars()
             if v.name.startswith("gen_") and "pages" in v.name}
    assert pools == {f"gen_k_pages_{j}": (1, 25, 16 + 8, 16)
                     for j in range(3)}
    ops = [op.type for op in main.global_block().ops]
    assert ops.count("latent_paged_attention") == 3
    assert ops.count("latent_cache_write") == 3


@pytest.mark.parametrize("decode_after", [40, 0])
def test_step_program_through_latent_pages_equals_one_forward(decode_after):
    """Chunks of 4 then a token a step (40), and a token a step from the
    start (0: every position is judged), over pages of 16: the absorbed
    kernel over the latents the step wrote, against one full forward of
    the expanded reference."""
    rng = np.random.RandomState(11)
    plan = {0: rng.randint(1, 89, 77), 2: rng.randint(1, 89, 50)}
    lanes, chunk = 3, ENG["chunk_tokens"]
    scope = _scope_with(PLACED)
    cache = PagedKVCache(
        3, *MCFG.pool_geometry("latent")[:2],
        k_dim=MCFG.pool_geometry("latent")[2], latent=True,
        num_pages=ENG["num_pages"], page_size=ENG["page_size"],
        max_seqs=lanes, max_pages_per_seq=8,
        state=MCFG.state_shapes(lanes), scope=scope.new_scope())
    cache.reset_buffers()
    assert cache.v_pages == [] and len(cache.k_pages) == 3
    assert cache.pool_bytes() == 3 * 25 * 16 * 24 * 4
    geom = CacheGeometry(num_pages=ENG["num_pages"],
                         page_size=ENG["page_size"], max_pages_per_seq=8)
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
        feed = {"gen_tokens": toks, "gen_pos_ids": ids,
                "gen_positions": pos, "gen_num_valid": nv,
                "gen_block_tables": cache.block_tables.copy(),
                **cache.state}
        outs = exe.run(main, feed=feed, fetch_list=fetches + [logits_var],
                       scope=cache.scope, return_numpy=False)
        cache.set_state(outs[1:-1])
        logits = np.asarray(outs[-1])
        for r in plan:
            if nv[slot[r]]:
                done[r] += int(nv[slot[r]])
                got[r][done[r] - 1] = logits[slot[r], 0]
    for r, seq in plan.items():
        want = _ref_logits(seq)
        assert len(got[r]) == (len(seq) if not decode_after
                               else 10 + len(seq) - 40)
        for at, logits in got[r].items():
            np.testing.assert_allclose(logits, want[at], atol=TOL)


# -- (b) the expert layer: the chip's share and the shared expert ------------------


def _moe_case(seed=5, T=12, d=32, E=8, f=16):
    rng = np.random.RandomState(seed)
    r = lambda *s: jnp.asarray(0.3 * rng.randn(*s), jnp.float32)  # noqa: E731
    return r(T, d), {"router.w": r(d, E), "router.bias": r(E),
                     "experts_in.w": r(E, d, 2 * f),
                     "experts_out.w": r(E, f, d),
                     "shared_in.w": r(d, 2 * f), "shared_out.w": r(f, d)}


def _share(x, p, held, first):
    out, loads = topk_moe(
        x, jnp.ones(x.shape[0], bool), p["router.w"],
        p["experts_in.w"][first:first + held],
        p["experts_out.w"][first:first + held], top_k=3, num_experts=8,
        first_expert=first, block_rows=8, score_func="sigmoid",
        select_bias=p["router.bias"], routed_scale=2.5)
    return np.asarray(out), np.asarray(loads)


def test_the_shares_and_the_shared_expert_add_up_to_the_uncut_layer():
    """The chip's share is right if the shares add up.
    The routed parts of four shares of two experts each, each over the
    same router, plus the shared expert once (every chip computes it
    alike), equal the uncut reference layer of eight experts."""
    x, p = _moe_case(seed=9)
    cfg = dict(CFG, n_routed_experts=8, num_experts_per_tok=3,
               moe_intermediate_size=16,
               deployment={"router_experts": 8, "first_expert": 0})
    whole = np.asarray(ref.moe(cfg, ref.products("highest"), p, x))
    parts = [_share(x, p, 2, first) for first in range(0, 8, 2)]
    shared = np.asarray(gated_silu_ffn(x, p["shared_in.w"],
                                       p["shared_out.w"]))
    np.testing.assert_allclose(sum(o for o, _n in parts) + shared, whole,
                               atol=TOL)
    assert sum(n.sum() for _o, n in parts) == 3 * 12    # dropless
    # the routed scale and the shared expert are each part of the layer
    for fault in ("routed_scale_1", "no_shared"):
        wrong = np.asarray(ref.moe(cfg, ref.products("highest"), p, x,
                                   fault))
        assert np.abs(wrong - whole).max() > 100 * TOL, fault


# -- (c) the engine -----------------------------------------------------------------


@pytest.fixture(scope="module")
def predictor(tmp_path_factory):
    d = str(tmp_path_factory.mktemp("openpangu_lm"))
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


def test_engine_serves_the_reference_through_latent_pages(predictor):
    """Five requests on three lanes (two lanes are reused), prompts of
    33-90 tokens in chunks of 4, then decoding through the latent pages:
    every served token is the one the reference puts first (a gap of
    float32 round-off). The latent counters follow the kernel's rule: a
    token at position p attends p + 1 keys, in each of the 3 layers."""
    eng = _engine(predictor)
    rng = np.random.default_rng(0)
    streams = []
    for p, n in ((70, 30), (40, 24), (90, 26), (55, 20), (33, 40)):
        prompt = rng.integers(1, 89, p, dtype=np.int64)
        streams.append((prompt, eng.submit(prompt, max_new_tokens=n,
                                           eos_id=None,
                                           on_token=lambda _t: eng.cache
                                           .check_integrity())))
    pairs = [(p, np.asarray(s.result(timeout=600), np.int64))
             for p, s in streams]
    eng.close()
    eng.cache.check_integrity()
    gaps = ref.served_gaps(CFG, WEIGHTS, pairs, 144, 40)
    assert len(gaps) == 140 and max(gaps) < TOL
    st = eng.stats()
    assert st["latent_layers"] == 3 and eng.cache.v_pages == []
    fed = [len(p) + len(s) - 1 for p, s in pairs]       # positions fed
    assert st["attn_latent_key_rows_total"] == 3 * sum(
        n * (n + 1) // 2 for n in fed)
    assert st["attn_latent_pages_total"] == 3 * st["attn_live_pages_total"]
    assert st["moe_tokens_routed_total"] % 2 == 0       # 2 expert layers
    assert 0 < st["moe_held_assignments_total"] <= \
        3 * st["moe_tokens_routed_total"]
    assert st["evicted_total"] == 0 and st["kv_pages_resident_full"] == 0


def test_engine_evicted_sequence_resumes_identically(predictor):
    """A latent pool too small for three long sequences: the youngest is
    preempted, gives its latent pages back, and resumes to the same
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
    (dict(prefix_cache=True), "latent pool's one page kind"),
    (dict(spec_tokens=2, draft=object()), "the MTP module"),
    (dict(page_store=object()), "latent pages on the wire"),
    (dict(mode="two_lane"), "ragged engine"),
    (dict(kv_dtype="int8"), "no latent pages"),
])
def test_engine_refuses_what_latent_layers_cannot_serve(predictor, kwargs,
                                                        needs):
    with pytest.raises(ValueError, match=needs):
        _engine(predictor, start=False, **kwargs)


# -- (d) each planted fault is far over the tolerance ---------------------------------


@pytest.mark.parametrize("fault", ref.FAULTS)
def test_planted_fault_moves_the_logits(fault):
    toks = np.random.RandomState(4).randint(1, 89, 64)
    assert np.abs(_ref_logits(toks, fault)
                  - _ref_logits(toks)).max() > 100 * TOL


# -- (e) the kernel under the interpreter serves the same tokens ------------------


def test_engine_serves_the_same_tokens_through_the_kernel(predictor,
                                                         monkeypatch):
    """The absorbed kernel under the Pallas interpreter
    (PADDLE_TPU_KERNEL_INTERPRET=1, ``mosaic:latent_paged_attention`` on
    the chip) serves the tokens of its jnp reference path."""
    from paddle_tpu.runtime import dispatch

    rng = np.random.default_rng(5)
    prompts = [rng.integers(1, 89, n, dtype=np.int64) for n in (37, 9, 21)]
    seen = []
    for interpret in (False, True):
        if interpret:
            monkeypatch.setenv("PADDLE_TPU_KERNEL_INTERPRET", "1")
        dispatch._SHARED_CACHE.clear()      # compiled by program content
        try:
            with _engine(predictor) as eng:
                streams = [eng.submit(p, max_new_tokens=10, eos_id=None)
                           for p in prompts]
                seen.append([s.result(timeout=600) for s in streams])
        finally:
            monkeypatch.undo()
            dispatch._SHARED_CACHE.clear()
    assert seen[0] == seen[1]
