"""The system under test for the `openpangu` model: what the benchmark
takes from the program to serve it: build_mimo_lm_program ->
save_inference_model -> create_predictor -> GenerationEngine in ragged
mode, a MiMoConfig whose every layer is `latent` attention (MLA) under
sandwich norms, a leading dense layer, then routed experts beside a
shared one. The one file of the pair that imports paddle_tpu."""

import os
import shutil
import tempfile
import weakref

import numpy as np

_BUILT = []     # the engine build_engine started last, by weak reference


def openpangu_config(cfg):
    """The configuration file's keys (the published config.json's own
    names) as the program's MiMoConfig: one latent cache head, keys a
    head qk_nope + qk_rope wide, the rope part the head's last values."""
    from paddle_tpu.models.mimo import MiMoConfig

    n, dense = cfg["num_hidden_layers"], cfg["first_k_dense_replace"]
    rope = cfg["qk_rope_head_dim"]
    return MiMoConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        attention_kinds=("latent",) * n,
        ffn_kinds=tuple("dense" if i < dense else "experts"
                        for i in range(n)),
        num_kv_heads=1, window_kv_heads=1,
        k_dim=cfg["qk_nope_head_dim"] + rope, v_dim=cfg["v_head_dim"],
        rotary_dim=rope, window=0, dense_size=cfg["intermediate_size"],
        moe_experts=cfg["deployment"]["router_experts"],
        moe_top_k=cfg["num_experts_per_tok"],
        moe_expert_size=cfg["moe_intermediate_size"],
        moe_held=cfg["n_routed_experts"],
        moe_first=cfg["deployment"]["first_expert"],
        max_position=cfg["engine"]["max_position"],
        rope_base=float(cfg["rope_theta"]), sink=False,
        q_rank=cfg["q_lora_rank"], kv_rank=cfg["kv_lora_rank"],
        sandwich_norm=bool(cfg["sandwich_norm"]),
        shared_expert_size=(cfg["n_shared_experts"] or 0)
        * cfg["moe_intermediate_size"],
        routed_scale=float(cfg["routed_scaling_factor"]),
        rms_eps=cfg["rms_norm_eps"],
        initializer_range=cfg["initializer_range"],
        param_dtype=cfg["storage_dtype"])


def placed(cfg, name, value):
    """A drawn leaf as the model holds it: the selection bias is
    rescaled from the draw as the configuration's `assumed` says (the
    reference does the same to its own copy); only that leaf is touched."""
    import jax.numpy as jnp

    if name.endswith("router.bias"):
        z = value.astype(jnp.float32) / cfg["initializer_range"]
        return (cfg["assumed_init"]["bias_std"] * z).astype(value.dtype)
    return value


def build_engine(cfg, weights):
    """Export the loss-free LM's program (no parameters: the weights are
    made on the device from the seed and put into the predictor's scope,
    as models/gpt_program.py does and for its reason), load it, and
    start the engine with the configuration's geometry."""
    import paddle_tpu as fluid
    from paddle_tpu import generation
    from paddle_tpu.inference import Config, create_predictor
    from paddle_tpu.models.mimo import build_mimo_lm_program

    mcfg = openpangu_config(cfg)
    eng_cfg = cfg["engine"]
    model_dir = tempfile.mkdtemp(prefix="bench_lm_")
    main, _startup, _feeds, fetches = build_mimo_lm_program(
        mcfg, int(eng_cfg["export_seq_len"]))
    exe = fluid.Executor(fluid.TPUPlace())
    fluid.io.save_inference_model(model_dir, ["tokens"], [fetches["logits"]],
                                  exe, main, program_only=True)
    np.savez(os.path.join(model_dir, "__params__.npz"))    # no arrays
    pred = create_predictor(Config(model_dir))
    shutil.rmtree(model_dir, ignore_errors=True)
    scope = pred._scope     # the seam GenerationEngine itself uses
    for name, value in weights.items():
        scope.set_var(name, placed(cfg, name, value))
    eng = generation.GenerationEngine(
        pred, mcfg, warmup=True, mode="ragged",
        page_size=int(eng_cfg["page_size"]),
        num_pages=int(eng_cfg["num_pages"]),
        max_decode_batch=int(eng_cfg["lanes"]),
        chunk_tokens=int(eng_cfg["chunk_tokens"]),
        queue_capacity=int(eng_cfg["queue_capacity"]),
        kv_dtype=eng_cfg["kv_dtype"], prefix_cache=False)
    _BUILT[:] = [weakref.ref(eng)]
    return eng, pred


def ragged_step():
    """The bound ragged step of the engine build_engine started (not any
    live step of that tag: a process that built several engines, as the
    rehearsal's workers do, holds several)."""
    return _BUILT[0]()._ragged_bound


def pool_fill(eng):
    """Share of the latent page pool that holds a sequence now."""
    return float(eng.cache.stats()["page_utilization"])


def state_arrays(eng):
    """The device arrays the release frees beside the latent pools (the
    cache has no V pools): the experts' load counts."""
    return list(eng.cache.state.values())


COUNTERS = ("decode_active_lane_steps_total",
            "decode_capacity_lane_steps_total", "prefill_tokens_total",
            "ragged_steps_total", "prefill_chunks_total",
            "moe_tokens_routed_total", "moe_held_assignments_total",
            "attn_live_pages_total", "attn_latent_pages_total",
            "attn_latent_key_rows_total", "evicted_total") + tuple(
                f"loop_{p}_us_total" for p in (
                    "wait", "admit", "grow", "draft", "assemble", "bind",
                    "step", "emit"))


def counters(eng):
    snap = eng.stats()
    return {k: int(snap[k]) for k in COUNTERS}


def gauges(eng):
    """Readings that are states, not counts: taken once, at the close of
    the window (the experts' loads are cumulative since the warm-up)."""
    snap = eng.stats()
    return {k: float(snap[k]) for k in (
        "moe_expert_load_max", "moe_expert_load_mean",
        "kv_pages_resident_full", "latent_layers")}
