"""The system under test for the `mimo` model: what the benchmark takes
from the program to serve it: build_mimo_lm_program ->
save_inference_model -> create_predictor -> GenerationEngine in ragged
mode. The one file of the pair that imports paddle_tpu."""

import os
import shutil
import tempfile

import numpy as np


def mimo_config(cfg):
    """The configuration file's keys (the published config.json's own
    names) as the program's MiMoConfig."""
    from paddle_tpu.models.mimo import MiMoConfig

    n, hd = cfg["num_hidden_layers"], cfg["head_dim"]
    return MiMoConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        attention_kinds=tuple("window" if w else "full"
                              for w in cfg["hybrid_layer_pattern"][:n]),
        ffn_kinds=tuple("experts" if e else "dense"
                        for e in cfg["moe_layer_freq"][:n]),
        num_kv_heads=cfg["num_key_value_heads"],
        window_kv_heads=cfg["swa_num_key_value_heads"],
        k_dim=hd, v_dim=cfg["v_head_dim"],
        rotary_dim=int(round(cfg["partial_rotary_factor"] * hd)) // 2 * 2,
        window=cfg["sliding_window"], dense_size=cfg["intermediate_size"],
        moe_experts=cfg["deployment"]["router_experts"],
        moe_top_k=cfg["num_experts_per_tok"],
        moe_expert_size=cfg["moe_intermediate_size"],
        moe_held=cfg["n_routed_experts"],
        moe_first=cfg["deployment"]["first_expert"],
        max_position=cfg["engine"]["max_position"],
        rope_base=float(cfg["rope_theta"]),
        window_rope_base=float(cfg["swa_rope_theta"]),
        sink=bool(cfg["add_swa_attention_sink_bias"]),
        value_scale=cfg["attention_value_scale"],
        rms_eps=cfg["layernorm_epsilon"],
        initializer_range=cfg["initializer_range"],
        param_dtype=cfg["storage_dtype"])


def placed(cfg, name, value):
    """A drawn leaf as the model holds it: the sink and the selection
    bias are rescaled from the draw as the configuration's `assumed`
    says (the reference does the same to its own copy)."""
    import jax.numpy as jnp

    a = cfg["assumed_init"]
    z = value.astype(jnp.float32) / cfg["initializer_range"]
    if name.endswith("attn_sink"):
        return (a["sink_mean"] + a["sink_std"] * z).astype(value.dtype)
    if name.endswith("router.bias"):
        return (a["bias_std"] * z).astype(value.dtype)
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

    mcfg = mimo_config(cfg)
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
    return eng, pred


def ragged_step():
    """The engine's bound ragged step, found among the live bound steps
    by its tag."""
    from paddle_tpu.runtime import dispatch

    return next(b for b in dispatch.live_bound_steps()
                if b.compiled.tag == "generation/ragged_step")


def pool_fill(eng):
    """Share of the full layers' page pool that holds a sequence now."""
    return float(eng.cache.stats()["page_utilization"])


def state_arrays(eng):
    """The device arrays the release frees beside the full layers'
    pools: the window layers' pools and the experts' load counts."""
    cache = eng.cache
    return (list(cache.window_k_pages) + list(cache.window_v_pages)
            + list(cache.state.values()))


COUNTERS = ("decode_active_lane_steps_total",
            "decode_capacity_lane_steps_total", "prefill_tokens_total",
            "ragged_steps_total", "prefill_chunks_total",
            "moe_tokens_routed_total", "moe_held_assignments_total",
            "attn_live_pages_full_total", "attn_live_pages_window_total",
            "kv_window_pages_recycled_total", "evicted_total") + tuple(
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
        "kv_pages_resident_full", "kv_pages_resident_window")}
