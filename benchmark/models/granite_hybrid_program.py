"""The system under test for the `granite_hybrid` model: what the
benchmark takes from the program to serve it: build_hybrid_lm_program ->
save_inference_model -> create_predictor -> GenerationEngine in ragged
mode. The one file of the pair that imports paddle_tpu."""

import os
import shutil
import tempfile

import numpy as np


def hybrid_config(cfg):
    """The configuration file's keys (the published config.json's own
    names) as the program's HybridConfig."""
    from paddle_tpu.models.hybrid import HybridConfig

    return HybridConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        layer_types=tuple(cfg["layer_types"][:cfg["num_hidden_layers"]]),
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        mamba_heads=cfg["mamba_n_heads"], mamba_head_dim=cfg["mamba_d_head"],
        mamba_state=cfg["mamba_d_state"], mamba_groups=cfg["mamba_n_groups"],
        mamba_conv=cfg["mamba_d_conv"], mamba_chunk=cfg["mamba_chunk_size"],
        moe_experts=cfg["deployment"]["router_experts"],
        moe_top_k=cfg["num_experts_per_tok"],
        moe_expert_size=cfg["intermediate_size"],
        moe_held=cfg["num_local_experts"],
        moe_first=cfg["deployment"]["first_expert"],
        shared_size=cfg["shared_intermediate_size"],
        max_position=cfg["engine"]["max_position"],
        embedding_multiplier=cfg["embedding_multiplier"],
        residual_multiplier=cfg["residual_multiplier"],
        attention_multiplier=cfg["attention_multiplier"],
        logits_scaling=cfg["logits_scaling"], rms_eps=cfg["rms_norm_eps"],
        initializer_range=cfg["initializer_range"],
        param_dtype=cfg["storage_dtype"],
        state_dtype=cfg["engine"]["state_dtype"])


def build_engine(cfg, weights):
    """Export the loss-free LM's program (no parameters: the weights are
    made on the device from the seed and put into the predictor's scope,
    as models/gpt_program.py does and for its reason), load it, and
    start the engine with the configuration's geometry."""
    import paddle_tpu as fluid
    from paddle_tpu import generation
    from paddle_tpu.inference import Config, create_predictor
    from paddle_tpu.models.hybrid import build_hybrid_lm_program

    hcfg = hybrid_config(cfg)
    eng_cfg = cfg["engine"]
    model_dir = tempfile.mkdtemp(prefix="bench_lm_")
    main, _startup, _feeds, fetches = build_hybrid_lm_program(
        hcfg, int(eng_cfg["export_seq_len"]))
    exe = fluid.Executor(fluid.TPUPlace())
    fluid.io.save_inference_model(model_dir, ["tokens"], [fetches["logits"]],
                                  exe, main, program_only=True)
    np.savez(os.path.join(model_dir, "__params__.npz"))    # no arrays
    pred = create_predictor(Config(model_dir))
    shutil.rmtree(model_dir, ignore_errors=True)
    scope = pred._scope     # the seam GenerationEngine itself uses
    for name, value in weights.items():
        scope.set_var(name, value)
    eng = generation.GenerationEngine(
        pred, hcfg, warmup=True, mode="ragged",
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
    """Share of the page pool's pages that hold a sequence now."""
    return float(eng.cache.stats()["page_utilization"])


def state_arrays(eng):
    """The device arrays of the recurrent state, for the release."""
    return list(eng.cache.state.values())


COUNTERS = ("decode_active_lane_steps_total",
            "decode_capacity_lane_steps_total", "prefill_tokens_total",
            "ragged_steps_total", "prefill_chunks_total",
            "moe_tokens_routed_total", "moe_held_assignments_total",
            "state_lane_resets_total") + tuple(
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
        "recurrent_state_bytes")}
