"""A toy preset of `mimo_v2_5_serve` for the rehearsal on the CPU: the
cell's keys at widths a CPU serves in seconds, float32 throughout, so
that the comparison's noise is round-off and each planted fault reads
far over the limit. Numbers from it mean nothing."""

CONFIG = {
    "name": "tiny_mimo", "model": "mimo",
    "hidden_size": 48, "num_attention_heads": 4, "head_dim": 24,
    "v_head_dim": 16, "num_key_value_heads": 1,
    "swa_num_key_value_heads": 2, "partial_rotary_factor": 0.334,
    "rope_theta": 10000000, "swa_rope_theta": 10000,
    "sliding_window": 20, "add_swa_attention_sink_bias": True,
    "attention_value_scale": 0.707, "layernorm_epsilon": 1e-5,
    "hybrid_layer_pattern": [0, 1, 1, 0, 1], "moe_layer_freq": [0, 1, 1, 1, 1],
    "num_hidden_layers": 4, "intermediate_size": 64,
    "moe_intermediate_size": 24, "n_routed_experts": 4,
    "num_experts_per_tok": 3, "vocab_size": 89,
    "deployment": {"router_experts": 8, "first_expert": 2},
    "initializer_range": 0.2,
    "assumed_init": {"sink_mean": 1.0, "sink_std": 1.0, "bias_std": 0.3},
    "storage_dtype": "float32", "reference_block": 16,
    "engine": {"mode": "ragged", "lanes": 3, "chunk_tokens": 4,
               "page_size": 16, "num_pages": 25, "kv_dtype": "float32",
               "queue_capacity": 16, "export_seq_len": 16,
               "max_position": 128},
}

TRAFFIC = {
    "kind": "serve_closed_loop_stated",
    "clients_per_lane": 2,
    "prompt_lens": [40, 70, 55, 90],
    "answer_lens": [24, 30, 20, 26],
    "drain": "cancel", "check_requests": 3, "check_pad_to": 128,
    "trace_seconds": 2,
    "limits": {"served_logit_gap": 1e-3, "step_argument_bytes_gap": 0.02},
}


def tiny_ctx(workload="mimo_v2_5_long_prompt_decode", seed=2 ** 31 + 77,
             seconds=1.0):
    # imported here: tests/test_mimo.py loads this file for CONFIG alone,
    # from where the harness is not importable
    import time

    import jax

    import harness

    cell = harness.Cell(harness.load_json(harness.os.path.join(
        harness.ROOT, "BENCHMARK.json")), workload)
    cell.config.update({k: (dict(v) if isinstance(v, dict) else v)
                        for k, v in CONFIG.items() if k != "name"})
    cell.traffic.update({k: (dict(v) if isinstance(v, dict) else v)
                         for k, v in TRAFFIC.items()})
    ctx = harness.Ctx(cell, seed, seconds, False,
                      jax.devices()[:cell.chips], time.perf_counter())
    ctx.peaks = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11,
                 "hbm_bytes": 16e9}
    return ctx
