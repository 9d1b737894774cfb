"""A toy preset of `openpangu_ultra_moe_718b_serve` for the rehearsal on
the CPU: the cell's keys at widths a CPU serves in seconds, float32
throughout, so that the comparison's noise is round-off and each planted
fault reads far over the limit. Numbers from it mean nothing."""

CONFIG = {
    "name": "tiny_openpangu", "model": "openpangu",
    "hidden_size": 48, "num_attention_heads": 4, "q_lora_rank": 24,
    "kv_lora_rank": 16, "qk_nope_head_dim": 12, "qk_rope_head_dim": 8,
    "v_head_dim": 10, "rope_theta": 25600000, "rms_norm_eps": 1e-5,
    "sandwich_norm": True, "first_k_dense_replace": 1,
    "num_hidden_layers": 3, "intermediate_size": 64,
    "moe_intermediate_size": 24, "n_shared_experts": 1,
    "n_routed_experts": 4, "num_experts_per_tok": 3,
    "routed_scaling_factor": 2.5, "vocab_size": 89,
    "deployment": {"router_experts": 8, "first_expert": 2},
    "initializer_range": 0.2, "assumed_init": {"bias_std": 0.3},
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


def tiny_ctx(workload="openpangu_ultra_moe_long_doc_decode",
             seed=2 ** 31 + 77, seconds=1.0):
    # imported here: tests/test_openpangu.py loads this file for CONFIG
    # alone, from where the harness is not importable
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
