"""Tiny preset of the hybrid serving cell for the rehearsal: the
configuration file's own keys cut to what a CPU test can hold (one
period shaped mamba, mamba, attention, mamba; 4 of 8 experts held;
float32 storage, so the tolerances are float32's; weights at a scale of
0.5, because a tied head makes a token's own logit the largest at any
small scale and greedy decoding then repeats one token whatever the
layers do: only where the gated feed-forwards carry the stream does a
planted fault change a served token)."""

import time

import harness

CONFIG = {
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
    "num_hidden_layers": 4,
    "layer_types": ["mamba", "mamba", "attention", "mamba"],
    "mamba_n_heads": 8, "mamba_d_head": 16, "mamba_d_state": 16,
    "mamba_chunk_size": 8, "intermediate_size": 32,
    "shared_intermediate_size": 48, "num_experts_per_tok": 3,
    "num_local_experts": 4, "vocab_size": 500, "storage_dtype": "float32",
    "initializer_range": 0.5,
    "deployment": {"router_experts": 8, "first_expert": 0},
    "engine": {"mode": "ragged", "lanes": 4, "chunk_tokens": 8,
               "page_size": 8, "num_pages": 160, "kv_dtype": "float32",
               "queue_capacity": 64, "export_seq_len": 32,
               "max_position": 256, "state_dtype": "float32"},
}
TRAFFIC = {"prompt_lens": [20, 33, 41, 50, 27, 38],
           "answer_lens": [12, 16, 20, 24, 14, 18],
           "check_pad_to": 128, "check_requests": 3,
           "limits": {"served_logit_gap": 1e-3,
                      "step_argument_bytes_gap": 0.002}}


def tiny_ctx(workload="granite4_h_small_chat_decode", seed=2 ** 31 + 77,
             seconds=1.0):
    import jax

    cell = harness.Cell(harness.load_json(harness.os.path.join(
        harness.ROOT, "BENCHMARK.json")), workload)
    cell.config.update(CONFIG)
    cell.traffic.update(TRAFFIC)
    ctx = harness.Ctx(cell, seed, seconds, False,
                      jax.devices()[:cell.chips], time.perf_counter())
    ctx.peaks = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11,
                 "hbm_bytes": 16e9}
    return ctx
