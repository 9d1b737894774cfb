"""Tiny presets for the rehearsal: the cells of BENCHMARK.json with
their configurations and traffic cut to what a CPU test can hold."""

import time

import harness

TINY = {
    "bert": {
        "config": {"vocab_size": 1024, "hidden_size": 64, "num_layers": 2,
                   "num_heads": 4, "ffn_size": 128, "max_position": 128},
        "traffic": {"seq_len": 128, "batch_per_chip": 4, "block_rows": 2,
                    "limits": {"loss_gap": 1e-4, "grad_norm_gap": 0.02,
                               "delta_norm_gap": 0.05}},
    },
    "gpt": {
        "config": {"vocab_size": 1000, "hidden_size": 64, "num_layers": 2,
                   "num_heads": 4, "head_dim": 16, "ffn_size": 256,
                   "max_position": 256, "storage_dtype": "float32",
                   "engine": {"mode": "ragged", "lanes": 4, "chunk_tokens": 8,
                              "page_size": 8, "num_pages": 160,
                              "kv_dtype": "float32", "queue_capacity": 64,
                              "export_seq_len": 32}},
        "traffic": {"prompt_lens": [20, 33, 41, 50, 27, 38],
                    "answer_lens": [12, 16, 20, 24, 14, 18],
                    "check_pad_to": 128, "check_requests": 3,
                    "limits": {"served_logit_gap": 1e-3,
                               "step_argument_bytes_gap": 0.005}},
    },
}


def tiny_ctx(workload, seed=2 ** 31 + 77, seconds=1.0):
    import jax

    cell = harness.Cell(harness.load_json(harness.os.path.join(
        harness.ROOT, "BENCHMARK.json")), workload)
    preset = TINY[cell.config["model"]]
    cell.config.update(preset["config"])
    cell.traffic.update(preset["traffic"])
    ctx = harness.Ctx(cell, seed, seconds, False,
                      jax.devices()[:cell.chips], time.perf_counter())
    ctx.peaks = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11,
                 "hbm_bytes": 16e9}
    return ctx
