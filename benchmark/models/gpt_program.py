"""The system under test for the `gpt` model: what the benchmark takes
from the program to serve it: build_lm_program -> save_inference_model
-> create_predictor -> GenerationEngine in ragged mode. The one file of
the pair that imports paddle_tpu."""

import os
import shutil
import tempfile

import numpy as np


def gpt_config(cfg):
    from paddle_tpu.models.gpt import GPTConfig

    return GPTConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_layers=cfg["num_layers"], num_heads=cfg["num_heads"],
        ffn_size=cfg["ffn_size"], max_position=cfg["max_position"],
        hidden_dropout=0.0, attention_dropout=0.0,
        initializer_range=cfg["initializer_range"])


def predictor_scope(pred):
    """The scope a predictor loaded its parameters into. The program
    has no public accessor for it; `GenerationEngine` itself takes it
    as `predictor._scope` (generation/engine.py), so this is the seam
    between the two that the program uses (PERF.md, Open questions)."""
    return pred._scope


def build_engine(cfg, weights):
    """Export the loss-free LM's program (no parameters: the weights
    are made on the device from the seed and put into the predictor's
    scope, so nothing of their 5.4 GB touches the disk: every run
    would write them anew, and a check of 14 runs a cell would pass
    the host's write limit), load it, and start the engine with the
    configuration's geometry."""
    import paddle_tpu as fluid
    from paddle_tpu import generation
    from paddle_tpu.generation.model import build_lm_program
    from paddle_tpu.inference import Config, create_predictor

    gcfg = gpt_config(cfg)
    eng_cfg = cfg["engine"]
    model_dir = tempfile.mkdtemp(prefix="bench_lm_")
    main, _startup, _feeds, fetches = build_lm_program(
        gcfg, int(eng_cfg["export_seq_len"]))
    exe = fluid.Executor(fluid.TPUPlace())
    fluid.io.save_inference_model(model_dir, ["tokens"], [fetches["logits"]],
                                  exe, main, program_only=True)
    np.savez(os.path.join(model_dir, "__params__.npz"))    # no arrays
    pred = create_predictor(Config(model_dir))
    shutil.rmtree(model_dir, ignore_errors=True)
    scope = predictor_scope(pred)
    for name, value in weights.items():
        scope.set_var(name, value)
    eng = generation.GenerationEngine(
        pred, gcfg, warmup=True, mode="ragged",
        page_size=int(eng_cfg["page_size"]),
        num_pages=int(eng_cfg["num_pages"]),
        max_decode_batch=int(eng_cfg["lanes"]),
        chunk_tokens=int(eng_cfg["chunk_tokens"]),
        queue_capacity=int(eng_cfg["queue_capacity"]),
        kv_dtype=eng_cfg["kv_dtype"], prefix_cache=False)
    return eng, pred


def ragged_step():
    """The engine's bound ragged step (one executable for every step),
    found among the live bound steps by its tag."""
    from paddle_tpu.runtime import dispatch

    return next(b for b in dispatch.live_bound_steps()
                if b.compiled.tag == "generation/ragged_step")


def pool_fill(eng):
    """Share of the page pool's pages that hold a sequence now."""
    return float(eng.cache.stats()["page_utilization"])


COUNTERS = ("decode_active_lane_steps_total",
            "decode_capacity_lane_steps_total", "prefill_tokens_total",
            "ragged_steps_total", "prefill_chunks_total")


def counters(eng):
    snap = eng.metrics.snapshot()
    return {k: int(snap[k]) for k in COUNTERS}
