"""paddle_tpu.generation — paged KV-cache + continuous-batching
autoregressive decode (the stateful LLM serving lane).

The serving subsystem (PR 3) coalesces stateless predict calls; this
package serves the workload that made TPU serving hard: autoregressive
decode under heavy concurrent traffic. K/V lives in fixed-size pages
behind per-sequence block tables (Ragged Paged Attention,
arXiv:2604.15464); ONE ragged [lanes, chunk] executable serves mixed
prefill chunks, decode rows and speculative-verify rows side by side
(mode="ragged", the default — "two_lane" retains the PR-6
prefill/decode lane pair as the token-identity oracle); sequences
join/leave the running batch every step; every token streams to its
caller the moment it is sampled. Long prompts prefill in chunks
across steps (decode ITL never stalls on a fat prompt); a draft model
(draft.HostDraft or any DraftModel) + spec_tokens turns on
speculative decoding (greedy-identical by construction); kv_dtype=
"int8" quantizes the page pools for ~2x+ resident sequences per byte
budget; and generation_prefix_cache turns on the radix KV cache —
per-page refcounts + a token-keyed prefix trie, so prompts sharing a
prefix attach its pages copy-on-write and prefill only their suffix
(ragged engine only; see PagedKVCache.acquire/publish/release).

    from paddle_tpu.inference import Config, create_predictor
    from paddle_tpu import generation

    main, startup, feeds, fetches = generation.build_lm_program(cfg, 64)
    ...train / load...; fluid.io.save_inference_model(d, ["tokens"],
                                                      [fetches["logits"]], exe, main)
    pred = create_predictor(Config(d))
    eng = generation.GenerationEngine(pred, cfg)     # cfg: GPTConfig
    for tok in eng.submit([1, 5, 9], max_new_tokens=32, eos_id=2):
        ...                                          # streamed tokens
    eng.close(drain=True)

`serving.ServingServer(serve_engine, generation_engine=eng)` exposes
the streamed `POST /v1/generate` HTTP endpoint. Flags: the
``generation_*`` family (flags.py). The decode attention kernel is
``paddle_tpu.kernels.paged_attention`` (Mosaic on TPU, pure-JAX
reference on CPU CI).
"""

from .draft import DraftModel, HostDraft
from .engine import GenerationEngine, GenerationMetrics, GenerationStream
from .kvcache import PagedKVCache, PagePoolExhausted, WindowKind
from .model import (CacheGeometry, GPTConfig, HybridConfig, MiMoConfig,
                    build_decode_program, build_hybrid_step_program,
                    build_lm_program, build_mimo_step_program,
                    build_prefill_program, build_ragged_step_program)

__all__ = [
    "GenerationEngine",
    "GenerationStream",
    "GenerationMetrics",
    "PagedKVCache",
    "PagePoolExhausted",
    "CacheGeometry",
    "GPTConfig",
    "DraftModel",
    "HostDraft",
    "build_lm_program",
    "build_prefill_program",
    "build_decode_program",
    "build_ragged_step_program",
    "HybridConfig",
    "build_hybrid_step_program",
    "MiMoConfig",
    "build_mimo_step_program",
    "WindowKind",
]
