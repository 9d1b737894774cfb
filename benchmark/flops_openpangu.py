"""Operations and bytes an openPangu-Ultra-MoE decoder's serving step is
due, from the configuration and the window's counts. Matrix products
only (2 FLOPs a multiply-add); norms, softmax, SiLU, the rotary
embedding and the embedding's gather are left out, as is anything
computed on padding. Latent attention is counted in the absorbed form
the program runs (kernels/latent_attention.py).

Names: d hidden_size; H query heads; N qk_nope_head_dim, R
qk_rope_head_dim, V v_head_dim; L kv_lora_rank, Q q_lora_rank; fd the
dense feed-forward's width; E the router's width, f an expert's width,
fs the shared expert's."""

import math

import numpy as np


def _dims(cfg):
    n, dense = cfg["num_hidden_layers"], cfg["first_k_dense_replace"]
    return dict(d=cfg["hidden_size"], H=cfg["num_attention_heads"],
                N=cfg["qk_nope_head_dim"], R=cfg["qk_rope_head_dim"],
                V=cfg["v_head_dim"], L=cfg["kv_lora_rank"],
                Q=cfg["q_lora_rank"], fd=cfg["intermediate_size"],
                f=cfg["moe_intermediate_size"],
                fs=(cfg["n_shared_experts"] or 0)
                * cfg["moe_intermediate_size"],
                E=cfg["deployment"]["router_experts"], v=cfg["vocab_size"],
                n_layers=n, n_dense=min(dense, n), n_experts=n - min(dense, n))


def _itemsize(dtype):
    return 2 if dtype == "bfloat16" else np.dtype(dtype).itemsize


def latent_token_flops(cfg):
    """One token through one latent layer's products, the cache aside:
    q_a, q_b, kv_a, the absorption q_nope W_uk (N -> L a head), the
    context's W_uv (L -> V a head) and W_o."""
    m = _dims(cfg)
    return 2 * (m["d"] * m["Q"] + m["Q"] * m["H"] * (m["N"] + m["R"])
                + m["d"] * (m["L"] + m["R"]) + m["H"] * m["N"] * m["L"]
                + m["H"] * m["L"] * m["V"] + m["H"] * m["V"] * m["d"])


def token_flops(cfg):
    """FLOPs of one token through every layer but the routed experts,
    the head and attention over the cache: latent attention's
    projections in every layer, the dense feed-forward, the routers and
    the shared experts."""
    m = _dims(cfg)
    return (m["n_layers"] * latent_token_flops(cfg)
            + m["n_dense"] * 6 * m["d"] * m["fd"]
            + m["n_experts"] * (2 * m["d"] * m["E"] + 6 * m["d"] * m["fs"]))


def assignment_flops(cfg):
    """One (token, held expert) pair: d x 2f in, f x d out."""
    return 6 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def head_flops(cfg):
    """One token through the head over the vocabulary held."""
    return 2 * cfg["hidden_size"] * cfg["vocab_size"]


def key_flops(cfg):
    """One cached key of one layer for one query token, absorbed: the
    H scores against the latent and the rope key, and the H contexts of
    the latent: 2 H (L + R) + 2 H L."""
    m = _dims(cfg)
    return 2 * m["H"] * (2 * m["L"] + m["R"])


def attention_flops(cfg, counters):
    """What the latent kernel is due over the window: every key every
    valid query token attends, in every layer (the engine's
    `attn_latent_key_rows_total`, summed over the layers)."""
    return key_flops(cfg) * counters["attn_latent_key_rows_total"]


def page_bytes(cfg):
    """Bytes of one latent page of one layer as stated: page_size tokens
    of kv_lora_rank + qk_rope_head_dim values, no V."""
    return (cfg["engine"]["page_size"]
            * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"])
            * _itemsize(cfg["storage_dtype"]))


def walked_bytes(cfg, counters):
    """Bytes of latent pages the kernel had to copy over the window: the
    pages it walked in every layer (`attn_latent_pages_total`)."""
    return counters["attn_latent_pages_total"] * page_bytes(cfg)


def forward_flops(cfg, tokens_processed, tokens_emitted, held_assignments,
                  counters):
    """What the window's work is due: every token processed through the
    layers, every pair that landed on a held expert through that expert,
    every token emitted through the head, every cached key attended."""
    return (token_flops(cfg) * tokens_processed
            + assignment_flops(cfg) * held_assignments
            + head_flops(cfg) * tokens_emitted
            + attention_flops(cfg, counters))


def weight_bytes(cfg, spec, tokens_per_step):
    """Stated bytes of the weights a step touches: every matrix once (a
    step of 32 lanes with prefill chunks routes some token to every held
    expert), of the embedding the rows of the step's tokens only."""
    item = _itemsize(cfg["storage_dtype"])
    whole = sum(math.prod(shape) for name, shape, _i in spec
                if name != "mimo_tok_emb")
    return item * (whole + tokens_per_step * cfg["hidden_size"])


def step_bytes(cfg, spec, counters, steps, tokens_processed):
    """Bytes one step must move: the weights it touches and the latent
    pages its attention kernel walks."""
    return (weight_bytes(cfg, spec, tokens_processed / steps)
            + walked_bytes(cfg, counters) / steps)
