"""Operations and bytes a MiMo-V2 decoder's serving step is due, from
the configuration and the window's counts. Matrix products only (2 FLOPs
a multiply-add); norms, softmax, SiLU, the rotary embedding and the
embedding's gather are left out, as is anything computed on padding.

Names: d hidden_size; nh query heads of hd (keys) and vd (values) on kvh
KV heads (full layers) or wkvh (window layers, window w); fd the dense
feed-forward's width; E the router's width, f an expert's width."""

import math

import numpy as np


def _dims(cfg):
    n = cfg["num_hidden_layers"]
    window = cfg["hybrid_layer_pattern"][:n]
    experts = cfg["moe_layer_freq"][:n]
    return dict(d=cfg["hidden_size"], nh=cfg["num_attention_heads"],
                hd=cfg["head_dim"], vd=cfg["v_head_dim"],
                kvh=cfg["num_key_value_heads"],
                wkvh=cfg["swa_num_key_value_heads"],
                w=cfg["sliding_window"], fd=cfg["intermediate_size"],
                f=cfg["moe_intermediate_size"],
                E=cfg["deployment"]["router_experts"], v=cfg["vocab_size"],
                n_window=sum(1 for x in window if x),
                n_full=sum(1 for x in window if not x),
                n_experts=sum(1 for x in experts if x),
                n_dense=sum(1 for x in experts if not x))


def _itemsize(dtype):
    return 2 if dtype == "bfloat16" else np.dtype(dtype).itemsize


def token_flops(cfg):
    """FLOPs of one token through every layer but the routed experts,
    the head and attention over the cache: the q, k, v and output
    projections of both kinds of layer, the dense feed-forward, the
    routers."""
    m = _dims(cfg)

    def attn(kvh):
        return (2 * m["d"] * (m["nh"] * m["hd"] + kvh * (m["hd"] + m["vd"]))
                + 2 * m["nh"] * m["vd"] * m["d"])
    return (m["n_full"] * attn(m["kvh"]) + m["n_window"] * attn(m["wkvh"])
            + m["n_dense"] * 6 * m["d"] * m["fd"]
            + m["n_experts"] * 2 * m["d"] * m["E"])


def assignment_flops(cfg):
    """One (token, held expert) pair: d x 2f in, f x d out."""
    return 6 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def forward_flops(cfg, tokens_processed, tokens_emitted, context_sum,
                  held_assignments):
    """What the window's work is due: every token processed through the
    layers, every pair that landed on a held expert through that expert,
    every token emitted through the head over the vocabulary held; a
    token at cached length c pays 2 * nh * (hd + vd) * c in each full
    layer (`context_sum` is the sum of c over the tokens processed) and
    2 * nh * (hd + vd) * min(c, w) in each window layer, the mean
    context standing for c there."""
    m = _dims(cfg)
    pair = 2 * m["nh"] * (m["hd"] + m["vd"])
    reach = min(context_sum / tokens_processed, m["w"]) if tokens_processed \
        else 0
    return (token_flops(cfg) * tokens_processed
            + assignment_flops(cfg) * held_assignments
            + 2 * m["d"] * m["v"] * tokens_emitted
            + m["n_full"] * pair * context_sum
            + m["n_window"] * pair * reach * tokens_processed)


def page_bytes(cfg, kind):
    """Bytes of one page of ONE layer of `kind` ("full" | "window"), K
    and V: what the attention kernel copies when it walks the page."""
    m = _dims(cfg)
    kvh = m["wkvh"] if kind == "window" else m["kvh"]
    return (kvh * cfg["engine"]["page_size"] * (m["hd"] + m["vd"])
            * _itemsize(cfg["storage_dtype"]))


def walked_bytes(cfg, counters, kinds=("full", "window")):
    """Bytes of K and V the attention kernels of `kinds` had to copy
    over the window: the pages they walked (the engine's
    `attn_live_pages_<kind>_total`, a count a layer) in every layer of
    the kind."""
    m = _dims(cfg)
    return sum(counters[f"attn_live_pages_{k}_total"] * page_bytes(cfg, k)
               * m["n_" + k] for k in kinds)


def weight_bytes(cfg, spec, tokens_per_step):
    """Stated bytes of the weights a step touches: every matrix once (a
    step of 32 lanes with prefill chunks routes some token to every held
    expert), of the embedding the rows of the step's tokens only."""
    item = _itemsize(cfg["storage_dtype"])
    whole = sum(math.prod(shape) for name, shape, _i in spec
                if name != "mimo_tok_emb")
    return item * (whole + tokens_per_step * cfg["hidden_size"])


def step_bytes(cfg, spec, counters, steps, tokens_processed):
    """Bytes one step must move: the weights it touches and the pages of
    K and V its attention kernels walk, in both kinds of layer."""
    return (weight_bytes(cfg, spec, tokens_processed / steps)
            + walked_bytes(cfg, counters) / steps)
