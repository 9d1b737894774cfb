"""Plain reference for the served decoder: one full causal forward
pass in straightforward jax.numpy, no cache, no pages, no batching.
Imports nothing of the program; weights come from models/weights.py.

GPT-3 (arXiv:2005.14165) as the program's `build_lm_program` states
it: learned token and position embeddings, pre-LN decoder layers with
fused QKV, exact (erf) GELU, a final layer norm and an untied head with
a bias.

`precision`: "highest" (float32 operands, Precision.HIGHEST: the
reference), "bf16" (operands and the residual stream rounded to
bfloat16, float32 accumulation: the precision below the float32 the
configuration states) or "fp8" (fp8 operands of every product).
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

LN_EPS = 1e-5


def spec(cfg):
    h, f, v = cfg["hidden_size"], cfg["ffn_size"], cfg["vocab_size"]
    rows = [("gpt_tok_emb", (v, h), "normal"),
            ("gpt_pos_emb", (cfg["max_position"], h), "normal")]
    for i in range(cfg["num_layers"]):
        p = f"dec{i}_"
        rows += [(p + "ln1.scale", (h,), "ones"), (p + "ln1.bias", (h,), "zeros"),
                 (p + "qkv.w", (h, 3 * h), "normal"), (p + "qkv.b", (3 * h,), "zeros"),
                 (p + "proj.w", (h, h), "normal"), (p + "proj.b", (h,), "zeros"),
                 (p + "ln2.scale", (h,), "ones"), (p + "ln2.bias", (h,), "zeros"),
                 (p + "ffn1.w", (h, f), "normal"), (p + "ffn1.b", (f,), "zeros"),
                 (p + "ffn2.w", (f, h), "normal"), (p + "ffn2.b", (h,), "zeros")]
    rows += [("gpt_lnf.scale", (h,), "ones"), ("gpt_lnf.bias", (h,), "zeros"),
             ("gpt_head.w", (h, v), "normal"), ("gpt_head.b", (v,), "zeros")]
    return rows


def stated_storage_bytes(cfg):
    """Bytes of the weights and of the K and V page pools in the type
    the configuration states they are kept in (`storage_dtype`): what
    the step program has to take as its arguments, to a few KB of
    tokens and page tables."""
    item = np.dtype(cfg["storage_dtype"]).itemsize
    eng = cfg["engine"]
    weights = sum(math.prod(shape) for _name, shape, _init in spec(cfg))
    pools = (cfg["num_layers"] * 2 * cfg["num_heads"] * eng["num_pages"]
             * eng["page_size"] * cfg["head_dim"])
    return item * (weights + pools)


def layer_norm(x, scale, bias):
    x = x.astype(jnp.float32)
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, -1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + LN_EPS) * scale + bias


def logits_at(cfg, precision, p, tokens, where):
    """tokens [T] (padded; causal, so padding after a position cannot
    reach it), where [K] positions -> float32 logits [K, V]."""
    if precision == "highest":
        act = jnp.float32

        def mm(a, b):
            return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)
    elif precision == "bf16":
        act = jnp.bfloat16

        def mm(a, b):
            return jnp.matmul(a.astype(act), b.astype(act),
                              preferred_element_type=jnp.float32)
    else:       # "fp8": float32 activations, fp8 operands of every product
        act = jnp.float32

        def mm(a, b):
            low = jnp.float8_e4m3fn
            return jnp.matmul(a.astype(low).astype(jnp.bfloat16),
                              b.astype(low).astype(jnp.bfloat16),
                              preferred_element_type=jnp.float32)
    t = tokens.shape[0]
    h, nh = cfg["hidden_size"], cfg["num_heads"]
    x = (p["gpt_tok_emb"][tokens] + p["gpt_pos_emb"][jnp.arange(t)]).astype(act)
    causal = jnp.tril(jnp.ones((t, t), bool))
    for i in range(cfg["num_layers"]):
        pre = f"dec{i}_"
        y = layer_norm(x, p[pre + "ln1.scale"], p[pre + "ln1.bias"])
        qkv = mm(y, p[pre + "qkv.w"]) + p[pre + "qkv.b"]
        q, k, v = (a.reshape(t, nh, h // nh).transpose(1, 0, 2)
                   for a in jnp.split(qkv, 3, axis=-1))
        s = mm(q, k.transpose(0, 2, 1)) / math.sqrt(h // nh)
        s = jnp.where(causal, s, -jnp.inf)
        ctx = mm(jax.nn.softmax(s, axis=-1), v).transpose(1, 0, 2).reshape(t, h)
        x = (x + mm(ctx, p[pre + "proj.w"]) + p[pre + "proj.b"]).astype(act)
        y = layer_norm(x, p[pre + "ln2.scale"], p[pre + "ln2.bias"])
        y = jax.nn.gelu(mm(y, p[pre + "ffn1.w"]) + p[pre + "ffn1.b"],
                        approximate=False)
        x = (x + mm(y, p[pre + "ffn2.w"]) + p[pre + "ffn2.b"]).astype(act)
    x = layer_norm(x[where], p["gpt_lnf.scale"], p["gpt_lnf.bias"])
    return (mm(x, p["gpt_head.w"]) + p["gpt_head.b"]).astype(jnp.float32)


def gaps_at(cfg, control, p, tokens, where, judged):
    """[K] gaps: how far the judged token's logit lies below the
    reference's best at each position of `where`. With `control` the
    tokens judged are those a bfloat16 pass puts first there."""
    logits = logits_at(cfg, "highest", p, tokens, where)
    if control:
        judged = jnp.argmax(logits_at(cfg, control, p, tokens, where), axis=-1)
    picked = jnp.take_along_axis(logits, judged[:, None], -1)[:, 0]
    return jnp.max(logits, axis=-1) - picked


def served_gaps(cfg, params, requests, pad_to, max_new, control=False):
    """requests: [(prompt, served tokens)]. For each served token, how
    far its logit lies below the reference's best at that position.
    Returns every token's gap, in order. With `control` (a precision
    of logits_at), the tokens judged are not the served ones but
    those a pass of the reference in that precision puts first at the
    same positions. One program of fixed shapes serves every request."""
    fn = jax.jit(functools.partial(gaps_at, cfg, control))
    gaps = []
    for prompt, served in requests:
        n = len(served)
        seq = np.zeros(pad_to, np.int32)
        full = np.concatenate([prompt, served[:-1]]).astype(np.int32)
        seq[:full.size] = full
        where = np.full(max_new, len(prompt) - 1, np.int32)
        where[:n] = len(prompt) - 1 + np.arange(n)
        judged = np.zeros(max_new, np.int32)
        judged[:n] = served
        gaps += np.asarray(fn(params, seq, where, judged))[:n].tolist()
    return gaps
