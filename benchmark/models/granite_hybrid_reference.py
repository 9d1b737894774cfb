"""Plain reference for the served hybrid decoder (`granitemoehybrid`):
one full causal forward pass a request in straightforward jax.numpy,
float32 at Precision.HIGHEST, the Mamba-2 recurrence one token at a
time, no chunked scan, no cache, no pages, no batching. Imports nothing
of the program; weights come from models/weights.py.

granite-4.0-h-small as its config.json states it (the `config` is
trusted over any description): d = hidden_size; `layer_types` says
which layers are attention, the others are Mamba-2; tied embedding;
`rms_norm_eps`; no bias but the conv's; no position embedding ("nope").

    h = E[token] * embedding_multiplier
    every layer:  h = h + residual_multiplier * Mixer(RMSNorm(h))
                  u = RMSNorm(h)
                  h = h + residual_multiplier * (MoE(u) + Shared(u))
    logits = RMSNorm(h) E^T / logits_scaling

Attention mixer (grouped queries): q, k, v = u W_q, u W_k, u W_v with
`num_attention_heads` query heads on `num_key_value_heads` KV heads of
hidden_size / num_attention_heads; scores q k^T * attention_multiplier
(1/128 here, not 1/sqrt(128)), causal softmax, ctx W_o.

Mamba-2 mixer: d_in = mamba_expand * d = H * P (H `mamba_n_heads` of P
`mamba_d_head`), G `mamba_n_groups`, N `mamba_d_state`, K `mamba_d_conv`.
[z | xBC | dt] = u W_in, W_in d x (d_in + (d_in + 2 G N) + H).
xBC_t = silu(sum_j w_conv[:, j] xBC_{t-K+1+j} + b_conv), depthwise and
causal. x_t [H, P], B_t [G, N], C_t [G, N] = split(xBC_t); dt_t =
softplus(dt_t + dt_bias); A = -exp(A_log). State S [H, P, N]:
S_t = exp(dt_t A) S_{t-1} + dt_t (x_t outer B_t); y_t = S_t C_t + D x_t.
y = RMSNorm_group(y * silu(z)) * w_norm; out = y W_out. `mamba_chunk_size`
is a block length of an implementation, not part of the mathematics.

MoE: router u W_r over all `deployment.router_experts`; the top
`num_experts_per_tok` logits, gates = softmax over those (float32).
Expert e: (a1, a2) = split(u W_in^e), o_e = (silu(a1) * a2) W_out^e.
MoE(u) = sum gates_e o_e, no token dropped. Shared expert: the same form
at `shared_intermediate_size`.

The configuration holds a chip's share of a two-chip deployment: experts
`deployment.first_expert` .. + `num_local_experts` of the router's, and
the first `vocab_size` rows of the embedding. The reference computes the
same share: routing over all experts, the sum over the held ones; logits
and their best over the rows held.

`precision`: "highest" (the reference), "bf16" (operands of every
product rounded to bfloat16, float32 accumulation) or "fp8" (fp8
operands): the controls of `served_gaps`.
"""

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np


def dims(cfg):
    d = cfg["hidden_size"]
    nh, kvh = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    H, P = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    G, N = cfg["mamba_n_groups"], cfg["mamba_d_state"]
    assert H * P == cfg["mamba_expand"] * d
    return dict(d=d, nh=nh, kvh=kvh, hd=d // nh, H=H, P=P, G=G, N=N,
                K=cfg["mamba_d_conv"], d_in=H * P, ch=H * P + 2 * G * N,
                f=cfg["intermediate_size"],
                fs=cfg["shared_intermediate_size"],
                held=cfg["num_local_experts"],
                experts=cfg["deployment"]["router_experts"],
                first=cfg["deployment"]["first_expert"],
                top_k=cfg["num_experts_per_tok"])


def layer_kinds(cfg):
    return list(cfg["layer_types"][:cfg["num_hidden_layers"]])


def layer_spec(cfg, i):
    m, p = dims(cfg), f"hyb{i}_"
    rows = [(p + "ln1.scale", (m["d"],), "ones")]
    if layer_kinds(cfg)[i] == "mamba":
        rows += [(p + "mamba_in.w", (m["d"], m["d_in"] + m["ch"] + m["H"]), "normal"),
                 (p + "mamba_conv.w", (m["ch"], m["K"]), "normal"),
                 (p + "mamba_conv.b", (m["ch"],), "zeros"),
                 (p + "mamba_dt_bias", (m["H"],), "zeros"),
                 (p + "mamba_a_log", (m["H"],), "zeros"),
                 (p + "mamba_d", (m["H"],), "ones"),
                 (p + "mamba_norm.scale", (m["d_in"],), "ones"),
                 (p + "mamba_out.w", (m["d_in"], m["d"]), "normal")]
    else:
        rows += [(p + "attn_q.w", (m["d"], m["nh"] * m["hd"]), "normal"),
                 (p + "attn_k.w", (m["d"], m["kvh"] * m["hd"]), "normal"),
                 (p + "attn_v.w", (m["d"], m["kvh"] * m["hd"]), "normal"),
                 (p + "attn_o.w", (m["nh"] * m["hd"], m["d"]), "normal")]
    rows += [(p + "ln2.scale", (m["d"],), "ones"),
             (p + "router.w", (m["d"], m["experts"]), "normal"),
             (p + "experts_in.w", (m["held"], m["d"], 2 * m["f"]), "normal"),
             (p + "experts_out.w", (m["held"], m["f"], m["d"]), "normal"),
             (p + "shared_in.w", (m["d"], 2 * m["fs"]), "normal"),
             (p + "shared_out.w", (m["fs"], m["d"]), "normal")]
    return rows


def spec(cfg):
    rows = [("hyb_tok_emb", (cfg["vocab_size"], cfg["hidden_size"]), "normal")]
    for i in range(cfg["num_hidden_layers"]):
        rows += layer_spec(cfg, i)
    return rows + [("hyb_lnf.scale", (cfg["hidden_size"],), "ones")]


def state_bytes(cfg):
    """The recurrent state of every lane: per Mamba layer an SSM state
    [H, P, N] and the conv's last K-1 inputs, in `state_dtype`; and the
    experts' load counts that ride with it (int32)."""
    m, eng = dims(cfg), cfg["engine"]
    n_mamba = sum(k == "mamba" for k in layer_kinds(cfg))
    lane = n_mamba * (m["H"] * m["P"] * m["N"] + (m["K"] - 1) * m["ch"])
    loads = 4 * cfg["num_hidden_layers"] * m["held"]
    return np.dtype(cfg["state_dtype"]).itemsize * eng["lanes"] * lane + loads


def stated_storage_bytes(cfg):
    """Bytes of the weights and of the K and V page pools of the
    attention layers in the type the configuration states they are kept
    in (`storage_dtype`), and of the recurrent state in `state_dtype`:
    what the step program has to take as its arguments, to a few KB of
    tokens and page tables. The engine's own `kv_dtype` and `state_dtype`
    are not read here: an engine run with others takes other bytes."""
    m, eng = dims(cfg), cfg["engine"]
    weights = sum(math.prod(shape) for _n, shape, _i in spec(cfg))
    n_attn = sum(k == "attention" for k in layer_kinds(cfg))
    pools = (n_attn * 2 * m["kvh"] * eng["num_pages"] * eng["page_size"]
             * m["hd"])
    return (jnp.dtype(cfg["storage_dtype"]).itemsize * (weights + pools)
            + state_bytes(cfg))


def products(precision):
    """mm(a, b) -> float32 in the given precision."""
    if precision == "highest":
        return lambda a, b: jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)
    low = jnp.bfloat16 if precision == "bf16" else jnp.float8_e4m3fn

    def mm(a, b):
        return jnp.matmul(a.astype(low).astype(jnp.bfloat16),
                          b.astype(low).astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)
    return mm


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def attention_mixer(cfg, mm, p, u):
    m, t = dims(cfg), u.shape[0]
    q = mm(u, p["attn_q.w"]).reshape(t, m["nh"], m["hd"])
    k = mm(u, p["attn_k.w"]).reshape(t, m["kvh"], m["hd"])
    v = mm(u, p["attn_v.w"]).reshape(t, m["kvh"], m["hd"])
    rep = m["nh"] // m["kvh"]           # query head h reads KV head h // rep
    k = jnp.repeat(k, rep, axis=1).transpose(1, 0, 2)
    v = jnp.repeat(v, rep, axis=1).transpose(1, 0, 2)
    s = mm(q.transpose(1, 0, 2), k.transpose(0, 2, 1)) * cfg["attention_multiplier"]
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
    ctx = mm(jax.nn.softmax(s, axis=-1), v).transpose(1, 0, 2)
    return mm(ctx.reshape(t, m["nh"] * m["hd"]), p["attn_o.w"])


def mamba_mixer(cfg, mm, p, u):
    m, t = dims(cfg), u.shape[0]
    H, P, G, N, K, d_in = m["H"], m["P"], m["G"], m["N"], m["K"], m["d_in"]
    z, xbc, dt = jnp.split(mm(u, p["mamba_in.w"]),
                           [d_in, d_in + m["ch"]], axis=-1)
    padded = jnp.concatenate([jnp.zeros((K - 1, m["ch"])), xbc])
    w = p["mamba_conv.w"]
    xbc = jax.nn.silu(sum(padded[j:j + t] * w[:, j] for j in range(K))
                      + p["mamba_conv.b"])
    x, b, c = jnp.split(xbc, [d_in, d_in + G * N], axis=-1)
    x = x.reshape(t, H, P)
    heads_of = H // G                   # head h reads group h // heads_of
    b = jnp.repeat(b.reshape(t, G, N), heads_of, axis=1)        # [t, H, N]
    c = jnp.repeat(c.reshape(t, G, N), heads_of, axis=1)
    dt = jax.nn.softplus(dt + p["mamba_dt_bias"])         # [t, H]
    a = -jnp.exp(p["mamba_a_log"])

    def step(s, inp):                   # s [H, P, N]
        x_t, b_t, c_t, dt_t = inp
        s = (jnp.exp(dt_t * a)[:, None, None] * s
             + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
        return s, jnp.sum(s * c_t[:, None, :], axis=-1)

    _, y = jax.lax.scan(step, jnp.zeros((H, P, N)), (x, b, c, dt))
    y = y + p["mamba_d"][:, None] * x
    y = (y.reshape(t, d_in) * jax.nn.silu(z)).reshape(t, G, d_in // G)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True)
                          + cfg["rms_norm_eps"])
    y = y.reshape(t, d_in) * p["mamba_norm.scale"]
    return mm(y, p["mamba_out.w"])


def gated_ffn(mm, u, w_in, w_out):
    a1, a2 = jnp.split(mm(u, w_in), 2, axis=-1)
    return mm(jax.nn.silu(a1) * a2, w_out)


def moe(cfg, mm, p, u):
    """The held experts' part of the routed sum: every held expert on
    every token, weighted by a gate that is zero where the token did
    not choose it."""
    m = dims(cfg)
    logits = mm(u, p["router.w"])                          # [t, experts]
    vals, idx = jax.lax.top_k(logits, m["top_k"])
    gates = jax.nn.softmax(vals, axis=-1)
    dense = jnp.zeros_like(logits).at[
        jnp.arange(u.shape[0])[:, None], idx].set(gates)
    dense = dense[:, m["first"]:m["first"] + m["held"]]          # [t, held]
    out = jnp.zeros_like(u)
    for e in range(m["held"]):
        out += dense[:, e:e + 1] * gated_ffn(
            mm, u, p["experts_in.w"][e], p["experts_out.w"][e])
    return out


def layer(cfg, precision, kind, p, h):
    """One layer on h [t, d]; p holds this layer's weights under their
    names without the layer's prefix."""
    mm = products(precision)
    p = {k: v.astype(jnp.float32) for k, v in p.items()}
    eps, res = cfg["rms_norm_eps"], cfg["residual_multiplier"]
    u = rms_norm(h, p["ln1.scale"], eps)
    mixer = mamba_mixer if kind == "mamba" else attention_mixer
    h = h + res * mixer(cfg, mm, p, u)
    u = rms_norm(h, p["ln2.scale"], eps)
    ffn = (moe(cfg, mm, p, u)
           + gated_ffn(mm, u, p["shared_in.w"], p["shared_out.w"]))
    return h + res * ffn


_LAYER_FNS = {}


def layer_fn(cfg, precision, kind):
    """One compiled function a kind of layer, a precision and a
    configuration."""
    key = (json.dumps(cfg, sort_keys=True), precision, kind)
    if key not in _LAYER_FNS:
        _LAYER_FNS[key] = jax.jit(
            functools.partial(layer, cfg, precision, kind))
    return _LAYER_FNS[key]


def logits_at(cfg, precision, params, tokens, where):
    """tokens [T] (padded; causal, so padding after a position cannot
    reach it), where [K] positions -> float32 logits [K, V held]. Layer
    by layer, one layer's weights upcast at a time."""
    mm = products(precision)
    emb = params["hyb_tok_emb"]
    h = emb[tokens].astype(jnp.float32) * cfg["embedding_multiplier"]
    for i, kind in enumerate(layer_kinds(cfg)):
        pre = f"hyb{i}_"
        h = layer_fn(cfg, precision, kind)(
            {n[len(pre):]: params[n] for n, _s, _i in layer_spec(cfg, i)}, h)
    x = rms_norm(h[where], params["hyb_lnf.scale"].astype(jnp.float32),
                 cfg["rms_norm_eps"])
    return mm(x, emb.astype(jnp.float32).T) / cfg["logits_scaling"]


def served_gaps(cfg, params, requests, pad_to, max_new, control=False):
    """requests: [(prompt, served tokens)]. For each served token, how
    far its logit lies below the reference's best at that position.
    With `control` (a precision of `products`), the tokens judged are
    not the served ones but those a pass of the reference in that
    precision puts first at the same positions."""
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
        logits = logits_at(cfg, "highest", params, seq, where)
        if control:
            judged = jnp.argmax(
                logits_at(cfg, control, params, seq, where), axis=-1)
        picked = jnp.take_along_axis(logits, jnp.asarray(judged)[:, None], -1)
        gaps += np.asarray(jnp.max(logits, axis=-1) - picked[:, 0])[:n].tolist()
    return gaps
