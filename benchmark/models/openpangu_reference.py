"""Plain reference for the served openPangu-Ultra-MoE decoder
(`pangu_ultra_moe`): one full causal forward pass a request in
straightforward jax.numpy, float32 at Precision.HIGHEST, no cache, no
pages, no kernel, no batching, and latent attention in its published,
EXPANDED form (keys and values a head rebuilt from the latent). The
sequence goes through attention a group of heads at a time and, within
it, in blocks of query rows; the feed-forwards a part of their width at
a time (18,432 tokens at once would not fit). Imports nothing of the
program; weights come from models/weights.py.

openPangu-Ultra-MoE-718B's language model as its config.json states it
(the `config` is trusted over any description): d = hidden_size, H =
num_attention_heads, N = qk_nope_head_dim, R = qk_rope_head_dim, V =
v_head_dim, L = kv_lora_rank, eps = rms_norm_eps.

    h = E[token]                                  (untied head)
    u = rms_norm(h)
    c_q = rms_norm(u W_q_a) (q_lora_rank);  [q_nope | q_pe]_h = c_q W_q_b
    [c_kv | k_pe] = u W_kv_a;  c_kv = rms_norm(c_kv)
    q_pe, k_pe rotated at the token's position (rope_theta, pairs (i,
        i + R / 2)); k_pe one for all heads
    [k_nope | v]_h = c_kv W_kv_b,h
    s_ij = (q_nope,i . k_nope,j + q_pe,i . k_pe,j) / sqrt(N + R), j <= i
    h = h + rms_norm_post((sum_j softmax(s)_ij v_j) W_o)     (sandwich_norm)
    u = rms_norm(h)
    FFN: layer < first_k_dense_replace: (silu(a1) * a2) W_out, (a1, a2) =
        split(u W_in), width intermediate_size;
        else: Shared(u) + routed_scaling_factor * sum over chosen and
        held e of w_e * Expert_e(u), the same gated form at
        moe_intermediate_size (the shared one n_shared_experts times as
        wide); s = sigmoid(u W_r) over all `deployment.router_experts`;
        chosen = top num_experts_per_tok of (s + b); w_e = s_e / sum over
        the chosen of s (the bias b ranks and does not weigh)
    h = h + rms_norm_post(FFN)
    logits = rms_norm(h) W_head

The configuration holds one chip's share: experts `deployment.
first_expert` .. + `n_routed_experts` of the router's, and the first
`vocab_size` rows of the embedding and columns of the head. The
reference computes the same share.

`init`: the selection bias is drawn with the weights (normal at
initializer_range) and rescaled here as the configuration's `assumed`
says: bias = bias_std * z, z the drawn value over initializer_range. The
program does the same (models/openpangu_program.py `placed`).

`precision`: "highest" (the reference), "bf16" or "fp8" (operands of
every product rounded): the controls of `served_gaps`. `fault`: one of
FAULTS planted in the mathematics: what a program that got that part
wrong would compute.
"""

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

FAULTS = ("no_k_pe", "no_kv_norm", "no_post_norm", "routed_scale_1",
          "no_shared")
HEAD_GROUP = 16          # heads of one pass of attention
FFN_PARTS = 8            # parts of the dense feed-forward's width


def dims(cfg):
    return dict(d=cfg["hidden_size"], nh=cfg["num_attention_heads"],
                q_rank=cfg["q_lora_rank"], L=cfg["kv_lora_rank"],
                N=cfg["qk_nope_head_dim"], R=cfg["qk_rope_head_dim"],
                V=cfg["v_head_dim"], fd=cfg["intermediate_size"],
                f=cfg["moe_intermediate_size"],
                fs=(cfg["n_shared_experts"] or 0) * cfg["moe_intermediate_size"],
                held=cfg["n_routed_experts"],
                experts=cfg["deployment"]["router_experts"],
                first=cfg["deployment"]["first_expert"],
                top_k=cfg["num_experts_per_tok"])


def layer_kinds(cfg):
    """The feed-forward kind of each layer held: "dense" before
    first_k_dense_replace, "experts" after."""
    return ["dense" if i < cfg["first_k_dense_replace"] else "experts"
            for i in range(cfg["num_hidden_layers"])]


def layer_spec(cfg, i):
    m, p = dims(cfg), f"mimo{i}_"
    d, nh = m["d"], m["nh"]
    rows = [(p + "ln1.scale", (d,), "ones"),
            (p + "attn_q_a.w", (d, m["q_rank"]), "normal"),
            (p + "attn_q_a_norm.scale", (m["q_rank"],), "ones"),
            (p + "attn_q_b.w", (m["q_rank"], nh * (m["N"] + m["R"])),
             "normal"),
            (p + "attn_kv_a.w", (d, m["L"] + m["R"]), "normal"),
            (p + "attn_kv_a_norm.scale", (m["L"],), "ones"),
            (p + "attn_kv_b.w", (m["L"], nh * (m["N"] + m["V"])), "normal"),
            (p + "attn_o.w", (nh * m["V"], d), "normal"),
            (p + "ln1_post.scale", (d,), "ones"),
            (p + "ln2.scale", (d,), "ones")]
    if layer_kinds(cfg)[i] == "dense":
        rows += [(p + "ffn_in.w", (d, 2 * m["fd"]), "normal"),
                 (p + "ffn_out.w", (m["fd"], d), "normal")]
    else:
        rows += [(p + "router.w", (d, m["experts"]), "normal"),
                 (p + "router.bias", (m["experts"],), "normal"),
                 (p + "experts_in.w", (m["held"], d, 2 * m["f"]), "normal"),
                 (p + "experts_out.w", (m["held"], m["f"], d), "normal")]
        if m["fs"]:
            rows += [(p + "shared_in.w", (d, 2 * m["fs"]), "normal"),
                     (p + "shared_out.w", (m["fs"], d), "normal")]
    return rows + [(p + "ln2_post.scale", (d,), "ones")]


def spec(cfg):
    rows = [("mimo_tok_emb", (cfg["vocab_size"], cfg["hidden_size"]),
             "normal")]
    for i in range(cfg["num_hidden_layers"]):
        rows += layer_spec(cfg, i)
    return rows + [("mimo_lnf.scale", (cfg["hidden_size"],), "ones"),
                   ("mimo_head.w", (cfg["hidden_size"], cfg["vocab_size"]),
                    "normal")]


def placed(cfg, name, value):
    """A drawn leaf as the model holds it: the selection bias rescaled
    from the draw (module docstring, `init`), every other leaf as
    drawn."""
    if name.endswith("router.bias"):
        z = value.astype(jnp.float32) / cfg["initializer_range"]
        return (cfg["assumed_init"]["bias_std"] * z).astype(value.dtype)
    return value


def pool_bytes(cfg):
    """Bytes of the latent page pools in `storage_dtype`: a token of a
    layer is kv_lora_rank + qk_rope_head_dim values, and there is no V
    pool."""
    m, eng = dims(cfg), cfg["engine"]
    item = jnp.dtype(cfg["storage_dtype"]).itemsize
    return (cfg["num_hidden_layers"] * eng["num_pages"] * eng["page_size"]
            * (m["L"] + m["R"]) * item)


def stated_storage_bytes(cfg):
    """Bytes of the weights and of the latent pages in the type the
    configuration states they are kept in, and the experts' load counts
    (int32): what the step program has to take as its arguments, to a
    few KB of tokens and page tables. A pool that is padded or copied,
    or a V pool beside the latents, takes more."""
    weights = sum(math.prod(shape) for _n, shape, _i in spec(cfg))
    n_experts = layer_kinds(cfg).count("experts")
    return (jnp.dtype(cfg["storage_dtype"]).itemsize * weights
            + pool_bytes(cfg) + 4 * n_experts * cfg["n_routed_experts"])


def products(precision):
    """mm(a, b) -> float32 in the given precision."""
    if precision == "highest":
        return lambda a, b: jnp.matmul(a.astype(jnp.float32),
                                       b.astype(jnp.float32),
                                       precision=jax.lax.Precision.HIGHEST)
    low = jnp.bfloat16 if precision == "bf16" else jnp.float8_e4m3fn

    def mm(a, b):
        return jnp.matmul(a.astype(low).astype(jnp.bfloat16),
                          b.astype(low).astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)
    return mm


def rms_norm(x, scale, eps):
    x = x.astype(jnp.float32)
    return (x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
            * scale.astype(jnp.float32))


def rotate(x, pos, theta):
    """x [t, heads, R], pos [t]: every value rotated at the token's
    position, pairs (i, i + R / 2)."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(cfg, mm, p, u, fault, block):
    """The attention sublayer on a whole sequence u [t, d] (normed),
    before its post norm: HEAD_GROUP heads at a time, their keys and
    values expanded from the latent for every token, their queries in
    blocks of `block` rows."""
    m, t = dims(cfg), u.shape[0]
    N, R, V, L = m["N"], m["R"], m["V"], m["L"]
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    g = min(HEAD_GROUP, m["nh"])
    pos = jnp.arange(t, dtype=jnp.int32)
    c_q = rms_norm(mm(u, p["attn_q_a.w"]), p["attn_q_a_norm.scale"], eps)
    kv = mm(u, p["attn_kv_a.w"])
    c_kv = (kv[:, :L] if fault == "no_kv_norm"
            else rms_norm(kv[:, :L], p["attn_kv_a_norm.scale"], eps))
    k_pe = rotate(kv[:, None, L:], pos, theta)                 # [t, 1, R]
    scale = 1.0 / math.sqrt(N + R)

    def heads(j, o):
        wq = jax.lax.dynamic_slice_in_dim(p["attn_q_b.w"], j * g * (N + R),
                                          g * (N + R), axis=1)
        wkv = jax.lax.dynamic_slice_in_dim(p["attn_kv_b.w"], j * g * (N + V),
                                           g * (N + V), axis=1)
        wo = jax.lax.dynamic_slice_in_dim(p["attn_o.w"], j * g * V, g * V)
        q = mm(c_q, wq).reshape(t, g, N + R)
        q = jnp.concatenate([q[..., :N], rotate(q[..., N:], pos, theta)], -1)
        kvh = mm(c_kv, wkv).reshape(t, g, N + V)
        k = jnp.concatenate(
            [kvh[..., :N], jnp.broadcast_to(k_pe, (t, g, R))], -1)
        if fault == "no_k_pe":
            q, k = q[..., :N], k[..., :N]
        k = k.transpose(1, 2, 0)                                # [g, _, t]
        v = kvh[..., N:].transpose(1, 0, 2)                     # [g, t, V]

        def rows(x):
            qb, pb = x                                          # [b, g, _]
            s = mm(qb.transpose(1, 0, 2), k) * scale            # [g, b, t]
            live = pos[None, :] <= pb[:, None]
            w = jax.nn.softmax(jnp.where(live[None], s, -jnp.inf), axis=-1)
            return mm(w, v).transpose(1, 0, 2).reshape(block, g * V)

        ctx = jax.lax.map(rows, (q.reshape(-1, block, g, q.shape[-1]),
                                 pos.reshape(-1, block)))
        return o + mm(ctx.reshape(t, g * V), wo)

    return jax.lax.fori_loop(0, m["nh"] // g, heads,
                             jnp.zeros((t, m["d"]), jnp.float32))


def gated_ffn(mm, u, w_in, w_out, parts=1):
    """(silu(a1) * a2) W_out, (a1, a2) = split(u W_in): the width in
    `parts` parts, summed."""
    f = w_out.shape[0]
    w = f // parts

    def part(j, out):
        a1 = mm(u, jax.lax.dynamic_slice_in_dim(w_in, j * w, w, axis=1))
        a2 = mm(u, jax.lax.dynamic_slice_in_dim(w_in, f + j * w, w, axis=1))
        return out + mm(jax.nn.silu(a1) * a2,
                        jax.lax.dynamic_slice_in_dim(w_out, j * w, w))

    return jax.lax.fori_loop(0, parts, part,
                             jnp.zeros((u.shape[0], w_out.shape[1]),
                                       jnp.float32))


def moe(cfg, mm, p, u, fault=None):
    """The shared expert and the held experts' part of the routed sum:
    every held expert on every token, weighted by a gate that is zero
    where the token did not choose it, times routed_scaling_factor."""
    m = dims(cfg)
    scores = jax.nn.sigmoid(mm(u, p["router.w"]))          # [t, experts]
    _, idx = jax.lax.top_k(scores + p["router.bias"].astype(jnp.float32),
                           m["top_k"])
    vals = jnp.take_along_axis(scores, idx, axis=-1)
    gates = vals / jnp.sum(vals, axis=-1, keepdims=True)
    if fault != "routed_scale_1":
        gates = gates * cfg["routed_scaling_factor"]
    dense = jnp.zeros_like(scores).at[
        jnp.arange(u.shape[0])[:, None], idx].set(gates)
    dense = dense[:, m["first"]:m["first"] + m["held"]]          # [t, held]

    def expert(e, out):
        return out + dense[:, e][:, None] * gated_ffn(
            mm, u, p["experts_in.w"][e], p["experts_out.w"][e])

    out = jax.lax.fori_loop(0, m["held"], expert, jnp.zeros_like(u))
    if m["fs"] and fault != "no_shared":
        out = out + gated_ffn(mm, u, p["shared_in.w"], p["shared_out.w"])
    return out


def layer(cfg, precision, ffn, fault, block, p, h):
    """One layer on a whole sequence h [t, d], t a multiple of
    `block`."""
    mm = products(precision)
    eps = cfg["rms_norm_eps"]

    def post(y, name):
        return y if fault == "no_post_norm" else rms_norm(y, p[name], eps)

    u = rms_norm(h, p["ln1.scale"], eps)
    h = h + post(attention(cfg, mm, p, u, fault, block), "ln1_post.scale")
    u = rms_norm(h, p["ln2.scale"], eps)
    y = (gated_ffn(mm, u, p["ffn_in.w"], p["ffn_out.w"], FFN_PARTS)
         if ffn == "dense" else moe(cfg, mm, p, u, fault))
    return h + post(y, "ln2_post.scale")


_LAYER_FNS = {}


def layer_fn(cfg, precision, ffn, fault, block):
    """One compiled function a kind of layer, a precision, a fault and a
    configuration."""
    key = (json.dumps(cfg, sort_keys=True), precision, ffn, fault, block)
    if key not in _LAYER_FNS:
        _LAYER_FNS[key] = jax.jit(functools.partial(
            layer, cfg, precision, ffn, fault, block))
    return _LAYER_FNS[key]


def logits_at(cfg, precision, params, tokens, where, fault=None):
    """tokens [T] (padded; causal, so padding after a position cannot
    reach it), where [K] positions -> float32 logits [K, V held]. Layer
    by layer, each layer's weights as stored (upcast where they are
    multiplied)."""
    mm = products(precision)
    t = len(tokens)
    block = cfg.get("reference_block", 128)
    block = block if t % block == 0 else t
    h = params["mimo_tok_emb"][tokens].astype(jnp.float32)
    for i, ffn in enumerate(layer_kinds(cfg)):
        pre = f"mimo{i}_"
        h = layer_fn(cfg, precision, ffn, fault, block)(
            {n[len(pre):]: placed(cfg, n, params[n])
             for n, _s, _i in layer_spec(cfg, i)}, h)
    x = rms_norm(h[where], params["mimo_lnf.scale"], cfg["rms_norm_eps"])
    return mm(x, params["mimo_head.w"])


def served_gaps(cfg, params, requests, pad_to, max_new, control=False):
    """requests: [(prompt, served tokens)]. For each served token, how
    far its logit lies below the reference's best at that position.
    With `control` (a precision of `products`, or one of FAULTS), the
    tokens judged are not the served ones but those a pass of the
    reference in that precision, or with that fault planted, puts first
    at the same positions."""
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
            fault = control if control in FAULTS else None
            judged = jnp.argmax(logits_at(
                cfg, "highest" if fault else control, params, seq, where,
                fault=fault), axis=-1)
        picked = jnp.take_along_axis(logits, jnp.asarray(judged)[:, None], -1)
        gaps += np.asarray(jnp.max(logits, axis=-1) - picked[:, 0])[:n].tolist()
    return gaps
