"""Plain reference for the served MiMo-V2 decoder (`mimo_v2`): one full
causal forward pass a request in straightforward jax.numpy, float32 at
Precision.HIGHEST, no cache, no pages, no kernel, no batching; the
sequence goes through each layer in blocks of query rows (the scores of
26k tokens at once would not fit). Imports nothing of the program;
weights come from models/weights.py.

MiMo-V2.5's language model as its config.json states it (the `config`
is trusted over any description): d = hidden_size, H = num_attention_heads;
`hybrid_layer_pattern[i]` 1 = window layer, 0 = full layer;
`moe_layer_freq[i]` 0 = dense feed-forward, 1 = experts.

    h = E[token]                               (no multiplier; untied head)
    u = rms_norm(h);  q = u Wq [H x head_dim], k = u Wk [KVH x head_dim],
        v = attention_value_scale * (u Wv) [KVH x v_head_dim]
        full layer:   KVH = num_key_value_heads, theta = rope_theta
        window layer: KVH = swa_num_key_value_heads, theta = swa_rope_theta,
                      window = sliding_window, a sink logit a head
    q, k: the first rot = round(partial_rotary_factor * head_dim) values
        of a head are rotated at the token's position (pairs (i, i +
        rot / 2), angle pos * theta^(-2i / rot)), the rest pass
    s_ij = q_i . k_j / sqrt(head_dim) for j <= i, and i - window < j on a
        window layer (the token's own key included)
    p_ij = exp(s_ij) / (sum_j exp(s_ij) + exp(sink_head))  (sink: window
        layers only)
    h = h + (sum_j p_ij v_j) Wo
    u = rms_norm(h);  dense: h = h + (silu(a1) * a2) W_out, (a1, a2) =
        split(u W_in), width intermediate_size
    experts: s = sigmoid(u Wr) over all `deployment.router_experts`;
        chosen = top num_experts_per_tok of (s + b); w_e = s_e / sum over
        the chosen of s (the bias b ranks and does not weigh);
        h = h + sum over chosen and held e of w_e * Expert_e(u), the same
        gated form at moe_intermediate_size
    logits = rms_norm(h) W_head

The configuration holds one chip's share: experts `deployment.
first_expert` .. + `n_routed_experts` of the router's, and the first
`vocab_size` rows of the embedding and columns of the head. The
reference computes the same share.

`init`: the sink and the selection bias are drawn with the weights
(normal at initializer_range) and rescaled here as the configuration's
`assumed` says: sink = sink_mean + sink_std * z, bias = bias_std * z,
z the drawn value over initializer_range. The program does the same
(models/mimo_program.py `placed`).

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

FAULTS = ("window_127", "window_129", "no_sink", "rotary_all",
          "bias_weighs")


def dims(cfg):
    hd = cfg["head_dim"]
    return dict(d=cfg["hidden_size"], nh=cfg["num_attention_heads"],
                kvh=cfg["num_key_value_heads"],
                wkvh=cfg["swa_num_key_value_heads"], hd=hd,
                vd=cfg["v_head_dim"],
                rot=int(round(cfg["partial_rotary_factor"] * hd)) // 2 * 2,
                window=cfg["sliding_window"], f=cfg["moe_intermediate_size"],
                fd=cfg["intermediate_size"], held=cfg["n_routed_experts"],
                experts=cfg["deployment"]["router_experts"],
                first=cfg["deployment"]["first_expert"],
                top_k=cfg["num_experts_per_tok"])


def layer_kinds(cfg):
    """[(attention kind, feed-forward kind)] of the layers held."""
    n = cfg["num_hidden_layers"]
    return [("window" if w else "full", "experts" if e else "dense")
            for w, e in zip(cfg["hybrid_layer_pattern"][:n],
                            cfg["moe_layer_freq"][:n])]


def layer_spec(cfg, i):
    m, p = dims(cfg), f"mimo{i}_"
    attn, ffn = layer_kinds(cfg)[i]
    kvh = m["wkvh"] if attn == "window" else m["kvh"]
    rows = [(p + "ln1.scale", (m["d"],), "ones"),
            (p + "attn_q.w", (m["d"], m["nh"] * m["hd"]), "normal"),
            (p + "attn_k.w", (m["d"], kvh * m["hd"]), "normal"),
            (p + "attn_v.w", (m["d"], kvh * m["vd"]), "normal"),
            (p + "attn_o.w", (m["nh"] * m["vd"], m["d"]), "normal")]
    if attn == "window" and cfg["add_swa_attention_sink_bias"]:
        rows.append((p + "attn_sink", (m["nh"],), "normal"))
    rows.append((p + "ln2.scale", (m["d"],), "ones"))
    if ffn == "dense":
        rows += [(p + "ffn_in.w", (m["d"], 2 * m["fd"]), "normal"),
                 (p + "ffn_out.w", (m["fd"], m["d"]), "normal")]
    else:
        rows += [(p + "router.w", (m["d"], m["experts"]), "normal"),
                 (p + "router.bias", (m["experts"],), "normal"),
                 (p + "experts_in.w", (m["held"], m["d"], 2 * m["f"]),
                  "normal"),
                 (p + "experts_out.w", (m["held"], m["f"], m["d"]),
                  "normal")]
    return rows


def spec(cfg):
    rows = [("mimo_tok_emb", (cfg["vocab_size"], cfg["hidden_size"]),
             "normal")]
    for i in range(cfg["num_hidden_layers"]):
        rows += layer_spec(cfg, i)
    return rows + [("mimo_lnf.scale", (cfg["hidden_size"],), "ones"),
                   ("mimo_head.w", (cfg["hidden_size"], cfg["vocab_size"]),
                    "normal")]


def placed(cfg, name, value):
    """A drawn leaf as the model holds it: the sink and the selection
    bias rescaled from the draw (module docstring, `init`), every other
    leaf as drawn."""
    a = cfg["assumed_init"]
    z = value.astype(jnp.float32) / cfg["initializer_range"]
    if name.endswith("attn_sink"):
        return (a["sink_mean"] + a["sink_std"] * z).astype(value.dtype)
    if name.endswith("router.bias"):
        return (a["bias_std"] * z).astype(value.dtype)
    return value


def window_ring_pages(cfg):
    """Pages a window layer's ring table holds a lane: what the keys of
    one step (window + chunk - 1 of them) can touch."""
    eng = cfg["engine"]
    n = cfg["sliding_window"] + eng["chunk_tokens"] - 1
    return (n + eng["page_size"] - 2) // eng["page_size"] + 1


def pool_bytes(cfg):
    """(full, window) bytes of the K and V page pools in
    `storage_dtype`: a token of a layer is KVH x (head_dim + v_head_dim)
    values; the window pools hold a ring a lane and the junk page."""
    m, eng = dims(cfg), cfg["engine"]
    item = jnp.dtype(cfg["storage_dtype"]).itemsize
    kinds = [a for a, _f in layer_kinds(cfg)]
    token = (m["hd"] + m["vd"]) * eng["page_size"] * item
    full = kinds.count("full") * m["kvh"] * eng["num_pages"] * token
    wpages = eng["lanes"] * window_ring_pages(cfg) + 1
    return full, kinds.count("window") * m["wkvh"] * wpages * token


def stated_storage_bytes(cfg):
    """Bytes of the weights and of the page pools of both kinds in the
    type the configuration states they are kept in, and the experts'
    load counts (int32): what the step program has to take as its
    arguments, to a few KB of tokens and page tables. A pool that is
    padded (keys stored 256 wide) or copied takes more."""
    weights = sum(math.prod(shape) for _n, shape, _i in spec(cfg))
    n_experts = sum(f == "experts" for _a, f in layer_kinds(cfg))
    return (jnp.dtype(cfg["storage_dtype"]).itemsize * weights
            + sum(pool_bytes(cfg)) + 4 * n_experts * cfg["n_routed_experts"])


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


def rotate(x, pos, rot, theta):
    """x [t, heads, hd], pos [t]: the first `rot` values of every head
    rotated at the token's position, the rest as they are."""
    half = rot // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:rot]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, x[..., rot:]], axis=-1)


def gated_ffn(mm, u, w_in, w_out):
    a1, a2 = jnp.split(mm(u, w_in), 2, axis=-1)
    return mm(jax.nn.silu(a1) * a2, w_out)


def moe(cfg, mm, p, u, fault=None):
    """The held experts' part of the routed sum: every held expert on
    every token, weighted by a gate that is zero where the token did
    not choose it."""
    m = dims(cfg)
    scores = jax.nn.sigmoid(mm(u, p["router.w"]))          # [t, experts]
    ranked = scores + p["router.bias"]
    _, idx = jax.lax.top_k(ranked, m["top_k"])
    vals = jnp.take_along_axis(ranked if fault == "bias_weighs" else scores,
                               idx, axis=-1)
    gates = vals / jnp.sum(vals, axis=-1, keepdims=True)
    dense = jnp.zeros_like(scores).at[
        jnp.arange(u.shape[0])[:, None], idx].set(gates)
    dense = dense[:, m["first"]:m["first"] + m["held"]]          # [t, held]
    out = jnp.zeros_like(u)
    for e in range(m["held"]):
        out += dense[:, e:e + 1] * gated_ffn(
            mm, u, p["experts_in.w"][e], p["experts_out.w"][e])
    return out


def keys_values(cfg, mm, attn, p, h, pos, fault=None):
    """k [t, KVH, hd] rotated and v [t, KVH, vd] scaled, of a block."""
    m, t = dims(cfg), h.shape[0]
    kvh = m["wkvh"] if attn == "window" else m["kvh"]
    theta = cfg["swa_rope_theta"] if attn == "window" else cfg["rope_theta"]
    rot = m["hd"] if fault == "rotary_all" else m["rot"]
    u = rms_norm(h, p["ln1.scale"], cfg["layernorm_epsilon"])
    k = rotate(mm(u, p["attn_k.w"]).reshape(t, kvh, m["hd"]), pos, rot, theta)
    v = cfg["attention_value_scale"] * mm(u, p["attn_v.w"])
    return k, v.reshape(t, kvh, m["vd"])


def block_out(cfg, mm, attn, ffn, p, h, pos, k, v, kpos, fault=None):
    """One layer on a block of rows h [b, d] at positions pos [b], over
    the keys k [s, KVH, hd] and values v [s, KVH, vd] at positions kpos
    [s] (negative: padding before the sequence)."""
    m, b = dims(cfg), h.shape[0]
    theta = cfg["swa_rope_theta"] if attn == "window" else cfg["rope_theta"]
    rot = m["hd"] if fault == "rotary_all" else m["rot"]
    eps = cfg["layernorm_epsilon"]
    u = rms_norm(h, p["ln1.scale"], eps)
    q = rotate(mm(u, p["attn_q.w"]).reshape(b, m["nh"], m["hd"]), pos, rot,
               theta)
    kvh, n = k.shape[1], kpos.shape[0]
    rep = m["nh"] // kvh                # query head h reads KV head h // rep
    q = q.reshape(b, kvh, rep, m["hd"]).transpose(1, 2, 0, 3)
    s = mm(q.reshape(kvh, rep * b, m["hd"]), k.transpose(1, 2, 0))
    s = s.reshape(kvh, rep, b, n) / math.sqrt(m["hd"])
    live = (kpos[None, :] >= 0) & (kpos[None, :] <= pos[:, None])
    if attn == "window":
        window = m["window"] + {"window_127": -1, "window_129": 1}.get(
            fault, 0)
        live = live & (kpos[None, :] > pos[:, None] - window)
    s = jnp.where(live, s, -jnp.inf)
    if "attn_sink" in p and fault != "no_sink":
        s = jnp.concatenate([s, jnp.broadcast_to(
            p["attn_sink"].reshape(kvh, rep, 1, 1), (kvh, rep, b, 1))], -1)
    w = jax.nn.softmax(s, axis=-1)[..., :n].reshape(kvh, rep * b, n)
    ctx = mm(w, v.transpose(1, 0, 2)).reshape(kvh, rep, b, m["vd"])
    ctx = ctx.transpose(2, 0, 1, 3).reshape(b, m["nh"] * m["vd"])
    h = h + mm(ctx, p["attn_o.w"])
    u = rms_norm(h, p["ln2.scale"], eps)
    if ffn == "dense":
        return h + gated_ffn(mm, u, p["ffn_in.w"], p["ffn_out.w"])
    return h + moe(cfg, mm, p, u, fault)


def layer(cfg, precision, attn, ffn, fault, block, p, h):
    """One layer on a whole sequence h [t, d], t a multiple of `block`:
    keys and values of every token first, then the rows block by block
    (a window layer's block sees its own keys and the `block` before
    them, a full layer's all)."""
    mm = products(precision)
    p = {k: v.astype(jnp.float32) for k, v in p.items()}
    t, m = h.shape[0], dims(cfg)
    pos = jnp.arange(t, dtype=jnp.int32)
    hb, pb = h.reshape(t // block, block, -1), pos.reshape(-1, block)
    k, v = jax.lax.map(
        lambda x: keys_values(cfg, mm, attn, p, x[0], x[1], fault), (hb, pb))
    k, v = k.reshape((t,) + k.shape[2:]), v.reshape((t,) + v.shape[2:])
    reach = -(-(m["window"] + 1) // block) * block      # covers window_129
    if attn == "window":
        k = jnp.concatenate([jnp.zeros((reach,) + k.shape[1:]), k])
        v = jnp.concatenate([jnp.zeros((reach,) + v.shape[1:]), v])

    def rows(x):
        hx, px = x
        if attn == "full":
            return block_out(cfg, mm, attn, ffn, p, hx, px, k, v, pos, fault)
        at = px[0]                      # the block's keys and `reach` before
        kx = jax.lax.dynamic_slice_in_dim(k, at, reach + block)
        vx = jax.lax.dynamic_slice_in_dim(v, at, reach + block)
        kpos = at - reach + jnp.arange(reach + block, dtype=jnp.int32)
        return block_out(cfg, mm, attn, ffn, p, hx, px, kx, vx, kpos, fault)

    return jax.lax.map(rows, (hb, pb)).reshape(t, -1)


_LAYER_FNS = {}


def layer_fn(cfg, precision, attn, ffn, fault, block):
    """One compiled function a kind of layer, a precision, a fault and a
    configuration."""
    key = (json.dumps(cfg, sort_keys=True), precision, attn, ffn, fault,
           block)
    if key not in _LAYER_FNS:
        _LAYER_FNS[key] = jax.jit(functools.partial(
            layer, cfg, precision, attn, ffn, fault, block))
    return _LAYER_FNS[key]


def logits_at(cfg, precision, params, tokens, where, fault=None):
    """tokens [T] (padded; causal, so padding after a position cannot
    reach it), where [K] positions -> float32 logits [K, V held]. Layer
    by layer, one layer's weights upcast at a time."""
    mm = products(precision)
    t = len(tokens)
    block = cfg.get("reference_block", 128)
    block = block if t % block == 0 else t
    h = params["mimo_tok_emb"][tokens].astype(jnp.float32)
    for i, (attn, ffn) in enumerate(layer_kinds(cfg)):
        pre = f"mimo{i}_"
        h = layer_fn(cfg, precision, attn, ffn, fault, block)(
            {n[len(pre):]: placed(cfg, n, params[n])
             for n, _s, _i in layer_spec(cfg, i)}, h)
    x = rms_norm(h[where], params["mimo_lnf.scale"].astype(jnp.float32),
                 cfg["layernorm_epsilon"])
    return mm(x, params["mimo_head.w"].astype(jnp.float32))


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
