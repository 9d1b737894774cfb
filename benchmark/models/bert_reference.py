"""Plain reference for the BERT pre-training step: forward, loss,
gradients and Adam in straightforward jax.numpy. Imports nothing of the
program and takes nothing it made; weights come from models/weights.py.

Follows arXiv:1810.04805 as the program's `build_bert_pretrain` states
it: token + position embeddings (no segment embedding), post-LN
encoder layers with fused QKV, exact (erf) GELU, an untied LM head over
the hidden states, full-softmax cross-entropy averaged over every
position. Dropout is 0 (the configuration file says why).

`precision` selects how every matrix product is computed:
  "highest"  float32 operands, Precision.HIGHEST: the reference;
  "bf16"     operands rounded to bfloat16, float32 accumulation;
  "fp8"      operands rounded to float8_e4m3fn: the control for a
             configuration that states bfloat16.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

LN_EPS = 1e-5
ADAM = dict(lr=1e-4, beta1=0.9, beta2=0.999, eps=1e-8)


def spec(cfg):
    """Ordered (name, shape, init) rows; names are the reference's own
    and happen to equal the program's parameter names."""
    h, f, v = cfg["hidden_size"], cfg["ffn_size"], cfg["vocab_size"]
    rows = [("word_embedding", (v, h), "normal"),
            ("pos_embedding", (cfg["max_position"], h), "normal"),
            ("emb_ln.scale", (h,), "ones"), ("emb_ln.bias", (h,), "zeros")]
    for i in range(cfg["num_layers"]):
        p = f"enc{i}_"
        rows += [(p + "qkv.w", (h, 3 * h), "normal"), (p + "qkv.b", (3 * h,), "zeros"),
                 (p + "proj.w", (h, h), "normal"), (p + "proj.b", (h,), "zeros"),
                 (p + "ln1.scale", (h,), "ones"), (p + "ln1.bias", (h,), "zeros"),
                 (p + "ffn1.w", (h, f), "normal"), (p + "ffn1.b", (f,), "zeros"),
                 (p + "ffn2.w", (f, h), "normal"), (p + "ffn2.b", (h,), "zeros"),
                 (p + "ln2.scale", (h,), "ones"), (p + "ln2.bias", (h,), "zeros")]
    rows += [("lm_head.w", (h, v), "normal"), ("lm_head.b", (v,), "zeros")]
    return rows


def matmul(precision):
    def mm(a, b):
        if precision == "highest":
            return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)
        low = {"bf16": jnp.bfloat16, "fp8": jnp.float8_e4m3fn}[precision]
        a = a.astype(low).astype(jnp.bfloat16)
        b = b.astype(low).astype(jnp.bfloat16)
        return jnp.matmul(a, b, preferred_element_type=jnp.float32)
    return mm


def layer_norm(x, scale, bias):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, -1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + LN_EPS) * scale + bias


def encoder_layer(cfg, mm, p, pre, x):
    b, s, h = x.shape
    nh = cfg["num_heads"]
    qkv = mm(x, p[pre + "qkv.w"]) + p[pre + "qkv.b"]
    q, k, v = (t.reshape(b, s, nh, h // nh).transpose(0, 2, 1, 3)
               for t in jnp.split(qkv, 3, axis=-1))
    scores = mm(q, k.transpose(0, 1, 3, 2)) / math.sqrt(h // nh)
    ctx = mm(jax.nn.softmax(scores, axis=-1), v)
    ctx = ctx.transpose(0, 2, 1, 3).reshape(b, s, h)
    x = layer_norm(x + mm(ctx, p[pre + "proj.w"]) + p[pre + "proj.b"],
                   p[pre + "ln1.scale"], p[pre + "ln1.bias"])
    ffn = jax.nn.gelu(mm(x, p[pre + "ffn1.w"]) + p[pre + "ffn1.b"],
                      approximate=False)
    ffn = mm(ffn, p[pre + "ffn2.w"]) + p[pre + "ffn2.b"]
    return layer_norm(x + ffn, p[pre + "ln2.scale"], p[pre + "ln2.bias"])


def loss_sum(cfg, precision, p, src, pos, labels):
    """Sum (not mean) of the per-token cross-entropy over a block of
    rows, so blocks add up to the whole batch."""
    mm = matmul(precision)
    x = p["word_embedding"][src] + p["pos_embedding"][pos]
    x = layer_norm(x, p["emb_ln.scale"], p["emb_ln.bias"])
    for i in range(cfg["num_layers"]):
        x = jax.checkpoint(functools.partial(encoder_layer, cfg, mm),
                           static_argnums=(1,))(p, f"enc{i}_", x)
    logits = mm(x, p["lm_head.w"]) + p["lm_head.b"]
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    return jnp.sum(lse - picked)


def adam_update(p, g, m1, m2, t):
    """Adam as Fluid states it: lr_t folds both bias corrections and
    epsilon is added to sqrt(m2) uncorrected."""
    a = ADAM
    lr_t = a["lr"] * math.sqrt(1 - a["beta2"] ** t) / (1 - a["beta1"] ** t)
    m1 = jax.tree.map(lambda m, g_: a["beta1"] * m + (1 - a["beta1"]) * g_, m1, g)
    m2 = jax.tree.map(lambda m, g_: a["beta2"] * m + (1 - a["beta2"]) * g_ * g_, m2, g)
    p = jax.tree.map(lambda p_, m, v: p_ - lr_t * m / (jnp.sqrt(v) + a["eps"]),
                     p, m1, m2)
    return p, m1, m2


def leaf_norms(tree):
    return {k: float(jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32)))))
            for k, v in tree.items()}


def train_readings(cfg, params, feeds, *, precision="highest", block_rows=8,
                   rows=None, devices=None):
    """Follow the first len(feeds) steps from `params`. `rows` (a slice)
    keeps only part of every batch, the mean taken over that part: the
    planted fault "half of the batch left out" reads through it.

    Returns {"loss": [...], "grad_norm": {leaf: norm of step 1's
    gradient}, "delta_norm": {leaf: norm of params' change after all
    steps}}. With several `devices` a block is block_rows on each of
    them, the same function with its rows laid over the chips."""
    put = jnp.asarray
    if devices is not None and len(devices) > 1:
        from jax.sharding import Mesh, NamedSharding, PartitionSpec

        mesh = Mesh(np.array(devices), ("rows",))
        params = jax.device_put(params, NamedSharding(mesh, PartitionSpec()))
        by_rows = NamedSharding(mesh, PartitionSpec("rows"))
        block_rows *= len(devices)

        def put(x):
            return jax.device_put(x, by_rows)
    grad_fn = jax.jit(jax.value_and_grad(
        functools.partial(loss_sum, cfg, precision)))
    update = jax.jit(adam_update, static_argnums=(4,), donate_argnums=(0, 2, 3))
    acc = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b), donate_argnums=(0,))
    scale = jax.jit(lambda t, s: jax.tree.map(lambda x: x * s, t),
                    donate_argnums=(0,))
    p0_norm_src = params
    p = jax.tree.map(jnp.copy, params)
    m1 = jax.tree.map(jnp.zeros_like, params)
    m2 = jax.tree.map(jnp.zeros_like, params)
    out = {"loss": []}
    for t, feed in enumerate(feeds, start=1):
        src, pos, labels = (np.asarray(feed[k])[rows or slice(None)]
                            for k in ("src_ids", "pos_ids", "labels"))
        total, grads = 0.0, None
        for lo in range(0, src.shape[0], block_rows):
            blk = slice(lo, lo + block_rows)
            val, g = grad_fn(p, put(src[blk].astype(np.int32)),
                             put(pos[blk].astype(np.int32)),
                             put(labels[blk].astype(np.int32)))
            total += float(val)
            grads = g if grads is None else acc(grads, g)
        ntok = src.size
        grads = scale(grads, 1.0 / ntok)
        out["loss"].append(total / ntok)
        if t == 1:
            out["grad_norm"] = leaf_norms(grads)
        p, m1, m2 = update(p, grads, m1, m2, t)
    out["delta_norm"] = leaf_norms(
        jax.tree.map(jnp.subtract, p, p0_norm_src))
    return out
