"""Bias-free decoder pieces over weights kept in their storage type:
RMS norm, a gated-SiLU feed-forward, a plain projection, a rotary
position embedding over part of a head, and the plain causal attention
of a full forward pass (grouped queries, an optional window and sink).

A served model's weights stay in the type they were published in
(bfloat16): every product here rounds its activation operand to the
weight's type (one MXU pass) and accumulates in float32, so nothing is
upcast and copied. The residual stream, the norms and the activations
stay float32.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..core.registry import register_op


def dot_stored(x, w, transpose_w: bool = False):
    """``x @ w`` (or ``x @ w.T``) with the operand rounded to the
    weight's storage type and float32 accumulation."""
    dims = (((x.ndim - 1,), (1 if transpose_w else 0,)), ((), ()))
    return jax.lax.dot_general(x.astype(w.dtype), w, dims,
                               preferred_element_type=jnp.float32)


def rms_norm(x, scale, eps):
    x = x.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)


def gated_silu_ffn(x, w_in, w_out):
    """``(silu(a1) * a2) W_out`` with ``(a1, a2) = split(x W_in)``."""
    a1, a2 = jnp.split(dot_stored(x, w_in), 2, axis=-1)
    return dot_stored(jax.nn.silu(a1) * a2, w_out)


def rotary_embedding(x, positions, rotary_dim: int, base: float):
    """x [..., T, H, D] float32, positions [..., T]: rotate the first
    ``rotary_dim`` values of every head at its token's position (pairs
    (i, i + rotary_dim / 2), frequency base^(-2i / rotary_dim)); the
    rest pass."""
    half = rotary_dim // 2
    inv = base ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:rotary_dim]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, x[..., rotary_dim:]], -1)


def causal_attention(q, k, v, window=None, sink=None):
    """q [B, T, H, D], k [B, T, KVH, D], v [B, T, KVH, Dv] -> [B, T, H,
    Dv]: causal softmax attention of whole sequences, float32, scores
    q.k / sqrt(D); query head h reads KV head h // (H / KVH). ``window``:
    keys i - window < j <= i. ``sink`` [H]: a logit a head that joins the
    denominator only."""
    B, T, H, D = q.shape
    rep = H // k.shape[2]
    k = jnp.repeat(k.astype(jnp.float32), rep, axis=2)
    v = jnp.repeat(v.astype(jnp.float32), rep, axis=2)
    s = jnp.einsum("bthd,bshd->bhts", q.astype(jnp.float32) / (D ** 0.5), k)
    i = jnp.arange(T)[:, None]
    j = jnp.arange(T)[None, :]
    live = j <= i
    if window is not None:
        live = live & (j > i - window)
    s = jnp.where(live, s, -1e30)
    if sink is not None:
        snk = jnp.broadcast_to(
            sink.astype(jnp.float32)[None, :, None, None], (B, H, T, 1))
        s = jnp.concatenate([s, snk], axis=-1)
    p = jax.nn.softmax(s, axis=-1)[..., :T]
    return jnp.einsum("bhts,bshd->bthd", p, v)


@register_op("rms_norm", inputs=("X", "Scale"), outputs=("Out",))
def _rms_norm_op(ctx, op, ins):
    return {"Out": [rms_norm(ins["X"][0], ins["Scale"][0],
                             float(op.attrs["epsilon"]))]}


@register_op("gated_silu_ffn", inputs=("X", "WIn", "WOut"), outputs=("Out",))
def _gated_silu_ffn_op(ctx, op, ins):
    return {"Out": [gated_silu_ffn(ins["X"][0], ins["WIn"][0],
                                   ins["WOut"][0])]}


@register_op("linear_stored", inputs=("X", "W"), outputs=("Out",))
def _linear_stored_op(ctx, op, ins):
    return {"Out": [dot_stored(ins["X"][0], ins["W"][0],
                               bool(op.attrs.get("transpose_w", False)))]}


@register_op("rotary_embedding", inputs=("X", "Positions"), outputs=("Out",),
             no_grad=("Positions",), stop_gradient=True)
def _rotary_embedding_op(ctx, op, ins):
    x = ins["X"][0]                                   # [B, T, H * D]
    h = int(op.attrs["num_heads"])
    out = rotary_embedding(
        x.astype(jnp.float32).reshape(x.shape[:-1] + (h, -1)),
        ins["Positions"][0], int(op.attrs["rotary_dim"]),
        float(op.attrs["base"]))
    return {"Out": [out.reshape(x.shape)]}


@register_op("causal_attention", inputs=("Q", "K", "V", "Sink"),
             outputs=("Out",), no_grad=("Sink",), stop_gradient=True)
def _causal_attention_op(ctx, op, ins):
    q, k, v = ins["Q"][0], ins["K"][0], ins["V"][0]   # [B, T, heads * D]
    h, kvh = int(op.attrs["num_heads"]), int(op.attrs["num_kv_heads"])
    B, T = q.shape[:2]
    sink, window = ins.get("Sink"), op.attrs.get("window")
    out = causal_attention(
        q.reshape(B, T, h, -1), k.reshape(B, T, kvh, -1),
        v.reshape(B, T, kvh, -1), int(window) if window else None,
        sink[0] if sink else None)
    return {"Out": [out.reshape(B, T, -1)]}
