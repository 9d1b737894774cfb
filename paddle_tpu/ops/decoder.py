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


def live_ffn_rows(rows: int, d: int, f: int, itemsize: int) -> int:
    """How many of a window's ``rows`` a gated feed-forward of width
    ``f`` on ``d`` multiplies alone when no more are valid: half the
    window, or 0 (every row, always) where the weights, 3 d f x
    ``itemsize`` bytes, are under ten times the ~12 rows d bytes that
    gathering the rows and putting them back moves in float32."""
    return rows // 2 if 3 * f * itemsize >= 120 * rows else 0


def gated_silu_ffn(x, w_in, w_out, gate_multiplier=None,
                   down_multiplier=None, num_valid=None, live_rows=0):
    """``(silu(a1) * a2) W_out`` with ``(a1, a2) = split(x W_in)``; the
    muP multipliers (falcon_h1), where given, scale a1 inside the SiLU
    and the output, in float32.

    With ``num_valid`` [rows] over x [rows, chunk, d] (the tokens of
    each row that are real) and ``live_rows`` R > 0: a window whose
    valid tokens number R or fewer gathers them into R rows, multiplies
    those and puts them back, a padding row reading 0; a fuller window
    multiplies every row. Each row's result is its own either way."""

    def ffn(x):
        a1, a2 = jnp.split(dot_stored(x, w_in), 2, axis=-1)
        if gate_multiplier is not None:
            a1 = a1 * gate_multiplier
        out = dot_stored(jax.nn.silu(a1) * a2, w_out)
        return out if down_multiplier is None else out * down_multiplier

    if num_valid is None or not live_rows:
        return ffn(x)
    rows, chunk, d = x.shape
    n = rows * chunk
    valid = (jnp.arange(chunk, dtype=jnp.int32)[None, :]
             < num_valid.astype(jnp.int32)[:, None]).reshape(n)
    (idx,) = jnp.nonzero(valid, size=live_rows, fill_value=n)
    # each valid row's place among the R, and past them (a zero) for
    # padding
    at = jnp.where(valid, jnp.cumsum(valid) - 1, live_rows)

    def narrow(flat):
        y = ffn(jnp.take(flat, idx, axis=0, mode="fill", fill_value=0))
        return jnp.take(y, at, axis=0, mode="fill", fill_value=0)

    out = jax.lax.cond(valid.sum() <= live_rows, narrow, ffn,
                       x.reshape(n, d))
    return out.reshape(rows, chunk, -1)


def rotary_embedding(x, positions, rotary_dim: int, base: float,
                     offset: int = 0):
    """x [..., T, H, D] float32, positions [..., T]: rotate the
    ``rotary_dim`` values of every head from ``offset`` on at its
    token's position (pairs (i, i + rotary_dim / 2), frequency
    base^(-2i / rotary_dim)); the rest pass."""
    half = rotary_dim // 2
    inv = base ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    end = offset + rotary_dim
    x1, x2 = x[..., offset:offset + half], x[..., offset + half:end]
    parts = [x1 * cos - x2 * sin, x2 * cos + x1 * sin, x[..., end:]]
    return jnp.concatenate([x[..., :offset]] * bool(offset) + parts, -1)


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


@register_op("gated_silu_ffn", inputs=("X", "WIn", "WOut", "NumValid"),
             outputs=("Out",), no_grad=("NumValid",))
def _gated_silu_ffn_op(ctx, op, ins):
    x, w_out = ins["X"][0], ins["WOut"][0]
    nv = ins.get("NumValid")
    live = 0
    if nv:
        f, d = w_out.shape
        live = live_ffn_rows(x.shape[0] * x.shape[1], d, f,
                             w_out.dtype.itemsize)
    return {"Out": [gated_silu_ffn(x, ins["WIn"][0], w_out,
                                   op.attrs.get("gate_multiplier"),
                                   op.attrs.get("down_multiplier"),
                                   nv[0] if nv else None, live)]}


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
        float(op.attrs["base"]), int(op.attrs.get("offset", 0)))
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
