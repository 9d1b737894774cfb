"""Bias-free decoder pieces over weights kept in their storage type:
RMS norm, a gated-SiLU feed-forward, a plain projection.

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
