"""Mamba-2 mixer (state-space duality, arXiv:2405.21060) over a ragged
window with a carried per-row recurrent state.

One op serves the full causal forward (state absent: every row starts
from zero) and the serving step, where a ``[rows, chunk]`` window holds
whatever each lane needs: a prefill chunk, one decode token or nothing.

Per token t of a row, with ``A = -exp(a_log)`` per head:

    [z | xBC | dt] = u W_in
    xBC_t = silu(sum_j conv_w[:, j] * xBC_{t-K+1+j} + conv_b)   (causal)
    x_t [H, P], B_t [G, N], C_t [G, N] = split(xBC_t)
    dt_t = softplus(dt_t + dt_bias)
    S_t = exp(dt_t A) S_{t-1} + dt_t * (x_t outer B_t)       S in [H, P, N]
    y_t = S_t C_t + D * x_t
    out = (group_rms_norm(y * silu(z)) * norm_w) W_out

The recurrence is evaluated a chunk at a time (the quadratic form inside
a chunk, the carried state between chunks), so the state is read and
written once a chunk and never once a token. A row advances by exactly
its ``num_valid`` tokens: dt is zero on padding, which leaves S as it
was, and the conv state becomes the last K-1 valid inputs. A row whose
``positions`` is 0 starts a sequence and reads zero state, inside the
graph: the host never resets a lane. ``num_valid == 0`` touches neither
state.

State: ``ssm`` [rows, H, P, N] and ``conv`` [rows, K-1, d_in + 2GN],
both in the dtype they arrive in (float32 in the serving step).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..core.registry import register_op
from ..kernels.mamba2_state import state_step
from .decoder import dot_stored

_HI = jax.lax.Precision.HIGHEST


def _ssd_chunk(ssm, keep, xh, dt, la, b, c):
    """One chunk of the recurrence. ssm [R, H, P, N]; keep [R] (0: the
    row starts a sequence and what the state holds is dropped); xh [R, T,
    G, K, P] (K heads a group); dt, la [R, T, G, K] (la = dt * A, the log
    decay, <= 0); b, c [R, T, G, N]. Returns (y [R, T, G, K, P], ssm')."""
    R, T, G, K, P = xh.shape
    cum = jnp.cumsum(la, axis=1)                           # [R, T, G, K]
    # inside the chunk: y_t += sum_{s<=t} (C_t . B_s) e^{cum_t-cum_s} dt_s x_s
    scores = jnp.einsum("rtgn,rsgn->rtsg", c, b, precision=_HI)
    seg = cum[:, :, None] - cum[:, None, :]                # [R, T, S, G, K]
    causal = jnp.tril(jnp.ones((T, T), bool))[None, :, :, None, None]
    decay = jnp.exp(jnp.where(causal, seg, -jnp.inf))
    w = scores[..., None] * decay * dt[:, None]            # [R, T, S, G, K]
    y = jnp.einsum("rtsgk,rsgkp->rtgkp", w, xh, precision=_HI)
    # against the carried state, one pass over it (kernels/mamba2_state):
    # y_t += e^{cum_t} S_0 C_t;  S' = e^{cum_T} S_0 + sum_s e^{cum_T-cum_s} dt_s x_s B_s
    last = cum[:, -1]                                      # [R, G, K]
    xw = xh * (jnp.exp(last[:, None] - cum) * dt)[..., None]
    keep = keep[:, None, None]
    from_state, ssm = state_step(
        ssm, jnp.swapaxes(c, 1, 2), jnp.swapaxes(b, 1, 2),
        xw.reshape(R, T, G * K * P),
        (jnp.exp(last) * keep).reshape(R, G * K))
    y += (from_state.reshape(R, T, G, K, P)
          * (jnp.exp(cum) * keep[:, None])[..., None])
    return y, ssm


# jitted so that a program's Mamba layers, and every later trace of it,
# share one trace and one lowering (kernels/ragged_paged_attention.py
# does the same for its kernel: seconds of set-up otherwise)
@functools.partial(jax.jit, static_argnames=(
    "num_heads", "head_dim", "num_groups", "state_size", "chunk_size", "eps"))
def mamba2_mixer(x, num_valid, positions, ssm, conv, w_in, conv_w, conv_b,
                 dt_bias, a_log, d_skip, norm_w, w_out, *, num_heads: int,
                 head_dim: int, num_groups: int, state_size: int,
                 chunk_size: int, eps: float):
    """x [R, C, d]; num_valid, positions [R] (None: every token valid,
    every row from position 0); ssm [R, H, P, N], conv [R, K-1, CH] or
    None (zero). Returns (out [R, C, d] float32, ssm', conv')."""
    R, C, _ = x.shape
    H, P, G, N = num_heads, head_dim, num_groups, state_size
    d_in, K = H * P, conv_w.shape[1]
    ch = d_in + 2 * G * N
    sdt = jnp.float32 if ssm is None else ssm.dtype
    if num_valid is None:
        num_valid = jnp.full((R,), C, jnp.int32)
        positions = jnp.zeros((R,), jnp.int32)
    num_valid = num_valid.astype(jnp.int32)
    if ssm is None:
        ssm = jnp.zeros((R, H, P, N), sdt)
        conv = jnp.zeros((R, K - 1, ch), sdt)
    # a row at position 0 starts a sequence: what its lane held is
    # dropped, the conv's inputs here, the state by a zero decay (never
    # a pass over the state to zero it)
    fresh = (positions == 0) & (num_valid > 0)
    keep = 1.0 - fresh.astype(jnp.float32)
    conv0 = jnp.where(fresh[:, None, None], 0, conv).astype(jnp.float32)
    valid = jnp.arange(C, dtype=jnp.int32)[None, :] < num_valid[:, None]

    zxbcdt = dot_stored(x, w_in)                           # [R, C, *] f32
    z, xbc, dt = jnp.split(zxbcdt, [d_in, d_in + ch], axis=-1)
    # depthwise causal conv over the carried K-1 inputs and the window
    cat = jnp.concatenate([conv0, xbc], axis=1)            # [R, K-1+C, CH]
    cw = conv_w.astype(jnp.float32)
    acc = conv_b.astype(jnp.float32)
    for j in range(K):
        acc = acc + cat[:, j:j + C] * cw[:, j]
    xbc = jax.nn.silu(acc)
    # the last K-1 valid inputs: rows n .. n+K-2 of cat (n = 0: unchanged)
    take = num_valid[:, None] + jnp.arange(K - 1, dtype=jnp.int32)[None, :]
    conv_out = jnp.take_along_axis(cat, take[:, :, None], axis=1)

    xs, b, c = jnp.split(xbc, [d_in, d_in + G * N], axis=-1)
    dt = jax.nn.softplus(dt + dt_bias.astype(jnp.float32)) * valid[..., None]
    a = -jnp.exp(a_log.astype(jnp.float32))
    k = H // G
    xh = xs.reshape(R, C, G, k, P)
    dtg = dt.reshape(R, C, G, k)
    la = dtg * a.reshape(G, k)
    b = b.reshape(R, C, G, N)
    c = c.reshape(R, C, G, N)
    s = ssm.astype(jnp.float32)
    if C <= chunk_size:
        y, s = _ssd_chunk(s, keep, xh, dtg, la, b, c)
    else:
        # long windows (the full forward): chunks of chunk_size, the
        # state carried between them; the tail is padded with dt = 0
        pad = -C % chunk_size
        n = (C + pad) // chunk_size

        def chunks(t):
            t = jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
            t = t.reshape((R, n, chunk_size) + t.shape[2:])
            return jnp.moveaxis(t, 1, 0)

        def body(carry, part):
            state, keep = carry
            y_part, state = _ssd_chunk(state, keep, *part)
            return (state, jnp.ones_like(keep)), y_part

        (s, _), ys = jax.lax.scan(
            body, (s, keep), tuple(map(chunks, (xh, dtg, la, b, c))))
        y = jnp.moveaxis(ys, 0, 1).reshape((R, n * chunk_size) + ys.shape[3:])
        y = y[:, :C]
    y = y + xh * d_skip.astype(jnp.float32).reshape(G, k)[..., None]
    # gated norm, a group at a time
    y = (y.reshape(R, C, d_in) * jax.nn.silu(z)).reshape(R, C, G, d_in // G)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + eps)
    y = y.reshape(R, C, d_in) * norm_w.astype(jnp.float32)
    out = dot_stored(y, w_out)
    return out, s.astype(sdt), conv_out.astype(sdt)


@register_op(
    "mamba2_mixer",
    inputs=("X", "NumValid", "Positions", "SsmState", "ConvState", "WIn",
            "ConvW", "ConvB", "DtBias", "ALog", "D", "NormW", "WOut"),
    outputs=("Out", "SsmStateOut", "ConvStateOut"),
    no_grad=("NumValid", "Positions"), stop_gradient=True)
def _mamba2_mixer_op(ctx, op, ins):
    def opt(slot):
        v = ins.get(slot)
        return v[0] if v else None

    a = op.attrs
    out, ssm, conv = mamba2_mixer(
        ins["X"][0], opt("NumValid"), opt("Positions"), opt("SsmState"),
        opt("ConvState"), ins["WIn"][0], ins["ConvW"][0], ins["ConvB"][0],
        ins["DtBias"][0], ins["ALog"][0], ins["D"][0], ins["NormW"][0],
        ins["WOut"][0], num_heads=int(a["num_heads"]),
        head_dim=int(a["head_dim"]), num_groups=int(a["num_groups"]),
        state_size=int(a["state_size"]), chunk_size=int(a["chunk_size"]),
        eps=float(a["epsilon"]))
    return {"Out": [out], "SsmStateOut": [ssm], "ConvStateOut": [conv]}
