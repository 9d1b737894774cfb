"""Ring attention: context/sequence parallelism for long sequences.

The reference has NO long-context parallelism (SURVEY.md §5 "verified
absences" — only LoD ragged batching); this goes beyond it per the
north star. Design: shard the sequence axis over a mesh axis `sp`;
each device holds a Q/K/V shard. K/V shards rotate around the ring via
lax.ppermute while each device accumulates blockwise
softmax(QK^T)V with running max/denominator (log-sum-exp merging), so
the full [S, S] score matrix never exists and comm overlaps compute on
ICI.

Used inside shard_map; composes with dp/mp axes.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    axis_name: str,
    causal: bool = False,
    sm_scale: Optional[float] = None,
    mask: Optional[jax.Array] = None,
):
    """q,k,v: [B, H, S_local, D] (already sharded on S over axis_name).
    mask: optional additive key mask [B, S_local] (0 valid / -inf
    masked), sharded on S like k — it rotates around the ring with its
    keys. Returns [B, H, S_local, D]. Must run inside shard_map with
    axis_name in the mesh. Differentiable: jax AD flows through the
    scan and ppermute (ppermute transposes to the inverse ring)."""
    B, H, S, D = q.shape
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(D)
    axis_size = lax.psum(1, axis_name)
    my_idx = lax.axis_index(axis_name)
    perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]

    q32 = q.astype(jnp.float32)

    def block(q_blk, k_blk, v_blk, mask_blk, kv_idx):
        s = jnp.einsum("bhqd,bhkd->bhqk", q_blk, k_blk.astype(jnp.float32)) * scale
        if mask_blk is not None:
            s = s + mask_blk[:, None, None, :]
        if causal:
            # global positions: row = my_idx*S + i, col = kv_idx*S + j
            rows = my_idx * S + jnp.arange(S)[:, None]
            cols = kv_idx * S + jnp.arange(S)[None, :]
            s = jnp.where(rows >= cols, s, -1e30)
        m_blk = jnp.max(s, axis=-1, keepdims=True)  # [B,H,S,1]
        p = jnp.exp(s - m_blk)
        l_blk = jnp.sum(p, axis=-1, keepdims=True)
        o_blk = jnp.einsum("bhqk,bhkd->bhqd", p, v_blk.astype(jnp.float32))
        return m_blk, l_blk, o_blk

    def step(carry, _):
        o, m, l, k_cur, v_cur, mask_cur, kv_idx = carry
        m_blk, l_blk, o_blk = block(q32, k_cur, v_cur, mask_cur, kv_idx)
        m_new = jnp.maximum(m, m_blk)
        alpha = jnp.exp(m - m_new)
        beta = jnp.exp(m_blk - m_new)
        l_new = l * alpha + l_blk * beta
        o_new = o * alpha + o_blk * beta
        k_nxt = lax.ppermute(k_cur, axis_name, perm)
        v_nxt = lax.ppermute(v_cur, axis_name, perm)
        mask_nxt = (lax.ppermute(mask_cur, axis_name, perm)
                    if mask_cur is not None else None)
        kv_nxt = (kv_idx - 1) % axis_size
        return (o_new, m_new, l_new, k_nxt, v_nxt, mask_nxt, kv_nxt), None

    # derive initial carry from q so its "varying over axis" type
    # matches the loop outputs (shard_map vma typing)
    o0 = jnp.zeros_like(q32)
    m0 = jnp.full_like(q32[..., :1], -jnp.inf)
    l0 = jnp.zeros_like(q32[..., :1])
    (o, m, l, _, _, _, _), _ = lax.scan(
        step, (o0, m0, l0, k, v, mask, my_idx), None, length=axis_size
    )
    return (o / jnp.maximum(l, 1e-30)).astype(q.dtype)


def make_ring_attention_fn(mesh, axis_name: str = "sp", causal: bool = False,
                           sm_scale: Optional[float] = None,
                           with_mask: bool = False):
    """Wrap ring_attention in shard_map over the given mesh: takes
    full [B, H, S, D] arrays sharded on S (and, if with_mask, an
    additive [B, S] key mask sharded on S)."""
    from jax.sharding import PartitionSpec as P

    smap = jax.shard_map
    spec = P(None, None, axis_name, None)
    mspec = P(None, axis_name)
    core = functools.partial(ring_attention, axis_name=axis_name,
                             causal=causal, sm_scale=sm_scale)

    if with_mask:
        def fn(q, k, v, mask):
            return smap(
                lambda q, k, v, m: core(q, k, v, mask=m),
                mesh=mesh,
                in_specs=(spec, spec, spec, mspec),
                out_specs=spec,
            )(q, k, v, mask)
    else:
        def fn(q, k, v):
            return smap(
                core,
                mesh=mesh,
                in_specs=(spec, spec, spec),
                out_specs=spec,
            )(q, k, v)

    return fn
