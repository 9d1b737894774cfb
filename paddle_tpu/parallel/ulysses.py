"""Ulysses-style sequence parallelism: all-to-all head<->sequence
re-sharding (the DeepSpeed-Ulysses recipe, arXiv:2309.14509).

The OTHER long-context strategy next to ring attention
(parallel/ring_attention.py): instead of rotating K/V shards around
the ring, ONE all-to-all converts the sequence-sharded [B, H, S/sp, D]
layout into a head-sharded [B, H/sp, S, D] layout, each device runs
ordinary full-sequence attention on its head subset (reusing the
single-chip flash kernels), and a second all-to-all restores sequence
sharding. Comm volume is 2 all-to-alls of the activations vs the
ring's sp-1 K/V rotations — better when heads divide evenly and the
interconnect favors few large transfers.

The reference has NO long-context parallelism (SURVEY.md §5 verified
absences); both strategies go beyond it per the north star. Selected
via CompiledProgram.with_sequence_parallel(mode="ulysses").
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax


def ulysses_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    axis_name: str,
    causal: bool = False,
    sm_scale: Optional[float] = None,
    mask: Optional[jax.Array] = None,
    attention_fn=None,
):
    """q,k,v: [B, H, S_local, D] sequence-sharded over axis_name; mask:
    optional additive [B, S_global] key mask, REPLICATED (full-sequence
    attention needs every key's mask bit). Returns [B, H, S_local, D].
    Must run inside shard_map. H must divide by the axis size.

    attention_fn(q, k, v, causal, sm_scale, mask) runs the local
    full-sequence attention — defaults to the fused flash kernels."""
    B, H, S_loc, D = q.shape
    sp = lax.psum(1, axis_name)
    if H % sp:
        raise ValueError(
            f"ulysses: num_heads {H} must be divisible by the sequence "
            f"axis size {sp} (use mode='ring' otherwise)")
    # [B, H, S_loc, D] -> [B, H/sp, S, D]: give each peer a head slice,
    # collect my heads' full sequence
    def a2a(x, fwd=True):
        if fwd:
            return lax.all_to_all(x, axis_name, split_axis=1,
                                  concat_axis=2, tiled=True)
        return lax.all_to_all(x, axis_name, split_axis=2,
                              concat_axis=1, tiled=True)

    qh, kh, vh = a2a(q), a2a(k), a2a(v)
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(D)
    if attention_fn is None:
        from ..kernels.flash_attention import flash_attention

        o = flash_attention(qh, kh, vh, causal=causal, sm_scale=scale,
                            mask=mask)
    else:
        o = attention_fn(qh, kh, vh, causal, scale, mask)
    return a2a(o.astype(q.dtype), fwd=False)


def make_ulysses_attention_fn(mesh, axis_name: str = "sp",
                              causal: bool = False,
                              sm_scale: Optional[float] = None,
                              with_mask: bool = False):
    """Wrap ulysses_attention in shard_map over the given mesh: takes
    full [B, H, S, D] arrays sharded on S (and, if with_mask, an
    additive [B, S] key mask — replicated, unlike the ring's sharded
    mask, because local attention covers the full sequence)."""
    import functools

    from jax.sharding import PartitionSpec as P

    smap = jax.shard_map
    spec = P(None, None, axis_name, None)
    core = functools.partial(ulysses_attention, axis_name=axis_name,
                             causal=causal, sm_scale=sm_scale)

    if with_mask:
        def fn(q, k, v, mask):
            return smap(
                lambda q, k, v, m: core(q, k, v, mask=m),
                mesh=mesh,
                in_specs=(spec, spec, spec, P(None, None)),
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
