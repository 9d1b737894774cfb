"""Paged-attention decode kernel + KV-page write ops.

The generation subsystem (paddle_tpu/generation/) keeps each
sequence's K/V in fixed-size pages inside one preallocated pool —
Ragged Paged Attention (PAPERS.md, arXiv:2604.15464): the decode-side
attention reads K/V *through a block table* (per-sequence list of page
ids) and masks by the sequence's true length, so a running batch of
sequences with wildly different lengths shares one dense executable
and zero per-step reallocation.

Two ops, both registered in the op registry (proglint PTL030 knows
them; PTL020-022 re-infer their shapes through the same lowerings):

  paged_attention  Q [B, 1, H*D] x pages -> Out [B, 1, H*D].
                   On TPU (or PADDLE_TPU_FORCE_PALLAS=1, the AOT-check
                   path) this wraps jax's Mosaic kernel
                   ``jax.experimental.pallas.ops.tpu.paged_attention``
                   (SNIPPETS.md [1] wraps the same entry point);
                   everywhere else — including the
                   PADDLE_TPU_KERNEL_INTERPRET CI mode — it runs the
                   pure-JAX reference below, which is also the
                   numerics oracle the tests diff against.
  kv_cache_write   write new K/V rows into the page pool at
                   positions derived from the block table, page by
                   page, in the pool's own layout (``write_page_rows``:
                   in place when the pool is donated). Covers both
                   lanes: prefill writes a whole [B, S] prompt window,
                   decode writes the single new row per sequence.
                   A window page that takes no valid row is routed to
                   the reserved junk page 0 and rewrites what is
                   there, so inactive decode lanes in the fixed batch
                   cost a wasted write, never a corrupted page.

The page-pool layout matches the jax kernel exactly:
k_pages/v_pages [num_kv_heads, total_pages, page_size, head_dim],
block tables [batch, pages_per_sequence] int32, lengths [batch] int32.

Reference analogue: the reference's decoder stack materializes the
whole K/V prefix per step (beam_search_decoder re-runs attention over
a dense cache); pages + block tables are the TPU-native replacement.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def _pallas_mode() -> Optional[str]:
    # same routing contract as the other fused kernels
    # (flash_attention._pallas_mode): interpret env wins, then real
    # TPU / forced-Pallas AOT validation, else None -> reference
    from .flash_attention import _pallas_mode as _fa_mode

    return _fa_mode()


def _reference_paged_attention(q, k_pages, v_pages, lengths, page_indices,
                               sm_scale: float):
    """Pure-JAX oracle: gather each sequence's pages into a contiguous
    [maxp * page_size] window, mask by true length, plain softmax
    attention. O(B * maxp * page_size * D) HBM — fine for CPU CI and
    the correctness tests, which is its whole job."""
    B, H, D = q.shape
    KVH, _P, ps, _ = k_pages.shape
    maxp = page_indices.shape[1]
    # [KVH, B, maxp, ps, D] -> [B, KVH, maxp*ps, D]
    k = jnp.transpose(k_pages[:, page_indices], (1, 0, 2, 3, 4)).reshape(
        B, KVH, maxp * ps, D)
    v = jnp.transpose(v_pages[:, page_indices], (1, 0, 2, 3, 4)).reshape(
        B, KVH, maxp * ps, D)
    if KVH != H:  # grouped-query: repeat KV heads over the query groups
        k = jnp.repeat(k, H // KVH, axis=1)
        v = jnp.repeat(v, H // KVH, axis=1)
    s = jnp.einsum("bhd,bhkd->bhk", q.astype(jnp.float32) * sm_scale,
                   k.astype(jnp.float32))
    valid = jnp.arange(maxp * ps, dtype=jnp.int32)[None, :] \
        < lengths[:, None]                                   # [B, K]
    s = jnp.where(valid[:, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhk,bhkd->bhd", p, v.astype(jnp.float32))
    # a length-0 row is all-masked (softmax of all -inf = NaN): define
    # its output as zeros instead of letting NaN escape into the batch
    o = jnp.where(lengths[:, None, None] > 0, o, 0.0)
    return o.astype(q.dtype)


def _compute_block_pages(pages_per_seq: int) -> int:
    """Largest divisor of the block-table width that is <= 8 — the
    jax kernel requires pages_per_compute_block | pages_per_sequence,
    and small blocks keep the VMEM working set bounded."""
    for c in (8, 4, 2, 1):
        if pages_per_seq % c == 0:
            return c
    return 1


def paged_attention(q, k_pages, v_pages, lengths, page_indices, *,
                    sm_scale: Optional[float] = None,
                    pages_per_compute_block: Optional[int] = None):
    """Decode-step attention over paged K/V.

    q:            [B, num_heads, head_dim] — one query row per sequence
    k_pages/v_pages: [num_kv_heads, total_pages, page_size, head_dim]
    lengths:      [B] int32 — tokens to attend over per sequence
                  (INCLUDING the row just written for this step)
    page_indices: [B, pages_per_sequence] int32 block tables

    Returns [B, num_heads, head_dim]. The softmax scale (default
    1/sqrt(head_dim)) is applied to q here — the jax Mosaic kernel
    expects pre-scaled queries, and both paths must agree so the CPU
    CI numerics are the TPU numerics.
    """
    B, H, D = q.shape
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(D)
    lengths = lengths.astype(jnp.int32)
    page_indices = page_indices.astype(jnp.int32)
    mode = _pallas_mode()
    if mode == "tpu":
        # no retry on the reference: a kernel that fails to trace,
        # lower or compile raises
        from jax.experimental.pallas.ops.tpu.paged_attention import (
            paged_attention as _jax_paged_attention,
        )

        blk = (pages_per_compute_block
               or _compute_block_pages(page_indices.shape[1]))
        return _jax_paged_attention(
            (q * scale).astype(q.dtype), k_pages, v_pages,
            lengths, page_indices,
            pages_per_compute_block=blk,
        )
    return _reference_paged_attention(q, k_pages, v_pages, lengths,
                                      page_indices, scale)


def window_pages(page_indices, positions, num_valid, S: int, ps: int,
                 ring: bool = False):
    """Which pages the windows ``positions[b] .. positions[b] + S - 1``
    fall into, and what each slot of those pages takes:

      page [B, T]      the pool page under the window's t-th page; junk
                       page 0 where that page takes no valid row (an
                       idle lane, batch padding, a short window)
      src  [B, T * ps] the window row that lands in each slot
      ok   [B, T, ps]  whether that row is real; elsewhere the slot
                       keeps what it holds

    T is the most pages a window of S rows can touch. ``ring``: the
    tables are rings (a window layer's: the page of positions p * ps ..
    lies at entry p % width). No two windows
    share a page that takes rows (the engine writes at positions >= a
    sequence's length, into pages that sequence alone holds)."""
    T = (S + ps - 2) // ps + 1
    page_indices = page_indices.astype(jnp.int32)
    positions = positions.astype(jnp.int32)
    num_valid = num_valid.astype(jnp.int32)
    col0, slot0 = positions // ps, positions % ps
    t = jnp.arange(T, dtype=jnp.int32)[None, :]
    touched = (num_valid[:, None] > 0) & (t * ps < (slot0 + num_valid)[:, None])
    width = page_indices.shape[1]
    col = ((col0[:, None] + t) % width if ring
           else jnp.clip(col0[:, None] + t, 0, width - 1))
    page = jnp.where(touched,
                     jnp.take_along_axis(page_indices, col, axis=1), 0)
    row = (t[:, :, None] * ps + jnp.arange(ps, dtype=jnp.int32)[None, None, :]
           - slot0[:, None, None])                              # [B, T, ps]
    ok = touched[:, :, None] & (row >= 0) & (row < num_valid[:, None, None])
    return page, jnp.clip(row, 0, S - 1).reshape(-1, T * ps), ok


def write_page_rows(pool, new, page, src, ok):
    """Write window rows into a pool, whole pages at a time: gather the
    pages the windows touch, put the new rows into their slots, store
    the pages back.

    pool [KVH, P, ps, ...] (K/V pages, or the scale planes of an int8
    pool), new [KVH, B, S, ...]; ``page``/``src``/``ok`` from
    ``window_pages``.

    Why not one scatter of rows (``pool.at[:, page, slot].set(rows)``,
    what this was): XLA's scatter wants the indexed dims outermost, so
    for a pool whose pages are its second dim it works in the layout
    [P, ps, KVH, D] and converts the WHOLE pool into it and back out of
    it for the attention kernel, which reads the pool as stored: two
    copies of every pool every step, donated or not (PERF.md, PR 30:
    19.8 ms of a 28.8 ms GPT-3 XL step). Seen as [KVH * P, ps, ...]
    the pool is the same bytes (the tiled dims are untouched), pages
    are its outermost dim, and both the gather and the scatter of
    whole pages work on it as it lies: with the pool donated the
    update is in place."""
    KVH, P, ps = pool.shape[:3]
    B, T = page.shape
    tail = pool.shape[3:]
    ones = (1,) * len(tail)
    flat = pool.reshape((KVH * P, ps) + tail)
    idx = (jnp.arange(KVH, dtype=jnp.int32)[:, None, None] * P
           + page[None]).reshape(-1)
    held = flat[idx].reshape((KVH, B, T, ps) + tail)
    rows = jnp.take_along_axis(
        new.astype(pool.dtype), src.reshape((1, B, T * ps) + ones),
        axis=2).reshape(held.shape)
    pages = jnp.where(ok.reshape((1, B, T, ps) + ones), rows, held)
    return flat.at[idx].set(
        pages.reshape((-1, ps) + tail)).reshape(pool.shape)


def kv_cache_write(k_pages, v_pages, k_new, v_new, page_indices,
                   positions, num_valid):
    """Write new K/V rows into the page pool.

    k_new/v_new:  [B, S, KVH, D] rows for positions
                  positions[b] .. positions[b] + S - 1
    positions:    [B] int32 — each sequence's first absolute slot
                  (decode: the current length; prefill: 0)
    num_valid:    [B] int32 — rows of S that are real; the rest (batch
                  padding, idle decode lanes) are written nowhere

    Returns (k_pages', v_pages'): a functional update that XLA does in
    place when the pools are donated, as the step programs' are
    (``write_page_rows``); where they are not it costs one copy.
    """
    S, ps = int(k_new.shape[1]), int(k_pages.shape[2])
    where = window_pages(page_indices, positions, num_valid, S, ps)
    return tuple(
        write_page_rows(pool, jnp.transpose(new, (2, 0, 1, 3)), *where)
        for pool, new in ((k_pages, k_new), (v_pages, v_new)))


# -- program-level layers ----------------------------------------------------


def paged_attention_layer(q_var, k_pages_var, v_pages_var, tables_var,
                          lengths_var, num_heads: int):
    """Emit the fused ``paged_attention`` op: Q [B, 1, H*D] attending
    over the page pool through the block tables. One op per decoder
    layer — the whole decode step stays a single XLA executable."""
    from ..layer_helper import LayerHelper
    from ..layers.nn import _out

    helper = LayerHelper("paged_attention")
    out = _out(helper, q_var, shape=q_var.shape)
    helper.append_op(
        type="paged_attention",
        inputs={"Q": [q_var], "KPages": [k_pages_var],
                "VPages": [v_pages_var], "BlockTables": [tables_var],
                "Lengths": [lengths_var]},
        outputs={"Out": [out]},
        attrs={"num_heads": num_heads},
    )
    return out


def kv_cache_write_layer(k_pages_var, v_pages_var, k_var, v_var,
                         tables_var, positions_var, num_valid_var,
                         num_heads: int):
    """Emit the ``kv_cache_write`` op. It writes ``OutKPages`` /
    ``OutVPages`` onto the pool Variables it reads (``ParamOut = Param``,
    as the optimizer ops do), so persistable pools are rewritten state
    that the executor donates and XLA updates in place; the attention
    ops after it read the same Variables. Returns them."""
    from ..layer_helper import LayerHelper

    LayerHelper("kv_cache_write").append_op(
        type="kv_cache_write",
        inputs={"KPages": [k_pages_var], "VPages": [v_pages_var],
                "K": [k_var], "V": [v_var], "BlockTables": [tables_var],
                "Positions": [positions_var], "NumValid": [num_valid_var]},
        outputs={"OutKPages": [k_pages_var], "OutVPages": [v_pages_var]},
        attrs={"num_heads": num_heads},
    )
    return k_pages_var, v_pages_var


# -- op registration ---------------------------------------------------------
from ..core.registry import register_op  # noqa: E402


@register_op("paged_attention",
             inputs=("Q", "KPages", "VPages", "BlockTables", "Lengths"),
             outputs=("Out",),
             no_grad=("BlockTables", "Lengths"), stop_gradient=True)
def _paged_attention_op(ctx, op, ins):
    q = ins["Q"][0]                       # [B, 1, H*D] layer layout
    kp, vp = ins["KPages"][0], ins["VPages"][0]
    tables, lengths = ins["BlockTables"][0], ins["Lengths"][0]
    h = int(op.attrs["num_heads"])
    B, S1, HD = q.shape
    if S1 != 1:
        raise ValueError(
            f"paged_attention is a decode op: Q must be [B, 1, H*D], got "
            f"seq dim {S1} (use flash_attention for the prefill lane)")
    D = HD // h
    o = paged_attention(q.reshape(B, h, D), kp, vp, lengths, tables)
    return {"Out": [o.reshape(B, 1, HD)]}


@register_op("kv_cache_write",
             inputs=("KPages", "VPages", "K", "V", "BlockTables",
                     "Positions", "NumValid"),
             outputs=("OutKPages", "OutVPages"),
             no_grad=("BlockTables", "Positions", "NumValid"),
             stop_gradient=True)
def _kv_cache_write_op(ctx, op, ins):
    kp, vp = ins["KPages"][0], ins["VPages"][0]
    k, v = ins["K"][0], ins["V"][0]       # [B, S, H*D] layer layout
    h = int(op.attrs["num_heads"])
    B, S, HD = k.shape
    D = HD // h
    kp, vp = kv_cache_write(
        kp, vp, k.reshape(B, S, h, D), v.reshape(B, S, h, D),
        ins["BlockTables"][0], ins["Positions"][0], ins["NumValid"][0])
    return {"OutKPages": [kp], "OutVPages": [vp]}
