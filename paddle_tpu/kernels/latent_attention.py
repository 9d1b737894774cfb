"""Latent attention (MLA) over a paged cache of latents: ONE kernel for
prefill chunks and decode rows.

A latent layer (models/mimo.py, kind ``latent``) keeps one entry a token
and no values: the normed latent ``c_kv`` (``L`` = kv_lora_rank wide)
and the rotated rope key ``k_pe`` (``R`` wide), shared by every query
head. Its published form expands the latent into keys and values a
head, ``[k_nope | v]_h = c_kv W_kvb,h``; the absorbed form never does:

    q_lat,h = q_nope,h W_uk,h^T                         (L wide)
    s_h     = (q_lat,h . c_kv + q_pe,h . k_pe) / sqrt(N + R)
    o_h     = (softmax(s_h) c_kv) W_uv,h                (v_dim wide)

with ``W_kvb,h = [W_uk,h | W_uv,h]`` (``N`` = qk_nope_head_dim columns,
then ``v_dim``). Equal in exact arithmetic; a cached key costs ``2 H
(2 L + R)`` FLOPs against the ``2 L H (N + v_dim)`` that expanding it
would, so one kernel serves both phases (PERF.md).

**The page.** One pool a layer, ``[1, P, ps + R, L]`` with ``ps == L``:
the split key layout of kernels/ragged_paged_attention.py (the entry is
a key ``L + R`` wide whose first ``L`` values are also its value) — a
page holds ``ps`` tokens' latents as rows over their rope keys
transposed, ``[R, ps]``, whole lane tiles at L = 512 and no padding:
``(L + R) x ps`` values, what the tokens hold.

**The kernel.** The grid is one step a lane. A step walks the lane's
live pages once, each copied from HBM into one of two VMEM buffers
(the next in flight while this one is multiplied), and multiplies every
valid token of the lane against it: a token's ``H`` query heads are one
``[H, L]`` tile (128 rows at H = 128, one MXU tile), so a decode lane
does one tile a page and a prefill chunk of 16 does sixteen; padding
tokens and idle lanes do nothing and read zero. The running softmax is
float32; the products take the pages' type: one bfloat16 MXU pass with
float32 accumulation over bfloat16 pages (q and the probabilities
rounded), a float32 contraction (HIGHEST) over float32 ones. Its name
in a device trace is ``latent_paged_attention``.

Routing as every kernel here: Pallas on a TPU (or under
PADDLE_TPU_FORCE_PALLAS=1), the Pallas interpreter under
PADDLE_TPU_KERNEL_INTERPRET=1, the jnp reference below elsewhere.

Ops: ``latent_cache_write`` (entries into the pool, rewritten state as
``kv_cache_write``'s), ``latent_paged_attention`` (the absorbed form of
the serving step: W_uk, the kernel, W_uv) and ``latent_attention`` (the
expanded form over whole sequences, what a full forward pass exports).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from .paged_attention import _pallas_mode, window_pages, write_page_rows
from .ragged_paged_attention import _F32_DOT, LANES, NEG_INF, _gather_kv

# what the call may hold in VMEM (a v5e has 128 MiB): at H 128, L 512 and
# a chunk of 16 the q and output blocks (two buffers each), the float32
# accumulator and softmax state and two pages come to ~17 MiB
_VMEM_LIMIT = 64 * 1024 * 1024


def latent_cache_write(pages, entry, page_indices, positions, num_valid):
    """Write entries ``[B, S, L + R]`` (rows for positions ``positions[b]
    ..``, ``num_valid[b]`` of them real) into the pool ``[1, P, ps + R,
    L]``; in place where the pool is donated.

    A page is 512 tokens here and a chunk writes a handful of them, so
    nothing moves whole pages (a page gathered and stored back for every
    lane would be ~75 MB a layer a step, most of it untouched): the pool
    is seen as tiles of ``tile`` rows (a bitcast: the pool's own row
    tiles), a token's latent goes into its row of its tile
    (``write_page_rows`` over tiles), and its rope key into its column of
    the ``R`` rows under the page (those rows of the touched pages
    gathered, the columns merged, stored back)."""
    _one, P, rows, L = pages.shape
    B, S = int(entry.shape[0]), int(entry.shape[1])
    ps, R = L, rows - L
    tile = next(t for t in (16, 8, 4, 2, 1) if ps % t == 0 and R % t == 0)
    per = rows // tile                                  # tiles a page
    page_indices = page_indices.astype(jnp.int32)
    tiles = pages.reshape(1, P * per, tile, L)
    # the latents: the tiles of a page's first ps rows, as pages of
    # `tile` tokens through a table of tiles
    tile_table = (page_indices[:, :, None] * per + jnp.arange(
        ps // tile, dtype=jnp.int32)).reshape(B, -1)
    tiles = write_page_rows(
        tiles, entry[None, :, :, :L],
        *window_pages(tile_table, positions, num_valid, S, tile))
    # the rope keys: a column of the R rows under each touched page
    page, src, ok = window_pages(page_indices, positions, num_valid, S, ps)
    T = page.shape[1]
    flat = tiles.reshape(P * per, tile, L)
    idx = (page[:, :, None] * per + ps // tile
           + jnp.arange(R // tile, dtype=jnp.int32)).reshape(-1)
    held = flat[idx].reshape(B, T, R, ps)
    new = jnp.take_along_axis(entry[:, :, L:].astype(pages.dtype),
                              src[:, :, None], axis=1)      # [B, T*ps, R]
    new = jnp.swapaxes(new.reshape(B, T, ps, R), -1, -2)
    merged = jnp.where(ok[:, :, None, :], new, held)
    return flat.at[idx].set(merged.reshape(-1, tile, L)).reshape(pages.shape)


def _reference_latent(q_lat, q_pe, pages, start_pos, num_valid,
                      page_indices, sm_scale: float):
    """The jnp oracle: gather each lane's pages, causal mask, float32
    softmax. Rows j >= num_valid[b] are zero."""
    B, C, H, L = q_lat.shape
    win = _gather_kv(pages, None, page_indices, int(pages.shape[3]))[:, 0]
    K = win.shape[1]
    f32 = jnp.float32
    s = (jnp.einsum("bchl,bkl->bchk", q_lat.astype(f32), win[..., :L],
                    precision=_F32_DOT)
         + jnp.einsum("bchr,bkr->bchk", q_pe.astype(f32), win[..., L:],
                      precision=_F32_DOT)) * sm_scale
    qpos = start_pos[:, None] + jnp.arange(C, dtype=jnp.int32)[None, :]
    live = jnp.arange(K, dtype=jnp.int32)[None, None, :] <= qpos[:, :, None]
    p = jax.nn.softmax(jnp.where(live[:, :, None], s, NEG_INF), axis=-1)
    o = jnp.einsum("bchk,bkl->bchl", p, win[..., :L], precision=_F32_DOT)
    row_ok = jnp.arange(C, dtype=jnp.int32)[None, :] < num_valid[:, None]
    return jnp.where(row_ok[..., None, None], o, 0.0).astype(q_lat.dtype)


def _make_kernel(ps: int, sm_scale: float, operand, precision):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def kernel(tables_ref, starts_ref, nvalid_ref, ql_ref, qp_ref, pool,
               o_ref, buf, sem, acc_ref, m_ref, l_ref):
        b = pl.program_id(0)
        start, nv = starts_ref[b], nvalid_ref[b]
        npages = pl.cdiv(jnp.where(nv > 0, start + nv, 0), ps)
        heads = acc_ref.shape[1]

        def copy(i, slot):
            return pltpu.make_async_copy(pool.at[0, tables_ref[b, i]],
                                         buf.at[slot], sem.at[slot])

        @pl.when(npages > 0)
        def _first():  # noqa: ANN202
            copy(0, 0).start()

        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

        def page(i, carry):
            slot = i % 2

            @pl.when(i + 1 < npages)
            def _ahead():  # noqa: ANN202
                copy(i + 1, 1 - slot).start()

            copy(i, slot).wait()
            ckv = buf[slot, :ps, :].astype(operand)           # [ps, L]
            kpe = buf[slot, ps:, :].astype(operand)           # [R, ps]
            key = i * ps + jax.lax.broadcasted_iota(jnp.int32, (1, ps), 1)

            def token(j, c):
                # one token's heads, one [H, L] tile, against the page
                s = jax.lax.dot_general(
                    ql_ref[0, j].astype(operand), ckv,
                    (((1,), (1,)), ((), ())), precision=precision,
                    preferred_element_type=jnp.float32)
                s = s + jax.lax.dot_general(
                    qp_ref[0, j].astype(operand), kpe,
                    (((1,), (0,)), ((), ())), precision=precision,
                    preferred_element_type=jnp.float32)
                s = jnp.where(key <= start + j, s * sm_scale, NEG_INF)
                m_prev = m_ref[j][:, :1]
                m_next = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
                alpha = jnp.exp(m_prev - m_next)
                p = jnp.exp(s - m_next)
                l_ref[j] = jnp.broadcast_to(
                    alpha * l_ref[j][:, :1] + p.sum(axis=-1, keepdims=True),
                    (heads, LANES))
                m_ref[j] = jnp.broadcast_to(m_next, (heads, LANES))
                acc_ref[j] = acc_ref[j] * alpha + jax.lax.dot_general(
                    p.astype(operand), ckv, (((1,), (0,)), ((), ())),
                    precision=precision, preferred_element_type=jnp.float32)
                return c

            jax.lax.fori_loop(0, nv, token, None)
            return carry

        jax.lax.fori_loop(0, npages, page, None)
        denom = l_ref[:, :, :1]
        denom = jnp.where(denom == 0.0, 1.0, denom)           # padding -> 0
        o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)

    return kernel


# jitted: a step's layers share one trace and lowering of the kernel
@functools.partial(jax.jit, static_argnames=("sm_scale", "interpret"))
def _latent_pallas(q_lat, q_pe, pages, start_pos, num_valid, page_indices,
                   sm_scale: float, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, C, H, L = q_lat.shape
    R = q_pe.shape[-1]
    ps = int(pages.shape[3])
    operand = pages.dtype
    precision = _F32_DOT if operand == jnp.float32 else None
    block = lambda w: pl.BlockSpec(  # noqa: E731
        (1, C, H, w), lambda b, *refs: (b, 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B,),
        in_specs=[block(L), block(R), pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=block(L),
        scratch_shapes=[
            pltpu.VMEM((2, ps + R, L), pages.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.VMEM((C, H, L), jnp.float32),              # acc
            pltpu.VMEM((C, H, LANES), jnp.float32),          # m
            pltpu.VMEM((C, H, LANES), jnp.float32),          # l
        ],
    )
    return pl.pallas_call(
        _make_kernel(ps, sm_scale, operand, precision),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, C, H, L), q_lat.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="latent_paged_attention",
    )(page_indices, start_pos, num_valid, q_lat, q_pe, pages)


def latent_paged_attention(q_lat, q_pe, pages, start_pos, num_valid,
                           page_indices, *, sm_scale: float):
    """Attend a ragged batch of new-token chunks over latent pages.

    q_lat:        [B, C, H, L] — the absorbed queries (q_nope W_uk^T)
    q_pe:         [B, C, H, R] — the rotated rope queries
    pages:        [1, P, ps + R, L], ps == L (``kvcache.key_page_shape``
                  of keys L + R wide on values L wide)
    start_pos:    [B] int32 — absolute position of row 0 of each chunk
    num_valid:    [B] int32 — real rows of each chunk (0: idle lane)
    page_indices: [B, maxp] int32 block tables

    Returns the context of the latents ``softmax(s) c_kv`` [B, C, H, L]
    in q_lat's type; rows j >= num_valid[b] are zeros. Query j attends
    keys 0 .. start_pos[b] + j (the chunk's own entries are written by
    ``latent_cache_write`` before)."""
    start_pos = start_pos.astype(jnp.int32)
    num_valid = num_valid.astype(jnp.int32)
    page_indices = page_indices.astype(jnp.int32)
    mode = _pallas_mode()
    if mode is not None:
        return _latent_pallas(q_lat, q_pe, pages, start_pos, num_valid,
                              page_indices, float(sm_scale),
                              interpret=mode == "interpret")
    return _reference_latent(q_lat, q_pe, pages, start_pos, num_valid,
                             page_indices, float(sm_scale))


def _split_heads(q, kv_b, num_heads: int, v_dim: int):
    """q [.., H * (N + R)] -> (q_nope, q_pe) [.., H, N] / [.., H, R];
    kv_b [L, H * (N + v_dim)] -> (W_uk, W_uv) [L, H, N] / [L, H, v_dim]."""
    L = kv_b.shape[0]
    w = kv_b.reshape(L, num_heads, -1)
    n = w.shape[-1] - v_dim
    q = q.reshape(q.shape[:-1] + (num_heads, -1))
    return q[..., :n], q[..., n:], w[..., :n], w[..., n:]


def absorbed_latent_attention(q, pages, kv_b, start_pos, num_valid,
                              page_indices, num_heads: int, v_dim: int):
    """The serving step's latent attention: q [B, C, H * (N + R)] (rope
    part rotated) over the pool through ``kv_b`` [L, H * (N + v_dim)]
    -> [B, C, H * v_dim] float32. The two weight products round their
    operand to the weight's type and accumulate in float32; the kernel
    takes its queries in the pages' type."""
    q_nope, q_pe, w_uk, w_uv = _split_heads(q, kv_b, num_heads, v_dim)
    scale = 1.0 / math.sqrt(q_nope.shape[-1] + q_pe.shape[-1])
    q_lat = jnp.einsum("bchn,lhn->bchl", q_nope.astype(kv_b.dtype), w_uk,
                       preferred_element_type=jnp.float32)
    ctx = latent_paged_attention(
        q_lat.astype(pages.dtype), q_pe.astype(pages.dtype), pages,
        start_pos, num_valid, page_indices, sm_scale=scale)
    o = jnp.einsum("bchl,lhv->bchv", ctx.astype(kv_b.dtype), w_uv,
                   preferred_element_type=jnp.float32)
    return o.reshape(o.shape[:2] + (-1,))


def expanded_latent_attention(q, entry, kv_b, num_heads: int, v_dim: int):
    """The published form over whole sequences: q [B, T, H * (N + R)],
    entry [B, T, L + R] (c_kv, k_pe) -> [B, T, H * v_dim] float32: keys
    and values expanded a head, causal softmax at 1 / sqrt(N + R)."""
    from ..ops.decoder import causal_attention, dot_stored

    q_nope, q_pe, _w_uk, _w_uv = _split_heads(q, kv_b, num_heads, v_dim)
    B, T, L = entry.shape[0], entry.shape[1], kv_b.shape[0]
    kv = dot_stored(entry[..., :L], kv_b).reshape(B, T, num_heads, -1)
    n = q_nope.shape[-1]
    k_pe = jnp.broadcast_to(entry[:, :, None, L:].astype(jnp.float32),
                            (B, T, num_heads, entry.shape[-1] - L))
    k = jnp.concatenate([kv[..., :n], k_pe], axis=-1)
    q = jnp.concatenate([q_nope, q_pe], axis=-1)
    return causal_attention(q, k, kv[..., n:]).reshape(B, T, -1)


# -- program-level layers ----------------------------------------------------


def latent_cache_write_layer(pages_var, entry_var, tables_var, positions_var,
                             num_valid_var):
    """Emit ``latent_cache_write``: the entries [B, S, L + R] into the
    pool Variable it reads, rewritten in place as donated state (the
    ``ParamOut = Param`` convention of ``kv_cache_write``)."""
    from ..layer_helper import LayerHelper

    LayerHelper("latent_cache_write").append_op(
        type="latent_cache_write",
        inputs={"Pages": [pages_var], "Entry": [entry_var],
                "BlockTables": [tables_var], "Positions": [positions_var],
                "NumValid": [num_valid_var]},
        outputs={"OutPages": [pages_var]})
    return pages_var


def _attention_layer(op_type, inputs, q_var, num_heads: int, v_dim: int):
    from ..layer_helper import LayerHelper
    from ..layers.nn import _out

    helper = LayerHelper(op_type)
    out = _out(helper, q_var, shape=tuple(q_var.shape[:-1])
               + (num_heads * v_dim,), dtype="float32")
    helper.append_op(type=op_type, inputs=inputs, outputs={"Out": [out]},
                     attrs={"num_heads": int(num_heads),
                            "v_dim": int(v_dim)})
    return out


def latent_paged_attention_layer(q_var, pages_var, kv_b_var, tables_var,
                                 positions_var, num_valid_var,
                                 num_heads: int, v_dim: int):
    """Emit ``latent_paged_attention`` (``absorbed_latent_attention``):
    Q [B, C, H * (N + R)] over the latent pool -> [B, C, H * v_dim]."""
    return _attention_layer(
        "latent_paged_attention",
        {"Q": [q_var], "Pages": [pages_var], "KvB": [kv_b_var],
         "BlockTables": [tables_var], "Positions": [positions_var],
         "NumValid": [num_valid_var]}, q_var, num_heads, v_dim)


def latent_attention_layer(q_var, entry_var, kv_b_var, num_heads: int,
                           v_dim: int):
    """Emit ``latent_attention`` (``expanded_latent_attention``): whole
    sequences, what a full forward pass uses."""
    return _attention_layer(
        "latent_attention", {"Q": [q_var], "Entry": [entry_var],
                             "KvB": [kv_b_var]}, q_var, num_heads, v_dim)


# -- op registration ---------------------------------------------------------
from ..core.registry import register_op  # noqa: E402


@register_op("latent_cache_write",
             inputs=("Pages", "Entry", "BlockTables", "Positions",
                     "NumValid"),
             outputs=("OutPages",),
             no_grad=("BlockTables", "Positions", "NumValid"),
             stop_gradient=True)
def _latent_cache_write_op(ctx, op, ins):
    return {"OutPages": [latent_cache_write(
        ins["Pages"][0], ins["Entry"][0], ins["BlockTables"][0],
        ins["Positions"][0], ins["NumValid"][0])]}


@register_op("latent_paged_attention",
             inputs=("Q", "Pages", "KvB", "BlockTables", "Positions",
                     "NumValid"),
             outputs=("Out",),
             no_grad=("BlockTables", "Positions", "NumValid"),
             stop_gradient=True)
def _latent_paged_attention_op(ctx, op, ins):
    return {"Out": [absorbed_latent_attention(
        ins["Q"][0], ins["Pages"][0], ins["KvB"][0], ins["Positions"][0],
        ins["NumValid"][0], ins["BlockTables"][0],
        int(op.attrs["num_heads"]), int(op.attrs["v_dim"]))]}


@register_op("latent_attention", inputs=("Q", "Entry", "KvB"),
             outputs=("Out",), stop_gradient=True)
def _latent_attention_op(ctx, op, ins):
    return {"Out": [expanded_latent_attention(
        ins["Q"][0], ins["Entry"][0], ins["KvB"][0],
        int(op.attrs["num_heads"]), int(op.attrs["v_dim"]))]}
