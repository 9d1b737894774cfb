"""Ragged paged attention: ONE kernel for mixed prefill + decode rows.

The two-lane GenerationEngine paid padding waste twice — a prefill
executable padded to the seq bucket and a decode executable whose
fixed lanes idle — and a two-executable step loop. Ragged Paged
Attention (arXiv:2604.15464, PAPERS.md [1]) collapses both into one
batch: each row of the ragged batch is a CHUNK of new tokens for one
sequence — a prefill chunk of up to `chunk` tokens, a single decode
token, a decode token plus k speculative draft tokens, or nothing at
all (an idle lane, num_valid = 0) — and one kernel attends every
chunk over its sequence's paged K/V through the block tables.

Semantics (the contract tests/test_ragged.py diffs against a dense
oracle): query j of row b sits at absolute position start_pos[b] + j
and attends keys 0 .. start_pos[b] + j of its sequence — full prefix
out of the page pool plus causal attention within the chunk (whose
K/V the step's kv_cache_write has already scattered into the pool
before this op runs). Rows j >= num_valid[b] and whole rows with
num_valid[b] == 0 are DEFINED as zeros — never NaN, so idle lanes and
batch padding can ride the same executable for free.

Three ops, all registered (proglint PTL030/PTL020-022 first-class,
no lint_suppress anywhere):

  ragged_paged_attention    Q [B, C, H*D] x pages -> Out [B, C, H*D]
  ragged_paged_attention_q  same, over int8 pages + per-(head, slot)
                            fp32 scales (the quantized-KV serving path)
  kv_cache_write_q          quantized twin of kv_cache_write: new K/V
                            rows are blockwise-int8 quantized (one
                            scale per [head_dim] row — the
                            kernels/quant.py EQuARX machinery) on the
                            way into the pool, roughly quadrupling the
                            tokens a byte budget holds (junk-page
                            routing for invalid rows preserved)

Routing matches every other fused kernel: a Pallas/Mosaic lowering on
real TPU or under PADDLE_TPU_FORCE_PALLAS=1 (tools/aot_check.py
validates it against the v5e compiler: rows ragged_attention_{f32,
bf16,int8kv} + ragged_kv_write_int8, runnable under
PT_AOT_ONLY=ragged), the pure-JAX reference below everywhere else —
including PADDLE_TPU_KERNEL_INTERPRET=1, which runs the real kernel
body in interpreter mode. The reference is the numerics oracle AND the
CPU-CI execution path.

The kernel's loop: the grid is one step a lane, every head at once,
and nothing in it is as wide as the block tables. Inside a grid step
the kernel walks the lane's LIVE keys only, in blocks of several pages:
the trip count is cdiv(start_pos + num_valid, block keys), read from
the scalar-prefetched arrays (0 for an idle lane, which writes zeros),
so a lane holding 13 pages of a 128-entry table does 13 pages of work.
The pools stay in HBM; for each page of a block one strided async copy
takes that page of every KV head ([KVH, ps, D] out of [KVH, P, ps, D])
into one of two VMEM buffers, so block i + 1 is in flight while block i
is multiplied: a KV head's query rows (group * chunk of them) against
[block keys, D], batched over the KV heads. The block's size comes from
the static shapes and a VMEM budget (_block_pages: 8 float32 pages of
16 x 128 for 16 heads); only live pages are copied, so table entries
past them (junk page 0) are never read, and the dead tail of a lane's
last block is masked (stale K) or zeroed (stale V) in VMEM. int8
pages: the per-key scales are gathered along each lane's table into
block order outside the kernel (keys on the lane axis) and multiply
the scores and the probabilities, q.(k * s) = (q.k) * s. Head dims
that are not whole lane tiles are zero-padded (a copy of the pool a
call): 128 is the fast case.

Precision on the chip: the kernel computes in float32 whatever the
pages hold — operands are up-cast, the online-softmax accumulators are
float32, and the two matmuls (q.k^T, p.v) ask Mosaic for
Precision.HIGHEST (float32 contraction, several MXU passes) instead of
the TPU default, which rounds float32 operands to bf16 for one pass.
The first chip run measured that default at 2.5e-3 relative against a
"highest"-precision reference; chip_smoke.py's kernel phase holds this
kernel to the float32 bound (2e-5), so a bf16 rounding anywhere in the
attend path fails there. Over bfloat16 pages the keys and values are
exact bfloat16, so three of HIGHEST's six passes (those with their
lower parts) are zero: the kernel cuts q and the probabilities into
three bfloat16 parts (hi + mid + lo = the float32 value), stacks them
as rows and multiplies them in ONE pass over the stored keys / values
with float32 accumulation, then sums the three row blocks: the same
terms as HIGHEST, a sixth of its weight loads into the MXU.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

from .paged_attention import window_pages, write_page_rows
from .quant import blockwise_dequantize, blockwise_quantize

NEG_INF = -1e30
LANES = 128  # TPU minor tile; m/l scratch is lane-replicated
# the TPU default rounds float32 dot operands to bf16 (module docstring)
_F32_DOT = jax.lax.Precision.HIGHEST


def _pallas_mode() -> Optional[str]:
    # same routing contract as flash/paged attention: interpret env
    # wins, then real TPU / forced-Pallas AOT validation, else None
    from .flash_attention import _pallas_mode as _fa_mode

    return _fa_mode()


# -- reference (the oracle + the CPU-CI path) --------------------------------


def _join_split_keys(win, ps: int):
    """Pages of the split key layout ``[.., ps + d_hi, d_lo]`` (module
    docstring, "Keys wider than their values") -> ``[.., ps, d_lo +
    d_hi]`` rows of whole keys."""
    return jnp.concatenate(
        [win[..., :ps, :], jnp.swapaxes(win[..., ps:, :], -1, -2)], axis=-1)


def _gather_kv(pages, scales, page_indices, ps=None):
    """[KVH, P, ps, D] pages -> [B, KVH, maxp*ps, D] fp32 windows per
    the block tables, dequantizing int8 pages against their
    per-(head, slot) scales on the way out. ``ps`` below the pages'
    row count: the pages are split-layout keys."""
    B, maxp = page_indices.shape
    KVH, _P, rows, _ = pages.shape
    ps = rows if ps is None else ps
    win = jnp.transpose(pages[:, page_indices], (1, 0, 2, 3, 4))
    win = win.astype(jnp.float32)
    if rows != ps:
        win = _join_split_keys(win, ps)
    win = win.reshape(B, KVH, maxp * ps, win.shape[-1])
    if scales is not None:
        s = jnp.transpose(scales[:, page_indices], (1, 0, 2, 3))
        win = blockwise_dequantize(win, s.reshape(B, KVH, maxp * ps))
    return win


def _first_page(start_pos, num_valid, window: int, ps: int):
    """The first page a lane's window overlaps: the page of the oldest
    key its first row attends (0 for an idle lane)."""
    lo = jnp.maximum(start_pos - window + 1, 0)
    return jnp.where(num_valid > 0, lo, 0) // ps


def _reference_ragged(q, k_pages, v_pages, start_pos, num_valid,
                      page_indices, sm_scale: float, k_scales, v_scales,
                      window=None, sink=None):
    """Pure-JAX oracle: gather each row's pages into a contiguous
    window, apply the ragged causal mask (key_pos <= start + j, and
    key_pos > start + j - window under a window), plain fp32 softmax
    (with the head's sink logit in the denominator, if given).
    O(B * C * maxp * ps) HBM: exactly right for CPU CI and the
    correctness tests."""
    B, C, H, D = q.shape
    KVH = k_pages.shape[0]
    maxp, ps = page_indices.shape[1], v_pages.shape[2]
    K = maxp * ps
    kpos = jnp.arange(K, dtype=jnp.int32)[None, :]
    if window is not None:
        # the table is a ring: logical page p lies at entry p % maxp.
        # Walk the maxp logical pages from the window's first one
        logical = (_first_page(start_pos, num_valid, window, ps)[:, None]
                   + jnp.arange(maxp, dtype=jnp.int32)[None, :])
        page_indices = jnp.take_along_axis(page_indices, logical % maxp, 1)
        kpos = (logical[:, :, None] * ps + jnp.arange(
            ps, dtype=jnp.int32)[None, None, :]).reshape(B, K)
    k = _gather_kv(k_pages, k_scales, page_indices, ps)
    v = _gather_kv(v_pages, v_scales, page_indices)
    if KVH != H:  # grouped-query: repeat KV heads over the query groups
        k = jnp.repeat(k, H // KVH, axis=1)
        v = jnp.repeat(v, H // KVH, axis=1)
    s = jnp.einsum("bchd,bhkd->bhck", q.astype(jnp.float32) * sm_scale, k)
    qpos = start_pos[:, None] + jnp.arange(C, dtype=jnp.int32)[None, :]
    mask = kpos[:, None, :] <= qpos[:, :, None]              # [B, C, K]
    if window is not None:
        mask = mask & (kpos[:, None, :] > qpos[:, :, None] - window)
    s = jnp.where(mask[:, None], s, NEG_INF)
    if sink is None:
        p = jax.nn.softmax(s, axis=-1)
    else:
        # the sink: one more logit a head, in the denominator only
        snk = jnp.broadcast_to(
            sink.astype(jnp.float32)[None, :, None, None], (B, H, C, 1))
        p = jax.nn.softmax(jnp.concatenate([s, snk], -1), axis=-1)[..., :K]
    o = jnp.einsum("bhck,bhkd->bchd", p, v)
    # invalid rows (j >= num_valid, idle lanes with num_valid == 0)
    # are DEFINED zero — all-masked softmax NaN must never escape
    row_ok = (jnp.arange(C, dtype=jnp.int32)[None, :]
              < num_valid[:, None])                          # [B, C]
    return jnp.where(row_ok[..., None, None], o, 0.0).astype(q.dtype)


# -- Pallas lowering ---------------------------------------------------------

# what the K and V blocks of a grid step may hold in VMEM: two buffers
# each (float32, 16 heads x 128 keys x 128 = 1 MiB a buffer)
_BLOCK_VMEM_BYTES = 4 << 20


def _block_pages(kvh: int, ps: int, d: int, itemsize: int, rows: int,
                 maxp: int, page_elems: Optional[int] = None) -> int:
    """Pages of one K/V block, from the static shapes alone: the
    double-buffered K and V blocks fill _BLOCK_VMEM_BYTES
    (``page_elems`` values a page and KV head, K and V together: 2 * ps
    * d unless keys and values differ), the [kvh, rows, keys] float32
    score tile stays under a quarter of it, and pages narrower than a
    lane tile come in whole tiles of keys."""
    page_elems = page_elems or 2 * ps * d
    bp = _BLOCK_VMEM_BYTES // (2 * kvh * page_elems * itemsize)
    bp = min(bp, _BLOCK_VMEM_BYTES // 4 // (kvh * rows * 4 * ps), maxp)
    tile = max(1, LANES // ps)
    return max(1, bp - bp % tile if bp >= tile else bp)


def _block_scales(scales, page_indices, npages, bp: int):
    """[KVH, P, ps] scale planes -> [B, blocks, KVH, bp * ps]: each
    lane's scales in the order its blocks walk the keys, keys on the
    lane axis (where the kernel scales scores and probabilities; a
    [.., ps, 1] column cannot be sliced out of a tiled pool). Columns
    past a lane's live pages repeat its last live page: the kernel
    multiplies them into masked scores and zero probabilities, so
    they must be finite, which a dead table entry's need not be."""
    KVH, _P, ps = scales.shape
    B, maxp = page_indices.shape
    blocks = -(-maxp // bp)
    col = jnp.minimum(jnp.arange(blocks * bp, dtype=jnp.int32)[None, :],
                      jnp.maximum(npages - 1, 0)[:, None])
    pages = jnp.take_along_axis(page_indices, col, axis=1)
    return jnp.transpose(
        scales[:, pages].reshape(KVH, B, blocks, bp * ps), (1, 2, 0, 3))


def lean_decode_rows(group: int, chunk: int, stored_products: bool) -> int:
    """The rows a lane multiplies on the ``lean_decode`` branch: its
    first token's ``group`` rows, to whole sublane tiles of the rows as
    the kernel cuts them (8 float32 rows; 16 packed bfloat16 ones where
    the products round to bfloat16), at most the chunk's. A lane takes
    the branch when ``num_valid * group`` fits."""
    tile = 16 if stored_products else 8
    return min(-(-group // tile) * tile, group * -(-chunk // 8) * 8)


def _make_ragged_kernel(C: int, group: int, ps: int, bp: int,
                        sm_scale: float, quantized: bool, window=None,
                        ring: int = 0, sinks: bool = False, d_lo: int = 0,
                        d_hi: int = 0, stored_products: bool = False,
                        lean_rows: int = 0, parts: bool = False):
    """One grid step = one lane, every head: walk the lane's live keys
    in blocks of ``bp`` pages. ``C`` is the padded chunk; a KV head's
    ``group`` query heads ride as group * C rows of one matmul.
    ``window``: the walk starts at the first page the window overlaps,
    and the table (``ring`` entries) is a ring. ``sinks``: a per-row
    logit starts the softmax's denominator. ``d_hi``: the K pages are
    in the split layout, ``d_lo`` wide. ``stored_products``: both
    products round their operands to bfloat16, one MXU pass, where the
    default is a float32 contraction. ``lean_rows``: the
    rows are chunk-major (row j * group + g), so a lane's valid rows
    come first, and a lane whose valid rows fit the first ``lean_rows``
    (a decode row: one token of ``group`` heads) multiplies only those.
    ``parts``: the pages are bfloat16 and the products float32: q and
    the probabilities go in as three bfloat16 parts stacked as rows,
    one MXU pass over the stored keys and values (module docstring)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bk = bp * ps
    precision = None if stored_products else _F32_DOT

    def kernel(*refs):
        it = iter(refs)
        tables_ref, starts_ref, nvalid_ref = next(it), next(it), next(it)
        q_ref = next(it)
        pools = next(it), next(it)       # K and V, whole, in HBM
        ks_ref = next(it) if quantized else None
        vs_ref = next(it) if quantized else None
        sink_ref = next(it) if sinks else None
        o_ref = next(it)
        bufs = next(it), next(it)        # their two-slot VMEM blocks
        sem = next(it)
        acc_ref, m_ref, l_ref = next(it), next(it), next(it)

        b = pl.program_id(0)
        start = starts_ref[b]
        # keys this lane attends; an idle lane walks nothing
        total = jnp.where(nvalid_ref[b] > 0, start + nvalid_ref[b], 0)
        if window is None:
            first = 0                    # the walk's first page
        else:
            first = jnp.where(nvalid_ref[b] > 0,
                              jnp.maximum(start - window + 1, 0), 0) // ps
        if window is None:
            npages, nblocks = pl.cdiv(total, ps), pl.cdiv(total, bk)
        else:
            npages = pl.cdiv(total, ps) - first
            nblocks = pl.cdiv(total - first * ps, bk)

        def entry(rel):
            # the table entry of the walk's rel-th page
            return rel if window is None else (first + rel) % ring

        def fetch(i, slot):
            # block i -> buffer `slot`: one strided copy a live page
            # takes that page of every KV head. Table entries past the
            # lane's live pages are never read.
            for j in range(bp):
                @pl.when(i * bp + j < npages)
                def live():  # noqa: ANN202
                    page = tables_ref[b, entry(i * bp + j)]
                    for pool, buf in zip(pools, bufs):
                        pltpu.make_async_copy(pool.at[:, page],
                                              buf.at[slot, :, j],
                                              sem.at[slot]).start()

        def wait(i, slot):
            # the last block's pages past the live ones hold whatever
            # the buffer held: stale K is masked with its scores, stale
            # V would meet a probability of 0 as 0 * NaN, so it is zeroed
            vbuf = bufs[1]
            for j in range(bp):
                @pl.when(i * bp + j < npages)
                def live():  # noqa: ANN202
                    for pool, buf in zip(pools, bufs):
                        pltpu.make_async_copy(pool.at[:, 0],
                                              buf.at[slot, :, j],
                                              sem.at[slot]).wait()

                @pl.when(i * bp + j >= npages)
                def dead():  # noqa: ANN202
                    vbuf[slot, :, j] = jnp.zeros(
                        (vbuf.shape[1],) + vbuf.shape[3:], vbuf.dtype)

        @pl.when(nblocks > 0)
        def first_block():  # noqa: ANN202
            fetch(0, 0)

        acc_ref[...] = jnp.zeros_like(acc_ref)
        if sinks:
            # the sink is a key of value zero: its logit starts the
            # running maximum, and the denominator at exp(0)
            m_ref[...] = sink_ref[...]
            l_ref[...] = jnp.ones_like(l_ref)
        else:
            m_ref[...] = jnp.full_like(m_ref, NEG_INF)
            l_ref[...] = jnp.zeros_like(l_ref)
        q = q_ref[0].astype(jnp.float32) * sm_scale       # [KVH, G*C, D]
        kvh = q.shape[0]
        operand = jnp.bfloat16 if stored_products else jnp.float32
        def positions(n):
            """(key index, query position) [n, bk] of the first n rows."""
            if lean_rows:
                # row j * group + g is query j of the g-th query head,
                # for any group (a lean tile may end inside a query)
                qpos = start + jax.lax.div(jax.lax.broadcasted_iota(
                    jnp.int32, (n, bk), 0), group)
            else:
                # row g * C + j of a KV head is query j of its g-th head
                qpos = start + jax.lax.broadcasted_iota(
                    jnp.int32, (group, C, bk), 1).reshape(group * C, bk)
            key = jax.lax.broadcasted_iota(jnp.int32, (n, bk), 1)
            if window is not None:
                key = key + first * ps   # the walk's first key
            return key, qpos

        # once a grid step, outside the walk's loop
        where = {n: positions(n)
                 for n in dict.fromkeys((group * C, lean_rows)) if n}

        def split3(x):
            """float32 [KVH, n, k] -> [KVH, 3n, k] bfloat16: three parts,
            stacked as rows, whose sum is x"""
            hi = x.astype(jnp.bfloat16).astype(jnp.float32)
            mid = (x - hi).astype(jnp.bfloat16).astype(jnp.float32)
            return jnp.concatenate([hi, mid, x - hi - mid], axis=1).astype(
                jnp.bfloat16)

        def fold(y, n):
            """[KVH, 3n, k] products of the three parts -> their sum"""
            return y[:, :n] + y[:, n:2 * n] + y[:, 2 * n:]

        q_parts = {n: split3(q[:, :n]) for n in where} if parts else {}

        def scores(q, kb):
            """q . k^T over one block's K pages [KVH, bp, rows, width]."""
            if not d_hi:
                return jax.lax.dot_general(
                    q, kb.astype(operand).reshape(kvh, bk, -1),
                    (((2,), (2,)), ((0,), (0,))), precision=precision,
                    preferred_element_type=jnp.float32)    # [KVH, G*C, bk]
            # split keys: a page is [ps, d_lo] rows of the keys' first
            # d_lo values over [d_hi, ps], their last d_hi transposed
            s = jax.lax.dot_general(
                q[:, :, :d_lo],
                kb[:, :, :ps].astype(operand).reshape(kvh, bk, d_lo),
                (((2,), (2,)), ((0,), (0,))), precision=precision,
                preferred_element_type=jnp.float32)
            q_hi = q[:, :, d_lo:d_lo + d_hi]
            return s + jnp.concatenate([jax.lax.dot_general(
                q_hi, kb[:, j, ps:].astype(operand),
                (((2,), (1,)), ((0,), (0,))), precision=precision,
                preferred_element_type=jnp.float32) for j in range(bp)],
                axis=-1)

        def update(i, slot, n):
            """Block i into the running softmax of the first ``n`` rows
            (all of them, or a decode lane's ``lean_rows``)."""
            top = slice(None) if n == group * C else slice(0, n)
            # (rows are cut in float32: whole 8-row tiles)
            if parts:
                s = fold(jax.lax.dot_general(
                    q_parts[n], bufs[0][slot].reshape(kvh, bk, -1),
                    (((2,), (2,)), ((0,), (0,))),
                    preferred_element_type=jnp.float32), n)
                v = bufs[1][slot].reshape(kvh, bk, -1)
            else:
                s = scores(q[:, top].astype(operand), bufs[0][slot])
                v = bufs[1][slot].astype(operand).reshape(kvh, bk, -1)
            if quantized:
                # int8 pages: q.(k * scale) = (q.k) * scale, a key's
                # scale applied where keys lie on the lane axis
                s = s * ks_ref[0, i][:, None, :]
            key, qpos = where[n]
            live = i * bk + key <= qpos
            if window is not None:
                live = live & (i * bk + key > qpos - window)
            s = jnp.where(live[None], s, NEG_INF)
            m_prev = m_ref[:, top, :1]
            m_next = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_next)
            pexp = jnp.exp(s - m_next)
            l_ref[:, top] = jnp.broadcast_to(
                alpha * l_ref[:, top, :1] + pexp.sum(axis=-1, keepdims=True),
                (kvh, n, LANES))
            m_ref[:, top] = jnp.broadcast_to(m_next, (kvh, n, LANES))
            if quantized:
                pexp = pexp * vs_ref[0, i][:, None, :]
            if parts:
                acc_ref[:, top] = acc_ref[:, top] * alpha + fold(
                    jax.lax.dot_general(
                        split3(pexp), v, (((2,), (1,)), ((0,), (0,))),
                        preferred_element_type=jnp.float32), n)
            else:
                acc_ref[:, top] = acc_ref[:, top] * alpha + (
                    jax.lax.dot_general(
                        pexp.astype(operand), v, (((2,), (1,)), ((0,), (0,))),
                        precision=precision,
                        preferred_element_type=jnp.float32))

        def block(i, carry):
            slot = i % 2

            @pl.when(i + 1 < nblocks)
            def ahead():  # noqa: ANN202
                fetch(i + 1, 1 - slot)

            wait(i, slot)
            if lean_rows:
                lean = nvalid_ref[b] * group <= lean_rows
                pl.when(lean)(lambda: update(i, slot, lean_rows))
                pl.when(jnp.logical_not(lean))(
                    lambda: update(i, slot, group * C))
            else:
                update(i, slot, group * C)
            return carry

        jax.lax.fori_loop(0, nblocks, block, None)
        denom = l_ref[:, :, :1]
        denom = jnp.where(denom == 0.0, 1.0, denom)       # idle lane -> 0
        o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)

    return kernel


# jitted so that a step program's 24 layers, and every later trace of
# it, share one trace and one lowering of the kernel (unrolled over a
# block's pages, it costs 0.15 s to trace: seconds of set-up otherwise)
@functools.partial(jax.jit, static_argnames=(
    "sm_scale", "interpret", "window", "stored_products", "name",
    "lean_decode"))
def _ragged_pallas(q, k_pages, v_pages, start_pos, num_valid, page_indices,
                   sm_scale: float, k_scales, v_scales, interpret,
                   window=None, sink=None, stored_products=False,
                   name="ragged_paged_attention", lean_decode=False):
    """``interpret``: False on the chip, True for the Pallas interpreter
    (or a pltpu.InterpretParams for the TPU one, which is slower and
    starts every buffer as NaN)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, C, H, D0 = q.shape
    KVH, _P, ps, Dv0 = v_pages.shape
    quantized = k_scales is not None
    k_rows, k_w = k_pages.shape[2:]
    d_hi = k_rows - ps          # > 0: K pages in the split layout
    d_lo = k_w if d_hi else 0
    # sublane-align the chunk so the [C, D] scratch tiles cleanly, and
    # lane-align the head dim: a copy out of the pool moves whole lane
    # tiles. Zero columns change no score and are cut off the output;
    # the pad copies the pool, so head dims of whole tiles (128) are
    # the fast case, and keys wider than their values come in the split
    # layout, whose pages are whole tiles as they lie.
    Cp = -(-C // 8) * 8
    D = -(-D0 // LANES) * LANES
    group = H // KVH
    rows = group * Cp          # query head h = kv head h // group
    lean_rows = (lean_decode_rows(group, C, stored_products) if lean_decode
                 else 0)
    if lean_rows:
        # chunk-major rows: row j * group + g of a KV head
        qt = jnp.transpose(
            jnp.pad(q, ((0, 0), (0, Cp - C), (0, 0), (0, D - D0))).reshape(
                B, Cp, KVH, group, D), (0, 2, 1, 3, 4))
    else:
        qt = jnp.pad(jnp.transpose(q, (0, 2, 1, 3)),      # [B, H, C, D]
                     ((0, 0), (0, 0), (0, Cp - C), (0, D - D0)))
    if not d_hi and D != D0:
        k_pages, v_pages = (jnp.pad(p, ((0, 0),) * 3 + ((0, D - D0),))
                            for p in (k_pages, v_pages))
    k_w, v_w = k_pages.shape[3], v_pages.shape[3]
    bp = _block_pages(KVH, ps, v_w, k_pages.dtype.itemsize, rows,
                      page_indices.shape[1], k_rows * k_w + ps * v_w)
    q_block = pl.BlockSpec((1, KVH, rows, D), lambda b, *refs: (b, 0, 0, 0))
    o_block = pl.BlockSpec((1, KVH, rows, v_w), lambda b, *refs: (b, 0, 0, 0))
    in_specs = [q_block] + [pl.BlockSpec(memory_space=pl.ANY)] * 2
    args = [qt.reshape(B, KVH, rows, D), k_pages, v_pages]
    if quantized:
        npages = -(-jnp.where(num_valid > 0, start_pos + num_valid, 0) // ps)
        args += [_block_scales(sc, page_indices, npages, bp)
                 for sc in (k_scales, v_scales)]
        in_specs += [pl.BlockSpec((1,) + args[-1].shape[1:],
                                  lambda b, *refs: (b, 0, 0, 0))] * 2
    if sink is not None:
        # a row's sink is its query head's (h * group + g of KV head h)
        by_head = sink.astype(jnp.float32).reshape(KVH, group)
        args.append(jnp.broadcast_to(
            by_head[:, None, :, None] if lean_rows
            else by_head[:, :, None, None],
            (KVH, Cp, group, LANES) if lean_rows
            else (KVH, group, Cp, LANES)).reshape(KVH, rows, LANES))
        in_specs.append(pl.BlockSpec((KVH, rows, LANES),
                                     lambda b, *refs: (0, 0, 0)))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B,),
        in_specs=in_specs,
        out_specs=o_block,
        scratch_shapes=[
            pltpu.VMEM((2, KVH, bp, k_rows, k_w), k_pages.dtype),
            pltpu.VMEM((2, KVH, bp, ps, v_w), v_pages.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.VMEM((KVH, rows, v_w), jnp.float32),     # acc
            pltpu.VMEM((KVH, rows, LANES), jnp.float32),   # m
            pltpu.VMEM((KVH, rows, LANES), jnp.float32),   # l
        ],
    )
    kernel = _make_ragged_kernel(
        Cp, group, ps, bp, sm_scale, quantized, window=window,
        ring=page_indices.shape[1], sinks=sink is not None, d_lo=d_lo,
        d_hi=d_hi, stored_products=stored_products, lean_rows=lean_rows,
        parts=(not (stored_products or quantized or d_hi)
               and k_pages.dtype == v_pages.dtype == jnp.bfloat16))
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KVH, rows, v_w), q.dtype),
        interpret=interpret,
        name=name,
    )(page_indices, start_pos, num_valid, *args)
    if lean_rows:
        out = jnp.transpose(out.reshape(B, KVH, Cp, group, v_w),
                            (0, 2, 1, 3, 4)).reshape(
                                B, Cp, H, v_w)[:, :C, :, :Dv0]
    else:
        out = jnp.transpose(out.reshape(B, H, Cp, v_w)[:, :, :C, :Dv0],
                            (0, 2, 1, 3))
    row_ok = (jnp.arange(C, dtype=jnp.int32)[None, :]
              < num_valid[:, None])
    return jnp.where(row_ok[..., None, None], out, 0.0)   # [B, C, H, Dv]


# -- public entry ------------------------------------------------------------


def ragged_paged_attention(q, k_pages, v_pages, start_pos, num_valid,
                           page_indices, *, sm_scale: Optional[float] = None,
                           k_scales=None, v_scales=None,
                           window: Optional[int] = None, sink=None,
                           stored_products: bool = False,
                           name: str = "ragged_paged_attention",
                           lean_decode: bool = False):
    """Attend a ragged batch of new-token chunks over paged K/V.

    q:            [B, C, H, D] — up to C new tokens per sequence
                  (prefill chunk / decode row / decode + draft tokens)
    k_pages/v_pages: [KVH, P, ps, D]; int8 with ``k_scales/v_scales``
                  [KVH, P, ps] fp32 for the quantized-KV pool. Values
                  may be narrower than keys ([KVH, P, ps, Dv]); keys
                  wider than their values come in the split layout
                  ``[KVH, P, ps + D - Dv, Dv]`` (``split_key_pages``)
    start_pos:    [B] int32 — absolute position of q[:, 0]
    num_valid:    [B] int32 — real rows in each chunk (0 = idle lane)
    page_indices: [B, maxp] int32 block tables
    window:       query i attends keys i - window < j <= i only, the
                  kernel walks only the pages that overlap a lane's
                  window, and the table is a RING: the page of keys
                  p * ps .. p * ps + ps - 1 is entry p % maxp
    sink:         [H] — a learned logit a head that joins the softmax's
                  denominator and no value
    stored_products: round the operands of both products to bfloat16
                  (one MXU pass; exact for bfloat16 pages) instead of a
                  float32 contraction
    name:         the kernel's name in a device trace
    lean_decode:  a lane whose valid rows fit ``lean_decode_rows`` (a
                  decode lane: one token's query heads) multiplies those
                  rows only, not the whole chunk's (the kernel orders
                  its rows chunk-major for it)

    Returns [B, C, H, Dv]; rows j >= num_valid[b] are zeros. Query j
    attends keys 0 .. start_pos[b] + j (the chunk's own K/V has been
    written by kv_cache_write before this op in every program). The
    softmax scale (default 1/sqrt(D)) applies to q identically on both
    paths, and the kernel's matmuls are float32 on the chip too
    (module docstring) unless ``stored_products``.
    """
    B, C, H, D = q.shape
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(D)
    start_pos = start_pos.astype(jnp.int32)
    num_valid = num_valid.astype(jnp.int32)
    page_indices = page_indices.astype(jnp.int32)
    if k_scales is not None and (window is not None or sink is not None
                                 or k_pages.shape[2] != v_pages.shape[2]):
        raise ValueError("int8 pages take no window, sink or split keys")
    mode = _pallas_mode()
    if mode is not None:
        # no retry on the reference: a kernel that fails to trace,
        # lower or compile raises
        return _ragged_pallas(q, k_pages, v_pages, start_pos, num_valid,
                              page_indices, scale, k_scales, v_scales,
                              interpret=(mode == "interpret"),
                              window=window, sink=sink,
                              stored_products=stored_products, name=name,
                              lean_decode=lean_decode)
    return _reference_ragged(q, k_pages, v_pages, start_pos, num_valid,
                             page_indices, scale, k_scales, v_scales,
                             window=window, sink=sink)


# -- keys wider than their values: the split page layout -----------------------


def split_key_pages(k, d_lo: int):
    """Key rows ``[.., ps, D]`` -> one split page ``[.., ps + D - d_lo,
    d_lo]``: the keys' first ``d_lo`` values as rows, their last ``D -
    d_lo`` transposed beneath (``ps == d_lo``, so that both parts are
    ``d_lo`` wide)."""
    return jnp.concatenate(
        [k[..., :d_lo], jnp.swapaxes(k[..., d_lo:], -1, -2)], axis=-2)


def split_kv_cache_write(k_pages, v_pages, k_new, v_new, page_indices,
                         positions, num_valid, ring: bool = False):
    """``kv_cache_write`` where K is wider than V: V pages ``[KVH, P,
    ps, Dv]`` as ever, K pages in the split layout ``[KVH, P, ps + D -
    Dv, Dv]``. ``ring``: the tables are rings (a window layer's)."""
    S, ps = int(k_new.shape[1]), int(v_pages.shape[2])
    page, src, ok = window_pages(page_indices, positions, num_valid, S, ps,
                                 ring=ring)
    v_pages = write_page_rows(v_pages, jnp.transpose(v_new, (2, 0, 1, 3)),
                              page, src, ok)
    if k_pages.shape[2] == ps:
        return write_page_rows(k_pages, jnp.transpose(k_new, (2, 0, 1, 3)),
                               page, src, ok), v_pages
    KVH, P, k_rows, d_lo = k_pages.shape
    if ps != d_lo:
        raise ValueError(f"split keys need page_size == {d_lo}, got {ps}")
    B, T = page.shape
    flat = k_pages.reshape(KVH * P, k_rows, d_lo)
    idx = (jnp.arange(KVH, dtype=jnp.int32)[:, None, None] * P
           + page[None]).reshape(-1)
    held = flat[idx].reshape(KVH, B, T, k_rows, d_lo)
    rows = jnp.take_along_axis(
        jnp.transpose(k_new, (2, 0, 1, 3)).astype(k_pages.dtype),
        src.reshape(1, B, T * ps, 1), axis=2).reshape(KVH, B, T, ps, -1)
    new = split_key_pages(rows, d_lo)
    # a slot is a row of the first part and a column of the second
    slot_ok = jnp.concatenate(
        [jnp.broadcast_to(ok[..., None], (B, T, ps, d_lo)),
         jnp.broadcast_to(ok[:, :, None, :], (B, T, k_rows - ps, ps))], 2)
    pages = jnp.where(slot_ok[None], new, held)
    return flat.at[idx].set(pages.reshape(-1, k_rows, d_lo)).reshape(
        k_pages.shape), v_pages


# -- quantized KV page write -------------------------------------------------


def quantized_kv_cache_write(k_pages, v_pages, k_scales, v_scales,
                             k_new, v_new, page_indices, positions,
                             num_valid):
    """int8 twin of paged_attention.kv_cache_write: each new [D] row
    quantizes to int8 with one fp32 max-abs/127 scale (the
    kernels/quant.py block unit with block = head_dim), then goes into
    the int8 pool + the [KVH, P, ps] scale planes page by page, exactly
    like the fp32 write (``write_page_rows``: in place when the pools
    are donated). Pure functional."""
    B, S, KVH, D = k_new.shape
    where = window_pages(page_indices, positions, num_valid, S,
                         int(k_pages.shape[2]))
    out = []
    for pool, scales, new in ((k_pages, k_scales, k_new),
                              (v_pages, v_scales, v_new)):
        # [KVH, B, S, D] rows -> blockwise int8 (one scale per [D] row)
        q, sc = blockwise_quantize(
            jnp.transpose(new, (2, 0, 1, 3)).astype(jnp.float32)
            .reshape(KVH * B * S, D))
        out.append((write_page_rows(pool, q.reshape(KVH, B, S, D), *where),
                    write_page_rows(scales, sc.reshape(KVH, B, S), *where)))
    (k_pages, k_scales), (v_pages, v_scales) = out
    return k_pages, v_pages, k_scales, v_scales


# -- program-level layers ----------------------------------------------------


def ragged_paged_attention_layer(q_var, k_pages_var, v_pages_var,
                                 tables_var, positions_var, num_valid_var,
                                 num_heads: int, k_scales_var=None,
                                 v_scales_var=None, window=None,
                                 sink_var=None, stored_products=False,
                                 kernel_name=None, lean_decode=False):
    """Emit the ragged attention op: Q [B, C, H*D] over the page pool.
    One op per decoder layer — the whole mixed prefill+decode step
    stays a single XLA executable. Passing the scale Variables selects
    the int8-pool variant. ``window``, ``sink_var`` [H],
    ``stored_products``, ``lean_decode`` and ``kernel_name`` as
    ``ragged_paged_attention`` takes them; the output is as wide as the V pages' rows."""
    from ..layer_helper import LayerHelper
    from ..layers.nn import _out

    quantized = k_scales_var is not None
    op = "ragged_paged_attention_q" if quantized else "ragged_paged_attention"
    helper = LayerHelper(op)
    shape = tuple(q_var.shape[:-1]) + (num_heads * int(v_pages_var.shape[-1]),)
    out = _out(helper, q_var, shape=shape)
    inputs = {"Q": [q_var], "KPages": [k_pages_var], "VPages": [v_pages_var],
              "BlockTables": [tables_var], "Positions": [positions_var],
              "NumValid": [num_valid_var]}
    attrs = {"num_heads": num_heads}
    if quantized:
        inputs["KScales"] = [k_scales_var]
        inputs["VScales"] = [v_scales_var]
    if sink_var is not None:
        inputs["Sink"] = [sink_var]
    if window is not None:
        attrs["window"] = int(window)
    if stored_products:
        attrs["stored_products"] = True
    if lean_decode:
        attrs["lean_decode"] = True
    if kernel_name is not None:
        attrs["kernel_name"] = str(kernel_name)
    helper.append_op(type=op, inputs=inputs, outputs={"Out": [out]},
                     attrs=attrs)
    return out


def split_kv_cache_write_layer(k_pages_var, v_pages_var, k_var, v_var,
                               tables_var, positions_var, num_valid_var,
                               num_kv_heads: int, ring: bool = False):
    """Emit ``kv_cache_write_split``: ``kv_cache_write_layer`` for keys
    wider than their values (K [B, S, KVH * D], V [B, S, KVH * Dv]; the
    K pool in the split layout when D > Dv) and for the ring tables of a
    window layer. The pools are rewritten state, as there."""
    from ..layer_helper import LayerHelper

    LayerHelper("kv_cache_write_split").append_op(
        type="kv_cache_write_split",
        inputs={"KPages": [k_pages_var], "VPages": [v_pages_var],
                "K": [k_var], "V": [v_var], "BlockTables": [tables_var],
                "Positions": [positions_var], "NumValid": [num_valid_var]},
        outputs={"OutKPages": [k_pages_var], "OutVPages": [v_pages_var]},
        attrs={"num_heads": num_kv_heads, "ring": bool(ring)},
    )
    return k_pages_var, v_pages_var


def quantized_kv_cache_write_layer(k_pages_var, v_pages_var, k_scales_var,
                                   v_scales_var, k_var, v_var, tables_var,
                                   positions_var, num_valid_var,
                                   num_heads: int):
    """Emit ``kv_cache_write_q``: like ``kv_cache_write_layer`` it
    writes its outputs onto the (k_pages, v_pages, k_scales, v_scales)
    Variables it reads, so the int8 pools and their scale planes are
    donated state rewritten in place. Returns them."""
    from ..layer_helper import LayerHelper

    pools = (k_pages_var, v_pages_var, k_scales_var, v_scales_var)
    LayerHelper("kv_cache_write_q").append_op(
        type="kv_cache_write_q",
        inputs={"KPages": [k_pages_var], "VPages": [v_pages_var],
                "KScales": [k_scales_var], "VScales": [v_scales_var],
                "K": [k_var], "V": [v_var], "BlockTables": [tables_var],
                "Positions": [positions_var], "NumValid": [num_valid_var]},
        outputs=dict(zip(("OutKPages", "OutVPages", "OutKScales",
                          "OutVScales"), ([p] for p in pools))),
        attrs={"num_heads": num_heads},
    )
    return pools


# -- op registration ---------------------------------------------------------
from ..core.registry import register_op  # noqa: E402


def _lower_ragged(ins, op, quantized: bool):
    q = ins["Q"][0]                       # [B, C, H*D] layer layout
    h = int(op.attrs["num_heads"])
    B, C, HD = q.shape
    D = HD // h
    sink = ins.get("Sink")
    window = op.attrs.get("window")
    o = ragged_paged_attention(
        q.reshape(B, C, h, D), ins["KPages"][0], ins["VPages"][0],
        ins["Positions"][0], ins["NumValid"][0], ins["BlockTables"][0],
        k_scales=ins["KScales"][0] if quantized else None,
        v_scales=ins["VScales"][0] if quantized else None,
        window=int(window) if window else None,
        sink=sink[0] if sink else None,
        stored_products=bool(op.attrs.get("stored_products", False)),
        name=str(op.attrs.get("kernel_name", "ragged_paged_attention")),
        lean_decode=bool(op.attrs.get("lean_decode", False)))
    return {"Out": [o.reshape(B, C, -1)]}


@register_op("ragged_paged_attention",
             inputs=("Q", "KPages", "VPages", "BlockTables", "Positions",
                     "NumValid", "Sink"),
             outputs=("Out",),
             no_grad=("BlockTables", "Positions", "NumValid", "Sink"),
             stop_gradient=True)
def _ragged_paged_attention_op(ctx, op, ins):
    return _lower_ragged(ins, op, quantized=False)


@register_op("ragged_paged_attention_q",
             inputs=("Q", "KPages", "VPages", "KScales", "VScales",
                     "BlockTables", "Positions", "NumValid"),
             outputs=("Out",),
             no_grad=("KScales", "VScales", "BlockTables", "Positions",
                      "NumValid"),
             stop_gradient=True)
def _ragged_paged_attention_q_op(ctx, op, ins):
    return _lower_ragged(ins, op, quantized=True)


@register_op("kv_cache_write_q",
             inputs=("KPages", "VPages", "KScales", "VScales", "K", "V",
                     "BlockTables", "Positions", "NumValid"),
             outputs=("OutKPages", "OutVPages", "OutKScales", "OutVScales"),
             no_grad=("BlockTables", "Positions", "NumValid"),
             stop_gradient=True)
def _kv_cache_write_q_op(ctx, op, ins):
    k, v = ins["K"][0], ins["V"][0]       # [B, S, H*D] layer layout
    h = int(op.attrs["num_heads"])
    B, S, HD = k.shape
    D = HD // h
    kp, vp, ks, vs = quantized_kv_cache_write(
        ins["KPages"][0], ins["VPages"][0], ins["KScales"][0],
        ins["VScales"][0], k.reshape(B, S, h, D), v.reshape(B, S, h, D),
        ins["BlockTables"][0], ins["Positions"][0], ins["NumValid"][0])
    return {"OutKPages": [kp], "OutVPages": [vp],
            "OutKScales": [ks], "OutVScales": [vs]}


@register_op("kv_cache_write_split",
             inputs=("KPages", "VPages", "K", "V", "BlockTables",
                     "Positions", "NumValid"),
             outputs=("OutKPages", "OutVPages"),
             no_grad=("BlockTables", "Positions", "NumValid"),
             stop_gradient=True)
def _kv_cache_write_split_op(ctx, op, ins):
    k, v = ins["K"][0], ins["V"][0]       # [B, S, KVH*D], [B, S, KVH*Dv]
    h = int(op.attrs["num_heads"])
    B, S, _ = k.shape
    kp, vp = split_kv_cache_write(
        ins["KPages"][0], ins["VPages"][0], k.reshape(B, S, h, -1),
        v.reshape(B, S, h, -1), ins["BlockTables"][0], ins["Positions"][0],
        ins["NumValid"][0], ring=bool(op.attrs.get("ring", False)))
    return {"OutKPages": [kp], "OutVPages": [vp]}
