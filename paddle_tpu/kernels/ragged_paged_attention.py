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
attend path fails there.
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


def _gather_kv(pages, scales, page_indices):
    """[KVH, P, ps, D] pages -> [B, KVH, maxp*ps, D] fp32 windows per
    the block tables, dequantizing int8 pages against their
    per-(head, slot) scales on the way out."""
    B, maxp = page_indices.shape
    KVH, _P, ps, D = pages.shape
    win = jnp.transpose(pages[:, page_indices], (1, 0, 2, 3, 4))
    win = win.astype(jnp.float32).reshape(B, KVH, maxp * ps, D)
    if scales is not None:
        s = jnp.transpose(scales[:, page_indices], (1, 0, 2, 3))
        win = blockwise_dequantize(win, s.reshape(B, KVH, maxp * ps))
    return win


def _reference_ragged(q, k_pages, v_pages, start_pos, num_valid,
                      page_indices, sm_scale: float, k_scales, v_scales):
    """Pure-JAX oracle: gather each row's pages into a contiguous
    window, apply the ragged causal mask (key_pos <= start + j), plain
    fp32 softmax. O(B * C * maxp * ps) HBM — exactly right for CPU CI
    and the correctness tests."""
    B, C, H, D = q.shape
    KVH = k_pages.shape[0]
    maxp, ps = page_indices.shape[1], k_pages.shape[2]
    K = maxp * ps
    k = _gather_kv(k_pages, k_scales, page_indices)
    v = _gather_kv(v_pages, v_scales, page_indices)
    if KVH != H:  # grouped-query: repeat KV heads over the query groups
        k = jnp.repeat(k, H // KVH, axis=1)
        v = jnp.repeat(v, H // KVH, axis=1)
    s = jnp.einsum("bchd,bhkd->bhck", q.astype(jnp.float32) * sm_scale, k)
    kpos = jnp.arange(K, dtype=jnp.int32)
    qpos = start_pos[:, None] + jnp.arange(C, dtype=jnp.int32)[None, :]
    mask = kpos[None, None, :] <= qpos[:, :, None]           # [B, C, K]
    s = jnp.where(mask[:, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
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
                 maxp: int) -> int:
    """Pages of one K/V block, from the static shapes alone: the
    double-buffered K and V blocks fill _BLOCK_VMEM_BYTES, the
    [kvh, rows, keys] float32 score tile stays under a quarter of it,
    and pages narrower than a lane tile come in whole tiles of keys."""
    bp = _BLOCK_VMEM_BYTES // (2 * 2 * kvh * ps * d * itemsize)
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


def _make_ragged_kernel(C: int, group: int, ps: int, bp: int,
                        sm_scale: float, quantized: bool):
    """One grid step = one lane, every head: walk the lane's live keys
    in blocks of ``bp`` pages. ``C`` is the padded chunk; a KV head's
    ``group`` query heads ride as group * C rows of one matmul."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bk = bp * ps

    def kernel(*refs):
        it = iter(refs)
        tables_ref, starts_ref, nvalid_ref = next(it), next(it), next(it)
        q_ref = next(it)
        pools = next(it), next(it)       # K and V, whole, in HBM
        ks_ref = next(it) if quantized else None
        vs_ref = next(it) if quantized else None
        o_ref = next(it)
        bufs = next(it), next(it)        # their two-slot VMEM blocks
        sem = next(it)
        acc_ref, m_ref, l_ref = next(it), next(it), next(it)

        b = pl.program_id(0)
        start = starts_ref[b]
        # keys this lane attends; an idle lane walks nothing
        total = jnp.where(nvalid_ref[b] > 0, start + nvalid_ref[b], 0)
        npages = pl.cdiv(total, ps)
        nblocks = pl.cdiv(total, bk)

        def fetch(i, slot):
            # block i -> buffer `slot`: one strided copy a live page
            # takes that page of every KV head. Table entries past the
            # lane's live pages are never read.
            for j in range(bp):
                @pl.when(i * bp + j < npages)
                def live():  # noqa: ANN202
                    page = tables_ref[b, i * bp + j]
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
        def first():  # noqa: ANN202
            fetch(0, 0)

        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        q = q_ref[0].astype(jnp.float32) * sm_scale       # [KVH, G*C, D]
        kvh = q.shape[0]
        # row g * C + j of a KV head is query j of its g-th query head
        qpos = start + jax.lax.broadcasted_iota(
            jnp.int32, (group, C, bk), 1).reshape(group * C, bk)
        key = jax.lax.broadcasted_iota(jnp.int32, (group * C, bk), 1)

        def block(i, carry):
            slot = i % 2

            @pl.when(i + 1 < nblocks)
            def ahead():  # noqa: ANN202
                fetch(i + 1, 1 - slot)

            wait(i, slot)
            k, v = (buf[slot].astype(jnp.float32).reshape(kvh, bk, -1)
                    for buf in bufs)
            s = jax.lax.dot_general(
                q, k, (((2,), (2,)), ((0,), (0,))), precision=_F32_DOT,
                preferred_element_type=jnp.float32)        # [KVH, G*C, bk]
            if quantized:
                # int8 pages: q.(k * scale) = (q.k) * scale, a key's
                # scale applied where keys lie on the lane axis
                s = s * ks_ref[0, i][:, None, :]
            s = jnp.where((i * bk + key <= qpos)[None], s, NEG_INF)
            m_prev = m_ref[:, :, :1]
            m_next = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_next)
            pexp = jnp.exp(s - m_next)
            l_ref[...] = jnp.broadcast_to(
                alpha * l_ref[:, :, :1] + pexp.sum(axis=-1, keepdims=True),
                l_ref.shape)
            m_ref[...] = jnp.broadcast_to(m_next, m_ref.shape)
            if quantized:
                pexp = pexp * vs_ref[0, i][:, None, :]
            acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
                pexp, v, (((2,), (1,)), ((0,), (0,))), precision=_F32_DOT,
                preferred_element_type=jnp.float32)
            return carry

        jax.lax.fori_loop(0, nblocks, block, None)
        denom = l_ref[:, :, :1]
        denom = jnp.where(denom == 0.0, 1.0, denom)       # idle lane -> 0
        o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)

    return kernel


# jitted so that a step program's 24 layers, and every later trace of
# it, share one trace and one lowering of the kernel (unrolled over a
# block's pages, it costs 0.15 s to trace: seconds of set-up otherwise)
@functools.partial(jax.jit, static_argnames=("sm_scale", "interpret"))
def _ragged_pallas(q, k_pages, v_pages, start_pos, num_valid, page_indices,
                   sm_scale: float, k_scales, v_scales, interpret):
    """``interpret``: False on the chip, True for the Pallas interpreter
    (or a pltpu.InterpretParams for the TPU one, which is slower and
    starts every buffer as NaN)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, C, H, D0 = q.shape
    KVH, _P, ps, _ = k_pages.shape
    quantized = k_scales is not None
    # sublane-align the chunk so the [C, D] scratch tiles cleanly, and
    # lane-align the head dim: a copy out of the pool moves whole lane
    # tiles. Zero columns change no score and are cut off the output;
    # the pad copies the pool, so head dims of whole tiles (128) are
    # the fast case.
    Cp = -(-C // 8) * 8
    D = -(-D0 // LANES) * LANES
    qt = jnp.pad(jnp.transpose(q, (0, 2, 1, 3)),          # [B, H, C, D]
                 ((0, 0), (0, 0), (0, Cp - C), (0, D - D0)))
    if D != D0:
        k_pages, v_pages = (jnp.pad(p, ((0, 0),) * 3 + ((0, D - D0),))
                            for p in (k_pages, v_pages))
    group = H // KVH
    rows = group * Cp          # query head h = kv head h // group
    bp = _block_pages(KVH, ps, D, k_pages.dtype.itemsize, rows,
                      page_indices.shape[1])
    lane_block = pl.BlockSpec((1, KVH, rows, D), lambda b, *refs: (b, 0, 0, 0))
    in_specs = [lane_block] + [pl.BlockSpec(memory_space=pl.ANY)] * 2
    args = [qt.reshape(B, KVH, rows, D), k_pages, v_pages]
    if quantized:
        npages = -(-jnp.where(num_valid > 0, start_pos + num_valid, 0) // ps)
        args += [_block_scales(sc, page_indices, npages, bp)
                 for sc in (k_scales, v_scales)]
        in_specs += [pl.BlockSpec((1,) + args[-1].shape[1:],
                                  lambda b, *refs: (b, 0, 0, 0))] * 2
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B,),
        in_specs=in_specs,
        out_specs=lane_block,
        scratch_shapes=[
            pltpu.VMEM((2, KVH, bp, ps, D), k_pages.dtype),
            pltpu.VMEM((2, KVH, bp, ps, D), v_pages.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.VMEM((KVH, rows, D), jnp.float32),       # acc
            pltpu.VMEM((KVH, rows, LANES), jnp.float32),   # m
            pltpu.VMEM((KVH, rows, LANES), jnp.float32),   # l
        ],
    )
    kernel = _make_ragged_kernel(Cp, group, ps, bp, sm_scale, quantized)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KVH, rows, D), q.dtype),
        interpret=interpret,
        name="ragged_paged_attention",
    )(page_indices, start_pos, num_valid, *args)
    out = jnp.transpose(out.reshape(B, H, Cp, D)[:, :, :C, :D0],
                        (0, 2, 1, 3))
    row_ok = (jnp.arange(C, dtype=jnp.int32)[None, :]
              < num_valid[:, None])
    return jnp.where(row_ok[..., None, None], out, 0.0)   # [B, C, H, D]


# -- public entry ------------------------------------------------------------


def ragged_paged_attention(q, k_pages, v_pages, start_pos, num_valid,
                           page_indices, *, sm_scale: Optional[float] = None,
                           k_scales=None, v_scales=None):
    """Attend a ragged batch of new-token chunks over paged K/V.

    q:            [B, C, H, D] — up to C new tokens per sequence
                  (prefill chunk / decode row / decode + draft tokens)
    k_pages/v_pages: [KVH, P, ps, D]; int8 with ``k_scales/v_scales``
                  [KVH, P, ps] fp32 for the quantized-KV pool
    start_pos:    [B] int32 — absolute position of q[:, 0]
    num_valid:    [B] int32 — real rows in each chunk (0 = idle lane)
    page_indices: [B, maxp] int32 block tables

    Returns [B, C, H, D]; rows j >= num_valid[b] are zeros. Query j
    attends keys 0 .. start_pos[b] + j (the chunk's own K/V has been
    written by kv_cache_write before this op in every program). The
    softmax scale (default 1/sqrt(D)) applies to q identically on both
    paths, and the kernel's matmuls are float32 on the chip too
    (module docstring).
    """
    B, C, H, D = q.shape
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(D)
    start_pos = start_pos.astype(jnp.int32)
    num_valid = num_valid.astype(jnp.int32)
    page_indices = page_indices.astype(jnp.int32)
    mode = _pallas_mode()
    if mode is not None:
        # no retry on the reference: a kernel that fails to trace,
        # lower or compile raises
        return _ragged_pallas(q, k_pages, v_pages, start_pos, num_valid,
                              page_indices, scale, k_scales, v_scales,
                              interpret=(mode == "interpret"))
    return _reference_ragged(q, k_pages, v_pages, start_pos, num_valid,
                             page_indices, scale, k_scales, v_scales)


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
                                 v_scales_var=None):
    """Emit the ragged attention op: Q [B, C, H*D] over the page pool.
    One op per decoder layer — the whole mixed prefill+decode step
    stays a single XLA executable. Passing the scale Variables selects
    the int8-pool variant."""
    from ..layer_helper import LayerHelper
    from ..layers.nn import _out

    quantized = k_scales_var is not None
    op = "ragged_paged_attention_q" if quantized else "ragged_paged_attention"
    helper = LayerHelper(op)
    out = _out(helper, q_var, shape=q_var.shape)
    inputs = {"Q": [q_var], "KPages": [k_pages_var], "VPages": [v_pages_var],
              "BlockTables": [tables_var], "Positions": [positions_var],
              "NumValid": [num_valid_var]}
    if quantized:
        inputs["KScales"] = [k_scales_var]
        inputs["VScales"] = [v_scales_var]
    helper.append_op(type=op, inputs=inputs, outputs={"Out": [out]},
                     attrs={"num_heads": num_heads})
    return out


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
    o = ragged_paged_attention(
        q.reshape(B, C, h, D), ins["KPages"][0], ins["VPages"][0],
        ins["Positions"][0], ins["NumValid"][0], ins["BlockTables"][0],
        k_scales=ins["KScales"][0] if quantized else None,
        v_scales=ins["VScales"][0] if quantized else None)
    return {"Out": [o.reshape(B, C, HD)]}


@register_op("ragged_paged_attention",
             inputs=("Q", "KPages", "VPages", "BlockTables", "Positions",
                     "NumValid"),
             outputs=("Out",),
             no_grad=("BlockTables", "Positions", "NumValid"),
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
