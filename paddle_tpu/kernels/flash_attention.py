"""Blockwise (flash) attention for TPU via Pallas — forward AND backward,
with key-padding mask and additive attention bias (BiasQK).

Design — two regimes, routed per call on the (padded) sequence length:
  S <= 2048 (PADDLE_TPU_FLASH_PANEL_MAX): grid (batch, heads,
    seq_block); each program brings one Q (or K/V) block plus the full
    opposing [S, D] panel for its (b,h) into VMEM (512KB at S=2048,
    D=64) and works on the MXU with a single softmax — no inner loop,
    no online-softmax bookkeeping; the win over naive XLA attention is
    never materializing [B,H,S,S] in HBM.
  S > 2048: KV-block streaming (FA-2): grid (batch, heads, q_block,
    kv_block) with the KV axis innermost, online-softmax accumulators
    (acc, m, l) in VMEM scratch — VMEM use is O(blk_q*blk_k), so the
    single-chip ceiling is HBM-bound (8k/16k+ work on one chip).
When the executor compiles over a mesh with an `sp` axis (sequence
parallelism), the flash_attention op routes to ring attention instead
(parallel/ring_attention.py via _sequence_parallel_mesh below): each
device keeps its local S/sp shard and K/V rotate over ICI; the local
shard itself uses these kernels, so ring x streaming composes.

Masking (reference operators/fused/multihead_matmul_op.cu:441 takes a
BiasQK input for exactly this):
  mask  — [B, S] key-padding mask, bool (True = attend) or additive
          float (0 / -inf). O(B*S) HBM: the cheap form covering the
          padded-batch BERT case without an O(S^2) tensor.
  bias  — [B|1, H|1, S, S] additive attention bias (the general BiasQK
          / relative-position case). Differentiable: dbias is emitted
          blockwise by the dQ kernel and reduced over broadcast dims.
Sequence lengths that don't divide the q/k block are zero-padded up to
the block multiple; padded KEY positions are force-masked (even when
the caller passed no mask), padded QUERY rows are sliced off.

Backward (FlashAttention-2 style, no O(S^2) residuals):
  forward additionally emits LSE = m + log(sum exp(s - m)) per row;
  delta = rowsum(dO * O) is a cheap XLA elementwise;
  dQ kernel  (grid b,h,q_block):  recompute P from Q_i,K,LSE_i;
      dP = dO_i V^T; dS = P*(dP - delta_i)*scale; dQ_i = dS K;
      [has_bias] dBias_i = P*(dP - delta_i)  (the logits cotangent).
  dKV kernel (grid b,h,k_block):  P^T from K_j,Q,LSE;
      dV_j = P^T dO; dP^T = V_j dO^T; dS^T = P^T*(dP^T - delta)*scale;
      dK_j = dS^T Q.
Residual memory is O(S) per (b,h) — the [B,H,S,S] blocks never exist,
in forward or backward (except the dbias output itself when a dense
bias is used, which is inherently O(S^2)).

Set PADDLE_TPU_FLASH_INTERPRET=1 to run the Pallas kernels in
interpreter mode on any backend (how tests/test_flash_attention.py
exercises the real kernels on CPU).

Reference analogue: operators/fused/multihead_matmul_op.cu (inference
fused attention). This version also trains.
"""

from __future__ import annotations

import functools
import logging
import math
import os
from typing import Optional

import jax
import jax.numpy as jnp

_logger = logging.getLogger("paddle_tpu.flash_attention")

NEG_INF = -1e30
LANES = 128  # TPU minor-dim tile; lse/delta are stored lane-replicated
DEFAULT_BLK = 256


def _panel_max() -> int:
    """Above this sequence length the kernels switch from the
    full-K/V-panel design (one [S, D] panel per (b, h) in VMEM — fastest
    for the flagship <=2k configs) to KV-block streaming (FA-2 grid
    iteration with online-softmax scratch accumulators — O(blk) VMEM,
    lifts the single-chip ceiling to 8k+). Read per call so tests can
    force the streaming path at tiny S."""
    return int(os.environ.get("PADDLE_TPU_FLASH_PANEL_MAX", "2048"))


def _reference_attention(q, k, v, sm_scale, causal, mask=None, bias=None):
    # [B, H, S, D]; mask additive [B, S]; bias [B|1, H|1, S, S]
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * sm_scale
    if bias is not None:
        s = s + bias
    if mask is not None:
        s = s + mask[:, None, None, :]
    if causal:
        S = q.shape[2]
        cm = jnp.tril(jnp.ones((S, S), bool))
        s = jnp.where(cm[None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)


def _pallas_mode() -> Optional[str]:
    # PADDLE_TPU_KERNEL_INTERPRET is the shared interpret switch the
    # other fused kernels (layer_norm, softmax_xent) use — honoring it
    # here keeps CI smoke coverage real: with only the flash-specific
    # var, tests/test_models_zoo.py's flash cases would take the
    # reference path on CPU
    if (os.environ.get("PADDLE_TPU_FLASH_INTERPRET", "")
            or os.environ.get("PADDLE_TPU_KERNEL_INTERPRET", "")):
        return "interpret"
    if (jax.default_backend() == "tpu"
            or os.environ.get("PADDLE_TPU_FORCE_PALLAS") == "1"):
        # FORCE_PALLAS: local AOT validation lowers the real Mosaic
        # kernels for a v5e topology from a CPU host (tools/aot_check.py)
        return "tpu"
    return None


def _bias_index(Bb: int, Hb: int):
    """Index map for a broadcastable [B|1, H|1, ...] bias block."""
    def idx(b, h, i):
        return (b if Bb > 1 else 0, h if Hb > 1 else 0, i, 0)
    return idx


# -- forward ----------------------------------------------------------------


def _make_fwd_kernel(blk_q: int, causal: bool, sm_scale: float,
                     with_lse: bool, has_mask: bool, has_bias: bool):
    from jax.experimental import pallas as pl

    def kernel(*refs):
        it = iter(refs)
        q_ref, k_ref, v_ref = next(it), next(it), next(it)
        mask_ref = next(it) if has_mask else None
        bias_ref = next(it) if has_bias else None
        o_ref = next(it)
        lse_ref = next(it) if with_lse else None

        qi = pl.program_id(2)
        q = q_ref[0, 0].astype(jnp.float32)  # [blk_q, D]
        k = k_ref[0, 0].astype(jnp.float32)  # [S, D]
        v = v_ref[0, 0].astype(jnp.float32)  # [S, D]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * sm_scale  # [blk_q, S]
        if has_bias:
            s = s + bias_ref[0, 0].astype(jnp.float32)
        if has_mask:
            s = s + mask_ref[0, 0].astype(jnp.float32)[None, :]
        if causal:
            rows = qi * blk_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(rows >= cols, s, NEG_INF)
        m = jnp.max(s, axis=1, keepdims=True)
        p = jnp.exp(s - m)
        denom = jnp.sum(p, axis=1, keepdims=True)
        o = jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        ) / denom
        o_ref[0, 0] = o.astype(o_ref.dtype)
        if with_lse:
            # lse is per-row but stored lane-replicated [blk_q, 128]:
            # TPU tiling wants a 128 minor dim (same layout as jax's
            # own pallas flash kernel's l/m outputs)
            lse_ref[0, 0] = jnp.broadcast_to(
                m + jnp.log(denom), (m.shape[0], LANES)
            )

    return kernel


def _flash_fwd_pallas(q, k, v, mask, bias, sm_scale, causal, interpret,
                      blk_q=DEFAULT_BLK, with_lse=True):
    """with_lse=False is the inference path: no residual output, no
    HBM write of the [B,H,S,128] lse buffer. mask/bias may be None."""
    from jax.experimental import pallas as pl

    B, H, S, D = q.shape
    blk_q = min(blk_q, S)
    assert S % blk_q == 0, f"seq {S} not divisible by q block {blk_q}"
    grid = (B, H, S // blk_q)
    has_mask, has_bias = mask is not None, bias is not None
    kernel = _make_fwd_kernel(blk_q, causal, sm_scale, with_lse,
                              has_mask, has_bias)
    in_specs = [
        pl.BlockSpec((1, 1, blk_q, D), lambda b, h, i: (b, h, i, 0)),
        pl.BlockSpec((1, 1, S, D), lambda b, h, i: (b, h, 0, 0)),
        pl.BlockSpec((1, 1, S, D), lambda b, h, i: (b, h, 0, 0)),
    ]
    args = [q, k, v]
    if has_mask:
        in_specs.append(
            pl.BlockSpec((1, 1, S), lambda b, h, i: (b, 0, 0)))
        args.append(mask[:, None, :])
    if has_bias:
        Bb, Hb = bias.shape[0], bias.shape[1]
        in_specs.append(
            pl.BlockSpec((1, 1, blk_q, S), _bias_index(Bb, Hb)))
        args.append(bias)
    out_shape = [jax.ShapeDtypeStruct(q.shape, q.dtype)]
    out_specs = [pl.BlockSpec((1, 1, blk_q, D), lambda b, h, i: (b, h, i, 0))]
    if with_lse:
        out_shape.append(jax.ShapeDtypeStruct((B, H, S, LANES), jnp.float32))
        out_specs.append(
            pl.BlockSpec((1, 1, blk_q, LANES), lambda b, h, i: (b, h, i, 0))
        )
    res = pl.pallas_call(
        kernel,
        out_shape=tuple(out_shape),
        grid=grid,
        in_specs=in_specs,
        out_specs=tuple(out_specs),
        interpret=interpret,
        name="flash_attention_fwd_panel",
    )(*args)
    return res if with_lse else (res[0], None)


# -- KV-block streaming (S > _panel_max()) ----------------------------------
# FA-2 grid iteration: grid (B, H, nq, nk) with the KV axis innermost
# ("arbitrary" semantics — same-output-block revisits are consecutive),
# online-softmax state in VMEM scratch. Only O(blk_q x blk_k) tiles ever
# live in VMEM, so sequence length is bounded by HBM, not VMEM. A dense
# [S, S] bias at this length is O(S^2) HBM by definition (same problem
# the ring-attention route warns about), so bias inputs stay on the
# panel kernel — which raises if S is too big for its VMEM panel.


def _make_fwd_stream_kernel(blk_q: int, blk_k: int, nk: int, causal: bool,
                            sm_scale: float, with_lse: bool, has_mask: bool):
    from jax.experimental import pallas as pl

    def kernel(*refs):
        it = iter(refs)
        q_ref, k_ref, v_ref = next(it), next(it), next(it)
        mask_ref = next(it) if has_mask else None
        o_ref = next(it)
        lse_ref = next(it) if with_lse else None
        acc_ref, m_ref, l_ref = next(it), next(it), next(it)

        qi, kj = pl.program_id(2), pl.program_id(3)

        @pl.when(kj == 0)
        def _init():
            m_ref[...] = jnp.full_like(m_ref, NEG_INF)
            l_ref[...] = jnp.zeros_like(l_ref)
            acc_ref[...] = jnp.zeros_like(acc_ref)

        # causal: skip blocks entirely above the diagonal
        run = (qi * blk_q + blk_q - 1 >= kj * blk_k) if causal else True

        @pl.when(run)
        def _compute():
            q = q_ref[0, 0].astype(jnp.float32)    # [blk_q, D]
            k = k_ref[0, 0].astype(jnp.float32)    # [blk_k, D]
            v = v_ref[0, 0].astype(jnp.float32)    # [blk_k, D]
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * sm_scale
            if has_mask:
                s = s + mask_ref[0, 0].astype(jnp.float32)[None, :]
            if causal:
                rows = qi * blk_q + jax.lax.broadcasted_iota(
                    jnp.int32, s.shape, 0)
                cols = kj * blk_k + jax.lax.broadcasted_iota(
                    jnp.int32, s.shape, 1)
                s = jnp.where(rows >= cols, s, NEG_INF)
            m_prev = m_ref[:, :1]                  # [blk_q, 1]
            l_prev = l_ref[:, :1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            corr = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)                 # [blk_q, blk_k]
            l_new = l_prev * corr + jnp.sum(p, axis=1, keepdims=True)
            acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
            l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

        @pl.when(kj == nk - 1)
        def _final():
            o_ref[0, 0] = (acc_ref[...] / l_ref[:, :1]).astype(o_ref.dtype)
            if with_lse:
                lse_ref[0, 0] = m_ref[...] + jnp.log(l_ref[...])

    return kernel


def _flash_fwd_stream(q, k, v, mask, sm_scale, causal, interpret,
                      blk_q=DEFAULT_BLK, blk_k=DEFAULT_BLK, with_lse=True):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, S, D = q.shape
    blk_q, blk_k = min(blk_q, S), min(blk_k, S)
    assert S % blk_q == 0 and S % blk_k == 0
    nq, nk = S // blk_q, S // blk_k
    has_mask = mask is not None
    kernel = _make_fwd_stream_kernel(blk_q, blk_k, nk, causal, sm_scale,
                                     with_lse, has_mask)
    in_specs = [
        pl.BlockSpec((1, 1, blk_q, D), lambda b, h, i, j: (b, h, i, 0)),
        pl.BlockSpec((1, 1, blk_k, D), lambda b, h, i, j: (b, h, j, 0)),
        pl.BlockSpec((1, 1, blk_k, D), lambda b, h, i, j: (b, h, j, 0)),
    ]
    args = [q, k, v]
    if has_mask:
        in_specs.append(
            pl.BlockSpec((1, 1, blk_k), lambda b, h, i, j: (b, 0, j)))
        args.append(mask[:, None, :])
    out_shape = [jax.ShapeDtypeStruct(q.shape, q.dtype)]
    out_specs = [pl.BlockSpec((1, 1, blk_q, D),
                              lambda b, h, i, j: (b, h, i, 0))]
    if with_lse:
        out_shape.append(jax.ShapeDtypeStruct((B, H, S, LANES), jnp.float32))
        out_specs.append(pl.BlockSpec((1, 1, blk_q, LANES),
                                      lambda b, h, i, j: (b, h, i, 0)))
    res = pl.pallas_call(
        kernel,
        out_shape=tuple(out_shape),
        grid=(B, H, nq, nk),
        in_specs=in_specs,
        out_specs=tuple(out_specs),
        scratch_shapes=[
            pltpu.VMEM((blk_q, D), jnp.float32),      # acc
            pltpu.VMEM((blk_q, LANES), jnp.float32),  # m
            pltpu.VMEM((blk_q, LANES), jnp.float32),  # l
        ],
        compiler_params=pltpu.CompilerParams(dimension_semantics=(
            "parallel", "parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_attention_fwd",
    )(*args)
    return res if with_lse else (res[0], None)


def _make_dq_stream_kernel(blk_q: int, blk_k: int, nk: int, causal: bool,
                           sm_scale: float, has_mask: bool):
    from jax.experimental import pallas as pl

    def kernel(*refs):
        it = iter(refs)
        q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref = (
            next(it), next(it), next(it), next(it), next(it), next(it))
        mask_ref = next(it) if has_mask else None
        dq_ref = next(it)
        dq_acc = next(it)

        qi, kj = pl.program_id(2), pl.program_id(3)

        @pl.when(kj == 0)
        def _init():
            dq_acc[...] = jnp.zeros_like(dq_acc)

        run = (qi * blk_q + blk_q - 1 >= kj * blk_k) if causal else True

        @pl.when(run)
        def _compute():
            q = q_ref[0, 0].astype(jnp.float32)
            k = k_ref[0, 0].astype(jnp.float32)
            v = v_ref[0, 0].astype(jnp.float32)
            do = do_ref[0, 0].astype(jnp.float32)
            lse = lse_ref[0, 0][:, :1]
            # delta = rowsum(dO * O): recomputed per block from the o/do
            # tiles (cheap elementwise) instead of materializing a
            # lane-replicated [B,H,S,128] HBM array — which would be a
            # 128x blow-up at exactly the long-S regime this path serves
            delta = jnp.sum(do * o_ref[0, 0].astype(jnp.float32),
                            axis=1, keepdims=True)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * sm_scale
            if has_mask:
                s = s + mask_ref[0, 0].astype(jnp.float32)[None, :]
            if causal:
                rows = qi * blk_q + jax.lax.broadcasted_iota(
                    jnp.int32, s.shape, 0)
                cols = kj * blk_k + jax.lax.broadcasted_iota(
                    jnp.int32, s.shape, 1)
                s = jnp.where(rows >= cols, s, NEG_INF)
            p = jnp.exp(s - lse)
            dp = jax.lax.dot_general(
                do, v, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            ds = p * (dp - delta) * sm_scale
            dq_acc[...] += jax.lax.dot_general(
                ds, k, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

        @pl.when(kj == nk - 1)
        def _final():
            dq_ref[0, 0] = dq_acc[...].astype(dq_ref.dtype)

    return kernel


def _make_dkv_stream_kernel(blk_q: int, blk_k: int, nq: int, causal: bool,
                            sm_scale: float, has_mask: bool):
    from jax.experimental import pallas as pl

    def kernel(*refs):
        it = iter(refs)
        k_ref, v_ref, q_ref, do_ref, o_ref, lse_ref = (
            next(it), next(it), next(it), next(it), next(it), next(it))
        mask_ref = next(it) if has_mask else None
        dk_ref, dv_ref = next(it), next(it)
        dk_acc, dv_acc = next(it), next(it)

        kj, qi = pl.program_id(2), pl.program_id(3)

        @pl.when(qi == 0)
        def _init():
            dk_acc[...] = jnp.zeros_like(dk_acc)
            dv_acc[...] = jnp.zeros_like(dv_acc)

        run = (qi * blk_q + blk_q - 1 >= kj * blk_k) if causal else True

        @pl.when(run)
        def _compute():
            k = k_ref[0, 0].astype(jnp.float32)    # [blk_k, D]
            v = v_ref[0, 0].astype(jnp.float32)
            q = q_ref[0, 0].astype(jnp.float32)    # [blk_q, D]
            do = do_ref[0, 0].astype(jnp.float32)
            lse = lse_ref[0, 0][:, 0]              # [blk_q]
            delta = jnp.sum(do * o_ref[0, 0].astype(jnp.float32),
                            axis=1)                # [blk_q] (see dq kernel)
            st = jax.lax.dot_general(
                k, q, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * sm_scale
            if has_mask:
                st = st + mask_ref[0, 0].astype(jnp.float32)[:, None]
            if causal:
                rows = kj * blk_k + jax.lax.broadcasted_iota(
                    jnp.int32, st.shape, 0)
                cols = qi * blk_q + jax.lax.broadcasted_iota(
                    jnp.int32, st.shape, 1)
                st = jnp.where(cols >= rows, st, NEG_INF)  # keep q >= k
            pt = jnp.exp(st - lse[None, :])        # [blk_k, blk_q]
            dv_acc[...] += jax.lax.dot_general(
                pt, do, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            dpt = jax.lax.dot_general(
                v, do, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            dst = pt * (dpt - delta[None, :]) * sm_scale
            dk_acc[...] += jax.lax.dot_general(
                dst, q, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

        @pl.when(qi == nq - 1)
        def _final():
            dk_ref[0, 0] = dk_acc[...].astype(dk_ref.dtype)
            dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)

    return kernel


def _flash_bwd_stream(q, k, v, mask, o, lse, g, sm_scale, causal, interpret,
                      blk_q=DEFAULT_BLK, blk_k=DEFAULT_BLK):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, S, D = q.shape
    blk_q, blk_k = min(blk_q, S), min(blk_k, S)
    assert S % blk_q == 0 and S % blk_k == 0
    nq, nk = S // blk_q, S // blk_k
    has_mask = mask is not None

    dq_in_specs = [
        pl.BlockSpec((1, 1, blk_q, D), lambda b, h, i, j: (b, h, i, 0)),
        pl.BlockSpec((1, 1, blk_k, D), lambda b, h, i, j: (b, h, j, 0)),
        pl.BlockSpec((1, 1, blk_k, D), lambda b, h, i, j: (b, h, j, 0)),
        pl.BlockSpec((1, 1, blk_q, D), lambda b, h, i, j: (b, h, i, 0)),
        pl.BlockSpec((1, 1, blk_q, D), lambda b, h, i, j: (b, h, i, 0)),
        pl.BlockSpec((1, 1, blk_q, LANES), lambda b, h, i, j: (b, h, i, 0)),
    ]
    dq_args = [q, k, v, g, o, lse]
    if has_mask:
        dq_in_specs.append(pl.BlockSpec((1, 1, blk_k),
                                        lambda b, h, i, j: (b, 0, j)))
        dq_args.append(mask[:, None, :])
    dq = pl.pallas_call(
        _make_dq_stream_kernel(blk_q, blk_k, nk, causal, sm_scale, has_mask),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        grid=(B, H, nq, nk),
        in_specs=dq_in_specs,
        out_specs=pl.BlockSpec((1, 1, blk_q, D),
                               lambda b, h, i, j: (b, h, i, 0)),
        scratch_shapes=[pltpu.VMEM((blk_q, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=(
            "parallel", "parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_attention_bwd_dq",
    )(*dq_args)

    dkv_in_specs = [
        pl.BlockSpec((1, 1, blk_k, D), lambda b, h, j, i: (b, h, j, 0)),
        pl.BlockSpec((1, 1, blk_k, D), lambda b, h, j, i: (b, h, j, 0)),
        pl.BlockSpec((1, 1, blk_q, D), lambda b, h, j, i: (b, h, i, 0)),
        pl.BlockSpec((1, 1, blk_q, D), lambda b, h, j, i: (b, h, i, 0)),
        pl.BlockSpec((1, 1, blk_q, D), lambda b, h, j, i: (b, h, i, 0)),
        pl.BlockSpec((1, 1, blk_q, LANES), lambda b, h, j, i: (b, h, i, 0)),
    ]
    dkv_args = [k, v, q, g, o, lse]
    if has_mask:
        dkv_in_specs.append(pl.BlockSpec((1, 1, blk_k),
                                         lambda b, h, j, i: (b, 0, j)))
        dkv_args.append(mask[:, None, :])
    dk, dv = pl.pallas_call(
        _make_dkv_stream_kernel(blk_q, blk_k, nq, causal, sm_scale,
                                has_mask),
        out_shape=(jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)),
        grid=(B, H, nk, nq),
        in_specs=dkv_in_specs,
        out_specs=(
            pl.BlockSpec((1, 1, blk_k, D), lambda b, h, j, i: (b, h, j, 0)),
            pl.BlockSpec((1, 1, blk_k, D), lambda b, h, j, i: (b, h, j, 0)),
        ),
        scratch_shapes=[pltpu.VMEM((blk_k, D), jnp.float32),
                        pltpu.VMEM((blk_k, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=(
            "parallel", "parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_attention_bwd_dkv",
    )(*dkv_args)
    return dq, dk, dv, None


# -- backward ---------------------------------------------------------------


def _make_dq_kernel(blk_q: int, causal: bool, sm_scale: float,
                    has_mask: bool, has_bias: bool, qi_axis: int = 2,
                    accum_pred=None):
    """qi_axis: which grid axis walks the q blocks (2 for the plain
    (B,H,nq) grid; 0 for the bias grids, which put bias-broadcast dims
    innermost so same-output-block revisits are consecutive).
    accum_pred: None -> each grid cell owns its dbias block (full-rank
    bias); else a () -> bool fn that is True on a block's FIRST visit
    (later visits accumulate — how a broadcast bias's grad is reduced
    in-kernel instead of via an [B,H,S,S] HBM intermediate)."""
    from jax.experimental import pallas as pl

    def kernel(*refs):
        it = iter(refs)
        q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref = (
            next(it), next(it), next(it), next(it), next(it), next(it))
        mask_ref = next(it) if has_mask else None
        bias_ref = next(it) if has_bias else None
        dq_ref = next(it)
        dbias_ref = next(it) if has_bias else None

        qi = pl.program_id(qi_axis)
        q = q_ref[0, 0].astype(jnp.float32)        # [blk_q, D]
        k = k_ref[0, 0].astype(jnp.float32)        # [S, D]
        v = v_ref[0, 0].astype(jnp.float32)        # [S, D]
        do = do_ref[0, 0].astype(jnp.float32)      # [blk_q, D]
        lse = lse_ref[0, 0][:, :1]                 # [blk_q, 1] (lane-replicated)
        delta = delta_ref[0, 0][:, :1]             # [blk_q, 1]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * sm_scale  # [blk_q, S]
        if has_bias:
            s = s + bias_ref[0, 0].astype(jnp.float32)
        if has_mask:
            s = s + mask_ref[0, 0].astype(jnp.float32)[None, :]
        if causal:
            rows = qi * blk_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(rows >= cols, s, NEG_INF)
        p = jnp.exp(s - lse)                       # [blk_q, S]
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # [blk_q, S]
        dlogits = p * (dp - delta)                 # [blk_q, S]
        ds = dlogits * sm_scale
        dq = jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )  # [blk_q, D]
        dq_ref[0, 0] = dq.astype(dq_ref.dtype)
        if has_bias:
            if accum_pred is None:
                dbias_ref[0, 0] = dlogits.astype(dbias_ref.dtype)
            else:
                first = accum_pred()

                @pl.when(first)
                def _init():
                    dbias_ref[0, 0] = dlogits.astype(dbias_ref.dtype)

                @pl.when(jnp.logical_not(first))
                def _accum():
                    dbias_ref[0, 0] += dlogits.astype(dbias_ref.dtype)

    return kernel


def _make_dkv_kernel(blk_k: int, causal: bool, sm_scale: float,
                     has_mask: bool, has_bias: bool):
    from jax.experimental import pallas as pl

    def kernel(*refs):
        it = iter(refs)
        k_ref, v_ref, q_ref, do_ref, lse_ref, delta_ref = (
            next(it), next(it), next(it), next(it), next(it), next(it))
        mask_ref = next(it) if has_mask else None
        bias_ref = next(it) if has_bias else None
        dk_ref, dv_ref = next(it), next(it)

        ki = pl.program_id(2)
        k = k_ref[0, 0].astype(jnp.float32)        # [blk_k, D]
        v = v_ref[0, 0].astype(jnp.float32)        # [blk_k, D]
        q = q_ref[0, 0].astype(jnp.float32)        # [S, D]
        do = do_ref[0, 0].astype(jnp.float32)      # [S, D]
        lse = lse_ref[0, 0][:, 0]                  # [S] (lane-replicated)
        delta = delta_ref[0, 0][:, 0]              # [S]
        st = jax.lax.dot_general(
            k, q, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * sm_scale  # [blk_k, S]  (s transposed: rows=k, cols=q)
        if has_bias:
            # bias block is [S_q, blk_k] — transpose to the st layout
            st = st + bias_ref[0, 0].astype(jnp.float32).T
        if has_mask:
            st = st + mask_ref[0, 0].astype(jnp.float32)[:, None]
        if causal:
            rows = ki * blk_k + jax.lax.broadcasted_iota(jnp.int32, st.shape, 0)
            cols = jax.lax.broadcasted_iota(jnp.int32, st.shape, 1)
            st = jnp.where(cols >= rows, st, NEG_INF)  # keep q >= k
        pt = jnp.exp(st - lse[None, :])            # [blk_k, S]
        dv = jax.lax.dot_general(
            pt, do, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )  # [blk_k, D]
        dpt = jax.lax.dot_general(
            v, do, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # [blk_k, S]
        dst = pt * (dpt - delta[None, :]) * sm_scale
        dk = jax.lax.dot_general(
            dst, q, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )  # [blk_k, D]
        dk_ref[0, 0] = dk.astype(dk_ref.dtype)
        dv_ref[0, 0] = dv.astype(dv_ref.dtype)

    return kernel


def _flash_bwd_pallas(q, k, v, mask, bias, o, lse, g, sm_scale, causal,
                      interpret, blk_q=DEFAULT_BLK, blk_k=DEFAULT_BLK):
    from jax.experimental import pallas as pl

    B, H, S, D = q.shape
    blk_q = min(blk_q, S)
    blk_k = min(blk_k, S)
    assert S % blk_q == 0 and S % blk_k == 0
    has_mask, has_bias = mask is not None, bias is not None
    delta = jnp.broadcast_to(
        jnp.sum(g.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)[..., None],
        (B, H, S, LANES),
    )

    if not has_bias:
        # plain grid (B, H, nq): every cell owns its outputs
        dq_in_specs = [
            pl.BlockSpec((1, 1, blk_q, D), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, S, D), lambda b, h, i: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, S, D), lambda b, h, i: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, blk_q, D), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, blk_q, LANES), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, blk_q, LANES), lambda b, h, i: (b, h, i, 0)),
        ]
        dq_args = [q, k, v, g, lse, delta]
        if has_mask:
            dq_in_specs.append(
                pl.BlockSpec((1, 1, S), lambda b, h, i: (b, 0, 0)))
            dq_args.append(mask[:, None, :])
        dq = pl.pallas_call(
            _make_dq_kernel(blk_q, causal, sm_scale, has_mask, False),
            out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
            grid=(B, H, S // blk_q),
            in_specs=dq_in_specs,
            out_specs=pl.BlockSpec((1, 1, blk_q, D),
                                   lambda b, h, i: (b, h, i, 0)),
            interpret=interpret,
            name="flash_attention_bwd_dq_panel",
        )(*dq_args)
        dbias = None
    else:
        # bias grid: q-blocks outermost, bias-BROADCAST dims innermost,
        # so every revisit of a shared dbias block is consecutive and
        # the kernel can accumulate in place (dbias stays bias-shaped —
        # no [B,H,S,S] HBM intermediate for a [1,H,S,S] bias).
        Bb, Hb = bias.shape[0], bias.shape[1]
        if Bb == 1 and Hb > 1:
            # batch is the broadcast dim -> innermost
            to_bh = lambda i, a, c: (c, a)   # (grid a=head, c=batch)
            d1, d2 = H, B
        else:
            to_bh = lambda i, a, c: (a, c)   # (grid a=batch, c=head)
            d1, d2 = B, H
        full = Bb > 1 and Hb > 1

        def spec(shape_blk, which):
            def idx(i, a, c):
                b_, h_ = to_bh(i, a, c)
                return {"q": (b_, h_, i, 0), "kv": (b_, h_, 0, 0),
                        "mask": (b_, 0, 0),
                        "bias": (b_ if Bb > 1 else 0,
                                 h_ if Hb > 1 else 0, i, 0)}[which]
            return pl.BlockSpec(shape_blk, idx)

        dq_in_specs = [
            spec((1, 1, blk_q, D), "q"),
            spec((1, 1, S, D), "kv"),
            spec((1, 1, S, D), "kv"),
            spec((1, 1, blk_q, D), "q"),
            spec((1, 1, blk_q, LANES), "q"),
            spec((1, 1, blk_q, LANES), "q"),
        ]
        dq_args = [q, k, v, g, lse, delta]
        if has_mask:
            dq_in_specs.append(spec((1, 1, S), "mask"))
            dq_args.append(mask[:, None, :])
        dq_in_specs.append(spec((1, 1, blk_q, S), "bias"))
        dq_args.append(bias)

        if full:
            accum_pred = None
        else:
            def accum_pred():
                # first visit of the shared block: the innermost
                # (broadcast) axis is at 0 — and when BOTH dims are
                # broadcast, the middle axis must be at 0 too
                first = pl.program_id(2) == 0
                if Bb == 1 and Hb == 1:
                    first = jnp.logical_and(first, pl.program_id(1) == 0)
                return first

        res = pl.pallas_call(
            _make_dq_kernel(blk_q, causal, sm_scale, has_mask, True,
                            qi_axis=0, accum_pred=accum_pred),
            out_shape=(
                jax.ShapeDtypeStruct(q.shape, q.dtype),
                jax.ShapeDtypeStruct((Bb, Hb, S, S), jnp.float32),
            ),
            grid=(S // blk_q, d1, d2),
            in_specs=dq_in_specs,
            out_specs=(
                spec((1, 1, blk_q, D), "q"),
                spec((1, 1, blk_q, S), "bias"),
            ),
            interpret=interpret,
            name="flash_attention_bwd_dq_panel_bias",
        )(*dq_args)
        dq, dbias = res
        dbias = dbias.astype(bias.dtype)

    dkv_in_specs = [
        pl.BlockSpec((1, 1, blk_k, D), lambda b, h, j: (b, h, j, 0)),
        pl.BlockSpec((1, 1, blk_k, D), lambda b, h, j: (b, h, j, 0)),
        pl.BlockSpec((1, 1, S, D), lambda b, h, j: (b, h, 0, 0)),
        pl.BlockSpec((1, 1, S, D), lambda b, h, j: (b, h, 0, 0)),
        pl.BlockSpec((1, 1, S, LANES), lambda b, h, j: (b, h, 0, 0)),
        pl.BlockSpec((1, 1, S, LANES), lambda b, h, j: (b, h, 0, 0)),
    ]
    dkv_args = [k, v, q, g, lse, delta]
    if has_mask:
        dkv_in_specs.append(
            pl.BlockSpec((1, 1, blk_k), lambda b, h, j: (b, 0, j)))
        dkv_args.append(mask[:, None, :])
    if has_bias:
        Bb, Hb = bias.shape[0], bias.shape[1]
        dkv_in_specs.append(pl.BlockSpec(
            (1, 1, S, blk_k),
            lambda b, h, j: (b if Bb > 1 else 0, h if Hb > 1 else 0, 0, j)))
        dkv_args.append(bias)
    dk, dv = pl.pallas_call(
        _make_dkv_kernel(blk_k, causal, sm_scale, has_mask, has_bias),
        out_shape=(
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ),
        grid=(B, H, S // blk_k),
        in_specs=dkv_in_specs,
        out_specs=(
            pl.BlockSpec((1, 1, blk_k, D), lambda b, h, j: (b, h, j, 0)),
            pl.BlockSpec((1, 1, blk_k, D), lambda b, h, j: (b, h, j, 0)),
        ),
        interpret=interpret,
        name="flash_attention_bwd_dkv_panel",
    )(*dkv_args)
    return dq, dk, dv, dbias


# -- padding + normalization ------------------------------------------------


def _normalize_mask(mask, B, S, dtype=jnp.float32):
    """bool (True=valid) or additive float [B, S] -> additive f32."""
    if mask is None:
        return None
    mask = jnp.asarray(mask)
    if mask.dtype == jnp.bool_:
        mask = jnp.where(mask, 0.0, NEG_INF).astype(dtype)
    else:
        mask = mask.astype(dtype)
    # accept [S], [B,S] or paddle-style [B,1,1,S]
    return jnp.broadcast_to(mask.reshape(-1, S), (B, S))


def _pad_amount(S: int, blk: int = DEFAULT_BLK) -> int:
    if S <= blk:
        return 0  # single block: any length works
    return (-S) % blk


def _pad_qkv(q, k, v, mask, bias, pad):
    """Zero-pad the seq dim; padded keys are force-masked."""
    if pad == 0:
        return q, k, v, mask, bias
    B, H, S, D = q.shape
    padded = lambda x: jnp.pad(x, ((0, 0), (0, 0), (0, pad), (0, 0)))
    q, k, v = padded(q), padded(k), padded(v)
    if mask is None:
        mask = jnp.zeros((B, S), jnp.float32)
    mask = jnp.pad(mask, ((0, 0), (0, pad)), constant_values=NEG_INF)
    if bias is not None:
        bias = jnp.pad(bias, ((0, 0), (0, 0), (0, pad), (0, pad)))
    return q, k, v, mask, bias


# -- custom-vjp core --------------------------------------------------------
# One core covers every mask/bias combination: a None primal is an
# empty pytree to custom_vjp, and its cotangent slot is simply None —
# so absent operands cost nothing and need no duplicate plumbing.


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _core(q, k, v, mask, bias, causal, sm_scale):
    o, _ = _run_fwd(q, k, v, mask, bias, causal, sm_scale, with_lse=False)
    return o


def _core_fwd(q, k, v, mask, bias, causal, sm_scale):
    o, lse = _run_fwd(q, k, v, mask, bias, causal, sm_scale)
    return o, (q, k, v, mask, bias, o, lse)


def _core_bwd(causal, sm_scale, res, g):
    q, k, v, mask, bias, o, lse = res
    dq, dk, dv, dbias = _run_bwd(q, k, v, mask, bias, o, lse, g, causal,
                                 sm_scale)
    # the padding mask is 0/-inf: no meaningful cotangent
    dmask = jnp.zeros_like(mask) if mask is not None else None
    return dq, dk, dv, dmask, dbias


_core.defvjp(_core_fwd, _core_bwd)


def _run_fwd(q, k, v, mask, bias, causal, sm_scale, with_lse=True):
    # the reference is the path where no Pallas mode applies (CPU
    # without the interpret switch), never a retry: a kernel that fails
    # to trace, lower or compile raises
    mode = _pallas_mode()
    if mode is not None:
        if q.shape[2] > _panel_max() and bias is None:
            return _flash_fwd_stream(
                q, k, v, mask, sm_scale, causal,
                interpret=(mode == "interpret"), with_lse=with_lse,
            )
        return _flash_fwd_pallas(
            q, k, v, mask, bias, sm_scale, causal,
            interpret=(mode == "interpret"), with_lse=with_lse,
        )
    o = _reference_attention(q, k, v, sm_scale, causal, mask, bias)
    return o, None


def _run_bwd(q, k, v, mask, bias, o, lse, g, causal, sm_scale):
    # lse present <=> the forward took the Pallas path (mode is
    # re-derived, not stashed: residuals must be jax types)
    mode = _pallas_mode() if lse is not None else None
    if mode is not None:
        if q.shape[2] > _panel_max() and bias is None:
            return _flash_bwd_stream(
                q, k, v, mask, o, lse, g, sm_scale, causal,
                interpret=(mode == "interpret"),
            )
        return _flash_bwd_pallas(
            q, k, v, mask, bias, o, lse, g, sm_scale, causal,
            interpret=(mode == "interpret"),
        )

    def ref(q, k, v, bias):
        return _reference_attention(q, k, v, sm_scale, causal, mask, bias)

    if bias is not None:
        _, vjp = jax.vjp(ref, q, k, v, bias)
        return vjp(g)
    _, vjp = jax.vjp(lambda q, k, v: ref(q, k, v, None), q, k, v)
    return vjp(g) + (None,)


# -- public API -------------------------------------------------------------


def flash_attention(q, k, v, causal: bool = False,
                    sm_scale: Optional[float] = None,
                    mask=None, bias=None):
    """q,k,v: [B, H, S, D] -> [B, H, S, D].

    mask: optional [B, S] key-padding mask — bool (True = attend) or
    additive float (0 valid / -inf masked). bias: optional additive
    attention bias broadcastable as [B|1, H|1, S, S] (the reference's
    BiasQK, multihead_matmul_op.cu:441); differentiable. Sequence
    lengths that don't divide the 256 block are padded internally.
    """
    B, H, S, D = q.shape
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(D)
    mask = _normalize_mask(mask, B, S)
    if bias is not None:
        bias = jnp.asarray(bias)
        if (bias.ndim != 4 or bias.shape[2:] != (S, S)
                or bias.shape[0] not in (1, B) or bias.shape[1] not in (1, H)):
            raise ValueError(
                f"flash_attention bias must be [B|1, H|1, S, S] = "
                f"[{B}|1, {H}|1, {S}, {S}], got shape "
                f"{tuple(bias.shape)}")
    pad = _pad_amount(S)
    q2, k2, v2, mask, bias = _pad_qkv(q, k, v, mask, bias, pad)
    o = _core(q2, k2, v2, mask, bias, causal, scale)
    return o[:, :, :S] if pad else o


def flash_attention_layer(q_var, k_var, v_var, num_heads: int,
                          causal: bool = False, mask_var=None,
                          bias_var=None, mask_type: str = "binary"):
    """Program-level layer emitting the fused attention op (reference
    layers would compose ~10 ops; this is one). mask_var: [B, S]
    key-padding mask — mask_type="binary" (default) means 1 = attend /
    0 = padding; mask_type="additive" means the float values are added
    to the logits directly (0 / -inf). bias_var: [B|1, H|1, S, S]
    additive bias."""
    from ..layer_helper import LayerHelper
    from ..layers.nn import _out

    if mask_type not in ("binary", "additive"):
        raise ValueError(f"mask_type must be 'binary' or 'additive', "
                         f"got {mask_type!r}")
    helper = LayerHelper("flash_attention")
    out = _out(helper, q_var, shape=q_var.shape)
    inputs = {"Q": [q_var], "K": [k_var], "V": [v_var]}
    if mask_var is not None:
        inputs["Mask"] = [mask_var]
    if bias_var is not None:
        inputs["BiasQK"] = [bias_var]
    helper.append_op(
        type="flash_attention",
        inputs=inputs,
        outputs={"Out": [out]},
        attrs={"num_heads": num_heads, "causal": causal,
               "mask_type": mask_type},
    )
    return out


# op registration: operates on [B, S, H*D] inputs (layer layout)
from ..core.registry import register_op


@register_op("flash_attention", inputs=("Q", "K", "V", "Mask", "BiasQK"),
             outputs=("Out",), no_grad=("Mask",))
def _flash_attention_op(ctx, op, ins):
    q, k, v = ins["Q"][0], ins["K"][0], ins["V"][0]
    h = int(op.attrs["num_heads"])
    causal = bool(op.attrs.get("causal", False))
    B, S, HD = q.shape
    D = HD // h

    def split(x):
        return x.reshape(B, S, h, D).transpose(0, 2, 1, 3)

    mask = ins["Mask"][0] if ins.get("Mask") else None
    if mask is not None and mask.dtype != jnp.bool_:
        if op.attrs.get("mask_type", "binary") == "binary":
            # 1 = attend / 0 = padding -> additive 0 / -inf
            mask = jnp.where(mask.reshape(B, S) > 0.5, 0.0, NEG_INF)
        else:
            mask = mask.reshape(B, S)  # already-additive float values
    bias = ins["BiasQK"][0] if ins.get("BiasQK") else None
    o = None
    sp_mesh = _sequence_parallel_mesh(ctx)
    if sp_mesh is not None:
        if bias is not None:
            _logger.warning(
                "flash_attention: BiasQK is dense [S, S] and cannot ride "
                "the ring; using the unsharded flash kernel (GSPMD will "
                "all-gather K/V across the sp axis)")
        else:
            # mode comes from with_sequence_parallel(mode=...): "ring"
            # rotates K/V shards (parallel/ring_attention.py); "ulysses"
            # re-shards head<->sequence with 2 all-to-alls
            # (parallel/ulysses.py) and needs H % sp == 0
            mode = (ctx.axis_env or {}).get("sp_mode", "ring")
            n_heads_ok = h % dict(sp_mesh.shape)["sp"] == 0
            if mode == "ulysses" and not n_heads_ok:
                _logger.warning(
                    "flash_attention: ulysses needs heads %% sp == 0 "
                    "(H=%s, sp=%s); using ring", h,
                    dict(sp_mesh.shape)["sp"])
                mode = "ring"
            if mode == "ulysses":
                from ..parallel.ulysses import make_ulysses_attention_fn

                make_fn = make_ulysses_attention_fn
            else:
                from ..parallel.ring_attention import make_ring_attention_fn

                make_fn = make_ring_attention_fn
            sp_fn = make_fn(
                sp_mesh, "sp", causal=causal, with_mask=mask is not None)
            qs, ks, vs = split(q), split(k), split(v)
            if mask is not None:
                # bool or [B,1,1,S]-shaped masks must become additive
                # [B, S] first; its shard_map in_spec is per-mode —
                # ring: P(None, 'sp') (mask rotates with its keys),
                # ulysses: P(None, None) (replicated — local attention
                # spans the full sequence)
                o = sp_fn(qs, ks, vs, _normalize_mask(mask, B, S))
            else:
                o = sp_fn(qs, ks, vs)
    if o is None:
        from . import mesh_wrap as _mw

        wmode, wmesh, waxes = _mw.mode(ctx)
        qs, ks, vs = split(q), split(k), split(v)
        if _pallas_mode() is None or wmode == "direct":
            # XLA fallback / single device: no partitioning hazard
            # (interpret mode under a mesh DOES take the wrap branch
            # below, so CI covers the spec threading the real-TPU
            # compile depends on — round-5 review finding)
            o = flash_attention(qs, ks, vs, causal, None,
                                mask=mask, bias=bias)
        elif wmode == "xla":
            # inside a manual region with auto axes left (pipeline
            # stages under dp): nesting a partial-manual shard_map is
            # not attempted — use the XLA attention, which GSPMD
            # partitions fine
            o = _reference_attention(qs, ks, vs, 1.0 / math.sqrt(D),
                                     causal, mask=mask, bias=bias)
        else:
            # multi-device mesh: shard_map the kernel over every auto
            # axis (real TPU cannot GSPMD-auto-partition Mosaic) —
            # batch rides dp, heads ride mp, anything else replicates
            dim_axes = {0: "dp", 1: "mp"}
            qspec = _mw.dim_spec(qs.shape, dim_axes, wmesh, waxes)
            args = [qs, ks, vs]
            specs = [qspec, qspec, qspec]
            if mask is not None:
                args.append(mask)
                specs.append(_mw.dim_spec(mask.shape, {0: "dp"},
                                          wmesh, waxes))
            if bias is not None:
                args.append(bias)
                specs.append(_mw.dim_spec(bias.shape, {0: "dp", 1: "mp"},
                                          wmesh, waxes))
            has_m, has_b = mask is not None, bias is not None

            def _local(*a):
                it = iter(a)
                ql, kl, vl = next(it), next(it), next(it)
                ml = next(it) if has_m else None
                bl = next(it) if has_b else None
                return flash_attention(ql, kl, vl, causal, None,
                                       mask=ml, bias=bl)

            o = _mw.wrap_call(wmesh, waxes, _local, tuple(specs),
                              qspec)(*args)
    return {"Out": [o.transpose(0, 2, 1, 3).reshape(B, S, HD)]}


def _sequence_parallel_mesh(ctx):
    """The routing contract the module docstring promises: when the
    executor compiles over a mesh with an `sp` axis of size > 1, the
    fused attention op runs as ring attention (sequence parallelism,
    parallel/ring_attention.py) instead of the single-chip flash
    kernel. Sequence shards then rotate K/V over ICI and the [S, S]
    score matrix never exists, globally or locally."""
    mesh = getattr(ctx, "mesh", None)
    if mesh is None:
        return None
    try:
        if dict(mesh.shape).get("sp", 1) > 1:
            return mesh
    except (TypeError, AttributeError):
        return None
    return None
