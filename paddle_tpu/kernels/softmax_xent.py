"""Fused softmax + cross-entropy via Pallas — forward AND backward.

Reference analogue: operators/softmax_with_cross_entropy_op.cu (the
hand-fused CUDA kernel; a BASELINE north-star fused op).

Hard labels, last-axis classes. Grid over row blocks; each program
holds a [BLOCK_R, C] logits panel in VMEM and computes per row
  m = max(s); lse = m + log(sum exp(s - m)); loss = lse - s[label]
without materializing softmax in HBM for the loss. Backward is the
classic fused form dlogits = (softmax - onehot(label)) * dloss.

The vocab panel must fit VMEM: C * BLOCK_R * 4B (30k vocab, BLOCK_R 8
-> ~1MB). For larger vocabs callers keep the XLA path (which is also
fine — XLA fuses log_softmax chains well; this kernel exists for the
north-star's named fused set and for when the softmax residual write
is the bottleneck).

TPU layout notes (r4, first real-chip compile): every ref is >= 2D —
labels and the per-row loss/lse ride lane-replicated as [rows, 128]
(the f32/int32 native tile), like the flash kernels' LSE; the label
pick uses a broadcasted-iota compare, not take_along_axis (a per-row
dynamic gather Mosaic would scalarize).

PADDLE_TPU_KERNEL_INTERPRET=1 runs in interpreter mode (CPU tests).
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BLOCK_R = 8
LANES = 128


def _interpret() -> bool:
    return bool(os.environ.get("PADDLE_TPU_KERNEL_INTERPRET", ""))


def _fwd_kernel(s_ref, lbl_ref, loss_ref, lse_ref):
    s = s_ref[...].astype(jnp.float32)            # [BR, C]
    lbl = lbl_ref[...][:, :1]                     # [BR, 1] int32
    m = jnp.max(s, axis=1, keepdims=True)
    lse = m + jnp.log(jnp.sum(jnp.exp(s - m), axis=1, keepdims=True))
    onehot = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) == lbl
    picked = jnp.sum(jnp.where(onehot, s, 0.0), axis=1, keepdims=True)
    loss_ref[...] = jnp.broadcast_to(
        lse - picked, loss_ref.shape).astype(loss_ref.dtype)
    lse_ref[...] = jnp.broadcast_to(lse, lse_ref.shape).astype(jnp.float32)


def _bwd_kernel(s_ref, lbl_ref, lse_ref, dloss_ref, ds_ref):
    s = s_ref[...].astype(jnp.float32)
    lbl = lbl_ref[...][:, :1]
    lse = lse_ref[...][:, :1]
    dloss = dloss_ref[...][:, :1]
    p = jnp.exp(s - lse)                           # softmax
    onehot = (jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
              == lbl).astype(jnp.float32)
    ds_ref[...] = ((p - onehot) * dloss).astype(ds_ref.dtype)


def _pad_rows(a, br, fill=0):
    r = a.shape[0]
    pad = (-r) % br
    if pad:
        cfg = ((0, pad),) + ((0, 0),) * (a.ndim - 1)
        a = jnp.pad(a, cfg, constant_values=fill)
    return a, r


def _replicate(v, dtype):
    """[R] -> lane-replicated [R, LANES]."""
    return jnp.broadcast_to(v.astype(dtype)[:, None], (v.shape[0], LANES))


# VMEM bound: BLOCK_R x C panels; callers keep XLA past this vocab size
MAX_C = 32768


@jax.custom_vjp
def fused_softmax_xent(logits2, labels):
    """logits2 [R, C]; labels [R] int32 -> loss [R]. (lse stays an
    internal residual: exposing it as an output would leave its
    cotangent undefined in the custom_vjp.)"""
    loss, _ = _fwd_impl(logits2, labels)
    return loss


def _fwd_impl(logits2, labels):
    """Returns (loss [R], lane-replicated lse [R, LANES])."""
    R, C = logits2.shape
    sp, true_r = _pad_rows(logits2, BLOCK_R)
    lp, _ = _pad_rows(_replicate(labels, jnp.int32), BLOCK_R)
    n_blocks = sp.shape[0] // BLOCK_R
    loss, lse = pl.pallas_call(
        _fwd_kernel,
        grid=(n_blocks,),
        in_specs=[
            pl.BlockSpec((BLOCK_R, C), lambda i: (i, 0)),
            pl.BlockSpec((BLOCK_R, LANES), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((BLOCK_R, LANES), lambda i: (i, 0)),
            pl.BlockSpec((BLOCK_R, LANES), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((sp.shape[0], LANES), logits2.dtype),
            jax.ShapeDtypeStruct((sp.shape[0], LANES), jnp.float32),
        ],
        interpret=_interpret(),
        name="softmax_xent_fwd",
    )(sp, lp)
    return loss[:true_r, 0], lse[:true_r]


def _vjp_fwd(logits2, labels):
    loss, lse = _fwd_impl(logits2, labels)
    # keep the [R] lse as the held residual (not [R, 128] — 128x the
    # fwd->bwd footprint); bwd re-broadcasts lane-replication
    return loss, (logits2, labels, lse[:, 0])


def _vjp_bwd(res, dloss):
    logits2, labels, lse = res                    # lse [R]
    R, C = logits2.shape
    sp, true_r = _pad_rows(logits2, BLOCK_R)
    lp, _ = _pad_rows(_replicate(labels, jnp.int32), BLOCK_R)
    lsep, _ = _pad_rows(_replicate(lse, jnp.float32), BLOCK_R)
    dlp, _ = _pad_rows(_replicate(dloss, jnp.float32), BLOCK_R)
    n_blocks = sp.shape[0] // BLOCK_R
    ds = pl.pallas_call(
        _bwd_kernel,
        grid=(n_blocks,),
        in_specs=[
            pl.BlockSpec((BLOCK_R, C), lambda i: (i, 0)),
            pl.BlockSpec((BLOCK_R, LANES), lambda i: (i, 0)),
            pl.BlockSpec((BLOCK_R, LANES), lambda i: (i, 0)),
            pl.BlockSpec((BLOCK_R, LANES), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((BLOCK_R, C), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(sp.shape, logits2.dtype),
        interpret=_interpret(),
        name="softmax_xent_bwd",
    )(sp, lp, lsep, dlp)
    return ds[:true_r], None


fused_softmax_xent.defvjp(_vjp_fwd, _vjp_bwd)
