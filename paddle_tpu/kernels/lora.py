"""Batched LoRA matmul: per-row adapter deltas over paged (A, B) pools
— the kernel layer under ``paddle_tpu.adapters`` (ROADMAP item 6: the
paged-KV block-table pattern applied to WEIGHTS).

A production tier serves hundreds of fine-tuned variants of one base
checkpoint; giving each its own engine wastes a whole accelerator per
low-traffic adapter. Batched LoRA multiplexes them instead: the base
matmul runs once for the whole mixed batch, and each batch row adds its
OWN adapter's low-rank delta

    y_m = x_m @ W  +  (x_m @ A[slot_m]) @ B[slot_m] * (alpha/r)

where ``slot_m`` indexes device-resident factor pools exactly like a KV
block table indexes page pools. Slot 0 is the reserved ZERO adapter
(all-zero factors, scale 0), so base-only rows are identity by
construction — one executable serves any adapter mix per micro-batch,
including none.

Pools are rank-bucketed (adapters/store.py): one (A, B) pool pair per
configured rank bucket, each row's slot vector naming at most one
bucket. The delta is the sum over buckets; rows absent from a bucket
point at its zero slot and contribute exactly 0.0 (float addition of
+0.0 is identity), so the summed path stays bitwise-stable for
base-only rows.

Ops (both registered; the ``adapters.rewrite_for_lora`` repoint
targets):

  batched_lora_matmul   X [..., K] (matmul/matmul_v2 semantics;
                        transpose_X honored) + base weight
  batched_lora_fc       the ``mul`` twin: X flattened at x_num_col_dims

Both compose with quantized bases: ``base_kind`` selects the dense
``W [K, N]`` path or the quant_matmul int8/int8_block/fp8 path
(``W`` = QWeight + ``WScale``), and the delta applies to the
DEQUANTIZED product — the quantized base computation is the exact
``quantized_matmul`` call the quantized ops make, so base numerics are
bitwise-unchanged by the rewrite.

Routing is the house kernel contract (flash/ragged/quant_matmul): the
Pallas lowering on real TPU or under PADDLE_TPU_FORCE_PALLAS=1,
interpreter mode under PADDLE_TPU_KERNEL_INTERPRET=1, and the pure-JAX
reference everywhere else — the reference IS the numerics oracle AND
the CPU-CI execution path. The Pallas kernel loops the slot axis on
the GRID: per (m, n) tile it masks the rows belonging to slot s,
runs the two small-rank matmuls, and accumulates into a VMEM scratch
tile — the gathered [M, K, r] factor tensor the reference materializes
never exists in HBM. Mosaic's sublane constraint puts a geometry floor
on the bucket rank (multiple of 8, see ``lora_rank_geometry_issue``);
tile-unaligned ranks keep the reference path (numerics fine, kernel
win lost — the same PTL092 story as small int8_block blocks).
"""

from __future__ import annotations

import os
from typing import Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from .quant_matmul import DEFAULT_BLOCK, quantized_matmul

LANES = 128
SUBLANES = 8
LORA_BASE_KINDS = ("dense", "int8", "int8_block", "fp8")


def _pallas_mode() -> Optional[str]:
    from .flash_attention import _pallas_mode as _fa_mode

    return _fa_mode()


# -- geometry (shared with kernels/constraints.py + the store) ---------------


def lora_rank_geometry_issue(rank) -> Optional[str]:
    """Mosaic's sublane constraint on the factor panels: the bucket
    rank is the A panel's trailing dim and the B panel's middle dim,
    so it must be a multiple of 8 (f32 sublane tile) for the Pallas
    path to tile. Returns the diagnosis when NOT tileable, else None.

    Single source of truth: ``_lora_delta_pallas``'s runtime guard
    raises this exact message; the static kernel-geometry pass emits
    it as PTL092 (reference fallback) / PTL091 (FORCE_PALLAS)."""
    if rank is None:
        return None
    rank = int(rank)
    if rank > 0 and rank % SUBLANES == 0:
        return None
    return (
        f"LoRA bucket rank {rank} is not Mosaic-tileable: the factor "
        f"panels tile at {SUBLANES}-row granularity, so the bucket rank "
        f"must be a positive multiple of {SUBLANES} — round the rank "
        "bucket up, or this delta runs the reference gather path on TPU")


def lora_pool_shapes(K: int, N: int, rank: int,
                     slots: int) -> Tuple[Tuple[int, ...], Tuple[int, ...]]:
    """(A pool, B pool) shapes for one target weight [K, N] in a
    ``rank`` bucket with ``slots`` slots (slot 0 = the zero adapter)."""
    return (slots, int(K), int(rank)), (slots, int(rank), int(N))


def lora_slot_bytes(K: int, N: int, rank: int, itemsize: int = 4) -> int:
    """Device bytes ONE adapter slot costs for one [K, N] target:
    A [K, r] + B [r, N] (+ its scale entry)."""
    return (int(K) * int(rank) + int(rank) * int(N)) * itemsize + 4


# -- reference (the oracle + the CPU-CI path) --------------------------------


def _reference_lora_delta(x2, a, b, scale, slots):
    xf = x2.astype(jnp.float32)
    u = jnp.einsum("mk,mkr->mr", xf, a[slots].astype(jnp.float32))
    d = jnp.einsum("mr,mrn->mn", u, b[slots].astype(jnp.float32))
    return (d * scale[slots].astype(jnp.float32)[:, None]).astype(x2.dtype)


# -- Pallas lowering ---------------------------------------------------------


def _make_lora_kernel(nslots: int):
    from jax.experimental import pallas as pl

    def kernel(x_ref, a_ref, b_ref, sc_ref, sl_ref, o_ref, acc_ref):
        s = pl.program_id(2)

        @pl.when(s == 0)
        def init():  # noqa: ANN202
            acc_ref[...] = jnp.zeros_like(acc_ref)

        # rows not owned by slot s zero out BEFORE the contraction, so
        # one pair of small-rank matmuls per grid step covers the whole
        # tile — the per-row gathered factor tensor never materializes
        mask = sl_ref[...] == s                              # [bm, 1]
        x = jnp.where(mask, x_ref[...].astype(jnp.float32), 0.0)
        u = jax.lax.dot_general(
            x, a_ref[0].astype(jnp.float32),
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        d = jax.lax.dot_general(
            u, b_ref[0].astype(jnp.float32),
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        acc_ref[...] += d * sc_ref[0, 0].astype(jnp.float32)

        @pl.when(s == nslots - 1)
        def finish():  # noqa: ANN202
            o_ref[...] = acc_ref[...].astype(o_ref.dtype)

    return kernel


def _pad_axis(a, axis: int, to: int):
    pad = to - a.shape[axis]
    if pad <= 0:
        return a
    widths = [(0, 0)] * a.ndim
    widths[axis] = (0, pad)
    return jnp.pad(a, widths)


def _lora_delta_pallas(x2, a, b, scale, slots, interpret: bool):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    M, K = x2.shape
    S, _, r = a.shape
    N = b.shape[2]
    if not interpret:
        issue = lora_rank_geometry_issue(r)
        if issue:
            raise ValueError(issue)
    Mp = -(-M // 16) * 16
    Np = -(-N // LANES) * LANES
    bm = next(c for c in (256, 128, 64, 32, 16) if Mp % c == 0)
    bn = LANES
    xp = _pad_axis(_pad_axis(x2, 0, Mp), 1, K)
    bp = _pad_axis(b, 2, Np)
    # padded rows carry slot 0 (the zero adapter) so they add nothing
    sl = _pad_axis(slots.astype(jnp.int32).reshape(M, 1), 0, Mp)
    sc = scale.astype(jnp.float32).reshape(S, 1)
    kernel = _make_lora_kernel(S)
    out = pl.pallas_call(
        kernel,
        grid=(Mp // bm, Np // bn, S),
        in_specs=[
            pl.BlockSpec((bm, K), lambda m, n, s: (m, 0)),       # x
            pl.BlockSpec((1, K, r), lambda m, n, s: (s, 0, 0)),  # A[s]
            pl.BlockSpec((1, r, bn), lambda m, n, s: (s, 0, n)),  # B[s]
            pl.BlockSpec((1, 1), lambda m, n, s: (s, 0)),        # scale[s]
            pl.BlockSpec((bm, 1), lambda m, n, s: (m, 0)),       # slots
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda m, n, s: (m, n)),
        out_shape=jax.ShapeDtypeStruct((Mp, Np), x2.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
        name="lora_delta",
    )(xp, a, bp, sc, sl)
    return out[:M, :N]


# -- public entries ----------------------------------------------------------


def batched_lora_delta(x2, a, b, scale, slots):
    """Per-row LoRA delta over ONE rank-bucket pool.

    ``x2 [M, K]``, ``a [S, K, r]``, ``b [S, r, N]``, ``scale [S]``
    (alpha/r per slot), ``slots [M]`` int32 -> delta ``[M, N]`` in x2's
    dtype. Slot 0 is the reserved zero adapter: rows pointing at it
    (base-only rows, rows owned by another bucket, padding) contribute
    exactly 0.0."""
    m = _pallas_mode()
    if (m == "tpu" and lora_rank_geometry_issue(a.shape[2])
            and os.environ.get("PADDLE_TPU_FORCE_PALLAS") != "1"):
        # the declared geometry rule (PTL092) SELECTS the reference for
        # a rank Mosaic cannot tile; under FORCE_PALLAS the kernel's
        # own guard raises instead (PTL091)
        m = None
    if m is not None:
        # no retry on the reference: a kernel that fails to trace,
        # lower or compile raises
        return _lora_delta_pallas(x2, a, b, scale, slots,
                                  interpret=(m == "interpret"))
    return _reference_lora_delta(x2, a, b, scale, slots)


def batched_lora_matmul(x, weight, a_pools: Sequence, b_pools: Sequence,
                        adapter_scales: Sequence, slots, *,
                        base_kind: str = "dense", weight_scale=None,
                        quant_block: int = DEFAULT_BLOCK):
    """``x [..., K]`` through the base matmul plus per-row adapter
    deltas -> ``[..., N]``.

    ``slots [R, n_buckets]`` int32 names each of the R batch rows' slot
    in each bucket pool; when x's flattened row count M is a multiple
    of R (the ragged engine's [R, chunk, K] activations), each row's
    slot broadcasts across its chunk. ``base_kind`` "dense" takes
    ``weight`` as the fp32/bf16 [K, N] weight (bitwise the ``mul`` /
    ``matmul`` lowering); the quant modes take it as QWeight with
    ``weight_scale`` and run the exact ``quantized_matmul`` call the
    quantized ops make — the delta applies to the dequantized
    product."""
    if base_kind not in LORA_BASE_KINDS:
        raise ValueError(
            f"batched_lora_matmul: base_kind must be one of "
            f"{LORA_BASE_KINDS}, got {base_kind!r}")
    lead = x.shape[:-1]
    K = x.shape[-1]
    x2 = x.reshape(-1, K)
    M = x2.shape[0]
    if base_kind == "dense":
        N = weight.shape[1]
        out = x2 @ weight
    else:
        N = weight.shape[1]
        out = quantized_matmul(x2, weight, weight_scale, mode=base_kind,
                               block=int(quant_block))
    slots = jnp.asarray(slots, jnp.int32)
    if slots.ndim == 1:
        slots = slots[:, None]
    R = slots.shape[0]
    if M % R:
        raise ValueError(
            f"batched_lora_matmul: {M} activation rows do not broadcast "
            f"over {R} slot rows (chunked rows must be a whole multiple)")
    rep = M // R
    row_slots = jnp.repeat(slots, rep, axis=0) if rep > 1 else slots
    n_buckets = min(int(slots.shape[1]),
                    len(a_pools), len(b_pools), len(adapter_scales))
    for j in range(n_buckets):
        out = out + batched_lora_delta(
            x2, a_pools[j], b_pools[j], adapter_scales[j],
            row_slots[:, j]).astype(out.dtype)
    return out.reshape(tuple(lead) + (N,))


# -- op registration ---------------------------------------------------------
from ..core.registry import register_op  # noqa: E402

_LORA_SLOTS = ("X", "W", "WScale", "A", "B", "AdapterScale", "Slots")
_LORA_NO_GRAD = ("W", "WScale", "A", "B", "AdapterScale", "Slots")


def _lora_args(op, ins):
    return dict(
        base_kind=str(op.attrs.get("base_kind", "dense")),
        weight_scale=(ins.get("WScale") or [None])[0],
        quant_block=int(op.attrs.get("quant_block", DEFAULT_BLOCK)
                        or DEFAULT_BLOCK))


@register_op("batched_lora_matmul", inputs=_LORA_SLOTS, outputs=("Out",),
             no_grad=_LORA_NO_GRAD, stop_gradient=True)
def _batched_lora_matmul_op(ctx, op, ins):
    x = ins["X"][0]
    if op.attrs.get("transpose_X", False) or op.attrs.get("trans_x", False):
        x = jnp.swapaxes(x, -1, -2)
    out = batched_lora_matmul(
        x, ins["W"][0], ins.get("A", []), ins.get("B", []),
        ins.get("AdapterScale", []), ins["Slots"][0], **_lora_args(op, ins))
    alpha = float(op.attrs.get("alpha", 1.0))
    if alpha != 1.0:
        out = out * alpha
    return {"Out": [out]}


@register_op("batched_lora_fc", inputs=_LORA_SLOTS, outputs=("Out",),
             no_grad=_LORA_NO_GRAD, stop_gradient=True)
def _batched_lora_fc_op(ctx, op, ins):
    # the ``mul`` twin: flatten X at x_num_col_dims, 2-D base+delta,
    # restore the leading dims (handled inside batched_lora_matmul —
    # the flattened row count is a chunk multiple of the slot rows)
    x = ins["X"][0]
    xnc = int(op.attrs.get("x_num_col_dims", 1))
    lead = x.shape[:xnc]
    x2 = x.reshape((int(np.prod(lead or (1,))), -1))
    out = batched_lora_matmul(
        x2, ins["W"][0], ins.get("A", []), ins.get("B", []),
        ins.get("AdapterScale", []), ins["Slots"][0], **_lora_args(op, ins))
    return {"Out": [out.reshape(tuple(lead) + (out.shape[-1],))]}
