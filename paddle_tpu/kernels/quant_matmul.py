"""Quantized weight matmul: int8 / blockwise-int8 / fp8 weights with
scale tracking — the inference half of the raw-speed push (ROADMAP
item 5: "int8/fp8 matmul with scale tracking for the inference path").

Decode is bandwidth-bound on WEIGHT streaming: every token re-reads
every matmul weight, so the bytes of the weights — not the flops —
set the step time, and weight HBM caps how many sequences stay
resident next to the page pools. Quantizing the weights once at load
(paddle_tpu.quantize.rewrite_for_inference) cuts both by ~4x (int8)
while the arithmetic stays in fp32/bf16: the Tensor Processing
Primitives discipline (arXiv:2104.05755) — ONE primitive, a handful of
lowerings — applied to the serving stack.

Three weight formats behind one op pair:

  int8        per-OUTPUT-CHANNEL fp32 scales [N]: scale_n = max|w[:,n]|
              / 127. The scale factors out of the contraction, so the
              kernel applies it once to the accumulator tile.
  int8_block  blockwise scales [ceil(K/block), N] (the kernels/quant.py
              EQuARX block unit, applied down the contraction axis):
              one outlier poisons only its own [block] slice of a
              column — tighter error at 4/block extra scale bytes.
  fp8         float8_e4m3fn weights + per-channel fp32 scales
              (scale_n = max|w[:,n]| / 448, the e4m3 max): bf16
              compute, ~same bytes as int8 with no rounding cliff for
              near-zero weights.

Ops (both registered; proglint PTL030/PTL020-022 first-class):

  quantized_matmul   X [..., K] x QWeight [K, N] (+ Scale) -> [..., N]
                     (matmul/matmul_v2 semantics; transpose_X honored,
                     a transposed WEIGHT is ineligible at rewrite time)
  quantized_fc       the ``mul`` twin: X flattened at x_num_col_dims

Routing is the house kernel contract (flash/ragged): the custom Pallas
lowering on real TPU or under PADDLE_TPU_FORCE_PALLAS=1 (AOT rows
``quant_matmul_{int8,int8_block,fp8}``, runnable with
PT_AOT_ONLY=quant), interpreter mode under
PADDLE_TPU_KERNEL_INTERPRET=1, and the pure-JAX reference everywhere
else — the reference IS the numerics oracle AND the CPU-CI execution
path (zero Pallas dependence). The Pallas kernel dequantizes IN
REGISTERS inside the tile loop: the int8/fp8 tile loads, converts and
scales in VMEM/registers per [KB, bn] block — the fp32 weight never
exists in HBM. Scales stream as [1, bn] VMEM blocks next to their
weight tiles (one tiny row per grid step — SMEM is reserved for true
scalars; a vocab-sized scale row would not fit it anyway).
"""

from __future__ import annotations

import functools
import math
import os
from typing import Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

QUANT_MODES = ("int8", "int8_block", "fp8")
_I8MAX = 127.0
_F8MAX = 448.0  # ml_dtypes.finfo(float8_e4m3fn).max
DEFAULT_BLOCK = 256
LANES = 128


def _pallas_mode() -> Optional[str]:
    from .flash_attention import _pallas_mode as _fa_mode

    return _fa_mode()


# -- quantize / dequantize (load-time + the reference path) ------------------


def quantize_weight(w, mode: str = "int8",
                    block: int = DEFAULT_BLOCK) -> Tuple[jax.Array, jax.Array]:
    """fp32/bf16 weight [K, N] -> (qweight, scales).

    int8:       (int8 [K, N],  fp32 [N])        per-output-channel
    int8_block: (int8 [K, N],  fp32 [nb, N])    nb = ceil(K / block)
    fp8:        (e4m3 [K, N],  fp32 [N])

    All-zero columns/blocks get scale 1.0 so dequantize never divides
    by zero. Accepts numpy or jax arrays; returns jax arrays (the
    rewrite stores them device-resident in the Scope)."""
    if mode not in QUANT_MODES:
        raise ValueError(
            f"quantize_weight: mode must be one of {QUANT_MODES}, "
            f"got {mode!r}")
    w = jnp.asarray(w).astype(jnp.float32)
    if w.ndim != 2:
        raise ValueError(f"quantize_weight: expected a 2-D weight, "
                         f"got shape {w.shape}")
    K, N = w.shape
    if mode == "fp8":
        amax = jnp.max(jnp.abs(w), axis=0)
        scale = jnp.where(amax > 0, amax / _F8MAX, 1.0).astype(jnp.float32)
        return (w / scale[None, :]).astype(jnp.float8_e4m3fn), scale
    if mode == "int8":
        amax = jnp.max(jnp.abs(w), axis=0)
        scale = jnp.where(amax > 0, amax / _I8MAX, 1.0).astype(jnp.float32)
        q = jnp.clip(jnp.round(w / scale[None, :]), -_I8MAX, _I8MAX)
        return q.astype(jnp.int8), scale
    nb = -(-K // block)
    pad = nb * block - K
    wp = jnp.pad(w, ((0, pad), (0, 0))) if pad else w
    amax = jnp.max(jnp.abs(wp.reshape(nb, block, N)), axis=1)   # [nb, N]
    scale = jnp.where(amax > 0, amax / _I8MAX, 1.0).astype(jnp.float32)
    srow = jnp.repeat(scale, block, axis=0)[:K]                 # [K, N]
    q = jnp.clip(jnp.round(w / srow), -_I8MAX, _I8MAX)
    return q.astype(jnp.int8), scale


def dequantize_weight(qw, scales, mode: str = "int8",
                      block: int = DEFAULT_BLOCK):
    """Inverse of quantize_weight (fp32 for int8 modes, bf16 for fp8) —
    the oracle the kernel tests diff against; also the reference
    lowering's weight materialization."""
    if mode == "fp8":
        return qw.astype(jnp.bfloat16) * scales.astype(jnp.bfloat16)[None, :]
    w = qw.astype(jnp.float32)
    if mode == "int8":
        return w * scales[None, :]
    K = qw.shape[0]
    return w * jnp.repeat(scales, block, axis=0)[:K]


def scale_shape(weight_shape, mode: str, block: int = DEFAULT_BLOCK):
    """The scale-plane shape for a [K, N] weight under ``mode`` (what
    the program rewrite declares for the Scale variable)."""
    K, N = int(weight_shape[0]), int(weight_shape[1])
    if mode == "int8_block":
        return (-(-K // block), N)
    return (N,)


def quantized_weight_bytes(weight_shape, mode: str,
                           block: int = DEFAULT_BLOCK) -> int:
    """Bytes of (qweight + scales) for a [K, N] weight — int8 and fp8
    are both 1 byte/element, scales 4. The autotune cost model and the
    rewrite report both use this accounting."""
    K, N = int(weight_shape[0]), int(weight_shape[1])
    ss = scale_shape(weight_shape, mode, block)
    n_scales = 1
    for d in ss:
        n_scales *= d
    return K * N + 4 * n_scales


# -- reference (the oracle + the CPU-CI path) --------------------------------


def _reference_quant_matmul(x2, qw, scales, mode: str, block: int):
    wd = dequantize_weight(qw, scales, mode, block)
    if mode == "fp8":
        out = jnp.matmul(x2.astype(jnp.bfloat16), wd,
                         preferred_element_type=jnp.float32)
    else:
        out = jnp.matmul(x2.astype(jnp.float32), wd)
    return out.astype(x2.dtype)


# -- Pallas lowering ---------------------------------------------------------


def _make_quant_mm_kernel(mode: str, nk: int):
    from jax.experimental import pallas as pl

    def kernel(x_ref, w_ref, s_ref, o_ref, acc_ref):
        k = pl.program_id(2)

        @pl.when(k == 0)
        def init():  # noqa: ANN202
            acc_ref[...] = jnp.zeros_like(acc_ref)

        # dequantize-in-registers: the int8/fp8 tile converts (and, in
        # blockwise mode, scales) right here — fp32 weights never
        # exist outside this [KB, bn] tile
        if mode == "fp8":
            # Mosaic (this jax) has no f8 extension at all ("only
            # 16-bit to 32-bit extensions supported"), but int8->f32
            # works — so the wrapper bitcasts the e4m3 bytes to int8
            # and the kernel decodes them with integer math: s(1)e(4)
            # m(3), bias 7, subnormals at e=0. Every e4m3 value is
            # exact in bf16, so this matches the reference's direct
            # .astype(bf16) bit for bit (quantize_weight never emits
            # the NaN encodings 0x7f/0xff).
            x = x_ref[...].astype(jnp.bfloat16)
            u = w_ref[...].astype(jnp.int32) & 0xFF
            sign = jnp.where(u >= 128, -1.0, 1.0).astype(jnp.float32)
            e = ((u >> 3) & 0xF).astype(jnp.float32)
            man = (u & 7).astype(jnp.float32)
            mag = jnp.where(e > 0,
                            jnp.exp2(e - 7.0) * (1.0 + man * 0.125),
                            0.015625 * (man * 0.125))
            w = (sign * mag).astype(jnp.bfloat16)
        else:
            x = x_ref[...].astype(jnp.float32)
            w = w_ref[...].astype(jnp.float32)
        if mode == "int8_block":
            # one scale row per k-step: KB == block by construction
            # (s_ref block is [1, 1, bn] — the leading dim indexes k)
            w = w * s_ref[0].astype(jnp.float32)
        acc_ref[...] += jax.lax.dot_general(
            x, w, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

        @pl.when(k == nk - 1)
        def finish():  # noqa: ANN202
            acc = acc_ref[...]
            if mode != "int8_block":
                # per-channel scale factors out of the contraction:
                # applied ONCE to the finished accumulator tile
                acc = acc * s_ref[...].astype(jnp.float32)
            o_ref[...] = acc.astype(o_ref.dtype)

    return kernel


def _pad_to(a, rows: int, cols: int, fill=0):
    pr, pc = rows - a.shape[0], cols - a.shape[1]
    if pr or pc:
        a = jnp.pad(a, ((0, pr), (0, pc)), constant_values=fill)
    return a


def _quant_matmul_pallas(x2, qw, scales, mode: str, block: int,
                         interpret: bool):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    M, K = x2.shape
    N = qw.shape[1]
    KB = block if mode == "int8_block" else DEFAULT_BLOCK
    Kp = -(-K // KB) * KB
    if not interpret and mode == "int8_block":
        # Mosaic's lane constraint: the x tile's trailing dim (KB) must
        # be 128-divisible or the FULL padded K. The diagnosis lives in
        # kernels/constraints.py so the static kernel-geometry pass
        # (PTL092), the public wrapper's selection of the reference
        # and this runtime backstop can never disagree. Interpret mode
        # executes any geometry, so CPU CI still covers small blocks.
        from .constraints import int8_block_geometry_issue

        issue = int8_block_geometry_issue(K, KB)
        if issue:
            raise ValueError(issue)
    Mp = -(-M // 16) * 16              # bf16 sublane tile (covers f32)
    Np = -(-N // LANES) * LANES
    bm = next(c for c in (256, 128, 64, 32, 16) if Mp % c == 0)
    bn = LANES
    nk = Kp // KB
    xp = _pad_to(x2, Mp, Kp)
    wp = _pad_to(qw, Kp, Np)
    if mode == "fp8":
        # int8 bit-pattern view for the kernel's in-register decode
        wp = jax.lax.bitcast_convert_type(wp, jnp.int8)
    if mode == "int8_block":
        # pad scale rows for the K padding with 1.0 (the padded weight
        # rows are zeros — any scale works; 1.0 keeps them finite).
        # The k index rides a LEADING dim ([nk, 1, Np], block
        # [1, 1, bn]) so the trailing two block dims satisfy Mosaic's
        # (8, 128)-divisible-or-full constraint
        sp = _pad_to(scales, nk, Np, fill=1.0).reshape(nk, 1, Np)
        s_spec = pl.BlockSpec((1, 1, bn), lambda m, n, k: (k, 0, n))
    else:
        sp = _pad_to(scales.reshape(1, N), 1, Np, fill=1.0)
        s_spec = pl.BlockSpec((1, bn), lambda m, n, k: (0, n))
    kernel = _make_quant_mm_kernel(mode, nk)
    out = pl.pallas_call(
        kernel,
        grid=(Mp // bm, Np // bn, nk),
        in_specs=[
            pl.BlockSpec((bm, KB), lambda m, n, k: (m, k)),     # x
            pl.BlockSpec((KB, bn), lambda m, n, k: (k, n)),     # qw
            s_spec,                                             # scales
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda m, n, k: (m, n)),
        out_shape=jax.ShapeDtypeStruct((Mp, Np), x2.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
        name="quant_matmul",
    )(xp, wp, sp)
    return out[:M, :N]


# -- public entry ------------------------------------------------------------


def quantized_matmul(x, qw, scales, *, mode: str = "int8",
                     block: int = DEFAULT_BLOCK):
    """``x [..., K] @ dequant(qw [K, N])`` -> ``[..., N]`` in x's dtype.

    ``mode`` selects the weight format (see module docstring);
    ``block`` is the contraction-axis block size for ``int8_block``
    (must match the one the weight was quantized with). Leading dims
    flatten through the 2-D kernel and restore after."""
    if mode not in QUANT_MODES:
        raise ValueError(
            f"quantized_matmul: mode must be one of {QUANT_MODES}, "
            f"got {mode!r}")
    lead = x.shape[:-1]
    K = x.shape[-1]
    N = qw.shape[1]
    x2 = x.reshape(-1, K)
    m = _pallas_mode()
    if (m == "tpu" and mode == "int8_block"
            and os.environ.get("PADDLE_TPU_FORCE_PALLAS") != "1"):
        from .constraints import int8_block_geometry_issue

        # the declared geometry rule (PTL092) SELECTS the reference for
        # a block Mosaic cannot tile; under FORCE_PALLAS the kernel's
        # own guard raises instead (PTL091)
        if int8_block_geometry_issue(K, int(block)):
            m = None
    if m is not None:
        # no retry on the reference: a kernel that fails to trace,
        # lower or compile raises
        out = _quant_matmul_pallas(x2, qw, scales, mode, int(block),
                                   interpret=(m == "interpret"))
        return out.reshape(tuple(lead) + (N,))
    out = _reference_quant_matmul(x2, qw, scales, mode, int(block))
    return out.reshape(tuple(lead) + (N,))


# -- op registration ---------------------------------------------------------
from ..core.registry import register_op  # noqa: E402


@register_op("quantized_matmul",
             inputs=("X", "QWeight", "Scale"), outputs=("Out",),
             no_grad=("QWeight", "Scale"), stop_gradient=True)
def _quantized_matmul_op(ctx, op, ins):
    x, qw, s = ins["X"][0], ins["QWeight"][0], ins["Scale"][0]
    if op.attrs.get("transpose_X", False) or op.attrs.get("trans_x", False):
        x = jnp.swapaxes(x, -1, -2)
    out = quantized_matmul(
        x, qw, s, mode=str(op.attrs.get("quant_mode", "int8")),
        block=int(op.attrs.get("quant_block", DEFAULT_BLOCK)))
    alpha = float(op.attrs.get("alpha", 1.0))
    if alpha != 1.0:
        out = out * alpha
    return {"Out": [out]}


@register_op("quantized_fc",
             inputs=("X", "QWeight", "Scale"), outputs=("Out",),
             no_grad=("QWeight", "Scale"), stop_gradient=True)
def _quantized_fc_op(ctx, op, ins):
    # the ``mul`` twin (fc's inner op): flatten X at x_num_col_dims,
    # 2-D quantized matmul, restore the leading dims
    x, qw, s = ins["X"][0], ins["QWeight"][0], ins["Scale"][0]
    xnc = int(op.attrs.get("x_num_col_dims", 1))
    lead = x.shape[:xnc]
    x2 = x.reshape((int(np.prod(lead or (1,))), -1))
    out = quantized_matmul(
        x2, qw, s, mode=str(op.attrs.get("quant_mode", "int8")),
        block=int(op.attrs.get("quant_block", DEFAULT_BLOCK)))
    return {"Out": [out.reshape(tuple(lead) + (qw.shape[1],))]}
