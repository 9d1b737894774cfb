"""Fused layer normalization via Pallas — forward AND backward.

Reference analogue: operators/layer_norm_op.cu (hand-fused CUDA
row-reduction kernels, one of the BASELINE north-star fused ops).

Design: rows = everything before begin_norm_axis flattened, C = the
normalized extent. Grid over row blocks; each program holds a
[BLOCK_R, C] panel in VMEM, computes mean/rstd with one pass, writes
y plus the saved per-row (mean, rstd) residuals. Backward recomputes
x_hat from the residuals (no [R, C] extra residual beyond x itself):

  dx = rstd * (dy*g - mean_row(dy*g) - x_hat * mean_row(dy*g*x_hat))
  dgamma = sum_rows(dy * x_hat);  dbeta = sum_rows(dy)

dgamma/dbeta cross-row sums are per-block partials accumulated by XLA
(a [n_blocks, C] sum — tiny).

TPU layout notes (r4, first real-chip compile): every ref is >= 2D —
gamma/beta ride as [1, C] panels and the per-row mean/rstd stats are
lane-replicated [rows, 128] exactly like the flash kernels' LSE
(Mosaic's compile helper crashed on the earlier rank-1 block specs;
narrow (rows, 1) f32 layouts are the other classic trap).

Set PADDLE_TPU_KERNEL_INTERPRET=1 to run the kernels in interpreter
mode on any backend (CPU tests do this); on non-TPU backends without
the flag, callers keep the plain-XLA path.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _interpret() -> bool:
    return bool(os.environ.get("PADDLE_TPU_KERNEL_INTERPRET", ""))


def kernels_enabled() -> bool:
    # PADDLE_TPU_FUSED_KERNELS=0 is the kill switch
    if os.environ.get("PADDLE_TPU_FUSED_KERNELS", "1") == "0":
        return False
    # FORCE_PALLAS: compile the real (non-interpret) Mosaic kernels
    # regardless of the default backend — the local AOT validation
    # path (tools/aot_check.py) lowers for a v5e topology from a CPU
    # host, where default_backend() still says "cpu"
    if os.environ.get("PADDLE_TPU_FORCE_PALLAS") == "1":
        return True
    return _interpret() or jax.default_backend() == "tpu"


BLOCK_R = 256
LANES = 128  # per-row stats are lane-replicated [*, LANES] (f32 tile)


def _fwd_kernel(x_ref, g_ref, b_ref, y_ref, mean_ref, rstd_ref, *, eps):
    x = x_ref[...].astype(jnp.float32)          # [BR, C]
    mean = jnp.mean(x, axis=1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    xhat = (x - mean) * rstd
    y = xhat * g_ref[...].astype(jnp.float32) + b_ref[...].astype(jnp.float32)
    y_ref[...] = y.astype(y_ref.dtype)
    mean_ref[...] = jnp.broadcast_to(mean, mean_ref.shape).astype(jnp.float32)
    rstd_ref[...] = jnp.broadcast_to(rstd, rstd_ref.shape).astype(jnp.float32)


def _bwd_kernel(x_ref, g_ref, dy_ref, mean_ref, rstd_ref,
                dx_ref, dg_ref, db_ref):
    x = x_ref[...].astype(jnp.float32)
    dy = dy_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)           # [1, C]
    mean = mean_ref[...][:, :1]                  # [BR, 1] from [BR, LANES]
    rstd = rstd_ref[...][:, :1]
    xhat = (x - mean) * rstd
    dyg = dy * g
    m1 = jnp.mean(dyg, axis=1, keepdims=True)
    m2 = jnp.mean(dyg * xhat, axis=1, keepdims=True)
    dx = rstd * (dyg - m1 - xhat * m2)
    dx_ref[...] = dx.astype(dx_ref.dtype)
    # per-block partials, written as [1, 1, C] blocks of a rank-3
    # [n_blocks, 1, C] output: Mosaic requires the last TWO block dims
    # to be (8-divisible | equal-to-array); a rank-2 (1, C) block over
    # [n_blocks, C] violates that (round-5 local AOT check)
    dg_ref[...] = jnp.sum(dy * xhat, axis=0)[None, None]
    db_ref[...] = jnp.sum(dy, axis=0)[None, None]


def _pad_rows(a, br):
    r = a.shape[0]
    pad = (-r) % br
    if pad:
        a = jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
    return a, r


# VMEM bound: the bwd kernel holds 3 [BLOCK_R, C] float32 panels plus
# intermediates; cap C so they fit comfortably in ~16MB VMEM.
MAX_C = 4096


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def fused_layer_norm(x2, gamma, beta, eps):
    """x2 [R, C] float; gamma/beta [C]. Returns y ONLY — auxiliary
    mean/variance outputs are computed by XLA outside the custom_vjp
    (cheap, and their cotangents then flow exactly; a custom_vjp that
    returned them would silently drop grads through Mean/Variance)."""
    y, _, _ = _fwd_impl(x2, gamma, beta, eps)
    return y


def _fwd_impl(x2, gamma, beta, eps):
    """Returns y [R, C] plus LANE-REPLICATED mean/rstd [R, LANES]."""
    R, C = x2.shape
    xp, true_r = _pad_rows(x2, BLOCK_R)
    n_blocks = xp.shape[0] // BLOCK_R
    g2 = gamma.reshape(1, C)
    b2 = beta.reshape(1, C)
    y, mean, rstd = pl.pallas_call(
        functools.partial(_fwd_kernel, eps=eps),
        grid=(n_blocks,),
        in_specs=[
            pl.BlockSpec((BLOCK_R, C), lambda i: (i, 0)),
            pl.BlockSpec((1, C), lambda i: (0, 0)),
            pl.BlockSpec((1, C), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((BLOCK_R, C), lambda i: (i, 0)),
            pl.BlockSpec((BLOCK_R, LANES), lambda i: (i, 0)),
            pl.BlockSpec((BLOCK_R, LANES), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(xp.shape, x2.dtype),
            jax.ShapeDtypeStruct((xp.shape[0], LANES), jnp.float32),
            jax.ShapeDtypeStruct((xp.shape[0], LANES), jnp.float32),
        ],
        interpret=_interpret(),
        name="layer_norm_fwd",
    )(xp, g2, b2)
    return y[:true_r], mean[:true_r], rstd[:true_r]


def _vjp_fwd(x2, gamma, beta, eps):
    y, mean, rstd = _fwd_impl(x2, gamma, beta, eps)
    # residuals live from forward to backward: keep the [R] vectors,
    # not the lane-replicated [R, 128] (128x the footprint); bwd
    # re-broadcasts — XLA fuses that into the kernel's operand copy
    return y, (x2, gamma, mean[:, 0], rstd[:, 0])


def _vjp_bwd(eps, res, dy):
    x2, gamma, mean, rstd = res                  # mean/rstd [R]
    R, C = x2.shape
    xp, true_r = _pad_rows(x2, BLOCK_R)
    dyp, _ = _pad_rows(dy, BLOCK_R)
    rep = lambda v: jnp.broadcast_to(v[:, None], (R, LANES))  # noqa: E731
    meanp, _ = _pad_rows(rep(mean), BLOCK_R)
    rstdp, _ = _pad_rows(rep(rstd), BLOCK_R)
    n_blocks = xp.shape[0] // BLOCK_R
    dx, dg_part, db_part = pl.pallas_call(
        _bwd_kernel,
        grid=(n_blocks,),
        in_specs=[
            pl.BlockSpec((BLOCK_R, C), lambda i: (i, 0)),
            pl.BlockSpec((1, C), lambda i: (0, 0)),
            pl.BlockSpec((BLOCK_R, C), lambda i: (i, 0)),
            pl.BlockSpec((BLOCK_R, LANES), lambda i: (i, 0)),
            pl.BlockSpec((BLOCK_R, LANES), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((BLOCK_R, C), lambda i: (i, 0)),
            pl.BlockSpec((1, 1, C), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, 1, C), lambda i: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(xp.shape, x2.dtype),
            jax.ShapeDtypeStruct((n_blocks, 1, C), jnp.float32),
            jax.ShapeDtypeStruct((n_blocks, 1, C), jnp.float32),
        ],
        interpret=_interpret(),
        name="layer_norm_bwd",
    )(xp, gamma.reshape(1, C), dyp, meanp, rstdp)
    dgamma = jnp.sum(dg_part, axis=(0, 1)).astype(gamma.dtype)
    dbeta = jnp.sum(db_part, axis=(0, 1)).astype(gamma.dtype)
    return dx[:true_r], dgamma, dbeta


fused_layer_norm.defvjp(_vjp_fwd, _vjp_bwd)


def layer_norm_pallas(x, gamma, beta, eps, begin_norm_axis):
    """Public entry: reshape to [R, C], run the fused kernel for y,
    and let XLA produce the (differentiable) Mean/Variance outputs.
    Returns (y, mean, var) matching the layer_norm op contract.
    Returns None when C exceeds the VMEM bound — caller keeps XLA."""
    import numpy as np

    shape = x.shape
    C = int(np.prod(shape[begin_norm_axis:]))
    if C > MAX_C:
        return None
    R = int(np.prod(shape[:begin_norm_axis]))
    x2 = x.reshape(R, C)
    if gamma is None:
        gamma = jnp.ones((C,), x.dtype)
    if beta is None:
        beta = jnp.zeros((C,), x.dtype)
    y = fused_layer_norm(x2, gamma.reshape(C), beta.reshape(C), float(eps))
    # XLA-side aux outputs: exact cotangents, trivially fused
    mean = jnp.mean(x2, axis=1)
    var = jnp.var(x2, axis=1)
    return y.reshape(shape), mean, var


def layer_norm_pallas_meshed(x, gamma, beta, eps, begin_norm_axis,
                             mesh, axes):
    """Mosaic-safe meshed form: the kernel runs inside a shard_map over
    every auto mesh axis (real TPU cannot GSPMD-auto-partition Pallas —
    kernels/mesh_wrap.py). Rows are independent, so batch/sequence
    dims shard (dp/sp) and the kernel sees its local rows; gamma/beta
    replicate. Mean/Variance aux come from XLA outside the wrap.
    Returns None past the VMEM bound (caller keeps XLA)."""
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from .mesh_wrap import dim_spec, wrap_call

    shape = x.shape
    C = int(np.prod(shape[begin_norm_axis:]))
    if C > MAX_C:
        return None
    if gamma is None:
        gamma = jnp.ones((C,), x.dtype)
    if beta is None:
        beta = jnp.zeros((C,), x.dtype)
    dim_axes = {0: "dp"}
    if begin_norm_axis >= 2:
        dim_axes[1] = "sp"
    xspec = dim_spec(shape, dim_axes, mesh, axes)

    def local_fn(xl, g, b):
        return fused_layer_norm(
            xl.reshape(-1, C), g.reshape(C), b.reshape(C),
            float(eps)).reshape(xl.shape)

    y = wrap_call(mesh, axes, local_fn, (xspec, P(), P()), xspec)(
        x, gamma, beta)
    x2 = x.reshape(-1, C)
    return y, jnp.mean(x2, axis=1), jnp.var(x2, axis=1)
