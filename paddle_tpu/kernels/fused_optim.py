"""One-pass fused optimizer updates (Adam / AdamW / Momentum) via Pallas.

Reference analogue: operators/optimizers/adam_op.cu runs the whole
m/v/param update as ONE CUDA kernel per parameter; the TPU-native seed
deliberately left Adam to XLA ("a pure elementwise chain that XLA
already fuses") — but the lowered HLO for a ZeRO-sharded train step
shows the optimizer tail as a CHAIN of fusions, each reading and
writing full state tensors: m is read+written, v is read+written, p is
read+written, and the intermediate m'/(sqrt(v')+eps) quotient
materializes besides. Tensor Processing Primitives (arXiv:2104.05755)
makes the case that this bandwidth-bound tail is exactly where a small
fused primitive pays: one pass reads (p, g, m, v) once, writes
(p', m', v') once, and ``input_output_aliases`` lets Mosaic update the
donated buffers in place — the optimizer step moves the theoretical
minimum of HBM bytes.

Three ops share one lowering skeleton:

  fused_adam      m' = b1*m + (1-b1)*g;  v' = b2*v + (1-b2)*g^2
                  p' = p - lr_t * m'/(sqrt(v')+eps)
                  (lr_t carries the bias correction, computed XLA-side
                  from the [1]-shaped beta-pow state — scalars ride in
                  SMEM, never a VMEM panel)
  fused_adamw     fused_adam + decoupled decay  p' -= lr*coeff*p
  fused_momentum  vel' = mu*vel + g;  p' = p - lr*vel'
                  (nesterov: p' = p - lr*(g + mu*vel'))

The global-norm clip seam: the op accepts an optional ``ClipScale``
scalar operand and applies ``g * scale`` INSIDE the pass. The
optimizer folds ``GradientClipByGlobalNorm`` into that scalar (the
norm reduction still runs XLA-side), so clipping costs zero extra
full-tensor reads — and because the scale's producers consume the raw
gradients, the PR-9 collective planner repoints them to the reduced
twins exactly as it repointed the unfused clip ops.

Routing is the house kernel contract (layer_norm/flash): real Mosaic
on TPU or under ``PADDLE_TPU_FORCE_PALLAS=1`` (the AOT-check path),
interpreter mode under ``PADDLE_TPU_KERNEL_INTERPRET=1``, and the
pure-JAX reference everywhere else — the reference IS the numerics
oracle, written to be op-for-op identical to the unfused
``ops/optim.py`` chain so fused-vs-unfused trajectories match bitwise
on CPU CI.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .layer_norm import _interpret, kernels_enabled

LANES = 128
# rows are padded to a multiple of 16 (the bf16 sublane tile; also a
# multiple of the f32 tile 8) so one panel layout serves every dtype
ROW_PAD = 16
MAX_BLOCK_R = 512  # 512x128 f32 x 7 live panels ~= 1.8 MB VMEM


def _panels(a):
    """Flatten to [R, LANES] with R a multiple of ROW_PAD. Returns the
    panel array and the true element count (padding is zeros — inert
    through every update rule here: 0-grad, 0-moment rows stay 0)."""
    n = int(a.size)
    rows = -(-n // LANES)
    rows += (-rows) % ROW_PAD
    flat = a.reshape(-1)
    pad = rows * LANES - n
    if pad:
        flat = jnp.pad(flat, (0, pad))
    return flat.reshape(rows, LANES), n


def _unpanel(panel, n, shape):
    return panel.reshape(-1)[:n].reshape(shape)


def _block_rows(rows: int) -> int:
    for c in (MAX_BLOCK_R, 256, 128, 64, 32, 16):
        if rows % c == 0:
            return c
    return rows


# -- kernels -----------------------------------------------------------------
# scal is a (1, 4) float32 SMEM panel: [lr_t, lr, clip_scale, unused]


def _adam_kernel(scal_ref, p_ref, g_ref, m_ref, v_ref,
                 po_ref, mo_ref, vo_ref, *, beta1, beta2, eps, coeff):
    lr_t = scal_ref[0, 0]
    lr = scal_ref[0, 1]
    clip = scal_ref[0, 2]
    p = p_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32) * clip
    if po_ref.dtype != jnp.float32:
        # the reference (== the unfused chain) rounds the clipped grad
        # to the param dtype before the moment update; match it so the
        # bf16 kernel and the CPU oracle see the same inputs
        g = g.astype(po_ref.dtype).astype(jnp.float32)
    m = beta1 * m_ref[...].astype(jnp.float32) + (1.0 - beta1) * g
    v = beta2 * v_ref[...].astype(jnp.float32) + (1.0 - beta2) * (g * g)
    p_new = p - lr_t * m / (jnp.sqrt(v) + eps)
    if coeff:
        # decoupled weight decay (AdamW): on the ORIGINAL p, scaled by
        # the raw lr — matching ops/optim.py's adamw composition
        p_new = p_new - lr * coeff * p
    po_ref[...] = p_new.astype(po_ref.dtype)
    mo_ref[...] = m.astype(mo_ref.dtype)
    vo_ref[...] = v.astype(vo_ref.dtype)


def _momentum_kernel(scal_ref, p_ref, g_ref, vel_ref,
                     po_ref, velo_ref, *, mu, nesterov):
    lr = scal_ref[0, 1]
    clip = scal_ref[0, 2]
    p = p_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32) * clip
    if po_ref.dtype != jnp.float32:
        g = g.astype(po_ref.dtype).astype(jnp.float32)
    vel = mu * vel_ref[...].astype(jnp.float32) + g
    if nesterov:
        p_new = p - lr * (g + mu * vel)
    else:
        p_new = p - lr * vel
    po_ref[...] = p_new.astype(po_ref.dtype)
    velo_ref[...] = vel.astype(velo_ref.dtype)


def _run_fused(kernel, scal, arrays, n_out: int, name: str):
    """Shared pallas_call driver: panels every array, grids over row
    blocks, aliases state inputs onto their outputs (in-place over the
    executor's donated buffers), un-panels the results. ``name`` is the
    caller's kernel name, as a device trace shows it."""
    shape = arrays[0].shape
    panels = []
    n = None
    for a in arrays:
        pa, na = _panels(a)
        n = na if n is None else n
        panels.append(pa)
    rows = panels[0].shape[0]
    br = _block_rows(rows)
    panel_spec = pl.BlockSpec((br, LANES), lambda i: (i, 0))
    # inputs: (scal, p, g, state...); outputs (p', state'...) — p and
    # every state panel alias their output slot; g (index 2) does not
    aliases = {1: 0}
    for j in range(n_out - 1):
        aliases[3 + j] = 1 + j
    outs = pl.pallas_call(
        kernel,
        grid=(rows // br,),
        in_specs=[pl.BlockSpec((1, 4), lambda i: (0, 0),
                               memory_space=pltpu.SMEM)]
        + [panel_spec] * len(panels),
        out_specs=[panel_spec] * n_out,
        out_shape=[jax.ShapeDtypeStruct((rows, LANES), a.dtype)
                   for a in ([arrays[0]] + list(arrays[2:2 + n_out - 1]))],
        input_output_aliases=aliases,
        interpret=_interpret(),
        name=name,
    )(scal, *panels)
    return tuple(_unpanel(o, n, shape) for o in outs)


def _scal(lr_t, lr, clip):
    vals = jnp.stack([
        jnp.asarray(lr_t, jnp.float32).reshape(()),
        jnp.asarray(lr, jnp.float32).reshape(()),
        (jnp.asarray(clip, jnp.float32).reshape(())
         if clip is not None else jnp.float32(1.0)),
        jnp.float32(0.0),
    ])
    return vals.reshape(1, 4)


# -- references (the CPU-CI path AND the numerics oracle) --------------------
# Op-for-op the unfused ops/optim.py chain, so fused-vs-unfused
# trajectories agree bitwise on one backend.


def _reference_adam(p, g, m1, m2, lr_t, lr, clip, beta1, beta2, eps, coeff):
    if clip is not None:
        g = g * clip.reshape(())
    g = g.astype(p.dtype)
    m1n = beta1 * m1 + (1 - beta1) * g
    m2n = beta2 * m2 + (1 - beta2) * jnp.square(g)
    p_new = p - lr_t * m1n / (jnp.sqrt(m2n) + eps)
    if coeff:
        p_new = p_new - lr * coeff * p
    return p_new, m1n, m2n


def _reference_momentum(p, g, vel, lr, clip, mu, nesterov):
    if clip is not None:
        g = g * clip.reshape(())
    g = g.astype(p.dtype)
    vel_new = mu * vel + g
    if nesterov:
        p_new = p - lr * (g + mu * vel_new)
    else:
        p_new = p - lr * vel_new
    return p_new, vel_new


# -- public entry points -----------------------------------------------------


def fused_adam_update(p, g, m1, m2, lr, beta1_pow, beta2_pow, *,
                      beta1: float = 0.9, beta2: float = 0.999,
                      epsilon: float = 1e-8,
                      clip_scale=None,
                      weight_decay: float = 0.0,
                      ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One-pass Adam(W): returns (p', m1', m2'). The beta-pow updates
    stay with the caller (tiny [1] state). ``clip_scale`` is the folded
    global-norm clip factor; ``weight_decay`` > 0 selects the AdamW
    decoupled-decay tail."""
    lr = jnp.asarray(lr, jnp.float32).reshape(())
    b1p = jnp.asarray(beta1_pow, jnp.float32).reshape(())
    b2p = jnp.asarray(beta2_pow, jnp.float32).reshape(())
    if clip_scale is not None:
        clip_scale = jnp.asarray(clip_scale, jnp.float32)
    lr_t = lr * jnp.sqrt(1 - b2p) / (1 - b1p)
    if not kernels_enabled():
        return _reference_adam(p, g, m1, m2, lr_t, lr, clip_scale,
                               beta1, beta2, epsilon, weight_decay)
    kernel = functools.partial(
        _adam_kernel, beta1=float(beta1), beta2=float(beta2),
        eps=float(epsilon), coeff=float(weight_decay))
    return _run_fused(kernel, _scal(lr_t, lr, clip_scale),
                      (p, g, m1, m2), 3, "fused_adam")


def fused_momentum_update(p, g, vel, lr, *, mu: float = 0.9,
                          use_nesterov: bool = False,
                          clip_scale=None,
                          ) -> Tuple[jax.Array, jax.Array]:
    """One-pass SGD-momentum: returns (p', vel')."""
    lr = jnp.asarray(lr, jnp.float32).reshape(())
    if clip_scale is not None:
        clip_scale = jnp.asarray(clip_scale, jnp.float32)
    if not kernels_enabled():
        return _reference_momentum(p, g, vel, lr, clip_scale,
                                   float(mu), bool(use_nesterov))
    kernel = functools.partial(_momentum_kernel, mu=float(mu),
                               nesterov=bool(use_nesterov))
    return _run_fused(kernel, _scal(lr, lr, clip_scale), (p, g, vel), 2,
                      "fused_momentum")


def optimizer_fuse_enabled() -> bool:
    """The ``optimizer_fuse`` live flag: "on"/"off" force; "auto" (the
    default) fuses exactly on real TPU targets (or under
    PADDLE_TPU_FORCE_PALLAS=1, the AOT-check path). CPU CI — including
    interpreter-mode kernel runs — keeps the unfused chain unless a
    test opts in explicitly, so the fused path never silently changes
    seed-test trajectories (and interpret-mode Pallas never lands on
    the full-size bench models' optimizer tail)."""
    import os

    from ..flags import flag

    v = str(flag("optimizer_fuse")).lower()
    if v in ("on", "1", "true", "yes"):
        return True
    if v in ("off", "0", "false", "no"):
        return False
    if os.environ.get("PADDLE_TPU_FUSED_KERNELS", "1") == "0":
        return False
    return (jax.default_backend() == "tpu"
            or os.environ.get("PADDLE_TPU_FORCE_PALLAS") == "1")


# -- op registration ---------------------------------------------------------
from ..core.registry import register_op  # noqa: E402
from ..core.selected_rows import SelectedRows  # noqa: E402


def _sparse_ins(ins):
    """SelectedRows grads keep the UNFUSED ops' lazy-sparse semantics
    (only touched rows' moments update — densifying would decay every
    row and change trajectories). The fused lowerings delegate to the
    unfused ones in that case, pre-applying the folded clip scale to
    the sparse values (== the clipped gradient)."""
    g = ins["Grad"][0]
    if not isinstance(g, SelectedRows):
        return None
    ins = dict(ins)
    if ins.get("ClipScale"):
        s = ins["ClipScale"][0].reshape(())
        ins["Grad"] = [SelectedRows(g.rows, g.values * s, g.height)]
    return ins


def _wrap_spec(ctx, op, shape):
    """The ONE PartitionSpec shared by every full-tensor operand of a
    wrapped fused update (p/g/state must partition identically or the
    elementwise kernel's blocks stop lining up): the stamped sharding
    of the first moment/velocity accumulator, else the param's — this
    keeps a ZeRO-sharded update LOCAL to each shard's slice (the param
    splits along the moment spec; the executor's out_shardings
    all-gather the written param back, which IS the ZeRO update
    pattern). Axes that are absent from the mesh or don't divide the
    dim are dropped (replicated — wasteful, never wrong)."""
    from jax.sharding import PartitionSpec as P

    ss = getattr(ctx, "state_shardings", None) or {}
    axis_size = dict(ctx.mesh.shape)
    cand = None
    for slot in ("Moment1", "Velocity", "Param"):
        for n in (getattr(op, "inputs", None) or {}).get(slot, ()):
            if ss.get(n) is not None:
                cand = tuple(ss[n])
                break
        if cand is not None:
            break
    if cand is None:
        return P()
    names = []
    for d in range(len(shape)):
        e = cand[d] if d < len(cand) else None
        axes_t = () if e is None else (
            (e,) if isinstance(e, str) else tuple(e))
        k = 1
        for a in axes_t:
            k *= int(axis_size.get(a, 0))
        names.append(e if (axes_t and k and shape[d] % k == 0) else None)
    return P(*names)


def _mesh_route(ctx):
    """('wrap', mesh, axes) when the Pallas pass must run inside a
    shard_map (GSPMD cannot auto-partition Mosaic calls — the same
    round-5 finding kernels/mesh_wrap.py encodes); 'direct' on single
    device / fully-manual regions; 'xla' = keep the reference."""
    from .mesh_wrap import mode

    if not kernels_enabled():
        return "xla", None, ()
    return mode(ctx)


def _lower_fused_adam(ctx, op, ins, default_coeff):
    sparse = _sparse_ins(ins)
    if sparse is not None:
        from ..ops import optim as _optim

        out = _optim._adam(ctx, op, sparse)
        coeff = float(op.attrs.get("coeff", default_coeff))
        if coeff:
            # decoupled decay is dense on the whole param, exactly as
            # the unfused adamw composition applies it
            lr = ins["LearningRate"][0].reshape(())
            out["ParamOut"] = [out["ParamOut"][0]
                               - lr * coeff * ins["Param"][0]]
        return out
    p, g = ins["Param"][0], ins["Grad"][0]
    m1, m2 = ins["Moment1"][0], ins["Moment2"][0]
    b1p, b2p = ins["Beta1Pow"][0], ins["Beta2Pow"][0]
    beta1 = float(op.attrs.get("beta1", 0.9))
    beta2 = float(op.attrs.get("beta2", 0.999))
    eps = float(op.attrs.get("epsilon", 1e-8))
    coeff = float(op.attrs.get("coeff", default_coeff))
    clip = ins["ClipScale"][0] if ins.get("ClipScale") else None
    lr = ins["LearningRate"][0].reshape(())
    route, wmesh, waxes = _mesh_route(ctx)
    if route == "wrap":
        from jax.sharding import PartitionSpec as P

        spec = _wrap_spec(ctx, op, p.shape)
        clip_in = (jnp.asarray(clip, jnp.float32).reshape(())
                   if clip is not None else jnp.float32(1.0))

        def local(pl_, gl, m1l, m2l, lrl, b1l, b2l, cl):
            return fused_adam_update(
                pl_, gl, m1l, m2l, lrl, b1l, b2l, beta1=beta1,
                beta2=beta2, epsilon=eps, clip_scale=cl,
                weight_decay=coeff)

        from .mesh_wrap import wrap_call

        # g passes through UNCAST: the kernel applies ClipScale first
        # and then rounds to the param dtype, exactly like the
        # reference — casting here would double-round the bf16 path
        p_new, m1n, m2n = wrap_call(
            wmesh, waxes, local,
            (spec, spec, spec, spec, P(), P(), P(), P()),
            (spec, spec, spec),
        )(p, g, m1, m2, lr, b1p.reshape(()), b2p.reshape(()), clip_in)
    elif route == "xla" and kernels_enabled():
        # nested partial-manual region: neither auto-partitioning nor
        # another partial shard_map is safe — keep the reference form
        lr_t = (lr * jnp.sqrt(1 - b2p.reshape(()))
                / (1 - b1p.reshape(())))
        p_new, m1n, m2n = _reference_adam(
            p, g, m1, m2, lr_t, lr, clip, beta1, beta2, eps, coeff)
    else:
        p_new, m1n, m2n = fused_adam_update(
            p, g, m1, m2, lr, b1p, b2p, beta1=beta1, beta2=beta2,
            epsilon=eps, clip_scale=clip, weight_decay=coeff)
    return {
        "ParamOut": [p_new],
        "Moment1Out": [m1n],
        "Moment2Out": [m2n],
        "Beta1PowOut": [b1p * beta1],
        "Beta2PowOut": [b2p * beta2],
    }


@register_op(
    "fused_adam",
    inputs=("Param", "Grad", "LearningRate", "Moment1", "Moment2",
            "Beta1Pow", "Beta2Pow", "ClipScale"),
    outputs=("ParamOut", "Moment1Out", "Moment2Out", "Beta1PowOut",
             "Beta2PowOut"),
    stop_gradient=True,
)
def _fused_adam_op(ctx, op, ins):
    return _lower_fused_adam(ctx, op, ins, 0.0)


@register_op(
    "fused_adamw",
    inputs=("Param", "Grad", "LearningRate", "Moment1", "Moment2",
            "Beta1Pow", "Beta2Pow", "ClipScale"),
    outputs=("ParamOut", "Moment1Out", "Moment2Out", "Beta1PowOut",
             "Beta2PowOut"),
    stop_gradient=True,
)
def _fused_adamw_op(ctx, op, ins):
    return _lower_fused_adam(ctx, op, ins, 0.01)


@register_op(
    "fused_momentum",
    inputs=("Param", "Grad", "Velocity", "LearningRate", "ClipScale"),
    outputs=("ParamOut", "VelocityOut"),
    stop_gradient=True,
)
def _fused_momentum_op(ctx, op, ins):
    sparse = _sparse_ins(ins)
    if sparse is not None:
        from ..ops import optim as _optim

        return _optim._momentum(ctx, op, sparse)
    p, g, vel = ins["Param"][0], ins["Grad"][0], ins["Velocity"][0]
    clip = ins["ClipScale"][0] if ins.get("ClipScale") else None
    lr = ins["LearningRate"][0].reshape(())
    mu = float(op.attrs.get("mu", 0.9))
    nesterov = bool(op.attrs.get("use_nesterov", False))
    route, wmesh, waxes = _mesh_route(ctx)
    if route == "wrap":
        from jax.sharding import PartitionSpec as P

        from .mesh_wrap import wrap_call

        spec = _wrap_spec(ctx, op, p.shape)
        clip_in = (jnp.asarray(clip, jnp.float32).reshape(())
                   if clip is not None else jnp.float32(1.0))

        def local(pl_, gl, vl, lrl, cl):
            return fused_momentum_update(pl_, gl, vl, lrl, mu=mu,
                                         use_nesterov=nesterov,
                                         clip_scale=cl)

        p_new, vel_new = wrap_call(
            wmesh, waxes, local, (spec, spec, spec, P(), P()),
            (spec, spec))(p, g, vel, lr, clip_in)
    elif route == "xla" and kernels_enabled():
        p_new, vel_new = _reference_momentum(p, g, vel, lr, clip, mu,
                                             nesterov)
    else:
        p_new, vel_new = fused_momentum_update(
            p, g, vel, lr, mu=mu, use_nesterov=nesterov, clip_scale=clip)
    return {"ParamOut": [p_new], "VelocityOut": [vel_new]}
