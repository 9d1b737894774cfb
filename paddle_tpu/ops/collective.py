"""Collective communication ops.

Reference: operators/collective/c_allreduce_op.h:33-136, c_broadcast,
c_allgather, c_reducescatter, c_comm_init / c_gen_nccl_id (NCCL ring
setup, keyed by ring_id attr).

TPU-native redesign: a ring_id maps to a *named mesh axis*. Inside
shard_map the lowering emits a lax collective over that axis; under
plain pjit/GSPMD (where collectives are inserted automatically by XLA
from shardings) the ops are identity/annotation ops. Comm-setup ops
(c_gen_nccl_id, c_comm_init, c_sync_*_stream) are no-ops: rendezvous is
jax.distributed.initialize and XLA orders collectives itself.
"""

from __future__ import annotations

from typing import Any, Dict, List

import jax
import jax.numpy as jnp

from ..core.registry import register_op


def _axis_for(ctx, op):
    ring_id = int(op.attrs.get("ring_id", 0))
    return ctx.axis_env.get(ring_id) or ctx.axis_env.get(str(ring_id))


def _register_allreduce(name, red):
    @register_op(name, inputs=("X",), outputs=("Out",))
    def _lower(ctx, op, ins, _red=red):
        x = ins["X"][0]
        axis = _axis_for(ctx, op)
        if axis is None:
            # GSPMD path: gradient summation happens via sharding
            # propagation; op is identity.
            return {"Out": [x]}
        if _red == "sum":
            return {"Out": [jax.lax.psum(x, axis)]}
        if _red == "max":
            return {"Out": [jax.lax.pmax(x, axis)]}
        if _red == "min":
            return {"Out": [jax.lax.pmin(x, axis)]}
        if _red == "prod":
            return {"Out": [jnp.exp(jax.lax.psum(jnp.log(x), axis))]}
        raise NotImplementedError(_red)


_register_allreduce("c_allreduce_sum", "sum")
_register_allreduce("c_allreduce_max", "max")
_register_allreduce("c_allreduce_min", "min")
_register_allreduce("c_allreduce_prod", "prod")
_register_allreduce("allreduce", "sum")  # dygraph-friendly variant


@register_op("c_broadcast", inputs=("X",), outputs=("Out",))
def _c_broadcast(ctx, op, ins):
    x = ins["X"][0]
    axis = _axis_for(ctx, op)
    if axis is None:
        return {"Out": [x]}
    root = int(op.attrs.get("root", 0))
    # broadcast = select root's value on every member of the axis
    idx = jax.lax.axis_index(axis)
    masked = jnp.where(idx == root, x, jnp.zeros_like(x))
    return {"Out": [jax.lax.psum(masked, axis)]}


@register_op("broadcast", inputs=("X",), outputs=("Out",))
def _broadcast_op(ctx, op, ins):
    return _c_broadcast(ctx, op, ins)


@register_op("c_allgather", inputs=("X",), outputs=("Out",))
def _c_allgather(ctx, op, ins):
    x = ins["X"][0]
    axis = _axis_for(ctx, op)
    if axis is None:
        return {"Out": [x]}
    g = jax.lax.all_gather(x, axis)  # [axis_size, ...]
    return {"Out": [g.reshape((-1,) + x.shape[1:])]}


@register_op("c_reducescatter", inputs=("X",), outputs=("Out",))
def _c_reducescatter(ctx, op, ins):
    x = ins["X"][0]
    axis = _axis_for(ctx, op)
    if axis is None:
        return {"Out": [x]}
    return {"Out": [jax.lax.psum_scatter(x, axis, scatter_dimension=0, tiled=True)]}


@register_op("collective_bucket_reduce", inputs=("X",), outputs=("Out",),
             stop_gradient=True)
def _collective_bucket_reduce(ctx, op, ins):
    """One gradient bucket's all-reduce (parallel/collectives.py).

    Inside the planner's manual shard_map region (the lowering context
    carries ``collective_axis``/``collective_axis_size``) each input is
    a per-shard PARTIAL gradient; the op emits the cross-replica mean —
    a plain psum/size in fp32 mode, or the EQuARX-style two-shot
    blockwise-int8 exchange when the planner asked for
    ``quantization="int8"``. Because the op sits in program order right
    after the bucket's last producer, its collective is data-ready the
    moment that slice of backward finishes — XLA's latency-hiding
    scheduler can run it under the remaining backward compute instead
    of serializing every gradient behind the last one.

    Anywhere else — no mesh, a GSPMD-auto compile, the gradient-merge
    or pipeline paths — the inputs are already LOGICAL (fully reduced)
    gradients and the op is identity, so a planned program degrades to
    exactly the monolithic PR-8 semantics.
    """
    xs = ins["X"]
    env = ctx.axis_env or {}
    axis = env.get("collective_axis")
    if axis is None or env.get("collective_skip_reduce"):
        # collective_skip_reduce (CollectivePlan.skip_reduce, which no
        # caller sets): same program shape, collectives elided
        return {"Out": list(xs)}
    size = int(env.get("collective_axis_size", 1))
    quantized = op.attrs.get("quantization", "none") == "int8"
    block = int(op.attrs.get("quant_block", 256))
    # the real int8 all-to-all/all-gather exchange requires a
    # FULLY-manual region; inside a partial-manual one (dp x tp mesh)
    # XLA's manual-subgroup partitioner only lowers psum, so the
    # numerics-equivalent psum form runs there
    exchange = bool(env.get("collective_exchange_ok", True))

    if not quantized:
        # fp32: one psum per gradient, grouped at the bucket point.
        # Deliberately NOT flattened into one payload: the psum is
        # elementwise either way, but slicing grads back out of a flat
        # buffer reshapes the tensors downstream consumers reduce over
        # (clip-by-global-norm's sum of squares), changing summation
        # order — and the bucketed fp32 path is contractually
        # BIT-identical to the monolithic one. XLA combines adjacent
        # same-ready all-reduces itself where profitable.
        inv = 1.0 / size
        return {"Out": [jax.lax.psum(x, axis) * jnp.asarray(inv, x.dtype)
                        for x in xs]}

    # int8: the bucket reduces as ONE flat payload (per dtype): one
    # quantized exchange per bucket instead of one per gradient, so
    # block + dp-chunk padding amortize over the whole bucket (a
    # 4-element bias grad would otherwise pad to a full block times a
    # dp multiple and cost MORE wire than fp32)
    from ..kernels.quant import quantized_mean

    out: List[Any] = [None] * len(xs)
    by_dtype: Dict[Any, List[int]] = {}
    for i, x in enumerate(xs):
        by_dtype.setdefault(jnp.dtype(x.dtype), []).append(i)
    for dt, idxs in by_dtype.items():
        flat = (xs[idxs[0]].reshape(-1) if len(idxs) == 1 else
                jnp.concatenate([xs[i].reshape(-1) for i in idxs]))
        red = quantized_mean(flat, axis, size, block, exchange=exchange)
        off = 0
        for i in idxs:
            n = xs[i].size
            out[i] = jax.lax.dynamic_slice_in_dim(
                red, off, n).reshape(xs[i].shape)
            off += n
    return {"Out": out}


def _register_noop(name, slots=("X",)):
    @register_op(name, inputs=slots, outputs=("Out",), stop_gradient=True)
    def _lower(ctx, op, ins):
        vals = ins.get(slots[0], []) if slots else []
        return {"Out": list(vals)}


# comm setup / stream ordering: subsumed by jax.distributed + XLA
_register_noop("c_comm_init", ())
_register_noop("c_comm_init_all", ())
_register_noop("c_gen_nccl_id", ())
_register_noop("c_sync_calc_stream")
_register_noop("c_sync_comm_stream")
_register_noop("c_wait_comm", ())
_register_noop("c_wait_compute", ())


@register_op("local_sgd_select", inputs=("Step", "Avg", "Param"), outputs=("Out",), stop_gradient=True)
def _local_sgd_select(ctx, op, ins):
    """Gate for LocalSGD (transpiler/collective.py): take the
    cross-replica average only every `every` steps, else keep the local
    param (reference LocalSGD's conditional communication)."""
    step = ins["Step"][0].reshape(())
    every = float(op.attrs.get("every", 1.0))
    sync = jnp.mod(step, every) < 0.5
    return {"Out": [jnp.where(sync, ins["Avg"][0], ins["Param"][0])]}
