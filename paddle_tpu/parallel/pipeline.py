"""Pipeline parallelism over a mesh axis.

Reference: PipelineOptimizer (optimizer.py:3414) splits the program at
cut vars into sections run by SectionWorker threads with scope queues
between devices (trainer.h:118, framework/section_worker.cc,
trainer_desc.proto:74-95).

TPU-native: the SPMD looped-pipeline pattern — every device holds one
stage's parameters (sharded on axis `pp`); microbatch activations flow
between neighbors with lax.ppermute inside shard_map; a lax.fori_loop
runs M + S - 1 ticks (GPipe schedule: fill, steady state, drain).
Backward comes from jax.grad THROUGH the loop (jax.checkpoint on the
stage fn bounds activation memory, playing the role the reference's
section scopes + 2k-1 topology did). No threads, no queues: the
schedule is compiled.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax


def _auto_axes_of(mesh, axis_name):
    return tuple(a for a in mesh.axis_names if a != axis_name)


def _pin_auto_replicated(tree, auto_axes):
    """Partial-manual hazard guard. When the pipeline axis is manual
    but other mesh axes (dp) stay GSPMD-auto, an auto-axis collective
    must complete INSIDE the branch that contains it with a
    branch-output layout identical across branches — otherwise the
    branch-output reshard lands inside a device-varying lax.switch and
    its full-mesh rendezvous deadlocks (observed: CollectivePermute
    stuck on a dp2 x mp2 x pp2 CPU mesh). Pin every branch output to
    auto-replicated. A bare PartitionSpec resolves against the CONTEXT
    mesh (auto+manual axis types); a NamedSharding(mesh, ...) would
    carry all-Auto types and fail the consistency check."""
    if not auto_axes:
        return tree
    from jax.sharding import PartitionSpec as _P

    return jax.tree_util.tree_map(
        lambda a: jax.lax.with_sharding_constraint(a, _P()), tree)


def _manual_axis_kwargs(mesh, axis_name, kwargs):
    """Restrict shard_map's manual axes to the pipeline axis so every
    other mesh axis (dp) stays GSPMD-auto inside the stages — batch
    sharding composes with the pipeline with zero manual collectives
    (round-5: the user-stack dp x pp path)."""
    if set(mesh.axis_names) != {axis_name}:
        kwargs["axis_names"] = {axis_name}
    return kwargs


def pipeline_apply(
    stage_fn: Callable,
    stage_params,
    microbatches: jax.Array,
    mesh,
    axis_name: str = "pp",
    remat: bool = True,
):
    """Run a pipeline of identical-structure stages.

    stage_fn(params, x) -> y          (same activation shape in/out)
    stage_params: pytree whose leaves have a leading stage axis S,
        sharded over `axis_name`.
    microbatches: [M, mb, ...] activations for stage 0 (replicated).

    Returns [M, mb, ...] outputs of the last stage. Differentiable —
    wrap in jax.grad for training.
    """
    from jax.sharding import PartitionSpec as P

    if remat:
        stage_fn = jax.checkpoint(stage_fn)

    n_stages = mesh.shape[axis_name]
    leaf_stages = {
        int(a.shape[0]) for a in jax.tree_util.tree_leaves(stage_params)
    }
    if leaf_stages != {n_stages}:
        raise ValueError(
            f"stage_params leading (stage) dim {sorted(leaf_stages)} must equal "
            f"mesh axis {axis_name!r} size {n_stages} — with fewer devices than "
            "stages the pipeline would silently run only the resident stages"
        )

    def per_device(params, mb):
        # params: leaves [1, ...] (this device's stage); mb: [M, ...] (replicated)
        params = jax.tree_util.tree_map(lambda a: a[0], params)
        idx = lax.axis_index(axis_name)
        M = mb.shape[0]
        total = M + n_stages - 1
        fwd_perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

        x0 = jnp.zeros_like(mb[0])
        outs0 = jnp.zeros((M,) + mb.shape[1:], mb.dtype)
        # make carry "varying" over the axis so scan types check
        x0 = x0 + jnp.zeros_like(x0) * idx.astype(mb.dtype)
        outs0 = outs0 + jnp.zeros_like(outs0) * idx.astype(mb.dtype)

        def tick(t, carry):
            inflight, outs = carry
            # stage 0 ingests microbatch t (when in range)
            mb_t = lax.dynamic_index_in_dim(mb, jnp.clip(t, 0, M - 1), 0,
                                            keepdims=False)
            x_in = jnp.where(idx == 0, mb_t, inflight)
            active = (t - idx >= 0) & (t - idx < M)
            y = stage_fn(params, x_in)
            y = jnp.where(active, y, inflight)
            # last stage writes its finished microbatch t - (S-1)
            out_slot = jnp.clip(t - (n_stages - 1), 0, M - 1)
            write = active & (idx == n_stages - 1)
            outs = lax.dynamic_update_index_in_dim(
                outs,
                jnp.where(write, y, lax.dynamic_index_in_dim(outs, out_slot, 0, False)),
                out_slot,
                0,
            )
            # rotate activations to the next stage
            inflight_next = lax.ppermute(y, axis_name, fwd_perm)
            return (inflight_next, outs)

        _, outs = lax.fori_loop(0, total, tick, (x0, outs0))
        # only the last device's buffer is real; psum of the masked
        # buffer broadcasts it AND lets shard_map prove replication
        masked = jnp.where(idx == n_stages - 1, outs, jnp.zeros_like(outs))
        return lax.psum(masked, axis_name)

    pspec = jax.tree_util.tree_map(lambda _: P(axis_name), stage_params)
    return jax.shard_map(
        per_device,
        mesh=mesh,
        in_specs=(pspec, P()),
        out_specs=P(),
    )(stage_params, microbatches)


def pipeline_schedule(
    stage_fns,
    params,
    feeds_mb,
    boundary0,
    aux0,
    mesh,
    axis_name: str = "pp",
    remat: bool = True,
):
    """GPipe fill/steady/drain schedule for S *heterogeneous* stage
    callables on one SPMD mesh axis (the Program-level pipeline path;
    `pipeline_apply` above is the stacked-weights fast path for
    identical stages).

    stage_fns: S callables ``f_s(params, boundary_in, mb_feeds, mb_idx)
        -> (boundary_out, aux)`` (mb_idx: the scalar microbatch index —
    fold it into any stage-local RNG so microbatches don't share
    dropout masks). Every stage must produce/consume ONE
    common boundary pytree structure — the SPMD analogue of the
    reference's scope-queue payload between SectionWorkers
    (framework/section_worker.cc). Only the LAST stage's aux is kept
    (earlier stages return zeros).
    params: pytree threaded to every stage, replicated. Everything a
        stage reads from the outer trace MUST come through here or
    feeds_mb, not lexical closure: closed-over jit arguments carry the
    caller mesh's Auto shardings, which clash with the Manual context.
    feeds_mb: pytree of [M, ...] microbatched feeds, replicated — each
        stage slices the microbatch it is working on.
    boundary0 / aux0: pytrees of ShapeDtypeStruct-likes (.shape/.dtype)
        fixing the carry structures; the zeros are materialized inside
        the per-device body (outside it they would carry the caller
        mesh's Auto sharding and clash with the Manual context).

    Returns aux summed over the M microbatches, replicated.
    Differentiable: lax.switch/ppermute transpose cleanly and the
    static-trip fori_loop unrolls to scan under reverse AD, so
    `jax.grad` through the schedule yields the pipelined backward
    (reverse fill/drain) without a hand-written 1F1B transpose.
    """
    from jax.sharding import PartitionSpec as P

    n_stages = mesh.shape[axis_name]
    if len(stage_fns) != n_stages:
        raise ValueError(
            f"{len(stage_fns)} pipeline stages but mesh axis {axis_name!r} "
            f"has {n_stages} devices — they must match"
        )
    if remat:
        stage_fns = [jax.checkpoint(f) for f in stage_fns]

    M = jax.tree_util.tree_leaves(feeds_mb)[0].shape[0]
    tmap = jax.tree_util.tree_map

    auto_axes = _auto_axes_of(mesh, axis_name)
    _pin_replicated = lambda tree: _pin_auto_replicated(tree, auto_axes)

    def per_device(prms, feeds):
        idx = lax.axis_index(axis_name)
        total = M + n_stages - 1
        perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
        # make carries device-varying so the loop types check under shard_map
        vary = lambda a: a + (idx * 0).astype(a.dtype)
        b0 = tmap(lambda a: vary(jnp.zeros(a.shape, a.dtype)), boundary0)
        a0 = tmap(lambda a: vary(jnp.zeros(a.shape, a.dtype)), aux0)

        def tick(t, carry):
            inflight, aux_acc = carry
            mb_idx = jnp.clip(t - idx, 0, M - 1)
            mb = tmap(
                lambda a: lax.dynamic_index_in_dim(a, mb_idx, 0, keepdims=False),
                feeds,
            )
            # every branch's outputs must carry the same varying-over-pp
            # type, but e.g. the last stage returns constant zeros for
            # its boundary — mark all outputs varying
            branches = [
                (lambda f: lambda p, b, m, i: tmap(
                    vary, _pin_replicated(f(p, b, m, i))))(f)
                for f in stage_fns
            ]
            b_out, aux = lax.switch(idx, branches, prms, inflight, mb, mb_idx)
            active = (t - idx >= 0) & (t - idx < M)
            b_out = tmap(lambda y, old: jnp.where(active, y, old), b_out, inflight)
            take = active & (idx == n_stages - 1)
            aux_acc = tmap(
                lambda acc, a: acc + jnp.where(take, a, jnp.zeros_like(a)),
                aux_acc,
                aux,
            )
            return (lax.ppermute(b_out, axis_name, perm), aux_acc)

        _, aux_acc = lax.fori_loop(0, total, tick, (b0, a0))
        # nonzero only on the last stage; psum broadcasts + proves replication
        return tmap(lambda a: lax.psum(a, axis_name), aux_acc)

    # check_vma=False: with varying-manual-axes checking ON, the
    # transpose of lax.switch/cond on a device-varying index mis-routes
    # cotangents (minimal repro: 2-device switch picking p[idx] gives
    # grad (4,0) instead of (2,5)). The schedule's replication proofs
    # are handled by the explicit psum above, so the check is safely
    # dropped.
    kwargs = _manual_axis_kwargs(mesh, axis_name, {
        "mesh": mesh, "in_specs": (P(), P()), "out_specs": P()})
    wrapped = jax.shard_map(per_device, check_vma=False, **kwargs)
    return wrapped(params, feeds_mb)


def pipeline_schedule_1f1b(
    stage_fns,
    diff_params,
    rest_params,
    feeds_mb,
    boundary0,
    aux0,
    mesh,
    axis_name: str = "pp",
    loss_index: int = 0,
    grad_scale=1.0,
):
    """1F1B schedule for S heterogeneous Program stages — the
    hand-scheduled analogue of autodiff-through-`pipeline_schedule`
    (reference SectionWorker's steady-state F/B overlap,
    framework/section_worker.cc).

    Same stage contract as `pipeline_schedule`:
    ``f_s((dv, *rest), boundary_in, mb_feeds, mb_idx) -> (b_out, aux)``
    except params arrive split: ``diff_params`` (the pytree to
    differentiate) and ``rest_params`` (tuple appended verbatim).
    The backward of each micro-op is jax.vjp of the stage against its
    stashed boundary INPUT (feeds are re-sliced by index, so only the
    boundary rings — O(S) slots, not O(M) — persist between ticks); the
    loss gradient is seeded at the last stage through the aux output
    slot ``loss_index`` scaled by ``grad_scale``.

    Returns (aux_sums, grads): aux summed over microbatches (last
    stage), grads = d(grad_scale * sum_mb loss)/d(diff_params); both
    replicated.
    """
    from jax.sharding import PartitionSpec as P

    n_stages = mesh.shape[axis_name]
    if len(stage_fns) != n_stages:
        raise ValueError(
            f"{len(stage_fns)} pipeline stages but mesh axis {axis_name!r} "
            f"has {n_stages} devices — they must match"
        )
    tmap = jax.tree_util.tree_map
    M = jax.tree_util.tree_leaves(feeds_mb)[0].shape[0]
    R = 2 * n_stages
    total = one_f_one_b_ticks(M, n_stages)
    fwd_perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
    bwd_perm = [(i, (i - 1) % n_stages) for i in range(n_stages)]
    n_aux = len(aux0)
    auto_axes = _auto_axes_of(mesh, axis_name)
    _pin_replicated = lambda tree: _pin_auto_replicated(tree, auto_axes)

    def per_device(dv, rest, feeds, gscale):
        idx = lax.axis_index(axis_name)
        vary = lambda a: a + (idx * 0).astype(a.dtype)
        stash0 = tuple(
            vary(jnp.zeros((R,) + tuple(a.shape), a.dtype)) for a in boundary0)
        fwd0 = tuple(vary(jnp.zeros(tuple(a.shape), a.dtype)) for a in boundary0)
        bwd0 = tuple(vary(jnp.zeros(tuple(a.shape), a.dtype)) for a in boundary0)
        aux_acc0 = tuple(vary(jnp.zeros((), jnp.float32)) for _ in range(n_aux))
        gacc0 = tmap(lambda p: vary(jnp.zeros_like(p)), dv)

        def mb_at(i):
            return tmap(
                lambda a: lax.dynamic_index_in_dim(a, i, 0, keepdims=False),
                feeds)

        fwd_branches = [
            (lambda f: lambda d, b, m, i: tmap(
                vary, _pin_replicated(f((d,) + tuple(rest), b, m, i))))(f)
            for f in stage_fns
        ]

        def mk_bwd(s):
            is_last = s == n_stages - 1

            def branch(d, b_saved, m, i, dy):
                def primal(d_, b_):
                    return stage_fns[s]((d_,) + tuple(rest), b_, m, i)

                _, vjp = jax.vjp(primal, d, b_saved)
                # gscale is a per-device ARG (not a closure): ratio
                # losses seed a TRACED 1/denominator, and traced
                # closures must not leak into the shard_map body
                aux_seed = tuple(
                    (gscale if (is_last and j == loss_index)
                     else jnp.zeros((), jnp.float32))
                    for j in range(n_aux))
                # the last stage's boundary output is constant zeros, so
                # its (garbage) incoming dy contributes nothing
                dd, db = vjp((dy, aux_seed))
                return (tmap(vary, _pin_replicated(dd)),
                        tmap(vary, _pin_replicated(db)))

            return branch

        bwd_branches = [mk_bwd(s) for s in range(n_stages)]

        def tick(t, carry):
            stash, fwd_in, bwd_in, gacc, aux_acc = carry
            # ---- forward micro-op: microbatch f = t - idx
            f = t - idx
            f_act = (f >= 0) & (f < M)
            fc = jnp.clip(f, 0, M - 1)
            b_out, aux = lax.switch(idx, fwd_branches, dv, fwd_in,
                                    mb_at(fc), fc)
            slot_f = jnp.mod(fc, R)
            stash = tuple(
                lax.dynamic_update_index_in_dim(
                    st,
                    jnp.where(
                        f_act, bi,
                        lax.dynamic_index_in_dim(st, slot_f, 0, False)),
                    slot_f, 0)
                for st, bi in zip(stash, fwd_in))
            take = f_act & (idx == n_stages - 1)
            aux_acc = tuple(
                acc + jnp.where(take, jnp.reshape(a, ()), 0.0)
                for acc, a in zip(aux_acc, aux))

            # ---- backward micro-op: microbatch b = t - 2(S-1) + idx
            b = t - 2 * (n_stages - 1) + idx
            b_act = (b >= 0) & (b < M)
            bc = jnp.clip(b, 0, M - 1)
            b_saved = tuple(
                lax.dynamic_index_in_dim(st, jnp.mod(bc, R), 0, False)
                for st in stash)
            dd, db = lax.switch(idx, bwd_branches, dv, b_saved, mb_at(bc),
                                bc, bwd_in)
            gacc = tmap(
                lambda acc, g: acc + jnp.where(b_act, g, jnp.zeros_like(g)),
                gacc, dd)

            fwd_next = lax.ppermute(
                tuple(jnp.where(f_act, y, o) for y, o in zip(b_out, fwd_in)),
                axis_name, fwd_perm)
            bwd_next = lax.ppermute(
                tuple(jnp.where(b_act, y, o) for y, o in zip(db, bwd_in)),
                axis_name, bwd_perm)
            return (stash, fwd_next, bwd_next, gacc, aux_acc)

        carry = (stash0, fwd0, bwd0, gacc0, aux_acc0)
        _, _, _, gacc, aux_acc = lax.fori_loop(0, total, tick, carry)
        # aux lives on the last device; each device's gacc holds its own
        # stage's contribution to the replicated params' grads
        aux_out = tuple(
            lax.psum(jnp.where(idx == n_stages - 1, a, 0.0), axis_name)
            for a in aux_acc)
        grads = tmap(lambda g: lax.psum(g, axis_name), gacc)
        return aux_out, grads

    kwargs = _manual_axis_kwargs(mesh, axis_name, {
        "mesh": mesh, "in_specs": (P(), P(), P(), P()),
        "out_specs": (P(), P())})
    wrapped = jax.shard_map(per_device, check_vma=False, **kwargs)
    return wrapped(diff_params, tuple(rest_params), feeds_mb,
                   jnp.asarray(grad_scale, jnp.float32))


def pipeline_train_step(
    stage_fn: Callable,
    loss_fn: Callable,
    mesh,
    axis_name: str = "pp",
):
    """Build a differentiable train-step: returns
    f(stage_params, microbatches, targets) -> (loss, grads)."""

    def step(stage_params, microbatches, targets):
        def loss_of(params):
            outs = pipeline_apply(stage_fn, params, microbatches, mesh, axis_name)
            return loss_fn(outs, targets)

        return jax.value_and_grad(loss_of)(stage_params)

    return step


def pipeline_train_step_3d(
    stage_fn: Callable,
    mesh,
    param_specs,
    pp_axis: str = "pp",
    dp_axis: str = "dp",
    remat: bool = True,
):
    """Full 3D parallelism on ONE mesh (round-3 verdict next-step #6:
    each axis was only ever proven alone): GPipe pipeline over
    ``pp_axis``, tensor parallelism INSIDE ``stage_fn`` (which receives
    its local parameter shards and performs its own psum over the
    tensor axis, megatron-style), and batch sharding over ``dp_axis``.

    stage_fn(params_local, x_local) -> y_local: one stage on one
        device's param shard; activation batch dim is the dp shard.
    param_specs: pytree of PartitionSpec matching stage_params — leading
        dim must be the stage axis (pp), tensor dims may name the mp
        axis; dp must NOT appear (params are dp-replicated, shard_map's
        transpose then psums the data-parallel gradient reduction).

    Returns step(stage_params, microbatches, targets) -> (loss, grads):
    microbatches/targets [M, mb, ...] sharded P(None, dp_axis, ...);
    loss is the GLOBAL mean of (y - target)^2, identical on every
    device; grads are sharded exactly like the params.
    """
    from jax.sharding import PartitionSpec as P

    if remat:
        stage_fn = jax.checkpoint(stage_fn)
    n_stages = mesh.shape[pp_axis]
    dp = mesh.shape[dp_axis]

    def _check_stage_dims(stage_params):
        bad = {a.shape[0] for a in jax.tree_util.tree_leaves(stage_params)
               if a.shape[0] != n_stages}
        if bad:
            raise ValueError(
                f"stage_params leading (stage) dims {sorted(bad)} must equal "
                f"mesh axis {pp_axis!r} size {n_stages} — the per-device "
                "shard keeps only its first slice, so extra stages would "
                "silently never run")

    def per_device_loss(params, mb, tgt):
        params = jax.tree_util.tree_map(lambda a: a[0], params)
        idx = lax.axis_index(pp_axis)
        M = mb.shape[0]
        total = M + n_stages - 1
        fwd_perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

        # derive the carries from mb so their varying-manual-axes type
        # (dp from the batch sharding) matches the loop outputs; the
        # idx term adds the pp variance
        x0 = mb[0] * 0 + jnp.zeros_like(mb[0]) * idx.astype(mb.dtype)
        outs0 = mb * 0 + jnp.zeros_like(mb) * idx.astype(mb.dtype)

        def tick(t, carry):
            inflight, outs = carry
            mb_t = lax.dynamic_index_in_dim(mb, jnp.clip(t, 0, M - 1), 0,
                                            keepdims=False)
            x_in = jnp.where(idx == 0, mb_t, inflight)
            active = (t - idx >= 0) & (t - idx < M)
            y = stage_fn(params, x_in)
            y = jnp.where(active, y, inflight)
            out_slot = jnp.clip(t - (n_stages - 1), 0, M - 1)
            write = active & (idx == n_stages - 1)
            outs = lax.dynamic_update_index_in_dim(
                outs,
                jnp.where(write, y,
                          lax.dynamic_index_in_dim(outs, out_slot, 0, False)),
                out_slot, 0,
            )
            return (lax.ppermute(y, pp_axis, fwd_perm), outs)

        _, outs = lax.fori_loop(0, total, tick, (x0, outs0))
        masked = jnp.where(idx == n_stages - 1, outs, jnp.zeros_like(outs))
        outs = lax.psum(masked, pp_axis)  # replicated over pp (+ grad path)
        # global mean: psum the dp-local sum; denominator is static
        local_sum = jnp.sum((outs - tgt) ** 2)
        global_n = outs.size * dp
        return lax.psum(local_sum, dp_axis) / global_n

    smap = jax.shard_map
    mb_spec = P(None, dp_axis)

    def step(stage_params, microbatches, targets):
        _check_stage_dims(stage_params)

        def loss_of(params):
            return smap(
                per_device_loss,
                mesh=mesh,
                in_specs=(param_specs, mb_spec, mb_spec),
                out_specs=P(),
            )(params, microbatches, targets)

        return jax.value_and_grad(loss_of)(stage_params)

    return step


def one_f_one_b_ticks(n_microbatches: int, n_stages: int) -> int:
    """Trip count of the 1F1B schedule: M + 2(S-1) lockstep ticks (each
    tick a device does its F and/or its B micro-op). GPipe-by-autodiff
    runs M+S-1 forward ticks THEN M+S-1 backward ticks = 2(M+S-1): 1F1B
    saves M-1 ticks of bubble (reference section_worker.cc's async
    section threads achieve the same overlap with queues)."""
    return n_microbatches + 2 * (n_stages - 1)


def pipeline_train_step_1f1b(
    stage_fn: Callable,
    loss_fn: Callable,
    mesh,
    axis_name: str = "pp",
):
    """1F1B pipeline train step — the schedule the reference's
    SectionWorker threads approximate (framework/section_worker.cc,
    trainer_desc.proto:74-95), compiled as one SPMD loop.

    Unlike `pipeline_train_step` (GPipe: autodiff through the fill/
    drain loop — forward of ALL M microbatches, then backward of all),
    this interleaves: device s runs the backward of microbatch b at the
    tick its cotangent arrives, so steady-state ticks do one F and one
    B each, the loop has M + 2(S-1) ticks instead of 2(M+S-1), and the
    stash of saved stage inputs is a ring of 2S slots — O(S), NOT O(M):
    activation memory stays flat as microbatch count grows.

    The backward of each micro-op is jax.vjp of the stage with its
    stashed input (recompute-from-boundary, the 1F1B analogue of the
    GPipe path's jax.checkpoint).

    stage_fn(params, x) -> y (same activation shape in/out);
    loss_fn(y_mb, target_mb) -> scalar (per-microbatch); the step loss
    is the mean over microbatches.

    Returns f(stage_params, microbatches, targets) -> (loss, grads)
    with grads matching `pipeline_train_step` whose loss_fn is the
    microbatch mean of this one.
    """
    from jax.sharding import PartitionSpec as P

    tmap = jax.tree_util.tree_map

    def step(stage_params, microbatches, targets):
        n_stages = mesh.shape[axis_name]
        M = microbatches.shape[0]
        R = 2 * n_stages  # ring capacity > max in-flight 2(S-1)
        total = one_f_one_b_ticks(M, n_stages)
        fwd_perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
        bwd_perm = [(i, (i - 1) % n_stages) for i in range(n_stages)]

        def per_device(params, mb, tgt):
            params = tmap(lambda a: a[0], params)
            idx = lax.axis_index(axis_name)
            vary = lambda a: a + (idx * 0).astype(a.dtype)

            x_shape = mb.shape[1:]
            stash0 = vary(jnp.zeros((R,) + x_shape, mb.dtype))
            fwd0 = vary(jnp.zeros(x_shape, mb.dtype))
            bwd0 = vary(jnp.zeros(x_shape, mb.dtype))
            gacc0 = tmap(lambda p: vary(jnp.zeros_like(p)), params)
            loss0 = vary(jnp.zeros((), jnp.float32))

            def last_stage_seed(y, t_idx):
                # loss + dL/dy for the microbatch the last stage just
                # finished (its F and B land on the same tick)
                tg = lax.dynamic_index_in_dim(tgt, t_idx, 0, keepdims=False)
                return jax.value_and_grad(lambda yy: loss_fn(yy, tg))(y)

            def tick(t, carry):
                stash, fwd_in, bwd_in, gacc, loss_acc = carry
                # ---- forward micro-op: microbatch f = t - idx
                f = t - idx
                f_act = (f >= 0) & (f < M)
                fc = jnp.clip(f, 0, M - 1)
                mb_f = lax.dynamic_index_in_dim(mb, fc, 0, keepdims=False)
                x_in = jnp.where(idx == 0, mb_f, fwd_in)
                y = stage_fn(params, x_in)
                slot_f = jnp.mod(fc, R)
                old = lax.dynamic_index_in_dim(stash, slot_f, 0, keepdims=False)
                stash = lax.dynamic_update_index_in_dim(
                    stash, jnp.where(f_act, x_in, old), slot_f, 0)
                loss_f, dy_last = last_stage_seed(y, fc)
                loss_acc = loss_acc + jnp.where(
                    f_act & (idx == n_stages - 1), loss_f, 0.0)

                # ---- backward micro-op: microbatch b = t - 2(S-1) + idx
                b = t - 2 * (n_stages - 1) + idx
                b_act = (b >= 0) & (b < M)
                bc = jnp.clip(b, 0, M - 1)
                # at the last stage b == f: seed from this tick's loss
                dy = jnp.where(idx == n_stages - 1, dy_last, bwd_in)
                x_saved = lax.dynamic_index_in_dim(
                    stash, jnp.mod(bc, R), 0, keepdims=False)
                _, vjp = jax.vjp(stage_fn, params, x_saved)
                dp, dx = vjp(dy.astype(y.dtype))
                gacc = tmap(
                    lambda acc, g: acc + jnp.where(b_act, g, jnp.zeros_like(g)),
                    gacc, dp)

                fwd_next = lax.ppermute(
                    jnp.where(f_act, y, fwd_in), axis_name, fwd_perm)
                bwd_next = lax.ppermute(
                    jnp.where(b_act, dx, bwd_in), axis_name, bwd_perm)
                return (stash, fwd_next, bwd_next, gacc, loss_acc)

            carry = (stash0, fwd0, bwd0, gacc0, loss0)
            _, _, _, gacc, loss_acc = lax.fori_loop(0, total, tick, carry)
            # loss lives on the last device; grads are per-stage (this
            # device's slice of the stacked [S, ...] param tree)
            loss = lax.psum(
                jnp.where(idx == n_stages - 1, loss_acc, 0.0), axis_name) / M
            grads = tmap(lambda g: (g / M)[None], gacc)
            return loss, grads

        pspec = tmap(lambda _: P(axis_name), stage_params)
        kwargs = {
            "mesh": mesh,
            "in_specs": (pspec, P(), P()),
            "out_specs": (P(), pspec),
        }
        wrapped = jax.shard_map(per_device, check_vma=False, **kwargs)
        return wrapped(stage_params, microbatches, targets)

    return step
