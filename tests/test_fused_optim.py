"""Fused one-pass optimizer (kernels/fused_optim.py + the
optimizer_fuse flag): trajectory equivalence against the unfused XLA
chain on every execution path that matters — single device, dp /
ZeRO-1 / dp x tp meshes, under the PR-9 bucketed-collective program
rewrite — plus interpret-mode Pallas vs the pure-JAX oracle, strict
proglint on the rewritten program, the folded global-norm-clip seam,
and a bitwise checkpoint/resume round trip with fused state."""

import os

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import io, partition

IN, HID, CLS, BATCH = 16, 32, 4, 8


@pytest.fixture()
def _flags_guard():
    old = fluid.get_flags(["optimizer_fuse", "collective_bucket_mb",
                           "autotune_apply"])
    yield
    fluid.set_flags(old)


def _build(optimizer_factory, clip=None):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = fluid.layers.data("x", [IN])
        y = fluid.layers.data("y", [1], dtype="int64")
        h = fluid.layers.fc(
            x, HID, act="relu",
            param_attr=fluid.ParamAttr(name="fu_w1",
                                       logical_axes=("embed", "mlp")),
            bias_attr=fluid.ParamAttr(name="fu_b1", logical_axes=("mlp",)))
        logits = fluid.layers.fc(
            h, CLS, param_attr=fluid.ParamAttr(name="fu_w2",
                                               logical_axes=("mlp",
                                                             "embed")))
        loss = fluid.layers.mean(
            fluid.layers.softmax_with_cross_entropy(logits, y))
        optimizer_factory(clip).minimize(loss)
    return main, startup, loss


def _adam(clip):
    return fluid.optimizer.Adam(0.01, grad_clip=clip)


def _momentum(clip):
    return fluid.optimizer.Momentum(0.05, momentum=0.9, grad_clip=clip)


def _feed(step):
    rng = np.random.RandomState(100 + step)
    return {"x": rng.rand(BATCH, IN).astype("float32"),
            "y": (rng.rand(BATCH, 1) * CLS).astype("int64")}


def _train(fuse, opt=_adam, clip=None, steps=5, compiled=None):
    fluid.set_flags({"optimizer_fuse": "on" if fuse else "off"})
    main, startup, loss = _build(opt, clip)
    prog = compiled(main) if compiled is not None else main
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        losses = [float(exe.run(prog, feed=_feed(s),
                                fetch_list=[loss])[0])
                  for s in range(steps)]
        weights = {p.name: np.asarray(scope.find_var(p.name))
                   for p in main.all_parameters()}
    return losses, weights, main


# -- op emission -------------------------------------------------------------


def test_flag_controls_op_emission(_flags_guard):
    fluid.set_flags({"optimizer_fuse": "on"})
    main, _, _ = _build(_adam)
    ops = [op.type for op in main.global_block().ops]
    assert "fused_adam" in ops and "adam" not in ops
    fluid.set_flags({"optimizer_fuse": "off"})
    main, _, _ = _build(_adam)
    ops = [op.type for op in main.global_block().ops]
    assert "adam" in ops and "fused_adam" not in ops


def test_auto_stays_unfused_on_cpu(_flags_guard):
    # "auto" must not change CPU-CI trajectories: no TPU, no fuse
    fluid.set_flags({"optimizer_fuse": "auto"})
    main, _, _ = _build(_adam)
    assert "fused_adam" not in [op.type for op in main.global_block().ops]


def test_momentum_emits_fused_op(_flags_guard):
    fluid.set_flags({"optimizer_fuse": "on"})
    main, _, _ = _build(_momentum)
    ops = [op.type for op in main.global_block().ops]
    assert "fused_momentum" in ops and "momentum" not in ops


def test_subclasses_stay_unfused(_flags_guard):
    """Lamb extends AdamOptimizer but appends its own op — the fused
    rewrite must not hijack it."""
    fluid.set_flags({"optimizer_fuse": "on"})
    main, _, _ = _build(lambda clip: fluid.optimizer.Lamb(0.01))
    ops = [op.type for op in main.global_block().ops]
    assert "lamb" in ops and "fused_adam" not in ops


# -- trajectory equivalence --------------------------------------------------


def test_fused_adam_matches_unfused_bitwise(_flags_guard):
    l0, w0, _ = _train(False)
    l1, w1, _ = _train(True)
    assert l0 == l1
    for n in w0:
        assert (w0[n] == w1[n]).all(), n


def test_fused_momentum_matches_unfused_bitwise(_flags_guard):
    l0, w0, _ = _train(False, opt=_momentum)
    l1, w1, _ = _train(True, opt=_momentum)
    assert l0 == l1
    for n in w0:
        assert (w0[n] == w1[n]).all(), n


def test_fused_clip_fold_matches_unfused_clip(_flags_guard):
    """Global-norm clip folds into the ops' ClipScale scalar operand:
    same trajectory as the unfused clip-then-adam chain, with the
    per-grad multiply gone from the program."""
    clip = fluid.clip.GradientClipByGlobalNorm(0.3)
    l0, w0, _ = _train(False, clip=clip)
    clip = fluid.clip.GradientClipByGlobalNorm(0.3)
    l1, w1, fused_main = _train(True, clip=clip)
    assert l0 == l1
    for n in w0:
        assert (w0[n] == w1[n]).all(), n
    fused_ops = [op for op in fused_main.global_block().ops
                 if op.type == "fused_adam"]
    assert fused_ops and all("ClipScale" in op.inputs for op in fused_ops)


def test_regularization_falls_back_to_standard_chain(_flags_guard):
    """With a regularizer in play the clip cannot fold (ordering:
    clip -> reg -> update); the fused op then consumes the rewritten
    grads exactly like the unfused op did — trajectories still
    match."""
    def opt(clip):
        return fluid.optimizer.Adam(
            0.01, grad_clip=clip,
            regularization=fluid.regularizer.L2Decay(1e-4))

    clip = fluid.clip.GradientClipByGlobalNorm(0.3)
    l0, w0, _ = _train(False, opt=opt, clip=clip)
    clip = fluid.clip.GradientClipByGlobalNorm(0.3)
    l1, w1, fused_main = _train(True, opt=opt, clip=clip)
    assert l0 == l1
    for n in w0:
        assert (w0[n] == w1[n]).all(), n
    fused_ops = [op for op in fused_main.global_block().ops
                 if op.type == "fused_adam"]
    assert fused_ops and all("ClipScale" not in op.inputs
                             for op in fused_ops)


@pytest.mark.parametrize("mesh_kw", [
    {"mesh_axes": {"dp": 8}},
    {"mesh_axes": {"dp": 8}, "zero": 1},
    {"mesh_axes": {"dp": 4, "tp": 2}, "zero": 1},
], ids=["dp8", "dp8-zero1", "dp4xtp2-zero1"])
def test_fused_mesh_trajectory_matches_single_device(_flags_guard, mesh_kw):
    single, _, _ = _train(True)
    meshed, _, _ = _train(
        True, compiled=lambda m: fluid.CompiledProgram(m)
        .with_partitioning(partition.PartitionConfig(**mesh_kw)))
    np.testing.assert_allclose(single, meshed, atol=1e-5, rtol=1e-5)


def test_fused_under_bucketed_collective_rewrite(_flags_guard):
    """The PR-9 planner buckets the raw grads and repoints the fused
    ops (and the folded clip-scale producers) onto the reduced twins —
    the rewritten program must keep the single-device trajectory."""
    single, _, _ = _train(True, clip=fluid.clip.GradientClipByGlobalNorm(0.5))
    bucketed, _, _ = _train(
        True, clip=fluid.clip.GradientClipByGlobalNorm(0.5),
        compiled=lambda m: fluid.CompiledProgram(m).with_partitioning(
            partition.PartitionConfig(mesh_axes={"dp": 4}, zero=1,
                                      collective_bucket_mb=0.001)))
    np.testing.assert_allclose(single, bucketed, atol=1e-5, rtol=1e-5)


def _train_sparse(fuse, steps=5):
    """Sparse-embedding model: lookup_table_grad with is_sparse=True
    yields SelectedRows grads — the fused lowering must keep the
    unfused ops' lazy-sparse semantics (untouched rows' moments do NOT
    decay), so both paths must match bitwise."""
    fluid.set_flags({"optimizer_fuse": "on" if fuse else "off"})
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        ids = fluid.layers.data("ids", [4], dtype="int64")
        y = fluid.layers.data("y", [1], dtype="int64")
        emb = fluid.layers.embedding(
            ids, [50, 8], is_sparse=True,
            param_attr=fluid.ParamAttr(name="sp_emb"))
        pooled = fluid.layers.reduce_mean(emb, dim=1)
        loss = fluid.layers.mean(fluid.layers.softmax_with_cross_entropy(
            fluid.layers.fc(pooled, CLS), y))
        fluid.optimizer.Adam(0.01).minimize(loss)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        rng = np.random.RandomState(0)
        losses = []
        for s in range(steps):
            feed = {"ids": rng.randint(0, 50, (BATCH, 4)).astype("int64"),
                    "y": (rng.rand(BATCH, 1) * CLS).astype("int64")}
            losses.append(float(exe.run(main, feed=feed,
                                        fetch_list=[loss])[0]))
        emb_w = np.asarray(scope.find_var("sp_emb"))
    return losses, emb_w


def test_fused_sparse_grads_keep_lazy_semantics(_flags_guard):
    l0, w0 = _train_sparse(False)
    l1, w1 = _train_sparse(True)
    assert l0 == l1
    assert (w0 == w1).all()


def test_autotune_apply_mid_bind_does_not_orphan_the_bound_step(
        _flags_guard, tmp_path):
    """A profile applied inside the first bind bumps the flags
    generation; the bound step must be cached under the NEW key or
    every later run re-lowers and re-compiles the program."""
    from paddle_tpu import flags as pflags
    from paddle_tpu.runtime.dispatch import program_fingerprint

    old_dir = fluid.get_flags(["autotune_dir"])
    fluid.set_flags({"autotune_dir": str(tmp_path),
                     "autotune_apply": True})
    try:
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup), fluid.unique_name.guard():
            x = fluid.layers.data("x", [4])
            out = fluid.layers.fc(x, 3)
        fp = program_fingerprint(main)
        pflags.save_autotune_profile(fp, {"dispatch_pipeline_depth": 3})
        pflags._explicit.discard("dispatch_pipeline_depth")
        pflags._autotune_probed.discard(fp)
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(startup)
            feed = {"x": np.zeros((2, 4), "float32")}
            exe.run(main, feed=feed, fetch_list=[out])
            assert pflags.flag("dispatch_pipeline_depth") == 3
            stats = exe.cache_stats()
            exe.run(main, feed=feed, fetch_list=[out])
            after = exe.cache_stats()
        assert after["jit_compiles"] == stats["jit_compiles"]
        assert after["bound_hits"] > stats["bound_hits"]
    finally:
        fluid.set_flags(old_dir)


# -- the kernel itself -------------------------------------------------------


def test_interpret_pallas_matches_oracle(monkeypatch):
    """The Pallas lowering (interpret mode on CPU) against the
    pure-JAX reference, on deliberately tile-unaligned shapes, with
    clip + AdamW decay engaged."""
    import jax.numpy as jnp

    from paddle_tpu.kernels import fused_optim as fo

    monkeypatch.setenv("PADDLE_TPU_KERNEL_INTERPRET", "1")
    rng = np.random.RandomState(3)
    for shape in ((7,), (37, 19), (3, 5, 11)):
        p = jnp.asarray(rng.randn(*shape), jnp.float32)
        g = jnp.asarray(rng.randn(*shape), jnp.float32)
        m = jnp.asarray(rng.rand(*shape), jnp.float32)
        v = jnp.asarray(rng.rand(*shape), jnp.float32)
        clip = jnp.float32(0.7)
        got = fo.fused_adam_update(p, g, m, v, 0.01, 0.9, 0.999,
                                   beta1=0.9, beta2=0.999, epsilon=1e-8,
                                   clip_scale=clip, weight_decay=0.01)
        monkeypatch.delenv("PADDLE_TPU_KERNEL_INTERPRET")
        lr_t = jnp.float32(0.01 * np.sqrt(1 - 0.999) / (1 - 0.9))
        want = fo._reference_adam(p, g, m, v, lr_t, jnp.float32(0.01),
                                  clip, 0.9, 0.999, 1e-8, 0.01)
        monkeypatch.setenv("PADDLE_TPU_KERNEL_INTERPRET", "1")
        for a, b in zip(got, want):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-6, rtol=1e-6)

        vel = jnp.asarray(rng.rand(*shape), jnp.float32)
        got = fo.fused_momentum_update(p, g, vel, 0.1, mu=0.9,
                                       use_nesterov=True)
        monkeypatch.delenv("PADDLE_TPU_KERNEL_INTERPRET")
        want = fo._reference_momentum(p, g, vel, jnp.float32(0.1), None,
                                      0.9, True)
        monkeypatch.setenv("PADDLE_TPU_KERNEL_INTERPRET", "1")
        for a, b in zip(got, want):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-6, rtol=1e-6)


def test_reference_lowering_moves_no_more_bytes_than_the_chain():
    """The fused op off the chip lowers to ``_reference_adam``: by
    XLA's own count of bytes accessed it may not move more than the
    unfused chain it replaces (a wrapper that grew a copy or an output
    would). The Mosaic kernel's own traffic is the ``fused_adam_*``
    rows of tools/aot_check.py (``temp_bytes`` 0 on a v5e)."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.kernels import fused_optim as fo

    rng = np.random.RandomState(0)
    p = jnp.asarray(rng.randn(64, 256), jnp.float32)
    g = jnp.asarray(rng.randn(64, 256), jnp.float32)
    m, v = jnp.zeros_like(p), jnp.zeros_like(p)
    b1p, b2p = jnp.float32(0.9), jnp.float32(0.999)

    def chain(p, g, m1, m2, lr):      # ops/optim.py's adam math
        lr_t = lr * jnp.sqrt(1 - b2p) / (1 - b1p)
        m1n = 0.9 * m1 + (1 - 0.9) * g
        m2n = 0.999 * m2 + (1 - 0.999) * jnp.square(g)
        return p - lr_t * m1n / (jnp.sqrt(m2n) + 1e-8), m1n, m2n

    def fused(p, g, m1, m2, lr):
        lr_t = lr * jnp.sqrt(1 - b2p) / (1 - b1p)
        return fo._reference_adam(p, g, m1, m2, lr_t, lr, None,
                                  0.9, 0.999, 1e-8, 0.0)

    def bytes_of(fn):
        cost = jax.jit(fn).lower(p, g, m, v, jnp.float32(1e-3)) \
            .compile().cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0]
        return float(cost["bytes accessed"])

    assert bytes_of(fused) <= bytes_of(chain) * 1.01


def test_bf16_param_f32_moments(monkeypatch):
    """Mixed-precision layout (bf16 params, f32 moments) through the
    interpret-mode kernel: dtypes preserved, values near the f32
    oracle."""
    import jax.numpy as jnp

    from paddle_tpu.kernels import fused_optim as fo

    monkeypatch.setenv("PADDLE_TPU_KERNEL_INTERPRET", "1")
    rng = np.random.RandomState(4)
    p = jnp.asarray(rng.randn(33, 17), jnp.bfloat16)
    g = jnp.asarray(rng.randn(33, 17), jnp.bfloat16)
    m = jnp.zeros((33, 17), jnp.float32)
    v = jnp.zeros((33, 17), jnp.float32)
    pn, mn, vn = fo.fused_adam_update(p, g, m, v, 0.01, 0.9, 0.999,
                                      beta1=0.9, beta2=0.999,
                                      epsilon=1e-8, clip_scale=0.7)
    assert pn.dtype == jnp.bfloat16
    assert mn.dtype == jnp.float32 and vn.dtype == jnp.float32
    # the kernel rounds the clipped grad to the param dtype exactly
    # like the oracle; the remaining difference is that the kernel
    # keeps the moment arithmetic in f32 where the reference's weak-
    # scalar promotion rounds (1-beta)*g through bf16 — so bf16 parity
    # holds at bf16 resolution (f32 parity is bitwise, tested above)
    monkeypatch.delenv("PADDLE_TPU_KERNEL_INTERPRET")
    lr_t = jnp.float32(0.01 * np.sqrt(1 - 0.999) / (1 - 0.9))
    pr, mr, vr = fo._reference_adam(p, g, m, v, lr_t, jnp.float32(0.01),
                                    jnp.float32(0.7), 0.9, 0.999, 1e-8,
                                    0.0)
    np.testing.assert_allclose(np.asarray(mn), np.asarray(mr),
                               atol=1e-2, rtol=1e-2)
    np.testing.assert_allclose(np.asarray(vn), np.asarray(vr),
                               atol=1e-2, rtol=1e-2)
    np.testing.assert_allclose(np.asarray(pn, np.float32),
                               np.asarray(pr, np.float32),
                               atol=1e-2, rtol=1e-2)


def test_python_float_clip_scale_on_reference_path():
    """clip_scale accepts a plain Python float on BOTH routes (the
    reference path reshapes it — a raw float used to AttributeError)."""
    import jax.numpy as jnp

    from paddle_tpu.kernels import fused_optim as fo

    p = jnp.ones((4, 4), jnp.float32)
    g = jnp.ones((4, 4), jnp.float32)
    pn, mn, vn = fo.fused_adam_update(p, g, p * 0, p * 0, 0.01, 0.9,
                                      0.999, beta1=0.9, beta2=0.999,
                                      epsilon=1e-8, clip_scale=0.5)
    assert np.isfinite(np.asarray(pn)).all()
    pn2, vn2 = fo.fused_momentum_update(p, g, p * 0, 0.1, mu=0.9,
                                        clip_scale=0.5)
    assert np.isfinite(np.asarray(pn2)).all()


# -- program health ----------------------------------------------------------


def test_strict_proglint_on_fused_program(_flags_guard):
    from paddle_tpu.analysis import validate_for_run

    fluid.set_flags({"optimizer_fuse": "on"})
    main, _, loss = _build(_adam, fluid.clip.GradientClipByGlobalNorm(1.0))
    validate_for_run(main, fetch_names=[loss.name], feed_names=["x", "y"],
                     mode="strict", label="fused_optim")


def test_checkpoint_resume_bitwise_with_fused_state(_flags_guard, tmp_path):
    """Kill-free half of the Supervisor contract: save mid-run, resume
    in a FRESH scope, finish — final params bitwise-identical to the
    uninterrupted run (the fused state surface is exactly the unfused
    one: same accumulator vars, same commit manifest)."""
    fluid.set_flags({"optimizer_fuse": "on"})
    main, startup, loss = _build(_adam,
                                 fluid.clip.GradientClipByGlobalNorm(1.0))
    ck = str(tmp_path / "ck")

    def run(scope, exe, lo, hi):
        for s in range(lo, hi):
            exe.run(main, feed=_feed(s), fetch_list=[loss], scope=scope)

    # uninterrupted
    scope_a = fluid.Scope()
    with fluid.scope_guard(scope_a):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        run(scope_a, exe, 0, 6)
        want = {p.name: np.asarray(scope_a.find_var(p.name))
                for p in main.all_parameters()}

    # interrupted at 3 + fresh-scope resume
    scope_b = fluid.Scope()
    with fluid.scope_guard(scope_b):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        run(scope_b, exe, 0, 3)
        io.save_checkpoint(ck, main_program=main, scope=scope_b, step=3)
    scope_c = fluid.Scope()
    with fluid.scope_guard(scope_c):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        io.load_checkpoint(ck, main_program=main, scope=scope_c, step=3)
        run(scope_c, exe, 3, 6)
        got = {p.name: np.asarray(scope_c.find_var(p.name))
               for p in main.all_parameters()}
    for n in want:
        assert (want[n] == got[n]).all(), n
