"""Hot-path dispatch + compilation caching (runtime/dispatch):
counters, cross-executor compile sharing, device-array fetches,
stale-scope invalidation, persistent-cache location, sharded-feed
validation, partial-manual shard_map kwargs."""

import os

import numpy as np
import pytest

import paddle_tpu as fluid


def _mlp():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = fluid.layers.data("x", [8])
        y = fluid.layers.data("y", [1], dtype="int64")
        h = fluid.layers.fc(x, 8, act="relu")
        loss = fluid.layers.mean(
            fluid.layers.softmax_with_cross_entropy(
                fluid.layers.fc(h, 4), y))
        fluid.optimizer.SGD(0.1).minimize(loss)
    return main, startup, loss


def _feed(batch=4):
    rng = np.random.RandomState(0)
    return {"x": rng.rand(batch, 8).astype("float32"),
            "y": np.zeros((batch, 1), "int64")}


def test_bound_step_hit_miss_counters():
    main, startup, loss = _mlp()
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        feed = _feed()
        exe.run(main, feed=feed, fetch_list=[loss])
        st = exe.cache_stats()
        assert st["bound_misses"] == 2  # startup + main first-run
        assert st["jit_compiles"] == 2
        for _ in range(3):
            exe.run(main, feed=feed, fetch_list=[loss])
        st = exe.cache_stats()
        assert st["bound_hits"] == 3
        assert st["bound_misses"] == 2  # no new misses
        assert st["jit_compiles"] == 2  # no recompiles
        assert st["compile_time_s"] > 0
        # a NEW feed shape is a new signature: one more miss+compile
        exe.run(main, feed=_feed(batch=6), fetch_list=[loss])
        st = exe.cache_stats()
        assert st["bound_misses"] == 3
        assert st["jit_compiles"] == 3


def test_no_recompile_across_executor_instances():
    """The predictor/PS clone-per-thread pattern: a second Executor
    running the same program must not re-jit — served by the shared
    compiled-block cache, reported via cache_stats()."""
    main, startup, loss = _mlp()
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe1 = fluid.Executor(fluid.CPUPlace())
        exe1.run(startup)
        feed = _feed()
        (l1,) = exe1.run(main, feed=feed, fetch_list=[loss])

        exe2 = fluid.Executor(fluid.CPUPlace())
        (l2,) = exe2.run(main, feed=feed, fetch_list=[loss])
        st2 = exe2.cache_stats()
        assert st2["jit_compiles"] == 0, st2
        assert st2["shared_cache_hits"] == 1, st2
        assert np.isfinite(l2)


def test_no_recompile_for_content_identical_clone():
    """program.clone() has a new uid but identical IR — the canonical
    fingerprint must route it to the already-compiled executable."""
    main, startup, loss = _mlp()
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        feed = _feed()
        exe.run(main, feed=feed, fetch_list=[loss])
        before = exe.cache_stats()["jit_compiles"]

        clone = main.clone()
        exe.run(clone, feed=feed, fetch_list=[loss.name])
        assert exe.cache_stats()["jit_compiles"] == before, (
            "content-identical clone re-jitted")


def test_return_numpy_false_returns_device_arrays():
    import jax

    main, startup, loss = _mlp()
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        # both the bind step and the cached-BoundStep step
        for _ in range(2):
            (out,) = exe.run(main, feed=_feed(), fetch_list=[loss],
                             return_numpy=False)
            assert isinstance(out, jax.Array), type(out)
        (out,) = exe.run(main, feed=_feed(), fetch_list=[loss])
        assert isinstance(out, np.ndarray)


def test_stale_scope_invalidation_on_set_var():
    """External scope.set_var between steps must be visible to the next
    step (the BoundStep re-resolves its cached state refs on the scope
    generation bump)."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = fluid.layers.data("x", [3])
        pred = fluid.layers.fc(x, 1, bias_attr=False)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        w_name = main.all_parameters()[0].name
        xv = np.ones((2, 3), "float32")
        exe.run(main, feed={"x": xv}, fetch_list=[pred])  # bind + warm
        scope.set_var(w_name, np.zeros((3, 1), "float32"))
        (out,) = exe.run(main, feed={"x": xv}, fetch_list=[pred])
        np.testing.assert_allclose(out, np.zeros((2, 1)), atol=0)
        scope.set_var(w_name, np.ones((3, 1), "float32"))
        (out,) = exe.run(main, feed={"x": xv}, fetch_list=[pred])
        np.testing.assert_allclose(out, np.full((2, 1), 3.0), rtol=1e-6)


def test_scope_updates_seen_across_programs_sharing_scope():
    """Train/eval alternation over one scope: the eval program's bound
    step must see the params the train step just wrote."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = fluid.layers.data("x", [2])
        pred = fluid.layers.fc(x, 1, bias_attr=False)
        loss = fluid.layers.mean(pred)
        fluid.optimizer.SGD(0.5).minimize(loss)
    test_prog = main.clone(for_test=True)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        xv = np.ones((4, 2), "float32")
        evals = []
        for _ in range(3):
            (e,) = exe.run(test_prog, feed={"x": xv}, fetch_list=[pred])
            evals.append(float(e.mean()))
            exe.run(main, feed={"x": xv}, fetch_list=[loss])
        # SGD on mean(pred) strictly decreases pred each step; a stale
        # eval BoundStep would repeat the same value
        assert evals[0] > evals[1] > evals[2], evals


def test_program_mutation_invalidates_bound_step():
    """Appending an op bumps program.version: the bound path must not
    serve the stale executable."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = fluid.layers.data("x", [2])
        out = fluid.layers.scale(x, scale=2.0)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        xv = np.ones((1, 2), "float32")
        (o1,) = exe.run(main, feed={"x": xv}, fetch_list=[out])
        np.testing.assert_allclose(o1, 2 * xv)
        with fluid.program_guard(main, startup), fluid.unique_name.guard():
            out2 = fluid.layers.scale(out, scale=5.0)
        (o2,) = exe.run(main, feed={"x": xv}, fetch_list=[out2])
        np.testing.assert_allclose(o2, 10 * xv)


def test_strategy_after_run_rebinds_dispatch():
    """Running a CompiledProgram BEFORE its with_* strategy must not
    poison the dispatch key: after with_data_parallel the next run has
    to use the sharded executable, not the cached mesh-less one."""
    import jax

    if len(jax.devices()) < 2:
        pytest.skip("needs >1 device")
    main, startup, loss = _mlp()
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        cp = fluid.CompiledProgram(main)
        feed = _feed(batch=len(jax.devices()))
        exe.run(cp, feed=feed, fetch_list=[loss])  # binds mesh-less frag
        before = exe.cache_stats()["jit_compiles"]
        cp.with_data_parallel(loss_name=loss.name)
        (out,) = exe.run(cp, feed=feed, fetch_list=[loss],
                         return_numpy=False)
        assert exe.cache_stats()["jit_compiles"] == before + 1, (
            "with_data_parallel after a run did not re-bind/recompile")
        from jax.sharding import NamedSharding

        assert isinstance(out.sharding, NamedSharding)


def test_sharded_feed_divisibility_clear_error():
    """A batch not divisible over the dp mesh axis must raise a clear
    message naming the strategy, not an opaque GSPMD error."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = fluid.layers.data("x", [4])
        loss = fluid.layers.mean(fluid.layers.fc(x, 2))
        fluid.optimizer.SGD(0.1).minimize(loss)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        cp = fluid.CompiledProgram(main).with_data_parallel(
            loss_name=loss.name)
        import jax

        ndev = len(jax.devices())
        if ndev < 2:
            pytest.skip("needs >1 device")
        bad = np.ones((ndev + 1, 4), "float32")  # indivisible batch
        with pytest.raises(ValueError, match="not divisible by mesh axis"):
            exe.run(cp, feed={"x": bad}, fetch_list=[loss])


def test_with_pipeline_static_batch_validation():
    """with_pipeline(dp=...) rejects a static, indivisible leading dim
    at compile-wrap time (ADVICE.md round-5)."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = fluid.layers.data("x", [3, 4], append_batch_size=False)
        h = fluid.layers.fc(x, 4)
        loss = fluid.layers.mean(fluid.layers.fc(h, 2))
        fluid.optimizer.PipelineOptimizer(
            fluid.optimizer.SGD(0.1), cut_list=[[h]],
            num_microbatches=2).minimize(loss)
    cp = fluid.CompiledProgram(main)
    with pytest.raises(ValueError, match="not divisible by dp=2"):
        cp.with_pipeline(dp=2)


def test_manual_axis_kwargs_partial_manual_only():
    """axis_names (the partial-manual spelling) is passed only when the
    mesh has axes beside the pipeline axis."""
    import jax
    import numpy as _np
    from jax.sharding import Mesh

    from paddle_tpu.parallel.pipeline import _manual_axis_kwargs

    devs = jax.devices()
    if len(devs) < 4:
        pytest.skip("needs 4 devices")
    mesh = Mesh(_np.array(devs[:4]).reshape(2, 2), ("dp", "pp"))
    kwargs = _manual_axis_kwargs(mesh, "pp", {"mesh": mesh})
    assert kwargs["axis_names"] == {"pp"}
    mesh1 = Mesh(_np.array(devs[:2]), ("pp",))
    assert "axis_names" not in _manual_axis_kwargs(mesh1, "pp",
                                                   {"mesh": mesh1})


def test_predictor_pad_feed_skips_static_dim1(tmp_path):
    """Bucketing must not zero-pad dim 1 of a feed whose declared
    second dim is static ([B, F] features) — only declared-dynamic
    (sequence) feeds bucket on dim 1 (ADVICE.md)."""
    from paddle_tpu.inference import Config, create_predictor

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        feats = fluid.layers.data("feats", [6])  # static dim 1
        out = fluid.layers.fc(feats, 3, act="softmax")
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        fluid.io.save_inference_model(
            str(tmp_path), ["feats"], [out], exe, main)

    cfg = Config(str(tmp_path))
    cfg.enable_shape_bucketing(seq_buckets=(16, 32), batch_buckets=(4, 8))
    pred = create_predictor(cfg)
    ref = create_predictor(Config(str(tmp_path)))
    rng = np.random.RandomState(3)
    for b in (1, 3, 5):
        f = rng.rand(b, 6).astype("float32")
        (got,) = pred.run([f])
        (want,) = ref.run([f])
        assert got.shape == want.shape == (b, 3)
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    # the padded executable saw dim1=6 untouched (a seq-bucketed run
    # would have compiled with dim1=16 and produced garbage)
    st = pred.bucket_stats()
    assert st["compiled_shapes"] <= 2  # batch buckets only
