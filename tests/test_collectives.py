"""parallel.collectives — bucketed, backward-overlapped, optionally
int8-quantized DP gradient all-reduce.

Proof layers, per the subsystem's contract:

* the planner rewrite itself (bucket assignment in backward-production
  order under the size cap, insertion right after each bucket's last
  producer, consumer repointing, idempotence, flag gating);
* numerics: the bucketed fp32 path is BIT-identical to the PR-8
  monolithic GSPMD path (losses and updated params) — including under
  ZeRO-1 and a dp x tp mesh, and to an ulp under clip-by-global-norm
  (the compiler sums a gradient's squares in another order) — and
  degrades to exactly the monolithic result when no mesh is attached; int8
  composes with ZeRO-1 (tuple-spec moments included) within the
  quantization tolerance;
* the quantization kernel: round-trip error bounded by the per-block
  scale bound;
* static analysis: proglint strict passes on the rewritten program;
* observability: paddle_collective_* gauges in the one scrape;
* the parse_mesh/parse_rules diagnostics name the offending token and
  its position (satellite).
"""

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import observability, partition
from paddle_tpu.kernels import quant
from paddle_tpu.parallel import collectives
from paddle_tpu.parallel.collectives import OP_TYPE, REDUCED_SUFFIX
from paddle_tpu.partition.rules import parse_mesh, parse_rules


def _model(seed=7, clip=None, dropout=0.0):
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = seed
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = fluid.layers.data("x", [16])
        y = fluid.layers.data("y", [1], dtype="int64")
        h = fluid.layers.fc(
            x, 32, act="relu",
            param_attr=fluid.ParamAttr(name="c_w1",
                                       logical_axes=("embed", "mlp")),
            bias_attr=fluid.ParamAttr(name="c_b1", logical_axes=("mlp",)))
        if dropout:
            h = fluid.layers.dropout(h, dropout_prob=dropout)
        logits = fluid.layers.fc(
            h, 4, param_attr=fluid.ParamAttr(name="c_w2",
                                             logical_axes=("mlp", "embed")))
        loss = fluid.layers.mean(
            fluid.layers.softmax_with_cross_entropy(logits, y))
        fluid.optimizer.Adam(0.01, grad_clip=clip).minimize(loss)
    return main, startup, loss


def _batch(step, n=32):
    rng = np.random.RandomState(10_000 + step)
    return {"x": rng.randn(n, 16).astype("float32"),
            "y": rng.randint(0, 4, (n, 1)).astype("int64")}


def _train(prog_factory, steps=5, clip=None, explicit=None, n=32,
           param="c_w1"):
    main, startup, loss = _model(clip=clip)
    if explicit:
        main.global_block().var(param).sharding = explicit
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        prog = prog_factory(main)
        losses = [float(exe.run(prog, feed=_batch(s, n), fetch_list=[loss])[0])
                  for s in range(steps)]
        weights = scope.get_numpy(param).copy()
    return losses, weights


def _cfg(**kw):
    return partition.PartitionConfig(mesh_axes={"dp": 8}, **kw)


# -- the planner rewrite -----------------------------------------------------


def test_planner_buckets_in_backward_production_order():
    main, _, _ = _model()
    plan = collectives.ensure_planned(main, bucket_mb=0.0005)  # ~0.5 KB cap
    assert plan is not None and len(plan.buckets) >= 2
    block = main.global_block()
    producer = {}
    for i, op in enumerate(block.ops):
        for ns in op.outputs.values():
            for nm in ns:
                producer[nm] = i
    # buckets are ordered by when backward produces their grads, and
    # every bucket op sits AFTER its last producer and BEFORE the
    # optimizer ops that consume its outputs
    last_end = -1
    for b in plan.buckets:
        ends = [producer[g] for g in b["grads"]]
        assert min(ends) > last_end
        last_end = max(ends)
    ops = block.ops
    for b in plan.buckets:
        op_idx = next(i for i, op in enumerate(ops)
                      if op.type == OP_TYPE
                      and op.inputs["X"] == list(b["grads"]))
        for g in b["grads"]:
            assert producer[g] < op_idx
    # consumers switched to the reduced twins: no optimizer op reads a
    # raw @GRAD that has a reduced twin
    reduced = set(plan.reduced_names())
    raw = {r[:-len(REDUCED_SUFFIX)] for r in reduced}
    for i, op in enumerate(ops):
        if op.type == OP_TYPE:
            continue
        after = i > max(j for j, o in enumerate(ops) if o.type == OP_TYPE)
        if after:
            for ns in op.inputs.values():
                assert not (set(ns) & raw)


def test_planner_size_cap_and_single_bucket():
    main, _, _ = _model()
    plan = collectives.ensure_planned(main, bucket_mb=64)
    assert len(plan.buckets) == 1
    assert plan.snapshot()["grads_total"] == 4  # w1, b1, w2, b2


def test_planner_idempotent_and_flag_gated():
    main, _, _ = _model()
    assert collectives.ensure_planned(main) is None  # flags off by default
    plan = collectives.ensure_planned(main, bucket_mb=1)
    assert collectives.ensure_planned(main, bucket_mb=1) is plan
    n_ops = len([op for op in main.global_block().ops
                 if op.type == OP_TYPE])
    collectives.ensure_planned(main, bucket_mb=1)
    assert len([op for op in main.global_block().ops
                if op.type == OP_TYPE]) == n_ops


def test_replan_with_conflicting_settings_warns(caplog):
    """The rewrite is one-shot: a later ensure_planned with different
    settings cannot be honored — it must warn, not silently return the
    old plan as if the new request took effect."""
    import logging

    main, _, _ = _model()
    plan = collectives.ensure_planned(main, bucket_mb=1)
    with caplog.at_level(logging.WARNING, logger="paddle_tpu.collectives"):
        assert collectives.ensure_planned(main, bucket_mb=1) is plan
        assert not caplog.records  # same settings: silent idempotence
        assert collectives.ensure_planned(
            main, bucket_mb=1, quantization="int8") is plan
    assert any("conflicting" in r.getMessage() for r in caplog.records)


def test_planner_rejects_bad_quant_config():
    main, _, _ = _model()
    with pytest.raises(ValueError, match="collective_quantization"):
        collectives.ensure_planned(main, quantization="fp4")
    with pytest.raises(ValueError, match="collective_quant_block"):
        collectives.ensure_planned(main, quantization="int8",
                                   quant_block=0)


def test_collective_flags_drive_partition_config():
    old = fluid.get_flags(["collective_bucket_mb",
                           "collective_quantization",
                           "collective_quant_block"])
    try:
        fluid.set_flags({"collective_bucket_mb": 2.5,
                         "collective_quantization": "int8",
                         "collective_quant_block": 128})
        cfg = partition.PartitionConfig(mesh_axes={"dp": 8})
        assert cfg.collective_bucket_mb == 2.5
        assert cfg.collective_quantization == "int8"
        assert cfg.collective_quant_block == 128
        assert cfg.collectives_active()
    finally:
        fluid.set_flags(old)
    assert not partition.PartitionConfig(
        mesh_axes={"dp": 8}).collectives_active()


# -- numerics: fp32 bucketed == monolithic, bitwise --------------------------


def test_bucketed_fp32_bit_identical_to_monolithic():
    """The acceptance-criteria core: same mesh, same model, same feeds
    — the explicit per-bucket psum path reproduces PR-8's monolithic
    GSPMD all-reduce bit for bit, losses AND updated params."""
    mono, w_mono = _train(lambda m: fluid.CompiledProgram(m)
                          .with_partitioning(_cfg()))
    buck, w_buck = _train(lambda m: fluid.CompiledProgram(m)
                          .with_partitioning(_cfg(collective_bucket_mb=0.001)))
    assert mono == buck
    assert np.array_equal(w_mono, w_buck)


def test_bucketed_fp32_bit_identical_under_zero1():
    mono, w0 = _train(lambda m: fluid.CompiledProgram(m)
                      .with_partitioning(_cfg(zero=1)))
    buck, w1 = _train(lambda m: fluid.CompiledProgram(m)
                      .with_partitioning(_cfg(zero=1,
                                              collective_bucket_mb=0.001)))
    assert mono == buck
    assert np.array_equal(w0, w1)


def test_bucketed_fp32_bit_identical_on_dp_tp_mesh():
    """Partial-manual shard_map (dp manual, tp GSPMD-auto): the
    megatron-sharded weights keep their tp placement inside the
    collective segment and the result still matches monolithic
    bitwise."""
    cfg = dict(mesh_axes={"dp": 4, "tp": 2}, zero=1)
    mono, _ = _train(lambda m: fluid.CompiledProgram(m).with_partitioning(
        partition.PartitionConfig(**cfg)))
    buck, _ = _train(lambda m: fluid.CompiledProgram(m).with_partitioning(
        partition.PartitionConfig(collective_bucket_mb=0.001, **cfg)))
    assert mono == buck


def test_bucketed_fp32_matches_to_an_ulp_with_global_norm_clip():
    """Clip-by-global-norm must see the REDUCED (true global) grads:
    the planner reduces before the clip ops, and the reduced grads are
    bit-equal to the monolithic path's (the test above; the same holds
    here). What differs is the compiler's: each ``reduce_sum(square(g))``
    sums one gradient's elements in another order when g comes out of
    the bucket's psum than when it comes out of GSPMD's all-reduce
    (up to 6 ulps on a per-gradient sum at step 0, 1 ulp on the norm),
    so the clip scale, the weights (1.5e-8) and, from step 4 on, the
    loss move by an ulp. A clip that saw local grads would move the
    loss in the second decimal."""
    clip = fluid.clip.GradientClipByGlobalNorm(0.5)
    mono, _ = _train(lambda m: fluid.CompiledProgram(m)
                     .with_partitioning(_cfg()), clip=clip)
    buck, _ = _train(lambda m: fluid.CompiledProgram(m)
                     .with_partitioning(_cfg(collective_bucket_mb=0.001)),
                     clip=clip)
    np.testing.assert_array_max_ulp(np.float32(mono), np.float32(buck),
                                    maxulp=2)


def test_planned_program_without_mesh_degrades_to_monolithic():
    """A planned program run with NO mesh (single device) lowers its
    bucket ops as identity on the already-global grads — bitwise the
    un-planned result."""
    plain, w0 = _train(lambda m: m)
    planned, w1 = _train(
        lambda m: (collectives.ensure_planned(m, bucket_mb=0.001), m)[1])
    assert plain == planned
    assert np.array_equal(w0, w1)


def test_optimizer_seam_plans_under_flags():
    """The apply_gradients seam: flags set at minimize time plan the
    program with no partition/compile involvement, and the DP
    trajectory stays bit-identical to monolithic."""
    old = fluid.get_flags(["collective_bucket_mb"])
    try:
        fluid.set_flags({"collective_bucket_mb": 0.001})
        main, startup, loss = _model()
        assert main._collective_plan is not None
        assert len(main._collective_plan.buckets) >= 2
    finally:
        fluid.set_flags(old)
    mono, _ = _train(lambda m: fluid.CompiledProgram(m)
                     .with_partitioning(_cfg()))
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        prog = fluid.CompiledProgram(main).with_partitioning(_cfg())
        got = [float(exe.run(prog, feed=_batch(s), fetch_list=[loss])[0])
               for s in range(5)]
    assert got == mono


def test_tainted_integer_export_refused_not_silently_local():
    """An integer fetch computed from dp-split feeds inside the sharded
    segment has no sound cross-replica correction (floats return the
    pmean) — the lowering must refuse it, not return one shard's local
    value where the monolithic path returns the global one."""
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 7
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = fluid.layers.data("x", [16])
        y = fluid.layers.data("y", [1], dtype="int64")
        logits = fluid.layers.fc(x, 4)
        loss = fluid.layers.mean(
            fluid.layers.softmax_with_cross_entropy(logits, y))
        cnt = fluid.layers.cast(
            fluid.layers.reduce_sum(fluid.layers.cast(y, "float32")),
            "int64")
        fluid.optimizer.Adam(0.01).minimize(loss)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        prog = fluid.CompiledProgram(main).with_partitioning(
            _cfg(collective_bucket_mb=0.001))
        with pytest.raises(NotImplementedError, match="integer var"):
            exe.run(prog, feed=_batch(0), fetch_list=[loss, cnt])


def test_rng_derived_integer_export_refused():
    """Inside the collective segment the PRNG key folds in the dp rank,
    so RNG-op outputs differ per shard even from replicated inputs — an
    integer fetch derived from one must be refused exactly like a
    dp-split-derived integer, not silently returned per-shard."""
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 7
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = fluid.layers.data("x", [16])
        y = fluid.layers.data("y", [1], dtype="int64")
        logits = fluid.layers.fc(x, 4)
        loss = fluid.layers.mean(
            fluid.layers.softmax_with_cross_entropy(logits, y))
        sampled = fluid.layers.reduce_sum(
            fluid.layers.sampling_id(fluid.layers.softmax(logits)))
        fluid.optimizer.Adam(0.01).minimize(loss)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        prog = fluid.CompiledProgram(main).with_partitioning(
            _cfg(collective_bucket_mb=0.001))
        with pytest.raises(NotImplementedError, match="integer var"):
            exe.run(prog, feed=_batch(0), fetch_list=[loss, sampled])


# -- numerics: int8 ----------------------------------------------------------


def test_int8_zero1_trains_close_to_fp32():
    """ZeRO-1 + int8 collectives compose: dp-sharded Adam moments
    update from the quantized-reduced grads, and the loss trajectory
    stays within the quantization tolerance of the exact path."""
    ref, _ = _train(lambda m: fluid.CompiledProgram(m)
                    .with_partitioning(_cfg(zero=1)), steps=8)
    q, _ = _train(lambda m: fluid.CompiledProgram(m)
                  .with_partitioning(_cfg(zero=1,
                                          collective_quantization="int8")),
                  steps=8)
    div = max(abs(a - b) / max(abs(b), 1e-9) for a, b in zip(q, ref))
    assert div < 0.02, f"int8 trajectory diverged: {div}"
    assert q[-1] < q[0]  # it actually trains


def test_int8_composes_with_tuple_spec_moments():
    """A param pinned to a joint ("dp","tp") placement: ZeRO-1 keeps
    the moments on the tuple spec, the collective segment re-shards the
    param dp-free on entry, and the int8 reduce still lands within
    tolerance of the exact trajectory."""
    cfg = dict(mesh_axes={"dp": 4, "tp": 2}, zero=1)
    explicit = (("dp", "tp"), None)
    ref, _ = _train(lambda m: fluid.CompiledProgram(m).with_partitioning(
        partition.PartitionConfig(**cfg)), explicit=explicit)
    q, _ = _train(lambda m: fluid.CompiledProgram(m).with_partitioning(
        partition.PartitionConfig(collective_quantization="int8", **cfg)),
        explicit=explicit)
    div = max(abs(a - b) / max(abs(b), 1e-9) for a, b in zip(q, ref))
    assert div < 0.02, f"tuple-spec int8 diverged: {div}"


def test_wire_gauges_honest_in_psum_fallback_region():
    """On a partial-manual mesh (any non-dp axis, even size 1) the int8
    exchange falls back to psum of the dequantized fp32 payload — the
    wire gauges must report that transport, not the ~3.9x int8 model."""
    plans = {}

    def factory(axes):
        def f(m):
            cp = fluid.CompiledProgram(m).with_partitioning(
                partition.PartitionConfig(
                    mesh_axes=axes, collective_quantization="int8"))
            plans[tuple(axes)] = m._collective_plan
            return cp
        return f

    _train(factory({"dp": 4, "tp": 2}), steps=1)
    fallback = plans[("dp", "tp")]
    assert not fallback.snapshot()["quantized_exchange"]
    assert fallback.wire_stats()["wire_bytes_saved_ratio"] <= 1.0

    _train(factory({"dp": 8}), steps=1)
    real = plans[("dp",)]
    assert real.snapshot()["quantized_exchange"]
    assert real.wire_stats()["wire_bytes_saved_ratio"] > 1.0


def test_quant_roundtrip_error_bounded_per_block():
    rng = np.random.RandomState(0)
    # heavy-tailed grads: one outlier per region must only poison its
    # own block's scale
    x = (rng.randn(10_000).astype("float32")
         * rng.choice([1.0, 30.0], 10_000, p=[0.99, 0.01]))
    for block in (64, 256):
        q, s = quant.blockwise_quantize(
            np.pad(x, (0, -len(x) % block)).reshape(-1, block))
        back = np.asarray(quant.blockwise_dequantize(q, s)).reshape(-1)
        err = np.abs(back[:len(x)] - x).max()
        bound = quant.blockwise_error_bound(x, block)
        assert err <= bound + 1e-7, (block, err, bound)
        # blockwise beats one per-tensor scale by construction
        tensor_bound = np.abs(x).max() / 127 / 2
        assert bound <= tensor_bound + 1e-7


def test_quantized_mean_psum_form_matches_exchange_form():
    """The partial-manual fallback (psum of dequantized payload +
    requantize) must be numerically equivalent to the real two-shot
    int8 exchange — same quantize/requantize pipeline, different
    transport."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P

    devs = np.array(jax.devices()[:8])
    mesh = Mesh(devs, ("dp",))
    rng = np.random.RandomState(1)
    x = rng.randn(8, 600).astype("float32")

    def run(exchange):
        def body(v):
            return quant.quantized_mean(v[0], "dp", 8, 64,
                                        exchange=exchange)[None]

        f = jax.shard_map(body, mesh=mesh, in_specs=(P("dp"),),
                          out_specs=P("dp"), check_vma=False)
        return np.asarray(jax.jit(f)(jnp.asarray(x)))

    a, b = run(True), run(False)
    ref = x.mean(axis=0)
    np.testing.assert_allclose(a[0], b[0], atol=1e-5, rtol=1e-5)
    # and both approximate the true mean within the two-stage bound
    bound = 2 * quant.blockwise_error_bound(x, 64)
    assert np.abs(a[0] - ref).max() <= bound


# -- static analysis / infra -------------------------------------------------


def test_proglint_strict_passes_on_rewritten_program():
    main, startup, loss = _model()
    collectives.ensure_planned(main, bucket_mb=0.001,
                               quantization="int8")
    cp = fluid.CompiledProgram(main).with_partitioning(_cfg())
    report = cp.validate(fetch_list=[loss], strict=True)
    assert report.ok
    # and through the executor's pre-lowering gate while running
    old = fluid.get_flags(["validate_program"])
    scope = fluid.Scope()
    try:
        fluid.set_flags({"validate_program": "strict"})
        with fluid.scope_guard(scope):
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(startup)
            exe.run(cp, feed=_batch(0), fetch_list=[loss])
    finally:
        fluid.set_flags(old)


def test_wire_model_and_gauges_in_unified_scrape():
    plans = []

    def factory(m):
        cp = fluid.CompiledProgram(m).with_partitioning(
            _cfg(collective_quantization="int8"))
        plans.append(m._collective_plan)
        return cp

    _train(factory, steps=1)  # compile over the mesh: gauges concrete
    plan = plans[0]
    plan.set_measured(overlap_hidden_fraction=0.5, max_quant_error=1e-3)
    label = '{plan="%s"}' % plan._obs_id
    snap = observability.snapshot()["collected"]
    series = {k: v for k, v in snap.items()
              if k.startswith("paddle_collective_")}
    assert series["paddle_collective_buckets"][label] == 1
    assert series["paddle_collective_dp"][label] == 8
    assert series["paddle_collective_wire_bytes_per_step"][label] > 0
    assert series["paddle_collective_wire_bytes_fp32_per_step"][label] > \
        series["paddle_collective_wire_bytes_per_step"][label]
    assert series["paddle_collective_overlap_hidden_fraction"][label] == 0.5
    assert series["paddle_collective_max_quant_error"][label] == 1e-3
    text = observability.to_prometheus_text()
    assert "paddle_collective_wire_bytes_saved_per_step" in text
    # the wire model at a REAL payload size: ~600 KB of grads at block
    # 256 over dp8 beats fp32 by ~3.9x (the tiny test model above is
    # dominated by dp-chunk padding — the bench gates the GPT case)
    stats_fp32 = sum(b["numels"][0] for b in plan.buckets)  # sanity only
    numel = 150_000
    nb = -(-numel // 256)
    nb = -(-nb // 8) * 8
    ratio = (numel * 4) / (nb * 256 + 4 * nb)
    assert ratio > 3.8 and stats_fp32 > 0


def test_run_pipelined_matches_run_on_collective_mesh():
    """The async host/device pipeline drives the collective executable
    identically to the sync path."""
    feeds = [_batch(s) for s in range(6)]
    results = {}
    for mode in ("run", "pipelined"):
        main, startup, loss = _model()
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(startup)
            prog = fluid.CompiledProgram(main).with_partitioning(
                _cfg(collective_bucket_mb=0.001))
            if mode == "run":
                out = [float(exe.run(prog, feed=f, fetch_list=[loss])[0])
                       for f in feeds]
            else:
                out = [float(o[0]) for o in exe.run_pipelined(
                    prog, feeds=feeds, fetch_list=[loss])]
        results[mode] = out
    assert results["run"] == results["pipelined"]


def test_skip_reduce_rekeys_executable():
    """The bench's compute-only timing variant must not serve the real
    executable from any cache (fingerprint + version both move)."""
    from paddle_tpu.runtime.dispatch import program_fingerprint

    main, _, _ = _model()
    plan = collectives.ensure_planned(main, bucket_mb=0.001)
    v0, f0 = main.version, program_fingerprint(main)
    plan.set_skip_reduce(True)
    assert main.version > v0
    assert program_fingerprint(main) != f0


def test_pipeline_optimizer_suppresses_flag_planning():
    """PipelineOptimizer stamps its cuts AFTER the inner minimize, so
    the flag seam must not rewrite the soon-to-be-pipelined program —
    a bucket op spanning stages would break the stage partitioner."""
    old = fluid.get_flags(["collective_bucket_mb"])
    try:
        fluid.set_flags({"collective_bucket_mb": 0.001})
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup), fluid.unique_name.guard():
            x = fluid.layers.data("x", [16])
            y = fluid.layers.data("y", [1], dtype="int64")
            h1 = fluid.layers.fc(x, 32, act="relu")
            loss = fluid.layers.mean(
                fluid.layers.softmax_with_cross_entropy(
                    fluid.layers.fc(h1, 4), y))
            fluid.optimizer.PipelineOptimizer(
                fluid.optimizer.SGD(0.1), cut_list=[h1],
                num_microbatches=2).minimize(loss)
        assert getattr(main, "_collective_plan", None) is None
        assert not any(op.type == OP_TYPE
                       for op in main.global_block().ops)
        assert main._pipeline_cuts  # the pipeline itself still marked
    finally:
        fluid.set_flags(old)


def test_gradient_merge_optimizer_suppresses_flag_planning():
    """GradientMergeOptimizer's scan accumulator owns the gradient
    flow and its build path wins the executor routing — a plan stamped
    by the inner minimize would lower its bucket ops as identity while
    the gauges claim wire savings that never happen."""
    old = fluid.get_flags(["collective_bucket_mb"])
    try:
        fluid.set_flags({"collective_bucket_mb": 0.001})
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup), fluid.unique_name.guard():
            x = fluid.layers.data("x", [16])
            y = fluid.layers.data("y", [1], dtype="int64")
            loss = fluid.layers.mean(
                fluid.layers.softmax_with_cross_entropy(
                    fluid.layers.fc(x, 4), y))
            fluid.optimizer.GradientMergeOptimizer(
                fluid.optimizer.SGD(0.1), k_steps=2).minimize(loss)
        assert getattr(main, "_collective_plan", None) is None
        assert not any(op.type == OP_TYPE
                       for op in main.global_block().ops)
        assert main._gradient_merge_k == 2  # the merge itself marked
        # the config seam refuses the already-stamped program too
        assert collectives.ensure_planned(main, bucket_mb=0.001) is None
    finally:
        fluid.set_flags(old)


# -- satellite: parse diagnostics name token + position ----------------------


def test_parse_mesh_errors_name_token_and_position():
    with pytest.raises(ValueError, match=r"entry 2 \('tp'\)"):
        parse_mesh("dp=4,tp")
    with pytest.raises(ValueError, match=r"entry 1 \('dp=four'\)"):
        parse_mesh("dp=four,tp=2")
    with pytest.raises(ValueError, match="not an integer"):
        parse_mesh("dp=4,tp=x")
    with pytest.raises(ValueError, match="axis name is empty"):
        parse_mesh("dp=4, =2")


def test_parse_rules_errors_name_token_and_position():
    with pytest.raises(ValueError, match=r"entry 3 \('heads'\)"):
        parse_rules("batch=dp,embed=,heads")
    with pytest.raises(ValueError, match="logical axis name is empty"):
        parse_rules("batch=dp,=tp")
