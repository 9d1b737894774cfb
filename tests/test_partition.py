"""paddle_tpu.partition — the sharded end-to-end proof.

Reference strategy (SURVEY §4.2/§4.4, TestDistBase): run the same model
single-device and sharded over the 8-device virtual CPU mesh
(conftest.py forces --xla_force_host_platform_device_count=8) and
assert parity. Three layers of proof, per the subsystem's contract:

* the rules table itself (resolution semantics: first match, replicated
  pin, inapplicable-axis fallthrough, divisibility skip + reason);
* the resolve pass (tagged params, var_rules patterns, explicit
  var.sharding precedence, ZeRO accumulator inheritance);
* end-to-end execution: DP training numerically equivalent to a single
  device, TP predict equivalent through Predictor/ServingEngine, and a
  mesh checkpoint that survives a hard kill and resumes bit-exactly in
  a fresh process.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import io, partition, resilience
from paddle_tpu.partition.rules import (DEFAULT_RULES, parse_mesh,
                                        parse_rules, resolve_spec,
                                        rules_to_str)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- rules table -------------------------------------------------------------


def test_parse_mesh_forms():
    assert parse_mesh("dp=4,tp=2") == {"dp": 4, "tp": 2}
    assert parse_mesh({"tp": 8}) == {"tp": 8}
    assert parse_mesh("") == {}
    assert parse_mesh(None) == {}
    with pytest.raises(ValueError, match="axis=size"):
        parse_mesh("dp4")


def test_parse_rules_forms():
    rules = parse_rules("batch=dp,embed=,heads=tp")
    assert rules == (("batch", "dp"), ("embed", None), ("heads", "tp"))
    assert parse_rules(None) == tuple(DEFAULT_RULES)
    # round trip through the flag syntax
    assert parse_rules(rules_to_str(rules)) == rules
    with pytest.raises(ValueError, match="logical=mesh"):
        parse_rules("heads")


def test_resolve_spec_first_match_and_replicated_pin():
    rules = (("embed", None), ("embed", "tp"), ("mlp", "tp"))
    spec, skipped = resolve_spec(("embed", "mlp"), rules, {"tp": 2},
                                 shape=(64, 64))
    # the embed=None rule matches FIRST and pins replicated — the later
    # embed=tp rule never applies
    assert spec == (None, "tp")
    assert skipped == []


def test_resolve_spec_inapplicable_axis_falls_through():
    # heads=sp is inapplicable on a tp-only mesh; the later heads=tp
    # rule wins — one table serves every mesh shape
    rules = (("heads", "sp"), ("heads", "tp"))
    spec, _ = resolve_spec(("heads",), rules, {"tp": 2}, shape=(8,))
    assert spec == ("tp",)


def test_resolve_spec_one_mesh_axis_per_tensor():
    rules = (("heads", "tp"), ("mlp", "tp"))
    spec, skipped = resolve_spec(("heads", "mlp"), rules, {"tp": 2},
                                 shape=(8, 8))
    assert spec == ("tp", None)
    assert skipped and skipped[0][3] == "axis already used"


def test_resolve_spec_divisibility_skip_has_reason():
    spec, skipped = resolve_spec(("mlp",), (("mlp", "tp"),), {"tp": 8},
                                 shape=(12,))
    assert spec == (None,)
    assert skipped and "not divisible" in skipped[0][3]


def test_resolve_spec_untagged_dims_replicated():
    spec, _ = resolve_spec((None, "mlp"), (("mlp", "tp"),), {"tp": 2},
                           shape=(4, 8))
    assert spec == (None, "tp")


# -- the resolve pass --------------------------------------------------------


def _tagged_model(seed=7, dropout=0.0):
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = seed
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = fluid.layers.data("x", [16])
        y = fluid.layers.data("y", [1], dtype="int64")
        h = fluid.layers.fc(
            x, 32, act="relu",
            param_attr=fluid.ParamAttr(name="p_w1",
                                       logical_axes=("embed", "mlp")),
            bias_attr=fluid.ParamAttr(name="p_b1", logical_axes=("mlp",)))
        if dropout:
            h = fluid.layers.dropout(h, dropout_prob=dropout)
        logits = fluid.layers.fc(
            h, 4, param_attr=fluid.ParamAttr(name="p_w2",
                                             logical_axes=("mlp", "embed")))
        loss = fluid.layers.mean(
            fluid.layers.softmax_with_cross_entropy(logits, y))
        fluid.optimizer.Adam(0.01).minimize(loss)
    return main, startup, loss


def _batch(step, n=32):
    rng = np.random.RandomState(10_000 + step)
    return {"x": rng.randn(n, 16).astype("float32"),
            "y": rng.randint(0, 4, (n, 1)).astype("int64")}


def _rows_by_name(resolved):
    return {r["name"]: r for r in resolved.rows}


def test_resolve_tagged_params_tp():
    main, _, _ = _tagged_model()
    cfg = partition.PartitionConfig(mesh_axes={"tp": 8})
    resolved = cfg.resolve(main)
    rows = _rows_by_name(resolved)
    assert rows["p_w1"]["spec"] == (None, "tp")   # embed repl, mlp->tp
    assert rows["p_b1"]["spec"] == ("tp",)
    assert rows["p_w2"]["spec"] == ("tp", None)
    # tp-only mesh: the batch->dp rule is inapplicable, feeds replicate
    assert resolved.summary["feeds_sharded"] == 0
    assert resolved.summary["vars_sharded"] >= 3


def test_resolve_data_vars_batch_over_dp():
    main, _, _ = _tagged_model()
    cfg = partition.PartitionConfig(mesh_axes={"dp": 8})
    resolved = cfg.resolve(main)
    from jax.sharding import PartitionSpec as P

    assert resolved.in_shardings["x"] == P("dp", None)
    assert resolved.in_shardings["y"] == P("dp", None)
    # tagged weights: mlp->tp has no tp axis here -> replicated
    assert _rows_by_name(resolved)["p_w1"]["spec"] == (None, None)


def test_resolve_var_rules_for_untagged_models():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = fluid.layers.data("x", [16])
        h = fluid.layers.fc(x, 32)
        fluid.layers.fc(h, 8)
    cfg = partition.PartitionConfig(
        mesh_axes={"tp": 8},
        var_rules=((r"fc_0\.w_0", ("embed", "mlp")),
                   (r"fc_1\.w_0", ("mlp", "embed"))))
    rows = _rows_by_name(cfg.resolve(main))
    assert rows["fc_0.w_0"]["spec"] == (None, "tp")
    assert rows["fc_1.w_0"]["spec"] == ("tp", None)


def test_explicit_var_sharding_precedence():
    main, _, _ = _tagged_model()
    gb = main.global_block()
    gb.var("p_w1").sharding = ("tp", None)  # megatron-style manual spec
    cfg = partition.PartitionConfig(mesh_axes={"tp": 8})
    rows = _rows_by_name(cfg.resolve(main))
    assert rows["p_w1"]["spec"] == ("tp", None)
    assert rows["p_w1"]["note"] == "explicit var.sharding"


def test_explicit_sharding_absent_axis_overridden_replicated():
    main, _, _ = _tagged_model()
    gb = main.global_block()
    gb.var("p_w2").sharding = ("sp", None)  # axis not on this mesh
    cfg = partition.PartitionConfig(mesh_axes={"tp": 8})
    rows = _rows_by_name(cfg.resolve(main))
    assert rows["p_w2"]["spec"] == (None, None)
    assert "absent from this mesh" in rows["p_w2"]["note"]


def test_data_var_explicit_sharding_respected():
    """Feeds obey the same precedence as params: a manual feed spec
    (e.g. pinning an auxiliary input replicated to keep it off the dp
    axis) beats the batch->dp rules default."""
    main, _, _ = _tagged_model()
    main.global_block().var("x").sharding = (None, None)
    cfg = partition.PartitionConfig(mesh_axes={"dp": 8})
    resolved = cfg.resolve(main)
    assert "x" not in resolved.in_shardings  # pinned replicated
    rows = _rows_by_name(resolved)
    assert rows["x"]["note"] == "explicit var.sharding"
    from jax.sharding import PartitionSpec as P

    assert resolved.in_shardings["y"] == P("dp", None)  # default untouched


def test_zero1_composes_with_joint_axis_explicit_spec():
    """ZeRO-1 must see dp inside a joint-axis tuple placement
    ((("dp","tp"), None) — megatron joint specs are serialized by
    framework.py) and not add a second dp shard, which NamedSharding
    rejects as a duplicate axis."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    main, _, _ = _tagged_model()
    main.global_block().var("p_w1").sharding = (("dp", "tp"), None)
    cfg = partition.PartitionConfig(mesh_axes={"dp": 4, "tp": 2}, zero=1)
    resolved = cfg.resolve(main)
    m1 = _rows_by_name(resolved)["p_w1_moment1_0"]["spec"]
    assert m1 == (("dp", "tp"), None)
    NamedSharding(resolved.mesh, P(*m1))  # constructible: no dup dp


def test_zero1_accumulators_inherit_then_dp_shard():
    main, _, _ = _tagged_model()
    cfg = partition.PartitionConfig(mesh_axes={"dp": 4, "tp": 2}, zero=1)
    resolved = cfg.resolve(main)
    rows = _rows_by_name(resolved)
    # p_w1 sharded (None, tp); its Adam moments inherit that AND gain a
    # dp shard on the still-replicated dim
    m1 = rows["p_w1_moment1_0"]
    assert m1["spec"] == ("dp", "tp")
    assert "zero-dp" in m1["note"]
    # scalar state stays replicated
    beta = rows["p_w1_beta1_pow_acc_0"]
    assert beta["spec"] == (None,)
    assert "scalar" in beta["note"]
    # zero=0 leaves accumulators wherever inheritance put them (no dp)
    rows0 = _rows_by_name(
        partition.PartitionConfig(mesh_axes={"dp": 4, "tp": 2},
                                  zero=0).resolve(main))
    assert rows0["p_w1_moment1_0"]["spec"] == (None, "tp")


def test_zero3_shards_params_over_dp():
    main, _, _ = _tagged_model()
    cfg = partition.PartitionConfig(mesh_axes={"dp": 4}, zero=3)
    rows = _rows_by_name(cfg.resolve(main))
    assert "dp" in rows["p_w1"]["spec"]
    assert "dp" in rows["p_w2"]["spec"]


def test_logical_axes_rank_mismatch_raises_at_build():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = fluid.layers.data("x", [16])
        with pytest.raises(ValueError, match="logical_axes"):
            fluid.layers.fc(
                x, 8, param_attr=fluid.ParamAttr(
                    name="bad_w", logical_axes=("embed",)))  # rank-2 param


def test_logical_axes_survive_program_serialization():
    main, _, _ = _tagged_model()
    clone = fluid.Program.from_dict(main.to_dict())
    assert clone.global_block().var("p_w1").logical_axes == ("embed", "mlp")


def test_gpt_model_is_tp_ready():
    """The in-repo GPT's ParamAttr logical_axes tags resolve to the
    megatron placement on a dp x tp mesh with zero model edits."""
    from paddle_tpu.models.gpt import GPTConfig, build_gpt_lm

    cfg = GPTConfig.tiny()
    cfg.hidden_dropout = cfg.attention_dropout = 0.0
    main, _, _, _ = build_gpt_lm(cfg, 32)
    resolved = partition.PartitionConfig(
        mesh_axes={"dp": 4, "tp": 2}).resolve(main)
    rows = _rows_by_name(resolved)
    qkv = next(r for n, r in rows.items() if n.endswith("_qkv.w"))
    proj = next(r for n, r in rows.items() if n.endswith("_proj.w"))
    ffn1 = next(r for n, r in rows.items() if n.endswith("_ffn1.w"))
    assert qkv["spec"] == (None, "tp")      # (embed, heads)
    assert proj["spec"] == ("tp", None)     # (heads, embed)
    assert ffn1["spec"] == (None, "tp")     # (embed, mlp)
    assert rows["gpt_tok_emb"]["spec"] == ("tp", None)  # (vocab, embed)
    # feeds shard over dp
    from jax.sharding import PartitionSpec as P

    assert resolved.in_shardings["tokens"] == P("dp", None)


def test_missing_mesh_is_a_clear_error():
    main, _, _ = _tagged_model()
    cfg = partition.PartitionConfig()  # no mesh_axes, flag empty
    with pytest.raises(ValueError, match="partition_mesh"):
        cfg.resolve(main)


def test_partition_flags_drive_config():
    old = fluid.get_flags(["partition_mesh", "partition_rules",
                           "partition_zero"])
    try:
        fluid.set_flags({"partition_mesh": "tp=2",
                         "partition_rules": "mlp=,heads=tp",
                         "partition_zero": 1})
        cfg = partition.PartitionConfig()
        assert cfg.mesh_axes == {"tp": 2}
        assert cfg.rules == (("mlp", None), ("heads", "tp"))
        assert cfg.zero == 1
    finally:
        fluid.set_flags(old)


# -- DP training end to end --------------------------------------------------


def _train(prog_factory, steps=5):
    main, startup, loss = _tagged_model()
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        prog = prog_factory(main)
        return [float(exe.run(prog, feed=_batch(s), fetch_list=[loss])[0])
                for s in range(steps)]


def test_dp_train_trajectory_matches_single_device():
    single = _train(lambda m: m)
    dp = _train(lambda m: fluid.CompiledProgram(m).with_partitioning(
        partition.PartitionConfig(mesh_axes={"dp": 8})))
    np.testing.assert_allclose(single, dp, atol=1e-5, rtol=1e-5)


def test_dp_zero1_train_trajectory_matches_single_device():
    single = _train(lambda m: m)
    z1 = _train(lambda m: fluid.CompiledProgram(m).with_partitioning(
        partition.PartitionConfig(mesh_axes={"dp": 8}, zero=1)))
    np.testing.assert_allclose(single, z1, atol=1e-5, rtol=1e-5)


def test_dp_tp_train_trajectory_matches_single_device():
    single = _train(lambda m: m)
    dptp = _train(lambda m: fluid.CompiledProgram(m).with_partitioning(
        partition.PartitionConfig(mesh_axes={"dp": 4, "tp": 2}, zero=1)))
    np.testing.assert_allclose(single, dptp, atol=1e-5, rtol=1e-5)


def test_foreign_axis_sharding_still_runs_overridden_replicated():
    """A model whose serialized sharding annotations name a mesh axis
    this mesh lacks (dp/ep tags served on a different mesh) must RUN
    replicated as report() promises, not crash the jit: the resolved
    replicated spec has to reach the executor, whose per-var fallback
    would otherwise re-apply the raw annotation."""
    def factory(m):
        m.global_block().var("p_w1").sharding = ("sp", None)
        return fluid.CompiledProgram(m).with_partitioning(
            partition.PartitionConfig(mesh_axes={"dp": 8}))

    single = _train(lambda m: m)
    dp = _train(factory)
    np.testing.assert_allclose(single, dp, atol=1e-5, rtol=1e-5)


def test_run_pipelined_on_mesh_bit_exact_vs_run():
    """The async host/device pipeline drives the mesh executable
    identically to the sync path (the feeder must NOT device_put feeds
    whose placement GSPMD owns)."""
    feeds = [_batch(s) for s in range(6)]
    results = {}
    for mode in ("run", "pipelined"):
        main, startup, loss = _tagged_model(dropout=0.1)
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(startup)
            prog = fluid.CompiledProgram(main).with_partitioning(
                partition.PartitionConfig(mesh_axes={"dp": 8}))
            if mode == "run":
                out = [float(exe.run(prog, feed=f, fetch_list=[loss])[0])
                       for f in feeds]
            else:
                out = [float(o[0]) for o in exe.run_pipelined(
                    prog, feeds=feeds, fetch_list=[loss])]
        results[mode] = out
    assert results["run"] == results["pipelined"]  # bitwise


def test_undivisible_feed_is_a_clear_error():
    main, startup, loss = _tagged_model()
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        prog = fluid.CompiledProgram(main).with_partitioning(
            partition.PartitionConfig(mesh_axes={"dp": 8}))
        with pytest.raises(ValueError, match="with_partitioning"):
            exe.run(prog, feed=_batch(0, n=6), fetch_list=[loss])


def test_one_strategy_per_compile():
    main, _, _ = _tagged_model()
    cp = fluid.CompiledProgram(main).with_partitioning(
        partition.PartitionConfig(mesh_axes={"dp": 8}))
    with pytest.raises(ValueError, match="mutually exclusive"):
        cp.with_data_parallel()
    with pytest.raises(ValueError, match="not both"):
        fluid.CompiledProgram(main).with_partitioning(
            partition.PartitionConfig(mesh_axes={"dp": 8}), mesh_axes="dp=8")


# -- proglint ----------------------------------------------------------------


def test_proglint_strict_passes_on_partitioned_program():
    main, startup, loss = _tagged_model()
    cp = fluid.CompiledProgram(main).with_partitioning(
        partition.PartitionConfig(mesh_axes={"dp": 8}))
    report = cp.validate(fetch_list=[loss], strict=True)
    assert report.ok
    # and through the executor's pre-lowering verification gate
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


# -- TP serving end to end ---------------------------------------------------


@pytest.fixture(scope="module")
def infer_model_dir(tmp_path_factory):
    tmp = str(tmp_path_factory.mktemp("tp_model"))
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = fluid.layers.data("x", [16])
        h = fluid.layers.fc(
            x, 32, act="relu",
            param_attr=fluid.ParamAttr(name="s_w1",
                                       logical_axes=("embed", "mlp")),
            bias_attr=fluid.ParamAttr(name="s_b1", logical_axes=("mlp",)))
        out = fluid.layers.fc(
            h, 8, act="softmax",
            param_attr=fluid.ParamAttr(name="s_w2",
                                       logical_axes=("mlp", "embed")))
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        fluid.io.save_inference_model(tmp, ["x"], [out], exe, main)
    return tmp


def test_tp_predict_matches_single_device(infer_model_dir):
    from paddle_tpu.inference import Config, create_predictor

    feed = np.random.RandomState(0).rand(4, 16).astype("float32")
    (ref,) = create_predictor(Config(infer_model_dir)).run([feed])

    cfg = Config(infer_model_dir)
    cfg.enable_partitioning(mesh_axes={"tp": 8})
    pred = create_predictor(cfg)
    # the saved model's serialized logical_axes tags drove the resolve
    assert pred.partition.summary["vars_sharded"] >= 3
    (tp,) = pred.run([feed])
    np.testing.assert_allclose(ref, tp, atol=1e-6, rtol=1e-6)
    # clones share the one mesh + binding cache (the worker-pool form)
    clone = pred.clone()
    assert clone.partition is pred.partition
    (tpc,) = clone.run([feed])
    np.testing.assert_allclose(ref, tpc, atol=1e-6, rtol=1e-6)


def test_tp_serving_engine_workers_share_mesh(infer_model_dir):
    from paddle_tpu.inference import Config, create_predictor
    from paddle_tpu.serving import ServingEngine

    feed = np.random.RandomState(1).rand(3, 16).astype("float32")
    (ref,) = create_predictor(Config(infer_model_dir)).run([feed])

    cfg = Config(infer_model_dir)
    cfg.enable_partitioning(mesh_axes={"tp": 8})
    eng = ServingEngine(create_predictor(cfg), num_workers=2,
                        max_batch_size=8, batch_timeout_ms=1.0)
    try:
        outs = [eng.predict({"x": feed}, timeout=60) for _ in range(3)]
    finally:
        eng.close(drain=True)
    for out in outs:
        np.testing.assert_allclose(ref, out[0], atol=1e-6, rtol=1e-6)


# -- observability -----------------------------------------------------------


def test_partition_gauges_in_unified_scrape():
    from paddle_tpu import observability

    main, _, _ = _tagged_model()
    resolved = partition.PartitionConfig(
        mesh_axes={"dp": 4, "tp": 2}, zero=1).resolve(main)
    snap = observability.snapshot()["collected"]
    series = {k: v for k, v in snap.items()
              if k.startswith("paddle_partition_")}
    label = '{resolve="%s"}' % resolved._obs_id
    assert series["paddle_partition_mesh_dp"][label] == 4
    assert series["paddle_partition_mesh_tp"][label] == 2
    assert series["paddle_partition_mesh_devices"][label] == 8
    assert series["paddle_partition_state_sharded_bytes"][label] > 0
    text = observability.to_prometheus_text()
    assert "paddle_partition_state_sharded_bytes" in text


# -- mesh checkpoint: save -> kill -> resume, bitwise ------------------------


def _spawn_child(tmp, name, steps, ckpt_dir, every, fault=""):
    loss_out = os.path.join(str(tmp), f"{name}.json")
    cmd = [sys.executable, os.path.abspath(__file__),
           "--steps", str(steps), "--ckpt-dir", str(ckpt_dir),
           "--ckpt-every", str(every), "--loss-out", loss_out]
    if fault:
        cmd += ["--fault", fault]
    env = dict(os.environ)
    flags = env.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        flags = (flags + " --xla_force_host_platform_device_count=8").strip()
    env.update(JAX_PLATFORMS="cpu", XLA_FLAGS=flags, PYTHONPATH=REPO)
    proc = subprocess.run(cmd, capture_output=True, text=True, timeout=600,
                          env=env, cwd=REPO)
    data = None
    if os.path.exists(loss_out):
        with open(loss_out) as f:
            data = json.load(f)
    return proc, data


def test_mesh_checkpoint_kill_resume_bitwise(tmp_path):
    """A DP+ZeRO-1 supervised run on the 8-device mesh, hard-killed at
    step 8, auto-resumes in a FRESH PROCESS from the step-6 commit and
    reproduces the uninterrupted run's loss trajectory bitwise —
    sharded optimizer state and dropout PRNG both round-trip through
    the addressable-shard save + commit marker."""
    steps, every, kill_at = 12, 3, 8
    ck = tmp_path / "ck"

    ref_proc, ref = _spawn_child(tmp_path, "ref", steps,
                                 tmp_path / "ref_ck", every)
    assert ref_proc.returncode == 0, ref_proc.stderr[-2000:]

    kill_proc, _ = _spawn_child(tmp_path, "killed", steps, ck, every,
                                fault=f"kill@{kill_at}")
    assert kill_proc.returncode == resilience.KILL_EXIT_CODE, (
        kill_proc.returncode, kill_proc.stderr[-2000:])
    assert io.latest_checkpoint(str(ck)) == 6

    # the committed marker records the mesh that produced the trajectory
    marker = io.read_commit_marker(os.path.join(str(ck), "6"))
    assert marker["extra"]["mesh"] == {"dp": 8}

    res_proc, res = _spawn_child(tmp_path, "resumed", steps, ck, every)
    assert res_proc.returncode == 0, res_proc.stderr[-2000:]
    assert res["stats"]["resumed_from"] == 6
    mismatch = {s: (v, ref["losses"][s]) for s, v in res["losses"].items()
                if ref["losses"][s] != v}
    assert not mismatch, f"resumed trajectory diverged: {mismatch}"
    assert io.latest_checkpoint(str(ck)) == steps


def _child_main(argv):
    """Child-process entry for the kill/resume test: one supervised
    DP+ZeRO-1 partitioned run over the 8-device mesh."""
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, required=True)
    ap.add_argument("--ckpt-dir", required=True)
    ap.add_argument("--ckpt-every", type=int, default=3)
    ap.add_argument("--fault", default="")
    ap.add_argument("--loss-out", required=True)
    args = ap.parse_args(argv)

    main, startup, loss = _tagged_model(dropout=0.1)
    prog = fluid.CompiledProgram(main).with_partitioning(
        partition.PartitionConfig(mesh_axes={"dp": 8}, zero=1))
    scope = fluid.Scope()
    losses = {}
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        sup = resilience.Supervisor(
            exe, prog, checkpoint_dir=args.ckpt_dir,
            feed_fn=lambda s: _batch(s, n=8), fetch_list=[loss],
            policy=resilience.CheckpointPolicy(
                args.ckpt_dir, every_steps=args.ckpt_every, keep_last=3),
            fault_injector=resilience.FaultInjector(args.fault),
            on_step=lambda s, f: losses.__setitem__(
                s, float(np.asarray(f[0]))))
        stats = sup.run_loop(args.steps)
    with open(args.loss_out, "w") as f:
        json.dump({"losses": {str(s): v for s, v in losses.items()},
                   "stats": stats}, f)
    return 0


if __name__ == "__main__":
    sys.exit(_child_main(sys.argv[1:]))
