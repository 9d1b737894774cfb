"""distlint — the distributed/TPU analysis pass families (ISSUE 16):

  PTL06x partition consistency, PTL07x collective safety, PTL08x
  donation/aliasing, PTL09x kernel call-site geometry.

Per family: a known-bad fixture asserting the exact code and a clean
fixture asserting silence; plus the cross-cutting contracts — strict
mode raises BEFORE lowering, ``lint_suppress`` covers the new codes,
the donation plan is derived through the executor's own classifier,
the kernel table and the runtime guards share one geometry helper, and
the regression fixtures for the latent inconsistencies this lint
surfaced (DEFAULT_RULES mapped ``expert`` to ``tp`` while every
expert-parallel mesh in the codebase is named ``ep``; the GPT megatron
sharding pays a vocab-sharded softmax reduction PTL063 makes visible).
"""

import importlib.util
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import analysis
from paddle_tpu.analysis import dist_passes

DIST_PASSES = ["partition-consistency", "collective-safety",
               "donation-safety", "kernel-geometry"]

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _codes(report):
    return [d.code for d in report.diagnostics]


def _dist_lint(program, mesh_axes=None, rules=None, feed_names=None,
               fetch_names=None):
    return analysis.analyze_program(
        program, passes=DIST_PASSES, mesh_axes=mesh_axes, rules=rules,
        feed_names=feed_names, fetch_names=fetch_names)


@pytest.fixture
def flag_guard():
    prev = fluid.get_flags(["validate_program"])
    yield
    fluid.set_flags(prev)


def _tagged_fc_program(logical_axes=("embed", "mlp"), sharding=None,
                       in_dim=64, out_dim=256):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = fluid.layers.data("x", [in_dim])
        attr = fluid.ParamAttr(name="w0", logical_axes=logical_axes)
        out = fluid.layers.fc(x, out_dim, param_attr=attr)
    if sharding is not None:
        main.global_block().var("w0").sharding = sharding
    return main, startup, out


# -------------------------------------------------------------------------
# PTL06x — partition consistency
# -------------------------------------------------------------------------


def test_ptl060_arity_mismatch():
    # the layer builder rejects bad arity at construction time, so a
    # mismatch can only arrive via serialized/hand-built programs —
    # mutate the var the way a stale checkpoint would present it
    main, _, _ = _tagged_fc_program(logical_axes=("embed", "mlp"))
    main.global_block().var("w0").logical_axes = ("embed", "mlp",
                                                  "heads")
    r = _dist_lint(main, mesh_axes={"tp": 4})
    assert any(d.code == "PTL060" and "line them up" in d.message
               for d in r.warnings)


def test_ptl060_dead_logical_axis_is_meshless_finding():
    """A tag no rule maps is wrong on EVERY mesh — it fires without a
    mesh context too."""
    main, _, _ = _tagged_fc_program(logical_axes=("embed", "headz"))
    r = _dist_lint(main)  # no mesh supplied
    hits = [d for d in r.warnings if d.code == "PTL060"]
    assert hits and "headz" in hits[0].message
    assert hits[0].loc.var == "w0"


def test_ptl060_explicit_sharding_absent_mesh_axis():
    """The BERT-class bug: megatron tags name axis 'mp' but the serving
    mesh only has 'tp' — the resolver silently replicates everything."""
    main, _, _ = _tagged_fc_program(logical_axes=None,
                                    sharding=(None, "mp"))
    r = _dist_lint(main, mesh_axes={"dp": 2, "tp": 4})
    assert any(d.code == "PTL060" and "'mp'" in d.message
               for d in r.warnings)
    # same program on a mesh that HAS the axis: silent
    r2 = _dist_lint(main, mesh_axes={"mp": 4})
    assert not r2.errors and not r2.warnings


def test_ptl061_duplicate_axis_in_explicit_spec():
    main, _, _ = _tagged_fc_program(logical_axes=None,
                                    sharding=("tp", "tp"))
    r = _dist_lint(main, mesh_axes={"tp": 4})
    assert any(d.code == "PTL061" for d in r.errors)


def test_ptl061_explicit_vs_rules_disagreement():
    """logical_axes resolve dim 1 to tp (mlp rule) while the explicit
    spec pins it on dp — two sources, two placements."""
    main, _, _ = _tagged_fc_program(logical_axes=("embed", "mlp"),
                                    sharding=(None, "dp"))
    r = _dist_lint(main, mesh_axes={"dp": 2, "tp": 4})
    hits = [d for d in r.warnings if d.code == "PTL061"]
    assert hits and "disagree" in hits[0].message


def test_ptl062_explicit_non_divisible_is_error():
    main, _, _ = _tagged_fc_program(logical_axes=None,
                                    sharding=(None, "tp"), out_dim=10)
    r = _dist_lint(main, mesh_axes={"tp": 4})
    assert any(d.code == "PTL062" for d in r.errors)


def test_ptl062_rules_skip_non_divisible_is_warning():
    main, _, _ = _tagged_fc_program(logical_axes=("embed", "mlp"),
                                    out_dim=10)
    r = _dist_lint(main, mesh_axes={"tp": 4})
    hits = [d for d in r.warnings if d.code == "PTL062"]
    assert hits and "not divisible" in hits[0].message


def test_ptl063_reshard_hotspot_is_info_and_never_fails_strict():
    """Row-parallel weight: the matmul contracts over the sharded dim,
    GSPMD inserts an allreduce. Intended megatron behaviour — INFO."""
    main, _, _ = _tagged_fc_program(logical_axes=("mlp", "embed"),
                                    in_dim=256, out_dim=64)
    r = _dist_lint(main, mesh_axes={"tp": 4})
    infos = [d for d in r.diagnostics if d.severity == analysis.INFO]
    assert any(d.code == "PTL063" for d in infos)
    assert not r.errors and not r.warnings  # strict/--strict stay green


def test_ptl063_cites_gpt_vocab_sharded_softmax():
    """The latent finding on the repo's own model zoo: megatron-sharded
    GPT pays a cross-shard softmax_with_cross_entropy over the
    vocab-sharded logits — invisible before this pass."""
    from paddle_tpu.models import (GPTConfig, build_gpt_lm,
                                   apply_gpt_megatron_sharding)

    cfg = GPTConfig(vocab_size=512, hidden_size=64, num_layers=1,
                    num_heads=4)
    main, _, _, fetches = build_gpt_lm(cfg, 16)
    apply_gpt_megatron_sharding(main, mp_axis="tp")
    r = _dist_lint(main, mesh_axes={"dp": 2, "tp": 4},
                   fetch_names=[fetches["loss"].name])
    assert not r.errors and not r.warnings
    softmax_hits = [
        d for d in r.diagnostics
        if d.code == "PTL063"
        and d.loc.op_type == "softmax_with_cross_entropy"
    ]
    assert softmax_hits, "the vocab-sharded logits hotspot must surface"


def test_default_rules_expert_axis_regression():
    """Regression for the rules-table inconsistency this lint caught:
    DEFAULT_RULES shipped ``expert -> tp`` while with_expert_parallel,
    ops/moe.py and the MoE examples all build the expert axis as
    ``ep`` — an expert-tagged tensor could never shard on an actual
    expert-parallel mesh (the rule was silently inapplicable)."""
    from paddle_tpu.partition.rules import DEFAULT_RULES, resolve_spec

    assert ("expert", "ep") in tuple(DEFAULT_RULES)
    spec, skipped = resolve_spec(("expert", "embed"), DEFAULT_RULES,
                                 {"dp": 2, "ep": 4}, (8, 64))
    assert spec == ("ep", None) and not skipped

    # and the PTL060 INFO that surfaces this class of dead mapping:
    # under the OLD table the tag resolves to nothing on an ep mesh
    old_rules = tuple(r if r[0] != "expert" else ("expert", "tp")
                      for r in DEFAULT_RULES)
    main, _, _ = _tagged_fc_program(logical_axes=("expert", "mlp"),
                                    in_dim=64, out_dim=256)
    r_old = _dist_lint(main, mesh_axes={"dp": 2, "ep": 4},
                       rules=old_rules)
    assert any(d.code == "PTL060" and "'expert'" in d.message
               and d.severity == analysis.INFO
               for d in r_old.diagnostics)
    r_new = _dist_lint(main, mesh_axes={"dp": 2, "ep": 4})
    assert not any("'expert'" in d.message for d in r_new.diagnostics
                   if d.code == "PTL060")


def test_gpt_accumulator_sharding_regression():
    """Regression for the second latent inconsistency distlint caught:
    apply_gpt_megatron_sharding matched param names by SUBSTRING, so
    Adam's scalar beta-pow accumulators (dec0_qkv.w_beta1_pow_acc_0,
    shape [1]) inherited rank-2 specs — PTL060 arity + PTL062
    non-dividing errors on every trained megatron GPT. Accumulators
    now inherit structurally, shape-guarded, like models/bert.py."""
    from paddle_tpu.models import (GPTConfig, build_gpt_lm,
                                   apply_gpt_megatron_sharding)

    cfg = GPTConfig(vocab_size=128, hidden_size=32, num_layers=1,
                    num_heads=2)
    with fluid.unique_name.guard():
        main, _, _, fetches = build_gpt_lm(
            cfg, 8, optimizer=fluid.optimizer.Adam(1e-4))
    apply_gpt_megatron_sharding(main, mp_axis="tp")
    gb = main.global_block()
    # moment buffers (param-shaped) inherit; scalar beta-pow does not
    assert gb.vars["dec0_qkv.w_moment1_0"].sharding == (None, "tp")
    assert gb.vars["dec0_qkv.w_beta1_pow_acc_0"].sharding is None
    r = _dist_lint(main, mesh_axes={"dp": 2, "tp": 4},
                   fetch_names=[fetches["loss"].name])
    assert not r.errors and not r.warnings, _codes(r)


def _quantized_mlp(mode="int8_block", block=16):
    from paddle_tpu import quantize

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = fluid.layers.data("x", [16])
        attr = fluid.ParamAttr(name="w0", logical_axes=("embed", "mlp"))
        h = fluid.layers.fc(x, 32, act="relu", param_attr=attr)
        out = fluid.layers.fc(h, 8)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.TPUPlace())
        exe.run(startup)
        rep = quantize.rewrite_for_inference(main, scope, mode,
                                             block=block)
    return main, scope, rep


def test_ptl064_quantized_tag_inheritance_holds_and_breaks():
    main, _, rep = _quantized_mlp()
    # the rewrite recorded the inheritance machine-readably
    rows = [r for r in rep.tag_rows if r["name"] == "w0"]
    assert rows and not rows[0]["dropped_reason"]
    r = _dist_lint(main, mesh_axes={"tp": 4})
    assert not any(d.code == "PTL064" for d in r.diagnostics)

    # corrupt the scale plane's tags: the invariant must fire
    main.global_block().var("w0.qscale").logical_axes = ("embed", "mlp")
    r2 = _dist_lint(main, mesh_axes={"tp": 4})
    assert any(d.code == "PTL064" for d in r2.errors)


def test_ptl060_quantize_dropped_tags_are_errors():
    """A tag arity the 2-D quantized layout cannot inherit is recorded
    by the rewrite and reported as a lost partition intent."""
    main, startup, _ = _tagged_fc_program(logical_axes=("embed", "mlp"))
    # an arity the rewrite can't map onto the 2-D quantized layout
    # (build-time validation forbids authoring it, but serialized /
    # hand-patched programs can still present it)
    main.global_block().var("w0").logical_axes = ("embed",)
    from paddle_tpu import quantize

    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.TPUPlace())
        exe.run(startup)
        quantize.rewrite_for_inference(main, scope, "int8")
    rec = getattr(main, "_quant_tag_record", None)
    assert rec and rec[0]["dropped_reason"]
    r = _dist_lint(main, mesh_axes={"tp": 4})
    assert any(d.code == "PTL060" and "dropped" in d.message
               for d in r.errors)


# -------------------------------------------------------------------------
# PTL07x — collective safety
# -------------------------------------------------------------------------


def _transpiled_gpt(nrings=2):
    from paddle_tpu.models import GPTConfig, build_gpt_lm
    from paddle_tpu.transpiler.collective import GradAllReduce

    cfg = GPTConfig(vocab_size=128, hidden_size=32, num_layers=1,
                    num_heads=2)
    with fluid.unique_name.guard():
        main, startup, _, fetches = build_gpt_lm(
            cfg, 8, optimizer=fluid.optimizer.SGD(1e-3))
    t = GradAllReduce(nrings=nrings)
    t.transpile(startup, main, rank=0, endpoints=["a:1", "b:2"],
                current_endpoint="a:1", wait_port=False)
    return main, startup


def test_collective_clean_transpiled_program():
    main, startup = _transpiled_gpt()
    for prog in (main, startup):
        r = _dist_lint(prog)
        assert not r.errors and not r.warnings, _codes(r)


def test_ptl070_collective_in_data_dependent_control_flow():
    p = fluid.Program()
    gb = p.global_block()
    x = gb.create_var(name="x", shape=[4], dtype="float32",
                      persistable=True)
    cond = gb.create_var(name="cond", shape=[1], dtype="bool")
    body = p._create_block()
    body.create_var(name="x_local", shape=[4], dtype="float32")
    body.append_op("c_allreduce_sum", inputs={"X": ["x"]},
                   outputs={"Out": ["x"]}, attrs={"ring_id": 0})
    p._rollback()
    gb.append_op("while", inputs={"Condition": ["cond"]}, outputs={},
                 attrs={"sub_block": body})
    r = _dist_lint(p)
    hits = [d for d in r.errors if d.code == "PTL070"]
    assert hits and "while" in hits[0].message


def test_ptl072_ring_never_initialized():
    main, _ = _transpiled_gpt(nrings=2)
    gb = main.global_block()
    colls = [op for op in gb.ops
             if op.type in dist_passes.COLLECTIVE_OPS]
    assert colls, "transpiled program must carry collectives"
    colls[0].attrs["ring_id"] = 9
    r = _dist_lint(main)
    hits = [d for d in r.errors if d.code == "PTL072"]
    assert hits and "ring_id 9" in hits[0].message


def test_ptl073_divergent_streams_across_ranks():
    main_a, _ = _transpiled_gpt()
    main_b, _ = _transpiled_gpt()
    gb = main_b.global_block()
    idx = next(i for i, op in enumerate(gb.ops)
               if op.type in dist_passes.COLLECTIVE_OPS)
    del gb.ops[idx]
    findings = dist_passes.check_program_batch(
        {"rank0": main_a, "rank1": main_b})
    ptl073 = [f for f in findings if f[0] == "PTL073"]
    assert ptl073 and "deadlock" in ptl073[0][2] or "blocks" in ptl073[0][2]

    # identical ranks: silent
    main_c, _ = _transpiled_gpt()
    main_d, _ = _transpiled_gpt()
    assert not dist_passes.check_program_batch(
        {"rank0": main_c, "rank1": main_d})


# -------------------------------------------------------------------------
# PTL08x — donation / aliasing
# -------------------------------------------------------------------------


def _counter_program():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        step = fluid.layers.create_global_var(
            [1], 0.0, "float32", persistable=True, name="step")
        fluid.layers.increment(step)
    return main, startup


def test_donation_plan_matches_executor_classifier():
    """donation_plan is analyze_block_state verbatim — the static plan
    and the runtime donate_argnums share one derivation."""
    from paddle_tpu.core.executor import analyze_block_state

    main, _ = _counter_program()
    plan = dist_passes.donation_plan(main)
    state, written = analyze_block_state(main.global_block(), [])
    assert plan["state"] == state and plan["written"] == written
    assert plan["donatable"] == ["step"]


def test_ptl082_fed_var_is_donated_state():
    main, _ = _counter_program()
    r = _dist_lint(main, feed_names=["step"])
    hits = [d for d in r.errors if d.code == "PTL082"]
    assert hits and hits[0].loc.var == "step"
    # not fed: no aliasing hazard
    assert not any(d.code == "PTL082"
                   for d in _dist_lint(main).diagnostics)


def test_ptl081_double_in_place_update():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = fluid.layers.data("x", [4])
        y = fluid.layers.data("y", [1])
        pred = fluid.layers.fc(x, 1)
        loss = fluid.layers.mean(
            fluid.layers.square_error_cost(pred, y))
        fluid.optimizer.SGD(0.1).minimize(loss)
        fluid.optimizer.SGD(0.1).minimize(loss)
    r = _dist_lint(main)
    hits = [d for d in r.warnings if d.code == "PTL081"]
    assert hits, "two sgd updates of one param must warn"
    assert "sgd" in hits[0].message

    # single minimize: quiet
    main2, startup2 = fluid.Program(), fluid.Program()
    with fluid.program_guard(main2, startup2), fluid.unique_name.guard():
        x = fluid.layers.data("x", [4])
        y = fluid.layers.data("y", [1])
        pred = fluid.layers.fc(x, 1)
        loss = fluid.layers.mean(
            fluid.layers.square_error_cost(pred, y))
        fluid.optimizer.SGD(0.1).minimize(loss)
    assert not any(d.code == "PTL081"
                   for d in _dist_lint(main2).diagnostics)


def test_ptl080_cross_program_quantize_erasure():
    """Program A was quantize-rewritten (fc weights erased from the
    shared scope); program B still reads them as state — B's bind
    would KeyError. The batch check makes it a static finding."""
    qmain, _, _ = _quantized_mlp(mode="int8")
    with fluid.unique_name.guard():
        stale_main, stale_startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(stale_main, stale_startup):
            x = fluid.layers.data("x", [16])
            attr = fluid.ParamAttr(name="w0",
                                   logical_axes=("embed", "mlp"))
            h = fluid.layers.fc(x, 32, act="relu", param_attr=attr)
            fluid.layers.fc(h, 8)
    findings = dist_passes.check_program_batch(
        {"quantized": qmain, "stale": stale_main})
    ptl080 = [f for f in findings if f[0] == "PTL080"]
    assert ptl080 and ptl080[0][1] == "stale"
    assert "rewritten together" in ptl080[0][2]


def test_donation_audit_static_cross_check_passes():
    """Satellite: the live donation audit and the static PTL08x plan
    agree (drift between them is a failure)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(_REPO, "tools",
                                      "donation_audit.py"),
         "--check-static"],
        cwd=_REPO, env=env, capture_output=True, text=True, timeout=560)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "static" in proc.stdout.lower()


# -------------------------------------------------------------------------
# PTL09x — kernel call-site geometry
# -------------------------------------------------------------------------


def _kernel_call_program(op_type, shapes, attrs, extra_outputs=("Out",)):
    p = fluid.Program()
    gb = p.global_block()
    inputs = {}
    for slot, shape in shapes.items():
        name = slot.lower()
        gb.create_var(name=name, shape=list(shape), dtype="float32")
        inputs[slot] = [name]
    outputs = {}
    for slot in extra_outputs:
        name = f"out_{slot.lower()}"
        gb.create_var(name=name, shape=[1], dtype="float32")
        outputs[slot] = [name]
    gb.append_op(op_type, inputs=inputs, outputs=outputs, attrs=attrs)
    return p, gb.ops[-1]


def test_ptl092_int8_block_bad_block_matches_runtime_guard():
    """The static finding and the runtime backstop share ONE helper —
    the messages can never drift."""
    from paddle_tpu.kernels.constraints import int8_block_geometry_issue

    p, _ = _kernel_call_program(
        "quantized_matmul",
        {"X": (4, 1000), "QWeight": (1000, 64), "Scale": (4, 64)},
        {"quant_mode": "int8_block", "quant_block": 250})
    r = _dist_lint(p)
    hits = [d for d in r.warnings if d.code == "PTL092"]
    assert hits
    assert int8_block_geometry_issue(1000, 250) in hits[0].message

    # lane-aligned block: clean; single covering block: clean
    assert int8_block_geometry_issue(1000, 256) is None
    assert int8_block_geometry_issue(100, 112) is None
    # the grid equivalence with the old runtime condition
    for K in (64, 100, 128, 1000):
        for blk in (32, 100, 112, 128, 250, 256):
            Kp = -(-K // blk) * blk
            legacy_bad = (blk % 128 != 0) and (Kp != blk)
            assert (int8_block_geometry_issue(K, blk) is not None) \
                == legacy_bad, (K, blk)


def test_ptl091_force_pallas_escalates_to_error(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_FORCE_PALLAS", "1")
    p, _ = _kernel_call_program(
        "quantized_matmul",
        {"X": (4, 1000), "QWeight": (1000, 64), "Scale": (4, 64)},
        {"quant_mode": "int8_block", "quant_block": 250})
    r = _dist_lint(p)
    assert any(d.code == "PTL091" for d in r.errors)
    assert not any(d.code == "PTL092" for d in r.diagnostics)


def test_ptl093_flash_attention_heads_contract():
    p, _ = _kernel_call_program(
        "flash_attention",
        {"Q": (2, 16, 48), "K": (2, 16, 48), "V": (2, 16, 48)},
        {"num_heads": 5})
    r = _dist_lint(p)
    hits = [d for d in r.errors if d.code == "PTL093"]
    assert hits and "num_heads=5" in hits[0].message


def test_ptl093_paged_attention_rejects_prefill_q():
    p, _ = _kernel_call_program(
        "paged_attention",
        {"Q": (2, 16, 64), "KPages": (4, 8, 16, 16),
         "VPages": (4, 8, 16, 16)},
        {"num_heads": 4})
    r = _dist_lint(p)
    assert any(d.code == "PTL093" and "decode op" in d.message
               for d in r.errors)


def test_ptl094_flash_attention_vmem_budget():
    p, _ = _kernel_call_program(
        "flash_attention",
        {"Q": (1, 16384, 128), "K": (1, 16384, 128),
         "V": (1, 16384, 128)},
        {"num_heads": 1})
    r = _dist_lint(p)
    hits = [d for d in r.warnings if d.code == "PTL094"]
    assert hits and "VMEM" in hits[0].message


def test_kernel_geometry_dynamic_dims_stay_quiet():
    p, _ = _kernel_call_program(
        "flash_attention",
        {"Q": (-1, -1, -1), "K": (-1, -1, -1), "V": (-1, -1, -1)},
        {"num_heads": 5})
    r = _dist_lint(p)
    assert not r.errors and not r.warnings


def test_generation_programs_pass_strict_distlint():
    """Every registered Pallas kernel as actually emitted by the
    generation builders (flash_attention, kv_cache_write,
    paged_attention, ragged_paged_attention) lints clean."""
    import paddle_tpu.generation.model as gm
    from paddle_tpu.models import GPTConfig

    cfg = GPTConfig(vocab_size=128, hidden_size=64, num_layers=1,
                    num_heads=4)
    geom = gm.CacheGeometry(num_pages=16, page_size=8,
                            max_pages_per_seq=4)
    for label, prog in [
        ("lm", gm.build_lm_program(cfg, 16)[0]),
        ("prefill", gm.build_prefill_program(cfg, 16, geom)[0]),
        ("decode", gm.build_decode_program(cfg, geom)[0]),
        ("ragged", gm.build_ragged_step_program(cfg, geom, 8,
                                                "float32")[0]),
    ]:
        r = _dist_lint(prog, mesh_axes={"tp": 4})
        assert not r.errors and not r.warnings, (label, _codes(r))


def test_constraint_table_covers_registered_kernels():
    from paddle_tpu.kernels.constraints import (constrained_op_types,
                                                constraint_table)

    ops = constrained_op_types()
    for required in ("quantized_matmul", "quantized_fc",
                     "flash_attention", "paged_attention",
                     "kv_cache_write", "ragged_paged_attention",
                     "fused_adam", "fused_momentum", "layer_norm",
                     "softmax_with_cross_entropy"):
        assert required in ops, required
    table = constraint_table()
    assert all(isinstance(v, str) and v for v in table.values())


# -------------------------------------------------------------------------
# cross-cutting: suppression, strict mode, CLI, serving hook
# -------------------------------------------------------------------------


def test_lint_suppress_covers_dist_codes():
    p, op = _kernel_call_program(
        "flash_attention",
        {"Q": (2, 16, 48), "K": (2, 16, 48), "V": (2, 16, 48)},
        {"num_heads": 5})
    op.attrs["lint_suppress"] = ["PTL093"]
    r = _dist_lint(p)
    assert not any(d.code == "PTL093" for d in r.diagnostics)


def test_strict_mode_rejects_dist_error_before_lowering(monkeypatch,
                                                        flag_guard):
    from paddle_tpu.core import executor as executor_mod

    lowered = []
    orig = executor_mod._lower_block

    def probe(*args, **kwargs):
        lowered.append(1)
        return orig(*args, **kwargs)

    monkeypatch.setattr(executor_mod, "_lower_block", probe)
    fluid.set_flags({"validate_program": "strict"})
    main, _ = _counter_program()
    exe = fluid.Executor(fluid.TPUPlace())
    with pytest.raises(analysis.ProgramVerificationError) as ei:
        exe.run(main, feed={"step": np.zeros(1, "float32")},
                fetch_list=["step"])
    assert "PTL082" in str(ei.value)
    assert lowered == [], "dist findings must reject before lowering"


def _load_proglint():
    spec = importlib.util.spec_from_file_location(
        "proglint", os.path.join(_REPO, "tools", "proglint.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_proglint_dist_mode_cross_checks_batch(tmp_path, capsys):
    qmain, _, _ = _quantized_mlp(mode="int8")
    with fluid.unique_name.guard():
        stale_main, stale_startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(stale_main, stale_startup):
            x = fluid.layers.data("x", [16])
            h = fluid.layers.fc(x, 32, act="relu",
                                param_attr=fluid.ParamAttr(name="w0"))
            fluid.layers.fc(h, 8)
    qp, sp = tmp_path / "quantized.json", tmp_path / "stale.json"
    qp.write_text(qmain.to_json())
    sp.write_text(stale_main.to_json())
    proglint = _load_proglint()
    rc = proglint.main(["--json", "--dist", "--mesh", "tp=4",
                        str(qp), str(sp)])
    out = json.loads(capsys.readouterr().out)
    assert rc == 1
    stale_doc = next(p for p in out["programs"]
                     if p["program"] == "stale.json")
    assert any(d["code"] == "PTL080"
               for d in stale_doc["diagnostics"])

    # the same two programs WITHOUT --dist: no cross-program findings
    rc2 = proglint.main(["--json", str(qp), str(sp)])
    out2 = json.loads(capsys.readouterr().out)
    assert rc2 == 0
    assert not any(d["code"] == "PTL080"
                   for p in out2["programs"]
                   for d in p["diagnostics"])


def test_proglint_rejects_bad_mesh_spec(capsys):
    proglint = _load_proglint()
    rc = proglint.main(["--mesh", "dp=x", "nonexistent.json"])
    assert rc == 2


def test_compiled_program_validate_threads_mesh():
    """CompiledProgram.validate resolves its own mesh into the PTL06x
    context: the row-parallel hotspot is visible with zero extra
    arguments."""
    from paddle_tpu.partition import PartitionConfig

    main, _, _ = _tagged_fc_program(logical_axes=("mlp", "embed"),
                                    in_dim=256, out_dim=64)
    cp = fluid.CompiledProgram(main).with_partitioning(
        PartitionConfig(mesh_axes={"tp": 8}))
    report = cp.validate()
    assert any(d.code == "PTL063" for d in report.diagnostics)


def test_predictor_partitioned_load_carries_lint_report(tmp_path):
    from paddle_tpu.inference import Config, create_predictor
    from paddle_tpu.serving import ServingEngine

    model_dir = str(tmp_path / "model")
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = fluid.layers.data("x", [16])
        h = fluid.layers.fc(
            x, 32, act="relu",
            param_attr=fluid.ParamAttr(name="p_w1",
                                       logical_axes=("embed", "mlp")))
        out = fluid.layers.fc(h, 8)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        fluid.io.save_inference_model(model_dir, ["x"], [out], exe, main)

    cfg = Config(model_dir)
    cfg.enable_partitioning(mesh_axes={"tp": 8})
    pred = create_predictor(cfg)
    assert pred.lint_report is not None
    assert not pred.lint_report.errors, _codes(pred.lint_report)
    # the engine surfaces it without running anything
    eng = ServingEngine(pred, num_workers=1, start=False)
    st = eng.predictor_stats()
    assert "distlint" in st and st["distlint"]["errors"] == 0

    # unpartitioned load: no mesh, no lint report
    pred2 = create_predictor(Config(model_dir))
    assert pred2.lint_report is None
