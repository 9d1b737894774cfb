"""Rehearsal of the hybrid serving cell at a tiny preset on the CPU, as
test_rehearsal.py does for the cells it names: a sound run is correct
and reports what the cell lists; with a fault planted underneath
(recurrent state lost between steps; one expert left out;
the engine's state or pages in a lower type) `correct` comes out false, by the check that
should catch it. And the operation counts against a hand figure. No
number printed here is a device metric."""

import os

import numpy as np
import pytest

import flops_hybrid
import harness
from tiny_hybrid import tiny_ctx

CELL = "granite4_h_small_chat_decode"


def failing(r):
    return {k for k, v in r["checks"].items()
            if v["value"] is None or not v["value"] <= v["limit"]}


def test_sound_run_is_correct_and_counts():
    ctx = tiny_ctx()
    kind = ctx.cell.kind().Kind(ctx)
    ctx.cell.kind = lambda: type("m", (), {"Kind": lambda _ctx: kind})
    r = harness.drive(ctx)
    assert r["correct"], r["checks"]
    assert r["failed"] == 0 and r["attempted"] > 0
    assert set(r["metrics"]) == {"itl_p95_ms", "setup_s"}
    assert r["checks"]["compiles_in_window"]["value"] == 0


def test_readers_read_the_window_counters():
    ctx = tiny_ctx(seconds=2.0)
    kind = ctx.cell.kind().Kind(ctx)
    kind.setup()
    raw = kind.window()
    kind.release()
    c, g = raw["counters"], raw["gauges"]
    # every token the window processed went through the 4 expert layers
    assert c["moe_tokens_routed_total"] > 0
    assert c["moe_tokens_routed_total"] % 4 == 0
    assert 0 < c["moe_held_assignments_total"] <= 3 * c["moe_tokens_routed_total"]
    assert c["ragged_steps_total"] == raw["ragged_steps"]
    assert g["moe_expert_load_max"] >= g["moe_expert_load_mean"] > 0
    x = {"raw": raw, "config": ctx.config, "traffic": ctx.traffic,
         "chips": 1, "peaks": ctx.peaks,
         "trace": {"step_device_s": [0.002] * 5, "window_s": raw["window_s"],
                   "busy_s": raw["window_s"] / 2, "mosaic_s": 0.0}}
    new = ("serve_step.mfu_hybrid", "serve_step.hbm_share_hybrid",
           "moe.held_assignments_per_token", "moe.expert_load_max_over_mean")
    assert set(new) <= {m["name"] for m in ctx.cell.per_layer}
    readers = {name: harness.load_module(os.path.join(
        harness.HERE, "metrics", name + ".py")) for name in new}
    values = {name: r.read(x) for name, r in readers.items()}
    per_token = values["moe.held_assignments_per_token"]
    assert 0.5 < per_token < 2.5            # 3 x 4/8 = 1.5 if even
    assert values["moe.expert_load_max_over_mean"] >= 1.0
    assert values["serve_step.mfu_hybrid"] > 0
    assert values["serve_step.hbm_share_hybrid"] > 0
    # a reader with nothing to read gives None, never 0
    bare = dict(x, raw={k: v for k, v in raw.items()
                        if k not in ("counters", "gauges")})
    for name, reader in readers.items():
        assert reader.read(bare) is None, name


def drive_broken(plant, seconds=2.0):
    ctx = tiny_ctx(seconds=seconds)
    Kind = ctx.cell.kind().Kind
    ctx.cell.kind = lambda: type("m", (), {"Kind": plant(Kind)})
    return harness.drive(ctx)


def state_dropped(Kind):
    """The recurrent state is lost between steps: the mixers take every
    row for a sequence's first and start it from zero state, so a token
    sees nothing of its predecessors through the Mamba layers (pages
    are still written and read where they belong). The opposite fault,
    a lane that keeps its predecessor's state, cannot be seen from
    served tokens at these weights: A_log = 0 and dt_bias = 0 make the
    state forget within a few tokens, less than a prompt (PERF.md,
    Open questions); tests/test_hybrid.py holds the reset at the
    logits."""
    class Broken(Kind):
        def setup(self):
            from paddle_tpu.ops import ssm
            from paddle_tpu.runtime import dispatch

            mixer = ssm.mamba2_mixer

            def always_fresh(x, num_valid, positions, *a, **kw):
                return mixer(x, num_valid, positions * 0, *a, **kw)

            # the process keeps compiled blocks by the program's content:
            # neither may an earlier test's sound step serve this run,
            # nor this run's broken one a later test
            dispatch._SHARED_CACHE.clear()
            ssm.mamba2_mixer = always_fresh
            try:
                Kind.setup(self)
            finally:
                ssm.mamba2_mixer = mixer
                dispatch._SHARED_CACHE.clear()
    return Broken


def expert_left_out(Kind):
    """Expert 0 of every layer gives nothing: its output matrix is
    zero in the program's copy of the weights only."""
    class Broken(Kind):
        def setup(self):
            model, build = self.model, self.model.build_engine

            def build_without(cfg, weights):
                weights = dict(weights)
                for name, w in weights.items():
                    if name.endswith("experts_out.w"):
                        weights[name] = w.at[0].set(0)
                return build(cfg, weights)

            model.build_engine = build_without
            try:
                Kind.setup(self)
            finally:
                model.build_engine = build
    return Broken


@pytest.mark.parametrize("plant", [state_dropped, expert_left_out])
def test_planted_fault_is_not_correct(plant):
    r = drive_broken(plant)
    assert not r["correct"]
    assert "served_logit_gap" in failing(r), r["checks"]


def test_reference_in_fp8_is_not_correct_at_tiny_size():
    ctx = tiny_ctx(seconds=2.0)
    kind = ctx.cell.kind().Kind(ctx)
    kind.setup()
    kind.window()
    kind.release()
    assert harness.judge(kind.check())
    assert not harness.judge(kind.control()["reference_in_fp8"])


def test_hybrid_flops_match_the_hand_figure():
    """granite-4.0-h-small, one token through the 10 layers held, without
    routed experts and head: Mamba 2*4096*16768 + 2*8192*4096 +
    4*128*64*128 = 208,666,624; attention 2*4096*(32+16)*128 +
    2*4096*4096 = 83,886,080; router + shared 2*4096*72 + 6*4096*1536 =
    38,338,560 a layer: 9 * 208,666,624 + 83,886,080 + 10 * 38,338,560
    = 2,345,271,296. A pair: 6 * 4096 * 768 = 18,874,368."""
    cfg = harness.load_json(os.path.join(
        harness.HERE, "configs", "granite4_h_small_serve.json"))
    assert flops_hybrid.token_flops(cfg) == 2_345_271_296
    assert flops_hybrid.assignment_flops(cfg) == 18_874_368
    assert flops_hybrid.hybrid_forward_flops(cfg, 10, 2, 1000, 50) == (
        10 * 2_345_271_296 + 50 * 18_874_368 + 2 * 2 * 4096 * 50176
        + 4 * 32 * 128 * 1000)
    # one call of the state kernel: 32 lanes x 128 x 64 x 128 of state,
    # read and written in float32, plus 2 x 32 x 16 x 8192 rows and
    # B, C and the decays; 4 x 16 multiply-add FLOPs an element
    state = 32 * 128 * 64 * 128
    assert flops_hybrid.state_step_call(cfg) == (
        64 * state, 8 * state + 4 * (2 * 32 * 16 * 8192
                                     + 2 * 32 * 16 * 128 + 32 * 128))
    reader = harness.load_module(os.path.join(
        harness.HERE, "metrics", "mamba2_state_step_roofline.py"))
    peaks = harness.load_peaks("TPU v5 lite")
    x = {"config": cfg, "peaks": peaks, "raw": {"ragged_steps": 100},
         "trace": {"top_ops": [["mosaic:mamba2_state_step f32[32,16,8192]",
                                0.5], ["fusion f32[1]", 9.0]]}}
    least = (8 * state + 4 * (2 * 32 * 16 * 8192 + 2 * 32 * 16 * 128
                              + 32 * 128)) / 819e9
    assert abs(reader.read(x) - 100 * 900 * least / 0.5) < 1e-9
    x["trace"]["top_ops"] = [["fusion f32[1]", 9.0]]
    assert reader.read(x) is None           # no such kernel: None, never 0
    reference = harness.load_module(os.path.join(
        harness.HERE, "models", "granite_hybrid_reference.py"))
    spec = reference.spec(cfg)
    n = sum(int(np.prod(s)) for _n, s, _i in spec)
    assert 4.75e9 < n < 4.77e9
    assert flops_hybrid.weight_bytes(cfg, spec) == 2 * n
    state = reference.state_bytes(cfg)
    assert 1.23e9 < state < 1.25e9
    assert reference.stated_storage_bytes(cfg) == (
        2 * n + 2 * 8 * 1088 * 16 * 128 * 2 + state)


@pytest.mark.parametrize("key,value", [("state_dtype", "bfloat16"),
                                       ("kv_dtype", "bfloat16")])
def test_engine_in_a_lower_type_fails_the_bytes(key, value):
    """The cell's controls on the program's side: the engine run with its
    recurrent state, or its pages, in a type other than the configuration
    states (control.py --set engine.state_dtype=bfloat16; the tiny preset
    states float32 pages, the cell bfloat16 and its control float32). The
    tokens cannot tell; the step's arguments do. Last of the file's runs:
    the kind finds the step it drove by its tag among the live ones, and
    this one's must not be found by a later test."""
    ctx = tiny_ctx(seconds=2.0)
    ctx.config["engine"][key] = value
    r = harness.drive(ctx)
    assert not r["correct"]
    assert failing(r) == {"step_argument_bytes_gap"}, r["checks"]
