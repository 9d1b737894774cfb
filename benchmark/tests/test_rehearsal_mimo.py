"""Rehearsal of the MiMo serving cell at a tiny preset on the CPU, as
test_rehearsal_hybrid.py does for its cell: a sound run is correct and
reports what the cell lists; every reader listed for the cell returns a
number on a traced-style pass whose idle gaps are short pauses only;
with a fault planted underneath the program, or in the mathematics,
`correct` comes out false by the check that should catch it. And the
operation and byte counts against a hand figure. No number printed here
is a device metric."""

import os

import numpy as np
import pytest

import flops_mimo
import harness
import span_math
from tiny_mimo import tiny_ctx

CELL = "mimo_v2_5_long_prompt_decode"
LISTED = {
    "engine.host_ms_per_step_serve", "serve_step.device_ms_p50",
    "kernels.mosaic_share_serve", "device.idle_share_serve",
    "device.peak_hbm_share_serve", "moe.held_assignments_per_token",
    "moe.expert_load_max_over_mean", "serve_step.mfu_mimo",
    "serve_step.hbm_share_mimo", "ragged_attention_roofline_mimo",
    "kv.window_resident_share"}


def failing(r):
    return {k for k, v in r["checks"].items()
            if v["value"] is None or not v["value"] <= v["limit"]}


def test_sound_run_is_correct_and_counts():
    ctx = tiny_ctx()
    r = harness.drive(ctx)
    assert r["correct"], r["checks"]
    assert r["failed"] == 0 and r["attempted"] > 0
    assert set(r["metrics"]) == {"itl_p95_ms", "setup_s"}
    assert r["checks"]["compiles_in_window"]["value"] == 0
    assert r["checks"]["step_argument_bytes_gap"]["value"] < 0.02


def test_every_listed_reader_returns_a_number():
    """One traced-style pass: the window's own counters and gauges, a
    trace summary in which the device was never idle but for pauses
    under 50 us (the usual case since the loop runs ahead, and what
    left `engine.idle_in_spans_share_serve` out of PR 34's line: that
    reader is not listed for this cell), and of the attention kernels
    only the full layers' among the top operations."""
    ctx = tiny_ctx(seconds=2.0)
    assert {m["name"] for m in ctx.cell.per_layer} == LISTED
    kind = ctx.cell.kind().Kind(ctx)
    kind.setup()
    raw = kind.window()
    kind.release()
    c, g = raw["counters"], raw["gauges"]
    assert c["moe_tokens_routed_total"] > 0
    assert c["moe_tokens_routed_total"] % 3 == 0        # 3 expert layers
    assert 0 < c["moe_held_assignments_total"] <= 3 * c["moe_tokens_routed_total"]
    assert 0 < c["attn_live_pages_window_total"] <= c["attn_live_pages_full_total"]
    assert c["kv_window_pages_recycled_total"] > 0 and c["evicted_total"] == 0
    assert 0 < g["kv_pages_resident_window"] <= 3 * 3
    trace = {"step_device_s": [0.002] * 5, "window_s": raw["window_s"],
             "busy_s": raw["window_s"] * 0.999, "mosaic_s": 0.3,
             "top_ops": [["mosaic:ragged_paged_attention f32[3,1,16,16]",
                          0.2], ["fusion f32[1]", 0.9]],
             "idle_gaps": [[span_math.SHORT_PAUSES, 0.001]]}
    x = {"trace": trace, "raw": raw, "peaks": ctx.peaks,
         "config": ctx.config, "traffic": ctx.traffic, "chips": 1,
         "memory_peak_bytes": 5e9}
    got = harness.read_per_layer(ctx.cell, x)
    assert set(got) == LISTED
    assert all(np.isfinite(v["value"]) for v in got.values()), got
    for name in ("serve_step.mfu_mimo", "serve_step.hbm_share_mimo",
                 "ragged_attention_roofline_mimo",
                 "kv.window_resident_share"):
        assert got[name]["value"] > 0, name
    # the window kernel's entry adds to the reading and is not needed
    only_full = got["ragged_attention_roofline_mimo"]["value"]
    trace["top_ops"].append(
        ["mosaic:ragged_paged_attention_window f32[3,2,8,16]", 0.05])
    both = harness.read_per_layer(ctx.cell, x)[
        "ragged_attention_roofline_mimo"]["value"]
    full_b = flops_mimo.walked_bytes(ctx.config, c, ["full"])
    all_b = flops_mimo.walked_bytes(ctx.config, c)
    assert abs(only_full - 100 * full_b / 1e11 / 0.2) < 1e-9
    assert abs(both - 100 * all_b / 1e11 / 0.25) < 1e-9
    # a reader with nothing to read gives None, never 0
    bare = dict(x, raw={k: v for k, v in raw.items()
                        if k not in ("counters", "gauges")},
                trace=dict(trace, top_ops=[["fusion f32[1]", 0.9]]))
    for name in ("serve_step.mfu_mimo", "serve_step.hbm_share_mimo",
                 "ragged_attention_roofline_mimo",
                 "kv.window_resident_share"):
        reader = harness.load_module(os.path.join(
            harness.HERE, "metrics", name + ".py"))
        assert reader.read(bare) is None, name


def program_built_with(**changed):
    """The program alone is built from a configuration with `changed`
    keys; the reference keeps the cell's."""
    def plant(Kind):
        class Broken(Kind):
            def setup(self):
                model, build = self.model, self.model.build_engine

                def build_other(cfg, weights):
                    other = dict(cfg, **{k: v for k, v in changed.items()
                                         if k != "engine"})
                    other["engine"] = dict(cfg["engine"],
                                           **changed.get("engine", {}))
                    return build(other, weights)

                model.build_engine = build_other
                try:
                    Kind.setup(self)
                finally:
                    model.build_engine = build
        return Broken
    return plant


@pytest.mark.parametrize("changed", [
    dict(engine={"kv_dtype": "float8_e4m3fn"}),         # (a) fp8 pages
    dict(sliding_window=19), dict(sliding_window=21),   # (b) window +- 1
    dict(add_swa_attention_sink_bias=False),            # (c) no sink
    dict(partial_rotary_factor=1.0),                    # (d) rotary on all
], ids=["fp8_pages", "window_19", "window_21", "no_sink", "rotary_all"])
def test_fault_planted_in_the_program_is_not_correct(changed):
    ctx = tiny_ctx(seconds=2.0)
    Kind = ctx.cell.kind().Kind
    ctx.cell.kind = lambda: type("m", (), {
        "Kind": program_built_with(**changed)(Kind)})
    r = harness.drive(ctx)
    assert not r["correct"]
    assert "served_logit_gap" in failing(r), r["checks"]


def test_faults_in_the_mathematics_read_over_the_limit():
    """(b)-(e) planted in the reference's own mathematics, and the
    reference in the precision below: the tokens each puts first lie
    further below the true reference's best than the limit allows."""
    ctx = tiny_ctx(seconds=2.0)
    kind = ctx.cell.kind().Kind(ctx)
    kind.setup()
    kind.window()
    kind.release()
    assert harness.judge(kind.check())
    assert not harness.judge(kind.control()["reference_in_fp8"])
    t = ctx.traffic
    for fault in kind.reference.FAULTS:
        gaps = kind.reference.served_gaps(
            ctx.config, kind.params, kind.pairs, int(t["check_pad_to"]),
            max(t["answer_lens"]), control=fault)
        assert max(gaps) > 10 * t["limits"]["served_logit_gap"], fault


def test_mimo_counts_match_the_hand_figure():
    """mimo_v2_5_serve, one token through the 7 layers held, without
    routed experts, head and attention over the cache: a full layer's
    projections 2*4096*(64*192 + 4*320) + 2*64*128*4096 = 178,257,920,
    a window layer's 2*4096*(64*192 + 8*320) + 67,108,864 = 188,743,680,
    the dense feed-forward 6*4096*16384 = 402,653,184, a router
    2*4096*256 = 2,097,152: 2 * 178,257,920 + 5 * 188,743,680 +
    402,653,184 + 6 * 2,097,152 = 1,715,470,336. A pair: 6 * 4096 * 2048
    = 50,331,648."""
    cfg = harness.load_json(os.path.join(
        harness.HERE, "configs", "mimo_v2_5_serve.json"))
    assert flops_mimo.token_flops(cfg) == 1_715_470_336
    assert flops_mimo.assignment_flops(cfg) == 50_331_648
    pair = 2 * 64 * 320
    assert flops_mimo.forward_flops(cfg, 10, 2, 100000, 50) == (
        10 * 1_715_470_336 + 50 * 50_331_648 + 2 * 2 * 4096 * 19072
        + 2 * pair * 100000 + 5 * pair * 128 * 10)
    assert flops_mimo.page_bytes(cfg, "full") == 4 * 128 * 320 * 2 == 327_680
    assert flops_mimo.page_bytes(cfg, "window") == 655_360
    c = {"attn_live_pages_full_total": 1000,
         "attn_live_pages_window_total": 30}
    assert flops_mimo.walked_bytes(cfg, c) == (
        1000 * 327_680 * 2 + 30 * 655_360 * 5)
    reference = harness.load_module(os.path.join(
        harness.HERE, "models", "mimo_reference.py"))
    spec = reference.spec(cfg)
    n = sum(int(np.prod(s)) for _n, s, _i in spec)
    assert 3.42e9 < n < 3.44e9
    full, window = reference.pool_bytes(cfg)
    assert full == 2 * 6657 * 327_680 and 4.35e9 < full < 4.37e9
    assert window == 5 * 97 * 655_360
    assert reference.stated_storage_bytes(cfg) == (
        2 * n + full + window + 4 * 6 * 16)
    assert flops_mimo.weight_bytes(cfg, spec, 200) == 2 * (
        n - 19072 * 4096 + 200 * 4096)


def test_engine_in_another_type_fails_the_bytes():
    """The cell's control on the program's side: pages in a type other
    than the configuration states (the tiny preset states float32, the
    cell bfloat16). Last of the file's runs: the kind finds the step it
    drove by its tag among the live ones."""
    ctx = tiny_ctx(seconds=2.0)
    ctx.config["engine"]["kv_dtype"] = "bfloat16"
    r = harness.drive(ctx)
    assert not r["correct"]
    assert "step_argument_bytes_gap" in failing(r), r["checks"]
