"""Rehearsal of the openPangu serving cell at a tiny preset on the CPU,
as test_rehearsal_mimo.py does for its cell: a sound run is correct and
reports what the cell lists; every reader listed for the cell returns a
number on a traced-style pass whose idle gaps are short pauses only;
with a fault planted underneath the program, or in the mathematics,
`correct` comes out false by the check that should catch it. And the
operation and byte counts against a hand figure. No number printed here
is a device metric."""

import os

import numpy as np
import pytest

import flops_openpangu
import harness
import span_math
from tiny_openpangu import tiny_ctx

CELL = "openpangu_ultra_moe_long_doc_decode"
LISTED = {
    "engine.host_ms_per_step_serve", "serve_step.device_ms_p50",
    "kernels.mosaic_share_serve", "device.idle_share_serve",
    "device.peak_hbm_share_serve", "moe.held_assignments_per_token",
    "moe.expert_load_max_over_mean", "host.gc_idle_share_serve",
    "engine.fetch_ready_idle_share_serve", "latent_attention_roofline_pangu",
    "serve_step.mfu_pangu", "serve_step.hbm_share_pangu"}
NEW = ("latent_attention_roofline_pangu", "serve_step.mfu_pangu",
       "serve_step.hbm_share_pangu")


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
    """One traced-style pass: the window's own counters and gauges, and
    a trace summary in which the device was never idle but for pauses
    under 50 us, with the latent kernel among the top operations."""
    ctx = tiny_ctx(seconds=2.0)
    assert {m["name"] for m in ctx.cell.per_layer} == LISTED
    kind = ctx.cell.kind().Kind(ctx)
    kind.setup()
    raw = kind.window()
    kind.release()
    c, g = raw["counters"], raw["gauges"]
    assert g["latent_layers"] == 3
    assert c["moe_tokens_routed_total"] > 0
    assert c["moe_tokens_routed_total"] % 2 == 0        # 2 expert layers
    assert c["attn_latent_pages_total"] == 3 * c["attn_live_pages_total"]
    # a valid token attends at least one key, and every page holds
    # page_size of them at most
    assert c["attn_latent_pages_total"] > 0
    assert c["attn_latent_key_rows_total"] >= 3 * raw["tokens_processed"]
    assert c["evicted_total"] == 0
    trace = {"step_device_s": [0.002] * 5, "window_s": raw["window_s"],
             "busy_s": raw["window_s"] * 0.999, "mosaic_s": 0.3,
             "top_ops": [["mosaic:latent_paged_attention f32[3,4,4,16]",
                          0.2], ["fusion f32[1]", 0.9]],
             "idle_gaps": [[span_math.SHORT_PAUSES, 0.001]]}
    x = {"trace": trace, "raw": raw, "peaks": ctx.peaks,
         "config": ctx.config, "traffic": ctx.traffic, "chips": 1,
         "memory_peak_bytes": 5e9}
    got = harness.read_per_layer(ctx.cell, x)
    assert set(got) == LISTED
    assert all(np.isfinite(v["value"]) for v in got.values()), got
    for name in NEW:
        assert got[name]["value"] > 0, name
    # the roofline is the larger of the two bounds over the kernel's time
    flops = flops_openpangu.attention_flops(ctx.config, c)
    walked = flops_openpangu.walked_bytes(ctx.config, c)
    assert abs(got["latent_attention_roofline_pangu"]["value"] - 100 * max(
        flops / 1e12, walked / 1e11) / 0.2) < 1e-9
    # a reader with nothing to read gives None, never 0: a program
    # without the latent counters (the parent's) or the kernel's name
    bare = dict(x, raw={k: v for k, v in raw.items()
                        if k not in ("counters", "gauges")},
                trace=dict(trace, top_ops=[["fusion f32[1]", 0.9]]))
    for name in NEW:
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
    dict(engine={"kv_dtype": "float8_e4m3fn"}),         # fp8 pages
    dict(sandwich_norm=False),                          # no post norms
    dict(routed_scaling_factor=1.0),                    # routed scale 1
    dict(n_shared_experts=0),                           # no shared expert
    dict(rope_theta=10000),                             # another rope base
], ids=["fp8_pages", "no_post_norm", "routed_scale_1", "no_shared",
        "rope_base"])
def test_fault_planted_in_the_program_is_not_correct(changed):
    ctx = tiny_ctx(seconds=2.0)
    Kind = ctx.cell.kind().Kind
    ctx.cell.kind = lambda: type("m", (), {
        "Kind": program_built_with(**changed)(Kind)})
    r = harness.drive(ctx)
    assert not r["correct"]
    assert "served_logit_gap" in failing(r), r["checks"]


def test_faults_in_the_mathematics_read_over_the_limit():
    """Each of FAULTS planted in the reference's own mathematics, and
    the reference in the precision below: the tokens each puts first lie
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


def test_openpangu_counts_match_the_hand_figure():
    """openpangu_ultra_moe_718b_serve, one token through the 5 layers
    held, without routed experts, head and attention over the cache: a
    latent layer's products 2 * (7680*1536 + 1536*128*192 + 7680*576 +
    128*128*512 + 128*512*128 + 128*128*7680) = 393,150,464, the dense
    feed-forward 6*7680*18432 = 849,346,560, an expert layer's router
    2*7680*256 = 3,932,160 and shared expert 6*7680*2048 = 94,371,840:
    5 * 393,150,464 + 849,346,560 + 4 * 98,304,000 = 3,208,314,880 (a
    count that takes the norm scales and the selection bias, 164,864
    values, as products too reads 3,208,644,608). A pair 94,371,840; a
    token through the head 2*7680*19200 = 294,912,000; a cached key of a
    layer, absorbed, 2*128*(512 + 64 + 512) = 278,528."""
    cfg = harness.load_json(os.path.join(
        harness.HERE, "configs", "openpangu_ultra_moe_718b_serve.json"))
    assert flops_openpangu.latent_token_flops(cfg) == 393_150_464
    assert flops_openpangu.token_flops(cfg) == 3_208_314_880
    assert flops_openpangu.assignment_flops(cfg) == 94_371_840
    assert flops_openpangu.head_flops(cfg) == 294_912_000
    assert flops_openpangu.key_flops(cfg) == 278_528
    c = {"attn_latent_key_rows_total": 10 ** 6,
         "attn_latent_pages_total": 400}
    assert flops_openpangu.forward_flops(cfg, 10, 2, 50, c) == (
        10 * 3_208_314_880 + 50 * 94_371_840 + 2 * 294_912_000
        + 10 ** 6 * 278_528)
    assert flops_openpangu.page_bytes(cfg) == 512 * 576 * 2 == 589_824
    assert flops_openpangu.walked_bytes(cfg, c) == 400 * 589_824
    reference = harness.load_module(os.path.join(
        harness.HERE, "models", "openpangu_reference.py"))
    spec = reference.spec(cfg)
    n = sum(int(np.prod(s)) for _n, s, _i in spec)
    assert n == 4_919_140_864
    assert reference.pool_bytes(cfg) == 5 * 901 * 589_824
    assert reference.stated_storage_bytes(cfg) == (
        2 * n + 5 * 901 * 589_824 + 4 * 4 * 16)
    assert flops_openpangu.weight_bytes(cfg, spec, 200) == 2 * (
        n - 19200 * 7680 + 200 * 7680)


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
