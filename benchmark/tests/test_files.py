"""BENCHMARK.json against the files it names, and the yardstick's
tables: every cell finds its configuration, traffic, kind and readers;
the FLOP count matches the hand figure in PERF.md."""

import os

import pytest

import flops
import harness

BENCH = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))


@pytest.mark.parametrize("name", [w["name"] for w in BENCH["workloads"]])
def test_cell_finds_its_files(name):
    cell = harness.Cell(BENCH, name)
    assert cell.config["name"] == cell.spec["config"]
    assert hasattr(cell.kind(), "Kind")
    for m in cell.per_layer:
        reader = harness.load_module(
            os.path.join(harness.HERE, "metrics", m["name"] + ".py"))
        assert callable(reader.read)
        assert name in {w for e in BENCH["end_to_end"]
                        if e["name"] == m["moves"]
                        for w in e.get("workloads", [name])}
    for key in ("reduced", "assumed", "source"):
        assert key in cell.config
    models = os.path.join(harness.HERE, "models")
    for part in ("_program.py", "_reference.py"):
        assert os.path.exists(os.path.join(models, cell.config["model"] + part))


def test_reference_imports_nothing_of_the_program():
    models = os.path.join(harness.HERE, "models")
    for f in os.listdir(models):
        if f.endswith("_reference.py") or f == "weights.py":
            assert "paddle_tpu" not in open(os.path.join(models, f)).read().replace(
                "paddle_tpu/", "").split('"""', 2)[2], f


def test_bert_base_flops_per_token_matches_the_hand_figure():
    """PERF.md, layer `train step`: 6 * (12 * (4*768^2 + 2*768*3072) +
    768*30522) + 12*12*768*512 = 6 * 108,375,552 + 56,623,104
    = 706,876,416 FLOPs a token."""
    cfg = harness.load_json(os.path.join(harness.HERE, "configs",
                                         "bert_base_pretrain.json"))
    assert flops.bert_train_flops_per_token(cfg, 512) == 706_876_416


def test_gpt3_xl_flops():
    cfg = harness.load_json(os.path.join(harness.HERE, "configs",
                                         "gpt3_xl_serve.json"))
    body = 24 * (4 * 2048 ** 2 + 2 * 2048 * 8192)
    assert body == 1_207_959_552
    assert flops.gpt_forward_flops(cfg, 10, 2, 1000) == (
        2 * body * 10 + 2 * 2048 * 50257 * 2 + 4 * 24 * 2048 * 1000)


def test_unknown_device_kind_is_an_error():
    assert harness.load_peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        harness.load_peaks("TPU v9")
