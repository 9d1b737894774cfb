"""Local AOT validation against the real TPU (v5e) compiler. The full
run recompiles every Pallas kernel plus the train cell's BERT step with
libtpu's Mosaic/XLA pipeline (~10 min), so it only runs with
PT_AOT_CHECK=1; AOT_TPU_CHECK.json archives the committed result (this
is how the flash mask and layer_norm backward block-spec rejections
were found and fixed without a chip). What tier-1 holds without a
compile: the tool's headline row is the benchmark's own train step, and
the archive names only rows the tool still makes."""

import ast
import json
import os
import re
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOOL = os.path.join(HERE, "tools", "aot_check.py")


def _row_name_patterns():
    """Every name the tool can give a row: the first argument of each
    ``aot`` / ``record`` / ``mc`` call in its source, a formatted or
    computed piece standing for any text."""
    def pattern(node):
        if isinstance(node, ast.Constant) and isinstance(node.value, str):
            return re.escape(node.value)
        if isinstance(node, ast.JoinedStr):
            return "".join(pattern(v) for v in node.values)
        if isinstance(node, ast.BinOp) and isinstance(node.op, ast.Add):
            return pattern(node.left) + pattern(node.right)
        return ".+"

    with open(TOOL) as f:
        tree = ast.parse(f.read())
    return [re.compile(pattern(call.args[0]))
            for call in ast.walk(tree)
            if isinstance(call, ast.Call) and call.args
            and isinstance(call.func, ast.Name)
            and call.func.id in ("aot", "record", "mc")]


def test_headline_is_the_train_cell_and_the_archive_is_current():
    with open(TOOL) as f:
        assert not re.search(r"^\s*(import|from) +bench\b", f.read(), re.M)
    sys.path.insert(0, os.path.dirname(TOOL))
    import aot_check as tool
    with open(os.path.join(HERE, "benchmark", "traffic",
                           "pretrain_s512.json")) as f:
        traffic = json.load(f)
    with open(os.path.join(HERE, "benchmark", "configs",
                           "bert_base_pretrain.json")) as f:
        cfg = json.load(f)
    main, _startup, loss, feed = tool.headline_step()
    assert loss.name in main.global_block().vars
    assert {a.shape for a in feed.values()} == {
        (traffic["batch_per_chip"], traffic["seq_len"])}
    emb = [p for p in main.global_block().all_parameters()
           if tuple(p.shape) == (cfg["vocab_size"], cfg["hidden_size"])]
    assert emb, "the program is not the configuration's width"

    patterns = _row_name_patterns()
    with open(os.path.join(HERE, "AOT_TPU_CHECK.json")) as f:
        rows = json.load(f)["rows"]
    stale = [r["name"] for r in rows
             if not any(p.fullmatch(r["name"]) for p in patterns)]
    assert not stale, f"rows no call of tools/aot_check.py makes: {stale}"
    assert "stage_headline_bert_base_s512_flash" in {r["name"] for r in rows}


def test_archive_counts_the_expert_kernel_in_both_moe_steps():
    """``mosaic_kernels`` names a row's custom calls by ``pallas_call``
    name: the two MoE cells' step programs, compiled for a v5e at the
    cells' shapes, run every expert layer on ``moe_grouped_ffn`` (10 and
    6) and none on XLA's ``ragged-dot`` kernels."""
    sys.path.insert(0, os.path.dirname(TOOL))
    import aot_check as tool
    for line, name in (
            ("  %moe_grouped_ffn.7 = f32[512,4096]{1,0} custom-call(",
             "moe_grouped_ffn"),
            ("  ROOT %ragged-dot-none = f32[128,4096]{1,0} custom-call(",
             "ragged-dot-none"),
            ("  layer_norm_fwd = (f32[8,128]) custom-call(", "layer_norm_fwd")):
        assert tool._KERNEL_NAME.match(line).group(1) == name
    with open(os.path.join(HERE, "AOT_TPU_CHECK.json")) as f:
        rows = {r["name"]: r for r in json.load(f)["rows"]}
    for name, layers in (
            ("hybrid_step_program_granite4_h_small_10layer", 10),
            ("mimo_step_program_mimo_v2_5_7layer", 6)):
        kernels = rows[name]["mosaic_kernels"]
        assert kernels["moe_grouped_ffn"] == layers, kernels
        assert not [k for k in kernels if k.startswith("ragged-dot")], kernels
    for name in ("moe_ffn_hybrid_36x4096x768_top10",
                 "moe_ffn_mimo_16x4096x2048_top8"):
        assert rows[name]["ok"], rows[name]
        assert rows[name]["mosaic_kernels"] == {"moe_grouped_ffn": 1}
        assert 0 <= (rows[name]["arg_bytes"]
                     - rows[name]["stated_arg_bytes"]) < 4096


@pytest.mark.skipif(
    os.environ.get("PT_AOT_CHECK") != "1",
    reason="multi-minute real-TPU-target AOT compile; set PT_AOT_CHECK=1",
)
def test_all_kernels_and_headline_compile_for_v5e():
    proc = subprocess.run(
        [sys.executable, TOOL],
        capture_output=True, text=True, timeout=5400,
    )
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-1000:]
    with open(os.path.join(HERE, "AOT_TPU_CHECK.json")) as f:
        results = json.load(f)
    assert "v5" in results["target"].lower()
    bad = [r for r in results["rows"] if not r.get("ok")]
    assert not bad, bad
    names = {r["name"] for r in results["rows"]}
    assert "stage_headline_bert_base_s512_flash" in names
    # the quantized-inference kernel rows (PT_AOT_ONLY=quant group)
    for mode in ("int8", "int8_block", "fp8"):
        assert f"quant_matmul_{mode}" in names
