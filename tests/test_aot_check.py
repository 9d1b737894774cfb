"""Local AOT validation against the real TPU (v5e) compiler — gated
like the scale proofs: a full run recompiles every Pallas kernel plus
the headline BERT step with libtpu's Mosaic/XLA pipeline (~10 min), so
it only runs with PT_AOT_CHECK=1; AOT_TPU_CHECK.json archives the
committed result (this is how the flash mask and layer_norm backward
block-spec rejections were found and fixed without a chip)."""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

pytestmark = pytest.mark.skipif(
    os.environ.get("PT_AOT_CHECK") != "1",
    reason="multi-minute real-TPU-target AOT compile; set PT_AOT_CHECK=1",
)


def test_all_kernels_and_headline_compile_for_v5e():
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "tools", "aot_check.py")],
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
