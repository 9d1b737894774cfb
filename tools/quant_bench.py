#!/usr/bin/env python
"""Quantized-inference bench: the three gates that make the weight
quantization claim real (ISSUE 15 acceptance criteria).

  1. WEIGHT BYTES — the rewrite must shrink the executable, not shadow
     it: the quantized GPT predict executable's XLA memory_analysis
     argument bytes must be <= --max-bytes-ratio (0.55) of the fp32
     executable's, AND the rewrite report's own accounting (the bytes
     the rewrite owns) must show the int8 cut. A rewrite that kept the
     fp32 originals anywhere in the Scope would fail the first number.
  2. TOKEN AGREEMENT — greedy decode through the RAGGED engine with
     int8 weights + int8 KV pages (the fully-quantized config) must
     agree with the fp32 engine on >= --min-agreement (0.8, the PR-12
     int8-KV gate) of emitted tokens.
  3. RESIDENT-SEQUENCE HEADROOM — at one fixed HBM budget (fp32
     weights + fp32 page pool), the fully-quantized config must hold
     STRICTLY more resident sequences: smaller weights free bytes that
     become extra int8 pages. Checked arithmetically from the measured
     byte numbers, then PROVEN by serving that many concurrent
     sequences through a real engine sized to the computed pool.

Run:  JAX_PLATFORMS=cpu python tools/quant_bench.py --smoke --out quant_bench.json
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np  # noqa: E402


def _gpt_cfg():
    from paddle_tpu.generation.model import GPTConfig

    # big enough that matmul weights dominate the embeddings, small
    # enough for CPU CI
    return GPTConfig(vocab_size=211, hidden_size=64, num_layers=2,
                     num_heads=4, ffn_size=256, max_position=64,
                     hidden_dropout=0.0, attention_dropout=0.0)


def _export_lm(fluid, cfg, seq, dirname):
    from paddle_tpu.generation.model import build_lm_program

    main, startup, _feeds, fetches = build_lm_program(cfg, seq)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.TPUPlace())
        exe.run(startup)
        fluid.io.save_inference_model(dirname, ["tokens"],
                                      [fetches["logits"]], exe, main)


def _predict_arg_bytes(fluid, lm_dir, seq, quantized: bool):
    """One predictor, one run, the executable's XLA argument bytes
    (weights + feeds as compiled) + the quantize report."""
    from paddle_tpu.inference import Config, create_predictor

    cfg = Config(lm_dir)
    if quantized:
        cfg.enable_weight_quantization("int8")
    pred = create_predictor(cfg)
    toks = np.zeros((1, seq), np.int64)
    pred.run([toks])
    bound = next(iter(pred._bindings.values()))
    analysis = dict(getattr(bound.compiled, "analysis", None) or {})
    return {
        "argument_bytes": analysis.get("paddle_xla_argument_bytes"),
        "output_bytes": analysis.get("paddle_xla_output_bytes"),
        "report": (pred.quantize_report.to_dict()
                   if pred.quantize_report else None),
    }, pred


def run_smoke(args):
    import paddle_tpu as fluid
    from paddle_tpu import generation
    from paddle_tpu.generation.kvcache import PagedKVCache

    fluid.set_flags({"observability_xla_analysis": True})
    cfg = _gpt_cfg()
    seq = 48
    report = {"scenario": "quantized_inference", "config": {
        "hidden": cfg.hidden_size, "layers": cfg.num_layers,
        "vocab": cfg.vocab_size, "seq": seq}}
    tmp = tempfile.mkdtemp(prefix="pt_quant_bench_")
    _export_lm(fluid, cfg, seq, tmp)

    # -- gate 1: weight bytes (XLA memory_analysis) --------------------
    f32_info, _f32_pred = _predict_arg_bytes(fluid, tmp, seq, False)
    q_info, q_pred = _predict_arg_bytes(fluid, tmp, seq, True)
    fb, qb = f32_info["argument_bytes"], q_info["argument_bytes"]
    bytes_ratio = (qb / fb) if (fb and qb) else None
    rewrite_summary = q_info["report"]["summary"]
    report["weight_bytes"] = {
        "fp32_argument_bytes": fb, "quantized_argument_bytes": qb,
        "xla_ratio": round(bytes_ratio, 4) if bytes_ratio else None,
        "rewrite": rewrite_summary,
        "skip_reasons": {
            r["name"]: r["reason"] for r in q_info["report"]["vars"]
            if r["action"] == "skipped"},
    }
    ok_bytes = bool(bytes_ratio is not None
                    and bytes_ratio <= args.max_bytes_ratio)

    # -- gate 2: greedy token agreement through the ragged engine ------
    rng = np.random.RandomState(0)
    prompts = [rng.randint(1, cfg.vocab_size, int(n)).astype(np.int64)
               for n in rng.randint(4, 12, args.requests)]

    def decode_all(pred, kv_dtype, quantize, num_pages=96, lanes=4):
        eng = generation.GenerationEngine(
            pred, cfg, page_size=8, num_pages=num_pages,
            max_decode_batch=lanes, kv_dtype=kv_dtype,
            quantize_weights=quantize)
        try:
            streams = [eng.submit(p, max_new_tokens=args.new_tokens)
                       for p in prompts]
            return [s.result(timeout=600) for s in streams]
        finally:
            eng.close(drain=True)

    f32_out = decode_all(_f32_pred, "float32", "off")
    q_out = decode_all(q_pred, "int8", "int8")
    agree = total = 0
    for a, b in zip(f32_out, q_out):
        total += len(a)
        agree += sum(1 for x, y in zip(a, b) if x == y)
    agreement = agree / max(total, 1)
    report["token_agreement"] = {
        "agreement": round(agreement, 4), "tokens": total,
        "gate": args.min_agreement}
    ok_agree = agreement >= args.min_agreement

    # -- gate 3: resident sequences at a fixed HBM budget --------------
    head_dim = cfg.hidden_size // cfg.num_heads
    page_size = 8
    f32_pages = 16  # small enough that the serving proof below engages
    pb_f32 = PagedKVCache.page_bytes(cfg.num_heads, head_dim, page_size,
                                     "float32")
    pb_int8 = PagedKVCache.page_bytes(cfg.num_heads, head_dim, page_size,
                                      "int8")
    w_before = rewrite_summary["weight_bytes_before"]
    w_after = rewrite_summary["weight_bytes_after"]
    budget = w_before + cfg.num_layers * f32_pages * pb_f32
    pool_q = budget - w_after
    q_pages = int(pool_q // (cfg.num_layers * pb_int8))
    need = 16 + args.new_tokens  # a short prompt + its decode budget
    pages_per_seq = -(-need // page_size)
    f32_resident = (f32_pages - 1) // pages_per_seq
    q_resident = (q_pages - 1) // pages_per_seq
    report["resident_sequences"] = {
        "hbm_budget_bytes": int(budget),
        "fp32": {"pages": f32_pages, "resident_seqs": int(f32_resident)},
        "quantized": {"pages": q_pages, "resident_seqs": int(q_resident)},
        "bytes_per_page": {"float32": pb_f32, "int8": pb_int8},
        "weight_bytes": {"before": int(w_before), "after": int(w_after)},
    }
    ok_resident = q_resident > f32_resident
    # prove the computed capacity serves: more concurrent sequences
    # than the fp32 pool could hold, through a REAL fully-quantized
    # engine sized to the computed page count
    n_serve = min(int(q_resident), 8)
    if ok_resident and n_serve > f32_resident:
        lanes = n_serve
        prompts2 = [rng.randint(1, cfg.vocab_size, 16).astype(np.int64)
                    for _ in range(n_serve)]
        eng = generation.GenerationEngine(
            q_pred, cfg, page_size=page_size, num_pages=q_pages,
            max_decode_batch=lanes, kv_dtype="int8",
            quantize_weights="int8")
        try:
            streams = [eng.submit(p, max_new_tokens=args.new_tokens)
                       for p in prompts2]
            outs = [s.result(timeout=600) for s in streams]
            served = sum(1 for o in outs if len(o) == args.new_tokens)
            evicted = eng.stats()["evicted_total"]
        finally:
            eng.close(drain=True)
        report["resident_sequences"]["served_concurrent"] = served
        report["resident_sequences"]["evictions"] = int(evicted)
        ok_resident = bool(served == n_serve)

    report["gates"] = {
        "weight_bytes_ratio_le": args.max_bytes_ratio,
        "weight_bytes_ok": ok_bytes,
        "token_agreement_ok": bool(ok_agree),
        "resident_headroom_ok": bool(ok_resident),
    }
    report["ok"] = bool(ok_bytes and ok_agree and ok_resident)
    if not ok_bytes:
        report["fail"] = (f"quantized argument bytes ratio {bytes_ratio} "
                          f"> {args.max_bytes_ratio}")
    elif not ok_agree:
        report["fail"] = (f"token agreement {agreement:.3f} < "
                          f"{args.min_agreement}")
    elif not ok_resident:
        report["fail"] = "quantized config did not serve more sequences"
    return report


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="CI mode: tiny GPT, all three gates")
    ap.add_argument("--out", default=None, help="artifact JSON path")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--new-tokens", type=int, default=10)
    ap.add_argument("--max-bytes-ratio", type=float, default=0.55)
    ap.add_argument("--min-agreement", type=float, default=0.8)
    args = ap.parse_args()

    t0 = time.time()
    report = run_smoke(args)
    report["wall_s"] = round(time.time() - t0, 1)
    out = json.dumps(report, indent=1, sort_keys=True)
    print(out)
    if args.out:
        with open(args.out, "w") as f:
            f.write(out + "\n")
    if not report["ok"]:
        print(f"[quant_bench] GATE FAILED: {report.get('fail')}",
              file=sys.stderr)
        return 1
    print("[quant_bench] OK: "
          f"bytes ratio {report['weight_bytes']['xla_ratio']}, "
          f"agreement {report['token_agreement']['agreement']}, "
          f"resident {report['resident_sequences']['fp32']['resident_seqs']}"
          f" -> "
          f"{report['resident_sequences']['quantized']['resident_seqs']}",
          file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
