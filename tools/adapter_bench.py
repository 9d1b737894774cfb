#!/usr/bin/env python
"""Multi-adapter serving bench: the gates that make the batched-LoRA
multiplexing + hot-swap claim real (ISSUE 19 acceptance criteria).

  1. MULTIPLEX THROUGHPUT — serving 8 DISTINCT adapters in one ragged
     micro-batch must keep >= --min-throughput-ratio (0.7) of the SAME
     engine's base-only tokens/s. The whole point of the slot-indexed
     factor pools is that adapter DIVERSITY costs a bounded delta —
     one executable for any mix, vs the naive per-adapter grouping
     that runs 8 fragments of the batch. (The cost of having the LoRA
     epilogue in the graph at all is reported as the ungated
     ``subsystem_overhead_ratio``: it is rank/width-dependent — r*(K+N)
     vs K*N MACs per target — so at bench widths it reads far larger
     than production widths and would gate on model size, not on the
     multiplexing design.)
  2. TOKEN IDENTITY — every adapter's greedy output in the mixed batch
     must be token-identical to a dedicated single-adapter engine, and
     base-only rows served alongside must match a no-adapter engine
     exactly (slot 0 is a true zero adapter, not an approximate one).
  3. HOT SWAP WINDOW — a signature-identical base-weight swap flipped
     under live submissions must finish with ZERO failed in-flight
     requests, ZERO new persistent-compile-cache entries and the SAME
     bound executable (the swap is scope state, never a recompile).

Run:  JAX_PLATFORMS=cpu python tools/adapter_bench.py --smoke \
          --out adapter_bench.json
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np  # noqa: E402


def _gpt_cfg():
    from paddle_tpu.generation.model import GPTConfig

    # hidden 256 on purpose: the rank-r delta costs r*(K+N) MACs per
    # target against the base matmul's K*N, so at toy widths the ratio
    # gate would measure the model size (hidden 64 puts the rank-8+16
    # buckets at ~40% of base FLOPs — unpassable by construction), not
    # the multiplexing overhead. At 256 the delta is ~14% of base.
    return GPTConfig(vocab_size=211, hidden_size=256, num_layers=2,
                     num_heads=4, ffn_size=1024, max_position=96,
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


def _engine(fluid, lm_dir, cfg, lanes, adapters: bool, slots=12):
    from paddle_tpu.generation import GenerationEngine
    from paddle_tpu.inference import Config, create_predictor

    if adapters:
        fluid.set_flags({"adapter_pool_max_bytes": 1,
                         "adapter_slots_per_bucket": int(slots)})
    try:
        pred = create_predictor(Config(lm_dir))
        return GenerationEngine(pred, cfg, page_size=4, num_pages=96,
                                max_decode_batch=lanes, chunk_tokens=8)
    finally:
        if adapters:
            fluid.set_flags({"adapter_pool_max_bytes": 0,
                             "adapter_slots_per_bucket": 0})


def _random_factors(rng, store, targets, rank):
    fac = {}
    for t in targets:
        K, N = store.targets[t]
        fac[t] = (rng.randn(K, rank).astype(np.float32) * 0.05,
                  rng.randn(rank, N).astype(np.float32) * 0.05)
    return fac


def _tokens_per_s(eng, prompts, new_tokens, adapters=None):
    t0 = time.monotonic()
    streams = [eng.submit(p, max_new_tokens=new_tokens,
                          **({"adapter": adapters[i % len(adapters)]}
                             if adapters else {}))
               for i, p in enumerate(prompts)]
    outs = [s.result(timeout=600) for s in streams]
    dt = time.monotonic() - t0
    return sum(len(o) for o in outs) / dt, outs


def run_smoke(args):
    import paddle_tpu as fluid
    from paddle_tpu.runtime.dispatch import persistent_cache_dir

    cfg = _gpt_cfg()
    n_adapters = int(args.adapters)
    lanes = n_adapters + 1
    report = {"scenario": "multi_adapter_serving",
              "adapters": n_adapters, "lanes": lanes}
    tmp = tempfile.mkdtemp(prefix="pt_adapter_bench_")
    _export_lm(fluid, cfg, 40, tmp)
    rng = np.random.RandomState(0)
    prompts = [rng.randint(1, cfg.vocab_size, int(n)).astype(np.int64)
               for n in rng.randint(6, 12, lanes * 2)]

    # -- gate 1+2: base-only vs 8-adapter multiplex --------------------
    base_eng = _engine(fluid, tmp, cfg, lanes, adapters=False)
    try:
        # the no-LoRA engine: the ungated subsystem-overhead reference
        # and the token-identity oracle for base rows
        base_eng.generate(prompts[0], max_new_tokens=4, timeout=300)
        nolora_tps, _ = _tokens_per_s(base_eng, prompts, args.new_tokens)
    finally:
        base_eng.close(drain=True)

    eng = _engine(fluid, tmp, cfg, lanes, adapters=True)
    try:
        store = eng.adapter_store
        targets = sorted(store.targets)
        factors = {}
        for i in range(n_adapters):
            rank = 8 if i % 2 == 0 else 16
            fac = _random_factors(rng, store, targets[: 1 + (i % 3)], rank)
            factors[f"ad{i}"] = (fac, 2.0 * rank)
            store.upload(f"ad{i}", fac, alpha=2.0 * rank)
        ids = [f"ad{i}" for i in range(n_adapters)]
        # warm the executable + adapter path off the clock (same
        # compiled fn either way — the slots feed is data — but
        # first-touch pool reads and page allocation shouldn't bill
        # the measured waves)
        eng.generate(prompts[0], max_new_tokens=4, adapter=ids[0],
                     timeout=300)
        base_tps, _ = _tokens_per_s(eng, prompts, args.new_tokens)
        mixed_tps, _ = _tokens_per_s(eng, prompts, args.new_tokens,
                                     adapters=ids)
        ratio = mixed_tps / max(base_tps, 1e-9)
        report["throughput"] = {
            "base_tokens_per_s": round(base_tps, 1),
            "mixed_tokens_per_s": round(mixed_tps, 1),
            "ratio": round(ratio, 3),
            "gate": args.min_throughput_ratio,
            "no_lora_engine_tokens_per_s": round(nolora_tps, 1),
            "subsystem_overhead_ratio": round(
                base_tps / max(nolora_tps, 1e-9), 3),
        }
        ok_tps = ratio >= args.min_throughput_ratio

        # token identity: the mixed batch vs dedicated oracles + a
        # base row alongside
        probe = prompts[0]
        streams = [eng.submit(probe, max_new_tokens=args.new_tokens,
                              adapter=a) for a in ids]
        streams.append(eng.submit(probe, max_new_tokens=args.new_tokens))
        mixed = [s.result(timeout=600) for s in streams]
        base_probe = None
        b_eng = _engine(fluid, tmp, cfg, 2, adapters=False)
        try:
            base_probe = b_eng.generate(probe,
                                        max_new_tokens=args.new_tokens,
                                        timeout=300)
        finally:
            b_eng.close(drain=True)
        identical = mixed[-1] == base_probe
        for i in (0, n_adapters // 2, n_adapters - 1):
            solo_eng = _engine(fluid, tmp, cfg, 2, adapters=True, slots=3)
            try:
                fac, alpha = factors[f"ad{i}"]
                solo_eng.adapter_store.upload(f"ad{i}", fac, alpha=alpha)
                solo = solo_eng.generate(probe,
                                         max_new_tokens=args.new_tokens,
                                         adapter=f"ad{i}", timeout=300)
            finally:
                solo_eng.close(drain=True)
            identical = identical and solo == mixed[i]
        report["token_identity"] = {"ok": bool(identical)}

        # -- gate 3: hot swap under live traffic -----------------------
        cache_dir = persistent_cache_dir()
        entries_before = (len(os.listdir(cache_dir))
                          if cache_dir and os.path.isdir(cache_dir) else 0)
        bound_before = eng._ragged_bound
        new_weights = {
            t: np.asarray(eng._scope.find_var(t))
            + rng.randn(*store.targets[t]).astype(np.float32) * 0.01
            for t in targets}
        failures = []
        done = []
        stop = threading.Event()

        def pump():
            i = 0
            while not stop.is_set():
                try:
                    s = eng.submit(prompts[i % len(prompts)],
                                   max_new_tokens=4,
                                   adapter=ids[i % len(ids)])
                    s.result(timeout=300)
                    done.append(1)
                except Exception as e:  # noqa: BLE001 — any drop fails the gate
                    failures.append(repr(e))
                i += 1

        threads = [threading.Thread(target=pump, daemon=True)
                   for _ in range(3)]
        for t in threads:
            t.start()
        time.sleep(0.3)
        label = eng.swap_base(new_weights, version="bench-v2")
        time.sleep(0.3)
        stop.set()
        for t in threads:
            t.join(60)
        entries_after = (len(os.listdir(cache_dir))
                         if cache_dir and os.path.isdir(cache_dir) else 0)
        swapped = eng.generate(probe, max_new_tokens=args.new_tokens,
                               timeout=300)
        report["hot_swap"] = {
            "label": label,
            "requests_through_window": len(done),
            "failed_in_flight": len(failures),
            "failures": failures[:3],
            "bound_identity_unchanged": eng._ragged_bound is bound_before,
            "cache_entries_before": entries_before,
            "cache_entries_after": entries_after,
            "tokens_changed_after_swap": swapped != base_probe,
        }
        ok_swap = (not failures and len(done) > 0
                   and eng._ragged_bound is bound_before
                   and entries_after == entries_before)
    finally:
        eng.close(drain=True)

    report["gates"] = {
        "throughput_ratio_ok": bool(ok_tps),
        "token_identity_ok": bool(identical),
        "hot_swap_zero_drop_zero_compile": bool(ok_swap),
    }
    report["ok"] = bool(ok_tps and identical and ok_swap)
    if not ok_tps:
        report["fail"] = (f"mixed/base throughput {ratio:.3f} < "
                          f"{args.min_throughput_ratio}")
    elif not identical:
        report["fail"] = "mixed-batch tokens != dedicated-engine tokens"
    elif not ok_swap:
        report["fail"] = "hot swap dropped requests or recompiled"
    return report


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="CI mode: tiny GPT, all three gates")
    ap.add_argument("--out", default=None, help="artifact JSON path")
    ap.add_argument("--adapters", type=int, default=8)
    ap.add_argument("--new-tokens", type=int, default=8)
    ap.add_argument("--min-throughput-ratio", type=float, default=0.7)
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
        print(f"[adapter_bench] GATE FAILED: {report.get('fail')}",
              file=sys.stderr)
        return 1
    print("[adapter_bench] OK: "
          f"throughput ratio {report['throughput']['ratio']}, "
          f"swap window {report['hot_swap']['requests_through_window']} "
          "requests, 0 dropped", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
