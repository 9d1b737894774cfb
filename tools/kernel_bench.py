"""Pallas kernel validation + timing on the TPU, one process.

    python tools/kernel_bench.py      # through the chip tool

Measures, compiled (interpret=False), bf16:
  - flash attention forward, blk_q in {128, 256, 512}, S in {512, 2048}
  - flash attention fwd+bwd (train step shape) vs XLA-native attention
  - fused layer_norm and softmax_xent vs their XLA-native forms
  - fused Adam vs the unfused chain, quantized matmuls, raw MXU/conv
Writes every measurement incrementally to chiprun_out/kernel_bench.json
(the directory the chip tool brings back); a row that errors is
recorded with its error and makes the exit code non-zero. Every timing
ends in block_until_ready.

PT_KERNEL_BENCH_SMOKE=1 runs every row-builder on the CPU with
interpret-mode kernels at tiny shapes (tests/test_bench_smoke.py), so a
harness bug — a wrong import binding, call signature or label rank — is
caught without a chip.
"""

import functools
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)
T0 = time.time()
SMOKE = os.environ.get("PT_KERNEL_BENCH_SMOKE") == "1"
OUT = os.environ.get("PT_KERNEL_BENCH_OUT") or os.path.join(
    HERE, "kernel_bench_smoke.json" if SMOKE
    else os.path.join("chiprun_out", "kernel_bench.json"))

RESULTS = {"device": None, "backend": None, "rows": [], "started_at": None}


def _save():
    os.makedirs(os.path.dirname(OUT) or ".", exist_ok=True)
    with open(OUT, "w") as f:
        json.dump({"runs": [RESULTS]}, f, indent=1)


def main():
    import datetime

    import numpy as np
    import jax
    import jax.numpy as jnp

    RESULTS["started_at"] = datetime.datetime.now(
        datetime.timezone.utc).strftime("%Y-%m-%dT%H:%M:%SZ")
    backend = jax.default_backend()
    RESULTS["backend"] = backend
    if backend != "tpu" and not SMOKE:
        sys.stderr.write(f"kernel_bench.py needs a TPU; found {backend!r}\n")
        return 2
    if SMOKE:
        # interpreter-mode Pallas everywhere so every kernel call
        # actually executes on CPU
        os.environ["PADDLE_TPU_KERNEL_INTERPRET"] = "1"
    RESULTS["device"] = str(jax.devices()[0].device_kind)
    RESULTS["smoke"] = SMOKE
    _save()

    # NOTE: `from paddle_tpu.kernels import flash_attention` binds the
    # FUNCTION re-exported by kernels/__init__.py, not the module —
    # import the module explicitly
    import importlib

    fa = importlib.import_module("paddle_tpu.kernels.flash_attention")
    from paddle_tpu.kernels.layer_norm import fused_layer_norm
    from paddle_tpu.kernels.softmax_xent import fused_softmax_xent

    rng = np.random.RandomState(0)

    def bench_chain(fn, args, iters=20, chain=None):
        """Device-loop timing: ONE dispatch running `iters` chained
        applications inside lax.fori_loop, so per-call dispatch
        overhead cannot pollute the per-iter number — this variant
        tells measurement pollution apart from a broken lowering.
        `chain(out, *args) -> args` threads a data dependency so XLA
        cannot collapse the loop."""
        from jax import lax

        if SMOKE:
            iters = 2
        chain = chain or (lambda out, *a: (out,) + a[1:])

        def body(_, a):
            return tuple(chain(fn(*a), *a))

        looped = jax.jit(lambda *a: lax.fori_loop(0, iters, body, a))
        jax.block_until_ready(looped(*args))  # compile + warm run
        t0 = time.time()
        jax.block_until_ready(looped(*args))
        return (time.time() - t0) / iters * 1e3

    def bench(fn, args, iters=20, warmup=2):
        """Compile + time; returns (ms_per_iter, compile_s)."""
        if SMOKE:
            iters, warmup = 1, 1
        c0 = time.time()
        out = jax.block_until_ready(fn(*args))
        compile_s = time.time() - c0
        for _ in range(warmup - 1):
            out = fn(*args)
        jax.block_until_ready(out)
        t0 = time.time()
        for _ in range(iters):
            out = fn(*args)
        jax.block_until_ready(out)
        return (time.time() - t0) / iters * 1e3, compile_s

    def row(name, **kw):
        kw["name"] = name
        RESULTS["rows"].append(kw)
        _save()
        print(json.dumps(kw))

    def mk_qkv(B, H, S, D):
        shape = (B, H, S, D)
        mk = lambda: jnp.asarray(rng.randn(*shape), jnp.bfloat16) * 0.1
        return mk(), mk(), mk()

    # -- flash attention: blk_q sweep, forward, causal -----------------
    # smoke: one tiny (S, B) and one block size; the 256-block kernel
    # internally pads S=128 -> the full pad/unpad path still runs
    H, D = (2, 64) if SMOKE else (12, 64)
    fa_sweep = ((128, 1),) if SMOKE else ((512, 8), (2048, 2))
    blk_list = (128,) if SMOKE else (128, 256, 512)
    interp = SMOKE  # compiled on TPU; interpreter in CI smoke
    for S, B in fa_sweep:
        q, k, v = mk_qkv(B, H, S, D)
        sm = 1.0 / (D ** 0.5)

        # XLA-native reference first: the number to beat.
        ref = jax.jit(lambda q, k, v: fa._reference_attention(
            q, k, v, sm, True))
        try:
            ms, cs = bench(ref, (q, k, v))
            row("xla_attention_fwd", S=S, B=B, ms=ms, compile_s=cs)
        except Exception as e:  # noqa: BLE001
            row("xla_attention_fwd", S=S, B=B, error=repr(e)[:300])

        for blk in blk_list:
            if blk > S:
                continue
            f = jax.jit(lambda q, k, v, blk=blk: fa._flash_fwd_pallas(
                q, k, v, None, None, sm, True, interpret=interp,
                blk_q=blk, with_lse=False)[0])
            try:
                ms, cs = bench(f, (q, k, v))
                row("flash_fwd", S=S, B=B, blk_q=blk, ms=ms, compile_s=cs)
            except Exception as e:  # noqa: BLE001
                row("flash_fwd", S=S, B=B, blk_q=blk, error=repr(e)[:300])

        # numerics on-device: compiled kernel vs XLA reference
        try:
            got = np.asarray(jax.jit(
                lambda q, k, v: fa._flash_fwd_pallas(
                    q, k, v, None, None, sm, True, interpret=interp,
                    with_lse=False)[0])(q, k, v), np.float32)
            want = np.asarray(ref(q, k, v), np.float32)
            err = float(np.max(np.abs(got - want)))
            row("flash_fwd_numerics", S=S, max_abs_err=err,
                ok=bool(err < 5e-2))
        except Exception as e:  # noqa: BLE001
            row("flash_fwd_numerics", S=S, error=repr(e)[:300])

    # -- flash attention: fwd+bwd (training shape) ---------------------
    for S, B in fa_sweep:
        q, k, v = mk_qkv(B, H, S, D)

        def loss_flash(q, k, v):
            return fa.flash_attention(q, k, v, causal=True).astype(
                jnp.float32).sum()

        def loss_xla(q, k, v):
            sm = 1.0 / (D ** 0.5)
            return fa._reference_attention(q, k, v, sm, True).astype(
                jnp.float32).sum()

        for name, fn in (("flash_train", loss_flash),
                         ("xla_attention_train", loss_xla)):
            g = jax.jit(jax.grad(fn, argnums=(0, 1, 2)))
            try:
                ms, cs = bench(g, (q, k, v), iters=10)
                row(name, S=S, B=B, ms=ms, compile_s=cs)
            except Exception as e:  # noqa: BLE001
                row(name, S=S, B=B, error=repr(e)[:300])

    # -- fused layer_norm ----------------------------------------------
    R, C = (64, 256) if SMOKE else (8 * 512, 768)
    x = jnp.asarray(rng.randn(R, C), jnp.float32)
    gmm = jnp.ones((C,), jnp.float32)
    bta = jnp.zeros((C,), jnp.float32)

    def ln_xla(x, g, b):
        m = x.mean(-1, keepdims=True)
        v = ((x - m) ** 2).mean(-1, keepdims=True)
        return (x - m) * jax.lax.rsqrt(v + 1e-5) * g + b

    for name, fn in (
            # fused_layer_norm returns y only — no tuple to index
            ("layer_norm_pallas",
             jax.jit(lambda x, g, b: fused_layer_norm(x, g, b, 1e-5))),
            ("layer_norm_xla", jax.jit(ln_xla))):
        try:
            ms, cs = bench(fn, (x, gmm, bta))
            row(name, rows=R, cols=C, ms=ms, compile_s=cs)
        except Exception as e:  # noqa: BLE001
            row(name, rows=R, cols=C, error=repr(e)[:300])
        # single-dispatch chained loop: dispatch-overhead-free
        try:
            ms = bench_chain(fn, (x, gmm, bta))
            row(name + "_device_loop", rows=R, cols=C, ms=ms)
        except Exception as e:  # noqa: BLE001
            row(name + "_device_loop", rows=R, cols=C,
                error=repr(e)[:300])

    # -- fused softmax_xent --------------------------------------------
    R, V = (64, 1024) if SMOKE else (8 * 512, 30522)
    logits = jnp.asarray(rng.randn(R, V), jnp.float32)
    labels = jnp.asarray(rng.randint(0, V, (R, 1)), jnp.int32)

    def sx_xla(s, lbl):
        lse = jax.scipy.special.logsumexp(s, -1, keepdims=True)
        return jnp.take_along_axis(lse - s, lbl, 1)

    for name, fn in (
            # kernel takes labels [R] (not [R,1]) and returns the
            # per-row loss vector
            ("softmax_xent_pallas",
             jax.jit(lambda s, l: fused_softmax_xent(s, l[:, 0]))),
            ("softmax_xent_xla", jax.jit(sx_xla))):
        try:
            ms, cs = bench(fn, (logits, labels))
            row(name, rows=R, vocab=V, ms=ms, compile_s=cs)
        except Exception as e:  # noqa: BLE001
            row(name, rows=R, vocab=V, error=repr(e)[:300])
        try:
            ms = bench_chain(
                fn, (logits, labels),
                chain=lambda out, s, l: (s + 0 * out.reshape(R, 1), l))
            row(name + "_device_loop", rows=R, vocab=V, ms=ms)
        except Exception as e:  # noqa: BLE001
            row(name + "_device_loop", rows=R, vocab=V,
                error=repr(e)[:300])

    # -- fused optimizer: one-pass Adam vs the unfused XLA chain -------
    # Step wall-ms AND bytes-moved (XLA cost analysis) per variant.
    # The gate: the fused path must never move MORE bytes than the
    # unfused chain — the whole claim of the fusion is the bandwidth
    # floor (read p/g/m/v once, write p'/m'/v' once). SCOPE of the
    # smoke arm: CPU XLA cannot cost-analyze a Mosaic kernel, so smoke
    # gates the fused op's pure-JAX reference LOWERING (it catches a
    # wrapper that grows extra copies/outputs, not kernel-internal
    # traffic); the real Mosaic-kernel byte accounting is gated by the
    # non-smoke TPU run of this tool plus the AOT rows'
    # temp_bytes == 0 (tools/aot_check.py fused_adam_{f32,bf16}).
    # Rows carry mode= so the evidence file says which was measured.
    from paddle_tpu.kernels import fused_optim as fo

    N = (64, 256) if SMOKE else (4096, 2048)
    p0 = jnp.asarray(rng.randn(*N), jnp.float32)
    g0 = jnp.asarray(rng.randn(*N), jnp.float32)
    m0 = jnp.zeros_like(p0)
    v0 = jnp.zeros_like(p0)
    lr0 = jnp.float32(1e-3)
    b1p = jnp.full((1,), 0.9, jnp.float32)
    b2p = jnp.full((1,), 0.999, jnp.float32)

    def unfused_chain(p, g, m1, m2, lr, b1, b2):
        # ops/optim.py's exact adam math — the chain being replaced
        beta1, beta2, eps = 0.9, 0.999, 1e-8
        lr_t = lr * jnp.sqrt(1 - b2.reshape(())) / (1 - b1.reshape(()))
        m1n = beta1 * m1 + (1 - beta1) * g
        m2n = beta2 * m2 + (1 - beta2) * jnp.square(g)
        return p - lr_t * m1n / (jnp.sqrt(m2n) + eps), m1n, m2n

    def fused(p, g, m1, m2, lr, b1, b2):
        return fo.fused_adam_update(p, g, m1, m2, lr, b1, b2,
                                    beta1=0.9, beta2=0.999,
                                    epsilon=1e-8)

    def fused_reference(p, g, m1, m2, lr, b1, b2):
        lr_t = lr * jnp.sqrt(1 - b2.reshape(())) / (1 - b1.reshape(()))
        return fo._reference_adam(p, g, m1, m2, lr_t, lr, None,
                                  0.9, 0.999, 1e-8, 0.0)

    args = (p0, g0, m0, v0, lr0, b1p, b2p)

    def bytes_of(fn):
        comp = jax.jit(fn).lower(*args).compile()
        cost = comp.cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0] if cost else {}
        v = cost.get("bytes accessed") if hasattr(cost, "get") else None
        return float(v) if isinstance(v, (int, float)) else None

    opt_rows = {}
    for name, fn in (("adam_unfused_chain", unfused_chain),
                     ("adam_fused", fused)):
        try:
            ms, cs = bench(jax.jit(fn), args, iters=10)
            nbytes = bytes_of(fused_reference if (SMOKE
                              and name == "adam_fused") else fn)
            opt_rows[name] = nbytes
            row(name, shape=list(N), ms=ms, compile_s=cs,
                bytes_accessed=nbytes,
                mode=("reference_lowering" if SMOKE else "mosaic"))
        except Exception as e:  # noqa: BLE001
            row(name, shape=list(N), error=repr(e)[:300])
    fb, ub = opt_rows.get("adam_fused"), opt_rows.get(
        "adam_unfused_chain")
    if fb is not None and ub is not None:
        ok = fb <= ub * 1.01  # float-accounting slack only
        r = {"name": "fused_optim_bytes_gate", "fused_bytes": fb,
             "unfused_bytes": ub, "ok": bool(ok),
             "mode": ("reference_lowering" if SMOKE else "mosaic")}
        if not ok:
            r["error"] = (f"fused adam moves MORE bytes than the "
                          f"unfused chain ({fb:.0f} > {ub:.0f})")
        RESULTS["rows"].append(r)
        _save()
        print(json.dumps(r))

    # -- quantized weight matmul (the inference serving path) ----------
    # int8 / blockwise-int8 / fp8 weight matmul vs the fp32 baseline:
    # wall-ms per variant + a numerics row against the dequantized
    # reference. Smoke runs the interpret-mode Pallas kernel; on TPU
    # the compiled Mosaic kernel's weight-streaming win is the number
    # this table exists to capture.
    from paddle_tpu.kernels import quant_matmul as qm

    Mq, Kq, Nq = (32, 256, 128) if SMOKE else (1024, 4096, 4096)
    wq = rng.randn(Kq, Nq).astype("float32") * 0.1
    xq = jnp.asarray(rng.randn(Mq, Kq).astype("float32"))
    base = jax.jit(jnp.matmul)
    try:
        ms, cs = bench(base, (xq, jnp.asarray(wq)))
        row("matmul_fp32_baseline", M=Mq, K=Kq, N=Nq, ms=ms,
            compile_s=cs)
    except Exception as e:  # noqa: BLE001
        row("matmul_fp32_baseline", error=repr(e)[:300])
    want = np.asarray(xq) @ wq
    # block must be a 128-multiple: the contraction tile IS the
    # block, and Mosaic rejects sub-lane trailing tiles — a
    # smaller value would error the TPU row this table exists for
    qblk = 128
    for mode, tol in (("int8", 0.05), ("int8_block", 0.05),
                      ("fp8", 0.08)):
        try:
            q, s = qm.quantize_weight(wq, mode, block=qblk)
            fn = jax.jit(functools.partial(
                qm.quantized_matmul, mode=mode, block=qblk))
            ms, cs = bench(fn, (xq, q, s))
            got = np.asarray(fn(xq, q, s), np.float32)
            rel = float(np.abs(got - want).max()
                        / (np.abs(want).max() or 1.0))
            row(f"quant_matmul_{mode}", M=Mq, K=Kq, N=Nq, ms=ms,
                compile_s=cs, max_rel_err=round(rel, 5),
                ok=bool(rel < tol),
                mode=("interpret" if SMOKE else "mosaic"))
        except Exception as e:  # noqa: BLE001
            row(f"quant_matmul_{mode}", M=Mq, K=Kq, N=Nq,
                error=repr(e)[:300])

    # -- microbench: locate the ResNet/BERT MFU gap --------------------
    # isolated timings that tell WHERE a model step's time goes (raw
    # MXU ceiling, conv layout NCHW vs NHWC, encoder-block dots).
    def tflops_row(name, fn, args, flops, **kw):
        try:
            ms, cs = bench(fn, args, iters=10)
            row(name, ms=ms, compile_s=cs,
                tflops=round(flops / (ms / 1e3) / 1e12, 2), **kw)
        except Exception as e:  # noqa: BLE001
            row(name, error=repr(e)[:300], **kw)

    M = 256 if SMOKE else 8192
    a = jnp.asarray(rng.randn(M, M), jnp.bfloat16)
    b = jnp.asarray(rng.randn(M, M), jnp.bfloat16)
    tflops_row("mm_bf16_8192", jax.jit(jnp.dot), (a, b), 2 * M**3)
    try:
        ms = bench_chain(jnp.dot, (a, b), iters=10,
                         chain=lambda out, a_, b_: (a_ + 0 * out, b_))
        row("mm_bf16_8192_device_loop", ms=ms,
            tflops=round(2 * M**3 / (ms / 1e3) / 1e12, 2))
    except Exception as e:  # noqa: BLE001
        row("mm_bf16_8192_device_loop", error=repr(e)[:300])

    B, Cc, H = (2, 16, 8) if SMOKE else (64, 256, 56)
    xc = jnp.asarray(rng.randn(B, Cc, H, H), jnp.bfloat16)
    wc = jnp.asarray(rng.randn(Cc, Cc, 3, 3), jnp.bfloat16)
    conv_flops = 2 * B * H * H * Cc * Cc * 9

    def conv_nchw(x, w):
        return jax.lax.conv_general_dilated(
            x, w, (1, 1), "SAME",
            dimension_numbers=("NCHW", "OIHW", "NCHW"))

    tflops_row("conv3x3_nchw_bf16", jax.jit(conv_nchw), (xc, wc),
               conv_flops, B=B, C=Cc, HW=H)

    xh = jnp.transpose(xc, (0, 2, 3, 1))
    wh = jnp.transpose(wc, (2, 3, 1, 0))

    def conv_nhwc(x, w):
        return jax.lax.conv_general_dilated(
            x, w, (1, 1), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))

    tflops_row("conv3x3_nhwc_bf16", jax.jit(conv_nhwc), (xh, wh),
               conv_flops, B=B, C=Cc, HW=H)

    # one BERT-base encoder block fwd (dots only, no attention
    # softmax subtleties): [B*S, 768] x MLP + QKV-sized matmuls
    R2, D, F = (64, 128, 256) if SMOKE else (16 * 512, 768, 3072)
    h = jnp.asarray(rng.randn(R2, D), jnp.bfloat16)
    wq = jnp.asarray(rng.randn(D, 3 * D), jnp.bfloat16)
    w1 = jnp.asarray(rng.randn(D, F), jnp.bfloat16)
    w2 = jnp.asarray(rng.randn(F, D), jnp.bfloat16)

    def block(h, wq, w1, w2):
        qkv = h @ wq
        mlp = jax.nn.gelu(h @ w1) @ w2
        return qkv[:, :D] + mlp

    flops = 2 * R2 * (D * 3 * D + 2 * D * F)
    tflops_row("bert_block_dots_bf16", jax.jit(block),
               (h, wq, w1, w2), flops, rows=R2)

    RESULTS["wall_s"] = time.time() - T0
    _save()
    return 1 if any("error" in r for r in RESULTS["rows"]) else 0


if __name__ == "__main__":
    sys.exit(main())
