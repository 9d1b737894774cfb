"""chip_smoke.py — does the system start, and compute the right thing, on
the chip?

    python chip_smoke.py          # no arguments, one process, TPU only

Drives the two hot paths once through the entry points a user calls, at
the full width of a model the repo supports (random weights from a seed),
in this order:

  leg A    a trainer takes steps: BERT-base, seq 512, batch 16, bf16
           AMP, flash attention + fused kernels + fused Adam, through
           fluid.Executor.run (what examples/train_bert.py builds);
  kernels  every Pallas kernel the two legs dispatch, compiled (never
           interpreted) at the legs' shapes, against its pure-JAX
           reference computed at "highest" matmul precision;
  leg B    a server answers requests: GPT-3 XL widths exported with
           save_inference_model, loaded with create_predictor, served
           by the ragged GenerationEngine behind ServingServer's
           POST /v1/generate to four concurrent clients (what
           examples/generate_stream.py builds at toy size); all 24
           layers unless the export directory's disk is too small,
           when depth alone is cut and the cut is printed;
  leg C    when four chips are visible: leg A's program data-parallel
           over all four, and GPT-small under a dp2 x tp2 partitioning.

The platform must be "tpu": there is no CPU mode on the command line and
no environment variable that makes one (tests/test_chip_smoke.py imports
the leg functions and calls them at toy size with interpret-mode kernels
instead). Every phase runs even after an earlier one failed, so one chip
call reports everything that is wrong; any failure makes the exit code
non-zero. The last line of stdout is one JSON object,
{"ok": true, "device": {"platform", "kind", "count"}}.

Step and compile seconds are printed for the record; they are not a
result (there is no benchmark cell yet — ROADMAP queue 1 item 1).
"""

import gc
import http.client
import json
import math
import os
import shutil
import sys
import tempfile
import threading
import time

import numpy as np

# a Mosaic (Pallas TPU) kernel in optimized HLO text
MOSAIC_TARGET = 'custom_call_target="tpu_custom_call"'


class Report:
    """Collects named checks so every phase runs to its end and the
    process still fails when any check did. Also counts the compile
    requests jax makes (jit cache misses, whether XLA then compiles or
    the persistent cache answers): the program's own jit_compiles
    counter cannot see a jitted step that jax traces a second time."""

    def __init__(self):
        import jax

        self.failed = []
        self.xla_compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event, *_args, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.xla_compiles += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def check(self, name, ok, detail=""):
        ok = bool(ok)
        print(f"  [{'ok' if ok else 'FAIL'}] {name}" +
              (f": {detail}" if detail else ""), flush=True)
        if not ok:
            self.failed.append(name)
        return ok

    def phase(self, name, fn, *args, **kwargs):
        """Run one phase; an exception is a failed check, not the end
        of the run. Prints the phase's one result line."""
        import traceback

        before = len(self.failed)
        t0 = time.perf_counter()
        result = {}
        try:
            result = fn(self, *args, **kwargs) or {}
        except Exception as e:  # noqa: BLE001 — recorded; the run fails below
            traceback.print_exc()
            self.check(f"{name} ran to its end", False,
                       f"{type(e).__name__}: {e}"[:500])
        result["wall_s"] = round(time.perf_counter() - t0, 1)
        result["memory"] = _memory()
        ok = len(self.failed) == before
        print(f"{name}: {'PASS' if ok else 'FAIL'} {json.dumps(result)}",
              flush=True)
        gc.collect()
        return result


def _on_tpu():
    import jax

    return jax.devices()[0].platform == "tpu"


def _memory():
    """Device 0's allocator counters after a phase; the peak is the
    process's so far and never resets (None where the backend reports
    nothing — the CPU)."""
    import jax

    stats = jax.devices()[0].memory_stats()
    return None if not stats else {
        k: stats.get(k) for k in ("bytes_in_use", "peak_bytes_in_use",
                                  "largest_alloc_size", "bytes_limit")}


def _check_mosaic(report, name, compiled):
    """A kernel that was quietly routed to XLA must not pass: read the
    Mosaic custom calls off the executable itself, not off flags."""
    n = compiled.as_text().count(MOSAIC_TARGET)
    if _on_tpu():
        report.check(f"{name}: Mosaic custom calls in the executable",
                     n > 0, str(n))
    return n


def _rel_err(got, want):
    """Frobenius-relative error, accumulated in float64 on the host."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / max(np.linalg.norm(want),
                                                  1e-30))


def _jit_compiles():
    from paddle_tpu.runtime import dispatch

    return dispatch.cache_stats()["jit_compiles"]


# -- kernel phase -------------------------------------------------------------

# Tolerances are Frobenius-relative and written beside their reason.
# bf16 has an 8-bit significand: one rounding is at most 2^-9 = 2.0e-3
# relative, ~1.1e-3 rms. float32 has 24 bits: 6e-8.
#
# flash attention takes bf16 q/k/v, computes in float32 and rounds its
# output to bf16, so forward error is one bf16 rounding; 4e-3 leaves
# 3.5x the rms and still fails a bf16 accumulation over 512 keys
# (~sqrt(512) * 1.1e-3 = 2.5e-2) or an fp8 operand (6e-2 steps).
TOL_FLASH_FWD = 4e-3
# backward: the kernels recompute probabilities in float32, but
# delta = rowsum(dO * O) reads the SAVED forward output, already
# rounded to bf16, and dq/dk/dv are rounded to bf16 on the way out,
# while the reference is float32 throughout: three chained bf16
# roundings, ~3 * 1.1e-3 rms; 1e-2 keeps the same 3x margin and the
# same failures as above.
TOL_FLASH_BWD = 1e-2
# float32 kernels (layer_norm, softmax_xent, Adam, ragged paged
# attention): float32 arithmetic plus a reduction in a different order
# and the hardware's rsqrt/exp/log, each a few float32 ulps; 2e-5 is
# ~300 ulps and fails any bf16 intermediate (1e-3) by 50x. The ragged
# kernel's two matmuls ask Mosaic for Precision.HIGHEST to stay under
# it: at the TPU default (float32 operands rounded to bf16 for one MXU
# pass) the first chip run measured 2.46e-3 here.
TOL_F32 = 2e-5


def kernel_phase(report, *, flash_shape, rows, hidden, vocab, adam_shapes,
                 ragged):
    """Each Pallas kernel of the two legs, jitted at the legs' shapes on
    the current backend, against its reference. ``ragged`` is the
    engine's geometry: lanes, chunk, heads, head_dim, num_pages,
    page_size, max_pages."""
    import importlib

    import jax
    import jax.numpy as jnp

    from paddle_tpu.kernels.fused_optim import (_reference_adam,
                                                fused_adam_update)
    from paddle_tpu.kernels.layer_norm import fused_layer_norm
    from paddle_tpu.kernels.paged_attention import kv_cache_write
    from paddle_tpu.kernels.ragged_paged_attention import (
        _reference_ragged, ragged_paged_attention)
    from paddle_tpu.kernels.softmax_xent import fused_softmax_xent

    # the package rebinds the name `flash_attention` to the function
    fa = importlib.import_module("paddle_tpu.kernels.flash_attention")
    out = {}
    key = jax.random.PRNGKey(0)

    def run(name, fn, args):
        """Compile fn, count its Mosaic calls, run it."""
        jitted = jax.jit(fn)
        compiled = jitted.lower(*args).compile()
        out[f"{name}_mosaic_calls"] = _check_mosaic(report, name, compiled)
        return jax.block_until_ready(compiled(*args))

    def reference(fn, args):
        with jax.default_matmul_precision("highest"):
            return jax.block_until_ready(jax.jit(fn)(*args))

    def compare(kernel, parts, got, want, tol):
        for part, g_, w_ in zip(parts, got, want):
            err = _rel_err(g_, w_)
            finite = bool(np.isfinite(np.asarray(g_, np.float32)).all())
            out[f"{kernel}_{part}_rel_err"] = float(f"{err:.3g}")
            report.check(f"{kernel} {part} within {tol:g} of reference",
                         finite and err <= tol,
                         f"rel_err {err:.3g}, finite {finite}")

    # -- flash attention fwd + bwd, key-padding mask, bf16 ---------------
    B, H, S, D = flash_shape
    ks = jax.random.split(key, 8)
    q, k, v, g = (jax.random.normal(ks[i], (B, H, S, D), jnp.float32)
                  .astype(jnp.bfloat16) for i in range(4))
    lengths = np.linspace(S // 2, S, B).astype(np.int32)   # padded rows
    mask = jnp.asarray(np.arange(S)[None, :] < lengths[:, None])

    def flash_loss(q, k, v, g, mask):
        o = fa.flash_attention(q, k, v, causal=False, mask=mask)
        return (o.astype(jnp.float32) * g.astype(jnp.float32)).sum(), o

    def flash_ref_loss(q, k, v, g, mask):      # float32 in, float32 out
        add = jnp.where(mask, 0.0, fa.NEG_INF).astype(jnp.float32)
        o = fa._reference_attention(q, k, v, 1.0 / math.sqrt(D), False,
                                    add, None)
        return (o * g).sum(), o

    def with_grads(loss_fn):
        def f(q, k, v, g, mask):
            (_, o), grads = jax.value_and_grad(
                loss_fn, argnums=(0, 1, 2), has_aux=True)(q, k, v, g, mask)
            return (o,) + grads
        return f

    got = run("flash", with_grads(flash_loss), (q, k, v, g, mask))
    want = reference(
        with_grads(flash_ref_loss),
        tuple(a.astype(jnp.float32) for a in (q, k, v, g)) + (mask,))
    compare("flash", ("o",), got[:1], want[:1], TOL_FLASH_FWD)
    compare("flash", ("dq", "dk", "dv"), got[1:], want[1:], TOL_FLASH_BWD)
    del q, k, v, g, got, want

    # -- fused layer_norm fwd + bwd, float32 -----------------------------
    x = jax.random.normal(ks[4], (rows, hidden), jnp.float32) * 2.0 + 0.5
    gamma = 1.0 + 0.1 * jax.random.normal(ks[5], (hidden,), jnp.float32)
    beta = 0.1 * jax.random.normal(ks[6], (hidden,), jnp.float32)
    dy = jax.random.normal(ks[7], (rows, hidden), jnp.float32)

    def ln_ref(x, gamma, beta):
        mean = jnp.mean(x, axis=1, keepdims=True)
        var = jnp.mean((x - mean) ** 2, axis=1, keepdims=True)
        return (x - mean) * jax.lax.rsqrt(var + 1e-5) * gamma + beta

    def ln_all(ln):
        def f(x, gamma, beta, dy):
            y, vjp = jax.vjp(ln, x, gamma, beta)
            return (y,) + vjp(dy)
        return f

    got = run("layer_norm",
              ln_all(lambda x, g_, b_: fused_layer_norm(x, g_, b_, 1e-5)),
              (x, gamma, beta, dy))
    want = reference(ln_all(ln_ref), (x, gamma, beta, dy))
    compare("layer_norm", ("y", "dx", "dgamma", "dbeta"), got, want,
            TOL_F32)
    del x, dy, got, want

    # -- fused softmax cross-entropy fwd + bwd, float32 -------------------
    logits = 3.0 * jax.random.normal(ks[0], (rows, vocab), jnp.float32)
    labels = jax.random.randint(ks[1], (rows,), 0, vocab, jnp.int32)
    dloss = jax.random.uniform(ks[2], (rows,), jnp.float32, 0.5, 1.5)

    def xent_ref(logits, labels):
        lse = jax.nn.logsumexp(logits, axis=1)
        return lse - jnp.take_along_axis(logits, labels[:, None], 1)[:, 0]

    def xent_all(xent):
        def f(logits, labels, dloss):
            loss, vjp = jax.vjp(lambda s: xent(s, labels), logits)
            return loss, vjp(dloss)[0]
        return f

    got = run("softmax_xent", xent_all(fused_softmax_xent),
              (logits, labels, dloss))
    want = reference(xent_all(xent_ref), (logits, labels, dloss))
    compare("softmax_xent", ("loss", "dlogits"), got, want, TOL_F32)
    del logits, got, want

    # -- fused Adam, float32, a weight and a vector ----------------------
    hyper = dict(beta1=0.9, beta2=0.999, epsilon=1e-8)
    lr, b1p, b2p = (jnp.asarray([v_], jnp.float32)
                    for v_ in (1e-4, 0.9 ** 3, 0.999 ** 3))
    for i, shape in enumerate(adam_shapes):
        kk = jax.random.split(ks[3 + i], 4)
        p = 0.02 * jax.random.normal(kk[0], shape, jnp.float32)
        grad = 1e-3 * jax.random.normal(kk[1], shape, jnp.float32)
        m1 = 1e-3 * jax.random.normal(kk[2], shape, jnp.float32)
        m2 = 1e-6 * jax.random.uniform(kk[3], shape, jnp.float32)

        def adam_ref(p, grad, m1, m2, lr, b1p, b2p):
            lr_ = lr.reshape(())
            lr_t = lr_ * jnp.sqrt(1 - b2p.reshape(())) / (1 - b1p.reshape(()))
            pn, m1n, m2n = _reference_adam(
                p, grad, m1, m2, lr_t, lr_, None, hyper["beta1"],
                hyper["beta2"], hyper["epsilon"], 0.0)
            return pn - p, m1n, m2n      # the update, not p: p hides it

        def adam_fused(p, grad, m1, m2, lr, b1p, b2p):
            pn, m1n, m2n = fused_adam_update(p, grad, m1, m2, lr, b1p, b2p,
                                             **hyper)
            return pn - p, m1n, m2n

        name = "adam_" + "x".join(str(d) for d in shape)
        args = (p, grad, m1, m2, lr, b1p, b2p)
        got = run(name, adam_fused, args)
        want = reference(adam_ref, args)
        compare(name, ("update", "m1", "m2"), got, want, TOL_F32)
    del p, grad, m1, m2, got, want

    # -- KV page write + ragged paged attention, float32 pages -----------
    R, C = ragged["lanes"], ragged["chunk"]
    Hh, Dd = ragged["heads"], ragged["head_dim"]
    P, ps, maxp = (ragged["num_pages"], ragged["page_size"],
                   ragged["max_pages"])
    rng = np.random.RandomState(0)
    # every kind of row the engine builds: idle lane, decode token, a
    # first prefill chunk, a later (page-unaligned) chunk, a full chunk
    starts = np.zeros(R, np.int32)
    nvalid = np.zeros(R, np.int32)
    kinds = [(0, 0), (min(37, maxp * ps - 1), 1), (0, C), (C + 3, C - 1),
             (2 * C, C)]
    for r in range(R):
        starts[r], nvalid[r] = kinds[r % len(kinds)]
    tables = np.zeros((R, maxp), np.int32)
    free = list(rng.permutation(np.arange(1, P)))   # page 0 is the junk page
    for r in range(R):
        need = -(-int(starts[r] + nvalid[r]) // ps)
        tables[r, :need] = [free.pop() for _ in range(need)]
    k_pages = rng.randn(Hh, P, ps, Dd).astype(np.float32)
    v_pages = rng.randn(Hh, P, ps, Dd).astype(np.float32)
    qn, kn, vn = (rng.randn(R, C, Hh, Dd).astype(np.float32)
                  for _ in range(3))
    # the oracle's page write: plain numpy indexing, no shared code
    k_want, v_want = k_pages.copy(), v_pages.copy()
    for r in range(R):
        for j in range(int(nvalid[r])):
            pos = int(starts[r]) + j
            page, slot = tables[r, pos // ps], pos % ps
            k_want[:, page, slot] = kn[r, j]
            v_want[:, page, slot] = vn[r, j]

    def write_then_attend(attend):
        def f(kp, vp, q, kn, vn, tables, starts, nvalid):
            kp, vp = kv_cache_write(kp, vp, kn, vn, tables, starts, nvalid)
            return attend(q, kp, vp, starts, nvalid, tables), kp, vp
        return f

    args = tuple(jnp.asarray(a) for a in (k_pages, v_pages, qn, kn, vn,
                                          tables, starts, nvalid))
    o, kp, vp = run("ragged", write_then_attend(ragged_paged_attention), args)
    o_want = reference(
        lambda q, kp, vp, st, nv, tb: _reference_ragged(
            q, kp, vp, st, nv, tb, 1.0 / math.sqrt(Dd), None, None),
        tuple(jnp.asarray(a) for a in (qn, k_want, v_want, starts, nvalid,
                                       tables)))
    # page 0 takes the invalid rows' writes by design; compare the rest
    report.check("kv_cache_write pages equal the numpy oracle",
                 np.array_equal(np.asarray(kp)[:, 1:], k_want[:, 1:])
                 and np.array_equal(np.asarray(vp)[:, 1:], v_want[:, 1:]))
    compare("ragged", ("o",), (o,), (o_want,), TOL_F32)
    return out


# -- leg A: a trainer takes steps ---------------------------------------------


def _bert_train_program(cfg, seq):
    """What examples/train_bert.py --flash builds: bf16 AMP around Adam,
    flash attention on, fused kernels and optimizer_fuse at defaults."""
    import paddle_tpu as fluid
    from paddle_tpu.contrib.mixed_precision import decorate
    from paddle_tpu.models import build_bert_pretrain

    cfg.use_flash_attention = True
    opt = decorate(fluid.optimizer.Adam(1e-4), init_loss_scaling=1.0,
                   use_dynamic_loss_scaling=False, dest_dtype="bfloat16")
    main, startup, _feeds, fetches = build_bert_pretrain(cfg, seq,
                                                         optimizer=opt)
    return main, startup, fetches["loss"]


def _train_steps(report, name, exe, program, feed, loss, scope, steps,
                 first_loss):
    """``steps`` calls of exe.run on one fixed feed. Checks: finite
    losses, the first near ``first_loss`` (when given), the last below
    the first, no compile after the first call."""
    losses, secs = [], []
    compiles_warm = None
    for i in range(steps):
        if i == 1:
            compiles_warm = (exe.cache_stats()["jit_compiles"],
                             _jit_compiles(), report.xla_compiles)
        t0 = time.perf_counter()
        (out,) = exe.run(program, feed=feed, fetch_list=[loss], scope=scope)
        losses.append(float(np.asarray(out)))     # host read = device sync
        secs.append(time.perf_counter() - t0)
    report.check(f"{name}: every loss finite", np.isfinite(losses).all(),
                 str([round(v, 4) for v in losses]))
    if first_loss is not None:
        report.check(f"{name}: first loss within 0.3 of ln(vocab) = "
                     f"{first_loss:.3f}", abs(losses[0] - first_loss) <= 0.3,
                     f"{losses[0]:.4f}")
    report.check(f"{name}: last loss below the first",
                 losses[-1] < losses[0], f"{losses[0]:.4f} -> {losses[-1]:.4f}")
    now = (exe.cache_stats()["jit_compiles"], _jit_compiles(),
           report.xla_compiles)
    report.check(f"{name}: no compile after the first call",
                 now == compiles_warm, f"{compiles_warm} -> {now}")
    return {"losses": [round(v, 4) for v in losses],
            "first_call_s": round(secs[0], 2),
            "step_s": [round(s, 4) for s in secs[1:]]}


def leg_a(report, *, cfg, seq, batch, steps=8):
    import paddle_tpu as fluid
    from paddle_tpu.models.bert import synthetic_batch

    main, startup, loss = _bert_train_program(cfg, seq)
    feed = synthetic_batch(np.random.RandomState(0), batch, seq,
                           cfg.vocab_size, min_len=seq // 2)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.TPUPlace())
        exe.run(startup)
        out = _train_steps(report, "leg A", exe, main, feed, loss, scope,
                           steps, math.log(cfg.vocab_size))
        bound = exe.bind(main, feed, [loss], scope=scope)
        out["mosaic_calls"] = _check_mosaic(report, "leg A step",
                                            bound.aot_compiled())
        out["compile_time_s"] = round(exe.cache_stats()["compile_time_s"], 1)
    fused = sum(op.type == "fused_adam" for op in main.global_block().ops)
    out["fused_adam_ops"] = fused
    if _on_tpu():
        report.check("leg A: the optimizer tail is fused Adam", fused > 0,
                     str(fused))
    return out


# -- leg B: a server answers requests -----------------------------------------


def _export_bytes(program, layers):
    """(fixed, per decoder layer) float32 bytes save_inference_model
    writes for an LM program of ``layers`` equal decoder layers; layer
    i's weights are named dec<i>_*."""
    sizes = {v.name: 4 * int(np.prod(v.shape))
             for v in program.global_block().vars.values()
             if v.persistable and not v.is_data}
    per_layer = sum(n for name, n in sizes.items()
                    if name.startswith("dec0_"))
    return sum(sizes.values()) - layers * per_layer, per_layer


def _export_lm(cfg, seq, model_dir, out):
    """Export ``cfg`` with random weights; returns the config exported.
    Depth is cut — never a width — when the export directory's disk
    cannot hold all of it, and the cut is printed."""
    import dataclasses
    import resource

    import paddle_tpu as fluid
    from paddle_tpu.generation.model import build_lm_program

    main, startup, _feeds, fetches = build_lm_program(cfg, seq)
    fixed, per_layer = _export_bytes(main, cfg.num_layers)
    free = shutil.disk_usage(model_dir).free
    # the machine's limits, on record (RLIMIT_FSIZE: -1 is none): the
    # driver's chip machine refused one 5.4 GB file with EFBIG
    out.update(export_bytes=fixed + cfg.num_layers * per_layer,
               export_dir_free_bytes=free,
               file_size_limit=resource.getrlimit(resource.RLIMIT_FSIZE)[0])
    fit = int((0.9 * free - fixed) // per_layer)
    if fit < cfg.num_layers:
        print(f"leg B: DEPTH CUT {cfg.num_layers} -> {fit} layers: "
              f"{model_dir} has {free} bytes free, the export needs "
              f"{fixed} + {per_layer} per layer", flush=True)
        if fit < 1:
            raise OSError(f"no room in {model_dir} for one decoder layer")
        cfg = dataclasses.replace(cfg, num_layers=fit)
        main, startup, _feeds, fetches = build_lm_program(cfg, seq)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.TPUPlace())
        exe.run(startup)
        fluid.io.save_inference_model(model_dir, ["tokens"],
                                      [fetches["logits"]], exe, main)
    return cfg


def _http_get(server, path):
    conn = http.client.HTTPConnection(server.host, server.port, timeout=60)
    try:
        conn.request("GET", path)
        resp = conn.getresponse()
        return resp.status, resp.read()
    finally:
        conn.close()


def leg_b(report, *, cfg, prompt_lens, new_tokens=32, export_seq=128,
          engine_kwargs=None):
    from paddle_tpu import generation
    from paddle_tpu.inference import Config, create_predictor
    from paddle_tpu.runtime import dispatch
    from paddle_tpu.serving import ServingEngine, ServingServer

    out = {}
    model_dir = tempfile.mkdtemp(prefix="chip_smoke_lm_")
    eng = srv = serve = None
    try:
        t0 = time.perf_counter()
        cfg = _export_lm(cfg, export_seq, model_dir, out)
        out["layers"] = cfg.num_layers
        out["largest_file_bytes"] = max(
            os.path.getsize(os.path.join(model_dir, f))
            for f in os.listdir(model_dir))
        gc.collect()            # the export scope's weights leave the chip
        pred = create_predictor(Config(model_dir))
        out["export_load_s"] = round(time.perf_counter() - t0, 1)

        t0 = time.perf_counter()
        eng = generation.GenerationEngine(pred, cfg, warmup=True,
                                          **(engine_kwargs or {}))
        out["engine_warmup_s"] = round(time.perf_counter() - t0, 1)
        out.update(mode=eng.mode, lanes=eng.lanes, chunk=eng.chunk_tokens,
                   page_size=eng.page_size, num_pages=eng.num_pages)
        report.check("leg B: engine runs its default ragged mode",
                     eng.mode == "ragged", eng.mode)
        report.check("leg B: a prompt is longer than the prefill chunk",
                     max(prompt_lens) > eng.chunk_tokens)
        compiles_warm = (_jit_compiles(), report.xla_compiles)
        serve = ServingEngine(pred, start=False)
        srv = ServingServer(serve, generation_engine=eng)

        rng = np.random.RandomState(0)
        prompts = [rng.randint(1, cfg.vocab_size, n).astype(np.int64)
                   for n in prompt_lens]
        streamed = [None] * len(prompts)

        def client(i):
            conn = http.client.HTTPConnection(srv.host, srv.port,
                                              timeout=600)
            try:
                conn.request("POST", "/v1/generate", json.dumps({
                    "tokens": [int(t) for t in prompts[i]],
                    "max_new_tokens": new_tokens}))
                resp = conn.getresponse()
                streamed[i] = (resp.status,
                               [json.loads(ln) for ln in resp if ln.strip()])
            finally:
                conn.close()

        t0 = time.perf_counter()
        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(len(prompts))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(900)
        out["serve_s"] = round(time.perf_counter() - t0, 2)
        report.check("leg B: every client returned",
                     not any(t.is_alive() for t in threads)
                     and all(s is not None for s in streamed))
        snap = eng.stats()          # the concurrent window only
        got = []
        for i, res in enumerate(streamed):
            status, lines = res if res else (None, [])
            tail = lines[-1] if lines else {}
            ok = (status == 200 and tail.get("done") is True
                  and tail.get("finish_reason") == "length"
                  and not any("error" in ln for ln in lines))
            toks = [ln["token"] for ln in lines[:-1]] if ok else None
            report.check(f"leg B: stream {i} (prompt {prompt_lens[i]}) ends "
                         f"done with {new_tokens} tokens and no error",
                         ok and len(toks) == new_tokens,
                         "" if ok else json.dumps(lines[-2:])[:400])
            got.append(toks)
        for i, toks in enumerate(got):
            want = eng.generate(prompts[i], max_new_tokens=new_tokens)
            report.check(f"leg B: stream {i} equals eng.generate",
                         toks == want,
                         "" if toks == want else f"{toks} != {want}")
        steps = snap["decode_steps_total"]
        mean_active = (snap["decode_active_lane_steps_total"] / steps
                       if steps else 0.0)
        out["mean_active_lanes"] = round(mean_active, 2)
        out["prefill_chunks"] = snap["prefill_chunks_total"]
        out["step_ms_p50"] = snap["decode_step_ms"].get("p50")
        report.check("leg B: more than one lane active per step on average",
                     mean_active > 1.0, f"{mean_active:.2f}")
        report.check("leg B: chunked prefill ran",
                     snap["prefill_chunks_total"] > len(prompts),
                     str(snap["prefill_chunks_total"]))
        for path in ("/healthz", "/metrics"):
            status, body = _http_get(srv, path)
            report.check(f"leg B: GET {path} answers", status == 200
                         and len(body) > 0, str(status))
        now = (_jit_compiles(), report.xla_compiles)
        report.check("leg B: no compile after warm-up", now == compiles_warm,
                     f"{compiles_warm} -> {now}")
        ragged_step = next(b for b in dispatch.live_bound_steps()
                           if b.compiled.tag == "generation/ragged_step")
        out["mosaic_calls"] = _check_mosaic(report, "leg B ragged step",
                                            ragged_step.aot_compiled())
        st = eng.stats()
        donated, skipped = (st.get("step_donated_bytes"),
                            st.get("donation_skip_reason"))
        out["step_donated_bytes"] = donated
        # (the executor donates nothing on a CPU, where the test of this
        # leg runs it, and says so)
        report.check("leg B: the ragged step is donated every page pool",
                     donated == (0 if skipped else eng.cache.pool_bytes()),
                     f"{donated} of {eng.cache.pool_bytes()} B ({skipped})")
        aliases = ragged_step.donation_aliases()
        report.check("leg B: each pool is aliased onto its own output",
                     all(k == v for k, v in aliases.items())
                     and bool(aliases or skipped),
                     str({k: v for k, v in aliases.items() if k != v})[:300])
        eng.close(drain=True)
        in_use = eng.cache.stats()["pages_in_use"]
        report.check("leg B: zero pages in use after drain", in_use == 0,
                     str(in_use))
        eng.cache.check_integrity()
        report.check("leg B: page pool integrity", True)
    finally:
        if srv is not None:
            srv.close()
        if serve is not None:
            serve.close()
        if eng is not None:
            eng.close(drain=False)
        shutil.rmtree(model_dir, ignore_errors=True)
    return out


# -- leg C: four chips, one process -------------------------------------------


def _bytes_in_use(devices):
    return [(d.memory_stats() or {}).get("bytes_in_use") for d in devices]


def _check_placement(report, name, scope, param, devices, before):
    """Code that has only ever seen one real device may put everything
    on the first: every device must hold live bytes of the order of its
    share, and a parameter's shards must span the mesh. ``before`` is
    what each device held when leg C began: legs A and B ran on device
    0 alone, and what they left there is not leg C's share."""
    shard_devs = {s.device for s in scope.find_var(param).addressable_shards}
    report.check(f"{name}: {param} has shards on {len(devices)} devices",
                 shard_devs == set(devices), str(len(shard_devs)))
    in_use = _bytes_in_use(devices)
    if _on_tpu() or all(b is not None for b in in_use):
        grown = [b - b0 for b, b0 in zip(in_use, before)]
        report.check(f"{name}: every device holds live bytes of the order "
                     "of its share",
                     all(g > 0 and g >= 0.5 * max(grown) for g in grown),
                     f"{in_use} (before leg C: {before})")
    return in_use


def leg_c(report, *, bert_cfg, bert_seq, bert_batch, gpt_cfg, gpt_seq,
          gpt_batch, steps_c1=5, steps_c2=3):
    import jax

    import paddle_tpu as fluid
    from paddle_tpu.models.bert import synthetic_batch
    from paddle_tpu.models.gpt import build_gpt_lm
    from paddle_tpu.partition import PartitionConfig

    devices = jax.devices()[:4]
    report.check("leg C: four distinct devices of one platform",
                 len({d.id for d in devices}) == 4
                 and len({d.platform for d in devices}) == 1,
                 str(devices))
    places = [fluid.TPUPlace(i) for i in range(4)]
    gc.collect()
    before = _bytes_in_use(devices)
    out = {"bytes_in_use_before": before}

    # C1: leg A's program, data-parallel over all four
    main, startup, loss = _bert_train_program(bert_cfg, bert_seq)
    feed = synthetic_batch(np.random.RandomState(0), bert_batch, bert_seq,
                           bert_cfg.vocab_size, min_len=bert_seq // 2)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.TPUPlace())
        exe.run(startup)
        cp = fluid.CompiledProgram(main).with_data_parallel(
            loss_name=loss.name, places=places)
        out["c1"] = _train_steps(report, "leg C1 (dp4)", exe, cp, feed, loss,
                                 scope, steps_c1,
                                 math.log(bert_cfg.vocab_size))
        out["c1"]["bytes_in_use"] = _check_placement(
            report, "leg C1", scope, "enc0_qkv.w", devices, before)
    del scope, exe, cp
    gc.collect()

    # C2: GPT under the partitioner, dp2 x tp2
    main, startup, _feeds, fetches = build_gpt_lm(
        gpt_cfg, gpt_seq, optimizer=fluid.optimizer.Adam(1e-4))
    rng = np.random.RandomState(0)
    toks = rng.randint(0, gpt_cfg.vocab_size,
                       (gpt_batch, gpt_seq)).astype("int64")
    feed = {"tokens": toks, "labels": np.roll(toks, -1, 1)}
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.TPUPlace())
        exe.run(startup)
        cp = fluid.CompiledProgram(main).with_partitioning(
            PartitionConfig(mesh_axes={"dp": 2, "tp": 2}))
        out["c2"] = _train_steps(report, "leg C2 (dp2 x tp2)", exe, cp, feed,
                                 fetches["loss"], scope, steps_c2, None)
        out["c2"]["bytes_in_use"] = _check_placement(
            report, "leg C2", scope, "dec0_qkv.w", devices, before)
    return out


# -- entry point --------------------------------------------------------------


def main():
    if len(sys.argv) > 1:
        sys.stderr.write("chip_smoke.py takes no arguments\n")
        return 2
    import jax

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    if dev.platform != "tpu":
        sys.stderr.write(
            f"chip_smoke.py needs a TPU; jax {jax.__version__} found "
            f"platform {dev.platform!r} ({dev.device_kind}). Reach the chip "
            "through the chip tool (README 'Running').\n")
        return 2
    for var in ("PADDLE_TPU_KERNEL_INTERPRET", "PADDLE_TPU_FLASH_INTERPRET"):
        if os.environ.get(var):
            sys.stderr.write(f"chip_smoke.py: unset {var} — the kernels "
                             "must compile, not interpret\n")
            return 2
    if os.environ.get("PADDLE_TPU_FUSED_KERNELS", "1") == "0":
        sys.stderr.write("chip_smoke.py: PADDLE_TPU_FUSED_KERNELS=0 turns "
                         "the fused kernels off; unset it\n")
        return 2

    from paddle_tpu.flags import flag
    from paddle_tpu.models import BertConfig
    from paddle_tpu.models.gpt import GPTConfig
    from paddle_tpu.runtime import dispatch

    dispatch.ensure_persistent_cache()
    print(f"jax {jax.__version__} platform {dev.platform} kind "
          f"{dev.device_kind!r} count {device['count']} "
          f"jax_compilation_cache_dir "
          f"{jax.config.jax_compilation_cache_dir}", flush=True)

    report = Report()
    bert = BertConfig.base()
    gpt = GPTConfig.gpt3_1p3b()
    gpt.hidden_dropout = gpt.attention_dropout = 0.0
    head_dim = gpt.hidden_size // gpt.num_heads
    page_size = int(flag("generation_page_size"))
    # leg A first: its peak bytes are then its own (the counter never
    # resets, and the kernel phase holds a 1 GB logits panel three times)
    report.phase("leg A", leg_a, cfg=bert, seq=512, batch=16, steps=8)
    report.phase(
        "kernels", kernel_phase,
        flash_shape=(16, bert.num_heads, 512,
                     bert.hidden_size // bert.num_heads),
        rows=16 * 512, hidden=bert.hidden_size, vocab=bert.vocab_size,
        adam_shapes=((bert.vocab_size, bert.hidden_size),
                     (bert.hidden_size,)),
        ragged=dict(lanes=int(flag("generation_max_decode_batch")),
                    chunk=int(flag("generation_chunk_tokens")),
                    heads=gpt.num_heads, head_dim=head_dim,
                    num_pages=int(flag("generation_num_pages")),
                    page_size=page_size,
                    max_pages=-(-gpt.max_position // page_size)))
    report.phase("leg B", leg_b, cfg=gpt, prompt_lens=(5, 12, 23, 40))
    if device["count"] >= 4:
        report.phase("leg C", leg_c, bert_cfg=BertConfig.base(),
                     bert_seq=512, bert_batch=64, gpt_cfg=GPTConfig.small(),
                     gpt_seq=512, gpt_batch=8)
    else:
        print(f"leg C: not run ({device['count']} device visible; it needs "
              "four)", flush=True)

    stats = dispatch.cache_stats()
    print(f"compile: jit_compiles {stats['jit_compiles']} first_call_s "
          f"{stats['compile_time_s']:.1f} persistent_cache_dir "
          f"{stats['persistent_cache_dir']} compile_requests "
          f"{report.xla_compiles} persistent_cache_hits "
          f"{report.cache_hits}", flush=True)
    if report.failed:
        sys.stderr.write("chip_smoke.py FAILED: "
                         + "; ".join(report.failed) + "\n")
        print(json.dumps({"ok": False, "failed": report.failed,
                          "device": device}))
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
