"""Local AOT validation of the Pallas kernels + headline step against
the REAL TPU compiler: libtpu ships in the image, so the Mosaic
compiler can run in the chipless sandbox against a v5e topology — no
chip needed to prove the kernels COMPILE (rank-1 block specs and the
like, which the CPU interpreter never sees); execution and numerics
still need the chip (chip_smoke.py). Everything here runs through
jax.experimental.topologies.get_topology_desc("v5e:2x2") +
jit(...).lower(...).compile() with PADDLE_TPU_FORCE_PALLAS=1, i.e. the
exact kernels a chip run dispatches. Use it as the free pre-flight
before spending chip time.

Run:  python tools/aot_check.py            # writes AOT_TPU_CHECK.json
Gated test: PT_AOT_CHECK=1 pytest tests/test_aot_check.py

Reference capability mirrored: the reference's fused GPU kernels are
compiled by nvcc for their target arch at build time
(/root/reference/paddle/fluid/operators/fused/multihead_matmul_op.cu:1);
this is the TPU analogue — target-arch compilation as a local,
driver-checkable step.
"""

import collections
import hashlib
import json
import os
import re
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OUT = os.path.join(HERE, "AOT_TPU_CHECK.json")

_CHILD_ENV = {
    "JAX_PLATFORMS": "cpu",
    # 8 virtual CPU devices so the with_* strategies can BUILD their
    # meshes; aot_compile then re-lays each mesh over topology devices
    "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
    "TPU_ACCELERATOR_TYPE": "v5litepod-4",
    "TPU_WORKER_HOSTNAMES": "localhost",
    "TPU_SKIP_MDS_QUERY": "1",
    "PADDLE_TPU_FORCE_PALLAS": "1",
}


# a custom call's instruction name in optimized HLO: the pallas_call's
# ``name=``, less the ".N" XLA appends to a repeat
_KERNEL_NAME = re.compile(r"\s*(?:ROOT )?%?([A-Za-z_][\w-]*?)(?:\.\d+)? = ")


def _load(path):
    """A file of benchmark/ as a module, by path: the benchmark is not
    a package of the program."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        os.path.basename(path)[:-3], path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def headline_step():
    """(main, startup, loss, feed) of the cell bert_base_s512_1chip:
    benchmark/configs/bert_base_pretrain.json built by
    benchmark/models/bert_program.py, one batch of
    benchmark/traffic/pretrain_s512.json's batch_per_chip rows."""
    import numpy as np

    bench = os.path.join(HERE, "benchmark")
    with open(os.path.join(bench, "configs",
                           "bert_base_pretrain.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(bench, "traffic", "pretrain_s512.json")) as f:
        traffic = json.load(f)
    model = _load(os.path.join(bench, "models", "bert_program.py"))
    seq = traffic["seq_len"]
    main_prog, startup, loss_var = model.build(cfg, seq)
    (feed,) = model.make_feeds(np.random.default_rng(0), 1,
                               traffic["batch_per_chip"], seq,
                               cfg["vocab_size"])
    return main_prog, startup, loss_var, feed


def _child():
    import numpy as np
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    sys.path.insert(0, HERE)
    # persistent compilation cache (the program's one mechanism): the
    # headline stage alone is ~4 min of Mosaic+XLA; re-runs of the tool
    # should pay it once
    from chip_smoke import MOSAIC_TARGET
    from paddle_tpu.runtime.dispatch import ensure_persistent_cache

    ensure_persistent_cache()
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    dev = topo.devices[0]
    mesh1 = Mesh(np.array([dev]), ("d",))
    R = NamedSharding(mesh1, P())  # replicated on the single device

    results = {"target": str(dev.device_kind), "rows": []}

    def row(name, **kw):
        kw["name"] = name
        results["rows"].append(kw)
        print(json.dumps(kw), flush=True)

    # PT_AOT_ONLY=<substring>: compile only matching rows (iterating on
    # one kernel must not pay the whole flash sweep every run)
    only = os.environ.get("PT_AOT_ONLY", "")

    def record(name, compile_fn, group=None, **meta):
        """Run compile_fn() -> jax compiled object for the v5e target;
        record ok/compile_s/memory/Mosaic calls or the compiler's
        rejection. ``group`` is an extra PT_AOT_ONLY match target (e.g.
        every fused-optimizer row answers to PT_AOT_ONLY=fused_optim
        regardless of row name)."""
        if only and only not in name and only != group:
            return True
        if group:
            meta["group"] = group
        t0 = time.time()
        try:
            compiled = compile_fn()
            ma = compiled.memory_analysis()
            total = int(ma.temp_size_in_bytes + ma.argument_size_in_bytes
                        + ma.output_size_in_bytes)
            text = compiled.as_text()
            # the optimized HLO names its source files, and so do the
            # Mosaic kernels' payloads: the hash compares between trees
            # unpacked at one path, one after the other (PT_AOT_PARENT)
            sha = hashlib.sha256(text.encode()).hexdigest()
            row(name, ok=True, compile_s=round(time.time() - t0, 1),
                temp_bytes=int(ma.temp_size_in_bytes),
                arg_bytes=int(ma.argument_size_in_bytes),
                hbm_frac_v5e=round(total / 16e9, 3),
                mosaic_calls=text.count(MOSAIC_TARGET),
                mosaic_kernels=dict(collections.Counter(
                    _KERNEL_NAME.match(ln).group(1)
                    for ln in text.splitlines() if MOSAIC_TARGET in ln)),
                hlo_sha256=sha, **meta)
            return True
        except Exception as e:  # noqa: BLE001 — record the rejection
            row(name, ok=False, compile_s=round(time.time() - t0, 1),
                error=f"{type(e).__name__}: {e}"[:400], **meta)
            return False

    def aot(name, fn, abstract_args, group=None, **meta):
        """A bare function, jitted for the single v5e device."""
        def compile_fn():
            n = len(jax.tree_util.tree_leaves(abstract_args))
            return jax.jit(fn, in_shardings=(R,) * n).lower(
                *abstract_args).compile()
        return record(name, compile_fn, group, **meta)

    import importlib

    fa = importlib.import_module("paddle_tpu.kernels.flash_attention")
    from paddle_tpu.kernels.layer_norm import fused_layer_norm
    from paddle_tpu.kernels.softmax_xent import fused_softmax_xent

    bf = jnp.bfloat16
    H, D = 12, 64
    # -- flash forward: blk sweep x seq, the r3/r4 unvalidated matrix --
    for S, B in ((512, 8), (2048, 2)):
        q = jax.ShapeDtypeStruct((B, H, S, D), bf)
        sm = 1.0 / D ** 0.5
        for blk in (128, 256, 512):
            if blk > S:
                continue
            aot(f"flash_fwd_S{S}_blk{blk}",
                lambda q, k, v, blk=blk, sm=sm: fa._flash_fwd_pallas(
                    q, k, v, None, None, sm, True, interpret=False,
                    blk_q=blk, with_lse=False)[0],
                (q, q, q), S=S, blk_q=blk)
        # fwd+bwd through the public API (mask path + custom vjp)
        aot(f"flash_train_S{S}",
            jax.grad(lambda q, k, v: fa.flash_attention(
                q, k, v, causal=True).astype(jnp.float32).sum(),
                argnums=(0, 1, 2)),
            (q, q, q), S=S)
    # masked + bias variant at head dim 128 (the GPT-1.3B shape)
    q128 = jax.ShapeDtypeStruct((2, 8, 512, 128), bf)
    m = jax.ShapeDtypeStruct((2, 512), jnp.float32)
    aot("flash_fwd_hd128_mask",
        lambda q, k, v, m: fa.flash_attention(q, k, v, causal=False,
                                              mask=m),
        (q128, q128, q128, m), S=512, head_dim=128)
    # mask AND bias through fwd+bwd — the configuration whose bias-path
    # dq kernel held the one rank-2 mask spec the r5 migration missed
    bshape = jax.ShapeDtypeStruct((1, 8, 512, 512), jnp.float32)
    aot("flash_train_mask_bias",
        jax.grad(lambda q, k, v, m, b: fa.flash_attention(
            q, k, v, causal=False, mask=m, bias=b).astype(
                jnp.float32).sum(), argnums=(0, 1, 2, 4)),
        (q128, q128, q128, m, bshape), S=512, head_dim=128)
    # masked train at the plain shape too (the stream-kernel bwd path)
    qm = jax.ShapeDtypeStruct((2, H, 512, D), bf)
    mm2 = jax.ShapeDtypeStruct((2, 512), jnp.float32)
    aot("flash_train_mask",
        jax.grad(lambda q, k, v, m: fa.flash_attention(
            q, k, v, causal=True, mask=m).astype(jnp.float32).sum(),
            argnums=(0, 1, 2)),
        (qm, qm, qm, mm2), S=512)

    # -- fused layer_norm fwd + bwd ------------------------------------
    x = jax.ShapeDtypeStruct((4096, 768), jnp.float32)
    g = jax.ShapeDtypeStruct((768,), jnp.float32)
    aot("layer_norm_fwd",
        lambda x, g, b: fused_layer_norm(x, g, b, 1e-5), (x, g, g))
    aot("layer_norm_train",
        jax.grad(lambda x, g, b: fused_layer_norm(
            x, g, b, 1e-5).sum(), argnums=(0, 1, 2)), (x, g, g))

    # -- fused softmax_xent fwd + bwd ----------------------------------
    s = jax.ShapeDtypeStruct((4096, 30522), jnp.float32)
    lbl = jax.ShapeDtypeStruct((4096,), jnp.int32)
    aot("softmax_xent_fwd", fused_softmax_xent, (s, lbl))
    aot("softmax_xent_train",
        jax.grad(lambda s, lbl: fused_softmax_xent(s, lbl).sum()),
        (s, lbl))

    # -- paged-attention decode kernel + page write (generation/) -----
    # PADDLE_TPU_FORCE_PALLAS=1 routes the wrapper onto the real jax
    # Mosaic kernel, so these rows prove the decode hot path compiles
    # for v5e BEFORE a chip call ever runs continuous batching.
    from paddle_tpu.kernels.paged_attention import (
        kv_cache_write, paged_attention as paged)

    for tag, dt in (("f32", jnp.float32), ("bf16", bf)):
        Bd, Hh, Dd, Pp, psz, maxp = 8, 8, 128, 128, 16, 16
        qa = jax.ShapeDtypeStruct((Bd, Hh, Dd), dt)
        kpg = jax.ShapeDtypeStruct((Hh, Pp, psz, Dd), dt)
        lens = jax.ShapeDtypeStruct((Bd,), jnp.int32)
        pidx = jax.ShapeDtypeStruct((Bd, maxp), jnp.int32)
        aot(f"paged_attention_decode_{tag}",
            lambda q, k, v, ln, pi: paged(q, k, v, ln, pi,
                                          pages_per_compute_block=4),
            (qa, kpg, kpg, lens, pidx),
            B=Bd, heads=Hh, head_dim=Dd, pages=Pp, page_size=psz)
        knew = jax.ShapeDtypeStruct((Bd, 1, Hh, Dd), dt)
        aot(f"paged_kv_write_{tag}",
            lambda kp, vp, k, v, pi, pos, nv: kv_cache_write(
                kp, vp, k, v, pi, pos, nv),
            (kpg, kpg, knew, knew, pidx, lens, lens),
            B=Bd, heads=Hh, head_dim=Dd, pages=Pp, page_size=psz)

    # -- ragged paged attention (the ONE mixed prefill+decode kernel) --
    # generation's ragged engine runs its whole life through this op:
    # prefill chunks, decode rows and speculative-verify rows in one
    # [lanes, chunk] batch. Rows compile the custom Pallas kernel for
    # v5e in f32, bf16 AND the int8-quantized-KV variant (pages int8 +
    # fp32 scale planes), plus the quantized page write. Run just
    # these with PT_AOT_ONLY=ragged.
    from paddle_tpu.kernels.ragged_paged_attention import (
        quantized_kv_cache_write, ragged_paged_attention as ragged)

    Rl, Ck, Hh, Dd, Pp, psz, maxp = 8, 32, 8, 128, 128, 16, 16
    ivec = jax.ShapeDtypeStruct((Rl,), jnp.int32)
    pidx = jax.ShapeDtypeStruct((Rl, maxp), jnp.int32)
    for tag, dt in (("f32", jnp.float32), ("bf16", bf)):
        qa = jax.ShapeDtypeStruct((Rl, Ck, Hh, Dd), dt)
        kpg = jax.ShapeDtypeStruct((Hh, Pp, psz, Dd), dt)
        aot(f"ragged_attention_{tag}",
            lambda q, k, v, st, nv, pi: ragged(q, k, v, st, nv, pi),
            (qa, kpg, kpg, ivec, ivec, pidx),
            lanes=Rl, chunk=Ck, heads=Hh, head_dim=Dd, pages=Pp,
            page_size=psz)
    qbf = jax.ShapeDtypeStruct((Rl, Ck, Hh, Dd), bf)
    kq8 = jax.ShapeDtypeStruct((Hh, Pp, psz, Dd), jnp.int8)
    scl = jax.ShapeDtypeStruct((Hh, Pp, psz), jnp.float32)
    aot("ragged_attention_int8kv",
        lambda q, k, v, ks, vs, st, nv, pi: ragged(
            q, k, v, st, nv, pi, k_scales=ks, v_scales=vs),
        (qbf, kq8, kq8, scl, scl, ivec, ivec, pidx),
        lanes=Rl, chunk=Ck, heads=Hh, head_dim=Dd, pages=Pp,
        page_size=psz)
    knew = jax.ShapeDtypeStruct((Rl, Ck, Hh, Dd), jnp.float32)
    aot("ragged_kv_write_int8",
        lambda kp, vp, ks, vs, k, v, pi, pos, nv: quantized_kv_cache_write(
            kp, vp, ks, vs, k, v, pi, pos, nv),
        (kq8, kq8, scl, scl, knew, knew, pidx, ivec, ivec),
        lanes=Rl, chunk=Ck, heads=Hh, head_dim=Dd, pages=Pp,
        page_size=psz)

    # -- the MiMo cell's two attention shapes (PT_AOT_ONLY=mimo): 32
    # lanes x 16 tokens, 64 query heads, keys 192 wide in the split page
    # layout [128 + 64, 128] over values 128, bfloat16 pages of 128
    # tokens; full layers 4 KV heads over a 208-entry table, window
    # layers 8 KV heads, window 128, a sink, a ring of 3 under the
    # kernel's own name; and the page write into both layouts.
    from paddle_tpu.kernels.ragged_paged_attention import split_kv_cache_write

    mq = jax.ShapeDtypeStruct((32, 16, 64, 192), jnp.float32)
    mvec = jax.ShapeDtypeStruct((32,), jnp.int32)
    for tag, kvh, width, pages, window in (("full", 4, 208, 512, None),
                                           ("window", 8, 3, 97, 128)):
        mk = jax.ShapeDtypeStruct((kvh, pages, 192, 128), bf)
        mv = jax.ShapeDtypeStruct((kvh, pages, 128, 128), bf)
        mtab = jax.ShapeDtypeStruct((32, width), jnp.int32)
        msink = jax.ShapeDtypeStruct((64,), bf)
        aot(f"ragged_attention_mimo_{tag}_bf16",
            lambda q, k, v, st, nv, pi, sk, window=window: ragged(
                q, k, v, st, nv, pi, window=window,
                sink=sk if window else None, stored_products=True,
                lean_decode=True,
                name="ragged_paged_attention" + ("_window" if window
                                                 else "")),
            (mq, mk, mv, mvec, mvec, mtab, msink), group="mimo", lanes=32,
            chunk=16, heads=64, kv_heads=kvh, k_dim=192, v_dim=128,
            page_size=128, table=width, window=window)
        aot(f"ragged_kv_write_split_mimo_{tag}",
            lambda kp, vp, k, v, pi, pos, nv, window=window:
            split_kv_cache_write(kp, vp, k, v, pi, pos, nv,
                                 ring=bool(window)),
            (mk, mv, jax.ShapeDtypeStruct((32, 16, kvh, 192), jnp.float32),
             jax.ShapeDtypeStruct((32, 16, kvh, 128), jnp.float32), mtab,
             mvec, mvec), group="mimo", kv_heads=kvh, page_size=128)

    # -- quantized weight matmul (the inference serving path) ----------
    # paddle_tpu.quantize rewrites every matmul/fc weight onto these
    # kernels at load; the rows compile the custom Pallas lowering
    # (dequantize-in-registers, scales streamed as [1, bn] blocks) for
    # v5e in all three weight formats at a GPT-shaped [M, K] x [K, N].
    # Run just these with PT_AOT_ONLY=quant.
    from paddle_tpu.kernels.quant_matmul import _quant_matmul_pallas

    Mq, Kq, Nq = 256, 2048, 2048
    xq = jax.ShapeDtypeStruct((Mq, Kq), bf)
    for qtag, qdt, sshape in (
            ("int8", jnp.int8, (Nq,)),
            ("int8_block", jnp.int8, (Kq // 256, Nq)),
            ("fp8", jnp.float8_e4m3fn, (Nq,))):
        wq8 = jax.ShapeDtypeStruct((Kq, Nq), qdt)
        sq = jax.ShapeDtypeStruct(sshape, jnp.float32)
        aot(f"quant_matmul_{qtag}",
            lambda x, w, s, m=qtag: _quant_matmul_pallas(
                x, w, s, m, 256, interpret=False),
            (xq, wq8, sq), group="quant", M=Mq, K=Kq, N=Nq, mode=qtag)

    # -- fused optimizer: ONE Pallas pass per parameter ----------------
    # The whole m/v/param Adam update (bias correction + folded
    # global-norm clip scale) compiles as one Mosaic kernel over
    # donated buffers — for a GPT-scale [4096, 1024] parameter panel in
    # f32 AND the bf16-param/f32-moment mixed-precision form. Run just
    # these with PT_AOT_ONLY=fused_optim.
    from paddle_tpu.kernels.fused_optim import fused_adam_update

    scalar = jax.ShapeDtypeStruct((1,), jnp.float32)
    for tag, dt in (("f32", jnp.float32), ("bf16", bf)):
        pshape = jax.ShapeDtypeStruct((4096, 1024), dt)
        mshape = jax.ShapeDtypeStruct((4096, 1024), jnp.float32)
        aot(f"fused_adam_{tag}",
            lambda p, g, m, v, lr, b1p, b2p, c: fused_adam_update(
                p, g, m, v, lr, b1p, b2p, beta1=0.9, beta2=0.999,
                epsilon=1e-8, clip_scale=c),
            (pshape, pshape, mshape, mshape, scalar, scalar, scalar,
             scalar),
            group="fused_optim", shape=[4096, 1024])

    # -- chip_smoke.py's shapes (PT_AOT_ONLY=chip_smoke) ----------------
    # what the chip check dispatches, at the shapes it dispatches them:
    # leg A's head_dim-64 flash with a padding mask at batch 16, the
    # 8192-row layer_norm / softmax_xent, Adam over the embedding
    # table, and leg B's geometry — 16 heads x 128, chunk 16, fp32
    # pages of 16 x 128 over 64-page block tables — both as the bare
    # kernel and as the engine's ragged step program (2 of 24 layers:
    # depth repeats the same kernels).
    qa16 = jax.ShapeDtypeStruct((16, 12, 512, 64), bf)
    m16 = jax.ShapeDtypeStruct((16, 512), jnp.bool_)
    aot("chip_smoke_flash_train_mask_b16",
        jax.grad(lambda q, k, v, m: fa.flash_attention(
            q, k, v, causal=False, mask=m).astype(jnp.float32).sum(),
            argnums=(0, 1, 2)),
        (qa16, qa16, qa16, m16), group="chip_smoke", S=512, head_dim=64)
    x8k = jax.ShapeDtypeStruct((8192, 768), jnp.float32)
    aot("chip_smoke_layer_norm_train_8192",
        jax.grad(lambda x, g, b: fused_layer_norm(
            x, g, b, 1e-5).sum(), argnums=(0, 1, 2)), (x8k, g, g),
        group="chip_smoke")
    s8k = jax.ShapeDtypeStruct((8192, 30522), jnp.float32)
    l8k = jax.ShapeDtypeStruct((8192,), jnp.int32)
    aot("chip_smoke_softmax_xent_train_8192",
        jax.grad(lambda s, lbl: fused_softmax_xent(s, lbl).sum()),
        (s8k, l8k), group="chip_smoke")
    for pshape in ((30522, 768), (768,)):
        pa_ = jax.ShapeDtypeStruct(pshape, jnp.float32)
        aot("chip_smoke_fused_adam_" + "x".join(map(str, pshape)),
            lambda p, g, m, v, lr, b1p, b2p: fused_adam_update(
                p, g, m, v, lr, b1p, b2p, beta1=0.9, beta2=0.999,
                epsilon=1e-8),
            (pa_, pa_, pa_, pa_, scalar, scalar, scalar),
            group="chip_smoke", shape=list(pshape))
    Rl, Ck, Hh, Dd, Pp, psz, maxp = 8, 16, 16, 128, 512, 16, 64
    ivec = jax.ShapeDtypeStruct((Rl,), jnp.int32)
    pidx = jax.ShapeDtypeStruct((Rl, maxp), jnp.int32)
    qa = jax.ShapeDtypeStruct((Rl, Ck, Hh, Dd), jnp.float32)
    kpg = jax.ShapeDtypeStruct((Hh, Pp, psz, Dd), jnp.float32)

    def _write_attend(kp, vp, q, kn, vn, pi, st, nv):
        kp, vp = kv_cache_write(kp, vp, kn, vn, pi, st, nv)
        return ragged(q, kp, vp, st, nv, pi), kp, vp

    aot("chip_smoke_ragged_write_attend_f32", _write_attend,
        (kpg, kpg, qa, qa, qa, pidx, ivec, ivec), group="chip_smoke",
        lanes=Rl, chunk=Ck, heads=Hh, head_dim=Dd, pages=Pp,
        page_size=psz, max_pages=maxp)

    def ragged_step_program(cfg, lanes, chunk, pages, page_size, table):
        """A GPT's ragged step as the engine builds it (zeros in the
        scope: only shapes and types reach the compiler)."""
        import paddle_tpu as fluid
        from paddle_tpu.generation.model import (
            CacheGeometry, build_lm_program, build_ragged_step_program)

        geom = CacheGeometry(num_pages=pages, page_size=page_size,
                             max_pages_per_seq=table)
        main, _startup, _f, _o = build_lm_program(cfg, 32)
        prog, fetches = build_ragged_step_program(cfg, geom, chunk)
        feed = {"gen_tokens": np.zeros((lanes, chunk), np.int64),
                "gen_pos_ids": np.zeros((lanes, chunk), np.int64),
                "gen_positions": np.zeros(lanes, np.int64),
                "gen_num_valid": np.zeros(lanes, np.int32),
                "gen_block_tables": np.zeros((lanes, table), np.int32)}
        scope = fluid.Scope()
        for v in main.global_block().all_parameters():
            scope.set_var(v.name, np.zeros(v.shape, np.float32))
        # the page pools are state of the step, donated and rewritten
        # in place: they come from the scope like the weights
        for li in range(cfg.num_layers):
            for kv in "kv":
                scope.set_var(f"gen_{kv}_pages_{li}", np.zeros(
                    (cfg.num_heads, pages, page_size,
                     cfg.hidden_size // cfg.num_heads), np.float32))
        with fluid.scope_guard(scope):
            exe = fluid.Executor(fluid.TPUPlace())
            return exe.aot_compile(prog, feed, fetches, scope=scope,
                                   devices=[dev])

    def smoke_step():
        from paddle_tpu.models.gpt import GPTConfig

        cfg = GPTConfig.gpt3_1p3b()
        cfg.num_layers = 2
        return ragged_step_program(cfg, Rl, Ck, Pp, psz, maxp)

    record("chip_smoke_ragged_step_program_gpt3xl_2layer", smoke_step,
           group="chip_smoke")

    # the two GPT cells' own step (PT_AOT_ONLY=gpt_serve): gpt3_xl_serve
    # as benchmark/ runs it, all 24 layers. A PR that should not reach the
    # dense cells shows this row's and the headline's hlo_sha256 equal to
    # the parent's (PT_AOT_PARENT).
    def gpt_serve_step():
        bench = os.path.join(HERE, "benchmark")
        with open(os.path.join(bench, "configs", "gpt3_xl_serve.json")) as f:
            cfg = json.load(f)
        eng = cfg["engine"]
        return ragged_step_program(
            _load(os.path.join(bench, "models", "gpt_program.py"))
            .gpt_config(cfg), eng["lanes"], eng["chunk_tokens"],
            eng["num_pages"], eng["page_size"],
            cfg["max_position"] // eng["page_size"])

    record("gpt3_xl_serve_step_program_24layer", gpt_serve_step,
           group="gpt_serve")

    # -- the hybrid serving cell's step program at its own shapes
    # (PT_AOT_ONLY=hybrid): granite4_h_small_serve as benchmark/ runs it:
    # 10 layers, 36 of 72 experts, bfloat16 weights (zeros: only shapes
    # and types reach the compiler), 32 lanes x 16 tokens, float32
    # recurrent state, bfloat16 pages. A v5e compile failure or a step
    # that does not fit 16 GB shows here, before a chip call.
    def hybrid_step_program():
        import ml_dtypes

        import paddle_tpu as fluid
        from paddle_tpu.generation.model import (
            CacheGeometry, build_hybrid_step_program)

        bench = os.path.join(HERE, "benchmark")
        with open(os.path.join(bench, "configs",
                               "granite4_h_small_serve.json")) as f:
            cfg = json.load(f)
        ref = _load(os.path.join(bench, "models",
                                 "granite_hybrid_reference.py"))
        hcfg = _load(os.path.join(
            bench, "models", "granite_hybrid_program.py")).hybrid_config(cfg)
        eng = cfg["engine"]
        lanes, chunk = eng["lanes"], eng["chunk_tokens"]
        maxp = -(-eng["max_position"] // eng["page_size"])
        geom = CacheGeometry(num_pages=eng["num_pages"],
                             page_size=eng["page_size"],
                             max_pages_per_seq=maxp)
        prog, fetches = build_hybrid_step_program(hcfg, geom, chunk,
                                                  eng["kv_dtype"])
        feed = {"gen_tokens": np.zeros((lanes, chunk), np.int64),
                "gen_pos_ids": np.zeros((lanes, chunk), np.int64),
                "gen_positions": np.zeros(lanes, np.int64),
                "gen_num_valid": np.zeros(lanes, np.int32),
                "gen_block_tables": np.zeros((lanes, maxp), np.int32)}
        for name, (shape, dt) in hcfg.state_shapes(lanes).items():
            feed[name] = np.zeros(shape, dt)
        scope = fluid.Scope()
        for j in range(len(hcfg.attention_layers)):
            for kv in "kv":         # state of the step, as the weights
                scope.set_var(f"gen_{kv}_pages_{j}", np.zeros(
                    (hcfg.num_kv_heads, geom.num_pages, geom.page_size,
                     hcfg.head_dim), ml_dtypes.bfloat16))
        for name, shape, _init in ref.spec(cfg):
            scope.set_var(name, np.zeros(shape, ml_dtypes.bfloat16))
        exe = fluid.Executor(fluid.TPUPlace())
        return exe.aot_compile(prog, feed, fetches, scope=scope,
                               devices=[dev])

    record("hybrid_step_program_granite4_h_small_10layer",
           hybrid_step_program, group="hybrid")
    # -- the MiMo serving cell's step program at its own shapes
    # (PT_AOT_ONLY=mimo): mimo_v2_5_serve as benchmark/ runs it: 7
    # layers, 16 of 256 experts, bfloat16 weights and pages of both kinds
    # (zeros: only shapes and types reach the compiler), 32 lanes x 16
    # tokens. A v5e compile failure, a pool that is copied or padded, or
    # a step that does not fit 16 GB shows here, before a chip call.
    def mimo_step_program():
        import ml_dtypes

        import paddle_tpu as fluid
        from paddle_tpu.generation.kvcache import (key_page_shape,
                                                   window_ring_pages)
        from paddle_tpu.generation.model import (CacheGeometry,
                                                 build_mimo_step_program)

        bench = os.path.join(HERE, "benchmark")
        with open(os.path.join(bench, "configs",
                               "mimo_v2_5_serve.json")) as f:
            cfg = json.load(f)
        ref = _load(os.path.join(bench, "models", "mimo_reference.py"))
        mcfg = _load(os.path.join(
            bench, "models", "mimo_program.py")).mimo_config(cfg)
        eng = cfg["engine"]
        lanes, chunk, ps = eng["lanes"], eng["chunk_tokens"], eng["page_size"]
        maxp = -(-eng["max_position"] // ps)
        ring = window_ring_pages(mcfg.window, chunk, ps)
        geom = CacheGeometry(num_pages=eng["num_pages"], page_size=ps,
                             max_pages_per_seq=maxp,
                             window_num_pages=lanes * ring + 1,
                             window_pages_per_seq=ring)
        prog, fetches = build_mimo_step_program(mcfg, geom, chunk,
                                                eng["kv_dtype"])
        feed = {"gen_tokens": np.zeros((lanes, chunk), np.int64),
                "gen_pos_ids": np.zeros((lanes, chunk), np.int64),
                "gen_positions": np.zeros(lanes, np.int64),
                "gen_num_valid": np.zeros(lanes, np.int32),
                "gen_block_tables": np.zeros((lanes, maxp), np.int32),
                "gen_block_tables_window": np.zeros((lanes, ring), np.int32)}
        for name, (shape, dt) in mcfg.state_shapes(lanes).items():
            feed[name] = np.zeros(shape, dt)
        scope = fluid.Scope()
        k_page = key_page_shape(ps, mcfg.k_dim, mcfg.v_dim)
        for kind, pre, pages in (("full", "", geom.num_pages),
                                 ("window", "w", geom.window_num_pages)):
            kvh = mcfg.kv_heads_of(kind)
            for j in range(len(mcfg.layers_of(kind))):
                scope.set_var(f"gen_{pre}k_pages_{j}", np.zeros(
                    (kvh, pages) + k_page, ml_dtypes.bfloat16))
                scope.set_var(f"gen_{pre}v_pages_{j}", np.zeros(
                    (kvh, pages, ps, mcfg.v_dim), ml_dtypes.bfloat16))
        for name, shape, _init in ref.spec(cfg):
            scope.set_var(name, np.zeros(shape, ml_dtypes.bfloat16))
        exe = fluid.Executor(fluid.TPUPlace())
        return exe.aot_compile(prog, feed, fetches, scope=scope,
                               devices=[dev])

    record("mimo_step_program_mimo_v2_5_7layer", mimo_step_program,
           group="mimo")
    from paddle_tpu.kernels.mamba2_state import state_step

    f32 = jnp.float32
    aot("hybrid_mamba2_state_step_32x128x64x128_t16", state_step,
        (jax.ShapeDtypeStruct((32, 128, 64, 128), f32),
         jax.ShapeDtypeStruct((32, 1, 16, 128), f32),
         jax.ShapeDtypeStruct((32, 1, 16, 128), f32),
         jax.ShapeDtypeStruct((32, 16, 8192), f32),
         jax.ShapeDtypeStruct((32, 128), f32)),
        group="hybrid", lanes=32, heads=128, head_dim=64, state=128,
        chunk=16)

    # -- the expert kernel at both cells' shapes (PT_AOT_ONLY=moe_ffn; it
    # also answers hybrid / mimo): 512 window rows, the held experts as
    # stored. The compiled module must hold the weights once: arguments
    # are the stated bytes, and no copy, transpose or other op yields an
    # array of a weight's size (the kernel reads them where they are).
    def moe_ffn_row(name, group, held, d, f, top_k):
        T = 512
        pairs = T * top_k
        args = (jax.ShapeDtypeStruct((T, d), f32),
                jax.ShapeDtypeStruct((held, d, 2 * f), bf),
                jax.ShapeDtypeStruct((held, f, d), bf),
                jax.ShapeDtypeStruct((pairs,), jnp.int32),
                jax.ShapeDtypeStruct((pairs,), f32),
                jax.ShapeDtypeStruct((held,), jnp.int32))
        stated = (T * d * 4 + held * 3 * d * f * 2 + pairs * 8 + held * 4)

        def compile_fn():
            from paddle_tpu.kernels import moe_ffn

            assert moe_ffn.fits(T, d, f, bf)
            compiled = jax.jit(moe_ffn.grouped_ffn,
                               in_shardings=(R,) * 6).lower(*args).compile()
            text = compiled.as_text()
            assert "moe_grouped_ffn" in text
            for shape in (f"bf16[{held},{d},{2 * f}]",
                          f"bf16[{held},{f},{d}]"):
                made = [ln for ln in text.splitlines()
                        if f" = {shape}" in ln and " parameter(" not in ln]
                assert not made, f"a second {shape}: {made[0][:200]}"
            ma = compiled.memory_analysis()
            # (the small arrays are padded to whole tiles: 4 KiB of room)
            assert 0 <= ma.argument_size_in_bytes - stated < 4096, (
                ma.argument_size_in_bytes, stated)
            assert ma.temp_size_in_bytes < 64 * 1024 * 1024, \
                ma.temp_size_in_bytes
            return compiled
        return record(name, compile_fn, group, held=held, d=d, f=f,
                      top_k=top_k, stated_arg_bytes=stated)

    moe_ffn_row("moe_ffn_hybrid_36x4096x768_top10", "hybrid", 36, 4096, 768,
                10)
    moe_ffn_row("moe_ffn_mimo_16x4096x2048_top8", "mimo", 16, 4096, 2048, 8)

    # -- the headline: the train cell's own step at its REAL shapes ----
    # params + adam state from the startup program, full fwd+bwd+
    # update through the Executor's own compile path (what
    # Executor.run binds and donates), not a private jit. Also the
    # chipless answer to "does batch 24 seq 512 fit 16 GB of v5e HBM".
    if os.environ.get("PT_AOT_HEADLINE", "1") == "1":
        main_prog, startup, loss_var, feed = headline_step()

        def headline():
            import paddle_tpu as fluid

            scope = fluid.Scope()
            with fluid.scope_guard(scope):
                exe = fluid.Executor(fluid.TPUPlace())
                exe.run(startup)
                return exe.aot_compile(main_prog, feed, [loss_var],
                                       scope=scope, devices=[dev])

        batch, seq = feed["src_ids"].shape
        record("stage_headline_bert_base_s512_flash", headline,
               config="bert_base_pretrain", traffic="pretrain_s512",
               batch=batch, seq=seq)

    # -- MULTICHIP: distributed paths compiled for a real v5e x4 -------
    # Executor.aot_compile re-lays the CompiledProgram's mesh onto the
    # topology devices: ring attention's ppermutes, the dp x pp GPipe
    # schedule, and plain dp all compile through the real TPU SPMD
    # partitioner (the driver's CPU dryrun proves execution semantics;
    # this proves the target-silicon compile).
    if os.environ.get("PT_AOT_MULTICHIP", "0") == "1":
        import paddle_tpu as fluid
        from paddle_tpu.models import BertConfig, build_bert_pretrain
        from paddle_tpu.models.bert import synthetic_batch
        from paddle_tpu.models.gpt import GPTConfig, build_gpt_lm

        devs4 = list(topo.devices)
        rng = np.random.RandomState(0)

        def mc(name, cp_fn, prog_pack, feed, group=None, **meta):
            if only and only not in name and only != group:
                return
            if group:
                meta["group"] = group
            main_prog, startup, loss = prog_pack
            t0 = time.time()
            try:
                scope = fluid.Scope()
                with fluid.scope_guard(scope):
                    exe = fluid.Executor(fluid.TPUPlace())
                    exe.run(startup)
                    cp = cp_fn(main_prog)
                    compiled = exe.aot_compile(cp, feed, [loss],
                                               scope=scope, devices=devs4)
                txt = compiled.as_text()
                ma = compiled.memory_analysis()
                row(name, ok=True, compile_s=round(time.time() - t0, 1),
                    mosaic_calls=txt.count(MOSAIC_TARGET),
                    collective_permute=txt.count("collective-permute"),
                    all_reduce=txt.count("all-reduce"),
                    all_gather=txt.count("all-gather"),
                    all_to_all=txt.count("all-to-all"),
                    per_dev_bytes=int(ma.argument_size_in_bytes
                                      + ma.output_size_in_bytes
                                      + ma.temp_size_in_bytes), **meta)
            except Exception as e:  # noqa: BLE001
                row(name, ok=False, compile_s=round(time.time() - t0, 1),
                    error=f"{type(e).__name__}: {e}"[:400], **meta)

        # (a) ring-attention sp4 GPT S=2048 train step
        gcfg = GPTConfig.tiny()
        gcfg.use_flash_attention = True
        gcfg.max_position = 2048
        gmain, gstart, _, gf = build_gpt_lm(
            gcfg, 2048, optimizer=fluid.optimizer.Adam(1e-3))
        gfeed = {"tokens": rng.randint(0, gcfg.vocab_size,
                                       (2, 2048)).astype("int64"),
                 "labels": rng.randint(0, gcfg.vocab_size,
                                       (2, 2048)).astype("int64")}
        mc("multichip_sp4_ring_attention_gpt_s2048",
           lambda m: fluid.CompiledProgram(m).with_sequence_parallel(
               sp=4, places=[fluid.TPUPlace(i) for i in range(4)]),
           (gmain, gstart, gf["loss"]), gfeed, mesh="sp4")

        # (b) dp2 x pp2 GPipe BERT through the user pipeline stack
        bcfg = BertConfig.tiny()
        bcfg.num_layers = 2
        bcfg.hidden_dropout = bcfg.attention_dropout = 0.0
        pmain, pstart, _, pf = build_bert_pretrain(bcfg, 64,
                                                   optimizer=None)
        with fluid.program_guard(pmain, pstart):
            fluid.optimizer.PipelineOptimizer(
                fluid.optimizer.SGD(0.05),
                cut_list=pf["encoder_outputs"][:-1],
                num_microbatches=4).minimize(pf["loss"])
        pfeed = synthetic_batch(rng, 8, 64, bcfg.vocab_size)
        mc("multichip_dp2xpp2_gpipe_bert",
           lambda m: fluid.CompiledProgram(m).with_pipeline(dp=2),
           (pmain, pstart, pf["loss"]), pfeed, mesh="dp2 x pp2")

        # (c) plain dp4 BERT (the fleet data-parallel form)
        dmain, dstart, _, df = build_bert_pretrain(
            BertConfig.tiny(), 128, optimizer=fluid.optimizer.Adam(1e-4))
        dfeed = synthetic_batch(rng, 8, 128, 1024)
        mc("multichip_dp4_bert",
           lambda m: fluid.CompiledProgram(m).with_data_parallel(
               loss_name=df["loss"].name,
               places=[fluid.TPUPlace(i) for i in range(4)]),
           (dmain, dstart, df["loss"]), dfeed, mesh="dp4")

        # (d) dp2 x ep2 switch-MoE GPT (expert parallelism; alltoall
        # dispatch) — completes the axis coverage: dp/sp/pp above
        ecfg = GPTConfig.tiny()
        ecfg.moe_every = 2
        ecfg.moe_experts = 4
        emain, estart, _, ef = build_gpt_lm(
            ecfg, 128, optimizer=fluid.optimizer.Adam(1e-3))
        efeed = {"tokens": rng.randint(0, ecfg.vocab_size,
                                       (8, 128)).astype("int64"),
                 "labels": rng.randint(0, ecfg.vocab_size,
                                       (8, 128)).astype("int64")}
        mc("multichip_dp2xep2_moe_gpt",
           lambda m: fluid.CompiledProgram(m).with_expert_parallel(
               ep=2, dp=2, dispatch="alltoall",
               places=[fluid.TPUPlace(i) for i in range(4)]),
           (emain, estart, ef["loss"]), efeed, mesh="dp2 x ep2")

        # (e) LONG CONTEXT: sp4 ring attention at S=8192 — each local
        # S/sp=2048 shard sits at the panel/streaming boundary, so the
        # ring rotation composes with the FA-2 KV-streaming kernels;
        # this is the long-context flagship compiling for real silicon
        lcfg = GPTConfig.tiny()
        lcfg.use_flash_attention = True
        lcfg.max_position = 8192
        lmain, lstart, _, lf = build_gpt_lm(
            lcfg, 8192, optimizer=fluid.optimizer.Adam(1e-3))
        lfeed = {"tokens": rng.randint(0, lcfg.vocab_size,
                                       (1, 8192)).astype("int64"),
                 "labels": rng.randint(0, lcfg.vocab_size,
                                       (1, 8192)).astype("int64")}
        mc("multichip_sp4_ring_longctx_gpt_s8192",
           lambda m: fluid.CompiledProgram(m).with_sequence_parallel(
               sp=4, places=[fluid.TPUPlace(i) for i in range(4)]),
           (lmain, lstart, lf["loss"]), lfeed, mesh="sp4", seq=8192)

        # (f) PARTITIONER: the logical-axis-rules path (paddle_tpu.
        # partition) — the same GPT whose ParamAttr tags drive the CPU
        # dp/tp parity tests compiles its SHARDED TRAIN step for real
        # v5e silicon through one rules table (dp2 x tp2 + ZeRO-1
        # optimizer state), proving the config surface reaches the
        # target SPMD partitioner, not just the CPU emulation
        pt = fluid.partition
        pcfg = GPTConfig.tiny()
        pmain2, pstart2, _, pf2 = build_gpt_lm(
            pcfg, 128, optimizer=fluid.optimizer.Adam(1e-3))
        pfeed2 = {"tokens": rng.randint(0, pcfg.vocab_size,
                                        (8, 128)).astype("int64"),
                  "labels": rng.randint(0, pcfg.vocab_size,
                                        (8, 128)).astype("int64")}
        mc("multichip_partition_dp2xtp2_zero1_gpt_train",
           lambda m: fluid.CompiledProgram(m).with_partitioning(
               pt.PartitionConfig(mesh_axes={"dp": 2, "tp": 2}, zero=1)),
           (pmain2, pstart2, pf2["loss"]), pfeed2,
           mesh="dp2 x tp2 zero1")

        # (h) COLLECTIVES: the bucketed and int8-quantized DP gradient
        # all-reduce (parallel/collectives.py) — the planner's
        # shard_map step with explicit per-bucket collectives compiles
        # through the real TPU SPMD partitioner for v5e, so a chip
        # call never burns on a partial-manual lowering the CPU
        # emulation can't see. The HLO collective counts prove the
        # bucket reduces are real ops: >= 2 all-reduces for the
        # bucketed row, all-to-all + all-gather for the int8 exchange.
        for ctag, cquant in (("bucketed", "none"), ("int8", "int8")):
            ccfg = GPTConfig.tiny()
            cmain, cstart, _, cf = build_gpt_lm(
                ccfg, 128, optimizer=fluid.optimizer.Adam(1e-3))
            cfeed = {"tokens": rng.randint(0, ccfg.vocab_size,
                                           (8, 128)).astype("int64"),
                     "labels": rng.randint(0, ccfg.vocab_size,
                                           (8, 128)).astype("int64")}
            mc(f"multichip_collective_dp4_{ctag}_gpt_train",
               lambda m, q=cquant: fluid.CompiledProgram(m)
               .with_partitioning(pt.PartitionConfig(
                   mesh_axes={"dp": 4}, collective_bucket_mb=0.25,
                   collective_quantization=q)),
               (cmain, cstart, cf["loss"]), cfeed,
               mesh=f"dp4 collective {ctag}")

        # (i) FUSED OPTIMIZER under dp4 + ZeRO-1: the one-pass Pallas
        # Adam composes with the partitioner — sharded moments feed
        # the Mosaic kernel through the same GSPMD optimizer tail the
        # unfused chain used, compiled for real v5e silicon. Also
        # answers PT_AOT_ONLY=fused_optim.
        _fuse_old = fluid.get_flags(["optimizer_fuse"])
        fluid.set_flags({"optimizer_fuse": "on"})
        fcfg = GPTConfig.tiny()
        fmain, fstart, _, ff = build_gpt_lm(
            fcfg, 128, optimizer=fluid.optimizer.Adam(1e-3))
        ffeed = {"tokens": rng.randint(0, fcfg.vocab_size,
                                       (8, 128)).astype("int64"),
                 "labels": rng.randint(0, fcfg.vocab_size,
                                       (8, 128)).astype("int64")}
        fused_ops = sum(op.type == "fused_adam"
                        for op in fmain.global_block().ops)
        mc("multichip_fused_adam_dp4_zero1",
           lambda m: fluid.CompiledProgram(m).with_partitioning(
               pt.PartitionConfig(mesh_axes={"dp": 4}, zero=1)),
           (fmain, fstart, ff["loss"]), ffeed, group="fused_optim",
           mesh="dp4 zero1", fused_adam_ops=fused_ops)
        # restore the OPERATOR's value, not a literal: an env-driven
        # FLAGS_optimizer_fuse=on sweep must keep fusing after this row
        fluid.set_flags(_fuse_old)

        # (j) chip_smoke.py leg C at its real size — the free pre-flight
        # for the four-chip call (PT_AOT_ONLY=chip_smoke_c): leg A's
        # BERT-base program dp4 at global batch 64, and GPT-small
        # seq 512 under the dp2 x tp2 partitioning
        import chip_smoke

        c1main, c1start, c1loss = chip_smoke._bert_train_program(
            BertConfig.base(), 512)
        mc("chip_smoke_c1_bert_base_dp4_b64",
           lambda m: fluid.CompiledProgram(m).with_data_parallel(
               loss_name=c1loss.name,
               places=[fluid.TPUPlace(i) for i in range(4)]),
           (c1main, c1start, c1loss),
           synthetic_batch(rng, 64, 512, 30522), group="chip_smoke_c",
           mesh="dp4")
        c2cfg = GPTConfig.small()
        c2main, c2start, _, c2f = build_gpt_lm(
            c2cfg, 512, optimizer=fluid.optimizer.Adam(1e-4))
        c2toks = rng.randint(0, c2cfg.vocab_size, (8, 512)).astype("int64")
        mc("chip_smoke_c2_gpt_small_dp2xtp2",
           lambda m: fluid.CompiledProgram(m).with_partitioning(
               pt.PartitionConfig(mesh_axes={"dp": 2, "tp": 2})),
           (c2main, c2start, c2f["loss"]),
           {"tokens": c2toks, "labels": np.roll(c2toks, -1, 1)},
           group="chip_smoke_c", mesh="dp2 x tp2")

        # (g) the TP-predict executable (the ServingEngine worker form):
        # forward-only logits over a tp4 mesh from the same tags
        imain, istart, _, if_ = build_gpt_lm(pcfg, 128, is_test=True)
        ifeed = {"tokens": rng.randint(0, pcfg.vocab_size,
                                       (4, 128)).astype("int64"),
                 "labels": rng.randint(0, pcfg.vocab_size,
                                       (4, 128)).astype("int64")}
        mc("multichip_partition_tp4_gpt_predict",
           lambda m: fluid.CompiledProgram(m).with_partitioning(
               pt.PartitionConfig(mesh_axes={"tp": 4})),
           (imain, istart, if_["logits"]), ifeed, mesh="tp4")

    # PT_AOT_PARENT=<the AOT_TPU_CHECK.json this tool wrote in the parent's
    # tree, when that lay at this path>: each row says whether its
    # optimized HLO is the parent's
    parent = os.environ.get("PT_AOT_PARENT")
    if parent:
        with open(parent) as f:
            theirs = {r["name"]: r.get("hlo_sha256")
                      for r in json.load(f)["rows"]}
        for r in results["rows"]:
            if r.get("hlo_sha256") and theirs.get(r["name"]):
                r["hlo_equals_parent"] = \
                    r["hlo_sha256"] == theirs[r["name"]]

    # merge-by-name into the existing archive: different env
    # selections (kernels-only / stages / multichip) must accumulate,
    # not erase each other's evidence (round-5 review finding)
    merged = dict(results)
    if os.path.exists(OUT):
        try:
            with open(OUT) as f:
                prior = json.load(f)
            have = {r["name"] for r in merged["rows"]}
            merged["rows"] = [r for r in prior.get("rows", [])
                              if r["name"] not in have] + merged["rows"]
        except (json.JSONDecodeError, OSError):
            pass
    with open(OUT, "w") as f:
        json.dump(merged, f, indent=1)
    bad = [r for r in results["rows"] if not r.get("ok")]
    print(f"AOT check: {len(results['rows']) - len(bad)}/"
          f"{len(results['rows'])} compiled for {results['target']}")
    return 1 if bad else 0


def main():
    if os.environ.get("PT_AOT_CHILD") == "1":
        return _child()
    env = dict(os.environ)
    env.update(_CHILD_ENV)
    env["PT_AOT_CHILD"] = "1"
    proc = subprocess.run([sys.executable, os.path.abspath(__file__)],
                          env=env, timeout=5400)
    return proc.returncode


if __name__ == "__main__":
    sys.exit(main())
