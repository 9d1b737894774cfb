"""paddle_tpu.quantize: int8/fp8 weight matmul with scale tracking,
checkpoint load -> one-shot rewrite -> quantized serving (ISSUE 15).

Correctness anchors:
  * kernel — quantized_matmul (interpret-mode Pallas) vs the pure-JAX
    reference, all three weight formats, tile-unaligned shapes;
  * rewrite — idempotent, per-var skip reasons, fp32 originals GONE
    from the scope, strict proglint on the rewritten program;
  * serving — token agreement through churn/eviction/resume on the
    ragged engine with int8 weights + int8 KV pages together (the
    fully-quantized config), checkpoint load -> quantize -> serve;
  * TP — quantized predict parity on a clone-shared mesh (the int8
    weight + scale vars inherit the partition tags).
"""

import os

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import quantize
from paddle_tpu.kernels import quant_matmul as qm

# -- kernel vs oracle --------------------------------------------------------


@pytest.mark.parametrize("mode,tol", [("int8", 0.02), ("int8_block", 0.02),
                                      ("fp8", 0.08)])
def test_quantized_matmul_matches_fp32(mode, tol):
    """Quantize -> matmul stays within the format's error budget of
    the fp32 product, on a deliberately tile-unaligned shape."""
    import jax.numpy as jnp

    rng = np.random.RandomState(0)
    w = rng.randn(70, 33).astype("float32")
    x = rng.randn(5, 70).astype("float32")
    q, s = qm.quantize_weight(w, mode, block=32)
    assert q.shape == w.shape
    assert s.shape == ((3, 33) if mode == "int8_block" else (33,))
    out = np.asarray(qm.quantized_matmul(jnp.asarray(x), q, s, mode=mode,
                                         block=32), np.float32)
    ref = x @ w
    rel = np.abs(out - ref).max() / np.abs(ref).max()
    assert rel < tol, (mode, rel)
    # round trip: dequantize within one quantization step per element
    wd = np.asarray(qm.dequantize_weight(q, s, mode, 32), np.float32)
    if mode != "fp8":
        step = np.asarray(s).max() / 2 + 1e-6
        assert np.abs(wd - w).max() <= 2 * step


@pytest.mark.parametrize("mode", ["int8", "int8_block", "fp8"])
@pytest.mark.parametrize("shape", [(5, 70, 33), (16, 256, 128),
                                   (3, 130, 200)])
def test_interpret_pallas_matches_reference(monkeypatch, mode, shape):
    """The real kernel body (interpreter mode) against the reference
    lowering — including shapes that exercise every pad path (M, K
    and N all tile-unaligned)."""
    import jax.numpy as jnp

    M, K, N = shape
    rng = np.random.RandomState(1)
    w = rng.randn(K, N).astype("float32") * 0.3
    x = jnp.asarray(rng.randn(M, K).astype("float32"))
    blk = 64
    q, s = qm.quantize_weight(w, mode, block=blk)
    pal = np.asarray(qm._quant_matmul_pallas(x, q, s, mode, blk,
                                             interpret=True), np.float32)
    ref = np.asarray(qm._reference_quant_matmul(x, q, s, mode, blk),
                     np.float32)
    # identical math modulo scale-application order (per-channel
    # scales factor out of the contraction)
    assert np.abs(pal - ref).max() <= 2e-2 * max(np.abs(ref).max(), 1.0)


def test_quantize_weight_validates():
    with pytest.raises(ValueError, match="mode"):
        qm.quantize_weight(np.zeros((4, 4), "float32"), "int4")
    with pytest.raises(ValueError, match="2-D"):
        qm.quantize_weight(np.zeros((4,), "float32"))
    with pytest.raises(ValueError, match="mode"):
        qm.quantized_matmul(np.zeros((2, 4), "float32"),
                            np.zeros((4, 3), "int8"),
                            np.ones((3,), "float32"), mode="nope")
    # all-zero columns quantize to scale 1.0, never a divide-by-zero
    q, s = qm.quantize_weight(np.zeros((8, 3), "float32"), "int8")
    assert np.all(np.asarray(s) == 1.0)


# -- the rewrite -------------------------------------------------------------


def _mlp_program():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = fluid.layers.data("x", [16])
        h = fluid.layers.fc(x, 32, act="relu")
        out = fluid.layers.fc(h, 8, act="softmax")
    return main, startup, out


@pytest.mark.parametrize("mode", ["int8", "int8_block", "fp8"])
def test_rewrite_quantizes_and_preserves_outputs(mode):
    main, startup, out = _mlp_program()
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.TPUPlace())
        exe.run(startup)
        feed = {"x": np.random.RandomState(0).rand(4, 16).astype("float32")}
        (ref,) = exe.run(main, feed=feed, fetch_list=[out])
        rep = quantize.rewrite_for_inference(main, scope, mode, block=16)
        (got,) = exe.run(main, feed=feed, fetch_list=[out])
    assert rep.n_quantized == 2
    assert rep.summary()["weight_bytes_ratio"] < 0.5
    # softmax outputs: absolute agreement is the meaningful check
    np.testing.assert_allclose(got, ref, atol=0.05)
    # the fp32 originals are GONE — scope and program both
    assert scope.find_var("fc_0.w_0") is None
    assert not main.global_block().has_var("fc_0.w_0")
    qv = main.global_block().var("fc_0.w_0.q")
    assert qv.dtype == ("float8_e4m3fn" if mode == "fp8" else "int8")
    types = [op.type for op in main.global_block().ops]
    assert "mul" not in types and types.count("quantized_fc") == 2


def test_rewrite_is_idempotent_and_shares_scope():
    """Second rewrite of the same program: no-op. Second PROGRAM over
    the same scope: repoints onto the already-quantized buffers
    without re-quantizing (the Predictor/GenerationEngine sharing
    contract)."""
    main, startup, out = _mlp_program()
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.TPUPlace())
        exe.run(startup)
        rep1 = quantize.rewrite_for_inference(main, scope, "int8")
        v1 = main.version
        rep2 = quantize.rewrite_for_inference(main, scope, "int8")
    assert rep1.n_quantized == 2 and rep2.n_quantized == 0
    assert main.version == v1  # idempotent: no version churn

    # a second program with the same weight names (the engine's decode
    # program pattern): scope conversion is a cache hit
    main2 = fluid.Program.from_dict(main.to_dict())
    gen0 = scope.generation
    rep3 = quantize.rewrite_for_inference(main2, scope, "int8")
    assert rep3.n_quantized == 0  # already quantized ops after round trip
    assert scope.generation == gen0


def test_rewrite_skip_reasons():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        ids = fluid.layers.data("ids", [4], dtype="int64")
        emb = fluid.layers.embedding(ids, size=[50, 16])
        h = fluid.layers.fc(emb, 16, num_flatten_dims=2)
        # a weight consumed by matmul AND elementwise_add: ineligible
        w = fluid.layers.create_parameter([16, 16], "float32",
                                          name="shared_w")
        mm = fluid.layers.matmul(h, w)
        out = fluid.layers.elementwise_add(mm, w)
        # a transposed weight operand: ineligible
        wt = fluid.layers.create_parameter([8, 16], "float32", name="wt")
        out2 = fluid.layers.matmul(h, wt, transpose_y=True)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.TPUPlace())
        exe.run(startup)
        rep = quantize.rewrite_for_inference(main, scope, "int8")
    reasons = rep.skip_reasons()
    assert "embedding_0.w_0" in reasons  # lookup_table-only consumer
    assert "lookup_table" in reasons["embedding_0.w_0"]
    assert "shared_w" in reasons and "elementwise_add" in reasons["shared_w"]
    assert "wt" in reasons and "transposed" in reasons["wt"]
    assert rep.n_quantized == 1  # the fc weight
    del out, out2


def test_rewrite_missing_scope_value_skips():
    main, _startup, _out = _mlp_program()
    scope = fluid.Scope()  # startup never ran: no weights anywhere
    rep = quantize.rewrite_for_inference(main, scope, "int8")
    assert rep.n_quantized == 0
    assert all("missing from scope" in r for r in
               rep.skip_reasons().values())


def test_rewritten_program_passes_strict_proglint():
    from paddle_tpu.analysis import validate_for_run

    main, startup, out = _mlp_program()
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.TPUPlace())
        exe.run(startup)
        quantize.rewrite_for_inference(main, scope, "int8_block", block=8)
    validate_for_run(main, fetch_names=[out.name], feed_names=["x"],
                     mode="strict", label="quantized")


def test_calibrate_observes_activation_scales():
    """The ops/quant.py scale observers wired end to end: running
    abs-max per matmul input, on the fp32 AND the rewritten program."""
    main, startup, out = _mlp_program()
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.TPUPlace())
        exe.run(startup)
        rng = np.random.RandomState(3)
        feeds = [{"x": rng.rand(4, 16).astype("float32") * 2.0}
                 for _ in range(3)]
        scales = quantize.calibrate(main, feeds, scope=scope, executor=exe)
        assert set(scales) == {"x", "fc_0.tmp_2"}  # both matmul inputs
        assert all(0.0 < v < 4.0 for v in scales.values())
        # calibration state must not leak into the model scope
        assert scope.find_var("x.act_accum") is None
        # works identically on the quantized program
        quantize.rewrite_for_inference(main, scope, "int8")
        scales_q = quantize.calibrate(main, feeds, scope=scope,
                                      executor=exe)
        assert set(scales_q) == set(scales)
    del out


# -- TP predict parity (clone-shared mesh) -----------------------------------


@pytest.fixture()
def tagged_model_dir(tmp_path):
    d = str(tmp_path / "tagged")
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = fluid.layers.data("x", [16])
        h = fluid.layers.fc(
            x, 32, act="relu",
            param_attr=fluid.ParamAttr(name="q_w1",
                                       logical_axes=("embed", "mlp")),
            bias_attr=fluid.ParamAttr(name="q_b1", logical_axes=("mlp",)))
        out = fluid.layers.fc(
            h, 8, act="softmax",
            param_attr=fluid.ParamAttr(name="q_w2",
                                       logical_axes=("mlp", "embed")))
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        fluid.io.save_inference_model(d, ["x"], [out], exe, main)
    return d


def test_tp_quantized_predict_parity(tagged_model_dir):
    """int8 weights + scale planes resolve through the SAME partition
    tags as the fp32 weights they replace: tp-sharded quantized
    predict matches the single-device quantized predict, clones share
    the mesh."""
    from paddle_tpu.inference import Config, create_predictor

    feed = np.random.RandomState(0).rand(4, 16).astype("float32")
    c0 = Config(tagged_model_dir)
    c0.enable_weight_quantization("int8")
    (ref,) = create_predictor(c0).run([feed])

    cfg = Config(tagged_model_dir)
    cfg.enable_weight_quantization("int8")
    cfg.enable_partitioning(mesh_axes={"tp": 8})
    pred = create_predictor(cfg)
    assert pred.quantize_report.n_quantized == 2
    # the quantized weight + its scale plane both resolved sharded
    rows = {r["name"]: r for r in pred.partition.report()["vars"]}
    assert rows["q_w1.q"]["spec"] == [None, "tp"]
    assert rows["q_w1.qscale"]["spec"] == ["tp"]
    (tp,) = pred.run([feed])
    np.testing.assert_allclose(ref, tp, atol=1e-5, rtol=1e-5)
    clone = pred.clone()
    assert clone.partition is pred.partition
    assert clone.quantize_report is pred.quantize_report
    (tpc,) = clone.run([feed])
    np.testing.assert_allclose(ref, tpc, atol=1e-5, rtol=1e-5)


# -- end to end: checkpoint load -> quantize -> serve ------------------------

CFG = None
SEQ = 40


def _gpt_cfg():
    from paddle_tpu.generation.model import GPTConfig

    global CFG
    if CFG is None:
        CFG = GPTConfig(vocab_size=97, hidden_size=32, num_layers=2,
                        num_heads=4, ffn_size=64, max_position=64,
                        hidden_dropout=0.0, attention_dropout=0.0)
    return CFG


@pytest.fixture(scope="module")
def lm_dir(tmp_path_factory):
    from paddle_tpu.generation.model import build_lm_program

    cfg = _gpt_cfg()
    d = str(tmp_path_factory.mktemp("quant_lm"))
    main, startup, _feeds, fetches = build_lm_program(cfg, SEQ)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.TPUPlace())
        exe.run(startup)
        fluid.io.save_inference_model(d, ["tokens"],
                                      [fetches["logits"]], exe, main)
    return d


@pytest.mark.slow
def test_flag_consumed_at_predictor_construction(lm_dir):
    """The quantize_weights FLAG (not just the Config call) rewrites at
    load — and the loaded-checkpoint round trip serves quantized."""
    from paddle_tpu.inference import Config, create_predictor

    old = fluid.get_flags(["quantize_weights"])
    fluid.set_flags({"quantize_weights": "int8"})
    try:
        pred = create_predictor(Config(lm_dir))
    finally:
        fluid.set_flags(old)
    assert pred.quantize_report is not None
    assert pred.quantize_report.n_quantized == 9  # 8 layer mats + head
    toks = np.zeros((1, SEQ), np.int64)
    (logits,) = pred.run([toks])
    assert logits.shape == (1, SEQ, _gpt_cfg().vocab_size)
    assert np.all(np.isfinite(logits))


def test_fully_quantized_ragged_engine_through_churn_eviction(lm_dir):
    """THE serving proof: int8 weights + int8 KV pages together, token
    agreement with the fp32 engine through slot churn, pool-pressure
    eviction and resume (greedy prefix identity held to >= the PR-12
    int8-KV gate)."""
    from paddle_tpu.generation import GenerationEngine
    from paddle_tpu.inference import Config, create_predictor

    cfg = _gpt_cfg()
    rng = np.random.RandomState(7)
    prompts = [rng.randint(1, cfg.vocab_size, int(n)).astype(np.int64)
               for n in rng.randint(8, 14, 4)]

    def run(quantized):
        c = Config(lm_dir)
        if quantized:
            c.enable_weight_quantization("int8")
        pred = create_predictor(c)
        eng = GenerationEngine(
            pred, cfg, page_size=4, num_pages=16, max_decode_batch=3,
            chunk_tokens=6,
            kv_dtype="int8" if quantized else "float32",
            quantize_weights="int8" if quantized else "off")
        try:
            streams = [eng.submit(p, max_new_tokens=14) for p in prompts]
            outs = [s.result(timeout=600) for s in streams]
            st = eng.stats()
            eng.cache.check_integrity()
        finally:
            eng.close(drain=True)
        assert st["evicted_total"] >= 1, "must exercise eviction/resume"
        assert st["cache"]["pages_in_use"] == 0
        if quantized:
            assert eng.quantize_report is not None
            assert eng.quantize_report.n_quantized >= 1
        return outs

    f32 = run(False)
    q = run(True)
    agree = sum(sum(1 for a, b in zip(x, y) if a == b)
                for x, y in zip(f32, q))
    total = sum(len(x) for x in f32)
    assert agree / total >= 0.8, (agree, total)


@pytest.mark.slow
def test_engine_quantize_rewrites_shared_predictor(lm_dir):
    """Engine-level opt-in must not brick the caller's predictor: the
    shared program is rewritten too, and predictor.run keeps
    working against the quantized scope."""
    from paddle_tpu.generation import GenerationEngine
    from paddle_tpu.inference import Config, create_predictor

    cfg = _gpt_cfg()
    pred = create_predictor(Config(lm_dir))
    assert pred.quantize_report is None
    eng = GenerationEngine(pred, cfg, page_size=4, num_pages=32,
                           max_decode_batch=2, quantize_weights="int8")
    try:
        out = eng.generate(np.asarray([3, 5, 7], np.int64),
                           max_new_tokens=4, timeout=600)
        assert len(out) == 4
    finally:
        eng.close(drain=True)
    # the predictor the engine cloned from was rewritten alongside
    assert pred.quantize_report is not None
    (logits,) = pred.run([np.zeros((1, SEQ), np.int64)])
    assert np.all(np.isfinite(logits))


@pytest.mark.slow
def test_two_lane_engine_quantized(lm_dir):
    """quantize_weights covers BOTH engine modes: the two-lane
    prefill-bucket ladder + decode executable rewrite lazily."""
    from paddle_tpu.generation import GenerationEngine
    from paddle_tpu.inference import Config, create_predictor

    cfg = _gpt_cfg()
    c = Config(lm_dir)
    c.enable_weight_quantization("int8")
    pred = create_predictor(c)
    prompt = np.asarray([2, 9, 4, 11], np.int64)
    f32_pred = create_predictor(Config(lm_dir))
    # note: f32 predictor built from the SAME dir gets its own scope
    eng_f32 = GenerationEngine(f32_pred, cfg, page_size=4, num_pages=64,
                               max_decode_batch=2, mode="two_lane",
                               prefill_buckets=(8, 16))
    eng_q = GenerationEngine(pred, cfg, page_size=4, num_pages=64,
                             max_decode_batch=2, mode="two_lane",
                             prefill_buckets=(8, 16),
                             quantize_weights="int8")
    try:
        want = eng_f32.generate(prompt, max_new_tokens=8, timeout=600)
        got = eng_q.generate(prompt, max_new_tokens=8, timeout=600)
    finally:
        eng_f32.close(drain=True)
        eng_q.close(drain=True)
    assert sum(1 for a, b in zip(want, got) if a == b) >= 6


def test_registry_knows_quantized_ops():
    from paddle_tpu.core.registry import get_op_def, registered_ops

    assert "quantized_matmul" in registered_ops()
    assert "quantized_fc" in registered_ops()
    d = get_op_def("quantized_fc")
    assert d.stop_gradient and "Scale" in d.no_grad_slots


# -- review-hardening regressions --------------------------------------------


def test_scope_mode_mismatch_refused():
    """A second program over one scope must quantize with the SAME
    mode/block — decoding int8 bytes as e4m3 (or mismatched block
    scale planes) would be silent garbage, so it raises instead."""
    main, startup, _out = _mlp_program()
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.TPUPlace())
        exe.run(startup)
        quantize.rewrite_for_inference(main, scope, "int8")
    main2 = fluid.Program.from_dict(main.to_dict())
    # round-tripped program is already quantized: mismatch can't bite
    # there — rebuild a FRESH fp32 program with the same weight names
    with fluid.unique_name.guard():
        main3, _s3, _o3 = _mlp_program()
    with pytest.raises(ValueError, match="same mode"):
        quantize.rewrite_for_inference(main3, scope, "fp8")
    with pytest.raises(ValueError, match="same mode"):
        quantize.rewrite_for_inference(main3, scope, "int8_block",
                                       block=16)
    # matching mode/block reuses the buffers fine
    rep = quantize.rewrite_for_inference(main3, scope, "int8")
    assert rep.n_quantized == 2
    del main2


def test_rerewrite_does_not_report_scale_planes():
    """Re-running the rewrite on an int8_block program must not
    misreport the 2-D .qscale planes as skipped fp32 weights."""
    main, startup, _out = _mlp_program()
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.TPUPlace())
        exe.run(startup)
        rep1 = quantize.rewrite_for_inference(main, scope, "int8_block",
                                              block=8)
        rep2 = quantize.rewrite_for_inference(main, scope, "int8_block",
                                              block=8)
    assert rep1.n_quantized == 2
    assert rep2.rows == []  # nothing quantized, nothing misreported


@pytest.mark.slow
def test_engine_refuses_quantizing_partitioned_predictor(tagged_model_dir):
    """Engine-level opt-in on an already-partitioned (but fp32)
    predictor would bind the quantized vars replicated — refused with
    the ordered path named."""
    from paddle_tpu.generation import GenerationEngine
    from paddle_tpu.generation.model import GPTConfig
    from paddle_tpu.inference import Config, create_predictor

    cfg = Config(tagged_model_dir)
    cfg.enable_partitioning(mesh_axes={"tp": 8})
    pred = create_predictor(cfg)
    gcfg = GPTConfig(vocab_size=20, hidden_size=16, num_layers=1,
                     num_heads=2, ffn_size=32, max_position=32,
                     hidden_dropout=0.0, attention_dropout=0.0)
    with pytest.raises(ValueError, match="Predictor construction"):
        GenerationEngine(pred, gcfg, page_size=4, num_pages=16,
                         max_decode_batch=2, quantize_weights="int8",
                         start=False)


def test_int8_block_mosaic_geometry_guard():
    """A non-128-multiple block with K > block cannot tile on Mosaic:
    the pallas wrapper names the geometry instead of an opaque
    compile error (interpret mode still executes it — CPU CI covers
    small blocks)."""
    import jax.numpy as jnp

    rng = np.random.RandomState(0)
    w = rng.randn(256, 64).astype("float32")
    x = jnp.asarray(rng.randn(4, 256).astype("float32"))
    q, s = qm.quantize_weight(w, "int8_block", block=64)
    with pytest.raises(ValueError, match="128"):
        qm._quant_matmul_pallas(x, q, s, "int8_block", 64,
                                interpret=False)
    # interpret executes the same geometry fine
    out = qm._quant_matmul_pallas(x, q, s, "int8_block", 64,
                                  interpret=True)
    assert np.isfinite(np.asarray(out)).all()
    # K <= block: single full-K tile is legal — no raise at the guard
    q2, s2 = qm.quantize_weight(w[:48], "int8_block", block=64)
    try:
        qm._quant_matmul_pallas(x[:, :48], q2, s2, "int8_block", 64,
                                interpret=True)
    except ValueError as e:  # pragma: no cover - guard must not fire
        raise AssertionError(f"guard fired on legal geometry: {e}")
