#!/usr/bin/env python
"""Donation / host-sync audit over every bound executable.

The two silent ways a framework leaves the device idle are (a) state
buffers that stop being donated — every step then materializes a second
copy of the parameters and pays an HBM round trip the reference's
in-place ParamOut update never did — and (b) host-sync points creeping
onto the hot path (`block_until_ready`, implicit `np.asarray` on a
fetch), which serialize the async pipeline the loader and the
dispatch feeder exist to fill.

This tool drives every subsystem that owns executables — Executor
training step, Predictor inference, ServingEngine worker pool,
GenerationEngine prefill + decode lanes — through a tiny model each,
then walks the process-wide BoundStep registry
(`runtime.dispatch.live_bound_steps()`) and reports, per call site:

  * which rewritten state buffers COULD be donated vs which ARE
    (donation is forced on for the audit run — on CPU the executor
    deliberately skips it for speed, which would make the check
    vacuous);
  * how many times the call site forced a host sync on the fetch path
    (BoundStep counts every return_numpy conversion and every
    FLAGS_benchmark forced sync);
  * the per-executable XLA memory/cost analysis
    (`observability_xla_analysis` gauges: argument/output/temp bytes,
    flops) so a donation miss is visible as bytes, not just a name.

The verdict diffs against the checked-in allowlist
(tools/donation_allowlist.json): a donation miss or a host-syncing
call site that is not allowlisted fails the run (CI gates on this).
`--update` rewrites the allowlist from the observed state after a
deliberate change.

Run:  JAX_PLATFORMS=cpu python tools/donation_audit.py --out audit.json
"""

from __future__ import annotations

import argparse
import json
import os
import re
import shutil
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# the partition phase audits MESH-bound executables (sharded train
# state must donate exactly like unsharded) — force 8 host devices so
# a dp4 x tp2 mesh exists on the CPU CI runner
_xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _xla_flags:
    os.environ["XLA_FLAGS"] = (
        _xla_flags + " --xla_force_host_platform_device_count=8").strip()

ALLOWLIST_PATH = os.path.join(HERE, "donation_allowlist.json")

import numpy as np  # noqa: E402


# -- subsystem drivers --------------------------------------------------------


def _phase_executor(fluid):
    """Training step: forward + backward + SGD — rewritten params and
    optimizer state are exactly the buffers donation must alias."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = fluid.layers.data("x", [16])
        y = fluid.layers.data("y", [1], dtype="int64")
        h = fluid.layers.fc(x, 16, act="relu")
        loss = fluid.layers.mean(
            fluid.layers.softmax_with_cross_entropy(
                fluid.layers.fc(h, 10), y))
        fluid.optimizer.SGD(0.1).minimize(loss)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe._force_donation = True  # CPU skips donation; the audit must see it
        exe.run(startup)
        feed = {"x": np.random.RandomState(0).rand(8, 16).astype("float32"),
                "y": np.zeros((8, 1), "int64")}
        for _ in range(3):
            exe.run(main, feed=feed, fetch_list=[loss])
    return [exe, scope]


def _export_infer_model(fluid, tmpdir):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = fluid.layers.data("x", [6])
        h = fluid.layers.fc(x, 12, act="relu")
        out = fluid.layers.fc(h, 3, act="softmax")
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        fluid.io.save_inference_model(tmpdir, ["x"], [out], exe, main)


def _phase_predictor(fluid, tmpdir):
    from paddle_tpu.inference import Config, create_predictor

    cfg = Config(tmpdir)
    cfg.enable_shape_bucketing(seq_buckets=(16, 32), batch_buckets=(4, 8))
    pred = create_predictor(cfg)
    pred._exe._force_donation = True
    rng = np.random.RandomState(1)
    for b in (2, 4):
        pred.run([rng.rand(b, 6).astype("float32")])
    return [pred]


def _phase_serving(fluid, tmpdir):
    from paddle_tpu.inference import Config, create_predictor
    from paddle_tpu.serving import ServingEngine

    pred = create_predictor(Config(tmpdir))
    pred._exe._force_donation = True
    eng = ServingEngine(pred, num_workers=2, max_batch_size=4,
                        batch_timeout_ms=1.0)
    rng = np.random.RandomState(2)
    for _ in range(3):
        eng.predict({"x": rng.rand(2, 6).astype("float32")}, timeout=60)
    eng.close(drain=True)
    return [pred, eng]


def _phase_generation(fluid, tmpdir):
    from paddle_tpu import generation
    from paddle_tpu.generation.model import GPTConfig, build_lm_program
    from paddle_tpu.inference import Config, create_predictor

    cfg = GPTConfig(vocab_size=97, hidden_size=32, num_layers=2,
                    num_heads=2, ffn_size=64, max_position=64,
                    hidden_dropout=0.0, attention_dropout=0.0)
    seq = 32
    lm_dir = os.path.join(tmpdir, "lm")
    main, startup, _feeds, fetches = build_lm_program(cfg, seq)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.TPUPlace())
        exe.run(startup)
        fluid.io.save_inference_model(lm_dir, ["tokens"],
                                      [fetches["logits"]], exe, main)
    pred = create_predictor(Config(lm_dir))
    pred._exe._force_donation = True
    rng = np.random.RandomState(3)
    engines = []
    # both modes: the one ragged executable, and two_lane's prefill
    # buckets + decode step; all of them rewrite the engine's page
    # pools as donated state (generation_pools_check)
    for mode in ("ragged", "two_lane"):
        eng = generation.GenerationEngine(
            pred, cfg, page_size=8, num_pages=64, max_decode_batch=4,
            prefill_buckets=(16, seq), mode=mode)
        streams = [eng.submit(
            rng.randint(1, cfg.vocab_size, 7).astype(np.int64),
            max_new_tokens=4) for _ in range(3)]
        for s in streams:
            s.result(timeout=300)
        eng.close(drain=True)
        engines.append(eng)
    return [pred] + engines


def generation_pools_check(rows):
    """The page pools are the largest state a generation step rewrites
    and were, fed and fetched, invisible here until PR 30: every
    ``generation/`` executable of the audit's report rows (or of
    ``--check-static``'s static plans) must list each of its program's
    pools (``gen_k_pages_*`` ...) as donated. Returns violations."""
    violations = []
    steps = [r for r in rows if str(r["tag"]).startswith("generation/")]
    tags = {str(r["tag"]).split("[")[0] for r in steps}
    for want in ("generation/ragged_step", "generation/prefill",
                 "generation/decode"):
        if want not in tags:
            violations.append(
                f"generation phase ran no {want!r} executable — the "
                "audit lost its coverage of the page pools")
    for r in steps:
        donated = r.get("donated", r.get("static_donatable"))
        pools = [n for n in r.get("pools", ()) if n not in donated]
        if pools or not r.get("pools"):
            violations.append(
                f"page pools not donated: generation / {r['tag']} "
                f"donates {sorted(donated)} and leaves out "
                f"{pools or 'every pool (it declares none as state)'} — "
                "the step would copy each of them every step")
    return violations


def _pools_of(bound):
    """The page-pool variables a bound step's program declares."""
    return sorted(n for n in bound.block.vars
                  if re.fullmatch(r"gen_[kv]_(pages|scales)_\d+", n))


def _phase_partition(fluid, tmpdir):
    """Mesh-bound executables: a dp4(+ZeRO-1) sharded training step and
    a tp2 predictor over one partitioned model. The audit must treat
    these exactly like single-device executables — sharded train state
    still rewrites in place, so every rewritten buffer must donate —
    and the report rows carry the mesh shape to prove none were
    skipped."""
    import numpy as np

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = fluid.layers.data("x", [16])
        y = fluid.layers.data("y", [1], dtype="int64")
        h = fluid.layers.fc(
            x, 32, act="relu",
            param_attr=fluid.ParamAttr(name="pt_w1",
                                       logical_axes=("embed", "mlp")),
            bias_attr=fluid.ParamAttr(name="pt_b1", logical_axes=("mlp",)))
        logits = fluid.layers.fc(
            h, 4, param_attr=fluid.ParamAttr(name="pt_w2",
                                             logical_axes=("mlp", "embed")))
        loss = fluid.layers.mean(
            fluid.layers.softmax_with_cross_entropy(logits, y))
        fluid.optimizer.Adam(0.01).minimize(loss)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe._force_donation = True  # CPU mesh skips donation; audit must see it
        exe.run(startup)
        cfg = fluid.partition.PartitionConfig(mesh_axes={"dp": 4}, zero=1)
        compiled = fluid.CompiledProgram(main).with_partitioning(cfg)
        feed = {"x": np.random.RandomState(4).rand(8, 16).astype("float32"),
                "y": np.zeros((8, 1), "int64")}
        for _ in range(3):
            exe.run(compiled, feed=feed, fetch_list=[loss])

    from paddle_tpu.inference import Config, create_predictor

    icfg = Config(tmpdir)
    # the exported model carries no logical_axes tags — the name-pattern
    # var_rules path is what untouched third-party models use
    icfg.enable_partitioning(
        mesh_axes={"tp": 2}, zero=0,
        var_rules=((r"fc_0\.w_0", ("embed", "mlp")),
                   (r"fc_1\.w_0", ("mlp", "embed"))))
    pred = create_predictor(icfg)
    pred._exe._force_donation = True
    pred.run([np.random.RandomState(5).rand(4, 6).astype("float32")])
    return [exe, scope, compiled, pred]


def _phase_collectives(fluid):
    """Quantized-collective DP training (parallel/collectives.py): the
    rewritten program's forward+backward runs inside the planner's
    shard_map with int8 bucket reduces, and the contract is unchanged —
    every rewritten sharded state buffer (params + ZeRO-1 moments)
    still donates, and the bucket collectives add ZERO new hot-path
    host syncs (the only sync stays the caller's loss fetch)."""
    import numpy as np

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = fluid.layers.data("x", [16])
        y = fluid.layers.data("y", [1], dtype="int64")
        h = fluid.layers.fc(
            x, 32, act="relu",
            param_attr=fluid.ParamAttr(name="qc_w1",
                                       logical_axes=("embed", "mlp")),
            bias_attr=fluid.ParamAttr(name="qc_b1", logical_axes=("mlp",)))
        logits = fluid.layers.fc(
            h, 4, param_attr=fluid.ParamAttr(name="qc_w2",
                                             logical_axes=("mlp", "embed")))
        loss = fluid.layers.mean(
            fluid.layers.softmax_with_cross_entropy(logits, y))
        fluid.optimizer.Adam(0.01).minimize(loss)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe._force_donation = True  # CPU mesh skips donation; audit must see it
        exe.run(startup)
        cfg = fluid.partition.PartitionConfig(
            mesh_axes={"dp": 4}, zero=1,
            collective_bucket_mb=0.001, collective_quantization="int8")
        compiled = fluid.CompiledProgram(main).with_partitioning(cfg)
        feed = {"x": np.random.RandomState(6).rand(8, 16).astype("float32"),
                "y": np.zeros((8, 1), "int64")}
        for _ in range(3):
            exe.run(compiled, feed=feed, fetch_list=[loss])
    return [exe, scope, compiled]


def _phase_fused_optim(fluid):
    """Fused one-pass optimizer (kernels/fused_optim.py) under dp4 +
    ZeRO-1 with a folded global-norm clip: the whole point of the
    fusion is REMOVING state copies, so the proof is this audit — every
    rewritten state buffer (params + both sharded Adam moments + the
    beta-pow scalars) must still donate, with ZERO extra state copies
    or host syncs vs the unfused chain's phase."""
    import numpy as np

    old = fluid.get_flags(["optimizer_fuse"])
    fluid.set_flags({"optimizer_fuse": "on"})
    try:
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup), fluid.unique_name.guard():
            x = fluid.layers.data("x", [16])
            y = fluid.layers.data("y", [1], dtype="int64")
            h = fluid.layers.fc(
                x, 32, act="relu",
                param_attr=fluid.ParamAttr(name="fo_w1",
                                           logical_axes=("embed", "mlp")),
                bias_attr=fluid.ParamAttr(name="fo_b1",
                                          logical_axes=("mlp",)))
            logits = fluid.layers.fc(
                h, 4, param_attr=fluid.ParamAttr(name="fo_w2",
                                                 logical_axes=("mlp",
                                                               "embed")))
            loss = fluid.layers.mean(
                fluid.layers.softmax_with_cross_entropy(logits, y))
            fluid.optimizer.Adam(
                0.01, grad_clip=fluid.clip.GradientClipByGlobalNorm(1.0)
            ).minimize(loss)
        ops = [op.type for op in main.global_block().ops]
        if "fused_adam" not in ops:
            raise RuntimeError(
                "fused_optim phase: optimizer_fuse=on did not emit "
                "fused_adam ops — the audit would silently re-prove "
                "the unfused chain")
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            exe = fluid.Executor(fluid.CPUPlace())
            exe._force_donation = True  # CPU skips donation; audit must see it
            exe.run(startup)
            cfg = fluid.partition.PartitionConfig(mesh_axes={"dp": 4},
                                                  zero=1)
            compiled = fluid.CompiledProgram(main).with_partitioning(cfg)
            feed = {"x": np.random.RandomState(7).rand(8, 16)
                    .astype("float32"),
                    "y": np.zeros((8, 1), "int64")}
            for _ in range(3):
                exe.run(compiled, feed=feed, fetch_list=[loss])
        return [exe, scope, compiled]
    finally:
        fluid.set_flags(old)


def _phase_quantized_predict(fluid, tmpdir):
    """Quantized tp2 GPT predict (paddle_tpu.quantize): the rewrite
    swaps every matmul weight for int8 buffer + scale plane state —
    the audit proves the quantized path adds ZERO new host-sync points
    vs the fp32 predict allowlist, and that the mesh-bound quantized
    executable is audited like every other sharded one (the
    mesh-coverage hard error covers this site)."""
    import numpy as np

    from paddle_tpu.inference import Config, create_predictor
    from paddle_tpu.models.gpt import GPTConfig, build_gpt_lm

    gcfg = GPTConfig.tiny()
    qdir = os.path.join(tmpdir, "quant_lm")
    main, startup, _, fetches = build_gpt_lm(gcfg, 32, is_test=True)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.TPUPlace())
        exe.run(startup)
        fluid.io.save_inference_model(qdir, ["tokens"],
                                      [fetches["logits"]], exe, main)
    icfg = Config(qdir)
    icfg.enable_weight_quantization("int8")
    # the gpt ParamAttr logical_axes tags survive save/load AND the
    # quantize rewrite (the int8 weight + scale vars inherit them), so
    # the same rules table shards the quantized predict over tp2
    icfg.enable_partitioning(mesh_axes={"tp": 2})
    pred = create_predictor(icfg)
    if pred.quantize_report is None or pred.quantize_report.n_quantized == 0:
        raise RuntimeError(
            "quantized_predict phase: the rewrite quantized nothing — "
            "the audit would silently re-prove the fp32 path")
    pred._exe._force_donation = True
    rng = np.random.RandomState(8)
    for _ in range(3):
        pred.run([rng.randint(0, gcfg.vocab_size, (2, 32)).astype("int64")])
    return [pred]


# -- the audit ----------------------------------------------------------------


def run_audit():
    import paddle_tpu as fluid
    from paddle_tpu.runtime import dispatch

    # per-executable XLA memory/cost gauges must be captured at compile
    # time — turn the analysis on BEFORE anything binds
    fluid.set_flags({"observability_xla_analysis": True})

    tmpdir = tempfile.mkdtemp(prefix="pt_donation_audit_")
    keep = []  # strong refs: audited bound steps must not be GC'd mid-report
    sites = {}
    seen = set()

    def snapshot(site):
        new = [b for b in dispatch.live_bound_steps() if id(b) not in seen]
        for b in new:
            seen.add(id(b))
        keep.extend(new)
        sites[site] = new

    try:
        keep.extend(_phase_executor(fluid))
        snapshot("executor.train")
        _export_infer_model(fluid, tmpdir)
        snapshot("model_export")  # save/load machinery, not a hot path
        keep.extend(_phase_predictor(fluid, tmpdir))
        snapshot("predictor.run")
        keep.extend(_phase_serving(fluid, tmpdir))
        snapshot("serving.predict")
        keep.extend(_phase_generation(fluid, tmpdir))
        snapshot("generation")
        keep.extend(_phase_partition(fluid, tmpdir))
        snapshot("partition")
        keep.extend(_phase_collectives(fluid))
        snapshot("collectives")
        keep.extend(_phase_fused_optim(fluid))
        snapshot("fused_optim")
        keep.extend(_phase_quantized_predict(fluid, tmpdir))
        snapshot("quantized_predict")
    finally:
        shutil.rmtree(tmpdir, ignore_errors=True)

    # the partition/collectives/fused_optim phases exist to prove
    # mesh-bound executables are audited, not skipped — an empty mesh
    # column there means the audit silently lost its sharded coverage
    for site in ("partition", "collectives", "fused_optim",
                 "quantized_predict"):
        if not any(b.audit_info().get("mesh")
                   for b in sites.get(site, [])):
            raise RuntimeError(
                f"donation audit: the {site} phase produced no "
                "mesh-bound executables — sharded coverage was "
                "silently lost")

    report = {"sites": {}, "summary": {
        "total_executables": 0,
        "host_sync_sites": {},
        "donation_missed": [],
    }}
    for site, bounds in sites.items():
        rows = []
        for b in bounds:
            row = b.audit_info()
            if site == "generation":
                row["pools"] = _pools_of(b)
            rows.append(row)
        rows.sort(key=lambda r: r["tag"])
        report["sites"][site] = rows
        report["summary"]["total_executables"] += len(rows)
        syncs = sum(r["host_sync_calls"] for r in rows)
        if syncs:
            report["summary"]["host_sync_sites"][site] = syncs
        for r in rows:
            for name in r["donation_missed"]:
                report["summary"]["donation_missed"].append(
                    {"site": site, "tag": r["tag"], "state": name})
    return report, sites


def static_cross_check(report, sites, allow):
    """--check-static: re-derive every live executable's donation plan
    OFFLINE through the same classifier the compile used
    (core.executor.analyze_block_state — what the PTL08x
    donation-safety pass runs over the Program IR) and fail on drift:

      * a bound executable whose static plan disagrees with the
        runtime donatable set means the static pass no longer models
        the executor (the single-source-of-truth contract broke);
      * an allowlisted donation_miss whose (site, state) no static
        plan can produce is stale hand-maintained state.

    Returns (static_rows, violations). The rows are what ``--update``
    regenerates the allowlist from, making donation_allowlist.json a
    derived artifact of the static pass rather than a hand-edited one.
    """
    from paddle_tpu.core.executor import analyze_block_state

    static_rows = []
    violations = []
    donatable_by_site = {}
    for site, bounds in sites.items():
        for b in bounds:
            c = b.compiled
            state, written = analyze_block_state(b.block,
                                                 list(c.feed_names))
            written_set = set(written)
            static_don = sorted(n for n in state if n in written_set)
            runtime_don = sorted(getattr(c, "donatable_names", ()) or ())
            row = {
                "site": site, "tag": c.tag or "program",
                "static_donatable": static_don,
                "runtime_donatable": runtime_don,
                "agrees": static_don == runtime_don,
            }
            if site == "generation":
                row["pools"] = _pools_of(b)
            static_rows.append(row)
            donatable_by_site.setdefault(site, set()).update(static_don)
            if not row["agrees"]:
                violations.append(
                    f"static-plan drift: {site} / {row['tag']}: the "
                    f"static donation plan {static_don} disagrees with "
                    f"the runtime donatable set {runtime_don} — "
                    "analysis PTL08x and the executor no longer share "
                    "one classification")
    for m in allow.get("donation_miss", []):
        site_don = donatable_by_site.get(m.get("site"), set())
        if m.get("state") not in site_don:
            violations.append(
                f"stale allowlist entry: donation_miss "
                f"{m.get('site')!r}/{m.get('state')!r} names state no "
                "static donation plan produces — regenerate the "
                "allowlist (--check-static --update)")
    return static_rows, violations


def load_allowlist():
    if not os.path.exists(ALLOWLIST_PATH):
        return {"host_sync": {}, "donation_miss": []}
    with open(ALLOWLIST_PATH) as f:
        allow = json.load(f)
    if isinstance(allow.get("host_sync"), list):
        # legacy presence-only form: tolerate it, but every listed site
        # gates at its CURRENT count the next time --update runs
        allow["host_sync"] = {s: None for s in allow["host_sync"]}
    return allow


def check(report, allow):
    """Regressions = observed behavior the allowlist does not cover.
    Host-sync sites gate on COUNT, not just presence: the audit
    drivers run a fixed step count per phase, so a new forced sync
    inside an already-allowlisted site shows up as a higher number."""
    violations = []
    allowed_sync = allow.get("host_sync", {})
    allowed_miss = {(m["site"], m["state"])
                    for m in allow.get("donation_miss", [])}
    for site, n in report["summary"]["host_sync_sites"].items():
        if site not in allowed_sync:
            violations.append(
                f"host-sync regression: call site {site!r} forced {n} "
                "host sync(s) on the fetch path and is not allowlisted "
                "(tools/donation_allowlist.json)")
        elif allowed_sync[site] is not None and n > allowed_sync[site]:
            violations.append(
                f"host-sync regression: call site {site!r} forced {n} "
                f"host sync(s), up from the allowlisted "
                f"{allowed_sync[site]} — a new sync crept onto the "
                "fetch path (rerun with --update only if deliberate)")
    for m in report["summary"]["donation_missed"]:
        if (m["site"], m["state"]) not in allowed_miss:
            violations.append(
                f"donation regression: {m['site']} / {m['tag']} rewrites "
                f"state {m['state']!r} without donating its buffer")
    return violations


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--out", default=None, help="write the report JSON here")
    ap.add_argument("--update", action="store_true",
                    help="rewrite the allowlist from the observed state")
    ap.add_argument("--check-static", action="store_true",
                    help="cross-validate every executable's runtime "
                    "donation plan against the static PTL08x derivation "
                    "and the allowlist; fail on drift or stale entries")
    args = ap.parse_args()

    report, sites = run_audit()
    allow = load_allowlist()
    violations = check(report, allow) + generation_pools_check(
        report["sites"]["generation"])
    if args.check_static:
        static_rows, static_violations = static_cross_check(
            report, sites, allow)
        report["static_plans"] = static_rows
        violations = violations + static_violations + generation_pools_check(
            [r for r in static_rows if r["site"] == "generation"])
    report["violations"] = violations
    report["allowlist"] = allow

    out = json.dumps(report, indent=2, sort_keys=True)
    print(out)
    if args.out:
        with open(args.out, "w") as f:
            f.write(out + "\n")

    if args.update:
        new_allow = {
            "host_sync": dict(sorted(
                report["summary"]["host_sync_sites"].items())),
            "donation_miss": [
                {"site": m["site"], "state": m["state"]}
                for m in report["summary"]["donation_missed"]],
        }
        with open(ALLOWLIST_PATH, "w") as f:
            json.dump(new_allow, f, indent=2, sort_keys=True)
            f.write("\n")
        print(f"[donation_audit] allowlist rewritten: {ALLOWLIST_PATH}",
              file=sys.stderr)
        return 0

    if violations:
        for v in violations:
            print(f"[donation_audit] {v}", file=sys.stderr)
        return 1
    print("[donation_audit] OK: zero non-allowlisted donation misses / "
          "host-sync points", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
