"""Observability overhead + flight-recorder round-trip bench.

Smoke mode (the CI ``obs`` job)::

    python tools/obs_bench.py --smoke --out obs_bench.json

measures what the unified telemetry layer costs on the step hot path
and proves the crash-time story end to end:

1. **Overhead gate (<3%)** — per-step cost of metrics+tracing+flight
   ENABLED vs disabled. Two numbers, same methodology as
   chaos_train.py: (a) end-to-end steps/s for both configurations
   (reported, informational — jax CPU dispatch noise on a sub-ms step
   swamps a single-digit-us cost rep to rep); (b) the telemetry
   MACHINERY cost per step measured in isolation (the exact extra work
   BoundStep.run does when enabled: one perf_counter pair, the
   step-telemetry record incl. its flight-ring append, and one traced
   span), which is the gated number: machinery_us / bare_step_us < 3%.
2. **Flight-dump round trip** — a supervised run with an injected
   ``nan@N`` and another with ``hang@N`` under the watchdog each
   produce a JSON dump that parses and contains the spans and
   step-metric samples leading up to the fault.
3. **Scrape sanity** — one ``observability.snapshot()`` exposes the
   serving/dispatch/executor/resilience/reader/step families.

The report is written as a JSON artifact for the perf trajectory.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

OBS_FLAG_NAMES = ("observability_metrics", "observability_tracing",
                  "observability_flight")


def _set_obs(fluid, on: bool):
    fluid.set_flags({k: on for k in OBS_FLAG_NAMES})


def build_bench_model(hidden=128, batch=32, feat=64, seed=7):
    """A representative small train step (2-layer MLP + dropout +
    Adam): ~1ms on a CI CPU. chaos_train's micro-model (~0.35ms) is
    deliberately tiny for chaos round trips; gating a per-step
    overhead ratio against it would overstate the cost of telemetry
    on any real workload, whose steps are milliseconds."""
    import numpy as np

    import paddle_tpu as fluid

    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = seed
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = fluid.layers.data("x", [feat])
        y = fluid.layers.data("y", [1], dtype="int64")
        h = fluid.layers.fc(x, hidden, act="relu")
        h = fluid.layers.dropout(h, dropout_prob=0.1)
        h = fluid.layers.fc(h, hidden, act="relu")
        logits = fluid.layers.fc(h, 8)
        loss = fluid.layers.mean(
            fluid.layers.softmax_with_cross_entropy(logits, y))
        fluid.optimizer.Adam(1e-3).minimize(loss)

    def feed_fn(step):
        rng = np.random.RandomState(20_000 + step)
        return {"x": rng.randn(batch, feat).astype("float32"),
                "y": rng.randint(0, 8, (batch, 1)).astype("int64")}

    return main, startup, loss, feed_fn


def measure_loops(reps=5, timed=150):
    """End-to-end steps/s, observability fully on vs fully off, plus
    the isolated per-step machinery cost."""
    import paddle_tpu as fluid
    from paddle_tpu.observability import flight, tracing
    from paddle_tpu.observability.registry import step_telemetry

    med = lambda xs: sorted(xs)[len(xs) // 2]  # noqa: E731
    main, startup, loss, feed_fn = build_bench_model()
    feeds = [feed_fn(s) for s in range(32)]
    scope = fluid.Scope()
    out = {}
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.TPUPlace())
        exe.run(startup)

        def loop():
            for s in range(timed):
                exe.run(main, feed=feeds[s % 32], fetch_list=[loss])

        times = {}
        for label, on in (("disabled", False), ("enabled", True)):
            _set_obs(fluid, on)
            loop()  # warm: (re)bind BoundSteps for this flag generation
            ts = []
            for _ in range(reps):
                t0 = time.perf_counter()
                loop()
                ts.append(time.perf_counter() - t0)
            times[label] = med(ts) / timed
        bare_step_s = times["disabled"]
        out["bare_steps_per_s"] = 1.0 / bare_step_s
        out["enabled_steps_per_s"] = 1.0 / times["enabled"]
        out["end_to_end_delta_pct"] = (
            times["enabled"] / bare_step_s - 1) * 100

        # isolated machinery: exactly what BoundStep.run adds per step
        # when everything is enabled, measured over enough iterations
        # that the clock resolution is irrelevant
        _set_obs(fluid, True)
        flight.clear()
        tel = step_telemetry()
        n = 20_000

        t0 = time.perf_counter()
        for i in range(n):
            # what one Executor.run opens: three annotations (always
            # on, flag or no flag) and the one span the flag gives ids
            with tracing.annotation("executor/bind"):
                pass
            with tracing.annotation("executor/feed"):
                pass
            t_obs = time.perf_counter()  # the pair BoundStep pays
            with tracing.span("executor/step", {"step": i, "tag": "bench"}):
                pass
            with tracing.annotation("executor/fetch"):
                pass
            tel.record((time.perf_counter() - t_obs) * 1e3, 8, step=i)
        machinery_s = (time.perf_counter() - t0) / n
        _set_obs(fluid, False)

    out["telemetry_machinery_us_per_step"] = machinery_s * 1e6
    out["bare_step_us"] = bare_step_s * 1e6
    out["overhead_pct"] = machinery_s / bare_step_s * 100.0
    out.update(measure_propagation(bare_step_s))
    return out


def measure_propagation(bare_step_s: float, n: int = 20_000):
    """Per-boundary cost of the cross-process trace codec
    (observability/propagate.py) with tracing ON: what one
    request-hop pays end to end — extract the incoming header, attach
    it, open a span, and format+inject the outgoing header (the exact
    work server.py + PageStoreClient add per hop). Gated like the
    step machinery: propagation_us / bare_step_us < 3%."""
    import paddle_tpu as fluid
    from paddle_tpu.observability import propagate, tracing

    fluid.set_flags({"observability_tracing": True})
    try:
        with tracing.span("bench/root") as root:
            header = propagate.format_traceparent(root)
        carrier = {"traceparent": header}
        t0 = time.perf_counter()
        for _ in range(n):
            ctx = propagate.extract(carrier)
            with tracing.attach(ctx), tracing.span("bench/hop") as s:
                propagate.inject(s, {})
        prop_s = (time.perf_counter() - t0) / n
    finally:
        fluid.set_flags({"observability_tracing": False})
    return {
        "propagation_us_per_request": prop_s * 1e6,
        "propagation_overhead_pct": prop_s / bare_step_s * 100.0,
    }


def flight_round_trip(tmp):
    """nan@N and hang@N each produce a parseable dump with the spans
    and metric samples leading up to the fault."""
    import chaos_train
    import paddle_tpu as fluid
    from paddle_tpu import resilience
    from paddle_tpu.observability import flight

    fluid.set_flags({
        "observability_metrics": True, "observability_tracing": True,
        "observability_flight": True,
        "observability_dump_dir": os.path.join(tmp, "dumps"),
    })
    results = {}
    for label, fault, kw in (
        ("nan", "nan@5", {}),
        ("hang", "hang@4:1.5", {"watchdog_timeout_s": 0.3}),
    ):
        flight.clear()
        main, startup, loss = chaos_train.build_model()
        scope = fluid.Scope()
        ck = os.path.join(tmp, f"ck_{label}")
        with fluid.scope_guard(scope):
            exe = fluid.Executor(fluid.TPUPlace())
            exe.run(startup)
            sup = resilience.Supervisor(
                exe, main, checkpoint_dir=ck,
                feed_fn=chaos_train.feed_fn, fetch_list=[loss],
                policy=resilience.CheckpointPolicy(ck, every_steps=3,
                                                   keep_last=2),
                fault_injector=resilience.FaultInjector(fault), **kw)
            stats = sup.run_loop(8)
        assert stats["flight_dumps"], f"{label}: no flight dump produced"
        with open(stats["flight_dumps"][0]) as f:
            dump = json.load(f)  # parseable is the contract
        kinds = {e["kind"] for e in dump["entries"]}
        assert "span" in kinds and "step" in kinds, (label, kinds)
        results[label] = {
            "dump": stats["flight_dumps"][0],
            "reason": dump["reason"],
            "entries": len(dump["entries"]),
            "span_entries": sum(e["kind"] == "span"
                                for e in dump["entries"]),
            "step_samples": sum(e["kind"] == "step"
                                for e in dump["entries"]),
        }
    fluid.set_flags({"observability_tracing": False,
                     "observability_dump_dir": ""})
    return results


def smoke(out_path=None):
    from paddle_tpu import observability

    report = {"bench": "obs_bench", "mode": "smoke"}
    report.update(measure_loops())
    print(f"bare: {report['bare_steps_per_s']:.0f} steps/s | enabled: "
          f"{report['enabled_steps_per_s']:.0f} steps/s | machinery "
          f"{report['telemetry_machinery_us_per_step']:.2f}us/step = "
          f"{report['overhead_pct']:.3f}% of a bare "
          f"{report['bare_step_us']:.0f}us step")
    print(f"propagation: {report['propagation_us_per_request']:.2f}us/"
          f"request-hop = {report['propagation_overhead_pct']:.3f}% of a "
          "bare step")

    tmp = tempfile.mkdtemp(prefix="obs_bench_")
    report["flight_round_trip"] = flight_round_trip(tmp)
    for label, r in report["flight_round_trip"].items():
        print(f"flight[{label}]: {r['reason']} -> {r['entries']} entries "
              f"({r['span_entries']} spans, {r['step_samples']} step "
              "samples) OK")

    snap = observability.snapshot()
    families = set(snap["collected"]) | set(snap["instruments"])
    need = {"paddle_dispatch_jit_compiles", "paddle_executor_bound_hits",
            "paddle_resilience_steps_completed", "paddle_step_total"}
    missing = {f for f in need if not any(f in fam for fam in families)}
    assert not missing, f"unified scrape missing families: {missing}"
    report["scrape_families"] = len(families)
    print(f"unified scrape: {len(families)} metric families")

    if out_path:
        with open(out_path, "w") as f:
            json.dump(report, f, indent=2)
        print(f"wrote {out_path}")

    # the acceptance gates: enabled telemetry costs <3% of a bare
    # step, and so does one full propagation hop (extract + attach +
    # span + inject) with tracing ON
    assert report["overhead_pct"] < 3.0, (
        f"observability overhead {report['overhead_pct']:.3f}% >= 3% budget")
    assert report["propagation_overhead_pct"] < 3.0, (
        f"trace propagation overhead "
        f"{report['propagation_overhead_pct']:.3f}% >= 3% budget")
    return 0


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--smoke", action="store_true",
                   help="overhead gate + flight round trip + scrape sanity")
    p.add_argument("--out", default=None, help="JSON report path")
    args = p.parse_args(argv)
    return smoke(args.out)


if __name__ == "__main__":
    sys.exit(main())
