"""Runs of one cell as the driver makes them, each a process of its
own, and their spread by the contract's rule.

    python3 benchmark/spread.py --workload W --seconds S --seeds 11,12,13 \
        [--sets 2] [--trace 0] [--out chiprun_out/W.jsonl] [--more "W2:S2:seeds" ...]

A set is one run per seed; with --sets 2 the same seeds run again after
everything else named on the command line (`--more` entries
"W:S:seeds[:trace[:once]]": other cells or lengths, run after each set
as the driver's other side would be, or with `once` after the first
set only). The parent never touches jax, so each child gets the chip.
Every result line is appended to --out with its workload, seconds,
set, seed and wall time. Spread = (Q3 - Q1) / median by
statistics.quantiles(n=4), per metric and set."""

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))


def one_run(workload, seconds, seed, trace, out, tag):
    cmd = [sys.executable, os.path.join(HERE, "run.py"), "--workload",
           workload, "--seed", str(seed), "--seconds", str(seconds),
           "--trace", str(trace)]
    t0 = time.time()
    p = subprocess.run(cmd, capture_output=True, text=True)
    wall = time.time() - t0
    lines = p.stdout.strip().splitlines()
    try:
        result = json.loads(lines[-1])
    except (IndexError, ValueError):
        result = {"error": (p.stdout[-1500:] + p.stderr[-3000:])}
    result.update(workload=workload, seconds=seconds, seed=seed, trace=trace,
                  tag=tag, wall_s=wall, rc=p.returncode)
    if not result.get("correct"):
        result["stderr_tail"] = p.stderr[-2500:]
    with open(out, "a") as f:
        f.write(json.dumps(result) + "\n")
    vals = {k: v["value"] for k, v in result.get("metrics", {}).items()}
    print(f"[{tag}] {workload} s={seconds} seed={seed} rc={p.returncode} "
          f"correct={result.get('correct')} wall={wall:.0f}s {vals}",
          flush=True)
    if "error" in result:
        print(result["error"][-3000:], flush=True)
    return result


def spread(values):
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def report(results, label):
    names = sorted({k for r in results for k in r.get("metrics", {})})
    for name in names:
        v = [r["metrics"][name]["value"] for r in results
             if name in r.get("metrics", {})]
        if len(v) >= 2:
            print(f"  {label} {name}: n={len(v)} median={statistics.median(v):.6g}"
                  f" spread={spread(v) if len(v) >= 3 else float('nan'):.4%}"
                  f" min={min(v):.6g} max={max(v):.6g}", flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--sets", type=int, default=1)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--out", default=None)
    ap.add_argument("--more", action="append", default=[],
                    help="workload:seconds:seed,seed,...[:trace[:once]], run "
                         "after the main cell's runs of each set")
    a = ap.parse_args()
    out = a.out or f"chiprun_out/{a.workload}.jsonl"
    os.makedirs(os.path.dirname(out) or ".", exist_ok=True)
    seeds = [int(s) for s in a.seeds.split(",")]
    groups = [(a.workload, a.seconds, seeds, a.trace, False)]
    for m in a.more:
        w, s, sd, *opt = m.split(":")
        groups.append((w, float(s), [int(x) for x in sd.split(",")],
                       int(opt[0]) if opt else a.trace, "once" in opt))
    done = {}
    for k in range(a.sets):
        for w, s, sd, trace, once in groups:
            if once and k > 0:
                continue
            rs = [one_run(w, s, seed, trace, out, f"set{k + 1}")
                  for seed in sd]
            done.setdefault((w, s, trace), []).append(rs)
            report(rs, f"{w} s={s} trace={trace} set{k + 1}")
    for (w, s, trace), sets in done.items():
        report([r for rs in sets for r in rs], f"{w} s={s} trace={trace} all")
    bad = [r for sets in done.values() for rs in sets for r in rs
           if not r.get("correct")]
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
