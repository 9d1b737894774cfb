"""Readings the limits of `correct` are set from, on the chip at a
cell's own size (PERF.md gives the readings and the limits):

    python3 benchmark/control.py --workload W --seeds 1,2,3 [--seconds S] [--set engine.kv_dtype=bfloat16]

For each seed, one process-local run of the cell as run.py makes it
(set-up, a window of --seconds, the comparison: the program's reading,
the lower one), then the kind's `control()`: the reference put in the
program's place in the precision below the one the configuration
states, and with each fault planted that the cell can have (the upper
readings). `--set` changes one key of the configuration as it is run:
where the program has a lower-precision path of its own, the program
with that path switched on is a control too, and its `program` reading
has to fail. The benchmark's own runs never run this. One line of JSON
per seed goes to standard output and to chiprun_out/control_<W>.jsonl.
"""

import argparse
import gc
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import harness  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--set", default=None, metavar="KEY.PATH=VALUE",
                    help="one key of the configuration, changed for "
                         "this reading")
    a = ap.parse_args()
    out_path = os.path.join(harness.ROOT, "chiprun_out",
                            f"control_{a.workload}.jsonl")
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    for seed in (int(s) for s in a.seeds.split(",")):
        ctx = harness.open_cell(a.workload, seed, a.seconds, False,
                                time.perf_counter())
        cell = ctx.cell
        row = {"workload": a.workload, "seed": seed}
        if a.set:
            path, value = a.set.split("=", 1)
            *groups, key = path.split(".")
            where = ctx.config
            for g in groups:
                where = where[g]
            where[key] = type(where[key])(value)
            row["set"] = a.set
        kind = cell.kind().Kind(ctx)
        kind.setup()
        kind.window()
        kind.release()
        row["program"] = harness.checks_dict(kind.check())
        for name, checks in kind.control().items():
            row[name] = harness.checks_dict(checks)
        if hasattr(kind, "gaps"):       # every token's gap, for the study
            row["gaps"] = dict(kind.control_gaps, program=kind.gaps)
            row["lens"] = [[len(p), len(s)] for p, s in kind.pairs]
        row["wall_s"] = time.perf_counter() - ctx.t0
        line = json.dumps(row)
        print(line, flush=True)
        with open(out_path, "a") as f:
            f.write(line + "\n")
        del kind, ctx, cell     # closures tie them in cycles: collect
        gc.collect()
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    os._exit(code)
