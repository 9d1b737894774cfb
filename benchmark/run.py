"""python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One run of one cell of BENCHMARK.json on the chips this machine holds.
The last line of standard output is the result; see README.md."""

import time

T0 = time.perf_counter()     # set-up is counted from the process's start

import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import harness  # noqa: E402

if __name__ == "__main__":
    try:
        code = harness.run(sys.argv[1:], T0)
    except harness.NoChip as e:
        sys.stderr.write(str(e) + "\n")
        code = 2
    sys.stdout.flush()
    sys.stderr.flush()
    # daemon threads of the program (its step loop is closed by now)
    # must not hold the exit
    os._exit(code)
