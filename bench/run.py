"""Run one benchmark cell; the last line of standard output is the result.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>
    python3 bench/run.py --workload <cell> --seed 1 --seconds 2 --rehearse

See ``bench/harness.py`` for what a run does.
"""
import time

T_START = time.perf_counter()

import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import harness  # noqa: E402

if __name__ == "__main__":
    print(json.dumps(harness.main(t_start=T_START)), flush=True)
