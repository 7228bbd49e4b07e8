"""Entry point of the benchmark; see `bench/harness.py`.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>
"""
import time

T0 = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

from bench.harness import parse, run  # noqa: E402

if __name__ == "__main__":
    sys.exit(run(parse(sys.argv[1:]), t0=T0))
