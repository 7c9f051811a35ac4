"""Run one cell of the benchmark.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

See `bench/harness.py` for what a run does and prints.
"""
import os
import sys
import time

T_START = time.perf_counter()
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

if __name__ == "__main__":
    from bench.harness import main
    sys.exit(main(t_start=T_START))
