"""The benchmark's one command.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the checkout root; it puts the checkout and its ``src/`` on the
path itself.  The last line of standard output is the result as one JSON
object.  Without a TPU, or with fewer chips than the cell asks for, it
exits non-zero and prints no result.
"""
import os
import sys
import time

T_START = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

if __name__ == "__main__":
    from bench.harness import main

    sys.exit(main(t_start=T_START))
