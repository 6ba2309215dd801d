"""Readings for the comparison's limits, on the chip, in one process.

For each seed: one run of the cell (set-up, a window of ``--seconds``,
the drain) and the numbers the benchmark compares for the requests it
samples; for the first ``--control`` seeds, the same numbers for each
control (``reference.CONTROLS``: the reference's first choices with its
GEMMs in int8, and in float8) at the same positions.  Every reading goes
through the benchmark's own verdict (``harness.correct_of``): the
program's has to come out correct and each control's not.  The limits in
``bench/workloads/<cell>.json`` are set from these readings (PERF.md).

    python3 bench/control.py --workload <cell> --seeds 1,2,...,12 --seconds 51 --control 3

Exits 1 where a verdict comes out the other way.
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", type=int, default=3)
    args = ap.parse_args(argv)

    from bench import harness, reference
    from bench.spec import load_cell

    cell = load_cell(args.workload)
    harness.place_cache(cell.root)
    wrong = 0
    for j, seed in enumerate(int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        head, run = harness.serve(cell, seed, args.seconds, False, t0)
        line = {"seed": seed, "attempted": head["attempted"], "failed": head["failed"],
                "metrics": {k: v["value"] for k, v in head["metrics"].items()},
                "memory_peak_bytes": head["device"]["memory_peak_bytes"]}
        for name in (None,) + (reference.CONTROLS if j < args.control else ()):
            t1 = time.perf_counter()
            numbers = harness.compare(cell, seed, run, name)
            ok = harness.correct_of(numbers)
            wrong += ok != (name is None)
            line[name or "program"] = {"correct": ok, "seconds": time.perf_counter() - t1,
                                       **{k: v["value"] for k, v in numbers.items()}}
        print("control: " + json.dumps(line), flush=True)
    print(f"control: {wrong} verdict(s) the wrong way", flush=True)
    return 1 if wrong else 0


if __name__ == "__main__":
    sys.exit(main())
