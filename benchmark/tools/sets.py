#!/usr/bin/env python3
"""Two sets of runs of one cell, the same seeds in both, as the driver's
check makes them: each run a process of its own (``run.py``), one after the
other.  Prints every result line and, per end-to-end metric, each set's
median and spread (interquartile range over the median, quartiles as
``statistics.quantiles(values, n=4)`` gives them) — what a bound is set from
(PERF.md section 2).

    python3 benchmark/tools/sets.py --workload <cell> --seeds 1,2,3,4,5,6 [--sets 2] [--seconds N]
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def spread(values: list) -> float:
    q1, _q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--seconds", type=int, default=None)
    args = ap.parse_args(argv)
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
        bench = json.load(f)
    seconds = args.seconds or bench["run_seconds"]
    seeds = [int(s) for s in args.seeds.split(",")]
    sets = []
    for k in range(args.sets):
        lines = []
        for seed in seeds:
            out = subprocess.run(
                bench["command"] + ["--workload", args.workload, "--seed", str(seed),
                                    "--seconds", str(seconds), "--trace", "0"],
                cwd=ROOT, stdout=subprocess.PIPE, check=False)
            text = out.stdout.decode().strip().splitlines()
            doc = json.loads(text[-1]) if out.returncode == 0 and text else None
            print(json.dumps({"set": k, "seed": seed, "rc": out.returncode, "line": doc,
                              "detail": text[:3]}), flush=True)
            if doc is not None:
                lines.append(doc)
        sets.append(lines)
    names = sorted({m for lines in sets for doc in lines for m in doc["metrics"]})
    for name in names:
        row = {"metric": name}
        for k, lines in enumerate(sets):
            values = [doc["metrics"][name]["value"] for doc in lines if name in doc["metrics"]]
            if len(values) >= 2:
                row[f"set{k}"] = {"n": len(values), "median": statistics.median(values),
                                  "spread": spread(values), "values": values}
        if all(f"set{k}" in row for k in (0, 1)):
            row["second_over_first"] = row["set1"]["median"] / row["set0"]["median"] - 1.0
        print(json.dumps(row), flush=True)
    wrong = [doc for lines in sets for doc in lines if not doc["correct"]]
    print(json.dumps({"runs": sum(len(x) for x in sets), "not_correct": len(wrong)}))
    return 1 if wrong else 0


if __name__ == "__main__":
    sys.exit(main())
