#!/usr/bin/env python3
"""Many seeds of one cell against ONE server: how ``correct``'s limits were
read (PERF.md section 2).  Set-up is most of a run, so the dozen seeds a
limit needs, and the control beside them, share a process here; the
benchmark's own runs never call this.

    python3 benchmark/tools/seeds.py --workload <cell> --seeds 1,2,3 --seconds 10
                                     [--base-seeds 0,1,2] [--control]

Each seed goes through ``run.drive`` and ``run.judge``, exactly as a run of
``run.py`` does.  ``--base-seeds`` draws the deployment's *objects* from other
seeds than the configuration's own (other clusters, other streams: the plain
reference is their judge, they have no lock); the server compiles their new
shapes.  ``--control`` adds the control's reading, the plain reference at
bfloat16 put in the program's place and judged like the program:

- an ``import`` cell: ``reference.as_export`` over the same exports and
  sample — its ``score_mismatch_share`` has to lie far above the program's;
- a ``job`` cell: the counts of ``replay.replay(..., precision="bf16")`` in
  the job's result document — they have to differ from the exact replay's.
"""

from __future__ import annotations

import argparse
import copy
import json
import os
import random
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import checks
import reference
import run as harness


def control(c: dict, inputs: dict, seed: int, got: dict, device: dict) -> list:
    """The control's readings for one seed: ``{"control", "correct", ...}``."""
    if c["is_job"]:
        counts = harness.replayed_counts(c, inputs, "bf16")
        doc = copy.deepcopy(got["win"]["counted"][0]["doc"])
        doc["result"].update(zip(checks.COUNT_KEYS, counts))
        win = {"counted": [dict(got["win"]["counted"][0], doc=doc)], "failed": 0}
        cmp_b = harness.judge(c, inputs, seed, win, [], [], device, got["ident"])
        return [{"control": "replay at bfloat16", "counts": counts,
                 "correct": all(x["ok"] for x in cmp_b),
                 "failing": [x for x in cmp_b if not x["ok"]]}]
    names = sorted(p["metadata"]["name"] for p in inputs["pods"])
    n = min(c["guarantees"]["reference_sample_pods"], len(names))
    out = []
    for index, raw in got["reservoir"]:
        pods = json.loads(raw)["pods"]
        sample = set(random.Random(seed + index).sample(names, n))
        served = reference.as_export(inputs["nodes"], pods, sample, "bf16")
        cmp_b = harness.reference_comparisons(inputs["nodes"], pods, sample, c["guarantees"], served)
        out.append({"control": "reference at bfloat16", "correct": all(x["ok"] for x in cmp_b),
                    "reference": {x["name"]: x["value"] for x in cmp_b}})
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--base-seeds", default=None,
                    help="comma-separated; default: the configuration's own")
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args(argv)
    bench = harness.load("BENCHMARK.json")
    cell = harness.load_cell(bench, args.workload, args.rehearsal)
    bases = [int(s) for s in args.base_seeds.split(",")] if args.base_seeds else [None]
    server, work = harness.start_server(cell, False)
    bad = 0
    try:
        for base in bases:
            c = cell if base is None else dict(cell, config=harness.overlay(
                cell["config"], {"generator": {"base_seed": base}}))
            for seed in [int(s) for s in args.seeds.split(",")]:
                inputs = harness.build_inputs(c["config"], c["traffic"], seed)
                got = harness.drive(server, c, inputs, seed, args.seconds)
                win, ident = got["win"], got["ident"]
                device = {"platform": ident.get("platform"), "count": ident.get("device_count")}
                comparisons = harness.judge(c, inputs, seed, win, got["warm"], got["reservoir"],
                                            device, ident)
                failing = [x for x in comparisons if not x["ok"]]
                bad += bool(failing)
                print(json.dumps({
                    "base_seed": c["config"]["generator"]["base_seed"], "seed": seed,
                    "correct": not failing, "counted": len(win["counted"]),
                    "wall_s": "not measured" if args.rehearsal
                    else [round(r["wall_s"], 4) for r in win["counted"]],
                    "cache_entries": win["cache_entries"],
                    "job_counts": sorted({tuple(checks.job_counts(r["doc"])) for r in win["counted"]})
                    if c["is_job"] else None,
                    "reference": {x["name"]: x["value"] for x in comparisons
                                  if x["name"].startswith("reference.")},
                    "failing": failing,
                }), flush=True)
                if args.control:
                    for reading in control(c, inputs, seed, got, device):
                        print(json.dumps(dict(reading, seed=seed, base_seed=base)), flush=True)
    finally:
        server.stop()
        print(json.dumps({"device_report": harness.device_report(work)}))
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
