#!/usr/bin/env python3
"""Where every pod stands at a job's end, in 64 characters: the digest a
job's result document carries as ``replay.placements_digest``, computed here
from the cell's plain reference (``replay.py``, or the one its configuration
names) and from nothing of the program.  A configuration that lists it under ``guarantees.replay_equals``
holds every job's placements to the reference's, pod by pod — the counts
alone do not show a pod that landed elsewhere (in ``burst-5k`` the control at
bfloat16 moves 8,941 of 10,000 pods and none of the counts).

    python3 benchmark/placements.py --workload <cell> [--rehearsal]

prints the digest of the exact replay (the number for the configuration's
file) and, beside it, the control's and how many pods the control moves.

The digest: sha256 over one line ``<namespace>/<name> <node>\\n`` per pod left
in the cluster, lines sorted, an unbound pod's node empty.  ``replay.py``
covers the default namespace only.
"""

from __future__ import annotations

import argparse
import hashlib
import json
import sys


def digest(placements: dict) -> str:
    """``placements``: pod name -> node name or ``None``, as ``replay.replay`` returns them."""
    lines = sorted(f"default/{name} {node or ''}\n" for name, node in placements.items())
    return hashlib.sha256("".join(lines).encode()).hexdigest()


def main(argv=None) -> int:
    import run as harness

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args(argv)
    c = harness.load_cell(harness.load("BENCHMARK.json"), args.workload, args.rehearsal)
    inputs = harness.build_inputs(c["config"], c["traffic"], 0)
    cap = c["config"]["simulator"].get("maxPodsPerPass")
    replay = c["reference"].replay
    exact = replay(inputs["operations"], max_pods_per_pass=cap)["placements"]
    control = replay(inputs["operations"], max_pods_per_pass=cap, precision="bf16")["placements"]
    print(json.dumps({
        "workload": args.workload, "rehearsal": args.rehearsal, "pods": len(exact),
        "placements_digest": digest(exact), "control_digest": digest(control),
        "control_moved": sum(1 for name, node in exact.items() if control.get(name) != node),
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
