"""The plain reference of a job that STARTS FROM A SNAPSHOT
(``spec.simulator.initialSnapshot``) under the default scheduler configuration
on zone-labelled nodes: ``references/sampled_zoned.py``'s sequential scheduler
over a cluster that is already running.  Imports nothing of the program.

``operations`` are KEP-140 ``spec.operations``; those at a NEGATIVE step are the
snapshot's objects (``kinds/snapshot.py`` writes them at step -1).  They are the
state the job starts from and not part of its scenario:

- They are no events: ``eventsApplied`` counts the scenario's operations alone.
- They run no scheduling pass of their own and add no entry to ``steps``: the
  job's first pass is that of the scenario's first step, and a pending pod of
  the snapshot is queued there beside that step's arrivals.
- The snapshot's nodes join the node tree in ONE step, by name, with whatever
  nodes the scenario's first step creates (the scheduler service meets them
  all at its first pass; ``references/sampled_zoned.py``: nodes that join in
  one step join by name).
- Its bound pods (``spec.nodeName`` set) are charged to their nodes before the
  first attempt: their requests, their place among the node's pods, their
  labels wherever a spread constraint or an affinity term counts them.

So with ``charge_snapshot=True`` this is ``sampled_zoned.replay`` over the same
operations with the snapshot moved into the scenario's first step, less the
snapshot's share of the events.  A snapshot with no scenario step after it
schedules nothing.

**The controls.**  ``charge_snapshot=False``: a program that schedules as if
the cluster were empty — the snapshot's bound pods stand where they stood (they
are in ``placements``) but no node is charged for them: other walks
(``nodes_visited``), other placements, another digest.  ``interleave=False``
(the walk in name order) and ``precision="bf16"`` are ``sampled_zoned``'s.
"""

from __future__ import annotations

from references import sampled_zoned


def replay(operations: list, *, max_pods_per_pass: "int | None" = None,
           precision: str = "exact", interleave: bool = True, percentage: int = 0,
           charge_snapshot: bool = True) -> dict:
    """Replay the scenario of ``operations`` on top of their snapshot; returns
    what ``sampled_zoned.replay`` returns."""
    snapshot = [op for op in operations if int(op["step"]) < 0]
    scenario = [op for op in operations if int(op["step"]) >= 0]
    objects = [op["createOperation"]["object"] for op in snapshot]
    stands = {obj["metadata"]["name"]: obj["spec"].get("nodeName") or None
              for obj in objects if obj["kind"] == "Pod"}
    if not scenario:
        return {"eventsApplied": 0, "podsScheduled": 0, "unschedulableAttempts": 0, "steps": [],
                "placements": stands, "sampled_attempts": 0, "nodes_visited": 0,
                "nodes_scored": 0, "sampling_start": 0, "sampling_zones": 0}
    bound = {} if charge_snapshot else {name: node for name, node in stands.items() if node}
    snapshot = [op for op, obj in zip(snapshot, objects)
                if obj["kind"] != "Pod" or obj["metadata"]["name"] not in bound]
    first = min(int(op["step"]) for op in scenario)
    got = sampled_zoned.replay(
        [dict(op, step=first) for op in snapshot] + scenario,
        max_pods_per_pass=max_pods_per_pass, precision=precision, interleave=interleave,
        percentage=percentage)
    got["eventsApplied"] -= len(snapshot)
    got["placements"] = dict(bound, **got["placements"])   # uncharged pods stand where they stood
    return got
