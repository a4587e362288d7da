"""The plain reference of a deployment that runs the DEFAULT scheduler
configuration on more than 100 ZONE-LABELLED nodes: sampled scoring
(``percentageOfNodesToScore``) whose walk goes through the scheduler
cache's node tree.  ``references/sampled.py``'s sequential scheduler, one pod
at a time, over the tree's list; written from upstream kube-scheduler v1.30
``pkg/scheduler/internal/cache/node_tree.go``, ``cache.go``
(``updateNodeInfoSnapshotList``) and ``schedule_one.go``, and from nothing of
the program.

Upstream does not walk the nodes in name or creation order:

- ``GetZoneKey(node)``: region and zone from the node's labels
  (``topology.kubernetes.io/region`` / ``topology.kubernetes.io/zone``, else the
  legacy ``failure-domain.beta.kubernetes.io/region`` / ``zone``); both empty:
  ``""``; else ``region + ":\\x00:" + zone``.
- ``nodeTree``: ``zones``, a list in order of first appearance, and per zone the
  node names in the order they were added.  ``addNode`` appends to the node's
  zone (a new zone is appended to ``zones``); ``removeNode`` takes the name out
  of its zone's list and drops a zone that is left empty; ``updateNode`` of a
  node whose zone key changed removes and adds it.
- ``list()``: for i = 0, 1, ...: for each zone in ``zones`` order, the zone's
  i-th node if it has one; until every node is out.
- The snapshot's node list is that list, and ``findNodesThatPassFilters`` walks
  it from ``sched.nextStartNodeIndex`` (``references/sampled.py`` has the rest:
  ``numFeasibleNodesToFind``, the wrap, the index moving on by the nodes
  processed).

**Departures, each the simulator's stated convention** (docs/jobs.md):

- "The order they were added": a KEP-140 step has no order inside it that a
  scheduler could see, so the nodes that join in one step join BY NAME, after
  the step's removals (upstream adds them as their watch events arrive).  A
  node deleted and created again under one name within one step, with the same
  zone key, is the same node to the tree and keeps its place.
- The sequential walk stands for upstream's racy one (16 filter workers), as in
  ``references/sampled.py``.
- Equal totals go to the first node in the SIMULATOR's node order
  (``replay.Cluster``: by name among nodes that join together, a node that goes
  hands its place to the last), not in the tree's: the tree orders the walk and
  nothing else.  Upstream draws among them at random.

Nodes with no zone label are one zone ``""``: with no node ever removed the
list is the simulator's node order, and this file gives what
``references/sampled.py`` gives.

``interleave=False`` is **the control**: the walk in the simulator's node order
(name order), which IS ``references/sampled.py``: on zone-labelled nodes the
same three counts, other sums of visited nodes, another digest.  Besides what
``references/sampled.py`` returns the result carries ``sampling_zones`` (zones
in the tree at the end).  DefaultPreemption under sampling: ``NotCovered``.
"""

from __future__ import annotations

import numpy as np

from references.sampled import num_feasible_nodes_to_find, walk
from replay import (FLUSH_CAP_PASSES, MAX_BACKOFF_PASSES, Cluster, NotCovered, Pod,
                    PriorityClasses, feasible_with_nominated, total_scores)

ZONE = ("topology.kubernetes.io/zone", "failure-domain.beta.kubernetes.io/zone")
REGION = ("topology.kubernetes.io/region", "failure-domain.beta.kubernetes.io/region")


def get_zone_key(node: dict) -> str:
    labels = (node.get("metadata") or {}).get("labels") or {}
    zone = next((labels[k] for k in ZONE if labels.get(k)), "")
    region = next((labels[k] for k in REGION if labels.get(k)), "")
    return "" if not zone and not region else region + ":\x00:" + zone


class NodeTree:
    """node_tree.go, on names."""

    def __init__(self) -> None:
        self.zones: list = []   # zone keys, in order of first appearance
        self.tree: dict = {}    # zone key -> node names, in the order added
        self.zone_of: dict = {}

    def add(self, name: str, zone: str) -> None:
        if name in self.zone_of:
            if self.zone_of[name] == zone:
                return
            self.remove(name)
        if zone not in self.tree:
            self.tree[zone] = []
            self.zones.append(zone)
        self.tree[zone].append(name)
        self.zone_of[name] = zone

    def remove(self, name: str) -> None:
        zone = self.zone_of.pop(name)
        self.tree[zone].remove(name)
        if not self.tree[zone]:
            del self.tree[zone]
            self.zones.remove(zone)

    def list(self) -> list:
        out, i = [], 0
        while len(out) < len(self.zone_of):
            for zone in self.zones:
                if i < len(self.tree[zone]):
                    out.append(self.tree[zone][i])
            i += 1
        return out


def replay(operations: list, *, max_pods_per_pass: "int | None" = None,
           precision: str = "exact", interleave: bool = True, percentage: int = 0) -> dict:
    """Replay ``operations`` (KEP-140 ``spec.operations``) under sampled
    scoring over the node tree's list; returns what
    ``references.sampled.replay`` returns, and ``sampling_zones``."""
    cl, pods, classes, tree = Cluster(), {}, PriorityClasses(), NodeTree()
    backoff: dict = {}   # pod -> (attempts, the last pass it sits out)
    born: list = []      # pods created with a nodeName, charged once their node has a place
    passes = events = scheduled = unschedulable = 0
    start = sampled = visited_sum = scored_sum = 0
    order = None         # the columns of ``cl`` in the order of the tree's list
    per_step = []
    by_step: dict = {}
    for op in operations:
        by_step.setdefault(int(op["step"]), []).append(op)
    for step in sorted(by_step):
        batch = by_step[step]
        drained: set = set()
        flush = False
        joined: dict = {}    # the step's net node events, for the tree
        gone: list = []
        for op in batch:
            if "createOperation" in op:
                obj = op["createOperation"]["object"]
                if obj["kind"] == "Node":
                    cl.add(obj)
                    joined[obj["metadata"]["name"]] = get_zone_key(obj)
                    flush = True
                elif obj["kind"] == "Pod":
                    pod = Pod(obj, classes)
                    pods[pod.name] = pod
                    if pod.born_on:
                        pod.node = pod.born_on
                        born.append(pod)
                elif obj["kind"] == "PriorityClass":
                    classes.add(obj)
                else:
                    raise NotCovered(f"creation of a {obj['kind']}")
            elif "deleteOperation" in op:
                kind = op["deleteOperation"]["typeMeta"]["kind"]
                name = op["deleteOperation"]["objectMeta"]["name"]
                flush = True
                if kind == "Node":
                    drained |= cl.remove(name)
                    if joined.pop(name, None) is None:
                        gone.append(name)
                elif kind == "Pod":
                    pod = pods.pop(name, None)
                    if pod is None:
                        raise NotCovered(f"deletion of the pod {name}, which is gone")
                    backoff.pop(name, None)
                    if pod in born:
                        born.remove(pod)
                    elif pod.node is not None and pod.name not in drained:
                        cl.charge(pod, cl.place[pod.node], -1)
                else:
                    raise NotCovered(f"deletion of a {kind}")
            else:
                raise NotCovered(f"operation {sorted(set(op) - {'step'})}")
        events += len(batch)
        if gone or joined:
            # The step's removals, then the nodes that join, by name.  A name
            # that went and came back with its zone key is the same node.
            for name in gone:
                if joined.get(name) != tree.zone_of[name]:
                    tree.remove(name)
            for name in sorted(joined):
                tree.add(name, joined[name])
            order = None
        for name in drained:   # the pods of a drained node queue again
            if name in pods:
                pods[name].node = None
        if flush:
            backoff = {k: (n, min(last, passes + min(n - 1, FLUSH_CAP_PASSES)))
                       for k, (n, last) in backoff.items()}
        done = [0, 0]
        if cl.live:
            passes += 1
            queue = sorted((p for p in pods.values() if p.node is None
                            and not (p.name in backoff and backoff[p.name][1] >= passes)),
                           key=lambda p: p.queue_key)
            if queue or born:
                cl.sync()
            for pod in born:
                if pod.node not in cl.place:
                    raise NotCovered(f"a pod created on {pod.node}, which is no node")
                cl.charge(pod, cl.place[pod.node], +1)
            born = []
            nodes = len(cl.names)
            want = num_feasible_nodes_to_find(nodes, percentage)
            for pod in queue[:max_pods_per_pass]:
                ok = feasible_with_nominated(pod, cl, pods)
                sample = ok
                if want < nodes:
                    if order is None:
                        order = np.array([cl.place[n] for n in tree.list()] if interleave
                                         else range(nodes), np.int64)
                    seen, found, start = walk(ok[order], start % nodes, want)
                    sample = np.zeros(nodes, bool)
                    sample[order] = found
                    sampled += 1
                    visited_sum += int(seen.sum())
                    scored_sum += int(found.sum())
                if sample.any():
                    total = total_scores(pod, cl, pods, sample, precision)
                    best = int(np.argmax(np.where(sample, total, np.iinfo(np.int64).min)))
                    pod.node = cl.names[best]
                    cl.charge(pod, best, +1)
                    backoff.pop(pod.name, None)
                    done[0] += 1
                    continue
                if cl.has("bound", lambda priority: priority < pod.priority):
                    raise NotCovered("DefaultPreemption under sampled scoring")
                done[1] += 1
                attempts = backoff.get(pod.name, (0, 0))[0] + 1
                backoff[pod.name] = (attempts, passes + min(2 ** (attempts - 1), MAX_BACKOFF_PASSES))
        scheduled += done[0]
        unschedulable += done[1]
        per_step.append(tuple(done))
    return {"eventsApplied": events, "podsScheduled": scheduled,
            "unschedulableAttempts": unschedulable, "steps": per_step,
            "placements": {p.name: p.node for p in pods.values()},
            "sampled_attempts": sampled, "nodes_visited": visited_sum,
            "nodes_scored": scored_sum, "sampling_start": start,
            "sampling_zones": len(tree.zones)}
