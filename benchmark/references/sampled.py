"""The plain reference of a deployment that runs the DEFAULT scheduler
configuration on more than 100 nodes: sampled scoring
(``percentageOfNodesToScore``).  A sequential scheduler, one pod at a time,
written from upstream kube-scheduler v1.30 ``pkg/scheduler/schedule_one.go``
and from nothing of the program.

Upstream, for every pod (``schedulePod`` -> ``findNodesThatFitPod`` ->
``findNodesThatPassFilters``, then ``prioritizeNodes`` and ``selectHost``):

- ``numFeasibleNodesToFind``: under 100 nodes, all of them.  Else a share of
  the nodes: the profile's or the configuration's ``percentageOfNodesToScore``
  or, where that is unset (the default), ``50 - nodes / 125`` per cent and at
  least 5; the share, in whole nodes, and at least 100.  At 5,000 nodes 10 % =
  500, at 500 nodes 46 % = 230.
- The node list is walked from ``sched.nextStartNodeIndex``, wrapping, and
  the walk stops when that many feasible nodes are found; the index moves on
  by the nodes processed (``processedNodes = feasible found + failed``),
  modulo the node count.
- Score, NormalizeScore and the choice run over the feasible nodes found, and
  over no other.

**The contract where upstream leaves the outcome to chance** (upstream filters
with 16 parallel workers, so which nodes were "processed" when the count was
reached is racy): the sequential walk.  Nodes in the simulator's node order
(``replay.Cluster``: a new node joins at the end, by name among those that join
together; a node that goes hands its place to the last) from ``start``; stop
when k feasible nodes are found or every node was seen; visited = every node
seen, sample = the feasible among them; ``start <- (start + visited) mod
nodes``; equal totals go to the first node in node order, as everywhere in
this benchmark.  One ``start`` for the life of the scheduler.  (For nodes with
no zone label, as ``node-default``, upstream's node tree is one zone: no
interleaving to model.)

Covered: what ``replay.py`` covers of objects, filters and scores (its
``Cluster``, ``Pod``, ``feasible_with_nominated`` and ``total_scores``, which
normalises over the mask it is given), creations and deletions, the
pass-counted backoff.  **Not** DefaultPreemption: a pod that fits nowhere
while a pod of a lower priority is bound raises ``NotCovered`` (no deployment
of this benchmark samples and preempts; a pod that fails has seen every node,
so the walk would change nothing of the search).

``walk=False`` is the control: the same loop scoring every feasible node,
which is ``replay.replay``.  Besides the three counts and the placements the
result carries what the walk counted: ``sampled_attempts`` (attempts that
walked for a sample), ``nodes_visited`` and ``nodes_scored`` (summed over
them) and ``sampling_start`` (the index the replay leaves).
"""

from __future__ import annotations

import numpy as np

from replay import (FLUSH_CAP_PASSES, MAX_BACKOFF_PASSES, Cluster, NotCovered, Pod,
                    PriorityClasses, feasible_with_nominated, total_scores)

#: schedule_one.go: minFeasibleNodesToFind, minFeasibleNodesPercentageToFind.
MIN_FEASIBLE_NODES_TO_FIND = 100
MIN_FEASIBLE_NODES_PERCENTAGE_TO_FIND = 5


def num_feasible_nodes_to_find(nodes: int, percentage: int = 0) -> int:
    """How many feasible nodes end the walk; ``percentage`` 0 is "unset"."""
    if nodes < MIN_FEASIBLE_NODES_TO_FIND:
        return nodes
    if percentage == 0:
        percentage = max(50 - nodes // 125, MIN_FEASIBLE_NODES_PERCENTAGE_TO_FIND)
    return min(max(nodes * percentage // 100, MIN_FEASIBLE_NODES_TO_FIND), nodes)


def walk(ok: np.ndarray, start: int, want: int) -> "tuple[np.ndarray, np.ndarray, int]":
    """findNodesThatPassFilters, sequentially: (visited, sample, next start)
    of one attempt over the feasibility mask ``ok`` in node order."""
    n = len(ok)
    order = (start + np.arange(n)) % n
    found = np.flatnonzero(ok[order])
    processed = int(found[want - 1]) + 1 if len(found) >= want else n
    visited = np.zeros(n, bool)
    visited[order[:processed]] = True
    return visited, visited & ok, (start + processed) % n


def replay(operations: list, *, max_pods_per_pass: "int | None" = None,
           precision: str = "exact", walk_on: bool = True, percentage: int = 0) -> dict:
    """Replay ``operations`` (KEP-140 ``spec.operations``) under sampled
    scoring; returns what ``replay.replay`` returns, and the walk's counts."""
    cl, pods, classes = Cluster(), {}, PriorityClasses()
    backoff: dict = {}   # pod -> (attempts, the last pass it sits out)
    born: list = []      # pods created with a nodeName, charged once their node has a place
    passes = events = scheduled = unschedulable = 0
    start = sampled = visited_sum = scored_sum = 0
    per_step = []
    by_step: dict = {}
    for op in operations:
        by_step.setdefault(int(op["step"]), []).append(op)
    for step in sorted(by_step):
        batch = by_step[step]
        drained: set = set()
        flush = False
        for op in batch:
            if "createOperation" in op:
                obj = op["createOperation"]["object"]
                if obj["kind"] == "Node":
                    cl.add(obj)
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
                if walk_on and want < nodes:
                    visited, sample, start = walk(ok, start % nodes, want)
                    sampled += 1
                    visited_sum += int(visited.sum())
                    scored_sum += int(sample.sum())
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
            "nodes_scored": scored_sum, "sampling_start": start}
