"""Generator kind ``snapshot``: a what-if against a cluster that is ALREADY
RUNNING.  The job's body carries the cluster as ``spec.simulator.initialSnapshot``
(a ``ResourcesForSnap`` document, what the reference simulator's export and
``docs/import-cluster-resources.md`` produce: nodes, Running pods with
``spec.nodeName``) and a KEP-140 scenario of ONE step on top of it: a
Deployment of the snapshot scaled up.

``generator`` block of the configuration:

- ``base_seed``: draws everything that is drawn (how many pods a node runs,
  which Deployment's pods share a node).  ``--seed`` orders the scenario's
  pod creations and nothing else (``generators.shuffle_operations``).
- ``nodeTemplate`` / ``labelKey`` / ``labelValues``: the nodes, named
  ``<template>-<i>`` and labelled round-robin by index as ``scheduler_perf``'s
  ``labelNodePrepareStrategy`` does (``kinds/sperf_labels.py``).
- ``scaledTemplate``: the Deployment that is scaled.  The snapshot holds
  ``replicas[z]`` of its pods on the nodes of ``labelValues[z]`` (dealt
  round-robin over that zone's nodes) and the scenario creates ``rollout``
  more, the names counting on (``<template>-<replicas>`` ...).
- ``fillTemplate`` / ``deployments``: every other pod of the snapshot.  Their
  names are ``<template>-<i>``; pod ``i`` carries ``labels[deployments.labelKey]
  = <group>-<n>``, the groups cut as clusterloader2's load test cuts them:
  ``shares[g]`` of the pods in Deployments of ``sizes[g]`` pods, what is left
  over in the last group.  They are dealt over the nodes' free places in an
  order drawn from ``base_seed``.
- ``workloads`` / ``workload``: the sizes ``{nodes, podsPerNode: {mean,
  spread}, replicas, rollout}``; the traffic file's ``workload`` takes the
  configuration's place.  A node runs ``mean - spread`` .. ``mean + spread``
  pods, drawn uniformly and then moved one pod at a time until the cluster
  holds exactly ``nodes x mean``.

``inputs`` returns ``units`` = the scenario's operations (what a job's
``eventsApplied`` counts: the snapshot's objects are no events), ``steps`` = 1
and, as ``operations`` — all that ``run.replayed_counts`` and ``placements.py``
hand the plain reference — the snapshot's nodes and pods as creations at step
``SNAPSHOT_STEP`` (-1), before the scenario's step 0
(``references/snapshot_zoned.py`` reads a negative step as the state a job
starts from).  Imports nothing of the program.
"""

from __future__ import annotations

import json
import random

from generators import create_op, shuffle_operations
from kinds import sperf

#: The step the snapshot's objects stand at in ``operations``.
SNAPSHOT_STEP = -1


def loads(rng: random.Random, nodes: int, mean: int, spread: int) -> list:
    """Pods a node: uniform in ``mean +- spread``, summing to ``nodes * mean``."""
    low, high = mean - spread, mean + spread
    counts = [rng.randint(low, high) for _ in range(nodes)]
    over = sum(counts) - nodes * mean
    while over:
        i = rng.randrange(nodes)
        if over > 0 and counts[i] > low:
            counts[i] -= 1
            over -= 1
        elif over < 0 and counts[i] < high:
            counts[i] += 1
            over += 1
    return counts


def deployment_labels(spec: dict, total: int) -> list:
    """One ``<group>-<n>`` a pod: ``shares[g]`` of ``total`` in Deployments of
    ``sizes[g]``, whole Deployments only, the rest in the last group."""
    out = []
    groups = list(zip(spec["groups"], spec["sizes"], spec["shares"]))
    for g, (group, size, share) in enumerate(groups):
        last = g == len(groups) - 1
        pods = total - len(out) if last else int(total * share) // size * size
        out += [f"{group}-{k // size}" for k in range(pods)]
    return out


def named(text: str, name: str) -> dict:
    """The pod ``text`` (a template as JSON: parsing it is a deep copy at a
    tenth of ``copy.deepcopy``'s price) under ``name`` in ``default``."""
    pod = json.loads(text)
    pod["metadata"].update(name=name, namespace="default")
    return pod


def running(text: str, name: str, node: str) -> dict:
    pod = named(text, name)
    pod["spec"]["nodeName"] = node
    pod["status"] = {"phase": "Running"}
    return pod


def cluster(gen: dict, workload: str) -> "tuple[list, list, list]":
    """The snapshot's nodes and pods, and the scenario's operations."""
    size = gen["workloads"][workload]
    rng = random.Random(f"snapshot:{gen['base_seed']}")
    values, key = list(gen["labelValues"]), gen["labelKey"]
    node_text = json.dumps(sperf.template(gen["nodeTemplate"]))
    nodes = []
    for i in range(size["nodes"]):
        node = json.loads(node_text)
        node["metadata"]["name"] = f"{gen['nodeTemplate']}-{i}"
        node["metadata"].setdefault("labels", {})[key] = values[i % len(values)]
        nodes.append(node)
    names = [n["metadata"]["name"] for n in nodes]
    per = size["podsPerNode"]
    free = loads(rng, len(nodes), per["mean"], per["spread"])
    scaled, pods = json.dumps(sperf.template(gen["scaledTemplate"])), []
    for z, replicas in enumerate(size["replicas"]):
        zone = range(z, len(nodes), len(values))
        for j in range(replicas):
            i = zone[j % len(zone)]
            free[i] -= 1
            pods.append(running(scaled, f"{gen['scaledTemplate']}-{len(pods)}", names[i]))
    if min(free) < 0:
        raise ValueError("snapshot: more replicas on a node than it runs pods")
    fill, spec = json.dumps(sperf.template(gen["fillTemplate"])), gen["deployments"]
    places = [i for i, n in enumerate(free) for _ in range(n)]
    rng.shuffle(places)
    for k, (i, label) in enumerate(zip(places, deployment_labels(spec, len(places)))):
        pod = running(fill, f"{gen['fillTemplate']}-{k}", names[i])
        pod["metadata"].setdefault("labels", {})[spec["labelKey"]] = label
        pods.append(pod)
    held = sum(size["replicas"])
    scenario = [create_op(0, named(scaled, f"{gen['scaledTemplate']}-{held + j}"))
                for j in range(size["rollout"])]
    return nodes, pods, scenario


def snapshot_document(nodes: list, pods: list) -> dict:
    """``ResourcesForSnap`` (the reference simulator's export): every key,
    the kinds this cluster has none of empty."""
    return {"pods": pods, "nodes": nodes, "pvs": [], "pvcs": [], "storageClasses": [],
            "priorityClasses": [], "schedulerConfig": None,
            "namespaces": [{"apiVersion": "v1", "kind": "Namespace",
                            "metadata": {"name": "default"}}]}


def inputs(config: dict, traffic: dict, seed: int) -> dict:
    gen = config["generator"]
    nodes, pods, scenario = cluster(gen, traffic.get("workload", gen["workload"]))
    scenario = shuffle_operations(seed, scenario)
    simulator = dict(config["simulator"], initialSnapshot=snapshot_document(nodes, pods))
    body = {"spec": {"simulator": simulator, "scenario": {"operations": scenario}}}
    return {"body": json.dumps(body).encode(), "units": len(scenario),
            "operations": [create_op(SNAPSHOT_STEP, obj) for obj in nodes + pods] + scenario,
            "steps": 1}
