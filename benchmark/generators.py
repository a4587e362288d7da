"""Seeded input generators — the benchmark's own copies.

``random_cluster`` is a copy of ``tests/helpers.py`` ``random_cluster``
(with ``make_node`` / ``make_pod``) and ``churn_operations`` of
``ksim_tpu/scenario/generate.py`` ``churn_scenario`` folded together with
``ksim_tpu/scenario/spec.py`` ``spec_from_operations``, as they stood at
PR 21: the same ``random.Random(seed)`` draws in the same order, so seed 0
still produces the streams the behaviour locks were taken on.  They live
here so that a later PR to the program cannot move the traffic; nothing in
this file imports the program (or jax), so the harness generates its
inputs in-process before the server — the one process that may touch the
chip — starts.

What ``--seed`` does (``shuffle_*``): a deployment's objects are drawn from
the configuration's own ``base_seed``, and the run's seed sets the order in
which they ARRIVE — the order of the pods in the snapshot document, the order
of the pod creations inside each step of the stream.  The scheduling queue
orders pods by priority and name, not by arrival, so every seed gives the
server the same scheduling problem through a differently ordered document:
the same work, the same answers (the behaviour locks hold at every seed),
other uids, resourceVersions and load order.  Drawing the objects themselves
from ``--seed`` moved the work by 8 % (import) to 40 % (stream) on the chip,
and permuting their names still by 4 % — reordering the queue or the
tie-breaks changes who fits where (PERF.md, PR 24) — far more than two runs
of one seed differ.
"""

from __future__ import annotations

import random
from typing import Any

JSON = dict[str, Any]


def make_node(name: str, cpu: str, memory: str, pods: int, *, labels: dict,
              taints: "list[JSON] | None" = None, unschedulable: bool = False) -> JSON:
    alloc = {"cpu": cpu, "memory": memory, "pods": str(pods), "ephemeral-storage": "100Gi"}
    spec: JSON = {}
    if taints:
        spec["taints"] = taints
    if unschedulable:
        spec["unschedulable"] = True
    return {
        "apiVersion": "v1", "kind": "Node",
        "metadata": {"name": name, "labels": labels},
        "spec": spec,
        "status": {"allocatable": dict(alloc), "capacity": dict(alloc)},
    }


def make_pod(name: str, cpu: "str | None", memory: "str | None", *, labels: dict,
             node_name: str = "", tolerations=None, affinity=None, node_selector=None,
             topology_spread_constraints=None) -> JSON:
    requests: JSON = {}
    if cpu is not None:
        requests["cpu"] = cpu
    if memory is not None:
        requests["memory"] = memory
    spec: JSON = {
        "containers": [
            {"name": "c", "image": "img",
             "resources": {"requests": requests} if requests else {}}
        ]
    }
    if node_name:
        spec["nodeName"] = node_name
    if tolerations:
        spec["tolerations"] = tolerations
    if affinity:
        spec["affinity"] = affinity
    if node_selector:
        spec["nodeSelector"] = node_selector
    if topology_spread_constraints:
        spec["topologySpreadConstraints"] = topology_spread_constraints
    return {
        "apiVersion": "v1", "kind": "Pod",
        "metadata": {"name": name, "namespace": "default", "labels": labels},
        "spec": spec, "status": {},
    }


ZONES = ["zone-a", "zone-b", "zone-c"]
DISKS = ["ssd", "hdd"]
ZONE_KEY = "topology.kubernetes.io/zone"
HOST_KEY = "kubernetes.io/hostname"


def random_cluster(seed: int, n_nodes: int, n_pods: int, *, bound_fraction: float = 0.0,
                   unschedulable_fraction: float = 0.1,
                   pod_affinity_fraction: float = 0.15) -> "tuple[list[JSON], list[JSON]]":
    """Reproducible random cluster; quantities are Mi/milli multiples."""
    rng = random.Random(seed)
    nodes = []
    for i in range(n_nodes):
        taints = []
        if rng.random() < 0.15:
            taints.append({"key": "dedicated", "value": rng.choice(["gpu", "db"]),
                           "effect": "NoSchedule"})
        if rng.random() < 0.15:
            taints.append({"key": "maintenance", "value": "", "effect": "PreferNoSchedule"})
        nodes.append(make_node(
            f"node-{i}",
            cpu=f"{rng.choice([2, 4, 8, 16, 32])}",
            memory=f"{rng.choice([4, 8, 16, 32, 64])}Gi",
            pods=rng.choice([8, 16, 32, 110]),
            unschedulable=rng.random() < unschedulable_fraction,
            labels={ZONE_KEY: rng.choice(ZONES), HOST_KEY: f"node-{i}",
                    "disktype": rng.choice(DISKS)},
            taints=taints or None,
        ))
    apps = ["web", "db", "cache", "batch"]
    pods = []
    for i in range(n_pods):
        bound = rng.random() < bound_fraction
        app = rng.choice(apps)
        spread = None
        if rng.random() < 0.3:
            spread = [{
                "maxSkew": rng.choice([1, 2]),
                "topologyKey": rng.choice([ZONE_KEY, HOST_KEY]),
                "whenUnsatisfiable": rng.choice(["DoNotSchedule", "ScheduleAnyway"]),
                "labelSelector": {"matchLabels": {"app": app}},
            }]
            if rng.random() < 0.3:
                spread.append({
                    "maxSkew": 3, "topologyKey": ZONE_KEY,
                    "whenUnsatisfiable": "ScheduleAnyway",
                    "labelSelector": {"matchLabels": {"app": app}},
                })
        tolerations = []
        if rng.random() < 0.15:
            tolerations.append({"key": "node.kubernetes.io/unschedulable",
                                "operator": "Exists", "effect": "NoSchedule"})
        if rng.random() < 0.25:
            tolerations.append({"key": "dedicated", "operator": rng.choice(["Exists", "Equal"]),
                                "value": "gpu", "effect": "NoSchedule"})
        if rng.random() < 0.15:
            tolerations.append({"key": "maintenance", "operator": "Exists"})
        node_selector = {"disktype": rng.choice(DISKS)} if rng.random() < 0.2 else None
        affinity = None
        if rng.random() < 0.3:
            node_affinity = {}
            if rng.random() < 0.6:
                node_affinity["requiredDuringSchedulingIgnoredDuringExecution"] = {
                    "nodeSelectorTerms": [{"matchExpressions": [
                        {"key": ZONE_KEY, "operator": "In",
                         "values": rng.sample(ZONES, rng.randint(1, 2))}
                    ]}]
                }
            if rng.random() < 0.7:
                node_affinity["preferredDuringSchedulingIgnoredDuringExecution"] = [
                    {"weight": rng.choice([1, 10, 50, 100]),
                     "preference": {"matchExpressions": [
                         {"key": "disktype", "operator": rng.choice(["In", "NotIn"]),
                          "values": [rng.choice(DISKS)]}
                     ]}}
                ]
            if node_affinity:
                affinity = {"nodeAffinity": node_affinity}
        if rng.random() < pod_affinity_fraction:
            tk = rng.choice([ZONE_KEY, HOST_KEY])
            term = {"labelSelector": {"matchLabels": {"app": rng.choice(apps)}},
                    "topologyKey": tk}
            kind = rng.random()
            pod_aff: JSON = {}
            if kind < 0.35:
                pod_aff["podAffinity"] = {
                    "requiredDuringSchedulingIgnoredDuringExecution": [term]}
            elif kind < 0.65:
                pod_aff["podAntiAffinity"] = {
                    "requiredDuringSchedulingIgnoredDuringExecution": [term]}
            else:
                pod_aff["podAffinity"] = {
                    "preferredDuringSchedulingIgnoredDuringExecution": [
                        {"weight": rng.choice([1, 25, 100]), "podAffinityTerm": term}]}
                if rng.random() < 0.5:
                    pod_aff["podAntiAffinity"] = {
                        "preferredDuringSchedulingIgnoredDuringExecution": [{
                            "weight": rng.choice([1, 25, 100]),
                            "podAffinityTerm": {
                                "labelSelector": {"matchLabels": {"app": rng.choice(apps)}},
                                "topologyKey": ZONE_KEY,
                            },
                        }]}
            affinity = {**(affinity or {}), **pod_aff}
        pods.append(make_pod(
            f"pod-{i}",
            cpu=rng.choice([None, "50m", "100m", "250m", "500m", "1", "2"]),
            memory=rng.choice([None, "64Mi", "128Mi", "512Mi", "1Gi", "4Gi"]),
            node_name=f"node-{rng.randrange(n_nodes)}" if bound else "",
            labels={"app": app},
            tolerations=tolerations or None,
            node_selector=node_selector,
            affinity=affinity,
            topology_spread_constraints=spread,
        ))
    return nodes, pods


def _churn_node(rng: random.Random, name: str) -> JSON:
    return make_node(
        name,
        cpu=f"{rng.choice([4, 8, 16, 32])}",
        memory=f"{rng.choice([8, 16, 32, 64])}Gi",
        pods=rng.choice([32, 64, 110]),
        labels={ZONE_KEY: rng.choice(ZONES), HOST_KEY: name,
                "disktype": rng.choice(DISKS)},
    )


def _churn_pod(rng: random.Random, name: str) -> JSON:
    app = rng.choice(["web", "db", "cache", "batch"])
    spread = None
    if rng.random() < 0.2:
        spread = [{
            "maxSkew": rng.choice([1, 2]),
            "topologyKey": rng.choice([ZONE_KEY, HOST_KEY]),
            "whenUnsatisfiable": rng.choice(["DoNotSchedule", "ScheduleAnyway"]),
            "labelSelector": {"matchLabels": {"app": app}},
        }]
    affinity = None
    if rng.random() < 0.1:
        term = {"labelSelector": {"matchLabels": {"app": rng.choice(["web", "db"])}},
                "topologyKey": ZONE_KEY}
        if rng.random() < 0.5:
            affinity = {"podAntiAffinity": {
                "requiredDuringSchedulingIgnoredDuringExecution": [term]}}
        else:
            affinity = {"podAffinity": {
                "preferredDuringSchedulingIgnoredDuringExecution": [
                    {"weight": rng.choice([1, 50, 100]), "podAffinityTerm": term}]}}
    return make_pod(
        name,
        cpu=rng.choice(["100m", "250m", "500m", "1", "2"]),
        memory=rng.choice(["128Mi", "512Mi", "1Gi", "2Gi"]),
        labels={"app": app},
        topology_spread_constraints=spread,
        affinity=affinity,
    )


def _create(step: int, obj: JSON) -> JSON:
    return {"step": step, "createOperation": {"object": obj}}


def _delete(step: int, kind: str, name: str, namespace: str) -> JSON:
    return {"step": step, "deleteOperation": {
        "typeMeta": {"kind": kind},
        "objectMeta": {"name": name, "namespace": namespace},
    }}


def churn_operations(seed: int, *, n_nodes: int, n_events: int, ops_per_step: int,
                     pod_create_frac: float = 0.65,
                     pod_delete_frac: float = 0.25) -> "list[JSON]":
    """The KEP-140 ``spec.operations`` list of the churn stream: the node
    bootstrap (step 0), then steps of ``ops_per_step`` events mixing pod
    arrivals, completions of live pods and node drain/replace pairs.  A
    stream of ``n_events`` is a prefix of any longer one at the same seed."""
    rng = random.Random(seed)
    pod_seq = 0
    node_seq = n_nodes
    live_pods: list[str] = []
    live_nodes = [f"node-{i}" for i in range(n_nodes)]
    ops = [_create(0, _churn_node(rng, name)) for name in live_nodes]
    emitted = n_nodes
    step = 1
    while emitted < n_events:
        budget = min(ops_per_step, n_events - emitted)
        for _ in range(budget):
            r = rng.random()
            if r < pod_create_frac or not live_pods:
                name = f"pod-{pod_seq}"
                pod_seq += 1
                live_pods.append(name)
                ops.append(_create(step, _churn_pod(rng, name)))
            elif r < pod_create_frac + pod_delete_frac:
                victim = live_pods.pop(rng.randrange(len(live_pods)))
                ops.append(_delete(step, "Pod", victim, "default"))
            else:
                gone = live_nodes.pop(rng.randrange(len(live_nodes)))
                ops.append(_delete(step, "Node", gone, ""))
                fresh = f"node-{node_seq}"
                node_seq += 1
                live_nodes.append(fresh)
                ops.append(_create(step, _churn_node(rng, fresh)))
        emitted += budget
        step += 1
    return ops


def shuffle_cluster(seed: int, nodes: list, pods: list) -> "tuple[list, list]":
    """The same snapshot with its pods listed in the order ``seed`` draws."""
    pods = list(pods)
    random.Random(f"pods:{seed}").shuffle(pods)
    return nodes, pods


def shuffle_operations(seed: int, ops: list) -> list:
    """The same stream with the pod creations of each step dealt anew among
    that step's pod-creation slots.  A pod that is also deleted within its
    step keeps its slot (its deletion has to find it)."""
    rng = random.Random(f"ops:{seed}")
    out = list(ops)
    by_step: dict = {}
    for i, op in enumerate(ops):
        by_step.setdefault(op["step"], []).append(i)
    for indices in by_step.values():
        gone = {ops[i]["deleteOperation"]["objectMeta"]["name"] for i in indices
                if "deleteOperation" in ops[i] and ops[i]["deleteOperation"]["typeMeta"]["kind"] == "Pod"}
        slots = [i for i in indices if "createOperation" in ops[i]
                 and ops[i]["createOperation"]["object"]["kind"] == "Pod"
                 and ops[i]["createOperation"]["object"]["metadata"]["name"] not in gone]
        dealt = list(slots)
        rng.shuffle(dealt)
        for slot, source in zip(slots, dealt):
            out[slot] = ops[source]
    return out
