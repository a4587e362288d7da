"""Generator kind ``cluster``: a random snapshot for the import request.

``random_cluster`` is a copy of ``tests/helpers.py`` ``random_cluster`` as it
stood at PR 21: the same ``random.Random(seed)`` draws in the same order.

``generator`` block: ``base_seed``, ``n_nodes``, ``n_pods``, ``bound_fraction``.
"""

from __future__ import annotations

import json
import random

from generators import DISKS, HOST_KEY, JSON, ZONE_KEY, ZONES, make_node, make_pod, shuffle_cluster


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


def inputs(config: dict, traffic: dict, seed: int) -> dict:
    gen = config["generator"]
    nodes, pods = shuffle_cluster(seed, *random_cluster(
        gen["base_seed"], gen["n_nodes"], gen["n_pods"],
        bound_fraction=gen.get("bound_fraction", 0.0)))
    snapshot = {"nodes": nodes, "pods": pods, "pvs": [], "pvcs": [], "storageClasses": [],
                "priorityClasses": [], "namespaces": [], "schedulerConfig": None}
    return {"body": json.dumps(snapshot).encode(), "units": len(pods),
            "nodes": nodes, "pods": pods}
