"""Shared fixture builders: k8s-shaped JSON objects."""

from __future__ import annotations

import json
import os
import random
from typing import Any

JSON = dict[str, Any]


def sanitized_cpu_env(extra: dict[str, str] | None = None) -> dict[str, str]:
    """Subprocess environment pinned to the CPU backend, so entrypoint
    subprocess tests stay hermetic under any hardware condition and
    never compete for the chip (one process per chip).  Only
    ``tests/test_tpu_parity.py`` deliberately lets its child take the
    environment's default platform (it wants the real chip)."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    if extra:
        env.update(extra)
    return env


def make_node(
    name: str,
    cpu: str = "4",
    memory: str = "16Gi",
    pods: int = 110,
    *,
    labels: dict[str, str] | None = None,
    taints: list[JSON] | None = None,
    unschedulable: bool = False,
    extra_alloc: dict[str, str] | None = None,
) -> JSON:
    alloc = {"cpu": cpu, "memory": memory, "pods": str(pods), "ephemeral-storage": "100Gi"}
    alloc.update(extra_alloc or {})
    spec: JSON = {}
    if taints:
        spec["taints"] = taints
    if unschedulable:
        spec["unschedulable"] = True
    return {
        "apiVersion": "v1",
        "kind": "Node",
        "metadata": {"name": name, "labels": labels or {}},
        "spec": spec,
        "status": {"allocatable": dict(alloc), "capacity": dict(alloc)},
    }


def make_pod(
    name: str,
    cpu: str | None = "100m",
    memory: str | None = "128Mi",
    *,
    namespace: str = "default",
    node_name: str = "",
    labels: dict[str, str] | None = None,
    phase: str = "",
    tolerations: list[JSON] | None = None,
    affinity: JSON | None = None,
    node_selector: dict[str, str] | None = None,
    topology_spread_constraints: list[JSON] | None = None,
    priority: int | None = None,
    extra_requests: dict[str, str] | None = None,
) -> JSON:
    requests: JSON = {}
    if cpu is not None:
        requests["cpu"] = cpu
    if memory is not None:
        requests["memory"] = memory
    requests.update(extra_requests or {})
    spec: JSON = {
        "containers": [
            {"name": "c", "image": "img", "resources": {"requests": requests} if requests else {}}
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
    if priority is not None:
        spec["priority"] = priority
    status: JSON = {"phase": phase} if phase else {}
    return {
        "apiVersion": "v1",
        "kind": "Pod",
        "metadata": {"name": name, "namespace": namespace, "labels": labels or {}},
        "spec": spec,
        "status": status,
    }


def random_cluster(
    seed: int,
    n_nodes: int,
    n_pods: int,
    *,
    bound_fraction: float = 0.3,
    unschedulable_fraction: float = 0.1,
    pod_affinity_fraction: float = 0.15,
) -> tuple[list[JSON], list[JSON]]:
    """Reproducible random cluster; quantities are Mi/milli multiples."""
    rng = random.Random(seed)
    zones = ["zone-a", "zone-b", "zone-c"]
    disks = ["ssd", "hdd"]
    nodes = []
    for i in range(n_nodes):
        taints = []
        if rng.random() < 0.15:
            taints.append({"key": "dedicated", "value": rng.choice(["gpu", "db"]), "effect": "NoSchedule"})
        if rng.random() < 0.15:
            taints.append({"key": "maintenance", "value": "", "effect": "PreferNoSchedule"})
        nodes.append(
            make_node(
                f"node-{i}",
                cpu=f"{rng.choice([2, 4, 8, 16, 32])}",
                memory=f"{rng.choice([4, 8, 16, 32, 64])}Gi",
                pods=rng.choice([8, 16, 32, 110]),
                unschedulable=rng.random() < unschedulable_fraction,
                labels={
                    "topology.kubernetes.io/zone": rng.choice(zones),
                    "kubernetes.io/hostname": f"node-{i}",
                    "disktype": rng.choice(disks),
                },
                taints=taints or None,
            )
        )
    apps = ["web", "db", "cache", "batch"]
    pods = []
    for i in range(n_pods):
        bound = rng.random() < bound_fraction
        app = rng.choice(apps)
        spread = None
        if rng.random() < 0.3:
            spread = [{
                "maxSkew": rng.choice([1, 2]),
                "topologyKey": rng.choice(["topology.kubernetes.io/zone", "kubernetes.io/hostname"]),
                "whenUnsatisfiable": rng.choice(["DoNotSchedule", "ScheduleAnyway"]),
                "labelSelector": {"matchLabels": {"app": app}},
            }]
            if rng.random() < 0.3:
                spread.append({
                    "maxSkew": 3,
                    "topologyKey": "topology.kubernetes.io/zone",
                    "whenUnsatisfiable": "ScheduleAnyway",
                    "labelSelector": {"matchLabels": {"app": app}},
                })
        tolerations = []
        if rng.random() < 0.15:
            tolerations.append(
                {"key": "node.kubernetes.io/unschedulable", "operator": "Exists", "effect": "NoSchedule"}
            )
        if rng.random() < 0.25:
            tolerations.append(
                {"key": "dedicated", "operator": rng.choice(["Exists", "Equal"]), "value": "gpu", "effect": "NoSchedule"}
            )
        if rng.random() < 0.15:
            tolerations.append({"key": "maintenance", "operator": "Exists"})
        node_selector = {"disktype": rng.choice(disks)} if rng.random() < 0.2 else None
        affinity = None
        if rng.random() < 0.3:
            node_affinity = {}
            if rng.random() < 0.6:
                node_affinity["requiredDuringSchedulingIgnoredDuringExecution"] = {
                    "nodeSelectorTerms": [
                        {"matchExpressions": [
                            {"key": "topology.kubernetes.io/zone", "operator": "In",
                             "values": rng.sample(zones, rng.randint(1, 2))}
                        ]}
                    ]
                }
            if rng.random() < 0.7:
                node_affinity["preferredDuringSchedulingIgnoredDuringExecution"] = [
                    {"weight": rng.choice([1, 10, 50, 100]),
                     "preference": {"matchExpressions": [
                         {"key": "disktype", "operator": rng.choice(["In", "NotIn"]),
                          "values": [rng.choice(disks)]}
                     ]}}
                ]
            if node_affinity:
                affinity = {"nodeAffinity": node_affinity}
        if rng.random() < pod_affinity_fraction:
            tk = rng.choice(["topology.kubernetes.io/zone", "kubernetes.io/hostname"])
            term = {
                "labelSelector": {"matchLabels": {"app": rng.choice(apps)}},
                "topologyKey": tk,
            }
            kind = rng.random()
            pod_aff: JSON = {}
            if kind < 0.35:
                pod_aff["podAffinity"] = {
                    "requiredDuringSchedulingIgnoredDuringExecution": [term]
                }
            elif kind < 0.65:
                pod_aff["podAntiAffinity"] = {
                    "requiredDuringSchedulingIgnoredDuringExecution": [term]
                }
            else:
                pod_aff["podAffinity"] = {
                    "preferredDuringSchedulingIgnoredDuringExecution": [
                        {"weight": rng.choice([1, 25, 100]), "podAffinityTerm": term}
                    ]
                }
                if rng.random() < 0.5:
                    pod_aff["podAntiAffinity"] = {
                        "preferredDuringSchedulingIgnoredDuringExecution": [
                            {
                                "weight": rng.choice([1, 25, 100]),
                                "podAffinityTerm": {
                                    "labelSelector": {
                                        "matchLabels": {"app": rng.choice(apps)}
                                    },
                                    "topologyKey": "topology.kubernetes.io/zone",
                                },
                            }
                        ]
                    }
            affinity = {**(affinity or {}), **pod_aff}
        pods.append(
            make_pod(
                f"pod-{i}",
                cpu=rng.choice([None, "50m", "100m", "250m", "500m", "1", "2"]),
                memory=rng.choice([None, "64Mi", "128Mi", "512Mi", "1Gi", "4Gi"]),
                node_name=f"node-{rng.randrange(n_nodes)}" if bound else "",
                labels={"app": app},
                tolerations=tolerations or None,
                node_selector=node_selector,
                affinity=affinity,
                topology_spread_constraints=spread,
            )
        )
    return nodes, pods


def pods_by_node(pods: list[JSON]) -> dict[str, list[JSON]]:
    """Bound, non-terminal pods grouped by node (the spread-stats view)."""
    out: dict[str, list[JSON]] = {}
    for p in pods:
        if not p.get("spec", {}).get("nodeName"):
            continue
        if p.get("status", {}).get("phase") in ("Succeeded", "Failed"):
            continue
        out.setdefault(p["spec"]["nodeName"], []).append(p)
    return out


def write_synthetic_borg(path: str, records: int, seed: int = 0) -> None:
    """A Borg-format JSONL trace of ``records`` SUBMIT/FINISH pairs,
    deterministic from ``seed``.  Lifetimes stay short against the trace
    span (~25 ms mean interarrival) so FINISH deletes interleave with
    arrivals and the live pod population stays bounded."""
    rng = random.Random(seed)
    t_us = 0
    with open(path, "w") as f:
        for i in range(records):
            t_us += rng.randrange(1_000, 50_000)
            life_us = rng.randrange(500_000, 60_000_000)
            req = {
                "cpus": rng.choice((0.01, 0.025, 0.05, 0.1)),
                "memory": rng.choice((0.005, 0.01, 0.02, 0.05)),
            }
            f.write(json.dumps({
                "time": t_us, "type": "SUBMIT", "collection_id": i,
                "instance_index": 0,
                "priority": rng.choice((0, 103, 117, 200, 360)),
                "resource_request": req,
            }) + "\n")
            f.write(json.dumps({
                "time": t_us + life_us, "type": "FINISH",
                "collection_id": i, "instance_index": 0,
            }) + "\n")


def replay_synthetic_borg(tmp_path):
    """A 400-pair synthetic Borg trace (120 events kept, 8 nodes)
    through the device path twice: streamed in 64-op windows, then
    materialized.  Returns (stream, streamed runner, streamed result,
    materialized result)."""
    from ksim_tpu.scenario import ScenarioRunner
    from ksim_tpu.traces import stream_trace_operations, trace_operations

    path = str(tmp_path / "synthetic_borg.jsonl")
    write_synthetic_borg(path, 400)
    kw = dict(nodes=8, max_events=120, seed=0, ops_per_step=10)
    stream = stream_trace_operations(path, "borg", window=64, queue_windows=2, **kw)
    runner = ScenarioRunner(pod_bucket_min=64, device_replay=True)
    streamed = runner.run(stream)
    materialized = ScenarioRunner(pod_bucket_min=64, device_replay=True).run(
        list(trace_operations(path, "borg", **kw))
    )
    return stream, runner, streamed, materialized
