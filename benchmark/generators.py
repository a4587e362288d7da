"""What the generator kinds (``kinds/<kind>.py``) share: the object makers,
the two operation shapes of a KEP-140 stream, the request body of a job, and
what ``--seed`` does.  Nothing here imports the program (or jax), so the
harness generates its inputs in-process before the server — the one process
that may touch the chip — starts.

What ``--seed`` does (``shuffle_*``): a deployment's objects are drawn from
the configuration's own ``base_seed`` (or are fixed by its templates), and
the run's seed sets the order in which they ARRIVE — the order of the pods in
the snapshot document, the order of the pod creations inside each step of the
stream.  The scheduling queue orders pods by priority and name, not by
arrival, so every seed gives the server the same scheduling problem through a
differently ordered document: the same work, the same answers (the behaviour
locks hold at every seed), other uids, resourceVersions and load order.
Drawing the objects themselves from ``--seed`` moved the work by 8 % (import)
to 40 % (stream) on the chip, and permuting their names still by 4 % —
reordering the queue or the tie-breaks changes who fits where (PERF.md, PR
24) — far more than two runs of one seed differ.
"""

from __future__ import annotations

import json
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


def create_op(step: int, obj: JSON) -> JSON:
    return {"step": step, "createOperation": {"object": obj}}


def delete_op(step: int, kind: str, name: str, namespace: str) -> JSON:
    return {"step": step, "deleteOperation": {
        "typeMeta": {"kind": kind},
        "objectMeta": {"name": name, "namespace": namespace},
    }}


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


def job_inputs(config: dict, ops: list, units: int) -> dict:
    """What a job kind's ``inputs`` returns: the request body (the
    configuration's ``simulator`` block around the operations), the units a
    request counts for, the operations and how many steps they span."""
    body = {"spec": {"simulator": config["simulator"], "scenario": {"operations": ops}}}
    return {"body": json.dumps(body).encode(), "units": units, "operations": ops,
            "steps": len({op["step"] for op in ops})}
