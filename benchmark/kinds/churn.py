"""Generator kind ``churn``: this repo's churn stream as a job.

``churn_operations`` is a copy of ``ksim_tpu/scenario/generate.py``
``churn_scenario`` folded together with ``ksim_tpu/scenario/spec.py``
``spec_from_operations``, as they stood at PR 21: the same
``random.Random(seed)`` draws in the same order, so seed 0 still produces the
streams the behaviour locks were taken on.  It lives under ``benchmark/`` so
that a later PR to the program cannot move the traffic.

``generator`` block: ``base_seed``, ``n_nodes``, ``n_events`` (the traffic's
``events`` takes its place: a prefix), ``ops_per_step``, ``pod_create_frac``,
``pod_delete_frac``.
"""

from __future__ import annotations

import random

from generators import (DISKS, HOST_KEY, JSON, ZONE_KEY, ZONES, create_op, delete_op, job_inputs,
                        make_node, make_pod, shuffle_operations)


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
    ops = [create_op(0, _churn_node(rng, name)) for name in live_nodes]
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
                ops.append(create_op(step, _churn_pod(rng, name)))
            elif r < pod_create_frac + pod_delete_frac:
                victim = live_pods.pop(rng.randrange(len(live_pods)))
                ops.append(delete_op(step, "Pod", victim, "default"))
            else:
                gone = live_nodes.pop(rng.randrange(len(live_nodes)))
                ops.append(delete_op(step, "Node", gone, ""))
                fresh = f"node-{node_seq}"
                node_seq += 1
                live_nodes.append(fresh)
                ops.append(create_op(step, _churn_node(rng, fresh)))
        emitted += budget
        step += 1
    return ops


def inputs(config: dict, traffic: dict, seed: int) -> dict:
    gen = config["generator"]
    n_events = traffic.get("events", gen["n_events"])
    ops = shuffle_operations(seed, churn_operations(
        gen["base_seed"], n_nodes=gen["n_nodes"], n_events=n_events,
        ops_per_step=gen["ops_per_step"],
        pod_create_frac=gen.get("pod_create_frac", 0.65),
        pod_delete_frac=gen.get("pod_delete_frac", 0.25)))
    return job_inputs(config, ops, n_events)
