"""Generator kind ``sperf_pvs``: the ``sperf`` kind (``kinds/sperf.py``) plus
the two parts of upstream ``scheduler_perf`` that its volume cases use.

A ``createPods`` opcode of the ``workloadTemplate`` may give

    "persistentVolumeTemplatePath": "<template>",
    "persistentVolumeClaimTemplatePath": "<template>"

and every pod it creates then comes with a PersistentVolume and a
PersistentVolumeClaim of its own, bound to each other before the scheduler
sees them, as upstream's ``createPodsWithPVs`` strategy makes them
(``test/utils/runners.go`` ``CreatePodWithPersistentVolume``): the PV gets
``spec.csi.volumeHandle`` (its own name; only where the template has a
``spec.csi``), ``spec.claimRef`` to the claim and ``status.phase: Bound``; the
claim gets ``spec.volumeName`` and ``status.phase: Bound``; the pod gets one
volume ``{"name": "vol", "persistentVolumeClaim": {"claimName": <claim>}}``.
In the stream the PV and the claim of a pod stand directly before it, in the
pod's step.

A ``createNodes`` opcode may give upstream's

    "nodeAllocatableStrategy": {"nodeAllocatable": {"<resource>": "<quantity>"}, ...}

and the nodes it creates then state those resources in ``status.allocatable``
and ``status.capacity`` (``NodeAllocatableStrategy``).  Upstream's strategy
also writes a CSINode (``csiNodeAllocatable``, ``migratedPlugins``); the
simulator's snapshot model has no CSINode, so those two keys are carried in the
data and read by nothing: the attach limit is the allocatable key.

Names are ``<template>-<i>`` as in ``sperf`` (the PV's and the claim's count
with their pod).  ``--seed`` orders the pods of a step — each with its PV and
claim before it — and nothing else.  Imports nothing of the program.
"""

from __future__ import annotations

import copy
import random

from generators import create_op, job_inputs
from kinds import sperf

PV, PVC = "persistentVolumeTemplatePath", "persistentVolumeClaimTemplatePath"
STRATEGY = "nodeAllocatableStrategy"


def operations(gen: dict, workload: str) -> list:
    """``sperf.operations`` with the volume templates and the allocatable
    strategies applied; a pod's PV and claim stand before it."""
    ops = sperf.operations(gen, workload)
    by_name = {(op["createOperation"]["object"]["kind"],
                op["createOperation"]["object"]["metadata"]["name"]): op for op in ops}
    params, made, before = gen["workloads"][workload], {}, {}
    for op in gen["workloadTemplate"]:
        first = made.get(op["template"], 0)
        n = sperf.count_of(op, params)
        made[op["template"]] = first + n
        strategy = op.get(STRATEGY)
        if strategy is not None:
            if op["opcode"] != "createNodes" or not isinstance(strategy.get("nodeAllocatable"), dict):
                raise ValueError(f"sperf_pvs: not a {STRATEGY} of createNodes: {strategy!r}")
            for i in range(first, first + n):
                status = by_name["Node", f"{op['template']}-{i}"]["createOperation"]["object"]["status"]
                for part in ("allocatable", "capacity"):
                    status.setdefault(part, {}).update(strategy["nodeAllocatable"])
        if (PV in op) != (PVC in op):
            raise ValueError(f"sperf_pvs: {PV} and {PVC} go together")
        if PV not in op:
            continue
        if op["opcode"] != "createPods":
            raise ValueError(f"sperf_pvs: {PV} on {op['opcode']}")
        pv_t, pvc_t = sperf.template(op[PV]), sperf.template(op[PVC])
        if pv_t["kind"] != "PersistentVolume" or pvc_t["kind"] != "PersistentVolumeClaim":
            raise ValueError(f"sperf_pvs: {op[PV]} / {op[PVC]} are not a PV and a claim")
        for i in range(first, first + n):
            pod_op = by_name["Pod", f"{op['template']}-{i}"]
            pod = pod_op["createOperation"]["object"]
            pv, pvc = copy.deepcopy(pv_t), copy.deepcopy(pvc_t)
            pv.setdefault("metadata", {})["name"] = f"{op[PV]}-{i}"
            pvc.setdefault("metadata", {}).update(name=f"{op[PVC]}-{i}", namespace="default")
            if "csi" in pv["spec"]:
                pv["spec"]["csi"]["volumeHandle"] = pv["metadata"]["name"]
            pv["spec"]["claimRef"] = {"kind": "PersistentVolumeClaim", "namespace": "default",
                                      "name": pvc["metadata"]["name"]}
            pv["status"] = {"phase": "Bound"}
            pvc["spec"]["volumeName"] = pv["metadata"]["name"]
            pvc["status"] = {"phase": "Bound"}
            pod["spec"].setdefault("volumes", []).append(
                {"name": "vol", "persistentVolumeClaim": {"claimName": pvc["metadata"]["name"]}})
            before[id(pod_op)] = [create_op(pod_op["step"], pv), create_op(pod_op["step"], pvc)]
    return [x for op in ops for x in before.get(id(op), []) + [op]]


def shuffle(seed: int, ops: list) -> list:
    """The same stream with the pods of each step — a pod with the PV and the
    claim that stand before it — in the order ``seed`` draws; everything
    else keeps its place."""
    rng = random.Random(f"ops:{seed}")
    groups, open_ = [], []   # the operations that move together
    for op in ops:
        open_.append(op)
        if op["createOperation"]["object"]["kind"] not in ("PersistentVolume",
                                                          "PersistentVolumeClaim"):
            groups.append(open_)
            open_ = []
    if open_:
        groups.append(open_)
    slots: dict = {}   # step -> where its pods' groups stand
    for at, group in enumerate(groups):
        if group[-1]["createOperation"]["object"]["kind"] == "Pod":
            slots.setdefault(group[-1]["step"], []).append(at)
    out = list(groups)
    for indices in slots.values():
        dealt = list(indices)
        rng.shuffle(dealt)
        for slot, source in zip(indices, dealt):
            out[slot] = groups[source]
    return [op for group in out for op in group]


def inputs(config: dict, traffic: dict, seed: int) -> dict:
    gen = config["generator"]
    ops = operations(gen, traffic.get("workload", gen["workload"]))
    return job_inputs(config, shuffle(seed, ops), len(ops))
