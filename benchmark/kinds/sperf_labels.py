"""Generator kind ``sperf_labels``: the ``sperf`` kind (``kinds/sperf.py``)
plus upstream ``scheduler_perf``'s ``labelNodePrepareStrategy`` on
``createNodes``.

A ``createNodes`` opcode of the ``workloadTemplate`` may give

    "labelNodePrepareStrategy": {"labelKey": "<key>", "labelValues": ["a", "b", ...]}

and the nodes it creates then carry ``metadata.labels[<key>]``, dealt
round-robin by the node's index within the opcode (node ``i`` gets
``labelValues[i % len(labelValues)]``), as upstream's strategy does
(``test/utils/runners.go`` ``LabelNodePrepareStrategy``).  Everything else —
opcodes to steps, names ``<template>-<i>``, what ``--seed`` does — is the
``sperf`` kind's.  Imports nothing of the program.
"""

from __future__ import annotations

from generators import job_inputs, shuffle_operations
from kinds import sperf

STRATEGY = "labelNodePrepareStrategy"


def operations(gen: dict, workload: str) -> list:
    """``sperf.operations`` with the label strategies applied."""
    ops = sperf.operations(gen, workload)
    nodes = {op["createOperation"]["object"]["metadata"]["name"]: op["createOperation"]["object"]
             for op in ops if op["createOperation"]["object"]["kind"] == "Node"}
    params, made = gen["workloads"][workload], {}
    for op in gen["workloadTemplate"]:
        first = made.get(op["template"], 0)
        made[op["template"]] = first + sperf.count_of(op, params)
        strategy = op.get(STRATEGY)
        if strategy is None:
            continue
        if op["opcode"] != "createNodes":
            raise ValueError(f"sperf_labels: {STRATEGY} on {op['opcode']}")
        key, values = strategy["labelKey"], list(strategy["labelValues"])
        if not isinstance(key, str) or not values or not all(isinstance(v, str) for v in values):
            raise ValueError(f"sperf_labels: not a label strategy: {strategy!r}")
        for i in range(made[op["template"]] - first):
            node = nodes[f"{op['template']}-{first + i}"]
            node["metadata"].setdefault("labels", {})[key] = values[i % len(values)]
    return ops


def inputs(config: dict, traffic: dict, seed: int) -> dict:
    gen = config["generator"]
    ops = operations(gen, traffic.get("workload", gen["workload"]))
    return job_inputs(config, shuffle_operations(seed, ops), len(ops))
