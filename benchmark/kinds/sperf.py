"""Generator kind ``sperf``: upstream ``scheduler_perf``'s ``workloadTemplate``
(kubernetes ``test/integration/scheduler_perf``) as data, rendered as a
KEP-140 stream.

``generator`` block of the configuration:

- ``workloadTemplate``: the list of opcodes, in order.  ``createNodes`` /
  ``createPods`` / ``createAny`` with ``template`` (a file
  ``templates/<template>.json`` whose ``object`` is the manifest) and ``count``
  (a number) or ``countParam`` (``"$name"``, looked up in the chosen workload's
  parameters, as upstream writes it); ``createPods`` may give ``podsPerStep``.
- ``workloads``: ``{name: {param: number}}``, upstream's ``workloads`` list;
  ``workload``: the one that is run (the traffic file's ``workload`` takes its
  place, as ``events`` does for the churn kind).

Opcodes to steps.  A KEP-140 stream has no waits and no clock, only steps,
and the simulator runs one scheduling pass a step.  ``createNodes`` and
``createAny`` write into the current step (the first ones: step 0) and share
it with each other; every ``createPods`` starts a step of its own after them
and takes ``ceil(count / podsPerStep)`` steps (one without ``podsPerStep``).
Upstream's ``barrier`` (wait until every pod is scheduled) has no step to
stand in: a workload whose last pods need one more pass — a preemptor binds in
the pass after its victims went — ends with a ``createPods`` of a few
trailing pods, which is plain in the data.

Names are ``<template>-<i>``, ``i`` counting from 0 through all the opcodes
that use the template (upstream's ``generateName`` draws random suffixes);
pods go to the ``default`` namespace (upstream makes one a test).  ``--seed``
orders the pod creations inside a step and nothing else.  Imports nothing of
the program.
"""

from __future__ import annotations

import copy
import json
import os
import re

from generators import create_op, job_inputs, shuffle_operations

TEMPLATES = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "templates")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
KINDS = {"createNodes": "Node", "createPods": "Pod"}


def template(name: str) -> dict:
    if not isinstance(name, str) or not NAME.match(name):
        raise ValueError(f"sperf: not a template name: {name!r}")
    with open(os.path.join(TEMPLATES, name + ".json"), encoding="utf-8") as f:
        return json.load(f)["object"]


def count_of(op: dict, params: dict) -> int:
    if "countParam" in op:
        return int(params[op["countParam"].lstrip("$")])
    return int(op["count"])


def operations(gen: dict, workload: str) -> list:
    """The KEP-140 ``spec.operations`` of ``gen['workloadTemplate']`` at the
    sizes of ``gen['workloads'][workload]``."""
    params = gen["workloads"][workload]
    ops, made = [], {}
    step, written = 0, False   # the step the next opcode writes to; whether it holds anything
    for op in gen["workloadTemplate"]:
        code, name = op["opcode"], op["template"]
        if code not in ("createNodes", "createPods", "createAny"):
            raise ValueError(f"sperf: no opcode {code!r}")
        obj, n = template(name), count_of(op, params)
        if code in KINDS and obj["kind"] != KINDS[code]:
            raise ValueError(f"sperf: {code} of a {obj['kind']} ({name})")
        if code == "createPods" and written:
            step, written = step + 1, False
        per = int(op.get("podsPerStep") or n or 1) if code == "createPods" else n or 1
        for k in range(n):
            one = copy.deepcopy(obj)
            i = made[name] = made.get(name, -1) + 1
            one.setdefault("metadata", {})["name"] = f"{name}-{i}"
            if one["kind"] == "Pod":
                one["metadata"]["namespace"] = "default"
            ops.append(create_op(step + k // per, one))
        if n:
            if code == "createPods":
                step += -(-n // per)
            else:
                written = True
    return ops


def inputs(config: dict, traffic: dict, seed: int) -> dict:
    gen = config["generator"]
    ops = operations(gen, traffic.get("workload", gen["workload"]))
    return job_inputs(config, shuffle_operations(seed, ops), len(ops))
