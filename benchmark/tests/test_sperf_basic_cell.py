"""The cell ``sperf-5k-basic_10kpods`` (PR 34): upstream's ``SchedulingBasic`` at
``5000Nodes_10000Pods`` under the default scheduler configuration, as data
(``configs/sperf-5k-basic.json``, ``traffic/10kpods.json``,
``cells/sperf-5k-basic_10kpods.json``; generator kind ``sperf``) with a plain
reference of its own, ``references/sampled.py``: sampled scoring
(``percentageOfNodesToScore``).  Pinned here: the reference on cases derived
by hand from upstream's definitions; the bytes of the request body at two
seeds and both sizes; that the numbers in the data files are the
reference's; the cell judged by ``run.judge`` as a run is; and the control —
the same reference scoring every node, which is ``replay.replay`` — failed by
the digest alone."""

import ast
import hashlib
import os

import numpy as np
import pytest

import checks
import placements
import replay
import run as harness
from references import sampled

CELL = "sperf-5k-basic_10kpods"

PINS = [   # rehearsal, seed, bytes, sha256 of inputs["body"]
    (False, 0, 5816920, "63b21277845fbe9a1ebece59235b70063dad93a66afe5a998ece7298132c00ef"),
    (False, 1, 5816920, "d07a6a7fcf84eec17da6244c1ea88ff61765fb6f5e8ef6aff83eed4c0d4be634"),
    (True, 0, 729420, "2d5e38ff33137c3dce9af077d27dc3ddaf8b628ea9f8b9e8017fcd08d3521665"),
    (True, 1, 729420, "6f7e40d5eb1254fb474a83b890c1b06ab069e4af9f10cd44c0d100b2fcd710cc"),
]


def cell(rehearsal: bool) -> dict:
    return harness.load_cell(harness.load("BENCHMARK.json"), CELL, rehearsal)


def inputs_of(rehearsal: bool, seed: int = 0) -> dict:
    c = cell(rehearsal)
    return harness.build_inputs(c["config"], c["traffic"], seed)


# -- the reference, by hand -----------------------------------------------------


def test_num_feasible_nodes_to_find_is_upstreams():
    """schedule_one.go numFeasibleNodesToFind: all under 100 nodes; else the
    share (unset: 50 - nodes/125 per cent, at least 5), at least 100 nodes."""
    want = sampled.num_feasible_nodes_to_find
    assert want(99) == 99
    assert want(100) == 100          # 50 % = 50, raised to the floor: all of them
    assert want(120) == 100          # 50 - 0 = 50 % = 60, raised to 100
    assert want(500) == 230          # 50 - 4 = 46 %
    assert want(5000) == 500         # 50 - 40 = 10 %
    assert want(125_000) == 6250     # 50 - 1,000 -> the 5 % floor
    assert want(5000, 20) == 1000 and want(5000, 100) == 5000 and want(5000, 1) == 100


def test_the_walk_on_eight_nodes():
    """findNodesThatPassFilters, one node after the other."""
    ok = np.array([1, 0, 1, 1, 0, 1, 1, 1], bool)
    # From 6: n6, n7, n0 are the first three feasible; three processed.
    visited, sample, start = sampled.walk(ok, 6, 3)
    assert np.flatnonzero(visited).tolist() == [0, 6, 7] and (sample == visited).all() and start == 1
    # From 1: n1 fails, n2, n3 pass, n4 fails, n5 is the third: five processed.
    visited, sample, start = sampled.walk(ok, 1, 3)
    assert np.flatnonzero(visited).tolist() == [1, 2, 3, 4, 5]
    assert np.flatnonzero(sample).tolist() == [2, 3, 5] and start == 6
    # Six feasible, seven wanted: every node is seen and the index comes round.
    visited, sample, start = sampled.walk(ok, 3, 7)
    assert visited.all() and (sample == ok).all() and start == 3


def node(i: int, big: bool = False) -> dict:
    size = {"cpu": "16", "memory": "64Gi"} if big else {"cpu": "4", "memory": "16Gi"}
    return {"apiVersion": "v1", "kind": "Node", "metadata": {"name": f"n{i:03d}"}, "spec": {},
            "status": {"allocatable": dict(size, pods="110")}}


def pod(name: str, cpu: str = "1", memory: str = "4Gi", on: str = "") -> dict:
    spec = {"containers": [{"name": "c", "resources": {"requests": {"cpu": cpu, "memory": memory}}}]}
    if on:
        spec["nodeName"] = on
    return {"apiVersion": "v1", "kind": "Pod", "metadata": {"name": name, "namespace": "default"},
            "spec": spec}


def create(step: int, obj: dict) -> dict:
    return {"step": step, "createOperation": {"object": obj}}


def hand_case() -> list:
    """120 nodes of 4 cpu / 16Gi (k = 100), n110 of 16 cpu / 64Gi: the best
    score for a 1-cpu / 4Gi pod even with two on it (LeastAllocated 93, 87,
    81 against an empty small node's 75).  n005 and n010 are born full."""
    ops = [create(0, node(i, big=i == 110)) for i in range(120)]
    ops += [create(0, pod(f"full-{i}", cpu="4", memory="1Gi", on=f"n{i:03d}")) for i in (5, 10)]
    return ops + [create(1, pod("p0")), create(1, pod("p1")), create(2, pod("p2"))]


def test_the_reference_on_a_case_derived_by_hand():
    """p0 from 0: the 100th feasible node is n101 (n005, n010 are full): 102
    visited, 100 scored, n110 not among them: the first of the equal nodes,
    n000; start 102.  p1 from 102: n102..n119 give 18, n000..n083 the other
    82: 102 visited, start (102 + 102) mod 120 = 84; n110 is in the sample and
    wins.  p2, a pass later, from 84: n084..n119 give 36, n000..n065 give 64:
    102 visited, start 66; n110 again.  The control scores every node: all
    three on n110."""
    got = sampled.replay(hand_case())
    assert [got[k] for k in checks.COUNT_KEYS] == [125, 3, 0]
    placed = {k: v for k, v in got["placements"].items() if k.startswith("p")}
    assert placed == {"p0": "n000", "p1": "n110", "p2": "n110"}
    assert (got["sampled_attempts"], got["nodes_visited"], got["nodes_scored"]) == (3, 306, 300)
    assert got["sampling_start"] == 66
    control = sampled.replay(hand_case(), walk_on=False)
    assert {k: v for k, v in control["placements"].items() if k.startswith("p")} \
        == {"p0": "n110", "p1": "n110", "p2": "n110"}
    assert control["sampled_attempts"] == 0 and control["sampling_start"] == 0
    assert control["placements"] == replay.replay(hand_case())["placements"]


def test_under_a_hundred_nodes_nothing_is_sampled():
    ops = [create(0, node(i, big=i == 90)) for i in range(99)] + [create(1, pod("p0"))]
    got = sampled.replay(ops)
    assert got["placements"]["p0"] == "n090" and got["sampled_attempts"] == 0
    assert got["placements"] == replay.replay(ops)["placements"]


def test_preemption_under_sampling_is_not_covered():
    ops = [create(0, node(i)) for i in range(100)]
    ops += [create(0, pod(f"low-{i}", cpu="4", memory="1Gi", on=f"n{i:03d}")) for i in range(100)]
    high = pod("high")
    high["spec"]["priority"] = 10
    with pytest.raises(replay.NotCovered):
        sampled.replay(ops + [create(1, high)])


def test_the_reference_imports_nothing_of_the_program():
    path = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                        "references", "sampled.py")
    with open(path, encoding="utf-8") as f:
        tree = ast.parse(f.read())
    names = [a.name for n in ast.walk(tree) if isinstance(n, ast.Import) for a in n.names]
    names += [n.module for n in ast.walk(tree) if isinstance(n, ast.ImportFrom)]
    assert sorted(names) == ["__future__", "numpy", "replay"]


# -- the cell's data ------------------------------------------------------------


@pytest.mark.parametrize("rehearsal,seed,size,sha", PINS,
                         ids=[f"{'rehearsal' if p[0] else 'full'}-{p[1]}" for p in PINS])
def test_the_request_body_is_pinned(rehearsal, seed, size, sha):
    body = inputs_of(rehearsal, seed)["body"]
    assert len(body) == size and hashlib.sha256(body).hexdigest() == sha


def test_the_shapes_are_upstreams_and_nothing_is_cut():
    c = cell(False)
    assert c["config"]["reduced"] == [] and c["config"]["architecture"] is None
    assert c["config"]["simulator"] == {"deviceReplay": True, "preemption": True,
                                        "nodeSampling": True, "podBucketMin": 128}
    assert c["reference"] is sampled and c["cell"]["chips"] == 1
    inputs = inputs_of(False)
    kinds = {}
    for op in inputs["operations"]:
        obj = op["createOperation"]["object"]
        kinds.setdefault((op["step"], obj["metadata"]["name"].rsplit("-", 1)[0]), []).append(obj)
    assert {k: len(v) for k, v in kinds.items()} == {
        (0, "node-default"): 5000, (1, "pod-default"): 1000, (2, "pod-default"): 10000}
    assert inputs["steps"] == 3 and inputs["units"] == 16000
    alloc = kinds[0, "node-default"][0]["status"]["allocatable"]
    assert (alloc["cpu"], alloc["memory"], alloc["pods"]) == ("4", "32Gi", "110")
    requests = kinds[2, "pod-default"][0]["spec"]["containers"][0]["resources"]["requests"]
    assert requests == {"cpu": "100m", "memory": "500Mi"}


@pytest.mark.parametrize("rehearsal", [True, False], ids=["rehearsal", "full"])
def test_the_data_files_hold_the_references_numbers(rehearsal):
    c, inputs = cell(rehearsal), inputs_of(rehearsal, seed=2147483693)
    got = sampled.replay(inputs["operations"])
    nodes, pods, k = (500, 1500, 230) if rehearsal else (5000, 11000, 500)
    assert [got[k_] for k_ in checks.COUNT_KEYS] == [nodes + pods] + c["locks"]["0"] == [nodes + pods, pods, 0]
    equals = c["guarantees"]["replay_equals"]
    assert placements.digest(got["placements"]) == equals["placements_digest"]
    # Every node stays feasible (a node holds 40 such pods by cpu), so every walk stops at k.
    assert got["sampled_attempts"] == equals["sampled_attempts"] == pods
    assert got["nodes_visited"] == equals["nodes_visited"] == pods * k == got["nodes_scored"]
    assert got["sampling_start"] == pods * k % nodes


def as_job_document(c: dict, got: dict, steps: int, **replay_block) -> dict:
    """A job's result document that says of the device path all that the
    configuration guarantees, with ``got``'s counts and placements in it."""
    block = dict(c["guarantees"]["replay_equals"], device_steps=steps,
                 placements_digest=placements.digest(got["placements"]), **replay_block)
    result = dict({k: got[k] for k in checks.COUNT_KEYS}, steps=steps)
    return {"state": "succeeded", "result": result, "replay": block}


def judged(c: dict, inputs: dict, doc: dict) -> set:
    """The names of the comparisons that fail, judged as a run is."""
    win = {"counted": [{"doc": doc}], "failed": 0}
    device = {"platform": c["platform"], "count": 1}
    out = harness.judge(c, inputs, 0, win, [], [], device, {"platform": c["platform"]})
    return {x["name"] for x in out if not x["ok"]}


def test_the_cell_is_correct_with_the_reference_in_the_programs_place_and_the_control_is_not():
    """At the rehearsal size, by ``run.judge``.  The control scores every
    node: the same three counts (every pod fits somewhere either way), so the
    lock and the counts pass — and another digest: 1,495 of 1,500 pods stand
    elsewhere.  A program that scores every node and says so also fails the
    two counters."""
    c, inputs = cell(True), inputs_of(True)
    exact = sampled.replay(inputs["operations"])
    assert judged(c, inputs, as_job_document(c, exact, inputs["steps"])) == set()
    control = replay.replay(inputs["operations"])
    assert [control[k] for k in checks.COUNT_KEYS] == [exact[k] for k in checks.COUNT_KEYS]
    assert sum(control["placements"][p] != n for p, n in exact["placements"].items()) == 1495
    assert judged(c, inputs, as_job_document(c, control, inputs["steps"])) == {"replay.placements_digest"}
    honest = as_job_document(c, control, inputs["steps"], sampled_attempts=0, nodes_visited=0)
    assert judged(c, inputs, honest) == {
        "replay.placements_digest", "replay.sampled_attempts", "replay.nodes_visited"}
