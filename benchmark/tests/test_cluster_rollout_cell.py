"""The cell ``cluster-5k-150k_rollout5k`` (PR 46): a what-if against a cluster
that is already running, as data (``configs/cluster-5k-150k.json``,
``traffic/rollout5k.json``, ``cells/cluster-5k-150k_rollout5k.json``; generator
kind ``snapshot``) with a plain reference of its own,
``references/snapshot_zoned.py``: ``references/sampled_zoned.py`` started from
the job's ``initialSnapshot``.  Pinned here: the reference on cases derived by
hand; the kind's ``units`` / ``steps`` / ``operations`` contract and the
cluster it draws; the bytes of the request body; that the numbers in the data
files are the reference's; the cell judged by ``run.judge`` as a run is; and
the two controls — the snapshot's pods not charged, the walk in name order —
each failed by the digest and by ``nodes_visited``."""

import ast
import collections
import hashlib
import io
import json
import os
from contextlib import redirect_stdout

import pytest

import checks
import placements
import run as harness
from kinds import snapshot
from references import sampled_zoned, snapshot_zoned

CELL = "cluster-5k-150k_rollout5k"
ZONE_KEY = "topology.kubernetes.io/zone"

PINS = [   # rehearsal, seed, bytes, sha256 of inputs["body"]
    (True, 0, 7186544, "6c90869135a75be151b33ff5313432afd4b9f8a59e6a5ae2f48321d3172cbe95"),
    (True, 1, 7186544, "407553c7e75b77d524a5bea0c5b61695769d69c5c2a48a51480ab40eda4d0469"),
]


def cell(rehearsal: bool) -> dict:
    return harness.load_cell(harness.load("BENCHMARK.json"), CELL, rehearsal)


@pytest.fixture(scope="module")
def small():
    """The rehearsal size: inputs, the reference's answers, the controls'."""
    c = cell(True)
    inputs = harness.build_inputs(c["config"], c["traffic"], 0)
    replay = snapshot_zoned.replay
    return {"c": c, "inputs": inputs, "exact": replay(inputs["operations"]),
            "uncharged": replay(inputs["operations"], charge_snapshot=False),
            "by_name": replay(inputs["operations"], interleave=False)}


# -- the reference, by hand -----------------------------------------------------


def node(i: int, zone: str) -> dict:
    return {"apiVersion": "v1", "kind": "Node",
            "metadata": {"name": f"n{i:03d}", "labels": {ZONE_KEY: zone}}, "spec": {},
            "status": {"allocatable": {"cpu": "4", "memory": "16Gi", "pods": "110"}}}


def pod(name: str, cpu: str, on: str = "") -> dict:
    spec = {"containers": [{"name": "c", "resources": {"requests": {"cpu": cpu, "memory": "1Gi"}}}]}
    if on:
        spec["nodeName"] = on
    return {"apiVersion": "v1", "kind": "Pod", "metadata": {"name": name, "namespace": "default"},
            "spec": spec}


def create(step: int, obj: dict) -> dict:
    return {"step": step, "createOperation": {"object": obj}}


def hand_case() -> list:
    """Three nodes of 4 cpu (under 100 nodes: nothing samples).  The snapshot:
    ``b0`` (3 cpu) runs on n000, ``b1`` (2 cpu) on n001, ``waiting`` (1 cpu) is
    pending.  The scenario's one step, at 7, creates ``new`` (2 cpu)."""
    snap = [node(0, "a"), node(1, "b"), node(2, "a"),
            pod("b0", "3", on="n000"), pod("b1", "2", on="n001"), pod("waiting", "1")]
    return [create(-1, obj) for obj in snap] + [create(7, pod("new", "2"))]


def test_the_snapshot_is_the_state_a_job_starts_from():
    """One pass, at the scenario's step: ``new`` (first in the queue by name)
    cannot go to n000 (3 of 4 cpu taken) and takes the empty n002; ``waiting``,
    the snapshot's pending pod, is queued beside it: n001 (2 of 4 taken) and
    n002 (now 2 of 4) score alike, the first in node order takes it.  The six
    objects of the snapshot are no events and add no step."""
    got = snapshot_zoned.replay(hand_case())
    assert [got[k] for k in checks.COUNT_KEYS] == [1, 2, 0]
    assert got["steps"] == [(2, 0)]
    assert got["placements"] == {"b0": "n000", "b1": "n001", "waiting": "n001", "new": "n002"}
    assert got["sampling_zones"] == 2 and got["sampled_attempts"] == 0
    # The same objects as creations of a step before the scenario's are
    # another job to ``sampled_zoned``: seven events, two steps, two passes.
    plain = sampled_zoned.replay(hand_case())
    assert [plain[k] for k in checks.COUNT_KEYS] == [7, 2, 0] and len(plain["steps"]) == 2


def test_the_control_leaves_the_snapshots_pods_where_they_stood_and_charges_no_node():
    """Uncharged, n000 looks empty: ``new`` takes it (the first of three equal
    nodes) and ``waiting`` the next empty one."""
    got = snapshot_zoned.replay(hand_case(), charge_snapshot=False)
    assert [got[k] for k in checks.COUNT_KEYS] == [1, 2, 0]
    assert got["placements"] == {"b0": "n000", "b1": "n001", "waiting": "n001", "new": "n000"}


def test_a_snapshot_with_no_scenario_schedules_nothing():
    got = snapshot_zoned.replay(hand_case()[:-1])
    assert [got[k] for k in checks.COUNT_KEYS] == [0, 0, 0] and got["steps"] == []
    assert got["placements"] == {"b0": "n000", "b1": "n001", "waiting": None}


def test_the_reference_imports_nothing_of_the_program():
    path = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                        "references", "snapshot_zoned.py")
    with open(path, encoding="utf-8") as f:
        tree = ast.parse(f.read())
    names = [a.name for n in ast.walk(tree) if isinstance(n, ast.Import) for a in n.names]
    names += [n.module for n in ast.walk(tree) if isinstance(n, ast.ImportFrom)]
    assert sorted(names) == ["__future__", "references"]


# -- the generator kind ---------------------------------------------------------


def test_the_kinds_contract_units_steps_operations(small):
    inputs = small["inputs"]
    body = json.loads(inputs["body"])
    scenario = body["spec"]["scenario"]["operations"]
    snap = body["spec"]["simulator"]["initialSnapshot"]
    assert inputs["units"] == len(scenario) == 500 and inputs["steps"] == 1
    assert {op["step"] for op in scenario} == {0}
    # The reference is handed the snapshot's objects as creations of a step
    # before the scenario's, and the scenario's operations as submitted.
    ops = inputs["operations"]
    assert [op["step"] for op in ops] == [snapshot.SNAPSHOT_STEP] * 15500 + [0] * 500
    assert [op["createOperation"]["object"] for op in ops[:15500]] == snap["nodes"] + snap["pods"]
    assert ops[15500:] == scenario
    assert sorted(snap) == sorted(["pods", "nodes", "pvs", "pvcs", "storageClasses",
                                   "priorityClasses", "schedulerConfig", "namespaces"])
    assert {k: v for k, v in body["spec"]["simulator"].items() if k != "initialSnapshot"} == \
        small["c"]["config"]["simulator"]


def test_the_seed_orders_the_rollouts_arrivals_and_nothing_else(small):
    c = small["c"]
    other = harness.build_inputs(c["config"], c["traffic"], 2147484999)
    a, b = small["inputs"]["operations"], other["operations"]
    assert a[:15500] == b[:15500] and a[15500:] != b[15500:]
    name = lambda op: op["createOperation"]["object"]["metadata"]["name"]
    assert sorted(map(name, a[15500:])) == sorted(map(name, b[15500:]))


def test_the_cluster_is_the_one_the_configuration_describes(small):
    snap = json.loads(small["inputs"]["body"])["spec"]["simulator"]["initialSnapshot"]
    zone = {n["metadata"]["name"]: n["metadata"]["labels"][ZONE_KEY] for n in snap["nodes"]}
    assert [zone[f"node-default-{i}"] for i in range(6)] == ["moscow-1", "moscow-2", "moscow-3"] * 2
    assert all(p["status"] == {"phase": "Running"} and p["spec"]["nodeName"] in zone
               for p in snap["pods"])
    per_node = collections.Counter(p["spec"]["nodeName"] for p in snap["pods"])
    assert len(per_node) == 500 and sum(per_node.values()) == 15000
    assert (min(per_node.values()), max(per_node.values())) == (20, 40)
    blue = [p for p in snap["pods"] if p["metadata"].get("labels", {}).get("color") == "blue"]
    assert collections.Counter(zone[p["spec"]["nodeName"]] for p in blue) == {
        "moscow-1": 400, "moscow-2": 300, "moscow-3": 200}
    assert all(p["spec"]["topologySpreadConstraints"][0]["maxSkew"] == 5 for p in blue)
    groups = collections.Counter(p["metadata"]["labels"]["name"] for p in snap["pods"]
                                 if "name" in p["metadata"].get("labels", {}))
    sizes = collections.Counter(g.rsplit("-", 1)[0] for g in groups)
    assert sum(groups.values()) == 14100
    assert sizes == {"big-deployment": 14, "medium-deployment": 117, "small-deployment": 1418}
    assert {groups["big-deployment-0"], groups["medium-deployment-0"],
            groups["small-deployment-0"]} == {250, 30, 5}
    # The rollout counts on from the replicas the snapshot holds.
    names = sorted(op["createOperation"]["object"]["metadata"]["name"]
                   for op in small["inputs"]["operations"][15500:])
    assert names == sorted(f"pod-with-topology-spreading-{i}" for i in range(900, 1400))


def test_nothing_is_cut_and_the_full_size_is_the_envelope():
    c = cell(False)
    assert c["config"]["reduced"] == [] and c["config"]["architecture"] is None
    assert c["reference"] is snapshot_zoned and c["cell"]["chips"] == 1
    size = c["config"]["generator"]["workloads"][c["traffic"]["workload"]]
    assert size == {"nodes": 5000, "podsPerNode": {"mean": 30, "spread": 10},
                    "replicas": [4000, 3000, 2000], "rollout": 5000}
    assert size["nodes"] * size["podsPerNode"]["mean"] == 150000
    bench = harness.load("BENCHMARK.json")
    entry = next(e for e in bench["configs"] if e["name"] == "cluster-5k-150k")
    assert entry["reduced"] == [] and entry["source"] == c["config"]["source"]
    assert len(entry["source"]) <= 200


@pytest.mark.parametrize("rehearsal,seed,size,sha", PINS,
                         ids=[f"{'rehearsal' if p[0] else 'full'}-{p[1]}" for p in PINS])
def test_the_request_body_is_pinned(rehearsal, seed, size, sha):
    c = cell(rehearsal)
    body = harness.build_inputs(c["config"], c["traffic"], seed)["body"]
    assert len(body) == size and hashlib.sha256(body).hexdigest() == sha


# -- the cell's data ------------------------------------------------------------


def test_the_data_files_hold_the_references_numbers(small):
    c, got = small["c"], small["exact"]
    assert [got[k] for k in checks.COUNT_KEYS] == [500] + c["locks"]["0"] == [500, 500, 0]
    equals = c["guarantees"]["replay_equals"]
    assert placements.digest(got["placements"]) == equals["placements_digest"]
    for key in ("sampled_attempts", "nodes_visited", "nodes_scored", "sampling_zones"):
        assert got[key] == equals[key], key
    assert len(got["placements"]) == 15500 and got["nodes_visited"] > got["nodes_scored"]


def test_placements_py_prints_the_digest_the_configuration_holds(small):
    out = io.StringIO()
    with redirect_stdout(out):
        assert placements.main(["--workload", CELL, "--rehearsal"]) == 0
    line = json.loads(out.getvalue())
    equals = small["c"]["guarantees"]["replay_equals"]
    assert line["placements_digest"] == equals["placements_digest"] and line["pods"] == 15500
    # bfloat16 is no control here: loads differ in whole pods of one size.
    assert line["control_moved"] == 0 and line["control_digest"] == line["placements_digest"]


def as_job_document(c: dict, got: dict, steps: int) -> dict:
    """A job's result document that says of the device path all that the
    configuration guarantees, with ``got``'s counts, sums and placements."""
    block = dict(c["guarantees"]["replay_equals"], device_steps=steps,
                 placements_digest=placements.digest(got["placements"]),
                 **{k: got[k] for k in ("sampled_attempts", "nodes_visited", "nodes_scored")})
    result = dict({k: got[k] for k in checks.COUNT_KEYS}, steps=steps)
    return {"state": "succeeded", "result": result, "replay": block}


def judged(c: dict, inputs: dict, doc: dict) -> set:
    """The names of the comparisons that fail, judged as a run is."""
    win = {"counted": [{"doc": doc}], "failed": 0}
    device = {"platform": c["platform"], "count": 1}
    out = harness.judge(c, inputs, 0, win, [], [], device, {"platform": c["platform"]})
    return {x["name"] for x in out if not x["ok"]}


def test_the_cell_is_correct_with_the_reference_in_the_programs_place_and_no_control_is(small):
    """At the rehearsal size, by ``run.judge``.  Either control schedules all
    500 arrivals (every pod fits somewhere), so the lock and the three counts
    pass — and gives another digest and other sums.  A program that counted
    the snapshot's objects as events, or ran a step for them, fails the counts
    and ``job.steps``."""
    c, inputs, exact = small["c"], small["inputs"], small["exact"]
    assert judged(c, inputs, as_job_document(c, exact, 1)) == set()
    stood = [op["createOperation"]["object"]["metadata"]["name"]   # the snapshot's pods
             for op in inputs["operations"][500:15500]]
    for control, moved_at_least, fails in (
            (small["uncharged"], 450, {"replay.placements_digest", "replay.nodes_visited",
                                       "replay.nodes_scored"}),
            (small["by_name"], 30, {"replay.placements_digest", "replay.nodes_visited"})):
        assert [control[k] for k in checks.COUNT_KEYS] == [exact[k] for k in checks.COUNT_KEYS]
        moved = sum(control["placements"][p] != n for p, n in exact["placements"].items())
        assert moved >= moved_at_least
        assert all(control["placements"][p] == exact["placements"][p] for p in stood)
        assert judged(c, inputs, as_job_document(c, control, 1)) == fails
    as_events = sampled_zoned.replay(inputs["operations"])
    assert as_events["placements"] == exact["placements"]
    assert judged(c, inputs, as_job_document(c, as_events, 2)) == {
        "job.steps", "replay.device_steps", "job.counts_vs_reference_replay"}
