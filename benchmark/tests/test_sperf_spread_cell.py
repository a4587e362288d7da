"""The cell ``sperf-5k-spread_5kpods`` (PR 40): upstream's ``TopologySpreading``
at ``5000Nodes_5000Pods`` under the default scheduler configuration, as data
(``configs/sperf-5k-spread.json``, ``traffic/5kpods.json``,
``cells/sperf-5k-spread_5kpods.json``, ``templates/pod-with-topology-spreading.json``;
generator kind ``sperf_labels``) with a plain reference of its own,
``references/sampled_zoned.py``: sampled scoring whose walk goes through the
scheduler's zone-interleaved node tree.  Pinned here: the tree and the
reference on cases derived by hand from ``node_tree.go``; the ``sperf_labels``
kind's round-robin labels; the bytes of the request body at two seeds and both
sizes; that the numbers in the data files are the reference's; the cell judged
by ``run.judge`` as a run is; and the control — the same reference walking in
name order, which is ``references/sampled.py`` — failed by the digest and by
``nodes_visited``."""

import ast
import hashlib
import os

import pytest

import checks
import placements
import run as harness
from kinds import sperf, sperf_labels
from references import sampled, sampled_zoned

CELL = "sperf-5k-spread_5kpods"
ZONE_KEY = "topology.kubernetes.io/zone"

PINS = [   # rehearsal, seed, bytes, sha256 of inputs["body"]
    (False, 0, 6861810, "3a2d67f83a483ea4311582229335f34bdbdc0dd82ffb67f63422a5c1da7e4e2f"),
    (False, 1, 6861810, "13b59b813b7f280e394008787d4edc5aeb231f05b9fec5baee8a1b000a58cfdc"),
    (True, 0, 1171310, "153262c4fc864b2f79a6648593e75a50f3e10c59a4161a636718c1b929f3e05b"),
    (True, 1, 1171310, "cc014c96d4868ea394d12adb7ce69aed16a60d2cb8bee4798018237fe3400d91"),
]


def cell(rehearsal: bool) -> dict:
    return harness.load_cell(harness.load("BENCHMARK.json"), CELL, rehearsal)


def inputs_of(rehearsal: bool, seed: int = 0) -> dict:
    c = cell(rehearsal)
    return harness.build_inputs(c["config"], c["traffic"], seed)


# -- the tree and the reference, by hand ----------------------------------------


def test_the_zone_key_is_upstreams():
    key = sampled_zoned.get_zone_key
    node = lambda **labels: {"metadata": {"name": "n", "labels": labels}}
    assert key({"metadata": {"name": "n"}}) == "" and key(node(disk="ssd")) == ""
    assert key(node(**{ZONE_KEY: "z"})) == ":\x00:z"
    assert key(node(**{"topology.kubernetes.io/region": "r", ZONE_KEY: "z"})) == "r:\x00:z"
    assert key(node(**{"failure-domain.beta.kubernetes.io/zone": "z",
                       "failure-domain.beta.kubernetes.io/region": "r"})) == "r:\x00:z"


def test_the_tree_deals_uneven_zones_round_robin_and_drops_a_zone_that_empties():
    tree = sampled_zoned.NodeTree()
    for name, zone in [("n0", "b"), ("n1", "a"), ("n2", "a"), ("n3", "c"), ("n4", "a"),
                       ("n5", "c"), ("n6", "a")]:
        tree.add(name, zone)
    assert tree.zones == ["b", "a", "c"]
    assert tree.list() == ["n0", "n1", "n3", "n2", "n5", "n4", "n6"]
    tree.remove("n0")                       # b is left empty: it goes
    assert tree.zones == ["a", "c"] and tree.list() == ["n1", "n3", "n2", "n5", "n4", "n6"]
    tree.remove("n2")                       # the others keep their order
    tree.add("n7", "b")                     # b comes back last
    tree.add("n2", "c")
    assert tree.zones == ["a", "c", "b"]
    assert tree.list() == ["n1", "n3", "n7", "n4", "n5", "n6", "n2"]
    tree.add("n4", "b")                     # a relabelled node is removed and added
    assert tree.list() == ["n1", "n3", "n7", "n6", "n5", "n4", "n2"]


def node(i: int, zone: str = "", big: bool = False) -> dict:
    size = {"cpu": "16", "memory": "64Gi"} if big else {"cpu": "4", "memory": "16Gi"}
    meta = {"name": f"n{i:03d}"}
    if zone:
        meta["labels"] = {ZONE_KEY: zone}
    return {"apiVersion": "v1", "kind": "Node", "metadata": meta, "spec": {},
            "status": {"allocatable": dict(size, pods="110")}}


def pod(name: str) -> dict:
    spec = {"containers": [{"name": "c", "resources": {"requests": {"cpu": "1", "memory": "4Gi"}}}]}
    return {"apiVersion": "v1", "kind": "Pod", "metadata": {"name": name, "namespace": "default"},
            "spec": spec}


def create(step: int, obj: dict) -> dict:
    return {"step": step, "createOperation": {"object": obj}}


def hand_case() -> list:
    """120 nodes of 4 cpu / 16Gi (k = 100): n000..n059 in zone a, n060..n119
    in zone b.  n110, of 16 cpu / 64Gi, is the best score for a 1-cpu / 4Gi
    pod.  The tree's list: n000, n060, n001, n061, ...: n110 is the 102nd."""
    ops = [create(0, node(i, "a" if i < 60 else "b", big=i == 110)) for i in range(120)]
    return ops + [create(1, pod("p0")), create(1, pod("p1"))]


def test_the_reference_on_a_case_derived_by_hand():
    """Interleaved, p0 from 0 visits the list's first 100 places: n000..n049
    and n060..n109, so n110 is NOT among them: the first of the equal nodes in
    the simulator's node order, n000; start 100.  p1 from 100 visits places
    100-119 and 0-79, n110 (place 101) among them: it wins; start 80.  With a
    full node n055 (place 110) the two orders count differently, as worked out
    below."""
    got = sampled_zoned.replay(hand_case())
    assert [got[k] for k in checks.COUNT_KEYS] == [122, 2, 0]
    assert got["placements"] == {"p0": "n000", "p1": "n110"}
    assert (got["sampled_attempts"], got["nodes_visited"], got["sampling_start"]) == (2, 200, 80)
    assert got["sampling_zones"] == 2
    full = pod("full")
    full["spec"]["containers"][0]["resources"]["requests"] = {"cpu": "4", "memory": "1Gi"}
    full["spec"]["nodeName"] = "n055"
    ops = hand_case() + [create(0, full)]
    zoned, named = sampled_zoned.replay(ops), sampled_zoned.replay(ops, interleave=False)
    # Interleaved: p0 sees places 0-99 (n055 is at 110): 100; p1 from 100 sees
    # places 100-119 and 0-80, n055 among them: 101.
    assert (zoned["nodes_visited"], zoned["nodes_scored"], zoned["sampling_start"]) == (201, 200, 81)
    # By name: p0 sees n000..n100 with n055 among them: 101; p1 from 101 sees
    # n101..n119 and n000..n081, past n055 again: 101.
    assert (named["nodes_visited"], named["nodes_scored"], named["sampling_start"]) == (202, 200, 82)
    for key in ("placements", "nodes_visited", "nodes_scored", "sampling_start"):
        assert named[key] == sampled.replay(ops)[key], key


def test_unlabelled_nodes_give_what_the_name_order_reference_gives():
    ops = [create(0, node(i, big=i == 110)) for i in range(120)] + [create(1, pod("p0")),
                                                                     create(2, pod("p1"))]
    got, want = sampled_zoned.replay(ops), sampled.replay(ops)
    for key in ("placements", "nodes_visited", "nodes_scored", "sampling_start", "steps"):
        assert got[key] == want[key], key
    assert got["sampling_zones"] == 1


def test_the_reference_imports_nothing_of_the_program():
    path = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                        "references", "sampled_zoned.py")
    with open(path, encoding="utf-8") as f:
        tree = ast.parse(f.read())
    names = [a.name for n in ast.walk(tree) if isinstance(n, ast.Import) for a in n.names]
    names += [n.module for n in ast.walk(tree) if isinstance(n, ast.ImportFrom)]
    assert sorted(names) == ["__future__", "numpy", "references.sampled", "replay"]


# -- the generator kind ---------------------------------------------------------


def test_the_label_strategy_deals_round_robin_by_node_index():
    gen = cell(True)["config"]["generator"]
    ops = sperf_labels.operations(gen, "500Nodes")
    plain = sperf.operations(gen, "500Nodes")
    assert len(ops) == len(plain) == 2500
    nodes = [op["createOperation"]["object"] for op in ops
             if op["createOperation"]["object"]["kind"] == "Node"]
    assert [n["metadata"]["name"] for n in nodes] == [f"node-default-{i}" for i in range(500)]
    assert [n["metadata"]["labels"] for n in nodes] == [
        {ZONE_KEY: ("moscow-1", "moscow-2", "moscow-3")[i % 3]} for i in range(500)]
    # Nothing else differs from the plain kind's stream.
    for op in ops:
        op["createOperation"]["object"]["metadata"].pop("labels", None) \
            if op["createOperation"]["object"]["kind"] == "Node" else None
    assert ops == plain


def test_a_strategy_on_another_opcode_is_refused():
    gen = dict(cell(True)["config"]["generator"])
    gen["workloadTemplate"] = [dict(op) for op in gen["workloadTemplate"]]
    gen["workloadTemplate"][1]["labelNodePrepareStrategy"] = {"labelKey": "k", "labelValues": ["v"]}
    with pytest.raises(ValueError):
        sperf_labels.operations(gen, "500Nodes")


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
    assert c["reference"] is sampled_zoned and c["cell"]["chips"] == 1
    inputs = inputs_of(False)
    kinds = {}
    for op in inputs["operations"]:
        obj = op["createOperation"]["object"]
        kinds.setdefault((op["step"], obj["metadata"]["name"].rsplit("-", 1)[0]), []).append(obj)
    assert {k: len(v) for k, v in kinds.items()} == {
        (0, "node-default"): 5000, (1, "pod-default"): 5000,
        (2, "pod-with-topology-spreading"): 5000}
    assert inputs["steps"] == 3 and inputs["units"] == 15000
    spreading = kinds[2, "pod-with-topology-spreading"][0]
    assert spreading["metadata"]["labels"] == {"color": "blue"}
    assert spreading["spec"]["topologySpreadConstraints"] == [{
        "maxSkew": 5, "topologyKey": ZONE_KEY, "whenUnsatisfiable": "DoNotSchedule",
        "labelSelector": {"matchLabels": {"color": "blue"}}}]
    requests = spreading["spec"]["containers"][0]["resources"]["requests"]
    assert requests == {"cpu": "100m", "memory": "500Mi"}
    zones = sorted({n["metadata"]["labels"][ZONE_KEY] for n in kinds[0, "node-default"]})
    assert zones == ["moscow-1", "moscow-2", "moscow-3"]


@pytest.mark.parametrize("rehearsal", [True, False], ids=["rehearsal", "full"])
def test_the_data_files_hold_the_references_numbers(rehearsal):
    c, inputs = cell(rehearsal), inputs_of(rehearsal, seed=2147483693)
    got = sampled_zoned.replay(inputs["operations"])
    nodes, pods = (500, 2000) if rehearsal else (5000, 10000)
    assert [got[k] for k in checks.COUNT_KEYS] == [nodes + pods] + c["locks"]["0"] == [nodes + pods, pods, 0]
    equals = c["guarantees"]["replay_equals"]
    assert placements.digest(got["placements"]) == equals["placements_digest"]
    for key in ("sampled_attempts", "nodes_visited", "nodes_scored", "sampling_zones"):
        assert got[key] == equals[key], key
    # The constraint bites: walks pass nodes that cannot take the pod.
    assert got["sampled_attempts"] == pods and got["nodes_visited"] > got["nodes_scored"]
    assert got["sampling_start"] == got["nodes_visited"] % nodes


def as_job_document(c: dict, got: dict, steps: int, **replay_block) -> dict:
    """A job's result document that says of the device path all that the
    configuration guarantees, with ``got``'s counts, sums and placements."""
    block = dict(c["guarantees"]["replay_equals"], device_steps=steps,
                 placements_digest=placements.digest(got["placements"]),
                 **{k: got[k] for k in ("sampled_attempts", "nodes_visited", "nodes_scored")},
                 **replay_block)
    result = dict({k: got[k] for k in checks.COUNT_KEYS}, steps=steps)
    return {"state": "succeeded", "result": result, "replay": block}


def judged(c: dict, inputs: dict, doc: dict) -> set:
    """The names of the comparisons that fail, judged as a run is."""
    win = {"counted": [{"doc": doc}], "failed": 0}
    device = {"platform": c["platform"], "count": 1}
    out = harness.judge(c, inputs, 0, win, [], [], device, {"platform": c["platform"]})
    return {x["name"] for x in out if not x["ok"]}


def test_the_cell_is_correct_with_the_reference_in_the_programs_place_and_the_control_is_not():
    """At the rehearsal size, by ``run.judge``.  The control walks in name
    order (what the program did before it kept a node tree): the same three
    counts (every pod fits somewhere either way), so the lock and the counts
    pass — and another digest, other sums.  A program that reports one zone
    fails that too."""
    c, inputs = cell(True), inputs_of(True)
    exact = sampled_zoned.replay(inputs["operations"])
    assert judged(c, inputs, as_job_document(c, exact, inputs["steps"])) == set()
    control = sampled_zoned.replay(inputs["operations"], interleave=False)
    assert [control[k] for k in checks.COUNT_KEYS] == [exact[k] for k in checks.COUNT_KEYS]
    assert control["placements"] == sampled.replay(inputs["operations"])["placements"]
    moved = sum(control["placements"][p] != n for p, n in exact["placements"].items())
    assert moved > 1000
    assert (control["nodes_visited"], control["nodes_scored"]) == (506813, 456393)
    assert judged(c, inputs, as_job_document(c, control, inputs["steps"])) == {
        "replay.placements_digest", "replay.nodes_visited", "replay.nodes_scored"}
    one_zone = as_job_document(c, control, inputs["steps"], sampling_zones=1)
    assert "replay.sampling_zones" in judged(c, inputs, one_zone)
