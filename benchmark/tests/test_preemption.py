"""Priorities and DefaultPreemption in the plain replay (``replay.py``), and
upstream's ``PreemptionBasic`` generated from data (``kinds/sperf.py``,
``templates/``, ``tests/data/sperf-preempt.json``: a configuration-shaped
document; no Python file holds a size).

``PreemptionBasic`` at ``5Nodes``, by hand.  Step 0: five nodes of 4 cpu.
Step 1: twenty ``pod-low-priority`` pods (900m, priority 0) in one pass, queued
by name (0, 1, 10, 11, ..., 19, 2, ..., 9); every one goes to the emptiest
node, the first in node order among equals, so they are dealt round: node 0
gets 0, 13, 18, 5; node 1 gets 1, 14, 19, 6; node 2 gets 10, 15, 2, 7; node 3
gets 11, 16, 3, 8; node 4 gets 12, 17, 4, 9 (3,600m of 4,000m each).  Step 2:
five ``pod-high-priority`` pods (3,000m, priority 10); no node has room, so
each preempts.  High-priority 0: on every node all four low pods come off,
the pod fits, and they come back most important first — equal priority and
start, so by name: the first fits beside it (900m + 3,000m), the other three
do not.  Five candidates tie on every criterion (highest priority 0, three
victims, equal starts); the first by name wins: node 0, victims 13, 18, 5.
High-priority 1: node 0 now holds one low pod and, counted in as nominated,
high-priority 0: with the low pod off there is 1,000m, not 3,000m: no
candidate; nodes 1 to 4 tie; node 1, victims 14, 19, 6.  And so on: each of
the five takes the next node and three victims (15 in all), none is scheduled
(0 scheduled, 5 unschedulable attempts).  Step 3 (the trailing ``pod-default``
pod, which gives the stream its next pass): each high-priority pod is tried on
its nominated node first and binds there; the 100m pod fits the 100m that is
left everywhere, scores equal, and goes to node 0: 6 scheduled.  In all 31
events, 26 scheduled, 5 unschedulable attempts; 11 pods are left, one low and
one high on every node.
"""

import copy
import json
import os
import time

import pytest

import generators
import placements
import replay
import run as harness

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def load(name):
    with open(os.path.join(DATA, name), encoding="utf-8") as f:
        return json.load(f)


CASES = load("preemption_victims.json")["cases"]


def case_operations(case):
    """A victim case as a stream: nodes and their bound victims in step 0,
    the preemptor in step 1."""
    ops = [generators.create_op(0, generators.make_node(n["name"], n["cpu"], "8Gi", 110, labels={}))
           for n in case["nodes"]]
    for v in case["victims"]:
        pod = generators.make_pod(v["name"], v["cpu"], None, labels={}, node_name=v["node"])
        pod["spec"]["priority"] = v["priority"]
        pod["metadata"]["creationTimestamp"] = v.get("creationTimestamp") or "2024-01-01T00:00:00Z"
        pod["status"] = {"phase": "Running"}
        if v["startTime"]:
            pod["status"]["startTime"] = v["startTime"]
        ops.append(generators.create_op(0, pod))
    want = case["preemptor"]
    pod = generators.make_pod("preemptor", want["cpu"], None, labels={})
    pod["spec"]["priority"] = want["priority"]
    if want["preemptionPolicy"]:
        pod["spec"]["preemptionPolicy"] = want["preemptionPolicy"]
    return ops + [generators.create_op(1, pod)]


@pytest.mark.parametrize("case", CASES, ids=[c["name"] for c in CASES])
def test_the_hand_derived_victim_cases(case):
    """Every case's nominated node and its victims in the order they go.  The
    replay covers the objects of all of them (bound pods, priorities, start
    times, ``preemptionPolicy``): it refuses none."""
    got = replay.replay(case_operations(case))
    assert got["nominated"].get("preemptor") == case["expected_nominated"]
    assert got["evicted"] == case["expected_victims"]
    assert got["steps"] == [(0, 0), (0, 1)] and "preemptor" in got["placements"]
    assert not set(got["evicted"]) & set(got["placements"])


def test_a_priority_class_gives_the_priority_and_has_to_exist():
    case = copy.deepcopy(CASES[0])
    ops = case_operations(case)
    spec = ops[-1]["createOperation"]["object"]["spec"]
    del spec["priority"]
    spec["priorityClassName"] = "high"
    with pytest.raises(replay.NotCovered):
        replay.replay(ops)
    cls = {"apiVersion": "scheduling.k8s.io/v1", "kind": "PriorityClass",
           "metadata": {"name": "high"}, "value": 10}
    got = replay.replay([generators.create_op(0, cls)] + ops)
    assert got["evicted"] == case["expected_victims"] and got["eventsApplied"] == len(ops) + 1
    never = dict(cls, preemptionPolicy="Never")
    assert replay.replay([generators.create_op(0, never)] + ops)["evicted"] == []
    spec["priorityClassName"] = "system-node-critical"   # exists in every cluster
    assert replay.replay(ops)["evicted"] == case["expected_victims"]


def test_the_queue_goes_by_priority_before_name():
    """One 2-cpu node, two 2-cpu pods in one pass: the one of the higher
    priority is placed, whatever its name."""
    node = generators.create_op(0, generators.make_node("n", "2", "8Gi", 110, labels={}))
    pods = []
    for name, priority in (("a", 1), ("b", 5)):
        pod = generators.make_pod(name, "2", None, labels={})
        pod["spec"]["priority"] = priority
        pods.append(generators.create_op(1, pod))
    got = replay.replay([node] + pods)
    assert got["placements"] == {"a": None, "b": "n"} and got["steps"][1] == (1, 1)


def constrained(name, cpu, priority, app, anti_app=None, node=None):
    """A pod labelled ``app``; with ``anti_app``, a required anti-affinity
    against pods so labelled on its own node."""
    affinity = anti_app and {"podAntiAffinity": {"requiredDuringSchedulingIgnoredDuringExecution": [
        {"labelSelector": {"matchLabels": {"app": anti_app}}, "topologyKey": generators.HOST_KEY}]}}
    pod = generators.make_pod(name, cpu, None, labels={"app": app}, node_name=node or "",
                              affinity=affinity)
    pod["spec"]["priority"] = priority
    return generators.create_op(1 if node is None else 0, pod)


def labelled_node(name):
    return generators.create_op(0, generators.make_node(
        name, "4", "8Gi", 110, labels={generators.HOST_KEY: name}))


def test_a_victim_that_the_preemptors_own_anti_affinity_names():
    """By hand.  Nodes a and b have room; a holds a ``web`` pod of priority 1,
    b one of priority 20; the preemptor (priority 10) may not run beside a
    ``web`` pod, so no node fits.  On a the lower pod comes off, the preemptor
    fits, the pod comes back and it no longer fits: a victim.  On b nothing is
    of a lower priority: no candidate."""
    ops = [labelled_node("a"), labelled_node("b"),
           constrained("web-lo", "1", 1, "web", node="a"), constrained("web-hi", "1", 20, "web", node="b"),
           constrained("preemptor", "1", 10, "db", anti_app="web")]
    got = replay.replay(ops)
    assert got["nominated"] == {"preemptor": "a"} and got["evicted"] == ["web-lo"]


def test_a_victim_whose_own_anti_affinity_keeps_the_preemptor_out():
    """By hand.  The one node has room, but its ``guard`` pod (priority 1) may
    not run beside a ``db`` pod, which the preemptor (priority 10) is.  Both
    lower pods come off and it fits; ``other`` (priority 2, the more important)
    comes back and it still fits; ``guard`` comes back and it does not: the
    victim is ``guard`` alone."""
    ops = [labelled_node("a"), constrained("guard", "1", 1, "guard", anti_app="db", node="a"),
           constrained("other", "1", 2, "x", node="a"), constrained("preemptor", "1", 10, "db")]
    got = replay.replay(ops)
    assert got["nominated"] == {"preemptor": "a"} and got["evicted"] == ["guard"]


# -- PreemptionBasic from data --------------------------------------------------


def preemption_basic(workload, seed=0, trailing=1):
    config = load("sperf-preempt.json")
    last = config["generator"]["workloadTemplate"][-1]
    last.update(count=trailing, podsPerStep=1)
    return harness.build_inputs(config, {"workload": workload}, seed)


def test_preemption_basic_at_5_nodes_is_the_hand_derived_answer():
    inputs = preemption_basic("5Nodes", seed=11)
    assert inputs["units"] == 31 and inputs["steps"] == 4
    names = [op["createOperation"]["object"]["metadata"]["name"] for op in inputs["operations"]]
    assert sorted(names[:5]) == [f"node-default-{i}" for i in range(5)] and len(set(names)) == 31
    got = replay.replay(inputs["operations"])
    assert [got[k] for k in ("eventsApplied", "podsScheduled", "unschedulableAttempts")] == [31, 26, 5]
    assert got["steps"] == [(0, 0), (20, 0), (0, 5), (6, 0)]
    low = "pod-low-priority-"
    assert got["evicted"] == [low + i for i in "13 18 5 14 19 6 15 2 7 16 3 8 17 4 9".split()]
    want = {f"pod-high-priority-{i}": f"node-default-{i}" for i in range(5)}
    want.update({low + str(k): f"node-default-{i}" for i, k in enumerate((0, 1, 10, 11, 12))})
    want["pod-default-0"] = "node-default-0"
    assert got["placements"] == want and got["nominated"] == {}


def test_the_seed_orders_arrivals_and_nothing_else():
    a, b = preemption_basic("5Nodes", seed=1), preemption_basic("5Nodes", seed=2147483693)
    assert a["body"] != b["body"] and len(a["body"]) == len(b["body"])
    assert a["body"] == preemption_basic("5Nodes", seed=1)["body"]
    ra, rb = replay.replay(a["operations"]), replay.replay(b["operations"])
    assert ra["placements"] == rb["placements"] and ra["evicted"] == rb["evicted"]


def test_opcodes_to_steps_and_a_priority_class_through_create_any(monkeypatch, tmp_path):
    """Nodes and other objects share a step, every ``createPods`` starts one
    of its own and takes ``ceil(count / podsPerStep)``; names count through a
    template's opcodes; a pod may take its priority from a class that a
    ``createAny`` made."""
    from kinds import sperf
    for name in ("node-default", "pod-default"):
        (tmp_path / f"{name}.json").write_text(json.dumps({"object": sperf.template(name)}))
    (tmp_path / "pc.json").write_text(json.dumps({"object": {
        "apiVersion": "scheduling.k8s.io/v1", "kind": "PriorityClass", "value": 7}}))
    classy = sperf.template("pod-default")
    classy["spec"]["priorityClassName"] = "pc-0"
    (tmp_path / "pod-classy.json").write_text(json.dumps({"object": classy}))
    monkeypatch.setattr(sperf, "TEMPLATES", str(tmp_path))
    gen = {"workloads": {"w": {"n": 2, "p": 5}}, "workloadTemplate": [
        {"opcode": "createNodes", "countParam": "$n", "template": "node-default"},
        {"opcode": "createAny", "count": 1, "template": "pc"},
        {"opcode": "createPods", "countParam": "$p", "podsPerStep": 2, "template": "pod-default"},
        {"opcode": "createNodes", "count": 1, "template": "node-default"},
        {"opcode": "createPods", "count": 1, "template": "pod-classy"},
        {"opcode": "createPods", "count": 2, "template": "pod-default"}]}
    ops = sperf.operations(gen, "w")
    where = [(op["step"], op["createOperation"]["object"]["metadata"]["name"]) for op in ops]
    assert where == [(0, "node-default-0"), (0, "node-default-1"), (0, "pc-0"),
                     (1, "pod-default-0"), (1, "pod-default-1"), (2, "pod-default-2"),
                     (2, "pod-default-3"), (3, "pod-default-4"), (4, "node-default-2"),
                     (5, "pod-classy-0"), (6, "pod-default-5"), (6, "pod-default-6")]
    got = replay.replay(ops)
    assert got["eventsApplied"] == 12 and got["podsScheduled"] == 8
    with pytest.raises(ValueError):
        sperf.operations(dict(gen, workloadTemplate=[{"opcode": "barrier", "template": "pc"}]), "w")
    with pytest.raises(ValueError):
        sperf.operations(dict(gen, workloadTemplate=[
            {"opcode": "createPods", "count": 1, "template": "node-default"}]), "w")


@pytest.mark.parametrize("workload,counts,digest", [
    ("500Nodes", [3001, 2501, 500], "e4365f71e609"),
    ("5000Nodes", [30001, 25001, 5000], "5412064edab3"),
])
def test_preemption_basic_repeats_exactly(workload, counts, digest):
    """Counts and the digest of all placements at upstream's two larger sizes,
    pinned: the same from run to run and from session to session.  Every
    measured pod evicts three and binds one pass later.  The check's own time
    (CPU sandbox, PR 31): 0.7 s and 32 s."""
    inputs = preemption_basic(workload, seed=3)
    started = time.monotonic()
    got = replay.replay(inputs["operations"])
    print(f"{workload}: replay of {inputs['units']} operations in {time.monotonic() - started:.1f} s")
    assert [got[k] for k in ("eventsApplied", "podsScheduled", "unschedulableAttempts")] == counts
    n = counts[2]
    assert got["steps"] == [(0, 0), (4 * n, 0), (0, n), (n + 1, 0)] and len(got["evicted"]) == 3 * n
    assert placements.digest(got["placements"]).startswith(digest)
    if workload == "500Nodes":
        again = replay.replay(preemption_basic(workload, seed=4)["operations"])
        assert again["placements"] == got["placements"] and again["evicted"] == got["evicted"]


def test_the_control_at_bfloat16_is_seen_by_neither_counts_nor_digest_here():
    """``PreemptionBasic``'s pods are all alike and its nodes too: at bfloat16
    the scores are other numbers with the same order, so the control lands
    every pod where the exact replay does.  A cell of this workload cannot
    rest on the control at lower precision: its control has to break a
    guarantee (a victim spared, a nomination ignored), as the contract says of
    a system that states no precision.  On the priority-tiered churn stream
    below the digest sees it."""
    inputs = preemption_basic("500Nodes")
    exact = replay.replay(inputs["operations"])
    control = replay.replay(inputs["operations"], precision="bf16")
    assert control["placements"] == exact["placements"] and control["steps"] == exact["steps"]


def test_the_general_victim_search_gives_the_arithmetic_ones_answers(monkeypatch):
    """``victims_on`` has two ways: node arithmetic where NodeResourcesFit
    alone decides, and taking pods off and putting them back in the cluster's
    columns with every filter run again.  Forced down the second, the
    unconstrained workloads come out the same."""
    def tiny():
        config = load("sperf-preempt.json")
        config["generator"]["workloads"]["tiny"] = {"initNodes": 30, "initPods": 120, "measurePods": 30}
        return harness.build_inputs(config, {"workload": "tiny"}, 0)["operations"]
    streams = [tiny(), tiered_churn(5, 24, 2), tiered_churn(6, 20, 3)]
    fast = [replay.replay(ops) for ops in streams]
    monkeypatch.setattr(replay.Cluster, "plain", lambda self, pod: False)
    for ops, want in zip(streams, fast):
        got = replay.replay(ops)
        assert want["evicted"] and got == want


# -- against the program's per-pass path (CPU) ---------------------------------


def program(operations):
    """The program's per-pass path over ``operations``, in process: per step
    (scheduled, unschedulable), the evictions in order, where every pod is."""
    import sys
    sys.path.insert(0, ROOT)
    scenario = pytest.importorskip("ksim_tpu.scenario")
    from ksim_tpu.scenario.spec import load_scenario
    runner = scenario.ScenarioRunner(preemption=True, pod_bucket_min=128)
    evicted = []
    runner.service.add_eviction_listener(lambda namespace, name: evicted.append(name))
    result = runner.run(iter(load_scenario({"spec": {"operations": operations}})))
    pods = runner.store.list("pods", copy_objs=False)
    return {"steps": [(s.scheduled, s.unschedulable) for s in result.steps], "evicted": evicted,
            "placements": {p["metadata"]["name"]: p["spec"].get("nodeName") for p in pods},
            "nominated": {p["metadata"]["name"]: p["status"]["nominatedNodeName"] for p in pods
                          if (p.get("status") or {}).get("nominatedNodeName")}}


def tiered_churn(n_nodes, steps, per_step):
    """Nodes of 4 cpu, pods of 1,500m at priorities 0, 0, 5, 10 in turn (the
    shape of tests/test_replay_device.py's priority-strata stream): the nodes
    fill after a few steps, then the higher strata preempt the lowest."""
    ops = [generators.create_op(0, generators.make_node(f"n-{i}", "4", "16Gi", 110, labels={}))
           for i in range(n_nodes)]
    k = 0
    for step in range(1, steps + 1):
        for j in range(per_step):
            k += 1
            pod = generators.make_pod(f"p-{k}", "1500m", "256Mi", labels={"app": "web"})
            pod["spec"]["priority"] = [0, 0, 5, 10][k % 4]
            pod["metadata"]["creationTimestamp"] = f"2026-01-{step:02d}T00:00:{j:02d}Z"
            ops.append(generators.create_op(step, pod))
    return ops


@pytest.mark.parametrize("size", [(3, 16, 1), (3, 16, 2), (5, 24, 2)], ids=str)
def test_a_priority_tiered_stream_agrees_with_the_program(size):
    """At most one preemptor finds a victim in a pass of these streams: the
    replay and the program's per-pass path agree in every step's counts, every
    eviction in order, every placement and every nomination that stands."""
    ops = tiered_churn(*size)
    got, want = replay.replay(ops), program(ops)
    assert got["evicted"], "the stream never preempts: the test is vacuous"
    for key in ("steps", "evicted", "placements", "nominated"):
        assert got[key] == want[key], key


def test_the_control_at_bfloat16_on_the_tiered_stream():
    """Counts or digest?  Neither, on this stream either: its pods and nodes
    are all alike too (the next test has mixed objects)."""
    ops = tiered_churn(5, 24, 2)
    exact, control = replay.replay(ops), replay.replay(ops, precision="bf16")
    assert control["placements"] == exact["placements"]


def tiered_constrained_churn(last_step):
    """The churn generator's own objects (mixed sizes, spread constraints,
    zone anti-affinity, node replacements; no pod deletions) with the pods'
    priorities 0, 0, 5, 10 in turn, one event a step."""
    from kinds import churn
    ops = churn.churn_operations(0, n_nodes=30, n_events=400, ops_per_step=1,
                                 pod_create_frac=0.92, pod_delete_frac=0.0)
    k = 0
    for op in ops:
        obj = op.get("createOperation", {}).get("object", {})
        if obj.get("kind") == "Pod":
            k += 1
            obj["spec"]["priority"] = [0, 0, 5, 10][k % 4]
    return [op for op in ops if op["step"] <= last_step]


def test_a_tiered_stream_of_the_churn_objects_agrees_until_a_nomination_is_tried():
    """Through step 231 (passes of up to 12 pods, two evictions) everything
    agrees.  In step 232 a pod nominated in the pass before is tried: the
    replay, as upstream's ``evaluateNominatedNode``, tries its nominated node
    first and binds it there; the program scores every node and binds it
    elsewhere.  Written down (PERF.md section 7), not tuned away."""
    ops = tiered_constrained_churn(231)
    got, want = replay.replay(ops, max_pods_per_pass=1024), program(ops)
    assert got["evicted"] == ["pod-144", "pod-188"]
    for key in ("steps", "evicted", "placements", "nominated"):
        assert got[key] == want[key], key
    assert got["nominated"] == {"pod-106": "node-0"}
    ops = tiered_constrained_churn(232)
    got, want = replay.replay(ops, max_pods_per_pass=1024), program(ops)
    assert got["placements"]["pod-106"] == "node-0" and got["steps"] == want["steps"]
    if want["placements"]["pod-106"] != "node-0":
        pytest.xfail("the program does not try a nominated node first (PERF.md section 7)")


def test_the_control_at_bfloat16_moves_counts_and_victims_where_objects_differ():
    """On the churn generator's mixed objects under priorities the control is
    seen by the counts, by the victims and by the digest alike."""
    ops = tiered_constrained_churn(10**9)
    exact = replay.replay(ops, max_pods_per_pass=1024)
    control = replay.replay(ops, max_pods_per_pass=1024, precision="bf16")
    counts = lambda got: [got[k] for k in ("podsScheduled", "unschedulableAttempts")]
    assert counts(control) != counts(exact) and control["evicted"] != exact["evicted"]
    assert placements.digest(control["placements"]) != placements.digest(exact["placements"])


DISAGREEMENT = """the program's per-pass path nominates a preemptor onto the node
that an earlier preemptor of the same pass has just emptied, as a candidate
with no victim; upstream's dry run refuses a node without a victim and counts
the earlier preemptor in as nominated (PERF.md section 7)"""


@pytest.mark.parametrize("nodes", [5, 20])
def test_preemption_basic_against_the_program(nodes):
    """Where several preemptors share a pass the two part ways, and this test
    writes down how (it is a finding about the program, not tuned away): the
    replay gives every preemptor of the pass a node of its own and binds them
    all in the next pass; the program gives the first one a node and
    nominates the others onto that same node, with no victim, so it places one
    preemptor a pass.  Given as many trailing passes as preemptors, both end
    with the same victims evicted in the same order and every high-priority
    pod on the same node.  (At 500Nodes the program needs 500 passes of its
    quadratic host search: not a test.)"""
    config = load("sperf-preempt.json")
    config["generator"]["workloads"]["test"] = {
        "initNodes": nodes, "initPods": 4 * nodes, "measurePods": nodes}
    config["generator"]["workloadTemplate"][-1].update(count=nodes + 1, podsPerStep=1)
    ops = harness.build_inputs(config, {"workload": "test"}, 0)["operations"]
    got, want = replay.replay(ops), program(ops)
    assert got["steps"][:4] == [(0, 0), (4 * nodes, 0), (0, nodes), (nodes + 1, 0)]
    high = lambda placed: {k: v for k, v in placed.items() if k.startswith("pod-high")}
    assert got["evicted"] == want["evicted"] and len(got["evicted"]) == 3 * nodes
    assert high(got["placements"]) == high(want["placements"])
    assert None not in high(got["placements"]).values()
    if got["steps"] != want["steps"]:
        assert want["steps"][2] == (0, nodes) and want["steps"][3] == (2, nodes - 1)
        pytest.xfail(DISAGREEMENT)
