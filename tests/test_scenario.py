"""Scenario replay harness (KEP-140 analogue): operation application,
node-drain requeue, result aggregation, and generator invariants."""

from __future__ import annotations

import copy
import json

import pytest

from ksim_tpu.scenario import Operation, ScenarioRunner, churn_scenario
from tests.helpers import make_node, make_pod


def test_runner_basic_flow():
    runner = ScenarioRunner()
    ops = [
        Operation(step=0, op="create", kind="nodes", obj=make_node("n0", cpu="2")),
        Operation(step=1, op="create", kind="pods", obj=make_pod("a", cpu="1", memory=None)),
        Operation(step=1, op="create", kind="pods", obj=make_pod("b", cpu="1", memory=None)),
        Operation(step=2, op="create", kind="pods", obj=make_pod("c", cpu="1", memory=None)),
        Operation(step=3, op="delete", kind="pods", name="a", namespace="default"),
    ]
    res = runner.run(ops)
    assert res.events_applied == 5
    assert res.pods_scheduled == 3  # a, b at step 1; c after a's deletion
    # Step 2: c could not fit (2 cpu taken) -> one unschedulable attempt.
    assert res.steps[2].unschedulable == 1
    # Step 3: a deleted frees capacity, c binds.
    assert res.steps[3].scheduled == 1
    assert res.steps[3].pending_after == 0
    assert runner.store.get("pods", "c")["spec"]["nodeName"] == "n0"


def test_node_delete_requeues_pods():
    runner = ScenarioRunner()
    res = runner.run(
        [
            Operation(step=0, op="create", kind="nodes", obj=make_node("n0")),
            Operation(step=0, op="create", kind="nodes", obj=make_node("n1")),
            Operation(step=1, op="create", kind="pods", obj=make_pod("p", cpu="1")),
        ]
    )
    assert res.pods_scheduled == 1
    bound_to = runner.store.get("pods", "p")["spec"]["nodeName"]
    other = {"n0": "n1", "n1": "n0"}[bound_to]
    res2 = runner.run(
        [Operation(step=0, op="delete", kind="nodes", name=bound_to)]
    )
    # The drained node's pod was requeued and rescheduled onto the other.
    assert res2.pods_scheduled == 1
    assert runner.store.get("pods", "p")["spec"]["nodeName"] == other


def test_churn_generator_shape():
    ops = list(churn_scenario(0, n_nodes=50, n_events=600, ops_per_step=40))
    assert sum(1 for o in ops if o.step == 0) == 50  # node bootstrap
    assert len(ops) >= 600
    kinds = {o.op for o in ops}
    assert kinds == {"create", "delete"}
    # Deterministic for equal seeds.
    ops2 = list(churn_scenario(0, n_nodes=50, n_events=600, ops_per_step=40))
    assert [(o.step, o.op, o.kind, o.name) for o in ops] == [
        (o.step, o.op, o.kind, o.name) for o in ops2
    ]


def test_churn_replay_end_to_end():
    runner = ScenarioRunner()
    res = runner.run(churn_scenario(3, n_nodes=30, n_events=400, ops_per_step=40))
    assert res.events_applied >= 400
    assert res.pods_scheduled > 100
    # The store stays consistent: every bound pod's node exists.
    nodes = {n["metadata"]["name"] for n in runner.store.list("nodes")}
    for p in runner.store.list("pods"):
        nn = p["spec"].get("nodeName")
        assert nn is None or nn in nodes


def test_churn_replay_deterministic():
    """Same seed -> identical placements and aggregates (the replayable-
    trace property the deterministic selectHost tiebreak exists for)."""
    def run_once():
        runner = ScenarioRunner()
        res = runner.run(churn_scenario(9, n_nodes=20, n_events=300, ops_per_step=30))
        bound = sorted(
            (p["metadata"]["name"], p["spec"].get("nodeName"))
            for p in runner.store.list("pods")
        )
        return res.pods_scheduled, res.unschedulable_attempts, bound

    assert run_once() == run_once()


# -- the segment reconciler's pod writes: re-wrap against the old patch --------
#
# The reconciler replaces a pod by a shallow re-wrap that shares the frozen
# manifest (``placed_pod`` / ``requeued_pod`` through ``ClusterStore.rewrap``).
# The closures below are what it handed the deep-copying ``ClusterStore.patch``
# before, verbatim: the stored objects, their resourceVersions and the watch
# events must come out the same.


def _old_write(store, att):
    """One attempt's pod write as ``_stage_attempt`` made it through
    ``patch`` (``_stage_device_step``'s plain bind is its ``node`` branch)."""
    from ksim_tpu.engine.annotations import apply_results_to_pod

    def mutate(obj):
        if att.anno:
            annos = obj.setdefault("metadata", {}).setdefault("annotations", {})
            apply_results_to_pod(annos, att.anno)
        if att.node:
            obj.setdefault("spec", {})["nodeName"] = att.node
            obj.setdefault("status", {})["phase"] = "Running"
            obj.get("status", {}).pop("nominatedNodeName", None)
        elif att.nominated:
            obj.setdefault("status", {})["nominatedNodeName"] = att.nominated
        elif att.gave_up:
            obj.get("status", {}).pop("nominatedNodeName", None)

    store.patch("pods", att.name, att.namespace, mutate, copy_ret=False)


def _old_requeue(store, name, namespace):
    def clear(obj):
        obj["spec"].pop("nodeName", None)
        obj.get("status", {}).pop("phase", None)

    store.patch("pods", name, namespace, clear, copy_ret=False)


def _att(name, **kw):
    from ksim_tpu.engine.replay import AttemptOutcome

    fields = dict(namespace="default", name=name, node=None, nominated=None,
                  victims=[], anno=None)
    return AttemptOutcome(**{**fields, **kw})


def _outcome(*, binds=(), attempts=None):
    from ksim_tpu.engine.replay import StepOutcome

    return StepOutcome(scheduled=0, unschedulable=0, pending_after=0, eligible=0,
                       slots_run=0, binds=list(binds), attempts=attempts)


def _rich_pod(name, **kw):
    """A manifest with depth: affinity terms, a spread constraint, labels."""
    term = {"labelSelector": {"matchLabels": {"app": "x"}},
            "topologyKey": "kubernetes.io/hostname"}
    pod = make_pod(
        name, cpu="250m", labels={"app": "x"},
        affinity={"podAntiAffinity": {
            "preferredDuringSchedulingIgnoredDuringExecution": [
                {"weight": 5, "podAffinityTerm": term}]}},
        topology_spread_constraints=[{"maxSkew": 1, "topologyKey": "zone",
                                      "whenUnsatisfiable": "ScheduleAnyway",
                                      "labelSelector": {"matchLabels": {"app": "x"}}}],
        **kw,
    )
    pod["metadata"]["annotations"] = {"kept": "as-is"}
    return pod


def _no_status(name):
    pod = _rich_pod(name)
    del pod["status"]
    return pod


def _nominated(name):
    pod = _rich_pod(name)
    pod["status"] = {"nominatedNodeName": "n1", "phase": "Pending"}
    return pod


ANNO = {"kube-scheduler-simulator.sigs.k8s.io/selected-node": "n0",
        "kube-scheduler-simulator.sigs.k8s.io/filter-result": '{"n0":{"NodeName":"passed"}}'}

# case -> (the pods in the store, the outcome's attempts or None for plain
# binds, the pod keys written)
WRITES = {
    "plain_bind": ([_rich_pod("a"), _no_status("b")], None, ["a", "b"]),
    "bind_with_annotations": (
        [_rich_pod("a"), _no_status("b")],
        [_att("a", node="n0", anno=ANNO), _att("b", node="n1", anno=ANNO)], ["a", "b"]),
    "annotations_alone": ([_rich_pod("a")], [_att("a", anno=ANNO)], ["a"]),
    "nomination": (
        [_rich_pod("a"), _no_status("b")],
        [_att("a", nominated="n0"), _att("b", nominated="n1", anno=ANNO)], ["a", "b"]),
    "nomination_given_up": (
        [_nominated("a"), _rich_pod("b"), _no_status("c")],
        [_att("a", gave_up=True), _att("b", gave_up=True), _att("c", gave_up=True)],
        ["a", "b", "c"]),
    "bind_of_a_nominated_pod": ([_nominated("a")], [_att("a", node="n1")], ["a"]),
    "requeue_clear": (
        [_rich_pod("a", node_name="n0", phase="Running"), _rich_pod("b", node_name="n1"),
         _rich_pod("c", node_name="n0", phase="Running")],
        "drain", ["a", "c"]),
}


def _seeded_store(pods):
    from ksim_tpu.state.cluster import ClusterStore

    store = ClusterStore()
    for n in ("n0", "n1"):
        store.create("nodes", make_node(n))
    for pod in pods:
        store.create("pods", copy.deepcopy(pod))
    return store


def _drain(stream):
    out = []
    while (ev := stream.next(timeout=0)) is not None:
        out.append((ev.kind, ev.event_type, json.dumps(ev.obj)))
    stream.close()
    return out


def _live(store):
    """name -> the store's own (frozen) pod object."""
    return {p["metadata"]["name"]: p for p in store.list("pods", copy_objs=False)}


@pytest.mark.parametrize("case", sorted(WRITES))
def test_segment_reconcile_rewrap_stores_what_patch_stored(case):
    pods, attempts, written = WRITES[case]
    old_store, new_store = _seeded_store(pods), _seeded_store(pods)
    old_watch, new_watch = old_store.watch(("pods", "nodes")), new_store.watch(("pods", "nodes"))
    runner = ScenarioRunner(store=new_store)
    before = _live(new_store)
    frozen = copy.deepcopy(before)

    batch: list = []
    if attempts == "drain":
        batch, outcome, old_atts = [Operation(step=0, op="delete", kind="nodes", name="n0")], _outcome(), []
    elif attempts is None:
        outcome = _outcome(binds=[("default", k, f"n{i}") for i, k in enumerate(written)])
        old_atts = [_att(k, node=node) for _ns, k, node in outcome.binds]
    else:
        outcome, old_atts = _outcome(attempts=attempts), attempts

    with old_store.transaction(epoch_exempt=True):
        if batch:
            old_store.delete("nodes", "n0")
            for k in written:
                _old_requeue(old_store, k, "default")
        for att in old_atts:
            _old_write(old_store, att)
    with new_store.transaction(epoch_exempt=True):
        runner._stage_device_step(batch, outcome, [])
        # The transaction's pre-image IS the replaced object.
        for k in written:
            assert new_store._txn.pre[("pods", f"default/{k}")] is before[k]

    assert runner._writes_shared == len(written) and runner._writes_copied == 0
    got, want = new_store.list("pods", copy_objs=False), old_store.list("pods", copy_objs=False)
    assert got == want
    # Key for key, in the same order: what an export or a checkpoint writes.
    assert json.dumps(got) == json.dumps(want)
    assert [p["metadata"]["resourceVersion"] for p in got] == [
        p["metadata"]["resourceVersion"] for p in want
    ]
    assert json.dumps(new_store.checkpoint()) == json.dumps(old_store.checkpoint())
    events = _drain(new_watch)
    assert events == _drain(old_watch) and len(events) >= len(written)
    # The replaced object is what it was (the watch history and a rollback
    # hold it), and the manifest's depth is shared with the new one.
    after = _live(new_store)
    for k in written:
        old, new = before[k], after[k]
        assert old == frozen[k] and new is not old
        assert new["spec"]["containers"] is old["spec"]["containers"]
        assert new["spec"]["affinity"] is old["spec"]["affinity"]
        assert new["metadata"] is not old["metadata"]
        assert new["metadata"]["labels"] is old["metadata"]["labels"]


class _Segment:
    """As much of a ``SegmentOutcome`` and its driver as a reconcile that
    does not reach its commit asks for."""

    _segment_seq = 1

    def __init__(self, steps):
        self.steps = steps
        self.faults = 0

    def note_reconcile_fault(self):
        self.faults += 1


def _two_step_segment():
    pods = [_rich_pod("a"), _nominated("b"), _rich_pod("c", node_name="n0", phase="Running")]
    steps = [
        _outcome(attempts=[_att("a", node="n1", anno=ANNO), _att("b", gave_up=True)]),
        _outcome(binds=[("default", "c", "n1")]),
    ]
    batches = [[Operation(step=0, op="delete", kind="nodes", name="n0")], []]
    return pods, batches, steps


def _dumped(store):
    """Every object as the bytes a checkpoint would write, by kind and key
    (a rollback re-inserts a key: the tables' own order is not state)."""
    return {kind: {key: json.dumps(obj) for key, obj in table.items()}
            for kind, table in store.checkpoint()["objects"].items()}


def _same_store(store, objects, dumped):
    """Byte for byte and object for object what it was (the rv counter
    alone is never rewound)."""
    assert _dumped(store) == dumped
    for kind, table in objects.items():
        live = store.list(kind, copy_objs=False)
        assert len(live) == len(table) and all(any(o is t for t in table) for o in live)
    assert {p["metadata"]["name"] for p in store.pods_with_node()} == {"c"}
    assert [p["metadata"]["name"] for p in store.pods_on_nodes({"n0"})] == ["c"]


def test_injected_fault_after_rewrapped_writes_restores_the_store():
    from ksim_tpu.faults import FaultPlane

    pods, batches, steps = _two_step_segment()
    store = _seeded_store(pods)
    objects = {k: store.list(k, copy_objs=False) for k in ("pods", "nodes")}
    dumped = _dumped(store)
    watch = store.watch(("pods", "nodes"))
    plane = FaultPlane()
    plane.arm("replay.reconcile", "call:2")  # step 0 staged, then the fault
    runner = ScenarioRunner(store=store, private_faults=plane)
    seg = _Segment(steps)
    assert runner._commit_segment([0, 1], batches, seg, seg, None) is False
    assert seg.faults == 1 and plane.fired("replay.reconcile") == 1
    assert runner._writes_shared == 3  # c requeued, a placed, b's nomination dropped
    _same_store(store, objects, dumped)
    assert _drain(watch) == []


def test_a_store_error_out_of_a_rewrap_rolls_back_and_propagates():
    """``tests/test_fault_injection.py``'s ``FlakyStore`` fails ``rewrap``
    for pods; the segment reconcile now binds through it."""
    from ksim_tpu.errors import SimulatorError
    from ksim_tpu.state.cluster import ClusterStore

    class FailsThirdRewrap(ClusterStore):
        calls = 0

        def rewrap(self, kind, name, namespace, build):
            self.calls += kind == "pods"
            if self.calls == 3:
                raise SimulatorError("injected bind failure")
            return super().rewrap(kind, name, namespace, build)

    pods, batches, steps = _two_step_segment()
    store = FailsThirdRewrap()
    for n in ("n0", "n1"):
        store.create("nodes", make_node(n))
    for pod in pods:
        store.create("pods", pod)
    objects = {k: store.list(k, copy_objs=False) for k in ("pods", "nodes")}
    dumped = _dumped(store)
    watch = store.watch(("pods", "nodes"))
    runner = ScenarioRunner(store=store)
    seg = _Segment(steps)
    with pytest.raises(SimulatorError, match="injected bind failure"):
        runner._commit_segment([0, 1], batches, seg, seg, None)
    assert seg.faults == 0  # not a chaos fault: nothing absorbed it
    _same_store(store, objects, dumped)
    assert _drain(watch) == []
