"""The sampling walk in the order of the node tree (docs/jobs.md; upstream's
zone-interleaved node list), on the per-pass path and on the segment path,
against the benchmark's plain reference
``benchmark/references/sampled_zoned.py`` (a sequential scheduler written from
upstream's definitions that imports nothing of the program).

Seeded clusters of 36 to 200 nodes in 2 to 4 uneven zones; pods with and
without a zone-keyed DoNotSchedule spread constraint; small nodes that fill
up, so that walks pass infeasible nodes; ``numFeasibleNodesToFind`` forced
below the node count (upstream's floor of 100 nodes lowered to 10 on both
sides, ``percentageOfNodesToScore`` 30).  Static streams, and streams that
replace nodes mid-stream (the segment path then walks by the walk tensor,
``_SegmentStatics.sample`` 2).  Unlabelled nodes give what PR 34's reference
(``references/sampled.py``, the walk in name order) gives.
"""

from __future__ import annotations

import os
import random

import pytest

from ksim_tpu.scenario import ScenarioRunner
from ksim_tpu.scenario.spec import operations_from_spec
from ksim_tpu.scheduler.nodetree import NodeTree
from ksim_tpu.scheduler.service import SchedulerService
from ksim_tpu.state.cluster import ClusterStore

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark")
SCHED = "default-scheduler"
ZONE_KEY = "topology.kubernetes.io/zone"
FLOOR, PERCENT = 10, 30


@pytest.fixture()
def bench(monkeypatch):
    """The benchmark's modules, and upstream's floor of 100 nodes lowered on
    both sides so that small clusters sample."""
    monkeypatch.syspath_prepend(BENCH)
    import generators
    from references import sampled, sampled_zoned

    monkeypatch.setattr(sampled, "MIN_FEASIBLE_NODES_TO_FIND", FLOOR)
    monkeypatch.setattr(SchedulerService, "_MIN_FEASIBLE_NODES_TO_FIND", FLOOR)
    return {"generators": generators, "sampled": sampled, "zoned": sampled_zoned}


def stream(gen, seed: int, n_nodes: int, zones: "list[int] | None", *, replace: bool) -> list:
    """KEP-140 operations: ``n_nodes`` nodes in step 0 (``zones``: the zones'
    shares, dealt by a seeded shuffle; None: no labels), then six steps of
    pods, a third of them zone-spread; with ``replace`` the last two steps also take a
    node away and bring a new one, in another zone."""
    rng = random.Random(seed)
    names = [f"z{i}" for i in range(len(zones or ()))]
    deal = [z for z, share in zip(names, zones or ()) for _ in range(share)]
    rng.shuffle(deal)

    def labels(i: int) -> dict:
        return {ZONE_KEY: deal[i % len(deal)]} if zones else {}

    def node(i: int, zone_labels: dict):
        small = rng.random() < 0.3
        return gen.make_node(f"node-{i}", "1" if small else "8", "4Gi" if small else "32Gi", 110,
                             labels=zone_labels)

    ops = [gen.create_op(0, node(i, labels(i))) for i in range(n_nodes)]
    spread = [{"maxSkew": 1, "topologyKey": ZONE_KEY, "whenUnsatisfiable": "DoNotSchedule",
               "labelSelector": {"matchLabels": {"color": "blue"}}}]
    made = 0
    for step in range(1, 7):
        if replace and step in (5, 6):
            gone = rng.randrange(n_nodes)
            ops.append(gen.delete_op(step, "Node", f"node-{gone}", ""))
            new = n_nodes + step
            ops.append(gen.create_op(step, node(new, labels(new + 1))))
        for _ in range(n_nodes // 3):
            blue = bool(zones) and rng.random() < 0.35
            ops.append(gen.create_op(step, gen.make_pod(
                f"pod-{made:04d}", "500m", "1Gi", labels={"color": "blue"} if blue else {},
                topology_spread_constraints=spread if blue else None)))
            made += 1
    return ops


def run(ops: list, *, device: bool):
    store = ClusterStore()
    service = SchedulerService(store, record="selection", preemption=False,
                               node_sampling=True, pod_bucket_min=32)
    service._config = dict(service._config or {}, percentageOfNodesToScore=PERCENT)
    runner = ScenarioRunner(store=store, service=service, device_replay=device,
                            device_segment_steps=4)
    result = runner.run(iter(operations_from_spec({"operations": ops})))
    placements = {p["metadata"]["name"]: p.get("spec", {}).get("nodeName")
                  for p in store.list("pods")}
    return runner, result, placements


def counts(result) -> list:
    return [result.events_applied, result.pods_scheduled, result.unschedulable_attempts]


CASES = [
    (11, 36, [3, 1]),
    (12, 90, [5, 3, 1]),
    (13, 130, [4, 3, 2, 1]),
    (14, 200, [2, 2, 1]),
]
IDS = [f"{n}nodes-{len(z)}zones" for _, n, z in CASES]


@pytest.mark.parametrize("replace", [False, True], ids=["static", "node-replace"])
@pytest.mark.parametrize("seed, n_nodes, zones", CASES, ids=IDS)
def test_the_segment_path_equals_the_plain_reference(bench, seed, n_nodes, zones, replace):
    ops = stream(bench["generators"], seed, n_nodes, zones, replace=replace)
    want = bench["zoned"].replay(ops, percentage=PERCENT)
    control = bench["zoned"].replay(ops, percentage=PERCENT, interleave=False)
    runner, result, placements = run(ops, device=True)
    driver = runner.replay_driver
    assert driver.fallback_steps == 0, driver.unsupported
    stats = driver.stats()
    assert counts(result) == [want[k] for k in ("eventsApplied", "podsScheduled",
                                                "unschedulableAttempts")]
    assert placements == want["placements"]
    for key in ("sampled_attempts", "nodes_visited", "nodes_scored", "sampling_start",
                "sampling_zones"):
        assert stats[key] == want[key], key
    assert stats["nodes_skipped"] == want["nodes_visited"] - want["nodes_scored"] > 0
    # The walk in name order is another scheduler: the control is not met.
    assert control["placements"] != want["placements"]
    assert control["nodes_visited"] != want["nodes_visited"]
    # A stream that loses no node walks in slot order; node churn sends the
    # later windows through the walk tensor.
    routes = [e["sampled_by_rank"] > 0 for e in driver.lower_log if e["sampled_attempts"]]
    assert routes == ([False, True] if replace else [False, False])
    assert stats["sampled_by_rank"] == sum(
        e["sampled_attempts"] for e in driver.lower_log if e["sampled_by_rank"])
    assert runner.service._node_tree.list() == tree_list(bench, ops)


def tree_list(bench, ops) -> list:
    """The reference's own tree over the stream's node events."""
    tree = bench["zoned"].NodeTree()
    by_step: dict = {}
    for op in ops:
        by_step.setdefault(op["step"], []).append(op)
    for step in sorted(by_step):
        gone = [op["deleteOperation"]["objectMeta"]["name"] for op in by_step[step]
                if "deleteOperation" in op]
        new = [op["createOperation"]["object"] for op in by_step[step]
               if "createOperation" in op and op["createOperation"]["object"]["kind"] == "Node"]
        for name in gone:
            tree.remove(name)
        for obj in sorted(new, key=lambda o: o["metadata"]["name"]):
            tree.add(obj["metadata"]["name"], bench["zoned"].get_zone_key(obj))
    return tree.list()


@pytest.mark.parametrize("replace", [False, True], ids=["static", "node-replace"])
@pytest.mark.parametrize("seed, n_nodes, zones", CASES[:3], ids=IDS[:3])
def test_the_per_pass_path_equals_the_plain_reference(bench, seed, n_nodes, zones, replace):
    """The per-pass path keeps no count of visited nodes: placements, the
    three counts and the start index it leaves."""
    ops = stream(bench["generators"], seed, n_nodes, zones, replace=replace)
    want = bench["zoned"].replay(ops, percentage=PERCENT)
    runner, result, placements = run(ops, device=False)
    assert counts(result) == [want[k] for k in ("eventsApplied", "podsScheduled",
                                                "unschedulableAttempts")]
    assert placements == want["placements"]
    assert runner.service._pnts_start[SCHED] == want["sampling_start"]
    assert len(runner.service._node_tree.zones) == want["sampling_zones"] == len(zones)
    assert runner.service._node_tree.list() == tree_list(bench, ops)


@pytest.mark.parametrize("device", [False, True], ids=["per-pass", "segment"])
@pytest.mark.parametrize("seed, n_nodes", [(21, 48), (22, 150)])
def test_unlabelled_nodes_walk_in_the_order_they_always_did(bench, seed, n_nodes, device):
    """No zone label: one zone, whose list is the order the nodes joined in.
    A stream that loses no node gets exactly what PR 34's reference (the walk
    in name order) gives, which is also the zoned reference's control."""
    ops = stream(bench["generators"], seed, n_nodes, None, replace=False)
    want = bench["sampled"].replay(ops, percentage=PERCENT)
    zoned = bench["zoned"].replay(ops, percentage=PERCENT)
    for key in ("placements", "nodes_visited", "nodes_scored", "sampling_start"):
        assert zoned[key] == want[key], key
    assert zoned["sampling_zones"] == 1
    runner, result, placements = run(ops, device=device)
    assert placements == want["placements"]
    assert runner.service._pnts_start[SCHED] == want["sampling_start"]
    assert runner.service._node_tree.zones == [""]
    if device:
        stats = runner.replay_driver.stats()
        assert runner.replay_driver.fallback_steps == 0
        assert (stats["nodes_visited"], stats["nodes_scored"]) == (
            want["nodes_visited"], want["nodes_scored"])
        assert stats["sampled_by_rank"] == 0 and stats["sampling_zones"] == 1


def test_a_service_that_does_not_sample_keeps_no_tree(bench):
    """The tree orders the walk and nothing else: zone-labelled nodes under
    a service without ``node_sampling`` leave it empty, and the result says
    0 zones."""
    ops = stream(bench["generators"], 31, 40, [2, 1], replace=False)
    store = ClusterStore()
    runner = ScenarioRunner(store=store, record="selection", pod_bucket_min=32,
                            device_replay=True, device_segment_steps=4)
    runner.run(iter(operations_from_spec({"operations": ops})))
    assert len(runner.service._node_tree) == 0
    stats = runner.replay_driver.stats()
    assert stats["sampling_zones"] == 0 and stats["sampled_attempts"] == 0
    assert runner.replay_driver._last_plan.statics.sample == 0


def test_the_tree_survives_a_checkpoint_resume(bench):
    """The carry a job checkpoint records (``checkpoint_carries``) holds the
    tree beside the start index: a fresh store and service restored after the
    first committed segment finish a zone-labelled stream with node
    replacement exactly as the uninterrupted run does.  A tree rebuilt from
    the store alone would list the nodes of the later steps by name, not in
    the order they joined."""
    ops = stream(bench["generators"], 41, 110, [3, 2, 1], replace=True)
    want = bench["zoned"].replay(ops, percentage=PERCENT)
    taken = []

    def hook(cursor, driver, result):
        taken.append((cursor, driver.store.checkpoint(), driver.service.checkpoint_carries()))

    def start(store):
        service = SchedulerService(store, record="selection", preemption=False,
                                   node_sampling=True, pod_bucket_min=32)
        service._config = dict(service._config or {}, percentageOfNodesToScore=PERCENT)
        return service

    store = ClusterStore()
    first = ScenarioRunner(store=store, service=start(store), device_replay=True,
                           device_segment_steps=2, checkpoint_hook=hook)
    first.run(iter(operations_from_spec({"operations": ops})))
    # After steps 0-5: one replacement is in the carried tree (its node is
    # the last of its zone, not where its name would put it), one is ahead.
    cursor, snapshot, carries = next(t for t in taken if t[0] == 6)
    assert any(names[-1] == "node-115" for _, names in carries["node_tree"])
    store = ClusterStore.from_checkpoint(snapshot)
    service = start(store)
    service.restore_carries(carries)
    assert service._node_tree.to_carry() == carries["node_tree"]
    rebuilt = NodeTree()
    rebuilt.sync(store.list("nodes"))
    assert rebuilt.list() != service._node_tree.list()
    resumed = ScenarioRunner(store=store, service=service, device_replay=True,
                             device_segment_steps=2)
    resumed.run(iter(operations_from_spec({"operations": ops})), resume_cursor=cursor)
    assert resumed.replay_driver.fallback_steps == 0
    placements = {p["metadata"]["name"]: p.get("spec", {}).get("nodeName")
                  for p in store.list("pods")}
    assert placements == want["placements"]
    assert service._pnts_start[SCHED] == want["sampling_start"]
    assert service._node_tree.list() == first.service._node_tree.list() == tree_list(bench, ops)
