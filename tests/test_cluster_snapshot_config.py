"""A what-if against a populated cluster (the benchmark's deployment
``cluster-5k-150k``): a job whose ``spec.simulator.initialSnapshot`` holds a
running cluster — zone-labelled nodes, every pod Running on a node — and whose
scenario scales a zone-spread Deployment on top of it, under the default
scheduler configuration (``nodeSampling``) on the device path.

The document is the benchmark's own generator kind
(``benchmark/kinds/snapshot.py``) at the cell's rehearsal size (500 nodes,
15,000 bound pods, 500 arrivals: k = 230 of 500); the witness is the benchmark's
plain reference ``benchmark/references/snapshot_zoned.py``, which imports
nothing of the program.  Pinned here besides: the result's ``snapshot`` block,
and ``replay.featurize_bound_records`` + ``replay.featurize_bound_shared``
(together families x bound pods, once; builders run for the distinct manifests).
"""

from __future__ import annotations

import json
import os

import pytest

from ksim_tpu.jobs import JobJournal, JobManager
from ksim_tpu.jobs.journal import JOURNAL_NAME
from ksim_tpu.scenario import ScenarioRunner
from ksim_tpu.scenario.spec import operations_from_spec
from ksim_tpu.state.cluster import ClusterStore
from ksim_tpu.state.snapshot import SnapshotService

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark")
CELL = "cluster-5k-150k_rollout5k"
COUNT_KEYS = ("eventsApplied", "podsScheduled", "unschedulableAttempts")
SUM_KEYS = ("sampled_attempts", "nodes_visited", "nodes_scored", "sampling_zones")
#: The additive bound-pod families of a featurizer call (state/boundagg.py):
#: ``resvals``, ``requested``, ``ip_match``, ``ip_terms``, ``spread_init``.
FAMILIES = 5


@pytest.fixture(scope="module")
def bench():
    """The benchmark's modules, imported as the benchmark imports them."""
    mp = pytest.MonkeyPatch()
    mp.syspath_prepend(BENCH)
    import placements
    import run as harness
    from kinds import snapshot
    from references import snapshot_zoned

    yield {"harness": harness, "kind": snapshot, "reference": snapshot_zoned,
           "digest": placements.digest}
    mp.undo()


@pytest.fixture(scope="module")
def rehearsal(bench):
    """The cell at its rehearsal size: the job's document, the reference's
    answers and the control's (the cluster scheduled as if it were empty)."""
    harness = bench["harness"]
    c = harness.load_cell(harness.load("BENCHMARK.json"), CELL, True)
    inputs = harness.build_inputs(c["config"], c["traffic"], 0)
    replay = bench["reference"].replay
    return {"cell": c, "inputs": inputs, "want": replay(inputs["operations"]),
            "control": replay(inputs["operations"], charge_snapshot=False)}


def run_job(body: dict) -> dict:
    manager = JobManager(workers=1)
    try:
        job = manager.submit(body)
        assert job.wait_done(600)
        assert job.status()["state"] == "succeeded", job.status()
        return job.result
    finally:
        manager.shutdown()


@pytest.fixture(scope="module")
def served(rehearsal):
    """The rehearsal document through the job plane, once."""
    return run_job(json.loads(rehearsal["inputs"]["body"]))


def test_the_job_equals_the_plain_reference_started_from_the_snapshot(bench, rehearsal, served):
    want, inputs = rehearsal["want"], rehearsal["inputs"]
    assert rehearsal["cell"]["config"]["simulator"] == {
        "deviceReplay": True, "preemption": True, "nodeSampling": True, "podBucketMin": 128}
    # The snapshot's objects are no events and run no pass of their own.
    assert [served["result"][k] for k in COUNT_KEYS] == [want[k] for k in COUNT_KEYS] == [500, 500, 0]
    assert served["result"]["steps"] == inputs["steps"] == len(want["steps"]) == 1
    replay = served["replay"]
    assert replay["device_steps"] == 1 and replay["fallback_steps"] == 0
    assert replay["unsupported"] == {}
    for key in SUM_KEYS:
        assert replay[key] == want[key], key
    assert replay["placements_digest"] == bench["digest"](want["placements"])
    assert len(want["placements"]) == 15500
    # The configuration's file holds the same numbers.
    equals = rehearsal["cell"]["guarantees"]["replay_equals"]
    assert {k: replay[k] for k in equals} == equals
    # The walks pass full nodes and the zones the constraint rules out.
    assert want["nodes_visited"] > want["nodes_scored"] and want["sampling_zones"] == 3
    # The rehearsal's digest, as it was before the bound families went by
    # content; and the one cold lowering met every bound pod in every family,
    # running a builder for the distinct manifests only.
    digest = replay["placements_digest"]
    assert (digest[:8], digest[-4:]) == ("c0ec54b0", "3575")
    document = json.loads(inputs["body"])["spec"]["simulator"]["initialSnapshot"]
    assert replay["featurize_bound_records"] + replay["featurize_bound_shared"] == FAMILIES * 15000
    assert 0 < replay["featurize_bound_records"] <= FAMILIES * distinct_contents(document["pods"])
    assert replay["featurize_bound_shared"] >= FAMILIES * 15000 * 0.8


def test_a_scheduler_that_sees_an_empty_cluster_is_another_scheduler(bench, rehearsal, served):
    """The control: the snapshot's bound pods not charged to their nodes."""
    want, control = rehearsal["want"], rehearsal["control"]
    assert [control[k] for k in COUNT_KEYS] == [want[k] for k in COUNT_KEYS]
    assert control["nodes_visited"] != want["nodes_visited"] == served["replay"]["nodes_visited"]
    assert bench["digest"](control["placements"]) != served["replay"]["placements_digest"]
    assert set(control["placements"]) == set(want["placements"])


def test_the_result_says_what_the_job_started_from(rehearsal, served):
    block = served["snapshot"]
    assert set(block) == {"load_s", "batched_objects", "nodes", "pods", "bound_pods",
                          "pending_pods"}
    assert (block["nodes"], block["pods"], block["bound_pods"], block["pending_pods"]) == (
        500, 15000, 15000, 0)
    assert block["load_s"] > 0
    # Every object of the document went into the store through a kind's
    # batch (``ClusterStore.apply_many``): nodes + pods + the one namespace.
    document = json.loads(rehearsal["inputs"]["body"])["spec"]["simulator"]["initialSnapshot"]
    assert block["batched_objects"] == 15501 == sum(
        len(document[field]) for field in ("nodes", "pods", "namespaces"))
    # The load is a timed stage of the job's own plane, never a ring child.
    assert served["latency"]["jobs.run.snapshot"]["count"] == 1
    assert "jobs.run.snapshot" not in served["phases"]


def test_a_job_without_a_snapshot_says_nothing_of_one(bench):
    from generators import create_op, make_node, make_pod

    ops = [create_op(0, make_node("n0", "4", "16Gi", 110, labels={})),
           create_op(0, make_pod("p0", "100m", "100Mi", labels={}))]
    doc = run_job({"spec": {"simulator": {"deviceReplay": True},
                            "scenario": {"operations": ops}}})
    assert doc["result"]["podsScheduled"] == 1 and "snapshot" not in doc


def test_a_job_resumed_from_a_checkpoint_loads_nothing_again(bench, rehearsal, tmp_path):
    """Twenty steps of one pod (two windows) on the small snapshot, a
    checkpoint after the first window; the journal cut there and resumed: the
    restored store holds the snapshot's objects, so the block says ``restored``
    and neither ``load_s`` nor ``batched_objects``, and the job ends on the uninterrupted run's digest."""
    snapshot, scenario = small_cluster(bench, rehearsal)
    ops = [dict(op, step=i) for i, op in enumerate(scenario[:20])]
    body = {"spec": {"simulator": {"deviceReplay": True, "nodeSampling": True, "podBucketMin": 128,
                                   "initialSnapshot": snapshot},
                     "scenario": {"operations": ops}}}

    def finished(manager, job) -> dict:
        try:
            assert job.wait_done(600) and job.status()["state"] == "succeeded", job.status()
            return job.result
        finally:
            manager.shutdown()

    first = JobManager(workers=1, jobs_dir=str(tmp_path), checkpoint_every=1)
    whole = finished(first, first.submit(body))
    assert set(whole["snapshot"]) == {"load_s", "batched_objects", "nodes", "pods", "bound_pods",
                                      "pending_pods"}
    assert whole["snapshot"]["batched_objects"] == 120 + 1200 + 1
    path = os.path.join(str(tmp_path), JOURNAL_NAME)
    records = JobJournal(path).replay()
    cut = next(i for i, r in enumerate(records) if r["t"] == "checkpoint")
    os.unlink(path)
    journal = JobJournal(path)
    for record in records[: cut + 1]:
        journal.append(record)
    second = JobManager(workers=1, jobs_dir=str(tmp_path), resume=True, checkpoint_every=0)
    resumed = finished(second, second.get(records[0]["id"]))
    assert resumed["resume"]["cursor"] == records[cut]["cursor"] == 16
    assert resumed["snapshot"] == {"nodes": 120, "pods": 1200, "bound_pods": 1200,
                                   "pending_pods": 0, "restored": True}
    assert resumed["replay"]["placements_digest"] == whole["replay"]["placements_digest"]
    assert resumed["result"]["podsScheduled"] == whole["result"]["podsScheduled"] == 20


def distinct_contents(pods: list) -> int:
    """Manifests that differ once who the pod is (name, uid), where it runs
    (``spec.nodeName``) and its ``status`` are taken out: derived here from the
    generator's pods, not from the program's key."""
    seen = set()
    for pod in pods:
        meta = {k: v for k, v in pod["metadata"].items() if k not in ("name", "uid")}
        spec = {k: v for k, v in pod["spec"].items() if k != "nodeName"}
        rest = {k: v for k, v in pod.items() if k not in ("metadata", "spec", "status")}
        seen.add(json.dumps([meta, spec, rest], sort_keys=True))
    return len(seen)


def small_cluster(bench, rehearsal) -> "tuple[dict, list]":
    """120 nodes x 8-12 pods (1,200 bound, 90 of them earlier replicas) and a
    rollout of 40, from the kind's own code."""
    gen = dict(rehearsal["cell"]["config"]["generator"])
    gen["workloads"] = {"small": {"nodes": 120, "podsPerNode": {"mean": 10, "spread": 2},
                                  "replicas": [40, 30, 20], "rollout": 40}}
    nodes, pods, scenario = bench["kind"].cluster(gen, "small")
    return bench["kind"].snapshot_document(nodes, pods), scenario


def test_bound_records_are_families_times_bound_pods_once(bench, rehearsal):
    """Windows of one step: the first lowering meets every bound pod once a
    family; the second meets the 40 pods the first window bound; the third and
    fourth meet nothing new (their pods fit nowhere).  A record either RAN its
    family's builder (``bound_records``: at most once a distinct manifest and
    family) or took its content's contribution (``bound_shared``)."""
    snapshot, scenario = small_cluster(bench, rehearsal)
    huge = lambda step, name: {"step": step, "createOperation": {"object": {
        "apiVersion": "v1", "kind": "Pod", "metadata": {"name": name, "namespace": "default"},
        "spec": {"containers": [{"name": "c", "resources": {"requests": {"cpu": "64"}}}]}}}}
    ops = scenario + [huge(1, "huge-1"), huge(2, "huge-2"), huge(3, "huge-3")]
    store = ClusterStore()
    SnapshotService(store).load(snapshot)
    runner = ScenarioRunner(store=store, preemption=True, node_sampling=True, pod_bucket_min=128,
                            device_replay=True, device_segment_steps=1)
    result = runner.run(iter(operations_from_spec({"operations": ops})))
    driver = runner.replay_driver
    assert driver.fallback_steps == 0 and not driver.unsupported, driver.unsupported
    assert (result.pods_scheduled, len(result.steps)) == (40, 4)
    built = [entry["bound_records"] for entry in driver.lower_log]
    shared = [entry["bound_shared"] for entry in driver.lower_log]
    assert [b + s for b, s in zip(built, shared)] == [FAMILIES * 1200, FAMILIES * 40, 0, 0]
    stats = driver.stats()
    assert stats["featurize_bound_records"] == sum(built)
    assert stats["featurize_bound_shared"] == sum(shared)
    assert sum(built) + sum(shared) == FAMILIES * 1240
    # The 40 arrivals are replicas of the scaled Deployment, whose earlier
    # replicas the snapshot holds: the second window runs no builder at all.
    contents = distinct_contents(
        snapshot["pods"] + [op["createOperation"]["object"] for op in scenario])
    assert contents < 1200 and 0 < built[0] <= FAMILIES * contents
    assert built[1:] == [0, 0, 0] and shared[1] == FAMILIES * 40
