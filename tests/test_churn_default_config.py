"""The churn stream under the default scheduler configuration (the
benchmark's deployment ``churn-2k-default``): every scheduling attempt walks
the node tree's zone-interleaved list for a sample while nodes leave and
join in every step, so on the segment path every attempt goes by the step's
walk tensor (``_SegmentStatics.sample`` 2).

The stream is the benchmark's own ``churn`` generator
(``benchmark/kinds/churn.py``) at 200 nodes x 1,600 events and at 300 nodes x
3,000 events; nothing is lowered to make it sample (upstream's floor of 100
nodes stands: k = 100 of 200, 144 of 300), and ``maxPodsPerPass`` is set.  The
witness is the benchmark's plain reference
``benchmark/references/sampled_zoned.py``, which imports nothing of the
program.
"""

from __future__ import annotations

import os

import pytest

from ksim_tpu.scenario import ScenarioRunner
from ksim_tpu.scenario.spec import operations_from_spec
from ksim_tpu.scheduler.service import SchedulerService
from ksim_tpu.state.cluster import ClusterStore

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark")
SCHED = "default-scheduler"
COUNT_KEYS = ("eventsApplied", "podsScheduled", "unschedulableAttempts")
SUM_KEYS = ("sampled_attempts", "nodes_visited", "nodes_scored", "sampling_start", "sampling_zones")
K = 16  # steps a window: the driver's default

# nodes, events, maxPodsPerPass (the second cap binds: a step brings ~65 pods), k
SIZES = [(200, 1600, 1024, 100), (300, 3000, 48, 144)]
IDS = [f"{n}nodes-{e}events-cap{c}" for n, e, c, _ in SIZES]


@pytest.fixture(scope="module")
def bench():
    """The benchmark's modules, imported as the benchmark imports them."""
    mp = pytest.MonkeyPatch()
    mp.syspath_prepend(BENCH)
    import placements
    from kinds import churn
    from references import sampled, sampled_zoned

    yield {"churn": churn, "sampled": sampled, "zoned": sampled_zoned, "digest": placements.digest}
    mp.undo()


@pytest.fixture(scope="module")
def streams(bench):
    """Per size: the operations and the reference's answers (and the
    control's: the same reference walking in name order)."""
    out = {}
    for n_nodes, n_events, cap, _ in SIZES:
        ops = bench["churn"].churn_operations(0, n_nodes=n_nodes, n_events=n_events,
                                              ops_per_step=100)
        out[n_nodes] = {
            "ops": ops,
            "want": bench["zoned"].replay(ops, max_pods_per_pass=cap),
            "control": bench["zoned"].replay(ops, max_pods_per_pass=cap, interleave=False),
        }
    return out


def runner_for(store, cap: int, *, device: bool, **kw) -> ScenarioRunner:
    # Priority-flat: on the per-pass path ``preemption`` would only walk every
    # node x every pod for each unschedulable pod, to find no victim.
    return ScenarioRunner(store=store, preemption=device, node_sampling=True,
                          max_pods_per_pass=cap, pod_bucket_min=128, device_replay=device, **kw)


def placements_of(store) -> dict:
    return {p["metadata"]["name"]: p.get("spec", {}).get("nodeName") for p in store.list("pods")}


def node_event_steps(ops: list) -> list:
    return sorted({op["step"] for op in ops
                   if op.get("deleteOperation", {}).get("typeMeta", {}).get("kind") == "Node"
                   or op.get("createOperation", {}).get("object", {}).get("kind") == "Node"})


@pytest.mark.parametrize("n_nodes, n_events, cap, k", SIZES, ids=IDS)
def test_the_stream_samples_with_no_floor_lowered_and_the_control_differs(
        bench, streams, n_nodes, n_events, cap, k):
    s = streams[n_nodes]
    assert bench["sampled"].num_feasible_nodes_to_find(n_nodes, 0) == k < n_nodes
    assert SchedulerService._MIN_FEASIBLE_NODES_TO_FIND == bench["sampled"].MIN_FEASIBLE_NODES_TO_FIND == 100
    want, control = s["want"], s["control"]
    # Every attempt sampled, walks passed infeasible nodes, every step after
    # the bootstrap replaced nodes, and the zones are the generator's three.
    assert want["sampled_attempts"] == want["podsScheduled"] + want["unschedulableAttempts"] > 0
    assert want["nodes_visited"] > want["nodes_scored"] and want["sampling_zones"] == 3
    steps = sorted({op["step"] for op in s["ops"]})
    assert node_event_steps(s["ops"]) == steps
    # The walk in name order is another scheduler.
    assert control["placements"] != want["placements"]
    assert control["nodes_visited"] != want["nodes_visited"]
    assert [control[key] for key in COUNT_KEYS] != [want[key] for key in COUNT_KEYS]


@pytest.mark.parametrize("n_nodes, n_events, cap, k", SIZES, ids=IDS)
def test_the_segment_path_equals_the_plain_reference_all_by_the_walk_tensor(
        streams, n_nodes, n_events, cap, k):
    ops, want = streams[n_nodes]["ops"], streams[n_nodes]["want"]
    store = ClusterStore()
    runner = runner_for(store, cap, device=True)
    result = runner.run(iter(operations_from_spec({"operations": ops})))
    driver = runner.replay_driver
    assert driver.fallback_steps == 0 and not driver.unsupported, driver.unsupported
    assert [result.events_applied, result.pods_scheduled, result.unschedulable_attempts] == [
        want[key] for key in COUNT_KEYS]
    assert placements_of(store) == want["placements"]
    stats = driver.stats()
    for key in SUM_KEYS:
        assert stats[key] == want[key], key
    # Node churn in every step: no attempt went in slot order.
    assert stats["sampled_by_rank"] == stats["sampled_attempts"]
    # What the stream implies: a step that attempted a pod walked by its
    # walk tensor; the lowering computed one walk row for every step with a
    # node event (here: all of them; a window whose first step had none
    # would add one).
    attempted = [k_ for k_, (placed, failed) in enumerate(want["steps"]) if placed + failed]
    n_steps = len(want["steps"])
    assert stats["sampled_by_rank_steps"] == len(attempted) == n_steps - 1
    assert stats["walk_rows_built"] == len(node_event_steps(ops)) == n_steps
    # Per segment in lower_log, summing to the job's.
    log = driver.lower_log
    assert len(log) == -(-n_steps // K)
    for key in ("sampled_by_rank_steps", "walk_rows_built", "sampled_by_rank"):
        assert sum(e[key] for e in log) == stats[key], key
    assert [e["sampled_by_rank_steps"] for e in log] == [
        sum(1 for k_ in attempted if i * K <= k_ < (i + 1) * K) for i in range(len(log))]
    assert driver._last_plan.statics.sample == 2


@pytest.mark.parametrize("n_nodes, n_events, cap, k", SIZES, ids=IDS)
def test_the_per_pass_path_equals_the_plain_reference(streams, n_nodes, n_events, cap, k):
    """The per-pass path keeps no count of visited nodes: the three counts,
    the placements, the start index it leaves and the tree's zones."""
    ops, want = streams[n_nodes]["ops"], streams[n_nodes]["want"]
    store = ClusterStore()
    runner = runner_for(store, cap, device=False)
    result = runner.run(iter(operations_from_spec({"operations": ops})))
    assert [result.events_applied, result.pods_scheduled, result.unschedulable_attempts] == [
        want[key] for key in COUNT_KEYS]
    assert placements_of(store) == want["placements"]
    assert runner.service._pnts_start[SCHED] == want["sampling_start"]
    assert len(runner.service._node_tree.zones) == want["sampling_zones"]


def test_a_resume_from_a_mid_stream_checkpoint_ends_on_the_same_digest(bench, streams):
    """A fresh store and service restored from the checkpoint taken after the
    first window (16 steps, 15 of them with node replacements: the carried
    tree lists replacement nodes last in their zones) finish the stream where
    the uninterrupted run does; the resumed driver's counts are its own
    windows'."""
    n_nodes, _, cap, _ = SIZES[1]
    ops, want = streams[n_nodes]["ops"], streams[n_nodes]["want"]
    digest = bench["digest"]  # ``replay.placements_digest`` (benchmark/placements.py)
    taken = []

    def hook(cursor, driver, result):
        taken.append((cursor, driver.store.checkpoint(), driver.service.checkpoint_carries()))

    first_store = ClusterStore()
    first = runner_for(first_store, cap, device=True, checkpoint_hook=hook)
    first.run(iter(operations_from_spec({"operations": ops})))
    assert digest(placements_of(first_store)) == digest(want["placements"])
    cursor, snapshot, carries = next(t for t in taken if t[0] == K)
    replaced = {f"node-{i}" for i in range(n_nodes, n_nodes + 400)}
    assert any(names[-1] in replaced for _, names in carries["node_tree"])
    store = ClusterStore.from_checkpoint(snapshot)
    resumed = runner_for(store, cap, device=True)
    resumed.service.restore_carries(carries)
    resumed.run(iter(operations_from_spec({"operations": ops})), resume_cursor=cursor)
    driver = resumed.replay_driver
    assert driver.fallback_steps == 0 and not driver.unsupported
    assert digest(placements_of(store)) == digest(want["placements"])
    assert resumed.service._pnts_start[SCHED] == want["sampling_start"]
    assert resumed.service._node_tree.list() == first.service._node_tree.list()
    stats, whole = driver.stats(), first.replay_driver.stats()
    assert stats["sampled_by_rank"] == stats["sampled_attempts"] > 0
    first_window = first.replay_driver.lower_log[0]
    for key in ("sampled_by_rank_steps", "walk_rows_built", "sampled_attempts"):
        assert stats[key] == whole[key] - first_window[key], key
