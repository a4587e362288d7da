"""Sampled scoring (``percentageOfNodesToScore``; the service's
``node_sampling``, a job's ``spec.simulator.nodeSampling``) on the device
replay path (engine/replay.py ``_SegmentStatics.sample``) beside the
per-pass path.

The contract is the sequential walk (docs/jobs.md): nodes in the
simulator's node order from a start index; stop when k feasible ones are
found or every node was seen; visited = every node seen, sample = the
feasible among them; score, normalise and choose over the sample only;
``start <- (start + visited) mod nodes``; a pod that takes its nominated
node does not move ``start``; one ``start`` for the life of the service.
With 120 nodes upstream's adaptive formula gives k = 100
(``50 - 120/125`` = 50 % = 60 nodes, raised to the floor of 100).

Pinned here, every expectation derived by hand from that contract:

- the walk's fixtures on both paths (infeasible nodes inside it, fewer
  than k feasible, the wrap past the last node, the nominated node);
- the segment path against the per-pass path over a churn stream with
  node replacement, step by step, float32 and x64;
- ``start`` through a device segment -> per-pass step -> device segment
  hand-over, and through a checkpoint resume;
- the served job against the benchmark's plain reference on upstream's
  ``SchedulingBasic`` / ``500Nodes``;
- the lowered text of the programs that do NOT sample (flat, flat
  full-record, preempt): what every accepted benchmark cell runs.

Each case fails where the walk is replaced by scoring every node.
"""

from __future__ import annotations

import hashlib
import json
import os
import sys

import jax
import pytest

from ksim_tpu.engine import replay
from ksim_tpu.engine.annotations import FILTER_RESULT_KEY, SCORE_RESULT_KEY
from ksim_tpu.scenario import ScenarioRunner, churn_scenario
from ksim_tpu.scenario.runner import Operation
from ksim_tpu.scheduler.service import SchedulerService
from ksim_tpu.state.cluster import ClusterStore
from tests.helpers import make_node, make_pod

PATHS = [False, True]
PATH_IDS = ["per-pass", "segment"]
SCHED = "default-scheduler"
N = 120  # k = 100


def node(i: int, *, big: bool = False, **kw) -> dict:
    """4 cpu / 16Gi; ``big``: 16 cpu / 64Gi, the best score on the cluster
    for a 1-cpu / 4Gi pod even with two such pods on it (LeastAllocated 93,
    87, 81 against an empty small node's 75; the fractions stay balanced)."""
    return make_node(f"n{i:03d}", cpu="16" if big else "4", memory="64Gi" if big else "16Gi", **kw)


def pod(name: str, **kw) -> dict:
    return make_pod(name, cpu="1", memory="4Gi", **kw)


def create(step: int, kind: str, obj: dict) -> Operation:
    return Operation(step=step, op="create", kind=kind, obj=obj)


def names(indices) -> list:
    return [f"n{i:03d}" for i in indices]


def run(ops, *, device: bool, record: str = "full", x64: bool = False, store=None,
        start: int = 0, preemption: bool = False, **kw):
    prev = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", x64)
    try:
        store = store if store is not None else ClusterStore()
        service = SchedulerService(
            store, record=record, preemption=preemption, node_sampling=True,
            pod_bucket_min=32,
        )
        service._pnts_start[SCHED] = start
        runner = ScenarioRunner(
            store=store, service=service, device_replay=device,
            device_segment_steps=4, **kw,
        )
        result = runner.run(iter(ops))
    finally:
        jax.config.update("jax_enable_x64", prev)
    driver = runner.replay_driver
    if device:
        assert driver.fallback_steps == 0, driver.unsupported
    return runner, result


def recorded(runner, pod: str) -> "tuple[list, list, str | None]":
    """(nodes in the pod's filter-result, nodes in its score-result, its node)."""
    obj = runner.store.get("pods", pod, "default")
    anno = obj["metadata"]["annotations"]
    return (sorted(json.loads(anno[FILTER_RESULT_KEY])),
            sorted(json.loads(anno[SCORE_RESULT_KEY])), obj["spec"].get("nodeName"))


@pytest.mark.parametrize("device", PATHS, ids=PATH_IDS)
def test_the_walk_skips_infeasible_nodes_wraps_and_hands_the_start_on(device):
    """120 nodes, n005 and n010 cordoned, n110 four times as large (the
    best score on the cluster).  k = 100.

    - p0 from start 0: the 100th feasible node is n101 (two of n000..n101
      are cordoned), 102 nodes visited, 100 scored, start 102.  n110 is
      not in the sample: p0 goes to the first of the equal nodes, n000.
      A scheduler that scores every node puts it on n110.
    - p1 (same pass) from 102: n102..n119 give 18, n000..n083 give the
      other 82 (84 nodes less the two cordoned): 102 visited, the walk
      wraps, start (102 + 102) mod 120 = 84.  n110 is in the sample: p1
      takes it.
    - p2 (next pass) from 84: n084..n119 give 36, n000..n065 give 64:
      102 visited, start (84 + 102) mod 120 = 66.  n110, still the
      emptiest with p1 on it, is in the sample: p2 takes it too."""
    ops = [create(0, "nodes", node(i, unschedulable=i in (5, 10), big=i == 110))
           for i in range(N)]
    ops += [create(1, "pods", pod("p0")), create(1, "pods", pod("p1")),
            create(2, "pods", pod("p2"))]
    runner, result = run(ops, device=device)
    assert [(s.scheduled, s.unschedulable) for s in result.steps] == [(0, 0), (2, 0), (1, 0)]
    cordoned = {"n005", "n010"}
    want = {
        "p0": (names(range(102)), "n000"),
        "p1": (names(list(range(102, 120)) + list(range(84))), "n110"),
        "p2": (names(list(range(84, 120)) + list(range(66))), "n110"),
    }
    for name, (visited, where) in want.items():
        filt, scored, bound = recorded(runner, name)
        assert filt == sorted(visited), name
        assert scored == sorted(set(visited) - cordoned) and len(scored) == 100, name
        assert bound == where, name
    assert runner.service._pnts_start[SCHED] == 66
    if device:
        stats = runner.replay_driver.stats()
        assert (stats["sampled_attempts"], stats["nodes_visited"], stats["nodes_scored"]) == (3, 306, 300)
        assert stats["sampling_start"] == 66
        assert [e["sampling_start"] for e in runner.replay_driver.lower_log] == [66]
        assert sum(e["nodes_visited"] for e in runner.replay_driver.lower_log) == 306


@pytest.mark.parametrize("device", PATHS, ids=PATH_IDS)
def test_fewer_feasible_nodes_than_k_visits_every_node_and_the_start_stays(device):
    """n000..n029 cordoned: 90 feasible nodes, fewer than k = 100.  From
    start 7 the walk sees all 120, scores the 90, and the index comes
    back to (7 + 120) mod 120 = 7."""
    ops = [create(0, "nodes", node(i, unschedulable=i < 30)) for i in range(N)]
    ops.append(create(1, "pods", make_pod("p0")))
    runner, _ = run(ops, device=device, start=7)
    filt, scored, bound = recorded(runner, "p0")
    assert filt == names(range(N)) and scored == names(range(30, N))
    assert bound == "n030" and runner.service._pnts_start[SCHED] == 7
    if device:
        stats = runner.replay_driver.stats()
        assert (stats["sampled_attempts"], stats["nodes_visited"], stats["nodes_scored"]) == (1, 120, 90)


@pytest.mark.parametrize("device", PATHS, ids=PATH_IDS)
def test_a_pod_that_takes_its_nominated_node_walks_nowhere(device):
    """``pn`` (priority 10) waits in the store nominated to n050, which
    fits it: evaluateNominatedNode takes that node alone, before any
    walk, and the index stays at 0.  ``q`` (priority 0) comes behind it
    in the same pass and walks n000..n099 from 0: start 100.  Had ``pn``
    walked, ``q`` would have started at 100 and left 80."""
    store = ClusterStore()
    for i in range(N):
        store.create("nodes", node(i))
    pn = make_pod("pn", priority=10)
    pn["status"] = {"nominatedNodeName": "n050"}
    store.create("pods", pn)
    ops = [create(1, "pods", make_pod("q", priority=0))]
    runner, result = run(ops, device=device, store=store, preemption=True)
    assert [(s.scheduled, s.unschedulable) for s in result.steps] == [(2, 0)]
    assert runner.store.get("pods", "pn", "default")["spec"]["nodeName"] == "n050"
    filt, scored, bound = recorded(runner, "q")
    assert filt == scored == names(range(100)) and bound == "n000"
    assert runner.service._pnts_start[SCHED] == 100
    if device:
        plan, stats = runner.replay_driver._last_plan, runner.replay_driver.stats()
        assert plan.statics.preempt and plan.statics.sample
        assert (stats["sampled_attempts"], stats["nodes_visited"]) == (1, 100)


# -- a churn stream with node replacement --------------------------------------


def churn_ops():
    return list(churn_scenario(3, n_nodes=130, n_events=130 + 600, ops_per_step=60))


def outcome(runner, result) -> dict:
    return {
        "steps": [(s.step, s.scheduled, s.unschedulable, s.pending_after) for s in result.steps],
        "pods": sorted((p["metadata"]["name"], p.get("spec", {}).get("nodeName"))
                       for p in runner.store.list("pods")),
        "start": runner.service._pnts_start.get(SCHED, 0),
    }


@pytest.mark.parametrize("x64", [False, True], ids=["f32-fast", "exact-x64"])
def test_the_segment_path_equals_the_per_pass_path_under_node_replacement(x64):
    """130 nodes, 600 events, a node replaced in about one event of ten:
    the device's node table and the service's node order drift apart, so
    the walk goes by the rank tensor (``sample`` 2).  Step by step the
    same counts, at the end every pod on the same node and the same
    start index; and not the placements of a scheduler that scores every
    node."""
    ops = churn_ops()
    host, host_result = run(ops, device=False, record="selection", x64=x64)
    dev, dev_result = run(ops, device=True, record="selection", x64=x64)
    want, got = outcome(host, host_result), outcome(dev, dev_result)
    assert got["steps"] == want["steps"]
    assert got["pods"] == want["pods"] and got["start"] == want["start"]
    driver = dev.replay_driver
    stats = driver.stats()
    assert driver._last_plan.statics.sample == 2
    assert stats["sampled_attempts"] == sum(s + u for _, s, u, _ in want["steps"]) > 400
    # Nodes fill up: some walks pass infeasible nodes, none scores more than k.
    assert stats["nodes_scored"] < stats["nodes_visited"] < stats["pairs_evaluated"]
    assert stats["nodes_scored"] <= 100 * stats["sampled_attempts"]
    if not x64:
        unsampled = ScenarioRunner(record="selection", pod_bucket_min=32)
        all_nodes = outcome(unsampled, unsampled.run(iter(ops)))
        assert all_nodes["pods"] != want["pods"] and all_nodes["start"] == 0


# -- the start index across the paths and across a resume ----------------------


def arrivals(steps: int, per_step: int, odd_at: "int | None" = None) -> list:
    """120 equal nodes, then ``per_step`` small pods a step; step
    ``odd_at`` also creates a Namespace, an operation the segment path
    does not lower: that step runs per-pass."""
    ops = [create(0, "nodes", node(i)) for i in range(N)]
    for step in range(1, steps + 1):
        if step == odd_at:
            ops.append(create(step, "namespaces", {
                "apiVersion": "v1", "kind": "Namespace", "metadata": {"name": "other"}}))
        ops += [create(step, "pods", make_pod(f"p-{step:02d}-{j}")) for j in range(per_step)]
    return ops


def test_the_start_index_survives_the_hand_over_between_the_paths():
    """Ten steps of one pod; step 5 also creates a Namespace.  Steps 0-3
    and step 4 run as device segments (the window stops at the operation
    it cannot lower), step 5 per-pass, steps 6-9 and step 10 on the device
    again.  Every node fits every pod, so each of the 10 attempts visits
    exactly 100 nodes: the index stands at 300, 400, (500 per-pass,) 900
    and 1,000 mod 120 — and every pod stands where the pure per-pass run
    puts it."""
    ops = arrivals(10, 1, odd_at=5)
    host, host_result = run(ops, device=False, record="selection")
    store = ClusterStore()
    service = SchedulerService(store, record="selection", preemption=False,
                               node_sampling=True, pod_bucket_min=32)
    dev = ScenarioRunner(store=store, service=service, device_replay=True, device_segment_steps=4)
    dev_result = dev.run(iter(ops))
    driver = dev.replay_driver
    assert driver.unsupported == {"op:create/namespaces": 1}
    assert driver.fallback_steps == 1 and driver.device_steps == 10
    assert outcome(dev, dev_result) == outcome(host, host_result)
    assert service._pnts_start[SCHED] == 1000 % 120 == 40
    # Nine attempts walked on the device, one on the per-pass path.
    assert driver.stats()["sampled_attempts"] == 9 and driver.stats()["nodes_visited"] == 900
    # The index each segment left: the walk went on where the other path stopped.
    assert [e["sampling_start"] for e in driver.lower_log] == [60, 40, 900 % 120, 40]


def test_the_start_index_survives_a_checkpoint_resume():
    """The carry a job checkpoint records (``checkpoint_carries``) holds
    the index: a fresh store and service restored after the first
    committed segment finish the stream exactly as the uninterrupted run
    does.  Nine steps of three pods: 27 attempts, 2,700 mod 120 = 60."""
    ops = arrivals(9, 3)
    whole, whole_result = run(ops, device=True, record="selection")
    taken = []

    def hook(cursor, driver, result):
        if not taken:
            taken.append((cursor, driver.store.checkpoint(), driver.service.checkpoint_carries()))

    first, _ = run(ops, device=True, record="selection", checkpoint_hook=hook)
    cursor, snapshot, carries = taken[0]
    assert carries["pnts_start"] == {SCHED: 9 * 100 % 120}  # steps 0-3: nine attempts
    store = ClusterStore.from_checkpoint(snapshot)
    service = SchedulerService(store, record="selection", preemption=False,
                               node_sampling=True, pod_bucket_min=32)
    service.restore_carries(carries)
    resumed = ScenarioRunner(store=store, service=service, device_replay=True,
                             device_segment_steps=4)
    resumed.run(iter(ops), resume_cursor=cursor)
    assert resumed.replay_driver.fallback_steps == 0
    assert outcome(resumed, whole_result)["pods"] == outcome(whole, whole_result)["pods"]
    assert service._pnts_start[SCHED] == whole.service._pnts_start[SCHED] == 60


# -- the served job against the benchmark's plain reference --------------------

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark")


def test_the_served_job_equals_the_plain_reference_on_scheduling_basic_500_nodes():
    """Upstream's ``SchedulingBasic`` / ``500Nodes`` (500 nodes, 500 init
    pods, 1,000 measured pods) as the benchmark cell ``sperf-5k-basic_10kpods``
    submits it at its rehearsal size, through ``POST /api/v1/jobs`` with
    ``spec.simulator.nodeSampling``: k = 46 % of 500 = 230.  Counts,
    placements digest and the walk's counters equal the plain reference's
    (``benchmark/references/sampled.py``: a sequential scheduler written from
    upstream's definitions, importing nothing of the program) — and the
    digest is not that of the same reference scoring every node."""
    from ksim_tpu.server import DIContainer, SimulatorServer
    from tests.test_jobs import _req, _wait_state

    sys.path.insert(0, BENCH)
    try:
        import placements
        import run as harness
        from references import sampled

        cell = harness.load_cell(harness.load("BENCHMARK.json"), "sperf-5k-basic_10kpods", True)
        inputs = harness.build_inputs(cell["config"], cell["traffic"], 2147483693)
        want = sampled.replay(inputs["operations"])
        control = sampled.replay(inputs["operations"], walk_on=False)
    finally:
        sys.path.remove(BENCH)
    assert cell["config"]["simulator"]["nodeSampling"] is True
    di = DIContainer()
    srv = SimulatorServer(di, port=0).start()
    try:
        status, job = _req(srv, "POST", "/api/v1/jobs", json.loads(inputs["body"]))
        assert status == 202
        _wait_state(srv, job["id"], {"succeeded"}, deadline_s=300.0)
        _, doc = _req(srv, "GET", f"/api/v1/jobs/{job['id']}/result")
    finally:
        srv.shutdown_server()
        di.shutdown()
    result, block = doc["result"], doc["replay"]
    assert [result[k] for k in ("eventsApplied", "podsScheduled", "unschedulableAttempts")] \
        == [want[k] for k in ("eventsApplied", "podsScheduled", "unschedulableAttempts")] \
        == [2000, 1500, 0]
    assert block["device_steps"] == 3 and block["fallback_steps"] == 0 and block["unsupported"] == {}
    assert block["placements_digest"] == placements.digest(want["placements"])
    assert block["placements_digest"] == cell["guarantees"]["replay_equals"]["placements_digest"]
    assert block["placements_digest"] != placements.digest(control["placements"])
    for key in ("sampled_attempts", "nodes_visited", "nodes_scored", "sampling_start"):
        assert block[key] == want[key], key
    # Every node stays feasible: each of 1,500 walks stops at exactly 230 nodes.
    assert (block["sampled_attempts"], block["nodes_visited"]) == (1500, 1500 * 230)


# -- the programs that do not sample -------------------------------------------

#: sha256 of ``_segment_fn.lower(...).as_text()`` on three fixed small plans,
#: taken from the tree BEFORE sampling reached the segment path (PR 33,
#: 68d8aaa) with this jax.  They are what every accepted benchmark cell runs:
#: a change to one of them recompiles those cells and may move them, and has
#: to say so (refresh the pin from the parent of such a change).  The two
#: flat ones still are 68d8aaa's; ``preempt`` is PR 44's, which meant to move
#: it (one filter run a state in a node-local window, the candidate cut by
#: one sort, the count of filter runs: 3c57f32e...4789c before).
UNSAMPLED_PROGRAMS = {
    "flat": "84623a633e9446c484804e23965f7a428d626251e3ce58fac89b0a1e5af518b0",
    "preempt": "57f0a26cb7514a03b14d3a3e801e72498d6b6f2cee20d77ff39e5b20376c1af6",
    "flat-full": "3d36b065ddc4ebbb0d3cb8fec6d50f2882d054551c0c5a943b7709eac3d7ecd6",
}


class _Lowered(Exception):
    pass


def lowered_text(monkeypatch, ops, **kw) -> "tuple[str, replay._SegmentStatics]":
    """The lowered text of the first window's segment program."""
    got = {}

    def capture(self, plan):
        const, (ev, st) = replay._pack_plan_buffers(plan, (plan.ev, plan.state0))
        got["text"] = replay._segment_fn.lower(plan.statics, plan.prog, const, ev, st).as_text()
        got["statics"] = plan.statics
        raise _Lowered()

    monkeypatch.setattr(replay.ReplayDriver, "_device_exec", capture)
    runner = ScenarioRunner(device_replay=True, device_segment_steps=4, pod_bucket_min=32, **kw)
    with pytest.raises(_Lowered):
        runner.run(iter(ops))
    return got["text"], got["statics"]


def _plan_ops(name: str):
    if name == "preempt":
        ops = [create(0, "nodes", make_node(f"n-{i}", cpu="4", memory="16Gi")) for i in range(4)]
        for step in range(1, 4):
            for j in range(3):
                ops.append(create(step, "pods", make_pod(
                    f"p-{step}-{j}", cpu="1500m", memory="256Mi",
                    priority=[0, 0, 5, 10][(3 * step + j) % 4])))
        return ops, dict(preemption=True)
    ops = list(churn_scenario(0, n_nodes=24, n_events=24 + 60, ops_per_step=20))
    return ops, (dict(preemption=False, record="full") if name == "flat-full" else dict(preemption=True))


@pytest.mark.parametrize("name", sorted(UNSAMPLED_PROGRAMS))
def test_a_universe_that_does_not_sample_lowers_the_program_it_always_did(monkeypatch, name):
    prev = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", False)
    try:
        ops, kw = _plan_ops(name)
        text, statics = lowered_text(monkeypatch, ops, **kw)
        assert statics.sample == 0 and statics.preempt == (name == "preempt")
        assert hashlib.sha256(text.encode()).hexdigest() == UNSAMPLED_PROGRAMS[name]
        # The control: the same window with sampling asked for is another
        # program only where a step is large enough to sample.
        small, small_statics = lowered_text(monkeypatch, ops, node_sampling=True, **kw)
        assert small_statics.sample == 0 and small == text
    finally:
        jax.config.update("jax_enable_x64", prev)


def test_a_window_that_samples_lowers_the_walk_and_no_sort(monkeypatch):
    """With 120 nodes the same flat window samples: start index and k are
    operands (``sample_start`` in the carry, ``sample_k`` in the events),
    the walk counts by prefix sum (a ``cumsum``) and nothing sorts the node
    axis in slot order (``sample`` 1: no node was replaced)."""
    ops = arrivals(3, 2)
    text, statics = lowered_text(monkeypatch, ops, node_sampling=True)
    plain, plain_statics = lowered_text(monkeypatch, ops)
    assert statics.sample == 1 and plain_statics.sample == 0 and text != plain
    assert text.count("call @cumsum") > plain.count("call @cumsum")
    assert "stablehlo.sort" not in text
