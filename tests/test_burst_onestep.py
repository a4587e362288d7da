"""One uncapped pass over a whole backlog (the ``burst-5k`` deployment's
shape at a size a test holds): 200 nodes are created in step 0, 400 pods
arrive at once in step 1, and ONE scheduling pass with no
``max_pods_per_pass`` drains the queue — the queue bucket is the whole
backlog's, not the churn cells' 128-1,024 slots, and one step commits
hundreds of pods in sequence.

The device-replay path is held against two witnesses that share nothing
with it but the objects: the per-pass path (``ScenarioRunner`` without
``device_replay``) and the pure-Python oracle's greedy cycle
(``tests/test_engine_schedule.greedy_oracle`` over
``ksim_tpu/plugins/oracle.py``), placement by placement — in float32 and
exact (x64), for a burst that fits and for one that does not (every
node's pod capacity cut to one, so half the backlog ends unschedulable
and, with ``preemption=True`` and flat priorities, walks the preemption
gate without finding a victim).

The counts of the burst that fits (384 scheduled / 16 unschedulable) are
the rehearsal-size lock of the benchmark cell ``burst-5k_onestep``
(``benchmark/cells/burst-5k_onestep.json``): the plain replay under
``benchmark/`` and the oracle give them; they are not read off the
program.

Reference intent: upstream's scheduler drains its active queue
(scheduler_perf SchedulingBasic measures exactly that); the pass cap is
this repo's own device.
"""

import copy
import dataclasses
import hashlib

import jax
import pytest

from ksim_tpu.engine.replay import SEGMENT_STEPS
from ksim_tpu.scenario import ScenarioRunner, churn_scenario
from ksim_tpu.scheduler.service import queue_sort_key
from ksim_tpu.state.featurizer import bucket_size
from tests.test_engine_schedule import greedy_oracle

N_NODES, N_PODS = 200, 400
LOCK = (384, 16)  # benchmark/cells/burst-5k_onestep.json rehearsal_locks

CASES = ["fits", "tight"]
MODES = [False, True]
MODE_IDS = ["f32-fast", "exact-x64"]


def burst_ops(case: str) -> list:
    """Step 0: the nodes; step 1: the whole backlog.  ``tight`` cuts
    every node's pod capacity to one."""
    ops = list(
        churn_scenario(
            0,
            n_nodes=N_NODES,
            n_events=N_NODES + N_PODS,
            ops_per_step=N_PODS,
            pod_create_frac=1.0,
            pod_delete_frac=0.0,
        )
    )
    assert [op.step for op in ops] == [0] * N_NODES + [1] * N_PODS
    if case == "tight":
        for i, op in enumerate(ops[:N_NODES]):
            node = copy.deepcopy(op.obj)
            for block in ("allocatable", "capacity"):
                node["status"][block]["pods"] = "1"
            ops[i] = dataclasses.replace(op, obj=node)
    return ops


def _run(case: str, x64: bool, device: bool) -> dict:
    prev = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", x64)
    try:
        runner = ScenarioRunner(
            preemption=True, pod_bucket_min=128, device_replay=device
        )
        res = runner.run(iter(burst_ops(case)))
    finally:
        jax.config.update("jax_enable_x64", prev)
    driver = runner.replay_driver
    return {
        "counts": (res.events_applied, res.pods_scheduled, res.unschedulable_attempts),
        "steps": [(s.scheduled, s.unschedulable) for s in res.steps],
        "placements": {
            p["metadata"]["name"]: p["spec"].get("nodeName")
            for p in runner.store.list("pods", copy_objs=False)
        },
        "digest": runner.store.placements_digest(),
        "stats": driver.stats() if driver is not None else None,
        "lower_log": list(driver.lower_log) if driver is not None else None,
    }


_RUNS: dict = {}


def run(case: str, x64: bool, device: bool) -> dict:
    key = (case, x64, device)
    if key not in _RUNS:
        _RUNS[key] = _run(case, x64, device)
    return _RUNS[key]


_ORACLE: dict = {}


def oracle_placements(case: str) -> dict:
    """The oracle's greedy cycle over the same objects: nodes in the
    simulator's order for a fresh cluster (by name), pods in the queue's
    order; equal totals go to the first node, as the program documents."""
    if case not in _ORACLE:
        ops = burst_ops(case)
        nodes = sorted(
            (op.obj for op in ops[:N_NODES]), key=lambda n: n["metadata"]["name"]
        )
        queue = sorted((op.obj for op in ops[N_NODES:]), key=queue_sort_key)
        chosen = greedy_oracle(nodes, [], queue)
        _ORACLE[case] = {
            p["metadata"]["name"]: nodes[i]["metadata"]["name"] if i >= 0 else None
            for p, i in zip(queue, chosen)
        }
    return _ORACLE[case]


@pytest.mark.parametrize("x64", MODES, ids=MODE_IDS)
@pytest.mark.parametrize("case", CASES)
def test_device_replay_stays_on_the_device(case, x64):
    stats = run(case, x64, device=True)["stats"]
    assert stats["fallback_steps"] == 0 and stats["unsupported"] == {}
    assert stats["device_steps"] == 2 and stats["device_round_trips"] == 1
    assert stats["device_errors"] == 0 and stats["watchdog_timeouts"] == 0
    assert not stats["breaker_tripped"]


@pytest.mark.parametrize("x64", MODES, ids=MODE_IDS)
@pytest.mark.parametrize("case", CASES)
def test_device_replay_equals_the_per_pass_path(case, x64):
    dev, host = run(case, x64, device=True), run(case, x64, device=False)
    assert dev["counts"] == host["counts"]
    assert dev["steps"] == host["steps"]
    assert dev["placements"] == host["placements"]


@pytest.mark.parametrize("x64", MODES, ids=MODE_IDS)
@pytest.mark.parametrize("case", CASES)
def test_device_replay_equals_the_oracle(case, x64):
    dev, want = run(case, x64, device=True), oracle_placements(case)
    assert dev["placements"] == want
    bound = sum(1 for node in want.values() if node is not None)
    assert dev["counts"] == (N_NODES + N_PODS, bound, N_PODS - bound)
    assert dev["steps"] == [(0, 0), (bound, N_PODS - bound)]


def digest_of(placements: dict) -> str:
    """The job result's ``replay.placements_digest`` as documented
    (docs/jobs.md), from a witness's placements."""
    lines = sorted(f"default/{name} {node or ''}\n" for name, node in placements.items())
    return hashlib.sha256("".join(lines).encode()).hexdigest()


@pytest.mark.parametrize("x64", MODES, ids=MODE_IDS)
@pytest.mark.parametrize("case", CASES)
def test_the_placements_digest_is_the_oracles(case, x64):
    """What the benchmark cell holds every job to, with the plain replay
    in the oracle's place: one moved pod would move the digest (the
    counts would not show it — they are decided by the filters)."""
    want = oracle_placements(case)
    assert run(case, x64, device=True)["digest"] == digest_of(want)
    name, node = next((n, v) for n, v in sorted(want.items()) if v is not None)
    elsewhere = next(v for v in sorted(set(want.values()) - {None, node}))
    assert digest_of({**want, name: elsewhere}) != digest_of(want)


@pytest.mark.parametrize("x64", MODES, ids=MODE_IDS)
def test_the_burst_that_fits_counts_the_lock(x64):
    assert run("fits", x64, device=True)["counts"][1:] == LOCK


@pytest.mark.parametrize("x64", MODES, ids=MODE_IDS)
def test_the_burst_that_does_not_fit_leaves_half_the_backlog_pending(x64):
    """One pod per node: at most 200 bind; the rest fail NodeResourcesFit
    on every node, and with flat priorities the preemption gate finds no
    lower-priority victim — nobody is nominated, nothing evicted."""
    got = run("tight", x64, device=True)
    assert got["counts"] == (N_NODES + N_PODS, N_NODES, N_PODS - N_NODES)
    per_node = {}
    for node in got["placements"].values():
        if node is not None:
            per_node[node] = per_node.get(node, 0) + 1
    assert len(per_node) == N_NODES and set(per_node.values()) == {1}


@pytest.mark.parametrize("x64", MODES, ids=MODE_IDS)
@pytest.mark.parametrize("case", CASES)
def test_shape_counters(case, x64):
    """``queue_width_max``: the one compiled queue bucket is the whole
    backlog's; ``steps_padded``: a two-step job in a K-step program;
    ``pairs_evaluated``: every pod attempted once over every live node."""
    got = run(case, x64, device=True)
    stats, (entry,) = got["stats"], got["lower_log"]
    assert stats["queue_width_max"] == bucket_size(N_PODS) == entry["queue_width"]
    assert stats["steps_padded"] == SEGMENT_STEPS - 2 == entry["steps_padded"]
    assert stats["pairs_evaluated"] == N_PODS * N_NODES == entry["pairs_evaluated"]


@pytest.mark.parametrize("x64", MODES, ids=MODE_IDS)
@pytest.mark.parametrize("case", CASES)
def test_pod_loops_run_the_backlog_not_the_bucket(case, x64):
    """``queue_slots_run``: the node-only step 0 runs no slot, and step
    1 runs its 400 attempts of the 512-slot bucket (400 is a whole
    number of the loop's blocks)."""
    got = run(case, x64, device=True)
    stats, (entry,) = got["stats"], got["lower_log"]
    assert sum(a + b for a, b in got["steps"]) == N_PODS
    assert stats["queue_slots_run"] == N_PODS == entry["slots_run"]
    assert stats["queue_slots_run"] < stats["queue_width_max"]


def test_the_served_burst_job_reports_the_counters_and_a_submit_span():
    """Through ``POST /api/v1/jobs`` as the benchmark cell submits it: the
    result's ``replay`` block carries the three counters and the digest of
    where every pod landed (the oracle's), and the request
    itself — body read, 600 operations parsed, enqueue — is one
    ``jobs.submit`` span on the global plane (at the cell's size 0.2 s of a
    3.6-s job had no span before it)."""
    from ksim_tpu.obs import TRACE
    from ksim_tpu.scenario import spec_from_operations
    from ksim_tpu.server import DIContainer, SimulatorServer
    from tests.test_jobs import _req, _wait_state

    sim = {"deviceReplay": True, "preemption": True, "podBucketMin": 128}
    doc = {"spec": {"simulator": sim, "scenario": spec_from_operations(burst_ops("fits"))}}
    prev = (TRACE._active, TRACE._ring_on, TRACE._user_disabled)
    TRACE.reset()
    TRACE.enable()
    di = DIContainer()
    srv = SimulatorServer(di, port=0).start()
    try:
        status, job = _req(srv, "POST", "/api/v1/jobs", doc)
        assert status == 202
        _wait_state(srv, job["id"], {"succeeded"}, deadline_s=300.0)
        _, result = _req(srv, "GET", f"/api/v1/jobs/{job['id']}/result")
        submits = [r for r in TRACE.ring_records() if r["name"] == "jobs.submit"]
    finally:
        srv.shutdown_server()
        di.shutdown()
        TRACE.reset()
        TRACE._active, TRACE._ring_on, TRACE._user_disabled = prev
    assert len(submits) == 1 and submits[0]["d"] > 0
    assert (result["result"]["podsScheduled"], result["result"]["unschedulableAttempts"]) == LOCK
    replay = result["replay"]
    assert replay["device_steps"] == 2 and replay["fallback_steps"] == 0
    assert replay["queue_width_max"] == bucket_size(N_PODS)
    assert replay["steps_padded"] == SEGMENT_STEPS - 2
    assert replay["pairs_evaluated"] == N_PODS * N_NODES
    assert replay["queue_slots_run"] == N_PODS
    assert replay["placements_digest"] == digest_of(oracle_placements("fits"))
