"""Fault-plane schedules over the device-resident replay executor.

The reference simulator has no fault injection (SURVEY.md §5); round 8
adds a first-class fault plane (ksim_tpu/faults.py) and a crash-safe
replay executor: watchdogged dispatch, a sticky circuit breaker, and an
all-or-nothing segment reconcile (store transaction).  The invariant
under EVERY injected schedule is the behavior lock (repo CLAUDE.md):
seed 0, 2000 nodes, 6k events -> 2524/471, byte-identical — plus a
nonzero exercised-fault counter (a green run whose fault never fired
would be vacuous) and the degradation evidence the schedule promises.

The schedules here are the SHIPPED ones the acceptance criteria name:
dispatch error, dispatch hang (watchdog), mid-reconcile fault (rollback),
lowering fault, and permanent device failure (breaker trip).

Tier-1 budget: the canonical dispatch-error schedule and the breaker
trip run in the default suite; the other three 6k schedules are
slow-marked (each is a full 6k replay, ~30-45 s) and run via
``make faults``, which overrides the repo's default ``-m 'not slow'``
deselection.  Every small-stream probe stays tier-1.
"""

from __future__ import annotations

import subprocess
import sys
import time
from pathlib import Path

import jax
import pytest

from ksim_tpu.faults import FAULTS, InjectedFault
from ksim_tpu.scenario import ScenarioRunner, churn_scenario, spec_from_operations
from ksim_tpu.scenario.runner import Operation
from tests.helpers import (
    make_node,
    make_pod,
    replay_synthetic_borg,
    sanitized_cpu_env,
)

LOCK = (2524, 471)  # scheduled/unschedulable, seed 0 / 2000 nodes / 6k events


@pytest.fixture(autouse=True)
def _clean_fault_plane():
    FAULTS.reset()
    yield
    FAULTS.reset()


@pytest.fixture(autouse=True)
def _f32_fast_mode():
    # The locked counts hold in both modes; f32 is how the benchmark runs it.
    jax.config.update("jax_enable_x64", False)
    yield
    jax.config.update("jax_enable_x64", True)


def _run_6k():
    runner = ScenarioRunner(
        max_pods_per_pass=1024,
        pod_bucket_min=128,
        device_replay=True,
        device_segment_steps=16,
    )
    res = runner.run(
        churn_scenario(0, n_nodes=2000, n_events=6000, ops_per_step=100)
    )
    return runner, res


def _assert_lock(res, driver):
    assert (res.pods_scheduled, res.unschedulable_attempts) == LOCK
    # Step accounting stays exact under degradation: every step landed
    # through exactly one path (a rolled-back segment must not
    # double-book its steps as device AND fallback).
    assert driver.device_steps + driver.fallback_steps == len(res.steps)


# ---------------------------------------------------------------------------
# Shipped 6k schedules
# ---------------------------------------------------------------------------


def test_dispatch_error_degrades_to_host_path():
    """One injected dispatch failure: that segment re-runs per-pass
    under the ``device_error`` reason, the next dispatch succeeds (the
    breaker window resets), and the locked counts hold."""
    FAULTS.arm("replay.dispatch", "call:2")
    runner, res = _run_6k()
    driver = runner.replay_driver
    _assert_lock(res, driver)
    assert FAULTS.fired("replay.dispatch") == 1
    assert driver.device_errors == 1
    assert driver.unsupported.get("device_error") == 1
    assert not driver.breaker_tripped
    assert driver.device_steps >= 32  # the device path carried the run


@pytest.mark.slow
def test_dispatch_hang_watchdog_degrades(monkeypatch):
    """A hung dispatch (a backend that stopped answering:
    block_until_ready never returns) is bounded by the watchdog and degrades instead of
    stalling the trajectory.  Deliberately loose on HOW FAR it degrades:
    the hung call 1 never reached the segment program's first
    trace/compile, so later dispatches pay it under the shortened test
    watchdog and may time out too (even trip the breaker) — the
    contract is that the run completes, bounded, with the locked
    counts, never that a hang is free."""
    monkeypatch.setenv("KSIM_REPLAY_WATCHDOG_S", "10")
    FAULTS.arm("replay.dispatch", "hang:15:1")  # first dispatch hangs 15s
    runner, res = _run_6k()
    driver = runner.replay_driver
    _assert_lock(res, driver)
    assert FAULTS.fired("replay.dispatch") == 1
    assert driver.watchdog_timeouts >= 1
    assert driver.device_errors >= driver.watchdog_timeouts


@pytest.mark.slow
def test_mid_reconcile_fault_rolls_back_atomically():
    """A fault in the middle of a segment's store reconcile rolls the
    WHOLE segment back (the store never observes a partially applied
    segment) and the segment re-runs per-pass — counts byte-identical."""
    FAULTS.arm("replay.reconcile", "call:2")  # second staged step faults
    runner, res = _run_6k()
    driver = runner.replay_driver
    _assert_lock(res, driver)
    assert FAULTS.fired("replay.reconcile") == 1
    assert driver.unsupported.get("reconcile_fault") == 1
    assert driver.device_steps >= 32


@pytest.mark.slow
def test_lowering_fault_classified_fallback():
    """An expected (SimulatorError) lowering failure falls back under
    the stable ``lowering_fault`` reason instead of crashing or being
    silently swallowed."""
    FAULTS.arm("replay.lower", "first:2")
    runner, res = _run_6k()
    driver = runner.replay_driver
    _assert_lock(res, driver)
    assert FAULTS.fired("replay.lower") == 2
    assert driver.unsupported.get("lowering_fault") == 2
    assert driver.device_steps >= 32


def test_permanent_device_failure_trips_breaker(monkeypatch):
    """A permanently failing backend costs exactly breaker-threshold
    failed dispatches, then the sticky breaker disables the device path
    and the whole run completes per-pass — no per-segment timeout tax,
    locked counts intact."""
    monkeypatch.setenv("KSIM_REPLAY_BREAKER_N", "2")
    FAULTS.arm("replay.dispatch", "always")
    runner, res = _run_6k()
    driver = runner.replay_driver
    _assert_lock(res, driver)
    assert FAULTS.fired("replay.dispatch") == 2  # breaker stops the bleeding
    assert driver.breaker_tripped
    assert driver.device_errors == 2
    assert driver.unsupported.get("device_error") == 2
    assert driver.unsupported.get("breaker_open", 0) > 0
    assert driver.device_steps == 0
    assert driver.fallback_steps == len(res.steps)


@pytest.mark.slow
def test_breaker_half_open_probe_closes(monkeypatch):
    """Half-open recovery (round 15): with KSIM_REPLAY_BREAKER_COOLDOWN_S
    set, a tripped breaker admits ONE probe segment after the cooldown;
    the injected fault is transient (first:1), so the probe dispatch
    succeeds, the breaker closes and the rest of the run is back on the
    device path."""
    monkeypatch.setenv("KSIM_REPLAY_BREAKER_N", "1")
    monkeypatch.setenv("KSIM_REPLAY_BREAKER_COOLDOWN_S", "0.05")
    FAULTS.arm("replay.dispatch", "first:1@device")
    runner = ScenarioRunner(
        max_pods_per_pass=1024, pod_bucket_min=128,
        device_replay=True, device_segment_steps=8,
    )
    runner.run(churn_scenario(0, n_nodes=100, n_events=1200, ops_per_step=40))
    d = runner.replay_driver
    assert d.breaker_probes >= 1
    assert d.breaker_closes >= 1
    assert d.breaker_reopens == 0
    assert d.breaker_tripped is False
    assert d.device_steps > 0  # post-close segments dispatched on-device
    b = d.stats()["breaker"]
    assert b["closes"] == d.breaker_closes
    assert b["cooldown_current_s"] == 0.05  # close resets the ladder


@pytest.mark.slow
def test_breaker_failed_probes_double_cooldown(monkeypatch):
    """A permanently dead backend: every probe fails, each failure
    re-opens with a DOUBLED cooldown (bounded), and the run still
    completes on the host path — recovery attempts never compromise
    the fallback guarantee."""
    monkeypatch.setenv("KSIM_REPLAY_BREAKER_N", "1")
    monkeypatch.setenv("KSIM_REPLAY_BREAKER_COOLDOWN_S", "0.05")
    FAULTS.arm("replay.dispatch", "always@device")
    runner = ScenarioRunner(
        max_pods_per_pass=1024, pod_bucket_min=128,
        device_replay=True, device_segment_steps=8,
    )
    res = runner.run(
        churn_scenario(0, n_nodes=100, n_events=1200, ops_per_step=40)
    )
    d = runner.replay_driver
    assert d.breaker_tripped is True
    assert d.breaker_reopens >= 1
    assert d.breaker_closes == 0
    assert d.device_steps == 0
    assert d.fallback_steps == len(res.steps)
    b = d.stats()["breaker"]
    # Doubled at least once, never past the base * 2**reopens ladder.
    assert b["cooldown_current_s"] >= 0.1
    assert b["cooldown_current_s"] == pytest.approx(
        min(0.05 * 2 ** d.breaker_reopens, 3600.0)
    )


def test_breaker_sticky_by_default(monkeypatch):
    """Without KSIM_REPLAY_BREAKER_COOLDOWN_S the breaker stays sticky:
    no probes, no closes — exactly the pre-round-15 contract."""
    monkeypatch.setenv("KSIM_REPLAY_BREAKER_N", "1")
    monkeypatch.delenv("KSIM_REPLAY_BREAKER_COOLDOWN_S", raising=False)
    FAULTS.arm("replay.dispatch", "always")
    runner = _small_runner()
    runner.run(_small_stream())
    d = runner.replay_driver
    assert d.breaker_tripped is True
    assert d.breaker_probes == 0
    assert d.breaker_closes == 0
    assert d.stats()["breaker"]["cooldown_s"] == 0.0


# ---------------------------------------------------------------------------
# Classification: programming errors must surface, not become fallbacks
# ---------------------------------------------------------------------------


def _small_stream():
    for i in range(4):
        yield Operation(
            step=0, op="create", kind="nodes",
            obj=make_node(f"n-{i}", cpu="8", memory="16Gi"),
        )
    for step in range(1, 5):
        yield Operation(
            step=step, op="create", kind="pods",
            obj=make_pod(f"p-{step}", cpu="500m", memory="512Mi"),
        )


def _small_runner():
    return ScenarioRunner(device_replay=True, device_segment_steps=4)


def test_planted_type_error_in_lowering_surfaces():
    """The taxonomy is classified, not a catch-all: a TypeError planted
    in lowering RE-RAISES instead of becoming a silent fallback."""
    FAULTS.arm("replay.lower", "call:1", exc=TypeError)
    with pytest.raises(TypeError, match="injected fault"):
        _small_runner().run(_small_stream())


def test_planted_type_error_in_dispatch_surfaces():
    FAULTS.arm("replay.dispatch", "call:1", exc=TypeError)
    with pytest.raises(TypeError, match="injected fault"):
        _small_runner().run(_small_stream())


def test_injected_lowering_fault_is_contained_on_small_stream():
    """The same site armed with the default (SimulatorError) class is
    contained — the run completes and matches the per-pass baseline."""
    base = ScenarioRunner().run(_small_stream())
    FAULTS.arm("replay.lower", "call:1")
    runner = _small_runner()
    dev = runner.run(_small_stream())
    assert [
        (s.step, s.scheduled, s.unschedulable) for s in dev.steps
    ] == [(s.step, s.scheduled, s.unschedulable) for s in base.steps]
    assert runner.replay_driver.unsupported.get("lowering_fault") == 1


# ---------------------------------------------------------------------------
# Atomicity probe: rolled-back segments leave byte-identical store state
# ---------------------------------------------------------------------------


def test_reconcile_rollback_store_matches_per_pass_baseline():
    """Small-stream end-to-end probe of reconcile atomicity: with a
    mid-reconcile fault forcing a rollback, the final store (every pod's
    node, phase, annotations) is byte-identical to the pure per-pass
    run, and no watcher ever saw an event from the rolled-back staging."""

    def state(runner):
        return sorted(
            (
                p["metadata"]["name"],
                p.get("spec", {}).get("nodeName"),
                p.get("status", {}).get("phase"),
            )
            for p in runner.store.list("pods")
        )

    base_r = ScenarioRunner()
    base = base_r.run(_small_stream())

    runner = _small_runner()
    stream = runner.store.watch(("pods",))
    FAULTS.arm("replay.reconcile", "call:1")
    dev = runner.run(_small_stream())
    assert FAULTS.fired("replay.reconcile") == 1
    assert runner.replay_driver.unsupported.get("reconcile_fault") == 1
    assert state(runner) == state(base_r)
    assert (dev.pods_scheduled, dev.unschedulable_attempts) == (
        base.pods_scheduled, base.unschedulable_attempts,
    )
    # Drain the watch queue: every MODIFIED bind event must name a pod
    # whose FINAL state carries that bind — a delivered event from a
    # rolled-back staging would have no matching final state.
    final = {name: node for name, node, _ph in state(runner)}
    while True:
        ev = stream.next(timeout=0)
        if ev is None:
            break
        node = ev.obj.get("spec", {}).get("nodeName")
        if ev.event_type == "MODIFIED" and node:
            assert final.get(ev.obj["metadata"]["name"]) == node
    stream.close()


def test_store_integrity_error_in_reconcile_surfaces():
    """Reconcile containment is scoped to InjectedFault: a NotFoundError
    raised mid-reconcile is a device-decode bug wearing a store-error
    class — it must roll back and then RE-RAISE, never be absorbed as a
    chaos fallback."""
    from ksim_tpu.errors import NotFoundError

    FAULTS.arm("replay.reconcile", "call:1", exc=NotFoundError)
    with pytest.raises(NotFoundError, match="injected fault"):
        _small_runner().run(_small_stream())


def test_persistent_reconcile_fault_trips_breaker(monkeypatch):
    """A reconcile that fails every time must not pay lowering +
    dispatch + rollback for every remaining step: consecutive rollbacks
    trip the same sticky breaker and the run completes per-pass with
    baseline-identical results."""
    monkeypatch.setenv("KSIM_REPLAY_BREAKER_N", "2")
    base = ScenarioRunner().run(_small_stream())
    FAULTS.arm("replay.reconcile", "always")
    runner = _small_runner()
    dev = runner.run(_small_stream())
    driver = runner.replay_driver
    assert driver.breaker_tripped
    assert driver.unsupported.get("reconcile_fault") == 2
    assert driver.device_steps == 0  # no segment ever committed
    assert [
        (s.step, s.scheduled, s.unschedulable) for s in dev.steps
    ] == [(s.step, s.scheduled, s.unschedulable) for s in base.steps]


def test_breaker_state_is_per_driver(monkeypatch):
    """Two runners in one process must not share breaker state: a run
    whose breaker tripped leaves the next run's device path intact."""
    FAULTS.arm("replay.dispatch", "always")
    monkeypatch.setenv("KSIM_REPLAY_BREAKER_N", "1")
    r1 = _small_runner()
    r1.run(_small_stream())
    assert r1.replay_driver.breaker_tripped
    monkeypatch.delenv("KSIM_REPLAY_BREAKER_N")
    FAULTS.reset()
    r2 = _small_runner()
    r2.run(_small_stream())
    assert not r2.replay_driver.breaker_tripped
    assert r2.replay_driver.device_steps > 0


# ---------------------------------------------------------------------------
# A dead device, surface by surface: every dispatch fails
# (``replay.dispatch=always@device``, breaker at 2) and each surface
# that drives the device path must finish on the per-pass host path
# with the counts of the run it is compared with.  The solo surface is
# test_permanent_device_failure_trips_breaker above (6k stream, locked
# counts, the whole breaker account).
# ---------------------------------------------------------------------------

_CHURN_KW = dict(
    max_pods_per_pass=1024, pod_bucket_min=128, device_replay=True, preemption=True
)


def _churn(n_events=300, n_nodes=64):
    return churn_scenario(0, n_nodes=n_nodes, n_events=n_events, ops_per_step=100)


def _counts(res):
    return [res.pods_scheduled, res.unschedulable_attempts]


def _churn_job_doc(n_events):
    return {
        "spec": {
            "simulator": {
                "preemption": True,
                "maxPodsPerPass": 1024,
                "podBucketMin": 128,
                "deviceReplay": True,
            },
            "scenario": spec_from_operations(list(_churn(n_events))),
        }
    }


def _job_counts(job):
    state, result, err = job.result_view()
    assert state == "succeeded", (state, err)
    return [
        result["result"]["podsScheduled"],
        result["result"]["unschedulableAttempts"],
    ]


def _dead_jobs(tmp_path, monkeypatch):
    """2 jobs on a 2-worker pool: all succeed with the solo counts."""
    from ksim_tpu.jobs import JobManager

    jm = JobManager(workers=2, queue_limit=4)
    try:
        doc = _churn_job_doc(300)
        jobs = [jm.submit(doc) for _ in range(2)]
        assert jm.join(timeout=300)
    finally:
        jm.shutdown(timeout=5)
    solo = ScenarioRunner(**_CHURN_KW).run(_churn())
    assert solo.pods_scheduled > 0
    for job in jobs:
        assert _job_counts(job) == _counts(solo)
        assert job.result_view()[1]["replay"]["device_steps"] == 0


def _dead_fleet(tmp_path, monkeypatch, lanes=3):
    """3 lanes: every lane lands the solo counts, none on the device."""
    solo = ScenarioRunner(**_CHURN_KW).run(_churn())
    assert solo.pods_scheduled > 0
    fleet = ScenarioRunner(**_CHURN_KW, fleet=lanes)
    res = fleet.run(_churn())
    assert [_counts(r) for r in res.lanes] == [_counts(solo)] * lanes
    stats = fleet.fleet_driver.stats()
    assert stats["lanes_on_device"] == 0.0
    assert stats["lane_device_steps"] == [0] * lanes


def _dead_fleet_mesh(tmp_path, monkeypatch):
    """2 lanes over dp of a (2, 4) fleet mesh, node tensors over tp."""
    monkeypatch.setenv("KSIM_FLEET_DP", "2")
    monkeypatch.setenv("KSIM_REPLAY_TP", "4")
    _dead_fleet(tmp_path, monkeypatch, lanes=2)


def _dead_trace(tmp_path, monkeypatch):
    """The bundled Borg fixture keeps its locked counts (56 / 19)."""
    from ksim_tpu.traces import trace_operations

    ops = trace_operations(
        "tests/fixtures/traces/borg_mini.jsonl", "borg", nodes=24, ops_per_step=2
    )
    base = ScenarioRunner(pod_bucket_min=64).run(list(ops))
    dev_r = ScenarioRunner(pod_bucket_min=64, device_replay=True)
    dev = dev_r.run(list(ops))
    assert _counts(dev) == _counts(base) == [56, 19]
    assert dev_r.replay_driver.device_steps == 0
    assert dev_r.replay_driver.unsupported.get("device_error", 0) >= 2


def _dead_stream(tmp_path, monkeypatch):
    """Streamed ingest degrades mid-pipeline and still equals the
    materialized run; the producer's own fallback is a separate plane."""
    stream, dev_r, streamed, mat = replay_synthetic_borg(tmp_path)
    assert _counts(streamed) == _counts(mat)
    assert streamed.pods_scheduled > 0
    assert stream.stats()["fallback"] == 0
    assert dev_r.replay_driver.device_steps == 0


def _dead_shard(tmp_path, monkeypatch):
    """tp 1 and tp 8 both degrade and agree."""
    sigs = []
    for tp in (1, 8):
        monkeypatch.setenv("KSIM_REPLAY_TP", str(tp))
        runner = ScenarioRunner(**_CHURN_KW)
        res = runner.run(_churn())
        sigs.append(_fleet_sig(res))
        assert runner.replay_driver.device_steps == 0
        assert runner.replay_driver.unsupported.get("device_error", 0) >= 1
    assert sigs[0] == sigs[1] and sum(s[1] for s in sigs[0]) > 0


def _dead_workers(tmp_path, monkeypatch):
    """2 worker PROCESSES behind a front door (the fault plane rides
    their environment): the job succeeds with the in-process solo
    counts, and the fleet-scope scrape still answers — the telemetry
    pull is host-side I/O a dead chip must not take down."""
    from ksim_tpu import obs
    from ksim_tpu.jobs import JobManager

    d = str(tmp_path)
    env = sanitized_cpu_env({
        "KSIM_FAULTS": "replay.dispatch=always@device",
        "KSIM_REPLAY_BREAKER_N": "2",
        "KSIM_WORKERS_POLL_S": "0.1",
        "KSIM_WORKERS_LEASE_S": "8",
        "KSIM_OBS_PUBLISH_S": "1",
        "KSIM_JOBS_CHECKPOINT_EVERY": "0",
    })
    procs = [
        subprocess.Popen(
            [
                sys.executable, "-m", "ksim_tpu.jobs",
                "--dir", d, "--worker-id", f"w{i}", "--workers", "1",
            ],
            env=env, cwd=Path(__file__).resolve().parent.parent,
            stdout=subprocess.PIPE, text=True,
        )
        for i in range(2)
    ]
    fd = None
    try:
        for i, proc in enumerate(procs):
            assert proc.stdout.readline().strip() == f"READY w{i}"
        fd = JobManager(
            workers=0, queue_limit=4, jobs_dir=d,
            role="frontdoor", worker_id="fd", lease_s=8.0, poll_s=0.1,
        )
        job = fd.submit(_churn_job_doc(200))
        deadline = time.monotonic() + 240
        while job.status()["state"] not in ("succeeded", "failed", "interrupted"):
            assert time.monotonic() < deadline, job.status()
            time.sleep(0.2)
        solo = ScenarioRunner(**_CHURN_KW).run(_churn(200))
        assert _job_counts(job) == _counts(solo)
        fleet_doc = obs.merge_fleet_docs(obs.read_fleet_snapshots(d))
        expo = obs.render_prometheus(fleet_doc)
        obs.parse_prometheus(expo)
        assert expo
    finally:
        for proc in procs:
            proc.terminate()
        for proc in procs:
            try:
                proc.wait(timeout=15)
            except subprocess.TimeoutExpired:
                proc.kill()
                proc.wait()
        if fd is not None:
            fd.shutdown()


_DEAD_SURFACES = {
    "jobs": _dead_jobs,
    "fleet": _dead_fleet,
    "trace": _dead_trace,
    "stream": _dead_stream,
    "shard": _dead_shard,
    "workers": _dead_workers,
    "fleet_mesh": _dead_fleet_mesh,
}


@pytest.mark.parametrize("surface", list(_DEAD_SURFACES))
def test_dead_device_is_carried_by_host_path(surface, monkeypatch, tmp_path):
    monkeypatch.setenv("KSIM_REPLAY_BREAKER_N", "2")
    monkeypatch.delenv("KSIM_REPLAY_TP", raising=False)
    monkeypatch.delenv("KSIM_FLEET_DP", raising=False)
    FAULTS.arm("replay.dispatch", "always@device")
    _DEAD_SURFACES[surface](tmp_path, monkeypatch)
    # Not vacuous: the armed site fired in this process (for `workers`,
    # in the solo run the job is compared with).
    assert FAULTS.fired("replay.dispatch") >= 2


# ---------------------------------------------------------------------------
# Fleet replay per-lane chaos (round 12, engine/fleet.py): a lane's
# PRIVATE fault plane (KSIM_FLEET_FAULTS) degrades that lane alone.
# Slow-marked for the tier-1 budget; `make faults` runs them (-m '').
# ---------------------------------------------------------------------------


def _fleet_sig(res):
    return [
        (s.step, s.scheduled, s.unschedulable, s.pending_after) for s in res.steps
    ]


def _fleet_churn():
    return churn_scenario(0, n_nodes=48, n_events=200, ops_per_step=20)


@pytest.mark.slow
def test_fleet_lane_fault_degrades_only_that_lane():
    """Per-lane chaos (KSIM_FLEET_FAULTS syntax): an injected dispatch
    fault on lane 2 degrades lane 2 alone — it diverges to the solo
    path, walks the device_error ladder, and still lands byte-identical
    counts; every other lane stays in the convergent cohort with zero
    degradation."""
    jax.config.update("jax_enable_x64", False)
    kw = dict(max_pods_per_pass=1024, pod_bucket_min=128, device_segment_steps=8)
    solo_r = ScenarioRunner(device_replay=True, **kw)
    solo = solo_r.run(_fleet_churn())
    fleet_r = ScenarioRunner(
        device_replay=True, fleet=4, fleet_faults="2:replay.dispatch=call:1", **kw
    )
    fleet_r.run(_fleet_churn())
    lanes = fleet_r.fleet_lanes
    for ln in lanes:
        assert _fleet_sig(ln.result) == _fleet_sig(solo), f"lane {ln.idx}"
    assert lanes[2].driver.device_errors == 1
    assert lanes[2].driver.unsupported.get("device_error") == 1
    assert not lanes[2].convergent
    assert lanes[2].driver.fallback_steps >= 1
    for ln in (lanes[0], lanes[1], lanes[3]):
        assert ln.driver.device_errors == 0
        assert ln.driver.fallback_steps == 0
        assert ln.convergent
    assert fleet_r.fleet_driver.stats()["divergences"] == 1


@pytest.mark.slow
def test_fleet_lane_reconcile_fault_rolls_back_only_that_lane():
    """A per-lane injected reconcile fault rolls back ONE lane's segment
    (its store byte-identical to the window start, the head step re-run
    per-pass) while the cohort commits; all lanes still converge on the
    solo counts."""
    jax.config.update("jax_enable_x64", False)
    kw = dict(max_pods_per_pass=1024, pod_bucket_min=128, device_segment_steps=8)
    solo_r = ScenarioRunner(device_replay=True, **kw)
    solo = solo_r.run(_fleet_churn())
    fleet_r = ScenarioRunner(
        device_replay=True, fleet=3, fleet_faults="1:replay.reconcile=call:1", **kw
    )
    fleet_r.run(_fleet_churn())
    lanes = fleet_r.fleet_lanes
    for ln in lanes:
        assert _fleet_sig(ln.result) == _fleet_sig(solo), f"lane {ln.idx}"
    assert lanes[1].driver.unsupported.get("reconcile_fault") == 1
    assert not lanes[1].convergent
    assert lanes[0].driver.unsupported.get("reconcile_fault") is None
    assert lanes[2].driver.unsupported.get("reconcile_fault") is None


@pytest.mark.slow
def test_fleet_leader_lane_lower_fault_degrades_leader_alone():
    """Review regression (round 12): a replay.lower fault armed on the
    COHORT LEADER's lane must fire exactly on its scheduled call and
    degrade the leader alone — not double-count through the shared
    lowering and not blast the whole cohort with lowering_fault."""
    jax.config.update("jax_enable_x64", False)
    kw = dict(max_pods_per_pass=1024, pod_bucket_min=128, device_segment_steps=8)
    solo_r = ScenarioRunner(device_replay=True, **kw)
    solo = solo_r.run(_fleet_churn())
    fleet_r = ScenarioRunner(
        device_replay=True, fleet=3, fleet_faults="0:replay.lower=call:1", **kw
    )
    fleet_r.run(_fleet_churn())
    lanes = fleet_r.fleet_lanes
    for ln in lanes:
        assert _fleet_sig(ln.result) == _fleet_sig(solo), f"lane {ln.idx}"
    assert lanes[0].driver.unsupported.get("lowering_fault") == 1
    assert lanes[0].driver.fallback_steps == 1
    assert not lanes[0].convergent
    for ln in lanes[1:]:
        assert "lowering_fault" not in ln.driver.unsupported, ln.driver.unsupported
        assert ln.driver.fallback_steps == 0
        assert ln.convergent
    # The lane plane fired exactly once (no gate+prepare double count).
    assert lanes[0].faults.fired("replay.lower") == 1
