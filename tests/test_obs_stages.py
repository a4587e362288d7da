"""The timed stage (PR 38): the sequential child of a span that lands in
the plane's histograms (``phase_totals``, a job's ``phases``) and the
profiler bridge but never in the ring or the sink — so what is inside
``replay.reconcile``, the featurizer call and ``jobs.submit`` has a number
a job while the parents' self times stay what they were.  The primitive on
a bare plane, the three sites on a served CPU job, the node-table count on
a hand-made stream."""

from __future__ import annotations

import os
import sys

import pytest

from ksim_tpu import obs
from ksim_tpu.obs import _NOOP, TRACE, TracePlane
from ksim_tpu.scenario import Operation, ScenarioRunner
from tests.helpers import make_node, make_pod
from tests.test_obs_phases import _spans, served_job  # noqa: F401 (a fixture)

RECONCILE_STAGES = tuple(
    f"replay.reconcile.{s}" for s in ("apply", "write", "verify", "commit")
)
EFFECTS = "replay.reconcile.effects"
FEATURIZER_STAGES = tuple(
    f"service.featurize.{s}"
    for s in ("index", "resources", "affinity", "spread", "interpod", "extras")
)
PROGRAM = "replay.lower.featurize.program"
SUBMIT_STAGES = tuple(
    f"jobs.submit.{s}" for s in ("read", "parse", "build", "enqueue")
)
# PR 51: the job's start and end, and the seams of the lowering's last lap.
JOB_STAGES = (
    "jobs.run.build", "jobs.finish.digest", "jobs.finish.document",
    "jobs.finish.release",
)
TENSOR_SEAMS = tuple(
    f"replay.lower.tensors.{s}" for s in ("state", "interpod", "ranks", "statics")
)
STAGE_NAMES = (
    RECONCILE_STAGES + (EFFECTS,) + FEATURIZER_STAGES + (PROGRAM,) + SUBMIT_STAGES
    + JOB_STAGES + TENSOR_SEAMS
)


@pytest.fixture()
def fresh():
    """A private plane with the ring on and a sink that keeps what it is
    handed."""
    p = TracePlane()
    p.enable()
    sunk: list = []
    p.set_sink(sunk.append)
    return p, sunk


# -- the primitive -------------------------------------------------------------


def test_a_stage_reaches_the_histograms_and_neither_ring_nor_sink(fresh):
    p, sunk = fresh
    with p.span("replay.reconcile"):
        p.stage("replay.reconcile.apply")
        p.stage("replay.reconcile.write")
        p.stage("replay.reconcile.apply")
        p.stage("replay.reconcile.write")
        p.stage("replay.reconcile.verify")
    totals = p.phase_totals()
    assert {n: c for n, (_s, c) in totals.items()} == {
        "replay.reconcile.apply": 2, "replay.reconcile.write": 2,
        "replay.reconcile.verify": 1, "replay.reconcile": 1,
    }
    hist = p.snapshot()["histograms"]
    assert hist["replay.reconcile.apply"]["count"] == 2
    # One clock reading a boundary: the stages tile their part of the parent.
    staged = sum(s for n, (s, _c) in totals.items() if n != "replay.reconcile")
    assert 0 < staged <= totals["replay.reconcile"][0]
    assert [r["name"] for r in p.ring_records()] == ["replay.reconcile"]
    assert [r["name"] for r in sunk] == ["replay.reconcile"]
    assert p.snapshot()["ring"]["appended"] == 1
    assert p._tls.stages == [] and p._tls.depth == 0


def test_the_span_exit_closes_the_open_stage_on_an_exception_too(fresh):
    p, _sunk = fresh
    with pytest.raises(ValueError):
        with p.span("replay.reconcile"):
            p.stage("replay.reconcile.apply")
            raise ValueError("boom")
    assert p.phase_totals()["replay.reconcile.apply"][1] == 1
    assert p._tls.stages == []
    assert p.ring_records()[0]["args"]["error"] == "ValueError"


def test_a_lap_closes_the_stages_opened_inside_it(fresh):
    p, _sunk = fresh
    with p.span("replay.lower") as sp:
        sp.lap("replay.lower.featurize")
        p.stage("service.featurize.index")
        p.stage("replay.lower.featurize.program")
        sp.lap("replay.lower.tensors")
        assert p._tls.stages == []
        totals = p.phase_totals()
        assert totals[PROGRAM][1] == 1 and totals["service.featurize.index"][1] == 1
        assert totals[PROGRAM][0] + totals["service.featurize.index"][0] <= (
            totals["replay.lower.featurize"][0]
        )
    assert [r["name"] for r in p.ring_records()] == [
        "replay.lower.featurize", "replay.lower.tensors", "replay.lower",
    ]


def test_an_explicit_end_and_a_stage_with_no_parent(fresh):
    p, _sunk = fresh
    p.stage("service.featurize.extras")
    p.stage_end()
    p.stage_end()  # nothing open: nothing happens
    with p.stage("replay.reconcile.effects"):
        pass
    with pytest.raises(KeyError):
        with p.stage("replay.reconcile.effects"):
            raise KeyError("x")
    totals = p.phase_totals()
    assert totals["service.featurize.extras"][1] == 1
    assert totals["replay.reconcile.effects"][1] == 2
    assert p.ring_records() == [] and p._tls.stages == []


def test_a_ring_span_inside_a_stage_leaves_it_open(fresh):
    """``replay.reconcile.evict`` nests in the ``write`` stage's interval;
    a stage opened under a deeper span is that span's and closes with
    it."""
    p, _sunk = fresh
    with p.span("replay.reconcile"):
        p.stage("replay.reconcile.write")
        with p.span("replay.reconcile.evict"):
            p.stage("replay.reconcile.verify")
            assert len(p._tls.stages) == 2
        assert [s[0] for s in p._tls.stages] == ["replay.reconcile.write"]
        p.stage("replay.reconcile.commit")
    totals = p.phase_totals()
    assert totals["replay.reconcile.write"][0] >= totals["replay.reconcile.evict"][0]
    assert {r["name"] for r in p.ring_records()} == {
        "replay.reconcile", "replay.reconcile.evict",
    }


@pytest.mark.parametrize("name", STAGE_NAMES)
def test_plane_off_is_the_noop_singleton(name):
    p = TracePlane()
    assert p.stage(name) is _NOOP
    p.stage_end()
    with p.stage(name):
        pass
    assert p.phase_totals() == {}
    assert getattr(p._tls, "stages", None) is None


def test_every_stage_name_is_registered():
    assert set(STAGE_NAMES) <= set(obs.SPAN_NAMES)
    assert len(set(STAGE_NAMES)) == 24


def test_a_stage_follows_the_threads_scoped_plane(fresh):
    p, _sunk = fresh
    assert not TRACE.scope()
    with TRACE.scoped(p):
        with TRACE.span("replay.reconcile"):
            TRACE.stage("replay.reconcile.apply")
        with TRACE.stage("replay.reconcile.effects"):
            pass
    assert p.phase_totals()["replay.reconcile.apply"][1] == 1
    assert p.phase_totals()["replay.reconcile.effects"][1] == 1


def test_the_bridge_annotates_a_stage_inside_its_parent(fresh, monkeypatch):
    p, _sunk = fresh
    log: list = []

    def enter(name):
        log.append(("in", name))
        return name

    monkeypatch.setattr(obs, "_jax_annotation", enter)
    monkeypatch.setattr(obs, "_jax_annotation_exit", lambda ctx: log.append(("out", ctx)))
    p.set_jax_bridge(True)
    with p.span("replay.reconcile"):
        p.stage("replay.reconcile.write")
        with p.span("replay.reconcile.evict"):
            pass
        p.stage("replay.reconcile.commit")
    assert log == [
        ("in", "replay.reconcile"),
        ("in", "replay.reconcile.write"),
        ("in", "replay.reconcile.evict"), ("out", "replay.reconcile.evict"),
        ("out", "replay.reconcile.write"),
        ("in", "replay.reconcile.commit"), ("out", "replay.reconcile.commit"),
        ("out", "replay.reconcile"),
    ]
    log.clear()
    p.set_jax_bridge(False)
    with p.span("replay.reconcile"):
        p.stage("replay.reconcile.write")
    assert log == []


def test_the_real_bridge_does_not_break_a_stage(fresh):
    p, _sunk = fresh
    p.set_jax_bridge(True)
    with p.span("replay.reconcile"):
        p.stage("replay.reconcile.apply")
        p.stage("replay.reconcile.write")
    with p.stage("replay.reconcile.effects"):
        pass
    assert p.phase_totals()["replay.reconcile.write"][1] == 1
    assert [r["name"] for r in p.ring_records()] == ["replay.reconcile"]


# -- the three sites, on a served device-replay job ----------------------------


def test_job_result_carries_every_stage_below_its_parent(served_job):
    result, _trace = served_job
    phases = result["phases"]
    for name in RECONCILE_STAGES + (EFFECTS,) + FEATURIZER_STAGES + (PROGRAM,):
        assert phases[name] >= 0, name
        assert result["latency"][name]["count"] >= 1, name
    eps = 1e-5  # each entry is rounded to the microsecond
    assert sum(phases[n] for n in RECONCILE_STAGES) <= phases["replay.reconcile"] + eps
    assert sum(phases[n] for n in FEATURIZER_STAGES) + phases[PROGRAM] <= (
        phases["replay.lower.featurize"] + eps
    )
    # Apply and write are entered once a step, the others once a segment.
    segments = result["latency"]["replay.reconcile"]["count"]
    steps = result["result"]["steps"]
    assert result["latency"]["replay.reconcile.apply"]["count"] == steps
    assert result["latency"]["replay.reconcile.write"]["count"] == steps
    for name in ("replay.reconcile.verify", "replay.reconcile.commit", EFFECTS):
        assert result["latency"][name]["count"] == segments, name
    lowerings = result["latency"]["replay.lower.featurize"]["count"]
    for name in FEATURIZER_STAGES + (PROGRAM,):
        assert result["latency"][name]["count"] == lowerings, name


def test_job_result_carries_the_jobs_own_stages_and_the_tensor_seams(served_job):
    """What had closed when the document took its latency summary is in
    it: the build, the digest, every seam once a lowering, tiling the
    lap.  (``jobs.finish.document`` and ``.release`` close after it:
    the ``account`` carries their seconds.)"""
    result, _trace = served_job
    latency, phases = result["latency"], result["phases"]
    for name in ("jobs.run.build", "jobs.finish.digest"):
        assert latency[name]["count"] == 1, name
        assert name not in phases  # outside runner.run, like jobs.run.snapshot
    lowerings = latency["replay.lower.tensors"]["count"]
    for name in TENSOR_SEAMS:
        # The walk-order stage cuts the ranks seam in two on a sampling
        # service only; this job does not sample.
        assert latency[name]["count"] == lowerings, name
    eps = 1e-5
    seams = sum(phases[n] for n in TENSOR_SEAMS)
    assert seams <= phases["replay.lower.tensors"] + eps
    assert seams >= 0.9 * phases["replay.lower.tensors"]
    account = result["account"]
    assert account["build_s"] == pytest.approx(
        latency["jobs.run.build"]["mean_seconds"], abs=1e-3
    )


def test_job_result_carries_the_submit_block(served_job):
    result, _trace = served_job
    submit = result["submit"]
    assert set(submit) == {"read_s", "parse_s", "build_s", "enqueue_s", "total_s"}
    assert all(v >= 0 for v in submit.values())
    parts = sum(v for k, v in submit.items() if k != "total_s")
    # The five readings are consecutive: the four parts ARE the total.
    assert parts == pytest.approx(submit["total_s"], abs=1e-5)
    # The job plane never saw the handler's span (global plane).
    assert not any(n.startswith("jobs.submit") for n in result["phases"])


def test_job_ring_holds_no_stage_and_the_parents_keep_their_self_time(served_job):
    """No STAGE name is in the job's ring, so a stage takes nothing from
    its parent's self time: that stays the parent's duration less the
    ring spans that ARE nested in it, whatever they are — ``evict`` under
    ``replay.reconcile`` by design, a ``service.gc`` collection wherever
    the collector happened to run."""
    _result, trace = served_job
    spans = _spans(trace)
    names = {e["name"] for e in spans}
    assert not names & set(STAGE_NAMES)
    assert {"replay.reconcile", "replay.lower.featurize"} <= names
    assert trace["otherData"]["ring"]["evicted"] == 0
    # The benchmark's own reduction (``job_span_self`` reads it).
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(__file__)), "benchmark"))
    try:
        import xplane
    finally:
        sys.path.pop(0)
    # Whole nanoseconds: the ring's ``ts`` and ``dur`` are microseconds to
    # three places, and ``ts + dur`` in floats can end a span a hair AFTER
    # the sibling that starts on the same clock reading (a ``lap``) — the
    # reduction then takes that sibling for a child and clips it to
    # nothing.  (The benchmark's client adds the epoch in seconds, where
    # a double's step swallows the hair.)
    ring = [
        (round(e["ts"] * 1000), round((e["ts"] + e["dur"]) * 1000), e["name"], e["tid"])
        for e in spans
    ]
    selfs = xplane.self_times(ring)

    def inside(e, p):
        return e is not p and e[3] == p[3] and p[0] <= e[0] and e[1] <= p[1]

    for parent in (
        "replay.lower.featurize", "replay.reconcile", "replay.lower",
        "replay.lower.tensors", "jobs.run",
    ):
        want = 0
        for p in (e for e in ring if e[2] == parent):
            nested = [e for e in ring if inside(e, p)]
            children = [e for e in nested if not any(inside(e, o) for o in nested)]
            assert not {e[2] for e in children} & set(STAGE_NAMES)
            want += p[1] - p[0] - sum(e[1] - e[0] for e in children)
        assert selfs[parent] == want, parent


def test_submit_clock_needs_all_five_readings():
    from ksim_tpu.jobs import JobClock

    clock = JobClock()
    for _ in range(3):
        clock.mark()
    clock.close()
    doc = clock.seconds()
    assert list(doc) == ["read_s", "parse_s", "build_s", "enqueue_s", "total_s"]
    short = JobClock()
    short.close()
    assert short.seconds() is None
    # Four and a close that never comes is a handler that died: None,
    # after the wait (not exercised here); more than five is no submit.
    long = JobClock()
    for _ in range(4):
        long.mark()
    long.close()
    assert long.seconds() is None


def test_a_job_no_handler_submitted_has_no_submit_block():
    from ksim_tpu.jobs import JobManager

    mgr = JobManager(workers=1)
    try:
        ops = [{"step": 0, "createOperation": {"object": make_node("n0", cpu="4")}}]
        job = mgr.submit({"spec": {"scenario": {"operations": ops}}})
        assert job.wait_done(60)
        assert job.status()["state"] == "succeeded"
        assert "submit" not in job.result
    finally:
        mgr.shutdown()


# -- the node-side tables built afresh -----------------------------------------


def _uniform_stream() -> list:
    """Twelve nodes, then identical pods two a step; at step 13 one node
    is replaced.  Four steps a segment, four lowerings: the first meets
    the operations' own node objects, the second the store's copies of
    them, the third those again, the fourth a new node."""
    ops = [
        Operation(step=0, op="create", kind="nodes", obj=make_node(f"n{i}", cpu="8"))
        for i in range(12)
    ]
    for step in range(1, 16):
        if step == 13:
            ops.append(Operation(step=step, op="delete", kind="nodes", name="n11"))
            ops.append(
                Operation(step=step, op="create", kind="nodes", obj=make_node("n12", cpu="8"))
            )
        for j in range(2):
            ops.append(
                Operation(step=step, op="create", kind="pods", obj=make_pod(f"p{step}-{j}"))
            )
    return ops


def test_node_builds_are_counted_cold_and_after_a_node_is_replaced():
    runner = ScenarioRunner(device_replay=True, device_segment_steps=4)
    result = runner.run(_uniform_stream())
    driver = runner.replay_driver
    stats = driver.stats()
    assert stats["fallback_steps"] == 0 and stats["device_steps"] == 16
    builds = [entry["node_builds"] for entry in driver.lower_log]
    assert len(builds) == 4
    assert builds[0] > 0  # a cold memo: one table a family
    # builds[1]: the window that created the nodes lowered the operations'
    # objects, this one meets the store's copies (today: all built again).
    assert builds[2] == 0  # the same node objects, the same vocabulary
    assert builds[3] > 0  # a node was replaced inside the window
    assert stats["featurize_node_builds"] == sum(builds)
    assert runner.service.memo.stats()["seq_builds"] >= sum(builds)
    # The run's own phase split carries the stages too (timing-only plane).
    assert result.phase_seconds["replay.reconcile.verify"] >= 0
    assert result.phase_seconds["service.featurize.index"] >= 0
