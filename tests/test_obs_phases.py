"""Phase spans beneath the four opaque spans, the gc / XLA-compile
evidence and the one-clock bridge (PR 25): the children of
``replay.lower`` and of the dispatch on a served CPU job, the pass
phases that feed timer and span from one reading, the HTTP edge, and the
always-on process-level counters."""

from __future__ import annotations

import gc
import http.client
import json
import threading
import time

import jax
import jax.numpy as jnp
import pytest

from ksim_tpu import obs
from ksim_tpu.obs import _NOOP, TRACE, TracePlane
from ksim_tpu.scenario import ScenarioRunner, churn_scenario, spec_from_operations
from ksim_tpu.server import DIContainer, SimulatorServer
from ksim_tpu.util import Metrics, watch_xla_compiles
from tests.helpers import make_node, make_pod

LOWER_CHILDREN = {
    "replay.lower.universe",
    "replay.lower.featurize",
    "replay.lower.tensors",
}
WORKER_SPANS = {"replay.pack", "replay.exec", "replay.pull", "replay.decode"}
PASS_SPANS = {
    "service.featurize", "engine.pack", "engine.exec", "engine.pull", "service.bind",
}
NEW_SPANS = (
    LOWER_CHILDREN
    | WORKER_SPANS
    | PASS_SPANS
    | {"replay.lower.parse", "replay.lower.warm", "service.import",
       "service.export", "service.gc"}
)


@pytest.fixture()
def plane():
    """The global plane on with a clean ring, restored afterwards."""
    prev = (TRACE._active, TRACE._ring_on, TRACE._user_disabled)
    TRACE.reset()
    TRACE.enable()
    yield TRACE
    TRACE.reset()
    TRACE._active, TRACE._ring_on, TRACE._user_disabled = prev


@pytest.fixture()
def no_auto_gc():
    """Only the collections a test forces: the counts below are exact."""
    gc.disable()
    yield
    gc.enable()


@pytest.fixture()
def server():
    di = DIContainer()
    srv = SimulatorServer(di, port=0).start()
    yield srv
    srv.shutdown_server()
    di.shutdown()


def _req(srv, method, path, body=None):
    c = http.client.HTTPConnection("127.0.0.1", srv.port, timeout=60)
    c.request(
        method,
        path,
        json.dumps(body) if body is not None else None,
        {"Content-Type": "application/json"},
    )
    r = c.getresponse()
    data = r.read()
    c.close()
    return r.status, (json.loads(data) if data else None)


def _device_job_doc(n_events: int = 700) -> dict:
    ops = list(churn_scenario(7, n_nodes=30, n_events=n_events, ops_per_step=20))
    sim = {"deviceReplay": True, "podBucketMin": 64}
    return {"spec": {"simulator": sim, "scenario": spec_from_operations(ops)}}


@pytest.fixture(scope="module")
def served_job():
    """One small device-replay job through the served path; its result
    document and its private trace ring."""
    di = DIContainer()
    srv = SimulatorServer(di, port=0).start()
    try:
        status, job = _req(srv, "POST", "/api/v1/jobs", _device_job_doc())
        assert status == 202
        end = time.monotonic() + 300
        while True:
            _, body = _req(srv, "GET", f"/api/v1/jobs/{job['id']}")
            if body["state"] in ("succeeded", "failed"):
                break
            assert time.monotonic() < end
            time.sleep(0.05)
        assert body["state"] == "succeeded", body
        _, result = _req(srv, "GET", f"/api/v1/jobs/{job['id']}/result")
        _, trace = _req(srv, "GET", f"/api/v1/jobs/{job['id']}/trace")
        yield result, trace
    finally:
        srv.shutdown_server()
        di.shutdown()


def _spans(trace: dict) -> list:
    return [e for e in trace["traceEvents"] if e["ph"] == "X"]


def _inside(child: dict, parent: dict) -> bool:
    return (
        child["tid"] == parent["tid"]
        and child["ts"] >= parent["ts"]
        and child["ts"] + child["dur"] <= parent["ts"] + parent["dur"] + 1e-3
    )


# -- the job plane: children of replay.lower and of the dispatch -------------


def test_lower_children_nest_under_replay_lower_on_the_main_thread(served_job):
    _result, trace = served_job
    spans = _spans(trace)
    lowers = [e for e in spans if e["name"] == "replay.lower"]
    assert lowers
    main_tid = next(e["tid"] for e in spans if e["name"] == "jobs.run")
    for name in LOWER_CHILDREN:
        kids = [e for e in spans if e["name"] == name]
        # One per lowering, each inside a replay.lower of the job's thread.
        assert len(kids) == len(lowers), name
        for kid in kids:
            assert kid["tid"] == main_tid
            assert any(_inside(kid, lo) for lo in lowers), name
    # The children leave the parent little of its own: they start at its
    # first statement and the last one ends with it.
    for lo in lowers:
        covered = sum(
            e["dur"] for e in spans
            if e["name"].startswith("replay.lower.") and _inside(e, lo)
        )
        assert covered >= 0.85 * lo["dur"]


def test_prelower_children_and_parse(served_job):
    _result, trace = served_job
    spans = _spans(trace)
    pre = [e for e in spans if e["name"] == "replay.prelower"]
    assert pre
    for name in ("replay.lower.parse", "replay.lower.warm"):
        assert any(
            _inside(e, p) for e in spans if e["name"] == name for p in pre
        ), name


def test_dispatch_children_run_on_the_worker_thread(served_job):
    _result, trace = served_job
    spans = _spans(trace)
    dispatches = [e for e in spans if e["name"] == "replay.dispatch"]
    assert dispatches
    main_tid = dispatches[0]["tid"]
    thread_name = {
        e["tid"]: e["args"]["name"]
        for e in trace["traceEvents"]
        if e["ph"] == "M" and e["name"] == "thread_name"
    }
    for name in WORKER_SPANS:
        kids = [e for e in spans if e["name"] == name]
        assert len(kids) == len(dispatches), name
        for kid in kids:
            assert kid["tid"] != main_tid
            assert thread_name[kid["tid"]] == "replay-dispatch"
            # Inside the dispatch span's wall-clock window, on another thread.
            assert any(
                d["ts"] <= kid["ts"] and kid["ts"] + kid["dur"] <= d["ts"] + d["dur"] + 1e-3
                for d in dispatches
            ), name
    # pack -> exec -> pull -> decode, in this order, inside each dispatch.
    worker = sorted(
        (e for e in spans if e["name"] in WORKER_SPANS), key=lambda e: e["ts"]
    )
    order = [e["name"] for e in worker[:4]]
    assert order == ["replay.pack", "replay.exec", "replay.pull", "replay.decode"]


def test_job_result_runtime_block_and_device_wait(served_job):
    result, trace = served_job
    runtime = result["runtime"]
    assert set(runtime) == {
        "gc_gen2_collections", "gc_gen2_pause_s",
        "gc_scheduled_collections", "gc_scheduled_pause_s",
        "xla_compiles", "xla_compile_s", "xla_cache_loads",
    }
    assert all(v >= 0 for v in runtime.values())
    wait = result["replay"]["device_wait_s"]
    assert wait >= 0
    # The summed replay.exec walls, from a second pair of clock reads
    # around the same region.
    exec_s = sum(e["dur"] for e in _spans(trace) if e["name"] == "replay.exec") / 1e6
    assert wait == pytest.approx(exec_s, rel=0.05, abs=5e-3)
    assert result["replay"]["device_round_trips"] >= 1
    # The cumulative block stays beside the per-job growth.
    assert "compile_cache" in result["replay"]
    assert result["latency"]["replay.exec"]["count"] >= 1


def test_no_span_per_pod_or_step_on_the_job_plane(served_job):
    """The granularity rule: the job ring is always on, so the new sites
    count per segment and per dispatch — never per pod, event or step."""
    result, trace = served_job
    spans = _spans(trace)
    segments = sum(1 for e in spans if e["name"] == "replay.lower")
    for name in NEW_SPANS - {"service.gc"}:
        n = sum(1 for e in spans if e["name"] == name)
        assert n <= segments + 1, (name, n, segments)


# -- the job plane inherits the bridge ---------------------------------------


@pytest.mark.parametrize("bridge", [False, True])
def test_job_plane_inherits_the_jax_bridge_flag(bridge):
    from ksim_tpu.jobs import JobManager

    prev = TRACE.set_jax_bridge(bridge)
    try:
        mgr = JobManager(workers=1)
        try:
            ops = [{"step": 0, "createOperation": {"object": make_node("n0", cpu="4")}}]
            job = mgr.submit({"spec": {"scenario": {"operations": ops}}})
            assert job.trace.jax_bridge is bridge
            assert job.trace.active
        finally:
            mgr.shutdown()
    finally:
        TRACE.set_jax_bridge(prev)


def test_bridge_annotates_laps_and_spans_without_breaking_them(plane):
    prev = plane.set_jax_bridge(True)
    try:
        with plane.span("replay.lower") as sp:
            sp.lap("replay.lower.universe")
            sp.lap("replay.lower.tensors")
    finally:
        plane.set_jax_bridge(prev)
    assert [r["name"] for r in plane.ring_records()] == [
        "replay.lower.universe", "replay.lower.tensors", "replay.lower",
    ]


# -- laps ---------------------------------------------------------------------


def test_laps_are_sequential_children_closed_by_the_parent(plane):
    with plane.span("replay.lower", segment=1) as sp:
        sp.lap("replay.lower.universe")
        sp.lap("replay.lower.featurize", rows=3)
        sp.lap("replay.lower.tensors")
    recs = plane.ring_records()
    assert [(r["name"], r["depth"]) for r in recs] == [
        ("replay.lower.universe", 1),
        ("replay.lower.featurize", 1),
        ("replay.lower.tensors", 1),
        ("replay.lower", 0),
    ]
    uni, feat, tens, parent = recs
    # One clock reading per boundary: each lap starts where the last ended,
    # and the parent's exit closes the last.
    assert uni["t"] + uni["d"] == feat["t"]
    assert feat["t"] + feat["d"] == tens["t"]
    assert tens["t"] + tens["d"] == parent["t"] + parent["d"]
    assert feat["args"] == {"rows": 3}
    assert plane._tls.depth == 0


def test_an_exception_still_closes_the_open_lap(plane):
    with pytest.raises(ValueError):
        with plane.span("replay.lower") as sp:
            sp.lap("replay.lower.universe")
            raise ValueError("boom")
    recs = plane.ring_records()
    assert [r["name"] for r in recs] == ["replay.lower.universe", "replay.lower"]
    assert recs[1]["args"]["error"] == "ValueError"
    assert plane._tls.depth == 0


# -- the pass: one reading feeds timer and span -------------------------------


def test_phase_feeds_timer_and_span_from_one_reading(plane):
    metrics = Metrics()
    for _ in range(3):
        with plane.phase("service.featurize", metrics, "featurize"):
            time.sleep(0.001)
    timer = metrics.snapshot()["timings"]["featurize"]
    span = plane.snapshot()["histograms"]["service.featurize"]
    assert timer["count"] == span["count"] == 3
    assert timer["total_seconds"] == span["total_seconds"]
    assert timer["buckets"] == span["buckets"]


def test_phase_with_the_plane_off_keeps_the_timer_and_nothing_else():
    pl = TracePlane()
    metrics = Metrics()
    with pl.phase("service.bind", metrics, "bind") as ph:
        ph.set(render_s=0.0)
    assert metrics.snapshot()["timings"]["bind"]["count"] == 1
    assert pl.snapshot()["histograms"] == {}
    assert pl.phase("engine.exec") is _NOOP


def _schedule_one_pass(record: str = "full"):
    from ksim_tpu.scheduler import SchedulerService
    from ksim_tpu.state import ClusterStore

    store = ClusterStore()
    for i in range(3):
        store.create("nodes", make_node(f"n{i}", cpu="4"))
    for i in range(5):
        store.create("pods", make_pod(f"p{i}", cpu="100m"))
    svc = SchedulerService(store, record=record)
    placements = svc.schedule_pending()
    assert len(placements) == 5
    return svc


def test_pass_phases_are_spans_and_timers_of_equal_totals(plane):
    svc = _schedule_one_pass()
    timings = svc.metrics.snapshot()["timings"]
    hists = plane.snapshot()["histograms"]
    pairs = {
        "service.featurize": "featurize", "engine.pack": "engine_pack",
        "engine.exec": "engine_exec", "engine.pull": "engine_pull",
        "service.bind": "bind",
    }
    for span, timer in pairs.items():
        assert hists[span]["count"] == timings[timer]["count"] >= 1, span
        assert hists[span]["total_seconds"] == timings[timer]["total_seconds"], span
    # The keys tests/test_server.py and three benchmark metrics read keep
    # their names; `engine` still spans pack + exec + pull.
    assert {"featurize", "engine", "bind"} <= set(timings)
    assert timings["engine"]["total_seconds"] >= (
        timings["engine_pack"]["total_seconds"]
        + timings["engine_exec"]["total_seconds"]
        + timings["engine_pull"]["total_seconds"]
    ) - 1e-6
    # Per-pod work is summed and recorded ONCE per pass.
    assert timings["render"]["count"] == timings["bind_store"]["count"] == 1
    assert (
        timings["render"]["total_seconds"] + timings["bind_store"]["total_seconds"]
        <= timings["bind"]["total_seconds"]
    )
    bind = next(r for r in plane.ring_records() if r["name"] == "service.bind")
    assert bind["args"]["render_s"] == pytest.approx(
        timings["render"]["total_seconds"], abs=1e-5
    )
    assert bind["args"]["store_s"] >= 0
    # All five nest under the pass span.
    sched = next(r for r in plane.ring_records() if r["name"] == "service.schedule")
    for r in plane.ring_records():
        if r["name"] in PASS_SPANS:
            assert r["depth"] == 1
            assert sched["t"] <= r["t"] and r["t"] + r["d"] <= sched["t"] + sched["d"]


def test_pass_timers_run_with_the_plane_off():
    svc = _schedule_one_pass(record="selection")
    timings = svc.metrics.snapshot()["timings"]
    for name in ("featurize", "engine", "engine_pack", "engine_exec",
                 "engine_pull", "bind", "render", "bind_store"):
        assert timings[name]["count"] == 1, name


# -- the HTTP edge ------------------------------------------------------------


def test_import_and_export_are_spans_and_timers(server, plane):
    snapshot = {
        "nodes": [make_node("n0", cpu="4")], "pods": [make_pod("p0", cpu="100m")],
        "pvs": [], "pvcs": [], "storageClasses": [], "priorityClasses": [],
        "namespaces": [], "schedulerConfig": None,
    }
    status, _ = _req(server, "POST", "/api/v1/import", snapshot)
    assert status == 200
    status, export = _req(server, "GET", "/api/v1/export")
    assert status == 200 and len(export["pods"]) == 1
    _, doc = _req(server, "GET", "/api/v1/metrics")
    for timer in ("import_load", "export_snap", "export_encode"):
        assert doc["timings"][timer]["count"] == 1, timer
    hists = doc["trace"]["histograms"]
    assert hists["service.import"]["count"] == 1
    assert hists["service.import"]["total_seconds"] == doc["timings"]["import_load"]["total_seconds"]
    assert hists["service.export"]["count"] == 1
    exp = next(r for r in plane.ring_records() if r["name"] == "service.export")
    assert exp["args"]["snap_s"] == pytest.approx(
        doc["timings"]["export_snap"]["total_seconds"], abs=1e-5
    )
    assert exp["args"]["snap_s"] + exp["args"]["encode_s"] <= exp["d"] / 1e9 + 1e-5


def test_metrics_document_serves_gc_and_xla_evidence(server):
    _, doc = _req(server, "GET", "/api/v1/metrics")
    assert {"xla_compiles", "xla_cache_loads"} <= set(doc["counters"])
    assert {"gc_gen2", "xla_compile"} <= set(doc["timings"])
    before = doc["timings"]["gc_gen2"]["count"]
    gc.collect()
    _, doc = _req(server, "GET", "/api/v1/metrics")
    assert doc["timings"]["gc_gen2"]["count"] >= before + 1
    assert doc["timings"]["gc_gen2"]["total_seconds"] > 0


# -- garbage collection --------------------------------------------------------


def test_forced_full_collection_is_one_span_and_one_count(plane, no_auto_gc):
    before = obs.runtime_totals()
    gc.collect()  # generation 2
    gc.collect(0)  # a young collection: not recorded
    after = obs.runtime_totals()
    assert after["gc_gen2_collections"] == before["gc_gen2_collections"] + 1
    assert after["gc_gen2_pause_s"] > before["gc_gen2_pause_s"]
    recs = [r for r in plane.ring_records() if r["name"] == "service.gc"]
    assert len(recs) == 1
    assert recs[0]["args"]["generation"] == 2 and recs[0]["args"]["collected"] >= 0
    assert recs[0]["tid"] == threading.get_ident()
    assert obs.runtime_snapshot()["timings"]["gc_gen2"]["count"] == after["gc_gen2_collections"]
    assert plane.snapshot()["histograms"]["service.gc"]["count"] == 1


def test_collection_lands_on_the_collecting_threads_scoped_plane(plane, no_auto_gc):
    job_plane = TracePlane(tags={"job": "j1"})
    job_plane.enable()
    with TRACE.scoped(job_plane):
        with TRACE.span("replay.lower") as sp:
            sp.lap("replay.lower.featurize")
            gc.collect()
    names = [r["name"] for r in job_plane.ring_records()]
    assert names == ["service.gc", "replay.lower.featurize", "replay.lower"]
    rec = job_plane.ring_records()[0]
    assert rec["args"]["job"] == "j1" and rec["depth"] == 2
    assert not [r for r in plane.ring_records() if r["name"] == "service.gc"]


def test_collection_while_the_plane_lock_is_held_does_not_deadlock(plane, no_auto_gc):
    """The hook runs inside whatever allocation tripped the collector —
    possibly one made under the plane's own lock."""
    done = threading.Event()

    def work():
        with plane._lock:
            gc.collect()
        done.set()

    t = threading.Thread(target=work, daemon=True)
    t.start()
    assert done.wait(10), "gc hook blocked on the plane lock"
    assert [r["name"] for r in plane.ring_records()] == ["service.gc"]


def test_collection_with_every_plane_off_costs_a_count_only(no_auto_gc):
    pl = TRACE
    prev = (pl._active, pl._ring_on, pl._user_disabled)
    pl._active = False
    try:
        before = obs.runtime_totals()["gc_gen2_collections"]
        size = len(pl._deferred)
        gc.collect()
        assert obs.runtime_totals()["gc_gen2_collections"] == before + 1
        assert len(pl._deferred) == size
    finally:
        pl._active, pl._ring_on, pl._user_disabled = prev


# -- XLA compiles ---------------------------------------------------------------


def test_first_time_jit_call_grows_xla_compiles(plane):
    watch_xla_compiles()
    watch_xla_compiles()  # idempotent: one listener, one count per compile

    @jax.jit
    def fresh(x):
        return x * 3 + 41

    x = jnp.ones((3,), jnp.float32)
    jax.block_until_ready(x)
    before = obs.runtime_totals()
    seen = len(plane.ring_records())
    jax.block_until_ready(fresh(x))
    mid = obs.runtime_totals()
    grown = (mid["xla_compiles"] - before["xla_compiles"]) + (
        mid["xla_cache_loads"] - before["xla_cache_loads"]
    )
    assert grown == 1  # compiled, or loaded from the suite's warm disk cache
    jax.block_until_ready(fresh(x))  # warm: neither grows
    warm = obs.runtime_totals()
    for key in ("xla_compiles", "xla_cache_loads", "xla_compile_s"):
        assert warm[key] == mid[key], key
    if mid["xla_compiles"] > before["xla_compiles"]:
        assert mid["xla_compile_s"] > before["xla_compile_s"]
        events = [
            r for r in plane.ring_records()[seen:] if r["name"] == "engine.compile"
        ]
        assert len(events) == 1 and events[0]["args"]["seconds"] > 0
        assert events[0]["tid"] == threading.get_ident()


def test_runtime_growth_is_the_difference_of_two_readings():
    before = obs.runtime_totals()
    obs.note_xla_compile(0.25)
    obs.note_xla_cache_load()
    grew = obs.runtime_growth(before)
    assert grew["xla_compiles"] == 1 and grew["xla_cache_loads"] == 1
    assert grew["xla_compile_s"] == pytest.approx(0.25)


# -- the disabled path ----------------------------------------------------------


@pytest.mark.parametrize("name", sorted(NEW_SPANS))
def test_disabled_plane_returns_the_noop_singleton_at_every_new_site(name):
    pl = TracePlane()  # never enabled
    assert pl.span(name) is _NOOP
    assert pl.phase(name) is _NOOP
    with pl.span(name) as sp:
        sp.lap(name)  # the no-op singleton takes laps too
        sp.set(x=1)
    assert pl.ring_records() == [] and pl.snapshot()["histograms"] == {}


def test_every_new_name_is_registered():
    assert NEW_SPANS <= set(obs.SPAN_NAMES)
    assert "engine.compile" in obs.EVENT_NAMES


def test_obs_imports_neither_jax_nor_numpy():
    import subprocess
    import sys

    from tests.helpers import sanitized_cpu_env

    code = (
        "import sys, ksim_tpu.obs; "
        "bad = [m for m in ('jax', 'numpy') if m in sys.modules]; "
        "assert not bad, bad"
    )
    subprocess.run([sys.executable, "-c", code], check=True, env=sanitized_cpu_env())


# -- zero perturbation ------------------------------------------------------------


def _small_device_replay():
    runner = ScenarioRunner(pod_bucket_min=64, device_replay=True)
    res = runner.run(churn_scenario(11, n_nodes=40, n_events=600, ops_per_step=25))
    drv = runner.replay_driver
    return (
        res.events_applied, res.pods_scheduled, res.unschedulable_attempts,
        drv.device_steps, drv.fallback_steps,
    ), drv


def test_device_replay_counts_are_identical_with_tracing_on_and_off(plane):
    on, drv = _small_device_replay()
    names = {r["name"] for r in plane.ring_records()}
    assert LOWER_CHILDREN | WORKER_SPANS <= names
    assert drv.stats()["device_wait_s"] > 0
    plane._active = False
    try:
        off, drv_off = _small_device_replay()
    finally:
        plane._active = True
    assert on == off
    # Read with tracing off too.
    assert drv_off.stats()["device_wait_s"] > 0
