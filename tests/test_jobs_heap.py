"""What a finished job leaves behind, and who walks the old generation
(jobs/manager.py "the old generation", state/objcache.py "Lifetime").

A terminal job keeps what a client can still ask for and nothing of the
cluster it ran; its parse memo dies with its service; while a job runs
CPython's automatic full collection is out of reach and the worker runs
ONE itself at the job's end, inside the job's own ``runtime`` block.
Counts only — nothing here is a speed."""

from __future__ import annotations

import gc
import http.client
import json
import time
import weakref

import pytest

from ksim_tpu import obs
from ksim_tpu.jobs import JobManager
from ksim_tpu.jobs import manager as jobs_manager
from ksim_tpu.server import DIContainer, SimulatorServer
from ksim_tpu.state import objcache
from tests.test_jobs import device_spec, tiny_spec

TERMINAL = ("succeeded", "failed", "cancelled", "interrupted")


@pytest.fixture()
def jm():
    m = JobManager(workers=1, queue_limit=8)
    yield m
    m.shutdown(timeout=5)


@pytest.fixture()
def server():
    di = DIContainer()
    srv = SimulatorServer(di, port=0).start()
    yield srv
    srv.shutdown_server()
    di.shutdown()


def _req(srv, method, path, body=None, raw=False):
    c = http.client.HTTPConnection("127.0.0.1", srv.port, timeout=60)
    c.request(
        method, path, json.dumps(body) if body is not None else None,
        {"Content-Type": "application/json"},
    )
    r = c.getresponse()
    data = r.read()
    c.close()
    return r.status, data if raw else (json.loads(data) if data else None)


def _handles_while_running(job, deadline_s: float = 120.0):
    """Weak references to the job's graph, taken while the worker holds
    it (a terminal job has let it go)."""
    end = time.monotonic() + deadline_s
    while time.monotonic() < end:
        runner = job.runner
        if runner is not None:
            refs = [weakref.ref(runner), weakref.ref(runner.service), weakref.ref(runner.store)]
            for ln in runner.fleet_lanes or ():
                refs += [weakref.ref(ln.runner.service), weakref.ref(ln.runner.store)]
            del runner
            return refs
        if job.status()["state"] in TERMINAL:
            return None
        time.sleep(0.001)
    raise AssertionError("the job never started")


def _idle(m: JobManager, deadline_s: float = 30.0) -> None:
    """The worker has moved on: nothing runs, the thresholds are back."""
    end = time.monotonic() + deadline_s
    while time.monotonic() < end:
        if m.snapshot()["workers"]["active"] == 0 and jobs_manager._old_gen_running == 0:
            return
        time.sleep(0.005)
    raise AssertionError("the job plane never went idle")


# -- (1) nothing of a finished job stays pinned ---------------------------------


@pytest.mark.parametrize(
    "spec",
    [
        pytest.param(lambda: tiny_spec(n_pods=6), id="per_pass"),
        pytest.param(lambda: device_spec(n_events=200), id="device"),
        pytest.param(lambda: device_spec(n_events=200, fleet=2), id="fleet"),
    ],
)
def test_terminal_job_lets_its_graph_go_by_reference_count(jm, spec):
    gc.collect()
    gc.disable()  # what dies below dies by reference count alone
    try:
        job = jm.submit(spec())
        refs = _handles_while_running(job)
        assert job.wait_done(300)
        assert job.status()["state"] == "succeeded", job.result_view()
    finally:
        gc.enable()
    assert refs is not None, "the job ended before a handle could be taken"
    assert job.store is None and job.runner is None
    assert job.ops == [] and job.sim == {} and job.checkpoints == []
    # wait_done returns with the terminal event: the graph went BEFORE it.
    assert [r() for r in refs] == [None] * len(refs)


def test_no_memo_entry_and_no_heap_growth_across_finished_jobs(jm):
    spec = device_spec(n_events=200)
    default0 = objcache.stats()

    def run_one():
        job = jm.submit(json.loads(json.dumps(spec)))
        assert job.wait_done(300)
        assert job.status()["state"] == "succeeded", job.result_view()
        return job

    for _ in range(2):  # compile, warm the process
        run_one()
    _idle(jm)
    gc.collect()
    after_n = len(gc.get_objects())
    pauses = []
    for _ in range(8):
        pauses.append(run_one().result_view()[1]["runtime"])
    _idle(jm)
    gc.collect()
    after_n8 = len(gc.get_objects())
    # Eight more retained result documents, event logs and trace rings —
    # and no cluster: a few per cent (22,600 objects a job stayed before).
    assert after_n8 - after_n < 0.03 * after_n, (after_n, after_n8)
    # The jobs memoized into their own services' tables, which died with
    # them: the process default saw none of it.
    assert objcache.stats() == default0
    assert [r["gc_scheduled_collections"] for r in pauses] == [1] * 8
    assert [r["gc_gen2_collections"] for r in pauses] == [1] * 8


def test_a_runner_memoizes_into_its_own_services_table():
    from ksim_tpu.scenario import ScenarioRunner, operations_from_spec

    default0 = objcache.stats()
    runner = ScenarioRunner()
    runner.run(operations_from_spec(tiny_spec(n_pods=4)["spec"]["scenario"]))
    assert runner.service.memo.stats()["entries"] > 0
    assert objcache.stats() == default0
    assert objcache.current() is not runner.service.memo  # the scope ended with run()


# -- (2) a retained terminal job still answers ----------------------------------


def test_every_retained_terminal_job_still_answers(server):
    ids = []
    for spec in (tiny_spec(n_pods=3), device_spec(n_events=200), tiny_spec(n_pods=2)):
        status, job = _req(server, "POST", "/api/v1/jobs", spec)
        assert status == 202
        ids.append(job["id"])
    jm = server.di.job_manager
    for jid in ids:
        assert jm.get(jid).wait_done(300)
    _idle(jm)
    for jid in ids:
        assert jm.get(jid).store is None
        status, st = _req(server, "GET", f"/api/v1/jobs/{jid}")
        assert status == 200 and st["state"] == "succeeded"
        assert st["progress"]["steps_done"] == st["progress"]["steps_total"] > 0
        status, res = _req(server, "GET", f"/api/v1/jobs/{jid}/result")
        assert status == 200 and res["result"]["eventsApplied"] > 0
        assert res["runtime"]["gc_scheduled_collections"] >= 1
        status, raw = _req(server, "GET", f"/api/v1/jobs/{jid}/events", raw=True)
        events = [json.loads(ln[5:]) for ln in raw.decode().splitlines() if ln.startswith("data:")]
        assert status == 200
        assert [e["state"] for e in events if e.get("event") == "state"] == [
            "queued", "running", "succeeded",
        ]
        status, trace = _req(server, "GET", f"/api/v1/jobs/{jid}/trace")
        names = {e["name"] for e in trace["traceEvents"] if e.get("ph") == "X"}
        assert status == 200 and "jobs.run" in names
        # The job's one full collection is on its own ring, marked.
        gcs = [e for e in trace["traceEvents"] if e.get("name") == "service.gc"]
        assert [e["args"]["scheduled"] for e in gcs] == [True]


# -- (3)-(5) who walks the old generation ---------------------------------------


def _burst(n: int) -> list:
    """``n`` containers that survive the young generations: under
    CPython's own thresholds, more than enough for a full collection."""
    return [[i] for i in range(n)]  # lists: always tracked by the collector


def _burst_size() -> int:
    # The growth trigger wants a quarter of the old generation promoted.
    return max(400_000, len(gc.get_objects()))


def _inside_job(monkeypatch, body):
    """Run ``body()`` on the worker thread while the job is running,
    after its replay and before its end."""
    real = JobManager._execute

    def execute(self, job):
        out = real(self, job)
        body()
        return out

    monkeypatch.setattr(JobManager, "_execute", execute)


def test_no_automatic_full_collection_inside_a_job_and_one_scheduled_at_its_end(
    jm, monkeypatch
):
    idle = gc.get_threshold()
    n = _burst_size()
    # Control: the same burst with the plane idle trips CPython's rule.
    gc.collect()
    before = obs.runtime_totals()
    keep = _burst(n)
    grew = obs.runtime_growth(before)
    del keep
    assert grew["gc_gen2_collections"] >= 1 and grew["gc_scheduled_collections"] == 0
    seen = {}

    def body():
        seen["threshold"] = gc.get_threshold()
        t0 = obs.runtime_totals()
        keep = _burst(n)
        seen["grew"] = obs.runtime_growth(t0)
        del keep

    _inside_job(monkeypatch, body)
    gc.collect()
    job = jm.submit(tiny_spec(n_pods=3))
    assert job.wait_done(120)
    state, result, err = job.result_view()
    assert state == "succeeded", err
    assert seen["threshold"] == (idle[0], idle[1], jobs_manager.GEN2_THRESHOLD_WHILE_RUNNING)
    assert seen["grew"]["gc_gen2_collections"] == 0
    assert result["runtime"]["gc_gen2_collections"] == 1
    assert result["runtime"]["gc_scheduled_collections"] == 1
    assert result["runtime"]["gc_scheduled_pause_s"] == result["runtime"]["gc_gen2_pause_s"] > 0
    _idle(jm)
    assert gc.get_threshold() == idle  # the interactive path keeps CPython's rule


def test_thresholds_stay_far_until_the_last_running_job_ends(monkeypatch):
    import threading

    idle = gc.get_threshold()
    gate, inside = threading.Event(), threading.Semaphore(0)

    def body():
        inside.release()
        assert gate.wait(60)

    _inside_job(monkeypatch, body)
    m = JobManager(workers=2, queue_limit=8)
    try:
        jobs = [m.submit(tiny_spec(n_pods=2)) for _ in range(2)]
        for _ in jobs:
            assert inside.acquire(timeout=120)
        assert gc.get_threshold()[2] == jobs_manager.GEN2_THRESHOLD_WHILE_RUNNING
        assert jobs_manager._old_gen_running == 2
        gate.set()
        for j in jobs:
            assert j.wait_done(120)
        _idle(m)
        assert gc.get_threshold() == idle
    finally:
        gate.set()
        m.shutdown(timeout=5)


def test_safety_net_reclaims_cycles_made_inside_a_long_job(jm, monkeypatch):
    """A job that never ends still gets a full collection: the far
    threshold is a threshold, not a switch."""
    monkeypatch.setattr(jobs_manager, "GEN2_THRESHOLD_WHILE_RUNNING", 3)
    seen = {}

    class Node:
        pass

    def body():
        a, b = Node(), Node()
        a.other, b.other = b, a  # garbage only a collector can free
        seen["ref"] = weakref.ref(a)
        del a, b
        t0 = obs.runtime_totals()
        keep = _burst(_burst_size())
        seen["alive_after_burst"] = seen["ref"]() is not None
        seen["grew"] = obs.runtime_growth(t0)
        del keep

    _inside_job(monkeypatch, body)
    job = jm.submit(tiny_spec(n_pods=3))
    assert job.wait_done(120)
    state, result, err = job.result_view()
    assert state == "succeeded", err
    assert seen["alive_after_burst"] is False
    assert seen["grew"]["gc_gen2_collections"] >= 1
    assert seen["grew"]["gc_scheduled_collections"] == 0
    # The job's account shows both: the net's and its own scheduled one.
    rt = result["runtime"]
    assert rt["gc_scheduled_collections"] == 1
    assert rt["gc_gen2_collections"] == 1 + seen["grew"]["gc_gen2_collections"]


@pytest.mark.parametrize("through", ["manager", "http"])
def test_a_collection_during_the_submit_is_in_the_jobs_runtime_block(
    server, monkeypatch, through
):
    real = jobs_manager._parse_job_spec

    def parse(doc, **kw):
        gc.collect()  # a full collection while the POST is handled
        return real(doc, **kw)

    monkeypatch.setattr(jobs_manager, "_parse_job_spec", parse)
    jm = server.di.job_manager
    if through == "http":
        status, doc = _req(server, "POST", "/api/v1/jobs", tiny_spec(n_pods=2))
        assert status == 202
        job = jm.get(doc["id"])
    else:
        job = jm.submit(tiny_spec(n_pods=2))
    assert job.wait_done(120)
    state, result, err = job.result_view()
    assert state == "succeeded", err
    rt = result["runtime"]
    assert rt["gc_scheduled_collections"] == 1
    assert rt["gc_gen2_collections"] >= 2  # the submit's and the scheduled one
    assert rt["gc_gen2_pause_s"] > rt["gc_scheduled_pause_s"] > 0


def test_failed_and_cancelled_jobs_collect_and_let_go_too(jm, monkeypatch):
    def boom(self, job):
        raise RuntimeError("boom")

    before = obs.runtime_totals()
    monkeypatch.setattr(JobManager, "_execute", boom)
    job = jm.submit(tiny_spec(n_pods=2))
    assert job.wait_done(60)
    state, result, err = job.result_view()
    assert state == "failed" and "boom" in err and result is None
    assert job.ops == [] and job.store is None
    assert obs.runtime_growth(before)["gc_scheduled_collections"] == 1
    queued = JobManager(workers=0, queue_limit=2)
    try:
        j = queued.submit(tiny_spec(n_pods=2))
        assert j.ops
        assert queued.cancel(j.id) == "cancelled"
        assert j.ops == [] and j.sim == {}
    finally:
        queued.shutdown(timeout=5)
