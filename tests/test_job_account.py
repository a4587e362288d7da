"""A job accounts for its own wall (PR 51): the result's ``account``
block — parts of one clock that share their end points, what no part
names (``unnamed_s``), what no child of ``jobs.run`` covers
(``run_self_s``), and the worker's CPU time against its wall
(``off_cpu_s``) — the stages of the job's end, the import's two bind
timers, and the metric files that read them all.  Counts and closure
only: nothing here is a speed."""

from __future__ import annotations

import json
import os
import sys
import time

import pytest

from ksim_tpu.jobs import JobClock, JobManager
from ksim_tpu.jobs import manager as jobs_manager
from ksim_tpu.obs import TRACE, TracePlane
from ksim_tpu.server import DIContainer, SimulatorServer
from tests.helpers import make_node, make_pod
from tests.test_jobs import device_spec, tiny_spec
from tests.test_obs_phases import _req, served_job  # noqa: F401 (a fixture)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SEQUENTIAL = (
    "submit_s", "queue_s", "journal_s", "run_s", "digest_s", "document_s",
    "release_s", "collect_s",
)
ACCOUNT_KEYS = set(SEQUENTIAL) | {
    "wall_s", "build_s", "unnamed_s", "run_self_s", "off_cpu_s",
    "worker_cpu_s", "process_cpu_s",
}

NEW_METRICS = (
    "job_queue_s_per_job", "job_run_self_s_per_job", "job_digest_s_per_job",
    "job_document_s_per_job", "job_release_s_per_job", "job_unnamed_s_per_job",
    "job_off_cpu_s_per_job", "lower_parse_s_per_job", "lower_warm_s_per_job",
    "lower_tensors_s_per_job", "lower_tensors_state_s_per_job",
    "lower_tensors_interpod_s_per_job", "lower_tensors_ranks_s_per_job",
    "lower_tensors_statics_s_per_job", "bind_hooks_ms", "bind_cpu_ms",
)


@pytest.fixture()
def jm():
    m = JobManager(workers=1, queue_limit=8)
    yield m
    m.shutdown(timeout=5)


def _run(m: JobManager, spec: dict) -> dict:
    job = m.submit(spec)
    assert job.wait_done(300)
    state, result, err = job.result_view()
    assert state == "succeeded", err
    return result


def _closes(account: dict) -> None:
    assert set(account) == ACCOUNT_KEYS
    parts = sum(account[k] for k in SEQUENTIAL) + account["unnamed_s"]
    assert parts == pytest.approx(account["wall_s"], abs=1e-3)
    assert account["unnamed_s"] >= 0 and account["run_self_s"] >= 0
    assert all(account[k] >= 0 for k in SEQUENTIAL)
    assert 0 <= account["build_s"] <= account["run_s"]
    assert account["run_self_s"] <= account["run_s"]


# -- (a) every succeeded job's account closes ------------------------------------


def test_a_served_jobs_account_has_every_key_and_closes(served_job):
    result, _trace = served_job
    account = result["account"]
    _closes(account)
    # The handler's clock is the job's: the account starts where the
    # submit block starts, and the queue hand-off lies before the 202.
    assert account["submit_s"] <= result["submit"]["total_s"] + 1e-6
    assert account["journal_s"] == 0  # no journal configured
    assert account["wall_s"] >= account["run_s"] > 0
    # The named parts leave milliseconds, not a stretch of the job.
    assert account["unnamed_s"] < 0.05


@pytest.mark.parametrize("device", [False, True], ids=["per_pass", "device"])
def test_a_job_no_handler_submitted_accounts_from_its_submit_call(jm, device):
    spec = device_spec(n_nodes=8, n_events=40) if device else tiny_spec(n_pods=3)
    result = _run(jm, spec)
    _closes(result["account"])
    assert "submit" not in result


def test_a_journaled_jobs_running_record_is_journal_s(tmp_path):
    m = JobManager(workers=1, queue_limit=4, jobs_dir=str(tmp_path))
    try:
        result = _run(m, tiny_spec(n_pods=2))
    finally:
        m.shutdown(timeout=5)
    _closes(result["account"])
    assert result["account"]["journal_s"] > 0


def test_the_clock_reads_nothing_it_was_not_given():
    clock = JobClock()
    assert clock.seconds() is None  # no handler: at once, no wait
    assert clock.between("journal0", "journal1") == 0.0
    clock.claimed()
    clock.at("run0")
    clock.at("run1")
    account = clock.account()
    assert set(account) == ACCOUNT_KEYS
    assert account["queue_s"] >= 0 and account["digest_s"] == 0.0
    parts = sum(account[k] for k in SEQUENTIAL) + account["unnamed_s"]
    assert parts == pytest.approx(account["wall_s"], abs=1e-5)


# -- (b) the closure readings see what they exist to see -------------------------


def _sleeper(real, seconds: float):
    def wrapped(*a, **kw):
        time.sleep(seconds)
        return real(*a, **kw)

    return wrapped


@pytest.mark.parametrize(
    "where", ["control", "between_stretches", "execute_outside_children", "inside_snapshot"]
)
def test_a_sleep_lands_in_the_reading_that_exists_for_it(jm, monkeypatch, where):
    """50 ms between the collection and the sealing (no named stretch):
    ``unnamed_s``.  50 ms in ``_execute`` outside every child:
    ``run_self_s``.  50 ms inside ``jobs.run.snapshot``: neither."""
    from ksim_tpu.state import snapshot as snapshot_mod

    spec = tiny_spec(n_pods=2)
    spec["spec"]["simulator"] = {
        "initialSnapshot": {"nodes": [make_node("s0", cpu="4")], "pods": []}
    }
    if where == "between_stretches":
        monkeypatch.setattr(
            jobs_manager, "runtime_growth", _sleeper(jobs_manager.runtime_growth, 0.05)
        )
    elif where == "execute_outside_children":
        monkeypatch.setattr(
            jobs_manager, "_snapshot_counts",
            _sleeper(jobs_manager._snapshot_counts, 0.05),
        )
    elif where == "inside_snapshot":
        monkeypatch.setattr(
            snapshot_mod.SnapshotService, "load",
            _sleeper(snapshot_mod.SnapshotService.load, 0.05),
        )
    account = _run(jm, spec)["account"]
    _closes(account)
    if where == "between_stretches":
        assert account["unnamed_s"] >= 0.045
    else:
        assert account["unnamed_s"] < 0.02
    if where == "execute_outside_children":
        assert account["run_self_s"] >= 0.045
    elif where == "inside_snapshot":
        # The stage covers the sleep: a child of jobs.run, not its own.
        assert account["run_s"] >= 0.045
        assert account["run_self_s"] < account["run_s"] - 0.045 + 1e-3


def test_a_sleep_before_the_run_is_unnamed_too(jm, monkeypatch):
    """Between the claim (``queue_s`` ends) and ``jobs.run``."""
    monkeypatch.setattr(
        JobManager, "_journal_state",
        _sleeper(JobManager._journal_state, 0.05),
    )
    account = _run(jm, tiny_spec(n_pods=2))["account"]
    _closes(account)
    assert account["unnamed_s"] >= 0.045


def test_watch_sums_direct_children_of_every_kind_once():
    """Spans, laps and stages at the watching span's own level count;
    what nests deeper, what closes inside an open stage of that level,
    and another thread's records do not."""
    import threading

    p = TracePlane()
    p.enable()
    with p.span("jobs.run") as run:
        run.watch()
        with p.stage("jobs.run.snapshot"):
            time.sleep(0.01)
            with p.span("service.gc"):  # inside the stage: the stage's
                time.sleep(0.01)
        with p.span("replay.lower") as lo:
            lo.lap("replay.lower.parse")  # deeper: the child's
            p.stage("replay.lower.walk_order")
            time.sleep(0.01)
        t = threading.Thread(target=lambda: p.span("replay.pack").__enter__().__exit__(None, None, None))
        t.start()
        t.join()
        p.stage("replay.reconcile.effects")
        time.sleep(0.01)
        p.stage_end()
        time.sleep(0.02)  # jobs.run's own
    totals = p.phase_totals()
    children = (
        totals["jobs.run.snapshot"][0] + totals["replay.lower"][0]
        + totals["replay.reconcile.effects"][0]
    )
    assert run.children_ns / 1e9 == pytest.approx(children, abs=1e-6)
    own = totals["jobs.run"][0] - run.children_ns / 1e9
    assert 0.02 <= own < 0.03 + 0.02
    assert p._watch is None
    # Nobody watching: the sum stays where it was.
    with p.span("jobs.run") as again:
        with p.span("replay.lower"):
            pass
    assert again.children_ns == 0


# -- (c) is in tests/test_obs_stages.py -------------------------------------------
# -- (d) a job that fails at its end, or is cancelled, carries no account ---------


def _no_open_stage(job) -> None:
    assert getattr(job.trace._tls, "stages", None) in (None, [])


@pytest.mark.parametrize("where", ["digest", "document"])
def test_a_job_that_fails_inside_the_result_document_ends_failed(jm, monkeypatch, where):
    from ksim_tpu.state.cluster import ClusterStore

    def boom(*a, **kw):
        raise RuntimeError("no document")

    seen = {}
    real = JobManager._result_doc

    def result_doc(self, job, res, runner):
        try:
            return real(self, job, res, runner)
        finally:
            # On the worker's thread, as the exception leaves.
            seen["stages"] = list(getattr(job.trace._tls, "stages", None) or ())
            seen["depth"] = getattr(job.trace._tls, "depth", 0)

    monkeypatch.setattr(JobManager, "_result_doc", result_doc)
    if where == "digest":
        monkeypatch.setattr(ClusterStore, "placements_digest", boom)
        spec = device_spec(n_nodes=8, n_events=40)
    else:
        monkeypatch.setattr(jobs_manager.Job, "trace_summary", boom)
        spec = tiny_spec(n_pods=2)
    job = jm.submit(spec)
    assert job.wait_done(300)
    state, result, err = job.result_view()
    assert state == "failed" and "no document" in err and result is None
    assert seen == {"stages": [], "depth": 0}
    assert job.ops == [] and job.store is None and job.runner is None
    hist = job.trace.snapshot()["histograms"]
    closed = "jobs.finish.digest" if where == "digest" else "jobs.finish.document"
    assert hist[closed]["count"] == 1  # the stage closed with the exception
    assert hist["jobs.finish.release"]["count"] == 1  # and the graph went


def test_a_cancelled_job_carries_no_account(jm, monkeypatch):
    real = JobManager._execute

    def execute(self, job):
        job.cancel.set()
        return real(self, job)

    monkeypatch.setattr(JobManager, "_execute", execute)
    job = jm.submit(tiny_spec(n_pods=3))
    assert job.wait_done(120)
    state, result, _err = job.result_view()
    assert state == "cancelled" and result is None
    assert job.ops == [] and job.store is None and job.runner is None
    hist = job.trace.snapshot()["histograms"]
    assert "jobs.finish.digest" not in hist
    assert hist["jobs.finish.release"]["count"] == 1
    assert job.trace._watch is None


# -- (e) a worker that sleeps is off the CPU --------------------------------------


def test_a_sleeping_worker_raises_off_cpu_and_not_worker_cpu(jm, monkeypatch):
    spec = tiny_spec(n_pods=2)
    base = _run(jm, spec)["account"]  # warm: imports, compiles
    base = _run(jm, spec)["account"]
    real = JobManager._execute

    def execute(self, job):
        time.sleep(0.1)
        return real(self, job)

    monkeypatch.setattr(JobManager, "_execute", execute)
    slept = _run(jm, spec)["account"]
    assert slept["off_cpu_s"] >= base["off_cpu_s"] + 0.09
    assert slept["off_cpu_s"] >= 0.09
    assert slept["worker_cpu_s"] < base["worker_cpu_s"] + 0.05
    assert slept["run_self_s"] >= 0.09  # and it is jobs.run's own
    for account in (base, slept):
        assert account["worker_cpu_s"] + account["off_cpu_s"] <= account["wall_s"] + 1e-3
        assert account["process_cpu_s"] >= account["worker_cpu_s"] - 1e-3


# -- (f) the metric files read what a real job and a real import carry -----------


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _spec(name: str) -> dict:
    with open(os.path.join(ROOT, "benchmark", "metrics", f"{name}.json")) as f:
        return json.load(f)


def _readers():
    """The benchmark's own reader kinds (``benchmark/readers.py``)."""
    sys.path.insert(0, os.path.join(ROOT, "benchmark"))
    try:
        import readers
    finally:
        sys.path.pop(0)
    return readers


def _listed() -> list:
    bench = _bench()
    ours = [m for m in bench["per_layer"] if m["name"] in NEW_METRICS]
    assert bench["per_layer"][-len(ours):] == ours  # appended, in one block
    return ours


def test_every_new_metric_file_is_listed_with_its_cells():
    bench = _bench()
    ours = _listed()
    assert ours, "no metric of this PR is listed"
    cells = {w["name"] for w in bench["workloads"]}
    reports = {e["name"]: set(e.get("workloads") or cells) for e in bench["end_to_end"]}
    kinds = ("job_result", "metrics_timer")
    for m in ours:
        spec = _spec(m["name"])
        assert spec["name"] == m["name"] and spec["kind"] in kinds, m["name"]
        assert m["better"] == "lower" and m["workloads"], m["name"]
        assert set(m["workloads"]) <= reports[m["moves"]], m["name"]
        assert m["source"] == "program_span", m["name"]
    # No file of this PR that BENCHMARK.json does not list.
    listed = {m["name"] for m in bench["per_layer"]} | {e["name"] for e in bench["end_to_end"]}
    on_disk = {f[:-5] for f in os.listdir(os.path.join(ROOT, "benchmark", "metrics"))}
    assert on_disk <= listed


def test_every_job_result_path_resolves_in_a_real_served_job(served_job):
    result, _trace = served_job
    readers = _readers()
    read = 0
    for m in _listed():
        spec = _spec(m["name"])
        if spec["kind"] != "job_result":
            continue
        if spec["path"] == ["phases", "replay.lower.warm"]:
            continue  # a job of one window prelowers nothing: below
        # Through the reader kind the harness will use, on this one job.
        value = readers.KINDS[spec["kind"]]({"requests": [{"doc": result}]}, spec)
        assert value is not None and value >= 0, m["name"]
        read += 1
    assert read >= 10


def test_a_job_of_several_windows_records_the_warm_lap(jm):
    result = _run(jm, device_spec(n_nodes=30, n_events=400))
    assert result["latency"]["replay.lower"]["count"] >= 2
    assert result["phases"]["replay.lower.warm"] >= 0
    for seam in ("state", "interpod", "ranks", "statics"):
        assert result["phases"][f"replay.lower.tensors.{seam}"] >= 0, seam


@pytest.fixture(scope="module")
def import_metrics():
    """``/api/v1/metrics`` after one served import of a few pending pods."""
    di = DIContainer(start_scheduler=True)
    srv = SimulatorServer(di, port=0).start()
    try:
        snapshot = {
            "nodes": [make_node(f"n{i}", cpu="4") for i in range(3)],
            "pods": [make_pod(f"p{i}", cpu="100m") for i in range(5)],
        }
        status, _ = _req(srv, "POST", "/api/v1/import", snapshot)
        assert status == 200
        end = time.monotonic() + 120
        while True:
            _, doc = _req(srv, "GET", "/api/v1/export")
            if all((p.get("spec") or {}).get("nodeName") for p in doc["pods"]):
                break
            assert time.monotonic() < end
            time.sleep(0.05)
        _, metrics = _req(srv, "GET", "/api/v1/metrics")
        _, trace = _req(srv, "GET", "/api/v1/trace")
        yield metrics, trace
    finally:
        srv.shutdown_server()
        di.shutdown()


def test_the_two_bind_timers_resolve_in_a_real_imports_metrics(import_metrics):
    metrics, _trace = import_metrics
    timings = metrics["timings"]
    readers = _readers()
    read = 0
    for m in _listed():
        spec = _spec(m["name"])
        if spec["kind"] != "metrics_timer":
            continue
        assert m["workloads"] == ["import-1k_full"], m["name"]
        grown = readers.KINDS[spec["kind"]](
            {"requests": [{"metrics_before": {}, "metrics_after": metrics}]}, spec
        )
        assert grown == pytest.approx(
            timings[spec["timer"]]["total_seconds"] * spec["scale"]
        ), m["name"]
        assert timings[spec["timer"]]["count"] == timings["bind"]["count"], m["name"]
        read += 1
    assert read == 2
    # The bind closes: render, store and hooks are its parts, and the
    # thread's own CPU time cannot pass its wall by more than a tick.
    bind = timings["bind"]["total_seconds"]
    parts = sum(
        timings[t]["total_seconds"] for t in ("render", "bind_store", "bind_hooks")
    )
    assert 0 < parts <= bind + 1e-4
    assert timings["bind_cpu"]["total_seconds"] <= bind + 0.02


def test_the_result_handler_has_its_span_on_the_global_plane(monkeypatch):
    di = DIContainer()
    srv = SimulatorServer(di, port=0).start()
    was_active = TRACE.active
    TRACE.enable()
    try:
        status, job = _req(srv, "POST", "/api/v1/jobs", tiny_spec(n_pods=1))
        assert status == 202
        end = time.monotonic() + 120
        while _req(srv, "GET", f"/api/v1/jobs/{job['id']}")[1]["state"] != "succeeded":
            assert time.monotonic() < end
            time.sleep(0.02)
        _, result = _req(srv, "GET", f"/api/v1/jobs/{job['id']}/result")
        assert result["account"]["wall_s"] > 0
        spans = [r for r in TRACE.ring_records() if r["name"] == "jobs.result"]
        assert len(spans) == 1 and spans[0]["args"]["job"] == job["id"]
        _, own = _req(srv, "GET", f"/api/v1/jobs/{job['id']}/trace")
        assert not any(e["name"] == "jobs.result" for e in own["traceEvents"])
        # The stages that close after the document took its latency
        # summary are on the operator's surface all the same.
        _, metrics = _req(srv, "GET", "/api/v1/metrics")
        hist = metrics["jobs"]["jobs"][job["id"]]["trace"]["histograms"]
        for name in ("jobs.finish.digest", "jobs.finish.document", "jobs.finish.release"):
            assert hist[name]["count"] == 1, name
        assert "jobs.finish.document" not in result["latency"]
        assert not any(e["name"].startswith("jobs.finish") for e in own["traceEvents"])
    finally:
        if not was_active:
            TRACE.disable()
            TRACE._user_disabled = False
        TRACE.reset()
        srv.shutdown_server()
        di.shutdown()
