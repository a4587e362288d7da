"""Tenant job plane (ksim_tpu/jobs + /api/v1/jobs): lifecycle over
HTTP, bounded-queue backpressure, SSE progress streaming, cancel-mid-
segment rollback, the shared compile cache, and per-tenant fault
containment (slow-marked; `make jobs` / `make faults` run it)."""

from __future__ import annotations

import http.client
import json
import time

import pytest

from ksim_tpu.engine.compilecache import COMPILE_CACHE
from ksim_tpu.jobs import JobManager, JobQueueFull, parse_job_faults
from ksim_tpu.scenario import (
    churn_scenario,
    operations_from_spec,
    spec_from_operations,
)
from ksim_tpu.scenario.spec import ScenarioSpecError
from ksim_tpu.server import DIContainer, SimulatorServer
from tests.helpers import make_node, make_pod

# The locked 6k churn prefix (repo CLAUDE.md).
LOCK_6K = (2524, 471)


def tiny_spec(n_pods: int = 3, *, priority: int = 0) -> dict:
    ops = [
        {"step": 0, "createOperation": {"object": make_node(f"n{i}", cpu="4")}}
        for i in range(2)
    ]
    ops += [
        {"step": i + 1, "createOperation": {"object": make_pod(f"p{i}", cpu="100m")}}
        for i in range(n_pods)
    ]
    return {"spec": {"priority": priority, "scenario": {"operations": ops}}}


def device_spec(
    seed: int = 7, n_nodes: int = 30, n_events: int = 200, **sim_extra
) -> dict:
    """A small in-vocabulary churn stream as a device-replay job doc."""
    ops = list(
        churn_scenario(seed, n_nodes=n_nodes, n_events=n_events, ops_per_step=20)
    )
    sim = {"deviceReplay": True, "podBucketMin": 64, **sim_extra}
    return {"spec": {"simulator": sim, "scenario": spec_from_operations(ops)}}


# ---------------------------------------------------------------------------
# HTTP plumbing (the test_server.py idiom)
# ---------------------------------------------------------------------------


@pytest.fixture()
def server():
    di = DIContainer()
    srv = SimulatorServer(di, port=0).start()
    yield srv
    srv.shutdown_server()
    di.shutdown()


def _conn(srv):
    return http.client.HTTPConnection("127.0.0.1", srv.port, timeout=30)


def _req(srv, method, path, body=None):
    c = _conn(srv)
    c.request(
        method,
        path,
        json.dumps(body) if body is not None else None,
        {"Content-Type": "application/json"},
    )
    r = c.getresponse()
    data = r.read()
    c.close()
    return r.status, json.loads(data) if data else None


def _wait_state(srv, job_id, states, deadline_s=60.0):
    end = time.monotonic() + deadline_s
    while time.monotonic() < end:
        status, body = _req(srv, "GET", f"/api/v1/jobs/{job_id}")
        assert status == 200
        if body["state"] in states:
            return body
        time.sleep(0.05)
    raise AssertionError(f"job {job_id} never reached {states}")


# ---------------------------------------------------------------------------
# Lifecycle over HTTP
# ---------------------------------------------------------------------------


def test_job_lifecycle_over_http(server):
    """submit -> status -> result round-trip, plus the list and the
    per-job trace endpoint (every record job-tagged)."""
    status, job = _req(server, "POST", "/api/v1/jobs", tiny_spec())
    assert status == 202
    jid = job["id"]
    assert job["state"] in ("queued", "running")
    # Result before completion may 409 (depending on scheduling) — the
    # status endpoint always answers.
    final = _wait_state(server, jid, {"succeeded", "failed"})
    assert final["state"] == "succeeded", final
    assert final["progress"]["steps_done"] == final["progress"]["steps_total"] == 4
    # Fleet fields ride along even solo: no owner, no lease (the keys
    # are always present so clients need no feature detection).
    assert final["owner"] is None and final["lease"] is None

    status, res = _req(server, "GET", f"/api/v1/jobs/{jid}/result")
    assert status == 200
    assert res["result"]["podsScheduled"] == 3
    assert res["result"]["unschedulableAttempts"] == 0
    assert res["latency"]["runner.step"]["count"] == 4
    assert res["latency"]["runner.step"]["p99_seconds"] >= res["latency"][
        "runner.step"
    ]["p50_seconds"]

    status, listing = _req(server, "GET", "/api/v1/jobs")
    assert status == 200
    assert any(j["id"] == jid for j in listing["items"])

    # The JOB's private ring as Chrome trace JSON — isolation visible.
    status, doc = _req(server, "GET", f"/api/v1/jobs/{jid}/trace")
    assert status == 200
    spans = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert {"jobs.run", "runner.step", "service.schedule"} <= {
        e["name"] for e in spans
    }
    for e in spans:
        assert e["args"]["job"] == jid

    # Unknown id: 404 everywhere.
    status, _ = _req(server, "GET", "/api/v1/jobs/nope")
    assert status == 404
    status, _ = _req(server, "DELETE", "/api/v1/jobs/nope")
    assert status == 404


def test_job_bad_specs_rejected(server):
    status, body = _req(server, "POST", "/api/v1/jobs", {"spec": {}})
    assert status == 400
    status, body = _req(
        server,
        "POST",
        "/api/v1/jobs",
        {"spec": {"scenario": {"operations": []}, "initialSnapshotPath": "/etc/x"}},
    )
    assert status == 400
    assert "not allowed" in body["message"]
    # File paths are refused in the simulator block too.
    status, body = _req(
        server,
        "POST",
        "/api/v1/jobs",
        {
            "spec": {
                "simulator": {"initialSnapshotPath": "/etc/passwd"},
                "scenario": {"operations": []},
            }
        },
    )
    assert status == 400


def test_job_queue_full_returns_429(monkeypatch):
    """A saturated bounded queue answers 429, and the queued job can be
    cancelled (immediately terminal) via DELETE."""
    monkeypatch.setenv("KSIM_JOBS_WORKERS", "0")  # accept, never run
    monkeypatch.setenv("KSIM_JOBS_QUEUE", "1")
    di = DIContainer()
    srv = SimulatorServer(di, port=0).start()
    try:
        status, first = _req(srv, "POST", "/api/v1/jobs", tiny_spec())
        assert status == 202 and first["state"] == "queued"
        status, body = _req(srv, "POST", "/api/v1/jobs", tiny_spec())
        assert status == 429
        assert "full" in body["message"]
        # Queue-full evidence in the merged metrics document.
        status, m = _req(srv, "GET", "/api/v1/metrics")
        assert m["jobs"]["queue"] == {
            "depth": 1, "capacity": 1, "submitted": 1, "rejected": 1,
            "bypass_pops": 0,
        }
        assert m["jobs"]["workers"] == {"pool": 0, "active": 0}
        # Cancel the queued job: immediate terminal state.
        status, out = _req(srv, "DELETE", f"/api/v1/jobs/{first['id']}")
        assert status == 200 and out["state"] == "cancelled"
        status, st = _req(srv, "GET", f"/api/v1/jobs/{first['id']}")
        assert st["state"] == "cancelled"
    finally:
        srv.shutdown_server()
        di.shutdown()


def test_metrics_jobs_section_shape(server):
    """GET /api/v1/metrics carries the jobs section without breaking
    the existing merged-document shape — empty before the job plane is
    ever used, populated after."""
    status, m = _req(server, "GET", "/api/v1/metrics")
    assert status == 200
    assert set(m) >= {"counters", "timings", "trace", "faults", "jobs", "process"}
    assert set(m["process"]) >= {"role", "worker_id", "pid", "started_at", "uptime_s"}
    assert m["jobs"]["workers"]["pool"] == 0 and m["jobs"]["jobs"] == {}
    status, job = _req(server, "POST", "/api/v1/jobs", tiny_spec())
    assert status == 202
    _wait_state(server, job["id"], {"succeeded", "failed"})
    status, m = _req(server, "GET", "/api/v1/metrics")
    assert m["jobs"]["workers"]["pool"] >= 1
    jm_entry = m["jobs"]["jobs"][job["id"]]
    assert jm_entry["state"] == "succeeded"
    # The per-job plane snapshot rides along: private histograms.
    assert jm_entry["trace"]["histograms"]["runner.step"]["count"] == 4
    # compile_cache is a first-class provider section (process-wide),
    # including the AOT prewarm counters (startup + speculative rescan).
    assert "compile_cache" in m
    assert set(m["compile_cache"]) >= {
        "hits", "misses", "shared_rungs", "disk_prewarmed",
        "disk_speculative",
    }
    # Solo manager: no fleet section (it appears only under a role).
    assert "fleet" not in m["jobs"]


def test_fleet_status_and_metrics_over_http(tmp_path, monkeypatch):
    """Satellite: /api/v1/jobs/<id> carries the owner worker id and the
    lease age, and /api/v1/metrics the per-worker fleet counters, when
    the server runs as the fleet's front door."""
    monkeypatch.setenv("KSIM_JOBS_DIR", str(tmp_path))
    monkeypatch.setenv("KSIM_WORKERS_ROLE", "frontdoor")
    monkeypatch.setenv("KSIM_WORKER_ID", "fd")
    monkeypatch.setenv("KSIM_WORKERS_POLL_S", "0.1")
    di = DIContainer()
    srv = SimulatorServer(di, port=0).start()
    wk = JobManager(
        workers=1, queue_limit=8, jobs_dir=str(tmp_path),
        role="worker", worker_id="w1", lease_s=3.0, poll_s=0.1,
    )
    try:
        status, job = _req(srv, "POST", "/api/v1/jobs", tiny_spec())
        assert status == 202
        final = _wait_state(srv, job["id"], {"succeeded", "failed"})
        assert final["state"] == "succeeded", final
        assert final["owner"] == "w1"
        assert set(final["lease"]) == {"epoch", "age"}
        assert final["lease"]["epoch"] == 1
        assert final["lease"]["age"] >= 0
        status, m = _req(srv, "GET", "/api/v1/metrics")
        assert status == 200
        fleet = m["jobs"]["fleet"]
        assert fleet["role"] == "frontdoor" and fleet["worker_id"] == "fd"
        assert set(fleet["workers"]["w1"]) == {
            "claims", "takeovers", "renews", "expired",
        }
        assert fleet["workers"]["w1"]["claims"] == 1
        assert fleet["workers"]["w1"]["takeovers"] == 0
    finally:
        wk.shutdown()
        srv.shutdown_server()
        di.shutdown()


# ---------------------------------------------------------------------------
# SSE stream
# ---------------------------------------------------------------------------


def _read_sse(srv, path, deadline_s=60.0):
    """Collect all SSE data frames until the server ends the stream."""
    c = _conn(srv)
    c.request("GET", path, headers={"Accept": "text/event-stream"})
    resp = c.getresponse()
    assert resp.status == 200
    assert resp.getheader("Content-Type") == "text/event-stream"
    events = []
    end = time.monotonic() + deadline_s
    while time.monotonic() < end:
        line = resp.readline()
        if not line:
            break  # stream closed by the server
        line = line.strip()
        if line.startswith(b"data: "):
            events.append(json.loads(line[len(b"data: "):]))
    c.close()
    return events


def test_sse_stream_carries_monotonic_progress(server):
    status, job = _req(server, "POST", "/api/v1/jobs", tiny_spec(n_pods=4))
    assert status == 202
    events = _read_sse(server, f"/api/v1/jobs/{job['id']}/events")
    assert events, "empty SSE stream"
    # Sequence numbers are the replayable event-log order.
    assert [e["seq"] for e in events] == sorted(e["seq"] for e in events)
    states = [e["state"] for e in events if e["event"] == "state"]
    assert states[0] == "queued" and states[-1] == "succeeded"
    progress = [e for e in events if e["event"] == "progress"]
    assert progress, "no progress events in the stream"
    done = [e["steps_done"] for e in progress]
    assert done == sorted(done), f"progress regressed: {done}"
    assert done[-1] == progress[-1]["steps_total"] == 5
    # Late joiner replays the full history (the log, not a live tap).
    again = _read_sse(server, f"/api/v1/jobs/{job['id']}/events")
    assert [e["seq"] for e in again] == [e["seq"] for e in events]


# ---------------------------------------------------------------------------
# Cancellation
# ---------------------------------------------------------------------------


def test_cancel_mid_segment_rolls_back_store():
    """Cancel landing INSIDE a device segment's reconcile aborts the
    store transaction: the job ends cancelled and its store is
    byte-identical to the segment's start (here: empty — the hang sits
    in the FIRST segment).  The reconcile hang (the job's private
    fault plane) pins the timing deterministically."""
    jm = JobManager(
        workers=1,
        queue_limit=4,
        fault_spec="0:replay.reconcile=hang:1.5:1",
    )
    try:
        job = jm.submit(device_spec(n_events=200))
        # The hang fires fault.fired on the JOB's plane before sleeping;
        # it is forwarded into the job's event log — our cue that the
        # reconcile transaction is open right now.
        end = time.monotonic() + 120
        idx, seen = 0, False
        while time.monotonic() < end and not seen:
            evs, idx, done = job.events_since(idx, timeout=0.5)
            seen = any(
                e.get("event") == "trace" and e.get("name") == "fault.fired"
                for e in evs
            )
            if done:
                break
        assert seen, "reconcile hang never fired — wrong fault wiring"
        # The handle is taken while the job runs: a terminal job lets
        # its store go (docs/jobs.md "What a finished job keeps").
        store = job.store
        assert store is not None
        assert jm.cancel(job.id) in ("running", "cancelled")
        assert job.wait_done(60)
        state, result, err = job.result_view()
        assert state == "cancelled", (state, err)
        assert job.store is None and job.runner is None
        # Store consistency: the rolled-back first segment left nothing.
        assert store.list("pods") == []
        assert store.list("nodes") == []
    finally:
        jm.shutdown(timeout=5)


def test_cancel_running_job_between_steps():
    """A per-pass (host path) job cancels at the next step boundary."""
    jm = JobManager(workers=1, queue_limit=4)
    try:
        # Enough steps that cancellation lands mid-run.
        job = jm.submit(tiny_spec(n_pods=40))
        assert job.wait_done(0.0) is False
        end = time.monotonic() + 60
        while time.monotonic() < end and job.status()["state"] == "queued":
            time.sleep(0.02)
        jm.cancel(job.id)
        assert job.wait_done(60)
        assert job.status()["state"] in ("cancelled", "succeeded")
    finally:
        jm.shutdown(timeout=5)


def test_cancel_running_fleet_job_lands_at_round_boundary():
    """Round 16: DELETE on a running FLEET job now cancels — the cohort
    checks the parent run's flag once per dispatch round, so the cancel
    lands at the next lane dispatch boundary with every lane's store at
    a committed segment (no torn transactions)."""
    jm = JobManager(workers=1, queue_limit=4)
    try:
        job = jm.submit(device_spec(n_events=830, fleet=2))
        # The first progress event = the first committed cohort round:
        # the fleet is mid-run with more rounds to go.
        end = time.monotonic() + 120
        idx, seen = 0, False
        while time.monotonic() < end and not seen:
            evs, idx, done = job.events_since(idx, timeout=0.5)
            seen = any(e.get("event") == "progress" for e in evs)
            if done:
                break
        assert seen, "fleet job never committed a round"
        runner = job.runner  # taken while it runs: a terminal job lets it go
        assert runner is not None
        assert jm.cancel(job.id) in ("running", "cancelled")
        assert job.wait_done(120)
        state = job.status()["state"]
        # "succeeded" only if the last round was already in flight when
        # the flag flipped — the boundary semantics are pinned
        # deterministically at the runner layer (test_replay_device).
        assert state in ("cancelled", "succeeded")
        if state == "cancelled" and runner.fleet_lanes:
            for ln in runner.fleet_lanes:
                assert ln.runner.store._txn is None
    finally:
        jm.shutdown(timeout=5)


# ---------------------------------------------------------------------------
# Shared compile cache
# ---------------------------------------------------------------------------


def test_two_same_rung_jobs_compile_once():
    """Two identical device-replay jobs share every shape rung: the
    process-wide compile cache shows rungs owned by BOTH jobs with
    exactly one compile each (misses bounded by distinct rungs, hits
    from the second tenant)."""
    COMPILE_CACHE.reset()
    jm = JobManager(workers=2, queue_limit=4)
    try:
        doc = device_spec(n_events=160)
        j1 = jm.submit(doc)
        j2 = jm.submit(doc)
        assert jm.join(timeout=300)
        for j in (j1, j2):
            state, result, err = j.result_view()
            assert state == "succeeded", (j.id, state, err)
            assert result["replay"]["device_round_trips"] >= 1, result["replay"]
            # The result names the backend that ran the segments.
            assert result["replay"]["platform"] == "cpu", result["replay"]
            assert result["replay"]["device_kind"]
            assert result["replay"]["device_count"] >= 1
            # Dispatch latency comes from the job's own trace plane.
            assert result["latency"]["replay.dispatch"]["p50_seconds"] > 0
        s1 = j1.result_view()[1]["result"]
        s2 = j2.result_view()[1]["result"]
        assert (s1["podsScheduled"], s1["unschedulableAttempts"]) == (
            s2["podsScheduled"],
            s2["unschedulableAttempts"],
        )
        snap = COMPILE_CACHE.snapshot()
        assert snap["misses"] >= 1 and snap["hits"] >= 1, snap
        # The tenancy claim: >= 1 rung served BOTH jobs off ONE compile.
        assert snap["shared_rungs"] >= 1, snap
        assert snap["shared_single_compile_rungs"] >= 1, snap
        assert snap["max_owners_per_rung"] == 2, snap
        assert snap["aborts"] == 0, snap
    finally:
        jm.shutdown(timeout=5)


# ---------------------------------------------------------------------------
# Spec plumbing & queue semantics (unit)
# ---------------------------------------------------------------------------


def test_spec_from_operations_roundtrip():
    ops = list(churn_scenario(3, n_nodes=15, n_events=60, ops_per_step=10))
    assert operations_from_spec(spec_from_operations(ops)) == ops


def test_parse_job_faults_refusals():
    with pytest.raises(ValueError, match="expected"):
        parse_job_faults("replay.dispatch=always")  # no ordinal
    with pytest.raises(ValueError, match="job-plane site"):
        parse_job_faults("0:service.schedule=always")
    planes = parse_job_faults("1:replay.dispatch=call:1;1:jobs.run=first:1")
    assert set(planes) == {1}


def test_queue_priority_then_fifo():
    from ksim_tpu.jobs import JobQueue

    q = JobQueue(limit=10)
    q.put("a", priority=0)
    q.put("b", priority=5)
    q.put("c", priority=0)
    assert [q.get(0.1) for _ in range(3)] == ["b", "a", "c"]
    q2 = JobQueue(limit=1)
    q2.put("x")
    with pytest.raises(JobQueueFull):
        q2.put("y")
    assert q2.stats()["rejected"] == 1


def test_rejected_submission_does_not_consume_fault_ordinal():
    """A queue-full refusal must not shift which job an armed
    KSIM_JOBS_FAULTS ordinal lands on (a silently-shifted schedule is
    a vacuously-green chaos run)."""
    jm = JobManager(
        workers=0,
        queue_limit=1,
        fault_spec="1:replay.dispatch=always@device",
    )
    try:
        first = jm.submit(tiny_spec())
        assert first.ordinal == 0 and first.faults is None
        with pytest.raises(JobQueueFull):
            jm.submit(tiny_spec())  # refused: ordinal 1 NOT consumed
        # Drain the slot (no workers) and resubmit: the retry — the
        # first job that can actually run next — gets ordinal 1 and
        # the armed plane with it.
        assert jm.queue.get(0.1) is first
        second = jm.submit(tiny_spec())
        assert second.ordinal == 1
        assert second.faults is not None
    finally:
        jm.shutdown(timeout=1)


# ---------------------------------------------------------------------------
# Per-tenant admission (round 16): quotas + rate limits
# ---------------------------------------------------------------------------


def test_tenant_quota_throttles_and_releases():
    """KSIM_JOBS_TENANT_MAX_ACTIVE bounds a tenant's NON-TERMINAL jobs;
    other tenants are unaffected, and a terminal job frees the slot."""
    from ksim_tpu.jobs import JobThrottled

    jm = JobManager(workers=0, queue_limit=8, tenant_max_active=1)
    try:
        first = jm.submit(tiny_spec(), tenant="acme")
        with pytest.raises(JobThrottled) as ei:
            jm.submit(tiny_spec(), tenant="acme")
        assert ei.value.retry_after > 0
        assert "KSIM_JOBS_TENANT_MAX_ACTIVE" in str(ei.value)
        jm.submit(tiny_spec(), tenant="umbrella")  # per-tenant, not global
        t = jm.snapshot()["tenants"]
        assert t["acme"]["admitted"] == 1 and t["acme"]["throttled"] == 1
        assert t["umbrella"]["admitted"] == 1 and t["umbrella"]["throttled"] == 0
        # A terminal job no longer counts against the quota.
        assert jm.cancel(first.id) == "cancelled"
        assert jm.submit(tiny_spec(), tenant="acme").status()["state"] == "queued"
    finally:
        jm.shutdown(timeout=1)


def test_tenant_rate_limit_token_bucket():
    """KSIM_JOBS_TENANT_RATE is a per-tenant token bucket (burst
    max(rate, 1)): a drained bucket throttles with retry_after = the
    time until the next token; buckets never bleed across tenants."""
    from ksim_tpu.jobs import JobThrottled

    jm = JobManager(workers=0, queue_limit=16, tenant_rate=0.001)
    try:
        jm.submit(tiny_spec(), tenant="acme")  # the burst token
        with pytest.raises(JobThrottled) as ei:
            jm.submit(tiny_spec(), tenant="acme")
        assert ei.value.retry_after > 1.0  # ~1000 s to the next token
        assert "KSIM_JOBS_TENANT_RATE" in str(ei.value)
        jm.submit(tiny_spec(), tenant="umbrella")
    finally:
        jm.shutdown(timeout=1)


def test_tenant_routing_header_wins_over_spec_then_default():
    """The HTTP layer's X-Ksim-Tenant (the ``tenant=`` kwarg) wins over
    ``spec.tenant``; absent both, jobs pool under ``default``."""
    jm = JobManager(workers=0, queue_limit=8)
    try:
        doc = tiny_spec()
        doc["spec"]["tenant"] = "spec-t"
        assert jm.submit(doc, tenant="header-t").tenant == "header-t"
        assert jm.submit(doc).tenant == "spec-t"
        assert jm.submit(tiny_spec()).tenant == "default"
        assert jm.submit(tiny_spec()).status()["tenant"] == "default"
    finally:
        jm.shutdown(timeout=1)


def test_throttled_submission_does_not_consume_fault_ordinal():
    """Same invariant as the queue-full refusal: a throttled tenant
    must not shift which job an armed KSIM_JOBS_FAULTS ordinal lands
    on."""
    from ksim_tpu.jobs import JobThrottled

    jm = JobManager(
        workers=0,
        queue_limit=8,
        tenant_max_active=1,
        fault_spec="1:replay.dispatch=always@device",
    )
    try:
        first = jm.submit(tiny_spec(), tenant="acme")
        assert first.ordinal == 0 and first.faults is None
        with pytest.raises(JobThrottled):
            jm.submit(tiny_spec(), tenant="acme")  # ordinal 1 NOT consumed
        second = jm.submit(tiny_spec(), tenant="umbrella")
        assert second.ordinal == 1
        assert second.faults is not None
    finally:
        jm.shutdown(timeout=1)


def test_tenant_throttle_http_429_with_retry_after(monkeypatch):
    """Over HTTP: a throttled tenant gets 429 + a whole-second
    Retry-After header, routed by X-Ksim-Tenant; the merged metrics
    document carries the per-tenant counters."""
    monkeypatch.setenv("KSIM_JOBS_WORKERS", "0")
    monkeypatch.setenv("KSIM_JOBS_TENANT_MAX_ACTIVE", "1")
    di = DIContainer()
    srv = SimulatorServer(di, port=0).start()
    try:
        def post(tenant=None):
            c = _conn(srv)
            headers = {"Content-Type": "application/json"}
            if tenant:
                headers["X-Ksim-Tenant"] = tenant
            c.request("POST", "/api/v1/jobs", json.dumps(tiny_spec()), headers)
            r = c.getresponse()
            body = json.loads(r.read())
            retry = r.getheader("Retry-After")
            c.close()
            return r.status, body, retry

        status, first, _ = post("acme")
        assert status == 202
        status, body, retry = post("acme")
        assert status == 429
        assert "KSIM_JOBS_TENANT_MAX_ACTIVE" in body["message"]
        assert retry is not None and int(retry) >= 1
        status, other, _ = post("umbrella")
        assert status == 202
        status, m = _req(srv, "GET", "/api/v1/metrics")
        t = m["jobs"]["tenants"]
        assert t["acme"]["admitted"] == 1 and t["acme"]["throttled"] == 1
        assert t["umbrella"]["throttled"] == 0
    finally:
        srv.shutdown_server()
        di.shutdown()


def test_fleet_job_with_armed_faults_or_config_refused():
    """The fleet runner cannot carry a private fault plane, a tenant
    schedulerConfig or an initialSnapshot — dropped-on-the-floor specs
    must refuse at submission, not succeed wrongly."""
    jm = JobManager(
        workers=0, queue_limit=4, fault_spec="0:replay.dispatch=always"
    )
    try:
        fleet_doc = {
            "spec": {
                "simulator": {"fleet": 2, "deviceReplay": True},
                "scenario": tiny_spec()["spec"]["scenario"],
            }
        }
        with pytest.raises(ScenarioSpecError, match="KSIM_JOBS_FAULTS"):
            jm.submit(fleet_doc)
        for field in ("schedulerConfig", "initialSnapshot"):
            doc = {
                "spec": {
                    "simulator": {"fleet": 2, field: {"x": 1}},
                    "scenario": tiny_spec()["spec"]["scenario"],
                }
            }
            with pytest.raises(ScenarioSpecError, match="not supported"):
                jm.submit(doc)
    finally:
        jm.shutdown(timeout=1)


def test_direct_submit_rejects_bad_documents():
    jm = JobManager(workers=0, queue_limit=4)
    try:
        with pytest.raises(ScenarioSpecError):
            jm.submit({"spec": {}})
        with pytest.raises(ScenarioSpecError):
            jm.submit("not a mapping")
        with pytest.raises(ScenarioSpecError):
            jm.submit({"operations": [], "scenarioResultFilePath": "/tmp/x"})
    finally:
        jm.shutdown(timeout=1)


# ---------------------------------------------------------------------------
# Per-tenant fault containment (the chaos matrix leg; slow)
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_job_fault_containment_6k_locked():
    """KSIM_JOBS_FAULTS arms ONE job's private plane: that job's device
    path degrades (breaker opens, per-pass fallback) while running
    CONCURRENTLY with a clean job — and BOTH land the locked 6k counts
    (2524/471).  The `make faults`/`make jobs` matrix runs this."""
    import jax

    prev_x64 = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", False)
    jm = JobManager(
        workers=2,
        queue_limit=4,
        fault_spec="0:replay.dispatch=always@device",
    )
    try:
        doc = {
            "spec": {
                "simulator": {
                    "deviceReplay": True,
                    "preemption": True,
                    "maxPodsPerPass": 1024,
                    "podBucketMin": 128,
                },
                "scenario": spec_from_operations(
                    list(
                        churn_scenario(
                            0, n_nodes=2000, n_events=6000, ops_per_step=100
                        )
                    )
                ),
            }
        }
        chaos = jm.submit(doc)
        clean = jm.submit(doc)
        assert jm.join(timeout=900)
        for j, label in ((chaos, "chaos"), (clean, "clean")):
            state, result, err = j.result_view()
            assert state == "succeeded", (label, state, err)
            counts = (
                result["result"]["podsScheduled"],
                result["result"]["unschedulableAttempts"],
            )
            assert counts == LOCK_6K, (label, counts)
        chaos_replay = chaos.result_view()[1]["replay"]
        clean_replay = clean.result_view()[1]["replay"]
        # The armed job degraded ALONE: its private plane fired, its
        # breaker opened, and it fell back to the host path...
        assert chaos.faults is not None
        assert chaos.faults.fired("replay.dispatch") >= 1
        assert chaos_replay["device_errors"] >= 1
        assert chaos_replay["breaker_tripped"] is True
        assert chaos_replay["device_steps"] == 0
        # ...while the concurrent clean job stayed on the device path.
        assert clean.faults is None
        assert clean_replay["device_errors"] == 0
        assert clean_replay["breaker_tripped"] is False
        assert clean_replay["device_steps"] > 0
    finally:
        jm.shutdown(timeout=5)
        jax.config.update("jax_enable_x64", prev_x64)


# ---------------------------------------------------------------------------
# Round 14: cost-aware admission (SJF + starvation bound)
# ---------------------------------------------------------------------------


def test_queue_sjf_within_priority_band():
    from ksim_tpu.jobs import JobQueue

    q = JobQueue(limit=10)
    q.put("big", cost=1000)
    q.put("small", cost=5)
    q.put("mid", cost=50)
    q.put("prio", priority=5, cost=9999)  # a higher band beats any cost
    assert [q.get(0.1) for _ in range(4)] == ["prio", "small", "mid", "big"]
    # cost=0 ties keep FIFO (the pre-round-14 special case).
    q.put("a"); q.put("b")
    assert [q.get(0.1), q.get(0.1)] == ["a", "b"]


def test_queue_starvation_bound():
    """A long job is overtaken at most max_bypass times, then pops
    regardless of cost — the SJF starvation bound, deterministically."""
    from ksim_tpu.jobs import JobQueue

    q = JobQueue(limit=0, max_bypass=2)
    q.put("long", cost=1000)
    q.put("s1", cost=1)
    assert q.get(0.1) == "s1"      # bypass 1
    q.put("s2", cost=1)
    assert q.get(0.1) == "s2"      # bypass 2
    q.put("s3", cost=1)
    assert q.get(0.1) == "long"    # the bound fires: cost ignored
    assert q.get(0.1) == "s3"
    assert q.stats()["bypass_pops"] == 1


def test_manager_submit_costs_queue_by_event_count(monkeypatch):
    """With no workers, submissions queue up; the pop order proves the
    manager passed the spec's event count as the cost."""
    jm = JobManager(workers=0, queue_limit=8)
    try:
        big = jm.submit(tiny_spec(10))
        small = jm.submit(tiny_spec(1))
        assert jm.queue.get(0.1) is small
        assert jm.queue.get(0.1) is big
    finally:
        jm.shutdown(timeout=1)


# ---------------------------------------------------------------------------
# Round 14: per-job resource bounds -> HTTP 413
# ---------------------------------------------------------------------------


def test_job_limits_refuse_oversized_specs():
    from ksim_tpu.jobs import JobLimitExceeded

    jm = JobManager(workers=0, queue_limit=8, max_job_events=5, max_job_nodes=0)
    try:
        with pytest.raises(JobLimitExceeded, match="KSIM_JOBS_MAX_EVENTS"):
            jm.submit(tiny_spec(10))
        # A refused submission consumes no ordinal and queues nothing.
        assert jm.queue.depth() == 0
        ok = jm.submit(tiny_spec(1))
        assert ok.ordinal == 0
    finally:
        jm.shutdown(timeout=1)
    jm2 = JobManager(workers=0, queue_limit=8, max_job_nodes=1)
    try:
        with pytest.raises(JobLimitExceeded, match="KSIM_JOBS_MAX_NODES"):
            jm2.submit(tiny_spec(1))  # tiny_spec creates 2 nodes
    finally:
        jm2.shutdown(timeout=1)


def test_job_limit_returns_413_over_http(monkeypatch):
    monkeypatch.setenv("KSIM_JOBS_MAX_EVENTS", "5")
    monkeypatch.setenv("KSIM_JOBS_WORKERS", "0")
    di = DIContainer()
    srv = SimulatorServer(di, port=0).start()
    try:
        status, body = _req(srv, "POST", "/api/v1/jobs", tiny_spec(10))
        assert status == 413
        assert "KSIM_JOBS_MAX_EVENTS" in body["message"]
        status, _ = _req(srv, "POST", "/api/v1/jobs", tiny_spec(1))
        assert status == 202
    finally:
        srv.shutdown_server()
        di.shutdown()


def test_job_trace_bound_refused_during_streaming_ingest(monkeypatch):
    """A trace-sourced spec over KSIM_JOBS_MAX_EVENTS is refused DURING
    streaming ingest (TraceBoundExceeded -> JobLimitExceeded): the
    refusal message carries both the env-var name and the early-stop
    marker, and nothing is queued."""
    from ksim_tpu.jobs import JobLimitExceeded

    monkeypatch.setenv("KSIM_TRACES_DIR", "tests/fixtures/traces")
    jm = JobManager(workers=0, queue_limit=8, max_job_events=5)
    try:
        with pytest.raises(JobLimitExceeded, match="KSIM_JOBS_MAX_EVENTS"):
            jm.submit(
                _trace_job(
                    name="borg_mini.jsonl", format="borg", nodes=4, opsPerStep=8
                )
            )
        with pytest.raises(JobLimitExceeded, match="ingest stopped early"):
            jm.submit(
                _trace_job(
                    name="borg_mini.jsonl", format="borg", nodes=4, opsPerStep=8
                )
            )
        assert jm.queue.depth() == 0
    finally:
        jm.shutdown(timeout=1)


# ---------------------------------------------------------------------------
# Round 14: trace-by-name submission + spec-armed chaos
# ---------------------------------------------------------------------------


def _trace_job(**trace):
    return {"spec": {"scenario": {"source": {"trace": trace}}}}


def test_job_submits_registered_trace_by_name(server, monkeypatch):
    monkeypatch.setenv("KSIM_TRACES_DIR", "tests/fixtures/traces")
    status, names = _req(server, "GET", "/api/v1/traces")
    assert status == 200
    assert "alibaba_batch_mini.csv" in [e["name"] for e in names["items"]]
    status, job = _req(
        server,
        "POST",
        "/api/v1/jobs",
        _trace_job(name="alibaba_batch_mini.csv", format="alibaba", nodes=4,
                   opsPerStep=8),
    )
    assert status == 202, job
    body = _wait_state(server, job["id"], {"succeeded", "failed"})
    assert body["state"] == "succeeded"
    status, result = _req(server, "GET", f"/api/v1/jobs/{job['id']}/result")
    assert status == 200
    assert result["result"]["eventsApplied"] > 24  # nodes + creates + deletes


def test_job_refuses_trace_paths_and_unregistered_names(server, monkeypatch):
    monkeypatch.setenv("KSIM_TRACES_DIR", "tests/fixtures/traces")
    status, body = _req(
        server, "POST", "/api/v1/jobs",
        _trace_job(path="/etc/passwd", format="borg"),
    )
    assert status == 400
    assert "registered" in body["message"]
    status, body = _req(
        server, "POST", "/api/v1/jobs",
        _trace_job(name="../../../etc/passwd", format="borg"),
    )
    assert status == 400
    status, body = _req(
        server, "POST", "/api/v1/jobs",
        _trace_job(name="nope.jsonl", format="borg"),
    )
    assert status == 400
    assert "no registered trace" in body["message"]


def test_spec_armed_faults_degrade_the_submitting_job_alone(server):
    """The chaos-native spec: a job arming its own jobs.run fault fails
    by itself while a concurrently submitted clean job succeeds."""
    chaotic = dict(tiny_spec(2))
    chaotic["spec"] = dict(chaotic["spec"], faults={"jobs.run": "always"})
    status, bad = _req(server, "POST", "/api/v1/jobs", chaotic)
    assert status == 202
    status, good = _req(server, "POST", "/api/v1/jobs", tiny_spec(2))
    assert status == 202
    bad_body = _wait_state(server, bad["id"], {"failed", "succeeded", "cancelled"})
    good_body = _wait_state(server, good["id"], {"failed", "succeeded", "cancelled"})
    assert bad_body["state"] == "failed"
    assert "InjectedFault" in bad_body["error"]
    assert good_body["state"] == "succeeded"


def test_spec_faults_refuse_non_job_sites(server):
    doc = dict(tiny_spec(1))
    doc["spec"] = dict(doc["spec"], faults={"service.schedule": "always"})
    status, body = _req(server, "POST", "/api/v1/jobs", doc)
    assert status == 400
    assert "job-plane site" in body["message"]


def test_malformed_jobs_faults_schedule_fails_at_construction():
    """An operator typo in a KSIM_JOBS_FAULTS SCHEDULE raises at
    JobManager construction (fail-fast), never later as a tenant-blamed
    400 with the chaos silently unarmed."""
    with pytest.raises(ValueError):
        JobManager(workers=0, queue_limit=2, fault_spec="0:jobs.run=bogus")


def test_spec_faults_schedule_smuggling_refused_over_http(server):
    doc = dict(tiny_spec(1))
    doc["spec"] = dict(
        doc["spec"], faults={"replay.dispatch": "always;service.schedule=always"}
    )
    status, body = _req(server, "POST", "/api/v1/jobs", doc)
    assert status == 400
    assert "one schedule per site" in body["message"]
