"""HTTP server integration: the reference's /api/v1 surface end-to-end
(reference simulator/server/server.go:44-54) — config-change -> schedule ->
export cycle, reset, and the streaming listwatchresources endpoint."""

from __future__ import annotations

import http.client
import json
import time

import pytest

from ksim_tpu.server import DIContainer, SimulatorServer
from tests.helpers import make_node, make_pod


@pytest.fixture()
def server():
    di = DIContainer()
    srv = SimulatorServer(di, port=0).start()  # ephemeral port
    yield srv
    srv.shutdown_server()
    di.shutdown()


def _conn(srv):
    return http.client.HTTPConnection("127.0.0.1", srv.port, timeout=10)


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


def _raw(srv, method, path, body=None, ctype="application/json"):
    c = _conn(srv)
    c.request(method, path, body, {"Content-Type": ctype})
    r = c.getresponse()
    data = r.read()
    c.close()
    return r.status, data.decode()


def test_full_cycle_over_http(server):
    di = server.di
    # Import a snapshot.
    snap = {
        "nodes": [make_node("n0", cpu="4", memory="8Gi")],
        "pods": [make_pod("p0", cpu="1", memory="1Gi")],
        "pvs": [], "pvcs": [], "storageClasses": [], "priorityClasses": [],
        "namespaces": [], "schedulerConfig": None,
    }
    status, _ = _req(server, "POST", "/api/v1/import", snap)
    assert status == 200

    # Apply a scheduler config (only profiles/extenders are taken).
    cfg = {
        "apiVersion": "kubescheduler.config.k8s.io/v1",
        "kind": "KubeSchedulerConfiguration",
        "profiles": [{"schedulerName": "my-scheduler"}],
    }
    status, _ = _req(server, "POST", "/api/v1/schedulerconfiguration", cfg)
    assert status == 202
    status, got = _req(server, "GET", "/api/v1/schedulerconfiguration")
    assert status == 200
    assert got["profiles"] == [{"schedulerName": "my-scheduler"}]

    # A bad config rolls back and returns 500.
    bad = {"profiles": [{"plugins": {"multiPoint": {"enabled": [{"name": "Nope"}]}}}]}
    status, _ = _req(server, "POST", "/api/v1/schedulerconfiguration", bad)
    assert status == 500
    _, got = _req(server, "GET", "/api/v1/schedulerconfiguration")
    assert got["profiles"] == [{"schedulerName": "my-scheduler"}]

    # Schedule the pending pod (profile renamed the scheduler, so address it).
    di.store.patch(
        "pods", "p0", "default",
        lambda o: o["spec"].__setitem__("schedulerName", "my-scheduler"),
    )
    placements = di.scheduler_service.schedule_pending()
    assert placements == {"default/p0": "n0"}

    # Export reflects the binding and the applied config.
    status, out = _req(server, "GET", "/api/v1/export")
    assert status == 200
    assert out["pods"][0]["spec"]["nodeName"] == "n0"
    assert out["schedulerConfig"]["profiles"] == [{"schedulerName": "my-scheduler"}]

    # Reset restores the boot-time (empty) cluster and default config.
    status, _ = _req(server, "PUT", "/api/v1/reset")
    assert status == 202
    status, out = _req(server, "GET", "/api/v1/export")
    assert out["nodes"] == [] and out["pods"] == []
    _, got = _req(server, "GET", "/api/v1/schedulerconfiguration")
    # Reset returns the scheme-defaulted document (reference
    # DefaultSchedulerConfig, scheduler/config/config.go:19-26).
    assert got["profiles"] == [{"schedulerName": "default-scheduler"}]
    assert got["kind"] == "KubeSchedulerConfiguration"


def test_extender_routes_present(server):
    status, body = _req(server, "POST", "/api/v1/extender/filter/0", {})
    assert status == 400  # no extenders configured
    status, _ = _req(server, "POST", "/api/v1/extender/nope/0", {})
    assert status == 404


def _read_events(resp, n, deadline=10.0):
    events = []
    end = time.monotonic() + deadline
    while len(events) < n and time.monotonic() < end:
        try:
            line = resp.readline()
        except TimeoutError:
            break
        if line.strip():
            events.append(json.loads(line))
    return events


def test_listwatch_stream(server):
    di = server.di
    di.store.create("nodes", make_node("n0"))
    c = _conn(server)
    c.request("GET", "/api/v1/listwatchresources")
    resp = c.getresponse()
    assert resp.status == 200
    # Initial LIST as ADDED.
    (ev,) = _read_events(resp, 1)
    assert ev["Kind"] == "nodes" and ev["EventType"] == "ADDED"
    assert ev["Obj"]["metadata"]["name"] == "n0"
    # Live event.
    di.store.create("pods", make_pod("p0"))
    (ev2,) = _read_events(resp, 1)
    assert ev2["Kind"] == "pods" and ev2["EventType"] == "ADDED"
    rv = int(ev2["Obj"]["metadata"]["resourceVersion"])
    c.close()

    # Resume from lastResourceVersion: only newer events arrive.
    di.store.create("pods", make_pod("p1"))
    c2 = _conn(server)
    c2.request(
        "GET",
        "/api/v1/listwatchresources?podsLastResourceVersion="
        f"{rv}&nodesLastResourceVersion={rv}",
    )
    resp2 = c2.getresponse()
    (ev3,) = _read_events(resp2, 1)
    assert ev3["Kind"] == "pods" and ev3["Obj"]["metadata"]["name"] == "p1"
    c2.close()


def test_watch_driven_scheduling_over_http(server):
    """The full product loop: watch stream sees the pod arrive and then
    get bound by the running scheduler."""
    di = server.di
    di.store.create("nodes", make_node("n0"))
    di.scheduler_service.start()
    try:
        c = _conn(server)
        c.request("GET", "/api/v1/listwatchresources")
        resp = c.getresponse()
        (ev,) = _read_events(resp, 1)  # node list
        di.store.create("pods", make_pod("p0", cpu="100m"))
        seen_bound = False
        end = time.monotonic() + 20
        while not seen_bound and time.monotonic() < end:
            for ev in _read_events(resp, 1, deadline=5.0):
                if (
                    ev["Kind"] == "pods"
                    and ev["EventType"] in ("ADDED", "MODIFIED")
                    and ev["Obj"]["spec"].get("nodeName") == "n0"
                ):
                    seen_bound = True
        assert seen_bound
        c.close()
    finally:
        di.scheduler_service.stop()


def test_metrics_and_ui(server):
    di = server.di
    di.store.create("nodes", make_node("n0"))
    di.store.create("pods", make_pod("p0"))
    di.scheduler_service.schedule_pending()
    status, m = _req(server, "GET", "/api/v1/metrics")
    assert status == 200
    assert m["counters"]["scheduling_passes"] >= 1
    assert m["counters"]["pods_scheduled"] >= 1
    assert m["timings"]["engine"]["count"] >= 1
    # Timers are histograms now: buckets + quantiles next to the legacy
    # total/count/mean keys.
    assert m["timings"]["engine"]["total_seconds"] > 0
    assert sum(c for _, c in m["timings"]["engine"]["buckets"]) == (
        m["timings"]["engine"]["count"]
    )
    assert m["timings"]["engine"]["p99_seconds"] >= m["timings"]["engine"]["p50_seconds"]
    # The built-in UI serves at / and references the watch endpoint.
    c = _conn(server)
    c.request("GET", "/")
    r = c.getresponse()
    body = r.read().decode()
    c.close()
    assert r.status == 200 and "listwatchresources" in body


def test_metrics_merges_faults_trace_and_replay_stats(server):
    """One GET shows the whole degradation-evidence surface (the former
    gap: fault counters and replay stats were not served)."""
    from ksim_tpu.faults import FAULTS, InjectedFault
    from ksim_tpu.obs import TRACE

    di = server.di
    di.store.create("nodes", make_node("n0"))
    di.store.create("pods", make_pod("p0"))
    prev_state = (TRACE._active, TRACE._ring_on, TRACE._user_disabled)
    TRACE.enable(ring=True)
    FAULTS.arm("service.schedule", "call:1")
    try:
        with pytest.raises(InjectedFault):
            di.scheduler_service.schedule_pending()
        di.scheduler_service.schedule_pending()  # a clean pass after
        status, m = _req(server, "GET", "/api/v1/metrics")
        assert status == 200
        # Fault-plane evidence, per site.
        assert m["faults"]["service.schedule"]["fired"] == 1
        assert m["faults"]["service.schedule"]["calls"] >= 2
        # Trace-plane evidence: the schedule span histogram + the
        # fault.fired event counter.
        assert m["trace"]["enabled"]
        assert m["trace"]["histograms"]["service.schedule"]["count"] >= 1
        assert m["trace"]["events"]["fault.fired"] >= 1
        # Replay stats appear once a driver exists in the process (other
        # tests in the suite may have created one); the KEY contract is
        # that the document is a single merged object.
        assert set(m) >= {"counters", "timings", "trace", "faults", "process"}
        if "replay" in m:
            # Live stats, a weakly-referenced driver already collected,
            # or a provider error — all are valid merged-doc shapes.
            assert any(
                k in m["replay"] for k in ("device_steps", "collected", "error")
            )
    finally:
        FAULTS.reset()
        TRACE._active, TRACE._ring_on, TRACE._user_disabled = prev_state


def test_metrics_identity_block_and_prometheus_exposition(server):
    """Solo scope carries the process-identity block unconditionally
    (the fleet aggregator keys on it), and GET /metrics renders the
    document as Prometheus text the in-repo stdlib parser accepts —
    for BOTH scopes, every family in the lint-enforced registry."""
    import os

    from ksim_tpu.obs import METRIC_NAMES, parse_prometheus

    status, m = _req(server, "GET", "/api/v1/metrics")
    assert status == 200
    ident = m["process"]
    assert set(ident) >= {"role", "worker_id", "pid", "started_at", "uptime_s"}
    assert ident["role"] == "solo" and ident["pid"] == os.getpid()
    assert ident["uptime_s"] >= 0
    # The backend fields are always present (null until a dispatch ran)
    # and name the process's backend after one scheduling pass.
    assert set(ident) >= {"platform", "device_kind", "device_count"}
    server.di.store.create("nodes", make_node("n0"))
    server.di.store.create("pods", make_pod("p0"))
    server.di.scheduler_service.schedule_pending()
    ident = _req(server, "GET", "/api/v1/metrics")[1]["process"]
    assert ident["platform"] == "cpu" and ident["device_kind"]
    assert ident["device_count"] >= 1
    for path in ("/metrics", "/metrics?scope=fleet"):
        status, text = _raw(server, "GET", path)
        assert status == 200, path
        families = parse_prometheus(text)
        assert set(families) <= set(METRIC_NAMES), path
        assert "ksim_up" in families, path
    # Fleet scope without a jobs dir still answers: the serving process
    # itself is the one (live, never-stale) worker.
    status, fm = _req(server, "GET", "/api/v1/metrics?scope=fleet")
    assert status == 200 and fm["scope"] == "fleet"
    (wid,) = fm["workers"]
    assert fm["workers"][wid]["stale"] is False


def test_trace_endpoint_serves_chrome_json(server):
    from ksim_tpu.obs import TRACE

    di = server.di
    di.store.create("nodes", make_node("n0"))
    di.store.create("pods", make_pod("p0"))
    prev_state = (TRACE._active, TRACE._ring_on, TRACE._user_disabled)
    TRACE.enable(ring=True)
    try:
        di.scheduler_service.schedule_pending()
        status, doc = _req(server, "GET", "/api/v1/trace")
        assert status == 200
        assert isinstance(doc["traceEvents"], list)
        names = {e["name"] for e in doc["traceEvents"]}
        assert "service.schedule" in names
        spans = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
        assert all("ts" in e and "dur" in e for e in spans)
    finally:
        TRACE._active, TRACE._ring_on, TRACE._user_disabled = prev_state


def test_resource_crud_routes(server):
    """Per-resource CRUD under /api/v1/resources — the role the KWOK
    apiserver plays for the reference UI (web/api/v1/*.ts)."""
    node = make_node("crud-n1", cpu="2")
    status, created = _req(server, "POST", "/api/v1/resources/nodes", node)
    assert status == 201 and created["metadata"]["resourceVersion"]
    status, got = _req(server, "GET", "/api/v1/resources/nodes/crud-n1")
    assert status == 200 and got["metadata"]["name"] == "crud-n1"
    got["metadata"]["labels"] = {"zone": "a"}
    status, updated = _req(server, "PUT", "/api/v1/resources/nodes/crud-n1", got)
    assert status == 200 and updated["metadata"]["labels"] == {"zone": "a"}
    status, listing = _req(server, "GET", "/api/v1/resources/nodes")
    assert status == 200 and any(
        n["metadata"]["name"] == "crud-n1" for n in listing["items"]
    )
    # Namespaced kind: pods default to the "default" namespace.
    pod = make_pod("crud-p1", cpu="100m")
    status, _ = _req(server, "POST", "/api/v1/resources/pods", pod)
    assert status == 201
    status, got = _req(server, "GET", "/api/v1/resources/pods/default/crud-p1")
    assert status == 200
    status, _ = _req(server, "DELETE", "/api/v1/resources/pods/default/crud-p1")
    assert status == 200
    status, _ = _req(server, "GET", "/api/v1/resources/pods/default/crud-p1")
    assert status == 404
    status, _ = _req(server, "DELETE", "/api/v1/resources/nodes/crud-n1")
    assert status == 200
    # Unknown kind and double-create conflict.
    status, _ = _req(server, "GET", "/api/v1/resources/gadgets")
    assert status == 404
    status, _ = _req(server, "POST", "/api/v1/resources/nodes", make_node("c2"))
    assert status == 201
    status, _ = _req(server, "POST", "/api/v1/resources/nodes", make_node("c2"))
    assert status == 409
    _req(server, "DELETE", "/api/v1/resources/nodes/c2")


def test_ui_edit_workflow_reschedules_pod(server):
    """The UI's view/edit workflow (YamlEditor.vue analogue): GET a live
    unschedulable pod through the CRUD, shrink its requests, PUT it back
    — the watch-driven scheduler must retry it promptly (backoff cleared
    by the user's update, upstream Pod-update QueueingHints) and bind."""
    di = server.di
    di.store.create("nodes", make_node("edit-n1", cpu="2", memory="4Gi"))
    di.store.create(
        "pods", make_pod("edit-huge", cpu="32", memory="256Mi")
    )
    di.scheduler_service.start()
    try:
        deadline = time.time() + 60
        while time.time() < deadline:
            status, pod = _req(server, "GET", "/api/v1/resources/pods/default/edit-huge")
            if status == 200 and pod["metadata"].get("annotations"):
                break
            time.sleep(0.1)
        assert pod["spec"].get("nodeName") is None  # unschedulable as-is
        # Edit: make it fit (and tag it, proving arbitrary field edits).
        pod["spec"]["containers"][0]["resources"]["requests"]["cpu"] = "500m"
        pod["metadata"].setdefault("labels", {})["edited"] = "yes"
        status, _ = _req(server, "PUT", "/api/v1/resources/pods/default/edit-huge", pod)
        assert status == 200
        deadline = time.time() + 60
        bound = None
        while time.time() < deadline and not bound:
            _, pod = _req(server, "GET", "/api/v1/resources/pods/default/edit-huge")
            bound = pod["spec"].get("nodeName")
            time.sleep(0.1)
        assert bound == "edit-n1"
        assert pod["metadata"]["labels"]["edited"] == "yes"
        # Both attempts live in result-history — the data the UI's
        # attempt browser renders (storereflector.go:148-167).
        from ksim_tpu.engine.annotations import RESULT_HISTORY_KEY

        history = json.loads(pod["metadata"]["annotations"][RESULT_HISTORY_KEY])
        assert len(history) >= 2
        # The failed attempt has no selected-node; the final one does.
        sel = "kube-scheduler-simulator.sigs.k8s.io/selected-node"
        assert sel not in history[0]
        assert history[-1][sel] == "edit-n1"
    finally:
        di.scheduler_service.stop()


def test_ui_page_has_board_editor_and_history_panels(server):
    """The built-in page ships the three debuggability surfaces the
    reference UI has: pods-by-node board with an unscheduled bucket
    (web/store/pod.ts:12-16), live-resource editor (YamlEditor.vue), and
    the result-history attempt browser (SchedulingResults.vue)."""
    c = _conn(server)
    c.request("GET", "/")
    body = c.getresponse().read().decode()
    c.close()
    assert 'id="board"' in body and "unscheduled" in body
    assert 'id="editPanel"' in body and "doSave" in body
    assert "data-attempt" in body and "result-history" in body


def test_external_scheduler_over_http(server):
    """The reference's integrate-your-scheduler workflow: an EXTERNAL
    scheduler watches for its pods and binds them through the resource
    API, while the built-in scheduler ignores pods addressed elsewhere
    (upstream schedulers only touch pods naming one of their profiles)."""
    di = server.di
    di.store.create("nodes", make_node("ext-n1"))
    foreign = make_pod("ext-p1")
    foreign["spec"]["schedulerName"] = "my-external-scheduler"
    di.store.create("pods", foreign)
    di.scheduler_service.start()
    try:
        # The built-in scheduler must leave it alone.
        time.sleep(1.5)
        _, pod = _req(server, "GET", "/api/v1/resources/pods/default/ext-p1")
        assert "nodeName" not in pod["spec"]
        assert di.scheduler_service.pending_count() == 0  # not its pod

        # External scheduler: read, decide, bind via PUT.
        pod["spec"]["nodeName"] = "ext-n1"
        pod["status"] = {"phase": "Running"}
        status, bound = _req(
            server, "PUT", "/api/v1/resources/pods/default/ext-p1", pod
        )
        assert status == 200 and bound["spec"]["nodeName"] == "ext-n1"

        # The binding is visible on the watch stream and in exports.
        _, export = _req(server, "GET", "/api/v1/export")
        got = {p["metadata"]["name"]: p["spec"].get("nodeName") for p in export["pods"]}
        assert got["ext-p1"] == "ext-n1"
    finally:
        di.scheduler_service.stop(timeout=None)


def test_listwatch_410_on_foreign_resume_point(server):
    """A reconnect carrying a resourceVersion this server never issued
    (the signature of a server restart) answers 410 Gone so the client
    drops its cache and relists — the etcd-compaction contract."""
    status, body = _req(
        server, "GET", "/api/v1/listwatchresources?podsLastResourceVersion=999999"
    )
    assert status == 410
    assert "resourceVersion" in body["message"]


def test_yaml_resource_roundtrip(server):
    """YAML is a first-class wire format for the CRUD + config routes
    (the reference UI edits resources and config as YAML in Monaco,
    web/components/ResourceBar/YamlEditor.vue): GET ?format=yaml serves
    YAML, and YAML request bodies parse by Content-Type."""
    import yaml

    node_yaml = yaml.safe_dump(make_node("yaml-node"))
    c = _conn(server)
    c.request(
        "POST", "/api/v1/resources/nodes", node_yaml,
        {"Content-Type": "application/yaml"},
    )
    r = c.getresponse()
    assert r.status == 201
    r.read()
    c.close()

    status, raw = _raw(server, "GET", "/api/v1/resources/nodes/yaml-node?format=yaml")
    assert status == 200
    obj = yaml.safe_load(raw)
    assert obj["metadata"]["name"] == "yaml-node"

    # Edit workflow over YAML: mutate and PUT back as YAML.
    obj["spec"]["unschedulable"] = True
    c = _conn(server)
    c.request(
        "PUT", "/api/v1/resources/nodes/yaml-node", yaml.safe_dump(obj),
        {"Content-Type": "application/yaml"},
    )
    r = c.getresponse()
    assert r.status == 200
    r.read()
    c.close()
    _status, body = _req(server, "GET", "/api/v1/resources/nodes/yaml-node")
    assert body["spec"]["unschedulable"] is True

    # Scheduler config serves + applies as YAML too.
    status, raw = _raw(server, "GET", "/api/v1/schedulerconfiguration?format=yaml")
    assert status == 200
    cfg = yaml.safe_load(raw)
    assert cfg["kind"] == "KubeSchedulerConfiguration"
    cfg["profiles"] = [
        {"plugins": {"multiPoint": {"disabled": [{"name": "ImageLocality"}]}}}
    ]
    c = _conn(server)
    c.request(
        "POST", "/api/v1/schedulerconfiguration", yaml.safe_dump(cfg),
        {"Content-Type": "application/yaml"},
    )
    r = c.getresponse()
    assert r.status == 202, r.read()
    r.read()
    c.close()
    _status, got = _req(server, "GET", "/api/v1/schedulerconfiguration")
    assert got["profiles"][0]["plugins"]["multiPoint"]["disabled"] == [
        {"name": "ImageLocality"}
    ]


def test_traces_endpoint_lists_entries_with_metadata(server, tmp_path, monkeypatch):
    """GET /api/v1/traces pins the registry-entry shape — ``name`` /
    ``size_bytes`` / ``gzip`` / ``format`` — across a plain Borg JSONL,
    an Alibaba CSV, and a gzipped trace (detected format is advisory;
    job specs still name theirs explicitly)."""
    import gzip

    (tmp_path / "mini.jsonl").write_text('{"time": 0, "type": "SUBMIT"}\n')
    (tmp_path / "batch.csv").write_text("t1,task,j1,1,0,100,Terminated,0.5,1.0\n")
    with gzip.open(tmp_path / "mini2.jsonl.gz", "wt") as f:
        f.write('{"time": 1}\n')
    monkeypatch.setenv("KSIM_TRACES_DIR", str(tmp_path))
    status, body = _req(server, "GET", "/api/v1/traces")
    assert status == 200
    items = body["items"]
    assert [e["name"] for e in items] == [
        "batch.csv",
        "mini.jsonl",
        "mini2.jsonl.gz",
    ]
    for entry in items:
        assert set(entry) == {"name", "size_bytes", "gzip", "format"}
        assert entry["size_bytes"] > 0
    by_name = {e["name"]: e for e in items}
    assert by_name["mini.jsonl"]["format"] == "borg"
    assert by_name["mini.jsonl"]["gzip"] is False
    assert by_name["batch.csv"]["format"] == "alibaba"
    assert by_name["batch.csv"]["gzip"] is False
    assert by_name["mini2.jsonl.gz"]["format"] == "borg"
    assert by_name["mini2.jsonl.gz"]["gzip"] is True
