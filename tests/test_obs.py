"""Trace plane (ksim_tpu/obs.py): spans, histograms, ring, export,
and the registry-sync guards that keep the fault-site / fallback-reason
taxonomies and the trace event names from drifting apart.

The plane is process-global in production; these tests construct
private ``TracePlane`` instances wherever possible and restore the
global one when they must touch it."""

from __future__ import annotations

import functools
import json
import threading

import pytest

from ksim_tpu import obs
from ksim_tpu.obs import (
    EVENT_NAMES,
    SPAN_NAMES,
    LatencyHistogram,
    TracePlane,
)


@pytest.fixture
def plane() -> TracePlane:
    p = TracePlane()
    p.enable(ring=True)
    return p


# ---------------------------------------------------------------------------
# Spans
# ---------------------------------------------------------------------------


def test_span_nesting_depth_and_order(plane):
    with plane.span("runner.step", step=1):
        with plane.span("service.schedule", pass_num=1):
            pass
        with plane.span("service.schedule", pass_num=2):
            pass
    recs = plane.ring_records()
    # Spans record at EXIT: inner spans land before their parent.
    assert [r["name"] for r in recs] == [
        "service.schedule",
        "service.schedule",
        "runner.step",
    ]
    assert [r["depth"] for r in recs] == [1, 1, 0]
    outer = recs[2]
    for inner in recs[:2]:
        # Interval containment (what makes Chrome/Perfetto nest them).
        assert outer["t"] <= inner["t"]
        assert inner["t"] + inner["d"] <= outer["t"] + outer["d"]
    assert outer["args"] == {"step": 1}


def test_span_records_error_and_propagates(plane):
    with pytest.raises(ValueError):
        with plane.span("replay.lower", segment=1):
            raise ValueError("boom")
    (rec,) = plane.ring_records()
    assert rec["args"]["error"] == "ValueError"
    # Histogram observed the failed span too (time was still spent).
    assert plane.phase_totals()["replay.lower"][1] == 1


def test_span_histograms_accumulate(plane):
    for _ in range(5):
        with plane.span("kubeapi.request"):
            pass
    total, count = plane.phase_totals()["kubeapi.request"]
    assert count == 5
    assert total > 0.0
    snap = plane.snapshot()["histograms"]["kubeapi.request"]
    assert snap["count"] == 5
    assert sum(c for _, c in snap["buckets"]) == 5


# ---------------------------------------------------------------------------
# Histogram buckets
# ---------------------------------------------------------------------------


def test_histogram_edges_are_fixed_log_spaced():
    edges = LatencyHistogram.EDGES
    assert len(edges) == 33
    assert edges[0] == pytest.approx(1e-6)
    assert edges[-1] == pytest.approx(100.0)
    # 4 per decade: every 4th edge is a decade step.
    assert edges[4] == pytest.approx(1e-5)
    assert edges[32] == pytest.approx(1e-6 * 10**8)


def test_histogram_bucket_edge_assignment():
    h = LatencyHistogram()
    # An observation exactly ON an edge belongs to the bucket it is the
    # upper edge of (le semantics).
    h.observe(1e-6)
    assert h.counts[0] == 1
    # Just above the first edge -> second bucket.
    h.observe(1.0000001e-6)
    assert h.counts[1] == 1
    # Overflow bucket catches everything past 100 s.
    h.observe(1e9)
    assert h.counts[-1] == 1
    # Sub-first-edge lands in the first bucket.
    h.observe(1e-9)
    assert h.counts[0] == 2
    assert h.count == 4
    snap = h.snapshot()
    assert snap["count"] == 4
    # The overflow bucket serializes with a null upper edge.
    assert [edge for edge, _ in snap["buckets"]][-1] is None


def test_histogram_quantiles_clamped_to_observed_max():
    h = LatencyHistogram()
    h.observe(0.01)
    h.observe(5.0)
    # The 5.0 bucket's upper edge is ~5.62; estimates must not exceed
    # anything actually observed.
    assert h.quantile(0.99) == pytest.approx(5.0)
    assert h.quantile(0.5) == pytest.approx(0.01)
    assert h.snapshot()["max_seconds"] == pytest.approx(5.0)


# ---------------------------------------------------------------------------
# Ring
# ---------------------------------------------------------------------------


def test_ring_eviction_under_concurrent_writers():
    p = TracePlane()
    p.configure_from_env({"KSIM_TRACE_RING": "64", "KSIM_TRACE": "1"})
    n_threads, per_thread = 8, 200

    def hammer(i: int) -> None:
        for j in range(per_thread):
            p.event("replay.fallback", reason=f"t{i}", n=j)
            with p.span("runner.step", thread=i):
                pass

    threads = [threading.Thread(target=hammer, args=(i,)) for i in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    snap = p.snapshot()
    assert snap["ring"]["capacity"] == 64
    assert snap["ring"]["size"] == 64
    appended = n_threads * per_thread * 2
    assert snap["ring"]["appended"] == appended
    assert snap["ring"]["evicted"] == appended - 64
    # Nothing was lost from the aggregate layers despite eviction.
    assert snap["events"]["replay.fallback"] == n_threads * per_thread
    assert snap["histograms"]["runner.step"]["count"] == n_threads * per_thread
    # Every surviving record is well-formed.
    for r in p.ring_records():
        assert r["ph"] in ("X", "i")
        assert isinstance(r["t"], int) and isinstance(r["args"], dict)


# ---------------------------------------------------------------------------
# Disabled path
# ---------------------------------------------------------------------------


def test_disabled_plane_is_noop():
    p = TracePlane()
    assert not p.active
    s1 = p.span("runner.step", step=1)
    s2 = p.span("service.schedule")
    # The disabled path hands out ONE shared no-op object — no
    # allocation, no clock read.
    assert s1 is s2 is obs._NOOP
    with s1:
        pass
    p.event("fault.fired", site="replay.dispatch")
    assert p.ring_records() == []
    assert p.phase_totals() == {}
    assert p.snapshot()["events"] == {}


def test_disable_reenable_cycle(plane):
    with plane.span("runner.step"):
        pass
    plane.disable()
    with plane.span("runner.step"):
        pass
    assert plane.phase_totals()["runner.step"][1] == 1
    plane.enable(ring=True)
    with plane.span("runner.step"):
        pass
    assert plane.phase_totals()["runner.step"][1] == 2


def test_ensure_timing_keeps_ring_off():
    p = TracePlane()
    p.ensure_timing()
    assert p.active
    with p.span("runner.step"):
        pass
    assert p.phase_totals()["runner.step"][1] == 1
    assert p.ring_records() == []  # timing-only: histograms, no ring


def test_ensure_timing_respects_explicit_disable():
    """Convenience activation (ScenarioRunner.run) must never override
    an operator's stated opt-out — disable()/KSIM_TRACE=off is sticky
    against it; only an explicit enable() turns the plane back on."""
    p = TracePlane()
    p.disable()
    p.ensure_timing()
    assert not p.active
    p2 = TracePlane()
    p2.configure_from_env({"KSIM_TRACE": "off"})
    p2.ensure_timing()
    assert not p2.active
    p2.enable(ring=False)
    assert p2.active


# ---------------------------------------------------------------------------
# Export
# ---------------------------------------------------------------------------


def test_chrome_export_roundtrip(plane, tmp_path):
    with plane.span("replay.lower", segment=1, steps=16):
        with plane.span("replay.dispatch", segment=1, steps=16):
            pass
    plane.event("store.txn_commit", writes=3, events=3)
    out = tmp_path / "trace.json"
    doc = plane.export_chrome(str(out))
    on_disk = json.loads(out.read_text())
    assert on_disk == doc
    evs = doc["traceEvents"]
    phases = {e["ph"] for e in evs}
    assert phases == {"M", "X", "i"}
    xs = [e for e in evs if e["ph"] == "X"]
    assert {e["name"] for e in xs} == {"replay.lower", "replay.dispatch"}
    for e in xs:
        assert e["ts"] >= 0 and e["dur"] >= 0
        assert e["cat"] == "replay"
    (instant,) = [e for e in evs if e["ph"] == "i"]
    assert instant["s"] == "t" and instant["args"]["writes"] == 3
    # Thread metadata names the recording thread.
    (meta,) = [e for e in evs if e["ph"] == "M"]
    assert meta["name"] == "thread_name"


def test_env_configuration(tmp_path):
    p = TracePlane()
    out = tmp_path / "t.json"
    p.configure_from_env({"KSIM_TRACE_OUT": str(out)})
    assert p.active and p.out_path == str(out)
    p2 = TracePlane()
    p2.configure_from_env({"KSIM_TRACE": "timing"})
    assert p2.active
    with p2.span("runner.step"):
        pass
    assert p2.ring_records() == []
    p3 = TracePlane()
    p3.configure_from_env({"KSIM_TRACE": "off"})
    assert not p3.active
    # The operator's opt-out beats a wrapper-exported KSIM_TRACE_OUT.
    p4 = TracePlane()
    p4.configure_from_env({"KSIM_TRACE": "off", "KSIM_TRACE_OUT": "/tmp/x.json"})
    assert not p4.active and p4.out_path is None


# ---------------------------------------------------------------------------
# Registry sync: fault sites <-> spans, fallback reasons <-> events
# ---------------------------------------------------------------------------


def _repo_root():
    import os

    return os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@functools.lru_cache(maxsize=1)
def _lint_project():
    """The analyzer's view of the tree (tools/ksimlint, docs/lint.md).
    These tests are RE-BACKED by the analyzer's call-site scans — the
    same AST pass `make lint` runs — so the in-suite registry checks
    can never drift from what the lint rule actually sees (the old
    inline grep/ast logic lived here and could).  Cached: the tree is
    immutable while tests run, and three tests share the parse."""
    from tools.ksimlint.core import Project

    return Project.load(_repo_root())


def test_fault_sites_match_source_and_span_taxonomy():
    """Every FAULTS.check("...") literal in the codebase is a declared
    site, every declared site is wired somewhere, and every site has a
    same-named span enclosing it on the timeline — the taxonomies
    cannot drift apart silently.  Also pins the analyzer's AST-read
    registries to the imported runtime values: the lint rule checks
    call sites against what it PARSES, this asserts what it parses is
    what the process actually runs."""
    from ksim_tpu.faults import SITES
    from tools.ksimlint.rules import registry_literals as rl

    project = _lint_project()
    regs = rl.load_registries(project)
    assert regs.sites == SITES
    assert regs.span_names == SPAN_NAMES
    assert regs.event_names == EVENT_NAMES

    scan = rl.scan_fault_sites(project)
    assert not scan.dynamic, f"non-literal FAULTS.check sites: {scan.dynamic}"
    assert set(scan.literals) == set(SITES)
    assert set(SITES) <= set(SPAN_NAMES)
    assert "fault.fired" in EVENT_NAMES


def test_trace_literals_match_taxonomy():
    """Every TRACE.span / TRACE.event name spelled at a call site is in
    the registry (the analyzer's scan, asserted in-suite)."""
    from tools.ksimlint.rules import registry_literals as rl

    spans, events = rl.scan_trace_literals(_lint_project())
    assert not spans.dynamic and not events.dynamic
    assert set(spans.literals) <= set(SPAN_NAMES), (
        set(spans.literals) - set(SPAN_NAMES)
    )
    assert set(events.literals) <= set(EVENT_NAMES), (
        set(events.literals) - set(EVENT_NAMES)
    )


def test_metric_names_match_exposition_literals():
    """The Prometheus exposition surface is machine-checked the same
    way SITES/SPAN_NAMES are: the analyzer's AST view of METRIC_NAMES
    equals the imported runtime tuple, and every `_expo_family("...")`
    declaration resolves into the registry with no dead entries."""
    from tools.ksimlint.rules import registry_literals as rl

    project = _lint_project()
    regs = rl.load_registries(project)
    assert regs.metric_names == obs.METRIC_NAMES

    scan = rl.scan_metric_literals(project)
    assert not scan.dynamic, f"non-literal exposition families: {scan.dynamic}"
    assert set(scan.literals) == set(obs.METRIC_NAMES)
    # The runtime family table renders exactly the registry, in order.
    assert tuple(f["name"] for f in obs._EXPO_FAMILIES) == obs.METRIC_NAMES


def test_fallback_reasons_match_replay_source():
    """Every statically spelled fallback reason in engine/replay.py is
    registered in FALLBACK_REASONS (so it reaches the trace taxonomy),
    and the registry carries no dead entries — via the analyzer's scan
    (it replaced the inline ast walk this test used to carry)."""
    from ksim_tpu.engine.replay import (
        FALLBACK_REASON_PREFIXES,
        FALLBACK_REASONS,
    )
    from tools.ksimlint.rules import registry_literals as rl

    project = _lint_project()
    regs = rl.load_registries(project)
    assert regs.fallback_reasons == FALLBACK_REASONS
    assert regs.fallback_prefixes == FALLBACK_REASON_PREFIXES

    fb = rl.scan_fallback_reasons(project)
    unregistered = set(fb.call_reasons) - FALLBACK_REASONS
    assert not unregistered, (
        f"fallback reasons missing from FALLBACK_REASONS: {sorted(unregistered)}"
    )
    # The post-dispatch validation discards return their reason as a
    # string (featurize_prediction / preemption_overflow): registry
    # entries must exist SOMEWHERE in the source.
    dead = FALLBACK_REASONS - set(fb.call_reasons) - fb.return_strings
    assert not dead, f"FALLBACK_REASONS entries not found in source: {sorted(dead)}"
    for prefix in fb.fstring_prefixes:
        assert any(prefix.startswith(p) for p in FALLBACK_REASON_PREFIXES), (
            f"dynamic fallback reason family {prefix!r} not in "
            f"FALLBACK_REASON_PREFIXES"
        )
    assert "replay.fallback" in EVENT_NAMES


#: Every environment variable the engine layer names in a string (an
#: environ read, a docstring, an error message): the analyzer's own
#: scan, held to this tuple.  A knob is added here in the PR that brings
#: the cell, test or deployment file that sets it.
ENGINE_ENV_KNOBS = (
    "KSIM_AOT_CACHE",
    "KSIM_AOT_PREWARM",
    "KSIM_FAULTS",
    "KSIM_FLEET_DP",
    "KSIM_FLEET_FAULTS",
    "KSIM_FLEET_VMAP",
    "KSIM_JOBS_DIR",
    "KSIM_REPLAY_BREAKER_COOLDOWN_S",
    "KSIM_REPLAY_BREAKER_N",
    "KSIM_REPLAY_TP",
    "KSIM_REPLAY_WATCHDOG_S",
)


def test_engine_env_knobs_are_the_listed_ones():
    """The segment program's compile shapes and the transfer path are
    constants of the source: ksim_tpu/engine/ names no environment
    variable beyond the ones above."""
    from tools.ksimlint.core import Project
    from tools.ksimlint.rules import env_contract as ec

    project = _lint_project()
    engine = Project(
        root=project.root,
        files={
            rel: sf
            for rel, sf in project.files.items()
            if rel.startswith("ksim_tpu/engine/")
        },
    )
    assert engine.files
    assert sorted(ec.scan_env_literals(engine)) == sorted(ENGINE_ENV_KNOBS)


def test_fault_fire_emits_trace_event():
    """The fault plane lands fault.fired on the global plane; exercised
    through a private enable/restore cycle of the global TRACE."""
    from ksim_tpu.faults import FaultPlane, InjectedFault
    from ksim_tpu.obs import TRACE

    prev_state = (TRACE._active, TRACE._ring_on, TRACE._user_disabled)
    TRACE.enable(ring=True)
    try:
        before = TRACE.snapshot()["events"].get("fault.fired", 0)
        plane = FaultPlane()
        plane.arm("replay.dispatch", "call:1")
        with pytest.raises(InjectedFault):
            plane.check("replay.dispatch")
        events = [
            r for r in TRACE.ring_records() if r["name"] == "fault.fired"
        ]
        assert events and events[-1]["args"]["site"] == "replay.dispatch"
        assert TRACE.snapshot()["events"]["fault.fired"] == before + 1
    finally:
        # Exact flag restore (not disable(): its sticky opt-out would
        # leak into later tests' ensure_timing).
        TRACE._active, TRACE._ring_on, TRACE._user_disabled = prev_state


def test_provider_registry_rejects_reserved_names():
    for name in obs.RESERVED_PROVIDER_NAMES:
        with pytest.raises(ValueError):
            obs.register_provider(name, dict)


def test_provider_registry():
    obs.register_provider("_test_ok", lambda: {"x": 1})
    obs.register_provider("_test_boom", lambda: 1 / 0)
    try:
        snaps = obs.provider_snapshots()
        assert snaps["_test_ok"] == {"x": 1}
        assert "ZeroDivisionError" in snaps["_test_boom"]["error"]
    finally:
        with obs._providers_lock:
            obs._providers.pop("_test_ok", None)
            obs._providers.pop("_test_boom", None)
