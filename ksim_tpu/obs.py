"""Process-global trace plane: spans, latency histograms, event ring.

The reference simulator's observability is the upstream scheduler's
Prometheus metrics plus klog (SURVEY §5); before this module the repo's
analogue was a mean-only ``Metrics`` counter/timer and scattered ad-hoc
dicts (``ReplayDriver.stats()``, ``FaultPlane`` site counters).  None of
it could answer the ROADMAP's open TPU wall-clock question — *where*
does the 50k trajectory spend its time, and *when* did a degradation
(fallback, watchdog timeout, breaker trip) actually happen.

This module is the single answer surface:

- **Spans** — named intervals on a monotonic clock (``TRACE.span``),
  one per pipeline phase (segment lower / dispatch / reconcile, the
  per-pass host step, write-back pushes, kubeapi requests).  Every span
  lands its duration in a fixed-bucket log-spaced latency histogram and
  (ring mode) a structured record in the event ring.  A timed **stage**
  (``TRACE.stage``) is the sequential child that lands in the histogram
  (and the profiler bridge) ONLY: what is inside a span whose self time
  is itself a metric.
- **Events** — instants (``TRACE.event``): fallback reasons with the
  segment context, pass outcomes, fault-plane fires, breaker state
  changes, store-transaction commit/rollback.
- **Export** — the ring renders as Chrome trace-event JSON
  (``chrome://tracing`` / https://ui.perfetto.dev): spans become ``X``
  complete events nested per thread, instants become ``i`` events.
  ``KSIM_TRACE_OUT=path`` arms an atexit export, so any entrypoint can
  be traced from the environment alone; ``/api/v1/trace`` serves the
  same document live.

Observability is zero-perturbation by construction: nothing here reads
or writes scheduling state, so the churn behavior locks (repo
CLAUDE.md) hold byte-identically with tracing fully enabled —
tests/test_behavior_locks.py pins that.  With the plane fully disabled
every site costs ONE attribute check (``TRACE._active``) and nothing
else; the module is stdlib-only and never imports jax at module scope
(the optional ``jax.profiler.TraceAnnotation`` bridge is lazy and
guarded, so host spans can be correlated with device timelines when a
jax profile is being captured: ``KSIM_TRACE_JAX=1``).

Environment:

- ``KSIM_TRACE_OUT=path``  enable timing + ring; export Chrome trace
  JSON to ``path`` at process exit (and on demand).
- ``KSIM_TRACE=1``         enable timing + ring without a file.
- ``KSIM_TRACE=timing``    histograms/counters only (no ring storage).
- ``KSIM_TRACE_RING=N``    ring capacity (default 65536 records).
- ``KSIM_TRACE_JAX=1``     also wrap spans in
  ``jax.profiler.TraceAnnotation`` (guarded; no-op if jax is absent or
  no profiler session is active).  A job's private plane inherits the
  flag (jobs/manager.py), so job spans sit on the profiler's own clock
  beside the device lines.

Two process-level evidence streams ride beside the planes, always on:
full (generation-2) garbage collections, timed by ONE ``gc.callbacks``
hook this module installs at import, and XLA compiles / persistent-cache
loads, fed by ``jax.monitoring`` listeners that ``ksim_tpu.util``
registers once jax is in the process (this module stays stdlib-only).
Both land in ``runtime_snapshot()`` (``/api/v1/metrics`` ``counters`` /
``timings``) and, as growth across a job, in the job result's
``runtime`` block; with a plane active they also show on the timeline
(``service.gc`` spans, ``engine.compile`` instants).

The span/event name taxonomy lives in ``SPAN_NAMES`` / ``EVENT_NAMES``
below; tests/test_obs.py's registry-sync test asserts every
``faults.py`` injection site and every replay fallback reason stays
covered (see docs/observability.md for the full table).
"""

from __future__ import annotations

import atexit
import bisect
import gc
import json
import os
import threading
import time
from collections import deque
from typing import Any, Callable, Iterator

__all__ = [
    "TRACE",
    "TracePlane",
    "LatencyHistogram",
    "SPAN_NAMES",
    "EVENT_NAMES",
    "METRIC_NAMES",
    "OBS_DIR",
    "register_provider",
    "provider_snapshots",
    "process_identity",
    "note_device",
    "device_identity",
    "note_xla_compile",
    "note_xla_cache_load",
    "runtime_snapshot",
    "runtime_totals",
    "collect_scheduled",
    "runtime_growth",
    "publish_snapshot",
    "read_fleet_snapshots",
    "read_fleet_traces",
    "merge_latency_snapshots",
    "merge_fleet_docs",
    "merge_chrome_traces",
    "render_prometheus",
    "parse_prometheus",
]

# ---------------------------------------------------------------------------
# Taxonomy (docs/observability.md keeps the prose table in sync)
# ---------------------------------------------------------------------------

#: Interval (span) names.  The fault-plane injection sites
#: (faults.SITES) each fire INSIDE the span of the same name, so a
#: fault event always has an enclosing phase on the timeline.
SPAN_NAMES: tuple[str, ...] = (
    "replay.lower",  # segment lowering (engine/replay.py)
    "replay.prelower",  # NEXT window's speculative store-independent
    #                     prefix, overlapped with the in-flight dispatch
    #                     (runs on the main thread INSIDE the dispatch
    #                     span's wall-clock window — the two are
    #                     concurrent by design, not additive)
    "replay.lower.parse",  # child of replay.lower / replay.prelower:
    #                        the window's store-independent parse
    #                        (_parse_window) — under replay.lower only
    #                        when no speculative spec could be consumed
    "replay.lower.warm",  # child of replay.prelower: per-object parse
    #                       memos warmed for the window's created objects
    "replay.lower.universe",  # child of replay.lower: store-dependent
    #                           window validation + the cached universe
    #                           merge, up to the featurizer call
    "replay.lower.featurize",  # child of replay.lower: the
    #                            Featurizer.featurize call, the slot map
    #                            and the plugin/program build
    "replay.lower.tensors",  # child of replay.lower: initial state,
    #                          inter-pod locals, event index tensors,
    #                          slot simulation, preemption ranks, consts
    "replay.dispatch",  # device dispatch incl. watchdog wait
    "replay.pack",  # dispatch worker: host->device transfer of the
    #                 plan's constants + transient trees (H2D)
    "replay.exec",  # dispatch worker: launch (compile-or-load inside)
    #                 until the program's outputs are ready — an upper
    #                 bound of the device time of one dispatch
    "replay.pull",  # dispatch worker: device->host transfer (D2H)
    "replay.decode",  # dispatch worker: host decode of the pulled
    #                   tensors into the SegmentOutcome
    "replay.reconcile",  # staged store reconcile (the segment txn)
    "replay.reconcile.evict",  # child of replay.reconcile: one step's
    #                            preemptions written back, from the
    #                            first preemptor's attempt to the last
    #                            one's — nomination patches, victims'
    #                            evictions, nominations cleared (args
    #                            preemptions, victims)
    "runner.step",  # one per-pass host step (ops + flush + schedule)
    "service.schedule",  # one scheduling pass (scheduler/service.py)
    "service.featurize",  # pass phase: featurize (Metrics timer
    #                       ``featurize``, same clock reading)
    "engine.pack",  # pass phase: Engine construction = host->device
    #                 pack of the snapshot (timer ``engine_pack``)
    "engine.exec",  # pass phase: one scan dispatch until its outputs
    #                 are ready (timer ``engine_exec``)
    "engine.pull",  # pass phase: device->host pull of one dispatch's
    #                 results (timer ``engine_pull``)
    "service.bind",  # pass phase: decode + annotation render + store
    #                  writes of every pod of the pass (timer ``bind``;
    #                  args render_s / store_s = the per-pod sums)
    "service.import",  # POST /api/v1/import: body parse + snapshot load
    #                    (server/http.py; timer ``import_load``)
    "service.export",  # GET /api/v1/export: snapshot + encode + write
    #                    (timers ``export_snap`` / ``export_encode``)
    "service.gc",  # one full (generation-2) garbage collection, on the
    #                collecting thread's plane (args generation,
    #                collected, scheduled) — recorded by this module's
    #                gc hook
    "writeback.push",  # live-cluster write-back push
    "kubeapi.request",  # any kube-apiserver HTTP request
    "jobs.run",  # one tenant job end-to-end on a job-plane worker
    #              (ksim_tpu/jobs/manager.py; recorded on the JOB's
    #              private plane via the worker's scoped override)
    "jobs.result",  # GET /api/v1/jobs/<id>/result on the HTTP handler
    #                 thread: the document serialised and written — the
    #                 last thing a job's client waits for (global plane)
    "jobs.submit",  # POST /api/v1/jobs on the HTTP handler thread:
    #                 body read + parse, spec validation (15,000
    #                 operations are 15,000 Operation objects) and the
    #                 enqueue (server/http.py; global plane — the job's
    #                 private ring does not exist yet)
    "scenario.ingest",  # one trace ingestion: parse + resample +
    #                     compile of a real cluster trace into the
    #                     operation stream (ksim_tpu/traces/compile.py;
    #                     args carry format/records/ops)
    "jobs.journal_append",  # one durable append to the job journal
    #                         (ksim_tpu/jobs/journal.py; the write-ahead
    #                         record behind every submission/transition)
    "jobs.journal_replay",  # one startup journal replay: scan + torn-
    #                         tail truncation + registry reconstruction
    "jobs.checkpoint_append",  # one segment-checkpoint record built and
    #                            durably appended to the job journal
    #                            (ksim_tpu/jobs/manager.py; wraps the
    #                            nested jobs.journal_append span)
    "jobs.checkpoint_restore",  # one restore attempt from a journaled
    #                             checkpoint: store + service carries
    #                             reconstructed on the worker thread
    #                             before the suffix replay
    "jobs.lease_claim",  # one fleet claim attempt: fold the lease file
    #                      under the exclusive flock, decide, append
    #                      (ksim_tpu/jobs/fleet.py; refusals return
    #                      inside the span without a claim record)
    "jobs.lease_renew",  # one heartbeat batch renewing this worker's
    #                      live leases (args.n — a missed batch is
    #                      survivable until lease expiry)
    "obs.publish",  # one crash-atomic telemetry snapshot written to
    #                 KSIM_JOBS_DIR/obs/<worker_id>.json (the fleet
    #                 observability plane's per-worker publish —
    #                 publish_snapshot below)
    "obs.fleet_merge",  # one fleet-scope aggregation: fold every
    #                     worker's published snapshot (or Chrome trace)
    #                     into the merged document (merge_fleet_docs /
    #                     merge_chrome_traces below)
    "traces.stream",  # one streaming trace ingestion on the producer
    #                   thread: parse + bounded-memory select + windowed
    #                   compile feeding the replay executor
    #                   (ksim_tpu/traces/stream.py; args carry
    #                   format/windows/ops — overlaps the replay it
    #                   feeds by construction)
    # -- timed STAGES (``TracePlane.stage``): histograms and the
    # profiler bridge only, never the ring or the sink, so the parents'
    # self times (the ``job_span_self`` metrics) stay what they were --
    "replay.reconcile.apply",  # stage of replay.reconcile: one step's
    #                            own operations into the store
    #                            (_apply_batch); entered once a step
    "replay.reconcile.write",  # stage of replay.reconcile: one step's
    #                            placements patched into the store (the
    #                            replay.reconcile.evict ring span nests
    #                            inside its interval); once a step
    "replay.reconcile.verify",  # stage of replay.reconcile: the device-
    #                             vs-store parity check (verify_segment)
    "replay.reconcile.commit",  # stage of replay.reconcile: the
    #                             transaction's exit, every buffered
    #                             watch event delivered
    "replay.reconcile.effects",  # stage with no ring parent, right
    #                              after replay.reconcile: eviction
    #                              listeners, slot advance, service
    #                              sync, result accounting
    "service.featurize.index",  # stage of Featurizer.featurize: node
    #                             slots, bound map and diff, the row
    #                             table's identity lookup and content
    #                             keys, the static and identity families
    "service.featurize.resources",  # stage: value sets, resource axis
    #                                 and units, node arrays, request
    #                                 aggregate and rows, pod tensors
    "service.featurize.affinity",  # stage: encode_affinity +
    #                                encode_taints
    "service.featurize.spread",  # stage: encode_topology_spread
    "service.featurize.interpod",  # stage: encode_inter_pod
    "service.featurize.volumes",  # stage: encode_volumes (the four
    #                               volume plugins' tensors)
    "service.featurize.extras",  # stage: node name, ports, image
    #                              locality, extra encoders, the
    #                              snapshot's assembly
    "replay.lower.featurize.program",  # stage of replay.lower.featurize
    #                                    after the featurizer call: the
    #                                    plugin factory, the host-hook
    #                                    screen, _Program(...)
    "replay.lower.walk_order",  # stage of replay.lower (sampling
    #                             services only): the node tree's list
    #                             as each step's node events leave it,
    #                             and the new slots laid out in it
    "jobs.submit.read",  # stage of jobs.submit: the body off the socket
    "jobs.submit.parse",  # stage of jobs.submit: json.loads / YAML
    "jobs.submit.build",  # stage of jobs.submit: _parse_job_spec
    #                       (validation, one Operation an operation)
    "jobs.submit.enqueue",  # stage of jobs.submit: reserve, journal,
    #                         queue, the 202
    "jobs.run.snapshot",  # stage of jobs.run: the spec's
    #                       initialSnapshot loaded into the job's store
    #                       (SnapshotService.load), before the runner
    "jobs.run.build",  # stage of jobs.run: SchedulerService(...) and
    #                    ScenarioRunner(...), up to runner.run
    "jobs.finish.digest",  # stage after jobs.run, on the job's plane:
    #                        store.placements_digest() over every pod
    "jobs.finish.document",  # stage: the result document (the job's
    #                          latency summary, drv.stats(), the blocks)
    "jobs.finish.release",  # stage: job.let_go(), the job's graph freed
    #                         (the scheduled collection that follows is
    #                         the ring span service.gc)
    "replay.lower.tensors.state",  # stage of replay.lower.tensors: the
    #                                initial dynamic state (live / bound /
    #                                backoff rows: one pass over the
    #                                store's nodes and pods)
    "replay.lower.tensors.interpod",  # stage: the inter-pod per-node
    #                                   accumulators of the bound pods
    #                                   and their check against the
    #                                   featurizer's carry
    "replay.lower.tensors.ranks",  # stage: the per-step event index
    #                                tensors, the slot simulation, rank
    #                                rows and live-node views a step
    #                                (around replay.lower.walk_order)
    "replay.lower.tensors.statics",  # stage: queue width, statics,
    #                                  consts, nominations, the victim-
    #                                  search tables, state0, the log
)

#: Instant event names.
EVENT_NAMES: tuple[str, ...] = (
    "replay.fallback",  # segment rejected/degraded; args.reason is the
    #                     stable histogram reason (ReplayDriver._reject)
    "replay.watchdog_timeout",  # a dispatch exceeded the watchdog
    "replay.breaker_open",  # the circuit breaker tripped (args.cause:
    #                         device_error / reconcile_fault /
    #                         probe_failed — the last is a half-open
    #                         probe that failed and re-opened with a
    #                         doubled cooldown)
    "service.pass",  # pass outcome: attempts/scheduled/unschedulable
    "fault.fired",  # the fault plane injected at args.site
    "store.txn_commit",  # segment transaction committed (args.writes)
    "store.txn_rollback",  # segment transaction rolled back
    "replay.cache_invalidate",  # the lowered-universe cache flushed
    #                             (args.reason: fallback / rollback /
    #                             epoch_mismatch / epoch_raced /
    #                             sched_config / no_plan)
    "replay.fleet_lane_fallback",  # one fleet lane left the convergent
    #                                cohort (args.lane, args.reason) and
    #                                continues on the solo device path
    #                                (engine/fleet.py)
    "jobs.enqueue",  # a tenant job entered the job queue (args.job,
    #                  args.priority — ksim_tpu/jobs/manager.py)
    "job.cancelled",  # a tenant job was cancelled (queued or mid-run;
    #                   mid-segment cancellation rolls the in-flight
    #                   segment transaction back first)
    "replay.breaker_probe",  # the open breaker's cooldown elapsed and
    #                          ONE probe segment was admitted to the
    #                          device path (half-open state)
    "replay.breaker_close",  # a probe dispatch came back healthy: the
    #                          breaker closed and the driver re-promoted
    #                          to the device path
    "compilecache.evict",  # an on-disk serialized executable was
    #                        discarded (args.reason: corrupt /
    #                        key_mismatch / deserialize_failed /
    #                        exec_failed — engine/compilecache.py)
    "jobs.journal_recover",  # startup journal replay reconstructed the
    #                          job registry (args: jobs / interrupted /
    #                          resumed / truncated_bytes)
    "jobs.checkpoint",  # segment-checkpoint cadence outcome: written
    #                     (args: job / segment / cursor / bytes) or
    #                     skipped (args.skipped=True, args.reason:
    #                     max_bytes / waiting_pods / append_failed —
    #                     a skip never fails the job)
    "jobs.checkpoint_restore",  # restore-from-checkpoint outcome
    #                             (args.restored True/False; a failed
    #                             attempt falls back to the previous
    #                             checkpoint, then to scratch)
    "jobs.fleet_claim",  # a fleet member won a job lease (args: job /
    #                      worker / epoch / takeover — takeover=True is
    #                      the fail-over path re-claiming an expired
    #                      lease; ksim_tpu/jobs/fleet.py)
    "jobs.lease_expired",  # a lease aged out un-renewed and a survivor
    #                        took the job over (args: job / worker — the
    #                        DEAD owner being charged — / epoch)
    "obs.snapshot_stale",  # fleet aggregation found a worker snapshot
    #                        older than its publish cadence allows
    #                        (args: worker / stale_s — the dead worker
    #                        is FLAGGED in the merged doc, never
    #                        silently dropped)
    "engine.compile",  # XLA compiled a program (not a cache load) on
    #                    this thread (args.seconds) — the timeline shows
    #                    inside which span a step recompiled
    "traces.ingest_fallback",  # the streaming producer degraded to the
    #                            materialized ingest path (args.reason —
    #                            an armed fault or unexpected error
    #                            before the first window; counts stay
    #                            byte-identical, only the O(window)
    #                            memory claim is forfeited for this run)
)

_KNOWN_NAMES = frozenset(SPAN_NAMES) | frozenset(EVENT_NAMES)

#: Prometheus exposition metric FAMILY names (``GET /metrics``).  Like
#: SPAN_NAMES/EVENT_NAMES this is a machine-checked registry: the
#: registry-literals lint rule asserts every ``_expo_family("...")``
#: literal below is registered here and every entry here is spelled at
#: exactly such a call site (docs/lint.md "Registry literals").
#: Individual counter/timer/site names become LABELS (``name`` /
#: ``site``), not families, so the family set stays a static literal.
METRIC_NAMES: tuple[str, ...] = (
    "ksim_counter_total",
    "ksim_event_total",
    "ksim_fault_calls_total",
    "ksim_fault_fired_total",
    "ksim_latency_seconds",
    "ksim_queue_depth",
    "ksim_queue_capacity",
    "ksim_workers_pool",
    "ksim_workers_active",
    "ksim_breaker_open",
    "ksim_uptime_seconds",
    "ksim_snapshot_age_seconds",
    "ksim_up",
    "ksim_trace_ring_evicted_total",
)


def _expo_family(name: str, kind: str, help_: str) -> dict:
    """Declare one exposition family.  The first argument MUST be a
    string literal — the registry-literals rule scans these calls the
    same way it scans ``TRACE.span("...")`` sites."""
    return {"name": name, "kind": kind, "help": help_}


#: The exposition surface, in render order.  ``kind`` is the Prometheus
#: TYPE; histogram families render ``_bucket``/``_sum``/``_count``
#: samples with ``le`` labels from the fixed LatencyHistogram edges.
_EXPO_FAMILIES: tuple[dict, ...] = (
    _expo_family(
        "ksim_counter_total", "counter",
        "Scheduler counters (label: name).",
    ),
    _expo_family(
        "ksim_event_total", "counter",
        "Trace-plane instant events (label: name).",
    ),
    _expo_family(
        "ksim_fault_calls_total", "counter",
        "Fault-plane site traversals (label: site).",
    ),
    _expo_family(
        "ksim_fault_fired_total", "counter",
        "Fault-plane injections fired (label: site).",
    ),
    _expo_family(
        "ksim_latency_seconds", "histogram",
        "Latency histograms over the fixed log-spaced edges "
        "(label: site = span or timer name).",
    ),
    _expo_family("ksim_queue_depth", "gauge", "Job queue depth."),
    _expo_family("ksim_queue_capacity", "gauge", "Job queue capacity."),
    _expo_family("ksim_workers_pool", "gauge", "Local worker pool size."),
    _expo_family(
        "ksim_workers_active", "gauge", "Local workers running a job.",
    ),
    _expo_family(
        "ksim_breaker_open", "gauge",
        "Replay circuit breaker state (1 = open).",
    ),
    _expo_family("ksim_uptime_seconds", "gauge", "Process uptime."),
    _expo_family(
        "ksim_snapshot_age_seconds", "gauge",
        "Age of a worker's published snapshot (fleet scope).",
    ),
    _expo_family(
        "ksim_up", "gauge", "1 = snapshot fresh, 0 = stale.",
    ),
    _expo_family(
        "ksim_trace_ring_evicted_total", "counter",
        "Trace ring records evicted.",
    ),
)


# ---------------------------------------------------------------------------
# Latency histogram
# ---------------------------------------------------------------------------


def _log_edges() -> tuple[float, ...]:
    """Fixed log-spaced bucket upper edges: 4 per decade from 1 µs to
    100 s (33 edges; an overflow bucket catches the rest).  Fixed — not
    adaptive — so two snapshots (or two processes) always merge and
    compare bucket-for-bucket."""
    return tuple(1e-6 * 10 ** (i / 4) for i in range(33))


class LatencyHistogram:
    """Fixed-bucket latency histogram (seconds).  NOT thread-safe on its
    own — callers (``TracePlane``, ``util.Metrics``) hold their lock."""

    EDGES: tuple[float, ...] = _log_edges()

    __slots__ = ("counts", "count", "total", "vmin", "vmax")

    def __init__(self) -> None:
        self.counts = [0] * (len(self.EDGES) + 1)  # +1 = overflow
        self.count = 0
        self.total = 0.0
        self.vmin = float("inf")
        self.vmax = 0.0

    def observe(self, seconds: float) -> None:
        # bisect_left: an observation exactly ON an edge belongs to the
        # bucket whose upper edge it is (le semantics, like Prometheus).
        self.counts[bisect.bisect_left(self.EDGES, seconds)] += 1
        self.count += 1
        self.total += seconds
        if seconds < self.vmin:
            self.vmin = seconds
        if seconds > self.vmax:
            self.vmax = seconds

    def quantile(self, q: float) -> float:
        """Bucket-interpolated quantile estimate (upper edge of the
        bucket holding the q-th observation; the overflow bucket
        reports the observed max)."""
        if not self.count:
            return 0.0
        target = q * self.count
        seen = 0
        for i, c in enumerate(self.counts):
            seen += c
            if seen >= target and c:
                # Clamped: a bucket's upper edge can exceed anything
                # actually observed.
                return (
                    min(self.EDGES[i], self.vmax)
                    if i < len(self.EDGES)
                    else self.vmax
                )
        return self.vmax

    def snapshot(self) -> dict:
        """JSON-ready view.  Keeps the legacy mean-only timer keys
        (``total_seconds`` / ``count`` / ``mean_seconds`` — pinned by
        tests/test_server.py) and adds the histogram: nonzero buckets
        as ``[upper_edge_seconds, count]`` pairs plus estimated
        quantiles."""
        if not self.count:
            return {"count": 0, "total_seconds": 0.0, "mean_seconds": 0.0}
        buckets = [
            [round(self.EDGES[i], 9) if i < len(self.EDGES) else None, c]
            for i, c in enumerate(self.counts)
            if c
        ]
        return {
            "count": self.count,
            "total_seconds": round(self.total, 6),
            "mean_seconds": round(self.total / self.count, 6),
            "min_seconds": round(self.vmin, 6),
            "max_seconds": round(self.vmax, 6),
            "p50_seconds": round(self.quantile(0.50), 6),
            "p90_seconds": round(self.quantile(0.90), 6),
            "p99_seconds": round(self.quantile(0.99), 6),
            "buckets": buckets,
        }

    def merge_snapshot(self, snap: dict) -> None:
        """Fold one ``snapshot()`` document into this histogram,
        bucket-for-bucket.  EXACT by construction: the edges are fixed
        (never adaptive), so two snapshots' buckets are the same
        partition of the real line and addition loses nothing — the
        merged quantiles are as honest as solo ones.  A bucket edge
        that is not one of ours means the snapshot came from a
        different (future?) edge layout: fail loudly rather than fold
        counts into the wrong bucket."""
        count = int(snap.get("count") or 0)
        if count <= 0:
            return
        for edge, c in snap.get("buckets") or ():
            if edge is None:
                i = len(self.EDGES)
            else:
                i = _EDGE_INDEX.get(edge)
                if i is None:
                    raise ValueError(
                        f"snapshot bucket edge {edge!r} is not one of the "
                        f"fixed histogram edges"
                    )
            self.counts[i] += int(c)
        self.count += count
        self.total += float(snap.get("total_seconds") or 0.0)
        vmin = snap.get("min_seconds")
        if vmin is not None and float(vmin) < self.vmin:
            self.vmin = float(vmin)
        vmax = snap.get("max_seconds")
        if vmax is not None and float(vmax) > self.vmax:
            self.vmax = float(vmax)

    @classmethod
    def from_snapshot(cls, snap: dict) -> "LatencyHistogram":
        h = cls()
        h.merge_snapshot(snap)
        return h


#: Serialized-edge -> bucket index (snapshots round edges to 9 digits;
#: JSON round-trips floats exactly, so dict lookup is safe).
_EDGE_INDEX: dict[float, int] = {
    round(e, 9): i for i, e in enumerate(LatencyHistogram.EDGES)
}


def merge_latency_snapshots(snaps: "list[dict]") -> dict:
    """Bucket-wise merge of K ``LatencyHistogram.snapshot()`` documents
    into one merged snapshot (the fleet aggregation primitive; the
    property test in tests/test_obs_fleet.py pins merge == histogram of
    the concatenated observations)."""
    h = LatencyHistogram()
    for snap in snaps:
        h.merge_snapshot(snap)
    return h.snapshot()


# ---------------------------------------------------------------------------
# The plane
# ---------------------------------------------------------------------------


class _NoopSpan:
    """Shared do-nothing context manager — the whole disabled path."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **args) -> None:
        pass

    def lap(self, name: str, **args) -> None:
        pass

    def watch(self) -> None:
        pass

    children_ns = 0


_NOOP = _NoopSpan()


def _jax_annotation(name: str):
    """Enter a ``jax.profiler.TraceAnnotation`` (the ``KSIM_TRACE_JAX``
    bridge); None when jax is absent or the annotation fails."""
    try:
        import jax

        ctx = jax.profiler.TraceAnnotation(name)
        ctx.__enter__()
        return ctx
    except Exception:
        return None


def _jax_annotation_exit(ctx) -> None:
    try:
        ctx.__exit__(None, None, None)
    except Exception:
        pass


class _StageEnd:
    """What ``TracePlane.stage`` returns: ``with TRACE.stage(name):``
    ends the stage with the block (a stretch that has no open span to
    close it); a bare call ignores it.  One per plane, no state."""

    __slots__ = ("_plane",)

    def __init__(self, plane: "TracePlane") -> None:
        self._plane = plane

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self._plane.stage_end()
        return False


class _Span:
    """One live span.  Records at EXIT: a span that never exits (a
    wedged dispatch abandoned with its watchdog worker) simply leaves
    no record — the caller-side watchdog timeout event is the evidence
    for that case.

    ``_observe`` / ``_timer`` (``TracePlane.phase``) feed a
    ``Metrics``-style timer from the SAME pair of clock reads, so the
    timer and the span histogram can never disagree."""

    __slots__ = (
        "_plane", "name", "args", "_t0", "_jax_ctx", "_lap", "_observe", "_timer",
        "_watch", "children_ns",
    )

    def __init__(self, plane: "TracePlane", name: str, args: dict) -> None:
        self._plane = plane
        self.name = name
        self.args = args
        self._t0 = 0
        self._jax_ctx = None
        self._lap = None  # (name, t0, args, jax_ctx) of the open lap
        self._observe = None
        self._timer = None
        self._watch = None
        self.children_ns = 0  # what ``watch`` summed, set at exit

    def __enter__(self):
        plane = self._plane
        tl = plane._tls
        tl.depth = getattr(tl, "depth", 0) + 1
        if plane._jax_bridge:
            # Guarded device-timeline bridge: annotations show up in a
            # captured jax profile next to the XLA ops they enclose.
            self._jax_ctx = _jax_annotation(self.name)
        self._t0 = time.perf_counter_ns()
        return self

    def set(self, **args) -> None:
        """Refine span attributes mid-flight (recorded at exit) — for
        values the caller only learns inside the span, e.g. the ACTUAL
        lowered step count of a window that hit a vocabulary miss."""
        self.args.update(args)

    def watch(self) -> None:
        """Ask the plane to sum what this span's DIRECT children on this
        thread take from here on — child spans, laps and stages alike,
        the gc hook's spans too — into ``children_ns`` (read it after the
        exit): the span's extent less that is the time no name covers.
        The ring cannot say it (a stage is not in it).  One span a plane
        at a time, called inside the ``with``; every other close pays
        one attribute read for it."""
        plane = self._plane
        self._watch = plane._watch = [
            threading.get_ident(), getattr(plane._tls, "depth", 0), 0,
        ]

    def lap(self, name: str, **args) -> None:
        """Open the next SEQUENTIAL child phase of this span, closing
        the one before it at the same clock reading; the span's own exit
        closes the last.  For a long straight-line body (the segment
        lowering) whose phases follow one another: one call per
        boundary instead of re-indenting the body under ``with``
        blocks, and an exception anywhere still closes the open lap.
        ``name`` must be a string literal (registry-literals lint).
        Same thread as the span, like every span."""
        now = time.perf_counter_ns()
        self._end_lap(now)
        plane = self._plane
        tl = plane._tls
        tl.depth = getattr(tl, "depth", 0) + 1
        ctx = _jax_annotation(name) if plane._jax_bridge else None
        self._lap = (name, now, args, ctx)

    def _end_lap(self, now: int) -> None:
        lap = self._lap
        if lap is None:
            return
        self._lap = None
        name, t0, args, ctx = lap
        tl = self._plane._tls
        depth = getattr(tl, "depth", 1)
        if getattr(tl, "stages", None):
            self._plane._close_stages(tl.stages, depth, now)
        if ctx is not None:
            _jax_annotation_exit(ctx)
        tl.depth = depth - 1
        self._plane._record_span(name, t0, now, depth - 1, args)

    def __exit__(self, exc_type, exc, tb):
        t1 = time.perf_counter_ns()
        self._end_lap(t1)
        plane = self._plane
        tl = plane._tls
        depth = getattr(tl, "depth", 1)
        if getattr(tl, "stages", None):
            plane._close_stages(tl.stages, depth, t1)
        if self._jax_ctx is not None:
            _jax_annotation_exit(self._jax_ctx)
        if self._observe is not None:
            self._observe(self._timer, (t1 - self._t0) / 1e9)
        if self._watch is not None:
            self.children_ns = self._watch[2]
            plane._watch = self._watch = None
        tl.depth = depth - 1
        if exc_type is not None:
            self.args["error"] = exc_type.__name__
        plane._record_span(self.name, self._t0, t1, depth - 1, self.args)
        return False


class _PlaneScope:
    """Context manager installing an override plane for the current
    thread (``TracePlane.scoped``); restores the previous override on
    exit, so scopes nest."""

    __slots__ = ("_plane", "_override", "_prev")

    def __init__(self, plane: "TracePlane", override: "TracePlane | None") -> None:
        self._plane = plane
        self._override = override
        self._prev = None

    def __enter__(self):
        tls = self._plane._tls
        self._prev = getattr(tls, "scope", None)
        tls.scope = self._override
        return self._override

    def __exit__(self, *exc):
        self._plane._tls.scope = self._prev
        return False


class TracePlane:
    """Bounded, thread-safe trace storage — instance-scoped since
    round 13 (the job plane), with the process-global ``TRACE`` as the
    default instance.

    Three independently useful layers, one ``_active`` gate:

    - per-name latency histograms + event counters (``timing``),
    - the structured event ring (``ring``),
    - the Chrome-trace exporter over the ring.

    Thread-safe: spans/events land from the scheduler watch loop, the
    write-back thread, HTTP handler threads, and the replay dispatch
    worker concurrently; one leaf lock guards all storage (nothing
    under it calls out, so it cannot participate in a lock cycle).

    **Scoped override** (multi-tenancy): ``TRACE.scoped(plane)``
    installs ``plane`` as the CURRENT THREAD's recording target — every
    ``span``/``event``/``ensure_timing``/``phase_totals`` call on the
    default plane delegates to it until the scope exits.  Call sites
    keep addressing the module-global ``TRACE``; a tenant-job worker
    (ksim_tpu/jobs) wraps its run in a scope and gets a private ring,
    private histograms, and per-record ``tags`` (e.g. ``job=<id>``)
    without a single call-site change.  The replay executor propagates
    the scope onto its watchdogged dispatch worker
    (engine/replay.py ``_run_watchdogged``), so spans/events emitted
    there stay attributed to the owning job.  Reads of a SPECIFIC
    plane's storage (``snapshot``/``ring_records``/``export_chrome``)
    never delegate — an HTTP handler asking the global plane gets the
    global plane.

    ``tags`` merge into every recorded span/event's args (the job id on
    every record); ``sink`` — set via ``set_sink`` — receives each
    record dict AFTER the storage lock is released (it may fan records
    into an SSE stream; a raising sink is swallowed)."""

    def __init__(self, *, tags: "dict | None" = None) -> None:
        self._lock = threading.Lock()
        self._tls = threading.local()
        self._active = False
        # Set by an explicit disable() / KSIM_TRACE=off: ensure_timing's
        # convenience activation must never override an operator's
        # stated choice.
        self._user_disabled = False
        self._ring_on = False  # guarded-by: _lock
        self._jax_bridge = False
        self.out_path: str | None = None
        # Constant after construction (read-only on the hot path, so no
        # lock): args merged into every record, and the out-of-lock
        # record callback.
        self._tags: dict = dict(tags or {})
        self._sink: "Callable[[dict], None] | None" = None
        self._epoch_ns = time.perf_counter_ns()  # guarded-by: _lock
        self._hist: dict[str, LatencyHistogram] = {}  # guarded-by: _lock
        self._counters: dict[str, int] = {}  # guarded-by: _lock
        self._ring: deque = deque(maxlen=65536)  # guarded-by: _lock
        # guarded-by: _lock (ring pressure evidence: dropped = appended - len)
        self._appended = 0
        self._thread_names: dict[int, str] = {}  # guarded-by: _lock
        # Records produced where the lock must not be taken: the gc hook
        # runs at whatever allocation tripped the collection — possibly
        # one made by THIS thread while it holds ``_lock``.  It appends
        # here (atomic, lock-free) and every locked section drains first.
        self._deferred: deque = deque()
        self._stage_end = _StageEnd(self)
        # ``[thread id, children's depth, summed ns]`` while a span of
        # this plane watches its children (``_Span.watch``), else None.
        # Only the watching thread writes the sum.
        self._watch: "list | None" = None

    # -- configuration ---------------------------------------------------

    def enable(self, *, ring: bool = True, out: str | None = None) -> None:
        """Turn the plane on.  ``ring=False`` keeps histograms/counters
        only (no per-record storage); ``out`` arms the atexit Chrome
        export (also settable via ``KSIM_TRACE_OUT``)."""
        with self._lock:
            self._ring_on = ring or out is not None
            if out is not None:
                self.out_path = out
            self._user_disabled = False
            self._active = True

    def disable(self) -> None:
        """One attribute check per site from here on (storage kept;
        ``reset`` clears it).  Sticky against ``ensure_timing``: only an
        explicit ``enable`` turns the plane back on."""
        self._active = False
        self._user_disabled = True

    def reset(self) -> None:
        """Drop all recorded state (test teardown); enablement flags
        and the ring capacity survive."""
        with self._lock:
            self._hist.clear()
            self._counters.clear()
            self._ring.clear()
            self._appended = 0
            self._thread_names.clear()
            self._deferred.clear()
            self._epoch_ns = time.perf_counter_ns()

    def configure_from_env(self, environ=os.environ) -> None:
        """Apply ``KSIM_TRACE*`` (import-time; tests re-invoke)."""
        cap = environ.get("KSIM_TRACE_RING", "")
        if cap:
            try:
                maxlen = max(int(cap), 16)
            except ValueError:
                maxlen = None
            if maxlen is not None:
                # Swap under the lock: a concurrent event() append must
                # never land in an orphaned deque (that record would
                # vanish and the eviction accounting would over-report).
                with self._lock:
                    self._ring = deque(self._ring, maxlen=maxlen)
        self._jax_bridge = environ.get("KSIM_TRACE_JAX", "") == "1"
        out = environ.get("KSIM_TRACE_OUT", "")
        mode = environ.get("KSIM_TRACE", "")
        if mode in ("0", "off"):
            # The operator's opt-out beats everything, including a
            # KSIM_TRACE_OUT a wrapper script may have exported — the
            # same never-override-a-stated-choice contract as
            # ensure_timing vs disable().
            self.disable()
        elif out:
            self.enable(ring=True, out=out)
        elif mode:
            self.enable(ring=(mode != "timing"))

    @property
    def active(self) -> bool:
        return self._active

    @property
    def jax_bridge(self) -> bool:
        """Whether spans also enter ``jax.profiler.TraceAnnotation``
        (``KSIM_TRACE_JAX=1``) — a job's private plane copies it."""
        return self._jax_bridge

    def set_jax_bridge(self, on: bool) -> bool:
        """Switch the ``TraceAnnotation`` bridge; returns the previous
        setting.  ``start_profiling`` turns it on for the capture so an
        operator's profile shows the program's spans, not only XLA's."""
        prev, self._jax_bridge = self._jax_bridge, bool(on)
        return prev

    def set_sink(self, sink: "Callable[[dict], None] | None") -> None:
        """Install (or clear) the record callback.  Set before the plane
        starts receiving records — the hot path reads it unlocked."""
        self._sink = sink

    # -- scoped override -------------------------------------------------

    def scoped(self, plane: "TracePlane | None") -> _PlaneScope:
        """Install ``plane`` as the current thread's recording target
        for ``span``/``event``/``ensure_timing``/``phase_totals`` calls
        on THIS plane (``None`` = a no-op scope).  Used by the job plane
        to give each tenant job a private trace plane without changing
        any call site; the previous scope restores on exit."""
        return _PlaneScope(self, plane)

    def scope(self) -> "TracePlane | None":
        """The current thread's override plane, if any — captured by the
        replay executor before handing work to its dispatch worker so
        the scope survives the thread hop."""
        return getattr(self._tls, "scope", None)

    def scope_tags(self) -> dict:
        """The effective record tags for the calling thread (the
        override plane's, else this plane's) — e.g. the owning job id
        for the compile cache's per-tenant sharing evidence."""
        ov = getattr(self._tls, "scope", None)
        return (ov if ov is not None else self)._tags

    def ensure_timing(self) -> None:
        """Idempotent timing-only activation.  ScenarioRunner calls this
        so per-phase wall-clock totals always exist (the histogram cost
        is two clock reads + one locked increment per span, at
        segment/pass granularity); ring storage stays off unless the
        operator armed it, and an explicit ``disable()`` /
        ``KSIM_TRACE=off`` wins — convenience activation never
        overrides a stated opt-out."""
        ov = getattr(self._tls, "scope", None)
        if ov is not None:
            ov.ensure_timing()
            return
        if not self._active and not self._user_disabled:
            self.enable(ring=False)

    # -- the hot path ----------------------------------------------------

    def span(self, name: str, **args):
        """Open a named span; a no-op singleton when the plane is off
        (the disabled path is one TLS read + one attribute check).  A
        thread-scoped override plane (``scoped``) takes the record
        instead."""
        ov = getattr(self._tls, "scope", None)
        if ov is not None:
            return ov.span(name, **args)
        if not self._active:
            return _NOOP
        return _Span(self, name, args)

    def phase(self, name: str, metrics=None, timer: "str | None" = None, **args):
        """A span that ALSO feeds ``metrics.observe(timer, seconds)``
        from the same pair of clock reads — the scheduling pass's phase
        timers (``Metrics`` timers ``featurize`` / ``engine_*`` /
        ``bind``, served at /api/v1/metrics with tracing off) and the
        timeline are one measurement, not two systems.  Plane off: the
        timer alone runs; plane off and no ``metrics``: the no-op
        singleton.  ``name`` must be a string literal (registry-literals
        lint, like ``span``)."""
        ov = getattr(self._tls, "scope", None)
        if ov is not None:
            return ov.phase(name, metrics, timer, **args)
        if not self._active:
            return _NOOP if metrics is None else metrics.timer(timer)
        sp = _Span(self, name, args)
        if metrics is not None:
            sp._observe = metrics.observe
            sp._timer = timer
        return sp

    def stage(self, name: str):
        """Open a timed STAGE on this thread: the next sequential child
        of whatever span is open (or of none), closed by the next
        ``stage`` at the same level, by ``stage_end``, or by the exit of
        the span (or lap) it was opened in — an exception anywhere
        still closes it there.  Unlike ``lap`` the record goes to the
        per-name histograms (``phase_totals``, a job's ``phases`` and
        ``latency``) and, bridge on, to a ``TraceAnnotation``, but NOT
        to the ring and NOT to the sink: a stage never shows in
        ``ring_records`` / ``GET .../trace``, so the self time of its
        parent — what the benchmark's ``job_span_self`` metrics read —
        stays the parent's whole duration, and a job ring holds no more
        records for it.  A ring span may open and close inside a stage
        (it leaves the stage open).  The open stage is the thread's, so
        a callee (the featurizer) stages its caller's span without
        being handed it.  Returns a context manager that ends the stage
        with its block, for a stretch with no span around it; plane
        off: the no-op singleton.  ``name`` must be a string literal
        (registry-literals lint, like ``span``)."""
        ov = getattr(self._tls, "scope", None)
        if ov is not None:
            return ov.stage(name)
        if not self._active:
            return _NOOP
        now = time.perf_counter_ns()
        tl = self._tls
        depth = getattr(tl, "depth", 0)
        stages = getattr(tl, "stages", None)
        if stages is None:
            stages = tl.stages = []
        elif stages:
            self._close_stages(stages, depth, now)
        ctx = _jax_annotation(name) if self._jax_bridge else None
        stages.append((name, now, depth, ctx))
        return self._stage_end

    def stage_end(self) -> None:
        """Close this thread's open stage of the current level, if any
        (the explicit end of ``stage``)."""
        ov = getattr(self._tls, "scope", None)
        if ov is not None:
            ov.stage_end()
            return
        tl = self._tls
        stages = getattr(tl, "stages", None)
        if stages:
            self._close_stages(
                stages, getattr(tl, "depth", 0), time.perf_counter_ns()
            )

    def _close_stages(self, stages: list, depth: int, now: int) -> None:
        """Close the calling thread's stages opened at ``depth`` or
        deeper (``depth`` = the open spans and laps around the caller),
        innermost first: the timing layer only."""
        while stages and stages[-1][2] >= depth:
            name, t0, _depth, ctx = stages.pop()
            if ctx is not None:
                _jax_annotation_exit(ctx)
            if self._watch is not None:
                self._watch_child(threading.get_ident(), _depth, now - t0)
            with self._lock:
                self._drain_deferred()
                self._time_span(name, t0, now)

    def event(self, name: str, **args) -> None:
        """Record one instant event (counted always; stored when the
        ring is on)."""
        ov = getattr(self._tls, "scope", None)
        if ov is not None:
            ov.event(name, **args)
            return
        if not self._active:
            return
        now = time.perf_counter_ns()
        tid = threading.get_ident()
        if self._tags:
            args = {**self._tags, **args}
        sink = self._sink
        rec = None
        with self._lock:
            self._drain_deferred()
            self._counters[name] = self._counters.get(name, 0) + 1
            if self._ring_on or sink is not None:
                rec = {"ph": "i", "name": name, "t": now, "tid": tid, "args": args}
                if self._ring_on:
                    self._note_thread(tid)
                    self._appended += 1
                    self._ring.append(rec)
        if rec is not None and sink is not None:
            try:
                sink(rec)
            except Exception:  # a broken sink must not break the plane
                pass

    def _record_span(
        self, name: str, t0: int, t1: int, depth: int, args: dict
    ) -> None:
        tid = threading.get_ident()
        if self._watch is not None:
            self._watch_child(tid, depth, t1 - t0)
        if self._tags:
            args = {**self._tags, **args}
        sink = self._sink
        rec = None
        with self._lock:
            self._drain_deferred()
            rec = self._store_span(name, t0, t1, tid, depth, args, sink is not None)
        if rec is not None and sink is not None:
            try:
                sink(rec)
            except Exception:  # a broken sink must not break the plane
                pass

    def _watch_child(self, tid: int, depth: int, ns: int) -> None:
        """A span, lap or stage of ``depth`` closed on thread ``tid``:
        add it to the watching span's sum if it is that span's direct
        child.  A span that closes INSIDE an open stage of the same
        level (``replay.reconcile.evict`` in ``.write``) is the stage's
        time already."""
        w = self._watch
        if w is None or w[1] != depth or w[0] != tid:
            return
        stages = getattr(self._tls, "stages", None)
        if stages and stages[-1][2] == depth:
            return
        w[2] += ns

    def _time_span(self, name: str, t0: int, t1: int) -> None:  # ksimlint: lock-held(_lock)
        """The timing layer alone: one observation in ``name``'s
        histogram (all a stage leaves)."""
        hist = self._hist.get(name)
        if hist is None:
            hist = self._hist[name] = LatencyHistogram()
        hist.observe((t1 - t0) / 1e9)

    def _store_span(  # ksimlint: lock-held(_lock)
        self, name: str, t0: int, t1: int, tid: int, depth: int, args: dict,
        want_rec: bool, tname: "str | None" = None,
    ) -> "dict | None":
        self._time_span(name, t0, t1)
        if not (self._ring_on or want_rec):
            return None
        rec = {
            "ph": "X",
            "name": name,
            "t": t0,
            "d": t1 - t0,
            "tid": tid,
            "depth": depth,
            "args": args,
        }
        if self._ring_on:
            self._note_thread(tid, tname)
            self._appended += 1
            self._ring.append(rec)
        return rec

    def _note_thread(  # ksimlint: lock-held(_lock)
        self, tid: int, tname: "str | None" = None
    ) -> None:
        if tid not in self._thread_names:
            self._thread_names[tid] = tname or threading.current_thread().name

    def _drain_deferred(self) -> None:  # ksimlint: lock-held(_lock)
        """Store the spans the gc hook left (``_defer_span``).  They
        bypass the sink: the SSE feed is a job's progress log, not its
        pause log."""
        while self._deferred:  # only lock holders pop
            name, t0, t1, tid, tname, depth, args = self._deferred.popleft()
            if self._tags:
                args = {**self._tags, **args}
            self._store_span(name, t0, t1, tid, depth, args, False, tname)

    def _defer_span(self, name: str, t0: int, t1: int, args: dict) -> None:
        """Record a span WITHOUT taking the lock (see ``_deferred``);
        it reaches histograms and ring at the next locked section."""
        t = threading.current_thread()
        depth = getattr(self._tls, "depth", 0)
        if self._watch is not None:
            self._watch_child(t.ident, depth, t1 - t0)
        self._deferred.append((name, t0, t1, t.ident, t.name, depth, args))

    # -- evidence --------------------------------------------------------

    def phase_totals(self) -> dict[str, tuple[float, int]]:
        """Per-span-name ``(total_seconds, count)`` — the runner diffs
        two of these around a run for its per-phase breakdown.  Follows
        the thread's scoped override, so a job-scoped run's phase split
        reads the JOB's histograms."""
        ov = getattr(self._tls, "scope", None)
        if ov is not None:
            return ov.phase_totals()
        with self._lock:
            self._drain_deferred()
            return {n: (h.total, h.count) for n, h in self._hist.items()}

    def snapshot(self) -> dict:
        """Histograms + event counters + ring pressure, JSON-ready (the
        ``trace`` section of /api/v1/metrics)."""
        with self._lock:
            self._drain_deferred()
            return {
                "enabled": self._active,
                "ring": {
                    "capacity": self._ring.maxlen,
                    "size": len(self._ring),
                    "appended": self._appended,
                    "evicted": self._appended - len(self._ring),
                },
                "histograms": {n: h.snapshot() for n, h in sorted(self._hist.items())},
                "events": dict(sorted(self._counters.items())),
            }

    def ring_records(self) -> list[dict]:
        """A consistent copy of the ring (tests; the exporter)."""
        with self._lock:
            self._drain_deferred()
            return list(self._ring)

    # -- export ----------------------------------------------------------

    def _chrome_events(self) -> Iterator[dict]:
        with self._lock:
            self._drain_deferred()
            ring = list(self._ring)
            names = dict(self._thread_names)
            epoch = self._epoch_ns
        pid = os.getpid()
        for tid, tname in names.items():
            yield {
                "ph": "M",
                "name": "thread_name",
                "pid": pid,
                "tid": tid,
                "args": {"name": tname},
            }
        for r in ring:
            ev: dict[str, Any] = {
                "name": r["name"],
                "cat": r["name"].partition(".")[0],
                "ph": r["ph"],
                "ts": (r["t"] - epoch) / 1e3,  # µs
                "pid": pid,
                "tid": r["tid"],
                "args": r["args"],
            }
            if r["ph"] == "X":
                ev["dur"] = r["d"] / 1e3
            else:
                ev["s"] = "t"  # instant scoped to its thread
            yield ev

    def export_chrome(self, path: str | None = None) -> dict:
        """Render the ring as a Chrome trace-event document (the JSON
        object format, so Perfetto metadata can ride along); write it
        to ``path`` when given.  Returns the document either way.

        The ``otherData`` metadata carries what the RING cannot: the
        per-phase histogram totals (``phase_totals``) and the eviction
        count, so a consumer of an export whose ring wrapped knows
        exactly how many records were dropped and what the aggregate
        timings were anyway — the "no silent caps" rule
        (docs/observability.md); and ``epoch_unix_s``, the wall-clock
        instant of this plane's perf_counter epoch, which is what lets
        ``merge_chrome_traces`` align exports from different processes
        (each plane's ``ts`` values are relative to its own epoch) on
        one timeline."""
        now_wall = time.time()
        now_ns = time.perf_counter_ns()
        with self._lock:
            self._drain_deferred()
            phase = {
                n: [round(h.total, 6), h.count]
                for n, h in sorted(self._hist.items())
            }
            appended = self._appended
            size = len(self._ring)
            epoch = self._epoch_ns
        doc = {
            "traceEvents": list(self._chrome_events()),
            "displayTimeUnit": "ms",
            "otherData": {
                "producer": "ksim_tpu.obs",
                "pid": os.getpid(),
                "epoch_unix_s": round(now_wall - (now_ns - epoch) / 1e9, 6),
                "phase_totals": phase,
                "ring": {
                    "appended": appended,
                    "size": size,
                    "evicted": appended - size,
                },
            },
        }
        if path:
            # Crash-atomic, same discipline as lease/journal compaction:
            # a reader (the fleet trace merge) never sees a torn file.
            tmp = f"{path}.tmp{os.getpid()}"
            with open(tmp, "w") as f:
                json.dump(doc, f)
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, path)
        return doc


# ---------------------------------------------------------------------------
# Stats providers (non-timing evidence merged into /api/v1/metrics)
# ---------------------------------------------------------------------------

_providers: dict[str, Callable[[], dict]] = {}  # guarded-by: _providers_lock
_providers_lock = threading.Lock()

#: Top-level sections of the merged /api/v1/metrics document that a
#: provider must not shadow (the endpoint merges providers at the top
#: level, so a collision would silently clobber a core section).
RESERVED_PROVIDER_NAMES = frozenset({"counters", "timings", "trace", "faults"})


def register_provider(name: str, fn: Callable[[], dict]) -> None:
    """Register (or replace) a named evidence provider.  The metrics
    endpoint snapshots every provider per GET — e.g. the CURRENT run's
    ``ReplayDriver.stats()`` registers under ``"replay"`` (latest
    driver wins; one driver exists per ScenarioRunner run)."""
    if name in RESERVED_PROVIDER_NAMES:
        raise ValueError(
            f"provider name {name!r} shadows a core /api/v1/metrics section"
        )
    with _providers_lock:
        _providers[name] = fn


def provider_snapshots() -> dict[str, dict]:
    """All providers' current snapshots; a provider that raises reports
    its error instead of poisoning the metrics document."""
    with _providers_lock:
        items = list(_providers.items())
    out: dict[str, dict] = {}
    for name, fn in items:
        try:
            out[name] = fn()
        except Exception as e:  # evidence endpoint must never 500
            out[name] = {"error": f"{type(e).__name__}: {e}"}
    return out


#: The process-global plane every span/event site checks.  ``KSIM_TRACE*``
#: configures it at import so subprocess children (fleet workers, the
#: make trace children) inherit tracing through the environment — a
#: stdlib-only parent never has to import this module.
TRACE = TracePlane()
TRACE.configure_from_env()


# ---------------------------------------------------------------------------
# Process-level runtime evidence: full garbage collections, XLA compiles
# ---------------------------------------------------------------------------

#: ``/api/v1/metrics`` names (merged into ``counters`` / ``timings``
#: beside the scheduler's own; ``runtime_totals`` flattens them for the
#: job result's ``runtime`` block).
_runtime_lock = threading.Lock()
_runtime_counters = {"xla_compiles": 0, "xla_cache_loads": 0}  # guarded-by: _runtime_lock
_runtime_hists = {  # guarded-by: _runtime_lock
    # Every full collection, and beside it those the job plane ran on
    # purpose (``collect_scheduled``): the difference is what CPython's
    # growth trigger fired inside somebody's request.
    "gc_gen2": LatencyHistogram(),
    "gc_scheduled": LatencyHistogram(),
    "xla_compile": LatencyHistogram(),
}
#: Finished full collections not yet folded into the histograms: the gc
#: hook appends ``(seconds, scheduled)`` here lock-free (it may run on a
#: thread that holds ``_runtime_lock``), readers fold under the lock.
_gc_done: deque = deque()
_gc_open: "tuple | None" = None  # (t0_ns, plane, jax_ctx, scheduled) of the running full collection
_gc_scheduled = False  # True while collect_scheduled() is inside gc.collect()
#: One scheduled collection at a time: ``gc.collect()`` called while another
#: thread is inside a collection (its callbacks and finalizers let the GIL
#: go) returns at once, having collected nothing and called no hook — two
#: jobs that end together would leave one of them without its collection.
_gc_scheduled_lock = threading.Lock()


def _gc_callback(phase: str, info: dict) -> None:
    if info["generation"] < 2:
        return
    # Collections never nest and run under the GIL, so one module slot
    # holds the open one.  No lock may be taken here and nothing may
    # raise: this runs inside whatever allocation tripped the collector.
    global _gc_open
    if phase == "start":
        ov = getattr(TRACE._tls, "scope", None)
        plane = ov if ov is not None else TRACE
        ctx = None
        if not plane._active:
            plane = None
        elif plane._jax_bridge:
            ctx = _jax_annotation("service.gc")
        _gc_open = (time.perf_counter_ns(), plane, ctx, _gc_scheduled)
        return
    opened, _gc_open = _gc_open, None
    if opened is None:
        return
    t1 = time.perf_counter_ns()
    t0, plane, ctx, scheduled = opened
    if ctx is not None:
        _jax_annotation_exit(ctx)
    _gc_done.append(((t1 - t0) / 1e9, scheduled))
    if plane is not None:
        plane._defer_span(
            "service.gc", t0, t1,
            {
                "generation": info["generation"],
                "collected": info.get("collected", 0),
                "scheduled": scheduled,
            },
        )


gc.callbacks.append(_gc_callback)


def collect_scheduled() -> int:
    """One full collection run on purpose, at a point its caller chose
    (the job plane: at a job's end, once its graph is let go and the
    heap is smallest — jobs/manager.py).  Counted like any full collection
    (``gc_gen2``, the ``service.gc`` span) and once more under
    ``gc_scheduled``.  Returns what ``gc.collect`` returns."""
    global _gc_scheduled
    with _gc_scheduled_lock:
        _gc_scheduled = True
        try:
            return gc.collect()
        finally:
            _gc_scheduled = False


def _fold_gc() -> None:  # ksimlint: lock-held(_runtime_lock)
    every, scheduled = _runtime_hists["gc_gen2"], _runtime_hists["gc_scheduled"]
    while _gc_done:  # only lock holders pop
        seconds, was_scheduled = _gc_done.popleft()
        every.observe(seconds)
        if was_scheduled:
            scheduled.observe(seconds)


def note_xla_compile(seconds: float) -> None:
    """XLA compiled a program on this thread (``ksim_tpu.util``'s
    ``jax.monitoring`` listener; a persistent-cache load is
    ``note_xla_cache_load`` instead)."""
    with _runtime_lock:
        _runtime_counters["xla_compiles"] += 1
        _runtime_hists["xla_compile"].observe(seconds)
    TRACE.event("engine.compile", seconds=round(seconds, 6))


def note_xla_cache_load() -> None:
    """An executable came from JAX's persistent compilation cache."""
    with _runtime_lock:
        _runtime_counters["xla_cache_loads"] += 1


def runtime_snapshot() -> dict:
    """``{"counters", "timings"}`` of the process-level evidence, in the
    shape of ``Metrics.snapshot()`` — /api/v1/metrics merges the two."""
    with _runtime_lock:
        _fold_gc()
        return {
            "counters": dict(_runtime_counters),
            "timings": {n: h.snapshot() for n, h in _runtime_hists.items()},
        }


def runtime_totals() -> dict:
    """The same evidence as flat cumulative numbers — two readings
    around a job give its ``runtime`` block (``runtime_growth``)."""
    with _runtime_lock:
        _fold_gc()
        gc2, xla = _runtime_hists["gc_gen2"], _runtime_hists["xla_compile"]
        sched = _runtime_hists["gc_scheduled"]
        return {
            "gc_gen2_collections": gc2.count,
            "gc_gen2_pause_s": gc2.total,
            "gc_scheduled_collections": sched.count,
            "gc_scheduled_pause_s": sched.total,
            "xla_compiles": _runtime_counters["xla_compiles"],
            "xla_compile_s": xla.total,
            "xla_cache_loads": _runtime_counters["xla_cache_loads"],
        }


def runtime_growth(before: dict) -> dict:
    """``runtime_totals()`` now minus ``before``.  Process-wide: with
    several jobs in flight each one's block also holds the others'
    collections and compiles."""
    now = runtime_totals()
    return {k: round(v - before.get(k, 0), 6) for k, v in now.items()}


# ---------------------------------------------------------------------------
# Fleet observability plane (docs/observability.md "Fleet observability")
#
# Each fleet member publishes its merged evidence document
# crash-atomically to KSIM_JOBS_DIR/obs/<worker_id>.json on a cadence
# (KSIM_OBS_PUBLISH_S; the obs-publisher thread in jobs/fleet.py) and
# once at clean shutdown; the front door folds every published snapshot
# into one fleet-scope document (counters sum, histograms merge
# bucket-wise exactly) and renders either scope as Prometheus text
# exposition.  Everything here is stdlib-only, like the rest of the
# module.
# ---------------------------------------------------------------------------

#: Subdirectory of KSIM_JOBS_DIR holding published worker snapshots.
#: Created lazily by the FIRST publish — with publishing off
#: (KSIM_OBS_PUBLISH_S=0) it never appears.
OBS_DIR = "obs"

_STARTED_AT = time.time()
_seq_lock = threading.Lock()
_publish_seq = 0  # guarded-by: _seq_lock


def next_publish_seq() -> int:
    """Monotonic per-process snapshot sequence number — lets a consumer
    of ``obs/<worker_id>.json`` distinguish "worker restarted" (seq
    reset) from "worker stalled" (seq frozen, published_at aging)."""
    global _publish_seq
    with _seq_lock:
        _publish_seq += 1
        return _publish_seq


_NO_DEVICE = {"platform": None, "device_kind": None, "device_count": None}
_device = _NO_DEVICE  # replaced whole by note_device, never mutated


def note_device(platform: str, device_kind: str, device_count: int) -> bool:
    """Record the JAX backend this process computes on.  This module is
    stdlib-only, so whoever just completed a dispatch passes the values
    in (``ksim_tpu.util.note_backend``) — reading them earlier would
    force a backend init on the caller's thread.  Returns True the
    first time (the caller logs it once)."""
    global _device
    first = _device is _NO_DEVICE
    _device = {
        "platform": platform,
        "device_kind": device_kind,
        "device_count": device_count,
    }
    return first


def device_identity() -> dict:
    """``platform`` / ``device_kind`` / ``device_count`` of the backend
    that did this process's work; all ``None`` until a dispatch ran."""
    return dict(_device)


def process_identity(
    *, role: "str | None" = None, worker_id: "str | None" = None
) -> dict:
    """The process-identity block every metrics document carries (solo
    ``/api/v1/metrics`` and published fleet snapshots alike): who
    produced this evidence, from which process, alive since when, and
    on which backend (a server that came up on JAX's CPU fallback must
    be distinguishable from one that holds the chip)."""
    return {
        "role": role or "solo",
        "worker_id": worker_id or f"w{os.getpid()}",
        "pid": os.getpid(),
        "started_at": round(_STARTED_AT, 3),
        "uptime_s": round(time.time() - _STARTED_AT, 3),
        **_device,
    }


def _atomic_json(path: str, doc: dict) -> None:
    """tmp + fsync + os.replace — the journal-compaction discipline
    (jobs/fleet.py ``LeasePlane.maybe_compact``): a crashed writer
    leaves the previous snapshot intact, never a torn file."""
    tmp = f"{path}.tmp{os.getpid()}"
    with open(tmp, "w", encoding="utf-8") as f:
        json.dump(doc, f)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)


def publish_snapshot(
    jobs_dir: str,
    doc: dict,
    *,
    worker_id: str,
    trace_doc: "dict | None" = None,
) -> str:
    """Write one worker's telemetry snapshot (and optionally its merged
    Chrome trace export) crash-atomically under ``<jobs_dir>/obs/``.
    Returns the snapshot path."""
    with TRACE.span("obs.publish", worker=worker_id):
        obs_dir = os.path.join(jobs_dir, OBS_DIR)
        os.makedirs(obs_dir, exist_ok=True)
        path = os.path.join(obs_dir, f"{worker_id}.json")
        _atomic_json(path, doc)
        if trace_doc is not None:
            _atomic_json(
                os.path.join(obs_dir, f"{worker_id}.trace.json"), trace_doc
            )
        return path


def _read_json_docs(obs_dir: str, suffix: str) -> "dict[str, dict]":
    out: dict[str, dict] = {}
    try:
        names = sorted(os.listdir(obs_dir))
    except OSError:
        return out
    for name in names:
        if not name.endswith(suffix):
            continue
        if suffix == ".json" and name.endswith(".trace.json"):
            continue
        try:
            with open(
                os.path.join(obs_dir, name), "r", encoding="utf-8"
            ) as f:
                doc = json.load(f)
        except (OSError, ValueError):
            continue  # mid-replace or torn: the previous read stands
        if isinstance(doc, dict):
            out[name[: -len(suffix)]] = doc
    return out


def read_fleet_snapshots(jobs_dir: str) -> "dict[str, dict]":
    """All published worker snapshots, by worker id.  Unreadable files
    are skipped (a concurrent os.replace can momentarily lose the race
    with listdir); staleness judgment belongs to ``merge_fleet_docs``,
    not here."""
    return _read_json_docs(os.path.join(jobs_dir, OBS_DIR), ".json")


def read_fleet_traces(jobs_dir: str) -> "dict[str, dict]":
    """All published worker Chrome-trace exports, by worker id."""
    return _read_json_docs(os.path.join(jobs_dir, OBS_DIR), ".trace.json")


def merge_fleet_docs(
    docs: "dict[str, dict]",
    *,
    now: "float | None" = None,
    stale_after: "float | None" = None,
) -> dict:
    """Fold per-worker snapshot documents into ONE fleet document:
    counters and event/fault counts SUM; latency histograms (Metrics
    timings and trace-plane span histograms alike) merge bucket-wise
    exactly into ``timings``; each worker's full document survives
    under ``workers[<id>]`` with its identity block plus ``stale_s`` /
    ``stale`` — a dead worker is FLAGGED (and an ``obs.snapshot_stale``
    event fires), never silently dropped.  A snapshot is stale past
    ``stale_after`` seconds (default: 3x its own published cadence,
    floored at 1 s)."""
    with TRACE.span("obs.fleet_merge", workers=len(docs)):
        if now is None:
            now = time.time()
        workers: dict[str, dict] = {}
        counters: dict[str, float] = {}
        events: dict[str, int] = {}
        faults: dict[str, dict] = {}
        hists: dict[str, LatencyHistogram] = {}
        for wid in sorted(docs):
            doc = docs[wid]
            ident = doc.get("process") or {}
            published = float(ident.get("published_at") or 0.0)
            cadence = float(ident.get("publish_s") or 0.0) or 10.0
            stale_s = max(0.0, now - published) if published else None
            limit = (
                stale_after
                if stale_after is not None
                else max(3.0 * cadence, 1.0)
            )
            stale = stale_s is None or stale_s > limit
            if stale:
                TRACE.event(
                    "obs.snapshot_stale",
                    worker=wid,
                    stale_s=None if stale_s is None else round(stale_s, 3),
                )
            wdoc = dict(doc)
            wdoc["stale"] = stale
            wdoc["stale_s"] = (
                None if stale_s is None else round(stale_s, 3)
            )
            workers[wid] = wdoc
            for name, v in (doc.get("counters") or {}).items():
                if isinstance(v, (int, float)):
                    counters[name] = counters.get(name, 0) + v
            trace = doc.get("trace") or {}
            for name, v in (trace.get("events") or {}).items():
                if isinstance(v, (int, float)):
                    events[name] = events.get(name, 0) + int(v)
            for section in (
                doc.get("timings") or {},
                trace.get("histograms") or {},
            ):
                for name, snap in section.items():
                    if isinstance(snap, dict):
                        hists.setdefault(
                            name, LatencyHistogram()
                        ).merge_snapshot(snap)
            for site, c in (doc.get("faults") or {}).items():
                if not isinstance(c, dict):
                    continue
                agg = faults.setdefault(site, {"calls": 0, "fired": 0})
                agg["calls"] += int(c.get("calls") or 0)
                agg["fired"] += int(c.get("fired") or 0)
        return {
            "scope": "fleet",
            "generated_at": round(now, 3),
            "workers": workers,
            "counters": counters,
            "timings": {n: h.snapshot() for n, h in sorted(hists.items())},
            "trace": {"events": events},
            "faults": faults,
        }


def _flow_events(events: "list[dict]") -> "list[dict]":
    """Chrome flow events (``s``/``t``/``f`` phases) stitching each
    job's ``jobs.enqueue`` -> ``jobs.fleet_claim`` -> ``jobs.run``
    records into one arrow across process lanes.  Only COMPLETE triples
    emit — a partial chain (job still queued, ring evicted an anchor)
    draws no arrow rather than a misleading stub."""
    anchors: dict[str, dict] = {}
    want = {
        "jobs.enqueue": "s",
        "jobs.fleet_claim": "t",
        "jobs.run": "f",
    }
    for ev in events:
        ph = want.get(ev.get("name") or "")
        if ph is None:
            continue
        args = ev.get("args") or {}
        jid = args.get("job")
        if not isinstance(jid, str):
            continue
        anchors.setdefault(jid, {}).setdefault(ph, ev)
    out: list[dict] = []
    for idx, jid in enumerate(sorted(anchors)):
        chain = anchors[jid]
        if len(chain) != 3:
            continue
        for ph in ("s", "t", "f"):
            ev = chain[ph]
            rec = {
                "ph": ph,
                "name": "jobs.flow",
                "cat": "jobs",
                "id": idx + 1,
                "ts": ev.get("ts", 0),
                "pid": ev.get("pid"),
                "tid": ev.get("tid"),
                "args": {"job": jid},
            }
            if ph == "f":
                rec["bp"] = "e"  # bind the arrow end to the run slice
            out.append(rec)
    return out


def merge_chrome_traces(
    docs: "dict[str, dict]", *, flows: bool = False
) -> dict:
    """Merge per-process Chrome trace exports into ONE document with
    one process lane per worker.  Each export's ``ts`` values are
    relative to its own plane's perf_counter epoch; the exports'
    ``epoch_unix_s`` anchors rebase them all onto the EARLIEST epoch,
    so cross-process ordering is honest to wall-clock sync.  The
    merged document records its own base epoch, so merges compose
    (a worker's local global+per-job merge feeds the frontdoor's
    fleet merge).  ``flows=True`` additionally synthesizes the
    submit->claim->run flow arrows (``_flow_events``)."""
    with TRACE.span("obs.fleet_merge", traces=len(docs)):
        epochs: dict[str, float] = {}
        for wid, doc in docs.items():
            od = doc.get("otherData") or {}
            try:
                epochs[wid] = float(od.get("epoch_unix_s") or 0.0)
            except (TypeError, ValueError):
                epochs[wid] = 0.0
        known = [e for e in epochs.values() if e]
        base = min(known) if known else 0.0
        merged: list[dict] = []
        lane_names: dict = {}  # pid -> worker id (first wins)
        named: set = set()  # pids already carrying process_name metadata
        for wid in sorted(docs):
            doc = docs[wid]
            od = doc.get("otherData") or {}
            doc_pid = od.get("pid")
            off_us = (epochs[wid] - base) * 1e6 if epochs[wid] else 0.0
            for ev in doc.get("traceEvents") or ():
                ev = dict(ev)
                pid = ev.get("pid", doc_pid)
                if ev.get("ph") == "M" and ev.get("name") == "process_name":
                    named.add(pid)
                elif pid is not None and pid not in lane_names:
                    lane_names[pid] = wid
                if "ts" in ev and off_us:
                    ev["ts"] = ev["ts"] + off_us
                merged.append(ev)
        meta = [
            {
                "ph": "M",
                "name": "process_name",
                "pid": pid,
                "tid": 0,
                "args": {"name": wid},
            }
            for pid, wid in lane_names.items()
            if pid not in named
        ]
        events = meta + merged
        if flows:
            events = events + _flow_events(merged)
        return {
            "traceEvents": events,
            "displayTimeUnit": "ms",
            "otherData": {
                "producer": "ksim_tpu.obs",
                "pid": os.getpid(),
                "merged": sorted(docs),
                "epoch_unix_s": base,
            },
        }


# -- Prometheus text exposition ---------------------------------------------


def _escape_label(value) -> str:
    return (
        str(value)
        .replace("\\", "\\\\")
        .replace('"', '\\"')
        .replace("\n", "\\n")
    )


def _fmt_value(v) -> str:
    f = float(v)
    if f != f:
        return "NaN"
    if f == float("inf"):
        return "+Inf"
    if f == float("-inf"):
        return "-Inf"
    return format(f, ".10g")


def _fmt_edge(edge: float) -> str:
    return format(edge, ".9g")


def _sample_line(name: str, labels: dict, value) -> str:
    if labels:
        body = ",".join(
            f'{k}="{_escape_label(v)}"' for k, v in labels.items()
        )
        return f"{name}{{{body}}} {_fmt_value(value)}"
    return f"{name} {_fmt_value(value)}"


def _emit_histogram(
    out: "list[tuple[str, dict, Any]]", family: str, labels: dict, snap: dict
) -> None:
    """Expand one LatencyHistogram snapshot into cumulative ``_bucket``
    samples over EVERY fixed edge (plus ``+Inf``), ``_sum`` and
    ``_count`` — the native Prometheus histogram shape, ``le``
    semantics matching ``observe``'s bisect_left exactly."""
    counts = [0] * (len(LatencyHistogram.EDGES) + 1)
    for edge, c in snap.get("buckets") or ():
        i = len(LatencyHistogram.EDGES) if edge is None else _EDGE_INDEX[edge]
        counts[i] += int(c)
    cum = 0
    for i, edge in enumerate(LatencyHistogram.EDGES):
        cum += counts[i]
        out.append(
            (f"{family}_bucket", {**labels, "le": _fmt_edge(edge)}, cum)
        )
    cum += counts[-1]
    out.append((f"{family}_bucket", {**labels, "le": "+Inf"}, cum))
    out.append((f"{family}_sum", labels, snap.get("total_seconds") or 0.0))
    out.append((f"{family}_count", labels, snap.get("count") or 0))


def _expose_section(
    samples: "dict[str, list]", doc: dict, labels: dict
) -> None:
    """Render one solo-shaped metrics document (a worker snapshot or
    the serving process's own document) into per-family samples."""
    for name, v in sorted((doc.get("counters") or {}).items()):
        if isinstance(v, (int, float)):
            samples["ksim_counter_total"].append(
                ("ksim_counter_total", {**labels, "name": name}, v)
            )
    trace = doc.get("trace") or {}
    for name, v in sorted((trace.get("events") or {}).items()):
        if isinstance(v, (int, float)):
            samples["ksim_event_total"].append(
                ("ksim_event_total", {**labels, "name": name}, v)
            )
    ring = trace.get("ring") or {}
    if ring:
        samples["ksim_trace_ring_evicted_total"].append(
            (
                "ksim_trace_ring_evicted_total",
                labels,
                ring.get("evicted") or 0,
            )
        )
    merged_hists = dict(doc.get("timings") or {})
    merged_hists.update(trace.get("histograms") or {})
    for name in sorted(merged_hists):
        snap = merged_hists[name]
        if isinstance(snap, dict):
            _emit_histogram(
                samples["ksim_latency_seconds"],
                "ksim_latency_seconds",
                {**labels, "site": name},
                snap,
            )
    for site, c in sorted((doc.get("faults") or {}).items()):
        if not isinstance(c, dict):
            continue
        samples["ksim_fault_calls_total"].append(
            (
                "ksim_fault_calls_total",
                {**labels, "site": site},
                c.get("calls") or 0,
            )
        )
        samples["ksim_fault_fired_total"].append(
            (
                "ksim_fault_fired_total",
                {**labels, "site": site},
                c.get("fired") or 0,
            )
        )
    jobs = doc.get("jobs") or {}
    q = jobs.get("queue") or {}
    if q:
        samples["ksim_queue_depth"].append(
            ("ksim_queue_depth", labels, q.get("depth") or 0)
        )
        samples["ksim_queue_capacity"].append(
            ("ksim_queue_capacity", labels, q.get("capacity") or 0)
        )
    w = jobs.get("workers") or {}
    if w:
        samples["ksim_workers_pool"].append(
            ("ksim_workers_pool", labels, w.get("pool") or 0)
        )
        samples["ksim_workers_active"].append(
            ("ksim_workers_active", labels, w.get("active") or 0)
        )
    replay = doc.get("replay") or {}
    if isinstance(replay, dict) and "breaker_tripped" in replay:
        samples["ksim_breaker_open"].append(
            (
                "ksim_breaker_open",
                labels,
                1.0 if replay["breaker_tripped"] else 0.0,
            )
        )
    ident = doc.get("process") or {}
    if "uptime_s" in ident:
        samples["ksim_uptime_seconds"].append(
            ("ksim_uptime_seconds", labels, ident["uptime_s"])
        )


def render_prometheus(doc: dict) -> str:
    """Render a metrics document — solo (``/api/v1/metrics`` shape) or
    fleet (``merge_fleet_docs`` shape) — as Prometheus text exposition.
    Fleet scope renders PER-WORKER series only (``worker``/``role``
    labels); a scraper's ``sum()`` re-derives the fleet totals, so
    nothing is double-counted.  ``parse_prometheus`` round-trips and
    validates this output in-suite."""
    samples: dict[str, list] = {f["name"]: [] for f in _EXPO_FAMILIES}
    if doc.get("scope") == "fleet":
        for wid, wdoc in sorted((doc.get("workers") or {}).items()):
            ident = wdoc.get("process") or {}
            labels = {
                "worker": str(ident.get("worker_id") or wid),
                "role": str(ident.get("role") or ""),
            }
            _expose_section(samples, wdoc, labels)
            stale_s = wdoc.get("stale_s")
            if stale_s is not None:
                samples["ksim_snapshot_age_seconds"].append(
                    ("ksim_snapshot_age_seconds", labels, stale_s)
                )
            samples["ksim_up"].append(
                ("ksim_up", labels, 0.0 if wdoc.get("stale") else 1.0)
            )
    else:
        ident = doc.get("process") or {}
        labels = {
            "worker": str(ident.get("worker_id") or f"w{os.getpid()}"),
            "role": str(ident.get("role") or "solo"),
        }
        _expose_section(samples, doc, labels)
        samples["ksim_up"].append(("ksim_up", labels, 1.0))
    lines: list[str] = []
    for fam in _EXPO_FAMILIES:
        rows = samples[fam["name"]]
        if not rows:
            continue
        lines.append(f"# HELP {fam['name']} {fam['help']}")
        lines.append(f"# TYPE {fam['name']} {fam['kind']}")
        for name, labels, value in rows:
            lines.append(_sample_line(name, labels, value))
    return "\n".join(lines) + "\n"


_NAME_START = frozenset(
    "abcdefghijklmnopqrstuvwxyzABCDEFGHIJKLMNOPQRSTUVWXYZ_:"
)
_NAME_CHARS = _NAME_START | frozenset("0123456789")


def _parse_sample(line: str) -> "tuple[str, dict, float]":
    """Strict parse of one exposition sample line."""
    i = 0
    n = len(line)
    if not line or line[0] not in _NAME_START:
        raise ValueError(f"bad metric name: {line!r}")
    while i < n and line[i] in _NAME_CHARS:
        i += 1
    name = line[:i]
    labels: dict[str, str] = {}
    if i < n and line[i] == "{":
        i += 1
        while True:
            if i >= n:
                raise ValueError(f"unterminated label set: {line!r}")
            if line[i] == "}":
                i += 1
                break
            j = i
            while j < n and line[j] in _NAME_CHARS:
                j += 1
            key = line[i:j]
            if (
                not key
                or j + 1 >= n
                or line[j] != "="
                or line[j + 1] != '"'
            ):
                raise ValueError(f"bad label at col {i}: {line!r}")
            j += 2
            buf: list[str] = []
            while j < n and line[j] != '"':
                if line[j] == "\\":
                    if j + 1 >= n:
                        raise ValueError(f"bad escape: {line!r}")
                    esc = line[j + 1]
                    buf.append(
                        {"n": "\n", "\\": "\\", '"': '"'}.get(esc, esc)
                    )
                    j += 2
                else:
                    buf.append(line[j])
                    j += 1
            if j >= n:
                raise ValueError(f"unterminated label value: {line!r}")
            labels[key] = "".join(buf)
            j += 1
            if j < n and line[j] == ",":
                j += 1
            i = j
    rest = line[i:].strip()
    if not rest:
        raise ValueError(f"sample has no value: {line!r}")
    value_str = rest.split()[0]
    if value_str == "+Inf":
        value = float("inf")
    elif value_str == "-Inf":
        value = float("-inf")
    else:
        value = float(value_str)
    return name, labels, value


def parse_prometheus(text: str) -> "dict[str, dict]":
    """Stdlib validator for the exposition format: every sample must
    follow a ``# TYPE`` for its family, histogram samples must carry
    coherent ``le`` labels (cumulative, non-decreasing, ``+Inf``
    present and equal to ``_count``).  Returns families with their
    parsed samples; raises ``ValueError`` on any violation — the
    golden test pins the format by parser, not by hope."""
    families: dict[str, dict] = {}
    for lineno, line in enumerate(text.splitlines(), 1):
        if not line.strip():
            continue
        if line.startswith("# HELP "):
            name, _, help_ = line[len("# HELP "):].partition(" ")
            families.setdefault(
                name, {"kind": None, "help": None, "samples": []}
            )["help"] = help_
            continue
        if line.startswith("# TYPE "):
            name, _, kind = line[len("# TYPE "):].partition(" ")
            fam = families.setdefault(
                name, {"kind": None, "help": None, "samples": []}
            )
            if fam["samples"]:
                raise ValueError(
                    f"line {lineno}: TYPE for {name!r} after its samples"
                )
            fam["kind"] = kind.strip()
            continue
        if line.startswith("#"):
            continue  # free comment
        try:
            name, labels, value = _parse_sample(line)
        except ValueError as e:
            raise ValueError(f"line {lineno}: {e}") from None
        base = name
        for suffix in ("_bucket", "_sum", "_count"):
            if name.endswith(suffix):
                cand = name[: -len(suffix)]
                if families.get(cand, {}).get("kind") == "histogram":
                    base = cand
                    break
        fam = families.get(base)
        if fam is None or not fam["kind"]:
            raise ValueError(
                f"line {lineno}: sample {name!r} has no preceding # TYPE"
            )
        if (
            fam["kind"] == "histogram"
            and name.endswith("_bucket")
            and "le" not in labels
        ):
            raise ValueError(
                f"line {lineno}: histogram bucket without le label"
            )
        fam["samples"].append({"name": name, "labels": labels, "value": value})
    for fname, fam in families.items():
        if fam["kind"] != "histogram":
            continue
        series: dict[tuple, dict] = {}
        for sample in fam["samples"]:
            name, labels, value = (
                sample["name"], sample["labels"], sample["value"]
            )
            key = tuple(
                sorted((k, v) for k, v in labels.items() if k != "le")
            )
            ent = series.setdefault(key, {"buckets": [], "count": None})
            if name.endswith("_bucket"):
                le = labels["le"]
                ent["buckets"].append(
                    (float("inf") if le == "+Inf" else float(le), value)
                )
            elif name.endswith("_count"):
                ent["count"] = value
        for key, ent in series.items():
            buckets = sorted(ent["buckets"])
            if not buckets or buckets[-1][0] != float("inf"):
                raise ValueError(
                    f"{fname}{dict(key)}: histogram missing +Inf bucket"
                )
            prev = -1.0
            for le, v in buckets:
                if v < prev:
                    raise ValueError(
                        f"{fname}{dict(key)}: bucket counts decrease at "
                        f"le={le}"
                    )
                prev = v
            if ent["count"] is not None and buckets[-1][1] != ent["count"]:
                raise ValueError(
                    f"{fname}{dict(key)}: +Inf bucket != _count"
                )
    return families


@atexit.register
def _export_at_exit() -> None:
    if TRACE.out_path and TRACE.active:
        try:
            TRACE.export_chrome(TRACE.out_path)
        except OSError:
            pass
