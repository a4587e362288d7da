"""Streaming-windowed trace ingest (round 22, ksim_tpu/traces/stream).

The golden property everything here leans on: the windowed producer is
BYTE-IDENTICAL to the materialized pipeline — same selection
(StreamSelector == resample, any feed order), same compiled operation
sequence (window boundaries are invisible), same degraded output when a
producer fault reroutes through the materialized batch path.  Plus the
early-refusal satellite: an event/node bound provably blown mid-read
stops consuming the source instead of compiling it whole.
"""

from __future__ import annotations

import json
import random

import pytest

from ksim_tpu.traces import (
    StreamSelector,
    TraceBoundExceeded,
    TraceOperationStream,
    stream_trace_operations,
    trace_operations,
)
from ksim_tpu.traces.resample import resample
from ksim_tpu.traces.schema import TraceRecord

FIXTURES = "tests/fixtures/traces"


def _mk_records(n: int, seed: int) -> list[TraceRecord]:
    rng = random.Random(seed)
    return [
        TraceRecord(
            name=f"t{i}",
            arrival_s=round(rng.uniform(0, 1000), 3),
            cpu_milli=rng.randrange(100, 4000),
            mem_mib=rng.randrange(128, 8192),
            lifetime_s=rng.choice((0.0, round(rng.uniform(1, 500), 3))),
            tier=rng.randrange(5),
            priority=rng.randrange(450),
            kind=rng.choice(("batch", "service")),
        )
        for i in range(n)
    ]


# ---------------------------------------------------------------------------
# StreamSelector == resample (order-independent selection)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "max_events,target_nodes,source_nodes",
    [
        (0, None, None),  # keep everything
        (60, None, None),  # budget only
        (60, 10, 40),  # budget + rescale
        (0, 10, 40),  # rescale only
    ],
)
def test_stream_selector_matches_resample_any_order(
    max_events, target_nodes, source_nodes
):
    """The selection rule is a pure function of the record SET: feeding
    the stream selector a shuffled permutation yields byte-identically
    what batch resample computes on the original order."""
    records = _mk_records(500, 3)
    batch = resample(
        records,
        seed=7,
        max_events=max_events,
        target_nodes=target_nodes,
        source_nodes=source_nodes,
    )
    shuffled = list(records)
    random.Random(99).shuffle(shuffled)
    sel = StreamSelector(
        seed=7,
        max_events=max_events,
        target_nodes=target_nodes,
        source_nodes=source_nodes,
    )
    sel.feed_all(shuffled)
    assert sel.finish() == batch


def test_stream_selector_heap_is_bounded_by_budget():
    """Budgeted mode holds at most B+1 candidates however long the
    stream runs — the O(window) memory claim's selection half."""
    sel = StreamSelector(seed=0, max_events=40)
    for rec in _mk_records(2000, 11):
        sel.feed(rec)
        assert len(sel._heap) <= 41
    assert sel.finish() == resample(_mk_records(2000, 11), seed=0, max_events=40)


# ---------------------------------------------------------------------------
# Windowed == materialized on the bundled fixtures
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "fname,fmt",
    [("borg_mini.jsonl", "borg"), ("alibaba_batch_mini.csv", "alibaba")],
)
@pytest.mark.parametrize("window", [1, 3, 64])
def test_windowed_stream_equals_materialized_fixture(fname, fmt, window):
    path = f"{FIXTURES}/{fname}"
    kw = dict(nodes=6, ops_per_step=3, max_events=30, seed=0)
    mat = trace_operations(path, fmt, **kw)
    stream = stream_trace_operations(
        path, fmt, window=window, queue_windows=2, **kw
    )
    assert list(stream) == mat
    stats = stream.stats()
    assert stats["fallback"] == 0
    assert stats["ops"] == len(mat)
    assert stats["windows"] == -(-len(mat) // window)  # ceil division


def test_window_boundary_splits_a_create_delete_pair():
    """A create and its delete landing in DIFFERENT windows must not
    perturb the stream — window boundaries are a transport detail, not
    a semantic one."""
    lines = []
    for i in range(6):
        lines.append(
            json.dumps(
                {
                    "time": i * 1_000_000,
                    "type": "SUBMIT",
                    "collection_id": i,
                    "instance_index": 0,
                    "priority": 0,
                    "resource_request": {"cpus": 0.01, "memory": 0.01},
                }
            )
        )
        lines.append(
            json.dumps(
                {
                    "time": i * 1_000_000 + 500_000,
                    "type": "FINISH",
                    "collection_id": i,
                    "instance_index": 0,
                }
            )
        )
    kw = dict(nodes=2, ops_per_step=2, seed=0)
    mat = trace_operations(lines, "borg", **kw)
    window = 3
    creates = [
        i for i, op in enumerate(mat) if op.kind == "pods" and op.op == "create"
    ]
    deletes = {
        op.name: i
        for i, op in enumerate(mat)
        if op.kind == "pods" and op.op == "delete"
    }
    split = [
        (i, deletes[mat[i].obj["metadata"]["name"]])
        for i in creates
        if mat[i].obj["metadata"]["name"] in deletes
        and i // window != deletes[mat[i].obj["metadata"]["name"]] // window
    ]
    assert split, "fixture must place some create/delete pair across a boundary"
    stream = stream_trace_operations(
        lines, "borg", window=window, queue_windows=2, **kw
    )
    assert list(stream) == mat


# ---------------------------------------------------------------------------
# The windowed pipeline under the device executor
# ---------------------------------------------------------------------------


def test_streamed_device_replay_equals_materialized_and_prefetches(tmp_path):
    """A synthetic Borg trace (400 SUBMIT/FINISH pairs, 120 events kept)
    through the windowed pipeline feeding the device executor, then
    through the materialized path: the same counts, more than one
    64-op window, no producer fallback, and the executor drained at
    least one ready window while a dispatch was in flight."""
    import jax

    from tests.helpers import replay_synthetic_borg

    prev_x64 = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", False)
    try:
        stream, dev_r, streamed, mat = replay_synthetic_borg(tmp_path)
    finally:
        jax.config.update("jax_enable_x64", prev_x64)
    assert (streamed.pods_scheduled, streamed.unschedulable_attempts) == (
        mat.pods_scheduled, mat.unschedulable_attempts,
    )
    assert streamed.events_applied == mat.events_applied > 0
    stats = stream.stats()
    assert stats["window_ops"] == 64 and stats["queue_windows"] == 2
    assert stats["windows"] >= 2
    assert stats["fallback"] == 0
    drv = dev_r.replay_driver
    assert drv.device_steps > 0
    assert drv.stats()["ingest_prefetches"] >= 1


# ---------------------------------------------------------------------------
# Producer-fault degradation (the armed-chaos satellite)
# ---------------------------------------------------------------------------


def test_producer_fault_degrades_to_materialized_path():
    """An armed ``traces.stream`` fault fails the streaming ingest; the
    producer falls back to the materialized batch path, counts the
    degrade (stats + the ``traces.ingest_fallback`` event), and the
    operation sequence stays byte-identical."""
    from ksim_tpu.faults import FAULTS
    from ksim_tpu.obs import TRACE

    path = f"{FIXTURES}/borg_mini.jsonl"
    kw = dict(nodes=6, ops_per_step=3, seed=0)
    mat = trace_operations(path, "borg", **kw)
    FAULTS.reset()
    TRACE.reset()
    TRACE.enable(ring=True)
    try:
        FAULTS.arm("traces.stream", "always")
        stream = stream_trace_operations(
            path, "borg", window=4, queue_windows=2, **kw
        )
        assert list(stream) == mat
        assert stream.stats()["fallback"] == 1
        names = [r["name"] for r in TRACE.ring_records()]
        assert "traces.ingest_fallback" in names
    finally:
        FAULTS.reset()
        TRACE.reset()


# ---------------------------------------------------------------------------
# Early bound refusal (the KSIM_JOBS_MAX_* satellite)
# ---------------------------------------------------------------------------


def _borg_pair_lines(n: int) -> list[str]:
    out = []
    for i in range(n):
        out.append(
            json.dumps(
                {
                    "time": i * 1_000_000,
                    "type": "SUBMIT",
                    "collection_id": i,
                    "instance_index": 0,
                    "priority": 0,
                    "resource_request": {"cpus": 0.01, "memory": 0.01},
                }
            )
        )
        out.append(
            json.dumps(
                {
                    "time": i * 1_000_000 + 500_000,
                    "type": "FINISH",
                    "collection_id": i,
                    "instance_index": 0,
                }
            )
        )
    return out


def test_event_bound_refusal_stops_reading_the_source():
    """The bound trips mid-read: the refusal surfaces before the
    producer has consumed more than a small prefix of the source."""
    lines = _borg_pair_lines(200)
    consumed = []

    def counting():
        for line in lines:
            consumed.append(1)
            yield line

    stream = TraceOperationStream(
        counting(), "borg", nodes=4, ops_per_step=2, event_bound=20
    )
    with pytest.raises(TraceBoundExceeded, match="at least"):
        list(stream)
    assert 0 < len(consumed) < len(lines) // 2


def test_event_bound_refusal_before_reading_when_nodes_alone_blow_it():
    consumed = []

    def counting():
        for line in _borg_pair_lines(5):
            consumed.append(1)
            yield line

    with pytest.raises(TraceBoundExceeded, match="events"):
        TraceOperationStream(
            counting(), "borg", nodes=30, ops_per_step=2, event_bound=20
        )
    assert consumed == []


def test_node_bound_refuses_synchronously():
    with pytest.raises(TraceBoundExceeded, match="nodes"):
        TraceOperationStream(
            _borg_pair_lines(5), "borg", nodes=8, ops_per_step=2, node_bound=4
        )


# ---------------------------------------------------------------------------
# Stream object contract
# ---------------------------------------------------------------------------


def test_stream_close_is_idempotent_and_early():
    stream = stream_trace_operations(
        f"{FIXTURES}/borg_mini.jsonl", "borg", nodes=6, ops_per_step=3,
        window=1, queue_windows=1,
    )
    first = next(iter(stream))
    assert first.kind == "nodes"
    stream.close()
    stream.close()


def test_runner_refuses_streaming_off_the_solo_path():
    """Fleet replay, incremental resume, and checkpointing all need the
    materialized step-key index — each refuses a streaming source
    loudly instead of silently draining it."""
    from ksim_tpu.scenario import ScenarioRunner

    def fresh():
        return stream_trace_operations(
            f"{FIXTURES}/borg_mini.jsonl", "borg", nodes=6, ops_per_step=3
        )

    s = fresh()
    try:
        with pytest.raises(ValueError, match="solo-run path"):
            ScenarioRunner(device_replay=True, fleet=2).run(s)
        with pytest.raises(ValueError, match="resume"):
            ScenarioRunner().run(fresh(), resume_cursor=3)
        with pytest.raises(ValueError, match="checkpoint_hook"):
            ScenarioRunner(
                device_replay=True, checkpoint_hook=lambda *a: None
            ).run(fresh())
        with pytest.raises(ValueError, match="materialized"):
            ScenarioRunner(device_replay=True, fleet=2).run(
                [], lane_ops={0: fresh()}
            )
    finally:
        s.close()
