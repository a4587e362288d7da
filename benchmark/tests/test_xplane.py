"""The trace -> metrics reduction: device-busy union, idle-gap attribution
to the covering host span, span self time.  On a synthetic ``XSpace``
written with the few lines of protobuf encoding below (exact expected
numbers), on a small trace recorded with ``jax.profiler`` on the CPU
(``data/cpu_small.xplane.pb``: three ``replay.dispatch`` spans, each with a
10-ms ``replay.lower`` inside) and on a trace recorded on the v5e in this
PR's first chip call, one traced import of ``import-1k_full``, trimmed to the
lines the reduction reads and the first 3,000 device ops
(``data/tpu_import_trimmed.xplane.pb``)."""

import os

import pytest

import xplane

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def varint(n):
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def field(num, value):
    if isinstance(value, int):
        return varint(num << 3) + varint(value)
    if isinstance(value, str):
        value = value.encode()
    return varint(num << 3 | 2) + varint(len(value)) + value


def plane(name, lines, names):
    """lines: {line_name: [(start_us, dur_us, event_name)]}"""
    ids = {n: i + 1 for i, n in enumerate(names)}
    buf = field(2, name)
    for n, i in ids.items():
        buf += field(4, field(1, i) + field(2, field(1, i) + field(2, n)))
    for lname, events in lines.items():
        line = field(2, lname) + field(3, 1_000)  # timestamp_ns
        for start_us, dur_us, ename in events:
            line += field(4, field(1, ids[ename]) + field(2, start_us * 10**6) + field(3, dur_us * 10**6))
        buf += field(3, line)
    return field(1, buf)


@pytest.fixture
def synthetic(tmp_path):
    dev = plane("/device:TPU:0", {
        "XLA Modules": [(100, 200, "jit_step(123)"), (400, 100, "jit_step(123)"), (900, 50, "jit_copy(9)")],
        "XLA Ops": [(100, 200, "while.1"), (150, 100, "fusion.2"),   # nested: union 100..300
                    (400, 100, "fusion.3"),                         # 400..500
                    (900, 50, "%copy.4 = f32[8]{0} copy(f32[8]{0} %p)")],  # its whole HLO text
        "Steps": [(0, 1000, "ignored")],
    }, ["jit_step(123)", "jit_copy(9)", "while.1", "fusion.2", "fusion.3",
        "%copy.4 = f32[8]{0} copy(f32[8]{0} %p)", "ignored"])
    host = plane("/host:CPU", {
        "worker": [(0, 1000, "noise"), (50, 600, "replay.dispatch"), (290, 120, "replay.lower"),
                   (700, 310, "replay.reconcile")],
    }, ["noise", "replay.dispatch", "replay.lower", "replay.reconcile"])
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(dev + host)
    return str(path)


def test_reduction_on_synthetic_trace(synthetic):
    planes = xplane.read_planes(synthetic, ("replay.",))
    got = xplane.reduce_trace(planes, ("replay.",))
    us = 1e-6
    assert got["chips"] == 1
    assert got["window_s"] == pytest.approx(1010 * us)
    assert got["busy_s"] == pytest.approx(350 * us)  # the three programs: 200 + 100 + 50
    ops = dict(got["device_ops"])
    assert ops["jit_step/while.1"] == pytest.approx(200 * us)
    assert ops["jit_step/fusion.2"] == pytest.approx(100 * us)
    assert ops["jit_copy/copy.4"] == pytest.approx(50 * us)
    gaps = dict(got["idle_gaps"])
    # Gaps are split at span boundaries: 0..50 nothing, 50..100 dispatch;
    # 300..400 lower; 500..650 dispatch, 650..700 nothing, 700..900
    # reconcile; 950..1010 reconcile.
    assert gaps["replay.lower"] == pytest.approx(100 * us)
    assert gaps["replay.dispatch"] == pytest.approx(200 * us)
    assert gaps["replay.reconcile"] == pytest.approx(260 * us)
    assert gaps["_no_program_span_"] == pytest.approx(100 * us)
    assert sum(gaps.values()) + got["busy_s"] == pytest.approx(got["window_s"])
    selfs = got["span_self_s"]
    assert selfs["replay.dispatch"] == pytest.approx(480 * us)  # 600 less the child's 120
    assert selfs["replay.lower"] == pytest.approx(120 * us)
    assert selfs["replay.reconcile"] == pytest.approx(310 * us)


def test_job_ring_spans_join_the_attribution(synthetic):
    planes = xplane.read_planes(synthetic, ("replay.",))
    extra = [(1e-6, 45e-6, "jobs.run", "ring")]  # inside the first gap, before replay.dispatch
    got = xplane.reduce_trace(planes, ("replay.",), extra)
    assert dict(got["idle_gaps"])["jobs.run"] == pytest.approx(44e-6)


def test_short_gaps_are_pooled():
    busy = xplane.union([(0.0, 1.0), (1.00001, 2.0)])
    assert xplane.gaps(busy, 0.0, 2.0) == [(1.0, 1.00001)]
    assert xplane.total(busy) == pytest.approx(1.99999)


def test_union_clip_gaps():
    assert xplane.union([(3, 4), (0, 2), (1, 2.5)]) == [[0, 2.5], [3, 4]]
    assert xplane.clip([(0, 2), (3, 5)], 1, 4) == [(1, 2), (3, 4)]
    assert xplane.gaps([[1, 2], [3, 4]], 0, 5) == [(0, 1), (2, 3), (4, 5)]


def test_self_times_across_threads():
    spans = [(0, 10, "a", 1), (2, 4, "b", 1), (5, 6, "b", 1), (0, 10, "b", 2)]
    got = xplane.self_times(spans)
    assert got["a"] == pytest.approx(7) and got["b"] == pytest.approx(13)


def test_recorded_cpu_trace():
    planes = xplane.read_planes(os.path.join(DATA, "cpu_small.xplane.pb"), ("replay.",))
    got = xplane.reduce_trace(planes, ("replay.",))
    assert got["chips"] == 0 and got["busy_s"] == 0.0
    assert 0.03 < got["window_s"] < 0.2
    # three 10-ms sleeps inside replay.lower; dispatch's self time is the rest
    assert 0.030 <= got["span_self_s"]["replay.lower"] < 0.040
    assert 0.0 < got["span_self_s"]["replay.dispatch"] < 0.02


def test_recorded_tpu_trace():
    planes = xplane.read_planes(os.path.join(DATA, "tpu_import_trimmed.xplane.pb"),
                                ("service.", "bench.anchor"))
    assert xplane.find_event(planes, "bench.anchor") == pytest.approx(0.048103, abs=1e-5)
    got = xplane.reduce_trace(planes, ("service.",))
    assert got["chips"] == 1
    assert got["window_s"] == pytest.approx(3.3343, abs=1e-3)
    # five programs: unpack, two converts, _schedule_fn (42.4 ms), pack (3.4 ms)
    assert got["busy_s"] == pytest.approx(0.045963, abs=1e-5)
    assert got["device_ops"][0][0] == "jit__schedule_fn/while.2"
    assert got["device_ops"][0][1] == pytest.approx(0.042361, abs=1e-5)
    gaps = dict(got["idle_gaps"])
    assert gaps["service.schedule"] == pytest.approx(3.1015, abs=1e-3)
    assert sum(gaps.values()) + got["busy_s"] == pytest.approx(got["window_s"])
