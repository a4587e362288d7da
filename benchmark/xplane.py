"""From a profiler trace (``.xplane.pb``) to device-busy time, idle gaps and
the host spans that cover them.

The file is a serialized ``XSpace`` (tsl/profiler/protobuf/xplane.proto).
It is read here with a few lines of protobuf wire-format decoding, so that
the reduction needs nothing but the standard library and runs in the
harness's own process, which never imports jax.  The field numbers used
are listed beside each message below.

Times: an ``XLine`` carries ``timestamp_ns`` (UNIX epoch) and each event an
``offset_ps`` relative to it; everything is returned in seconds since the
epoch, host threads and device lines on one clock.
"""

from __future__ import annotations

import bisect


def _varint(buf, pos: int) -> "tuple[int, int]":
    result = shift = 0
    while True:
        b = buf[pos]
        pos += 1
        result |= (b & 0x7F) << shift
        if b < 0x80:
            return result, pos
        shift += 7


def fields(buf):
    """Yield ``(field_number, wire_type, value)`` of one message; a
    length-delimited value is a memoryview slice."""
    pos, end = 0, len(buf)
    while pos < end:
        key, pos = _varint(buf, pos)
        num, wt = key >> 3, key & 7
        if wt == 0:
            val, pos = _varint(buf, pos)
        elif wt == 2:
            size, pos = _varint(buf, pos)
            val = buf[pos:pos + size]
            pos += size
        elif wt == 1:
            val = bytes(buf[pos:pos + 8])
            pos += 8
        elif wt == 5:
            val = bytes(buf[pos:pos + 4])
            pos += 4
        else:
            raise ValueError(f"wire type {wt} at byte {pos}")
        yield num, wt, val


def _signed(v: int) -> int:
    return v - (1 << 64) if v >= 1 << 63 else v


def _metadata(buf) -> "tuple[int, str]":
    """XEventMetadata / XStatMetadata: id = 1, name = 2, display_name = 4."""
    ident, name = 0, ""
    for num, _wt, val in fields(buf):
        if num == 1:
            ident = val
        elif num == 2:
            name = bytes(val).decode("utf-8", "replace")
    return ident, name


DEVICE_LINES = ("XLA Ops", "XLA Modules")


def _event(ev, t0_ns: int, names: dict):
    """XEvent -> ``(start_s, end_s, name)``.  metadata_id = 1, offset_ps = 2
    and duration_ps = 3 are written first and in this order; the stats
    (field 4) that follow are not read.  Inlined varints: a device line
    holds millions of events."""
    vals = [0, 0, 0, 0]
    pos, end = 0, len(ev)
    while pos < end:
        key = ev[pos]
        if key not in (0x08, 0x10, 0x18):  # a varint field 1..3, else stop
            break
        pos += 1
        result = shift = 0
        while True:
            b = ev[pos]
            pos += 1
            result |= (b & 0x7F) << shift
            if b < 0x80:
                break
            shift += 7
        vals[key >> 3] = result
    start = t0_ns * 1e-9 + _signed(vals[2]) * 1e-12
    return start, start + _signed(vals[3]) * 1e-12, names.get(vals[1], str(vals[1]))


def read_planes(path: str, host_prefixes: tuple) -> list:
    """``[{"name", "lines": [{"name", "events": [(start_s, end_s, name)]}]}]``
    of the device planes (lines ``XLA Ops`` and ``XLA Modules``, every
    event) and the host planes (only events whose name starts with one of
    ``host_prefixes``, plus each line's first and last event under the name
    ``""`` so that the slice's extent can be read).  A host-bound server
    fills the host planes with events nobody reads; skipping them by their
    metadata id is what keeps this affordable in pure Python."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    planes = []
    for num, _wt, plane_buf in fields(space):
        if num != 1:  # XSpace.planes
            continue
        name, lines, event_names = "", [], {}
        for pnum, _pwt, val in fields(plane_buf):  # XPlane: name = 2, lines = 3, event_metadata = 4
            if pnum == 2:
                name = bytes(val).decode("utf-8", "replace")
            elif pnum == 3:
                lines.append(val)
            elif pnum == 4:  # map entry: key = 1, value = 2
                for mnum, _mwt, mval in fields(val):
                    if mnum == 2:
                        ident, mname = _metadata(mval)
                        # a device op's name is its whole HLO text: keep "while.34"
                        event_names[ident] = mname.split(" = ")[0].lstrip("%")
        device = is_device_plane(name)
        if not device and not name.startswith("/host:"):
            continue
        wanted = None if device else {
            i for i, n in event_names.items() if n.startswith(host_prefixes)}
        out_lines = []
        for line_buf in lines:  # XLine: name = 2, timestamp_ns = 3, events = 4
            lname, t0_ns, events = "", 0, []
            for lnum, _lwt, val in fields(line_buf):
                if lnum == 2:
                    lname = bytes(val).decode("utf-8", "replace")
                elif lnum == 3:
                    t0_ns = _signed(val)
                elif lnum == 4:
                    events.append(val)
            if device:
                if lname not in DEVICE_LINES:
                    continue
                decoded = [_event(bytes(ev[:32]), t0_ns, event_names) for ev in events]
            else:
                decoded = []
                for ev in events:
                    # metadata_id is field 1 and is written first: key byte 0x08.
                    if ev[0] == 0x08 and _varint(ev, 1)[0] in wanted:
                        decoded.append(_event(bytes(ev[:32]), t0_ns, event_names))
                for ev in (events[:1] + events[-1:]):
                    s, e, _n = _event(bytes(ev[:32]), t0_ns, event_names)
                    decoded.append((s, e, ""))
            out_lines.append({"name": lname, "events": decoded})
        planes.append({"name": name, "lines": out_lines})
    return planes


# -- reductions ---------------------------------------------------------------


def union(intervals: list) -> list:
    """Merge ``(start, end)`` intervals into disjoint ones, sorted."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def clip(intervals: list, lo: float, hi: float) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def total(intervals: list) -> float:
    return sum(e - s for s, e in intervals)


def gaps(busy: list, lo: float, hi: float) -> list:
    """The idle intervals of ``[lo, hi]`` given disjoint sorted ``busy``."""
    out, cur = [], lo
    for s, e in busy:
        if s > cur:
            out.append((cur, min(s, hi)))
        cur = max(cur, e)
        if cur >= hi:
            break
    if cur < hi:
        out.append((cur, hi))
    return out


def self_times(spans: list) -> dict:
    """name -> self time of ``(start, end, name, thread)`` spans: a span's
    duration minus the part its child spans (nested on the same thread)
    cover."""
    out: dict = {}
    by_thread: dict = {}
    for s, e, name, thread in spans:
        by_thread.setdefault(thread, []).append((s, e, name))
    for items in by_thread.values():
        items.sort(key=lambda x: (x[0], -x[1]))
        stack = []  # [start, end, name, covered-by-children]
        def close(upto):
            while stack and stack[-1][1] <= upto:
                s, e, name, covered = stack.pop()
                out[name] = out.get(name, 0.0) + (e - s) - covered
                if stack:
                    stack[-1][3] += e - s
        for s, e, name in items:
            close(s)
            if stack:
                e = min(e, stack[-1][1])  # clock skew: a child cannot outlive its parent
            stack.append([s, e, name, 0.0])
        close(float("inf"))
    return out


def attribute(gap: tuple, spans: list) -> dict:
    """Split one idle gap at the boundaries of the host spans that touch it
    and give each piece to the shortest span that covers it: name ->
    seconds (``_no_program_span_`` where none does)."""
    lo, hi = gap
    near = [(s, e, n) for s, e, n, _t in spans if e > lo and s < hi]
    cuts = sorted({lo, hi, *(p for s, e, _n in near for p in (s, e) if lo < p < hi)})
    out: dict = {}
    for a, b in zip(cuts, cuts[1:]):
        best = None
        for s, e, n in near:
            if s <= a and e >= b and (best is None or e - s < best[0]):
                best = (e - s, n)
        key = best[1] if best else "_no_program_span_"
        out[key] = out.get(key, 0.0) + b - a
    return out


def is_device_plane(name: str) -> bool:
    return name.startswith("/device:") and "CUSTOM" not in name.upper()


def find_event(planes: list, name: str) -> "float | None":
    """Start of the first host event called ``name``."""
    for plane in planes:
        if plane["name"].startswith("/host:"):
            for line in plane["lines"]:
                for s, _e, n in line["events"]:
                    if n == name:
                        return s
    return None


def reduce_trace(planes: list, span_prefixes: tuple, extra_spans: "list | None" = None,
                 min_gap_s: float = 50e-6, top: int = 10) -> dict:
    """Device-busy union, idle share and the breakdown of one traced
    slice.  The program's host spans are the host-thread events whose names
    start with one of ``span_prefixes`` (the ``TraceAnnotation`` bridge
    writes them) plus ``extra_spans`` (``(start, end, name, thread)`` on the
    trace's clock).  The slice is the extent of everything the trace holds."""
    device_ops = {}   # plane -> [(s, e, module/name)]
    device_busy = {}  # plane -> [(s, e)] of its programs
    host_spans = list(extra_spans or [])
    lo, hi = float("inf"), float("-inf")
    for plane in planes:
        by_line = {line["name"]: line["events"] for line in plane["lines"]}
        if is_device_plane(plane["name"]):
            modules = sorted(by_line.get("XLA Modules") or [])
            device_busy[plane["name"]] = [(s, e) for s, e, _n in modules]
            starts = [m[0] for m in modules]
            ops = device_ops.setdefault(plane["name"], [])
            for s, e, name in by_line.get("XLA Ops") or []:
                i = bisect.bisect_right(starts, s) - 1
                if i >= 0 and modules[i][1] >= s:
                    name = modules[i][2].split("(")[0] + "/" + name
                ops.append((s, e, name))
        elif plane["name"].startswith("/host:"):
            for line in plane["lines"]:
                for s, e, name in line["events"]:
                    lo, hi = min(lo, s), max(hi, e)
                    if name and name.startswith(span_prefixes):
                        host_spans.append((s, e, name, line["name"]))
    for modules in device_busy.values():
        for s, e in modules:
            lo, hi = min(lo, s), max(hi, e)
    if hi <= lo:
        return {"window_s": 0.0, "busy_s": 0.0, "chips": len(device_ops),
                "device_ops": [], "idle_gaps": [], "span_self_s": {}}
    busy_total = 0.0
    op_time: dict = {}
    gap_time: dict = {}
    pooled = f"_gaps_under_{int(min_gap_s * 1e6)}_us_"
    for plane_name, ops in device_ops.items():
        # Busy = a program (an ``XLA Modules`` event) is running on the chip.
        # On the recorded traces this equals the union of the op events to
        # 1 part in 10,000, and it survives a truncated op line.
        busy = union(clip(device_busy[plane_name], lo, hi))
        busy_total += total(busy)
        for s, e, name in ops:
            if e > lo and s < hi:
                op_time[name] = op_time.get(name, 0.0) + min(e, hi) - max(s, lo)
        for gap in gaps(busy, lo, hi):
            if gap[1] - gap[0] < min_gap_s:
                gap_time[pooled] = gap_time.get(pooled, 0.0) + gap[1] - gap[0]
                continue
            for key, seconds in attribute(gap, host_spans).items():
                gap_time[key] = gap_time.get(key, 0.0) + seconds
    chips = max(len(device_ops), 1)
    rank = lambda d: [[k, v / chips] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:top]]
    clipped = [(max(s, lo), min(e, hi), n, t) for s, e, n, t in host_spans if e > lo and s < hi]
    return {
        "window_s": hi - lo, "busy_s": busy_total / chips, "chips": len(device_ops),
        "device_ops": rank(op_time), "idle_gaps": rank(gap_time),
        "span_self_s": self_times(clipped),
    }
