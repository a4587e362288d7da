"""The measured window: one client, closed loop, whole requests only.

A request counts if it started and ended inside the window.  Another is
started only while the window has room for one of the last one's length.
The first request of a window always runs to its end and counts, even past
the window, so that a slower system reads as a worse number and not as a
missing one.  Pure arithmetic over an injected clock, so the tests drive it
with canned timings.
"""

from __future__ import annotations

import statistics
import time
from typing import Callable


def run_window(seconds: float, request: Callable[[], dict], between: "Callable[[], None] | None" = None,
               clock: Callable[[], float] = time.monotonic) -> dict:
    """Drive ``request`` (which returns a record with ``wall_s``) in a
    closed loop for ``seconds``; ``between`` runs before every request and
    is outside the request's own time but inside the window."""
    start = clock()
    end = start + seconds
    counted, attempted, failed, dropped = [], 0, 0, 0
    last = 0.0
    while True:
        if between is not None:
            between()
        now = clock()
        if attempted and now + last > end:
            break
        attempted += 1
        rec = request()
        last = rec["wall_s"]
        if rec.get("failed"):
            failed += 1
        elif attempted == 1 or clock() <= end:
            counted.append(rec)
        else:
            dropped += 1  # finished past the window: neither counted nor failed
    return {"start": start, "end": clock(), "seconds": seconds, "counted": counted,
            "attempted": attempted, "failed": failed, "dropped": dropped}


def median_wall_s(records: list) -> "float | None":
    return statistics.median(r["wall_s"] for r in records) if records else None


def rate_per_s(records: list, units_key: str = "units") -> "float | None":
    """Total units of the counted requests over their summed wall."""
    wall = sum(r["wall_s"] for r in records)
    return sum(r[units_key] for r in records) / wall if wall > 0 else None
