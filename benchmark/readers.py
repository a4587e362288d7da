"""The fixed vocabulary of metric readers.

A metric is a small data file ``metrics/<name>.json`` that names one of the
kinds below and its parameters; the harness finds it by the metric's name in
``BENCHMARK.json``.  A reader takes the run's context and returns a number,
or ``None`` when it finds nothing to read — the metric is then left out of
the result line.  Adding a metric is adding a file; a new *kind* is the only
thing that needs code here.

The context (``ctx``): ``requests`` — the counted requests of the window,
each ``{"wall_s", "units", "doc", "spans", "phases_s", "metrics_before",
"metrics_after"}`` as far as its request kind fills them; ``setup_s``;
``trace`` — the reduced profiler trace of a ``--trace 1`` run
(``xplane.reduce_trace``) or ``None``; ``rss_mb`` — the server child's
peak resident set (``ru_maxrss``) once it has exited.
"""

from __future__ import annotations

import statistics

import window
import xplane


def _median(values: list, scale: float = 1.0) -> "float | None":
    values = [v for v in values if v is not None]
    return statistics.median(values) * scale if values else None


def _dig(doc, path: list):
    for key in path:
        if not isinstance(doc, dict) or key not in doc:
            return None
        doc = doc[key]
    return doc if isinstance(doc, (int, float)) and not isinstance(doc, bool) else None


def setup(ctx: dict, spec: dict):
    """Process start to the start of the window, host clock."""
    return ctx["setup_s"]


def rate(ctx: dict, spec: dict):
    """Units (events) of the counted requests over their summed wall."""
    return window.rate_per_s(ctx["requests"])


def median_wall(ctx: dict, spec: dict):
    """Median client-side wait of the counted requests."""
    return window.median_wall_s(ctx["requests"])


def job_span_self(ctx: dict, spec: dict):
    """Self time of the named spans in each job's own trace ring, per
    request or per thousand units; median over the requests."""
    per_request = []
    for r in ctx["requests"]:
        spans = r.get("spans")
        if not spans:
            continue
        selfs = xplane.self_times(spans)
        if not any(n in selfs for n in spec["spans"]):
            continue
        value = sum(selfs.get(n, 0.0) for n in spec["spans"])
        if spec.get("per") == "kunit":
            value /= r["units"] / 1000.0
        per_request.append(value)
    return _median(per_request, spec.get("scale", 1.0))


def job_result(ctx: dict, spec: dict):
    """A number at ``path`` of each job's result document; median."""
    return _median([_dig(r.get("doc"), spec["path"]) for r in ctx["requests"]])


def growth(r: dict, path: list):
    """By how much the number at ``path`` of ``/api/v1/metrics`` grew across
    one request."""
    a, b = _dig(r.get("metrics_before"), path), _dig(r.get("metrics_after"), path)
    if b is None:
        return None
    return b - (a or 0)


def metrics_counter(ctx: dict, spec: dict):
    """Growth of a ``/api/v1/metrics`` counter across each request; median."""
    return _median([growth(r, ["counters", spec["counter"]]) for r in ctx["requests"]])


def metrics_timer(ctx: dict, spec: dict):
    """Growth of a ``/api/v1/metrics`` timer's ``total_seconds`` across each
    request; median."""
    return _median(
        [growth(r, ["timings", spec["timer"], "total_seconds"]) for r in ctx["requests"]],
        spec.get("scale", 1.0))


def client_phase(ctx: dict, spec: dict):
    """A client-side phase of each request, host clock; median."""
    return _median([(r.get("phases_s") or {}).get(spec["phase"]) for r in ctx["requests"]],
                   spec.get("scale", 1.0))


def device_busy(ctx: dict, spec: dict):
    """Device-busy share of the traced slice times the median request
    wall: the device time one request takes.  For cells whose slice spans a
    whole request; where it is a sample of a long one, read the share."""
    trace = ctx.get("trace")
    wall = window.median_wall_s(ctx["requests"])
    if not trace or not trace["window_s"] or not trace["chips"] or wall is None:
        return None
    return trace["busy_s"] / trace["window_s"] * wall * spec.get("scale", 1.0)


def device_busy_share(ctx: dict, spec: dict):
    """Share of the traced slice in which an operation ran on the device,
    averaged over the chips; ``scale`` 100 gives per cent."""
    trace = ctx.get("trace")
    if not trace or not trace["window_s"] or not trace["chips"]:
        return None
    return trace["busy_s"] / trace["window_s"] * spec.get("scale", 1.0)


def server_rss(ctx: dict, spec: dict):
    """Peak resident memory (``ru_maxrss``) of the server child, MB."""
    return ctx.get("rss_mb")


KINDS = {f.__name__: f for f in (
    setup, rate, median_wall, job_span_self, job_result, metrics_counter,
    metrics_timer, client_phase, device_busy, device_busy_share, server_rss)}
