#!/usr/bin/env python3
"""One run of one cell of the benchmark (``BENCHMARK.json``).

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Generates the cell's inputs from ``--seed`` (the generator kind the
configuration names, ``kinds/``), starts ONE
server child — the only process that touches JAX — warms the cell's own
request up until a repeat adds nothing to the compile cache (all of which is
``setup_s``), drives the request in a closed loop for ``--seconds``
(``window.py``), stops the server, checks the documents the window produced
(``checks.py``; the plain references: ``replay.py`` or the one the
configuration names for jobs, ``reference.py`` for exports) and prints, as the last line of stdout,
one JSON object: ``correct``, ``attempted``, ``failed``, ``metrics``,
``device`` and, traced, ``breakdown``.  Earlier lines carry the individual
request times, the compile-cache entry counts around the window, how late
the client's probes ran and every number compared beside its limit.

Everything that belongs to one configuration, traffic mix, cell or metric
is a data file found by its name (README.md), and so are the generator kinds
and the plain references of jobs; this file names none of them.  A run that
cannot succeed — a generator kind or reference no file provides, a job that
leaves the device path where the configuration guarantees every step on it, a
warm-up document without a key the guarantees name, a traced request that ends
before its slice opened (the slice lies ``trace.offset_share`` of the previous
request's wall into the traced one: ``Slice``) — ends at once, non-zero, with
the reason and no result line.
``--rehearsal`` runs the same code at the sizes the data files give under
``"rehearsal"`` with the server pinned to the CPU, and prints counts only.
"""

from __future__ import annotations

_T0 = __import__("time").monotonic()

import argparse
import glob
import json
import os
import random
import resource
import shutil
import signal
import sys
import tempfile
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)

import byname
import checks
import client
import readers
import reference
import replay
import window
import xplane

READY_CAP_S = 240.0
REQUEST_CAP_S = 1100.0
#: The event a job's stream carries when a step leaves the device path
#: (``ksim_tpu/engine/replay.py`` ``_reject``, with the reason).
FALLBACK_EVENT = "replay.fallback"
#: The program's host spans (ksim_tpu/obs.py SPAN_NAMES) that the
#: ``KSIM_TRACE_JAX=1`` bridge writes into the profiler's trace.
SPAN_PREFIXES = ("replay.", "service.", "jobs.", "runner.", "engine.", "scenario.")


def log(msg: str) -> None:
    print(f"[bench +{time.monotonic() - _T0:7.1f}s] {msg}", file=sys.stderr, flush=True)


def load(rel: str) -> dict:
    with open(os.path.join(ROOT, rel), encoding="utf-8") as f:
        return json.load(f)


def overlay(doc: dict, over: "dict | None") -> dict:
    out = dict(doc)
    for k, v in (over or {}).items():
        out[k] = overlay(out[k], v) if isinstance(v, dict) and isinstance(out.get(k), dict) else v
    return out


def cache_entries() -> int:
    """Files in the compile cache the server child will use."""
    root = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(ROOT, ".jax_cache")
    return sum(len(files) for _, _, files in os.walk(root))


# -- inputs -------------------------------------------------------------------


def build_inputs(config: dict, traffic: dict, seed: int) -> dict:
    """``body``, ``units`` and what the checks need, from the generator kind
    the configuration names (``kinds/<kind>.py``)."""
    kind = (config.get("generator") or {}).get("kind")
    return byname.load("kinds", kind, "inputs").inputs(config, traffic, seed)


def reference_of(config: dict):
    """The module whose ``replay`` judges a job configuration's jobs: the
    ``references/<name>.py`` it names under ``reference``, else ``replay.py``."""
    name = config.get("reference")
    return replay if name is None else byname.load("references", name, "replay")


# -- the traced slice ---------------------------------------------------------


class BadTraffic(ValueError):
    """A traffic file the harness refuses before a server starts."""


class SliceMissed(client.CannotSucceed):
    """The traced request ended before its slice was to open."""


def trace_spec(traffic: dict) -> dict:
    """The traffic file's ``trace`` block, held to its keys.  Where the
    slice opens is ``offset_share`` of the wall of the last request the run
    completed before the traced one (README.md): never a number of seconds,
    which a faster program walks out from under."""
    spec = dict(traffic.get("trace") or {})
    name = traffic.get("name")
    unknown = sorted(set(spec) - {"request", "offset_share", "max_s", "why"})
    if unknown:
        raise BadTraffic(
            f"traffic {name!r}: trace holds {', '.join(unknown)}; its keys are request, "
            "offset_share (a share of the previous request's wall, not seconds), max_s and why")
    share, request = spec.get("offset_share", 0.0), spec.get("request", 0)
    if isinstance(share, bool) or not isinstance(share, (int, float)) or not 0.0 <= share < 1.0:
        raise BadTraffic(f"traffic {name!r}: trace.offset_share {share!r} is not in [0, 1)")
    warmups = min(traffic.get("warmup_min", 2), traffic.get("warmup_max", 4))
    if share > 0 and request == 0 and warmups < 1:
        raise BadTraffic(
            f"traffic {name!r}: trace.offset_share {share} is a share of the wall of the last "
            "request completed before the traced one, and with request 0 and no warm-up "
            "(warmup_min / warmup_max under 1) the run completes none before it")
    return spec


class Slice:
    """Profiler on for one slice of the window: SIGUSR1 / SIGUSR2 to the
    server child (``server_child.py``), from a timer, so that the traced
    request is driven like any other.  The timer runs ``offset_share`` of
    the previous request's wall, read in the same run."""

    Timer = threading.Timer

    def __init__(self, server, spec: dict, out_dir: str) -> None:
        self.server, self.dir = server, out_dir
        self.state = "idle"  # idle -> armed -> on -> off, or armed -> missed
        self.request, self.max_s = spec.get("request", 0), spec.get("max_s", 8.0)
        self.share = float(spec.get("offset_share", 0.0))
        self.of_wall_s = self.open_at_s = self.request_wall_s = self._began = None
        self._lock = threading.Lock()
        self._timer = None

    def request_starts(self, index: int, previous_wall_s: "float | None") -> None:
        if self.state != "idle" or index != self.request:
            return
        if self.share > 0 and not previous_wall_s:
            raise client.CannotSucceed(
                f"trace.offset_share {self.share}: no request was completed before request {index}")
        self.of_wall_s = previous_wall_s
        self.open_at_s = self.share * (previous_wall_s or 0.0)
        self.state = "armed"
        self._began = time.monotonic()
        self._timer = self.Timer(self.open_at_s, self._start)
        self._timer.start()

    def _start(self) -> None:
        with self._lock:
            if self.state != "armed":
                return
            self.state = "on"
            self.server.proc.send_signal(signal.SIGUSR1)
        # The profiler takes a while to start next to a busy server: count
        # the slice from the moment the child says it is tracing.
        waited = time.monotonic() + 60.0
        while not os.path.exists(os.path.join(self.dir, "ANCHOR.json")):
            if time.monotonic() >= waited or self.state != "on":
                break
            time.sleep(0.05)
        with self._lock:
            if self.state == "on":
                log(f"slice: tracing from {time.monotonic() - self._began:.3f} s into request "
                    f"{self.request} (planned: {self.open_at_s:.3f} s = offset_share "
                    f"{self.share} of {self.of_wall_s or 0.0:.3f} s), {self.max_s} s at most")
                self._timer = self.Timer(self.max_s, self.stop)
                self._timer.start()

    def stop(self) -> None:
        with self._lock:
            if self._timer is not None:
                self._timer.cancel()
            if self.state == "on":
                self.server.proc.send_signal(signal.SIGUSR2)
                self.state = "off"
            elif self.state == "armed":
                self.state = "missed"

    def request_ends(self, wall_s: float) -> None:
        if self.state in ("armed", "on"):
            self.request_wall_s = wall_s
            self.stop()

    def placement(self) -> str:
        """Where the slice was to open, in the one line a failed traced run
        ends on: the share, the wall it was taken of, the second that makes,
        and the traced request's own wall."""
        if self.open_at_s is None:
            return f"request {self.request}, the traced one, never started"
        said = lambda v: "not known" if v is None else f"{v:.3f} s"
        return (f"offset_share {self.share} of the previous request's wall {said(self.of_wall_s)} "
                f"put the slice's start {self.open_at_s:.3f} s into the traced request, "
                f"which took {said(self.request_wall_s)}")

    def collect(self, deadline: float) -> "str | None":
        """Wait for the child to finish writing, return the trace file.  A
        slice that was never opened and closed leaves none to wait for."""
        if self.state != "off":
            return None
        while not os.path.exists(os.path.join(self.dir, "DONE")):
            if time.monotonic() >= deadline or self.server.proc.poll() is not None:
                return None
            time.sleep(0.1)
        found = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"), recursive=True)
        return found[0] if found else None


class StallWatch(threading.Thread):
    """Sleeps ``STEP_S`` at a time through the window and keeps the wake-ups
    that came ``LATE_S`` late or more, as ``[seconds into the window, seconds
    late]``.  A host that held every process shows here; a server that
    stalled alone does not (``slow_requests`` then says where).  Diagnostic
    only: no metric reads it."""

    STEP_S, LATE_S = 0.05, 0.25

    def __init__(self) -> None:
        super().__init__(daemon=True)
        self.late: list = []
        self._halt = threading.Event()

    def run(self) -> None:
        start = due = time.monotonic()
        while not self._halt.is_set():
            due += self.STEP_S
            time.sleep(max(due - time.monotonic(), 0.0))
            over = time.monotonic() - due
            if over >= self.LATE_S:
                self.late.append([round(due - start, 2), round(over, 3)])
                due = time.monotonic()

    def halt(self) -> list:
        self._halt.set()
        self.join()
        return self.late


def slow_requests(counted: list, is_job: bool, factor: float = 1.5, most: int = 6) -> list:
    """The counted requests that took ``factor`` times the median or more,
    with what the server's own document says of them (a job's phases, its
    run's wall and its runtime account): where a stall landed."""
    if not counted:
        return []
    cut = factor * window.median_wall_s(counted)
    out = []
    for i, r in enumerate(counted):
        if r["wall_s"] >= cut:
            row = {"index": i, "wall_s": round(r["wall_s"], 4)}
            if is_job:
                doc = r["doc"]
                row["server_wall_s"] = (doc.get("result") or {}).get("wallSeconds")
                row["phases"] = {k: round(v, 3) if isinstance(v, float) else v
                                 for k, v in (doc.get("phases") or {}).items()}
                row["runtime"] = doc.get("runtime")
            else:
                row["phases_s"] = {k: round(v, 3) for k, v in r["phases_s"].items()}
            out.append(row)
    return out[:most]


# -- the parts of a run -------------------------------------------------------


def load_cell(bench: dict, name: str, rehearsal: bool) -> dict:
    """Everything the data files say about one cell."""
    cell = next(w for w in bench["workloads"] if w["name"] == name)
    cfg_entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = load(cfg_entry["file"])
    traffic = load(f"benchmark/traffic/{cell['traffic']}.json")
    cell_doc = load(f"benchmark/cells/{cell['name']}.json")
    locks = cell_doc["locks"]
    if rehearsal:
        config = overlay(config, config.get("rehearsal"))
        traffic = overlay(traffic, traffic.get("rehearsal"))
        locks = cell_doc.get("rehearsal_locks") or {}
    traffic["trace"] = trace_spec(traffic)
    is_job = config["request"] == "job"
    return {"cell": cell, "config": config, "traffic": traffic, "locks": locks,
            "is_job": is_job, "guarantees": config["guarantees"],
            "reference": reference_of(config) if is_job else None,
            "platform": "cpu" if rehearsal else "tpu", "rehearsal": rehearsal}


def start_server(c: dict, trace: bool) -> "tuple[client.Server, str]":
    """The server child with a quiet, fixed environment; ``work`` is a
    scratch directory under ``TMPDIR`` for its device report and profile."""
    work = tempfile.mkdtemp(prefix="bench-")
    env = {k: v for k, v in os.environ.items() if not k.startswith("KSIM_TRACE")}
    env["PYTHONHASHSEED"] = "0"
    env["BENCH_DEVICE_REPORT"] = os.path.join(work, "device.json")
    if c["rehearsal"]:
        env["JAX_PLATFORMS"] = "cpu"
    if trace:
        env.update(KSIM_TRACE="1", KSIM_TRACE_JAX="1",
                   BENCH_PROFILE_DIR=os.path.join(work, "profile"))
    server = client.Server(ROOT, env, c["platform"], c["cell"]["chips"])
    if c["guarantees"].get("every_step_on_device"):
        server.fatal_events = (FALLBACK_EVENT,)
    return server, work


def device_report(work: str) -> dict:
    try:
        with open(os.path.join(work, "device.json"), encoding="utf-8") as f:
            return json.load(f)
    except (OSError, ValueError):
        return {}


class Driver:
    """Warm-up and window of one seed's inputs against a running server."""

    def __init__(self, server, c: dict, inputs: dict, seed: int, slice_=None) -> None:
        self.server, self.c, self.inputs, self.slice = server, c, inputs, slice_
        self.rng = random.Random(seed)
        self.reservoir: list = []  # a seeded sample of the window's raw answers
        self.started = 0
        self.last_wall_s = None  # of the last request that succeeded, warm-ups included

    def request(self) -> dict:
        deadline = time.monotonic() + REQUEST_CAP_S
        try:
            if self.c["is_job"]:
                rec = self.server.run_job(self.inputs["body"], deadline)
                rec["failed"] = rec["doc"].get("state") != "succeeded"
            else:
                rec = self.server.run_import(self.inputs["body"], self.inputs["units"], deadline)
                rec["failed"] = False
        except client.CannotSucceed:
            raise
        except client.BenchFailure as e:
            self.server.alive()  # a dead server ends the run; a refused request is a failure
            log(f"request failed: {e}")
            return {"wall_s": 0.0, "failed": True}
        rec["units"] = self.inputs["units"]
        if not rec["failed"]:
            self.last_wall_s = rec["wall_s"]
        return rec

    def between(self) -> None:
        if self.c["traffic"].get("reset_between"):
            self.server.reset(time.monotonic() + REQUEST_CAP_S)

    def windowed_request(self) -> dict:
        index = self.started
        self.started += 1
        if self.slice is not None:
            self.slice.request_starts(index, self.last_wall_s)
        rec = self.request()
        if self.slice is not None:
            self.slice.request_ends(rec["wall_s"])
            if self.slice.state == "missed":
                raise SliceMissed(self.slice.placement())
            if self.c["is_job"] and not rec.get("failed"):
                rec["spans"] = self.server.job_spans(rec["id"])
        raw = rec.pop("raw", None)
        if raw is not None:
            if len(self.reservoir) < 2:
                self.reservoir.append((index, raw))
            elif self.rng.random() < 2.0 / (index + 1):
                self.reservoir[self.rng.randrange(2)] = (index, raw)
        return rec

    def warm_up(self) -> list:
        """The cell's own request, ``warmup_min`` times at least and until a
        repeat adds no entry to the compile cache (programs load from a warm
        cache without adding one, and the first requests after that are
        still slow: the minimum is read off the chip, PERF.md)."""
        traffic, warm = self.c["traffic"], []
        entries = cache_entries()
        for i in range(traffic.get("warmup_max", 4)):
            self.between()
            rec = self.request()
            rec.pop("raw", None)
            if rec.get("failed"):
                raise client.BenchFailure("a warm-up request failed")
            if self.c["is_job"]:
                have = rec["doc"].get("replay") or {}
                lacks = sorted(set(self.c["guarantees"].get("replay_equals") or ()) - set(have))
                if lacks:
                    raise client.CannotSucceed(
                        f"the warm-up job's result has no replay.{', replay.'.join(lacks)}, "
                        "which the configuration's guarantees name")
            now_entries = cache_entries()
            log(f"warm-up {i}: {rec['wall_s']:.3f} s, compile-cache entries {entries} -> {now_entries}")
            warm.append(rec)
            added, entries = now_entries - entries, now_entries
            if i + 1 >= traffic.get("warmup_min", 2) and added == 0:
                break
        return warm

    def window(self, seconds: float) -> dict:
        self.between()
        self.server.late_s = 0.0
        entries = cache_entries()
        watch = StallWatch()
        watch.start()
        try:
            win = window.run_window(seconds, self.windowed_request, self.between)
        finally:
            win_late = watch.halt()
        win["cache_entries"] = (entries, cache_entries())
        win["client_late_wakeups"] = win_late
        return win


def replayed_counts(c: dict, inputs: dict, precision: str = "exact") -> "list | None":
    """What the cell's plain reference (``replay.py``, or the one its
    configuration names) counts over the submitted operations: events
    applied, pods scheduled, unschedulable attempts.  ``None`` — which no job
    equals — where it does not cover them."""
    started = time.monotonic()
    try:
        got = c["reference"].replay(
            inputs["operations"], precision=precision,
            max_pods_per_pass=c["config"]["simulator"].get("maxPodsPerPass"))
    except replay.NotCovered as e:
        log(f"reference replay: not covered: {e}")
        return None
    log(f"reference replay ({precision}) of {len(inputs['operations'])} operations: "
        f"{time.monotonic() - started:.1f} s")
    return [got[k] for k in checks.COUNT_KEYS]


def judge(c: dict, inputs: dict, seed: int, win: dict, warm: list, reservoir: list,
          device: dict, ident: dict) -> "list[dict]":
    """Every document of the window against the configuration's guarantees,
    the plain reference (``replay.py`` for jobs, ``reference.py`` for
    exports) and the locks: the comparisons that decide ``correct``."""
    counted, guarantees = win["counted"], c["guarantees"]
    out = checks.check_device(device, c["platform"], c["cell"]["chips"])
    out.append(checks.cmp_eq("device.served_platform", ident.get("platform"), c["platform"]))
    out.append(checks.cmp_min("requests.counted", len(counted), 1))
    out.append(checks.cmp_eq("requests.failed", win["failed"], 0))
    if c["is_job"]:
        # Every seed submits the base stream's scheduling problem (the seed
        # orders arrivals inside a step only), so its lock holds at every seed.
        lock = c["locks"].get(str(c["config"]["generator"].get("base_seed", 0)))
        want = replayed_counts(c, inputs)
        for r in counted:
            out += checks.check_job(r["doc"], guarantees, steps=inputs["steps"], lock=lock)
            out.append(checks.cmp_eq("job.counts_vs_reference_replay",
                                     checks.job_counts(r["doc"]), want))
        distinct = {tuple(checks.job_counts(r["doc"])) for r in counted + warm}
        out.append(checks.cmp_eq("job.distinct_counts_in_run", len(distinct), 1))
        return out
    names = sorted(p["metadata"]["name"] for p in inputs["pods"])
    sample_n = min(guarantees.get("reference_sample_pods", 0), len(names))
    for index, raw in reservoir:
        export = json.loads(raw)
        out += checks.check_export(export, inputs["nodes"], inputs["pods"], guarantees)
        sample = set(random.Random(seed + index).sample(names, sample_n))
        out += reference_comparisons(inputs["nodes"], export["pods"], sample, guarantees)
    return out


def reference_comparisons(nodes: list, exported_pods: list, sample: set, guarantees: dict,
                          served: "list | None" = None) -> "list[dict]":
    """``served`` (default: the export itself) against the plain reference
    evaluated over ``exported_pods``' bindings."""
    want = reference.evaluate(nodes, exported_pods, sample)
    got = reference.compare(served if served is not None else exported_pods, want)
    if got["score_mismatches"]:
        log(f"reference: scores differ by up to {got['score_worst_gap']} in "
            f"{got['score_mismatches_by_plugin']}")
    return [
        checks.cmp_min("reference.score_entries_compared", got["score_compared"], 1),
        checks.cmp_max("reference.filter_mismatches", got["filter_mismatches"],
                       guarantees["filter_mismatches_limit"]),
        checks.cmp_max("reference.score_mismatch_share", got["score_mismatch_share"],
                       guarantees["score_mismatch_share_limit"]),
    ]


def print_comparisons(comparisons: list) -> "tuple[bool, list]":
    """One line per distinct comparison; ``correct`` is their conjunction.
    Also returns the distinct ones as ``[name, value, limit, passed]``: the
    result line's last key and the run's last lines on stderr."""
    seen, distinct = set(), []
    for cmp_ in comparisons:
        key = (cmp_["name"], json.dumps(cmp_["value"], sort_keys=True), cmp_["ok"])
        if key not in seen:
            seen.add(key)
            print(json.dumps({"compared": cmp_["name"], "value": cmp_["value"],
                              "limit": cmp_["limit"], "ok": cmp_["ok"]}))
            distinct.append([cmp_["name"], cmp_["value"], cmp_["limit"], cmp_["ok"]])
    distinct.sort(key=lambda row: not row[3])  # stable: the failed ones last
    return all(cmp_["ok"] for cmp_ in comparisons), distinct


def log_compared(compared: list) -> None:
    """Every number compared beside its limit, failed ones last: the run's
    last lines on stderr."""
    for name, value, limit, ok in compared:
        print(f"compared {name}: {json.dumps(value)} limit {json.dumps(limit)} "
              f"{'ok' if ok else 'NOT OK'}", file=sys.stderr)
    sys.stderr.flush()


def reduce_slice(trace_file: str, profile_dir: str, counted: list) -> dict:
    planes = xplane.read_planes(trace_file, SPAN_PREFIXES + ("bench.anchor",))
    # The job rings keep UNIX time; the anchor event says where that is on
    # the trace's clock.
    extra = []
    try:
        with open(os.path.join(profile_dir, "ANCHOR.json"), encoding="ascii") as f:
            shift = xplane.find_event(planes, "bench.anchor") - json.load(f)["unix_s"]
        extra = [(s + shift, e + shift, n, t) for r in counted for s, e, n, t in r.get("spans") or []]
    except (OSError, ValueError, TypeError):
        log("no anchor in the trace: job-ring spans are left out of the breakdown")
    trace = xplane.reduce_trace(planes, SPAN_PREFIXES, extra)
    log(f"trace: {os.path.getsize(trace_file)} bytes, slice {trace['window_s']:.3f} s, "
        f"device busy {trace['busy_s']:.3f} s on {trace['chips']} chip(s)")
    return trace


# -- one run ------------------------------------------------------------------


def drive(server, c: dict, inputs: dict, seed: int, seconds: float, slice_=None) -> dict:
    """One seed's inputs against a (starting or running) server: warm-up and
    window — what ``judge`` and the readers take.  ``tools/seeds.py`` drives
    many seeds through here against one server."""
    server.wait_ready(time.monotonic() + READY_CAP_S)
    driver = Driver(server, c, inputs, seed, slice_)
    warm = driver.warm_up()
    driver.between()
    setup_s = time.monotonic() - _T0
    log(f"server on :{server.port}; set-up done in {setup_s:.1f} s; window of {seconds} s starts")
    win = driver.window(seconds)
    return {"warm": warm, "win": win, "setup_s": setup_s,
            "reservoir": driver.reservoir, "ident": server.metrics()["process"]}


def run(args) -> int:
    bench = load("BENCHMARK.json")
    if args.workload not in {w["name"] for w in bench["workloads"]}:
        log(f"no workload {args.workload!r} in BENCHMARK.json")
        return 2
    seconds = bench["run_seconds"] if args.seconds is None else args.seconds
    try:
        c = load_cell(bench, args.workload, args.rehearsal)
        inputs = build_inputs(c["config"], c["traffic"], args.seed)
    except (byname.Unknown, BadTraffic) as e:
        log(f"FAILED before the server starts: {e}")
        return 2
    cell = c["cell"]
    log(f"{cell['name']}: inputs from seed {args.seed}: {len(inputs['body'])} bytes, "
        f"{inputs['units']} units per request")
    server, work = start_server(c, bool(args.trace))
    profile_dir = os.path.join(work, "profile")
    slice_ = Slice(server, c["traffic"]["trace"], profile_dir) if args.trace else None

    def on_term(signum, frame):
        raise client.BenchFailure(f"signal {signum}")

    signal.signal(signal.SIGTERM, on_term)
    try:
        got = drive(server, c, inputs, args.seed, seconds, slice_)
        trace_file = slice_.collect(time.monotonic() + 300) if slice_ is not None else None
    except (client.BenchFailure, OSError, ValueError, KeyError) as e:
        if slice_ is not None:
            slice_.stop()
        missed = isinstance(e, SliceMissed)  # the placement's fault, not the server's
        if not missed:
            log(f"FAILED: {type(e).__name__}: {e}")
            log("server output, last 40 lines:\n" + server.log_tail())
        server.stop()
        shutil.rmtree(work, ignore_errors=True)
        if missed:
            log(f"FAILED: the traced request ended before its slice opened: {e}")
        return 1
    warm, win, setup_s = got["warm"], got["win"], got["setup_s"]
    log("window closed" + (f"; trace file {'in hand' if trace_file else 'missing'}" if slice_ else ""))
    server.stop()
    log("server stopped; judging")
    device = device_report(work)
    # The server child is the only child this process has waited for.
    rss_mb = resource.getrusage(resource.RUSAGE_CHILDREN).ru_maxrss / 1024.0

    counted = win["counted"]
    not_timed = "not measured" if args.rehearsal else None
    print(json.dumps({"requests_counted": len(counted), "attempted": win["attempted"],
                      "failed": win["failed"], "finished_past_window": win["dropped"],
                      "wall_s": not_timed or [round(r["wall_s"], 4) for r in counted],
                      "warmup_wall_s": not_timed or [round(r["wall_s"], 4) for r in warm]}))
    if c["is_job"]:
        print(json.dumps({"job_counts": sorted({tuple(checks.job_counts(r["doc"])) for r in counted})}))
    else:
        grew = lambda r, *path: readers.growth(r, list(path))
        print(json.dumps({
            "passes_per_request": [grew(r, "counters", "scheduling_passes") for r in counted],
            "exports_per_request": [r["exports"] for r in counted],
            "export_bytes": sorted({r["export_bytes"] for r in counted}),
            "export_s": not_timed or [round(r["phases_s"]["export"], 3) for r in counted],
            "bind_s": not_timed or [round(grew(r, "timings", "bind", "total_seconds") or 0.0, 3)
                                    for r in counted],
        }))
    print(json.dumps({"compile_cache_entries": {
        "window_start": win["cache_entries"][0], "window_end": win["cache_entries"][1],
        "added_in_window": win["cache_entries"][1] - win["cache_entries"][0]},
        "client_worst_probe_overrun_s": not_timed or round(server.late_s, 6),
        "client_late_wakeups": not_timed or win["client_late_wakeups"][:20],
        "slow_requests": not_timed or slow_requests(counted, c["is_job"])}))
    correct, compared = print_comparisons(
        judge(c, inputs, args.seed, win, warm, got["reservoir"], device, got["ident"]))

    trace = None
    if trace_file:
        trace = reduce_slice(trace_file, profile_dir, counted)
        if args.keep_trace:
            os.makedirs(args.keep_trace, exist_ok=True)
            shutil.copy(trace_file, os.path.join(args.keep_trace, f"{cell['name']}.xplane.pb"))
    shutil.rmtree(work, ignore_errors=True)
    ctx = {"requests": counted, "setup_s": setup_s, "trace": trace, "rss_mb": rss_mb}
    metrics, kinds = {}, {}
    for m in bench["per_layer"] if args.trace else bench["end_to_end"]:
        if "workloads" in m and cell["name"] not in m["workloads"]:
            continue
        spec = load(f"benchmark/metrics/{m['name']}.json")
        value = readers.KINDS[spec["kind"]](ctx, spec)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
            kinds[m["name"]] = spec["kind"]

    if args.rehearsal:
        # Counts only: nothing here may read as a chip run.
        counts = {k: v["value"] for k, v in metrics.items()
                  if kinds[k] in ("job_result", "metrics_counter")}
        log_compared(compared)
        print(json.dumps({
            "rehearsal": True, "platform": device.get("platform"), "correct": correct,
            "attempted": win["attempted"], "failed": win["failed"], "counts": counts,
            "not_measured": sorted(set(metrics) - set(counts))
            + ["device.busy_s", "device.memory_peak_bytes"],
        }))
        return 0 if correct else 1
    dev = {"platform": device.get("platform"), "kind": device.get("kind"),
           "count": device.get("count"), "memory_peak_bytes": device.get("memory_peak_bytes")}
    line = {"correct": correct, "attempted": win["attempted"], "failed": win["failed"],
            "metrics": metrics, "device": dev}
    if args.trace:
        if not trace or trace["busy_s"] <= 0:
            log_compared(compared)
            log(f"FAILED: the traced slice holds no device operation: {slice_.placement()}")
            return 1
        dev["busy_s"], dev["window_s"] = trace["busy_s"], trace["window_s"]
        line["breakdown"] = {"device_ops": trace["device_ops"], "idle_gaps": trace["idle_gaps"]}
    line["compared"] = compared
    log_compared(compared)
    print(json.dumps(line), flush=True)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None,
                    help="the measured window; default: BENCHMARK.json's run_seconds")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearsal", action="store_true",
                    help="tiny sizes, server on JAX_PLATFORMS=cpu, counts only (debugging)")
    ap.add_argument("--keep-trace", default=None, help="copy the .xplane.pb into this directory")
    return run(ap.parse_args(argv))


if __name__ == "__main__":
    sys.exit(main())
