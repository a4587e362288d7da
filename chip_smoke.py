#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that ksim-tpu still starts and
computes on the attached chip.

Drives the product's main path once, through the entry points a user
calls, against ONE server process (``python -m ksim_tpu.cmd.simulator``)
that is the only process to initialise a JAX backend:

- phase ``served``: BASELINE config 2 — 1,000 pending pods on 200 nodes
  through ``POST /api/v1/import`` under the product defaults
  (record="full", preemption on, f32), read back from
  ``GET /api/v1/export`` and checked with plain-Python sums that share
  nothing with the kernels;
- phase ``replay``: BASELINE config 5's 2,000-node cluster through the
  job plane (``POST /api/v1/jobs``, ``deviceReplay``) on the 6k-event
  locked prefix of the churn stream; ``--seed 0`` must reproduce the
  behaviour lock 2524 / 471 with every step on the device path.

Exit 0 only if every check passed AND the server reports ``platform:
"tpu"``; stdout then carries two JSON lines — the detail document
(versions, per-phase counts, ``reduced``, compile cache), and LAST the
verdict the chip check reads, exactly ``{"ok": true, "device":
{"platform", "kind", "count"}}`` as the server's JAX reports them.
Nothing is printed on stdout otherwise.  ``--rehearsal`` (explicit,
never auto-detected) runs the same code path at a tiny size with the
server pinned to ``JAX_PLATFORMS=cpu`` — how to debug before spending
chip time; it prints the detail document (``"rehearsal": true``) and no
verdict line, so a CPU run can never read as a chip pass.

This parent stays stdlib-only (``make lint`` import-boundary): the
inputs are generated by a short-lived child that exits before the
server starts, so exactly one process ever holds the chip.  Wall times
in the output are set-up information (they include compilation), not a
benchmark.
"""

from __future__ import annotations

import argparse
from fractions import Fraction
import importlib.metadata
import json
import os
import signal
import socket
import subprocess
import sys
import tempfile
import time
import urllib.error
import urllib.request

REPO = os.path.dirname(os.path.abspath(__file__))

PREFIX = "kube-scheduler-simulator.sigs.k8s.io/"
FILTER_KEY = PREFIX + "filter-result"
FINAL_SCORE_KEY = PREFIX + "finalscore-result"
SELECTED_NODE_KEY = PREFIX + "selected-node"

#: served = BASELINE config 2; replay = BASELINE config 5's cluster at
#: full width on the locked 6k-event prefix of its 50k-event stream.
FULL_SIZES = {
    "served_nodes": 200, "served_pods": 1000, "served_min_bound": 900,
    "replay_nodes": 2000, "replay_events": 6000,
}
REHEARSAL_SIZES = {
    "served_nodes": 20, "served_pods": 100, "served_min_bound": 80,
    "replay_nodes": 200, "replay_events": 800,
}
#: (seed, nodes, events) -> (podsScheduled, unschedulableAttempts): the
#: behaviour locks (CLAUDE.md, tests/test_behavior_locks.py).
LOCKED_COUNTS = {
    (0, 2000, 6000): (2524, 471),
    (0, 200, 800): (358, 8),  # the rehearsal size
}
REDUCED = [
    "served: 1,000 pods x 200 nodes (BASELINE config 2) is the widest the "
    "record=full path can be served over HTTP — annotation volume grows as "
    "pods x nodes x plugins (~235 MB of export here, several GB at 5k x 1k)",
    "replay: cluster at full width (2,000 nodes, BASELINE config 5); the "
    "stream is cut from 50,000 events to its locked 6,000-event prefix",
]

READY_CAP_S = 180.0
SERVED_CAP_S = 420.0
REPLAY_CAP_S = 600.0
TOTAL_CAP_S = 1100.0  # the contract allows 1200 s, compilation included
TERMINAL_STATES = ("succeeded", "failed", "cancelled", "interrupted")


class SmokeFailure(Exception):
    """A failed check, a missed deadline or a dead server."""


_T0 = time.monotonic()


def log(msg: str) -> None:
    print(f"[chip_smoke +{time.monotonic() - _T0:7.1f}s] {msg}", file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# Checks — pure functions over documents (tests/test_chip_smoke.py feeds
# them canned ones)
# ---------------------------------------------------------------------------

_SUFFIX = {
    "m": 1, "": 1000, "k": 10**6, "M": 10**9, "G": 10**12, "T": 10**15,
    "Ki": 1000 * 2**10, "Mi": 1000 * 2**20, "Gi": 1000 * 2**30, "Ti": 1000 * 2**40,
}


def milli(quantity: "str | int | None") -> int:
    """A Kubernetes resource quantity in integer milli-units."""
    if quantity is None:
        return 0
    q = str(quantity)
    digits = q.rstrip("KMGTikm")
    return int(Fraction(digits) * _SUFFIX[q[len(digits):]])


def check_device(ident: dict, *, rehearsal: bool) -> list[str]:
    """The backend the SERVER reports (metrics ``process`` block or a job
    result's ``replay`` block).  JAX falls back to the CPU with only a
    warning when the TPU fails to initialise; this is where that shows."""
    want = "cpu" if rehearsal else "tpu"
    fails = []
    if ident.get("platform") != want:
        fails.append(f"server reports platform {ident.get('platform')!r}, need {want!r}")
    if not ident.get("device_kind"):
        fails.append("server reports no device_kind")
    if not isinstance(ident.get("device_count"), int) or ident["device_count"] < 1:
        fails.append(f"server reports device_count {ident.get('device_count')!r}")
    return fails


def check_served(
    export: dict, nodes: list, pods: list, *, min_bound: int, sample: int = 50
) -> "tuple[dict, list[str]]":
    """The exported cluster against the imported one: every pod
    annotated, bound only to imported nodes, no node over its
    allocatable, and (for ``sample`` pods) annotations that parse, name
    only imported nodes and agree with the binding."""
    fails: list[str] = []
    alloc = {
        n["metadata"]["name"]: n["status"]["allocatable"] for n in nodes
    }
    exported = {p["metadata"]["name"]: p for p in export.get("pods") or []}
    used = {name: [0, 0, 0] for name in alloc}  # milli-cpu, milli-bytes, pods
    annotated = bound = 0
    stride = max(len(pods) // sample, 1)
    for i, src in enumerate(pods):
        name = src["metadata"]["name"]
        pod = exported.get(name)
        if pod is None:
            fails.append(f"pod {name} missing from the export")
            continue
        anno = pod["metadata"].get("annotations") or {}
        has_results = FILTER_KEY in anno and FINAL_SCORE_KEY in anno
        annotated += has_results
        node = pod.get("spec", {}).get("nodeName")
        if node:
            bound += 1
            if node not in alloc:
                fails.append(f"pod {name} bound to unknown node {node!r}")
                continue
            if anno.get(SELECTED_NODE_KEY) != node:
                fails.append(
                    f"pod {name}: selected-node {anno.get(SELECTED_NODE_KEY)!r} "
                    f"!= spec.nodeName {node!r}"
                )
            u = used[node]
            for c in pod["spec"].get("containers") or []:
                req = (c.get("resources") or {}).get("requests") or {}
                u[0] += milli(req.get("cpu"))
                u[1] += milli(req.get("memory"))
            u[2] += 1
        if has_results and i % stride == 0:
            fails.extend(_check_annotations(name, anno, node, alloc))
    for node, (cpu, mem, count) in used.items():
        a = alloc[node]
        if cpu > milli(a["cpu"]) or mem > milli(a["memory"]) or count > int(a["pods"]):
            fails.append(
                f"node {node} over-committed: cpu {cpu}m/{milli(a['cpu'])}m "
                f"memory {mem // 1000}/{milli(a['memory']) // 1000} "
                f"pods {count}/{a['pods']}"
            )
    if annotated != len(pods):
        fails.append(f"{annotated} of {len(pods)} pods carry result annotations")
    if bound < min_bound:
        fails.append(f"{bound} pods bound, need at least {min_bound}")
    counts = {
        "nodes": len(nodes), "pods": len(pods), "annotated": annotated, "bound": bound,
    }
    return counts, fails


def _check_annotations(name: str, anno: dict, node: "str | None", alloc: dict) -> list[str]:
    fails = []
    docs = {}
    for key in (FILTER_KEY, FINAL_SCORE_KEY):
        try:
            docs[key] = json.loads(anno[key])
        except ValueError as e:
            return [f"pod {name}: {key} is not JSON ({e})"]
        if not isinstance(docs[key], dict) or not set(docs[key]) <= set(alloc):
            return [f"pod {name}: {key} names nodes outside the import"]
    if node:
        verdicts = docs[FILTER_KEY].get(node)
        if not verdicts or any(v != "passed" for v in verdicts.values()):
            fails.append(f"pod {name}: bound to {node} which did not pass its filters")
        if node not in docs[FINAL_SCORE_KEY]:
            fails.append(f"pod {name}: bound to {node} which has no final score")
    return fails


def check_replay(doc: dict, *, expect: "tuple[int, int] | None") -> "tuple[dict, list[str]]":
    """A device-replay job's result document: succeeded, the locked
    counts (when this seed/size has a lock), and — the point of the
    phase — every step on the device path.  The product's containment
    (watchdog, breaker, fallback router) keeps the counts right when the
    device path never ran; on a healthy chip any of it firing fails."""
    fails: list[str] = []
    if doc.get("state") != "succeeded":
        return {}, [f"job ended {doc.get('state')!r}: {doc.get('message')}"]
    result = doc.get("result") or {}
    replay = doc.get("replay") or {}
    counts = {
        k: result.get(k) for k in ("podsScheduled", "unschedulableAttempts", "steps")
    }
    for k in (
        "device_steps", "fallback_steps", "device_errors", "watchdog_timeouts",
        "breaker_tripped", "unsupported",
    ):
        counts[k] = replay.get(k)
    got = (counts["podsScheduled"], counts["unschedulableAttempts"])
    if expect is not None and got != expect:
        fails.append(f"replay counts {got} != locked {expect}")
    for k in ("fallback_steps", "device_errors", "watchdog_timeouts"):
        if counts[k] != 0:
            fails.append(f"replay.{k} = {counts[k]!r}, need 0")
    if counts["breaker_tripped"] is not False:
        fails.append(f"replay.breaker_tripped = {counts['breaker_tripped']!r}")
    if counts["unsupported"] != {}:
        fails.append(f"replay.unsupported = {counts['unsupported']!r}, need {{}}")
    if not counts["steps"] or counts["device_steps"] != counts["steps"]:
        fails.append(
            f"replay.device_steps {counts['device_steps']!r} != steps {counts['steps']!r}"
        )
    return counts, fails


# ---------------------------------------------------------------------------
# Inputs — generated from the seed in a child that exits before the
# server starts (ksim_tpu.scenario imports jax)
# ---------------------------------------------------------------------------


def _child_emit_inputs(seed: int, sizes: dict) -> int:
    from ksim_tpu.scenario import churn_scenario, spec_from_operations
    from tests.helpers import random_cluster

    nodes, pods = random_cluster(
        seed, sizes["served_nodes"], sizes["served_pods"], bound_fraction=0.0
    )
    job = {
        "spec": {
            "simulator": {
                "deviceReplay": True, "preemption": True,
                "maxPodsPerPass": 1024, "podBucketMin": 128,
            },
            "scenario": spec_from_operations(list(churn_scenario(
                seed, n_nodes=sizes["replay_nodes"],
                n_events=sizes["replay_events"], ops_per_step=100,
            ))),
        }
    }
    json.dump({"nodes": nodes, "pods": pods, "job": job}, sys.stdout)
    return 0


def generate_inputs(seed: int, sizes: dict) -> dict:
    out = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--seed", str(seed),
         "--emit-inputs", json.dumps(sizes)],
        cwd=REPO, stdout=subprocess.PIPE, timeout=300,
    )
    if out.returncode != 0:
        raise SmokeFailure(f"input generation exited with {out.returncode}")
    return json.loads(out.stdout)


# ---------------------------------------------------------------------------
# The server child
# ---------------------------------------------------------------------------


class Server:
    """One ``python -m ksim_tpu.cmd.simulator`` child on a free port."""

    def __init__(self, env: dict) -> None:
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            self.port = s.getsockname()[1]
        self._stderr = tempfile.TemporaryFile()
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "ksim_tpu.cmd.simulator", "--port", str(self.port)],
            cwd=REPO, env=env, stdout=self._stderr, stderr=subprocess.STDOUT,
        )

    def request(self, method: str, path: str, body: "bytes | None" = None,
                timeout: float = 300.0) -> "tuple[int, bytes]":
        req = urllib.request.Request(
            f"http://127.0.0.1:{self.port}{path}", method=method, data=body,
            headers={"Content-Type": "application/json"},
        )
        try:
            with urllib.request.urlopen(req, timeout=timeout) as resp:
                return resp.status, resp.read()
        except urllib.error.HTTPError as e:
            return e.code, e.read()

    def json(self, method: str, path: str, body: "bytes | None" = None) -> dict:
        status, raw = self.request(method, path, body)
        if status not in (200, 202):
            raise SmokeFailure(f"{method} {path} -> HTTP {status}: {raw[:300]!r}")
        return json.loads(raw) if raw else {}

    def exit_code(self) -> "int | None":
        return self.proc.poll()

    def stop(self) -> None:
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(60)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait()

    def stderr_tail(self, lines: int = 40) -> str:
        self._stderr.seek(0)
        return "\n".join(
            self._stderr.read().decode("utf-8", "replace").splitlines()[-lines:]
        )


def poll(server, what: str, deadline: float, probe, interval: float = 0.5):
    """Call ``probe`` until it returns non-None.  Every wait in this
    script ends here: the service loop logs a failed pass and keeps
    going, so a kernel the compiler refuses shows up as pods that never
    bind — a deadline, not a crash."""
    while True:
        rc = server.exit_code()
        if rc is not None:
            raise SmokeFailure(f"server exited with code {rc} while waiting for {what}")
        got = probe()
        if got is not None:
            return got
        if time.monotonic() >= deadline:
            raise SmokeFailure(f"deadline passed waiting for {what}")
        time.sleep(interval)


# ---------------------------------------------------------------------------
# The two phases
# ---------------------------------------------------------------------------


def _ready(server):
    try:
        return server.request("GET", "/api/v1/metrics", timeout=5)[0] == 200 or None
    except OSError:
        return None


def phase_served(server, inputs: dict, sizes: dict, *, rehearsal: bool, deadline: float):
    nodes, pods = inputs["nodes"], inputs["pods"]
    snapshot = {
        "nodes": nodes, "pods": pods, "pvs": [], "pvcs": [], "storageClasses": [],
        "priorityClasses": [], "namespaces": [], "schedulerConfig": None,
    }
    t0 = time.monotonic()
    status, raw = server.request("POST", "/api/v1/import", json.dumps(snapshot).encode())
    if status != 200:
        raise SmokeFailure(f"POST /api/v1/import -> HTTP {status}: {raw[:300]!r}")
    first_pass_s = None

    def attempted():
        nonlocal first_pass_s
        m = server.json("GET", "/api/v1/metrics")
        if m["process"].get("platform") is None:
            return None
        if first_pass_s is None:
            first_pass_s = time.monotonic() - t0
            log(f"served: first pass done, server on {m['process']['platform']}")
            # Fail NOW on the wrong backend, not after the whole phase.
            _require("device", check_device(m["process"], rehearsal=rehearsal))
        return m["counters"].get("scheduling_attempts", 0) >= len(pods) or None

    poll(server, "every imported pod to be attempted", deadline, attempted)

    def exported():
        status, raw = server.request("GET", "/api/v1/export")
        if status != 200:
            raise SmokeFailure(f"GET /api/v1/export -> HTTP {status}")
        doc = json.loads(raw)
        done = all(
            FILTER_KEY in (p["metadata"].get("annotations") or {})
            for p in doc.get("pods") or []
        )
        return (doc, len(raw)) if done else None

    export, export_bytes = poll(
        server, "every pod to carry result annotations", deadline, exported, interval=2.0
    )
    counts, fails = check_served(
        export, nodes, pods, min_bound=sizes["served_min_bound"]
    )
    counts.update(
        export_bytes=export_bytes,
        first_pass_wall_s=round(first_pass_s, 1),
        setup_wall_s=round(time.monotonic() - t0, 1),
    )
    return counts, fails


def phase_replay(server, inputs: dict, sizes: dict, seed: int, *, rehearsal: bool,
                 deadline: float):
    t0 = time.monotonic()
    job = server.json("POST", "/api/v1/jobs", json.dumps(inputs["job"]).encode())
    path = f"/api/v1/jobs/{job['id']}"

    def finished():
        return server.json("GET", path)["state"] in TERMINAL_STATES or None

    poll(server, f"job {job['id']} to finish", deadline, finished)
    doc = server.json("GET", path + "/result")
    expect = LOCKED_COUNTS.get((seed, sizes["replay_nodes"], sizes["replay_events"]))
    counts, fails = check_replay(doc, expect=expect)
    fails += check_device(doc.get("replay") or {}, rehearsal=rehearsal)
    counts.update(
        nodes=sizes["replay_nodes"], events=sizes["replay_events"],
        locked=expect is not None, setup_wall_s=round(time.monotonic() - t0, 1),
    )
    return counts, fails


def _cache_report() -> dict:
    """Where the server's persistent compile cache lives
    (ksim_tpu.util.enable_compilation_cache) and what it holds now."""
    root = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(REPO, ".jax_cache")
    entries = sum(len(files) for _, _, files in os.walk(root))
    return {"dir": root, "entries": entries}


def _version(dist: str) -> "str | None":
    try:
        return importlib.metadata.version(dist)
    except importlib.metadata.PackageNotFoundError:
        return None


def _require(what: str, fails: list) -> None:
    if fails:
        raise SmokeFailure(f"{what}: " + "; ".join(fails[:10]))


def run(seed: int, sizes: dict, *, rehearsal: bool) -> dict:
    """Both phases against one server; the summary document, or
    SmokeFailure.  The server is stopped on every way out."""
    start = time.monotonic()
    inputs = generate_inputs(seed, sizes)
    log(f"inputs generated: {len(inputs['pods'])} pods x {len(inputs['nodes'])} nodes, "
        f"{len(inputs['job']['spec']['scenario']['operations'])} replay operations")
    env = dict(os.environ)
    if rehearsal:
        env["JAX_PLATFORMS"] = "cpu"
    server = Server(env)
    total = start + TOTAL_CAP_S
    try:
        poll(
            server, "the server to answer",
            min(time.monotonic() + READY_CAP_S, total), lambda: _ready(server),
        )
        log(f"server answers on :{server.port}")
        served, fails = phase_served(
            server, inputs, sizes, rehearsal=rehearsal,
            deadline=min(time.monotonic() + SERVED_CAP_S, total),
        )
        log(f"served: {served}")
        _require("served", fails)
        replay, fails = phase_replay(
            server, inputs, sizes, seed, rehearsal=rehearsal,
            deadline=min(time.monotonic() + REPLAY_CAP_S, total),
        )
        log(f"replay: {replay}")
        _require("replay", fails)
        ident = server.json("GET", "/api/v1/metrics")["process"]
        _require("device", check_device(ident, rehearsal=rehearsal))
    except (SmokeFailure, OSError, ValueError, KeyError):
        log("server output, last 40 lines:\n" + server.stderr_tail())
        raise
    finally:
        server.stop()
    return {
        "ok": True,
        "rehearsal": rehearsal,
        "platform": ident["platform"],
        "device_kind": ident["device_kind"],
        "device_count": ident["device_count"],
        "jax": _version("jax"), "jaxlib": _version("jaxlib"), "libtpu": _version("libtpu"),
        "seed": seed,
        "phases": {"served": served, "replay": replay},
        "wall_note": "*_wall_s are host-clock set-up information, compilation "
                     "included — not a benchmark",
        "reduced": REDUCED,
        "compile_cache": _cache_report(),
    }


def verdict(summary: dict) -> dict:
    """The chip check's line: these keys and no others, the device as
    the server's JAX reported it."""
    return {
        "ok": True,
        "device": {
            "platform": summary["platform"], "kind": summary["device_kind"],
            "count": summary["device_count"],
        },
    }


def main(argv: "list[str] | None" = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument(
        "--rehearsal", action="store_true",
        help="tiny size with the server on JAX_PLATFORMS=cpu (debugging only)",
    )
    ap.add_argument("--emit-inputs", default=None, help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.emit_inputs is not None:
        return _child_emit_inputs(args.seed, json.loads(args.emit_inputs))

    def on_term(signum, frame):
        raise SmokeFailure(f"signal {signum}")

    signal.signal(signal.SIGTERM, on_term)
    sizes = REHEARSAL_SIZES if args.rehearsal else FULL_SIZES
    try:
        summary = run(args.seed, sizes, rehearsal=args.rehearsal)
    except (SmokeFailure, OSError, ValueError, KeyError) as e:
        log(f"FAILED: {type(e).__name__}: {e}")
        return 1
    print(json.dumps(summary), flush=True)
    if not args.rehearsal:
        print(json.dumps(verdict(summary)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
