"""The server child and the client's side of every request.

Copied from ``chip_smoke.py`` (PR 21) where it drove the served path
soundly — child on a free port, readiness probe, stderr tail — with its
waits replaced: a job's end is read off its server-sent event stream (a
blocking read, no poll), an import's end off the cheap
``/api/v1/metrics`` counters at ``PROBE_S`` with one export at the end.
Stdlib only: this process never imports jax, so the child is the one
process that holds the chip.
"""

from __future__ import annotations

import http.client
import json
import os
import signal
import socket
import subprocess
import sys
import tempfile
import time

from checks import FILTER_KEY_RAW

HERE = os.path.dirname(os.path.abspath(__file__))
TERMINAL_STATES = ("succeeded", "failed", "cancelled", "interrupted")
PROBE_S = 0.02


class BenchFailure(Exception):
    """A dead server, a missed deadline or an HTTP error: the run has no
    result."""


class CannotSucceed(BenchFailure):
    """What the run has seen already breaks a guarantee of the configuration:
    no later request can make it correct, so it ends now."""


class Server:
    """One server child (``server_child.py``) on a free port, cwd = the
    checkout root."""

    def __init__(self, root: str, env: dict, platform: str, chips: int) -> None:
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            self.port = s.getsockname()[1]
        self._log = tempfile.TemporaryFile()
        self.late_s = 0.0  # worst probe overrun since reset_lateness()
        #: Names of trace events on a job's stream that end the run at once
        #: (``run.py`` sets them from the configuration's guarantees).
        self.fatal_events: tuple = ()
        self.proc = subprocess.Popen(
            [sys.executable, os.path.join(HERE, "server_child.py"), platform, str(chips),
             "--port", str(self.port)],
            cwd=root, env=env, stdout=self._log, stderr=subprocess.STDOUT,
        )

    # -- plumbing ---------------------------------------------------------

    def request(self, method: str, path: str, body: "bytes | None" = None,
                timeout: float = 600.0) -> "tuple[int, bytes]":
        conn = http.client.HTTPConnection("127.0.0.1", self.port, timeout=timeout)
        try:
            conn.request(method, path, body=body,
                         headers={"Content-Type": "application/json"})
            resp = conn.getresponse()
            return resp.status, resp.read()
        finally:
            conn.close()

    def json(self, method: str, path: str, body: "bytes | None" = None) -> dict:
        status, raw = self.request(method, path, body)
        if status not in (200, 202):
            raise BenchFailure(f"{method} {path} -> HTTP {status}: {raw[:300]!r}")
        return json.loads(raw) if raw else {}

    def alive(self) -> None:
        rc = self.proc.poll()
        if rc is not None:
            raise BenchFailure(f"server exited with code {rc}")

    def sleep_probe(self, interval: float = PROBE_S) -> None:
        """Sleep one probe interval and record by how much the wake-up
        overran: a starved client must not read as a fast server."""
        due = time.monotonic() + interval
        time.sleep(interval)
        self.late_s = max(self.late_s, time.monotonic() - due)
        self.alive()

    def wait_ready(self, deadline: float) -> None:
        while True:
            self.alive()
            try:
                if self.request("GET", "/api/v1/metrics", timeout=5)[0] == 200:
                    return
            except OSError:
                pass
            if time.monotonic() >= deadline:
                raise BenchFailure("deadline passed waiting for the server to answer")
            time.sleep(0.1)

    def stop(self) -> None:
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(120)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait()

    def log_tail(self, lines: int = 40) -> str:
        self._log.seek(0)
        return "\n".join(self._log.read().decode("utf-8", "replace").splitlines()[-lines:])

    # -- the two kinds of request ----------------------------------------

    def metrics(self) -> dict:
        return self.json("GET", "/api/v1/metrics")

    def run_job(self, body: bytes, deadline: float) -> dict:
        """POST a job, block on its event stream until the terminal state,
        fetch the result document.  ``wall_s`` spans all three."""
        t0 = time.monotonic()
        job = self.json("POST", "/api/v1/jobs", body)
        conn = http.client.HTTPConnection(
            "127.0.0.1", self.port, timeout=max(deadline - time.monotonic(), 1.0))
        state = None
        try:
            conn.request("GET", f"/api/v1/jobs/{job['id']}/events")
            resp = conn.getresponse()
            if resp.status != 200:
                raise BenchFailure(f"event stream -> HTTP {resp.status}")
            for raw in resp:
                if not raw.startswith(b"data:"):
                    continue
                ev = json.loads(raw[5:])
                if ev.get("event") == "state" and ev.get("state") in TERMINAL_STATES:
                    state = ev["state"]
                    break
                if ev.get("event") == "trace" and ev.get("name") in self.fatal_events:
                    self.request("DELETE", f"/api/v1/jobs/{job['id']}", timeout=30.0)
                    raise CannotSucceed(
                        f"job {job['id']} emitted {ev['name']} after "
                        f"{time.monotonic() - t0:.1f} s: {json.dumps(ev.get('args') or {})}")
        except (OSError, http.client.HTTPException) as e:
            self.alive()
            raise BenchFailure(f"event stream of {job['id']} broke: {e}") from e
        finally:
            conn.close()
        if state is None:
            self.alive()
            raise BenchFailure(f"event stream of {job['id']} ended without a terminal state")
        doc = self.json("GET", f"/api/v1/jobs/{job['id']}/result")
        return {"wall_s": time.monotonic() - t0, "doc": doc, "id": job["id"],
                "phases_s": {}}

    def job_spans(self, job_id: str) -> list:
        """The job's private trace ring (always recorded) as ``(start,
        end, name, thread)`` in UNIX seconds — read after the timed
        request."""
        doc = self.json("GET", f"/api/v1/jobs/{job_id}/trace")
        epoch = float((doc.get("otherData") or {}).get("epoch_unix_s") or 0.0)
        return [(epoch + e["ts"] * 1e-6, epoch + (e["ts"] + e["dur"]) * 1e-6, e["name"], e.get("tid"))
                for e in doc.get("traceEvents") or [] if e.get("ph") == "X"]

    def reset(self, deadline: float) -> None:
        """``PUT /api/v1/reset`` and wait for the boot state (no pods, no
        nodes) — outside the timed request."""
        status, raw = self.request("PUT", "/api/v1/reset")
        if status != 202:
            raise BenchFailure(f"PUT /api/v1/reset -> HTTP {status}: {raw[:200]!r}")
        while True:
            doc = self.json("GET", "/api/v1/export")
            if not doc.get("pods") and not doc.get("nodes"):
                return
            if time.monotonic() >= deadline:
                raise BenchFailure("deadline passed waiting for the boot state")
            self.sleep_probe()

    def _attempts(self) -> int:
        return self.metrics()["counters"].get("scheduling_attempts", 0)

    def _post_import(self, body: bytes) -> None:
        status, raw = self.request("POST", "/api/v1/import", body)
        if status != 200:
            raise BenchFailure(f"POST /api/v1/import -> HTTP {status}: {raw[:300]!r}")

    def _wait_attempts(self, target: int, deadline: float) -> None:
        while self._attempts() < target:
            if time.monotonic() >= deadline:
                raise BenchFailure("deadline passed waiting for every pod to be attempted")
            self.sleep_probe()

    def run_import(self, body: bytes, n_pods: int, deadline: float) -> dict:
        """POST a snapshot, wait until every pod was attempted (counter
        probe), export once; an export in which fewer than ``n_pods`` pods
        carry result annotations is a second wait, counted in ``exports``
        and in the wall.  The export stays raw bytes inside the request."""
        before = self.metrics()
        base = before["counters"].get("scheduling_attempts", 0)
        t0 = time.monotonic()
        self._post_import(body)
        t_posted = time.monotonic()
        exports = 0
        while True:
            self._wait_attempts(base + n_pods, deadline)
            t_e = time.monotonic()
            status, raw = self.request("GET", "/api/v1/export")
            if status != 200:
                raise BenchFailure(f"GET /api/v1/export -> HTTP {status}")
            export_s = time.monotonic() - t_e
            exports += 1
            if raw.count(FILTER_KEY_RAW) >= n_pods:
                break
            if time.monotonic() >= deadline:
                raise BenchFailure("deadline passed waiting for every pod to be annotated")
            self.sleep_probe(0.25)
        wall = time.monotonic() - t0
        after = self.metrics()
        return {
            "wall_s": wall, "raw": raw, "export_bytes": len(raw), "exports": exports,
            "phases_s": {"import_post": t_posted - t0, "export": export_s},
            "metrics_before": before, "metrics_after": after,
        }
