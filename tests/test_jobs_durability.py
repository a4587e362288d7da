"""Durable job plane (round 15, ksim_tpu/jobs/journal.py +
engine/compilecache.py disk layer): crash-safe journal units
(torn-tail/corrupt-CRC bytes are HAND-WRITTEN, never derived from the
writer), persistent-executable cache units (fake disk spec, jax-free),
in-process restart recovery, the kill -9 end-to-end (slow; `make
restart-check` runs it), the SSE listener-leak regression, and the
round-16 segment-checkpoint matrix: crash at every checkpoint
boundary, corrupt-checkpoint fallback, skip containment, and the
SIGKILL-mid-run incremental resume (slow) whose suffix replay must
land the locked 6k churn counts byte-identically."""

from __future__ import annotations

import http.client
import json
import os
import socket
import subprocess
import sys
import threading
import time
import zlib

import pytest

from ksim_tpu.engine.compilecache import CompileCache
from ksim_tpu.faults import FAULTS, InjectedFault
from ksim_tpu.jobs import JobJournal, JobManager, LeasePlane
from ksim_tpu.jobs.journal import JOURNAL_NAME, _decode_line
from ksim_tpu.server import DIContainer, SimulatorServer
from tests.helpers import make_node, make_pod, sanitized_cpu_env


@pytest.fixture(autouse=True)
def _clean_fault_plane():
    FAULTS.reset()
    yield
    FAULTS.reset()


def tiny_doc(n_pods: int = 3) -> dict:
    ops = [
        {"step": 0, "createOperation": {"object": make_node(f"n{i}", cpu="4")}}
        for i in range(2)
    ]
    ops += [
        {"step": i + 1, "createOperation": {"object": make_pod(f"p{i}", cpu="100m")}}
        for i in range(n_pods)
    ]
    return {"spec": {"scenario": {"operations": ops}}}


def _wait(job, states, deadline_s=60.0):
    end = time.monotonic() + deadline_s
    while time.monotonic() < end:
        if job.status()["state"] in states:
            return job.status()
        time.sleep(0.02)
    raise AssertionError(f"job {job.id} never reached {states}: {job.status()}")


# ---------------------------------------------------------------------------
# Journal units: append/replay, torn tail, corrupt CRC, compaction
# ---------------------------------------------------------------------------


def test_journal_append_replay_round_trip(tmp_path):
    j = JobJournal(str(tmp_path / "j.jsonl"))
    recs = [
        {"t": "submit", "id": "a", "ordinal": 0, "priority": 0, "doc": {"x": 1}},
        {"t": "state", "id": "a", "state": "running", "ts": 1.0},
        {"t": "result", "id": "a", "result": {"podsScheduled": 3}},
        {"t": "state", "id": "a", "state": "succeeded", "ts": 2.0},
    ]
    for r in recs:
        j.append(r)
    assert JobJournal(j.path).replay() == recs
    snap = j.snapshot()
    assert snap["appends"] == 4 and snap["append_errors"] == 0


def test_journal_torn_tail_is_truncated_not_fatal(tmp_path):
    """A process killed mid-append leaves a partial final line; replay
    keeps every whole record and truncates the debris.  The torn bytes
    are hand-written — the writer never produces them."""
    j = JobJournal(str(tmp_path / "j.jsonl"))
    j.append({"t": "submit", "id": "a", "ordinal": 0, "priority": 0, "doc": {}})
    j.append({"t": "state", "id": "a", "state": "running", "ts": 1.0})
    torn = b'{"crc": 123, "rec": {"t": "state", "id": "a", "sta'
    with open(j.path, "ab") as f:
        f.write(torn)
    j2 = JobJournal(j.path)
    recs = j2.replay()
    assert [r["t"] for r in recs] == ["submit", "state"]
    assert j2.snapshot()["truncated_bytes"] == len(torn)
    # The file was repaired in place: a fresh append then full replay works.
    j2.append({"t": "state", "id": "a", "state": "succeeded", "ts": 2.0})
    assert [r["t"] for r in JobJournal(j.path).replay()] == [
        "submit", "state", "state",
    ]


def test_journal_corrupt_crc_drops_record_and_tail(tmp_path):
    """A bit-flipped record fails its checksum; the WAL contract can
    vouch for nothing after it, so the tail (even well-formed lines) is
    dropped too.  The bad line is hand-written with a deliberately
    wrong CRC."""
    j = JobJournal(str(tmp_path / "j.jsonl"))
    j.append({"t": "submit", "id": "a", "ordinal": 0, "priority": 0, "doc": {}})
    bad_rec = {"t": "state", "id": "a", "state": "running", "ts": 1.0}
    with open(j.path, "a", encoding="utf-8") as f:
        f.write(json.dumps({"crc": 1, "rec": bad_rec}) + "\n")
    j.append({"t": "state", "id": "a", "state": "succeeded", "ts": 2.0})
    j2 = JobJournal(j.path)
    recs = j2.replay()
    assert [r["t"] for r in recs] == ["submit"]
    assert j2.snapshot()["truncated_bytes"] > 0


def test_journal_garbage_and_missing_file(tmp_path):
    p = str(tmp_path / "j.jsonl")
    assert JobJournal(p).replay() == []  # missing file: empty registry
    with open(p, "w", encoding="utf-8") as f:
        f.write("not json at all\n")
    assert JobJournal(p).replay() == []


def test_journal_crc_covers_canonical_form(tmp_path):
    """A record re-serialized with different key order / whitespace
    still validates: the checksum is over the canonical JSON."""
    j = JobJournal(str(tmp_path / "j.jsonl"))
    rec = {"t": "submit", "id": "a", "ordinal": 0, "priority": 0, "doc": {"k": 1}}
    body = json.dumps(rec, sort_keys=True, separators=(",", ":"))
    crc = zlib.crc32(body.encode()) & 0xFFFFFFFF
    # Hand-write the wrapper with scrambled key order and spaces.
    with open(j.path, "w", encoding="utf-8") as f:
        f.write(json.dumps({"rec": rec, "crc": crc}, indent=None) + "\n")
    assert JobJournal(j.path).replay() == [rec]


def test_journal_compaction_bounds_file(tmp_path):
    j = JobJournal(str(tmp_path / "j.jsonl"), max_bytes=256)
    for i in range(50):
        j.append({"t": "state", "id": "a", "state": "running", "ts": float(i)})
    live = [{"t": "submit", "id": "a", "ordinal": 0, "priority": 0, "doc": {}}]
    assert j.maybe_compact(lambda: live) is True
    assert j.snapshot()["compactions"] == 1
    assert os.path.getsize(j.path) < 256
    assert JobJournal(j.path).replay() == live
    # Under the bound: no-op.
    assert j.maybe_compact(lambda: live) is False


def test_journal_append_fault_raises_and_counts(tmp_path):
    j = JobJournal(str(tmp_path / "j.jsonl"))
    FAULTS.arm("jobs.journal_append", "call:1")
    with pytest.raises(InjectedFault):
        j.append({"t": "submit", "id": "a", "ordinal": 0, "priority": 0, "doc": {}})
    assert j.snapshot()["append_errors"] == 1
    j.append({"t": "state", "id": "a", "state": "running", "ts": 1.0})
    assert [r["t"] for r in JobJournal(j.path).replay()] == ["state"]


# ---------------------------------------------------------------------------
# CompileCache disk layer (fake disk spec — stdlib-only, no jax)
# ---------------------------------------------------------------------------


class FakeDisk:
    """Duck-typed disk spec: 'serializes' to a fixed blob; load/invoke
    count calls so tests can tell the disk path from the compile path."""

    def __init__(self, path, token="tok-1", blob=b"fake-executable-bytes"):
        self.path = str(path)
        self.token = token
        self.blob = blob
        self.loads = 0
        self.invokes = 0
        self.fail_invoke = False

    def load(self, blob):
        assert blob == self.blob
        self.loads += 1
        return ("exec", blob)

    def invoke(self, exec_obj):
        self.invokes += 1
        if self.fail_invoke:
            raise RuntimeError("platform mismatch")
        return "disk-result"

    def serialize(self):
        return self.blob


def test_disk_store_then_warm_load(tmp_path):
    path = tmp_path / "e.aot"
    cc1 = CompileCache()
    d1 = FakeDisk(path)
    out = cc1.run("k", lambda: "compiled-result", disk=d1)
    assert out == "compiled-result"
    s1 = cc1.snapshot()
    assert s1["disk_misses"] == 1 and s1["disk_stores"] == 1
    header, _, blob = path.read_bytes().partition(b"\n")
    meta = json.loads(header)
    assert meta["v"] == 1 and meta["key"] == "tok-1"
    assert meta["crc"] == (zlib.crc32(blob) & 0xFFFFFFFF)
    # A "restarted process": fresh cache, same file -> no compile.
    cc2 = CompileCache()
    d2 = FakeDisk(path)
    out = cc2.run("k", lambda: pytest.fail("compiled on a disk hit"), disk=d2)
    assert out == "disk-result"
    s2 = cc2.snapshot()
    assert s2["disk_hits"] == 1 and d2.loads == 1 and d2.invokes == 1


def test_disk_corrupt_blob_evicted_and_recompiled(tmp_path):
    path = tmp_path / "e.aot"
    cc = CompileCache()
    cc.run("k", lambda: "r", disk=FakeDisk(path))
    raw = bytearray(path.read_bytes())
    raw[-3] ^= 0xFF  # hand-flip a blob byte: CRC must catch it
    path.write_bytes(bytes(raw))
    cc2 = CompileCache()
    out = cc2.run("k", lambda: "recompiled", disk=FakeDisk(path))
    assert out == "recompiled"
    s = cc2.snapshot()
    assert s["disk_evictions"] == 1 and s["disk_hits"] == 0
    # The eviction unlinked, then the store re-persisted a good entry.
    assert s["disk_stores"] == 1
    assert json.loads(path.read_bytes().partition(b"\n")[0])["v"] == 1


def test_disk_garbage_header_evicted(tmp_path):
    path = tmp_path / "e.aot"
    path.write_bytes(b"\x00\x01 not a header\nblob")
    cc = CompileCache()
    assert cc.run("k", lambda: "r", disk=FakeDisk(path)) == "r"
    assert cc.snapshot()["disk_evictions"] == 1


def test_disk_headerless_file_evicted(tmp_path):
    """No newline at all — the partition finds no separator."""
    path = tmp_path / "e.aot"
    path.write_bytes(b'{"v": 1, "crc": 0, "key": "tok-1"}')
    cc = CompileCache()
    assert cc.run("k", lambda: "r", disk=FakeDisk(path)) == "r"
    assert cc.snapshot()["disk_evictions"] == 1


def test_disk_key_mismatch_evicted(tmp_path):
    """A stale jaxlib (or hash-colliding path) changes the token; the
    entry must never reach the deserializer."""
    path = tmp_path / "e.aot"
    cc = CompileCache()
    cc.run("k", lambda: "r", disk=FakeDisk(path, token="jax-0.4.0|cpu|sig"))
    cc2 = CompileCache()
    d = FakeDisk(path, token="jax-9.9.9|cpu|sig")
    assert cc2.run("k", lambda: "recompiled", disk=d) == "recompiled"
    assert cc2.snapshot()["disk_evictions"] == 1
    assert d.loads == 0  # blob never handed to load()


def test_disk_exec_failure_evicts_and_falls_back(tmp_path):
    path = tmp_path / "e.aot"
    cc = CompileCache()
    cc.run("k", lambda: "r", disk=FakeDisk(path))
    cc2 = CompileCache()
    d = FakeDisk(path)
    d.fail_invoke = True
    assert cc2.run("k", lambda: "recompiled", disk=d) == "recompiled"
    s = cc2.snapshot()
    assert s["disk_evictions"] == 1 and d.loads == 1 and d.invokes == 1


def test_disk_serialize_none_skips_store(tmp_path):
    path = tmp_path / "e.aot"
    cc = CompileCache()
    d = FakeDisk(path)
    d.serialize = lambda: None  # non-exportable plan
    assert cc.run("k", lambda: "r", disk=d) == "r"
    assert cc.snapshot()["disk_stores"] == 0
    assert not path.exists()


# ---------------------------------------------------------------------------
# Manager recovery (in-process restarts: new JobManager over the same dir)
# ---------------------------------------------------------------------------


def test_restart_serves_result_byte_identically(tmp_path):
    jm = JobManager(workers=1, queue_limit=8, jobs_dir=str(tmp_path))
    job = jm.submit(tiny_doc())
    _wait(job, {"succeeded", "failed"})
    state, result, _ = job.result_view()
    assert state == "succeeded"
    jm.shutdown()
    jm2 = JobManager(workers=0, queue_limit=8, jobs_dir=str(tmp_path))
    j2 = jm2.get(job.id)
    assert j2 is not None
    state2, result2, _ = j2.result_view()
    assert state2 == "succeeded"
    assert json.dumps(result2, sort_keys=True) == json.dumps(result, sort_keys=True)
    jm2.shutdown()


def test_di_container_builds_manager_eagerly_when_jobs_dir_set(
    tmp_path, monkeypatch
):
    """A restarted SERVER must recover before the first tenant request:
    the DI container's lazy job-plane build (a classic-surface
    optimization) is skipped when KSIM_JOBS_DIR is set, otherwise a
    journaled result 404s until something happens to force the manager
    into existence — the gap an end-to-end restart drive caught."""
    jm = JobManager(workers=1, queue_limit=8, jobs_dir=str(tmp_path))
    job = jm.submit(tiny_doc())
    _wait(job, {"succeeded", "failed"})
    jm.shutdown()
    monkeypatch.setenv("KSIM_JOBS_DIR", str(tmp_path))
    monkeypatch.setenv("KSIM_JOBS_WORKERS", "0")
    di = DIContainer()
    try:
        recovered = di.job_manager_if_built
        assert recovered is not None  # built (and recovered) at construction
        j2 = recovered.get(job.id)
        assert j2 is not None
        state, result, _ = j2.result_view()
        assert state == "succeeded"
        assert result["result"]["podsScheduled"] == 3
    finally:
        di.shutdown()


def test_restart_marks_unfinished_jobs_interrupted(tmp_path):
    jm = JobManager(workers=0, queue_limit=8, jobs_dir=str(tmp_path))
    job = jm.submit(tiny_doc())  # no workers: stays queued forever
    jm.shutdown()
    jm2 = JobManager(workers=0, queue_limit=8, jobs_dir=str(tmp_path))
    j2 = jm2.get(job.id)
    state, result, error = j2.result_view()
    assert state == "interrupted"
    assert result is None and "restart" in error
    jm2.shutdown()


def test_resume_reenqueues_unfinished_jobs(tmp_path):
    jm = JobManager(workers=0, queue_limit=8, jobs_dir=str(tmp_path))
    job = jm.submit(tiny_doc())
    jm.shutdown()
    jm2 = JobManager(
        workers=1, queue_limit=8, jobs_dir=str(tmp_path), resume=True
    )
    j2 = jm2.get(job.id)
    final = _wait(j2, {"succeeded", "failed", "interrupted"})
    assert final["state"] == "succeeded", final
    assert j2.result_view()[1]["result"]["podsScheduled"] == 3
    jm2.shutdown()


def test_interrupted_then_resume_still_reenqueues(tmp_path):
    """Regression: a job journaled as `interrupted` by a resume-less
    restart must still be reachable by a LATER restart with
    KSIM_JOBS_RESUME=1 — interrupted is terminal for serving, not for
    the resume policy."""
    jm = JobManager(workers=0, queue_limit=8, jobs_dir=str(tmp_path))
    job = jm.submit(tiny_doc())
    jm.shutdown()
    jm2 = JobManager(workers=0, queue_limit=8, jobs_dir=str(tmp_path))
    assert jm2.get(job.id).result_view()[0] == "interrupted"
    jm2.shutdown()
    jm3 = JobManager(
        workers=1, queue_limit=8, jobs_dir=str(tmp_path), resume=True
    )
    final = _wait(jm3.get(job.id), {"succeeded", "failed", "interrupted"})
    assert final["state"] == "succeeded", final
    jm3.shutdown()


def test_recovery_survives_torn_tail(tmp_path):
    jm = JobManager(workers=1, queue_limit=8, jobs_dir=str(tmp_path))
    job = jm.submit(tiny_doc())
    _wait(job, {"succeeded"})
    jm.shutdown()
    torn = b'{"crc": 99, "rec": {"t": "subm'  # the kill -9 artifact
    with open(os.path.join(str(tmp_path), JOURNAL_NAME), "ab") as f:
        f.write(torn)
    jm2 = JobManager(workers=0, queue_limit=8, jobs_dir=str(tmp_path))
    assert jm2.get(job.id).result_view()[0] == "succeeded"
    assert jm2.snapshot()["journal"]["truncated_bytes"] == len(torn)
    jm2.shutdown()


def test_submit_append_fault_fails_one_job_not_registry(tmp_path):
    """An armed jobs.journal_append failure fails the ONE submission
    whose record was lost; the manager and later submissions are
    untouched."""
    FAULTS.arm("jobs.journal_append", "first:1", exc=OSError)
    # workers=0: the submit-path append is the only journal writer, so
    # the armed first:1 lands on it deterministically.
    jm = JobManager(workers=0, queue_limit=8, jobs_dir=str(tmp_path))
    job = jm.submit(tiny_doc())
    state, _, error = job.result_view()
    assert state == "failed" and "journal append failed" in error
    job2 = jm.submit(tiny_doc())
    assert job2.status()["state"] == "queued"
    jm.shutdown()
    # The failed job's submit record never landed: a restart only
    # knows the successful one — and resume runs it to completion.
    jm2 = JobManager(
        workers=1, queue_limit=8, jobs_dir=str(tmp_path), resume=True
    )
    assert jm2.get(job.id) is None
    assert _wait(jm2.get(job2.id), {"succeeded", "failed"})["state"] == "succeeded"
    jm2.shutdown()


def test_replay_fault_starts_empty_registry_not_crash(tmp_path):
    jm = JobManager(workers=1, queue_limit=8, jobs_dir=str(tmp_path))
    job = jm.submit(tiny_doc())
    _wait(job, {"succeeded"})
    jm.shutdown()
    FAULTS.arm("jobs.journal_replay", "call:1", exc=OSError)
    jm2 = JobManager(workers=0, queue_limit=8, jobs_dir=str(tmp_path))
    assert jm2.jobs() == []  # lost the registry, kept the process
    jm2.shutdown()


def test_cancel_is_journaled(tmp_path):
    jm = JobManager(workers=0, queue_limit=8, jobs_dir=str(tmp_path))
    job = jm.submit(tiny_doc())
    assert jm.cancel(job.id) == "cancelled"
    jm.shutdown()
    jm2 = JobManager(workers=0, queue_limit=8, jobs_dir=str(tmp_path))
    assert jm2.get(job.id).result_view()[0] == "cancelled"
    jm2.shutdown()


# ---------------------------------------------------------------------------
# The kill -9: a real process dies mid-job, the next one recovers
# ---------------------------------------------------------------------------

_CRASH_CHILD = r"""
import sys, time
import jax
jax.config.update("jax_platforms", "cpu")
from ksim_tpu.jobs import JobManager
from tests.helpers import make_node, make_pod

# 200 one-pod steps (enough to still be mid-run when killed) on nodes
# big enough that every pod fits — the resumed run must schedule ALL.
ops = [
    {"step": 0, "createOperation": {"object": make_node(f"n{i}", cpu="32")}}
    for i in range(2)
]
ops += [
    {"step": i + 1, "createOperation": {"object": make_pod(f"p{i}", cpu="100m")}}
    for i in range(200)
]
doc = {"spec": {"scenario": {"operations": ops}}}

jm = JobManager(workers=1, queue_limit=8, jobs_dir=sys.argv[1])
job = jm.submit(doc)
while job.status()["state"] == "queued":
    time.sleep(0.01)
print("RUNNING", job.id, flush=True)
time.sleep(600)  # parent kills -9 long before this returns
"""


@pytest.mark.slow
def test_sigkill_mid_job_then_restart_recovers(tmp_path):
    """The acceptance scenario: kill -9 a server mid-job; a restarted
    manager over the same KSIM_JOBS_DIR replays the journal, marks the
    died-mid-run job `interrupted`, and a resume restart re-runs it to
    completion."""
    proc = subprocess.Popen(
        [sys.executable, "-c", _CRASH_CHILD, str(tmp_path)],
        env=sanitized_cpu_env(),
        cwd="/root/repo",
        stdout=subprocess.PIPE,
        text=True,
    )
    try:
        line = proc.stdout.readline()
        assert line.startswith("RUNNING"), line
        jid = line.split()[1]
        time.sleep(0.2)  # let a few steps land in the running state
    finally:
        proc.kill()  # SIGKILL: no atexit, no flush, no goodbye
        proc.wait()
    jm = JobManager(workers=0, queue_limit=8, jobs_dir=str(tmp_path))
    state, result, error = jm.get(jid).result_view()
    assert state == "interrupted" and result is None
    assert "restart" in error
    jm.shutdown()
    jm2 = JobManager(
        workers=1, queue_limit=8, jobs_dir=str(tmp_path), resume=True
    )
    final = _wait(jm2.get(jid), {"succeeded", "failed", "interrupted"}, 120.0)
    assert final["state"] == "succeeded", final
    assert jm2.get(jid).result_view()[1]["result"]["podsScheduled"] == 200
    jm2.shutdown()


# ---------------------------------------------------------------------------
# Segment checkpoints + incremental resume (round 16, docs/jobs.md)
# ---------------------------------------------------------------------------


def churn_device_doc(
    seed: int = 3, n_nodes: int = 32, n_steps: int = 40, **sim_extra
) -> dict:
    """A device-replay churn job long enough to cross several segment
    commits (K=16 steps each): step 0 bootstraps the fleet, then
    ``n_steps`` churn steps of 20 events."""
    from ksim_tpu.scenario import churn_scenario, spec_from_operations

    ops = list(
        churn_scenario(
            seed,
            n_nodes=n_nodes,
            n_events=n_nodes + 20 * n_steps,
            ops_per_step=20,
        )
    )
    sim = {"deviceReplay": True, "podBucketMin": 64, **sim_extra}
    return {"spec": {"simulator": sim, "scenario": spec_from_operations(ops)}}


def _locked_counts(result_doc: dict) -> dict:
    """The byte-identical slice of a job result: everything except the
    wall-clock fields (a resumed run's ``wallSeconds`` covers only its
    own suffix replay — documented, and exactly the point)."""
    return {
        k: v for k, v in result_doc["result"].items() if k != "wallSeconds"
    }


def _run_checkpointed(tmp_path, doc, **mgr_kw) -> tuple[str, dict]:
    """Run one job to completion with checkpoints on; return
    (job_id, final result doc)."""
    jm = JobManager(
        workers=1, queue_limit=8, jobs_dir=str(tmp_path),
        checkpoint_every=mgr_kw.pop("checkpoint_every", 1), **mgr_kw,
    )
    job = jm.submit(doc)
    final = _wait(job, {"succeeded", "failed"}, 300.0)
    assert final["state"] == "succeeded", final
    _, result, _ = job.result_view()
    jm.shutdown()
    return job.id, result


def _rewrite_journal(tmp_path, recs) -> str:
    """Replace the dir's journal with exactly ``recs`` (each re-appended
    through the writer, so CRCs are valid)."""
    path = os.path.join(str(tmp_path), JOURNAL_NAME)
    os.unlink(path)
    j = JobJournal(path)
    for r in recs:
        j.append(r)
    return path


def test_checkpoints_append_at_cadence_and_throttle(tmp_path):
    """checkpoint_every=1 appends one record per committed segment with
    monotonically increasing cursors; a coarser cadence appends strictly
    fewer.  The newest checkpoint's segment shows in job status."""
    jid, _ = _run_checkpointed(tmp_path, churn_device_doc())
    recs = JobJournal(os.path.join(str(tmp_path), JOURNAL_NAME)).replay()
    cks = [r for r in recs if r["t"] == "checkpoint"]
    assert len(cks) >= 2
    cursors = [c["cursor"] for c in cks]
    assert cursors == sorted(set(cursors))
    assert all(c["id"] == jid for c in cks)
    for c in cks:
        assert c["store"]["objects"]["nodes"]  # exact state rode along
        assert "pass_count" in c["service"]
    jm = JobManager(workers=0, queue_limit=8, jobs_dir=str(tmp_path))
    assert jm.get(jid).status()["checkpoint_segment"] is None  # terminal: not carried
    jm.shutdown()

    coarse = tmp_path / "coarse"
    coarse.mkdir()
    _run_checkpointed(coarse, churn_device_doc(), checkpoint_every=2)
    coarse_cks = [
        r
        for r in JobJournal(os.path.join(str(coarse), JOURNAL_NAME)).replay()
        if r["t"] == "checkpoint"
    ]
    assert 0 < len(coarse_cks) < len(cks)


def test_resume_from_every_checkpoint_boundary_byte_identical(tmp_path):
    """The crash matrix: truncate the journal right after EACH
    checkpoint record in turn (the crash window between the checkpoint
    append and the next journaled transition), resume, and require the
    final counts byte-identical to the uninterrupted run — with the
    suffix replay doing strictly less work the later the crash."""
    jid, full = _run_checkpointed(tmp_path, churn_device_doc())
    recs = JobJournal(os.path.join(str(tmp_path), JOURNAL_NAME)).replay()
    ck_idx = [i for i, r in enumerate(recs) if r["t"] == "checkpoint"]
    assert len(ck_idx) >= 2
    total_events = full["result"]["eventsApplied"]
    replayed = []
    for idx in ck_idx:
        _rewrite_journal(tmp_path, recs[: idx + 1])
        jm = JobManager(
            workers=1, queue_limit=8, jobs_dir=str(tmp_path),
            resume=True, checkpoint_every=0,
        )
        job = jm.get(jid)
        final = _wait(job, {"succeeded", "failed", "interrupted"}, 300.0)
        assert final["state"] == "succeeded", final
        _, res, _ = job.result_view()
        assert _locked_counts(res) == _locked_counts(full)
        assert res["resume"]["cursor"] == recs[idx]["cursor"]
        assert final["resumed_from"] == recs[idx]["segment"]
        replayed.append(res["resume"]["eventsReplayed"])
        jm.shutdown()
    # Later checkpoints leave strictly less to replay, and even the
    # earliest resume did less work than a from-scratch replay.
    assert replayed == sorted(replayed, reverse=True)
    assert replayed[0] < total_events


@pytest.mark.slow
def test_dead_device_job_lands_no_checkpoint_and_resume_serves_its_result(
    tmp_path, monkeypatch
):
    """With every dispatch failing, the job runs on the per-pass host
    path, which commits no segment: no checkpoint record ever lands,
    the job still succeeds, and a resuming restart over the same dir
    serves the journaled result instead of replaying anything."""
    monkeypatch.setenv("KSIM_REPLAY_BREAKER_N", "2")
    FAULTS.arm("replay.dispatch", "always@device")
    jid, result = _run_checkpointed(tmp_path, churn_device_doc())
    assert FAULTS.fired("replay.dispatch") >= 2
    assert result["replay"]["device_steps"] == 0
    recs = JobJournal(os.path.join(str(tmp_path), JOURNAL_NAME)).replay()
    assert not [r for r in recs if r["t"] == "checkpoint"]
    jm = JobManager(
        workers=1, queue_limit=8, jobs_dir=str(tmp_path),
        resume=True, checkpoint_every=0,
    )
    try:
        job = jm.get(jid)
        final = _wait(job, {"succeeded", "failed", "interrupted"}, 60.0)
        assert final["state"] == "succeeded", final
        assert final["resumed_from"] is None
        assert _locked_counts(job.result_view()[1]) == _locked_counts(result)
    finally:
        jm.shutdown()


def test_resume_with_torn_tail_after_checkpoint(tmp_path):
    """kill -9 mid-append AFTER the last checkpoint: the torn bytes are
    dropped by the journal's tail rule and the checkpoint restores."""
    jid, full = _run_checkpointed(tmp_path, churn_device_doc())
    recs = JobJournal(os.path.join(str(tmp_path), JOURNAL_NAME)).replay()
    last_ck = max(i for i, r in enumerate(recs) if r["t"] == "checkpoint")
    path = _rewrite_journal(tmp_path, recs[: last_ck + 1])
    with open(path, "ab") as f:
        f.write(b'{"crc": 7, "rec": {"t": "checkpo')  # the kill artifact
    jm = JobManager(
        workers=1, queue_limit=8, jobs_dir=str(tmp_path),
        resume=True, checkpoint_every=0,
    )
    final = _wait(jm.get(jid), {"succeeded", "failed", "interrupted"}, 300.0)
    assert final["state"] == "succeeded", final
    assert _locked_counts(jm.get(jid).result_view()[1]) == _locked_counts(full)
    jm.shutdown()


def test_corrupt_checkpoint_falls_back_to_previous(tmp_path):
    """A checkpoint whose CRC validates but whose payload no longer
    restores (bit rot past the line hash, a format drift) must fall
    back to the PREVIOUS checkpoint, not fail the job or restart it
    from scratch."""
    jid, full = _run_checkpointed(tmp_path, churn_device_doc())
    recs = JobJournal(os.path.join(str(tmp_path), JOURNAL_NAME)).replay()
    ck_idx = [i for i, r in enumerate(recs) if r["t"] == "checkpoint"]
    assert len(ck_idx) >= 2
    keep = recs[: ck_idx[-1] + 1]
    keep[-1] = dict(keep[-1], store={"not": "a store"})  # re-CRC'd on append
    _rewrite_journal(tmp_path, keep)
    jm = JobManager(
        workers=1, queue_limit=8, jobs_dir=str(tmp_path),
        resume=True, checkpoint_every=0,
    )
    job = jm.get(jid)
    final = _wait(job, {"succeeded", "failed", "interrupted"}, 300.0)
    assert final["state"] == "succeeded", final
    assert final["resumed_from"] == recs[ck_idx[-2]]["segment"]
    assert _locked_counts(job.result_view()[1]) == _locked_counts(full)
    jm.shutdown()


def test_restore_fault_falls_back_to_scratch(tmp_path):
    """Every checkpoint unusable (armed jobs.checkpoint_restore): the
    resumed job replays from scratch and still lands the identical
    result — restore is an optimization, never a correctness gate."""
    jid, full = _run_checkpointed(tmp_path, churn_device_doc())
    recs = JobJournal(os.path.join(str(tmp_path), JOURNAL_NAME)).replay()
    last_ck = max(i for i, r in enumerate(recs) if r["t"] == "checkpoint")
    _rewrite_journal(tmp_path, recs[: last_ck + 1])
    FAULTS.arm("jobs.checkpoint_restore", "always")
    jm = JobManager(
        workers=1, queue_limit=8, jobs_dir=str(tmp_path),
        resume=True, checkpoint_every=0,
    )
    job = jm.get(jid)
    final = _wait(job, {"succeeded", "failed", "interrupted"}, 300.0)
    assert final["state"] == "succeeded", final
    _, res, _ = job.result_view()
    assert _locked_counts(res) == _locked_counts(full)
    assert "resume" not in res and final["resumed_from"] is None
    jm.shutdown()


def test_resume_across_spec_change_refuses_checkpoints(tmp_path):
    """The code half of "Resume across a config change" (docs/jobs.md;
    the doc half shipped in round 17): every checkpoint record carries
    the simulator-spec hash, and a resumed job whose spec CHANGED
    refuses the mismatched records — replaying from scratch under the
    new config instead of silently installing carries the old config
    produced.  The refusal must be total (resumed_from None) even
    though structurally valid checkpoints sit right there in the
    journal."""
    jid, full = _run_checkpointed(tmp_path, churn_device_doc())
    recs = JobJournal(os.path.join(str(tmp_path), JOURNAL_NAME)).replay()
    cks = [r for r in recs if r["t"] == "checkpoint"]
    assert cks and all(r.get("spec") for r in cks)
    assert len({r["spec"] for r in cks}) == 1  # one spec, one hash
    last_ck = max(i for i, r in enumerate(recs) if r["t"] == "checkpoint")
    keep = recs[: last_ck + 1]
    for i, r in enumerate(keep):
        if r["t"] == "submit":
            doc = json.loads(json.dumps(r["doc"]))
            # The config change: a knob that reshapes the pod batching
            # but not the locked counts.
            doc["spec"]["simulator"]["podBucketMin"] = 128
            keep[i] = dict(r, doc=doc)
    _rewrite_journal(tmp_path, keep)
    jm = JobManager(
        workers=1, queue_limit=8, jobs_dir=str(tmp_path),
        resume=True, checkpoint_every=0,
    )
    job = jm.get(jid)
    final = _wait(job, {"succeeded", "failed", "interrupted"}, 300.0)
    assert final["state"] == "succeeded", final
    _, res, _ = job.result_view()
    assert "resume" not in res and final["resumed_from"] is None
    assert _locked_counts(res) == _locked_counts(full)
    jm.shutdown()


def test_checkpoint_append_fault_never_fails_the_job(tmp_path):
    """The best-effort contract: an armed jobs.checkpoint_append (or
    any snapshot failure) skips checkpoints with a counted event; the
    run itself completes untouched."""
    FAULTS.arm("jobs.checkpoint_append", "always", exc=OSError)
    jid, result = _run_checkpointed(tmp_path, churn_device_doc())
    assert result["result"]["podsScheduled"] > 0
    recs = JobJournal(os.path.join(str(tmp_path), JOURNAL_NAME)).replay()
    assert not [r for r in recs if r["t"] == "checkpoint"]


def test_checkpoint_max_bytes_skips_oversized_snapshots(tmp_path):
    """A snapshot over KSIM_JOBS_CHECKPOINT_MAX_BYTES is skipped (the
    journal must not bloat unboundedly); the job still succeeds."""
    jid, result = _run_checkpointed(
        tmp_path, churn_device_doc(), checkpoint_max_bytes=64
    )
    assert result["result"]["podsScheduled"] > 0
    recs = JobJournal(os.path.join(str(tmp_path), JOURNAL_NAME)).replay()
    assert not [r for r in recs if r["t"] == "checkpoint"]


def test_compaction_keeps_newest_checkpoint_for_live_jobs(tmp_path, monkeypatch):
    """The compaction snapshot re-emits exactly ONE checkpoint — the
    newest — for each non-terminal job (a terminal job's checkpoints
    are dead weight and dropped)."""
    jid, _ = _run_checkpointed(tmp_path, churn_device_doc())
    recs = JobJournal(os.path.join(str(tmp_path), JOURNAL_NAME)).replay()
    ck = [r for r in recs if r["t"] == "checkpoint"]
    assert len(ck) >= 2
    # Crash right after the last checkpoint; the resumed-but-unserved
    # job is LIVE (workers=0: it stays queued).
    last_ck = max(i for i, r in enumerate(recs) if r["t"] == "checkpoint")
    _rewrite_journal(tmp_path, recs[: last_ck + 1])
    monkeypatch.setenv("KSIM_JOBS_JOURNAL_MAX_BYTES", "1")  # force compaction
    jm = JobManager(
        workers=0, queue_limit=8, jobs_dir=str(tmp_path), resume=True
    )
    live = [r for r in jm._journal_records() if r["t"] == "checkpoint"]
    assert len(live) == 1 and live[0]["seq"] == ck[-1]["seq"]
    assert jm._journal.maybe_compact(jm._journal_records) is True
    jm.shutdown()
    # The compacted journal still resumes from that checkpoint.
    jm2 = JobManager(
        workers=1, queue_limit=8, jobs_dir=str(tmp_path),
        resume=True, checkpoint_every=0,
    )
    final = _wait(jm2.get(jid), {"succeeded", "failed", "interrupted"}, 300.0)
    assert final["state"] == "succeeded", final
    assert final["resumed_from"] == ck[-1]["segment"]
    jm2.shutdown()


def test_resumed_job_sse_backlog_is_gap_free(tmp_path):
    """Satellite regression: a tenant reconnecting to a resumed job's
    SSE stream must see the PRE-restart lifecycle (queued→running)
    replayed ahead of the re-enqueue, not a log that starts mid-life."""
    jm = JobManager(workers=0, queue_limit=8, jobs_dir=str(tmp_path))
    job = jm.submit(tiny_doc())
    jm.shutdown()
    # The crashed worker's journal footprint: it had started running.
    JobJournal(os.path.join(str(tmp_path), JOURNAL_NAME)).append(
        {"t": "state", "id": job.id, "state": "running", "ts": 1.0}
    )
    jm2 = JobManager(
        workers=0, queue_limit=8, jobs_dir=str(tmp_path), resume=True
    )
    j2 = jm2.get(job.id)
    with j2._cond:
        events = [dict(e) for e in j2._events]
    states = [
        (e["state"], e.get("recovered", False), e.get("resumed", False))
        for e in events
        if e.get("event") == "state"
    ]
    assert states == [
        ("running", True, False),  # the journaled pre-crash history
        ("queued", False, True),  # then the re-enqueue
    ]
    jm2.shutdown()


_CKPT_CRASH_CHILD = r"""
import sys, time
import jax
jax.config.update("jax_platforms", "cpu")
from ksim_tpu.jobs import JobManager
from ksim_tpu.scenario import churn_scenario, spec_from_operations

# The locked 6k churn prefix (repo CLAUDE.md), as a device-replay job.
ops = list(churn_scenario(0, n_nodes=2000, n_events=6000, ops_per_step=100))
doc = {"spec": {
    "simulator": {
        "deviceReplay": True, "maxPodsPerPass": 1024, "podBucketMin": 128,
    },
    "scenario": spec_from_operations(ops),
}}
jm = JobManager(workers=1, queue_limit=8, jobs_dir=sys.argv[1],
                checkpoint_every=1)
job = jm.submit(doc)
while True:
    st = job.status()
    if st["checkpoint_segment"] is not None:
        break
    if st["state"] in ("succeeded", "failed"):
        print("FINISHED-EARLY", st["state"], flush=True)
        sys.exit(2)
    time.sleep(0.05)
print("CHECKPOINTED", job.id, flush=True)
time.sleep(600)  # parent kills -9 long before this returns
"""


@pytest.mark.slow
def test_sigkill_mid_run_resumes_suffix_with_locked_counts(tmp_path):
    """The round-16 acceptance scenario: kill -9 a worker after its
    first durable checkpoint; a KSIM_JOBS_RESUME=1 restart restores the
    checkpoint and replays ONLY the remaining suffix — strictly fewer
    events than the full stream — landing the locked 6k churn counts
    (2524/471, seed 0, 2000 nodes) byte-identically."""
    proc = subprocess.Popen(
        [sys.executable, "-c", _CKPT_CRASH_CHILD, str(tmp_path)],
        env=sanitized_cpu_env(),
        cwd="/root/repo",
        stdout=subprocess.PIPE,
        text=True,
    )
    try:
        line = proc.stdout.readline()
        assert line.startswith("CHECKPOINTED"), line
        jid = line.split()[1]
    finally:
        proc.kill()  # SIGKILL: no atexit, no flush, no goodbye
        proc.wait()
    jm = JobManager(
        workers=1, queue_limit=8, jobs_dir=str(tmp_path),
        resume=True, checkpoint_every=0,
    )
    job = jm.get(jid)
    assert job is not None
    final = _wait(job, {"succeeded", "failed", "interrupted"}, 300.0)
    assert final["state"] == "succeeded", final
    _, res, _ = job.result_view()
    assert res["result"]["eventsApplied"] == 6430
    assert (
        res["result"]["podsScheduled"],
        res["result"]["unschedulableAttempts"],
    ) == (2524, 471)
    assert final["resumed_from"] is not None
    assert 0 < res["resume"]["eventsReplayed"] < 6430
    jm.shutdown()


# ---------------------------------------------------------------------------
# SSE hardening: aborted readers must not leak listeners
# ---------------------------------------------------------------------------


def test_sse_aborted_reader_releases_listener(monkeypatch):
    """An EventSource that vanishes mid-stream (socket torn down, no
    graceful close) must be detected by the heartbeat write and its
    listener count released — the pre-round-15 handler leaked the
    thread until the job finished."""
    monkeypatch.setenv("KSIM_JOBS_WORKERS", "0")  # job stays queued: stream idles
    monkeypatch.setenv("KSIM_JOBS_SSE_HEARTBEAT_S", "0.2")
    di = DIContainer()
    srv = SimulatorServer(di, port=0).start()
    try:
        conn = http.client.HTTPConnection("127.0.0.1", srv.port, timeout=30)
        conn.request(
            "POST", "/api/v1/jobs", json.dumps(tiny_doc()),
            {"Content-Type": "application/json"},
        )
        resp = conn.getresponse()
        jid = json.loads(resp.read())["id"]
        assert resp.status == 202
        conn.close()

        raw = socket.create_connection(("127.0.0.1", srv.port), timeout=30)
        raw.sendall(
            f"GET /api/v1/jobs/{jid}/events HTTP/1.1\r\n"
            f"Host: 127.0.0.1\r\n\r\n".encode()
        )
        first = raw.recv(4096)  # headers + the replayed queued event
        assert b"text/event-stream" in first

        job = di.job_manager_if_built.get(jid)
        deadline = time.monotonic() + 10
        while job.status()["sse_listeners"] != 1:
            assert time.monotonic() < deadline, job.status()
            time.sleep(0.02)

        # Keepalives flow while the stream idles (nothing new to send).
        buf = b""
        deadline = time.monotonic() + 10
        while b": keepalive" not in buf:
            assert time.monotonic() < deadline, buf
            buf += raw.recv(4096)

        # The abort: RST the socket, no FIN handshake, reader gone.
        raw.setsockopt(
            socket.SOL_SOCKET, socket.SO_LINGER,
            __import__("struct").pack("ii", 1, 0),
        )
        raw.close()
        deadline = time.monotonic() + 10
        while job.status()["sse_listeners"] != 0:
            assert time.monotonic() < deadline, job.status()
            time.sleep(0.05)
    finally:
        srv.shutdown_server()
        di.shutdown()

# ---------------------------------------------------------------------------
# Multi-worker fleet (round 20): the lease plane, the shared journal,
# and kill-a-worker fail-over (docs/jobs.md "Multi-worker fleet")
# ---------------------------------------------------------------------------


class _FakeClock:
    """Injectable clock for the lease protocol tests — expiry windows
    advance exactly when the test says so."""

    def __init__(self, t: float = 1000.0) -> None:
        self.t = t

    def __call__(self) -> float:
        return self.t


def _planes(tmp_path, clock, *workers, lease_s=10.0):
    return [
        LeasePlane(str(tmp_path), worker=w, lease_s=lease_s, clock=clock)
        for w in workers
    ]


def test_lease_claim_race_exactly_one_winner(tmp_path):
    """Two members claiming the same job simultaneously serialize on
    the exclusive flock and exactly one wins (flock is per-open-
    description, so two planes in one process exclude each other)."""
    a, b = _planes(tmp_path, _FakeClock(), "wA", "wB")
    barrier = threading.Barrier(2)
    results: dict[str, "dict | None"] = {}

    def race(name, plane):
        barrier.wait()
        results[name] = plane.claim("job-0")

    threads = [
        threading.Thread(target=race, args=p) for p in (("wA", a), ("wB", b))
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(30)
    winners = [n for n, r in results.items() if r is not None]
    assert len(winners) == 1, results
    lease = a.leases()["job-0"]
    assert lease["worker"] == winners[0] and lease["epoch"] == 1
    counters = a.counters()
    assert counters[winners[0]]["claims"] == 1
    assert sum(c["claims"] for c in counters.values()) == 1


def test_lease_double_claim_refused_and_own_reclaim_idempotent(tmp_path):
    clock = _FakeClock()
    a, b = _planes(tmp_path, clock, "wA", "wB")
    first = a.claim("job-0")
    assert first is not None and first["epoch"] == 1
    assert b.claim("job-0") is None  # live lease, someone else's
    again = a.claim("job-0")  # the owner re-claiming is a no-op
    assert again is not None and again["epoch"] == 1
    assert a.counters()["wA"]["claims"] == 1  # ... and appended nothing


def test_lease_renew_extends_expiry_and_skips_not_ours(tmp_path):
    clock = _FakeClock()
    (a,) = _planes(tmp_path, clock, "wA")
    a.claim("job-0")
    before = a.leases()["job-0"]["expires"]
    clock.t += 5.0
    assert a.renew(["job-0", "job-ghost"]) == 1  # the ghost is skipped
    assert a.leases()["job-0"]["expires"] == before + 5.0
    assert a.counters()["wA"]["renews"] == 1


def test_expired_lease_takeover_bumps_epoch_and_counters(tmp_path):
    """The fail-over path: a lease whose owner stopped renewing ages
    out, the next claimer wins with a bumped epoch, the takeover is
    charged to the claimer and the expiry to the worker that lost."""
    clock = _FakeClock()
    a, b = _planes(tmp_path, clock, "wA", "wB")
    a.claim("job-0")
    clock.t += 5.0
    assert b.claim("job-0") is None  # still live: refused
    clock.t += 6.0  # past the 10s lease: the fail-over window
    won = b.claim("job-0")
    assert won is not None and won["epoch"] == 2
    counters = b.counters()
    assert counters["wB"]["claims"] == 1 and counters["wB"]["takeovers"] == 1
    assert counters["wA"]["expired"] == 1
    # The deposed owner cannot renew its way back in.
    assert a.renew(["job-0"]) == 0


def test_released_lease_is_never_reclaimable(tmp_path):
    """released == finished (releases happen only after the terminal
    record is durable), so no amount of clock is ever enough."""
    clock = _FakeClock()
    a, b = _planes(tmp_path, clock, "wA", "wB")
    a.claim("job-0")
    a.release("job-0")
    clock.t += 10_000.0
    assert b.claim("job-0") is None
    assert a.claim("job-0") is None  # not even the old owner


def test_lease_compaction_preserves_leases_and_counters(tmp_path):
    """Compaction rewrites newest-record-per-id + a trailing counters
    snapshot; the fold over the compacted file must be identical —
    including the released tombstones the claim protocol depends on."""
    clock = _FakeClock()
    a, b = _planes(tmp_path, clock, "wA", "wB")
    a.claim("job-0")
    b.claim("job-1")
    for _ in range(50):
        clock.t += 1.0
        a.renew(["job-0"])
        b.renew(["job-1"])
    b.release("job-1")
    before_leases, before_counters = a.leases(), a.counters()
    size = os.path.getsize(a.path)
    assert a.maybe_compact(max_bytes=1) is True
    assert os.path.getsize(a.path) < size
    assert a.leases() == before_leases
    assert a.counters() == before_counters
    # A brand-new member folds the compacted file to the same view,
    # and the released job stays unclaimable.
    (c,) = _planes(tmp_path, clock, "wC")
    assert c.leases() == before_leases
    assert c.claim("job-1") is None


# -- the shared journal: satellite regression (multi-appender safety) -------


def test_shared_journal_interleaved_appenders_record_atomic(tmp_path):
    """Two handles interleaving appends — including a
    multi-hundred-KB checkpoint-sized record — leave a file where every
    line decodes independently: the single-``os.write``-per-record rule
    means appenders interleave only at record granularity.  Checked on
    the raw BYTES, not through replay."""
    path = os.path.join(str(tmp_path), JOURNAL_NAME)
    j1 = JobJournal(path, shared=True)
    j2 = JobJournal(path, shared=True)
    big = {
        "t": "checkpoint", "id": "job-0", "segment": 1, "cursor": 16,
        "store": {"blob": "x" * 300_000},
    }
    j1.append({"t": "submit", "id": "job-0", "ordinal": 0, "doc": {}})
    j2.append({"t": "state", "id": "job-0", "state": "running"})
    j1.append(big)
    j2.append({"t": "state", "id": "job-0", "state": "succeeded"})
    j1.append({"t": "result", "id": "job-0", "result": {"ok": 1}})
    with open(path, "r", encoding="utf-8", newline="") as f:
        lines = f.readlines()
    assert len(lines) == 5
    recs = [_decode_line(ln) for ln in lines]
    assert all(r is not None for r in recs)
    assert [r["t"] for r in recs] == [
        "submit", "state", "checkpoint", "state", "result",
    ]
    assert recs[2]["store"]["blob"] == big["store"]["blob"]
    # A third handle replays the merged stream intact.
    assert len(JobJournal(path, shared=True).replay()) == 5


def test_shared_journal_concurrent_append_stress(tmp_path):
    """The actual race: two handles appending concurrently from two
    threads (flock is per-open-description, so this exercises the real
    cross-process exclusion).  Nothing torn, nothing lost."""
    path = os.path.join(str(tmp_path), JOURNAL_NAME)
    j1 = JobJournal(path, shared=True)
    j2 = JobJournal(path, shared=True)

    def pump(j, tag):
        for i in range(100):
            j.append({
                "t": "state", "id": f"{tag}-{i}", "state": "running",
                "pad": "y" * (4096 if i % 7 == 0 else 8),
            })

    threads = [
        threading.Thread(target=pump, args=p)
        for p in ((j1, "one"), (j2, "two"))
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(60)
    recs = JobJournal(path, shared=True).replay()
    assert len(recs) == 200
    assert {r["id"] for r in recs} == {
        f"{tag}-{i}" for tag in ("one", "two") for i in range(100)
    }


def test_shared_compaction_folds_other_appenders_records(tmp_path):
    """The satellite regression: pre-round-20 compaction rewrote the
    journal from the LOCAL registry snapshot, silently dropping records
    a second process appended.  Shared compaction folds the file's own
    records — keeping the other appender's newest state/checkpoint, the
    record types it does not understand, and never stranding the other
    appender on the replaced inode."""
    path = os.path.join(str(tmp_path), JOURNAL_NAME)
    j1 = JobJournal(path, shared=True, max_bytes=256)
    j2 = JobJournal(path, shared=True)
    j1.append({"t": "submit", "id": "job-0", "ordinal": 0, "doc": {"spec": {}}})
    for i in range(20):
        j2.append({"t": "state", "id": "job-0", "state": "running", "ts": i})
    j2.append({"t": "checkpoint", "id": "job-0", "segment": 3, "cursor": 48})
    j2.append({"t": "checkpoint", "id": "job-0", "segment": 7, "cursor": 112})
    j2.append({"t": "fleet-extension", "custom": True})  # unknown type
    assert j1.maybe_compact(lambda: []) is True  # snapshot_fn IGNORED
    # The second appender keeps appending: per-record re-open lands the
    # write on the NEW inode, not the compacted-away one.
    j2.append({"t": "state", "id": "job-0", "state": "succeeded", "ts": 99})
    recs = JobJournal(path, shared=True).replay()
    assert [r["t"] for r in recs] == [
        "submit", "state", "checkpoint", "fleet-extension", "state",
    ]
    assert recs[1]["ts"] == 19  # newest pre-compaction state won
    assert recs[2]["segment"] == 7  # newest checkpoint won, older shed
    assert recs[4]["ts"] == 99


# -- the fleet loop in-process: frontdoor mirror + worker adoption ----------


def test_fleet_frontdoor_worker_lifecycle_in_process(tmp_path):
    """One frontdoor + one worker manager over a shared dir: the
    frontdoor journals the submit, the worker claims/runs/releases, and
    the frontdoor mirror folds state, result, events, owner and lease
    back for status/result/SSE."""
    fd = JobManager(
        workers=0, queue_limit=8, jobs_dir=str(tmp_path),
        role="frontdoor", worker_id="fd", lease_s=3.0, poll_s=0.1,
    )
    wk = JobManager(
        workers=1, queue_limit=8, jobs_dir=str(tmp_path),
        role="worker", worker_id="w1", lease_s=3.0, poll_s=0.1,
    )
    try:
        job = fd.submit(tiny_doc())
        final = _wait(job, {"succeeded", "failed"}, 120.0)
        assert final["state"] == "succeeded", final
        assert final["owner"] == "w1"
        assert final["lease"]["epoch"] == 1
        state, res, _ = job.result_view()
        assert state == "succeeded"
        assert res["result"]["podsScheduled"] == 3  # ran on the worker
        # The mirrored SSE ring: state + progress events crossed the
        # manager boundary via the per-job event file.
        deadline = time.monotonic() + 15
        while True:
            evs, _, done = job.events_since(0, 0)
            kinds = [e["event"] for e in evs]
            if done and "state" in kinds and "progress" in kinds:
                break
            assert time.monotonic() < deadline, kinds
            time.sleep(0.05)
        flt = fd.snapshot()["fleet"]
        assert flt["role"] == "frontdoor" and flt["worker_id"] == "fd"
        assert flt["workers"]["w1"]["claims"] == 1
        wflt = wk.snapshot()["fleet"]
        assert wflt["role"] == "worker"
        # Released after the terminal record — on the worker's NEXT poll
        # tick, which the mirrored SSE completion above does not order
        # against, so wait for it rather than racing it.
        deadline = time.monotonic() + 15
        while wk.snapshot()["fleet"]["owned"]:
            assert time.monotonic() < deadline, wk.snapshot()["fleet"]
            time.sleep(0.05)
    finally:
        wk.shutdown()
        fd.shutdown()


def test_fleet_cancel_routes_to_owning_worker(tmp_path):
    """A cancel submitted at the front door reaches the owning worker
    through the journal's cancel record and stops the run mid-flight."""
    ops = [
        {"step": 0, "createOperation": {"object": make_node(f"n{i}", cpu="32")}}
        for i in range(2)
    ]
    ops += [
        {"step": i + 1, "createOperation": {"object": make_pod(f"p{i}", cpu="100m")}}
        for i in range(400)
    ]
    doc = {"spec": {"scenario": {"operations": ops}}}
    fd = JobManager(
        workers=0, queue_limit=8, jobs_dir=str(tmp_path),
        role="frontdoor", worker_id="fd", lease_s=3.0, poll_s=0.05,
    )
    wk = JobManager(
        workers=1, queue_limit=8, jobs_dir=str(tmp_path),
        role="worker", worker_id="w1", lease_s=3.0, poll_s=0.05,
    )
    try:
        job = fd.submit(doc)
        running = _wait(job, {"running", "succeeded", "failed"}, 60.0)
        assert running["state"] == "running", running
        fd.cancel(job.id)
        final = _wait(job, {"cancelled", "succeeded", "failed"}, 60.0)
        assert final["state"] == "cancelled", final
        assert final["owner"] == "w1"
    finally:
        wk.shutdown()
        fd.shutdown()


# -- kill-a-worker chaos: the acceptance scenario ---------------------------


_SIX_K_DOC_SRC = """
from ksim_tpu.scenario import churn_scenario, spec_from_operations

ops = list(churn_scenario(0, n_nodes=2000, n_events=6000, ops_per_step=100))
doc = {"spec": {
    "simulator": {
        "deviceReplay": True, "maxPodsPerPass": 1024, "podBucketMin": 128,
    },
    "scenario": spec_from_operations(ops),
}}
"""


def _six_k_doc() -> dict:
    ns: dict = {}
    exec(_SIX_K_DOC_SRC, ns)
    return ns["doc"]


def _spawn_fleet_worker(tmp_path, worker_id: str) -> subprocess.Popen:
    proc = subprocess.Popen(
        [
            sys.executable, "-m", "ksim_tpu.jobs",
            "--dir", str(tmp_path), "--worker-id", worker_id,
            "--workers", "1",
        ],
        env=sanitized_cpu_env({
            "KSIM_WORKERS_LEASE_S": "4",
            "KSIM_WORKERS_HEARTBEAT_S": "1",
            "KSIM_WORKERS_POLL_S": "0.2",
            "KSIM_JOBS_CHECKPOINT_EVERY": "1",
        }),
        cwd="/root/repo",
        stdout=subprocess.PIPE,
        text=True,
    )
    line = proc.stdout.readline()
    assert line.strip() == f"READY {worker_id}", line
    return proc


@pytest.mark.slow
def test_fleet_sigkill_owner_fails_over_with_locked_counts(tmp_path):
    """The round-20 acceptance scenario (`make restart-check`): a fleet
    of two worker PROCESSES behind an in-process front door; SIGKILL
    the worker that owns the locked 6k churn job after its first
    durable checkpoint.  The survivor's claim succeeds once the lease
    expires (takeover, epoch 2), it adopts the job from the journal
    fold and resumes from the newest checkpoint — landing 2524/471
    byte-identically with strictly fewer events replayed, exactly one
    result record, and the takeover/expiry charged to the right
    workers."""
    procs = {
        "wA": _spawn_fleet_worker(tmp_path, "wA"),
        "wB": _spawn_fleet_worker(tmp_path, "wB"),
    }
    fd = JobManager(
        workers=0, queue_limit=8, jobs_dir=str(tmp_path),
        role="frontdoor", worker_id="fd", lease_s=4.0, poll_s=0.2,
    )
    try:
        job = fd.submit(_six_k_doc())
        # Wait for an owner AND its first durable checkpoint (both
        # mirrored into frontdoor status) — the kill window where
        # fail-over must resume, not restart.
        deadline = time.monotonic() + 300
        while True:
            st = job.status()
            assert st["state"] not in ("succeeded", "failed"), st
            if st["owner"] in procs and st["checkpoint_segment"] is not None:
                break
            assert time.monotonic() < deadline, st
            time.sleep(0.1)
        owner, survivor = st["owner"], ("wA" if st["owner"] == "wB" else "wB")
        procs[owner].kill()  # SIGKILL: no atexit, no flush, no goodbye
        procs[owner].wait()

        final = _wait(job, {"succeeded", "failed", "interrupted"}, 600.0)
        assert final["state"] == "succeeded", final
        assert final["owner"] == survivor
        assert final["lease"]["epoch"] >= 2
        _, res, _ = job.result_view()
        assert res["result"]["eventsApplied"] == 6430
        assert (
            res["result"]["podsScheduled"],
            res["result"]["unschedulableAttempts"],
        ) == (2524, 471)
        assert 0 < res["resume"]["eventsReplayed"] < 6430
        # Zero lost, zero duplicated: exactly one result record made it
        # into the shared journal.
        recs = JobJournal(
            os.path.join(str(tmp_path), JOURNAL_NAME), shared=True
        ).replay()
        assert sum(1 for r in recs if r["t"] == "result") == 1
        counters = fd.snapshot()["fleet"]["workers"]
        assert counters[survivor]["takeovers"] == 1
        assert counters[owner]["expired"] == 1
    finally:
        for proc in procs.values():
            proc.kill()
            proc.wait()
        fd.shutdown()
