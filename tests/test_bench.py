"""The driver bench must emit its one JSON line under any condition.

bench.py's parent process is stdlib-only and runs each rung in a
subprocess (see its module docstring for the round-1/round-2 failure
modes this guards against); these tests exercise the orchestrator
end-to-end on CPU and the guaranteed-emission paths.
"""

from __future__ import annotations

import json
import signal
import subprocess
import sys
import time

import pytest
from pathlib import Path

from tests.helpers import sanitized_cpu_env

REPO = Path(__file__).resolve().parent.parent


def _last_json_line(stdout: str) -> dict:
    lines = [ln for ln in stdout.strip().splitlines() if ln.startswith("{")]
    assert lines, f"no JSON line in stdout:\n{stdout[-2000:]}"
    return json.loads(lines[-1])


def test_bench_emits_json_on_cpu(tmp_path):
    proc = subprocess.run(
        [sys.executable, str(REPO / "bench.py"), "--only", "200x20", "--repeats", "1"],
        capture_output=True,
        text=True,
        timeout=420,
        cwd=REPO,
        env=sanitized_cpu_env(),
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = _last_json_line(proc.stdout)
    assert out["metric"] == "sched_pairs_per_sec"
    assert out["value"] > 0
    assert out["platform"] == "cpu"
    assert out["rungs"]["200x20"]["exact"] is True


def test_bench_emits_json_when_budget_exhausted():
    """With a near-zero budget every rung is skipped, but the line still
    prints with a non-null payload (the BENCH_r02 failure mode)."""
    env = sanitized_cpu_env({"BENCH_BUDGET_S": "1"})
    proc = subprocess.run(
        [sys.executable, str(REPO / "bench.py")],
        capture_output=True,
        text=True,
        timeout=120,
        cwd=REPO,
        env=env,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = _last_json_line(proc.stdout)
    assert out["metric"] == "sched_pairs_per_sec"
    # Nothing ran: the payload must SAY why (top-level error, or every
    # attempted stage recorded as an error) — a bare value-0 line with no
    # explanation is the regression this test guards.
    stage_errors = [r for r in out["rungs"].values() if "error" in r]
    assert "error" in out or (out["rungs"] and len(stage_errors) == len(out["rungs"])), out


def test_bench_emits_json_on_sigterm():
    """An external watchdog's SIGTERM (the driver `timeout` kill) still
    yields the JSON line before exit."""
    proc = subprocess.Popen(
        [sys.executable, str(REPO / "bench.py"), "--only", "200x20", "--repeats", "1"],
        stdout=subprocess.PIPE,
        stderr=subprocess.DEVNULL,
        text=True,
        cwd=REPO,
        env=sanitized_cpu_env(),
    )
    # Let the orchestrator install its handlers and start the probe.
    time.sleep(5)
    proc.send_signal(signal.SIGTERM)
    try:
        stdout, _ = proc.communicate(timeout=60)
    except subprocess.TimeoutExpired:
        proc.kill()
        raise
    out = _last_json_line(stdout)
    assert out["metric"] == "sched_pairs_per_sec"
    assert out.get("interrupted") == "SIGTERM", out


def test_bench_churn_child_reports_breaker_under_permanent_dispatch_fault(tmp_path):
    """Round 8: a churn child whose device dispatch permanently fails
    (fault plane armed through the environment — the stdlib-only parent
    never imports anything) still writes its JSON record, with the
    degradation evidence: device_error fallbacks counted, breaker
    tripped, the whole stream carried by the per-pass path."""
    out = tmp_path / "churn.json"
    env = sanitized_cpu_env(
        {
            "KSIM_FAULTS": "replay.dispatch=always",
            "KSIM_REPLAY_BREAKER_N": "2",
        }
    )
    proc = subprocess.run(
        [
            sys.executable, str(REPO / "bench.py"),
            "--child", "churn", "--out", str(out),
            "--seed", "0", "--churn-events", "800", "--churn-nodes", "200",
            "--churn-device",
        ],
        capture_output=True,
        text=True,
        timeout=420,
        cwd=REPO,
        env=env,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    rec = json.loads(out.read_text())
    assert rec["breaker_tripped"] is True
    assert rec["device_errors"] >= 2
    assert rec["unsupported"].get("device_error", 0) >= 2
    assert rec["unsupported"].get("breaker_open", 0) > 0
    assert rec["device_steps"] == 0
    assert rec["fallback_steps"] == rec["steps"]
    assert rec["pods_scheduled"] > 0  # the host path carried the stream


@pytest.mark.slow
def test_bench_churn_fleet_child_records_fleet_evidence(tmp_path):
    """Round 12: the churn_fleet child's JSON record carries the fleet
    evidence the acceptance contract names — trajectories/sec, the
    aggregate-speedup comparison vs solo, per-lane counts matching the
    solo run, the lanes-on-device fraction, and the cohort leader's
    lower_cache/prelower counters (the lowered-once guard)."""
    out = tmp_path / "fleet.json"
    proc = subprocess.run(
        [
            sys.executable, str(REPO / "bench.py"),
            "--child", "churn_fleet", "--out", str(out),
            "--seed", "0", "--churn-events", "300", "--churn-nodes", "64",
            "--fleet-lanes", "3",
        ],
        capture_output=True,
        text=True,
        timeout=420,
        cwd=REPO,
        env=sanitized_cpu_env(),
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    rec = json.loads(out.read_text())
    assert rec["lanes"] == 3
    assert rec["lanes_match_solo"] is True
    assert rec["trajectories_per_sec"] > 0
    assert rec["aggregate_speedup"] > 0
    assert rec["fleet"]["lanes_on_device"] == 1.0
    assert rec["fleet"]["group_dispatches"] >= 1
    # Lowered once per window: exactly one driver carries lowerings.
    lowerings = rec["fleet"]["lane_lowerings"]
    assert sum(lowerings) == max(lowerings) > 0
    assert "lower_cache" in rec and "prelower" in rec and "phases" in rec


@pytest.mark.slow
def test_bench_churn_jobs_child_records_job_evidence(tmp_path):
    """Round 13: the churn_jobs child's record carries the job-plane
    evidence — sustained jobs/min, per-job counts + jobs_match_solo,
    per-job latency quantiles from each job's PRIVATE plane, and the
    process-wide compile_cache counters proving same-rung tenants
    compiled once (shared_rungs >= 1)."""
    out = tmp_path / "jobs.json"
    proc = subprocess.run(
        [
            sys.executable, str(REPO / "bench.py"),
            "--child", "churn_jobs", "--out", str(out),
            "--seed", "0", "--churn-events", "300", "--churn-nodes", "64",
            "--jobs-count", "3", "--jobs-workers", "2",
        ],
        capture_output=True,
        text=True,
        timeout=420,
        cwd=REPO,
        env=sanitized_cpu_env(),
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    rec = json.loads(out.read_text())
    assert rec["jobs"] == 3 and rec["workers"] == 2
    assert rec["all_finished"] is True
    assert rec["jobs_match_solo"] is True
    assert rec["jobs_per_min"] > 0
    assert len(rec["per_job"]) == 3
    for pj in rec["per_job"]:
        assert pj["state"] == "succeeded"
        assert pj["counts"] == rec["solo_counts"]
        assert pj["dispatch_p50_s"] > 0  # the job's own histogram
    cc = rec["compile_cache"]
    assert cc["misses"] >= 1 and cc["hits"] >= 1
    assert cc["shared_rungs"] >= 1, cc
    assert cc["shared_single_compile_rungs"] >= 1, cc
    assert rec["queue"]["submitted"] == 3 and rec["queue"]["rejected"] == 0


def test_bench_churn_jobs_child_survives_dead_device(tmp_path):
    """One-JSON-line-under-any-hardware, job-plane edition: with every
    dispatch failing (the dead-backend stand-in) all jobs degrade to
    the host path, finish, and still match the solo counts."""
    out = tmp_path / "jobs_dead.json"
    env = sanitized_cpu_env(
        {
            "KSIM_FAULTS": "replay.dispatch=always@device",
            "KSIM_REPLAY_BREAKER_N": "2",
        }
    )
    proc = subprocess.run(
        [
            sys.executable, str(REPO / "bench.py"),
            "--child", "churn_jobs", "--out", str(out),
            "--seed", "0", "--churn-events", "300", "--churn-nodes", "64",
            "--jobs-count", "2", "--jobs-workers", "2",
        ],
        capture_output=True,
        text=True,
        timeout=420,
        cwd=REPO,
        env=env,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    rec = json.loads(out.read_text())
    assert rec["all_finished"] is True
    assert rec["jobs_match_solo"] is True
    for pj in rec["per_job"]:
        assert pj["state"] == "succeeded"


def test_bench_churn_fleet_child_survives_dead_device(tmp_path):
    """The one-JSON-line-under-any-hardware contract, fleet edition: a
    churn_fleet child whose every dispatch fails (the dead-backend
    stand-in, armed through the environment) still writes its record —
    every lane carried by the per-pass host path, breakers tripped,
    counts intact."""
    out = tmp_path / "fleet_dead.json"
    env = sanitized_cpu_env(
        {
            "KSIM_FAULTS": "replay.dispatch=always@device",
            "KSIM_REPLAY_BREAKER_N": "2",
        }
    )
    proc = subprocess.run(
        [
            sys.executable, str(REPO / "bench.py"),
            "--child", "churn_fleet", "--out", str(out),
            "--seed", "0", "--churn-events", "300", "--churn-nodes", "64",
            "--fleet-lanes", "3",
        ],
        capture_output=True,
        text=True,
        timeout=420,
        cwd=REPO,
        env=env,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    rec = json.loads(out.read_text())
    assert rec["lanes_match_solo"] is True  # the host path carried all lanes
    assert rec["fleet"]["lanes_on_device"] == 0.0
    assert all(s == 0 for s in rec["fleet"]["lane_device_steps"])


def test_bench_churn_trace_child_records_trace_evidence(tmp_path):
    """Round 14: the churn_trace child's record carries the trace-plane
    acceptance evidence — both paths' counts with counts_match (the
    bundled fixture's locked family), device_step_fraction 1.0 with 0
    fallbacks, and the phases split."""
    out = tmp_path / "trace.json"
    proc = subprocess.run(
        [
            sys.executable, str(REPO / "bench.py"),
            "--child", "churn_trace", "--out", str(out),
        ],
        capture_output=True,
        text=True,
        timeout=420,
        cwd=REPO,
        env=sanitized_cpu_env(),
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    rec = json.loads(out.read_text())
    assert rec["format"] == "borg" and rec["trace"] == "borg_mini.jsonl"
    # The locked trace family (tests/test_behavior_locks.py).
    assert rec["counts"] == [56, 19]
    assert rec["counts_match"] is True
    assert rec["device_step_fraction"] == 1.0
    assert rec["fallback_steps"] == 0 and rec["unsupported"] == {}
    assert "phases" in rec and "replay.dispatch" in rec["phases"]


def test_bench_churn_trace_child_survives_dead_device(tmp_path):
    """One-JSON-line-under-any-hardware, trace edition: with every
    dispatch failing, the whole trace stream degrades to the per-pass
    host path, the counts still match, and the record still exists."""
    out = tmp_path / "trace_dead.json"
    env = sanitized_cpu_env(
        {
            "KSIM_FAULTS": "replay.dispatch=always@device",
            "KSIM_REPLAY_BREAKER_N": "2",
        }
    )
    proc = subprocess.run(
        [
            sys.executable, str(REPO / "bench.py"),
            "--child", "churn_trace", "--out", str(out),
        ],
        capture_output=True,
        text=True,
        timeout=420,
        cwd=REPO,
        env=env,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    rec = json.loads(out.read_text())
    assert rec["counts_match"] is True  # the host path carried the stream
    assert rec["counts"] == [56, 19]
    assert rec["device_step_fraction"] == 0.0
    assert rec["unsupported"].get("device_error", 0) >= 2


_STREAM_CHILD_ARGS = [
    "--stream-records", "400", "--stream-max-events", "120",
    "--stream-nodes", "8", "--stream-ops-per-step", "10",
    "--stream-window", "64", "--stream-queue", "2",
]


def test_bench_churn_stream_child_records_streaming_evidence(tmp_path):
    """Round 20: the churn_stream child's record carries the streaming
    acceptance evidence — the mid-run VmHWM snapshot (taken before the
    materialized comparison), the events/sec headline, the producer's
    window/queue stats with zero fallbacks, and streamed-vs-materialized
    counts_match."""
    out = tmp_path / "stream.json"
    proc = subprocess.run(
        [
            sys.executable, str(REPO / "bench.py"),
            "--child", "churn_stream", "--out", str(out),
            *_STREAM_CHILD_ARGS,
        ],
        capture_output=True,
        text=True,
        timeout=420,
        cwd=REPO,
        env=sanitized_cpu_env(),
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    rec = json.loads(out.read_text())
    assert rec["counts_match"] is True
    assert rec["counts"] == rec["materialized_counts"]
    assert rec["rss_after_stream_kb"] > 0
    assert rec["rss_after_stream_kb"] <= rec["rss_peak_kb"]
    assert rec["events_per_sec"] > 0
    assert rec["window_ops"] == 64 and rec["queue_windows"] == 2
    assert rec["windows"] >= 2  # ~128 ops over 64-op windows
    assert rec["ingest_fallback"] == 0
    assert rec["ingest_prefetches"] >= 1


def test_bench_churn_stream_child_survives_dead_device(tmp_path):
    """One-JSON-line-under-any-hardware, streaming edition: with every
    dispatch failing the streamed replay degrades to the per-step host
    path mid-pipeline, the streamed counts still match the materialized
    run, and the record still exists."""
    out = tmp_path / "stream_dead.json"
    env = sanitized_cpu_env(
        {
            "KSIM_FAULTS": "replay.dispatch=always@device",
            "KSIM_REPLAY_BREAKER_N": "2",
        }
    )
    proc = subprocess.run(
        [
            sys.executable, str(REPO / "bench.py"),
            "--child", "churn_stream", "--out", str(out),
            *_STREAM_CHILD_ARGS,
        ],
        capture_output=True,
        text=True,
        timeout=420,
        cwd=REPO,
        env=env,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    rec = json.loads(out.read_text())
    assert rec["counts_match"] is True  # the host path carried the stream
    assert rec["counts"] == rec["materialized_counts"]
    assert rec["ingest_fallback"] == 0  # producer faults are a separate plane


@pytest.mark.slow
def test_bench_churn_restart_child_records_warm_restart_evidence(tmp_path):
    """Round 15: the churn_restart child's record carries the warm-restart
    acceptance evidence — time-to-first-scheduled-pod plus the on-disk AOT
    compile-cache counters. Two children over the SAME state dir: the cold
    run stores the serialized executable, the warm run loads it from disk
    without compiling, and both produce identical counts."""
    state = tmp_path / "state"
    env = sanitized_cpu_env(
        {
            "KSIM_AOT_CACHE": str(state / "aot"),
            "JAX_COMPILATION_CACHE_DIR": str(state / "xla"),
        }
    )
    recs = []
    for leg in ("cold", "warm"):
        out = tmp_path / f"restart_{leg}.json"
        proc = subprocess.run(
            [
                sys.executable, str(REPO / "bench.py"),
                "--child", "churn_restart", "--out", str(out),
                "--seed", "0", "--churn-events", "600", "--churn-nodes", "200",
            ],
            capture_output=True,
            text=True,
            timeout=420,
            cwd=REPO,
            env=env,
        )
        assert proc.returncode == 0, proc.stderr[-2000:]
        recs.append(json.loads(out.read_text()))
    cold, warm = recs
    for rec in recs:
        assert rec["wall_s"] > 0
        assert rec["first_scheduled_s"] is not None
        assert 0 < rec["first_scheduled_s"] <= rec["wall_s"] + 0.1
        assert rec["device_steps"] > 0 and rec["fallback_steps"] == 0
    # Identical streams -> identical counts, cold or warm.
    assert (warm["pods_scheduled"], warm["unschedulable_attempts"]) == (
        cold["pods_scheduled"], cold["unschedulable_attempts"])
    # The cold leg compiled and persisted; the warm leg restored from disk.
    assert cold["compile_cache"]["disk_stores"] >= 1
    assert cold["compile_cache"]["disk_hits"] == 0
    assert warm["compile_cache"]["disk_hits"] >= 1
    assert warm["compile_cache"]["disk_stores"] == 0


@pytest.mark.slow
def test_bench_churn_resume_children_record_resume_evidence(tmp_path):
    """Round 16: the churn_resume rung's three children over ONE shared
    jobs dir. The victim writes its evidence JSON the moment the first
    segment checkpoint is durable and then SIGKILLs itself (the JSON
    must land despite the -9 exit); the resume child restores that
    checkpoint and replays only the suffix; scratch is the control.
    Counts must match byte-identically across resume and scratch."""
    state = tmp_path / "state"
    state.mkdir()
    # 200 creates + 32 churn steps = two K=16 segments: the first
    # checkpoint lands with a full segment of work still ahead, so the
    # kill is mid-run, not a degenerate post-completion snapshot.
    shape = ["--seed", "0", "--churn-events", "3400", "--churn-nodes", "200"]
    recs = {}
    for phase in ("victim", "resume", "scratch"):
        out = tmp_path / f"resume_{phase}.json"
        proc = subprocess.run(
            [
                sys.executable, str(REPO / "bench.py"),
                "--child", "churn_resume", "--out", str(out),
                "--resume-phase", phase, "--state-dir", str(state),
                *shape,
            ],
            capture_output=True,
            text=True,
            timeout=420,
            cwd=REPO,
            env=sanitized_cpu_env(),
        )
        if phase == "victim":
            # The victim dies by its own SIGKILL — after the JSON.
            assert proc.returncode == -signal.SIGKILL, proc.stderr[-2000:]
        else:
            assert proc.returncode == 0, proc.stderr[-2000:]
        recs[phase] = json.loads(out.read_text())
    victim, resume, scratch = recs["victim"], recs["resume"], recs["scratch"]
    assert victim["state_at_kill"] == "running"
    assert victim["checkpoint_segment"] is not None
    assert resume["state"] == "succeeded" and scratch["state"] == "succeeded"
    # Crash-safe restore, byte-identical counts (wall excluded).
    assert resume["counts"] == scratch["counts"]
    assert resume["events"] == scratch["events"]
    assert resume["resumed_from"] == victim["checkpoint_segment"]
    assert 0 < resume["events_replayed"] < resume["events"]
    assert resume["resume"]["cursor"] > 0


@pytest.mark.slow
def test_bench_churn_resume_child_survives_dead_device(tmp_path):
    """One-JSON-line-under-any-hardware, resume edition: with every
    dispatch failing the job degrades to the per-pass host path, which
    never commits segments, so NO checkpoint ever lands — the victim's
    poll exits on job completion instead, and the resume child serves
    the journaled terminal result rather than replaying. The rung still
    writes valid JSON at every phase."""
    state = tmp_path / "state"
    state.mkdir()
    env = sanitized_cpu_env(
        {
            "KSIM_FAULTS": "replay.dispatch=always@device",
            "KSIM_REPLAY_BREAKER_N": "2",
        }
    )
    shape = ["--seed", "0", "--churn-events", "800", "--churn-nodes", "100"]
    recs = {}
    for phase in ("victim", "resume"):
        out = tmp_path / f"resume_dead_{phase}.json"
        proc = subprocess.run(
            [
                sys.executable, str(REPO / "bench.py"),
                "--child", "churn_resume", "--out", str(out),
                "--resume-phase", phase, "--state-dir", str(state),
                *shape,
            ],
            capture_output=True,
            text=True,
            timeout=420,
            cwd=REPO,
            env=env,
        )
        if phase == "victim":
            assert proc.returncode == -signal.SIGKILL, proc.stderr[-2000:]
        else:
            assert proc.returncode == 0, proc.stderr[-2000:]
        recs[phase] = json.loads(out.read_text())
    # Host path == no segment commits == no checkpoints: the victim ran
    # to completion before its kill, and resume folds the journaled
    # terminal state instead of restoring.
    assert recs["victim"]["checkpoint_segment"] is None
    assert recs["victim"]["state_at_kill"] == "succeeded"
    assert recs["resume"]["state"] == "succeeded"
    assert recs["resume"]["resumed_from"] is None
    assert recs["resume"]["counts"] is not None


@pytest.mark.slow
def test_bench_emits_json_when_probe_backend_is_dead():
    """A dead/absent accelerator at PROBE time (the failure mode the
    stdlib-only parent exists for): the probe child fails backend
    init, the orchestrator falls back to the CPU environment, and the
    one JSON line still appears."""
    env = sanitized_cpu_env({"BENCH_BUDGET_S": "360"})
    # Point the probe at a backend this host does not have: jax raises
    # inside the probe subprocess, which is exactly a dead-chip probe.
    env["JAX_PLATFORMS"] = "tpu"
    proc = subprocess.run(
        [sys.executable, str(REPO / "bench.py"), "--only", "200x20", "--repeats", "1"],
        capture_output=True,
        text=True,
        timeout=420,
        cwd=REPO,
        env=env,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = _last_json_line(proc.stdout)
    assert out["metric"] == "sched_pairs_per_sec"
    assert out["value"] > 0
    assert out["platform"] == "cpu"  # the fallback environment ran it


def test_bench_churn_shard_child_records_shard_evidence(tmp_path):
    """Round 17: the churn_shard child runs the SAME stream at tp=1 and
    tp=8 in one process and its record carries the sharding acceptance
    evidence — counts_match/device_steps_match, every tp=8 segment
    lowered at width 8 with zero shard_mesh fallbacks, the per-shard
    full-record byte budget shrunk by the mesh width, and the per-chip
    memory watermark field next to the phases split (null on CPU, whose
    backend has no memory_stats)."""
    out = tmp_path / "shard.json"
    proc = subprocess.run(
        [
            sys.executable, str(REPO / "bench.py"),
            "--child", "churn_shard", "--out", str(out),
            "--seed", "0", "--churn-events", "800", "--churn-nodes", "200",
            "--shard-tp", "8",
        ],
        capture_output=True,
        text=True,
        timeout=420,
        cwd=REPO,
        env=sanitized_cpu_env(),
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    rec = json.loads(out.read_text())
    assert rec["counts_match"] is True
    assert rec["device_steps_match"] is True
    tp1, tp8 = rec["modes"]["tp1"], rec["modes"]["tp8"]
    assert tp1["lowered_tps"] == [1] and tp8["lowered_tps"] == [8]
    for mode in (tp1, tp8):
        assert "shard_mesh" not in mode["unsupported"], mode["unsupported"]
        assert mode["fallback_steps"] == 0
        assert mode["device_steps"] >= 1
        assert "phases" in mode and "replay.dispatch" in mode["phases"]
        assert "per_chip_peak_bytes" in mode
    # The round-17 memory claim in one line: the full-record budget is
    # per shard, so tp=8 carries 1/8th of tp=1's bytes per chip.
    assert (
        tp8["full_bytes_per_shard_max"] * 8 == tp1["full_bytes_per_shard_max"]
    )


def test_bench_churn_shard_child_survives_dead_device(tmp_path):
    """One-JSON-line-under-any-hardware, shard edition: with every
    dispatch failing, BOTH widths degrade to the per-pass host path,
    the counts still match between them, and the record still exists."""
    out = tmp_path / "shard_dead.json"
    env = sanitized_cpu_env(
        {
            "KSIM_FAULTS": "replay.dispatch=always@device",
            "KSIM_REPLAY_BREAKER_N": "2",
        }
    )
    proc = subprocess.run(
        [
            sys.executable, str(REPO / "bench.py"),
            "--child", "churn_shard", "--out", str(out),
            "--seed", "0", "--churn-events", "300", "--churn-nodes", "64",
            "--shard-tp", "8",
        ],
        capture_output=True,
        text=True,
        timeout=420,
        cwd=REPO,
        env=env,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    rec = json.loads(out.read_text())
    assert rec["counts_match"] is True  # the host path carried both widths
    for mode in rec["modes"].values():
        assert mode["device_steps"] == 0
        assert mode["unsupported"].get("device_error", 0) >= 1


def test_bench_churn_fleet_shard_child_records_mesh_evidence(tmp_path):
    """Round 19: the churn_fleet_shard child runs the solo device
    replay and the 2-lane tp=4 fleet of the SAME stream in one process
    and its record carries the 2-D mesh acceptance evidence — per-lane
    counts matching solo, the (2, 4) grid actually built, every fleet
    segment lowered at the declared width, the per-shard byte budget,
    and the leader's dev_const counters with hits (the committed fleet
    layout was adopted and steady-state windows re-transferred
    nothing)."""
    out = tmp_path / "fleet_shard.json"
    proc = subprocess.run(
        [
            sys.executable, str(REPO / "bench.py"),
            "--child", "churn_fleet_shard", "--out", str(out),
            "--seed", "0", "--churn-events", "1200", "--churn-nodes", "64",
            "--fleet-lanes", "2", "--shard-tp", "4",
        ],
        capture_output=True,
        text=True,
        timeout=420,
        cwd=REPO,
        env=sanitized_cpu_env(),
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    rec = json.loads(out.read_text())
    assert rec["lanes"] == 2 and rec["tp"] == 4
    assert rec["counts_match"] is True
    assert rec["mesh_failed"] is False
    assert rec["mesh_grids"] == [[2, 4]]
    assert rec["lowered_tps"] == [4]
    assert rec["full_bytes_per_shard_max"] > 0
    assert rec["aggregate_speedup"] > 0
    assert rec["fleet"]["lanes_on_device"] == 1.0
    assert rec["fleet"]["group_dispatches"] >= 1
    # Zero-resharding engagement: at least one steady-state window hit
    # the id-keyed reuse map under the ("mesh", 2, 4) layout token.
    assert rec["dev_const"]["hits"] > 0, rec["dev_const"]


@pytest.mark.slow
def test_bench_churn_fleet_shard_child_survives_dead_device(tmp_path):
    """One-JSON-line-under-any-hardware, 2-D mesh edition: with every
    dispatch failing, both legs degrade to the per-pass host path, the
    lane counts still match solo, and the record still exists."""
    out = tmp_path / "fleet_shard_dead.json"
    env = sanitized_cpu_env(
        {
            "KSIM_FAULTS": "replay.dispatch=always@device",
            "KSIM_REPLAY_BREAKER_N": "2",
        }
    )
    proc = subprocess.run(
        [
            sys.executable, str(REPO / "bench.py"),
            "--child", "churn_fleet_shard", "--out", str(out),
            "--seed", "0", "--churn-events", "300", "--churn-nodes", "64",
            "--fleet-lanes", "2", "--shard-tp", "4",
        ],
        capture_output=True,
        text=True,
        timeout=420,
        cwd=REPO,
        env=env,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    rec = json.loads(out.read_text())
    assert rec["counts_match"] is True  # the host path carried all lanes
    assert rec["fleet"]["lanes_on_device"] == 0.0
    assert all(s == 0 for s in rec["fleet"]["lane_device_steps"])


@pytest.mark.slow
def test_bench_churn_workers_child_records_fleet_scaleout_evidence(tmp_path):
    """Round 20: the churn_workers child's record carries the
    horizontal-scale-out evidence — a 1-worker leg and an N-worker
    subprocess fleet leg over the same multi-tenant storm, every job's
    counts byte-identical to the in-process solo baseline, lease
    counters showing the fleet actually spread the claims, and zero
    takeovers (nobody died, nobody was deposed)."""
    out = tmp_path / "workers.json"
    proc = subprocess.run(
        [
            sys.executable, str(REPO / "bench.py"),
            "--child", "churn_workers", "--out", str(out),
            "--seed", "0", "--churn-events", "300", "--churn-nodes", "64",
            "--jobs-count", "2", "--workers-fleet", "2",
        ],
        capture_output=True,
        text=True,
        timeout=420,
        cwd=REPO,
        env=sanitized_cpu_env(),
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    rec = json.loads(out.read_text())
    assert rec["jobs"] == 2 and rec["fleet"] == 2
    assert rec["jobs_match_solo"] is True
    legs = rec["legs"]
    assert legs["one_worker"]["workers"] == 1
    assert legs["fleet"]["workers"] == 2
    for leg in legs.values():
        assert leg["finished"] == 2
        assert leg["jobs_per_min"] > 0
        assert leg["takeovers"] == 0
        assert leg["step_p99_max_s"] > 0
        for pj in leg["per_job"]:
            assert pj["state"] == "succeeded"
            assert pj["counts"] == rec["solo_counts"]
    # The 1-worker leg funnels every claim through one worker; in the
    # fleet leg every claim is accounted to some worker and nothing
    # expired.  (Claim SPREAD is racy at this tiny shape — a fast
    # worker may legally adopt both jobs in one poll — so only the
    # conservation law is asserted.)
    solo_counters = legs["one_worker"]["lease_counters"]
    assert len(solo_counters) == 1
    assert sum(c["claims"] for c in solo_counters.values()) == 2
    fleet_counters = legs["fleet"]["lease_counters"]
    assert sum(c["claims"] for c in fleet_counters.values()) == 2
    assert all(c["expired"] == 0 for c in fleet_counters.values())
    # Round 21: each leg records a timed fleet-scope observability
    # scrape (workers publish at KSIM_OBS_PUBLISH_S=1; the leg merges
    # the snapshots and round-trips the Prometheus exposition).
    for leg in legs.values():
        scrape = leg["obs_scrape"]
        assert scrape["scrape_ms"] >= 0
        assert scrape["exposition_bytes"] > 0
        # Jobs run for multiple publish intervals, so every worker of
        # the leg has published at least one snapshot by scrape time.
        assert len(scrape["workers_published"]) >= leg["workers"]
        assert scrape["dispatch_p99_s"] is None or scrape["dispatch_p99_s"] > 0


def test_bench_churn_workers_child_survives_dead_device(tmp_path):
    """One-JSON-line-under-any-hardware, scale-out edition: the fault
    plane rides the environment into every fleet worker subprocess
    (sanitized_cpu_env copies the parent env), every dispatch fails,
    each worker degrades to the host path — and the counts still match
    the (equally degraded) in-child solo baseline."""
    out = tmp_path / "workers_dead.json"
    env = sanitized_cpu_env(
        {
            "KSIM_FAULTS": "replay.dispatch=always@device",
            "KSIM_REPLAY_BREAKER_N": "2",
        }
    )
    proc = subprocess.run(
        [
            sys.executable, str(REPO / "bench.py"),
            "--child", "churn_workers", "--out", str(out),
            "--seed", "0", "--churn-events", "200", "--churn-nodes", "64",
            "--jobs-count", "1", "--workers-fleet", "2",
        ],
        capture_output=True,
        text=True,
        timeout=420,
        cwd=REPO,
        env=env,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    rec = json.loads(out.read_text())
    assert rec["jobs_match_solo"] is True
    for leg in rec["legs"].values():
        assert leg["finished"] == 1
        assert all(pj["state"] == "succeeded" for pj in leg["per_job"])
        # The fleet-scope scrape must survive the dead device too — the
        # observability plane is pure host-side I/O, so a wedged chip
        # can degrade the jobs but never the telemetry pull.
        assert leg["obs_scrape"]["scrape_ms"] >= 0
        assert leg["obs_scrape"]["exposition_bytes"] > 0
