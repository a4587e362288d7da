"""`make trace`: end-to-end trace-plane validation (docs/observability.md).

Replays the locked 6k churn prefix (seed 0, 2000 nodes — repo CLAUDE.md)
through the DEVICE-resident path with tracing fully enabled
(``KSIM_TRACE_OUT``) in the sanitized CPU environment (runnable under
any hardware condition, like ``make faults``), then validates:

- the behavior locks hold byte-identically with tracing on (2524/471);
- the emitted Chrome-trace JSON parses and contains a
  lower/dispatch/reconcile span for EVERY on-device segment plus a
  ``store.txn_commit`` event per committed segment;
- with a ``KSIM_FAULTS`` schedule armed (second, smaller run), the
  timeline carries the ``fault.fired`` and ``replay.fallback`` events
  the chaos evidence story depends on;
- two CONCURRENT tenant jobs (fourth run, the job plane —
  ksim_tpu/jobs) record job-tagged ``runner.step``/``replay.dispatch``
  spans into ISOLATED per-job trace rings (every record in a job's
  ring carries that job's id and no other's), with both jobs landing
  identical counts;
- a 2-worker FLEET (fifth run, the fleet observability plane —
  docs/observability.md "Fleet observability"): every worker's
  SIGTERM-published trace export merges into ONE Chrome trace with one
  process lane per worker, job-tagged records attributed to the
  owning worker's lane, and at least one complete
  submit→claim→run flow-event triple (``s``/``t``/``f``) per job.

The parent process is stdlib-only (crash containment: jax backend init
can hang on a dead backend, so anything that must complete runs jax
only in subprocesses)."""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CHILD_TIMEOUT_S = 840

# The locked 6k prefix (repo CLAUDE.md; tests/test_behavior_locks.py).
LOCK = (2524, 471)


# ---------------------------------------------------------------------------
# Child payload (imports jax; only ever runs in a subprocess)
# ---------------------------------------------------------------------------


def _child_jobs(events: int, nodes: int, out_path: str) -> None:
    """Two concurrent tenant jobs of the same churn stream through the
    job plane; dumps each job's state, counts, and PRIVATE trace ring
    for the parent's attribution/isolation asserts."""
    if _REPO not in sys.path:
        sys.path.insert(0, _REPO)
    import jax

    from ksim_tpu.jobs import JobManager
    from ksim_tpu.scenario import churn_scenario, spec_from_operations
    from ksim_tpu.util import enable_compilation_cache, raise_map_count_limit

    enable_compilation_cache()
    raise_map_count_limit()
    jax.config.update("jax_enable_x64", False)
    doc = {
        "spec": {
            "simulator": {
                "maxPodsPerPass": 1024,
                "podBucketMin": 128,
                "deviceReplay": True,
                "preemption": True,
            },
            "scenario": spec_from_operations(
                list(
                    churn_scenario(
                        0, n_nodes=nodes, n_events=events, ops_per_step=100
                    )
                )
            ),
        }
    }
    jm = JobManager(workers=2, queue_limit=4)
    jobs = [jm.submit(doc) for _ in range(2)]
    finished = jm.join(timeout=CHILD_TIMEOUT_S - 60)
    record = {"finished": finished, "jobs": []}
    for j in jobs:
        state, result, err = j.result_view()
        counts = None
        replay = {}
        if result:
            counts = [
                result["result"]["podsScheduled"],
                result["result"]["unschedulableAttempts"],
            ]
            replay = result.get("replay") or {}
        record["jobs"].append(
            {
                "id": j.id,
                "state": state,
                "error": err,
                "counts": counts,
                "device_round_trips": replay.get("device_round_trips", 0),
                "account": (result or {}).get("account"),
                "ring": [
                    {"name": r["name"], "ph": r["ph"], "args": r["args"]}
                    for r in j.trace.ring_records()
                ],
            }
        )
    jm.shutdown(timeout=5)
    with open(out_path, "w") as f:
        json.dump(record, f)


def _child_fleet_obs(out_path: str) -> None:
    """A 2-worker process fleet behind an in-process front door: submit
    two tiny jobs, SIGTERM the workers (their final telemetry publish
    lands each worker's merged trace export in ``obs/``), then merge
    every published trace with flow stitching for the parent's
    lane/attribution/flow asserts."""
    if _REPO not in sys.path:
        sys.path.insert(0, _REPO)
    import signal
    import tempfile as tf
    import time

    from ksim_tpu import obs
    from ksim_tpu.jobs import JobManager
    from tests.helpers import make_node, make_pod, sanitized_cpu_env

    jobs_dir = tf.mkdtemp(prefix="ksim_fleet_obs_")
    workers: dict = {}
    # Explicitly CPU, whatever this child inherited: two worker
    # processes cannot share one chip (docs/jobs.md "Multi-worker fleet").
    worker_env = sanitized_cpu_env()
    for wid in ("w1", "w2"):
        proc = subprocess.Popen(
            [
                sys.executable, "-m", "ksim_tpu.jobs",
                "--dir", jobs_dir, "--worker-id", wid, "--workers", "1",
            ],
            cwd=_REPO, env=worker_env, stdout=subprocess.PIPE, text=True,
        )
        line = proc.stdout.readline()
        if line.strip() != f"READY {wid}":
            raise SystemExit(f"worker {wid} never came up: {line!r}")
        workers[wid] = proc
    jm = JobManager(
        workers=0, queue_limit=8, jobs_dir=jobs_dir,
        role="frontdoor", worker_id="fd", lease_s=30.0, poll_s=0.2,
    )
    spec = {
        "spec": {
            "scenario": {
                "operations": [
                    {
                        "step": 0,
                        "createOperation": {"object": make_node("n0", cpu="4")},
                    },
                    {
                        "step": 1,
                        "createOperation": {"object": make_pod("p0", cpu="100m")},
                    },
                ]
            }
        }
    }
    submitted = [jm.submit(spec) for _ in range(2)]
    deadline = time.time() + CHILD_TIMEOUT_S - 120
    states: dict = {}
    for job in submitted:
        while True:
            st = job.status()
            if st["state"] in ("succeeded", "failed"):
                break
            if time.time() > deadline:
                break
            time.sleep(0.1)
        states[job.id] = st
    pids = {wid: p.pid for wid, p in workers.items()}
    for p in workers.values():
        p.send_signal(signal.SIGTERM)
    for p in workers.values():
        p.wait(timeout=60)
    jm.shutdown()
    traces = obs.read_fleet_traces(jobs_dir)
    record = {
        "worker_pids": pids,
        "frontdoor_pid": os.getpid(),
        "published": sorted(traces),
        "jobs": {
            j.id: {
                "state": states[j.id]["state"],
                "owner": states[j.id]["owner"],
            }
            for j in submitted
        },
        "merged": obs.merge_chrome_traces(traces, flows=True),
    }
    with open(out_path, "w") as f:
        json.dump(record, f)


def _child(events: int, nodes: int, out_path: str, fleet: int = 0) -> None:
    # Scripts put THEIR directory (tools/) on sys.path, not the repo.
    if _REPO not in sys.path:
        sys.path.insert(0, _REPO)
    import jax

    from ksim_tpu.obs import TRACE
    from ksim_tpu.scenario import ScenarioRunner, churn_scenario
    from ksim_tpu.util import enable_compilation_cache, raise_map_count_limit

    enable_compilation_cache()
    raise_map_count_limit()
    jax.config.update("jax_enable_x64", False)
    runner = ScenarioRunner(
        max_pods_per_pass=1024,
        pod_bucket_min=128,
        device_replay=True,
        preemption=True,
        fleet=fleet or None,
    )
    res = runner.run(
        churn_scenario(0, n_nodes=nodes, n_events=events, ops_per_step=100)
    )
    drv = runner.replay_driver
    # Flush the trace explicitly (the atexit hook would too; an explicit
    # write means the result JSON below can promise the file exists).
    if TRACE.out_path:
        TRACE.export_chrome(TRACE.out_path)
    record = {
        "scheduled": res.pods_scheduled,
        "unschedulable": res.unschedulable_attempts,
        "steps": len(res.steps),
        "phases": res.phase_seconds,
        **drv.stats(),
    }
    if fleet:
        record["lane_counts"] = [
            [r.pods_scheduled, r.unschedulable_attempts] for r in res.lanes
        ]
        record["fleet"] = runner.fleet_driver.stats()
    with open(out_path, "w") as f:
        json.dump(record, f)


# ---------------------------------------------------------------------------
# Parent validation (stdlib only)
# ---------------------------------------------------------------------------


def _sanitized_env() -> dict:
    sys.path.insert(0, _REPO)
    try:
        from tests.helpers import sanitized_cpu_env
    finally:
        sys.path.pop(0)
    return sanitized_cpu_env()


def _run_child(
    events: int, nodes: int, env: dict, tmp: str, tag: str, fleet: int = 0
) -> tuple[dict, dict]:
    """One traced child replay; returns (result record, trace doc)."""
    trace_path = os.path.join(tmp, f"trace_{tag}.json")
    result_path = os.path.join(tmp, f"result_{tag}.json")
    env = dict(env, KSIM_TRACE_OUT=trace_path)
    cmd = [
        sys.executable, os.path.abspath(__file__),
        "--child", "--events", str(events), "--nodes", str(nodes),
        "--out", result_path, "--fleet", str(fleet),
    ]
    proc = subprocess.run(cmd, cwd=_REPO, env=env, timeout=CHILD_TIMEOUT_S)
    if proc.returncode != 0:
        raise SystemExit(f"trace-check child ({tag}) exited rc={proc.returncode}")
    with open(result_path) as f:
        result = json.load(f)
    with open(trace_path) as f:
        trace = json.load(f)  # must PARSE — that is half the check
    return result, trace


def _span_counts(trace: dict) -> dict[str, int]:
    out: dict[str, int] = {}
    for ev in trace.get("traceEvents", ()):
        if ev.get("ph") in ("X", "i"):
            out[ev["name"]] = out.get(ev["name"], 0) + 1
    return out


def _fail(msg: str) -> None:
    raise SystemExit(f"trace-check FAILED: {msg}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--child", action="store_true")
    ap.add_argument("--child-jobs", action="store_true")
    ap.add_argument("--child-fleet-obs", action="store_true")
    ap.add_argument("--events", type=int, default=6000)
    ap.add_argument("--nodes", type=int, default=2000)
    ap.add_argument("--out", type=str, default="")
    ap.add_argument("--fleet", type=int, default=0)
    args = ap.parse_args()
    if args.child_fleet_obs:
        _child_fleet_obs(args.out)
        return
    if args.child_jobs:
        _child_jobs(args.events, args.nodes, args.out)
        return
    if args.child:
        _child(args.events, args.nodes, args.out, args.fleet)
        return

    env = _sanitized_env()
    with tempfile.TemporaryDirectory(prefix="ksim_trace_check_") as tmp:
        # -- run 1: the locked 6k prefix, fully traced, no faults ------
        result, trace = _run_child(args.events, args.nodes, env, tmp, "clean")
        counts = (result["scheduled"], result["unschedulable"])
        if args.events == 6000 and args.nodes == 2000 and counts != LOCK:
            _fail(f"locked counts diverged under tracing: {counts} != {LOCK}")
        names = _span_counts(trace)
        segments = result["device_round_trips"]
        if segments < 1:
            _fail(f"no device segments ran (stats: {result})")
        for span in ("replay.lower", "replay.dispatch"):
            if names.get(span, 0) < segments:
                _fail(
                    f"{span}: {names.get(span, 0)} spans for {segments} "
                    f"dispatched segments"
                )
        # The double-buffered executor (round 10) pre-lowers every
        # non-final window's successor while its dispatch is in flight.
        if segments > 1 and not names.get("replay.prelower"):
            _fail("pipelined run recorded no replay.prelower spans")
        cache = result.get("lower_cache", {})
        if segments > 1 and not cache.get("hits"):
            _fail(f"lowered-universe cache never hit across {segments} segments: {cache}")
        # device_round_trips counts HEALTHY dispatches only (errored
        # ones never increment it); of those, post-dispatch validation
        # discards return before any reconcile, and a reconcile that
        # rolled back has a span but no commit.
        unsupported = result.get("unsupported", {})
        discards = unsupported.get("featurize_prediction", 0) + unsupported.get(
            "preemption_overflow", 0
        )
        reconciled = segments - discards
        committed = reconciled - unsupported.get("reconcile_fault", 0)
        if names.get("replay.reconcile", 0) < reconciled:
            _fail(
                f"replay.reconcile: {names.get('replay.reconcile', 0)} spans "
                f"for {reconciled} reconciled segments"
            )
        if names.get("store.txn_commit", 0) < committed:
            _fail(
                f"store.txn_commit: {names.get('store.txn_commit', 0)} events "
                f"for {committed} committed segments"
            )
        if result["fallback_steps"] and not names.get("runner.step"):
            _fail("fallback steps ran but no runner.step spans recorded")
        print(
            f"trace-check: clean run OK — counts {counts}, "
            f"{segments} segments, spans {({k: names[k] for k in sorted(names)})}"
        )

        # -- run 2: a KSIM_FAULTS schedule armed -----------------------
        # One injected dispatch failure over a small prefix: the
        # timeline must show the fault firing AND the resulting
        # degradation (device_error fallback -> per-pass step).
        armed_env = dict(env, KSIM_FAULTS="replay.dispatch=call:1")
        result2, trace2 = _run_child(1000, 500, armed_env, tmp, "armed")
        names2 = _span_counts(trace2)
        if not names2.get("fault.fired"):
            _fail("armed run recorded no fault.fired event")
        if not names2.get("replay.fallback"):
            _fail("armed run recorded no replay.fallback event")
        reasons = {
            ev["args"].get("reason")
            for ev in trace2["traceEvents"]
            if ev.get("name") == "replay.fallback"
        }
        if "device_error" not in reasons:
            _fail(f"armed run's fallback reasons lack device_error: {reasons}")
        print(
            f"trace-check: armed run OK — fault.fired x{names2['fault.fired']}, "
            f"fallback reasons {sorted(r for r in reasons if r)}"
        )

        # -- run 3: a 2-lane FLEET replay (round 12) -------------------
        # Per-lane span attribution: every replay.dispatch span of a
        # fleet run must name the lanes it advanced, and every
        # replay.reconcile span the ONE lane it reconciled — a Chrome
        # trace from an S-lane run is useless if the phases are not
        # attributable per trajectory.
        result3, trace3 = _run_child(1000, 500, env, tmp, "fleet", fleet=2)
        fleet_stats = result3.get("fleet", {})
        if fleet_stats.get("group_dispatches", 0) < 1:
            _fail(f"fleet run dispatched no groups (stats: {fleet_stats})")
        if any(c != result3["lane_counts"][0] for c in result3["lane_counts"]):
            _fail(f"fleet lanes diverged: {result3['lane_counts']}")
        dispatch_spans = [
            ev
            for ev in trace3["traceEvents"]
            if ev.get("name") == "replay.dispatch" and ev.get("ph") == "X"
        ]
        reconcile_spans = [
            ev
            for ev in trace3["traceEvents"]
            if ev.get("name") == "replay.reconcile" and ev.get("ph") == "X"
        ]
        if not dispatch_spans or not reconcile_spans:
            _fail("fleet run recorded no dispatch/reconcile spans")
        for ev in dispatch_spans:
            if "lane" not in ev.get("args", {}):
                _fail(f"fleet replay.dispatch span without lane attribution: {ev}")
        lanes_seen = set()
        for ev in reconcile_spans:
            lane = ev.get("args", {}).get("lane")
            if lane is None:
                _fail(f"fleet replay.reconcile span without lane attribution: {ev}")
            lanes_seen.add(lane)
        if lanes_seen != {0, 1}:
            _fail(f"fleet reconcile spans cover lanes {lanes_seen}, expected {{0, 1}}")
        print(
            f"trace-check: fleet run OK — {fleet_stats['group_dispatches']} group "
            f"dispatches, reconcile lanes {sorted(lanes_seen)}"
        )

        # -- run 4: two CONCURRENT tenant jobs (the job plane) ---------
        # Per-job isolation made checkable: every record in a job's
        # private ring must carry that job's id (the scoped trace
        # plane's tag), the two rings must never cross-contaminate,
        # and the locked stream must land the same counts in both.
        result4_path = os.path.join(tmp, "result_jobs.json")
        cmd = [
            sys.executable, os.path.abspath(__file__),
            "--child-jobs", "--events", "1000", "--nodes", "500",
            "--out", result4_path,
        ]
        proc = subprocess.run(cmd, cwd=_REPO, env=env, timeout=CHILD_TIMEOUT_S)
        if proc.returncode != 0:
            raise SystemExit(f"trace-check child (jobs) exited rc={proc.returncode}")
        with open(result4_path) as f:
            result4 = json.load(f)
        if not result4.get("finished"):
            _fail(f"job-plane run did not finish: {result4}")
        ids = [j["id"] for j in result4["jobs"]]
        if len(set(ids)) != 2:
            _fail(f"expected 2 distinct jobs, got {ids}")
        counts_seen = []
        for jrec in result4["jobs"]:
            if jrec["state"] != "succeeded":
                _fail(f"job {jrec['id']} ended {jrec['state']}: {jrec['error']}")
            counts_seen.append(jrec["counts"])
            if jrec["device_round_trips"] < 1:
                _fail(f"job {jrec['id']} ran no device segments")
            names4 = {}
            for rec in jrec["ring"]:
                names4[rec["name"]] = names4.get(rec["name"], 0) + 1
                tag = rec["args"].get("job")
                if tag != jrec["id"]:
                    _fail(
                        f"record in {jrec['id']}'s ring tagged job={tag!r} "
                        f"({rec['name']}) — per-job rings must be isolated"
                    )
            for span in ("jobs.run", "replay.dispatch"):
                if not names4.get(span):
                    _fail(f"job {jrec['id']}'s ring has no {span} span")
            if not names4.get("runner.step") and not names4.get("replay.reconcile"):
                _fail(f"job {jrec['id']}'s ring has no step/reconcile spans")
            # The job accounts for its own wall (docs/jobs.md "account"):
            # the sequential parts and what none of them names ARE the
            # wall, to the millisecond, with two jobs sharing the process.
            account = jrec["account"] or {}
            parts = (
                "submit_s", "queue_s", "journal_s", "run_s", "digest_s",
                "document_s", "release_s", "collect_s", "unnamed_s",
            )
            if any(account.get(k) is None or account[k] < 0 for k in parts):
                _fail(f"job {jrec['id']}'s account lacks a part: {account}")
            closed = sum(account[k] for k in parts)
            if abs(closed - account["wall_s"]) > 1e-3:
                _fail(
                    f"job {jrec['id']}'s account does not close: parts "
                    f"{closed:.6f} s against wall_s {account['wall_s']:.6f} s"
                )
            if not 0 <= account["run_self_s"] <= account["run_s"]:
                _fail(f"job {jrec['id']}'s run_self_s is off: {account}")
        if counts_seen[0] != counts_seen[1]:
            _fail(f"concurrent jobs diverged: {counts_seen}")
        print(
            f"trace-check: jobs run OK — 2 isolated job rings, counts "
            f"{counts_seen[0]}"
        )

        # -- run 5: a 2-worker fleet obs leg (round 19) ----------------
        # The fleet observability plane end-to-end: two worker
        # PROCESSES publish their trace exports at SIGTERM, the merged
        # Chrome trace must carry one process lane per worker, every
        # job-tagged run record must sit in its owning worker's lane,
        # and each job must draw a complete submit->claim->run flow
        # arrow (s/t/f triple) across the lanes.
        result5_path = os.path.join(tmp, "result_fleet_obs.json")
        fleet_env = dict(
            env,
            KSIM_TRACE="1",
            KSIM_OBS_PUBLISH_S="5",
            KSIM_WORKERS_POLL_S="0.2",
            KSIM_WORKERS_LEASE_S="30",
        )
        cmd = [
            sys.executable, os.path.abspath(__file__),
            "--child-fleet-obs", "--out", result5_path,
        ]
        proc = subprocess.run(
            cmd, cwd=_REPO, env=fleet_env, timeout=CHILD_TIMEOUT_S
        )
        if proc.returncode != 0:
            raise SystemExit(
                f"trace-check child (fleet-obs) exited rc={proc.returncode}"
            )
        with open(result5_path) as f:
            result5 = json.load(f)
        worker_pids = result5["worker_pids"]  # wid -> pid
        jobs5 = result5["jobs"]  # jid -> {state, owner}
        for jid, jrec in jobs5.items():
            if jrec["state"] != "succeeded":
                _fail(f"fleet-obs job {jid} ended {jrec['state']}")
            if jrec["owner"] not in worker_pids:
                _fail(
                    f"fleet-obs job {jid} owned by {jrec['owner']!r}, "
                    f"not a fleet worker {sorted(worker_pids)}"
                )
        missing = set(worker_pids) - set(result5["published"])
        if missing:
            _fail(f"workers never published a trace export: {sorted(missing)}")
        merged5 = result5["merged"]["traceEvents"]
        # One process lane per worker: exactly one process_name
        # metadata record per worker id, all on distinct pids.
        lanes = {}
        for ev in merged5:
            if ev.get("ph") == "M" and ev.get("name") == "process_name":
                lanes.setdefault(ev["args"]["name"], set()).add(ev["pid"])
        for wid, pid in worker_pids.items():
            if lanes.get(wid) != {pid}:
                _fail(
                    f"worker {wid} lane is {sorted(lanes.get(wid, ()))}, "
                    f"expected exactly its pid {pid}"
                )
        # Job-tagged run records attribute to the OWNING worker's lane.
        runs_seen = set()
        for ev in merged5:
            if ev.get("name") != "jobs.run" or ev.get("ph") != "X":
                continue
            jid = (ev.get("args") or {}).get("job")
            if jid not in jobs5:
                continue
            owner_pid = worker_pids[jobs5[jid]["owner"]]
            if ev.get("pid") != owner_pid:
                _fail(
                    f"job {jid} run record in pid {ev.get('pid')}'s lane; "
                    f"owner {jobs5[jid]['owner']} is pid {owner_pid}"
                )
            runs_seen.add(jid)
        if runs_seen != set(jobs5):
            _fail(
                f"merged trace lacks jobs.run spans for "
                f"{sorted(set(jobs5) - runs_seen)}"
            )
        # >=1 COMPLETE submit->claim->run flow triple per job.
        flows: dict = {}
        for ev in merged5:
            if ev.get("name") == "jobs.flow":
                flows.setdefault(ev["args"]["job"], set()).add(ev["ph"])
        for jid in jobs5:
            if flows.get(jid) != {"s", "t", "f"}:
                _fail(
                    f"job {jid} flow phases {sorted(flows.get(jid, ()))}, "
                    f"expected a complete s/t/f triple"
                )
        print(
            f"trace-check: fleet-obs run OK — lanes {sorted(lanes)}, "
            f"{len(flows)} complete flow triples"
        )
    print("trace-check: PASS")


if __name__ == "__main__":
    main()
