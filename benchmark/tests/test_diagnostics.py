"""What a run prints beside its result: the numbers compared (failed ones
last, where the driver's record keeps the end) and where a stall landed."""

import json
import time

import checks
import run as harness


def test_compared_lists_each_distinct_number_once_with_the_failed_ones_last(capsys):
    comparisons = [checks.cmp_eq("job.state", "succeeded", "succeeded"),
                   checks.cmp_eq("replay.fallback_steps", 1, 0),
                   checks.cmp_eq("job.state", "succeeded", "succeeded"),
                   checks.cmp_max("reference.score_mismatch_share", 0.001, 0.02)]
    correct, compared = harness.print_comparisons(comparisons)
    assert correct is False
    assert compared == [["job.state", "succeeded", "succeeded", True],
                        ["reference.score_mismatch_share", 0.001, 0.02, True],
                        ["replay.fallback_steps", 1, 0, False]]
    assert len(capsys.readouterr().out.splitlines()) == 3
    harness.log_compared(compared)
    last = capsys.readouterr().err.splitlines()[-1]
    assert last == "compared replay.fallback_steps: 1 limit 0 NOT OK"
    # The result line's key never reads as a failed comparison line.
    assert '"ok": false' not in json.dumps({"compared": compared})


def test_slow_requests_names_the_stalled_jobs_with_the_servers_own_account():
    job = lambda wall, server: {"wall_s": wall, "doc": {
        "result": {"wallSeconds": server}, "phases": {"replay.lower": 0.5234567},
        "runtime": {"gc_gen2_pause_s": 0.04}}}
    counted = [job(1.1, 1.0)] * 17 + [job(5.26, 5.2), job(3.47, 1.1)] + [job(1.2, 1.1)] * 20
    got = harness.slow_requests(counted, is_job=True)
    assert [(r["index"], r["wall_s"], r["server_wall_s"]) for r in got] == [(17, 5.26, 5.2), (18, 3.47, 1.1)]
    assert got[0]["phases"] == {"replay.lower": 0.523} and got[0]["runtime"] == {"gc_gen2_pause_s": 0.04}
    assert harness.slow_requests([], is_job=True) == []
    imports = [{"wall_s": 4.2, "phases_s": {"export": 1.4}}] * 5 + [{"wall_s": 9.0, "phases_s": {"export": 6.1}}]
    assert harness.slow_requests(imports, is_job=False) == [
        {"index": 5, "wall_s": 9.0, "phases_s": {"export": 6.1}}]


def test_the_stall_watch_keeps_only_late_wake_ups(monkeypatch):
    # A host that holds the process for a second: the sleeper wakes late once.
    real_sleep, held = time.sleep, []

    def stalled(seconds):
        if not held:
            held.append(1)
            real_sleep(seconds + 0.4)
        else:
            real_sleep(seconds)

    monkeypatch.setattr(harness.time, "sleep", stalled)
    watch = harness.StallWatch()
    watch.start()
    real_sleep(0.7)
    late = watch.halt()
    assert len(late) == 1 and 0.35 <= late[0][1] <= 0.6
