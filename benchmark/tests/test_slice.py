"""Where the traced slice opens: ``trace.offset_share`` of the wall of the
last request the run completed before the traced one, never a number of
seconds (``run.py`` ``Slice``, ``trace_spec``).  A ``Slice`` driven with a
fake server and a timer that records its delay; every traffic file against
the one key; and a whole traced run (rehearsal sizes) whose traced request
ends before its slice was to open: it fails within seconds, on one line that
holds the share, the wall it was taken of, the planned second and the traced
request's wall."""

import glob
import json
import os
import signal
import time

import pytest

import client
import run as harness

TRAFFIC = sorted(glob.glob(os.path.join(harness.HERE, "traffic", "*.json")))


class Proc:
    def __init__(self):
        self.signals = []

    def send_signal(self, signum):
        self.signals.append(signum)

    def poll(self):
        return None


class Server:
    def __init__(self):
        self.proc = Proc()


class Timer:
    """``threading.Timer``'s face; never fires by itself."""

    made: list = []

    def __init__(self, delay, fn):
        self.delay, self.fn, self.cancelled = delay, fn, False
        Timer.made.append(self)

    def start(self):
        pass

    def cancel(self):
        self.cancelled = True


@pytest.fixture
def sliced(tmp_path):
    def make(share, request=0, max_s=3.0):
        Timer.made = []
        s = harness.Slice(Server(), {"request": request, "offset_share": share, "max_s": max_s},
                          str(tmp_path))
        s.Timer = Timer
        return s
    return make


@pytest.mark.parametrize("share", [0.0, 0.35, 0.67])
def test_the_slice_opens_a_share_of_the_previous_wall_into_the_traced_request(sliced, tmp_path, share):
    s = sliced(share)
    s.request_starts(0, 6.63)
    assert s.state == "armed" and len(Timer.made) == 1
    assert Timer.made[0].delay == pytest.approx(share * 6.63)
    assert s.server.proc.signals == []
    # The timer fires: the child is told, says it is tracing, and the slice
    # runs max_s (seconds: what a slice may hold is device time) at most.
    (tmp_path / "ANCHOR.json").write_text("{}")
    Timer.made[0].fn()
    assert s.state == "on" and s.server.proc.signals == [signal.SIGUSR1]
    assert Timer.made[1].delay == 3.0
    s.request_ends(6.7)
    assert s.state == "off" and s.server.proc.signals == [signal.SIGUSR1, signal.SIGUSR2]
    (tmp_path / "DONE").write_text("")
    (tmp_path / "t.xplane.pb").write_text("")
    assert s.collect(time.monotonic() + 5).endswith("t.xplane.pb")
    for number in (str(share), "6.630 s", f"{share * 6.63:.3f} s", "6.700 s"):
        assert number in s.placement()


def test_a_faster_program_takes_its_slice_with_it(sliced):
    """ISSUE 53's arithmetic: at 0.35 the slice opens ~2.3 s into the
    accepted tree's 6.63-s job (its one dispatch ends ~5.5 s in) and ~1.9 s
    into a 5.4-s job whose device runs from ~1.6 to ~4.3 s — where the 5.0 s
    of ``offset_s`` lay behind that program's last device operation."""
    for wall, opens_s, device_s in ((6.63, 2.32, (1.6, 5.5)), (5.4, 1.89, (1.6, 4.3))):
        s = sliced(0.35)
        s.request_starts(0, wall)
        assert Timer.made[0].delay == pytest.approx(opens_s, abs=0.005)
        assert device_s[0] < Timer.made[0].delay < device_s[1] - 2.0


def test_only_the_traced_request_arms_the_slice_and_only_once(sliced):
    s = sliced(0.5, request=1)
    s.request_starts(0, 9.0)
    assert s.state == "idle" and Timer.made == []
    s.request_ends(2.0)
    s.request_starts(1, 2.0)
    assert s.state == "armed" and Timer.made[0].delay == pytest.approx(1.0)
    s.request_starts(1, 7.0)
    assert len(Timer.made) == 1


def test_a_request_that_ends_while_the_slice_is_armed_is_an_answer_not_a_wait(sliced):
    s = sliced(0.67)
    s.request_starts(0, 100.0)     # the stream cell's one warm-up: it traces every program
    s.request_ends(18.7)
    assert s.state == "missed" and Timer.made[0].cancelled
    assert s.server.proc.signals == []   # the profiler was never started: no DONE will come
    started = time.monotonic()
    assert s.collect(started + 300) is None
    assert time.monotonic() - started < 1.0
    Timer.made[0].fn()               # a timer that fires late all the same starts nothing
    assert s.state == "missed" and s.server.proc.signals == []
    line = s.placement()
    assert "\n" not in line
    for number in ("0.67", "100.000 s", "67.000 s", "18.700 s"):
        assert number in line


def test_a_traced_request_that_never_started_leaves_nothing_to_wait_for(sliced):
    s = sliced(0.0, request=3)
    s.request_starts(0, None)
    started = time.monotonic()
    assert s.collect(started + 300) is None and time.monotonic() - started < 1.0
    assert "request 3" in s.placement() and "never started" in s.placement()


def test_a_share_of_nothing_cannot_succeed(sliced):
    s = sliced(0.35)
    with pytest.raises(client.CannotSucceed, match="no request was completed before request 0"):
        s.request_starts(0, None)
    whole = sliced(0.0)
    whole.request_starts(0, None)    # offset_share 0.0 is what offset_s 0.0 was
    assert whole.state == "armed" and Timer.made[0].delay == 0.0


# -- the traffic files ----------------------------------------------------------


@pytest.mark.parametrize("path", TRAFFIC, ids=[os.path.basename(p)[:-5] for p in TRAFFIC])
def test_every_traffic_file_places_its_slice_by_a_share(path):
    with open(path, encoding="utf-8") as f:
        text = f.read()
    assert "offset_s\"" not in text
    traffic = json.loads(text)
    for doc in (traffic, harness.overlay(traffic, traffic.get("rehearsal"))):
        spec = harness.trace_spec(doc)
        assert "offset_share" in spec and 0.0 <= spec["offset_share"] < 1.0
        assert spec["max_s"] > 0 and isinstance(spec["request"], int)
        if spec["offset_share"] > 0:
            assert spec["request"] >= 1 or doc["warmup_min"] >= 1
            assert spec["why"]          # what the share was derived from


def test_three_cells_sample_a_long_request_and_the_others_trace_a_whole_one():
    shares = {}
    for path in TRAFFIC:
        with open(path, encoding="utf-8") as f:
            doc = json.load(f)
        shares[doc["name"]] = (doc["trace"]["request"], doc["trace"]["offset_share"])
    sampled = {k: v for k, v in shares.items() if v[1] > 0}
    # The stream's one warm-up takes five times a job (it traces every
    # program): its share is of request 0's wall, so it traces request 1.
    assert sampled == {"basic": (0, 0.35), "prefix20k": (0, 0.59), "stream": (1, 0.67)}


@pytest.mark.parametrize("trace, said", [
    ({"request": 0, "offset_s": 5.0, "max_s": 3.0}, "offset_s"),
    ({"request": 0, "offset_share": 0.3, "offset_s": 0.0}, "offset_s"),
    ({"offset_share": 1.0}, "not in [0, 1)"),
    ({"offset_share": -0.1}, "not in [0, 1)"),
    ({"offset_share": "0.3"}, "not in [0, 1)"),
    ({"offset_share": True}, "not in [0, 1)"),
])
def test_a_trace_block_with_seconds_or_a_share_out_of_range_is_refused(trace, said):
    with pytest.raises(harness.BadTraffic) as e:
        harness.trace_spec({"name": "t", "warmup_min": 2, "trace": trace})
    assert said in str(e.value) and "'t'" in str(e.value)


@pytest.mark.parametrize("warm", [{"warmup_min": 0}, {"warmup_min": 1, "warmup_max": 0}])
def test_a_share_with_no_request_completed_before_the_traced_one_is_refused(warm):
    doc = dict({"name": "t", "trace": {"request": 0, "offset_share": 0.35}}, **warm)
    with pytest.raises(harness.BadTraffic, match="completes none before it"):
        harness.trace_spec(doc)
    harness.trace_spec(dict(doc, trace={"request": 0, "offset_share": 0.0}))   # a whole request needs none
    harness.trace_spec(dict(doc, trace={"request": 1, "offset_share": 0.35}))  # request 0 comes before it


def test_a_refused_traffic_file_ends_the_run_before_a_server_starts(monkeypatch, capsys):
    real = harness.load

    def doctored(rel):
        doc = real(rel)
        if rel.endswith("traffic/basic.json"):
            doc["trace"] = {"request": 0, "offset_s": 5.0, "max_s": 3.0}
        return doc

    monkeypatch.setattr(harness, "load", doctored)
    monkeypatch.setattr(harness, "start_server", lambda *a: pytest.fail("a server was started"))
    rc = harness.main(["--workload", "sperf-5k-preempt_basic", "--seconds", "1", "--rehearsal"])
    out = capsys.readouterr()
    assert rc == 2 and out.out == ""
    assert "FAILED before the server starts" in out.err and "offset_s" in out.err


# -- whole traced runs, rehearsal sizes ---------------------------------------------


def traced(monkeypatch, capsys, workload, seed):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    started = time.monotonic()
    rc = harness.main(["--workload", workload, "--seed", str(seed), "--seconds", "1",
                       "--trace", "1", "--rehearsal"])
    return rc, time.monotonic() - started, capsys.readouterr()


def test_a_sound_traced_run_opens_its_slice_inside_the_traced_request(monkeypatch, capsys):
    rc, _, out = traced(monkeypatch, capsys, "sperf-5k-preempt_basic", 31)
    doc = json.loads(out.out.strip().splitlines()[-1])
    assert rc == 0 and doc["correct"] is True and doc["rehearsal"] is True
    assert "trace file in hand" in out.err


def test_a_traced_request_that_outruns_its_slice_fails_the_run_within_seconds(monkeypatch, capsys):
    """The last warm-up reads 100 s (the stream cell's does: it traces every
    program), so 0.35 of it lies far behind the end of the traced job."""
    real_warm_up, real_job = harness.Driver.warm_up, client.Server.run_job
    windowed = []

    def slow_warm_up(self):
        warm = real_warm_up(self)
        self.last_wall_s = 100.0
        del windowed[:]
        return warm

    def counted_job(self, body, deadline):
        rec = real_job(self, body, deadline)
        windowed.append(rec["wall_s"])
        return rec

    monkeypatch.setattr(harness.Driver, "warm_up", slow_warm_up)
    monkeypatch.setattr(client.Server, "run_job", counted_job)
    rc, took, out = traced(monkeypatch, capsys, "sperf-5k-preempt_basic", 32)
    assert rc == 1 and out.out == "" and took < 60
    assert len(windowed) == 1        # the run ended with the traced request
    last = out.err.strip().splitlines()[-1]
    assert "FAILED: the traced request ended before its slice opened" in last
    for number in ("offset_share 0.35", "100.000 s", "35.000 s", f"{windowed[0]:.3f} s"):
        assert number in last
    assert "server output" not in out.err
