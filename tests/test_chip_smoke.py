"""chip_smoke.py's checks against canned documents: everything the
product's containment would hide on a sick chip (fallbacks, a tripped
breaker, JAX's CPU fallback), an over-committed node and a missed
deadline must each make the smoke exit non-zero with NOTHING on stdout.
The end-to-end run (a real server, tiny size, CPU) is the slow-marked
rehearsal at the bottom — ``make chip-smoke-rehearsal``."""

from __future__ import annotations

import copy
import json
import subprocess
import sys
from pathlib import Path

import pytest

import chip_smoke
from tests.helpers import make_node, make_pod

REPO = Path(__file__).resolve().parent.parent

NODES = [make_node("n0", cpu="2", memory="4Gi", pods=2), make_node("n1", cpu="4", memory="8Gi")]
PODS = [make_pod(f"p{i}", cpu="1", memory="1Gi") for i in range(3)]
SIZES = {
    "served_nodes": 2, "served_pods": 3, "served_min_bound": 2,
    "replay_nodes": 200, "replay_events": 800,
}
TPU = {"platform": "tpu", "device_kind": "TPU v5 lite", "device_count": 1}


def _export(placements=("n0", "n0", "n1")) -> dict:
    pods = []
    for pod, node in zip(copy.deepcopy(PODS), placements):
        verdict = {n["metadata"]["name"]: {"NodeResourcesFit": "passed"} for n in NODES}
        anno = {
            chip_smoke.FILTER_KEY: json.dumps(verdict),
            chip_smoke.FINAL_SCORE_KEY: json.dumps(
                {n: {"NodeResourcesFit": "50"} for n in verdict} if node else {}
            ),
        }
        if node:
            pod["spec"]["nodeName"] = node
            anno[chip_smoke.SELECTED_NODE_KEY] = node
        pod["metadata"]["annotations"] = anno
        pods.append(pod)
    return {"nodes": NODES, "pods": pods}


def _result(**replay) -> dict:
    return {
        "id": "j1", "state": "succeeded",
        "result": {"podsScheduled": 358, "unschedulableAttempts": 8, "steps": 7},
        "replay": {
            **TPU, "device_steps": 7, "fallback_steps": 0, "device_errors": 0,
            "watchdog_timeouts": 0, "breaker_tripped": False, "unsupported": {},
            **replay,
        },
    }


def test_milli_parses_the_quantities_the_clusters_use():
    assert chip_smoke.milli("250m") == 250
    assert chip_smoke.milli("2") == 2000
    assert chip_smoke.milli("64Mi") == 64 * 2**20 * 1000
    assert chip_smoke.milli("4Gi") == 4 * 2**30 * 1000
    assert chip_smoke.milli(None) == 0


def test_check_served_accepts_a_consistent_export():
    counts, fails = chip_smoke.check_served(_export(), NODES, PODS, min_bound=2)
    assert not fails
    assert counts == {"nodes": 2, "pods": 3, "annotated": 3, "bound": 3}


def test_check_served_flags_what_the_kernels_could_get_wrong():
    # Three 1-cpu pods on the 2-cpu / 2-pod node: over-committed.
    _, fails = chip_smoke.check_served(
        _export(("n0", "n0", "n0")), NODES, PODS, min_bound=2
    )
    assert any("over-committed" in f for f in fails), fails
    # Bound to a node that was never imported.
    _, fails = chip_smoke.check_served(
        _export(("n0", "ghost", "n1")), NODES, PODS, min_bound=2
    )
    assert any("unknown node" in f for f in fails), fails
    # Too few bound, and an unannotated pod.
    ex = _export(("n0", None, None))
    del ex["pods"][2]["metadata"]["annotations"]
    _, fails = chip_smoke.check_served(ex, NODES, PODS, min_bound=2)
    assert any("need at least 2" in f for f in fails), fails
    assert any("2 of 3 pods carry" in f for f in fails), fails
    # selected-node disagrees with the binding; annotation names a
    # foreign node; the bound node did not pass its filters.
    ex = _export()
    ex["pods"][0]["metadata"]["annotations"][chip_smoke.SELECTED_NODE_KEY] = "n1"
    ex["pods"][1]["metadata"]["annotations"][chip_smoke.FILTER_KEY] = '{"ghost":{}}'
    ex["pods"][2]["metadata"]["annotations"][chip_smoke.FILTER_KEY] = json.dumps(
        {"n1": {"NodeResourcesFit": "Insufficient cpu"}}
    )
    _, fails = chip_smoke.check_served(ex, NODES, PODS, min_bound=2)
    assert any("selected-node" in f for f in fails), fails
    assert any("outside the import" in f for f in fails), fails
    assert any("did not pass its filters" in f for f in fails), fails


def test_check_replay_fails_when_containment_fired():
    counts, fails = chip_smoke.check_replay(_result(), expect=(358, 8))
    assert not fails and counts["device_steps"] == counts["steps"] == 7
    for sick, needle in [
        ({"fallback_steps": 3, "device_steps": 4}, "fallback_steps"),
        ({"breaker_tripped": True}, "breaker_tripped"),
        ({"device_errors": 1}, "device_errors"),
        ({"watchdog_timeouts": 1}, "watchdog_timeouts"),
        ({"unsupported": {"device_error": 3}}, "unsupported"),
        ({"device_steps": 6}, "device_steps"),
    ]:
        _, fails = chip_smoke.check_replay(_result(**sick), expect=(358, 8))
        assert any(needle in f for f in fails), (sick, fails)
    _, fails = chip_smoke.check_replay(_result(), expect=(2524, 471))
    assert any("locked" in f for f in fails), fails
    _, fails = chip_smoke.check_replay(
        {"state": "failed", "message": "boom"}, expect=None
    )
    assert fails


def test_check_device_is_not_fooled_by_the_cpu_fallback():
    assert not chip_smoke.check_device(TPU, rehearsal=False)
    cpu = {"platform": "cpu", "device_kind": "cpu", "device_count": 1}
    assert chip_smoke.check_device(cpu, rehearsal=False)
    assert not chip_smoke.check_device(cpu, rehearsal=True)
    assert chip_smoke.check_device(TPU, rehearsal=True)  # rehearsal is CPU-only
    unknown = {"platform": None, "device_kind": None, "device_count": None}
    assert chip_smoke.check_device(unknown, rehearsal=False)


class FakeServer(chip_smoke.Server):
    """Canned answers in place of the simulator child."""

    ident = TPU
    export = None
    result = None
    attempts = 3

    def __init__(self, env):
        self.port = 0

    def request(self, method, path, body=None, timeout=300.0):
        doc: dict = {}
        if path == "/api/v1/metrics":
            doc = {
                "process": dict(self.ident),
                "counters": {"scheduling_attempts": self.attempts},
            }
        elif path == "/api/v1/export":
            doc = self.export if self.export is not None else _export()
        elif path == "/api/v1/jobs":
            return 202, b'{"id": "j1", "state": "queued"}'
        elif path == "/api/v1/jobs/j1":
            doc = {"id": "j1", "state": "succeeded"}
        elif path == "/api/v1/jobs/j1/result":
            doc = self.result if self.result is not None else _result()
        return 200, json.dumps(doc).encode()

    def exit_code(self):
        return None

    def stop(self):
        pass

    def stderr_tail(self, lines=40):
        return "(fake server)"


def _main(monkeypatch, capsys, **attrs) -> "tuple[int, str]":
    fake = type("Fake", (FakeServer,), attrs)
    monkeypatch.setattr(chip_smoke, "Server", fake)
    monkeypatch.setattr(chip_smoke, "FULL_SIZES", SIZES)
    monkeypatch.setattr(
        chip_smoke, "generate_inputs",
        lambda seed, sizes: {
            "nodes": NODES, "pods": PODS,
            "job": {"spec": {"scenario": {"operations": []}}},
        },
    )
    rc = chip_smoke.main(["--seed", "0"])
    return rc, capsys.readouterr().out


def test_main_prints_detail_then_verdict_on_a_healthy_chip(monkeypatch, capsys):
    rc, out = _main(monkeypatch, capsys)
    assert rc == 0
    detail, last = out.strip().splitlines()
    # The chip check's line: exactly these keys, last on stdout.
    assert json.loads(last) == {
        "ok": True,
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
    }
    doc = json.loads(detail)
    assert doc["ok"] is True and doc["rehearsal"] is False
    assert doc["platform"] == "tpu" and doc["device_count"] == 1
    assert doc["phases"]["served"]["bound"] == 3
    assert doc["phases"]["replay"]["device_steps"] == 7
    assert doc["reduced"] and "dir" in doc["compile_cache"]


@pytest.mark.parametrize(
    "attrs",
    [
        {"result": _result(fallback_steps=3, device_steps=4)},
        {"result": _result(breaker_tripped=True)},
        {"result": _result(platform="cpu")},
        {"ident": {"platform": "cpu", "device_kind": "cpu", "device_count": 1}},
        {"export": _export(("n0", "n0", "n0"))},
        {"attempts": 0},  # pods never attempted: the served deadline
    ],
    ids=["fallbacks", "breaker", "job-on-cpu", "server-on-cpu", "overcommit", "deadline"],
)
def test_main_exits_nonzero_and_prints_nothing(monkeypatch, capsys, attrs):
    monkeypatch.setattr(chip_smoke, "SERVED_CAP_S", 0.0)
    rc, out = _main(monkeypatch, capsys, **attrs)
    assert rc != 0
    assert out == ""


def test_poll_reports_a_dead_server():
    class Dead:
        def exit_code(self):
            return 1

    with pytest.raises(chip_smoke.SmokeFailure, match="exited with code 1"):
        chip_smoke.poll(Dead(), "anything", 1e18, lambda: None)


@pytest.mark.slow
def test_rehearsal_end_to_end():
    """The real thing at a tiny size: one simulator child on the CPU,
    both phases, the locked 800-event counts."""
    proc = subprocess.run(
        [sys.executable, str(REPO / "chip_smoke.py"), "--rehearsal", "--seed", "0"],
        capture_output=True, text=True, timeout=600, cwd=REPO,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    (line,) = proc.stdout.strip().splitlines()  # detail only: no verdict off the chip
    doc = json.loads(line)
    assert doc["ok"] and doc["rehearsal"] is True and doc["platform"] == "cpu"
    replay = doc["phases"]["replay"]
    assert (replay["podsScheduled"], replay["unschedulableAttempts"]) == (358, 8)
    assert replay["device_steps"] == replay["steps"] and replay["fallback_steps"] == 0
