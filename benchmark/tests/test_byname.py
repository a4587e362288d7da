"""Generator kinds and plain references are files found by the name the
configuration's data gives (``byname.py``, ``kinds/``, ``references/``): a
name no file provides ends the run non-zero before a server starts, and a
reference that a configuration names is the one that judges its jobs."""

import os
import sys

import pytest

import byname
import client
import references
import replay
import run as harness

CELL = "churn-2k_prefix6k"


def with_config(monkeypatch, change):
    """``run.load`` with ``change`` applied to every configuration it reads."""
    real = harness.load

    def altered(rel):
        doc = real(rel)
        if rel.startswith("benchmark/configs/"):
            change(doc)
        return doc

    monkeypatch.setattr(harness, "load", altered)


def no_server(monkeypatch):
    def refuse(*args, **kwargs):
        raise AssertionError("a server was started")
    monkeypatch.setattr(client.Server, "__init__", refuse)


@pytest.mark.parametrize("change", [
    lambda doc: doc["generator"].update(kind="nonesuch"),
    lambda doc: doc["generator"].update(kind="../run"),
    lambda doc: doc["generator"].pop("kind"),
    lambda doc: doc.update(reference="nonesuch"),
    lambda doc: doc.update(reference="__init__"),
], ids=["kind", "kind-as-a-path", "no-kind", "reference", "reference-underscored"])
def test_an_unknown_kind_or_reference_ends_the_run_before_a_server_starts(monkeypatch, capsys, change):
    with_config(monkeypatch, change)
    no_server(monkeypatch)
    rc = harness.main(["--workload", CELL, "--seed", "1", "--trace", "0", "--rehearsal"])
    out = capsys.readouterr()
    assert rc != 0 and out.out == "" and "FAILED before the server starts" in out.err
    assert "this benchmark has" in out.err   # the names there are


def test_every_kind_and_the_default_reference_are_found():
    for kind in ("churn", "cluster", "sperf"):
        assert callable(byname.load("kinds", kind, "inputs").inputs)
    assert harness.reference_of({}) is replay
    with pytest.raises(byname.Unknown):
        byname.load("kinds", "churn", "no_such_function")


def test_a_reference_by_name_judges_the_configurations_jobs(monkeypatch, tmp_path):
    """A stub in a ``references`` directory, named by the configuration: the
    cell's counts come from it, and ``judge`` holds a job to them."""
    (tmp_path / "references").mkdir()
    (tmp_path / "references" / "stub.py").write_text(
        "from replay import NotCovered\n"
        "def replay(operations, *, precision='exact', max_pods_per_pass=None):\n"
        "    if precision != 'exact':\n"
        "        raise NotCovered('the stub knows one precision')\n"
        "    return {'eventsApplied': len(operations), 'podsScheduled': 7,\n"
        "            'unschedulableAttempts': max_pods_per_pass, 'placements': {}}\n")
    (tmp_path / "kinds").symlink_to(os.path.join(byname.HERE, "kinds"))
    monkeypatch.setattr(byname, "HERE", str(tmp_path))
    monkeypatch.setattr(references, "__path__", [str(tmp_path / "references")])
    monkeypatch.delitem(sys.modules, "references.stub", raising=False)
    with_config(monkeypatch, lambda doc: doc.update(reference="stub"))
    c = harness.load_cell(harness.load("BENCHMARK.json"), CELL, True)
    assert c["reference"].__name__ == "references.stub"
    inputs = harness.build_inputs(c["config"], c["traffic"], 0)
    n = len(inputs["operations"])
    assert harness.replayed_counts(c, inputs) == [n, 7, 1024]
    assert harness.replayed_counts(c, inputs, "bf16") is None   # NotCovered: no job equals it
    doc = {"state": "succeeded", "result": {"steps": inputs["steps"], "eventsApplied": n,
                                            "podsScheduled": 7, "unschedulableAttempts": 1024},
           "replay": dict(c["guarantees"]["replay_equals"], device_steps=inputs["steps"])}
    win = {"counted": [{"doc": doc}], "failed": 0}
    device = {"platform": "cpu", "count": 1}
    out = harness.judge(dict(c, locks={}), inputs, 0, win, [], [], device, {"platform": "cpu"})
    assert all(x["ok"] for x in out), [x for x in out if not x["ok"]]
    doc["result"]["podsScheduled"] = 8
    out = harness.judge(dict(c, locks={}), inputs, 0, win, [], [], device, {"platform": "cpu"})
    assert [x["name"] for x in out if not x["ok"]] == ["job.counts_vs_reference_replay"]


def test_seconds_default_to_the_benchmarks_run_seconds(monkeypatch):
    """``run.py --workload <cell>`` with no ``--seconds`` measures for
    ``run_seconds`` (51), not for 10 s as before PR 31."""
    seen = {}

    def drive(server, c, inputs, seed, seconds, slice_=None):
        seen["seconds"] = seconds
        raise client.BenchFailure("seen enough")

    class Quiet:
        proc = None
        def stop(self): pass
        def log_tail(self): return ""

    monkeypatch.setattr(harness, "drive", drive)
    monkeypatch.setattr(harness, "start_server", lambda c, trace: (Quiet(), str("/nonexistent")))
    assert harness.main(["--workload", CELL, "--rehearsal"]) == 1
    assert seen["seconds"] == harness.load("BENCHMARK.json")["run_seconds"] == 51
    assert harness.main(["--workload", CELL, "--rehearsal", "--seconds", "2"]) == 1 and seen["seconds"] == 2
