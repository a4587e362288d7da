"""The cell ``sperf-5k-preempt_basic`` (PR 32): upstream's ``PreemptionBasic`` at
``5000Nodes`` as data (``configs/sperf-5k-preempt.json``, ``traffic/basic.json``,
``cells/sperf-5k-preempt_basic.json``; generator kind ``sperf``, no Python
file holds a size).  Pinned here: the bytes of its request body at two seeds
and both sizes; that the numbers in the data files are the plain replay's;
the cell judged by ``run.judge`` as a run is; and a control that ``correct``
sees.  The control cannot be bfloat16 (all pods and nodes of this workload are
alike: ``test_preemption.py``), so it is the plain replay with one of
DefaultPreemption's guarantees broken — nominated pods not counted in — which
reads other counts and another digest."""

import hashlib

import pytest

import checks
import placements
import replay
import run as harness

CELL = "sperf-5k-preempt_basic"

PINS = [   # rehearsal, seed, bytes, sha256 of inputs["body"]
    (False, 0, 11987158, "d72bf4108d4862ce011cdda818e4b68945739310a7de5df9f0302b33f72b5039"),
    (False, 1, 11987158, "fa08816abe6626aece15c16b4545ddb5e0ea63f12b3db689abe83fdb14354452"),
    (True, 0, 12388, "9b507992e2346bf8713817e4b8ecfaee37e19e44998c7c550a1b6006dee50496"),
    (True, 1, 12388, "494238a9d28e4f13c0ab55d70bc5e34fcb03b5a0c6cf20bc2dfc6f4f5eaa5b3f"),
]


def cell(rehearsal: bool) -> dict:
    return harness.load_cell(harness.load("BENCHMARK.json"), CELL, rehearsal)


def inputs_of(rehearsal: bool, seed: int = 0) -> dict:
    c = cell(rehearsal)
    return harness.build_inputs(c["config"], c["traffic"], seed)


@pytest.mark.parametrize("rehearsal,seed,size,sha", PINS,
                         ids=[f"{'rehearsal' if p[0] else 'full'}-{p[1]}" for p in PINS])
def test_the_request_body_is_pinned(rehearsal, seed, size, sha):
    body = inputs_of(rehearsal, seed)["body"]
    assert len(body) == size and hashlib.sha256(body).hexdigest() == sha


def test_the_shapes_are_upstreams_and_nothing_is_cut():
    c = cell(False)
    assert c["config"]["reduced"] == [] and c["config"]["architecture"] is None
    assert "maxPodsPerPass" not in c["config"]["simulator"]
    assert c["config"]["simulator"] == {"deviceReplay": True, "preemption": True, "podBucketMin": 128}
    ops = inputs_of(False)["operations"]
    kinds = {}
    for op in ops:
        obj = op["createOperation"]["object"]
        name = obj["metadata"]["name"].rsplit("-", 1)[0]
        kinds.setdefault((op["step"], name), []).append(obj)
    assert {k: len(v) for k, v in kinds.items()} == {
        (0, "node-default"): 5000, (1, "pod-low-priority"): 20000,
        (2, "pod-high-priority"): 5000, (3, "pod-default"): 1}
    node = kinds[0, "node-default"][0]["status"]["allocatable"]
    assert (node["cpu"], node["memory"], node["pods"]) == ("4", "32Gi", "110")
    low, high = kinds[1, "pod-low-priority"][0]["spec"], kinds[2, "pod-high-priority"][0]["spec"]
    assert low["containers"][0]["resources"]["requests"]["cpu"] == "900m" and not low.get("priority")
    assert high["containers"][0]["resources"]["requests"]["cpu"] == "3000m" and high["priority"] == 10


def test_the_data_files_hold_the_replays_numbers_at_the_rehearsal_size():
    c, inputs = cell(True), inputs_of(True, seed=2147483693)
    got = replay.replay(inputs["operations"])
    assert [got[k] for k in checks.COUNT_KEYS] == [31] + c["locks"]["0"] == [31, 26, 5]
    assert len(got["evicted"]) == 15 and inputs["steps"] == 4
    assert placements.digest(got["placements"]) == c["guarantees"]["replay_equals"]["placements_digest"]
    full = cell(False)
    assert full["locks"] == {"0": [25001, 5000]}
    assert full["guarantees"]["replay_equals"]["placements_digest"].startswith("5412064edab3")
    assert full["traffic"]["workload"] == "5000Nodes" and c["traffic"]["workload"] == "5Nodes"


def as_job_document(c: dict, got: dict, steps: int) -> dict:
    """A job's result document that says of the device path all that the
    configuration guarantees, with ``got``'s counts and placements in it."""
    block = dict(c["guarantees"]["replay_equals"], device_steps=steps,
                 placements_digest=placements.digest(got["placements"]))
    result = dict({k: got[k] for k in checks.COUNT_KEYS}, steps=steps)
    return {"state": "succeeded", "result": result, "replay": block}


def judged(c: dict, inputs: dict, got: dict) -> set:
    """The names of the comparisons that fail, judged as a run is."""
    win = {"counted": [{"doc": as_job_document(c, got, inputs["steps"])}], "failed": 0}
    device = {"platform": c["platform"], "count": 1}
    out = harness.judge(c, inputs, 0, win, [], [], device, {"platform": c["platform"]})
    return {x["name"] for x in out if not x["ok"]}


def nominees_not_counted(monkeypatch):
    """Break ``RunFilterPluginsWithNominatedPods`` in the plain replay: no
    pod counts another's nomination, in the pass or in the dry run."""
    has, level_sum = replay.Cluster.has, replay.Cluster.level_sum
    monkeypatch.setattr(replay.Cluster, "has", lambda self, which, keep:
                        which != "nominated" and has(self, which, keep))
    monkeypatch.setattr(replay.Cluster, "level_sum", lambda self, which, keep, but=None: {
        column: per_node * (which != "nominated")
        for column, per_node in level_sum(self, which, keep, but).items()})


def test_the_cell_is_correct_with_the_replay_in_the_programs_place_and_the_control_is_not(monkeypatch):
    """At the rehearsal size, by ``run.judge``.  The control: with nominated
    pods not counted in, the second preemptor of the pass sees the room the
    first one's victims left and binds into it: pods scheduled in the
    preemptors' own pass, fewer victims, another digest — and ``correct``
    says so by the lock, by the counts against the reference and by the
    digest."""
    c, inputs = cell(True), inputs_of(True)
    exact = replay.replay(inputs["operations"])
    assert judged(c, inputs, exact) == set()
    with monkeypatch.context() as m:
        nominees_not_counted(m)
        control = replay.replay(inputs["operations"])
    assert control["steps"] != exact["steps"] and control["evicted"] != exact["evicted"]
    assert judged(c, inputs, control) == {
        "job.locked_counts", "job.counts_vs_reference_replay", "replay.placements_digest"}


def test_the_control_at_500_nodes_moves_counts_victims_and_digest(monkeypatch):
    c = cell(False)
    traffic = dict(c["traffic"], workload="500Nodes")
    ops = harness.build_inputs(c["config"], traffic, 0)["operations"]
    exact = replay.replay(ops)
    assert [exact[k] for k in checks.COUNT_KEYS] == [3001, 2501, 500] and len(exact["evicted"]) == 1500
    with monkeypatch.context() as m:
        nominees_not_counted(m)
        control = replay.replay(ops)
    assert [control[k] for k in checks.COUNT_KEYS] != [exact[k] for k in checks.COUNT_KEYS]
    assert len(control["evicted"]) < len(exact["evicted"])
    assert placements.digest(control["placements"]) != placements.digest(exact["placements"])
