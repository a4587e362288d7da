"""The cell ``churn-2k-default_prefix20k`` (PR 42): ``churn-2k``'s cluster and
stream under the default scheduler configuration, as data
(``configs/churn-2k-default.json``, ``traffic/prefix20k.json``,
``cells/churn-2k-default_prefix20k.json``; generator kind ``churn``, plain
reference ``references/sampled_zoned.py``).  Pinned here: that the files load
and say what the issue names; that the stream is ``churn-2k``'s; that the locks
and the ``replay_equals`` numbers in the data files are the reference's; the
cell judged by ``run.judge`` as a run is; and the two controls — the same
reference walking in name order, and the same reference at bfloat16 — each
failed by the digest and by ``nodes_visited``."""

import json

import pytest

import checks
import placements
import run as harness
from kinds import churn
from references import sampled_zoned

CELL = "churn-2k-default_prefix20k"
SUMS = ("sampled_attempts", "nodes_visited", "nodes_scored", "sampling_zones")


def cell(rehearsal: bool) -> dict:
    return harness.load_cell(harness.load("BENCHMARK.json"), CELL, rehearsal)


def inputs_of(rehearsal: bool, seed: int = 0) -> dict:
    c = cell(rehearsal)
    return harness.build_inputs(c["config"], c["traffic"], seed)


def test_the_cell_is_what_the_issue_names():
    bench = harness.load("BENCHMARK.json")
    entry = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert entry["chips"] == 1   # wherever it stands: later PRs append cells after it
    assert (entry["config"], entry["traffic"]) == ("churn-2k-default", "prefix20k")
    listed = next(cfg for cfg in bench["configs"] if cfg["name"] == "churn-2k-default")
    assert listed["reduced"] == []
    c = cell(False)
    flagship = harness.load("benchmark/configs/churn-2k.json")
    assert c["config"]["generator"] == flagship["generator"]
    assert c["config"]["simulator"] == dict(flagship["simulator"], nodeSampling=True)
    assert c["config"]["reduced"] == [] and c["config"]["architecture"] is None
    assert c["config"]["precision"] == "float32" and c["reference"] is sampled_zoned
    assert len(c["config"]["source"]) <= 200 and c["config"]["source"] == listed["source"]
    traffic = c["traffic"]
    assert (traffic["loop"], traffic["clients"], traffic["events"]) == ("closed", 1, 20000)
    assert (traffic["warmup_min"], traffic["warmup_max"]) == (2, 3)
    # Only keys a job's result has had since PR 40, so that the parent runs the cell.
    assert set(c["guarantees"]["replay_equals"]) == {
        "fallback_steps", "device_errors", "watchdog_timeouts", "breaker_tripped", "unsupported",
        "sampled_attempts", "sampled_by_rank", "nodes_visited", "nodes_scored", "sampling_zones",
        "placements_digest"}
    # The two counts this PR adds are metrics of this cell alone, read from the result document.
    for name, key in (("sampled_by_rank_steps_per_job", "sampled_by_rank_steps"),
                      ("walk_rows_built_per_job", "walk_rows_built")):
        metric = next(m for m in bench["per_layer"] if m["name"] == name)
        assert metric["workloads"] == [CELL] and metric["better"] == "lower"
        assert harness.load(f"benchmark/metrics/{name}.json") == {
            "name": name, "kind": "job_result", "path": ["replay", key]}


def test_the_stream_is_the_flagships_prefix():
    """The same operations as ``churn-2k``'s, 20,000 events of them: 2,000
    nodes in step 0, then 180 steps of 100 operations, a node replaced (one
    delete, one create more) in every one of them."""
    inputs = inputs_of(False)
    ops = inputs["operations"]
    whole = churn.churn_operations(0, n_nodes=2000, n_events=50000, ops_per_step=100)
    # ``--seed`` orders the arrivals inside a step and nothing else.
    key = lambda op: (op["step"], json.dumps(op, sort_keys=True))
    assert sorted(ops, key=key) == sorted(whole[:len(ops)], key=key)
    assert (len(ops), inputs["steps"], inputs["units"]) == (21860, 181, 20000)
    per_step: dict = {}
    for op in ops:
        kind = (op.get("deleteOperation", {}).get("typeMeta") or
                op.get("createOperation", {}).get("object"))["kind"]
        if kind == "Node" and "deleteOperation" in op:
            per_step[op["step"]] = per_step.get(op["step"], 0) + 1
    assert sorted(per_step) == list(range(1, 181)) and sum(per_step.values()) == 1860


@pytest.mark.parametrize("rehearsal", [True, False], ids=["rehearsal", "full"])
def test_the_data_files_hold_the_references_numbers(rehearsal):
    c, inputs = cell(rehearsal), inputs_of(rehearsal, seed=2147483693)
    got = sampled_zoned.replay(inputs["operations"], max_pods_per_pass=1024)
    assert [got[k] for k in checks.COUNT_KEYS][1:] == c["locks"]["0"]
    assert got["eventsApplied"] == (1747 if rehearsal else 21860)
    equals = c["guarantees"]["replay_equals"]
    assert placements.digest(got["placements"]) == equals["placements_digest"]
    for key in SUMS:
        assert got[key] == equals[key], key
    # Every attempt samples, and every one of them under node churn.
    assert equals["sampled_by_rank"] == got["sampled_attempts"] == sum(c["locks"]["0"])
    assert got["nodes_visited"] > got["nodes_scored"]
    assert len(got["placements"]) == (551 if rehearsal else 7150)


def as_job_document(c: dict, got: dict, steps: int) -> dict:
    """A job's result document that says of the device path all that the
    configuration guarantees, with ``got``'s counts, sums and placements."""
    block = dict(c["guarantees"]["replay_equals"], device_steps=steps,
                 placements_digest=placements.digest(got["placements"]),
                 sampled_by_rank=got["sampled_attempts"], **{k: got[k] for k in SUMS})
    result = dict({k: got[k] for k in checks.COUNT_KEYS}, steps=steps)
    return {"state": "succeeded", "result": result, "replay": block}


def judged(c: dict, inputs: dict, doc: dict) -> set:
    """The names of the comparisons that fail, judged as a run is."""
    win = {"counted": [{"doc": doc}], "failed": 0}
    device = {"platform": c["platform"], "count": 1}
    out = harness.judge(c, inputs, 0, win, [], [], device, {"platform": c["platform"]})
    return {x["name"] for x in out if not x["ok"]}


CONTROLS = [   # the reference's keyword, its podsScheduled / unschedulableAttempts, nodes_visited
    ({"interleave": False}, [974, 92], 130897),
    ({"precision": "bf16"}, [960, 86], 136468),
]


def test_the_cell_is_correct_with_the_reference_in_the_programs_place():
    c, inputs = cell(True), inputs_of(True)
    exact = sampled_zoned.replay(inputs["operations"], max_pods_per_pass=1024)
    assert judged(c, inputs, as_job_document(c, exact, inputs["steps"])) == set()
    by_slots = as_job_document(c, exact, inputs["steps"])
    by_slots["replay"]["sampled_by_rank"] = 0
    assert judged(c, inputs, by_slots) == {"replay.sampled_by_rank"}


@pytest.mark.parametrize("kw, counts, visited", CONTROLS, ids=["name-order", "bf16"])
def test_a_control_in_the_programs_place_is_not(kw, counts, visited):
    """At the rehearsal size, by ``run.judge``: a scheduler that walks in name
    order, and one that scores in bfloat16 (here pods and nodes differ, so the
    precision shows), each place most pods elsewhere: failed by the digest and
    by ``nodes_visited``, and by the lock and the counts besides."""
    c, inputs = cell(True), inputs_of(True)
    exact = sampled_zoned.replay(inputs["operations"], max_pods_per_pass=1024)
    control = sampled_zoned.replay(inputs["operations"], max_pods_per_pass=1024, **kw)
    assert [control[k] for k in checks.COUNT_KEYS][1:] == counts != c["locks"]["0"]
    assert control["nodes_visited"] == visited != exact["nodes_visited"]
    moved = sum(control["placements"][p] != n for p, n in exact["placements"].items())
    assert moved > 400
    failed = judged(c, inputs, as_job_document(c, control, inputs["steps"]))
    assert {"replay.placements_digest", "replay.nodes_visited"} <= failed
    assert failed == {"replay.placements_digest", "replay.nodes_visited", "replay.nodes_scored",
                      "replay.sampled_attempts", "replay.sampled_by_rank", "job.locked_counts",
                      "job.counts_vs_reference_replay"}
