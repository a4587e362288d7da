"""The plain sequential replay (``replay.py``) on the ``burst-5k`` deployment:
every node in step 0, the whole backlog in step 1, one pass with no cap.  Its
counts are the third behaviour lock — at the cell's own size and at the
rehearsal size — and do not move with the order in which the pods arrive.
The control (resource scores in bfloat16) moves most placements and none of
the counts: the filters decide them.  So the configuration holds every job to
the replay's placements too, through the digest the job's result document
carries (``guarantees.replay_equals.placements_digest``, ``placements.py``),
and that comparison is the one the control fails — judged here by
``run.judge``, as a run is.  The numbers in the data files are the replay's,
not the program's."""

import pytest

import checks
import generators
import placements
import replay
import run as harness
from kinds import churn

CELL = "burst-5k_onestep"


def cell(rehearsal: bool) -> dict:
    """The cell as a run loads it (``run.load_cell``: data files, rehearsal overlays)."""
    return harness.load_cell(harness.load("BENCHMARK.json"), CELL, rehearsal)


def operations(rehearsal: bool) -> list:
    c = cell(rehearsal)
    gen = c["config"]["generator"]
    assert "maxPodsPerPass" not in c["config"]["simulator"]
    return churn.churn_operations(
        gen["base_seed"], n_nodes=gen["n_nodes"], n_events=c["traffic"]["events"],
        ops_per_step=gen["ops_per_step"], pod_create_frac=gen["pod_create_frac"],
        pod_delete_frac=gen["pod_delete_frac"])


@pytest.mark.parametrize("rehearsal", [True, False])
def test_the_replay_gives_the_cells_lock(rehearsal):
    ops = operations(rehearsal)
    steps = [op["step"] for op in ops]
    n_nodes = steps.count(0)
    assert set(steps) == {0, 1} and all("createOperation" in op for op in ops)
    lock = cell(rehearsal)["locks"]["0"]
    got = replay.replay(ops, max_pods_per_pass=None)
    assert [got["eventsApplied"], got["podsScheduled"], got["unschedulableAttempts"]] == [len(ops)] + lock
    assert got["steps"] == [(0, 0), tuple(lock)]
    assert sum(lock) == len(ops) - n_nodes  # every pod attempted once, in one pass
    assert sum(1 for node in got["placements"].values() if node) == lock[0]


def test_the_locks_are_the_expected_numbers():
    assert cell(False)["locks"] == {"0": [9390, 610]} and cell(True)["locks"] == {"0": [384, 16]}


@pytest.mark.parametrize("rehearsal", [True, False])
def test_arrival_order_does_not_move_counts_or_placements(rehearsal):
    ops = operations(rehearsal)
    want = replay.replay(ops, max_pods_per_pass=None)
    for seed in (7, 2147483693):
        got = replay.replay(generators.shuffle_operations(seed, ops), max_pods_per_pass=None)
        assert got["steps"] == want["steps"] and got["placements"] == want["placements"]


@pytest.mark.parametrize("rehearsal", [True, False])
def test_the_configurations_digest_is_the_replays(rehearsal):
    got = replay.replay(operations(rehearsal), max_pods_per_pass=None)
    want = cell(rehearsal)["guarantees"]["replay_equals"]["placements_digest"]
    assert placements.digest(got["placements"]) == want


def test_the_two_sizes_have_two_digests():
    full, small = (cell(r)["guarantees"]["replay_equals"]["placements_digest"] for r in (False, True))
    assert full != small and len(full) == len(small) == 64


def as_job_document(c: dict, got: dict, steps: int) -> dict:
    """A job's result document that says of the device path all that the
    configuration guarantees, with ``got``'s counts and placements in it:
    the replay, exact or at bfloat16, put in the program's place."""
    block = dict(c["guarantees"]["replay_equals"], device_steps=steps,
                 placements_digest=placements.digest(got["placements"]))
    result = dict({k: got[k] for k in checks.COUNT_KEYS}, steps=steps)
    return {"state": "succeeded", "result": result, "replay": block}


@pytest.mark.parametrize("rehearsal", [True, False])
def test_the_control_is_not_correct_and_the_digest_is_what_tells(rehearsal):
    """With the resource scores in bfloat16 most pods land elsewhere (8,941 of
    10,000 at the cell's size) and the three counts stay.  Judged like a run:
    the exact replay in the program's place is correct; the control fails,
    by ``replay.placements_digest`` and by nothing else."""
    c, ops = cell(rehearsal), operations(rehearsal)
    inputs = {"operations": ops, "steps": 2}
    device = {"platform": c["platform"], "count": 1}

    def failing(precision):
        got = replay.replay(ops, max_pods_per_pass=None, precision=precision)
        win = {"counted": [{"doc": as_job_document(c, got, 2)}], "failed": 0}
        out = harness.judge(c, inputs, 0, win, [], [], device, {"platform": c["platform"]})
        return got, {x["name"] for x in out if not x["ok"]}

    want, none = failing("exact")
    got, names = failing("bf16")
    assert none == set() and names == {"replay.placements_digest"}
    assert got["steps"] == want["steps"]
    moved = sum(1 for name, node in want["placements"].items() if got["placements"][name] != node)
    assert moved > len(want["placements"]) // 2
    if not rehearsal:
        assert moved == 8941
