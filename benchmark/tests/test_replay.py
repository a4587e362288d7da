"""The plain sequential replay (``replay.py``) that judges the job cells: it
reproduces the repo's behaviour locks with nothing of the program in it, the
order in which a step's pods arrive does not move it, its control — the same
replay with the resource scores in bfloat16 — counts otherwise at the cells'
own sizes, and what it does not cover raises instead of passing."""

import copy

import pytest

import generators
import replay
from kinds import churn

CELL = dict(n_nodes=2000, ops_per_step=100)


def counts(ops, precision="exact"):
    got = replay.replay(ops, max_pods_per_pass=1024, precision=precision)
    return [got["eventsApplied"], got["podsScheduled"], got["unschedulableAttempts"]]


@pytest.mark.parametrize("n_events,lock", [(6000, [6430, 2524, 471]), (50000, [54986, 52781, 42829])])
def test_the_replay_reproduces_the_behaviour_locks(n_events, lock):
    ops = churn.churn_operations(0, n_events=n_events, **CELL)
    assert counts(ops) == lock


@pytest.mark.parametrize("base", [0, 1, 2, 3])
def test_arrival_order_does_not_move_the_counts_and_the_control_does(base):
    ops = churn.churn_operations(base, n_events=6000, **CELL)
    want = counts(ops)
    for seed in (5, 2147483693):
        assert counts(generators.shuffle_operations(seed, ops)) == want
    assert counts(ops, "bf16") != want


def test_the_control_counts_otherwise_on_the_whole_stream():
    ops = churn.churn_operations(0, n_events=50000, **CELL)
    got = counts(ops, "bf16")
    assert got[0] == 54986 and got[1:] != [52781, 42829]


def test_what_is_not_covered_raises():
    ops = churn.churn_operations(0, n_nodes=20, n_events=200, ops_per_step=50)
    tainted = copy.deepcopy(ops)
    tainted[0]["createOperation"]["object"]["spec"]["taints"] = [
        {"key": "k", "value": "v", "effect": "NoSchedule"}]
    with pytest.raises(replay.NotCovered):
        replay.replay(tainted)
    pod = next(op for op in ops if op.get("createOperation", {}).get("object", {}).get("kind") == "Pod")
    selective = copy.deepcopy(ops)   # a priority, refused until PR 31, is covered now (test_preemption.py)
    selective[ops.index(pod)]["createOperation"]["object"]["spec"]["nodeSelector"] = {"disktype": "ssd"}
    with pytest.raises(replay.NotCovered):
        replay.replay(selective)


def test_the_replay_agrees_with_the_program_step_by_step_and_pod_by_pod():
    """How the reference was validated (PERF.md section 2), at a size a test
    holds: the program's per-pass path, in process, against the replay —
    every step's counts and every live pod's node.  Only this test imports
    the program; ``replay.py`` does not."""
    import os
    import sys
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))
    runner_mod = pytest.importorskip("ksim_tpu.scenario")
    for base in (0, 5):
        size = dict(n_nodes=200, n_events=1600, ops_per_step=100)
        runner = runner_mod.ScenarioRunner(max_pods_per_pass=1024, pod_bucket_min=128)
        res = runner.run(runner_mod.churn_scenario(base, **size))
        got = replay.replay(churn.churn_operations(base, **size), max_pods_per_pass=1024)
        assert got["steps"] == [(s.scheduled, s.unschedulable) for s in res.steps]
        assert got["placements"] == {
            p["metadata"]["name"]: p["spec"].get("nodeName")
            for p in runner.store.list("pods", copy_objs=False)}
