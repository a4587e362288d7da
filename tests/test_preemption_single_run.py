"""The device victim search of a node-local window: ONE run of the filter
chain a hypothetical state, and the candidate cut in slot space (PR 44).

Upstream runs the chain twice a state (``RunFilterPluginsWithNominatedPods``:
with the nominated pods of the pod's priority or above counted in, then as
the node stands).  In a node-local window (``_SegmentStatics.local``: no
DoNotSchedule spread constraint, no required pod (anti-)affinity) the device
program runs it once, with the nominees counted in
(``engine/replay.py _preempt_search`` has the argument); the per-pass path
(``scheduler/preemption.py``) keeps both runs and is the witness here, beside
the expectations, which are derived BY HAND from upstream's definitions in
each scenario's docstring (conventions: ``tests/test_preemption_upstream.py``).

The first ``want`` candidates in name order are kept by one sort of the
node axis (a slot's name rank against the ``want``-th smallest among the
candidates); the cut's cases run on a slot table whose order is NOT the name
order and that holds a dead slot.
"""

from __future__ import annotations

import json
import os
import sys

import pytest

from ksim_tpu.engine import core
from ksim_tpu.scheduler import preemption
from tests.helpers import make_node
from tests.test_preemption_upstream import PATHS, _ops, _pod, _run, _runner, _traced

HOST = "kubernetes.io/hostname"


def _node(name, cpu):
    return ("nodes", make_node(name, cpu=cpu, memory="8Gi", labels={HOST: name}))


def _bound(name, cpu, priority, on):
    return ("pods", _pod(name, cpu, priority, node=on))


def _pending(name, cpu, priority, *, spread=False):
    """``spread``: the pod carries a DoNotSchedule spread constraint that
    never bites (its selector matches no pod), which is enough to take the
    window off the node axis: the search walks (``_victims_by_walk``)."""
    p = _pod(name, cpu, priority)
    if spread:
        p["spec"]["topologySpreadConstraints"] = [{
            "maxSkew": 5, "topologyKey": HOST, "whenUnsatisfiable": "DoNotSchedule",
            "labelSelector": {"matchLabels": {"app": "nothing-carries-this"}},
        }]
    return ("pods", p)


# -- (a), (b): what a nominee decides ------------------------------------------


def _nominee_decides_with_all_lower_pods_gone(walk):
    """Nodes a (5,100m) with va (3 cpu) and vx (2 cpu), b (4 cpu) with vb
    (4 cpu, priority 5); the others priority 0.  One pass: p1 (3 cpu,
    priority 10) fits nowhere.  a: both off, it fits; va back: 6 > 5.1, a
    victim; vx back: 5, reprieved.  b: vb off, it fits; vb back: 7 > 4, a
    victim.  Highest victim priority 0 on a against 5 on b: a, victim va.
    p2 (4 cpu, priority 10): a's one lower pod is vx.  vx off: AS THE NODE
    STANDS 4 <= 5.1, and vx back 6 > 5.1 would make a a candidate with a
    victim of priority 0, ahead of b; WITH p1 COUNTED IN 3 + 4 > 5.1: a
    fails with every lower pod gone and is no candidate.  b: vb off: 4 <= 4;
    vb is the victim.  Next pass: p1 binds on a (2 + 3), p2 on b (4 of 4),
    the 100m tail on a (5.1 of 5.1)."""
    steps = [
        [_node("a", "5100m"), _node("b", "4"),
         _bound("va", "3", 0, "a"), _bound("vx", "2", 0, "a"), _bound("vb", "4", 5, "b")],
        [_pending("p1", "3", 10), _pending("p2", "4", 10)],
        [_pending("tail", "100m", 0, spread=walk)],
    ]
    want = {
        "steps": [(0, 2), (3, 0)],
        "evicted": ["va", "vb"],
        "placements": {"vx": "a", "p1": "a", "p2": "b", "tail": "a"},
        # p1: 1 + fit0 + two ranks (a holds two lower pods); p2: 1 + fit0 +
        # one rank (only b passes fit0, one lower pod); three attempts after.
        "runs": 4 + 3 + 3,
    }
    return steps, want


def _nominee_decides_at_a_reprieve_rank(walk):
    """One node a (10,100m) with va, vb, vc (3 cpu each, priority 0).  One
    pass: p1 (4 cpu, priority 10): 9 + 4 does not fit.  All off, it fits;
    back by name: va (7), vb (10): reprieved; vc (13): the victim.
    Nominated to a.  p2 (3 cpu, priority 10): AS THE NODE STANDS 6 + 3 fits —
    the attempt itself must count p1 in: 6 + 4 + 3 > 10.1, no node.  Its
    search: va, vb off and p1 counted in: 7, it fits; va back: 10 <= 10.1,
    reprieved; vb back: 13: the victim — as the node stands both would come
    back (6, 9) and a would be no candidate at all.  Next pass: p1 (3 + 3
    nominated + 4 = 10), p2 (3 + 4 + 3 = 10) and the 100m tail all bind on
    a."""
    steps = [
        [_node("a", "10100m"),
         _bound("va", "3", 0, "a"), _bound("vb", "3", 0, "a"), _bound("vc", "3", 0, "a")],
        [_pending("p1", "4", 10), _pending("p2", "3", 10)],
        [_pending("tail", "100m", 0, spread=walk)],
    ]
    want = {
        "steps": [(0, 2), (3, 0)],
        "evicted": ["vc", "vb"],
        "placements": {"va": "a", "p1": "a", "p2": "a", "tail": "a"},
        # p1: 1 + fit0 + three ranks; p2: 1 + fit0 + two ranks; three attempts.
        "runs": 5 + 4 + 3,
    }
    return steps, want


def _a_lower_nominee_does_not_count_at_the_attempt(walk):
    """One node a (4 cpu) with v (3 cpu) and w (1 cpu), priority 0.  Step 1:
    lo (3 cpu, priority 5): both off, it fits; v back: 6 > 4, the victim; w
    back: 4, reprieved.  Nominated to a.  Step 2: hi (3 cpu, priority 10)
    comes first in the queue and does NOT count lo (5 < 10): w's 1 + 3 = 4,
    it binds on a with no search.  Then lo, on its nominated node: 1 + 3 + 3
    > 4; the one lower pod is w: off, 3 + 3 > 4 still: no candidate; lo gives
    its nomination up."""
    steps = [
        [_node("a", "4"), _bound("v", "3", 0, "a"), _bound("w", "1", 0, "a")],
        [_pending("lo", "3", 5, spread=walk)],
        [_pending("hi", "3", 10)],
    ]
    want = {
        "steps": [(0, 1), (1, 1)],
        "evicted": ["v"],
        "placements": {"w": "a", "hi": "a", "lo": None},
        # lo: 1 + fit0 + two ranks; hi: 1; lo again: 1 + fit0 (it fails: no rank).
        "runs": 4 + 1 + 2,
    }
    return steps, want


def _a_lower_nominee_does_not_count_in_the_dry_run(walk):
    """Nodes a (4 cpu) with v (3 cpu) and w (1 cpu) of priority 0, b (4 cpu)
    with u (4 cpu, priority 8).  Step 1: lo (3 cpu, priority 5): on a the
    victim is v (w is reprieved); u is not of a lower priority: a.  Step 2:
    hi (4 cpu, priority 10) fits nowhere.  Its dry run does not count lo: a:
    w off: 4 <= 4; w back: no: victim w, priority 0.  b: u off: it fits;
    victim u, priority 8.  a — counting lo in would rule a out (3 + 4 > 4)
    and take b.  lo's nomination, of a lower priority on the taken node, is
    cleared; lo then counts hi in, finds nothing lower anywhere it could use
    and is backed off.  Step 3: hi binds on a; the 4-cpu tail fits nowhere;
    lo sits the pass out."""
    steps = [
        [_node("a", "4"), _node("b", "4"),
         _bound("v", "3", 0, "a"), _bound("w", "1", 0, "a"), _bound("u", "4", 8, "b")],
        [_pending("lo", "3", 5, spread=walk)],
        [_pending("hi", "4", 10)],
        [_pending("tail", "4", 0)],
    ]
    want = {
        "steps": [(0, 1), (0, 2), (1, 1)],
        "evicted": ["v", "w"],
        "placements": {"u": "b", "lo": None, "hi": "a", "tail": None},
        # lo: 1 + fit0 + two ranks; hi: 1 + fit0 + one rank (a and b hold one
        # lower pod each); lo: 1 (nothing of a lower priority is bound where
        # it could go: u is 8); then hi and the tail (lo is backed off).
        "runs": 4 + 3 + 1 + 2,
    }
    return steps, want


NOMINEE_CASES = {
    "fails_with_all_lower_pods_gone": _nominee_decides_with_all_lower_pods_gone,
    "fails_at_a_reprieve_rank": _nominee_decides_at_a_reprieve_rank,
    "lower_nominee_at_the_attempt": _a_lower_nominee_does_not_count_at_the_attempt,
    "lower_nominee_in_the_dry_run": _a_lower_nominee_does_not_count_in_the_dry_run,
}


@PATHS
@pytest.mark.parametrize("walk", [False, True], ids=["node_axis", "walk"])
@pytest.mark.parametrize("case", sorted(NOMINEE_CASES))
def test_what_a_nominee_decides(case, walk, device):
    """(a), (b) and, with ``walk``, (d): the same answers from the host
    oracle (both filter runs), from the search over the node axis (one run)
    and from the walk of a window that holds a DoNotSchedule pod (both
    runs)."""
    steps, want = NOMINEE_CASES[case](walk)
    got = _run(steps, device=device)
    assert got["steps"] == want["steps"]
    assert got["evicted"] == want["evicted"]
    assert got["placements"] == want["placements"] and got["nominated"] == {}
    if device:
        assert got["local"] is not walk
        runs = got["stats"]["preempt_filter_runs"]
        if walk:
            # Upstream's two runs a state where a nominee stands, one node
            # a trip (a sum over the nodes, not their maximum): never fewer.
            assert runs >= want["runs"]
        else:
            assert runs == want["runs"]


# -- (c): the candidate cut ----------------------------------------------------

#: name -> the priority of the node's one 3-cpu pod.  ``n-0`` joins in the
#: stream (the last slot, the first name); ``n-g``'s pod outranks the
#: preemptor, so that node is live and no candidate.
VICTIM_PRIORITY = {"n-0": 6, "n-a": 5, "n-b": 4, "n-c": 3, "n-d": 2, "n-e": 1, "n-f": 0}


def _cut_steps():
    """Eight 4-cpu nodes with one 3-cpu pod each and ``n-c0`` (1 cpu, empty).
    Step 1: ``n-0`` joins with the pending v-0 (3 cpu, priority 6), which
    only it has room for.  Step 2: ``n-c0`` goes and p (3 cpu, priority 10)
    arrives: it fits nowhere; every node but ``n-g`` is a candidate with its
    one pod the victim.  Of the first ``want`` candidates BY NAME the one
    whose victim has the lowest priority wins: priorities fall along the
    names, so it is the last one kept."""
    initial = [_node(n, "4") for n in ("n-f", "n-b", "n-d", "n-a", "n-e", "n-c", "n-g")]
    initial.insert(4, _node("n-c0", "1"))
    initial += [_bound("v-" + n[2:], "3", p, n) for n, p in VICTIM_PRIORITY.items() if n != "n-0"]
    initial.append(_bound("k-g", "3", 20, "n-g"))
    return [
        initial,
        [_node("n-0", "4"), _pending("v-0", "3", 6)],
        [("delete", "nodes", "n-c0"), _pending("p", "3", 10)],
        [_pending("tail", "100m", 0)],
    ]


#: the absolute floor of upstream's candidate count (100 there) -> the node
#: p must be nominated to, the candidates kept.  8 nodes are live at the
#: search, 7 of them candidates.
CUTS = {
    "fewer_than_want": (9, "n-f", 7),  # want = min(9, 8 live) = 8 > 7
    "exactly_want": (7, "n-f", 7),
    "more_than_want": (3, "n-b", 3),  # n-0, n-a, n-b
    "want_is_one": (1, "n-0", 1),
}


@PATHS
@pytest.mark.parametrize("cut", sorted(CUTS))
def test_the_first_want_candidates_by_name_are_kept(cut, device, monkeypatch):
    floor, node, kept = CUTS[cut]
    monkeypatch.setattr(preemption, "MIN_CANDIDATE_NODES_ABSOLUTE", floor)
    steps = _cut_steps()
    runner = _runner(steps[0], device=device)
    evicted = []
    runner.service.add_eviction_listener(lambda ns, nm: evicted.append(nm))
    result = runner.run(_ops(*steps[1:]))
    assert [(s.scheduled, s.unschedulable) for s in result.steps] == [(1, 0), (0, 1), (2, 0)]
    assert evicted == ["v-" + node[2:]]
    assert runner.store.get("pods", "p")["spec"]["nodeName"] == node
    if device:
        driver = runner.replay_driver
        assert driver.fallback_steps == 0, driver.unsupported
        plan, stats = driver._last_plan, driver.stats()
        assert plan.statics.local is True
        names = list(plan.node_names)
        # Slot order is not name order (n-0 took the last slot), and the
        # slot that went dead before the search lies inside the name order.
        assert names[-1] == "n-0" and names != sorted(names)
        assert 0 < names.index("n-c0") < len(names) - 1
        assert stats["preempt_searches"] == 1 and stats["preempt_candidates"] == kept
        # v-0; p and its search (fit0, one rank); p and the tail.
        assert stats["preempt_filter_runs"] == 1 + 3 + 2


# -- the mechanism's shape -----------------------------------------------------


def _plan(record, walk):
    """A lowered two-priority window (one node, one victim, one search)."""
    steps = [
        [_node("a", "4"), _bound("v", "3", 0, "a")],
        [_pending("p", "3", 10, spread=walk)],
    ]
    runner = _runner(steps[0], device=True, record=record)
    runner.run(_ops(*steps[1:]))
    driver = runner.replay_driver
    assert driver.fallback_steps == 0, driver.unsupported
    plan = driver._last_plan
    assert plan.statics.preempt and plan.statics.local is not walk
    assert driver.stats()["preempt_searches"] == 1
    return plan


@pytest.mark.parametrize(
    "record, walk, chains",
    [
        # The attempt, fit0, the reprieve loop's body: one evaluation each.
        ("selection", False, 3),
        # The attempt keeps its pair (the as-it-stands run gives the recorded
        # reasons, which the search reads for ``resolvable``); fit0 and the
        # loop's body one each: ``st.local`` is all their argument needs.
        ("full", False, 4),
        # A window that is not node-local keeps upstream's two everywhere:
        # the attempt, the walk's fit0, its reprieve body.
        ("selection", True, 6),
        ("full", True, 6),
    ],
    ids=["local-selection", "local-full", "walk-selection", "walk-full"],
)
def test_the_chain_is_traced_once_a_state_in_a_node_local_window(record, walk, chains, monkeypatch):
    """Calls of ``_Program._eval_filters`` while the segment program of a
    window is traced (a loop body traces once).  Before PR 44 all four read
    6."""
    plan = _plan(record, walk)
    calls = []
    chain = core._Program._eval_filters

    def counted(self, *args, **kwargs):
        calls.append(1)
        return chain(self, *args, **kwargs)

    monkeypatch.setattr(core._Program, "_eval_filters", counted)
    _traced(plan)
    assert len(calls) == chains


BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark")


def _rehearsal_body():
    sys.path.insert(0, BENCH)
    try:
        import run as harness

        cell = harness.load_cell(harness.load("BENCHMARK.json"), "sperf-5k-preempt_basic", True)
        return json.loads(harness.build_inputs(cell["config"], cell["traffic"], 2147483693)["body"])
    finally:
        sys.path.remove(BENCH)


@pytest.mark.parametrize(
    "record, runs",
    [
        # 31 attempts (20 + 5 + 6), one evaluation each; 5 searches, each
        # fit0 and four reprieve ranks (every search finds an untouched node
        # with its four low pods): 31 + 5 x 5.
        ("selection", 56),
        # The attempt keeps upstream's pair: a nominee stands at attempts 2-5
        # of step 2 and, each nominee's own being off the books at its
        # attempt, at the first four of step 3: 31 + 8 + 5 x 5.  (Before PR
        # 44, with the pair in the search too: 31 + 8 + 5 + 4 x 10 = 84.)
        ("full", 64),
    ],
)
def test_filter_runs_of_the_rehearsal_stream_equal_the_hand_count(record, runs):
    """``replay.preempt_filter_runs`` of PreemptionBasic / 5Nodes as the
    benchmark cell submits it at its rehearsal size (26 scheduled, 5
    unschedulable attempts, 15 victims): the device's own sum, through the
    job plane's result document."""
    from ksim_tpu.jobs.manager import JobManager

    body = _rehearsal_body()
    body["spec"]["simulator"]["recordMode"] = record
    manager = JobManager(workers=1)
    try:
        job = manager.submit(body)
        assert job.wait_done(300)
        state, result, error = job.result_view()
    finally:
        manager.shutdown(timeout=5)
    assert state == "succeeded", error
    block = result["replay"]
    assert (result["result"]["podsScheduled"], result["result"]["unschedulableAttempts"]) == (26, 5)
    assert block["fallback_steps"] == 0 and block["unsupported"] == {}
    assert (block["preempt_searches"], block["preempt_victims"]) == (5, 15)
    assert block["preempt_filter_runs"] == runs
