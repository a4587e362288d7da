"""DefaultPreemption as kube-scheduler v1.30 defines it, on both paths.

Every expectation below is derived BY HAND from upstream's definitions
(``dryRunPreemption`` / ``selectVictimsOnNode``: a candidate needs one
victim at least; ``RunFilterPluginsWithNominatedPods``: nominated pods of
the evaluated pod's priority or above are counted in, in the pass and in
the dry run; ``evaluateNominatedNode``: a pod's nominated node is tried
first; ``prepareCandidate``: nominations of a lower priority onto the
taken node are cleared; ``pickOneNodeForPreemption``: the victims'
priorities are summed with 2**31 added to each) and from the simulator's
conventions (one pass a step, queue by priority then name, candidate walk
from the first node by name, a victim gone at once, equal scores to the
first node in slot order).  The per-pass path and the device-resident
replay must both land on it: steps' counts, evictions in order,
nominations, placements.

``PreemptionBasic`` (upstream scheduler_perf), by hand.  Step 0: n nodes of
4 cpu.  Step 1: 4n pods of 900m at priority 0 in one pass, queued by name;
each goes to the emptiest node, the first in slot order (= the nodes' name
order) among equals, so they are dealt round: the node at name position m
gets the low pods at name positions m, m + n, m + 2n, m + 3n (3,600m of
4,000m).  Step 2: n pods of 3,000m at priority 10.  None fits.  The first in
the queue finds every node a candidate — all four low pods off, it fits;
they come back by name (equal priority and start): the first fits beside it
(900m + 3,000m), the other three stay off — all tie, the first node by name
wins: three victims, nominated there.  The second counts the first in as
nominated on that node (900m + 3,000m: no room even with the last low pod
off), so that node is no candidate; it takes the next by name.  And so on:
pod k of the queue takes node k by name, 3n victims, none scheduled.  Step
3, one 100m pod (the stream's next pass): each high pod is tried on its
nominated node first and binds there; the small pod fits the 100m left
everywhere and goes to the first node.  n + 1 scheduled.
"""

from __future__ import annotations

import jax
import pytest

from ksim_tpu.scenario import Operation, ScenarioRunner
from ksim_tpu.scheduler.preemption import find_preemption
from ksim_tpu.state.cluster import ClusterStore
from tests.helpers import make_node, make_pod

T0 = "2024-01-01T00:00:00Z"


def _pod(name, cpu, priority, *, node=None, memory=None, created=T0, **kw):
    p = make_pod(name, cpu=cpu, memory=memory, node_name=node or "", priority=priority, **kw)
    p["metadata"]["creationTimestamp"] = created
    if node:
        p.setdefault("status", {})["phase"] = "Running"
    return p


def _ops(*steps):
    """steps: lists of ("nodes" | "pods", obj) creations or ("delete", kind,
    name) — one list a step, the first being step 1."""
    for k, step in enumerate(steps, start=1):
        for entry in step:
            if entry[0] == "delete":
                yield Operation(step=k, op="delete", kind=entry[1], name=entry[2],
                                namespace="default" if entry[1] == "pods" else "")
            else:
                yield Operation(step=k, op="create", kind=entry[0], obj=entry[1])


def _runner(initial, *, device, k=4, record="selection"):
    """A runner over a store that already holds ``initial`` (the cluster
    as it stands, bound pods included: a stream cannot create those on
    the device path)."""
    jax.config.update("jax_enable_x64", False)
    store = ClusterStore()
    for kind, obj in initial:
        store.create(kind, obj)
    return ScenarioRunner(
        store=store, preemption=True, pod_bucket_min=128, device_replay=device,
        device_segment_steps=k, record=record,
    )


def _run(steps, *, device, k=4, record="selection"):
    """``steps[0]`` is the cluster before the stream; the rest run."""
    runner = _runner(steps[0], device=device, k=k, record=record)
    evicted = []
    runner.service.add_eviction_listener(lambda ns, nm: evicted.append(nm))
    result = runner.run(_ops(*steps[1:]))
    pods = runner.store.list("pods", copy_objs=False)
    got = {
        "steps": [(s.scheduled, s.unschedulable) for s in result.steps],
        "evicted": evicted,
        "placements": {p["metadata"]["name"]: p["spec"].get("nodeName") for p in pods},
        "nominated": {
            p["metadata"]["name"]: p["status"]["nominatedNodeName"]
            for p in pods
            if (p.get("status") or {}).get("nominatedNodeName")
        },
    }
    if device:
        driver = runner.replay_driver
        assert driver.fallback_steps == 0, driver.unsupported
        assert driver.device_steps == len(result.steps)
        got["stats"] = driver.stats()
        got["local"] = driver._last_plan.statics.local
    return got


PATHS = pytest.mark.parametrize("device", [False, True], ids=["per_pass", "device"])


def _preemption_basic(n):
    nodes = [("nodes", make_node(f"node-{i}", cpu="4", memory="32Gi")) for i in range(n)]
    low = [("pods", _pod(f"low-{i}", "900m", 0)) for i in range(4 * n)]
    high = [("pods", _pod(f"high-{i}", "3", 10)) for i in range(n)]
    return [nodes, low, high, [("pods", _pod("tail-0", "100m", 0, memory="500Mi"))]]


@PATHS
@pytest.mark.parametrize("n", [5, 20])
def test_preemption_basic_is_the_hand_derived_answer(n, device):
    got = _run(_preemption_basic(n), device=device)
    assert got["steps"] == [(4 * n, 0), (0, n), (n + 1, 0)]
    node_names = sorted(f"node-{i}" for i in range(n))
    low_names = sorted(f"low-{i}" for i in range(4 * n))
    high_names = sorted(f"high-{i}" for i in range(n))
    evicted, placed = [], {"tail-0": node_names[0]}
    for m, node in enumerate(node_names):
        evicted += [low_names[m + n], low_names[m + 2 * n], low_names[m + 3 * n]]
        placed[low_names[m]] = node
        placed[high_names[m]] = node
    assert got["evicted"] == evicted
    assert got["placements"] == placed and got["nominated"] == {}
    if n == 5:   # not (0, 5), (2, 4), (2, 3), ...: one preemptor a pass
        assert got["steps"][1:] == [(0, 5), (6, 0)]
    if device:
        stats = got["stats"]
        assert stats["preempt_searches"] == n and stats["preempt_victims"] == 3 * n
        assert stats["preempt_nominations"] == n and stats["preempt_overflows"] == 0
        # One pass, one priority: the n searches share one victim table.
        assert stats["preempt_table_builds"] == 1
        # Preemptor k finds the n - k nodes no earlier one took.
        assert stats["preempt_candidates"] == n * (n + 1) // 2


def test_a_node_whose_pods_would_all_be_reprieved_is_no_candidate():
    """(a), on the host search alone (a pass never asks it for a pod that
    fits): the 1-cpu pod fits beside the node's 1-cpu pod of priority 0, so
    that pod comes back and no victim is left: no candidate, no
    nomination — not a candidate without victims."""
    nodes = [make_node("n0", cpu="2", memory="8Gi")]
    bound = [_pod("v", "1", 0, node="n0")]
    d = find_preemption(_pod("p", "1", 10), nodes, bound)
    assert d.nominated_node is None and d.victims == []


def _two_preemptors_one_pass():
    """Nodes a and b (4 cpu), each with one 3-cpu pod: a's of priority 0,
    b's of priority 1.  Two 3-cpu preemptors (priority 10) in one pass.  p1:
    both nodes are candidates with one victim; a's has the lower priority: a,
    victim va.  p2: a holds no pod of a lower priority any more (and p1,
    counted in, leaves 1 cpu): no candidate; b, victim vb.  Next pass: both
    bind where they were nominated."""
    return [
        [("nodes", make_node("a", cpu="4", memory="8Gi")),
         ("nodes", make_node("b", cpu="4", memory="8Gi")),
         ("pods", _pod("va", "3", 0, node="a")), ("pods", _pod("vb", "3", 1, node="b"))],
        [("pods", _pod("p1", "3", 10)), ("pods", _pod("p2", "3", 10))],
        [("pods", _pod("tail", "100m", 0))],
    ]


@PATHS
def test_a_later_preemptor_of_the_pass_takes_another_node(device):
    """(a) and (b) in the dry run: the node the first preemptor emptied is
    no candidate for the second."""
    got = _run(_two_preemptors_one_pass(), device=device)
    assert got["steps"] == [(0, 2), (3, 0)]
    assert got["evicted"] == ["va", "vb"]
    assert got["placements"] == {"p1": "a", "p2": "b", "tail": "a"}


def _nominee_and_later_pods():
    """One node of 4 cpu holding v (3 cpu, priority 0).  Step 1, one pass, in
    queue order: p (3 cpu, priority 10) evicts v and is nominated; q (2 cpu,
    priority 10, after p by name) sees 4 cpu free but has to count p in: 1 cpu,
    it fits nowhere, and with no pod of a lower priority left there is nothing
    to preempt: it is backed off.  Step 2: r (2 cpu, priority 20) comes first
    in the queue and does NOT count p (priority 10 < 20): it binds.  Then p
    is tried on its nominated node: 2 cpu free, it does not fit; r is no
    victim for it: p gives its nomination up.  (q sits out the pass after
    its first failure.)"""
    return [
        [("nodes", make_node("n0", cpu="4", memory="8Gi")), ("pods", _pod("v", "3", 0, node="n0"))],
        [("pods", _pod("p", "3", 10)), ("pods", _pod("q", "2", 10))],
        [("pods", _pod("r", "2", 20))],
    ]


@PATHS
def test_a_nominee_counts_for_its_priority_and_below_only(device):
    """(b) in the pass: a nominee of equal priority keeps a later pod off its
    node; for a pod of a higher priority it does not count."""
    got = _run(_nominee_and_later_pods(), device=device)
    assert got["steps"] == [(0, 2), (1, 1)]
    assert got["evicted"] == ["v"]
    assert got["placements"] == {"p": None, "q": None, "r": "n0"}
    assert got["nominated"] == {}


def _lower_nominee_in_the_dry_run():
    """One node of 4 cpu with v (3 cpu, priority 0) and w (1 cpu, priority 0).
    Step 1: lo (3 cpu, priority 5): with both off it fits; v comes back
    first (by name): 3 + 3 > 4, a victim; w: 1 + 3 fits, reprieved.  lo is
    nominated.  Step 2: hi (4 cpu, priority 10) is first in the queue: 3 cpu
    free, it does not fit.  Its dry run does not count lo (5 < 10): w off, it
    fits; w back: it does not: victim w, hi nominated, and lo's nomination,
    of a lower priority on the taken node, is cleared.  Then lo: it counts hi
    in: no room; nothing of a lower priority is left: backed off, no
    nomination.  Step 3: hi binds on its nominated node."""
    return [
        [("nodes", make_node("n0", cpu="4", memory="8Gi")),
         ("pods", _pod("v", "3", 0, node="n0")), ("pods", _pod("w", "1", 0, node="n0"))],
        [("pods", _pod("lo", "3", 5))],
        [("pods", _pod("hi", "4", 10))],
        [("pods", _pod("tail", "4", 0))],
    ]


@PATHS
def test_a_nominee_of_a_lower_priority_neither_counts_nor_keeps_its_node(device):
    got = _run(_lower_nominee_in_the_dry_run(), device=device)
    assert got["steps"] == [(0, 1), (0, 2), (1, 1)]
    assert got["evicted"] == ["v", "w"]
    assert got["placements"] == {"lo": None, "hi": "n0", "tail": None}
    assert got["nominated"] == {}


def _nominated_node_first():
    """Nodes a and b of 4 cpu.  a: keep (1 cpu, priority 20) and va (3 cpu,
    priority 0); b: vb (4 cpu, priority 8).  Step 1: p (3 cpu, priority 10):
    a's victim has the lower priority: a, victim va, nominated.  Step 2: vb
    is deleted, so b stands empty and scores above a (1 cpu used): p still
    binds on a, its nominated node, which is tried first."""
    return [
        [("nodes", make_node("a", cpu="4", memory="8Gi")),
         ("nodes", make_node("b", cpu="4", memory="8Gi")),
         ("pods", _pod("keep", "1", 20, node="a")), ("pods", _pod("va", "3", 0, node="a")),
         ("pods", _pod("vb", "4", 8, node="b"))],
        [("pods", _pod("p", "3", 10))],
        [("delete", "pods", "vb"), ("pods", _pod("tail", "100m", 0))],
    ]


@PATHS
def test_the_nominated_node_is_tried_first(device):
    got = _run(_nominated_node_first(), device=device)
    assert got["steps"] == [(0, 1), (2, 0)]
    assert got["evicted"] == ["va"]
    assert got["placements"]["p"] == "a" and got["placements"]["tail"] == "b"


@PATHS
def test_the_sum_adds_two_to_the_31_a_victim(device):
    """(c): nodes a and b of 2 cpu; a holds priorities 3 and 1, b holds 3, 0
    and 0 (1 cpu, 500m, 500m); the 2-cpu preemptor needs the whole node.
    Highest priorities tie; upstream's sums are 4 + 2 x 2**31 for a and
    3 + 3 x 2**31 for b: a.  Bare sums (4 against 3) would say b."""
    steps = [
        [("nodes", make_node("a", cpu="2", memory="8Gi")),
         ("nodes", make_node("b", cpu="2", memory="8Gi")),
         ("pods", _pod("a-hi", "1", 3, node="a")), ("pods", _pod("a-lo", "1", 1, node="a")),
         ("pods", _pod("b-hi", "1", 3, node="b")), ("pods", _pod("b-m", "500m", 0, node="b")),
         ("pods", _pod("b-n", "500m", 0, node="b"))],
        [("pods", _pod("p", "2", 10))],
    ]
    got = _run(steps, device=device)
    assert got["nominated"] == {"p": "a"} and got["evicted"] == ["a-hi", "a-lo"]


def _constrained(name, cpu, priority, app, anti=None, node=None):
    p = _pod(name, cpu, priority, node=node, labels={"app": app})
    if anti:
        p["spec"]["affinity"] = {"podAntiAffinity": {
            "requiredDuringSchedulingIgnoredDuringExecution": [
                {"labelSelector": {"matchLabels": {"app": anti}},
                 "topologyKey": "kubernetes.io/hostname"}]}}
    return p


@PATHS
def test_a_victim_that_only_the_anti_affinity_names(device):
    """The walk over candidates (a window with a required anti-affinity
    does not take the search over the node axis).  One node of 4 cpu with
    web (1 cpu, priority 0, app=web) and db (1 cpu, priority 0, app=db).
    The preemptor (1 cpu, priority 10) has room but a required
    anti-affinity against app=web on its node: with both off it fits; db
    comes back first (by name) and stays; web cannot: the one victim."""
    steps = [
        [("nodes", make_node("n0", cpu="4", memory="8Gi",
                             labels={"kubernetes.io/hostname": "n0"})),
         ("pods", _constrained("db", "1", 0, "db", node="n0")),
         ("pods", _constrained("web", "1", 0, "web", node="n0"))],
        [("pods", _constrained("p", "1", 10, "p", anti="web"))],
        [("pods", _pod("tail", "100m", 0))],
    ]
    got = _run(steps, device=device)
    assert got["steps"] == [(0, 1), (2, 0)]
    assert got["evicted"] == ["web"] and got["placements"]["p"] == "n0"
    if device:
        assert got["stats"]["preempt_searches"] == 1


def _three_tiers_one_pass(*, walk=False):
    """One pass over three tiers of pending pods (priorities 20, 10, 0) in
    which binds and searches interleave, two preemptors take the SAME node
    and the preemptors' priority changes mid-pass.  Nodes (cpu) and what they
    hold (cpu, priority): n-v (1): v-lo (1, 0); n-w (1): w-lo (1, 5); n-x
    (8): x-a (1, 6), x-b (4, 1), x-c (1, 1), x-d (2, 0); n-y (4): y-hi
    (2, 30), y-lo (2, 15); n-z (2,100m): z-hi (1, 30).  All full but n-z.
    The queue, by priority then name:

    - t20-a (6 cpu) fits nowhere.  Lower than 20: everything but y-hi and
      z-hi.  Only n-x is large enough: all four off, it fits; they come back
      most important first: x-a (7 of 8), x-b (11: stays off), x-c (8), x-d
      (10: off).  Victims x-b, x-d — the second and the fourth of the node's
      row, which closes up to x-a, x-c.  Nominated to n-x.
    - t20-b (500m) binds on n-z, the one node with room (n-x counts t20-a in).
    - t20-c (2 cpu) fits nowhere.  n-x, from the closed-up row and with
      t20-a counted in (6): x-a and x-c off, it fits (8); neither can come
      back (9): victims x-a, x-c.  n-y: y-lo off, it fits; victim y-lo.  The
      highest victim priority decides: 6 on n-x against 15 on n-y: n-x again.
    - t10-a (1 cpu) fits nowhere (n-x holds 8 cpu of nominees of its priority
      or above).  Lower than 10 now: v-lo and w-lo — NOT y-lo, which the
      table of the pods before it held.  Both nodes are candidates with one
      victim; priority 0 against 5: n-v, victim v-lo.
    - t10-b (250m) binds on n-z.
    - t10-c (2 cpu) fits nowhere; w-lo is the one pod lower, on a node too
      small: no candidate, no nomination (a table kept from priority 20
      would offer y-lo).
    - t00-a (250m) binds on n-z.

    Four searches over two tables (priorities 20 and 10).  Next pass: t20-a
    and t20-c bind on n-x, t10-a on n-v, each on its nominated node; t10-c
    sits the pass out; the 100m tail takes what n-z has left.  ``walk``: the
    last pod carries a required anti-affinity (against nothing there is), so
    the window's search walks its candidates in name order."""
    def node(name, cpu):
        return ("nodes", make_node(name, cpu=cpu, memory="8Gi",
                                   labels={"kubernetes.io/hostname": name}))

    def bound(name, cpu, priority, on):
        return ("pods", _constrained(name, cpu, priority, name, node=on))

    def pending(name, cpu, priority, anti=None):
        return ("pods", _constrained(name, cpu, priority, name, anti=anti))

    return [
        [node("n-v", "1"), node("n-w", "1"), node("n-x", "8"), node("n-y", "4"),
         node("n-z", "2100m"),
         bound("v-lo", "1", 0, "n-v"), bound("w-lo", "1", 5, "n-w"),
         bound("x-a", "1", 6, "n-x"), bound("x-b", "4", 1, "n-x"),
         bound("x-c", "1", 1, "n-x"), bound("x-d", "2", 0, "n-x"),
         bound("y-hi", "2", 30, "n-y"), bound("y-lo", "2", 15, "n-y"),
         bound("z-hi", "1", 30, "n-z")],
        [pending("t20-a", "6", 20), pending("t20-b", "500m", 20), pending("t20-c", "2", 20),
         pending("t10-a", "1", 10), pending("t10-b", "250m", 10), pending("t10-c", "2", 10),
         pending("t00-a", "250m", 0, anti="nothing" if walk else None)],
        [pending("tail", "100m", 0)],
    ]


@PATHS
@pytest.mark.parametrize("walk", [False, True], ids=["node_axis", "walk"])
def test_two_preemptors_share_a_node_and_the_priority_changes_mid_pass(walk, device):
    """The pass's victim table: the second preemptor of a node reads the row
    the first one's verdict closed up, and the first preemptor of a lower
    priority gets a table of its own."""
    got = _run(_three_tiers_one_pass(walk=walk), device=device)
    assert got["steps"] == [(3, 4), (4, 0)]
    assert got["evicted"] == ["x-b", "x-d", "x-a", "x-c", "v-lo"]
    assert got["placements"] == {
        "w-lo": "n-w", "y-hi": "n-y", "y-lo": "n-y", "z-hi": "n-z",
        "t20-a": "n-x", "t20-b": "n-z", "t20-c": "n-x", "t10-a": "n-v",
        "t10-b": "n-z", "t10-c": None, "t00-a": "n-z", "tail": "n-z",
    }
    assert got["nominated"] == {}
    if device:
        assert got["local"] is not walk
        stats = got["stats"]
        assert stats["preempt_searches"] == 4 and stats["preempt_victims"] == 5
        assert stats["preempt_nominations"] == 3 and stats["preempt_overflows"] == 0
        # One table a pass and priority that searched: 20 and 10.
        assert stats["preempt_table_builds"] == 2


def test_nine_lower_priority_pods_on_a_node_discard_the_segment():
    """VMAX = 8 pods of a lower priority a node: one more and the segment is
    discarded as ``preemption_overflow`` before any store effect; the
    per-pass path carries the step to the same answer."""
    steps = [
        [("nodes", make_node("n0", cpu="9", memory="8Gi"))]
        + [("pods", _pod(f"v{i}", "1", 0, node="n0")) for i in range(9)],
        [("pods", _pod("p", "9", 10))],
    ]
    runner = _runner(steps[0], device=True)
    evicted = []
    runner.service.add_eviction_listener(lambda ns, nm: evicted.append(nm))
    runner.run(_ops(*steps[1:]))
    driver = runner.replay_driver
    assert driver.unsupported.get("preemption_overflow", 0) >= 1
    assert driver.stats()["preempt_overflows"] >= 1
    assert sorted(evicted) == [f"v{i}" for i in range(9)]
    assert runner.store.get("pods", "p")["status"]["nominatedNodeName"] == "n0"


def _plan_of(late):
    """The lowered window (and the run's counters) of: two nodes of 2 cpu,
    two 1,500m pods of priority 0 in step 1, one of priority ``late`` in
    step 2 (one window of two steps)."""
    nodes = [("nodes", make_node(f"n{i}", cpu="2", memory="8Gi")) for i in range(2)]
    runner = _runner(nodes, device=True)
    runner.run(_ops(
        [("pods", _pod(f"p{i}", "1500m", 0)) for i in range(2)],
        [("pods", _pod("late", "1500m", late))],
    ))
    driver = runner.replay_driver
    assert driver.fallback_steps == 0, driver.unsupported
    return driver._last_plan, driver.stats()


def _traced(plan):
    """The segment program of ``plan``, traced."""
    from ksim_tpu.engine import replay

    const, (ev, st) = replay._pack_plan_buffers(plan, (plan.ev, plan.state0))
    return jax.make_jaxpr(
        lambda c, e, s: replay._segment_body(plan.statics, plan.prog, c, e, s)
    )(const, ev, st)


def test_a_priority_flat_window_lowers_without_the_search():
    """The accepted benchmark cells are priority-flat: with preemption on
    they must still run the program they always ran.  The statics say so,
    the counters say so, and the traced program holds no sort (the victim
    table's and the nodes' name order are the sorts of the segment
    program); a window with two priorities holds them."""
    def sorts(plan):
        return str(_traced(plan)).count("sort[")

    flat, flat_stats = _plan_of(0)
    assert flat.statics.preempt is False and "nom_node" not in flat.state0
    assert flat_stats["preempt_searches"] == 0 and sorts(flat) == 0
    assert flat_stats["preempt_table_builds"] == 0
    tiered, tiered_stats = _plan_of(5)
    assert tiered.statics.preempt is True and tiered.statics.local is True
    assert tiered_stats["preempt_searches"] == 1 and tiered_stats["preempt_victims"] == 1
    assert tiered_stats["preempt_table_builds"] == 1
    assert sorts(tiered) >= 1


def test_a_search_with_a_current_table_sorts_and_searches_nothing():
    """The victim table is built in a conditional of its own, taken once a
    pass and priority; the search's conditional reads the carried table.  In
    the traced program of a window with two priorities: ONE sort of the pod
    axis by (node, importance) and ONE ``searchsorted``, both inside the
    build's branch, whose conditional hands back the table; the search's
    conditional (it hands back the verdict: slot, victim rows, overflow,
    candidates, filter runs) holds neither in any branch — its one sort is
    the candidate cut's: a single operand, the node axis (PR 44)."""
    plan, stats = _plan_of(5)
    assert stats["preempt_searches"] == 1 and stats["preempt_table_builds"] == 1
    program = _traced(plan).jaxpr

    def inner(eqn):
        for value in eqn.params.values():
            for v in value if isinstance(value, (tuple, list)) else (value,):
                v = getattr(v, "jaxpr", v)
                if hasattr(v, "eqns"):
                    yield v

    def table_work(eqn):
        return (eqn.primitive.name == "sort" and eqn.params["num_keys"] == 2) or (
            eqn.primitive.name in ("jit", "pjit") and eqn.params.get("name") == "searchsorted"
        )

    def count(jaxpr, pred):
        return sum(
            pred(e) + sum(count(j, pred) for j in inner(e)) for e in jaxpr.eqns
        )

    def conds(jaxpr):
        for e in jaxpr.eqns:
            if e.primitive.name == "cond":
                yield e
            for j in inner(e):
                yield from conds(j)

    assert count(program, table_work) == 2  # the sort and the edge search
    shapes = lambda e: sorted((v.aval.shape, str(v.aval.dtype)) for v in e.outvars)
    builds = [
        e for e in conds(program)
        if any(table_work(q) for b in e.params["branches"] for q in b.jaxpr.eqns)
    ]
    assert len(builds) == 1
    n_nodes = plan.state0["valid"].shape[0]
    v = min(plan.statics.v_max * plan.statics.tp, plan.state0["alive"].shape[0])
    assert ((n_nodes,), "int32") in shapes(builds[0])  # cnt
    assert ((v, n_nodes), "int32") in shapes(builds[0])  # vrow
    assert ((v, n_nodes), "bool") in shapes(builds[0])  # vact
    searches = [
        e for e in conds(program)
        if shapes(e) == sorted(
            [((), "int32"), ((), "int32"), ((), "int32"), ((), "bool"), ((v,), "int32")]
        )
    ]
    assert len(searches) == 1
    branches = [b.jaxpr for b in searches[0].params["branches"]]
    assert sum(count(b, table_work) for b in branches) == 0

    def cut(eqn):
        return (
            eqn.primitive.name == "sort"
            and [v.aval.shape for v in eqn.invars] == [(n_nodes,)]
        )

    assert sum(count(b, cut) for b in branches) == 1
    assert sum(count(b, lambda e: e.primitive.name == "sort") for b in branches) == 1


def test_the_write_back_of_a_steps_preemptions_is_one_child_span():
    """``replay.reconcile.evict``: one span a step that preempted, inside
    ``replay.reconcile``, with the step's preemptions and victims as args —
    not one a preemption, which at 5,000 preemptors would push every other
    span out of a job's ring."""
    from ksim_tpu.obs import TRACE

    prev = (TRACE._active, TRACE._ring_on, TRACE._user_disabled)
    TRACE.reset()
    TRACE.enable()
    try:
        got = _run(_preemption_basic(5), device=True)
        recs = TRACE.ring_records()
    finally:
        TRACE.reset()
        TRACE._active, TRACE._ring_on, TRACE._user_disabled = prev
    assert got["steps"][1] == (0, 5)
    evict = [r for r in recs if r["name"] == "replay.reconcile.evict"]
    assert len(evict) == 1
    assert evict[0]["args"]["preemptions"] == 5 and evict[0]["args"]["victims"] == 15
    parent = next(r for r in recs if r["name"] == "replay.reconcile")
    assert parent["t"] <= evict[0]["t"] and evict[0]["t"] + evict[0]["d"] <= parent["t"] + parent["d"]
