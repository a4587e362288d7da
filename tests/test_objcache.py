"""Per-object memo contracts: store writes invalidate by object identity,
events share frozen objects without being corrupted by later writes.
The tables belong to an owner (``objcache.Memo``, installed with
``objcache.scope``); the module-level calls act on the installed one."""

import numpy as np

from ksim_tpu.state import objcache
from ksim_tpu.state.cluster import ClusterStore
from ksim_tpu.state.featurizer import Featurizer
from ksim_tpu.state.resources import pod_requests
from tests.helpers import make_node, make_pod


def test_store_write_yields_fresh_object_and_fresh_parse():
    store = ClusterStore()
    store.create("pods", make_pod("p1", cpu="1"))
    before = store.list("pods", copy_objs=False)[0]
    memo = objcache.Memo()
    with objcache.scope(memo):
        _fresh_object_fresh_parse(store, before)
    # All of it went into the owner's table, and the scope is over.
    assert memo.stats()["entries"] == 2 and objcache.current() is not memo
    # Outside it the same object parses again, into another table.
    assert pod_requests(before) is not memo.get(("preq", id(before), False))


def _fresh_object_fresh_parse(store, before):
    req1 = pod_requests(before)
    assert req1["cpu"] == 1000
    assert pod_requests(before) is req1  # memo hit on the same object

    def bump(obj):
        obj["spec"]["containers"][0]["resources"]["requests"]["cpu"] = "2"

    store.patch("pods", "p1", "default", bump)
    after = store.list("pods", copy_objs=False)[0]
    assert after is not before  # writes replace, never mutate
    assert pod_requests(after)["cpu"] == 2000
    assert pod_requests(before)["cpu"] == 1000  # old object's parse intact


def test_delete_event_does_not_mutate_shared_object():
    store = ClusterStore()
    store.create("nodes", make_node("n1"))
    stored = store.list("nodes", copy_objs=False)[0]
    rv_before = stored["metadata"]["resourceVersion"]
    stream = store.watch(("nodes",))
    store.delete("nodes", "n1")
    # The DELETED event carries a bumped rv on a re-wrapped object; the
    # previously shared dict keeps its original rv (frozen contract).
    assert stored["metadata"]["resourceVersion"] == rv_before
    ev = stream.next(timeout=1)
    stream.close()
    assert ev is not None and ev.event_type == "DELETED"
    assert ev.obj["metadata"]["resourceVersion"] != rv_before


def test_featurize_consistent_across_memo_flush():
    nodes = [make_node(f"n{i}", cpu="4") for i in range(4)]
    pods = [make_pod(f"p{i}", cpu="1") for i in range(6)]
    f = Featurizer()
    with objcache.scope(objcache.Memo()) as memo:
        a = f.featurize(nodes, pods)
        assert memo.stats()["entries"] > 0
        objcache.clear()  # the installed memo, through the module call
        assert memo.stats()["entries"] == 0
        b = f.featurize(nodes, pods)
    np.testing.assert_array_equal(a.nodes.allocatable, b.nodes.allocatable)
    np.testing.assert_array_equal(a.pods.requests, b.pods.requests)


def test_maybe_flush_sweeps_only_stale_entries(monkeypatch):
    """The sweep is the guard of ONE long-lived owner (the interactive
    store's service); it works on that owner's table alone."""
    monkeypatch.setattr(objcache, "LIMIT", 4)
    memo, other = objcache.Memo(), objcache.Memo()
    bystander = {"k": 0}
    other.cached("slot", bystander, lambda: "untouched")
    objs = [{"i": i} for i in range(6)]
    for i, o in enumerate(objs):
        memo.cached("slot", o, lambda i=i: i)
    assert memo.stats()["entries"] == 6  # put never evicts inline
    # A sweep while everything is fresh reclaims nothing and doubles the
    # working limit instead of rescanning every pass.
    memo.maybe_flush()
    assert memo.stats()["entries"] == 6
    # Keep the first two warm; age the rest past STALE_GENERATIONS, then
    # grow the table over the doubled limit to trigger the next sweep.
    for _ in range(objcache.STALE_GENERATIONS + 1):
        memo.maybe_flush()
        for o in objs[:2]:
            memo.cached("slot", o, lambda: None)
    fresh = [{"j": j} for j in range(3)]
    for j, o in enumerate(fresh):
        memo.cached("slot", o, lambda j=j: j)
    memo.maybe_flush()
    st = memo.stats()
    assert st["entries"] == 5  # 2 warm + 3 fresh; 4 stale swept
    assert st["refs"] == 5
    # Warm entries still serve their original values.
    assert memo.cached("slot", objs[0], lambda: "recomputed") == 0
    # Another owner's table saw none of it.
    assert other.stats()["entries"] == 1
    assert other.cached("slot", bystander, lambda: "recomputed") == "untouched"


def _terms_pod(name, app, **kw):
    """Required anti-affinity, a preferred affinity term and a spread
    constraint: every kind of sub-object the encoders parse."""
    sel = {"matchLabels": {"app": app}}
    return make_pod(
        name, cpu="500m", labels={"app": app},
        affinity={
            "podAntiAffinity": {"requiredDuringSchedulingIgnoredDuringExecution": [
                {"labelSelector": sel, "topologyKey": "kubernetes.io/hostname"}]},
            "podAffinity": {"preferredDuringSchedulingIgnoredDuringExecution": [
                {"weight": 7, "podAffinityTerm": {
                    "labelSelector": {"matchLabels": {"app": "web"}}, "topologyKey": "zone"}}]},
        },
        topology_spread_constraints=[{
            "maxSkew": 1, "topologyKey": "zone", "whenUnsatisfiable": "DoNotSchedule",
            "labelSelector": sel}],
        **kw,
    )


def test_entries_keyed_by_shared_subobjects_survive_a_rewrap_and_stay_true():
    """A pod written by re-wrap (``ClusterStore.rewrap``: the per-pass bind,
    the segment reconciler's placements and requeues) keeps its containers,
    affinity terms and spread constraints BY IDENTITY, so a memo entry keyed
    by such a sub-object outlives the write.  That is sound because every
    such entry is a function of the keyed sub-object (and the key's extras)
    alone: after binds and requeues the warm memo's tensors are a cold
    memo's, byte for byte."""
    from ksim_tpu.scenario.runner import placed_pod, requeued_pod
    from tests.test_featurizer import _assert_equal

    store = ClusterStore()
    for i in range(4):
        node = make_node(f"n{i}", cpu="4")
        node["metadata"]["labels"]["zone"] = f"z{i % 2}"
        store.create("nodes", node)
    for i in range(6):
        store.create("pods", _terms_pod(f"p{i}", "web" if i % 2 else "db"))
    store.create("pods", _terms_pod("held", "web", node_name="n3", phase="Running"))

    def featurize():
        return Featurizer().featurize(
            store.list("nodes", copy_objs=False), (),
            queue_pods=store.pods_without_node(), bound_pods=store.pods_with_node(),
            namespaces=store.list("namespaces", copy_objs=False),
        )

    def term_of(name):
        pod = next(p for p in store.list("pods", copy_objs=False) if p["metadata"]["name"] == name)
        anti = pod["spec"]["affinity"]["podAntiAffinity"]
        return pod, anti["requiredDuringSchedulingIgnoredDuringExecution"][0]

    warm = objcache.Memo()
    with objcache.scope(warm):
        featurize()
        old_pod, term = term_of("p0")
        assert warm.get(("ipctx", id(term), "default")) is not objcache.MISS
        for i, node in enumerate(("n0", "n1", "n2")):
            store.rewrap("pods", f"p{i}", "default", lambda o, node=node: placed_pod(o, node=node))
        store.rewrap("pods", "p3", "default", lambda o: placed_pod(o, nominated="n1"))
        store.rewrap("pods", "held", "default", requeued_pod)
        new_pod, new_term = term_of("p0")
        # The pod is new, its parse entries with it; the term is the same
        # object, and its entry is still there to be hit.
        assert new_pod is not old_pod and new_term is term
        assert warm.get(("ipparsed", id(new_pod))) is objcache.MISS
        assert warm.get(("ipparsed", id(old_pod))) is not objcache.MISS
        after_warm = featurize()
        assert warm.get(("ipparsed", id(new_pod))) is not objcache.MISS
    with objcache.scope(objcache.Memo()):
        after_cold = featurize()
    assert after_warm.pods.keys == ["default/held", "default/p3", "default/p4", "default/p5"]
    _assert_equal(after_warm, after_cold)
