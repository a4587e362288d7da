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
