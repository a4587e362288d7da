"""ClusterStore CRUD / watch / restore semantics."""

import pytest

from ksim_tpu.errors import ConflictError, NotFoundError
from ksim_tpu.state.cluster import ADDED, DELETED, MODIFIED, ClusterStore
from tests.helpers import make_node, make_pod


def test_crud_roundtrip():
    s = ClusterStore()
    s.create("nodes", make_node("n1"))
    got = s.get("nodes", "n1")
    assert got["metadata"]["name"] == "n1"
    assert got["metadata"]["resourceVersion"]
    with pytest.raises(ConflictError):
        s.create("nodes", make_node("n1"))
    s.delete("nodes", "n1")
    with pytest.raises(NotFoundError):
        s.get("nodes", "n1")


def test_namespaced_listing():
    s = ClusterStore()
    s.create("pods", make_pod("p1", namespace="a"))
    s.create("pods", make_pod("p1", namespace="b"))
    assert len(s.list("pods")) == 2
    assert len(s.list("pods", namespace="a")) == 1


def test_update_conflict_detection():
    s = ClusterStore()
    created = s.create("nodes", make_node("n1"))
    rv = created["metadata"]["resourceVersion"]
    s.update("nodes", created, expect_rv=rv)
    with pytest.raises(ConflictError):
        s.update("nodes", created, expect_rv=rv)  # stale now


def test_patch_is_atomic_and_bumps_rv():
    s = ClusterStore()
    created = s.create("pods", make_pod("p1"))
    updated = s.patch(
        "pods", "p1", "default",
        lambda o: o["metadata"].setdefault("annotations", {}).update(x="y"),
    )
    assert updated["metadata"]["annotations"]["x"] == "y"
    assert updated["metadata"]["resourceVersion"] != created["metadata"]["resourceVersion"]


def test_watch_events():
    s = ClusterStore()
    w = s.watch(("pods",))
    s.create("pods", make_pod("p1"))
    s.create("nodes", make_node("n1"))  # not subscribed
    s.patch("pods", "p1", "default", lambda o: None)
    s.delete("pods", "p1", "default")
    events = [w.next(timeout=1) for _ in range(3)]
    assert [e.event_type for e in events] == [ADDED, MODIFIED, DELETED]
    assert all(e.kind == "pods" for e in events)
    assert w.next(timeout=0.05) is None
    w.close()


def test_update_defaults_namespace():
    s = ClusterStore()
    s.create("pods", make_pod("p1"))
    pod = {"metadata": {"name": "p1"}, "spec": {}}  # no namespace field
    s.update("pods", pod)
    listed = s.list("pods", namespace="default")
    assert len(listed) == 1 and listed[0]["metadata"]["namespace"] == "default"


def test_apply_unknown_kind_raises_not_found():
    s = ClusterStore()
    with pytest.raises(NotFoundError):
        s.apply("widgets", {"metadata": {"name": "w"}})


def test_dump_restore_reset_semantics():
    s = ClusterStore()
    s.create("nodes", make_node("n1"))
    initial = s.dump()
    s.create("nodes", make_node("n2"))
    s.delete("nodes", "n1")
    s.restore(initial)
    names = [n["metadata"]["name"] for n in s.list("nodes")]
    assert names == ["n1"]


def test_restore_keeps_resource_version_monotonic():
    s = ClusterStore()
    for i in range(5):
        s.create("nodes", make_node(f"n{i}"))
    dump = s.dump()
    fresh = ClusterStore()
    fresh.restore(dump)
    created = fresh.create("nodes", make_node("new"))
    restored_rvs = [int(n["metadata"]["resourceVersion"]) for n in fresh.list("nodes") if n["metadata"]["name"] != "new"]
    assert int(created["metadata"]["resourceVersion"]) > max(restored_rvs)


def test_watch_resume_replays_deletes_and_expires():
    from ksim_tpu.errors import ExpiredError
    from tests.helpers import make_pod

    store = ClusterStore()
    store.create("pods", make_pod("a"))
    b = store.create("pods", make_pod("b"))
    last = int(b["metadata"]["resourceVersion"])
    # Disconnect; a delete happens while away.
    store.delete("pods", "a")
    stream = store.watch(("pods",), since={"pods": last})
    ev = stream.next(timeout=1)
    assert ev is not None and ev.event_type == "DELETED"
    assert ev.obj["metadata"]["name"] == "a"
    # The DELETED event carries a fresh resourceVersion (> last).
    assert int(ev.obj["metadata"]["resourceVersion"]) > last
    stream.close()
    # A resume point older than the history buffer raises ExpiredError.
    store2 = ClusterStore()
    store2.HISTORY_DEPTH = 4
    store2._history = __import__("collections").deque(maxlen=4)
    for i in range(8):
        store2.create("pods", make_pod(f"p{i}"))
    try:
        store2.watch(("pods",), since={"pods": 1})
        raise AssertionError("expected ExpiredError")
    except ExpiredError:
        pass


def test_restore_emits_fresh_resource_versions():
    from tests.helpers import make_pod

    store = ClusterStore()
    store.create("pods", make_pod("a"))
    dump = store.dump()
    stream = store.watch(("pods",))
    store.restore(dump)
    rvs = []
    while True:
        ev = stream.next(timeout=0.2)
        if ev is None:
            break
        rvs.append(int(ev.obj["metadata"]["resourceVersion"]))
    stream.close()
    # DELETED then ADDED, both with fresh monotonically-increasing rvs.
    assert len(rvs) == 2 and rvs[0] < rvs[1] and rvs[0] > 1


def test_watch_resume_rejects_foreign_resume_points():
    """Resume points from a PREVIOUS store life answer Gone: a fresh
    store has no history to verify against, and a store whose history
    ends below the requested version never issued it.  Silently accepting
    either would leave the client's cache stale forever."""
    import pytest

    from ksim_tpu.errors import ExpiredError

    fresh = ClusterStore()
    with pytest.raises(ExpiredError):
        fresh.watch(("pods",), since={"pods": 5})

    store = ClusterStore()
    store.create("pods", make_pod("p1"))
    store.create("pods", make_pod("p2"))
    with pytest.raises(ExpiredError):
        store.watch(("pods",), since={"pods": 1000})  # ahead of history
    # A genuine resume point still replays the later event.
    first_rv = int(store.get("pods", "p1", "default")["metadata"]["resourceVersion"])
    stream = store.watch(("pods",), since={"pods": first_rv})
    ev = stream.next(timeout=1)
    assert ev is not None and ev.obj["metadata"]["name"] == "p2"
    stream.close()


def test_pod_node_name_partition_tracks_every_write_path():
    """The nodeName partition (pods_with_node / pods_without_node) must
    mirror the store through create, bind (patch), update, rewrap,
    delete, and restore — the scheduler reads one side instead of
    walking all pods every pass."""
    store = ClusterStore()
    store.create("nodes", make_node("n1"))
    store.create("pods", make_pod("a"))
    store.create("pods", make_pod("b", node_name="n1"))

    def names(side):
        return sorted(p["metadata"]["name"] for p in side)

    assert names(store.pods_without_node()) == ["a"]
    assert names(store.pods_with_node()) == ["b"]

    # Bind via patch: a moves sides.
    store.patch("pods", "a", "default", lambda o: o["spec"].__setitem__("nodeName", "n1"))
    assert names(store.pods_without_node()) == []
    assert names(store.pods_with_node()) == ["a", "b"]

    # Unbind via update (drain): b moves back.
    b = store.get("pods", "b", "default")
    b["spec"].pop("nodeName")
    store.update("pods", b)
    assert names(store.pods_without_node()) == ["b"]

    # Rewrap (the bind path's write primitive).
    store.rewrap(
        "pods", "b", "default",
        lambda cur: dict(
            cur,
            spec=dict(cur["spec"], nodeName="n1"),
            metadata=dict(cur["metadata"]),
        ),
    )
    assert names(store.pods_without_node()) == []

    # Delete drops the entry from its side.
    store.delete("pods", "a", "default")
    assert names(store.pods_with_node()) == ["b"]

    # Restore rebuilds the partition from the dump.
    dump = store.dump()
    store.create("pods", make_pod("c"))
    store.restore(dump)
    assert names(store.pods_with_node()) == ["b"]
    assert names(store.pods_without_node()) == []

    # Phase is deliberately NOT part of the partition: a Succeeded pod
    # with a nodeName stays on the with-node side (the requeue path must
    # still see it, matching the full-walk semantics).
    store.create("pods", make_pod("s", node_name="n1", phase="Succeeded"))
    assert "s" in names(store.pods_with_node())


def _digest_of(placements: dict) -> str:
    """The documented definition, spelled out: sha256 over the sorted
    lines ``<namespace>/<name> <node>\\n``, an unbound pod's node empty."""
    import hashlib

    lines = sorted(f"{key} {node or ''}\n" for key, node in placements.items())
    return hashlib.sha256("".join(lines).encode()).hexdigest()


def test_placements_digest_is_the_documented_hash():
    store = ClusterStore()
    assert store.placements_digest() == _digest_of({})
    store.create("pods", make_pod("b", node_name="n1"))
    store.create("pods", make_pod("a"))
    store.create("pods", make_pod("a", namespace="other", node_name="n2"))
    want = {"default/a": None, "default/b": "n1", "other/a": "n2"}
    assert store.placements_digest() == _digest_of(want)


@pytest.mark.parametrize("change", ["bind", "move", "unbind", "delete", "create"])
def test_placements_digest_moves_with_any_one_placement(change):
    """One pod bound, bound elsewhere, unbound, gone or new: another
    digest — and the same digest again whatever the order of the writes."""
    def build(order):
        store = ClusterStore()
        for nm in order:
            store.create("pods", make_pod(nm, node_name="n1" if nm != "a" else ""))
        return store

    store, other = build(["a", "b", "c"]), build(["c", "a", "b"])
    before = store.placements_digest()
    assert before == other.placements_digest()

    def set_node(name, node):
        def mutate(o):
            o["spec"].pop("nodeName", None)
            if node:
                o["spec"]["nodeName"] = node
        store.patch("pods", name, "default", mutate)

    if change == "bind":
        set_node("a", "n1")
    elif change == "move":
        set_node("b", "n2")
    elif change == "unbind":
        set_node("c", "")
    elif change == "delete":
        store.delete("pods", "c", "default")
    else:
        store.create("pods", make_pod("d"))
    assert store.placements_digest() != before


def test_pods_without_node_is_name_sorted():
    """The without-node side is the scheduling queue's stable pre-order:
    it must come back (name, key)-sorted like list("pods")."""
    store = ClusterStore()
    for nm in ("zz", "aa", "mm"):
        store.create("pods", make_pod(nm))
    assert [p["metadata"]["name"] for p in store.pods_without_node()] == [
        "aa", "mm", "zz",
    ]


def test_restore_clears_node_bucket_index():
    """restore() must wipe the nodeName bucket index with the other pod
    partitions: a pre-reset bound pod must not appear in pods_on_nodes()
    after a restore that lacks it (review finding, round 5 — the stale
    entry fed a phantom pod into node-drain requeue, whose patch then
    raised NotFoundError)."""
    store = ClusterStore()
    boot = store.dump()
    store.create("pods", make_pod("ghost", node_name="n1"))
    assert len(store.pods_on_nodes(["n1"])) == 1
    store.restore(boot)
    assert store.pods_on_nodes(["n1"]) == []
    # And the index repopulates from a dump that HAS bound pods.
    store.create("pods", make_pod("real", node_name="n2"))
    snap = store.dump()
    store.restore(boot)
    store.restore(snap)
    assert [p["metadata"]["name"] for p in store.pods_on_nodes(["n2"])] == ["real"]


# ---------------------------------------------------------------------------
# Transactions (round 8: the atomic-segment-reconcile substrate)
# ---------------------------------------------------------------------------


def test_transaction_commit_delivers_events_in_write_order():
    store = ClusterStore()
    store.create("nodes", make_node("n1"))
    stream = store.watch(("pods", "nodes"))
    with store.transaction():
        store.create("pods", make_pod("p1"))
        store.patch(
            "pods", "p1", "default",
            lambda o: o["spec"].__setitem__("nodeName", "n1"),
        )
        store.delete("nodes", "n1")
        # Mid-transaction, the owning thread reads its own staged state...
        assert store.get("pods", "p1")["spec"]["nodeName"] == "n1"
        # ...but nothing has been delivered to watchers yet.
        assert stream.next(timeout=0) is None
    got = []
    while True:
        ev = stream.next(timeout=0)
        if ev is None:
            break
        got.append((ev.event_type, ev.kind, ev.obj["metadata"]["name"]))
    stream.close()
    assert got == [
        (ADDED, "pods", "p1"),
        (MODIFIED, "pods", "p1"),
        (DELETED, "nodes", "n1"),
    ]


def test_transaction_rollback_restores_objects_indexes_and_events():
    """An exception rolls every staged write back: objects, the sorted
    key order, the nodeName partition/bucket indexes — and no watch
    event is ever delivered (a watcher cannot observe the attempt)."""
    store = ClusterStore()
    store.create("nodes", make_node("n1"))
    store.create("pods", make_pod("keep"))
    store.create("pods", make_pod("bound", node_name="n1"))
    before_objs = store.dump()
    stream = store.watch(("pods", "nodes"))
    with pytest.raises(RuntimeError, match="boom"):
        with store.transaction():
            store.create("pods", make_pod("staged"))
            store.patch(
                "pods", "keep", "default",
                lambda o: o["spec"].__setitem__("nodeName", "n1"),
            )
            store.delete("pods", "bound", "default")
            store.delete("nodes", "n1")
            raise RuntimeError("boom")
    assert stream.next(timeout=0) is None  # nothing leaked
    stream.close()
    assert store.dump() == before_objs
    # Incremental indexes repaired, not just the object tables:
    assert [p["metadata"]["name"] for p in store.pods_without_node()] == ["keep"]
    assert [p["metadata"]["name"] for p in store.pods_on_nodes(["n1"])] == ["bound"]
    assert [n["metadata"]["name"] for n in store.list("nodes")] == ["n1"]
    # The store still works normally afterwards (watchers, indexes, rv).
    store.create("pods", make_pod("after"))
    assert store.get("pods", "after")["metadata"]["name"] == "after"


def test_transaction_rollback_restores_update_pre_image():
    store = ClusterStore()
    store.create("pods", make_pod("p1", cpu="100m"))
    rv_before = store.get("pods", "p1")["metadata"]["resourceVersion"]
    with pytest.raises(ValueError):
        with store.transaction():
            obj = store.get("pods", "p1")
            obj["metadata"]["labels"] = {"x": "1"}
            store.update("pods", obj)
            raise ValueError("abort")
    got = store.get("pods", "p1")
    assert got["metadata"].get("labels") == {}
    assert got["metadata"]["resourceVersion"] == rv_before


def test_transaction_nested_and_restore_refused():
    store = ClusterStore()
    with pytest.raises(RuntimeError, match="nested"):
        with store.transaction():
            with store.transaction():
                pass
    boot = store.dump()
    with pytest.raises(RuntimeError, match="restore"):
        with store.transaction():
            store.restore(boot)


def test_strict_mode_asserts_lock_held_on_internal_mutators():
    """Sanitizer-lite (KSIM_STORE_STRICT / strict=True, docs/lint.md):
    internal mutators called without the store lock raise, with it (and
    through every public API path) they work exactly as before."""
    from ksim_tpu.state.cluster import ADDED, WatchEvent

    store = ClusterStore(strict=True)
    # Public API acquires the lock itself: unchanged behavior.
    store.create("pods", make_pod("ok"))
    store.patch("pods", "ok", "default", lambda o: o["metadata"].setdefault(
        "labels", {}
    ).update(x="y"))
    store.delete("pods", "ok", "default")
    with store.transaction():
        store.create("pods", make_pod("txn"))
    # Internal mutators without the lock: loud AssertionError.
    ev = WatchEvent("pods", ADDED, make_pod("raw"))
    with pytest.raises(AssertionError, match="KSIM_STORE_STRICT"):
        store._notify(ev)
    with pytest.raises(AssertionError, match="KSIM_STORE_STRICT"):
        store._index_pod("default/raw", None)
    with pytest.raises(AssertionError, match="KSIM_STORE_STRICT"):
        store._touch("pods", "default/raw")
    # Under the lock the same calls are legal (the lock-held contract).
    with store._lock:
        store._notify(ev)


def test_strict_mode_default_comes_from_env(monkeypatch):
    monkeypatch.setenv("KSIM_STORE_STRICT", "1")
    assert ClusterStore()._strict
    monkeypatch.delenv("KSIM_STORE_STRICT")
    assert not ClusterStore()._strict
    # Explicit argument beats the environment either way.
    monkeypatch.setenv("KSIM_STORE_STRICT", "1")
    assert not ClusterStore(strict=False)._strict


def test_apply_many_takes_ownership_and_counts_what_it_applied():
    store = ClusterStore(strict=True)
    store.create("pods", make_pod("b"))
    given = [make_pod("c", node_name="n0"), make_pod("a"), make_pod("b", node_name="n1")]
    assert store.apply_many("pods", given) == 3
    live = store.list("pods", copy_objs=False)
    # Stored as handed over (no copy), in name order, one rv an object in
    # the order given; the key that was there keeps its uid.
    assert [o is g for o, g in zip(live, (given[1], given[2], given[0]))] == [True] * 3
    assert [o["metadata"]["resourceVersion"] for o in given] == ["2", "3", "4"]
    assert [o["metadata"]["uid"] for o in given] == ["uid-pods-2", "uid-pods-3", "uid-pods-1"]
    assert store.apply_many("pods", []) == 0
    with pytest.raises(NotFoundError):
        store.apply_many("widgets", [make_pod("x")])
    with pytest.raises(AssertionError, match="KSIM_STORE_STRICT"):
        store._add_sorted_keys("pods", [("x", "default/x")])


def test_apply_many_is_one_lock_hold_for_readers():
    """A kind's batch becomes visible together: a reader in another
    thread sees none of it or all of it, and never a key list that
    disagrees with the object table (``list`` would raise KeyError)."""
    import sys
    import threading

    store = ClusterStore()
    batch = [make_pod(f"p-{i}", node_name="n0" if i % 2 else "") for i in range(4000)]
    seen, errors, started = set(), [], threading.Event()
    done = threading.Event()

    def reader():
        try:
            while not done.is_set():
                seen.add(len(store.list("pods", copy_objs=False)))
                seen.add(2 * len(store.pods_with_node()))
                seen.add(2 * len(store.pods_without_node()))
                started.set()
        except Exception as e:  # noqa: BLE001 — reported by the assert below
            errors.append(e)
            started.set()

    threads = [threading.Thread(target=reader) for _ in range(4)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for t in threads:
            t.start()
        assert started.wait(10)
        assert store.apply_many("pods", batch) == 4000
        store.list("pods", copy_objs=False)
    finally:
        done.set()
        for t in threads:
            t.join(10)
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert not errors, errors
    assert seen <= {0, 4000}, sorted(seen)
