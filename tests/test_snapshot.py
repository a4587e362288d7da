"""Snapshot export/import: reference JSON-schema compatibility."""

import json

import pytest

from ksim_tpu.state.cluster import ClusterStore
from ksim_tpu.state.snapshot import SnapshotService
from tests.helpers import make_node, make_pod


def _store_with_content() -> ClusterStore:
    s = ClusterStore()
    s.create("nodes", make_node("n1"))
    s.create("pods", make_pod("p1", labels={"app": "web"}))
    s.create("pods", make_pod("p2", labels={"app": "db"}))
    s.create("namespaces", {"metadata": {"name": "default"}})
    s.create("namespaces", {"metadata": {"name": "kube-system"}})
    s.create("priorityclasses", {"metadata": {"name": "high"}, "value": 100})
    s.create(
        "priorityclasses",
        {"metadata": {"name": "system-cluster-critical"}, "value": 2000000000},
    )
    return s


def test_snap_shape_matches_reference_schema():
    svc = SnapshotService(_store_with_content())
    snap = svc.snap()
    # Exact key set of ResourcesForSnap (reference snapshot.go:33-42).
    assert set(snap.keys()) == {
        "pods", "nodes", "pvs", "pvcs", "storageClasses",
        "priorityClasses", "schedulerConfig", "namespaces",
    }
    assert len(snap["pods"]) == 2
    assert len(snap["nodes"]) == 1


def test_snap_excludes_system_pcs_and_kube_namespaces():
    snap = SnapshotService(_store_with_content()).snap()
    assert [p["metadata"]["name"] for p in snap["priorityClasses"]] == ["high"]
    assert [n["metadata"]["name"] for n in snap["namespaces"]] == ["default"]


def test_snap_label_selector_filtering():
    snap = SnapshotService(_store_with_content()).snap(
        {"matchLabels": {"app": "web"}}
    )
    assert [p["metadata"]["name"] for p in snap["pods"]] == ["p1"]
    assert snap["nodes"] == []  # nodes lack the label


def test_load_round_trip():
    exported = SnapshotService(_store_with_content()).export_json()
    dst = ClusterStore()
    SnapshotService(dst).import_json(exported)
    assert [n["metadata"]["name"] for n in dst.list("nodes")] == ["n1"]
    assert len(dst.list("pods")) == 2
    # UIDs are re-assigned on load, not carried in.
    src_uid = json.loads(exported)["pods"][0]["metadata"].get("uid")
    dst_uid = dst.list("pods")[0]["metadata"]["uid"]
    assert dst_uid and dst_uid != src_uid


def test_load_fixes_pv_claim_ref_uid():
    dst = ClusterStore()
    SnapshotService(dst).load(
        {
            "pvcs": [{"metadata": {"name": "claim", "namespace": "apps", "uid": "old-pvc-uid"}}],
            "pvs": [{
                "metadata": {"name": "vol"},
                "spec": {"claimRef": {"name": "claim", "namespace": "apps", "uid": "old-pvc-uid"}},
                "status": {"phase": "Bound"},
            }, {
                "metadata": {"name": "vol-avail"},
                "spec": {"claimRef": {"name": "claim", "namespace": "apps", "uid": "old-pvc-uid"}},
                "status": {"phase": "Available"},
            }, {
                "metadata": {"name": "vol-orphan"},
                "spec": {"claimRef": {"name": "gone", "namespace": "apps", "uid": "stale"}},
                "status": {"phase": "Bound"},
            }],
        }
    )
    pvc = dst.get("persistentvolumeclaims", "claim", "apps")
    pv = dst.get("persistentvolumes", "vol")
    assert pvc["metadata"]["uid"] != "old-pvc-uid"  # re-assigned on load
    assert pv["spec"]["claimRef"]["uid"] == pvc["metadata"]["uid"]
    # Non-Bound PVs are untouched; missing PVC clears the stale UID.
    assert dst.get("persistentvolumes", "vol-avail")["spec"]["claimRef"]["uid"] == "old-pvc-uid"
    assert dst.get("persistentvolumes", "vol-orphan")["spec"]["claimRef"]["uid"] is None


def test_load_skips_kube_namespaces():
    dst = ClusterStore()
    SnapshotService(dst).load(
        {"namespaces": [
            {"metadata": {"name": "kube-system"}},
            {"metadata": {"name": "apps"}},
        ]}
    )
    assert [n["metadata"]["name"] for n in dst.list("namespaces")] == ["apps"]


def test_load_skips_system_priority_classes():
    dst = ClusterStore()
    SnapshotService(dst).load(
        {"priorityClasses": [
            {"metadata": {"name": "system-node-critical"}, "value": 1},
            {"metadata": {"name": "normal"}, "value": 5},
        ]}
    )
    assert [p["metadata"]["name"] for p in dst.list("priorityclasses")] == ["normal"]


def test_load_snapshot_applies_scheduler_config():
    # Round-1 verdict weak #4: loading a snapshot carrying a
    # schedulerConfig through a live SchedulerService must apply it (the
    # reference calls RestartScheduler after load, snapshot.go:202-219).
    from ksim_tpu.scheduler.service import SchedulerService
    from ksim_tpu.state.cluster import ClusterStore
    from ksim_tpu.state.snapshot import SnapshotService

    store = ClusterStore()
    sched = SchedulerService(store, config={})
    svc = SnapshotService(store, scheduler_service=sched)
    cfg = {
        "apiVersion": "kubescheduler.config.k8s.io/v1",
        "kind": "KubeSchedulerConfiguration",
        "profiles": [{"schedulerName": "my-sched"}],
    }
    svc.load({"nodes": [], "pods": [], "schedulerConfig": cfg})
    assert sched.get_scheduler_config() == cfg
    # ignore_scheduler_configuration leaves the config untouched.
    svc.load(
        {"schedulerConfig": {"profiles": [{"schedulerName": "other"}]}},
        ignore_scheduler_configuration=True,
    )
    assert sched.get_scheduler_config() == cfg


# -- the batch load equals the per-object load ------------------------------
#
# ``SnapshotService.load`` hands the store one batch a kind
# (``ClusterStore.apply_many``).  The per-object sequence it replaced —
# two shallow copies, then ``store.apply(kind, obj)`` with its two deep
# copies and one insort an object — stays HERE as the reference: the
# store after a batch load has to be the store after that one.


def _load_per_object(store, resources, *, ignore_err=False):
    from ksim_tpu.errors import SimulatorError
    from ksim_tpu.state.snapshot import (
        _LOAD_ORDER,
        is_ignored_namespace,
        is_system_priority_class,
    )

    fix_claim_ref = SnapshotService(store)._fix_claim_ref
    for field, kind in _LOAD_ORDER:
        for obj in resources.get(field) or []:
            name = obj.get("metadata", {}).get("name", "")
            if field == "priorityClasses" and is_system_priority_class(name):
                continue
            if field == "namespaces" and is_ignored_namespace(name):
                continue
            try:
                obj = dict(obj)
                md = dict(obj.get("metadata") or {})
                md.pop("uid", None)
                md.pop("resourceVersion", None)
                obj["metadata"] = md
                if field == "pvs":
                    obj = fix_claim_ref(obj)
                store.apply(kind, obj)
            except SimulatorError:
                if not ignore_err:
                    raise


class _RefusingStore(ClusterStore):
    """A store that refuses one key on either path, before it consumes
    anything for it (``_touch`` is the first thing ``create``, ``update``
    and the batch do to a key)."""

    def _touch(self, kind, key):
        from ksim_tpu.errors import ConflictError

        if key == "default/refused":
            raise ConflictError(f"{kind} {key!r} refused")
        super()._touch(kind, key)


def _mixed_pods():
    # Bound and pending mixed, listed in numeric order, which is not
    # name order ("p-10" < "p-2").
    return [
        make_pod(f"p-{i}", node_name=f"n-{i % 3}" if i % 4 else "", labels={"i": str(i)})
        for i in range(13)
    ]


def _doc(**fields):
    doc = {
        "pods": [], "nodes": [], "pvs": [], "pvcs": [], "storageClasses": [],
        "priorityClasses": [], "schedulerConfig": None, "namespaces": [],
    }
    doc.update(fields)
    # What an export carries: a foreign uid and resourceVersion an object.
    for field in fields:
        for i, obj in enumerate(doc[field]):
            obj["metadata"].setdefault("uid", f"foreign-{field}-{i}")
            obj["metadata"].setdefault("resourceVersion", str(9000 + i))
    return doc


def _full_doc():
    return _doc(
        namespaces=[{"metadata": {"name": "default"}}, {"metadata": {"name": "kube-system"}}],
        priorityClasses=[
            {"metadata": {"name": "system-node-critical"}, "value": 1},
            {"metadata": {"name": "normal"}, "value": 5},
        ],
        storageClasses=[{"metadata": {"name": "fast"}, "provisioner": "x"}],
        nodes=[make_node(f"n-{i}") for i in (2, 0, 1)],
        pods=_mixed_pods(),
    )


def _pv_doc():
    return _doc(
        pvcs=[{"metadata": {"name": "claim", "namespace": "apps"}}],
        pvs=[
            {"metadata": {"name": "vol"}, "status": {"phase": "Bound"},
             "spec": {"claimRef": {"name": "claim", "namespace": "apps", "uid": "old"}}},
            {"metadata": {"name": "vol-avail"}, "status": {"phase": "Available"},
             "spec": {"claimRef": {"name": "claim", "namespace": "apps", "uid": "old"}}},
            {"metadata": {"name": "vol-orphan"}, "status": {"phase": "Bound"},
             "spec": {"claimRef": {"name": "gone", "namespace": "apps", "uid": "stale"}}},
        ],
    )


def _seed_some_keys(store):
    # A reset server that kept some objects: these keys are updated
    # (uid kept, MODIFIED), the others created.
    store.create("namespaces", {"metadata": {"name": "default"}})
    store.create("nodes", make_node("n-1", cpu="1"))
    store.create("pods", make_pod("p-10", node_name="n-2"))  # bound -> pending
    store.create("pods", make_pod("p-3"))  # pending -> bound
    store.create("pods", make_pod("other"))
    store.delete("pods", "other")  # an rv gap and a DELETED in the history


def _seed_large_population(store):
    # Many keys against a batch of a few: the key list is repaired by
    # insort, not by the one sort.
    for i in range(150):
        store.create("pods", make_pod(f"q-{i}", node_name="n-0" if i % 2 else ""), copy_obj=False)


_BATCH_CASES = {
    # name: (document, seed the store, store class, load keywords, raises)
    "fresh": (_full_doc, None, ClusterStore, {}, None),
    "some_keys_present": (_full_doc, _seed_some_keys, ClusterStore, {}, None),
    "same_key_twice": (
        lambda: _doc(pods=_mixed_pods() + [make_pod("p-2", node_name="n-9"), make_pod("p-1")],
                     nodes=[make_node("n-0"), make_node("n-0", cpu="9")]),
        None, ClusterStore, {}, None,
    ),
    "few_into_many": (
        lambda: _doc(pods=_mixed_pods()[:5]), _seed_large_population, ClusterStore, {}, None,
    ),
    "bound_pv_claim_ref": (_pv_doc, None, ClusterStore, {}, None),
    "refused_ignored": (
        lambda: _doc(pods=_mixed_pods()[:6] + [make_pod("refused")] + _mixed_pods()[6:]),
        None, _RefusingStore, {"ignore_err": True}, None,
    ),
    "refused_propagates": (
        lambda: _doc(nodes=[make_node("n-0")],
                     pods=_mixed_pods()[:6] + [make_pod("refused")] + _mixed_pods()[6:]),
        None, _RefusingStore, {}, "ConflictError",
    ),
    "strict": (_full_doc, _seed_some_keys, lambda: ClusterStore(strict=True), {}, None),
    "strict_from_env": (_full_doc, None, "env", {}, None),
    "transaction_commits": (_full_doc, _seed_some_keys, ClusterStore, {"txn": "commit"}, None),
    "transaction_rolls_back": (
        _full_doc, _seed_some_keys, ClusterStore, {"txn": "raise"}, "RuntimeError",
    ),
}


def _drain(stream):
    out = []
    while (ev := stream.next(timeout=0)) is not None:
        out.append((ev.kind, ev.event_type, ev.obj))
    return out


def _observe(store, stream):
    from ksim_tpu.state.cluster import KINDS

    keys = lambda objs: sorted(  # noqa: E731
        f"{o['metadata'].get('namespace', '')}/{o['metadata']['name']}" for o in objs
    )
    nodes = [n["metadata"]["name"] for n in store.list("nodes")] + ["n-9", "nowhere"]
    return {
        "checkpoint": store.checkpoint(),
        "list_order": {k: [o["metadata"]["name"] for o in store.list(k)] for k in KINDS},
        "sorted_keys": {k: list(v) for k, v in store._sorted_keys.items()},
        "with_node": keys(store.pods_with_node()),
        "without_node": [o["metadata"]["name"] for o in store.pods_without_node()],
        "on_nodes": {n: keys(store.pods_on_nodes([n])) for n in nodes},
        "digest": store.placements_digest(),
        "events": _drain(stream),
        "history": [(rv, ev.kind, ev.event_type, ev.obj) for rv, ev in store._history],
    }


@pytest.mark.parametrize("case", sorted(_BATCH_CASES))
def test_batch_load_equals_per_object_load(case, monkeypatch):
    make_doc, seed, store_cls, kwargs, raises = _BATCH_CASES[case]
    kwargs = dict(kwargs)
    txn = kwargs.pop("txn", None)
    if store_cls == "env":
        monkeypatch.setenv("KSIM_STORE_STRICT", "1")
        store_cls = ClusterStore

    def run(load):
        store = store_cls()
        if seed is not None:
            seed(store)
        stream = store.watch()
        before = _observe(store, stream)
        doc = make_doc()
        error = None
        try:
            if txn is None:
                load(store, doc)
            else:
                with store.transaction():
                    load(store, doc)
                    # Staged, not delivered: the watcher has seen nothing.
                    assert stream.next(timeout=0) is None
                    if txn == "raise":
                        raise RuntimeError("after the load")
        except Exception as e:  # noqa: BLE001 — compared by type below
            error = type(e).__name__
        return before, _observe(store, stream), error

    batch = run(lambda s, d: SnapshotService(s).load(d, **kwargs))
    each = run(lambda s, d: _load_per_object(s, d, **kwargs))
    assert batch[2] == each[2] == raises
    assert batch[0] == each[0]
    after, ref = batch[1], each[1]
    for what in ref:
        assert after[what] == ref[what], what
    if txn == "raise":
        # Everything rolled back, no event delivered; only the two
        # counters moved on (rv gaps are legal, the epoch only rises).
        def objects_only(seen):
            return dict(seen, checkpoint=seen["checkpoint"]["objects"])

        assert objects_only(after) == objects_only(batch[0])
        assert after["events"] == []
    else:
        assert after["events"], "the load delivered no event"
        uids = [o["metadata"]["uid"] for o in after["checkpoint"]["objects"]["pods"].values()]
        assert not any(u.startswith("foreign-") for u in uids)


def test_batch_load_counts_what_the_batches_applied():
    store = _RefusingStore()
    doc = _doc(
        namespaces=[{"metadata": {"name": "default"}}, {"metadata": {"name": "kube-system"}}],
        priorityClasses=[{"metadata": {"name": "system-x"}, "value": 1}],
        nodes=[make_node("n-0"), make_node("n-1")],
        pods=_mixed_pods()[:4] + [make_pod("refused")],
    )
    # 1 namespace + 2 nodes + 4 pods: the skipped and the refused are not counted.
    assert SnapshotService(store).load(doc, ignore_err=True) == 7
    assert SnapshotService(ClusterStore()).import_json(json.dumps(_full_doc())) == 19


def test_load_leaves_the_callers_document_unchanged():
    import copy

    for make_doc in (_full_doc, _pv_doc):
        doc = make_doc()
        kept = copy.deepcopy(doc)
        store = ClusterStore()
        _seed_some_keys(store)
        SnapshotService(store).load(doc)
        assert doc == kept
        some = (doc["pods"] or doc["pvs"])[0]["metadata"]
        assert some["uid"].startswith("foreign-") and "resourceVersion" in some
        # What the store took over is shared below the two shallow copies,
        # not copied: the ownership contract of ``load``.
        if doc["pods"]:
            live = store.list("pods", copy_objs=False)
            by_name = {p["metadata"]["name"]: p for p in doc["pods"]}
            assert all(p["spec"] is by_name[p["metadata"]["name"]]["spec"] for p in live)
            assert all(p is not by_name[p["metadata"]["name"]] for p in live)
            assert all(p["metadata"] is not by_name[p["metadata"]["name"]]["metadata"] for p in live)
