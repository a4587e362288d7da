"""The persistent per-pod row table (state/podtable.py) and what the
Featurizer keeps in it: identity indexing, immediate release, bounded
length, per-family tokens, and that nothing the featurizer owns outlives
a departed pod object."""

from __future__ import annotations

import copy
import gc

import numpy as np
import pytest

from ksim_tpu.state.featurizer import Featurizer
from ksim_tpu.state.podtable import (
    LIST,
    ROW,
    Column,
    Interner,
    PodTable,
    content_key,
    first_seen,
    rank_lut,
)
from tests.helpers import make_node, make_pod, random_cluster

_COLS = (
    Column("n", np.int32, -1),
    Column("ids", np.int32, -1, LIST),
    Column("vec", np.int64, 0, ROW),
    Column("obj", object, None),
)


def _pods(n: int, start: int = 0) -> list[dict]:
    return [{"metadata": {"name": f"p{start + i}"}, "n": start + i} for i in range(n)]


def _build(width: int, calls: "list | None" = None):
    def build(pod):
        if calls is not None:
            calls.append(pod["n"])
        n = pod["n"]
        return (n, list(range(n % 4)), np.full(width, n, dtype=np.int64), ("t", n))

    return build


def test_index_is_by_identity_and_new_rows_are_counted():
    table = PodTable()
    pods = _pods(5)
    assert table.index(pods) == 5
    assert table.idx.tolist() == [0, 1, 2, 3, 4]
    # The same objects again, reordered and one twice: no new row.
    again = [pods[3], pods[0], pods[3], pods[1], pods[2], pods[4]]
    assert table.index(again) == 0
    assert table.idx.tolist() == [3, 0, 3, 1, 2, 4]
    # An equal but distinct object is a different pod.
    assert table.index([copy.deepcopy(pods[0])]) == 1


def test_sync_builds_each_row_once_in_queue_order_and_gathers():
    table = PodTable()
    fam = table.family("f", _COLS)
    pods = _pods(6)
    calls: list[int] = []
    table.index(pods)
    table.sync(fam, "tok", _build(3, calls), {"vec": 3})
    assert calls == [0, 1, 2, 3, 4, 5]
    more = _pods(3, start=6)
    queue = [more[0], pods[2], more[1], pods[5], more[2], pods[0]]
    table.index(queue)
    table.sync(fam, "tok", _build(3, calls), {"vec": 3})
    assert calls[6:] == [6, 7, 8]  # only the new pods, in queue order
    assert fam.take("n").tolist() == [6, 2, 7, 5, 8, 0]
    assert fam.take("vec")[:, 0].tolist() == [6, 2, 7, 5, 8, 0]
    assert fam.take("ids").tolist() == [
        [0, 1, -1], [0, 1, -1], [0, 1, 2], [0, -1, -1], [-1, -1, -1], [-1, -1, -1],
    ]
    assert fam.take("obj").tolist() == [("t", n) for n in (6, 2, 7, 5, 8, 0)]
    assert table.rows_rebuilt == 0


def test_token_move_rebuilds_that_family_only_and_is_counted():
    table = PodTable()
    a = table.family("a", _COLS)
    b = table.family("b", _COLS)
    pods = _pods(4)
    calls_a: list[int] = []
    calls_b: list[int] = []
    table.index(pods)
    table.sync(a, 1, _build(2, calls_a), {"vec": 2})
    table.sync(b, 1, _build(2, calls_b), {"vec": 2})
    table.index(pods + _pods(1, start=4))
    table.sync(a, 2, _build(5, calls_a), {"vec": 5})  # a's token moved
    table.sync(b, 1, _build(2, calls_b), {"vec": 2})
    assert len(calls_a) == 4 + 5 and len(calls_b) == 4 + 1
    assert a.take("vec").shape == (5, 5) and b.take("vec").shape == (5, 2)
    # Four surviving pods recomputed in one family; the new pod is a build.
    assert table.rows_rebuilt == 4


def test_rows_not_asked_for_are_released_and_storage_compacts():
    table = PodTable()
    fam = table.family("f", _COLS)
    pods = _pods(200)
    released: list[int] = []
    table.index(pods)
    table.sync(fam, 0, _build(1), {"vec": 1})
    keep = pods[150:]
    table.index(keep, lambda rows: released.extend(fam.cols["n"][rows].tolist()))
    assert sorted(released) == list(range(150))
    assert table.live == 50 and len(table) <= 2 * table.live
    assert fam.take("n").tolist() == list(range(150, 200))  # rows moved intact
    assert fam.take("obj").tolist() == [("t", n) for n in range(150, 200)]
    calls: list[int] = []
    table.sync(fam, 0, _build(1, calls), {"vec": 1})
    assert calls == []  # survivors stay valid across a compaction
    for p in pods[:150]:
        assert not any(r is table._pods for r in gc.get_referrers(p))
    # A released pod that returns is a new pod.
    assert table.index(keep + pods[:1]) == 1


def test_table_grows_past_its_first_capacity():
    table = PodTable()
    fam = table.family("f", _COLS)
    pods = _pods(1000)
    for n in (10, 100, 1000):
        table.index(pods[:n])
        table.sync(fam, 0, _build(2), {"vec": 2})
    assert fam.take("n").tolist() == list(range(1000))
    assert fam.take("vec")[:, 1].tolist() == list(range(1000))


def test_empty_call_releases_everything():
    table = PodTable()
    fam = table.family("f", _COLS)
    table.index(_pods(3))
    table.sync(fam, 0, _build(1), {"vec": 1})
    assert table.index([]) == 0
    table.sync(fam, 0, _build(1), {"vec": 1})
    assert table.live == 0 and fam.take("n").shape == (0,)


# -- rows by content: one build per distinct manifest ------------------------


def _replicas(contents: "list[int]", start: int = 0) -> list[dict]:
    """One pod per entry: ``n`` is all of its content, the name (and a
    uid, a creationTimestamp) all of its identity."""
    return [
        {"metadata": {"name": f"r{start + i}", "uid": f"u{start + i}",
                      "creationTimestamp": f"t{start + i}", "labels": {"c": str(n)}}, "n": n}
        for i, n in enumerate(contents)
    ]


def _pods_of(contents: "list[int]") -> list[dict]:
    """Distinct manifests with the same ``n``: nothing is shared."""
    return [{"metadata": {"name": f"d{i}", "annotations": {"i": str(i)}}, "n": n}
            for i, n in enumerate(contents)]


_COL_NAMES = tuple(c.name for c in _COLS)


def _rows(fam) -> list[tuple]:
    return list(zip(*(fam.take(c).tolist() for c in _COL_NAMES)))


def test_builder_runs_once_per_content_in_first_appearance_order_and_rows_are_copied():
    table = PodTable()
    fam = table.family("f", _COLS)
    contents = [7, 3, 7, 7, 9, 3, 2, 9]
    calls: list[int] = []
    assert table.index(_replicas(contents)) == 8
    fresh = table.sync(fam, "tok", _build(3, calls), {"vec": 3})
    assert calls == [7, 3, 9, 2]  # first appearance, queue order
    assert fresh.tolist() == list(range(8))  # every stale row, copies included
    assert table.rows_copied == 4
    # SCALAR, LIST, ROW and object columns: what a build per pod gives.
    want = PodTable()
    every = want.family("f", _COLS)
    want.index(_pods_of(contents))
    want.sync(every, "tok", _build(3), {"vec": 3})
    assert _rows(fam) == _rows(every)
    assert fam.take("obj").tolist() == [("t", n) for n in contents]
    # Under the same token a second family call builds nothing.
    table.sync(fam, "tok", _build(3, calls), {"vec": 3})
    assert len(calls) == 4 and table.rows_rebuilt == 0


def test_content_met_in_an_earlier_call_copies_and_each_family_keeps_its_own_count():
    table = PodTable()
    a = table.family("a", _COLS)
    b = table.family("b", _COLS)
    first = _replicas([1, 2, 1])
    calls_a: list[int] = []
    calls_b: list[int] = []
    table.index(first)
    table.sync(a, 0, _build(2, calls_a), {"vec": 2})
    more = _replicas([2, 5, 1, 5], start=3)
    table.index(first + more)
    table.sync(a, 0, _build(2, calls_a), {"vec": 2})
    # Family b meets all seven rows cold: once per content, again.
    table.sync(b, 0, _build(2, calls_b), {"vec": 2})
    assert calls_a == [1, 2, 5] and calls_b == [1, 2, 5]
    assert a.take("n").tolist() == b.take("n").tolist() == [1, 2, 1, 2, 5, 1, 5]
    assert table.rows_copied == 1 + 3


def test_released_representative_leaves_its_copies_valid_and_later_pods_still_copy():
    table = PodTable()
    fam = table.family("f", _COLS)
    pods = _replicas([4, 4, 4, 6])
    calls: list[int] = []
    table.index(pods)
    table.sync(fam, 0, _build(2, calls), {"vec": 2})
    assert calls == [4, 6]
    # The pod the builder ran for goes; its content lives on in two rows.
    late = _replicas([4], start=10)
    assert table.index(pods[1:] + late) == 1
    table.sync(fam, 0, _build(2, calls), {"vec": 2})
    assert calls == [4, 6]  # nothing built
    assert fam.take("n").tolist() == [4, 4, 6, 4]
    assert fam.take("ids").tolist() == [[-1, -1], [-1, -1], [0, 1], [-1, -1]]
    assert fam.take("obj").tolist() == [("t", 4), ("t", 4), ("t", 6), ("t", 4)]
    assert table.rows_copied == 2 + 1


def test_last_row_of_a_content_drops_its_key_and_the_map_pins_no_manifest():
    table = PodTable()
    fam = table.family("f", _COLS)
    stream = _pods(10_000)  # 10,000 distinct manifests
    table.index(stream)
    table.sync(fam, 0, _build(1), {"vec": 1})
    assert len(table._cid_of) == 10_000 and table.rows_copied == 0
    owned = _owned_containers([table._cid_of, table._cid_key, table._cid_rows, table._cid_free])
    assert not any(id(p) in owned or id(p["metadata"]) in owned for p in stream)
    assert all(type(k) is bytes for k in table._cid_of)
    table.index([])
    assert table._cid_of == {} and not any(table._cid_key) and not any(table._cid_rows)
    # Freed ids are handed out again: the id space is bounded by the
    # live rows like the table itself.
    table.index(_pods(50, start=20_000))
    assert len(table._cid_key) == 10_000 and len(table._cid_of) == 50
    # A content that left and returns is built again.
    again = _replicas([1, 1])
    calls: list[int] = []
    table.index(again)
    table.sync(fam, 0, _build(1, calls), {"vec": 1})
    table.index([])
    table.index(_replicas([1], start=5))
    table.sync(fam, 0, _build(1, calls), {"vec": 1})
    assert calls == [1, 1]


def test_token_move_rebuilds_once_per_content_and_counts_rows():
    table = PodTable()
    fam = table.family("f", _COLS)
    pods = _replicas([1, 2, 1, 1, 2])
    calls: list[int] = []
    table.index(pods)
    table.sync(fam, "old", _build(2, calls), {"vec": 2})
    table.index(pods + _replicas([2, 8], start=5))
    table.sync(fam, "new", _build(4, calls), {"vec": 4})
    assert calls == [1, 2] + [1, 2, 8]
    assert fam.take("vec").shape == (7, 4)
    assert fam.take("vec")[:, 3].tolist() == [1, 2, 1, 1, 2, 2, 8]
    assert table.rows_rebuilt == 5  # rows of pods the table held, as ever


def test_unshared_family_builds_every_row_and_odd_manifests_share_with_nobody():
    table = PodTable()
    shared = table.family("s", _COLS)
    own = table.family("o", (Column("name", object, None),))

    class Odd:  # nothing marshal can take
        pass

    odd = Odd()
    pods = _replicas([3, 3]) + [
        {"metadata": {"name": "x"}, "n": 5, "odd": odd},
        {"metadata": {"name": "y"}, "n": 5, "odd": odd},
    ]
    assert content_key(pods[2]) is None
    calls: list[int] = []
    table.index(pods)
    table.sync(shared, 0, _build(1, calls), {"vec": 1})
    table.sync(own, 0, lambda p: (p["metadata"]["name"],), shared=False)
    assert calls == [3, 5, 5] and table.rows_copied == 1
    assert own.take("name").tolist() == ["r0", "r1", "x", "y"]
    table.index([])
    assert table._cid_of == {} and not any(table._cid_rows)


@pytest.mark.parametrize(
    "field", ["name", "uid", "resourceVersion", "creationTimestamp", "generateName",
              "selfLink", "managedFields"],
)
def test_content_key_leaves_out_identity_and_nothing_else(field):
    base = {"metadata": {"namespace": "d", "labels": {"a": "b"}}, "spec": {"x": 1}}
    one = copy.deepcopy(base)
    one["metadata"][field] = "v1"
    two = copy.deepcopy(base)
    two["metadata"][field] = "v2"
    assert content_key(one) == content_key(two) == content_key(base)
    # The same word anywhere else is content.
    one["spec"][field] = "v1"
    two["spec"][field] = "v2"
    assert content_key(one) != content_key(two)
    # A field nobody listed tells two pods apart; so does a value's type.
    assert content_key({**base, "other": 1}) != content_key(base)
    assert content_key({"spec": {"x": 1}}) != content_key({"spec": {"x": True}})
    assert content_key({"spec": {"x": 1}}) != content_key({"spec": {"x": 1.0}})
    # Equal values in distinct string objects are the same content.
    assert content_key({"a": "".join(["x", "y"])}) == content_key({"a": "xy"})


def test_interner_is_append_only_until_its_valve():
    v = Interner()
    assert [v.intern(k) for k in ("a", "b", "a")] == [0, 1, 0]
    assert v.intern("c", item={"x": 1}) == 2 and v.items[2] == {"x": 1}
    v.valve()
    assert v.gen == 0 and len(v.items) == 3
    for i in range(Interner.LIMIT + 1):
        v.intern(("k", i))
    v.valve()
    assert v.gen == 1 and v.items == [] and v.intern("b") == 0


@pytest.mark.parametrize(
    "ids, want",
    [
        ([[3, -1], [1, 3], [0, 1]], [3, 1, 0]),
        ([[-1, -1]], []),
        ([], []),
        ([[5], [5], [2]], [5, 2]),
    ],
)
def test_first_seen_is_row_major_first_appearance(ids, want):
    arr = np.asarray(ids, dtype=np.int32).reshape(len(ids), len(ids[0]) if ids else 0)
    present = first_seen(arr)
    assert present.tolist() == want
    lut = rank_lut(present, 8)
    assert lut[-1] == -1
    assert [int(lut[p]) for p in want] == list(range(len(want)))


# -- what the Featurizer keeps in the table ---------------------------------


def _owned_containers(root) -> set[int]:
    """ids of every dict / list / set / tuple reachable from ``root``
    through Python containers and instance dicts (numpy arrays do not
    take part in gc, so object columns are checked separately)."""
    seen: set[int] = set()
    stack = [root]
    while stack:
        o = stack.pop()
        if id(o) in seen:
            continue
        seen.add(id(o))
        if isinstance(o, dict):
            stack.extend(o.keys())
            stack.extend(o.values())
        elif isinstance(o, (list, tuple, set, frozenset)):
            stack.extend(o)
        elif isinstance(o, np.ndarray):
            if o.dtype == object:
                stack.extend(x for x in o.ravel().tolist() if x is not None)
        elif hasattr(o, "__dict__"):
            stack.append(vars(o))
    return seen


def _assert_released(feat: Featurizer, departed: list[dict], live: int) -> None:
    table = feat._table
    assert table.live == live
    assert len(table) <= max(2 * live, 1)
    owned = _owned_containers(feat)
    for pod in departed:
        assert id(pod) not in owned
        holders = [r for r in gc.get_referrers(pod) if id(r) in owned]
        assert not holders, holders


def test_deleted_pods_are_released_and_the_table_stays_bounded():
    nodes, pods = random_cluster(5, 8, 120, bound_fraction=0.0)
    feat = Featurizer()
    feat.featurize(nodes, (), queue_pods=pods)
    assert feat.pod_rows_built == 120
    survivors = pods[100:]
    feat.featurize(nodes, (), queue_pods=survivors)
    assert (feat.pod_rows_built, feat.pod_rows_reused) == (120, 20)
    _assert_released(feat, pods[:100], live=20)
    # Scaling math no longer sees the departed pods' values.
    fresh = Featurizer().featurize(nodes, (), queue_pods=survivors)
    again = feat.featurize(nodes, (), queue_pods=survivors)
    assert again.units == fresh.units and again.resources == fresh.resources
    np.testing.assert_array_equal(again.pods.requests, fresh.pods.requests)


def test_a_universe_of_new_objects_refills_the_table_and_releases_the_old():
    """What a ``_LowerCache`` miss or invalidation hands the featurizer:
    every universe object is new."""
    nodes, pods = random_cluster(6, 8, 90, bound_fraction=0.0)
    feat = Featurizer()
    feat.featurize(nodes, (), queue_pods=pods)
    clones = copy.deepcopy(pods)
    out = feat.featurize(nodes, (), queue_pods=clones)
    assert feat.pod_rows_built == 180 and feat.pod_rows_rebuilt == 0
    _assert_released(feat, pods, live=90)
    fresh = Featurizer().featurize(nodes, (), queue_pods=clones)
    assert out.pods.keys == fresh.pods.keys
    np.testing.assert_array_equal(out.pods.requests, fresh.pods.requests)


def test_store_reset_between_imports_releases_the_first_import():
    """``PUT /api/v1/reset`` (server/reset.py): the store goes back to its
    boot content and the scheduler configuration is re-applied."""
    from ksim_tpu.scheduler.service import SchedulerService
    from ksim_tpu.state.cluster import ClusterStore

    store = ClusterStore()
    svc = SchedulerService(store)
    initial = store.dump()

    def load(seed: int) -> list[dict]:
        nodes, pods = random_cluster(seed, 6, 40, bound_fraction=0.0)
        for n in nodes:
            store.create("nodes", n)
        for p in pods:
            store.create("pods", p)
        svc.schedule_pending()
        return store.list("pods", copy_objs=False)

    first = load(11)
    assert svc._featurizers
    store.restore(initial)
    svc.reset_scheduler_config()
    load(12)
    assert svc._featurizers
    for feat in svc._featurizers.values():
        table = feat._table
        assert len(table) <= max(2 * table.live, 1)
        owned = _owned_containers(feat)
        for pod in first:
            assert id(pod) not in owned


def test_one_pod_call_gathers_one_row():
    """The extender path: ``queue_pods=[pod]`` against a persistent
    featurizer is the same code with a one-row index."""
    nodes = [make_node(f"n{i}", cpu="4", memory="8Gi") for i in range(3)]
    pod = make_pod("solo", cpu="500m", memory="256Mi")
    feat = Featurizer()
    a = feat.featurize(nodes, (), queue_pods=[pod])
    b = feat.featurize(nodes, (), queue_pods=[pod])
    assert (feat.pod_rows_built, feat.pod_rows_reused) == (1, 1)
    np.testing.assert_array_equal(a.pods.requests, b.pods.requests)
    assert a.pods.keys == b.pods.keys == ["default/solo"]
