"""One content key a store pod in a cold lowering.

The cold universe walk (``engine/replay.py _cold_universe``) keys each
store pod once (``boundagg.content_key``), screens the first pod of a key
in full and every other for its phase alone, and hands the keys to the
featurizer, whose pod table (``state/podtable.py``) and bound contents
(``state/boundagg.py``) take them instead of keying again.  Held here to
what the parent did: a featurizer that marshals every key itself, and the
per-pod screen in store order.
"""

from __future__ import annotations

import copy
import json

import jax
import numpy as np
import pytest

from ksim_tpu.engine import replay
from ksim_tpu.scenario import ScenarioRunner
from ksim_tpu.scenario.runner import Operation
from ksim_tpu.scheduler.service import queue_sort_key
from ksim_tpu.state import boundagg, podtable
from ksim_tpu.state.cluster import ClusterStore
from ksim_tpu.state.featurizer import Featurizer
from ksim_tpu.state.priorities import build_priority_resolver
from tests.helpers import make_node, make_pod

N_NODES = 8
REPLICAS = {"a": 250, "b": 60, "c": 40}
IDENTITY = ("name", "uid", "resourceVersion", "creationTimestamp")


@pytest.fixture(autouse=True)
def _f32_fast_mode():
    jax.config.update("jax_enable_x64", False)
    yield
    jax.config.update("jax_enable_x64", True)


def _replica(template: str, i: int, node: "str | None") -> dict:
    """Replica ``i`` of one of three templates; bound and Running on
    ``node``, or as a controller creates it (no ``status`` at all)."""
    kw: dict = {"labels": {"app": template}, "node_name": node or "", "phase": "Running" if node else ""}
    if template == "a":
        pod = make_pod(f"a-{i}", "100m", "64Mi", topology_spread_constraints=[{
            "maxSkew": 1, "topologyKey": "zone", "whenUnsatisfiable": "ScheduleAnyway",
            "labelSelector": {"matchLabels": {"app": "a"}}}], **kw)
    elif template == "b":
        pod = make_pod(f"b-{i}", "250m", "128Mi", affinity={"podAntiAffinity": {
            "preferredDuringSchedulingIgnoredDuringExecution": [{"weight": 10, "podAffinityTerm": {
                "labelSelector": {"matchLabels": {"app": "b"}},
                "topologyKey": "kubernetes.io/hostname"}}]}}, **kw)
    else:
        pod = make_pod(f"c-{i}", "50m", None, node_selector={"pool": "x"}, **kw)
    pod["metadata"]["creationTimestamp"] = f"2025-01-01T00:{i % 60:02d}:00Z"
    if node is None:
        del pod["status"]
    return pod


def _stocked(edit=None) -> ClusterStore:
    """Eight nodes and 350 bound pods of three templates; ``edit(pods)``
    bends some of them before the store takes them over."""
    store = ClusterStore()
    for i in range(N_NODES):
        store.create("nodes", make_node(f"n-{i}", "64", "256Gi", labels={
            "zone": f"z{i % 3}", "kubernetes.io/hostname": f"n-{i}", "pool": "x"}))
    pods = [_replica(t, i, f"n-{i % N_NODES}") for t, n in REPLICAS.items() for i in range(n)]
    if edit is not None:
        edit({p["metadata"]["name"]: p for p in pods})
    for p in pods:
        store.create("pods", p, copy_obj=False)
    return store


def _driver(store: ClusterStore) -> replay.ReplayDriver:
    runner = ScenarioRunner(store=store, device_replay=True)
    return replay.ReplayDriver(runner.store, runner.service)


def _cold(store: ClusterStore):
    """The cold walk's products for ``store``: the universe in queue
    order and the handed keys."""
    driver = _driver(store)
    cur = store.list("pods", copy_objs=False)
    decorated, keys = driver._cold_universe(
        cur, driver.service._scheduler_names, build_priority_resolver(()))
    decorated.sort()
    return driver, [d[2] for d in decorated], keys


def _created(n: int = 5, start: int = 1000) -> list[dict]:
    return [_replica("a", start + i, None) for i in range(n)]


def _featurized(store, universe, keys):
    feat = Featurizer()
    snap = feat.featurize(
        store.list("nodes", copy_objs=False), (), queue_pods=universe,
        bound_pods=store.pods_with_node(), content_keys=keys)
    return feat, snap


def _partition(ids) -> set:
    groups: dict = {}
    for pos, c in enumerate(ids):
        groups.setdefault(c, []).append(pos)
    return {frozenset(g) for g in groups.values()}


def _manifest_class(pod: dict, empty_status_is_absent: bool) -> str:
    """The oracle's key, derived here from the manifest and not from the
    program's: what is left once identity, the node and the phase are out."""
    meta = {k: v for k, v in pod["metadata"].items() if k not in IDENTITY}
    spec = {k: v for k, v in pod["spec"].items() if k != "nodeName"}
    rest = {k: v for k, v in pod.items() if k not in ("metadata", "spec", "status")}
    status = {k: v for k, v in pod.get("status", {}).items() if k != "phase"}
    if "status" not in pod or (empty_status_is_absent and not status):
        status = None
    return json.dumps([meta, spec, rest, status], sort_keys=True, default=id)


def _assert_same_rows(one: Featurizer, two: Featurizer) -> None:
    assert one._table._fams.keys() == two._table._fams.keys()
    for name, fam in one._table._fams.items():
        for col in fam.cols:
            got, want = fam.take(col), two._table._fams[name].take(col)
            if got.dtype == object:
                assert got.tolist() == want.tolist(), (name, col)
            else:
                np.testing.assert_array_equal(got, want, err_msg=f"{name}.{col}")


def _assert_same_tensors(one, two) -> None:
    assert one.pods.keys == two.pods.keys
    for part in ("nodes", "pods"):
        for field, want in vars(getattr(two, part)).items():
            if isinstance(want, np.ndarray):
                np.testing.assert_array_equal(getattr(getattr(one, part), field), want, err_msg=field)
    for key, aux in two.aux.items():
        for field, want in vars(aux).items():
            if isinstance(want, np.ndarray):
                np.testing.assert_array_equal(getattr(one.aux[key], field), want, err_msg=f"{key}.{field}")


# -- (a) the partition -------------------------------------------------------


def test_handed_keys_cut_the_pods_as_marshalled_keys_do_but_for_the_empty_status():
    store = _stocked()
    _d, cleaned, keys = _cold(store)
    created = _created()
    # The replay bisects a window's creates into the sorted universe:
    # the same list.
    universe = sorted(cleaned + created, key=lambda p: queue_sort_key(p, None))
    handed, snap_h = _featurized(store, universe, keys)
    marshalled, snap_m = _featurized(store, universe, None)

    def rows(feat):
        return _partition(feat._table._cid[feat._table.idx].tolist())

    # The parent's cut tells an empty ``status`` from an absent one: the
    # created replicas of template a sit beside its 250 bound replicas.
    assert rows(marshalled) == _partition(_manifest_class(p, False) for p in universe)
    assert rows(handed) == _partition(_manifest_class(p, True) for p in universe)
    assert len(rows(marshalled)) == 4 and len(rows(handed)) == 3
    assert handed.pod_rows_copied == marshalled.pod_rows_copied + 1 == len(universe) - 3
    assert handed.pod_rows_built == marshalled.pod_rows_built == len(universe)

    bound = store.pods_with_node()
    of_h = [handed._contents.of[id(p)] for p in bound]
    of_m = [marshalled._contents.of[id(p)] for p in bound]
    assert _partition(of_h) == _partition(of_m) == _partition(
        _manifest_class(p, True) for p in bound)
    assert handed.bound_records_built == marshalled.bound_records_built == 5 * 3
    assert handed.bound_records_shared == marshalled.bound_records_shared == 5 * (350 - 3)
    # What was keyed where: nothing but the created pods in the handed
    # call, every queue pod and every bound pod in the other.
    assert (handed._table.keys_built, handed._contents.keys_built) == (len(created), 0)
    assert (marshalled._table.keys_built, marshalled._contents.keys_built) == (355, 350)
    assert handed.content_keys_built == 5 and marshalled.content_keys_built == 705
    _assert_same_rows(handed, marshalled)
    _assert_same_tensors(snap_h, snap_m)


# -- (b) the screen ----------------------------------------------------------


def _bend(reason: str, pod: dict) -> None:
    if reason == "host_ports":
        pod["spec"]["containers"][0]["ports"] = [{"containerPort": 80, "hostPort": 8080}]
    elif reason == "ephemeral_volume_claim":
        pod["spec"]["volumes"] = [{"name": "v", "ephemeral": {"volumeClaimTemplate": {}}}]
    elif reason == "foreign_scheduler":
        pod["spec"]["schedulerName"] = "someone-else"
    elif reason == "scheduling_gates":
        pod["spec"]["schedulingGates"] = [{"name": "wait"}]
    else:
        assert reason == "terminal_phase"
        pod["status"]["phase"] = "Succeeded"


REASONS = ["host_ports", "ephemeral_volume_claim", "foreign_scheduler", "scheduling_gates",
           "terminal_phase"]


@pytest.mark.parametrize("reason", REASONS)
def test_cold_walk_raises_the_per_pod_screens_reason_for_the_first_offender(reason):
    """One replica among 250 is bent, and a LATER pod in store order is
    bent another way: the reason is the first offender's, as the screen
    of every pod in store order gives it."""
    other = REASONS[(REASONS.index(reason) + 1) % len(REASONS)]

    def edit(pods):
        _bend(reason, pods["a-137"])
        _bend(other, pods["a-93"])  # "a-93" sorts after "a-137"

    store = _stocked(edit)
    driver = _driver(store)
    names = driver.service._scheduler_names
    cur = store.list("pods", copy_objs=False)
    want = next(r for r in (driver._pod_supported(p, names) for p in cur) if r is not None)
    assert want == reason
    with pytest.raises(replay._Unsupported) as raised:
        driver._cold_universe(cur, names, build_priority_resolver(()))
    assert raised.value.reason == reason
    # The walk stopped there, having screened one pod a manifest.
    first = next(i for i, p in enumerate(cur) if p["metadata"]["name"] == "a-137")
    assert driver.content_keys_built == first + 1
    assert driver.universe_screens == (1 if reason == "terminal_phase" else 2)
    # Through the lowering the window falls back under that reason.
    ops = [Operation(step=1, op="create", kind="pods", obj=p) for p in _created(2)]
    assert driver.prepare_segment([ops]) is None
    assert driver.unsupported == {reason: 1}


def _vary_what_the_key_leaves_out(pods: dict) -> None:
    """Every replica differs from its template's others in every field
    ``boundagg.content_key`` leaves out."""
    for i, pod in enumerate(pods.values()):
        pod["metadata"].update(
            uid=f"uid-{i}", resourceVersion=str(1000 + i), generateName=f"gen-{i}-",
            selfLink=f"/api/v1/pods/{i}", managedFields=[{"manager": f"m-{i}"}])
        pod["status"].update(
            phase=("Running", "Pending", "Unknown")[i % 3], podIP=f"10.1.{i // 250}.{i % 250}",
            startTime=f"2025-01-01T01:{i % 60:02d}:00Z", nominatedNodeName=f"n-{i % 5}",
            conditions=[{"type": "Ready", "status": str(bool(i % 2))}])


@pytest.mark.parametrize("reason", [None] + REASONS)
def test_cold_walk_agrees_with_the_per_pod_screen_where_replicas_differ_in_all_the_key_leaves_out(reason):
    def edit(pods):
        _vary_what_the_key_leaves_out(pods)
        if reason is not None:
            _bend(reason, pods["b-41"])

    store = _stocked(edit)
    driver = _driver(store)
    names = driver.service._scheduler_names
    cur = store.list("pods", copy_objs=False)
    want = next((r for r in (driver._pod_supported(p, names) for p in cur) if r is not None), None)
    assert want == reason
    if reason is None:
        _decorated, keys = driver._cold_universe(cur, names, build_priority_resolver(()))
        assert driver.universe_screens == 3 and driver.content_keys_built == 350
        assert len({keys[id(p)] for p in cur}) == 3
    else:
        with pytest.raises(replay._Unsupported) as raised:
            driver._cold_universe(cur, names, build_priority_resolver(()))
        assert raised.value.reason == reason


class _Spy(dict):
    """A dict that notes which of its keys were asked for; a read of the
    whole (iteration, a copy) notes ``*``."""

    def __init__(self, data, seen, path):
        super().__init__(data)
        self._seen, self._path = seen, path

    def _note(self, key):
        self._seen.add((*self._path, key))

    def get(self, key, default=None):
        self._note(key)
        return super().get(key, default)

    def __getitem__(self, key):
        self._note(key)
        return super().__getitem__(key)

    def __contains__(self, key):
        self._note(key)
        return super().__contains__(key)

    def __iter__(self):
        self._note("*")
        return super().__iter__()

    def keys(self):
        self._note("*")
        return super().keys()

    def items(self):
        self._note("*")
        return super().items()

    def values(self):
        self._note("*")
        return super().values()


@pytest.mark.parametrize("reason", [None] + REASONS)
def test_the_screen_reads_nothing_the_key_leaves_out_but_the_phase(reason):
    """What lets the walk screen once a key: of identity, ``spec.nodeName``
    and ``status``, ``_pod_supported`` asks for ``status.phase`` alone —
    on a pod that passes (every test runs) and on one of each reason."""
    pod = _replica("a", 0, "n-0")
    _vary_what_the_key_leaves_out({"a-0": pod})
    pod["spec"]["volumes"] = [{"name": "v", "emptyDir": {}}]
    if reason is not None:
        _bend(reason, pod)
    seen: set = set()
    spied = _Spy(pod, seen, ())
    for part in ("metadata", "spec", "status"):
        dict.__setitem__(spied, part, _Spy(pod[part], seen, (part,)))
    names = _driver(_stocked()).service._scheduler_names
    assert replay.ReplayDriver._pod_supported(spied, names) == reason
    left_out = {("metadata", k) for k in podtable._IDENTITY} | {("metadata", "*"), ("spec", "nodeName"), ("spec", "*"), ("*",)}
    assert not seen & left_out, seen & left_out
    assert {path for path in seen if path[0] == "status"} <= {("status",), ("status", "phase")}
    if reason in (None, "host_ports", "ephemeral_volume_claim", "terminal_phase"):
        assert ("status", "phase") in seen


# -- (c) a status that is not empty ------------------------------------------


def test_a_status_with_pod_ip_keeps_the_pod_tables_own_key():
    def edit(pods):
        for i in range(30):
            pods[f"b-{i}"]["status"].update(podIP=f"10.0.0.{i}", startTime="2025-01-01T00:00:00Z")
        # Two with one status: they share a row, as on the parent.
        pods["b-1"]["status"]["podIP"] = pods["b-0"]["status"]["podIP"]

    store = _stocked(edit)
    _d, universe, keys = _cold(store)
    # The walk hands the table no key for a queue object whose ``status``
    # holds something; the bound object's goes to the bound contents.
    by_name = {p["metadata"]["name"]: p for p in universe}
    assert id(by_name["b-3"]) not in keys and keys[id(by_name["b-40"])] is not None
    assert all(keys[id(p)] is not None for p in store.pods_with_node())
    handed, snap_h = _featurized(store, universe, keys)
    marshalled, snap_m = _featurized(store, universe, None)
    # The table keyed those thirty itself; the bound side took all 350.
    assert (handed._table.keys_built, handed._contents.keys_built) == (30, 0)
    cut = _partition(handed._table._cid[handed._table.idx].tolist())
    assert cut == _partition(marshalled._table._cid[marshalled._table.idx].tolist())
    assert cut == _partition(_manifest_class(p, True) for p in universe)
    assert len(cut) == 3 + 29
    assert handed.pod_rows_copied == marshalled.pod_rows_copied == 350 - 32
    # Bound contents leave ``status`` out, as ever.
    assert len(handed._contents) == len(marshalled._contents) == 3
    _assert_same_rows(handed, marshalled)
    _assert_same_tensors(snap_h, snap_m)


# -- (d) a manifest without a key --------------------------------------------


def test_a_manifest_marshal_refuses_is_screened_itself_and_shares_with_nobody():
    class Odd:  # nothing marshal can take
        pass

    odd = Odd()

    def edit(pods):
        for name in ("c-5", "c-6", "c-7"):
            pods[name]["metadata"]["annotations"] = {"odd": odd}

    store = _stocked(edit)
    driver, universe, keys = _cold(store)
    odd_pods = [p for p in store.pods_with_node() if "annotations" in p["metadata"]]
    assert len(odd_pods) == 3 and all(boundagg.content_key(p) is None for p in odd_pods)
    assert driver.universe_screens == 3 + 3 and driver.content_keys_built == 350
    assert all(keys[id(p)] is None for p in odd_pods)
    handed, snap_h = _featurized(store, universe, keys)
    marshalled, snap_m = _featurized(store, universe, None)
    assert handed.content_keys_built == 0
    cut = _partition(handed._table._cid[handed._table.idx].tolist())
    assert cut == _partition(marshalled._table._cid[marshalled._table.idx].tolist())
    assert sum(len(g) == 1 for g in cut) == 3 and len(cut) == 6
    assert len(handed._contents) == len(marshalled._contents) == 6
    assert handed.bound_records_built == marshalled.bound_records_built == 5 * 6
    _assert_same_rows(handed, marshalled)
    _assert_same_tensors(snap_h, snap_m)
    # One of them hiding a host port is found, whatever its replicas passed.
    def edit_port(pods):
        edit(pods)
        _bend("host_ports", pods["c-7"])

    with pytest.raises(replay._Unsupported) as raised:
        _cold(_stocked(edit_port))
    assert raised.value.reason == "host_ports"


# -- (e) the counts ----------------------------------------------------------


def test_counts_one_key_a_store_pod_one_screen_a_manifest_and_none_on_a_cache_hit():
    store = _stocked()
    ops = [Operation(step=1, op="create", kind="pods", obj=p) for p in _created(5)]
    ops += [Operation(step=2, op="create", kind="pods", obj=p) for p in _created(3, start=2000)]
    runner = ScenarioRunner(store=store, device_replay=True, device_segment_steps=1)
    result = runner.run(copy.deepcopy(ops))
    driver = runner.replay_driver
    assert driver.fallback_steps == 0 and not driver.unsupported, driver.unsupported
    assert result.pods_scheduled == 8
    log = driver.lower_log
    assert [e["cache_hit"] for e in log] == [False, True]
    # The cold window: one key a store pod (where the parent's two tables
    # made two) and one a created pod; one screen a distinct manifest
    # (where every pod was screened).
    assert (log[0]["keys_built"], log[0]["screens"]) == (350 + 5, 3)
    assert log[0]["rows_copied"] == 355 - 3
    # The cache hit screens nothing and keys what is new to it: its three
    # creates, and the five re-wrapped pods the first window bound.
    assert (log[1]["keys_built"], log[1]["screens"]) == (3 + 5, 0)
    stats = driver.stats()
    assert stats["content_keys_built"] == 355 + 8 and stats["universe_screens"] == 3
    # Without the hand-over the same featurizer call keys every pod twice.
    fresh = _stocked()
    _d, universe, _keys = _cold(fresh)
    marshalled, _snap = _featurized(fresh, universe, None)
    assert marshalled.content_keys_built == 2 * 350
