"""Pods with volumes on the segment path (PR 49; the benchmark's deployment
``sperf-5k-csipvs``): the four volume plugins run inside the slot and their
counted state rides the window beside ``requested``.

The witnesses: the benchmark's plain reference
``benchmark/references/sampled_volumes.py`` (imports nothing of the program)
over the benchmark's own generator ``benchmark/kinds/sperf_pvs.py`` — once as
upstream shapes ``SchedulingCSIPVs`` and once with the shapes bent so that
EVERY one of the four filters rejects somewhere — and the per-pass path over a
churn stream with volume pods, pod deletes and a node replaced.
"""

from __future__ import annotations

import copy
import json
import os
import time

import numpy as np
import pytest

from ksim_tpu.engine import replay
from ksim_tpu.scenario import ScenarioRunner
from ksim_tpu.scenario.spec import operations_from_spec
from ksim_tpu.scheduler.service import SchedulerService
from ksim_tpu.state.cluster import ClusterStore
from ksim_tpu.state.featurizer import Featurizer
from tests.helpers import make_node, make_pod
from tests.test_node_sampling_device import lowered_text

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark")
COUNT_KEYS = ("eventsApplied", "podsScheduled", "unschedulableAttempts")
VOLUME_KEYS = ("volume_attempts", "volume_rejections", "volume_attached",
               "volume_headroom_min", "volume_objects")
SUM_KEYS = ("sampled_attempts", "nodes_visited", "nodes_scored", "sampling_zones")
POOL = "attachable-volumes-csi-ebs.csi.aws.com"
ZONE = "topology.kubernetes.io/zone"


@pytest.fixture(scope="module")
def bench():
    """The benchmark's modules, imported as the benchmark imports them."""
    mp = pytest.MonkeyPatch()
    mp.syspath_prepend(BENCH)
    import placements
    from kinds import sperf_pvs
    from references import sampled_volumes

    with open(os.path.join(BENCH, "configs", "sperf-5k-csipvs.json"), encoding="utf-8") as f:
        config = json.load(f)
    yield {"kind": sperf_pvs, "ref": sampled_volumes, "digest": placements.digest,
           "config": config}
    mp.undo()


def reduced(bench, nodes: int, init: int, measured: int) -> list:
    """The configuration's own workloadTemplate at a reduced workload."""
    gen = copy.deepcopy(bench["config"]["generator"])
    gen["workloads"]["reduced"] = {"initNodes": nodes, "initPods": init, "measurePods": measured}
    return bench["kind"].shuffle(7, bench["kind"].operations(gen, "reduced"))


def bend(ops: list) -> list:
    """The same stream with the shapes bent so that each of the four filters
    turns a node down: the limit lowered to 2, the nodes zone-labelled, PVs
    with a node affinity and with zone labels, one ReadWriteOncePod claim used
    by two pods, one EBS disk used by two pods."""
    ops = copy.deepcopy(ops)
    zones = ("a", "b", "c")
    objs = [op["createOperation"]["object"] for op in ops]
    for i, node in enumerate(o for o in objs if o["kind"] == "Node"):
        node["metadata"].setdefault("labels", {})[ZONE] = zones[i % 3]
        for part in ("allocatable", "capacity"):
            node["status"][part][POOL] = "2"
    for i, pv in enumerate(o for o in objs if o["kind"] == "PersistentVolume"):
        if i % 5 == 1:
            pv["spec"]["nodeAffinity"] = {"required": {"nodeSelectorTerms": [{"matchExpressions": [
                {"key": ZONE, "operator": "In", "values": [zones[i % 3]]}]}]}}
        if i % 5 == 2:
            pv["metadata"].setdefault("labels", {})[ZONE] = "__".join(zones[: 1 + i % 2])
    pods = [o for o in objs if o["kind"] == "Pod"]
    claims = {o["metadata"]["name"]: o for o in objs if o["kind"] == "PersistentVolumeClaim"}
    # Two pods of the last step share the first one's claim, made ReadWriteOncePod.
    first, second = pods[-1], pods[-2]
    shared = first["spec"]["volumes"][0]["persistentVolumeClaim"]["claimName"]
    claims[shared]["spec"]["accessModes"] = ["ReadWriteOncePod"]
    second["spec"]["volumes"][0]["persistentVolumeClaim"]["claimName"] = shared
    # Two pods name one EBS disk directly, beside their claims.
    for pod in (pods[-3], pods[-4]):
        pod["spec"]["volumes"].append({"name": "disk", "awsElasticBlockStore": {"volumeID": "vol-1"}})
    return ops


def through_the_job_plane(ops: list, simulator: dict) -> dict:
    from ksim_tpu.jobs.manager import JobManager

    jm = JobManager(workers=1)
    try:
        job = jm.submit({"spec": {"simulator": simulator, "scenario": {"operations": ops}}})
        deadline = time.monotonic() + 600
        while job.state not in ("succeeded", "failed", "cancelled"):
            assert time.monotonic() < deadline
            time.sleep(0.05)
        assert job.state == "succeeded", job.result
        return job.result
    finally:
        jm.shutdown()


SHAPES = ["as-upstream", "bent"]


@pytest.fixture(scope="module")
def streams(bench):
    ops = reduced(bench, nodes=120, init=60, measured=150)
    out = {}
    for shape in SHAPES:
        shaped = ops if shape == "as-upstream" else bend(ops)
        out[shape] = {"ops": shaped, "want": bench["ref"].replay(shaped),
                      "control": bench["ref"].replay(shaped, volumes=False)}
    return out


@pytest.mark.parametrize("shape", SHAPES)
def test_the_benchmarks_generator_through_the_job_plane_equals_the_plain_reference(
        bench, streams, shape):
    s = streams[shape]
    want = s["want"]
    doc = through_the_job_plane(s["ops"], bench["config"]["simulator"])
    rep = doc["replay"]
    assert rep["fallback_steps"] == 0 and rep["unsupported"] == {}, rep["unsupported"]
    assert [doc["result"][k] for k in COUNT_KEYS] == [want[k] for k in COUNT_KEYS]
    assert rep["placements_digest"] == bench["digest"](want["placements"])
    for key in VOLUME_KEYS + SUM_KEYS:
        assert rep[key] == want[key], key
    assert rep["volume_attempts"] == want["podsScheduled"] + want["unschedulableAttempts"] > 0
    assert doc["phases"]["service.featurize.volumes"] > 0
    if shape == "as-upstream":
        # No filter turns a node down: the blind control lands every pod alike.
        assert want["volume_rejections"] == 0 and rep["volume_classes"] == 1
        assert rep["volume_shared"] == 0
        assert s["control"]["placements"] == want["placements"]
        assert rep["volume_headroom_min"] == 39 - max(
            np.bincount([int(n.rsplit("-", 1)[1]) for n in want["placements"].values()]))
    else:
        # Every one of the four rejects somewhere, and the blind control
        # is another scheduler: its digest differs.
        assert all(n > 0 for n in want["volume_rejections_by"].values()), want["volume_rejections_by"]
        assert bench["digest"](s["control"]["placements"]) != rep["placements_digest"]
        assert rep["volume_classes"] > 3 and rep["volume_shared"] >= 3


def test_the_references_limit_columns_equal_its_per_node_loop(bench, streams):
    ops = streams["bent"]["ops"]
    slow = bench["ref"].replay(ops, fast=False)
    want = streams["bent"]["want"]
    for key in ("placements", "volume_rejections", "volume_rejections_by", "volume_attached",
                "volume_headroom_min", "nodes_visited"):
        assert slow[key] == want[key], key


# -- the segment path against the per-pass path ---------------------------------


def create(step: int, obj: dict) -> dict:
    return {"step": step, "createOperation": {"object": obj}}


def delete(step: int, kind: str, name: str) -> dict:
    return {"step": step, "deleteOperation": {
        "typeMeta": {"kind": kind}, "objectMeta": {"name": name, "namespace": "default"}}}


def pv(name: str, *, zone=None, affinity=None, ebs=None) -> dict:
    obj = {"apiVersion": "v1", "kind": "PersistentVolume", "metadata": {"name": name, "labels": {}},
           "spec": {"capacity": {"storage": "1Gi"}, "accessModes": ["ReadOnlyMany"]}}
    if ebs:
        obj["spec"]["awsElasticBlockStore"] = {"volumeID": ebs}
    else:
        obj["spec"]["csi"] = {"driver": "ebs.csi.aws.com", "volumeHandle": name}
    if zone:
        obj["metadata"]["labels"][ZONE] = zone
    if affinity:
        obj["spec"]["nodeAffinity"] = {"required": {"nodeSelectorTerms": [{"matchExpressions": [
            {"key": ZONE, "operator": "In", "values": [affinity]}]}]}}
    return obj


def pvc(name: str, volume: str, *, modes=("ReadOnlyMany",), sc=None) -> dict:
    spec = {"accessModes": list(modes), "resources": {"requests": {"storage": "1Gi"}},
            "volumeName": volume}
    if sc:
        spec["storageClassName"] = sc
    return {"apiVersion": "v1", "kind": "PersistentVolumeClaim",
            "metadata": {"name": name, "namespace": "default"}, "spec": spec}


def vpod(name: str, claims=(), extra=(), **kw) -> dict:
    pod = make_pod(name, cpu="100m", memory="100Mi", **kw)
    pod["spec"]["volumes"] = [{"name": f"v{i}", "persistentVolumeClaim": {"claimName": c}}
                              for i, c in enumerate(claims)] + list(extra)
    return pod


def node(name: str, zone: str, limit: int = 2) -> dict:
    return make_node(name, cpu="4", memory="16Gi", labels={ZONE: zone},
                     extra_alloc={POOL: str(limit)})


UNREAD = [{"name": "scratch", "emptyDir": {}},
          {"name": "kube-api-access", "projected": {"sources": [{"serviceAccountToken": {"path": "token"}}]}},
          {"name": "cfg", "configMap": {"name": "cfg"}}]


def churn_with_volumes(n_nodes: int = 6) -> list:
    """Volume pods arriving over four steps (each with its own PV and claim, a
    few PVs zoned or pinned by affinity), a ReadWriteOncePod claim and an EBS
    disk used twice, pod deletes, a node replaced while it holds attachments,
    and a late pod re-using a deleted pod's claim."""
    zones = ("a", "b", "c")
    ops = [create(0, node(f"n{i}", zones[i % 3])) for i in range(n_nodes)]
    for step in range(1, 5):
        for i in range(5):
            nm = f"s{step}-{i}"
            ops += [create(step, pv("pv-" + nm, zone=zones[i % 3] if i % 2 else None,
                                    affinity=zones[(i + 1) % 3] if i == 4 else None)),
                    create(step, pvc("c-" + nm, "pv-" + nm)),
                    create(step, vpod("p-" + nm, ["c-" + nm], extra=UNREAD[:1]))]
    ops += [create(2, pv("pv-rwop")), create(2, pvc("c-rwop", "pv-rwop", modes=("ReadWriteOncePod",))),
            create(2, vpod("rw-a", ["c-rwop"])), create(3, vpod("rw-b", ["c-rwop"]))]
    disk = [{"name": "d", "awsElasticBlockStore": {"volumeID": "vol-1"}}]
    ops += [create(2, vpod("ebs-a", extra=disk)), create(3, vpod("ebs-b", extra=disk))]
    ops += [delete(3, "Pod", "p-s1-0"), delete(3, "Pod", "p-s1-1"), delete(4, "Node", "n0"),
            create(4, node(f"n{n_nodes}", "a")), create(5, vpod("late", ["c-s1-0"])),
            delete(6, "Pod", "rw-a"), create(7, vpod("after", ["c-rwop"]))]
    return ops


def run_both(ops: list, **kw):
    out = []
    for device in (True, False):
        store = ClusterStore()
        runner = ScenarioRunner(store=store, preemption=False, pod_bucket_min=32,
                                device_replay=device, **kw)
        result = runner.run(iter(operations_from_spec({"operations": ops})))
        placed = {p["metadata"]["name"]: p.get("spec", {}).get("nodeName")
                  for p in store.list("pods")}
        out.append((runner, result, placed, store))
    return out


def attached_in(store) -> "tuple[int, int]":
    """(distinct attachments the nodes hold, smallest limit less attached),
    counted from the store's bound pods."""
    claims = {c["metadata"]["name"]: c for c in store.list("persistentvolumeclaims")}
    held: dict = {}
    for pod in store.list("pods"):
        on = pod.get("spec", {}).get("nodeName")
        for vol in pod["spec"].get("volumes") or [] if on else []:
            claim = (vol.get("persistentVolumeClaim") or {}).get("claimName")
            if claim:
                held.setdefault(on, set()).add("pv:" + claims[claim]["spec"]["volumeName"])
    limits = {n["metadata"]["name"]: int(n["status"]["allocatable"][POOL]) for n in store.list("nodes")}
    return (sum(len(v) for v in held.values()),
            min(limit - len(held.get(name, ())) for name, limit in limits.items()))


@pytest.mark.parametrize("sampling, record", [(False, "selection"), (True, "selection"), (False, "full")],
                         ids=["every-node", "sampled", "full-record"])
def test_the_segment_path_equals_the_per_pass_path_under_deletes_and_a_node_replacement(
        monkeypatch, sampling, record):
    n_nodes = 6
    if sampling:
        # Small clusters sample: the floor lowered on the service (k = 4 of 6).
        monkeypatch.setattr(SchedulerService, "_MIN_FEASIBLE_NODES_TO_FIND", 4)
    ops = churn_with_volumes(n_nodes)
    (dev, dres, dplaced, dstore), (_pp, pres, pplaced, pstore) = run_both(
        ops, node_sampling=sampling, record=record)
    driver = dev.replay_driver
    assert driver.fallback_steps == 0 and not driver.unsupported, driver.unsupported
    assert dplaced == pplaced
    if record == "full":
        # Every node's verdict, the volume filters' reasons among them.
        def verdicts(store):
            return {p["metadata"]["name"]: (p["metadata"].get("annotations") or {}).get(
                "kube-scheduler-simulator.sigs.k8s.io/filter-result") for p in store.list("pods")}
        assert verdicts(dstore) == verdicts(pstore)
        assert any("max volume count" in (v or "") for v in verdicts(dstore).values())
    assert (dres.pods_scheduled, dres.unschedulable_attempts) == (
        pres.pods_scheduled, pres.unschedulable_attempts)
    assert dres.unschedulable_attempts > 0  # the limit of 2 and the conflicts bind
    plan = driver._last_plan
    assert plan.statics.volumes and (plan.statics.sample > 0) == sampling
    stats = driver.stats()
    # The carried state at the last step is what the store's bound pods come
    # to: the deleted pods' attachments released, the replaced node's gone
    # with it and its pods' re-counted where they landed again.  (The EBS
    # disk has the aws-ebs pool, which no node limits: counted apart.)
    claimed, room = attached_in(dstore)
    direct = len({dplaced[p] for p in ("ebs-a", "ebs-b") if dplaced[p]})
    assert stats["volume_attached"] == claimed + direct
    assert stats["volume_headroom_min"] == room
    assert stats["volume_attempts"] == dres.pods_scheduled + dres.unschedulable_attempts
    # (One window of K = 16 holds the whole stream; full-record windows are
    # K = 4 and none of them holds all five shared names at once.)
    assert stats["volume_rejections"] > 0
    assert stats["volume_shared"] == (5 if record == "selection" else 4)
    assert stats["volume_objects"] == sum(
        len(dstore.list(k)) for k in ("persistentvolumes", "persistentvolumeclaims", "storageclasses"))
    assert sum(e["volume_attempts"] for e in driver.lower_log) == stats["volume_attempts"]
    assert len(driver.lower_log) == (1 if record == "selection" else 2)


def test_pods_whose_volumes_no_plugin_reads_lower_to_the_volume_free_program(monkeypatch):
    import jax

    from ksim_tpu.scenario.runner import Operation

    def ops_with(volumes):
        ops = [Operation(step=0, op="create", kind="nodes",
                         obj=make_node(f"n-{i}", cpu="4", memory="16Gi")) for i in range(4)]
        for step in range(1, 4):
            for j in range(3):
                pod = make_pod(f"p-{step}-{j}", cpu="500m", memory="256Mi")
                if volumes:
                    pod["spec"]["volumes"] = copy.deepcopy(volumes)
                ops.append(Operation(step=step, op="create", kind="pods", obj=pod))
        return ops

    prev = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", False)
    try:
        with monkeypatch.context() as mp:  # ``lowered_text`` stops the dispatch
            plain, plain_statics = lowered_text(mp, ops_with(None), preemption=True)
            unread, statics = lowered_text(mp, ops_with(UNREAD), preemption=True)
            assert not statics.volumes and statics == plain_statics
            assert unread == plain
            # The control: one claim among them is another program.
            claimed, c_statics = lowered_text(
                mp, ops_with(UNREAD + [{"name": "v", "persistentVolumeClaim": {"claimName": "c"}}]),
                preemption=True)
            assert c_statics.volumes and claimed != plain
    finally:
        jax.config.update("jax_enable_x64", prev)
    # And end to end: no fallback, no volume state, the per-pass placements.
    json_ops = [create(0, make_node(f"n-{i}", cpu="4", memory="16Gi")) for i in range(4)]
    for step in range(1, 4):
        json_ops += [create(step, vpod(f"p-{step}-{j}", extra=UNREAD)) for j in range(3)]
    json_ops.append(create(1, pv("unused")))  # a volume object in the store changes nothing
    (dev, _dres, dplaced, _), (_pp, _pres, pplaced, _) = run_both(json_ops)
    driver = dev.replay_driver
    assert driver.fallback_steps == 0 and not driver.unsupported
    assert dplaced == pplaced and not driver._last_plan.statics.volumes
    assert driver.stats()["volume_attempts"] == 0 and driver.stats()["volume_headroom_min"] is None


def _base(step_pods: dict) -> list:
    ops = [create(0, node(f"n{i}", "a", limit=4)) for i in range(3)]
    for step, objs in step_pods.items():
        ops += [create(step, o) if isinstance(o, dict) and "kind" in o else o for o in objs]
    return ops


WFFC = {"apiVersion": "storage.k8s.io/v1", "kind": "StorageClass", "metadata": {"name": "wffc"},
        "provisioner": "ebs.csi.aws.com", "volumeBindingMode": "WaitForFirstConsumer"}
FALLBACKS = {
    # a generic ephemeral volume: its claim is made after the pod
    "ephemeral_volume_claim": (dict(preemption=False), {
        1: [vpod("e", extra=[{"name": "scratch", "ephemeral": {"volumeClaimTemplate": {"spec": {}}}}])]}),
    # an unbound claim of a WaitForFirstConsumer class: PreBind would bind it
    "unbound_wffc_claim": (dict(preemption=False), {
        1: [WFFC, pvc("w", "", sc="wffc"), vpod("p", ["w"])]}),
    # a volume pod in a window whose victim search is live (two priorities)
    "volume_victim_search": (dict(preemption=True), {
        1: [pv("pv-a"), pvc("c-a", "pv-a"), vpod("p", ["c-a"], priority=10), vpod("q", [], priority=0)]}),
    # the claim arrives a step after the pod that names it
    "volume_object_order": (dict(preemption=False), {
        1: [vpod("p", ["late"])], 2: [pv("pv-l"), pvc("late", "pv-l"), vpod("q", [])]}),
    # a volume object created under a name the store holds
    "volume_name_reuse": (dict(preemption=False), {
        1: [pv("pv-a"), vpod("p", [])], 17: [pv("pv-a"), vpod("q", [])]}),
    # a volume object deleted inside the stream
    "op:delete/persistentvolumes": (dict(preemption=False), {
        1: [pv("pv-a"), pvc("c-a", "pv-a"), vpod("p", ["c-a"])],
        2: [delete(2, "PersistentVolume", "pv-a"), create(2, vpod("q", []))]}),
}


@pytest.mark.parametrize("reason", sorted(FALLBACKS))
def test_what_stays_a_fallback_does_so_under_a_reason_that_names_it(reason):
    kw, steps = FALLBACKS[reason]
    assert reason in replay.FALLBACK_REASONS or reason.startswith(replay.FALLBACK_REASON_PREFIXES)
    assert not {"volumes", "volume_objects"} & replay.FALLBACK_REASONS
    ops = _base(steps)
    out = []
    for device in (True, False):
        store = ClusterStore()
        runner = ScenarioRunner(store=store, pod_bucket_min=32, device_replay=device, **kw)
        try:
            result = runner.run(iter(operations_from_spec({"operations": ops})))
        except Exception as e:  # the per-pass path's own verdict on a bad stream
            out.append((runner, type(e).__name__, None))
            continue
        out.append((runner, (result.pods_scheduled, result.unschedulable_attempts),
                    {p["metadata"]["name"]: p.get("spec", {}).get("nodeName")
                     for p in store.list("pods")}))
    (dev, dcounts, dplaced), (_pp, pcounts, pplaced) = out
    assert reason in dev.replay_driver.unsupported, dev.replay_driver.unsupported
    assert dev.replay_driver.fallback_steps > 0
    assert dcounts == pcounts and dplaced == pplaced


# -- the encoding ---------------------------------------------------------------


def test_pvs_of_one_template_are_one_row_and_cost_no_pair_walk(monkeypatch):
    from ksim_tpu.state import volumes as sv

    calls = {"affinity": 0, "zone": 0}
    real_aff, real_zone = sv._pv_affinity_admits, sv._pv_zone_admits
    monkeypatch.setattr(sv, "_pv_affinity_admits",
                        lambda *a: calls.__setitem__("affinity", calls["affinity"] + 1) or real_aff(*a))
    monkeypatch.setattr(sv, "_pv_zone_admits",
                        lambda *a: calls.__setitem__("zone", calls["zone"] + 1) or real_zone(*a))
    nodes = [node(f"n{i}", "abc"[i % 3], limit=39) for i in range(12)]
    n_pods = 40
    pvs = [pv(f"pv-{i}") for i in range(n_pods)]
    pvcs = [pvc(f"c-{i}", f"pv-{i}") for i in range(n_pods)]
    queue = [vpod(f"p-{i}", [f"c-{i}"]) for i in range(n_pods)]
    vt = Featurizer().featurize(nodes, [], queue_pods=queue, pvs=pvs, pvcs=pvcs).aux["volumes"]
    # One template: one class, no (PV, node) pair evaluated, no column.
    assert vt.live and vt.n_classes == 1 and vt.n_shared == 0 and calls == {"affinity": 0, "zone": 0}
    assert vt.pv_node_ok.shape[0] == vt.attached_init.shape[1] == 8  # the vocabulary floor
    assert vt.pod_excl[:n_pods].sum() == n_pods and vt.pod_vol.sum() == 0
    # Zoned and pinned PVs: one row a distinct (affinity, zone labels), each
    # evaluated once a node — not once a PV and node.
    for i, v in enumerate(pvs):
        if i % 2:
            v = pvs[i] = pv(f"pv-{i}", zone="abc"[i % 3], affinity="abc"[(i + 1) % 3] if i % 4 == 1 else None)
    vt2 = Featurizer().featurize(nodes, [], queue_pods=queue, pvs=pvs, pvcs=pvcs).aux["volumes"]
    classes = {sv._pv_class(v) for v in pvs}
    assert vt2.n_classes == len(classes) < n_pods / 3
    assert calls == {"affinity": sum(1 for aff, _z in classes if aff) * len(nodes),
                     "zone": sum(1 for _a, zones in classes if zones) * len(nodes)}
    # A volume two pods share gets a column; the others stay counts.
    queue[1]["spec"]["volumes"][0]["persistentVolumeClaim"]["claimName"] = "c-0"
    vt3 = Featurizer().featurize(nodes, [], queue_pods=[copy.deepcopy(p) for p in queue],
                                 pvs=pvs, pvcs=pvcs).aux["volumes"]
    assert vt3.n_shared == 1 and vt3.pod_vol[:2].sum() == 2
    assert vt3.pod_excl[:n_pods].sum() == n_pods - 2
