"""Volume-family plugins (VolumeBinding, VolumeZone, NodeVolumeLimits,
VolumeRestrictions) — kernel vs oracle on hand-built scenarios covering
every failure branch, plus the service end-to-end flow."""

from __future__ import annotations

import json

from ksim_tpu.engine import Engine
from ksim_tpu.engine.annotations import FILTER_RESULT_KEY
from ksim_tpu.engine.profiles import default_plugins
from ksim_tpu.plugins import oracle
from ksim_tpu.plugins.volumes import (
    ERR_BIND_CONFLICT,
    ERR_MAX_VOLUME_COUNT,
    ERR_NODE_CONFLICT,
    ERR_RWOP_CONFLICT,
    ERR_UNBOUND_IMMEDIATE,
    ERR_ZONE_CONFLICT,
    ERR_DISK_CONFLICT,
)
from ksim_tpu.scheduler.service import SchedulerService
from ksim_tpu.state.cluster import ClusterStore
from ksim_tpu.state.featurizer import Featurizer
from tests.helpers import make_node, make_pod


def _pvc(name, *, volume_name="", sc="", modes=("ReadWriteOnce",), storage="1Gi"):
    return {
        "apiVersion": "v1", "kind": "PersistentVolumeClaim",
        "metadata": {"name": name, "namespace": "default"},
        "spec": {
            "accessModes": list(modes),
            "storageClassName": sc,
            "volumeName": volume_name,
            "resources": {"requests": {"storage": storage}},
        },
    }


def _pv(name, *, zone=None, affinity_zone=None, capacity="10Gi", sc="",
        phase="Available", claim_ref=None, source=None):
    pv = {
        "apiVersion": "v1", "kind": "PersistentVolume",
        "metadata": {"name": name, "labels": {}},
        "spec": {
            "capacity": {"storage": capacity},
            "accessModes": ["ReadWriteOnce"],
            "storageClassName": sc,
        },
        "status": {"phase": phase},
    }
    if zone:
        pv["metadata"]["labels"]["topology.kubernetes.io/zone"] = zone
    if affinity_zone:
        pv["spec"]["nodeAffinity"] = {"required": {"nodeSelectorTerms": [
            {"matchExpressions": [{"key": "topology.kubernetes.io/zone",
                                   "operator": "In", "values": [affinity_zone]}]}
        ]}}
    if claim_ref:
        pv["spec"]["claimRef"] = claim_ref
    if source:
        pv["spec"].update(source)
    return pv


def _sc(name, *, provisioner="pd.csi.storage.gke.io", mode="WaitForFirstConsumer"):
    return {
        "apiVersion": "storage.k8s.io/v1", "kind": "StorageClass",
        "metadata": {"name": name},
        "provisioner": provisioner,
        "volumeBindingMode": mode,
    }


def _pod_with_claim(name, claim, **kw):
    p = make_pod(name, **kw)
    p["spec"]["volumes"] = [
        {"name": "data", "persistentVolumeClaim": {"claimName": claim}}
    ]
    return p


def _run(nodes, queue, pvs=(), pvcs=(), scs=(), pods=()):
    feats = Featurizer().featurize(
        nodes, list(pods), queue_pods=queue, pvs=list(pvs), pvcs=list(pvcs),
        storage_classes=list(scs),
    )
    eng = Engine(feats, default_plugins(feats), record="full")
    return feats, eng.schedule()[0]


def _reasons(feats, res, plugins_name, pi, ni):
    fi = res.filter_plugin_names.index(plugins_name)
    import ksim_tpu.plugins.volumes as vol

    cls = {
        "VolumeBinding": vol.VolumeBinding,
        "VolumeZone": vol.VolumeZone,
        "NodeVolumeLimits": vol.NodeVolumeLimits,
        "VolumeRestrictions": vol.VolumeRestrictions,
    }[plugins_name]
    inst = cls.__new__(cls)
    return inst.decode_reasons(int(res.reason_bits[pi, fi, ni]))


def test_volume_binding_node_affinity_conflict():
    nodes = [
        make_node("na", labels={"topology.kubernetes.io/zone": "a"}),
        make_node("nb", labels={"topology.kubernetes.io/zone": "b"}),
    ]
    pvs = [_pv("pv1", affinity_zone="a")]
    pvcs = [_pvc("claim", volume_name="pv1")]
    queue = [_pod_with_claim("p", "claim")]
    feats, res = _run(nodes, queue, pvs=pvs, pvcs=pvcs)
    assert feats.nodes.names[int(res.selected[0])] == "na"
    assert _reasons(feats, res, "VolumeBinding", 0, 1) == [ERR_NODE_CONFLICT]
    # Oracle agreement on both nodes.
    for ni, node in enumerate(nodes):
        want = oracle.volume_binding_filter(queue[0], node, pvcs, pvs, [])
        assert _reasons(feats, res, "VolumeBinding", 0, ni) == want


def test_volume_binding_unbound_immediate_and_missing():
    nodes = [make_node("n0")]
    pvcs = [_pvc("immediate", sc="")]  # no SC -> Immediate, unbound
    q1 = _pod_with_claim("p1", "immediate")
    q2 = _pod_with_claim("p2", "nosuch")
    feats, res = _run(nodes, [q1, q2], pvcs=pvcs)
    assert int(res.selected[0]) == -1 and int(res.selected[1]) == -1
    assert _reasons(feats, res, "VolumeBinding", 0, 0) == [ERR_UNBOUND_IMMEDIATE]
    assert "not found" in _reasons(feats, res, "VolumeBinding", 1, 0)[0]


def test_volume_binding_wffc_candidates_and_provisioning():
    nodes = [
        make_node("na", labels={"topology.kubernetes.io/zone": "a"}),
        make_node("nb", labels={"topology.kubernetes.io/zone": "b"}),
    ]
    # WFFC claim with a static candidate PV only in zone a, no provisioner.
    scs = [_sc("local", provisioner="kubernetes.io/no-provisioner")]
    pvs = [_pv("pv-a", affinity_zone="a", sc="local")]
    pvcs = [_pvc("claim", sc="local")]
    queue = [_pod_with_claim("p", "claim")]
    feats, res = _run(nodes, queue, pvs=pvs, pvcs=pvcs, scs=scs)
    assert feats.nodes.names[int(res.selected[0])] == "na"
    assert _reasons(feats, res, "VolumeBinding", 0, 1) == [ERR_BIND_CONFLICT]
    # With a dynamic provisioner the claim binds anywhere.
    scs2 = [_sc("dyn")]
    pvcs2 = [_pvc("claim", sc="dyn")]
    feats2, res2 = _run(nodes, [_pod_with_claim("p", "claim")], pvcs=pvcs2, scs=scs2)
    assert int(res2.selected[0]) >= 0
    assert _reasons(feats2, res2, "VolumeBinding", 0, 0) == []


def test_volume_zone_conflict():
    nodes = [
        make_node("na", labels={"topology.kubernetes.io/zone": "a"}),
        make_node("nb", labels={"topology.kubernetes.io/zone": "b"}),
    ]
    pvs = [_pv("pv1", zone="a")]
    pvcs = [_pvc("claim", volume_name="pv1")]
    queue = [_pod_with_claim("p", "claim")]
    feats, res = _run(nodes, queue, pvs=pvs, pvcs=pvcs)
    assert feats.nodes.names[int(res.selected[0])] == "na"
    assert _reasons(feats, res, "VolumeZone", 0, 1) == [ERR_ZONE_CONFLICT]
    for ni, node in enumerate(nodes):
        assert _reasons(feats, res, "VolumeZone", 0, ni) == oracle.volume_zone_filter(
            queue[0], node, pvcs, pvs
        )


def test_node_volume_limits_and_commit():
    nodes = [make_node("n0", extra_alloc={"attachable-volumes-csi-d": "1"}),
             make_node("n1", extra_alloc={"attachable-volumes-csi-d": "2"})]
    scs = [_sc("fast", provisioner="d")]
    pvs = [
        _pv("pv1", sc="fast", phase="Bound"),
        _pv("pv2", sc="fast", phase="Bound"),
        _pv("pv3", sc="fast", phase="Bound"),
    ]
    for pv in pvs:
        pv["spec"]["csi"] = {"driver": "d", "volumeHandle": pv["metadata"]["name"]}
    pvcs = [_pvc(f"c{i}", volume_name=f"pv{i+1}", sc="fast") for i in range(3)]
    queue = [_pod_with_claim(f"p{i}", f"c{i}") for i in range(3)]
    feats, res = _run(nodes, queue, pvs=pvs, pvcs=pvcs, scs=scs)
    placed = [feats.nodes.names[int(res.selected[i])] if res.selected[i] >= 0 else None
              for i in range(3)]
    # Capacity 1+2: all three fit, the scan carry enforcing per-node limits.
    assert sorted(p for p in placed if p) == ["n0", "n1", "n1"]
    # A fourth claim cannot fit anywhere.
    pv4 = _pv("pv4", sc="fast", phase="Bound")
    pv4["spec"]["csi"] = {"driver": "d", "volumeHandle": "pv4"}
    pvcs4 = pvcs + [_pvc("c3", volume_name="pv4", sc="fast")]
    bound = []
    for i, p in enumerate(queue):
        b = _pod_with_claim(f"b{i}", f"c{i}", node_name=placed[i])
        bound.append(b)
    feats2, res2 = _run(nodes, [_pod_with_claim("p3", "c3")], pvs=pvs + [pv4],
                        pvcs=pvcs4, scs=scs, pods=bound)
    assert int(res2.selected[0]) == -1
    assert _reasons(feats2, res2, "NodeVolumeLimits", 0, 0) == [ERR_MAX_VOLUME_COUNT]
    want = oracle.node_volume_limits_filter(
        _pod_with_claim("p3", "c3"), nodes[0], [bound[0]], pvcs4, pvs + [pv4], scs
    )
    assert want == [ERR_MAX_VOLUME_COUNT]


def test_volume_restrictions_rwop_and_disk():
    nodes = [make_node("n0"), make_node("n1")]
    pvcs = [_pvc("shared", volume_name="", modes=("ReadWriteOncePod",))]
    bound = _pod_with_claim("holder", "shared", node_name="n0")
    q = _pod_with_claim("p", "shared")
    feats, res = _run(nodes, [q], pvcs=pvcs, pods=[bound])
    # RWOP claim in use on n0 -> lands on n1.
    assert feats.nodes.names[int(res.selected[0])] == "n1"
    assert _reasons(feats, res, "VolumeRestrictions", 0, 0) == [ERR_RWOP_CONFLICT]
    assert oracle.volume_restrictions_filter(q, [bound], pvcs) == [ERR_RWOP_CONFLICT]

    # GCE PD: rw conflicts with any use; both-read-only shares.
    def gce(name, node_name, ro):
        p = make_pod(name, node_name=node_name)
        p["spec"]["volumes"] = [{
            "name": "d", "gcePersistentDisk": {"pdName": "disk-1", "readOnly": ro}
        }]
        return p

    q_rw = gce("q-rw", "", False)
    q_ro = gce("q-ro", "", True)
    holder_ro = gce("h", "n0", True)
    feats2, res2 = _run(nodes, [q_rw], pods=[holder_ro])
    assert feats2.nodes.names[int(res2.selected[0])] == "n1"
    assert _reasons(feats2, res2, "VolumeRestrictions", 0, 0) == [ERR_DISK_CONFLICT]
    feats3, res3 = _run(nodes, [q_ro], pods=[holder_ro])
    assert _reasons(feats3, res3, "VolumeRestrictions", 0, 0) == []  # ro+ro shares
    assert oracle.volume_restrictions_filter(q_ro, [holder_ro], []) == []
    assert oracle.volume_restrictions_filter(q_rw, [holder_ro], []) == [ERR_DISK_CONFLICT]


def test_service_end_to_end_with_pvc_pods():
    """The VERDICT gap: a snapshot with PVC-backed pods must schedule
    CORRECTLY (zone-affine PV pins the pod) instead of silently ignoring
    volumes."""
    store = ClusterStore()
    store.create("nodes", make_node("na", labels={"topology.kubernetes.io/zone": "a"}))
    store.create("nodes", make_node("nb", cpu="64", memory="128Gi",
                                    labels={"topology.kubernetes.io/zone": "b"}))
    store.create("persistentvolumes", _pv("pv1", affinity_zone="a"))
    store.create("persistentvolumeclaims", _pvc("claim", volume_name="pv1"))
    store.create("pods", _pod_with_claim("p", "claim", cpu="100m"))
    svc = SchedulerService(store)
    # nb is far bigger (better LeastAllocated score) but the PV pins to na.
    assert svc.schedule_pending() == {"default/p": "na"}
    anno = store.get("pods", "p")["metadata"]["annotations"]
    fr = json.loads(anno[FILTER_RESULT_KEY])
    assert fr["nb"]["VolumeBinding"] == ERR_NODE_CONFLICT


def test_bound_volume_count_tracks_across_passes():
    """The persistent featurizer's incremental bound-volumes count must
    engage/disengage the trivial fast path correctly as volume-using
    pods come and go — and never produce different tensors than a fresh
    featurizer."""
    import numpy as np

    from ksim_tpu.state.featurizer import Featurizer
    from tests.helpers import make_node, make_pod

    node = make_node("n0")
    voluser = make_pod("voluser", node_name="n0")
    voluser["spec"]["volumes"] = [
        {"name": "d", "gcePersistentDisk": {"pdName": "disk-1"}}
    ]
    plain_bound = make_pod("plain", node_name="n0")
    queue = [make_pod("q0")]

    f = Featurizer()
    # Pass 1: a bound volume user -> full encode (its disk, which no other
    # pod uses, is one exclusive attachment of its pool and no column).
    feats1 = f.featurize([node], [voluser, plain_bound], queue_pods=queue)
    assert feats1.aux["volumes"].live
    assert feats1.aux["volumes"].excl_init.sum() == 1
    assert feats1.aux["volumes"].disk_any_init.sum() == 0
    # Pass 2: the volume user is gone -> trivial path, zero tensors.
    feats2 = f.featurize([node], [plain_bound], queue_pods=queue)
    assert not feats2.aux["volumes"].live
    assert feats2.aux["volumes"].disk_any_init.sum() == 0
    # Fresh featurizer agrees with the persistent one, field by field.
    fresh = Featurizer().featurize([node], [plain_bound], queue_pods=queue)
    for name in ("disk_any_init", "attached_init", "pod_vol", "pod_fail"):
        np.testing.assert_array_equal(
            getattr(feats2.aux["volumes"], name),
            getattr(fresh.aux["volumes"], name),
        )
    # Pass 3: a QUEUE pod with volumes still forces the full encode even
    # though no bound pod uses any.
    volq = make_pod("volq")
    volq["spec"]["volumes"] = [{"name": "d", "gcePersistentDisk": {"pdName": "disk-2"}}]
    feats3 = f.featurize([node], [plain_bound], queue_pods=[volq])
    assert feats3.aux["volumes"].pod_excl.sum() == 1
    assert feats3.aux["volumes"].pod_vol.sum() == 0
