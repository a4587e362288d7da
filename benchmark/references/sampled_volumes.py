"""The plain reference of a deployment whose pods carry volumes:
``references/sampled_zoned.py``'s sequential scheduler (sampled scoring over
the node tree's list) plus PersistentVolume / PersistentVolumeClaim /
StorageClass objects and the four volume filters of the default profile,
written from upstream kube-scheduler v1.30 ``pkg/scheduler/framework/plugins/
{volumebinding,volumezone,nodevolumelimits,volumerestrictions}`` over the
objects themselves — and from nothing of the program.

Per attempt the pod's volumes are looked up in the objects as they stand:

- **VolumeBinding** (``volume_binding.go`` PreFilter / Filter, ``binder.go``
  ``FindPodVolumes``): a claim that does not exist, or whose
  ``spec.volumeName`` names no PersistentVolume, fails every node; so does an
  unbound claim of binding mode ``Immediate`` (no StorageClass: Immediate).  A
  bound claim's PV must admit the node by ``spec.nodeAffinity.required``
  (``nodeSelectorTerms`` ORed, a term's ``matchExpressions`` /
  ``matchFields`` ANDed; ``In`` / ``NotIn`` / ``Exists`` / ``DoesNotExist`` /
  ``Gt`` / ``Lt``).
- **VolumeZone** (``volume_zone.go``): for each of a bound PV's zone / region
  labels (``topology.kubernetes.io/zone`` / ``region`` and the two
  ``failure-domain.beta`` keys) the node must carry the key with one of the
  label's ``__``-separated values.
- **NodeVolumeLimits** (``nodevolumelimits/csi.go``, ``non_csi.go``): a node
  that states ``attachable-volumes-<pool>`` allocatable takes at most that many
  distinct volumes of the pool.  A pool is checked for the volumes the pod
  would ADD to the node (those it names that no pod on the node attaches
  already); with none to add it passes.  Pools: a PV's in-tree source
  (``awsElasticBlockStore`` -> ``aws-ebs``, ``gcePersistentDisk`` -> ``gce-pd``,
  ``azureDisk`` -> ``azure-disk``, ``cinder`` -> ``cinder``), else
  ``csi-<driver>`` from ``spec.csi.driver``, else from the claim's
  StorageClass's ``provisioner``; a pod's direct in-tree disk likewise.
- **VolumeRestrictions** (``volume_restrictions.go``): a claim with access mode
  ``ReadWriteOncePod`` that a pod on the node uses already; a direct disk a pod
  on the node uses already — an ``awsElasticBlockStore`` volume never shares,
  ``gcePersistentDisk`` / ``iscsi`` / ``rbd`` share only where both uses are
  read-only (``isVolumeConflict``).

**The simulator's conventions, stated** (docs/jobs.md): the snapshot model has
no CSINode, so a limit is the node's ``attachable-volumes-*`` allocatable key
(the pre-CSINode mechanism; upstream's test writes both).  A volume's identity
is its PV's name, or ``<source>:<id>`` for a disk a pod names directly (upstream:
driver and volume handle).  The arrival of a volume object shortens the backoff
like a node's.  A step's operations all stand when its pass begins, so a pod may
name a claim that the same step creates.

**Not covered, by name** (``NotCovered``): a generic ephemeral volume; an
unbound claim of a ``WaitForFirstConsumer`` class (upstream's PreBind chooses
and writes a PV); the deletion of a volume object; DefaultPreemption (as
``sampled_zoned.py``).  ``emptyDir``, ``configMap``, ``secret``, ``projected``,
``downwardAPI``, ``hostPath``, ``nfs`` and inline ``csi`` volumes concern none
of the four plugins and are passed over.

``volumes=False`` is **the control**: the same scheduler blind to volumes (the
four filters admit every node, nothing is attached).  Where a filter rejects,
its digest differs.  Besides what ``sampled_zoned.replay`` returns the result
carries ``volume_attempts`` (attempts of a pod with a volume a plugin reads),
``volume_rejections`` (node verdicts one of the four turned down, among the
nodes those attempts visited), ``volume_attached`` (distinct attachments the
live nodes hold at the end, volumes with a pool), ``volume_headroom_min`` (the
smallest limit less attached over the live nodes and their limited pools;
``None``: nothing is limited), ``volume_objects`` (PVs + claims + classes
created) and ``volume_rejections_by`` (per plugin, the visited nodes it turned
down: a node two plugins refuse counts once in ``volume_rejections`` and once
for each of them here).
"""

from __future__ import annotations

import numpy as np

from references.sampled import num_feasible_nodes_to_find, walk
from references.sampled_zoned import NodeTree, get_zone_key
from replay import (FLUSH_CAP_PASSES, MAX_BACKOFF_PASSES, Cluster, NotCovered, Pod,
                    PriorityClasses, feasible_with_nominated, total_scores)

ZONE_KEYS = ("topology.kubernetes.io/zone", "topology.kubernetes.io/region",
             "failure-domain.beta.kubernetes.io/zone", "failure-domain.beta.kubernetes.io/region")
LIMIT_PREFIX = "attachable-volumes-"
#: in-tree source -> (id field, pool, shares when both uses are read-only,
#: has a conflict rule)
DISKS = {
    "gcePersistentDisk": ("pdName", "gce-pd", True, True),
    "awsElasticBlockStore": ("volumeID", "aws-ebs", False, True),
    "iscsi": ("iqn", None, True, True),
    "rbd": ("rbdImage", None, True, True),
    "azureDisk": ("diskName", "azure-disk", False, False),
    "cinder": ("volumeID", "cinder", False, False),
}
PASSED_OVER = ("emptyDir", "configMap", "secret", "projected", "downwardAPI", "hostPath", "nfs",
               "csi")
NO_PROVISIONER = "kubernetes.io/no-provisioner"
VOLUME_KINDS = ("PersistentVolume", "PersistentVolumeClaim", "StorageClass")
PLUGINS = ("VolumeBinding", "VolumeZone", "NodeVolumeLimits", "VolumeRestrictions")


def _expression(expr: dict, value: "str | None") -> bool:
    op, values = expr.get("operator"), expr.get("values") or []
    if op == "In":
        return value is not None and value in values
    if op == "NotIn":
        return value is None or value not in values
    if op == "Exists":
        return value is not None
    if op == "DoesNotExist":
        return value is None
    if op in ("Gt", "Lt"):
        try:
            a, b = int(value), int(values[0])
        except (TypeError, ValueError, IndexError):
            return False
        return a > b if op == "Gt" else a < b
    raise NotCovered(f"a node selector operator {op!r}")


def affinity_admits(required: "dict | None", name: str, labels: dict) -> bool:
    """``spec.nodeAffinity.required`` of a PV against one node."""
    if not required:
        return True
    for term in required.get("nodeSelectorTerms") or []:
        exprs, fields = term.get("matchExpressions") or [], term.get("matchFields") or []
        if not exprs and not fields:
            continue   # an empty term matches nothing
        if all(_expression(e, labels.get(e.get("key"))) for e in exprs) and all(
                e.get("key") == "metadata.name" and _expression(e, name) for e in fields):
            return True
    return False


def zone_admits(pv_labels: dict, labels: dict) -> bool:
    for key in ZONE_KEYS:
        if key in pv_labels and labels.get(key) not in set(str(pv_labels[key]).split("__")):
            return False
    return True


class Volumes:
    """The volume objects, what every node has attached, and one attempt's
    verdicts for all nodes (in ``Cluster``'s node order)."""

    def __init__(self) -> None:
        self.pvs: dict = {}
        self.claims: dict = {}     # "<namespace>/<name>" -> the claim
        self.classes: dict = {}
        self.limits: dict = {}     # node -> {pool: limit}
        self.attached: dict = {}   # node -> {pool: {volume id: users on the node}}
        self.holders: dict = {}    # volume id -> the nodes that have it attached
        self.rwop: dict = {}       # claim key -> {node: users}
        self.disks: dict = {}      # (source, id) -> {node: [users, read-write users]}
        self.created = 0

    def add(self, obj: dict) -> None:
        meta = obj["metadata"]
        if obj["kind"] == "PersistentVolume":
            self.pvs[meta["name"]] = obj
        elif obj["kind"] == "PersistentVolumeClaim":
            self.claims[f"{meta.get('namespace') or 'default'}/{meta['name']}"] = obj
        else:
            self.classes[meta["name"]] = obj
        self.created += 1

    def add_node(self, obj: dict) -> None:
        alloc = (obj.get("status") or {}).get("allocatable") or {}
        self.limits[obj["metadata"]["name"]] = {
            k[len(LIMIT_PREFIX):]: int(v) for k, v in alloc.items() if k.startswith(LIMIT_PREFIX)}

    def drop_node(self, name: str) -> None:
        """The node goes, with what it had attached."""
        self.limits.pop(name, None)
        for have in self.attached.pop(name, {}).values():
            for vid in have:
                self.holders[vid].discard(name)
        for table in (self.rwop, self.disks):
            for on in table.values():
                on.pop(name, None)

    # -- a pod's volumes ----------------------------------------------------

    @staticmethod
    def parse(obj: dict) -> list:
        """The volumes of a pod's manifest that a plugin reads."""
        out = []
        for vol in (obj.get("spec") or {}).get("volumes") or []:
            if vol.get("ephemeral"):
                raise NotCovered("a generic ephemeral volume")
            if (vol.get("persistentVolumeClaim") or {}).get("claimName") or any(
                    (vol.get(src) or {}).get(DISKS[src][0]) for src in DISKS):
                out.append(vol)
            elif not any(vol.get(key) is not None for key in PASSED_OVER):
                raise NotCovered(f"a volume source {sorted(set(vol) - {'name'})}")
        return out

    def use(self, pod) -> dict:
        """What ``pod``'s volumes come to against the objects as they stand:
        ``fail`` (no node can take it), the bound PVs, the attachable volumes
        a pool, the ReadWriteOncePod claims, the direct disks."""
        out = {"fail": False, "pvs": [], "pooled": {}, "rwop": set(), "disks": {}}
        for vol in pod.volumes:
            claim_name = (vol.get("persistentVolumeClaim") or {}).get("claimName")
            if claim_name:
                key = f"default/{claim_name}"
                claim = self.claims.get(key)
                if claim is None:
                    out["fail"] = True
                    continue
                spec = claim.get("spec") or {}
                if "ReadWriteOncePod" in (spec.get("accessModes") or ()):
                    out["rwop"].add(key)
                sc = self.classes.get(spec.get("storageClassName") or "")
                if not spec.get("volumeName"):
                    if ((sc or {}).get("volumeBindingMode") or "Immediate") != "Immediate":
                        raise NotCovered(
                            f"the unbound WaitForFirstConsumer claim {key}: PreBind would bind it")
                    out["fail"] = True
                    continue
                pv = self.pvs.get(spec["volumeName"])
                if pv is None:
                    out["fail"] = True
                    continue
                out["pvs"].append(pv)
                pv_spec = pv.get("spec") or {}
                pool = next((DISKS[src][1] for src in DISKS
                             if (pv_spec.get(src) or {}).get(DISKS[src][0])), None)
                if pool is None:
                    driver = (pv_spec.get("csi") or {}).get("driver") or (sc or {}).get("provisioner")
                    pool = f"csi-{driver}" if driver and driver != NO_PROVISIONER else None
                if pool:
                    out["pooled"].setdefault(pool, set()).add("pv:" + pv["metadata"]["name"])
                continue
            for src, (id_field, pool, _share, restricted) in DISKS.items():
                source = vol.get(src) or {}
                if not source.get(id_field):
                    continue
                disk = (src, str(source[id_field]))
                if restricted:
                    out["disks"][disk] = out["disks"].get(disk, False) or not source.get("readOnly")
                if pool:
                    out["pooled"].setdefault(pool, set()).add(f"{src}:{source[id_field]}")
        return out

    # -- one attempt ----------------------------------------------------------

    def verdicts(self, use: dict, cl: Cluster) -> dict:
        """Per plugin, which nodes of ``cl`` (its order) it turns down."""
        names = cl.names
        no = {plugin: np.zeros(len(names), bool) for plugin in PLUGINS}
        if use["fail"]:
            no["VolumeBinding"][:] = True
        for pv in use["pvs"]:
            required = ((pv.get("spec") or {}).get("nodeAffinity") or {}).get("required")
            pv_labels = (pv.get("metadata") or {}).get("labels") or {}
            zoned = any(key in pv_labels for key in ZONE_KEYS)
            for i, name in enumerate(names if required or zoned else ()):
                if not affinity_admits(required, name, cl.labels[i]):
                    no["VolumeBinding"][i] = True
                if not zone_admits(pv_labels, cl.labels[i]):
                    no["VolumeZone"][i] = True
        for pool, wanted in use["pooled"].items():
            for i, name in enumerate(names):
                limit = self.limits[name].get(pool)
                if limit is None:
                    continue
                have = self.attached.get(name, {}).get(pool, ())
                new = sum(1 for vid in wanted if vid not in have)
                if new and len(have) + new > limit:
                    no["NodeVolumeLimits"][i] = True
        for key in use["rwop"]:
            for name, users in self.rwop.get(key, {}).items():
                if users > 0 and name in cl.place:
                    no["VolumeRestrictions"][cl.place[name]] = True
        for disk, rw in use["disks"].items():
            share = DISKS[disk[0]][2]
            for name, (users, writers) in self.disks.get(disk, {}).items():
                if users > 0 and name in cl.place and (not share or rw or writers > 0):
                    no["VolumeRestrictions"][cl.place[name]] = True
        return no

    def charge(self, use: dict, node: str, sign: int) -> None:
        """``use`` attached to (+1) or released from (-1) ``node``."""
        for pool, wanted in use["pooled"].items():
            have = self.attached.setdefault(node, {}).setdefault(pool, {})
            for vid in wanted:
                have[vid] = have.get(vid, 0) + sign
                if have[vid] <= 0:
                    del have[vid]
                    self.holders[vid].discard(node)
                else:
                    self.holders.setdefault(vid, set()).add(node)
        for key in use["rwop"]:
            on = self.rwop.setdefault(key, {})
            on[node] = on.get(node, 0) + sign
        for disk, rw in use["disks"].items():
            row = self.disks.setdefault(disk, {}).setdefault(node, [0, 0])
            row[0] += sign
            row[1] += sign if rw else 0

    def summary(self) -> "tuple[int, int | None]":
        held = sum(len(have) for per_pool in self.attached.values() for have in per_pool.values())
        room = [limit - len(self.attached.get(name, {}).get(pool, ()))
                for name, limits in self.limits.items() for pool, limit in limits.items()]
        return held, min(room) if room else None


class FastLimits:
    """``Volumes.verdicts``' attach-limit loop over numpy columns: per pool the
    nodes' limits and attachment counts in ``Cluster``'s node order, rebuilt
    when that order moves and kept by ``charge`` in between.  The same
    verdicts (a test holds it to the loop); 7,000 attempts x 5,000 nodes are
    otherwise a minute of Python."""

    def __init__(self, vol: Volumes) -> None:
        self.vol, self.place, self.cols = vol, None, {}

    def columns(self, pool: str, cl: Cluster) -> tuple:
        if self.place is not cl.place:
            self.place, self.cols = cl.place, {}
        if pool not in self.cols:
            vol = self.vol
            self.cols[pool] = (
                np.array([vol.limits[n].get(pool, -1) for n in cl.names], np.int64),
                np.array([len(vol.attached.get(n, {}).get(pool, ())) for n in cl.names], np.int64))
        return self.cols[pool]

    def over(self, use: dict, cl: Cluster) -> np.ndarray:
        out = np.zeros(len(cl.names), bool)
        for pool, wanted in use["pooled"].items():
            limit, have = self.columns(pool, cl)
            new = np.full(len(cl.names), len(wanted), np.int64)
            for vid in wanted:   # a node that holds one already adds one less
                for name in self.vol.holders.get(vid, ()):
                    if name in cl.place:
                        new[cl.place[name]] -= 1
            out |= (limit >= 0) & (new > 0) & (have + new > limit)
        return out

    def charge(self, use: dict, node: str, sign: int) -> None:
        """``Volumes.charge``, and the count columns along."""
        at = None if self.place is None else self.place.get(node)
        before = {pool: len(self.vol.attached.get(node, {}).get(pool, ())) for pool in use["pooled"]}
        self.vol.charge(use, node, sign)
        for pool, n in before.items():
            if at is not None and pool in self.cols:
                self.cols[pool][1][at] += len(self.vol.attached[node][pool]) - n

    def moved(self) -> None:
        self.cols = {}


def replay(operations: list, *, max_pods_per_pass: "int | None" = None,
           precision: str = "exact", interleave: bool = True, percentage: int = 0,
           volumes: bool = True, fast: bool = True) -> dict:
    """Replay ``operations`` (KEP-140 ``spec.operations``) under sampled
    scoring over the node tree's list, with the volume filters; returns what
    ``references.sampled_zoned.replay`` returns, and the ``volume_*`` sums."""
    cl, pods, classes, tree, vol = Cluster(), {}, PriorityClasses(), NodeTree(), Volumes()
    limits = FastLimits(vol)
    backoff: dict = {}   # pod -> (attempts, the last pass it sits out)
    born: list = []      # pods created with a nodeName, charged once their node has a place
    passes = events = scheduled = unschedulable = 0
    start = sampled = visited_sum = scored_sum = 0
    vol_attempts = vol_rejections = 0
    rejections_by = dict.fromkeys(PLUGINS, 0)
    order = None         # the columns of ``cl`` in the order of the tree's list
    per_step = []
    by_step: dict = {}
    for op in operations:
        by_step.setdefault(int(op["step"]), []).append(op)

    def release(pod) -> None:
        if volumes and pod.attached_as is not None:
            limits.charge(pod.attached_as, pod.node, -1)
            pod.attached_as = None

    for step in sorted(by_step):
        batch = by_step[step]
        drained: set = set()
        flush = False
        joined: dict = {}    # the step's net node events, for the tree
        gone: list = []
        for op in batch:
            if "createOperation" in op:
                obj = op["createOperation"]["object"]
                if obj["kind"] == "Node":
                    cl.add(obj)
                    vol.add_node(obj)
                    joined[obj["metadata"]["name"]] = get_zone_key(obj)
                    flush = True
                elif obj["kind"] == "Pod":
                    bare = dict(obj, spec={k: v for k, v in obj["spec"].items() if k != "volumes"})
                    pod = Pod(bare, classes)
                    pod.volumes = Volumes.parse(obj)
                    pod.attached_as = None
                    pods[pod.name] = pod
                    if pod.born_on:
                        if pod.volumes:
                            raise NotCovered("a pod with volumes created on a node")
                        pod.node = pod.born_on
                        born.append(pod)
                elif obj["kind"] == "PriorityClass":
                    classes.add(obj)
                elif obj["kind"] in VOLUME_KINDS:
                    vol.add(obj)
                    flush = True
                else:
                    raise NotCovered(f"creation of a {obj['kind']}")
            elif "deleteOperation" in op:
                kind = op["deleteOperation"]["typeMeta"]["kind"]
                name = op["deleteOperation"]["objectMeta"]["name"]
                flush = True
                if kind == "Node":
                    drained |= cl.remove(name)
                    vol.drop_node(name)
                    limits.moved()
                    if joined.pop(name, None) is None:
                        gone.append(name)
                elif kind == "Pod":
                    pod = pods.pop(name, None)
                    if pod is None:
                        raise NotCovered(f"deletion of the pod {name}, which is gone")
                    backoff.pop(name, None)
                    if pod in born:
                        born.remove(pod)
                    elif pod.node is not None and pod.name not in drained:
                        cl.charge(pod, cl.place[pod.node], -1)
                        release(pod)
                else:
                    raise NotCovered(f"deletion of a {kind}")
            else:
                raise NotCovered(f"operation {sorted(set(op) - {'step'})}")
        events += len(batch)
        if gone or joined:
            for name in gone:
                if joined.get(name) != tree.zone_of[name]:
                    tree.remove(name)
            for name in sorted(joined):
                tree.add(name, joined[name])
            order = None
        for name in drained:   # the pods of a drained node queue again
            if name in pods:
                pods[name].node = None
                pods[name].attached_as = None   # went with the node
        if flush:
            backoff = {k: (n, min(last, passes + min(n - 1, FLUSH_CAP_PASSES)))
                       for k, (n, last) in backoff.items()}
        done = [0, 0]
        if cl.live:
            passes += 1
            queue = sorted((p for p in pods.values() if p.node is None
                            and not (p.name in backoff and backoff[p.name][1] >= passes)),
                           key=lambda p: p.queue_key)
            if queue or born:
                cl.sync()
            for pod in born:
                if pod.node not in cl.place:
                    raise NotCovered(f"a pod created on {pod.node}, which is no node")
                cl.charge(pod, cl.place[pod.node], +1)
            born = []
            nodes = len(cl.names)
            want = num_feasible_nodes_to_find(nodes, percentage)
            for pod in queue[:max_pods_per_pass]:
                ok = feasible_with_nominated(pod, cl, pods)
                use, refused = None, None
                if volumes and pod.volumes:
                    use = vol.use(pod)
                    refused = vol.verdicts(dict(use, pooled={}) if fast else use, cl)
                    if fast:
                        refused["NodeVolumeLimits"] = limits.over(use, cl)
                    admitted = ~np.logical_or.reduce(list(refused.values()))
                    ok = ok & admitted
                    vol_attempts += 1
                sample, seen = ok, np.ones(nodes, bool)
                if want < nodes:
                    if order is None:
                        order = np.array([cl.place[n] for n in tree.list()] if interleave
                                         else range(nodes), np.int64)
                    in_order, found, start = walk(ok[order], start % nodes, want)
                    sample = np.zeros(nodes, bool)
                    sample[order] = found
                    seen = np.zeros(nodes, bool)
                    seen[order] = in_order
                    sampled += 1
                    visited_sum += int(in_order.sum())
                    scored_sum += int(found.sum())
                if refused is not None:
                    vol_rejections += int((seen & ~admitted).sum())
                    for plugin, no in refused.items():
                        rejections_by[plugin] += int((seen & no).sum())
                if sample.any():
                    total = total_scores(pod, cl, pods, sample, precision)
                    best = int(np.argmax(np.where(sample, total, np.iinfo(np.int64).min)))
                    pod.node = cl.names[best]
                    cl.charge(pod, best, +1)
                    if use is not None:
                        limits.charge(use, pod.node, +1)
                        pod.attached_as = use
                    backoff.pop(pod.name, None)
                    done[0] += 1
                    continue
                if cl.has("bound", lambda priority: priority < pod.priority):
                    raise NotCovered("DefaultPreemption under sampled scoring")
                done[1] += 1
                attempts = backoff.get(pod.name, (0, 0))[0] + 1
                backoff[pod.name] = (attempts, passes + min(2 ** (attempts - 1), MAX_BACKOFF_PASSES))
        scheduled += done[0]
        unschedulable += done[1]
        per_step.append(tuple(done))
    held, room = vol.summary() if volumes else (0, None)
    return {"eventsApplied": events, "podsScheduled": scheduled,
            "unschedulableAttempts": unschedulable, "steps": per_step,
            "placements": {p.name: p.node for p in pods.values()},
            "sampled_attempts": sampled, "nodes_visited": visited_sum,
            "nodes_scored": scored_sum, "sampling_start": start,
            "sampling_zones": len(tree.zones),
            "volume_attempts": vol_attempts, "volume_rejections": vol_rejections,
            "volume_attached": held, "volume_headroom_min": room,
            "volume_objects": vol.created, "volume_rejections_by": rejections_by}
