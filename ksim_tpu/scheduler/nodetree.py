"""The scheduler cache's node tree: the order in which a sampling attempt
walks the nodes (upstream kube-scheduler ``pkg/scheduler/internal/cache/
node_tree.go``; the contract is in docs/jobs.md).

Upstream does not walk nodes in name or creation order.  The cache keeps
them by zone, ``list()`` deals them round-robin across the zones, the
snapshot's node list is that list, and ``findNodesThatPassFilters`` walks
it from ``nextStartNodeIndex``.  The tree orders the WALK of a service
that samples (``SchedulerService(node_sampling=True)``) and nothing
else: equal totals still go to the first node in the featurizer's slot
order, and a service that does not sample keeps no tree.

Nodes with no zone or region label share the zone ``""``: one zone's
list is the order its nodes joined in, which for a cluster without node
churn is the order the service has always walked.
"""

from __future__ import annotations

from typing import Any, Iterable, Mapping, Sequence

import numpy as np

from ksim_tpu.state.resources import name_of

ZONE_LABEL = "topology.kubernetes.io/zone"
REGION_LABEL = "topology.kubernetes.io/region"
LEGACY_ZONE_LABEL = "failure-domain.beta.kubernetes.io/zone"
LEGACY_REGION_LABEL = "failure-domain.beta.kubernetes.io/region"


def zone_key(node: Mapping[str, Any]) -> str:
    """GetZoneKey: ``""`` for a node with neither label, else the region
    and the zone around a separator no label value can hold."""
    labels = (node.get("metadata") or {}).get("labels") or {}
    zone = labels.get(ZONE_LABEL) or labels.get(LEGACY_ZONE_LABEL) or ""
    region = labels.get(REGION_LABEL) or labels.get(LEGACY_REGION_LABEL) or ""
    if not zone and not region:
        return ""
    return region + ":\x00:" + zone


class NodeTree:
    """``zones`` in order of first appearance; per zone the node names in
    the order they were added."""

    def __init__(self) -> None:
        self.zones: list[str] = []
        self.tree: dict[str, list[str]] = {}
        self.zone_of: dict[str, str] = {}

    def __len__(self) -> int:
        return len(self.zone_of)

    def copy(self) -> "NodeTree":
        out = NodeTree()
        out.zones = list(self.zones)
        out.tree = {z: list(names) for z, names in self.tree.items()}
        out.zone_of = dict(self.zone_of)
        return out

    def add(self, name: str, zone: str) -> None:
        """Append to the zone's list; a new zone joins ``zones`` last.  A
        name the tree holds moves only when its zone changed."""
        have = self.zone_of.get(name)
        if have is not None:
            if have == zone:
                return
            self.remove(name)
        names = self.tree.get(zone)
        if names is None:
            names = self.tree[zone] = []
            self.zones.append(zone)
        names.append(name)
        self.zone_of[name] = zone

    def remove(self, name: str) -> None:
        """Take the name out of its zone's list (the others keep their
        order); a zone left empty goes."""
        zone = self.zone_of.pop(name, None)
        if zone is None:
            return
        names = self.tree[zone]
        names.remove(name)
        if not names:
            del self.tree[zone]
            self.zones.remove(zone)

    def apply(self, gone: Iterable[str], joined: Iterable[Mapping[str, Any]]) -> None:
        """One step's node events: the removals, then the nodes that
        join, by name (the simulator's convention for nodes that join
        together; upstream adds them as their events arrive)."""
        for name in gone:
            self.remove(name)
        for name, zone in sorted((name_of(node), zone_key(node)) for node in joined):
            self.add(name, zone)

    def sync(self, nodes: Sequence[Mapping[str, Any]]) -> None:
        """Bring the tree to a live node set: what a pass sees of the
        node events since the last one.  A node that is still there
        under the same zone key keeps its place."""
        now = {name_of(node): zone_key(node) for node in nodes}
        if now == self.zone_of:
            return
        gone = [name for name, zone in self.zone_of.items() if now.get(name) != zone]
        joined = [node for node in nodes if self.zone_of.get(name_of(node)) != now[name_of(node)]]
        self.apply(gone, joined)

    def list(self) -> list[str]:
        """The walk's order: for i = 0, 1, ...: each zone's i-th node,
        in ``zones`` order, until every node is out."""
        if len(self.zones) <= 1:
            return list(self.tree[self.zones[0]]) if self.zones else []
        n_zones = len(self.zones)
        names: list[str] = []
        keys = []
        for z, zone in enumerate(self.zones):
            members = self.tree[zone]
            names.extend(members)
            keys.append(np.arange(len(members), dtype=np.int64) * n_zones + z)
        order = np.argsort(np.concatenate(keys), kind="stable")
        return [names[i] for i in order]

    def positions(self, slot_of: Mapping[str, int], width: int, fill: int) -> np.ndarray:
        """Per slot of a node table (``slot_of``: name -> slot), the
        node's place in ``list()``; ``fill`` where the slot holds no
        node of the tree."""
        out = np.full(width, fill, np.int32)
        order = self.list()
        slots = np.fromiter((slot_of.get(name, -1) for name in order), np.int64, len(order))
        at = np.flatnonzero(slots >= 0)
        out[slots[at]] = at
        return out

    # -- checkpoint carry (jobs/manager.py incremental resume) ----------------

    def to_carry(self) -> list:
        return [[zone, list(self.tree[zone])] for zone in self.zones]

    @classmethod
    def from_carry(cls, carry: "Sequence | None") -> "NodeTree":
        out = cls()
        for zone, names in carry or ():
            for name in names:
                out.add(str(name), str(zone))
        return out
