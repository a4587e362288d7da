"""Volume-family filter kernels: VolumeBinding, VolumeZone,
NodeVolumeLimits, VolumeRestrictions.

Upstream kube-scheduler v1.30 semantics over the snapshot model's
pvs/pvcs/storageClasses (encoding + documented simplifications in
state/volumes.py).  All four are filter-only in the default profile
(VolumeBinding's capacity score is gated behind an alpha feature).
Every per-pod check is a ``[N, X] x [X]`` matvec over the factored
volume tensors; the attach/usage state mutated by scheduling rides the
scan carries with the same elementwise outer-product commit as the other
carried plugins.  One definition for both paths: the per-pass engine
re-reads the carries' start from the encoder every pass, the segment
program (engine/replay.py) carries them through a window's steps — a
bind adds a pod's ``carry_rows``, a delete takes them off.
"""

from __future__ import annotations

import jax.numpy as jnp

from ksim_tpu.plugins.base import FilterOutput, NodeStateView, PodView
from ksim_tpu.state.volumes import VolumeTensors

VOLUME_BINDING = "VolumeBinding"
VOLUME_ZONE = "VolumeZone"
NODE_VOLUME_LIMITS = "NodeVolumeLimits"
VOLUME_RESTRICTIONS = "VolumeRestrictions"

# VolumeBinding (volume_binding.go / binder.go)
ERR_UNBOUND_IMMEDIATE = "pod has unbound immediate PersistentVolumeClaims"
ERR_PVC_NOT_FOUND = "persistentvolumeclaim not found"
ERR_NODE_CONFLICT = "node(s) had volume node affinity conflict"
ERR_BIND_CONFLICT = "node(s) didn't find available persistent volumes to bind"
UNBOUND_IMMEDIATE_BIT = 1
PVC_MISSING_BIT = 2
NODE_CONFLICT_BIT = 4
BIND_CONFLICT_BIT = 8

# VolumeZone (volume_zone.go)
ERR_ZONE_CONFLICT = "node(s) had no available volume zone"

# NodeVolumeLimits (nodevolumelimits csi.go/non_csi.go)
ERR_MAX_VOLUME_COUNT = "node(s) exceed max volume count"

# VolumeRestrictions (volume_restrictions.go)
ERR_DISK_CONFLICT = "node(s) had no available disk"
ERR_RWOP_CONFLICT = (
    "node has pod using PersistentVolumeClaim with the same name and "
    "ReadWriteOncePod access mode"
)
DISK_CONFLICT_BIT = 1
RWOP_CONFLICT_BIT = 2


def _dot_bool(mat: jnp.ndarray, vec: jnp.ndarray) -> jnp.ndarray:
    """(mat[X, N] or [N, X]) boolean hit-count against vec[X] -> i32."""
    return jnp.dot(mat.astype(jnp.int32), vec.astype(jnp.int32))


class VolumeBinding:
    # Static reason-bit width: result tensors downcast when every
    # filter plugin's bits fit a narrower dtype (engine/core.py).
    reason_bit_width = 4
    name = VOLUME_BINDING
    volume_family = True

    def __init__(self, vt: VolumeTensors) -> None:
        del vt

    def static_sig(self) -> tuple:
        return (VOLUME_BINDING,)

    def failure_unresolvable(self, bits: int) -> bool:
        return True  # upstream: all UnschedulableAndUnresolvable

    def filter(self, state: NodeStateView, pod: PodView, aux) -> FilterOutput:
        a = aux["volumes"]
        j = pod.index
        n = state.valid.shape[0]
        i32 = jnp.int32
        pod_level = a["pod_fail"][j]  # bitmask: 1 unbound-immediate | 2 missing
        # Bound PVs whose node affinity rejects the node.
        node_conf = _dot_bool(~a["pv_node_ok"].T, a["pod_pv"][j]) > 0  # [N]
        # WFFC claims with neither a candidate PV on the node nor dynamic
        # provisioning.
        unsat = ~(a["pvc_cand_ok"] | a["pvc_provisionable"][:, None])  # [C, N]
        bind_conf = _dot_bool(unsat.T, a["pod_wffc"][j]) > 0  # [N]
        # pod_fail's bit layout matches UNBOUND_IMMEDIATE_BIT/PVC_MISSING_BIT.
        pod_bits = pod_level
        code = (
            jnp.broadcast_to(pod_bits, (n,))
            + jnp.where(node_conf, NODE_CONFLICT_BIT, 0)
            + jnp.where(bind_conf, BIND_CONFLICT_BIT, 0)
        ).astype(i32)
        return FilterOutput(ok=code == 0, reason_bits=code)

    def decode_reasons(self, bits: int) -> list[str]:
        out = []
        if bits & UNBOUND_IMMEDIATE_BIT:
            out.append(ERR_UNBOUND_IMMEDIATE)
        if bits & PVC_MISSING_BIT:
            out.append(ERR_PVC_NOT_FOUND)
        if bits & NODE_CONFLICT_BIT:
            out.append(ERR_NODE_CONFLICT)
        if bits & BIND_CONFLICT_BIT:
            out.append(ERR_BIND_CONFLICT)
        return out


class VolumeZone:
    # Static reason-bit width: result tensors downcast when every
    # filter plugin's bits fit a narrower dtype (engine/core.py).
    reason_bit_width = 1
    name = VOLUME_ZONE
    volume_family = True

    def __init__(self, vt: VolumeTensors) -> None:
        del vt

    def static_sig(self) -> tuple:
        return (VOLUME_ZONE,)

    def failure_unresolvable(self, bits: int) -> bool:
        return True  # upstream: UnschedulableAndUnresolvable

    def filter(self, state: NodeStateView, pod: PodView, aux) -> FilterOutput:
        a = aux["volumes"]
        j = pod.index
        conflict = _dot_bool(~a["pv_zone_ok"].T, a["pod_pv"][j]) > 0
        return FilterOutput(
            ok=~conflict, reason_bits=jnp.where(conflict, 1, 0).astype(jnp.int32)
        )

    def decode_reasons(self, bits: int) -> list[str]:
        return [ERR_ZONE_CONFLICT] if bits else []


class NodeVolumeLimits:
    """Attach-limit filter over one or all attachable-volumes-* pools.

    ``NodeVolumeLimits`` covers every pool (upstream v1.30's CSI plugin
    counts migrated in-tree volumes too); the legacy registry names —
    EBSLimits, GCEPDLimits, AzureDiskLimits, CinderLimits (upstream
    nodevolumelimits/non_csi.go, carried by the reference's exported
    default config, simulator/snapshot/snapshot_test.go:1415) — are
    instances restricted to their one pool via ``pools``.

    Where a pod of the call reads a volume (``vt.live``) the carry is
    COUNTED: ``att`` [N, V] users of each shared volume on the node and
    ``excl`` [N, K] exclusive attachments a pool, both linear in the
    pods bound (``carry_rows``), so the segment program can take a
    deleted pod's row off again.  Where none does, the carry and the
    program are what a volume-free pass always compiled."""

    # Static reason-bit width: result tensors downcast when every
    # filter plugin's bits fit a narrower dtype (engine/core.py).
    reason_bit_width = 1
    volume_family = True

    def __init__(
        self,
        vt: VolumeTensors,
        *,
        name: str = NODE_VOLUME_LIMITS,
        pools: tuple[str, ...] | None = None,
    ) -> None:
        self.name = name
        self._live = vt.live
        self._pool_ids = tuple(
            k
            for k, pool in enumerate(vt.pool_names[: int(vt.n_pools)])
            if pools is None or pool in pools
        )

    def static_sig(self) -> tuple:
        return (NODE_VOLUME_LIMITS, self.name, self._pool_ids) + (
            ("live",) if self._live else ()
        )

    def failure_unresolvable(self, bits: int) -> bool:
        return False  # evicting pods detaches volumes

    def carry_init(self, aux):
        a = aux["volumes"]
        if not self._live:
            return a["attached_init"]  # i32 [N, V]
        return {"att": a["attached_init"], "excl": a["excl_init"]}

    def carry_rows(self, aux) -> dict:
        """Per pod, what its binding adds to the node's row of each
        carried array (live only)."""
        a = aux["volumes"]
        return {"att": a["pod_vol"], "excl": a["pod_excl"]}

    def carry_commit(self, carry, aux, pod: PodView, best):
        a = aux["volumes"]
        if not self._live:
            uses = a["pod_vol"][pod.index].astype(carry.dtype)  # [V]
            onehot = ((jnp.arange(carry.shape[0]) == best) & (best >= 0)).astype(
                carry.dtype
            )
            # Attachment is unique per (volume, node): saturate at 1.
            return jnp.maximum(carry, onehot[:, None] * uses[None, :])
        onehot = (
            (jnp.arange(carry["att"].shape[0]) == best) & (best >= 0)
        ).astype(jnp.int32)[:, None]
        return {
            key: carry[key] + onehot * rows[pod.index].astype(jnp.int32)[None, :]
            for key, rows in self.carry_rows(aux).items()
        }

    def filter(self, state: NodeStateView, pod: PodView, aux, carry) -> FilterOutput:
        a = aux["volumes"]
        j = pod.index
        attached = (carry["att"] if self._live else carry) > 0  # [N, V]
        pod_vol = a["pod_vol"][j]  # [V]
        over = jnp.zeros(state.valid.shape[0], dtype=bool)
        for k in self._pool_ids:  # static unroll over this plugin's pools
            in_pool = a["vol_key"] == k  # [V]
            used = _dot_bool(attached, in_pool)  # [N]
            new = _dot_bool(~attached, pod_vol & in_pool)  # [N] dedup'd
            limit = a["limits"][:, k]
            if self._live:
                # csi.go: a pool is checked for the volumes the pod would
                # ADD to the node; with none to add it passes.
                used = used + carry["excl"][:, k]
                new = new + a["pod_excl"][j, k]
                over = over | ((limit >= 0) & (new > 0) & (used + new > limit))
            else:
                over = over | ((limit >= 0) & (used + new > limit))
        return FilterOutput(
            ok=~over, reason_bits=jnp.where(over, 1, 0).astype(jnp.int32)
        )

    def attach_summary(self, carry, aux, valid) -> dict:
        """From the carried state (live only): the attachments the valid
        nodes hold, and the smallest limit-less-attached over the valid
        nodes and this plugin's limited pools (int32 max where nothing is
        limited)."""
        a = aux["volumes"]
        attached = carry["att"] > 0
        big = jnp.iinfo(jnp.int32).max
        total = jnp.int32(0)
        head = jnp.int32(big)
        for k in self._pool_ids:
            used = _dot_bool(attached, a["vol_key"] == k) + carry["excl"][:, k]
            limit = a["limits"][:, k]
            total = total + jnp.sum(jnp.where(valid, used, 0), dtype=jnp.int32)
            head = jnp.minimum(
                head, jnp.min(jnp.where(valid & (limit >= 0), limit - used, big))
            )
        return {"attached": total, "headroom": head.astype(jnp.int32)}

    def decode_reasons(self, bits: int) -> list[str]:
        return [ERR_MAX_VOLUME_COUNT] if bits else []


class VolumeRestrictions:
    # Static reason-bit width: result tensors downcast when every
    # filter plugin's bits fit a narrower dtype (engine/core.py).
    reason_bit_width = 2
    name = VOLUME_RESTRICTIONS
    volume_family = True

    def __init__(self, vt: VolumeTensors) -> None:
        del vt

    def static_sig(self) -> tuple:
        return (VOLUME_RESTRICTIONS,)

    def failure_unresolvable(self, bits: int) -> bool:
        return False  # upstream: Unschedulable (preemptable)

    def carry_init(self, aux) -> dict:
        a = aux["volumes"]
        return {
            "rwop": a["rwop_init"],
            "disk_any": a["disk_any_init"],
            "disk_rw": a["disk_rw_init"],
        }

    def carry_rows(self, aux) -> dict:
        """Per pod, what its binding adds to the node's row of each
        carried array (``carry_commit`` below, row by row)."""
        a = aux["volumes"]
        return {
            "rwop": a["pod_rwop"],
            "disk_any": a["pod_disk_any"],
            "disk_rw": a["pod_disk_rw"],
        }

    def carry_commit(self, carry, aux, pod: PodView, best) -> dict:
        a = aux["volumes"]
        j = pod.index
        onehot = ((jnp.arange(carry["rwop"].shape[0]) == best) & (best >= 0)).astype(
            jnp.int32
        )

        def add(c, uses):
            return c + onehot[:, None] * uses.astype(jnp.int32)[None, :]

        return {
            "rwop": add(carry["rwop"], a["pod_rwop"][j]),
            "disk_any": add(carry["disk_any"], a["pod_disk_any"][j]),
            "disk_rw": add(carry["disk_rw"], a["pod_disk_rw"][j]),
        }

    def filter(self, state: NodeStateView, pod: PodView, aux, carry) -> FilterOutput:
        a = aux["volumes"]
        j = pod.index
        # ReadWriteOncePod: any other user of the claim on the node.
        rwop = _dot_bool(carry["rwop"] > 0, a["pod_rwop"][j]) > 0  # [N]
        # Disk conflicts (isVolumeConflict): EBS never shares; GCE/ISCSI/
        # RBD share only when BOTH uses are read-only.
        share = a["disk_ro_shareable"]
        pod_any = a["pod_disk_any"][j]
        pod_rw = a["pod_disk_rw"][j]
        any_used = carry["disk_any"] > 0
        rw_used = carry["disk_rw"] > 0
        disk = (
            (_dot_bool(any_used, pod_any & ~share) > 0)
            | (_dot_bool(any_used, pod_rw & share) > 0)
            | (_dot_bool(rw_used, pod_any & ~pod_rw & share) > 0)
        )
        code = jnp.where(disk, DISK_CONFLICT_BIT, 0) + jnp.where(
            rwop, RWOP_CONFLICT_BIT, 0
        )
        return FilterOutput(ok=code == 0, reason_bits=code.astype(jnp.int32))

    def decode_reasons(self, bits: int) -> list[str]:
        out = []
        if bits & DISK_CONFLICT_BIT:
            out.append(ERR_DISK_CONFLICT)
        if bits & RWOP_CONFLICT_BIT:
            out.append(ERR_RWOP_CONFLICT)
        return out
