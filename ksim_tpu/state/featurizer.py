"""Snapshot -> fixed-shape device tensors.

This is the host->TPU boundary of the framework: the analogue of the
reference's NodeInfo/PodInfo construction in the upstream scheduler cache
(which the wrapped plugins consume per-(pod,node) call,
reference simulator/scheduler/plugin/wrappedplugin.go:420-548).  Everything
the batched Filter/Score kernels need is lowered here once per snapshot:

- **Resource axis.** The tracked resource set is cpu, memory,
  ephemeral-storage plus any extended resources present in the snapshot.
  ``pods`` capacity is a separate scalar ("Too many pods" check).
- **Exact unit scaling.** Kube-scheduler does int64 math; TPU integer math
  is int32.  Each resource r gets a unit u_r = gcd of every observed value
  of r, and all values are stored as value/u_r.  Integer-division score
  formulas like ``(c-r)*100//c`` are ratios of the raw values, so dividing
  numerator and denominator by the same u_r leaves every result bit-exact.
  If the scaled values could still overflow ``int32`` through the ``*100``
  in the score formula the featurizer falls back to lossy scaling and
  records ``exact=False`` (callers can then route parity-critical runs to
  the int64 path / host oracle).
- **Padding + bucketing.**  Pod and node counts are padded up to
  bucketed shapes (powers of two, with a 3/4 step in the >= 8192-pow2
  octaves — see ``bucket_size``) so recompiles are bounded (SURVEY.md
  section 7 hard part 4); ``valid`` masks carry the true extents.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Any, Iterable, Sequence

import numpy as np

from ksim_tpu.obs import TRACE
from ksim_tpu.state import objcache
from ksim_tpu.state.boundagg import (
    BoundContents,
    NodeSlots,
    records_built,
    records_shared,
    sync_family,
)
from ksim_tpu.state.podtable import ROW, Column, PodTable
from ksim_tpu.state.resources import (
    BASE_RESOURCES,
    UNSCHEDULABLE_TAINT,
    CPU,
    JSON,
    MEMORY,
    EPHEMERAL_STORAGE,
    PODS,
    labels_of,
    name_of,
    namespaced_key,
    node_allocatable,
    node_unschedulable,
    pod_is_scheduled,
    pod_node_name,
    pod_requests,
    pod_tolerations,
    tolerations_tolerate_taint,
)

# Largest per-resource scaled value that keeps v*100 (MaxNodeScore) in int32.
MAX_EXACT_SCALED = (2**31 - 1) // 128

# The tracked-resource prefix is BASE_RESOURCES (state/resources.py);
# extended resources are appended in sorted order.


def bucket_size(n: int, minimum: int = 8) -> int:
    """Round up to the next power of two (>= minimum) — with a 3/4 step
    once the pow2 reaches 8192 (…, 2048, 4096, 6144, 8192, 12288,
    16384, …).

    Pure powers of two waste up to half the compiled program's work on
    padding (5000 pods -> 8192 meant the headline scan burned 39% of
    its FLOPs on masked rows; 10k x 5k burned 44% across both axes).
    The extra bucket exists only at >= 8192 pow2s, so churn-scale
    shapes (pods capped per pass, vocabularies reset-valved at 4096,
    thousands of nodes) keep the exact old ladder — no new recompile
    boundaries there — and every 3/4 step is divisible by 2048, so
    dp/tp mesh sharding still divides evenly."""
    if n <= minimum:
        return minimum
    p = 1 << (n - 1).bit_length()
    if p >= 8192 and n <= (p * 3) // 4:
        return (p * 3) // 4
    return p


def vocab_pad(n: int, minimum: int = 8) -> int:
    """Bucket for a VOCABULARY axis (the ``bucket_size`` ladder): churn
    replay adds and removes vocab entries constantly, and unbucketed
    vocab shapes would force an XLA recompile on nearly every step (the
    pod/node axes are bucketed the same way)."""
    return bucket_size(max(n, 1), minimum)


@dataclass
class NodeTensors:
    """Per-node device-ready arrays, shape [N] or [N, R]."""

    names: list[str]
    allocatable: np.ndarray  # int32 [N, R] scaled
    allowed_pods: np.ndarray  # int32 [N]
    requested: np.ndarray  # int32 [N, R] from already-bound pods
    nonzero_requested: np.ndarray  # int32 [N, R] scoring-path accumulation
    pod_count: np.ndarray  # int32 [N]
    unschedulable: np.ndarray  # bool [N]
    valid: np.ndarray  # bool [N]

    @property
    def count(self) -> int:
        return len(self.names)

    @property
    def padded(self) -> int:
        return self.valid.shape[0]


@dataclass
class PodTensors:
    """Per-pod device-ready arrays, shape [P] or [P, R]."""

    keys: list[str]  # namespace/name
    requests: np.ndarray  # int32 [P, R] scaled (Fit filter path)
    nonzero_requests: np.ndarray  # int32 [P, R] scaled (scoring path)
    valid: np.ndarray  # bool [P]
    tolerates_unschedulable: np.ndarray  # bool [P]
    has_requests: np.ndarray  # bool [P] (fitsRequest early-exit predicate)
    index: np.ndarray  # int32 [P] == arange (row into per-pod aux arrays)

    @property
    def count(self) -> int:
        return len(self.keys)


@dataclass
class FeaturizedSnapshot:
    """Everything the batched kernels need, plus host-side decode tables."""

    resources: tuple[str, ...]  # the R axis
    units: dict[str, int]  # resource -> divisor used in scaling
    exact: bool  # int32 math is bit-exact vs int64
    nodes: NodeTensors
    pods: PodTensors
    aux: dict[str, Any] = field(default_factory=dict)  # plugin extras

    def resource_index(self, r: str) -> int:
        return self.resources.index(r)


_BASE_SET = frozenset(BASE_RESOURCES)

# Table families of the base pod rows (state/podtable.py).
_IDENTITY_COLUMNS = (Column("key", object, None),)
_STATIC_COLUMNS = (
    Column("vals", object, None),  # ((resource, raw value), ...)
    Column("tol", bool, False),
    Column("has", bool, False),
)
_REQUEST_COLUMNS = (
    Column("req", np.int64, 0, ROW),
    Column("nz", np.int64, 0, ROW),
)


def _vals_apply(counters: "dict[str, dict[int, int]]", pairs, sign: int) -> None:
    """Add (``sign`` > 0) or remove (< 0) ``(resource, raw value)``
    pairs, ``abs(sign)`` times each, in a per-resource multiset of
    values."""
    for r, v in pairs:
        c = counters.setdefault(r, {})
        nv = c.get(v, 0) + sign
        if nv:
            c[v] = nv
        else:
            del c[v]
            if not c:
                del counters[r]


def _gcd_unit(values: "Iterable[int]") -> int:
    g = 0
    for v in values:
        g = math.gcd(g, v)
    return g or 1


class Featurizer:
    """Lower a snapshot (lists of pod/node JSON objects) to tensors."""

    def __init__(
        self,
        *,
        node_bucket_min: int | None = None,
        pod_bucket_min: int | None = None,
        interpod_hard_weight: int | None = None,
        extra_encoders: "dict[str, Any] | None" = None,
        added_affinity: "JSON | None" = None,
        spread_defaults: "tuple | None" = None,
    ) -> None:
        """``extra_encoders`` maps aux key -> fn(nodes, queue_pods,
        n_padded, p_padded) -> dataclass-with-AXES — the hook out-of-tree
        plugins use to ship their own tensors to the device (the sample
        NodeNumber / data-provider plugins ride this).  ``added_affinity``
        is the profile's NodeAffinityArgs.addedAffinity (upstream
        node_affinity.go addedNodeSelector/addedPrefSchedTerms)."""
        if interpod_hard_weight is None:
            from ksim_tpu.state.interpod import DEFAULT_HARD_POD_AFFINITY_WEIGHT

            interpod_hard_weight = DEFAULT_HARD_POD_AFFINITY_WEIGHT
        self._node_bucket_min = node_bucket_min if node_bucket_min else 8
        self._pod_bucket_min = pod_bucket_min if pod_bucket_min else 8
        self._interpod_hard_weight = interpod_hard_weight
        self._extra_encoders = dict(extra_encoders or {})
        self._added_affinity = added_affinity
        # PodTopologySpreadArgs default constraints (List defaulting, or
        # the upstream systemDefaultConstraints for System) — inert in
        # the snapshot model (see encoding.default_spread_selector) but
        # threaded so the behavior is upstream-shaped.
        self._spread_defaults = spread_defaults
        # Incremental bound-pod aggregation across featurizations of the
        # SAME evolving cluster (state/boundagg.py): node-name slots keep
        # the node axis stable under churn, and the additive aggregates
        # update by delta instead of re-walking every bound pod.  A fresh
        # instance behaves exactly like the one-shot path (slot order =
        # first-seen order = the caller's order).
        self._slots = NodeSlots()
        # Slot churn applied through advance_slots() between featurize
        # calls (the device-resident replay rolls node history forward
        # step by step without featurizing); merged into the next
        # featurize's changed-slot set so family repair still sees it.
        self._pending_changed: set[int] = set()
        self._agg: dict[str, Any] = {}
        # Shared per-pass bound-set diff (see boundagg.sync_family): one
        # O(bound) comparison per pass instead of one per family — and
        # the bound pods' content ids, maintained from it: what the
        # families' contribution tables are keyed by.
        self._prev_bound: dict[int, JSON] = {}
        self._bound_gen = 0
        self._contents = self._agg["__contents__"] = BoundContents()
        # Bound pods carrying a volume that a plugin reads, maintained
        # from the diff — the volumes fast path needs "does ANY bound pod
        # read a volume", and
        # re-scanning 15k+ bound pods per pass was the single largest
        # steady-state featurize cost.  Asked once a content id.
        self._bound_vol_count = 0
        self._vol_of: dict[int, bool] = {}
        # The persistent per-pod row table (state/podtable.py): every
        # pod-axis output is a gather from it, so a call's per-pod Python
        # runs only for pods it has not seen — and the raw request values
        # of the table's pods, kept as a multiset per resource the way
        # the ``resvals`` family keeps the bound pods', decide the
        # resource axis and the gcd units without a walk over the queue.
        self._table = PodTable()
        self._queue_vals: dict[str, dict[int, int]] = {}
        # O(delta) evidence counters.  ``pod_rows_built``: pods lowered
        # for the first time; ``pod_rows_reused``: pods whose rows the
        # table served by gather; ``pod_rows_rebuilt``: family rows
        # recomputed for a pod the table held because the family's token
        # moved (vocabulary growth, a new resource, namespace labels).
        # A caller with an identity-stable queue (the replay lower-cache
        # keeps surviving universe pods' objects alive across segments)
        # sees ``pod_rows_built`` grow with its per-window object churn,
        # not with the universe size — the counters the
        # ``make lock-check`` O(delta) guard reads (docs/churn_floor.md
        # "Incremental lowering + pipelined executor").
        self.pod_rows_built = 0
        self.pod_rows_reused = 0
        self.featurize_passes = 0

    @property
    def pod_rows_rebuilt(self) -> int:
        return self._table.rows_rebuilt

    @property
    def pod_rows_copied(self) -> int:
        """Of ``pod_rows_built``, the pods whose manifest equalled a
        live pod's but for its identity: their rows were copied, no
        builder ran (state/podtable.py)."""
        return self._table.rows_copied

    @property
    def content_keys_built(self) -> int:
        """``content_key`` calls (one ``marshal.dumps`` each) this
        featurizer made: a new queue pod and a bound arrival cost one
        each unless the caller handed its key in (``featurize``'s
        ``content_keys``)."""
        return self._table.keys_built + self._contents.keys_built

    @property
    def bound_records_built(self) -> int:
        """Bound-pod records for which an additive family ran its
        contribution builder, summed over the families (state/boundagg.py
        ``records_built``)."""
        return records_built(self._agg)

    @property
    def bound_records_shared(self) -> int:
        """Bound-pod records that took their content's contribution
        from a family's table, no builder run (``records_shared``)."""
        return records_shared(self._agg)

    def slot_names(self) -> list[str]:
        """The current node-slot order, lowest slot first — the carry a
        segment checkpoint records so ``seed_slots`` can reinstall it on
        a restored run (scheduler/service.py ``checkpoint_carries``)."""
        return list(self._slots._names)

    def seed_slots(self, names: Sequence[str]) -> None:
        """Install a checkpoint-recorded node-slot order on a FRESH
        featurizer (job-plane incremental resume — see
        ``boundagg.NodeSlots.seed``).  Every seeded slot is queued as
        changed so the first featurize repairs families against the
        live objects; on a fresh instance that repair is the from-
        scratch rebuild it would have done anyway."""
        self._slots.seed(names)
        self._pending_changed |= set(range(len(names)))

    def advance_slots(self, nodes: Sequence[JSON]) -> None:
        """Advance the persistent node-slot history WITHOUT featurizing.

        The device-resident replay (engine/replay.py) schedules whole
        step segments off-host; between those steps this featurizer never
        runs, but its slot assignment must still follow every node
        delete/create so a later per-pass fallback sees the exact order
        the pure per-pass history would have produced.  Changed slots
        accumulate and merge into the next featurize's repair set."""
        _ordered, changed = self._slots.sync(list(nodes))
        self._pending_changed |= changed

    def featurize(
        self,
        nodes: Sequence[JSON],
        pods: Sequence[JSON],
        *,
        queue_pods: Sequence[JSON] = (),
        bound_pods: "Sequence[JSON] | None" = None,
        namespaces: Sequence[JSON] = (),
        pvs: Sequence[JSON] = (),
        pvcs: Sequence[JSON] = (),
        storage_classes: Sequence[JSON] = (),
        content_keys: "dict[int, bytes | None] | None" = None,
    ) -> FeaturizedSnapshot:
        """``pods`` are existing cluster pods (bound ones charge their node);
        ``queue_pods`` are the pods to schedule (the pod axis P);
        ``bound_pods``, when given, are the node-bound pods (spec.nodeName
        set; callers with an indexed store pass
        ``store.pods_with_node()`` to skip the O(all pods) split —
        phase filtering still happens here);
        ``namespaces`` feed namespaceSelector matching (InterPodAffinity);
        ``pvs``/``pvcs``/``storage_classes`` feed the volume plugins;
        ``content_keys`` maps ``id(pod)`` to the pod's
        ``boundagg.content_key`` for the pods whose key the caller has
        taken already: a bound pod found there is not keyed again, nor
        is a queue pod — which the caller lists only where that key
        leaves out nothing the queue object holds (no ``spec.nodeName``,
        an empty ``status`` or none), so that it cuts the queue as the
        pod table's own key would; every other pod is keyed here as if
        nothing had been handed in.

        Queue pods are recognised by object identity across calls (the
        row table, state/podtable.py): a pod object handed in again is
        served its stored rows, so — as for every objcache memo — an
        object must not be edited in place after it was featurized.

        The call is seven timed stages of whatever span the caller has
        open (``service.featurize.index`` / ``.resources`` /
        ``.affinity`` / ``.spread`` / ``.interpod`` / ``.volumes`` /
        ``.extras``:
        obs.py ``TracePlane.stage``), the sequential seams of the body;
        every encoder does its own node side, pod rows and bound
        aggregate, and the node-side tables built afresh are counted by
        ``Memo.seq_builds``."""
        TRACE.stage("service.featurize.index")
        # Safe point for memo-table size enforcement: no memo key is in
        # flight here (see objcache.maybe_flush).
        objcache.maybe_flush()

        sched_pods = list(queue_pods) if queue_pods else [
            p for p in pods if not pod_is_scheduled(p)
        ]
        bound_src = pods if bound_pods is None else bound_pods
        bound_pods = [
            p
            for p in bound_src
            if pod_is_scheduled(p)
            and (p.get("status", {}).get("phase") not in ("Succeeded", "Failed"))
        ]

        # Stable node slots: churn must not shift the node axis under the
        # incremental aggregates.  For a fresh featurizer this is the
        # caller's order.
        nodes, changed_slots = self._slots.sync(nodes)
        if self._pending_changed:
            changed_slots = changed_slots | self._pending_changed
            self._pending_changed = set()
        bound_map = {id(p): p for p in bound_pods}
        # Publish the shared arrival/departure diff for every family this
        # pass syncs (holding the previous map's pod refs keeps ids from
        # being recycled while they can still appear in a diff).
        prev = self._prev_bound
        self._bound_gen += 1
        added = [pid for pid in bound_map if pid not in prev]
        removed = [pid for pid in prev if pid not in bound_map]
        from ksim_tpu.state.volumes import _pod_reads_volumes

        cid_of, vol_of = self._contents.of, self._vol_of
        for pid in removed:
            self._bound_vol_count -= vol_of[cid_of[pid]]
        released = self._contents.sync(bound_map, added, removed, content_keys)
        for c in released:
            del vol_of[c]
        for pid in added:
            c = cid_of[pid]
            if c not in vol_of:
                vol_of[c] = _pod_reads_volumes(bound_map[pid])
            self._bound_vol_count += vol_of[c]
        self._agg["__diff__"] = {
            "gen": self._bound_gen,
            "added": added,
            "removed": removed,
            "released": released,
        }
        self._prev_bound = bound_map

        # The pod axis: one identity lookup per pod, shared by every
        # family (state/podtable.py); all per-pod Python below runs
        # inside a family's row builder, for new pods only and once per
        # distinct manifest among them.
        table = self._table
        queue_vals = self._queue_vals
        static = table.family("static", _STATIC_COLUMNS)
        P = len(sched_pods)

        def count_vals(rows: np.ndarray, sign: int) -> None:
            """The multiset counts pods: every row's pairs, those of a
            copied row too — taken once per distinct manifest, times
            the rows that share it."""
            reps, counts = table.by_content(rows)
            for pairs, k in zip(static.cols["vals"][reps], counts):
                _vals_apply(queue_vals, pairs, sign * k)

        def forget_vals(rows: np.ndarray) -> None:
            count_vals(rows[static.valid[rows]], -1)

        n_new = table.index(sched_pods, forget_vals, content_keys)
        self.pod_rows_built += n_new
        self.pod_rows_reused += P - n_new
        self.featurize_passes += 1

        def static_row(p: JSON) -> tuple:
            """Everything about a pod's base row that no token can move."""
            reqs = pod_requests(p)
            nz = pod_requests(p, non_zero=True)
            # Every raw value that enters math, plus the zero-valued keys
            # (a requested resource joins the axis whatever its value).
            pairs = tuple(reqs.items()) + tuple((r, v) for r, v in nz.items() if v)
            return (
                pairs,
                tolerations_tolerate_taint(pod_tolerations(p), UNSCHEDULABLE_TAINT),
                # Upstream fitsRequest early-exit predicate: base requests
                # all zero AND no scalar-resource key present (a zero-
                # valued extended-resource key still defeats the early
                # return).
                any(reqs.get(r, 0) for r in BASE_RESOURCES)
                or any(k not in _BASE_SET and k != PODS for k in reqs),
            )

        count_vals(table.sync(static, None, static_row), +1)
        # The one column that reads who the pod is.
        ident = table.family("identity", _IDENTITY_COLUMNS)
        table.sync(ident, None, lambda p: (namespaced_key(p),), shared=False)

        TRACE.stage("service.featurize.resources")
        # Bound pods' raw request values as an incrementally-maintained
        # multiset per resource: the resource axis and exact gcd units
        # need every value that enters math, without an O(bound) walk.
        def _resvals(p: JSON):
            pairs = []
            for non_zero in (False, True):
                for r, v in pod_requests(p, non_zero=non_zero).items():
                    if v:
                        pairs.append((r, v))
            return tuple(pairs) or None

        bound_vals: dict[str, dict[int, int]] = sync_family(
            self._agg,
            "resvals",
            (),
            bound_map,
            set(),  # node-independent
            make_arrays=dict,
            slot_of=None,
            contribution=_resvals,
            apply=lambda counters, _slot, pairs, sign: _vals_apply(counters, pairs, sign),
        )

        def build_node_vals():
            vals: dict[str, set[int]] = {}
            for n in nodes:
                for r, v in node_allocatable(n).items():
                    vals.setdefault(r, set()).add(v)
            return vals

        node_vals = objcache.cached_seq("feat_node_vals", nodes, build_node_vals)

        # Resource axis: base prefix + extended resources seen anywhere.
        seen = set(node_vals) | set(queue_vals) | set(bound_vals)
        seen.discard(PODS)
        extended = sorted(seen - _BASE_SET)
        resources = BASE_RESOURCES + tuple(extended)
        ridx = {r: i for i, r in enumerate(resources)}
        R = len(resources)
        exact = True
        if R > 29:
            # Reason bits past bit 30 saturate into a shared bit (see
            # plugins/noderesources.py); decoded reasons are then ambiguous.
            exact = False

        # Exact gcd units per resource across every value that enters math.
        units: dict[str, int] = {}
        for r in resources:
            vals = {
                *node_vals.get(r, ()),
                *queue_vals.get(r, ()),
                *bound_vals.get(r, ()),
            }
            vals.discard(0)
            unit = _gcd_unit(vals)
            max_scaled = max((v // unit for v in vals), default=0)
            if max_scaled > MAX_EXACT_SCALED:
                # Lossy fallback: keep magnitudes bounded, mark inexact.
                unit = unit * -(-max_scaled // MAX_EXACT_SCALED)
                exact = False
            units[r] = unit

        # The requests dicts are memoized per pod object (pod_requests),
        # so lowered rows can be memoized on the dict's identity as long
        # as the unit scaling they were lowered with is part of the key.
        units_token = (resources, tuple(units[r] for r in resources))
        memo = objcache.current()

        def lower(d: dict[str, int]) -> np.ndarray:
            key = ("lower", memo.ref_id(d), units_token)
            hit = memo.get(key)
            if hit is not objcache.MISS:
                return hit
            row = np.zeros(R, dtype=np.int64)
            for r, v in d.items():
                i = ridx.get(r)
                if i is not None:
                    u = units[r]
                    row[i] = v // u if v % u == 0 else -(-v // u)
            return memo.put(key, row)

        N = len(nodes)
        NP, PP = bucket_size(N, self._node_bucket_min), bucket_size(P, self._pod_bucket_min)

        def build_node_arrays():
            alloc = np.zeros((NP, R), dtype=np.int32)
            allowed_pods = np.zeros(NP, dtype=np.int32)
            unsched = np.zeros(NP, dtype=bool)
            nvalid = np.zeros(NP, dtype=bool)
            node_names = [name_of(n) for n in nodes]
            for i, n in enumerate(nodes):
                node_alloc = node_allocatable(n)
                alloc[i] = lower(node_alloc)
                allowed_pods[i] = node_alloc.get(PODS, 0)
                unsched[i] = node_unschedulable(n)
                nvalid[i] = True
            return alloc, allowed_pods, unsched, nvalid, node_names

        # Family-cached on the exact node objects + unit scaling: under
        # churn the node list and units are stable most passes, so the
        # 2k-iteration lowering loop collapses to one dict hit.
        alloc, allowed_pods, unsched, nvalid, node_names = objcache.cached_seq(
            "feat_nodes", nodes, build_node_arrays, units_token, NP
        )
        node_index = self._slots.slot_of

        # Per-node request sums from bound pods, maintained by delta.
        # Masters accumulate in int64: per-value bounds don't bound the
        # SUM over bound pods; clamp (and drop exactness) on the copies
        # only if a sum overflows.
        def bound_slot(p: JSON) -> "int | None":
            """The slot of a bound pod's node; None while the node is
            not on the axis.  The same for every family with a slot."""
            ni = node_index.get(pod_node_name(p))
            return None if ni is None or ni >= N else ni

        # One master row a node — requests | non-zero requests | pods —
        # so that a pod comes and goes by ONE row operation.
        def _req_row(p: JSON) -> np.ndarray:
            return np.concatenate(
                (lower(pod_requests(p)), lower(pod_requests(p, non_zero=True)), (1,))
            )

        def _req_apply(master: np.ndarray, ni: int, row: np.ndarray, sign: int) -> None:
            if sign > 0:
                master[ni] += row
            else:
                master[ni] -= row

        reqagg = sync_family(
            self._agg,
            "requested",
            (units_token, NP),
            bound_map,
            changed_slots,
            make_arrays=lambda: np.zeros((NP, 2 * R + 1), dtype=np.int64),
            slot_of=bound_slot,
            contribution=_req_row,
            apply=_req_apply,
        )
        requested = reqagg[:, :R].copy()
        nz_requested = reqagg[:, R : 2 * R].copy()
        pod_count = reqagg[:, 2 * R].astype(np.int32)

        if requested.max(initial=0) > MAX_EXACT_SCALED or nz_requested.max(initial=0) > MAX_EXACT_SCALED:
            exact = False
            requested = np.minimum(requested, MAX_EXACT_SCALED)
            nz_requested = np.minimum(nz_requested, MAX_EXACT_SCALED)
        requested = requested.astype(np.int32)
        nz_requested = nz_requested.astype(np.int32)

        # Request rows are kept RAW over the resource axis and scaled at
        # the gather, so a unit that moves (a new value changing a gcd)
        # costs one vectorised division, not a rebuild; only a new
        # extended resource re-lowers the rows.
        def raw_rows(p: JSON) -> tuple:
            out = []
            for d in (pod_requests(p), pod_requests(p, non_zero=True)):
                row = np.zeros(R, dtype=np.int64)
                for r, v in d.items():
                    i = ridx.get(r)
                    if i is not None:
                        row[i] = v
                out.append(row)
            return tuple(out)

        reqfam = table.family("requests", _REQUEST_COLUMNS)
        table.sync(reqfam, resources, raw_rows, {"req": R, "nz": R})
        unit_row = np.array([units[r] for r in resources], dtype=np.int64)
        preq = np.zeros((PP, R), dtype=np.int32)
        pnz = np.zeros((PP, R), dtype=np.int32)
        pvalid = np.zeros(PP, dtype=bool)
        ptol = np.zeros(PP, dtype=bool)
        phas = np.zeros(PP, dtype=bool)
        # Ceiling division: exact for gcd units, rounds up under the
        # lossy fallback.
        preq[:P] = -(-reqfam.take("req") // unit_row)
        pnz[:P] = -(-reqfam.take("nz") // unit_row)
        ptol[:P] = static.take("tol")
        phas[:P] = static.take("has")
        pvalid[:P] = True

        from ksim_tpu.state.encoding import (
            encode_affinity,
            encode_taints,
            encode_topology_spread,
        )
        from ksim_tpu.state.extras import (
            encode_image_locality,
            encode_node_name,
            encode_node_ports,
        )
        from ksim_tpu.state.interpod import encode_inter_pod
        from ksim_tpu.state.volumes import encode_volumes

        aux = {}
        TRACE.stage("service.featurize.affinity")
        aux["affinity"] = encode_affinity(
            nodes, table, NP, PP, added_affinity=self._added_affinity
        )
        aux["taints"] = encode_taints(nodes, table, NP, PP)
        TRACE.stage("service.featurize.spread")
        aux["spread"] = encode_topology_spread(
            nodes, table, NP, PP,
            agg=self._agg, bound_map=bound_map,
            changed_slots=changed_slots, slot_of=bound_slot,
            default_constraints=self._spread_defaults,
        )
        TRACE.stage("service.featurize.interpod")
        aux["interpod"] = encode_inter_pod(
            nodes, table, namespaces, NP, PP,
            hard_weight=self._interpod_hard_weight,
            agg=self._agg, bound_map=bound_map,
            changed_slots=changed_slots, slot_of=bound_slot,
        )
        TRACE.stage("service.featurize.volumes")
        volumes = encode_volumes(
            nodes, table, bound_pods, pvs, pvcs, storage_classes, NP, PP,
            bound_volume_free=self._bound_vol_count == 0,
        )
        TRACE.stage("service.featurize.extras")
        aux["nodename"] = encode_node_name(nodes, table, PP)
        aux["nodeports"] = encode_node_ports(nodes, table, bound_pods, NP, PP)
        aux["imagelocality"] = encode_image_locality(nodes, table, NP, PP)
        aux["volumes"] = volumes
        for key, encoder in self._extra_encoders.items():
            aux[key] = encoder(nodes, sched_pods, NP, PP)

        snapshot = FeaturizedSnapshot(
            resources=resources,
            units=units,
            exact=exact,
            aux=aux,
            nodes=NodeTensors(
                names=node_names,
                allocatable=alloc,
                allowed_pods=allowed_pods,
                requested=requested,
                nonzero_requested=nz_requested,
                pod_count=pod_count,
                unschedulable=unsched,
                valid=nvalid,
            ),
            pods=PodTensors(
                keys=ident.take("key").tolist(),
                requests=preq,
                nonzero_requests=pnz,
                valid=pvalid,
                tolerates_unschedulable=ptol,
                has_requests=phas,
                index=np.arange(PP, dtype=np.int32),
            ),
        )
        TRACE.stage_end()
        return snapshot
