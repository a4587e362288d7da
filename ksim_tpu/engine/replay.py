"""Device-resident churn replay: K scheduling passes per device dispatch.

The per-pass replay path (scenario/runner.py + scheduler/service.py) pays
one host->device->host round trip per scheduling pass — the per-dispatch
latency, ~480 times over the 50k churn replay — because each pass's
placements mutate the host ClusterStore before the next step's events can
apply (docs/churn_floor.md, "Where the remaining time goes").  What that
latency is on a directly attached chip is unmeasured.

This module removes that serialization for the common churn op vocabulary
(pod create / pod delete / node drain / node replace): a SEGMENT of K
scenario steps is pre-lowered on the host into padded tensor event
streams over a pod/node UNIVERSE (every object alive during the segment,
including ones created mid-segment), and a single compiled program runs
all K steps — event application, backoff bookkeeping, queue compaction,
and the sequential-commit scheduling scan — inside one ``lax.scan`` whose
carry holds the full cluster tensor state.  The host store remains the
JSON-speaking source of truth: placements stream back once per segment
and are reconciled into the store step by step (scenario/runner.py).

Parity contract (the behavior locks, repo CLAUDE.md): the device path
must reproduce the per-pass path's scheduled/unschedulable counts
byte-identically.  The design choices that guarantee it:

- **Universe row order is queue order.**  Pod rows are pre-sorted by the
  exact ``queue_sort_key`` (priority desc, creationTimestamp, namespace,
  name) — static per pod — so per-step queue compaction preserves the
  per-pass scheduling order without a device sort.
- **Rank-based selectHost.**  The per-pass path's tie-break is "lowest
  node index" in the persistent featurizer's slot order, which evolves by
  NodeSlots swap-remove under churn.  The lowering simulates that exact
  slot history step by step (``_SlotSim``) and ships a per-step rank
  tensor; the device selects the max-score feasible node with minimal
  rank — the same node the per-pass argmax picks.
- **Integer-space deltas.**  Event application mutates only additive
  integer state (requested/nonzero/pod-count aggregates, spread selector
  counts, inter-pod term accumulators, backoff counters), so the
  f32-fast-mode cross-platform determinism argument of round 5
  (docs/churn_floor.md "Cross-platform count determinism") carries over
  unchanged: the scoring kernels see bit-identical inputs.
- **Local accumulators for InterPodAffinity.**  The per-pass carry is a
  domain-AGGREGATED view that cannot absorb deletes; the segment carry
  keeps per-node local term sums and re-derives the domain view each
  step with fixed segment reductions (``_derive_interpod``) — verified
  at lowering time against the featurizer's own aggregation.

Two former fallback classes are now lowered instead (round 7):

- **DefaultPreemption** runs ON-DEVICE, as v1.30 defines it
  (scheduler/preemption.py has the definitions and the conventions):
  every pod of a pass is evaluated against the state as its
  predecessors left it — their binds, their victims gone, their
  nominations — with the nominated pods of its priority or above
  counted in (upstream's second filter run; ONE run decides both in a
  node-local window, ``_preempt_search``), and its own nominated node
  tried first; a pod that fits nowhere searches for victims on the spot.  The
  search evaluates the preemptor's candidates TOGETHER where every
  filter verdict of the window is node-local (no DoNotSchedule spread
  constraint and no required pod (anti-)affinity in the universe: the
  lowering reads that off the lowered tensors, ``_SegmentStatics.
  local``): the victim table groups the lower-priority pods by node
  in MoreImportantPod order (one sort of the pod axis), the filter
  chain runs over the whole node axis for "everything lower gone" and
  once per reprieve rank, and pickOneNode is one lexicographic argmin
  over the first ``candidate_count(live nodes)`` candidates in name
  order (kept by one sort of the node axis in slot space).  The table is built once a PASS AND PRIORITY LEVEL, by the
  first search of the level, and carried through the pass's pod loop:
  the universe axis is in queue order (priority descending), so a pod
  that binds in the pass is never of a lower priority than a
  preemptor behind it, and the only pods that go inside a pass are a
  preemptor's own victims — between two searches of a level the
  table only shrinks, by those victims, on the one node chosen, and
  each verdict takes them out of that node's row
  (``_repair_table``).  A search that finds the table current sorts
  and gathers nothing of the pod axis.  A window
  that holds such a constraint walks the candidates in name order
  instead, one exact hypothetical state a check, until upstream's
  count is found.  The only bound is ``VMAX`` lower-priority pods a
  node: exceeded -> per-step overflow flag -> segment discarded before
  any store effect.
- **record="full"** streams the per-attempt reason-bit / raw / final
  score tensors out of the scan as stacked segment outputs (shorter
  fixed K to bound device memory); the host decodes them into the exact
  per-pass result annotations at segment boundaries.

A third since PR 34: **sampled scoring** (the service's
``node_sampling``: upstream's ``percentageOfNodesToScore``).  An attempt
of a step whose live node count is sampled walks the node axis from a
start index carried through the pod loop, the steps and the segment
(``state0["sample_start"]``; written back to the service at commit),
stops at the step's ``ev["sample_k"]`` feasible nodes, and scores,
normalises and selects over those (``engine/core.py sample_visited``:
a prefix count, no sort; k and the index are operands).  The walk
goes in the order of the service's node tree (scheduler/nodetree.py:
upstream's zone-interleaved node list): this table's slot order where
the lowering saw them agree at every step — it lays a window's new
slots out in the tree's order, so a stream without node churn does —
else by the step's walk tensor (``_SegmentStatics.sample`` 2;
``sample_visited_at``: the masks stay in slot order and one sort of
the node axis a slot finds where the walk stops).  The tree
orders the walk and nothing else: equal totals still go by the rank
tensor, the per-pass featurizer's slot order.  A universe that does
not sample lowers the program it always lowered.

A fourth since PR 49: **volumes**.  A pod whose claims are bound
(claim -> ``spec.volumeName`` -> PV) or that names a disk directly runs
the four volume filters inside its slot (plugins/volumes.py over the
encoding of state/volumes.py: PV rows by class, a pool count for a
volume one pod alone uses, a column only for what two pods share), and
the plugins' counted carries ride the window's state beside
``requested`` (``_SegmentStatics.volumes``; state keys ``vc.<plugin>.
<leaf>``): a bind adds the pod's ``carry_rows``, a pod delete takes them
off, a node that goes takes its rows along.  PersistentVolumes, claims
and StorageClasses may stand in the store or be created by a step,
before the pods of that step that name them.  A universe none of whose
pods reads a volume — whatever ``emptyDir`` / ``configMap`` /
``projected`` volumes they carry — lowers the program it always lowered.

Segments shorter than the compiled K (stream tails, mid-window
vocabulary misses) are tail-padded with inactive no-op steps and reuse
the existing compile.  Anything outside the remaining vocabulary
(patch/update ops, pods with host ports or scheduling gates, claims
the scheduler itself would have to bind, extenders, multiple profiles,
node images, inexact unit scaling, ...)
makes ``lower()`` return None and the segment falls back to the
per-pass path, so coverage can grow incrementally without risking the
locks.
"""

from __future__ import annotations

import bisect
import hashlib
import logging
import math
import os
import threading
import time
import zlib
from dataclasses import dataclass, field, fields, is_dataclass
from functools import partial
from typing import Any, Sequence

import jax
import numpy as np

from ksim_tpu.errors import (
    DeviceUnavailableError,
    ReplayFallback,
    RunCancelled,
    SimulatorError,
)
from ksim_tpu.engine.compilecache import COMPILE_CACHE
from ksim_tpu.engine.kernelreg import device_kernel
from ksim_tpu.faults import FAULTS
from ksim_tpu.obs import TRACE, device_identity, register_provider
from ksim_tpu.state.resources import JSON, name_of, namespace_of
from ksim_tpu.util import note_backend

logger = logging.getLogger(__name__)

#: Every STATIC fallback/discard reason ``ReplayDriver._reject`` can
#: record — the machine-readable half of the taxonomy prose in
#: docs/churn_floor.md.  Each rejection also lands a ``replay.fallback``
#: trace event carrying the reason, so a timeline shows WHICH segment
#: degraded and why; tests/test_obs.py's registry-sync test scans this
#: module's source for reason literals and asserts this set matches
#: (drift = a reason that silently never reaches the trace taxonomy).
FALLBACK_REASONS: frozenset[str] = frozenset(
    {
        # service/profile configuration outside the vocabulary
        "record_mode", "extenders", "shard_mesh",
        "featurizer_override", "multi_profile", "no_profile",
        "queue_hooks", "permit_waiters", "plugin_extender",
        # object vocabulary misses
        "scheduling_gates", "foreign_scheduler", "terminal_phase",
        "host_ports", "node_images",
        "create_bound_pod", "bound_to_unknown_node", "inexact_units",
        # volumes the segment program does not carry (each names its case)
        "ephemeral_volume_claim", "unbound_wffc_claim",
        "volume_victim_search", "volume_object_order", "volume_name_reuse",
        # stream-shape misses
        "pod_name_reuse", "backoff_name_reuse", "node_name_reuse",
        "delete_unknown_pod", "delete_unknown_node",
        "drain_without_requeue", "duplicate_pod_keys",
        # lowering-time guards
        "interpod_local_mismatch", "preemption_filter_set",
        "preemption_bits_width", "full_record_bytes", "priority_levels",
        # post-dispatch validation discards
        "featurize_prediction", "preemption_overflow",
        # degradation ladder (docs/churn_floor.md round 8)
        "lowering_fault", "device_error", "reconcile_fault",
        "breaker_open",
    }
)

#: Dynamic reason families (``op:<op>/<kind>``, ``host_hook:<attr>``) —
#: prefix-matched by the registry-sync test.
FALLBACK_REASON_PREFIXES: tuple[str, ...] = ("op:", "host_hook:")

#: The three volume kinds, by the featurizer argument each feeds.
_VOLUME_ARGS = (
    ("pvs", "persistentvolumes"),
    ("pvcs", "persistentvolumeclaims"),
    ("storage_classes", "storageclasses"),
)

# Steps batched per device dispatch.  The dispatch-latency win scales
# with K; lowering/reconcile host work amortizes over it.  8-32 is the
# useful range (beyond that the universe grows stale and the first
# fallback forces a re-lower anyway).
SEGMENT_STEPS = 16

# record="full" segments stack per-step [Q, F|S, N] result tensors on
# device, so they run at a SHORTER fixed K (one extra compiled shape)
# and are rejected outright when even that would exceed the byte bound
# below ("full_record_bytes" fallback).
FULL_SEGMENT_STEPS = 4
FULL_RECORD_BYTES = 1 << 30

# On-device preemption: the victims-per-node bound (a static shape of the
# victim table).  A search that meets a node holding more pods of a lower
# priority sets an overflow flag and the whole segment is DISCARDED
# before any store effect ("preemption_overflow" fallback) —
# bounded-exact, never approximate.  The candidate bound is upstream's
# own, ``candidate_count(live nodes)``, derived per step by the lowering.
PREEMPT_VICTIMS = 8

# Distinct pod priorities a window's universe may hold with the victim
# search on: the nominated pods' per-node load is carried per level
# ([N, levels, ...]).  More makes the window fall back
# ("priority_levels"); real clusters hold a handful of classes.
PREEMPT_LEVELS_MAX = 32

# Failure containment (docs/churn_floor.md "Failure containment"):
# each segment dispatch runs on a worker thread bounded by the watchdog
# (a hung backend blocks block_until_ready FOREVER); N CONSECUTIVE
# device failures trip a sticky
# circuit breaker that disables the device path for the rest of the
# run, so a dead backend costs N watchdog timeouts total rather than
# one per remaining segment.  Read at ReplayDriver construction so
# tests tune them through the environment.
WATCHDOG_DEFAULT_S = 300.0  # generous: first dispatch includes XLA compile
BREAKER_DEFAULT_N = 3


def _watchdog_seconds() -> float:
    return float(os.environ.get("KSIM_REPLAY_WATCHDOG_S", str(WATCHDOG_DEFAULT_S)))


def _breaker_threshold() -> int:
    return int(os.environ.get("KSIM_REPLAY_BREAKER_N", str(BREAKER_DEFAULT_N)))


def _replay_tp() -> int:
    """``KSIM_REPLAY_TP``: lay every node-axis tensor of the segment
    program over a ``make_mesh(tp, dp=1)`` node mesh (round 17).  1 (the
    default) keeps the single-device layout.  The byte bound
    (``FULL_RECORD_BYTES``) and the preemption victim bound
    (``PREEMPT_VICTIMS``) are PER-SHARD budgets —
    record="full" and bounded-exact preemption scale with the mesh.
    Read at ReplayDriver construction; an explicit service ``shard_mesh``
    takes precedence over the env knob."""
    return max(int(os.environ.get("KSIM_REPLAY_TP", "1")), 1)


#: Minimum node rows per shard before _lower narrows the mesh width.
#: Empirical partitioner-hazard floor, NOT tunable: below it the SPMD
#: preemption scan silently doubled sel/nom values (see the narrowing
#: comment in _lower, docs/churn_floor.md, and the standalone
#: jax-only repro in tools/shard_repro.py).
_MIN_SHARD_NODES = 4


#: Half-open cooldown doubling is bounded here: a backend that stays
#: dead costs one probe per hour at worst, never less often.
_BREAKER_COOLDOWN_CAP_S = 3600.0


def _breaker_cooldown_s() -> float:
    """``KSIM_REPLAY_BREAKER_COOLDOWN_S``: 0 (the default) keeps the
    round-8 STICKY breaker — openings only, the behavior every breaker
    test pins; > 0 arms half-open recovery (ISSUE 11): after the
    cooldown an open breaker admits ONE probe segment, a healthy probe
    closes it (re-promoting the driver to the device path), a failed
    probe re-opens with the cooldown doubled (bounded above)."""
    return float(os.environ.get("KSIM_REPLAY_BREAKER_COOLDOWN_S", "0"))

_I32_MIN = np.iinfo(np.int32).min
_I32_MAX = np.iinfo(np.int32).max


def _backoff_constants() -> tuple[int, int]:
    """(MAX_BACKOFF_PASSES, FLUSH_CAP_PASSES) from the ONE source of
    truth — the per-pass scheduler (lazy import: ksim_tpu.scheduler
    imports this package).  Tuning the service constants must retune the
    device kernel's mirror or the byte-identical count contract breaks."""
    from ksim_tpu.scheduler.service import SchedulerService

    return SchedulerService.MAX_BACKOFF_PASSES, SchedulerService.FLUSH_CAP_PASSES


class ReplayParityError(RuntimeError):
    """Device-resident replay state diverged from the host store — a bug
    in the delta application.  Deliberately NOT a SimulatorError: the
    classified fault handlers must re-raise it, never absorb it into a
    silent per-pass fallback (that would mask a kernel bug behind
    correct-looking counts).  Since the atomic segment reconcile
    (round 8) the store it fired against has been ROLLED BACK — the
    error is loud but no longer leaves device-computed placements
    behind."""


def _pod_key(pod: JSON) -> str:
    """The SERVICE's pod key scheme (`namespace/name`, namespace
    defaulted): op-created objects may lack metadata.namespace until the
    store defaults it, so every universe/event/backoff key must go
    through this one normalization."""
    return f"{namespace_of(pod) or 'default'}/{name_of(pod)}"


# ---------------------------------------------------------------------------
# Canonical slot simulation (the per-pass featurizer's NodeSlots history)
# ---------------------------------------------------------------------------


class _SlotSim:
    """Name-only replica of boundagg.NodeSlots' swap-remove assignment.

    The per-pass path's node tie-break order is the persistent
    featurizer's slot order, which depends on the entire churn history
    (a delete moves the LAST slot's node into the freed slot).  The
    lowering replays that exact evolution one step ahead of the store to
    produce the per-step rank tensors."""

    def __init__(self, slot_of: dict[str, int] | None = None, names: list[str] | None = None) -> None:
        self.slot_of: dict[str, int] = dict(slot_of or {})
        self.names: list[str] = list(names or [])

    def sync(
        self, current_names: Sequence[str]
    ) -> tuple[list[str], list[tuple[str, int]]]:
        """Mirror NodeSlots.sync for a post-step node-name set, in the
        store's name-sorted list order (what featurize receives).

        Returns ``(removed_names, changed_assignments)`` — the per-step
        DELTA, so the lowering maintains its rank row incrementally
        instead of re-walking the whole slot map every step (the old
        O(K*N) python loop).  Entries in ``changed_assignments`` apply
        in order (a name moved twice within one sync keeps its last
        slot)."""
        present = set(current_names)
        removed: list[str] = []
        changed: list[tuple[str, int]] = []
        gone = [s for nm, s in self.slot_of.items() if nm not in present]
        for s in sorted(gone, reverse=True):
            nm = self.names[s]
            last = len(self.names) - 1
            del self.slot_of[nm]
            removed.append(nm)
            if s != last:
                moved = self.names[last]
                self.names[s] = moved
                self.slot_of[moved] = s
                changed.append((moved, s))
            self.names.pop()
        for nm in current_names:
            if nm not in self.slot_of:
                self.slot_of[nm] = len(self.names)
                self.names.append(nm)
                changed.append((nm, len(self.names) - 1))
        return removed, changed


# ---------------------------------------------------------------------------
# Window parse (the store-independent prefix of segment lowering)
# ---------------------------------------------------------------------------


@dataclass
class _StepParse:
    """One step's net object events, window-locally validated."""

    pc: list[str] = field(default_factory=list)  # created pod keys
    pd: list[str] = field(default_factory=list)  # deleted pod keys
    nc: list[str] = field(default_factory=list)  # created node names
    nd: list[str] = field(default_factory=list)  # deleted node names
    flush: bool = False


@dataclass
class _WindowSpec:
    """The STORE-INDEPENDENT prefix of one window's lowering: event
    parsing, op-vocabulary screening, window-local name bookkeeping and
    created-object support checks — everything ``_lower`` needs that
    does not read the ClusterStore or the service's mutable state.

    Built either synchronously (inside the ``replay.lower`` span) or
    SPECULATIVELY for segment N+1 on the main thread while segment N's
    dispatch runs on the watchdogged worker (``replay.prelower`` span /
    fault site) — the double-buffered executor's overlap.  A speculative
    spec is keyed by the identity of its batch lists and discarded
    whenever the window it predicted is not the window that actually
    runs next (mid-window fallback, rollback, shorter consumed prefix,
    service reconfiguration).

    Store-membership validation (delete-of-unknown, name reuse against
    live objects, backoff-entry reuse) cannot run here; those checks are
    recorded in op order in ``checks`` and replayed against the live
    store/service sets by ``_lower``.  A window-local vocabulary miss
    stops the parse and lands in ``err_step``/``err_reason``: the
    consumer lowers only the supported prefix, and the erroring step
    heads the next window, which head-rejects it (prefix-granular
    fallback)."""

    wlen: int  # window length this spec was parsed for
    sched_names: tuple[str, ...]  # service config the support checks used
    n: int = 0  # op-screen prefix length (steps fully parsed)
    head_reason: str | None = None  # op-vocabulary reject of step 0
    err_step: int = _I32_MAX  # step where a window-local miss stopped parse
    err_reason: str | None = None
    steps: list[_StepParse] = field(default_factory=list)
    # (step, kind, key) store-membership checks, in op order; kind in
    # {"create_pod", "delete_pod", "create_node", "delete_node",
    # "create_volume" (key "<kind>/<name>")}.
    checks: list[tuple[int, str, str]] = field(default_factory=list)
    created_pods: list[tuple[int, str, JSON]] = field(default_factory=list)
    created_nodes: list[tuple[int, JSON]] = field(default_factory=list)
    # (step, kind, "namespace/name" or name, object) of the PersistentVolumes,
    # claims and StorageClasses the window creates.
    created_volumes: list[tuple[int, str, str, JSON]] = field(default_factory=list)


# ---------------------------------------------------------------------------
# Persistent lowered-universe cache
# ---------------------------------------------------------------------------


class _LowerCache:
    """Lowered-universe state reused across CONSECUTIVE committed
    segments, making per-segment host lowering O(delta) instead of
    O(universe): the queue-sorted universe (cleaned pod objects + their
    static ``queue_sort_key`` tuples), the priority resolution, and —
    by keeping the surviving objects' IDENTITY stable — every per-pod
    featurizer/encoder memo row behind them.  Only objects created
    inside the new window are featurized fresh.

    Validity contract (docs/churn_floor.md "Incremental lowering +
    pipelined executor"): the cache is trustworthy exactly when nothing
    touched the store except committed device segments, which is what
    ``ClusterStore.mutation_epoch`` certifies — segment reconciles run
    in an epoch-exempt transaction, every other write moves the epoch.
    Invalidation is STRICT: any per-pass fallback, a segment rollback, a
    breaker trip, or an epoch mismatch (out-of-band store write) flushes
    the whole cache; the next lower rebuilds from the store and
    re-screens every object.  ``verify_segment``'s store-vs-device
    parity check (which runs inside every segment transaction) is what
    anchors the cached survivor view to the real store contents."""

    def __init__(self) -> None:
        self.valid = False
        self.epoch = -1
        self.keys: list[str] = []  # queue-sort order
        self.sort_keys: list[tuple] = []  # parallel queue_sort_key tuples
        self.clean_pods: list[JSON] = []  # parallel cleaned pending objects
        self.priority_of = None
        self.prio_gen = 0  # memo token for resolver-dependent per-pod keys
        self.sched_names = None  # profile set the survivors were screened against
        self.hits = 0
        self.misses = 0
        self.invalidations = 0

    def invalidate(self, reason: str) -> None:
        if not self.valid:
            return
        self.valid = False
        self.invalidations += 1
        self.keys = []
        self.sort_keys = []
        self.clean_pods = []
        self.priority_of = None
        self.sched_names = None
        TRACE.event("replay.cache_invalidate", reason=reason)

    def stats(self) -> dict:
        return {
            "hits": self.hits,
            "misses": self.misses,
            "invalidations": self.invalidations,
        }


# ---------------------------------------------------------------------------
# Static program configuration (jit cache key material)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class _SegmentStatics:
    """Hashable statics of one compiled segment program."""

    k: int  # steps per dispatch
    q: int  # compacted queue width
    cap: int  # max_pods_per_pass (large sentinel when uncapped)
    n_tk: int  # inter-pod topology-key vocab width
    n_dom: int  # inter-pod padded domain count (segment id space)
    record: str = "selection"  # "selection" | "full" (streamed results)
    preempt: bool = False  # on-device DefaultPreemption victim search
    v_max: int = PREEMPT_VICTIMS  # lower-priority pods a node (per shard)
    # With ``preempt``: the universe's distinct priorities (bucketed), the
    # level axis of the nominated pods' carried load; and whether every
    # filter verdict of the window is node-local, which lets the victim
    # search evaluate all candidates at once over the node axis.
    n_lvl: int = 1
    local: bool = False
    # Whether any pod of the universe matches or carries an inter-pod
    # term: where none does, a victim's going cannot move the domain view.
    ip_terms: bool = True
    # percentageOfNodesToScore (the service's ``node_sampling``): 0 = every
    # attempt scores every feasible node (the program every window lowered
    # before sampling reached this path, unchanged); 1 = an attempt walks
    # the node axis from the carried start index, in SLOT order, which the
    # lowering saw to be the order of the service's node tree at every
    # step (it lays new slots out in that order); 2 = the walk goes by the
    # step's walk tensor, each slot's place in the tree's list (one sort
    # of the node axis a slot, ``sample_visited_at``: node churn has
    # moved the two orders apart).
    sample: int = 0
    # Whether a pod of the universe reads a volume: the volume plugins'
    # counted carries (``carry_rows``) then ride the window's state
    # (keys ``vc.<plugin>.<leaf>``) through pod deletes and node events,
    # and the steps report ``vatt`` / ``vrej`` / ``vheld`` / ``vhead``.
    # False lowers the program every volume-free window lowered.
    volumes: bool = False
    tp: int = 1  # node-axis mesh width (round 17 sharded replay)
    # Round 19: the vmap axis name the fleet program maps lanes over, or
    # None for a solo program.  With it set, the preemption-search gate
    # reduces its predicate over the lane axis (lax.psum) so the
    # lax.cond predicate stays UNBATCHED under vmap — the gate lowers
    # to a real XLA conditional instead of a both-branches select (the
    # select bomb, docs/scaling.md "2-D mesh (round 19)").
    lane_axis: "str | None" = None


# ---------------------------------------------------------------------------
# The compiled K-step program
# ---------------------------------------------------------------------------


@device_kernel(static=("st",))
def _derive_interpod(loc: dict, ipa: dict, st: _SegmentStatics) -> dict:
    """Local per-node term accumulators -> the domain-aggregated carry
    view the InterPodAffinity kernels consume (state/interpod.py
    cnt_node/ecnt_node/ew_node/total semantics):

    ``cnt[n, t] = sum over n' in n's term_tk[t]-domain of loc_cnt[n', t]``

    computed per topology key with one segment reduction (the key vocab
    is tiny and static, so the per-key results select together), and
    ``total[t]`` summed over key-carrying nodes only — exactly the
    encoder's "no topologyPair exists on a keyless node" rule."""
    import jax
    import jax.numpy as jnp

    node_dom = ipa["node_dom"]  # i32 [N, TK]
    term_tk = ipa["term_tk"]  # i32 [T]
    dom_t = ipa["dom_t"]  # i32 [N, T]
    out = {}
    for name, key in (("cnt", "cnt"), ("ecnt", "eat"), ("ew", "vw")):
        arr = loc[key]  # i32 [N, T]
        acc = jnp.zeros_like(arr)
        for k in range(st.n_tk):
            ids = node_dom[:, k]  # [N], -1 = key absent
            safe = jnp.where(ids >= 0, ids, st.n_dom)  # junk segment
            seg = jax.ops.segment_sum(arr, safe, num_segments=st.n_dom + 1)
            derived = jnp.where(ids[:, None] >= 0, seg[jnp.minimum(safe, st.n_dom)], 0)
            acc = jnp.where((term_tk == k)[None, :], derived, acc)
        out[name] = acc
    out["total"] = jnp.sum(jnp.where(dom_t >= 0, loc["cnt"], 0), axis=0)
    return out


@device_kernel(static=("st", "prog"))
def _segment_body(st: _SegmentStatics, prog, const: dict, ev: dict, state0: dict):
    """Run K scenario steps on-device.

    const: universe-static arrays — node statics (allocatable /
        allowed_pods / unschedulable), pod rows (requests / nonzero /
        tolerates / has_requests / spread-selector and inter-pod term
        rows; with preemption also priority / importance / start-time
        ranks), the full plugin aux pytree, and (full-record preemption)
        the per-plugin reason-bit resolvability table.
    ev: per-step event streams, leading axis K — pod/node create/delete
        index lists (-1 padded), the flush flag, the canonical rank
        tensor, the per-step active flag (False = tail padding: the step
        is a pure no-op), (preemption) the per-step name-order node
        ranks + upstream candidate count, and (sampling) the per-step
        numFeasibleNodesToFind.
    state0: the carried cluster tensor state at segment start; with
        sampling, the walk's start index.

    Returns (final_state, outputs) where outputs stack per-step selected
    node rows + attempted pod rows and the step aggregates, plus (full
    record) the per-attempt result tensors and (preemption) nominated
    nodes / victim rows / the bound-overflow flag."""
    import jax
    import jax.numpy as jnp

    from ksim_tpu.plugins.base import NodeStateView, PodBatch
    from ksim_tpu.engine.core import SCAN_UNROLL, sample_visited, sample_visited_at

    # Slots per trip of the pod loops (``run_slots`` in ``_run_step``):
    # the widest block <= SCAN_UNROLL that divides the queue, so a block
    # never reaches past slot q - 1 (every bucket_size rung is a
    # multiple of 4, the default).
    # The victim-search variant runs one slot a trip: its slot body holds
    # the whole search, and four copies of it quadruple the compile
    # (243 s against ~1 min on the chip, PR 32) for a loop overhead that
    # is small beside a slot of that variant.
    blk = 1 if st.preempt else math.gcd(SCAN_UNROLL, st.q)
    max_backoff, flush_cap = _backoff_constants()
    # _record_attempts' delay is min(2^(attempts_new-1), MAX) — computed
    # as a shift with the exponent clamped where the cap saturates.
    shift_cap = max(max_backoff.bit_length() - 1, 0)
    aux = const["aux"]
    nstat = const["node"]
    prow = const["pods"]
    ipa = aux["interpod"]
    P = prow["requests"].shape[0]
    N = nstat["allocatable"].shape[0]
    sel_rows = aux["spread"]["pod_sel_match"]  # bool [P, S]
    qm_rows = ipa["pod_term_match"]  # bool [P, T]
    eat_rows = ipa["pod_eat"]  # i32 [P, T]
    vw_rows = ipa["pod_vw"]  # i32 [P, T]
    n_filters = sum(1 for sp in prog.plugins if sp.filter_enabled)
    n_scores = sum(1 for sp in prog.plugins if sp.score_enabled)
    bits_dtype, final_dtype = prog._result_dtypes()
    # Raw scores keep the width the mode computes them in (core.py
    # records them unconverted): int64 in exact (x64) mode, else int32.
    raw_dtype = jax.dtypes.canonicalize_dtype(int)
    # The effective victim bound: the configured static is a PER-SHARD
    # budget (round 17) — multiplied by the mesh width, then clamped to
    # the padded pod axis.  Bounded-exact: a node that holds more pods
    # of a lower priority discards the segment before any store effect.
    v_eff = min(st.v_max * st.tp, P)
    L = st.n_lvl
    # The nominated pods' per-node load, carried per priority level
    # (``nm_*[n, l]`` = the sum over the pods nominated to node n whose
    # level is l or above, i.e. what a pod of level l has to count in):
    # the state keys, and the per-pod rows each sums (``nm_cnt`` is a
    # head count and has none).  A
    # window whose verdicts are all node-local (st.local) never reads
    # the spread / inter-pod terms of a nominee, and carries none.
    nm_keys = ("nm_req", "nm_cnt")
    nm_rows = {"nm_req": prow["requests"]}
    if st.preempt and not st.local:
        nm_keys += ("nm_sel", "nm_qm", "nm_eat", "nm_vw")
        nm_rows.update(nm_sel=sel_rows, nm_qm=qm_rows, nm_eat=eat_rows, nm_vw=vw_rows)

    # Volumes (st.volumes): the plugins whose carry is LINEAR in the pods
    # bound to a node (``carry_rows``: NodeVolumeLimits, VolumeRestrictions
    # and the one-pool legacy names) — per plugin its per-pod rows, each
    # leaf carried through the window as ``vc.<plugin>.<leaf>`` — and which
    # of the filter chain's verdicts are a volume filter's.
    vol_rows = {}
    vol_filters = ()
    vol_summary = None
    if st.volumes:
        vol_rows = {
            sp.plugin.name: sp.plugin.carry_rows(aux)
            for sp in prog.plugins
            if hasattr(sp.plugin, "carry_rows")
        }
        enabled = [sp.plugin for sp in prog.plugins if sp.filter_enabled]
        vol_filters = tuple(
            i for i, pl in enumerate(enabled) if getattr(pl, "volume_family", False)
        )
        vol_summary = next(
            (sp.plugin for sp in prog.plugins if hasattr(sp.plugin, "attach_summary")),
            None,
        )

    def _vol_leaves():
        """(state key, the plugin's per-pod rows [P, X]) per carried leaf."""
        for pn, rows in vol_rows.items():
            for leaf, table in rows.items():
                yield f"vc.{pn}.{leaf}", table

    if st.preempt:
        # What a search reads of a node's lower-priority pods, each a
        # column of the pod axis: its requests (one a resource), its
        # priority, its start rank.  The victim table gathers them once
        # a pass and level (``_lower_table``).
        R = prow["requests"].shape[1]
        pod_facts = tuple(prow["requests"][:, c] for c in range(R)) + (
            prow["priority"],
            prow["start_rank"],
        )

    def _nom_apply(live: dict, nodes, rows, sign: int) -> dict:
        """Put (sign +1) or take (-1) the load of pod ``rows`` [E], as
        nominated, on ``nodes`` [E] (N = none: the update drops): every
        level at or below the pod's own counts it."""
        m = jnp.arange(L, dtype=jnp.int32)[None, :] <= prow["level"][rows][:, None]
        live = dict(live)
        heads = live["nm_cnt"]
        live["nm_cnt"] = heads.at[nodes].add(m.astype(heads.dtype) * sign, mode="drop")
        for key, table in nm_rows.items():
            arr = live[key]
            upd = (
                m[:, :, None].astype(arr.dtype)
                * table[rows][:, None, :].astype(arr.dtype)
                * sign
            )
            live[key] = arr.at[nodes].add(upd, mode="drop")
        return live

    def _nom_at(live: dict, lvl) -> dict:
        """What a pod of level ``lvl`` counts in on every node."""
        return {
            key: jax.lax.dynamic_index_in_dim(live[key], lvl, 1, keepdims=False)
            for key in nm_keys
        }

    # One-row updates of the pod loop's carried arrays, as one-hot
    # elementwise passes: a scatter of one row costs the chip ~18 us a
    # slot (my chip runs, PR 32), an elementwise pass over a few thousand
    # rows a fraction of that — the idiom of ``NodeStateView.commit`` and
    # of the plugins' ``carry_commit`` (no gather / scatter in the scan
    # step).  ``idx`` out of range (N, P, -1) touches nothing.
    def _hit(arr, idx):
        hit = jnp.arange(arr.shape[0], dtype=jnp.int32) == idx
        return hit.reshape((-1,) + (1,) * (arr.ndim - 1))

    def _row_add(arr, idx, row):
        return arr + jnp.where(_hit(arr, idx), jnp.asarray(row, arr.dtype)[None], 0)

    def _row_set(arr, idx, row):
        return jnp.where(_hit(arr, idx), jnp.asarray(row, arr.dtype)[None], arr)

    def _col_set(arr, idx, col):
        """``_row_set`` for an array whose LAST axis is the indexed one."""
        hit = jnp.arange(arr.shape[-1], dtype=jnp.int32) == idx
        return jnp.where(hit, jnp.asarray(col, arr.dtype)[..., None], arr)

    def _nom_apply_one(live: dict, node, row, sign: int) -> dict:
        """``_nom_apply`` for one pod inside the pod loop."""
        m = jnp.arange(L, dtype=jnp.int32) <= prow["level"][row]
        live = dict(live)
        live["nm_cnt"] = _row_add(live["nm_cnt"], node, m.astype(jnp.int32) * sign)
        for key, table in nm_rows.items():
            live[key] = _row_add(
                live[key], node, m[:, None] * table[row][None, :] * sign
            )
        return live

    def _victim_deltas(rows, act):
        """Summed universe-row contributions of ``rows`` where ``act``
        — the aggregate a victim set adds to (or removal subtracts
        from) one node's carried state (mirrors apply_pod_deletes)."""
        w = act[:, None]
        safe = jnp.clip(rows, 0, P - 1)
        return dict(
            req=jnp.sum(jnp.where(w, prow["requests"][safe], 0), axis=0),
            nz=jnp.sum(jnp.where(w, prow["nonzero_requests"][safe], 0), axis=0),
            # (x64 mode promotes a sum of i32 to i64; the carried
            # aggregates these are taken from stay i32.)
            cnt=jnp.sum(act.astype(jnp.int32)).astype(jnp.int32),
            sel=jnp.sum(jnp.where(w, sel_rows[safe].astype(jnp.int32), 0), axis=0).astype(jnp.int32),
            qm=jnp.sum(jnp.where(w, qm_rows[safe].astype(jnp.int32), 0), axis=0).astype(jnp.int32),
            eat=jnp.sum(jnp.where(w, eat_rows[safe], 0), axis=0).astype(eat_rows.dtype),
            vw=jnp.sum(jnp.where(w, vw_rows[safe], 0), axis=0).astype(vw_rows.dtype),
        )

    def apply_pod_deletes(s: dict, pdel: jnp.ndarray) -> dict:
        v = pdel >= 0
        safe = jnp.clip(pdel, 0, P - 1)
        bnode = jnp.where(v, s["bound"][safe], -1)  # [Ed]
        tgt = jnp.where(bnode >= 0, bnode, N)  # OOB rows drop
        s = dict(s)
        s["requested"] = s["requested"].at[tgt].add(
            -prow["requests"][safe], mode="drop"
        )
        s["nonzero_requested"] = s["nonzero_requested"].at[tgt].add(
            -prow["nonzero_requests"][safe], mode="drop"
        )
        s["pod_count"] = s["pod_count"].at[tgt].add(-1, mode="drop")
        s["spread"] = s["spread"].at[tgt].add(
            -sel_rows[safe].astype(s["spread"].dtype), mode="drop"
        )
        s["ip_cnt"] = s["ip_cnt"].at[tgt].add(
            -qm_rows[safe].astype(s["ip_cnt"].dtype), mode="drop"
        )
        s["ip_eat"] = s["ip_eat"].at[tgt].add(-eat_rows[safe], mode="drop")
        s["ip_vw"] = s["ip_vw"].at[tgt].add(-vw_rows[safe], mode="drop")
        for key, table in _vol_leaves():
            # A deleted pod detaches: its rows come off its node's.
            s[key] = s[key].at[tgt].add(
                -table[safe].astype(s[key].dtype), mode="drop"
            )
        gone = jnp.where(v, pdel, P)
        s["alive"] = s["alive"].at[gone].set(False, mode="drop")
        s["bound"] = s["bound"].at[gone].set(-1, mode="drop")
        if st.preempt:
            # A deleted pod's nomination goes with it.
            nn = jnp.where(v, s["nom_node"][safe], -1)
            s = _nom_apply(s, jnp.where(nn >= 0, nn, N), safe, -1)
            s["nom_node"] = s["nom_node"].at[gone].set(-1, mode="drop")
        return s

    def apply_node_events(s: dict, ndel, ncre) -> dict:
        s = dict(s)
        dmask = (
            jnp.zeros(N, bool).at[jnp.where(ndel >= 0, ndel, N)].set(True, mode="drop")
        )
        s["valid"] = s["valid"] & ~dmask
        keep = ~dmask
        s["requested"] = jnp.where(keep[:, None], s["requested"], 0)
        s["nonzero_requested"] = jnp.where(keep[:, None], s["nonzero_requested"], 0)
        s["pod_count"] = jnp.where(keep, s["pod_count"], 0)
        s["spread"] = jnp.where(keep[:, None], s["spread"], 0)
        s["ip_cnt"] = jnp.where(keep[:, None], s["ip_cnt"], 0)
        s["ip_eat"] = jnp.where(keep[:, None], s["ip_eat"], 0)
        s["ip_vw"] = jnp.where(keep[:, None], s["ip_vw"], 0)
        for key, _table in _vol_leaves():
            # A node that goes takes its attachments along (a node that
            # comes has a slot of its own and starts with none).
            s[key] = jnp.where(keep[:, None], s[key], 0)
        if st.preempt:
            # A node that goes takes the nominations onto it along (the
            # store keeps the dead name on the pod; it counts for
            # nothing on either path and goes at the pod's next attempt).
            for key in nm_keys:
                arr = s[key]
                s[key] = jnp.where(
                    keep.reshape((N,) + (1,) * (arr.ndim - 1)), arr, 0
                )
            nn = s["nom_node"]
            s["nom_node"] = jnp.where(
                (nn >= 0) & dmask[jnp.clip(nn, 0, N - 1)], -1, nn
            )
        # Drained nodes' pods re-enter the pending queue (the runner's
        # requeue_on_node_delete — their backoff state is untouched, the
        # per-pass entry was popped when they scheduled).
        requeued = s["alive"] & (s["bound"] >= 0) & dmask[jnp.clip(s["bound"], 0, N - 1)]
        s["bound"] = jnp.where(requeued, -1, s["bound"])
        s["valid"] = (
            s["valid"].at[jnp.where(ncre >= 0, ncre, N)].set(True, mode="drop")
        )
        return s

    def _lane_cond(pred, on_true, on_false):
        """``lax.cond`` on a per-trajectory predicate.  In the fleet
        program (st.lane_axis set) the predicate is psum-reduced over the
        vmap lane axis first, which keeps it UNBATCHED — a real XLA
        conditional instead of the both-branches select a batched
        predicate forces (docs/scaling.md "2-D mesh (round 19)") — and a
        lane whose own predicate is false keeps ``on_false``'s value:
        if ANY lane takes the branch, every lane computes it."""
        if st.lane_axis is None:
            return jax.lax.cond(pred, on_true, on_false)
        go = jax.lax.psum(pred.astype(jnp.int32), st.lane_axis) > 0
        out = jax.lax.cond(go, on_true, on_false)
        return jax.tree.map(lambda a, b: jnp.where(pred, a, b), out, on_false())

    def _lane_max(n):
        """A loop bound every lane of the fleet program shares (pmax over
        the lane axis; the solo program's own): a batched bound would
        batch the loop's predicate.  The loops below are written so that
        a trip past a lane's own bound changes nothing for it."""
        return n if st.lane_axis is None else jax.lax.pmax(n, st.lane_axis)

    def _filters_with(nstate, pcarries, live, pod, over):
        """The filter chain's verdicts with the nominated pods of
        ``over`` (``_nom_at``) counted in as if they ran on their nodes
        (RunFilterPluginsWithNominatedPods' first run)."""
        view = nstate._replace(
            requested=nstate.requested + over["nm_req"].astype(nstate.requested.dtype),
            pod_count=nstate.pod_count + over["nm_cnt"].astype(nstate.pod_count.dtype),
        )
        carr = pcarries
        if not st.local:
            carr = dict(pcarries)
            spread_c = pcarries["PodTopologySpread"]
            carr["PodTopologySpread"] = spread_c + over["nm_sel"].astype(spread_c.dtype)
            carr["InterPodAffinity"] = _derive_interpod(
                {
                    "cnt": live["ip_cnt"] + over["nm_qm"].astype(live["ip_cnt"].dtype),
                    "eat": live["ip_eat"] + over["nm_eat"],
                    "vw": live["ip_vw"] + over["nm_vw"],
                },
                ipa,
                st,
            )
        return prog._eval_filters(view, pod, aux, carr)[0]

    def _lower_table(live, lower):
        """The victim table: the pods of ``lower`` (bool [P]) grouped by
        the node they are bound to, each node's in MoreImportantPod
        order — ``cnt`` [N], ``vrow`` (universe rows), ``vact``
        (present) and ``facts`` (each such pod's requests, priority and
        start rank, ``pod_facts``; zero where absent).  One sort of the
        pod axis by (node, importance rank), a binary search per node
        for where its group starts, two gathers of N x V rows; a node's
        pods beyond the V-th are cut (a search raises the overflow flag
        for such a node it examines, and the segment is discarded: a cut
        row is never refilled).

        Kept with the NODE AXIS LAST — ``vrow`` / ``vact`` [V, N],
        ``facts`` R + 2 arrays [V, N] — so that the carried arrays are
        dense on the chip (a trailing axis of 5 or 8 is padded to 128
        lanes, and the compiler keeps such an axis last whatever the
        order it is written in: 25 MB for the facts where 1 MB does) and
        one node's entries are one column (``_col_set``); a search
        reads them as they are kept.

        Built ONCE A PASS AND PRIORITY LEVEL, by the first attempt of
        the level that searches, and carried through the pass's pod loop
        (``pod_body_preempt``).  Between two searches of a level the set
        it groups only SHRINKS: the queue is in priority order (the
        universe axis, checked by the lowering), so a pod that binds in
        the pass is never of a lower priority than a preemptor behind
        it; events and deletes happen between passes; the only pods that
        go inside a pass are a preemptor's own victims, all on the one
        node it chose.  ``_repair_table`` takes those out of that node's
        row after every verdict."""
        nkey = jnp.where(lower, live["bound"], N).astype(jnp.int32)
        sn, _si, srow = jax.lax.sort(
            (nkey, prow["imp_rank"], jnp.arange(P, dtype=jnp.int32)), num_keys=2
        )
        edges = jnp.searchsorted(
            sn, jnp.arange(N + 1, dtype=jnp.int32), side="left"
        ).astype(jnp.int32)
        start, cnt = edges[:-1], edges[1:] - edges[:-1]
        kk = jnp.arange(v_eff, dtype=jnp.int32)
        vact = kk[:, None] < cnt[None, :]
        vrow = jnp.where(
            vact, srow[jnp.clip(start[None, :] + kk[:, None], 0, P - 1)], 0
        )
        facts = tuple(jnp.where(vact, col[vrow], 0) for col in pod_facts)
        return {"cnt": cnt, "vrow": vrow, "vact": vact, "facts": facts}

    def _repair_table(table, found):
        """The carried victim table after a search's verdict (``found``):
        the chosen node's victims leave its row, the pods that stay close
        up in order (``found["vic"]`` is slot-aligned with the row: -1
        where the pod was reprieved or absent), the count falls by the
        victims.  One row, written as one-hot passes like the rest of the
        pod loop's state; no nomination (``nom`` < 0) touches nothing."""
        nom = found["nom"]
        chosen = jnp.maximum(nom, 0)

        def at(arr):
            return jax.lax.dynamic_index_in_dim(arr, chosen, arr.ndim - 1, keepdims=False)

        kk = jnp.arange(v_eff, dtype=jnp.int32)
        stay = at(table["vact"]) & (found["vic"] < 0)  # [V]
        dest = jnp.cumsum(stay.astype(jnp.int32)) - 1
        # place[d, s]: the pod in slot s stays and lands in slot d.
        place = stay[None, :] & (dest[None, :] == kk[:, None])
        n_vic = jnp.sum((found["vic"] >= 0).astype(jnp.int32))
        return dict(
            table,
            cnt=_row_add(table["cnt"], nom, -n_vic),
            vrow=_col_set(
                table["vrow"],
                nom,
                jnp.sum(jnp.where(place, at(table["vrow"])[None, :], 0), axis=1),
            ),
            vact=_col_set(table["vact"], nom, jnp.any(place, axis=1)),
            facts=tuple(
                _col_set(f, nom, jnp.sum(jnp.where(place, at(f)[None, :], 0), axis=1))
                for f in table["facts"]
            ),
        )

    def _victims_over_nodes(nstate, pcarries, live, pod, cnt, vreq, vact, examine, over):
        """selectVictimsOnNode for EVERY node at once, for a window whose
        filter verdicts are node-local (st.local): a node's verdict reads
        that node's own load, so one evaluation of the chain over the
        node axis, on a state where every node's lower-priority pods are
        gone, answers each node's own question; then the pods come back
        rank by rank (each node's k-th most important at step k) and stay
        where the chain still passes.  Each hypothetical state goes
        through the chain ONCE, with the nominated pods of ``over``
        counted in: in a node-local window that run decides both of
        upstream's (``_preempt_search`` has the argument).  ``vreq``: the
        victims' requests, one [V, N] array a resource; ``vact`` [V, N].
        Returns (fits with all lower gone [N], victim mask [V, N], whole
        chain evaluations run)."""
        req_dt, cnt_dt = nstate.requested.dtype, nstate.pod_count.dtype
        base_req = nstate.requested - jnp.stack(
            [jnp.sum(f, axis=0) for f in vreq], axis=1
        ).astype(req_dt)
        base_cnt = nstate.pod_count - jnp.sum(vact, axis=0).astype(cnt_dt)

        def fit(req, pc):
            view = nstate._replace(requested=req, pod_count=pc)
            return _filters_with(view, pcarries, live, pod, over)

        fit0 = fit(base_req, base_cnt) & examine

        def reprieve(k, c):
            req, pc, vic = c
            a = jax.lax.dynamic_index_in_dim(vact, k, 0, keepdims=False)
            r = jnp.stack(
                [jax.lax.dynamic_index_in_dim(f, k, 0, keepdims=False) for f in vreq],
                axis=1,
            )
            t_req = req + r.astype(req_dt)
            t_pc = pc + a.astype(cnt_dt)
            ok_k = fit(t_req, t_pc)
            back = a & ok_k  # reprieved: stays re-added
            vic = jax.lax.dynamic_update_index_in_dim(vic, a & ~ok_k, k, 0)
            return (
                jnp.where(back[:, None], t_req, req),
                jnp.where(back, t_pc, pc),
                vic,
            )

        # A rank past a node's own pods is absent there (vact false):
        # the trip is a no-op for it.
        ranks = jnp.max(jnp.where(fit0, jnp.minimum(cnt, v_eff), 0)).astype(jnp.int32)
        _req, _pc, vic = jax.lax.fori_loop(
            0,
            _lane_max(ranks),
            reprieve,
            (base_req, base_cnt, jnp.zeros((v_eff, N), bool)),
        )
        return fit0, vic & fit0[None, :], 1 + ranks

    def _victims_by_walk(nstate, pcarries, live, pod, cnt, vrow, vact, examine, over, has_nom, rank_names, want_k):
        """selectVictimsOnNode node by node, for a window that holds a
        DoNotSchedule spread constraint or a required pod (anti-)affinity
        (their verdicts read other nodes' pods): the nodes to examine in
        live name order, one exact hypothetical state a check — that
        node's lower-priority pods gone, the spread and inter-pod carries
        re-derived from the modified locals — through the whole compiled
        chain, both filter runs (a nominee can satisfy an affinity term
        here, so neither run decides the other), until ``want_k``
        candidates are found.  Returns (candidate [N], victim mask
        [V, N], whole chain evaluations run)."""
        order = jnp.argsort(jnp.where(examine, rank_names, _I32_MAX)).astype(jnp.int32)
        n_exam = jnp.sum(examine.astype(jnp.int32)).astype(jnp.int32)

        def eval_fit(node_i, rows, act):
            d = _victim_deltas(rows, act)
            view = nstate._replace(
                requested=nstate.requested.at[node_i].add(-d["req"]),
                nonzero_requested=nstate.nonzero_requested.at[node_i].add(-d["nz"]),
                pod_count=nstate.pod_count.at[node_i].add(-d["cnt"]),
            )
            spread_c = pcarries["PodTopologySpread"]
            loc = {
                "ip_cnt": live["ip_cnt"].at[node_i].add(-d["qm"]),
                "ip_eat": live["ip_eat"].at[node_i].add(-d["eat"]),
                "ip_vw": live["ip_vw"].at[node_i].add(-d["vw"]),
            }
            carr = dict(pcarries)
            carr["PodTopologySpread"] = spread_c.at[node_i].add(
                -d["sel"].astype(spread_c.dtype)
            )
            carr["InterPodAffinity"] = _derive_interpod(
                {"cnt": loc["ip_cnt"], "eat": loc["ip_eat"], "vw": loc["ip_vw"]},
                ipa,
                st,
            )
            ok_a = prog._eval_filters(view, pod, aux, carr)[0][node_i]
            ok_b = jax.lax.cond(
                has_nom,
                lambda: _filters_with(view, carr, loc, pod, over)[node_i],
                lambda: jnp.ones((), bool),
            )
            return ok_a & ok_b

        def wanted(i, found):
            return (i < n_exam) & (found < want_k)

        # What one ``eval_fit`` runs of the chain.
        per_fit = 1 + has_nom.astype(jnp.int32)

        def walk(c):
            i, found, is_c, vic, runs = c
            # In the fleet program a lane that is done idles here while
            # another still walks (its trips write nothing).
            more = wanted(i, found)
            n_i = order[jnp.minimum(i, N - 1)]
            rows, act = vrow[:, n_i], vact[:, n_i] & more
            fit0 = eval_fit(n_i, rows, act)

            def reprieve(v, rc):
                removed, vc = rc
                test = removed.at[v].set(False)
                ok_v = eval_fit(n_i, rows, act & test)
                removed = jnp.where(act[v] & ok_v, test, removed)
                return removed, vc.at[v].set(act[v] & ~ok_v)

            trips = jnp.where(fit0, jnp.minimum(cnt[n_i], v_eff), 0).astype(jnp.int32)
            _removed, vc = jax.lax.fori_loop(
                0, _lane_max(trips), reprieve, (act, jnp.zeros(v_eff, bool))
            )
            cand = more & fit0 & jnp.any(vc)
            at = jnp.where(more, n_i, N)
            return (
                i + more.astype(jnp.int32),
                found + cand.astype(jnp.int32),
                is_c.at[at].set(cand, mode="drop"),
                vic.at[:, at].set(vc & cand, mode="drop"),
                runs + jnp.where(more, (1 + trips) * per_fit, 0),
            )

        def any_lane_wants(c):
            go = wanted(c[0], c[1])
            if st.lane_axis is None:
                return go
            return jax.lax.psum(go.astype(jnp.int32), st.lane_axis) > 0

        _i, _found, is_c, vic, runs = jax.lax.while_loop(
            any_lane_wants,
            walk,
            (
                jnp.int32(0),
                jnp.int32(0),
                jnp.zeros(N, bool),
                jnp.zeros((v_eff, N), bool),
                jnp.int32(0),
            ),
        )
        return is_c, vic, runs

    def _preempt_search(s, nstate, pcarries, live, pod, lvl, bits_mat, ev_k, table):
        """DefaultPreemption's victim search for one unschedulable pod,
        against the pass's state as its predecessors left it (their
        binds, their victims gone, their nominations), over the pass's
        carried victim ``table`` of the pod's priority level
        (``_lower_table``: current when this runs; the search itself
        sorts, searches and gathers nothing of the pod axis):

        - nodes to examine = nodes holding >= 1 pod of a lower priority,
          resolvable per the reason-bit table (full-record mode only —
          the per-pass path has no bits in selection mode);
        - a node is a candidate when the pod passes every filter there
          with all of those pods gone — as the node stands and with the
          nominated pods of the pod's priority or above counted in — and
          at least one of them has to stay gone once they come back in
          MoreImportantPod order (the pre-lowered imp_rank); the lowering
          gates on the profile's filter set matching the oracle fit
          chain (preemption.py ORACLE_FIT_FILTER_NAMES);
        - the first ``want`` candidates in live name order are kept
          (upstream's candidate_count; it stops looking there): those
          whose name rank is at most the ``want``-th smallest among the
          candidates', found by ONE sort of the node axis in slot space
          (``name_rank`` is a permutation of the live nodes);
        - pickOneNodeForPreemption is the lexicographic min over (max
          victim prio, sum of prio + 2**31 as two 16-bit limbs, count,
          -latest earliest-top-start, name order) — the host's
          narrowing cascade;
        Returns the verdict — the nominated slot (-1: none), the victim
        rows in reprieve order, the overflow flag, the candidates kept,
        the whole-chain filter evaluations it ran — and changes nothing
        (``_apply_preemption`` does).

        ONE CHAIN EVALUATION A HYPOTHETICAL STATE IN A NODE-LOCAL WINDOW
        (``st.local``; ``_victims_over_nodes``, and the attempt itself in
        ``pod_body_preempt``).  Upstream runs the chain twice
        (RunFilterPluginsWithNominatedPods: with the nominees counted
        in, then as the node stands) and a node has to pass both.  Here
        the first run alone decides: (1) ``st.local`` says no pod of the
        window carries a DoNotSchedule spread constraint or a required
        pod (anti-)affinity, so ``_filters_with`` adds the nominees'
        ``nm_req`` / ``nm_cnt`` to ``requested`` / ``pod_count`` and
        touches nothing else; (2) of the chain the lowering admits —
        ``ORACLE_FIT_FILTER_NAMES`` with or without the volume filters,
        anything else raises ``preemption_filter_set`` and the window
        goes to the host — only NodeResourcesFit reads those two, and it
        is monotone in both: more load never makes a node pass; (3)
        ``nm_req`` / ``nm_cnt`` are sums of requests and head counts,
        never negative.  So a node that passes with the nominees counted
        in passes as it stands (``ok_with & ok_plain == ok_with``), and
        where nobody is nominated the addend is zero and the two runs
        are one.  The gate fails closed: a filter outside that set never
        reaches this program, and a window that is not node-local takes
        ``_victims_by_walk``, where a nominee can SATISFY an affinity
        term and both runs stay."""
        valid_now = s["valid"]
        if st.record == "full":
            fail = bits_mat != 0  # [F, N]
            fail_any = jnp.any(fail, axis=0)
            first = jnp.argmax(fail, axis=0)
            bval = jnp.take_along_axis(bits_mat, first[None, :], axis=0)[0]
            tw = const["resolv"].shape[1]
            bval = jnp.clip(bval, 0, tw - 1)
            resolvable = const["resolv"][first, bval] & fail_any
        else:
            resolvable = jnp.ones(N, bool)
        rank_names, want_k = ev_k["name_rank"], ev_k["want"]
        cnt, vrow, vact = table["cnt"], table["vrow"], table["vact"]
        vreq, vprio, vstart = table["facts"][:R], table["facts"][R], table["facts"][R + 1]
        examine = (cnt > 0) & valid_now & resolvable
        over_v = jnp.any(examine & (cnt > v_eff))
        over = _nom_at(live, lvl)
        if st.local:
            # One run of the chain a state: a filter outside
            # ORACLE_FIT_FILTER_NAMES and the volume filters has sent the
            # window to the host already (the docstring has the argument).
            is_c, vic, runs = _victims_over_nodes(
                nstate, pcarries, live, pod, cnt, vreq, vact, examine, over
            )
            is_c = is_c & jnp.any(vic, axis=0)
        else:
            has_nom = jnp.any(over["nm_cnt"] > 0)
            is_c, vic, runs = _victims_by_walk(
                nstate, pcarries, live, pod, cnt, vrow, vact, examine, over,
                has_nom, rank_names, want_k,
            )
        # Upstream stops after `want` candidates (discovery = name
        # order); a node whose pods were all reprieved is none.  The
        # want-th smallest name rank among the candidates bounds them
        # (_I32_MAX where there are fewer: all stay); single operand,
        # unstable, as ``sample_visited_at`` sorts.
        by_name = jnp.sort(jnp.where(is_c, rank_names, _I32_MAX), stable=False)
        last = by_name[jnp.clip(want_k - 1, 0, N - 1)]
        keep = is_c & (rank_names <= last) & (want_k > 0)
        any_c = jnp.any(keep)
        maxp = jnp.max(jnp.where(vic, vprio, _I32_MIN), axis=0)
        # Sum of (priority + 2**31) over the victims, exactly, in 32-bit
        # lanes: each term as two 16-bit limbs, the low sum's carry
        # folded into the high one.
        lo = jnp.sum(jnp.where(vic, vprio & 0xFFFF, 0), axis=0)
        hi = jnp.sum(jnp.where(vic, (vprio >> 16) + 0x8000, 0), axis=0) + (lo >> 16)
        lo = lo & 0xFFFF
        n_vic = jnp.sum(vic.astype(jnp.int32), axis=0)
        est = jnp.min(
            jnp.where(
                vic & (vprio == maxp[None, :]),
                vstart,
                _I32_MAX,
            ),
            axis=0,
        )
        m = keep
        for arr, take_min in (
            (maxp, True),
            (hi, True),
            (lo, True),
            (n_vic, True),
            (est, False),
            (rank_names, True),
        ):
            kv = jnp.where(m, arr, _I32_MAX if take_min else _I32_MIN)
            tgt = jnp.min(kv) if take_min else jnp.max(kv)
            m = m & (arr == tgt)
        chosen = jnp.argmax(m).astype(jnp.int32)
        return {
            "nom": jnp.where(any_c, chosen, -1).astype(jnp.int32),
            "vic": jnp.where(any_c & vic[:, chosen], vrow[:, chosen], -1),
            "over": over_v,
            "cands": jnp.sum(keep.astype(jnp.int32)).astype(jnp.int32),
            "runs": runs.astype(jnp.int32),
        }

    def _apply_preemption(nstate, pcarries, live, pod, lvl, found):
        """What a search's verdict (``found``: the nominated slot or -1,
        the victim rows) does to the pass's state: the victims go,
        nominations of a lower priority onto the node are cleared, the pod
        is nominated there.  Runs for EVERY attempt, outside the search's
        conditional, as scatters that drop where nothing was found: the
        conditional then hands back a few scalars and not the whole
        carried state.  Returns (nstate', pcarries', live', cleared)."""
        nom, vic_rows = found["nom"], found["vic"]
        any_c = nom >= 0
        chosen = jnp.maximum(nom, 0)
        vact2 = vic_rows >= 0
        d = _victim_deltas(vic_rows, vact2)
        spread_c = pcarries["PodTopologySpread"]
        nstate = nstate._replace(
            requested=_row_add(nstate.requested, nom, -d["req"]),
            nonzero_requested=_row_add(nstate.nonzero_requested, nom, -d["nz"]),
            pod_count=_row_add(nstate.pod_count, nom, -d["cnt"]),
        )
        pcarries = dict(pcarries)
        pcarries["PodTopologySpread"] = _row_add(spread_c, nom, -d["sel"])
        live = dict(live)
        if st.ip_terms:
            live["ip_cnt"] = _row_add(live["ip_cnt"], nom, -d["qm"])
            live["ip_eat"] = _row_add(live["ip_eat"], nom, -d["eat"])
            live["ip_vw"] = _row_add(live["ip_vw"], nom, -d["vw"])
            # The domain view follows the locals (preferred terms score
            # on it): re-derived only where a victim carried a term.
            ip_moved = (
                jnp.any(d["qm"] != 0) | jnp.any(d["eat"] != 0) | jnp.any(d["vw"] != 0)
            )
            ip_prev = pcarries["InterPodAffinity"]
            pcarries["InterPodAffinity"] = jax.lax.cond(
                ip_moved,
                lambda: _derive_interpod(
                    {"cnt": live["ip_cnt"], "eat": live["ip_eat"], "vw": live["ip_vw"]},
                    ipa,
                    st,
                ),
                lambda: ip_prev,
            )
        gone = jnp.any(
            (jnp.arange(P, dtype=jnp.int32)[None, :] == vic_rows[:, None])
            & vact2[:, None],
            axis=0,
        )
        live["alive"] = live["alive"] & ~gone
        live["bound"] = jnp.where(gone, -1, live["bound"])
        # prepareCandidate: nominations of a lower priority onto the node
        # are cleared — on every level below the pod's the node keeps
        # what the pod's own level counts, and nothing else.
        cleared = (
            any_c
            & (live["nom_node"] == nom)
            & (prow["priority"] < prow["priority"][pod.index])
        )
        live["nom_node"] = jnp.where(cleared, -1, live["nom_node"])
        below = jnp.arange(L, dtype=jnp.int32) < lvl
        for key in nm_keys:
            arr = live[key]
            row = arr[chosen]  # [L, ...]
            at_lvl = jax.lax.dynamic_index_in_dim(row, lvl, 0, keepdims=True)
            sel_b = below.reshape((L,) + (1,) * (row.ndim - 1))
            live[key] = _row_set(arr, nom, jnp.where(sel_b, at_lvl, row))
        live = _nom_apply_one(live, nom, pod.index, +1)
        live["nom_node"] = _row_set(
            live["nom_node"], jnp.where(any_c, pod.index, P), nom
        )
        return nstate, pcarries, live, jnp.sum(cleared.astype(jnp.int32)).astype(jnp.int32)

    def _vol_state_summary(s):
        """``vheld`` / ``vhead`` of a step's outputs: the attachments the
        live nodes hold and the smallest limit-less-attached, read from
        the carried state (``NodeVolumeLimits.attach_summary``)."""
        if vol_summary is None:
            return {"vheld": jnp.int32(0), "vhead": jnp.int32(_I32_MAX)}
        carry = {
            leaf: s[f"vc.{vol_summary.name}.{leaf}"]
            for leaf in vol_rows[vol_summary.name]
        }
        got = vol_summary.attach_summary(carry, aux, s["valid"])
        return {
            "vheld": got["attached"].astype(jnp.int32),
            "vhead": got["headroom"].astype(jnp.int32),
        }

    def step(carry, ev_k):
        def run_step(s):
            return _run_step(s, ev_k)

        def skip_step(s):
            z = {
                "sel": jnp.full(st.q, -1, jnp.int32),
                "idx": jnp.full(st.q, P, jnp.int32),
                "scheduled": jnp.zeros((), jnp.int32),
                "unschedulable": jnp.zeros((), jnp.int32),
                "eligible": jnp.zeros((), jnp.int32),
                "slots": jnp.zeros((), jnp.int32),
                "pass_count": s["pass_count"],
                "pending_after": jnp.zeros((), jnp.int32),
            }
            if st.preempt:
                z["nom"] = jnp.full(st.q, -1, jnp.int32)
                z["vic"] = jnp.full((st.q, v_eff), -1, jnp.int32)
                z["clr"] = jnp.zeros(st.q, jnp.int32)
                z["overflow"] = jnp.zeros((), bool)
                z["searches"] = jnp.zeros((), jnp.int32)
                z["cands"] = jnp.zeros((), jnp.int32)
                z["builds"] = jnp.zeros((), jnp.int32)
                z["fruns"] = jnp.zeros((), jnp.int32)
            if st.sample:
                z["walks"] = jnp.zeros((), jnp.int32)
                z["nvis"] = jnp.zeros((), jnp.int32)
                z["nsc"] = jnp.zeros((), jnp.int32)
            if st.volumes:
                z["vatt"] = jnp.zeros((), jnp.int32)
                z["vrej"] = jnp.zeros((), jnp.int32)
                z.update(_vol_state_summary(s))
            if st.record == "full":
                z["bits"] = jnp.zeros((st.q, n_filters, N), bits_dtype)
                z["raw"] = jnp.zeros((st.q, n_scores, N), raw_dtype)
                z["final"] = jnp.zeros((st.q, n_scores, N), final_dtype)
                if st.sample:
                    z["walked"] = jnp.zeros(st.q, bool)
                    z["visited"] = jnp.zeros((st.q, N), bool)
            return s, z

        # Tail-padded (inactive) steps are pure no-ops: same compiled K
        # shape, zero semantic effect.  Scalar-pred cond in a scan is a
        # real XLA conditional, so padding costs nothing at runtime.
        return jax.lax.cond(ev_k["active"], run_step, skip_step, dict(carry))

    def _run_step(s, ev_k):
        s = dict(s)
        s = apply_pod_deletes(s, ev_k["pod_delete"])
        s = apply_node_events(s, ev_k["node_delete"], ev_k["node_create"])
        s["alive"] = (
            s["alive"]
            .at[jnp.where(ev_k["pod_create"] >= 0, ev_k["pod_create"], P)]
            .set(True, mode="drop")
        )
        # flush_backoff (service semantics): existing entries' remaining
        # wait capped at min(attempts-1, FLUSH_CAP) from the pre-pass
        # count.
        has_entry = s["attempts"] > 0
        flushed = jnp.minimum(
            s["retry_at"],
            s["pass_count"] + jnp.minimum(s["attempts"] - 1, flush_cap),
        )
        s["retry_at"] = jnp.where(
            ev_k["flush"] & has_entry, flushed, s["retry_at"]
        )
        any_valid = jnp.any(s["valid"])
        pc = s["pass_count"] + any_valid.astype(jnp.int32)
        s["pass_count"] = pc

        # Queue build: pending, not backed off, in universe (= queue
        # sort) order, first min(eligible, cap) attempted.
        in_backoff = has_entry & (s["retry_at"] >= pc)
        elig = s["alive"] & (s["bound"] < 0) & ~in_backoff
        pos = jnp.cumsum(elig.astype(jnp.int32)) - 1
        att = elig & (pos < min(st.cap, st.q)) & any_valid
        idx_q = (
            jnp.full(st.q, P, jnp.int32)
            .at[jnp.where(att, pos, st.q)]
            .set(jnp.arange(P, dtype=jnp.int32), mode="drop")
        )
        # The attempted pods fill slots 0 .. n_att - 1 and every later
        # slot is invalid: an invalid slot binds nothing (best = -1, so
        # every commit and scatter below drops) and no decoder reads
        # its outputs (idx == P marks it).  So the pod loops run the
        # first n_blocks * blk slots only — the step's attempts rounded
        # up to a whole block — and the compiled queue width costs
        # nothing beyond them.  In the fleet program the bound is
        # pmax-reduced over the lane axis, like `go` below: a batched
        # bound would make vmap batch the `while` predicate and turn
        # every carry update into a select; a lane with fewer attempts
        # runs a few invalid slots instead.
        n_att = jnp.sum(att.astype(jnp.int32)).astype(jnp.int32)
        n_blocks = (n_att + (blk - 1)) // blk
        if st.lane_axis is not None:
            n_blocks = jax.lax.pmax(n_blocks, st.lane_axis)

        def all_invalid(invalid):
            """Stacked outputs of a queue of invalid slots."""
            return jax.tree.map(
                lambda v: jnp.broadcast_to(v, (st.q,) + v.shape), invalid
            )

        def run_slots(slot_body, carry0, xs, invalid):
            """``lax.scan(slot_body, carry0, xs)`` over slots
            0 .. n_blocks * blk - 1; the later rows of the stacked
            outputs hold ``invalid``, what ``slot_body`` yields for an
            invalid slot (per-slot shapes and dtypes)."""

            def block(c):
                b, carry, ys = c
                x_blk = jax.tree.map(
                    lambda a: jax.lax.dynamic_slice_in_dim(a, b * blk, blk), xs
                )
                # Traced once, unrolled whole: no inner loop.
                carry, y_blk = jax.lax.scan(slot_body, carry, x_blk, unroll=True)
                ys = jax.tree.map(
                    lambda buf, y: jax.lax.dynamic_update_slice_in_dim(
                        buf, y, b * blk, 0
                    ),
                    ys,
                    y_blk,
                )
                return b + 1, carry, ys

            _, carry, ys = jax.lax.while_loop(
                lambda c: c[0] < n_blocks,
                block,
                (jnp.int32(0), carry0, all_invalid(invalid)),
            )
            return carry, ys

        clamped = jnp.minimum(idx_q, P - 1)
        pods_q = PodBatch(
            requests=prow["requests"][clamped],
            nonzero_requests=prow["nonzero_requests"][clamped],
            valid=idx_q < P,
            tolerates_unschedulable=prow["tolerates_unschedulable"][clamped],
            has_requests=prow["has_requests"][clamped],
            index=clamped,
        )

        node_state = NodeStateView(
            allocatable=nstat["allocatable"],
            allowed_pods=nstat["allowed_pods"],
            valid=s["valid"],
            unschedulable=nstat["unschedulable"],
            requested=s["requested"],
            nonzero_requested=s["nonzero_requested"],
            pod_count=s["pod_count"],
        )
        carries = prog.init_carries(aux)
        carries["PodTopologySpread"] = s["spread"]
        carries["InterPodAffinity"] = _derive_interpod(
            {"cnt": s["ip_cnt"], "eat": s["ip_eat"], "vw": s["ip_vw"]}, ipa, st
        )
        for pn, rows in vol_rows.items():
            # The window's own counts, not the encoder's start.
            carries[pn] = {leaf: s[f"vc.{pn}.{leaf}"] for leaf in rows}
        rank = ev_k["rank"]  # i32 [N], canonical slot, big when dead

        def select_host(ok, total, valid):
            """selectHost with the canonical-slot tie-break: max summed
            score, minimal rank — the node the per-pass argmax (lowest
            slot index) picks; -1 where nothing is feasible."""
            feasible = jnp.any(ok)
            masked = jnp.where(ok, total, _I32_MIN)
            cand = ok & (masked == jnp.max(masked))
            best = jnp.argmin(jnp.where(cand, rank, _I32_MAX)).astype(jnp.int32)
            return jnp.where(feasible & valid, best, -1)

        if st.sample:
            # percentageOfNodesToScore: the step's numFeasibleNodesToFind
            # (0: this many nodes are not sampled) and the visit order.
            # In slot order the walk never sorts: ``sample_visited``
            # counts feasible nodes by prefix sum, with k and the start
            # index as operands.
            n_live = jnp.sum(s["valid"], dtype=jnp.int32)
            k_step = ev_k["sample_k"]
            sampling = k_step > 0
            k_walk = jnp.where(sampling, k_step, _I32_MAX)

        def sample_walk(ok, start, go):
            """One attempt's walk for a sample (``st.sample``): what is
            scored, the start index it leaves and what it counted.
            ``go`` False (a padding slot, a pod that takes its nominated
            node) walks nowhere: ``ok`` stands and the index stays."""
            if st.sample == 2:
                # Visit order is the service's node tree (the step's walk
                # tensor: each slot's place in the tree's list, big when
                # dead), not this table's slot order.
                visited, sample, nxt = sample_visited_at(
                    ok, s["valid"], ev_k["walk"], start, n_live, k_walk
                )
            else:
                visited, sample, nxt = sample_visited(
                    ok, s["valid"], start, n_live, k_walk
                )
            walked = go & sampling
            out = {
                "walked": walked,
                "nvis": jnp.where(walked, jnp.sum(visited, dtype=jnp.int32), 0),
                "nsc": jnp.where(walked, jnp.sum(sample, dtype=jnp.int32), 0),
            }
            if st.record == "full":
                out["visited"] = visited
            if st.volumes:
                out["seen"] = jnp.where(walked, visited, s["valid"])
            return jnp.where(go, sample, ok), jnp.where(go, nxt, start), out

        def record_rows(_bits, _raw, _final):
            return {
                "bits": (
                    jnp.stack(_bits) if _bits else jnp.zeros((0, N), jnp.int32)
                ).astype(bits_dtype),
                "raw": (
                    jnp.stack(_raw) if _raw else jnp.zeros((0, N), jnp.int32)
                ).astype(raw_dtype),
                "final": (
                    jnp.stack(_final) if _final else jnp.zeros((0, N), jnp.int32)
                ).astype(final_dtype),
            }

        def pod_body(pcarry, pb):
            nstate, pcarries, *walk = pcarry
            from ksim_tpu.plugins.base import PodView

            pod = PodView(
                requests=pb.requests,
                nonzero_requests=pb.nonzero_requests,
                tolerates_unschedulable=pb.tolerates_unschedulable,
                has_requests=pb.has_requests,
                index=pb.index,
            )
            seen = nstate.valid
            if st.sample:
                # Filter everywhere (the mask FINDS the sample), then
                # score, normalise and choose over the sample only.
                ok, _bits = prog._eval_filters(nstate, pod, aux, pcarries)
                ok, start, walk_out = sample_walk(ok, walk[0], pb.valid)
                walk = [start]
                seen = walk_out.pop("seen", seen)
                _raw, _final, total = prog._eval_scores(
                    nstate, pod, aux, pcarries, ok
                )
            else:
                ok, _bits, _raw, _final, total = prog._eval_one(
                    nstate, pod, aux, pcarries
                )
            best = select_host(ok, total, pb.valid)
            nstate = nstate.commit(best, pb.requests, pb.nonzero_requests)
            pcarries = prog._commit_carries(pcarries, pod, best, aux)
            out_pod = {"best": best}
            if st.record == "full":
                out_pod.update(record_rows(_bits, _raw, _final))
            if st.sample:
                out_pod.update(walk_out)
            if st.volumes:
                # An attempt of a pod with a plugin-read volume, and the
                # nodes it visited that a volume filter turned down.
                turned = jnp.zeros(N, bool)
                for i in vol_filters:
                    turned = turned | (_bits[i] != 0)
                out_pod["vatt"] = pb.valid & prow["vol_reads"][pb.index]
                out_pod["vrej"] = jnp.where(
                    pb.valid, jnp.sum(turned & seen, dtype=jnp.int32), 0
                )
            return (nstate, pcarries, *walk), out_pod

        invalid_search = {
            "nom": jnp.int32(-1),
            "vic": jnp.full(v_eff, -1, jnp.int32),
            "over": jnp.zeros((), bool),
            "cands": jnp.int32(0),
            "runs": jnp.int32(0),
        }

        def pod_body_preempt(pcarry, pb):
            """One attempt of a window with the victim search on: the
            pod is evaluated against the state its predecessors of this
            pass left (binds, victims gone, nominations), binds or
            searches, and hands the state on — the victim table of the
            pass with it (``_lower_table``)."""
            nstate, pcarries, live, table, *walk = pcarry
            from ksim_tpu.plugins.base import PodView

            pod = PodView(
                requests=pb.requests,
                nonzero_requests=pb.nonzero_requests,
                tolerates_unschedulable=pb.tolerates_unschedulable,
                has_requests=pb.has_requests,
                index=pb.index,
            )
            j = pb.index
            lvl = prow["level"][j]
            # The pod's own nomination is off the books from here on: it
            # does not count against itself, and the attempt ends it
            # either way (a bind, a new nomination, or giving it up).
            n0 = jnp.where(pb.valid, live["nom_node"][j], -1)
            live = _nom_apply_one(live, n0, j, -1)
            live["nom_node"] = _row_set(live["nom_node"], jnp.where(n0 >= 0, j, P), -1)
            # RunFilterPluginsWithNominatedPods: a node has to pass as it
            # stands and with the nominated pods of this priority or
            # above counted in.
            over = _nom_at(live, lvl)
            if st.local and st.record == "selection":
                # Node-local window: the run with the nominees counted in
                # decides both (``_preempt_search`` has the argument and
                # the gate it rests on); nothing reads the reasons.
                ok, _bits = _filters_with(nstate, pcarries, live, pod, over), None
                runs = jnp.int32(1)
            else:
                # The as-it-stands run gives the recorded reasons (full
                # record: the search reads them for ``resolvable``); the
                # other is skipped where nobody is nominated.
                ok, _bits = prog._eval_filters(nstate, pod, aux, pcarries)
                has_nom = jnp.any(over["nm_cnt"] > 0)
                ok = ok & _lane_cond(
                    has_nom,
                    lambda: _filters_with(nstate, pcarries, live, pod, over),
                    lambda: jnp.ones(N, bool),
                )
                runs = 1 + has_nom.astype(jnp.int32)
            # evaluateNominatedNode: the nominated node, if it passes,
            # is the whole feasible set.
            own = (n0 >= 0) & ok[jnp.clip(n0, 0, N - 1)]
            ok = jnp.where(own, ok & (jnp.arange(N) == n0), ok)
            if st.sample:
                # findNodesThatPassFilters comes after the nominated
                # node: a pod that takes it walks nowhere.
                ok, start, walk_out = sample_walk(ok, walk[0], pb.valid & ~own)
                walk = [start]
            _raw, _final, total = prog._eval_scores(nstate, pod, aux, pcarries, ok)
            best = select_host(ok, total, pb.valid)
            nstate = nstate.commit(best, pb.requests, pb.nonzero_requests)
            pcarries = prog._commit_carries(pcarries, pod, best, aux)
            if st.ip_terms:
                live["ip_cnt"] = _row_add(live["ip_cnt"], best, qm_rows[j])
                live["ip_eat"] = _row_add(live["ip_eat"], best, eat_rows[j])
                live["ip_vw"] = _row_add(live["ip_vw"], best, vw_rows[j])
            live["bound"] = _row_set(live["bound"], jnp.where(best >= 0, j, P), best)
            out_pod = {"best": best}
            if st.record == "full":
                out_pod.update(record_rows(_bits, _raw, _final))
            lower = (
                live["alive"]
                & (live["bound"] >= 0)
                & (prow["priority"] < prow["priority"][j])
            )
            failed = pb.valid & (best < 0)
            pred = failed & prow["preempt_ok"][j] & jnp.any(lower)
            bits_mat = out_pod["bits"] if st.record == "full" and n_filters else None

            # The table is built where a search finds it stale: at the
            # pass's first search, and where the level moves.
            build = pred & (table["lvl"] != lvl)
            table = _lane_cond(
                build,
                lambda: dict(_lower_table(live, lower), lvl=lvl),
                lambda: table,
            )
            found = _lane_cond(
                pred,
                lambda: _preempt_search(
                    s, nstate, pcarries, live, pod, lvl, bits_mat, ev_k, table
                ),
                lambda: dict(invalid_search),
            )
            nstate, pcarries, live, cleared = _apply_preemption(
                nstate, pcarries, live, pod, lvl, found
            )
            table = _repair_table(table, found)
            # -2: the attempt failed, preemption did not help, and the
            # nomination the pod came with is given up.
            out_pod["nom"] = jnp.where(
                failed & (found["nom"] < 0) & (n0 >= 0), -2, found["nom"]
            ).astype(jnp.int32)
            out_pod["vic"] = found["vic"]
            out_pod["over"] = found["over"]
            out_pod["cands"] = found["cands"]
            # (A fleet lane counts its own: not the padding slots and the
            # idle trips it shares with a longer lane.)
            out_pod["runs"] = jnp.where(pb.valid, runs, 0) + found["runs"]
            out_pod["clr"] = cleared
            out_pod["searched"] = pred
            out_pod["built"] = build
            if st.sample:
                out_pod.update(walk_out)
            return (nstate, pcarries, live, table, *walk), out_pod

        invalid_pod = {"best": jnp.int32(-1)}
        if st.record == "full":
            invalid_pod["bits"] = jnp.zeros((n_filters, N), bits_dtype)
            invalid_pod["raw"] = jnp.zeros((n_scores, N), raw_dtype)
            invalid_pod["final"] = jnp.zeros((n_scores, N), final_dtype)
        walk0 = ()
        if st.volumes:
            invalid_pod.update(vatt=jnp.zeros((), bool), vrej=jnp.int32(0))
        if st.sample:
            invalid_pod.update(
                walked=jnp.zeros((), bool), nvis=jnp.int32(0), nsc=jnp.int32(0)
            )
            if st.record == "full":
                invalid_pod["visited"] = jnp.zeros(N, bool)
            # The start index rides the pod loop's carry.
            walk0 = (s["sample_start"].reshape(()).astype(jnp.int32),)
        if st.preempt:
            invalid_pod.update(
                invalid_search,
                clr=jnp.int32(0),
                searched=jnp.zeros((), bool),
                built=jnp.zeros((), bool),
            )
            live0 = {
                k: s[k]
                for k in ("alive", "bound", "ip_cnt", "ip_eat", "ip_vw", "nom_node")
                + nm_keys
            }
            # A pass starts with no victim table (level -1: events,
            # deletes and the last pass's binds have moved ``live``).
            table0 = {
                "cnt": jnp.zeros(N, jnp.int32),
                "vrow": jnp.zeros((v_eff, N), jnp.int32),
                "vact": jnp.zeros((v_eff, N), bool),
                "facts": tuple(
                    jnp.zeros((v_eff, N), col.dtype) for col in pod_facts
                ),
                "lvl": jnp.int32(-1),
            }
            (node_state, carries, live, _table, *walk_end), pod_outs = run_slots(
                pod_body_preempt,
                (node_state, carries, live0, table0, *walk0),
                pods_q,
                invalid_pod,
            )
        else:
            (node_state, carries, *walk_end), pod_outs = run_slots(
                pod_body, (node_state, carries, *walk0), pods_q, invalid_pod
            )
        if st.sample:
            s["sample_start"] = walk_end[0].reshape(s["sample_start"].shape)
        sel = pod_outs["best"]
        bound_mask = (idx_q < P) & (sel >= 0)
        fail_mask = (idx_q < P) & (sel < 0)
        s["requested"] = node_state.requested
        s["nonzero_requested"] = node_state.nonzero_requested
        s["pod_count"] = node_state.pod_count
        # The committed spread carry is node-local — carry it forward.
        s["spread"] = carries["PodTopologySpread"]
        for pn, rows in vol_rows.items():
            for leaf in rows:
                s[f"vc.{pn}.{leaf}"] = carries[pn][leaf]
        if st.preempt:
            # live already holds binds, victim removals and nominations:
            # it IS the post-step state.
            for k in live:
                s[k] = live[k]
            nomd = s["nom_node"][clamped] >= 0
        else:
            bind_node = jnp.where(bound_mask, sel, N)
            s["ip_cnt"] = s["ip_cnt"].at[bind_node].add(
                qm_rows[clamped].astype(s["ip_cnt"].dtype), mode="drop"
            )
            s["ip_eat"] = s["ip_eat"].at[bind_node].add(eat_rows[clamped], mode="drop")
            s["ip_vw"] = s["ip_vw"].at[bind_node].add(vw_rows[clamped], mode="drop")
            s["bound"] = s["bound"].at[jnp.where(bound_mask, idx_q, P)].set(
                sel, mode="drop"
            )
            s["nominated"] = (
                s["nominated"]
                .at[jnp.where(bound_mask, idx_q, P)]
                .set(False, mode="drop")
            )
            nomd = s["nominated"][clamped]
        # Backoff bookkeeping (_record_attempts): success pops the entry,
        # failure doubles the delay (capped) — UNLESS the pod holds a
        # nomination (from this pass or an earlier one): a nominated pod
        # expects to schedule as soon as its victims are gone, so the
        # per-pass path pops its entry instead of backing it off.
        a_prev = s["attempts"][clamped]
        delay = jnp.minimum(1 << jnp.minimum(a_prev, shift_cap), max_backoff)
        succ_idx = jnp.where(bound_mask, idx_q, P)
        pop_idx = jnp.where(fail_mask & nomd, idx_q, P)
        inc_idx = jnp.where(fail_mask & ~nomd, idx_q, P)
        s["attempts"] = (
            s["attempts"]
            .at[succ_idx].set(0, mode="drop")
            .at[pop_idx].set(0, mode="drop")
            .at[inc_idx].set(a_prev + 1, mode="drop")
        )
        s["retry_at"] = (
            s["retry_at"]
            .at[succ_idx].set(0, mode="drop")
            .at[pop_idx].set(0, mode="drop")
            .at[inc_idx].set(pc + delay, mode="drop")
        )
        out = {
            "sel": sel,
            "idx": idx_q,
            # astype pins the cond-branch dtype (x64 mode promotes sums
            # of i32 to i64, and the inactive skip branch emits i32).
            "scheduled": jnp.sum(bound_mask.astype(jnp.int32)).astype(jnp.int32),
            "unschedulable": jnp.sum(fail_mask.astype(jnp.int32)).astype(jnp.int32),
            # Zero when the pass never ran (no valid nodes: the per-pass
            # path returns before even building the queue) — this is what
            # the featurize-schedule validation and slot advancing key on.
            "eligible": jnp.where(
                any_valid, jnp.sum(elig.astype(jnp.int32)), 0
            ).astype(jnp.int32),
            # Queue slots the pod loops ran (replay.queue_slots_run).
            "slots": n_blocks * blk,
            "pass_count": pc,
            "pending_after": jnp.sum(
                (s["alive"] & (s["bound"] < 0)).astype(jnp.int32)
            ).astype(jnp.int32),
        }
        if st.preempt:
            out["nom"] = pod_outs["nom"]
            out["vic"] = pod_outs["vic"]
            out["clr"] = pod_outs["clr"]
            out["overflow"] = jnp.any(pod_outs["over"])
            out["searches"] = jnp.sum(pod_outs["searched"].astype(jnp.int32)).astype(jnp.int32)
            out["cands"] = jnp.sum(pod_outs["cands"]).astype(jnp.int32)
            out["builds"] = jnp.sum(pod_outs["built"].astype(jnp.int32)).astype(jnp.int32)
            # Whole-chain filter evaluations the pod loop ran, attempts
            # and searches (replay.preempt_filter_runs).
            out["fruns"] = jnp.sum(pod_outs["runs"]).astype(jnp.int32)
        if st.volumes:
            out["vatt"] = jnp.sum(pod_outs["vatt"], dtype=jnp.int32)
            out["vrej"] = jnp.sum(pod_outs["vrej"], dtype=jnp.int32)
            out.update(_vol_state_summary(s))
        if st.sample:
            # Summed here, on the device, and pulled with the outputs.
            out["walks"] = jnp.sum(pod_outs["walked"], dtype=jnp.int32)
            out["nvis"] = jnp.sum(pod_outs["nvis"], dtype=jnp.int32)
            out["nsc"] = jnp.sum(pod_outs["nsc"], dtype=jnp.int32)
        if st.record == "full":
            out["bits"] = pod_outs["bits"]
            out["raw"] = pod_outs["raw"]
            out["final"] = pod_outs["final"]
            if st.sample:
                out["walked"] = pod_outs["walked"]
                out["visited"] = pod_outs["visited"]
        return s, out

    final_state, outs = jax.lax.scan(step, dict(state0), ev)
    return final_state, outs


#: Donation (round 19): argument 4 is the carried cluster state.  Both
#: executors transfer it FRESH every dispatch, so donating it can never
#: hand XLA a buffer a later dispatch still needs, and the output carry
#: reuses the input's allocation instead of holding two copies of
#: ``[N]``/``[N, R]`` cluster state per chip (SNIPPETS.md scan-carry
#: donation idiom; the fleet's dominant per-lane footprint).
#:
#: MESH dispatches never donate (the ``_nodonate`` twins below): on the
#: forced-8-virtual-device CPU backend, donating the carry of a
#: multi-device (dp, tp) program made replay diverge from the store
#: NONDETERMINISTICALLY at 1200-event fleet scale (ReplayParityError
#: with the device view AHEAD of the store, or silently wrong counts)
#: while any host-sync instrumentation made it pass — a timing race in
#: input-output aliasing across the virtual devices, not a logic bug:
#: the same program is byte-stable donation-off (repeated-trial
#: bisection, round 19) and single-device donation is locked by
#: tests/test_replay_device.py.  Virtual CPU devices share one host
#: allocator, so per-device "exclusive" donated buffers can alias in
#: ways real per-chip HBM cannot; re-evaluate on silicon before
#: donating mesh carries.
_DONATE_ARGNUMS = (4,)


@partial(jax.jit, static_argnums=(0, 1), donate_argnums=_DONATE_ARGNUMS)
@device_kernel(static=("st", "prog"))
def _segment_fn(st: _SegmentStatics, prog, const: dict, ev: dict, state0: dict):
    """Solo replay program: ``_segment_body`` jitted with the carry
    donated (see ``_DONATE_ARGNUMS``).  The jit boundary lives on this
    thin wrapper — not on the body — so the fleet program can vmap the
    UNJITTED body: donation must be declared on the outermost jit, and
    a jit-inside-vmap would re-trace per lane."""
    return _segment_body(st, prog, const, ev, state0)


@partial(jax.jit, static_argnums=(0, 1))
@device_kernel(static=("st", "prog"))
def _segment_fn_nodonate(
    st: _SegmentStatics, prog, const: dict, ev: dict, state0: dict
):
    """``_segment_fn`` without carry donation — the MESH twin.  Sharded
    (tp > 1) dispatches route here: see the ``_DONATE_ARGNUMS`` note for
    the virtual-device aliasing race that forbids donating multi-device
    carries on this backend."""
    return _segment_body(st, prog, const, ev, state0)


def _fleet_segment_impl(
    st: _SegmentStatics, prog, const: dict, ev: dict, state0: dict
):
    """Shared traced body of the fleet program (see ``_fleet_segment_fn``
    for the semantics); the donating and non-donating jit twins both
    wrap this so the vmap/lane-axis structure is written once."""
    import dataclasses

    import jax

    lane_st = dataclasses.replace(st, lane_axis="lane")
    return jax.vmap(
        lambda s: _segment_body(lane_st, prog, const, ev, s),
        axis_name="lane",
    )(state0)


@partial(jax.jit, static_argnums=(0, 1), donate_argnums=_DONATE_ARGNUMS)
@device_kernel(static=("st", "prog"))
def _fleet_segment_fn(st: _SegmentStatics, prog, const: dict, ev: dict, state0: dict):
    """Fleet replay: advance S INDEPENDENT trajectories by K steps in one
    dispatch — ``_segment_body`` vmapped over a leading lane axis on the
    carried cluster state (``state0``), with the lane axis NAMED: the
    statics gain ``lane_axis="lane"`` so the preemption-search gate can
    psum its trigger over lanes and keep a lane-uniform (unbatched)
    ``lax.cond`` predicate — the round-19 select-bomb fix.

    ``const`` AND ``ev`` are closed over, i.e. broadcast across lanes:
    the fleet's contract is that every grouped lane shares ONE lowered
    plan (engine/fleet.py lowers it once via the cohort leader), so the
    universe tables and the per-step event streams are lane-identical by
    construction.  Keeping ``ev`` unbatched is load-bearing, not just a
    transfer saving: the per-step inactive-tail ``lax.cond`` predicates
    on ``ev['active']``, and under vmap a cond with a BATCHED predicate
    lowers to select — both branches execute — while an unbatched
    predicate keeps the real conditional, so tail padding stays free in
    the batched program exactly as it is solo.  (The same select
    semantics are why priority-flat windows lower preempt-free —
    ``_lower``'s ``preempt_plan`` screen.)  Per-lane event DELTAS are
    the ROADMAP's fleet round 2; they will stack ``ev`` and re-split
    this axis handling.

    The kernels are RNG-free and every per-lane reduction runs over the
    same axes in the same order as the solo program, so each lane's
    slice of the outputs is byte-identical to its solo ``_segment_fn``
    dispatch — the fleet parity lock (tests/test_replay_device.py,
    `make lock-check`)."""
    return _fleet_segment_impl(st, prog, const, ev, state0)


@partial(jax.jit, static_argnums=(0, 1))
@device_kernel(static=("st", "prog"))
def _fleet_segment_fn_nodonate(
    st: _SegmentStatics, prog, const: dict, ev: dict, state0: dict
):
    """``_fleet_segment_fn`` without carry donation — the MESH twin.
    Fleet dispatches on a (dp, tp) mesh route here: donating a
    multi-device carry raced on the virtual CPU backend (see the
    ``_DONATE_ARGNUMS`` note); single-device fleet packs keep the
    donating twin."""
    return _fleet_segment_impl(st, prog, const, ev, state0)


# ---------------------------------------------------------------------------
# Host driver: segment lowering, dispatch, reconcile
# ---------------------------------------------------------------------------


@dataclass
class AttemptOutcome:
    """One scheduling attempt within a device step, in commit order —
    everything the reconcile needs to mirror the per-pass path's store
    writes for that pod: the bind (or nomination), the preemption
    victims to evict right after the pod's own write, and the fully
    rendered record="full" result annotations."""

    namespace: str
    name: str
    node: str | None  # bound node (None = unschedulable this pass)
    nominated: str | None  # newly nominated node (preemption)
    victims: list[tuple[str, str]]  # (namespace, name) in reprieve order
    anno: dict | None  # record="full" annotations (None in selection)
    # The attempt failed, preemption did not help, and the pod gives up
    # the nomination it came with.
    gave_up: bool = False
    # Pods of a lower priority nominated to ``nominated`` lose their
    # nomination (how many; the reconcile finds them in the store).
    cleared: int = 0


@dataclass
class StepOutcome:
    """One device-computed scheduling pass, ready for store reconcile."""

    scheduled: int
    unschedulable: int
    pending_after: int
    eligible: int  # queue size before the cap (0 = the pass never featurized)
    slots_run: int  # queue slots the device's pod loops ran for this step
    searches: int = 0  # victim searches the step ran on the device
    candidates: int = 0  # candidates those searches kept, summed
    table_builds: int = 0  # victim tables the step built (once a pass and level)
    filter_runs: int = 0  # whole-chain filter evaluations of a preempt window's pod loop
    # percentageOfNodesToScore: attempts that walked for a sample, the
    # nodes they visited and the nodes they scored, summed on the device.
    sampled: int = 0
    visited: int = 0
    scored: int = 0
    # Of ``sampled``, the attempts whose walk went by the walk tensor
    # (``_SegmentStatics.sample`` 2: one sort of the node axis each).
    by_rank: int = 0
    # Volumes (``_SegmentStatics.volumes``): attempts of a pod with a
    # plugin-read volume, visited nodes a volume filter turned down, and
    # from the state the step leaves: attachments held and the smallest
    # limit-less-attached (None: nothing limited).
    vol_attempts: int = 0
    vol_rejections: int = 0
    vol_attached: "int | None" = None
    vol_headroom: "int | None" = None
    # (namespace, name, node_name) in queue (commit) order.
    binds: list[tuple[str, str, str]] = field(default_factory=list)
    # Per-attempt detail (preemption / full-record segments); None means
    # the binds list is the whole story (pure selection mode).
    attempts: "list[AttemptOutcome] | None" = None


@dataclass
class SegmentOutcome:
    steps: list[StepOutcome]
    pass_count: int
    # namespace/name -> (attempts, retry_at) for the service backoff sync.
    backoff: dict[str, tuple[int, int]]
    # Device end-of-segment views for the store parity check.
    bound_view: dict[str, str]  # pod key -> node name
    pending_view: set[str]  # pod keys
    # pod key -> the node it is nominated to (None: the segment's
    # program carries no nominations).
    nominated_view: "dict[str, str] | None" = None
    # The sampling walk's start index as the segment leaves it (None: the
    # segment's program does not sample).
    sample_start: "int | None" = None
    # The service's node tree as the segment's node events leave it
    # (None: the service does not sample).
    node_tree: Any = None


def _headroom(raw) -> "int | None":
    """A step's ``vhead`` output: int32 max says nothing is limited."""
    return None if int(raw) >= _I32_MAX else int(raw)


def _cleaned_pending(pod: JSON) -> JSON:
    """The pod as the per-pass path would featurize it when PENDING
    (node-drain requeue shape: spec.nodeName and status.phase cleared) —
    identity-cached per source object so the featurizer's per-pod memo
    rows survive across segments."""
    from ksim_tpu.state import objcache

    def build() -> JSON:
        spec = dict(pod.get("spec") or {})
        spec.pop("nodeName", None)
        status = dict(pod.get("status") or {})
        status.pop("phase", None)
        return dict(pod, spec=spec, status=status)

    if not pod.get("spec", {}).get("nodeName") and not pod.get("status", {}).get(
        "phase"
    ):
        return pod
    return objcache.cached("replay_clean", pod, build)


class ReplayDriver:
    """Segment-batched device replay over a ClusterStore + SchedulerService.

    One instance per ScenarioRunner run.  ``try_segment`` lowers K steps
    against the CURRENT store state and runs them in a single dispatch;
    ``None`` means the segment is outside the supported vocabulary and
    the caller must fall back to the per-pass path for those steps."""

    def __init__(
        self,
        store,
        service,
        *,
        k: int = SEGMENT_STEPS,
        requeue_on_node_delete: bool = True,
        lane: "int | None" = None,
        lane_faults=None,
        ingest_hook=None,
    ) -> None:
        self.store = store
        self.service = service
        self.k = max(int(k), 1)
        # Fleet-lane identity (engine/fleet.py): stamped on every span
        # and fallback event this driver emits so a Chrome trace from an
        # S-lane run stays attributable, and the lane's PRIVATE fault
        # plane (parsed from the per-lane KSIM_FLEET_FAULTS spec) checked
        # next to the process-global FAULTS at the replay sites — a lane
        # fault degrades only this lane.
        self.lane = lane
        self._lane_faults = lane_faults
        self._span_tags = {} if lane is None else {"lane": lane}
        # The segment program bakes the runner's drain-requeue semantics
        # in; a no-requeue runner must take the per-pass path for any
        # segment containing node deletes.
        self._requeue = requeue_on_node_delete
        self._featurizer = None  # persistent device-side featurizer
        self._sched_name: str | None = None
        self._record_mode = "selection"  # set by service_supported
        self._preempt_active = False  # set by service_supported
        # record="full" segments run at a shorter fixed K (their stacked
        # result tensors multiply device memory by K).
        self._full_k = max(1, min(self.k, FULL_SEGMENT_STEPS))
        # Evidence counters (``stats()`` reports them).  guarded-by:
        # main-thread — the driver's mutable state is thread-confined:
        # only the main thread writes it; the watchdogged dispatch
        # worker (``_run``, annotated worker-thread below) must stay
        # side-effect-free on the driver so an abandoned late-finishing
        # worker can never corrupt the degraded run's accounting.
        # tools/ksimlint's lock-discipline rule enforces the write side.
        self.device_steps = 0  # guarded-by: main-thread
        self.fallback_steps = 0  # guarded-by: main-thread
        self.device_round_trips = 0  # guarded-by: main-thread
        # Summed replay.exec walls (launch until outputs ready) of the
        # healthy dispatches: an upper bound of this run's device time.
        self.device_wait_s = 0.0  # guarded-by: main-thread
        # Shape evidence of the healthy dispatches (PR 27): the widest
        # compiled queue bucket, the tail-padded step slots (compiled K
        # less the real steps, summed), and — counted at commit — the
        # pod x node pairs the committed passes evaluated (attempts
        # times live nodes: the north star's unit).
        self.queue_width_max = 0  # guarded-by: main-thread
        self.steps_padded = 0  # guarded-by: main-thread
        self.pairs_evaluated = 0  # guarded-by: main-thread
        self.queue_slots_run = 0  # guarded-by: main-thread
        # The on-device victim search, over the committed segments:
        # searches run, victim tables built for them (one a pass and
        # priority level: near the searches = the carried table is not
        # engaging), whole-chain filter evaluations the pod loops of the
        # windows that search ran (attempts and searches: one a state in
        # a node-local window, upstream's two elsewhere), candidates the
        # searches kept, victims evicted, nominations made; and the
        # segments discarded for VMAX.
        self.preempt_searches = 0  # guarded-by: main-thread
        self.preempt_table_builds = 0  # guarded-by: main-thread
        self.preempt_filter_runs = 0  # guarded-by: main-thread
        self.preempt_candidates = 0  # guarded-by: main-thread
        self.preempt_victims = 0  # guarded-by: main-thread
        self.preempt_nominations = 0  # guarded-by: main-thread
        self.preempt_overflows = 0  # guarded-by: main-thread
        # percentageOfNodesToScore on the device, over the committed
        # segments: attempts that walked for a sample, the nodes they
        # visited and the nodes they scored — the device's own sums.
        self.sampled_attempts = 0  # guarded-by: main-thread
        self.nodes_visited = 0  # guarded-by: main-thread
        self.nodes_scored = 0  # guarded-by: main-thread
        self.sampled_by_rank = 0  # guarded-by: main-thread
        # What the walk tensor took: steps of the committed segments
        # whose walks went by it (the device's ``outs["walks"]``), and
        # walk rows the lowerings computed (``NodeTree.positions`` calls:
        # one a window and one more a step with a node event).
        self.sampled_by_rank_steps = 0  # guarded-by: main-thread
        self.walk_rows_built = 0  # guarded-by: main-thread
        # Volumes (PR 49).  Summed on the device over the committed
        # steps: attempts of a pod with a plugin-read volume, and node
        # verdicts a volume filter turned down among the nodes those
        # attempts visited.  From the last committed step's carried
        # state: attachments held, and the smallest limit-less-attached
        # (None: nothing limited, or no window carried volume state).
        # From the lowerings, the largest any window read or built:
        # volume objects, distinct PV rows, columns for shared volumes.
        self.volume_attempts = 0  # guarded-by: main-thread
        self.volume_rejections = 0  # guarded-by: main-thread
        self.volume_attached = 0  # guarded-by: main-thread
        self.volume_headroom_min: "int | None" = None  # guarded-by: main-thread
        self.volume_objects = 0  # guarded-by: main-thread
        self.volume_classes = 0  # guarded-by: main-thread
        self.volume_shared = 0  # guarded-by: main-thread
        # Whole-node-axis tables the featurizer's encoders built afresh
        # (``Memo.seq_builds``), summed over the lowerings: about one a
        # family in a cold call, none while the node objects stay.
        self.featurize_node_builds = 0  # guarded-by: main-thread
        # Bound-pod records of the featurizer's additive families,
        # summed over the lowerings: those that RAN a contribution
        # builder (``boundagg.records_built``: one a distinct manifest a
        # family) and those that took their content's contribution from
        # the family's table (``records_shared``).  Together: families x
        # bound pods in a job's first call, then families x the pods
        # bound since; a multiple of the first = a family walked the
        # whole bound population again.
        self.featurize_bound_records = 0  # guarded-by: main-thread
        self.featurize_bound_shared = 0  # guarded-by: main-thread
        # ``marshal.dumps`` calls made for a content key, the cold
        # universe walks' and the featurizer's two tables' together: one
        # a store pod of a cold lowering and one a created pod; two a
        # store pod = the hand-over is not engaging (a cleaned copy
        # whose ``status`` is not empty, a copy that is not the memoised
        # one).  And the support screens the cold walks ran: one a
        # distinct manifest, plus the pods without a key.
        self.content_keys_built = 0  # guarded-by: main-thread
        self.universe_screens = 0  # guarded-by: main-thread
        # Store writes of the committed segments' reconciles that
        # replaced an object that was there: by a shallow re-wrap that
        # shares the frozen manifest (every placement, nomination and
        # requeue) and through the deep-copying ``patch`` (thousands =
        # placements have gone back through it).
        self.reconcile_writes_shared = 0  # guarded-by: main-thread
        self.reconcile_writes_copied = 0  # guarded-by: main-thread
        # Streaming ingest overlap (round 22, traces/stream.py): a
        # runner-provided NONBLOCKING drain of the trace-ingest queue,
        # called on the main thread while the dispatch worker owns the
        # device — the third stage of the ingest ∥ prelower ∥ dispatch
        # pipeline.  None for materialized runs.
        self._ingest_hook = ingest_hook
        self.ingest_prefetches = 0  # guarded-by: main-thread
        self.unsupported: dict[str, int] = {}  # guarded-by: main-thread
        # Failure-containment state — PER DRIVER, never process-global
        # (two runners in one process must not trip each other's
        # breaker).  ``stats()`` surfaces all of it.
        self.watchdog_s = _watchdog_seconds()
        self.breaker_threshold = max(_breaker_threshold(), 1)
        self.device_errors = 0  # guarded-by: main-thread (degraded dispatches)
        self.watchdog_timeouts = 0  # guarded-by: main-thread (subset of above)
        # Sticky with the default cooldown of 0; with
        # KSIM_REPLAY_BREAKER_COOLDOWN_S > 0 the half-open machinery
        # below may close it again after a healthy probe segment.
        self.breaker_tripped = False  # guarded-by: main-thread
        self._consecutive_device_errors = 0  # guarded-by: main-thread
        self._consecutive_reconcile_faults = 0  # guarded-by: main-thread
        # Half-open recovery state (round 15, _breaker_cooldown_s).
        self.breaker_cooldown_s = max(_breaker_cooldown_s(), 0.0)
        self._breaker_cooldown_cur = self.breaker_cooldown_s  # guarded-by: main-thread
        self._breaker_retry_at: "float | None" = None  # guarded-by: main-thread
        self._breaker_probe = False  # guarded-by: main-thread
        self.breaker_probes = 0  # guarded-by: main-thread
        self.breaker_closes = 0  # guarded-by: main-thread
        self.breaker_reopens = 0  # guarded-by: main-thread
        # Segment sequence number (trace-span correlation id: every
        # lower/dispatch/reconcile span of one window shares it).
        self._segment_seq = 0
        # Incremental-lowering state (docs/churn_floor.md round 10): the
        # persistent lowered-universe cache, the speculative next-window
        # spec from the double-buffered executor and the committed plan
        # the cache advances from.
        self._cache = _LowerCache()  # guarded-by: main-thread
        self._spec: "tuple[tuple[int, ...], _WindowSpec] | None" = None  # guarded-by: main-thread
        self._last_plan: "_SegmentPlan | None" = None  # guarded-by: main-thread
        # Sharded replay (round 17): the requested node-mesh width.  An
        # explicit service shard_mesh (validated in service_supported)
        # wins over the env knob.  Fleet lanes honor the knob too since
        # round 19: the group dispatch lays the lane axis over dp and
        # the node axis over tp of its own (dp, tp) fleet mesh, so a
        # lane's tp declaration composes with KSIM_FLEET_DP instead of
        # being forced to 1 (the round-17 whole-lane-per-device rule).
        self._tp_env = _replay_tp()
        self._tp_req = self._tp_env  # guarded-by: main-thread
        self._shard_mesh_obj: Any = None  # guarded-by: main-thread
        self._prio_gen = 0
        # Pipeline / O(delta) evidence counters (job result, lock-check
        # guard).  ``lower_log`` records one entry per successful lower:
        # the window's event count vs the fresh per-pod featurize rows it
        # actually built — the counter-based O(delta) guard's input.
        self.prelower_windows = 0  # guarded-by: main-thread
        self.prelower_consumed = 0  # guarded-by: main-thread
        self.prelower_discarded = 0  # guarded-by: main-thread
        self.prelower_faults = 0  # guarded-by: main-thread
        self.lower_log: list[dict] = []  # guarded-by: main-thread
        # Last _reject reason — the fleet coordinator mirrors a shared
        # (cohort-leader) rejection onto every follower lane's histogram
        # so per-lane evidence matches what each solo run would record.
        self._last_reject: "str | None" = None  # guarded-by: main-thread
        # The live driver's degradation evidence rides in the merged
        # /api/v1/metrics document (latest driver wins — one per
        # ScenarioRunner run).  Weakly referenced: the module-global
        # provider registry must not root a finished run's driver (and
        # its store/service graph) for the rest of the process.
        import weakref

        ref = weakref.ref(self)

        def _stats() -> dict:
            drv = ref()
            return drv.stats() if drv is not None else {"collected": True}

        register_provider("replay", _stats)

    @property
    def segment_seq(self) -> int:
        """Segments lowered so far (the trace-correlation counter).  The
        job plane's checkpoint cadence keys off this — a restored run's
        driver restarts at 0, which only re-bases span tags, never the
        schedule (docs/jobs.md "Incremental resume")."""
        return self._segment_seq

    def stats(self) -> dict:
        """Degradation evidence: a job result's ``replay`` block."""
        feat = self._featurizer
        return {
            # Which backend ran the segments (None until one dispatch
            # came back healthy): a run whose chip was lost to JAX's CPU
            # fallback has the same counts and must not read the same.
            **device_identity(),
            "device_steps": self.device_steps,
            "fallback_steps": self.fallback_steps,
            "device_round_trips": self.device_round_trips,
            "device_wait_s": round(self.device_wait_s, 6),
            "queue_width_max": self.queue_width_max,
            "steps_padded": self.steps_padded,
            "pairs_evaluated": self.pairs_evaluated,
            "queue_slots_run": self.queue_slots_run,
            "reconcile_writes_shared": self.reconcile_writes_shared,
            "reconcile_writes_copied": self.reconcile_writes_copied,
            "preempt_searches": self.preempt_searches,
            "preempt_table_builds": self.preempt_table_builds,
            "preempt_filter_runs": self.preempt_filter_runs,
            "preempt_candidates": self.preempt_candidates,
            "preempt_victims": self.preempt_victims,
            "preempt_nominations": self.preempt_nominations,
            "preempt_overflows": self.preempt_overflows,
            "sampled_attempts": self.sampled_attempts,
            "nodes_visited": self.nodes_visited,
            "nodes_scored": self.nodes_scored,
            # Nodes the walks passed that could not take the pod.
            "nodes_skipped": self.nodes_visited - self.nodes_scored,
            # Attempts whose walk went by the walk tensor, one sort of
            # the node axis each (0: the slot order was the walk order).
            "sampled_by_rank": self.sampled_by_rank,
            # Steps with such an attempt (nothing is sorted a step) and
            # the walk rows the lowerings computed.
            "sampled_by_rank_steps": self.sampled_by_rank_steps,
            "walk_rows_built": self.walk_rows_built,
            # The index the walk stands at (either path moves it).
            "sampling_start": self.service._pnts_start.get(self._sched_name or "", 0),
            # Zones of the service's node tree (0: it does not sample).
            "sampling_zones": len(self.service._node_tree.zones),
            # Volumes on the segment path (docs/jobs.md): device sums,
            # the carried state at the last committed step, and what the
            # largest lowering read and built.
            "volume_attempts": self.volume_attempts,
            "volume_rejections": self.volume_rejections,
            "volume_attached": self.volume_attached,
            "volume_headroom_min": self.volume_headroom_min,
            "volume_objects": self.volume_objects,
            "volume_classes": self.volume_classes,
            "volume_shared": self.volume_shared,
            "ingest_prefetches": self.ingest_prefetches,
            "device_errors": self.device_errors,
            "watchdog_timeouts": self.watchdog_timeouts,
            "breaker_tripped": self.breaker_tripped,
            # Half-open recovery evidence: zeros (and cooldown_s 0)
            # under the default sticky configuration.
            "breaker": {
                "cooldown_s": self.breaker_cooldown_s,
                "cooldown_current_s": self._breaker_cooldown_cur,
                "probes": self.breaker_probes,
                "closes": self.breaker_closes,
                "reopens": self.breaker_reopens,
            },
            "unsupported": dict(self.unsupported),
            # Incremental-lowering evidence (round 10): the cache's
            # hit/miss/invalidation counters and the driver featurizer's
            # fresh per-pod row builds make the O(delta) lowering claim
            # machine-checkable straight from the job result.
            "lower_cache": self._cache.stats(),
            "featurize_calls": feat.pod_rows_built if feat is not None else 0,
            "featurize_reused": feat.pod_rows_reused if feat is not None else 0,
            "featurize_rebuilt": feat.pod_rows_rebuilt if feat is not None else 0,
            "featurize_copied": feat.pod_rows_copied if feat is not None else 0,
            "featurize_passes": feat.featurize_passes if feat is not None else 0,
            "featurize_node_builds": self.featurize_node_builds,
            "featurize_bound_records": self.featurize_bound_records,
            "featurize_bound_shared": self.featurize_bound_shared,
            "content_keys_built": self.content_keys_built,
            "universe_screens": self.universe_screens,
            "prelower": {
                "windows": self.prelower_windows,
                "consumed": self.prelower_consumed,
                "discarded": self.prelower_discarded,
                "faults": self.prelower_faults,
            },
            # PROCESS-WIDE (shared by every driver/tenant in the
            # process): the compiled-executable cache's rung counters —
            # misses = actual compiles, shared_rungs = rungs serving
            # more than one tenant (engine/compilecache.py).
            "compile_cache": COMPILE_CACHE.snapshot(),
        }

    # -- support checks ------------------------------------------------------

    def _reject(self, reason: str) -> None:
        self.unsupported[reason] = self.unsupported.get(reason, 0) + 1
        self._last_reject = reason
        # Every degradation is a timeline event: reason + which window
        # (the lower/dispatch spans of the same segment share the seq).
        TRACE.event(
            "replay.fallback",
            reason=reason,
            segment=self._segment_seq,
            **self._span_tags,
        )

    def service_supported(self) -> bool:
        svc = self.service
        if svc._record not in ("selection", "full"):
            self._reject("record_mode")
            return False
        if getattr(svc, "_extenders", None):
            self._reject("extenders")
            return False
        if svc._shard_mesh is not None:
            # Round 17: a node-axis (tp) mesh is SUPPORTED — the segment
            # program lays every [N]/[N, R] tensor over it and GSPMD
            # inserts the per-step collectives.  Only genuinely
            # unsupported shapes still reject: a dp>1 mesh would split
            # the pod axis under the sequential-commit scan (order is
            # the parity contract), and a mesh without a tp axis has
            # nothing to lay the node axis over.  Axis sizes come off
            # the mesh object itself — no backend init on this thread.
            # A FLEET lane (round 19) takes the mesh as its tp-width
            # declaration only: the group dispatch lays lanes over its
            # own (dp, tp) fleet mesh of the same node-shard width
            # (engine/fleet.py _worker_mesh), while the lane's solo
            # fallback dispatches honor the declared (1, tp) layout.
            from ksim_tpu.engine.sharding import DP, TP

            mesh = svc._shard_mesh
            axes = dict(zip(mesh.axis_names, mesh.devices.shape))
            if axes.get(DP, 1) != 1 or TP not in axes:
                self._reject("shard_mesh")
                return False
            self._shard_mesh_obj = mesh
            self._tp_req = int(axes[TP])
        else:
            self._shard_mesh_obj = None
            self._tp_req = self._tp_env
        if svc._featurizer_override is not None:
            self._reject("featurizer_override")
            return False
        names = svc._scheduler_names
        if len(names) != 1:
            self._reject("multi_profile")
            return False
        prof = None
        if svc._plugins_factory is None:
            prof = svc._profiles.get(names[0])
            if prof is None:
                self._reject("no_profile")
                return False
            if prof.pre_enqueue_hooks or prof.queue_sort_plugin is not None:
                self._reject("queue_hooks")
                return False
        if svc._waiting:
            self._reject("permit_waiters")
            return False
        self._sched_name = names[0]
        self._record_mode = svc._record
        # Preemption lowers into the segment scan unless the profile
        # disabled DefaultPreemption (then PostFilter is inert for the
        # modeled vocabulary — custom post_filter hooks reject below).
        preempt = bool(svc._preemption)
        if preempt and prof is not None and "DefaultPreemption" in prof.postfilter_disabled:
            preempt = False
        self._preempt_active = preempt
        return True

    _OP_KINDS = frozenset({"pods", "nodes"})
    #: Kinds a step may CREATE besides (a delete or an update of one inside
    #: a stream ends the window as ``op:delete/persistentvolumes`` etc.).
    _VOLUME_KINDS = frozenset(kind for _arg, kind in _VOLUME_ARGS)

    @classmethod
    def _op_in_vocabulary(cls, op) -> bool:
        if op.kind in cls._OP_KINDS:
            return op.op in ("create", "delete")
        return op.kind in cls._VOLUME_KINDS and op.op == "create"

    def _window_len(self) -> int:
        """Steps one lowered window may consume (record mode dependent;
        valid after ``service_supported``)."""
        return self._full_k if self._record_mode == "full" else self.k

    def _parse_window(self, batches: list[list[Any]]) -> _WindowSpec:
        """The store-independent lowering prefix for up to one window of
        batches: op-vocabulary screening, per-step net object events
        (same-step create+delete cancels), window-local name
        bookkeeping, and support checks on CREATED objects.  Never reads
        the store or mutable service state, so it can run speculatively
        while the previous segment's dispatch is in flight.  Vocabulary
        misses never propagate: they stop the parse and land in the
        spec's ``head_reason`` / ``err_step``+``err_reason`` fields for
        the consumer to raise (or ignore, when its clamped window ends
        before the erroring step)."""
        spec = _WindowSpec(
            wlen=self._window_len(), sched_names=self.service._scheduler_names
        )
        # (The op screen below is also run — head batch only, pre-span —
        # by _batch_ops_ok, so a head-rejected window never opens the
        # replay.lower span; keep the two in sync.)
        win_pod_seen: set[str] = set()  # keys ever used by window creates
        win_pod_live: set[str] = set()  # window-created keys still alive
        ext_del_pods: set[str] = set()  # pre-window keys deleted in-window
        win_node_seen: set[str] = set()
        win_node_live: set[str] = set()
        ext_del_nodes: set[str] = set()
        win_vol_seen: set[tuple[str, str]] = set()
        try:
            for k, batch in enumerate(batches):
                for op in batch:
                    if not self._op_in_vocabulary(op):
                        if k == 0:
                            spec.head_reason = f"op:{op.op}/{op.kind}"
                        return spec  # op-screen prefix ends here
                # (A volume object's arrival flushes the backoff like a
                # node's: scenario/runner.py ``_run_step_traced``.)
                st = _StepParse(
                    flush=any(
                        op.kind != "pods" or op.op == "delete" for op in batch
                    )
                )
                for op in batch:
                    if op.kind in self._VOLUME_KINDS:
                        key = (
                            name_of(op.obj)
                            if op.kind != "persistentvolumeclaims"
                            else f"{namespace_of(op.obj) or 'default'}/{name_of(op.obj)}"
                        )
                        if (op.kind, key) in win_vol_seen:
                            raise _Unsupported("volume_name_reuse")
                        win_vol_seen.add((op.kind, key))
                        spec.checks.append((k, "create_volume", f"{op.kind}/{key}"))
                        spec.created_volumes.append((k, op.kind, key, op.obj))
                    elif op.kind == "pods":
                        if op.op == "create":
                            key = _pod_key(op.obj)
                            if key in win_pod_seen or key in ext_del_pods:
                                raise _Unsupported("pod_name_reuse")
                            # Against the live store + the service's
                            # backoff table: deferred (_lower).
                            spec.checks.append((k, "create_pod", key))
                            if op.obj.get("spec", {}).get("nodeName") or op.obj.get(
                                "status", {}
                            ).get("phase"):
                                raise _Unsupported("create_bound_pod")
                            reason = self._pod_supported(op.obj, spec.sched_names)
                            if reason is not None:
                                raise _Unsupported(reason)
                            win_pod_seen.add(key)
                            win_pod_live.add(key)
                            st.pc.append(key)
                            spec.created_pods.append((k, key, op.obj))
                        else:
                            key = f"{op.namespace or 'default'}/{op.name}"
                            if key in win_pod_live:
                                if key in st.pc:
                                    st.pc.remove(key)  # same-step net no-op
                                else:
                                    st.pd.append(key)
                                win_pod_live.discard(key)
                            elif key in win_pod_seen or key in ext_del_pods:
                                # Window-locally provable double delete.
                                raise _Unsupported("delete_unknown_pod")
                            else:
                                # Must exist in the store: deferred.
                                spec.checks.append((k, "delete_pod", key))
                                ext_del_pods.add(key)
                                st.pd.append(key)
                    else:  # nodes
                        if op.op == "create":
                            nm = name_of(op.obj)
                            if nm in win_node_seen or nm in ext_del_nodes:
                                raise _Unsupported("node_name_reuse")
                            spec.checks.append((k, "create_node", nm))
                            if op.obj.get("status", {}).get("images"):
                                raise _Unsupported("node_images")
                            win_node_seen.add(nm)
                            win_node_live.add(nm)
                            st.nc.append(nm)
                            spec.created_nodes.append((k, op.obj))
                        else:
                            if not self._requeue:
                                raise _Unsupported("drain_without_requeue")
                            nm = op.name
                            if nm in win_node_live:
                                if nm in st.nc:
                                    st.nc.remove(nm)
                                else:
                                    st.nd.append(nm)
                                win_node_live.discard(nm)
                            elif nm in win_node_seen or nm in ext_del_nodes:
                                raise _Unsupported("delete_unknown_node")
                            else:
                                spec.checks.append((k, "delete_node", nm))
                                ext_del_nodes.add(nm)
                                st.nd.append(nm)
                spec.steps.append(st)
                spec.n = len(spec.steps)
        except _Unsupported as e:
            spec.err_step = len(spec.steps)
            spec.err_reason = str(e)
        return spec

    # -- the double-buffered executor's speculative prefix -------------------

    def _discard_spec(self) -> None:
        if self._spec is not None:
            self._spec = None
            self.prelower_discarded += 1

    def _flush_incremental(self, reason: str) -> None:
        """Strictly drop ALL incremental lowering state — the cache, the
        speculative prefix and the retained plan — ahead of a path the
        incremental bookkeeping cannot track.  One helper so no future
        invalidation site can flush the cache but leave a stale plan
        behind it."""
        self._cache.invalidate(reason)
        self._discard_spec()
        self._last_plan = None

    def _take_spec(self, batches: list[list[Any]]) -> "_WindowSpec | None":
        """Consume the speculative prefix if it predicted exactly this
        window (same batch-list identities, same window length, same
        profile config); discard it otherwise."""
        held = self._spec
        self._spec = None
        if held is None:
            return None
        lists, spec = held
        if (
            len(batches) < len(lists)
            or any(a is not b for a, b in zip(lists, batches))
            or spec.wlen != self._window_len()
            or spec.sched_names != self.service._scheduler_names
        ):
            self.prelower_discarded += 1
            return None
        self.prelower_consumed += 1
        return spec

    def _prelower_next(self, plan: "_SegmentPlan", future: list[list[Any]]) -> None:
        """Speculatively parse + memo-warm the NEXT window while the
        current segment's dispatch runs on the worker thread.  The
        prefix is store-independent by construction, so it cannot race
        the (not-yet-known) outcome of segment N; the store-dependent
        remainder runs in ``_lower`` only after N's reconcile commits.
        Containment: any classified failure here — including an armed
        ``replay.prelower`` fault — degrades THIS window's overlap only
        (the window parses synchronously instead); it never touches the
        in-flight dispatch or the locks."""
        self._discard_spec()  # a stale prediction can never be consumed
        nxt = future[plan.n_steps : plan.n_steps + self._window_len()]
        if not nxt:
            return
        self.prelower_windows += 1
        try:
            with TRACE.span(
                "replay.prelower", segment=self._segment_seq, steps=len(nxt)
            ) as sp:
                FAULTS.check("replay.prelower")
                sp.lap("replay.lower.parse")
                spec = self._parse_window(nxt)
                sp.lap("replay.lower.warm")
                self._warm_spec(spec)
        except Exception as e:
            # Catch EVERYTHING, not just SimulatorError: this runs while
            # the dispatch worker is in flight, and a propagating
            # programming error would be misclassified by the dispatch
            # handlers as a device_error (feeding the breaker) or crash
            # past the un-joined worker.  A real bug is not masked — the
            # window re-parses synchronously inside replay.lower, where
            # the taxonomy re-raises non-SimulatorErrors with the worker
            # safely joined.
            self.prelower_faults += 1
            logger.warning(
                "speculative prelower failed (%s: %s); the next window "
                "lowers synchronously",
                type(e).__name__, e,
            )
            return
        # Hold the batch lists themselves, not bare id()s: the pinned
        # references keep CPython from recycling an id onto a different
        # list, so _take_spec's identity match can never false-positive.
        self._spec = (tuple(nxt), spec)

    def _warm_spec(self, spec: _WindowSpec) -> None:
        """Populate the per-object parse memos for the window's CREATED
        objects (the only ones the next featurize will miss on) off the
        critical path.  Every warmed function is a pure parse of a
        frozen object memoized on its identity (state/objcache.py), so
        warming is semantically invisible — the completion path would
        compute the identical entries, just inside the replay.lower
        span."""
        from ksim_tpu.state.encoding import _parsed_node_affinity
        from ksim_tpu.state.interpod import parsed_terms
        from ksim_tpu.state.resources import node_allocatable, pod_tolerations
        from ksim_tpu.state.resources import pod_requests as _preqs

        for _step, _key, obj in spec.created_pods:
            _preqs(obj)
            _preqs(obj, non_zero=True)
            pod_tolerations(obj)
            _parsed_node_affinity(obj)
            parsed_terms(obj)
        for _step, obj in spec.created_nodes:
            node_allocatable(obj)

    def _batch_ops_ok(self, batch: Sequence[Any], record: bool) -> bool:
        """Cheap op-vocabulary screen for ONE step's batch (no store
        access).  ``record`` counts the reject reason — only the batch
        that actually forces a fallback (the segment head) should."""
        for op in batch:
            if not self._op_in_vocabulary(op):
                if record:
                    self._reject(f"op:{op.op}/{op.kind}")
                return False
        return True

    @staticmethod
    def _pod_supported(pod: JSON, sched_names: tuple[str, ...]) -> str | None:
        """None when the pod fits the tensor vocabulary, else the reason.

        Of what ``boundagg.content_key`` leaves out of a manifest — the
        identity fields of ``metadata`` (``podtable._IDENTITY``),
        ``spec.nodeName`` and all of ``status`` — this reads
        ``status.phase`` and nothing else: ``_cold_universe`` runs it
        once a key and tests the phase of every other pod itself.  A
        reason that reads another of those fields has to join that
        per-pod half (tests/test_content_key_handover.py holds the walk
        to this screen of every pod)."""
        from ksim_tpu.scheduler.profile import DEFAULT_SCHEDULER_NAME
        from ksim_tpu.state.extras import _host_ports
        from ksim_tpu.state.volumes import _has_ephemeral_claim

        spec = pod.get("spec", {})
        if spec.get("schedulingGates"):
            return "scheduling_gates"
        name = spec.get("schedulerName") or DEFAULT_SCHEDULER_NAME
        if name not in sched_names:
            return "foreign_scheduler"
        if pod.get("status", {}).get("phase") in ("Succeeded", "Failed"):
            return "terminal_phase"
        if _host_ports(pod):
            return "host_ports"
        if _has_ephemeral_claim(pod):
            # A generic ephemeral volume's claim is made by a controller
            # after the pod: the stream does not hold it.
            return "ephemeral_volume_claim"
        return None

    def _cold_universe(
        self, cur_pods: list[JSON], sched_names: tuple[str, ...], priority_of
    ) -> "tuple[list[tuple], dict[int, bytes | None]]":
        """The store's pods for a lowering that missed the cache, in one
        walk: ``(queue_sort_key, pod key, cleaned pending copy)`` of each,
        unsorted, and the content keys to hand to the featurizer,
        ``id(object) -> boundagg.content_key`` (one bytes object a
        distinct manifest): of each pod that has a cleaned copy, for the
        bound contents, and of each queue object whose pod-table key
        would cover the same fields, for the pod table.

        Raises ``_Unsupported`` with the reason of the first offending
        pod in store order: the first pod of a key is screened in full;
        a later one can differ from it only in what the key leaves out,
        of which ``_pod_supported`` reads the phase alone (its
        docstring).  A pod without a key (a manifest ``marshal`` cannot
        take) is screened itself."""
        from ksim_tpu.scheduler.service import queue_sort_key
        from ksim_tpu.state.boundagg import content_key

        passed: dict[bytes, bytes] = {}
        keys: "dict[int, bytes | None]" = {}
        decorated = []
        for p in cur_pods:
            key = content_key(p)
            self.content_keys_built += 1
            known = None if key is None else passed.get(key)
            if known is None:
                self.universe_screens += 1
                reason = self._pod_supported(p, sched_names)
                if reason is not None:
                    raise _Unsupported(reason)
                if key is not None:
                    passed[key] = key
            else:
                key = known
                if p.get("status", {}).get("phase") in ("Succeeded", "Failed"):
                    raise _Unsupported("terminal_phase")
            clean = _cleaned_pending(p)
            if clean is not p:
                keys[id(p)] = key
            # The pod table's own key (podtable.content_key) leaves out
            # identity alone: this key, which leaves out spec.nodeName
            # and status too, cuts the queue as that one would only
            # where the queue object holds neither.  An empty status is
            # taken for an absent one: no row builder can tell them
            # apart (they read it through ``.get``, if at all).
            if "nodeName" not in (clean.get("spec") or ()) and clean.get("status", {}) == {}:
                keys[id(clean)] = key
            decorated.append((queue_sort_key(p, priority_of), _pod_key(p), clean))
        return decorated, keys

    # -- lowering ------------------------------------------------------------

    def try_segment(self, batches: list[list[Any]]):
        """Lower + run up to one window of steps (``batches`` may carry
        LOOKAHEAD beyond the window — the double-buffered executor
        pre-lowers the following window's store-independent prefix while
        this one's dispatch is in flight); returns SegmentOutcome (whose
        ``steps`` may be SHORTER than the window: the supported prefix,
        tail-padded on-device to the compiled K) or None (the FIRST step
        is unsupported — the caller falls back for it).  Must be called
        BEFORE the steps' ops touch the store.

        Failure taxonomy (classified, never a bare catch-all):

        - ``ReplayFallback`` (lowering vocabulary misses, validation
          discards) -> per-pass fallback under its stable reason;
        - any other ``SimulatorError`` during LOWERING -> fallback as
          ``lowering_fault`` (an expected, containable failure);
        - device/runtime errors or a watchdog timeout during DISPATCH ->
          ``device_error`` fallback, counted toward the circuit breaker;
        - everything else (TypeError & friends) is a programming error
          and RE-RAISES — silent fallback must never mask a bug.

        Any None return STRICTLY invalidates the lowered-universe cache,
        discards the speculative prefix, and drops the device-resident
        constant buffers: the per-pass path is about to mutate store and
        service state the incremental bookkeeping cannot track.
        """
        out = self._try_segment_impl(batches)
        if out is None:
            # A probe admitted in prepare_segment that never reached a
            # dispatch verdict (lowering fault / vocabulary miss) must
            # not leave the half-open gate ajar: re-open, cooldown
            # doubled — unbounded free re-probing would defeat the
            # backoff.  (A probe that failed IN dispatch was already
            # resolved by _note_device_error, which clears the flag.)
            if self._breaker_probe:
                self._breaker_reopen("probe lost before dispatch")
            self._flush_incremental("fallback")
        return out

    def _try_segment_impl(self, batches: list[list[Any]]):
        plan = self.prepare_segment(batches)
        if plan is None:
            return None
        return self.dispatch_segment(plan, batches)

    def prepare_segment(
        self, batches: list[list[Any]], *, check_lane_faults: bool = True
    ) -> "_SegmentPlan | None":
        """The lowering half of ``try_segment``: breaker / support / op
        screens plus the classified lowering taxonomy, ending in a
        dispatch-ready ``_SegmentPlan`` or None with the reason
        recorded.  Split from the dispatch half so
        the fleet coordinator (engine/fleet.py) can lower a shared
        window ONCE on the cohort leader and dispatch all lanes in one
        program.  The fleet passes ``check_lane_faults=False``: it gates
        EVERY cohort lane's private plane itself (including the
        leader's) so a lane-armed replay.lower fault degrades exactly
        one lane — a check here too would both double-count the
        leader's schedule and land the injected fault inside the SHARED
        lowering, degrading the whole cohort."""
        if self.breaker_tripped and not self._breaker_admit_probe():
            # Open: every window falls back immediately — no lowering
            # work, no watchdog tax.  Sticky under the default cooldown
            # of 0; otherwise ONE probe segment per elapsed cooldown
            # gets through the gate above.
            self._reject("breaker_open")
            return None
        if not self.service_supported():
            return None
        # Pre-span head screen: a window whose FIRST step is outside the
        # op vocabulary never lowers — no replay.lower span, no fault
        # slot, no segment seq — so phase counts and armed call:N fault
        # schedules keep tracking REAL lowerings (the pre-round-10
        # semantics).  try_segment's None wrapper discards any held
        # speculative spec and flushes the cache, as for any fallback.
        if not batches or not self._batch_ops_ok(batches[0], record=True):
            return None
        wlen = self._window_len()
        spec = self._take_spec(batches)
        self._segment_seq += 1
        try:
            with TRACE.span(
                "replay.lower",
                segment=self._segment_seq,
                steps=min(len(batches), wlen),
                **self._span_tags,
            ) as sp:
                FAULTS.check("replay.lower")
                if check_lane_faults and self._lane_faults is not None:
                    self._lane_faults.check("replay.lower")
                if spec is None:
                    sp.lap("replay.lower.parse")
                    spec = self._parse_window(batches[:wlen])
                m = min(spec.n, wlen)
                if m == 0:
                    raise _Unsupported(spec.head_reason or spec.err_reason)
                # The opening value is window CAPACITY; refine to the
                # actually-lowered count so lower spans line up with
                # dispatch spans on short (vocabulary-miss) segments.
                sp.set(steps=m)
                plan = self._lower(list(batches[:m]), spec, sp)
        except ReplayFallback as e:
            self._reject(str(e))
            return None
        except SimulatorError as e:
            logger.warning(
                "segment lowering failed (%s: %s); falling back per-pass",
                type(e).__name__, e,
            )
            self._reject("lowering_fault")
            return None
        return plan

    def dispatch_segment(self, plan: "_SegmentPlan", batches: list[list[Any]]):
        """The dispatch half of ``try_segment``: the watchdogged device
        run plus post-dispatch accounting.  Returns the SegmentOutcome
        or None (reason recorded, breaker fed)."""
        try:
            with TRACE.span(
                "replay.dispatch",
                segment=self._segment_seq,
                steps=plan.n_steps,
                **self._span_tags,
            ):
                res = self._run_watchdogged(plan, batches)
        except ReplayParityError:
            raise  # a kernel bug, not a degradable condition
        except ReplayFallback as e:
            self._reject(str(e))
            return None
        except (DeviceUnavailableError, SimulatorError, RuntimeError, OSError) as e:
            return self._note_device_error(e)
        # The dispatch came back healthy (even if validation discarded
        # the segment): the backend is alive — reset the breaker window.
        self.note_dispatch_healthy(plan)
        if isinstance(res, str):
            if res == "preemption_overflow":
                self.preempt_overflows += 1
                if plan.log_entry is not None:
                    plan.log_entry["preempt_overflows"] = 1
            # Post-dispatch validation discard (featurize_prediction /
            # preemption_overflow): store untouched, fall back.
            self._reject(res)
            return None
        # device_steps is counted by the caller once the segment COMMITS
        # (a rolled-back reconcile re-runs its steps per-pass — counting
        # here would double-book them).
        self._last_plan = plan
        return res

    def note_dispatch_healthy(self, plan: "_SegmentPlan") -> None:
        """Main-thread accounting for one healthy dispatch join: breaker
        window reset, round-trip count.  Shared by the solo path above
        and the fleet's group dispatch (every lane's driver gets it)."""
        self._consecutive_device_errors = 0
        if self._breaker_probe:
            # The half-open probe segment came back healthy: the
            # backend recovered — close the breaker and re-promote the
            # driver to the device path.
            self._breaker_close()
        self.device_round_trips += 1
        self.device_wait_s += plan.exec_s
        self.queue_width_max = max(self.queue_width_max, plan.statics.q)
        self.steps_padded += plan.statics.k - plan.n_steps
        note_backend()

    def _run_watchdogged(self, plan: "_SegmentPlan", future: list[list[Any]]):
        """Run ``_run`` on a worker thread bounded by the watchdog, and
        OVERLAP the wait with the next window's speculative prelower on
        this (the main) thread — the double-buffered pipeline.  The
        watchdog budget still covers the dispatch from ITS start: the
        join timeout is reduced by however long the prelower took.

        ``block_until_ready`` against a wedged backend never returns;
        the join timeout turns that hang into DeviceUnavailableError so
        the run DEGRADES instead of stalling.  The abandoned worker is a
        daemon — it cannot be killed, but the breaker counts CUMULATIVE
        watchdog timeouts (see ``_note_device_error``), so at most
        ``breaker_threshold`` of them ever exist.  ``_run`` is
        side-effect-free on the driver (counters are applied by the
        caller on the MAIN thread), so a late-finishing stray worker
        cannot corrupt the accounting of the degraded run."""
        if self.watchdog_s <= 0:
            out = self._run(plan)
            # No worker to overlap with; the parse/memo warm still moves
            # off the next window's replay.lower span.
            self._prelower_next(plan, future)
            self._drain_ingest()
            return out
        box: dict[str, Any] = {}
        # A job-scoped caller's trace override is thread-local; carry it
        # onto the worker so dispatch-side spans/events (fault.fired,
        # the lane plane's checks) stay attributed to the owning job.
        scope = TRACE.scope()

        def work() -> None:  # ksimlint: worker-thread
            try:
                with TRACE.scoped(scope):
                    box["out"] = self._run(plan)
            except BaseException as e:  # classified by the caller
                box["err"] = e

        t = threading.Thread(target=work, name="replay-dispatch", daemon=True)
        t.start()
        t0 = time.monotonic()
        self._prelower_next(plan, future)
        self._drain_ingest()
        t.join(max(self.watchdog_s - (time.monotonic() - t0), 0.001))
        if t.is_alive():
            self.watchdog_timeouts += 1
            TRACE.event(
                "replay.watchdog_timeout",
                segment=self._segment_seq,
                watchdog_s=self.watchdog_s,
            )
            raise DeviceUnavailableError(
                f"segment dispatch exceeded the {self.watchdog_s:.0f}s watchdog"
            )
        if "err" in box:
            raise box["err"]
        return box["out"]

    def _drain_ingest(self) -> None:
        """Pull whatever the trace-ingest producer has ready (a bounded,
        nonblocking window drain) while the dispatch worker owns the
        device.  Errors other than cancellation are swallowed HERE on
        purpose: a mid-dispatch raise would be misclassified by the
        device-error ladder (or strand the un-joined worker), and the
        same error re-raises deterministically at the runner's next
        blocking ensure."""
        if self._ingest_hook is None:
            return
        try:
            self._ingest_hook()
            self.ingest_prefetches += 1
        except RunCancelled:
            raise
        except Exception:
            logger.debug(
                "ingest prefetch hook failed; deferring to the blocking "
                "ingest path",
                exc_info=True,
            )

    def _note_device_error(self, e: BaseException) -> None:
        """Account one degraded dispatch; trip the breaker on the Nth
        CONSECUTIVE failure — or the Nth watchdog timeout over the whole
        run: every timeout abandons a worker thread pinned on its
        segment plan forever, so cumulative timeouts must trip even when
        healthy dispatches reset the consecutive window in between
        (bounding leaked workers at breaker_threshold).  Always returns
        None (the fallback)."""
        self.device_errors += 1
        self._consecutive_device_errors += 1
        self._reject("device_error")
        if self._breaker_probe:
            # This WAS the half-open probe: the backend is still dead.
            # Re-open with a doubled (bounded) cooldown; none of the
            # trip logic below applies — the breaker never closed.
            self._breaker_reopen(f"{type(e).__name__}: {e}")
            return None
        if (
            not self.breaker_tripped
            and (
                self._consecutive_device_errors >= self.breaker_threshold
                or self.watchdog_timeouts >= self.breaker_threshold
            )
        ):
            self.breaker_tripped = True
            self._breaker_schedule_retry()
            TRACE.event(
                "replay.breaker_open",
                cause="device_error",
                consecutive=self._consecutive_device_errors,
                watchdog_timeouts=self.watchdog_timeouts,
            )
            logger.error(
                "device replay circuit breaker TRIPPED (%d consecutive "
                "device failures, %d watchdog timeouts total, threshold %d; "
                "last: %s: %s); remaining steps run on the per-pass host "
                "path",
                self._consecutive_device_errors, self.watchdog_timeouts,
                self.breaker_threshold, type(e).__name__, e,
            )
        else:
            logger.warning(
                "segment dispatch failed (%s: %s); the window's head step "
                "re-runs per-pass, the rest retries on-device "
                "(%d/%d consecutive failures before the circuit breaker "
                "opens)",
                type(e).__name__, e,
                self._consecutive_device_errors, self.breaker_threshold,
            )
        return None

    # -- breaker half-open recovery (round 15) ---------------------------
    # All main-thread, like every other breaker field: probes are
    # admitted in prepare_segment and resolved on the main thread after
    # the dispatch joins — the worker never touches the gate.

    def _breaker_schedule_retry(self) -> None:
        """Arm the next probe window (no-op under the sticky default)."""
        if self.breaker_cooldown_s > 0:
            self._breaker_retry_at = time.monotonic() + self._breaker_cooldown_cur

    def _breaker_admit_probe(self) -> bool:
        """One probe segment per elapsed cooldown: True admits THIS
        window through the open breaker as the probe.  False while the
        cooldown runs, while a probe is already in flight, or under the
        sticky default (cooldown 0)."""
        if self.breaker_cooldown_s <= 0 or self._breaker_probe:
            return False
        if self._breaker_retry_at is None or time.monotonic() < self._breaker_retry_at:
            return False
        self._breaker_probe = True
        self.breaker_probes += 1
        TRACE.event(
            "replay.breaker_probe",
            cooldown_s=self._breaker_cooldown_cur,
            probes=self.breaker_probes,
            **self._span_tags,
        )
        logger.info(
            "circuit breaker half-open: admitting one probe segment "
            "(cooldown %.1fs elapsed)", self._breaker_cooldown_cur,
        )
        return True

    def _breaker_close(self) -> None:
        """A healthy probe: close the breaker, reset both consecutive
        windows and the cooldown ladder — the driver is back on the
        device path as if it never tripped."""
        self._breaker_probe = False
        self.breaker_tripped = False
        self.breaker_closes += 1
        self._consecutive_device_errors = 0
        self._consecutive_reconcile_faults = 0
        self._breaker_cooldown_cur = self.breaker_cooldown_s
        self._breaker_retry_at = None
        TRACE.event(
            "replay.breaker_close",
            closes=self.breaker_closes,
            **self._span_tags,
        )
        logger.info(
            "device replay circuit breaker CLOSED after a healthy probe "
            "segment; device path re-promoted"
        )

    def _breaker_reopen(self, why: str) -> None:
        """A failed (or lost) probe: stay open, double the cooldown
        (bounded by _BREAKER_COOLDOWN_CAP_S) before the next probe."""
        self._breaker_probe = False
        self.breaker_reopens += 1
        self._breaker_cooldown_cur = min(
            self._breaker_cooldown_cur * 2.0, _BREAKER_COOLDOWN_CAP_S
        )
        self._breaker_retry_at = time.monotonic() + self._breaker_cooldown_cur
        TRACE.event(
            "replay.breaker_open",
            cause="probe_failed",
            cooldown_s=self._breaker_cooldown_cur,
            **self._span_tags,
        )
        logger.warning(
            "circuit breaker probe failed (%s); re-opened, next probe in "
            "%.1fs", why, self._breaker_cooldown_cur,
        )

    def _service_featurizer(self):
        """The canonical per-pass featurizer (created exactly as the
        service would, so a later fallback pass sees the same instance
        and — critically — the same NodeSlots history)."""
        svc = self.service
        name = self._sched_name
        feat = svc._featurizers.get(name)
        if feat is None:
            from ksim_tpu.state.featurizer import Featurizer

            if svc._plugins_factory is not None:
                feat = Featurizer(pod_bucket_min=svc._pod_bucket_min)
            else:
                feat = svc._profiles[name].featurizer(
                    pod_bucket_min=svc._pod_bucket_min
                )
            svc._featurizers[name] = feat
        return feat

    def _lower(self, batches: list[list[Any]], spec: _WindowSpec, span):
        """Lower one validated window into a dispatch-ready plan.  ``span``
        is the enclosing ``replay.lower`` span: the three phases below
        are its sequential children (``span.lap``), so the timeline says
        whether a slow lowering is universe assembly, the featurizer or
        the tensor build."""
        from ksim_tpu.engine.core import _Program
        from ksim_tpu.scheduler.service import queue_sort_key
        from ksim_tpu.state.featurizer import bucket_size
        from ksim_tpu.state.priorities import build_priority_resolver

        span.lap("replay.lower.universe")
        svc = self.service
        store = self.store
        m_steps = len(batches)
        lower_epoch = store.mutation_epoch
        cur_pods = store.list("pods", copy_objs=False)
        cur_nodes = store.list("nodes", copy_objs=False)
        node_names = {name_of(n) for n in cur_nodes}
        sched_names = svc._scheduler_names
        cache = self._cache
        use_cache = (
            cache.valid
            and cache.epoch == lower_epoch
            and cache.sched_names == sched_names
        )
        if cache.valid and not use_cache:
            # An out-of-band store write moved the mutation epoch — or a
            # scheduler reconfiguration changed the profile set the cached
            # survivors' support screen ran against (config changes never
            # write the store, so the epoch alone cannot see them) —
            # since the cache's segment committed: strict flush, rebuild.
            cache.invalidate(
                "epoch_mismatch"
                if cache.epoch != lower_epoch
                else "sched_config"
            )

        # Store-dependent half of the window validation: replay the
        # parse's deferred membership checks against the live store and
        # the service backoff table, in recorded op order, then raise
        # any window-local miss that sits inside this window.  (The
        # window-LOCAL half — op vocabulary, same-window name reuse,
        # created-object support — already ran in _parse_window,
        # possibly speculatively while the previous dispatch flew.)
        with svc._backoff_lock:
            backoff_keys = set(svc._backoff)
        # O(checks), not O(universe): each deferred pod-membership check
        # is one keyed store probe — building a key set over every store
        # pod here would reintroduce the per-segment O(U) host walk on
        # exactly the common cache-hit path this cache exists to avoid.
        for stp, check, key in spec.checks:
            if stp >= m_steps:
                break
            if check == "create_pod":
                ns, _, nm = key.partition("/")
                if store.contains("pods", nm, ns):
                    raise _Unsupported("pod_name_reuse")
                if key in backoff_keys:
                    # A stale backoff entry for a DEAD same-name pod: the
                    # per-pass path would let the new pod inherit it
                    # (_in_backoff is key-based), which the fresh
                    # universe row cannot model.
                    raise _Unsupported("backoff_name_reuse")
            elif check == "delete_pod":
                ns, _, nm = key.partition("/")
                if not store.contains("pods", nm, ns):
                    raise _Unsupported("delete_unknown_pod")
            elif check == "create_node":
                if key in node_names:
                    raise _Unsupported("node_name_reuse")
            elif check == "create_volume":
                kind, _, rest = key.partition("/")
                ns, _, nm = rest.rpartition("/")
                if store.contains(kind, nm, ns):
                    raise _Unsupported("volume_name_reuse")
            else:  # delete_node
                if key not in node_names:
                    raise _Unsupported("delete_unknown_node")
        # A parse error can only sit AT or PAST the lowered prefix's end
        # (err_step == spec.n by construction and m_steps <= spec.n):
        # the erroring step heads the NEXT window, which head-rejects it
        # — the prefix-granular fallback.
        assert spec.err_step >= m_steps, spec.err_reason

        # Net per-step object events from the (possibly speculative)
        # window parse; copies, because tail padding appends below.
        steps = spec.steps[:m_steps]
        step_pod_creates = [list(s.pc) for s in steps]
        step_pod_deletes = [list(s.pd) for s in steps]
        step_node_creates = [list(s.nc) for s in steps]
        step_node_deletes = [list(s.nd) for s in steps]
        step_flush = [s.flush for s in steps]
        created_pod_entries = [e for e in spec.created_pods if e[0] < m_steps]
        created_nodes = [obj for stp, obj in spec.created_nodes if stp < m_steps]
        # The volume objects the window's verdicts are read from: the
        # store's and the window's own (a step creates them before its
        # pods; ``volume_object_order`` below holds the window to that).
        volume_kw = {
            arg: store.list(kind, copy_objs=False)
            + [o for stp, kd, _key, o in spec.created_volumes if stp < m_steps and kd == kind]
            for arg, kind in _VOLUME_ARGS
        }

        # Tail padding: segments shorter than the compiled K (the stream
        # tail, a mid-window vocabulary miss, or full-record's shorter
        # K) extend with inactive no-op steps so they reuse the existing
        # compile instead of falling back (ROADMAP open item).
        k_pad = self._window_len()
        step_active = [True] * m_steps + [False] * (k_pad - m_steps)
        for _ in range(k_pad - m_steps):
            step_pod_creates.append([])
            step_pod_deletes.append([])
            step_node_creates.append([])
            step_node_deletes.append([])
            step_flush.append(False)

        # Universe pods, globally sorted by the exact per-pass queue key
        # (static per pod), so slot order IS queue order every step.
        # O(delta) on a cache hit: survivors keep their cached order and
        # sort keys (``queue_sort_key`` is total over distinct pod keys
        # — priority desc, creationTimestamp, namespace, name — so a
        # bisect merge of the window's creates reproduces exactly what a
        # full stable sort would); only created objects compute keys.
        # The UNIVERSE LIST HOLDS THE CLEANED PENDING OBJECTS: identical
        # to the live store objects in every lowered field (sort key,
        # requests, labels, tolerations, affinity, preemption statics —
        # binds/annotations only touch nodeName/phase/annotations/rv),
        # and identity-stable across segments, which is what keeps every
        # per-pod featurizer memo row alive (the O(delta) claim).
        content_keys = None
        keys_built0, screens0 = self.content_keys_built, self.universe_screens
        if use_cache:
            cache.hits += 1
            priority_of = cache.priority_of
            prio_gen = cache.prio_gen
            uni_keys = list(cache.keys)
            uni_sort = list(cache.sort_keys)
            uni_clean = list(cache.clean_pods)
        else:
            cache.misses += 1
            priority_of = build_priority_resolver(
                store.list("priorityclasses", copy_objs=False)
            )
            self._prio_gen += 1
            prio_gen = self._prio_gen
            # Full support screen + node-image screen (survivors on the
            # cache-hit path were screened when they entered the
            # universe and cannot have changed: only segment-exempt
            # writes happened since, and those never touch the screened
            # fields).  The screen runs once a DISTINCT MANIFEST: ONE
            # walk keys each store pod by content, screens the first pod
            # of a key in full and every other for what the key does not
            # cover (its phase), and makes its cleaned copy, sort key and
            # pod key; the keys go on to the featurizer, which takes
            # them for its own two tables instead of keying again.
            decorated, content_keys = self._cold_universe(
                cur_pods, sched_names, priority_of
            )
            for n in cur_nodes:
                if n.get("status", {}).get("images"):
                    raise _Unsupported("node_images")
            decorated.sort()
            uni_sort = [d[0] for d in decorated]
            uni_keys = [d[1] for d in decorated]
            uni_clean = [d[2] for d in decorated]
        for _stp, key, obj in created_pod_entries:
            sk = queue_sort_key(obj, priority_of)
            j = bisect.bisect_left(uni_sort, sk)
            uni_sort.insert(j, sk)
            uni_keys.insert(j, key)
            uni_clean.insert(j, obj)

        universe_pods = uni_clean
        universe_keys = uni_keys
        row_of = {k: j for j, k in enumerate(universe_keys)}
        if len(row_of) != len(universe_pods):
            raise _Unsupported("duplicate_pod_keys")

        # On-device preemption statics, window-scoped: a PRIORITY-FLAT
        # window can never enter DefaultPreemption's search — a
        # candidate node needs a bound pod of strictly LOWER priority
        # than the preemptor (`prow["priority"] < prio_p`), and no pod
        # carries a prior nomination — so it lowers preempt-free: the
        # bounded victim search is neither compiled nor traced.  Besides
        # the solo compile win, this is what keeps FLEET dispatch honest
        # (round 12): under jax.vmap a lax.cond lowers to select — BOTH
        # branches execute for every pod attempt — so for the search's
        # no-candidate case to stay free in a batched program it must be
        # absent from the statics, not merely predicated off.
        # record="full" keeps the search statics regardless: with
        # preemption enabled the host path writes a postfilter-result
        # annotation for every failed attempt, which only the preempt
        # decode path reproduces.
        preempt_plan = self._preempt_active
        prios = None
        if preempt_plan:
            prios = [priority_of(p) for p in universe_pods]
            # The pass's victim table (``_lower_table``) is kept across
            # the searches of a priority level because a pod that binds
            # is never of a lower priority than a preemptor behind it.
            if any(a < b for a, b in zip(prios, prios[1:])):
                raise AssertionError(
                    "the universe axis is not in queue order (priority "
                    "descending): the victim table a pass carries would miss "
                    "the pods that bind between two searches of a level"
                )
            if (
                self._record_mode == "selection"
                and not any(
                    p.get("status", {}).get("nominatedNodeName")
                    for p in cur_pods
                )
                and (not prios or prios.count(prios[0]) == len(prios))
            ):
                preempt_plan = False

        # Featurize the universe once (persistent device featurizer:
        # per-pod rows memoize, bound aggregates update by delta; with
        # the identity-stable cached universe, fresh row builds are
        # O(window creates) — tracked in pod_rows_built and logged per
        # segment in lower_log for the counter-based O(delta) guard).
        span.lap("replay.lower.featurize")
        if self._featurizer is None:
            if svc._plugins_factory is not None:
                from ksim_tpu.state.featurizer import Featurizer

                self._featurizer = Featurizer()
            else:
                self._featurizer = svc._profiles[self._sched_name].featurizer()
        feat = self._featurizer
        rows0 = (
            feat.pod_rows_built, feat.pod_rows_reused, feat.pod_rows_rebuilt,
            feat.pod_rows_copied,
        )
        universe_nodes = list(cur_nodes) + created_nodes
        walk_tree = None
        if svc._node_sampling:
            # A sampling attempt walks the nodes in the order of the
            # service's node tree (scheduler/nodetree.py: round-robin
            # across zones).  Give this table's new slots that order, so
            # that a stream without node churn walks for its samples in
            # slot order (``_SegmentStatics.sample`` 1: no gather in the
            # slot); slots the table already holds stay where they are.
            with TRACE.stage("replay.lower.walk_order"):
                walk_tree = svc._node_tree.copy()
                walk_tree.sync(cur_nodes)
                ahead = walk_tree
                if created_nodes:
                    ahead = walk_tree.copy()
                    ahead.apply((), created_nodes)
                place = {nm: i for i, nm in enumerate(ahead.list())}
                universe_nodes.sort(key=lambda n: place[name_of(n)])
        bound_pods = store.pods_with_node()
        from ksim_tpu.state import objcache

        memo = objcache.current()
        node_builds0 = memo.seq_builds
        bound_records0 = self._featurizer.bound_records_built
        bound_shared0 = self._featurizer.bound_records_shared
        feat_keys0 = self._featurizer.content_keys_built
        feats = self._featurizer.featurize(
            universe_nodes,
            (),
            queue_pods=universe_pods,
            bound_pods=bound_pods,
            namespaces=store.list("namespaces", copy_objs=False),
            content_keys=content_keys,
            **volume_kw,
        )
        self.content_keys_built += self._featurizer.content_keys_built - feat_keys0
        node_builds = memo.seq_builds - node_builds0
        self.featurize_node_builds += node_builds
        bound_records = self._featurizer.bound_records_built - bound_records0
        self.featurize_bound_records += bound_records
        bound_shared = self._featurizer.bound_records_shared - bound_shared0
        self.featurize_bound_shared += bound_shared
        # What is left of the lap after the call, as a stage of its own.
        TRACE.stage("replay.lower.featurize.program")
        if not feats.exact:
            raise _Unsupported("inexact_units")
        slot_of = dict(self._featurizer._slots.slot_of)

        factory = (
            svc._plugins_factory
            if svc._plugins_factory is not None
            else svc._profiles[self._sched_name].plugins
        )
        plugins = tuple(factory(feats))
        for sp in plugins:
            if sp.extender is not None:
                raise _Unsupported("plugin_extender")
            for attr in (
                "reserve",
                "unreserve",
                "permit",
                "pre_bind",
                "bind",
                "post_bind",
                "post_filter",
            ):
                if hasattr(sp.plugin, attr):
                    raise _Unsupported(f"host_hook:{attr}")
        prog = _Program(plugins, self._record_mode)

        # Volumes: whether any pod of the universe reads one (then the
        # plugins' counted carries ride the window's state), and the
        # cases the segment program does not carry.
        vt = feats.aux["volumes"]
        volumes_plan = bool(vt.live)
        if volumes_plan:
            if vt.pod_wffc.any():
                # Upstream's PreBind chooses a PV for an unbound
                # WaitForFirstConsumer claim and writes the binding.
                raise _Unsupported("unbound_wffc_claim")
            if preempt_plan:
                # The victim search's hypothetical states take pods off
                # a node by their request rows alone.
                raise _Unsupported("volume_victim_search")
            born = {
                (kd, key): stp
                for stp, kd, key, _o in spec.created_volumes
                if stp < m_steps
            }
            if born:
                # A pod's volume rows are one verdict for the window: the
                # objects they were read from have to stand by the pod's
                # first attempt (its own step; step 0 for a pod of the
                # store).
                first = {row_of[key]: stp for stp, key, _o in created_pod_entries}
                for j, refs in vt.pod_refs.items():
                    at = first.get(j, 0)
                    if any(born.get(ref, -1) > at for ref in refs):
                        raise _Unsupported("volume_object_order")

        if preempt_plan:
            from ksim_tpu.scheduler.preemption import (
                ORACLE_FIT_FILTER_NAMES,
                VOLUME_FIT_FILTER_NAMES,
            )

            # The device victim search re-checks fits through the
            # PROFILE's filter kernels, but the host oracle's fit chain
            # is FIXED — exactness requires the profile's filter set to
            # match it (volume filters optional: trivially passing for
            # this vocabulary, which has no volume objects or pod
            # volumes).
            fnames = {sp.plugin.name for sp in plugins if sp.filter_enabled}
            if not (
                ORACLE_FIT_FILTER_NAMES
                <= fnames
                <= (ORACLE_FIT_FILTER_NAMES | VOLUME_FIT_FILTER_NAMES)
            ):
                raise _Unsupported("preemption_filter_set")

        span.lap("replay.lower.tensors")
        N = feats.nodes.padded
        P = feats.pods.requests.shape[0]
        K = k_pad
        # Round 17: the mesh width for THIS universe.  N is a power-of-
        # two bucket, so gcd against the requested width finds the
        # largest divisor both agree on — the node axis always splits
        # evenly, and a universe narrower than the requested mesh just
        # runs at a narrower tp instead of rejecting.  An EXPLICIT
        # service shard_mesh is a layout contract, not a hint: a
        # universe its tp axis cannot divide is a genuinely unsupported
        # mesh shape (the narrowed "shard_mesh" reason).
        #
        # The per-shard width floor is a partitioner-hazard guard, not a
        # perf heuristic: at N=8 with tp>=4 the SPMD-partitioned
        # preemption scan returned sel/nom tensors with every value
        # DOUBLED (-1 came back -2, node 2 came back 4 — a partial sum
        # an all-reduce never folded), byte-identical at every width
        # with >= _MIN_SHARD_NODES rows per shard.  Silent corruption,
        # caught only because the doubled slot overran node_names — so
        # narrow below the floor rather than trust the compiler there.
        # A universe this small has nothing to gain from sharding
        # anyway; see docs/churn_floor.md.
        tp = math.gcd(self._tp_req, N)
        while tp > 1 and N // tp < _MIN_SHARD_NODES:
            tp //= 2
        if self._shard_mesh_obj is not None and tp != self._tp_req:
            raise _Unsupported("shard_mesh")
        ipa = feats.aux["interpod"]
        spread = feats.aux["spread"]

        # Initial dynamic state.  (The lap's seams are stages: the span
        # exit, or the next seam, closes each.)
        TRACE.stage("replay.lower.tensors.state")
        valid0 = np.zeros(N, bool)
        for n in cur_nodes:
            valid0[slot_of[name_of(n)]] = True
        alive0 = np.zeros(P, bool)
        bound0 = np.full(P, -1, np.int32)
        cur_keys = {_pod_key(p) for p in cur_pods}
        for p in cur_pods:
            j = row_of[_pod_key(p)]
            alive0[j] = True
            nn = p.get("spec", {}).get("nodeName")
            if nn:
                ns = slot_of.get(nn)
                if ns is None:
                    raise _Unsupported("bound_to_unknown_node")
                bound0[j] = ns
        attempts0 = np.zeros(P, np.int32)
        retry0 = np.zeros(P, np.int32)
        for key, (a, r) in svc._backoff.items():
            j = row_of.get(key)
            if j is not None and key in cur_keys:
                attempts0[j] = a
                retry0[j] = r

        # Inter-pod local per-node accumulators from the bound population
        # (the linear pre-aggregation the segment re-derives each step).
        TRACE.stage("replay.lower.tensors.interpod")
        T = ipa.pod_term_match.shape[1]
        ip_cnt0 = np.zeros((N, T), np.int32)
        ip_eat0 = np.zeros((N, T), np.int32)
        ip_vw0 = np.zeros((N, T), np.int32)
        b_rows = [row_of[_pod_key(p)] for p in bound_pods]
        b_slots = [int(bound0[j]) for j in b_rows]
        if b_rows:
            rows = np.asarray(b_rows)
            slots = np.asarray(b_slots)
            np.add.at(ip_cnt0, slots, ipa.pod_term_match[rows].astype(np.int32))
            np.add.at(ip_eat0, slots, ipa.pod_eat[rows])
            np.add.at(ip_vw0, slots, ipa.pod_vw[rows])
        n_dom = int(ipa.n_domains)
        from ksim_tpu.state.featurizer import vocab_pad

        n_dom_pad = vocab_pad(n_dom + 1)
        if not self._check_interpod_locals(
            ipa, ip_cnt0, ip_eat0, ip_vw0, n_dom_pad
        ):
            self._reject("interpod_local_mismatch")
            return None

        # Per-step event index tensors (-1 padded) + canonical ranks.
        # Widths bucket like every other axis: an exact-max width would
        # hand the jit cache a fresh shape (= a multi-second compile)
        # nearly every segment.
        TRACE.stage("replay.lower.tensors.ranks")

        def pad(lists: list[list[int]]) -> np.ndarray:
            width = vocab_pad(max((len(x) for x in lists), default=1))
            out = np.full((K, width), -1, np.int32)
            for k, xs in enumerate(lists):
                out[k, : len(xs)] = xs
            return out

        pod_create = pad([[row_of[k] for k in xs] for xs in step_pod_creates])
        pod_delete = pad([[row_of[k] for k in xs] for xs in step_pod_deletes])
        node_create = pad([[slot_of[n] for n in xs] for xs in step_node_creates])
        node_delete = pad([[slot_of[n] for n in xs] for xs in step_node_deletes])

        # The canonical featurizer advances its slot assignment ONLY on
        # passes that featurize — an empty eligible queue skips the sync
        # entirely (_schedule_pending_locked's `if not queue: continue`).
        # Queue emptiness depends on scheduling outcomes, so the lowering
        # PREDICTS it (a step with pod creates always has an eligible
        # queue: fresh pods carry no backoff) and the run validates the
        # prediction against the device-computed eligible counts,
        # discarding the segment on any mismatch (store untouched).
        pred_featurizes = [len(xs) > 0 for xs in step_pod_creates]
        sim_feat = self._service_featurizer()
        # No getattr default: if NodeSlots' internals ever change shape,
        # this must fail loudly — a silently empty seed would produce
        # wrong rank tensors and break the count locks undetected.
        sim = _SlotSim(sim_feat._slots.slot_of, sim_feat._slots._names)
        ranks = np.full((K, N), _I32_MAX, np.int32)
        # Per-step live-node views: name-order ranks + upstream's
        # candidate count for the preemption search; the live slot/name
        # lists (store list order = name order) for full-record decode.
        name_ranks = np.full((K, N), _I32_MAX, np.int32)
        want = np.zeros(K, np.int32)
        step_live_slots: list[np.ndarray] = []
        step_live_names: list[list[str]] = []
        step_node_event = [
            bool(step_node_creates[k] or step_node_deletes[k]) for k in range(K)
        ]
        from ksim_tpu.scheduler.preemption import candidate_count

        # Rank rows are maintained INCREMENTALLY: ``rank_row`` applies
        # only the slots each sync actually changed (the per-step delta
        # _SlotSim.sync now returns), and the sorted live-name list
        # evolves by bisect insert/remove — per-step cost is O(events +
        # one vectorized row copy), not the old O(N) python walk per
        # step over the whole slot map.
        rank_row = np.full(N, _I32_MAX, np.int32)
        for nm, slot in sim.slot_of.items():
            # .get: a dead node's name can linger in the service
            # featurizer's slot map (an empty-queue pass skips the
            # sync entirely); it has no universe slot and the kernels
            # never read its rank.
            j = slot_of.get(nm)
            if j is not None:
                rank_row[j] = slot
        need_names = preempt_plan or self._record_mode == "full"
        # percentageOfNodesToScore: each step's numFeasibleNodesToFind
        # from its live node count (0: no sampling at that size), and
        # whether the walk may go in slot order — at every step the live
        # nodes fill slots 0 .. n - 1 in the order of the service's node
        # tree (a stream without node churn) — or has to go by the walk
        # tensor.
        sampling = svc._node_sampling
        sample_prof = (
            svc._profiles.get(self._sched_name)
            if svc._plugins_factory is None
            else None
        )
        sample_k = np.zeros(K, np.int32)
        slot_order = True
        walk_rows = None
        walk_rows_built = 0
        if sampling:
            # Each step's walk order: every slot's place in the list of
            # the node tree as the step's node events leave it.  The tree
            # follows the node events alone, whatever the passes did.
            with TRACE.stage("replay.lower.walk_order"):
                joining = {name_of(n): n for n in created_nodes}
                walk_rows = np.empty((K, N), np.int32)
                walk_row = None
                for k in range(K):
                    if step_node_event[k]:
                        walk_tree.apply(
                            step_node_deletes[k],
                            [joining[nm] for nm in step_node_creates[k]],
                        )
                        walk_row = None
                    if walk_row is None:
                        walk_row = walk_tree.positions(slot_of, N, _I32_MAX)
                        walk_rows_built += 1
                    walk_rows[k] = walk_row
                self.walk_rows_built += walk_rows_built
            # The walk-order stage closed the seam's: open it again.
            TRACE.stage("replay.lower.tensors.ranks")
        live_row = valid0.copy()
        live_sorted: list[str] = sorted(node_names)
        live_slots = (
            np.asarray([slot_of[nm] for nm in live_sorted], np.int64)
            if need_names
            else None
        )
        for k in range(K):
            for nm in step_node_deletes[k]:
                j = bisect.bisect_left(live_sorted, nm)
                live_sorted.pop(j)
                if need_names:
                    live_slots = np.delete(live_slots, j)
            for nm in step_node_creates[k]:
                j = bisect.bisect_left(live_sorted, nm)
                live_sorted.insert(j, nm)
                if need_names:
                    live_slots = np.insert(live_slots, j, slot_of[nm])
            if pred_featurizes[k]:
                removed, changed = sim.sync(live_sorted)
                for nm in removed:
                    # .get: the sync may drop a name that predates the
                    # universe (see the seed loop above).
                    j = slot_of.get(nm)
                    if j is not None:
                        rank_row[j] = _I32_MAX
                for nm, slot in changed:
                    rank_row[slot_of[nm]] = slot
            ranks[k] = rank_row
            if sampling and step_active[k]:
                n_live = len(live_sorted)
                sample_k[k] = svc._sampling_k_for(sample_prof, n_live) or 0
                live_row[[slot_of[nm] for nm in step_node_deletes[k]]] = False
                live_row[[slot_of[nm] for nm in step_node_creates[k]]] = True
                # Only a step that attempts a pod walks: its own sync's
                # step, or one with no node event since the last
                # (``_decode_outputs`` discards the segment otherwise).
                if pred_featurizes[k] or not any(step_node_event[: k + 1]):
                    slot_order = slot_order and bool(
                        live_row[:n_live].all()
                        and (walk_rows[k, :n_live] == np.arange(n_live)).all()
                    )
            if need_names:
                want[k] = candidate_count(len(live_sorted))
                name_ranks[k, live_slots] = np.arange(
                    len(live_sorted), dtype=np.int32
                )
                if self._record_mode == "full":
                    # Only the full-record decode consumes the slot/name
                    # views — don't build them on the selection hot path.
                    step_live_slots.append(live_slots)
                    step_live_names.append(list(live_sorted))

        # Queue width: pending(now) + creates + requeue-able is an exact
        # upper bound on the pending population at any step, so eligible
        # can never exceed it (overflow-free by construction).
        TRACE.stage("replay.lower.tensors.statics")
        pending_now = int(np.sum(alive0 & (bound0 < 0)))
        drained = set().union(*step_node_deletes) if step_node_deletes else set()
        drained_bound = sum(
            1
            for p in bound_pods
            if p.get("spec", {}).get("nodeName") in drained
        )
        hard_bound = pending_now + sum(len(x) for x in step_pod_creates) + drained_bound
        cap = svc._max_pods_per_pass or (1 << 30)
        q = bucket_size(max(min(cap, hard_bound), 1))

        # Victim-search statics.  The priority levels: a nominated pod
        # counts for the pods of its level and below, so the nominees'
        # load is carried per level (bucketed: the level count is a
        # compiled shape).  And whether every filter verdict of the
        # window is node-local — no pod of the universe carries a
        # DoNotSchedule spread constraint or a required pod
        # (anti-)affinity term — which lets the search evaluate all of a
        # preemptor's candidates at once over the node axis
        # (_victims_over_nodes); otherwise it walks them in name order
        # (_victims_by_walk).  Read off the lowered tensors: the
        # window's objects choose, nothing else does.
        n_lvl, search_local, levels, ip_terms = 1, False, None, True
        if preempt_plan:
            ip_terms = bool(
                ipa.pod_term_match.any() or ipa.pod_eat.any() or ipa.pod_vw.any()
            )
            distinct = sorted(set(prios))
            if len(distinct) > PREEMPT_LEVELS_MAX:
                raise _Unsupported("priority_levels")
            n_lvl = vocab_pad(len(distinct), 2)
            level_of = {v: i for i, v in enumerate(distinct)}
            levels = np.zeros(P, np.int32)
            levels[: len(prios)] = [level_of[v] for v in prios]
            search_local = not (
                (spread.con_valid & (spread.con_mode == 0)).any()
                or ipa.req_aff.any()
                or ipa.req_anti.any()
                or ipa.pod_eat.any()
            )

        statics = _SegmentStatics(
            k=K,
            q=q,
            cap=cap,
            n_tk=ipa.node_dom.shape[1],
            n_dom=n_dom_pad,
            record=self._record_mode,
            preempt=preempt_plan,
            v_max=PREEMPT_VICTIMS,
            n_lvl=n_lvl,
            local=search_local,
            ip_terms=ip_terms,
            sample=0 if not sample_k.any() else 1 if slot_order else 2,
            tp=tp,
            volumes=volumes_plan,
        )
        const = {
            "node": dict(
                allocatable=feats.nodes.allocatable,
                allowed_pods=feats.nodes.allowed_pods,
                unschedulable=feats.nodes.unschedulable,
            ),
            "pods": dict(
                requests=feats.pods.requests,
                nonzero_requests=feats.pods.nonzero_requests,
                tolerates_unschedulable=feats.pods.tolerates_unschedulable,
                has_requests=feats.pods.has_requests,
            ),
            "aux": None,  # filled with the packed aux pytree below
        }
        ev = {
            "rank": ranks,
            "flush": np.asarray(step_flush, bool),
            "active": np.asarray(step_active, bool),
            "pod_create": pod_create,
            "pod_delete": pod_delete,
            "node_create": node_create,
            "node_delete": node_delete,
        }
        if statics.sample:
            ev["sample_k"] = sample_k
            if statics.sample == 2:
                ev["walk"] = walk_rows
        U = len(universe_pods)
        # Nominations that stand (pending pods only, onto a node that is
        # live: the runner clears the others with the node).
        nom_node0 = np.full(P, -1, np.int32)
        for p in cur_pods:
            nn = p.get("status", {}).get("nominatedNodeName")
            if nn and not p.get("spec", {}).get("nodeName"):
                ns = slot_of.get(nn)
                if ns is not None and valid0[ns]:
                    nom_node0[row_of[_pod_key(p)]] = ns
        # Stacked result tensors multiply one pass's [Q, F|S, N]
        # footprint by K on-device — bound it before dispatch.  The
        # budget is PER SHARD (round 17): each chip holds N/tp node
        # columns of every stacked tensor, so record="full" headroom
        # scales with the mesh.  Computed in every record mode (the
        # lower_log reports it as sizing evidence); only
        # record="full" actually allocates, so only it rejects.
        bits_dt, final_dt = prog._result_dtypes()
        n_f = sum(1 for sp in plugins if sp.filter_enabled)
        n_s = sum(1 for sp in plugins if sp.score_enabled)
        per_cell = (
            n_f * np.dtype(bits_dt).itemsize
            + n_s * 4
            + n_s * np.dtype(final_dt).itemsize
            + (1 if statics.sample else 0)  # the visited mask
        )
        full_bytes_shard = K * q * (N // tp) * per_cell
        if self._record_mode == "full" and full_bytes_shard > FULL_RECORD_BYTES:
            raise _Unsupported("full_record_bytes")
        if preempt_plan:
            from ksim_tpu.scheduler.preemption import (
                more_important_key,
                pod_eligible_to_preempt,
                start_time,
            )

            # Per-pod statics memoized on object identity (the cached
            # universe keeps survivors' objects alive across segments,
            # so the JSON walks behind these keys run once per pod, not
            # once per segment).  ``more_important_key`` depends on the
            # priority resolver, so its memo carries the resolver
            # generation — a rebuilt resolver (cache miss) mints fresh
            # entries instead of trusting stale priorities.
            def mik(p: JSON):
                return objcache.cached(
                    "replay_mik",
                    p,
                    lambda: more_important_key(p, priority_of),
                    prio_gen,
                )

            def stime(p: JSON) -> str:
                return objcache.cached("replay_stime", p, lambda: start_time(p))

            priority = np.zeros(P, np.int32)
            imp_rank = np.full(P, _I32_MAX, np.int32)
            start_rank = np.zeros(P, np.int32)
            preempt_ok = np.zeros(P, bool)
            priority[:U] = prios  # computed with the preempt_plan screen above
            for r, j in enumerate(
                sorted(range(U), key=lambda j: mik(universe_pods[j]))
            ):
                imp_rank[j] = r
            starts = sorted({stime(p) for p in universe_pods})
            srank = {sv: i for i, sv in enumerate(starts)}
            for j, p in enumerate(universe_pods):
                start_rank[j] = srank[stime(p)]
                preempt_ok[j] = objcache.cached(
                    "replay_pel", p, lambda p=p: pod_eligible_to_preempt(p)
                )
            const["pods"].update(
                priority=priority,
                imp_rank=imp_rank,
                start_rank=start_rank,
                preempt_ok=preempt_ok,
                level=levels,
            )
            ev["name_rank"] = name_ranks
            ev["want"] = want
            if self._record_mode == "full":
                # Per-plugin reason-bit -> "resolvable by preemption"
                # tables (the traceable form of service._resolvable_mask:
                # a missing failure_unresolvable rule is conservatively
                # unresolvable, exactly like the host path).
                tables = []
                for sp in plugins:
                    if not sp.filter_enabled:
                        continue
                    w = int(getattr(sp.plugin, "reason_bit_width", 31))
                    if w > 10:
                        raise _Unsupported("preemption_bits_width")
                    rule = getattr(sp.plugin, "failure_unresolvable", None)
                    t = np.zeros(1 << w, bool)
                    if rule is not None:
                        for b in range(1, 1 << w):
                            t[b] = not rule(b)
                    tables.append(t)
                tw = max((len(t) for t in tables), default=1)
                resolv = np.zeros((max(len(tables), 1), tw), bool)
                for fi, t in enumerate(tables):
                    resolv[fi, : len(t)] = t
                const["resolv"] = resolv
        state0 = {
            "valid": valid0,
            "requested": feats.nodes.requested,
            "nonzero_requested": feats.nodes.nonzero_requested,
            "pod_count": feats.nodes.pod_count,
            "alive": alive0,
            "bound": bound0,
            "attempts": attempts0,
            "retry_at": retry0,
            "spread": spread.init_counts,
            "ip_cnt": ip_cnt0,
            "ip_eat": ip_eat0,
            "ip_vw": ip_vw0,
            "pass_count": np.asarray(svc._pass_count, np.int32),
        }
        if preempt_plan:
            # The nominees' per-node load by level (``_segment_body``'s
            # nm_keys): level l of a node sums the pods nominated to it
            # whose level is l or above.
            state0["nom_node"] = nom_node0
            held = np.nonzero(nom_node0 >= 0)[0]
            at = nom_node0[held]

            def by_level(rows_of: "np.ndarray | None", width: tuple, dtype):
                out = np.zeros((N, n_lvl) + width, dtype)
                for l in range(n_lvl):
                    m = levels[held] >= l
                    np.add.at(
                        out[:, l],
                        at[m],
                        1 if rows_of is None else rows_of[held[m]].astype(dtype),
                    )
                return out

            req = feats.pods.requests
            state0["nm_req"] = by_level(req, req.shape[1:], req.dtype)
            state0["nm_cnt"] = by_level(None, (), np.int32)
            if not search_local:
                sel = spread.pod_sel_match
                state0["nm_sel"] = by_level(sel, sel.shape[1:], spread.init_counts.dtype)
                state0["nm_qm"] = by_level(ipa.pod_term_match, (T,), np.int32)
                state0["nm_eat"] = by_level(ipa.pod_eat, (T,), np.int32)
                state0["nm_vw"] = by_level(ipa.pod_vw, (T,), np.int32)
        else:
            state0["nominated"] = np.zeros(P, bool)
        if volumes_plan:
            # The volume plugins' counted carries, from the bound
            # population as the encoder counted it (``carry_init`` picks
            # arrays, so the host tree serves).
            from ksim_tpu.engine.core import _aux_host

            const["pods"]["vol_reads"] = vt.pod_reads
            aux_host = _aux_host(feats.aux)[0]
            for sp in plugins:
                if hasattr(sp.plugin, "carry_rows"):
                    for leaf, arr in sp.plugin.carry_init(aux_host).items():
                        state0[f"vc.{sp.plugin.name}.{leaf}"] = arr
        if statics.sample:
            # The walk continues where the service's last attempt, on
            # either path, left it.
            state0["sample_start"] = np.asarray(
                svc._pnts_start.get(self._sched_name, 0), np.int32
            )
        # O(delta) evidence: fresh per-pod featurize rows this lower
        # actually built vs the window's event count (the lock-check
        # guard asserts steady-state proportionality; counters, not
        # timings, so it is CI-stable).
        log_entry = {
            "events": sum(len(b) for b in batches),
            "steps": m_steps,
            "universe": U,
            "rows_built": feat.pod_rows_built - rows0[0],
            "rows_reused": feat.pod_rows_reused - rows0[1],
            "rows_rebuilt": feat.pod_rows_rebuilt - rows0[2],
            "rows_copied": feat.pod_rows_copied - rows0[3],
            "node_builds": node_builds,
            "bound_records": bound_records,
            "bound_shared": bound_shared,
            "keys_built": self.content_keys_built - keys_built0,
            "screens": self.universe_screens - screens0,
            "cache_hit": use_cache,
            "tp": tp,
            "full_bytes_per_shard": int(full_bytes_shard),
            "queue_width": q,
            "steps_padded": K - m_steps,
            # Filled in when the segment commits:
            "pairs_evaluated": 0,
            "slots_run": 0,
            "writes_shared": 0,
            "writes_copied": 0,
            "preempt_searches": 0,
            "preempt_table_builds": 0,
            "preempt_filter_runs": 0,
            "preempt_candidates": 0,
            "preempt_victims": 0,
            "preempt_nominations": 0,
            "preempt_overflows": 0,
            "sampled_attempts": 0,
            "nodes_visited": 0,
            "nodes_scored": 0,
            "nodes_skipped": 0,
            "sampled_by_rank": 0,
            "sampled_by_rank_steps": 0,
            "walk_rows_built": walk_rows_built,
            "sampling_start": None,
            # What the volume encoding read and built (0s: no pod of the
            # universe reads a volume), and the device's sums.
            "volume_objects": (
                sum(len(v) for v in volume_kw.values()) if volumes_plan else 0
            ),
            "volume_classes": vt.n_classes,
            "volume_shared": vt.n_shared,
            "volume_attempts": 0,
            "volume_rejections": 0,
        }
        for key in ("volume_objects", "volume_classes", "volume_shared"):
            setattr(self, key, max(getattr(self, key), log_entry[key]))
        self.lower_log.append(log_entry)
        return _SegmentPlan(
            statics=statics,
            prog=prog,
            const=const,
            aux=feats.aux,
            ev=ev,
            state0=state0,
            universe_keys=universe_keys,
            universe_row_of=row_of,
            node_names=list(feats.nodes.names),
            n_steps=m_steps,
            pred_featurizes=pred_featurizes,
            initial_pass_count=int(svc._pass_count),
            step_live_slots=step_live_slots,
            step_live_names=step_live_names,
            step_node_event=step_node_event,
            node_tree=walk_tree,
            lower_epoch=lower_epoch,
            sort_keys=uni_sort,
            clean_pods=uni_clean,
            priority_of=priority_of,
            prio_gen=prio_gen,
            sched_names=sched_names,
            mesh=self._shard_mesh_obj,
            log_entry=log_entry,
            volume_objects=(
                tuple(len(v) for v in volume_kw.values()) if volumes_plan else None
            ),
        )

    @staticmethod
    def _check_interpod_locals(ipa, cnt, eat, vw, n_dom_pad: int) -> bool:
        """Verify the local accumulators re-derive the featurizer's own
        domain-aggregated carry init (numpy mirror of _derive_interpod) —
        the lowering-time guard against delta/aggregation skew."""
        node_dom = ipa.node_dom  # [N, TK]
        term_tk = ipa.term_tk  # [T]
        dom_t = ipa.dom_t
        expect = {"cnt": ipa.cnt_node, "ecnt": ipa.ecnt_node, "ew": ipa.ew_node}
        got = {}
        for name, arr in (("cnt", cnt), ("ecnt", eat), ("ew", vw)):
            acc = np.zeros_like(arr)
            for k in range(node_dom.shape[1]):
                ids = node_dom[:, k]
                safe = np.where(ids >= 0, ids, n_dom_pad)
                seg = np.zeros((n_dom_pad + 1, arr.shape[1]), arr.dtype)
                np.add.at(seg, safe, arr)
                derived = np.where(ids[:, None] >= 0, seg[safe], 0)
                acc = np.where((term_tk == k)[None, :], derived, acc)
            got[name] = acc
        total = np.sum(np.where(dom_t >= 0, cnt, 0), axis=0, dtype=np.int64)
        ok = all(np.array_equal(got[k], expect[k]) for k in expect) and np.array_equal(
            total.astype(np.int32), ipa.total
        )
        if not ok:
            logger.warning(
                "device replay: inter-pod local accumulators disagree with "
                "the featurizer's aggregation; falling back to per-pass"
            )
        return ok

    # -- dispatch + decode ---------------------------------------------------

    def _step_render_ctx(self, plan: "_SegmentPlan", k: int):
        """RenderCtx over step k's live node set (rebuilt only when a
        node event changed the set — the common segment reuses one)."""
        from ksim_tpu.engine.annotations import RenderCtx

        return RenderCtx(plan.step_live_names[k], plan.prog.plugins)

    def _render_step_annotations(
        self, plan: "_SegmentPlan", k: int, att, pulled, noms, ctx
    ) -> list[dict]:
        """record="full": the 13 result annotations for every attempt of
        step k, decoded from the streamed result tensors exactly as the
        per-pass path renders them — same renderer, node axis restricted
        to the step's live set (dead universe slots never existed for
        that pass), postfilter map from the on-device preemption
        outcome."""
        from ksim_tpu.engine.annotations import render_pod_results
        from ksim_tpu.engine.core import EngineResult
        from ksim_tpu.scheduler.preemption import DEFAULT_PREEMPTION, NOMINATED_MESSAGE

        slots = plan.step_live_slots[k]
        names = plan.step_live_names[k]
        pos_of = {int(s): i for i, s in enumerate(slots)}
        sel_k = np.asarray(pulled["sel"][k])[att]
        bits = np.asarray(pulled["bits"][k])[att][:, :, slots]
        raw = np.asarray(pulled["raw"][k])[att][:, :, slots]
        fin = np.asarray(pulled["final"][k])[att][:, :, slots]
        # percentageOfNodesToScore: an attempt that walked records the
        # nodes it visited only, as the per-pass path does.
        vis = walked = None
        if plan.statics.sample:
            walked = np.asarray(pulled["walked"][k])[att]
            vis = np.asarray(pulled["visited"][k])[att][:, slots]
        sel_sub = np.asarray(
            [pos_of.get(int(s), -1) if s >= 0 else -1 for s in sel_k], np.int64
        )
        plugins = plan.prog.plugins
        res = EngineResult(
            plugin_names=[sp.plugin.name for sp in plugins if sp.score_enabled],
            filter_plugin_names=[
                sp.plugin.name for sp in plugins if sp.filter_enabled
            ],
            reason_bits=bits,
            scores=raw,
            final_scores=fin,
            total=None,
            feasible=sel_sub >= 0,
            selected=sel_sub,
        )
        preempt = plan.statics.preempt
        out = []
        for i, qq in enumerate(att):
            postfilter = None
            if preempt and sel_sub[i] < 0:
                # _attempt_preemption's render_postfilter_result: every
                # live node gets an entry; the nominated one (if any)
                # names the plugin.
                postfilter = {nm: {} for nm in names}
                nsl = int(noms[k, qq])
                if nsl >= 0:
                    postfilter[plan.node_names[nsl]] = {
                        DEFAULT_PREEMPTION: NOMINATED_MESSAGE
                    }
            out.append(
                render_pod_results(
                    None, plugins, res, i, postfilter=postfilter, ctx=ctx,
                    visited=vis[i] if vis is not None and walked[i] else None,
                )
            )
        return out

    def _run(self, plan: "_SegmentPlan") -> "SegmentOutcome | str":  # ksimlint: worker-thread
        """Dispatch one lowered segment and decode its outputs.

        Returns the SegmentOutcome, or a DISCARD REASON string when
        post-dispatch validation rejects the results (store untouched
        either way).  Runs on the watchdog worker thread: it must not
        mutate driver state — ``try_segment`` applies all accounting on
        the main thread after a successful join."""
        if self._lane_faults is not None:
            # The lane's private plane fires here — inside the
            # watchdogged worker like the global plane — so a
            # lane-armed hang schedule is watchdog-bounded on the solo
            # path.  The check lives in _run, NOT _device_exec: the
            # fleet's group dispatch calls _device_exec directly after
            # gating every lane's plane on the coordinator thread, and
            # a second check here would double-count the leader's
            # schedule.
            self._lane_faults.check("replay.dispatch")
        pulled_state, pulled = self._device_exec(plan)
        with TRACE.span("replay.decode", segment=self._segment_seq):
            return self._decode_outputs(plan, pulled_state, pulled)

    def _device_exec(self, plan: "_SegmentPlan"):  # ksimlint: worker-thread
        """The device half of a dispatch: pack the buffers, execute the
        compiled segment program, pull the carried state + per-step
        outputs back to host numpy.  Worker thread; side-effect-free on
        the driver.  The dispatch goes through the process-wide
        compile-once gate (engine/compilecache.py): the first caller of
        a shape rung compiles, concurrent same-rung callers — other
        tenant jobs on the same bucketed shapes — wait and reuse."""
        FAULTS.check("replay.dispatch")
        with TRACE.span("replay.pack", segment=self._segment_seq):
            if plan.statics.tp > 1:
                # Round 17: committed NamedShardings on every input leaf
                # — GSPMD lays the node axis over the tp mesh and inserts
                # the per-step collectives; the scan carry stays sharded
                # on device end to end.  An explicit service mesh rides
                # on the plan; the env-knob mesh is built lazily HERE
                # (this is the watchdogged worker — jax.devices() may
                # initialize the backend, which must never happen on the
                # main thread).
                mesh = (
                    plan.mesh if plan.mesh is not None else _tp_mesh(plan.statics.tp)
                )
                const_dev, (ev_dev, state_dev) = _shard_plan_buffers(
                    plan, (plan.ev, plan.state0), mesh
                )
            else:
                mesh = None
                const_dev, (ev_dev, state_dev) = _pack_plan_buffers(
                    plan, (plan.ev, plan.state0)
                )
        # Mesh dispatches take the non-donating twin — donated
        # multi-device carries race on the virtual-device CPU backend
        # (the _DONATE_ARGNUMS note); the cache key's mesh component
        # keeps the two executables distinct.
        seg_fn = _segment_fn if mesh is None else _segment_fn_nodonate
        return _exec_and_pull(
            plan,
            lambda: COMPILE_CACHE.run(
                _compile_cache_key(
                    "solo", plan, (const_dev, ev_dev, state_dev), mesh=mesh
                ),
                lambda: seg_fn(
                    plan.statics, plan.prog, const_dev, ev_dev, state_dev
                ),
                owner=TRACE.scope_tags().get("job"),
                wait_s=self.watchdog_s if self.watchdog_s > 0 else 300.0,
                # The persistent layer (round 15): a warm restart loads
                # the serialized executable instead of re-compiling; None
                # when KSIM_AOT_CACHE is off/unset (and no KSIM_JOBS_DIR)
                # or the plan's identity is process-local.
                disk=_aot_disk_spec("solo", plan, (const_dev, ev_dev, state_dev)),
            ),
            segment=self._segment_seq,
        )

    def _decode_outputs(  # ksimlint: worker-thread
        self, plan: "_SegmentPlan", pulled_state, pulled
    ) -> "SegmentOutcome | str":
        """The host half of a dispatch: validate the featurize/overflow
        predictions and decode the pulled tensors into a SegmentOutcome
        (or a discard-reason string).  Runs on the watchdog worker for
        solo dispatches; the fleet calls it once per LANE on the main
        thread with that lane's slice of the stacked outputs — the
        decode only reads the (shared) plan, the lane's pulled arrays,
        and the lane's own service backoff table."""
        st = plan.statics
        eligible = np.asarray(pulled["eligible"])
        for k in range(plan.n_steps):
            if bool(eligible[k] > 0) != plan.pred_featurizes[k]:
                # The sync-schedule prediction missed (a create-free step
                # still had eligible pods, or every eligible pod vanished).
                # That only matters when the divergent sync schedules can
                # see DIFFERENT node sets: the slot sim is a pure function
                # of the live-node sequence, and a sync over an unchanged
                # set is a no-op.  Both schedules agree (and synced the
                # same steps) before this first mismatch; if no node event
                # happened after the last predicted sync, the node set is
                # frozen from there on, every later sync in EITHER
                # schedule is a no-op, and the shipped rank tensors are
                # provably identical — the window stays on-device.  (This
                # is what keeps static-universe trace streams, whose
                # create-free steps routinely carry eligible pods, at
                # zero fallbacks — docs/churn_floor.md.)  With a node
                # event past that sync the divergence is real: the
                # shipped rank tensors may assume the wrong slot history.
                # The store is untouched: discard and fall back.
                last_sync = max(
                    (j for j in range(k) if plan.pred_featurizes[j]), default=-1
                )
                if any(plan.step_node_event[last_sync + 1 : plan.n_steps]):
                    return "featurize_prediction"
                break
        if st.preempt and bool(
            np.any(np.asarray(pulled["overflow"])[: plan.n_steps])
        ):
            # A victim search met a node with more pods of a lower
            # priority than the victim table holds: the computed
            # outcomes past that point assumed a cut table.  Store
            # untouched — discard, fall back.
            return "preemption_overflow"

        sel = np.asarray(pulled["sel"])  # [K, Q]
        idx = np.asarray(pulled["idx"])  # [K, Q]
        P = len(plan.universe_keys)
        detailed = st.preempt or st.record == "full"
        noms = np.asarray(pulled["nom"]) if st.preempt else None
        vics = np.asarray(pulled["vic"]) if st.preempt else None
        clrs = np.asarray(pulled["clr"]) if st.preempt else None
        steps: list[StepOutcome] = []
        render_ctx = None
        for k in range(plan.n_steps):
            att = np.nonzero(idx[k] < P)[0]
            binds = []
            attempts = None
            if detailed:
                annos = [None] * len(att)
                if st.record == "full":
                    if render_ctx is None or plan.step_node_event[k]:
                        render_ctx = self._step_render_ctx(plan, k)
                    annos = self._render_step_annotations(
                        plan, k, att, pulled, noms, render_ctx
                    )
                attempts = []
                for i, qq in enumerate(att):
                    key = plan.universe_keys[int(idx[k, qq])]
                    ns, _, nm = key.partition("/")
                    sl = int(sel[k, qq])
                    node = plan.node_names[sl] if sl >= 0 else None
                    nominated = None
                    victims: list[tuple[str, str]] = []
                    gave_up, cleared = False, 0
                    if st.preempt:
                        nsl = int(noms[k, qq])
                        nominated = plan.node_names[nsl] if nsl >= 0 else None
                        gave_up = nsl == -2
                        if nsl >= 0:
                            cleared = int(clrs[k, qq])
                            for vr in vics[k, qq]:
                                if vr >= 0:
                                    vkey = plan.universe_keys[int(vr)]
                                    vns, _, vnm = vkey.partition("/")
                                    victims.append((vns, vnm))
                    attempts.append(
                        AttemptOutcome(
                            namespace=ns,
                            name=nm,
                            node=node,
                            nominated=nominated,
                            victims=victims,
                            anno=annos[i],
                            gave_up=gave_up,
                            cleared=cleared,
                        )
                    )
                    if node is not None:
                        binds.append((ns, nm, node))
            else:
                for qq in np.nonzero((idx[k] < P) & (sel[k] >= 0))[0]:
                    key = plan.universe_keys[int(idx[k, qq])]
                    ns, _, nm = key.partition("/")
                    binds.append((ns, nm, plan.node_names[int(sel[k, qq])]))
            steps.append(
                StepOutcome(
                    scheduled=int(pulled["scheduled"][k]),
                    unschedulable=int(pulled["unschedulable"][k]),
                    pending_after=int(pulled["pending_after"][k]),
                    eligible=int(eligible[k]),
                    slots_run=int(pulled["slots"][k]),
                    searches=int(pulled["searches"][k]) if st.preempt else 0,
                    candidates=int(pulled["cands"][k]) if st.preempt else 0,
                    table_builds=int(pulled["builds"][k]) if st.preempt else 0,
                    filter_runs=int(pulled["fruns"][k]) if st.preempt else 0,
                    sampled=int(pulled["walks"][k]) if st.sample else 0,
                    visited=int(pulled["nvis"][k]) if st.sample else 0,
                    scored=int(pulled["nsc"][k]) if st.sample else 0,
                    by_rank=int(pulled["walks"][k]) if st.sample == 2 else 0,
                    vol_attempts=int(pulled["vatt"][k]) if st.volumes else 0,
                    vol_rejections=int(pulled["vrej"][k]) if st.volumes else 0,
                    vol_attached=int(pulled["vheld"][k]) if st.volumes else None,
                    vol_headroom=_headroom(pulled["vhead"][k]) if st.volumes else None,
                    binds=binds,
                    attempts=attempts,
                )
            )
        alive = np.asarray(pulled_state["alive"])[:P]
        bound = np.asarray(pulled_state["bound"])[:P]
        attempts = np.asarray(pulled_state["attempts"])[:P]
        retry = np.asarray(pulled_state["retry_at"])[:P]
        # Per-pass keeps DEAD pods' backoff entries too (until its
        # shedding valve prunes them), so export every universe row's
        # entry — device flushes already updated the dead ones — and
        # fold in pre-segment entries for keys outside the universe,
        # applying the same flush cap the per-pass path would have
        # (one min against the FIRST flush step's pre-pass count is
        # exactly the running minimum over all of them).
        backoff = {
            plan.universe_keys[j]: (int(attempts[j]), int(retry[j]))
            for j in np.nonzero(attempts > 0)[0]
        }
        pcs = np.asarray(pulled["pass_count"]).reshape(-1)
        _max_backoff, flush_cap = _backoff_constants()
        flush = np.asarray(plan.ev["flush"])
        first_flush_pc = None
        for k in range(plan.n_steps):
            if bool(flush[k]):
                first_flush_pc = int(pcs[k - 1]) if k else plan.initial_pass_count
                break
        # Snapshot under the service's lock: _run executes on the
        # watchdog worker thread, and an ABANDONED worker (timeout)
        # races the main thread's per-pass fallback mutating _backoff —
        # an unlocked iteration could die mid-dict-resize.
        with self.service._backoff_lock:
            svc_backoff = dict(self.service._backoff)
        for key, (a, r) in svc_backoff.items():
            if key in backoff or key in plan.universe_row_of:
                continue
            if first_flush_pc is not None:
                r = min(r, first_flush_pc + min(a - 1, flush_cap))
            backoff[key] = (a, r)
        bound_view = {
            plan.universe_keys[j]: plan.node_names[int(bound[j])]
            for j in np.nonzero(alive & (bound >= 0))[0]
        }
        pending_view = {
            plan.universe_keys[j] for j in np.nonzero(alive & (bound < 0))[0]
        }
        nominated_view = None
        if st.preempt:
            nom_node = np.asarray(pulled_state["nom_node"])[:P]
            nominated_view = {
                plan.universe_keys[j]: plan.node_names[int(nom_node[j])]
                for j in np.nonzero(alive & (bound < 0) & (nom_node >= 0))[0]
            }
        return SegmentOutcome(
            steps=steps,
            pass_count=int(np.asarray(pulled_state["pass_count"]).ravel()[0]),
            backoff=backoff,
            bound_view=bound_view,
            pending_view=pending_view,
            nominated_view=nominated_view,
            sample_start=(
                int(np.asarray(pulled_state["sample_start"]).ravel()[0])
                if st.sample
                else None
            ),
            node_tree=plan.node_tree,
        )

    # -- reconcile -----------------------------------------------------------

    def advance_service_slots(self, step_nodes: "Sequence[Any]") -> None:
        """Roll the canonical featurizer's slot history forward one
        entry per reconciled step (``None`` = the pass never featurized:
        empty eligible queue — the per-pass path skips the sync too), so
        any LATER fallback pass sees exactly the node order the pure
        per-pass history would have produced.  Called AFTER the segment
        transaction commits: the featurizer has no rollback, so staging
        must never touch it."""
        feat = self._service_featurizer()
        for nodes in step_nodes:
            if nodes is not None:
                feat.advance_slots(nodes)

    def note_segment_committed(
        self,
        seg: SegmentOutcome,
        step_nodes: "Sequence[Any]",
        writes: "tuple[int, int]" = (0, 0),
    ) -> None:
        """Post-commit accounting of one device segment: its steps, and
        the pod x node pairs its passes evaluated — every attempt of a
        pass runs Filter and Score over the nodes live at that step
        (``step_nodes`` as for ``advance_service_slots``: ``None`` where
        the pass never ran, and then nothing was attempted) — and the
        queue slots the device's pod loops ran for them
        (``queue_slots_run``: the attempts, each step's rounded up to a
        whole block of the loop).  ``writes``: the reconcile's own count
        of the objects it replaced by re-wrap and through ``patch``."""
        self.device_steps += len(seg.steps)
        self.reconcile_writes_shared += writes[0]
        self.reconcile_writes_copied += writes[1]
        pairs = sum(
            (o.scheduled + o.unschedulable) * len(nodes)
            for o, nodes in zip(seg.steps, step_nodes)
            if nodes is not None
        )
        self.pairs_evaluated += pairs
        slots = sum(o.slots_run for o in seg.steps)
        self.queue_slots_run += slots
        preempt = {
            "preempt_searches": sum(o.searches for o in seg.steps),
            "preempt_table_builds": sum(o.table_builds for o in seg.steps),
            "preempt_filter_runs": sum(o.filter_runs for o in seg.steps),
            "preempt_candidates": sum(o.candidates for o in seg.steps),
            "preempt_victims": sum(
                len(a.victims) for o in seg.steps for a in o.attempts or ()
            ),
            "preempt_nominations": sum(
                1 for o in seg.steps for a in o.attempts or () if a.nominated
            ),
        }
        sampling = {
            "sampled_attempts": sum(o.sampled for o in seg.steps),
            "nodes_visited": sum(o.visited for o in seg.steps),
            "nodes_scored": sum(o.scored for o in seg.steps),
            "sampled_by_rank": sum(o.by_rank for o in seg.steps),
            # Steps whose walks went by their walk tensor.
            "sampled_by_rank_steps": sum(o.by_rank > 0 for o in seg.steps),
        }
        volumes = {
            "volume_attempts": sum(o.vol_attempts for o in seg.steps),
            "volume_rejections": sum(o.vol_rejections for o in seg.steps),
        }
        for key, n in (preempt | sampling | volumes).items():
            setattr(self, key, getattr(self, key) + n)
        if seg.steps and seg.steps[-1].vol_attached is not None:
            self.volume_attached = seg.steps[-1].vol_attached
            self.volume_headroom_min = seg.steps[-1].vol_headroom
        plan = self._last_plan  # None on a fleet follower: it lowered nothing
        if plan is not None and plan.log_entry is not None:
            plan.log_entry["pairs_evaluated"] = pairs
            plan.log_entry["slots_run"] = slots
            plan.log_entry["writes_shared"], plan.log_entry["writes_copied"] = writes
            plan.log_entry.update(preempt | sampling | volumes)
            plan.log_entry["nodes_skipped"] = (
                sampling["nodes_visited"] - sampling["nodes_scored"]
            )
            plan.log_entry["sampling_start"] = seg.sample_start

    def verify_segment(self, seg: SegmentOutcome) -> None:
        """Verify the staged store converged to the device's view of the
        cluster.  Runs INSIDE the segment transaction: a mismatch raises
        ReplayParityError and the transaction rolls every staged write
        back — loud, but no longer store-poisoning."""
        store_bound = {
            _pod_key(p): p["spec"]["nodeName"]
            for p in self.store.pods_with_node()
        }
        store_pending = {
            _pod_key(p) for p in self.store.pods_without_node()
        }
        if store_bound != seg.bound_view or store_pending != seg.pending_view:
            extra = set(store_bound) ^ set(seg.bound_view)
            raise ReplayParityError(
                "device-resident replay diverged from the store after "
                f"reconcile: {len(extra)} pod(s) differ (e.g. "
                f"{sorted(extra)[:3]}); bound {len(store_bound)} vs "
                f"{len(seg.bound_view)}, pending {len(store_pending)} vs "
                f"{len(seg.pending_view)}"
            )
        plan = self._last_plan
        if plan is not None and plan.volume_objects is not None:
            # The volume objects the verdicts were read from are the ones
            # the staged store holds: the window's creates all landed and
            # nothing else came or went.
            have = tuple(
                len(self.store.list(kind, copy_objs=False)) for _arg, kind in _VOLUME_ARGS
            )
            if have != plan.volume_objects:
                raise ReplayParityError(
                    "device-resident replay diverged from the store after "
                    f"reconcile: volume objects {have} in the store, "
                    f"{plan.volume_objects} lowered"
                )
        if seg.nominated_view is not None:
            # Nominations that stand: a name the store still holds for a
            # node that is gone counts on neither side.
            live = {name_of(n) for n in self.store.list("nodes", copy_objs=False)}
            store_nominated = {}
            for p in self.store.pods_without_node():
                nn = p.get("status", {}).get("nominatedNodeName")
                if nn and nn in live:
                    store_nominated[_pod_key(p)] = nn
            if store_nominated != seg.nominated_view:
                odd = {
                    k
                    for k in set(store_nominated) | set(seg.nominated_view)
                    if store_nominated.get(k) != seg.nominated_view.get(k)
                }
                raise ReplayParityError(
                    "device-resident replay diverged from the store after "
                    f"reconcile: {len(odd)} nomination(s) differ (e.g. "
                    f"{sorted(odd)[:3]})"
                )

    def sync_service(self, seg: SegmentOutcome) -> None:
        """Sync service bookkeeping (pass counter, backoff table) to the
        committed device outcome — post-commit only, like every other
        non-store effect of a segment."""
        svc = self.service
        svc._pass_count = seg.pass_count
        with svc._backoff_lock:
            svc._backoff = dict(seg.backoff)
        if seg.sample_start is not None:
            # A per-pass step after this segment continues the walk.
            svc._pnts_start[self._sched_name] = seg.sample_start
        if seg.node_tree is not None:
            # A copy: fleet lanes decode one shared plan.
            svc._node_tree = seg.node_tree.copy()
        # A committed segment proves the whole device->store pipeline is
        # healthy: reset the reconcile side of the breaker window.
        self._consecutive_reconcile_faults = 0
        self._advance_cache(seg)

    def _advance_cache(self, seg: SegmentOutcome) -> None:
        """Roll the lowered-universe cache forward to the committed
        segment's end state: the lowered universe filtered to the pods
        the device left alive (``verify_segment`` — which ran inside the
        just-committed transaction — proved that view byte-identical to
        the store).  Refuses and invalidates if the store epoch moved
        since the lowering read it: an out-of-band write interleaved
        with the dispatch, and the cache must not paper over it."""
        plan = self._last_plan
        cache = self._cache
        if plan is None:
            cache.invalidate("no_plan")
            return
        if self.store.mutation_epoch != plan.lower_epoch:
            cache.invalidate("epoch_raced")
            return
        surv = set(seg.bound_view) | set(seg.pending_view)
        keep = [j for j, k in enumerate(plan.universe_keys) if k in surv]
        cache.keys = [plan.universe_keys[j] for j in keep]
        cache.sort_keys = [plan.sort_keys[j] for j in keep]
        cache.clean_pods = [plan.clean_pods[j] for j in keep]
        cache.priority_of = plan.priority_of
        cache.prio_gen = plan.prio_gen
        cache.sched_names = plan.sched_names
        cache.epoch = plan.lower_epoch
        cache.valid = True

    def note_reconcile_fault(self) -> None:
        """Account one rolled-back segment reconcile (the runner's
        atomic-commit fallback).  Consecutive rollbacks trip the same
        sticky breaker as device failures: a persistently failing
        reconcile would otherwise pay a full lowering + dispatch +
        rollback for every remaining step with no containment.  The
        lowered-universe cache and the speculative prefix are STRICTLY
        flushed: the rolled-back window's head step is about to re-run
        per-pass, mutating state the incremental bookkeeping does not
        track."""
        self._reject("reconcile_fault")
        self._flush_incremental("rollback")
        self._consecutive_reconcile_faults += 1
        if (
            not self.breaker_tripped
            and self._consecutive_reconcile_faults >= self.breaker_threshold
        ):
            self.breaker_tripped = True
            self._breaker_schedule_retry()
            TRACE.event(
                "replay.breaker_open",
                cause="reconcile_fault",
                consecutive=self._consecutive_reconcile_faults,
            )
            logger.error(
                "device replay circuit breaker TRIPPED after %d consecutive "
                "segment-reconcile rollbacks (threshold %d); remaining steps "
                "run on the per-pass host path",
                self._consecutive_reconcile_faults, self.breaker_threshold,
            )


def _compile_cache_key(kind: str, plan: "_SegmentPlan", dev_tree, mesh=None) -> tuple:
    """The shape-rung identity of one dispatch, for the process-wide
    compile-once gate (engine/compilecache.py): the hashable program
    statics, the profile token (``_Program`` hashes on its plugin
    signature, so two tenants with equal scheduler configs share), the
    x64 mode, and the dtype/shape signature of every input leaf — the
    bucketed shape ladder makes these collide across same-rung tenants
    by construction.  ``kind`` separates the solo and lane-stacked
    (fleet) programs, which compile differently for identical inputs;
    ``mesh`` (round 19) adds the (dp, tp) device-grid shape — a fleet
    dispatch on a 2-D mesh commits different input shardings than a
    single-device one of identical avals, so they must not share a
    rung."""
    leaves = jax.tree_util.tree_leaves(dev_tree)
    sig = tuple((str(a.dtype), tuple(a.shape)) for a in leaves)
    grid = tuple(int(d) for d in mesh.devices.shape) if mesh is not None else None
    return (
        kind, plan.statics, plan.prog, bool(jax.config.jax_enable_x64), grid, sig,
    )


# ---------------------------------------------------------------------------
# Persistent executables (round 15): the compile cache's on-disk layer
# ---------------------------------------------------------------------------


def _aot_cache_dir() -> "str | None":
    """Where serialized executables live: ``KSIM_AOT_CACHE`` (a path, or
    ``off`` to disable), defaulting to ``$KSIM_JOBS_DIR/aot`` when the
    durable job plane is on — a restarted server then warms from the
    same directory its journal lives in.  None disables persistence
    (the jax compilation cache wired in ksim_tpu/util.py:15 still
    soft-warms XLA compiles underneath either way)."""
    raw = os.environ.get("KSIM_AOT_CACHE", "")
    if raw == "off":
        return None
    if raw:
        return raw
    jobs_dir = os.environ.get("KSIM_JOBS_DIR", "")
    return os.path.join(jobs_dir, "aot") if jobs_dir else None


def _aot_stable_token(obj) -> "str | None":
    """A CROSS-PROCESS-deterministic rendering of jit-cache key
    material, or None when the object's identity is process-local and
    must not be persisted.  The in-memory key (``_compile_cache_key``)
    leans on ``hash``/``repr`` semantics that do not survive a restart:
    frozenset iteration order moves with hash randomization, and
    ``_plugin_sig``'s ``("@id", id(plugin))`` fallback (engine/core.py)
    is a memory address.  This canonicalizer sorts unordered
    collections, recurses dataclasses field-by-field, admits only
    scalar leaves — and refuses (None) anything else, so a plan whose
    identity cannot be pinned simply skips the disk layer instead of
    colliding in it."""
    if obj is None or isinstance(obj, (bool, int, float, str, bytes)):
        return repr(obj)
    if isinstance(obj, tuple) and len(obj) == 2 and obj[0] == "@id":
        return None  # process-local plugin identity
    if isinstance(obj, (tuple, list)):
        parts = []
        for item in obj:
            t = _aot_stable_token(item)
            if t is None:
                return None
            parts.append(t)
        return "(" + ",".join(parts) + ")"
    if isinstance(obj, (frozenset, set)):
        parts = []
        for item in obj:
            t = _aot_stable_token(item)
            if t is None:
                return None
            parts.append(t)
        return "{" + ",".join(sorted(parts)) + "}"
    if is_dataclass(obj) and not isinstance(obj, type):
        parts = [type(obj).__name__]
        for f in fields(obj):
            t = _aot_stable_token(getattr(obj, f.name))
            if t is None:
                return None
            parts.append(f"{f.name}={t}")
        return "<" + ";".join(parts) + ">"
    return None


class _AotDiskSpec:
    """The compile cache's duck-typed disk handle for one solo segment
    dispatch (engine/compilecache.py ``run(disk=...)``): entry path +
    identity token + the three jax-touching callables.  Lives entirely
    on the watchdogged worker thread and holds no driver reference —
    kernel purity and the worker-thread write ban stay intact."""

    __slots__ = ("path", "token", "_plan", "_args")

    def __init__(self, path: str, token: str, plan, args) -> None:
        self.path = path
        self.token = token
        self._plan = plan
        self._args = args

    def load(self, blob: bytes):
        """Serialized entry -> a dispatchable callable.  ``jax.jit``
        over the exported call keeps repeat dispatches on the fast
        C++ path.  A matching startup-prewarmed executable
        (``prewarm_aot_cache``) is served instead of deserializing
        again — the crc re-check means a rewritten entry can never be
        handed a stale program."""
        from jax import export as jax_export

        with _PREWARM_LOCK:
            ent = _PREWARMED.get(self.path)
        if ent is not None and ent[0] == (zlib.crc32(blob) & 0xFFFFFFFF):
            return ent[1]
        return jax.jit(jax_export.deserialize(blob).call)

    def invoke(self, exec_obj):
        return exec_obj(*self._args)

    def serialize(self) -> "bytes | None":
        """Export the freshly compiled program for the next process.
        ``jax.export`` bakes the static argnums in at export time, so
        the deserialized call takes only the dynamic operands."""
        from jax import export as jax_export

        ex = jax_export.export(_segment_fn)(
            self._plan.statics, self._plan.prog, *self._args
        )
        return ex.serialize()


def _aot_disk_spec(kind: str, plan: "_SegmentPlan", args) -> "_AotDiskSpec | None":
    """Build the disk handle for one dispatch, or None when persistence
    is off or the plan's identity is not stable across processes
    (custom plugin objects without a static signature).  The token pins
    everything a stale entry could differ in: jax/jaxlib version,
    backend, program kind, statics, the profile signature, x64 mode and
    the full dtype/shape ladder rung."""
    base = _aot_cache_dir()
    if base is None:
        return None
    body = _aot_stable_token((
        kind,
        plan.statics,
        plan.prog._sig,
        bool(jax.config.jax_enable_x64),
        tuple(
            (str(a.dtype), tuple(a.shape))
            for a in jax.tree_util.tree_leaves(args)
        ),
    ))
    if body is None:
        return None
    # The device count joins the version/backend prefix (round 17): a
    # serialized executable bakes its input shardings in, so a warm
    # restart on a DIFFERENT topology (tp=8 entry, single-device host)
    # must be a counted miss/eviction, never a wrong load.  The mesh
    # width itself already rides in the statics (``tp``) inside body.
    token = f"{jax.__version__}|{jax.default_backend()}|d{jax.device_count()}|{body}"
    name = hashlib.sha256(token.encode()).hexdigest()[:32] + ".aot"
    return _AotDiskSpec(os.path.join(base, name), token, plan, args)


#: Executables deserialized at server startup (``prewarm_aot_cache``):
#: path -> (crc32 of the stored blob, jitted call).  Consulted by
#: ``_AotDiskSpec.load`` so the first tenant dispatch of an
#: already-learned shape rung skips the deserialize round.
_PREWARM_LOCK = threading.Lock()
_PREWARMED: dict = {}  # guarded-by: _PREWARM_LOCK


def prewarm_aot_cache(*, speculative: bool = False) -> int:  # ksimlint: thread-role(service-loop)
    """``KSIM_AOT_PREWARM=1`` (cmd/simulator.py): walk the on-disk AOT
    directory at server startup and deserialize every entry whose token
    matches THIS process's jax version / backend / device count —
    load-only, never cold-compiles.  A corrupt, foreign-version or
    foreign-topology entry is SKIPPED, not evicted: eviction authority
    stays with the dispatch path's token check, where the exact rung
    identity is known.  Returns the number prewarmed; the process-wide
    ``compile_cache`` counters carry it as ``disk_prewarmed``.

    ``speculative=True`` is the rescan-loop variant (AOT cache round 2,
    ``prewarm_rescan_loop``): only entries NOT already in the prewarm
    registry load — on-disk executables that appeared after startup are
    another fleet worker's compiles, including ladder rungs this
    process never dispatched, and loading them makes one worker's
    compile every worker's warm start.  Counted separately as
    ``disk_speculative``."""
    base = _aot_cache_dir()
    if base is None or not os.path.isdir(base):
        return 0
    from jax import export as jax_export

    prefix = f"{jax.__version__}|{jax.default_backend()}|d{jax.device_count()}|"
    n = 0
    for fname in sorted(os.listdir(base)):
        if not fname.endswith(".aot"):
            continue
        path = os.path.join(base, fname)
        if speculative:
            with _PREWARM_LOCK:
                if path in _PREWARMED:
                    continue
        ent = COMPILE_CACHE.read_disk_entry(path)
        if ent is None:
            continue
        token, blob = ent
        if not token.startswith(prefix):
            continue
        try:
            call = jax.jit(jax_export.deserialize(blob).call)
        except Exception:
            logger.warning("aot prewarm: skipping undeserializable %s", fname)
            continue
        with _PREWARM_LOCK:
            _PREWARMED[path] = (zlib.crc32(blob) & 0xFFFFFFFF, call)
        n += 1
    if n:
        if speculative:
            COMPILE_CACHE.note_speculative(n)
        else:
            COMPILE_CACHE.note_prewarmed(n)
    return n


def prewarm_rescan_loop(
    stop: "threading.Event | None" = None,
    interval_s: float = 30.0,
) -> None:  # ksimlint: thread-role(service-loop)
    """``KSIM_AOT_PREWARM=2`` (cmd/simulator.py): the startup prewarm
    pass, then a speculative rescan every 30 seconds — how stale another
    fleet worker's freshly stored executable can be before this process
    has it warm.  Runs forever on its daemon thread; ``stop`` and
    ``interval_s`` are the tests' handles."""
    interval_s = max(float(interval_s), 0.05)
    if stop is None:
        stop = threading.Event()
    try:
        prewarm_aot_cache()
    except RunCancelled:
        raise
    except Exception:
        logger.exception("aot prewarm startup pass failed")
    while not stop.wait(interval_s):
        try:
            prewarm_aot_cache(speculative=True)
        except RunCancelled:
            raise
        except Exception:
            # One failed rescan (e.g. the cache dir vanished mid-walk)
            # must not kill the loop — the next tick retries.
            logger.exception("aot speculative rescan failed")


def _plan_const_parts(plan: "_SegmentPlan"):
    """The plan's universe-constant trees in canonical order (node
    statics, pod rows, the optional preemption extras, the packed aux
    host tree) — the half of a dispatch's inputs that does not change
    from step to step, shared by the solo and fleet executors."""
    from ksim_tpu.engine.core import _aux_host

    aux_host, _axes = _aux_host(plan.aux)
    const = dict(plan.const)
    extra = {k: const[k] for k in ("resolv",) if k in const}
    return (const["node"], const["pods"], extra, aux_host)


def _const_dev_dict(parts_dev) -> dict:
    node_dev, pods_dev, extra_dev, aux_dev = parts_dev
    return {"node": node_dev, "pods": pods_dev, "aux": aux_dev, **extra_dev}


def _pack_plan_buffers(plan: "_SegmentPlan", transient):
    """The single-device transfer: the plan's constant buffers (node
    statics, pod rows, aux tables) and the caller's per-segment
    ``transient`` tree (event streams + the solo or lane-stacked carry)
    go up in ONE packed byte-buffer transfer.

    Returns ``(const_dev, transient_dev)``."""
    from ksim_tpu.engine.core import _pack_tree_to_device

    c_leaves, c_def = jax.tree_util.tree_flatten(_plan_const_parts(plan))
    t_leaves, t_def = jax.tree_util.tree_flatten(transient)
    packed = _pack_tree_to_device(c_leaves + t_leaves)
    n_c = len(c_leaves)
    const_dev = _const_dev_dict(jax.tree_util.tree_unflatten(c_def, packed[:n_c]))
    transient_dev = jax.tree_util.tree_unflatten(t_def, packed[n_c:])
    return const_dev, transient_dev


#: Lazily built (1, tp) node meshes for env-requested sharded dispatch,
#: memoized per width (mesh construction touches jax.devices()).
_TP_MESH_LOCK = threading.Lock()
_TP_MESHES: dict = {}  # guarded-by: _TP_MESH_LOCK


def _tp_mesh(tp: int):
    """The ``make_mesh(tp, dp=1)`` node mesh for ``KSIM_REPLAY_TP``
    dispatches.  Built on the watchdogged worker only (``jax.devices``
    initializes the backend — a hung backend becomes a watchdog
    timeout, never a main-thread hang); a host with fewer devices than
    the requested width raises DeviceUnavailableError, which feeds the
    ordinary device-error ladder and breaker instead of crashing the
    run — dead-device containment is identical to tp=1."""
    from ksim_tpu.engine import sharding

    with _TP_MESH_LOCK:
        mesh = _TP_MESHES.get(tp)
        if mesh is None:
            n = len(jax.devices())
            if n < tp:
                raise DeviceUnavailableError(
                    f"KSIM_REPLAY_TP={tp} but only {n} device(s) present"
                )
            mesh = sharding.make_mesh(tp, dp=1)
            _TP_MESHES[tp] = mesh
        return mesh


#: Carried cluster-state keys whose LEADING axis is the node axis [N] /
#: [N, R] — sharded over tp.  Everything else in state0 (the pod-axis
#: queue state and the pass counter) replicates: every chip needs the
#: whole pod table to score its node shard, and the pod rows are tiny
#: next to the node tensors (docs/scaling.md memory budgets).
_NODE_STATE_KEYS = frozenset(
    {"valid", "requested", "nonzero_requested", "pod_count",
     "spread", "ip_cnt", "ip_eat", "ip_vw",
     "nm_req", "nm_cnt", "nm_sel", "nm_qm", "nm_eat", "nm_vw"}
)


def _node_state_key(key: str) -> bool:
    """``_NODE_STATE_KEYS``, and the volume plugins' carries (``vc.
    <plugin>.<leaf>``: every one [N, X])."""
    return key in _NODE_STATE_KEYS or key.startswith("vc.")


def _plan_shard_specs(plan: "_SegmentPlan", transient, mesh):
    """NamedSharding spec trees mirroring ``_plan_const_parts(plan)``
    and the ``(ev, state0)`` transient tree, structure-identical so the
    flattened leaves zip with the data leaves:

    - node statics and node-leading aux tables ("node" in the AXES map,
      state/encoding.py) lay their leading axis over tp;
    - the per-step rank tensors (``rank``/``name_rank``, [K, N]) shard
      axis 1 — their leading axis is the step;
    - pod rows, event index lists, scalars and everything else
      replicate (the pod axis must stay whole: the sequential-commit
      scan's queue order is the parity contract).

    The aux specs iterate the dict pairs manually: ``_aux_host``'s axes
    tree carries ``None`` at leaf positions, which jax's tree_map would
    read as an empty subtree and raise on."""
    from ksim_tpu.engine import sharding

    def node_lead(a):
        return sharding.node_leading_sharding(mesh, np.ndim(a))

    def repl(a):
        return sharding.replicated_sharding(mesh, np.ndim(a))

    node_spec = {k: node_lead(v) for k, v in plan.const["node"].items()}
    pods_spec = {k: repl(v) for k, v in plan.const["pods"].items()}
    extra_spec = {
        k: repl(plan.const[k])
        for k in ("resolv",)
        if k in plan.const
    }
    from ksim_tpu.engine.core import _aux_host

    aux_host, aux_axes = _aux_host(plan.aux)
    aux_spec: dict = {}
    for k, v in aux_host.items():
        ax = aux_axes[k]
        if isinstance(v, dict):
            aux_spec[k] = {
                name: node_lead(arr)
                if ax.get(name) == "node" and np.ndim(arr)
                else repl(arr)
                for name, arr in v.items()
            }
        else:
            aux_spec[k] = jax.tree_util.tree_map(repl, v)
    ev, state0 = transient
    ev_spec = {
        k: sharding.node_axis_sharding(mesh, np.ndim(v), 1)
        if k in ("rank", "name_rank", "walk")
        else repl(v)
        for k, v in ev.items()
    }
    state_spec = {
        k: node_lead(v) if _node_state_key(k) else repl(v)
        for k, v in state0.items()
    }
    return (node_spec, pods_spec, extra_spec, aux_spec), (ev_spec, state_spec)


def _fleet_shard_specs(plan: "_SegmentPlan", transient, mesh):
    """Spec trees for a FLEET dispatch on a (dp, tp) mesh (round 19):
    constants and event streams take the solo tp specs — on a 2-D mesh
    a ``P(TP, ...)`` spec replicates over dp automatically, so every
    lane's row of chips reads the same node-sharded tables — while the
    lane-STACKED carry (``transient[1]``, leading axis S) lays lanes
    over dp and, for the ``_NODE_STATE_KEYS`` tensors, the node axis
    (axis 1) over tp.  Structure-identical to the transient tree so the
    flattened leaves zip, like ``_plan_shard_specs``."""
    from ksim_tpu.engine import sharding

    ev, st_s = transient
    c_spec, (ev_spec, _solo_state_spec) = _plan_shard_specs(
        plan, (ev, plan.state0), mesh
    )
    state_spec = {
        k: sharding.lane_node_sharding(mesh, np.ndim(v))
        if _node_state_key(k)
        else sharding.lane_sharding(mesh, np.ndim(v))
        for k, v in st_s.items()
    }
    return c_spec, (ev_spec, state_spec)


def _shard_plan_buffers(plan: "_SegmentPlan", transient, mesh, *, specs=None):
    """The mesh mirror of ``_pack_plan_buffers``: every leaf goes up
    COMMITTED to its NamedSharding in one batched ``jax.device_put`` —
    jit then respects the input layouts without in_shardings and GSPMD
    propagates them through the scan.  ``specs`` overrides the solo spec
    trees — the fleet passes ``_fleet_shard_specs`` so its lane-stacked
    carry lays lanes over dp and node axes over tp.

    Returns ``(const_dev, transient_dev)`` exactly like the packed
    path."""
    c_spec, t_spec = (
        specs if specs is not None else _plan_shard_specs(plan, transient, mesh)
    )
    c_leaves, c_def = jax.tree_util.tree_flatten(_plan_const_parts(plan))
    cs_leaves = jax.tree_util.tree_leaves(c_spec)
    t_leaves, t_def = jax.tree_util.tree_flatten(transient)
    ts_leaves = jax.tree_util.tree_leaves(t_spec)

    # Mirror _pack_tree_to_device's host canonicalization EXACTLY, so a
    # sharded dispatch sees the same avals as a packed one and shares
    # its compiled shape rung: np.ascontiguousarray promotes 0-d leaves
    # to (1,) (pass_count, scalar aux), and with x64 off 64-bit leaves
    # downcast by value.  A () -vs- (1,) skew here is not cosmetic — it
    # compiles a DIFFERENT program whose broadcasting silently corrupts
    # the scan (selected slots past N were observed under tp=4).
    x64 = bool(jax.config.jax_enable_x64)

    def _canon(a):
        if isinstance(a, np.ndarray):
            a = np.ascontiguousarray(a)
            if not x64 and a.dtype.itemsize == 8 and a.dtype.kind in "iuf":
                a = a.astype(np.dtype(f"{a.dtype.kind}4"))
        return a

    put = jax.device_put(
        [_canon(a) for a in c_leaves + t_leaves], cs_leaves + ts_leaves
    )
    n_c = len(c_leaves)
    const_dev = _const_dev_dict(jax.tree_util.tree_unflatten(c_def, put[:n_c]))
    transient_dev = jax.tree_util.tree_unflatten(t_def, put[n_c:])
    return const_dev, transient_dev


def _fleet_exec(plan: "_SegmentPlan", lanes_state0, mesh=None):
    """One vmapped dispatch advancing S independent trajectories by the
    plan's K steps (engine/fleet.py's group dispatch; runs on the fleet
    watchdog worker thread).

    ``lanes_state0`` is one carried-state tree per lane, all
    shape-identical to the plan's own: the scan carry stacks along a
    new leading lane axis while the universe constants AND the per-step
    event streams transfer once and broadcast across lanes
    (``_fleet_segment_fn`` closes over them — see its docstring for why
    broadcasting ``ev`` is load-bearing under vmap).  With ``mesh`` (a
    ``(dp, tp)`` fleet mesh), every leaf goes up COMMITTED to its
    NamedSharding via the sharded packer: lanes lay over ``dp``, node
    tensors over ``tp`` (round 19 — ``_fleet_shard_specs``).

    Returns ``(pulled_state, pulled)`` exactly as a solo dispatch would,
    with a leading lane axis on every leaf; the caller decodes each
    lane's slice through ``ReplayDriver._decode_outputs``.  Module
    function, side-effect-free on every driver."""
    FAULTS.check("replay.dispatch")
    with TRACE.span("replay.pack", lanes=len(lanes_state0)):
        st_s = jax.tree_util.tree_map(lambda *xs: np.stack(xs), *lanes_state0)
        if mesh is not None:
            const_dev, (ev_dev, state_dev) = _shard_plan_buffers(
                plan,
                (plan.ev, st_s),
                mesh,
                specs=_fleet_shard_specs(plan, (plan.ev, st_s), mesh),
            )
        else:
            const_dev, (ev_dev, state_dev) = _pack_plan_buffers(
                plan, (plan.ev, st_s)
            )
    # Mesh cohorts take the non-donating twin (_DONATE_ARGNUMS note:
    # donated multi-device carries race on virtual CPU devices).
    fleet_fn = _fleet_segment_fn if mesh is None else _fleet_segment_fn_nodonate
    return _exec_and_pull(
        plan,
        lambda: COMPILE_CACHE.run(
            _compile_cache_key(
                "fleet", plan, (const_dev, ev_dev, state_dev), mesh=mesh
            ),
            lambda: fleet_fn(
                plan.statics, plan.prog, const_dev, ev_dev, state_dev
            ),
            owner=TRACE.scope_tags().get("job"),
        ),
        lanes=len(lanes_state0),
    )


def _exec_and_pull(plan: "_SegmentPlan", launch, **tags):
    """The device half of a dispatch after packing, shared by the solo
    and fleet executors (worker thread): ``launch()`` starts the
    compiled program (compile-or-load inside ``COMPILE_CACHE.run``) and
    returns ``(final_state, outs)``; ``replay.exec`` ends when those are
    READY — the pull would block there anyway, so waiting inside the
    span costs nothing and makes its wall an upper bound of the
    dispatch's device time (``plan.exec_s``, summed into the job
    result's ``replay.device_wait_s`` with tracing on or off).
    ``replay.pull`` is then the device->host transfer alone."""
    from ksim_tpu.engine.core import _pull_tree_to_host

    t0 = time.perf_counter()
    with TRACE.span("replay.exec", **tags):
        final_state, outs = launch()
        jax.block_until_ready((final_state, outs))
    plan.exec_s = time.perf_counter() - t0
    with TRACE.span("replay.pull", **tags):
        return _pull_tree_to_host(
            (
                {
                    k: final_state[k]
                    for k in (
                        "alive", "bound", "attempts", "retry_at", "pass_count",
                        "nom_node", "sample_start",
                    )
                    if k in final_state
                },
                outs,
            )
        )


@dataclass
class _SegmentPlan:
    statics: _SegmentStatics
    prog: Any
    const: dict
    aux: dict
    ev: dict
    state0: dict
    universe_keys: list[str]
    universe_row_of: dict[str, int]
    node_names: list[str]
    n_steps: int  # REAL steps (the compiled K may be tail-padded longer)
    pred_featurizes: list[bool]
    initial_pass_count: int
    # Per-step live-node decode views (preemption / full-record only).
    step_live_slots: list = field(default_factory=list)
    step_live_names: list = field(default_factory=list)
    step_node_event: list = field(default_factory=list)
    # The service's node tree after the window's node events (sampling
    # services only): installed on the service when the segment commits.
    node_tree: Any = None
    # Lower-cache seed (ReplayDriver._advance_cache filters it to the
    # committed segment's survivors) + the store epoch the lowering read.
    lower_epoch: int = -1
    sort_keys: list = field(default_factory=list)
    clean_pods: list = field(default_factory=list)
    priority_of: Any = None
    prio_gen: int = 0
    sched_names: Any = None  # profile set the lowering screened against
    exec_s: float = 0.0  # wall of this dispatch's replay.exec (worker)
    log_entry: "dict | None" = None  # this lowering's lower_log entry
    # Round 17: the EXPLICIT service shard_mesh this plan was lowered
    # for (None for env-knob sharding — _device_exec builds that mesh
    # lazily on the worker — and for tp=1 plans).
    mesh: Any = None
    # PersistentVolumes / claims / StorageClasses the lowering read (store
    # and window), for ``verify_segment``; None: no volume state carried.
    volume_objects: "tuple[int, int, int] | None" = None


class _Unsupported(ReplayFallback):
    """Lowering found an op/object outside the tensor vocabulary — the
    replay-local spelling of errors.ReplayFallback (str(e) is the
    histogram reason, as before)."""

    def __init__(self, reason: str) -> None:
        super().__init__(reason)
