"""The batched scheduling cycle.

Replaces the reference's per-(pod, node, plugin) hot loop (SURVEY.md
section 3.3; reference simulator/scheduler/plugin/wrappedplugin.go:420-548)
with two compiled programs:

- ``evaluate_batch`` — all pods x all nodes x all plugins against a FIXED
  snapshot: filter reason-bit matrices, raw score matrices, final
  (normalized x weight) score matrices, in one vmap'ed pass.  This is the
  "batch evaluating" product capability and the throughput benchmark.
- ``schedule`` — the sequential-commit loop: ``lax.scan`` over the pod
  queue carrying node state (requested/pod-count tensors), so each pod
  sees earlier pods' placements exactly like the upstream scheduler's
  Reserve-phase cache commit (SURVEY.md section 7 hard part 2).

Selection follows upstream selectHost (max summed final score) except ties
are broken by lowest node index instead of randomly (upstream
schedule_one.go selectHost picks uniformly among the max scorers; a
deterministic choice keeps replays reproducible).  Unschedulable pods
(no feasible node) get selected index -1.

Every pod x node result the reference records is preserved (the recorded
results ARE the product — SURVEY.md hard part 7); ``record`` modes bound
result-tensor memory for the 10k x 5k configs.

Compiled-program reuse: the jitted programs live on ``_Program``, a small
static object keyed by (record mode, plugin static signatures).  Engines
built for re-featurized snapshots share programs whenever the signatures
and array shapes match — the analogue of NOT restarting the reference's
scheduler container when nothing about the profile changed
(scheduler.go:58-111).  The jit cache pins only the ``_Program`` (plugins
hold vocab-sized statics, never snapshot tensors), so dropping an Engine
frees its device arrays.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from functools import partial
from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ksim_tpu.engine.kernelreg import device_kernel
from ksim_tpu.obs import TRACE
from ksim_tpu.plugins.base import (
    FilterOutput,
    NodeStateView,
    PodBatch,
    PodView,
)
from ksim_tpu.state.featurizer import FeaturizedSnapshot


@dataclass(frozen=True)
class PluginExtender:
    """Before/After hooks around one plugin's extension points — the
    TPU-native form of the reference's PluginExtender surface
    (simulator/scheduler/plugin/wrappedplugin.go:47-171): hooks are
    jax-traceable callables over the BATCHED tensors, compiled into the
    engine programs rather than wrapped around per-(pod,node) calls.

    Device-side hooks (jax-traceable, compiled into the engine):

    - before_filter(state, pod, aux) -> (state, pod): rewrite inputs;
    - after_filter(state, pod, aux, out: FilterOutput) -> FilterOutput;
    - before_score(state, pod, aux) -> (state, pod);
    - after_score(state, pod, aux, scores) -> scores (pre-normalize);
    - before_normalize(state, pod, aux, raw, ok) -> raw;
      after_normalize(state, pod, aux, normalized, ok) -> normalized
      (the NormalizeScore extender pair, wrappedplugin.go:388-418;
      weight applies after).

    The reference's PreFilter/PreScore extenders have no separate hooks
    here by design: those upstream points precompute per-cycle state
    that this architecture folds into the featurizer and the fused
    filter/score kernels, so before_filter/before_score are their
    extension seams (they see the same batched inputs the kernels do).

    Host-side hooks (plain Python over pod JSON, run by the scheduler
    service around the corresponding host extension points — the
    reference's Permit/PreBind/Bind/PostBind/PostFilter extender
    interfaces, wrappedplugin.go:47-171).  ``before_*`` returning a
    non-None string is a non-success status: the original plugin hook is
    skipped and the message becomes the point's result (for post_bind the
    original is skipped silently, matching wrappedplugin.go:728-738).
    ``after_*`` receives the point's outcome and may replace it:

    - before_post_filter(pod) -> str | None;
      after_post_filter(pod, nominated, msg) -> (nominated, msg);
    - before_reserve(pod, node) -> str | None;
      after_reserve(pod, node, msg) -> str | None;
    - before_unreserve(pod, node) -> str | None (non-None skips the
      original unreserve, like BeforePostBind);
      after_unreserve(pod, node) -> None;
    - before_permit(pod, node) -> str | None;
      after_permit(pod, node, result) -> result (a PermitResult);
    - before_pre_bind(pod, node) -> str | None;
      after_pre_bind(pod, node, msg) -> str | None;
    - before_bind(pod, node) -> str | None;
      after_bind(pod, node, outcome) -> outcome;
    - before_post_bind(pod, node) -> str | None;
      after_post_bind(pod, node) -> None.

    Implement ``static_sig()`` for cross-instance program reuse; without
    it the engine keys the jit cache by extender identity (always safe).
    """

    before_filter: Any = None
    after_filter: Any = None
    before_score: Any = None
    after_score: Any = None
    before_normalize: Any = None
    after_normalize: Any = None
    before_post_filter: Any = None
    after_post_filter: Any = None
    before_reserve: Any = None
    after_reserve: Any = None
    before_unreserve: Any = None
    after_unreserve: Any = None
    before_permit: Any = None
    after_permit: Any = None
    before_pre_bind: Any = None
    after_pre_bind: Any = None
    before_bind: Any = None
    after_bind: Any = None
    before_post_bind: Any = None
    after_post_bind: Any = None

    def static_sig(self) -> tuple | None:
        return None


@dataclass(frozen=True)
class ScoredPlugin:
    """A plugin enabled in a profile, with its score weight."""

    plugin: Any
    weight: int = 1
    filter_enabled: bool = True
    score_enabled: bool = True
    extender: PluginExtender | None = None
    # Host-side recording hints (not part of the traced computation): is
    # the plugin active at the Reserve/Permit/PreBind/PostFilter/Bind/
    # PostBind points (profiles can disable single extension points; the
    # annotation renderer consults these for reserve-result/prebind-
    # result, and the scheduler service consults them before calling a
    # plugin's host-side ``permit(pod, node_name)`` / ``post_filter`` /
    # ``pre_bind`` / ``bind`` / ``post_bind`` hooks).
    reserve_enabled: bool = True
    prebind_enabled: bool = True
    permit_enabled: bool = True
    postfilter_enabled: bool = True
    bind_enabled: bool = True
    postbind_enabled: bool = True


@dataclass
class EngineResult:
    """Host-side results for a pod batch.

    Shapes: P pods (padded), N nodes (padded); slices [:num_pods,:num_nodes]
    are valid.  ``selected`` is -1 for unschedulable (or padding) pods.
    """

    plugin_names: list[str]
    filter_plugin_names: list[str]
    reason_bits: np.ndarray | None  # i32 [P, F, N], 0 == passed
    scores: np.ndarray | None  # i32 [P, S, N] raw plugin scores
    final_scores: np.ndarray | None  # i32 [P, S, N] normalized x weight
    total: np.ndarray | None  # i32 [P, N] summed final scores
    feasible: np.ndarray  # bool [P]
    selected: np.ndarray  # i32 [P]
    # percentageOfNodesToScore emulation (Engine(sampling_k=...)):
    # per-pod visited-node mask (upstream iterates nodes from a rotating
    # start index and stops after finding K feasible — only visited
    # nodes appear in recorded results) and the rotating index's value
    # after this batch (feeds the next pass).
    visited: np.ndarray | None = None  # bool [P, N]
    sampling_next_start: int | None = None


# lax.scan unroll factor for the sequential-commit loop (see
# _Program._schedule_fn), and the slots per trip of the segment
# program's pod loops (engine/replay.py).  A constant since PR 29: on
# the chip 8 and 16 bought nothing against 4 and round more slots up
# (PERF.md section 6, PR 28).
SCAN_UNROLL = 4


def _aux_host(aux: dict) -> tuple[dict, dict]:
    """FeaturizedSnapshot.aux -> (pytree of HOST arrays, leading-axis map).

    Dataclasses become dicts of their ndarray fields; host-only fields
    stay behind.  The axis map mirrors the array tree with "node"/"pod"/
    None leading-axis kinds (from each dataclass's AXES classvar) for
    sharding."""
    out = {}
    axes = {}
    for k, v in (aux or {}).items():
        if dataclasses.is_dataclass(v):
            declared = getattr(v, "AXES", {})
            out[k] = {
                f.name: getattr(v, f.name)
                for f in dataclasses.fields(v)
                if isinstance(getattr(v, f.name), np.ndarray)
            }
            axes[k] = {name: declared.get(name) for name in out[k]}
        else:
            out[k] = v
            axes[k] = jax.tree_util.tree_map(lambda _: None, v)
    return out, axes


# One jitted unpack program per packing signature (grouped dtypes/shapes
# are bucketed upstream, so churn replay sees only a handful).
_UNPACK_CACHE: dict[tuple, Any] = {}

# One jitted byte-pack program per output signature (the device->host
# mirror of _pack_tree_to_device).
_OUTPACK_CACHE: dict[tuple, Any] = {}


def _multi_device(a) -> bool:
    """True for a jax.Array laid out over more than one device.  The
    jitted byte-pack below must never see one: GSPMD partitions the
    bitcast+concatenate and inserts a cross-replica reduction, so every
    output byte comes back SUMMED over the mesh replicas (observed on
    the 8-device CPU mesh: selected values 4x on a dp=2 x tp=4 layout,
    -1 bytes wrapping to 0xFC).  Sharded results gather per-leaf."""
    s = getattr(a, "sharding", None)
    try:
        return s is not None and len(s.device_set) > 1
    except Exception:
        return False


def _owned_host(a) -> np.ndarray:
    """Pull ONE device array to host as an OWNED numpy array.

    ``np.asarray`` on a jax.Array is ZERO-COPY on the CPU backend where
    the layout allows it — single-device outputs view a ``memoryview``
    of the result buffer, and a replicated multi-device output views
    shard 0's buffer directly (sharded leaves gather, which copies).  A
    retained view is a time bomb once the producing buffer's memory can
    be recycled: with the segment carry DONATED (round 19) XLA reuses
    execution memory aggressively, and the fleet tp*dp replay was
    observed to decode garbage through exactly such views — committed
    counts diverged nondeterministically at 1200-event scale, and any
    host-sync instrumentation made the race vanish.  One explicit copy
    per leaf pins the decode to host-owned memory; host numpy inputs
    pass through untouched."""
    if isinstance(a, np.ndarray):
        return a
    h = np.asarray(a)
    if isinstance(h, np.ndarray) and not h.flags["OWNDATA"]:
        h = np.array(h)
    return h


def _pull_tree_to_host(tree):
    """Transfer a pytree of device arrays to host numpy with ONE
    device->host transfer: a jitted program bitcasts every leaf to bytes
    and concatenates them into a single uint8 buffer; the host splits and
    re-views.  The record="full" product path pulls 5 result tensors per
    pod chunk and each pull is a blocking device->host transfer, so
    collapsing them is the mirror of the input packing.
    Every returned leaf is host-OWNED (``_owned_host``): zero-copy
    views of device buffers must never escape the pull boundary."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    if len(leaves) < 2 or not all(
        hasattr(a, "dtype") and np.dtype(a.dtype) != object for a in leaves
    ) or any(_multi_device(a) for a in leaves):
        # Mirror _pack_tree_to_device's non-array fallback.
        return jax.tree_util.tree_unflatten(
            treedef, [_owned_host(a) for a in leaves]
        )
    sig = tuple((np.dtype(a.dtype).str, a.shape) for a in leaves)
    fn = _OUTPACK_CACHE.get(sig)
    if fn is None:

        def pack(*xs):
            chunks = []
            for x in xs:
                if x.dtype == jnp.bool_:
                    x = x.astype(jnp.uint8)
                if x.dtype != jnp.uint8:
                    # Every chunk must be uint8: concatenate would PROMOTE
                    # a stray int8 chunk and silently double the buffer.
                    x = jax.lax.bitcast_convert_type(x, jnp.uint8)
                chunks.append(x.reshape(-1))
            return jnp.concatenate(chunks) if len(chunks) > 1 else chunks[0]

        fn = jax.jit(pack)
        _OUTPACK_CACHE[sig] = fn
    # _owned_host: the split below RE-VIEWS buf, so buf itself must own
    # its memory or every decoded leaf aliases the device result buffer.
    buf = _owned_host(fn(*leaves))
    out = []
    off = 0
    for dtype_str, shape in sig:
        dt = np.dtype(dtype_str)
        n = int(np.prod(shape, dtype=np.int64))
        nbytes = n * dt.itemsize
        seg = buf[off : off + nbytes]
        if dt == np.bool_:
            arr = seg.astype(np.bool_)
        else:
            arr = seg.view(dt)
        out.append(arr.reshape(shape))
        off += nbytes
    return jax.tree_util.tree_unflatten(treedef, out)


def _pack_tree_to_device(tree):
    """Move a pytree of host arrays to device with ONE byte-buffer
    transfer plus one jitted unpack dispatch, instead of one device_put
    per leaf.

    The featurized snapshot is ~83 small arrays and every transfer
    pays a fixed per-transfer latency, so per-leaf device_put costs 83
    of them per pass and one-transfer-per-dtype still 4-6.  All
    ndarray leaves are viewed as bytes, concatenated into a single uint8
    buffer, transferred once, and sliced + bitcast back to their dtypes
    on device (little-endian on both host and TPU).  Non-ndarray leaves
    fall back to jnp.asarray."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    pack_idx = [
        i
        for i, a in enumerate(leaves)
        if isinstance(a, np.ndarray) and a.dtype != object
    ]
    if len(pack_idx) < 4:
        return jax.tree_util.tree_unflatten(
            treedef, [jnp.asarray(a) for a in leaves]
        )
    x64 = bool(jax.config.jax_enable_x64)
    chunks = []
    sig = []
    for i in pack_idx:
        a = np.ascontiguousarray(leaves[i])
        if not x64 and a.dtype.itemsize == 8 and a.dtype.kind in "iuf":
            # Mirror jnp.asarray's canonicalization: with x64 off, 64-bit
            # leaves downcast by VALUE (the f32 fast mode relies on it).
            a = a.astype(np.dtype(f"{a.dtype.kind}4"))
        chunks.append(a.view(np.uint8).ravel())
        sig.append((a.dtype.str, a.shape))
    buf = jnp.asarray(np.concatenate(chunks))
    sig = tuple(sig)
    fn = _UNPACK_CACHE.get(sig)
    if fn is None:

        def unpack(b):
            outs = []
            off = 0
            for dtype_str, shape in sig:
                dt = np.dtype(dtype_str)
                nbytes = int(np.prod(shape, dtype=np.int64)) * dt.itemsize
                seg = jax.lax.dynamic_slice_in_dim(b, off, nbytes)
                if dt == np.bool_:
                    arr = seg.astype(jnp.bool_)
                elif dt.itemsize == 1:
                    arr = jax.lax.bitcast_convert_type(seg, dt)
                else:
                    arr = jax.lax.bitcast_convert_type(
                        seg.reshape(-1, dt.itemsize), dt
                    )
                outs.append(arr.reshape(shape))
                off += nbytes
            return outs

        fn = jax.jit(unpack)
        _UNPACK_CACHE[sig] = fn
    unpacked = fn(buf)
    out = list(leaves)
    for pos, i in enumerate(pack_idx):
        out[i] = unpacked[pos]
    for i, a in enumerate(out):
        if i not in pack_idx and not isinstance(a, jnp.ndarray):
            out[i] = jnp.asarray(a)
    return jax.tree_util.tree_unflatten(treedef, out)


def _final_from_raw(
    plugin: Any,
    raw: jnp.ndarray,
    ok: jnp.ndarray,
    weight: int,
    state=None,
    pod=None,
    aux=None,
    kw=None,
    ext=None,
) -> jnp.ndarray:
    """normalize (if the plugin defines it) then apply weight — the
    reference's applyWeightOnScore (resultstore/store.go:504-507).
    Plugins declaring ``normalize_needs_ctx = True`` get the evaluation
    context (PodTopologySpread's normalize depends on the pod).  The
    extender's before/after_normalize hooks wrap the plugin's normalize
    (the reference's NormalizeScorePluginExtender,
    wrappedplugin.go:388-418): before may rewrite the raw scores, after
    the normalized ones — both jax-traceable, pre-weight."""
    if ext is not None and ext.before_normalize is not None:
        raw = ext.before_normalize(state, pod, aux, raw, ok)
    if hasattr(plugin, "normalize"):
        if getattr(plugin, "normalize_needs_ctx", False):
            raw = plugin.normalize(raw, ok, state=state, pod=pod, aux=aux, **(kw or {}))
        else:
            raw = plugin.normalize(raw, ok)
    if ext is not None and ext.after_normalize is not None:
        raw = ext.after_normalize(state, pod, aux, raw, ok)
    return raw * weight


def _plugin_sig(plugin: Any) -> tuple:
    """Hashable jit-cache key component for one plugin: its declared
    static_sig, or object identity for plugins that don't implement one
    (no cross-instance program reuse, but always safe)."""
    try:
        sig = plugin.static_sig()
    except (AttributeError, NotImplementedError):
        sig = None
    if sig is None:
        return ("@id", id(plugin))
    return tuple(sig)


@device_kernel()
def sample_visited(feasible, real, start, n_real, k):
    """Upstream's find-K-feasible iteration as tensor ops
    (schedule_one.go findNodesThatPassFilters + numFeasibleNodesToFind,
    idealized as the sequential visit order — upstream's parallel
    workers make the exact visited set racy; the deterministic
    sequential semantics is the reproducible contract).  The ONE
    definition of the walk: the per-pass scan and the device replay's
    pod loop both call it.

    ``feasible`` / ``real`` are bool masks over the node axis IN VISIT
    ORDER (the real nodes fill positions 0 .. n_real - 1), ``feasible``
    already confined to ``real``.  Nodes are visited from the rotating
    ``start`` (taken modulo ``n_real``) and the iteration stops once
    ``k`` feasible nodes are found, or every node was seen: a node is
    visited exactly when fewer than ``k`` feasible nodes come before it
    in visit order.  With ``c`` the running count of feasible nodes in
    index order and ``T`` its total, the count before node i in visit
    order is ``c[i-1] - c[start-1]`` at or after ``start``, else
    ``T - c[start-1] + c[i-1]`` — one prefix sum, no sort, and ``k`` an
    operand.

    Returns (visited, sample = feasible & visited, next start):
    nextStartNodeIndex advances by the nodes processed this cycle
    (feasible found + filtered-out visited = every visited node)."""
    n = feasible.shape[0]
    i = jnp.arange(n, dtype=jnp.int32)
    nr = jnp.maximum(n_real, 1).astype(jnp.int32)
    start = (start % nr).astype(jnp.int32)
    f = feasible.astype(jnp.int32)
    before = jnp.cumsum(f, dtype=jnp.int32) - f  # feasible nodes below i
    total = jnp.sum(f, dtype=jnp.int32)
    at_start = jnp.sum(jnp.where(i < start, f, 0), dtype=jnp.int32)
    ahead = jnp.where(i >= start, before - at_start, total - at_start + before)
    visited = real & (ahead < k)
    n_visited = jnp.sum(visited, dtype=jnp.int32)
    return visited, feasible & visited, (start + n_visited) % nr


@device_kernel()
def sample_visited_at(feasible, real, pos, start, n_real, k):
    """``sample_visited`` with the masks IN SLOT ORDER and the visit
    order as an operand: ``pos`` is each slot's place in the visit
    order, i32 over the node axis, the real slots a permutation of
    0 .. n_real - 1 (what it holds elsewhere is not read).  Same
    contract, same triple; ``feasible`` need not be confined to
    ``real``.

    In visit order the visited set is one cyclic interval from
    ``start``, so no mask is carried into visit order and back: a
    slot's distance from the start is ``(pos - start) mod n_real``, the
    walk stops behind the ``k``-th smallest distance among the feasible
    slots (after all ``n_real`` where fewer than ``k`` are feasible),
    and a slot is visited exactly when its distance is below that
    count.  The one thing the node axis is sorted for is that scalar: a
    single-operand sort read at ``k - 1``."""
    n = feasible.shape[0]
    nr = jnp.maximum(n_real, 1).astype(jnp.int32)
    start = (start % nr).astype(jnp.int32)
    away = (pos.astype(jnp.int32) - start) % nr
    none = jnp.iinfo(jnp.int32).max  # what an infeasible slot sorts as
    found = jnp.sort(jnp.where(feasible & real, away, none), stable=False)
    kth = found[jnp.clip(k - 1, 0, n - 1)]  # ``none``: fewer than k feasible
    n_visited = jnp.where(
        k <= 0, 0, jnp.where(kth == none, n_real, kth + 1)
    ).astype(jnp.int32)
    visited = real & (away < n_visited)
    return visited, feasible & visited, (start + n_visited) % nr


class _Program:
    """The static half of an Engine: plugin set + record mode, hashable by
    signature.  jax.jit keys its cache on this object (static argnum 0),
    so equal-signature programs share compiled code while the cache entry
    retains only vocab-sized plugin statics — never snapshot tensors."""

    def __init__(
        self,
        plugins: tuple[ScoredPlugin, ...],
        record: str,
        sampled: "bool | int" = False,
    ) -> None:
        self.plugins = plugins
        self.record = record
        # percentageOfNodesToScore emulation: find-K-feasible sampling in
        # the sequential scan (upstream numFeasibleNodesToFind,
        # schedule_one.go).  Only WHETHER the scan samples, and whether
        # in slot order (1) or by a visit-order operand (2), is static: K
        # is an operand of ``_schedule_sampled_fn``.
        self.sampled = int(sampled)
        self._sig = (
            record,
            # False / True as before the ordered walk (the AOT tokens of
            # the segment programs hold this signature), 2 for it.
            self.sampled if self.sampled == 2 else bool(self.sampled),
            tuple(
                (
                    _plugin_sig(sp.plugin),
                    sp.weight,
                    sp.filter_enabled,
                    sp.score_enabled,
                    _plugin_sig(sp.extender) if sp.extender is not None else None,
                )
                for sp in plugins
            ),
        )

    def __hash__(self) -> int:
        return hash(self._sig)

    def __eq__(self, other) -> bool:
        return isinstance(other, _Program) and self._sig == other._sig

    # -- shared per-pod evaluation -----------------------------------------

    def _eval_one(self, state: NodeStateView, pod: PodView, aux: dict, carries: dict):
        """One pod vs all nodes through every plugin.

        ``carries`` maps plugin name -> that plugin's scan-carried state
        (e.g. PodTopologySpread's per-selector per-node match counts);
        plugins without carry state never see the dict.
        """
        filter_ok, reason_bits = self._eval_filters(state, pod, aux, carries)
        raw_scores, final_scores, total = self._eval_scores(
            state, pod, aux, carries, filter_ok
        )
        return filter_ok, reason_bits, raw_scores, final_scores, total

    def _eval_filters(self, state: NodeStateView, pod: PodView, aux: dict, carries: dict):
        reason_bits = []
        filter_ok = state.valid
        if "node_mask" in aux:
            # Nodes a caller ruled out beforehand (``Engine(node_mask=)``:
            # the per-pass path's verdict with the nominated pods counted
            # in).  Scores normalize over what is left, as over any
            # feasible set.
            filter_ok = filter_ok & aux["node_mask"]
        for sp in self.plugins:
            if not sp.filter_enabled:
                continue
            kw = {"carry": carries[sp.plugin.name]} if sp.plugin.name in carries else {}
            ext = sp.extender
            f_state, f_pod = state, pod
            if ext is not None and ext.before_filter is not None:
                f_state, f_pod = ext.before_filter(f_state, f_pod, aux)
            out: FilterOutput = sp.plugin.filter(f_state, f_pod, aux, **kw)
            if ext is not None and ext.after_filter is not None:
                out = ext.after_filter(f_state, f_pod, aux, out)
            reason_bits.append(out.reason_bits)
            filter_ok = filter_ok & out.ok
        return filter_ok, reason_bits

    def _eval_scores(
        self, state: NodeStateView, pod: PodView, aux: dict, carries: dict, filter_ok
    ):
        """``filter_ok`` is the mask scoring/normalizing runs over — the
        full feasible set normally, the SAMPLED feasible set under
        percentageOfNodesToScore emulation (upstream normalizes over the
        nodes it actually scored)."""
        n = state.valid.shape[0]
        raw_scores = []
        final_scores = []
        total = jnp.zeros(n, dtype=jnp.int32)
        for sp in self.plugins:
            if not sp.score_enabled:
                continue
            kw = {"carry": carries[sp.plugin.name]} if sp.plugin.name in carries else {}
            ext = sp.extender
            s_state, s_pod = state, pod
            if ext is not None and ext.before_score is not None:
                s_state, s_pod = ext.before_score(s_state, s_pod, aux)
            raw = sp.plugin.score(s_state, s_pod, aux, ok=filter_ok, **kw)
            if ext is not None and ext.after_score is not None:
                raw = ext.after_score(s_state, s_pod, aux, raw)
            final = _final_from_raw(
                sp.plugin, raw, filter_ok, sp.weight, s_state, s_pod, aux, kw,
                ext=ext,
            )
            raw_scores.append(raw)
            final_scores.append(final)
            total = total + final.astype(jnp.int32)
        return raw_scores, final_scores, total

    def init_carries(self, aux: dict) -> dict:
        return {
            sp.plugin.name: sp.plugin.carry_init(aux)
            for sp in self.plugins
            if hasattr(sp.plugin, "carry_init")
        }

    def _commit_carries(self, carries: dict, pod: PodView, best, aux: dict) -> dict:
        out = dict(carries)
        for sp in self.plugins:
            if sp.plugin.name in carries and hasattr(sp.plugin, "carry_commit"):
                out[sp.plugin.name] = sp.plugin.carry_commit(
                    carries[sp.plugin.name], aux, pod, best
                )
        return out

    def _select(self, filter_ok: jnp.ndarray, total: jnp.ndarray) -> jnp.ndarray:
        """selectHost: index of the max-scoring feasible node, -1 when
        none is feasible (feasibility is fully encoded in the sign)."""
        feasible = jnp.any(filter_ok)
        masked = jnp.where(filter_ok, total, jnp.iinfo(jnp.int32).min)
        best = jnp.argmax(masked).astype(jnp.int32)
        return jnp.where(feasible, best, -1)

    def _result_dtypes(self):
        """Smallest SAFE dtypes for the recorded result tensors, decided
        statically from plugin declarations — the device->host transfer
        of [P,F,N]/[P,S,N] tensors is the record="full" bottleneck on a
        bandwidth-limited link, and bytes scale with dtype width.

        - reason bits: each filter plugin declares ``reason_bit_width``
          (low bits it can set); missing declaration means int32.
        - final scores: each score plugin declares ``final_score_bound``
          (max post-normalize value); final = bound x weight per plugin.
          Raw scores stay int32 (data-dependent magnitudes)."""
        widths = [
            getattr(sp.plugin, "reason_bit_width", 31)
            for sp in self.plugins
            if sp.filter_enabled
        ]
        maxw = max(widths, default=0)
        bits_dtype = (
            jnp.int8 if maxw <= 7 else jnp.int16 if maxw <= 15 else jnp.int32
        )
        fmax = 0
        for sp in self.plugins:
            if not sp.score_enabled:
                continue
            bound = getattr(sp.plugin, "final_score_bound", None)
            if bound is None:
                fmax = None
                break
            fmax = max(fmax, bound * max(sp.weight, 1))
        final_dtype = (
            jnp.int16 if fmax is not None and fmax < 2**15 else jnp.int32
        )
        return bits_dtype, final_dtype

    def _pod_outputs(self, pv, best, bits, raw, final, total) -> dict:
        # No separate feasible output: selected >= 0 iff (valid & any node
        # passed), so _to_result derives it — one fewer device->host pull
        # per chunk (each costs ~150ms over a high-latency link).
        out = dict(selected=jnp.where(pv, best, -1))
        n = total.shape[0]
        bits_dtype, final_dtype = self._result_dtypes()
        if self.record in ("full", "final"):
            out["total"] = total
            out["final"] = (
                jnp.stack(final).astype(final_dtype)
                if final
                else jnp.zeros((0, n), final_dtype)
            )
        if self.record == "full":
            out["bits"] = (
                jnp.stack(bits).astype(bits_dtype)
                if bits
                else jnp.zeros((0, n), bits_dtype)
            )
            out["raw"] = jnp.stack(raw) if raw else jnp.zeros((0, n), jnp.int32)
        return out

    # -- compiled entry points ----------------------------------------------

    @partial(jax.jit, static_argnums=0)
    @device_kernel(static=("self",))
    def _batch_fn(self, state, pods: PodBatch, aux: dict, carries: dict):
        def per_pod(pb: PodBatch):
            pod = PodView(
                requests=pb.requests,
                nonzero_requests=pb.nonzero_requests,
                tolerates_unschedulable=pb.tolerates_unschedulable,
                has_requests=pb.has_requests,
                index=pb.index,
            )
            ok, bits, raw, final, total = self._eval_one(state, pod, aux, carries)
            best = self._select(ok, total)
            return self._pod_outputs(pb.valid, best, bits, raw, final, total)

        return jax.vmap(per_pod)(pods)

    @partial(jax.jit, static_argnums=0)
    @device_kernel(static=("self",))
    def _schedule_sampled_fn(
        self, state, pods: PodBatch, aux: dict, carries: dict, start, n_real, k,
        order=None,
    ):
        """The sequential-commit scan with percentageOfNodesToScore
        emulation: filter everywhere (the mask is needed to FIND the
        K feasible), then score/normalize/select over the sampled
        feasible set only, with the rotating start index carried across
        pods exactly like upstream's sched.nextStartNodeIndex.

        ``order`` (``self.sampled`` 2; else None: slot order) is each
        slot's place in the visit order, i32 over the node axis, padding
        slots last: the walk then stays in slot order and finds where it
        stops by one sort of the node axis a pod
        (``sample_visited_at``)."""

        def body(carry, pb: PodBatch):
            node_state, plugin_carries, start = carry
            pod = PodView(
                requests=pb.requests,
                nonzero_requests=pb.nonzero_requests,
                tolerates_unschedulable=pb.tolerates_unschedulable,
                has_requests=pb.has_requests,
                index=pb.index,
            )
            ok, bits = self._eval_filters(node_state, pod, aux, plugin_carries)
            in_real = jnp.arange(ok.shape[0], dtype=jnp.int32) < n_real
            if self.sampled == 2:
                visited, sample, new_start = sample_visited_at(
                    ok, in_real, order, start, n_real, k
                )
            else:
                visited, sample, new_start = sample_visited(
                    ok & in_real, in_real, start, n_real, k
                )
            # Padding pods never ran a cycle upstream: no rotation.
            new_start = jnp.where(pb.valid, new_start, start)
            raw, final, total = self._eval_scores(
                node_state, pod, aux, plugin_carries, sample
            )
            best = jnp.where(pb.valid, self._select(sample, total), -1)
            node_state = node_state.commit(best, pb.requests, pb.nonzero_requests)
            plugin_carries = self._commit_carries(plugin_carries, pod, best, aux)
            out = self._pod_outputs(pb.valid, best, bits, raw, final, total)
            if self.record == "full":
                out["visited"] = visited
            return (node_state, plugin_carries, new_start), out

        (final_state, final_carries, final_start), out = jax.lax.scan(
            body, (state, carries, start), pods, unroll=SCAN_UNROLL
        )
        return final_state, final_carries, final_start, out

    @partial(jax.jit, static_argnums=0)
    @device_kernel(static=("self",))
    def _schedule_fn(self, state, pods: PodBatch, aux: dict, carries: dict):
        def body(carry, pb: PodBatch):
            node_state, plugin_carries = carry
            pod = PodView(
                requests=pb.requests,
                nonzero_requests=pb.nonzero_requests,
                tolerates_unschedulable=pb.tolerates_unschedulable,
                has_requests=pb.has_requests,
                index=pb.index,
            )
            ok, bits, raw, final, total = self._eval_one(node_state, pod, aux, plugin_carries)
            best = jnp.where(pb.valid, self._select(ok, total), -1)
            node_state = node_state.commit(best, pb.requests, pb.nonzero_requests)
            plugin_carries = self._commit_carries(plugin_carries, pod, best, aux)
            return (node_state, plugin_carries), self._pod_outputs(
                pb.valid, best, bits, raw, final, total
            )

        # Unrolling amortizes per-iteration loop overhead: each step's
        # compute is tiny ([N]-wide elementwise + small matmuls), so the
        # while-loop bookkeeping is a measurable fraction of scan time
        # (415ms -> 348ms at padded 8192x1024, unroll=4).  Compile time
        # grows with the factor; the persistent compile cache absorbs it.
        (final_state, final_carries), out = jax.lax.scan(
            body, (state, carries), pods, unroll=SCAN_UNROLL
        )
        return final_state, final_carries, out


class Engine:
    """Compiled filter/score programs for one profile + featurized snapshot.

    Building an Engine binds a snapshot's device arrays to a ``_Program``
    (the static plugin set + record mode); the heavy compilation caches on
    the program signature and array shapes, so rebuilding an Engine for a
    fresh same-shaped snapshot costs only the host->device transfer.
    """

    def __init__(
        self,
        feats: FeaturizedSnapshot,
        plugins: Sequence[ScoredPlugin],
        *,
        record: str = "full",  # full | final | selection
        sampling_k: int | None = None,
        sampling_order: "np.ndarray | None" = None,
        metrics=None,
        node_mask: "np.ndarray | None" = None,
    ) -> None:
        """``node_mask`` (bool over the padded node axis) rules nodes out
        before any filter runs, for every pod of the batch; the recorded
        reason bits stay the filters' own.

        ``sampling_k`` enables percentageOfNodesToScore emulation on
        the ``schedule`` path: each pod's cycle visits nodes from a
        rotating start index and stops after finding K feasible — only
        visited nodes are scored/recorded, exactly upstream's adaptive
        sampling (scan-only; batch evaluation has no visit order).
        ``sampling_order`` gives each real node slot's place in the
        visit order (a permutation of 0 .. count - 1; the service's node
        tree); None visits in slot order.

        ``metrics`` (a ``util.Metrics``; the scheduler service passes
        its own) receives the pass's device phases as timers
        ``engine_pack`` / ``engine_exec`` / ``engine_pull``, from the
        same clock readings as the ``engine.*`` spans."""
        if record not in ("full", "final", "selection"):
            raise ValueError(f"unknown record mode {record!r}")
        # Validate against the REAL node count, not the padded axis: a K
        # between count and padding would "find" padding rows that never
        # pass filters, silently scoring fewer nodes than asked.
        if sampling_k is not None and not (
            0 < sampling_k <= int(feats.nodes.count)
        ):
            raise ValueError(
                f"sampling_k {sampling_k} out of range: must be in "
                f"[1, {int(feats.nodes.count)}] (real node count; the "
                f"padded axis is {int(feats.nodes.valid.shape[0])})"
            )
        self._feats = feats
        self._sampling_k = sampling_k
        self._sampling_order = None
        if sampling_k is not None and sampling_order is not None:
            width = int(feats.nodes.valid.shape[0])
            order = np.arange(width, dtype=np.int32)
            order[: len(sampling_order)] = sampling_order
            self._sampling_order = jnp.asarray(order)
        self._prog = _Program(
            tuple(plugins),
            record,
            sampled=0 if sampling_k is None else 1 if self._sampling_order is None else 2,
        )
        n = feats.nodes
        p = feats.pods
        node_host = dict(
            allocatable=n.allocatable,
            allowed_pods=n.allowed_pods,
            valid=n.valid,
            unschedulable=n.unschedulable,
            requested=n.requested,
            nonzero_requested=n.nonzero_requested,
            pod_count=n.pod_count,
        )
        pod_host = dict(
            requests=p.requests,
            nonzero_requests=p.nonzero_requests,
            valid=p.valid,
            tolerates_unschedulable=p.tolerates_unschedulable,
            has_requests=p.has_requests,
            index=p.index,
        )
        self._metrics = metrics
        with TRACE.phase("engine.pack", metrics, "engine_pack"):
            aux_host, self._aux_axes = _aux_host(feats.aux)
            node_dev, pod_dev, self._aux = _pack_tree_to_device(
                (node_host, pod_host, aux_host)
            )
        if node_mask is not None:
            # Joins the aux tree after the pack: a key of its own, so a
            # pass without a mask runs the program it always ran.
            self._aux = dict(self._aux, node_mask=jnp.asarray(node_mask, bool))
            self._aux_axes = dict(self._aux_axes, node_mask="node")
        self._node_state = NodeStateView(**node_dev)
        self._pods = PodBatch(**pod_dev)

    @property
    def _plugins(self) -> tuple[ScoredPlugin, ...]:
        return self._prog.plugins

    @property
    def _record(self) -> str:
        return self._prog.record

    def shard(self, mesh) -> "Engine":
        """Lay the engine's arrays out over a device mesh: node axis over
        "tp", pod batch over "dp" (see engine/sharding.py).  GSPMD inserts
        the node-axis collectives (any/argmax reductions) over ICI.

        Note: the sequential ``schedule`` path wants replicated pod arrays
        (lax.scan consumes one row per step); ``evaluate_batch`` benefits
        from the dp sharding.  Shard for the path you will run.
        """
        from ksim_tpu.engine import sharding as shlib

        self._node_state = shlib.shard_node_state(self._node_state, mesh)
        self._pods = shlib.shard_pod_batch(self._pods, mesh)
        self._aux = shlib.shard_aux(self._aux, self._aux_axes, mesh)
        return self

    def batch_step(self, state, pods: PodBatch, aux: dict, carries: dict):
        """Pure jittable batch-evaluation step (un-jitted public form)."""
        return _Program._batch_fn.__wrapped__(self._prog, state, pods, aux, carries)

    @property
    def example_args(self):
        return (self._node_state, self._pods, self._aux, self._prog.init_carries(self._aux))

    def evaluate_batch_chunks(self, *, chunk: int | None = None):
        """Yield ``(start, device_out)`` per contiguous pod chunk — the
        streaming form of ``evaluate_batch``.  Each ``device_out`` is the
        device-resident result pytree for one pod chunk; callers decode
        or transfer it before the next iteration if they want bounded
        device memory (record="full" at 16k x 8k is ~9GB of result
        tensors — far more than it costs to recompute, so nothing is
        retained)."""
        if self._prog.sampled:
            raise ValueError(
                "percentageOfNodesToScore emulation is scan-only "
                "(batch evaluation has no sequential visit order)"
            )
        P = int(self._pods.valid.shape[0])
        if chunk is None:
            chunk = min(P, self._default_batch_chunk())
        carries = self._prog.init_carries(self._aux)
        for s in range(0, P, chunk):
            pods_c = jax.tree_util.tree_map(
                lambda x: x[s : s + chunk], self._pods
            )
            yield s, self._prog._batch_fn(self._node_state, pods_c, self._aux, carries)

    def evaluate_batch(self, *, chunk: int | None = None) -> EngineResult:
        """All pods x nodes against the fixed snapshot (no state commit).

        Pod-chunked like ``schedule`` so the recorded result tensors
        ([P, plugins, N] in record="full") never exceed one chunk's worth
        of device memory; chunks stream to host and concatenate."""
        chunks = [
            _pull_tree_to_host(out)
            for _s, out in self.evaluate_batch_chunks(chunk=chunk)
        ]
        merged = jax.tree_util.tree_map(
            lambda *xs: np.concatenate(xs, axis=0), *chunks
        )
        return self._to_result(merged)

    # Default pod-axis chunk for the sequential scan.  One device program
    # per chunk bounds both the compiled scan length and the live result
    # buffers (full [P,*,N] stacks at 16k x 8k exceed a v5e chip); the
    # carries thread through unchanged so chunking is semantically
    # invisible.
    SCHEDULE_CHUNK = 2048
    # Batch-evaluation chunk on CPU: the vmapped batch program
    # materializes [chunk, plugins, N] intermediates, and on CPU the pass
    # is memory-bandwidth-bound — chunks small enough to stay cache-warm
    # measure fastest (256: 15.9s vs 2048: 28.3s at 5000x1000 full-record;
    # docs/scaling.md "batch-vs-scan platform asymmetry").  TPU keeps the
    # large chunk: HBM bandwidth prefers big tiles and fewer chunks
    # mean fewer dispatches.
    BATCH_CHUNK_CPU = 256

    def _default_batch_chunk(self) -> int:
        if jax.default_backend() == "cpu":
            return self.BATCH_CHUNK_CPU
        return self.SCHEDULE_CHUNK

    def _default_schedule_chunk(self) -> int:
        if self._record == "selection" and jax.default_backend() != "cpu":
            # One dispatch for the whole pod axis: at 2048-pod chunks the
            # TPU scan pays six dispatch round-trips at the 10kx5k shape
            # (measured 2051ms -> 1405ms, 24.4 -> 35.6M pairs/s exact,
            # going single-dispatch).  Selection-mode outputs are
            # [P]-sized, so the per-chunk result-buffer bound that forces
            # chunking in the recording modes does not apply.  CPU keeps
            # the smaller chunk — its cache-resident working set wins
            # there (1056ms vs 1235ms at 5000x1000).
            return 1 << 30
        return self.SCHEDULE_CHUNK

    def schedule(
        self,
        *,
        chunk: int | None = None,
        pull_state: bool = True,
        sampling_start: int = 0,
    ) -> tuple[EngineResult, NodeStateView | None]:
        """Greedy sequential scheduling of the pod queue with capacity
        commit; pod order is queue order (upstream pops by priority —
        callers sort the queue before featurizing).

        The scan runs in ``chunk``-sized pod segments (host loop, one
        compiled program reused across segments); results are concatenated
        host-side.  ``pull_state=False`` skips the device->host transfer
        of the final node state (callers that only consume the per-pod
        results — the scheduler service — save ~7 blocking pulls per
        pass, which dominate wall-clock over a high-latency link).

        ``sampling_start`` (sampling_k engines only) is the rotating
        node index carried over from the previous pass (upstream's
        sched.nextStartNodeIndex); the result's ``sampling_next_start``
        feeds the next pass."""
        P = int(self._pods.valid.shape[0])
        if chunk is None:
            chunk = min(P, self._default_schedule_chunk())
        state, carries = self._node_state, self._prog.init_carries(self._aux)
        outs = []
        sampled = self._prog.sampled
        start = jnp.asarray(sampling_start, dtype=jnp.int32)
        k = jnp.asarray(self._sampling_k or 0, dtype=jnp.int32)
        n_real = jnp.asarray(int(self._feats.nodes.count), dtype=jnp.int32)
        for s in range(0, P, chunk):
            pods_c = jax.tree_util.tree_map(
                lambda x: x[s : s + chunk], self._pods
            )
            with TRACE.phase("engine.exec", self._metrics, "engine_exec"):
                if sampled:
                    state, carries, start, out = self._prog._schedule_sampled_fn(
                        state, pods_c, self._aux, carries, start, n_real, k,
                        self._sampling_order,
                    )
                else:
                    state, carries, out = self._prog._schedule_fn(
                        state, pods_c, self._aux, carries
                    )
                # The pull below blocks here anyway: waiting inside the
                # phase splits device wait from the transfer.
                jax.block_until_ready(out)
            with TRACE.phase("engine.pull", self._metrics, "engine_pull"):
                outs.append(_pull_tree_to_host(out))
        merged = jax.tree_util.tree_map(
            lambda *xs: np.concatenate(xs, axis=0), *outs
        )
        final_state = _pull_tree_to_host(state) if pull_state else None
        result = self._to_result(merged)
        if sampled:
            result.sampling_next_start = int(start)
        return result, final_state

    # -- decode -------------------------------------------------------------

    def _to_result(self, out: dict) -> EngineResult:
        filter_names = [
            sp.plugin.name for sp in self._plugins if sp.filter_enabled
        ]
        score_names = [sp.plugin.name for sp in self._plugins if sp.score_enabled]
        get = lambda k: np.asarray(out[k]) if k in out else None
        selected = np.asarray(out["selected"])
        return EngineResult(
            plugin_names=score_names,
            filter_plugin_names=filter_names,
            reason_bits=get("bits"),
            scores=get("raw"),
            final_scores=get("final"),
            total=get("total"),
            feasible=selected >= 0,
            selected=selected,
            visited=get("visited"),
        )
