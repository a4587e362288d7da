"""Render engine results to the reference's Pod result annotations.

The recorded results ARE the product (SURVEY.md hard part 7): the reference
wraps every plugin, records per-node per-plugin outcomes into a result
store, and reflects them onto the scheduled Pod's annotations (reference
simulator/scheduler/plugin/resultstore/store.go:133-198 GetStoredResult,
simulator/scheduler/plugin/annotation/annotation.go:3-31 keys,
simulator/scheduler/storereflector/storereflector.go:148-167 history).

This module reconstructs the exact same annotation contract from the
batched EngineResult tensors:

- ``filter-result``: node -> plugin -> "passed" | reason message, with the
  upstream framework's early-exit semantics (a node rejected by filter k
  has no entries for filters > k — upstream RunFilterPlugins stops at the
  first failure).
- ``score-result``: node -> plugin -> raw score (feasible nodes only —
  upstream only scores nodes that passed all filters).
- ``finalscore-result``: node -> plugin -> normalized x weight
  (resultstore/store.go:461-507: AddScoreResult seeds final with
  raw x weight; NormalizeScore overwrites with normalized x weight).
- ``prefilter-result`` / ``prefilter-result-status`` / ``prescore-result``:
  per-plugin "success" for plugins whose upstream counterpart implements
  the extension point (our kernels fold Pre* work into the fused kernels,
  so the recorded status is always success; PreFilterResult node lists are
  always nil upstream for the default plugins -> "{}" here).
- ``reserve-result`` / ``prebind-result``: {"VolumeBinding": "success"}
  for scheduled pods when VolumeBinding is enabled at that point (the
  default profile's only Reserve/PreBind plugin; wrappedplugin.go:616-645
  Reserve, :670-697 PreBind); per-point profile disables drop it.
- ``permit-result`` / ``permit-result-timeout``: "{}" — the default
  profile has no Permit plugins.
- ``bind-result``: {"DefaultBinder": "success"} for scheduled pods.
- ``selected-node``: set only when the pod was scheduled (reference
  store.go AddSelectedNode is called at Reserve).

JSON is serialized with sorted keys and compact separators to byte-match
Go's json.Marshal of map[string]string.

How the text is assembled: the three per-node maps are written as JSON
text directly, never as nested dicts handed to ``json.dumps``.  Names go
through ``json.dumps`` once per atom (``RenderCtx``), so escaping stays
Go-compatible.  The filter map joins one shared row string per node (a
handful of distinct rows a pod).  The two score maps are one ``"".join``
each over a flat list of fragments — per feasible node its ``"node":``
prefix, then per plugin a constant separator and the value — where a
value's decimal text comes from the pass's integer -> text table
(``RenderCtx.int_text``), formatted once per distinct integer.  What does
not depend on the pod (prefilter / prescore statuses, the small
``{"VolumeBinding":"success"}``-style maps) is text held by the
``RenderCtx``.
"""

from __future__ import annotations

import json
from typing import Sequence

from ksim_tpu.engine.core import EngineResult, ScoredPlugin
from ksim_tpu.state.featurizer import FeaturizedSnapshot

PREFIX = "kube-scheduler-simulator.sigs.k8s.io/"

PRE_FILTER_STATUS_KEY = PREFIX + "prefilter-result-status"
PRE_FILTER_RESULT_KEY = PREFIX + "prefilter-result"
FILTER_RESULT_KEY = PREFIX + "filter-result"
POST_FILTER_RESULT_KEY = PREFIX + "postfilter-result"
PRE_SCORE_RESULT_KEY = PREFIX + "prescore-result"
SCORE_RESULT_KEY = PREFIX + "score-result"
FINAL_SCORE_RESULT_KEY = PREFIX + "finalscore-result"
RESERVE_RESULT_KEY = PREFIX + "reserve-result"
PERMIT_RESULT_KEY = PREFIX + "permit-result"
PERMIT_TIMEOUT_RESULT_KEY = PREFIX + "permit-result-timeout"
PRE_BIND_RESULT_KEY = PREFIX + "prebind-result"
BIND_RESULT_KEY = PREFIX + "bind-result"
SELECTED_NODE_KEY = PREFIX + "selected-node"
RESULT_HISTORY_KEY = PREFIX + "result-history"

ALL_RESULT_KEYS = (
    PRE_FILTER_STATUS_KEY,
    PRE_FILTER_RESULT_KEY,
    FILTER_RESULT_KEY,
    POST_FILTER_RESULT_KEY,
    PRE_SCORE_RESULT_KEY,
    SCORE_RESULT_KEY,
    FINAL_SCORE_RESULT_KEY,
    RESERVE_RESULT_KEY,
    PERMIT_RESULT_KEY,
    PERMIT_TIMEOUT_RESULT_KEY,
    PRE_BIND_RESULT_KEY,
    BIND_RESULT_KEY,
    SELECTED_NODE_KEY,
)

PASSED_FILTER_MESSAGE = "passed"  # resultstore PassedFilterMessage
SUCCESS_MESSAGE = "success"  # resultstore SuccessMessage
POST_FILTER_NOMINATED_MESSAGE = "preemption victim"

# Upstream extension points implemented by each kernel's Go counterpart
# (v1.30 plugin sources); used to emit the per-plugin "success" statuses
# the wrapped plugins would have recorded.
UPSTREAM_PRE_FILTER = {
    "NodeResourcesFit",
    "NodeAffinity",
    "PodTopologySpread",
    "InterPodAffinity",
    "NodePorts",
    "VolumeBinding",
    "VolumeRestrictions",
    "NodeVolumeLimits",
}
UPSTREAM_PRE_SCORE = {
    "TaintToleration",
    "NodeAffinity",
    "PodTopologySpread",
    "InterPodAffinity",
    "NodeResourcesFit",
    "NodeResourcesBalancedAllocation",
    "VolumeBinding",
}


def _marshal(obj) -> str:
    """Byte-compatible with Go json.Marshal for string maps."""
    return json.dumps(obj, sort_keys=True, separators=(",", ":"))


class _IntText(dict):
    """integer -> its decimal text, exactly ``"%d" % v``, filled on a miss."""

    def __missing__(self, v) -> str:
        text = self[v] = "%d" % v
        return text


class RenderCtx:
    """Per-pass shared state for rendering many pods' results.  Build
    once per scheduling pass; everything a pod's text is assembled from
    that does not depend on the pod lives here:

    - the sorted node-name order, the pre-JSON'd node / plugin names and
      the constant fragments of a score row (``score_row``: the
      per-plugin separators ``{"p1":"`` / ``","p2":"`` and the closing
      ``"},``, a hole before each for the node prefix and the values);
    - ``int_text``, the pass's integer -> decimal text table: a score
      value is formatted the first time the pass meets it and looked up
      ever after, whatever its range (``values_formatted`` against
      ``values_written`` says how often);
    - the all-pass filter row and the cross-pod memo of failing rows;
    - the texts of the pod-independent maps (prefilter / prescore
      statuses, VolumeBinding's reserve / prebind maps) and a memo by
      content for the small per-pod ones.

    The maps are assembled as JSON text directly — at 10k pods x 5k nodes
    the per-entry dict building + json.dumps of the nested maps dominated
    the product path, and per-element number formatting dominated what
    was left."""

    def __init__(self, feats, plugins: Sequence[ScoredPlugin]) -> None:
        """``feats`` is a FeaturizedSnapshot, or a plain sequence of
        node names — the device-replay decode (engine/replay.py) renders
        per-step annotations over a step's live-node subset without a
        featurized snapshot in hand."""
        import numpy as np

        self.node_names = (
            list(feats) if isinstance(feats, (list, tuple)) else feats.nodes.names
        )
        self.filter_plugins = [sp for sp in plugins if sp.filter_enabled]
        self.score_plugins = [sp for sp in plugins if sp.score_enabled]
        names = self.node_names
        # json.dumps per atom keeps byte-compatibility with _marshal
        # (escaping, ensure_ascii) while the maps are joined by hand.
        self.node_json = [json.dumps(nm) for nm in names]
        order = sorted(range(len(names)), key=lambda i: names[i])
        self.rank = np.empty(len(names), dtype=np.int64)
        for r, i in enumerate(order):
            self.rank[i] = r
        fnames = [sp.plugin.name for sp in self.filter_plugins]
        self.fname_json = [json.dumps(n) for n in fnames]
        passed = json.dumps(PASSED_FILTER_MESSAGE)
        self.passed_row = "{" + ",".join(
            f"{k}:{passed}" for k in sorted(self.fname_json)
        ) + "}"
        # Inner score rows list plugin names sorted (Go map marshal order);
        # a column, so that [score_order, feasible] indexes [S, F] at once.
        sorder = sorted(range(len(self.score_plugins)),
                        key=lambda s: self.score_plugins[s].plugin.name)
        self.score_order = np.asarray(sorder, dtype=np.int64)[:, None]
        # '"node":' prefixes: an object array in node order (indexed by
        # the feasible set) and a list in key-sorted order (filter map).
        self.sorted_order_arr = np.asarray(order, dtype=np.int64)
        self.node_json_prefix_arr = np.array(
            [nj + ":" for nj in self.node_json], dtype=object
        )
        self.node_json_sorted_prefix = [self.node_json[i] + ":" for i in order]
        # One score row's fragments: the node prefix, then per plugin its
        # separator ('{"p1":"', '","p2":"', ...) and the value; the row
        # closes '"},'.  The holes (None) sit at the even positions and
        # are filled per pod.
        self.score_row: list = []
        for k, s in enumerate(sorder):
            name = json.dumps(self.score_plugins[s].plugin.name)
            self.score_row += [None, ("{" if k == 0 else '",') + name + ':"']
        self.score_row += [None, '"},']
        self.int_text = _IntText()
        #: Score values written by this ctx (both maps, every pod).
        self.values_written = 0
        # (fi, bits) -> rendered filter row JSON, shared across pods.
        self.fail_row_memo: dict[tuple[int, int], str] = {}
        self.prefilter_status_json = _marshal({
            sp.plugin.name: SUCCESS_MESSAGE
            for sp in self.filter_plugins
            if sp.plugin.name in UPSTREAM_PRE_FILTER
        })
        self.prescore_json = _marshal({
            sp.plugin.name: SUCCESS_MESSAGE
            for sp in self.score_plugins
            if sp.plugin.name in UPSTREAM_PRE_SCORE
        })
        # VolumeBinding is the default profile's only Reserve/PreBind
        # plugin; on a successful cycle upstream's wrappers record
        # "success" for it (wrappedplugin.go:616-645 Reserve, :670-697
        # PreBind).  Profiles can disable it at a single point
        # (ScoredPlugin.reserve/prebind_enabled).
        def volume_binding(flag: str) -> dict:
            return {
                sp.plugin.name: SUCCESS_MESSAGE
                for sp in plugins
                if sp.plugin.name == "VolumeBinding" and getattr(sp, flag, True)
            }

        self.reserve_map = volume_binding("reserve_enabled")
        self.prebind_map = volume_binding("prebind_enabled")
        self._map_text_memo: dict[tuple, str] = {}

    @property
    def values_formatted(self) -> int:
        """Integers this ctx formatted afresh: the size of its table."""
        return len(self.int_text)

    def map_text(self, obj: dict) -> str:
        """``_marshal`` of a flat string map, memoised by content: a pass
        writes the same two or three small maps for every pod."""
        if not obj:
            return "{}"
        key = tuple(obj.items())
        text = self._map_text_memo.get(key)
        if text is None:
            text = self._map_text_memo[key] = _marshal(obj)
        return text

    def score_map(self, node_prefixes: list, vals) -> str:
        """One score map as text: ``vals`` is [S, F] integers, plugin
        rows in ``score_order``, columns matching ``node_prefixes``.
        The values become text through ``int_text``; the map is one
        join over the flat list of every row's fragments."""
        S, F = vals.shape
        texts = list(map(self.int_text.__getitem__, vals.ravel().tolist()))
        width = len(self.score_row)
        flat = self.score_row * F
        flat[0::width] = node_prefixes
        for s in range(S):
            flat[2 * s + 2 :: width] = texts[s * F : (s + 1) * F]
        flat[-1] = '"}}'
        self.values_written += S * F
        return "{" + "".join(flat)

    def fail_row(self, fi: int, bits: int) -> str:
        """Row for a node whose first filter failure is plugin ``fi``
        with ``bits``: upstream RunFilterPlugins stops at the first
        failure, so plugins after ``fi`` are absent from the row."""
        key = (fi, bits)
        row = self.fail_row_memo.get(key)
        if row is None:
            msg = ", ".join(self.filter_plugins[fi].plugin.decode_reasons(bits))
            entries = {self.fname_json[i]: json.dumps(PASSED_FILTER_MESSAGE) for i in range(fi)}
            entries[self.fname_json[fi]] = json.dumps(msg)
            row = "{" + ",".join(f"{k}:{v}" for k, v in sorted(entries.items())) + "}"
            self.fail_row_memo[key] = row
        return row


def render_pod_results(
    feats: FeaturizedSnapshot,
    plugins: Sequence[ScoredPlugin],
    res: EngineResult,
    pi: int,
    *,
    postfilter: dict | None = None,
    permit: tuple[dict, dict] | None = None,
    bound: bool = True,
    reserve_extra: dict | None = None,
    prebind_extra: dict | None = None,
    bind_map: dict | None = None,
    ctx: "RenderCtx | None" = None,
    visited: "np.ndarray | None" = None,
) -> dict[str, str]:
    """The 13 result annotations for queue pod ``pi`` (all keys present,
    empty maps as "{}", mirroring GetStoredResult's unconditional adds).
    ``postfilter`` is the {node: {plugin: msg}} map recorded by the
    PostFilter wrapper when preemption ran (wrappedplugin.go:550-577);
    ``permit`` is ({plugin: status}, {plugin: timeout_str}) recorded by
    the Permit wrapper (wrappedplugin.go:582-611, store.go:549-560);
    ``bound=False`` marks a cycle that selected a node but never reached
    Bind (a Permit rejection): selected-node and reserve-result stay
    recorded — upstream wrote them at Reserve — while prebind/bind maps
    stay empty because those wrappers never ran.
    ``reserve_extra``/``prebind_extra`` merge out-of-tree Reserve and
    PreBind hook results into their maps; ``bind_map`` overrides the
    bind-result map when a custom binder handled (or failed) the bind
    (wrappedplugin.go:699-726 AddBindResult records under the actual
    binder's name).
    ``visited`` (percentageOfNodesToScore emulation, res.visited[pi]):
    only visited nodes appear in the recorded maps — upstream's
    NodeToStatusMap and score lists cover the nodes its sampled filter
    iteration actually touched.
    Pass a shared ``ctx`` when rendering many pods of one pass."""
    if res.reason_bits is None:
        raise ValueError("render_pod_results needs record='full' results")
    import numpy as np

    if ctx is None:
        ctx = RenderCtx(feats, plugins)
    node_names = ctx.node_names
    N = len(node_names)

    bits_pi = np.asarray(res.reason_bits[pi])[:, :N]  # [F, N]
    failed = bits_pi != 0
    any_fail = failed.any(axis=0)
    # First failing plugin per node (argmax finds the first True); with
    # no filter plugins every node is feasible and argmax is undefined.
    if bits_pi.shape[0]:
        first_fail = np.argmax(failed, axis=0)
    else:
        first_fail = np.zeros(N, dtype=np.int64)
    vis = None if visited is None else np.asarray(visited)[:N].astype(bool)
    if vis is None:
        feasible_nodes = np.nonzero(~any_fail)[0]
    else:
        feasible_nodes = np.nonzero(~any_fail & vis)[0]

    # filter-result: every (visited) node gets a row; rows are shared
    # strings.  Nodes share a handful of distinct rows (the all-pass row
    # or one per (first failing plugin, bits) pattern): classify every
    # node to a pattern code in bulk, render each distinct row once,
    # then join.
    so = ctx.sorted_order_arr
    ff_s = first_fail[so].astype(np.int64)
    bits_at_ff = bits_pi[ff_s, so].astype(np.int64)
    codes = np.where(any_fail[so], (ff_s << 32) | (bits_at_ff & 0xFFFFFFFF), -1)
    uniq, inv = np.unique(codes, return_inverse=True)
    row_strs = []
    for code in uniq:
        if code < 0:
            row_strs.append(ctx.passed_row)
        else:
            row_strs.append(ctx.fail_row(int(code >> 32), int(code & 0xFFFFFFFF)))
    prefixes = ctx.node_json_sorted_prefix
    if vis is None:
        parts = [p + row_strs[i] for p, i in zip(prefixes, inv.tolist())]
    else:
        parts = [
            p + row_strs[i]
            for p, i, v in zip(prefixes, inv.tolist(), vis[so].tolist())
            if v
        ]
    filter_json = "{" + ",".join(parts) + "}"

    # Upstream schedulePod returns right after filtering when exactly one
    # node is feasible (schedule_one.go findNodesThatFitPod early return):
    # PreScore/Score/NormalizeScore never run, so the reference records
    # empty score maps.  Zero feasible nodes goes to PostFilter, likewise
    # without scoring.
    ran_scoring = len(feasible_nodes) > 1
    score_json = "{}"
    final_json = "{}"
    if res.scores is not None and ctx.score_plugins and ran_scoring:
        # Feasible nodes in key-sorted order, shared by both maps.
        feas = feasible_nodes[np.argsort(ctx.rank[feasible_nodes], kind="stable")]
        at = (ctx.score_order, feas)
        node_pre = ctx.node_json_prefix_arr[feas].tolist()
        score_json = ctx.score_map(node_pre, np.asarray(res.scores[pi])[at])
        final_json = ctx.score_map(node_pre, np.asarray(res.final_scores[pi])[at])

    selected = int(res.selected[pi])
    reserve_map = ctx.reserve_map if selected >= 0 else {}
    if reserve_extra and selected >= 0:
        reserve_map = {**reserve_map, **reserve_extra}
    prebind_map = ctx.prebind_map if selected >= 0 and bound else {}
    if prebind_extra and selected >= 0:
        prebind_map = {**prebind_map, **prebind_extra}
    if bind_map is None:
        bind_map = {"DefaultBinder": SUCCESS_MESSAGE} if selected >= 0 and bound else {}
    elif selected < 0:
        bind_map = {}
    out = {
        PRE_FILTER_RESULT_KEY: "{}",
        PRE_FILTER_STATUS_KEY: ctx.prefilter_status_json,
        FILTER_RESULT_KEY: filter_json,
        POST_FILTER_RESULT_KEY: _marshal(postfilter) if postfilter else "{}",
        PRE_SCORE_RESULT_KEY: ctx.prescore_json if ran_scoring else "{}",
        SCORE_RESULT_KEY: score_json,
        FINAL_SCORE_RESULT_KEY: final_json,
        RESERVE_RESULT_KEY: ctx.map_text(reserve_map),
        PERMIT_RESULT_KEY: ctx.map_text(permit[0]) if permit else "{}",
        PERMIT_TIMEOUT_RESULT_KEY: ctx.map_text(permit[1]) if permit else "{}",
        PRE_BIND_RESULT_KEY: ctx.map_text(prebind_map),
        BIND_RESULT_KEY: ctx.map_text(bind_map),
    }
    if selected >= 0:
        out[SELECTED_NODE_KEY] = node_names[selected]
    return out


def update_result_history(annotations: dict[str, str], result: dict[str, str]) -> None:
    """Append ``result`` to the result-history annotation in place
    (reference storereflector.go:148-167 updateResultHistory)."""
    history = json.loads(annotations.get(RESULT_HISTORY_KEY, "[]"))
    history.append(result)
    annotations[RESULT_HISTORY_KEY] = _marshal(history)


def apply_results_to_pod(
    pod_annotations: dict[str, str], result: dict[str, str]
) -> dict[str, str]:
    """What storeAllResultToPodFunc does to one Pod's annotations: merge
    the result keys, then append the same set to the history."""
    pod_annotations.update(result)
    update_result_history(pod_annotations, result)
    return pod_annotations
