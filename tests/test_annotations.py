"""Result-annotation rendering: the reference's 13-key contract."""

import json

import pytest

from ksim_tpu.engine import Engine
from ksim_tpu.engine.annotations import (
    ALL_RESULT_KEYS,
    BIND_RESULT_KEY,
    FILTER_RESULT_KEY,
    FINAL_SCORE_RESULT_KEY,
    PRE_SCORE_RESULT_KEY,
    RESULT_HISTORY_KEY,
    SCORE_RESULT_KEY,
    SELECTED_NODE_KEY,
    apply_results_to_pod,
    render_pod_results,
    update_result_history,
)
from ksim_tpu.engine.profiles import default_plugins
from ksim_tpu.state.featurizer import Featurizer
from tests.helpers import make_node, make_pod


def run(nodes, bound, queue):
    feats = Featurizer().featurize(nodes, bound, queue_pods=queue)
    plugins = default_plugins(feats)
    eng = Engine(feats, plugins, record="full")
    return feats, plugins, eng.evaluate_batch()


def test_all_keys_present_and_json():
    nodes = [make_node("n1"), make_node("n2")]
    feats, plugins, res = run(nodes, [], [make_pod("p")])
    anno = render_pod_results(feats, plugins, res, 0)
    for key in ALL_RESULT_KEYS:
        assert key in anno, key
    for key, val in anno.items():
        if key != SELECTED_NODE_KEY:
            json.loads(val)  # every value is valid JSON


def test_filter_result_passed_and_early_exit():
    # n2 is cordoned: NodeUnschedulable (first filter) rejects, later
    # filters must have NO entry for n2 (upstream early exit).
    nodes = [make_node("n1"), make_node("n2", unschedulable=True)]
    feats, plugins, res = run(nodes, [], [make_pod("p", cpu="100m")])
    anno = render_pod_results(feats, plugins, res, 0)
    fm = json.loads(anno[FILTER_RESULT_KEY])
    assert fm["n1"]["NodeUnschedulable"] == "passed"
    assert fm["n1"]["NodeResourcesFit"] == "passed"
    assert fm["n2"]["NodeUnschedulable"] == "node(s) were unschedulable"
    assert list(fm["n2"].keys()) == ["NodeUnschedulable"]


def test_scores_only_on_feasible_nodes():
    nodes = [
        make_node("big", cpu="8"),
        make_node("big2", cpu="8"),
        make_node("tiny", cpu="100m"),
    ]
    feats, plugins, res = run(nodes, [], [make_pod("p", cpu="2")])
    anno = render_pod_results(feats, plugins, res, 0)
    sm = json.loads(anno[SCORE_RESULT_KEY])
    assert "big" in sm and "big2" in sm and "tiny" not in sm
    fm = json.loads(anno[FINAL_SCORE_RESULT_KEY])
    # finalscore = normalized x weight: TaintToleration weight 3, all nodes
    # taintless -> normalized 100 -> 300.
    assert fm["big"]["TaintToleration"] == "300"
    assert anno[SELECTED_NODE_KEY] == "big"
    assert json.loads(anno[BIND_RESULT_KEY]) == {"DefaultBinder": "success"}


def test_one_feasible_node_skips_scoring():
    # Upstream schedulePod early-returns when exactly one node passes
    # filtering: Score/PreScore never run, the recorded maps are empty,
    # but the pod is still bound to that node.
    nodes = [make_node("big", cpu="8"), make_node("tiny", cpu="100m")]
    feats, plugins, res = run(nodes, [], [make_pod("p", cpu="2")])
    anno = render_pod_results(feats, plugins, res, 0)
    assert json.loads(anno[SCORE_RESULT_KEY]) == {}
    assert json.loads(anno[FINAL_SCORE_RESULT_KEY]) == {}
    assert json.loads(anno[PRE_SCORE_RESULT_KEY]) == {}
    assert anno[SELECTED_NODE_KEY] == "big"
    assert json.loads(anno[BIND_RESULT_KEY]) == {"DefaultBinder": "success"}


def test_unschedulable_pod_has_no_selected_node():
    nodes = [make_node("tiny", cpu="100m")]
    feats, plugins, res = run(nodes, [], [make_pod("p", cpu="4")])
    anno = render_pod_results(feats, plugins, res, 0)
    assert SELECTED_NODE_KEY not in anno
    assert json.loads(anno[BIND_RESULT_KEY]) == {}
    assert json.loads(anno[SCORE_RESULT_KEY]) == {}


def test_multi_reason_message_joined():
    nodes = [make_node("small", cpu="1", memory="1Gi", pods=1)]
    bound = [make_pod("b", cpu="500m", memory="512Mi", node_name="small")]
    feats, plugins, res = run(nodes, bound, [make_pod("big", cpu="2", memory="2Gi")])
    anno = render_pod_results(feats, plugins, res, 0)
    fm = json.loads(anno[FILTER_RESULT_KEY])
    assert fm["small"]["NodeResourcesFit"] == (
        "Too many pods, Insufficient cpu, Insufficient memory"
    )


def test_result_history_appends():
    anno = {}
    update_result_history(anno, {"a": "1"})
    update_result_history(anno, {"b": "2"})
    assert json.loads(anno[RESULT_HISTORY_KEY]) == [{"a": "1"}, {"b": "2"}]


def test_apply_results_merges_and_records_history():
    nodes = [make_node("n1")]
    feats, plugins, res = run(nodes, [], [make_pod("p")])
    result = render_pod_results(feats, plugins, res, 0)
    pod_anno = {"user-key": "untouched"}
    apply_results_to_pod(pod_anno, result)
    assert pod_anno["user-key"] == "untouched"
    assert pod_anno[SELECTED_NODE_KEY] == "n1"
    hist = json.loads(pod_anno[RESULT_HISTORY_KEY])
    assert len(hist) == 1 and hist[0][SELECTED_NODE_KEY] == "n1"


def test_reserve_prebind_record_volume_binding():
    """Scheduled pods record VolumeBinding success at Reserve/PreBind
    (the default profile's only plugin at those points); a per-point
    profile disable drops it from that annotation only."""
    import json

    from ksim_tpu.scheduler.service import SchedulerService
    from ksim_tpu.state.cluster import ClusterStore
    from tests.helpers import make_node, make_pod
    from ksim_tpu.engine.annotations import (
        PRE_BIND_RESULT_KEY,
        RESERVE_RESULT_KEY,
    )

    store = ClusterStore()
    store.create("nodes", make_node("n0"))
    store.create("pods", make_pod("p0"))
    SchedulerService(store).schedule_pending()
    annos = store.get("pods", "p0")["metadata"]["annotations"]
    assert json.loads(annos[RESERVE_RESULT_KEY]) == {"VolumeBinding": "success"}
    assert json.loads(annos[PRE_BIND_RESULT_KEY]) == {"VolumeBinding": "success"}

    store2 = ClusterStore()
    store2.create("nodes", make_node("n0"))
    store2.create("pods", make_pod("p0"))
    cfg = {"profiles": [{
        "plugins": {"reserve": {"disabled": [{"name": "VolumeBinding"}]}},
    }]}
    SchedulerService(store2, config=cfg).schedule_pending()
    annos2 = store2.get("pods", "p0")["metadata"]["annotations"]
    assert json.loads(annos2[RESERVE_RESULT_KEY]) == {}
    assert json.loads(annos2[PRE_BIND_RESULT_KEY]) == {"VolumeBinding": "success"}


def test_reason_dtype_grows_with_taint_vocab():
    """TaintToleration's reason is a 1-based taint-vocabulary INDEX; the
    engine's result-tensor downcast must widen with the vocabulary so a
    large cluster's indices don't wrap (engine/core.py _result_dtypes)."""
    import numpy as np

    from ksim_tpu.engine.core import _Program, ScoredPlugin
    from ksim_tpu.engine.profiles import default_plugins
    from ksim_tpu.state.featurizer import Featurizer
    from tests.helpers import make_node, make_pod

    def eval_bits_dtype(n_taints):
        nodes = []
        for i in range(max(n_taints, 2)):
            n = make_node(f"n{i}")
            n["spec"]["taints"] = [
                {"key": f"k{i}", "value": "v", "effect": "NoSchedule"}
            ]
            nodes.append(n)
        feats = Featurizer().featurize(nodes, [], queue_pods=[make_pod("p")])
        plugins = default_plugins(feats)
        prog = _Program(tuple(plugins), "full")
        bits_dtype, _final = prog._result_dtypes()
        return np.dtype(bits_dtype)

    assert eval_bits_dtype(4) == np.int8
    assert eval_bits_dtype(200) == np.int16


# -- the assembled text against a plain rendering ------------------------------
#
# The renderer writes the three per-node maps as JSON text by hand (a
# number table and one join a map, engine/annotations.py); the plain
# rendering below builds nested dicts entry by entry and hands them to
# json.dumps.  Every annotation of every pod has to come out byte-equal.


def _plain_marshal(obj):
    return json.dumps(obj, sort_keys=True, separators=(",", ":"))


def _plain_render(names, plugins, res, pi, visited=None):
    from ksim_tpu.engine.annotations import (
        PERMIT_RESULT_KEY,
        PERMIT_TIMEOUT_RESULT_KEY,
        POST_FILTER_RESULT_KEY,
        PRE_BIND_RESULT_KEY,
        PRE_FILTER_RESULT_KEY,
        PRE_FILTER_STATUS_KEY,
        RESERVE_RESULT_KEY,
        UPSTREAM_PRE_FILTER,
        UPSTREAM_PRE_SCORE,
    )

    fps = [sp for sp in plugins if sp.filter_enabled]
    sps = [sp for sp in plugins if sp.score_enabled]
    filt, feasible = {}, []
    for n, name in enumerate(names):
        if visited is not None and not visited[n]:
            continue
        row, ok = {}, True
        for f, sp in enumerate(fps):
            bits = int(res.reason_bits[pi][f][n])
            if bits:
                row[sp.plugin.name] = ", ".join(sp.plugin.decode_reasons(bits))
                ok = False
                break
            row[sp.plugin.name] = "passed"
        filt[name] = row
        if ok:
            feasible.append(n)
    ran = len(feasible) > 1
    raw, fin = {}, {}
    if ran and sps:
        for n in feasible:
            raw[names[n]] = {
                sp.plugin.name: str(int(res.scores[pi][s][n])) for s, sp in enumerate(sps)
            }
            fin[names[n]] = {
                sp.plugin.name: str(int(res.final_scores[pi][s][n]))
                for s, sp in enumerate(sps)
            }
    sel = int(res.selected[pi])
    volume = (
        {"VolumeBinding": "success"}
        if sel >= 0 and any(sp.plugin.name == "VolumeBinding" for sp in plugins)
        else {}
    )
    out = {
        PRE_FILTER_RESULT_KEY: "{}",
        PRE_FILTER_STATUS_KEY: _plain_marshal(
            {sp.plugin.name: "success" for sp in fps if sp.plugin.name in UPSTREAM_PRE_FILTER}
        ),
        FILTER_RESULT_KEY: _plain_marshal(filt),
        POST_FILTER_RESULT_KEY: "{}",
        PRE_SCORE_RESULT_KEY: _plain_marshal(
            {sp.plugin.name: "success" for sp in sps if sp.plugin.name in UPSTREAM_PRE_SCORE}
            if ran
            else {}
        ),
        SCORE_RESULT_KEY: _plain_marshal(raw),
        FINAL_SCORE_RESULT_KEY: _plain_marshal(fin),
        RESERVE_RESULT_KEY: _plain_marshal(volume),
        PERMIT_RESULT_KEY: "{}",
        PERMIT_TIMEOUT_RESULT_KEY: "{}",
        PRE_BIND_RESULT_KEY: _plain_marshal(volume),
        BIND_RESULT_KEY: _plain_marshal({"DefaultBinder": "success"} if sel >= 0 else {}),
    }
    if sel >= 0:
        out[SELECTED_NODE_KEY] = names[sel]
    return out


class _Renamed:
    """A plugin under another name (its reason decoding kept)."""

    def __init__(self, plugin, name):
        self._plugin, self.name = plugin, name

    def decode_reasons(self, bits):
        return self._plugin.decode_reasons(bits)


def _random_results(seed=7, n_nodes=40, n_pods=24):
    """Engine results of a seeded random cluster, as writable arrays."""
    import dataclasses

    import numpy as np

    from tests.helpers import random_cluster

    nodes, pods = random_cluster(seed, n_nodes, n_pods, bound_fraction=0.0)
    feats, plugins, res = run(nodes, [], pods)
    names = list(feats.nodes.names)
    res = dataclasses.replace(
        res,
        reason_bits=np.array(res.reason_bits[: len(pods), :, : len(names)]),
        scores=np.array(res.scores[: len(pods), :, : len(names)], dtype=np.int64),
        final_scores=np.array(res.final_scores[: len(pods), :, : len(names)], dtype=np.int64),
        selected=np.array(res.selected[: len(pods)]),
    )
    return names, list(plugins), res, len(pods)


def _case_engine(names, plugins, res, rng):
    return names, plugins, res, None


def _case_negative_and_large_raw(names, plugins, res, rng):
    # Raw scores are whatever a plugin returns: negative, and far past
    # 2^20 (the table has one path for every range).
    res.scores[:] = rng.integers(-(2**40), 2**40, size=res.scores.shape)
    res.scores[0, 0, :] = -1
    res.scores[1, 0, :] = 2**20 + 1
    res.final_scores[:] = rng.integers(-5, 2**31, size=res.final_scores.shape)
    return names, plugins, res, None


def _case_value_first_seen_in_second_pod(names, plugins, res, rng):
    res.reason_bits[:] = 0
    res.scores[:], res.final_scores[:] = 7, 7
    res.scores[1, 2, 5] = 123456789
    return names, plugins, res, None


def _case_zero_feasible(names, plugins, res, rng):
    res.reason_bits[:, 0, :] = 1
    res.selected[:] = -1
    return names, plugins, res, None


def _case_one_feasible(names, plugins, res, rng):
    res.reason_bits[:, 0, :] = 1
    for pi in range(res.reason_bits.shape[0]):
        res.reason_bits[pi, :, pi % len(names)] = 0
        res.selected[pi] = pi % len(names)
    return names, plugins, res, None


def _case_visited_subset(names, plugins, res, rng):
    visited = rng.random((res.reason_bits.shape[0], len(names))) < 0.5
    visited[0] = False  # nothing visited: three empty maps
    visited[1] = True
    return names, plugins, res, visited


def _case_no_score_plugins(names, plugins, res, rng):
    import dataclasses

    plugins = [dataclasses.replace(sp, score_enabled=False) for sp in plugins]
    res.scores = res.scores[:, :0]
    res.final_scores = res.final_scores[:, :0]
    return names, plugins, res, None


def _case_names_that_need_escaping(names, plugins, res, rng):
    import dataclasses

    odd = ['no"de', "back\\slash", "nœud-é", "tab\tbed", "日本", "</script>", " "]
    names = [f"{odd[i % len(odd)]}-{i}" for i in range(len(names))]
    rng.shuffle(names)  # key-sorted order is no longer the node order
    plugins = [
        dataclasses.replace(sp, plugin=_Renamed(sp.plugin, f'{sp.plugin.name}"\\é{i % 3}'))
        for i, sp in enumerate(plugins)
    ]
    return names, plugins, res, None


_CASES = [
    _case_engine,
    _case_negative_and_large_raw,
    _case_value_first_seen_in_second_pod,
    _case_zero_feasible,
    _case_one_feasible,
    _case_visited_subset,
    _case_no_score_plugins,
    _case_names_that_need_escaping,
]



@pytest.mark.parametrize("case", _CASES, ids=lambda c: c.__name__[len("_case_"):])
def test_rendered_text_equals_plain_rendering(case):
    import numpy as np

    from ksim_tpu.engine.annotations import RenderCtx

    names, plugins, res, n_pods = _random_results()
    names, plugins, res, visited = case(names, plugins, res, np.random.default_rng(11))
    ctx = RenderCtx(names, plugins)
    written = distinct = 0
    seen: set[int] = set()
    for pi in range(n_pods):
        vis = None if visited is None else visited[pi]
        formatted_before = ctx.values_formatted
        got = render_pod_results(None, plugins, res, pi, ctx=ctx, visited=vis)
        want = _plain_render(names, plugins, res, pi, visited=vis)
        assert got == want, (case.__name__, pi)
        # The shared table and a throw-away one write the same bytes.
        assert render_pod_results(names, plugins, res, pi, visited=vis) == want
        values = [
            int(v)
            for key in (SCORE_RESULT_KEY, FINAL_SCORE_RESULT_KEY)
            for row in json.loads(got[key]).values()
            for v in row.values()
        ]
        written += len(values)
        new = set(values) - seen
        seen |= new
        # An integer is formatted when the pass meets it first, and only then.
        assert ctx.values_formatted - formatted_before == len(new), pi
    assert ctx.values_written == written
    assert ctx.values_formatted == len(seen)
    if case is _case_value_first_seen_in_second_pod:
        assert seen == {7, 123456789}
    if case in (_case_zero_feasible, _case_one_feasible, _case_no_score_plugins):
        assert written == 0 and ctx.values_formatted == 0


def test_a_new_render_ctx_starts_an_empty_table():
    """Two passes share nothing: the table belongs to the pass's ctx."""
    from ksim_tpu.engine.annotations import RenderCtx

    names, plugins, res, n_pods = _random_results()
    first = RenderCtx(names, plugins)
    a = [render_pod_results(None, plugins, res, pi, ctx=first) for pi in range(n_pods)]
    assert first.values_formatted > 0
    second = RenderCtx(names, plugins)
    assert second.values_formatted == 0 and second.values_written == 0
    assert not second.int_text and second.int_text is not first.int_text
    b = [render_pod_results(None, plugins, res, pi, ctx=second) for pi in range(n_pods)]
    assert a == b
    assert second.values_formatted == first.values_formatted
    assert second.values_written == first.values_written


def test_number_text_is_percent_d():
    from ksim_tpu.engine.annotations import RenderCtx

    table = RenderCtx(["n"], []).int_text
    for v in (0, -0, 7, -7, 100, 400, 2**20 + 1, -(2**31), 2**63 - 1, -(2**63)):
        assert table[v] == "%d" % v == str(v)
    assert len(table) == 9  # 0 and -0 are one integer


def test_render_counters_say_the_table_engages():
    """One pass of the service: `render_values` counts the score values
    its pods' maps hold, `render_values_formatted` the integers formatted
    afresh for them — never more than the distinct values, once a pass."""
    from ksim_tpu.scheduler.service import SchedulerService
    from ksim_tpu.state.cluster import ClusterStore
    from tests.helpers import random_cluster

    nodes, pods = random_cluster(3, 12, 10, bound_fraction=0.0)
    store = ClusterStore()
    for n in nodes:
        store.create("nodes", n)
    for p in pods:
        store.create("pods", p)
    svc = SchedulerService(store)
    svc.schedule_pending()
    snap = svc.metrics.snapshot()
    counters, timings = snap["counters"], snap["timings"]
    values = []
    for p in store.list("pods"):
        for attempt in json.loads(p["metadata"]["annotations"][RESULT_HISTORY_KEY]):
            for key in (SCORE_RESULT_KEY, FINAL_SCORE_RESULT_KEY):
                for row in json.loads(attempt[key]).values():
                    values += [int(v) for v in row.values()]
    assert len(values) > 100
    assert counters["render_values"] == len(values)
    runs = timings["render"]["count"]
    assert 0 < counters["render_values_formatted"] <= len(set(values)) * runs
    assert counters["render_values_formatted"] < len(values) / 4
    # A service that records selections only renders nothing.
    store2 = ClusterStore()
    for n in nodes:
        store2.create("nodes", n)
    for p in pods:
        store2.create("pods", p)
    svc2 = SchedulerService(store2, record="selection")
    svc2.schedule_pending()
    c2 = svc2.metrics.snapshot()["counters"]
    assert c2["render_values"] == 0 and c2["render_values_formatted"] == 0
