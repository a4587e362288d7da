"""Incremental bound-pod aggregation (state/boundagg.py): a persistent
Featurizer replaying cluster mutations must be engine-equivalent to a
fresh featurization of the same snapshot.

The persistent path orders nodes by stable slot (first-seen, swap-remove)
while a fresh featurizer uses the caller's order, so outputs are compared
per NODE NAME.  ``selected`` is excluded: selection breaks score ties by
node index, which legitimately differs between orderings."""

from __future__ import annotations

import copy
import random

import numpy as np
import pytest

from ksim_tpu.engine import Engine
from ksim_tpu.engine.profiles import default_plugins
from ksim_tpu.state.boundagg import NodeSlots
from ksim_tpu.state.featurizer import Featurizer
from tests.helpers import make_node, make_pod


def test_node_slots_swap_remove():
    slots = NodeSlots()
    a, b, c = make_node("a"), make_node("b"), make_node("c")
    ordered, changed = slots.sync([a, b, c])
    assert [n["metadata"]["name"] for n in ordered] == ["a", "b", "c"]
    assert changed == {0, 1, 2}
    # Deleting "a" moves "c" (last) into slot 0.
    ordered, changed = slots.sync([b, c])
    assert [n["metadata"]["name"] for n in ordered] == ["c", "b"]
    assert 0 in changed and 2 in changed  # slot 0 re-occupied, slot 2 gone
    # Same set, same objects: nothing changes.
    ordered, changed = slots.sync([c, b])
    assert [n["metadata"]["name"] for n in ordered] == ["c", "b"]
    assert changed == set()
    # Replacing an object (same name) flags its slot.
    b2 = copy.deepcopy(b)
    ordered, changed = slots.sync([b2, c])
    assert changed == {1}


def _rand_pod(rng: random.Random, seq: int) -> dict:
    pod = make_pod(
        f"p{seq}",
        cpu=f"{rng.choice([100, 250, 500])}m",
        memory=f"{rng.choice([128, 256])}Mi",
    )
    labels = {"app": rng.choice(["web", "db", "cache"])}
    pod["metadata"]["labels"] = labels
    spec = pod["spec"]
    if rng.random() < 0.5:
        spec["topologySpreadConstraints"] = [{
            "maxSkew": 1,
            "topologyKey": "zone",
            "whenUnsatisfiable": rng.choice(["DoNotSchedule", "ScheduleAnyway"]),
            "labelSelector": {"matchLabels": {"app": labels["app"]}},
        }]
    if rng.random() < 0.5:
        term = {
            "topologyKey": rng.choice(["zone", "kubernetes.io/hostname"]),
            "labelSelector": {"matchLabels": {"app": rng.choice(["web", "db"])}},
        }
        aff = spec.setdefault("affinity", {})
        if rng.random() < 0.5:
            aff["podAntiAffinity"] = {
                "preferredDuringSchedulingIgnoredDuringExecution": [
                    {"weight": rng.randint(1, 50), "podAffinityTerm": term}
                ]
            }
        else:
            aff["podAffinity"] = {
                "requiredDuringSchedulingIgnoredDuringExecution": [term]
            }
    return pod


def _rand_node(rng: random.Random, seq: int) -> dict:
    node = make_node(f"n{seq}", cpu="4", memory="8Gi")
    node["metadata"]["labels"] = {
        "zone": rng.choice(["az-1", "az-2", "az-3"]),
        "kubernetes.io/hostname": f"n{seq}",
    }
    return node


def _engine_view(feats):
    eng = Engine(feats, default_plugins(feats), record="full")
    res = eng.evaluate_batch()
    return eng, res


def test_persistent_featurizer_matches_fresh_replay():
    rng = random.Random(7)
    persistent = Featurizer()
    nodes = [_rand_node(rng, i) for i in range(6)]
    pods: list[dict] = []
    node_seq, pod_seq = 6, 0

    for step in range(14):
        # Mutate like the store would: objects are replaced, not edited.
        for _ in range(rng.randint(1, 6)):
            r = rng.random()
            if r < 0.45 or not pods:
                pods.append(_rand_pod(rng, pod_seq))
                pod_seq += 1
            elif r < 0.65 and any(p["spec"].get("nodeName") for p in pods):
                bound = [i for i, p in enumerate(pods) if p["spec"].get("nodeName")]
                pods.pop(rng.choice(bound))
            elif r < 0.8:
                # Bind a pending pod (new object, like the store's patch).
                pending = [i for i, p in enumerate(pods) if not p["spec"].get("nodeName")]
                if pending:
                    i = rng.choice(pending)
                    p = copy.deepcopy(pods[i])
                    p["spec"]["nodeName"] = rng.choice(nodes)["metadata"]["name"]
                    pods[i] = p
            elif r < 0.93 and len(nodes) > 3:
                # Drain/replace a node; its pods go pending (new objects).
                gone = nodes.pop(rng.randrange(len(nodes)))
                gname = gone["metadata"]["name"]
                for i, p in enumerate(pods):
                    if p["spec"].get("nodeName") == gname:
                        p2 = copy.deepcopy(p)
                        p2["spec"].pop("nodeName", None)
                        pods[i] = p2
                nodes.append(_rand_node(rng, node_seq))
                node_seq += 1
            else:
                # Relabel a node in place on the axis (new object).
                i = rng.randrange(len(nodes))
                n2 = copy.deepcopy(nodes[i])
                n2["metadata"]["labels"]["zone"] = rng.choice(["az-1", "az-2", "az-3"])
                nodes[i] = n2

        queue = [p for p in pods if not p["spec"].get("nodeName")]
        if not queue:
            continue
        feats_p = persistent.featurize(list(nodes), list(pods), queue_pods=queue)
        feats_f = Featurizer().featurize(list(nodes), list(pods), queue_pods=queue)

        # Node-name alignment: permutation from fresh order to persistent.
        names_p = feats_p.nodes.names
        names_f = feats_f.nodes.names
        assert sorted(names_p) == sorted(names_f)
        perm = [names_p.index(nm) for nm in names_f]

        np.testing.assert_array_equal(
            feats_p.nodes.requested[perm], feats_f.nodes.requested[: len(perm)],
            err_msg=f"step {step}: requested diverged",
        )
        np.testing.assert_array_equal(
            feats_p.nodes.pod_count[perm], feats_f.nodes.pod_count[: len(perm)]
        )

        _, res_p = _engine_view(feats_p)
        _, res_f = _engine_view(feats_f)
        P = len(queue)
        np.testing.assert_array_equal(
            res_p.feasible[:P], res_f.feasible[:P],
            err_msg=f"step {step}: feasibility diverged",
        )
        np.testing.assert_array_equal(
            (res_p.reason_bits[:P][:, :, perm] != 0),
            (res_f.reason_bits[:P][:, :, : len(perm)] != 0),
            err_msg=f"step {step}: filter masks diverged",
        )
        np.testing.assert_array_equal(
            res_p.total[:P][:, perm], res_f.total[:P][:, : len(perm)],
            err_msg=f"step {step}: total scores diverged",
        )


# -- per-family equivalence over replayed churn streams ----------------------
#
# At every segment of a device-replayed stream the driver's PERSISTENT
# featurizer (row table, append-only vocabularies, delta aggregates) must
# produce what a fresh ``Featurizer()`` produces on the same (nodes,
# universe, bound) — equal in value, dtype and shape, up to the node-slot
# permutation and, for the families whose vocabularies persist (spread
# selectors; inter-pod contexts, terms and domains), a permutation of
# vocabulary ids with possibly inert extra entries.

_FAMILIES = (
    "nodes", "pods", "affinity", "taints", "spread", "interpod",
    "nodename", "nodeports", "imagelocality", "volumes",
)
# VolumeTensors fields whose MINOR axis is the node axis.
_NODE_MINOR = {"pv_node_ok", "pv_zone_ok", "pvc_cand_ok"}


def _late_selector_ops():
    """Node replacement throughout, and half way a batch of pods whose
    spread selector, affinity context, node selector, toleration and
    image the stream has not seen: every persistent vocabulary moves
    mid-stream."""
    from ksim_tpu.scenario.generate import churn_scenario
    from ksim_tpu.scenario.runner import Operation

    ops = list(churn_scenario(3, n_nodes=24, n_events=600, ops_per_step=40))
    mid = max(op.step for op in ops) // 2
    at = max(i for i, op in enumerate(ops) if op.step == mid) + 1
    late = []
    for i in range(6):
        pod = make_pod(
            f"late-{i}", cpu="100m", memory="64Mi", labels={"app": "late"},
            topology_spread_constraints=[{
                "maxSkew": 1,
                "topologyKey": "kubernetes.io/hostname",
                "whenUnsatisfiable": "ScheduleAnyway",
                "labelSelector": {"matchLabels": {"app": "late"}},
            }],
            affinity={"podAntiAffinity": {
                "preferredDuringSchedulingIgnoredDuringExecution": [{
                    "weight": 7,
                    "podAffinityTerm": {
                        "labelSelector": {"matchLabels": {"app": "late"}},
                        "topologyKey": "disktype",
                    },
                }],
            }},
        )
        pod["spec"]["nodeSelector"] = {"disktype": "ssd"}
        pod["spec"]["tolerations"] = [{"key": "late", "operator": "Exists"}]
        pod["spec"]["containers"][0]["image"] = f"registry.example/late:v{i % 2}"
        late.append(Operation(step=mid, op="create", kind="pods", obj=pod))
    return ops[:at] + late + ops[at:]


def _rehearsal_ops():
    from ksim_tpu.scenario.generate import churn_scenario

    return list(churn_scenario(0, n_nodes=200, n_events=1600, ops_per_step=100))


@pytest.fixture(scope="module", params=["rehearsal", "late_selector"])
def replayed_segments(request):
    """(persistent output, fresh output) for every lowering of a stream."""
    from ksim_tpu.scenario.runner import ScenarioRunner

    ops = _rehearsal_ops() if request.param == "rehearsal" else _late_selector_ops()
    calls = []
    orig = Featurizer.featurize

    def spy(self, nodes, pods, **kw):
        out = orig(self, nodes, pods, **kw)
        fresh = Featurizer(
            node_bucket_min=self._node_bucket_min,
            pod_bucket_min=self._pod_bucket_min,
            interpod_hard_weight=self._interpod_hard_weight,
            extra_encoders=self._extra_encoders,
            added_affinity=self._added_affinity,
            spread_defaults=self._spread_defaults,
        )
        calls.append((self, out, orig(fresh, nodes, pods, **kw), self.pod_rows_rebuilt))
        return out

    runner = ScenarioRunner(
        max_pods_per_pass=64, device_replay=True, device_segment_steps=4
    )
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(Featurizer, "featurize", spy)
        runner.run(ops)
    driver = runner.replay_driver
    assert driver.stats()["fallback_steps"] == 0
    segs = [c[1:] for c in calls if c[0] is driver._featurizer]
    assert len(segs) >= 3 and len(segs) == len(driver.lower_log)
    assert any(e["rows_reused"] for e in driver.lower_log)
    if request.param == "late_selector":
        # The new selector / context really moved a token mid-stream.
        assert segs[-1][2] > 0
    return segs


def _relabel(col: np.ndarray) -> bytes:
    """Ids -> labels by first appearance (-1 stays): id-free partition."""
    seen: dict[int, int] = {}
    return np.asarray(
        [v if v < 0 else seen.setdefault(v, len(seen)) for v in col.tolist()]
    ).tobytes()


def _spread_view(sp, perm, P):
    def tk_sig(k):
        return (
            _relabel(sp.node_dom[perm, k]), _relabel(sp.node_ldom[perm, k]),
            sp.tk_sizes[k], sp.tk_singleton[k],
        )

    def sel_sig(s):
        return (sp.pod_sel_match[:P, s].tobytes(), sp.init_counts[perm, s].tobytes())

    assert not sp.con_valid[P:].any()
    pods = []
    for j in range(P):
        cons = []
        for ci in range(sp.con_valid.shape[1]):
            if not sp.con_valid[j, ci]:
                cons.append(None)
                continue
            cons.append((
                int(sp.con_mode[j, ci]), int(sp.con_max_skew[j, ci]),
                int(sp.con_min_domains[j, ci]), bool(sp.con_self[j, ci]),
                bool(sp.con_honor_aff[j, ci]), bool(sp.con_honor_taints[j, ci]),
                tk_sig(int(sp.con_tk[j, ci])), sel_sig(int(sp.con_sel[j, ci])),
            ))
        pods.append((cons, bool(sp.has_score_con[j])))
    return sp.n_domains, sp.con_valid.shape, pods


def _interpod_view(ip, perm, P):
    terms = []
    for t in range(ip.term_u.shape[0]):
        own = [a[:P, t] for a in (ip.req_aff, ip.req_anti, ip.pref_w, ip.pod_vw, ip.pod_eat)]
        carried = [ip.ecnt_node[perm, t], ip.ew_node[perm, t]]
        if not any(a.any() for a in own + carried):
            continue  # no current pod carries it: inert
        terms.append(tuple(a.tobytes() for a in own + carried) + (
            ip.pod_term_match[:P, t].tobytes(),
            ip.pod_ctx_match[:P, ip.term_u[t]].tobytes(),
            ip.cnt_node[perm, t].tobytes(), int(ip.total[t]),
            _relabel(ip.dom_t[perm, t]),
            _relabel(ip.node_dom[perm, ip.term_tk[t]]),
        ))
    return ip.hard_weight, ip.self_aff.tobytes(), sorted(terms)


def _assert_same_arrays(name, a, b, perm, n, axis):
    assert a.dtype == b.dtype and a.shape == b.shape, name
    if axis is None:
        np.testing.assert_array_equal(a, b, err_msg=name)
        return
    np.testing.assert_array_equal(
        np.take(a, perm, axis=axis), np.take(b, np.arange(n), axis=axis), err_msg=name
    )
    tail = np.arange(n, a.shape[axis])
    np.testing.assert_array_equal(
        np.take(a, tail, axis=axis), np.take(b, tail, axis=axis), err_msg=name
    )


@pytest.mark.parametrize("family", _FAMILIES)
def test_persistent_featurizer_matches_fresh_per_family(replayed_segments, family):
    import dataclasses

    for seg, (pers, fresh, _rebuilt) in enumerate(replayed_segments):
        n, P = fresh.nodes.count, fresh.pods.count
        assert sorted(pers.nodes.names) == sorted(fresh.nodes.names)
        perm = np.asarray([pers.nodes.names.index(nm) for nm in fresh.nodes.names])
        where = f"segment {seg} {family}"
        if family == "nodes":
            assert (pers.resources, pers.units, pers.exact) == (
                fresh.resources, fresh.units, fresh.exact
            ), where
            for f in dataclasses.fields(fresh.nodes):
                if f.name != "names":
                    _assert_same_arrays(
                        f"{where}.{f.name}", getattr(pers.nodes, f.name),
                        getattr(fresh.nodes, f.name), perm, n, 0,
                    )
        elif family == "pods":
            assert pers.pods.keys == fresh.pods.keys, where
            for f in dataclasses.fields(fresh.pods):
                if f.name != "keys":
                    _assert_same_arrays(
                        f"{where}.{f.name}", getattr(pers.pods, f.name),
                        getattr(fresh.pods, f.name), perm, n, None,
                    )
        elif family == "spread":
            assert _spread_view(pers.aux[family], perm, P) == _spread_view(
                fresh.aux[family], np.arange(n), P
            ), where
        elif family == "interpod":
            assert _interpod_view(pers.aux[family], perm, P) == _interpod_view(
                fresh.aux[family], np.arange(n), P
            ), where
        else:
            # Call-local vocabularies: identical down to the ids.
            a, b = pers.aux[family], fresh.aux[family]
            for f in dataclasses.fields(b):
                va, vb = getattr(a, f.name), getattr(b, f.name)
                if not isinstance(vb, np.ndarray):
                    assert va == vb, f"{where}.{f.name}"
                    continue
                axis = 0 if type(b).AXES.get(f.name) == "node" else None
                if f.name in _NODE_MINOR:
                    axis = 1
                if f.name == "pod_req_node":
                    # Node indices: through the slot permutation.
                    vb = np.where(vb >= 0, perm[np.maximum(vb, 0)], vb).astype(vb.dtype)
                _assert_same_arrays(f"{where}.{f.name}", va, vb, perm, n, axis)


# -- the five additive families against a per-pod loop ------------------------
#
# What a bound pod adds to an aggregate is (slot of its node, contribution of
# its content); ``sync_family`` builds the contribution once a distinct
# manifest and looks it up for every other pod that shares it.  The oracle
# below is the loop the families were before that: one record a pod, parsed
# from THAT pod, applied to arrays made afresh — against the vocabularies and
# the node order the featurizer under test ended its call with.

BOUND_FAMILIES = ("resvals", "requested", "spread_init", "ip_match", "ip_terms")
_ZONES = ("az-1", "az-2", "az-3")


def _zone_node(name: str, zone: str) -> dict:
    node = make_node(name, cpu="16", memory="32Gi")
    node["metadata"]["labels"] = {"zone": zone, "kubernetes.io/hostname": name}
    return node


def _spread(app: str) -> list:
    return [{"maxSkew": 1, "topologyKey": "zone", "whenUnsatisfiable": "DoNotSchedule",
             "labelSelector": {"matchLabels": {"app": app}}}]


def _anti(app: str, key: str = "kubernetes.io/hostname") -> dict:
    return {"podAntiAffinity": {"requiredDuringSchedulingIgnoredDuringExecution": [
        {"topologyKey": key, "labelSelector": {"matchLabels": {"app": app}}}]}}


def _prefer(app: str, weight: int) -> dict:
    return {"podAffinity": {"preferredDuringSchedulingIgnoredDuringExecution": [
        {"weight": weight, "podAffinityTerm": {
            "topologyKey": "zone", "namespaces": ["default", "batch"],
            "labelSelector": {"matchLabels": {"app": app}}}}]}}


#: template -> the keyword arguments of its replicas (``make_pod``).
_TEMPLATES = {
    "web": dict(cpu="250m", memory="256Mi", labels={"app": "web"},
                topology_spread_constraints=_spread("web")),
    "db": dict(cpu="1", memory="1Gi", labels={"app": "db"}, affinity=_anti("db")),
    "cache": dict(cpu="100m", memory="64Mi", labels={"app": "cache"},
                  affinity=_prefer("web", 30)),
    "plain": dict(cpu="500m", memory="128Mi", labels={"app": "plain"}),
    "batch-web": dict(cpu="250m", memory="256Mi", labels={"app": "web"}, namespace="batch"),
}


def _replica(template: str, i: int, node: str) -> dict:
    """Replica ``i`` of a template on ``node``: name, uid, node and status
    are its own, everything else is the template's."""
    pod = make_pod(f"{template}-{i}", node_name=node, phase="Running", **_TEMPLATES[template])
    pod["metadata"]["uid"] = f"uid-{template}-{i}"
    pod["status"]["podIP"] = f"10.0.{len(template)}.{i}"
    pod["status"]["startTime"] = f"2024-01-01T00:00:{i:02d}Z"
    return pod


def _cluster(seed: int):
    rng = random.Random(seed)
    nodes = [_zone_node(f"n{i}", _ZONES[i % 3]) for i in range(7)]
    bound = []
    for template in _TEMPLATES:
        for i in range(rng.randint(3, 6)):
            bound.append(_replica(template, i, f"n{rng.randrange(7)}"))
    # Manifests nobody shares, one of them with an extended resource ...
    for i in range(4):
        bound.append(make_pod(
            f"solo-{i}", cpu=f"{150 + i}m", memory="96Mi", node_name=f"n{rng.randrange(7)}",
            labels={"app": rng.choice(["web", "db"]), "solo": str(i)}, phase="Running",
            extra_requests={"example.com/gpu": "2"} if i == 0 else None))
    # ... and two replicas on a node that does not exist.
    bound += [_replica("web", 90, "ghost"), _replica("db", 91, "ghost")]
    rng.shuffle(bound)
    queue = [
        make_pod("q-web", labels={"app": "web"}, topology_spread_constraints=_spread("web")),
        make_pod("q-db", labels={"app": "db"}, affinity=_anti("db")),
        make_pod("q-cache", labels={"app": "cache"}, affinity=_prefer("web", 30)),
        make_pod("q-batch", labels={"app": "web"}, namespace="batch",
                 topology_spread_constraints=_spread("web"), affinity=_anti("web", "zone")),
    ]
    namespaces = [
        {"metadata": {"name": "default", "labels": {"team": "a"}}},
        {"metadata": {"name": "batch", "labels": {"team": "b"}}},
    ]
    return nodes, bound, queue, namespaces


def _per_pod_arrays(family: str, f: Featurizer, feats, bound: list, namespaces: list):
    """The family's arrays by the loop that was there before: a record a pod."""
    from ksim_tpu.state.interpod import context_matches, parsed_terms
    from ksim_tpu.state.resources import labels_of, namespace_of, pod_requests
    from ksim_tpu.state.selectors import match_label_selector

    agg = f._agg
    slot_of = {nm: i for i, nm in enumerate(feats.nodes.names)}
    shape_of = lambda a: (a.shape, a.dtype)

    def on_axis(pod) -> "int | None":
        return slot_of.get(pod["spec"].get("nodeName", ""))

    if family == "resvals":
        counters: dict = {}
        for pod in bound:
            for non_zero in (False, True):
                for r, v in pod_requests(pod, non_zero=non_zero).items():
                    if v:
                        c = counters.setdefault(r, {})
                        c[v] = c.get(v, 0) + 1
        return counters

    if family == "requested":
        # One master row a node: requests | non-zero requests | pods.
        out = np.zeros(*shape_of(agg["requested"]["arrays"]))
        ridx = {r: i for i, r in enumerate(feats.resources)}
        R = len(ridx)
        assert out.shape[1] == 2 * R + 1

        def lower(d):
            row = np.zeros(len(ridx), dtype=np.int64)
            for r, v in d.items():
                if r in ridx:
                    u = feats.units[r]
                    row[ridx[r]] = v // u if v % u == 0 else -(-v // u)
            return row

        for pod in bound:
            ni = on_axis(pod)
            if ni is not None:
                out[ni, :R] += lower(pod_requests(pod))
                out[ni, R : 2 * R] += lower(pod_requests(pod, non_zero=True))
                out[ni, 2 * R] += 1
        return out

    if family == "spread_init":
        out = np.zeros(*shape_of(agg["spread_init"]["arrays"]))
        for pod in bound:
            ni = on_axis(pod)
            if ni is None:
                continue
            for s, (ns, sel) in enumerate(agg["spread_sels"]["list"]):
                out[ni, s] += (namespace_of(pod) or "default") == ns and match_label_selector(
                    sel, labels_of(pod))
        return out

    vocab = agg["ip_vocab"]
    node_dom = feats.aux["interpod"].node_dom
    ns_labels = {ns["metadata"]["name"]: ns["metadata"]["labels"] for ns in namespaces}
    if family == "ip_match":
        out = np.zeros(*shape_of(agg["ip_match"]["arrays"]))
        for pod in bound:
            ni = on_axis(pod)
            if ni is None:
                continue
            for ui, ctx in enumerate(vocab.ctxs):
                if context_matches(ctx, pod, ns_labels):
                    for d in node_dom[ni]:
                        if d >= 0:
                            out[d, ui] += 1
        return out

    assert family == "ip_terms"
    ranti, ew = (np.zeros(*shape_of(a)) for a in agg["ip_terms"]["arrays"])
    signed = {"req_anti": (1, 0, 0), "req_aff": (0, f._interpod_hard_weight, 0),
              "pref_aff": (0, 0, 1), "pref_anti": (0, 0, -1)}
    for pod in bound:
        ni = on_axis(pod)
        if ni is None:
            continue
        for fam, items in parsed_terms(pod).items():
            dr, hard, sign = signed[fam]
            for _ctx, ck, tk, w in items:
                tki = vocab.tk_ids[tk]
                t = vocab.term_ids[(vocab.ctx_ids[ck], tki)]
                d = node_dom[ni][tki]
                if d >= 0:
                    ranti[d, t] += dr
                    ew[d, t] += hard + sign * w
    return ranti, ew


def _assert_family(family: str, f: Featurizer, feats, bound, namespaces, where: str):
    got = f._agg[family]["arrays"]
    want = _per_pod_arrays(family, f, feats, bound, namespaces)
    if family == "resvals":
        assert got == want, where
        return
    if isinstance(got, np.ndarray):
        got, want = [got], [want]
    assert any(a.any() for a in want), f"{where}: the oracle adds nothing, the case is vacuous"
    for a, b in zip(got, want):
        np.testing.assert_array_equal(a, b, err_msg=f"{where} {family}")


@pytest.mark.parametrize("family", BOUND_FAMILIES)
def test_bound_family_by_content_equals_the_per_pod_loop(family):
    nodes, bound, queue, namespaces = _cluster(11)
    f = Featurizer()
    state = {}

    def call(where: str):
        feats = f.featurize(list(nodes), (), queue_pods=list(queue), bound_pods=list(bound),
                            namespaces=namespaces)
        _assert_family(family, f, feats, bound, namespaces, where)
        state["tokens"] = {fam: f._agg[fam]["token"] for fam in BOUND_FAMILIES}
        return feats

    call("cold")
    # From scratch, a builder ran once a distinct manifest and family: the
    # replicas (the two on the ghost node too) took their template's.
    contents = len(f._contents)
    assert contents == len(_TEMPLATES) + 4
    assert f.bound_records_built <= len(BOUND_FAMILIES) * contents
    assert f.bound_records_built + f.bound_records_shared == len(BOUND_FAMILIES) * len(bound)

    # Departures of pods whose contribution was shared; every replica of
    # ``plain`` goes, so its content id goes back ...
    gone = [p for p in bound if p["metadata"]["name"].startswith("plain-")]
    gone += [p for p in bound if p["metadata"]["name"] in ("web-0", "db-1", "cache-0", "solo-2")]
    plain_id = f._contents.of[id(gone[0])]
    bound = [p for p in bound if not any(p is g for g in gone)]
    call("departures")
    assert plain_id not in f._contents.of.values()
    # ... and the next new content holds it: the tables must not hand the
    # arrival what ``plain`` added.
    fresh = make_pod("fresh-0", cpu="2", memory="3Gi", node_name="n1", phase="Running",
                     labels={"app": "db"}, affinity=_anti("db", "zone"),
                     topology_spread_constraints=_spread("db"))
    bound = bound + [fresh, _replica("web", 7, "n2"), _replica("plain", 8, "n2")]
    built0 = f.bound_records_built
    call("arrivals")
    assert f._contents.of[id(fresh)] == plain_id
    # web-7 shares with the live web replicas; fresh-0 and the returning
    # ``plain`` are new contents: at most two builders a family ran.
    assert f.bound_records_built - built0 <= 2 * len(BOUND_FAMILIES)

    # A slot repair: a node replaced by one in another zone (and the ghost
    # node appears, so the pods that waited for it start to count).
    nodes[2] = _zone_node("n2", "az-9")
    nodes.append(_zone_node("ghost", "az-1"))
    tokens = dict(state["tokens"])
    call("slot repair")
    assert state["tokens"]["requested"] == tokens["requested"]

    # Token moves: a selector and a context the vocabularies have not seen
    # (``spread_init`` / ``ip_match``), relabelled namespaces (``ip_match``),
    # an extended resource on a new pod (``requested``).
    queue.append(make_pod("q-new", labels={"app": "cache"}, namespace="batch",
                          topology_spread_constraints=_spread("cache"),
                          affinity=_anti("cache", "zone")))
    namespaces[1] = {"metadata": {"name": "batch", "labels": {"team": "c"}}}
    bound = bound + [make_pod("fpga-0", cpu="1", memory="1Gi", node_name="n0", phase="Running",
                              labels={"app": "web"}, extra_requests={"example.com/fpga": "1"})]
    call("token move")
    moved = {fam for fam in BOUND_FAMILIES if state["tokens"][fam] != tokens[fam]}
    assert moved >= {"requested", "spread_init", "ip_match"}
    # The content ids outlive every token.
    assert len(f._contents) == len(set(f._contents.of.values()))
    call("steady")


def test_content_id_is_shared_by_replicas_and_by_nothing_else():
    from ksim_tpu.state.boundagg import BoundContents, content_key

    base = _replica("db", 0, "n0")
    twin = _replica("db", 1, "n4")
    twin["metadata"]["resourceVersion"] = "77"
    twin["status"]["phase"] = "Pending"
    assert base["metadata"]["name"] != twin["metadata"]["name"]
    assert base["metadata"]["uid"] != twin["metadata"]["uid"]
    assert base["spec"]["nodeName"] != twin["spec"]["nodeName"]
    assert base["status"] != twin["status"]
    apart = {}
    for field in ("label", "request", "namespace", "term", "annotation"):
        pod = apart[field] = copy.deepcopy(base)
        if field == "label":
            pod["metadata"]["labels"]["tier"] = "x"
        elif field == "request":
            pod["spec"]["containers"][0]["resources"]["requests"]["cpu"] = "1001m"
        elif field == "namespace":
            pod["metadata"]["namespace"] = "batch"
        elif field == "term":
            term = pod["spec"]["affinity"]["podAntiAffinity"]
            term["requiredDuringSchedulingIgnoredDuringExecution"][0]["topologyKey"] = "zone"
        else:  # a field nothing reads: a deny-list shares only what it names
            pod["metadata"]["annotations"] = {"note": "x"}
    pods = [base, twin, *apart.values()]
    contents = BoundContents()
    bound_map = {id(p): p for p in pods}
    assert contents.sync(bound_map, list(bound_map), []) == []
    ids = [contents.of[id(p)] for p in pods]
    assert ids[0] == ids[1]
    assert len(set(ids)) == 1 + len(apart) == len(contents)
    assert content_key(base) == content_key(twin)
    # The inputs keep what the key leaves out.
    assert base["spec"]["nodeName"] == "n0" and "status" in base and "name" in base["metadata"]
    # One replica leaves: the id stays with the other; both gone: it goes
    # back, and the next content takes it.
    assert contents.sync({id(twin): twin}, [], [id(p) for p in pods if p is not twin]) == sorted(
        set(ids) - {ids[0]}, key=ids.index)
    assert contents.of == {id(twin): ids[0]} and len(contents) == 1
    assert contents.sync({}, [], [id(twin)]) == [ids[0]]
    other = apart["label"]
    contents.sync({id(other): other}, [id(other)], [])
    assert contents.of[id(other)] in ids and len(contents) == 1
    assert contents.firsts([id(other)]) == [id(other)]
