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
