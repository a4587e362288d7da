"""Sequential-commit scheduling loop vs an oracle greedy simulation."""

import numpy as np

from ksim_tpu.engine import Engine, ScoredPlugin
from ksim_tpu.plugins import oracle
from ksim_tpu.plugins.noderesources import (
    NodeResourcesBalancedAllocation,
    NodeResourcesFit,
)
from ksim_tpu.state.featurizer import Featurizer
from tests.helpers import make_node, make_pod, random_cluster


def greedy_oracle(nodes, pods, queue):
    """Pure-Python replication of the full default-profile cycle: all five
    filters, raw scores, per-plugin normalization over feasible nodes,
    upstream weights, first-max selection, commit."""
    from tests.helpers import pods_by_node as group_pods

    infos = oracle.build_node_infos(nodes, pods)
    pods_by_node = group_pods(pods)
    out = []
    for pod in queue:
        spread_reasons = oracle.topology_spread_filter_all(pod, infos, pods_by_node)
        ipa_reasons = oracle.inter_pod_affinity_filter_all(pod, infos, pods_by_node)
        feasible_mask = [
            not (
                oracle.node_unschedulable_filter(pod, info)
                or oracle.fit_filter(pod, info)
                or oracle.taint_toleration_filter(pod, info)
                or oracle.node_affinity_filter(pod, info)
                or spread_reasons[ni]
                or ipa_reasons[ni]
            )
            for ni, info in enumerate(infos)
        ]
        feasible = [ni for ni, m in enumerate(feasible_mask) if m]
        _, spread_norm = oracle.topology_spread_score_all(
            pod, infos, pods_by_node, feasible_mask
        )
        _, ipa_norm = oracle.inter_pod_affinity_score_all(
            pod, infos, pods_by_node, feasible_mask
        )
        best, best_score = -1, None
        fit = [oracle.least_allocated_score(pod, infos[ni]) for ni in feasible]
        bal = [oracle.balanced_allocation_score(pod, infos[ni]) for ni in feasible]
        tnt = oracle.default_normalize_score(
            [oracle.taint_toleration_score(pod, infos[ni]) for ni in feasible],
            reverse=True,
        )
        aff = oracle.default_normalize_score(
            [oracle.node_affinity_score(pod, infos[ni]) for ni in feasible],
            reverse=False,
        )
        for k, ni in enumerate(feasible):
            total = (
                fit[k] * 1 + bal[k] * 1 + tnt[k] * 3 + aff[k] * 2
                + spread_norm[ni] * 2 + ipa_norm[ni] * 2
            )
            if best_score is None or total > best_score:
                best, best_score = ni, total
        if best >= 0:
            oracle.commit_pod(infos[best], pod)
            pods_by_node.setdefault(infos[best]["name"], []).append(pod)
        out.append(best)
    return out


from ksim_tpu.engine.profiles import default_plugins


def run_engine(nodes, pods, queue):
    feats = Featurizer().featurize(nodes, pods, queue_pods=queue)
    eng = Engine(feats, default_plugins(feats), record="full")
    res, state = eng.schedule()
    return feats, res, state


def test_cordoned_node_filtered_unless_tolerated():
    nodes = [make_node("up", cpu="4", memory="8Gi"),
             make_node("cordoned", cpu="32", memory="64Gi", unschedulable=True)]
    tol = [{"key": "node.kubernetes.io/unschedulable", "operator": "Exists", "effect": "NoSchedule"}]
    queue = [make_pod("plain", cpu="1", memory="1Gi"),
             make_pod("tolerant", cpu="1", memory="1Gi", tolerations=tol)]
    _, res, _ = run_engine(nodes, [], queue)
    # Plain pod can only land on "up"; tolerant pod prefers the big
    # cordoned node (more free resources -> higher least-allocated score).
    assert [int(x) for x in res.selected[:2]] == [0, 1]


def test_schedule_matches_oracle_greedy():
    for seed in (0, 7):
        nodes, pods = random_cluster(seed, n_nodes=9, n_pods=40, bound_fraction=0.2)
        queue = [p for p in pods if not p["spec"].get("nodeName")]
        feats, res, state = run_engine(nodes, pods, queue)
        want = greedy_oracle(nodes, pods, queue)
        got = [int(x) for x in res.selected[: len(queue)]]
        assert got == want


def test_capacity_fills_up():
    # One node fits exactly two of these pods; third must go unschedulable.
    nodes = [make_node("n1", cpu="1", memory="1Gi", pods=110)]
    queue = [make_pod(f"p{i}", cpu="500m", memory="256Mi") for i in range(3)]
    _, res, state = run_engine(nodes, [], queue)
    assert [int(x) for x in res.selected[:3]] == [0, 0, -1]
    assert bool(res.feasible[0]) and not bool(res.feasible[2])
    # Committed state reflects both placements.
    assert int(state.pod_count[0]) == 2


def test_spread_prefers_emptier_node():
    nodes = [make_node("a", cpu="2", memory="4Gi"), make_node("b", cpu="2", memory="4Gi")]
    queue = [make_pod(f"p{i}", cpu="500m", memory="1Gi") for i in range(4)]
    _, res, _ = run_engine(nodes, [], queue)
    sel = [int(x) for x in res.selected[:4]]
    # Least-allocated scoring alternates nodes.
    assert sel == [0, 1, 0, 1]


def test_padding_pods_not_scheduled():
    nodes = [make_node("n1")]
    queue = [make_pod("p0")]
    feats, res, _ = run_engine(nodes, [], queue)
    assert [int(x) for x in res.selected[1:]] == [-1] * (len(res.selected) - 1)


def test_chunked_schedule_and_batch_match_unchunked():
    """Chunk boundaries must be semantically invisible: the carries thread
    through the host loop unchanged (engine/core.py schedule chunking)."""
    nodes, pods = random_cluster(3, n_nodes=16, n_pods=60, bound_fraction=0.2)
    queue = [p for p in pods if not p["spec"].get("nodeName")]
    feats = Featurizer().featurize(nodes, pods, queue_pods=queue)
    eng = Engine(feats, default_plugins(feats), record="full")
    whole, state_whole = eng.schedule(chunk=int(feats.pods.valid.shape[0]))
    parts, state_parts = eng.schedule(chunk=17)
    for field in ("reason_bits", "scores", "final_scores", "total", "feasible", "selected"):
        a, b = getattr(whole, field), getattr(parts, field)
        assert np.array_equal(a, b), field
    assert np.array_equal(state_whole.requested, state_parts.requested)
    assert np.array_equal(state_whole.pod_count, state_parts.pod_count)

    bwhole = eng.evaluate_batch(chunk=int(feats.pods.valid.shape[0]))
    bparts = eng.evaluate_batch(chunk=13)
    for field in ("reason_bits", "scores", "final_scores", "total", "feasible", "selected"):
        assert np.array_equal(getattr(bwhole, field), getattr(bparts, field)), field


def test_engine_jit_cache_reused_across_instances():
    """Re-featurizing a same-shaped snapshot must NOT recompile: Engine
    hashes by (record, plugin static signatures) and shapes key the rest
    (engine/core.py _sig) — the watch-driven service builds a fresh Engine
    per pass and relies on this."""
    from ksim_tpu.engine.core import _Program

    nodes, pods = random_cluster(11, n_nodes=10, n_pods=30, bound_fraction=0.2)
    queue = [p for p in pods if not p["spec"].get("nodeName")]
    feats1 = Featurizer().featurize(nodes, pods, queue_pods=queue)
    eng1 = Engine(feats1, default_plugins(feats1), record="full")
    res1, _ = eng1.schedule()
    eng1.evaluate_batch()
    size_sched = _Program._schedule_fn._cache_size()
    size_batch = _Program._batch_fn._cache_size()

    # Mutate one pod's requests (same shapes/vocabs), re-featurize: the
    # compiled programs must be reused AND produce the new values.
    import copy

    queue2 = copy.deepcopy(queue)
    queue2[0]["spec"]["containers"][0]["resources"] = {"requests": {"cpu": "3"}}
    feats2 = Featurizer().featurize(nodes, pods, queue_pods=queue2)
    eng2 = Engine(feats2, default_plugins(feats2), record="full")
    assert eng2._prog == eng1._prog and hash(eng2._prog) == hash(eng1._prog)
    res2, _ = eng2.schedule()
    eng2.evaluate_batch()
    assert _Program._schedule_fn._cache_size() == size_sched
    assert _Program._batch_fn._cache_size() == size_batch
    assert not np.array_equal(res1.total, res2.total)  # new values flowed
