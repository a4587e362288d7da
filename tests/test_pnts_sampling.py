"""percentageOfNodesToScore emulation (opt-in: ``SchedulerService(
node_sampling=True)``, a job's ``spec.simulator.nodeSampling``).

Upstream kube-scheduler v1.30 samples which feasible nodes get scored
once a cluster exceeds 100 nodes: it visits nodes in index order from a
rotating start (sched.nextStartNodeIndex), stops filtering after finding
numFeasibleNodesToFind feasible ones, scores/normalizes only those, and
advances the start by the number of nodes processed
(pkg/scheduler/schedule_one.go findNodesThatPassFilters +
numFeasibleNodesToFind).  The reference simulator inherits this
behavior; its exported default config carries the field
(simulator/snapshot/snapshot_test.go:1415).

The emulation is deliberately the DETERMINISTIC sequential idealization
(upstream's parallel filter workers make the exact visited set racy);
docs/migration.md states the contract.  Expectations below are
hand-derived from the upstream formulas, never from running the engine.
"""

import numpy as np
import pytest

from ksim_tpu.engine import Engine
from ksim_tpu.engine.profiles import default_plugins
from ksim_tpu.state.featurizer import Featurizer
from tests.helpers import make_node, make_pod


def _engine(n_nodes, pods, k, record="full"):
    nodes = [make_node(f"n{i:03d}") for i in range(n_nodes)]
    feats = Featurizer().featurize(nodes, [], queue_pods=pods)
    return Engine(feats, default_plugins(feats), record=record, sampling_k=k), feats


def test_sampling_visits_first_k_feasible_from_start():
    """12 feasible nodes, K=4, start=0: exactly nodes 0..3 are visited
    and scored; selection comes from that sample; the start index
    advances by 4 (all visited nodes were feasible)."""
    eng, feats = _engine(12, [make_pod("p0")], 4)
    res, _ = eng.schedule(sampling_start=0)
    N = feats.nodes.count
    vis = res.visited[0][:N]
    assert vis.tolist() == [True] * 4 + [False] * 8
    assert int(res.selected[0]) in range(4)
    assert res.sampling_next_start == 4


def test_sampling_rotates_across_pods():
    """Two pods in one pass: the second pod's window starts where the
    first stopped (hand-derived: K=4 from start 0 -> visits 0-3, next
    start 4 -> second pod visits 4-7)."""
    eng, feats = _engine(12, [make_pod("p0"), make_pod("p1")], 4)
    res, _ = eng.schedule(sampling_start=0)
    N = feats.nodes.count
    assert res.visited[0][:N].tolist() == [True] * 4 + [False] * 8
    assert res.visited[1][:N].tolist() == [False] * 4 + [True] * 4 + [False] * 4
    assert res.sampling_next_start == 8


def test_sampling_wraps_modulo_node_count():
    """start=10 with 12 nodes and K=4 wraps: visits 10, 11, 0, 1."""
    eng, feats = _engine(12, [make_pod("p0")], 4)
    res, _ = eng.schedule(sampling_start=10)
    N = feats.nodes.count
    want = [False] * N
    for i in (10, 11, 0, 1):
        want[i] = True
    assert res.visited[0][:N].tolist() == want
    assert res.sampling_next_start == 2


def test_sampling_skips_infeasible_until_k_found():
    """Nodes 1 and 2 infeasible (cordoned): from start 0 with K=3 the
    visit order is 0(feasible), 1(x), 2(x), 3, 4 — five nodes processed,
    visited mask covers all five, and the infeasible ones carry their
    filter failure in the recorded results."""
    nodes = [make_node(f"n{i:03d}", unschedulable=i in (1, 2)) for i in range(10)]
    feats = Featurizer().featurize(nodes, [], queue_pods=[make_pod("p0")])
    eng = Engine(feats, default_plugins(feats), record="full", sampling_k=3)
    res, _ = eng.schedule(sampling_start=0)
    N = feats.nodes.count
    assert res.visited[0][:N].tolist() == [True] * 5 + [False] * 5
    assert res.sampling_next_start == 5
    # Selection comes from the 3 feasible visited nodes (final-score
    # values for nodes OUTSIDE the sample are dead weight the selection
    # and the renderer both mask, exactly like infeasible nodes in the
    # unsampled path).
    assert int(res.selected[0]) in (0, 3, 4)


def test_sampling_fewer_feasible_than_k_visits_everything():
    """With every node infeasible but 2 and K=3, the whole list is
    processed (upstream iterates to the end) and the start wraps to 0."""
    nodes = [make_node(f"n{i:03d}", unschedulable=i not in (5, 6)) for i in range(8)]
    feats = Featurizer().featurize(nodes, [], queue_pods=[make_pod("p0")])
    eng = Engine(feats, default_plugins(feats), record="full", sampling_k=3)
    res, _ = eng.schedule(sampling_start=0)
    N = feats.nodes.count
    assert res.visited[0][:N].tolist() == [True] * 8
    assert res.sampling_next_start == 0
    assert int(res.selected[0]) in (5, 6)


def test_sampling_normalizes_over_sample_only():
    """Normalization (e.g. NodeAffinity's DefaultNormalizeScore) runs
    over the sampled nodes, not the full feasible set — a high-scoring
    node OUTSIDE the window must not depress the sampled nodes'
    normalized scores.  Node 9 has the preferred label; window 0..3
    doesn't include it, so the sampled max is over equal scores and
    normalize sees only them."""
    labels = {"zone": "hot"}
    aff = {
        "nodeAffinity": {
            "preferredDuringSchedulingIgnoredDuringExecution": [
                {
                    "weight": 100,
                    "preference": {
                        "matchExpressions": [
                            {"key": "zone", "operator": "In", "values": ["hot"]}
                        ]
                    },
                }
            ]
        }
    }
    nodes = [
        make_node(f"n{i:03d}", labels=labels if i == 9 else None) for i in range(10)
    ]
    pod = make_pod("p0", affinity=aff)
    feats = Featurizer().featurize(nodes, [], queue_pods=[pod])
    eng = Engine(feats, default_plugins(feats), record="full", sampling_k=4)
    res, _ = eng.schedule(sampling_start=0)
    # All sampled nodes have raw NodeAffinity 0: upstream's
    # DefaultNormalizeScore with max 0 leaves them 0 — node 9's raw 100
    # must NOT have entered the normalize max.
    na = res.plugin_names.index("NodeAffinity")
    N = feats.nodes.count
    assert (res.final_scores[0][na][:4] == 0).all()
    # Unsampled nodes contribute nothing.
    assert int(res.selected[0]) in range(4)


def test_sampling_scan_only():
    eng, _ = _engine(8, [make_pod("p0")], 3)
    with pytest.raises(ValueError):
        eng.evaluate_batch()


def test_recorded_maps_cover_visited_nodes_only():
    """filter-result lists exactly the visited nodes (upstream's
    NodeToStatusMap covers nodes the sampled iteration touched); score
    maps cover the sampled feasible set."""
    import json

    from ksim_tpu.engine.annotations import FILTER_RESULT_KEY, SCORE_RESULT_KEY, render_pod_results

    nodes = [make_node(f"n{i:03d}", unschedulable=i == 1) for i in range(10)]
    feats = Featurizer().featurize(nodes, [], queue_pods=[make_pod("p0")])
    plugins = default_plugins(feats)
    eng = Engine(feats, plugins, record="full", sampling_k=3)
    res, _ = eng.schedule(sampling_start=0)
    anno = render_pod_results(
        feats, plugins, res, 0, visited=res.visited[0]
    )
    filt = json.loads(anno[FILTER_RESULT_KEY])
    # Visit order 0(ok), 1(x), 2(ok), 3(ok): four visited nodes.
    assert sorted(filt) == ["n000", "n001", "n002", "n003"]
    assert "NodeUnschedulable" in str(filt["n001"])
    score = json.loads(anno[SCORE_RESULT_KEY])
    assert sorted(score) == ["n000", "n002", "n003"]


def test_service_sampling_k_resolution():
    """numFeasibleNodesToFind hand-derivations (schedule_one.go):
    <100 nodes -> no sampling; adaptive percentage 50 - n/125 floored at
    5; explicit percentage respected; floor of 100 feasible nodes."""
    from ksim_tpu.scheduler.service import SchedulerService
    from ksim_tpu.state.cluster import ClusterStore

    svc = SchedulerService(
        ClusterStore(), record="selection", preemption=False, node_sampling=True
    )
    # 99 nodes: below minFeasibleNodesToFind -> score all.
    assert svc._sampling_k_for(None, 99) is None
    # 5000 nodes, adaptive: 50 - 40 = 10% -> 500.
    assert svc._sampling_k_for(None, 5000) == 500
    # 125000 nodes: adaptive hits the 5% floor -> 6250.
    assert svc._sampling_k_for(None, 125_000) == 6250
    # 200 nodes, adaptive: 50 - 1 = 49% -> 98 -> floored to 100.
    assert svc._sampling_k_for(None, 200) == 100
    # 110 nodes adaptive: 50% -> 55 -> floored to 100 (< 110): upstream
    # really does sample 100 of 110 here.
    assert svc._sampling_k_for(None, 110) == 100
    # Explicit global percentage.
    svc._config = {"percentageOfNodesToScore": 20}
    assert svc._sampling_k_for(None, 5000) == 1000
    # >= 100 percent -> everything.
    svc._config = {"percentageOfNodesToScore": 100}
    assert svc._sampling_k_for(None, 5000) is None
    # Sampling not asked for (the constructor's default) -> always None.
    svc = SchedulerService(ClusterStore(), record="selection", preemption=False)
    assert svc._sampling_k_for(None, 5000) is None


def test_service_end_to_end_sampling():
    """``node_sampling=True`` + 120 nodes: the service schedules through
    the sampled scan (adaptive K=100 of 120), records visited-restricted
    maps, and persists the rotating start across passes."""
    import json

    from ksim_tpu.engine.annotations import FILTER_RESULT_KEY
    from ksim_tpu.scheduler.service import SchedulerService
    from ksim_tpu.state.cluster import ClusterStore

    store = ClusterStore()
    for i in range(120):
        store.create("nodes", make_node(f"n{i:03d}"))
    store.create("pods", make_pod("p0", cpu="100m", memory="64Mi"))
    svc = SchedulerService(store, record="full", preemption=False, node_sampling=True)
    placements = svc.schedule_pending()
    assert placements["default/p0"] is not None
    # K=100 of 120 from start 0: nodes 0..99 visited; start advanced.
    pod = store.get("pods", "p0", "default")
    filt = json.loads(pod["metadata"]["annotations"][FILTER_RESULT_KEY])
    assert len(filt) == 100
    assert "n000" in filt and "n099" in filt and "n100" not in filt
    assert svc._pnts_start["default-scheduler"] == 100
    # Second pass starts at 100 and wraps.
    store.create("pods", make_pod("p1", cpu="100m", memory="64Mi"))
    svc.schedule_pending()
    pod1 = store.get("pods", "p1", "default")
    filt1 = json.loads(pod1["metadata"]["annotations"][FILTER_RESULT_KEY])
    assert "n100" in filt1 and "n119" in filt1 and "n099" not in filt1
    assert svc._pnts_start["default-scheduler"] == 80


def test_sampled_schedule_sharded_equals_single_device():
    """The sampling emulation composes with the tp mesh: the rotating
    start/n_real/k scalars replicate and the walk's prefix count runs
    under GSPMD identically to single-device."""
    from ksim_tpu.engine.sharding import make_mesh

    nodes = [make_node(f"n{i:03d}", unschedulable=i % 7 == 3) for i in range(24)]
    pods = [make_pod(f"p{i}") for i in range(6)]
    feats = Featurizer().featurize(nodes, [], queue_pods=pods)
    plain = Engine(feats, default_plugins(feats), record="full", sampling_k=5)
    res_plain, _ = plain.schedule(sampling_start=2)
    sharded = Engine(feats, default_plugins(feats), record="full", sampling_k=5)
    sharded.shard(make_mesh(8, dp=1))
    res_shard, _ = sharded.schedule(sampling_start=2)
    assert np.array_equal(res_plain.selected, res_shard.selected)
    assert np.array_equal(res_plain.visited, res_shard.visited)
    assert res_plain.sampling_next_start == res_shard.sampling_next_start
