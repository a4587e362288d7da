"""The seeded input generators: the same seed gives the same inputs, any
seed gives the same scheduling problem in another arrival order."""

import generators as g
from kinds import churn, cluster


def key(obj):
    return repr(obj)


def test_stream_is_a_function_of_its_arguments_and_prefix_closed():
    a = churn.churn_operations(0, n_nodes=50, n_events=600, ops_per_step=100)
    assert a == churn.churn_operations(0, n_nodes=50, n_events=600, ops_per_step=100)
    short = churn.churn_operations(0, n_nodes=50, n_events=400, ops_per_step=100)
    assert a[:len(short)] == short  # a node replacement is one event and two operations
    assert len(a) >= 600 and a[0]["step"] == 0 and a[-1]["step"] == 6


def test_shuffled_stream_is_the_same_problem_in_another_order():
    ops = churn.churn_operations(0, n_nodes=50, n_events=900, ops_per_step=100)
    for seed in (1, 7, 2**31 + 12345):
        got = g.shuffle_operations(seed, ops)
        assert got == g.shuffle_operations(seed, ops) and got != ops
        assert sorted(map(key, got)) == sorted(map(key, ops))
        assert [o["step"] for o in got] == [o["step"] for o in ops]
        # only pod creations moved
        for a, b in zip(got, ops):
            if a != b:
                assert a["createOperation"]["object"]["kind"] == "Pod"
                assert b["createOperation"]["object"]["kind"] == "Pod"
        live = set()
        for op in got:  # every deletion still finds what it names
            if "createOperation" in op:
                obj = op["createOperation"]["object"]
                live.add((obj["kind"], obj["metadata"]["name"]))
            else:
                d = op["deleteOperation"]
                live.remove((d["typeMeta"]["kind"], d["objectMeta"]["name"]))
    assert g.shuffle_operations(1, ops) != g.shuffle_operations(2, ops)


def test_shuffled_cluster_lists_the_same_pods_in_another_order():
    nodes, pods = cluster.random_cluster(0, 20, 60)
    n2, p2 = g.shuffle_cluster(2**31 + 5, nodes, pods)
    assert n2 == nodes and p2 != pods and sorted(map(key, p2)) == sorted(map(key, pods))
    assert g.shuffle_cluster(2**31 + 5, nodes, pods)[1] == p2
    assert all(not p["spec"].get("nodeName") for p in pods)
