"""Featurizer: unit scaling exactness, accumulation, bucketing."""

import copy
import dataclasses
import json
import os
import sys

import numpy as np
import pytest

from ksim_tpu.state.featurizer import Featurizer, bucket_size
from ksim_tpu.state.podtable import content_key
from tests.helpers import make_node, make_pod


def test_bucket_size():
    assert bucket_size(1) == 8
    assert bucket_size(8) == 8
    assert bucket_size(9) == 16
    assert bucket_size(1000) == 1024


def test_resource_axis_and_units():
    nodes = [make_node("n1", cpu="4", memory="16Gi")]
    pods = [make_pod("p1", cpu="100m", memory="128Mi")]
    f = Featurizer().featurize(nodes, pods)
    assert f.resources[:3] == ("cpu", "memory", "ephemeral-storage")
    assert f.exact
    ci, mi = f.resource_index("cpu"), f.resource_index("memory")
    # Ratios are preserved exactly: alloc/request == raw ratio.
    assert f.nodes.allocatable[0, ci] / f.pods.requests[0, ci] == 4000 / 100
    assert f.nodes.allocatable[0, mi] / f.pods.requests[0, mi] == (16 * 1024) / 128


def test_bound_pods_accumulate():
    nodes = [make_node("n1", cpu="4", memory="16Gi")]
    pods = [
        make_pod("p1", cpu="500m", memory="1Gi", node_name="n1"),
        make_pod("p2", cpu="250m", memory="1Gi", node_name="n1"),
        make_pod("p3", cpu="250m", memory="1Gi", node_name="n1", phase="Succeeded"),
        make_pod("q1", cpu="100m", memory="128Mi"),
    ]
    f = Featurizer().featurize(nodes, pods)
    ci = f.resource_index("cpu")
    unit = f.units["cpu"]
    assert f.nodes.requested[0, ci] * unit == 750  # terminal pod excluded
    assert f.nodes.pod_count[0] == 2
    assert f.pods.count == 1  # only the unbound pod is in the queue


def test_nonzero_requests_default():
    nodes = [make_node("n1")]
    pods = [make_pod("p1", cpu=None, memory=None)]
    f = Featurizer().featurize(nodes, pods)
    ci, mi = f.resource_index("cpu"), f.resource_index("memory")
    assert f.pods.requests[0, ci] == 0
    assert f.pods.nonzero_requests[0, ci] * f.units["cpu"] == 100  # 100m default
    assert f.pods.nonzero_requests[0, mi] * f.units["memory"] == 200 * 1024 * 1024


def test_extended_resources():
    nodes = [make_node("n1", extra_alloc={"example.com/gpu": "4"})]
    pods = [make_pod("p1", extra_requests={"example.com/gpu": "2"})]
    f = Featurizer().featurize(nodes, pods)
    gi = f.resource_index("example.com/gpu")
    assert f.nodes.allocatable[0, gi] * f.units["example.com/gpu"] == 4
    assert f.pods.requests[0, gi] * f.units["example.com/gpu"] == 2


def test_padding_masks():
    nodes = [make_node(f"n{i}") for i in range(3)]
    pods = [make_pod(f"p{i}") for i in range(5)]
    f = Featurizer().featurize(nodes, pods)
    assert f.nodes.padded == 8 and f.nodes.count == 3
    assert np.sum(f.nodes.valid) == 3
    assert np.sum(f.pods.valid) == 5


def test_bucket_size_three_quarter_step():
    """From the 8192 pow2 up the bucket ladder gains a 3/4 step (6144,
    12288, …): caps padding waste at 1/3 where the big-shape scans pay
    for it, every step divisible by 2048 for mesh sharding, and NO new
    recompile boundaries at churn-scale shapes (<= 4096)."""
    assert bucket_size(4097) == 6144
    assert bucket_size(5000) == 6144
    assert bucket_size(6144) == 6144
    assert bucket_size(6145) == 8192
    assert bucket_size(10000) == 12288
    assert bucket_size(12289) == 16384
    # Below the threshold the ladder is unchanged.
    assert bucket_size(2049) == 4096
    assert bucket_size(2048) == 2048
    assert bucket_size(4096) == 4096
    assert bucket_size(1000) == 1024


def test_featurize_with_bound_pods_param_matches_split():
    """featurize(bound_pods=...) — the indexed-store fast path — must
    produce the same tensors as the O(all pods) split it replaces,
    including the phase filter it still applies."""
    import numpy as np

    from tests.helpers import make_node, make_pod

    nodes = [make_node(f"n{i}") for i in range(4)]
    bound = [make_pod(f"b{i}", node_name=f"n{i % 4}") for i in range(6)]
    done = [make_pod("done", node_name="n0", phase="Succeeded")]
    queue = [make_pod(f"q{i}") for i in range(3)]
    pods = bound + done + queue

    f1 = Featurizer().featurize(nodes, pods, queue_pods=queue)
    f2 = Featurizer().featurize(
        nodes, (), queue_pods=queue, bound_pods=bound + done
    )
    np.testing.assert_array_equal(f1.nodes.requested, f2.nodes.requested)
    np.testing.assert_array_equal(f1.nodes.pod_count, f2.nodes.pod_count)
    np.testing.assert_array_equal(f1.pods.requests, f2.pods.requests)


# -- rows by content: one build per distinct manifest (state/podtable.py) ----

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark")

#: The benchmark's five pod generators at their rehearsal sizes: the cell
#: whose objects they make, and the template a sperf pod was stamped from.
SOURCES = {
    "churn": ("churn-2k_prefix6k", None),
    "pod-default": ("sperf-5k-basic_10kpods", "pod-default"),
    "pod-low-priority": ("sperf-5k-preempt_basic", "pod-low-priority"),
    "pod-high-priority": ("sperf-5k-preempt_basic", "pod-high-priority"),
    "random_cluster": ("import-1k_full", None),
}


def _objects(source: str) -> "tuple[list[dict], list[dict]]":
    """(nodes, pods) of a generator, through a JSON round trip as the
    server receives them: new objects at every call."""
    cell, template = SOURCES[source]
    sys.path.insert(0, BENCH)
    try:
        import run as harness

        c = harness.load_cell(harness.load("BENCHMARK.json"), cell, True)
        doc = json.loads(harness.build_inputs(c["config"], c["traffic"], 2147483693)["body"])
    finally:
        sys.path.remove(BENCH)
    if "spec" in doc:
        objs = [op["createOperation"]["object"]
                for op in doc["spec"]["scenario"]["operations"] if "createOperation" in op]
    else:
        objs = doc["nodes"] + doc["pods"]
    nodes = [o for o in objs if o["kind"] == "Node"]
    pods = [o for o in objs if o["kind"] == "Pod"
            and (template is None or o["metadata"]["name"].startswith(template + "-"))]
    assert nodes and len(pods) >= 5
    return nodes, pods


def _assert_equal(a, b, path="snapshot"):
    """Every tensor byte for byte, every list and scalar equal."""
    assert type(a) is type(b), path
    if dataclasses.is_dataclass(a):
        for f in dataclasses.fields(a):
            _assert_equal(getattr(a, f.name), getattr(b, f.name), f"{path}.{f.name}")
    elif isinstance(a, dict):
        assert list(a) == list(b), path
        for k in a:
            _assert_equal(a[k], b[k], f"{path}[{k!r}]")
    elif isinstance(a, (list, tuple)):
        assert len(a) == len(b), path
        for i, (x, y) in enumerate(zip(a, b)):
            _assert_equal(x, y, f"{path}[{i}]")
    elif isinstance(a, np.ndarray):
        assert a.dtype == b.dtype and a.shape == b.shape, path
        assert a.tobytes() == b.tobytes() if a.dtype != object else a.tolist() == b.tolist(), path
    else:
        assert a == b, path


@pytest.mark.parametrize("source", SOURCES)
def test_unique_unread_annotation_is_the_control_same_tensors_no_copies(source):
    """The control without a switch: an annotation nothing reads makes
    every manifest distinct, so every row is BUILT — and the tensors are
    those of the originals, whose rows were mostly COPIED."""
    nodes, pods = _objects(source)
    marked = copy.deepcopy(pods)
    for i, p in enumerate(marked):
        p["metadata"].setdefault("annotations", {})["example.test/serial"] = str(i)
    distinct = len({content_key(p) for p in pods})
    assert None not in {content_key(p) for p in pods}
    copied, built = Featurizer(), Featurizer()
    a = copied.featurize(nodes, (), queue_pods=pods)
    b = built.featurize(nodes, (), queue_pods=marked)
    assert built.pod_rows_copied == 0
    assert copied.pod_rows_copied == len(pods) - distinct
    assert copied.pod_rows_built == built.pod_rows_built == len(pods)
    if source.startswith("pod-"):
        assert distinct == 1  # a template's replicas differ in name only
    _assert_equal(a, b)
    assert a.units == b.units and a.resources == b.resources
    assert a.pods.keys == b.pods.keys == [f"{p['metadata']['namespace']}/{p['metadata']['name']}"
                                          for p in pods]


@pytest.mark.parametrize("source", SOURCES)
def test_value_multiset_after_copies_releases_and_readds_is_a_fresh_featurizers(source):
    nodes, pods = _objects(source)
    n = len(pods)
    extra = copy.deepcopy(pods[:2])
    for p in extra:  # two replicas that alone carry an extended resource
        p["metadata"]["name"] += "-gpu"
        p["spec"]["containers"][0].setdefault("resources", {}).setdefault(
            "requests", {})["example.com/gpu"] = "3"
    feat = Featurizer()
    stages = [
        pods + extra,                                   # copies
        pods[n // 3:] + extra[:1],                      # releases, one gpu replica stays
        pods[n // 3:],                                  # the axis loses its last gpu row
        pods[n // 3:] + copy.deepcopy(pods[: n // 3]),  # re-adds, as new objects
        copy.deepcopy(extra) + pods[n // 2:],           # both at once
    ]
    for i, queue in enumerate(stages):
        got = feat.featurize(nodes, (), queue_pods=queue)
        fresh = Featurizer()
        want = fresh.featurize(nodes, (), queue_pods=queue)
        assert feat._queue_vals == fresh._queue_vals, i
        assert got.units == want.units and got.resources == want.resources, i
        assert ("example.com/gpu" in got.resources) == (i in (0, 1, 4)), i
        assert got.pods.keys == want.pods.keys, i
        np.testing.assert_array_equal(got.pods.requests, want.pods.requests)
        np.testing.assert_array_equal(got.pods.nonzero_requests, want.pods.nonzero_requests)
    assert feat.pod_rows_copied > 0 and feat.pod_rows_rebuilt > 0


def _full_pod(name: str = "base") -> dict:
    """A pod with something in every field a row builder reads."""
    zone = "topology.kubernetes.io/zone"
    pod = make_pod(
        name, cpu="250m", memory="256Mi", namespace="team-a", labels={"app": "web"},
        tolerations=[{"key": "node.kubernetes.io/unschedulable", "operator": "Exists",
                      "effect": "NoSchedule"}],
        affinity={
            "nodeAffinity": {"requiredDuringSchedulingIgnoredDuringExecution": {
                "nodeSelectorTerms": [{"matchExpressions": [
                    {"key": "disktype", "operator": "In", "values": ["ssd"]}]}]}},
            "podAntiAffinity": {"requiredDuringSchedulingIgnoredDuringExecution": [
                {"labelSelector": {"matchLabels": {"app": "web"}}, "topologyKey": zone}]},
        },
        topology_spread_constraints=[{
            "maxSkew": 1, "topologyKey": zone, "whenUnsatisfiable": "DoNotSchedule",
            "labelSelector": {"matchLabels": {"app": "web"}}}],
        priority=5,
    )
    pod["spec"]["containers"][0]["image"] = "registry.example/web:v1"
    pod["spec"]["containers"][0]["ports"] = [{"containerPort": 80, "hostPort": 8080}]
    # (A volume a plugin reads: an ``emptyDir`` concerns none of them and
    # the ``volumes`` family does not see it.)
    pod["spec"]["volumes"] = [{"name": "data", "gcePersistentDisk": {"pdName": "disk-a"}}]
    return pod


def _edit(path: str, value):
    def apply(pod: dict) -> None:
        *parents, last = path.split("/")
        at = pod
        for key in parents:
            at = at[int(key)] if isinstance(at, list) else at[key]
        if value is None:
            del at[last]
        else:
            at[last] = value
    return apply


#: field -> (the edit, the family and column that read the field)
ONE_FIELD = {
    "requests": (_edit("spec/containers/0/resources/requests/cpu", "500m"), ("requests", "req")),
    "label": (_edit("metadata/labels/app", "db"), ("spread_match", "match")),
    "namespace": (_edit("metadata/namespace", "team-b"), ("spread_cons", "sel")),
    "nodeName": (_edit("spec/nodeName", "n1"), ("nodename", "want")),
    "toleration": (_edit("spec/tolerations", None), ("static", "tol")),
    "affinity_term": (
        _edit("spec/affinity/nodeAffinity/requiredDuringSchedulingIgnoredDuringExecution/"
              "nodeSelectorTerms/0/matchExpressions/0/values", ["hdd"]), ("affinity", "req")),
    "interpod_term": (
        _edit("spec/affinity/podAntiAffinity/requiredDuringSchedulingIgnoredDuringExecution/"
              "0/topologyKey", "kubernetes.io/hostname"), ("interpod_terms", "t")),
    "spread_constraint": (
        _edit("spec/topologySpreadConstraints/0/maxSkew", 2), ("spread_cons", "max_skew")),
    "host_port": (_edit("spec/containers/0/ports/0/hostPort", 9090), ("nodeports", "ports")),
    "volume": (_edit("spec/volumes", None), ("volumes", "has")),
    "image": (
        _edit("spec/containers/0/image", "registry.example/web:v2"), ("imagelocality", "images")),
    # No row reads the priority (the queue's order does): the manifests
    # still differ, so the two never share.
    "priority": (_edit("spec/priority", 6), None),
}


@pytest.mark.parametrize("field", ONE_FIELD)
def test_one_field_apart_never_shares_a_row(field):
    edit, reader = ONE_FIELD[field]
    base, other = _full_pod("a"), _full_pod("a")
    edit(other)
    assert content_key(base) != content_key(other)
    nodes = [make_node(f"n{i}", labels={"disktype": "ssd", "topology.kubernetes.io/zone": "z"})
             for i in range(2)]
    feat = Featurizer()
    feat.featurize(nodes, (), queue_pods=[base, other])
    table = feat._table
    assert feat.pod_rows_copied == 0
    assert table._cid[table.idx[0]] != table._cid[table.idx[1]]
    if reader is not None:
        fam, col = reader
        rows = table._fams[fam].take(col)
        assert rows[0].tolist() != rows[1].tolist(), (fam, col)


def test_identity_apart_shares_every_row_and_keeps_its_own_key():
    base, twin = _full_pod("a"), _full_pod("b")
    base["metadata"].update(uid="u-1", creationTimestamp="2026-01-01T00:00:00Z")
    twin["metadata"].update(uid="u-2", creationTimestamp="2026-01-02T00:00:00Z")
    nodes = [make_node("n0", labels={"disktype": "ssd"})]
    feat = Featurizer()
    out = feat.featurize(nodes, (), queue_pods=[base, twin])
    table = feat._table
    assert feat.pod_rows_copied == 1
    assert table._cid[table.idx[0]] == table._cid[table.idx[1]]
    for name, fam in table._fams.items():
        for col in fam.cols:
            rows = fam.take(col).tolist()
            if name == "identity":
                assert rows == ["team-a/a", "team-a/b"]
            else:
                assert rows[0] == rows[1], (name, col)
    assert out.pods.keys == ["team-a/a", "team-a/b"]
    want = Featurizer().featurize(nodes, (), queue_pods=[_full_pod("a"), _full_pod("b")])
    _assert_equal(out, want)


def test_cold_call_over_bound_replicas_registers_the_interpod_vocabulary_in_walk_order():
    """The bound side registers one pod a content (the first); the contexts,
    terms and topology keys must still get the ids a walk over EVERY bound pod
    gives them — a later replica registers nothing its template's first did
    not.  Three templates whose replicas interleave, the second one's first
    replica behind a replica of the third."""
    from ksim_tpu.state.interpod import parsed_terms

    def term(app: str, key: str) -> dict:
        return {"topologyKey": key, "labelSelector": {"matchLabels": {"app": app}}}

    templates = {
        "a": {"podAntiAffinity": {"requiredDuringSchedulingIgnoredDuringExecution": [
            term("a", "kubernetes.io/hostname"), term("b", "zone")]}},
        "b": {"podAffinity": {"preferredDuringSchedulingIgnoredDuringExecution": [
            {"weight": 5, "podAffinityTerm": term("a", "zone")}]}},
        "c": {"podAffinity": {"requiredDuringSchedulingIgnoredDuringExecution": [
            term("c", "rack"), term("a", "kubernetes.io/hostname")]}},
    }
    order = ["c", "a", "c", "b", "a", "b", "c", "a"]
    nodes = [make_node(f"n{i}", labels={"zone": f"z{i % 2}", "rack": f"r{i}",
                                        "kubernetes.io/hostname": f"n{i}"}) for i in range(3)]
    bound = [make_pod(f"{t}-{i}", labels={"app": t}, affinity=templates[t],
                      node_name=f"n{i % 3}", phase="Running") for i, t in enumerate(order)]
    feat = Featurizer()
    out = feat.featurize(nodes, (), queue_pods=[make_pod("q", labels={"app": "a"})],
                         bound_pods=bound)
    assert len(feat._contents) == 3

    # The per-pod walk, written out: first appearance over every pod's terms.
    ctxs, tks, terms = [], [], []
    for pod in bound:
        for items in parsed_terms(pod).values():
            for _ctx, ck, tk, _w in items:
                if ck not in ctxs:
                    ctxs.append(ck)
                if tk not in tks:
                    tks.append(tk)
                if (ctxs.index(ck), tks.index(tk)) not in terms:
                    terms.append((ctxs.index(ck), tks.index(tk)))
    vocab = feat._agg["ip_vocab"]
    assert list(vocab.ctx_ids) == ctxs and list(vocab.tk_ids) == tks and vocab.terms == terms
    # Pinned: c's two terms first, then a's second (its first is c's second), then b's.
    assert tks == ["rack", "kubernetes.io/hostname", "zone"]
    assert terms == [(0, 0), (1, 1), (2, 2), (1, 2)]
    ip = out.aux["interpod"]
    assert ip.term_u[:4].tolist() == [0, 1, 2, 1] and ip.term_tk[:4].tolist() == [0, 1, 2, 2]
    # What the eight pods add, by hand: required anti-affinity counts (a's two
    # terms; its three replicas all stand on n1, the one node of zone z1) ...
    n = len(nodes)
    assert ip.ecnt_node[:n, 1].tolist() == [0, 3, 0]
    assert ip.ecnt_node[:n, 2].tolist() == [0, 3, 0]
    # ... and signed weights: c's required terms at the hard weight 1 (pods on
    # n0, n2, n0: racks and hostnames apart), b's preference 5 a replica (n0
    # and n2: both zone z0).
    assert ip.ew_node[:n, 0].tolist() == [2, 0, 1]
    assert ip.ew_node[:n, 1].tolist() == [2, 0, 1]
    assert ip.ew_node[:n, 3].tolist() == [10, 0, 10]
    _assert_equal(out, Featurizer().featurize(
        nodes, (), queue_pods=[make_pod("q", labels={"app": "a"})], bound_pods=copy.deepcopy(bound)))
