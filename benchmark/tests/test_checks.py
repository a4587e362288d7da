"""The plain-Python checks fed canned good and bad documents, and the plain
reference against its own bfloat16 control on a fabricated export."""

import copy
import json

import checks
from kinds import cluster
import reference

GUARANTEES = {
    "every_step_on_device": True,
    "replay_equals": {"fallback_steps": 0, "device_errors": 0, "watchdog_timeouts": 0,
                      "breaker_tripped": False, "unsupported": {}},
}
GOOD_JOB = {
    "state": "succeeded",
    "result": {"eventsApplied": 6100, "podsScheduled": 2524, "unschedulableAttempts": 471, "steps": 41},
    "replay": {"device_steps": 41, "fallback_steps": 0, "device_errors": 0, "watchdog_timeouts": 0,
               "breaker_tripped": False, "unsupported": {}},
}


def failing(comparisons):
    return sorted(c["name"] for c in comparisons if not c["ok"])


def test_job_good():
    assert failing(checks.check_job(GOOD_JOB, GUARANTEES, steps=41, lock=[2524, 471])) == []
    assert failing(checks.check_job(GOOD_JOB, GUARANTEES, steps=41, lock=None)) == []


def test_job_bad():
    bad = copy.deepcopy(GOOD_JOB)
    bad["replay"].update(device_steps=40, fallback_steps=1, unsupported={"device_error": 1})
    assert failing(checks.check_job(bad, GUARANTEES, steps=41, lock=[2524, 471])) == [
        "replay.device_steps", "replay.fallback_steps", "replay.unsupported"]
    wrong = copy.deepcopy(GOOD_JOB)
    wrong["result"]["podsScheduled"] = 2523
    assert failing(checks.check_job(wrong, GUARANTEES, steps=41, lock=[2524, 471])) == ["job.locked_counts"]
    assert failing(checks.check_job({"state": "failed", "message": "x"}, GUARANTEES, steps=41, lock=None)) == ["job.state"]
    tripped = copy.deepcopy(GOOD_JOB)
    tripped["replay"]["breaker_tripped"] = True
    assert failing(checks.check_job(tripped, GUARANTEES, steps=41, lock=None)) == ["replay.breaker_tripped"]


def test_device():
    assert failing(checks.check_device({"platform": "tpu", "count": 1}, "tpu", 1)) == []
    assert failing(checks.check_device({"platform": "cpu", "count": 1}, "tpu", 1)) == ["device.platform"]
    assert failing(checks.check_device({"platform": "tpu", "count": 1}, "tpu", 4)) == ["device.count"]


def test_milli():
    assert checks.milli("250m") == 250 and checks.milli("2") == 2000
    assert checks.milli("4Gi") == 4 * 2**30 * 1000 and checks.milli(None) == 0


def fabricate_export(seed, n_nodes=12, n_pods=40, precision="exact"):
    """An export as a correct server would write it: pods walked in name
    order, each bound to its best node, annotated with the reference's own
    verdicts and scores."""
    nodes, pods = cluster.random_cluster(seed, n_nodes, n_pods)
    state = {n["metadata"]["name"]: reference.NodeState(n) for n in nodes}
    out = []
    for rv, pod in enumerate(pods, start=100):
        pod = copy.deepcopy(pod)
        every = list(state.values())
        verdicts = reference.all_verdicts(pod, every)
        feasible = [state[n] for n, v in verdicts.items() if all(v.values())]
        finals = reference.final_scores(pod, feasible, precision, every)
        per_node = {n.name: {p: str(finals[p][n.name]) for p in finals} for n in feasible}
        anno = {
            checks.FILTER_KEY: json.dumps({
                n: {p: ("passed" if ok else "refused") for p, ok in v.items()}
                for n, v in verdicts.items()}),
            checks.FINAL_SCORE_KEY: json.dumps(per_node),
        }
        if feasible:
            best = max(per_node, key=lambda n: sum(int(x) for x in per_node[n].values()))
            pod["spec"]["nodeName"] = best
            anno[checks.SELECTED_NODE_KEY] = best
            state[best].commit(pod)
        pod["metadata"].update(annotations=anno, resourceVersion=str(rv))
        out.append(pod)
    return nodes, pods, {"pods": out, "nodes": nodes}


def test_export_good_and_bad():
    nodes, pods, export = fabricate_export(3)
    g = {"min_bound_share": 0.5}
    assert failing(checks.check_export(export, nodes, pods, g)) == []
    raw = json.dumps(export).encode()
    assert raw.count(checks.FILTER_KEY_RAW) == len(pods)
    # A pod moved to another node: no longer its selected, top-ranked node.
    moved = copy.deepcopy(export)
    victim = next(p for p in moved["pods"] if p["spec"].get("nodeName"))
    finals = json.loads(victim["metadata"]["annotations"][checks.FINAL_SCORE_KEY])
    totals = {n: sum(int(x) for x in v.values()) for n, v in finals.items()}
    worst = min(totals, key=totals.get)
    if totals[worst] < max(totals.values()):
        victim["spec"]["nodeName"] = worst
        assert "export.bound_on_outranked_node" in failing(checks.check_export(moved, nodes, pods, g))
    # A pod without annotations; a node over its allocatable.
    bare = copy.deepcopy(export)
    del bare["pods"][0]["metadata"]["annotations"][checks.FILTER_KEY]
    assert "export.pods_annotated" in failing(checks.check_export(bare, nodes, pods, g))
    stuffed = copy.deepcopy(export)
    target = stuffed["pods"][0]["spec"].get("nodeName") or nodes[0]["metadata"]["name"]
    for p in stuffed["pods"]:
        p["spec"]["nodeName"] = target
    assert "export.nodes_over_allocatable" in failing(checks.check_export(stuffed, nodes, pods, g))


def test_reference_agrees_with_itself_and_the_bf16_control_does_not():
    shares = []
    for seed in (1, 2, 3, 2**31 + 11):
        nodes, pods, export = fabricate_export(seed, n_nodes=30, n_pods=80)
        names = {p["metadata"]["name"] for p in pods}
        want = reference.evaluate(nodes, export["pods"], names)
        sound = reference.compare(export["pods"], want)
        assert sound["score_compared"] > 500
        assert sound["score_mismatches"] == 0 and sound["filter_mismatches"] == 0
        control = reference.compare(reference.as_export(nodes, export["pods"], names, "bf16"), want)
        shares.append(control["score_mismatch_share"])
    # The control: scores computed in bfloat16 differ in a tenth of the entries.
    assert min(shares) > 0.05, shares


def test_bf16_rounding():
    assert reference.bf16(1.0) == 1.0
    assert reference.bf16(1.00390625) == 1.0          # 1 + 2^-8: ties to even
    assert reference.bf16(1.01171875) == 1.015625     # 1 + 3 * 2^-8 -> 1 + 2^-6
    assert reference.bf16(3.0e9) == 179 * 2.0**24
