"""The plain reference of the ``import`` deployments: a pod's Filter verdicts
and final scores re-derived node by node from the imported objects alone.

Written from the upstream kube-scheduler v1.30 plugin definitions
(pkg/scheduler/framework/plugins: nodeunschedulable, tainttoleration,
nodeaffinity, noderesources fit / least_allocated / balanced_allocation,
helper.DefaultNormalizeScore) over Python ints and IEEE doubles, as Go's
int64 and float64.  It imports nothing of the program and takes nothing
the program made except the *order* in which results were written (the
pods' ``resourceVersion``) and the bindings themselves, which is the state
every later pod was evaluated against.

Covered: NodeUnschedulable, TaintToleration, NodeAffinity (with
``nodeSelector``), NodeResourcesFit (filter and LeastAllocated score),
NodeResourcesBalancedAllocation, PodTopologySpread (filter and score, pods'
own constraints; ``matchLabels`` selectors, default inclusion policies) and
InterPodAffinity (filter and score; terms in the pod's own namespace,
``matchLabels`` selectors) — every plugin of the default profile whose
verdict or score varies on these clusters.

``precision="bf16"`` is the control: the same reference with the
resource-score arithmetic rounded to bfloat16 after every operation, the
step below the float32 the configuration states.  Put in the program's
place it has to come out as not correct (tests/test_control.py).
"""

from __future__ import annotations

import json
import math
import struct

from checks import FILTER_KEY, FINAL_SCORE_KEY, milli

MAX_NODE_SCORE = 100
DEFAULT_MILLI_CPU = 100          # schedutil.DefaultMilliCPURequest
DEFAULT_MEMORY = 200 * 2**20     # schedutil.DefaultMemoryRequest
UNSCHEDULABLE_TAINT = {"key": "node.kubernetes.io/unschedulable", "effect": "NoSchedule"}
#: plugin -> weight in the default profile (v1.30 default plugins).
WEIGHTS = {"TaintToleration": 3, "NodeAffinity": 2, "NodeResourcesFit": 1,
           "NodeResourcesBalancedAllocation": 1, "PodTopologySpread": 2, "InterPodAffinity": 2}
FILTERS = ("NodeUnschedulable", "TaintToleration", "NodeAffinity", "NodeResourcesFit",
           "PodTopologySpread", "InterPodAffinity")
HOSTNAME_KEY = "kubernetes.io/hostname"
HARD_POD_AFFINITY_WEIGHT = 1


def bf16(x: float) -> float:
    """Round to the nearest bfloat16 (ties to even)."""
    bits = struct.unpack("<I", struct.pack("<f", x))[0]
    bits = (bits + 0x7FFF + ((bits >> 16) & 1)) & 0xFFFF0000
    return struct.unpack("<f", struct.pack("<I", bits))[0]


# -- objects ----------------------------------------------------------------


def pod_requests(pod: dict, non_zero: bool) -> "tuple[int, int]":
    """(milli-cpu, bytes) over the app containers; ``non_zero`` applies the
    scoring path's defaults to containers that state none."""
    cpu = mem = 0
    for c in pod["spec"].get("containers") or []:
        req = (c.get("resources") or {}).get("requests") or {}
        if "cpu" in req or not non_zero:
            cpu += milli(req.get("cpu"))
        else:
            cpu += DEFAULT_MILLI_CPU
        if "memory" in req or not non_zero:
            mem += milli(req.get("memory")) // 1000
        else:
            mem += DEFAULT_MEMORY
    return cpu, mem


class NodeState:
    def __init__(self, node: dict) -> None:
        alloc = node["status"]["allocatable"]
        self.name = node["metadata"]["name"]
        self.labels = node["metadata"].get("labels") or {}
        self.taints = node.get("spec", {}).get("taints") or []
        self.unschedulable = bool(node.get("spec", {}).get("unschedulable"))
        self.cpu = milli(alloc["cpu"])
        self.mem = milli(alloc["memory"]) // 1000
        self.max_pods = int(alloc["pods"])
        self.req = [0, 0]       # requested, as stated
        self.req_nz = [0, 0]    # requested, with the scoring defaults
        self.pods = 0
        self.bound: list = []   # the pods charged to this node

    def commit(self, pod: dict) -> None:
        cpu, mem = pod_requests(pod, False)
        self.req[0] += cpu
        self.req[1] += mem
        cpu, mem = pod_requests(pod, True)
        self.req_nz[0] += cpu
        self.req_nz[1] += mem
        self.pods += 1
        self.bound.append(pod)


# -- plugins ----------------------------------------------------------------


def tolerates(tol: dict, taint: dict) -> bool:
    if tol.get("effect") and tol["effect"] != taint.get("effect"):
        return False
    if tol.get("key") and tol["key"] != taint.get("key"):
        return False
    op = tol.get("operator") or "Equal"
    if op == "Exists":
        return True
    return op == "Equal" and (tol.get("value") or "") == (taint.get("value") or "")


def _tolerated(tolerations: list, taint: dict) -> bool:
    return any(tolerates(t, taint) for t in tolerations)


def _match_expression(labels: dict, expr: dict) -> bool:
    key, op, values = expr["key"], expr["operator"], expr.get("values") or []
    if op == "In":
        return key in labels and labels[key] in values
    if op == "NotIn":
        return key not in labels or labels[key] not in values
    if op == "Exists":
        return key in labels
    if op == "DoesNotExist":
        return key not in labels
    if op in ("Gt", "Lt"):
        try:
            have, want = int(labels[key]), int(values[0])
        except (KeyError, ValueError, IndexError):
            return False
        return have > want if op == "Gt" else have < want
    raise ValueError(f"node selector operator {op!r}")


def _match_term(labels: dict, term: dict) -> bool:
    exprs = term.get("matchExpressions") or []
    if not exprs and not term.get("matchFields"):
        return False  # an empty term matches no object
    return all(_match_expression(labels, e) for e in exprs)


def filter_verdicts(pod: dict, node: NodeState) -> dict:
    """plugin -> passed?, for the Filter plugins that look at one node."""
    spec = pod["spec"]
    tolerations = spec.get("tolerations") or []
    out = {"NodeUnschedulable": not node.unschedulable
           or _tolerated(tolerations, UNSCHEDULABLE_TAINT)}
    out["TaintToleration"] = all(
        _tolerated(tolerations, t) for t in node.taints
        if t.get("effect") in ("NoSchedule", "NoExecute"))
    selector_ok = all(node.labels.get(k) == v for k, v in (spec.get("nodeSelector") or {}).items())
    required = ((spec.get("affinity") or {}).get("nodeAffinity") or {}).get(
        "requiredDuringSchedulingIgnoredDuringExecution")
    terms_ok = required is None or any(
        _match_term(node.labels, t) for t in required.get("nodeSelectorTerms") or [])
    out["NodeAffinity"] = selector_ok and terms_ok
    cpu, mem = pod_requests(pod, False)
    fits = node.pods + 1 <= node.max_pods
    if cpu or mem:
        fits = fits and cpu <= node.cpu - node.req[0] and mem <= node.mem - node.req[1]
    out["NodeResourcesFit"] = fits
    return out


def all_verdicts(pod: dict, nodes: list) -> dict:
    """node -> plugin -> passed?, every covered Filter plugin."""
    out = {n.name: filter_verdicts(pod, n) for n in nodes}
    spread = spread_filter(pod, nodes, {n.name: out[n.name]["NodeAffinity"] for n in nodes})
    interpod = interpod_filter(pod, nodes)
    for n in nodes:
        out[n.name]["PodTopologySpread"] = spread[n.name]
        out[n.name]["InterPodAffinity"] = interpod[n.name]
    return out


def taint_score(pod: dict, node: NodeState) -> int:
    tolerations = [t for t in pod["spec"].get("tolerations") or []
                   if not t.get("effect") or t["effect"] == "PreferNoSchedule"]
    return sum(1 for t in node.taints
               if t.get("effect") == "PreferNoSchedule" and not _tolerated(tolerations, t))


def affinity_score(pod: dict, node: NodeState) -> int:
    preferred = ((pod["spec"].get("affinity") or {}).get("nodeAffinity") or {}).get(
        "preferredDuringSchedulingIgnoredDuringExecution") or []
    return sum(int(p["weight"]) for p in preferred
               if p.get("weight") and _match_term(node.labels, p.get("preference") or {}))


def least_allocated(pod: dict, node: NodeState, precision: str) -> int:
    pod_nz = pod_requests(pod, True)
    total = weights = 0
    for alloc, used, mine in ((node.cpu, node.req_nz[0], pod_nz[0]),
                              (node.mem, node.req_nz[1], pod_nz[1])):
        if alloc == 0:
            continue
        requested = used + mine
        if requested > alloc:
            s = 0
        elif precision == "bf16":
            a, r = bf16(float(alloc)), bf16(float(requested))
            s = int(bf16(bf16(bf16(a - r) * 100.0) / a))
        else:
            s = ((alloc - requested) * MAX_NODE_SCORE) // alloc
        total += s
        weights += 1
    return total // weights if weights else 0


def balanced_allocation(pod: dict, node: NodeState, precision: str) -> int:
    rnd = bf16 if precision == "bf16" else float
    pod_nz = pod_requests(pod, True)
    fractions = []
    for alloc, used, mine in ((node.cpu, node.req_nz[0], pod_nz[0]),
                              (node.mem, node.req_nz[1], pod_nz[1])):
        if alloc == 0:
            continue
        fractions.append(min(rnd(rnd(float(used + mine)) / rnd(float(alloc))), 1.0))
    std = rnd(abs(rnd(fractions[0] - fractions[1]) / 2)) if len(fractions) == 2 else 0.0
    return int(rnd(rnd(1 - std) * float(MAX_NODE_SCORE)))


# -- plugins that look at the other pods ---------------------------------------


def _selects(selector: "dict | None", labels: dict) -> bool:
    """A ``matchLabels`` label selector; a nil selector selects nothing."""
    if selector is None:
        return False
    if selector.get("matchExpressions"):
        raise ValueError("matchExpressions in a pod label selector")
    return all(labels.get(k) == v for k, v in (selector.get("matchLabels") or {}).items())


def _labels(pod: dict) -> dict:
    return pod["metadata"].get("labels") or {}


def _count_matching(node: NodeState, selector: dict, namespace: str) -> int:
    return sum(1 for p in node.bound
               if (p["metadata"].get("namespace") or "default") == namespace
               and _selects(selector, _labels(p)))


def _spread(pod: dict, when: str) -> list:
    return [c for c in pod["spec"].get("topologySpreadConstraints") or []
            if (c.get("whenUnsatisfiable") or "DoNotSchedule") == when]


def spread_filter(pod: dict, nodes: list, node_affinity_ok: dict) -> dict:
    """PodTopologySpread Filter (DoNotSchedule): node -> passed?  Domains
    and their pod counts come from the nodes that carry every constraint's
    topology key and match the pod's node affinity (NodeAffinityPolicy
    Honor, NodeTaintsPolicy Ignore: the defaults)."""
    hard = _spread(pod, "DoNotSchedule")
    if not hard:
        return {n.name: True for n in nodes}
    ns = pod["metadata"].get("namespace") or "default"
    counts: dict = {}
    for n in nodes:
        if not node_affinity_ok[n.name] or not all(c["topologyKey"] in n.labels for c in hard):
            continue
        for c in hard:
            pair = (c["topologyKey"], n.labels[c["topologyKey"]])
            counts[pair] = counts.get(pair, 0) + _count_matching(n, c.get("labelSelector"), ns)
    out = {}
    for n in nodes:
        ok = True
        for c in hard:
            key = c["topologyKey"]
            if key not in n.labels:
                ok = False
                break
            of_key = [v for (k, _val), v in counts.items() if k == key]
            least = min(of_key) if of_key else 0
            own = 1 if _selects(c.get("labelSelector"), _labels(pod)) else 0
            if counts.get((key, n.labels[key]), 0) + own - least > int(c["maxSkew"]):
                ok = False
                break
        out[n.name] = ok
    return out


def spread_score(pod: dict, nodes: list, feasible: list, node_affinity_ok: dict) -> dict:
    """PodTopologySpread Score + NormalizeScore (ScheduleAnyway): node ->
    0..100 over the ``feasible`` nodes."""
    soft = _spread(pod, "ScheduleAnyway")
    if not soft:
        return {n.name: 0 for n in feasible}
    ns = pod["metadata"].get("namespace") or "default"
    has_all = lambda n: all(c["topologyKey"] in n.labels for c in soft)
    ignored = {n.name for n in feasible if not has_all(n)}
    counts: dict = {}
    sizes = [0] * len(soft)
    for n in feasible:
        if n.name in ignored:
            continue
        for i, c in enumerate(soft):
            if c["topologyKey"] == HOSTNAME_KEY:
                continue
            pair = (c["topologyKey"], n.labels[c["topologyKey"]])
            if pair not in counts:
                counts[pair] = 0
                sizes[i] += 1
    weights = []
    for i, c in enumerate(soft):
        size = len(feasible) - len(ignored) if c["topologyKey"] == HOSTNAME_KEY else sizes[i]
        weights.append(math.log(size + 2))
    for n in nodes:
        if not has_all(n) or not node_affinity_ok[n.name]:
            continue
        for c in soft:
            pair = (c["topologyKey"], n.labels[c["topologyKey"]])
            if c["topologyKey"] != HOSTNAME_KEY and pair in counts:
                counts[pair] += _count_matching(n, c.get("labelSelector"), ns)
    raw = {}
    for n in feasible:
        if n.name in ignored:
            continue
        score = 0.0
        for i, c in enumerate(soft):
            key = c["topologyKey"]
            if key not in n.labels:
                continue
            if key == HOSTNAME_KEY:
                cnt = _count_matching(n, c.get("labelSelector"), ns)
            else:
                cnt = counts[(key, n.labels[key])]
            score += float(cnt) * weights[i] + float(int(c["maxSkew"]) - 1)
        raw[n.name] = int(math.floor(score + 0.5))  # math.Round of a non-negative
    top = max(raw.values(), default=0)
    low = min(raw.values(), default=0)
    out = {}
    for n in feasible:
        if n.name in ignored:
            out[n.name] = 0
        elif top == 0:
            out[n.name] = MAX_NODE_SCORE
        else:
            out[n.name] = MAX_NODE_SCORE * (top + low - raw[n.name]) // top
    return out


def _terms(pod: dict, kind: str, required: bool) -> list:
    """``kind`` is podAffinity or podAntiAffinity; preferred terms come back
    as ``(weight, term)``, required ones as ``(None, term)``."""
    aff = (pod["spec"].get("affinity") or {}).get(kind) or {}
    if required:
        return [(None, t) for t in aff.get("requiredDuringSchedulingIgnoredDuringExecution") or []]
    return [(int(w["weight"]), w["podAffinityTerm"])
            for w in aff.get("preferredDuringSchedulingIgnoredDuringExecution") or []]


def _term_matches(term: dict, owner: dict, other: dict) -> bool:
    """Does ``other`` fall under ``owner``'s affinity term?  Namespaces: the
    term's own list, else the owner's namespace."""
    if term.get("namespaceSelector") is not None:
        raise ValueError("namespaceSelector in an affinity term")
    spaces = term.get("namespaces") or [owner["metadata"].get("namespace") or "default"]
    if (other["metadata"].get("namespace") or "default") not in spaces:
        return False
    return _selects(term.get("labelSelector"), _labels(other))


def interpod_filter(pod: dict, nodes: list) -> dict:
    """InterPodAffinity Filter: node -> passed?"""
    existing_anti: dict = {}   # pairs an existing pod's required anti-affinity forbids
    affinity: dict = {}        # pairs where a pod matching my required affinity term i lives
    anti: dict = {}
    my_aff = _terms(pod, "podAffinity", True)
    my_anti = _terms(pod, "podAntiAffinity", True)
    for n in nodes:
        for other in n.bound:
            for _w, term in _terms(other, "podAntiAffinity", True):
                key = term["topologyKey"]
                if key in n.labels and _term_matches(term, other, pod):
                    pair = (key, n.labels[key])
                    existing_anti[pair] = existing_anti.get(pair, 0) + 1
            for table, terms in ((affinity, my_aff), (anti, my_anti)):
                for _w, term in terms:
                    key = term["topologyKey"]
                    if key in n.labels and _term_matches(term, pod, other):
                        pair = (key, n.labels[key])
                        table[pair] = table.get(pair, 0) + 1
    out = {}
    for n in nodes:
        ok = True
        if my_aff:
            met = all(t["topologyKey"] in n.labels
                      and affinity.get((t["topologyKey"], n.labels[t["topologyKey"]]), 0) > 0
                      for _w, t in my_aff)
            if not met:
                # The first pod of a group may satisfy its own affinity.
                ok = not affinity and all(_term_matches(t, pod, pod) for _w, t in my_aff)
        if ok and any(t["topologyKey"] in n.labels
                      and anti.get((t["topologyKey"], n.labels[t["topologyKey"]]), 0) > 0
                      for _w, t in my_anti):
            ok = False
        if ok and any(existing_anti.get((k, v), 0) > 0 for k, v in n.labels.items()):
            ok = False
        out[n.name] = ok
    return out


def interpod_score(pod: dict, nodes: list, feasible: list) -> dict:
    """InterPodAffinity Score + NormalizeScore: node -> 0..100."""
    my_pref = [(w, t) for w, t in _terms(pod, "podAffinity", False)]
    my_pref += [(-w, t) for w, t in _terms(pod, "podAntiAffinity", False)]
    table: dict = {}

    def add(pair, weight):
        table[pair] = table.get(pair, 0) + weight

    for n in nodes:
        for other in n.bound:
            for w, term in my_pref:
                if term["topologyKey"] in n.labels and _term_matches(term, pod, other):
                    add((term["topologyKey"], n.labels[term["topologyKey"]]), w)
            theirs = [(HARD_POD_AFFINITY_WEIGHT, t) for _w, t in _terms(other, "podAffinity", True)]
            theirs += _terms(other, "podAffinity", False)
            theirs += [(-w, t) for w, t in _terms(other, "podAntiAffinity", False)]
            for w, term in theirs:
                if term["topologyKey"] in n.labels and _term_matches(term, other, pod):
                    add((term["topologyKey"], n.labels[term["topologyKey"]]), w)
    raw = {n.name: sum(w for (k, v), w in table.items() if n.labels.get(k) == v) for n in feasible}
    if not table or not raw:
        return {n.name: 0 for n in feasible}
    top, low = max(raw.values()), min(raw.values())
    if top == low:
        return {n.name: 0 for n in feasible}
    return {name: int(float(MAX_NODE_SCORE) * float(s - low) / float(top - low))
            for name, s in raw.items()}


def normalize(scores: dict, reverse: bool) -> dict:
    """helper.DefaultNormalizeScore over the scored (feasible) nodes."""
    top = max(scores.values(), default=0)
    if top == 0:
        return {n: (MAX_NODE_SCORE if reverse else s) for n, s in scores.items()}
    out = {}
    for n, s in scores.items():
        s = MAX_NODE_SCORE * s // top
        out[n] = MAX_NODE_SCORE - s if reverse else s
    return out


def final_scores(pod: dict, feasible: list, precision: str, nodes: "list | None" = None) -> dict:
    """plugin -> node -> weighted final score over the ``feasible`` nodes;
    ``nodes`` is the whole cluster (default: the feasible nodes)."""
    names = [n.name for n in feasible]
    nodes = feasible if nodes is None else nodes
    affinity_ok = {n.name: filter_verdicts(pod, n)["NodeAffinity"] for n in nodes}
    raw = {
        "PodTopologySpread": spread_score(pod, nodes, feasible, affinity_ok),
        "InterPodAffinity": interpod_score(pod, nodes, feasible),
        "TaintToleration": normalize({n.name: taint_score(pod, n) for n in feasible}, True),
        "NodeAffinity": normalize({n.name: affinity_score(pod, n) for n in feasible}, False),
        "NodeResourcesFit": {n.name: least_allocated(pod, n, precision) for n in feasible},
        "NodeResourcesBalancedAllocation": {
            n.name: balanced_allocation(pod, n, precision) for n in feasible},
    }
    return {p: {n: raw[p][n] * w for n in names} for p, w in WEIGHTS.items()}


# -- the comparison -----------------------------------------------------------


def evaluate(nodes: list, exported_pods: list, sample: set, precision: str = "exact") -> dict:
    """Walk the exported pods in the order their results were written,
    charging each binding to its node; for the pods in ``sample`` return
    ``name -> {"filter": {node: {plugin: passed}}, "final": {plugin: {node:
    score}}}``, the final scores over the nodes the *exported* score
    annotation covers (normalisation is over the feasible set, and the
    uncovered plugins decide part of it)."""
    state = {n["metadata"]["name"]: NodeState(n) for n in nodes}
    out = {}
    for pod in sorted(exported_pods, key=lambda p: int(p["metadata"]["resourceVersion"])):
        name = pod["metadata"]["name"]
        anno = pod["metadata"].get("annotations") or {}
        if name in sample and FINAL_SCORE_KEY in anno:
            scored = json.loads(anno[FINAL_SCORE_KEY])
            feasible = [state[n] for n in scored if n in state]
            every = list(state.values())
            out[name] = {"filter": all_verdicts(pod, every),
                         "final": final_scores(pod, feasible, precision, every)}
        node = pod.get("spec", {}).get("nodeName")
        if node in state:
            state[node].commit(pod)
    return out


def compare(exported_pods: list, expected: dict) -> dict:
    """Exported annotations of the sampled pods against ``expected`` (an
    ``evaluate`` result): counts of compared and differing entries."""
    by_name = {p["metadata"]["name"]: p for p in exported_pods}
    f_cmp = f_bad = s_cmp = s_bad = 0
    worst = 0
    by_plugin: dict = {}
    for name, want in expected.items():
        anno = by_name[name]["metadata"]["annotations"]
        verdicts = json.loads(anno[FILTER_KEY])
        finals = json.loads(anno[FINAL_SCORE_KEY])
        for node, per in verdicts.items():
            for plugin, verdict in per.items():
                if plugin in FILTERS:
                    f_cmp += 1
                    f_bad += (verdict == "passed") != want["filter"][node][plugin]
        for plugin, per_node in want["final"].items():
            for node, score in per_node.items():
                s_cmp += 1
                got = int(finals[node][plugin])
                if got != score:
                    s_bad += 1
                    worst = max(worst, abs(got - score))
                    by_plugin[plugin] = by_plugin.get(plugin, 0) + 1
    return {"filter_compared": f_cmp, "filter_mismatches": f_bad,
            "score_compared": s_cmp, "score_mismatches": s_bad,
            "score_mismatch_share": s_bad / s_cmp if s_cmp else None,
            "score_worst_gap": worst, "score_mismatches_by_plugin": by_plugin}


def as_export(nodes: list, exported_pods: list, sample: set, precision: str) -> list:
    """The control's side: the exported pods with the covered plugins'
    final scores of the sampled pods replaced by this reference's at
    ``precision`` — what the export would read had the program computed its
    scores so."""
    got = evaluate(nodes, exported_pods, sample, precision)
    out = []
    for pod in exported_pods:
        name = pod["metadata"]["name"]
        if name in got:
            anno = dict(pod["metadata"]["annotations"])
            finals = json.loads(anno[FINAL_SCORE_KEY])
            for plugin, per_node in got[name]["final"].items():
                for node, score in per_node.items():
                    finals[node][plugin] = str(score)
            anno[FINAL_SCORE_KEY] = json.dumps(finals)
            pod = {**pod, "metadata": {**pod["metadata"], "annotations": anno}}
        out.append(pod)
    return out
