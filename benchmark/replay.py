"""The plain reference of the ``job`` deployments: a KEP-140 operations list
replayed by a sequential scheduler, one pod at a time.

What a job hands back is three counts (events applied, pods scheduled,
unschedulable attempts), so the reference has to be a whole scheduler: this
file replays the submitted operations step by step — apply the step's
operations, re-queue the pods of drained nodes, one scheduling pass over the
queue — and returns the same three counts, to be compared exactly.

Written from the upstream kube-scheduler v1.30 definitions, as
``reference.py`` is (whose per-node functions the exports of the import cell
hold to the program's annotations); here each plugin is evaluated for all
nodes at once over numpy int64 / float64 columns, because the stream cell
replays ~96,000 scheduling attempts over 2,000 nodes.  The simulator's own
conventions, which a replay has to share to be a replay of the same system,
are stated where they apply: queue order, the pass-counted backoff, ties
between top-scoring nodes going to the first node by name.  It imports nothing
of the program and takes nothing the program made.

Covered: NodeResourcesFit (filter, LeastAllocated score),
NodeResourcesBalancedAllocation, PodTopologySpread (filter and score; the
pods' own constraints, ``matchLabels`` selectors) and InterPodAffinity
(required anti-affinity, preferred affinity and anti-affinity, both ways
round; ``matchLabels`` selectors).  The other default plugins give every node
of these clusters the same verdict and score; an object that would make them
matter (a taint, a node selector, a required pod affinity, a priority, a
second namespace) raises ``NotCovered``, and the run comes out not correct
rather than unchecked.

``precision="bf16"`` is the control: the resource scores computed in
bfloat16, the step below the float32 the configuration states.
"""

from __future__ import annotations

import math

import numpy as np

from checks import milli
from reference import DEFAULT_MEMORY, DEFAULT_MILLI_CPU, HOSTNAME_KEY, MAX_NODE_SCORE, WEIGHTS

#: The simulator's queue backoff, counted in passes (its scheduler service):
#: attempt k waits min(2**(k-1), 16) passes; a node operation or a pod
#: deletion shortens every wait to min(k-1, 4).
MAX_BACKOFF_PASSES = 16
FLUSH_CAP_PASSES = 4


class NotCovered(ValueError):
    """The operations hold something this reference does not evaluate."""


def bf16(x: np.ndarray) -> np.ndarray:
    """Round float64 values to the nearest bfloat16 (ties to even)."""
    bits = np.asarray(x, np.float32).view(np.uint32).astype(np.uint64)
    bits = (bits + 0x7FFF + ((bits >> 16) & 1)) & 0xFFFF0000
    return bits.astype(np.uint32).view(np.float32).astype(np.float64)


def _selector(sel: "dict | None") -> tuple:
    if sel is None or sel.get("matchExpressions"):
        raise NotCovered("a label selector other than matchLabels")
    return tuple(sorted((sel.get("matchLabels") or {}).items()))


def _selects(selector: tuple, labels: dict) -> bool:
    return all(labels.get(k) == v for k, v in selector)


class Pod:
    """One pod, parsed once."""

    def __init__(self, obj: dict) -> None:
        meta, spec = obj["metadata"], obj["spec"]
        self.name = meta["name"]
        if (meta.get("namespace") or "default") != "default":
            raise NotCovered("a pod outside the default namespace")
        for field in ("nodeName", "nodeSelector", "tolerations", "priority",
                      "priorityClassName", "schedulerName", "schedulingGates", "volumes"):
            if spec.get(field):
                raise NotCovered(f"pod spec.{field}")
        self.labels = meta.get("labels") or {}
        self.cpu = self.mem = self.cpu_nz = self.mem_nz = 0
        for c in spec.get("containers") or []:
            req = (c.get("resources") or {}).get("requests") or {}
            if set(req) - {"cpu", "memory"}:
                raise NotCovered("a request other than cpu and memory")
            self.cpu += milli(req.get("cpu"))
            self.mem += milli(req.get("memory")) // 1000
            self.cpu_nz += milli(req["cpu"]) if "cpu" in req else DEFAULT_MILLI_CPU
            self.mem_nz += milli(req["memory"]) // 1000 if "memory" in req else DEFAULT_MEMORY
        self.hard, self.soft = [], []   # (topology key, selector, maxSkew)
        for c in spec.get("topologySpreadConstraints") or []:
            if c.get("minDomains") or c.get("matchLabelKeys") or c.get("nodeAffinityPolicy") \
                    or c.get("nodeTaintsPolicy"):
                raise NotCovered("a spread constraint beyond key, skew and selector")
            entry = (c["topologyKey"], _selector(c.get("labelSelector")), int(c["maxSkew"]))
            when = c.get("whenUnsatisfiable") or "DoNotSchedule"
            (self.hard if when == "DoNotSchedule" else self.soft).append(entry)
        affinity = spec.get("affinity") or {}
        if set(affinity) - {"podAffinity", "podAntiAffinity"}:
            raise NotCovered("node affinity")
        self.anti, self.preferred = [], []   # (key, selector); (weight, key, selector)
        required, weighted = ("requiredDuringSchedulingIgnoredDuringExecution",
                              "preferredDuringSchedulingIgnoredDuringExecution")
        if (affinity.get("podAffinity") or {}).get(required):
            raise NotCovered("a required pod affinity")
        for term in (affinity.get("podAntiAffinity") or {}).get(required) or []:
            self.anti.append(self._term(term))
        for kind, sign in (("podAffinity", 1), ("podAntiAffinity", -1)):
            for w in (affinity.get(kind) or {}).get(weighted) or []:
                self.preferred.append((sign * int(w["weight"]),) + self._term(w["podAffinityTerm"]))
        self.node: "str | None" = None

    @staticmethod
    def _term(term: dict) -> tuple:
        if term.get("namespaces") or term.get("namespaceSelector") is not None:
            raise NotCovered("an affinity term with namespaces")
        return term["topologyKey"], _selector(term.get("labelSelector"))


class Cluster:
    """The live nodes as columns, with what the pods bound to each node add
    up to.  The columns keep the simulator's node order, because equal totals
    go to the first node in it (upstream draws at random among them): a node
    that goes hands its place to the last one, a new node joins at the end,
    and removals (last place first) and additions (by name) take effect
    together, when the next pass with a pod to place begins (``sync``)."""

    COLUMNS = ("cpu", "mem", "max_pods", "req_cpu", "req_mem", "nz_cpu", "nz_mem", "pods")

    def __init__(self) -> None:
        self.names: list = []
        self.place: dict = {}             # node name -> its place in the columns
        self.col = {c: np.zeros(0, np.int64) for c in self.COLUMNS}
        self.labels: list = []            # per node, its label dict
        self.domain: dict = {}            # topology key -> per-node domain id (-1: no label)
        self.domain_ids: dict = {}        # topology key -> {label value: id}
        self.matching: dict = {}          # selector -> per-node count of bound pods it selects
        self.carried: dict = {}           # (kind, key, selector) -> per-node sum over bound pods
        self.bound: list = []             # per node, the names of the pods bound to it
        self._gone: set = set()
        self._new: dict = {}

    def add(self, obj: dict) -> None:
        spec = obj.get("spec") or {}
        if spec.get("taints") or spec.get("unschedulable"):
            raise NotCovered("a tainted or unschedulable node")
        self._new[obj["metadata"]["name"]] = obj

    def remove(self, name: str) -> set:
        """The node goes at the next ``sync``; returns the pods bound to it."""
        if self._new.pop(name, None) is not None:
            return set()
        self._gone.add(name)
        return set(self.bound[self.place[name]])

    @property
    def live(self) -> int:
        return len(self.names) - len(self._gone) + len(self._new)

    def sync(self) -> None:
        keep = list(range(len(self.names)))
        for at in sorted((self.place[n] for n in self._gone), reverse=True):
            last = keep.pop()
            if at != len(keep):
                keep[at] = last
        fresh = [self._new[n] for n in sorted(self._new)]
        self._gone, self._new = set(), {}
        labels = [n["metadata"].get("labels") or {} for n in fresh]
        self.names = [self.names[i] for i in keep] + [n["metadata"]["name"] for n in fresh]
        self.labels = [self.labels[i] for i in keep] + labels
        self.bound = [self.bound[i] for i in keep] + [set() for _ in fresh]
        self.place = {name: i for i, name in enumerate(self.names)}
        alloc = [n["status"]["allocatable"] for n in fresh]
        values = {"cpu": [milli(a["cpu"]) for a in alloc],
                  "mem": [milli(a["memory"]) // 1000 for a in alloc],
                  "max_pods": [int(a["pods"]) for a in alloc]}
        keep = np.array(keep, np.int64)
        zeros = [0] * len(fresh)
        for key, column in self.col.items():
            self.col[key] = np.concatenate([column[keep], np.array(values.get(key, zeros), np.int64)])
        for key, column in self.domain.items():
            ids = [self._domain_id(key, l) for l in labels]
            self.domain[key] = np.concatenate([column[keep], np.array(ids, np.int64)])
        for table in (self.matching, self.carried):
            for key, column in table.items():
                table[key] = np.concatenate([column[keep], np.array(zeros, np.int64)])

    def _domain_id(self, key: str, labels: dict) -> int:
        if key not in labels:
            return -1
        ids = self.domain_ids.setdefault(key, {})
        return ids.setdefault(labels[key], len(ids))

    def domains(self, key: str) -> np.ndarray:
        if key not in self.domain:
            self.domain[key] = np.array([self._domain_id(key, l) for l in self.labels], np.int64)
        if (self.domain[key] < 0).any():
            raise NotCovered(f"a node without the topology key {key}")
        return self.domain[key]

    def selected(self, selector: tuple, pods: dict) -> np.ndarray:
        """Per node, how many of its bound pods ``selector`` selects."""
        if selector not in self.matching:
            self.matching[selector] = np.array(
                [sum(_selects(selector, pods[p].labels) for p in on) for on in self.bound], np.int64)
        return self.matching[selector]

    def charge(self, pod: Pod, i: int, sign: int) -> None:
        col = self.col
        col["req_cpu"][i] += sign * pod.cpu
        col["req_mem"][i] += sign * pod.mem
        col["nz_cpu"][i] += sign * pod.cpu_nz
        col["nz_mem"][i] += sign * pod.mem_nz
        col["pods"][i] += sign
        for selector, column in self.matching.items():
            if _selects(selector, pod.labels):
                column[i] += sign
        for key, selector in pod.anti:
            self._carry(("anti", key, selector))[i] += sign
        for weight, key, selector in pod.preferred:
            self._carry(("preferred", key, selector))[i] += sign * weight
        (self.bound[i].add if sign > 0 else self.bound[i].discard)(pod.name)

    def _carry(self, key: tuple) -> np.ndarray:
        if key not in self.carried:
            self.carried[key] = np.zeros(len(self.names), np.int64)
        return self.carried[key]

    def per_domain(self, key: str, per_node: np.ndarray) -> "tuple[np.ndarray, np.ndarray]":
        """(per-domain sums of ``per_node``, each node's domain id)."""
        dom = self.domains(key)
        size = len(self.domain_ids.get(key) or ())
        return np.bincount(dom, weights=per_node, minlength=size).astype(np.int64), dom


# -- one scheduling attempt ---------------------------------------------------


def feasible_nodes(pod: Pod, cl: Cluster, pods: dict) -> np.ndarray:
    col = cl.col
    ok = col["pods"] + 1 <= col["max_pods"]
    if pod.cpu or pod.mem:
        ok &= (pod.cpu <= col["cpu"] - col["req_cpu"]) & (pod.mem <= col["mem"] - col["req_mem"])
    # PodTopologySpread, DoNotSchedule: the skew against the emptiest domain
    # among the nodes that carry the key.
    for key, selector, max_skew in pod.hard:
        count, dom = cl.per_domain(key, cl.selected(selector, pods))
        present = np.bincount(dom, minlength=len(count)) > 0
        least = int(count[present].min()) if present.any() else 0
        own = 1 if _selects(selector, pod.labels) else 0
        ok &= count[dom] + own - least <= max_skew
    # InterPodAffinity: my required anti-affinity against the bound pods,
    # and theirs against me.
    for key, selector in pod.anti:
        count, dom = cl.per_domain(key, cl.selected(selector, pods))
        ok &= count[dom] == 0
    for (kind, key, selector), per_node in cl.carried.items():
        if kind == "anti" and _selects(selector, pod.labels):
            count, dom = cl.per_domain(key, per_node)
            ok &= count[dom] == 0
    return ok


def total_scores(pod: Pod, cl: Cluster, pods: dict, ok: np.ndarray, precision: str) -> np.ndarray:
    """Summed weighted final score per node; meaningful where ``ok``."""
    col = cl.col
    rnd = bf16 if precision == "bf16" else (lambda x: x)
    least, fractions = 0, []
    for alloc, used, mine in ((col["cpu"], col["nz_cpu"], pod.cpu_nz),
                              (col["mem"], col["nz_mem"], pod.mem_nz)):
        requested = used + mine
        if precision == "bf16":
            a, r = bf16(alloc.astype(np.float64)), bf16(requested.astype(np.float64))
            s = np.floor(bf16(bf16(bf16(a - r) * 100.0) / a)).astype(np.int64)
        else:
            s = ((alloc - requested) * MAX_NODE_SCORE) // alloc
        least = least + np.where(requested > alloc, 0, s)
        fractions.append(np.minimum(
            rnd(rnd(requested.astype(np.float64)) / rnd(alloc.astype(np.float64))), 1.0))
    least = least // 2
    std = rnd(np.abs(rnd(fractions[0] - fractions[1]) / 2))
    balanced = np.floor(rnd(rnd(1 - std) * float(MAX_NODE_SCORE))).astype(np.int64)
    total = (least * WEIGHTS["NodeResourcesFit"]
             + balanced * WEIGHTS["NodeResourcesBalancedAllocation"])

    if pod.soft:  # PodTopologySpread, ScheduleAnyway
        score = np.zeros(len(ok), np.float64)
        for key, selector, max_skew in pod.soft:
            per_node = cl.selected(selector, pods)
            if key == HOSTNAME_KEY:
                count, weight = per_node, math.log(int(ok.sum()) + 2)
            else:
                per_dom, dom = cl.per_domain(key, per_node)
                count = per_dom[dom]
                weight = math.log(len(np.unique(dom[ok])) + 2)
            score += count.astype(np.float64) * weight + float(max_skew - 1)
        raw = np.floor(score + 0.5).astype(np.int64)
        top, low = int(raw[ok].max()), int(raw[ok].min())
        spread = (MAX_NODE_SCORE * (top + low - raw) // top) if top else \
            np.full(len(ok), MAX_NODE_SCORE, np.int64)
        total = total + spread * WEIGHTS["PodTopologySpread"]

    raw = np.zeros(len(ok), np.int64)  # InterPodAffinity, preferred terms both ways round
    for weight, key, selector in pod.preferred:
        count, dom = cl.per_domain(key, cl.selected(selector, pods))
        raw += weight * count[dom]
    for (kind, key, selector), per_node in cl.carried.items():
        if kind == "preferred" and _selects(selector, pod.labels):
            count, dom = cl.per_domain(key, per_node)
            raw += count[dom]
    top, low = int(raw[ok].max()), int(raw[ok].min())
    if top != low:
        interpod = (float(MAX_NODE_SCORE) * (raw - low).astype(np.float64)
                    / float(top - low)).astype(np.int64)
        total = total + interpod * WEIGHTS["InterPodAffinity"]
    return total


# -- the replay ---------------------------------------------------------------


def replay(operations: list, *, max_pods_per_pass: "int | None" = None,
           precision: str = "exact") -> dict:
    """Replay ``operations`` (KEP-140 ``spec.operations``); returns the
    job's three counts, per step (scheduled, unschedulable), and where
    each live pod ended up."""
    cl, pods = Cluster(), {}
    backoff: dict = {}   # pod -> (attempts, the last pass it sits out)
    passes = events = scheduled = unschedulable = 0
    per_step = []
    by_step: dict = {}
    for op in operations:
        by_step.setdefault(int(op["step"]), []).append(op)
    for step in sorted(by_step):
        batch = by_step[step]
        drained: set = set()
        flush = False
        for op in batch:
            if "createOperation" in op:
                obj = op["createOperation"]["object"]
                if obj["kind"] == "Node":
                    cl.add(obj)
                    flush = True
                elif obj["kind"] == "Pod":
                    pod = Pod(obj)
                    pods[pod.name] = pod
                else:
                    raise NotCovered(f"creation of a {obj['kind']}")
            elif "deleteOperation" in op:
                kind = op["deleteOperation"]["typeMeta"]["kind"]
                name = op["deleteOperation"]["objectMeta"]["name"]
                flush = True
                if kind == "Node":
                    drained |= cl.remove(name)
                elif kind == "Pod":
                    pod = pods.pop(name)
                    backoff.pop(name, None)
                    if pod.node is not None and pod.name not in drained:
                        cl.charge(pod, cl.place[pod.node], -1)
                else:
                    raise NotCovered(f"deletion of a {kind}")
            else:
                raise NotCovered(f"operation {sorted(set(op) - {'step'})}")
        events += len(batch)
        for name in drained:   # the pods of a drained node queue again
            if name in pods:
                pods[name].node = None
        if flush:
            backoff = {k: (n, min(last, passes + min(n - 1, FLUSH_CAP_PASSES)))
                       for k, (n, last) in backoff.items()}
        done = [0, 0]
        if cl.live:
            passes += 1
            # PrioritySort without priorities or creation times: by name.
            queue = sorted(p.name for p in pods.values() if p.node is None
                           and not (p.name in backoff and backoff[p.name][1] >= passes))
            if queue:
                cl.sync()
            for name in queue[:max_pods_per_pass]:
                pod = pods[name]
                ok = feasible_nodes(pod, cl, pods)
                if ok.any():
                    total = total_scores(pod, cl, pods, ok, precision)
                    best = int(np.argmax(np.where(ok, total, np.iinfo(np.int64).min)))
                    pod.node = cl.names[best]
                    cl.charge(pod, best, +1)
                    backoff.pop(name, None)
                    done[0] += 1
                else:
                    attempts = backoff.get(name, (0, 0))[0] + 1
                    backoff[name] = (attempts,
                                     passes + min(2 ** (attempts - 1), MAX_BACKOFF_PASSES))
                    done[1] += 1
        scheduled += done[0]
        unschedulable += done[1]
        per_step.append(tuple(done))
    return {"eventsApplied": events, "podsScheduled": scheduled,
            "unschedulableAttempts": unschedulable, "steps": per_step,
            "placements": {p.name: p.node for p in pods.values()}}
