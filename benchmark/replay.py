"""The plain reference of the ``job`` deployments: a KEP-140 operations list
replayed by a sequential scheduler, one pod at a time.

What a job hands back is three counts (events applied, pods scheduled,
unschedulable attempts), so the reference has to be a whole scheduler: this
file replays the submitted operations step by step — apply the step's
operations, re-queue the pods of drained nodes, one scheduling pass over the
queue — and returns the same three counts, to be compared exactly, and where
every pod it leaves stands (``placements``, the digest's input).

Written from the upstream kube-scheduler v1.30 definitions, as
``reference.py`` is (whose per-node functions the exports of the import cell
hold to the program's annotations); here each plugin is evaluated for all
nodes at once over numpy int64 / float64 columns, because the stream cell
replays ~96,000 scheduling attempts over 2,000 nodes.  It imports nothing of
the program and takes nothing the program made.  The simulator's own
conventions, which a replay has to share to be a replay of the same system,
are taken from its documentation and stated where they apply:

- one scheduling pass a step, the pass-counted backoff, ties between
  top-scoring nodes going to the first node in the simulator's node order;
- the queue: priority, then ``metadata.creationTimestamp`` as written (the
  simulator stamps none), then name (upstream PrioritySort: priority, then the
  time the pod joined the queue);
- preemption: the candidate walk starts at the first node BY NAME and goes in
  name order (upstream draws the offset at random); a victim is gone the moment
  it is chosen (the simulator's pods terminate at once, as under KWOK) and its
  going does not flush the backoff; a pod that was just nominated is retried in
  the next pass (upstream: its victims' delete events re-activate it); a pod's
  start is ``status.startTime``, else ``metadata.creationTimestamp``, else the
  empty string, compared as strings, and equally important pods go by name
  (upstream: ``time.Now()`` for a pod that has not started, and an unstable
  sort).

Covered: NodeResourcesFit (filter, LeastAllocated score),
NodeResourcesBalancedAllocation, PodTopologySpread (filter and score; the
pods' own constraints, ``matchLabels`` selectors), InterPodAffinity
(required anti-affinity, preferred affinity and anti-affinity, both ways
round; ``matchLabels`` selectors), priorities (``spec.priority``, or
``spec.priorityClassName`` with the ``PriorityClass`` created before the pod,
``globalDefault`` and the two system classes) and DefaultPreemption
(``preempt`` below), a pod created with ``spec.nodeName`` (bound as it is
born).  The other default plugins give every node of these clusters the same
verdict and score; an object that would make them matter (a taint, a node
selector or node affinity, a required pod affinity, a volume, a host port, a
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
#: DefaultPreemptionArgs: the dry run stops at max(10 % of the nodes, 100)
#: candidates (GetOffsetAndNumCandidates).
MIN_CANDIDATE_NODES_PERCENTAGE = 10
MIN_CANDIDATE_NODES_ABSOLUTE = 100
#: scheduling.SystemCriticalPriority and the two classes every cluster has.
SYSTEM_CLASSES = {"system-cluster-critical": 2_000_000_000, "system-node-critical": 2_000_001_000}


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


class PriorityClasses:
    """The Priority admission plugin: what ``spec.priority`` and
    ``spec.preemptionPolicy`` a pod is created with."""

    def __init__(self) -> None:
        self.classes = {name: (value, None) for name, value in SYSTEM_CLASSES.items()}
        self.default: "tuple | None" = None

    def add(self, obj: dict) -> None:
        entry = (int(obj.get("value") or 0), obj.get("preemptionPolicy"))
        self.classes[obj["metadata"]["name"]] = entry
        if obj.get("globalDefault"):
            self.default = entry

    def admit(self, spec: dict) -> "tuple[int, str]":
        name = spec.get("priorityClassName")
        if name and name not in self.classes:
            raise NotCovered(f"a pod of the PriorityClass {name}, which was not created before it")
        value, policy = self.classes[name] if name else self.default or (0, None)
        if spec.get("priority") is not None:
            value = int(spec["priority"])
        return value, spec.get("preemptionPolicy") or policy or "PreemptLowerPriority"


class Pod:
    """One pod, parsed once."""

    def __init__(self, obj: dict, classes: PriorityClasses) -> None:
        meta, spec = obj["metadata"], obj["spec"]
        self.name = meta["name"]
        if (meta.get("namespace") or "default") != "default":
            raise NotCovered("a pod outside the default namespace")
        for field in ("nodeSelector", "tolerations", "schedulerName", "schedulingGates", "volumes",
                      "initContainers", "overhead"):
            if spec.get(field):
                raise NotCovered(f"pod spec.{field}")
        status = obj.get("status") or {}
        if status.get("phase") in ("Succeeded", "Failed") or status.get("nominatedNodeName"):
            raise NotCovered("a pod created finished or nominated")
        self.labels = meta.get("labels") or {}
        self.priority, self.policy = classes.admit(spec)
        self.created = meta.get("creationTimestamp") or ""
        self.start = status.get("startTime") or self.created
        self.born_on = spec.get("nodeName") or None
        self.nominated: "str | None" = None
        self.cpu = self.mem = self.cpu_nz = self.mem_nz = 0
        for c in spec.get("containers") or []:
            resources = c.get("resources") or {}
            req = resources.get("requests") or {}
            if set(req) - {"cpu", "memory"}:
                raise NotCovered("a request other than cpu and memory")
            if set(resources.get("limits") or ()) - set(req):
                raise NotCovered("a limit without its request (the apiserver would default it)")
            if any(port.get("hostPort") for port in c.get("ports") or []):
                raise NotCovered("a host port")
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
        #: Whether only NodeResourcesFit can refuse this pod a node, whatever
        #: the pods already bound carry (``Cluster.plain``).
        self.unconstrained = not (self.hard or self.anti)

    @property
    def queue_key(self) -> tuple:
        return -self.priority, self.created, self.name

    @property
    def importance(self) -> tuple:
        """util.MoreImportantPod, most important first."""
        return -self.priority, self.start, self.name

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
    together, when the next pass with a pod to place begins (``sync``).

    ``levels`` keeps, per priority, what the pods bound to each node request
    and what the pods nominated to it would (``"bound"`` / ``"nominated"``):
    what preemption may take away, and what a filter has to count in."""

    COLUMNS = ("cpu", "mem", "max_pods", "req_cpu", "req_mem", "nz_cpu", "nz_mem", "pods")
    LEVEL_COLUMNS = ("cpu", "mem", "pods")

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
        self.levels: dict = {}            # (which, priority, column) -> per-node sum
        self.count: dict = {}             # (which, priority) -> pods in all
        self.nominated_on: dict = {}      # node name -> the names of the pods nominated to it
        self.nominated_anti: dict = {}    # (key, selector) -> nominated pods that carry the term
        self._by_name = None
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
        for table in (self.matching, self.carried, self.levels):
            for key, column in table.items():
                table[key] = np.concatenate([column[keep], np.array(zeros, np.int64)])
        self._by_name = None

    @property
    def by_name(self) -> np.ndarray:
        """The places in the order of the nodes' names."""
        if self._by_name is None:
            self._by_name = np.array(sorted(range(len(self.names)), key=self.names.__getitem__),
                                     np.int64)
        return self._by_name

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
        self._level("bound", pod, i, sign)

    def _level(self, which: str, pod: Pod, i: int, sign: int) -> None:
        for column, amount in zip(self.LEVEL_COLUMNS, (pod.cpu, pod.mem, 1)):
            key = (which, pod.priority, column)
            if key not in self.levels:
                self.levels[key] = np.zeros(len(self.names), np.int64)
            self.levels[key][i] += sign * amount
        self.count[which, pod.priority] = self.count.get((which, pod.priority), 0) + sign

    def has(self, which: str, keep) -> bool:
        """Whether any pod is ``which`` at a priority that ``keep`` accepts."""
        return any(n and w == which and keep(priority) for (w, priority), n in self.count.items())

    def level_sum(self, which: str, keep, but: "Pod | None" = None) -> dict:
        """Per node, the summed requests and the number of the pods that are
        ``which`` at a priority ``keep`` accepts, ``but`` left out."""
        out = {c: np.zeros(len(self.names), np.int64) for c in self.LEVEL_COLUMNS}
        for (w, priority, column), per_node in self.levels.items():
            if w == which and keep(priority) and self.count.get((w, priority)):
                out[column] += per_node
        if but is not None and but.nominated is not None and which == "nominated" \
                and keep(but.priority):
            i = self.place[but.nominated]
            for column, amount in zip(self.LEVEL_COLUMNS, (but.cpu, but.mem, 1)):
                out[column][i] -= amount
        return out

    def nominate(self, pod: Pod, i: int) -> None:
        pod.nominated = self.names[i]
        self._nominated(pod, i, +1)

    def denominate(self, pod: Pod) -> None:
        """Clear ``pod``'s nomination, if it has one."""
        if pod.nominated is not None:
            self._nominated(pod, self.place[pod.nominated], -1)
            pod.nominated = None

    def _nominated(self, pod: Pod, i: int, sign: int) -> None:
        self._level("nominated", pod, i, sign)
        on = self.nominated_on.setdefault(self.names[i], set())
        (on.add if sign > 0 else on.discard)(pod.name)
        for term in pod.anti:
            self.nominated_anti[term] = self.nominated_anti.get(term, 0) + sign

    def plain(self, pod: Pod) -> bool:
        """Whether NodeResourcesFit alone decides where ``pod`` fits: it has no
        required constraint of its own and no pod, bound or nominated, carries
        a required anti-affinity term that selects it."""
        return pod.unconstrained and not any(
            kind == "anti" and _selects(selector, pod.labels) for kind, _, selector in self.carried
        ) and not any(n and _selects(selector, pod.labels)
                      for (_, selector), n in self.nominated_anti.items())

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


def resources_fit(pod: Pod, cl: Cluster, less: dict, more: dict) -> np.ndarray:
    """NodeResourcesFit with the pods of ``less`` taken off each node and
    those of ``more`` put on it (``Cluster.level_sum``)."""
    col = cl.col
    ok = col["pods"] - less["pods"] + more["pods"] + 1 <= col["max_pods"]
    if pod.cpu or pod.mem:
        ok &= ((pod.cpu <= col["cpu"] - col["req_cpu"] + less["cpu"] - more["cpu"])
               & (pod.mem <= col["mem"] - col["req_mem"] + less["mem"] - more["mem"]))
    return ok


def feasible_with_nominated(pod: Pod, cl: Cluster, pods: dict) -> np.ndarray:
    """RunFilterPluginsWithNominatedPods: a node has to pass as it stands and
    with the pods nominated to it, of the pod's priority or above, counted as
    if they ran there (a preemptor's room is kept for it)."""
    ok = feasible_nodes(pod, cl, pods)
    mine = lambda priority: priority >= pod.priority
    if not cl.has("nominated", mine):
        return ok
    more = cl.level_sum("nominated", mine, but=pod)
    if not more["pods"].any():
        return ok
    if cl.plain(pod):
        return ok & resources_fit(pod, cl, {c: 0 for c in cl.LEVEL_COLUMNS}, more)
    there = [(pods[q], cl.place[node]) for node, names in cl.nominated_on.items()
             for q in sorted(names) if q != pod.name and mine(pods[q].priority)]
    for other, i in there:
        cl.charge(other, i, +1)
    ok &= feasible_nodes(pod, cl, pods)
    for other, i in there:
        cl.charge(other, i, -1)
    return ok


# -- DefaultPreemption --------------------------------------------------------


def victims_on(pod: Pod, cl: Cluster, pods: dict, i: int, plain: bool,
               less: dict, more: dict) -> list:
    """selectVictimsOnNode: with every pod of a lower priority off node ``i``
    the pod has to fit; then those pods come back one by one, the most
    important first, and one stays off — a victim — where the pod would no
    longer fit beside it.  Empty: the node is no candidate."""
    lower = sorted((pods[n] for n in cl.bound[i] if pods[n].priority < pod.priority),
                   key=lambda v: v.importance)
    victims = []
    if plain:   # NodeResourcesFit alone: the node's own arithmetic
        col = cl.col
        room = [int(col[a][i] - col[r][i] + less[c][i] - more[c][i]) - mine for a, r, c, mine in
                (("cpu", "req_cpu", "cpu", pod.cpu), ("mem", "req_mem", "mem", pod.mem))]
        slots = int(col["max_pods"][i] - col["pods"][i] + less["pods"][i] - more["pods"][i]) - 1
        sized = bool(pod.cpu or pod.mem)
        for v in lower:
            if slots >= 1 and (not sized or (v.cpu <= room[0] and v.mem <= room[1])):
                room, slots = [room[0] - v.cpu, room[1] - v.mem], slots - 1
            else:
                victims.append(v)
        return victims
    for v in lower:
        cl.charge(v, i, -1)
    fits = bool(feasible_with_nominated(pod, cl, pods)[i])
    for v in lower:
        cl.charge(v, i, +1)
        if fits and not feasible_with_nominated(pod, cl, pods)[i]:
            cl.charge(v, i, -1)
            victims.append(v)
    for v in victims:
        cl.charge(v, i, +1)
    return victims


def preempt(pod: Pod, cl: Cluster, pods: dict) -> "tuple[int, list] | None":
    """DefaultPreemption's PostFilter for a pod that no node fits: the place
    of the node to nominate and the victims to delete there, most important
    first; ``None`` where preemption does not help.

    Eligible unless ``preemptionPolicy: Never`` (a nominated pod with a victim
    still terminating would wait too, upstream; here a victim is gone at
    once).  Every filter failure this file can produce is one that removing
    pods may cure, so every node with a pod of a lower priority is looked at:
    in the order of the nodes' names from the first (the simulator's
    convention; upstream starts at a random offset), until max(10 % of the
    nodes, 100) candidates are found.  A node is a candidate with its victims
    (``victims_on``) and only with one victim at least.  Among the candidates
    (no PodDisruptionBudget exists in the snapshot model): the lowest
    highest-priority victim; the smallest sum of the victims' priorities, each
    with 2**31 added as upstream does, so that fewer victims weigh less; the
    fewest victims; the latest start among each node's highest-priority
    victims' earliest; the first found."""
    lower = lambda priority: priority < pod.priority
    if pod.policy == "Never" or not cl.has("bound", lower):
        return None
    less = cl.level_sum("bound", lower)
    more = cl.level_sum("nominated", lambda priority: priority >= pod.priority, but=pod)
    plain = cl.plain(pod)
    possible = less["pods"] > 0
    if plain:
        possible &= resources_fit(pod, cl, less, more)
    want = min(max(len(cl.names) * MIN_CANDIDATE_NODES_PERCENTAGE // 100,
                   MIN_CANDIDATE_NODES_ABSOLUTE), len(cl.names))
    found = []   # (lowest first: top priority, summed priorities, victims), earliest top start, ...
    for i in cl.by_name[possible[cl.by_name]].tolist():
        victims = victims_on(pod, cl, pods, i, plain, less, more)
        if victims:
            top = victims[0].priority   # most important first
            found.append(((top, sum(v.priority + 2**31 for v in victims), len(victims)),
                          min(v.start for v in victims if v.priority == top), i, victims))
            if len(found) == want:
                break
    if not found:
        return None
    least = min(c[0] for c in found)
    found = [c for c in found if c[0] == least]
    latest = max(c[1] for c in found)
    return next(c[2:] for c in found if c[1] == latest)


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
    job's three counts, per step (scheduled, unschedulable), where each pod
    that is left ended up (``placements``: a victim is not among them), the
    victims in the order they went (``evicted``) and the nominations that
    stand at the end (``nominated``)."""
    cl, pods, classes = Cluster(), {}, PriorityClasses()
    backoff: dict = {}   # pod -> (attempts, the last pass it sits out)
    born: list = []      # pods created with a nodeName, to be charged once their node has a place
    evicted: list = []
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
                    pod = Pod(obj, classes)
                    pods[pod.name] = pod
                    if pod.born_on:
                        pod.node = pod.born_on
                        born.append(pod)
                elif obj["kind"] == "PriorityClass":
                    classes.add(obj)
                else:
                    raise NotCovered(f"creation of a {obj['kind']}")
            elif "deleteOperation" in op:
                kind = op["deleteOperation"]["typeMeta"]["kind"]
                name = op["deleteOperation"]["objectMeta"]["name"]
                flush = True
                if kind == "Node":
                    for waiting in sorted(cl.nominated_on.get(name, ())):
                        cl.denominate(pods[waiting])
                    drained |= cl.remove(name)
                elif kind == "Pod":
                    pod = pods.pop(name, None)
                    if pod is None:
                        raise NotCovered(f"deletion of the pod {name}, which is gone (a victim?)")
                    backoff.pop(name, None)
                    cl.denominate(pod)
                    if pod in born:
                        born.remove(pod)
                    elif pod.node is not None and pod.name not in drained:
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
            # PrioritySort: priority, then creation time as written, then name.
            queue = sorted((p for p in pods.values() if p.node is None
                            and not (p.name in backoff and backoff[p.name][1] >= passes)),
                           key=lambda p: p.queue_key)
            if queue or born:
                cl.sync()
            for pod in born:
                if pod.node not in cl.place:
                    raise NotCovered(f"a pod created on {pod.node}, which is no node")
                cl.charge(pod, cl.place[pod.node], +1)
            born = []
            for pod in queue[:max_pods_per_pass]:
                name = pod.name
                ok = feasible_with_nominated(pod, cl, pods)
                best = None
                if pod.nominated is not None and ok[cl.place[pod.nominated]]:
                    best = cl.place[pod.nominated]   # evaluateNominatedNode: that node alone, first
                elif ok.any():
                    total = total_scores(pod, cl, pods, ok, precision)
                    best = int(np.argmax(np.where(ok, total, np.iinfo(np.int64).min)))
                if best is not None:
                    cl.denominate(pod)
                    pod.node = cl.names[best]
                    cl.charge(pod, best, +1)
                    backoff.pop(name, None)
                    done[0] += 1
                    continue
                done[1] += 1
                chosen = preempt(pod, cl, pods)
                cl.denominate(pod)
                if chosen is None:
                    attempts = backoff.get(name, (0, 0))[0] + 1
                    backoff[name] = (attempts,
                                     passes + min(2 ** (attempts - 1), MAX_BACKOFF_PASSES))
                    continue
                at, victims = chosen
                for victim in victims:
                    cl.charge(victim, at, -1)
                    del pods[victim.name]
                    evicted.append(victim.name)
                # prepareCandidate: pods of a lower priority nominated here have to look again.
                for waiting in sorted(cl.nominated_on.get(cl.names[at], ())):
                    if pods[waiting].priority < pod.priority:
                        cl.denominate(pods[waiting])
                cl.nominate(pod, at)
                backoff.pop(name, None)
        scheduled += done[0]
        unschedulable += done[1]
        per_step.append(tuple(done))
    return {"eventsApplied": events, "podsScheduled": scheduled,
            "unschedulableAttempts": unschedulable, "steps": per_step,
            "placements": {p.name: p.node for p in pods.values()},
            "evicted": evicted,
            "nominated": {p.name: p.nominated for p in pods.values() if p.nominated}}
