"""Plain-Python checks of the guarantees a configuration states, over the
documents the served path returns.  Sums, rankings and counts that share
nothing with the kernels (copied from ``chip_smoke.py`` ``check_served`` /
``check_replay`` / ``check_device``, PR 21, and extended by the ranking
check).  Every check returns *comparisons*: ``{"name", "value", "limit",
"ok"}`` — the run prints each number beside its limit, and ``correct`` is
the conjunction of the ``ok``s.
"""

from __future__ import annotations

from fractions import Fraction
import json

PREFIX = "kube-scheduler-simulator.sigs.k8s.io/"
FILTER_KEY = PREFIX + "filter-result"
FINAL_SCORE_KEY = PREFIX + "finalscore-result"
SELECTED_NODE_KEY = PREFIX + "selected-node"
#: How a top-level annotation key reads in the raw export: inside the
#: ``result-history`` annotation the same key is backslash-escaped, so this
#: matches once per annotated pod and the timed request need not parse
#: 235 MB to know the export is complete.
FILTER_KEY_RAW = ('"' + FILTER_KEY + '":').encode()

_SUFFIX = {
    "m": 1, "": 1000, "k": 10**6, "M": 10**9, "G": 10**12, "T": 10**15,
    "Ki": 1000 * 2**10, "Mi": 1000 * 2**20, "Gi": 1000 * 2**30, "Ti": 1000 * 2**40,
}


def milli(quantity: "str | int | None") -> int:
    """A Kubernetes resource quantity in integer milli-units."""
    if quantity is None:
        return 0
    q = str(quantity)
    digits = q.rstrip("KMGTikm")
    return int(Fraction(digits) * _SUFFIX[q[len(digits):]])


def cmp_eq(name: str, value, want) -> dict:
    return {"name": name, "value": value, "limit": want, "ok": value == want}


def cmp_max(name: str, value, limit) -> dict:
    return {"name": name, "value": value, "limit": limit,
            "ok": value is not None and value <= limit}


def cmp_min(name: str, value, limit) -> dict:
    return {"name": name, "value": value, "limit": limit,
            "ok": value is not None and value >= limit}


def check_device(ident: dict, want_platform: str, chips: int) -> "list[dict]":
    """The backend the SERVER reports.  JAX falls back to the CPU with only
    a warning when the TPU fails to initialise; this is where that shows."""
    return [
        cmp_eq("device.platform", ident.get("platform"), want_platform),
        cmp_min("device.count", ident.get("device_count") or ident.get("count"), chips),
    ]


def check_job(doc: dict, guarantees: dict, *, steps: int, lock: "list | None") -> "list[dict]":
    """A device-replay job's result document against the configuration's
    guarantees: succeeded, every step on the device path, nothing of the
    containment (fallback router, watchdog, breaker) fired, and the locked
    counts where this seed and stream have a lock."""
    if doc.get("state") != "succeeded":
        return [cmp_eq("job.state", doc.get("state"), "succeeded")]
    result = doc.get("result") or {}
    replay = doc.get("replay") or {}
    out = [cmp_eq("job.state", "succeeded", "succeeded"),
           cmp_eq("job.steps", result.get("steps"), steps)]
    if guarantees.get("every_step_on_device"):
        out.append(cmp_eq("replay.device_steps", replay.get("device_steps"), steps))
    for key, want in (guarantees.get("replay_equals") or {}).items():
        out.append(cmp_eq("replay." + key, replay.get(key), want))
    if lock is not None:
        got = [result.get("podsScheduled"), result.get("unschedulableAttempts")]
        out.append(cmp_eq("job.locked_counts", got, list(lock)))
    return out


#: What a job's result document counts; the answer the reference replays.
COUNT_KEYS = ("eventsApplied", "podsScheduled", "unschedulableAttempts")


def job_counts(doc: dict) -> list:
    result = doc.get("result") or {}
    return [result.get(k) for k in COUNT_KEYS]


def check_export(export: dict, nodes: list, pods: list, guarantees: dict) -> "list[dict]":
    """The exported cluster against the imported one: every pod annotated;
    bound only to imported nodes; no node over its allocatable; every bound
    pod on the node its ``selected-node`` names, which passes every filter
    recorded for it and whose summed final score no feasible node beats."""
    alloc = {n["metadata"]["name"]: n["status"]["allocatable"] for n in nodes}
    exported = {p["metadata"]["name"]: p for p in export.get("pods") or []}
    used = {name: [0, 0, 0] for name in alloc}  # milli-cpu, milli-bytes, pods
    annotated = bound = missing = unknown_node = unparsable = 0
    not_selected = filter_refused = outranked = 0
    for src in pods:
        pod = exported.get(src["metadata"]["name"])
        if pod is None:
            missing += 1
            continue
        anno = pod["metadata"].get("annotations") or {}
        has_results = FILTER_KEY in anno and FINAL_SCORE_KEY in anno
        annotated += has_results
        node = pod.get("spec", {}).get("nodeName")
        if not node:
            continue
        bound += 1
        if node not in alloc:
            unknown_node += 1
            continue
        u = used[node]
        for c in pod["spec"].get("containers") or []:
            req = (c.get("resources") or {}).get("requests") or {}
            u[0] += milli(req.get("cpu"))
            u[1] += milli(req.get("memory"))
        u[2] += 1
        if anno.get(SELECTED_NODE_KEY) != node:
            not_selected += 1
        if not has_results:
            continue
        try:
            verdicts = json.loads(anno[FILTER_KEY])
            finals = json.loads(anno[FINAL_SCORE_KEY])
            totals = {n: sum(int(v) for v in per.values()) for n, per in finals.items()}
        except (ValueError, AttributeError, TypeError):
            unparsable += 1
            continue
        if not set(verdicts) <= set(alloc) or not set(totals) <= set(alloc):
            unknown_node += 1
        mine = verdicts.get(node)
        if not mine or any(v != "passed" for v in mine.values()):
            filter_refused += 1
        if totals:
            outranked += node not in totals or totals[node] < max(totals.values())
        else:
            # Upstream skips scoring when a single node is feasible.
            feasible = [n for n, per in verdicts.items()
                        if all(v == "passed" for v in per.values())]
            outranked += feasible != [node]
    over = sum(
        1 for name, (cpu, mem, count) in used.items()
        if cpu > milli(alloc[name]["cpu"]) or mem > milli(alloc[name]["memory"])
        or count > int(alloc[name]["pods"])
    )
    return [
        cmp_eq("export.pods_missing", missing, 0),
        cmp_eq("export.pods_annotated", annotated, len(pods)),
        cmp_min("export.pods_bound", bound, int(guarantees.get("min_bound_share", 0) * len(pods))),
        cmp_eq("export.bound_to_unknown_node", unknown_node, 0),
        cmp_eq("export.annotations_unparsable", unparsable, 0),
        cmp_eq("export.nodes_over_allocatable", over, 0),
        cmp_eq("export.bound_not_on_selected_node", not_selected, 0),
        cmp_eq("export.bound_on_filtered_node", filter_refused, 0),
        cmp_eq("export.bound_on_outranked_node", outranked, 0),
    ]
