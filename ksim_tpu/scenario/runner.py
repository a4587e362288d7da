"""Scenario runner: apply operations step-by-step, schedule, collect.

KEP-140 semantics (reference keps/140-scenario-based-simulation/README.md):
operations carry a step number; all operations of a step are applied,
then the scheduler runs, then results are recorded.  The engine's program
cache (engine/core.py _Program) keeps re-jits bounded to the distinct
padded-shape buckets the churn wanders through.
"""

from __future__ import annotations

import logging
import time
from dataclasses import dataclass, field
from typing import Any, Iterable, Sequence

from ksim_tpu.engine.annotations import apply_results_to_pod
from ksim_tpu.obs import TRACE
from ksim_tpu.scheduler.service import SchedulerService
from ksim_tpu.state import objcache
from ksim_tpu.state.cluster import ClusterStore
from ksim_tpu.state.resources import JSON, name_of, namespace_of

logger = logging.getLogger(__name__)


@dataclass(frozen=True)
class Operation:
    """One timed mutation (KEP-140 ScenarioOperation: createOperation /
    patchOperation / deleteOperation at a step)."""

    step: int
    op: str  # create | update | patch | delete | done
    kind: str
    obj: JSON | None = None  # create/update payload; merge patch for "patch"
    name: str = ""  # patch/delete target
    namespace: str = ""


@dataclass
class StepResult:
    step: int
    ops_applied: int
    scheduled: int  # pods bound this step
    unschedulable: int  # scheduling attempts with no feasible node
    pending_after: int


@dataclass
class ScenarioResult:
    """The .status.result analogue: per-step aggregates + totals."""

    steps: list[StepResult] = field(default_factory=list)
    events_applied: int = 0
    pods_scheduled: int = 0
    unschedulable_attempts: int = 0
    wall_seconds: float = 0.0
    succeeded: bool = False  # a doneOperation step completed (KEP-140)
    # Per-phase wall-clock split of wall_seconds, sourced from the trace
    # plane (obs.SPAN_NAMES keys): device path = replay.lower /
    # replay.dispatch / replay.reconcile; per-pass host path =
    # runner.step (which NESTS its service.schedule span — the two are
    # reported side by side, not additive).  A parent's timed stages
    # (replay.reconcile.*, service.featurize.*, ...) are keys of their
    # own: per-run sums, each no greater than its parent's entry.
    phase_seconds: dict[str, float] = field(default_factory=dict)
    # Fleet replay (engine/fleet.py): the per-lane ScenarioResults, in
    # lane order.  The top-level counts/steps are then AGGREGATES over
    # the lanes (events/scheduled/unschedulable summed; ``steps`` stays
    # empty — per-trajectory step records live on the lanes) and the
    # phase split covers the whole fleet run (spans are shared across
    # lanes by design — the group dispatch IS one span).
    lanes: "list[ScenarioResult] | None" = None

    @property
    def events_per_second(self) -> float:
        return self.events_applied / self.wall_seconds if self.wall_seconds else 0.0


def _phase_split(phase0: dict) -> dict[str, float]:
    """Seconds per span name since ``phase0`` (an earlier
    ``TRACE.phase_totals()``).  The trace plane is process-global: the
    totals are diffed around a run so earlier runs don't bleed into its
    split."""
    split = {}
    for name, (total, count) in TRACE.phase_totals().items():
        prev_total, prev_count = phase0.get(name, (0.0, 0))
        if count > prev_count:
            split[name] = round(total - prev_total, 6)
    return split


def placed_pod(
    obj: JSON,
    *,
    anno: "dict[str, str] | None" = None,
    node: "str | None" = None,
    nominated: "str | None" = None,
    gave_up: bool = False,
) -> JSON:
    """The pod as the segment reconciler leaves it after one attempt: a
    ``ClusterStore.rewrap`` builder.  Result annotations merged into a
    NEW ``metadata.annotations``; then the bind (``spec.nodeName``,
    phase Running, any nomination dropped), or the nomination, or the
    nomination given up.  Only the dicts on the way to a changed key
    are new; everything else (containers, affinity and spread terms,
    resource maps) is shared with ``obj``, which is frozen and is not
    touched.

    Key for key the object the deep-copying ``patch`` closure stored
    here before: annotations only when the attempt recorded some,
    ``status`` created by a bind or a nomination but not by a give-up.
    That is NOT ``SchedulerService._bind_results``' rebuild, which
    always writes ``metadata.annotations`` (possibly ``{}``), ``spec``
    and ``status``: the two stay apart because their objects differ."""
    new = dict(obj)
    if anno:
        md = dict(obj.get("metadata") or {})
        md["annotations"] = apply_results_to_pod(
            dict(md.get("annotations") or {}), anno
        )
        new["metadata"] = md
    if node:
        new["spec"] = dict(obj.get("spec") or {}, nodeName=node)
        status = dict(obj.get("status") or {}, phase="Running")
        status.pop("nominatedNodeName", None)
        new["status"] = status
    elif nominated:
        new["status"] = dict(obj.get("status") or {}, nominatedNodeName=nominated)
    elif gave_up:
        _drop(new, "status", "nominatedNodeName")
    return new


def requeued_pod(obj: JSON) -> JSON:
    """A pod of a drained node back in the queue (``rewrap`` builder):
    ``spec.nodeName`` and ``status.phase`` gone, the rest shared."""
    new = dict(obj)
    _drop(new, "spec", "nodeName")
    _drop(new, "status", "phase")
    return new


def _drop(new: JSON, section: str, key: str) -> None:
    """Replace ``new[section]`` by a copy without ``key`` — where it has
    the key; the section is shared with a frozen object otherwise."""
    sub = new.get(section)
    if sub and key in sub:
        sub = dict(sub)
        del sub[key]
        new[section] = sub


class _StreamFeeder:
    """Incremental step-grouper over a streaming operation source
    (traces/stream.py ``TraceOperationStream``): the windowed twin of
    ``ScenarioRunner._group_by_step``.

    ``keys``/``by_step`` grow as windows arrive; a step is COMPLETE (and
    appended to ``keys``) only once a later step's first operation — or
    EOF — proves no more operations belong to it.  Batch lists keep
    their object identity for as long as they are resident: the replay
    driver's speculative-prelower match (engine/replay.py ``_take_spec``)
    is identity-based, so ``by_step[s]`` must return the SAME list every
    iteration.  ``release`` evicts batches the run has committed past —
    that eviction is the O(window) half of the memory claim; the step
    keys themselves (small ints) are kept for cursor arithmetic.

    ``ensure`` BLOCKS on the producer queue; ``prefetch`` never blocks
    and is the replay driver's ingest-hook entry (drain while the
    device dispatch is in flight).  Both run on the consumer (main)
    thread only."""

    def __init__(self, stream) -> None:
        self._stream = stream
        self._it = iter(stream)
        self.keys: list[int] = []  # complete steps, ascending
        self.by_step: dict[int, list[Operation]] = {}
        self._open_step: "int | None" = None
        self._open_batch: "list[Operation] | None" = None
        self._eof = False
        self._released = 0  # keys-index cursor: everything below is evicted

    def _accept(self, op: Operation) -> None:
        if self._open_step is None or op.step > self._open_step:
            if self._open_step is not None:
                self._seal()
            elif self.keys and op.step <= self.keys[-1]:
                raise ValueError(
                    f"streaming operations out of step order: step {op.step} "
                    f"after step {self.keys[-1]} was sealed"
                )
            self._open_step = op.step
            self._open_batch = [op]
        elif op.step == self._open_step:
            self._open_batch.append(op)
        else:
            raise ValueError(
                f"streaming operations out of step order: step {op.step} "
                f"after step {self._open_step}"
            )

    def _seal(self) -> None:
        self.by_step[self._open_step] = self._open_batch
        self.keys.append(self._open_step)
        self._open_step = None
        self._open_batch = None

    def ensure(self, n: int) -> None:
        """Block until ``n`` complete steps exist or the stream ends."""
        while len(self.keys) < n and not self._eof:
            try:
                op = next(self._it)
            except StopIteration:
                self._eof = True
                if self._open_step is not None:
                    self._seal()
                return
            self._accept(op)

    def prefetch(self, n: int) -> int:
        """Drain whatever the producer has READY toward ``n`` complete
        steps; never blocks.  Producer-side errors are deferred: they
        re-raise at the next blocking ``ensure``."""
        pulled = 0
        while len(self.keys) < n and not self._eof:
            op = self._stream.next_nowait()
            if op is None:
                break
            self._accept(op)
            pulled += 1
        return pulled

    def release(self, upto: int) -> None:
        """Evict committed step batches (keys indices below ``upto``)."""
        while self._released < min(upto, len(self.keys)):
            self.by_step.pop(self.keys[self._released], None)
            self._released += 1


class ScenarioRunner:
    """Replays an operation stream against a store + scheduler service.

    ``requeue_on_node_delete`` re-marks a deleted node's bound pods as
    pending (the "node preemption" churn of BASELINE config 5 — a drained
    node's pods go back through scheduling, as a controller would recreate
    them).  ``record`` defaults to "selection": full per-node result
    recording multiplies host-side work by O(N) per pod and is opt-in for
    replay (the per-pass results remain available through the service's
    normal watch-driven path)."""

    def __init__(
        self,
        store: ClusterStore | None = None,
        service: SchedulerService | None = None,
        *,
        record: str = "selection",
        preemption: bool = False,
        node_sampling: bool = False,
        requeue_on_node_delete: bool = True,
        max_pods_per_pass: int | None = None,
        pod_bucket_min: int | None = None,
        device_replay: bool = False,
        device_segment_steps: int | None = None,
        fleet: int | None = None,
        fleet_faults: str | None = None,
        cancel: "Any | None" = None,
        private_faults: "Any | None" = None,
        checkpoint_hook: "Any | None" = None,
    ) -> None:
        """``device_replay=True`` routes supported step segments through
        the device-resident path (engine/replay.py): K steps of event
        application + scheduling per compiled dispatch, host reconcile at
        segment boundaries, byte-identical scheduling counts.  Steps
        containing ops outside the tensor vocabulary (patch/update/done,
        non-pod/node kinds, pods with host ports or volumes, ...) fall
        back to this per-pass path automatically; DefaultPreemption
        (``preemption=True``) and ``record="full"`` segments stay
        on-device since round 7 (on-device victim search + streamed
        result tensors).

        ``cancel`` (a ``threading.Event``-like object) makes the run
        cooperatively cancellable — the job plane's DELETE surface: the
        flag is checked before every per-pass step AND inside the
        segment reconcile loop, where a set flag raises
        ``errors.RunCancelled`` INSIDE the store transaction, rolling
        the whole in-flight segment back before propagating (the store
        is byte-identical to the segment's start — a cancelled job
        never leaves a half-applied window behind).

        ``private_faults`` (a ``FaultPlane``) is this run's PRIVATE
        fault plane (the job plane's ``KSIM_JOBS_FAULTS``): checked
        next to the process-global ``FAULTS`` at the replay sites
        (``replay.lower`` / ``replay.dispatch`` / ``replay.reconcile``)
        exactly like a fleet lane's plane, so a chaos schedule degrades
        THIS run alone while concurrent runs in the same process stay
        healthy.  Mutually exclusive with ``fleet`` (use
        ``fleet_faults`` there).

        ``checkpoint_hook`` (the job plane's incremental-resume cadence,
        ksim_tpu/jobs/manager.py) is called as ``hook(cursor, driver,
        result)`` after every COMMITTED device segment — cursor is the
        index into the sorted step keys the next iteration starts from,
        i.e. exactly the ``resume_cursor`` a later ``run`` needs to
        replay only the remaining suffix.  The hook runs outside the
        store transaction (the segment is fully committed; a mid-hook
        crash loses at most the not-yet-journaled checkpoint, never
        store integrity) and must not raise for policy reasons — skip
        internally and return.

        ``fleet=S`` (requires ``device_replay=True``) replays S
        INDEPENDENT trajectories — each with its own store, service and
        replay driver — advancing the whole fleet K steps per vmapped
        device dispatch with the shared universe lowered once per window
        (engine/fleet.py).  ``run`` then returns the aggregate result
        with the per-lane results on ``.lanes``; per-lane chaos arms via
        ``fleet_faults`` / ``KSIM_FLEET_FAULTS`` (``lane:site=schedule``
        entries), per-lane streams via ``run(..., lane_ops=...)``.  Lane
        0 reuses this runner's own store/service, so existing evidence
        surfaces (``.store``, ``.replay_driver``) stay meaningful."""
        if fleet is not None:
            if fleet < 2:
                raise ValueError("fleet needs at least 2 lanes")
            if not device_replay:
                raise ValueError("fleet replay requires device_replay=True")
            if store is not None or service is not None:
                raise ValueError(
                    "fleet lanes build their own stores/services; pass the "
                    "service CONFIG (record/preemption/...) instead"
                )
        elif fleet_faults is not None:
            # A lane fault spec with no fleet would be silently dropped —
            # the vacuously-green chaos sweep parse_fleet_faults refuses.
            raise ValueError("fleet_faults requires fleet=S")
        if fleet is not None and private_faults is not None:
            raise ValueError(
                "private_faults is the solo-run chaos surface; fleet lanes "
                "arm per-lane planes via fleet_faults/KSIM_FLEET_FAULTS"
            )
        self.store = store if store is not None else ClusterStore()
        self.service = (
            service
            if service is not None
            else SchedulerService(
                self.store,
                record=record,
                preemption=preemption,
                node_sampling=node_sampling,
                max_pods_per_pass=max_pods_per_pass,
                pod_bucket_min=pod_bucket_min,
            )
        )
        self._requeue = requeue_on_node_delete
        self._drained_nodes: set[str] = set()
        # Store writes of objects that were there, since the segment
        # reconcile in hand began staging: by re-wrap (``placed_pod``,
        # ``requeued_pod``, the nominations a preemptor clears) and
        # through the deep-copying ``patch`` (a patchOperation).  The
        # driver sums them at commit (``replay.reconcile_writes_*``).
        self._writes_shared = 0
        self._writes_copied = 0
        self._device_replay = device_replay
        self._device_segment_steps = device_segment_steps
        self._fleet = fleet
        self._fleet_faults = fleet_faults
        # Per-lane service construction config (fleet lanes must match
        # lane 0's scheduling semantics exactly).
        self._lane_cfg = dict(
            record=record,
            preemption=preemption,
            node_sampling=node_sampling,
            max_pods_per_pass=max_pods_per_pass,
            pod_bucket_min=pod_bucket_min,
        )
        # Fleet-lane identity: set on per-lane runners so the reconcile
        # and per-pass spans (and the lane's private fault plane) stay
        # attributable per trajectory.
        self._lane: int | None = None
        # One private-plane slot serves both chaos surfaces: fleet lanes
        # (set per lane in _run_fleet) and solo job runs (private_faults
        # here) — the reconcile/driver checks are identical.
        self._lane_faults = private_faults
        # Cooperative cancellation flag (Event-like; see __init__ doc).
        self._cancel = cancel
        # Post-commit segment callback (job-plane checkpoints; see
        # __init__ doc).  None for fleet lanes — cohort segments commit
        # lane-by-lane and a per-lane cursor is not a resume point.
        self._checkpoint_hook = checkpoint_hook
        # The last run's ReplayDriver (evidence counters: device_steps,
        # fallback_steps, device_round_trips, unsupported reasons).
        self.replay_driver = None
        # Fleet evidence (set by a fleet run): the FleetDriver (stats())
        # and the FleetLane list (per-lane runners/drivers/results).
        self.fleet_driver = None
        self.fleet_lanes = None

    def _check_cancelled(self) -> None:
        """Raise ``RunCancelled`` if the run's cancel flag is set.
        Called between per-pass steps and inside the segment reconcile
        loop — the latter aborts (and rolls back) the in-flight store
        transaction, so cancellation is never store-corrupting."""
        if self._cancel is not None and self._cancel.is_set():
            from ksim_tpu.errors import RunCancelled

            raise RunCancelled("scenario run cancelled")

    # -- one operation ------------------------------------------------------

    @staticmethod
    def _own(obj: JSON) -> JSON:
        """Hand an operation's object to the store without a deepcopy
        (two per create were ~11% of the 50k churn replay).  Only the
        top level and metadata are copied: the store writes rv/uid/
        namespace into metadata, and a same-ops-list replay must not see
        the previous run's values.  Nested structures are shared — safe
        under the store's replace-on-write contract (nothing mutates
        them in place)."""
        out = dict(obj)
        md = out.get("metadata")
        out["metadata"] = dict(md) if isinstance(md, dict) else {}
        return out

    def _apply(self, op: Operation) -> None:
        if op.op == "create":
            self.store.create(op.kind, self._own(op.obj), copy_obj=False)
        elif op.op == "update":
            self.store.update(op.kind, self._own(op.obj), copy_obj=False)
        elif op.op == "patch":
            # KEP-140 PatchOperation: RFC 7386 merge patch (scenario/spec.py).
            # Object identity is immutable under patch, like the apiserver:
            # name/namespace/uid survive whatever the patch does to
            # metadata (a patch can't rename or unkey an object).
            from ksim_tpu.scenario.spec import ScenarioSpecError, merge_patch

            def apply_merge(obj: JSON) -> None:
                merged = merge_patch(obj, op.obj)
                if not isinstance(merged, dict):
                    raise ScenarioSpecError(
                        f"patch for {op.kind}/{op.name} must produce an object"
                    )
                orig_md = obj.get("metadata", {})
                md = merged.get("metadata")
                md = dict(md) if isinstance(md, dict) else {}
                for key in ("name", "namespace", "uid", "resourceVersion"):
                    if orig_md.get(key) is not None:
                        md[key] = orig_md[key]
                merged["metadata"] = md
                obj.clear()
                obj.update(merged)

            self.store.patch(
                op.kind, op.name, op.namespace, apply_merge, copy_ret=False
            )
            self._writes_copied += 1
        elif op.op == "delete":
            if op.kind == "nodes" and self._requeue:
                # Deferred: run() re-queues all drained nodes' pods in ONE
                # pod walk after the step's ops (walking the whole pod
                # list per node delete dominated churn host time).
                self._drained_nodes.add(op.name)
            self.store.delete(op.kind, op.name, op.namespace)
        elif op.op == "done":
            pass  # handled in run(): terminates after this step
        else:
            raise ValueError(f"unknown op {op.op!r}")

    def _requeue_pods_of(self, node_names: set[str]) -> None:
        if not node_names:
            return

        # The store's nodeName bucket index bounds the walk to pods ON
        # the drained nodes (the earlier bound-side walk still scanned
        # every bound pod per drain — ~10s of the 50k replay); the
        # matches sort by (name, "ns/name") — exactly list("pods")'s
        # (name, key) order — so the writes apply (and consume
        # resourceVersions) in the same order the full walk produced.
        # Each is a re-wrap that shares the manifest (``requeued_pod``).
        hit = [
            (name_of(p), f"{namespace_of(p) or 'default'}/{name_of(p)}", namespace_of(p))
            for p in self.store.pods_on_nodes(node_names)
        ]
        for name, _key, ns in sorted(hit):
            self.store.rewrap("pods", name, ns, requeued_pod)
        self._writes_shared += len(hit)

    # -- replay -------------------------------------------------------------

    def _apply_batch(self, batch: Sequence[Operation]) -> bool:
        """Apply one step's operations to the store (+ deferred requeue).
        Returns whether the step carried a doneOperation."""
        done = False
        self._drained_nodes = set()
        for op in batch:
            self._apply(op)
            done = done or op.op == "done"
        self._requeue_pods_of(self._drained_nodes)
        return done

    def _run_step(self, step: int, batch: list[Operation], result: ScenarioResult) -> bool:
        """The per-pass step body: apply ops, flush, one scheduling pass.
        Returns the done flag."""
        tags = {} if self._lane is None else {"lane": self._lane}
        with TRACE.span("runner.step", step=step, ops=len(batch), **tags):
            return self._run_step_traced(step, batch, result)

    def _run_step_traced(
        self, step: int, batch: list[Operation], result: ScenarioResult
    ) -> bool:
        done = self._apply_batch(batch)
        result.events_applied += len(batch)
        # The runner drives the store directly (no watch loop), so it
        # raises the capacity-freed/topology-changed signal itself:
        # node ops and pod deletions flush the unschedulable backoff.
        if any(
            op.kind in ("nodes", "persistentvolumes",
                        "persistentvolumeclaims", "storageclasses")
            or (op.op == "delete" and op.kind == "pods")
            for op in batch
        ):
            self.service.flush_backoff()
        placements = self.service.schedule_pending()
        scheduled = sum(1 for v in placements.values() if v is not None)
        unsched = len(placements) - scheduled
        result.pods_scheduled += scheduled
        result.unschedulable_attempts += unsched
        result.steps.append(
            StepResult(
                step=step,
                ops_applied=len(batch),
                scheduled=scheduled,
                unschedulable=unsched,
                pending_after=self.service.pending_count(),
            )
        )
        return done

    def _stage_device_step(
        self,
        batch: list[Operation],
        outcome,
        eviction_sink: list[tuple[str, str]],
    ) -> None:
        """Stage one device-computed step's STORE writes: the step's ops
        (+ requeue), then the pass's placements in commit order.  With
        per-attempt detail (preemption / record="full" segments) each
        attempt's write mirrors the per-pass rebuild — result
        annotations, bind or nomination — followed by its preemption
        victims' evictions, in the exact per-pass order.  Runs inside
        the segment transaction: store-only, no service/result effects
        (victim eviction listeners defer into ``eviction_sink`` and fire
        after commit).  A placement replaces the pod by ``placed_pod``'s
        re-wrap, never by a deep copy.  Two timed stages of ``replay.reconcile`` a step:
        ``apply`` (the step's own operations) and ``write`` (its
        placements, up to the next step's ``apply``)."""
        TRACE.stage("replay.reconcile.apply")
        self._apply_batch(batch)
        TRACE.stage("replay.reconcile.write")
        if outcome.attempts is not None:
            # The write-back of the step's preemptions (nominations, their
            # victims' evictions, the nominations they clear) under ONE
            # child span, from the first preemptor's attempt to the last
            # one's: a job that evicts 15,000 pods must not hide that cost
            # in the parent, and a span a preemption would push everything
            # else out of the job's ring.  It is a ring span and nests
            # inside the ``write`` stage's interval, which stays open.
            atts = outcome.attempts
            hits = [
                i for i, a in enumerate(atts) if a.victims or a.nominated or a.gave_up
            ]
            first, end = (hits[0], hits[-1] + 1) if hits else (len(atts), len(atts))
            for att in atts[:first]:
                self._stage_attempt(att, eviction_sink)
            if hits:
                with TRACE.span(
                    "replay.reconcile.evict",
                    preemptions=len(hits),
                    victims=sum(len(atts[i].victims) for i in hits),
                ):
                    for att in atts[first:end]:
                        self._stage_attempt(att, eviction_sink)
            for att in atts[end:]:
                self._stage_attempt(att, eviction_sink)
        else:
            rewrap = self.store.rewrap
            for ns, name, node in outcome.binds:
                rewrap("pods", name, ns, lambda obj, node=node: placed_pod(obj, node=node))
            self._writes_shared += len(outcome.binds)

    def _stage_attempt(self, att, eviction_sink: list[tuple[str, str]]) -> None:
        """One attempt's store writes, mirroring the per-pass rebuild:
        result annotations, the bind or the nomination (or the
        nomination given up), then the victims' evictions and the
        lower-priority nominations the preemptor's node loses."""
        if att.anno or att.node or att.nominated or att.gave_up:
            self.store.rewrap(
                "pods",
                att.name,
                att.namespace,
                lambda obj: placed_pod(
                    obj,
                    anno=att.anno,
                    node=att.node,
                    nominated=att.nominated,
                    gave_up=att.gave_up,
                ),
            )
            self._writes_shared += 1
        # Victim evictions go through the service so delete
        # semantics match the per-pass path; listener callbacks
        # defer to post-commit (a rolled-back segment must never
        # have announced an eviction that did not happen).
        for vns, vname in att.victims:
            self.service._evict_victim(
                {"metadata": {"name": vname, "namespace": vns}},
                listener_sink=eviction_sink,
            )
        if att.cleared:
            from ksim_tpu.state.priorities import build_priority_resolver

            self._writes_shared += self.service._clear_lower_nominations(
                att.nominated,
                self.store.get("pods", att.name, att.namespace),
                priority_of=build_priority_resolver(
                    self.store.list("priorityclasses", copy_objs=False)
                ),
            )

    def _record_device_step(
        self, step: int, batch: list[Operation], outcome, result: ScenarioResult
    ) -> None:
        """Post-commit result accounting for one device step."""
        result.events_applied += len(batch)
        result.pods_scheduled += outcome.scheduled
        result.unschedulable_attempts += outcome.unschedulable
        result.steps.append(
            StepResult(
                step=step,
                ops_applied=len(batch),
                scheduled=outcome.scheduled,
                unschedulable=outcome.unschedulable,
                pending_after=outcome.pending_after,
            )
        )

    def _commit_segment(
        self, seg_keys, batches, seg, driver, result: ScenarioResult
    ) -> bool:
        """Reconcile one device-computed segment ALL-OR-NOTHING.

        Every store write of the segment — event ops, requeues,
        binds/nominations/annotations (shallow re-wraps that share the
        frozen manifest: ``placed_pod`` / ``requeued_pod`` through
        ``ClusterStore.rewrap``), victim evictions — stages
        inside one store transaction, the device-vs-store parity check
        runs against the staged state, and only then does the batch
        commit (watch events deliver at commit, in write order).  The
        service-side effects with no rollback story — eviction
        listeners, featurizer slot advances, backoff/pass-count sync,
        result accumulation — run strictly AFTER the commit.

        An INJECTED fault mid-reconcile (the fault plane's
        InjectedFault) rolls the whole segment back and returns False:
        the store is byte-identical to the segment's start and the
        caller proceeds exactly as if the segment had never lowered —
        the window's head step runs per-pass, the remaining steps are
        retried on-device in the next window.  Consecutive rollbacks
        feed the driver's circuit breaker, so a persistently failing
        reconcile stops paying lowering + dispatch + rollback per step.
        Everything else — ReplayParityError, store-integrity errors
        (NotFound/Conflict are device-decode bugs wearing a
        SimulatorError class), programming errors — still propagates
        LOUDLY, but now with the store rolled back rather than
        half-applied: a kernel bug must never be indistinguishable
        from an injected chaos fault."""
        from ksim_tpu.faults import FAULTS, InjectedFault

        evictions: list[tuple[str, str]] = []
        step_nodes: list = []
        self._writes_shared = self._writes_copied = 0
        tags = {} if self._lane is None else {"lane": self._lane}
        try:
            with TRACE.span(
                "replay.reconcile",
                segment=driver._segment_seq,
                steps=len(seg.steps),
                **tags,
            ), self.store.transaction(epoch_exempt=True):
                # epoch_exempt: the segment's own staged writes are the
                # deltas the driver's lower-cache already tracks; only
                # OUT-OF-BAND writes may move the store mutation epoch
                # (and thereby invalidate the cache).  A rollback takes
                # the explicit invalidation path (note_reconcile_fault).
                for batch, outcome in zip(batches, seg.steps):
                    # A cancel landing mid-segment aborts HERE: the
                    # RunCancelled is not an InjectedFault, so it rolls
                    # the transaction back and propagates to the caller
                    # (the job plane marks the job cancelled; the store
                    # is back at the segment's start state).
                    self._check_cancelled()
                    FAULTS.check("replay.reconcile")
                    if self._lane_faults is not None:
                        # The lane's PRIVATE plane (fleet chaos): an
                        # injected fault here rolls back ONLY this
                        # lane's segment.
                        self._lane_faults.check("replay.reconcile")
                    self._stage_device_step(batch, outcome, evictions)
                    # Captured per step for the deferred slot advance:
                    # live node dicts are frozen (replace-on-write), so
                    # the references stay valid after commit.
                    step_nodes.append(
                        self.store.list("nodes", copy_objs=False)
                        if outcome.eligible > 0
                        else None
                    )
                TRACE.stage("replay.reconcile.verify")
                driver.verify_segment(seg)
                # The transaction's exit (every buffered watch event
                # delivered), up to the span's own.
                TRACE.stage("replay.reconcile.commit")
        except InjectedFault as e:
            driver.note_reconcile_fault()
            logger.warning(
                "device segment reconcile aborted (%s: %s); store rolled "
                "back — the window's head step re-runs per-pass, the rest "
                "retries on-device",
                type(e).__name__, e,
            )
            return False
        # What has no rollback story runs after the span; a stage with no
        # ring parent times it.
        with TRACE.stage("replay.reconcile.effects"):
            self.service._notify_evictions(evictions)
            driver.advance_service_slots(step_nodes)
            driver.sync_service(seg)
            driver.note_segment_committed(
                seg, step_nodes, writes=(self._writes_shared, self._writes_copied)
            )
            for step, batch, outcome in zip(seg_keys, batches, seg.steps):
                self._record_device_step(step, batch, outcome, result)
        return True

    def run(
        self,
        ops: Iterable[Operation],
        *,
        lane_ops: "dict[int, Iterable[Operation]] | None" = None,
        resume_cursor: int = 0,
        resume_result: "ScenarioResult | None" = None,
    ) -> ScenarioResult:
        """Apply operations grouped by step; one scheduling pass per step
        (every pending pod is attempted each pass, like the upstream
        queue's flush on cluster events).  With ``device_replay`` on,
        supported K-step segments run as single device dispatches (see
        engine/replay.py); everything else takes this per-pass loop.

        ``resume_cursor``/``resume_result`` are the incremental-resume
        entry (docs/jobs.md): replay starts at sorted-step-key index
        ``resume_cursor`` — the cursor a ``checkpoint_hook`` reported —
        accumulating into ``resume_result`` (the checkpoint's partial
        accounting) instead of a fresh result.  The caller owns restoring
        the matching store/service state first; given that, the suffix
        replay is byte-identical to the uninterrupted run's tail (the
        restored store carries the exact rv counter and mutation epoch,
        the service its pass/backoff/slot-order carries).
        ``wall_seconds`` covers only THIS process's replay.

        With ``fleet=S`` the stream replays on every lane (``lane_ops``
        overrides individual lanes' streams — those lanes run the solo
        device path, outside the shared-universe cohort) and the result
        carries the per-lane results on ``.lanes``.

        A STREAMING source (``ops.streaming_ops`` — traces/stream.py)
        takes the windowed loop: operations are consumed as the
        producer emits them, never materialized whole, with ingest
        overlapping the in-flight device dispatch.  Streaming is the
        solo fresh-run path: fleet replays and incremental resume both
        need the full sorted step-key index up front.

        The whole replay — per-pass steps, device lowering, reconcile —
        runs with the service's parse memo installed on this thread
        (state/objcache.py): what the run memoizes dies with the
        service, not with the process."""
        with objcache.scope(self.service.memo):
            return self._run(
                ops,
                lane_ops=lane_ops,
                resume_cursor=resume_cursor,
                resume_result=resume_result,
            )

    def _run(
        self,
        ops: Iterable[Operation],
        *,
        lane_ops: "dict[int, Iterable[Operation]] | None",
        resume_cursor: int,
        resume_result: "ScenarioResult | None",
    ) -> ScenarioResult:
        if getattr(ops, "streaming_ops", False):
            if self._fleet is not None or lane_ops:
                raise ValueError(
                    "streaming ingest is the solo-run path (fleet replay "
                    "materializes its lanes)"
                )
            if resume_cursor or resume_result is not None:
                raise ValueError(
                    "incremental resume needs materialized operations "
                    "(a resume cursor indexes the full sorted step-key list)"
                )
            if self._checkpoint_hook is not None:
                raise ValueError(
                    "checkpoint_hook needs materialized operations (its "
                    "cursor must stay valid for a later resume)"
                )
            return self._run_streaming(ops)
        if self._fleet is not None:
            if resume_cursor or resume_result is not None:
                raise ValueError(
                    "incremental resume is the solo-run path; fleet runs "
                    "restart from scratch (no per-lane cursor yet)"
                )
            return self._run_fleet(ops, lane_ops)
        if lane_ops:
            raise ValueError("lane_ops requires fleet=S")
        result = resume_result if resume_result is not None else ScenarioResult()
        # Per-phase wall-clock split rides on the trace plane's latency
        # histograms; timing-only mode costs two clock reads per span at
        # segment/pass granularity and never touches scheduling state
        # (the behavior locks hold with it on — tests pin that).
        TRACE.ensure_timing()
        phase0 = TRACE.phase_totals()
        t0 = time.perf_counter()
        by_step, keys = self._group_by_step(ops)
        driver = None
        if self._device_replay:
            from ksim_tpu.engine.replay import SEGMENT_STEPS, ReplayDriver

            driver = ReplayDriver(
                self.store,
                self.service,
                k=self._device_segment_steps or SEGMENT_STEPS,
                requeue_on_node_delete=self._requeue,
                lane_faults=self._lane_faults,
            )
            self.replay_driver = driver
        i = resume_cursor
        while i < len(keys):
            self._check_cancelled()
            if driver is not None:
                # Tails shorter than K no longer fall back: the driver
                # consumes the supported PREFIX of the window (possibly
                # shorter than K for full-record segments or mid-window
                # vocabulary misses) and pads on-device to the compiled
                # shape.  Two windows' worth of batches ride along as
                # LOOKAHEAD: while this window's dispatch runs on the
                # watchdogged worker, the driver pre-lowers the next
                # window's store-independent prefix on this thread (the
                # double-buffered pipeline, engine/replay.py
                # _prelower_next).  The inner batch lists are the same
                # objects every iteration (by_step), so the speculative
                # prefix can be matched to the window that actually runs
                # next by identity alone.
                batches = [by_step[s] for s in keys[i : i + 2 * driver.k]]
                seg = driver.try_segment(batches)
                if seg is not None and self._commit_segment(
                    keys[i : i + len(seg.steps)],
                    batches[: len(seg.steps)],
                    seg,
                    driver,
                    result,
                ):
                    i += len(seg.steps)
                    if self._checkpoint_hook is not None:
                        self._checkpoint_hook(i, driver, result)
                    continue
            step = keys[i]
            if driver is not None:
                driver.fallback_steps += 1
            done = self._run_step(step, by_step[step], result)
            i += 1
            if done:
                # KEP-140 DoneOperation: "when finish the step
                # DoneOperation belongs, this Scenario changes its status
                # to Succeeded" — later steps are not run.
                result.succeeded = True
                break
        result.wall_seconds = time.perf_counter() - t0
        result.phase_seconds = _phase_split(phase0)
        return result

    def _run_streaming(self, stream) -> ScenarioResult:
        """The windowed twin of ``run``'s solo loop: a ``_StreamFeeder``
        stands in for the materialized ``by_step``/``keys`` view, the
        replay driver's ``ingest_hook`` drains ready windows while each
        dispatch is in flight (ingest ∥ prelower ∥ dispatch), and
        committed step batches are evicted as the cursor advances —
        peak host memory is O(window + lookahead), not O(stream).  The
        schedule itself is byte-identical to the materialized run: the
        feeder groups the same operations into the same step batches,
        only their lifetime in memory changes."""
        result = ScenarioResult()
        TRACE.ensure_timing()
        phase0 = TRACE.phase_totals()
        t0 = time.perf_counter()
        feeder = _StreamFeeder(stream)
        driver = None
        try:
            if self._device_replay:
                from ksim_tpu.engine.replay import SEGMENT_STEPS, ReplayDriver

                # The hook's prefetch target is re-aimed every iteration:
                # 4·k steps past the cursor bounds the opportunistic
                # drain, so overlap never turns back into O(stream)
                # buffering on the consumer side.
                target = [0]
                driver = ReplayDriver(
                    self.store,
                    self.service,
                    k=self._device_segment_steps or SEGMENT_STEPS,
                    requeue_on_node_delete=self._requeue,
                    lane_faults=self._lane_faults,
                    ingest_hook=lambda: feeder.prefetch(target[0]),
                )
                self.replay_driver = driver
            i = 0
            while True:
                self._check_cancelled()
                if driver is not None:
                    # The same 2-window lookahead the materialized loop
                    # slices out of ``keys`` — blocking here is the
                    # backpressure point when replay outruns ingest.
                    feeder.ensure(i + 2 * driver.k)
                    target[0] = i + 4 * driver.k
                else:
                    feeder.ensure(i + 1)
                if i >= len(feeder.keys):
                    break
                if driver is not None:
                    batches = [
                        feeder.by_step[s]
                        for s in feeder.keys[i : i + 2 * driver.k]
                    ]
                    seg = driver.try_segment(batches)
                    if seg is not None and self._commit_segment(
                        feeder.keys[i : i + len(seg.steps)],
                        batches[: len(seg.steps)],
                        seg,
                        driver,
                        result,
                    ):
                        i += len(seg.steps)
                        feeder.release(i)
                        continue
                step = feeder.keys[i]
                if driver is not None:
                    driver.fallback_steps += 1
                done = self._run_step(step, feeder.by_step[step], result)
                i += 1
                feeder.release(i)
                if done:
                    result.succeeded = True
                    break
        finally:
            # An abandoned producer blocked on a full queue would leak;
            # close() is idempotent and also covers clean exhaustion.
            stream.close()
        result.wall_seconds = time.perf_counter() - t0
        result.phase_seconds = _phase_split(phase0)
        return result

    @staticmethod
    def _group_by_step(ops: Iterable[Operation]) -> tuple[dict, list]:
        by_step: dict[int, list[Operation]] = {}
        for op in ops:
            by_step.setdefault(op.step, []).append(op)
        return by_step, sorted(by_step)

    def _run_fleet(self, ops, lane_ops) -> ScenarioResult:
        """Fleet replay (engine/fleet.py): S independent trajectories,
        the shared universe lowered once per window, one vmapped
        dispatch per cohort window, per-lane reconcile into each lane's
        own store.  Parity contract: every lane's counts/annotations
        are byte-identical to its solo ``device_replay=True`` run."""
        import os

        # The submission-boundary check catches a cancel that landed
        # before the fleet ever built; mid-run cancels thread through to
        # every lane runner below, so a DELETE lands at the next lane
        # dispatch/reconcile boundary (service round 4 (d)) — the
        # in-flight lane segment rolls back exactly like the solo path.
        self._check_cancelled()
        from ksim_tpu.engine.fleet import FleetDriver, FleetLane, parse_fleet_faults
        from ksim_tpu.engine.replay import SEGMENT_STEPS, ReplayDriver

        n = self._fleet
        if lane_ops:
            # Same refusal parse_fleet_faults makes for out-of-range
            # lanes: a typoed index would silently replay the BASE
            # stream on every lane and the sweep would be vacuous.
            bad = sorted(k for k in lane_ops if not 0 <= k < n)
            if bad:
                raise ValueError(
                    f"lane_ops lanes {bad} outside the fleet (0..{n - 1})"
                )
            if any(getattr(v, "streaming_ops", False) for v in lane_ops.values()):
                raise ValueError(
                    "streaming ingest is the solo-run path (lane_ops streams "
                    "must be materialized)"
                )
        spec = self._fleet_faults
        if spec is None:
            spec = os.environ.get("KSIM_FLEET_FAULTS", "")
        planes = parse_fleet_faults(spec, n) if spec else {}
        base_by_step, base_keys = self._group_by_step(ops)
        lanes: list[FleetLane] = []
        for idx in range(n):
            if idx == 0:
                lane_runner = ScenarioRunner(
                    store=self.store,
                    service=self.service,
                    requeue_on_node_delete=self._requeue,
                    device_replay=True,
                    device_segment_steps=self._device_segment_steps,
                    cancel=self._cancel,
                )
            else:
                lane_runner = ScenarioRunner(
                    requeue_on_node_delete=self._requeue,
                    device_replay=True,
                    device_segment_steps=self._device_segment_steps,
                    cancel=self._cancel,
                    **self._lane_cfg,
                )
            lane_runner._lane = idx
            lane_runner._lane_faults = planes.get(idx)
            lane_runner.service._trace_lane = idx
            # One memo for the whole fleet: the cohort's universe is
            # lowered once, from the leader's objects, for every lane.
            lane_runner.service.memo = self.service.memo
            own = lane_ops.get(idx) if lane_ops else None
            if own is not None:
                # A per-lane stream: this trajectory is divergent from
                # the start and rides the solo device path.
                by_step, keys = self._group_by_step(own)
                shared = False
            else:
                # Cohort lanes share the base dict — the SAME batch list
                # objects, which is what lets the leader's speculative
                # prelower spec match by identity for every lane.
                by_step, keys = base_by_step, base_keys
                shared = True
            driver = ReplayDriver(
                lane_runner.store,
                lane_runner.service,
                k=self._device_segment_steps or SEGMENT_STEPS,
                requeue_on_node_delete=self._requeue,
                lane=idx,
                lane_faults=planes.get(idx),
            )
            lane_runner.replay_driver = driver
            lanes.append(
                FleetLane(
                    idx=idx,
                    runner=lane_runner,
                    driver=driver,
                    keys=keys,
                    by_step=by_step,
                    result=ScenarioResult(),
                    faults=planes.get(idx),
                    shared_stream=shared,
                    convergent=shared,
                )
            )
        fleet = FleetDriver(lanes)
        self.fleet_driver = fleet
        self.fleet_lanes = lanes
        self.replay_driver = lanes[0].driver
        TRACE.ensure_timing()
        phase0 = TRACE.phase_totals()
        t0 = time.perf_counter()
        fleet.run()
        wall = time.perf_counter() - t0
        agg = ScenarioResult(lanes=[ln.result for ln in lanes])
        for ln in lanes:
            ln.result.wall_seconds = wall  # fleet lanes finish together
            agg.events_applied += ln.result.events_applied
            agg.pods_scheduled += ln.result.pods_scheduled
            agg.unschedulable_attempts += ln.result.unschedulable_attempts
        # Solo semantics per lane: succeeded = a doneOperation completed.
        agg.succeeded = all(ln.result.succeeded for ln in lanes)
        agg.wall_seconds = wall
        agg.phase_seconds = _phase_split(phase0)
        return agg
