"""Fleet replay: S independent what-if trajectories, one vmapped dispatch.

The ROADMAP's "millions of users" shape is thousands of INDEPENDENT
scenario variants — policy sweeps, Monte-Carlo chaos, autoscaler tuning
— each a full churn trajectory.  Running them solo pays S times the
segment lowering and S times the dispatch latency for work that shares
one pod/node universe.  This module multiplexes them:

- Every lane is a COMPLETE solo stack — its own ClusterStore, its own
  SchedulerService, its own ReplayDriver (cache, breaker, counters) —
  so per-lane reconcile, per-lane fallback and per-lane evidence are
  the solo code paths verbatim (scenario/runner.py drives them).
- Lanes replaying the SAME base stream form the CONVERGENT COHORT: the
  cohort leader lowers each window ONCE (``ReplayDriver.prepare_segment``
  — the shared-universe, O(delta)-cached lowering), and one
  ``jax.vmap``-batched dispatch (``replay._fleet_exec`` →
  ``_fleet_segment_fn``) advances every cohort lane K steps.  Each
  lane's slice of the stacked outputs decodes and reconciles against
  that lane's own store, byte-identical to its solo run — the fleet
  parity lock.
- Per-lane deltas degrade per lane, never fleet-wide: a lane whose
  private fault plane (``KSIM_FLEET_FAULTS``) fires, whose reconcile
  rolls back, or whose stream diverges (per-lane op streams) leaves the
  cohort and continues on the ordinary SOLO device path — its own
  lowering, its own dispatch — while the cohort keeps amortizing.
  Divergence is detected by cursor drift: the byte-identical parity
  contract means equal cursors over the shared stream imply equal
  stores, so any lane that stops advancing in lockstep is split off
  (and a ``replay.fleet_lane_fallback`` event marks the timeline).

``KSIM_FLEET_DP=n`` lays the stacked lane axis over a ``dp``-mesh
(engine/sharding.py ``fleet_mesh``) so lanes spread across devices;
constants replicate.  The mesh is built lazily ON the dispatch worker
thread — never an unguarded main-thread backend init (a hung backend
must stall the watchdogged worker, not the run).
"""

from __future__ import annotations

import logging
import os
import threading
import time
from dataclasses import dataclass, field
from typing import Any

import jax

from ksim_tpu.errors import (
    DeviceUnavailableError,
    ReplayFallback,
    SimulatorError,
)
from ksim_tpu.faults import FaultPlane
from ksim_tpu.obs import TRACE
from ksim_tpu.engine.replay import ReplayParityError, _fleet_exec

logger = logging.getLogger(__name__)


def parse_fleet_faults(spec: str, n_lanes: int) -> dict[int, FaultPlane]:
    """Parse a ``KSIM_FLEET_FAULTS`` spec into per-lane fault planes.

    Syntax (docs/env.md): comma/semicolon-separated
    ``<lane>:<site>=<schedule>[@error]`` entries, the right-hand side
    exactly the ``KSIM_FAULTS`` grammar, e.g.
    ``"2:replay.dispatch=call:1;2:replay.lower=first:1"`` arms lane 2
    only.  Each listed lane gets its OWN ``FaultPlane`` instance checked
    next to the process-global ``FAULTS`` at the replay sites, so chaos
    lands on one trajectory while the rest of the fleet stays healthy.
    Malformed entries raise (a silently dropped lane spec would make a
    chaos sweep vacuously green, like ``KSIM_FAULTS`` itself)."""
    planes: dict[int, FaultPlane] = {}
    for part in spec.replace(";", ",").split(","):
        part = part.strip()
        if not part:
            continue
        lane_s, sep, rest = part.partition(":")
        if not sep or not lane_s.strip().isdigit():
            raise ValueError(
                f"KSIM_FLEET_FAULTS entry {part!r}: expected "
                f"<lane>:<site>=<schedule>"
            )
        lane = int(lane_s)
        if not 0 <= lane < n_lanes:
            raise ValueError(
                f"KSIM_FLEET_FAULTS entry {part!r}: lane {lane} outside "
                f"the fleet (0..{n_lanes - 1})"
            )
        planes.setdefault(lane, FaultPlane()).configure(rest)
    return planes


@dataclass
class FleetLane:
    """One trajectory's full solo stack plus its fleet bookkeeping."""

    idx: int
    runner: Any  # per-lane ScenarioRunner (store+service owner)
    driver: Any  # per-lane ReplayDriver
    keys: list  # sorted step keys of THIS lane's stream
    by_step: dict  # step -> list[Operation] (cohort lanes share the base dict)
    result: Any  # per-lane ScenarioResult
    faults: "FaultPlane | None" = None
    shared_stream: bool = True  # replays the base stream (cohort-eligible)
    i: int = 0  # cursor into keys
    done: bool = False  # a doneOperation step completed
    convergent: bool = True
    # The reason this lane degraded in the CURRENT round (fleet-lane
    # fallback evidence; cleared each round).
    round_reason: "str | None" = field(default=None, repr=False)

    @property
    def finished(self) -> bool:
        return self.done or self.i >= len(self.keys)


class FleetDriver:
    """Drives every lane to completion, multiplexing the convergent
    cohort through shared lowerings and vmapped group dispatches."""

    def __init__(self, lanes: list[FleetLane]) -> None:
        self.lanes = lanes
        _dp = os.environ.get("KSIM_FLEET_DP")
        self.dp: "int | None" = int(_dp) if _dp else None
        # Cohort dispatch mode.  The convergence invariant makes every
        # cohort lane's carry BYTE-IDENTICAL, so the default dispatches
        # the leader's segment program ONCE and fans the pulled outputs
        # out to every lane's decode + reconcile (each lane's own
        # verify_segment still independently proves its store against
        # the device view) — computing S identical trajectories would
        # be pure redundancy, and on CPU the vmapped program's batched
        # scatters make it MORE than S times slower (docs/scaling.md
        # "Fleet replay", the measured vmap tax).  KSIM_FLEET_VMAP=1
        # forces the genuinely lane-stacked vmapped program
        # (_fleet_segment_fn) — the parity lock runs it to prove the
        # kernels are lane-independent, and it is the path per-lane
        # deltas will ride (ROADMAP "fleet round 2").  A KSIM_FLEET_DP
        # mesh implies it (the dedupe program has no lane axis to lay
        # over dp).
        self.vmap_cohort = (
            os.environ.get("KSIM_FLEET_VMAP") == "1" or self.dp is not None
        )
        # Mesh state is touched from the dispatch worker (the build must
        # run behind the watchdog — jax.devices() on a hung backend
        # never returns) and read by later workers, so it takes a real lock.
        self._mesh_lock = threading.Lock()
        # (dp, tp) -> Mesh: one entry per node-shard width the cohort's
        # plans have dispatched with (tp follows plan.statics.tp, round
        # 19 — the 2-D fleet lays lanes over dp AND node shards over tp).
        self._mesh: dict = {}  # guarded-by: _mesh_lock
        self._mesh_failed = False  # guarded-by: _mesh_lock
        # Fleet evidence counters (the lock-check's lowered-once guard
        # reads them).  All fleet
        # orchestration runs on the main thread; the dispatch worker
        # below is side-effect-free on this object.
        self.shared_lowerings = 0  # guarded-by: main-thread
        self.group_dispatches = 0  # guarded-by: main-thread
        self.lane_fallbacks = 0  # guarded-by: main-thread
        self.divergences = 0  # guarded-by: main-thread

    # -- evidence ------------------------------------------------------------

    def stats(self) -> dict:
        total = sum(d.device_steps + d.fallback_steps for d in self._drivers())
        on_dev = sum(d.device_steps for d in self._drivers())
        return {
            "lanes": len(self.lanes),
            "cohort_mode": "vmap" if self.vmap_cohort else "dedupe",
            "shared_lowerings": self.shared_lowerings,
            "group_dispatches": self.group_dispatches,
            "lane_fallbacks": self.lane_fallbacks,
            "divergences": self.divergences,
            "convergent_lanes": sum(1 for ln in self.lanes if ln.convergent),
            # The lanes-on-device fraction: device-committed lane-steps
            # over all lane-steps (1.0 = every step of every trajectory
            # rode a device segment).
            "lanes_on_device": round(on_dev / total, 4) if total else None,
            "lane_device_steps": [d.device_steps for d in self._drivers()],
            "lane_fallback_steps": [d.fallback_steps for d in self._drivers()],
            "lane_lowerings": [len(d.lower_log) for d in self._drivers()],
        }

    def _drivers(self):
        return [ln.driver for ln in self.lanes]

    # -- the fleet loop ------------------------------------------------------

    def run(self) -> None:
        while True:
            active = [ln for ln in self.lanes if not ln.finished]
            if not active:
                return
            # Cooperative cancel (service round 4 (d)): every lane
            # runner carries the PARENT run's cancel flag, so one check
            # per round — the lane dispatch boundary — raises
            # RunCancelled before the next shared lowering; a cancel
            # landing later, mid-segment, aborts inside that lane's
            # reconcile transaction instead (per-lane rollback, the
            # solo semantics), and the exception ladders below
            # deliberately do not catch it.
            active[0].runner._check_cancelled()
            for ln in active:
                ln.round_reason = None
            cohort = [ln for ln in active if ln.convergent]
            solos = [ln for ln in active if not ln.convergent]
            if len(cohort) == 1:
                # A cohort of one gains nothing from the group path;
                # hand the lane the richer solo pipeline (prelower
                # overlap) for the rest of the run.
                cohort[0].convergent = False
                solos.append(cohort[0])
                cohort = []
            if cohort:
                self._advance_cohort(cohort)
            for ln in solos:
                if not ln.finished:
                    self._advance_solo(ln)

    def _advance_solo(self, ln: FleetLane) -> None:
        """One solo advance: exactly the ScenarioRunner.run loop body."""
        drv = ln.driver
        batches = [ln.by_step[s] for s in ln.keys[ln.i : ln.i + 2 * drv.k]]
        seg = drv.try_segment(batches)
        if seg is not None and ln.runner._commit_segment(
            ln.keys[ln.i : ln.i + len(seg.steps)],
            batches[: len(seg.steps)],
            seg,
            drv,
            ln.result,
        ):
            ln.i += len(seg.steps)
            return
        self._per_pass_head(ln)

    def _per_pass_head(self, ln: FleetLane) -> None:
        """Run the lane's head step on the per-pass host path (the
        window fallback).  The lane's incremental lowering state is
        strictly flushed first — the per-pass pass mutates store and
        service state the lowered-universe cache cannot track (the
        try_segment wrapper does this on the solo path; fleet
        degradations must too)."""
        ln.driver._flush_incremental("fallback")
        ln.driver.fallback_steps += 1
        step = ln.keys[ln.i]
        done = ln.runner._run_step(step, ln.by_step[step], ln.result)
        ln.i += 1
        if done:
            ln.result.succeeded = True
            ln.done = True

    # -- per-lane degradation ------------------------------------------------

    def _lane_gate(self, ln: FleetLane, site: str) -> "BaseException | None":
        """Check the lane's PRIVATE fault plane at a replay site.
        Returns the containable exception (the lane degrades alone) or
        None; programming errors (``@type`` faults) propagate — the
        classified-taxonomy contract, same as the solo handlers."""
        if ln.faults is None:
            return None
        try:
            ln.faults.check(site)
            return None
        except (
            ReplayFallback,
            DeviceUnavailableError,
            SimulatorError,
            RuntimeError,
            OSError,
        ) as e:
            return e

    def _degrade_lane(self, ln: FleetLane, reason: str) -> None:
        """One lane leaves this round's shared path (reason recorded for
        the round-end divergence bookkeeping) and runs its head step
        per-pass."""
        ln.round_reason = reason
        self.lane_fallbacks += 1
        self._per_pass_head(ln)

    def _note_divergence(self, ln: FleetLane) -> None:
        ln.convergent = False
        self.divergences += 1
        TRACE.event(
            "replay.fleet_lane_fallback",
            lane=ln.idx,
            reason=ln.round_reason or "cursor_drift",
        )
        logger.info(
            "fleet lane %d left the convergent cohort (%s); it continues "
            "on the solo device path",
            ln.idx, ln.round_reason or "cursor_drift",
        )

    # -- the shared window ---------------------------------------------------

    def _advance_cohort(self, cohort: list[FleetLane]) -> None:
        """Advance every convergent lane by one window: one shared
        lowering (the cohort leader's driver — its lowered-universe
        cache makes steady-state windows O(delta)), one vmapped group
        dispatch, one per-lane decode + reconcile.  Any lane that fails
        a per-lane gate degrades ALONE; a shared failure (lowering
        vocabulary miss, device error, post-dispatch discard) degrades
        every lane IDENTICALLY, which keeps the cohort convergent — all
        lanes run the head step per-pass and retry the rest on-device
        next round, exactly like a solo run would."""
        start_i = cohort[0].i
        # 1. Per-lane gates.  First the service-support screen — the
        #    same check a solo prepare_segment opens with, run per lane
        #    because it also caches the lane driver's resolved profile
        #    config (_sched_name/record/preemption) that decode and slot
        #    advancement read.  Then the lane's private replay.lower
        #    fault plane: a firing lane degrades as its solo lowering
        #    would have.
        stay: list[FleetLane] = []
        for ln in cohort:
            if not ln.driver.service_supported():
                self._degrade_lane(ln, ln.driver._last_reject or "unsupported")
                continue
            e = self._lane_gate(ln, "replay.lower")
            if e is None:
                stay.append(ln)
            else:
                reason = str(e) if isinstance(e, ReplayFallback) else "lowering_fault"
                ln.driver._reject(reason)
                self._degrade_lane(ln, reason)
        if stay:
            self._dispatch_cohort(stay)
        # 2. Divergence bookkeeping: the parity contract makes equal
        #    cursors over the shared stream imply equal stores, so any
        #    lane off the common cursor leaves the cohort.  If EVERY
        #    lane took the same path (all committed, or all degraded
        #    identically) the cohort survives intact.
        cursors = {ln.i for ln in cohort}
        if len(cursors) > 1:
            lead_i = max(cursors)  # the device-committed lanes
            for ln in cohort:
                if ln.i != lead_i:
                    self._note_divergence(ln)
        else:
            # Lanes that degraded through a PRIVATE fault this round
            # diverge even at a common cursor unless everyone did: a
            # lane-local device_error fed only that lane's breaker, so
            # its future degradation ladder no longer matches the
            # cohort's.
            reasons = {ln.round_reason for ln in cohort}
            if len(reasons) > 1:
                for ln in cohort:
                    if ln.round_reason is not None:
                        self._note_divergence(ln)
        assert all(ln.i > start_i for ln in cohort), "fleet round made no progress"

    def _dispatch_cohort(self, stay: list[FleetLane]) -> None:
        lead = stay[0]
        drv = lead.driver
        keys, by_step = lead.keys, lead.by_step
        i = lead.i
        batches = [by_step[s] for s in keys[i : i + 2 * drv.k]]
        # Reset before the shared lowering so a None return's reason can
        # only be what THIS window just recorded — prepare_segment's
        # pre-span head screen returns None without a _reject, and
        # mirroring a stale reason from an earlier window would
        # fabricate per-lane fallback evidence no solo run records.
        drv._last_reject = None
        plan = drv.prepare_segment(batches, check_lane_faults=False)
        self.shared_lowerings += 1
        if plan is None:
            # Shared rejection (vocabulary miss, breaker, lowering
            # fault): mirror the leader's recorded reason onto every
            # follower's histogram — each solo run would have recorded
            # it — and degrade the whole cohort identically.
            reason = drv._last_reject
            for ln in stay:
                if ln is not lead and reason is not None:
                    ln.driver._reject(reason)
                self._per_pass_head(ln)
            return
        # 2. Per-lane dispatch gate: a lane whose private plane fires at
        #    replay.dispatch is excluded from the group program and
        #    degrades through the device_error ladder (its own breaker).
        ready: list[FleetLane] = []
        for ln in stay:
            e = self._lane_gate(ln, "replay.dispatch")
            if e is None:
                ready.append(ln)
            else:
                ln.driver._note_device_error(e)
                self._degrade_lane(ln, "device_error")
        if not ready:
            return
        outcome = self._group_dispatch(ready, lead, plan, batches)
        if outcome is None:
            return  # every ready lane already degraded identically
        pulled_state, pulled = outcome
        # 3. Per-lane decode + reconcile against each lane's own store.
        #    Vmapped outputs slice per lane; dedupe outputs are shared
        #    (read-only) — either way each lane decodes against its OWN
        #    service backoff table and reconciles into its OWN store.
        lead.driver._last_plan = plan  # the cache-advance anchor (leader only)
        stacked = self.vmap_cohort
        for j, ln in enumerate(ready):
            if stacked:
                lane_state = jax.tree_util.tree_map(lambda a, j=j: a[j], pulled_state)
                lane_pulled = jax.tree_util.tree_map(lambda a, j=j: a[j], pulled)
            else:
                lane_state, lane_pulled = pulled_state, pulled
            with TRACE.span("replay.decode", lane=ln.idx):
                res = ln.driver._decode_outputs(plan, lane_state, lane_pulled)
            if isinstance(res, str):
                # Post-dispatch validation discard — deterministic over
                # identical inputs, so every lane lands here together
                # and the cohort degrades convergently.
                ln.driver._reject(res)
                self._per_pass_head(ln)
                continue
            if ln.runner._commit_segment(
                keys[i : i + len(res.steps)],
                batches[: len(res.steps)],
                res,
                ln.driver,
                ln.result,
            ):
                ln.i += len(res.steps)
            else:
                # Per-lane reconcile rollback (the lane's store is
                # byte-identical to the window start).
                self._degrade_lane(ln, "reconcile_fault")

    def _group_dispatch(self, ready, lead, plan, batches):
        """The vmapped dispatch on a watchdogged worker, overlapped with
        the leader's speculative prelower of the next window (the solo
        pipeline's overlap, kept for the cohort).  Returns the stacked
        ``(pulled_state, pulled)`` or None after degrading every ready
        lane identically."""
        drv = lead.driver
        stacked = self.vmap_cohort
        # Vmapped mode: one scan-carry tree per lane.  The cohort's
        # lanes are byte-identical by the convergence invariant, so the
        # stacked carry is S references to the leader plan's state0;
        # per-lane carries become real when heterogeneous grouping
        # lands (ROADMAP "fleet round 2").
        lanes_state0 = [plan.state0] * len(ready)
        lane_ids = ",".join(str(ln.idx) for ln in ready)
        box: dict[str, Any] = {}

        def work() -> None:  # ksimlint: worker-thread
            try:
                if stacked:
                    box["out"] = _fleet_exec(
                        plan, lanes_state0, self._worker_mesh(plan.statics.tp)
                    )
                else:
                    # Dedupe: the leader's solo segment program (same
                    # compile); its outputs ARE every cohort lane's
                    # outputs.
                    box["out"] = lead.driver._device_exec(plan)
            except BaseException as e:  # classified below, on the main thread
                box["err"] = e

        err: "BaseException | None" = None
        try:
            with TRACE.span(
                "replay.dispatch",
                segment=drv._segment_seq,
                steps=plan.n_steps,
                lanes=len(ready),
                lane=lane_ids,
            ):
                if drv.watchdog_s <= 0:
                    work()
                    drv._prelower_next(plan, batches)
                else:
                    t = threading.Thread(
                        target=work, name="fleet-dispatch", daemon=True
                    )
                    t.start()
                    t0 = time.monotonic()
                    drv._prelower_next(plan, batches)
                    t.join(max(drv.watchdog_s - (time.monotonic() - t0), 0.001))
                    if t.is_alive():
                        # EVERY ready lane counts the timeout: solo
                        # semantics give each lane's breaker a
                        # cumulative-timeout leg, and the cohort's
                        # breakers must stay in lockstep (one abandoned
                        # worker per GROUP timeout, so the leaked-worker
                        # bound stays breaker_threshold — the lanes trip
                        # together).
                        for ln in ready:
                            ln.driver.watchdog_timeouts += 1
                        TRACE.event(
                            "replay.watchdog_timeout",
                            segment=drv._segment_seq,
                            watchdog_s=drv.watchdog_s,
                            lanes=len(ready),
                        )
                        raise DeviceUnavailableError(
                            f"fleet dispatch ({len(ready)} lanes) exceeded "
                            f"the {drv.watchdog_s:.0f}s watchdog"
                        )
                if "err" in box:
                    raise box["err"]
        except ReplayParityError:
            raise  # a kernel bug, not a degradable condition
        except ReplayFallback as e:
            for ln in ready:
                ln.driver._reject(str(e))
                self._per_pass_head(ln)
            return None
        except (DeviceUnavailableError, SimulatorError, RuntimeError, OSError) as e:
            err = e
        if err is not None:
            # A shared device failure: every lane's driver walks the
            # same device_error ladder its solo run would — breakers
            # stay in lockstep, so the cohort survives convergent.
            for ln in ready:
                ln.driver._note_device_error(err)
                self._per_pass_head(ln)
            return None
        for ln in ready:
            ln.driver.note_dispatch_healthy(plan)
        self.group_dispatches += 1
        return box["out"]

    def _worker_mesh(self, tp: int = 1):
        """The KSIM_FLEET_DP (dp, tp) fleet mesh, built lazily on the
        DISPATCH WORKER thread (jax.devices() initializes the backend;
        a hung backend must stall the watchdogged worker, never the
        main thread).  ``tp`` follows the dispatching plan's node-shard
        width (plan.statics.tp, round 19) — a cohort whose plans narrow
        tp across windows gets one memoized mesh per width.  A mesh
        build failure degrades to single-device fleet dispatch — once,
        loudly."""
        if self.dp is None:
            return None
        from ksim_tpu.engine.sharding import fleet_mesh

        with self._mesh_lock:
            if self._mesh_failed:
                return None
            mesh = self._mesh.get((self.dp, tp))
            if mesh is None:
                try:
                    # Deliberate worker-side store: the mesh is built
                    # lazily ON the dispatch worker so a hung backend
                    # stalls the watchdogged worker, never the main
                    # thread; _mesh_lock makes both writes safe.
                    mesh = fleet_mesh(self.dp, tp)  # ksimlint: disable=thread-role
                    self._mesh[(self.dp, tp)] = mesh  # ksimlint: disable=thread-role
                except Exception as e:
                    self._mesh_failed = True  # ksimlint: disable=thread-role
                    logger.warning(
                        "KSIM_FLEET_DP=%d x tp=%d mesh unavailable (%s: %s); "
                        "fleet dispatch stays single-device",
                        self.dp, tp, type(e).__name__, e,
                    )
                    return None
            return mesh
