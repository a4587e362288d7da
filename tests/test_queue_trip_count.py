"""The segment program's pod loops run to the step's attempt count, not
to the compiled queue width (engine/replay.py ``_run_step`` /
``run_slots``).

The compiled queue bucket ``q`` is sized for the whole K-step window,
the attempted pods are compacted to its front, and every slot past the
last attempt is invalid: it binds nothing and no decoder reads it.  The
bind loop and the gated victim-search loop therefore stop after the
step's attempts, rounded up to a whole block of ``SCAN_UNROLL`` slots,
and the device reports each step's trip count — summed into
``replay.queue_slots_run`` (``ReplayDriver.stats``) and into each
``lower_log`` entry as ``slots_run``.

Pinned here:

- the device path against the per-pass path — per-step counts, final
  placements and nominations, eviction order, result annotations — over
  a churn stream with priority strata and over a one-step burst, in
  float32 and x64, preemption on and off, record selection and full,
  with ``queue_slots_run`` equal to the attempts rounded up per step;
- steps that attempt nothing (no node exists yet; no pod is eligible)
  run zero slots;
- the fleet program's loop bound is reduced over the lane axis: lanes
  with different attempt counts equal their solo runs, and no ``while``
  of the vmapped program has a batched predicate.
"""

from __future__ import annotations

import jax
import numpy as np
import pytest

from ksim_tpu.engine import replay
from ksim_tpu.engine.core import SCAN_UNROLL
from ksim_tpu.scenario import ScenarioRunner, churn_scenario
from ksim_tpu.scenario.runner import Operation
from ksim_tpu.state.cluster import ClusterStore
from tests.helpers import make_node, make_pod

MODES = [False, True]
MODE_IDS = ["f32-fast", "exact-x64"]


def _blocks(n: int, searching: bool = False) -> int:
    """``n`` attempts rounded up to whole blocks of the pod loop: blocks
    of ``SCAN_UNROLL`` slots, or of one slot in a window that lowers with
    the victim search (its slot body holds the whole search; PR 32)."""
    return n if searching else -(-n // SCAN_UNROLL) * SCAN_UNROLL


def _churn_ops():
    """Four 4-cpu nodes hold eight 1.5-cpu pods; three pods of mixed
    priority arrive per step and one leaves, so from step 3 on the
    cluster is full, the queue holds backed-off pods beside the new
    ones, and the higher strata preempt.  Every window has the same
    shapes: one compile per case."""
    ops = [
        Operation(
            step=0, op="create", kind="nodes",
            obj=make_node(f"n-{i}", cpu="4", memory="16Gi"),
        )
        for i in range(4)
    ]
    for step in range(1, 9):
        for j in range(3):
            i = 3 * step + j
            pod = make_pod(
                f"p-{step}-{j}", cpu="1500m", memory="256Mi",
                priority=[0, 0, 5, 10][i % 4],
            )
            pod["metadata"]["creationTimestamp"] = f"2026-01-01T00:{i:02d}:00Z"
            ops.append(Operation(step=step, op="create", kind="pods", obj=pod))
        if step >= 3:
            ops.append(
                Operation(
                    step=step, op="delete", kind="pods",
                    name=f"p-{step - 2}-0", namespace="default",
                )
            )
    return ops


def _burst_ops():
    # The burst-5k shape: every node in step 0, the whole backlog in
    # step 1, one uncapped pass.
    return list(
        churn_scenario(
            0, n_nodes=40, n_events=40 + 96, ops_per_step=96,
            pod_create_frac=1.0, pod_delete_frac=0.0,
        )
    )


STREAMS = {
    "churn": (_churn_ops, dict(max_pods_per_pass=16, pod_bucket_min=32)),
    "burst": (_burst_ops, dict(pod_bucket_min=32)),
}


@pytest.fixture
def committed_steps(monkeypatch):
    """Every ``StepOutcome`` the device path commits, in order."""
    seen = []
    committed = replay.ReplayDriver.note_segment_committed

    def spy(self, seg, step_nodes, **kw):
        seen.extend(seg.steps)
        return committed(self, seg, step_nodes, **kw)

    monkeypatch.setattr(replay.ReplayDriver, "note_segment_committed", spy)
    return seen


def _run(stream: str, *, x64: bool, preempt: bool, record: str, device: bool):
    ops, kw = STREAMS[stream]
    prev = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", x64)
    try:
        runner = ScenarioRunner(
            preemption=preempt, record=record, device_replay=device,
            device_segment_steps=4, **kw,
        )
        evicted = []
        runner.service.add_eviction_listener(lambda ns, nm: evicted.append(nm))
        res = runner.run(iter(ops()))
    finally:
        jax.config.update("jax_enable_x64", prev)
    pods = runner.store.list("pods")
    return {
        "steps": [
            (s.step, s.scheduled, s.unschedulable, s.pending_after)
            for s in res.steps
        ],
        "state": sorted(
            (
                p["metadata"]["name"],
                p.get("spec", {}).get("nodeName"),
                p.get("status", {}).get("nominatedNodeName"),
            )
            for p in pods
        ),
        "annotations": {
            p["metadata"]["name"]: p["metadata"].get("annotations", {})
            for p in pods
        },
        "evicted": evicted,
        "driver": runner.replay_driver,
    }


@pytest.mark.parametrize("record", ["selection", "full"])
@pytest.mark.parametrize("preempt", [False, True], ids=["nopreempt", "preempt"])
@pytest.mark.parametrize("x64", MODES, ids=MODE_IDS)
@pytest.mark.parametrize("stream", sorted(STREAMS))
def test_device_path_equals_per_pass_and_runs_only_the_attempts(
    stream, x64, preempt, record, committed_steps
):
    base = _run(stream, x64=x64, preempt=preempt, record=record, device=False)
    dev = _run(stream, x64=x64, preempt=preempt, record=record, device=True)
    for key in ("steps", "state", "evicted"):
        assert dev[key] == base[key], key
    if record == "full":
        assert dev["annotations"] == base["annotations"]
    if preempt and stream == "churn":
        assert base["evicted"], "the stream never preempted: vacuous"
    driver = dev["driver"]
    stats = driver.stats()
    # A window the device path hands back (a victim search past its
    # static bounds, a missed featurize prediction) commits nothing and
    # counts no slot: the account is over the committed steps.
    assert stats["device_steps"] == len(committed_steps) >= len(dev["steps"]) // 2
    attempts = [o.scheduled + o.unschedulable for o in committed_steps]
    assert sum(attempts) > 0
    # A step's block is its window's: one slot where the window lowered
    # with the victim search (priorities differ in it), SCAN_UNROLL else.
    for o, a in zip(committed_steps, attempts):
        assert o.slots_run in (_blocks(a), _blocks(a, searching=True))
    if not preempt:
        assert [o.slots_run for o in committed_steps] == [_blocks(a) for a in attempts]
    assert stats["queue_slots_run"] == sum(o.slots_run for o in committed_steps)
    assert sum(attempts) <= stats["queue_slots_run"] <= sum(attempts) + (
        SCAN_UNROLL - 1
    ) * sum(1 for a in attempts if a)
    log = driver.lower_log
    assert sum(e["slots_run"] for e in log) == stats["queue_slots_run"]
    # The compiled width is the window's; the loops stopped well short.
    assert stats["queue_slots_run"] < sum(
        e["queue_width"] * e["steps"] for e in log if e["slots_run"]
    )


def test_steps_that_attempt_nothing_run_zero_slots(committed_steps):
    """Steps 1 and 2 create pods before any node exists (the pass never
    runs); step 3 brings the nodes and a sixth pod, and attempts all
    six; step 4 swaps a node in and deletes a bound pod with no pod
    pending.  Only step 3 runs a slot."""

    def stream():
        for i in range(5):
            yield Operation(
                step=1 + i // 3, op="create", kind="pods",
                obj=make_pod(f"p-{i}", cpu="500m", memory="256Mi"),
            )
        for i in range(2):
            yield Operation(
                step=3, op="create", kind="nodes",
                obj=make_node(f"n-{i}", cpu="4", memory="8Gi"),
            )
        yield Operation(
            step=3, op="create", kind="pods",
            obj=make_pod("p-5", cpu="500m", memory="256Mi"),
        )
        yield Operation(
            step=4, op="create", kind="nodes",
            obj=make_node("n-2", cpu="4", memory="8Gi"),
        )
        yield Operation(
            step=4, op="delete", kind="pods", name="p-0", namespace="default"
        )

    jax.config.update("jax_enable_x64", False)
    runner = ScenarioRunner(device_replay=True, device_segment_steps=2)
    res = runner.run(stream())
    driver = runner.replay_driver
    assert driver.device_steps == 4 and driver.fallback_steps == 0, driver.unsupported
    assert [(s.scheduled, s.unschedulable) for s in res.steps] == [
        (0, 0), (0, 0), (6, 0), (0, 0)
    ]
    assert [o.slots_run for o in committed_steps] == [0, 0, _blocks(6), 0]
    assert driver.stats()["queue_slots_run"] == _blocks(6)


# ---------------------------------------------------------------------------
# The fleet program: one loop bound for every lane
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def lane_plan():
    """One lowered window (10 pods of two priorities arrive on 6 nodes
    that each hold one low-priority pod and have room for no second) and
    two carried states for it: the plan's own, and one in which seven of
    the ten pods sit in backoff for the whole window — so lane B
    attempts three pods where lane A attempts ten."""
    jax.config.update("jax_enable_x64", False)
    store = ClusterStore()
    for i in range(6):
        store.create("nodes", make_node(f"n-{i}", cpu="2", memory="4Gi"))
        low = make_pod(
            f"low-{i}", cpu="1500m", memory="256Mi", node_name=f"n-{i}", priority=1
        )
        low["metadata"]["creationTimestamp"] = f"2025-01-01T00:{i:02d}:00Z"
        store.create("pods", low)
    pods = []
    for i in range(10):
        pod = make_pod(f"p-{i}", cpu="1500m", memory="256Mi", priority=[0, 10][i % 2])
        pod["metadata"]["creationTimestamp"] = f"2026-01-01T00:{i:02d}:00Z"
        pods.append(Operation(step=1, op="create", kind="pods", obj=pod))
    runner = ScenarioRunner(store=store, preemption=True, device_replay=True)
    driver = replay.ReplayDriver(runner.store, runner.service, k=4)
    plan = driver.prepare_segment([pods])
    assert plan is not None, driver.unsupported
    assert plan.statics.preempt and not np.asarray(plan.ev["flush"]).any()
    state_a = {k: np.asarray(v) for k, v in plan.state0.items()}
    state_b = {k: v.copy() for k, v in state_a.items()}
    rows = [plan.universe_row_of[f"default/p-{i}"] for i in range(3, 10)]
    state_b["attempts"][rows] = 1
    state_b["retry_at"][rows] = 1 << 20
    return plan, state_a, state_b


def _solo(plan, state):
    const, (ev, st) = replay._pack_plan_buffers(plan, (plan.ev, state))
    return jax.device_get(
        replay._segment_fn_nodonate(plan.statics, plan.prog, const, ev, st)
    )


def test_fleet_lanes_with_different_attempt_counts_equal_their_solo_runs(lane_plan):
    plan, state_a, state_b = lane_plan
    solo = [_solo(plan, s) for s in (state_a, state_b)]
    slots = [np.asarray(outs["slots"]) for _fin, outs in solo]
    assert plan.statics.preempt
    assert slots[0].tolist() == [_blocks(10, searching=True), 0, 0, 0]
    assert slots[1].tolist() == [_blocks(3, searching=True), 0, 0, 0]
    fin, outs = replay._fleet_exec(plan, [state_a, state_b])
    for lane, (solo_fin, solo_outs) in enumerate(solo):
        # (The packed transfer hands scalars back as shape (1,).)
        for name, got in fin.items():
            want = np.asarray(solo_fin[name])
            np.testing.assert_array_equal(
                got[lane].reshape(want.shape), want, err_msg=name
            )
        for name, want in solo_outs.items():
            if name != "slots":
                np.testing.assert_array_equal(
                    outs[name][lane].reshape(want.shape), want, err_msg=name
                )
        # Every lane runs the widest lane's trips.
        assert outs["slots"][lane].tolist() == slots[0].tolist()
    # Not vacuous: the lanes really differ, and lane A's pass preempted.
    assert [int(outs["unschedulable"][0]) for _fin, outs in solo] == [10, 3]
    assert (np.asarray(solo[0][1]["nom"]) >= 0).any()


def _while_predicates_batched(jaxpr) -> list[bool]:
    """For every ``while`` of ``jaxpr`` (nested ones included): is its
    predicate batched — one verdict per lane, which the lowering ORs
    together and pays for with a select on every carry update?"""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "while":
            found.append(eqn.params["cond_jaxpr"].out_avals[0].shape != ())
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found.extend(_while_predicates_batched(sub))
    return found


def test_fleet_program_has_no_batched_while_predicate(lane_plan):
    plan, state_a, state_b = lane_plan
    stacked = jax.tree.map(lambda a, b: np.stack([a, b]), state_a, state_b)
    const, (ev, lanes) = replay._pack_plan_buffers(plan, (plan.ev, stacked))
    fleet = jax.make_jaxpr(
        lambda s: replay._fleet_segment_impl(plan.statics, plan.prog, const, ev, s)
    )(lanes)
    flags = _while_predicates_batched(fleet.jaxpr)
    assert len(flags) >= 2  # the bind loop and the search loop
    assert not any(flags)
    # The detector's control: the same body vmapped WITHOUT the lane
    # axis reduces nothing, and its loop bound is batched.
    naive = jax.make_jaxpr(
        jax.vmap(
            lambda s: replay._segment_body(plan.statics, plan.prog, const, ev, s)
        )
    )(lanes)
    assert any(_while_predicates_batched(naive.jaxpr))
