"""Device-resident churn replay (engine/replay.py) behavior locks.

The segment-scan path must reproduce the per-pass path's scheduling
outcomes BYTE-IDENTICALLY — counts are the contract (repo CLAUDE.md).
These tests pin:

- step-by-step equivalence against the per-pass path on a mixed churn
  stream (spread + affinity pods, node drain/replace, bound-pod
  completions) in both float modes;
- the flagship 6k-event locked prefix (seed 0, 2000 nodes -> 2524/471)
  THROUGH the device path, with proof the device path actually ran
  (a silent blanket fallback would pass the counts vacuously);
- fallback behavior: segments containing unsupported ops take the
  per-pass path and land on identical results.
"""

from __future__ import annotations

import jax
import pytest

from ksim_tpu.scenario import ScenarioRunner, churn_scenario
from ksim_tpu.scenario.runner import Operation
from tests.fixtures.preemption_victims import CASES as PREEMPTION_CASES
from tests.helpers import make_node, make_pod


def _steps_sig(res):
    return [
        (s.step, s.scheduled, s.unschedulable, s.pending_after) for s in res.steps
    ]


def _run_pair(stream_factory, *, x64: bool, k: int = 8, **runner_kw):
    prev = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", x64)
    try:
        base = ScenarioRunner(**runner_kw).run(stream_factory())
        dev_runner = ScenarioRunner(
            device_replay=True, device_segment_steps=k, **runner_kw
        )
        dev = dev_runner.run(stream_factory())
    finally:
        jax.config.update("jax_enable_x64", prev)
    return base, dev, dev_runner.replay_driver


@pytest.mark.parametrize("x64", [False, True], ids=["f32-fast", "exact-x64"])
def test_device_replay_matches_per_pass_churn(x64):
    """Mixed-constraint churn: per-step (scheduled, unschedulable,
    pending) byte-identical through the device path, with real device
    coverage."""
    base, dev, driver = _run_pair(
        lambda: churn_scenario(0, n_nodes=200, n_events=800, ops_per_step=50),
        x64=x64,
        k=8,
        max_pods_per_pass=1024,
        pod_bucket_min=128,
    )
    assert _steps_sig(dev) == _steps_sig(base)
    assert (dev.pods_scheduled, dev.unschedulable_attempts) == (
        base.pods_scheduled,
        base.unschedulable_attempts,
    )
    assert driver.device_steps >= 8  # at least one real device segment


def test_device_replay_lock_6k_seed0_f32():
    """The flagship locked prefix through the device-resident path:
    seed 0, 2000 nodes, 6k events -> 2524/471 (repo CLAUDE.md), exactly
    as the benchmark's churn-2k deployment runs it.  The driver must have covered the bulk of the
    steps on-device — a blanket fallback passing vacuously is a failure."""
    jax.config.update("jax_enable_x64", False)
    try:
        runner = ScenarioRunner(
            max_pods_per_pass=1024,
            pod_bucket_min=128,
            device_replay=True,
            device_segment_steps=16,
        )
        res = runner.run(
            churn_scenario(0, n_nodes=2000, n_events=6000, ops_per_step=100)
        )
    finally:
        jax.config.update("jax_enable_x64", True)
    assert res.events_applied == 6430
    assert (res.pods_scheduled, res.unschedulable_attempts) == (2524, 471)
    driver = runner.replay_driver
    assert driver.device_steps >= 32
    assert driver.device_steps + driver.fallback_steps == len(res.steps)


@pytest.mark.slow
def test_device_replay_lock_6k_seed0_exact():
    """Exact-mode (x64) variant of the device-path lock."""
    runner = ScenarioRunner(
        max_pods_per_pass=1024,
        pod_bucket_min=128,
        device_replay=True,
        device_segment_steps=16,
    )
    res = runner.run(
        churn_scenario(0, n_nodes=2000, n_events=6000, ops_per_step=100)
    )
    assert (res.pods_scheduled, res.unschedulable_attempts) == (2524, 471)
    assert runner.replay_driver.device_steps >= 32


def test_device_replay_falls_back_on_unsupported_ops():
    """A patch op poisons its segment (outside the tensor vocabulary):
    that segment runs per-pass, the rest still runs on-device, and the
    end state matches the pure per-pass path."""

    def stream():
        step = 0
        for i in range(8):
            yield Operation(
                step=0, op="create", kind="nodes",
                obj=make_node(f"n-{i}", cpu="8", memory="16Gi"),
            )
        for step in range(1, 9):
            yield Operation(
                step=step, op="create", kind="pods",
                obj=make_pod(f"p-{step}", cpu="500m", memory="512Mi"),
            )
            if step == 4:
                # RFC 7386 merge patch: outside the device vocabulary.
                yield Operation(
                    step=step, op="patch", kind="pods",
                    name=f"p-{step}", namespace="default",
                    obj={"metadata": {"labels": {"patched": "yes"}}},
                )

    base, dev, driver = _run_pair(stream, x64=False, k=4)
    assert _steps_sig(dev) == _steps_sig(base)
    # Fallback is per-STEP granular: the patch step runs per-pass and the
    # driver re-segments right after it, so only the poisoned step(s)
    # leave the device path.
    assert driver.fallback_steps >= 1
    assert driver.device_steps >= 8
    assert any(r.startswith("op:patch") for r in driver.unsupported)


def test_device_replay_pod_vocabulary_fallback():
    """Pods with host ports are outside the tensor vocabulary: the
    lowering rejects the segment and results still match per-pass."""

    def stream():
        for i in range(4):
            yield Operation(
                step=0, op="create", kind="nodes",
                obj=make_node(f"n-{i}", cpu="8", memory="16Gi"),
            )
        ported = make_pod("ported", cpu="500m", memory="512Mi")
        ported["spec"]["containers"][0]["ports"] = [{"hostPort": 8080}]
        yield Operation(step=1, op="create", kind="pods", obj=ported)
        yield Operation(
            step=2, op="create", kind="pods",
            obj=make_pod("plain", cpu="500m", memory="512Mi"),
        )

    base, dev, driver = _run_pair(stream, x64=False, k=3)
    assert _steps_sig(dev) == _steps_sig(base)
    assert driver.unsupported.get("host_ports", 0) >= 1


def test_device_replay_namespaceless_create_op():
    """A create op whose pod object omits metadata.namespace (the store
    defaults it to "default" on create) must flow through the device
    path under the same key the service uses — review finding: the two
    key schemes diverged and crashed the lowering."""

    def stream():
        for i in range(4):
            yield Operation(
                step=0, op="create", kind="nodes",
                obj=make_node(f"n-{i}", cpu="8", memory="16Gi"),
            )
        bare = make_pod("nsless", cpu="500m", memory="512Mi")
        del bare["metadata"]["namespace"]
        yield Operation(step=1, op="create", kind="pods", obj=bare)
        yield Operation(
            step=2, op="create", kind="pods",
            obj=make_pod("plain", cpu="500m", memory="512Mi"),
        )

    base, dev, driver = _run_pair(stream, x64=False, k=3)
    assert _steps_sig(dev) == _steps_sig(base)
    assert driver.device_steps == 3


# ---------------------------------------------------------------------------
# Round 7: on-device preemption victim search + record="full" streaming
# ---------------------------------------------------------------------------


def _collect_evictions(runner):
    order = []
    runner.service.add_eviction_listener(lambda ns, nm: order.append((ns, nm)))
    return order


@pytest.mark.parametrize(
    "case", PREEMPTION_CASES, ids=[c["name"] for c in PREEMPTION_CASES]
)
def test_device_preemption_matches_fixtures(case):
    """The ON-DEVICE victim search lands on the hand-derived nominated
    node and evicts the same victims in the same (reprieve) order as the
    host oracle — with proof the segment actually ran on-device."""
    from tests.test_preemption_fixtures import case_objects

    jax.config.update("jax_enable_x64", False)
    nodes, victims, pre = case_objects(case)
    from ksim_tpu.state.cluster import ClusterStore

    store = ClusterStore()
    for n in nodes:
        store.create("nodes", n)
    for v in victims:
        store.create("pods", v)
    runner = ScenarioRunner(
        store=store, preemption=True, device_replay=True, device_segment_steps=4
    )
    evicted = _collect_evictions(runner)
    runner.run(iter([Operation(step=1, op="create", kind="pods", obj=pre)]))
    driver = runner.replay_driver
    assert driver.device_steps >= 1, driver.unsupported
    got = store.get("pods", "preemptor")
    assert (
        got.get("status", {}).get("nominatedNodeName")
        == case["expected_nominated"]
    )
    assert [nm for _ns, nm in evicted] == case["expected_victims"]


def test_device_preemption_churn_matches_per_pass():
    """A churn stream with priority strata (so preemption really fires
    mid-segment): per-step counts and the final store byte-identical
    between the per-pass path and the device path with preemption ON."""

    def stream():
        # 3 nodes x 4 cpu saturate after 8 x 1.5cpu pods; later
        # higher-priority arrivals must preempt the prio-0 stratum.
        for i in range(3):
            yield Operation(
                step=0, op="create", kind="nodes",
                obj=make_node(f"n-{i}", cpu="4", memory="16Gi"),
            )
        for step in range(1, 17):
            prio = [0, 0, 5, 10][step % 4]
            pod = make_pod(
                f"p-{step}", cpu="1500m", memory="256Mi", priority=prio
            )
            pod["metadata"]["creationTimestamp"] = f"2026-01-{step:02d}T00:00:00Z"
            yield Operation(step=step, op="create", kind="pods", obj=pod)

    def run(device):
        runner = ScenarioRunner(
            preemption=True, device_replay=device, device_segment_steps=4
        )
        ev = _collect_evictions(runner)
        res = runner.run(stream())
        state = sorted(
            (
                p["metadata"]["name"],
                p.get("spec", {}).get("nodeName"),
                p.get("status", {}).get("nominatedNodeName"),
            )
            for p in runner.store.list("pods")
        )
        return runner, res, state, ev

    jax.config.update("jax_enable_x64", False)
    r_base, base, st_base, ev_base = run(False)
    r_dev, dev, st_dev, ev_dev = run(True)
    assert _steps_sig(dev) == _steps_sig(base)
    assert st_dev == st_base
    assert ev_dev == ev_base
    assert ev_base, "stream never triggered preemption — fixture is vacuous"
    assert r_dev.replay_driver.device_steps >= 8
    assert "preemption" not in r_dev.replay_driver.unsupported


def test_device_full_record_annotations_match():
    """record="full" streams result tensors out of the segment scan; the
    host decode must reproduce the per-pass annotations BYTE-identically
    (filter/score/finalscore maps, history, selected-node)."""

    def stream():
        return churn_scenario(0, n_nodes=24, n_events=160, ops_per_step=16)

    def annos(store):
        return {
            p["metadata"]["name"]: p["metadata"].get("annotations", {})
            for p in store.list("pods")
        }

    jax.config.update("jax_enable_x64", False)
    base_r = ScenarioRunner(record="full", max_pods_per_pass=64, pod_bucket_min=32)
    base = base_r.run(stream())
    dev_r = ScenarioRunner(
        record="full", max_pods_per_pass=64, pod_bucket_min=32,
        device_replay=True, device_segment_steps=8,
    )
    dev = dev_r.run(stream())
    assert _steps_sig(dev) == _steps_sig(base)
    assert dev_r.replay_driver.device_steps >= 4, dev_r.replay_driver.unsupported
    a_base, a_dev = annos(base_r.store), annos(dev_r.store)
    assert set(a_base) == set(a_dev)
    for name in a_base:
        assert a_base[name] == a_dev[name], f"annotations diverged for {name}"


def test_device_preemption_with_full_record():
    """Preemption + record="full" together: the resolvable-candidate
    mask is derived from the streamed reason bits on-device, and the
    postfilter-result annotation (every failed node, nominated entry)
    matches the per-pass render."""
    import json

    from ksim_tpu.engine.annotations import POST_FILTER_RESULT_KEY
    from ksim_tpu.state.cluster import ClusterStore

    def build(device):
        store = ClusterStore()
        store.create("nodes", make_node("n0", cpu="2", memory="8Gi"))
        low = make_pod("low0", cpu="1", memory=None, node_name="n0", priority=1)
        low["metadata"]["creationTimestamp"] = "2024-01-01T00:00:00Z"
        store.create("pods", low)
        low2 = make_pod("low1", cpu="1", memory=None, node_name="n0", priority=1)
        low2["metadata"]["creationTimestamp"] = "2024-01-01T00:00:01Z"
        store.create("pods", low2)
        runner = ScenarioRunner(
            store=store, record="full", preemption=True,
            device_replay=True if device else False, device_segment_steps=4,
        )
        crit = make_pod("crit", cpu="1", memory=None, priority=100)
        runner.run(iter([Operation(step=1, op="create", kind="pods", obj=crit)]))
        return runner

    jax.config.update("jax_enable_x64", False)
    base = build(False)
    dev = build(True)
    assert dev.replay_driver.device_steps >= 1, dev.replay_driver.unsupported
    pb = base.store.get("pods", "crit")
    pd = dev.store.get("pods", "crit")
    assert pd["status"].get("nominatedNodeName") == "n0"
    assert (
        pb["metadata"]["annotations"] == pd["metadata"]["annotations"]
    )
    post = json.loads(pd["metadata"]["annotations"][POST_FILTER_RESULT_KEY])
    assert post == {"n0": {"DefaultPreemption": "preemption victim"}}


def test_tail_segment_padding_keeps_short_streams_on_device():
    """Streams shorter than K no longer fall back: the tail is padded
    with inactive no-op steps on-device (ROADMAP open item)."""

    def stream():
        for i in range(4):
            yield Operation(
                step=0, op="create", kind="nodes",
                obj=make_node(f"n-{i}", cpu="8", memory="16Gi"),
            )
        for step in range(1, 6):
            yield Operation(
                step=step, op="create", kind="pods",
                obj=make_pod(f"p-{step}", cpu="500m", memory="512Mi"),
            )

    base, dev, driver = _run_pair(stream, x64=False, k=8)
    assert _steps_sig(dev) == _steps_sig(base)
    assert driver.fallback_steps == 0
    assert driver.device_steps == 6  # step 0 (bootstrap) + 5 pod steps


def test_sampling_k_validated_against_real_node_count():
    """Library-direct regression (review satellite): sampling_k between
    the real node count and the padded axis must be rejected — padding
    rows never pass filters, so such a K silently under-samples."""
    from ksim_tpu.engine import Engine
    from ksim_tpu.engine.profiles import default_plugins
    from ksim_tpu.state.featurizer import Featurizer

    nodes = [make_node(f"n-{i}", cpu="4", memory="8Gi") for i in range(5)]
    pods = [make_pod("p-0", cpu="1", memory="1Gi")]
    feats = Featurizer().featurize(nodes, (), queue_pods=pods)
    assert feats.nodes.padded > feats.nodes.count  # padding exists
    Engine(feats, default_plugins(feats), record="selection", sampling_k=5)
    with pytest.raises(ValueError, match="real node count"):
        Engine(
            feats, default_plugins(feats), record="selection",
            sampling_k=feats.nodes.count + 1,
        )


# ---------------------------------------------------------------------------
# Fleet replay (round 12, engine/fleet.py): S independent trajectories,
# one vmapped dispatch, per-lane parity with the solo device path.
# ---------------------------------------------------------------------------


def _small_churn():
    return churn_scenario(0, n_nodes=48, n_events=200, ops_per_step=20)


def test_fleet_lanes_byte_identical_to_solo_device():
    """The fleet parity lock's in-suite form: every lane of a 3-lane
    fleet lands per-step (scheduled, unschedulable, pending) triples and
    totals byte-identical to the SOLO device-replay run of the same
    stream — and the shared universe is lowered ONCE per window (only
    the cohort leader's driver ever lowers; the counter-based guard)."""
    jax.config.update("jax_enable_x64", False)
    kw = dict(max_pods_per_pass=1024, pod_bucket_min=128, device_segment_steps=8)
    solo_r = ScenarioRunner(device_replay=True, **kw)
    solo = solo_r.run(_small_churn())
    assert solo_r.replay_driver.device_steps >= 8
    fleet_r = ScenarioRunner(device_replay=True, fleet=3, **kw)
    agg = fleet_r.run(_small_churn())
    assert agg.lanes is not None and len(agg.lanes) == 3
    for ln in fleet_r.fleet_lanes:
        assert _steps_sig(ln.result) == _steps_sig(solo), f"lane {ln.idx}"
        assert (
            ln.result.pods_scheduled,
            ln.result.unschedulable_attempts,
        ) == (solo.pods_scheduled, solo.unschedulable_attempts)
        assert ln.driver.device_steps == solo_r.replay_driver.device_steps
    # Aggregate = sum of lanes.
    assert agg.pods_scheduled == 3 * solo.pods_scheduled
    # Lowered once, not per lane: every follower's driver did ZERO
    # lowerings and built no device featurizer.
    stats = fleet_r.fleet_driver.stats()
    lowerings = stats["lane_lowerings"]
    assert sum(lowerings) == lowerings[0] > 0, stats
    assert stats["lanes_on_device"] == 1.0
    assert stats["group_dispatches"] == len(solo_r.replay_driver.lower_log)
    for ln in fleet_r.fleet_lanes[1:]:
        assert ln.driver._featurizer is None


@pytest.mark.slow
def test_fleet_full_record_annotations_byte_identical():
    """record="full" through the fleet: every lane's decoded result
    annotations (filter/score/finalscore maps, history, selected node)
    must be byte-identical to the solo device run's store contents."""

    def stream():
        return churn_scenario(0, n_nodes=24, n_events=160, ops_per_step=16)

    def annos(store):
        return {
            p["metadata"]["name"]: p["metadata"].get("annotations", {})
            for p in store.list("pods")
        }

    jax.config.update("jax_enable_x64", False)
    kw = dict(record="full", max_pods_per_pass=64, pod_bucket_min=32,
              device_replay=True, device_segment_steps=8)
    solo_r = ScenarioRunner(**kw)
    solo = solo_r.run(stream())
    assert solo_r.replay_driver.device_steps >= 4
    fleet_r = ScenarioRunner(fleet=2, **kw)
    fleet_r.run(stream())
    a_solo = annos(solo_r.store)
    for ln in fleet_r.fleet_lanes:
        assert _steps_sig(ln.result) == _steps_sig(solo)
        a_lane = annos(ln.runner.store)
        assert set(a_lane) == set(a_solo)
        for name in a_solo:
            assert a_lane[name] == a_solo[name], (
                f"lane {ln.idx} annotations diverged for {name}"
            )


@pytest.mark.slow
def test_fleet_lane_ops_override_runs_divergent_lane():
    """A per-lane stream (run(..., lane_ops=...)) rides the solo device
    path outside the cohort and matches ITS OWN solo run; base lanes
    still share one lowering."""
    jax.config.update("jax_enable_x64", False)
    kw = dict(max_pods_per_pass=1024, pod_bucket_min=128, device_segment_steps=8)

    def other_stream():
        return churn_scenario(7, n_nodes=32, n_events=120, ops_per_step=20)

    solo_base = ScenarioRunner(device_replay=True, **kw).run(_small_churn())
    solo_other = ScenarioRunner(device_replay=True, **kw).run(other_stream())
    fleet_r = ScenarioRunner(device_replay=True, fleet=3, **kw)
    fleet_r.run(_small_churn(), lane_ops={1: other_stream()})
    lanes = fleet_r.fleet_lanes
    assert _steps_sig(lanes[0].result) == _steps_sig(solo_base)
    assert _steps_sig(lanes[1].result) == _steps_sig(solo_other)
    assert _steps_sig(lanes[2].result) == _steps_sig(solo_base)
    assert not lanes[1].convergent and not lanes[1].shared_stream
    # The divergent lane lowered for itself; the cohort shared one.
    assert len(lanes[1].driver.lower_log) > 0
    assert len(lanes[2].driver.lower_log) == 0


def test_fleet_cancel_lands_at_dispatch_boundary():
    """Round 16: a cancel raised mid-run inside a fleet cohort aborts
    at the NEXT lane dispatch boundary (the per-round check in
    FleetDriver.run), propagating RunCancelled through the group
    exception ladders — which deliberately do not catch it — with
    every lane's store left at a committed segment boundary."""
    from ksim_tpu.errors import RunCancelled

    class FlipAfter:
        """A cancel flag that trips after N polls — mid-run, not
        before the first round."""

        def __init__(self, n):
            self.n = n
            self.polls = 0

        def is_set(self):
            self.polls += 1
            return self.polls > self.n

    jax.config.update("jax_enable_x64", False)
    flag = FlipAfter(3)
    fleet_r = ScenarioRunner(
        device_replay=True, fleet=2, cancel=flag,
        max_pods_per_pass=1024, pod_bucket_min=128, device_segment_steps=8,
    )
    with pytest.raises(RunCancelled):
        fleet_r.run(_small_churn())
    assert flag.polls > 3  # the run made progress before the trip
    # Rollback invariant: no lane's store holds a torn segment — every
    # store transaction either committed whole or rolled back.
    for ln in fleet_r.fleet_lanes or ():
        assert ln.runner.store._txn is None


def test_fleet_rejects_bad_config():
    with pytest.raises(ValueError, match="device_replay"):
        ScenarioRunner(fleet=2)
    with pytest.raises(ValueError, match="at least 2"):
        ScenarioRunner(device_replay=True, fleet=1)
    from ksim_tpu.state.cluster import ClusterStore

    with pytest.raises(ValueError, match="own stores"):
        ScenarioRunner(store=ClusterStore(), device_replay=True, fleet=2)
    with pytest.raises(ValueError, match="lane_ops requires fleet"):
        ScenarioRunner().run(iter(()), lane_ops={0: iter(())})
    with pytest.raises(ValueError, match="lane 5 outside"):
        ScenarioRunner(
            device_replay=True, fleet=2, fleet_faults="5:replay.lower=always"
        ).run(iter(()))
    # Same refusal for lane_ops: a typoed index would silently replay
    # the base stream everywhere and the sweep would be vacuous.
    with pytest.raises(ValueError, match=r"lane_ops lanes \[4\] outside"):
        ScenarioRunner(device_replay=True, fleet=4).run(
            iter(()), lane_ops={4: iter(())}
        )
    # ...and for a lane fault spec with no fleet to arm it on.
    with pytest.raises(ValueError, match="fleet_faults requires fleet"):
        ScenarioRunner(device_replay=True, fleet_faults="0:replay.lower=always")


def _preempt_then_create_free_stream():
    """Step 1 schedules a low-priority pod; step 2's critical pod must
    PREEMPT it mid-segment (nominated, stays pending); step 3 is
    create-free, so the lowering predicts no featurize — but the
    nominated pod is still eligible, and the device run must discard."""
    yield Operation(
        step=0, op="create", kind="nodes",
        obj=make_node("n0", cpu="2", memory="8Gi"),
    )
    low = make_pod("low", cpu="1500m", memory=None, priority=1)
    low["metadata"]["creationTimestamp"] = "2024-01-01T00:00:00Z"
    yield Operation(step=1, op="create", kind="pods", obj=low)
    crit = make_pod("crit", cpu="1500m", memory=None, priority=100)
    crit["metadata"]["creationTimestamp"] = "2024-01-01T00:00:01Z"
    yield Operation(step=2, op="create", kind="pods", obj=crit)
    yield Operation(
        step=3, op="create", kind="nodes",
        obj=make_node("n1", cpu="2", memory="8Gi"),
    )


def test_residual_preemption_then_create_free_step_discards_segment():
    """Regression pin for the documented residual (ROADMAP "known
    residuals"): a mid-segment preemption followed by a create-free step
    breaks the featurize prediction and DISCARDS the segment — the
    stream falls back per-pass with identical outcomes.  This pins the
    behavior (fallback, not wrong counts) until a workload motivates
    lifting it."""
    base, dev, driver = _run_pair(
        _preempt_then_create_free_stream, x64=False, k=8, preemption=True
    )
    assert _steps_sig(dev) == _steps_sig(base)
    assert driver.unsupported.get("featurize_prediction", 0) >= 1
    # The per-pass path really preempted (the residual needs a real
    # mid-segment preemption to trigger).
    assert base.pods_scheduled >= 2


def test_residual_featurize_prediction_inherited_per_lane_in_fleet():
    """Fleet-mode twin of the residual pin: the discard is deterministic
    over identical lanes, so EVERY lane inherits the documented
    fallback (per lane, convergently) and lands the per-pass counts."""
    jax.config.update("jax_enable_x64", False)
    solo_r = ScenarioRunner(device_replay=True, device_segment_steps=8, preemption=True)
    solo = solo_r.run(_preempt_then_create_free_stream())
    fleet_r = ScenarioRunner(
        device_replay=True, device_segment_steps=8, preemption=True, fleet=2
    )
    fleet_r.run(_preempt_then_create_free_stream())
    for ln in fleet_r.fleet_lanes:
        assert _steps_sig(ln.result) == _steps_sig(solo), f"lane {ln.idx}"
        assert ln.driver.unsupported.get("featurize_prediction", 0) >= 1
        assert ln.convergent  # a shared discard degrades convergently


@pytest.mark.slow
def test_fleet_dp_mesh_lanes_match_single_device(monkeypatch):
    """KSIM_FLEET_DP lays the lane axis over a dp-device mesh (the
    conftest forces 8 virtual CPU devices): the sharded group dispatch
    must land byte-identical per-lane outcomes, and the mesh must
    actually have been built (not the silent single-device fallback)."""
    jax.config.update("jax_enable_x64", False)
    kw = dict(max_pods_per_pass=1024, pod_bucket_min=128, device_segment_steps=8)
    solo = ScenarioRunner(device_replay=True, **kw).run(_small_churn())
    monkeypatch.setenv("KSIM_FLEET_DP", "2")
    fleet_r = ScenarioRunner(device_replay=True, fleet=2, **kw)
    fleet_r.run(_small_churn())
    fd = fleet_r.fleet_driver
    assert fd.dp == 2
    with fd._mesh_lock:
        assert fd._mesh and not fd._mesh_failed  # (dp, tp)-keyed, round 19
    assert fd.stats()["lanes_on_device"] == 1.0
    for ln in fleet_r.fleet_lanes:
        assert _steps_sig(ln.result) == _steps_sig(solo), f"lane {ln.idx}"


def test_fleet_vmap_cohort_tiny_stream(monkeypatch):
    """KSIM_FLEET_VMAP=1 drives the cohort through the genuinely
    lane-stacked ``_fleet_segment_fn`` (vmapped carry) — tiny stream so
    the batched compile stays tier-1 cheap; the 6k x 8-lane vmapped leg
    lives in `make lock-check`.  Every lane must match the solo device
    run byte-identically."""

    def stream():
        for i in range(3):
            yield Operation(
                step=0, op="create", kind="nodes",
                obj=make_node(f"n-{i}", cpu="4", memory="8Gi"),
            )
        for step in range(1, 6):
            yield Operation(
                step=step, op="create", kind="pods",
                obj=make_pod(f"p-{step}", cpu="500m", memory="512Mi"),
            )

    jax.config.update("jax_enable_x64", False)
    solo_r = ScenarioRunner(device_replay=True, device_segment_steps=4)
    solo = solo_r.run(stream())
    assert solo_r.replay_driver.device_steps == 6
    monkeypatch.setenv("KSIM_FLEET_VMAP", "1")
    fleet_r = ScenarioRunner(device_replay=True, device_segment_steps=4, fleet=3)
    fleet_r.run(stream())
    assert fleet_r.fleet_driver.stats()["cohort_mode"] == "vmap"
    assert fleet_r.fleet_driver.stats()["lanes_on_device"] == 1.0
    for ln in fleet_r.fleet_lanes:
        assert _steps_sig(ln.result) == _steps_sig(solo), f"lane {ln.idx}"


# ---------------------------------------------------------------------------
# Round 19: 2-D (tp x dp) fleet mesh + donated scan carries
# ---------------------------------------------------------------------------


def test_fleet_tp_dp_mesh_lanes_match_single_device(monkeypatch):
    """KSIM_FLEET_DP=2 composed with KSIM_REPLAY_TP=4 over the
    conftest's 8 virtual devices (the round-19 2-D fleet): lanes lay
    over dp, every lane's [N]/[N, R] node tensors shard over tp, and
    each lane's outcome stays byte-identical to the solo unsharded
    single-device run.  16 nodes keeps every shard at the
    _MIN_SHARD_NODES floor (16 // 4 = 4) so the width is honored, not
    narrowed."""

    def stream():
        for i in range(16):
            yield Operation(
                step=0, op="create", kind="nodes",
                obj=make_node(f"n-{i}", cpu="4", memory="8Gi"),
            )
        for step in range(1, 9):
            yield Operation(
                step=step, op="create", kind="pods",
                obj=make_pod(f"p-{step}", cpu="500m", memory="512Mi"),
            )

    jax.config.update("jax_enable_x64", False)
    monkeypatch.delenv("KSIM_REPLAY_TP", raising=False)
    solo_r = ScenarioRunner(device_replay=True, device_segment_steps=4)
    solo = solo_r.run(stream())
    assert solo_r.replay_driver.device_steps == 9
    monkeypatch.setenv("KSIM_FLEET_DP", "2")
    monkeypatch.setenv("KSIM_REPLAY_TP", "4")
    fleet_r = ScenarioRunner(device_replay=True, device_segment_steps=4, fleet=2)
    fleet_r.run(stream())
    fd = fleet_r.fleet_driver
    assert fd.stats()["cohort_mode"] == "vmap"
    assert fd.stats()["lanes_on_device"] == 1.0
    with fd._mesh_lock:
        assert not fd._mesh_failed
        assert (2, 4) in fd._mesh, fd._mesh  # the (dp, tp) grid was built
    for ln in fleet_r.fleet_lanes:
        assert _steps_sig(ln.result) == _steps_sig(solo), f"lane {ln.idx}"
    # The cohort leader lowers each window once for every lane; all of
    # its segment programs must carry the declared tp=4 node width.
    tps = sorted({e["tp"] for ln in fleet_r.fleet_lanes for e in ln.driver.lower_log})
    assert tps == [4], tps
    leader = max(
        (ln.driver for ln in fleet_r.fleet_lanes), key=lambda d: len(d.lower_log)
    )
    assert all(e["full_bytes_per_shard"] > 0 for e in leader.lower_log)
    assert fd.stats()["group_dispatches"] >= 1


def test_replay_donation_engages_and_stays_byte_identical():
    """The single-device segment programs donate the scan carry
    (engine/replay.py _DONATE_ARGNUMS): a donated dispatch
    must raise no jax donation warnings on CPU — XLA either consumed
    the buffers or would warn "Some donated buffers were not usable" —
    and the donated path's per-step outcomes stay byte-identical to
    the per-pass oracle on a preemption-bearing churn stream (the 6k
    lock's in-suite prefix runs through this same donated program;
    tests/test_behavior_locks.py pins its counts)."""
    import warnings

    from ksim_tpu.engine import replay as rmod

    assert rmod._DONATE_ARGNUMS == (4,)
    jax.config.update("jax_enable_x64", False)
    with warnings.catch_warnings():
        warnings.filterwarnings(
            "error", message=".*[Dd]onated buffers.*"
        )
        base = ScenarioRunner().run(
            churn_scenario(0, n_nodes=48, n_events=200, ops_per_step=20)
        )
        dev_r = ScenarioRunner(device_replay=True, device_segment_steps=8)
        dev = dev_r.run(
            churn_scenario(0, n_nodes=48, n_events=200, ops_per_step=20)
        )
    assert dev_r.replay_driver.device_steps > 0
    assert _steps_sig(dev) == _steps_sig(base)


def test_pull_tree_to_host_returns_owned_arrays():
    """Every leaf leaving _pull_tree_to_host must OWN its memory.
    np.asarray of a CPU-backend jax result is zero-copy where the
    layout allows (single-device outputs view the result buffer; a
    replicated multi-device output views shard 0), and with the carry
    donated (round 19) XLA recycles execution memory — a retained view
    decodes garbage once the buffer is reused.  The fleet tp*dp replay
    diverged nondeterministically through exactly this hole; this pins
    the _owned_host contract on both pull branches."""
    import numpy as np

    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ksim_tpu.engine.core import _pull_tree_to_host
    from ksim_tpu.engine.sharding import make_mesh

    jax.config.update("jax_enable_x64", False)

    def owned(h):
        return isinstance(h, np.ndarray) and (
            h.flags["OWNDATA"] or isinstance(h.base, np.ndarray)
            and h.base.flags["OWNDATA"]
        )

    # Packed branch: >= 2 single-device array leaves.
    f = jax.jit(lambda t: jax.tree_util.tree_map(lambda x: x * 2, t))
    tree = f({"a": jnp.arange(64, dtype=jnp.float32),
              "b": jnp.ones((8, 8), jnp.int32)})
    out = _pull_tree_to_host(tree)
    for k, h in out.items():
        assert owned(h), f"packed-branch leaf {k} is a device view"
    # Fallback branch: multi-device leaves (replicated is the zero-copy
    # trap; sharded gathers).  Needs the 8 virtual CPU devices conftest
    # forces.
    mesh = make_mesh(4, dp=2)
    repl = jax.device_put(np.arange(16, dtype=np.float32),
                          NamedSharding(mesh, P()))
    shrd = jax.device_put(np.arange(16, dtype=np.float32),
                          NamedSharding(mesh, P("tp")))
    out2 = _pull_tree_to_host({"r": repl, "s": shrd})
    for k, h in out2.items():
        assert owned(h), f"fallback-branch leaf {k} is a device view"


# ---------------------------------------------------------------------------
# Round 17: tp-sharded device replay (KSIM_REPLAY_TP / service shard_mesh)
# ---------------------------------------------------------------------------


def _run_sharded_pair(stream_factory, tp, monkeypatch, *, k=8, **runner_kw):
    """The same stream through the solo device path and the
    KSIM_REPLAY_TP-sharded one (conftest forces 8 virtual CPU devices);
    returns both results and both drivers so callers can pin counts AND
    coverage evidence."""
    jax.config.update("jax_enable_x64", False)
    monkeypatch.delenv("KSIM_REPLAY_TP", raising=False)
    solo_r = ScenarioRunner(device_replay=True, device_segment_steps=k, **runner_kw)
    solo = solo_r.run(stream_factory())
    monkeypatch.setenv("KSIM_REPLAY_TP", str(tp))
    shard_r = ScenarioRunner(device_replay=True, device_segment_steps=k, **runner_kw)
    shard = shard_r.run(stream_factory())
    return solo, solo_r, shard, shard_r


def _lowered_tps(driver):
    return sorted({e["tp"] for e in driver.lower_log})


def test_device_sharded_small_churn_byte_parity(monkeypatch):
    """KSIM_REPLAY_TP=8 lays the node axis over a tp mesh: per-step
    triples, totals and device coverage must all be byte-identical to
    the solo device run, with proof the mesh was honored (every lowered
    segment at tp=8) and zero shard_mesh fallbacks."""
    solo, solo_r, shard, shard_r = _run_sharded_pair(
        lambda: churn_scenario(0, n_nodes=200, n_events=800, ops_per_step=50),
        8, monkeypatch, max_pods_per_pass=1024, pod_bucket_min=128,
    )
    assert _steps_sig(shard) == _steps_sig(solo)
    assert (shard.pods_scheduled, shard.unschedulable_attempts) == (
        solo.pods_scheduled, solo.unschedulable_attempts,
    )
    d = shard_r.replay_driver
    assert d.device_steps == solo_r.replay_driver.device_steps
    assert d.device_steps >= 8
    assert "shard_mesh" not in d.unsupported, d.unsupported
    assert _lowered_tps(d) == [8], d.lower_log
    assert _lowered_tps(solo_r.replay_driver) == [1]
    assert d.fallback_steps == solo_r.replay_driver.fallback_steps == 0
    # The full-record budget is per shard: tp=8 carries 1/8th of the
    # solo run's bytes per chip.
    per_shard = max(e["full_bytes_per_shard"] for e in d.lower_log)
    assert per_shard * 8 == max(
        e["full_bytes_per_shard"] for e in solo_r.replay_driver.lower_log
    )


def test_device_sharded_full_record_annotations_byte_parity(monkeypatch):
    """record="full" under the mesh: result tensors stream out of the
    sharded scan per shard, and the host decode must land every pod
    annotation (filter/score/finalscore maps, history, selected-node)
    byte-identical to the solo device run.  The per-shard byte budget is
    the point of round 17 — the lower log must carry it."""

    def annos(runner):
        return {
            p["metadata"]["name"]: p["metadata"].get("annotations", {})
            for p in runner.store.list("pods")
        }

    solo, solo_r, shard, shard_r = _run_sharded_pair(
        lambda: churn_scenario(0, n_nodes=24, n_events=160, ops_per_step=16),
        8, monkeypatch, record="full", max_pods_per_pass=64, pod_bucket_min=32,
    )
    assert _steps_sig(shard) == _steps_sig(solo)
    assert annos(shard_r) == annos(solo_r)
    d = shard_r.replay_driver
    assert d.device_steps == solo_r.replay_driver.device_steps
    assert _lowered_tps(d) == [8]
    for entry in d.lower_log:
        assert entry["full_bytes_per_shard"] > 0


def test_device_sharded_preemption_parity_narrows_tiny_universe(monkeypatch):
    """Preemption through the sharded scan on a universe SMALLER than
    the requested mesh: the width floor (_MIN_SHARD_NODES) narrows
    tp=8 to tp=2 at N=8 instead of trusting the partitioner below it
    (the sel/nom doubling hazard — see _lower), and the narrowed run
    still lands store, eviction order and counts byte-identical."""

    def stream():
        for i in range(3):
            yield Operation(
                step=0, op="create", kind="nodes",
                obj=make_node(f"n-{i}", cpu="4", memory="16Gi"),
            )
        for step in range(1, 17):
            prio = [0, 0, 5, 10][step % 4]
            pod = make_pod(
                f"p-{step}", cpu="1500m", memory="256Mi", priority=prio
            )
            pod["metadata"]["creationTimestamp"] = f"2026-01-{step:02d}T00:00:00Z"
            yield Operation(step=step, op="create", kind="pods", obj=pod)

    def pods_state(runner):
        return sorted(
            (
                p["metadata"]["name"],
                p.get("spec", {}).get("nodeName"),
                p.get("status", {}).get("nominatedNodeName"),
            )
            for p in runner.store.list("pods")
        )

    jax.config.update("jax_enable_x64", False)
    monkeypatch.delenv("KSIM_REPLAY_TP", raising=False)
    solo_r = ScenarioRunner(
        preemption=True, device_replay=True, device_segment_steps=4
    )
    ev_solo = _collect_evictions(solo_r)
    solo = solo_r.run(stream())
    monkeypatch.setenv("KSIM_REPLAY_TP", "8")
    shard_r = ScenarioRunner(
        preemption=True, device_replay=True, device_segment_steps=4
    )
    ev_shard = _collect_evictions(shard_r)
    shard = shard_r.run(stream())
    assert _steps_sig(shard) == _steps_sig(solo)
    assert pods_state(shard_r) == pods_state(solo_r)
    assert ev_shard == ev_solo
    assert ev_solo, "stream never triggered preemption — fixture is vacuous"
    d = shard_r.replay_driver
    assert d.device_steps == solo_r.replay_driver.device_steps
    assert _lowered_tps(d) == [2], d.lower_log  # the floor, not the request


@pytest.fixture
def fresh_mesh_compiles():
    """The mesh twin of the segment program compiled IN this process, not
    loaded from the persistent compile cache.

    On the 8-virtual-device CPU mesh this test's tp=8 program holds two
    independent collectives (an all-gather and an all-reduce).  Compiled
    here it runs; the SAME program deserialized from a warm persistent
    cache (``cpu_aot_loader``) stops in them — some devices wait in the
    one, some in the other, none in both — until ``rendezvous.cc`` aborts
    the whole process after 40 s ("Termination timeout ... only 6 of them
    arrived"): 4 of 4 runs against a warm cache, 0 of 3 against a cold
    one, alone on an idle 8-core machine, the parent's tree and this one
    alike (PR 34).  That is what failed under the driver's six workers
    in PR 33 — its cache was warm because PR 33 left the flat program's
    text as it was — and not load: no limit of ours bounds the wait, so
    the test brings its own compile.  Real chips do not rendezvous
    through XLA:CPU; only the virtual mesh is exposed."""
    from jax.experimental.compilation_cache import compilation_cache

    from ksim_tpu.engine import replay

    def persistent_cache(on: bool) -> None:
        # An executable this process already holds would be reused as it is.
        replay._segment_fn_nodonate.clear_cache()
        jax.config.update("jax_enable_compilation_cache", on)
        compilation_cache.reset_cache()

    persistent_cache(False)
    try:
        yield
    finally:
        persistent_cache(True)


def test_device_sharded_explicit_mesh_contract(fresh_mesh_compiles):
    """An explicit service shard_mesh is a layout contract: a dp=1 tp
    mesh is honored by the device path (every segment lowered at its
    width); any other shape falls back per-pass with the narrowed
    "shard_mesh" reason — and both land the same counts."""
    from ksim_tpu.engine.sharding import make_mesh
    from ksim_tpu.scheduler.service import SchedulerService
    from ksim_tpu.state.cluster import ClusterStore

    jax.config.update("jax_enable_x64", False)

    def run(mesh):
        store = ClusterStore()
        svc = SchedulerService(store, shard_mesh=mesh)
        runner = ScenarioRunner(
            store, svc, device_replay=True, device_segment_steps=8,
            max_pods_per_pass=1024, pod_bucket_min=128,
        )
        res = runner.run(
            churn_scenario(0, n_nodes=48, n_events=200, ops_per_step=20)
        )
        return res, runner.replay_driver

    base, base_d = run(None)
    tp_res, tp_d = run(make_mesh(8, dp=1))
    dp_res, dp_d = run(make_mesh(8, dp=2))
    for res in (tp_res, dp_res):
        assert (res.pods_scheduled, res.unschedulable_attempts) == (
            base.pods_scheduled, base.unschedulable_attempts,
        )
    assert [(s.step, s.scheduled) for s in tp_res.steps] == [
        (s.step, s.scheduled) for s in base.steps
    ]
    assert tp_d.device_steps == base_d.device_steps
    assert _lowered_tps(tp_d) == [8]
    assert "shard_mesh" not in tp_d.unsupported
    # dp=2: rejected up front, every segment per-pass, counts intact.
    assert dp_d.device_steps == 0
    assert dp_d.unsupported.get("shard_mesh", 0) >= 1
    assert not dp_d.lower_log


def test_device_sharded_dead_device_contained(monkeypatch):
    """A mesh wider than the host's devices is a DEVICE error, not a
    lowering bug: the ladder counts device_error, the breaker opens
    after the threshold, and the whole stream still lands the per-pass
    counts (containment, repo invariant since round 4)."""
    jax.config.update("jax_enable_x64", False)
    monkeypatch.delenv("KSIM_REPLAY_TP", raising=False)
    solo = ScenarioRunner(device_replay=True, device_segment_steps=8).run(
        churn_scenario(0, n_nodes=48, n_events=200, ops_per_step=20)
    )
    # N=64 -> gcd(64, 64)=64, width-floor-narrowed to 16 — still wider
    # than the 8 virtual devices, so every dispatch attempt dies in
    # _tp_mesh before touching a buffer.
    monkeypatch.setenv("KSIM_REPLAY_TP", "64")
    shard_r = ScenarioRunner(device_replay=True, device_segment_steps=8)
    shard = shard_r.run(
        churn_scenario(0, n_nodes=48, n_events=200, ops_per_step=20)
    )
    assert _steps_sig(shard) == _steps_sig(solo)
    d = shard_r.replay_driver
    assert d.device_steps == 0
    assert d.unsupported.get("device_error", 0) >= 1, d.unsupported
    assert d.breaker_tripped
