"""The repo's churn behavior locks, asserted in-suite (round-5 verdict #1).

The flagship replay's counts (seed 0, 2000 nodes — repo CLAUDE.md) were
previously enforced only by discipline: a parity regression (the
class the locks exist to catch) would fail only if someone re-ran the
replay and eyeballed the counts.  A round-4 TPU run proved the gap — its
churn recorded 52582/42840 against the 52781/42829 lock and nothing
noticed, because the f32 fast mode diverged ACROSS PLATFORMS (TPU's
approximate f32 divide truncated exact integer ratios one ulp low in
InterPodAffinity's normalize, and backend f32 log ulps flipped
PodTopologySpread's round()).  Both kernels are now platform-
deterministic by construction (integer normalize floor; trace-time log
table + fixed-order reduce), so ONE set of counts is the contract on
every backend, in both modes — these tests pin the 6k prefix (~15 s;
the 50k run is `make lock-check`) exactly as the benchmark's churn-2k
deployment runs it (ScenarioRunner(max_pods_per_pass=1024,
pod_bucket_min=128), ops_per_step=100).

Reference intent: replay parity is the product metric — recorded
results as ground truth (storereflector.go:78-146).
"""

import jax
import pytest

from ksim_tpu.scenario import ScenarioRunner, churn_scenario

# seed 0, 2000 nodes, 6000 events -> applied events include the step
# padding the generator emits (6430), and the scheduling outcomes are
# the locked prefix of the 50k flagship replay (50k locks: 52781/42829).
LOCK_SCHEDULED = 2524
LOCK_UNSCHEDULABLE = 471
LOCK_EVENTS = 6430


def _run_locked_churn() -> tuple[int, int, int]:
    runner = ScenarioRunner(max_pods_per_pass=1024, pod_bucket_min=128)
    res = runner.run(
        churn_scenario(0, n_nodes=2000, n_events=6000, ops_per_step=100)
    )
    return res.pods_scheduled, res.unschedulable_attempts, res.events_applied


@pytest.mark.parametrize("x64", [False, True], ids=["f32-fast", "exact-x64"])
def test_churn_lock_6k_seed0(x64):
    """Both modes land on identical counts (exact mode has always been
    platform-identical; f32 now is too — drift here means a scoring-path
    behavior change that MUST be deliberate and re-baselined, see
    docs/churn_floor.md)."""
    prev = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", x64)
    try:
        scheduled, unschedulable, events = _run_locked_churn()
    finally:
        jax.config.update("jax_enable_x64", prev)
    assert events == LOCK_EVENTS
    assert (scheduled, unschedulable) == (LOCK_SCHEDULED, LOCK_UNSCHEDULABLE)


def test_churn_lock_6k_holds_with_tracing_enabled(tmp_path):
    """Observability must be zero-perturbation: the locked prefix's
    counts are byte-identical with the trace plane FULLY enabled
    (``KSIM_TRACE_OUT`` set: histograms + event ring + file export),
    and the emitted Chrome-trace JSON validates with the per-pass
    phase spans on it."""
    import json
    import os

    from ksim_tpu.obs import TRACE

    out = tmp_path / "trace.json"
    prev_state = (TRACE._active, TRACE._ring_on, TRACE._user_disabled)
    prev_x64 = jax.config.jax_enable_x64
    os.environ["KSIM_TRACE_OUT"] = str(out)
    try:
        TRACE.configure_from_env()
        jax.config.update("jax_enable_x64", False)
        scheduled, unschedulable, events = _run_locked_churn()
        assert events == LOCK_EVENTS
        assert (scheduled, unschedulable) == (LOCK_SCHEDULED, LOCK_UNSCHEDULABLE)
        TRACE.export_chrome(str(out))
        doc = json.loads(out.read_text())
        names = {e["name"] for e in doc["traceEvents"]}
        # The per-pass path's phase spans + pass-outcome events.
        assert {"runner.step", "service.schedule", "service.pass"} <= names
        # ... and the pass's phases beneath service.schedule (PR 25): the
        # lock holds with every new site recording.
        assert {
            "service.featurize", "engine.pack", "engine.exec", "engine.pull",
            "service.bind",
        } <= names
        n_sched_spans = sum(
            1
            for e in doc["traceEvents"]
            if e["name"] == "service.schedule" and e.get("ph") == "X"
        )
        assert n_sched_spans >= 1
    finally:
        jax.config.update("jax_enable_x64", prev_x64)
        os.environ.pop("KSIM_TRACE_OUT", None)
        TRACE.out_path = None
        # Drop the 6k run's ring contents (up to 65536 record dicts)
        # and restore the exact pre-test flags — NOT via disable(),
        # whose sticky opt-out would leave ensure_timing inert for
        # every later test in the process.
        TRACE.reset()
        TRACE._active, TRACE._ring_on, TRACE._user_disabled = prev_state


# The third lock (PR 27): the burst-5k deployment — every node created
# in step 0, the whole backlog in step 1, ONE pass with no
# max_pods_per_pass.  At the benchmark cell's own size (seed 0, 5000
# nodes, 10000 pods) the counts are 9390/610 — benchmark/replay.py and
# the chip runs hold them; here the rehearsal size (200 nodes, 400 pods)
# is pinned.  Both taken from the plain replay under benchmark/ and the
# oracle (tests/test_burst_onestep.py), not from the program.
BURST_LOCK_SCHEDULED = 384
BURST_LOCK_UNSCHEDULABLE = 16


@pytest.mark.parametrize("device", [False, True], ids=["per-pass", "device"])
@pytest.mark.parametrize("x64", [False, True], ids=["f32-fast", "exact-x64"])
def test_burst_lock_onestep_rehearsal_size(x64, device):
    """As the job plane runs the benchmark's burst-5k configuration
    (deviceReplay, preemption, podBucketMin 128, no pass cap), on both
    paths and in both modes."""
    prev = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", x64)
    try:
        runner = ScenarioRunner(
            preemption=True, pod_bucket_min=128, device_replay=device
        )
        res = runner.run(
            churn_scenario(
                0, n_nodes=200, n_events=600, ops_per_step=400,
                pod_create_frac=1.0, pod_delete_frac=0.0,
            )
        )
    finally:
        jax.config.update("jax_enable_x64", prev)
    assert res.events_applied == 600
    assert (res.pods_scheduled, res.unschedulable_attempts) == (
        BURST_LOCK_SCHEDULED, BURST_LOCK_UNSCHEDULABLE,
    )
    if device:
        stats = runner.replay_driver.stats()
        assert stats["device_steps"] == 2 and stats["fallback_steps"] == 0
        assert stats["unsupported"] == {}


@pytest.mark.slow
def test_churn_lock_6k_holds_under_dispatch_faults_with_recovery(monkeypatch):
    """The chaos leg (`make lock-check`, round 15): the locked 6k counts
    are BYTE-IDENTICAL while the fault plane kills the first two device
    dispatches, the breaker trips, and half-open recovery (a cooldown'd
    probe segment) re-promotes the device path mid-run.  Faults change
    WHERE steps execute (host vs device), never WHAT they compute —
    the durability round's end-to-end breaker-recovery proof."""
    from ksim_tpu.faults import FAULTS

    monkeypatch.setenv("KSIM_REPLAY_BREAKER_N", "2")
    monkeypatch.setenv("KSIM_REPLAY_BREAKER_COOLDOWN_S", "0.05")
    prev_x64 = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", False)
    FAULTS.reset()
    FAULTS.arm("replay.dispatch", "first:2@device")
    try:
        runner = ScenarioRunner(
            max_pods_per_pass=1024, pod_bucket_min=128, device_replay=True
        )
        res = runner.run(
            churn_scenario(0, n_nodes=2000, n_events=6000, ops_per_step=100)
        )
    finally:
        FAULTS.reset()
        jax.config.update("jax_enable_x64", prev_x64)
    assert res.events_applied == LOCK_EVENTS
    assert (res.pods_scheduled, res.unschedulable_attempts) == (
        LOCK_SCHEDULED,
        LOCK_UNSCHEDULABLE,
    )
    d = runner.replay_driver
    assert d.breaker_closes >= 1, d.stats()["breaker"]  # recovered mid-run
    assert d.breaker_tripped is False
    assert d.device_steps > 0
    assert d.device_steps + d.fallback_steps == len(res.steps)


# The trace workload family (round 14, ksim_tpu/traces): the bundled
# hand-checked Borg fixture compiled at 24 nodes / ops_per_step=2 —
# the SECOND locked-count family next to synthetic churn, and the
# first priority-DIVERSE one (trace tiers land on PRIORITY_LADDER, so
# windows are not priority-flat).
TRACE_LOCK_SCHEDULED = 56
TRACE_LOCK_UNSCHEDULABLE = 19
TRACE_LOCK_EVENTS = 126


def test_trace_lock_borg_mini_device_vs_per_pass():
    """The trace-ingestion acceptance lock: the bundled fixture compiles
    deterministically and replays byte-identically through the per-pass
    AND the device-resident path, with the device path carrying EVERY
    step (0 fallbacks — in-vocabulary by construction, and create-free
    steps with eligible pods stay on-device since the round-14
    featurize-prediction refinement for static node universes)."""
    from ksim_tpu.traces import trace_operations

    jax.config.update("jax_enable_x64", False)
    ops = trace_operations(
        "tests/fixtures/traces/borg_mini.jsonl",
        "borg",
        nodes=24,
        ops_per_step=2,
    )
    base_r = ScenarioRunner(pod_bucket_min=64)
    base = base_r.run(list(ops))
    assert base.events_applied == TRACE_LOCK_EVENTS
    assert (base.pods_scheduled, base.unschedulable_attempts) == (
        TRACE_LOCK_SCHEDULED,
        TRACE_LOCK_UNSCHEDULABLE,
    )
    dev_r = ScenarioRunner(pod_bucket_min=64, device_replay=True)
    dev = dev_r.run(list(ops))
    assert (dev.pods_scheduled, dev.unschedulable_attempts) == (
        TRACE_LOCK_SCHEDULED,
        TRACE_LOCK_UNSCHEDULABLE,
    )
    base_sig = [
        (s.step, s.scheduled, s.unschedulable, s.pending_after) for s in base.steps
    ]
    dev_sig = [
        (s.step, s.scheduled, s.unschedulable, s.pending_after) for s in dev.steps
    ]
    assert dev_sig == base_sig
    driver = dev_r.replay_driver
    assert driver.fallback_steps == 0, driver.unsupported
    assert driver.device_steps == len(dev.steps)


def test_trace_borg_mini_device_run_reports_its_phase_split():
    """What a trace replay hands its readers beside the counts: an
    empty fallback histogram and ``ScenarioResult.phase_seconds`` — the
    run's wall split by span name (the job result document's ``phases``,
    `tools/trace_check.py`'s record) — with the device path's three
    phases in it and no per-pass step."""
    from ksim_tpu.traces import trace_operations

    jax.config.update("jax_enable_x64", False)
    ops = trace_operations(
        "tests/fixtures/traces/borg_mini.jsonl", "borg", nodes=24, ops_per_step=2
    )
    runner = ScenarioRunner(pod_bucket_min=64, device_replay=True)
    res = runner.run(list(ops))
    assert (res.pods_scheduled, res.unschedulable_attempts) == (
        TRACE_LOCK_SCHEDULED,
        TRACE_LOCK_UNSCHEDULABLE,
    )
    assert runner.replay_driver.device_steps == len(res.steps)
    assert dict(runner.replay_driver.unsupported) == {}
    assert {"replay.lower", "replay.dispatch", "replay.reconcile"} <= set(
        res.phase_seconds
    )
    assert "runner.step" not in res.phase_seconds
    assert all(v >= 0 for v in res.phase_seconds.values())
    assert sum(res.phase_seconds.values()) > 0


def test_trace_lock_borg_mini_holds_with_streaming_ingest():
    """Round 20: the SAME locked counts through the windowed streaming
    pipeline (traces/stream.py feeding the runner window-by-window,
    tiny windows so every step crosses a boundary) on BOTH the per-pass
    and the device path — streaming is a transport change, not a
    behavior change."""
    from ksim_tpu.traces import stream_trace_operations

    jax.config.update("jax_enable_x64", False)

    def fresh():
        return stream_trace_operations(
            "tests/fixtures/traces/borg_mini.jsonl",
            "borg",
            nodes=24,
            ops_per_step=2,
            window=8,
            queue_windows=2,
        )

    base = ScenarioRunner(pod_bucket_min=64).run(fresh())
    assert base.events_applied == TRACE_LOCK_EVENTS
    assert (base.pods_scheduled, base.unschedulable_attempts) == (
        TRACE_LOCK_SCHEDULED,
        TRACE_LOCK_UNSCHEDULABLE,
    )
    dev_r = ScenarioRunner(pod_bucket_min=64, device_replay=True)
    dev = dev_r.run(fresh())
    assert (dev.pods_scheduled, dev.unschedulable_attempts) == (
        TRACE_LOCK_SCHEDULED,
        TRACE_LOCK_UNSCHEDULABLE,
    )
    assert [
        (s.step, s.scheduled, s.unschedulable, s.pending_after) for s in dev.steps
    ] == [
        (s.step, s.scheduled, s.unschedulable, s.pending_after) for s in base.steps
    ]
    assert dev_r.replay_driver.fallback_steps == 0


# The full 50k flagship locks (repo CLAUDE.md).
LOCK_50K_SCHEDULED = 52_781
LOCK_50K_UNSCHEDULABLE = 42_829


@pytest.mark.slow
def test_churn_lock_50k_stepwise_device_vs_per_pass():
    """The one-command behavior-lock verification (`make lock-check`):
    replay the FULL 50k stream through the per-pass path AND the
    device-resident path (preemption enabled — a no-op on this stream,
    which is exactly what the lock asserts) and require the 52781/42829
    totals plus stepwise-identical (scheduled, unschedulable, pending)
    triples between the two paths.  ~10 min CPU."""
    jax.config.update("jax_enable_x64", False)

    def run(device: bool, preemption: bool):
        runner = ScenarioRunner(
            max_pods_per_pass=1024,
            pod_bucket_min=128,
            preemption=preemption,
            device_replay=device,
        )
        res = runner.run(
            churn_scenario(0, n_nodes=2000, n_events=50_000, ops_per_step=100)
        )
        return runner, res

    _base_r, base = run(device=False, preemption=False)
    assert (base.pods_scheduled, base.unschedulable_attempts) == (
        LOCK_50K_SCHEDULED,
        LOCK_50K_UNSCHEDULABLE,
    )
    dev_r, dev = run(device=True, preemption=True)
    assert (dev.pods_scheduled, dev.unschedulable_attempts) == (
        LOCK_50K_SCHEDULED,
        LOCK_50K_UNSCHEDULABLE,
    )
    base_sig = [(s.step, s.scheduled, s.unschedulable, s.pending_after) for s in base.steps]
    dev_sig = [(s.step, s.scheduled, s.unschedulable, s.pending_after) for s in dev.steps]
    assert dev_sig == base_sig
    driver = dev_r.replay_driver
    # Preemption/tail support must keep the stream on-device: PR 1's
    # baseline with preemption enabled was 0 device steps (the whole
    # stream rejected), and even without it the tail step fell back.
    assert driver.fallback_steps == 0, driver.unsupported
    assert driver.device_steps == len(dev.steps)
    # Incremental lowering (round 10), asserted with the cache and the
    # double-buffered prelower fully ON (they are the defaults the
    # counts above were just produced under):
    cache = driver.stats()["lower_cache"]
    # (a) a clean stream keeps the lowered-universe cache hot — every
    # segment after the first is a hit and nothing ever flushed it;
    assert cache["misses"] == 1 and cache["invalidations"] == 0, cache
    assert cache["hits"] == driver.device_round_trips - 1
    # every non-final window's speculative prefix was consumed;
    assert driver.prelower_discarded == 0
    assert driver.prelower_consumed == driver.prelower_windows
    # (b) the counter-based O(delta) guard: every steady-state (cache
    # hit) segment built fresh featurize rows proportional to ITS
    # window's events — never to the universe size.  Counters, not
    # timings, so the guard is stable in CI.
    steady = [e for e in driver.lower_log if e["cache_hit"]]
    assert steady, driver.lower_log
    for entry in steady:
        assert entry["rows_built"] <= entry["events"] + 32, entry


@pytest.mark.slow
@pytest.mark.parametrize("mode", ["dedupe", "vmap"])
def test_churn_fleet_lock_6k_lanes8(mode, monkeypatch):
    """The fleet parity lock (`make lock-check`, round 12): 8 lanes of
    the locked 6k prefix through BOTH cohort dispatch modes — every
    lane must land 2524/471 with stepwise triples identical to the solo
    device run, the whole fleet on-device, and the shared universe
    lowered ONCE per window (counter-based guard: only the cohort
    leader's driver ever lowers; every follower records zero).

    The ``vmap`` leg runs the genuinely lane-stacked
    ``_fleet_segment_fn`` program (KSIM_FLEET_VMAP=1) — the proof that
    the carry, the RNG-free kernels and the reconcile boundaries are
    lane-INDEPENDENT, not merely that one trajectory fans out.  The
    ``dedupe`` leg locks the production default (one dispatch, S
    decodes/reconciles, each lane's verify_segment proving its own
    store)."""
    jax.config.update("jax_enable_x64", False)
    if mode == "vmap":
        monkeypatch.setenv("KSIM_FLEET_VMAP", "1")
    else:
        monkeypatch.delenv("KSIM_FLEET_VMAP", raising=False)
    kw = dict(max_pods_per_pass=1024, pod_bucket_min=128, preemption=True)

    def stream():
        return churn_scenario(0, n_nodes=2000, n_events=6000, ops_per_step=100)

    solo_r = ScenarioRunner(device_replay=True, **kw)
    solo = solo_r.run(stream())
    assert (solo.pods_scheduled, solo.unschedulable_attempts) == (
        LOCK_SCHEDULED,
        LOCK_UNSCHEDULABLE,
    )
    solo_sig = [
        (s.step, s.scheduled, s.unschedulable, s.pending_after) for s in solo.steps
    ]
    fleet_r = ScenarioRunner(device_replay=True, fleet=8, **kw)
    agg = fleet_r.run(stream())
    assert agg.pods_scheduled == 8 * LOCK_SCHEDULED
    assert agg.unschedulable_attempts == 8 * LOCK_UNSCHEDULABLE
    for ln in fleet_r.fleet_lanes:
        r = ln.result
        assert (r.pods_scheduled, r.unschedulable_attempts) == (
            LOCK_SCHEDULED,
            LOCK_UNSCHEDULABLE,
        ), f"lane {ln.idx}"
        sig = [
            (s.step, s.scheduled, s.unschedulable, s.pending_after) for s in r.steps
        ]
        assert sig == solo_sig, f"lane {ln.idx} stepwise divergence"
        assert ln.convergent
        assert ln.driver.fallback_steps == 0, ln.driver.unsupported
    stats = fleet_r.fleet_driver.stats()
    assert stats["cohort_mode"] == mode
    # The lowered-once-per-window guard: one driver (the cohort leader)
    # did ALL the lowering; 7 followers did none — and the leader's
    # lowered-universe cache stayed hot exactly as the solo run's does.
    lowerings = stats["lane_lowerings"]
    assert sum(lowerings) == max(lowerings) > 0, stats
    assert lowerings.count(0) == 7, stats
    assert stats["lanes_on_device"] == 1.0, stats
    assert stats["group_dispatches"] == stats["shared_lowerings"]
    leader = max(
        (ln.driver for ln in fleet_r.fleet_lanes), key=lambda d: len(d.lower_log)
    )
    cache = leader.stats()["lower_cache"]
    assert cache["misses"] == 1 and cache["invalidations"] == 0, cache


# ---------------------------------------------------------------------------
# Round 17: the locked counts through the tp-SHARDED device path
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_churn_lock_6k_sharded_tp8(monkeypatch):
    """The flagship locked prefix with the node axis laid over a tp=8
    mesh (8 virtual CPU devices, conftest): 2524/471 byte-identical,
    stepwise-identical to the SOLO device run, same device coverage,
    zero shard_mesh fallbacks, every lowered segment at tp=8.  GSPMD
    value-preservation is the claim under test — the collectives the
    partitioner inserts must never show up in the counts."""
    jax.config.update("jax_enable_x64", False)

    def run():
        runner = ScenarioRunner(
            max_pods_per_pass=1024,
            pod_bucket_min=128,
            device_replay=True,
            device_segment_steps=16,
        )
        res = runner.run(
            churn_scenario(0, n_nodes=2000, n_events=6000, ops_per_step=100)
        )
        return runner, res

    monkeypatch.delenv("KSIM_REPLAY_TP", raising=False)
    solo_r, solo = run()
    monkeypatch.setenv("KSIM_REPLAY_TP", "8")
    shard_r, shard = run()
    assert shard.events_applied == LOCK_EVENTS
    assert (shard.pods_scheduled, shard.unschedulable_attempts) == (
        LOCK_SCHEDULED,
        LOCK_UNSCHEDULABLE,
    )
    solo_sig = [
        (s.step, s.scheduled, s.unschedulable, s.pending_after) for s in solo.steps
    ]
    shard_sig = [
        (s.step, s.scheduled, s.unschedulable, s.pending_after) for s in shard.steps
    ]
    assert shard_sig == solo_sig
    d = shard_r.replay_driver
    assert d.device_steps == solo_r.replay_driver.device_steps
    assert d.device_steps >= 32
    assert "shard_mesh" not in d.unsupported, d.unsupported
    assert sorted({e["tp"] for e in d.lower_log}) == [8], d.lower_log
    # The per-shard full-record budget evidence rides on every entry.
    assert all("full_bytes_per_shard" in e for e in d.lower_log)


# ---------------------------------------------------------------------------
# Round 19: the locked counts through the 2-D (tp x dp) fleet mesh
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_churn_fleet_lock_6k_tp4_dp2(monkeypatch):
    """The flagship locked prefix through the round-19 2-D fleet mesh
    (`make mesh-check`): 2 lanes laid over dp composed with tp=4 node
    sharding on the 8 virtual devices — every lane 2524/471 stepwise-
    identical to the SOLO unsharded device run, the whole fleet
    on-device, the (2, 4) grid built, and every fleet segment lowered
    at the declared width.  This is the composition claim: GSPMD
    value-preservation (round 17) and lane-independence (round 12)
    hold SIMULTANEOUSLY, with the cond-gated preemption search in the
    lowered program.  (Mesh dispatches run the NON-donating twin —
    donated multi-device carries race on the virtual-device CPU
    backend; see replay.py's _DONATE_ARGNUMS note.)"""
    jax.config.update("jax_enable_x64", False)
    kw = dict(max_pods_per_pass=1024, pod_bucket_min=128, preemption=True)

    def stream():
        return churn_scenario(0, n_nodes=2000, n_events=6000, ops_per_step=100)

    monkeypatch.delenv("KSIM_REPLAY_TP", raising=False)
    monkeypatch.delenv("KSIM_FLEET_DP", raising=False)
    solo_r = ScenarioRunner(device_replay=True, **kw)
    solo = solo_r.run(stream())
    assert (solo.pods_scheduled, solo.unschedulable_attempts) == (
        LOCK_SCHEDULED,
        LOCK_UNSCHEDULABLE,
    )
    solo_sig = [
        (s.step, s.scheduled, s.unschedulable, s.pending_after) for s in solo.steps
    ]
    monkeypatch.setenv("KSIM_FLEET_DP", "2")
    monkeypatch.setenv("KSIM_REPLAY_TP", "4")
    fleet_r = ScenarioRunner(device_replay=True, fleet=2, **kw)
    agg = fleet_r.run(stream())
    assert agg.pods_scheduled == 2 * LOCK_SCHEDULED
    assert agg.unschedulable_attempts == 2 * LOCK_UNSCHEDULABLE
    for ln in fleet_r.fleet_lanes:
        r = ln.result
        assert (r.pods_scheduled, r.unschedulable_attempts) == (
            LOCK_SCHEDULED,
            LOCK_UNSCHEDULABLE,
        ), f"lane {ln.idx}"
        sig = [
            (s.step, s.scheduled, s.unschedulable, s.pending_after) for s in r.steps
        ]
        assert sig == solo_sig, f"lane {ln.idx} stepwise divergence"
        assert ln.convergent
        assert ln.driver.fallback_steps == 0, ln.driver.unsupported
    fd = fleet_r.fleet_driver
    stats = fd.stats()
    assert stats["cohort_mode"] == "vmap"
    assert stats["lanes_on_device"] == 1.0, stats
    with fd._mesh_lock:
        assert not fd._mesh_failed
        assert (2, 4) in fd._mesh, fd._mesh
    tps = sorted({e["tp"] for ln in fleet_r.fleet_lanes for e in ln.driver.lower_log})
    assert tps == [4], tps


@pytest.mark.slow
def test_churn_lock_50k_stepwise_sharded_tp8(monkeypatch):
    """The FULL 50k flagship stream under the tp=8 mesh: 52781/42829,
    stepwise-identical to the per-pass path, zero fallbacks — the
    100k-node-scale memory story (per-shard budgets) must not cost a
    single count.  Bench-tier wall clock; `make lock-check`."""
    jax.config.update("jax_enable_x64", False)

    monkeypatch.delenv("KSIM_REPLAY_TP", raising=False)
    base = ScenarioRunner(max_pods_per_pass=1024, pod_bucket_min=128).run(
        churn_scenario(0, n_nodes=2000, n_events=50_000, ops_per_step=100)
    )
    assert (base.pods_scheduled, base.unschedulable_attempts) == (
        LOCK_50K_SCHEDULED,
        LOCK_50K_UNSCHEDULABLE,
    )
    monkeypatch.setenv("KSIM_REPLAY_TP", "8")
    runner = ScenarioRunner(
        max_pods_per_pass=1024,
        pod_bucket_min=128,
        device_replay=True,
        device_segment_steps=16,
    )
    dev = runner.run(
        churn_scenario(0, n_nodes=2000, n_events=50_000, ops_per_step=100)
    )
    assert (dev.pods_scheduled, dev.unschedulable_attempts) == (
        LOCK_50K_SCHEDULED,
        LOCK_50K_UNSCHEDULABLE,
    )
    base_sig = [
        (s.step, s.scheduled, s.unschedulable, s.pending_after) for s in base.steps
    ]
    dev_sig = [
        (s.step, s.scheduled, s.unschedulable, s.pending_after) for s in dev.steps
    ]
    assert dev_sig == base_sig
    d = runner.replay_driver
    assert d.fallback_steps == 0, d.unsupported
    assert d.device_steps == len(dev.steps)
    assert sorted({e["tp"] for e in d.lower_log}) == [8]
