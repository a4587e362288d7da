"""Incremental segment lowering: lower-cache validity + pipelined
executor containment (docs/churn_floor.md "Incremental lowering +
pipelined executor (round 10)").

The lowered-universe cache makes per-segment host lowering O(delta); its
entire correctness story is STRICT invalidation — any path the
incremental bookkeeping cannot track (a per-pass fallback step, a
rolled-back segment reconcile, an out-of-band store write, a breaker
trip) must flush it, and the behavior locks must hold byte-identically
with the cache and the double-buffered prelower fully on.  Small-stream
probes (tier-1) pin the mechanics against per-pass ground truth; the
slow-marked 6k runs pin the locked counts (repo CLAUDE.md) under each
invalidation class and run via ``make faults`` / the full suite.
"""

from __future__ import annotations

import jax
import pytest

from ksim_tpu.faults import FAULTS
from ksim_tpu.scenario import ScenarioRunner, churn_scenario
from ksim_tpu.scenario.runner import Operation
from ksim_tpu.state.cluster import ClusterStore

LOCK = (2524, 471)  # scheduled/unschedulable, seed 0 / 2000 nodes / 6k events


@pytest.fixture(autouse=True)
def _clean_fault_plane():
    FAULTS.reset()
    yield
    FAULTS.reset()


@pytest.fixture(autouse=True)
def _f32_fast_mode():
    jax.config.update("jax_enable_x64", False)
    yield
    jax.config.update("jax_enable_x64", True)


# ---------------------------------------------------------------------------
# Store mutation epoch (the cache's validity anchor)
# ---------------------------------------------------------------------------


def test_store_mutation_epoch_semantics():
    """Every write bumps the epoch EXCEPT writes staged in an
    epoch-exempt transaction (the segment reconcile); a rollback never
    delivers events and exempt writes never move the epoch either way."""
    store = ClusterStore()
    e0 = store.mutation_epoch
    store.create("nodes", {"metadata": {"name": "n1"}})
    assert store.mutation_epoch == e0 + 1
    store.patch("nodes", "n1", "", lambda o: o["metadata"].setdefault("labels", {}))
    assert store.mutation_epoch == e0 + 2
    store.delete("nodes", "n1")
    assert store.mutation_epoch == e0 + 3

    # Exempt transaction: commit moves the store, not the epoch.
    e1 = store.mutation_epoch
    with store.transaction(epoch_exempt=True):
        store.create("nodes", {"metadata": {"name": "n2"}})
    assert store.mutation_epoch == e1
    # Non-exempt transaction: its writes count.
    with store.transaction():
        store.create("nodes", {"metadata": {"name": "n3"}})
    assert store.mutation_epoch == e1 + 1
    # Exempt rollback: store restored, epoch still untouched.
    with pytest.raises(RuntimeError):
        with store.transaction(epoch_exempt=True):
            store.create("nodes", {"metadata": {"name": "n4"}})
            raise RuntimeError("abort")
    assert store.mutation_epoch == e1 + 1
    assert len(store.list("nodes")) == 2  # n2, n3


# ---------------------------------------------------------------------------
# Small-stream probes (tier-1): mechanics against per-pass ground truth
# ---------------------------------------------------------------------------


def _small_ops(extra=()):
    ops = list(churn_scenario(7, n_nodes=24, n_events=600, ops_per_step=40))
    ops.extend(extra)
    return ops


def _signature(res, store):
    return (
        res.pods_scheduled,
        res.unschedulable_attempts,
        [(s.step, s.scheduled, s.unschedulable, s.pending_after) for s in res.steps],
        {
            f"{p['metadata']['namespace']}/{p['metadata']['name']}": p["spec"].get(
                "nodeName"
            )
            for p in store.list("pods")
        },
    )


def _run(ops, device, runner_cls=ScenarioRunner, k=8):
    runner = runner_cls(
        max_pods_per_pass=64, device_replay=device, device_segment_steps=k
    )
    res = runner.run(list(ops))
    return runner, _signature(res, runner.store)


def test_cache_and_pipeline_match_per_pass_small():
    """The steady-state happy path: cache hits + consumed speculative
    prefixes, zero invalidations, and stepwise equality with the
    per-pass ground truth."""
    ops = _small_ops()
    _base, sig_base = _run(ops, device=False)
    dev, sig_dev = _run(ops, device=True)
    assert sig_dev == sig_base
    d = dev.replay_driver
    cache = d.stats()["lower_cache"]
    assert cache["hits"] >= 1
    assert cache["invalidations"] == 0
    assert d.prelower_consumed >= 1
    assert d.prelower_discarded == 0
    # O(delta): every cache-hit lower built at most O(window events)
    # fresh featurize rows, never the whole universe.
    for entry in d.lower_log:
        if entry["cache_hit"]:
            assert entry["rows_built"] <= entry["events"] + 32


def test_gather_guard_survivors_cost_no_per_pod_python(monkeypatch):
    """The guard on what DRIVES the lowering's time, not only on the
    builds: per cache-hit segment every surviving universe pod is served
    by gather from the featurizer's row table (``rows_reused == U -
    rows_built``), a family's rows are recomputed only when its token
    moved (``rows_rebuilt``, 0 while no vocabulary or unit moves), and —
    by a counting wrapper around every per-pod row builder — no per-pod
    Python ran for a pod the table already held."""
    from ksim_tpu.state.featurizer import Featurizer
    from ksim_tpu.state.podtable import PodTable

    held: dict[int, set[int]] = {}  # table -> pod ids it held before this call
    survivor_builds: dict[int, int] = {}
    orig_index, orig_sync = PodTable.index, PodTable.sync

    def index(self, pods, *args):
        held[id(self)] = set(self._row_of)
        return orig_index(self, pods, *args)

    def sync(self, fam, token, build, widths=None, **kw):
        before = held[id(self)]

        def counted(pod):
            if id(pod) in before:
                survivor_builds[id(self)] = survivor_builds.get(id(self), 0) + 1
            return build(pod)

        return orig_sync(self, fam, token, counted, widths, **kw)

    # What a family token is made of, read after each driver lowering.
    vocab_sizes: list[tuple] = []
    orig_featurize = Featurizer.featurize

    def featurize(self, nodes, pods, **kw):
        out = orig_featurize(self, nodes, pods, **kw)
        sels = self._agg.get("spread_sels", {"list": ()})["list"]
        ip = self._agg.get("ip_vocab")
        vocab_sizes.append((
            id(self), out.resources, len(sels), len(ip.ctxs) if ip else 0,
            tuple((k, v.gen, len(v.items)) for k, v in sorted(self._table._vocabs.items())),
        ))
        return out

    monkeypatch.setattr(PodTable, "index", index)
    monkeypatch.setattr(PodTable, "sync", sync)
    monkeypatch.setattr(Featurizer, "featurize", featurize)
    dev, _sig = _run(_small_ops(), device=True, k=4)
    d = dev.replay_driver
    table = d._featurizer._table
    sizes = [v[1:] for v in vocab_sizes if v[0] == id(d._featurizer)]
    assert len(sizes) == len(d.lower_log) >= 3
    steady = 0
    for i, entry in enumerate(d.lower_log):
        if not entry["cache_hit"]:
            continue
        assert entry["rows_built"] <= entry["events"] + 32
        assert entry["rows_reused"] == entry["universe"] - entry["rows_built"], entry
        if sizes[i] == sizes[i - 1]:
            assert entry["rows_rebuilt"] == 0, entry
            steady += 1
    assert steady >= 2
    # Every builder run on a surviving pod is a counted rebuild.
    assert survivor_builds.get(id(table), 0) == d.stats()["featurize_rebuilt"]
    assert d.stats()["featurize_reused"] == sum(e["rows_reused"] for e in d.lower_log)


def test_mid_stream_fallback_discards_prefix_and_invalidates():
    """An op outside the tensor vocabulary (a patch) forces a per-pass
    fallback mid-stream: the speculative prefix for the shifted window
    is discarded, the cache strictly invalidates, and — because the
    per-pass path is the ground truth being fallen back to — the
    outcomes still match the pure per-pass replay exactly."""
    # An inert node-annotation patch: the per-pass path applies it (no
    # scheduling effect), the device path rejects the step (op:patch).
    # The target must exist at the patch step — replay the node events
    # up to it to pick one that does.
    base = _small_ops()
    live: set[str] = set()
    for op in sorted(base, key=lambda o: o.step):
        if op.step > 8:
            break
        if op.kind == "nodes":
            if op.op == "create":
                live.add(op.obj["metadata"]["name"])
            elif op.op == "delete":
                live.discard(op.name)
    patch = Operation(
        step=8,
        op="patch",
        kind="nodes",
        obj={"metadata": {"annotations": {"oob": "1"}}},
        name=sorted(live)[0],
    )
    ops = base + [patch]
    # K=4 so enough windows run on BOTH sides of the fallback to observe
    # the cache warming, flushing, and warming again.
    _base, sig_base = _run(ops, device=False, k=4)
    dev, sig_dev = _run(ops, device=True, k=4)
    assert sig_dev == sig_base
    d = dev.replay_driver
    assert d.fallback_steps >= 1
    assert d.unsupported.get("op:patch/nodes", 0) >= 1
    cache = d.stats()["lower_cache"]
    assert cache["invalidations"] >= 1
    # The head-rejected window never reaches _take_spec (the pre-span
    # op screen rejects first), so its speculative prefix is discarded
    # by the fallback wrapper; untouched windows still consume theirs.
    assert d.prelower_consumed >= 1
    assert d.prelower_discarded >= 1
    # The cache recovers after the fallback: at least one pre-fallback
    # hit and at least one post-rebuild hit.
    assert cache["hits"] >= 2


def test_unpredicted_window_shift_discards_speculative_prefix():
    """A device error mid-stream shifts the next window by ONE step
    instead of the speculated n_steps: the held prefix can no longer
    match and must be discarded, never consumed against the wrong
    window."""
    # call:1 — the FIRST dispatch fails, while a speculative prefix for
    # the window after it is already held (a later fault could land on
    # the stream tail, where there is nothing left to speculate about).
    FAULTS.arm("replay.dispatch", "call:1")
    ops = _small_ops()
    _base, sig_base = _run(ops, device=False)
    dev, sig_dev = _run(ops, device=True)
    assert sig_dev == sig_base
    d = dev.replay_driver
    assert FAULTS.fired("replay.dispatch") == 1
    assert d.device_errors == 1
    # The prefix speculated during the failed dispatch was discarded
    # (the window it predicted never ran).  No invalidation: the fault
    # hit before the cache ever became valid — invalidate() counts only
    # flushes of real state (the 6k rollback test covers the warm case).
    assert d.prelower_discarded >= 1
    assert d.stats()["lower_cache"]["invalidations"] == 0


class _OutOfBandRunner(ScenarioRunner):
    """Writes an inert object to the store after each committed segment
    — the out-of-band mutation class the epoch counter exists to catch.
    A PriorityClass no pod references cannot change any outcome."""

    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        self._oob = 0

    def _commit_segment(self, *a, **kw):
        out = super()._commit_segment(*a, **kw)
        if out:
            self._oob += 1
            self.store.create(
                "priorityclasses",
                {"metadata": {"name": f"oob-{self._oob}"}, "value": 7},
            )
        return out


def test_out_of_band_store_write_invalidates_cache_small():
    ops = _small_ops()
    _base, sig_base = _run(ops, device=False)
    dev, sig_dev = _run(ops, device=True, runner_cls=_OutOfBandRunner)
    assert sig_dev == sig_base
    cache = dev.replay_driver.stats()["lower_cache"]
    # Every post-commit write moved the epoch, so every subsequent
    # lower rebuilt from the store instead of trusting the cache.
    assert cache["invalidations"] >= 1
    assert cache["hits"] == 0


def test_stale_featurizer_slot_name_survives_lowering():
    """A node deleted on a per-pass step whose scheduling pass has an
    EMPTY queue lingers in the service featurizer's slot map (the
    canonical path skips the sync entirely).  The next lowered window's
    incremental rank seed iterates that map and must SKIP the stale
    name — it has no universe slot — instead of raising KeyError."""
    from tests.helpers import make_node, make_pod

    def ops():
        out = [
            Operation(step=0, op="create", kind="nodes", obj=make_node(f"n{i}"))
            for i in range(3)
        ]
        out.append(Operation(step=0, op="create", kind="pods", obj=make_pod("p0")))
        # Step 1 runs per-pass (the patch is an op-vocabulary head miss)
        # and its pass sees an empty queue (p0 bound at step 0), so the
        # featurizer never syncs away the deleted n2.
        out.append(
            Operation(
                step=1,
                op="patch",
                kind="nodes",
                obj={"metadata": {"annotations": {"x": "1"}}},
                name="n0",
            )
        )
        out.append(Operation(step=1, op="delete", kind="nodes", name="n2"))
        # Step 2 lowers on-device again, with n2 still in the slot map.
        out.append(Operation(step=2, op="create", kind="pods", obj=make_pod("p1")))
        return out

    _base, sig_base = _run(ops(), device=False)
    dev, sig_dev = _run(ops(), device=True)
    assert sig_dev == sig_base
    # Both the pre-patch window and the post-delete window ran on-device
    # (the KeyError class would have crashed the second lowering).
    assert dev.replay_driver.device_steps >= 2


class _SchedReconfigRunner(ScenarioRunner):
    """Swaps the scheduler profile set after the FIRST committed segment
    — the epoch-BLIND out-of-band mutation class: apply_scheduler_config
    writes no store object, so only the cache's sched_names token can
    see that the cached survivors' support screen is stale."""

    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        self._reconfigured = False

    def _commit_segment(self, *a, **kw):
        out = super()._commit_segment(*a, **kw)
        if out and not self._reconfigured:
            self._reconfigured = True
            self.service.apply_scheduler_config(
                {"profiles": [{"schedulerName": "other-sched"}]}, trusted=True
            )
        return out


def test_scheduler_reconfig_invalidates_cache_small():
    """After the swap every pending pod is foreign to the new profile.
    The rebuilt (NOT cached) screen must reject the next window to the
    per-pass path — whose queue skips foreign pods too — so scheduling
    stops at the swap instead of the stale cached universe smuggling
    default-profile pods onto the device."""
    ops = _small_ops()
    _clean, sig_clean = _run(ops, device=True)
    dev = _SchedReconfigRunner(
        max_pods_per_pass=64, device_replay=True, device_segment_steps=8
    )
    res = dev.run(list(ops))
    d = dev.replay_driver
    cache = d.stats()["lower_cache"]
    assert cache["invalidations"] >= 1
    assert d.unsupported.get("foreign_scheduler", 0) >= 1
    assert d.fallback_steps >= 1
    # Strictly fewer binds than the un-reconfigured run: nothing
    # schedules after the first (K=8) segment commits.
    assert res.pods_scheduled < sig_clean[0]
    assert all(s.scheduled == 0 for s in res.steps if s.step >= 8)


def test_prelower_fault_degrades_window_only_small():
    """An armed fault in the SPECULATIVE prefix loses that window's
    overlap and nothing else: no fallback step, no cache flush, same
    outcomes."""
    FAULTS.arm("replay.prelower", "call:1")
    ops = _small_ops()
    _base, sig_base = _run(ops, device=False)
    dev, sig_dev = _run(ops, device=True)
    assert sig_dev == sig_base
    d = dev.replay_driver
    assert FAULTS.fired("replay.prelower") == 1
    assert d.prelower_faults == 1
    assert d.fallback_steps == 0
    assert d.stats()["lower_cache"]["invalidations"] == 0


# ---------------------------------------------------------------------------
# The locked 6k prefix under each invalidation class (slow; make faults)
# ---------------------------------------------------------------------------


def _run_6k(runner_cls=ScenarioRunner):
    runner = runner_cls(
        max_pods_per_pass=1024,
        pod_bucket_min=128,
        device_replay=True,
        device_segment_steps=16,
    )
    res = runner.run(
        churn_scenario(0, n_nodes=2000, n_events=6000, ops_per_step=100)
    )
    return runner, res


def _assert_lock(res, driver):
    assert (res.pods_scheduled, res.unschedulable_attempts) == LOCK
    assert driver.device_steps + driver.fallback_steps == len(res.steps)


@pytest.mark.slow
def test_lock_holds_with_midstream_fallback_invalidation_6k():
    """A mid-stream lowering fault forces one window per-pass: the
    speculative prefix is discarded, the cache flushes and then
    recovers, and the locked counts hold byte-identically."""
    FAULTS.arm("replay.lower", "call:2")
    runner, res = _run_6k()
    d = runner.replay_driver
    _assert_lock(res, d)
    assert FAULTS.fired("replay.lower") == 1
    cache = d.stats()["lower_cache"]
    assert cache["invalidations"] >= 1
    assert cache["hits"] >= 1  # recovered after the fallback


@pytest.mark.slow
def test_lock_holds_with_rollback_invalidation_6k():
    """A mid-reconcile injected fault rolls the segment back
    (ClusterStore.transaction abort): the cache flushes, the head step
    re-runs per-pass, and the locked counts hold.  call:17 = the FIRST
    step of the SECOND segment's reconcile (the site fires per step,
    K=16), so the cache is warm when the rollback flushes it."""
    FAULTS.arm("replay.reconcile", "call:17")
    runner, res = _run_6k()
    d = runner.replay_driver
    _assert_lock(res, d)
    assert FAULTS.fired("replay.reconcile") == 1
    assert d.unsupported.get("reconcile_fault") == 1
    assert d.stats()["lower_cache"]["invalidations"] >= 1
    # The prefix speculated during the rolled-back segment's dispatch
    # predicted a window that never ran: discarded, not consumed.
    assert d.prelower_discarded >= 1


@pytest.mark.slow
def test_lock_holds_with_out_of_band_writes_6k():
    runner, res = _run_6k(runner_cls=_OutOfBandRunner)
    d = runner.replay_driver
    _assert_lock(res, d)
    cache = d.stats()["lower_cache"]
    assert cache["invalidations"] >= 1
    assert cache["hits"] == 0


@pytest.mark.slow
def test_lock_holds_with_prelower_fault_6k():
    """The replay.prelower fault site (faults.SITES): an armed fault in
    the speculative prefix degrades that window's overlap only — every
    step still runs on-device and the locked counts hold."""
    FAULTS.arm("replay.prelower", "call:1")
    runner, res = _run_6k()
    d = runner.replay_driver
    _assert_lock(res, d)
    assert FAULTS.fired("replay.prelower") == 1
    assert d.prelower_faults == 1
    assert d.fallback_steps == 0


# ---------------------------------------------------------------------------
# Round 17: startup AOT prewarm (KSIM_AOT_PREWARM — load-only warm start)
# ---------------------------------------------------------------------------


@pytest.fixture()
def _clean_aot_plane():
    """Process-wide prewarm registry + compile cache, restored after."""
    import ksim_tpu.engine.replay as R
    from ksim_tpu.engine.compilecache import COMPILE_CACHE

    with R._PREWARM_LOCK:
        R._PREWARMED.clear()
    COMPILE_CACHE.reset()
    yield
    with R._PREWARM_LOCK:
        R._PREWARMED.clear()
    COMPILE_CACHE.reset()


def _prewarm_stream():
    from tests.helpers import make_node, make_pod

    for i in range(4):
        yield Operation(
            step=0, op="create", kind="nodes",
            obj=make_node(f"n-{i}", cpu="8", memory="16Gi"),
        )
    for step in (1, 2, 3):
        yield Operation(
            step=step, op="create", kind="pods",
            obj=make_pod(f"p-{step}", cpu="500m", memory="512Mi"),
        )


def test_aot_prewarm_serves_without_deserializing(
    tmp_path, monkeypatch, _clean_aot_plane
):
    """The startup pass (prewarm_aot_cache) deserializes every on-disk
    rung ONCE; the first tenant dispatch of each rung is then served
    from the prewarm registry.  Proof: with jax.export.deserialize
    broken after the prewarm, a cold-cache run still lands every disk
    load as a hit with ZERO evictions — the dispatch path never needed
    the deserializer."""
    import os

    import ksim_tpu.engine.replay as R
    from ksim_tpu.engine.compilecache import COMPILE_CACHE

    monkeypatch.setenv("KSIM_AOT_CACHE", str(tmp_path))
    runner = ScenarioRunner(device_replay=True, device_segment_steps=4)
    runner.run(_prewarm_stream())
    assert runner.replay_driver.device_steps >= 1
    stored = [f for f in os.listdir(tmp_path) if f.endswith(".aot")]
    assert stored, "seeding run persisted no AOT entries"
    assert COMPILE_CACHE.snapshot()["disk_stores"] >= 1

    # "Restarted server": cold in-memory cache, same disk.
    COMPILE_CACHE.reset()
    n = R.prewarm_aot_cache()
    assert n == len(stored)
    snap = COMPILE_CACHE.snapshot()
    assert snap["disk_prewarmed"] == n
    with R._PREWARM_LOCK:
        assert len(R._PREWARMED) == n

    def boom(_blob):
        raise AssertionError("dispatch path deserialized despite prewarm")

    monkeypatch.setattr("jax.export.deserialize", boom)
    runner2 = ScenarioRunner(device_replay=True, device_segment_steps=4)
    runner2.run(_prewarm_stream())
    assert runner2.replay_driver.device_steps >= 1
    snap2 = COMPILE_CACHE.snapshot()
    assert snap2["disk_hits"] >= 1
    assert snap2["disk_evictions"] == 0, snap2


def test_aot_prewarm_skips_foreign_entries_without_evicting(
    tmp_path, monkeypatch, _clean_aot_plane
):
    """Load-only means load-only: a foreign-version token, a corrupt
    blob and a garbage header are all SKIPPED — counted nowhere,
    deleted never (eviction authority stays with the dispatch path's
    token check)."""
    import json
    import os
    import zlib

    import ksim_tpu.engine.replay as R
    from ksim_tpu.engine.compilecache import COMPILE_CACHE

    monkeypatch.setenv("KSIM_AOT_CACHE", str(tmp_path))
    blob = b"not-an-executable"

    def entry(token, payload, crc=None):
        header = json.dumps(
            {"v": 1, "key": token, "crc": crc if crc is not None else (zlib.crc32(payload) & 0xFFFFFFFF)}
        ).encode()
        return header + b"\n" + payload

    foreign = f"jax-9.9.9|cpu|d{jax.device_count()}|rest"
    native_prefix = f"{jax.__version__}|{jax.default_backend()}|d{jax.device_count()}|rest"
    (tmp_path / "foreign.aot").write_bytes(entry(foreign, blob))
    # Native prefix but the blob is not a serialized executable: the
    # deserialize attempt fails and the entry is skipped in place.
    (tmp_path / "undeser.aot").write_bytes(entry(native_prefix, blob))
    (tmp_path / "corrupt.aot").write_bytes(entry(native_prefix, blob, crc=1))
    (tmp_path / "garbage.aot").write_bytes(b"\x00 no header here")

    assert R.prewarm_aot_cache() == 0
    assert COMPILE_CACHE.snapshot()["disk_prewarmed"] == 0
    assert COMPILE_CACHE.snapshot()["disk_evictions"] == 0
    with R._PREWARM_LOCK:
        assert not R._PREWARMED
    assert sorted(os.listdir(tmp_path)) == [
        "corrupt.aot", "foreign.aot", "garbage.aot", "undeser.aot",
    ]


def test_aot_speculative_rescan_picks_up_new_entries(
    tmp_path, monkeypatch, _clean_aot_plane
):
    """Round 20 (fleet prewarm): a speculative pass re-reads the shared
    disk plane and warms ONLY entries it has never seen — the mechanism
    that turns one fleet worker's compile into every peer's warm start.
    Counted under disk_speculative (not disk_prewarmed), idempotent
    when nothing new landed, and the background rescan loop drives the
    same pass on its interval."""
    import os
    import shutil
    import threading
    import time

    import ksim_tpu.engine.replay as R
    from ksim_tpu.engine.compilecache import COMPILE_CACHE

    monkeypatch.setenv("KSIM_AOT_CACHE", str(tmp_path))
    runner = ScenarioRunner(device_replay=True, device_segment_steps=4)
    runner.run(_prewarm_stream())
    stored = sorted(f for f in os.listdir(tmp_path) if f.endswith(".aot"))
    assert stored, "seeding run persisted no AOT entries"

    COMPILE_CACHE.reset()
    n = R.prewarm_aot_cache()
    assert n == len(stored)
    base = COMPILE_CACHE.snapshot()
    assert base["disk_prewarmed"] == n
    assert base["disk_speculative"] == 0

    # A peer worker lands a new entry in the shared plane (stand-in: a
    # copy of an existing entry under a fresh name — the registry is
    # keyed by path, so this is "a file we have never deserialized").
    shutil.copyfile(tmp_path / stored[0], tmp_path / "peer-0.aot")
    assert R.prewarm_aot_cache(speculative=True) == 1
    snap = COMPILE_CACHE.snapshot()
    assert snap["disk_speculative"] == 1
    assert snap["disk_prewarmed"] == n  # startup counter untouched
    # Nothing new on disk: the speculative pass is a no-op, not a
    # re-count.
    assert R.prewarm_aot_cache(speculative=True) == 0
    assert COMPILE_CACHE.snapshot()["disk_speculative"] == 1

    # The background loop: a full startup pass, then speculative
    # rescans on the interval.  Wait for the startup pass (it bumps
    # disk_prewarmed), THEN land a new peer entry and watch the rescan
    # pick it up as speculative.
    prewarmed_before = COMPILE_CACHE.snapshot()["disk_prewarmed"]
    stop = threading.Event()
    t = threading.Thread(
        target=R.prewarm_rescan_loop,
        kwargs={"stop": stop, "interval_s": 0.05},
        daemon=True,
    )
    t.start()
    try:
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline:
            if COMPILE_CACHE.snapshot()["disk_prewarmed"] > prewarmed_before:
                break
            time.sleep(0.02)
        shutil.copyfile(tmp_path / stored[0], tmp_path / "peer-1.aot")
        while time.monotonic() < deadline:
            if COMPILE_CACHE.snapshot()["disk_speculative"] >= 2:
                break
            time.sleep(0.02)
    finally:
        stop.set()
        t.join(timeout=5)
    assert not t.is_alive()
    assert COMPILE_CACHE.snapshot()["disk_speculative"] == 2
