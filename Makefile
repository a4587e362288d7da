# Developer entrypoints (the reference's Makefile analogue).

PY ?= python

.PHONY: test test-tpu chip-smoke-rehearsal serve lint lock-check faults trace jobs restart-check shard-check mesh-check obs-check stream-check

test:
	$(PY) -m pytest tests/ -q --deselect tests/test_tpu_parity.py

# One-command behavior-lock verification: the FULL 50k churn stream
# through both the per-pass and device-resident paths, asserting the
# 52781/42829 counts stepwise (repo CLAUDE.md) — with the incremental
# lower-cache + double-buffered prelower fully ON (round 10), plus the
# counter-based O(delta) guard (steady-state featurize rows scale with
# window events, not universe size) — and the FLEET parity lock (round
# 12): 8 lanes x 6k events through the vmapped fleet path, every lane
# byte-identical to 2524/471 with the shared universe lowered once per
# window (counter-based guard).  ~15-25 min on CPU.  Round 15 adds the
# CHAOS leg: the locked 6k prefix with injected device-dispatch faults
# mid-stream — the breaker trips, the half-open probe recovers the
# device path, and the 2524/471 counts still hold byte-identically.
# The analyzer gates the lock run: a lock/kernel/registry contract
# violation is exactly the class of bug the 50k stepwise run exists to
# catch, and lint finds it in seconds instead of minutes.  Round 17
# adds the SHARDED legs: the locked 6k prefix and the full 50k stream
# replayed over a tp=8 virtual mesh (8 host devices), every step
# byte-identical to the solo counts with zero shard_mesh fallbacks.
lock-check: lint
	$(PY) -m pytest tests/test_behavior_locks.py::test_churn_lock_50k_stepwise_device_vs_per_pass tests/test_behavior_locks.py::test_churn_fleet_lock_6k_lanes8 tests/test_behavior_locks.py::test_churn_lock_6k_holds_under_dispatch_faults_with_recovery tests/test_behavior_locks.py::test_churn_lock_6k_sharded_tp8 tests/test_behavior_locks.py::test_churn_lock_50k_stepwise_sharded_tp8 -q -rs -m slow

# Sharded-replay verification (docs/scaling.md "Sharded device
# replay"): the fast tier-1 sharded-vs-solo parity matrix (byte parity
# on churn + full-record annotations + preemption, the explicit-mesh
# contract with the per-shard byte budget, dead-device containment of
# a mesh wider than the host and of a fault-armed dispatch at tp 1 and
# tp 8, the prewarm plane) plus the slow 6k sharded lock leg.  Gated on
# lint for the same reason lock-check is.
shard-check: lint
	$(PY) -m pytest tests/test_replay_device.py tests/test_replay_cache.py -q -k "sharded or prewarm"
	$(PY) -m pytest "tests/test_replay_faults.py::test_dead_device_is_carried_by_host_path[shard]" -q
	$(PY) -m pytest tests/test_behavior_locks.py::test_churn_lock_6k_sharded_tp8 -q -rs -m slow

# The 2-D mesh suite (round 19, docs/scaling.md "2-D mesh"): the tp x dp
# fleet parity test with its mesh evidence (lanes match solo, the
# (2, 4) grid, per-shard bytes) + the
# donated-carry byte-identity test, the mesh fleet under a dead device,
# and the slow tp=4 x dp=2 6k fleet lock leg — every lane 2524/471
# stepwise against the solo unsharded run.  Gated on lint like
# shard-check.
mesh-check: lint
	$(PY) -m pytest tests/test_replay_device.py -q -k "tp_dp or donation"
	$(PY) -m pytest "tests/test_replay_faults.py::test_dead_device_is_carried_by_host_path[fleet_mesh]" -q
	$(PY) -m pytest tests/test_behavior_locks.py::test_churn_fleet_lock_6k_tp4_dp2 -q -rs -m slow

# The fault suite (docs/faults.md) pinned to the CPU backend
# (tests/helpers.sanitized_cpu_env) — runnable under ANY hardware state.
# -m '' overrides pyproject's default -m 'not slow' so the slow-marked
# 6k fault schedules run here too (the full five-schedule matrix).
# KSIM_STORE_STRICT=1: the sanitizer-lite store mode (docs/env.md) is
# on for the whole fault matrix — an injected fault whose containment
# path touched the store without the lock would fail loudly here.
faults:
	$(PY) -c "import subprocess, sys; from tests.helpers import sanitized_cpu_env; \
	sys.exit(subprocess.call([sys.executable, '-m', 'pytest', \
	'tests/test_replay_faults.py', 'tests/test_fault_injection.py', \
	'tests/test_replay_cache.py', 'tests/test_jobs.py', \
	'tests/test_jobs_durability.py', \
	'-q', '-m', ''], env=sanitized_cpu_env({'KSIM_STORE_STRICT': '1'})))"

# The job-plane suite (docs/jobs.md) on CPU in the sanitized env, slow
# tests included (-m '' overrides the default 'not slow'): lifecycle
# over HTTP, queue backpressure, cancel-mid-segment rollback, SSE
# progress, the shared compile cache, and the per-tenant fault
# containment matrix (KSIM_JOBS_FAULTS).
jobs:
	$(PY) -c "import subprocess, sys; from tests.helpers import sanitized_cpu_env; \
	sys.exit(subprocess.call([sys.executable, '-m', 'pytest', \
	'tests/test_jobs.py', '-q', '-m', ''], env=sanitized_cpu_env()))"

# Crash-recovery verification (docs/jobs.md "Durability & recovery"):
# the journal/AOT-cache unit matrix (torn tails, corrupt CRCs, corrupt
# serialized executables — all hand-written bad bytes), manager replay
# on restart, the SSE aborted-reader leak regression, the round-16
# incremental-resume matrix (crash after EVERY checkpoint boundary,
# torn/corrupt checkpoint fallback, append-fault containment, gap-free
# recovered SSE backlogs), the round-20 fleet matrix (lease claim
# races, takeover epochs, release tombstones, shared-journal
# interleaved appenders + cross-process compaction), and the slow
# SIGKILL end-to-ends — interrupted-marking, checkpoint-resume, and
# the kill-a-worker fleet fail-over, all on the locked 6k stream
# (-m '' includes them).  Runs in the sanitized CPU env so it works
# under ANY hardware condition.
restart-check: lint
	$(PY) -c "import subprocess, sys; from tests.helpers import sanitized_cpu_env; \
	sys.exit(subprocess.call([sys.executable, '-m', 'pytest', \
	'tests/test_jobs_durability.py', '-q', '-m', ''], env=sanitized_cpu_env()))"

# Trace-plane validation (docs/observability.md): the locked 6k prefix
# through the device path with KSIM_TRACE_OUT set, in the sanitized CPU
# env — asserts the counts hold under tracing and the emitted Chrome
# trace parses with every expected phase span, then an armed-fault run
# asserting the fault/fallback timeline events, and (run 5) a 2-worker
# fleet leg whose SIGTERM-published trace exports must merge into one
# Chrome trace with a lane per worker and a complete submit->claim->run
# flow triple per job.  Stdlib-only parent.
trace:
	$(PY) tools/trace_check.py

# Fleet observability verification (docs/observability.md "Fleet
# observability"): the histogram bucket-merge property test, the
# Prometheus exposition golden + round-trip parser tests, crash-atomic
# publish, staleness flagging, the merged-trace lane/flow tests, and
# the slow 2-process fleet scrape end-to-end (-m '' includes it).
# Sanitized CPU env, so it runs under ANY hardware condition; gated on
# lint because METRIC_NAMES/registry drift is exactly what the
# analyzer catches in seconds.
obs-check: lint
	$(PY) -c "import subprocess, sys; from tests.helpers import sanitized_cpu_env; \
	sys.exit(subprocess.call([sys.executable, '-m', 'pytest', \
	'tests/test_obs_fleet.py', '-q', '-m', ''], env=sanitized_cpu_env()))"

# Streaming-ingest verification (docs/scenario.md "Streaming ingest"):
# the windowed-vs-materialized byte-identity suite (selector == batch
# resample on shuffled input, window-boundary splits, producer-fault
# degradation, mid-read bound refusal, the streamed device replay
# against the materialized one with its window / prefetch evidence),
# the streaming behavior-lock leg (borg_mini through tiny windows on
# both paths), and the streamed replay under a dead device.  Sanitized
# CPU env so it runs under ANY
# hardware condition; gated on lint because the trace-ingest
# thread-role and the traces.stream span/site registrations are
# exactly what the analyzer checks.
stream-check: lint
	$(PY) -c "import subprocess, sys; from tests.helpers import sanitized_cpu_env; \
	sys.exit(subprocess.call([sys.executable, '-m', 'pytest', \
	'tests/test_traces_stream.py', \
	'tests/test_behavior_locks.py::test_trace_lock_borg_mini_holds_with_streaming_ingest', \
	'tests/test_replay_faults.py::test_dead_device_is_carried_by_host_path[stream]', \
	'-q'], env=sanitized_cpu_env()))"

test-tpu:
	$(PY) -m pytest tests/test_tpu_parity.py -q -rs

# chip_smoke.py's two phases at a tiny size with the server pinned to
# the CPU: the rehearsal to run before spending chip time on
# `python chip_smoke.py --seed 0` (which fails off the chip by design).
chip-smoke-rehearsal:
	$(PY) chip_smoke.py --rehearsal --seed 0

serve:
	$(PY) -m ksim_tpu.cmd.simulator

# Static contract analysis (docs/lint.md): compile the tree, then run
# the AST analyzer over ksim_tpu/, chip_smoke.py and tools/ — exits nonzero
# on any unsuppressed finding.  tools/ksimlint is stdlib-only (it never
# imports jax, numpy or ksim_tpu), so this is safe under ANY hardware
# condition, including a backend whose init hangs — no CPU pin needed.
lint:
	$(PY) -m compileall -q ksim_tpu tools chip_smoke.py
	$(PY) -m tools.ksimlint
