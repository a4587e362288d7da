"""Small utilities (the reference's simulator/util package analogue)."""

from __future__ import annotations

import logging
import os
import threading
import time
from typing import Callable, TypeVar

from ksim_tpu.obs import (
    LatencyHistogram,
    device_identity,
    note_device,
    note_xla_cache_load,
    note_xla_compile,
)

logger = logging.getLogger(__name__)

T = TypeVar("T")

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def enable_compilation_cache() -> None:
    """Turn on JAX's persistent (on-disk) compilation cache.

    XLA compiles of the scheduling scan at large shapes cost seconds to
    tens of seconds each; the disk cache makes them one-time per machine
    instead of per process.  Called by the product entrypoints
    (simulator/scheduler CLIs, fleet workers) — NOT on library
    import, so embedding applications keep control of jax.config.

    Placement has one knob, JAX's own: with ``JAX_COMPILATION_CACHE_DIR``
    set, JAX reads it itself and this function sets no directory.
    Otherwise the cache lives at a FIXED path inside the checkout,
    ``<repo>/.jax_cache/<host fingerprint>`` (the path is part of the
    cache key, so a directory that moves never hits).

    The leaf is fingerprinted by the HOST CPU's feature set: XLA:CPU
    caches AOT-compiled code, and an artifact produced on a machine with
    different vector extensions can SIGILL when loaded on this one
    (cpu_aot_loader warns exactly that).  It is a function of the
    machine, never of the run."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        cache_dir = os.path.join(_REPO_ROOT, ".jax_cache", _host_fingerprint())
        try:
            os.makedirs(cache_dir, exist_ok=True)
        except OSError as e:
            # Read-only checkout (containers): run without the cache,
            # but say so — every start then pays every compile.
            logger.warning(
                "persistent compile cache disabled: cannot create %s (%s); "
                "set JAX_COMPILATION_CACHE_DIR to a writable directory",
                cache_dir, e,
            )
            return
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    watch_xla_compiles()


_xla_watch_lock = threading.Lock()
_xla_watched = False  # guarded-by: _xla_watch_lock
_xla_tls = threading.local()


def watch_xla_compiles() -> None:
    """Register (once) the ``jax.monitoring`` listeners behind
    ``obs.note_xla_compile`` / ``note_xla_cache_load``.  Called where jax
    is already in the process — the product entry points' cache set-up
    above and ``note_backend`` — never from ``obs``, which stays
    stdlib-only.  Listeners run on the compiling thread: JAX reports a
    persistent-cache hit as an event and THEN the (short) backend-compile
    duration of the same request, so the hit marks the thread and the
    duration that follows counts as a load, every other as a compile."""
    global _xla_watched
    with _xla_watch_lock:
        if _xla_watched:
            return
        _xla_watched = True
    from jax import monitoring

    def on_event(event: str, **kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            _xla_tls.cache_hit = True

    def on_duration(event: str, seconds: float, **kw) -> None:
        if event != "/jax/core/compile/backend_compile_duration":
            return
        if getattr(_xla_tls, "cache_hit", False):
            _xla_tls.cache_hit = False
            note_xla_cache_load()
        else:
            note_xla_compile(seconds)

    monitoring.register_event_listener(on_event)
    monitoring.register_event_duration_secs_listener(on_duration)


def note_backend() -> None:
    """Publish the JAX backend this process computes on into the
    process identity (``obs.process_identity``, ``ReplayDriver.stats``)
    and log it once.  Call only right after a dispatch completed: the
    backend exists by then, so ``jax.devices()`` is a lookup — never a
    backend init on the calling thread."""
    if device_identity()["platform"] is not None:
        return
    import jax

    watch_xla_compiles()

    devices = jax.devices()
    d0 = devices[0]
    if note_device(d0.platform, d0.device_kind, len(devices)):
        logger.info(
            "compute backend: platform=%s device_kind=%s device_count=%d",
            d0.platform, d0.device_kind, len(devices),
        )


def raise_map_count_limit(target: int = 1_000_000) -> None:
    """Best-effort raise of vm.max_map_count: every XLA:CPU executable
    mmaps code pages, and a long single process (the full test suite, a
    50k-event churn replay) can hit the kernel's 65530 default —
    observed as SIGSEGV/SIGABRT inside LLVM at ~63k maps (round 4).
    No-op without root/procfs."""
    try:
        with open("/proc/sys/vm/max_map_count") as f:
            if int(f.read()) >= target:
                return
        with open("/proc/sys/vm/max_map_count", "w") as f:
            f.write(str(target))
    except (OSError, ValueError):
        pass


def _host_fingerprint() -> str:
    """Short stable hash of this host's CPU feature flags (falls back to
    the platform string when /proc/cpuinfo is unavailable)."""
    import hashlib
    import platform

    flags = ""
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                if line.startswith(("flags", "Features")):
                    flags = " ".join(sorted(line.split(":", 1)[1].split()))
                    break
    except OSError:
        pass
    basis = flags or platform.processor() or platform.machine() or "unknown"
    return "host-" + hashlib.sha256(basis.encode()).hexdigest()[:12]


def retry_with_exponential_backoff(
    fn: Callable[[], T],
    *,
    initial: float = 0.1,
    factor: float = 2.0,
    steps: int = 6,
    retriable: tuple[type[BaseException], ...] = (Exception,),
) -> T:
    """Run ``fn`` until it succeeds, backing off exponentially — the
    reference's RetryWithExponentialBackOff (util/retry.go:9-26: 100ms
    initial, 6 steps).  Raises the last error when steps are exhausted."""
    delay = initial
    for attempt in range(steps):
        try:
            return fn()
        except retriable:
            if attempt == steps - 1:
                raise
            time.sleep(delay)
            delay *= factor
    raise AssertionError("unreachable")


class Metrics:
    """Thread-safe counters + latency histograms.

    The reference's observability is the upstream scheduler's Prometheus
    metrics plus klog (SURVEY section 5); this is the in-process
    analogue, exposed as JSON at /api/v1/metrics.  Timers record into
    fixed-bucket log-spaced histograms (ksim_tpu.obs.LatencyHistogram)
    — the former mean-only [total, count] pairs hid multimodal
    latencies (a 5 s cold XLA compile averaged into thousands of 10 ms
    warm passes reads as "15 ms mean"); the snapshot keeps the legacy
    total/count/mean keys and adds buckets + estimated quantiles."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._counters: dict[str, int] = {}
        self._timers: dict[str, LatencyHistogram] = {}

    def inc(self, name: str, value: int = 1) -> None:
        with self._lock:
            self._counters[name] = self._counters.get(name, 0) + value

    def observe(self, name: str, seconds: float) -> None:
        with self._lock:
            hist = self._timers.get(name)
            if hist is None:
                hist = self._timers[name] = LatencyHistogram()
            hist.observe(seconds)

    class _Timer:
        def __init__(self, metrics: "Metrics", name: str) -> None:
            self._m, self._name = metrics, name

        def __enter__(self):
            self._t0 = time.perf_counter()
            return self

        def __exit__(self, *exc):
            self._m.observe(self._name, time.perf_counter() - self._t0)
            return False

        def set(self, **args) -> None:
            """Span attributes: dropped — this is ``TRACE.phase`` with
            the plane off, where only the timer runs."""

    def timer(self, name: str) -> "_Timer":
        return self._Timer(self, name)

    def snapshot(self) -> dict:
        with self._lock:
            return {
                "counters": dict(self._counters),
                "timings": {
                    name: hist.snapshot() for name, hist in self._timers.items()
                },
            }
