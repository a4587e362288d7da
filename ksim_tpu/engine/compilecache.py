"""Process-wide compiled-executable cache evidence + compile-once gate.

jax's jit cache already reuses a compiled executable for identical
(statics, input avals) within one process — but it is silent (no
hit/miss evidence reaches a job result or /api/v1/metrics) and it
does not serialize FIRST calls: two tenant jobs hitting the same shape
rung concurrently can both pay the multi-second XLA trace+compile
before either lands in the cache.  This module adds the missing layer
for the job plane (ksim_tpu/jobs): a process-global registry keyed by
the bucketed shape ladder + profile token that

- counts ``hits``/``misses`` per rung (a miss = the first dispatch of a
  key, i.e. the one that compiles) and records which OWNERS (tenant
  jobs, via the scoped trace plane's ``job`` tag) used each rung — the
  "compile once, serve every tenant on that rung" claim becomes
  machine-checkable straight from a job result
  (``shared_rungs``/``shared_single_compile_rungs``);
- serializes the first call per key: one leader runs the compiling
  dispatch, concurrent same-rung callers WAIT (bounded) for it, then
  dispatch against jax's now-warm jit cache.  A leader that dies
  removes its entry (``aborts``) so the next caller retries as leader
  rather than deadlocking behind a tombstone.

Round 15 adds the ON-DISK layer (ISSUE 11 "persistent executables"):
``run`` takes an optional ``disk`` spec — a duck-typed handle the
CALLER builds (engine/replay.py ``_aot_disk_spec``) carrying the entry
``path``, a stable identity ``token`` (shape-ladder rung + profile
token + jaxlib version + backend), and ``load``/``invoke``/
``serialize`` callables.  A leader first tries load-from-disk (a
deserialized ``jax.export`` executable skips XLA compilation
entirely); corrupt, version-mismatched or un-invokable entries are
unlinked and counted with a ``compilecache.evict`` trace event, then
the leader falls back to compiling and best-effort persists the fresh
executable (atomic tmp+rename).  Followers reuse the leader's
deserialized executable — after a disk hit jax's jit cache was never
warmed, so dispatching ``fn`` again would re-pay the compile the disk
hit just skipped.

The module stays stdlib-only (json/os/zlib): all jax calls live inside
the caller's ``disk`` callables, so nothing here ever imports jax.
"""

from __future__ import annotations

import json
import logging
import os
import threading
import zlib
from typing import Any, Callable

from ksim_tpu.obs import TRACE, register_provider

logger = logging.getLogger(__name__)

__all__ = ["CompileCache", "COMPILE_CACHE"]

#: Bound on the follower wait for a leader's in-flight compile.  The
#: replay watchdog (KSIM_REPLAY_WATCHDOG_S, default 300 s — "generous:
#: first dispatch includes XLA compile") covers the same window from
#: the dispatch side, so a stuck leader degrades through the existing
#: device_error ladder instead of wedging followers forever.
_WAIT_DEFAULT_S = 300.0


class _Entry:
    """One shape rung's state: the leader-compiled gate + per-key
    evidence.  Mutated only under the owning cache's lock (the ready
    Event is the one cross-thread signal and is safe bare)."""

    __slots__ = ("ready", "hits", "owners", "exec_obj")

    def __init__(self) -> None:
        self.ready = threading.Event()
        self.hits = 0
        self.owners: set = set()
        # The leader's disk-loaded executable (None when the leader
        # compiled via fn — jax's jit cache is warm then and followers
        # dispatch fn directly).
        self.exec_obj: Any = None


class CompileCache:
    """Counting, compile-once-serializing front of the jit cache."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._entries: dict[Any, _Entry] = {}  # guarded-by: _lock
        self.hits = 0  # guarded-by: _lock
        self.misses = 0  # guarded-by: _lock
        self.waits = 0  # guarded-by: _lock (followers that blocked on a leader)
        self.aborts = 0  # guarded-by: _lock (leader dispatches that raised)
        self.disk_hits = 0  # guarded-by: _lock (leaders warm-started from disk)
        self.disk_misses = 0  # guarded-by: _lock (leaders that found no entry)
        self.disk_stores = 0  # guarded-by: _lock (fresh executables persisted)
        self.disk_evictions = 0  # guarded-by: _lock (corrupt/mismatched unlinks)
        self.disk_prewarmed = 0  # guarded-by: _lock (startup-deserialized entries)
        self.disk_speculative = 0  # guarded-by: _lock (rescan-loaded peers' entries)

    def run(
        self,
        key: Any,
        fn: Callable[[], Any],
        *,
        owner: "str | None" = None,
        wait_s: float = _WAIT_DEFAULT_S,
        disk: Any = None,
    ) -> Any:
        """Run ``fn`` (the jitted dispatch) under the compile-once gate.

        The first caller of ``key`` is the LEADER: it counts a miss and
        runs ``fn`` directly — jax traces+compiles, then caches.  Every
        later caller counts a hit; if the leader's first call is still
        in flight it waits (up to ``wait_s``) before dispatching, so a
        rung is compiled once no matter how many tenants race onto it.
        A leader that raises removes the entry and re-raises — the next
        caller becomes the new leader (counted in ``aborts``).

        ``disk`` (optional) is the persistent layer's handle for this
        key: ``.path`` (entry file), ``.token`` (the stable identity
        string the header must match), ``.load(blob) -> exec_obj``,
        ``.invoke(exec_obj) -> result`` and ``.serialize() -> bytes |
        None``.  A leader tries disk first (warm restart: no compile);
        any corruption, token mismatch, failed deserialize or failed
        invoke evicts the entry (``compilecache.evict``) and degrades
        to the compile path, after which the fresh executable is
        persisted best-effort.  Followers behind a disk-hit leader
        reuse its deserialized executable — ``fn`` would re-compile,
        jax's jit cache was never warmed on that path."""
        with self._lock:
            ent = self._entries.get(key)
            if ent is None:
                ent = self._entries[key] = _Entry()
                if owner is not None:
                    ent.owners.add(owner)
                self.misses += 1
                leader = True
            else:
                ent.hits += 1
                if owner is not None:
                    ent.owners.add(owner)
                self.hits += 1
                leader = False
            ready = ent.ready
        if leader:
            if disk is not None:
                exec_obj = self._disk_load(disk)
                if exec_obj is not None:
                    try:
                        out = disk.invoke(exec_obj)
                    except Exception:
                        # Deserialized fine but will not run (e.g. a
                        # platform the blob was not exported for):
                        # evict and fall through to the compile path.
                        self._evict(disk, "exec_failed")
                    else:
                        with self._lock:
                            ent.exec_obj = exec_obj
                        ready.set()
                        return out
            try:
                out = fn()
            except BaseException:
                with self._lock:
                    self.aborts += 1
                    self._entries.pop(key, None)
                # Wake any followers parked on this generation; they
                # dispatch themselves (jax may still have cached a
                # partial trace — correctness is jax's, we only lose
                # one dedupe opportunity).
                ready.set()
                raise
            ready.set()
            if disk is not None:
                self._disk_store(disk)
            return out
        if not ready.is_set():
            with self._lock:
                self.waits += 1
            ready.wait(wait_s)
        if disk is not None:
            with self._lock:
                live = self._entries.get(key)
                exec_obj = live.exec_obj if live is not None else None
            if exec_obj is not None:
                return disk.invoke(exec_obj)
        return fn()

    def note_prewarmed(self, n: int) -> None:
        """Count ``n`` entries deserialized by the startup prewarm pass
        (engine/replay.py ``prewarm_aot_cache``, ``KSIM_AOT_PREWARM``)
        — evidence only; the entries themselves live with the caller."""
        with self._lock:
            self.disk_prewarmed += n

    def note_speculative(self, n: int) -> None:
        """Count ``n`` entries the ``KSIM_AOT_PREWARM=2`` rescan loop
        loaded AFTER startup — executables another fleet worker stored
        (possibly for rungs this process never dispatched), now warm
        here too.  Same evidence-only contract as ``note_prewarmed``."""
        with self._lock:
            self.disk_speculative += n

    @staticmethod
    def read_disk_entry(path: str) -> "tuple[str, bytes] | None":
        """Non-destructively parse one on-disk entry: validate the
        header shape and blob CRC, return ``(stored token, blob)`` —
        or None for unreadable/corrupt files.  Unlike ``_disk_load``
        this NEVER evicts and does no token comparison: it serves scans
        (the prewarm pass) that do not know which rung identity the
        entry belongs to; eviction authority stays with the dispatch
        path, where the expected token is known."""
        try:
            with open(path, "rb") as f:
                header, sep, blob = f.read().partition(b"\n")
        except OSError:
            return None
        try:
            meta = json.loads(header)
            crc = int(meta.get("crc", -1))
            token = meta.get("key")
            ok_shape = bool(sep) and meta.get("v") == 1
        except (ValueError, TypeError):
            return None
        if (
            not ok_shape
            or not isinstance(token, str)
            or (zlib.crc32(blob) & 0xFFFFFFFF) != crc
        ):
            return None
        return token, blob

    # -- the persistent layer (leader-only helpers) ----------------------

    def _disk_load(self, disk: Any) -> Any:
        """entry file -> deserialized executable, or None (miss /
        evicted).  Validates the one-line JSON header (version, the
        caller's identity token, blob CRC) before handing bytes to
        ``disk.load`` — a stale jaxlib or a hash-colliding path must
        never reach the deserializer."""
        try:
            with open(disk.path, "rb") as f:
                header, sep, blob = f.read().partition(b"\n")
        except OSError:
            with self._lock:
                self.disk_misses += 1
            return None
        try:
            meta = json.loads(header)
            crc = int(meta.get("crc", -1))
            ok_shape = bool(sep) and meta.get("v") == 1
        except (ValueError, TypeError):
            self._evict(disk, "corrupt")
            return None
        if not ok_shape or (zlib.crc32(blob) & 0xFFFFFFFF) != crc:
            self._evict(disk, "corrupt")
            return None
        if meta.get("key") != disk.token:
            self._evict(disk, "key_mismatch")
            return None
        try:
            exec_obj = disk.load(blob)
        except Exception:
            self._evict(disk, "deserialize_failed")
            return None
        with self._lock:
            self.disk_hits += 1
        return exec_obj

    def _disk_store(self, disk: Any) -> None:
        """Best-effort persist of the leader's fresh executable —
        serialization or I/O failure costs only the NEXT process's
        warm start, never this dispatch."""
        try:
            blob = disk.serialize()
            if blob is None:
                return  # the caller deemed this plan non-exportable
            header = json.dumps({
                "v": 1, "key": disk.token,
                "crc": zlib.crc32(blob) & 0xFFFFFFFF,
            }).encode()
            os.makedirs(os.path.dirname(disk.path) or ".", exist_ok=True)
            tmp = f"{disk.path}.tmp{os.getpid()}"
            with open(tmp, "wb") as f:
                f.write(header + b"\n" + blob)
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, disk.path)
        except Exception:
            logger.debug("compile cache: could not persist %s", disk.path,
                         exc_info=True)
            return
        with self._lock:
            self.disk_stores += 1

    def _evict(self, disk: Any, reason: str) -> None:
        """Unlink an unusable entry and count it — the evidence trail
        behind the "discarded gracefully" contract."""
        try:
            os.unlink(disk.path)
        except OSError:
            pass
        with self._lock:
            self.disk_evictions += 1
        TRACE.event("compilecache.evict", reason=reason, path=disk.path)

    def snapshot(self) -> dict:
        """JSON-ready evidence (the ``compile_cache`` section of
        /api/v1/metrics and of a job result): aggregate counters plus
        the cross-tenant sharing proof — ``shared_rungs`` = keys used
        by >= 2 distinct owners, ``shared_single_compile_rungs`` = the
        subset that also compiled exactly once (present entries never
        re-miss; an aborted leader removes its key, so every LIVE
        entry's compile count is exactly 1)."""
        with self._lock:
            rungs = len(self._entries)
            shared = sum(1 for e in self._entries.values() if len(e.owners) >= 2)
            shared_hot = sum(
                1
                for e in self._entries.values()
                if len(e.owners) >= 2 and e.hits > 0
            )
            max_owners = max(
                (len(e.owners) for e in self._entries.values()), default=0
            )
            return {
                "hits": self.hits,
                "misses": self.misses,
                "waits": self.waits,
                "aborts": self.aborts,
                "disk_hits": self.disk_hits,
                "disk_misses": self.disk_misses,
                "disk_stores": self.disk_stores,
                "disk_evictions": self.disk_evictions,
                "disk_prewarmed": self.disk_prewarmed,
                "disk_speculative": self.disk_speculative,
                "rungs": rungs,
                "shared_rungs": shared,
                "shared_single_compile_rungs": shared_hot,
                "max_owners_per_rung": max_owners,
            }

    def reset(self) -> None:
        """Drop entries and counters (tests; production never calls
        this)."""
        with self._lock:
            self._entries.clear()
            self.hits = 0
            self.misses = 0
            self.waits = 0
            self.aborts = 0
            self.disk_hits = 0
            self.disk_misses = 0
            self.disk_stores = 0
            self.disk_evictions = 0
            self.disk_prewarmed = 0
            self.disk_speculative = 0


#: The process-wide cache every segment dispatch consults — one compile
#: per shape rung regardless of how many runners/tenants share the
#: process.  engine/replay.py owns the key construction.
COMPILE_CACHE = CompileCache()

# Self-register as a /api/v1/metrics evidence provider: any process
# that imports this module (the replay executor, the HTTP server)
# serves the rung counters live.  obs is stdlib-only like this module,
# and never imports back — no cycle.
register_provider("compile_cache", COMPILE_CACHE.snapshot)
