"""Per-object parse memos for the host featurization path.

Churn replay featurizes the whole cluster every scheduling pass, but most
objects are unchanged between passes: the cluster store hands out the
SAME dict object for an unchanged resource (``list(copy_objs=False)``)
and a brand-new TOP-LEVEL dict on every write (state/cluster.py: writes
replace, never mutate; nothing edits a stored object in place).
``id(obj)`` therefore identifies a frozen snapshot of an object's content
for as long as that object is alive — and the memo keeps a strong
reference to every key object so its id cannot be recycled while an entry
exists.

What a write does NOT renew is what lies below the top level.
``create`` / ``update`` / ``patch`` deep-copy, but ``ClusterStore.rewrap``
— the scheduler's bind, the segment reconciler's placements, nominations
and requeues (``scenario/runner.py`` ``placed_pod`` / ``requeued_pod``) —
and the runner's ``_own`` hand-over share every unmodified substructure
with the object they replace (or were handed): a bound pod's
``spec.containers``, its ``spec.affinity`` term dicts and its
``topologySpreadConstraints`` are the SAME objects before and after the
bind, and the operation's own object shares them too.  Sub-objects are
frozen like their owners, so they are valid memo keys — for an entry
whose value is a function of the keyed sub-object (and of the key's
``extra`` parts) ALONE.  Such an entry outlives the write and is hit
again, which is the point.  An entry keyed by a sub-object must never
hold what the write can change around it: the pod's name or uid, its
node, its phase, its annotations, its resourceVersion.  Whatever reads
those is keyed by the pod itself, the top-level dict that every write
renews: ``preq``, ``affpod``, ``ipterms``, ``ipparsed``, ``hostports``,
``has_vols``, ``replay_*`` — every entry but one today.  The one keyed
below the top level is ``ipctx`` (an affinity term's pod-matching part,
the owner's namespace in its key: state/interpod.py), and it reads the
term alone.  ``tests/test_objcache.py`` holds a warm memo to a cold one
across re-wrapped binds and requeues.

Eviction is generational, not clear-all: entries touched recently
survive, entries untouched for a few generations are swept and their key
objects unpinned (a clear-all would force a cold re-parse of the whole
working set at once).  Note that with the incremental bound-pod
aggregation (state/boundagg.py) an unchanged bound pod's parse entries
may legitimately go untouched for many passes — its contribution lives
in the aggregate's records instead — so a sweep can evict entries for
still-live pods; the cost surfaces only as a one-pass cold re-parse on
the next full rebuild (vocabulary growth or unit rescale), which is the
same cost the rebuild itself already carries.  By convention ``key[1]``
is the pinned object's id (see ``ref_id``), which is how the sweep knows
which pins survive.

Callers that build JSON by hand (tests, library use) must not mutate an
object in place after featurizing it — mutate-and-refeaturize would see
stale parses.  The store path never does this.  ``clear()`` drops
everything.

Lifetime.  The tables live in a ``Memo``, and a memo belongs to whoever
hands out the keyed objects: a ``SchedulerService`` owns one for its
store (``SchedulerService.memo``) and installs it on the calling thread
for every pass (``scope``); ``ScenarioRunner.run`` installs its
service's for the whole replay, device lowering included.  The keys are
ids of objects ONE store handed out and each owner has its own tables,
so one store's entries can never serve another's lookups: a job's memo dies with the job's service, by
reference count, and pins nothing of a finished job.  The sweep below
is therefore the guard of a long-lived store only (the interactive
server's).  The module-level functions act on the memo installed on
the calling thread, else on the process default (library and test use
of the featurizer outside any service); callers of the keyed
primitives (``ref_id`` / ``get`` / ``put`` / ``intern_token``) bind
``current()`` once and call its methods.
"""

from __future__ import annotations

import collections
import contextlib
import threading
from typing import Any, Callable, Iterator

_MISS = object()
MISS = _MISS

# Sweep trigger: ~10 slots per live pod means 512k entries ≈ 50k live
# objects — far above any benchmarked cluster, so sweeps are rare.  The
# working limit doubles whenever a sweep can't reclaim half the table
# (see maybe_flush); LIMIT is the starting point.
LIMIT = 1 << 19
# Entries untouched for this many generations are considered dead.  Live
# objects are touched every featurization; 4 covers multi-profile setups
# where alternating profiles featurize disjoint queues.
STALE_GENERATIONS = 4
# Family-cache table, SEPARATE from the per-object one: entries hold
# multi-MB arrays and pin a whole node list each, so the per-object
# memo's ~512k-entry sweep threshold would never trigger — a bounded LRU
# of a few dozen is the right shape (7 families x a handful of live
# token/node-list variants; anything older is dead after the next node
# event anyway).
_SEQ_LIMIT = 64


class Memo:
    """One owner's tables: the per-object memo with its pins, the
    family (whole-sequence) LRU and the token intern table."""

    __slots__ = (
        "_data", "_refs", "_gen", "_limit", "_seq", "_intern", "_intern_next",
        "seq_builds",
    )

    def __init__(self) -> None:
        # key -> [value, last_access_generation]; key[1] is the pinned id.
        self._data: dict[Any, list] = {}
        self._refs: dict[int, Any] = {}
        self._gen = 0
        self._limit: "int | None" = None  # set past LIMIT when sweeps can't reclaim
        self._seq: "collections.OrderedDict[Any, Any]" = collections.OrderedDict()
        self._intern: dict[Any, int] = {}
        self._intern_next = 0
        # ``cached_seq`` misses: whole-sequence (node-axis) tables built
        # afresh.  Never reset: the replay driver reads its growth
        # across a lowering.
        self.seq_builds = 0

    def ref_id(self, obj: Any) -> int:
        """id(obj), pinned: the object stays alive while the memo does."""
        i = id(obj)
        if i not in self._refs:
            self._refs[i] = obj
        return i

    def get(self, key: Any) -> Any:
        """Lookup; returns the module sentinel ``MISS`` when absent."""
        entry = self._data.get(key)
        if entry is None:
            return _MISS
        entry[1] = self._gen
        return entry[0]

    def put(self, key: Any, value: Any) -> Any:
        """Store an entry.  Never evicts inline: an eviction here could
        unpin the in-flight key object (its id was taken by the caller
        before the sweep), letting the id be recycled under a surviving
        entry.  Size enforcement happens at safe points via
        maybe_flush()."""
        self._data[key] = [value, self._gen]
        return value

    def maybe_flush(self) -> None:
        """Advance the generation; sweep stale entries when over the limit.

        Called at points where no memo key is in flight (the featurizer's
        entry), so surviving entries' key objects stay pinned and swept
        ids are only unpinned when no entry references them.

        If a sweep frees little (the working set is genuinely that
        large), the limit doubles so the O(table) sweep scan stays
        amortized instead of running — and evicting nothing — on every
        subsequent pass."""
        self._gen += 1
        limit = self._limit if self._limit is not None else LIMIT
        data = self._data
        if len(data) < limit:
            return
        floor = self._gen - STALE_GENERATIONS
        for key in [k for k, e in data.items() if e[1] < floor]:
            del data[key]
        live_ids = {k[1] for k in data}
        refs = self._refs
        for i in [i for i in refs if i not in live_ids]:
            del refs[i]
        if len(data) > limit // 2:
            self._limit = limit * 2
        elif self._limit is not None and len(data) < LIMIT // 2:
            self._limit = None  # working set shrank back; restore the baseline

    def cached(self, slot: str, obj: Any, fn: Callable[[], Any], *extra: Any) -> Any:
        """Memoize ``fn()`` under (slot, id(obj), *extra)."""
        key = (slot, self.ref_id(obj), *extra)
        hit = self.get(key)
        if hit is not _MISS:
            return hit
        return self.put(key, fn())

    def cached_seq(self, slot: str, objs: Any, fn: Callable[[], Any], *extra: Any) -> Any:
        """Memoize ``fn()`` under (slot, tuple-of-ids(objs), *extra) — the
        family form of ``cached`` for whole-sequence builds (an encoder's
        node-side tables: identical whenever the exact same node objects
        and vocabulary token recur, which under churn is every pass
        without a node event).

        Unlike ``cached``, the entry pins its key objects ITSELF: the
        stored value carries strong references to every object in
        ``objs``, so none of their ids can be recycled while the entry
        lives.  (The ``key[1]`` pin convention doesn't extend to
        id-tuples — a sweep would unpin the members and a recycled id
        could alias a different object into a stale hit.)  Eviction is
        LRU over a small dedicated table."""
        seq = tuple(objs)
        key = (slot, tuple(map(id, seq)), *extra)
        table = self._seq
        hit = table.get(key)
        if hit is not None:
            table.move_to_end(key)
            return hit[0]
        self.seq_builds += 1
        value = fn()
        table[key] = (value, seq)
        if len(table) > _SEQ_LIMIT:
            table.popitem(last=False)
        return value

    def intern_token(self, token: Any) -> int:
        """Small stable int for a hashable token (hashed once, here):
        per-pod memo keys embed vocabulary tokens (tuples of canonical
        strings, often hundreds of entries), and hashing such a tuple on
        EVERY lookup is O(vocab) per pod per family.

        Reset valve: if an adversarial stream mints unbounded distinct
        tokens, the WHOLE per-object memo resets with the intern table.
        Ints come from a MONOTONIC counter (never restarted): callers
        capture interned ints in locals and may write memo entries with
        them after the valve fires, so a restarted numbering could hand
        a later token an int an in-flight key still embeds — aliasing a
        fresh lookup into a stale entry."""
        i = self._intern.get(token)
        if i is None:
            if len(self._intern) > (1 << 16):
                self._data.clear()
                self._refs.clear()
                self._intern.clear()
            i = self._intern_next
            self._intern_next += 1
            self._intern[token] = i
        return i

    def clear(self) -> None:
        self._data.clear()
        self._refs.clear()
        self._intern.clear()
        self._seq.clear()
        self._gen = 0
        self._limit = None

    def stats(self) -> dict[str, int]:
        return {
            "entries": len(self._data),
            "refs": len(self._refs),
            "generation": self._gen,
            "seq_entries": len(self._seq),
            "seq_builds": self.seq_builds,
            "interned": len(self._intern),
        }


#: What a thread with no owner's memo installed acts on.
_DEFAULT = Memo()
_tls = threading.local()


def current() -> Memo:
    """The memo installed on this thread (``scope``), else the process
    default.  Hot loops bind it once and call its methods."""
    return getattr(_tls, "memo", _DEFAULT)


@contextlib.contextmanager
def scope(memo: Memo) -> Iterator[Memo]:
    """Install ``memo`` on the calling thread for the block: every
    module-level call below, from any depth, acts on it."""
    prev = getattr(_tls, "memo", None)
    _tls.memo = memo
    try:
        yield memo
    finally:
        if prev is None:
            del _tls.memo
        else:
            _tls.memo = prev


def maybe_flush() -> None:
    current().maybe_flush()


def cached(slot: str, obj: Any, fn: Callable[[], Any], *extra: Any) -> Any:
    return current().cached(slot, obj, fn, *extra)


def cached_seq(slot: str, objs: Any, fn: Callable[[], Any], *extra: Any) -> Any:
    return current().cached_seq(slot, objs, fn, *extra)


def clear() -> None:
    current().clear()


def stats() -> dict[str, int]:
    return current().stats()
