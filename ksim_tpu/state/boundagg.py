"""Incremental bound-pod aggregation for churn-scale featurization.

Featurizing a snapshot walks every BOUND pod to build additive node-space
aggregates (requested-resource sums, inter-pod-affinity domain counts,
topology-spread selector counts).  Under churn replay that walk is the
scaling wall: the bound population reaches 10k+ while only ~200 pods
change per scheduling pass, so re-aggregating from scratch costs
O(bound) Python work per pass (measured 0.6s/pass at 11k bound pods —
more than the TPU compute it feeds).  And a job that starts from a
populated cluster meets the whole population in its first call: 150,000
bound pods of ~15,000 distinct manifests, in five families.

This module lets a persistent ``Featurizer`` maintain those aggregates
across passes:

- ``NodeSlots`` pins each node NAME to a stable position on the node
  axis so that node churn does not shift every other node's index
  (deletion swap-removes: the last slot's node moves into the freed
  slot, so exactly two slots change).  For a fresh instance the order is
  first-seen order, i.e. identical to the caller's list.
- ``BoundContents`` gives every live bound pod a CONTENT ID: pods whose
  manifests are equal once identity, ``spec.nodeName`` and ``status``
  are taken out (``content_key``) share one.  What a bound pod adds to
  an aggregate is ``(slot of its node, contribution of its content)``:
  replicas of one template differ in who they are and where they run,
  never in what they add.  The ids are small ints, maintained once a
  call from the featurizer's bound-set diff and shared by the families;
  an id goes back with its last pod, so the key map is bounded by the
  live bound set and holds bytes, never a manifest.  Who makes a key:
  ``BoundContents.sync``, one ``content_key`` an arrival — unless the
  pod's key is HANDED in (``Featurizer.featurize(content_keys=)``) by
  a caller that has taken this module's ``content_key`` of the pod
  already, for a purpose of its own; an arrival that comes without
  one (a re-wrapped pod of a later window, every pod of the per-pass
  path) is keyed here.
- ``sync_family`` maintains one aggregate: a pod's contribution applied
  additively (+1 on arrival, -1 on departure), with per-slot repair
  when a slot's node changed (drained node, replaced object) and a full
  rebuild whenever the family's validity token changes (vocab growth,
  unit rescale, axis resize).  The family keeps a CONTRIBUTION TABLE,
  content id -> contribution under its current token: ``contribution``
  (the per-pod Python: request parses, selector and context matches,
  term mappings) runs for a content id the table does not hold, and
  every other pod of that content — in the from-scratch walk and among
  a window's arrivals alike — takes the table's.  A content whose
  contribution is ``None`` adds nothing on any node: its pods get no
  record, no slot lookup and no ``apply``.  A token move drops the
  family's arrays and its table, never the content ids.

Correctness contract: ``apply(arrays, slot, rec, +1)`` followed by
``apply(arrays, slot, rec, -1)`` must be a no-op; ``contribution(pod)``
must be a pure function of (the pod's content as ``content_key`` keeps
it, the family token) — never of the pod's name, node or status — and
``slot_of(pod)`` one of (``spec.nodeName``, current node slots).  The
equivalence tests (tests/test_boundagg.py) hold every family's arrays
to a per-pod loop over random mutation sequences and replay streams
asserting that a persistent featurizer's engine-visible outputs match a
fresh featurizer's.
"""

from __future__ import annotations

from typing import Any, Callable, Iterable, Sequence

from ksim_tpu.state import podtable
from ksim_tpu.state.resources import JSON, name_of

__all__ = [
    "BoundContents", "NodeSlots", "content_key", "records_built",
    "records_shared", "sync_family",
]

#: The state dict's keys beside the families: the shared bound-set diff
#: and the content ids (both written once a call by the featurizer), and
#: the counts of bound-pod records that ran a family's ``contribution``
#: builder and of those that took their content's from the table.
_DIFF = "__diff__"
_CONTENTS = "__contents__"
_BUILT = "__built__"
_SHARED = "__shared__"
_MISS = object()


def records_built(state: dict) -> int:
    """Bound-pod records for which a family of ``state`` RAN its
    contribution builder: one a distinct content a family and token.
    ``records_built + records_shared`` = families x bound pods met (a
    family's from-scratch walk meets its whole bound population, an
    incremental sync its arrivals and slot repairs); a multiple of
    families x bound pods = a family's token moved and it walked them
    all again."""
    return state.get(_BUILT, 0)


def records_shared(state: dict) -> int:
    """Bound-pod records that ran no builder: the pod's content had its
    contribution in the family's table."""
    return state.get(_SHARED, 0)


def content_key(pod: JSON) -> "bytes | None":
    """A bound pod's manifest less what tells replicas apart
    (``podtable._IDENTITY``), less ``spec.nodeName`` (the slot's side)
    and less ``status`` (no contribution reads it — the phase filter
    runs before — and a real export's differs in every pod: ``podIP``,
    ``startTime``), as bytes; equal keys mean equal content.  A
    deny-list, as ``podtable.content_key`` is, which writes the bytes: a
    field nobody thought of makes two manifests differ, never share.
    None for a manifest marshal cannot take: that pod shares with
    nobody."""
    spec = pod.get("spec")
    pod = dict(pod)
    pod.pop("status", None)
    if type(spec) is dict and "nodeName" in spec:
        spec = pod["spec"] = dict(spec)
        del spec["nodeName"]
    return podtable.content_key(pod)


class BoundContents:
    """Content ids of the live bound pods (see module docstring)."""

    def __init__(self) -> None:
        #: id(pod) -> content id, for exactly the pods of the last sync.
        self.of: dict[int, int] = {}
        # Per id its key (None: free, or a manifest without one) and its
        # live pods; freed ids are reused.
        self._ids: dict[bytes, int] = {}
        self._keys: "list[bytes | None]" = []
        self._left: list[int] = []
        self._free: list[int] = []
        #: ``content_key`` calls made here: arrivals that came without a
        #: handed key.
        self.keys_built = 0

    def __len__(self) -> int:
        """Contents that have a live pod."""
        return len(self._keys) - len(self._free)

    def sync(
        self,
        bound_map: "dict[int, JSON]",
        added: Iterable[int],
        removed: Iterable[int],
        handed: "dict[int, bytes | None] | None" = None,
    ) -> list[int]:
        """Follow one bound-set diff: departures give their ids back,
        arrivals get theirs — by the key that came with the pod
        (``handed``: ``id(pod)`` -> its ``content_key``, taken by the
        caller already), by ``content_key`` otherwise.  Returns the ids
        whose last pod left — a table keyed by content id drops them
        before it reads an arrival's, because an arrival of this very
        call may hold one again for another content."""
        handed = handed or {}
        of, ids, keys, left, free = self.of, self._ids, self._keys, self._left, self._free
        released = []
        for pid in removed:
            c = of.pop(pid)
            left[c] -= 1
            if not left[c]:
                key = keys[c]
                if key is not None:
                    del ids[key]
                    keys[c] = None
                released.append(c)
        free.extend(released)
        for pid in added:
            key = handed.get(pid, _MISS)
            if key is _MISS:
                key = content_key(bound_map[pid])
                self.keys_built += 1
            c = None if key is None else ids.get(key)
            if c is not None:
                left[c] += 1
            else:
                if free:
                    c = free.pop()
                    keys[c], left[c] = key, 1
                else:
                    c = len(keys)
                    keys.append(key)
                    left.append(1)
                if key is not None:
                    ids[key] = c
            of[pid] = c
        return released

    def firsts(self, pids: Iterable[int]) -> list[int]:
        """The first of ``pids`` of each content among them, in order:
        a walk that only REGISTERS what a pod's content holds (a
        vocabulary) meets new keys there in the order a walk over every
        pod would."""
        of = self.of
        seen: set[int] = set()
        out = []
        for pid in pids:
            c = of[pid]
            if c not in seen:
                seen.add(c)
                out.append(pid)
        return out


class NodeSlots:
    """Persistent node-name -> axis-slot assignment with swap-remove."""

    def __init__(self) -> None:
        self.slot_of: dict[str, int] = {}
        self._names: list[str] = []
        # The node OBJECT last seen per slot (a strong ref, compared by
        # identity): comparing bare id() values would miss a replacement
        # whose new dict recycled the old dict's address.
        self._objs: list[JSON] = []

    def seed(self, names: Sequence[str]) -> None:
        """Install a recorded name order VERBATIM (checkpoint restore,
        ksim_tpu/jobs/manager.py).  Slot order is scheduling-visible —
        selectHost breaks score ties by lowest slot index, and the
        evolved swap-remove order diverges from first-seen order — so a
        resumed run must start from the order the interrupted run had,
        not rediscover it from the caller's list.  Per-slot object refs
        reset to fresh sentinels: the next ``sync`` sees an identity
        mismatch on every slot and marks them all changed, so the
        additive families repair/rebuild against real objects (a fresh
        featurizer rebuilds from scratch anyway — the seed trades one
        full repair for the exact ORDER)."""
        self.slot_of = {nm: i for i, nm in enumerate(names)}
        self._names = list(names)
        self._objs = [{} for _ in names]

    def sync(self, nodes: Sequence[JSON]) -> tuple[list[JSON], set[int]]:
        """Update the assignment for the current node set.

        Returns (nodes reordered to slot order, slots whose occupant
        changed since the previous call — by name or by object).
        """
        by_name = {name_of(n): n for n in nodes}
        changed: set[int] = set()

        # Deletions: swap-remove, highest slot first so the swap source
        # is never itself a pending deletion's stale position.
        gone = [s for nm, s in self.slot_of.items() if nm not in by_name]
        for s in sorted(gone, reverse=True):
            nm = self._names[s]
            last = len(self._names) - 1
            del self.slot_of[nm]
            if s != last:
                moved = self._names[last]
                self._names[s] = moved
                self._objs[s] = self._objs[last]
                self.slot_of[moved] = s
                changed.add(s)
            self._names.pop()
            self._objs.pop()
            changed.discard(last)
            changed.add(last)  # slot vanished (or shrank away)

        # Additions + object changes.
        for nm, n in by_name.items():
            s = self.slot_of.get(nm)
            if s is None:
                s = len(self._names)
                self.slot_of[nm] = s
                self._names.append(nm)
                self._objs.append(n)
                changed.add(s)
            elif self._objs[s] is not n:
                self._objs[s] = n
                changed.add(s)

        ordered = [by_name[nm] for nm in self._names]
        # Slots past the current end stay in ``changed``: records pinned
        # to a vanished slot index must still be repaired.
        return ordered, changed


def sync_family(
    state: dict,
    name: str,
    token: Any,
    bound_map: dict[int, JSON],
    changed_slots: set[int],
    *,
    make_arrays: Callable[[], Any],
    slot_of: "Callable[[JSON], int | None] | None",
    contribution: Callable[[JSON], Any],
    apply: Callable[[Any, int, Any, int], None],
    place: "Callable[[int, Any], Any] | None" = None,
) -> Any:
    """Maintain one additive aggregate over the bound-pod population.

    ``state``: the featurizer's, with this call's ``__diff__`` and
    ``__contents__`` in it.
    ``bound_map``: id(pod) -> pod for the CURRENT bound set (caller
    builds it once per pass and shares it across families).
    ``slot_of``: pod -> slot of its node, or None when the node is not
    on the axis (no contribution until it appears).  None for the
    family: node-independent, every pod at slot -1.
    ``contribution``: pod -> what its CONTENT adds under ``token`` (None:
    nothing, on any node); runs once a content id, see the module
    docstring.
    ``place``: (slot, contribution) -> what that comes to on this slot
    (the inter-pod families join the node's domains in), or None for
    nothing there; kept with the pod, so that a departure takes away
    what the arrival added whatever the node has become.  Without it
    the contribution itself is applied.
    ``apply``: (arrays, slot, placed contribution, +1 or -1).

    Returns the family's arrays (the live master — callers must treat
    them as read-only and copy before handing them to the engine).
    """
    diff = state[_DIFF]
    cid_of = state[_CONTENTS].of
    fam = state.get(name)
    if fam is not None and fam["token"] != token:
        fam = None
    fresh = fam is None
    if fresh:
        fam = state[name] = {
            "token": token,
            # id(pod) -> (pod, slot, placed contribution), for the pods
            # that add something; slot None = waiting for its node.
            "records": {},
            "by_slot": {},
            "nones": set(),
            "table": {},
            "arrays": make_arrays(),
            "gen": None,
        }
    records: dict[int, tuple[JSON, "int | None", Any]] = fam["records"]
    by_slot: dict[int, set[int]] = fam["by_slot"]
    nones: set[int] = fam["nones"]
    table: dict[int, Any] = fam["table"]
    arrays = fam["arrays"]
    met = built = 0

    def _drop(pid: int) -> None:
        _p, slot, rec = records.pop(pid)
        if slot is None:
            nones.discard(pid)
            return
        if rec is not None:
            apply(arrays, slot, rec, -1)
        peers = by_slot.get(slot)
        if peers is not None:
            peers.discard(pid)
            if not peers:
                del by_slot[slot]

    def _add(pid: int, p: JSON) -> None:
        nonlocal met, built
        met += 1
        c = cid_of[pid]
        contrib = table.get(c, _MISS)
        if contrib is _MISS:
            contrib = table[c] = contribution(p)
            built += 1
        if contrib is None:
            return
        if slot_of is None:
            records[pid] = (p, -1, contrib)
            apply(arrays, -1, contrib, +1)
            return
        slot = slot_of(p)
        if slot is None:
            records[pid] = (p, None, None)
            nones.add(pid)
            return
        rec = contrib if place is None else place(slot, contrib)
        records[pid] = (p, slot, rec)
        by_slot.setdefault(slot, set()).add(pid)
        if rec is not None:
            apply(arrays, slot, rec, +1)

    if fresh:
        for pid, p in bound_map.items():
            _add(pid, p)
    else:
        # 1+3. Departures and arrivals.  When this family was synced on
        # the immediately preceding pass, consume the featurizer's
        # shared diff directly — O(changed) instead of two O(bound)
        # scans per family per pass (the dict-walk cost dominated
        # saturated churn-replay host time).  Any gap in the family's
        # sync history falls back to the full scans, and the table —
        # which missed the ids released meanwhile — starts over.
        if fam["gen"] == diff["gen"] - 1:
            for c in diff["released"]:
                table.pop(c, None)
            departures = [pid for pid in diff["removed"] if pid in records]
            arrivals: Iterable[int] = diff["added"]
        else:
            table.clear()
            departures = [pid for pid in records if pid not in bound_map]
            arrivals = bound_map
        for pid in departures:
            _drop(pid)
        # 2. Slot repairs: pods whose node changed (or vanished/moved),
        #    plus the pods waiting for their node whenever any slot
        #    changed (it may just have appeared).
        if changed_slots:
            repair = set(nones)
            for s in changed_slots:
                repair |= by_slot.get(s, set())
            for pid in repair:
                p = records[pid][0]
                _drop(pid)
                _add(pid, p)
        # 3. Arrivals.  (In the full scan, a pod with no record that
        #    adds nothing is looked up again: its content says so.)
        for pid in arrivals:
            if pid not in records:
                _add(pid, bound_map[pid])
    fam["gen"] = diff["gen"]
    state[_BUILT] = state.get(_BUILT, 0) + built
    state[_SHARED] = state.get(_SHARED, 0) + met - built
    return arrays
