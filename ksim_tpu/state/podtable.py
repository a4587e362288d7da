"""Persistent per-pod row table: the pod axis is assembled by gather.

A ``Featurizer`` is handed nearly the same pods call after call — the
replay's universe is identity-stable across segments and the store hands
out the same dict for an unchanged object (state/objcache.py) — while
only a window's creates are new.  Lowering the pod axis by a Python loop
over every pod, one memo lookup and one row assignment per pod per
family, made each call cost O(universe) although it BUILT only O(delta)
rows.  This table keeps the lowered rows instead:

- ``index`` maps the call's pods to table rows by object identity: one
  dict lookup per pod, shared by every family — the only pod-axis-length
  Python of a call.  Pods not in the table get a fresh row; rows the
  call did not ask for are RELEASED at once (pin dropped, family rows
  invalidated), so the table pins exactly the pods of the last call and
  a departed pod is referenced by nothing here.  Row storage compacts
  when released slots outnumber live rows (length <= 2 x live).
- Every row carries a CONTENT ID: pods whose manifests are equal once
  the identity fields (``_IDENTITY``) are taken out share one.  Pods
  come from templates (a Deployment's replicas, scheduler_perf's
  pod-default), and a family's row is a function of the manifest's
  content and the family's token, never of who the pod is.  The id is
  a small int, recycled when its last row is released, so the key map
  is bounded by the live rows exactly as the table is and holds bytes,
  never a manifest.  Who makes a key: this table, ``content_key`` of
  every pod new to it — unless the caller of ``index`` HANDS the pod's
  key in (``handed``), having keyed the pod already for a purpose of
  its own.  The table takes such a key as it is, and keys every other
  pod itself, as if nothing had been handed; that a handed key cuts
  the pods as ``content_key`` would is the caller's to see to.
- A ``RowFamily`` is a set of named growable arrays with one row per
  table row, valid for a TOKEN (a vocabulary lineage, a resource axis,
  namespace labels).  ``sync`` makes the rows of this call valid under
  the token — the new pods, or every row when the token moved (counted
  in ``rows_rebuilt``): the family's per-pod builder runs once for each
  content id that has no valid row yet, and every other row of that
  content is filled by one vectorised copy per column.
- Encoders produce each ``[P, ...]`` output with a vectorised gather of
  ``family.take(col)`` into the padded buffer.  Rows hold ids of
  PERSISTENT append-only vocabularies (``Interner``, reset-valved);
  a call-local vocabulary in the one-shot path's first-appearance order
  is recovered from the gathered ids with ``first_seen``, so output
  shapes and ids are what a fresh featurizer produces.

A one-shot ``Featurizer()`` runs the same code with an empty table.
"""

from __future__ import annotations

import marshal
import weakref
from dataclasses import dataclass
from itertools import repeat
from typing import Any, Callable, Sequence

import numpy as np

from ksim_tpu.state.resources import JSON

__all__ = [
    "Column", "Interner", "PodTable", "RowFamily", "first_seen", "rank_lut", "scatter_add",
]

# Column kinds: one value per row; a variable-length id list per row
# (padded with the fill value, widened on demand); a fixed-width row
# whose width belongs to the family token.
SCALAR, LIST, ROW = "scalar", "list", "row"

_MIN_CAP = 64
_CHUNK = 128
_UNSET = object()

# What tells two replicas of one template apart.  A deny-list on
# purpose: a field nobody thought of makes two manifests differ (the
# builders run for both, as before), never share a row.
_IDENTITY = frozenset((
    "name", "uid", "resourceVersion", "creationTimestamp", "generateName",
    "selfLink", "managedFields",
))


def content_key(pod: JSON) -> "bytes | None":
    """The manifest less its identity fields, as bytes: equal keys mean
    equal content.  Marshal format 2 writes values only (later formats
    add back-references and interning flags that depend on which string
    OBJECTS a manifest happens to share); dicts go in their own order,
    so a reordered manifest may miss and can never collide.  None for a
    manifest marshal cannot take: that pod shares with nobody."""
    meta = pod.get("metadata")
    if type(meta) is dict:
        pod = dict(pod)
        pod["metadata"] = {k: v for k, v in meta.items() if k not in _IDENTITY}
    try:
        return marshal.dumps(pod, 2)
    except ValueError:
        return None


@dataclass(frozen=True)
class Column:
    name: str
    dtype: Any
    fill: Any = 0
    kind: str = SCALAR


class Interner:
    """Append-only key -> small-int vocabulary that outlives a call, so
    table rows can store its ids.  ``valve`` (called before a call's
    first ``intern``) restarts it past ``LIMIT`` entries — an adversarial
    stream of distinct keys must not grow it without bound — and bumps
    ``gen``, which every family storing these ids carries in its token."""

    LIMIT = 4096

    def __init__(self) -> None:
        self.ids: dict[Any, int] = {}
        self.items: list[Any] = []
        self.gen = 0

    def valve(self) -> None:
        if len(self.items) > self.LIMIT:
            self.ids = {}
            self.items = []
            self.gen += 1

    def intern(self, key: Any, item: Any = None) -> int:
        i = self.ids.get(key)
        if i is None:
            i = self.ids[key] = len(self.items)
            self.items.append(key if item is None else item)
        return i


def first_seen(ids: np.ndarray) -> np.ndarray:
    """Distinct non-negative values of ``ids`` in order of first
    appearance, row-major — the numbering a per-pod loop registering ids
    into a fresh call-local vocabulary would produce."""
    flat = ids.ravel()
    flat = flat[flat >= 0]
    if not flat.size:
        return flat
    uniq, first = np.unique(flat, return_index=True)
    return uniq[np.argsort(first, kind="stable")]


def rank_lut(present: np.ndarray, size: int) -> np.ndarray:
    """id -> position in ``present`` (-1 when absent) over ``size``
    persistent ids; the extra last slot keeps ``lut[-1] == -1`` so padded
    id lists map through unmasked."""
    lut = np.full(size + 1, -1, np.int32)
    lut[present] = np.arange(present.size, dtype=np.int32)
    return lut


def scatter_add(
    out: np.ndarray, ids: np.ndarray, lut: np.ndarray, weights: "np.ndarray | int" = 1
) -> None:
    """``out[j, lut[ids[j, k]]] += weights[j, k]`` for every listed id
    (``ids`` [P, W], -1 = none): a pod's id list into its dense row."""
    rr, cc = np.nonzero(ids >= 0)
    if rr.size:
        w = weights if np.isscalar(weights) else weights[rr, cc]
        np.add.at(out, (rr, lut[ids[rr, cc]]), w)


class RowFamily:
    """One family's lowered rows (see module docstring)."""

    def __init__(self, table: "PodTable", name: str, columns: Sequence[Column]) -> None:
        cap = table._live.shape[0]
        self.name = name
        self.columns = tuple(columns)
        self.token: Any = _UNSET
        self.valid = np.zeros(cap, dtype=bool)
        self.cols = {c.name: self._alloc(c, cap, 0) for c in self.columns}
        # Weak: the table owns its families, and a back reference would
        # make a cycle of the table with every pod manifest it holds —
        # a finished job's cluster would wait for the collector instead
        # of going by reference count.
        self._table = weakref.ref(table)

    @staticmethod
    def _alloc(c: Column, cap: int, width: int) -> np.ndarray:
        shape = (cap,) if c.kind == SCALAR else (cap, width)
        return np.full(shape, c.fill, dtype=c.dtype)

    def take(self, col: str) -> np.ndarray:
        """The column's rows for the pods of the current call."""
        return self.cols[col][self._table().idx]

    def _reset(self, token: Any, widths: "dict[str, int]") -> None:
        self.token = token
        self.valid[:] = False
        cap = self.valid.shape[0]
        for c in self.columns:
            if c.kind == ROW:
                self.cols[c.name] = self._alloc(c, cap, widths.get(c.name, 0))

    def _resize(self, cap: int, keep: "np.ndarray | None" = None) -> None:
        """Reallocate at ``cap`` rows, carrying rows ``keep`` (all when
        None) to the front."""

        def carry(old: np.ndarray, new: np.ndarray) -> np.ndarray:
            src = old if keep is None else old[keep]
            new[: src.shape[0]] = src
            return new

        self.valid = carry(self.valid, np.zeros(cap, dtype=bool))
        for c in self.columns:
            old = self.cols[c.name]
            width = 0 if c.kind == SCALAR else old.shape[1]
            self.cols[c.name] = carry(old, self._alloc(c, cap, width))

    def _write(self, rows: np.ndarray, recs: "list[tuple]") -> None:
        for ci, c in enumerate(self.columns):
            col = self.cols[c.name]
            if c.kind == LIST:
                lens = [len(rec[ci]) for rec in recs]
                width = max(lens)
                if width > col.shape[1]:
                    wide = self._alloc(c, col.shape[0], width)
                    wide[:, : col.shape[1]] = col
                    col = self.cols[c.name] = wide
                col[rows] = c.fill
                for r, rec, n in zip(rows.tolist(), recs, lens):
                    if n:
                        col[r, :n] = rec[ci]
            elif c.dtype is object:
                # Never through np.asarray: a tuple-valued cell would be
                # broadcast as a sequence.
                for r, rec in zip(rows.tolist(), recs):
                    col[r] = rec[ci]
            else:
                col[rows] = np.asarray([rec[ci] for rec in recs], dtype=c.dtype)

    def _copy(self, dst: np.ndarray, src: np.ndarray) -> None:
        for col in self.cols.values():
            col[dst] = col[src]


class PodTable:
    def __init__(self) -> None:
        self._row_of: dict[int, int] = {}
        # row -> the pod object, pinned so its id cannot be recycled
        # while the row lives; None marks a released slot.
        self._pods: "list[JSON | None]" = []
        self._live = np.zeros(_MIN_CAP, dtype=bool)
        self._born = np.zeros(_MIN_CAP, dtype=np.int64)
        self._n_live = 0
        self._gen = 0
        # Content ids: per row, and per id its key (None: free, or a
        # manifest without one) and its live rows; freed ids are reused.
        self._cid = np.zeros(_MIN_CAP, dtype=np.intp)
        self._cid_of: dict[bytes, int] = {}
        self._cid_key: "list[bytes | None]" = []
        self._cid_rows: list[int] = []
        self._cid_free: list[int] = []
        self._fams: dict[str, RowFamily] = {}
        self._vocabs: dict[str, Interner] = {}
        # Row index per pod of the current call.
        self.idx = np.zeros(0, dtype=np.intp)
        # Rows recomputed for a pod the table already held, because a
        # family's token moved (summed over families).
        self.rows_rebuilt = 0
        # Pods new to the table whose content a live row (or an earlier
        # pod of the same call) already had.
        self.rows_copied = 0
        # ``content_key`` calls made here: new pods that came without a
        # handed key.
        self.keys_built = 0

    def __len__(self) -> int:
        return len(self._pods)

    @property
    def live(self) -> int:
        return self._n_live

    def pod(self, j: int) -> JSON:
        """The pod at position ``j`` of the current call."""
        return self._pods[self.idx[j]]

    def interner(self, name: str) -> Interner:
        v = self._vocabs.get(name)
        if v is None:
            v = self._vocabs[name] = Interner()
        return v

    def family(self, name: str, columns: Sequence[Column]) -> RowFamily:
        fam = self._fams.get(name)
        if fam is None:
            fam = self._fams[name] = RowFamily(self, name, columns)
        return fam

    def index(
        self,
        pods: Sequence[JSON],
        on_release: "Callable[[np.ndarray], None] | None" = None,
        handed: "dict[int, bytes | None] | None" = None,
    ) -> int:
        """Point the table at this call's pods (``self.idx``); returns
        how many of them were new.  ``on_release(rows)`` sees the rows
        about to be released while their columns are still readable.
        ``handed`` maps ``id(pod)`` to the content key its caller has
        taken already: bytes, equal for two pods only if their manifests
        are equal outside ``_IDENTITY`` (an empty ``status`` may stand
        for an absent one), or None for a pod that shares with nobody."""
        self._gen += 1
        row_of = self._row_of
        idx = np.array(list(map(row_of.get, map(id, pods), repeat(-1))), dtype=np.intp)
        n0 = len(self._pods)
        for j in np.nonzero(idx < 0)[0].tolist():
            p = pods[j]
            r = row_of.get(id(p))
            if r is None:
                r = row_of[id(p)] = len(self._pods)
                self._pods.append(p)
            idx[j] = r
        n = len(self._pods)
        if n > self._live.shape[0]:
            self._resize(max(n, 2 * self._live.shape[0]))
        self._live[n0:n] = True
        self._born[n0:n] = self._gen
        self._n_live += n - n0

        asked = np.zeros(n, dtype=bool)
        asked[idx] = True
        gone = np.nonzero(self._live[:n] & ~asked)[0]
        if gone.size:
            if on_release is not None:
                on_release(gone)
            pinned = self._pods
            for r in gone.tolist():
                del row_of[id(pinned[r])]
                pinned[r] = None
            self._live[gone] = False
            self._n_live -= gone.size
            for fam in self._fams.values():
                fam.valid[gone] = False
                for c in fam.columns:
                    if c.dtype is object:
                        fam.cols[c.name][gone] = None
            self._forget_content(gone)
        # After the release, so that "copied" means a row that stays.
        if n > n0:
            self._cid[n0:n] = self._content_ids(self._pods[n0:n], handed or {})
        if n > 2 * self._n_live:
            idx = self._compact(idx, n)
        self.idx = idx
        return n - n0

    def _content_ids(
        self, pods: "list[JSON]", handed: "dict[int, bytes | None]"
    ) -> "list[int]":
        """A content id for each of ``pods``, new to the table: by the
        key that came with the pod, by ``content_key`` otherwise."""
        ids, keys, left, free = self._cid_of, self._cid_key, self._cid_rows, self._cid_free
        out = []
        for p in pods:
            key = handed.get(id(p), _UNSET)
            if key is _UNSET:
                key = content_key(p)
                self.keys_built += 1
            c = None if key is None else ids.get(key)
            if c is not None:
                left[c] += 1
                self.rows_copied += 1
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
            out.append(c)
        return out

    def _forget_content(self, gone: np.ndarray) -> None:
        """Released rows leave their content ids; an id no row holds
        any more gives its key up."""
        left = self._cid_rows
        ids, counts = np.unique(self._cid[gone], return_counts=True)
        for c, k in zip(ids.tolist(), counts.tolist()):
            left[c] -= k
            if not left[c]:
                key = self._cid_key[c]
                if key is not None:
                    del self._cid_of[key]
                    self._cid_key[c] = None
                self._cid_free.append(c)

    def by_content(self, rows: np.ndarray) -> "tuple[np.ndarray, list[int]]":
        """One of ``rows`` for each content among them, and how many of
        ``rows`` have that content."""
        _, first, counts = np.unique(self._cid[rows], return_index=True, return_counts=True)
        return rows[first], counts.tolist()

    def _resize(self, cap: int, keep: "np.ndarray | None" = None) -> None:
        for name in ("_live", "_born", "_cid"):
            old = getattr(self, name)
            src = old if keep is None else old[keep]
            new = np.zeros(cap, dtype=old.dtype)
            new[: src.shape[0]] = src
            setattr(self, name, new)
        for fam in self._fams.values():
            fam._resize(cap, keep)

    def _compact(self, idx: np.ndarray, n: int) -> np.ndarray:
        keep = np.nonzero(self._live[:n])[0]
        lut = np.full(n, -1, dtype=np.intp)
        lut[keep] = np.arange(keep.size)
        self._pods = [self._pods[r] for r in keep.tolist()]
        self._row_of = {id(p): i for i, p in enumerate(self._pods)}
        self._resize(max(_MIN_CAP, 2 * keep.size), keep)
        return lut[idx]

    def _sources(self, fam: RowFamily, rows: np.ndarray) -> np.ndarray:
        """For each of the stale ``rows`` (queue order) the row its
        content comes from: a row of that content already valid under
        the family's token, else the first of ``rows`` with that content
        — itself, then, and the builder has to run for it."""
        cids = self._cid[rows]
        src_of = np.full(len(self._cid_key), -1, dtype=np.intp)
        uniq, first = np.unique(cids, return_index=True)
        src_of[uniq] = rows[first]
        # Every valid row is live (a release invalidates), so its
        # content id is current; which of several wins does not matter.
        have = np.nonzero(fam.valid)[0]
        src_of[self._cid[have]] = have
        return src_of[cids]

    def sync(
        self,
        fam: RowFamily,
        token: Any,
        build: "Callable[[JSON], tuple]",
        widths: "dict[str, int] | None" = None,
        shared: bool = True,
    ) -> np.ndarray:
        """Make ``fam``'s rows for the current call valid under
        ``token`` and return the rows that were not, in queue order.
        ``build(pod)`` — one value per column, in column order — runs
        for the first of them of each content id no valid row has, in
        queue order (a persistent vocabulary meets new keys in the order
        a walk over every pod would register them); the others copy a
        valid row of their content.  ``shared=False`` is for a family
        that reads who the pod is: ``build`` then runs for every row.
        ``widths`` gives the ROW columns' widths under this token."""
        if fam.token != token:
            fam._reset(token, widths or {})
        idx = self.idx
        stale = ~fam.valid[idx]
        if not stale.any():
            return idx[:0]
        rows, first = np.unique(idx[stale], return_index=True)
        rows = rows[np.argsort(first, kind="stable")]
        self.rows_rebuilt += int(np.count_nonzero(self._born[rows] < self._gen))
        src = self._sources(fam, rows) if shared else rows
        todo = rows[src == rows]
        pinned = self._pods
        # In chunks: a chunk's records (a tuple and a few lists per pod)
        # are written and dropped before they outnumber the collector's
        # young-generation threshold, so a cold call of a thousand pods
        # promotes none of them into the old generation.
        for at in range(0, todo.size, _CHUNK):
            part = todo[at : at + _CHUNK]
            fam._write(part, [build(pinned[r]) for r in part.tolist()])
        if todo.size < rows.size:
            dup = src != rows
            fam._copy(rows[dup], src[dup])
        fam.valid[rows] = True
        return rows
