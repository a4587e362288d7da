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
- A ``RowFamily`` is a set of named growable arrays with one row per
  table row, valid for a TOKEN (a vocabulary lineage, a resource axis,
  namespace labels).  ``sync`` runs the family's per-pod builder for the
  rows of this call that are not valid under the token — the new pods,
  or every row when the token moved (counted in ``rows_rebuilt``) — and
  writes them with one assignment per column.
- Encoders produce each ``[P, ...]`` output with a vectorised gather of
  ``family.take(col)`` into the padded buffer.  Rows hold ids of
  PERSISTENT append-only vocabularies (``Interner``, reset-valved);
  a call-local vocabulary in the one-shot path's first-appearance order
  is recovered from the gathered ids with ``first_seen``, so output
  shapes and ids are what a fresh featurizer produces.

A one-shot ``Featurizer()`` runs the same code with an empty table.
"""

from __future__ import annotations

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
        self._fams: dict[str, RowFamily] = {}
        self._vocabs: dict[str, Interner] = {}
        # Row index per pod of the current call.
        self.idx = np.zeros(0, dtype=np.intp)
        # Rows recomputed for a pod the table already held, because a
        # family's token moved (summed over families).
        self.rows_rebuilt = 0

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
    ) -> int:
        """Point the table at this call's pods (``self.idx``); returns
        how many of them were new.  ``on_release(rows)`` sees the rows
        about to be released while their columns are still readable."""
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
        if n > 2 * self._n_live:
            idx = self._compact(idx, n)
        self.idx = idx
        return n - n0

    def _resize(self, cap: int, keep: "np.ndarray | None" = None) -> None:
        for name in ("_live", "_born"):
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

    def sync(
        self,
        fam: RowFamily,
        token: Any,
        build: "Callable[[JSON], tuple]",
        widths: "dict[str, int] | None" = None,
    ) -> None:
        """Make ``fam``'s rows for the current call valid under
        ``token``: ``build(pod)`` — one value per column, in column
        order — runs for the rows that are not, in queue order.
        ``widths`` gives the ROW columns' widths under this token."""
        if fam.token != token:
            fam._reset(token, widths or {})
        idx = self.idx
        stale = ~fam.valid[idx]
        if not stale.any():
            return
        rows, first = np.unique(idx[stale], return_index=True)
        rows = rows[np.argsort(first, kind="stable")]
        self.rows_rebuilt += int(np.count_nonzero(self._born[rows] < self._gen))
        pinned = self._pods
        # In chunks: a chunk's records (a tuple and a few lists per pod)
        # are written and dropped before they outnumber the collector's
        # young-generation threshold, so a cold call of a thousand pods
        # promotes none of them into the old generation.
        for at in range(0, rows.size, _CHUNK):
            part = rows[at : at + _CHUNK]
            fam._write(part, [build(pinned[r]) for r in part.tolist()])
        fam.valid[rows] = True
