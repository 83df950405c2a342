"""Typed host mirror of a region's flagged scalar fields, one column a field.

A region's ``scalar_speedup_keys`` (VectorIndexParameter field 12, the
upstream's ScalarSchema ``enable_speed_up``) name the scalar fields its
filters are accelerated on. The slot store keeps one ``[capacity]`` numpy
column per such field beside ``ids_by_slot``, with a per-slot ``present``
bit for a row that lacks the field. A SCALAR filter on those fields then
compiles to a slot mask in numpy (``FilterSpec.slot_mask``): no engine read,
no per-candidate loop, and the mask rides the index's one search program.

Writes: the apply that upserts a row writes its columns (``write``), so an
acknowledged row filters at once; a delete clears the slot (``release``);
growth pads every column (``grow``). Nothing is saved with an index
snapshot: build and load fill the columns from the speed-up CF
(``VectorIndexManager.restore_columns``), which apply writes in the same
batch as the vectors and so always agrees with them.

A slot the store assigned but whose columns were never written is
*unknown* (a row loaded from a snapshot before the restore, or one that
landed between an upsert and its column write). While any live slot is
unknown the mirror is incomplete and ``match`` refuses
(``ColumnsUnavailable``): the reader then serves the filter on its host
path from the engine, so an incomplete mirror is slower, never wrong.

Semantics are ``ScalarPredicate.matches`` exactly: a missing field never
matches. Whole-number and float columns with a numeric operand compare in
numpy; any other column or operand (strings, mixed types, an int outside
int64) falls back to ``ScalarPredicate.matches`` over the present slots.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from dingo_tpu.coprocessor.scalar_filter import CmpOp, ScalarPredicate

_INT64_MIN, _INT64_MAX = -(1 << 63), (1 << 63) - 1
#: the largest whole number every float64 holds exactly
_EXACT_FLOAT_INT = 1 << 53
_NP_OPS = {CmpOp.EQ: np.equal, CmpOp.NE: np.not_equal, CmpOp.LT: np.less,
           CmpOp.LE: np.less_equal, CmpOp.GT: np.greater,
           CmpOp.GE: np.greater_equal}


class ColumnsUnavailable(Exception):
    """Predicates reached a store that mirrors no complete column for
    each of their fields; the reader serves them on its host path."""


def _is_int(v) -> bool:
    return isinstance(v, int) and _INT64_MIN <= v <= _INT64_MAX


def _kind(values: Sequence) -> str:
    """numpy dtype name that holds every value exactly, or 'object'."""
    if all(_is_int(v) for v in values):
        return "int64"
    if all(type(v) is float for v in values):
        return "float64"
    return "object"


def _object_array(values: Sequence) -> np.ndarray:
    out = np.empty(len(values), object)
    for i, v in enumerate(values):
        out[i] = v
    return out


class ScalarColumns:
    def __init__(self, fields: Sequence[str], capacity: int):
        self.fields: Tuple[str, ...] = tuple(fields)
        # (values, present) per field, swapped whole on growth so a reader
        # that took the pair never sees two lengths
        self._state: Tuple[Dict[str, np.ndarray], Dict[str, np.ndarray]] = (
            {f: np.zeros(capacity, np.int64) for f in self.fields},
            {f: np.zeros(capacity, np.bool_) for f in self.fields},
        )
        self._known = np.zeros(capacity, np.bool_)
        self.unknown = 0

    # -- slot lifecycle (SlotStore hooks) ----------------------------------
    def assign(self, slots: np.ndarray) -> None:
        """Slots the store just gave to ids it did not hold."""
        if len(slots):
            self._known[slots] = False
            self.unknown += len(slots)

    def release(self, slots: np.ndarray) -> None:
        """Slots the store freed: their rows are gone, so are the values."""
        slots = slots[slots >= 0]
        if not len(slots):
            return
        self.unknown -= int((~self._known[slots]).sum())
        self._known[slots] = False
        for present in self._state[1].values():
            present[slots] = False

    def grow(self, capacity: int) -> None:
        values, present = self._state
        pad = capacity - len(self._known)
        self._state = (
            {f: np.concatenate([v, np.zeros(pad, v.dtype)])
             for f, v in values.items()},
            {f: np.concatenate([p, np.zeros(pad, np.bool_)])
             for f, p in present.items()},
        )
        self._known = np.concatenate([self._known, np.zeros(pad, np.bool_)])

    def complete(self) -> bool:
        return self.unknown == 0

    def nbytes(self) -> int:
        values, present = self._state
        return self._known.nbytes + sum(
            values[f].nbytes + present[f].nbytes for f in self.fields)

    # -- writes --------------------------------------------------------------
    def write(self, slots: np.ndarray,
              rows: Optional[List[Optional[dict]]]) -> None:
        """The columns of the rows at `slots` (-1: an id the store does not
        hold, skipped). `rows` None: the write carried no scalar data, so
        the engine kept each id's scalars — a known slot keeps its values,
        a slot new to the store has none."""
        slots = np.asarray(slots, np.int64)
        held = slots >= 0
        if rows is None:
            self._mark_known(slots[held])
            return
        at = slots[held]
        kept = [rows[i] or {} for i in np.flatnonzero(held)]
        values, present = self._state
        for f in self.fields:
            has = np.fromiter((f in r for r in kept), np.bool_, len(kept))
            vals = [r[f] for r in kept if f in r]
            if vals:
                self._store(f, at[has], vals)
            present[f][at] = has
        self._mark_known(at)

    def _store(self, f: str, slots: np.ndarray, vals: list) -> None:
        values, present = self._state
        col = values[f]
        kind = _kind(vals)
        if col.dtype != object and kind != col.dtype.name:
            # a column takes its dtype from its first values; a value it
            # cannot hold exactly turns it into an object column
            if present[f].any():
                col = col.astype(object)
            else:
                col = np.zeros(len(col), kind)
            values[f] = col
        col[slots] = _object_array(vals) if col.dtype == object \
            else np.asarray(vals, col.dtype)

    def _mark_known(self, slots: np.ndarray) -> None:
        fresh = np.unique(slots[~self._known[slots]])
        self._known[fresh] = True
        self.unknown -= len(fresh)

    # -- reads ---------------------------------------------------------------
    def match(self, predicates: Sequence[ScalarPredicate],
              capacity: int) -> np.ndarray:
        """bool[capacity]: the slots whose columns satisfy every predicate
        (the columns are never shorter than the store's id array, which
        grows after them)."""
        if not self.complete():
            raise ColumnsUnavailable(
                f"{self.unknown} live slots have no columns yet")
        values, present = self._state
        mask = np.ones(capacity, np.bool_)
        for p in predicates:
            if p.field not in values:
                raise ColumnsUnavailable(f"field {p.field!r} is not mirrored")
            col = values[p.field][:capacity]
            has = present[p.field][:capacity]
            fast = _fast_compare(col, p)
            if fast is None:
                fast = np.zeros(capacity, np.bool_)
                idx = np.flatnonzero(has)
                fast[idx] = [p.matches({p.field: v})
                             for v in col[idx].tolist()]
            mask &= has & fast
        return mask


def _numeric_for(col: np.ndarray, v) -> bool:
    """An operand numpy compares with `col` exactly as Python would."""
    if isinstance(v, bool) or _is_int(v):
        return col.dtype == np.int64 or abs(int(v)) <= _EXACT_FLOAT_INT
    return type(v) is float and col.dtype == np.float64


def _fast_compare(col: np.ndarray, p: ScalarPredicate):
    """numpy's answer where it is Python's, else None."""
    if col.dtype == object:
        return None
    if p.op is CmpOp.IN:
        members = p.value
        if not isinstance(members, (list, tuple, set, frozenset)) or not all(
                _numeric_for(col, m) for m in members):
            return None
        return np.isin(col, np.asarray(list(members), col.dtype)) \
            if members else np.zeros(len(col), np.bool_)
    if not _numeric_for(col, p.value):
        return None
    return _NP_OPS[p.op](col, p.value)
