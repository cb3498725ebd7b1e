"""Diff-batch primitives for the incremental engine.

The unit of data flow is a *delta batch*: a list of ``(key, row, diff)``
triples at one logical timestamp, where ``key`` is a 128-bit Pointer, ``row``
a tuple of engine values and ``diff`` a signed multiplicity (reference
semantics: differential-dataflow ``Collection`` updates, see
/root/reference/src/engine/dataflow.rs).  A table state is the consolidated
sum of all batches up to the frontier: a map ``key -> row`` (every key has
multiplicity exactly one in table-land).
"""

from __future__ import annotations

import operator
import threading as _threading
import zlib as _zlib
from collections import defaultdict
from typing import Any, Iterable

Key = int  # Pointer
Row = tuple
Delta = tuple  # (key, row, diff)

import numpy as _np

from pathway_tpu.internals.api import Json as _Json


# ndarray values frozen by copying their buffer (an object dtype, or a
# layout other than C order): the misses only, a C-ordered array is keyed
# in place. The ring's ``engine.step`` span carries the count's increase
# over a step as ``array_copies`` (engine/runtime.py).
_array_copies = 0
_array_copies_lock = _threading.Lock()  # any thread may freeze a row


def array_copies() -> int:
    return _array_copies


def _count_array_copy() -> None:
    global _array_copies
    with _array_copies_lock:
        _array_copies += 1


class FrozenArray:
    """The frozen form of an ndarray value: the array itself, keyed by its
    bytes without a copy of them.

    Equal when shape, dtype and raw bytes are, as the bytes the form
    before it copied were: the same NaN bits are equal, ``-0.0`` and
    ``0.0`` are not. The hash is a crc32 over the buffer, computed on
    first use and kept. It holds a reference, so it relies on an engine
    value being immutable once it has entered the dataflow, as a ``Json``
    does. The array is C-contiguous (``freeze_value`` makes it so)."""

    __slots__ = ("array", "_hash")

    def __init__(self, array: _np.ndarray):
        self.array = array
        self._hash = None

    def __hash__(self):
        h = self._hash
        if h is None:
            h = self._hash = _zlib.crc32(self.array)
        return h

    def __eq__(self, other):
        if type(other) is not FrozenArray:
            return NotImplemented
        a, b = self.array, other.array
        if a is b:
            return True
        # met on a hash match only, as a retraction finding its row
        return (
            a.shape == b.shape
            and a.dtype.str == b.dtype.str
            and a.tobytes() == b.tobytes()
        )

    def __reduce__(self):
        return (FrozenArray, (self.array,))

    def __repr__(self):
        return f"FrozenArray({self.array!r})"


def _copied_form(v: _np.ndarray) -> tuple:
    # what an ndarray froze to before FrozenArray, and what shard digests
    # still serialise
    return ("__ndarray__", v.shape, v.dtype.str, v.tobytes())


def freeze_value(v: Any) -> Any:
    """Hashable, equality-faithful stand-in for any engine value (ndarrays,
    Json, nested tuples) — used to key multiset state so retractions cancel
    insertions exactly."""
    if isinstance(v, _np.ndarray):
        if v.dtype.hasobject:
            # its bytes are pointers: kept as the copied form they were
            _count_array_copy()
            return _copied_form(v)
        if not v.flags.c_contiguous:
            _count_array_copy()
            v = _np.ascontiguousarray(v)
        return FrozenArray(v)
    if isinstance(v, _Json):
        # hashable by construction: no probe, which would serialise it
        return v
    if isinstance(v, tuple):
        return tuple(freeze_value(x) for x in v)
    if isinstance(v, list):
        return ("__list__",) + tuple(freeze_value(x) for x in v)
    if isinstance(v, dict):
        return ("__dict__",) + tuple(
            sorted((freeze_value(k), freeze_value(x)) for k, x in v.items())
        )
    try:
        hash(v)
        return v
    except TypeError:
        return ("__repr__", repr(v))


def freeze_row(row: Row) -> tuple:
    # fast path: an already-hashable row IS its own frozen form (per-value
    # freezing only rewrites unhashable values, which would have made the
    # row unhashable too)
    try:
        hash(row)
        return row
    except TypeError:
        return tuple(freeze_value(v) for v in row)


def freeze_for_digest(v: Any) -> Any:
    """``freeze_value(v)`` as the shard digests serialise it
    (``procgroup.shard_hash``): each ``FrozenArray`` as the copied form,
    so the digests read the bytes they read before that class."""
    return _arrays_copied(freeze_value(v))


def _arrays_copied(fv: Any) -> Any:
    t = type(fv)
    if t is FrozenArray:
        return _copied_form(fv.array)
    if t is tuple:
        return tuple(map(_arrays_copied, fv))
    return fv


def _consolidate_py(deltas: Iterable[Delta]) -> list[Delta]:
    """Sum multiplicities of identical (key, row) pairs, drop zeros."""
    acc: dict[tuple, int] = {}
    rows: dict[tuple, tuple] = {}
    for key, row, diff in deltas:
        ident = (key, freeze_row(row))
        acc[ident] = acc.get(ident, 0) + diff
        rows[ident] = row
    return [
        (ident[0], rows[ident], diff) for ident, diff in acc.items() if diff != 0
    ]


class ConsolidatedList(list):
    """A delta batch already in net form (no duplicate (key, row) pairs, no
    zero diffs). consolidate() passes these through — node outputs are
    consolidated once at the producer and not re-consolidated per hop."""

    __slots__ = ()


_consolidate_impl = None
_fp_cached: Any = False
_nb_type: Any = False


def native_batch_type():
    """The pwexec.NativeBatch type (columnar zero-Python delta batch), or
    None without a toolchain. NativeBatch batches flow from the C parser
    straight into the C group-by executor; every other consumer sees a
    normal (key, row, diff) sequence via lazy materialization."""
    global _nb_type
    if _nb_type is False:
        try:
            from pathway_tpu.native import get_pwexec

            ex = get_pwexec()
            _nb_type = getattr(ex, "NativeBatch", None)
        except Exception:
            _nb_type = None
    return _nb_type


def is_native_batch(obj: Any) -> bool:
    t = native_batch_type()
    return t is not None and type(obj) is t


def get_fp():
    """The native fastpath extension module, or None without a toolchain.
    Cached after the first resolution attempt (same policy as
    consolidate's lazy binding)."""
    global _fp_cached
    if _fp_cached is False:
        try:
            from pathway_tpu.native import get_fastpath

            _fp_cached = get_fastpath()
        except Exception:
            _fp_cached = None
    return _fp_cached


def consolidate(deltas: Iterable[Delta]) -> list[Delta]:
    """Native C fast path when a toolchain exists (native/fastpath.c — the
    engine's hottest loop), else the Python implementation. Resolved
    lazily on first use so importing the package never compiles."""
    global _consolidate_impl
    if type(deltas) is ConsolidatedList:
        # fresh copy: the upstream batch object is shared by every consumer
        # (fan-out delivery), so callers that sort/mutate their view must
        # not alias siblings' data. A pointer-copy is still far cheaper
        # than re-hashing the batch.
        return ConsolidatedList(deltas)
    if is_native_batch(deltas):
        # parse output is net form by construction (distinct minted keys,
        # all +1); materialization is cached on the batch, the wrap gives
        # this consumer its own mutable view
        return ConsolidatedList(deltas.materialize())
    if _consolidate_impl is None:
        impl = _consolidate_py
        try:
            from pathway_tpu.native import get_fastpath

            fp = get_fastpath()
            if fp is not None:
                native_fn = fp.consolidate

                def impl(deltas):  # noqa: F811
                    return native_fn(
                        deltas
                        if isinstance(deltas, (list, tuple))
                        else list(deltas)
                    )
        except Exception:
            pass
        _consolidate_impl = impl
    return ConsolidatedList(_consolidate_impl(deltas))


class TableState:
    """Consolidated key->row view maintained from delta batches."""

    __slots__ = ("rows",)

    def __init__(self):
        self.rows: dict[Key, Row] = {}

    def apply(self, deltas: Iterable[Delta]) -> None:
        pending_add: dict[Key, Row] = {}
        for key, row, diff in deltas:
            if diff > 0:
                if key in self.rows and key not in pending_add:
                    # upsert arriving as (del, add) in any order within batch
                    pending_add[key] = row
                else:
                    self.rows[key] = row
            elif diff < 0:
                if key in self.rows:
                    del self.rows[key]
        for key, row in pending_add.items():
            self.rows[key] = row

    def __len__(self):
        return len(self.rows)


class MultisetState:
    """key -> Counter(row) multiset state; exact differential arrangement.

    Rows are keyed by their frozen (hashable) form but returned as original
    values, so ndarray/Json columns flow through joins and groupbys.
    """

    __slots__ = ("data",)

    def __init__(self):
        # key -> {frozen_row: [row, count]}
        self.data: dict[Key, dict[tuple, list]] = defaultdict(dict)

    def apply_one(self, key: Key, row: Row, diff: int) -> None:
        d = self.data[key]
        fr = freeze_row(row)
        entry = d.get(fr)
        if entry is None:
            entry = [row, 0]
            d[fr] = entry
        entry[1] += diff
        if entry[1] == 0:
            del d[fr]
            if not d:
                del self.data[key]

    def apply(self, deltas: Iterable[Delta]) -> None:
        for key, row, diff in deltas:
            self.apply_one(key, row, diff)

    def get(self, key: Key) -> list[tuple[Row, int]]:
        """[(row, count)] — a list, not a dict: rows may hold unhashable
        values (ndarrays); the frozen form is an internal detail."""
        return [
            (entry[0], entry[1]) for entry in self.data.get(key, {}).values()
        ]

    def items(self):
        for key, d in self.data.items():
            yield key, [(entry[0], entry[1]) for entry in d.values()]


def refreeze(state: Any) -> Any:
    """Operator state loaded from a snapshot, with each ndarray key in the
    copied form (``_copied_form``) that a tree before ``FrozenArray``
    wrote turned into a ``FrozenArray``: a row is then keyed as this
    tree's freezing keys it, and a retraction after the restore finds
    it. Lists, dicts, sets and ``MultisetState`` are rewritten in place,
    a tuple anew where it changes; anything else is returned as it is."""
    t = type(state)
    if t is tuple:
        if len(state) == 4 and type(state[0]) is str and state[0] == "__ndarray__":
            thawed = _thawed(*state[1:])
            if thawed is not None:
                return thawed
        out = tuple(map(refreeze, state))
        return state if all(map(operator.is_, out, state)) else out
    if t is MultisetState:
        refreeze(state.data)
    elif isinstance(state, list):
        state[:] = map(refreeze, state)
    elif isinstance(state, dict):
        items = [(refreeze(k), refreeze(v)) for k, v in state.items()]
        state.clear()
        state.update(items)
    elif isinstance(state, set):
        items = [refreeze(x) for x in state]
        state.clear()
        state.update(items)
    return state


def _thawed(shape: Any, dtype: Any, buf: Any) -> FrozenArray | None:
    """The ``FrozenArray`` of a copied form's parts, or None where they are
    not an ndarray's (or are an object array's, which keeps that form)."""
    if type(shape) is not tuple or type(dtype) is not str or type(buf) is not bytes:
        return None
    try:
        dt = _np.dtype(dtype)
        if dt.hasobject:
            return None
        return FrozenArray(_np.frombuffer(buf, dtype=dt).reshape(shape))
    except (TypeError, ValueError):
        return None


def negate(deltas: Iterable[Delta]) -> list[Delta]:
    return [(k, r, -d) for k, r, d in deltas]
