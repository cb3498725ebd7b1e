"""An ndarray value is keyed in the engine's multiset and consolidation
state by a ``FrozenArray``: the array itself, hashed over its buffer,
equal by shape, dtype and raw bytes. No copy of the bytes is made for a
C-ordered array of a plain dtype; ``array_copies`` counts the freezes
that still copy (an object dtype, or another layout)."""

import pickle

import numpy as np
import pytest

import pathway_tpu as pw
from pathway_tpu.engine import nodes as N
from pathway_tpu.engine import time_gate as TG
from pathway_tpu.engine.stream import (
    FrozenArray, MultisetState, _consolidate_py, array_copies, consolidate,
    freeze_for_digest, freeze_row, freeze_value, get_fp, refreeze,
)
from pathway_tpu.parallel.procgroup import shard_hash, stable_shard, stable_shard_many
from pathway_tpu.persistence import Backend, Config, PersistenceManager, _safe_loads

ARRAYS = {
    "f32": np.array([0.5, -0.0, np.nan, 3.0], dtype=np.float32),
    "f64-2d": np.array([[1.5, -0.0], [np.nan, 2.0]], dtype=np.float64),
    "i64": np.arange(6, dtype=np.int64),
    "bool": np.array([True, False, True]),
    "u8-3d": np.arange(24, dtype=np.uint8).reshape(2, 3, 4),
    "big-endian": np.arange(3, dtype=">i4"),
    "datetime": np.array(["2020-01-01", "2021-06-30"], dtype="M8[D]"),
    "str": np.array(["ab", "c"]),
    "record": np.zeros(2, dtype=[("a", "<i4"), ("b", "<f8")]),
    "empty": np.zeros((0, 4), dtype=np.float32),
    "0-d": np.array(7.25),
}


def _copies(fn):
    before = array_copies()
    out = fn()
    return out, array_copies() - before


# -- equality is byte equality --------------------------------------------------


@pytest.mark.parametrize("name", sorted(ARRAYS))
def test_a_distinct_equal_array_freezes_equal_without_a_copy(name):
    a = ARRAYS[name]
    (fa, fb), n = _copies(lambda: (freeze_value(a), freeze_value(a.copy())))
    assert n == 0
    assert type(fa) is FrozenArray and fa.array is a
    assert fa == fb and hash(fa) == hash(fb) and not fa != fb


def test_nan_bits_are_equal_and_signed_zeros_are_not():
    nan = np.array([np.nan], dtype=np.float32)
    assert freeze_value(nan) == freeze_value(nan.copy())
    other_nan = nan.view(np.uint32) | np.uint32(1)  # another NaN payload
    assert np.isnan(other_nan.view(np.float32)).all()
    assert freeze_value(nan) != freeze_value(other_nan.view(np.float32))
    zero = np.zeros(2, dtype=np.float32)
    assert freeze_value(zero) != freeze_value(-zero)
    assert np.array_equal(zero, -zero)  # value-equal, not byte-equal


@pytest.mark.parametrize(
    "a, b",
    [
        (np.arange(4, dtype=np.int32), np.arange(4, dtype=np.uint32)),
        (np.arange(4, dtype=np.int32), np.arange(4, dtype=np.int32).reshape(2, 2)),
        (np.zeros(2, dtype=np.float64), np.zeros(4, dtype=np.float32)),
        (np.arange(4, dtype="<i4"), np.arange(4, dtype=">i4")),
    ],
    ids=["dtype", "shape", "same-bytes", "byte-order"],
)
def test_shape_and_dtype_part_the_frozen_forms(a, b):
    assert freeze_value(a) != freeze_value(b)


def test_a_frozen_array_never_equals_another_type():
    fa = freeze_value(np.arange(3))
    assert fa != freeze_for_digest(np.arange(3)) and fa != 3 and fa != "x"
    assert fa.__eq__(np.arange(3)) is NotImplemented


# -- a retraction carrying an equal array cancels its insertion ----------------


def _row(v):
    return ("doc", v)


def test_multiset_state_cancels_on_an_equal_array():
    ms = MultisetState()
    a = ARRAYS["f32"]
    ms.apply_one(7, _row(a), 1)
    ms.apply_one(7, _row(a.copy()), 1)
    ((held, count),) = ms.get(7)
    assert held[1] is a and count == 2
    ms.apply_one(7, _row(a.copy()), -2)
    assert not ms.data


@pytest.mark.parametrize("impl", ["native", "python"])
def test_consolidate_cancels_on_an_equal_array(impl):
    fn = _consolidate_py
    if impl == "native":
        fp = get_fp()
        if fp is None:
            pytest.skip("no native toolchain")
        fn = fp.consolidate
    a, b = ARRAYS["f64-2d"], ARRAYS["i64"]
    out = fn([(1, _row(a), 1), (2, _row(b), 1), (1, _row(a.copy()), -1)])
    assert len(out) == 1 and out[0][0] == 2 and out[0][1][1] is b
    # same bytes under two keys stay two rows
    assert len(fn([(1, _row(a), 1), (2, _row(a.copy()), 1)])) == 2
    # and a signed zero is another row
    z = np.zeros(2)
    assert len(fn([(1, _row(z), 1), (1, _row(-z), -1)])) == 2


def test_a_join_carrying_vectors_retracts_a_row_by_an_equal_array():
    """A content removal from the Python connector carries a fresh array
    equal to the inserted one; the join (on its Python path, as an
    ndarray demotes it) cancels the row it arranged."""
    vec = lambda i: np.array([i, -0.0, np.nan], dtype=np.float32)  # noqa: E731

    class Subject(pw.io.python.ConnectorSubject):
        def run(self):
            for i in range(3):
                self.next(k=i, vec=vec(i))
            self.commit()
            self.remove(k=1, vec=vec(1))
            self.commit()

    class Schema(pw.Schema):
        k: int
        vec: np.ndarray

    docs = pw.io.python.read(Subject(), schema=Schema)
    labels = pw.debug.table_from_markdown(
        """
        k | label
        0 | zero
        1 | one
        2 | two
        """
    )
    joined = docs.join(labels, docs.k == labels.k).select(
        docs.k, docs.vec, labels.label
    )
    events, live = [], {}

    def on_change(key, row, time, is_addition):
        events.append((row["k"], is_addition))
        if is_addition:
            live[key] = row
        else:
            del live[key]

    pw.io.subscribe(joined, on_change=on_change)
    pw.run()
    assert sorted(events) == [(0, True), (1, False), (1, True), (2, True)]
    assert sorted(r["label"] for r in live.values()) == ["two", "zero"]
    for r in live.values():
        assert r["vec"].tobytes() == vec(r["k"]).tobytes()


# -- what still copies ------------------------------------------------------------


def test_contiguous_float32_rows_copy_nothing():
    rows = [(i, np.full(768, i, dtype=np.float32)) for i in range(64)]
    ms = MultisetState()
    _, n = _copies(lambda: [ms.apply_one(i, r, 1) for i, r in enumerate(rows)])
    assert n == 0
    _, n = _copies(lambda: consolidate([(i, r, -1) for i, r in enumerate(rows)]))
    assert n == 0
    # views of a fresh batch's rows, as an encoder hands them out
    batch = np.ones((8, 384), dtype=np.float32)
    _, n = _copies(lambda: [freeze_row((i, batch[i])) for i in range(8)])
    assert n == 0


def test_a_strided_array_is_copied_once_and_equals_its_contiguous_twin():
    base = np.arange(12, dtype=np.float32).reshape(3, 4)
    for strided in (base[:, ::2], base.T):
        assert not strided.flags.c_contiguous
        fs, n = _copies(lambda: freeze_value(strided))
        assert n == 1 and type(fs) is FrozenArray
        assert fs.array.flags.c_contiguous
        twin = np.ascontiguousarray(strided)
        assert fs == freeze_value(twin) and hash(fs) == hash(freeze_value(twin))
    ms = MultisetState()
    ms.apply_one(1, (base[:, ::2],), 1)
    ms.apply_one(1, (np.ascontiguousarray(base[:, ::2]),), -1)
    assert not ms.data


def test_an_object_array_keeps_the_copied_form():
    a = np.array(["x", 1, None], dtype=object)
    fa, n = _copies(lambda: freeze_value(a))
    assert n == 1
    assert fa == ("__ndarray__", (3,), "|O", a.tobytes())
    assert freeze_value(a) == fa  # the same objects: the same pointers


def test_a_zero_d_array_is_keyed_in_place():
    a = ARRAYS["0-d"]
    fa, n = _copies(lambda: freeze_value(a))
    assert n == 0 and type(fa) is FrozenArray and fa.array.shape == ()
    assert fa == freeze_value(np.array(7.25))
    assert fa != freeze_value(np.array([7.25]))


# -- sharding reads the bytes it always read -----------------------------------

# (shard_hash, stable_shard(v, 8), stable_shard_many([v], 5)) as the tree
# that froze an ndarray to ("__ndarray__", shape, dtype, bytes) read them
SHARD_GOLDEN = [
    (np.arange(4, dtype=np.float32), 0x15E76414D3AE1C78, 0, 0),
    (np.array([[1.5, -0.0], [np.nan, 2.0]]), 0x2FCC65818DBD1F6F, 7, 3),
    (np.arange(6, dtype=np.int64).reshape(2, 3)[:, ::2], 0xEA3A6FF1B607F3F6, 6, 2),
    (np.array(7, dtype=np.int32), 0x4C173970F356375C, 4, 4),
    (("doc", np.linspace(0, 1, 5, dtype=np.float32)), 0x8D1916D8C3213847, 7, 4),
]


@pytest.mark.parametrize("i", range(len(SHARD_GOLDEN)))
def test_shard_digests_of_ndarray_values_are_unchanged(i):
    value, digest, of8, of5 = SHARD_GOLDEN[i]
    assert shard_hash(value) == digest
    assert shard_hash(freeze_value(value)) == digest
    assert shard_hash(_as_copied_form(freeze_value(value))) == digest
    assert stable_shard(value, 8) == of8
    assert stable_shard_many([value], 5) == [of5]


# -- snapshots ----------------------------------------------------------------------


def test_a_frozen_array_pickles_as_its_array_and_loads_safely():
    a = ARRAYS["f64-2d"]
    fa = freeze_value(a)
    hash(fa)
    blob = pickle.dumps(fa)
    back = _safe_loads(blob)
    assert type(back) is FrozenArray and back._hash is None
    assert back == fa and hash(back) == hash(fa)
    assert back.array.tobytes() == a.tobytes()
    # a pickle of the array alone, and the class's name
    assert len(blob) <= len(pickle.dumps(a)) + 64


def test_a_multiset_state_round_trips_and_still_cancels():
    ms = MultisetState()
    ms.apply_one(3, _row(ARRAYS["f32"]), 1)
    back = pickle.loads(pickle.dumps(ms))
    back.apply_one(3, _row(ARRAYS["f32"].copy()), -1)
    assert not back.data


# MultisetState holding (1, float32 [0.5, -0.0, nan]) under key 7, as the
# tree that keyed it by ("__ndarray__", (3,), "<f4", bytes) pickled it
# (protocol 4, as persistence.save_operator_snapshot writes)
COPIED_FORM_PICKLE = bytes.fromhex(
    "80049548010000000000008c19706174687761795f7470752e656e67696e652e"
    "73747265616d948c0d4d756c746973657453746174659493942981944e7d948c"
    "0464617461948c0b636f6c6c656374696f6e73948c0b64656661756c74646963"
    "749493948c086275696c74696e73948c0464696374949394859452944b077d94"
    "4b01288c0b5f5f6e6461727261795f5f944b0385948c033c663494430c000000"
    "3f000000800000c07f94749486945d94284b018c166e756d70792e5f636f7265"
    "2e6d756c74696172726179948c0c5f7265636f6e7374727563749493948c056e"
    "756d7079948c076e6461727261799493944b0085944301629487945294284b01"
    "4b03859468198c0564747970659493948c02663494898887945294284b038c01"
    "3c944e4e4e4affffffff4affffffff4b0074946289430c0000003f0000008000"
    "00c07f9474946286944b01657373738694622e"
)


def _as_copied_form(frozen):
    """A frozen value as the tree that froze an ndarray to
    ("__ndarray__", shape, dtype, bytes) made it."""
    if isinstance(frozen, FrozenArray):
        a = frozen.array
        return ("__ndarray__", a.shape, a.dtype.str, a.tobytes())
    if isinstance(frozen, tuple):
        return tuple(_as_copied_form(x) for x in frozen)
    return frozen


def _restored(node_states):
    """``node_states`` written as an operator snapshot and read back."""
    pm = PersistenceManager(Config(Backend.memory()))
    pm.save_operator_snapshot(node_states, {"subject": 1}, ["fingerprint"])
    states, subjects, fingerprint = pm.load_operator_snapshot()
    assert subjects == {"subject": 1} and fingerprint == ["fingerprint"]
    return states


def test_a_state_pickled_in_the_copied_form_cancels_after_loading():
    (ms,) = _restored([pickle.loads(COPIED_FORM_PICKLE)])
    (frozen,) = ms.data[7]
    assert frozen[0] == 1 and type(frozen[1]) is FrozenArray
    row = (1, np.array([0.5, -0.0, np.nan], dtype=np.float32))
    ms.apply_one(7, row, -1)
    assert not ms.data


@pytest.mark.parametrize("name", sorted(ARRAYS))
def test_refreeze_turns_a_copied_form_into_the_frozen_array(name):
    a = ARRAYS[name]
    row = ("doc", a)
    state = {
        (3, _as_copied_form(freeze_row(row))): [row, [_as_copied_form(freeze_value(a))]]
    }
    assert refreeze(state) is state
    ((key, [held, [fa]]),) = state.items()
    assert key == (3, freeze_row(row)) and hash(key) == hash((3, freeze_row(row)))
    assert held is row and type(fa) is FrozenArray and fa == freeze_value(a)


def test_refreeze_leaves_what_is_no_ndarray_copied_form():
    obj = np.array(["x", 1], dtype=object)
    kept = [
        freeze_value(obj),  # an object array keeps the copied form
        ("__ndarray__", (3,), "<f4", b"\x00" * 4),  # bytes short of the shape
        ("__ndarray__", (1,), "no-dtype", b"\x00"),
        ("__ndarray__", [1], "<f4", b"\x00" * 4),
        ("doc", np.arange(3), ("a", 1)),
    ]
    for value in kept:
        assert refreeze(value) is value
    row = ("doc", np.arange(3))
    ms = MultisetState()
    ms.apply_one(1, row, 1)
    slots = ms.data[1]
    assert refreeze(ms) is ms and ms.data[1] is slots and ms.get(1) == [(row, 1)]


class _Runtime:
    current_trace = None

    def mark_pending(self, time, node):
        pass


class _Scope:
    def __init__(self):
        self.runtime = _Runtime()
        self.nodes = []

    def register(self, node):
        self.nodes.append(node)
        return len(self.nodes) - 1


def _group_node():
    """Groups by an ndarray column; a counted slot and a slot that reads
    the group's multiset of arguments, which hold an ndarray too."""
    scope = _Scope()
    specs = [
        ("abelian", lambda s, c, d: s + d, lambda s: s, 0),
        ("full", lambda entries, i: sum(e[1] for e in entries)),
    ]
    return N.GroupByNode(
        scope, N.SourceNode(scope),
        grouping_fn=lambda k, r: (r[0],),
        args_fn=lambda k, r: ((k,), (r[1], k)),
        reducer_specs=specs,
    )


def test_a_group_state_in_the_copied_form_cancels_after_loading():
    rows = [
        (i, (np.full(2, i % 2, dtype=np.float32), np.arange(3) + i), 1)
        for i in range(6)
    ]
    writer = _group_node()
    assert len(writer.process(1, [rows])) == 2
    state = pickle.loads(pickle.dumps(writer.state_dict()))
    # every key as the tree before FrozenArray made it
    state["groups"] = {
        _as_copied_form(g): entry[:1]
        + [{_as_copied_form(a): s for a, s in entry[1].items()}]
        + entry[2:]
        for g, entry in state["groups"].items()
    }
    reader = _group_node()
    reader.load_state(*_restored([state]))
    copies = [(k, (r[0].copy(), r[1].copy()), -1) for k, r, _ in rows]
    out = reader.process(2, [copies])
    assert sorted(d for _, _, d in out) == [-1, -1]
    assert not reader.groups


def _copied_keys(slots):
    return {_as_copied_form(fr): slot for fr, slot in slots.items()}


def test_a_buffer_state_in_the_copied_form_cancels_a_retraction_after_loading():
    """A row still held for its watermark, restored from a snapshot that
    keyed it by the copied form: its retraction before the watermark
    cancels it in the stash, so neither passes the buffer."""
    gate = lambda k, r: (r[0] + 10, r[0])  # noqa: E731 (threshold, event time)
    row = (0, np.array([0.5, -0.0, np.nan], dtype=np.float32))
    writer = TG.BufferNode(_Scope(), N.SourceNode(_Scope()), gate)
    assert writer.process(1, [[(1, row, 1)]]) == []
    state = pickle.loads(pickle.dumps(writer.state_dict()))
    state["stash"] = {(k, _as_copied_form(fr)): s for (k, fr), s in state["stash"].items()}
    scope = _Scope()
    reader = TG.BufferNode(scope, N.SourceNode(scope), gate)
    reader.load_state(*_restored([state]))
    assert reader.process(2, [[(1, (0, row[1].copy()), -1)]]) == []
    assert not reader.stash
    # the watermark passes the row's threshold: nothing is released
    other = (20, np.zeros(3, dtype=np.float32))
    assert reader.process(3, [[(2, other, 1)]]) == []
    assert list(reader.stash.values()) == [[2, other, 1, 30]]


def test_a_reindex_state_in_the_copied_form_cancels_after_loading():
    row = ("a", np.arange(4, dtype=np.float32))
    scope = _Scope()
    writer = N.CheckedReindexNode(scope, N.SourceNode(scope), lambda k, r: r[0], 2)
    assert writer.process(1, [[(1, row, 1)]]) == [("a", row, 1)]
    state = pickle.loads(pickle.dumps(writer.state_dict()))
    state["groups"] = {nk: _copied_keys(slots) for nk, slots in state["groups"].items()}
    scope = _Scope()
    reader = N.CheckedReindexNode(scope, N.SourceNode(scope), lambda k, r: r[0], 2)
    reader.load_state(*_restored([state]))
    out = reader.process(2, [[(1, (row[0], row[1].copy()), -1)]])
    assert [(k, d) for k, _, d in out] == [("a", -1)]
    assert not reader.groups  # no +1 / -1 pair of slots left behind


def test_a_memo_in_the_copied_form_replays_its_output_after_loading():
    """A memoized rowwise map replays the output it memoized for a row
    whose memo a snapshot keyed by the copied form: the function, which
    answers anew each call, is not asked again."""
    calls = []

    def batch_fn(keys, rows):
        calls.append(len(rows))
        return [(len(calls),) for _ in rows]

    row = (np.arange(3, dtype=np.int64),)
    scope = _Scope()
    writer = N.MemoizedRowwiseNode(scope, N.SourceNode(scope), batch_fn)
    assert writer.process(1, [[(1, row, 1)]]) == [(1, (1,), 1)]
    state = pickle.loads(pickle.dumps(writer.state_dict()))
    state["_memo"] = {k: (_as_copied_form(fr), out) for k, (fr, out) in state["_memo"].items()}
    scope = _Scope()
    reader = N.MemoizedRowwiseNode(scope, N.SourceNode(scope), batch_fn)
    reader.load_state(*_restored([state]))
    assert reader.process(2, [[(1, (row[0].copy(),), -1)]]) == [(1, (1,), -1)]
    assert calls == [1] and not reader._memo


def test_span_clock_check_reads_array_copies_a_step(monkeypatch):
    """``scripts/span_clock_check.py`` prints ``array_copies`` a commit
    beside ``json_hashes``, and None from steps that do not say."""
    import importlib.util
    import os
    import sys

    monkeypatch.setattr(sys, "path", list(sys.path))  # the script adds to it
    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "scripts", "span_clock_check.py",
    )
    spec = importlib.util.spec_from_file_location("span_clock_check", path)
    script = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(script)

    def commit(t, *args):
        step = (100 + t, "engine.step", 0, 1_000_000, 0, None, t, "t", t, *args)
        nodes = [
            (1000 * t + i, "engine.node", 0, 50_000, 0, 100 + t, t, "label", f"N#{i}")
            for i in range(10)
        ]
        return [step, *nodes]

    ring = commit(1, "json_hashes", 2, "array_copies", 0) + commit(
        2, "json_hashes", 4, "array_copies", 6
    )
    out = script.commits(ring)
    assert out["steps"] == 2
    assert out["array_copies"] == {"min": 0, "median": 3, "max": 6}
    assert out["json_hashes"] == {"min": 2, "median": 3, "max": 4}
    assert script.commits(commit(1, "json_hashes", 2))["array_copies"] is None
