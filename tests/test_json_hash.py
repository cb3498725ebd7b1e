"""A ``Json`` computes its hash at most once in a process, and the kept
hash never leaves the process or the object: ``str`` hashes are salted
per process, so a hash carried in a pickle would be stale where it is
loaded, and a retraction would no longer find its row.

Run as a script (``write PATH`` / ``restore PATH``) this file is the two
halves of the cross-process case: arrangements holding hashed ``Json``
rows pickled under one ``PYTHONHASHSEED`` and emptied under another."""

import copy
import os
import pickle
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if __name__ == "__main__":
    sys.path.insert(0, REPO)

from pathway_tpu.engine import nodes as N
from pathway_tpu.engine.stream import (
    MultisetState, consolidate, freeze_row, freeze_value, negate,
)
from pathway_tpu.internals.api import Json, json_hashes

DOC = {"text": "a b c", "metadata": {"path": "/d/1.txt", "n": [1, 2.5, None]}}
# the same value, every dict's keys in the other order
DOC_REORDERED = {
    "metadata": {"n": [1, 2.5, None], "path": "/d/1.txt"}, "text": "a b c",
}
# Json(DOC) as the class with the one slot ``value`` pickled it (commit
# 8d5c6ce; protocols 2 and 4): NEWOBJ, then BUILD with (None, {"value": v})
ONE_SLOT_PICKLES = [
    b"\x80\x02cpathway_tpu.internals.api\nJson\nq\x00)\x81q\x01N}q\x02X\x05"
    b"\x00\x00\x00valueq\x03}q\x04(X\x04\x00\x00\x00textq\x05X\x05\x00\x00"
    b"\x00a b cq\x06X\x08\x00\x00\x00metadataq\x07}q\x08(X\x04\x00\x00\x00"
    b"pathq\tX\x08\x00\x00\x00/d/1.txtq\nX\x01\x00\x00\x00nq\x0b]q\x0c(K\x01"
    b"G@\x04\x00\x00\x00\x00\x00\x00Neuus\x86q\rb.",
    b"\x80\x04\x95\x80\x00\x00\x00\x00\x00\x00\x00\x8c\x19pathway_tpu."
    b"internals.api\x94\x8c\x04Json\x94\x93\x94)\x81\x94N}\x94\x8c\x05value"
    b"\x94}\x94(\x8c\x04text\x94\x8c\x05a b c\x94\x8c\x08metadata\x94}\x94("
    b"\x8c\x04path\x94\x8c\x08/d/1.txt\x94\x8c\x01n\x94]\x94(K\x01G@\x04\x00"
    b"\x00\x00\x00\x00\x00Neuus\x86\x94b.",
]


def _computed(fn):
    """(what ``fn`` returned, how many Json hashes it made the process
    compute)."""
    before = json_hashes()
    out = fn()
    return out, json_hashes() - before


def test_equal_values_hash_equal_whatever_their_key_order():
    a, b = Json(DOC), Json(DOC_REORDERED)
    assert a == b and hash(a) == hash(b)
    assert Json(DOC) == Json(DOC) and Json(DOC) == DOC
    assert hash(a) != hash(Json({**DOC, "text": "a b d"}))
    assert len({a, b, Json(DOC)}) == 1
    # the hash is the one the class always had
    import json

    assert hash(a) == hash(json.dumps(DOC, sort_keys=True, default=str))


def test_a_hash_taken_twice_serialises_once():
    j = Json(DOC)
    first, n = _computed(lambda: hash(j))
    assert n == 1
    again, n = _computed(lambda: (hash(j), {j: 1}[j], hash((j, j))))
    assert n == 0 and again[0] == first
    # a Json made from a Json is a new object with the value alone
    assert _computed(lambda: hash(Json(j)))[1] == 1


@pytest.mark.parametrize(
    "clone",
    [
        lambda j: pickle.loads(pickle.dumps(j)),
        lambda j: pickle.loads(pickle.dumps(j, protocol=2)),
        copy.copy,
        copy.deepcopy,
    ],
    ids=["pickle", "pickle-protocol-2", "copy", "deepcopy"],
)
def test_a_copy_carries_the_value_alone(clone):
    j = Json(DOC)
    kept = hash(j)
    twin = clone(j)
    assert twin is not j and type(twin) is Json
    assert twin._hash is None and j._hash == kept
    assert twin == j and twin.value == DOC
    assert _computed(lambda: hash(twin)) == (kept, 1)


def test_the_pickle_holds_no_hash():
    j = Json(DOC)
    assert pickle.dumps(j) == pickle.dumps((hash(j), j)[1])
    plain = pickle.dumps(DOC)
    # the value's own pickle and the class's name, with room for the
    # opcodes between them: no second integer rides along
    assert len(pickle.dumps(j)) <= len(plain) + 48


@pytest.mark.parametrize("blob", ONE_SLOT_PICKLES, ids=["protocol-2", "protocol-4"])
def test_a_one_slot_pickle_still_loads(blob):
    from pathway_tpu.persistence import _safe_loads

    for loads in (pickle.loads, _safe_loads):
        j = loads(blob)
        assert type(j) is Json and j.value == DOC and j._hash is None
        assert hash(j) == hash(Json(DOC)) and j == Json(DOC_REORDERED)


def test_freeze_value_of_a_json_is_the_object_and_serialises_nothing():
    j = Json(DOC)
    frozen, n = _computed(lambda: freeze_value(j))
    assert frozen is j and n == 0
    # a row made unhashable by an ndarray: its Json is kept as it is
    row = (j, "text", np.arange(3, dtype=np.float32))
    fr, n = _computed(lambda: freeze_row(row))
    assert fr[0] is j and fr[1] == "text" and fr[2][0] == "__ndarray__"
    assert n == 1  # the row's own hash reached the Json before the array
    assert _computed(lambda: freeze_row(row))[1] == 0


def test_multiset_state_hashes_a_row_once():
    ms = MultisetState()
    rows = [(7, (Json({"i": i}), np.full(2, i))) for i in range(16)]
    _, n = _computed(
        lambda: [ms.apply_one(i, row, 1) for i, row in enumerate(rows)]
    )
    assert n == 16
    _, n = _computed(
        lambda: [ms.apply_one(i, row, -1) for i, row in enumerate(rows)]
    )
    assert n == 0 and not ms.data


# -- across processes ---------------------------------------------------------


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


def _join_node():
    """An id-join of (data, vector) with (data,), as the vector store's
    ``_combined_view`` makes: both sides carry the same Json, the left an
    ndarray beside it (the Python path)."""
    scope = _Scope()
    left, right = N.SourceNode(scope), N.SourceNode(scope)
    return N.JoinNode(
        scope, left, right, lambda k, r: (k,), lambda k, r: (k,), "inner",
        left_width=2, right_width=1, id_from_left=True,
    )


def _group_node():
    """Groups by a Json column; a counted slot and a slot that reads the
    group's multiset, so the arguments are kept too."""
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


def _documents():
    return [
        Json({"text": f"document {i} " * (1 + i % 3),
              "metadata": {"path": f"/d/{i}.txt", "modified_at": i}})
        for i in range(24)
    ]


def _inputs():
    docs = _documents()
    left = [
        (i, (d, np.full(4, i, dtype=np.float32)), 1) for i, d in enumerate(docs)
    ]
    right = [(i, (Json(d.value),), 1) for i, d in enumerate(docs)]
    # four rows a group: the grouping value is a Json, an argument too
    grouped = [
        (100 + i, (Json({"topic": i % 6}), d), 1) for i, d in enumerate(docs)
    ]
    plain = [(i % 5, (d, i), 1) for i, d in enumerate(docs)]
    return left, right, grouped, plain


def _write(path):
    left, right, grouped, plain = _inputs()
    join, group, ms = _join_node(), _group_node(), MultisetState()
    emitted = {
        "join": join.process(2, [left, right]),
        "group": group.process(2, [grouped]),
    }
    ms.apply(plain)
    assert len(emitted["join"]) == 24 and len(emitted["group"]) == 6
    # every Json held went through a dict: each has its hash on it
    for _, row, _ in left + right + grouped + plain:
        assert all(v._hash is not None for v in row if isinstance(v, Json))
    assert join._jstore is None and group._store is None  # the Python path
    with open(path, "wb") as f:
        # as persistence.save_operator_snapshot writes node states
        pickle.dump(
            {
                "seed": os.environ["PYTHONHASHSEED"],
                "hash": hash(_documents()[0]),
                "nodes": [join.state_dict(), group.state_dict()],
                "multiset": ms,
                "emitted": emitted,
            },
            f,
        )


def _restore(path):
    with open(path, "rb") as f:
        snap = pickle.load(f)
    assert snap["seed"] != os.environ["PYTHONHASHSEED"]
    assert snap["hash"] != hash(_documents()[0])  # another salt
    left, right, grouped, plain = _inputs()  # fresh objects, equal values
    join, group = _join_node(), _group_node()
    join.load_state(snap["nodes"][0])
    group.load_state(snap["nodes"][1])
    assert len(join.left.data) == 24 and len(group.groups) == 6
    ms = snap["multiset"]
    assert sum(c for _, rows in ms.items() for _, c in rows) == 24

    out = join.process(4, [negate(left), negate(right)])
    assert not join.left.data and not join.right.data
    assert consolidate(snap["emitted"]["join"] + out) == []
    out = group.process(4, [negate(grouped)])
    assert not group.groups
    assert consolidate(snap["emitted"]["group"] + out) == []
    ms.apply(negate(plain))
    assert not ms.data


def test_arrangements_pickled_under_one_hash_seed_empty_under_another(tmp_path):
    path = str(tmp_path / "snapshot.pickle")
    for mode, seed in (("write", "1"), ("restore", "2")):
        done = subprocess.run(
            [sys.executable, os.path.abspath(__file__), mode, path],
            env={**os.environ, "PYTHONHASHSEED": seed, "JAX_PLATFORMS": "cpu"},
            capture_output=True, text=True, timeout=120,
        )
        assert done.returncode == 0, (mode, done.stderr[-2000:])


if __name__ == "__main__":
    {"write": _write, "restore": _restore}[sys.argv[1]](sys.argv[2])
