"""Indexing stack tests (reference pattern:
python/pathway/tests/test_knn.py + external_index/ tests — static tables,
deterministic embedder, compare against oracle)."""

import numpy as np
import pytest

import pathway_tpu as pw
from pathway_tpu.internals.graph_runner import GraphRunner
from pathway_tpu.stdlib.indexing import (
    BruteForceKnn,
    DataIndex,
    HybridIndex,
    TantivyBM25,
    _SCORE,
)


def _run(table):
    captures = GraphRunner().run_tables(table)
    return list(captures[0].state.rows.values())


def _docs_table():
    return pw.debug.table_from_markdown(
        """
        doc     | vec
        apple   | 1.0,0.0,0.0
        banana  | 0.9,0.1,0.0
        carrot  | 0.0,1.0,0.0
        dill    | 0.0,0.0,1.0
        """
    ).select(
        pw.this.doc,
        vec=pw.apply_with_type(
            lambda s: tuple(float(x) for x in s.split(",")), tuple, pw.this.vec
        ),
    )


def _queries_table():
    return pw.debug.table_from_markdown(
        """
        qid | qvec
        q1  | 1.0,0.05,0.0
        q2  | 0.0,0.9,0.2
        """
    ).select(
        pw.this.qid,
        qvec=pw.apply_with_type(
            lambda s: tuple(float(x) for x in s.split(",")), tuple, pw.this.qvec
        ),
    )


def test_brute_force_knn_inner_index():
    docs = _docs_table()
    queries = _queries_table()
    index = BruteForceKnn(data_column=docs.vec, dimensions=3, metric="cos")
    res = index.query(queries.qvec, number_of_matches=2)
    rows = _run(res.select(pw.this.qid, ids=pw.this._pw_index_reply))
    by_q = {r[0]: r[1] for r in rows}
    assert len(by_q["q1"]) == 2 and len(by_q["q2"]) == 2
    # q1 nearest = apple then banana; scores descending
    assert by_q["q1"][0][1] >= by_q["q1"][1][1]


def test_data_index_collapsed_rows():
    docs = _docs_table()
    queries = _queries_table()
    index = DataIndex(
        docs, BruteForceKnn(data_column=docs.vec, dimensions=3, metric="cos")
    )
    res = index.query(queries.qvec, number_of_matches=2, collapse_rows=True)
    rows = _run(res.select(pw.this.qid, pw.this.doc, res[_SCORE]))
    by_q = {r[0]: r for r in rows}
    assert by_q["q1"][1][0] == "apple"  # best match first
    assert by_q["q1"][2][0] >= by_q["q1"][2][1]  # scores sorted desc
    assert by_q["q2"][1][0] == "carrot"


def test_data_index_flat_rows():
    docs = _docs_table()
    queries = _queries_table()
    index = DataIndex(
        docs, BruteForceKnn(data_column=docs.vec, dimensions=3, metric="cos")
    )
    res = index.query(queries.qvec, number_of_matches=2, collapse_rows=False)
    rows = _run(res.select(pw.this.qid, pw.this.doc))
    assert len(rows) == 4  # 2 queries x 2 matches
    assert ("q1", "apple") in rows and ("q2", "carrot") in rows


def test_bm25_index():
    docs = pw.debug.table_from_markdown(
        """
        text
        the quick brown fox jumps
        a lazy dog sleeps all day
        the dog chases the fox
        """
    )
    queries = pw.debug.table_from_markdown(
        """
        q
        fox
        lazy dog
        """
    )
    index = TantivyBM25(data_column=docs.text)
    res = index.query(queries.q, number_of_matches=2)
    rows = _run(res.select(pw.this.q, reply=pw.this._pw_index_reply))
    by_q = {r[0]: r[1] for r in rows}
    assert len(by_q["fox"]) == 2
    assert len(by_q["lazy dog"]) >= 1
    assert by_q["lazy dog"][0][1] > 0


def test_metadata_filter():
    docs = _docs_table().with_columns(
        meta=pw.apply_with_type(
            lambda d: pw.Json({"kind": "fruit" if d in ("apple", "banana") else "veg"}),
            pw.Json,
            pw.this.doc,
        )
    )
    queries = _queries_table().with_columns(
        filt=pw.apply_with_type(lambda q: "kind == 'veg'", str, pw.this.qid)
    )
    index = BruteForceKnn(
        data_column=docs.vec, metadata_column=docs.meta, dimensions=3, metric="cos"
    )
    res = index.query(queries.qvec, number_of_matches=2, metadata_filter=queries.filt)
    rows = _run(res.select(pw.this.qid, reply=pw.this._pw_index_reply))
    docs_rows = _run(docs.select(pw.this.doc))
    # all matched ids must be veg docs (carrot/dill)
    docs_by_key = {
        k: row[0] for k, row in GraphRunner().run_tables(_docs_table())[0].state.rows.items()
    }
    for qid, reply in rows:
        for doc_id, score in reply:
            assert docs_by_key[doc_id] in ("carrot", "dill")


def test_hybrid_index_rrf():
    docs = _docs_table()
    queries = _queries_table()
    knn1 = BruteForceKnn(data_column=docs.vec, dimensions=3, metric="cos")
    knn2 = BruteForceKnn(data_column=docs.vec, dimensions=3, metric="l2sq")
    hybrid = HybridIndex(
        data_column=docs.vec, retrievers=(knn1, knn2)
    )
    res = hybrid.query(queries.qvec, number_of_matches=2)
    rows = _run(res.select(pw.this.qid, reply=pw.this._pw_index_reply))
    by_q = {r[0]: r[1] for r in rows}
    assert len(by_q["q1"]) == 2
    # RRF score of a doc ranked 1st by both indexes: 2/(60+1)
    assert abs(by_q["q1"][0][1] - 2 / 61) < 1e-9


def test_index_as_of_now_streaming():
    """as-of-now: queries see the index as of their arrival; answers are not
    revised by later index updates (reference: external_index.rs:112)."""
    import threading

    class Docs(pw.io.python.ConnectorSubject):
        def __init__(self, gate):
            super().__init__()
            self.gate = gate

        def run(self):
            self.next(name="d1", vec="1.0,0.0")
            self.commit()
            self.gate.wait(timeout=5)
            self.next(name="d2", vec="0.0,1.0")
            self.commit()

    class Queries(pw.io.python.ConnectorSubject):
        def __init__(self, gate):
            super().__init__()
            self.gate = gate

        def run(self):
            import time

            time.sleep(0.3)
            self.next(qid="q1", qvec="0.0,1.0")
            self.commit()
            import time as t2

            t2.sleep(0.3)
            self.gate.set()

    class DS(pw.Schema):
        name: str = pw.column_definition(primary_key=True)
        vec: str

    class QS(pw.Schema):
        qid: str = pw.column_definition(primary_key=True)
        qvec: str

    gate = threading.Event()
    docs = pw.io.python.read(Docs(gate), schema=DS, autocommit_duration_ms=None)
    queries = pw.io.python.read(Queries(gate), schema=QS, autocommit_duration_ms=None)

    parse = pw.udf(
        lambda s: tuple(float(x) for x in s.split(",")),
        return_type=tuple,
        deterministic=True,
    )
    docs = docs.select(pw.this.name, vec=parse(pw.this.vec))
    queries = queries.select(pw.this.qid, qvec=parse(pw.this.qvec))

    index = BruteForceKnn(data_column=docs.vec, dimensions=2, metric="cos")
    res = index.query_as_of_now(queries.qvec, number_of_matches=1)
    events = []
    pw.io.subscribe(
        res,
        on_change=lambda key, row, time, is_addition: events.append(
            (row["qid"], row["_pw_index_reply"], is_addition)
        ),
    )
    pw.run()
    # q1 (asking for [0,1]) arrived when only d1 existed -> answered with d1
    # and NEVER revised even though d2 (a better match) arrived later
    additions = [e for e in events if e[2]]
    assert len(additions) == 1
    retractions = [e for e in events if not e[2]]
    assert not retractions


@pytest.mark.parametrize("collapse_rows", [True, False])
def test_data_index_as_of_now_answers_each_query_once(collapse_rows):
    """A ``DataIndex`` query as of now gives one answer per query and never
    revises it: the matches are forgotten at the next timestamp while the
    query row lives on, and a plain left join of the two would then revise
    every answer to "no matches" (a second row through everything
    downstream of ``/v1/retrieve``, a second prompt for a chat)."""
    import threading
    import time

    class Docs(pw.io.python.ConnectorSubject):
        def __init__(self, gate):
            super().__init__()
            self.gate = gate

        def run(self):
            self.next(name="d1", vec="1.0,0.0")
            self.next(name="d2", vec="0.0,1.0")
            self.commit()
            self.gate.wait(timeout=5)
            self.next(name="d3", vec="0.6,0.8")     # a later timestamp
            self.commit()

    class Queries(pw.io.python.ConnectorSubject):
        def __init__(self, gate):
            super().__init__()
            self.gate = gate

        def run(self):
            time.sleep(0.3)
            self.next(qid="q1", qvec="0.0,1.0")
            self.next(qid="q2", qvec="1.0,0.1")
            self.commit()
            time.sleep(0.3)
            self.gate.set()

    class DS(pw.Schema):
        name: str = pw.column_definition(primary_key=True)
        vec: str

    class QS(pw.Schema):
        qid: str = pw.column_definition(primary_key=True)
        qvec: str

    gate = threading.Event()
    docs = pw.io.python.read(Docs(gate), schema=DS, autocommit_duration_ms=None)
    queries = pw.io.python.read(Queries(gate), schema=QS, autocommit_duration_ms=None)
    parse = pw.udf(
        lambda s: tuple(float(x) for x in s.split(",")), return_type=tuple, deterministic=True)
    docs = docs.select(pw.this.name, vec=parse(pw.this.vec))
    queries = queries.select(pw.this.qid, qvec=parse(pw.this.qvec))

    index = DataIndex(docs, BruteForceKnn(data_column=docs.vec, dimensions=2, metric="cos"))
    res = index.query_as_of_now(
        queries.qvec, number_of_matches=1, collapse_rows=collapse_rows)
    events = []
    pw.io.subscribe(
        res,
        on_change=lambda key, row, time, is_addition: events.append(
            (row["qid"], row["name"], is_addition)),
    )
    pw.run()
    first = ("d2",) if collapse_rows else "d2"
    second = ("d1",) if collapse_rows else "d1"
    assert sorted(events, key=repr) == [("q1", first, True), ("q2", second, True)]
