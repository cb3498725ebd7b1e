"""VectorStoreServer tests (reference pattern:
python/pathway/xpacks/llm/tests/test_vector_store.py — fake deterministic
embedder, exercise retrieve/statistics/inputs in-thread)."""

import fnmatch
import queue
import socket
import threading
import time

import pytest

import pathway_tpu as pw
from pathway_tpu.internals.graph_runner import GraphRunner
from pathway_tpu.xpacks.llm.mocks import DeterministicMockEmbedder
from pathway_tpu.xpacks.llm.splitters import TokenCountSplitter
from pathway_tpu.xpacks.llm.vector_store import (
    VectorStoreClient,
    VectorStoreServer,
)


def _rows(table):
    captures = GraphRunner().run_tables(table)
    return list(captures[0].state.rows.values())


def _answered(table):
    """First insertion per key — matches serving semantics: the response
    writer resolves a query's future on its FIRST answer; the as-of-now
    retraction at the next timestamp never reaches the client."""
    captures = GraphRunner().run_tables(table)
    seen = set()
    out = []
    for key, row, _, d in captures[0].updates:
        if d > 0 and key not in seen:
            seen.add(key)
            out.append(row)
    return out


def _docs_source():
    import json

    t = pw.debug.table_from_markdown(
        """
        data                          | meta
        the cat sat on the mat        | a.txt
        dogs are loyal friendly pets  | b.txt
        """
    )
    return t.select(
        data=pw.this.data,
        _metadata=pw.apply_with_type(
            lambda p: pw.Json(
                {"path": p, "modified_at": 1, "seen_at": 2}
            ),
            pw.Json,
            pw.this.meta,
        ),
    )


def _server():
    return VectorStoreServer(
        _docs_source(), embedder=DeterministicMockEmbedder(dimension=12)
    )


def test_retrieve_query():
    server = _server()
    queries = pw.debug.table_from_markdown(
        """
        query | k
        the cat sat on the mat | 1
        """,
        schema=VectorStoreServer.RetrieveQuerySchema,
    )
    res = server.retrieve_query(queries)
    rows = _answered(res)
    assert len(rows) == 1
    results = rows[0][0].value
    assert len(results) == 1
    assert results[0]["text"] == "the cat sat on the mat"
    assert results[0]["dist"] < 1e-5  # identical text -> distance ~0


def test_statistics_query():
    server = _server()
    queries = pw.debug.table_from_markdown(
        """
        dummy
        1
        """
    ).select()
    res = server.statistics_query(queries)
    rows = _rows(res)
    stats = rows[0][0].value
    assert stats["file_count"] == 2
    assert stats["last_modified"] == 1
    assert stats["last_indexed"] == 2


def test_inputs_query_with_glob():
    server = _server()
    queries = pw.debug.table_from_markdown(
        """
        q
        1
        """
    ).select(
        metadata_filter=pw.apply_with_type(lambda q: None, str, pw.this.q),
        filepath_globpattern=pw.apply_with_type(lambda q: "a*", str, pw.this.q),
    )
    res = server.inputs_query(queries)
    rows = _rows(res)
    metas = rows[0][0].value
    assert len(metas) == 1
    assert metas[0]["path"] == "a.txt"


def test_retrieve_with_metadata_filter():
    server = _server()
    queries = pw.debug.table_from_markdown(
        """
        query | k
        pets | 5
        """,
        schema=VectorStoreServer.RetrieveQuerySchema,
    ).with_columns(filepath_globpattern="b*")
    res = server.retrieve_query(queries)
    rows = _answered(res)
    results = rows[0][0].value
    assert len(results) == 1
    assert "dogs" in results[0]["text"]


def test_splitter_in_pipeline():
    splitter = TokenCountSplitter(min_tokens=2, max_tokens=4)
    server = VectorStoreServer(
        _docs_source(),
        embedder=DeterministicMockEmbedder(dimension=8),
        splitter=splitter.func,
    )
    chunked = server._graph["chunked_docs"]
    rows = _rows(chunked.select(pw.this.text))
    assert len(rows) > 2  # docs got split into multiple chunks


# -- /v1/inputs and /v1/statistics against plain Python, through churn --------

# (commit time, key, path, modified_at, owner, diff): inserts, a metadata
# update, a second document with a path already there, deletions, and
# back to an empty corpus. Time 2 has no document at all.
_CHURN = [
    (4, 1, "a.txt", 10, "ann", 1),
    (4, 2, "b.txt", 11, "bob", 1),
    (4, 3, "c.md", 12, "bob", 1),
    (4, 4, "d/e.txt", 13, "cy", 1),
    (6, 2, "b.txt", 11, "bob", -1),       # metadata update of document 2
    (6, 2, "b.txt", 21, "ann", 1),
    (6, 5, "a.txt", 22, "bob", 1),        # a second document at a.txt
    (8, 3, "c.md", 12, "bob", -1),        # deletions
    (8, 1, "a.txt", 10, "ann", -1),
    (10, 2, "b.txt", 21, "ann", -1),
    (10, 4, "d/e.txt", 13, "cy", -1),
    (10, 5, "a.txt", 22, "bob", -1),
]
_CHURN_TIMES = (2, 4, 6, 8, 10)
# (key offset, metadata_filter, filepath_globpattern, what plain Python keeps)
_INPUT_QUESTIONS = [
    (0, None, None, lambda m: True),
    (1, "owner == `bob`", None, lambda m: m["owner"] == "bob"),
    (2, None, "*.txt", lambda m: fnmatch.fnmatch(m["path"], "*.txt")),
    (3, "modified_at > 11", "a*", lambda m: m["modified_at"] > 11 and m["path"].startswith("a")),
    # a second unfiltered question of the same commit: its own whole list
    (4, None, None, lambda m: True),
]


def _churn_server():
    docs = pw.debug.table_from_rows(
        pw.schema_from_types(data=str, path=str, modified=int, owner=str),
        [
            (key, f"text of {key}", path, modified, owner, time, diff)
            for time, key, path, modified, owner, diff in _CHURN
        ],
        is_stream=True,
    ).select(
        data=pw.this.data,
        _metadata=pw.apply_with_type(
            lambda p, m, o: pw.Json(
                {"path": p, "modified_at": m, "seen_at": m + 1, "owner": o}
            ),
            pw.Json,
            pw.this.path, pw.this.modified, pw.this.owner,
        ),
    )
    return VectorStoreServer(
        docs, embedder=DeterministicMockEmbedder(dimension=8)
    )


def _plain_corpus(time):
    """key -> metadata of the documents present once the commits up to
    and including ``time`` are applied: a dict kept in plain Python."""
    corpus = {}
    for t, key, path, modified, owner, diff in _CHURN:
        if t > time:
            break
        if diff > 0:
            corpus[key] = {
                "path": path, "modified_at": modified,
                "seen_at": modified + 1, "owner": owner,
            }
        else:
            del corpus[key]
    return corpus


def _first_answers(capture):
    """(question key, time) -> row of each question's insertion."""
    return {(k, t): row for k, row, t, d in capture.updates if d > 0}


def test_inputs_and_statistics_against_plain_python_through_churn():
    server = _churn_server()
    # every question is asked in the commit whose documents it must see,
    # and left standing
    input_queries = pw.debug.table_from_rows(
        VectorStoreServer.InputsQuerySchema,
        [
            (100 * t + off, mfilter, glob, t, 1)
            for t in _CHURN_TIMES
            for off, mfilter, glob, _ in _INPUT_QUESTIONS
        ],
        is_stream=True,
    )
    stat_queries = pw.debug.table_from_rows(
        pw.schema_from_types(dummy=int),
        [(100 * t, 0, t, 1) for t in _CHURN_TIMES],
        is_stream=True,
    ).select()
    inputs_cap, stats_cap, parsed_cap = GraphRunner().run_tables(
        server.inputs_query(input_queries),
        server.statistics_query(stat_queries),
        server._graph["parsed_docs"],
    )

    inputs = _first_answers(inputs_cap)
    stats = _first_answers(stats_cap)
    # an inputs question is answered once, as of its arrival: later
    # commits neither retract nor revise it
    assert len(inputs_cap.updates) == len(_CHURN_TIMES) * len(_INPUT_QUESTIONS)
    parsed_now: dict = {}
    parsed_updates = sorted(parsed_cap.updates, key=lambda u: (u[2], u[3]))
    for t in _CHURN_TIMES:
        for key, row, time, diff in parsed_updates:
            if time == t:
                if diff > 0:
                    parsed_now[key] = row[0].value["metadata"]
                else:
                    del parsed_now[key]
        corpus = _plain_corpus(t)
        # the order of the old construction: parsed_docs' row keys
        in_order = [parsed_now[key] for key in sorted(parsed_now)]
        assert sorted(in_order, key=repr) == sorted(corpus.values(), key=repr)
        for off, _, _, keep in _INPUT_QUESTIONS:
            (answer,) = inputs[(100 * t + off, t)]
            assert answer.value == [m for m in in_order if keep(m)], (t, off)
        (answer,) = stats[(100 * t, t)]
        assert answer.value == {
            "file_count": len(corpus),
            "last_modified": max(
                (m["modified_at"] for m in corpus.values()), default=None
            ),
            "last_indexed": max(
                (m["seen_at"] for m in corpus.values()), default=None
            ),
        }, t
    assert _plain_corpus(2) == {} == _plain_corpus(10)
    assert len(_plain_corpus(6)) == 5


def test_inputs_answer_goes_with_its_question():
    """A question retracted (the REST routes delete a completed query)
    takes its answer with it, unrevised by the documents that came
    between; a question of a later commit sees those documents."""
    server = _churn_server()
    queries = pw.debug.table_from_rows(
        VectorStoreServer.InputsQuerySchema,
        [(1, None, None, 4, 1), (1, None, None, 8, -1), (2, None, None, 8, 1)],
        is_stream=True,
    )
    (cap,) = GraphRunner().run_tables(server.inputs_query(queries))
    by_key: dict = {}
    for key, (answer,), time, diff in cap.updates:
        by_key.setdefault(key, []).append(
            (time, diff, sorted(m["modified_at"] for m in answer.value))
        )
    assert sorted(by_key[1]) == [
        (4, 1, [10, 11, 12, 13]), (8, -1, [10, 11, 12, 13]),
    ]
    assert by_key[2] == [(8, 1, [13, 21, 22])]


# -- the lowered run_server graph, fed by a connector -------------------------


def _served(**gateway):
    """``run_server(threaded=True)`` over a connector fed by ``feed``:
    (server, client factory, feed, the thread ``pw.run`` is on)."""
    feed: queue.Queue = queue.Queue()

    class Corpus(pw.io.python.ConnectorSubject):
        _deletions_enabled = False

        def run(self):
            while True:
                self.next_batch(feed.get())
                self.commit()

    class DocSchema(pw.Schema):
        data: str
        _metadata: pw.Json

    table = pw.io.python.read(
        Corpus(), schema=DocSchema, autocommit_duration_ms=None
    )
    server = VectorStoreServer(
        table, embedder=DeterministicMockEmbedder(dimension=8)
    )
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    engine = server.run_server("127.0.0.1", port, threaded=True, **gateway)
    return (
        server,
        lambda: VectorStoreClient(host="127.0.0.1", port=port, timeout=60),
        feed,
        engine,
    )


def _doc_rows(start, stop):
    return [
        dict(
            data=f"document {i} of the corpus",
            _metadata={"path": f"/d/{i}.txt", "modified_at": i, "seen_at": i + 1},
        )
        for i in range(start, stop)
    ]


def _wait_for(pred, what, deadline_s=60.0):
    end = time.monotonic() + deadline_s
    while not pred():
        assert time.monotonic() < end, f"timed out: {what}"
        time.sleep(0.01)


def _file_count(probe):
    """The corpus's size as the server says it, None while it is not up."""
    try:
        return probe.get_vectorstore_statistics()["file_count"]
    except ConnectionError:
        return None


def test_inputs_over_http_empty_corpus_and_two_questions_in_one_window():
    server, client, feed, _ = _served(window_ms=400.0)
    probe = client()

    # an empty corpus: no padded row shows through
    _wait_for(lambda: _file_count(probe) == 0, "the server to answer")
    assert probe.get_input_files() == []
    assert probe.get_input_files(filepath_globpattern="*.txt") == []

    feed.put(_doc_rows(0, 6))
    _wait_for(lambda: _file_count(probe) == 6, "six documents")
    inputs_route = server.webserver._routes[2][2].__self__
    assert inputs_route.route == "/v1/inputs"
    metrics = inputs_route.serve_metrics
    answers: dict = {}

    def ask(name, **filters):
        answers[name] = client().get_input_files(**filters)

    # two questions sent together share a 400 ms window unless the
    # machine holds one of them back: try again then
    for _ in range(5):
        before = (metrics.requests, metrics.commits)
        askers = [
            threading.Thread(target=ask, args=("all",)),
            threading.Thread(
                target=ask, args=("odd",),
                kwargs=dict(
                    metadata_filter="modified_at == 1 || modified_at == 3"
                ),
            ),
        ]
        for t in askers:
            t.start()
        for t in askers:
            t.join(60)
        assert metrics.requests == before[0] + 2
        if metrics.commits == before[1] + 1:
            break
    # one gateway window, one commit, two answers: each its own whole list
    assert metrics.commits == before[1] + 1
    assert sorted(m["modified_at"] for m in answers["all"]) == [0, 1, 2, 3, 4, 5]
    assert sorted(m["modified_at"] for m in answers["odd"]) == [1, 3]


def _tuple_elements(row):
    return sum(len(cell) for cell in row if isinstance(cell, tuple))


@pytest.mark.parametrize("corpus", [1024, 8192])
def test_commit_cost_does_not_depend_on_corpus(corpus, monkeypatch):
    """What a commit of 512 documents makes every node of the lowered
    ``run_server`` graph emit (deltas, and elements inside tuple-valued
    cells) is the same with 512 documents ingested before it as with
    ``corpus``: no node re-derives a whole-corpus value. Counts, not
    times."""
    from pathway_tpu.engine.nodes import GroupByNode
    from pathway_tpu.engine.runtime import Runtime
    from pathway_tpu.engine.stream import is_native_batch

    commit = 512
    emitted: dict = {}      # time -> node id -> [deltas, tuple elements]
    done: list = []         # times of finished steps
    runtimes = []
    # this server's engine thread: the servers of earlier tests are still
    # alive in this process and may step meanwhile
    engine: list = []
    deliver, run_step = Runtime._deliver, Runtime._run_step

    def counting_deliver(self, node, time_, out):
        if threading.current_thread() in engine:
            rows = out.materialize() if is_native_batch(out) else out
            got = emitted.setdefault(time_, {}).setdefault(node.node_id, [0, 0])
            got[0] += len(rows)
            got[1] += sum(_tuple_elements(row) for _, row, _ in rows)
        deliver(self, node, time_, out)

    def noting_run_step(self, time_):
        run_step(self, time_)
        if threading.current_thread() in engine:
            runtimes[:] = [self]
            done.append(time_)

    monkeypatch.setattr(Runtime, "_deliver", counting_deliver)
    monkeypatch.setattr(Runtime, "_run_step", noting_run_step)
    server, client, feed, thread = _served()
    engine.append(thread)

    def doc_steps():
        """Finished steps in which the documents' connector (node 0)
        emitted one whole commit."""
        return [
            t for t in list(done)
            if emitted.get(t, {}).get(0, [0])[0] == commit
        ]

    # no question is asked while commits are counted: a step then holds
    # one commit of documents and nothing else
    for n, start in enumerate(range(0, corpus + commit, commit), 1):
        feed.put(_doc_rows(start, start + commit))
        _wait_for(lambda: len(doc_steps()) == n, f"commit {n}")
    steps = doc_steps()

    nodes = runtimes[0].scope.nodes
    labels = [f"{type(n).__name__}#{i}" for i, n in enumerate(nodes)]
    assert not nodes[0].inputs  # the documents' connector
    # the commit after 512 documents against the commit after `corpus`
    early, late = emitted[steps[1]], emitted[steps[-1]]
    assert early.keys() == late.keys() and len(early) >= 10
    for nid in early:
        for a, b in zip(early[nid], late[nid]):
            assert abs(a - b) <= 0.05 * max(a, b), (labels[nid], early[nid], late[nid])

    # no group over the documents alone gathers them into a tuple
    def sources(node, seen):
        if not node.inputs:
            seen.add(node.node_id)
        for parent in node.inputs:
            sources(parent, seen)
        return seen

    for node in nodes:
        if isinstance(node, GroupByNode) and sources(node, set()) == {0}:
            assert not any(
                "tuple" in (code or "") for code in node.native_codes
            ), labels[node.node_id]

    # the graph counted is the one that answers
    probe = client()
    assert probe.get_vectorstore_statistics()["file_count"] == corpus + commit
    assert len(probe.get_input_files()) == corpus + commit


def test_a_commit_hashes_each_json_once():
    """Through the lowered ``run_server`` graph a commit of 512 documents
    (the third: 1,024 are in) serialises at most three ``Json``s a
    document to hash them, the three that are made for it (``parse_doc``'s,
    ``split_doc``'s, the index's metadata), whatever number of
    arrangements, consolidations and frozen rows each passes through:
    ``json_hashes`` on the ring's ``engine.step``. A count, not a time."""
    from pathway_tpu.internals import flight

    commit = 512
    lo = time.monotonic_ns()
    server, client, feed, thread = _served()
    probe = client()

    _wait_for(lambda: _file_count(probe) == 0, "the server to answer")
    for n in range(3):
        feed.put(_doc_rows(n * commit, (n + 1) * commit))
        _wait_for(lambda: _file_count(probe) == (n + 1) * commit, f"commit {n + 1}")
    steps = [
        flight.args_of(s)
        for s in flight.spans_between(lo, time.monotonic_ns())
        if s[flight.S_NAME] == "engine.step"
        and s[flight.S_THREAD] == thread.ident
    ]
    # the steps that took documents in are the three that hashed hundreds
    doc_steps = [a for a in steps if a["json_hashes"] >= commit]
    assert len(doc_steps) == 3, [a["json_hashes"] for a in steps]
    for args in doc_steps:
        assert commit <= args["json_hashes"] <= 3 * commit, args
    # the commit after 1,024 costs what the first did
    assert doc_steps[2]["json_hashes"] <= doc_steps[0]["json_hashes"] * 1.05
