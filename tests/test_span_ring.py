"""The always-on span ring (internals/flight.py) from gateway to device
call: the encoder's six spans with their counts, one REST question
followed across threads by ``trace_id``, the ring's cap, a span budget
that repeats, the slow-request report, the Perfetto export of the new
kinds, and the names a harness patches still reached through the
instance."""

import gc
import json
import logging
import threading
import time

import numpy as np
import pytest

import pathway_tpu as pw
from pathway_tpu.analysis.profile import validate_trace
from pathway_tpu.internals import flight
from pathway_tpu.internals.flight import (
    S_ID, S_NAME, S_PARENT, S_T0, S_T1, S_THREAD, S_TRACE,
)

ENCODER_CHILDREN = [
    "encoder.tokenize", "encoder.pad", "encoder.h2d", "encoder.forward",
    "encoder.wait", "encoder.d2h",
]


def _named(spans, name):
    return [s for s in spans if s[S_NAME] == name]


_args = flight.args_of


def _tiny_encoder(batch_size=8):
    from pathway_tpu.models import EncoderConfig, SentenceEncoder

    return SentenceEncoder(EncoderConfig.tiny(), batch_size=batch_size)


# -- (a) the encoder's spans ---------------------------------------------------


ONE_GROUP = ["alpha beta gamma delta", "epsilon zeta", "eta"]
# 20 rows at batch_size 8: two full groups and one of four rows
THREE_GROUPS = [" ".join(["word"] * (1 + 3 * i)) for i in range(20)]


@pytest.mark.parametrize(
    "texts, groups", [(ONE_GROUP, 1), (THREE_GROUPS, 3)],
    ids=["one-group", "three-groups"],
)
def test_encode_yields_six_children_in_order_with_counts(texts, groups):
    """One ``.pad`` / ``.h2d`` / ``.forward`` a dispatched group, every
    group queued before the one ``.wait`` / ``.d2h`` pass that closes the
    call; ``groups`` and ``padded`` on ``encoder.encode`` say how it cut."""
    from pathway_tpu.internals.device import (
        encoder_call_groups,
        encoder_group_shapes,
    )
    from pathway_tpu.models.encoder import pad_batch

    enc = _tiny_encoder()
    enc.encode(texts)  # the buckets' first sighting compiles: not measured
    lo = time.monotonic_ns()
    out = enc.encode(texts)
    spans = [
        s for s in flight.spans_between(lo, time.monotonic_ns())
        if s[S_THREAD] == threading.get_ident()
    ]
    ids, mask = enc.tokenizer(texts)
    lengths = np.sort(mask.sum(axis=1))[::-1]
    plan = encoder_call_groups(lengths, enc.batch_size, enc.config.max_len)
    assert len(plan) == groups
    (parent,) = _named(spans, "encoder.encode")
    assert _args(parent) == {
        "texts": len(texts), "groups": groups,
        "padded": sum(rows * width for _, _, rows, width in plan),
    }
    kids = sorted(
        (s for s in spans if s[S_PARENT] == parent[S_ID]), key=lambda s: s[S_T0]
    )
    assert [s[S_NAME] for s in kids] == (
        ENCODER_CHILDREN[:1] + ENCODER_CHILDREN[1:4] * groups + ENCODER_CHILDREN[4:]
    )
    at = parent[S_T0]
    for s in kids:  # inside the parent, one after the other
        assert at <= s[S_T0] <= s[S_T1] <= parent[S_T1]
        at = s[S_T1]
    assert _args(kids[0]) == {"texts": len(texts), "tokens": int(mask.sum())}
    real = padded_rows = 0
    for g, (first, stop, rows, width) in enumerate(plan):
        pad, h2d, fwd = (_args(s) for s in kids[1 + 3 * g:4 + 3 * g])
        if groups == 1:  # as the tokenizer made it: rows and width its own
            ids_p, mask_p, _ = pad_batch(ids, mask, enc.config.max_len, enc.batch_size)
            assert (rows, width) == ids_p.shape
            assert pad == {"rows": 3, "longest": ids.shape[1], "padded": ids_p.size}
            group_lengths = mask_p.sum(axis=1, dtype=np.int32)
            assert h2d["bytes"] == ids_p.astype(np.uint16).nbytes + group_lengths.nbytes
        else:  # at its enumerated shape already
            assert pad == {"rows": rows, "longest": width, "padded": rows * width}
            assert h2d["bytes"] == rows * width * 2 + rows * 4
            assert (rows, width) in encoder_group_shapes(
                enc.batch_size, enc.config.max_len
            )
        assert fwd["bucket"] == f"{rows}x{width}"
        assert fwd["real_tokens"] == int(lengths[first:stop].sum())
        assert fwd["padded_tokens"] == rows * width and fwd["first"] is False
        real += fwd["real_tokens"]
        padded_rows += rows
    assert real == int(mask.sum())
    # padded rows come back too: no slice on the device
    assert _args(kids[-1])["bytes"] == padded_rows * out.shape[1] * 4
    assert out.shape == (len(texts), enc.config.hidden)


# -- (c) the ring is bounded ---------------------------------------------------


def test_ring_keeps_the_newest_and_counts_dropped(monkeypatch):
    ring = flight.SpanRing(4)
    monkeypatch.setattr(flight, "RING", ring)
    threads = threading.active_count()
    for i in range(10):
        with flight.span("t.span", i=i):
            pass
    assert len(ring.spans) == 4 and ring.dropped == 6
    assert [_args(s)["i"] for s in ring.spans] == [6, 7, 8, 9]
    lo, hi = ring.spans[1][S_T0], ring.spans[2][S_T1]
    assert [_args(s)["i"] for s in flight.spans_between(lo, hi)] == [7, 8]
    # recording starts no thread: the ring is a deque and a counter
    assert threading.active_count() == threads


def test_span_parent_trace_id_and_context_across_threads():
    lo = time.monotonic_ns()
    with flight.span("t.outer", trace_id=41) as outer:
        with flight.span("t.inner") as inner:
            ctx = flight.context()
        got = []
        worker = threading.Thread(
            target=lambda: got.append(
                flight.note_span(
                    "t.elsewhere", lo, time.monotonic_ns(),
                    parent=ctx[0], trace_id=ctx[1],
                )
            )
        )
        worker.start()
        worker.join()
    assert flight.context() == (None, None)
    assert ctx == (inner.id, 41) and inner.parent == outer.id
    assert got[0][S_PARENT] == inner.id and got[0][S_TRACE] == 41
    assert got[0][S_THREAD] != threading.get_ident()
    assert outer.kids == 1


# -- the tiny pipeline: REST question -> embedder -> index -> reply ----------------


class _Pipeline:
    pass


@pytest.fixture(scope="module")
def pipeline():
    """VectorStoreServer over a tiny SentenceEncoder, run on a thread, as
    the benchmark builds it; the encoder and the index adapter wrapped as
    the benchmark's ``_tap_encoder`` / ``_span_adapter`` wrap them
    (instance attributes, before the first call)."""
    from pathway_tpu.internals.parse_graph import G
    from pathway_tpu.stdlib.indexing.nearest_neighbors import _KnnAdapter
    from pathway_tpu.xpacks.llm.embedders import SentenceTransformerEmbedder
    from pathway_tpu.xpacks.llm.vector_store import (
        VectorStoreClient,
        VectorStoreServer,
    )

    import queue

    G.clear()
    p = _Pipeline()
    p.calls = {"encode": 0, "_forward_compact": 0, "search": 0, "add_batch": 0}
    enc = _tiny_encoder(batch_size=16)

    def counted(owner, name):
        inner = getattr(owner, name)

        def call(*args, **kwargs):
            p.calls[name] += 1
            return inner(*args, **kwargs)

        setattr(owner, name, call)

    counted(enc, "encode")
    counted(enc, "_forward_compact")
    feed = queue.Queue()

    class Corpus(pw.io.python.ConnectorSubject):
        _deletions_enabled = False

        def run(self):
            while True:
                rows = feed.get()
                self.next_batch(rows)
                self.commit()

    class DocSchema(pw.Schema):
        data: str
        _metadata: pw.Json

    before = {id(o) for o in gc.get_objects() if type(o) is _KnnAdapter}
    table = pw.io.python.read(Corpus(), schema=DocSchema, autocommit_duration_ms=None)
    server = VectorStoreServer(
        table, embedder=SentenceTransformerEmbedder(encoder=enc, batch_size=16),
        index_params={"reserved_space": 256},
    )
    port = 9391
    server.run_server("127.0.0.1", port, threaded=True, window_ms=5.0)
    p.client = VectorStoreClient(host="127.0.0.1", port=port, timeout=60)
    deadline = time.monotonic() + 60
    while True:
        try:
            p.client.get_vectorstore_statistics()
            break
        except Exception:
            assert time.monotonic() < deadline, "gateway never came up"
            time.sleep(0.05)
    (adapter,) = [
        o for o in gc.get_objects()
        if type(o) is _KnnAdapter and id(o) not in before
    ]
    counted(adapter, "search")
    counted(adapter, "add_batch")
    p.adapter = adapter

    def ingest(first, n):
        rows = [
            {"data": f"document number {i} about topic {i % 7}",
             "_metadata": {"path": f"doc/{i:04d}"}}
            for i in range(first, first + n)
        ]
        feed.put(rows)
        deadline = time.monotonic() + 60
        while len(adapter.shard) < first + n:
            assert time.monotonic() < deadline, "documents never became searchable"
            time.sleep(0.01)
        time.sleep(0.05)  # the step that wrote them closes

    p.ingest = ingest
    ingest(0, 16)
    p.client.query("topic 3", k=2)  # warm the question's shapes
    time.sleep(0.3)  # and let its retraction's window pass
    yield p


def _ask(p, text):
    """One question; returns (its gateway.request span, every ring span
    of the request and of the commit that answered it)."""
    lo = time.monotonic_ns()
    hits = p.client.query(text, k=2)
    assert len(hits) == 2
    time.sleep(0.3)  # the reply's own spans close just after it is sent
    spans = flight.spans_between(lo, time.monotonic_ns())
    request = [
        s for s in _named(spans, "gateway.request")
        if _args(s).get("route") == "/v1/retrieve" and s[S_T0] >= lo
    ][0]
    key = request[S_TRACE]
    (commit,) = [
        s for s in _named(spans, "gateway.commit") if key in _args(s)["keys"]
    ]
    t = _args(commit)["t"]  # the timestamp its commit produced
    mine = [
        s for s in spans
        if s[S_TRACE] in (key, t) or s is commit
        or (s[S_NAME] == "gateway.pickup"
            and _args(s)["window"] == _args(commit)["window"])
    ]
    return request, commit, mine


# -- (b) one question, followed across threads ------------------------------------


def test_question_is_followed_from_admission_to_reply(pipeline):
    request, commit, mine = _ask(pipeline, "topic 5")
    names = [s[S_NAME] for s in mine]
    for name in ("gateway.request", "gateway.queue", "gateway.pickup",
                 "gateway.commit", "gateway.resolve", "engine.step",
                 "index.search", "encoder.encode", "knn.search",
                 "knn.search.wait", "knn.search.d2h", "knn.search.resolve"):
        assert name in names, (name, names)
    t = _args(commit)["t"]
    (step,) = [s for s in _named(mine, "engine.step") if s[S_TRACE] == t]
    assert _args(step)["t"] == t and _args(step)["nodes"] > 0
    for name in ("index.search", "encoder.encode", "gateway.resolve"):
        assert all(s[S_TRACE] == t for s in _named(mine, name)), name
    (queue_span,) = _named(mine, "gateway.queue")
    assert queue_span[S_TRACE] == request[S_TRACE]
    assert queue_span[S_PARENT] == request[S_ID]
    # the event loop, the dispatch worker and the engine's thread (the
    # response callback runs on the engine's thread in this engine: there
    # is no fourth)
    threads = {
        name: {s[S_THREAD] for s in _named(mine, name)}
        for name in ("gateway.request", "gateway.commit", "engine.step")
    }
    assert len(set().union(*threads.values())) == 3
    assert {s[S_THREAD] for s in _named(mine, "index.search")} == threads["engine.step"]
    # the legs the request's args carry sum to it
    a = _args(request)
    legs = sum(a[k] for k in ("admit_ms", "queue_ms", "pickup_ms", "dispatch_ms", "egress_ms"))
    assert legs == pytest.approx((request[S_T1] - request[S_T0]) / 1e6, abs=1e-6)
    assert a["status"] == 200
    # the index.search sits inside the step, and the step starts inside
    # the request (it may outlive it: nodes still run after the reply)
    (search,) = _named(mine, "index.search")
    assert step[S_T0] <= search[S_T0] <= search[S_T1] <= step[S_T1]
    assert request[S_T0] <= step[S_T0] <= request[S_T1]
    assert _args(search) == {"queries": 1, "k": 2}
    # the new histogram saw the request too
    from pathway_tpu.io.http._server import RestServerSubject

    subjects = [o for o in gc.get_objects() if type(o) is RestServerSubject
                and o.route == "/v1/retrieve"]
    assert any(s.serve_metrics.window_wait.total > 0 for s in subjects)


# -- (d) the span budget ------------------------------------------------------------


def _budget(spans):
    """Spans by name, without the nodes: which node runs 100 us is the
    clock's business, every other span is one a call."""
    names = sorted(s[S_NAME] for s in spans if s[S_NAME] != "engine.node")
    return names


def test_span_budget_of_a_question_repeats(pipeline):
    _, _, first = _ask(pipeline, "topic 1")
    _, _, again = _ask(pipeline, "topic 1")
    assert len(first) <= 40 and len(again) <= 40, (len(first), len(again))
    assert _budget(first) == _budget(again)


def test_span_budget_of_an_encoder_batch_repeats(pipeline):
    def commit_spans(first_doc):
        lo = time.monotonic_ns()
        pipeline.ingest(first_doc, 16)
        spans = flight.spans_between(lo, time.monotonic_ns())
        (add,) = _named(spans, "index.add_batch")
        assert _args(add) == {"rows": 16}
        return [s for s in spans if s[S_TRACE] == add[S_TRACE]]

    first = commit_spans(16)
    again = commit_spans(32)
    assert len(first) <= 60 and len(again) <= 60, (len(first), len(again))
    assert _budget(first) == _budget(again)
    names = _budget(first)
    assert names.count("encoder.encode") == 1 and names.count("knn.write") == 1
    (write,) = _named(first, "knn.write")
    assert _args(write)["rows"] == 16
    assert _args(write)["h2d_bytes"] == 16 * 64 * 4 + 16 * 4


# -- (g) what a harness patches is still reached through the instance ---------------


def test_patched_names_are_called(pipeline):
    before = dict(pipeline.calls)
    _ask(pipeline, "topic 2")
    pipeline.ingest(48, 16)
    after = pipeline.calls
    assert after["encode"] > before["encode"]
    assert after["search"] > before["search"]
    assert after["add_batch"] > before["add_batch"]
    # the compact forward was bound at the buckets' first sighting, which
    # came after the patch: every forward since went through it
    assert after["_forward_compact"] > before["_forward_compact"]


# -- (h) the step says how many Json values it serialised to hash them --------------


def _is_flat(record):
    return all(
        v is None or type(v) in (int, float, str, bool) for v in record
    )


def test_step_carries_json_hashes(pipeline):
    """``json_hashes`` on ``engine.step``: the Json values the process
    serialised to hash over the step (a kept hash is not counted), an int
    beside ``nodes`` / ``short_nodes`` / ``short_ns`` in the one flat
    tuple a span leaves behind."""
    lo = time.monotonic_ns()
    pipeline.ingest(64, 16)  # documents carry ``_metadata``, a Json
    spans = flight.spans_between(lo, time.monotonic_ns())
    (add,) = _named(spans, "index.add_batch")
    (step,) = [
        s for s in _named(spans, "engine.step") if s[S_TRACE] == add[S_TRACE]
    ]
    args = _args(step)
    assert list(args) == ["t", "nodes", "short_nodes", "short_ns", "json_hashes"]
    assert type(args["json_hashes"]) is int
    # at least the chunk's Json, at most the three made a document
    assert 16 <= args["json_hashes"] <= 3 * 16
    assert _is_flat(step)

    # a step that touched no Json reads zero
    from pathway_tpu.internals.graph_runner import GraphRunner

    lo = time.monotonic_ns()
    t = pw.debug.table_from_markdown(
        """
        word | n
        a    | 1
        b    | 2
        a    | 3
        """
    )
    GraphRunner().run_tables(t.groupby(t.word).reduce(t.word, s=pw.reducers.sum(t.n)))
    mine = [
        s for s in _named(flight.spans_between(lo, time.monotonic_ns()), "engine.step")
        if s[S_THREAD] == threading.get_ident()
    ]
    assert mine and all(_is_flat(s) for s in mine)
    assert [_args(s)["json_hashes"] for s in mine] == [0] * len(mine)
    assert sum(_args(s)["nodes"] for s in mine) > 0


# -- (e) the slow-request report ----------------------------------------------------


def test_slow_request_is_reported_once(monkeypatch, caplog):
    import urllib.request

    def post(url, value):
        req = urllib.request.Request(
            url, data=json.dumps({"value": value}).encode(),
            headers={"Content-Type": "application/json"}, method="POST",
        )
        with urllib.request.urlopen(req, timeout=30) as resp:
            return json.loads(resp.read().decode())

    class S(pw.Schema):
        value: int

    def slow(v):
        time.sleep(0.15)
        return v + 1

    port = 9392
    webserver = pw.io.http.PathwayWebserver(host="127.0.0.1", port=port)
    queries, writer = pw.io.http.rest_connector(
        webserver=webserver, schema=S, window_ms=1.0,
    )
    writer(queries.select(result=pw.apply(slow, pw.this.value)))
    threading.Thread(target=pw.run, daemon=True).start()
    time.sleep(1.0)
    monkeypatch.setattr(flight, "SLOW_REQUEST_NS", 100_000_000)
    monkeypatch.setattr(flight, "_last_slow_report_ns", None)
    url = f"http://127.0.0.1:{port}/"
    with caplog.at_level(logging.WARNING, logger="pathway_tpu.flight"):
        assert post(url, 1) == 2
        time.sleep(0.1)
        assert post(url, 2) == 3  # as slow, inside the 10 s: not reported
        time.sleep(0.1)
        # a step of seconds is a bulk commit's normal: steps never report
        t = time.monotonic_ns()
        flight.note_span("engine.step", t - 2_000_000_000, t, trace_id=7)
    lines = [r.getMessage() for r in caplog.records if r.name == "pathway_tpu.flight"]
    assert len(lines) == 1, lines
    assert lines[0].startswith("slow request ")
    report = json.loads(lines[0][len("slow request "):])
    head = report["slow_request"]
    assert head["name"] == "gateway.request" and head["dur_ms"] > 100
    assert head["args"]["dispatch_ms"] > 100  # it says in which leg
    under = {s["name"] for s in report["spans"]}
    assert {"gateway.queue", "gateway.commit", "engine.step", "engine.node"} <= under
    assert report["overlapping"] == len(report["spans"]) <= flight.SLOW_REPORT_SPANS
    # the node that slept is there by name, with the time it took
    slept = max(
        (s for s in report["spans"] if s["name"] == "engine.node"),
        key=lambda s: s["dur_ms"],
    )
    assert slept["dur_ms"] >= 150 and slept["args"]["label"]


# -- (f) the Perfetto export adopts the ring ------------------------------------------


def test_export_holds_the_ring_kinds_and_validates(tmp_path, monkeypatch):
    path = str(tmp_path / "trace.json")
    monkeypatch.setenv("PATHWAY_TRACE", path)
    monkeypatch.delenv("PATHWAY_LANE_PROCESSES", raising=False)
    t = pw.debug.table_from_markdown(
        """
        word
        a
        b
        a
        """
    )
    counts = t.groupby(pw.this.word).reduce(word=pw.this.word, c=pw.reducers.count())
    pw.io.subscribe(counts, on_change=lambda *a, **k: None)
    pw.run(monitoring_level=pw.MonitoringLevel.NONE)
    doc = json.load(open(path))
    assert doc["pathway"]["schema"] == flight.TRACE_SCHEMA_VERSION == 2
    assert validate_trace(doc) == [], validate_trace(doc)
    events = doc["traceEvents"]
    ring = [e for e in events if e.get("cat") == "engine" and e["name"] == "engine.step"]
    assert ring and all(
        {"id", "parent", "trace_id", "t", "nodes", "short_nodes"} <= set(e["args"])
        for e in ring
    )
    # beside the recorder's own kinds, on tracks of their own
    assert {"node", "step"} <= {e.get("cat") for e in events}
    assert all(e["tid"] >= 500 for e in ring)
    # a ring span without its id is caught
    broken = json.loads(json.dumps(doc))
    for e in broken["traceEvents"]:
        if e.get("cat") == "engine":
            del e["args"]["id"]
            break
    assert any("ring span missing id" in p for p in validate_trace(broken))
