"""One path from ``pw.run()`` to the device: every registered device site
is dispatched by what a user builds (a ``VectorStoreServer`` graph with
the real encoder at a toy width, on one device and on the virtual mesh,
one gateway window of its server, ``AnswerModel.generate``), and what
the benchmark taps of the encoder by name records what it says."""

import importlib.util
import os
import socket
import time

import pytest

import pathway_tpu as pw
from pathway_tpu.internals.device import PLANE, registered_sites
from pathway_tpu.models.encoder import EncoderConfig, SentenceEncoder
from pathway_tpu.xpacks.llm.embedders import SentenceTransformerEmbedder
from pathway_tpu.xpacks.llm.vector_store import (
    VectorStoreClient,
    VectorStoreServer,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# site -> the flow that has to dispatch it
FLOW_OF = {
    "encoder.forward": "graph",
    "knn.write": "graph",
    "knn.search": "graph",
    "knn.sharded_write": "sharded_graph",
    "knn.sharded_search": "sharded_graph",
    "serve.window": "gateway_window",
    "answer.prefill": "generate",
    "answer.decode": "generate",
}


def _embedder():
    return SentenceTransformerEmbedder(
        encoder=SentenceEncoder(EncoderConfig.tiny()), batch_size=16
    )


def _doc_rows(n):
    return [
        dict(
            data=f"document number {i} about topic {i % 7}",
            _metadata={"path": f"doc/{i:04d}", "modified_at": i, "seen_at": i + 1},
        )
        for i in range(n)
    ]


def _graph(mesh=None):
    """Documents and one question as static tables, answered by
    ``pw.run()``."""

    docs = pw.debug.table_from_markdown(
        "\n".join(["data | path"] + [f"{r['data']} | {r['_metadata']['path']}" for r in _doc_rows(24)])
    ).select(
        data=pw.this.data,
        _metadata=pw.apply_with_type(
            lambda path: pw.Json({"path": path, "modified_at": 1, "seen_at": 2}),
            pw.Json,
            pw.this.path,
        ),
    )
    server = VectorStoreServer(docs, embedder=_embedder(), mesh=mesh)
    questions = pw.debug.table_from_markdown(
        """
        query | k
        document number 13 about topic 6 | 3
        """,
        schema=VectorStoreServer.RetrieveQuerySchema,
    )
    answers = []
    pw.io.subscribe(
        server.retrieve_query(questions),
        on_change=lambda key, row, time, is_addition: (
            answers.append(row["result"]) if is_addition else None
        ),
    )
    pw.run()
    (answer,) = answers
    assert answer.value[0]["text"] == "document number 13 about topic 6"


def _sharded_graph():
    from pathway_tpu.parallel import make_mesh

    _graph(mesh=make_mesh(8, axes=("dp",), shape=(8,)))


def _gateway_window():
    """One question through ``run_server``'s gateway: its window's commit
    is the ``serve.window`` dispatch."""

    class Corpus(pw.io.python.ConnectorSubject):
        _deletions_enabled = False

        def run(self):
            self.next_batch(_doc_rows(8))
            self.commit()
            while True:  # a live source: the server outlives the test
                time.sleep(3600)

    class DocSchema(pw.Schema):
        data: str
        _metadata: pw.Json

    table = pw.io.python.read(Corpus(), schema=DocSchema, autocommit_duration_ms=None)
    server = VectorStoreServer(table, embedder=_embedder())
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    server.run_server("127.0.0.1", port, threaded=True, window_ms=5.0)
    client = VectorStoreClient(host="127.0.0.1", port=port, timeout=60)
    deadline = time.monotonic() + 60
    while True:
        try:
            if client.get_vectorstore_statistics()["file_count"] == 8:
                break
        except ConnectionError:
            pass
        assert time.monotonic() < deadline, "the server never held the documents"
        time.sleep(0.05)
    assert len(client.query("topic 3", k=2)) == 2


def _generate():
    from pathway_tpu.models.decoder import AnswerModel, DecoderConfig

    out = AnswerModel(DecoderConfig.tiny()).generate([[1, 2, 3]], 2)
    assert len(out) == 1


FLOWS = {
    "graph": _graph,
    "sharded_graph": _sharded_graph,
    "gateway_window": _gateway_window,
    "generate": _generate,
}


@pytest.fixture(scope="module")
def dispatched():
    """flow name -> the sites it dispatched (each flow run once)."""
    seen: dict[str, set] = {}
    begin = PLANE.begin
    current: list = []

    def recording(site, **kwargs):
        if current:
            seen[current[0]].add(site)
        return begin(site, **kwargs)

    def sites_of(flow: str) -> set:
        if flow not in seen:
            seen[flow] = set()
            current[:] = [flow]
            try:
                FLOWS[flow]()
            finally:
                current.clear()
        return seen[flow]

    PLANE.begin = recording
    try:
        yield sites_of
    finally:
        del PLANE.begin  # the instance attribute: the class's shows again


@pytest.mark.parametrize("site", sorted(FLOW_OF))
def test_every_registered_site_is_dispatched_by_what_a_user_builds(site, dispatched):
    # importing the dispatch modules is what registers their sites
    import pathway_tpu.io.http._server  # noqa: F401
    import pathway_tpu.models.decoder  # noqa: F401
    import pathway_tpu.ops.knn  # noqa: F401
    import pathway_tpu.parallel.sharded_knn  # noqa: F401

    # a site registered without a flow here has no user: it fails this
    assert set(registered_sites()) == set(FLOW_OF)
    assert site in dispatched(FLOW_OF[site])


# -- what the benchmark reads of the encoder by name ------------------------


def test_the_benchmark_taps_one_dispatch_a_group_and_one_batch_a_device_call(monkeypatch):
    """``benchmark/pipelines/vector_store._tap_encoder`` wraps ``encode``,
    ``encode_tokens_device``, ``_forward`` and ``_forward_compact`` on the
    instance: a rename of any of them shows here, not as a malformed
    result on the chip."""
    from pathway_tpu.internals.device import encoder_call_groups

    # the pipeline imports the harness's modules by their bare names
    monkeypatch.syspath_prepend(os.path.join(REPO, "benchmark"))
    spec = importlib.util.spec_from_file_location(
        "benchmark_pipeline_vector_store",
        os.path.join(REPO, "benchmark", "pipelines", "vector_store.py"),
    )
    pipeline = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(pipeline)
    enc = SentenceEncoder(EncoderConfig.tiny(), batch_size=16)
    tap = pipeline.Tap()
    pipeline._tap_encoder(enc, tap)
    tap.phase = "window"

    # 40 rows, long and short: more than one member of the shape set
    texts = [" ".join(["word"] * n) for n in [50] * 12 + [20] * 20 + [3] * 8]
    ids, _ = enc.tokenizer(texts)
    extents = sorted(((ids != 0).sum(axis=1)).tolist(), reverse=True)
    groups = encoder_call_groups(extents, enc.batch_size, enc.config.max_len)
    assert len(groups) > 1
    out = enc.encode(texts)
    assert out.shape == (40, enc.config.hidden)
    assert [(phase, n) for _, _, phase, n in tap.encodes] == [("window", 40)]
    shapes = [("window", rows, width) for _, _, rows, width in groups]
    assert [(phase, rows, width) for _, phase, rows, width in tap.dispatches] == shapes
    # encode hands every group to encode_tokens_device at its member's shape
    assert [(phase, rows, width) for _, phase, rows, width, _ in tap.batches] == shapes
    assert sum(real for *_, real in tap.batches) == sum(extents)

    enc.encode_tokens_device(*enc.tokenizer(texts[:5]))
    assert len(tap.encodes) == 1
    assert tap.batches[-1][1:3] == ("window", 5)
    assert len(tap.batches) == len(tap.dispatches) == len(groups) + 1
