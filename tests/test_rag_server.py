"""End-to-end RAG REST server test: HTTP answer + retrieve + statistics
over a live webserver with mock models (reference Tier-4 webserver tests)."""

import json
import socket
import threading
import time
import urllib.request

import pathway_tpu as pw
from pathway_tpu.xpacks.llm.mocks import (
    DeterministicMockEmbedder,
    IdentityMockChat,
)
from pathway_tpu.xpacks.llm.question_answering import (
    BaseRAGQuestionAnswerer,
    RAGClient,
)
from pathway_tpu.xpacks.llm.vector_store import VectorStoreServer


def test_rag_server_end_to_end():
    docs = pw.debug.table_from_markdown(
        """
        data | meta
        pathway is a streaming framework | a.txt
        """
    ).select(
        data=pw.this.data,
        _metadata=pw.apply_with_type(
            lambda p: pw.Json({"path": p, "modified_at": 1, "seen_at": 2}),
            pw.Json,
            pw.this.meta,
        ),
    )
    server = VectorStoreServer(
        docs, embedder=DeterministicMockEmbedder(dimension=8)
    )
    rag = BaseRAGQuestionAnswerer(
        llm=IdentityMockChat(), indexer=server, search_topk=1
    )
    rag.build_server(host="127.0.0.1", port=8941)

    @rag.serve_callable("/v1/ping")
    async def ping(name: str):
        return f"pong {name}"

    threading.Thread(target=pw.run, daemon=True).start()
    # the server listens after 0.8-1.1 s on an idle machine, later beside
    # other test workers: wait for the port, not for a fixed time
    deadline = time.monotonic() + 30
    while True:
        try:
            socket.create_connection(("127.0.0.1", 8941), timeout=1).close()
            break
        except OSError:
            assert time.monotonic() < deadline, "server did not come up"
            time.sleep(0.05)

    client = RAGClient(host="127.0.0.1", port=8941)
    out = client.answer("what is pathway")
    assert out["response"].startswith("mock,")
    assert "streaming framework" in out["response"]

    out = client.retrieve("framework", k=1)
    assert len(out) == 1 and "pathway" in out[0]["text"]

    out = client.statistics()
    assert out["file_count"] == 1

    # dynamic callable endpoint (serve_callable -> AsyncTransformer)
    req = urllib.request.Request(
        "http://127.0.0.1:8941/v1/ping",
        data=json.dumps({"name": "tpu"}).encode(),
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=15) as resp:
        assert json.loads(resp.read().decode()) == "pong tpu"
