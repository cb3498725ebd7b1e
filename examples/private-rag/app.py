"""private-rag template (reference: docs/2.developers/7.templates/
1002.private-rag-ollama-mistral + templates/private-rag): an adaptive RAG
service where EVERY model runs locally — embedder, reranker and LLM never
leave the machine, so documents and questions stay private.

The default app.yaml wires deterministic offline mocks so the template
boots anywhere; production deployments swap the `llm` entry for the
on-device decoder (pw.xpacks.llm.llms.TPUChat; see examples/on-chip-rag), a
local HF pipeline (pw.xpacks.llm.llms.HFPipelineChat) or a LiteLLM entry
pointed at a local server (e.g. ollama/mistral at localhost:11434), and
the embedder for pw.xpacks.llm.embedders.SentenceTransformerEmbedder —
no code changes, only YAML.

Run: python app.py  (serves on the configured host/port)
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import pathway_tpu as pw
from pathway_tpu.xpacks.llm.question_answering import (
    AdaptiveRAGQuestionAnswerer,
)
from pathway_tpu.xpacks.llm.vector_store import VectorStoreServer


def run(config_path: str | None = None):
    config_path = config_path or os.path.join(
        os.path.dirname(__file__), "app.yaml"
    )
    with open(config_path) as f:
        cfg = pw.load_yaml(f)

    from pathway_tpu.internals.yaml_loader import resolve_config_path

    docs_path = resolve_config_path(cfg["docs_path"], config_path)

    docs = pw.io.fs.read(
        docs_path, format="binary", with_metadata=True,
        mode="streaming", autocommit_duration_ms=100,
    )
    store = VectorStoreServer(
        docs,
        embedder=cfg["embedder"],
        splitter=cfg.get("splitter"),
    )
    # adaptive retrieval keeps local-LLM context windows small: start
    # with a few documents and grow geometrically only when the model
    # cannot answer — the cost lever that makes private (local) LLM
    # serving practical
    rag = AdaptiveRAGQuestionAnswerer(
        llm=cfg["llm"],
        indexer=store,
        n_starting_documents=cfg.get("n_starting_documents", 2),
        factor=cfg.get("factor", 2),
        max_iterations=cfg.get("max_iterations", 4),
        strict_prompt=cfg.get("strict_prompt", True),
    )
    rag.build_server(host=cfg["host"], port=cfg["port"])
    pw.run()


if __name__ == "__main__":
    run(sys.argv[1] if len(sys.argv) > 1 else None)
