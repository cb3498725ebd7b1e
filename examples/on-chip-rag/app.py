"""on-chip-rag: the whole RAG request on the accelerator — the question is
embedded, its documents retrieved from the HBM index, and the answer
generated by the in-repo hybrid decoder (Mamba-2 + attention + routed
experts: pathway_tpu/models/decoder.py) on the same device, behind the same
`/v2/answer` route as any other chat.

Run: python app.py [port]   then
     curl -X POST localhost:8000/v2/answer -d '{"prompt": "what is pathway"}'

The default builds the model at a toy width with random weights (no
checkpoint is downloaded here), so the reply is a string of word pieces:
what the example shows is the path. A deployment passes its own
`AnswerModel(DecoderConfig.from_hf(config_json, layers=..., experts_held=...,
vocab_held=...), params)`: the share of a published `granitemoehybrid` model
that one chip holds (benchmark/configs/granite-4.0-h-small.json states one).
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import pathway_tpu as pw
from pathway_tpu.models.decoder import AnswerModel, DecoderConfig
from pathway_tpu.xpacks.llm.embedders import SentenceTransformerEmbedder
from pathway_tpu.xpacks.llm.llms import TPUChat
from pathway_tpu.xpacks.llm.question_answering import BaseRAGQuestionAnswerer
from pathway_tpu.xpacks.llm.vector_store import VectorStoreServer


def build(docs_path: str, host: str, port: int, *, model=None, embedder="tiny"):
    docs = pw.io.fs.read(
        docs_path, format="binary", with_metadata=True,
        mode="streaming", autocommit_duration_ms=100,
    )
    store = VectorStoreServer(docs, embedder=SentenceTransformerEmbedder(embedder))
    # the chat is a batched UDF: the questions of one gateway window are
    # prefilled one after another and decoded together
    chat = TPUChat(model or AnswerModel(DecoderConfig.tiny()), max_new_tokens=16)
    rag = BaseRAGQuestionAnswerer(llm=chat, indexer=store, search_topk=2)
    rag.build_server(host=host, port=port)
    return rag


if __name__ == "__main__":
    here = os.path.dirname(os.path.abspath(__file__))
    build(os.path.join(here, "docs"), "0.0.0.0",
          int(sys.argv[1]) if len(sys.argv) > 1 else 8000)
    pw.run()
