"""on-chip-rag: the whole RAG request on the accelerator — the question is
embedded, its documents retrieved from the HBM index, and the answer
generated by the in-repo decoder (pathway_tpu/models/decoder.py) on the same
device, behind the same `/v2/answer` route as any other chat.

Run: python app.py [port] [config.json]   then
     curl -X POST localhost:8000/v2/answer -d '{"prompt": "what is pathway"}'

The answer model is chosen by its published `config.json`:
`DecoderConfig.from_hf` dispatches on `model_type`, `granitemoehybrid`
(Mamba-2 + attention + routed experts) or `deepseek_v2` (latent attention,
a group-limited router beside shared experts), and takes the share of the
model this chip holds (`layers`, `experts_held`, `vocab_held`;
benchmark/configs/granite-4.0-h-small.json and DeepSeek-V2.json each state
one). Without a file the default builds a toy geometry with random weights
(no checkpoint is downloaded here), so the reply is a string of word
pieces: what the example shows is the path.
"""

import json

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import pathway_tpu as pw
from pathway_tpu.models.decoder import AnswerModel, DecoderConfig
from pathway_tpu.xpacks.llm.embedders import SentenceTransformerEmbedder
from pathway_tpu.xpacks.llm.llms import TPUChat
from pathway_tpu.xpacks.llm.question_answering import BaseRAGQuestionAnswerer
from pathway_tpu.xpacks.llm.vector_store import VectorStoreServer


def answer_model(config_path: str | None = None, **share) -> AnswerModel:
    """The answer model of a published ``config.json`` (random weights
    from a seed: a deployment hands ``AnswerModel`` its own ``params``), or
    a toy one. ``share``: ``layers``, ``experts_held``, ``vocab_held`` and
    the serving sizes, as ``DecoderConfig.from_hf`` takes them."""
    if config_path is None:
        return AnswerModel(DecoderConfig.tiny())
    with open(config_path, encoding="utf-8") as f:
        return AnswerModel(DecoderConfig.from_hf(json.load(f), **share))


def build(docs_path: str, host: str, port: int, *, model=None, embedder="tiny"):
    docs = pw.io.fs.read(
        docs_path, format="binary", with_metadata=True,
        mode="streaming", autocommit_duration_ms=100,
    )
    store = VectorStoreServer(docs, embedder=SentenceTransformerEmbedder(embedder))
    # the chat is a batched UDF: the questions of one gateway window are
    # prefilled one after another and decoded together
    chat = TPUChat(model or answer_model(), max_new_tokens=16)
    rag = BaseRAGQuestionAnswerer(llm=chat, indexer=store, search_topk=2)
    rag.build_server(host=host, port=port)
    return rag


if __name__ == "__main__":
    here = os.path.dirname(os.path.abspath(__file__))
    build(os.path.join(here, "docs"), "0.0.0.0",
          int(sys.argv[1]) if len(sys.argv) > 1 else 8000,
          model=answer_model(sys.argv[2]) if len(sys.argv) > 2 else None)
    pw.run()
