"""DocumentStore (reference:
python/pathway/xpacks/llm/document_store.py:32-529 — the retriever-factory
driven sibling of VectorStoreServer: same parse/split pipeline, but the
index is built by an AbstractRetrieverFactory, so BM25/hybrid/KNN all fit)."""

from __future__ import annotations

from typing import Callable, Sequence

import pathway_tpu as pw
from pathway_tpu.stdlib.indexing.retrievers import InnerIndexFactory
from pathway_tpu.xpacks.llm.vector_store import VectorStoreServer


class DocumentStore(VectorStoreServer):
    """reference: document_store.py:32. Accepts `retriever_factory`
    (pw.indexing.*Factory); index construction is injected into the shared
    pipeline as a builder strategy — the factory owns embedding."""

    def __init__(
        self,
        *docs,
        retriever_factory: InnerIndexFactory,
        parser: Callable | None = None,
        splitter: Callable | None = None,
        doc_post_processors: Sequence[Callable] | None = None,
    ):
        self.retriever_factory = retriever_factory

        def build_index(chunked_docs):
            from pathway_tpu.internals import dtype as dt
            from pathway_tpu.internals.api import Json
            from pathway_tpu.internals.expression import apply_with_type

            return retriever_factory.build_index(
                chunked_docs.text,
                chunked_docs,
                metadata_column=apply_with_type(
                    lambda d: Json(d.value["metadata"]), dt.JSON,
                    chunked_docs.data,
                ),
            )

        super().__init__(
            *docs,
            index_builder=build_index,
            parser=parser,
            splitter=splitter,
            doc_post_processors=doc_post_processors,
        )


class SlidesDocumentStore(DocumentStore):
    """Document store for the slides-search application (reference:
    document_store.py:471): adds ``parsed_documents_query`` — the
    post-parse document metadata list the slide-search UI renders —
    with oversized fields (slide images) stripped from responses."""

    excluded_response_metadata = ["b64_image"]

    def parsed_documents_query(self, parse_docs_queries):
        """Table of parsed-document metadata (one Json list per query),
        filtered by the standard metadata_filter/filepath_globpattern
        pair."""
        from pathway_tpu.internals.api import Json
        from pathway_tpu.stdlib.indexing._filters import compile_filter

        excluded = tuple(self.excluded_response_metadata)

        @pw.udf(deterministic=True)
        def format_inputs(metadatas, metadata_filter: str | None) -> Json:
            pred = compile_filter(metadata_filter)
            out = []
            for m in metadatas:
                value = Json.parse(m).value
                if pred is not None and not pred(value):
                    continue
                out.append(
                    {k: v for k, v in value.items() if k not in excluded}
                )
            return Json(out)

        per_query = self._metadatas_as_of_now(parse_docs_queries)
        return per_query.select(
            result=format_inputs(pw.this.metadatas, pw.this.metadata_filter)
        )
