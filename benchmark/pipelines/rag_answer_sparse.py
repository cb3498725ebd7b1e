"""``pipelines/rag_answer_by_reference.py`` for an answer model whose
attention goes over the rows an indexer chose: ``snapshot`` adds the
counters the indexer brings. Everything else is the accepted pipeline's
(a kept ``Generation`` carries its indexers' work by itself, on the
device, and ``generators/open_loop_answers_sparse.py`` fetches it after
the window)."""

from __future__ import annotations

import loader

byref = loader.module("pipelines", "rag_answer_by_reference")

vs, AnswerTap, wait_until, check_index, doc_rows, doc_row, free_index = (
    byref.vs, byref.AnswerTap, byref.wait_until, byref.check_index, byref.doc_rows,
    byref.doc_row, byref.free_index)
control_inputs, decoder_params, build = byref.control_inputs, byref.decoder_params, byref.build

# counters of the model that neither snapshot below this one names
MORE_COUNTERS = ("indexed_positions_prefill", "indexed_positions_decode",
                 "selected_positions_prefill", "selected_positions_decode", "index_rows")


def snapshot(counters) -> dict:
    out = byref.snapshot(counters)
    for name in MORE_COUNTERS:
        if hasattr(counters, name):
            out[name] = getattr(counters, name)
    return out
