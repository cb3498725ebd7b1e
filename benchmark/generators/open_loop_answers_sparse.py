"""``generators/open_loop_answers_by_reference.py`` for an answer model
whose attention goes over the rows an indexer chose for each query
(``glm_moe_dsa``): the check's reference FOLLOWS the served choice of rows
inside the cell's index tolerance, as it follows the served expert
selections inside the router tolerance, and two more numbers are compared:
``index_gap`` (the served index scores of a generation's last queries,
its decode steps, against the reference's, over the widest spread among
them) and
``wrong_selections`` (rows of a followed query that the reference's
scores put further than the tolerance on the wrong side of its k-th
best).

Why it must follow: bfloat16 index scores lie a few thousandths of their
spread from the float32 reference's and a query's 2,048th best of 15,000
scores has dozens of others that near it, so the two sets differ in a few
rows a query; every later layer's latent rows, routes and logits differ
with them: a reference that chose for itself read logit_gap 0.071-0.083
where following reads 0.006, and limits that loose would lie a third of
the way to the fp8 control's 0.21 (PERF.md section 6, PR 35). The
control's own choice of rows is followed and judged the same way
(``control``): an indexer in fp8 reads index_gap 0.38.

The words are those the held vocabulary rows can say (``_HeldWords``):
the configuration holds rows 0-19,359 of the WordPiece asset's 26,921, a
piece outside them is ``[UNK]``, and 26.8% of ``corpus.words()`` lie
outside. Left in, more than a quarter of every prompt is ONE token, whose
8 experts a layer are or are not among the 16 held as the seed's weights
fall: the experts' ``ragged_dot``s, a quarter of a chunk, then move a
whole run's generations by 4-6% on one seed in four, and ``query_p50_ms``
spread 2.7-2.9% over seeds where a new cell is admitted at 2.5% (the
driver's check, PR 35). A deployment's tokenizer has a piece for every
word it is sent; so has this cell's.

Nothing is copied: this file loads a copy of
``open_loop_answers_by_reference`` through ``loader.module`` and replaces,
in ITS copy of the accepted generator, the word list, what a kept
generation's record holds and what ``compare_generations`` hands the
reference. The mix's parameters are the accepted generator's."""

from __future__ import annotations

import importlib

import numpy as np

import loader

byref = loader.module("generators", "open_loop_answers_by_reference")
base = byref.base

_questions, _asked, _send, _doc_id, prompt_of, detokenize, prompt_ids, in_slice, UNK = (
    byref._questions, byref._asked, byref._send, byref._doc_id, byref.prompt_of,
    byref.detokenize, byref.prompt_ids, byref.in_slice, byref.UNK)
collect = byref.collect


class _HeldWords:
    """``corpus`` with ``words()`` cut to the words whose piece is one of
    the ``rows`` held vocabulary rows (a word is one piece); in the same
    order, so the seed draws from them as it draws from the whole list."""

    def __init__(self, corpus, rows: int):
        vocab = base._tokenizer().vocab
        self._corpus, self.rows = corpus, rows
        self._words = [w for w in corpus.words() if vocab[w] < rows]

    def __getattr__(self, name):
        return getattr(self._corpus, name)

    def words(self) -> list[str]:
        return list(self._words)


def _held_words(fn):
    """``fn(ctx, ...)`` with the accepted generator's copy drawing its
    words from the rows ``ctx``'s configuration holds."""
    def call(ctx, *args, **kwargs):
        rows = int(ctx.config["held"]["vocab_rows"][1])
        if getattr(base.corpus, "rows", None) != rows:
            whole = getattr(base.corpus, "_corpus", base.corpus)
            base.corpus = _HeldWords(whole, rows)
        return fn(ctx, *args, **kwargs)

    call.__name__, call.__doc__ = fn.__name__, fn.__doc__
    return call


make_inputs, setup, window, check = (
    _held_words(byref.make_inputs), _held_words(byref.setup), _held_words(byref.window),
    _held_words(byref.check))

_accepted_record, _accepted_compare = base._generation_record, base.compare_generations


def _generation_record(gen) -> dict:
    """The accepted record, and every query's served choice of rows (bits)
    with the served index scores of the last queries."""
    record = _accepted_record(gen)
    if getattr(gen, "indexed", None) is not None:
        chosen, scores = gen.choices()      # fetched here, after the window
        record["selections"] = {
            "at": np.arange(len(record["ids"])), "chosen": chosen, "scores": scores}
    return record


class _Choosing:
    """The configuration's reference in the control's place: it keeps its
    own choice of rows and the index scores of its last queries, so that
    the control's indexers are judged as the program's are."""

    def __init__(self, reference):
        self.reference, self.made = reference, []

    def forward(self, arch, seed, sequences, **how):
        out = self.reference.forward(arch, seed, sequences, keep_chosen=True, **how)
        self.made = [{"at": np.arange(len(s)), "chosen": o.pop("chosen"),
                      "scores": o.pop("index_scores")} for s, o in zip(sequences, out)]
        return out


@_held_words
def control(ctx) -> dict:
    """The accepted control, each of its generations with the choice of
    rows the fp8 reference made for it: the comparison's float32 reference
    follows and judges that choice as it does a served one."""
    choosing = base.reference_decoder = _Choosing(
        importlib.import_module(ctx.config["reference"]))
    try:
        served = base.control(ctx)
    finally:
        base.reference_decoder = choosing.reference
    by_ids = {id(g["ids"]): made for g, made in zip(
        (served["gens"][i] for i in ctx.sample), choosing.made)}
    for gen in served["gens"].values():     # ``longest`` is one of the sample's
        gen["selections"] = by_ids[id(gen["ids"])]
    return served


class _Following:
    """The configuration's reference, handed the served choices beside the
    served routes; keeps what it judged of them."""

    def __init__(self, reference, selections, index_tol: float):
        self.reference, self.selections, self.index_tol = reference, selections, index_tol
        self.index_gap, self.wrong_selections = 0.0, 0

    def forward(self, arch, seed, sequences, **how):
        if all(s is not None for s in self.selections):
            how.update(selections=self.selections, index_tol=self.index_tol)
        out = self.reference.forward(arch, seed, sequences, **how)
        self.index_gap = max(o.get("index_gap", 0.0) for o in out)
        self.wrong_selections = sum(o.get("wrong_selections", 0) for o in out)
        return out


def compare_generations(ctx, gens: dict) -> tuple[dict, dict]:
    """The accepted comparison with a reference that follows the served
    choice of rows (the program's, or the control's own), and the two
    numbers of the indexers' agreement."""
    names = sorted(gens, key=str)           # the accepted comparison's order
    following = _Following(
        base.reference_decoder, [gens[n].get("selections") for n in names],
        float(ctx.limits["tolerances"]["index"]))
    base.reference_decoder = following
    try:
        compared, notes = _accepted_compare(ctx, gens)
    finally:
        base.reference_decoder = following.reference
    compared["index_gap"] = float(following.index_gap)
    compared["wrong_selections"] = float(following.wrong_selections)
    return compared, notes


base._generation_record, base.compare_generations = _generation_record, compare_generations
