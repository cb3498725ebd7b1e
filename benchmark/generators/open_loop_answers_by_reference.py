"""``generators/open_loop_answers.py`` for a cell whose configuration
names its own plain reference and holds a slice of the vocabulary smaller
than the WordPiece asset.

Nothing of the accepted generator is copied: this file loads a copy of it
through ``loader.module`` and gives that copy the named module in
``reference_decoder``'s place and the slice's ``prompt_ids`` (a piece
whose id lies outside the held rows is ``[UNK]``, as ``TPUChat`` makes
it; ``setup`` maps the tap's question suffixes the same way); ``collect`` cuts the longest prompt's kept cache rows to the
sequence's own positions, which is what the reference's ``states`` hold;
``check`` holds every number of the accepted comparison but ``answer_gap``,
which cannot move on a mix whose prompts are whole topics.
The mix's parameters are the accepted generator's.
"""

from __future__ import annotations

import importlib

import numpy as np

import loader

base = loader.module("generators", "open_loop_answers")

_questions, _asked, _send, _doc_id, prompt_of, detokenize = (
    base._questions, base._asked, base._send, base._doc_id, base.prompt_of, base.detokenize)
_whole_prompt_ids = base.prompt_ids
UNK = base._tokenizer().vocab["[UNK]"]


def in_slice(ctx, ids) -> np.ndarray:
    """Ids over the whole asset -> the held rows' ids: a piece outside them is ``[UNK]``."""
    ids = np.asarray(ids, np.int32)
    return np.where(ids >= int(ctx.config["held"]["vocab_rows"][1]), UNK, ids).astype(np.int32)


def prompt_ids(ctx, text: str) -> np.ndarray:
    return in_slice(ctx, _whole_prompt_ids(ctx, text))


base.prompt_ids = prompt_ids


def _bound(fn):
    def call(ctx, *args, **kwargs):
        base.reference_decoder = importlib.import_module(ctx.config["reference"])
        return fn(ctx, *args, **kwargs)

    call.__name__, call.__doc__ = fn.__name__, fn.__doc__
    return call


make_inputs, window, control = (
    _bound(base.make_inputs), _bound(base.window), _bound(base.control))


@_bound
def check(ctx, served=None) -> dict:
    """The accepted comparison but ``answer_gap``, which becomes a note:
    where a prompt's documents are its whole topic (``k`` = 24 of a topic
    of 24) the k-th score stands far above the next topic's best, and the
    number reads 0 for the program and for the control alike; the order
    of the 24 is held by ``rank_gap`` and ``score_gap``."""
    checked = base.check(ctx, served)
    if "answer_gap" in checked["compared"]:
        checked["notes"]["answer_gap"] = checked["compared"].pop("answer_gap")
    return checked


@_bound
def setup(ctx) -> None:
    """The accepted set-up; then the suffixes by which the tap knows a
    sampled question's prompt are the slice's ids too (the accepted code
    tokenises them over the whole asset)."""
    base.setup(ctx)
    tap = ctx.answer_tap
    tap.wanted = {tuple(int(t) for t in in_slice(ctx, suffix)): i
                  for suffix, i in tap.wanted.items()}


def collect(ctx) -> None:
    """The accepted collection; then the longest prompt's cache rows
    [max_positions, row] a layer become the sequence's own, float32."""
    base.collect(ctx)
    longest = ctx.answer_tap.longest
    if longest is not None and longest[1].ssm is not None:
        gen = longest[1]
        n = len(gen.prompt) + len(gen.tokens) - 1
        gen.ssm = [np.asarray(rows[:n], np.float32) for rows in gen.ssm]
