"""``pipelines/rag_answer.py`` for an answer model whose plain reference
is the module the configuration names (``"reference":
"reference_deepseek_v2"``), not ``reference_decoder``.

Nothing of the accepted pipeline is copied: this file loads a copy of it
through ``loader.module`` and gives that copy the named module in
``reference_decoder``'s place, an untied head among the parameters where
the reference makes one, and, where the model keeps latent cache rows, a
kept row's rows as the state the check compares (``Generation.latent`` in
``Generation.ssm``'s place). ``snapshot`` adds the counters the latent
attention brings to the accepted ones.
"""

from __future__ import annotations

import importlib

import loader

base = loader.module("pipelines", "rag_answer")

vs, AnswerTap = base.vs, base.AnswerTap
wait_until, check_index, doc_rows, doc_row = (
    base.wait_until, base.check_index, base.doc_rows, base.doc_row)
free_index, _accepted_params, _accepted_tap = (
    base.free_index, base.decoder_params, base._tap_model)

# counters of the model that the accepted snapshot does not name
MORE_COUNTERS = ("attended_positions_prefill", "attended_positions_decode", "latent_rows")


def _bind(ctx):
    """The plain reference the configuration names, in ``reference_decoder``'s place."""
    ref = base.reference_decoder = importlib.import_module(ctx.config["reference"])
    return ref


def snapshot(counters) -> dict:
    out = base.snapshot(counters)
    for name in MORE_COUNTERS:
        if hasattr(counters, name):
            out[name] = getattr(counters, name)
    return out


def control_inputs(ctx) -> None:
    _bind(ctx)
    base.control_inputs(ctx)


def decoder_params(ctx) -> dict:
    """The accepted tree, and the head where the reference makes one."""
    ref = _bind(ctx)
    params = _accepted_params(ctx)
    if hasattr(ref, "make_head"):
        params["head"] = ref.make_head(ctx.darch, ctx.seed)
    return params


def _latent_as_state(model) -> None:
    """A kept row's latent cache rows are the state the check compares."""
    inner = model.generate

    def generate(prompts, max_new_tokens, keep=()):
        made = inner(prompts, max_new_tokens, keep=keep)
        for gen in made:
            if getattr(gen, "latent", None) is not None:
                gen.ssm, gen.latent = gen.latent, None
        return made

    model.generate = generate


def build(ctx) -> None:
    _bind(ctx)

    def tap(model, answer_tap, retrieval_tap):
        _latent_as_state(model)
        _accepted_tap(model, answer_tap, retrieval_tap)

    base.decoder_params, base._tap_model = decoder_params, tap
    base.build(ctx)
