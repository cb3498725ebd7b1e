"""What the ``dsa_*`` readers share: the ``answer.prefill`` and
``answer.decode.step`` spans of the traced stretch (each says the real
positions, the pairs its indexers scored and the pairs a layer attended
after the choice), the model's counters over the window, and
``costs_glm_dsa``.

A program whose spans lack ``scored`` / ``selected``, or a run without a
trace, gives ``None`` everywhere and nothing raises."""

from __future__ import annotations

import costs
import costs_glm_dsa as cost
import ring_reduce
from answer_reduce import window_counters
from mla_reduce import held_selections_per_token
from trace_reduce import in_trace, module_runs


def _chunks(st):
    """(real, scored pairs, selected pairs) of each prefill chunk that
    started in the stretch."""
    out = []
    for s in st.started_in("answer.prefill"):
        if "scored" not in s.args or "selected" not in s.args:
            return None
        out.append((int(s.args["real"]), int(s.args["scored"]), int(s.args["selected"])))
    return out


def _steps(st):
    """(batch, summed contexts, selected positions) of each decode step
    that started in the stretch."""
    out = []
    for s in st.started_in("answer.decode.step"):
        if "positions" not in s.args or "selected" not in s.args:
            return None
        out.append((int(s.args["batch"]), int(s.args["positions"]), int(s.args["selected"])))
    return out


def dsa_prefill_roofline(ctx):
    """``jit_answer_prefill`` runs x the FLOPs of the stretch's mean
    dispatched chunk over peak, against their traced seconds."""
    st = ring_reduce.stretch(ctx)
    share = held_selections_per_token(ctx)
    if st is None or share is None:
        return None
    runs, secs = module_runs(ctx, "prefill")
    chunks = _chunks(st)
    if not runs or secs <= 0 or not chunks:
        return None
    width = int(ctx.config["serving"]["prefill_chunk"])
    flops = sum(
        cost.prefill_chunk_flops(ctx.darch, width, real, share, scored, selected)
        for real, scored, selected in chunks) / len(chunks)
    return 100.0 * runs * flops / ctx.peaks["bf16_flops_per_s"] / secs


def dsa_decode_roofline(ctx):
    """``jit_answer_decode`` runs x the bytes the stretch's mean step must
    move over peak bytes/s, against their traced seconds."""
    st = ring_reduce.stretch(ctx)
    c = window_counters(ctx)
    if st is None or not c:
        return None
    runs, secs = module_runs(ctx, "decode")
    steps = _steps(st)
    all_steps = sum(c["decode_steps"].values())
    if not runs or secs <= 0 or not steps or not all_steps:
        return None
    touched = c["decode_experts_touched"] / all_steps
    positions = sum(p for _, p, _ in steps) / len(steps)
    selected = sum(s for _, _, s in steps) / len(steps)
    nbytes = cost.decode_step_bytes(ctx.darch, touched, positions, selected)
    return 100.0 * runs * nbytes / ctx.peaks["hbm_bytes_per_s"] / secs


def dsa_answer_step_mfu(ctx):
    """FLOPs of the real prompt positions and the generated tokens that
    went through the held share in the traced stretch, the indexers'
    scores over their visible contexts and attention over the selected
    rows, plus the question embeddings' and the scans', over stretch x
    peak."""
    st = ring_reduce.stretch(ctx)
    share = held_selections_per_token(ctx)
    if st is None or share is None:
        return None
    chunks, steps = _chunks(st), _steps(st)
    if chunks is None or steps is None or not (chunks or steps):
        return None
    a = ctx.darch
    per_token = cost.token_flops(a, share)
    index, pair, absorbed = (cost.index_flops_per_pair(a), cost.attention_flops_per_pair(a),
                             cost.absorbed_flops_per_pair(a))
    flops = sum(real * per_token + scored * index + selected * pair
                for real, scored, selected in chunks)
    flops += len(chunks) * cost.head_flops(a)
    flops += sum(batch * (per_token + cost.head_flops(a)) + positions * index + selected * absorbed
                 for batch, positions, selected in steps)
    flops += costs.real_token_flops(ctx.arch, in_trace(ctx, ctx.tap.batches))
    flops += module_runs(ctx, "search")[0] * costs.scan_flops(
        1, ctx.capacity, ctx.arch["hidden_size"])
    return 100.0 * flops / (ctx.trace["window_s"] * ctx.peaks["bf16_flops_per_s"])


def dsa_selected_share(ctx):
    """The (query, cached position) pairs a layer attended over those its
    queries could see, prefill and decode, over the window (the model's
    counters, as ``pipelines/rag_answer_sparse.py`` snapshots them): 100
    means the indexer kept everything."""
    c = window_counters(ctx)
    if not c or "indexed_positions_prefill" not in c:
        return None
    seen = c["indexed_positions_prefill"] + c["indexed_positions_decode"]
    if not seen:
        return None
    return 100.0 * (c["selected_positions_prefill"] + c["selected_positions_decode"]) / seen
