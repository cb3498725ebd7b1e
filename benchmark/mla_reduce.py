"""What the ``mla_*`` readers share: the ``answer.prefill`` and
``answer.decode.step`` spans of the traced stretch (each says the real
positions and the context its dispatch went over), the model's counters
over the window, and ``costs_deepseek_v2``.

A program whose spans lack ``context`` / ``positions``, or a run without a
trace, gives ``None`` everywhere and nothing raises."""

from __future__ import annotations

import costs
import costs_deepseek_v2 as cost
import ring_reduce
from answer_reduce import window_counters
from trace_reduce import in_trace, module_runs


def held_selections_per_token(ctx):
    """Measured: routed selections a token an expert layer that fell on held experts."""
    c = window_counters(ctx)
    if not c or not (c["held_selections"] + c["absent_selections"]):
        return None
    picks = (c["held_selections"] + c["absent_selections"]) / ctx.darch["experts_per_token"]
    return c["held_selections"] / picks


def _chunks(st):
    """(real, attended pairs) of each prefill chunk that started in the
    stretch: position pos + i of a chunk at pos attends pos + i + 1."""
    out = []
    for s in st.started_in("answer.prefill"):
        if "context" not in s.args:
            return None
        n = int(s.args["real"])
        before = int(s.args["context"]) - n
        out.append((n, n * before + n * (n + 1) // 2))
    return out


def _steps(st):
    """(batch, summed contexts) of each decode step that started in the stretch."""
    out = []
    for s in st.started_in("answer.decode.step"):
        if "positions" not in s.args:
            return None
        out.append((int(s.args["batch"]), int(s.args["positions"])))
    return out


def mla_prefill_roofline(ctx):
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
        cost.prefill_chunk_flops(ctx.darch, width, real, share, attended)
        for real, attended in chunks) / len(chunks)
    return 100.0 * runs * flops / ctx.peaks["bf16_flops_per_s"] / secs


def mla_decode_roofline(ctx):
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
    positions = sum(p for _, p in steps) / len(steps)
    nbytes = cost.decode_step_bytes(ctx.darch, touched, positions)
    return 100.0 * runs * nbytes / ctx.peaks["hbm_bytes_per_s"] / secs


def mla_answer_step_mfu(ctx):
    """FLOPs of the real prompt positions and the generated tokens that
    went through the held share in the traced stretch, attention at their
    real contexts, plus the question embeddings' and the scans', over
    stretch x peak."""
    st = ring_reduce.stretch(ctx)
    share = held_selections_per_token(ctx)
    if st is None or share is None:
        return None
    chunks, steps = _chunks(st), _steps(st)
    if chunks is None or steps is None or not (chunks or steps):
        return None
    a = ctx.darch
    per_token = cost.token_flops(a, share, 0.0)
    pair = cost.attention_flops_per_pair(a)
    flops = sum(real * per_token + attended * pair for real, attended in chunks)
    flops += len(chunks) * cost.head_flops(a)
    flops += sum(batch * (per_token + cost.head_flops(a)) + positions * pair
                 for batch, positions in steps)
    flops += costs.real_token_flops(ctx.arch, in_trace(ctx, ctx.tap.batches))
    flops += module_runs(ctx, "search")[0] * costs.scan_flops(
        1, ctx.capacity, ctx.arch["hidden_size"])
    return 100.0 * flops / (ctx.trace["window_s"] * ctx.peaks["bf16_flops_per_s"])
