"""What the answer cell's per-layer readers share: the model's counters
over the window (the pipeline's snapshots at its open and close), the
ring's ``answer.*`` spans over the traced stretch, and the costs.

A program without the answer model, or a run without a trace, gives
``None`` everywhere and nothing raises."""

from __future__ import annotations

import numpy as np

import costs
import costs_decoder
import ring_reduce
from stats import percentile
from trace_reduce import in_trace, module_runs


def window_counters(ctx):
    """Counter differences over the window, or None."""
    at = getattr(getattr(ctx, "answer_tap", None), "counters_at", {})
    if "open" not in at or "close" not in at:
        return None
    a, b = at["open"], at["close"]
    out = {k: b[k] - a[k] for k in a if k not in ("decode_steps",)}
    out["decode_steps"] = {
        rows: n - a["decode_steps"].get(rows, 0) for rows, n in b["decode_steps"].items()
    }
    return out


def held_selections_per_token(ctx):
    """Measured: routed selections a token a layer that fell on held experts."""
    c = window_counters(ctx)
    if not c or not (c["held_selections"] + c["absent_selections"]):
        return None
    tokens_layers = (c["held_selections"] + c["absent_selections"]) / ctx.darch["experts_per_token"]
    return c["held_selections"] / tokens_layers


def answer_step_mfu(ctx):
    """FLOPs of the real prompt positions and the generated tokens that
    went through the held share in the traced stretch, plus the question
    embeddings' and the scans', over stretch x peak."""
    st = ring_reduce.stretch(ctx)
    share = held_selections_per_token(ctx)
    if st is None or share is None:
        return None
    a = ctx.darch
    per_token = costs_decoder.flops_per_token(a, share)
    chunks = st.started_in("answer.prefill")
    steps = st.started_in("answer.decode.step")
    flops = sum(int(s.args.get("real", 0)) for s in chunks) * per_token
    flops += len(chunks) * costs_decoder.head_flops(a)
    flops += sum(int(s.args.get("batch", 0)) for s in steps) * (
        per_token + costs_decoder.head_flops(a))
    if flops <= 0:
        return None
    flops += costs.real_token_flops(ctx.arch, in_trace(ctx, ctx.tap.batches))
    flops += module_runs(ctx, "search")[0] * costs.scan_flops(
        1, ctx.capacity, ctx.arch["hidden_size"])
    return 100.0 * flops / (ctx.trace["window_s"] * ctx.peaks["bf16_flops_per_s"])


def prefill_roofline(ctx):
    """``jit_answer_prefill`` runs x FLOPs of a dispatched chunk over peak,
    against their traced seconds. Compute-bound: a chunk is 1.7 TFLOP over
    9.5 GB of weights."""
    if not ctx.trace:
        return None
    runs, secs = module_runs(ctx, "prefill")
    c = window_counters(ctx)
    share = held_selections_per_token(ctx)
    if not runs or secs <= 0 or not c or share is None:
        return None
    chunk = int(ctx.config["serving"]["prefill_chunk"])
    chunks = (c["prefill_real"] + c["prefill_padded"]) / chunk
    mean_real = c["prefill_real"] / max(chunks, 1)
    flops = costs_decoder.prefill_chunk_flops(ctx.darch, chunk, mean_real, share)
    return 100.0 * runs * flops / ctx.peaks["bf16_flops_per_s"] / secs


def decode_roofline(ctx):
    """``jit_answer_decode`` runs x the bytes a step must move (dense
    weights, the experts the batch touched, states, keys/values, the
    head's rows) over peak bytes/s, against their traced seconds."""
    if not ctx.trace:
        return None
    runs, secs = module_runs(ctx, "decode")
    c = window_counters(ctx)
    steps = sum(c["decode_steps"].values()) if c else 0
    if not runs or secs <= 0 or not steps:
        return None
    batch = sum(rows * n for rows, n in c["decode_steps"].items()) / steps
    touched = c["decode_experts_touched"] / steps
    positions = c["prefill_real"] / max(c["prompts"], 1) + ctx.traffic["new_tokens"] / 2
    nbytes = costs_decoder.decode_step_bytes(ctx.darch, batch, touched, positions)
    return 100.0 * runs * nbytes / ctx.peaks["hbm_bytes_per_s"] / secs


def generate_share_of_request(ctx):
    """Median over the requests answered in the stretch of the seconds of
    ``answer.generate`` inside the request's dispatch leg, over that leg."""
    st = ring_reduce.stretch(ctx)
    if st is None:
        return None
    calls = st.by_name.get("answer.generate", ())
    shares = []
    for r in st.started_in("gateway.request"):
        if "dispatch_ms" not in r.args:
            continue
        lo = r.t0 + int(1e6 * (r.args.get("admit_ms", 0.0) + r.args["queue_ms"]
                               + r.args["pickup_ms"]))
        hi = lo + int(1e6 * r.args["dispatch_ms"])
        inside = sum(max(0, min(c.t1, hi) - max(c.t0, lo)) for c in calls)
        if hi > lo:
            shares.append(100.0 * inside / (hi - lo))
    return percentile(shares, 50) if shares and calls else None


def generations_per_answer(ctx):
    asked = len(getattr(ctx, "records", None) or [])
    tap = getattr(ctx, "answer_tap", None)
    if not asked or tap is None:
        return None
    made = sum(c[3] for c in tap.calls if c[2] == "window")
    return made / asked if made else None


def answer_block_share(ctx):
    """Share of the stretch the engine's thread is inside ``answer.generate``."""
    st = ring_reduce.stretch(ctx)
    if st is None or not st.by_name.get("answer.generate"):
        return None
    thread = st.engine_thread()
    mine = [s for s in st.by_name["answer.generate"] if s.thread == thread]
    return ring_reduce.share(st, st.covered(mine)) if mine else None


def decode_batch_occupancy(ctx):
    c = window_counters(ctx)
    steps = sum(c["decode_steps"].values()) if c else 0
    if not steps:
        return None
    return sum(rows * n for rows, n in c["decode_steps"].items()) / steps


def prefill_padding_share(ctx):
    c = window_counters(ctx)
    if not c or not c["prefill_real"]:
        return None
    return 100.0 * c["prefill_padded"] / (c["prefill_real"] + c["prefill_padded"])


def expert_load_max_over_mean(ctx):
    """The dearest held expert's tokens over the mean, by layer; the worst layer."""
    c = window_counters(ctx)
    if not c:
        return None
    tokens = np.asarray(c["expert_tokens"], np.float64)
    means = tokens.mean(axis=1)
    if not (means > 0).all():
        return None
    return float((tokens.max(axis=1) / means).max())
