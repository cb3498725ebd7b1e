"""The whole ingest step's share of the chip's peak: forward FLOPs of the
REAL tokens the encoder was given in the traced stretch (padding is not
work) over stretch x peak bf16 FLOP/s."""

import costs
from trace_reduce import in_trace


def read(ctx):
    if not ctx.trace:
        return None
    flops = costs.real_token_flops(ctx.arch, in_trace(ctx, ctx.tap.batches))
    if flops <= 0:
        return None
    return 100.0 * flops / (ctx.trace["window_s"] * ctx.peaks["bf16_flops_per_s"])
