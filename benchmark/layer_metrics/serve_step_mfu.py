"""The whole serve step's share of the chip's peak bf16 FLOP/s over the
traced stretch: encoder FLOPs of the real question tokens plus
2 x capacity x d for every scan run, over stretch x peak."""

import costs
from trace_reduce import in_trace, module_runs


def read(ctx):
    if not ctx.trace:
        return None
    flops = costs.real_token_flops(ctx.arch, in_trace(ctx, ctx.tap.batches))
    runs, _ = module_runs(ctx, "search")
    flops += runs * costs.scan_flops(1, ctx.capacity, ctx.arch["hidden_size"])
    if flops <= 0:
        return None
    return 100.0 * flops / (ctx.trace["window_s"] * ctx.peaks["bf16_flops_per_s"])
