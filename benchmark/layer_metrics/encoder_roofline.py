"""The encoder executables' share of the compute roofline: FLOPs of the
padded shapes dispatched (tap at the jitted forward) over peak bf16
FLOP/s, against their traced device time. Compute-bound: a 256 x 512
batch of bge-base is 2.5e13 FLOPs over 0.44 GB of weights."""

import costs
from trace_reduce import in_trace, module_runs


def read(ctx):
    if not ctx.trace:
        return None
    runs, secs = module_runs(ctx, "encoder")
    shapes = in_trace(ctx, ctx.tap.dispatches)
    if not runs or secs <= 0 or not shapes:
        return None
    # the traced runs and the tapped dispatches can differ by one at each
    # edge of the stretch: the mean dispatch, times the runs traced
    mean = sum(costs.encoder_flops(ctx.arch, r[2], r[3]) for r in shapes) / len(shapes)
    return 100.0 * runs * mean / ctx.peaks["bf16_flops_per_s"] / secs
