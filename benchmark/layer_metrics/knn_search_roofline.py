"""The search executable's share of its bandwidth roofline: the bytes one
scan must read (capacity x (4d + 5)) over the chip's peak bytes/s, against
the traced device time per run. Bandwidth-bound: at q = 1 the scan's
FLOPs need 1/600 of the time its bytes do."""

import costs
from trace_reduce import module_runs


def read(ctx):
    if not ctx.trace:
        return None
    runs, secs = module_runs(ctx, "search")
    if not runs or secs <= 0:
        return None
    floor = costs.scan_bytes(ctx.capacity, ctx.arch["hidden_size"]) / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * runs * floor / secs
