"""1 - real tokens / tokens of the bucket shapes dispatched, over the
whole window (tap: the mask's sum against the padded shape)."""


def read(ctx):
    real = sum(r[4] for r in ctx.tap.batches if r[1] == "window")
    padded = sum(r[2] * r[3] for r in ctx.tap.dispatches if r[1] == "window")
    if not real or not padded:
        return None
    return 100.0 * (1.0 - real / padded)
