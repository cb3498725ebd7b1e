"""Questions per committed gateway window (ServeMetrics: requests and
commits of the retrieve route over the whole window)."""


def read(ctx):
    g = getattr(ctx, "gateway", None)
    if not g or not g["commits"]:
        return None
    return g["requests"] / g["commits"]
