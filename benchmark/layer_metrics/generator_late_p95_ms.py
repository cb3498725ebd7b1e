"""How late the load generator sent: send time minus due time, 95th
percentile over the window's questions."""

from stats import percentile


def read(ctx):
    late = getattr(ctx, "late_ms", None)
    return percentile(late, 95) if late else None
