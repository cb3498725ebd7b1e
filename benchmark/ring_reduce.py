"""From the program's always-on span ring to per-layer readings.

The program records one span per call at every layer boundary between an
HTTP request (or a connector commit) and the device call
(``pathway_tpu/internals/flight.py``): ``(id, name, t0_ns, t1_ns, thread,
parent, trace_id, args)`` on ``time.monotonic_ns()``, the clock of
``ctx.trace["interval"]``. The readers under ``layer_metrics/`` share what
is here: the spans of the traced stretch, clipped to it; seconds by span
name; and the table the first reader of a run prints through ``ctx.note``.

A program without the ring (an older commit) gives ``None`` everywhere
and nothing raises: the result line then leaves the metric out.
"""

from __future__ import annotations

import collections

from stats import percentile
from trace_reduce import union

Span = collections.namedtuple("Span", "id name t0 t1 thread parent trace_id args")


def _flight():
    try:
        from pathway_tpu.internals import flight
    except Exception:
        return None
    return flight if hasattr(flight, "spans_between") else None


def spans_between(lo_ns: int, hi_ns: int):
    """The ring's spans overlapping [lo_ns, hi_ns], or None without a ring."""
    flight = _flight()
    if flight is None:
        return None
    return [Span(*s[:7], flight.args_of(s)) for s in flight.spans_between(lo_ns, hi_ns)]


class Stretch:
    """The spans that overlap the traced stretch, and the stretch."""

    def __init__(self, spans, lo_ns: int, hi_ns: int):
        self.spans, self.lo, self.hi = spans, lo_ns, hi_ns
        self.seconds = (hi_ns - lo_ns) * 1e-9
        self.by_name = collections.defaultdict(list)
        for s in spans:
            self.by_name[s.name].append(s)

    def clipped(self, s) -> float:
        """Seconds of the span inside the stretch."""
        return max(0, min(s.t1, self.hi) - max(s.t0, self.lo)) * 1e-9

    def seconds_in(self, *names) -> float:
        return sum(self.clipped(s) for n in names for s in self.by_name.get(n, ()))

    def started_in(self, name):
        return [s for s in self.by_name.get(name, ()) if self.lo <= s.t0 <= self.hi]

    def engine_thread(self):
        """The thread that ran the steps."""
        secs = collections.Counter()
        for s in self.by_name.get("engine.step", ()):
            secs[s.thread] += self.clipped(s)
        return secs.most_common(1)[0][0] if secs else None

    def covered(self, spans) -> float:
        """Seconds of the stretch that the union of the spans covers."""
        cut = [(max(s.t0, self.lo), min(s.t1, self.hi)) for s in spans]
        return sum(b - a for a, b in union(c for c in cut if c[1] > c[0])) * 1e-9


def stretch(ctx):
    """The traced stretch's spans, or None: no trace, no ring, no span."""
    if not getattr(ctx, "trace", None) or "interval" not in ctx.trace:
        return None
    cached = getattr(ctx, "_ring_stretch", None)
    if cached is not None:
        return cached
    lo, hi = (int(round(t * 1e9)) for t in ctx.trace["interval"])
    spans = spans_between(lo, hi)
    if not spans:
        return None
    ctx._ring_stretch = st = Stretch(spans, lo, hi)
    note_once(ctx, st)
    return st


def share(st, seconds: float) -> float:
    return 100.0 * seconds / st.seconds


# -- the readings ---------------------------------------------------------------------


def engine_self_share(ctx):
    """Seconds inside ``engine.step`` that no ``encoder.encode`` or
    ``index.*`` span beneath it covers, as a share of the stretch."""
    st = stretch(ctx)
    if st is None or not st.by_name.get("engine.step"):
        return None
    thread = st.engine_thread()
    steps = [s for s in st.by_name["engine.step"] if s.thread == thread]
    beneath = [
        s for s in st.spans
        if s.thread == thread
        and (s.name == "encoder.encode" or s.name.startswith("index."))
    ]
    return share(st, st.covered(steps) - st.covered(beneath))


def gateway_wait_ms(st) -> list[float]:
    """Per request admitted in the stretch: ``gateway.queue`` plus its
    window's ``gateway.pickup``, ms."""
    pickup = {s.args.get("window"): s for s in st.by_name.get("gateway.pickup", ())}
    out = []
    for q in st.started_in("gateway.queue"):
        p = pickup.get(q.args.get("window"))
        if p is not None:
            out.append(((q.t1 - q.t0) + (p.t1 - p.t0)) * 1e-6)
    return out


def gateway_wait_p50_ms(ctx):
    st = stretch(ctx)
    waits = gateway_wait_ms(st) if st is not None else []
    return percentile(waits, 50) if waits else None


def knn_host_ms_per_search(ctx):
    """Mean over the stretch's ``index.search`` calls of the call's time
    less the ``knn.search.wait`` inside it: what the host adds to a scan."""
    st = stretch(ctx)
    if st is None:
        return None
    searches = st.started_in("index.search")
    if not searches:
        return None
    waits = st.by_name.get("knn.search.wait", ())
    total = 0
    for s in searches:
        total += s.t1 - s.t0
        total -= sum(
            w.t1 - w.t0 for w in waits
            if w.thread == s.thread and s.t0 <= w.t0 and w.t1 <= s.t1
        )
    return total * 1e-6 / len(searches)


def tokenizer_share(ctx):
    st = stretch(ctx)
    if st is None or not st.by_name.get("encoder.tokenize"):
        return None
    return share(st, st.seconds_in("encoder.tokenize"))


def encoder_host_share(ctx):
    """``encoder.encode`` less ``encoder.tokenize`` and ``encoder.wait``:
    pad, copies, dispatch, as a share of the stretch."""
    st = stretch(ctx)
    if st is None or not st.by_name.get("encoder.encode"):
        return None
    host = st.seconds_in("encoder.encode") - st.seconds_in("encoder.tokenize", "encoder.wait")
    return share(st, host)


def span_bytes(s) -> tuple[int, int]:
    """(host-to-device, device-to-host) bytes a span says it moved."""
    h2d = int(s.args.get("h2d_bytes", 0) or 0)
    d2h = 0
    if s.name.endswith(".h2d"):
        h2d += int(s.args.get("bytes", 0) or 0)
    elif s.name.endswith(".d2h"):
        d2h += int(s.args.get("bytes", 0) or 0)
    return h2d, d2h


def transfer_bytes_per_doc(ctx):
    """h2d + d2h bytes of every span of the commits that ran whole inside
    the stretch, over the documents those commits encoded."""
    st = stretch(ctx)
    if st is None:
        return None
    whole = {
        s.trace_id for s in st.by_name.get("engine.step", ())
        if st.lo <= s.t0 and s.t1 <= st.hi
    }
    docs = sum(
        int(s.args.get("texts", 0)) for s in st.by_name.get("encoder.encode", ())
        if s.trace_id in whole
    )
    if not docs:
        return None
    moved = sum(sum(span_bytes(s)) for s in st.spans if s.trace_id in whole)
    return moved / docs


# -- the table behind them ------------------------------------------------------------


def self_seconds(st, thread) -> tuple[dict[str, float], float]:
    """On one thread: seconds of the stretch by span name, self time (the
    span less the spans directly beneath it), and what no span covers.
    The two sum to the stretch."""
    mine = [s for s in st.spans if s.thread == thread]
    ids = {s.id for s in mine}
    beneath = collections.defaultdict(float)
    for s in mine:
        if s.parent in ids:
            beneath[s.parent] += st.clipped(s)
    by_name = collections.defaultdict(float)
    covered = 0.0
    for s in mine:
        by_name[s.name] += st.clipped(s) - beneath.get(s.id, 0.0)
        if s.parent not in ids:
            covered += st.clipped(s)
    return dict(by_name), st.seconds - covered


def node_quarters(ctx, top: int = 5):
    """The ``engine.node`` labels with the most self time a commit in the
    first and in the last quarter of the window's commits: what grows
    with the corpus shows as a label that climbs between the two."""
    if getattr(ctx, "window_t0", None) is None:
        return None
    lo = int(ctx.window_t0 * 1e9)
    spans = spans_between(lo, lo + int(ctx.seconds * 1e9))
    if not spans:
        return None
    steps = sorted((s for s in spans if s.name == "engine.step" and s.t0 >= lo),
                   key=lambda s: s.t0)
    quarter = len(steps) // 4
    if not quarter:
        return None
    kids = collections.defaultdict(int)
    nodes = collections.defaultdict(list)
    thread_of = {s.id: s.thread for s in spans}
    for s in spans:
        if thread_of.get(s.parent) == s.thread:
            kids[s.parent] += s.t1 - s.t0
        if s.name == "engine.node":
            nodes[s.trace_id].append(s)
    out = {}
    for which, part in (("first", steps[:quarter]), ("last", steps[-quarter:])):
        ms = collections.Counter()
        for step in part:
            for n in nodes.get(step.trace_id, ()):
                label = " ".join(filter(None, (n.args.get("label", "?"), n.args.get("where"))))
                ms[label] += ((n.t1 - n.t0) - kids.get(n.id, 0)) * 1e-6
        out[which] = {
            "commits": len(part),
            "step_ms_a_commit": sum(s.t1 - s.t0 for s in part) * 1e-6 / len(part),
            "node_self_ms_a_commit": [
                [label, v / len(part)] for label, v in ms.most_common(top)
            ],
        }
    return out


def note_once(ctx, st) -> None:
    """The whole table, once a run: seconds of the stretch by span name on
    the engine thread, what no span covers, transfers by site, the
    request legs, and the node labels by quarter."""
    thread = st.engine_thread()
    table = {"stretch_s": st.seconds, "spans": len(st.spans)}
    flight = _flight()
    table["ring"] = {"held": len(flight.RING.spans), "dropped": flight.RING.dropped}
    if thread is not None:
        by_name, outside = self_seconds(st, thread)
        steps = [s for s in st.by_name["engine.step"] if s.thread == thread]
        short_s = sum(
            s.args.get("short_ns", 0) * 1e-9 * st.clipped(s) / max((s.t1 - s.t0) * 1e-9, 1e-12)
            for s in steps
        )
        table["engine_thread"] = {
            "thread": flight.thread_name(thread),
            "self_s_by_span": dict(sorted(by_name.items(), key=lambda kv: -kv[1])),
            "between_steps_in_no_span_s": outside,
            "sum_s": sum(by_name.values()) + outside,
            "steps": len(steps),
            "short_nodes": sum(s.args.get("short_nodes", 0) for s in steps),
            "short_nodes_s": short_s,
            "in_step_in_no_node_s": by_name.get("engine.step", 0.0) - short_s,
        }
    moved = {}
    for s in st.spans:
        h2d, d2h = span_bytes(s)
        if h2d or d2h:
            row = moved.setdefault(s.name, {"calls": 0, "h2d_bytes": 0, "d2h_bytes": 0, "span_s": 0.0})
            row["calls"] += 1
            row["h2d_bytes"] += h2d
            row["d2h_bytes"] += d2h
            row["span_s"] += st.clipped(s)
    table["transfers_by_site"] = moved
    requests = [s for s in st.started_in("gateway.request") if "queue_ms" in s.args]
    if requests:
        legs = ("queue_ms", "pickup_ms", "dispatch_ms", "egress_ms")
        gaps = [
            abs((s.t1 - s.t0) * 1e-6 - sum(s.args[k] for k in legs)) for s in requests
        ]
        table["requests"] = {
            "answered": len(requests),
            "legs_p50_ms": {k: percentile([s.args[k] for s in requests], 50) for k in legs},
            "request_less_legs_max_ms": max(gaps),
            "admit_p50_ms": percentile([s.args.get("admit_ms", 0.0) for s in requests], 50),
        }
    quarters = node_quarters(ctx)
    if quarters:
        table["nodes_by_quarter_of_commits"] = quarters
    ctx.note(ring_table=table)
