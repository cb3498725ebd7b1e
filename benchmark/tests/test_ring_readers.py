"""The readers of the program's span ring, on a synthetic ring and a
synthetic ``ctx``: each gives the value computed by hand, ``None`` on an
empty ring, and ``None`` (no raise) where the program has no ring."""

import os
import sys
import types

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for path in (BENCH, os.path.dirname(BENCH)):
    if path not in sys.path:
        sys.path.insert(0, path)

import loader  # noqa: E402
import ring_reduce  # noqa: E402
from pathway_tpu.internals import flight  # noqa: E402

MS = 1_000_000
LO = 5_000 * MS  # the stretch: 5 s .. 7 s on the ring's clock
HI = 7_000 * MS
ENGINE, LOOP, WORKER = 11, 22, 33  # threads

READERS = (
    "gateway_wait_p50_ms", "engine_self_share.serve", "knn_host_ms_per_search",
    "engine_self_share.ingest", "tokenizer_share.ingest", "encoder_host_share.ingest",
    "transfer_bytes_per_doc.ingest",
)


def read(name, ctx):
    return loader.module("layer_metrics", name).read(ctx)


class Ctx:
    def __init__(self, interval=(LO / 1e9, HI / 1e9)):
        self.trace = {"interval": interval, "window_s": interval[1] - interval[0]}
        self.window_t0 = 4.0
        self.seconds = 4.0
        self.notes = []

    def note(self, **fields):
        self.notes.append(fields)


@pytest.fixture
def ring(monkeypatch):
    ring = flight.SpanRing(1024)
    monkeypatch.setattr(flight, "RING", ring)
    ids = iter(range(1, 1000))

    def add(name, t0_ms, t1_ms, thread=ENGINE, parent=None, trace_id=None, **args):
        # the ring's layout: seven fields, then the args' names and values
        rec = (next(ids), name, t0_ms * MS, t1_ms * MS, thread, parent, trace_id)
        rec += tuple(x for pair in args.items() for x in pair)
        ring.append(rec)
        return rec[0]

    ring.add = add
    return ring


def ingest_commit(ring, t, at):
    """One commit of 4 documents from ``at`` ms: a step of 1,000 ms with a
    node of 900 ms holding an encode of 600 ms (tokenize 100, pad 20, h2d
    10, forward 30, wait 400, d2h 5) and an add_batch of 100 ms."""
    step = ring.add("engine.step", at, at + 1000, trace_id=t, t=t, nodes=5,
                    short_nodes=4, short_ns=20 * MS)
    node = ring.add("engine.node", at + 50, at + 950, parent=step, trace_id=t,
                    node=3, label="RowwiseNode#3", rows=4, native=False)
    enc = ring.add("encoder.encode", at + 100, at + 700, parent=node, trace_id=t, texts=4)
    ring.add("encoder.tokenize", at + 100, at + 200, parent=enc, trace_id=t, texts=4, tokens=40)
    ring.add("encoder.pad", at + 200, at + 220, parent=enc, trace_id=t, rows=4)
    ring.add("encoder.h2d", at + 220, at + 230, parent=enc, trace_id=t, bytes=4 * 100)
    ring.add("encoder.forward", at + 230, at + 260, parent=enc, trace_id=t)
    ring.add("encoder.wait", at + 260, at + 660, parent=enc, trace_id=t)
    ring.add("encoder.d2h", at + 660, at + 665, parent=enc, trace_id=t, bytes=4 * 300)
    add = ring.add("index.add_batch", at + 800, at + 900, parent=node, trace_id=t, rows=4)
    ring.add("knn.write", at + 820, at + 830, parent=add, trace_id=t, rows=4,
             h2d_bytes=4 * 304)


def test_ingest_readers_give_the_hand_computed_values(ring):
    ingest_commit(ring, 1, 4500)   # half inside the stretch (5,000 .. 5,500)
    ingest_commit(ring, 2, 5600)   # whole
    ctx = Ctx()
    # steps cover 500 + 1,000 ms; encode and add_batch beneath them cover
    # (200 of the first's encode + its add_batch 100) + (600 + 100)
    assert read("engine_self_share.ingest", ctx) == pytest.approx(100 * (1500 - 1000) / 2000)
    # the first commit's tokenize ended before the stretch began
    assert read("tokenizer_share.ingest", ctx) == pytest.approx(100 * 100 / 2000)
    # encode 200 + 600, less tokenize 100, less wait 160 + 400
    assert read("encoder_host_share.ingest", ctx) == pytest.approx(100 * (800 - 100 - 560) / 2000)
    # only the commit that ran whole inside the stretch counts: 4 documents
    assert read("transfer_bytes_per_doc.ingest", ctx) == pytest.approx(100 + 300 + 304)
    (note,) = ctx.notes  # the table, once, from the first reader
    table = note["ring_table"]["engine_thread"]
    assert table["sum_s"] == pytest.approx(2.0)
    assert table["between_steps_in_no_span_s"] == pytest.approx(0.5)
    # a step's own 100 ms, less its short nodes' 20 ms (half of each for
    # the commit half inside)
    assert table["in_step_in_no_node_s"] == pytest.approx(0.150 - 0.030)
    assert table["self_s_by_span"]["encoder.wait"] == pytest.approx(0.560)
    moved = note["ring_table"]["transfers_by_site"]
    assert moved["knn.write"]["h2d_bytes"] == 2 * 4 * 304
    assert moved["encoder.d2h"]["d2h_bytes"] == 2 * 4 * 300


def question(ring, n, admit, close, pick, window):
    """One question: admitted, its window closed and picked up (ms)."""
    req = ring.add("gateway.request", admit - 1, pick + 30, thread=LOOP, trace_id=f"k{n}",
                   route="/v1/retrieve", status=200, admit_ms=1.0, queue_ms=close - admit,
                   pickup_ms=pick - close, dispatch_ms=28.0, egress_ms=2.0)
    ring.add("gateway.queue", admit, close, thread=LOOP, parent=req, trace_id=f"k{n}",
             window=window)


def test_serve_readers_give_the_hand_computed_values(ring):
    # two windows: questions 1 and 2 share the first, 3 has its own
    question(ring, 1, 5100, 5125, 5127, window=1)
    question(ring, 2, 5115, 5125, 5127, window=1)
    question(ring, 3, 5300, 5325, 5326, window=2)
    question(ring, 4, 4900, 4925, 4926, window=0)  # admitted before the stretch
    for window, closed, picked in ((0, 4925, 4926), (1, 5125, 5127), (2, 5325, 5326)):
        ring.add("gateway.pickup", closed, picked, thread=WORKER, window=window)
    for t, at in ((1, 5130), (2, 5330)):
        step = ring.add("engine.step", at, at + 40, trace_id=t, t=t, nodes=9,
                        short_nodes=8, short_ns=2 * MS)
        node = ring.add("engine.node", at + 2, at + 38, parent=step, trace_id=t,
                        node=7, label="ExternalIndexNode#7", rows=1, native=False)
        ring.add("encoder.encode", at + 4, at + 10, parent=node, trace_id=t, texts=1)
        search = ring.add("index.search", at + 12, at + 30, parent=node, trace_id=t,
                          queries=1, k=6)
        ring.add("knn.search", at + 13, at + 15, parent=search, trace_id=t, h2d_bytes=1536)
        ring.add("knn.search.wait", at + 15, at + 25, parent=search, trace_id=t)
        ring.add("knn.search.d2h", at + 25, at + 26, parent=search, trace_id=t, bytes=48)
    ctx = Ctx()
    # waits of the three admitted in the stretch: 27, 12, 26 ms
    assert read("gateway_wait_p50_ms", ctx) == pytest.approx(26.0)
    # two steps of 40 ms, less encode 6 and search 18 each
    assert read("engine_self_share.serve", ctx) == pytest.approx(100 * 2 * (40 - 24) / 2000)
    # a search of 18 ms with 10 ms of it waiting for the scan
    assert read("knn_host_ms_per_search", ctx) == pytest.approx(8.0)
    assert len(ctx.notes) == 1
    requests = ctx.notes[0]["ring_table"]["requests"]
    assert requests["answered"] == 3
    assert requests["request_less_legs_max_ms"] == pytest.approx(1.0)


@pytest.mark.parametrize("name", READERS)
def test_empty_ring_reads_nothing(ring, name):
    ctx = Ctx()
    assert read(name, ctx) is None
    assert ctx.notes == []


@pytest.mark.parametrize("name", READERS)
def test_untraced_run_and_program_without_a_ring_read_nothing(ring, monkeypatch, name):
    ring.add("engine.step", 5100, 5200, trace_id=1)
    untraced = Ctx()
    untraced.trace = None
    assert read(name, untraced) is None
    # an older program: the module is there, the ring is not
    monkeypatch.setattr(ring_reduce, "_flight", lambda: None)
    assert read(name, Ctx()) is None


def test_nodes_by_quarter_names_what_grows(ring):
    # eight commits in the window (4 s .. 8 s); GroupByNode#5 grows
    for i in range(8):
        at = 4000 + i * 400
        step = ring.add("engine.step", at, at + 300, trace_id=i, t=i)
        ring.add("engine.node", at + 10, at + 10 + 20 * (i + 1), parent=step, trace_id=i,
                 node=5, label="GroupByNode#5", where="store.py:9 in build", rows=4)
        ring.add("engine.node", at + 200, at + 250, parent=step, trace_id=i,
                 node=6, label="JoinNode#6", rows=4)
    quarters = ring_reduce.node_quarters(Ctx())
    assert quarters["first"]["commits"] == quarters["last"]["commits"] == 2
    assert quarters["first"]["node_self_ms_a_commit"] == [
        ["JoinNode#6", pytest.approx(50.0)],
        ["GroupByNode#5 store.py:9 in build", pytest.approx(30.0)],
    ]
    assert quarters["last"]["node_self_ms_a_commit"][0] == [
        "GroupByNode#5 store.py:9 in build", pytest.approx(150.0),
    ]
