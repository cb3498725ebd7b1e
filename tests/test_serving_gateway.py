"""Async batching gateway tests (io/http/_server.py): one commit per
batch window under load, the rule that closes a window (at once on an
idle route, when the route's last outstanding request is answered, on
its timer, at max_batch; never after its timer, in live runs and in
virtual time), bit-identical parity with the per-request path
on an out-of-order mixed-timeout workload, admission shedding with
Retry-After, timed-out-request eviction, GET coercion 400s, serve
metrics, the batched subscribe egress, and the Plan Doctor's
row-expanding-sink diagnostic.

Serving through rollback (ISSUE 9): the park/replay protocol
transitions and their exactly-once boundary (a responded request never
replays; an all-parked window commits nothing), the serving model
checker (clean protocol verifies, the ``replay_committed_window``
mutant is caught with a replayable trace), the dispatch circuit
breaker + brownout degraded answers, the epoch-survivable frontend's
park/deadline-503/draining behavior, the KeepAliveSession Retry-After
retry contract, /healthz readiness states, and the new knob/fault-point
registrations."""

import json
import threading
import time
import urllib.error
import urllib.request

import pytest

import pathway_tpu as pw
from pathway_tpu.internals.monitoring import ProberStats, ServeMetrics

_PORT = [9120]


def _next_port():
    _PORT[0] += 1
    return _PORT[0]


def _post(url, payload, timeout=15):
    req = urllib.request.Request(
        url,
        data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"},
        method="POST",
    )
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return json.loads(resp.read().decode())


def _gateway(port, pipeline=None, **kw):
    """rest_connector echo server; returns (subject, url)."""

    class S(pw.Schema):
        value: int

    webserver = pw.io.http.PathwayWebserver(host="127.0.0.1", port=port)
    queries, writer = pw.io.http.rest_connector(
        webserver=webserver, schema=S, **kw
    )
    if pipeline is None:
        writer(queries.select(result=pw.this.value * 3))
    else:
        writer(pipeline(queries))
    subject = webserver._routes[0][2].__self__
    return subject, f"http://127.0.0.1:{port}/"


def _start_run():
    t = threading.Thread(target=pw.run, daemon=True)
    t.start()
    time.sleep(1.0)
    return t


def _held_gateway(port, **kw):
    """``_gateway`` whose pipeline holds a request of value -1 until the
    returned event is set — a dispatched request still waiting for its
    response, so the route is busy and later requests collect — and
    drops values >= 900 (never answered). Returns (subject, url,
    release, closes): every window close as (trigger, members, ms since
    the window's first admission)."""
    release = threading.Event()

    def hold(v):
        if v < 0:
            release.wait(30)
        return v * 3

    subject, url = _gateway(
        port,
        pipeline=lambda q: q.filter(pw.this.value < 900).select(
            result=pw.apply(hold, pw.this.value)
        ),
        **kw,
    )
    closes = []
    close = subject._close_window

    def recording(window, by):
        if window is subject._window:
            closes.append(
                (by, len(window),
                 (time.monotonic_ns() - window[0].admitted_at) / 1e6)
            )
        close(window, by)

    subject._close_window = recording
    return subject, url, release, closes


def _until(pred, what, deadline_s=15.0):
    end = time.monotonic() + deadline_s
    while not pred():
        assert time.monotonic() < end, f"timed out: {what}"
        time.sleep(0.005)


def _fire_later(url, values, timeout=15):
    """``_fire`` on a thread of its own: (thread, its results)."""
    out = {}
    t = threading.Thread(target=lambda: out.update(_fire(url, values, timeout)))
    t.start()
    return t, out


def _fire(url, values, timeout=15):
    """Concurrent closed clients; returns {value: (status, result)}."""
    out = {}
    lock = threading.Lock()

    def client(v):
        try:
            res = _post(url, {"value": v}, timeout=timeout)
            status = 200
        except urllib.error.HTTPError as e:
            res = None
            status = e.code
        except Exception as e:  # client-side timeout etc.
            res = None
            status = repr(e)
        with lock:
            out[v] = (status, res)

    threads = [threading.Thread(target=client, args=(v,)) for v in values]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return out


def test_one_commit_per_window_under_load():
    """The pinned tentpole invariant: N concurrent requests coalesce
    into a handful of windows, each window is exactly ONE subject
    commit, and the occupancy histogram proves multi-request windows.
    The route is busy first (a held dispatch): requests coalesce while a
    step is in flight."""
    port = _next_port()
    subject, url, release, _ = _held_gateway(
        port, window_ms=2000.0, max_batch=64, workers=1
    )
    commits = [0]
    orig_commit = subject.commit

    def counting_commit():
        commits[0] += 1
        orig_commit()

    subject.commit = counting_commit
    _start_run()

    n = 48
    held, held_out = _fire_later(url, [-1])
    _until(lambda: commits[0] == 1, "the held request's commit")
    try:
        load, out = _fire_later(url, range(n))
        _until(lambda: subject._inflight == n + 1, "every request admitted")
    finally:
        release.set()
    load.join()
    held.join()
    assert held_out == {-1: (200, -3)}
    assert all(st == 200 and res == v * 3 for v, (st, res) in out.items())
    assert len(out) == n
    m = subject.serve_metrics
    assert m.requests == n + 1
    # every request is accounted to exactly one window, and coalescing
    # engaged: far fewer commits than requests, occupancy sums to n + 1
    assert m.occupancy.sum == n + 1
    assert m.commits == m.occupancy.total == commits[0]
    assert commits[0] <= n // 4, (commits[0], n)
    # multi-request windows: at least one window carried > 2 requests
    # (buckets are cumulative edges 1,2,4,...: everything above the
    # le=2 bucket had occupancy > 2)
    assert m.occupancy.total - sum(m.occupancy.counts[:2]) >= 1
    assert m.shed == 0 and m.timeouts == 0


def test_parity_with_per_request_path_out_of_order_mixed_timeouts():
    """Batched gateway vs per-request path (window 0 / max_batch 1) on
    an out-of-order, mixed-timeout workload: clients fire concurrently
    (arrival order is scrambled vs completion order — windows group
    arbitrary subsets), and the values >= 900 are filtered out of the
    response table so their clients hit the request deadline while
    later requests already completed. Every completed response must be
    bit-identical between the two paths, and exactly the filtered
    requests 504 on both."""

    def pipeline(queries):
        return queries.filter(pw.this.value < 900).select(
            result=pw.this.value * 7 + 1
        )

    values = list(range(40)) + [900, 901]
    results = {}
    for mode, kw in (
        ("batched", dict(window_ms=25.0, max_batch=16)),
        ("per_request", dict(window_ms=0.0, max_batch=1)),
    ):
        pw.internals.parse_graph.G.clear()
        port = _next_port()
        subject, url = _gateway(
            port, pipeline=pipeline, timeout_s=1.5, **kw
        )
        _start_run()
        results[mode] = _fire(url, values)
        assert subject.serve_metrics.timeouts == 2, mode

    for v in values:
        assert results["batched"][v] == results["per_request"][v], v
        if v < 900:
            assert results["batched"][v] == (200, v * 7 + 1)
        else:
            assert results["batched"][v][0] == 504


def test_admission_shedding_503_with_retry_after():
    port = _next_port()
    subject, url, release, _ = _held_gateway(
        port, window_ms=600.0, max_batch=1000, queue_cap=4
    )
    _start_run()
    # a held dispatch keeps the route busy: what follows collects
    held, held_out = _fire_later(url, [-1])
    _until(lambda: subject._inflight == 1, "the held request")

    n = 16
    headers = {}
    out = {}
    lock = threading.Lock()

    def client(v):
        req = urllib.request.Request(
            url,
            data=json.dumps({"value": v}).encode(),
            headers={"Content-Type": "application/json"},
            method="POST",
        )
        try:
            with urllib.request.urlopen(req, timeout=15) as resp:
                res = (200, json.loads(resp.read().decode()))
        except urllib.error.HTTPError as e:
            if e.code == 503:
                with lock:
                    headers[v] = e.headers.get("Retry-After")
            res = (e.code, None)
        with lock:
            out[v] = res

    threads = [threading.Thread(target=client, args=(v,)) for v in range(n)]
    for t in threads:
        t.start()
    try:
        _until(
            lambda: subject._inflight + subject.serve_metrics.shed == n + 1,
            "every request admitted or shed",
        )
    finally:
        release.set()
    for t in threads:
        t.join()
    held.join()
    assert held_out == {-1: (200, -3)}

    ok = [v for v, (st, _) in out.items() if st == 200]
    shed = [v for v, (st, _) in out.items() if st == 503]
    assert len(ok) + len(shed) == n
    # the held dispatch and the window hold admitted requests in flight,
    # so the cap must have shed the overflow — with 503, a Retry-After
    # >= 1s, and the shed counter agreeing
    assert len(shed) >= 1 and len(ok) >= 1
    assert all(h is not None and int(h) >= 1 for h in headers.values())
    assert subject.serve_metrics.shed == len(shed)
    for v in ok:
        assert out[v][1] == v * 3


def test_timed_out_requests_evicted_before_dispatch():
    """A request that times out before its window is dispatched is
    evicted: the window dispatches empty — no commit, no device work,
    no occupancy sample. The dispatch path is held first (the first
    commit waits at a gate), so the request's window queues behind it."""
    port = _next_port()
    subject, url = _gateway(
        port, window_ms=800.0, max_batch=1000, timeout_s=0.3
    )
    commits = [0]
    gate = threading.Event()
    orig_commit = subject.commit

    def held_commit():
        gate.wait(30)
        commits[0] += 1
        orig_commit()

    subject.commit = held_commit
    _start_run()

    held, held_out = _fire_later(url, [1])
    _until(
        lambda: subject._window_seq == 1 and subject._windows_q.empty(),
        "the held request's dispatch",
    )
    try:
        with pytest.raises(urllib.error.HTTPError) as e:
            _post(url, {"value": 2})
        assert e.value.code == 504
    finally:
        gate.set()
    held.join()
    assert held_out[1][0] == 504  # it timed out at the gate too
    assert subject.serve_metrics.timeouts == 2
    _until(lambda: subject._windows_q.empty(), "the queued window's dispatch")
    time.sleep(0.2)
    # the held request's commit alone: the evicted one's window was empty
    assert commits[0] == 1
    assert subject.serve_metrics.occupancy.total == 1


_CLOSE_CASES = {
    # case: (gateway knobs, the closes expected in order, as (trigger, members))
    "idle": (dict(window_ms=600.0), [("idle", 1)]),
    "answered": (dict(window_ms=5000.0), [("idle", 1), ("answered", 3)]),
    "timer": (dict(window_ms=300.0), [("idle", 1), ("timer", 2)]),
    "zero": (dict(window_ms=0.0), [("timer", 1)] * 4),
    "full": (
        dict(window_ms=5000.0, max_batch=2),
        [("idle", 1), ("full", 2), ("full", 2)],
    ),
    "filtered": (
        dict(window_ms=300.0, timeout_s=2.0), [("idle", 1), ("timer", 1)]
    ),
    "evicted": (
        dict(window_ms=300.0, timeout_s=3.0),
        [("idle", 1), ("timer", 1), ("timer", 1)],
    ),
}


@pytest.mark.parametrize("case", sorted(_CLOSE_CASES))
def test_window_close_rule(case):
    """A window closes at the earliest of: at once on a route with no
    dispatched request still waiting for its response (``idle``); when
    the route's last outstanding request is answered (``answered``); its
    ``window_ms`` timer (``timer``); ``max_batch`` (``full``). A request
    the route cannot account for — filtered and never answered, or
    parked at a rollback — leaves the timer to decide, never a hang. No
    window closes later than its timer, and
    ``serve_window_closes_total{by=...}`` counts each trigger."""
    knobs, want = _CLOSE_CASES[case]
    window_ms = knobs["window_ms"]
    subject, url, release, closes = _held_gateway(_next_port(), **knobs)
    _start_run()
    m = subject.serve_metrics
    try:
        if case == "idle":
            # a lone request on an idle route commits at once
            t0 = time.monotonic()
            assert _post(url, {"value": 4}) == 12
            assert time.monotonic() - t0 < window_ms / 1e3
        elif case == "filtered":
            # a request the pipeline drops is never answered: the route
            # reads busy until its deadline, so the next one waits on the
            # timer — and no longer
            lost, lost_out = _fire_later(url, [900])
            _until(lambda: closes, "the dropped request's window")
            t0 = time.monotonic()
            assert _post(url, {"value": 5}) == 15
            assert window_ms / 1e3 <= time.monotonic() - t0 < 2.0
            lost.join()
            assert lost_out == {900: (504, None)}
        else:
            held, held_out = _fire_later(url, [-1])
            _until(lambda: closes, "the held request's window")
            if case == "evicted":
                _until(lambda: m.commits == 1, "the held request's commit")
                parked, parked_out = _fire_later(url, [7])
                _until(lambda: subject._inflight == 2, "the parked request")
                # a rollback parks the collecting window: its request
                # still awaits an answer the route cannot account for
                assert subject.abort_windows_for_rollback() == 1
                release.set()
                held.join()
                _until(lambda: len(closes) == 2, "the parked window's timer")
                t0 = time.monotonic()
                assert _post(url, {"value": 5}) == 15
                assert window_ms / 1e3 <= time.monotonic() - t0 < 3.0
                parked.join()
                assert parked_out == {7: (504, None)}
                assert m.commits == 2  # the parked window committed nothing
            else:
                values = {"answered": [1, 2, 3], "timer": [1, 2],
                          "zero": [1, 2, 3], "full": [1, 2, 3, 4]}[case]
                rest, out = _fire_later(url, values)
                _until(
                    lambda: subject._inflight == len(values) + 1,
                    "every request admitted",
                )
                # while the held request is in flight nothing closes but
                # the timer and max_batch
                _until(lambda: len(closes) == len(want) - (case == "answered"),
                       "the closes made while the request is held")
                release.set()
                rest.join()
                assert out == {v: (200, v * 3) for v in values}
            held.join()
            assert held_out == {-1: (200, -3)}
    finally:
        release.set()
    assert [(by, n) for by, n, _ in closes] == want
    for by, n, ms in closes:
        # never later than the timer the window was armed with
        assert ms <= window_ms + 300.0, (by, ms)
        if by == "timer" and window_ms > 0:
            assert ms >= window_ms * 0.98, ms
    from pathway_tpu.internals.monitoring import SERVE_WINDOW_CLOSE_CAUSES

    stats = ProberStats()
    stats.mount_serve_metrics(m)
    text = stats.render_openmetrics()
    assert "# TYPE serve_window_closes_total counter" in text
    for by in SERVE_WINDOW_CLOSE_CAUSES:
        n = sum(1 for b, _ in want if b == by)
        assert m.window_closes[by] == n
        assert f'serve_window_closes_total{{route="/",by="{by}"}} {n}' in text


@pytest.mark.parametrize(
    "rate, service_ms, window_ms, max_batch, seen",
    [
        # the serve cells: questions at 40/s, a step of 10 + 9.3 ms a question
        (40.0, (10.0, 9.3), 25.0, 32, {"idle", "answered", "timer"}),
        # past capacity: windows fill
        (150.0, (10.0, 9.3), 25.0, 4, {"full"}),
        # the answer cells: a generation of 350 ms at 1.4/s
        (1.4, (350.0, 0.0), 25.0, 32, {"idle", "timer"}),
    ],
)
def test_window_never_closes_after_its_timer(
    monkeypatch, rate, service_ms, window_ms, max_batch, seen
):
    """The rule in virtual time: 400 Poisson arrivals into a route whose
    engine answers one window at a time in a + b x members. Every window
    closes no later than ``window_ms`` after its first request — the
    timer that alone closed it before — or at ``max_batch``; an ``idle``
    close holds its one request and waits nothing; every request is
    answered and the route ends with nothing in flight."""
    import heapq
    import random
    import types

    from pathway_tpu.io.http import _server

    class Loop:
        def __init__(self):
            self.now, self.seq, self.events = 0.0, 0, []

        def at(self, t, fn, *args):
            self.seq += 1
            handle = types.SimpleNamespace(cancelled=False)
            handle.cancel = lambda: setattr(handle, "cancelled", True)
            heapq.heappush(self.events, (t, self.seq, handle, fn, args))
            return handle

        def call_later(self, delay, fn, *args):
            return self.at(self.now + delay, fn, *args)

    loop = Loop()
    monkeypatch.setattr(
        _server, "asyncio", types.SimpleNamespace(get_event_loop=lambda: loop)
    )
    subject, _ = _gateway(_next_port(), window_ms=window_ms, max_batch=max_batch)
    subject._gateway_up = True  # no dispatch workers: the loop below is the engine
    admitted, closes = {}, []
    close = subject._close_window

    def recording(window, by):
        if window is subject._window:
            closes.append((by, len(window), loop.now - admitted[window[0].key]))
        close(window, by)

    subject._close_window = recording

    def arrive(key):
        admitted[key] = loop.now
        subject._inflight += 1
        subject._join_window(_server._PendingRequest(key, {"value": key}, None))

    def answer(window):
        for p in window:
            subject._leave(p.key)

    rng = random.Random(39)
    t = 0.0
    for key in range(1, 401):
        t += rng.expovariate(rate)
        loop.at(t, arrive, key)
    a, b = (ms / 1e3 for ms in service_ms)
    free = 0.0
    while loop.events:
        loop.now, _, handle, fn, args = heapq.heappop(loop.events)
        if handle.cancelled:
            continue
        fn(*args)
        while not subject._windows_q.empty():
            window = subject._windows_q.get_nowait()
            free = max(loop.now, free) + a + b * len(window)
            loop.at(free, answer, window)

    assert subject._inflight == 0 and not subject._window
    assert sum(n for _, n, _ in closes) == len(admitted) == 400
    for by, n, wait in closes:
        assert wait <= window_ms / 1e3 + 1e-9, (by, n, wait)
        assert (n == max_batch) == (by == "full"), (by, n)
        if by == "idle":
            assert n == 1 and wait == 0.0
        if by == "timer":
            assert wait == pytest.approx(window_ms / 1e3)
    causes = {by for by, _, _ in closes}
    assert seen <= causes, causes
    assert dict(subject.serve_metrics.window_closes) == {
        by: sum(1 for c, _, _ in closes if c == by)
        for by in ("idle", "answered", "timer", "full")
    }


def test_get_coercion_failure_returns_400_naming_field():
    port = _next_port()

    class S(pw.Schema):
        value: int
        ratio: float = pw.column_definition(default_value=1.0)
        flag: bool = pw.column_definition(default_value=False)

    webserver = pw.io.http.PathwayWebserver(host="127.0.0.1", port=port)
    queries, writer = pw.io.http.rest_connector(
        webserver=webserver, schema=S, methods=("GET", "POST"),
        window_ms=0.0,
    )
    writer(queries.select(result=pw.this.value * 2))
    _start_run()

    base = f"http://127.0.0.1:{port}/"
    for qs, field in (
        ("value=abc", "value"),
        ("value=3&ratio=zz", "ratio"),
        ("value=3&flag=maybe", "flag"),
    ):
        with pytest.raises(urllib.error.HTTPError) as e:
            urllib.request.urlopen(base + "?" + qs, timeout=10)
        assert e.value.code == 400
        body = json.loads(e.value.read().decode())
        assert field in body["error"]
    # valid coercions still work
    with urllib.request.urlopen(
        base + "?value=21&ratio=0.5&flag=true", timeout=10
    ) as resp:
        assert json.loads(resp.read().decode()) == 42


def test_serve_metrics_openmetrics_render():
    stats = ProberStats()
    m = ServeMetrics(route="/v1/retrieve")
    stats.mount_serve_metrics(m)
    stats.mount_serve_metrics(m)  # idempotent
    assert len(stats.serve) == 1
    for _ in range(5):
        m.on_request()
    m.on_shed()
    m.on_timeout()
    m.on_latency_ms(3.0)
    m.on_latency_ms(40.0)
    m.on_window(4)
    m.on_window(1)
    text = stats.render_openmetrics()
    assert 'serve_requests_total{route="/v1/retrieve"} 5' in text
    assert 'serve_shed_total{route="/v1/retrieve"} 1' in text
    assert 'serve_timeouts_total{route="/v1/retrieve"} 1' in text
    assert 'serve_window_commits_total{route="/v1/retrieve"} 2' in text
    assert "# TYPE serve_request_latency_ms histogram" in text
    # cumulative buckets: le="5" holds the 3ms sample, le="+Inf" both
    assert 'serve_request_latency_ms_bucket{route="/v1/retrieve",le="5"} 1' in text
    assert 'serve_request_latency_ms_bucket{route="/v1/retrieve",le="+Inf"} 2' in text
    assert 'serve_batch_occupancy_bucket{route="/v1/retrieve",le="4"} 2' in text
    assert 'serve_batch_occupancy_count{route="/v1/retrieve"} 2' in text
    assert 'serve_batch_occupancy_sum{route="/v1/retrieve"} 5' in text


def test_subscribe_on_batch_delivers_batched_changes():
    t = pw.debug.table_from_markdown(
        """
        a | b
        1 | 10
        2 | 20
        3 | 30
        """
    )
    batches = []
    rows = {}

    def on_batch(time_, changes):
        batches.append(list(changes))
        for key, row, diff in changes:
            assert diff == 1
            rows[key] = row

    pw.io.subscribe(t, on_batch=on_batch)
    pw.run()
    assert sum(len(b) for b in batches) == 3
    assert sorted((r["a"], r["b"]) for r in rows.values()) == [
        (1, 10), (2, 20), (3, 30),
    ]


def test_plan_doctor_blames_row_expanding_sink():
    t = pw.debug.table_from_markdown(
        """
        a
        1
        """
    )
    pw.io.subscribe(t, on_change=lambda *a: None)
    report = pw.analyze(t)
    sink = [d for d in report.diagnostics if d.code == "sink.row-expanding"]
    assert len(sink) == 1
    assert "on_batch" in (sink[0].hint or "")

    # the batched egress is clean
    pw.internals.parse_graph.G.clear()
    t2 = pw.debug.table_from_markdown(
        """
        a
        1
        """
    )
    pw.io.subscribe(t2, on_batch=lambda *a: None)
    report2 = pw.analyze(t2)
    assert not [
        d for d in report2.diagnostics if d.code == "sink.row-expanding"
    ]


def test_rest_response_sink_is_batched_in_plan():
    """The gateway's own response path must not trip the sink pass."""

    class S(pw.Schema):
        value: int

    webserver = pw.io.http.PathwayWebserver(host="127.0.0.1", port=_next_port())
    queries, writer = pw.io.http.rest_connector(webserver=webserver, schema=S)
    writer(queries.select(result=pw.this.value))
    report = pw.analyze(queries)
    assert not [
        d for d in report.diagnostics if d.code == "sink.row-expanding"
    ]


def test_serve_knobs_registered_and_wired(monkeypatch):
    from pathway_tpu.analysis.knobs import KNOBS, validate_environment

    for name in (
        "PATHWAY_REST_TIMEOUT_S", "PATHWAY_SERVE_WINDOW_MS",
        "PATHWAY_SERVE_MAX_BATCH", "PATHWAY_SERVE_QUEUE_CAP",
        "PATHWAY_SERVE_WORKERS",
    ):
        assert name in KNOBS
    monkeypatch.setenv("PATHWAY_REST_TIMEOUT_S", "17.5")
    monkeypatch.setenv("PATHWAY_SERVE_WINDOW_MS", "9")
    monkeypatch.setenv("PATHWAY_SERVE_MAX_BATCH", "8")
    monkeypatch.setenv("PATHWAY_SERVE_QUEUE_CAP", "99")
    monkeypatch.setenv("PATHWAY_SERVE_WORKERS", "2")
    assert validate_environment() == []

    class S(pw.Schema):
        value: int

    webserver = pw.io.http.PathwayWebserver(
        host="127.0.0.1", port=_next_port()
    )
    pw.io.http.rest_connector(webserver=webserver, schema=S)
    subject = webserver._routes[0][2].__self__
    assert subject.timeout_s == 17.5
    assert subject.window_s == pytest.approx(0.009)
    assert subject.max_batch == 8
    assert subject.queue_cap == 99
    assert subject.workers == 2

    # out-of-range serve knob is a startup rejection
    monkeypatch.setenv("PATHWAY_SERVE_MAX_BATCH", "0")
    findings = validate_environment()
    assert any(n == "PATHWAY_SERVE_MAX_BATCH" for n, _, _ in findings)


# ===========================================================================
# ISSUE 9: serving through rollback — park/replay, brownout, frontend
# ===========================================================================

def test_serve_park_replay_protocol_transitions():
    """The park/replay decisions are pure protocol transitions; pin the
    exactly-once boundary at the decision level: responded requests are
    NEVER in the park set, and the replay split honors deadlines."""
    from pathway_tpu.parallel import protocol as proto

    # a request whose response was delivered must not replay
    assert proto.serve_park([1, 2, 3], [2]) == [1, 3]
    assert proto.serve_park([1, 2], [1, 2]) == []
    replay, expired = proto.serve_replay_split(
        [5, 6, 7], 10.0, {5: 20.0, 6: 3.0, 7: 10.5}
    )
    assert replay == [5, 7] and expired == [6]
    # admission: recovering parks up to the budget, then sheds
    assert proto.serve_admit("serving", 0, 8, 0, 4) == "admit"
    assert proto.serve_admit("serving", 8, 8, 0, 4) == "shed"
    assert proto.serve_admit("recovering", 0, 8, 3, 4) == "park"
    assert proto.serve_admit("recovering", 0, 8, 4, 4) == "shed"
    assert proto.serve_admit("draining", 0, 8, 0, 4) == "shed"
    # frontend readiness states
    assert proto.serve_frontend_state(True, False) == "serving"
    assert proto.serve_frontend_state(False, False) == "recovering"
    assert proto.serve_frontend_state(True, True) == "draining"
    # Retry-After sized by observed restart time, never < 1s
    assert proto.serve_retry_after(4.2) == 5
    assert proto.serve_retry_after(0.0) == 1
    assert proto.serve_retry_after(9999.0) == 600
    # breaker: threshold opens, cooldown half-opens, 0 disables
    assert proto.breaker_decide("closed", 2, 3, 0.0, 5.0) == "closed"
    assert proto.breaker_decide("closed", 3, 3, 0.0, 5.0) == "open"
    assert proto.breaker_decide("open", 3, 3, 1.0, 5.0) == "open"
    assert proto.breaker_decide("open", 3, 3, 6.0, 5.0) == "half_open"
    assert proto.breaker_decide("closed", 99, 0, 0.0, 5.0) == "closed"


def test_serving_checker_transitions_are_the_engine_objects():
    """Anti-drift pin (the NBDecision/meshcheck pattern): the serving
    checker drives the very function objects the frontend and gateway
    execute — same-object identity, so checker and engine cannot
    diverge."""
    from pathway_tpu.analysis import meshcheck as mc
    from pathway_tpu.parallel import protocol as proto

    t = mc.get_serve_transitions()
    for name in mc.ServeTransitions.NAMES:
        assert getattr(t, name) is proto.TRANSITIONS[name], name
        assert proto.TRANSITIONS[name] is getattr(proto, name), name


def test_serving_checker_clean_protocol_verifies():
    """Exhaustive park/replay model: every interleaving of arrivals,
    window commits, responses, crashes and reattaches ends with every
    admitted request answered exactly once (incl. deadline 503s)."""
    from pathway_tpu.analysis import meshcheck as mc

    report = mc.check_serving()
    assert report.ok, report.render()
    assert report.terminals > 0 and report.rollbacks_explored > 0
    # with a deeper fault budget too (two rollbacks back-to-back)
    report2 = mc.check_serving(mc.ServeCheckConfig(fault_budget=2))
    assert report2.ok, report2.render()


def test_serving_checker_catches_replay_committed_window_mutant():
    """The exactly-once boundary, adversarially: a park set that stops
    filtering responded requests (replay_committed_window) MUST be
    caught as a double-response with a minimal, replayable trace."""
    from pathway_tpu.analysis import meshcheck as mc

    report = mc.check_serving(
        mc.ServeCheckConfig(mutate="replay_committed_window")
    )
    assert not report.ok
    v = report.violations[0]
    assert v.kind == "double-response"
    plan = v.fault_plan()
    assert plan is not None and plan["rules"], v.to_dict()
    rule = plan["rules"][0]
    assert rule["point"] == "serve.dispatch"
    assert rule["action"] == "crash"
    assert rule["phase"] in ("window", "committed")
    # the trace names the crash and the replay that answered twice
    labels = " | ".join(s["label"] for s in v.trace)
    assert "CRASH" in labels and "reattach" in labels


def test_all_parked_window_commits_nothing():
    """The backend half of parking: windows aborted on the epoch-abort
    path have every member evicted, so a racing dispatch commits
    NOTHING for them — and the abort is counted."""
    port = _next_port()
    subject, url = _gateway(port, window_ms=600.0, max_batch=1000)
    commits = [0]
    orig_commit = subject.commit

    def counting_commit():
        commits[0] += 1
        orig_commit()

    subject.commit = counting_commit
    # stage two closed windows + one collecting window directly (the
    # dispatch workers are not running: no pw.run, no requests)
    from pathway_tpu.io.http._server import _PendingRequest

    class _F:
        def done(self):
            return True

    w1 = [_PendingRequest(("k", i), {"value": i}, _F()) for i in range(3)]
    w2 = [_PendingRequest(("k", 9), {"value": 9}, _F())]
    subject._windows_q.put(w1)
    subject._windows_q.put(w2)
    subject._window = [_PendingRequest(("k", 5), {"value": 5}, _F())]

    aborted = subject.abort_windows_for_rollback()
    assert aborted == 3  # two queued + the collecting window
    assert subject.serve_metrics.windows_aborted == 3
    assert all(p.evicted for p in w1 + w2)
    assert all(p.evicted for p in subject._window)
    # idempotent: a second abort finds nothing new
    assert subject.abort_windows_for_rollback() == 0
    # a dispatch racing the abort sees only evicted members: no commit,
    # no occupancy sample
    subject._dispatch_window(w1)
    subject._dispatch_window(w2)
    assert commits[0] == 0
    assert subject.serve_metrics.occupancy.total == 0


def test_breaker_opens_on_dispatch_failures_then_brownout(monkeypatch):
    """Consecutive dispatch failures open the breaker; with
    PATHWAY_SERVE_BROWNOUT=1 and a brownout_answer hook the gateway then
    answers DEGRADED (Degraded: true header, browned_out counter)
    instead of shedding."""
    from pathway_tpu.internals import faults

    monkeypatch.setenv("PATHWAY_SERVE_BROWNOUT", "1")
    port = _next_port()

    class S(pw.Schema):
        value: int

    webserver = pw.io.http.PathwayWebserver(host="127.0.0.1", port=port)
    queries, writer = pw.io.http.rest_connector(
        webserver=webserver, schema=S, window_ms=20.0,
        brownout_answer=lambda values: values["value"] * 3,
        breaker_threshold=1, breaker_cooldown_s=300.0,
    )
    writer(queries.select(result=pw.this.value * 3))
    subject = webserver._routes[0][2].__self__
    faults.install_plan(
        {
            "seed": 1,
            "rules": [
                {
                    "point": "serve.dispatch", "phase": "window",
                    "action": "raise",
                }
            ],
        }
    )
    try:
        _start_run()
        url = f"http://127.0.0.1:{port}/"
        # first request: its window dispatch fails (injected) — the
        # client gets a terminal 500 and the breaker opens
        req = urllib.request.Request(
            url, data=json.dumps({"value": 1}).encode(),
            headers={"Content-Type": "application/json"}, method="POST",
        )
        with pytest.raises(urllib.error.HTTPError) as e1:
            urllib.request.urlopen(req, timeout=15)
        assert e1.value.code == 500
        deadline = time.monotonic() + 10
        while subject._breaker != "open":
            assert time.monotonic() < deadline, subject._breaker
            time.sleep(0.05)
        # second request: browned out — degraded answer, no dataflow
        req2 = urllib.request.Request(
            url, data=json.dumps({"value": 7}).encode(),
            headers={"Content-Type": "application/json"}, method="POST",
        )
        with urllib.request.urlopen(req2, timeout=15) as resp:
            assert resp.headers.get("Degraded") == "true"
            assert json.loads(resp.read().decode()) == 21
        assert subject.serve_metrics.browned_out == 1
        assert subject.serve_metrics.breaker_state == "open"
        # metrics render carries the new families
        from pathway_tpu.internals.monitoring import ProberStats

        stats = ProberStats()
        stats.mount_serve_metrics(subject.serve_metrics)
        text = stats.render_openmetrics()
        assert "serve_browned_out_total" in text
        assert 'serve_breaker_state{route="/"} 2' in text
    finally:
        faults.reset()


def test_breaker_shed_503_when_brownout_off(monkeypatch):
    """Breaker open without brownout: requests shed 503 + Retry-After
    (the cooldown), never hang into the failing dispatch path."""
    from pathway_tpu.internals import faults

    monkeypatch.delenv("PATHWAY_SERVE_BROWNOUT", raising=False)
    port = _next_port()
    subject, url = _gateway(
        port, window_ms=20.0, breaker_threshold=1,
        breaker_cooldown_s=300.0,
    )
    faults.install_plan(
        {
            "seed": 1,
            "rules": [
                {
                    "point": "serve.dispatch", "phase": "window",
                    "action": "raise",
                }
            ],
        }
    )
    try:
        _start_run()
        with pytest.raises(urllib.error.HTTPError):
            _post(url, {"value": 1})
        deadline = time.monotonic() + 10
        while subject._breaker != "open":
            assert time.monotonic() < deadline, subject._breaker
            time.sleep(0.05)
        with pytest.raises(urllib.error.HTTPError) as e:
            _post(url, {"value": 2})
        assert e.value.code == 503
        assert e.value.headers.get("Retry-After") is not None
        assert subject.serve_metrics.shed >= 1
    finally:
        faults.reset()


def test_backend_port_env_rebinds_gateway_to_loopback(monkeypatch):
    """Frontend mode: PATHWAY_SERVE_BACKEND_PORT makes the gateway bind
    the loopback backend port while keeping its public identity — and
    with PATHWAY_SERVE_PUBLIC_PORT set, ONLY the webserver configured
    on the frontend's public port rewrites (a second webserver keeps
    its own port instead of colliding on the backend bind)."""
    monkeypatch.setenv("PATHWAY_SERVE_BACKEND_PORT", "9555")
    web = pw.io.http.PathwayWebserver(host="0.0.0.0", port=8080)
    assert (web.host, web.port) == ("127.0.0.1", 9555)
    assert (web.public_host, web.public_port) == ("0.0.0.0", 8080)
    monkeypatch.setenv("PATHWAY_SERVE_PUBLIC_PORT", "8080")
    web_match = pw.io.http.PathwayWebserver(host="0.0.0.0", port=8080)
    assert (web_match.host, web_match.port) == ("127.0.0.1", 9555)
    web_other = pw.io.http.PathwayWebserver(host="0.0.0.0", port=8082)
    assert (web_other.host, web_other.port) == ("0.0.0.0", 8082)
    monkeypatch.delenv("PATHWAY_SERVE_BACKEND_PORT")
    monkeypatch.delenv("PATHWAY_SERVE_PUBLIC_PORT")
    web2 = pw.io.http.PathwayWebserver(host="0.0.0.0", port=8081)
    assert (web2.host, web2.port) == ("0.0.0.0", 8081)


def test_frontend_parks_then_deadline_503_with_retry_after():
    """A request admitted while no backend epoch exists parks; when its
    deadline budget expires still parked it gets a terminal 503 with
    Retry-After — never a dropped connection. /healthz reports
    recovering (503) meanwhile."""
    from pathway_tpu.io.http import ServingFrontend

    port = _next_port()
    backend_port = _next_port()  # nothing ever listens here
    fe = ServingFrontend(
        host="127.0.0.1", port=port, backend_port=backend_port,
        timeout_s=0.8,
    ).start()
    try:
        with pytest.raises(urllib.error.HTTPError) as hz:
            urllib.request.urlopen(
                f"http://127.0.0.1:{port}/healthz", timeout=5
            )
        assert hz.value.code == 503
        assert json.loads(hz.value.read().decode())["state"] == "recovering"
        t0 = time.monotonic()
        with pytest.raises(urllib.error.HTTPError) as e:
            _post(f"http://127.0.0.1:{port}/", {"value": 1}, timeout=15)
        assert e.value.code == 503
        assert int(e.value.headers.get("Retry-After")) >= 1
        assert 0.5 < time.monotonic() - t0 < 10
        m = fe.metrics
        assert m.parked == 1 and m.deadline_expired == 1
        assert m.admitted == m.responses + m.deadline_expired + m.timeouts
        # the satellite metric families render
        text = m.render()
        for fam in (
            "serve_parked_total", "serve_replayed_total",
            "serve_deadline_expired_total",
            "serve_epoch_handoff_seconds_bucket",
        ):
            assert fam in text, fam
    finally:
        fe.stop()


def test_frontend_draining_sheds_with_retry_after():
    from pathway_tpu.io.http import ServingFrontend

    port = _next_port()
    fe = ServingFrontend(
        host="127.0.0.1", port=port, backend_port=_next_port(),
        timeout_s=5.0,
    ).start()
    try:
        fe.drain()
        time.sleep(0.2)
        with pytest.raises(urllib.error.HTTPError) as e:
            _post(f"http://127.0.0.1:{port}/", {"value": 1}, timeout=10)
        assert e.value.code == 503
        assert e.value.headers.get("Retry-After") is not None
        assert fe.metrics.shed == 1 and fe.metrics.admitted == 0
        assert fe.state() == "draining"
    finally:
        fe.stop()


def test_keepalive_session_retries_503_honoring_retry_after():
    """Satellite: a 503 with Retry-After is the documented backpressure
    contract — with retries opted in the session honors it (bounded);
    without, it stays terminal. 503s lacking Retry-After never retry."""
    import http.server

    from pathway_tpu.io.http import HttpError, KeepAliveSession

    hits = {"n": 0, "bare": 0}

    class H(http.server.BaseHTTPRequestHandler):
        def do_POST(self):
            self.rfile.read(int(self.headers.get("Content-Length", 0)))
            if self.path == "/bare503":
                hits["bare"] += 1
                body = b'{"error": "no retry-after"}'
                self.send_response(503)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)
                return
            hits["n"] += 1
            if hits["n"] <= 2:
                body = b'{"error": "overloaded"}'
                self.send_response(503)
                self.send_header("Retry-After", "0")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)
            else:
                body = b'42'
                self.send_response(200)
                self.send_header("Degraded", "true")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

        def log_message(self, *a):
            pass

    srv = http.server.ThreadingHTTPServer(("127.0.0.1", 0), H)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    port = srv.server_address[1]
    try:
        # opted in: two sheds then success
        s = KeepAliveSession(f"http://127.0.0.1:{port}", retries=3)
        assert s.post("/", {}) == 42
        assert hits["n"] == 3
        # a 200 still carries the contract's headers (brownout answers)
        assert s.last_headers.get("Degraded") == "true"
        # budget exhausted -> the last 503 propagates with headers
        hits["n"] = -10
        s2 = KeepAliveSession(f"http://127.0.0.1:{port}", retries=1)
        with pytest.raises(HttpError) as e:
            s2.post("/", {})
        assert e.value.code == 503
        assert e.value.headers.get("Retry-After") == "0"
        # not opted in: terminal on the first 503 (old behavior)
        hits["n"] = 0
        s3 = KeepAliveSession(f"http://127.0.0.1:{port}")
        with pytest.raises(HttpError):
            s3.post("/", {})
        assert hits["n"] == 1
        # no Retry-After -> no retry even when opted in
        s4 = KeepAliveSession(f"http://127.0.0.1:{port}", retries=5)
        with pytest.raises(HttpError):
            s4.post("/bare503", {})
        assert hits["bare"] == 1
    finally:
        srv.shutdown()


def test_rag_and_vector_clients_expose_retries():
    from pathway_tpu.xpacks.llm.question_answering import RAGClient
    from pathway_tpu.xpacks.llm.vector_store import VectorStoreClient

    c1 = VectorStoreClient(host="127.0.0.1", port=1, retries=2)
    assert c1._session.retries == 2
    c2 = RAGClient(host="127.0.0.1", port=1, retries=3)
    assert c2._session.retries == 3
    # default stays terminal-on-503
    assert VectorStoreClient(host="127.0.0.1", port=1)._session.retries == 0


def test_serve_rollback_knobs_registered(monkeypatch):
    from pathway_tpu.analysis.knobs import KNOBS, validate_environment

    for name in (
        "PATHWAY_SERVE_BROWNOUT", "PATHWAY_SERVE_BREAKER_THRESHOLD",
        "PATHWAY_SERVE_BREAKER_COOLDOWN_S", "PATHWAY_SERVE_PARK_BUDGET",
        "PATHWAY_SERVE_BACKEND_PORT",
    ):
        assert name in KNOBS, name
    monkeypatch.setenv("PATHWAY_SERVE_BROWNOUT", "1")
    monkeypatch.setenv("PATHWAY_SERVE_BREAKER_THRESHOLD", "2")
    monkeypatch.setenv("PATHWAY_SERVE_PARK_BUDGET", "64")
    monkeypatch.setenv("PATHWAY_SERVE_BACKEND_PORT", "9000")
    assert validate_environment() == []
    monkeypatch.setenv("PATHWAY_SERVE_BACKEND_PORT", "0")
    assert any(
        n == "PATHWAY_SERVE_BACKEND_PORT"
        for n, _, _ in validate_environment()
    )


def test_readyz_states_serving_draining_recovering():
    """Readiness states on the metrics server's /readyz: serving
    answers 200 ok; draining/recovering answer 503 with the state name
    so load balancers rotate away during the blip. /healthz stays an
    unconditional-200 LIVENESS probe — a 503 there during a rollback
    would make kubelet kill the pod mid-recovery."""
    import socket as _socket

    from pathway_tpu.internals.monitoring import (
        ProberStats, start_http_server,
    )

    probe = _socket.socket()
    probe.bind(("127.0.0.1", 0))
    port = probe.getsockname()[1]
    probe.close()
    stats = ProberStats()
    start_http_server(stats, port)
    with urllib.request.urlopen(
        f"http://127.0.0.1:{port}/readyz", timeout=5
    ) as r:
        assert r.status == 200 and r.read() == b"ok\n"
    for state in ("draining", "recovering"):
        stats.set_health_state(state)
        with pytest.raises(urllib.error.HTTPError) as e:
            urllib.request.urlopen(
                f"http://127.0.0.1:{port}/readyz", timeout=5
            )
        assert e.value.code == 503
        assert e.value.read().decode().strip() == state
        # liveness is state-independent
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/healthz", timeout=5
        ) as r:
            assert r.status == 200 and r.read() == b"ok\n"


def test_serve_fault_points_registered():
    from pathway_tpu.internals.faults import POINTS

    for p in ("serve.dispatch", "serve.park", "serve.replay"):
        assert p in POINTS, p


def test_retry_after_honesty_under_memory_ladder():
    """503s minted during a memory-ladder episode size Retry-After from
    the SAME pace_retry_after transition the pacing model checks —
    in-flight backlog over the EWMA drain rate — not the rolling-qps
    guess that reads near-zero exactly when the governor throttles."""
    import math

    from pathway_tpu.parallel import protocol as proto

    port = _next_port()
    subject, _url = _gateway(port)

    # a seeded drain rate: 2 responses/s with 7 in flight -> ceil(3.5)
    subject._done_rate_ewma = 2.0
    subject._inflight = 7
    for state in ("pacing", "brownout", "abort"):
        want = max(1, math.ceil(proto.pace_retry_after(7, 2.0)))
        assert subject._retry_after_s(state) == want == 4
    # drain rate unobserved -> the clamped long horizon, never "now"
    subject._done_rate_ewma = 0.0
    assert subject._retry_after_s("brownout") == 600
    # nothing in flight -> floor of one pending unit at the seeded rate
    subject._done_rate_ewma = 2.0
    subject._inflight = 0
    assert subject._retry_after_s("pacing") == max(
        1, math.ceil(proto.pace_retry_after(1, 2.0))
    )
    # ladder ok -> the legacy rolling-qps path is untouched
    assert subject._retry_after_s("ok") == 1
    assert subject._retry_after_s() == 1


def test_memory_brownout_sheds_503_then_recovers():
    """The serving breaker consumes the memory signal: while the
    installed accountant's ladder reads brownout/abort, requests shed
    503 with a paced Retry-After; once the ladder steps back to ok the
    same gateway serves 200s again."""
    from pathway_tpu.internals import memory as _memory

    port = _next_port()
    subject, url = _gateway(port)
    _start_run()
    try:
        assert _post(url, {"value": 5}) == 5 * 3

        acct = _memory.MemoryAccountant(
            environ={"PATHWAY_MEM_BUDGET_MB": "1"}
        )
        acct.state = "brownout"
        _memory.install(acct)
        shed_before = subject.serve_metrics.shed
        with pytest.raises(urllib.error.HTTPError) as e:
            _post(url, {"value": 6})
        assert e.value.code == 503
        assert int(e.value.headers.get("Retry-After")) >= 1
        assert "memory pressure" in e.value.read().decode()
        assert subject.serve_metrics.shed == shed_before + 1

        acct.state = "ok"
        assert _post(url, {"value": 7}) == 7 * 3
    finally:
        _memory.install(None)


def test_span_clock_check_counts_window_closes(monkeypatch):
    """``scripts/span_clock_check.py`` reads what closed each window of a
    traced stretch from its ``gateway.queue`` spans' ``closed`` arg: the
    windows closed inside the stretch, each trigger's share and its mean
    members; nothing from spans that do not say."""
    import importlib.util
    import os
    import sys

    monkeypatch.setattr(sys, "path", list(sys.path))  # the script adds to it
    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "scripts", "span_clock_check.py",
    )
    spec = importlib.util.spec_from_file_location("span_clock_check", path)
    script = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(script)

    def queued(key, closed_at, window, closed):
        return (key, "gateway.queue", closed_at - 5, closed_at, 0, None, key,
                "window", window, "closed", closed)

    ring = [
        queued(1, 100, 1, "idle"),
        queued(2, 200, 2, "answered"),
        queued(3, 200, 2, "answered"),
        queued(4, 300, 3, "timer"),
        queued(5, 5000, 4, "timer"),  # closed after the stretch
        (6, "gateway.pickup", 100, 104, 0, None, None, "window", 1),
    ]
    assert script.window_closes(ring, 0, 1000) == {
        "windows": 3,
        "share": {"idle": 1 / 3, "answered": 1 / 3, "timer": 1 / 3},
        "members_mean": {"idle": 1.0, "answered": 2.0, "timer": 1.0},
    }
    bare = [(1, "gateway.queue", 0, 10, 0, None, 1, "window", 1)]
    assert script.window_closes(bare, 0, 100) is None
    assert script.window_closes([], 0, 100) is None
