"""Flight recorder battery (ISSUE 8): trace schema, 2-rank merge,
hot-path blame, metrics-registry drift pin, /healthz, OTLP drain,
event-time lag watermarks, dashboard unification, native ring."""

import json
import os
import threading
import urllib.request

import pytest

import pathway_tpu as pw
from pathway_tpu.analysis.profile import (
    profile_trace,
    render_profile,
    validate_trace,
)
from pathway_tpu.internals.monitoring import (
    ProberStats,
    ServeMetrics,
    render_dashboard,
    start_http_server,
)


def _wordcount(n_rows=3000, batches=3, distinct=40):
    class Source(pw.io.python.ConnectorSubject):
        _deletions_enabled = False

        def run(self):
            per = n_rows // batches
            for b in range(batches):
                self.next_batch(
                    [
                        {"data": f"w{i % distinct}"}
                        for i in range(b * per, (b + 1) * per)
                    ]
                )
                self.commit()

    class S(pw.Schema):
        data: str

    t = pw.io.python.read(Source(), schema=S, autocommit_duration_ms=None)
    counts = t.groupby(pw.this.data).reduce(
        word=pw.this.data, c=pw.reducers.count()
    )
    seen = []
    pw.io.subscribe(counts, on_change=lambda *a: seen.append(1))
    return seen


def _run_traced(tmp_path, monkeypatch, name="trace.json", lane=None):
    path = str(tmp_path / name)
    monkeypatch.setenv("PATHWAY_TRACE", path)
    if lane is not None:
        monkeypatch.setenv("PATHWAY_LANE_PROCESSES", str(lane))
    _wordcount()
    pw.run(monitoring_level=pw.MonitoringLevel.NONE)
    return path


# -- trace schema --------------------------------------------------------

def test_trace_single_rank_schema(tmp_path, monkeypatch):
    monkeypatch.delenv("PATHWAY_LANE_PROCESSES", raising=False)
    path = _run_traced(tmp_path, monkeypatch)
    doc = json.load(open(path))
    assert validate_trace(doc) == []
    evs = doc["traceEvents"]
    cats = {e.get("cat") for e in evs}
    assert {"node", "step", "lag"} <= cats
    # node spans carry the args the blame pass joins on
    node_evs = [e for e in evs if e.get("cat") == "node"]
    assert node_evs
    for e in node_evs:
        assert {"node", "rows", "rep"} <= set(e["args"])
        assert e["args"]["rep"] in ("nb", "tuple")
    # per-node self-times sum to <= the step-span wall time (process()
    # is the node's self-time; steps bracket all node work at a commit)
    self_us = sum(e["dur"] for e in node_evs)
    step_us = sum(e["dur"] for e in evs if e.get("cat") == "step")
    assert 0 < self_us <= step_us * 1.001
    # spans nest: every node span sits inside some step span
    steps = [
        (e["ts"], e["ts"] + e["dur"])
        for e in evs
        if e.get("cat") == "step"
    ]
    eps = 2e-3
    for e in node_evs:
        assert any(
            t0 - eps <= e["ts"] and e["ts"] + e["dur"] <= t1 + eps
            for t0, t1 in steps
        ), "node span outside every step span"
    # plan metadata is embedded for the blame join: verdicts come from
    # the SAME NBDecision objects the executor gates on
    nodes = doc["pathway"]["nodes"]
    assert any(m.get("verdict") == "fused" for m in nodes.values())
    assert any(m.get("row_expanding") for m in nodes.values())
    # event-time lag watermarks: non-negative freshness per output
    lags = [e for e in evs if e.get("cat") == "lag"]
    assert lags and all(e["args"]["lag_ms"] >= 0 for e in lags)


def test_trace_two_rank_merged(tmp_path, monkeypatch):
    path = _run_traced(tmp_path, monkeypatch, lane=2)
    doc = json.load(open(path))
    assert validate_trace(doc) == []
    assert doc["pathway"]["merged_ranks"] == [0, 1]
    evs = doc["traceEvents"]
    assert {e["pid"] for e in evs} == {0, 1}
    cats = {e.get("cat") for e in evs}
    assert {"wave", "mesh", "mark"} <= cats
    marks = {e["name"] for e in evs if e.get("cat") == "mark"}
    assert "mesh_join" in marks
    # per-track monotonic timestamps (the offset shift must not reorder
    # a rank's track) — validate_trace pins this, assert it directly too
    last = {}
    for e in evs:
        if e.get("ph") == "M":
            continue
        key = (e["pid"], e["tid"])
        assert e["ts"] >= last.get(key, float("-inf")) - 2e-3
        last[key] = e["ts"]
    # merge consumed the partials
    assert not os.path.exists(path + ".r0")
    assert not os.path.exists(path + ".r1")
    # clock offsets were sampled during the epoch's clock handshake
    meta = doc["pathway"]["rank_meta"]
    assert meta["rank0"]["clock_offset_ns"] == 0
    assert "clock_offset_ns" in meta["rank1"]


def test_no_trace_file_without_knob(tmp_path, monkeypatch):
    monkeypatch.delenv("PATHWAY_TRACE", raising=False)
    monkeypatch.delenv("PATHWAY_LANE_PROCESSES", raising=False)
    _wordcount(n_rows=200, batches=1)
    pw.run(monitoring_level=pw.MonitoringLevel.NONE)
    assert list(tmp_path.iterdir()) == []


# -- hot-path blame (analysis --profile) ---------------------------------

def test_profile_names_top_node_with_verdict(tmp_path, monkeypatch):
    monkeypatch.delenv("PATHWAY_LANE_PROCESSES", raising=False)
    path = _run_traced(tmp_path, monkeypatch)
    report = profile_trace(path, top_k=3)
    assert report["valid"], report["problems"]
    assert report["top"]
    labels = {r["label"] for r in report["top"]}
    assert any("GroupByNode" in lb for lb in labels)
    verdicts = {r["label"]: r["verdict"] for r in report["top"]}
    gb = next(lb for lb in labels if "GroupByNode" in lb)
    assert verdicts[gb] == "fused"
    sink = [r for r in report["top"] if "sink" in r["verdict"]]
    assert sink, "row-expanding sink not named"
    text = render_profile(report)
    assert "top nodes by self-time" in text and "fused" in text


def test_profile_cli_exit_codes(tmp_path, monkeypatch):
    monkeypatch.delenv("PATHWAY_LANE_PROCESSES", raising=False)
    path = _run_traced(tmp_path, monkeypatch)
    from pathway_tpu.analysis.__main__ import main as cli_main

    assert cli_main(["--profile", path]) == 0
    bad = tmp_path / "bad.json"
    bad.write_text("{}")
    assert cli_main(["--profile", str(bad)]) == 2


def test_profile_flags_measured_degradation(tmp_path):
    """A node whose static verdict says fused but whose batches executed
    on the tuple path is a MEASURED degradation — the blame pass must
    say so instead of parroting the static verdict."""
    doc = {
        "traceEvents": [
            {
                "name": "GroupByNode#1", "cat": "node", "ph": "X",
                "pid": 0, "tid": 0, "ts": 10.0, "dur": 5.0,
                "args": {"node": 1, "t": 1, "rows": 10, "rep": "tuple"},
            },
        ],
        "pathway": {
            "schema": 1,
            "nodes": {"1": {"label": "GroupByNode#1", "verdict": "fused"}},
        },
    }
    p = tmp_path / "t.json"
    p.write_text(json.dumps(doc))
    report = profile_trace(str(p))
    assert "degraded at runtime" in report["top"][0]["verdict"]


# -- metrics registry drift pin ------------------------------------------

# every ProberStats on_* hook must surface in render_openmetrics() (or be
# explicitly exempted as dashboard-only state). A NEW hook without an
# entry here fails the completeness assertion — the knob-registry
# pattern applied to the metrics surface.
_PROBER_CALLS = {
    "on_ingest": ("conn_a", 5),
    "on_connector_restart": ("conn_a",),
    "on_connector_error": ("conn_a",),
    "on_connector_stall": ("conn_a",),
    "on_connector_degraded": ("conn_a",),
    # source pacing (ISSUE 19): gate engaged / live per-pass accrual /
    # episode closed — connector_paused gauge + paused_seconds counter
    "on_connector_paused": ("conn_a",),
    "on_connector_paced": ("conn_a", 1.5),
    "on_connector_resumed": ("conn_a", 0.5),
    "on_output": (3,),
    "on_output_lag": ("out_a", 5.0),
    "on_node_step": ("node_a", 0.25, 7, True),
    "on_exchange_frame": (128,),
    "on_exchange_elided": (2,),
    "on_exchange_fallback": (),
    "on_nb_fallback": (),
    "on_exchange_step": (0.1, 0.2),
    # cluster observability (ISSUE 10): per-peer recv-wait, wave
    # counters, and main-loop idle seconds
    "on_exchange_recv_wait": (1, 0.25),
    "on_exchange_wave": (0.5,),
    # fast wire (ISSUE 13): per-frame bytes before/after the wire codec
    # (exchange_{un,}compressed_bytes_total + the per-peer matrix)
    "on_exchange_compression": (1, 4096, 1024),
    "on_idle": (0.3,),
    "on_mesh_heartbeat_missed": (),
    "on_mesh_rank_restart": (),
    "on_mesh_rollback": (),
    "on_mesh_epoch_committed": (4,),
    # transactional egress (ISSUE 12): 2PC sink counters + epoch lag
    "on_sink_staged": ("sink_a",),
    "on_sink_finalized": ("sink_a", 2),
    "on_sink_aborted": ("sink_a", 1),
    "on_sink_recovered": ("sink_a", 1),
    "on_sink_epoch_lag": ("sink_a", 3),
    # columnar egress (ISSUE 14): arrow-delivered vs row-expanded rows
    # at the sinks + per-sink egress seconds
    "on_capture_arrow_batch": (7,),
    "on_capture_rows_expanded": (7,),
    "on_sink_egress_seconds": ("sink_a", 0.05),
    # device plane (ISSUE 15): per-dispatch-site device/wall seconds,
    # FLOPs, transfer bytes and queue depth — the device_* families.
    # Trailing arg (ISSUE 16): effective FLOPs (real rows only).
    "on_device_dispatch": (
        "knn.search", 0.5, 0.4, 1e9, 1e6, 4096, 2, 8e8,
    ),
    # shape-bucket churn visibility (ISSUE 16): fresh XLA compilations
    # per dispatch site — device_recompiles_total
    "on_device_recompile": ("encoder.forward",),
    # device fault domain (ISSUE 17): dispatch supervision verdicts,
    # watchdog trips, HBM-growth OOM refusals, and the epoch-aligned
    # index snapshot/restore accounting
    "on_device_dispatch_retry": ("knn.search",),
    "on_device_dispatch_failure": ("knn.search",),
    "on_device_watchdog_trip": ("knn.search",),
    "on_device_oom": ("knn.grow",),
    "on_index_restore_seconds": (1.5,),
    "on_index_snapshot_bytes": (4096,),
    "on_index_filter_error": (2,),
}
# state consumed by the dashboard/main loop, not an OpenMetrics family
_PROBER_EXEMPT = {"on_connector_finished"}

_SERVE_CALLS = {
    "on_request": (),
    "on_shed": (),
    "on_timeout": (),
    "on_latency_ms": (12.5,),
    "on_window": (4,),
    # batch windows closed, by trigger (serve_window_closes_total)
    "on_window_close": ("idle",),
    # serving-through-rollback (ISSUE 9): brownout answers and windows
    # aborted at an epoch rollback
    "on_brownout": (),
    "on_windows_aborted": (2,),
}


def test_metrics_registry_every_hook_renders():
    hooks = {
        n for n in dir(ProberStats)
        if n.startswith("on_") and callable(getattr(ProberStats, n))
    }
    assert hooks == set(_PROBER_CALLS) | _PROBER_EXEMPT, (
        "new ProberStats on_* hook: map it to a rendered OpenMetrics "
        "family in _PROBER_CALLS (or exempt it with a reason)"
    )
    for name, args in _PROBER_CALLS.items():
        stats = ProberStats()
        before = stats.render_openmetrics()
        getattr(stats, name)(*args)
        after = stats.render_openmetrics()
        assert after != before, (
            f"{name} incremented state that render_openmetrics() never "
            "surfaces — silent metrics drift"
        )
    serve_hooks = {
        n for n in dir(ServeMetrics)
        if n.startswith("on_") and callable(getattr(ServeMetrics, n))
    }
    assert serve_hooks == set(_SERVE_CALLS)
    for name, args in _SERVE_CALLS.items():
        stats = ProberStats()
        sm = ServeMetrics(route="/v1/q")
        stats.mount_serve_metrics(sm)
        before = stats.render_openmetrics()
        getattr(sm, name)(*args)
        assert stats.render_openmetrics() != before, name


def test_openmetrics_every_family_has_a_sample():
    stats = ProberStats()
    for name, args in _PROBER_CALLS.items():
        getattr(stats, name)(*args)
    sm = ServeMetrics(route="/v1/q")
    stats.mount_serve_metrics(sm)
    for name, args in _SERVE_CALLS.items():
        getattr(sm, name)(*args)
    text = stats.render_openmetrics()
    lines = text.splitlines()
    for i, line in enumerate(lines):
        if not line.startswith("# TYPE "):
            continue
        family = line.split()[2]
        rest = lines[i + 1:]
        assert any(
            ln.startswith(family) for ln in rest if not ln.startswith("#")
        ), f"family {family} declared but has no sample"
    # the new node/lag families render with their labels
    assert 'node_self_seconds_total{node="node_a"}' in text
    assert 'node_rows_total{node="node_a"} 7' in text
    assert 'output_lag_ms_bucket{output="out_a",le="5"}' in text


# -- /healthz + log silence ----------------------------------------------

def test_http_server_healthz_and_metrics():
    import socket

    probe = socket.socket()
    probe.bind(("127.0.0.1", 0))
    port = probe.getsockname()[1]
    probe.close()
    stats = ProberStats()
    stats.on_ingest("c1", 3)
    start_http_server(stats, port)
    with urllib.request.urlopen(
        f"http://127.0.0.1:{port}/healthz", timeout=5
    ) as r:
        assert r.status == 200
        assert r.read() == b"ok\n"
    with urllib.request.urlopen(
        f"http://127.0.0.1:{port}/metrics", timeout=5
    ) as r:
        body = r.read().decode()
        assert 'connector_rows_total{connector="c1"} 3' in body


# -- OTLP flush-on-shutdown ----------------------------------------------

def test_otlp_drain_exports_buffered_spans_and_gauges():
    """Short runs must not exit with spans queued and gauges never
    pushed (the periodic thread is on a 60 s cadence): drain() pushes
    both, including the flight recorder's per-node aggregate spans."""
    import http.server

    received = []

    class Collector(http.server.BaseHTTPRequestHandler):
        def do_POST(self):
            n = int(self.headers["Content-Length"])
            received.append((self.path, json.loads(self.rfile.read(n))))
            self.send_response(200)
            self.end_headers()

        def log_message(self, *a):
            pass

    srv = http.server.HTTPServer(("127.0.0.1", 0), Collector)
    port = srv.server_address[1]
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    try:
        from pathway_tpu.internals.otlp import OtlpTelemetry

        tel = OtlpTelemetry(
            f"http://127.0.0.1:{port}", autostart_metrics=False
        )
        with tel.span("graph_runner.run"):
            pass
        t0 = 1_700_000_000_000_000_000
        tel.drain(
            node_spans=[
                {
                    "name": "node.GroupByNode#1",
                    "start_ns": t0,
                    "end_ns": t0 + 5_000_000,
                    "attrs": {"node.self_s": 0.005, "node.rows": 100},
                }
            ]
        )
    finally:
        srv.shutdown()
    paths = [p for p, _ in received]
    assert "/v1/metrics" in paths, "drain did not push gauges"
    span_names = [
        s["name"]
        for p, b in received
        if p == "/v1/traces"
        for rs in b["resourceSpans"]
        for ss in rs["scopeSpans"]
        for s in ss["spans"]
    ]
    assert "graph_runner.run" in span_names
    assert "node.GroupByNode#1" in span_names


# -- event-time lag watermarks (no recorder needed) ----------------------

def test_lag_watermark_populates_stats_without_tracing(monkeypatch):
    monkeypatch.delenv("PATHWAY_TRACE", raising=False)
    monkeypatch.delenv("PATHWAY_LANE_PROCESSES", raising=False)
    from pathway_tpu.internals.graph_runner import GraphRunner

    class Source(pw.io.python.ConnectorSubject):
        _deletions_enabled = False

        def run(self):
            self.next_batch([{"data": f"w{i % 10}"} for i in range(500)])
            self.commit()

    class S(pw.Schema):
        data: str

    t = pw.io.python.read(Source(), schema=S, autocommit_duration_ms=None)
    counts = t.groupby(pw.this.data).reduce(
        word=pw.this.data, c=pw.reducers.count()
    )
    pw.io.subscribe(counts, on_change=lambda *a: None)
    runner = GraphRunner()
    runtime = runner._make_runtime()
    ops = runner.graph.reachable_operators(runner.graph.output_operators())
    runner._lower(ops, runtime)
    runtime.run()
    assert runtime.stats.lag, "no output lag histogram recorded"
    (label, h), = list(runtime.stats.lag.items())
    assert "OutputNode" in label
    assert h.total >= 1 and h.sum >= 0.0
    text = runtime.stats.render_openmetrics()
    assert "output_lag_ms_count" in text


# -- dashboard unification ------------------------------------------------

def test_dashboard_covers_whole_pipeline():
    from rich.console import Console

    stats = ProberStats()
    stats.on_ingest("kafka:orders", 10)
    stats.on_exchange_frame(4096)
    stats.on_exchange_elided(3)
    stats.on_exchange_step(0.5, 1.5)
    stats.on_nb_fallback()
    stats.on_mesh_heartbeat_missed()
    stats.on_mesh_rollback()
    stats.on_mesh_epoch_committed(2)
    stats.on_output_lag("OutputNode#4", 12.0)
    stats.on_node_step("GroupByNode#2", 1.25, 9000, True)
    sm = ServeMetrics(route="/v1/retrieve")
    sm.on_request()
    sm.on_window(8)
    stats.mount_serve_metrics(sm)

    console = Console(record=True, width=120)
    console.print(render_dashboard(stats))
    text = console.export_text()
    assert "exchange frames/bytes" in text
    assert "nb_fallbacks" in text
    assert "mesh hb-missed/restarts/rollbacks" in text
    assert "serve /v1/retrieve" in text
    assert "event-time lag" in text
    assert "hot GroupByNode#2" in text


def test_profile_survives_malformed_node_events(tmp_path):
    """A truncated/foreign trace with a node event missing args must
    land on the documented exit-2 schema-problem path, not a KeyError
    traceback (review fix)."""
    doc = {
        "traceEvents": [
            {"name": "x", "cat": "node", "ph": "X", "pid": 0, "tid": 0,
             "ts": 1.0, "dur": 1.0},
        ],
        "pathway": {"schema": 1, "nodes": {}},
    }
    p = tmp_path / "t.json"
    p.write_text(json.dumps(doc))
    report = profile_trace(str(p))
    assert not report["valid"]
    assert any("missing node/rows/rep" in pr for pr in report["problems"])
    from pathway_tpu.analysis.__main__ import main as cli_main

    assert cli_main(["--profile", str(p)]) == 2


def test_recorder_event_cap_keeps_newest(tmp_path, monkeypatch):
    """PATHWAY_TRACE_MAX_EVENTS bounds the in-memory log of a
    long-running traced pipeline: newest events are kept, the dump
    records the capping (review fix — unbounded growth until OOM)."""
    monkeypatch.setenv("PATHWAY_TRACE_MAX_EVENTS", "10000")
    from pathway_tpu.internals.flight import FlightRecorder

    rec = FlightRecorder(str(tmp_path / "t.json"))
    assert rec.max_events == 10_000
    for i in range(25_000):
        rec.note_node(1, i, i, i + 1, 1, True)
    assert len(rec.events) == 10_000
    # newest survive
    assert rec.events[-1][2] == 24_999 and rec.events[0][2] == 15_000
    rec.dump(scope=None)
    doc = json.load(open(rec.path))
    assert doc["pathway"]["capped"] is True
    assert doc["pathway"]["event_cap"] == 10_000


# -- supervisor fallback merge -------------------------------------------

def test_supervisor_merges_leftover_partials(tmp_path, monkeypatch):
    """After a rollback the aborting epoch's partials (with their
    rollback marks) may outlive rank 0's merge — the MeshSupervisor
    re-merges them on its way out."""
    from pathway_tpu.internals.flight import FlightRecorder
    from pathway_tpu.parallel.supervisor import MeshSupervisor

    path = str(tmp_path / "t.json")
    for rank in range(2):
        rec = FlightRecorder(path, rank=rank, world=2)
        rec.note_mark("rollback", error="MeshPeerFailure('peer 1')")
        rec.dump_partial(scope=None)
    monkeypatch.setenv("PATHWAY_TRACE", path)
    sup = MeshSupervisor(["true"], processes=2)
    sup._merge_trace_fallback()
    doc = json.load(open(path))
    assert doc["pathway"]["merged_ranks"] == [0, 1]
    marks = [
        e for e in doc["traceEvents"] if e.get("name") == "rollback"
    ]
    assert len(marks) == 2
    assert not os.path.exists(path + ".r0")


# -- native ring ----------------------------------------------------------

def test_native_trace_ring_direct():
    from pathway_tpu.native import get_pwexec

    ex = get_pwexec()
    if ex is None or not hasattr(ex, "trace_ring_enable"):
        pytest.skip("native toolchain unavailable")
    try:
        ex.trace_ring_enable(2048, 4)
        from pathway_tpu.internals.api import Pointer

        nb = ex.nb_decode(ex.nb_encode(_make_nb(ex)), Pointer)
        assert len(nb) == 3
        evs = ex.trace_ring_drain()
        assert evs, "encode/decode produced no ring events"
        tags = {tag for tag, *_ in evs}
        assert {4, 5} <= tags  # nb_encode + nb_decode
        for _tag, thr, t0, t1, _rows in evs:
            assert t1 >= t0 >= 0 and thr >= 0
        assert ex.trace_ring_drain() == []  # drain resets
    finally:
        ex.trace_ring_disable()


def _make_nb(ex):
    from pathway_tpu.internals.api import Pointer

    nb = ex.parse_upserts_nb(
        [
            {"a": 1, "b": "x"},
            {"a": 2, "b": "y"},
            {"a": 3, "b": "z"},
        ],
        0,
        ("a", "b"),
        (None, None),
        12345,  # int128 key base, like io/python.py's minted key_base
        0,
        Pointer,
    )
    assert nb is not None
    return nb[0]
