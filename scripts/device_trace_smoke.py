#!/usr/bin/env python
"""CI device-trace smoke lane (scripts/ci_lanes.sh lane 14; ISSUE 15).

Runs a REAL embed+KNN pipeline (SentenceEncoder forward inside a
rowwise UDF -> BruteForceKnn ExternalIndexNode) in a forked process
with the flight recorder armed (``PATHWAY_TRACE``) and the OpenMetrics
server on, then asserts the device observability chain end to end:

1. ``/metrics`` shows a NONZERO ``device_dispatch_seconds_total`` (and
   the ``device_mfu`` / ``device_hbm_peak_bytes`` gauges render) LIVE
   while the pipeline streams;
2. the trace contains device tracks: spans with ``cat == "device"``
   carrying dispatch ids, device time, FLOPs — correlated to node spans
   by their ``node`` arg — and validates against the trace schema;
3. ``python -m pathway_tpu.analysis --profile`` exits 0 and names the
   top dispatch site with its roofline verdict
   (compute-bound / bandwidth-bound / host-bound).

``--bench`` additionally measures the armed device plane's overhead on
the embed+KNN hot loop as INTERLEAVED armed/disarmed pairs (same
methodology as the PR 8 relational lanes; bar: <= 3%). A CPU count, not
a device number. What the always-on span ring costs on the chip is
measured by ``scripts/span_clock_check.py`` and ``PERF.md`` section 3.

Exit 0 = green; any assertion prints the reason and exits 1.
"""

from __future__ import annotations

import json
import os
import re
import subprocess
import sys
import tempfile
import time
import urllib.request

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

METRICS_PORT = 20000

PROGRAM = """
import os, sys, time
sys.path.insert(0, {repo!r})
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import numpy as np
import pathway_tpu as pw
from pathway_tpu.models.encoder import EncoderConfig, SentenceEncoder

enc = SentenceEncoder(EncoderConfig.tiny())
DIM = enc.embed_dim
DOCS = [f"document {{i}} about topic {{i % 13}}" for i in range(240)]

class Docs(pw.io.python.ConnectorSubject):
    _deletions_enabled = False
    def run(self):
        for s in range(0, len(DOCS), 24):
            self.next_batch([{{"text": t}} for t in DOCS[s : s + 24]])
            self.commit()
            time.sleep(0.25)  # paced so the parent can scrape LIVE

class DocSchema(pw.Schema):
    text: str

class Queries(pw.io.python.ConnectorSubject):
    _deletions_enabled = False
    def run(self):
        for i in range(10):
            self.next_batch([{{"q": f"topic {{i % 13}}"}}])
            self.commit()
            time.sleep(0.25)

class QSchema(pw.Schema):
    q: str

def embed(text):
    return tuple(float(x) for x in enc.encode([text])[0])

docs = pw.io.python.read(Docs(), schema=DocSchema,
                         autocommit_duration_ms=None)
docs = docs.select(pw.this.text, vec=pw.apply_with_type(embed, tuple,
                                                        pw.this.text))
queries = pw.io.python.read(Queries(), schema=QSchema,
                            autocommit_duration_ms=None)
queries = queries.select(pw.this.q, qvec=pw.apply_with_type(embed, tuple,
                                                            pw.this.q))

from pathway_tpu.stdlib.indexing import BruteForceKnn
index = BruteForceKnn(data_column=docs.vec, dimensions=DIM, metric="cos")
res = index.query_as_of_now(queries.qvec, number_of_matches=3)
pw.io.subscribe(
    res.select(pw.this.q, ids=pw.this._pw_index_reply),
    on_change=lambda *a: None,
)
pw.run(monitoring_level=pw.MonitoringLevel.NONE, with_http_server=True)
"""


def fail(msg: str) -> None:
    print(f"device_trace_smoke: FAIL — {msg}", file=sys.stderr)
    raise SystemExit(1)


def _scrape(port: int) -> str | None:
    try:
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=2
        ) as r:
            return r.read().decode()
    except Exception:
        return None


def _metric(text: str, name: str) -> float | None:
    m = re.search(rf"^{re.escape(name)} (\S+)$", text, re.M)
    return float(m.group(1)) if m else None


def run_smoke() -> None:
    td = tempfile.mkdtemp(prefix="pw_device_smoke_")
    trace = os.path.join(td, "trace.json")
    prog = os.path.join(td, "embed_knn.py")
    with open(prog, "w") as f:
        f.write(PROGRAM.format(repo=REPO))
    env = dict(os.environ)
    env.update(
        PATHWAY_TRACE=trace, JAX_PLATFORMS="cpu", PYTHONPATH=REPO
    )
    env.pop("PATHWAY_LANE_PROCESSES", None)
    env.pop("PATHWAY_PROCESSES", None)
    proc = subprocess.Popen(
        [sys.executable, prog], env=env, cwd=td,
        stdout=subprocess.PIPE, stderr=subprocess.PIPE,
    )
    # 1. live /metrics: nonzero device dispatch seconds while streaming
    live_ok = False
    live_text = ""
    deadline = time.monotonic() + 90
    while time.monotonic() < deadline and proc.poll() is None:
        text = _scrape(METRICS_PORT)
        if text:
            live_text = text
            secs = _metric(text, "device_dispatch_seconds_total")
            if secs is not None and secs > 0:
                live_ok = True
                break
        time.sleep(0.3)
    try:
        out, err = proc.communicate(timeout=120)
    except subprocess.TimeoutExpired:
        proc.kill()
        out, err = proc.communicate()
        fail("pipeline did not finish")
    if proc.returncode != 0:
        fail(
            f"pipeline exited {proc.returncode}\n"
            f"{err.decode(errors='replace')[-2000:]}"
        )
    if not live_ok:
        fail(
            "live /metrics never showed device_dispatch_seconds_total "
            f"> 0\nlast scrape:\n{live_text[-1500:]}"
        )
    for gauge in ("device_mfu", "device_hbm_peak_bytes"):
        if _metric(live_text, gauge) is None:
            fail(f"{gauge} gauge missing from /metrics")
    print("device_trace_smoke: live /metrics shows device dispatches "
          f"({_metric(live_text, 'device_dispatch_seconds_total'):.4f}s)")

    # 2. the trace carries device tracks correlated to node spans
    if not os.path.exists(trace):
        fail("trace file missing")
    doc = json.load(open(trace))
    from pathway_tpu.analysis.profile import profile_trace, validate_trace

    problems = validate_trace(doc)
    if problems:
        fail(f"trace schema problems: {problems[:5]}")
    devs = [e for e in doc["traceEvents"] if e.get("cat") == "device"]
    if not devs:
        fail("no device spans in the trace")
    sites = {e["name"] for e in devs}
    if not sites & {"knn.search", "knn.write", "encoder.forward"}:
        fail(f"unexpected device sites: {sites}")
    node_spans = {
        e["args"]["node"]
        for e in doc["traceEvents"]
        if e.get("cat") == "node"
    }
    engine_devs = [
        e for e in devs if e["args"].get("node") is not None
    ]
    if not engine_devs:
        fail("no device span carries an engine node id")
    for e in engine_devs:
        if e["args"]["node"] not in node_spans:
            fail(
                f"device span (dispatch {e['args']['dispatch']}) names "
                f"node {e['args']['node']} with no correlated node span"
            )
    print(
        f"device_trace_smoke: {len(devs)} device spans on "
        f"{len(sites)} tracks, all correlated"
    )

    # 3. --profile exits 0 and names the top dispatch with its verdict
    from pathway_tpu.analysis.__main__ import main as cli_main

    rc = cli_main(["--profile", trace])
    if rc != 0:
        fail(f"--profile exited {rc}")
    report = profile_trace(trace)
    dev = report.get("device")
    if not dev or not dev["sites"]:
        fail("--profile report has no device section")
    top = dev["sites"][0]
    if top["verdict"] not in (
        "compute-bound", "bandwidth-bound", "host-bound"
    ):
        fail(f"bad roofline verdict: {top['verdict']!r}")
    print(
        "device_trace_smoke: top dispatch "
        f"{top['site']} ({top['dispatches']} dispatches, "
        f"mfu {top['mfu']:.4f}) -> {top['verdict']}"
    )


def measure_overhead() -> None:
    """Interleaved traced-vs-untraced pairs on the embed+KNN hot loop
    (in-process; the device plane armed with a live recorder so the
    full note path is paid)."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import numpy as np  # noqa: F401

    from pathway_tpu.internals.device import PLANE
    from pathway_tpu.internals.flight import FlightRecorder
    from pathway_tpu.internals.monitoring import ProberStats
    from pathway_tpu.models.encoder import EncoderConfig, SentenceEncoder
    from pathway_tpu.ops.knn import KnnShard

    enc = SentenceEncoder(EncoderConfig.tiny())
    shard = KnnShard(enc.embed_dim, capacity=1024)
    texts = [f"doc {i} topic {i % 17}" for i in range(256)]
    keys = [f"k{j}" for j in range(len(texts))]

    def one_pass():
        emb = enc.encode(texts)
        shard.add(keys, emb)
        shard.search(emb[:16], 5)

    td = tempfile.mkdtemp(prefix="pw_device_bench_")
    stats = ProberStats()
    rec = FlightRecorder(os.path.join(td, "bench_trace.json"))
    one_pass()
    PLANE.arm(rec, stats)
    one_pass()
    PLANE.disarm()
    pairs = 11
    on_s, off_s, ratios = [], [], []
    for _ in range(pairs):
        PLANE.arm(rec, stats)
        t0 = time.perf_counter()
        one_pass()
        on_s.append(time.perf_counter() - t0)
        PLANE.disarm()
        t0 = time.perf_counter()
        one_pass()
        off_s.append(time.perf_counter() - t0)
        ratios.append(on_s[-1] / off_s[-1])
    on_med = sorted(on_s)[pairs // 2]
    off_med = sorted(off_s)[pairs // 2]
    # per-pair ratio median: each pair shares its moment's machine
    # noise, so the ratio is the stable estimator on a loaded host
    overhead_pct = 100.0 * (sorted(ratios)[pairs // 2] - 1.0)
    print(
        f"device_trace_smoke: overhead traced={on_med:.4f}s "
        f"untraced={off_med:.4f}s -> {overhead_pct:+.2f}% "
        f"(median of {pairs} interleaved pair ratios)"
    )
    if overhead_pct > 3.0:
        fail(f"device-plane overhead {overhead_pct:.2f}% > 3%")


def main() -> int:
    bench_only = "--bench-only" in sys.argv
    if not bench_only:
        run_smoke()
    if bench_only or "--bench" in sys.argv:
        measure_overhead()
    print("device_trace_smoke: PASS")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
