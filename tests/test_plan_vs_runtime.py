"""Analyzer-vs-runtime agreement battery: for the bench pipelines
(wordcount, stream_join, groupby; 1-, 2- and 4-rank), ``pw.analyze``
fused/degraded verdicts must match the observed runtime fallback
counters — zero false "fused" verdicts (ISSUE 5 acceptance criterion) —
and at N>1 the Plan Doctor's mesh-verifier verdict must agree with the
real mesh's rollback/restart counters (ISSUE 7 acceptance criterion).

The 1-rank cases lower once, analyze the SAME runtime statically, run
it, then audit counters. The 2- and 4-rank cases fork a real loopback
mesh and each rank audits itself.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile

import pytest

import pathway_tpu as pw
from pathway_tpu.analysis import analyzer as pa
from pathway_tpu.analysis import bench as pb

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _nb_toolchain() -> bool:
    try:
        from pathway_tpu.native import get_pwexec

        ex = get_pwexec()
    except Exception:
        return False
    return ex is not None and hasattr(ex, "parse_upserts_nb")


needs_nb = pytest.mark.skipif(
    not _nb_toolchain(), reason="native toolchain (pwexec) unavailable"
)


def _lower_analyze_run(out_table):
    """Lower the captured graph once, analyze that runtime statically,
    then run it; returns (runtime, report, capture)."""
    from pathway_tpu.engine.runtime import Runtime
    from pathway_tpu.internals.graph_runner import GraphRunner

    g = pw.internals.parse_graph.G
    targets = [out_table._source] + g.output_operators()
    ops = g.reachable_operators(targets)
    runtime = Runtime()
    ctx = GraphRunner()._lower(ops, runtime)
    report = pa.analyze_scope(runtime)
    cap = runtime.scope.capture(ctx.engine_table(out_table))
    runtime.run()
    return runtime, report, cap


def _counters(runtime):
    from pathway_tpu.engine import nodes as N

    joins = [n for n in runtime.scope.nodes if isinstance(n, N.JoinNode)]
    groupbys = [
        n for n in runtime.scope.nodes if isinstance(n, N.GroupByNode)
    ]
    return joins, groupbys


@needs_nb
@pytest.mark.parametrize(
    "build", [pb.build_wordcount, pb.build_stream_join, pb.build_groupby],
    ids=["wordcount", "stream_join", "groupby"],
)
def test_fused_verdict_matches_zero_fallbacks_1rank(build):
    bp = build()
    runtime, report, cap = _lower_analyze_run(bp.out)
    assert report.verdict == "fused", report.render()
    # zero false fused: no fallback counter moved anywhere
    assert pa.audit_runtime(runtime, report) == []
    assert runtime.stats.nb_fallbacks == 0
    assert runtime.stats.exchange_fallbacks == 0
    # and the fused path actually ran (the verdict is not vacuous)
    joins, groupbys = _counters(runtime)
    for n in joins + groupbys:
        assert n._nb_batches > 0, f"{type(n).__name__} never ran columnar"
    assert len(cap.state.rows) > 0


@needs_nb
def test_degraded_verdict_matches_fallback_counters_1rank():
    """A groupby over an expression key: the analyzer must call it
    degraded AND the runtime must count the de-optimized batches."""
    pw.internals.parse_graph.G.clear()
    words = ["a", "b", "c"]
    rows = [{"data": words[i % 3]} for i in range(120)]

    class Src(pw.io.python.ConnectorSubject):
        _deletions_enabled = False

        def run(self):
            for s in range(0, len(rows), 40):
                self.next_batch(rows[s : s + 40])
                self.commit()

    class S(pw.Schema):
        data: str

    t = pw.io.python.read(Src(), schema=S, autocommit_duration_ms=None)
    agg = t.groupby(pw.this.data + "!").reduce(c=pw.reducers.count())
    runtime, report, cap = _lower_analyze_run(agg)
    assert report.verdict == "degraded"
    [entry] = [n for n in report.nodes if n["kind"] == "groupby"]
    assert entry["verdict"] == "degraded"
    _joins, [gb] = _counters(runtime)
    assert gb._nb_batches == 0
    assert gb._nb_fallbacks > 0  # columnar input materialized per batch
    assert runtime.stats.nb_fallbacks == gb._nb_fallbacks
    assert pa.audit_runtime(runtime, report) == []  # no FUSED node lied


@needs_nb
def test_outer_join_pad_output_not_false_fused(monkeypatch):
    """A fused-eligible LEFT join keeps its input processing columnar,
    but pad transitions (a late right row flipping liveness) emit tuple
    batches. The analyzer must NOT call the chain downstream of the join
    fused, the runtime must NOT count those batches as fallbacks, and a
    strict run must complete — no NBStrictError on a correct pipeline."""
    monkeypatch.setenv("PATHWAY_NB_STRICT", "1")
    pw.internals.parse_graph.G.clear()

    class L(pw.Schema):
        a: int
        v: int

    class R(pw.Schema):
        b: int
        w: int

    class LS(pw.io.python.ConnectorSubject):
        _deletions_enabled = False

        def run(self):
            self.next_batch([{"a": i % 5, "v": i} for i in range(40)])
            self.commit()

    class RS(pw.io.python.ConnectorSubject):
        _deletions_enabled = False

        def run(self):
            self.commit()
            # late right row: retracts the pads minted for a==2 rows
            self.next_batch([{"b": 2, "w": 20}])
            self.commit()

    lt = pw.io.python.read(LS(), schema=L, autocommit_duration_ms=None)
    rt = pw.io.python.read(RS(), schema=R, autocommit_duration_ms=None)
    out = lt.join_left(rt, lt.a == rt.b).select(
        v=pw.left.v, w=pw.right.w
    )
    runtime, report, cap = _lower_analyze_run(out)
    assert report.verdict == "degraded", report.render()
    [entry] = [n for n in report.nodes if n["kind"] == "join"]
    assert entry["verdict"] == "degraded"
    [join], _ = _counters(runtime)
    assert join.nb_decision.ok          # the join ITSELF is fused-eligible
    assert join._nb_batches > 0         # and consumed columnar input
    assert join._nb_fallbacks == 0
    assert runtime.stats.exchange_fallbacks == 0
    assert pa.audit_runtime(runtime, report) == []
    assert len(cap.state.rows) == 40    # 32 padded + 8 matched


@needs_nb
def test_forced_tuple_env_matches_degraded_verdict(monkeypatch):
    monkeypatch.setenv("PATHWAY_NO_NB_JOIN", "1")
    bp = pb.build_stream_join()
    runtime, report, cap = _lower_analyze_run(bp.out)
    assert report.verdict == "degraded"
    [entry] = [n for n in report.nodes if n["kind"] == "join"]
    assert any("PATHWAY_NO_NB_JOIN" in r for r in entry["reasons"])
    joins, _ = _counters(runtime)
    assert joins[0]._nb_batches == 0
    assert joins[0]._nb_fallbacks > 0
    assert pa.audit_runtime(runtime, report) == []


# -- egress verdicts vs runtime counters (ISSUE 14 satellite) -------------


def _egress_pipeline(consumer: str):
    """stream_join variant whose OUTPUT chain is statically columnar,
    terminated by the requested consumer kind."""
    pw.internals.parse_graph.G.clear()

    class L(pw.Schema):
        k: int = pw.column_definition(primary_key=True)
        j: int
        v: int

    class R(pw.Schema):
        k: int = pw.column_definition(primary_key=True)
        j: int
        w: int

    lrows = [{"k": i, "j": i % 9, "v": i} for i in range(180)]
    rrows = [{"k": i, "j": i % 9, "w": i} for i in range(18)]

    class LS(pw.io.python.ConnectorSubject):
        _deletions_enabled = False

        def run(self):
            for s in range(0, len(lrows), 60):
                self.next_batch(lrows[s : s + 60])
                self.commit()

    class RS(pw.io.python.ConnectorSubject):
        _deletions_enabled = False

        def run(self):
            self.next_batch(rrows)
            self.commit()

    lt = pw.io.python.read(LS(), schema=L, autocommit_duration_ms=None)
    rt = pw.io.python.read(RS(), schema=R, autocommit_duration_ms=None)
    out = lt.join(rt, pw.left.j == pw.right.j).select(
        v=pw.left.v, w=pw.right.w
    )
    if consumer == "arrow":
        pw.io.subscribe(
            out, on_batch=lambda *a: None, batch_format="arrow"
        )
    elif consumer == "rows_batch":
        pw.io.subscribe(out, on_batch=lambda *a: None)
    else:
        pw.io.subscribe(out, on_change=lambda *a: None)
    return out


@needs_nb
@pytest.mark.parametrize(
    "consumer,expect",
    [
        ("arrow", "fused"),
        ("rows_batch", "row-expanding"),
        ("on_change", "row-expanding"),
    ],
)
def test_egress_verdict_matches_runtime_counters(consumer, expect):
    """The Plan Doctor's egress verdict must be corroborated by the
    runtime's capture counters (the plan-vs-reality contract extended
    to sinks): fused egress ⇔ arrow batches delivered + zero rows
    expanded at the sink; row-expanding egress ⇔ the expansion counter
    moves and ``sink.row-expanding`` names the consumer."""
    pytest.importorskip("pyarrow")
    out = _egress_pipeline(consumer)
    runtime, report, cap = _lower_analyze_run(out)
    sink_diags = [
        d for d in report.diagnostics if d.code == "sink.row-expanding"
    ]
    # the scratch capture node added by the harness is itself an
    # arrow-capable egress; only the subscriber's OutputNode may fire
    if expect == "fused":
        assert not sink_diags, [d.message for d in sink_diags]
        assert runtime.stats.capture_arrow_batches > 0
        assert runtime.stats.capture_rows_expanded == 0
    else:
        assert len(sink_diags) == 1, [d.message for d in sink_diags]
        assert "arrow" in (sink_diags[0].hint or "")
        assert runtime.stats.capture_rows_expanded > 0
        assert runtime.stats.capture_arrow_batches == 0


@needs_nb
def test_egress_verdict_degraded_chain_not_blamed_on_sink():
    """A tuple chain (groupby output) feeding a rows consumer: the sink
    is NOT the de-optimization — no columnar batches exist to expand,
    so the capture counters stay flat and the sink.row-expanding
    message (per-row on_change hint) carries the upstream context."""
    pytest.importorskip("pyarrow")
    bp = pb.build_wordcount()
    runtime, report, cap = _lower_analyze_run(bp.out)
    assert runtime.stats.capture_rows_expanded == 0
    assert runtime.stats.capture_arrow_batches == 0
    sink_diags = [
        d for d in report.diagnostics if d.code == "sink.row-expanding"
    ]
    assert len(sink_diags) == 1
    assert "not columnar" in sink_diags[0].message


@needs_nb
def test_egress_forced_off_flips_fused_to_row_expanding(monkeypatch):
    pytest.importorskip("pyarrow")
    monkeypatch.setenv("PATHWAY_NO_NB_CAPTURE", "1")
    out = _egress_pipeline("arrow")
    runtime, report, cap = _lower_analyze_run(out)
    sink_diags = [
        d for d in report.diagnostics if d.code == "sink.row-expanding"
    ]
    assert sink_diags and any(
        "NO_NB_CAPTURE" in d.message for d in sink_diags
    )
    assert runtime.stats.capture_arrow_batches == 0
    assert runtime.stats.capture_rows_expanded > 0


# -- 2-rank real-fork agreement ------------------------------------------

_RANK_PROGRAM = """
import json, os, sys
sys.path.insert(0, {repo!r})
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import pathway_tpu as pw
import pathway_tpu.engine.runtime as rt_mod
from pathway_tpu.analysis import analyzer as pa
from pathway_tpu.engine import nodes as N

_insts = []
_orig = rt_mod.Runtime.__init__
def _spy(self, *a, **k):
    _orig(self, *a, **k)
    _insts.append(self)
rt_mod.Runtime.__init__ = _spy

rank = int(os.environ.get("PATHWAY_PROCESS_ID", "0"))
P = int(os.environ.get("PATHWAY_PROCESSES", "1"))
words = [f"w{{i}}" for i in range(5)]
rows = [
    {{"data": words[i % 5], "v": i % 50}} for i in range(rank, 300, P)
]

class Src(pw.io.python.ConnectorSubject):
    _deletions_enabled = False
    _distributed_partitioned = True
    def run(self):
        for s in range(0, len(rows), 50):
            self.next_batch(rows[s : s + 50])
            self.commit()

class S(pw.Schema):
    data: str
    v: int

t = pw.io.python.read(Src(), schema=S, autocommit_duration_ms=3_600_000)
counts = t.groupby(pw.this.data).reduce(
    word=pw.this.data, c=pw.reducers.count(), s=pw.reducers.sum(pw.this.v)
)
rrows = [{{"j": w, "m": i + 1}} for i, w in enumerate(words)]
class RSrc(pw.io.python.ConnectorSubject):
    _deletions_enabled = False
    def run(self):
        self.next_batch(rrows)
        self.commit()
class R(pw.Schema):
    j: str
    m: int
rt = pw.io.python.read(RSrc(), schema=R, autocommit_duration_ms=3_600_000)
joined = t.join(rt, pw.left.data == pw.right.j).select(
    d=pw.left.data, v=pw.left.v, m=pw.right.m
)
state = {{}}
pw.io.subscribe(counts, on_change=lambda *a: None)
pw.io.subscribe(joined, on_change=lambda *a: None)
pw.run(monitoring_level=pw.MonitoringLevel.NONE)

runtime = _insts[0]
report = pa.analyze_scope(runtime)
problems = pa.audit_runtime(runtime, report)
joins = [n for n in runtime.scope.nodes if isinstance(n, N.JoinNode)]
gbs = [n for n in runtime.scope.nodes if isinstance(n, N.GroupByNode)]
xs = runtime.scope.exchange_nodes
mesh_diags = [d.code for d in report.diagnostics
              if d.code.startswith("mesh.")]
print(json.dumps({{
    "rank": rank,
    "verdict": report.verdict,
    "problems": problems,
    "nb_fallbacks": runtime.stats.nb_fallbacks,
    "exchange_fallbacks": runtime.stats.exchange_fallbacks,
    "join_nb_batches": sum(n._nb_batches for n in joins),
    "gb_nb_batches": sum(n._nb_batches for n in gbs),
    "x_nb_batches": sum(x._nb_batches for x in xs),
    "n_exchanges": len(xs),
    "mesh_diags": mesh_diags,
    "mesh_rollbacks": runtime.stats.mesh_rollbacks,
    "mesh_heartbeats_missed": runtime.stats.mesh_heartbeats_missed,
    "mesh_rank_restarts": runtime.stats.mesh_rank_restarts,
}}))
"""


def _free_port_base(n: int = 4) -> int:
    import socket

    for _ in range(50):
        probe = socket.socket()
        probe.bind(("127.0.0.1", 0))
        base = probe.getsockname()[1]
        probe.close()
        socks = []
        try:
            for i in range(n):
                s = socket.socket()
                s.bind(("127.0.0.1", base + i))
                socks.append(s)
            return base
        except OSError:
            continue
        finally:
            for s in socks:
                s.close()
    raise RuntimeError("no consecutive free port range found")


@needs_nb
@pytest.mark.parametrize("world", [2, 4], ids=["2rank", "4rank"])
def test_fused_verdict_matches_zero_fallbacks_multirank(world):
    """Analyzer-vs-runtime agreement on a REAL N-rank mesh: the program
    carries wordcount (counts) and stream_join (joined). Every rank
    audits its own fallback counters against the static verdicts AND —
    at N>1 — the Plan Doctor's distributed-safety pass (the mesh
    verifier over this plan's exchange topology) must report verified,
    in agreement with the real run's mesh counters: zero rollbacks,
    zero restarts (ISSUE 7 acceptance: doctor verdicts at 4 ranks agree
    with a real 4-rank run)."""
    with tempfile.TemporaryDirectory() as td:
        prog = os.path.join(td, "prog.py")
        with open(prog, "w") as f:
            f.write(_RANK_PROGRAM.format(repo=REPO))
        port = _free_port_base(world)
        procs = []
        for rank in range(world):
            env = dict(os.environ)
            env.pop("PATHWAY_LANE_PROCESSES", None)
            env.update(
                PATHWAY_PROCESSES=str(world),
                PATHWAY_PROCESS_ID=str(rank),
                PATHWAY_FIRST_PORT=str(port),
                JAX_PLATFORMS="cpu",
                PYTHONPATH=REPO,
                PATHWAY_MESHCHECK_ROUNDS="1",  # keep the doctor pass lean
            )
            procs.append(
                subprocess.Popen(
                    [sys.executable, prog], env=env, cwd=td,
                    stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                )
            )
        outs = []
        try:
            for p in procs:
                out, err = p.communicate(timeout=240)
                assert p.returncode == 0, err.decode()[-2000:]
                outs.append(json.loads(out.decode().strip().splitlines()[-1]))
        finally:
            for q in procs:
                if q.poll() is None:
                    q.kill()
                    q.communicate()
        for r in outs:
            assert r["verdict"] == "fused", r
            assert r["problems"] == [], r
            assert r["nb_fallbacks"] == 0, r
            assert r["exchange_fallbacks"] == 0, r
            assert r["n_exchanges"] > 0
            # the mesh verifier's verdict, computed per rank over the
            # SAME lowered plan, agrees with what the real mesh did:
            # verified <-> no rollback, no restart, no missed heartbeat
            assert r["mesh_diags"] == ["mesh.verified"], r
            assert r["mesh_rollbacks"] == 0, r
            assert r["mesh_rank_restarts"] == 0, r
            assert r["mesh_heartbeats_missed"] == 0, r
        # the fused multi-rank chain actually carried columnar batches
        assert sum(r["x_nb_batches"] for r in outs) > 0
        assert sum(r["gb_nb_batches"] for r in outs) > 0
        assert sum(r["join_nb_batches"] for r in outs) > 0


# -- device-plan predicted vs measured recompiles (ISSUE 20) ----------------
# Zero false "device-clean": the Doctor's static shape-bucket set,
# enumerated through the SAME bucket functions the dispatch sites pad
# with, must agree EXACTLY with the runtime's device_recompiles_total /
# device_site_recompiles_total counters when the runtime is driven with
# the declared batches.

def _jax_available() -> bool:
    try:
        import jax  # noqa: F401

        return True
    except Exception:
        return False


needs_jax = pytest.mark.skipif(
    not _jax_available(), reason="jax unavailable"
)


@needs_jax
def test_device_plan_predicts_encoder_recompiles_exactly():
    from pathway_tpu.analysis.device_plan import (
        WorkloadSpec,
        analyze_device_plan,
        join_profile,
    )
    from pathway_tpu.internals.device import PLANE
    from pathway_tpu.internals.monitoring import ProberStats
    from pathway_tpu.models.encoder import EncoderConfig, SentenceEncoder

    cfg = EncoderConfig.tiny()
    enc = SentenceEncoder(cfg)
    word = "retrieval"
    batches = [
        [" ".join([word] * 3)] * 4,          # small batch, short seqs
        [" ".join([word] * 3)] * 4,          # same shape: no new bucket
        [" ".join([word] * 40)] * 4,         # the ladder's next rung
        [" ".join([word] * 3)] * 12,         # bigger batch bucket
    ]
    # the declared workload: (rows, raw token length) per call, read
    # off the same tokenizer the encoder dispatches with
    declared = []
    for texts in batches:
        ids, _ = enc.tokenizer(list(texts))
        declared.append((len(texts), ids.shape[1]))
    spec = WorkloadSpec(
        ingest_batches=tuple(declared), batch_cap=enc.batch_size
    )
    report = analyze_device_plan(workload=spec, config=cfg)
    predicted = report.predictions["encoder.forward"]["buckets"]

    stats = ProberStats()
    PLANE.disarm()
    PLANE.arm(None, stats)
    try:
        for texts in batches:
            enc.encode(texts)
    finally:
        PLANE.disarm()
    measured = stats.device_recompiles.get("encoder.forward", 0)
    assert measured == len(predicted), (
        f"predicted buckets {sorted(predicted)} vs measured "
        f"{measured} recompiles"
    )
    # the runtime's bucket keys ARE the predicted set (identity-shared
    # bucket functions, not merely equal counts)
    assert set(enc._compiled) == predicted
    # and the --profile drift join agrees: measured == predicted is ok
    joined = join_profile(
        report, {"device_recompiles": dict(stats.device_recompiles)}
    )
    assert joined.predictions["encoder.forward"]["drift"] == "ok"
    assert joined.verdict == "device-clean"


@needs_jax
def test_device_plan_predicts_knn_recompiles_exactly():
    import numpy as np

    from pathway_tpu.analysis.device_plan import (
        WorkloadSpec,
        simulate_knn_buckets,
    )
    from pathway_tpu.internals.device import PLANE
    from pathway_tpu.internals.monitoring import ProberStats
    from pathway_tpu.ops.knn import KnnShard

    write_batches = (16, 16, 48, 96)   # 48 keeps cap, 96 grows it to 256
    query_batches = (1, 3, 8)
    ks = (5, 10)
    spec = WorkloadSpec(
        ingest_batches=(),             # every write's vectors made elsewhere
        write_batches=write_batches,
        query_batches=query_batches,
        ks=ks,
        initial_capacity=128,
    )
    pred_write, pred_search = simulate_knn_buckets(spec)

    shard = KnnShard(8, capacity=128)
    rng = np.random.default_rng(7)
    stats = ProberStats()
    PLANE.disarm()
    PLANE.arm(None, stats)
    try:
        seq = 0
        for b in write_batches:
            shard.add(
                [f"k{seq + j}" for j in range(b)],
                rng.normal(size=(b, 8)).astype(np.float32),
            )
            seq += b
        for q in query_batches:
            for k in ks:
                shard.search(
                    rng.normal(size=(q, 8)).astype(np.float32), k=k
                )
    finally:
        PLANE.disarm()
    assert stats.device_recompiles.get("knn.write", 0) == len(pred_write)
    assert stats.device_recompiles.get("knn.search", 0) == len(pred_search)
    # the runtime's seen-bucket keys are the predicted sets themselves
    assert shard._seen_buckets == pred_write | pred_search
    # aggregate pin: device_recompiles_total (the sum the OpenMetrics
    # endpoint renders) equals the Doctor's total prediction
    assert sum(stats.device_recompiles.values()) == (
        len(pred_write) + len(pred_search)
    )
