"""Relational-plane throughput bench: streaming wordcount + delta-join.

The reference's scaling story for this plane is N timely workers over key
shards (src/engine/dataflow.rs:5538, dataflow/config.rs:88-127). Ours is
worker-sharded batch execution with C++ inner loops plus the NativeBatch
fused chain (native/exec.cpp): parse → groupby with zero per-row Python.

Engine-bound harness: row dicts are pre-materialized BEFORE the measured
window and enter the engine through ``ConnectorSubject.next_batch`` (one C
parse call per batch), so the recorded rows/s measures parse + groupby +
delivery, not a Python generator loop. ``gen_s`` records the (unmeasured)
materialization cost for transparency.

Self-defending measurements (round-4 verdict: the driver artifact recorded
half the engine's real throughput): every metric runs warmup + 3 repeats
and reports the median with per-run values and dispersion (flagged >20%).
Artifacts always include the thread-scaling curve (threads=1/4/8) and a
PATHWAY_PROCESSES=2 wordcount, with ``host_cores`` annotated so a 1-core
host shows honest parity rather than silence.

Usage: python scripts/bench_relational.py [n_rows] [distinct_words]

N-rank scaling lanes (ISSUE 10): ``--ranks 1,2,4`` runs wordcount and
stream_join at every requested rank count through the real-fork mesh
harness and records throughput + ``scaling_efficiency`` (vs the 1-rank
lane measured in the same session) + ``mesh_skew_seconds`` (cross-rank
recv-wait spread). Every lane prints its metric lines; nothing is
written to disk.
"""

from __future__ import annotations

import json
import os
import socket
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from bench_util import median_of as _median_of  # noqa: E402


def _print_emit(metric: dict) -> None:
    print(json.dumps(metric), flush=True)


def _materialize_wordcount(n_rows: int, distinct: int, batch: int):
    t0 = time.perf_counter()
    words = [f"word{i}" for i in range(distinct)]
    batches = [
        [
            {"data": words[(i * 2654435761) % distinct]}
            for i in range(start, min(start + batch, n_rows))
        ]
        for start in range(0, n_rows, batch)
    ]
    return batches, time.perf_counter() - t0


def _transform_once(n_rows: int) -> dict:
    """Rowwise expression plane: 4 selected columns (6 binary ops) per
    row through the C binop fast path (native/fastpath.c fast_binop) and
    net-form batch passthrough."""
    import gc

    import pathway_tpu as pw
    from pathway_tpu.internals.graph_runner import GraphRunner

    gc.collect()
    pw.internals.parse_graph.G.clear()

    class S(pw.Schema):
        a: int
        b: int

    t0 = time.perf_counter()
    rows = [(i, i % 1000, (i * 7) % 997 + 1) for i in range(n_rows)]
    gen_s = time.perf_counter() - t0
    t = pw.debug.table_from_rows(S, rows)
    out = t.select(
        s=pw.this.a + pw.this.b,
        d=pw.this.a - pw.this.b,
        q=pw.this.a // pw.this.b,
        c=(pw.this.a > pw.this.b) & (pw.this.b > 10),
    )
    t0 = time.perf_counter()
    GraphRunner().run_tables(out)
    elapsed = time.perf_counter() - t0
    return {
        "metric": "transform_rows_per_s",
        "value": round(n_rows / elapsed, 1),
        "unit": "rows/s",
        "n_rows": n_rows,
        "exprs": 4,
        "binops": 6,
        "threads": int(os.environ.get("PATHWAY_THREADS", "1")),
        "host_cores": os.cpu_count() or 1,
        "gen_s": round(gen_s, 2),
        "elapsed_s": round(elapsed, 2),
    }


def bench_transform(n_rows: int = 200_000, emit=_print_emit) -> None:
    """transform_rows_per_s showed dispersion 0.345 in r5 (> the bench's
    own 20% flag) with only 1 warmup + 3 runs: the first measured run
    still carried allocator/compile warmup. Steady-state gate: 2 warmups
    + 5 measured runs, and if the spread still exceeds the flag threshold
    take 3 more so the recorded median has real support — the full run
    list and dispersion always land in the artifact."""
    from bench_util import DISPERSION_FLAG, dispersion

    runs = [_transform_once(n_rows) for _ in range(2 + 5)][2:]
    if dispersion([r["value"] for r in runs]) > DISPERSION_FLAG:
        runs += [_transform_once(n_rows) for _ in range(3)]
    emit(_median_of(runs, [r["value"] for r in runs]))


def _join_once(n_rows: int, n_keys: int, batch: int) -> dict:
    """Streaming two-table equi-join through the native delta-join executor
    (native/exec.cpp JoinStore): Δ(L⋈R) = ΔL⋈R + L'⋈ΔR, shard-parallel."""
    import gc

    import pathway_tpu as pw
    from pathway_tpu.internals.graph_runner import GraphRunner

    gc.collect()
    pw.internals.parse_graph.G.clear()

    class L(pw.Schema):
        k: int = pw.column_definition(primary_key=True)
        j: int
        v: int

    class R(pw.Schema):
        k: int = pw.column_definition(primary_key=True)
        j: int
        w: int

    # pre-materialized batches: the measured window is engine work only
    t0 = time.perf_counter()
    left_batches = [
        [
            {"k": i, "j": (i * 2654435761) % n_keys, "v": i}
            for i in range(start, min(start + batch, n_rows))
        ]
        for start in range(0, n_rows, batch)
    ]
    right_rows = [{"k": i, "j": i % n_keys, "w": i} for i in range(n_keys * 3)]
    gen_s = time.perf_counter() - t0

    class LS(pw.io.python.ConnectorSubject):
        _deletions_enabled = False

        def run(self):
            for b in left_batches:
                self.next_batch(b)
                self.commit()

    class RS(pw.io.python.ConnectorSubject):
        _deletions_enabled = False

        def run(self):
            self.next_batch(right_rows)
            self.commit()

    lt = pw.io.python.read(LS(), schema=L, autocommit_duration_ms=None)
    rt = pw.io.python.read(RS(), schema=R, autocommit_duration_ms=None)
    out = lt.join(rt, pw.left.j == pw.right.j).select(
        v=pw.left.v, w=pw.right.w
    )
    reset_phases, read_phases = _phase_tracker(section="join")
    reset_phases()
    t0 = time.perf_counter()
    cap = GraphRunner().run_tables(out)[0]
    elapsed = time.perf_counter() - t0
    phases = read_phases()
    # Columnar egress (ISSUE 14): the capture's committed output reads
    # out as Arrow record batches straight off the C-owned column
    # buffers (CaptureNode.arrow_table -> exec.cpp capture_collect_nb +
    # nb_export_arrow) — `value_incl_capture` now prices THAT, the cost
    # a production columnar sink actually pays, instead of the
    # per-row Python expansion the pre-columnar-egress artifacts
    # measured (87.2k vs 258.6k in round 5, a 2.97x gap). The row path
    # remains reachable via PATHWAY_NO_NB_CAPTURE=1 and is what
    # `capture_mode: "rows"` marks when the arrow reader declines.
    t0 = time.perf_counter()
    tbl = cap.arrow_table()
    if tbl is not None:
        out_rows = tbl.num_rows
        capture_mode = "arrow"
    else:
        out_rows = len(cap.state.rows)
        capture_mode = "rows"
    capture_s = time.perf_counter() - t0
    return {
        "metric": "stream_join_rows_per_s",
        **({"join_phases": phases} if phases is not None else {}),
        "value": round(n_rows / elapsed, 1),
        "value_incl_capture": round(n_rows / (elapsed + capture_s), 1),
        "unit": "left-rows/s",
        "n_rows": n_rows,
        "n_keys": n_keys,
        "out_rows": out_rows,
        "capture_mode": capture_mode,
        "threads": int(os.environ.get("PATHWAY_THREADS", "1")),
        "host_cores": os.cpu_count() or 1,
        "gen_s": round(gen_s, 2),
        "capture_materialize_s": round(capture_s, 3),
        "elapsed_s": round(elapsed, 2),
    }


def bench_join(
    n_rows: int = 60_000, n_keys: int = 300, batch: int = 2_000,
    emit=_print_emit,
) -> None:
    runs = [_join_once(n_rows, n_keys, batch) for _ in range(1 + 3)][1:]
    emit(_median_of(runs, [r["value"] for r in runs]))


def _phase_tracker(section: str | None = None):
    """(reset, read) over the native executor's per-phase wall-time
    accumulators — extract/emit hold the GIL, apply is shard-parallel
    GIL-free, so apply's share IS the multi-core scaling headroom
    (auditable even from a 1-core host; r4 verdict weak #5).
    section=None reads the group-by totals, "join" the join totals."""
    try:
        from pathway_tpu.native import get_pwexec

        ex = get_pwexec()
    except Exception:
        ex = None
    if ex is None or not hasattr(ex, "phase_stats"):
        return (lambda: None), (lambda: None)

    def read():
        s = ex.phase_stats()
        if section is not None:
            s = s.get(section) or {}
        total = (
            s.get("extract_s", 0.0)
            + s.get("apply_s", 0.0)
            + s.get("emit_s", 0.0)
        )
        if total <= 0:
            return None
        return {
            "extract_s": round(s["extract_s"], 4),
            "apply_s": round(s["apply_s"], 4),
            "emit_s": round(s["emit_s"], 4),
            "apply_share_gil_free": round(s["apply_s"] / total, 3),
        }

    return ex.phase_stats_reset, read


def _wordcount_once(
    n_rows: int, distinct: int, batch: int
) -> tuple[float, dict]:
    import gc

    import pathway_tpu as pw

    gc.collect()  # keep prior runs' garbage cycles out of the timed window
    pw.internals.parse_graph.G.clear()
    batches, gen_s = _materialize_wordcount(n_rows, distinct, batch)

    class Source(pw.io.python.ConnectorSubject):
        _deletions_enabled = False  # append-only: no remove()-by-content

        def run(self):
            for b in batches:
                self.next_batch(b)
                self.commit()

    class S(pw.Schema):
        data: str

    src = Source()
    # huge autocommit window: commits happen at the subject's own commit()
    # cadence (one per `batch` rows) — the reference-like configuration
    table = pw.io.python.read(src, schema=S, autocommit_duration_ms=3_600_000)
    counts = table.groupby(pw.this.data).reduce(
        word=pw.this.data, c=pw.reducers.count()
    )
    out = {"n": 0}

    def on_batch(time_, changes):
        # batched tuples egress (ISSUE 14): one callback per delivered
        # batch, zero per-row Python — the per-row on_change subscriber
        # this replaces paid ~125ns of call overhead per change
        # (OutputNode#2 = 18% of wordcount self-time in the r5 trace)
        out["n"] += len(changes)

    pw.io.subscribe(counts, on_batch=on_batch, batch_format="tuples")

    reset_phases, read_phases = _phase_tracker()
    reset_phases()
    t0 = time.perf_counter()
    pw.run(monitoring_level=pw.MonitoringLevel.NONE)
    elapsed = time.perf_counter() - t0
    metric = {
        "metric": "wordcount_rows_per_s",
        "value": round(n_rows / elapsed, 1),
        "unit": "rows/s",
        "n_rows": n_rows,
        "distinct": distinct,
        "threads": int(os.environ.get("PATHWAY_THREADS", "1")),
        "host_cores": os.cpu_count() or 1,
        "output_changes": out["n"],
        "gen_s": round(gen_s, 2),
        "elapsed_s": round(elapsed, 2),
    }
    phases = read_phases()
    if phases is not None:
        metric["groupby_phases"] = phases
    return elapsed, metric


_RANK_STATS_TAIL = """
from pathway_tpu.engine import runtime as _rt
_st = _rt.LAST_RUN_STATS
_extra = {{}}
if _st is not None:
    _extra = dict(
        recv_wait_s=round(_st.exchange_recv_wait_s, 4),
        comms_s=round(_st.exchange_comms_s, 4),
        compute_s=round(_st.exchange_compute_s, 4),
        idle_s=round(_st.idle_s, 4),
        waves=_st.exchange_waves,
        raw_bytes=_st.exchange_raw_bytes,
        wire_bytes=_st.exchange_wire_bytes,
        tree_depth=_st.mesh_tree_depth,
        arrow_batches=_st.capture_arrow_batches,
        arrow_rows=_st.capture_arrow_rows,
        rows_expanded=_st.capture_rows_expanded,
    )
print(json.dumps({{"rank": rank, "elapsed_s": time.perf_counter() - t0,
                   "changes": out["n"], **_extra}}))
"""

_RANK_PROGRAM = """
import json, os, sys, time
sys.path.insert(0, {repo!r})
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import pathway_tpu as pw
import pathway_tpu.parallel.mesh  # pre-import jax: keep it out of the timed window

rank = int(os.environ.get("PATHWAY_PROCESS_ID", "0"))
P = int(os.environ.get("PATHWAY_PROCESSES", "1"))
n_rows, distinct, batch = {n_rows}, {distinct}, {batch}
words = [f"word{{i}}" for i in range(distinct)]
rows = [
    {{"data": words[(i * 2654435761) % distinct]}}
    for i in range(rank, n_rows, P)
]
batches = [rows[s : s + batch] for s in range(0, len(rows), batch)]

class Source(pw.io.python.ConnectorSubject):
    _deletions_enabled = False
    # every rank reads its OWN residue-class shard (without this the
    # single-reader default would silently drop rank 1's rows and the
    # recorded rows/s would be 2x optimistic — caught by the r5
    # relational dryrun)
    _distributed_partitioned = True
    def run(self):
        for b in batches:
            self.next_batch(b)
            self.commit()

class S(pw.Schema):
    data: str

t = pw.io.python.read(Source(), schema=S, autocommit_duration_ms=3_600_000)
counts = t.groupby(pw.this.data).reduce(
    word=pw.this.data, c=pw.reducers.count()
)
out = {{"n": 0}}
# batched tuples egress (ISSUE 14): counting via one callback per batch
pw.io.subscribe(
    counts,
    on_batch=lambda time_, ch: out.__setitem__("n", out["n"] + len(ch)),
    batch_format="tuples",
)
t0 = time.perf_counter()
pw.run(monitoring_level=pw.MonitoringLevel.NONE)
""" + _RANK_STATS_TAIL

# N-rank streaming join: left stream sharded by residue class across
# ranks, right (build) side read on rank 0 only (single-reader default)
# — the join exchange re-shards both sides by key, so this measures the
# hash all-to-all under real skewless load
_JOIN_RANK_PROGRAM = """
import json, os, sys, time
sys.path.insert(0, {repo!r})
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import pathway_tpu as pw
import pathway_tpu.parallel.mesh  # pre-import jax: keep it out of the timed window

rank = int(os.environ.get("PATHWAY_PROCESS_ID", "0"))
P = int(os.environ.get("PATHWAY_PROCESSES", "1"))
n_rows, n_keys, batch = {n_rows}, {n_keys}, {batch}

class L(pw.Schema):
    k: int = pw.column_definition(primary_key=True)
    j: int
    v: int

class R(pw.Schema):
    k: int = pw.column_definition(primary_key=True)
    j: int
    w: int

mine = list(range(rank, n_rows, P))
left_batches = [
    [{{"k": i, "j": (i * 2654435761) % n_keys, "v": i}} for i in mine[s:s+batch]]
    for s in range(0, len(mine), batch)
]
right_rows = [{{"k": i, "j": i % n_keys, "w": i}} for i in range(n_keys * 3)]

class LS(pw.io.python.ConnectorSubject):
    _deletions_enabled = False
    _distributed_partitioned = True
    def run(self):
        for b in left_batches:
            self.next_batch(b)
            self.commit()

class RS(pw.io.python.ConnectorSubject):
    _deletions_enabled = False
    def run(self):
        self.next_batch(right_rows)
        self.commit()

lt = pw.io.python.read(LS(), schema=L, autocommit_duration_ms=None)
rt_t = pw.io.python.read(RS(), schema=R, autocommit_duration_ms=None)
joined = lt.join(rt_t, pw.left.j == pw.right.j).select(
    v=pw.left.v, w=pw.right.w
)
out = {{"n": 0}}
# columnar egress (ISSUE 14): the join's NativeBatch output gathers to
# rank 0 COLUMNAR and exports as Arrow record batches at the sink —
# capture is in-stream now, so the lane's value already prices it
# (capture_arrow_rows > 0 / rows_expanded == 0 on the rank-0 line is
# the fused-to-the-edge proof)
pw.io.subscribe(
    joined,
    on_batch=lambda time_, rb: out.__setitem__("n", out["n"] + rb.num_rows),
    batch_format="arrow",
    include_key=False,
)
t0 = time.perf_counter()
pw.run(monitoring_level=pw.MonitoringLevel.NONE)
""" + _RANK_STATS_TAIL


def _free_port_base(n: int = 4) -> int:
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


def _mesh_rank_once(
    prog: str, td: str, metric: str, world: int, extra_env: dict | None = None
):
    """One N-rank run of a rank program; returns the per-rank result
    dicts (or an error metric dict). Each rank prints one JSON line with
    elapsed_s plus its exchange counters (recv_wait/comms/compute/idle,
    read off engine.runtime.LAST_RUN_STATS) — the scaling lanes derive
    mesh_skew_seconds from the cross-rank recv-wait spread."""
    port = _free_port_base(world)
    procs = []
    for rank in range(world):
        env = dict(os.environ)
        env.update(
            PATHWAY_PROCESSES=str(world),
            PATHWAY_PROCESS_ID=str(rank),
            PATHWAY_FIRST_PORT=str(port),
            JAX_PLATFORMS="cpu",
            PYTHONPATH=REPO,
        )
        env.pop("PATHWAY_LANE_PROCESSES", None)
        if extra_env:
            env.update(extra_env)
        procs.append(
            subprocess.Popen(
                [sys.executable, prog],
                env=env,
                cwd=td,
                stdout=subprocess.PIPE,
                stderr=subprocess.PIPE,
            )
        )
    results = []
    try:
        for p in procs:
            try:
                out, err = p.communicate(timeout=600)
            except subprocess.TimeoutExpired:
                return {"metric": metric, "error": "timeout"}
            if p.returncode != 0:
                return {"metric": metric,
                        "error": f"rank exited {p.returncode}",
                        "stderr_tail": err.decode()[-400:]}
            last = out.decode().strip().splitlines()[-1]
            results.append(json.loads(last))
    finally:
        # a failed/timed-out rank must not orphan its surviving peers
        # (they would block forever on the mesh accept for the dead rank)
        for q in procs:
            if q.poll() is None:
                q.kill()
                q.communicate()
    return results


def _mesh_metric(
    metric: str, results: list, n_rows: int, world: int, **fields
) -> dict:
    elapsed = max(r["elapsed_s"] for r in results)
    waits = [r.get("recv_wait_s") for r in results]
    out = {
        "metric": metric,
        "value": round(n_rows / elapsed, 1),
        "n_rows": n_rows,
        "processes": world,
        "host_cores": os.cpu_count() or 1,
        "per_rank_elapsed_s": [round(r["elapsed_s"], 2) for r in results],
        "output_changes_rank0": results[0]["changes"],
        **fields,
    }
    if all(w is not None for w in waits) and world > 1:
        # cumulative per-wave finish spread: the fastest rank's total
        # recv-wait beyond the slowest's — same derivation as the
        # cluster view's mesh_skew_seconds gauge (internals/cluster.py)
        out["mesh_skew_seconds"] = round(max(waits) - min(waits), 4)
        out["per_rank_recv_wait_s"] = waits
    # fast wire (ISSUE 13): frame bytes before/after the wire codec,
    # summed over the mesh, plus the gather-tree depth — the ≥2x
    # frame-byte-reduction acceptance lane reads straight off this
    raw = sum(r.get("raw_bytes") or 0 for r in results)
    wire = sum(r.get("wire_bytes") or 0 for r in results)
    if wire:
        out["frame_bytes_raw"] = raw
        out["frame_bytes_wire"] = wire
        out["compression_ratio"] = round(raw / wire, 3)
    depth = max((r.get("tree_depth") or 0 for r in results), default=0)
    if depth:
        out["tree_depth"] = depth
    # columnar egress (ISSUE 14): the scaling lanes' sinks deliver
    # batched (tuples/arrow) IN-STREAM, so there is no deferred capture
    # leg left outside `elapsed` — value_incl_capture equals value by
    # construction and the egress counters prove which path ran
    # (arrow_rows > 0 + rows_expanded == 0 = columnar to the edge;
    # pre-ISSUE-14 lanes implicitly excluded capture entirely)
    out["value_incl_capture"] = out["value"]
    out["capture_materialize_s"] = 0.0
    if any(r.get("arrow_batches") is not None for r in results):
        out["egress"] = {
            "arrow_batches": sum(r.get("arrow_batches") or 0 for r in results),
            "arrow_rows": sum(r.get("arrow_rows") or 0 for r in results),
            "rows_expanded": sum(r.get("rows_expanded") or 0 for r in results),
        }
    return out


def _wordcount_2rank_once(prog: str, td: str, n_rows: int, distinct: int):
    """One 2-rank run; returns the metric dict (or an error dict)."""
    results = _mesh_rank_once(prog, td, "wordcount_2rank_rows_per_s", 2)
    if isinstance(results, dict):
        return results
    return _mesh_metric(
        "wordcount_2rank_rows_per_s", results, n_rows, 2,
        unit="rows/s", distinct=distinct,
    )


def bench_wordcount_2rank(
    n_rows: int, distinct: int, batch: int, emit=_print_emit
) -> None:
    """PATHWAY_PROCESSES=2 wordcount over the loopback TCP mesh: each rank
    generates its residue-class half, the NativeBatch stays columnar
    through the hash exchange at the groupby boundary (exec.cpp
    shard_partition_nb + the v2 typed-columnar frames), outputs gather to
    rank 0. Steady-state gate like the other relational metrics: 2
    warmup runs (mesh + native-build + allocator), 3 measured runs with
    the 20% dispersion flag, +3 more on a breach so the recorded median
    has real support."""
    import tempfile

    from bench_util import DISPERSION_FLAG, dispersion

    with tempfile.TemporaryDirectory() as td:
        prog = os.path.join(td, "wc2.py")
        with open(prog, "w") as f:
            f.write(
                _RANK_PROGRAM.format(
                    repo=REPO, n_rows=n_rows, distinct=distinct, batch=batch
                )
            )

        def once():
            return _wordcount_2rank_once(prog, td, n_rows, distinct)

        runs = [once() for _ in range(2 + 3)][2:]
        bad = next((r for r in runs if "error" in r), None)
        if bad is not None:
            emit(bad)
            return
        if dispersion([r["value"] for r in runs]) > DISPERSION_FLAG:
            extra = [once() for _ in range(3)]
            bad = next((r for r in extra if "error" in r), None)
            if bad is not None:
                emit(bad)
                return
            runs += extra
        emit(_median_of(runs, [r["value"] for r in runs]))


def bench_scaling(
    ranks: list[int],
    n_rows: int,
    distinct: int,
    batch: int,
    emit=_print_emit,
    join_rows: int = 60_000,
    n_keys: int = 300,
) -> None:
    """``--ranks 1,2,4``: the N-rank scaling-efficiency lanes
    (ISSUE 10). Each scenario (wordcount, stream_join) runs at every
    requested rank count through the SAME real-fork subprocess harness
    — the 1-rank lane is the baseline, so ``scaling_efficiency =
    value / (N × baseline)`` compares like with like (same process
    startup, same measurement window). Each N-rank entry also records
    ``mesh_skew_seconds`` (cross-rank recv-wait spread — the cumulative
    per-wave finish spread; exact per-wave skew comes from
    ``analysis --critical-path`` on a traced run) and the per-rank
    recv-wait vector, so a scaling regression triages straight to
    "comms-bound" vs "one slow rank". 1 warmup + 3 measured runs per
    lane (a 4-rank cell is ~4 processes on this host — the full
    steady-state gate would double the lane's cost for numbers the
    dispersion field already qualifies)."""
    import tempfile

    scenarios = [
        (
            "wordcount",
            _RANK_PROGRAM.format(
                repo=REPO, n_rows=n_rows, distinct=distinct, batch=batch
            ),
            "rows/s",
            n_rows,
            {"distinct": distinct},
        ),
        (
            "stream_join",
            _JOIN_RANK_PROGRAM.format(
                repo=REPO, n_rows=join_rows, n_keys=n_keys, batch=2_000
            ),
            "left-rows/s",
            join_rows,
            {"n_keys": n_keys},
        ),
    ]
    with tempfile.TemporaryDirectory() as td:
        for name, src, unit, rows, fields in scenarios:
            prog = os.path.join(td, f"{name}_scaling.py")
            with open(prog, "w") as f:
                f.write(src)
            baseline = None
            for world in sorted(set(int(r) for r in ranks)):
                metric = f"{name}_{world}rank_rows_per_s"

                def once(metric=metric, world=world, extra_env=None):
                    res = _mesh_rank_once(
                        prog, td, metric, world, extra_env=extra_env
                    )
                    if isinstance(res, dict):
                        return res
                    return _mesh_metric(
                        metric, res, rows, world, unit=unit, **fields
                    )

                runs = [once() for _ in range(1 + 3)][1:]
                bad = next((r for r in runs if "error" in r), None)
                if bad is not None:
                    emit(bad)
                    continue
                med = _median_of(runs, [r["value"] for r in runs])
                if world == 1:
                    baseline = med["value"]
                    med["role"] = "scaling_baseline"
                elif baseline:
                    med["baseline_rows_per_s"] = baseline
                    med["scaling_efficiency"] = round(
                        med["value"] / (world * baseline), 4
                    )
                emit(med)
                if world == 2 and name == "wordcount":
                    # fast-wire companion lane (ISSUE 13): the same
                    # 2-rank wordcount with the codec FORCED on, so the
                    # artifact records the real frame-byte reduction on
                    # live frames (stdlib zlib — always available) next
                    # to its wall-clock cost. The default lane above
                    # rides `auto`, which on a starved loopback host
                    # deliberately ships raw — compressing memcpys with
                    # the cores the ranks share measures as a straight
                    # efficiency loss; auto engages off-host or when
                    # sender threads have spare cores to run on.
                    metric_z = f"{name}_2rank_zlib_rows_per_s"
                    zenv = {"PATHWAY_MESH_COMPRESSION": "zlib"}
                    zruns = [
                        once(metric=metric_z, extra_env=zenv)
                        for _ in range(1 + 3)
                    ][1:]
                    bad = next(
                        (r for r in zruns if "error" in r), None
                    )
                    if bad is not None:
                        emit(bad)
                        continue
                    zmed = _median_of(
                        zruns, [r["value"] for r in zruns]
                    )
                    zmed["metric"] = metric_z
                    zmed["role"] = "compression_lane"
                    if baseline:
                        zmed["baseline_rows_per_s"] = baseline
                        zmed["scaling_efficiency"] = round(
                            zmed["value"] / (world * baseline), 4
                        )
                    emit(zmed)


def bench_traced_overhead(
    n_rows: int, distinct: int, batch: int, emit=_print_emit
) -> None:
    """Flight-recorder acceptance lane (ISSUE 8): wordcount and
    stream_join re-measured with ``PATHWAY_TRACE`` armed, PAIRED with
    fresh untraced runs from the same session so the overhead number
    compares like with like (same host state, same warmup). The traced
    entries carry the untraced value they were paired against plus
    ``overhead_pct`` — the bar is <= 3%."""
    import statistics
    import tempfile

    td = tempfile.mkdtemp(prefix="pw_bench_trace_")
    trace = os.path.join(td, "trace.json")

    def _paired(name: str, once, unit: str) -> None:
        # INTERLEAVED pairs, not two sequential blocks: successive
        # in-process runs drift slower (allocator/page-cache state), so
        # a traced block measured after an untraced block reads ~13%
        # "overhead" that is pure ordering bias (measured during this
        # lane's bring-up; interleaving collapses it to the real ~2%)
        def run(traced: bool) -> float:
            if traced:
                os.environ["PATHWAY_TRACE"] = trace
            else:
                os.environ.pop("PATHWAY_TRACE", None)
            try:
                return once()
            finally:
                os.environ.pop("PATHWAY_TRACE", None)

        run(False)
        run(True)  # one warmup per mode (build + ring arming)
        base: list[float] = []
        traced: list[float] = []
        for _ in range(5):
            base.append(run(False))
            traced.append(run(True))
        base_v = statistics.median(base)
        traced_v = statistics.median(traced)
        overhead = (1.0 - traced_v / base_v) * 100.0 if base_v else 0.0
        try:
            with open(trace) as f:
                n_events = len(json.load(f).get("traceEvents", ()))
        except (OSError, json.JSONDecodeError):
            n_events = None
        emit(
            {
                "metric": name,
                "value": round(traced_v, 1),
                "unit": unit,
                "untraced_value": round(base_v, 1),
                "overhead_pct": round(overhead, 2),
                "overhead_ok": overhead <= 3.0,
                "interleaved_pairs": len(base),
                "runs": [round(v, 1) for v in traced],
                "untraced_runs": [round(v, 1) for v in base],
                "trace_events": n_events,
                "host_cores": os.cpu_count() or 1,
            }
        )

    _paired(
        "wordcount_traced_rows_per_s",
        lambda: _wordcount_once(n_rows, distinct, batch)[1]["value"],
        "rows/s",
    )
    _paired(
        "stream_join_traced_rows_per_s",
        lambda: _join_once(60_000, 300, 2_000)["value"],
        "left-rows/s",
    )


def child(n_rows: int, distinct: int, batch: int, emit=_print_emit) -> None:
    """One measurement pass at the current PATHWAY_THREADS: warmup + 3
    measured wordcount runs (median + dispersion recorded), then the join
    and transform benches under the same policy. main() reuses this for
    the threads=1 baseline so parent and thread-curve children share one
    measurement policy."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    _wordcount_once(n_rows, distinct, batch)  # warmup: build + imports
    runs = [_wordcount_once(n_rows, distinct, batch)[1] for _ in range(3)]
    emit(_median_of(runs, [r["value"] for r in runs]))
    bench_join(emit=emit)
    bench_transform(emit=emit)


def _run_child_capture(args: list[str], env: dict, emit) -> None:
    """Run a child bench process, re-emitting its JSON lines through the
    parent's emit so the output holds the full curve. A timeout
    still salvages whatever lines the child managed to print; a failed
    child becomes a ``bench_child_error`` line, which main() turns into
    a non-zero exit once the remaining lanes have run."""
    stdout, stderr, exit_code = b"", b"", 0
    try:
        proc = subprocess.run(args, env=env, capture_output=True, timeout=900)
        stdout, stderr, exit_code = proc.stdout, proc.stderr, proc.returncode
    except subprocess.TimeoutExpired as exc:
        stdout = exc.stdout or b""
        stderr = exc.stderr or b""
        exit_code = -1
    for line in stdout.decode().splitlines():
        line = line.strip()
        if line.startswith("{"):
            try:
                emit(json.loads(line))
            except json.JSONDecodeError:
                pass
    if exit_code != 0:
        emit(
            {
                "metric": "bench_child_error",
                "argv": args[1:],
                "exit": exit_code,
                "stderr_tail": stderr.decode()[-400:],
            }
        )


def main(
    n_rows: int = 200_000, distinct: int = 5_000, batch: int = 2_000,
    emit=_print_emit,
) -> None:
    child(n_rows, distinct, batch, emit=emit)
    # thread-scaling curve: same wordcount with PATHWAY_THREADS=4 and 8 in
    # fresh processes (the executor shard count is fixed at store creation).
    # Always recorded — host_cores in the artifact says whether the host can
    # actually show the shard-thread speedup (a 1-core host shows parity).
    if os.environ.get("PATHWAY_THREADS", "1") == "1":
        # a child lane that failed still lets the later lanes run, but
        # the run as a whole must not exit 0 with a hole in its curve
        failed: list[str] = []

        def tracked(metric: dict) -> None:
            if metric.get("metric") == "bench_child_error" or "error" in metric:
                failed.append(str(metric.get("metric")))
            emit(metric)

        for nthreads in ("4", "8"):
            env = dict(
                os.environ, PATHWAY_THREADS=nthreads, JAX_PLATFORMS="cpu"
            )
            _run_child_capture(
                [
                    sys.executable, os.path.abspath(__file__),
                    str(n_rows), str(distinct), str(batch), "--child",
                ],
                env,
                tracked,
            )
        bench_wordcount_2rank(n_rows, distinct, batch, emit=tracked)
        # flight-recorder overhead lane: traced wordcount + stream_join
        # paired with fresh untraced runs (<= 3% acceptance bar)
        bench_traced_overhead(n_rows, distinct, batch, emit=tracked)
        if failed:
            raise SystemExit(
                f"bench_relational: child lane(s) failed: {', '.join(failed)}"
            )


if __name__ == "__main__":
    args = list(sys.argv[1:])
    ranks = None
    if "--ranks" in args:
        # --ranks 1,2,4: the N-rank scaling lanes (value consumed here
        # so it is not mistaken for the positional n_rows)
        i = args.index("--ranks")
        try:
            ranks = [int(x) for x in args[i + 1].split(",") if x]
        except (IndexError, ValueError):
            sys.exit(
                "usage: bench_relational.py --ranks N[,M,...]  "
                "(e.g. --ranks 1,2,4)"
            )
        if not ranks:
            sys.exit("--ranks needs at least one rank count")
        del args[i:i + 2]
    argv = [a for a in args if not a.startswith("--")]
    n = int(argv[0]) if len(argv) > 0 else 200_000
    d = int(argv[1]) if len(argv) > 1 else 5_000
    b = int(argv[2]) if len(argv) > 2 else 2_000
    if ranks is not None:
        bench_scaling(ranks, n, d, b)
    elif "--child" in args:
        child(n, d, b)
    else:
        main(n, d, b)
