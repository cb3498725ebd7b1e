"""Plan Doctor unit battery: pinned diagnostics for deliberately-broken
plans (fusion blame with node provenance), knob-registry validation,
strict mode, the JSON report shape, and the GIL lint's self-checks.

The agreement-with-runtime-counters battery lives in
tests/test_plan_vs_runtime.py.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

import pathway_tpu as pw
from pathway_tpu.analysis import analyzer as pa
from pathway_tpu.analysis import eligibility as elig
from pathway_tpu.analysis import knobs as pk

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _nb_toolchain() -> bool:
    try:
        from pathway_tpu.native import get_pwexec

        ex = get_pwexec()
    except Exception:
        return None
    return ex is not None and hasattr(ex, "parse_upserts_nb")


needs_nb = pytest.mark.skipif(
    not _nb_toolchain(), reason="native toolchain (pwexec) unavailable"
)


def _connector_pair(lcols=("a", "v"), rcols=("b", "w")):
    class L(pw.Schema):
        a: int
        v: int

    class R(pw.Schema):
        b: int
        w: int

    class LS(pw.io.python.ConnectorSubject):
        _deletions_enabled = False

        def run(self):
            self.next_batch([{"a": i, "v": i} for i in range(10)])
            self.commit()

    class RS(pw.io.python.ConnectorSubject):
        _deletions_enabled = False

        def run(self):
            self.next_batch([{"b": i, "w": i} for i in range(10)])
            self.commit()

    lt = pw.io.python.read(LS(), schema=L, autocommit_duration_ms=None)
    rt = pw.io.python.read(RS(), schema=R, autocommit_duration_ms=None)
    return lt, rt


def _source_table(extra_cols=None):
    cols = {"g": str, "v": int}
    cols.update(extra_cols or {})
    schema = pw.schema_from_types(**cols)

    class Src(pw.io.python.ConnectorSubject):
        _deletions_enabled = False

        def run(self):
            self.commit()

    return pw.io.python.read(
        Src(), schema=schema, autocommit_duration_ms=None
    )


def _diags(report, code):
    return [d for d in report.diagnostics if d.code == code]


# -- the six deliberately-broken plans (pinned blame + provenance) --------

@needs_nb
def test_broken_plan_join_id_expression():
    lt, rt = _connector_pair()
    out = lt.join(rt, lt.a == rt.b, id=lt.v).select(  # JOIN-ID-LINE
        v=pw.left.v, w=pw.right.w
    )
    report = pw.analyze(out)
    assert report.verdict == "degraded"
    [d] = _diags(report, "fusion.join")
    assert "id=" in d.message and "computed" in d.message
    assert d.where and "test_plan_doctor.py" in d.where
    assert "JOIN-ID-LINE" in d.where  # provenance = the user's join line


@needs_nb
def test_broken_plan_multi_arg_reducer():
    t = _source_table()
    agg = t.groupby(pw.this.g).reduce(
        g=pw.this.g, s=pw.reducers.sum(pw.this.v, pw.this.v)
    )
    report = pw.analyze(agg)
    assert report.verdict == "degraded"
    [d] = _diags(report, "fusion.groupby")
    assert "2 arguments" in d.message
    assert d.where and "test_plan_doctor.py" in d.where


@needs_nb
def test_broken_plan_expression_key_exchange():
    """Expression shard key at a 2-rank exchange: blame names the exact
    expression on both the exchange and the groupby."""
    t = _source_table()
    agg = t.groupby(pw.this.g + "!").reduce(c=pw.reducers.count())
    report = pw.analyze(agg, processes=2)
    assert report.verdict == "degraded"
    # the chain breaks AT the exchange (the first node the columnar flow
    # cannot pass); its blame names the exact grouping expression
    [d] = _diags(report, "fusion.exchange")
    assert "not a plain column" in d.message
    assert '.g + ' in d.message  # names the offending expression
    # downstream of the broken boundary the groupby is honestly "tuple",
    # with the same reasons recorded on its node entry
    [entry] = [n for n in report.nodes if n["kind"] == "groupby"]
    assert entry["verdict"] == "tuple"
    assert any("not a plain column" in r for r in entry["reasons"])


@needs_nb
def test_outer_join_blames_pad_transitions():
    """Fusion-blame for a fused-eligible left join names the real
    reason the chain breaks downstream: tuple pad-transition batches."""
    lt, rt = _connector_pair()
    out = lt.join_left(rt, lt.a == rt.b).select(
        v=pw.left.v, w=pw.right.w
    )
    report = pw.analyze(out)
    assert report.verdict == "degraded"
    [d] = _diags(report, "fusion.join")
    assert "pad-transition" in d.message
    assert "left join" in d.message


@needs_nb
def test_join_exchange_blame_is_per_side():
    """A join broken only on its RIGHT key: the left exchange still
    ships columnar on its own plain-column shard key, and the right
    exchange's blame names the RIGHT expression — not the whole combined
    tuple (which would misattribute the other side's expression)."""
    lt, rt = _connector_pair()
    out = lt.join(rt, lt.a == rt.b + 1).select(
        v=pw.left.v, w=pw.right.w
    )
    report = pw.analyze(out, processes=2)
    assert report.verdict == "degraded"
    lex, rex = report.by_kind("exchange")[:2]  # construction order: L, R
    assert lex["verdict"] == "fused", lex
    assert rex["verdict"] == "degraded", rex
    assert any("right join key" in r for r in rex["reasons"])
    assert not any("left join key" in r for r in rex["reasons"])
    # the JOIN carries the combined blame
    [entry] = [n for n in report.nodes if n["kind"] == "join"]
    assert any("right join key" in r for r in entry["reasons"])


@needs_nb
def test_broken_plan_object_key_source():
    """Tuple-typed column: the SOURCE has no columnar door — the plan is
    honestly 'tuple', and the source diagnostic names the column dtype."""
    t = _source_table(extra_cols={"meta": tuple})
    agg = t.groupby(pw.this.g).reduce(c=pw.reducers.count())
    report = pw.analyze(agg)
    assert report.verdict == "tuple"
    [d] = _diags(report, "fusion.source")
    assert "'meta'" in d.message and "columnar value set" in d.message


def test_broken_plan_nondeterministic_udf(monkeypatch):
    t = _source_table()
    label = pw.udf(lambda v: f"x{v}")  # pw.udf: deterministic=False
    sel = t.select(g=pw.this.g, lab=label(pw.this.v))
    agg = sel.groupby(pw.this.lab).reduce(c=pw.reducers.count())
    report = pw.analyze(agg, processes=2)
    diags = _diags(report, "replay.nondeterministic-udf")
    assert diags, report.render()
    assert "exchanged" in diags[0].message
    # and the memoized select breaks the fused chain
    assert report.verdict == "degraded" or not _nb_toolchain()


def test_nondeterministic_udf_persisted_single_rank():
    """At 1 rank nothing is exchanged, so the replay hazard exists only
    when the run persists state — pw.analyze(persistence=True) is how a
    caller says so (the scratch lowering never configures persistence)."""
    t = _source_table()
    label = pw.udf(lambda v: f"x{v}")  # pw.udf: deterministic=False
    sel = t.select(g=pw.this.g, lab=label(pw.this.v))
    assert not _diags(
        pw.analyze(sel), "replay.nondeterministic-udf"
    )  # 1 rank, no persistence: no divergence sink
    report = pw.analyze(sel, persistence=True)
    diags = _diags(report, "replay.nondeterministic-udf")
    assert diags, report.render()
    assert "persisted" in diags[0].message


def test_broken_plan_suspicious_deterministic_udf():
    import time as _time

    def stamp(v):
        return _time.time() + v

    t = _source_table()
    sel = t.select(s=pw.apply(stamp, pw.this.v))  # declared deterministic
    report = pw.analyze(sel)
    diags = _diags(report, "replay.suspicious-udf")
    assert diags, report.render()
    assert "'stamp'" in diags[0].message and "time" in diags[0].message


def test_broken_plan_unknown_env_knob(monkeypatch):
    monkeypatch.setenv("PATHWAY_THREDS", "8")  # typo'd PATHWAY_THREADS
    t = _source_table()
    report = pw.analyze(t)
    [d] = _diags(report, "knob.unknown")
    assert "PATHWAY_THREDS" in d.message
    assert d.hint and "PATHWAY_THREADS" in d.hint  # suggestion
    assert d.severity == "error"
    # PATHWAY_KNOB_CHECK=0 mirrors the runtime's escape hatch: the
    # finding is still reported but no longer gates (errors() empty, so
    # the CLI's exit-2 path and CI lanes keyed on it stay green)
    monkeypatch.setenv("PATHWAY_KNOB_CHECK", "0")
    report = pw.analyze(t)
    [d] = _diags(report, "knob.unknown")
    assert d.severity == "warning"
    assert not report.errors()


# -- knob registry --------------------------------------------------------

def test_knob_registry_covers_every_env_read():
    """Every PATHWAY_* name mentioned in the package source must be in
    the registry — a new knob without registration would be rejected at
    startup for users who set it."""
    import re

    pkg = os.path.join(REPO, "pathway_tpu")
    found = set()
    for root, _dirs, files in os.walk(pkg):
        for fn in files:
            if not fn.endswith(".py") or fn == "knobs.py":
                continue  # the registry's own docstring shows a typo
            with open(os.path.join(root, fn)) as f:
                found.update(re.findall(r"PATHWAY_[A-Z0-9_]+", f.read()))
    missing = found - set(pk.KNOBS)
    assert not missing, f"unregistered knobs: {sorted(missing)}"


def test_knob_registry_has_no_entry_that_nothing_reads():
    """The inverse: a registered name that no source under the package
    mentions (outside the registry) is an option nobody can turn — it
    went with its reader and its entry stayed."""
    import re

    text = []
    for root, _dirs, files in os.walk(os.path.join(REPO, "pathway_tpu")):
        for fn in files:
            if fn.endswith((".py", ".c", ".cc", ".cpp", ".h")) and fn != "knobs.py":
                with open(os.path.join(root, fn), errors="replace") as f:
                    text.append(f.read())
    # the suite's own switch is read where the suite starts
    with open(os.path.join(REPO, "tests", "conftest.py")) as f:
        text.append(f.read())
    read = set(re.findall(r"PATHWAY_[A-Z0-9_]+", "\n".join(text)))
    unread = set(pk.KNOBS) - read
    assert not unread, f"registered, read nowhere: {sorted(unread)}"


def test_knob_validation_rejects_bad_values(monkeypatch):
    monkeypatch.setenv("PATHWAY_THREADS", "zero")
    findings = pk.validate_environment()
    assert any(n == "PATHWAY_THREADS" for n, _, _ in findings)
    monkeypatch.setenv("PATHWAY_THREADS", "-3")
    findings = pk.validate_environment()
    assert any("below the minimum" in p for _, p, _ in findings)
    monkeypatch.setenv("PATHWAY_THREADS", "4")
    monkeypatch.setenv("PATHWAY_SNAPSHOT_ACCESS", "recrod")
    findings = pk.validate_environment()
    assert any("one of" in p for _, p, _ in findings)


def test_runtime_rejects_unknown_knob_at_startup(monkeypatch):
    from pathway_tpu.engine.runtime import Runtime

    pk._checked = None  # drop the memo so this env snapshot re-validates
    monkeypatch.setenv("PATHWAY_NO_NB_JION", "1")  # typo'd NO_NB_JOIN
    with pytest.raises(pk.KnobError, match="PATHWAY_NO_NB_JION"):
        Runtime()
    # escape hatch downgrades to a warning
    monkeypatch.setenv("PATHWAY_KNOB_CHECK", "0")
    pk._checked = None
    Runtime()
    pk._checked = None


def test_knob_table_markdown_lists_all():
    table = pk.knob_table_markdown()
    for name in pk.KNOBS:
        assert f"`{name}`" in table


# -- strict mode + fallback counter (satellite 1) -------------------------

@needs_nb
def test_nb_strict_raises_with_blame_on_demotion(monkeypatch):
    """A fused-eligible groupby that hits a beyond-i64 reducer arg
    normally demotes silently to the Python path; PATHWAY_NB_STRICT=1
    must raise the fusion-blame diagnostic instead."""
    from pathway_tpu.internals.graph_runner import GraphRunner

    def build():
        pw.internals.parse_graph.G.clear()
        t = pw.debug.table_from_rows(
            pw.schema_from_types(g=str, v=int),
            [(0, "a", 2**70), (1, "a", 1)],
        )
        return t.groupby(pw.this.g).reduce(
            g=pw.this.g, s=pw.reducers.sum(pw.this.v)
        )

    # sanity: non-strict run completes on the tuple path
    agg = build()
    rows = list(GraphRunner().run_tables(agg)[0].state.rows.values())
    assert rows == [("a", 2**70 + 1)]

    monkeypatch.setenv("PATHWAY_NB_STRICT", "1")
    agg = build()
    with pytest.raises(elig.NBStrictError, match="GroupByNode"):
        GraphRunner().run_tables(agg)


def test_nb_strict_covers_exchange_deoptimization(monkeypatch):
    """NB_STRICT's documented contract covers EVERY fused-eligible node
    leaving the columnar path — including an exchange whose
    statically-columnar input arrives as tuple deltas (which otherwise
    only shows up as an _fallbacks increment)."""
    import types

    from pathway_tpu.engine import nodes as N

    monkeypatch.setattr(
        N._elig, "expects_native_batch", lambda node: True
    )
    # a real ExchangeNode skeleton (strict_error names type(node)), with
    # __init__ bypassed so no scope/runtime plumbing is needed
    fake = object.__new__(N.ExchangeNode)
    fake.scope = types.SimpleNamespace(
        runtime=types.SimpleNamespace(
            procgroup=types.SimpleNamespace(world=2, rank=0),
            stats=types.SimpleNamespace(
                on_exchange_fallback=lambda: None,
                on_exchange_elided=lambda n: None,
            ),
        )
    )
    fake.mode = "hash"
    fake.nb_kidx = (0,)
    fake.nb_decision = elig.NBDecision(True, ())
    fake._nb_ok = True
    fake._nb_batches = 0
    fake._fallbacks = 0
    fake.inputs = [None]
    fake.key_batch = lambda keys, rows: [(r[0],) for r in rows]
    fake.trace = None
    fake.node_id = 7
    deltas = [(1, ("a",), 1), (2, ("b",), 1)]
    # non-strict: counted as a fallback, sliced on the tuple path
    own, sends = N.ExchangeNode._slice(fake, list(deltas))
    assert fake._fallbacks == 1
    monkeypatch.setenv("PATHWAY_NB_STRICT", "1")
    with pytest.raises(elig.NBStrictError, match="ExchangeNode"):
        N.ExchangeNode._slice(fake, list(deltas))
    # but an exchange the PLAN already called tuple must not raise
    fake.nb_decision = elig.NBDecision(False, ("expression shard key",))
    N.ExchangeNode._slice(fake, list(deltas))


@needs_nb
def test_fallback_counted_once_on_demotion_not_per_batch():
    """Demotion fallback accounting: a columnar-capable source whose
    mid-stream batch carries a beyond-i64 value demotes the groupby once;
    the post-demotion columnar batches must NOT each count a fallback."""
    from pathway_tpu.engine.nodes import GroupByNode
    from pathway_tpu.internals.graph_runner import GraphRunner

    class S(pw.Schema):
        g: str
        v: int

    class Src(pw.io.python.ConnectorSubject):
        _deletions_enabled = False

        def run(self):
            self.next_batch([{"g": "a", "v": 1}] * 5)
            self.commit()
            # beyond-i64 value: the columnar parser refuses the batch
            # (tuple path) and the native store Falls Back -> demotion
            self.next_batch([{"g": "a", "v": 2**70}])
            self.commit()
            for _ in range(3):  # post-demotion batches: no re-count
                self.next_batch([{"g": "b", "v": 2}] * 4)
                self.commit()

    t = pw.io.python.read(Src(), schema=S, autocommit_duration_ms=None)
    agg = t.groupby(pw.this.g).reduce(
        g=pw.this.g, s=pw.reducers.sum(pw.this.v)
    )
    import pathway_tpu.engine.runtime as rt_mod

    insts = []
    orig = rt_mod.Runtime.__init__

    def spy(self, *a, **k):
        orig(self, *a, **k)
        insts.append(self)

    rt_mod.Runtime.__init__ = spy
    try:
        [cap] = GraphRunner().run_tables(agg)
    finally:
        rt_mod.Runtime.__init__ = orig
    rows = dict(cap.state.rows)
    assert sorted(rows.values()) == [("a", 2**70 + 5), ("b", 24)]
    rt = insts[0]
    [gb] = [n for n in rt.scope.nodes if isinstance(n, GroupByNode)]
    assert gb._fallback_demoted
    assert gb._nb_fallbacks == 1, gb._nb_fallbacks
    assert rt.stats.nb_fallbacks == 1


# -- report shape + CLI ---------------------------------------------------

@needs_nb
def test_json_report_schema():
    lt, rt = _connector_pair()
    out = lt.join(rt, lt.a == rt.b).select(v=pw.left.v, w=pw.right.w)
    report = pw.analyze(out, processes=2)
    data = json.loads(report.to_json())
    assert data["schema"] == "pathway_tpu.analysis/v1"
    assert data["verdict"] == "fused"
    assert data["processes"] == 2
    assert set(data["summary"]) == {
        "nodes", "fused_nodes", "degraded_nodes", "diagnostics",
    }
    for node in data["nodes"]:
        assert {"node_id", "node", "kind", "verdict", "reasons", "where"} <= set(node)
        assert node["verdict"] in ("fused", "degraded", "tuple")
    for d in data["diagnostics"]:
        assert d["severity"] in ("info", "warning", "error")


def test_cli_program_mode_and_gate(tmp_path):
    prog = tmp_path / "prog.py"
    prog.write_text(
        "import pathway_tpu as pw\n"
        "t = pw.debug.table_from_rows(pw.schema_from_types(a=int), [(1,)])\n"
        "out = t.select(b=pw.this.a + 1)\n"
        "pw.io.subscribe(out, on_change=lambda *a: None)\n"
        "pw.run(monitoring_level=pw.MonitoringLevel.NONE)\n"
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    res = subprocess.run(
        [sys.executable, "-m", "pathway_tpu.analysis", "--json", str(prog)],
        capture_output=True, text=True, env=env, timeout=180,
    )
    assert res.returncode == 0, res.stderr
    data = json.loads(res.stdout)
    assert data["verdict"] == "tuple"  # static source: honest verdict
    # the gate rejects a non-fused plan
    res = subprocess.run(
        [sys.executable, "-m", "pathway_tpu.analysis", "--require-fused",
         str(prog)],
        capture_output=True, text=True, env=env, timeout=180,
    )
    assert res.returncode == 1
    assert "not fused" in res.stderr
    # flag-style args after the program path are the PROGRAM's argv
    # (argparse.REMAINDER), not doctor options to choke on
    argprog = prog.parent / "argprog.py"
    argprog.write_text(
        "import sys\n"
        "assert sys.argv[1:] == ['--limit', '5'], sys.argv\n"
        + prog.read_text()
    )
    res = subprocess.run(
        [sys.executable, "-m", "pathway_tpu.analysis", "--json",
         str(argprog), "--limit", "5"],
        capture_output=True, text=True, env=env, timeout=180,
    )
    assert res.returncode == 0, res.stderr
    assert json.loads(res.stdout)["verdict"] == "tuple"


def test_cli_diagnoses_bad_config_backed_knob(tmp_path):
    """A config-backed PATHWAY_* var that fails to parse must come back
    as the doctor's knob.invalid report (exit 2), not an import-time
    traceback — config construction is lazy exactly so the CLI can
    import the package under a broken environment."""
    prog = tmp_path / "prog.py"
    prog.write_text(
        "import pathway_tpu as pw\n"
        "t = pw.debug.table_from_rows(pw.schema_from_types(a=int), [(1,)])\n"
        "pw.io.subscribe(t, on_change=lambda *a: None)\n"
        "pw.run()\n"
    )
    env = dict(
        os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO,
        PATHWAY_PROCESSES="abc",
    )
    res = subprocess.run(
        [sys.executable, "-m", "pathway_tpu.analysis", str(prog)],
        capture_output=True, text=True, env=env, timeout=180,
    )
    assert res.returncode == 2, res.stderr
    assert "knob.invalid" in res.stderr
    assert "PATHWAY_PROCESSES" in res.stderr
    assert "Traceback" not in res.stderr


def test_cli_program_mode_sees_persistence(tmp_path):
    """The CLI observes the program's persistence_config (via the stubbed
    Runtime.__init__), so a 1-rank non-deterministic UDF feeding persisted
    state IS diagnosed — it would be invisible to a bare pw.analyze()."""
    pdir = tmp_path / "pstate"
    prog = tmp_path / "prog.py"
    prog.write_text(
        "import pathway_tpu as pw\n"
        "t = pw.debug.table_from_rows(pw.schema_from_types(a=int), [(1,)])\n"
        "lab = pw.udf(lambda v: f'x{v}')\n"
        "out = t.select(b=lab(pw.this.a))\n"
        "pw.io.subscribe(out, on_change=lambda *a: None)\n"
        "pw.run(persistence_config=pw.persistence.Config(\n"
        f"    backend=pw.persistence.Backend.filesystem({str(pdir)!r})))\n"
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    res = subprocess.run(
        [sys.executable, "-m", "pathway_tpu.analysis", "--json", str(prog)],
        capture_output=True, text=True, env=env, timeout=180,
    )
    assert res.returncode == 0, res.stderr
    data = json.loads(res.stdout)
    replay = [
        d for d in data["diagnostics"]
        if d["code"] == "replay.nondeterministic-udf"
    ]
    assert replay, data
    assert "persisted" in replay[0]["message"]


def test_gil_lint_clean_and_detects_seeded_violations(tmp_path):
    lint = os.path.join(REPO, "scripts", "lint_gil.py")
    res = subprocess.run(
        [sys.executable, lint], capture_output=True, text=True, timeout=120,
    )
    assert res.returncode == 0, res.stdout + res.stderr
    bad = tmp_path / "bad.cpp"
    bad.write_text(
        "int f() {\n"
        "    /* phase 1: extract */\n"
        '    PyErr_SetString(PyExc_TypeError, "x");\n'
        "    /* phase 1 passed */\n"
        "    Py_BEGIN_ALLOW_THREADS\n"
        "    Py_DECREF(x);\n"
        "    Py_END_ALLOW_THREADS\n"
        "}\n"
    )
    res = subprocess.run(
        [sys.executable, lint, str(bad)],
        capture_output=True, text=True, timeout=120,
    )
    assert res.returncode == 1
    assert "Py_DECREF" in res.stdout
    assert "non-Fallback error" in res.stdout


# -- eligibility is the single source of truth ----------------------------

def test_executor_decisions_come_from_eligibility(monkeypatch):
    """The node constructors must gate their columnar paths on the SAME
    NBDecision objects the analyzer reads — flipping the decision flips
    the node flag with no second predicate to drift."""
    calls = []
    orig = elig.decide_join_nb

    def spy(**kw):
        d = orig(**kw)
        calls.append(d)
        return d

    monkeypatch.setattr(elig, "decide_join_nb", spy)
    lt, rt = _connector_pair()
    out = lt.join(rt, lt.a == rt.b).select(v=pw.left.v)
    from pathway_tpu.engine.nodes import JoinNode
    from pathway_tpu.engine.runtime import Runtime
    from pathway_tpu.internals.graph_runner import GraphRunner

    g = pw.internals.parse_graph.G
    ops = g.reachable_operators([out._source])
    runtime = Runtime()
    GraphRunner()._lower(ops, runtime)
    [jn] = [n for n in runtime.scope.nodes if isinstance(n, JoinNode)]
    assert calls and jn.nb_decision is calls[-1]
    assert jn._nb_ok == jn.nb_decision.ok
