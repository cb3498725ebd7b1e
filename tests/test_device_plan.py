"""Device Doctor battery (ISSUE 20): static dispatch-plane analysis.

The seeded-defect battery — an un-donated index write, an injected
mid-chain ``.item()`` host sync, an unbounded-bucket pipeline, and an
over-budget shard layout — must each be caught STATICALLY with correct
provenance and a fix hint on a chain the product dispatches, while the
shipped encoder, KNN and sharded-KNN chains verify device-clean with
zero execution (the armed device plane records no dispatch during
analysis). Satellite coverage: the site
registry round-trips through the lint pass, the per-shape compiled-cost
cache is bounded, and every dispatch site ticks
``device_site_recompiles_total`` on a fresh shape bucket.
"""

from __future__ import annotations

import json
import os

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from pathway_tpu.analysis.device_plan import (  # noqa: E402
    MUTANTS,
    WorkloadSpec,
    analyze_device_plan,
    join_profile,
    simulate_knn_buckets,
)
from pathway_tpu.internals.device import (  # noqa: E402
    PLANE,
    registered_sites,
)
from pathway_tpu.internals.monitoring import ProberStats  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

ALL_SITES = {
    "answer.decode", "answer.prefill",
    "encoder.forward", "knn.search", "knn.sharded_search",
    "knn.sharded_write", "knn.write", "serve.window",
}


@pytest.fixture(autouse=True)
def _disarmed_plane():
    PLANE.disarm()
    yield
    PLANE.disarm()


def _diag(report, code):
    hits = [d for d in report.diagnostics if d.code == code]
    assert hits, (
        f"expected diagnostic {code}; got "
        f"{[d.code for d in report.diagnostics]}"
    )
    return hits[0]


# -- shipped chains: clean, with zero execution -----------------------------

def test_shipped_chains_analyze_clean_with_zero_execution():
    """The Doctor's whole contract: verdicts BEFORE a single dispatch
    runs. The device plane is armed during analysis — if any chain
    actually executed, its dispatch record/recompile tick would land on
    these stats."""
    stats = ProberStats()
    PLANE.arm(None, stats)
    try:
        report = analyze_device_plan()
    finally:
        PLANE.disarm()
    assert report.verdict == "device-clean"
    assert report.device_clean
    assert set(report.chains) == {"knn", "sharded", "encoder"}
    assert all(v == "clean" for v in report.chains.values())
    assert stats.device_sites == {}, "analysis must not dispatch"
    assert stats.device_recompiles == {}, "analysis must not compile-tick"


def test_report_shape_and_json_roundtrip():
    report = analyze_device_plan()
    d = report.to_dict()
    assert d["schema"] == "pathway_tpu.analysis.device/v1"
    assert d["verdict"] == "device-clean"
    # every registered chain site carries a bucket/recompile prediction
    for site in (
        "knn.write", "knn.search", "knn.sharded_write",
        "knn.sharded_search", "encoder.forward",
    ):
        assert d["predictions"][site]["recompiles"] >= 1
    assert d["hbm"]["footprint_bytes"] > 0
    assert d["hbm"]["budget_bytes"] > 0
    json.loads(report.to_json())  # serializable
    assert "device plan verdict: DEVICE-CLEAN" in report.render()


# -- seeded defect battery ---------------------------------------------------

# (mutant, declared world, site blamed, its file, its chain, the message's
# blame, the hint's fix): the defects sit in what the product dispatches
SEEDED = [
    ("undonated_write", 1, "knn.write", "pathway_tpu/ops/knn.py", "knn",
     "MB", "donate_argnums"),
    ("undonated_write", 4, "knn.sharded_write",
     "pathway_tpu/parallel/sharded_knn.py", "sharded",
     "MB", "donate_argnums"),
    ("host_sync", 1, "encoder.forward", "pathway_tpu/models/encoder.py",
     "encoder", ".item()", ""),
    ("host_sync", 1, "knn.search", "pathway_tpu/ops/knn.py", "knn",
     ".item()", ""),
]


@pytest.mark.parametrize(
    "mutant, world, site, path, chain, blame, fix", SEEDED,
    ids=[f"{m}-{s}" for m, _, s, *_ in SEEDED],
)
def test_mutant_is_caught_on_a_chain_the_product_dispatches(
    mutant, world, site, path, chain, blame, fix
):
    code = {
        "undonated_write": "device.donation", "host_sync": "device.host_sync",
    }[mutant]
    report = analyze_device_plan(mutant=mutant, world=world)
    assert report.verdict == "device-dirty"
    hits = [
        d for d in report.diagnostics if d.code == code and d.node == site
    ]
    assert hits, [(d.code, d.node) for d in report.diagnostics]
    d = hits[0]
    assert d.severity == "error"
    assert d.where.startswith(path + ":")
    assert blame in d.message  # the copy's cost, or the read that syncs
    assert d.hint and fix in d.hint
    assert report.chains[chain] == "dirty"
    # the defect is localized: a chain it was not seeded in stays clean
    seeded_in = {
        "undonated_write": {chain}, "host_sync": {"encoder", "knn"},
    }[mutant]
    for other in set(report.chains) - seeded_in:
        assert report.chains[other] == "clean", other


@pytest.mark.parametrize("mutant", MUTANTS)
def test_every_error_of_a_mutant_names_a_product_site_and_a_file(mutant):
    """The CLI's exit-2 contract (ci lane 18): whatever a seeded defect
    is blamed on is a site the product dispatches, in a file that is
    there to open."""
    report = analyze_device_plan(mutant=mutant)
    errors = [d for d in report.diagnostics if d.severity == "error"]
    assert errors and report.verdict == "device-dirty"
    for d in errors:
        assert d.node in registered_sites(), d
        where_file, _, where_name = d.where.partition(":")
        assert where_name and os.path.isfile(os.path.join(REPO, where_file)), d


def test_mutant_unbounded_buckets_is_refused():
    report = analyze_device_plan(mutant="unbounded_buckets")
    assert report.verdict == "device-dirty"
    d = _diag(report, "device.retrace.unbounded")
    assert d.severity == "error"
    assert "retrace" in d.message or "compile" in d.message
    assert "cap" in d.hint


def test_mutant_over_budget_layout_is_refused():
    report = analyze_device_plan(mutant="over_budget")
    assert report.verdict == "device-dirty"
    d = _diag(report, "device.hbm.over_budget")
    assert d.severity == "error"
    assert report.hbm["footprint_bytes"] > report.hbm["budget_bytes"]
    assert "PATHWAY_DEVICE_HBM_BYTES" in d.hint
    assert "shard" in d.hint


def test_unknown_mutant_rejected():
    with pytest.raises(ValueError, match="unknown device mutant"):
        analyze_device_plan(mutant="nope")
    assert set(MUTANTS) == {
        "undonated_write", "host_sync", "unbounded_buckets", "over_budget",
    }


def test_hbm_budget_honors_env_override(monkeypatch):
    """PATHWAY_DEVICE_HBM_BYTES models a target chip on CPU/CI: a tiny
    budget refuses even the default workload; a generous one admits a
    corpus the 8 GiB fallback would refuse at world=1."""
    monkeypatch.setenv("PATHWAY_DEVICE_HBM_BYTES", "1000000")
    report = analyze_device_plan()
    assert report.verdict == "device-dirty"
    assert any(d.code == "device.hbm.over_budget" for d in report.errors())

    monkeypatch.setenv("PATHWAY_DEVICE_HBM_BYTES", str(10**15))
    big = WorkloadSpec(corpus_rows=2**27)
    report = analyze_device_plan(workload=big)
    assert not any(
        d.code == "device.hbm.over_budget" for d in report.diagnostics
    )


def test_sharding_amortizes_the_hbm_footprint():
    """The same corpus that busts one chip fits when declared across a
    mesh: per-chip capacity scales down with the world."""
    spec = WorkloadSpec(corpus_rows=2**22)
    one = analyze_device_plan(workload=spec, world=1)
    eight = analyze_device_plan(workload=spec, world=8)
    assert (
        eight.hbm["per_chip_capacity"] < one.hbm["per_chip_capacity"]
    )
    assert eight.hbm["footprint_bytes"] < one.hbm["footprint_bytes"]


def test_tree_merge_requires_pow2_world():
    """PATHWAY_INDEX_MERGE=tree at a non-pow2 world silently degrades
    to gather at runtime (parallel/sharded_knn._merge_mode) — the
    Doctor surfaces the degradation statically."""
    old = os.environ.pop("PATHWAY_INDEX_MERGE", None)
    os.environ["PATHWAY_INDEX_MERGE"] = "tree"
    try:
        report = analyze_device_plan(world=3)
        assert any(
            d.code == "device.mesh.merge" for d in report.diagnostics
        )
        assert report.verdict == "device-degraded"
        clean = analyze_device_plan(world=4)
        assert not any(
            d.code == "device.mesh.merge" for d in clean.diagnostics
        )
    finally:
        if old is None:
            os.environ.pop("PATHWAY_INDEX_MERGE", None)
        else:
            os.environ["PATHWAY_INDEX_MERGE"] = old


# -- donation positive pin ---------------------------------------------------

def test_shipped_write_chain_lowers_with_aliasing_markers():
    """Positive half of the donation audit: the SHIPPED index-write
    chain's lowered MLIR really does alias the donated buffer triple
    (the audit is reading a real signal, not vacuously passing)."""
    from pathway_tpu.analysis.device_plan import (
        _aliased_flat_args,
        _donated_flat_indices,
    )
    from pathway_tpu.ops.knn import _write_slots

    S = jax.ShapeDtypeStruct
    avals = (
        S((128, 16), jnp.float32), S((128,), jnp.bool_),
        S((128,), jnp.float32), S((4,), jnp.int32),
        S((4, 16), jnp.float32), S((4,), jnp.bool_),
    )
    text = _write_slots.lower(*avals).as_text()
    aliased = _aliased_flat_args(text)
    wanted = _donated_flat_indices(avals, (0, 1, 2))
    assert wanted == [0, 1, 2]
    assert set(wanted) <= aliased


# -- retrace predictions (shared bucket enumeration) -------------------------

def test_bucket_simulation_dedups_equal_shapes():
    from pathway_tpu.models.encoder import EncoderConfig

    spec = WorkloadSpec(
        ingest_batches=((8, 40), (8, 40)),
        write_batches=(8, 8),
        query_batches=(1, 1),
        ks=(10,),
    )
    # an encode call's rows are one index write: four writes of 8 rows
    # (one member of the tiny encoder's shape set holds a call whole),
    # all inside the first 128 slots
    assert spec.write_rows() == (8, 8, 8, 8)
    wb, sb = simulate_knn_buckets(spec)
    assert len(wb) == 1
    assert len(sb) == 1
    predictions = analyze_device_plan(
        workload=spec, config=EncoderConfig.tiny()
    ).predictions
    assert predictions["encoder.forward"]["recompiles"] == 1
    assert predictions["knn.write"]["buckets"] == wb
    assert predictions["knn.search"]["buckets"] == sb
    assert predictions["knn.sharded_write"]["recompiles"] == 1

    # crossing the pow2 capacity IS a fresh bucket (growth reshape =
    # fresh executable) — the simulation models it, whichever kind of
    # write crosses it
    for grown in (
        WorkloadSpec(ingest_batches=((64, 40),) * 3, write_batches=()),
        WorkloadSpec(ingest_batches=(), write_batches=(64,) * 3),
    ):
        wb, _ = simulate_knn_buckets(grown)
        assert len(wb) == 2


def test_excessive_bucket_set_warns(monkeypatch):
    monkeypatch.setenv("PATHWAY_DEVICE_PLAN_MAX_BUCKETS", "2")
    spec = WorkloadSpec(
        ingest_batches=tuple((8 * (i + 1), 32 * (i + 1)) for i in range(4)),
    )
    report = analyze_device_plan(workload=spec)
    assert any(
        d.code == "device.retrace.excessive" for d in report.diagnostics
    )
    assert report.verdict == "device-degraded"


# -- drift join (--profile) --------------------------------------------------

def test_join_profile_flags_measured_exceeding_predicted():
    report = analyze_device_plan()
    predicted = report.predictions["encoder.forward"]["recompiles"]
    joined = join_profile(
        analyze_device_plan(),
        {"device_recompiles": {"encoder.forward": predicted + 5}},
    )
    assert joined.verdict == "device-dirty"
    d = _diag(joined, "device.retrace.drift")
    assert d.node == "encoder.forward"
    p = joined.predictions["encoder.forward"]
    assert p["drift"] == "exceeded"
    assert p["measured_recompiles"] == predicted + 5

    ok = join_profile(
        analyze_device_plan(),
        {"device_recompiles": {"encoder.forward": predicted}},
    )
    assert ok.verdict == "device-clean"
    assert ok.predictions["encoder.forward"]["drift"] == "ok"


# -- analyzer / CLI integration ----------------------------------------------

def test_analyze_device_kwarg_attaches_subreport():
    import pathway_tpu as pw

    t = pw.debug.table_from_rows(
        pw.schema_from_types(x=int), [(1,), (2,)]
    )
    report = pw.analyze(t, device=True)
    assert report.device is not None
    assert report.device["verdict"] == "device-clean"
    assert report.device["reachable_sites"] == []
    assert report.to_dict()["device"]["schema"] == (
        "pathway_tpu.analysis.device/v1"
    )
    plain = pw.analyze(t)
    assert plain.device is None
    assert "device" not in plain.to_dict()


def test_device_doctor_gate_knob(monkeypatch):
    import pathway_tpu as pw

    monkeypatch.setenv("PATHWAY_DEVICE_DOCTOR", "0")
    t = pw.debug.table_from_rows(pw.schema_from_types(x=int), [(3,)])
    report = pw.analyze(t, device=True)
    assert report.device is None


def test_cli_device_plan_exit_codes(capsys):
    from pathway_tpu.analysis.__main__ import main

    assert main(["--device-plan", "--require-device-clean"]) == 0
    out = capsys.readouterr().out
    assert "DEVICE-CLEAN" in out
    for mutant in MUTANTS:
        assert main(["--device-plan", "--device-mutant", mutant]) == 2


def test_cli_device_plan_json(capsys):
    from pathway_tpu.analysis.__main__ import main

    assert main(["--device-plan", "--json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["schema"] == "pathway_tpu.analysis.device/v1"
    assert doc["verdict"] == "device-clean"


def test_cli_profile_join(tmp_path, capsys):
    from pathway_tpu.analysis.__main__ import main

    trace = tmp_path / "trace.json"
    trace.write_text(json.dumps(
        {"device_recompiles": {"encoder.forward": 10_000}}
    ))
    rc = main(["--device-plan", "--profile", str(trace)])
    assert rc == 2  # drift is an error
    assert "drift" in capsys.readouterr().out


# -- registry + lint round-trip (satellite 6) --------------------------------

def test_registry_covers_every_dispatch_site():
    # registrations live next to their dispatch sites — importing the
    # dispatch modules populates the registry (analyze_device_plan pulls
    # most in; the serving gateway and the decoder register on import here)
    import pathway_tpu.io.http._server  # noqa: F401
    import pathway_tpu.models.decoder  # noqa: F401
    import pathway_tpu.models.encoder  # noqa: F401
    import pathway_tpu.ops.knn  # noqa: F401
    import pathway_tpu.parallel.sharded_knn  # noqa: F401

    sites = registered_sites()
    assert set(sites) == ALL_SITES
    for name, site in sites.items():
        assert callable(site.cost_model), name
        assert isinstance(site.dtypes, tuple), name
        assert site.where, name


def test_lint_device_site_pass_round_trips():
    import sys

    sys.path.insert(
        0,
        os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "scripts"),
    )
    try:
        import lint_gil
    finally:
        sys.path.pop(0)
    assert lint_gil.device_site_pass() == []


def test_lint_device_site_pass_catches_drift(tmp_path):
    import sys

    sys.path.insert(
        0,
        os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "scripts"),
    )
    try:
        import lint_gil
    finally:
        sys.path.pop(0)
    (tmp_path / "mod.py").write_text(
        'device_site("a.b", dtypes=())\n'
        '_DEVICE.begin("c.d")\n'
    )
    findings = lint_gil.device_site_pass(str(tmp_path))
    assert any("without cost_model" in f for f in findings)
    assert any("'c.d'" in f and "not in" in f for f in findings)
    assert any("never" in f and "'a.b'" in f for f in findings)


def test_external_index_node_exposes_adapter_sites():
    from pathway_tpu.ops.knn import KnnShard

    shard = KnnShard(8, capacity=128)
    assert shard.device_sites == ("knn.write", "knn.search")

    class _Node:
        device_sites = __import__(
            "pathway_tpu.engine.external_index",
            fromlist=["ExternalIndexNode"],
        ).ExternalIndexNode.device_sites

        def __init__(self, adapter):
            self.adapter = adapter

    assert _Node(shard).device_sites() == ("knn.write", "knn.search")
    assert _Node(object()).device_sites() == ()


# -- bounded cost cache (satellite 1) ----------------------------------------

def test_compiled_cost_cache_is_bounded(monkeypatch):
    from pathway_tpu.internals import device as dev

    monkeypatch.setenv("PATHWAY_DEVICE_COST_CACHE_CAP", "3")
    monkeypatch.setattr(dev, "_COST_CACHE", {})
    for i in range(10):
        dev.compiled_cost(("t", i), None, (), (float(i), float(i)))
    assert len(dev._COST_CACHE) == 3
    # oldest-first eviction: only the newest shape keys survive
    assert set(dev._COST_CACHE) == {("t", 7), ("t", 8), ("t", 9)}


# -- recompile ticking at every site (satellite 1) ---------------------------

def test_knn_sites_tick_recompiles_per_fresh_bucket():
    from pathway_tpu.ops.knn import KnnShard

    stats = ProberStats()
    PLANE.arm(None, stats)
    try:
        shard = KnnShard(8, capacity=128)
        rng = np.random.default_rng(0)
        shard.add(["a", "b"], rng.normal(size=(2, 8)).astype(np.float32))
        shard.search(rng.normal(size=(1, 8)).astype(np.float32), k=2)
        assert stats.device_recompiles["knn.write"] == 1
        assert stats.device_recompiles["knn.search"] == 1
        # same shapes again: no fresh bucket, no tick
        shard.add(["c", "d"], rng.normal(size=(2, 8)).astype(np.float32))
        shard.search(rng.normal(size=(1, 8)).astype(np.float32), k=2)
        assert stats.device_recompiles["knn.write"] == 1
        assert stats.device_recompiles["knn.search"] == 1
        # a new write width IS a fresh executable
        shard.add(
            ["e", "f", "g"], rng.normal(size=(3, 8)).astype(np.float32)
        )
        assert stats.device_recompiles["knn.write"] == 2
    finally:
        PLANE.disarm()


def test_sharded_sites_tick_recompiles():
    from jax.sharding import Mesh

    from pathway_tpu.parallel.sharded_knn import ShardedKnnIndex

    stats = ProberStats()
    PLANE.arm(None, stats)
    try:
        mesh = Mesh(np.array(jax.devices()[:1]), ("dp",))
        idx = ShardedKnnIndex(8, mesh)
        rng = np.random.default_rng(1)
        idx.add(["a", "b"], rng.normal(size=(2, 8)).astype(np.float32))
        idx.search(rng.normal(size=(1, 8)).astype(np.float32), k=2)
        assert stats.device_recompiles["knn.sharded_write"] >= 1
        assert stats.device_recompiles["knn.sharded_search"] >= 1
        before = dict(stats.device_recompiles)
        idx.add(["c", "d"], rng.normal(size=(2, 8)).astype(np.float32))
        idx.search(rng.normal(size=(1, 8)).astype(np.float32), k=2)
        assert stats.device_recompiles == before
    finally:
        PLANE.disarm()
