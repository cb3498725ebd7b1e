"""Device fault domain battery (ISSUE 17).

Pins the tentpole contracts:

* **restore-vs-rebuild parity** — an index restored from its
  epoch-aligned snapshot (inline OR segment chain) answers every query
  BIT-identical (ids AND float scores) to the uninterrupted index, over
  the same insert/delete/query interleavings the sharded parity battery
  runs, under both cross-shard merge strategies, same-world and through
  an N→M re-shard (2→3 and 3→2), and a double restore is idempotent;
* **quiet epochs are O(1)** — a cut with nothing dirty writes no
  segment and no device traffic, only re-listed manifest metadata;
* **dispatch supervision** — the transient/oom/permanent classifier and
  the pure ``device_dispatch_decide`` transition (identity-pinned, no
  second copy to drift): transient errors retry with bounded backoff,
  OOM refuses growth and browns the serving plane out via the listener
  hook, watchdog trips and permanent faults abort;
* **satellite** — index filter-predicate failures are counted and
  surfaced instead of swallowed.
"""

import numpy as np
import pytest

import jax

from pathway_tpu.internals import device as devsup
from pathway_tpu.internals import faults
from pathway_tpu.internals.device import PLANE
from pathway_tpu.internals.monitoring import ProberStats
from pathway_tpu.ops.knn import KnnShard
from pathway_tpu.parallel import ShardedKnnIndex, make_mesh
from pathway_tpu.parallel import protocol as proto
from pathway_tpu.parallel.procgroup import shard_hash
from pathway_tpu.parallel.protocol import shard_owner
from pathway_tpu.persistence import Backend, Config, PersistenceManager
from pathway_tpu.persistence import index_snapshot as isnap
from pathway_tpu.persistence.reshard import keep_fn


@pytest.fixture(autouse=True)
def _clean_plane():
    faults.clear_plan()
    PLANE.disarm()
    yield
    faults.clear_plan()
    PLANE.disarm()


@pytest.fixture
def pm(tmp_path):
    return PersistenceManager(
        Config(backend=Backend.filesystem(str(tmp_path / "pstore")))
    )


@pytest.fixture
def mesh8():
    return make_mesh(8, axes=("dp",), shape=(8,))


needs_mesh = pytest.mark.skipif(
    len(jax.devices()) < 8, reason="needs the virtual 8-device CPU mesh"
)


def _assert_bit_identical(got, want):
    assert len(got) == len(want)
    for g, w in zip(got, want):
        # exact tuple equality: ids AND float scores, no tolerance
        assert g == w


def _snap(idx, pm, tag, rank=0, world=1):
    with isnap.cut(pm, tag, rank=rank, world=world):
        return idx.snapshot_state()


def _restore(idx, pm, state, rank=0, world=1):
    with isnap.cut(pm, 0, rank=rank, world=world):
        return idx.load_state(state)


# ---------------------------------------------------------------------------
# anti-drift: the new transitions are the table objects the engine calls
# ---------------------------------------------------------------------------


def test_device_transitions_identity_pinned():
    for name in (
        "index_cut_decide", "index_restore_verdict", "device_dispatch_decide"
    ):
        assert proto.TRANSITIONS[name] is getattr(proto, name), name


def test_transition_semantics_total():
    assert proto.index_cut_decide(0, 3, 8) == "skip"
    assert proto.index_cut_decide(1, 8, 8) == "fold"
    assert proto.index_cut_decide(1, 2, 8) == "delta"
    assert proto.index_cut_decide(1, 100, 0) == "delta"  # folding disabled
    assert proto.index_restore_verdict(False, 0) == "rebuild"
    assert proto.index_restore_verdict(True, 2) == "refuse"
    assert proto.index_restore_verdict(True, 0) == "restore"
    assert proto.device_dispatch_decide("oom", 0, 2) == ("brownout",)
    assert proto.device_dispatch_decide("oom", 99, 2) == ("brownout",)
    assert proto.device_dispatch_decide("transient", 0, 2) == ("retry", 1)
    assert proto.device_dispatch_decide("transient", 2, 2) == ("abort",)
    assert proto.device_dispatch_decide("permanent", 0, 2) == ("abort",)


def test_classifier_feeds_the_transition():
    assert devsup.classify_device_error(MemoryError("oom")) == "oom"
    assert devsup.classify_device_error(
        RuntimeError("RESOURCE_EXHAUSTED: out of memory allocating 1GB")
    ) == "oom"
    assert devsup.classify_device_error(
        RuntimeError("UNAVAILABLE: connection reset")
    ) == "transient"
    # donation evidence wins over everything: a retry on consumed
    # buffers can only corrupt
    assert devsup.classify_device_error(
        RuntimeError("UNAVAILABLE: buffer was donated and deleted")
    ) == "permanent"
    assert devsup.classify_device_error(ValueError("shape")) == "permanent"
    assert devsup.classify_device_error(
        devsup.WatchdogTimeout("hung")
    ) == "permanent"
    # libtpu 0.0.34's own messages, as a v5e printed them (PR 21): a
    # kernel that outgrows VMEM at compile time and a chip held by
    # another process are permanent; only HBM exhaustion browns out
    for text, kind in (
        ("RESOURCE_EXHAUSTED: Allocation (size=201326592) would exceed "
         "memory (size=134217728) :: #allocation7 [shape = "
         "'u8[201326592]{0}', space=vmem, size = 0xc000000, tag = 'input "
         "window allocation for operator input 1.']", "permanent"),
        ("Unable to initialize backend 'tpu': ABORTED: The TPU is already "
         "in use by process with pid 608. Not attempting to load "
         "libtpu.so in this process.", "permanent"),
        ("Unable to initialize backend 'tpu': ABORTED: Internal error "
         "when accessing libtpu multi-process lockfile.", "permanent"),
        ("RESOURCE_EXHAUSTED: Error allocating device buffer: Attempting "
         "to allocate 24.00G. That was not possible. There are 15.56G "
         "free.; (0x0x0_HBM0)", "oom"),
    ):
        assert devsup.classify_device_error(RuntimeError(text)) == kind, text
    inj = faults.InjectedFault("device.dispatch", 1, retryable=True)
    assert devsup.classify_device_error(inj) == "transient"
    inj = faults.InjectedFault("device.dispatch", 1, retryable=False)
    assert devsup.classify_device_error(inj) == "permanent"
    inj = faults.InjectedFault("device.oom", 1)
    assert devsup.classify_device_error(inj) == "oom"


# ---------------------------------------------------------------------------
# supervised dispatch: retry / abort / brownout / watchdog
# ---------------------------------------------------------------------------


def test_supervised_dispatch_retries_transient_then_succeeds(monkeypatch):
    monkeypatch.setenv("PATHWAY_DEVICE_RETRIES", "3")
    faults.install_plan({"rules": [
        {"point": "device.dispatch", "hits": [1, 2], "action": "raise"},
    ]})
    stats = ProberStats()
    PLANE.arm(None, stats)
    calls = []
    out = devsup.supervised_dispatch("t.site", lambda: calls.append(1) or 42)
    assert out == 42
    # two injected failures, then success — thunk ran exactly once
    # (the injected raise fires BEFORE the launch: retry-safe)
    assert len(calls) == 1
    assert stats.device_dispatch_retries == {"t.site": 2}
    assert stats.device_dispatch_failures == {}


def test_supervised_dispatch_exhausted_budget_aborts(monkeypatch):
    monkeypatch.setenv("PATHWAY_DEVICE_RETRIES", "1")
    faults.install_plan({"rules": [
        {"point": "device.dispatch", "action": "raise"},  # every hit
    ]})
    stats = ProberStats()
    PLANE.arm(None, stats)
    with pytest.raises(faults.InjectedFault):
        devsup.supervised_dispatch("t.site", lambda: 1)
    assert stats.device_dispatch_retries == {"t.site": 1}
    assert stats.device_dispatch_failures == {"t.site": 1}


def test_supervised_dispatch_permanent_aborts_without_retry():
    faults.install_plan({"rules": [
        {"point": "device.dispatch", "action": "raise", "retryable": False},
    ]})
    stats = ProberStats()
    PLANE.arm(None, stats)
    with pytest.raises(faults.InjectedFault):
        devsup.supervised_dispatch("t.site", lambda: 1)
    assert stats.device_dispatch_retries == {}
    assert stats.device_dispatch_failures == {"t.site": 1}


def test_supervised_dispatch_oom_browns_out_and_notifies():
    seen = []
    devsup.on_oom(seen.append)
    stats = ProberStats()
    PLANE.arm(None, stats)
    try:
        def boom():
            raise MemoryError("hbm full")

        with pytest.raises(devsup.DeviceOom):
            devsup.supervised_dispatch("t.oom", boom)
    finally:
        devsup.remove_oom_listener(seen.append)
    assert seen == ["t.oom"]
    assert stats.device_oom_events == {"t.oom": 1}
    assert stats.device_dispatch_failures == {"t.oom": 1}


def test_watchdog_trips_hung_dispatch(monkeypatch):
    import time

    monkeypatch.setenv("PATHWAY_DEVICE_DISPATCH_TIMEOUT_S", "0.15")
    stats = ProberStats()
    PLANE.arm(None, stats)
    t0 = time.monotonic()
    with pytest.raises(devsup.WatchdogTimeout):
        devsup.supervised_dispatch("t.hang", lambda: time.sleep(30))
    # the trip lands promptly — far under the 300s mesh op backstop
    assert time.monotonic() - t0 < 5.0
    assert stats.device_watchdog_trips == {"t.hang": 1}
    # WatchdogTimeout classifies permanent: no retry burned the budget
    assert stats.device_dispatch_retries == {}


def test_oom_listener_errors_are_swallowed():
    def bad(site):
        raise RuntimeError("listener bug")

    seen = []
    devsup.on_oom(bad)
    devsup.on_oom(seen.append)
    try:
        devsup.notify_oom("x")
    finally:
        devsup.remove_oom_listener(bad)
        devsup.remove_oom_listener(seen.append)
    assert seen == ["x"]


def test_injected_grow_oom_refuses_growth_and_keeps_serving():
    """device.oom at the growth site: the add raises DeviceOom, the
    index keeps serving its committed rows, and once pressure clears
    the SAME add succeeds (growth was refused, not corrupted)."""
    rng = np.random.default_rng(11)
    idx = KnnShard(8, "cos")  # min capacity: 128 slots
    first = rng.normal(size=(128, 8)).astype(np.float32)
    idx.add(list(range(128)), first)
    faults.install_plan({"rules": [{"point": "device.oom", "action": "raise"}]})
    more = rng.normal(size=(8, 8)).astype(np.float32)
    with pytest.raises(devsup.DeviceOom):
        idx.add(list(range(200, 208)), more)
    # committed rows still answer
    assert len(idx) == 128
    hits = idx.search(first[:1], 1)
    assert hits[0][0][0] == 0
    faults.clear_plan()
    idx.add(list(range(200, 208)), more)
    assert len(idx) == 136


# ---------------------------------------------------------------------------
# restore-vs-rebuild parity battery (satellite)
# ---------------------------------------------------------------------------


def _interleave(idx, ref, rng, dim):
    """The sharded parity battery's insert/delete/query interleavings,
    applied to BOTH indexes; yields after each mutation batch so the
    caller can snapshot/restore at every intermediate state."""
    def both(op, *args):
        getattr(idx, op)(*args)
        getattr(ref, op)(*args)

    a = rng.normal(size=(60, dim)).astype(np.float32)
    both("add", [f"a{i}" for i in range(60)], a)
    yield
    both("remove", [f"a{i}" for i in range(0, 60, 3)])
    yield
    # re-add some removed keys with NEW vectors (fresh insertion seq)
    b = rng.normal(size=(10, dim)).astype(np.float32)
    both("add", [f"a{i * 3}" for i in range(10)], b)
    yield
    # upsert live keys in place
    c = rng.normal(size=(5, dim)).astype(np.float32)
    both("add", [f"a{i}" for i in range(1, 6)], c)
    yield


def test_single_chip_restore_parity_over_interleavings(pm):
    rng = np.random.default_rng(21)
    dim = 8
    idx = KnnShard(dim, "cos")
    ref = KnnShard(dim, "cos")
    q = rng.normal(size=(4, dim)).astype(np.float32)
    for tag, _ in enumerate(_interleave(idx, ref, rng, dim), start=1):
        state = _snap(idx, pm, tag)
        assert state.get("__index_segments__")
        fresh = KnnShard(dim, "cos")
        _restore(fresh, pm, state)
        _assert_bit_identical(fresh.search(q, 7), ref.search(q, 7))
        _assert_bit_identical(idx.search(q, 7), ref.search(q, 7))
    # post-restore inserts mint the SAME sequences the uninterrupted
    # run would: parity must survive continued mutation on the restored
    # index (the bit-identical-resumed-queries acceptance bar)
    fresh = KnnShard(dim, "cos")
    _restore(fresh, pm, _snap(idx, pm, 99))
    d = rng.normal(size=(6, dim)).astype(np.float32)
    for target in (fresh, idx, ref):
        target.add([f"z{i}" for i in range(6)], d)
        target.remove(["a2", "z1"])
    _assert_bit_identical(fresh.search(q, 9), ref.search(q, 9))
    _assert_bit_identical(idx.search(q, 9), ref.search(q, 9))


@needs_mesh
@pytest.mark.parametrize("merge", ["tree", "gather"])
def test_sharded_restore_parity_both_merges(pm, mesh8, merge, monkeypatch):
    monkeypatch.setenv("PATHWAY_INDEX_MERGE", merge)
    rng = np.random.default_rng(22)
    dim = 8
    idx = ShardedKnnIndex(dim, mesh8)
    ref = KnnShard(dim, "cos")
    q = rng.normal(size=(4, dim)).astype(np.float32)
    for tag, _ in enumerate(_interleave(idx, ref, rng, dim), start=1):
        state = _snap(idx, pm, tag)
        fresh = ShardedKnnIndex(dim, mesh8)
        _restore(fresh, pm, state)
        _assert_bit_identical(fresh.search(q, 7), ref.search(q, 7))
        # cross-type restore: the manifest is layout-free, so the same
        # committed state rebuilds a single-chip shard bit-identically
        single = KnnShard(dim, "cos")
        _restore(single, pm, state)
        _assert_bit_identical(single.search(q, 7), ref.search(q, 7))


def test_double_restore_is_idempotent(pm):
    rng = np.random.default_rng(23)
    idx = KnnShard(8, "cos")
    db = rng.normal(size=(40, 8)).astype(np.float32)
    idx.add(list(range(40)), db)
    idx.remove(list(range(0, 40, 5)))
    state = _snap(idx, pm, 1)
    q = rng.normal(size=(3, 8)).astype(np.float32)
    want = idx.search(q, 6)
    fresh = KnnShard(8, "cos")
    _restore(fresh, pm, state)
    _assert_bit_identical(fresh.search(q, 6), want)
    _restore(fresh, pm, state)  # restore is a rebuild, not an append
    assert len(fresh) == len(idx)
    _assert_bit_identical(fresh.search(q, 6), want)


def test_quiet_epoch_writes_no_segment_o1_metadata(pm):
    rng = np.random.default_rng(24)
    idx = KnnShard(8, "cos")
    idx.add(list(range(30)), rng.normal(size=(30, 8)).astype(np.float32))
    s1 = _snap(idx, pm, 1)
    stored_after_1 = pm.list_keys("index_segment/")
    # nothing touched since the cut: the next manifest re-lists the
    # SAME chain and the store gains no object
    s2 = _snap(idx, pm, 2)
    assert s2["segments"] == s1["segments"]
    assert pm.list_keys("index_segment/") == stored_after_1
    # one upsert -> exactly one new delta segment with exactly one row
    idx.add([3], rng.normal(size=(1, 8)).astype(np.float32))
    s3 = _snap(idx, pm, 3)
    assert len(s3["segments"]) == len(s1["segments"]) + 1
    assert s3["segments"][-1]["rows"] == 1


def test_chain_folds_at_cap_and_retires_with_two_cut_retention(
    pm, monkeypatch
):
    monkeypatch.setenv("PATHWAY_INDEX_SNAPSHOT_SEGMENTS", "3")
    rng = np.random.default_rng(25)
    idx = KnnShard(8, "cos")
    ref = KnnShard(8, "cos")
    q = rng.normal(size=(2, 8)).astype(np.float32)
    for tag in range(1, 8):
        row = rng.normal(size=(1, 8)).astype(np.float32)
        idx.add([f"k{tag}"], row)
        ref.add([f"k{tag}"], row)
        state = _snap(idx, pm, tag)
        assert len(state["segments"]) <= 3
    fresh = KnnShard(8, "cos")
    _restore(fresh, pm, state)
    _assert_bit_identical(fresh.search(q, 5), ref.search(q, 5))


def test_broken_chain_refuses_instead_of_serving_holes(pm):
    rng = np.random.default_rng(26)
    idx = KnnShard(8, "cos")
    idx.add(list(range(10)), rng.normal(size=(10, 8)).astype(np.float32))
    state = _snap(idx, pm, 1)
    pm.delete_key(state["segments"][0]["key"])
    fresh = KnnShard(8, "cos")
    with pytest.raises(RuntimeError, match="refusing"):
        _restore(fresh, pm, state)


def test_inline_fallback_without_cut_or_knob(pm, monkeypatch):
    rng = np.random.default_rng(27)
    idx = KnnShard(8, "cos")
    idx.add(list(range(12)), rng.normal(size=(12, 8)).astype(np.float32))
    q = rng.normal(size=(2, 8)).astype(np.float32)
    want = idx.search(q, 4)
    # no cut armed: inline full state, restorable with no persistence
    inline = idx.snapshot_state()
    assert inline.get("__index_inline__")
    fresh = KnnShard(8, "cos")
    fresh.load_state(inline)
    _assert_bit_identical(fresh.search(q, 4), want)
    # knob off: even an armed cut falls back to inline
    monkeypatch.setenv("PATHWAY_DEVICE_SNAPSHOT", "0")
    state = _snap(idx, pm, 1)
    assert state.get("__index_inline__")
    assert pm.list_keys("index_segment/") == []


# ---------------------------------------------------------------------------
# N→M re-shard: 2→3 and 3→2, bit-identical merged answers
# ---------------------------------------------------------------------------


def _reshard_envelope(parts, rank, world):
    return {
        "__index_reshard__": True,
        "parts": parts,
        "keep": keep_fn(rank, world),
    }


def _merged_answer(shards, ref, q, k):
    """Merge per-shard answers the way the exchange plane would: by
    (-score, insertion seq). The seqs come from the reference index —
    restore pins them equal on every shard."""
    hits = []
    for s in shards:
        for key, score in s.search(q[None, :], len(s) or 1)[0]:
            hits.append((key, score))
    hits.sort(key=lambda t: (-t[1], ref.key_seq[t[0]]))
    return hits[:k]


@pytest.mark.parametrize("worlds", [(2, 3), (3, 2)])
def test_reshard_rebuckets_without_loss_or_duplication(pm, worlds):
    old_world, new_world = worlds
    rng = np.random.default_rng(31)
    dim = 8
    n = 90
    keys = [f"doc{i}" for i in range(n)]
    db = rng.normal(size=(n, dim)).astype(np.float32)
    ref = KnnShard(dim, "cos")
    ref.add(keys, db)
    ref.remove(keys[::9])
    live = [k for k in keys if k in ref.key_to_slot]

    # old world: born from a committed cut (a 1→N reshard), the way
    # rank-local shards exist in practice — insertion seqs come from
    # the snapshot, so the tie-break survives every rescale hop
    seed_state = _snap(ref, pm, 1)
    old = [KnnShard(dim, "cos") for _ in range(old_world)]
    for r, shard in enumerate(old):
        _restore(shard, pm, _reshard_envelope([seed_state], r, old_world),
                 rank=r, world=old_world)
        assert all(shard_owner(shard_hash(k), old_world) == r
                   for k in shard.key_to_slot)
    states = [_snap(s, pm, 2, rank=r, world=old_world)
              for r, s in enumerate(old)]

    # new world: every rank folds ALL old chains through its keep set
    new = [KnnShard(dim, "cos") for _ in range(new_world)]
    for r, shard in enumerate(new):
        _restore(shard, pm, _reshard_envelope(states, r, new_world),
                 rank=r, world=new_world)
    # zero lost, zero duplicated: the new ranks partition the live set
    got = {}
    for r, shard in enumerate(new):
        for k in shard.key_to_slot:
            assert k not in got, f"{k} restored on ranks {got[k]} and {r}"
            got[k] = r
            assert shard_owner(shard_hash(k), new_world) == r
    assert set(got) == set(live)
    # merged answers bit-identical to the single full index — and the
    # restored seqs ARE the reference's (the tie-break survives reshard)
    for shard in new:
        for k in shard.key_to_slot:
            assert shard.key_seq[k] == ref.key_seq[k]
    for qi in range(4):
        q = rng.normal(size=(dim,)).astype(np.float32)
        want = ref.search(q[None, :], 10)[0]
        assert _merged_answer(new, ref, q, 10) == want
    # a resharded restore is rebased: the next cut writes a fresh base
    # this rank's chain can extend
    s2 = _snap(new[0], pm, 2, rank=0, world=new_world)
    assert len(s2["segments"]) == 1
    assert s2["segments"][0]["rows"] == len(new[0])


# ---------------------------------------------------------------------------
# satellite: filter-error surfacing
# ---------------------------------------------------------------------------


def test_filter_errors_counted_and_first_surfaced():
    from pathway_tpu.stdlib.indexing.nearest_neighbors import _KnnAdapter

    ad = _KnnAdapter(4, "cos")
    ad.add("good", np.ones(4, np.float32), {"lang": "en"})
    ad.add("bad", np.ones(4, np.float32), {"lang": "fr"})

    def pred(meta):
        if meta["lang"] == "fr":
            raise KeyError("boom")
        return True

    results = ad.search([(np.ones(4, np.float32), 5, pred)])
    # the failing row is dropped from results, not silently matched
    assert results[0][0] == ("good",)
    count, first = ad.filter_errors.drain()
    assert count == 1
    assert first is not None and "KeyError" in first[0]
    assert ad.filter_errors.count == 0  # drain resets
    stats = ProberStats()
    stats.on_index_filter_error(count)
    assert "index_filter_errors_total 1" in stats.render_openmetrics()
