"""Tests for the TPU KNN ops (run on CPU backend).

Mirrors the reference's brute-force index behavior coverage
(/root/reference/src/external_integration/brute_force_knn_integration.rs tests
+ python/pathway/tests/test_knn.py patterns): add/remove/upsert, metrics,
top-k exactness vs numpy oracle, capacity growth.
"""

import numpy as np
import pytest

from pathway_tpu.ops import KnnShard, Metric, merge_topk


def _oracle_topk(queries, db, k, metric):
    if metric == "cos":
        qn = queries / np.linalg.norm(queries, axis=-1, keepdims=True)
        dn = db / np.linalg.norm(db, axis=-1, keepdims=True)
        scores = qn @ dn.T
    elif metric == "dot":
        scores = queries @ db.T
    else:  # l2sq (negated)
        scores = -(
            (queries**2).sum(-1)[:, None]
            - 2 * queries @ db.T
            + (db**2).sum(-1)[None, :]
        )
    order = np.argsort(-scores, axis=-1, kind="stable")[:, :k]
    return order, np.take_along_axis(scores, order, axis=-1)


@pytest.mark.parametrize("metric", ["cos", "dot", "l2sq"])
def test_knn_shard_matches_oracle(metric):
    rng = np.random.default_rng(0)
    db = rng.normal(size=(200, 16)).astype(np.float32)
    queries = rng.normal(size=(7, 16)).astype(np.float32)
    shard = KnnShard(16, metric)
    shard.add(list(range(200)), db)
    got = shard.search(queries, k=5)
    want_idx, want_scores = _oracle_topk(queries, db, 5, metric)
    for qi in range(7):
        got_keys = [key for key, _ in got[qi]]
        assert got_keys == list(want_idx[qi])
        np.testing.assert_allclose(
            [s for _, s in got[qi]], want_scores[qi], rtol=1e-4, atol=1e-4
        )


def test_knn_remove_and_upsert():
    rng = np.random.default_rng(1)
    db = rng.normal(size=(10, 8)).astype(np.float32)
    shard = KnnShard(8, Metric.DOT)
    shard.add(list(range(10)), db)
    shard.remove([3, 4])
    assert len(shard) == 8
    res = shard.search(db[3][None, :], k=10)
    assert 3 not in [key for key, _ in res[0]]
    # upsert key 5 with vector of key 3 — must return new vector's score
    shard.add([5], db[3][None, :])
    res = shard.search(db[3][None, :], k=1)
    assert res[0][0][0] == 5


def test_knn_growth_over_capacity():
    rng = np.random.default_rng(2)
    db = rng.normal(size=(1000, 4)).astype(np.float32)
    shard = KnnShard(4, "cos")
    for start in range(0, 1000, 100):
        shard.add(list(range(start, start + 100)), db[start : start + 100])
    assert shard.capacity >= 1000 and (shard.capacity & (shard.capacity - 1)) == 0
    res = shard.search(db[777][None, :], k=1)
    assert res[0][0][0] == 777


def test_knn_fewer_rows_than_k():
    shard = KnnShard(4, "dot")
    shard.add([1, 2], np.eye(4, dtype=np.float32)[:2])
    res = shard.search(np.eye(4, dtype=np.float32)[:1], k=10)
    assert [key for key, _ in res[0]][0] == 1
    assert len(res[0]) == 2


def test_merge_topk():
    import jax.numpy as jnp

    va = jnp.array([[9.0, 5.0]])
    ia = jnp.array([[0, 1]])
    vb = jnp.array([[7.0, 6.0]])
    ib = jnp.array([[10, 11]])
    v, i = merge_topk(va, ia, vb, ib, 3)
    assert list(np.asarray(v)[0]) == [9.0, 7.0, 6.0]
    assert list(np.asarray(i)[0]) == [0, 10, 11]


def test_update_while_serving_consistency():
    """Concurrent add/remove churn against searches in flight: no torn
    snapshots, no donated-buffer crashes (shard.lock serializes write vs
    read+launch), and every answer maps to a key that existed."""
    import threading

    dim = 32
    shard = KnnShard(dim, "cos", capacity=4096)
    rng = np.random.default_rng(3)
    shard.add(list(range(256)), rng.normal(size=(256, dim)).astype(np.float32))
    shard.search(rng.normal(size=(1, dim)).astype(np.float32), k=4)

    stop = threading.Event()
    errors = []

    def updater():
        nk = 1000
        try:
            while not stop.is_set():
                vecs = rng.normal(size=(32, dim)).astype(np.float32)
                keys = list(range(nk, nk + 32))
                shard.add(keys, vecs)
                nk += 32
                shard.remove(keys[:16])
        except Exception as exc:  # pragma: no cover
            errors.append(exc)

    def querier(seed):
        qrng = np.random.default_rng(seed)
        try:
            for _ in range(30):
                query = qrng.normal(size=(1, dim)).astype(np.float32)
                hits = shard.search(query, k=4)[0]
                assert hits
                for key, score in hits:
                    assert isinstance(key, int)
                    assert np.isfinite(score)
        except Exception as exc:  # pragma: no cover
            errors.append(exc)

    ut = threading.Thread(target=updater)
    qs = [threading.Thread(target=querier, args=(s,)) for s in range(3)]
    ut.start()
    for q in qs:
        q.start()
    for q in qs:
        q.join(timeout=120)
    stop.set()
    ut.join(timeout=30)
    assert not errors, errors


def test_slot_reuse_between_dispatch_and_resolve_drops_hit():
    """A slot freed (and reused by a new key) after a search's dispatch
    must not map the in-flight score to the NEW key: the remove-epoch
    guard of ``_resolve_hits`` drops it (removed-row semantics)."""
    vecs = np.eye(8, dtype=np.float32)
    shard = KnnShard(8, "cos", capacity=64)
    shard.add(["old", "other"], vecs[:2])
    resolve = shard._resolve_hits
    reused = []

    def resolve_after_reuse(vals, idx, k, epoch):
        # the scan has run; its hits are not yet mapped back to keys
        old_slot = shard.key_to_slot["old"]
        shard.remove(["old"])
        shard.add(["new"], vecs[1:2])  # the free list reuses the slot
        reused.append(shard.key_to_slot["new"] == old_slot)
        return resolve(vals, idx, k, epoch)

    shard._resolve_hits = resolve_after_reuse
    hits = shard.search(vecs[:1], k=1)[0]
    shard._resolve_hits = resolve
    assert reused == [True]  # reuse actually happened
    assert all(key != "new" for key, _ in hits), hits

    # a fresh search resolves against the updated mapping
    hits2 = shard.search(vecs[1:2], k=1)[0]
    assert hits2 and hits2[0][0] in ("new", "other")
