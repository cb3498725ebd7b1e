"""HBM-resident brute-force KNN shard.

TPU-native re-design of the reference's BruteForceKNNIndex
(/root/reference/src/external_integration/brute_force_knn_integration.rs:22-237):
the reference keeps a row-major Array2<f64> on the host, grows/shrinks it
geometrically and scores queries with ndarray dot on CPU. Here the vector
store lives in device HBM as a padded f32[capacity, d] buffer with a
validity mask; capacity doubles on growth (powers of two only, so XLA sees
a small, stable set of shapes — no recompilation storms); deletes are O(1)
slot-free-list operations; scoring is a fused matmul + top-k on the MXU
(pathway_tpu.ops.topk) with queries padded to power-of-two batch sizes.
"""

from __future__ import annotations

import enum
import functools
import threading
from typing import Any, Sequence

import numpy as np

import jax
import jax.numpy as jnp

from pathway_tpu.internals import device as _devsup
from pathway_tpu.internals import flight as _flight
from pathway_tpu.internals.device import (
    PLANE as _DEVICE,
    device_site,
    knn_search_bucket,
    knn_write_bucket,
    nbytes_of,
    pow2_capacity,
)
from pathway_tpu.ops.topk import chunked_topk_scores, topk_scan_cost

_MIN_CAPACITY = 128


class Metric(enum.Enum):
    COS = "cos"
    L2SQ = "l2sq"
    DOT = "dot"


# shared-bucket alias (ISSUE 20): the capacity schedule jit sees and the
# shape set the Device Doctor enumerates are the SAME function — pinned
# by tests so they cannot drift
_next_pow2 = pow2_capacity


def write_cost_model(nrows: int, d: int) -> tuple[float, float]:
    """Analytical ``(flops, bytes_accessed)`` of one slot-write scatter:
    the optional normalize + sq-norm reduction over the written rows,
    touching the rows + norms in HBM. Shared by the ``knn.write`` /
    ``knn.sharded_write`` dispatch records and the Device Doctor's
    per-dispatch copy-cost blame (ISSUE 20)."""
    return 4.0 * nrows * d, 8.0 * nrows * d + 8.0 * nrows


device_site(
    "knn.write",
    cost_model=write_cost_model,
    dtypes=("float32", "bool", "int32"),
    where="pathway_tpu/ops/knn.py:KnnShard.add",
    donates=("vectors", "valid", "sq_norms"),
    description="donated in-place slot-write into the HBM buffer triple",
)

device_site(
    "knn.search",
    cost_model=topk_scan_cost,
    dtypes=("float32", "bool", "int32"),
    where="pathway_tpu/ops/knn.py:KnnShard.search",
    description="fused matmul + top-k scan over the padded vector store",
)


@functools.lru_cache(maxsize=None)
def _search_fn(k: int, metric: str, chunk: int, precision: str):
    @jax.jit
    def search(queries, vectors, valid, sq_norms):
        queries = queries.astype(jnp.float32)
        if metric == "cos":
            n = jnp.linalg.norm(queries, axis=-1, keepdims=True)
            queries = queries / jnp.maximum(n, 1e-30)
        sq = sq_norms if metric == "l2sq" else None
        return chunked_topk_scores(
            queries, vectors, valid, k,
            chunk=chunk, sq_norms=sq,
            metric="l2sq" if metric == "l2sq" else "dot",
            precision=precision,
        )

    return search


@functools.partial(
    jax.jit, static_argnames=("normalize",), donate_argnums=(0, 1, 2)
)
def _write_slots(vectors, valid, sq_norms, slots, new_vecs, new_valid, *,
                 normalize: bool = False):
    new_vecs = new_vecs.astype(jnp.float32)
    if normalize:
        n = jnp.linalg.norm(new_vecs, axis=-1, keepdims=True)
        new_vecs = new_vecs / jnp.maximum(n, 1e-30)
    vectors = vectors.at[slots].set(new_vecs)
    valid = valid.at[slots].set(new_valid)
    sq_norms = sq_norms.at[slots].set(jnp.sum(new_vecs * new_vecs, axis=-1))
    return vectors, valid, sq_norms


class KnnShard:
    """One device shard of a brute-force index: add/remove/search.

    Host side owns the key↔slot mapping (the reference's KeyToU64IdMapper,
    external_integration/mod.rs); the device side only sees dense slots.
    """

    def __init__(
        self,
        dimension: int,
        metric: Metric | str = Metric.COS,
        *,
        chunk: int | None = None,  # None = auto-scale to the scores budget
        precision: str = "highest",
        capacity: int = _MIN_CAPACITY,
        device: Any | None = None,
    ):
        self.dimension = int(dimension)
        self.metric = Metric(metric)
        self.chunk = chunk
        self.precision = precision
        self.device = device
        # pre-size to the expected corpus size to avoid growth reshapes
        # (each distinct capacity is a fresh XLA executable)
        self.capacity = _next_pow2(capacity)
        self.key_to_slot: dict[Any, int] = {}
        self.slot_to_key: dict[int, Any] = {}
        # insertion-sequence mint for the deterministic tie-break: equal
        # scores order by when the key was (last) inserted, so results
        # never depend on slot layout — the contract that makes sharded
        # and single-chip indexes bit-identical (tests/test_sharded_parity)
        self.key_seq: dict[Any, int] = {}
        self._next_seq = 0
        self.free_slots: list[int] = list(range(self.capacity - 1, -1, -1))
        self.vectors = jnp.zeros((self.capacity, self.dimension), jnp.float32)
        self.valid = jnp.zeros((self.capacity,), bool)
        self.sq_norms = jnp.zeros((self.capacity,), jnp.float32)
        # serializes writers against query launches (update-while-serving):
        # _write_slots DONATES the current buffers, so a reader must read
        # the array triple and enqueue its executable before the next
        # update invalidates those handles. Writers hold this lock; query
        # paths hold it across read+launch (the launch is asynchronous, so
        # the critical section is microseconds).
        self.lock = threading.Lock()
        # slot-reuse guard for in-flight queries: a hit resolved AFTER its
        # dispatch must not map a slot freed (and possibly reused) in
        # between to the new key. remove() stamps freed slots with a
        # monotonically increasing epoch; readers capture the epoch at
        # dispatch and drop hits whose slot was freed later.
        self.remove_epoch = 0
        self.slot_freed_epoch = np.full(self.capacity, -1, np.int64)
        # device fault domain (ISSUE 17): per-epoch dirty tracking for
        # delta snapshots plus the committed segment chain this index
        # extends. _dirty/_dirty_removed are insertion-ordered key sets
        # (dicts), mutually exclusive per key — a re-added key leaves
        # the removed set, a removed key leaves the dirty set.
        from pathway_tpu.persistence import index_snapshot as _isnap

        self.snapshot_name = _isnap.next_index_name("knn")
        self._dirty: dict[Any, None] = {}
        self._dirty_removed: dict[Any, None] = {}
        self._segments: list[dict] = []
        self._retired: list[list[str]] = []
        # seen compiled-shape buckets (ISSUE 20): a write/search key not
        # in this set is — by jit's cache discipline — a fresh XLA
        # compilation, ticked on device_site_recompiles_total so the
        # retrace audit's predictions pin against honest counters
        self._seen_buckets: set = set()

    # device sites reachable through this index as an external-index
    # adapter (the Device Doctor's plan-reachability hook, ISSUE 20)
    device_sites = ("knn.write", "knn.search")

    def __len__(self) -> int:
        return len(self.key_to_slot)

    # -- mutation ---------------------------------------------------------
    def _grow_to(self, n: int) -> None:
        new_cap = _next_pow2(n)
        if new_cap <= self.capacity:
            return
        pad = new_cap - self.capacity
        # HBM growth is the OOM site: allocate the doubled buffers into
        # locals and commit only on success, so a refused growth leaves
        # the index serving at its committed capacity (the failing add
        # aborts; the serving breaker browns out via notify_oom)
        try:
            from pathway_tpu.internals.faults import fault_point

            fault_point("device.oom", site="knn.grow")
            vectors = jnp.concatenate(
                [self.vectors, jnp.zeros((pad, self.dimension), jnp.float32)]
            )
            valid = jnp.concatenate([self.valid, jnp.zeros((pad,), bool)])
            sq_norms = jnp.concatenate(
                [self.sq_norms, jnp.zeros((pad,), jnp.float32)]
            )
        except BaseException as exc:
            if _devsup.classify_device_error(exc) == "oom":
                _devsup.notify_oom("knn.grow")
                raise _devsup.DeviceOom(
                    f"knn index refused growth to {new_cap} slots "
                    f"(HBM exhausted): {exc!r}"
                ) from exc
            raise
        self.vectors, self.valid, self.sq_norms = vectors, valid, sq_norms
        self.free_slots = (
            list(range(new_cap - 1, self.capacity - 1, -1)) + self.free_slots
        )
        self.slot_freed_epoch = np.concatenate(
            [self.slot_freed_epoch, np.full(pad, -1, np.int64)]
        )
        self.capacity = new_cap

    def _prepare(self, vecs):
        """Shape/dtype check; keeps device arrays on device. Normalization
        for cos happens on device inside the jitted write/search fns."""
        if not isinstance(vecs, jax.Array):
            vecs = np.asarray(vecs, dtype=np.float32)
        if vecs.ndim == 1:
            vecs = vecs[None, :]
        if vecs.shape[-1] != self.dimension:
            raise ValueError(
                f"vector dimension {vecs.shape[-1]} != index dimension {self.dimension}"
            )
        return vecs

    def _assign_slots(self, keys: Sequence[Any]) -> np.ndarray:
        """Map keys to dense slots (upsert semantics), growing first.
        Must be called under ``self.lock``."""
        self._grow_to(len(self.key_to_slot) + len(keys))
        slots = []
        for key in keys:
            slot = self.key_to_slot.get(key)
            if slot is None:
                slot = self.free_slots.pop()
                self.key_to_slot[key] = slot
                self.slot_to_key[slot] = key
                self.key_seq[key] = self._next_seq
                self._next_seq += 1
            slots.append(slot)
            # every upserted key is dirty for the next snapshot cut
            self._dirty[key] = None
            self._dirty_removed.pop(key, None)
        return np.asarray(slots, dtype=np.int32)

    def add(self, keys: Sequence[Any], vecs) -> None:
        """Upsert vectors; accepts numpy or device-resident jax arrays (the
        latter avoids a host round-trip when chaining from a jitted encoder).
        Safe to call while queries are in flight (update-while-serving)."""
        vecs = self._prepare(vecs)
        if len(keys) != vecs.shape[0]:
            raise ValueError("keys/vectors length mismatch")
        with self.lock:
            with _flight.span("knn.assign_slots", rows=len(keys)):
                slots = self._assign_slots(keys)
            bucket = knn_write_bucket(len(slots), self.capacity)
            if bucket not in self._seen_buckets:
                self._seen_buckets.add(bucket)
                _DEVICE.note_recompile("knn.write")
            h2d = (0 if isinstance(vecs, jax.Array) else nbytes_of(vecs)) \
                + 4 * len(slots)
            dev = _DEVICE.begin("knn.write", rows=len(slots), h2d_bytes=h2d)
            slots_arr = jnp.asarray(slots)

            def _launch():
                return _write_slots(
                    self.vectors, self.valid, self.sq_norms,
                    slots_arr, jnp.asarray(vecs),
                    jnp.ones((len(slots),), bool),
                    normalize=self.metric is Metric.COS,
                )

            try:
                # supervised (ISSUE 17): injected faults raise before the
                # launch so retry is safe; a real failure that consumed
                # the donated buffers classifies permanent and aborts
                self.vectors, self.valid, self.sq_norms = (
                    _devsup.supervised_dispatch("knn.write", _launch)
                )
            except BaseException:
                _DEVICE.end(dev, None, block=False)
                raise
            out_vectors = self.vectors
        # end() OUTSIDE the lock, like the search side — armed, its
        # block_until_ready must not serialize update-while-serving
        # (a racing writer may have re-donated out_vectors by now;
        # blocking on an invalidated array is absorbed by end()).
        # Scatter writes: touch the written rows + norms; FLOPs are
        # the optional normalize + sq-norm reduction.
        flops, acc = write_cost_model(len(slots), self.dimension)
        _DEVICE.end(
            dev, out_vectors,
            flops=flops,
            bytes_accessed=acc,
            transfer_bytes=nbytes_of(vecs) + 4 * len(slots),
        )

    def remove(self, keys: Sequence[Any]) -> None:
        with self.lock:
            slots = []
            for key in keys:
                slot = self.key_to_slot.pop(key, None)
                if slot is None:
                    continue
                del self.slot_to_key[slot]
                self.key_seq.pop(key, None)
                self.free_slots.append(slot)
                slots.append(slot)
                self._dirty_removed[key] = None
                self._dirty.pop(key, None)
            if not slots:
                return
            self.remove_epoch += 1
            self.slot_freed_epoch[np.asarray(slots)] = self.remove_epoch
            slots_arr = jnp.asarray(np.asarray(slots, dtype=np.int32))
            self.vectors, self.valid, self.sq_norms = _write_slots(
                self.vectors, self.valid, self.sq_norms,
                slots_arr,
                jnp.zeros((len(slots), self.dimension), jnp.float32),
                jnp.zeros((len(slots),), bool),
            )

    # -- snapshot / restore (ISSUE 17) ------------------------------------
    def snapshot_state(self, *, extra=None) -> dict:
        """Node state for the current persistence cut: a delta-segment
        manifest when a cut context is armed (persistence/index_snapshot),
        an inline full state otherwise. ``extra`` is an optional
        key->payload mapping that rides the segments (adapter metadata)."""
        from pathway_tpu.persistence import index_snapshot as _isnap

        return _isnap.snapshot_index(self, extra=extra)

    def load_state(self, state: dict) -> dict:
        """Rebuild HBM buffers + host maps from a committed snapshot
        (manifest chain or inline state) instead of re-embedding; returns
        the folded per-key extra payloads."""
        from pathway_tpu.persistence import index_snapshot as _isnap

        return _isnap.restore_index(self, state)

    def _load_entries(self, entries: list) -> None:
        """Replace the whole corpus with ``[(key, seq, vector), ...]``.
        Caller holds ``self.lock``. Vectors are as-committed (already
        normalized for cos), so the rewrite uses ``normalize=False`` —
        scores and the ``key_seq`` tie-break come back bit-identical."""
        n = len(entries)
        self.capacity = _next_pow2(max(n, _MIN_CAPACITY))
        self.key_to_slot = {}
        self.slot_to_key = {}
        self.key_seq = {}
        # the old corpus (and its mint position) is gone; restore_index
        # re-seats _next_seq from the snapshot so post-restore inserts
        # mint the same sequences as the uninterrupted run
        self._next_seq = 0
        self.free_slots = list(range(self.capacity - 1, -1, -1))
        self.remove_epoch = 0
        self.slot_freed_epoch = np.full(self.capacity, -1, np.int64)
        self.vectors = jnp.zeros((self.capacity, self.dimension), jnp.float32)
        self.valid = jnp.zeros((self.capacity,), bool)
        self.sq_norms = jnp.zeros((self.capacity,), jnp.float32)
        if not n:
            return
        slots = np.empty((n,), np.int32)
        rows = np.empty((n, self.dimension), np.float32)
        for i, (key, seq, row) in enumerate(entries):
            slot = self.free_slots.pop()
            self.key_to_slot[key] = slot
            self.slot_to_key[slot] = key
            self.key_seq[key] = int(seq)
            slots[i] = slot
            rows[i] = row
        self.vectors, self.valid, self.sq_norms = _write_slots(
            self.vectors, self.valid, self.sq_norms,
            jnp.asarray(slots), jnp.asarray(rows),
            jnp.ones((n,), bool), normalize=False,
        )

    # -- search -----------------------------------------------------------
    def search(self, queries, k: int) -> list[list[tuple[Any, float]]]:
        """Return per-query [(key, score)] sorted by descending score.

        Scores: cos/dot similarity, or negated squared L2 distance.
        Queries are padded to a power-of-two batch so the jitted kernel
        sees a bounded shape set.
        """
        queries = self._prepare(queries)
        n = queries.shape[0]
        if n == 0 or not self.key_to_slot:
            return [[] for _ in range(n)]
        # shared bucket key (ISSUE 20): pow2 query padding and the k
        # clamp (top_k per scored block cannot exceed the block width)
        # come from the SAME function the retrace audit enumerates with
        bucket = knn_search_bucket(n, self.capacity, k, self.chunk)
        padded_n, _, k_eff = bucket
        first = bucket not in self._seen_buckets
        if first:
            self._seen_buckets.add(bucket)
            _DEVICE.note_recompile("knn.search")
        if padded_n != n:
            pad = [(0, padded_n - n), (0, 0)]
            queries = (
                jnp.pad(queries, pad)
                if isinstance(queries, jax.Array)
                else np.pad(queries, pad)
            )
        fn = _search_fn(k_eff, self.metric.value, self.chunk, self.precision)
        # the dispatch: always its ring span (prepare, pad, lock,
        # enqueue); armed (ISSUE 15), also one timed record per scan —
        # block_until_ready-bounded device time, the scan's cost model
        # and host->device transfer bytes. end() runs OUTSIDE the lock
        # so attribution never serializes writers.
        dev = _DEVICE.begin(
            "knn.search", queries=padded_n, k=k_eff, first=first,
            h2d_bytes=0 if isinstance(queries, jax.Array)
            else nbytes_of(queries),
        )
        try:
            with self.lock:  # read+launch before the next donating update
                vals, idx = _devsup.supervised_dispatch(
                    "knn.search",
                    lambda: fn(
                        jnp.asarray(queries), self.vectors, self.valid,
                        self.sq_norms,
                    ),
                )
                epoch = self.remove_epoch
                live_rows = len(self.key_to_slot)
        except BaseException:
            # close the record on the failure path too (the gateway
            # site's rule): an abandoned record leaks queue depth
            _DEVICE.end(dev, None, block=False)
            raise
        flops, acc = topk_scan_cost(
            padded_n, self.capacity, self.dimension, k_eff
        )
        # effective FLOPs (ISSUE 16): only real queries against live
        # rows count as useful work — query padding and the empty
        # tail of the pow2 capacity buffer are visible padding waste
        flops_eff, _ = topk_scan_cost(n, live_rows, self.dimension, k_eff)
        _DEVICE.end(
            dev, (vals, idx), flops=flops,
            flops_effective=flops_eff, bytes_accessed=acc,
            transfer_bytes=nbytes_of(queries, vals, idx),
        )
        with _flight.span("knn.search.wait"):
            # both copies queue behind the scan before anything waits
            # (np.asarray alone queues each only when it is called); the
            # wait then tells the scan's time from the copies'
            vals.copy_to_host_async()
            idx.copy_to_host_async()
            jax.block_until_ready((vals, idx))
        with _flight.span("knn.search.d2h") as sp:
            vals, idx = np.asarray(vals), np.asarray(idx)
            sp.args["bytes"] = nbytes_of(vals, idx)
            vals, idx = vals[:n], idx[:n]
        with _flight.span("knn.search.resolve") as sp:
            out = self._resolve_hits(vals, idx, k, epoch)
            sp.args["hits"] = sum(len(h) for h in out)
        return out

    def _resolve_hits(self, vals, idx, k: int, epoch: int):
        """Slots back to keys, per query, best first."""
        out: list[list[tuple[Any, float]]] = []
        for qi in range(len(vals)):
            hits = []
            for vv, slot in zip(vals[qi], idx[qi]):
                if not np.isfinite(vv):
                    continue
                slot = int(slot)
                # slot freed after our dispatch (possibly reused by a new
                # key): this hit's key mapping is gone — drop it, matching
                # removed-row semantics
                if self.slot_freed_epoch[slot] > epoch:
                    continue
                key = self.slot_to_key.get(slot)
                if key is None:
                    continue
                hits.append((key, float(vv)))
            # deterministic tie-break over ALL k_eff candidates before
            # truncating: equal scores order by insertion sequence, so
            # the result never depends on slot layout (which a sharded
            # index lays out differently) — see ShardedKnnIndex.search
            hits.sort(key=lambda t: (-t[1], self.key_seq.get(t[0], 0)))
            out.append(hits[:k])
        return out
