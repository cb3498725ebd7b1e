"""Mesh-sharded brute-force KNN index (pod-sharded HBM index, ISSUE 16).

Replaces the reference's broadcast-replicated external index
(/root/reference/src/engine/dataflow/operators/external_index.rs:95-106 —
index diffs broadcast so every worker holds a FULL copy, bounded by host
RAM) with the TPU-native design from SURVEY §5: each chip's HBM holds one
shard of the padded vector store; queries are replicated to all shards
(their natural state under jit), each shard computes a local fused
matmul+top-k, and the partials are merged into the global top-k — either
by all-gather + one merge, or by a psum-style recursive-doubling
**tree merge** over ICI (``ops.topk.tree_merge_topk``,
``PATHWAY_INDEX_MERGE``) whose per-link traffic stays flat as the pod
grows. Index capacity scales with the number of chips instead of being
replicated per worker.

Delta routing (ISSUE 16): insert/delete deltas are routed to their
OWNING shard by the same stable mint the mesh's exchange plane uses —
``procgroup.shard_hash`` (blake2b-64) through ``protocol.shard_owner``
— so every rank computes the same owner without coordination, rows
spread evenly across shards (capacity actually scales ~linearly with
the mesh), and a re-shard is a pure re-bucketing of the same digests.

Write path: one donated, jitted batched slot-write per delta batch
(the same ``_write_slots`` executable the single-chip shard uses), not
one host `.at[].set` per row — writers hold the index lock against
query launches exactly like ``ops.knn.KnnShard`` (donation invalidates
the buffers a racing reader might still be holding).
"""

from __future__ import annotations

import functools
import os
import threading
from typing import Any, Sequence

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from pathway_tpu.internals import device as _devsup
from pathway_tpu.internals.device import (
    PLANE as _DEVICE,
    device_site,
    nbytes_of,
    sharded_search_bucket,
    sharded_write_bucket,
)
from pathway_tpu.ops.knn import Metric, _write_slots, write_cost_model
from pathway_tpu.ops.topk import (
    chunked_topk_scores,
    topk_scan_cost,
    tree_merge_topk,
)
from pathway_tpu.parallel.procgroup import shard_hash
from pathway_tpu.parallel.protocol import shard_owner


def _merge_mode(n_shards: int) -> str:
    """Resolve PATHWAY_INDEX_MERGE: 'tree' (recursive doubling over
    ICI) needs a pow2 axis; 'auto' picks tree when the axis allows it,
    'gather' is the all_gather + single-merge fallback."""
    raw = str(os.environ.get("PATHWAY_INDEX_MERGE", "auto")).strip().lower()
    pow2 = n_shards & (n_shards - 1) == 0
    if raw == "tree":
        return "tree" if pow2 else "gather"
    if raw == "gather":
        return "gather"
    return "tree" if pow2 else "gather"


device_site(
    "knn.sharded_write",
    cost_model=write_cost_model,
    dtypes=("float32", "bool", "int32"),
    where="pathway_tpu/parallel/sharded_knn.py:ShardedKnnIndex.add",
    donates=("vectors", "valid", "sq_norms"),
    description="donated slot-write into the mesh-sharded buffer triple "
                "(out_shardings pinned to the shard layout)",
)

device_site(
    "knn.sharded_search",
    cost_model=topk_scan_cost,
    dtypes=("float32", "bool", "int32"),
    where="pathway_tpu/parallel/sharded_knn.py:ShardedKnnIndex.search",
    description="per-shard fused matmul+top-k with tree/gather merge "
                "over the mesh axis",
)


def _empty_triple(capacity: int, dimension: int, db_sharding, row_sharding):
    """The zeroed (vectors, valid, sq_norms) triple, allocated sharded
    from the start: each device materializes only its own rows, so no
    full-capacity buffer is first built on one chip and then scattered
    (which works at smoke size and cannot at a size that fills the
    mesh)."""
    return jax.jit(
        lambda: (
            jnp.zeros((capacity, dimension), jnp.float32),
            jnp.zeros((capacity,), bool),
            jnp.zeros((capacity,), jnp.float32),
        ),
        out_shardings=(db_sharding, row_sharding, row_sharding),
    )()


def make_sharded_write(mesh: Mesh, axis: str):
    """The donated, layout-pinned batched slot-write for one mesh:
    returns ``(jitted_fn, out_shardings)``. Module-level so the Device
    Doctor (analysis/device_plan.py) builds the SAME jit — donation
    argnums, static args AND the out_shardings pin — that
    ``ShardedKnnIndex`` dispatches; the mesh-layout check introspects
    the returned shardings instead of guessing."""
    db = NamedSharding(mesh, P(axis, None))
    row = NamedSharding(mesh, P(axis))
    out_shardings = (db, row, row)
    fn = jax.jit(
        _write_slots.__wrapped__,
        static_argnames=("normalize",),
        donate_argnums=(0, 1, 2),
        out_shardings=out_shardings,
    )
    return fn, out_shardings


def sharded_topk(
    queries: jax.Array,   # [q, d] replicated
    database: jax.Array,  # [cap, d] sharded on axis 0 over `axis`
    valid: jax.Array,     # [cap] bool, sharded the same
    k: int,
    mesh: Mesh,
    *,
    axis: str = "dp",
    sq_norms: jax.Array | None = None,
    metric: str = "dot",
    chunk: int | None = None,
    precision: str = "highest",
    merge: str = "gather",
):
    """Global top-k over a row-sharded database. Returns replicated
    (values [q, k], global indices [q, k])."""
    use_sq = sq_norms is not None
    in_specs = [P(), P(axis, None), P(axis)]
    if use_sq:
        in_specs.append(P(axis))
    n_shards = mesh.shape[axis]

    def local(q, db_l, valid_l, *rest):
        sq_l = rest[0] if use_sq else None
        # per-shard k is bounded by the shard's rows; the merged global
        # top-k can still honor the full k from other shards' partials
        # (up to the index's total capacity)
        chunk_l = min(chunk or db_l.shape[0], db_l.shape[0])
        k_l = min(k, db_l.shape[0], chunk_l)
        vals, idx = chunked_topk_scores(
            q, db_l, valid_l, k_l,
            chunk=chunk_l, sq_norms=sq_l,
            metric=metric, precision=precision,
        )
        shard_i = jax.lax.axis_index(axis)
        idx = idx + shard_i * db_l.shape[0]
        if merge == "tree" and n_shards > 1:
            # psum-style butterfly: log2(n) ppermute+merge rounds, each
            # link carries 2·q·k_l instead of the gather's (n-1)·q·k_l
            k_out = min(k, n_shards * k_l)
            if k_out > k_l:
                # widen the partial to the merged width first so every
                # round merges equal shapes
                pad = k_out - k_l
                vals = jnp.pad(
                    vals, ((0, 0), (0, pad)),
                    constant_values=float("-inf"),
                )
                idx = jnp.pad(idx, ((0, 0), (0, pad)))
            return tree_merge_topk(vals, idx, k_out, axis, n_shards)
        # partial top-k exchange + flat merge (the retrieval analog of
        # ring attention's partial-result merge): [n, q, k_l] -> [q, k]
        all_vals = jax.lax.all_gather(vals, axis)
        all_idx = jax.lax.all_gather(idx, axis)
        n, nq, _ = all_vals.shape
        av = jnp.transpose(all_vals, (1, 0, 2)).reshape(nq, n * k_l)
        ai = jnp.transpose(all_idx, (1, 0, 2)).reshape(nq, n * k_l)
        k_out = min(k, n * k_l)
        best_v, pos = jax.lax.top_k(av, k_out)
        best_i = jnp.take_along_axis(ai, pos, axis=-1)
        return best_v, best_i

    # all_gather/ppermute make the outputs replicated, but the vma
    # checker can't see that through lax.top_k
    smapped = jax.shard_map(
        local, mesh=mesh, in_specs=tuple(in_specs), out_specs=(P(), P()),
        check_vma=False,
    )
    return smapped(queries, database, valid, *((sq_norms,) if use_sq else ()))


@functools.lru_cache(maxsize=None)
def _sharded_search_fn(mesh: Mesh, axis: str, k: int, metric: str,
                       chunk: int | None, precision: str, merge: str):
    def fn(queries, database, valid, sq_norms):
        # query prep is IDENTICAL to ops.knn._search_fn (same jnp ops,
        # same f32) — the sharded-vs-single-chip parity battery pins
        # scores bit-identical, so no host-side normalization variant
        queries = queries.astype(jnp.float32)
        if metric == "cos":
            n = jnp.linalg.norm(queries, axis=-1, keepdims=True)
            queries = queries / jnp.maximum(n, 1e-30)
        return sharded_topk(
            queries, database, valid, k, mesh, axis=axis,
            sq_norms=sq_norms if metric == "l2sq" else None,
            metric="l2sq" if metric == "l2sq" else "dot",
            chunk=chunk, precision=precision, merge=merge,
        )

    return jax.jit(fn)


class ShardedKnnIndex:
    """Host-facing sharded index: same contract as ops.KnnShard, but the
    vector store is laid out across a mesh axis, one HBM shard per chip.

    Slot layout: global slot = owner_shard * local_cap + local_slot; a
    key's owner shard is minted from its stable blake2b digest
    (``shard_owner(shard_hash(key), n_shards)``), so rows spread evenly
    and capacity scales with the mesh. Ties in query results are broken
    by insertion sequence (host-side, after the device merge) — the
    deterministic contract the sharded-vs-single-chip parity battery
    pins bit-identical.
    """

    def __init__(
        self,
        dimension: int,
        mesh: Mesh,
        *,
        metric: Metric | str = Metric.COS,
        axis: str = "dp",
        chunk: int | None = None,  # None = whole shard in one block
        precision: str = "highest",
    ):
        self.dimension = int(dimension)
        self.mesh = mesh
        self.axis = axis
        self.metric = Metric(metric)
        self.chunk = chunk
        self.precision = precision
        self.n_shards = int(mesh.shape[axis])
        # per-shard capacity is a power of two; total = n_shards * local
        # (divides evenly over the mesh axis for any device count)
        self.local_cap = 128
        self.capacity = self.n_shards * self.local_cap
        self.key_to_slot: dict[Any, int] = {}
        self.slot_to_key: dict[int, Any] = {}
        # insertion-sequence mint for the deterministic tie-break (a
        # re-added key gets a fresh sequence — it is a new row)
        self.key_seq: dict[Any, int] = {}
        self._next_seq = 0
        # per-shard free lists of GLOBAL slots (shard s owns
        # [s*local_cap, (s+1)*local_cap)): delta routing fills the
        # OWNING shard, not whichever slot a global list happens to pop
        self.free_by_shard: list[list[int]] = [
            list(range((s + 1) * self.local_cap - 1, s * self.local_cap - 1, -1))
            for s in range(self.n_shards)
        ]
        self._db_sharding = NamedSharding(mesh, P(axis, None))
        self._row_sharding = NamedSharding(mesh, P(axis))
        self._repl = NamedSharding(mesh, P())
        self.vectors, self.valid, self.sq_norms = _empty_triple(
            self.capacity, self.dimension,
            self._db_sharding, self._row_sharding,
        )
        # writers donate the buffer triple — same update-while-serving
        # lock discipline as ops.knn.KnnShard
        self.lock = threading.Lock()
        self.remove_epoch = 0
        self.slot_freed_epoch = np.full(self.capacity, -1, np.int64)
        # device fault domain (ISSUE 17): dirty tracking + segment chain,
        # same semantics as ops.knn.KnnShard
        from pathway_tpu.persistence import index_snapshot as _isnap

        self.snapshot_name = _isnap.next_index_name("sknn")
        self._dirty: dict[Any, None] = {}
        self._dirty_removed: dict[Any, None] = {}
        self._segments: list[dict] = []
        self._retired: list[list[str]] = []
        # seen compiled-shape buckets (ISSUE 20): fresh write/search
        # keys tick device_site_recompiles_total — the retrace audit's
        # predictions pin against these counters
        self._seen_buckets: set = set()
        # batched slot-write with the shard layout pinned on the outputs
        # (the scatter must not silently replicate the store); same body
        # as the single-chip shard's donated writer. The builder is the
        # shared object the Device Doctor lowers (ISSUE 20); the
        # shardings it pinned stay introspectable for the mesh check.
        self._write, self._write_out_shardings = make_sharded_write(
            mesh, axis
        )

    def __len__(self) -> int:
        return len(self.key_to_slot)

    # device sites reachable through this index as an external-index
    # adapter (the Device Doctor's plan-reachability hook, ISSUE 20)
    device_sites = ("knn.sharded_write", "knn.sharded_search")

    # -- routing -----------------------------------------------------------
    def owner_shard(self, key) -> int:
        """The shard that owns ``key`` — the mesh's stable mint
        (blake2b digest mod world), so every rank agrees without
        coordination and a re-shard is a pure re-bucketing."""
        return shard_owner(shard_hash(key), self.n_shards)

    def shard_fill(self) -> list[int]:
        """Live rows per shard (capacity-scaling observability)."""
        fill = [0] * self.n_shards
        for slot in self.slot_to_key:
            fill[slot // self.local_cap] += 1
        return fill

    def _prepare(self, vecs) -> np.ndarray:
        """Shape/dtype check only — cos normalization happens on device
        inside the jitted write/search fns, with the SAME jnp ops as the
        single-chip KnnShard (bit-identical parity contract)."""
        vecs = np.asarray(vecs, dtype=np.float32)
        if vecs.ndim == 1:
            vecs = vecs[None, :]
        if vecs.shape[-1] != self.dimension:
            raise ValueError(
                f"vector dimension {vecs.shape[-1]} != index dimension "
                f"{self.dimension}"
            )
        return vecs

    # -- mutation ----------------------------------------------------------
    def _grow_to_local(self, local_needed: int) -> None:
        """Double local capacity until every shard can hold its rows.
        Global slot = shard * local_cap + local, so growth REMAPS every
        live slot — host round-trip, rare by pow2 doubling."""
        local = self.local_cap
        while local < local_needed:
            local *= 2
        if local <= self.local_cap:
            return
        old_local, old_cap = self.local_cap, self.capacity
        new_cap = self.n_shards * local
        # HBM growth is the OOM site (ISSUE 17): stage into locals,
        # commit only on success — a refused growth leaves every shard
        # serving at committed capacity while the failing add aborts
        try:
            from pathway_tpu.internals.faults import fault_point

            fault_point("device.oom", site="knn.sharded_grow")
            host_vec = np.asarray(self.vectors)
            host_valid = np.asarray(self.valid)
            host_sq = np.asarray(self.sq_norms)
        except BaseException as exc:
            if _devsup.classify_device_error(exc) == "oom":
                _devsup.notify_oom("knn.sharded_grow")
                raise _devsup.DeviceOom(
                    f"sharded knn index refused growth to {new_cap} "
                    f"global slots (HBM exhausted): {exc!r}"
                ) from exc
            raise
        new_vec = np.zeros((new_cap, self.dimension), np.float32)
        new_valid = np.zeros((new_cap,), bool)
        new_sq = np.zeros((new_cap,), np.float32)
        new_epoch = np.full(new_cap, -1, np.int64)
        for s in range(self.n_shards):
            src = slice(s * old_local, (s + 1) * old_local)
            dst = slice(s * local, s * local + old_local)
            new_vec[dst] = host_vec[src]
            new_valid[dst] = host_valid[src]
            new_sq[dst] = host_sq[src]
            new_epoch[dst] = self.slot_freed_epoch[src]
        remap = {}
        for old_slot, key in self.slot_to_key.items():
            s, l = divmod(old_slot, old_local)
            remap[s * local + l] = key
        new_free = []
        for s in range(self.n_shards):
            shifted = [
                s * local + (sl - s * old_local)
                for sl in self.free_by_shard[s]
            ]
            fresh = list(
                range(s * local + local - 1, s * local + old_local - 1, -1)
            )
            new_free.append(fresh + shifted)
        try:
            # host arrays go straight to their owning shards (a
            # jnp.asarray first would land the whole buffer on chip 0)
            dev_vec = jax.device_put(new_vec, self._db_sharding)
            dev_valid = jax.device_put(new_valid, self._row_sharding)
            dev_sq = jax.device_put(new_sq, self._row_sharding)
        except BaseException as exc:
            if _devsup.classify_device_error(exc) == "oom":
                _devsup.notify_oom("knn.sharded_grow")
                raise _devsup.DeviceOom(
                    f"sharded knn index refused growth to {new_cap} "
                    f"global slots (HBM exhausted): {exc!r}"
                ) from exc
            raise
        self.slot_to_key = remap
        self.key_to_slot = {k: sl for sl, k in remap.items()}
        self.free_by_shard = new_free
        self.local_cap = local
        self.capacity = new_cap
        self.slot_freed_epoch = new_epoch
        self.vectors = dev_vec
        self.valid = dev_valid
        self.sq_norms = dev_sq

    def _assign_slots(self, keys: Sequence[Any]) -> np.ndarray:
        """Route every key to a slot on its OWNING shard (upsert
        semantics), growing all shards when any owner is full. Must be
        called under ``self.lock``."""
        # growth first: worst-case fill per shard after this batch
        pending: dict[int, int] = {}
        for key in keys:
            if key not in self.key_to_slot:
                s = self.owner_shard(key)
                pending[s] = pending.get(s, 0) + 1
        if pending:
            need = max(
                self.local_cap - len(self.free_by_shard[s]) + n
                for s, n in pending.items()
            )
            self._grow_to_local(need)
        slots = []
        for key in keys:
            slot = self.key_to_slot.get(key)
            if slot is None:
                s = self.owner_shard(key)
                slot = self.free_by_shard[s].pop()
                self.key_to_slot[key] = slot
                self.slot_to_key[slot] = key
                self.key_seq[key] = self._next_seq
                self._next_seq += 1
            slots.append(slot)
            # upserted keys are dirty for the next snapshot cut
            self._dirty[key] = None
            self._dirty_removed.pop(key, None)
        return np.asarray(slots, np.int32)

    def add(self, keys: Sequence[Any], vecs) -> None:
        """Upsert a batch: one donated jitted slot-write per call (the
        amortized-dispatch path ISSUE 16's ann-build fix rides)."""
        vecs = self._prepare(vecs)
        if len(keys) != vecs.shape[0]:
            raise ValueError("keys/vectors length mismatch")
        dev = _DEVICE.begin("knn.sharded_write", rows=len(keys))
        try:
            with self.lock:
                slots = self._assign_slots(keys)
                bucket = sharded_write_bucket(len(slots), self.capacity)
                if bucket not in self._seen_buckets:
                    self._seen_buckets.add(bucket)
                    _DEVICE.note_recompile("knn.sharded_write")
                # supervised dispatch (ISSUE 17): injected faults raise
                # before the launch so retry is safe; donation failures
                # classify permanent and abort the epoch
                self.vectors, self.valid, self.sq_norms = (
                    _devsup.supervised_dispatch(
                        "knn.sharded_write",
                        lambda: self._write(
                            self.vectors, self.valid, self.sq_norms,
                            jnp.asarray(slots), jnp.asarray(vecs),
                            jnp.ones((len(slots),), bool),
                            normalize=self.metric is Metric.COS,
                        ),
                    )
                )
                out_vectors = self.vectors
        except BaseException:
            _DEVICE.end(dev, None, block=False)
            raise
        flops, acc = write_cost_model(len(keys), self.dimension)
        _DEVICE.end(
            dev, out_vectors,
            flops=flops,
            bytes_accessed=acc,
            transfer_bytes=nbytes_of(vecs) + 4 * len(keys),
        )

    # batch-adapter alias (engine/external_index.py batched delta path)
    add_batch = add

    def remove(self, keys: Sequence[Any]) -> None:
        with self.lock:
            slots = []
            for key in keys:
                slot = self.key_to_slot.pop(key, None)
                if slot is None:
                    continue
                del self.slot_to_key[slot]
                self.key_seq.pop(key, None)
                self.free_by_shard[slot // self.local_cap].append(slot)
                slots.append(slot)
                self._dirty_removed[key] = None
                self._dirty.pop(key, None)
            if not slots:
                return
            self.remove_epoch += 1
            self.slot_freed_epoch[np.asarray(slots)] = self.remove_epoch
            self.vectors, self.valid, self.sq_norms = self._write(
                self.vectors, self.valid, self.sq_norms,
                jnp.asarray(np.asarray(slots, np.int32)),
                jnp.zeros((len(slots), self.dimension), jnp.float32),
                jnp.zeros((len(slots),), bool),
            )

    remove_batch = remove

    # -- snapshot / restore (ISSUE 17) --------------------------------------
    def snapshot_state(self, *, extra=None) -> dict:
        """Delta-segment manifest (cut context armed) or inline full
        state — same contract as ``KnnShard.snapshot_state``."""
        from pathway_tpu.persistence import index_snapshot as _isnap

        return _isnap.snapshot_index(self, extra=extra)

    def load_state(self, state: dict) -> dict:
        """Rebuild every HBM shard from a committed snapshot; returns
        folded per-key extras. Restoring under a DIFFERENT mesh than the
        one that cut the snapshot is the N→M re-shard: ``_load_entries``
        re-buckets every entry through the CURRENT ``owner_shard`` mint,
        so the same committed segments serve any shard count."""
        from pathway_tpu.persistence import index_snapshot as _isnap

        return _isnap.restore_index(self, state)

    def _load_entries(self, entries: list) -> None:
        """Replace the corpus with ``[(key, seq, vector), ...]``, routing
        each key to its owning shard at the CURRENT ``n_shards``. Caller
        holds ``self.lock``. Rows rewrite with ``normalize=False`` (the
        bit-identical restore contract)."""
        n = len(entries)
        per = [0] * self.n_shards
        owners = np.empty((n,), np.int64)
        for i, (key, _seq, _row) in enumerate(entries):
            s = self.owner_shard(key)
            owners[i] = s
            per[s] += 1
        local = 128
        peak = max(per) if per else 0
        while local < peak:
            local *= 2
        self.local_cap = local
        self.capacity = self.n_shards * local
        self.key_to_slot = {}
        self.slot_to_key = {}
        self.key_seq = {}
        # restore_index re-seats _next_seq from the snapshot afterwards
        self._next_seq = 0
        self.free_by_shard = [
            list(range((s + 1) * local - 1, s * local - 1, -1))
            for s in range(self.n_shards)
        ]
        self.remove_epoch = 0
        self.slot_freed_epoch = np.full(self.capacity, -1, np.int64)
        self.vectors, self.valid, self.sq_norms = _empty_triple(
            self.capacity, self.dimension,
            self._db_sharding, self._row_sharding,
        )
        if not n:
            return
        slots = np.empty((n,), np.int32)
        rows = np.empty((n, self.dimension), np.float32)
        for i, (key, seq, row) in enumerate(entries):
            slot = self.free_by_shard[int(owners[i])].pop()
            self.key_to_slot[key] = slot
            self.slot_to_key[slot] = key
            self.key_seq[key] = int(seq)
            slots[i] = slot
            rows[i] = row
        self.vectors, self.valid, self.sq_norms = self._write(
            self.vectors, self.valid, self.sq_norms,
            jnp.asarray(slots), jnp.asarray(rows),
            jnp.ones((n,), bool), normalize=False,
        )

    # -- search ------------------------------------------------------------
    def search(self, queries, k: int) -> list[list[tuple[Any, float]]]:
        queries = self._prepare(queries)
        n = queries.shape[0]
        if n == 0 or not self.key_to_slot:
            return [[] for _ in range(n)]
        # shared bucket key (ISSUE 20): pow2 query padding and the k
        # clamp (per-shard partial k capped inside sharded_topk, merged
        # up to min(k, total capacity)) come from the SAME function the
        # retrace audit enumerates with
        bucket = sharded_search_bucket(
            n, self.n_shards, self.local_cap, k, self.chunk
        )
        padded_n, _, k_eff = bucket
        if bucket not in self._seen_buckets:
            self._seen_buckets.add(bucket)
            _DEVICE.note_recompile("knn.sharded_search")
        if padded_n != n:
            queries = np.concatenate(
                [queries, np.zeros((padded_n - n, self.dimension), np.float32)]
            )
        fn = _sharded_search_fn(
            self.mesh, self.axis, k_eff, self.metric.value,
            self.chunk, self.precision, _merge_mode(self.n_shards),
        )
        dev = _DEVICE.begin("knn.sharded_search", queries=padded_n, k=k_eff)
        try:
            with self.lock:  # read+launch before the next donating write
                q_dev = jax.device_put(jnp.asarray(queries), self._repl)
                vals, idx = _devsup.supervised_dispatch(
                    "knn.sharded_search",
                    lambda: fn(
                        q_dev, self.vectors, self.valid, self.sq_norms
                    ),
                )
                epoch = self.remove_epoch
                live_rows = len(self.key_to_slot)
        except BaseException:
            _DEVICE.end(dev, None, block=False)
            raise
        flops, acc = topk_scan_cost(
            padded_n, self.capacity, self.dimension, k_eff
        )
        flops_eff, _ = topk_scan_cost(n, live_rows, self.dimension, k_eff)
        _DEVICE.end(
            dev, (vals, idx), flops=flops,
            flops_effective=flops_eff, bytes_accessed=acc,
            transfer_bytes=nbytes_of(queries, vals, idx),
        )
        vals = np.asarray(vals)[:n]
        idx = np.asarray(idx)[:n]
        out: list[list[tuple[Any, float]]] = []
        for qi in range(n):
            hits = []
            for vv, slot in zip(vals[qi], idx[qi]):
                if not np.isfinite(vv):
                    continue
                slot = int(slot)
                if self.slot_freed_epoch[slot] > epoch:
                    # freed (possibly reused) after our dispatch — the
                    # mapping this hit scored against is gone
                    continue
                key = self.slot_to_key.get(slot)
                if key is None:
                    continue
                hits.append((key, float(vv)))
            # deterministic tie-break: equal scores order by insertion
            # sequence — slot layout (which differs between shardings)
            # never leaks into results. This is the contract the
            # sharded-vs-single-chip parity battery pins bit-identical.
            hits.sort(key=lambda t: (-t[1], self.key_seq.get(t[0], 0)))
            out.append(hits[:k])
        return out
