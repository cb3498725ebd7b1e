"""Fused serving-path query engine: tokenize -> encode -> top-k in ONE
XLA executable with ONE packed result readback.

Latency budget (SURVEY §7 hard part 6): per-query cost is dominated by
dispatch + result readback, not FLOPs — so the whole path (encoder forward
+ fused matmul/top-k over the index shard) compiles into a single
executable, and scores+indices pack into one f32 buffer so the host pays
exactly one device-to-host transfer per query batch.
"""

from __future__ import annotations

import queue
import threading
import time
from typing import Any, Sequence

import numpy as np

import jax
import jax.numpy as jnp

from pathway_tpu.ops.topk import chunked_topk_scores


class QueryEngine:
    """encode+search for a SentenceEncoder + KnnShard pair. The jitted
    executable is owned by the engine instance, so dropping the engine
    releases the model params and compiled closures."""

    def __init__(self, encoder, shard, *, k: int = 6):
        self.encoder = encoder
        self.shard = shard
        self.k = k
        model = encoder.model
        chunk = shard.chunk
        precision = shard.precision
        # the packed-buffer layout [vals | idx] is baked into the jitted
        # executable here; finish() must slice with THIS k_eff even if the
        # shard's capacity grows later
        k_eff = self.k_eff = min(k, shard.capacity, shard.chunk or 8192)
        from pathway_tpu.ops.knn import Metric

        # encoder outputs are L2-normalized, so cos == dot on the query
        # side; l2sq shards score with their cached squared norms
        metric = "l2sq" if shard.metric is Metric.L2SQ else "dot"
        use_sq = metric == "l2sq"

        import functools

        @functools.partial(jax.jit, static_argnames=("packed",))
        def run(params, ids, mask, vectors, valid, sq_norms, *, packed):
            emb = model.apply({"params": params}, ids, mask)  # [q,d] unit
            vals, idx = chunked_topk_scores(
                emb, vectors, valid, k_eff, chunk=chunk, metric=metric,
                sq_norms=sq_norms if use_sq else None,
                precision=precision,
            )
            if packed:
                # pack scores and indices into ONE f32 buffer: a single
                # readback (exact only for slot ids < 2^24)
                return jnp.concatenate(
                    [vals, idx.astype(jnp.float32)], axis=1
                )
            # two-buffer path for >=16.7M-row shards: i32 indices stay
            # exact; the host pays a second (concurrent) readback
            return vals, idx.astype(jnp.int32)

        self._fn = run

    def query(self, texts: Sequence[str]) -> list[list[tuple[Any, float]]]:
        texts = list(texts)
        if not texts or not self.shard.key_to_slot:
            return [[] for _ in texts]
        out: list[list[tuple[Any, float]]] = []
        cap = self.encoder.batch_size
        for start in range(0, len(texts), cap):
            out.extend(self._query_batch(texts[start : start + cap]))
        return out

    def dispatch(self, texts: list[str]):
        """Phase 1: tokenize + launch the fused executable. Returns an
        opaque (device_array, n) ticket without blocking — dispatch is
        asynchronous, so the caller can have several tickets in flight
        and their readbacks overlap."""
        from pathway_tpu.models.encoder import pad_batch

        ids, mask = self.encoder.tokenizer(texts)
        ids_p, mask_p, n = pad_batch(
            ids, mask, self.encoder.config.max_len, self.encoder.batch_size
        )
        with self.shard.lock:
            # read the array triple AND enqueue the executable before the
            # next index update donates (invalidates) these buffers —
            # update-while-serving safety; the launch is asynchronous so
            # this section is microseconds. The packed/two-buffer decision
            # and the remove-epoch are captured under the same lock so a
            # concurrent growth past 2^24 rows (or a slot-freeing remove)
            # cannot race this dispatch.
            # f32 packing is exact for slot ids < 2^24 (16.7M rows/shard);
            # larger shards take the two-buffer path (i32 indices, second
            # readback)
            packed_ok = self.shard.capacity < (1 << 24)
            result = self._fn(
                self.encoder.params,
                jnp.asarray(ids_p),
                jnp.asarray(mask_p),
                self.shard.vectors,
                self.shard.valid,
                self.shard.sq_norms,
                packed=packed_ok,
            )
            epoch = self.shard.remove_epoch
        return result, n, packed_ok, epoch

    def finish(self, ticket) -> list[list[tuple[Any, float]]]:
        """Phase 2: the device->host readback(s) + result shaping — one
        packed readback below 16.7M rows, two buffers above."""
        result, n, packed_ok, epoch = ticket
        k_eff = self.k_eff  # compiled-in layout, not current capacity
        if packed_ok:
            packed = np.asarray(result)[:n]  # the ONE readback
            vals = packed[:, :k_eff]
            idx = packed[:, k_eff:].astype(np.int64)
        else:
            vals_dev, idx_dev = result
            vals = np.asarray(vals_dev)[:n]
            idx = np.asarray(idx_dev)[:n].astype(np.int64)
        out = []
        for qi in range(n):
            hits = []
            for vv, slot in zip(vals[qi], idx[qi]):
                if not np.isfinite(vv):
                    continue
                slot = int(slot)
                # slot freed after our dispatch (possibly reused by a new
                # key): the mapping this score belongs to is gone — drop
                # the hit, matching removed-row semantics
                if self.shard.slot_freed_epoch[slot] > epoch:
                    continue
                key = self.shard.slot_to_key.get(slot)
                if key is None:
                    continue
                hits.append((key, float(vv)))
                if len(hits) == self.k:
                    break
            out.append(hits)
        return out

    def _query_batch(self, texts: list[str]):
        return self.finish(self.dispatch(texts))


class _Err:
    __slots__ = ("exc",)

    def __init__(self, exc):
        self.exc = exc


class MicroBatcher:
    """Concurrent serving front-end: collect in-flight queries for up to
    ``max_wait_ms`` (or ``max_batch`` queries), then ONE fused
    encode+search dispatch and ONE packed readback for the whole group.

    This is the serving-loop analog of the engine's as-of-time index
    batching (reference: src/engine/dataflow/operators/external_index.rs:
    112-155 — index and query streams are merged and batched by logical
    time); here the batch boundary is wall-clock micro-windows over
    concurrent HTTP clients instead of a logical timestamp.

    Two-stage pipeline: the collector thread tokenizes + dispatches
    (asynchronous, sub-ms), a pool of readback threads blocks on the
    device->host transfers — several batches' readbacks are in flight
    at once, so throughput is bounded by device work, not by one
    readback round trip per batch.
    """

    def __init__(
        self,
        engine: QueryEngine,
        *,
        max_wait_ms: float = 2.0,
        max_batch: int | None = None,
        readback_workers: int = 4,
    ):
        self.engine = engine
        # clamp to the encoder's padded batch capacity: _flush dispatches
        # one batch directly, bypassing query()'s cap-splitting
        self.max_batch = min(
            max_batch or engine.encoder.batch_size, engine.encoder.batch_size
        )
        self.max_wait = max_wait_ms / 1000.0
        self._q: "queue.Queue" = queue.Queue()
        self._tickets: "queue.Queue" = queue.Queue()
        self._closed = False
        self._collector = threading.Thread(target=self._collect, daemon=True)
        self._readers = [
            threading.Thread(target=self._readback, daemon=True)
            for _ in range(max(1, readback_workers))
        ]
        self._collector.start()
        for t in self._readers:
            t.start()

    # -- client API -------------------------------------------------------
    def query(self, text: str, timeout: float | None = 30.0):
        """Blocking single-query call, safe from many threads: the query
        rides the next micro-batch. Returns [(key, score), ...]."""
        if self._closed:
            raise RuntimeError("MicroBatcher is closed")
        slot: "queue.SimpleQueue" = queue.SimpleQueue()
        self._q.put((text, slot))
        res = slot.get(timeout=timeout)
        if isinstance(res, _Err):
            raise res.exc
        return res

    def close(self) -> None:
        if self._closed:
            return
        self._closed = True
        self._q.put(None)
        self._collector.join(timeout=5)
        # fail any request that raced past the closed check after the
        # sentinel: an explicit error now beats an opaque timeout later
        while True:
            try:
                item = self._q.get_nowait()
            except queue.Empty:
                break
            if item is not None:
                item[1].put(_Err(RuntimeError("MicroBatcher is closed")))
        for _ in self._readers:
            self._tickets.put(None)
        for t in self._readers:
            t.join(timeout=5)

    # -- pipeline stages --------------------------------------------------
    def _collect(self) -> None:
        while True:
            item = self._q.get()
            if item is None:
                return
            batch = [item]
            deadline = time.monotonic() + self.max_wait
            while len(batch) < self.max_batch:
                rem = deadline - time.monotonic()
                if rem <= 0:
                    break
                try:
                    nxt = self._q.get(timeout=rem)
                except queue.Empty:
                    break
                if nxt is None:
                    self._flush(batch)
                    return
                batch.append(nxt)
            self._flush(batch)

    def _flush(self, batch: list) -> None:
        texts = [t for t, _ in batch]
        slots = [s for _, s in batch]
        if not self.engine.shard.key_to_slot:
            for s in slots:
                s.put([])
            return
        try:
            ticket = self.engine.dispatch(texts)
        except Exception as exc:
            for s in slots:
                s.put(_Err(exc))
            return
        self._tickets.put((ticket, slots))

    def _readback(self) -> None:
        while True:
            got = self._tickets.get()
            if got is None:
                return
            ticket, slots = got
            try:
                results = self.engine.finish(ticket)
            except Exception as exc:
                for s in slots:
                    s.put(_Err(exc))
                continue
            for s, r in zip(slots, results):
                s.put(r)
