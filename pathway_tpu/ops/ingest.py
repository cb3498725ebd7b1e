"""Fused ingest chain: tokenize → encode → index slot-write (ISSUE 16).

PR 15's Device Observatory verdicted the embed ingest path HOST-BOUND at
0.33 MFU: the device idled while the host tokenized, padded, round-
tripped embeddings to numpy and issued one micro slot-write per row.
This module is the fix — ROADMAP item 2's dispatch-chain rebuild:

* **one jitted chain per shape bucket**: encoder forward → (already
  L2-normalized) embeddings → scatter slot-write into the KNN shard's
  HBM buffers, with the index triple DONATED so the write is in-place
  and no intermediate device→host round trip exists between encode and
  insert;
* **tokenize-ahead host stage**: a producer thread tokenizes, pads and
  (optionally) stages the NEXT batch's token arrays on device while the
  previous batch's chain is executing — double-buffered H2D, bounded by
  ``PATHWAY_INGEST_DEPTH`` staged batches so host and device stay one
  batch apart instead of strictly alternating;
* **device-plane records** at the new ``ingest.fused`` site: padded and
  effective FLOPs (real tokens over bucket tokens) so ``--profile``
  shows the verdict flip from host-bound to compute/bandwidth-bound and
  the MFU gauge reports honest utilization.

Padding discipline: the encoder's pow2-batch × doubling-width-seq
buckets (``seq_bucket``'s ladder) bound the shape set; padded rows carry slot index == capacity,
which the scatter drops (``mode="drop"``) — no masking pass, no second
dispatch. The chain stores the encoder's L2-normalized embeddings
directly, which is exactly what the COS-metric shard would have
computed on its own write path.
"""

from __future__ import annotations

import os
import queue
import threading
from typing import Any, Iterable, Sequence

import numpy as np

import jax
import jax.numpy as jnp

from pathway_tpu.internals import device as _devsup
from pathway_tpu.internals.device import (
    PLANE as _DEVICE,
    device_site,
    ingest_bucket,
    nbytes_of,
)
from pathway_tpu.internals.faults import fault_point
from pathway_tpu.models.encoder import (
    SentenceEncoder,
    forward_cost_model,
    pad_batch,
)
from pathway_tpu.ops.knn import KnnShard, Metric

device_site(
    "ingest.fused",
    cost_model=forward_cost_model,
    dtypes=("uint16", "int32", "float32", "bool"),
    where="pathway_tpu/ops/ingest.py:IngestPipeline._dispatch",
    donates=("vectors", "valid", "sq_norms"),
    description="fused tokenize->encode->scatter-write chain "
                "(index triple donated, in-place in HBM)",
)


def _env_int(name: str, default: int) -> int:
    try:
        v = int(os.environ.get(name, "") or default)
        return v if v > 0 else default
    except ValueError:
        return default


def _env_on(name: str, default: bool = True) -> bool:
    raw = str(os.environ.get(name, "1" if default else "0")).strip().lower()
    return raw not in ("0", "false", "no")


# args 4..6 of the fused chain are the index buffer triple — donated so
# the slot-write is in-place in HBM. Module-level so the Device Doctor's
# donation audit checks the SAME argnums the pipeline jits with.
FUSED_DONATE_ARGNUMS = (4, 5, 6)


def make_fused(model):
    """The un-jitted fused chain body: encoder forward → scatter
    slot-write of the (already L2-normalized) embeddings into the index
    triple. Module-level so the Device Doctor (analysis/device_plan.py)
    lowers the SAME code object the pipeline dispatches — the anti-drift
    contract; ``IngestPipeline`` jits exactly this with
    ``donate_argnums=FUSED_DONATE_ARGNUMS``."""

    def fused(params, ids, lengths, slots, vectors, valid, sq_norms):
        mask = (
            jnp.arange(ids.shape[1], dtype=jnp.int32)[None, :]
            < lengths[:, None]
        ).astype(jnp.int32)
        emb = model.apply({"params": params}, ids.astype(jnp.int32), mask)
        # padded rows carry slot == capacity: out of bounds, dropped
        # by the scatter — no separate masking pass
        vectors = vectors.at[slots].set(emb, mode="drop")
        valid = valid.at[slots].set(
            jnp.ones(slots.shape, bool), mode="drop"
        )
        sq_norms = sq_norms.at[slots].set(
            jnp.sum(emb * emb, axis=-1), mode="drop"
        )
        return emb, vectors, valid, sq_norms

    return fused


class IngestPipeline:
    """Pipelined embed→index ingest over one encoder + one KNN shard.

    ``ingest(keys, texts)`` runs one batch through the fused chain;
    ``run(batches)`` drives the tokenize-ahead loop over an iterable of
    ``(keys, texts)`` batches. Not thread-safe itself (one producer, one
    dispatcher); concurrent *queries* against the shard remain safe —
    the chain holds the shard's writer lock across slot assignment and
    launch, same discipline as ``KnnShard.add``.
    """

    site = "ingest.fused"

    def __init__(
        self,
        encoder: SentenceEncoder,
        index: KnnShard,
        *,
        depth: int | None = None,
        stage_h2d: bool | None = None,
    ):
        if index.dimension != encoder.embed_dim:
            raise ValueError(
                f"index dimension {index.dimension} != encoder embed dim "
                f"{encoder.embed_dim}"
            )
        if index.metric not in (Metric.COS, Metric.DOT):
            # the chain stores L2-normalized embeddings; an L2SQ index
            # would need raw norms the encoder already collapsed to 1
            raise ValueError(
                "fused ingest supports cos/dot shards (normalized "
                f"embeddings), not {index.metric}"
            )
        self.encoder = encoder
        self.index = index
        self.depth = (
            depth if depth is not None
            else _env_int("PATHWAY_INGEST_DEPTH", 2)
        )
        self.stage_h2d = (
            stage_h2d if stage_h2d is not None
            else _env_on("PATHWAY_INGEST_STAGE_H2D", True)
        )
        self._seen_buckets: set = set()
        # running totals for MFU/bucket-fill accounting (bench + smoke):
        # real tokens are what the corpus contained, padded tokens are
        # what the device executed
        self.rows_ingested = 0
        self.real_tokens = 0
        self.padded_tokens = 0
        # donate the index triple: the slot-write is in-place in HBM —
        # the whole point of fusing encode and insert into one chain
        self._fused = jax.jit(
            make_fused(encoder.model), donate_argnums=FUSED_DONATE_ARGNUMS
        )

    # -- host stage --------------------------------------------------------
    def _stage(self, keys: Sequence[Any], texts: Sequence[str]):
        """Tokenize + pad one batch and (optionally) start its H2D copy.
        Runs on the producer thread in ``run`` — batch N+1 is staged
        while batch N's fused chain occupies the device."""
        enc = self.encoder
        ids, mask = enc.tokenizer(list(texts))
        ids_p, mask_p, n = pad_batch(
            ids, mask, enc.config.max_len, enc.batch_size
        )
        lengths = mask_p.sum(axis=1, dtype=np.int32)
        if enc.config.vocab_size <= 65536:
            ids_p = ids_p.astype(np.uint16)  # compact H2D wire format
        eff_tokens = float(np.sum(lengths[:n], dtype=np.int64))
        ids_dev: Any = ids_p
        lengths_dev: Any = lengths
        # injectable H2D staging failure (ISSUE 17): fires per staged
        # batch; run()'s producer supervision classifies and retries it
        fault_point("device.h2d", site=self.site)
        if self.stage_h2d:
            # start the copies now (async): the device pulls the next
            # batch's tokens while it still computes the previous one
            ids_dev = jax.device_put(ids_p)
            lengths_dev = jax.device_put(lengths)
        return (list(keys), ids_dev, lengths_dev, n, eff_tokens)

    # -- device stage ------------------------------------------------------
    def _dispatch(self, staged) -> Any:
        keys, ids_dev, lengths_dev, n, eff_tokens = staged
        index = self.index
        nb, Lb = ids_dev.shape
        self.rows_ingested += n
        self.real_tokens += int(eff_tokens)
        self.padded_tokens += nb * Lb
        dev = _DEVICE.begin(self.site, rows=n, padded_tokens=nb * Lb)
        try:
            with index.lock:
                slots = index._assign_slots(keys)
                cap = index.capacity
                # pad the slot vector to the batch bucket with the OOB
                # sentinel the scatter drops
                slots_full = np.full((nb,), cap, np.int32)
                slots_full[:n] = slots
                bucket = ingest_bucket(nb, Lb, cap, ids_dev.dtype.name)
                if bucket not in self._seen_buckets:
                    self._seen_buckets.add(bucket)
                    _DEVICE.note_recompile(self.site)
                # supervised (ISSUE 17): injected faults raise before
                # the launch (retry-safe); a real failure that consumed
                # the donated index triple classifies permanent
                emb, index.vectors, index.valid, index.sq_norms = (
                    _devsup.supervised_dispatch(
                        self.site,
                        lambda: self._fused(
                            self.encoder.params,
                            jnp.asarray(ids_dev),
                            jnp.asarray(lengths_dev),
                            jnp.asarray(slots_full),
                            index.vectors, index.valid, index.sq_norms,
                        ),
                    )
                )
                out_vectors = index.vectors
        except BaseException:
            _DEVICE.end(dev, None, block=False)
            raise
        cfg = self.encoder.config
        d = index.dimension
        # forward dominates; the scatter write adds the sq-norm
        # reduction + row traffic (same model as KnnShard.add)
        flops, acc = forward_cost_model(cfg, nb, Lb)
        flops += 4.0 * nb * d
        acc += 8.0 * nb * d + 8.0 * nb
        # armed, end() blocks: OUTSIDE the lock (update-while-serving)
        _DEVICE.end(
            dev, (emb, out_vectors),
            flops=flops, bytes_accessed=acc,
            transfer_bytes=nbytes_of(ids_dev, lengths_dev) + 4 * nb,
            effective_share=eff_tokens / float(nb * Lb),
        )
        return emb[:n]

    # -- public API --------------------------------------------------------
    def ingest(self, keys: Sequence[Any], texts: Sequence[str]) -> Any:
        """One batch through the fused chain: tokenize (host), then
        encode + slot-write as a single jitted dispatch. Returns the
        (async, device-resident) embeddings of the real rows."""
        if not keys:
            return jnp.zeros((0, self.encoder.embed_dim), jnp.float32)
        return self._dispatch(self._stage(keys, texts))

    def run(self, batches: Iterable[tuple[Sequence[Any], Sequence[str]]],
            *, block: bool = True) -> int:
        """Drive the pipelined loop: a tokenize-ahead producer thread
        stages up to ``depth`` batches while the caller's thread issues
        the fused dispatches. Returns the number of rows ingested."""
        staged_q: queue.Queue = queue.Queue(maxsize=self.depth)
        err: list[BaseException] = []

        def producer():
            # SupervisorPolicy semantics (io/_connector.py) for the
            # tokenize-ahead stage: a transient hiccup (tokenizer I/O,
            # H2D copy) restarts the producer on the SAME batch with
            # bounded backoff instead of killing the whole pipelined
            # run; pulling from the batches iterator itself cannot be
            # retried (a raised generator is dead), so those failures
            # stay permanent
            import time as _t

            from pathway_tpu.parallel import protocol as _proto
            from pathway_tpu.udfs.retries import is_retryable

            it = iter(batches)
            retries = _devsup.dispatch_retries()
            try:
                while True:
                    try:
                        keys, texts = next(it)
                    except StopIteration:
                        break
                    attempt = 0
                    while True:
                        try:
                            staged = self._stage(keys, texts)
                            break
                        except BaseException as e:
                            kind = (
                                "transient"
                                if isinstance(e, Exception)
                                and is_retryable(e)
                                else "permanent"
                            )
                            verdict = _proto.device_dispatch_decide(
                                kind, attempt, retries
                            )
                            if verdict[0] != "retry":
                                raise
                            attempt = verdict[1]
                            stats = _DEVICE.stats
                            if stats is not None:
                                stats.on_device_dispatch_retry(self.site)
                            _t.sleep(min(2.0, 0.05 * (2 ** (attempt - 1))))
                    staged_q.put(staged)
            except BaseException as e:  # surface on the consumer side
                err.append(e)
            finally:
                staged_q.put(None)

        t = threading.Thread(
            target=producer, name="ingest-tokenize-ahead", daemon=True
        )
        t.start()
        rows = 0
        while True:
            staged = staged_q.get()
            if staged is None:
                break
            self._dispatch(staged)
            rows += staged[3]
        t.join()
        if err:
            raise err[0]
        if block:
            jax.block_until_ready(self.index.vectors)
        return rows
