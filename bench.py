"""Headline benchmarks: embedding ingest throughput + RAG query latency.

North-star configs from BASELINE.json:
  * VectorStoreServer batch indexing, bge-small-class embedder — target
    >= 10k docs/s on TPU v5e-8 (1250 docs/s/chip).
  * RAG query p50 < 50 ms @ 1M docs.

This bench drives the flagship path end to end on a TPU and refuses any
other backend (a device lane on the CPU would print device metrics no
chip produced): REAL WordPiece tokenization
(BertTokenizerFast over the trained vocab; a cached HF checkpoint's own
tokenizer+weights are used when resolvable offline) → jitted bf16 encoder
forward (bucketed shapes) → HBM-resident KNN index add → fused query engine.

The artifact defends itself (round-4 verdict: the driver's stored tail lost
metric lines and recorded a contended box as steady state):
  * every metric line is also appended to BENCH_full.json in-repo;
  * a preflight load check settles the host before each timed phase;
  * volatile phases run warmup + 3 repeats and report median + dispersion
    (flagged when > 20%);
  * the ingest line carries a FLOP model: tokens/s, achieved FLOP/s, MFU
    and bucket fill-rate (model pinned against XLA cost analysis in
    tests/test_bench_flops.py).

Prints one JSON line per metric; the first line is the primary metric.
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from bench_util import (  # noqa: E402
    DISPERSION_FLAG,
    dispersion as _dispersion,
    median_index,
    write_artifact_atomic,
)

TARGET_PER_CHIP = 10_000 / 8  # BASELINE.json north-star on v5e-8
RAG_TARGET_P50_MS = 50.0
_INGEST_KEY_SPACE = 1 << 17  # half the ingest index capacity: never grows

ARTIFACT: list[dict] = []
_ARTIFACT_PATH = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "BENCH_full.json"
)


def emit(metric: dict) -> None:
    """Print the metric line AND record it for BENCH_full.json — stdout
    truncation in the driver can no longer lose data. The file is
    rewritten (atomically) after every emit so even a mid-run crash
    leaves a complete prefix on disk."""
    ARTIFACT.append(metric)
    print(json.dumps(metric), flush=True)
    write_artifact_atomic(_ARTIFACT_PATH, ARTIFACT)


def preflight(phase: str, max_wait_s: float = 60.0, per_core: float = 0.9) -> None:
    """Wait (bounded) for the 1-minute load to settle below
    `per_core * host_cores` before a timed phase; record what was seen.
    Round 4's driver artifact recorded half the engine's real throughput
    because something else was stealing the 1-core box mid-phase — the
    artifact must at least show whether the box was quiet."""
    threshold = per_core * (os.cpu_count() or 1)
    start = time.monotonic()
    load1 = os.getloadavg()[0]
    while load1 >= threshold and time.monotonic() - start < max_wait_s:
        time.sleep(5.0)
        load1 = os.getloadavg()[0]
    emit(
        {
            "metric": f"preflight_{phase}",
            "value": round(load1, 2),
            "unit": "load1",
            "settled": load1 < threshold,
            "waited_s": round(time.monotonic() - start, 1),
            "host_cores": os.cpu_count() or 1,
        }
    )


_DEVICE_PEAK_BF16 = {
    # per-chip dense bf16 peak FLOP/s (public spec sheets)
    "TPU v4": 275e12,
    "TPU v5e": 197e12,
    "TPU v5 lite": 197e12,
    "TPU v5": 459e12,
    "TPU v5p": 459e12,
    "TPU v6e": 918e12,
    "TPU v6 lite": 918e12,
}


def _device_peak() -> tuple[str, float | None]:
    import jax

    kind = jax.devices()[0].device_kind
    for name, peak in _DEVICE_PEAK_BF16.items():
        if kind.lower().startswith(name.lower()):
            return kind, peak
    return kind, None


def make_docs(n: int, words: int = 90, seed: int = 0) -> list[str]:
    """English-like documents drawn from the trained WordPiece vocab's full
    words, so tokenization cost and subword fragmentation are realistic."""
    rng = np.random.default_rng(seed)
    vocab_path = os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        "pathway_tpu", "models", "assets", "wordpiece_vocab.txt",
    )
    try:
        with open(vocab_path, encoding="utf-8") as f:
            vocab = [
                w for w in (line.strip() for line in f)
                if w.isalpha() and len(w) > 2
            ][:20000]
    except OSError:
        vocab = [f"token{i}" for i in range(5000)]
    return [
        " ".join(vocab[j] for j in rng.integers(0, len(vocab), size=words))
        for i in range(n)
    ]


def _ingest_window(enc, docs, batch_size, index, window_s, key_base0):
    """One timed ingest window through the tokenize-ahead pipeline.
    Returns (docs_done, elapsed, real_tokens, padded_tokens)."""
    import queue as _queue
    import threading

    from pathway_tpu.models.encoder import _bucket, _seq_bucket

    n_batches = len(docs) // batch_size
    tok_q: "_queue.Queue" = _queue.Queue(maxsize=4)
    stop = threading.Event()
    tok_err: list = []

    def tokenizer_ahead():
        batch_i = 1
        try:
            while not stop.is_set():
                start = (batch_i % n_batches) * batch_size
                chunk = docs[start : start + batch_size]
                batch_i += 1
                toks = enc.tokenizer(chunk)
                while not stop.is_set():
                    try:
                        tok_q.put((toks, len(chunk)), timeout=0.1)
                        break
                    except _queue.Full:
                        continue
        except Exception as exc:  # surfaced by the consumer's bounded get
            tok_err.append(exc)

    tt = threading.Thread(target=tokenizer_ahead, daemon=True)
    tt.start()

    done = 0
    real_tokens = 0
    padded_tokens = 0
    key_base = key_base0
    deadline = time.perf_counter() + window_s
    t0 = time.perf_counter()
    embs = None
    while time.perf_counter() < deadline:
        try:
            (ids, mask), n = tok_q.get(timeout=5.0)
        except _queue.Empty:
            stop.set()
            raise RuntimeError(
                "tokenize-ahead thread stalled"
            ) from (tok_err[0] if tok_err else None)
        embs = enc.encode_tokens_device(ids, mask)[:n]
        # keys cycle within half the index capacity: later windows upsert
        # (slot reuse, same device work) instead of growing the index —
        # a growth reshape would recompile INSIDE a timed window and
        # corrupt the median/dispersion machinery
        keys = [
            (key_base + i) % _INGEST_KEY_SPACE for i in range(n)
        ]
        index.add(keys, embs)
        key_base += n
        done += n
        real_tokens += int(mask.sum())
        nb = _bucket(ids.shape[0], 8, enc.batch_size)
        Lb = _seq_bucket(ids.shape[1], enc.config.max_len)
        padded_tokens += nb * Lb
    index.vectors.block_until_ready()
    elapsed = time.perf_counter() - t0
    stop.set()
    # the tokenizer thread must be fully gone before the next timed
    # window starts, or its tail contends with that window's measurement
    tt.join(timeout=10.0)
    if embs is not None:
        hits = index.search(np.asarray(embs[:4]), k=3)
        assert all(len(h) == 3 for h in hits)
    return done, elapsed, real_tokens, padded_tokens


def bench_ingest(enc, docs: list[str], batch_size: int) -> dict:
    """Warmup + 3 timed windows (median + dispersion): round 4 recorded a
    3.2x cold-vs-warm swing on this metric, so a single window cannot be
    the artifact of record. The MFU block makes the north star auditable:
    padded-token FLOPs are what the device executes; bucket_fill says how
    much of that is useful work."""
    from pathway_tpu.models.encoder import forward_flops_per_token
    from pathway_tpu.ops import KnnShard

    # pre-size the index: each capacity is a distinct XLA executable, so
    # growth reshapes mid-benchmark would measure recompiles, not ingest
    # (_INGEST_KEY_SPACE < capacity guarantees no growth at ANY rate)
    index = KnnShard(enc.embed_dim, "cos", precision="default", capacity=1 << 18)

    # warm up compilation (one pass per shape) before timing
    emb0 = enc.encode_device(docs[:batch_size])
    index.add(list(range(batch_size)), emb0)

    # warmup window (uncounted): caches, allocator, thread pools
    key_base = batch_size
    done, _, _, _ = _ingest_window(enc, docs, batch_size, index, 3.0, key_base)
    key_base += done

    runs = []
    for _ in range(3):
        done, elapsed, rt, pt = _ingest_window(
            enc, docs, batch_size, index, 4.0, key_base
        )
        key_base += done
        runs.append((done / elapsed, done, elapsed, rt, pt))

    rates = [r[0] for r in runs]
    med_i = median_index(rates)
    disp = _dispersion(rates)
    docs_per_s, done, elapsed, real_tokens, padded_tokens = runs[med_i]

    kind, peak = _device_peak()
    # per-doc padded length from the run itself
    padded_per_doc = padded_tokens / done if done else 0.0
    flops_per_tok = forward_flops_per_token(enc.config, int(padded_per_doc))
    achieved = flops_per_tok * (padded_tokens / elapsed)
    out = {
        "metric": "embed_ingest_docs_per_s_per_chip",
        "value": round(docs_per_s, 1),
        "unit": "docs/s",
        "tokenize_ahead": True,
        "runs": [round(r, 1) for r in rates],
        "dispersion": disp,
        "unsteady": disp > DISPERSION_FLAG,
        "tokens_per_s": round(real_tokens / elapsed, 1),
        "padded_tokens_per_s": round(padded_tokens / elapsed, 1),
        "bucket_fill": round(real_tokens / padded_tokens, 3)
        if padded_tokens
        else None,
        "model_flops_per_padded_token": round(flops_per_tok),
        "achieved_flops_per_s": round(achieved, -9),
        "device_kind": kind,
        "mfu": round(achieved / peak, 3) if peak else None,
        "vs_baseline": round(docs_per_s / TARGET_PER_CHIP, 3),
    }
    return out


def _fused_window(pipe, docs, batch_size, window_s, key_base0):
    """One timed window through the FUSED ingest chain (ops/ingest.py):
    the pipeline's own tokenize-ahead producer stages batches while the
    caller's thread issues fused encode+slot-write dispatches. Token
    accounting comes from the pipeline's running counters."""
    n_batches = len(docs) // batch_size
    t0 = time.perf_counter()
    deadline = t0 + window_s
    rows0 = pipe.rows_ingested
    real0, padded0 = pipe.real_tokens, pipe.padded_tokens

    def gen():
        bi = 1
        kb = key_base0
        while time.perf_counter() < deadline:
            start = (bi % n_batches) * batch_size
            chunk = docs[start : start + batch_size]
            bi += 1
            keys = [(kb + i) % _INGEST_KEY_SPACE for i in range(len(chunk))]
            kb += len(chunk)
            yield keys, chunk

    pipe.run(gen())  # blocks until the last slot-write is on device
    elapsed = time.perf_counter() - t0
    return (
        pipe.rows_ingested - rows0,
        elapsed,
        pipe.real_tokens - real0,
        pipe.padded_tokens - padded0,
    )


def bench_ingest_fused(enc, docs: list[str], batch_size: int) -> dict:
    """The ISSUE 16 lane: same corpus and windowing as bench_ingest, but
    through the fused tokenize→encode→index dispatch chain. Records BOTH
    MFU figures (effective = real tokens; padded = device-executed) and
    the device plane's roofline verdict for the fused site — the number
    that must flip from host-bound next to the old 0.33 baseline."""
    from pathway_tpu.internals.device import (
        PLANE,
        peak_bandwidth,
        roofline_verdict,
    )
    from pathway_tpu.internals.monitoring import ProberStats
    from pathway_tpu.models.encoder import forward_flops_per_token
    from pathway_tpu.ops import KnnShard
    from pathway_tpu.ops.ingest import IngestPipeline

    index = KnnShard(
        enc.embed_dim, "cos", precision="default", capacity=1 << 18
    )
    pipe = IngestPipeline(enc, index)
    # warm every shape bucket's fused executable before timing
    pipe.ingest(list(range(batch_size)), docs[:batch_size])
    key_base = batch_size
    done, _, _, _ = _fused_window(pipe, docs, batch_size, 3.0, key_base)
    key_base += done

    runs = []
    for _ in range(3):
        done, elapsed, rt, pt = _fused_window(
            pipe, docs, batch_size, 4.0, key_base
        )
        key_base += done
        runs.append((done / elapsed, done, elapsed, rt, pt))
    rates = [r[0] for r in runs]
    med_i = median_index(rates)
    disp = _dispersion(rates)
    docs_per_s, done, elapsed, real_tokens, padded_tokens = runs[med_i]

    kind, peak = _device_peak()
    padded_per_doc = padded_tokens / done if done else 0.0
    flops_per_tok = forward_flops_per_token(enc.config, int(padded_per_doc))
    achieved_padded = flops_per_tok * (padded_tokens / elapsed)
    fill = real_tokens / padded_tokens if padded_tokens else 0.0

    # verdict window: the device plane times the fused site's dispatches
    # (block_until_ready attribution), so the host-vs-device split is
    # measured, not inferred
    stats = ProberStats()
    PLANE.arm(None, stats)
    try:
        done, _, _, _ = _fused_window(pipe, docs, batch_size, 2.0, key_base)
        key_base += done
    finally:
        PLANE.disarm()
    agg = stats.device_sites.get("ingest.fused")
    verdict = None
    device_busy_share = None
    if agg is not None and agg[1] > 0:
        device_busy_share = agg[2] / agg[1]
        verdict = roofline_verdict(
            agg[1], agg[2], agg[3], agg[4], peak, peak_bandwidth(kind)
        )
    return {
        "metric": "embed_ingest_fused_docs_per_s_per_chip",
        "value": round(docs_per_s, 1),
        "unit": "docs/s",
        "fused_chain": True,
        "runs": [round(r, 1) for r in rates],
        "dispersion": disp,
        "unsteady": disp > DISPERSION_FLAG,
        "tokens_per_s": round(real_tokens / elapsed, 1),
        "padded_tokens_per_s": round(padded_tokens / elapsed, 1),
        "bucket_fill": round(fill, 3) if padded_tokens else None,
        "model_flops_per_padded_token": round(flops_per_tok),
        "device_kind": kind,
        # mfu is EFFECTIVE (real rows/tokens); the padded figure is what
        # the hardware executed — both recorded, never conflated
        "mfu": round(achieved_padded * fill / peak, 3) if peak else None,
        "mfu_padded": round(achieved_padded / peak, 3) if peak else None,
        "verdict": verdict,
        "device_busy_share": (
            round(device_busy_share, 3)
            if device_busy_share is not None
            else None
        ),
        "vs_baseline": round(docs_per_s / TARGET_PER_CHIP, 3),
    }


def bench_rag(
    enc, n_docs: int, n_queries: int = 100, k: int = 6
) -> tuple:
    """Returns (single_query_metrics, under_load_metrics, engine, index,
    queries) over an HBM-resident index of n_docs vectors: p50/p95
    end-to-end, then a 32-concurrent-client run through the
    micro-batcher."""
    from pathway_tpu.ops import KnnShard, QueryEngine

    dim = enc.embed_dim
    index = KnnShard(dim, "cos", precision="default", capacity=n_docs)
    rng = np.random.default_rng(0)
    block = 65536
    for start in range(0, n_docs, block):
        n = min(block, n_docs - start)
        vecs = rng.normal(size=(n, dim)).astype(np.float32)
        index.add(list(range(start, start + n)), vecs)
    index.vectors.block_until_ready()

    queries = [
        f"how do i connect a streaming source to the vector index variant {i}"
        for i in range(n_queries)
    ]
    engine = QueryEngine(enc, index, k=k)
    engine.query(queries[:1])  # compile the fused executable

    lat = []
    for q in queries:
        t0 = time.perf_counter()
        engine.query([q])
        lat.append((time.perf_counter() - t0) * 1000.0)
    lat.sort()
    p50 = lat[len(lat) // 2]
    p95 = lat[int(len(lat) * 0.95)]

    single = {
        "metric": "rag_query_p50_ms",
        "value": round(p50, 2),
        "unit": "ms",
        "p95_ms": round(p95, 2),
        "n_docs": n_docs,
        "k": k,
        "vs_baseline": round(RAG_TARGET_P50_MS / p50, 3),
    }

    # -- under concurrent load: 32 clients through the micro-batcher -----
    import threading

    from pathway_tpu.ops import MicroBatcher

    n_clients = 32
    duration_s = 8.0
    # warm every batch-size bucket the micro-batches can pad to (16 and 32
    # via pad_batch/_bucket) so no XLA compile lands inside the timed run
    engine.query(queries[:16])
    engine.query(queries[:32])
    # 10 ms window: wide enough that a full client generation regroups
    # into one fused dispatch even under host-thread scheduling jitter
    mb = MicroBatcher(engine, max_wait_ms=10.0, max_batch=32)
    mb.query(queries[0])
    lats: list[list[float]] = [[] for _ in range(n_clients)]
    stop_at = time.perf_counter() + duration_s

    def client(ci: int):
        i = 0
        while time.perf_counter() < stop_at:
            q = queries[(ci * 37 + i) % len(queries)]
            t0 = time.perf_counter()
            mb.query(q)
            lats[ci].append((time.perf_counter() - t0) * 1000.0)
            i += 1

    threads = [
        threading.Thread(target=client, args=(ci,)) for ci in range(n_clients)
    ]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    mb.close()
    all_lats = sorted(x for l in lats for x in l)
    n_done = len(all_lats)
    ul_p50 = all_lats[n_done // 2] if n_done else float("nan")
    ul_p95 = all_lats[int(n_done * 0.95)] if n_done else float("nan")
    under_load = {
        "metric": "rag_under_load_p50_ms",
        "value": round(ul_p50, 2),
        "unit": "ms",
        "p95_ms": round(ul_p95, 2),
        "qps": round(n_done / wall, 1),
        "n_clients": n_clients,
        "n_queries": n_done,
        "n_docs": n_docs,
        "k": k,
        "vs_baseline": round(RAG_TARGET_P50_MS / ul_p50, 3) if n_done else 0.0,
    }
    return single, under_load, engine, index, queries


def bench_load_curve(engine, queries) -> dict:
    """qps-vs-clients saturation curve: scale concurrent closed-loop
    clients 32 -> 128 -> 512 through the MicroBatcher, then measure
    open-loop device capacity."""
    import threading

    from pathway_tpu.ops import MicroBatcher

    curve = []
    for n_clients in (32, 128, 512):
        mb = MicroBatcher(
            engine, max_wait_ms=10.0, max_batch=32,
            readback_workers=max(4, n_clients // 16),
        )
        mb.query(queries[0])  # engage the pipeline
        duration_s = 6.0
        lats: list[list[float]] = [[] for _ in range(n_clients)]
        stop_at = time.perf_counter() + duration_s

        def client(ci: int):
            i = 0
            while time.perf_counter() < stop_at:
                q = queries[(ci * 37 + i) % len(queries)]
                t0 = time.perf_counter()
                mb.query(q, timeout=120.0)
                lats[ci].append((time.perf_counter() - t0) * 1000.0)
                i += 1

        threads = [
            threading.Thread(target=client, args=(ci,))
            for ci in range(n_clients)
        ]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
        mb.close()
        all_lats = sorted(x for l in lats for x in l)
        n_done = len(all_lats)
        curve.append(
            {
                "n_clients": n_clients,
                "qps": round(n_done / wall, 1),
                "p50_ms": round(all_lats[n_done // 2], 2) if n_done else None,
                "p95_ms": (
                    round(all_lats[int(n_done * 0.95)], 2) if n_done else None
                ),
                "mean_ms": (
                    round(sum(all_lats) / n_done, 2) if n_done else None
                ),
                "n_queries": n_done,
            }
        )

    # open-loop device capacity: dispatch batches back-to-back with no
    # readbacks; the device queue drains at the compute-bound rate
    # (block_until_ready on the last output waits for device completion
    # without paying a host readback per batch)
    batch = [queries[i % len(queries)] for i in range(32)]
    engine.finish(engine.dispatch(batch))  # warm
    m = 40
    t0 = time.perf_counter()
    last = None
    for _ in range(m):
        # ticket: (result, n, packed_ok, epoch); result is one packed
        # array below the 2^24-row cap, a (vals, idx) tuple above it
        last = engine.dispatch(batch)[0]
    (last[0] if isinstance(last, tuple) else last).block_until_ready()
    open_loop = time.perf_counter() - t0
    device_qps = 32 * m / open_loop
    return {
        "metric": "rag_qps_vs_clients",
        "value": curve[-1]["qps"],
        "unit": "qps",
        "curve": curve,
        "device_capacity_qps": round(device_qps, 1),
        "device_ms_per_batch32": round(open_loop / m * 1000.0, 2),
    }


def bench_update_while_serving(engine, index, queries) -> dict:
    """Serving under index churn: one updater thread streams add/remove
    batches against the HBM shard while 32 clients query through the
    MicroBatcher (as-of-dispatch snapshot semantics under churn; the
    engine-plane analog is the as-of-time external-index operator,
    reference external_index.rs:112-155). Consistency: every returned key
    was added at some point, and a final query scores exactly against the
    live state (brute-force numpy oracle)."""
    import threading

    from pathway_tpu.ops import MicroBatcher

    dim = engine.encoder.embed_dim
    rng = np.random.default_rng(7)
    n_clients = 32
    duration_s = 8.0
    churn_block = 256
    base_n = len(index.key_to_slot)
    ever_added = set(index.key_to_slot)

    mb = MicroBatcher(engine, max_wait_ms=10.0, max_batch=32,
                      readback_workers=8)
    mb.query(queries[0])

    stop = threading.Event()
    update_count = [0]

    def updater():
        """Cycle: add a block of fresh keys, then remove an older block —
        index size oscillates around base_n + churn_block."""
        next_key = base_n
        pending: list[range] = []
        while not stop.is_set():
            block = range(next_key, next_key + churn_block)
            next_key += churn_block
            vecs = rng.normal(size=(churn_block, dim)).astype(np.float32)
            index.add(list(block), vecs)
            ever_added.update(block)
            pending.append(block)
            update_count[0] += 2 * churn_block
            if len(pending) > 1:
                index.remove(list(pending.pop(0)))
            index.vectors.block_until_ready()

    lats: list[list[float]] = [[] for _ in range(n_clients)]
    bad_keys = [0]
    stop_at = time.perf_counter() + duration_s

    def client(ci: int):
        i = 0
        while time.perf_counter() < stop_at:
            q = queries[(ci * 37 + i) % len(queries)]
            t0 = time.perf_counter()
            hits = mb.query(q, timeout=120.0)
            lats[ci].append((time.perf_counter() - t0) * 1000.0)
            for key, _score in hits:
                if key not in ever_added:
                    bad_keys[0] += 1
            i += 1

    ut = threading.Thread(target=updater, daemon=True)
    threads = [
        threading.Thread(target=client, args=(ci,)) for ci in range(n_clients)
    ]
    t0 = time.perf_counter()
    ut.start()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    stop.set()
    ut.join(timeout=30)
    mb.close()

    # final exact-state check: engine answers == numpy oracle on the live
    # index contents for a probe query
    probe = queries[0]
    got = engine.query([probe])[0]
    vecs = np.asarray(index.vectors)
    valid = np.asarray(index.valid)
    emb = np.asarray(
        engine.encoder.encode_device([probe])
    )[0]
    scores = vecs @ emb
    scores[~valid] = -np.inf
    want_slots = np.argsort(-scores)[: len(got)]
    want = {index.slot_to_key[int(s)] for s in want_slots}
    consistency_ok = bad_keys[0] == 0 and {k for k, _ in got} == want

    all_lats = sorted(x for l in lats for x in l)
    n_done = len(all_lats)
    return {
        "metric": "rag_update_while_serving_p50_ms",
        "value": round(all_lats[n_done // 2], 2) if n_done else None,
        "unit": "ms",
        "p95_ms": round(all_lats[int(n_done * 0.95)], 2) if n_done else None,
        "qps": round(n_done / wall, 1),
        "updates_per_s": round(update_count[0] / wall, 1),
        "n_clients": n_clients,
        "consistency_ok": bool(consistency_ok),
    }


def bench_ann() -> dict | None:
    """ANN quality + speed on the host-side C++ HNSW (f16-quantized,
    reference bar: usearch f16): recall@10 vs the exact oracle and query
    throughput over BENCH_ANN_N vectors."""
    from pathway_tpu.native import NativeHnsw, available

    if not available():
        return None
    n = int(os.environ.get("BENCH_ANN_N", "100000"))
    dim, k, n_queries = 96, 10, 200
    rng = np.random.default_rng(5)
    centers = rng.normal(size=(64, dim)).astype(np.float32) * 3.0
    vectors = centers[rng.integers(0, 64, size=n)] + rng.normal(
        size=(n, dim)
    ).astype(np.float32)
    vectors /= np.linalg.norm(vectors, axis=1, keepdims=True)
    index = NativeHnsw(dim, "cos", M=16, ef_build=128, ef_search=96)
    t0 = time.perf_counter()
    # one native crossing for the whole corpus (ISSUE 16): graph
    # construction still dominates build_s, but the build now holds a
    # single GIL-released native call instead of n ctypes round trips —
    # a live pipeline keeps serving while the index builds
    index.add_batch(list(range(n)), vectors)
    build_s = time.perf_counter() - t0

    q_idx = rng.integers(0, n, size=n_queries)
    queries = vectors[q_idx] + 0.05 * rng.normal(
        size=(n_queries, dim)
    ).astype(np.float32)
    queries = (
        queries / np.linalg.norm(queries, axis=1, keepdims=True)
    ).astype(np.float32)
    truth = np.argsort(-(queries @ vectors.T), axis=1)[:, :k]
    t0 = time.perf_counter()
    hit = 0
    for qi in range(n_queries):
        got = {key for key, _ in index.search(queries[qi], k)}
        hit += len(got & set(truth[qi].tolist()))
    search_s = time.perf_counter() - t0
    recall = hit / (n_queries * k)
    return {
        "metric": "ann_recall_at_10",
        "value": round(recall, 4),
        "unit": "recall",
        "n_vectors": n,
        "dim": dim,
        "build_s": round(build_s, 1),
        "build": "batched",
        "queries_per_s": round(n_queries / search_s, 1),
        "quantization": "f16",
        "vs_baseline": round(recall / 0.95, 3),
    }


def main() -> None:
    import jax

    backend = jax.default_backend()
    if backend != "tpu":
        # the device lanes print per-chip rates and MFU: on any other
        # backend those would be numbers no chip produced
        raise SystemExit(
            f"bench.py: device lanes need a TPU, found backend {backend!r}"
        )
    from pathway_tpu.models.encoder import EncoderConfig, SentenceEncoder

    kind, _peak = _device_peak()
    emit(
        {
            "metric": "bench_meta",
            "value": 5,
            "unit": "round",
            "device_kind": kind,
            "host_cores": os.cpu_count() or 1,
            "load1_at_start": round(os.getloadavg()[0], 2),
        }
    )

    batch_size = 256
    # Real checkpoint when the HF cache has it; otherwise random weights with
    # the real WordPiece tokenizer — identical compute and tokenize cost.
    enc = SentenceEncoder(
        EncoderConfig.bge_small(),
        checkpoint="BAAI/bge-small-en-v1.5",
        batch_size=batch_size,
    )
    tok_kind = type(enc.tokenizer).__name__

    preflight("ingest")
    docs = make_docs(128 * batch_size)
    ingest = bench_ingest(enc, docs, batch_size)
    ingest["tokenizer"] = tok_kind
    emit(ingest)

    fused = bench_ingest_fused(enc, docs, batch_size)
    fused["tokenizer"] = tok_kind
    emit(fused)

    n_docs = int(os.environ.get("BENCH_RAG_DOCS", "1000000"))
    rag, under_load, engine, index, queries = bench_rag(enc, n_docs)
    emit(rag)
    emit(under_load)
    emit(bench_load_curve(engine, queries))
    emit(bench_update_while_serving(engine, index, queries))

    ann = bench_ann()
    if ann is not None:
        emit(ann)

    # relational plane: streaming wordcount through the sharded native
    # group-by executor. Settle first: the serving benches' reader threads
    # were just joined and XLA host callbacks drain asynchronously — on
    # small hosts their tail steals cycles from the first relational run.
    import gc

    gc.collect()
    preflight("relational")
    import importlib.util

    rel_path = os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        "scripts", "bench_relational.py",
    )
    spec = importlib.util.spec_from_file_location("bench_relational", rel_path)
    rel = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(rel)
    rel.main(200_000, emit=emit)


def main_trace() -> None:
    """``--trace``: run the relational lanes with the flight recorder
    armed so bench rows can record a per-phase breakdown artifact. The
    last run's Perfetto trace is kept next to BENCH_full.json
    (BENCH_trace_relational.json) and a ``trace_profile`` line — the
    hot-path blame summary (top nodes by self-time with their
    fused/degraded verdicts, native GIL-free phase totals, event-time
    lag maxima) — is spliced into the artifact in place. The untraced
    headline numbers are untouched; the paired overhead lanes live in
    ``scripts/bench_relational.py --traced-artifact``."""
    import importlib.util

    repo = os.path.dirname(os.path.abspath(__file__))
    rel_path = os.path.join(repo, "scripts", "bench_relational.py")
    spec = importlib.util.spec_from_file_location("bench_relational", rel_path)
    rel = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(rel)
    from pathway_tpu.analysis.profile import profile_trace

    # one traced run PER scenario, each dumped to its own artifact — a
    # shared path would let the second run overwrite the first and
    # silently waste it
    scenarios = {
        "wordcount": (
            "BENCH_trace_wordcount.json",
            lambda: rel._wordcount_once(200_000, 5_000, 2_000),
        ),
        "stream_join": (
            "BENCH_trace_join.json",
            lambda: rel._join_once(60_000, 300, 2_000),
        ),
    }
    reports = {}
    artifacts = []
    try:
        for name, (fname, run) in scenarios.items():
            trace_path = os.path.join(repo, fname)
            os.environ["PATHWAY_TRACE"] = trace_path
            run()
            os.environ.pop("PATHWAY_TRACE", None)
            reports[name] = profile_trace(trace_path, top_k=5)
            artifacts.append(fname)
    finally:
        os.environ.pop("PATHWAY_TRACE", None)
    first = reports["wordcount"]
    entry = {
        "metric": "trace_profile",
        "value": first["top"][0]["share"] if first["top"] else None,
        "unit": "top-node self-time share (wordcount)",
        "artifacts": artifacts,
        "scenarios": {
            name: {
                "wall_s": r["wall_s"],
                "total_self_s": r["total_self_s"],
                "native_s": r["native_s"],
                "lag_max_ms": r["lag_max_ms"],
                "top": r["top"][:3],
            }
            for name, r in reports.items()
        },
    }
    print(json.dumps(entry), flush=True)
    try:
        with open(_ARTIFACT_PATH) as f:
            artifact = json.load(f)
    except (OSError, json.JSONDecodeError):
        artifact = []
    artifact = [
        e
        for e in artifact
        if not (isinstance(e, dict) and e.get("metric") == "trace_profile")
    ] + [entry]
    write_artifact_atomic(_ARTIFACT_PATH, artifact)


if __name__ == "__main__":
    if "--trace" in sys.argv:
        main_trace()
    else:
        main()
