#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the product path starts on the chip.

One process, one pass, no flags and no CPU mode:

1. refuses any JAX backend but ``tpu`` (exit 2, no result line);
2. drives the path a user runs, at bge-small's published widths with
   random-init weights from a seed: a seeded in-memory corpus (token
   lengths 16-512, heavy tail) -> ``pw.io.python.read`` in uneven
   commits -> ``VectorStoreServer(embedder=SentenceTransformerEmbedder(
   "bge-small"))`` -> ``run_server(threaded=True)`` -> questions through
   ``VectorStoreClient`` over loopback HTTP, sequential and concurrent;
   with more than one chip visible the index is sharded over all of them
   (``PATHWAY_INDEX_SHARDS``);
3. checks what came out: every document searchable, every answer HTTP
   200 without a ``Degraded`` header and equal to exact top-k computed in
   NumPy float32 over the embeddings the chip stored, chip embeddings
   (bf16 activations) against ``reference_forward`` in float32 on
   ``jax.devices("cpu")``, buffers resident on TPU devices, HBM grown by
   at least the index;
4. dispatches every site in the device-site registry once at a serving
   shape and compares with NumPy. A site registered later without a check
   here fails the smoke;
5. prints the counts a later reader needs (compilations per phase,
   compile-cache directory / hits / writes, seconds per phase, peak HBM
   bytes, native build fingerprints) — and no rate, MFU or roofline
   figure: those are the benchmark's job.

The last line of stdout is the result:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
Any failed check, any exception in any thread (the threaded ``pw.run()``
included) is a non-zero exit without that line.

Tolerances, and why:

* ``EMB_ATOL`` / ``EMB_MAX_ANGLE`` — bf16 activations against the float32
  oracle. The same graph with bf16 emulated on the CPU lands at 1.6e-3
  max-abs and 1-cos 3e-5 on unit vectors whose elements are ~0.04; the
  bounds are ~3x / ~15x that, and still 20x below the 1-cos >= 1e-2
  that separates two different documents under random-init weights.
* ``TIE_TOL`` — answers against NumPy float32 top-k. Both sides score the
  same float32 vectors (the scan runs at ``precision="highest"``), so
  scores agree to float32 rounding of a 384-term dot product; keys may
  only differ among candidates closer than this.
"""

from __future__ import annotations

import gc
import hashlib
import json
import os
import sys
import threading
import time
import traceback

_T0 = time.monotonic()  # process start, for the set-up seconds

# Sizes are constants, not flags: there is one smoke. (A scratch driver
# debugging the phases on the CPU at a tiny size overrides them by
# assignment; the command line offers no such thing.)
PLATFORM = "tpu"
MODEL = "bge-small"
SEED = 0
N_DOCS = 4096
# uneven commits: a dozen encoder shape buckets, and capacity doublings
# (two from the document index's 1,024-slot start; four per shard from
# the sharded index's 128)
COMMITS = (1, 7, 40, 130, 256, 300, 90, 512, 700, 23, 1000, 1037)
K = 6
N_SEQUENTIAL = 16
N_CLIENTS = 8
PER_CLIENT = 2
PORT = 18721
WINDOW_MS = 25.0
N_REFERENCE = 8            # embeddings compared with the float32 oracle
INGEST_DEADLINE_S = 900.0

EMB_ATOL = 5e-3
EMB_MAX_ANGLE = 5e-4       # 1 - cos
TIE_TOL = 1e-5

# serving shape for the per-site checks
SITE_Q, SITE_CAP, SITE_ROWS = 32, 65536, 4096


class SmokeFailure(Exception):
    """A check did not hold."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def note(**fields) -> None:
    print(json.dumps(fields, default=str), flush=True)


# -- compile / cache accounting ------------------------------------------------


class CompileCounts:
    """Counts JAX's own monitoring events per smoke phase: compile
    requests (every lowering handed to the backend, cache hit or not),
    persistent-cache hits, and cache writes (JAX only writes compiles
    that took >= 1 s). requests - hits = compilations the backend ran."""

    _EVENTS = {
        "/jax/compilation_cache/cache_hits": "cache_hits",
        "/jax/compilation_cache/cache_misses": "cache_writes",
    }

    def __init__(self):
        self.phase = "startup"
        self.counts: dict[str, dict[str, int]] = {}
        self._lock = threading.Lock()

    def install(self) -> None:
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _tick(self, field: str) -> None:
        with self._lock:
            row = self.counts.setdefault(
                self.phase,
                {"compile_requests": 0, "cache_hits": 0, "cache_writes": 0},
            )
            row[field] += 1

    def _duration(self, event: str, _secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self._tick("compile_requests")

    def _event(self, event: str, **_kw) -> None:
        field = self._EVENTS.get(event)
        if field is not None:
            self._tick(field)

    def total(self, field: str) -> int:
        return sum(row[field] for row in self.counts.values())


# -- corpus ---------------------------------------------------------------------


def make_corpus(n_docs: int, seed: int):
    """``n_docs`` unique documents of whole WordPiece-vocabulary words (one
    token each), word counts 14-510 from a Pareto tail (median ~28, about
    1.5% at the 512-token cap) — the heavy tail that makes padding and
    shape buckets matter."""
    import numpy as np

    import pathway_tpu.models as models

    vocab_path = os.path.join(
        os.path.dirname(models.__file__), "assets", "wordpiece_vocab.txt"
    )
    with open(vocab_path, encoding="utf-8") as f:
        vocab = [
            w for w in (line.strip() for line in f)
            if w.isalpha() and len(w) > 2
        ][:20000]
    rng = np.random.default_rng(seed)
    lengths = np.minimum(
        510, (14 * (1.0 + rng.pareto(1.2, size=n_docs))).astype(int)
    )
    docs = [
        " ".join(vocab[j] for j in rng.integers(0, len(vocab), size=int(n)))
        for n in lengths
    ]
    check(len(set(docs)) == n_docs, "corpus texts are not unique")
    return docs, lengths


def pick_questions(docs, lengths, n: int, seed: int):
    """Questions are short documents (18-28 words) with a question mark:
    each must come back with its own document as the nearest neighbour."""
    import numpy as np

    rng = np.random.default_rng(seed + 1)
    eligible = [i for i, n_w in enumerate(lengths) if 18 <= n_w <= 28]
    check(len(eligible) >= n, "too few short documents to draw questions")
    picked = rng.choice(eligible, size=n, replace=False)
    return [(int(i), docs[int(i)] + " ?") for i in picked]


def tap_encoder(encoder) -> dict:
    """Record every embedding the product path computes, by text — read
    only. The gateway embeds a question inside whatever batch its window
    formed (and again when the answered query is retracted), so the
    vector the server scored with cannot be recomputed outside it to the
    last bit; the answers are verified against the recorded vectors."""
    seen: dict[str, list] = {}
    inner = encoder.encode

    def encode(texts):
        texts = list(texts)
        out = inner(texts)
        for text, emb in zip(texts, out):
            seen.setdefault(text, []).append(emb)
        return out

    encoder.encode = encode
    return seen


# -- the product path -------------------------------------------------------------


def start_server(docs, commits, embedder, port: int):
    """Build the pipeline and start ``pw.run()`` on the server thread.
    Returns (server, subject-gate, thread): the corpus subject waits on
    the gate before each commit so the smoke paces commits one engine
    timestamp at a time (deterministic shape buckets run to run)."""
    import pathway_tpu as pw
    from pathway_tpu.xpacks.llm.vector_store import VectorStoreServer

    gate = threading.Semaphore(0)

    class Corpus(pw.io.python.ConnectorSubject):
        _deletions_enabled = False

        def run(self):
            at = 0
            for size in commits:
                gate.acquire()
                self.next_batch([
                    {
                        "data": docs[i],
                        "_metadata": {"path": f"doc/{i:05d}"},
                    }
                    for i in range(at, at + size)
                ])
                self.commit()
                at += size
            # stay up: the server keeps answering after the last commit
            threading.Event().wait()

    class DocSchema(pw.Schema):
        data: str
        _metadata: pw.Json

    table = pw.io.python.read(
        Corpus(), schema=DocSchema, autocommit_duration_ms=None
    )
    server = VectorStoreServer(table, embedder=embedder)
    thread = server.run_server(
        "127.0.0.1", port, threaded=True, window_ms=WINDOW_MS
    )
    return server, gate, thread


def wait_until(pred, deadline_s: float, what: str, thread=None):
    end = time.monotonic() + deadline_s
    while True:
        got = pred()
        if got:
            return got
        if thread is not None and not thread.is_alive():
            raise SmokeFailure(f"server thread died while waiting for {what}")
        if time.monotonic() > end:
            raise SmokeFailure(f"timed out after {deadline_s:.0f}s: {what}")
        time.sleep(0.02)


def find_adapter():
    """The live index adapter of the running pipeline (created inside the
    graph lowering, so there is no public handle): used read-only, to
    compare what the chip stored with what the server answered."""
    from pathway_tpu.stdlib.indexing.nearest_neighbors import _KnnAdapter

    found = [o for o in gc.get_objects() if type(o) is _KnnAdapter]
    check(len(found) == 1, f"expected one live index adapter, found {len(found)}")
    return found[0]


def hbm(devices, field: str) -> list[int]:
    """``memory_stats()[field]`` of every device, in device order."""
    out = []
    for d in devices:
        stats = d.memory_stats()
        check(bool(stats) and field in stats,
              f"{d} reports no memory_stats()[{field!r}]")
        out.append(int(stats[field]))
    return out


def on_platform(array, what: str) -> list[str]:
    devs = sorted(array.devices(), key=lambda d: d.id)
    check(
        all(d.platform == PLATFORM for d in devs),
        f"{what} lives on {[str(d) for d in devs]}, not on {PLATFORM}",
    )
    return [str(d) for d in devs]


def ask(client, question: str) -> list[dict]:
    """One retrieve through the public client; HTTP >= 400 raises inside
    it, a 200 carrying ``Degraded`` is refused here."""
    hits = client.query(question, k=K)
    headers = {k.lower(): v for k, v in client._session.last_headers.items()}
    check(
        "degraded" not in headers,
        f"answer carried a Degraded header: {headers.get('degraded')!r}",
    )
    check(isinstance(hits, list) and len(hits) == K,
          f"expected {K} hits, got {hits!r}")
    return hits


def doc_id(hit: dict) -> int:
    return int(hit["metadata"]["path"].split("/")[1])


def verify_answer(hits, scores, row_of_doc) -> tuple[bool, float]:
    """``hits`` against exact float32 ``scores`` ([index rows], NumPy) for
    one recorded query vector. Returns (ordered keys equal NumPy's top-k,
    largest |server score - NumPy score|); raises unless every answered
    document is in the exact top-k up to ties within TIE_TOL."""
    import numpy as np

    order = np.argsort(-scores, kind="stable")[:K]
    got_rows = [row_of_doc[doc_id(h)] for h in hits]
    kth = scores[order[-1]]
    worst = 0.0
    for h, row in zip(hits, got_rows):
        worst = max(worst, abs(-h["dist"] - float(scores[row])))
        check(
            scores[row] >= kth - TIE_TOL,
            f"doc {doc_id(h)} answered but is not in the exact top-{K}",
        )
    check(worst <= TIE_TOL,
          f"server scores differ from NumPy by {worst!r}")
    return got_rows == [int(r) for r in order], worst


def product_path(counts: CompileCounts, report: dict) -> dict:
    import jax
    import numpy as np

    from pathway_tpu.xpacks.llm.embedders import SentenceTransformerEmbedder
    from pathway_tpu.xpacks.llm.vector_store import VectorStoreClient

    devices = jax.devices()
    if len(devices) > 1:
        # the existing switch: one index shard per visible chip
        os.environ.setdefault("PATHWAY_INDEX_SHARDS", str(len(devices)))
    hbm_start = hbm(devices, "bytes_in_use")

    docs, lengths = make_corpus(N_DOCS, SEED)
    check(sum(COMMITS) == N_DOCS, "COMMITS must sum to N_DOCS")
    questions = pick_questions(
        docs, lengths, N_SEQUENTIAL + N_CLIENTS * PER_CLIENT, SEED
    )
    embedder = SentenceTransformerEmbedder(MODEL)
    encoder = embedder._encoder
    seen = tap_encoder(encoder)
    server, gate, thread = start_server(docs, COMMITS, embedder, PORT)
    client = VectorStoreClient(host="127.0.0.1", port=PORT, timeout=600)

    def file_count():
        try:
            return client.get_vectorstore_statistics()["file_count"]
        except ConnectionError:
            return None  # gateway not listening yet

    wait_until(lambda: file_count() is not None, 120, "gateway up", thread)
    report["seconds"]["startup"] = round(time.monotonic() - _T0, 2)
    report["compilations_before_first_document"] = (
        counts.counts.get("startup", {}).get("compile_requests", 0)
    )

    # -- ingest: one commit at a time -----------------------------------------
    counts.phase = "ingest"
    t_ingest = time.monotonic()
    done = 0
    for size in COMMITS:
        gate.release()
        done += size
        wait_until(
            lambda: file_count() == done, INGEST_DEADLINE_S,
            f"{done} documents searchable", thread,
        )
    report["seconds"]["ingest"] = round(time.monotonic() - t_ingest, 2)

    adapter = find_adapter()
    index = adapter.shard
    check(len(index) == N_DOCS,
          f"index holds {len(index)} of {N_DOCS} documents")
    jax.block_until_ready(index.vectors)
    hbm_after = hbm(devices, "bytes_in_use")
    index_devices = on_platform(index.vectors, "index.vectors")
    n_shards = getattr(index, "n_shards", 1)
    check(
        len(index_devices) == n_shards == len(devices),
        f"index.vectors spans {index_devices}, expected every visible chip",
    )
    index_bytes = index.capacity * (4 * index.dimension + 1 + 4)
    grew = [b - a for a, b in zip(hbm_start, hbm_after)]
    for d, g in zip(devices, grew):
        check(
            g >= index_bytes // n_shards,
            f"{d}: bytes_in_use grew {g}, less than its index share "
            f"{index_bytes // n_shards}",
        )
    report["index"] = {
        "class": type(index).__name__,
        "rows": len(index),
        "capacity": index.capacity,
        "bytes": index_bytes,
        "devices": index_devices,
        "bytes_in_use_grew": grew,
    }

    # -- the chip's stored embeddings, by document -------------------------------
    stored = np.asarray(index.vectors)
    row_of_doc = {}
    for key, slot in index.key_to_slot.items():
        row_of_doc[int(adapter.meta[key].value["path"].split("/")[1])] = slot
    check(sorted(row_of_doc) == list(range(N_DOCS)),
          "index rows do not map one-to-one onto the corpus")
    live = np.zeros(stored.shape[0], bool)
    live[list(row_of_doc.values())] = True

    def exact_scores(query_vector):
        q = np.asarray(query_vector, np.float32)
        q = q / max(float(np.linalg.norm(q)), 1e-30)
        s = stored @ q
        s[~live] = -np.inf
        return s

    # -- serving: sequential, then concurrent -------------------------------------
    def serve(tag: str) -> dict:
        counts.phase = tag
        t = time.monotonic()
        answers: dict[int, list[dict]] = {}
        for qi in range(N_SEQUENTIAL):
            answers[qi] = ask(client, questions[qi][1])
        barrier = threading.Barrier(N_CLIENTS)
        lock = threading.Lock()

        def concurrent(ci: int) -> None:
            own = VectorStoreClient(host="127.0.0.1", port=PORT, timeout=600)
            for r in range(PER_CLIENT):
                qi = N_SEQUENTIAL + r * N_CLIENTS + ci
                barrier.wait(timeout=600)
                hits = ask(own, questions[qi][1])
                with lock:
                    answers[qi] = hits

        workers = [
            threading.Thread(target=concurrent, args=(ci,), name=f"client-{ci}")
            for ci in range(N_CLIENTS)
        ]
        for w in workers:
            w.start()
        for w in workers:
            w.join(timeout=900)
            check(not w.is_alive(), f"{w.name} never finished")
        check(len(answers) == len(questions),
              f"{len(answers)} of {len(questions)} questions answered")
        report["seconds"][tag] = round(time.monotonic() - t, 2)
        return answers

    answers = serve("serve")
    setup_s = time.monotonic() - _T0
    # the same questions again: every shape the gateway needs exists now
    # (compile requests in this pass are reported, not hidden — a window
    # that mixes new questions with a new number of retractions still
    # compiles its row slice)
    steady = serve("serve_steady")
    report["seconds"]["setup_total"] = round(setup_s, 2)

    # every answer, both passes, against the vectors the chip produced:
    # documents as stored in the index, the question as recorded at the
    # encoder (one of the times the gateway embedded it)
    exact = 0
    worst = 0.0
    for qi, (doc, text) in enumerate(questions):
        for hits in (answers[qi], steady[qi]):
            check(len({doc_id(h) for h in hits}) == K,
                  f"question {qi}: an answer repeats a document")
            check(doc_id(hits[0]) == doc,
                  f"question {qi} (from doc {doc}) answered doc "
                  f"{doc_id(hits[0])} first")
            failures = []
            for vector in seen.get(text, ()):
                try:
                    same, dev = verify_answer(
                        hits, exact_scores(vector), row_of_doc
                    )
                except SmokeFailure as failure:
                    failures.append(str(failure))
                    continue
                exact += same
                worst = max(worst, dev)
                break
            else:
                raise SmokeFailure(
                    f"question {qi}: answer matches none of the "
                    f"{len(failures)} recorded query vectors: {failures}"
                )
    digest = hashlib.sha256(
        json.dumps(
            [[doc_id(h) for h in answers[qi]] for qi in range(len(questions))]
        ).encode()
    ).hexdigest()
    report["answers"] = {
        "answers": 2 * len(questions),
        "ordered_keys_equal_numpy": exact,
        "within_tie_tolerance": 2 * len(questions) - exact,
        "max_score_deviation_from_numpy": worst,
        "question_embeddings_recorded": sum(
            len(seen.get(text, ())) for _, text in questions
        ),
        "keys_sha256": digest,
    }

    retrieve = server.webserver._routes[0][2].__self__
    m = retrieve.serve_metrics
    multi = m.occupancy.total - m.occupancy.counts[0]
    check(multi >= 1, "no gateway window had occupancy > 1")
    check(
        m.shed == 0 and m.timeouts == 0 and m.browned_out == 0,
        f"gateway shed={m.shed} timeouts={m.timeouts} "
        f"browned_out={m.browned_out}",
    )
    check(
        retrieve._breaker == "closed" and m.breaker_state == "closed",
        f"dispatch breaker is {retrieve._breaker!r}",
    )
    check(thread.is_alive(), "server thread is gone")
    report["gateway"] = {
        "requests": m.requests,
        "windows": m.occupancy.total,
        "multi_request_windows": multi,
        "breaker": m.breaker_state,
    }
    return {
        "encoder": encoder, "docs": docs, "lengths": lengths,
        "stored": stored, "row_of_doc": row_of_doc,
        "windows": m.occupancy.total,
    }


# -- every registered device site, once, at a serving shape --------------------


def numpy_topk(queries, rows, k: int):
    import numpy as np

    q = queries / np.maximum(np.linalg.norm(queries, axis=1, keepdims=True), 1e-30)
    scores = q @ rows.T
    order = np.argsort(-scores, axis=1, kind="stable")[:, :k]
    return order, np.take_along_axis(scores, order, 1)


def same_topk(got_keys, got_vals, want_keys, want_vals, what: str) -> None:
    """Keys equal NumPy's, except swaps among scores within TIE_TOL."""
    import numpy as np

    got_vals = np.asarray(got_vals, np.float32)
    check(
        np.allclose(got_vals, want_vals, atol=TIE_TOL, rtol=0),
        f"{what}: scores differ from NumPy by "
        f"{float(np.abs(got_vals - want_vals).max())!r}",
    )
    differ = np.asarray(got_keys) != np.asarray(want_keys)
    if differ.any():
        gaps = np.abs(np.diff(want_vals, axis=1)).min(axis=1)
        check(
            bool((gaps[differ.any(axis=1)] <= TIE_TOL).all()),
            f"{what}: keys differ from NumPy where scores are not tied",
        )


def keys_and_scores(hits) -> tuple[list, list]:
    """``index.search`` results as ([[key]], [[score]])."""
    return (
        [[key for key, _ in row] for row in hits],
        [[score for _, score in row] for row in hits],
    )


def site_checks(ctx: dict) -> dict:
    """``{site name: check}``. Every name in the registry must be here."""
    import jax
    import numpy as np

    from pathway_tpu.models.encoder import reference_forward
    from pathway_tpu.ops.knn import KnnShard
    from pathway_tpu.parallel.mesh import make_mesh
    from pathway_tpu.parallel.sharded_knn import ShardedKnnIndex

    encoder = ctx["encoder"]
    dim = encoder.embed_dim
    rng = np.random.default_rng(SEED + 2)
    rows = rng.normal(size=(SITE_ROWS, dim)).astype(np.float32)
    unit = rows / np.linalg.norm(rows, axis=1, keepdims=True)
    queries = rng.normal(size=(SITE_Q, dim)).astype(np.float32)
    want_keys, want_vals = numpy_topk(queries, unit, K)
    cpu = jax.devices("cpu")[0]

    def oracle(texts):
        ids, mask = encoder.tokenizer(list(texts))
        with jax.default_device(cpu):
            params = jax.device_put(encoder.params, cpu)
            ref = jax.jit(
                lambda p, i, m: reference_forward(p, encoder.config, i, m)
            )(params, ids, mask)
        return np.asarray(ref)

    def close_to_oracle(got, ref, what: str) -> dict:
        got = np.asarray(got, np.float32)
        check(got.shape == ref.shape and bool(np.isfinite(got).all()),
              f"{what}: shape {got.shape} / non-finite values")
        max_abs = float(np.abs(got - ref).max())
        angle = float((1.0 - np.sum(got * ref, axis=1)).max())
        check(max_abs <= EMB_ATOL,
              f"{what}: max |bf16 - float32| {max_abs} > {EMB_ATOL}")
        check(angle <= EMB_MAX_ANGLE,
              f"{what}: 1-cos to the float32 oracle {angle} > {EMB_MAX_ANGLE}")
        check(
            bool(((got @ ref.T).argmax(axis=1) == np.arange(len(ref))).all()),
            f"{what}: an embedding is nearer another document's oracle",
        )
        return {"max_abs": max_abs, "max_1_minus_cos": angle}

    # a sample across the length tail: shortest, longest, some between
    by_len = np.argsort(ctx["lengths"], kind="stable")
    sample = sorted(
        {int(i) for i in by_len[:2]} | {int(i) for i in by_len[-2:]}
        | {int(i) for i in rng.choice(len(by_len), N_REFERENCE - 4, False)}
    )
    sample_texts = [ctx["docs"][i] for i in sample]
    sample_ref = oracle(sample_texts)

    def encoder_forward():
        # what the product calls (SentenceTransformerEmbedder): host
        # rows back, so residency is read off the parameters
        on_platform(
            jax.tree_util.tree_leaves(encoder.params)[0], "encoder params"
        )
        fresh = close_to_oracle(
            encoder.encode(sample_texts), sample_ref, "encoder.forward"
        )
        # and what the product path stored for the same documents
        stored = ctx["stored"][[ctx["row_of_doc"][i] for i in sample]]
        served = close_to_oracle(stored, sample_ref, "stored embeddings")
        return {"documents": len(sample), **fresh,
                "stored_max_abs": served["max_abs"]}

    def write_rows(index, site: str) -> float:
        """Add the rows and compare what the index stored with NumPy's
        own normalization; returns the largest deviation."""
        index.add(list(range(SITE_ROWS)), rows)
        on_platform(index.vectors, f"{site} index")
        got = np.asarray(index.vectors)[
            [index.key_to_slot[i] for i in range(SITE_ROWS)]
        ]
        dev = float(np.abs(got - unit).max())
        check(dev <= 1e-6, f"{site}: stored rows differ from NumPy by {dev}")
        check(int(np.asarray(index.valid).sum()) == SITE_ROWS,
              f"{site}: valid mask does not mark the written rows")
        return dev

    def search_rows(index, site: str) -> None:
        check(len(index) == SITE_ROWS, f"{site} needs the write site's rows")
        same_topk(
            *keys_and_scores(index.search(queries, K)),
            want_keys, want_vals, site,
        )

    shard = KnnShard(dim, "cos", capacity=SITE_CAP)

    def knn_write():
        dev = write_rows(shard, "knn.write")
        return {"rows": SITE_ROWS, "capacity": SITE_CAP, "max_abs": dev}

    def knn_search():
        search_rows(shard, "knn.search")
        return {"queries": SITE_Q, "k": K}

    mesh_devices = len(jax.devices())
    mesh = make_mesh(mesh_devices, axes=("dp",), shape=(mesh_devices,))
    sharded = ShardedKnnIndex(dim, mesh, metric="cos")

    def sharded_write():
        dev = write_rows(sharded, "knn.sharded_write")
        spans = len(sharded.vectors.devices())
        check(spans == mesh_devices,
              f"sharded index spans {spans} devices, mesh has {mesh_devices}")
        return {"rows": SITE_ROWS, "shards": mesh_devices,
                "fill": sharded.shard_fill(), "max_abs": dev}

    def sharded_search():
        search_rows(sharded, "knn.sharded_search")
        return {"queries": SITE_Q, "k": K, "shards": mesh_devices}

    def serve_window():
        # host-only site: its dispatches are the gateway windows the
        # serving phase committed
        check(ctx["windows"] > 0, "serve.window: no window was committed")
        return {"windows": ctx["windows"]}

    return {
        "encoder.forward": encoder_forward,
        "knn.write": knn_write,
        "knn.search": knn_search,
        "knn.sharded_write": sharded_write,
        "knn.sharded_search": sharded_search,
        "serve.window": serve_window,
    }


def device_sites(ctx: dict, report: dict) -> None:
    # importing the modules is what registers their sites
    import pathway_tpu.parallel.sharded_knn  # noqa: F401
    from pathway_tpu.internals.device import registered_sites

    checks = site_checks(ctx)
    registry = registered_sites()
    unchecked = sorted(set(registry) - set(checks))
    check(not unchecked,
          f"registered device sites with no chip_smoke check: {unchecked}")
    report["sites"] = {}
    # in the checks' own order: each write site before its search site
    for name in checks:
        check(name in registry, f"chip_smoke checks unregistered site {name}")
        report["sites"][name] = checks[name]()
        note(site=name, ok=True, **report["sites"][name])


# -- native executors ----------------------------------------------------------------


def native_libraries(report: dict) -> None:
    from pathway_tpu import native

    loaded = {
        "libpathway_native": native.get_lib(),
        "fastpath": native.get_fastpath(),
        "pwexec": native.get_pwexec(),
    }
    missing = [name for name, mod in loaded.items() if mod is None]
    check(not missing, f"native libraries not loaded: {missing}")
    prints = native.loaded_fingerprints()
    check(
        sorted(prints) == sorted(loaded),
        f"native fingerprints {sorted(prints)} != loaded {sorted(loaded)}",
    )
    report["native"] = prints


# -- entry ------------------------------------------------------------------------------


def die_with_thread(args) -> None:
    """The server thread's death is the smoke's death: an exception in any
    thread — the threaded pw.run() above all — ends the process, instead
    of leaving clients to time out politely."""
    traceback.print_exception(args.exc_type, args.exc_value, args.exc_traceback)
    print(f"chip_smoke: thread {args.thread.name if args.thread else '?'} "
          "died — failing the smoke", file=sys.stderr, flush=True)
    os._exit(3)


def run(report: dict) -> None:
    import jax

    report["seconds"] = {}
    threading.excepthook = die_with_thread
    counts = CompileCounts()
    counts.install()

    from pathway_tpu.internals.device import place_compile_cache

    report["compile_cache"] = {"dir": place_compile_cache()}
    # SentenceTransformerEmbedder(model) never loads a checkpoint
    report["weights"] = "random-init"
    native_libraries(report)
    ctx = product_path(counts, report)

    counts.phase = "sites"
    t = time.monotonic()
    device_sites(ctx, report)
    report["seconds"]["sites"] = round(time.monotonic() - t, 2)

    report["peak_hbm_bytes"] = hbm(jax.devices(), "peak_bytes_in_use")
    report["compile_counts"] = counts.counts
    report["compile_cache"].update(
        hits=counts.total("cache_hits"),
        writes=counts.total("cache_writes"),
        requests=counts.total("compile_requests"),
    )
    report["seconds"]["total"] = round(time.monotonic() - _T0, 2)


def main() -> int:
    # the float32 oracle runs on jax.devices("cpu"): keep a CPU backend
    # beside the accelerator when the environment names platforms (the
    # first one listed stays the default backend)
    named = os.environ.get("JAX_PLATFORMS")
    if named and "cpu" not in named.split(","):
        os.environ["JAX_PLATFORMS"] = named + ",cpu"
    import jax

    devices = jax.devices()
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    print(
        f"chip_smoke: platform={device['platform']} "
        f"device_kind={device['kind']!r} count={device['count']}",
        flush=True,
    )
    if jax.default_backend() != PLATFORM:
        print(
            f"chip_smoke: JAX backend is {jax.default_backend()!r}, not "
            f"{PLATFORM!r} — refusing: this smoke has no CPU mode",
            file=sys.stderr,
        )
        return 2
    report: dict = {}
    try:
        run(report)
    except BaseException as failure:
        if not isinstance(failure, SmokeFailure):
            traceback.print_exc()
        # what was collected before the failure, for whoever debugs it
        print(json.dumps({"partial_report": report}, default=str),
              file=sys.stderr)
        print(f"chip_smoke: FAILED — {failure!r}", file=sys.stderr, flush=True)
        return 1
    note(report=report)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    # os._exit: the server thread and its gateway workers are daemons
    # with no stop handle; nothing they hold needs an orderly shutdown
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)
