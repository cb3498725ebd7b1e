"""Open-loop questions at a fixed rate against an index that already
holds its fill and a small live corpus.

Parameters (the mix's file): ``rate_per_s``, ``k``, ``question_words``
[lo, hi], ``setup_docs``, ``setup_commit_docs``, ``doc_words`` (scale,
alpha, cap), ``clients`` (sender threads), ``warm_questions``,
``shape_seed``, ``check_questions`` (how many answers the reference goes
over), ``trace_seconds``.

Latency runs from the moment a question was due to its full reply. A
reply with an error status or a ``Degraded`` header, or none within a
minute of the window's close, is ``failed``.
"""

from __future__ import annotations

import queue
import threading
import time

import numpy as np

import corpus
import reference
from loader import BenchmarkError
from stats import cos_gap, percentile


def _questions(ctx, n: int, stream: int) -> list[str]:
    lo, hi = ctx.traffic["question_words"]
    lengths = np.random.default_rng(ctx.traffic["shape_seed"] + stream).integers(
        lo, hi + 1, size=n
    )
    return corpus.texts(lengths, ctx.seed, stream, suffix=" ?")


def _warm_encoder(ctx) -> None:
    """Every (batch bucket, sequence bucket, real rows) the question path
    can dispatch at this rate, once each: the bucket executables and the
    row slices behind ``emb[:n]``."""
    lo, hi = ctx.traffic["question_words"]
    seq = sorted({16 if L <= 16 else -(-L // 32) * 32 for L in range(lo + 3, hi + 4)})
    word = corpus.words()[0]
    for Lb in seq:
        text = " ".join([word] * (Lb - 2))
        for n in range(1, int(ctx.traffic["warm_rows"]) + 1):
            ctx.encoder.encode([text] * n)


def _send(ctx, texts: list[str], due: np.ndarray, t0: float) -> dict:
    """Send ``texts[i]`` at ``t0 + due[i]``; returns per-question records
    (due, sent, done, hits or None, error)."""
    k = int(ctx.traffic["k"])
    work: queue.Queue = queue.Queue()
    records: list = [None] * len(texts)

    def worker():
        client = ctx.client()
        while True:
            i = work.get()
            if i is None:
                return
            sent = time.monotonic()
            hits, error = None, None
            try:
                hits = client.query(texts[i], k=k)
                headers = {h.lower() for h in client._session.last_headers}
                if "degraded" in headers:
                    error = "degraded"
                elif not isinstance(hits, list) or len(hits) != k:
                    error = f"expected {k} hits, got {hits!r:.200}"
            except Exception as exc:  # an HTTP error status raises in the client
                error = repr(exc)[:200]
            records[i] = (t0 + due[i], sent, time.monotonic(), hits, error)

    threads = [
        threading.Thread(target=worker, name=f"bench-client-{c}", daemon=True)
        for c in range(int(ctx.traffic["clients"]))
    ]
    for t in threads:
        t.start()
    handed_late = 0.0  # this thread's own lateness: it waits for no reply
    for i in range(len(texts)):
        wait = t0 + due[i] - time.monotonic()
        if wait > 0:
            time.sleep(wait)
        work.put(i)
        handed_late = max(handed_late, time.monotonic() - (t0 + due[i]))
    ctx.handed_late_max_ms = handed_late * 1e3
    for _ in threads:
        work.put(None)
    end = time.monotonic() + 60.0  # a minute past the close
    for t in threads:
        t.join(timeout=max(0.0, end - time.monotonic()))
    return records


def make_inputs(ctx) -> None:
    """Documents, questions and due times, from the seed alone."""
    t = ctx.traffic
    n_docs = int(t["setup_docs"])
    spec = dict(t["doc_words"], shuffle_within=int(ctx.config["encoder_batch_size"]))
    lengths = corpus.doc_lengths(n_docs, spec, t["shape_seed"], ctx.seed)
    ctx.docs = corpus.texts(lengths, ctx.seed, 21)
    n = int(round(t["rate_per_s"] * ctx.seconds))
    ctx.questions = _questions(ctx, n, 31)
    ctx.due = corpus.arrivals(n, ctx.seconds, t["shape_seed"])


def setup(ctx) -> None:
    t, vs = ctx.traffic, ctx.pipeline
    make_inputs(ctx)
    n_docs = len(ctx.docs)
    ctx.tap.phase = "setup_docs"
    step = int(t["setup_commit_docs"])
    for at in range(0, n_docs, step):
        ctx.feed.put([vs.doc_row(i, ctx.docs[i]) for i in range(at, min(at + step, n_docs))])
    vs.wait_until(
        lambda: len(ctx.shard) == ctx.fill_rows + n_docs, 900,
        f"{n_docs} documents searchable", ctx.server_thread,
    )
    vs.wait_until(lambda: ctx.file_count() == n_docs, 120, "statistics caught up",
                  ctx.server_thread, 0.05)
    ctx.note(phase="docs_searchable", seconds=round(time.monotonic() - ctx.t0, 2))

    ctx.tap.phase = "warm"
    _warm_encoder(ctx)
    # the cell's own traffic until a stretch of it compiles nothing
    n_warm = int(t["warm_questions"])
    for stretch in range(4):
        ctx.counts.phase = f"warm{stretch}"
        texts = _questions(ctx, n_warm, 41 + stretch)
        due = corpus.arrivals(n_warm, n_warm / t["rate_per_s"], t["shape_seed"])
        records = _send(ctx, texts, due, time.monotonic())
        bad = [r[4] for r in records if r is None or r[4]]
        if bad:
            raise BenchmarkError(f"warm-up questions failed: {bad[:3]}")
        compiled = ctx.counts.requests()
        ctx.note(phase=f"warm_stretch_{stretch}", compile_requests=compiled)
        if compiled == 0:
            break
    ctx.tap.keep_texts = True


def window(ctx) -> dict:
    vs = ctx.pipeline
    m = ctx.retrieve.serve_metrics
    before = (m.requests, m.commits, m.shed, m.timeouts, m.browned_out)
    ctx.tap.phase = "window"
    t0 = time.monotonic()
    ctx.window_t0 = t0
    records = _send(ctx, ctx.questions, ctx.due, t0)
    ctx.tap.phase = "after"
    after = (m.requests, m.commits, m.shed, m.timeouts, m.browned_out)
    vs.check_index(ctx, ctx.fill_rows + len(ctx.docs))
    done = [r for r in records if r is not None and not r[4]]
    failed = len(records) - len(done)
    latency_ms = [(r[2] - r[0]) * 1e3 for r in done]
    ctx.records = records
    ctx.gateway = {
        name: b - a for name, a, b in
        zip(("requests", "commits", "shed", "timeouts", "browned_out"), before, after)
    }
    ctx.late_ms = [(r[1] - r[0]) * 1e3 for r in done]
    ctx.note(
        gateway=ctx.gateway, breaker=m.breaker_state,
        questions=len(records), answered=len(done),
        generator_late_p95_ms=percentile(ctx.late_ms, 95) if done else None,
        last_reply_after_close_s=round(
            max((r[2] for r in done), default=t0) - (t0 + ctx.seconds), 3),
    )
    # where a stall sat, should one come (two of 55 runs of PR 24 held one of
    # seconds): the longest stretch with no reply, the longest single encode
    # call, and whether the thread that hands out the questions stalled too
    replies = sorted(r[2] for r in done)
    quiet = max(zip(np.diff([t0, *replies]), replies), default=(0.0, t0))
    ctx.note(stall={
        "longest_quiet_s": round(float(quiet[0]), 3), "ended_at_s": round(quiet[1] - t0, 3),
        "longest_encode_s": round(max(
            (e[1] - e[0] for e in ctx.tap.encodes if e[2] == "window"), default=0.0), 3),
        "handed_late_max_ms": round(ctx.handed_late_max_ms, 3),
    })
    metrics = {}
    if done:
        metrics["query_p50_ms"] = percentile(latency_ms, 50)
        metrics["query_p95_ms"] = percentile(latency_ms, 95)
    return {"attempted": len(records), "failed": failed, "metrics": metrics}


def collect(ctx) -> None:
    """What the comparison needs of the program's state, before that
    state is freed."""
    found, rows = ctx.pipeline.doc_rows(ctx, range(len(ctx.docs)))
    ctx.stored = dict(zip(found, rows))


def _doc_id(hit: dict) -> int:
    return int(hit["metadata"]["path"].split("/")[1])


def _sample(ctx, candidates: list[int]) -> list[int]:
    """``check_questions`` of ``candidates`` drawn from the seed, and the
    longest question among them."""
    rng = np.random.default_rng([ctx.seed & 0xFFFFFFFF, 51])
    n = min(int(ctx.traffic["check_questions"]), len(candidates))
    longest = max(candidates, key=lambda i: len(ctx.questions[i]))
    return sorted({longest, *rng.choice(candidates, size=n, replace=False).tolist()})


def control(ctx) -> dict:
    """The reference in the program's place, one precision down: fp8
    operands in the encoder (for bf16), the scan at ``high`` (for float32
    at ``highest``). Answers every question of the window."""
    k = int(ctx.traffic["k"])
    sample = _sample(ctx, list(range(len(ctx.questions))))
    e_docs = reference.embed_texts(ctx.params, ctx.arch, ctx.docs, "fp8")
    e_q = reference.embed_texts(ctx.params, ctx.arch, [ctx.questions[i] for i in sample], "fp8")
    scores = np.asarray(reference.scan_scores(e_q, e_docs, "high"))
    top = np.argsort(-scores, axis=1, kind="stable")[:, :k]
    return {
        "answers": {
            i: [(int(d), float(scores[row, d])) for d in top[row]]
            for row, i in enumerate(sample)
        },
        "q_emb": {i: [e_q[row]] for row, i in enumerate(sample)},
        "doc_emb": dict(enumerate(e_docs)),
    }


def check(ctx, served=None) -> dict:
    """Every compared number. ``served`` replaces the program's answers
    (tests and the control put something else in the program's place):
    ``{"answers": {question index: [(doc id, score)]}, "q_emb": {index:
    [vectors]}, "doc_emb": {doc id: vector}}``."""
    k = int(ctx.traffic["k"])
    arch = ctx.arch
    if served is None:
        answered = [i for i, r in enumerate(ctx.records) if r is not None and not r[4]]
        sample = _sample(ctx, answered)
        served = {
            "answers": {
                i: [(_doc_id(h), -float(h["dist"])) for h in ctx.records[i][3]]
                for i in sample
            },
            "q_emb": {i: ctx.tap.by_text.get(ctx.questions[i], []) for i in sample},
            "doc_emb": ctx.stored,
        }
    sample = sorted(served["answers"])
    n_docs = len(ctx.docs)
    t_start = time.monotonic()
    e_docs = reference.embed_texts(ctx.params, arch, ctx.docs)
    e_q = reference.embed_texts(ctx.params, arch, [ctx.questions[i] for i in sample])
    t_embedded = time.monotonic()
    s_docs = np.asarray(reference.scan_scores(e_q, e_docs))
    fill = reference.fill_topk(
        e_q, ctx.seed, ctx.fill_rows // reference.FILL_BLOCK_ROWS, arch["hidden_size"], k
    )
    t_scanned = time.monotonic()
    both = np.concatenate([s_docs, fill], axis=1)
    kth = -np.partition(-both, k - 1, axis=1)[:, k - 1]
    fill_best = float(fill[:, 0].max()) if fill.size else float("-inf")

    stored = served["doc_emb"]
    answer_gap = score_gap = scan_gap = rank_gap = 0.0
    top_ref = -np.sort(-both, axis=1)[:, :k]
    wrong = 0
    for row, i in enumerate(sample):
        hits = served["answers"][i]
        ids = [d for d, _ in hits]
        if len(set(ids)) != k or not all(0 <= d < n_docs for d in ids):
            wrong += 1
            continue
        for j, score in enumerate(sorted((s for _, s in hits), reverse=True)):
            rank_gap = max(rank_gap, abs(score - float(top_ref[row, j])))
        for d, score in hits:
            ref = float(s_docs[row, d])
            answer_gap = max(answer_gap, float(kth[row]) - ref)
            score_gap = max(score_gap, abs(score - ref))
        # the gateway embeds a question once per window it sits in: the
        # answer was scored with one of the recorded vectors
        per_vector = []
        if all(d in stored for d in ids):
            rows = np.stack([np.asarray(stored[d], np.float64) for d in ids])
            got = np.asarray([score for _, score in hits])
            for v in served["q_emb"].get(i, []):
                v = np.asarray(v, np.float64)
                per_vector.append(float(np.abs(got - rows @ (v / max(np.linalg.norm(v), 1e-30))).max()))
        scan_gap = max(scan_gap, min(per_vector, default=1.0))

    q_gaps = [
        float(cos_gap(np.stack(served["q_emb"][i]), e_q[row]).max())
        if len(served["q_emb"].get(i, [])) else 1.0
        for row, i in enumerate(sample)
    ]
    d_ids = [d for d in range(n_docs) if d in stored]
    d_gaps = cos_gap(np.stack([stored[d] for d in d_ids]), e_docs[d_ids])
    return {
        "compared": {
            "wrong_answers": float(wrong + n_docs - len(d_ids)),
            "question_embed_gap": max(q_gaps),
            "doc_embed_gap": float(d_gaps.max()),
            "answer_gap": answer_gap,
            "score_gap": score_gap,
            "rank_gap": rank_gap,
            "scan_gap": scan_gap,
        },
        "notes": {
            "answers_compared": len(sample), "docs_compared": len(d_ids),
            "fill_best_score": fill_best, "kth_score_min": float(kth.min()),
            "reference_embed_s": round(t_embedded - t_start, 2),
            "reference_scan_s": round(t_scanned - t_embedded, 2),
        },
    }
