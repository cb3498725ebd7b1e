"""Bulk ingest: the connector always has more documents.

Parameters (the mix's file): ``commit_docs``, ``backlog_commits`` (how
many commits may wait beyond what is searchable), ``doc_words`` (scale,
alpha, cap), ``pool_docs`` (documents made in set-up; the window may not
use them up), ``warm_commits``, ``shape_seed``, ``check_docs``,
``trace_seconds``.

Searchable documents are counted at the index (the live shard's length,
read every millisecond or two by a thread of the harness). The window
opens at a commit's completion with the backlog full and closes at the
first completion at or after ``seconds``: every second of ``seconds`` is
inside it, and a stall anywhere in it, the end included, puts the close
off and lowers the rate. The rate is all documents made searchable in the
window over all its time: whole commits over all their seconds. (Cut at
``seconds`` sharp, the count moves in steps of one commit, 3% of a 51 s
window: noted as ``docs_at_seconds``, not reported.)
"""

from __future__ import annotations

import threading
import time

import numpy as np

import corpus
import reference
from loader import BenchmarkError
from stats import cos_gap


class _Feeder:
    """Keeps ``backlog`` commits waiting; records when counts change."""

    def __init__(self, ctx):
        self.ctx = ctx
        t = ctx.traffic
        self.commit_docs = int(t["commit_docs"])
        self.backlog = int(t["backlog_commits"]) * self.commit_docs
        self.fed = 0
        self.changes: list[tuple[float, int]] = []  # (time, documents searchable)
        self.feeding = True
        self.stop = False
        self.error = None
        self.thread = threading.Thread(target=self._run, name="bench-feeder", daemon=True)

    def searchable(self) -> int:
        return len(self.ctx.shard) - self.ctx.fill_rows

    def _run(self) -> None:
        ctx, vs = self.ctx, self.ctx.pipeline
        last = self.searchable()
        try:
            while not self.stop:
                now_count = self.searchable()
                if now_count != last:
                    self.changes.append((time.monotonic(), now_count))
                    last = now_count
                if self.feeding and self.fed - now_count < self.backlog:
                    at, end = self.fed, self.fed + self.commit_docs
                    if end > len(ctx.doc_lengths):
                        raise BenchmarkError(
                            "the document pool ran out inside the window: raise pool_docs"
                        )
                    texts = corpus.texts(ctx.doc_lengths[at:end], ctx.seed, 1000 + at)
                    ctx.feed.put([vs.doc_row(at + j, text) for j, text in enumerate(texts)])
                    self.fed = end
                    continue
                time.sleep(0.001)
        except BaseException as exc:
            self.error = exc


def _warm_encoder(ctx) -> None:
    """The encoder shapes this pool can dispatch, and no others: commits
    go to the encoder in batches of ``encoder_batch_size`` rows, each
    padded to the multiple of 32 that holds its longest document."""
    rows = int(ctx.config["encoder_batch_size"])
    cap = ctx.arch["max_position_embeddings"]
    tokens = np.minimum(ctx.doc_lengths + 2, cap)
    usable = len(tokens) // rows * rows
    longest = tokens[:usable].reshape(-1, rows).max(axis=1)
    buckets = sorted({int(min(-(-int(L) // 32) * 32, cap)) for L in longest})
    word = corpus.words()[0]
    for Lb in buckets:
        ctx.encoder.encode([" ".join([word] * (Lb - 2))] * rows)
    ctx.note(phase="warm_encoder", buckets=buckets, rows=rows)


def make_inputs(ctx) -> None:
    """The pool's word counts, from the seed alone (texts are joined
    commit by commit as they are fed)."""
    t = ctx.traffic
    spec = dict(t["doc_words"], shuffle_within=int(ctx.config["encoder_batch_size"]))
    ctx.doc_lengths = corpus.doc_lengths(int(t["pool_docs"]), spec, t["shape_seed"], ctx.seed)


def _text(ctx, d: int) -> str:
    commit = int(ctx.traffic["commit_docs"])
    at = (d // commit) * commit
    return corpus.texts(ctx.doc_lengths[at:at + commit], ctx.seed, 1000 + at)[d - at]


def _sample(ctx, lo: int, hi: int) -> list[int]:
    """Drawn from the seed among documents lo..hi, the longest with them."""
    rng = np.random.default_rng([ctx.seed & 0xFFFFFFFF, 52])
    n = min(int(ctx.traffic["check_docs"]), hi - lo)
    longest = lo + int(np.argmax(ctx.doc_lengths[lo:hi]))
    return sorted({longest, *(lo + rng.choice(hi - lo, size=n, replace=False)).tolist()})


def control(ctx) -> dict:
    """The reference in the program's place with fp8 operands (one
    precision below the configuration's bf16 activations)."""
    if not hasattr(ctx, "sample"):  # no window ran: as if the pool's first 8,192 documents came in it
        ctx.sample = _sample(ctx, 0, min(8192, len(ctx.doc_lengths)))
        ctx.in_index = ctx.statistics_count = 0
    texts = [_text(ctx, d) for d in ctx.sample]
    emb = reference.embed_texts(ctx.params, ctx.arch, texts, "fp8")
    return {"doc_emb": dict(zip(ctx.sample, emb))}


def setup(ctx) -> None:
    t, vs = ctx.traffic, ctx.pipeline
    make_inputs(ctx)
    headroom = ctx.capacity - ctx.fill_rows
    if int(t["pool_docs"]) > headroom:
        raise BenchmarkError(f"pool_docs {t['pool_docs']} exceeds the index headroom {headroom}")
    ctx.tap.phase = "warm"
    _warm_encoder(ctx)
    ctx.feeder = feeder = _Feeder(ctx)
    feeder.thread.start()
    # the cell's own traffic until a stretch of commits compiles nothing
    step = int(t["warm_commits"]) * feeder.commit_docs
    for stretch in range(4):
        ctx.counts.phase = f"warm{stretch}"
        target = feeder.searchable() + step
        vs.wait_until(lambda: feeder.searchable() >= target or feeder.error, 900,
                      "warm commits searchable", ctx.server_thread)
        if feeder.error:
            raise feeder.error
        compiled = ctx.counts.requests()
        ctx.note(phase=f"warm_stretch_{stretch}", compile_requests=compiled,
                 searchable=feeder.searchable())
        if compiled == 0:
            break


def window_rate(changes, t0: float, seconds: float) -> dict | None:
    """What a window opened by the completion at ``t0`` holds, once the
    first completion at or after ``t0 + seconds`` has closed it (``None``
    until then): ``changes`` are (time, documents searchable)."""
    start = next(c for t, c in changes if t == t0)
    inside = [(t, c) for t, c in changes if t > t0]
    closing = next(((t, c) for t, c in inside if t >= t0 + seconds), None)
    if closing is None:
        return None
    t_close, count = closing
    sharp = [c for t, c in inside if t <= t0 + seconds]
    return {
        "docs": count - start, "seconds": t_close - t0, "start": start, "end": count,
        "commits": sum(1 for t, _ in inside if t <= t_close),
        "docs_at_seconds": (sharp[-1] if sharp else start) - start,
    }


def window(ctx) -> dict:
    vs, feeder = ctx.pipeline, ctx.feeder
    # open at a commit's completion, with the backlog full
    n = len(feeder.changes)
    vs.wait_until(lambda: len(feeder.changes) > n or feeder.error, 300,
                  "a commit to open the window", ctx.server_thread, 0.0005)
    if feeder.error:
        raise feeder.error
    t0, start = feeder.changes[-1]
    ctx.tap.phase = "window"
    ctx.window_t0 = t0
    # a hang never closes the window: the run then fails, with no result
    vs.wait_until(lambda: window_rate(feeder.changes, t0, ctx.seconds) or feeder.error,
                  ctx.seconds + 240, "the commit that closes the window",
                  ctx.server_thread)
    if feeder.error:
        raise feeder.error
    ctx.tap.phase = "after"
    feeder.feeding = False
    vs.check_index(ctx)
    ctx.ingest = window_rate(feeder.changes, t0, ctx.seconds)
    ctx.note(ingest=ctx.ingest)
    # what was fed and is still on its way becomes searchable, or fails
    vs.wait_until(lambda: feeder.searchable() == feeder.fed or feeder.error, 120,
                  "fed documents searchable", ctx.server_thread)
    feeder.stop = True
    stats = None
    end = time.monotonic() + 60
    while time.monotonic() < end:
        stats = ctx.file_count()
        if stats == feeder.fed:
            break
        time.sleep(0.1)
    vs.check_index(ctx, ctx.fill_rows + feeder.fed)
    ctx.note(fed=feeder.fed, statistics_file_count=stats)
    ctx.statistics_count = stats
    # attempted: every document offered from the window's first on
    return {
        "attempted": feeder.fed - start,
        "failed": 0 if stats == feeder.fed else abs(feeder.fed - (stats or 0)),
        "metrics": {"ingest_docs_per_s": ctx.ingest["docs"] / ctx.ingest["seconds"]},
    }


def collect(ctx) -> None:
    """A sample, drawn from the seed, of the documents that became
    searchable in the window, the longest among them: their stored rows."""
    ctx.sample = _sample(ctx, ctx.ingest["start"], ctx.ingest["end"])
    found, rows = ctx.pipeline.doc_rows(ctx, ctx.sample)
    ctx.stored = dict(zip(found, rows))
    ctx.in_index = len(ctx.adapter.meta)


def check(ctx, served=None) -> dict:
    """``served``: ``{"doc_emb": {doc number: vector}}`` in the program's place."""
    if served is None:
        served = {"doc_emb": ctx.stored}
    want = reference.embed_texts(ctx.params, ctx.arch, [_text(ctx, d) for d in ctx.sample])
    gaps, missing = [], 0
    for row, d in enumerate(ctx.sample):
        got = served["doc_emb"].get(d)
        if got is None:
            missing += 1
            continue
        gaps.append(float(cos_gap(got, want[row])))
    fed = ctx.feeder.fed if getattr(ctx, "feeder", None) else ctx.in_index
    uncounted = abs(ctx.in_index - fed) + abs((ctx.statistics_count or 0) - fed)
    return {
        "compared": {
            "missing_docs": float(missing + uncounted),
            "doc_embed_gap": max(gaps) if gaps else 1.0,
        },
        "notes": {"docs_compared": len(gaps), "longest_words": int(max(
            ctx.doc_lengths[d] for d in ctx.sample))},
    }
