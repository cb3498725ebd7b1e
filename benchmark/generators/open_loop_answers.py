"""Open-loop ``/v2/answer`` requests at a fixed rate: each question is
embedded, its ``k`` best documents retrieved from an index that holds its
fill and a live corpus, a prompt built from them, and ``new_tokens``
tokens generated greedily by the answer model on the same chip.

Parameters (the mix's file): ``rate_per_s``, ``k``, ``new_tokens``,
``question_words`` [lo, hi], ``setup_docs``, ``setup_commit_docs``,
``doc_words`` (scale, alpha, cap), ``topic_words`` and ``topic_share``
(every ``k`` documents share a topic, a question asks about one),
``clients`` (sender threads),
``warm_rows``, ``warm_answers``, ``shape_seed``, ``check_answers`` (how
many requests the reference goes over, beside the longest prompt),
``trace_seconds``.

Latency runs from the moment a request was due to its full reply. A
reply with an error status or a ``Degraded`` header, one that is not an
answer with its ``k`` context documents, or none within a minute of the
window's close, is ``failed``.

The retrieval half of ``correct`` is ``open_loop_questions.check`` as it
stands, fed the context documents each reply carries; the answer half is
here: the reference's one full forward over prompt + answer against what
the program kept of prefill and of decoding through the cache.
"""

from __future__ import annotations

import functools
import queue
import threading
import time

import numpy as np

import corpus
import loader
import reference
import reference_decoder
from loader import BenchmarkError
from stats import percentile

olq = loader.module("generators", "open_loop_questions")

# the default prompt of BaseRAGQuestionAnswerer (xpacks/llm/prompts.py
# prompt_qa), written out again: the reference builds its own
PROMPT = (
    "Please provide an answer based solely on the provided sources. "
    "If none of the sources answer the question, reply exactly: "
    "No information found.\n\n"
    "Sources:\n{context}\n\n"
    "Question: {query}\n"
    "Answer:"
)


def prompt_of(question: str, doc_texts: list[str]) -> str:
    return PROMPT.format(context="\n\n".join(doc_texts), query=question)


@functools.lru_cache(maxsize=None)
def _tokenizer() -> reference.Tokenizer:
    return reference.Tokenizer(1 << 30)


@functools.lru_cache(maxsize=None)
def _pieces() -> list[str]:
    vocab = _tokenizer().vocab
    return [p for p, _ in sorted(vocab.items(), key=lambda kv: kv[1])]


def prompt_ids(ctx, text: str) -> np.ndarray:
    """[CLS] and the pieces, no [SEP], cut to what the cache has room for."""
    room = int(ctx.config["serving"]["max_positions"]) - int(ctx.traffic["new_tokens"])
    return np.asarray(_tokenizer().ids(text)[:-1][:room], np.int32)


def detokenize(ids) -> str:
    pieces, words = _pieces(), []
    for i in ids:
        piece = pieces[int(i)] if int(i) < len(pieces) else f"<{int(i)}>"
        if piece.startswith("##") and words:
            words[-1] += piece[2:]
        else:
            words.append(piece)
    return " ".join(words)


def _send(ctx, texts: list[str], due: np.ndarray, t0: float) -> list:
    """Ask ``texts[i]`` at ``t0 + due[i]``; per-request records (due, sent,
    done, reply or None, error)."""
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
            reply, error = None, None
            try:
                reply = client.answer(texts[i], return_context_docs=True)
                headers = {h.lower() for h in client._session.last_headers}
                docs = reply.get("context_docs") if isinstance(reply, dict) else None
                if "degraded" in headers:
                    error = "degraded"
                elif not isinstance(reply.get("response"), str) or not isinstance(docs, list) \
                        or len(docs) != k:
                    error = f"expected an answer over {k} documents, got {reply!r:.200}"
            except Exception as exc:  # an HTTP error status raises in the client
                error = repr(exc)[:200]
            records[i] = (t0 + due[i], sent, time.monotonic(), reply, error)

    threads = [
        threading.Thread(target=worker, name=f"bench-client-{c}", daemon=True)
        for c in range(int(ctx.traffic["clients"]))
    ]
    for t in threads:
        t.start()
    handed_late = 0.0
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


def _sample(ctx, candidates: list[int]) -> list[int]:
    """``check_answers`` of ``candidates``, drawn from the seed."""
    rng = np.random.default_rng([ctx.seed & 0xFFFFFFFF, ctx.seed >> 32, 53])
    n = min(int(ctx.traffic["check_answers"]), len(candidates))
    return sorted(rng.choice(candidates, size=n, replace=False).tolist())


def _topics(ctx) -> np.ndarray:
    """Each topic's words, as rows of indices into the word list: one
    topic for every ``k`` consecutive documents, chosen by ``--seed``."""
    t = ctx.traffic
    n_topics = -(-int(t["setup_docs"]) // int(t["k"]))
    return corpus._rng(ctx.seed, 23).integers(
        0, len(corpus.words()), size=(n_topics, int(t["topic_words"])))


def _documents(ctx) -> list[str]:
    """``setup_docs`` documents of ``min(cap, scale * (1 + Pareto(alpha)))``
    words. Document ``i``'s word count comes from ``shape_seed`` alone and
    its topic is ``i // k``; ``--seed`` chooses the words: ``topic_share``
    of them from the topic's own, the rest from the whole list."""
    t, spec = ctx.traffic, ctx.traffic["doc_words"]
    n_docs, k = int(t["setup_docs"]), int(t["k"])
    lengths = np.minimum(spec["cap"], (spec["scale"] * (1.0 + np.random.default_rng(
        t["shape_seed"]).pareto(spec["alpha"], size=n_docs))).astype(int))
    vocab, topics = np.asarray(corpus.words(), dtype=object), _topics(ctx)
    rng = corpus._rng(ctx.seed, 21)
    docs = []
    for i, n in enumerate(lengths):
        own = topics[i // k][rng.integers(0, topics.shape[1], size=n)]
        picks = np.where(rng.random(n) < t["topic_share"], own, rng.integers(0, len(vocab), size=n))
        docs.append(" ".join(vocab[picks]))
    return docs


def _asked(ctx, n: int, stream: int) -> tuple[np.ndarray, np.ndarray]:
    """Word counts and topics of ``n`` questions, from ``shape_seed``."""
    t = ctx.traffic
    lo, hi = t["question_words"]
    shape = np.random.default_rng(t["shape_seed"] + stream)
    lengths = shape.integers(lo, hi + 1, size=n)
    return lengths, shape.integers(0, int(t["setup_docs"]) // int(t["k"]), size=n)


def _questions(ctx, n: int, stream: int) -> list[str]:
    """``n`` questions of ``question_words`` [lo, hi] words, each about one
    whole topic: lengths and topics from ``shape_seed``, the words (of the
    topic's own) from ``--seed``. So which documents a question's prompt
    is built from, and how long it is, is the same for every ``--seed``:
    under random weights the nearest documents are those that share a
    text's words, where documents of unrelated words rank by their length
    (the shortest first), which would let the seed choose the work."""
    vocab, topics = np.asarray(corpus.words(), dtype=object), _topics(ctx)
    rng = corpus._rng(ctx.seed, stream)
    return [
        " ".join(vocab[topics[g][rng.integers(0, topics.shape[1], size=m)]]) + " ?"
        for m, g in zip(*_asked(ctx, n, stream))
    ]


def make_inputs(ctx) -> None:
    """Documents, questions and due times, from the seed alone; and which
    requests the reference will go over."""
    t = ctx.traffic
    ctx.docs = _documents(ctx)
    n = int(round(t["rate_per_s"] * ctx.seconds))
    ctx.questions = _questions(ctx, n, 31)
    ctx.due = corpus.arrivals(n, ctx.seconds, t["shape_seed"])
    ctx.sample = _sample(ctx, list(range(len(ctx.questions))))


def _warm_model(ctx) -> None:
    """Every executable the answer path can dispatch: the prefill chunk,
    each decode bucket, the slot reset, and what keeping a row costs."""
    words = corpus.words()
    room = int(ctx.config["serving"]["max_positions"]) - int(ctx.traffic["new_tokens"])
    chunk = int(ctx.config["serving"]["prefill_chunk"])
    for rows in (1, 2, 4, 8):
        if rows > int(ctx.traffic["warm_rows"]):
            break
        prompts = [
            prompt_ids(ctx, " ".join(words[: min(room - 2, chunk // 2 + 7 * r)]))
            for r in range(rows)
        ]
        ctx.model.generate(prompts, int(ctx.traffic["new_tokens"]), keep=range(rows))
    # the longest prompt the cache takes, once: every chunk position
    ctx.model.generate([prompt_ids(ctx, " ".join(words[:room]))], 2)


def setup(ctx) -> None:
    t, pipe = ctx.traffic, ctx.pipeline
    make_inputs(ctx)
    n_docs = len(ctx.docs)
    ctx.tap.phase = "setup_docs"
    step = int(t["setup_commit_docs"])
    for at in range(0, n_docs, step):
        ctx.feed.put([pipe.doc_row(i, ctx.docs[i]) for i in range(at, min(at + step, n_docs))])
    pipe.wait_until(
        lambda: len(ctx.shard) == ctx.fill_rows + n_docs, 900,
        f"{n_docs} documents searchable", ctx.server_thread,
    )
    pipe.wait_until(lambda: ctx.file_count() == n_docs, 120, "statistics caught up",
                    ctx.server_thread, 0.05)
    ctx.note(phase="docs_searchable", seconds=round(time.monotonic() - ctx.t0, 2))

    ctx.tap.phase = "warm"
    olq._warm_encoder(ctx)
    _warm_model(ctx)
    ctx.note(phase="model_warm", seconds=round(time.monotonic() - ctx.t0, 2))
    # the cell's own traffic until a stretch of it compiles nothing
    n_warm = int(t["warm_answers"])
    for stretch in range(4):
        ctx.counts.phase = f"warm{stretch}"
        texts = _questions(ctx, n_warm, 41 + stretch)
        due = corpus.arrivals(n_warm, n_warm / t["rate_per_s"], t["shape_seed"])
        records = _send(ctx, texts, due, time.monotonic())
        bad = [r[4] if r is not None else "no reply" for r in records if r is None or r[4]]
        if bad:
            raise BenchmarkError(f"warm-up requests failed: {bad[:3]}")
        compiled = ctx.counts.requests()
        ctx.note(phase=f"warm_stretch_{stretch}", compile_requests=compiled)
        if compiled == 0:
            break
    ctx.tap.keep_texts = True
    # which prompts the tap keeps: those that end in a sampled question
    tap = ctx.answer_tap
    for i in ctx.sample:
        suffix = tuple(int(x) for x in _tokenizer().ids(
            f"Question: {ctx.questions[i]}\nAnswer:")[1:-1])
        tap.wanted[suffix] = i
        tap.suffix_len[i] = len(suffix)


def window(ctx) -> dict:
    pipe = ctx.pipeline
    m = ctx.retrieve.serve_metrics
    before = (m.requests, m.commits, m.shed, m.timeouts, m.browned_out)
    ctx.answer_tap.counters_at["open"] = pipe.snapshot(ctx.model.counters)
    ctx.tap.phase = "window"
    t0 = time.monotonic()
    ctx.window_t0 = t0
    records = _send(ctx, ctx.questions, ctx.due, t0)
    ctx.tap.phase = "after"
    ctx.answer_tap.counters_at["close"] = pipe.snapshot(ctx.model.counters)
    after = (m.requests, m.commits, m.shed, m.timeouts, m.browned_out)
    pipe.check_index(ctx, ctx.fill_rows + len(ctx.docs))
    done = [r for r in records if r is not None and not r[4]]
    failed = len(records) - len(done)
    latency_ms = [(r[2] - r[0]) * 1e3 for r in done]
    ctx.records = records
    ctx.gateway = {
        name: b - a for name, a, b in
        zip(("requests", "commits", "shed", "timeouts", "browned_out"), before, after)
    }
    ctx.late_ms = [(r[1] - r[0]) * 1e3 for r in done]
    calls = [c for c in ctx.answer_tap.calls if c[2] == "window"]
    k = int(ctx.traffic["k"])
    topics = _asked(ctx, len(records), 31)[1]
    own = [{_doc_id(d) // k for d in r[3]["context_docs"]} == {int(g)}
           for r, g in zip(records, topics) if r is not None and not r[4]]
    prompt_tokens = [c[4] / c[3] for c in calls]
    ctx.note(
        gateway=ctx.gateway, breaker=m.breaker_state,
        requests=len(records), answered=len(done),
        generator_late_p95_ms=percentile(ctx.late_ms, 95) if done else None,
        last_reply_after_close_s=round(
            max((r[2] for r in done), default=t0) - (t0 + ctx.seconds), 3),
        generate_calls=len(calls), prompts_generated=sum(c[3] for c in calls),
        own_topic_share=sum(own) / len(own) if own else None,
        prompt_tokens_p50=percentile(prompt_tokens, 50) if calls else None,
        prompt_tokens_min_max=[min(prompt_tokens), max(prompt_tokens)] if calls else None,
        generate_ms_p50=percentile([(c[1] - c[0]) * 1e3 for c in calls], 50) if calls else None,
        handed_late_max_ms=round(ctx.handed_late_max_ms, 3),
    )
    metrics = {}
    if done:
        metrics["query_p50_ms"] = percentile(latency_ms, 50)
        metrics["query_p95_ms"] = percentile(latency_ms, 95)
    return {"attempted": len(records), "failed": failed, "metrics": metrics}


def collect(ctx) -> None:
    """What the comparison needs of the program's state, before that
    state is freed: the stored document rows, and the longest prompt's
    final SSM state."""
    found, rows = ctx.pipeline.doc_rows(ctx, range(len(ctx.docs)))
    ctx.stored = dict(zip(found, rows))
    longest = ctx.answer_tap.longest
    if longest is not None and longest[1].ssm is not None:
        longest[1].ssm = [np.asarray(s) for s in longest[1].ssm]
    for gen in ctx.answer_tap.kept.values():
        if longest is None or gen is not longest[1]:
            gen.ssm = None


def _doc_id(doc: dict) -> int:
    return int(doc["metadata"]["path"].split("/")[1])


def _generation_record(gen) -> dict:
    """What the answer half compares, from a kept ``Generation``."""
    states = None
    if gen.ssm is not None:
        states = [np.asarray(layer) for layer in gen.ssm]
    return {
        "ids": np.concatenate([gen.prompt, gen.tokens[:-1]]),
        "tokens": np.asarray(gen.tokens),
        "logits": np.asarray(gen.logits, np.float32),
        "routes": np.concatenate([gen.prompt_routes, gen.decode_routes], axis=1),
        "states": states,
    }


def _served(ctx) -> dict:
    """What the program served for the sampled requests."""
    tap = ctx.answer_tap
    answers, gens, replies, prompts = {}, {}, {}, {}
    for i in ctx.sample:
        r = ctx.records[i]
        if r is None or r[4] or i not in tap.kept:
            continue
        docs = r[3]["context_docs"]
        answers[i] = [(_doc_id(d), -float(d["dist"])) for d in docs]
        prompts[i] = prompt_ids(ctx, prompt_of(ctx.questions[i], [d["text"] for d in docs]))
        replies[i] = r[3]["response"]
        gens[i] = _generation_record(tap.kept[i])
    if tap.longest is not None:
        gens["longest"] = _generation_record(tap.longest[1])
    return {
        "answers": answers,
        "q_emb": {i: ctx.tap.by_text.get(ctx.questions[i], []) for i in answers},
        "doc_emb": ctx.stored, "gens": gens, "replies": replies, "prompts": prompts,
    }


def control(ctx) -> dict:
    """The reference in the program's place, one precision down: fp8
    operands in the retriever's encoder and the scan at ``high`` (as
    ``serve-steady``'s control), and fp8 operands for every bfloat16
    matrix product of the answer model. Its prompts are built from its
    own retrieval; each is continued by ``new_tokens`` - 1 ids drawn
    from the seed, and the tokens it "generates" are its own argmax at
    the compared positions."""
    k, new = int(ctx.traffic["k"]), int(ctx.traffic["new_tokens"])
    sample = list(ctx.sample)
    e_docs = reference.embed_texts(ctx.params, ctx.arch, ctx.docs, "fp8")
    e_q = reference.embed_texts(ctx.params, ctx.arch, [ctx.questions[i] for i in sample], "fp8")
    scores = np.asarray(reference.scan_scores(e_q, e_docs, "high"))
    top = np.argsort(-scores, axis=1, kind="stable")[:, :k]
    answers = {i: [(int(d), float(scores[row, d])) for d in top[row]]
               for row, i in enumerate(sample)}
    rng = np.random.default_rng([ctx.seed & 0xFFFFFFFF, 57])
    rows = ctx.darch["vocab_rows"]
    prompts, seqs = {}, []
    for i in sample:
        prompts[i] = prompt_ids(
            ctx, prompt_of(ctx.questions[i], [ctx.docs[d] for d, _ in answers[i]]))
        seqs.append(np.concatenate(
            [prompts[i], rng.integers(1000, min(rows, 20000), size=new - 1)]).astype(np.int32))
    out = reference_decoder.forward(ctx.darch, ctx.seed, seqs, last=new, precision="fp8")
    gens, replies = {}, {}
    for i, ids, o in zip(sample, seqs, out):
        tokens = o["logits"].argmax(axis=-1)
        gens[i] = {"ids": ids, "tokens": tokens, "logits": o["logits"],
                   "routes": o["routes"], "states": None}
        replies[i] = detokenize(tokens)
    longest = max(sample, key=lambda i: len(prompts[i]))
    gens["longest"] = dict(gens[longest], states=out[sample.index(longest)]["states"])
    return {
        "answers": answers,
        "q_emb": {i: [e_q[row]] for row, i in enumerate(sample)},
        "doc_emb": dict(enumerate(e_docs)), "gens": gens, "replies": replies,
        "prompts": prompts,
    }


def compare_generations(ctx, gens: dict) -> tuple[dict, dict]:
    """The reference's full forward over each kept prompt + answer,
    teacher-forced on the served ids and following the served expert
    selections where they lie within the router tolerance, against the
    served logits, tokens, routes and final state."""
    tol = ctx.limits["tolerances"]
    names = sorted(gens, key=str)
    new = int(ctx.traffic["new_tokens"])
    t_start = time.monotonic()
    out = reference_decoder.forward(
        ctx.darch, ctx.seed, [gens[n]["ids"] for n in names], last=new,
        routes=[gens[n]["routes"] for n in names], router_tol=float(tol["router"]),
    )
    logit_gap = token_gap = router_gap = state_gap = 0.0
    wrong_tokens = wrong_routes = 0
    for name, o in zip(names, out):
        g = gens[name]
        want = o["logits"].astype(np.float64)
        spread = want.max(axis=-1) - want.min(axis=-1)
        logit_gap = max(logit_gap, float(
            (np.abs(g["logits"] - want).max(axis=-1) / spread).max()))
        behind = (want.max(axis=-1) - want[np.arange(new), g["tokens"]]) / spread
        token_gap = max(token_gap, float(behind.max()))
        wrong_tokens += int((behind > float(tol["token"])).sum())
        router_gap = max(router_gap, o["router_gap"])
        wrong_routes += o["wrong_routes"]
        if g["states"] is not None:
            for got, ref in zip(g["states"], o["states"]):
                state_gap = max(state_gap, float(
                    np.abs(got - ref).max() / max(np.abs(ref).max(), 1e-30)))
    compared = {
        "logit_gap": logit_gap, "token_gap": token_gap, "wrong_tokens": float(wrong_tokens),
        "router_gap": router_gap, "wrong_routes": float(wrong_routes),
        "state_gap": state_gap,
    }
    notes = {
        "generations_compared": len(names),
        "tokens_compared": [int(len(gens[n]["ids"])) for n in names],
        "reference_decoder_s": round(time.monotonic() - t_start, 2),
    }
    return compared, notes


def check(ctx, served=None) -> dict:
    """Every compared number. ``served`` replaces what the program served
    (tests and the control put something else in its place)."""
    if served is None:
        served = _served(ctx)
    sample = list(ctx.sample)
    missing = [i for i in sample if i not in served["answers"] or i not in served["gens"]]
    if "longest" not in served["gens"]:
        missing.append("longest")
    mismatched = 0
    for i in served["answers"]:
        g = served["gens"][i]
        n = len(g["ids"]) - (len(g["tokens"]) - 1)
        if not np.array_equal(g["ids"][:n], served["prompts"][i]) \
                or served["replies"][i] != detokenize(g["tokens"]):
            mismatched += 1
    if served["answers"]:
        retrieval = olq.check(ctx, {k: served[k] for k in ("answers", "q_emb", "doc_emb")})
    else:
        retrieval = {"compared": {}, "notes": {}}
    compared = dict(retrieval["compared"])
    compared["wrong_answers"] = compared.get("wrong_answers", 0.0) + mismatched
    compared["missing_replies"] = float(len(missing))
    notes = dict(retrieval["notes"], prompts_mismatched=mismatched)
    if served["gens"]:
        answer, more = compare_generations(ctx, served["gens"])
        compared.update(answer)
        notes.update(more)
    return {"compared": compared, "notes": notes}
