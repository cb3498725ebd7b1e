"""The product pipeline of the answer cell, as a user builds it:

    seeded ConnectorSubject -> pw.io.python.read -> VectorStoreServer(
        embedder=SentenceTransformerEmbedder(...), index_params=...)
    -> BaseRAGQuestionAnswerer(llm=TPUChat(AnswerModel(...)), indexer=store,
                               search_topk=k)
    -> BaseRestServer.serve("/v2/answer", rag.AnswerQuerySchema, rag.answer_query)
       .run(threaded=True)  <- RAGClient /v2/answer over HTTP

The server binds ``/v2/answer`` and ``/v1/statistics`` alone: every route
that queries the index builds an index of its own (``QARestServer``'s
three would be three copies of the vectors in HBM, each fed every
document), and this deployment serves answers.

The retrieval half is ``pipelines/vector_store.py``'s, reused as it
stands (its ``Tap``, index fill and checks); the answer half is new: the
decoder's weights are made layer by layer by ``reference_decoder.py`` from
the seed and handed to the program as they are. ``build``
imports the product's new modules first, before any thread starts: a
program that lacks them fails there, at once.
"""

from __future__ import annotations

import queue
import time

import loader
import reference
import reference_decoder
from loader import BenchmarkError

vs = loader.module("pipelines", "vector_store")

# what generators, checks and readers use of the retrieval pipeline
wait_until, check_index, doc_rows, doc_row = (
    vs.wait_until, vs.check_index, vs.doc_rows, vs.doc_row)


class AnswerTap:
    """What the chat was asked to generate for, and what it made for the
    requests the check will go over. ``wanted``: tuple(question's last
    ids) -> question index, set by the generator; beside those, every
    prompt longer than any before it is kept (the last such is the
    window's longest)."""

    def __init__(self):
        self.calls: list[tuple] = []      # (t0, t1, phase, rows, prompt tokens)
        self.wanted: dict[tuple, int] = {}
        self.suffix_len: dict[int, int] = {}
        self.kept: dict[int, object] = {}     # question index -> Generation
        self.longest: tuple | None = None     # (prompt tokens, Generation)
        self.counters_at: dict[str, dict] = {}


def _tap_model(model, tap: AnswerTap, retrieval_tap) -> None:
    import jax.profiler

    inner = model.generate

    def which(prompt) -> int | None:
        for n in set(tap.suffix_len.values()):
            i = tap.wanted.get(tuple(int(t) for t in prompt[-n:]))
            if i is not None and tap.suffix_len[i] == n:
                return i
        return None

    def generate(prompts, max_new_tokens, keep=()):
        phase = retrieval_tap.phase
        named, longest_row = {}, None
        if phase == "window":
            for row, prompt in enumerate(prompts):
                i = which(prompt)
                if i is not None:
                    named[row] = i
            row = max(range(len(prompts)), key=lambda r: len(prompts[r]))
            if tap.longest is None or len(prompts[row]) > tap.longest[0]:
                longest_row = row
        rows = sorted(set(keep) | set(named) | ({longest_row} - {None}))
        t0 = time.monotonic()
        with jax.profiler.TraceAnnotation("bench.answer.generate"):
            made = inner(prompts, max_new_tokens, keep=rows)
        tap.calls.append((t0, time.monotonic(), phase, len(prompts),
                          int(sum(len(p) for p in prompts))))
        for row, gen in enumerate(made):
            if row in named:
                tap.kept[named[row]] = gen
            if row == longest_row:
                tap.longest = (len(gen.prompt), gen)
            elif row in rows:
                gen.ssm = None      # only the longest prompt's state is compared
        return made

    model.generate = generate


def snapshot(counters) -> dict:
    """The model's counters as plain numbers, to difference later."""
    return {
        "expert_tokens": counters.expert_tokens.copy(),
        "held_selections": counters.held_selections,
        "absent_selections": counters.absent_selections,
        "prefill_real": counters.prefill_real,
        "prefill_padded": counters.prefill_padded,
        "prompts": counters.prompts,
        "decode_steps": dict(counters.decode_steps),
        "decode_experts_touched": counters.decode_experts_touched,
    }


def control_inputs(ctx) -> None:
    """What the configuration and the seed alone give: the retriever's
    sizes and weights (float32, one jitted call), the decoder's sizes
    (its weights are made layer by layer where they are used)."""
    config, index = ctx.config, ctx.config["index"]
    ctx.arch = {key: config["retriever"][key] for key in vs.ARCH_KEYS}
    ctx.params = reference.make_params(ctx.arch, ctx.seed)
    ctx.darch = reference_decoder.arch_of(config)
    ctx.capacity = int(index["reserved_space"])
    ctx.fill_rows = int(index["fill_rows"])


def decoder_params(ctx) -> dict:
    """The program's parameter tree, of the reference's per-layer weights."""
    a = ctx.darch
    return {
        "embed": reference_decoder.make_embed(a, ctx.seed),
        "final_norm": reference_decoder.final_norm(a),
        "layers": [
            reference_decoder.make_layer(a, ctx.seed, layer)
            for layer in range(len(a["layer_types"]))
        ],
    }


def build(ctx) -> None:
    # the product's new modules first: a program without them fails here
    from pathway_tpu.models.decoder import AnswerModel, DecoderConfig
    from pathway_tpu.xpacks.llm.llms import TPUChat

    import jax
    import jax.numpy as jnp

    import pathway_tpu as pw
    from pathway_tpu.models import EncoderConfig, SentenceEncoder
    from pathway_tpu.xpacks.llm.embedders import SentenceTransformerEmbedder
    from pathway_tpu.xpacks.llm.question_answering import BaseRAGQuestionAnswerer, RAGClient
    from pathway_tpu.xpacks.llm.servers import BaseRestServer
    from pathway_tpu.xpacks.llm.vector_store import VectorStoreServer

    control_inputs(ctx)
    config, arch, capacity, fill_rows = ctx.config, ctx.arch, ctx.capacity, ctx.fill_rows
    dim = arch["hidden_size"]
    if fill_rows % reference.FILL_BLOCK_ROWS:
        raise BenchmarkError("fill_rows must be whole fill blocks")

    # -- the answer model: published widths, this chip's share ---------------------
    held, serving = config["held"], config["serving"]
    cfg = DecoderConfig.from_hf(
        {**config, **{k: config["published"][k] for k in config["reduced"]}},
        layers=config["num_hidden_layers"], experts_held=tuple(held["experts"]),
        vocab_held=tuple(held["vocab_rows"]), **serving,
    )
    model = AnswerModel(cfg, decoder_params(ctx))
    jax.block_until_ready(model.params)
    ctx.model, ctx.decoder_config = model, cfg
    ctx.chat = TPUChat(model, max_new_tokens=int(ctx.traffic["new_tokens"]))
    ctx.note(phase="weights_made", seconds=round(time.monotonic() - ctx.t0, 2),
             param_bytes=sum(x.nbytes for x in jax.tree_util.tree_leaves(model.params)),
             cache_bytes=sum(x.nbytes for x in jax.tree_util.tree_leaves(model.cache.state)))

    # -- the retrieval plane, as pipelines/vector_store.py builds it ------------------
    encoder = SentenceEncoder(
        EncoderConfig(
            vocab_size=arch["vocab_size"], hidden=dim,
            layers=arch["num_hidden_layers"], heads=arch["num_attention_heads"],
            mlp=arch["intermediate_size"], max_len=arch["max_position_embeddings"],
        ),
        params=ctx.params, batch_size=int(config["encoder_batch_size"]),
    )
    ctx.encoder = encoder
    ctx.tap = vs.Tap()
    vs._tap_encoder(encoder, ctx.tap)
    ctx.answer_tap = AnswerTap()
    _tap_model(model, ctx.answer_tap, ctx.tap)
    embedder = SentenceTransformerEmbedder(
        encoder=encoder, batch_size=int(config["encoder_batch_size"])
    )
    ctx.feed = queue.Queue()

    class Corpus(pw.io.python.ConnectorSubject):
        _deletions_enabled = False

        def run(self):
            while True:
                rows = ctx.feed.get()
                self.next_batch(rows)
                self.commit()

    class DocSchema(pw.Schema):
        data: str
        _metadata: pw.Json

    table = pw.io.python.read(Corpus(), schema=DocSchema, autocommit_duration_ms=None)
    store = VectorStoreServer(
        table, embedder=embedder, index_params={"reserved_space": capacity}
    )
    rag = BaseRAGQuestionAnswerer(
        llm=ctx.chat, indexer=store, search_topk=int(ctx.traffic["k"]))
    port = vs._free_port()
    server = BaseRestServer(
        "127.0.0.1", port, window_ms=float(ctx.traffic.get("gateway_window_ms", 25.0)),
    )
    server.serve("/v2/answer", rag.AnswerQuerySchema, rag.answer_query)
    server.serve("/v1/statistics", rag.StatisticsQuerySchema, rag.statistics,
                 methods=("GET", "POST"))
    ctx.server_thread = server.run(threaded=True)
    ctx.client = lambda: RAGClient(host="127.0.0.1", port=port, timeout=180)
    probe = ctx.client()

    def file_count():
        try:
            return probe.statistics()["file_count"]
        except ConnectionError:
            return None

    ctx.file_count = file_count
    wait_until(lambda: file_count() is not None, 180, "gateway up", ctx.server_thread, 0.05)
    routes = {r[0]: r[2].__self__ for r in server.webserver._routes}
    ctx.retrieve = routes["/v2/answer"]
    ctx.adapter = adapter = vs._find_adapter()
    ctx.shard = shard = adapter.shard
    vs._span_adapter(adapter)
    if shard.capacity != capacity or shard.dimension != dim:
        raise BenchmarkError(
            f"index is {shard.capacity} x {shard.dimension}, the configuration "
            f"says {capacity} x {dim}"
        )
    ctx.note(phase="server_up", seconds=round(time.monotonic() - ctx.t0, 2), port=port)

    rows = reference.FILL_BLOCK_ROWS
    for b in range(fill_rows // rows):
        keys = range(-1 - b * rows, -1 - (b + 1) * rows, -1)
        shard.add(keys, reference.fill_block(ctx.seed, b, dim))
    jax.block_until_ready(shard.vectors)
    check_index(ctx, fill_rows)
    if shard.vectors.nbytes != capacity * dim * 4 or shard.vectors.dtype != jnp.float32:
        raise BenchmarkError("index vectors are not capacity x dim float32")
    ctx.note(phase="filled", seconds=round(time.monotonic() - ctx.t0, 2),
             fill_rows=fill_rows, vector_bytes=int(shard.vectors.nbytes))


def free_index(ctx) -> None:
    """Give the device's memory back before the reference runs: the
    index, the answer model's weights and its cache."""
    import jax

    vs.free_index(ctx)
    model = ctx.model
    for leaf in jax.tree_util.tree_leaves((model.params, model.cache.state)):
        leaf.delete()
