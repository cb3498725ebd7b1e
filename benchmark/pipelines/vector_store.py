"""The product pipeline of every retrieval cell, as a user builds it:

    seeded ConnectorSubject -> pw.io.python.read -> VectorStoreServer(
        embedder=SentenceTransformerEmbedder(...),
        index_params={"reserved_space": capacity}) -> run_server(threaded=True)
    <- VectorStoreClient over loopback HTTP

``build(ctx)`` starts it, fills the index to the configuration's fill
share from the seed (a restore from a snapshot stands behind that), and
leaves on ``ctx`` what generators, checks and metric readers use:
``feed`` (commits of rows for the connector), ``client()``, ``shard``,
``adapter``, ``encoder``, ``tap``, ``retrieve`` (the gateway subject).
Everything it reads of the program it reads without changing it.
"""

from __future__ import annotations

import gc
import queue
import socket
import time

import reference
from loader import BenchmarkError


ARCH_KEYS = (
    "hidden_size", "num_hidden_layers", "num_attention_heads", "intermediate_size",
    "vocab_size", "max_position_embeddings", "type_vocab_size", "layer_norm_eps",
)


class Tap:
    """What the encoder was asked and what it dispatched, with times."""

    def __init__(self):
        self.phase = "setup"
        self.by_text: dict[str, list] = {}   # question text -> embeddings
        self.keep_texts = False
        self.encodes: list[tuple] = []       # (t0, t1, phase, n_texts)
        self.batches: list[tuple] = []       # (t, phase, rows, longest, real_tokens)
        self.dispatches: list[tuple] = []    # (t, phase, padded_rows, padded_len)



def _tap_encoder(encoder, tap: Tap) -> None:
    import jax.profiler

    inner_encode = encoder.encode
    inner_tokens = encoder.encode_tokens_device

    def encode(texts):
        texts = list(texts)
        t0 = time.monotonic()
        with jax.profiler.TraceAnnotation("bench.encoder.encode"):
            out = inner_encode(texts)
        tap.encodes.append((t0, time.monotonic(), tap.phase, len(texts)))
        if tap.keep_texts:
            for text, emb in zip(texts, out):
                tap.by_text.setdefault(text, []).append(emb)
        return out

    def encode_tokens_device(ids, mask):
        tap.batches.append(
            (time.monotonic(), tap.phase, int(ids.shape[0]), int(ids.shape[1]),
             int(mask.sum()))
        )
        return inner_tokens(ids, mask)

    def recorded(fn):
        def call(params, ids, second):
            tap.dispatches.append(
                (time.monotonic(), tap.phase, int(ids.shape[0]), int(ids.shape[1]))
            )
            return fn(params, ids, second)

        return call

    encoder.encode = encode
    encoder.encode_tokens_device = encode_tokens_device
    encoder._forward = recorded(encoder._forward)
    encoder._forward_compact = recorded(encoder._forward_compact)


def _span_adapter(adapter) -> None:
    """Harness spans around the index adapter's calls (instance
    attributes; the class is untouched)."""
    import jax.profiler

    for name in ("search", "add_batch", "add"):
        inner = getattr(adapter, name)

        def spanned(*args, _inner=inner, _name=name, **kwargs):
            with jax.profiler.TraceAnnotation(f"bench.index.{_name}"):
                return _inner(*args, **kwargs)

        setattr(adapter, name, spanned)


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _find_adapter():
    from pathway_tpu.stdlib.indexing.nearest_neighbors import _KnnAdapter

    found = [o for o in gc.get_objects() if type(o) is _KnnAdapter]
    if len(found) != 1:
        raise BenchmarkError(f"expected one live index adapter, found {len(found)}")
    return found[0]


def wait_until(pred, deadline_s: float, what: str, thread=None, every: float = 0.005):
    end = time.monotonic() + deadline_s
    while True:
        got = pred()
        if got:
            return got
        if thread is not None and not thread.is_alive():
            raise BenchmarkError(f"server thread died while waiting for {what}")
        if time.monotonic() > end:
            raise BenchmarkError(f"timed out after {deadline_s:.0f}s: {what}")
        time.sleep(every)


def control_inputs(ctx) -> None:
    """What the configuration and the seed alone give: sizes, and the
    weights as one jitted call on the device, float32 as served. All that
    ``control.py`` needs, which builds no server."""
    config, index = ctx.config, ctx.config["index"]
    ctx.arch = {key: config[key] for key in ARCH_KEYS}
    ctx.params = reference.make_params(ctx.arch, ctx.seed)
    ctx.capacity = int(index["reserved_space"])
    ctx.fill_rows = int(index["fill_rows"])


def build(ctx) -> None:
    import jax
    import jax.numpy as jnp

    import pathway_tpu as pw
    from pathway_tpu.models import EncoderConfig, SentenceEncoder
    from pathway_tpu.xpacks.llm.embedders import SentenceTransformerEmbedder
    from pathway_tpu.xpacks.llm.vector_store import VectorStoreClient, VectorStoreServer

    control_inputs(ctx)
    config, arch, capacity, fill_rows = ctx.config, ctx.arch, ctx.capacity, ctx.fill_rows
    dim = arch["hidden_size"]
    if fill_rows % reference.FILL_BLOCK_ROWS:
        raise BenchmarkError("fill_rows must be whole fill blocks")

    encoder = SentenceEncoder(
        EncoderConfig(
            vocab_size=arch["vocab_size"], hidden=dim,
            layers=arch["num_hidden_layers"], heads=arch["num_attention_heads"],
            mlp=arch["intermediate_size"], max_len=arch["max_position_embeddings"],
        ),
        params=ctx.params, batch_size=int(config["encoder_batch_size"]),
    )
    ctx.encoder = encoder
    ctx.tap = Tap()
    _tap_encoder(encoder, ctx.tap)
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
    server = VectorStoreServer(
        table, embedder=embedder, index_params={"reserved_space": capacity}
    )
    port = _free_port()
    ctx.server_thread = server.run_server(
        "127.0.0.1", port, threaded=True,
        window_ms=float(ctx.traffic.get("gateway_window_ms", 25.0)),
    )
    ctx.client = lambda: VectorStoreClient(host="127.0.0.1", port=port, timeout=120)
    probe = ctx.client()

    def file_count():
        try:
            return probe.get_vectorstore_statistics()["file_count"]
        except ConnectionError:
            return None

    ctx.file_count = file_count
    wait_until(lambda: file_count() is not None, 180, "gateway up", ctx.server_thread, 0.05)
    ctx.retrieve = server.webserver._routes[0][2].__self__
    ctx.adapter = adapter = _find_adapter()
    ctx.shard = shard = adapter.shard
    _span_adapter(adapter)
    if shard.capacity != capacity or shard.dimension != dim:
        raise BenchmarkError(
            f"index is {shard.capacity} x {shard.dimension}, the configuration "
            f"says {capacity} x {dim}"
        )
    ctx.note(phase="server_up", seconds=round(time.monotonic() - ctx.t0, 2), port=port)

    # the fill: random unit vectors made on the device, equal blocks through
    # KnnShard.add -- keys are negative ints, which the engine (128-bit
    # unsigned row keys) never mints
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


def check_index(ctx, rows: int | None = None) -> None:
    """The index never grows, and holds what it should."""
    shard = ctx.shard
    if shard.capacity != ctx.capacity:
        raise BenchmarkError(
            f"the index grew to {shard.capacity} rows (capacity {ctx.capacity})"
        )
    if rows is not None and len(shard) != rows:
        raise BenchmarkError(f"index holds {len(shard)} rows, expected {rows}")


def doc_rows(ctx, ids) -> "tuple[list[int], object]":
    """(ids found, their stored rows as NumPy) for documents by number."""
    import numpy as np

    slot_of = {}
    for key, meta in ctx.adapter.meta.items():
        slot_of[int(meta.value["path"].split("/")[1])] = ctx.shard.key_to_slot[key]
    found = [i for i in ids if i in slot_of]
    if not found:
        return [], np.zeros((0, ctx.shard.dimension), np.float32)
    import jax.numpy as jnp

    slots = jnp.asarray(np.asarray([slot_of[i] for i in found], np.int32))
    return found, np.asarray(ctx.shard.vectors[slots])


def free_index(ctx) -> None:
    """Give the index's device memory back before the reference runs."""
    for name in ("vectors", "valid", "sq_norms"):
        getattr(ctx.shard, name).delete()


def doc_row(i: int, text: str) -> dict:
    return {"data": text, "_metadata": {"path": f"doc/{i:07d}"}}
