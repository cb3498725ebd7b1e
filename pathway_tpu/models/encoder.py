"""BERT-class sentence encoder in Flax — the framework's flagship model.

TPU-native replacement for the reference's SentenceTransformerEmbedder
(/root/reference/python/pathway/xpacks/llm/embedders.py:270 — torch
sentence-transformers, one string per call, `device=` param). Differences
that matter on TPU:

* whole logical-time batches are encoded in one jitted call (the ≥10k docs/s
  lever, SURVEY §7 stage 4) instead of one string per UDF call;
* every dispatch is padded onto one small ladder of shapes: widths double
  from 32 to ``max_len`` (``seq_bucket``: 32, 64, 128, 256, 512), rows are
  a power of two from 8. A call that one rung holds is one dispatch; a
  longer one is ordered by length and cut into the members
  ``encoder_group_shapes`` enumerates (64 x 32, 64 x 64, 32 x 128,
  16 x 256, 8 x 512 at the published sizes: 4,096 tokens a dispatch), so
  a heavy-tailed call is not one slab as wide as its longest document,
  XLA compiles one executable a shape, once, and none is keyed by a
  call's real row count (padded rows come back and are dropped on the
  host);
* activations in bfloat16 (MXU native), accumulation and outputs f32;
* mean-pool + L2-normalize pooling, bge-style.

Default geometry matches bge-small-en-v1.5 (384 hidden / 12 layers / 12
heads); weights are random unless loaded from a local checkpoint.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Sequence

import numpy as np

import jax
import jax.numpy as jnp
import flax.linen as nn

from pathway_tpu.internals import flight as _flight
from pathway_tpu.internals.device import (
    PLANE as _DEVICE,
    batch_bucket,
    compiled_cost,
    device_site,
    encoder_bucket,
    encoder_call_groups,
    nbytes_of,
    place_compile_cache,
    seq_bucket,
)
from pathway_tpu.models.tokenizer import get_tokenizer

place_compile_cache()


@dataclasses.dataclass(frozen=True)
class EncoderConfig:
    vocab_size: int = 30522
    hidden: int = 384
    layers: int = 12
    heads: int = 12
    mlp: int = 1536
    max_len: int = 512
    dtype: Any = jnp.bfloat16  # activation dtype; params stay f32

    @classmethod
    def bge_small(cls) -> "EncoderConfig":
        return cls()

    @classmethod
    def bge_base(cls) -> "EncoderConfig":
        return cls(hidden=768, layers=12, heads=12, mlp=3072)

    @classmethod
    def tiny(cls) -> "EncoderConfig":
        """Test/dry-run geometry: tiny but structurally identical."""
        return cls(vocab_size=512, hidden=64, layers=2, heads=4, mlp=128, max_len=64)


class _Block(nn.Module):
    config: EncoderConfig

    @nn.compact
    def __call__(self, x, mask):
        cfg = self.config
        attn_out = nn.MultiHeadDotProductAttention(
            num_heads=cfg.heads,
            qkv_features=cfg.hidden,
            dtype=cfg.dtype,
            name="attention",
        )(x, x, mask=mask)
        x = nn.LayerNorm(dtype=cfg.dtype, name="ln_attn")(x + attn_out)
        h = nn.Dense(cfg.mlp, dtype=cfg.dtype, name="mlp_in")(x)
        # erf-based gelu: HF BERT uses the exact form; the approximate tanh
        # form drifts ~1e-3 and breaks checkpoint parity
        h = nn.gelu(h, approximate=False)
        h = nn.Dense(cfg.hidden, dtype=cfg.dtype, name="mlp_out")(h)
        x = nn.LayerNorm(dtype=cfg.dtype, name="ln_mlp")(x + h)
        return x


# One traced body for the twelve layers: under ``nn.jit`` a block is
# traced and lowered once a shape and called ``layers`` times (XLA
# inlines the calls; parameters and results are what they were). A
# process pays tracing for every shape it meets, from a warm compile
# cache too: 0.7-1.1 s a shape unrolled, 0.15 s so (PERF.md section 6,
# PR 29).
_TracedOnceBlock = nn.jit(_Block)


class TransformerEncoder(nn.Module):
    """Token ids + mask -> L2-normalized sentence embeddings [n, hidden]."""

    config: EncoderConfig

    @nn.compact
    def __call__(self, ids, mask):
        cfg = self.config
        n, L = ids.shape
        tok = nn.Embed(cfg.vocab_size, cfg.hidden, dtype=cfg.dtype, name="tok_embed")(ids)
        pos = nn.Embed(cfg.max_len, cfg.hidden, dtype=cfg.dtype, name="pos_embed")(
            jnp.arange(L)[None, :]
        )
        # single-segment encoding: BERT's token_type embedding collapses to
        # one learned row added everywhere (kept as a 2-row table so HF
        # checkpoints load losslessly)
        typ = nn.Embed(2, cfg.hidden, dtype=cfg.dtype, name="type_embed")(
            jnp.zeros((1, 1), jnp.int32)
        )
        x = nn.LayerNorm(dtype=cfg.dtype, name="ln_embed")(tok + pos + typ)
        attn_mask = nn.make_attention_mask(mask, mask, dtype=cfg.dtype)
        for i in range(cfg.layers):
            x = _TracedOnceBlock(cfg, name=f"block_{i}")(x, attn_mask)
        # mean pool over valid tokens, then L2 normalize (bge pooling)
        m = mask[:, :, None].astype(jnp.float32)
        x = x.astype(jnp.float32)
        pooled = jnp.sum(x * m, axis=1) / jnp.maximum(jnp.sum(m, axis=1), 1.0)
        norm = jnp.linalg.norm(pooled, axis=-1, keepdims=True)
        return pooled / jnp.maximum(norm, 1e-9)


def reference_forward(params, cfg: EncoderConfig, ids, mask):
    """Plain float32 ``jax.numpy`` forward of :class:`TransformerEncoder`
    — no Flax, no bf16, one explicit equation per layer. The oracle the
    bf16 device path is compared against (tests at the tiny width on the
    CPU, ``chip_smoke.py`` at the published width with this function run
    on ``jax.devices("cpu")``). Same parameter tree as the Flax module."""
    f32 = jnp.float32
    hi = jax.lax.Precision.HIGHEST

    def ln(x, p):
        mu = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
        return (x - mu) * jax.lax.rsqrt(var + 1e-6) * p["scale"] + p["bias"]

    ids = jnp.asarray(ids, jnp.int32)
    mask = jnp.asarray(mask, jnp.int32)
    L = ids.shape[1]
    x = (
        jnp.asarray(params["tok_embed"]["embedding"], f32)[ids]
        + jnp.asarray(params["pos_embed"]["embedding"], f32)[None, :L]
        + jnp.asarray(params["type_embed"]["embedding"], f32)[0]
    )
    x = ln(x, params["ln_embed"])
    keep = (mask[:, None, :, None] * mask[:, None, None, :]) > 0  # [n,1,q,k]
    scale = (cfg.hidden // cfg.heads) ** -0.5
    for i in range(cfg.layers):
        p = params[f"block_{i}"]
        a = p["attention"]
        q, k, v = (
            jnp.einsum("nld,dhe->nlhe", x, a[w]["kernel"], precision=hi)
            + a[w]["bias"]
            for w in ("query", "key", "value")
        )
        s = jnp.einsum("nqhe,nkhe->nhqk", q * scale, k, precision=hi)
        s = jnp.where(keep, s, jnp.finfo(f32).min)
        w = jax.nn.softmax(s, axis=-1)
        ctx = jnp.einsum("nhqk,nkhe->nqhe", w, v, precision=hi)
        attn = (
            jnp.einsum("nqhe,hed->nqd", ctx, a["out"]["kernel"], precision=hi)
            + a["out"]["bias"]
        )
        x = ln(x + attn, p["ln_attn"])
        h = jnp.dot(x, p["mlp_in"]["kernel"], precision=hi) + p["mlp_in"]["bias"]
        h = jax.nn.gelu(h, approximate=False)
        h = jnp.dot(h, p["mlp_out"]["kernel"], precision=hi) + p["mlp_out"]["bias"]
        x = ln(x + h, p["ln_mlp"])
    m = mask[:, :, None].astype(f32)
    pooled = jnp.sum(x * m, axis=1) / jnp.maximum(jnp.sum(m, axis=1), 1.0)
    norm = jnp.linalg.norm(pooled, axis=-1, keepdims=True)
    return pooled / jnp.maximum(norm, 1e-9)


def forward_flops_per_token(cfg: EncoderConfig, seq_len: int) -> float:
    """Model FLOPs one padded token costs in a forward pass (the MFU
    denominator's numerator): per layer, QKV projections 6h², attention
    scores + weighted values 4·L·h, output projection 2h², and the MLP
    pair 4·h·mlp. Embedding lookups, layernorms and pooling are O(h) and
    omitted (<1% at these geometries). Pinned against XLA's own cost
    analysis in tests/test_bench_flops.py."""
    h, m = cfg.hidden, cfg.mlp
    per_layer = 8.0 * h * h + 4.0 * h * m + 4.0 * seq_len * h
    return cfg.layers * per_layer


def encoder_param_bytes(cfg: EncoderConfig) -> float:
    """HBM bytes of the f32 parameter set (embedding tables + per-layer
    attention/MLP weights) — shared by the forward cost model's traffic
    estimate and the Device Doctor's static HBM budget (ISSUE 20)."""
    h, m = cfg.hidden, cfg.mlp
    return 4.0 * (
        cfg.vocab_size * h + cfg.max_len * h
        + cfg.layers * (4.0 * h * h + 2.0 * h * m)
    )


def forward_cost_model(
    cfg: EncoderConfig, n: int, seq_len: int
) -> tuple[float, float]:
    """Analytical ``(flops, hbm_bytes_accessed)`` of one padded forward
    batch — the device plane's fallback when the compiled executable's
    ``cost_analysis()`` is unavailable. FLOPs: the per-token model above
    times the padded token count. Bytes: one read of the f32 parameter
    set (weights dominate HBM traffic at serving batch sizes) plus a
    few bf16 activation passes per layer."""
    flops = forward_flops_per_token(cfg, seq_len) * n * seq_len
    h = cfg.hidden
    act_b = 2.0 * n * seq_len * h * cfg.layers * 4.0
    return flops, encoder_param_bytes(cfg) + act_b


# shared-bucket aliases (ISSUE 20): the padding the jit sees and the shape
# set the Device Doctor's retrace audit enumerates are the SAME functions
# (internals/device.py) — tests pin these identities so they cannot drift
_bucket = batch_bucket
_seq_bucket = seq_bucket

device_site(
    "encoder.forward",
    cost_model=forward_cost_model,
    dtypes=("uint16", "int32", "float32", "bfloat16"),
    where="pathway_tpu/models/encoder.py:SentenceEncoder.encode_tokens_device",
    description="jitted sentence-encoder forward "
                "(pow2 batch x doubling-width seq buckets)",
)


def pad_batch(ids: np.ndarray, mask: np.ndarray, max_len: int, batch_cap: int):
    """Pad (ids, mask) to the bounded (batch, seq) shape set jit relies
    on: pow2 batch buckets x ``seq_bucket``'s ladder of widths. Returns
    (ids_p, mask_p, n_valid_rows)."""
    n, L = ids.shape
    Lb = _seq_bucket(L, max_len)
    nb = _bucket(n, 8, batch_cap)
    if n > nb:
        raise ValueError(f"batch of {n} exceeds batch capacity {batch_cap}")
    ids_p = np.zeros((nb, Lb), np.int32)
    mask_p = np.zeros((nb, Lb), np.int32)
    L_eff = min(L, Lb)
    ids_p[:n, :L_eff] = ids[:, :L_eff]
    mask_p[:n, :L_eff] = mask[:, :L_eff]
    return ids_p, mask_p, n


class SentenceEncoder:
    """Host-facing batched encoder: list[str] -> np.ndarray [n, hidden]."""

    def __init__(
        self,
        config: EncoderConfig | None = None,
        *,
        checkpoint: str | None = None,
        tokenizer_path: str | None = None,
        seed: int = 0,
        batch_size: int = 256,
        params: Any = None,
    ):
        tokenizer = None
        if checkpoint is not None and params is None:
            # Real HF weights when the checkpoint resolves offline (e.g.
            # "BAAI/bge-small-en-v1.5" in a populated HF cache); falls back
            # to random init + the trained WordPiece vocab otherwise.
            try:
                from pathway_tpu.models.hf_loader import load_bert_encoder
                from pathway_tpu.models.tokenizer import _HFTokenizerAdapter

                config, params, hf_tok = load_bert_encoder(checkpoint)
                tokenizer = _HFTokenizerAdapter(hf_tok, config.max_len)
            except OSError:
                # checkpoint not in the local HF cache (zero-egress hosts):
                # random init + trained WordPiece vocab. Any other exception
                # is a real loader/geometry bug and must surface.
                pass
        self.config = config or EncoderConfig.bge_small()
        self.tokenizer = tokenizer or get_tokenizer(
            tokenizer_path,
            vocab_size=self.config.vocab_size,
            max_length=self.config.max_len,
        )
        self.model = TransformerEncoder(self.config)
        self.batch_size = batch_size
        if params is None:
            rng = jax.random.PRNGKey(seed)
            ids = jnp.zeros((1, 8), jnp.int32)
            mask = jnp.ones((1, 8), jnp.int32)
            # one executable instead of one per initializer op: un-jitted,
            # init runs ~100 tiny op-by-op compiles before the first
            # document, none of which the persistent cache keeps
            params = jax.jit(self.model.init)(rng, ids, mask)["params"]
        self.params = params
        self._forward = jax.jit(
            lambda params, ids, mask: self.model.apply({"params": params}, ids, mask)
        )
        # compact-transfer variant: ids ride as uint16 (vocab < 2^16) and
        # the contiguous-prefix mask as per-row lengths, rebuilt on
        # device: ~4x fewer host->device bytes per batch.
        self._forward_compact = jax.jit(
            lambda params, ids_u16, lengths: self.model.apply(
                {"params": params},
                ids_u16.astype(jnp.int32),
                (
                    jnp.arange(ids_u16.shape[1], dtype=jnp.int32)[None, :]
                    < lengths[:, None]
                ).astype(jnp.int32),
            )
        )
        # shape-bucket → dispatch-fn cache (ISSUE 16): the (batch, seq,
        # compact) bucket resolves its jitted callable ONCE; a key that
        # was never seen is — by jit's own cache discipline — a fresh
        # XLA compilation, counted on device_recompiles_total so a
        # silent recompile storm (shape-bucket leak) is visible on the
        # TUI/cluster view instead of only as wall time.
        self._compiled: dict[tuple, Any] = {}

    @property
    def embed_dim(self) -> int:
        return self.config.hidden

    def encode(self, texts: Sequence[str]) -> np.ndarray:
        """Embeddings in arrival order. The call's rows are ordered by
        length and cut into the dispatches ``encoder_call_groups`` names
        (a short call is one, at ``pad_batch``'s shape); every group is
        queued, with its copy back, before the one wait, so the host lays
        out group k+1 while the device runs group k."""
        texts = list(texts)
        if not texts:
            return np.zeros((0, self.config.hidden), np.float32)
        with _flight.span("encoder.encode", texts=len(texts)) as call:
            ids, mask = self._tokenize(texts)
            held = mask != 0
            # a row's extent: one past its last real token (its length,
            # for the contiguous masks the tokenizers make)
            extents = np.where(
                held.any(axis=1),
                mask.shape[1] - np.argmax(held[:, ::-1], axis=1), 0,
            )
            order = np.argsort(-extents, kind="stable")
            groups = encoder_call_groups(
                extents[order], self.batch_size, self.config.max_len
            )
            queued = []
            for first, stop, rows, width in groups:
                picked = order[first:stop]
                longest = min(int(extents[picked[0]]), width)
                if len(groups) == 1:
                    g_ids, g_mask = ids[picked, :longest], mask[picked, :longest]
                else:
                    # at its member's shape already: encode_tokens_device
                    # has no word for it, and pads what it is given to
                    # the bucket of its own rows and width
                    g_ids = np.zeros((rows, width), np.int32)
                    g_mask = np.zeros((rows, width), np.int32)
                    g_ids[:len(picked), :longest] = ids[picked, :longest]
                    g_mask[:len(picked), :longest] = mask[picked, :longest]
                emb = self.encode_tokens_device(g_ids, g_mask)
                queue_copy = getattr(emb, "copy_to_host_async", None)
                if queue_copy is not None:
                    queue_copy()
                queued.append((picked, emb))
            call.args["groups"] = len(groups)
            call.args["padded"] = sum(rows * width for _, _, rows, width in groups)
            with _flight.span("encoder.wait"):
                # np.asarray below blocks on forward and copy alike; the
                # wait between only tells the device's time from the
                # copy's. (A wait BEFORE the copies are queued costs a
                # host round trip a dispatch: 0.4 ms, 13% on the serve
                # tail; PERF.md section 6.)
                jax.block_until_ready([emb for _, emb in queued])
            out = np.empty((len(texts), self.config.hidden), np.float32)
            with _flight.span("encoder.d2h") as sp:
                moved = 0
                for picked, emb in queued:
                    # padded rows come back with the real ones: a slice
                    # on the device is an executable per row count
                    host = np.asarray(emb, np.float32)
                    moved += int(host.nbytes)
                    out[picked] = host[:len(picked)]
                sp.args["bytes"] = moved
        return out

    def _tokenize(self, texts: list):
        with _flight.span("encoder.tokenize", texts=len(texts)) as sp:
            ids, mask = self.tokenizer(texts)
            sp.args["tokens"] = int(mask.sum())
        return ids, mask

    def encode_tokens_device(self, ids: np.ndarray, mask: np.ndarray):
        """Device-encode a pre-tokenized batch (async-dispatched) — the
        shared padding+forward core. Lets a tokenize-ahead thread overlap
        host tokenization of batch N+1 with device compute / transfers of
        batch N — the ingest-throughput lever. Returns the bucket's
        rows, the batch's first: a slice here would be one more
        executable per (bucket, row count)."""
        with _flight.span(
            "encoder.pad", rows=int(ids.shape[0]), longest=int(ids.shape[1])
        ) as sp:
            ids_p, mask_p, n = pad_batch(
                ids, mask, self.config.max_len, self.batch_size
            )
            # compact transfer when the mask is a contiguous prefix
            # (wordpiece and HF padders both produce this) and ids fit
            # uint16
            lengths = mask_p.sum(axis=1, dtype=np.int32)
            contiguous = bool(
                (mask_p.cumsum(axis=1)[np.arange(len(lengths)), lengths - 1]
                 == lengths).all()
            ) if mask_p.shape[1] else True
            compact = contiguous and self.config.vocab_size <= 65536
            nb_, Lb = ids_p.shape
            sp.args["padded"] = nb_ * Lb
        bucket = encoder_bucket(nb_, Lb, compact)
        fn = self._compiled.get(bucket)
        first = fn is None
        if first:
            # first sighting of this shape bucket: jit will lower+compile
            # a fresh executable on the call below — count it (ISSUE 16)
            fn = self._compiled[bucket] = (
                self._forward_compact if compact else self._forward
            )
            _DEVICE.note_recompile("encoder.forward")
        with _flight.span("encoder.h2d") as sp:
            if compact:
                args = (
                    self.params,
                    jnp.asarray(ids_p.astype(np.uint16)),
                    jnp.asarray(lengths),
                )
            else:
                args = (self.params, jnp.asarray(ids_p), jnp.asarray(mask_p))
            sp.args["bytes"] = nbytes_of(args[1], args[2])
        real_tokens = int(np.sum(lengths[:n], dtype=np.int64))
        # the dispatch: always its ring span; armed (ISSUE 15), also one
        # timed record — FLOPs/bytes from the compiled executable's
        # cost_analysis() (cached per (geometry, shape bucket); the
        # analytical model is the fallback), transfer bytes from the
        # wire arrays, a block on the embeddings (an armed run trades
        # the tokenize-ahead overlap for attribution)
        dev = _DEVICE.begin(
            "encoder.forward", bucket=f"{nb_}x{Lb}", real_tokens=real_tokens,
            padded_tokens=nb_ * Lb, first=first,
        )
        try:
            emb = fn(*args)
        except BaseException:
            # close the record on the failure path (an abandoned record
            # leaks dispatch-queue depth)
            _DEVICE.end(dev, None, block=False)
            raise
        cfg = self.config
        key = (
            "encoder", cfg.hidden, cfg.layers, cfg.mlp,
            cfg.vocab_size, nb_, Lb, compact,
        )
        # cost_fn runs after end() stamps the wall span: the first
        # call per shape bucket pays an AOT lower+compile that must
        # not read as host-assembly time in the dispatch record.
        # Effective share: real tokens over padded tokens — the
        # bucket-padding waste the effective-MFU gauge exposes.
        _DEVICE.end(
            dev, emb,
            transfer_bytes=nbytes_of(args[1], args[2], emb),
            cost_fn=lambda: compiled_cost(
                key, fn, args, forward_cost_model(cfg, nb_, Lb)
            ),
            effective_share=real_tokens / float(nb_ * Lb),
        )
        return emb

    def __call__(self, texts: Sequence[str]) -> np.ndarray:
        return self.encode(texts)
