"""The plain reference: WordPiece -> BERT-class encoder -> exact top-k.

Float32, ``jax.numpy``, ``precision=HIGHEST`` on every product, one
equation per layer; imports nothing of ``pathway_tpu``. The weights and
the index fill are made HERE from the seed (``make_params``,
``fill_block``): the harness hands the same arrays to the program, so the
reference takes nothing the program has made.

``precision="fp8"`` is the control (the nearest precision below the
configuration's bf16 activations): every activation the program keeps in
bf16 and both operands of every matrix product are rounded to
float8_e4m3fn under a per-tensor scale; accumulation, layer-norm
statistics, softmax and pooling stay float32, as in the program. ``scan_scores(..., precision="high")`` is the control for the
index scan, which the configuration states as float32 at ``highest``.
"""

from __future__ import annotations

import os
import string

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))
VOCAB_FILE = os.path.join(_HERE, "assets", "wordpiece_vocab.txt")
FILL_BLOCK_ROWS = 131072


# -- tokenizer ------------------------------------------------------------------


class Tokenizer:
    """Lower-cased, punctuation-split, greedy longest-match WordPiece with
    [CLS] ... [SEP], truncated to ``max_len`` (BERT's contract, ASCII
    input: the traffic is made of ASCII vocabulary words)."""

    def __init__(self, max_len: int, vocab_file: str = VOCAB_FILE):
        with open(vocab_file, encoding="utf-8") as f:
            self.vocab = {line.rstrip("\n"): i for i, line in enumerate(f)}
        self.max_len = max_len
        self.unk, self.cls, self.sep = (
            self.vocab["[UNK]"], self.vocab["[CLS]"], self.vocab["[SEP]"]
        )

    def _piece_ids(self, word: str) -> list[int]:
        ids, start = [], 0
        while start < len(word):
            end = len(word)
            while end > start:
                sub = word[start:end] if start == 0 else "##" + word[start:end]
                if sub in self.vocab:
                    break
                end -= 1
            if end == start:
                return [self.unk]
            ids.append(self.vocab[sub])
            start = end
        return ids

    def ids(self, text: str) -> list[int]:
        out = [self.cls]
        for raw in text.lower().split():
            word = ""
            for ch in raw + " ":
                if ch in string.punctuation or ch == " ":
                    if word:
                        out.extend(self._piece_ids(word))
                        word = ""
                    if ch != " ":
                        out.extend(self._piece_ids(ch))
                else:
                    word += ch
        del out[self.max_len - 1:]
        out.append(self.sep)
        return out


# -- weights and fill, from the seed -----------------------------------------------


def _key(seed: int, stream: int):
    import jax

    # --seed may exceed 32 signed bits: fold its two halves in
    key = jax.random.PRNGKey(stream)
    key = jax.random.fold_in(key, seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


def make_params(arch: dict, seed: int):
    """The encoder's float32 parameter tree in one jitted call on the
    device: N(0, 0.02) kernels, embeddings and biases (BERT's
    ``initializer_range``), layer-norm scales 1 + N(0, 0.02)."""
    import jax
    import jax.numpy as jnp

    h, m, heads = arch["hidden_size"], arch["intermediate_size"], arch["num_attention_heads"]
    hd = h // heads
    shapes = {
        ("tok_embed", "embedding"): (arch["vocab_size"], h),
        ("pos_embed", "embedding"): (arch["max_position_embeddings"], h),
        ("type_embed", "embedding"): (arch["type_vocab_size"], h),
        ("ln_embed", "scale"): (h,), ("ln_embed", "bias"): (h,),
    }
    for i in range(arch["num_hidden_layers"]):
        b = f"block_{i}"
        for w in ("query", "key", "value"):
            shapes[(b, "attention", w, "kernel")] = (h, heads, hd)
            shapes[(b, "attention", w, "bias")] = (heads, hd)
        shapes[(b, "attention", "out", "kernel")] = (heads, hd, h)
        shapes[(b, "attention", "out", "bias")] = (h,)
        shapes[(b, "mlp_in", "kernel")] = (h, m)
        shapes[(b, "mlp_in", "bias")] = (m,)
        shapes[(b, "mlp_out", "kernel")] = (m, h)
        shapes[(b, "mlp_out", "bias")] = (h,)
        for ln in ("ln_attn", "ln_mlp"):
            shapes[(b, ln, "scale")] = (h,)
            shapes[(b, ln, "bias")] = (h,)
    paths = sorted(shapes)
    sizes = [int(np.prod(shapes[p])) for p in paths]

    def init(key):
        # one draw, cut into the leaves: one random program, not one a leaf
        flat = 0.02 * jax.random.normal(key, (sum(sizes),), jnp.float32)
        tree: dict = {}
        at = 0
        for path, size in zip(paths, sizes):
            leaf = flat[at:at + size].reshape(shapes[path])
            at += size
            if path[-1] == "scale":
                leaf = 1.0 + leaf
            node = tree
            for part in path[:-1]:
                node = node.setdefault(part, {})
            node[path[-1]] = leaf
        return tree

    return jax.jit(init)(_key(seed, 1))


_FILL_FN = {}


def fill_block(seed: int, block: int, dim: int, rows: int | None = None):
    """Block ``block`` of the index fill: ``rows`` random unit vectors,
    float32, made on the device (one executable for every block)."""
    import jax
    import jax.numpy as jnp

    rows = rows or FILL_BLOCK_ROWS
    fn = _FILL_FN.get((dim, rows))
    if fn is None:
        def make(key, b):
            x = jax.random.normal(jax.random.fold_in(key, b), (rows, dim), jnp.float32)
            return x / jnp.linalg.norm(x, axis=-1, keepdims=True)

        fn = _FILL_FN[(dim, rows)] = jax.jit(make)
    return fn(_key(seed, 2), np.int32(block))


# -- encoder forward ------------------------------------------------------------------


def _fp8(x):
    import jax.numpy as jnp

    scale = 448.0 / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    return (x * scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) / scale


def forward(params, arch: dict, ids, mask, precision: str = "f32"):
    """Token ids + mask [n, L] -> L2-normalized mean-pooled embeddings
    [n, hidden], float32. BERT post-LN block, exact (erf) GELU, the
    configuration's ``layer_norm_eps``, single segment."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    hi = jax.lax.Precision.HIGHEST
    rnd = _fp8 if precision == "fp8" else (lambda x: x)
    eps = arch["layer_norm_eps"]

    def mm(spec, a, b):
        return jnp.einsum(spec, rnd(a), rnd(b), precision=hi)

    def ln(x, p):
        mu = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
        return rnd((x - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"])

    # ``rnd`` is the identity in float32. In the control it rounds every
    # activation the program keeps in bf16 (each op's output, the residual
    # stream included) and both operands of every product to fp8.
    ids = jnp.asarray(ids, jnp.int32)
    mask = jnp.asarray(mask, jnp.int32)
    L = ids.shape[1]
    x = (
        params["tok_embed"]["embedding"][ids]
        + params["pos_embed"]["embedding"][None, :L]
        + params["type_embed"]["embedding"][0]
    )
    x = ln(rnd(x), params["ln_embed"])
    keep = (mask[:, None, :, None] * mask[:, None, None, :]) > 0
    heads = arch["num_attention_heads"]
    scale = (arch["hidden_size"] // heads) ** -0.5
    for i in range(arch["num_hidden_layers"]):
        p = params[f"block_{i}"]
        a = p["attention"]
        q, k, v = (
            rnd(mm("nld,dhe->nlhe", x, a[w]["kernel"]) + a[w]["bias"])
            for w in ("query", "key", "value")
        )
        s = mm("nqhe,nkhe->nhqk", q * scale, k)
        s = jnp.where(keep, s, jnp.finfo(f32).min)
        w = rnd(jax.nn.softmax(s, axis=-1))
        ctx = rnd(mm("nhqk,nkhe->nqhe", w, v))
        attn = rnd(mm("nqhe,hed->nqd", ctx, a["out"]["kernel"]) + a["out"]["bias"])
        x = ln(rnd(x + attn), p["ln_attn"])
        hmid = rnd(mm("nld,dm->nlm", x, p["mlp_in"]["kernel"]) + p["mlp_in"]["bias"])
        hmid = rnd(jax.nn.gelu(hmid, approximate=False))
        hmid = rnd(mm("nlm,md->nld", hmid, p["mlp_out"]["kernel"]) + p["mlp_out"]["bias"])
        x = ln(rnd(x + hmid), p["ln_mlp"])
    m = mask[:, :, None].astype(f32)
    pooled = jnp.sum(x * m, axis=1) / jnp.maximum(jnp.sum(m, axis=1), 1.0)
    return pooled / jnp.maximum(jnp.linalg.norm(pooled, axis=-1, keepdims=True), 1e-9)


_FORWARD_JIT = {}


def embed_texts(params, arch: dict, texts, precision: str = "f32",
                block_tokens: int = 16384) -> np.ndarray:
    """Reference embeddings of ``texts`` [n, hidden] (NumPy float32), in
    blocks of two fixed shapes (padded length 128 or the model's longest;
    ``block_tokens`` tokens a block) so that it compiles two programs at
    most and fits beside nothing else."""
    import jax

    tok = Tokenizer(arch["max_position_embeddings"])
    seqs = [tok.ids(t) for t in texts]
    out = np.zeros((len(texts), arch["hidden_size"]), np.float32)
    cache_key = (precision, tuple(sorted(arch.items())))
    fn = _FORWARD_JIT.get(cache_key)
    if fn is None:
        fn = _FORWARD_JIT[cache_key] = jax.jit(
            lambda p, i, m: forward(p, arch, i, m, precision)
        )
    longest = arch["max_position_embeddings"]
    buckets = [b for b in (128,) if b < longest] + [longest]
    by_bucket: dict[int, list[int]] = {b: [] for b in buckets}
    for i, seq in enumerate(seqs):
        by_bucket[next(b for b in buckets if len(seq) <= b)].append(i)
    for L, members in by_bucket.items():
        rows = max(1, block_tokens // L)
        for at in range(0, len(members), rows):
            take = members[at:at + rows]
            ids = np.zeros((rows, L), np.int32)
            mask = np.zeros((rows, L), np.int32)
            for r, i in enumerate(take):
                ids[r, :len(seqs[i])] = seqs[i]
                mask[r, :len(seqs[i])] = 1
            out[take] = np.asarray(fn(params, ids, mask))[:len(take)]
    return out


# -- exact scan ---------------------------------------------------------------------------


def scan_scores(queries, rows, precision: str = "highest"):
    """[q, d] x [n, d] -> [q, n] float32 inner products on the device."""
    import jax.numpy as jnp

    return jnp.dot(
        jnp.asarray(queries, jnp.float32), jnp.asarray(rows, jnp.float32).T,
        precision=precision, preferred_element_type=jnp.float32,
    )


def fill_topk(queries: np.ndarray, seed: int, n_blocks: int, dim: int, k: int,
              precision: str = "highest") -> np.ndarray:
    """Best ``k`` scores of each query over every fill block [q, k],
    descending: the fill regenerated block by block, never held whole."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def step(best, q, block):
        s = jnp.dot(q, block.T, precision=precision,
                    preferred_element_type=jnp.float32)
        vals, _ = jax.lax.top_k(jnp.concatenate([best, s], axis=1), k)
        return vals

    q = jnp.asarray(queries, jnp.float32)
    best = jnp.full((q.shape[0], k), -jnp.inf, jnp.float32)
    for b in range(n_blocks):
        best = step(best, q, fill_block(seed, b, dim))
    return np.asarray(best)
