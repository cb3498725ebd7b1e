"""Top-k primitives: masked, chunked and mergeable.

Scoring a query batch against a large vector shard must not materialize the
full [n_queries, capacity] score matrix in HBM; we score in chunks and merge
partial top-k results. The same merge is the tree-reduction step for global
top-k across mesh shards (each chip's partial top-k is exchanged and merged —
the retrieval analog of ring attention's partial-softmax merge; SURVEY §5).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from pathway_tpu.internals.device import place_compile_cache

place_compile_cache()

# plain float: a module-scope jnp.float32() would jit a
# convert_element_type at IMPORT time (slow, and it drags XLA compilation
# into processes that only need the relational plane — e.g. the ASan CI
# lane, where jaxlib's C++ exceptions abort under the preloaded runtime);
# jnp.where/jnp.full coerce it to the array dtype exactly the same way
NEG_INF = float("-inf")


def masked_topk(scores: jax.Array, valid: jax.Array, k: int):
    """Top-k of `scores` [..., n] where `valid` [..., n] (bool) gates entries.

    Returns (values [..., k], indices [..., k]); invalid entries score -inf,
    so callers must treat -inf results as missing.
    """
    scores = jnp.where(valid, scores, NEG_INF)
    return jax.lax.top_k(scores, k)


def merge_topk(vals_a, idx_a, vals_b, idx_b, k: int):
    """Merge two partial top-k results (values desc) into one top-k.

    Index tensors may carry global ids (int32/int64); ties broken by source
    order (a first) which keeps the merge deterministic.
    """
    vals = jnp.concatenate([vals_a, vals_b], axis=-1)
    idx = jnp.concatenate([idx_a, idx_b], axis=-1)
    top_vals, pos = jax.lax.top_k(vals, k)
    top_idx = jnp.take_along_axis(idx, pos, axis=-1)
    return top_vals, top_idx


def tree_merge_topk(vals, idx, k: int, axis: str, axis_size: int):
    """Global top-k across a pow2 mesh axis by recursive doubling —
    the psum-style merge for the pod-sharded index (ISSUE 16, SURVEY
    §5): log2(n) ``ppermute`` exchange+merge rounds over ICI instead of
    one all_gather of every shard's partials. Each round ships 2·q·k
    values per link (vs (n-1)·q·k for the gather at the root), so the
    merge cost stays flat as the pod grows.

    Must run inside ``shard_map`` over ``axis``; vals/idx are one
    shard's partial top-k [q, k] (values desc). Ties at each merge are
    broken lower-rank-first (the XOR pairing keeps rank order inside
    every butterfly pair), matching the gather merge's shard-0-first
    order. Returns the REPLICATED global top-k — the butterfly is an
    all-reduce, every shard ends with the same answer.
    """
    me = jax.lax.axis_index(axis)
    step = 1
    while step < axis_size:
        perm = [(i, i ^ step) for i in range(axis_size)]
        other_vals = jax.lax.ppermute(vals, axis, perm)
        other_idx = jax.lax.ppermute(idx, axis, perm)
        # lower rank of the pair contributes first so top_k's stable
        # positional tie-break resolves by shard order, like the gather
        low = (me & step) == 0
        a_vals = jnp.where(low, vals, other_vals)
        a_idx = jnp.where(low, idx, other_idx)
        b_vals = jnp.where(low, other_vals, vals)
        b_idx = jnp.where(low, other_idx, idx)
        vals, idx = merge_topk(a_vals, a_idx, b_vals, b_idx, k)
        step *= 2
    return vals, idx


_SCORES_BUDGET_BYTES = 1 << 28  # 256 MB of f32 scores per block


def auto_chunk(cap: int, n_queries: int) -> int:
    """Largest pow2 block whose [q, chunk] f32 score matrix fits the budget.

    Small fixed chunks serialize the scan into latency-bound steps (a 1M-row
    index in 8192-row blocks is 128 sequential tiny matmuls ≈ 100+ ms); one
    block per ~256 MB keeps the MXU busy and the merge tree shallow.
    """
    rows = max(8192, _SCORES_BUDGET_BYTES // (4 * max(n_queries, 1)))
    b = 8192
    while b * 2 <= rows:
        b *= 2
    return min(b, cap)


def chunked_topk_scores(
    queries: jax.Array,   # [q, d] f32
    database: jax.Array,  # [cap, d] f32
    valid: jax.Array,     # [cap] bool
    k: int,
    *,
    chunk: int | None = None,
    sq_norms: jax.Array | None = None,  # [cap] f32, for l2 metric
    metric: str = "dot",
    precision: str = "highest",
):
    """Score queries against the database and return top-k per query.

    metric:
      - "dot": plain inner product (cos if inputs are pre-normalized)
      - "l2sq": negated squared L2 distance (so larger is better)

    precision: "highest" = exact f32 scores (reference parity — its brute
    force index is exact f64, brute_force_knn_integration.rs:150); "default"
    = backend-native fast path (bf16 MXU passes on TPU) for latency-bound
    serving where ~1e-3 score error is acceptable.

    The database is scanned in `chunk`-row blocks; per-block top-k results
    are merged, keeping peak memory at O(q * chunk) instead of O(q * cap).
    XLA fuses the matmul (MXU, bf16-friendly) with the masking per block.
    """
    q, d = queries.shape
    cap = database.shape[0]
    if chunk is None:
        chunk = auto_chunk(cap, q)
    if cap <= chunk:
        scores = _block_scores(queries, database, sq_norms, metric, precision)
        return masked_topk(scores, valid[None, :], k)

    n_blocks = cap // chunk
    assert cap % chunk == 0, "capacity must be a multiple of chunk"

    db_blocks = database.reshape(n_blocks, chunk, d)
    valid_blocks = valid.reshape(n_blocks, chunk)
    sq_blocks = (
        sq_norms.reshape(n_blocks, chunk) if sq_norms is not None else None
    )

    def body(carry, block):
        best_vals, best_idx = carry
        if sq_blocks is not None:
            db, vmask, sq, base = block
        else:
            db, vmask, base = block
            sq = None
        scores = _block_scores(queries, db, sq, metric, precision)
        vals, idx = masked_topk(scores, vmask[None, :], k)
        idx = idx.astype(jnp.int32) + base
        best_vals, best_idx = merge_topk(best_vals, best_idx, vals, idx, k)
        return (best_vals, best_idx), None

    init = (
        jnp.full((q, k), NEG_INF, dtype=jnp.float32),
        jnp.zeros((q, k), dtype=jnp.int32),
    )
    bases = (jnp.arange(n_blocks, dtype=jnp.int32) * chunk)
    xs = (
        (db_blocks, valid_blocks, sq_blocks, bases)
        if sq_blocks is not None
        else (db_blocks, valid_blocks, bases)
    )
    (vals, idx), _ = jax.lax.scan(body, init, xs)
    return vals, idx


def topk_scan_cost(
    q: int, cap: int, d: int, k: int
) -> tuple[float, float]:
    """Analytical ``(flops, hbm_bytes_accessed)`` of one chunked top-k
    scan — the device plane's fallback cost model when the compiled
    executable's own ``cost_analysis()`` is unavailable or too costly
    to obtain (re-lowering the 1M-row scan just for bookkeeping would
    compile a second executable; internals/device.py compiled_cost).

    FLOPs: the [q, cap] score matmul dominates (2·q·cap·d MACs); the
    per-block mask/compare/merge passes add ~3 ops per score. Bytes:
    one full database read (the scan streams every block from HBM
    exactly once), the query tile, validity mask + sq_norms, and the
    [q, k] result pair — per-block score tiles live in VMEM and never
    touch HBM, which is the point of the chunked design.

    This counts PADDED work: `q` is the pow2-padded query batch, `cap`
    the pow2 capacity including dead slots — what the hardware
    executed. For the effective (real-rows) number ISSUE 16's honest
    MFU reports, call it again with the real query count and live row
    count; the dispatch sites pass both to the device plane.
    """
    flops = 2.0 * q * cap * d + 3.0 * q * cap
    bytes_accessed = (
        4.0 * cap * d      # database blocks, streamed once
        + 4.0 * q * d      # query tile
        + cap              # validity mask (bool)
        + 4.0 * cap        # sq_norms (l2 metric; ~free for dot)
        + 8.0 * q * k      # merged (values, indices) result
    )
    return flops, bytes_accessed


def _block_scores(queries, db_block, sq_norms_block, metric, precision="highest"):
    scores = jnp.dot(
        queries, db_block.T,
        preferred_element_type=jnp.float32,
        precision=precision,
    )
    if metric == "l2sq":
        qn = jnp.sum(queries * queries, axis=-1, keepdims=True)
        scores = 2.0 * scores - qn - sq_norms_block[None, :]
    return scores
