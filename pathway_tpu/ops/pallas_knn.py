"""Pallas TPU kernel: fused KNN scoring — matmul + running top-k.

Single pass over the database shard in VMEM-sized blocks: each grid step
computes a [Q, BLOCK] score tile on the MXU and folds it into a running
[Q, K] top-k held in VMEM scratch, so the full [Q, capacity] score matrix
never exists in HBM. This is the TPU replacement for the reference's
batched `index.dot(query)` + k_smallest loop
(/root/reference/src/external_integration/brute_force_knn_integration.rs:150-214),
which bounds memory by query-batching instead; we bound it by db-blocking,
which keeps query batches intact for the MXU.

Top-k inside the kernel is K-step selection (max + mask-out), K static and
small; `jax.lax.top_k` does not lower inside Pallas TPU kernels. Every
array the kernel touches is 2-D with a lane (last) dimension that is a
multiple of 128, which is what Mosaic lowers: the running top-k is held
K-padded to ``_LANES``, the candidate tile is the lane-aligned
concatenation [running | block], each step's winner is located with
max + first-matching-lane (no lane argmax) and written back with a lane
select (no stacking of 1-D vectors).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from pathway_tpu.internals.device import (
    PLANE as _DEVICE,
    device_site,
    pallas_bucket,
)

NEG_INF = float("-inf")
_LANES = 128
_SUBLANES = 8


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _knn_kernel(q_ref, db_ref, mask_ref, out_v_ref, out_i_ref, sv_ref, si_ref,
                *, k: int, block: int):
    j = pl.program_id(0)
    nb = pl.num_programs(0)

    @pl.when(j == 0)
    def _init():
        sv_ref[:] = jnp.full(sv_ref.shape, NEG_INF, jnp.float32)
        si_ref[:] = jnp.zeros(si_ref.shape, jnp.int32)

    # [Q, D] x [B, D] contracted on D: the transposed-rhs matmul the MXU
    # takes natively, no in-kernel transpose of the database block
    scores = jax.lax.dot_general(
        q_ref[:], db_ref[:],
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    ) + mask_ref[:]                                        # [Q, B]
    q, kp = sv_ref.shape
    col_ids = jax.lax.broadcasted_iota(jnp.int32, (q, block), 1) + j * block

    # running entries first: they come from earlier blocks, so on equal
    # scores the first matching lane is the lower database index — the
    # same tie-break as lax.top_k over the whole row
    cand_v = jnp.concatenate([sv_ref[:], scores], axis=1)  # [Q, KP+B]
    cand_i = jnp.concatenate([si_ref[:], col_ids], axis=1)
    width = kp + block
    lane = jax.lax.broadcasted_iota(jnp.int32, (q, width), 1)
    out_lane = jax.lax.broadcasted_iota(jnp.int32, (q, kp), 1)

    new_v = jnp.full((q, kp), NEG_INF, jnp.float32)
    new_i = jnp.zeros((q, kp), jnp.int32)
    for t in range(k):
        m = jnp.max(cand_v, axis=1, keepdims=True)         # [Q, 1]
        first = jnp.min(
            jnp.where(cand_v == m, lane, width), axis=1, keepdims=True
        )
        hit = lane == first
        sel_i = jnp.sum(jnp.where(hit, cand_i, 0), axis=1, keepdims=True)
        new_v = jnp.where(out_lane == t, m, new_v)
        new_i = jnp.where(out_lane == t, sel_i, new_i)
        cand_v = jnp.where(hit, NEG_INF, cand_v)
    sv_ref[:] = new_v
    si_ref[:] = new_i

    @pl.when(j == nb - 1)
    def _flush():
        out_v_ref[:] = sv_ref[:]
        out_i_ref[:] = si_ref[:]


def pallas_knn_cost(
    q: int, cap: int, d: int, k: int, block: int
) -> tuple[float, float]:
    """Analytical ``(flops, hbm_bytes_accessed)`` of the fused kernel —
    the device plane's cost model for this dispatch site. FLOPs: the
    per-block score matmul (2·q·block·d MACs per grid step = 2·q·cap·d
    total) plus K selection sweeps over the [q, kp+block] candidate tile
    (k padded to the lane width; ~3 ops per candidate per step). Bytes:
    the database streams from HBM once, the query tile re-reads per grid
    step (its BlockSpec maps every step to the same [q, d] tile), and
    the running top-k lives in VMEM scratch — only the final [q, k] pair
    lands back in HBM."""
    nb = max(1, cap // block)
    flops = (
        2.0 * q * cap * d
        + 3.0 * k * q * (_round_up(k, _LANES) + block) * nb
    )
    bytes_accessed = (
        4.0 * cap * d          # database blocks, streamed once
        + 4.0 * q * d * nb     # query tile, re-fetched per grid step
        + 4.0 * cap            # additive validity mask (f32)
        + 8.0 * q * k          # (values, indices) result
    )
    return flops, bytes_accessed


device_site(
    "pallas.topk",
    cost_model=pallas_knn_cost,
    dtypes=("float32", "int32"),
    where="pathway_tpu/ops/pallas_knn.py:pallas_topk_scores",
    description="fused Pallas matmul + running top-k over VMEM blocks",
)

# seen compiled-shape buckets (ISSUE 20): every static-arg/shape combo of
# the pallas_call is one executable; a fresh key ticks
# device_site_recompiles_total so the retrace audit pins honest counters
_SEEN_BUCKETS: set = set()


def pallas_topk_scores(
    queries: jax.Array,    # [Q, D] f32
    database: jax.Array,   # [cap, D] f32
    add_mask: jax.Array,   # [cap] f32 additive (0 valid, -inf invalid)
    *,
    k: int,
    block: int = 1024,
    interpret: bool = False,
):
    """Fused scored top-k: returns (values [Q, k], indices [Q, k]).

    Host wrapper over the jitted kernel: one dispatch hook per call (its
    ring span always, the device plane's timed record when armed)."""
    q, d = queries.shape
    bucket = pallas_bucket(q, database.shape[0], d, k, block, interpret)
    if bucket not in _SEEN_BUCKETS:
        _SEEN_BUCKETS.add(bucket)
        _DEVICE.note_recompile("pallas.topk")
    dev = _DEVICE.begin("pallas.topk", queries=q, k=k)
    try:
        out = _pallas_topk_scores_jit(
            queries, database, add_mask, k=k, block=block,
            interpret=interpret,
        )
    except BaseException:
        _DEVICE.end(dev, None, block=False)
        raise
    flops, acc = pallas_knn_cost(q, database.shape[0], d, k, block)
    _DEVICE.end(dev, out, flops=flops, bytes_accessed=acc)
    return out


@functools.partial(
    jax.jit, static_argnames=("k", "block", "interpret")
)
def _pallas_topk_scores_jit(
    queries: jax.Array,    # [Q, D] f32
    database: jax.Array,   # [cap, D] f32
    add_mask: jax.Array,   # [cap] f32 additive (0 valid, -inf invalid)
    *,
    k: int,
    block: int = 1024,
    interpret: bool = False,
):
    q, d = queries.shape
    cap = database.shape[0]
    if cap % block:
        raise ValueError("capacity must be a multiple of block")
    if block % _LANES and not interpret:
        raise ValueError(
            f"block must be a multiple of {_LANES} lanes on the compiled "
            f"path, got {block}"
        )
    nb = cap // block
    # K padded to a lane multiple, Q to a sublane multiple: the kernel
    # only ever sees (8, 128)-tileable arrays; both pads are sliced off
    kp = _round_up(k, _LANES)
    qp = _round_up(q, _SUBLANES)
    if qp != q:
        queries = jnp.pad(queries, ((0, qp - q), (0, 0)))

    kernel = functools.partial(_knn_kernel, k=k, block=block)
    out_v, out_i = pl.pallas_call(
        kernel,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((qp, d), lambda j: (0, 0)),
            pl.BlockSpec((block, d), lambda j: (j, 0)),
            pl.BlockSpec((1, block), lambda j: (0, j)),
        ],
        out_specs=[
            pl.BlockSpec((qp, kp), lambda j: (0, 0)),
            pl.BlockSpec((qp, kp), lambda j: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((qp, kp), jnp.float32),
            jax.ShapeDtypeStruct((qp, kp), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((qp, kp), jnp.float32),
            pltpu.VMEM((qp, kp), jnp.int32),
        ],
        interpret=interpret,
    )(queries, database, add_mask[None, :])
    return out_v[:q, :k], out_i[:q, :k]
