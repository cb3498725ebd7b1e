"""Ring attention — sequence/context parallelism for long sequences.

The reference caps model context at what one device's memory holds (its
embedders/LLMs are external services or frozen local torch models). A
TPU-native framework owns the long-context story: attention over a
sequence sharded across a mesh axis, with K/V blocks rotating around the
ring via `jax.lax.ppermute` while a flash-attention-style online softmax
(running max + denominator) accumulates exact results block by block
(Liu et al., Ring Attention; the "How to Scale Your Model" sp recipe).

Memory per device is O(S/P · S/P) per step instead of O(S²); the ring
overlaps compute with neighbor transfers over ICI. The kernel is
expressed with `shard_map` + `lax.scan`, so XLA schedules the collective
permutes; no Python loops survive tracing.

Exactness: results match full single-device attention to numerical
tolerance — pinned by tests/test_ring_attention.py on an 8-device CPU
mesh (the driver's dryrun compiles the same path).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def _ring_attention_local(q, k, v, *, axis_name: str, causal: bool,
                          sm_scale: float):
    """Per-shard body under shard_map.

    q/k/v: [batch, heads, s_local, head_dim] — the sequence axis is the
    mesh-sharded one. Returns the exact attention output for the local
    query block against the FULL (ring-assembled) key/value sequence.
    """
    p = jax.lax.psum(1, axis_name)  # ring size
    my = jax.lax.axis_index(axis_name)
    s_local = q.shape[2]
    neg_inf = jnp.finfo(jnp.float32).min

    q32 = q.astype(jnp.float32) * sm_scale
    q_pos = my * s_local + jnp.arange(s_local)
    perm = [(r, (r + 1) % p) for r in range(p)]

    def accumulate(acc, k_blk, v_blk, i):
        m, l, o = acc
        # the block currently held originated at rank (my - i) mod p
        src = (my - i) % p
        s = jnp.einsum(
            "bhqd,bhkd->bhqk", q32, k_blk.astype(jnp.float32)
        )
        if causal:
            k_pos = src * s_local + jnp.arange(s_local)
            mask = q_pos[:, None] >= k_pos[None, :]
            s = jnp.where(mask[None, None], s, neg_inf)
        m_new = jnp.maximum(m, s.max(axis=-1))
        # guard fully-masked rows: exp(neg_inf - neg_inf) must not NaN
        alpha = jnp.exp(jnp.where(m == neg_inf, neg_inf, m - m_new))
        probs = jnp.exp(s - m_new[..., None])
        if causal:
            probs = jnp.where(mask[None, None], probs, 0.0)
        l_new = l * alpha + probs.sum(axis=-1)
        o_new = o * alpha[..., None] + jnp.einsum(
            "bhqk,bhkd->bhqd", probs, v_blk.astype(jnp.float32)
        )
        return m_new, l_new, o_new

    def step(carry, i):
        # rotate FIRST (steps 1..p-1): the local block was consumed
        # before the scan, so no discarded final rotation pays ICI time
        k_blk, v_blk, m, l, o = carry
        k_blk = jax.lax.ppermute(k_blk, axis_name, perm)
        v_blk = jax.lax.ppermute(v_blk, axis_name, perm)
        m, l, o = accumulate((m, l, o), k_blk, v_blk, i)
        return (k_blk, v_blk, m, l, o), None

    b, h, _, d = q.shape
    acc0 = (
        jnp.full((b, h, s_local), neg_inf, jnp.float32),
        jnp.zeros((b, h, s_local), jnp.float32),
        jnp.zeros((b, h, s_local, d), jnp.float32),
    )
    acc0 = accumulate(acc0, k, v, 0)  # local block, no rotation needed
    if p > 1:
        (_, _, m, l, o), _ = jax.lax.scan(
            step, (k, v) + acc0, jnp.arange(1, p)
        )
    else:
        m, l, o = acc0
    out = o / jnp.maximum(l, 1e-30)[..., None]
    return out.astype(q.dtype)


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh: Mesh,
    *,
    axis: str = "sp",
    causal: bool = False,
    sm_scale: float | None = None,
) -> jax.Array:
    """Exact attention over a sequence sharded on ``mesh`` axis ``axis``.

    Inputs are [batch, heads, seq, head_dim] with seq divisible by the
    axis size. Batch/heads/head_dim stay replicated across the ring axis
    (compose with dp/tp by sharding those dims on OTHER mesh axes).
    """
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    spec = P(None, None, axis, None)
    local = functools.partial(
        _ring_attention_local,
        axis_name=axis,
        causal=causal,
        sm_scale=sm_scale,
    )
    fn = jax.shard_map(
        local, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,
    )
    return fn(q, k, v)


def reference_attention(q, k, v, *, causal: bool = False,
                        sm_scale: float | None = None):
    """Single-device full-materialization attention (test oracle)."""
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    s = jnp.einsum(
        "bhqd,bhkd->bhqk", q.astype(jnp.float32) * sm_scale,
        k.astype(jnp.float32),
    )
    if causal:
        sq, sk = s.shape[-2], s.shape[-1]
        mask = jnp.arange(sq)[:, None] >= jnp.arange(sk)[None, :]
        s = jnp.where(mask[None, None], s, jnp.finfo(jnp.float32).min)
    w = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", w, v.astype(jnp.float32)).astype(
        q.dtype
    )
