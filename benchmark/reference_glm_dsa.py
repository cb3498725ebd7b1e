"""The plain reference of the answer model ``glm_moe_dsa`` (GLM-5.2:
multi-head latent attention under plain rotary frequencies, over the rows
a learned indexer keeps for each query; a sigmoid bias-corrected router
beside one shared expert; leading dense layers; an untied head).

Float32 ``jax.numpy`` at ``precision=highest``, one equation a line, no
cache, no absorption, no kernel, no chunks beyond blocks of queries that
let a 17,000-token sequence fit; the whole ``[T, T]`` index scores and an
exact top-``index_topk`` (``lax.top_k``: the lowest position first among
equal scores) on the layers that own an indexer, the same set on the
layers that share it; the routed experts a dense loop over the held ones
with masks; imports nothing of ``pathway_tpu``. What is the same as in
``reference_deepseek_v2`` (the keys, the leaf makers, the norm, the
rotation's pairing, the MLP, the tables, the fp8 control's rounding) is
taken from there; the weights are made HERE, layer by layer
(``make_layer``), bfloat16 matrices as served. Same entry points and
result keys as ``reference_deepseek_v2``; ``states`` are the final latent
cache rows of every layer, then the index keys of every layer that owns
an indexer, float32.

The equations (x [T, h]; eps = rms_norm_eps; every matrix W is applied as
x W; u = rmsnorm(x) of the layer's first norm):

* MLA: c_q = rmsnorm(u W_DQ); q = c_q W_UQ, a head [q_nope | q_rope];
  [c_kv | k_r] = u W_DKV; c_kv = rmsnorm(c_kv); q_rope, k_r rotated; a
  head's [k_nope | v] = c_kv W_UKV; s_h = (q_nope_h . k_nope_h + q_rope_h .
  k_r) (nope + rope)^-1/2; softmax over the query's chosen set S_t;
  out = concat(sum p v_h) W_O
* rotary, plain: pairs i of rope_dim / 2: angle = position x theta^(-2i /
  rope_dim), float64; dims 2i and 2i + 1 turn together and the result
  holds the first of every pair, then the second
* indexer (``full`` layers): q_I = c_q W_IQ, index_heads x index_dim;
  k_I = LayerNorm(u W_IK) (scale, bias, eps 1e-6), one key a position;
  the first rope_dim dims of both rotated as above; w = u W_Iw x
  index_heads^-1/2 x index_dim^-1/2; I[t, s] = sum_j w[t, j] relu(q_I[t,
  j] . k_I[s]) for s <= t; S_t = the index_topk positions of highest I
  (every s <= t while t < index_topk). ``shared`` layers: S_t of the
  nearest ``full`` layer before them
* experts: u2 = rmsnorm(x); s = sigmoid(u2 W_g); the k experts of highest
  s + b; gates = routed_scaling x s_e / sum of the k chosen s; y = sum
  over the HELD chosen e of g_e W_down,e (silu(a_e) * b_e); + shared(u2)

``forward`` can follow another computation's choices where they are what
this one could have made within a tolerance: its expert selections
(``routes``, as ``reference_deepseek_v2``: no followed expert lies
further than ``router_tol`` of the spread of s + b below the k-th best),
and, for a few queries of a sequence (``selections``), the rows its
indexer kept (no kept row lies further than ``index_tol`` of the query's
index-score spread below this indexer's k-th best, no row left out that
far above it). Everywhere else it selects for itself. Why follow at all:
bfloat16 index scores lie a few thousandths of their spread from these,
thousands of scores lie that near a query's k-th best, so the two sets
differ in a few rows a query, and every later layer's rows with them.

``precision="fp8"`` is the control: both operands of every matrix product
the program runs in bfloat16 are rounded to float8_e4m3fn.
"""

from __future__ import annotations

import functools

import numpy as np

import reference_deepseek_v2 as base
from reference_deepseek_v2 import (  # noqa: F401  (the harness reads the makers off this module)
    embed, final_norm, head, make_embed, make_head, mlp, pad_length, rms_norm, rotate)

MLA, MOE, DENSE = "mla", "moe", "dense"
FULL, SHARED = "full", "shared"
NORM_LEAVES = ("norm1", "norm2", "q_norm", "kv_norm", "ik_norm")
BIAS_LEAVES = ("router_bias", "ik_bias")
QUERY_BLOCK = 256       # queries whose scores over the whole sequence stand at once
INDEX_NORM_EPS = 1e-6
ROUTER_BIAS_STD = 0.01
KEPT_PAD = 64           # followed queries are padded to a multiple of this
BUCKETS = tuple(range(2048, 20481, 2048))      # the lengths sequences run at


def arch_of(config: dict) -> dict:
    """The sizes the equations need, from the configuration file's keys
    (the published names) and its ``held`` block."""
    held, rope = config["held"], config["rope_parameters"]
    if config["model_type"] != "glm_moe_dsa" or rope["rope_type"] != "default":
        raise ValueError("the reference writes down glm_moe_dsa under plain rotary frequencies")
    if (config["topk_method"], config["scoring_func"], config["norm_topk_prob"],
            config["n_group"], config["topk_group"]) != ("noaux_tc", "sigmoid", True, 1, 1):
        raise ValueError("the reference writes down the sigmoid bias-corrected router, no groups")
    if held["experts"][1] != config["n_routed_experts"] or \
            held["vocab_rows"][1] != config["vocab_size"] or \
            held["layers"][1] != config["num_hidden_layers"]:
        raise ValueError("the held block disagrees with the reduced keys")
    layers = config["num_hidden_layers"]
    dense = min(config["first_k_dense_replace"], layers)
    ffn = tuple(DENSE if t == "dense" else MOE for t in config["mlp_layer_types"])
    index = tuple(config["indexer_types"])
    if ffn != (DENSE,) * dense + (MOE,) * (layers - dense) or len(index) != layers \
            or not set(index) <= {FULL, SHARED} or index[0] != FULL:
        raise ValueError("mlp_layer_types / indexer_types disagree with the held layers")
    return {
        "hidden": config["hidden_size"],
        "first_layer": held["layers"][0],
        "layer_types": (MLA,) * layers, "ffn_types": ffn, "index_types": index,
        "heads": config["num_attention_heads"],
        "q_rank": config["q_lora_rank"], "kv_rank": config["kv_lora_rank"],
        "nope_dim": config["qk_nope_head_dim"], "rope_dim": config["qk_rope_head_dim"],
        "v_dim": config["v_head_dim"],
        "rope_theta": float(rope["rope_theta"]),
        "index_heads": config["index_n_heads"], "index_dim": config["index_head_dim"],
        "index_topk": config["index_topk"],
        "dense_width": config["intermediate_size"],
        "experts": config["published"]["n_routed_experts"],
        "experts_per_token": config["num_experts_per_tok"],
        "experts_held": tuple(held["experts"]),
        "expert_width": config["moe_intermediate_size"],
        "shared_width": config["n_shared_experts"] * config["moe_intermediate_size"],
        "routed_scaling": float(config["routed_scaling_factor"]),
        "vocab_rows": held["vocab_rows"][1],
        "rms_eps": config["rms_norm_eps"],
        # standard deviation of every random matrix (``assumed``; tests at
        # toy widths state a larger one, or the layers add nothing)
        "init_std": config.get("init_std", 0.02),
    }


def layer_shapes(a: dict, kind: str = MLA, ffn: str = MOE, index: str | None = None) -> dict:
    h, held, H = a["hidden"], a["experts_held"][1], a["heads"]
    shapes = {
        "norm1": (h,), "norm2": (h,),
        "w_dq": (h, a["q_rank"]), "q_norm": (a["q_rank"],),
        "w_uq": (a["q_rank"], H * (a["nope_dim"] + a["rope_dim"])),
        "w_dkv": (h, a["kv_rank"] + a["rope_dim"]), "kv_norm": (a["kv_rank"],),
        "w_ukv": (a["kv_rank"], H * (a["nope_dim"] + a["v_dim"])),
        "wo": (H * a["v_dim"], h),
    }
    if index == FULL:
        shapes.update({
            "w_iq": (a["q_rank"], a["index_heads"] * a["index_dim"]),
            "w_ik": (h, a["index_dim"]),
            "ik_norm": (a["index_dim"],), "ik_bias": (a["index_dim"],),
            "w_iw": (h, a["index_heads"]),
        })
    if ffn == MOE:
        shapes.update({
            "router": (h, a["experts"]), "router_bias": (a["experts"],),
            "shared_in": (h, 2 * a["shared_width"]), "shared_out": (a["shared_width"], h),
            "experts_in": (held, h, 2 * a["expert_width"]),
            "experts_out": (held, a["expert_width"], h),
        })
    else:
        shapes.update({"mlp_in": (h, 2 * a["dense_width"]), "mlp_out": (a["dense_width"], h)})
    return shapes


def param_count(a: dict) -> int:
    """Every held parameter: both tables, the final norm, every layer's leaves."""
    total = 2 * a["vocab_rows"] * a["hidden"] + a["hidden"]
    for ffn, index in zip(a["ffn_types"], a["index_types"]):
        total += sum(int(np.prod(s)) for s in layer_shapes(a, MLA, ffn, index).values())
    return total


# -- weights, from the seed ----------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _bias_maker(shape: tuple, std: float):
    import jax
    import jax.numpy as jnp

    return jax.jit(lambda key: std * jax.random.normal(key, shape, jnp.float32))


def make_layer(a: dict, seed: int, layer: int) -> dict:
    """The ``layer``-th held layer's weights from the key of its published
    index, on the device, a leaf a call: matrices N(0, init_std = 0.02)
    bfloat16; norm scales 1 + N(0, 0.02), the index keys' LayerNorm bias
    N(0, 0.02) and the router's correction bias N(0, 0.01), float32."""
    import jax

    key = jax.random.fold_in(base._key(seed, 21), a["first_layer"] + layer)
    shapes = sorted(layer_shapes(a, MLA, a["ffn_types"][layer], a["index_types"][layer]).items())
    out = {}
    for i, (name, shape) in enumerate(shapes):
        k = jax.random.fold_in(key, i)
        if name in BIAS_LEAVES:
            out[name] = _bias_maker(shape, ROUTER_BIAS_STD if name == "router_bias" else 0.02)(k)
        else:
            out[name] = base._leaf_maker(shape, a["init_std"], name in NORM_LEAVES)(k)
    return out


# -- the equations -----------------------------------------------------------------------


def rope_tables(a: dict, length: int):
    """cos, sin [length, rope_dim / 2] for positions 0.., angles in float64."""
    d = a["rope_dim"]
    freq = a["rope_theta"] ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    angles = np.arange(length, dtype=np.float64)[:, None] * freq[None, :]
    return np.cos(angles).astype(np.float32), np.sin(angles).astype(np.float32)


def _blocks(x, qb):
    return x.reshape(x.shape[0] // qb, qb, *x.shape[1:])


def index_scores(a: dict, p: dict, u, c_q, cos, sin, mm):
    """(I [T, T] float32, the index scores of every query over every
    position, the later ones too; k_I [T, index_dim])."""
    import jax
    import jax.numpy as jnp

    T, J, D, r = u.shape[0], a["index_heads"], a["index_dim"], a["rope_dim"]
    q = mm("td,de->te", c_q, p["w_iq"]).reshape(T, J, D)
    q = jnp.concatenate([rotate(q[..., :r], cos[:, None], sin[:, None]), q[..., r:]], axis=-1)
    k = mm("td,de->te", u, p["w_ik"])
    k = k - jnp.mean(k, axis=-1, keepdims=True)
    k = k * jax.lax.rsqrt(jnp.mean(jnp.square(k), axis=-1, keepdims=True) + INDEX_NORM_EPS)
    k = k * p["ik_norm"] + p["ik_bias"]
    k = jnp.concatenate([rotate(k[:, :r], cos, sin), k[:, r:]], axis=-1)
    w = mm("td,dj->tj", u, p["w_iw"]) * float(J ** -0.5 * D ** -0.5)

    def queries(block):
        qb, wb = block
        return jnp.sum(wb[:, :, None] * jax.nn.relu(mm("tjd,pd->tjp", qb, k)), axis=1)

    qb = min(QUERY_BLOCK, T)
    return jax.lax.map(queries, (_blocks(q, qb), _blocks(w, qb))).reshape(T, T), k


def choose_rows(a: dict, scores, follow=None, tol=0.0):
    """S_t of every query as a mask [T, T]: the index_topk causal
    positions of highest score, all of them while t < index_topk.
    ``follow`` = (theirs [T, T] bool, followed [T] bool): another
    indexer's choice for the queries marked; such a query takes their rows
    where they are what this indexer could have kept: none of them lies
    more than ``tol`` of the query's score spread below this indexer's
    k-th best, no row left out lies that far above it. Returns (mask,
    wrong [T]: the rows of a followed query that are not)."""
    import jax
    import jax.numpy as jnp

    T, K = scores.shape[0], min(a["index_topk"], scores.shape[0])

    def queries(block):
        s, at, theirs, followed = block
        causal = jnp.arange(T)[None, :] <= at[:, None]
        low = jnp.where(causal, s, -jnp.inf)
        best, where = jax.lax.top_k(low, K)
        mask = jnp.zeros(s.shape, bool).at[jnp.arange(s.shape[0])[:, None], where].set(True)
        mask = mask & causal
        if follow is None:
            return mask, jnp.zeros(s.shape[0], jnp.int32)
        kth = best[:, -1]                                               # -inf while t < K
        spread = jnp.max(low, axis=-1) - jnp.min(jnp.where(causal, s, jnp.inf), axis=-1)
        room = tol * spread
        below = theirs & causal & (s < (kth - room)[:, None])
        above = ~theirs & causal & (s > (kth + room)[:, None])
        wrong = jnp.sum(below | above | (theirs & ~causal), axis=-1, dtype=jnp.int32)
        wrong = jnp.where(followed, wrong, 0)
        return jnp.where((followed & (wrong == 0))[:, None], theirs, mask), wrong

    qb = min(QUERY_BLOCK, T)
    theirs, followed = follow if follow is not None else (
        jnp.zeros((T, 1), bool), jnp.zeros((T,), bool))
    mask, wrong = jax.lax.map(queries, (
        _blocks(scores, qb), _blocks(jnp.arange(T), qb), _blocks(theirs, qb),
        _blocks(followed, qb)))
    return mask.reshape(T, T), wrong.reshape(T)


def score_gap(scores, their_scores, at, valid):
    """The largest difference between another indexer's scores [S, T] of
    the queries at ``at`` [S] and this one's, over the widest spread among
    those queries (one that sees a few positions has next to none of its
    own); entries not ``valid`` are padding."""
    import jax.numpy as jnp

    T = scores.shape[0]
    mine = scores[at]
    causal = (jnp.arange(T)[None, :] <= at[:, None]) & valid[:, None]
    spread = jnp.max(jnp.where(causal, mine, -jnp.inf), axis=-1) - jnp.min(
        jnp.where(causal, mine, jnp.inf), axis=-1)
    apart = jnp.max(jnp.where(causal, jnp.abs(their_scores - mine), 0.0))
    return apart / jnp.maximum(jnp.max(jnp.where(valid, spread, 0.0)), 1e-30)


def mla_mixer(a: dict, index: str, p: dict, u, cos, sin, mm, chosen, follow=None, tol=0.0):
    """u [T, h] -> (out [T, h], cache rows [T, kv_rank + rope], chosen
    [T, T] bool: this layer's own choice where it owns an indexer, else
    the one handed in; k_I [T, index_dim] or None; (wrong, gap) of the
    followed queries or None; the index scores [T, T] or None)."""
    import jax
    import jax.numpy as jnp

    T, H, dn, dr, dv, rk = (u.shape[0], a["heads"], a["nope_dim"], a["rope_dim"],
                            a["v_dim"], a["kv_rank"])
    c_q = rms_norm(mm("td,de->te", u, p["w_dq"]), p["q_norm"], a["rms_eps"])
    q = mm("td,de->te", c_q, p["w_uq"]).reshape(T, H, dn + dr)
    q_nope, q_rope = q[..., :dn], rotate(q[..., dn:], cos[:, None], sin[:, None])
    down = mm("td,de->te", u, p["w_dkv"])
    c_kv = rms_norm(down[:, :rk], p["kv_norm"], a["rms_eps"])
    k_r = rotate(down[:, rk:], cos, sin)
    kv = mm("tc,ce->te", c_kv, p["w_ukv"]).reshape(T, H, dn + dv)
    k_nope, v = kv[..., :dn], kv[..., dn:]
    scale = float((dn + dr) ** -0.5)
    k_index = judged = scores = None
    if index == FULL:
        scores, k_index = index_scores(a, p, u, c_q, cos, sin, mm)
        if follow is None:
            chosen, _ = choose_rows(a, scores)
        else:
            theirs, followed, at, their_scores, valid = follow
            chosen, wrong = choose_rows(a, scores, (theirs, followed), tol)
            judged = (jnp.sum(wrong), score_gap(scores, their_scores, at, valid))

    def queries(block):                                    # a block of queries, all keys
        qn, qr, keep = block
        s = (mm("thd,phd->htp", qn, k_nope) + mm("thr,pr->htp", qr, k_r)) * scale
        w = jax.nn.softmax(jnp.where(keep[None], s, jnp.finfo(jnp.float32).min), axis=-1)
        return mm("htp,phd->thd", w, v)

    qb = min(QUERY_BLOCK, T)
    ctx = jax.lax.map(queries, (_blocks(q_nope, qb), _blocks(q_rope, qb), _blocks(chosen, qb)))
    out = mm("td,de->te", ctx.reshape(T, H * dv), p["wo"])
    return out, jnp.concatenate([c_kv, k_r], axis=-1), chosen, k_index, judged, scores


def choose(a: dict, p: dict, r):
    """From router logits r [T, experts]: (scores = sigmoid(r), the score
    the choice is made by = scores + bias, selected ids [T, k])."""
    import jax

    s = jax.nn.sigmoid(r)
    by = s + p["router_bias"]
    return s, by, jax.lax.top_k(by, a["experts_per_token"])[1]


def experts_and_shared(a: dict, p: dict, u, mm, follow=None, tol: float = 0.0):
    """moe(u) + shared(u) over the held experts, and the routing's record.
    ``follow`` [T, k]: another computation's selections; a token follows
    them where no followed expert's score + bias lies further than ``tol``
    of the spread of the token's scores + bias below the k-th best. Else
    the token keeps its own and is counted. Returns (sum [T, h],
    selections used [T, k], gap [T])."""
    import jax
    import jax.numpy as jnp

    E, k = a["experts"], a["experts_per_token"]
    first, n_held = a["experts_held"]
    s, by, sel = choose(a, p, mm("td,de->te", u, p["router"]))
    gap = jnp.zeros(u.shape[0], jnp.float32)
    if follow is not None:
        spread = jnp.max(by, axis=-1) - jnp.min(by, axis=-1)
        kth = jax.lax.top_k(by, k)[0][:, -1]
        gap = jnp.maximum(
            kth - jnp.min(jnp.take_along_axis(by, follow, axis=-1), axis=-1), 0.0) / spread
        sel = jnp.where((gap <= tol)[:, None], follow, sel)
    chosen = jnp.take_along_axis(s, sel, axis=-1)
    gates = a["routed_scaling"] * chosen / jnp.sum(chosen, axis=-1, keepdims=True)
    # gate of expert e for each token, 0 where it was not selected
    dense = jnp.sum(
        jnp.where(sel[:, :, None] == jnp.arange(E)[None, None, :], gates[:, :, None], 0.0),
        axis=1)                                                             # [T, experts]
    held_gates = dense[:, first:first + n_held].T                          # [held, T]

    def one(acc, scanned):
        w_in, w_out, g = scanned
        return acc + g[:, None] * mlp(u, w_in, w_out, a["expert_width"], mm), None

    routed, _ = jax.lax.scan(
        one, jnp.zeros_like(u), (p["experts_in"], p["experts_out"], held_gates))
    shared = mlp(u, p["shared_in"], p["shared_out"], a["shared_width"], mm)
    return routed + shared, sel, gap


def block(a: dict, ffn: str, index: str, p: dict, x, cos, sin, mm, chosen,
          follow=None, tol: float = 0.0, follow_rows=None, index_tol: float = 0.0,
          tail=None):
    """One layer over one sequence x [T, h]: (x, cache rows, chosen [T,
    T], index keys or None, (wrong selections, index gap) or None,
    selections used or None, router gap [T] or None, the index scores of
    the queries ``tail`` = (first, how many) or None)."""
    out, rows, chosen, k_index, judged, scores = mla_mixer(
        a, index, p, rms_norm(x, p["norm1"], a["rms_eps"]), cos, sin, mm, chosen,
        follow_rows, index_tol)
    if scores is not None and tail is not None:
        import jax

        scores = jax.lax.dynamic_slice_in_dim(scores, tail[0], tail[1], axis=0)
    else:
        scores = None
    x = x + out
    u = rms_norm(x, p["norm2"], a["rms_eps"])
    if ffn == DENSE:
        x = x + mlp(u, p["mlp_in"], p["mlp_out"], a["dense_width"], mm)
        return x, rows, chosen, k_index, judged, None, None, scores
    both, sel, gap = experts_and_shared(a, p, u, mm, follow, tol)
    return x + both, rows, chosen, k_index, judged, sel, gap, scores


# -- the streamed forward ------------------------------------------------------------------

_BLOCK_JIT: dict = {}


def _jitted_block(a: dict, ffn: str, index: str, precision: str, routed: bool, rowed: bool,
                  tail: int = 0):
    import jax

    cache_key = (ffn, index, precision, routed, rowed, tail, tuple(sorted(a.items())))
    fn = _BLOCK_JIT.get(cache_key)
    if fn is None:
        mm = base._ops(precision)

        def run(p, x, cos, sin, chosen, follow, tol, follow_rows, index_tol, first):
            return block(a, ffn, index, p, x, cos, sin, mm, chosen,
                         follow if routed else None, tol,
                         follow_rows if rowed else None, index_tol,
                         (first, tail) if tail else None)

        fn = _BLOCK_JIT[cache_key] = jax.jit(run)
    return fn


def _followed_rows(selection: dict, layer: int, padded: int):
    """One sequence's followed queries for the ``layer``-th indexer, as
    the arrays ``mla_mixer`` takes: (their choice by query position
    [padded, padded] bool, which queries are followed [padded], the
    positions of the queries whose scores are given [S], those scores [S,
    padded], which of the S are real). ``chosen`` may be bits (uint8 [..,
    P / 8] as the served program packs them: bit k of byte j is position
    k * P / 8 + j); ``scores`` are those of the LAST queries of ``at``."""
    import jax.numpy as jnp

    at = np.asarray(selection["at"], np.int64)
    chosen = np.asarray(selection["chosen"][layer])
    if chosen.dtype == np.uint8:
        chosen = np.concatenate([(chosen >> k) & 1 for k in range(8)], axis=-1).astype(bool)
    n = min(chosen.shape[-1], padded)
    theirs = np.zeros((padded, padded), bool)
    theirs[at, :n] = chosen[:, :n]
    followed = np.zeros(padded, bool)
    followed[at] = True
    given = np.asarray(selection["scores"][layer], np.float32)
    size = -(-len(given) // KEPT_PAD) * KEPT_PAD
    scores = np.zeros((size, padded), np.float32)
    m = min(given.shape[-1], padded)
    scores[:len(given), :m] = given[:, :m]
    scored_at = np.zeros(size, np.int32)
    scored_at[:len(given)] = at[len(at) - len(given):]
    return (jnp.asarray(theirs), jnp.asarray(followed), jnp.asarray(scored_at),
            jnp.asarray(scores), jnp.asarray(np.arange(size) < len(given)))


def forward(a: dict, seed: int, sequences, *, last: int, routes=None,
            router_tol: float = 0.0, selections=None, index_tol: float = 0.0,
            precision: str = "f32", layers=None, buckets=BUCKETS,
            keep_chosen: bool = False) -> list[dict]:
    """Every sequence (token ids, NumPy) through the whole model, layer
    by layer. ``routes``: per sequence [expert layers, n, k] selections to
    follow (see ``experts_and_shared``). ``selections``: per sequence
    ``{"at": positions [Q], "chosen": [index layers, Q, n] bool, "scores":
    [index layers, Q, n]}``, another indexer's work on a few queries, to
    follow and to judge (see ``choose_rows``, ``score_gap``; ``chosen``
    may be bits, ``scores`` cover the last queries of ``at``:
    ``_followed_rows``). ``layers``: ready-made trees
    ``{"embed", "head", "final_norm", "layers"}`` instead of the makers
    (tests). Returns per sequence ``{"logits" [last, rows] at the last
    ``last`` positions, "routes" [expert layers, n, k] used, "router_gap"
    (largest), "wrong_routes" (tokens beyond ``router_tol``), "states":
    [the n cache rows of each layer, then the n index keys of each layer
    that owns an indexer, float32], "index_gap", "wrong_selections" (of
    the followed queries), and with ``keep_chosen`` (tests; the control,
    whose own choice is judged) "chosen": [index layers] masks [n, n]
    used, "index_scores": [index layers] scores [last - 1, n] of the last
    ``last`` - 1 queries: a ``selections`` of this indexer's own making}``,
    NumPy."""
    import jax
    import jax.numpy as jnp

    table = make_embed(a, seed) if layers is None else layers["embed"]
    lengths = [len(s) for s in sequences]
    padded = [pad_length(n, buckets) for n in lengths]
    xs, tables = [], {}
    for s, L in zip(sequences, padded):
        ids = np.zeros(L, np.int32)
        ids[:len(s)] = s
        xs.append(embed(a, table, jnp.asarray(ids)))
        if L not in tables:
            tables[L] = tuple(jnp.asarray(t) for t in rope_tables(a, L))
    del table
    out = [{"routes": [], "router_gap": 0.0, "wrong_routes": 0, "states": [], "index_keys": [],
            "chosen": [], "index_scores": [], "index_gap": 0.0, "wrong_selections": 0}
           for _ in sequences]
    chosen = [jnp.zeros((L, L), bool) for L in padded]      # what a ``full`` layer hands on
    none = jnp.zeros((), jnp.float32)
    expert_layer = index_layer = 0
    for l, (ffn, index) in enumerate(zip(a["ffn_types"], a["index_types"])):
        p = make_layer(a, seed, l) if layers is None else layers["layers"][l]
        routed = routes is not None and ffn == MOE
        rowed = selections is not None and index == FULL
        tail = last - 1 if keep_chosen and index == FULL else 0
        fn = _jitted_block(a, ffn, index, precision, routed, rowed, tail)
        for i, n in enumerate(lengths):
            cos, sin = tables[padded[i]]
            follow, follow_rows = none, none
            if routed:
                follow = np.zeros((padded[i], a["experts_per_token"]), np.int32)
                follow[:n] = routes[i][expert_layer]
                follow = jnp.asarray(follow)
            if rowed:
                follow_rows = _followed_rows(selections[i], index_layer, padded[i])
            xs[i], rows, chosen[i], k_index, judged, sel, gap, scores = fn(
                p, xs[i], cos, sin, chosen[i], follow, np.float32(router_tol),
                follow_rows, np.float32(index_tol), np.int32(n - tail))
            out[i]["states"].append(np.asarray(rows)[:n])
            if index == FULL:
                out[i]["index_keys"].append(np.asarray(k_index)[:n])
                if keep_chosen:
                    out[i]["chosen"].append(np.asarray(chosen[i][:n, :n]))
                    if tail:
                        out[i]["index_scores"].append(np.asarray(scores)[:, :n])
                if judged is not None:
                    out[i]["wrong_selections"] += int(judged[0])
                    out[i]["index_gap"] = max(out[i]["index_gap"], float(judged[1]))
            if ffn == MOE:
                gap = np.asarray(gap)[:n]
                out[i]["routes"].append(np.asarray(sel)[:n])
                out[i]["router_gap"] = max(out[i]["router_gap"], float(gap.max()))
                out[i]["wrong_routes"] += int((gap > router_tol).sum())
        expert_layer += ffn == MOE
        index_layer += index == FULL
        del p
    del chosen
    mm = base._ops(precision)
    norm_w = final_norm(a) if layers is None else layers["final_norm"]
    table = make_head(a, seed) if layers is None else layers["head"]
    head_fn = jax.jit(lambda t, w, x: head(a, t, w, x, mm))
    for i, n in enumerate(lengths):
        tail = jax.lax.dynamic_slice_in_dim(xs[i], n - last, last, axis=0)
        out[i]["logits"] = np.asarray(head_fn(table, norm_w, tail))
        out[i]["routes"] = np.stack(out[i]["routes"])
        out[i]["states"] += out[i].pop("index_keys")
    return out
