"""The plain reference of the answer model ``deepseek_v2`` (DeepSeek-V2:
multi-head latent attention under YaRN, a leading dense layer, then routed
experts under a group-limited router beside the shared experts, an untied
head).

Float32 ``jax.numpy`` at ``precision=highest``, one equation a line, no
cache, no absorption, no chunks beyond blocks of queries that let a
7,500-token sequence's scores fit; the routed experts a dense loop over
the held ones with masks; imports nothing of ``pathway_tpu``. The weights
are made HERE, layer by layer from a per-layer key (``make_layer``),
bfloat16 matrices as served; the harness hands the same arrays to the
program. ``forward`` streams: it makes layer l, runs every sequence
through it, and lets it go. Same entry points and result keys as
``reference_decoder.py``; ``states`` are the final latent cache rows
``[c_kv after its norm | k_r after rotation]`` of each layer, float32.

The equations (x [T, h]; eps from the configuration; every matrix W is
applied as x W):

* x0 = E[id]; block: x += mla(rmsnorm(x)); x += ffn(rmsnorm(x));
  logits = rmsnorm(x_L) H^T, E and H separate, both the held rows
* MLA: c_q = rmsnorm(x W_DQ); q = c_q W_UQ, a head [q_nope | q_rope]
  [c_kv | k_r] = x W_DKV; c_kv = rmsnorm(c_kv); q_rope, k_r rotated at the
  token's position (k_r is one key for all heads)
  a head's [k_nope | v] = c_kv W_UKV
  s_h = (q_nope_h . k_nope_h + q_rope_h . k_r) * scale, causal softmax,
  o_h = sum p v_h, out = concat(o_h) W_O
* rotary, YaRN: the rope_dim / 2 pairs i: f_i = theta^(-2i / rope_dim);
  inv_freq_i = f_i / factor * (1 - m_i) + f_i * m_i,
  m_i = 1 - clip((i - low) / (high - low), 0, 1), low / high = floor / ceil of
  rope_dim ln(original / (2 pi b)) / (2 ln theta) at b = beta_fast / beta_slow,
  clipped to 0..rope_dim - 1; cos and sin times mscale(factor, mscale) /
  mscale(factor, mscale_all_dim); scale = (nope + rope)^-1/2 *
  mscale(factor, mscale_all_dim)^2, mscale(f, m) = 0.1 m ln f + 1. Dims 2i
  and 2i + 1 turn together and the result holds the first of every pair,
  then the second (the published code's de-interleaving)
* experts: u = rmsnorm(x); s = softmax(u W_g) over ALL experts; a group's
  score is its best expert's; the topk_group best groups stand, the rest
  are zeroed; the k largest remaining; gates = routed_scaling * s_e, not
  renormalised; y = sum over the HELD selected e of g_e W_down,e (silu(a_e)
  * b_e), [a_e | b_e] = u W_in,e; + shared(u), one MLP of the shared
  experts' summed width, always on. A leading dense layer: one MLP.

``precision="fp8"`` is the control: both operands of every matrix product
the program runs in bfloat16 (weights and activations) are rounded to
float8_e4m3fn under a per-tensor scale; norms, softmax, router scores and
gates stay float32, as in the program.
"""

from __future__ import annotations

import functools

import numpy as np

MLA, MOE, DENSE = "mla", "moe", "dense"
NORM_LEAVES = ("norm1", "norm2", "q_norm", "kv_norm")
F32_LEAVES = NORM_LEAVES
QUERY_BLOCK = 512       # queries whose scores over the whole sequence stand at once


def arch_of(config: dict) -> dict:
    """The sizes the equations need, from the configuration file's keys
    (the published names) and its ``held`` block."""
    held, yarn = config["held"], config["rope_scaling"]
    if config["model_type"] != "deepseek_v2" or yarn["type"] != "yarn":
        raise ValueError("the reference writes down deepseek_v2 under YaRN")
    if (config["topk_method"], config["scoring_func"], config["norm_topk_prob"]) != (
            "group_limited_greedy", "softmax", False) or config["moe_layer_freq"] != 1:
        raise ValueError("the reference writes down the group-limited softmax router")
    if held["experts"][1] != config["n_routed_experts"] or \
            held["vocab_rows"][1] != config["vocab_size"]:
        raise ValueError("the held block disagrees with the reduced keys")
    layers = config["num_hidden_layers"]
    dense = min(config["first_k_dense_replace"], layers)
    return {
        "hidden": config["hidden_size"],
        "layer_types": (MLA,) * layers,
        "ffn_types": (DENSE,) * dense + (MOE,) * (layers - dense),
        "heads": config["num_attention_heads"],
        "q_rank": config["q_lora_rank"], "kv_rank": config["kv_lora_rank"],
        "nope_dim": config["qk_nope_head_dim"], "rope_dim": config["qk_rope_head_dim"],
        "v_dim": config["v_head_dim"],
        "rope_theta": float(config["rope_theta"]), "rope_factor": float(yarn["factor"]),
        "rope_original": int(yarn["original_max_position_embeddings"]),
        "beta_fast": float(yarn["beta_fast"]), "beta_slow": float(yarn["beta_slow"]),
        "mscale": float(yarn["mscale"]), "mscale_all_dim": float(yarn["mscale_all_dim"]),
        "dense_width": config["intermediate_size"],
        "experts": config["published"]["n_routed_experts"],
        "experts_per_token": config["num_experts_per_tok"],
        "experts_held": tuple(held["experts"]),
        "expert_width": config["moe_intermediate_size"],
        "shared_width": config["n_shared_experts"] * config["moe_intermediate_size"],
        "groups": config["n_group"], "top_groups": config["topk_group"],
        "routed_scaling": float(config["routed_scaling_factor"]),
        "vocab_rows": held["vocab_rows"][1],
        "rms_eps": config["rms_norm_eps"],
        # standard deviation of every random matrix (``assumed``; tests at
        # toy widths state a larger one, or the layers add nothing)
        "init_std": config.get("init_std", 0.02),
    }


def layer_shapes(a: dict, kind: str = MLA, ffn: str = MOE) -> dict:
    h, held, H = a["hidden"], a["experts_held"][1], a["heads"]
    shapes = {
        "norm1": (h,), "norm2": (h,),
        "w_dq": (h, a["q_rank"]), "q_norm": (a["q_rank"],),
        "w_uq": (a["q_rank"], H * (a["nope_dim"] + a["rope_dim"])),
        "w_dkv": (h, a["kv_rank"] + a["rope_dim"]), "kv_norm": (a["kv_rank"],),
        "w_ukv": (a["kv_rank"], H * (a["nope_dim"] + a["v_dim"])),
        "wo": (H * a["v_dim"], h),
    }
    if ffn == MOE:
        shapes.update({
            "router": (h, a["experts"]),
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
    for ffn in a["ffn_types"]:
        total += sum(int(np.prod(s)) for s in layer_shapes(a, MLA, ffn).values())
    return total


# -- weights, from the seed ----------------------------------------------------------


def _key(seed: int, stream: int):
    import jax

    # "rbg": the device's own bit generator (threefry took twice as long
    # for the 4.76 G values of the other answer model; PR 27)
    key = jax.random.key(stream, impl="rbg")
    key = jax.random.fold_in(key, seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


@functools.lru_cache(maxsize=None)
def _leaf_maker(shape: tuple, std: float, norm: bool):
    import jax
    import jax.numpy as jnp

    if norm:
        return jax.jit(lambda key: 1.0 + 0.02 * jax.random.normal(key, shape, jnp.float32))
    return jax.jit(
        lambda key: (std * jax.random.normal(key, shape, jnp.float32)).astype(jnp.bfloat16))


def make_layer(a: dict, seed: int, layer: int) -> dict:
    """Layer ``layer``'s weights from its own key, on the device, a leaf
    a call: matrices N(0, init_std = 0.02) bfloat16; norm scales 1 + N(0,
    0.02) float32."""
    import jax

    key = jax.random.fold_in(_key(seed, 21), layer)
    shapes = sorted(layer_shapes(a, MLA, a["ffn_types"][layer]).items())
    return {
        name: _leaf_maker(shape, a["init_std"], name in NORM_LEAVES)(jax.random.fold_in(key, i))
        for i, (name, shape) in enumerate(shapes)
    }


def make_embed(a: dict, seed: int):
    """The held rows of the embedding table, N(0, init_std) bfloat16."""
    return _leaf_maker((a["vocab_rows"], a["hidden"]), a["init_std"], False)(_key(seed, 22))


def make_head(a: dict, seed: int):
    """The held rows of the head, its own matrix."""
    return _leaf_maker((a["vocab_rows"], a["hidden"]), a["init_std"], False)(_key(seed, 23))


def final_norm(a: dict):
    import jax.numpy as jnp

    return jnp.ones((a["hidden"],), jnp.float32)


# -- the equations -----------------------------------------------------------------------


def _fp8(x):
    import jax.numpy as jnp

    scale = 448.0 / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    return (x * scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) / scale


def _ops(precision: str):
    import jax
    import jax.numpy as jnp

    rnd = _fp8 if precision == "fp8" else (lambda x: x)
    hi = jax.lax.Precision.HIGHEST

    def mm(spec, a, b):
        return jnp.einsum(spec, rnd(a.astype(jnp.float32)), rnd(b.astype(jnp.float32)),
                          precision=hi)

    return mm


def rms_norm(x, w, eps):
    import jax
    import jax.numpy as jnp

    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * w


def yarn_inv_freq(a: dict) -> np.ndarray:
    """The rope_dim / 2 rotary frequencies, float64."""
    d, theta = a["rope_dim"], a["rope_theta"]
    f = theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)

    def dim_of(turns):
        return d * np.log(a["rope_original"] / (turns * 2 * np.pi)) / (2 * np.log(theta))

    low = max(np.floor(dim_of(a["beta_fast"])), 0)
    high = min(np.ceil(dim_of(a["beta_slow"])), d - 1)
    m = 1.0 - np.clip((np.arange(d // 2) - low) / max(high - low, 1e-3), 0, 1)
    return f / a["rope_factor"] * (1 - m) + f * m


def mscale(factor: float, m: float) -> float:
    return 0.1 * m * np.log(factor) + 1.0 if factor > 1 else 1.0


def softmax_scale(a: dict) -> float:
    return float((a["nope_dim"] + a["rope_dim"]) ** -0.5
                 * mscale(a["rope_factor"], a["mscale_all_dim"]) ** 2)


def rope_tables(a: dict, length: int):
    """cos, sin [length, rope_dim / 2] for positions 0.., angles in float64."""
    angles = np.arange(length, dtype=np.float64)[:, None] * yarn_inv_freq(a)[None, :]
    m = mscale(a["rope_factor"], a["mscale"]) / mscale(a["rope_factor"], a["mscale_all_dim"])
    return (np.cos(angles) * m).astype(np.float32), (np.sin(angles) * m).astype(np.float32)


def rotate(x, cos, sin):
    """x [T, .., rope_dim] at positions 0..T-1; cos, sin broadcast to x's pairs."""
    import jax.numpy as jnp

    first, second = x[..., 0::2], x[..., 1::2]
    return jnp.concatenate([first * cos - second * sin, second * cos + first * sin], axis=-1)


def mla_mixer(a: dict, p: dict, u, cos, sin, mm):
    """u [T, h] -> (out [T, h], the sequence's cache rows [T, kv_rank + rope])."""
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
    scale = softmax_scale(a)

    def queries(block):                                    # a block of queries, all keys
        qn, qr, at = block
        s = (mm("thd,phd->htp", qn, k_nope) + mm("thr,pr->htp", qr, k_r)) * scale
        causal = jnp.arange(T)[None, :] <= at[:, None]
        w = jax.nn.softmax(jnp.where(causal[None], s, jnp.finfo(jnp.float32).min), axis=-1)
        return mm("htp,phd->thd", w, v)

    qb = min(QUERY_BLOCK, T)
    blocks = (q_nope.reshape(T // qb, qb, H, dn), q_rope.reshape(T // qb, qb, H, dr),
              jnp.arange(T).reshape(T // qb, qb))
    ctx = jax.lax.map(queries, blocks).reshape(T, H * dv)
    return mm("td,de->te", ctx, p["wo"]), jnp.concatenate([c_kv, k_r], axis=-1)


def glu(x, width):
    import jax

    return jax.nn.silu(x[..., :width]) * x[..., width:]


def mlp(u, w_in, w_out, width, mm):
    return mm("tw,wd->td", glu(mm("td,dw->tw", u, w_in), width), w_out)


def choose(a: dict, r):
    """The group-limited choice from router logits r [T, experts]: (scores
    [T, experts] = softmax over all, selected ids [T, k], which groups
    stand [T, groups])."""
    import jax
    import jax.numpy as jnp

    G = a["groups"]
    s = jax.nn.softmax(r, axis=-1)
    group_score = jnp.max(s.reshape(-1, G, a["experts"] // G), axis=-1)
    _, best = jax.lax.top_k(group_score, a["top_groups"])
    stands = jnp.any(best[:, :, None] == jnp.arange(G)[None, None, :], axis=1)
    masked = jnp.where(jnp.repeat(stands, a["experts"] // G, axis=1), s, 0.0)
    _, sel = jax.lax.top_k(masked, a["experts_per_token"])
    return s, sel, stands


def experts_and_shared(a: dict, p: dict, u, mm, follow=None, tol: float = 0.0):
    """moe(u) + shared(u) over the held experts, and the routing's record.
    ``follow`` [T, k]: another computation's selections. A token follows
    them where they are what this router could have chosen within ``tol``
    of the spread of the token's router logits: every followed expert's
    group stands no further than that below this router's last standing
    group (a near-tie between groups), and, with the followed groups
    standing (filled up to ``top_groups`` with this router's best others),
    no followed expert lies further than that below the k-th best there
    (a near-tie between experts). Else the token keeps its own and is
    counted. Returns (sum [T, h], selections used [T, k], gap [T])."""
    import jax
    import jax.numpy as jnp

    E, G, k = a["experts"], a["groups"], a["experts_per_token"]
    first, n_held = a["experts_held"]
    r = mm("td,de->te", u, p["router"])
    s, sel, _ = choose(a, r)
    gap = jnp.zeros(u.shape[0], jnp.float32)
    if follow is not None:
        spread = jnp.max(r, axis=-1) - jnp.min(r, axis=-1)
        group_logit = jnp.max(r.reshape(-1, G, E // G), axis=-1)          # [T, G]
        last_standing = jax.lax.top_k(group_logit, a["top_groups"])[0][:, -1]
        their_groups = follow // (E // G)                                   # [T, k]
        theirs_stand = jnp.any(
            their_groups[:, :, None] == jnp.arange(G)[None, None, :], axis=1)
        group_gap = last_standing - jnp.min(
            jnp.take_along_axis(group_logit, their_groups, axis=-1), axis=-1)
        # their groups first, then this router's own best, up to top_groups
        order = jnp.where(theirs_stand, jnp.inf, group_logit)
        _, fill = jax.lax.top_k(order, a["top_groups"])
        stands = jnp.any(fill[:, :, None] == jnp.arange(G)[None, None, :], axis=1)
        among = jnp.where(jnp.repeat(stands, E // G, axis=1), r, -jnp.inf)
        kth = jax.lax.top_k(among, k)[0][:, -1]
        expert_gap = kth - jnp.min(jnp.take_along_axis(r, follow, axis=-1), axis=-1)
        gap = jnp.maximum(jnp.maximum(group_gap, expert_gap), 0.0) / spread
        sel = jnp.where((gap <= tol)[:, None], follow, sel)
    gates = a["routed_scaling"] * jnp.take_along_axis(s, sel, axis=-1)
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


def block(a: dict, ffn: str, p: dict, x, cos, sin, mm, follow=None, tol: float = 0.0):
    """One layer over one sequence x [T, h]: (x, cache rows [T, kv_rank +
    rope], selections used or None, router gap [T] or None)."""
    out, rows = mla_mixer(a, p, rms_norm(x, p["norm1"], a["rms_eps"]), cos, sin, mm)
    x = x + out
    u = rms_norm(x, p["norm2"], a["rms_eps"])
    if ffn == DENSE:
        return x + mlp(u, p["mlp_in"], p["mlp_out"], a["dense_width"], mm), rows, None, None
    both, sel, gap = experts_and_shared(a, p, u, mm, follow, tol)
    return x + both, rows, sel, gap


def embed(a: dict, table, ids):
    import jax.numpy as jnp

    return table[ids].astype(jnp.float32)


def head(a: dict, table, norm_w, x, mm):
    return mm("td,vd->tv", rms_norm(x, norm_w, a["rms_eps"]), table)


# -- the streamed forward ------------------------------------------------------------------

_BLOCK_JIT: dict = {}


def _jitted_block(a: dict, ffn: str, precision: str, following: bool):
    import jax

    cache_key = (ffn, precision, following, tuple(sorted(a.items())))
    fn = _BLOCK_JIT.get(cache_key)
    if fn is None:
        mm = _ops(precision)
        if following and ffn == MOE:
            fn = jax.jit(lambda p, x, cos, sin, follow, tol:
                         block(a, ffn, p, x, cos, sin, mm, follow, tol))
        else:
            fn = jax.jit(lambda p, x, cos, sin: block(a, ffn, p, x, cos, sin, mm))
        _BLOCK_JIT[cache_key] = fn
    return fn


def pad_length(n: int, buckets=(2048, 8192, 16384)) -> int:
    """Sequences run at a few fixed lengths, so a few programs compile."""
    for b in buckets:
        if n <= b:
            return b
    return -(-n // buckets[-1]) * buckets[-1]


def forward(a: dict, seed: int, sequences, *, last: int, routes=None,
            router_tol: float = 0.0, precision: str = "f32", layers=None,
            buckets=(2048, 8192, 16384)) -> list[dict]:
    """Every sequence (token ids, NumPy) through the whole model, layer
    by layer. ``routes``: per sequence [expert layers, n, k] selections to
    follow (see ``experts_and_shared``). ``layers``: ready-made trees
    ``{"embed", "head", "final_norm", "layers"}`` instead of the makers
    (tests). Returns per sequence ``{"logits" [last, rows] at the last
    ``last`` positions, "routes" [expert layers, n, k] used, "router_gap"
    (largest), "wrong_routes" (tokens beyond ``router_tol``), "states":
    [the n cache rows of each layer, float32]}``, NumPy."""
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
    out = [{"routes": [], "router_gap": 0.0, "wrong_routes": 0, "states": []}
           for _ in sequences]
    expert_layer = 0
    for l, ffn in enumerate(a["ffn_types"]):
        p = make_layer(a, seed, l) if layers is None else layers["layers"][l]
        following = routes is not None and ffn == MOE
        fn = _jitted_block(a, ffn, precision, following)
        for i, n in enumerate(lengths):
            cos, sin = tables[padded[i]]
            if following:
                follow = np.zeros((padded[i], a["experts_per_token"]), np.int32)
                follow[:n] = routes[i][expert_layer]
                xs[i], rows, sel, gap = fn(p, xs[i], cos, sin, jnp.asarray(follow),
                                           np.float32(router_tol))
            else:
                xs[i], rows, sel, gap = fn(p, xs[i], cos, sin)
            out[i]["states"].append(np.asarray(rows)[:n])
            if ffn == MOE:
                gap = np.asarray(gap)[:n]
                out[i]["routes"].append(np.asarray(sel)[:n])
                out[i]["router_gap"] = max(out[i]["router_gap"], float(gap.max()))
                out[i]["wrong_routes"] += int((gap > router_tol).sum())
        expert_layer += ffn == MOE
        del p
    mm = _ops(precision)
    norm_w = final_norm(a) if layers is None else layers["final_norm"]
    table = make_head(a, seed) if layers is None else layers["head"]
    head_fn = jax.jit(lambda t, w, x: head(a, t, w, x, mm))
    for i, n in enumerate(lengths):
        tail = jax.lax.dynamic_slice_in_dim(xs[i], n - last, last, axis=0)
        out[i]["logits"] = np.asarray(head_fn(table, norm_w, tail))
        out[i]["routes"] = np.stack(out[i]["routes"])
    return out
