"""The plain reference of the answer model (``granitemoehybrid``: Mamba-2
+ NoPE grouped-query attention + routed experts with a shared MLP).

Float32 ``jax.numpy`` at ``precision=highest``, one equation a line, no
cache, no chunks (the recurrence is a ``lax.scan`` over time), the routed
experts a dense loop over the held ones with masks; imports nothing of
``pathway_tpu``. The weights are made HERE, layer by layer from a
per-layer key (``make_layer``), bfloat16 matrices as served; the harness
hands the same arrays to the program. ``forward`` streams: it makes layer
l, runs every sequence through it, and lets it go, so 4.76 G parameters
never stand in float32 at once.

The equations (h = hidden, every multiplier from the configuration):

* x0 = embedding_multiplier * E[id]; logits = rmsnorm(x_L) E^T / logits_scaling
* block: x += r * mixer(rmsnorm(x)); u = rmsnorm(x); x += r * (moe(u) + shared(u))
* Mamba-2: [z | xBC | dt] = u W_in; xBC = silu(conv4(xBC) + b); x, B, C = split
  dt = softplus(dt + dt_bias); A = -exp(A_log)
  h_t = exp(dt_t A) h_{t-1} + dt_t x_t (x) B_t;  y_t = h_t C_t + D x_t
  out = (rmsnorm(y * silu(z)) * w) W_out
* attention: q, k, v = u Wq, u Wk, u Wv (32 / 8 / 8 heads of 128); scores
  q.k * attention_multiplier, causal softmax, no positions; out = ctx Wo
* experts: r = u Wr (all experts); the k largest; gates = softmax over those;
  moe = sum over the HELD selected e of g_e W_out,e (silu(a_e) * b_e),
  [a_e | b_e] = W_in,e u; shared: the same form, always on

``precision="fp8"`` is the control: both operands of every matrix product
the program runs in bfloat16 (weights and activations) are rounded to
float8_e4m3fn under a per-tensor scale; norms, softmax, gates, the
recurrence and its state stay float32, as in the program.
"""

from __future__ import annotations

import functools

import numpy as np

MAMBA, ATTENTION = "mamba", "attention"
F32_LEAVES = ("norm1", "norm2", "mixer_norm", "conv_w", "conv_b", "dt_bias", "A_log", "D")


def arch_of(config: dict) -> dict:
    """The sizes the equations need, from the configuration file's keys
    (the published names) and its ``held`` block."""
    held = config["held"]
    a = {
        "hidden": config["hidden_size"],
        "layer_types": tuple(config["layer_types"][: config["num_hidden_layers"]]),
        "heads": config["num_attention_heads"],
        "kv_heads": config["num_key_value_heads"],
        "attention_multiplier": config["attention_multiplier"],
        "mamba_heads": config["mamba_n_heads"], "mamba_head_dim": config["mamba_d_head"],
        "mamba_state": config["mamba_d_state"], "mamba_conv": config["mamba_d_conv"],
        "experts": config["published"]["num_local_experts"],
        "experts_per_token": config["num_experts_per_tok"],
        "experts_held": tuple(held["experts"]),
        "expert_width": config["intermediate_size"],
        "shared_width": config["shared_intermediate_size"],
        "vocab_rows": held["vocab_rows"][1],
        "embedding_multiplier": config["embedding_multiplier"],
        "residual_multiplier": config["residual_multiplier"],
        "logits_scaling": config["logits_scaling"],
        "rms_eps": config["rms_norm_eps"],
        # standard deviation of every random matrix (``assumed``; tests at
        # toy widths state a larger one, or the layers add nothing)
        "init_std": config.get("init_std", 0.02),
    }
    if config["mamba_n_groups"] != 1 or config["position_embedding_type"] != "nope":
        raise ValueError("the reference writes down one group and no positions")
    if held["experts"][1] != config["num_local_experts"] or \
            held["vocab_rows"][1] != config["vocab_size"]:
        raise ValueError("the held block disagrees with the reduced keys")
    a["head_dim"] = a["hidden"] // a["heads"]
    a["mamba_inner"] = a["mamba_heads"] * a["mamba_head_dim"]
    a["conv_width"] = a["mamba_inner"] + 2 * a["mamba_state"]
    return a


def layer_shapes(a: dict, kind: str) -> dict:
    h, held = a["hidden"], a["experts_held"][1]
    shapes = {
        "norm1": (h,), "norm2": (h,), "router": (h, a["experts"]),
        "shared_in": (h, 2 * a["shared_width"]), "shared_out": (a["shared_width"], h),
        "experts_in": (held, h, 2 * a["expert_width"]),
        "experts_out": (held, a["expert_width"], h),
    }
    if kind == MAMBA:
        di, cw = a["mamba_inner"], a["conv_width"]
        shapes.update({
            "in_proj": (h, di + cw + a["mamba_heads"]),
            "conv_w": (a["mamba_conv"], cw), "conv_b": (cw,),
            "dt_bias": (a["mamba_heads"],), "A_log": (a["mamba_heads"],),
            "D": (a["mamba_heads"],), "mixer_norm": (di,), "out_proj": (di, h),
        })
    else:
        qd, kvd = a["heads"] * a["head_dim"], a["kv_heads"] * a["head_dim"]
        shapes.update({"wq": (h, qd), "wk": (h, kvd), "wv": (h, kvd), "wo": (qd, h)})
    return shapes


def param_count(a: dict) -> int:
    total = a["vocab_rows"] * a["hidden"] + a["hidden"]
    for kind in a["layer_types"]:
        total += sum(int(np.prod(s)) for s in layer_shapes(a, kind).values())
    return total


# -- weights, from the seed ----------------------------------------------------------


def _key(seed: int, stream: int):
    import jax

    # "rbg": the device's own bit generator; the default (threefry) took 52 s
    # for the 4.76 G values of one chip's share (my chip run, PR 27)
    key = jax.random.key(stream, impl="rbg")
    key = jax.random.fold_in(key, seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


_MAKERS: dict = {}


def _maker(a: dict, kind: str):
    import jax
    import jax.numpy as jnp

    cache_key = (kind, tuple(sorted((k, v) for k, v in a.items())))
    fn = _MAKERS.get(cache_key)
    if fn is not None:
        return fn
    shapes = sorted(layer_shapes(a, kind).items())

    def make(key):
        out = {}
        for i, (name, shape) in enumerate(shapes):
            k = jax.random.fold_in(key, i)
            if name in ("norm1", "norm2", "mixer_norm"):
                leaf = 1.0 + 0.02 * jax.random.normal(k, shape, jnp.float32)
            elif name == "A_log":
                leaf = jnp.log(jax.random.uniform(k, shape, jnp.float32, 1.0, 16.0))
            elif name == "dt_bias":
                dt = jnp.exp(jax.random.uniform(
                    k, shape, jnp.float32, np.log(1e-3), np.log(1e-1)))
                leaf = dt + jnp.log(-jnp.expm1(-dt))      # softplus^-1
            elif name == "D":
                leaf = jnp.ones(shape, jnp.float32)
            elif name in ("conv_w", "conv_b"):
                leaf = jax.random.uniform(k, shape, jnp.float32, -0.5, 0.5)
            else:
                leaf = (a["init_std"] * jax.random.normal(k, shape, jnp.float32)
                        ).astype(jnp.bfloat16)
            out[name] = leaf
        return out

    fn = _MAKERS[cache_key] = jax.jit(make)
    return fn


def make_layer(a: dict, seed: int, layer: int) -> dict:
    """Layer ``layer``'s weights from its own key, on the device: matrices
    N(0, init_std = 0.02) bfloat16; norm scales 1 + N(0, 0.02); ``A_log`` = log U(1,
    16), ``dt_bias`` = softplus^-1 of a log-uniform step in [1e-3, 1e-1],
    ``D`` = 1, convolution U(-1/2, 1/2) with bias (float32 vectors)."""
    import jax

    return _maker(a, a["layer_types"][layer])(jax.random.fold_in(_key(seed, 11), layer))


@functools.lru_cache(maxsize=None)
def _embed_maker(shape: tuple, std: float):
    import jax
    import jax.numpy as jnp

    return jax.jit(
        lambda key: (std * jax.random.normal(key, shape, jnp.float32)).astype(jnp.bfloat16))


def make_embed(a: dict, seed: int):
    """The held rows of the tied table, N(0, init_std) bfloat16."""
    return _embed_maker((a["vocab_rows"], a["hidden"]), a["init_std"])(_key(seed, 12))


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


def mamba_mixer(a: dict, p: dict, u, n, mm):
    """u [T, h] -> (out [T, h], final state [heads, head_dim, state] after
    position n - 1): the recurrence one position at a time."""
    import jax
    import jax.numpy as jnp

    T, di, N, H = u.shape[0], a["mamba_inner"], a["mamba_state"], a["mamba_heads"]
    K, cw = a["mamba_conv"], a["conv_width"]
    proj = mm("td,de->te", u, p["in_proj"])
    z, xbc, dt = proj[:, :di], proj[:, di:di + cw], proj[:, di + cw:]
    ext = jnp.concatenate([jnp.zeros((K - 1, cw), jnp.float32), xbc])
    xbc = jax.nn.silu(p["conv_b"] + sum(p["conv_w"][j] * ext[j:j + T] for j in range(K)))
    x = xbc[:, :di].reshape(T, H, -1)
    B, C = xbc[:, di:di + N], xbc[:, di + N:]
    dt = jax.nn.softplus(dt + p["dt_bias"])
    dt = jnp.where((jnp.arange(T) < n)[:, None], dt, 0.0)    # padding moves no state
    A = -jnp.exp(p["A_log"])

    def step(h, t):
        x_t, B_t, C_t, dt_t = t
        h = jnp.exp(dt_t * A)[:, None, None] * h + (dt_t[:, None] * x_t)[:, :, None] * B_t
        return h, jnp.sum(h * C_t, axis=-1)

    h0 = jnp.zeros((H, x.shape[-1], N), jnp.float32)
    h, y = jax.lax.scan(step, h0, (x, B, C, dt))
    y = (y + p["D"][:, None] * x).reshape(T, di)
    y = rms_norm(y * jax.nn.silu(z), p["mixer_norm"], a["rms_eps"])
    return mm("td,de->te", y, p["out_proj"]), h


def attention_mixer(a: dict, p: dict, u, mm):
    import jax
    import jax.numpy as jnp

    T, hd, kv = u.shape[0], a["head_dim"], a["kv_heads"]
    g = a["heads"] // kv
    q = mm("td,de->te", u, p["wq"]).reshape(T, kv, g, hd)
    k = mm("td,de->te", u, p["wk"]).reshape(T, kv, hd)
    v = mm("td,de->te", u, p["wv"]).reshape(T, kv, hd)
    s = mm("tkgd,pkd->kgtp", q, k) * a["attention_multiplier"]
    causal = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]
    w = jax.nn.softmax(jnp.where(causal, s, jnp.finfo(jnp.float32).min), axis=-1)
    ctx = mm("kgtp,pkd->tkgd", w, v).reshape(T, a["heads"] * hd)
    return mm("td,de->te", ctx, p["wo"])


def glu(x, width):
    import jax

    return jax.nn.silu(x[..., :width]) * x[..., width:]


def experts_and_shared(a: dict, p: dict, u, mm, follow=None, tol: float = 0.0):
    """moe(u) + shared(u) over the held experts, and the routing's record.
    ``follow`` [T, k]: another computation's selections; a token follows
    them where every one of them has a router logit within ``tol`` of
    this router's k-th best (a near-tie then does not cascade), and else
    keeps its own and is counted. Returns (sum [T, h], selections used
    [T, k], gap [T]: k-th best logit less the least followed one, over
    the spread of the token's router logits)."""
    import jax
    import jax.numpy as jnp

    k = a["experts_per_token"]
    first, n_held = a["experts_held"]
    r = mm("td,de->te", u, p["router"])
    top, sel = jax.lax.top_k(r, k)
    gap = jnp.zeros(u.shape[0], jnp.float32)
    if follow is not None:
        theirs = jnp.take_along_axis(r, follow, axis=-1)
        gap = jnp.maximum(top[:, -1] - jnp.min(theirs, axis=-1), 0.0) / (
            jnp.max(r, axis=-1) - jnp.min(r, axis=-1))
        ok = (gap <= tol)[:, None]
        sel, top = jnp.where(ok, follow, sel), jnp.where(ok, theirs, top)
    gates = jax.nn.softmax(top, axis=-1)
    # gate of expert e for each token, 0 where it was not selected
    dense = jnp.sum(
        jnp.where(sel[:, :, None] == jnp.arange(a["experts"])[None, None, :],
                  gates[:, :, None], 0.0), axis=1)            # [T, experts]
    held_gates = dense[:, first:first + n_held].T               # [held, T]

    def one(acc, scanned):
        w_in, w_out, g = scanned
        y = mm("tw,wd->td", glu(mm("td,dw->tw", u, w_in), a["expert_width"]), w_out)
        return acc + g[:, None] * y, None

    routed, _ = jax.lax.scan(
        one, jnp.zeros_like(u), (p["experts_in"], p["experts_out"], held_gates))
    shared = mm("tw,wd->td", glu(mm("td,dw->tw", u, p["shared_in"]), a["shared_width"]),
                p["shared_out"])
    return routed + shared, sel, gap


def block(a: dict, kind: str, p: dict, x, n, mm, follow=None, tol: float = 0.0):
    """One layer over one sequence x [T, h] of n real positions:
    (x, final SSM state or None, selections used, router gap [T])."""
    r = a["residual_multiplier"]
    u = rms_norm(x, p["norm1"], a["rms_eps"])
    if kind == MAMBA:
        out, state = mamba_mixer(a, p, u, n, mm)
    else:
        out, state = attention_mixer(a, p, u, mm), None
    x = x + r * out
    u = rms_norm(x, p["norm2"], a["rms_eps"])
    both, sel, gap = experts_and_shared(a, p, u, mm, follow, tol)
    return x + r * both, state, sel, gap


def embed(a: dict, table, ids):
    import jax.numpy as jnp

    return a["embedding_multiplier"] * table[ids].astype(jnp.float32)


def head(a: dict, table, norm_w, x, mm):
    return mm("td,vd->tv", rms_norm(x, norm_w, a["rms_eps"]), table) / a["logits_scaling"]


# -- the streamed forward ------------------------------------------------------------------

_BLOCK_JIT: dict = {}


def _jitted_block(a: dict, kind: str, precision: str, following: bool):
    import jax

    cache_key = (kind, precision, following, tuple(sorted(a.items())))
    fn = _BLOCK_JIT.get(cache_key)
    if fn is None:
        mm = _ops(precision)
        if following:
            fn = jax.jit(lambda p, x, n, follow, tol: block(a, kind, p, x, n, mm, follow, tol))
        else:
            fn = jax.jit(lambda p, x, n: block(a, kind, p, x, n, mm))
        _BLOCK_JIT[cache_key] = fn
    return fn


def pad_length(n: int, buckets=(1024, 2048, 4096)) -> int:
    """Sequences run at a few fixed lengths, so a few programs compile."""
    for b in buckets:
        if n <= b:
            return b
    return -(-n // buckets[-1]) * buckets[-1]


def forward(a: dict, seed: int, sequences, *, last: int, routes=None,
            router_tol: float = 0.0, precision: str = "f32", layers=None,
            buckets=(1024, 2048, 4096)) -> list[dict]:
    """Every sequence (token ids, NumPy) through the whole model, layer
    by layer. ``routes``: per sequence [layers, n, k] selections to follow
    (see ``experts_and_shared``). ``layers``: ready-made layer trees
    instead of ``make_layer`` (tests). Returns per sequence ``{"logits"
    [last, rows] at the last ``last`` positions, "routes" [layers, n, k]
    used, "router_gap" (largest), "wrong_routes" (tokens beyond
    ``router_tol``), "states": [final SSM state of each Mamba layer]}``,
    NumPy."""
    import jax
    import jax.numpy as jnp

    table = make_embed(a, seed) if layers is None else layers["embed"]
    lengths = [len(s) for s in sequences]
    padded = [pad_length(n, buckets) for n in lengths]
    xs = []
    for s, L in zip(sequences, padded):
        ids = np.zeros(L, np.int32)
        ids[:len(s)] = s
        xs.append(embed(a, table, jnp.asarray(ids)))
    out = [{"routes": [], "router_gap": 0.0, "wrong_routes": 0, "states": []}
           for _ in sequences]
    for l, kind in enumerate(a["layer_types"]):
        p = make_layer(a, seed, l) if layers is None else layers["layers"][l]
        fn = _jitted_block(a, kind, precision, routes is not None)
        for i, n in enumerate(lengths):
            if routes is not None:
                follow = np.zeros((padded[i], a["experts_per_token"]), np.int32)
                follow[:n] = routes[i][l]
                xs[i], state, sel, gap = fn(p, xs[i], np.int32(n), jnp.asarray(follow),
                                            np.float32(router_tol))
            else:
                xs[i], state, sel, gap = fn(p, xs[i], np.int32(n))
            gap = np.asarray(gap)[:n]
            out[i]["routes"].append(np.asarray(sel)[:n])
            out[i]["router_gap"] = max(out[i]["router_gap"], float(gap.max()))
            out[i]["wrong_routes"] += int((gap > router_tol).sum())
            if state is not None:
                out[i]["states"].append(np.asarray(state))
        del p
    mm = _ops(precision)
    norm_w = final_norm(a) if layers is None else layers["final_norm"]
    head_fn = jax.jit(lambda t, w, x: head(a, t, w, x, mm))
    for i, n in enumerate(lengths):
        tail = jax.lax.dynamic_slice_in_dim(xs[i], n - last, last, axis=0)
        out[i]["logits"] = np.asarray(head_fn(table, norm_w, tail))
        out[i]["routes"] = np.stack(out[i]["routes"])
    return out
