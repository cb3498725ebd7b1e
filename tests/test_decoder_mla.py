"""The ``deepseek_v2`` family of models/decoder.py (latent attention with
its two paths, the group-limited router beside shared experts, a leading
dense layer, an untied head) against the plain float32 reference
(benchmark/reference_deepseek_v2.py) at a tiny size on the CPU: three MLA
layers (dense, experts, experts), 16 experts in 4 groups of which the best
2 stand, top-3, four shares of 4, a 64-row slice of 128 rows."""

import json
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

BENCH = os.path.join(os.path.dirname(__file__), "..", "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import reference_deepseek_v2 as ref  # noqa: E402

from pathway_tpu.models import decoder as dec  # noqa: E402

CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
SEED = 11
TINY = {
    "model_type": "deepseek_v2", "hidden_size": 32, "num_hidden_layers": 3,
    "first_k_dense_replace": 1, "intermediate_size": 48, "moe_intermediate_size": 16,
    "moe_layer_freq": 1, "n_group": 4, "topk_group": 2, "n_routed_experts": 4,
    "n_shared_experts": 2, "norm_topk_prob": False, "num_attention_heads": 4,
    "num_key_value_heads": 4, "num_experts_per_tok": 3, "q_lora_rank": 24,
    "kv_lora_rank": 16, "qk_nope_head_dim": 8, "qk_rope_head_dim": 4, "v_head_dim": 8,
    "rms_norm_eps": 1e-6, "rope_theta": 10000, "routed_scaling_factor": 4,
    "rope_scaling": {"type": "yarn", "factor": 4, "original_max_position_embeddings": 16,
                     "beta_fast": 4, "beta_slow": 1, "mscale": 0.707, "mscale_all_dim": 0.707},
    "scoring_func": "softmax", "topk_method": "group_limited_greedy",
    "tie_word_embeddings": False, "vocab_size": 64,
    # at hidden 32 the published N(0, 0.02) would leave every layer's output
    # far below the embedding's: the same products of width and deviation
    "init_std": 0.2,
    "published": {"num_hidden_layers": 5, "n_routed_experts": 16, "vocab_size": 128},
    "held": {"experts": [0, 4], "vocab_rows": [0, 64]},
}
SERVING = dict(prefill_chunk=16, max_positions=64, slots=4, decode_block=16)


def config_of(tiny: dict) -> dec.DecoderConfig:
    return dec.DecoderConfig.from_hf(
        {**tiny, **tiny["published"]}, layers=tiny["num_hidden_layers"],
        experts_held=tuple(tiny["held"]["experts"]),
        vocab_held=tuple(tiny["held"]["vocab_rows"]), **SERVING,
    )


def weights_of(arch: dict, seed: int = SEED) -> dict:
    """The reference's weights: the program takes them as they are."""
    return {"layers": [ref.make_layer(arch, seed, l) for l in range(len(arch["layer_types"]))],
            "embed": ref.make_embed(arch, seed), "head": ref.make_head(arch, seed),
            "final_norm": ref.final_norm(arch)}


@pytest.fixture(scope="module")
def world():
    arch = ref.arch_of(TINY)
    return arch, config_of(TINY), weights_of(arch)


def rel(got, want) -> float:
    want = np.asarray(want, np.float32)
    return float(np.max(np.abs(np.asarray(got, np.float32) - want))
                 / (np.max(want) - np.min(want)))


def reference_of(arch, w, gen):
    """The reference's full forward over prompt + answer, teacher-forced
    on the program's ids, following its selections."""
    ids = np.concatenate([gen.prompt, gen.tokens[:-1]])
    routes = np.concatenate([gen.prompt_routes, gen.decode_routes], axis=1)
    return ref.forward(arch, SEED, [ids], last=len(gen.tokens), layers=w,
                       routes=[routes], router_tol=0.05, buckets=(32, 64))[0]


# bf16 operands and a bf16 cache against float32 read 0.003-0.017 of this
# tiny model's logit spread (gates of 4 x a score); a cache whose rotary half is lost far more
TOL = 0.025


def test_shapes_and_counts_match_the_reference(world):
    arch, cfg, w = world
    assert cfg.layers == tuple(zip(arch["layer_types"], arch["ffn_types"]))
    for ffn in ("dense", "moe"):
        assert dec.layer_shapes(cfg, "mla", ffn) == ref.layer_shapes(arch, "mla", ffn)
    leaves = jax.tree_util.tree_leaves(w)
    assert sum(int(np.prod(x.shape)) for x in leaves) == ref.param_count(arch)
    assert dec.param_bytes(cfg) == sum(x.nbytes for x in leaves)
    mine = dec.init_params(cfg, 3)
    assert jax.tree_util.tree_map(lambda x: (x.shape, x.dtype), mine) == \
        jax.tree_util.tree_map(lambda x: (x.shape, x.dtype), w)


@pytest.mark.parametrize("cfg", [dec.DecoderConfig.tiny(), dec.DecoderConfig.tiny_mla()],
                         ids=["mamba+attention", "mla"])
def test_cache_bytes_are_what_empty_state_allocates(cfg):
    """(f) for all three kinds of per-sequence state."""
    state = dec.empty_state(cfg)
    assert {name for layer in state for name in layer} == (
        {"latent"} if cfg.layer_types[0] == "mla" else {"tail", "ssm", "keys", "values"})
    assert dec.cache_bytes(cfg) == sum(x.nbytes for x in jax.tree_util.tree_leaves(state))


@pytest.mark.parametrize("lengths", [(5,), (16,), (37,), (40, 3, 17)],
                         ids=["inside", "on-a-chunk", "three-chunks", "unequal-batch"])
def test_prefill_then_decode_equals_reference_full_forward(world, lengths):
    """(a) chunked prefill writing the latent cache, then decode through it
    on the absorbed path, against one full forward with no cache: logits
    at every generated position, the routes, and the final cache rows."""
    arch, cfg, w = world
    model = dec.AnswerModel(cfg, w)
    rng = np.random.default_rng(sum(lengths))
    prompts = [rng.integers(1, 64, size=n) for n in lengths]
    made = model.generate(prompts, 6, keep=range(len(prompts)))
    for gen in made:
        want = reference_of(arch, w, gen)
        assert gen.logits.shape == want["logits"].shape == (6, 64)
        assert rel(gen.logits, want["logits"]) < TOL
        assert want["wrong_routes"] == 0
        assert gen.prompt_routes.shape == (2, len(gen.prompt), 3)       # a row an expert layer
        assert np.array_equal(want["routes"],
                              np.concatenate([gen.prompt_routes, gen.decode_routes], axis=1))
        assert np.array_equal(gen.tokens, gen.logits.argmax(axis=-1))
        n = len(gen.prompt) + 5
        assert len(gen.latent) == len(want["states"]) == 3
        for got, rows in zip(gen.latent, want["states"]):
            assert got.shape == (cfg.max_positions, cfg.latent_width) and rows.shape[0] == n
            assert rel(np.asarray(got, np.float32)[:n], rows) < TOL
            assert not np.asarray(got, np.float32)[n:].any()   # nothing written past the end


def test_a_cache_without_its_rotary_half_is_seen(world):
    """The same comparison with k_r zeroed in the cache reads far above
    the tolerance: the tolerance tests something."""
    arch, cfg, w = world
    model = dec.AnswerModel(cfg, w)
    inner = model._prefill

    def no_rotary_key(p, state, slot, ids, pos, n):
        state, *out = inner(p, state, slot, ids, pos, n)
        return [{"latent": s["latent"].at[..., cfg.kv_rank:].set(0)} for s in state], *out

    model._prefill = no_rotary_key
    prompt = np.random.default_rng(1).integers(1, 64, size=40)
    gen = model.generate([prompt], 6, keep=[0])[0]
    assert rel(gen.logits, reference_of(arch, w, gen)["logits"]) > 2 * TOL


def test_absorbed_decode_equals_expanded_attention(world):
    """(b) one token through ``mla_decode`` (w_ukv absorbed into the query
    and the output, scores over the latent rows) and through
    ``mla_prefill`` as a chunk of one real position (keys and values
    expanded), on the same weights and the same cache."""
    arch, cfg, w = world
    p = w["layers"][1]
    rng = jax.random.PRNGKey(2)
    latent = jnp.zeros((cfg.slots + 1, cfg.max_positions, cfg.latent_width), jnp.bfloat16)
    T = cfg.prefill_chunk
    for at in (0, T):       # two chunks of context in slot 2
        u = jax.random.normal(jax.random.fold_in(rng, at), (T, cfg.hidden), jnp.float32)
        _, latent = dec.mla_prefill(cfg, p, u, latent, 2, at, T)
    u1 = jax.random.normal(jax.random.fold_in(rng, 99), (1, cfg.hidden), jnp.float32)
    pos = 2 * T
    chunk = jnp.concatenate([u1, jnp.zeros((T - 1, cfg.hidden))])
    expanded, after_e = dec.mla_prefill(cfg, p, chunk, latent, 2, pos, 1)
    absorbed, after_a = dec.mla_decode(
        cfg, p, u1, latent, jnp.asarray([2]), jnp.asarray([pos]))
    assert np.array_equal(np.asarray(after_e, np.float32), np.asarray(after_a, np.float32))
    assert rel(absorbed[0], expanded[0]) < 0.01
    assert float(jnp.max(jnp.abs(expanded[0]))) > 0      # and neither is trivially zero


def plain_mla_prefill(cfg, p, u, latent, slot, pos, n):
    """``mla_prefill`` stated plainly, a block of cached rows a trip of a
    loop, every block's float32 scores of every head a whole array: what
    the kernel is held to."""
    T, H, rk = u.shape[0], cfg.heads, cfg.kv_rank
    q_nope, q_rope, rows, _ = dec._mla_project(cfg, p, u, pos + jnp.arange(T))
    old = jax.lax.dynamic_slice(latent, (slot, pos, 0), (1, T, cfg.latent_width))
    real = (jnp.arange(T) < n)[None, :, None]
    latent = jax.lax.dynamic_update_slice(
        latent, jnp.where(real, rows[None], old), (slot, pos, 0))
    carry = (jnp.full((H, T), dec._MASKED, jnp.float32), jnp.zeros((H, T), jnp.float32),
             jnp.zeros((H, T, cfg.v_dim), jnp.float32))
    for b in range(pos // T + 1):
        rows_b = latent[slot, b * T:(b + 1) * T]
        kv = dec._mm(rows_b[:, :rk], p["w_ukv"]).astype(jnp.bfloat16).reshape(
            T, H, cfg.nope_dim + cfg.v_dim)
        s = jnp.einsum("thd,phd->htp", q_nope, kv[..., :cfg.nope_dim],
                       preferred_element_type=jnp.float32)
        s = s + jnp.einsum("thr,pr->htp", q_rope, rows_b[:, rk:],
                           preferred_element_type=jnp.float32)
        visible = (b * T + jnp.arange(T))[None, :] <= (pos + jnp.arange(T))[:, None]
        carry = dec._softmax_step(
            carry, s * dec.mla_scale(cfg), visible[None],
            lambda w: jnp.einsum("htp,phd->htd", w, kv[..., cfg.nope_dim:],
                                 preferred_element_type=jnp.float32))
    _, l, acc = carry
    out = jnp.transpose(acc / l[..., None], (1, 0, 2)).reshape(T, H * cfg.v_dim)
    return dec._mm(out, p["wo"]), latent


@pytest.mark.parametrize("slot, chunks, n", [(0, 0, 16), (0, 3, 16), (0, 2, 5), (3, 1, 16)],
                         ids=["first-chunk", "several-cached-blocks", "padded", "another-slot"])
def test_the_prefill_kernel_equals_the_plain_block_loop(world, slot, chunks, n):
    """The Pallas kernel (interpreted here) against the plain statement:
    ``chunks`` chunks of context in ``slot``, then one chunk of ``n`` real
    positions through both. The output within a bfloat16 step of the
    values that go into the output product, the slab equal bit for bit,
    the other slots untouched."""
    _, cfg, w = world
    p, T = w["layers"][1], cfg.prefill_chunk
    assert dec.mla_lowering() == "interpret"
    key = jax.random.PRNGKey(7 + slot + chunks)
    latent = jnp.zeros((cfg.slots + 1, cfg.max_positions, cfg.latent_width), jnp.bfloat16)
    for at in range(chunks):
        u = jax.random.normal(jax.random.fold_in(key, at), (T, cfg.hidden), jnp.float32)
        _, latent = plain_mla_prefill(cfg, p, u, latent, slot, at * T, T)
    u = jax.random.normal(jax.random.fold_in(key, 99), (T, cfg.hidden), jnp.float32)
    pos = chunks * T
    want, slab_want = plain_mla_prefill(cfg, p, u, latent, slot, pos, n)
    got, slab_got = jax.jit(
        lambda u, latent, slot, pos, n: dec.mla_prefill(cfg, p, u, latent, slot, pos, n)
    )(u, latent, jnp.int32(slot), jnp.int32(pos), jnp.int32(n))
    assert np.array_equal(np.asarray(slab_got).view(np.uint16),
                          np.asarray(slab_want).view(np.uint16))
    written = np.asarray(slab_got, np.float32)
    assert written[slot, pos:pos + n].any() and not written[slot, pos + n:].any()
    assert not np.delete(written, slot, axis=0).any()
    assert np.isfinite(np.asarray(got)).all()               # the padded rows too
    assert rel(got[:n], want[:n]) < 2 ** -8
    assert float(jnp.max(jnp.abs(want[:n]))) > 0


def test_granites_prefill_lowers_without_a_kernel():
    """The control cell's program: no mixer of ``DecoderConfig.tiny()``
    (Granite's kinds) reaches the kernel: its jaxpr has no
    ``pallas_call`` and its text no custom call; ``tiny_mla``'s jaxpr has
    one a layer (the interpreter leaves the text no name to find)."""
    def traced(cfg):
        params = jax.eval_shape(lambda: dec.init_params(cfg, 0))
        state = jax.eval_shape(lambda: dec.empty_state(cfg))
        i32 = jax.ShapeDtypeStruct((), jnp.int32)
        ids = jax.ShapeDtypeStruct((cfg.prefill_chunk,), jnp.int32)
        return jax.jit(
            lambda p, s, slot, ids, pos, n: dec.prefill_chunk(cfg, p, s, slot, ids, pos, n)
        ).trace(params, state, i32, ids, i32, i32)

    granite = traced(dec.DecoderConfig.tiny())
    assert "pallas_call" not in str(granite.jaxpr)
    text = granite.lower().as_text()
    assert "custom_call" not in text and "pallas" not in text.lower()
    assert str(traced(dec.DecoderConfig.tiny_mla()).jaxpr).count("pallas_call[") == 3


def test_the_prefill_span_says_blocks_and_the_first_dispatch_the_lowering(world):
    from pathway_tpu.internals import flight

    _, cfg, w = world
    t0 = flight._time.monotonic_ns()
    model = dec.AnswerModel(cfg, w)
    model.generate([np.arange(1, 41)], 2)
    model.generate([np.arange(1, 20)], 2)
    chunks = [flight.args_of(s) for s in flight.spans_between(t0, flight._time.monotonic_ns())
              if s[1] == "answer.prefill"]
    # 40 positions in chunks of 16, then 19: a chunk at 16 k attends k + 1 blocks a layer
    assert [a["blocks"] for a in chunks] == [1, 2, 3, 1, 2]
    assert [a.get("kernel") for a in chunks] == ["interpret", None, None, None, None]
    assert chunks[0]["first"] and not chunks[1]["first"]
    # a model without latent attention says neither
    t0 = flight._time.monotonic_ns()
    dec.AnswerModel(dec.DecoderConfig.tiny()).generate([[1, 2, 3]], 2)
    plain = [flight.args_of(s) for s in flight.spans_between(t0, flight._time.monotonic_ns())
             if s[1] == "answer.prefill"]
    assert plain and all("blocks" not in a and "kernel" not in a for a in plain)


def _router_inputs(arch, p, tokens=48):
    x = jax.random.normal(jax.random.PRNGKey(5), (tokens, arch["hidden"]), jnp.float32)
    return ref.rms_norm(x, p["norm2"], arch["rms_eps"])


def test_group_limited_routing_matches_the_reference(world):
    """(c) softmax over all 16, the best 2 groups of 4 stand, top-3 of
    those, gates the scores times routed_scaling, not renormalised."""
    arch, cfg, w = world
    p = w["layers"][1]
    u = _router_inputs(arch, p)
    live = jnp.ones(u.shape[0], bool)
    sel, gates, held = dec.route(cfg, p, u, live)
    # on the program's own router logits the reference's choice is the program's
    s, want_sel, stands = ref.choose(arch, dec._mm(u, p["router"]))
    assert np.array_equal(np.asarray(sel), np.asarray(want_sel))
    # and on the reference's float32 logits it lies within the router tolerance
    _, used, gap = ref.experts_and_shared(arch, p, u, ref._ops("f32"), follow=sel, tol=0.02)
    assert np.array_equal(np.asarray(used), np.asarray(sel)) and float(gap.max()) <= 0.02
    sel, s, stands = np.asarray(sel), np.asarray(s), np.asarray(stands)
    assert (stands.sum(axis=1) == 2).all()
    assert stands[np.arange(len(sel))[:, None], sel // 4].all()         # only standing groups
    assert (np.asarray(held) == (sel < 4)).all()
    assert np.allclose(np.asarray(gates), 4.0 * np.take_along_axis(s, sel, axis=1), rtol=1e-6)
    assert not np.allclose(np.asarray(gates).sum(axis=1), 1.0, atol=0.05)   # not renormalised
    # a group's score is its best expert's, so the best expert's group always
    # stands and the best expert is the first selected
    assert (sel[:, 0] == s.argmax(axis=1)).all()
    # some token's k best overall do not all stand: the groups limit the choice
    assert (np.sort(sel) != np.sort(np.argsort(-s)[:, :3])).any()


def test_a_tie_between_groups_is_followed_and_a_wrong_group_is_not(world):
    """(c) the reference follows a selection whose group lies within the
    tolerance of its own last standing group, and counts one that does not."""
    arch, _, w = world
    mm = ref._ops("f32")
    # router logits by hand: groups 0..3 hold experts 4g..4g+3
    r = np.full((2, 16), -4.0, np.float32)
    r[:, 0], r[:, 1] = 3.0, 2.0                 # group 0 stands, clear
    r[0, 4], r[0, 8] = 1.000, 1.001             # token 0: groups 1 and 2 tie for second
    r[1, 4], r[1, 8] = 0.0, 1.0                 # token 1: group 2 stands, clear
    p = dict(w["layers"][1])
    # u = identity rows picked so that u @ router == r: solve by least squares
    router = np.asarray(p["router"], np.float32)
    u = jnp.asarray(np.linalg.lstsq(router.T, r.T, rcond=None)[0].T)
    got = np.asarray(mm("td,de->te", u, p["router"]))
    assert np.abs(got - r).max() < 1e-3
    own = np.asarray(ref.choose(arch, jnp.asarray(got))[1])
    assert sorted(own[0].tolist()) == [0, 1, 8] and sorted(own[1].tolist()) == [0, 1, 8]
    follow = jnp.asarray([[0, 1, 4], [0, 1, 4]], jnp.int32)   # group 1's expert instead
    _, used, gap = ref.experts_and_shared(arch, p, u, mm, follow=follow, tol=0.02)
    used, gap = np.asarray(used), np.asarray(gap)
    assert used[0].tolist() == [0, 1, 4] and gap[0] <= 0.02      # the tie is followed
    assert sorted(used[1].tolist()) == [0, 1, 8] and gap[1] > 0.1    # the wrong group is not


def test_four_shares_and_the_shared_experts_once_add_up_to_the_uncut_layer():
    """(d) the share tied to the model: experts 0-3, 4-7, 8-11 and 12-15
    on four chips, the shared experts counted once, give the whole
    layer's routed + shared sum of the uncut reference."""
    whole = {**TINY, "n_routed_experts": 16, "held": {"experts": [0, 16], "vocab_rows": [0, 64]}}
    arch = ref.arch_of(whole)
    p = ref.make_layer(arch, SEED, 1)
    u = _router_inputs(arch, p, 32)
    live = jnp.ones(u.shape[0], bool)
    # every share routes alike; the reference follows within the router tolerance
    served = dec.route(config_of(TINY), p, u, live)[0]
    want, sel, gap = ref.experts_and_shared(
        arch, p, u, ref._ops("f32"), follow=served, tol=0.02)
    assert float(gap.max()) <= 0.02
    parts, shared = [], None
    for first in (0, 4, 8, 12):
        cfg = config_of({**TINY, "held": {"experts": [first, 4], "vocab_rows": [0, 64]}})
        share = {**p, "experts_in": p["experts_in"][first:first + 4],
                 "experts_out": p["experts_out"][first:first + 4]}
        s, gates, held = dec.route(cfg, share, u, live)
        routed, _ = dec.routed_experts(cfg, share, u, s, gates, held)
        parts.append(routed)
        shared = dec.shared_mlp(cfg, share, u)
        assert np.array_equal(np.asarray(s), np.asarray(sel))
    assert rel(sum(parts) + shared, want) < 0.02
    # no three shares are the layer, and the shared experts are not counted four times
    assert rel(sum(parts[:3]) + shared, want) > 0.05
    assert rel(sum(parts) + 4 * shared, want) > 0.05


@pytest.fixture(scope="module")
def published() -> dict:
    with open(CATALOG) as f:
        return next(r for r in map(json.loads, f) if r["name"] == "DeepSeek-V2")["config"]


def test_from_hf_gives_the_published_widths(published):
    """(g) on the catalog's config; the cut of the benchmark's configuration."""
    cfg = dec.DecoderConfig.from_hf(published, max_positions=16384)
    assert (cfg.hidden, cfg.heads, cfg.q_rank, cfg.kv_rank) == (5120, 128, 1536, 512)
    assert (cfg.nope_dim, cfg.rope_dim, cfg.v_dim, cfg.latent_width) == (128, 64, 128, 576)
    assert cfg.layers == (("mla", "dense"),) + (("mla", "moe"),) * 59
    assert (cfg.dense_width, cfg.expert_width, cfg.shared_width) == (12288, 1536, 3072)
    assert (cfg.experts, cfg.experts_per_token, cfg.experts_held) == (160, 6, (0, 160))
    assert (cfg.router_groups, cfg.router_top_groups, cfg.routed_scaling) == (8, 3, 16.0)
    assert not cfg.tied_head and cfg.vocab_held == (0, 102400) and cfg.rms_eps == 1e-6
    assert (cfg.embedding_multiplier, cfg.residual_multiplier, cfg.logits_scaling) == (1, 1, 1)
    cut = dec.DecoderConfig.from_hf(published, layers=5, experts_held=(0, 40),
                                    vocab_held=(0, 25600), max_positions=16384)
    shapes = dec.layer_shapes(cut, "mla", "moe")
    mla = sum(int(np.prod(shapes[k])) for k in ("w_dq", "w_uq", "w_dkv", "w_ukv", "wo"))
    assert mla == 149_225_472                                           # the issue's arithmetic
    matrices = sum(
        int(np.prod(s)) for kind, ffn in cut.layers
        for s in dec.layer_shapes(cut, kind, ffn).values() if len(s) > 1)
    assert matrices + 2 * 25600 * 5120 == 5_163_909_120
    assert abs(dec.param_bytes(cut) - 10.33e9) < 0.01e9
    # 9 slots x 16,384 positions x 5 layers x 1,152 B
    assert dec.cache_bytes(cut) == 9 * 16384 * 5 * 1152


def test_from_hf_refuses_what_is_not_written_down(published):
    with pytest.raises(ValueError, match="model_type"):
        dec.DecoderConfig.from_hf({**published, "model_type": "deepseek_v3"})
    with pytest.raises(ValueError, match="yarn"):
        dec.DecoderConfig.from_hf({**published, "rope_scaling": None})
    with pytest.raises(ValueError, match="router"):
        dec.DecoderConfig.from_hf({**published, "topk_method": "greedy"})


def test_yarn_frequencies_and_scale_at_the_published_keys(published):
    """(e) against constants computed by hand: low = 10, high = 23 of the 32
    pairs; f_i = 10^(-i / 8); mscale(40, 0.707) = 1.2608038."""
    cfg = dec.DecoderConfig.from_hf(published, max_positions=16384)
    f = dec.yarn_inv_freq(cfg)
    assert f.shape == (32,)
    by_hand = {0: 1.0, 10: 10 ** -1.25, 16: 0.01 * (7 / 13) + 0.01 / 40 * (6 / 13),
               23: 10 ** -2.875 / 40, 31: 10 ** -3.875 / 40}
    for i, want in by_hand.items():
        assert abs(f[i] / want - 1) < 1e-9, i
    assert (np.diff(f) < 0).all()
    assert abs(dec.mla_scale(cfg) - 0.1147214) < 1e-6
    # the reference computes the same from the configuration's keys
    arch = ref.arch_of({**published, "published": published, "num_hidden_layers": 5,
                        "held": {"experts": [0, 160], "vocab_rows": [0, 102400]}})
    assert np.allclose(ref.yarn_inv_freq(arch), f, rtol=1e-12)
    assert abs(ref.softmax_scale(arch) - dec.mla_scale(cfg)) < 1e-12
    # the rotation keeps a pair's length and turns dims 2i, 2i + 1 together
    x = jnp.asarray(np.random.default_rng(0).normal(size=(3, 64)), jnp.float32)
    turned = np.asarray(dec._rotate(cfg, x, jnp.asarray([0, 7, 16000])))
    assert np.allclose(turned[0], np.concatenate([x[0, 0::2], x[0, 1::2]]), atol=1e-6)
    pairs = np.asarray(x[:, 0::2]) ** 2 + np.asarray(x[:, 1::2]) ** 2
    assert np.allclose(turned[:, :32] ** 2 + turned[:, 32:] ** 2, pairs, rtol=1e-4)
    cos, sin = ref.rope_tables(arch, 16001)
    want = np.asarray(ref.rotate(x[2], cos[16000], sin[16000]))
    assert np.abs(turned[2] - want).max() < 5e-3      # float32 angles at position 16,000


def test_counters_and_spans_say_what_attention_went_over(world):
    from pathway_tpu.internals import flight

    _, cfg, w = world
    model = dec.AnswerModel(cfg, w)
    t0 = flight._time.monotonic_ns()
    rng = np.random.default_rng(6)
    model.generate([rng.integers(1, 64, size=n) for n in (20, 9)], 5)
    c = model.counters
    assert c.expert_tokens.shape == (2, 4)                     # a row an expert layer
    assert c.held_selections + c.absent_selections == (29 + 2 * 4) * 3 * 2
    assert c.attended_positions_prefill == 20 * 21 // 2 + 9 * 10 // 2
    assert c.attended_positions_decode == sum(n + t + 1 for n in (20, 9) for t in range(4))
    assert c.latent_rows == 3 * (29 + 2 * 4)
    spans = flight.spans_between(t0, flight._time.monotonic_ns())
    chunks = [flight.args_of(s) for s in spans if s[1] == "answer.prefill"]
    steps = [flight.args_of(s) for s in spans if s[1] == "answer.decode.step"]
    # the chunk's context: the positions its last real query attends
    assert [(a["real"], a["context"]) for a in chunks] == [(16, 16), (4, 20), (9, 9)]
    assert [a["positions"] for a in steps] == [31 + 2 * (t + 1) - 2 for t in range(4)]


def test_tpuchat_under_a_slice_smaller_than_the_asset_stays_inside_it():
    """(h) the tokenizer stays WordPiece, a piece whose id lies outside the
    held rows becomes [UNK] and is counted; a toy slice keeps the hash
    tokenizer."""
    from pathway_tpu.internals import flight
    from pathway_tpu.models.wordpiece import WordPieceTokenizer
    from pathway_tpu.xpacks.llm.llms import TPUChat

    class Model:        # TPUChat reads the geometry and calls generate
        def __init__(self, rows):
            self.cfg = dec.DecoderConfig(vocab_held=(0, rows), max_positions=512)
            self.seen = []

        def generate(self, prompts, max_new_tokens):
            self.seen.extend(prompts)
            return [dec.Generation(prompt=p, tokens=np.asarray([5, 6])) for p in prompts]

    model = Model(25600)
    chat = TPUChat(model, max_new_tokens=2)
    assert isinstance(chat.tokenizer, WordPieceTokenizer)
    assert chat.tokenizer.vocab_size > 25600
    late = [piece for piece, i in chat.tokenizer.vocab.items() if i >= 25600 and piece.isalpha()]
    early = [piece for piece, i in chat.tokenizer.vocab.items()
             if 1000 <= i < 25600 and piece.isalpha()]
    text = " ".join(early[:5] + late[:3] + early[5:8])
    ids = chat.tokenize([text])[0]
    assert ids.max() < 25600 and ids[0] == chat.tokenizer.cls_id
    assert (ids == chat.tokenizer.unk_id).sum() == 3
    t0 = flight._time.monotonic_ns()
    chat.func([[{"role": "user", "content": text}]])
    assert all(int(p.max()) < 25600 for p in model.seen)
    span = [s for s in flight.spans_between(t0, flight._time.monotonic_ns())
            if s[1] == "answer.tokenize"][-1]
    assert flight.args_of(span)["unk"] == 3
    # a slice that holds every piece replaces nothing; a toy one hashes
    assert (TPUChat(Model(50176)).tokenize([text])[0] == chat.tokenizer.unk_id).sum() == 0
    assert not isinstance(TPUChat(Model(64)).tokenizer, WordPieceTokenizer)


def test_device_plan_counts_the_latent_cache(published):
    from pathway_tpu.analysis.device_plan import analyze_device_plan

    cut = dec.DecoderConfig.from_hf(published, layers=5, experts_held=(0, 40),
                                    vocab_held=(0, 25600), max_positions=16384)
    old = os.environ.get("PATHWAY_DEVICE_HBM_BYTES")
    os.environ["PATHWAY_DEVICE_HBM_BYTES"] = str(16 * 10**9)
    try:
        held = analyze_device_plan(answer=cut)
        assert held.hbm["answer_param_bytes"] == dec.param_bytes(cut)
        assert held.hbm["answer_cache_bytes"] == dec.cache_bytes(cut) == 849_346_560
        assert not [d for d in held.diagnostics if d.code == "device.hbm.over_budget"]
        nine = dec.DecoderConfig.from_hf(published, layers=9, experts_held=(0, 40),
                                         vocab_held=(0, 25600), max_positions=16384)
        assert [d for d in analyze_device_plan(answer=nine).diagnostics
                if d.code == "device.hbm.over_budget"]
    finally:
        if old is None:
            del os.environ["PATHWAY_DEVICE_HBM_BYTES"]
        else:
            os.environ["PATHWAY_DEVICE_HBM_BYTES"] = old
