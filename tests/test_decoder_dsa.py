"""The ``glm_moe_dsa`` family of models/decoder.py (latent attention over
the rows a learned indexer keeps for each query, three layers in five
here reusing another's choice; the sigmoid bias-corrected router; plain
rotary frequencies) against the plain float32 reference
(benchmark/reference_glm_dsa.py) at a tiny size on the CPU: five MLA
layers whose indexers go ``full`` (the dense layer) ``shared shared full
shared``, 3 index heads of 8 that keep 8 cached rows a query, 16 experts
top-3, four shares of 4, a 64-row slice of 128 rows; chunks of 16 and
decode blocks of 16, so prompts cross chunks, blocks and the 8-row
boundary."""

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

import reference_glm_dsa as ref  # noqa: E402

from pathway_tpu.models import decoder as dec  # noqa: E402

CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
SEED = 13
PATTERN = ["full", "shared", "shared", "full", "shared"]
TINY = {
    "model_type": "glm_moe_dsa", "hidden_size": 32, "num_hidden_layers": 5,
    "first_k_dense_replace": 1, "intermediate_size": 48, "moe_intermediate_size": 16,
    "moe_layer_freq": 1, "n_group": 1, "topk_group": 1, "n_routed_experts": 4,
    "n_shared_experts": 1, "norm_topk_prob": True, "num_attention_heads": 4,
    "num_key_value_heads": 4, "num_experts_per_tok": 3, "q_lora_rank": 24,
    "kv_lora_rank": 16, "qk_nope_head_dim": 8, "qk_rope_head_dim": 4, "v_head_dim": 8,
    "rms_norm_eps": 1e-5, "routed_scaling_factor": 2.5, "rope_interleave": True,
    "rope_parameters": {"rope_theta": 10000, "rope_type": "default"},
    "scoring_func": "sigmoid", "topk_method": "noaux_tc", "attention_bias": False,
    "index_n_heads": 3, "index_head_dim": 8, "index_topk": 8, "index_topk_pattern": None,
    "indexer_rope_interleave": True, "indexer_types": PATTERN,
    "mlp_layer_types": ["dense"] + ["sparse"] * 4, "num_nextn_predict_layers": 1,
    "tie_word_embeddings": False, "vocab_size": 64,
    # at hidden 32 the published N(0, 0.02) would leave every layer's output
    # far below the embedding's: the same products of width and deviation
    "init_std": 0.2,
    # the published model these five layers are cut from: two more dense
    # layers before them, one more shared layer after
    "published": {
        "num_hidden_layers": 8, "first_k_dense_replace": 3, "n_routed_experts": 16,
        "vocab_size": 128, "indexer_types": ["full", "full"] + PATTERN + ["shared"],
        "mlp_layer_types": ["dense"] * 3 + ["sparse"] * 5},
    "held": {"layers": [2, 5], "experts": [0, 4], "vocab_rows": [0, 64]},
}
SERVING = dict(prefill_chunk=16, max_positions=64, slots=4, decode_block=16, first_layer=2)


def config_of(tiny: dict, **more) -> dec.DecoderConfig:
    return dec.DecoderConfig.from_hf(
        {**tiny, **tiny["published"]}, layers=tiny["num_hidden_layers"],
        experts_held=tuple(tiny["held"]["experts"]),
        vocab_held=tuple(tiny["held"]["vocab_rows"]), **{**SERVING, **more},
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


def kept_queries(gen, new: int) -> np.ndarray:
    """Positions of the queries whose index scores a generation keeps: the decode steps'."""
    return len(gen.prompt) + np.arange(new - 1)


def chosen_rows(gen) -> np.ndarray:
    """A generation's chosen rows, every query's: [index layers, queries, positions] bool."""
    n = len(gen.prompt) + len(gen.tokens) - 1
    bits, _ = gen.choices()
    assert bits.dtype == np.uint8 and bits.shape[1] == n
    return dec.unpack_rows(bits)[:, :, :n]


def reference_of(arch, w, gen, cfg, follow_rows="every", **more):
    """The reference's full forward over prompt + answer, teacher-forced
    on the program's ids, following its expert selections and the rows its
    indexers kept: on ``every`` query, on the ``kept`` ones alone (the
    decode steps, whose scores the generation keeps), or on none."""
    ids = np.concatenate([gen.prompt, gen.tokens[:-1]])
    routes = np.concatenate([gen.prompt_routes, gen.decode_routes], axis=1)
    selections = None
    if follow_rows:
        at = kept_queries(gen, len(gen.tokens))
        every, index_scores = gen.choices()
        assert index_scores.shape[:2] == (cfg.index_layers, len(at))
        chosen = every[:, at]
        if follow_rows == "every":
            at, chosen = np.arange(len(ids)), every
        selections = [{"at": at, "chosen": chosen, "scores": index_scores}]
    return ref.forward(arch, SEED, [ids], last=len(gen.tokens), layers=w, routes=[routes],
                       router_tol=ROUTER_TOL, selections=selections, index_tol=INDEX_TOL,
                       buckets=(32, 64), keep_chosen=True, **more)[0]


# bf16 operands and bf16 caches against float32 read 0.004-0.02 of this tiny
# model's logit spread; a cache without its index keys, or a shared layer
# that chooses for itself, far more (the tests below). The router's choice
# is followed inside 5% of the spread of score + bias (it reads under 2%),
# a kept row inside 5% of a query's index-score spread (the scores
# themselves read up to 4% of the widest spread apart at index_dim 8).
TOL = 0.03
ROUTER_TOL = 0.05
INDEX_TOL = 0.05


def test_shapes_and_counts_match_the_reference(world):
    arch, cfg, w = world
    assert cfg.layers == tuple(zip(arch["layer_types"], arch["ffn_types"]))
    assert cfg.index_types == arch["index_types"] == tuple(PATTERN)
    for (kind, ffn), index in zip(cfg.layers, cfg.index_types):
        assert dec.layer_shapes(cfg, kind, ffn, index) == ref.layer_shapes(arch, kind, ffn, index)
    # (c) a layer that shares a choice has no indexer leaf of its own
    for index, layer in zip(PATTERN, w["layers"]):
        assert ({"w_iq", "w_ik", "ik_norm", "ik_bias", "w_iw"} <= set(layer)) == (index == "full")
    leaves = jax.tree_util.tree_leaves(w)
    assert sum(int(np.prod(x.shape)) for x in leaves) == ref.param_count(arch)
    assert dec.param_bytes(cfg) == sum(x.nbytes for x in leaves)
    mine = dec.init_params(cfg, 3)
    assert jax.tree_util.tree_map(lambda x: (x.shape, x.dtype), mine) == \
        jax.tree_util.tree_map(lambda x: (x.shape, x.dtype), w)
    # the second kind of per-position state: index keys beside the latent rows
    state = dec.empty_state(cfg)
    assert [sorted(s) for s in state] == [
        ["index", "latent"] if index == "full" else ["latent"] for index in PATTERN]
    assert state[0]["index"].shape == (cfg.slots + 1, cfg.max_positions, cfg.index_dim)
    assert dec.cache_bytes(cfg) == sum(x.nbytes for x in jax.tree_util.tree_leaves(state))
    assert dec.DecoderConfig.tiny_dsa().index_types == tuple(PATTERN)


@pytest.mark.parametrize("lengths", [(5,), (16,), (37,), (40, 3, 17)],
                         ids=["inside", "on-a-chunk", "three-chunks", "unequal-batch"])
def test_prefill_then_decode_equals_reference_full_forward(world, lengths):
    """(a) chunked prefill writing both caches, then decode through them,
    against one full forward with no cache: logits at every generated
    position, the routes, the final latent rows and index keys, and the
    chosen sets themselves; (g) sequences of different lengths decoded in
    one batch choose independently."""
    arch, cfg, w = world
    model = dec.AnswerModel(cfg, w)
    rng = np.random.default_rng(sum(lengths))
    prompts = [rng.integers(1, 64, size=n) for n in lengths]
    rows = range(len(prompts))
    made = model.generate(prompts, 6, keep=rows)
    for gen in made:
        # with 8 rows kept, one row swapped on a near-tie is an eighth of a
        # query's attention: every query's choice is followed, not the kept ones' alone
        want = reference_of(arch, w, gen, cfg)
        assert gen.logits.shape == want["logits"].shape == (6, 64)
        assert rel(gen.logits, want["logits"]) < TOL
        assert want["wrong_routes"] == 0 and want["wrong_selections"] == 0
        assert want["index_gap"] < INDEX_TOL
        assert gen.prompt_routes.shape == (4, len(gen.prompt), 3)       # a row an expert layer
        assert np.array_equal(want["routes"],
                              np.concatenate([gen.prompt_routes, gen.decode_routes], axis=1))
        assert np.array_equal(gen.tokens, gen.logits.argmax(axis=-1))
        n = len(gen.prompt) + 5
        # the latent rows of the five layers, then the index keys of the two that own an indexer
        assert len(gen.latent) == len(want["states"]) == 5 + 2
        for i, (got, state) in enumerate(zip(gen.latent, want["states"])):
            width = cfg.latent_width if i < 5 else cfg.index_dim
            assert got.shape == (cfg.max_positions, width) and state.shape == (n, width)
            assert rel(np.asarray(got, np.float32)[:n], state) < TOL
            assert not np.asarray(got, np.float32)[n:].any()   # nothing written past the end
        # the chosen sets: a query keeps min(context, 8) rows, all of them
        # visible; on the kept queries the reference's own choice is the
        # program's but for near-ties, and what it used is the program's
        chosen = chosen_rows(gen)
        assert chosen.shape == (2, n, n)
        assert (chosen.sum(axis=-1) == np.minimum(np.arange(n) + 1, 8)[None, :]).all()
        assert not (chosen & (np.arange(n)[None, None, :] > np.arange(n)[None, :, None])).any()
        for layer in range(2):
            assert np.array_equal(want["chosen"][layer], chosen[layer])
        # judged on the kept queries alone: none wrong on the first indexer,
        # whose inputs no earlier choice has touched
        at = kept_queries(gen, 6)
        kept = reference_of(arch, w, gen, cfg, follow_rows="kept")
        assert np.array_equal(kept["chosen"][0][at], chosen[0][at])
        # left to itself the reference chooses the same rows there but for a
        # near-tie (a row swapped a query at the most)
        alone = reference_of(arch, w, gen, cfg, follow_rows=None)
        assert ((alone["chosen"][0] != chosen[0]).sum(axis=-1) <= 2).all()
    # a row nobody compares goes through the same two programs, generates the
    # same tokens and keeps nothing; the counters hold what the DEVICE kept
    # (the rows of the masks, a layer) and the host's count of what was visible
    other = dec.AnswerModel(cfg, w)
    plain = other.generate(prompts, 6)
    assert all(np.array_equal(a.tokens, b.tokens) and a.indexed is None
               for a, b in zip(plain, made))
    assert sorted(site for site, _ in other._seen | model._seen) == [
        "answer.decode", "answer.prefill"]        # one program a site, kept or not
    for c in (other.counters, model.counters):
        kept = sum(chosen_rows(gen)[0, :len(gen.prompt)].sum() for gen in made)
        assert c.selected_positions_prefill == c.attended_positions_prefill == kept
        assert c.indexed_positions_prefill == sum(n * (n + 1) // 2 for n in lengths)
        kept = sum(chosen_rows(gen)[0, len(gen.prompt):].sum() for gen in made)
        assert c.selected_positions_decode == c.attended_positions_decode == kept
        assert c.indexed_positions_decode == sum(5 * n + 15 for n in lengths)
    # (g) the batch's rows chose for themselves: each alone gives the same sets
    if len(prompts) > 1:
        alone = dec.AnswerModel(cfg, w).generate([prompts[1]], 6, keep=[0])[0]
        assert np.array_equal(alone.choices()[0], made[1].choices()[0])
        assert np.array_equal(alone.tokens, made[1].tokens)


def test_a_choice_that_covers_the_context_is_plain_latent_attention(world):
    """(b) with index_topk >= the context the indexer keeps every visible
    row and the model is plain MLA: the logits equal those of the same
    weights run with no indexer at all (the path DeepSeek-V2 guards),
    to the last bit; with index_topk 8 they differ."""
    arch, cfg, w = world
    wide = config_of(dict(TINY, index_topk=64))
    assert wide.index_topk == 64
    plain = dec.dataclasses.replace(wide, indexer_types=(), index_heads=0, index_dim=0,
                                    index_topk=0)
    bare = dict(w, layers=[{k: v for k, v in layer.items()
                            if k not in ("w_iq", "w_ik", "ik_norm", "ik_bias", "w_iw")}
                           for layer in w["layers"]])
    prompt = np.random.default_rng(3).integers(1, 64, size=41)
    everything = dec.AnswerModel(wide, w).generate([prompt], 6, keep=[0])[0]
    without = dec.AnswerModel(plain, bare).generate([prompt], 6, keep=[0])[0]
    assert np.array_equal(everything.logits, without.logits)
    assert (chosen_rows(everything).sum(axis=-1) == np.arange(46) + 1).all()
    sparse = dec.AnswerModel(cfg, w).generate([prompt], 6, keep=[0])[0]
    assert rel(sparse.logits, without.logits) > TOL


def test_a_shared_layer_attends_its_full_layers_choice(world):
    """(c) what travels between layers: the ``shared`` layers after a
    ``full`` one are handed its choice and nothing else's. A program whose
    shared layers are made to own indexers (fresh weights) and choose for
    themselves reads far from the reference; so does one whose cache loses
    its index keys."""
    arch, cfg, w = world
    prompt = np.random.default_rng(4).integers(1, 64, size=44)
    sound = dec.AnswerModel(cfg, w).generate([prompt], 6, keep=[0])[0]
    want = reference_of(arch, w, sound, cfg)
    assert rel(sound.logits, want["logits"]) < TOL

    # every layer its own indexer: the shared layers get indexer leaves of their own
    own = dec.dataclasses.replace(cfg, indexer_types=("full",) * 5)
    extra = dec.init_params(own, 9)["layers"]
    mixed = dict(w, layers=[dict(fresh, **layer) for fresh, layer in zip(extra, w["layers"])])
    gen = dec.AnswerModel(own, mixed).generate([prompt], 6, keep=[0])[0]
    assert rel(gen.logits, want["logits"]) > 2 * TOL

    # a cache whose index keys are lost: later chunks and every step choose from zeros
    model = dec.AnswerModel(cfg, w)
    inner = model._prefill

    def no_index_keys(p, state, slot, ids, pos, n):
        state, *out = inner(p, state, slot, ids, pos, n)
        return [dict(s, index=jnp.zeros_like(s["index"])) if "index" in s else s
                for s in state], *out

    model._prefill = no_index_keys
    gen = model.generate([prompt], 6, keep=[0])[0]
    judged = reference_of(arch, w, gen, cfg)
    assert judged["wrong_selections"] > 0 and rel(gen.logits, judged["logits"]) > 2 * TOL


def test_a_chunks_index_scores_equal_the_references(world):
    """Prefill's index scores (no generation carries them: 84 MB a chunk
    at the published sizes) against the reference's whole [T, T] scores:
    two chunks into one slot, the second's queries over both blocks of
    cached keys; and the choice made from them keeps the reference's rows
    but for near-ties."""
    arch, cfg, w = world
    p, T = w["layers"][0], cfg.prefill_chunk
    u = jax.random.normal(jax.random.PRNGKey(8), (2 * T, cfg.hidden), jnp.float32)
    latent = jnp.zeros((cfg.slots + 1, cfg.max_positions, cfg.latent_width), jnp.bfloat16)
    keys = jnp.zeros((cfg.slots + 1, cfg.max_positions, cfg.index_dim), jnp.bfloat16)
    got, kept = [], []
    for at in (0, T):
        _, latent, (chosen, scores, keys) = dec.mla_prefill(
            cfg, p, u[at:at + T], latent, 1, at, T, index=keys)
        got.append(np.asarray(scores)[:, :2 * T])
        kept.append(np.asarray(chosen)[:, :2 * T] != 0)
    mm = ref.base._ops("f32")
    cos, sin = (jnp.asarray(t) for t in ref.rope_tables(arch, 2 * T))
    c_q = ref.rms_norm(mm("td,de->te", u, p["w_dq"]), p["q_norm"], arch["rms_eps"])
    want, k_index = ref.index_scores(arch, p, u, c_q, cos, sin, mm)
    causal = np.tril(np.ones((2 * T, 2 * T), bool))
    want = np.where(causal, np.asarray(want), 0.0)
    assert rel(np.where(causal, np.concatenate(got), 0.0), want) < INDEX_TOL
    assert rel(np.asarray(keys[1, :2 * T], np.float32), k_index) < 0.01
    mine, _ = ref.choose_rows(arch, jnp.asarray(want))
    assert ((np.concatenate(kept) != np.asarray(mine)).sum(axis=-1) <= 2).all()


def test_select_rows_is_an_exact_top_k_with_the_lowest_position_first():
    """The bisection against a sort: random scores, rows that see fewer
    than k, exact ties across the k-th value, infinities and zeros of both
    signs."""
    rng = np.random.default_rng(0)
    scores = rng.normal(size=(12, 96)).astype(np.float32)
    scores[3] = np.round(scores[3])                   # many exact ties
    scores[4] = 0.0
    scores[5, ::2] = -np.inf
    scores[6, :40] = 1.5
    scores[7] = np.where(rng.random(96) < 0.5, 0.0, -0.0)
    seen = np.asarray([96, 5, 8, 96, 50, 96, 96, 96, 9, 1, 33, 64])
    visible = np.arange(96)[None, :] < seen[:, None]
    got = np.asarray(jax.jit(lambda s, v: dec.select_rows(s, v, 8))(scores, visible))
    for row in range(12):
        ranked = np.where(scores[row] == 0, 0.0, scores[row])        # -0.0 sorts below 0.0 by bits
        if row == 7:
            ranked = np.where(np.signbit(scores[row]), -1e-30, 0.0)
        order = np.lexsort((np.arange(96), -np.where(visible[row], ranked, -np.inf)))
        want = np.zeros(96, bool)
        want[[i for i in order[:8] if visible[row, i]]] = True
        assert np.array_equal(got[row], want), row
        assert got[row].sum() == min(8, seen[row])


def test_the_biased_router_chooses_by_the_bias_and_weighs_without_it(world):
    """(d) sigmoid scores; the 3 experts of highest score + bias; gates the
    chosen scores over their sum times 2.5, so a bias that changes the
    choice leaves the chosen experts' gates functions of the scores alone."""
    arch, cfg, w = world
    p = w["layers"][1]
    x = jax.random.normal(jax.random.PRNGKey(5), (48, arch["hidden"]), jnp.float32)
    u = ref.rms_norm(x, p["norm2"], arch["rms_eps"])
    live = jnp.ones(48, bool)
    sel, gates, held = dec.route(cfg, p, u, live)
    logits = dec._mm(u, p["router"])
    s, by, want = ref.choose(arch, p, logits)
    assert np.array_equal(np.sort(np.asarray(sel)), np.sort(np.asarray(want)))
    assert np.allclose(np.asarray(gates).sum(axis=-1), 2.5, atol=1e-5)
    chosen = np.take_along_axis(np.asarray(s), np.asarray(sel), axis=-1)
    assert np.allclose(np.asarray(gates), 2.5 * chosen / chosen.sum(-1, keepdims=True), atol=1e-6)
    assert np.array_equal(np.asarray(held), (np.asarray(sel) < 4))
    # a bias that lifts expert 9 into every choice
    lifted = dict(p, router_bias=p["router_bias"].at[9].add(10.0))
    sel2, gates2, _ = dec.route(cfg, lifted, u, live)
    assert (np.asarray(sel2) == 9).any(axis=-1).all()
    assert not np.array_equal(np.sort(np.asarray(sel)), np.sort(np.asarray(sel2)))
    chosen2 = np.take_along_axis(np.asarray(s), np.asarray(sel2), axis=-1)
    assert np.allclose(np.asarray(gates2), 2.5 * chosen2 / chosen2.sum(-1, keepdims=True),
                       atol=1e-6)


def test_the_four_shares_add_up_to_the_whole_layer(world):
    """(e) the routed parts of the four shares of 4 experts, plus the
    shared expert once, equal the uncut reference layer's feed-forward."""
    arch, cfg, _ = world
    whole = dict(arch, experts_held=(0, 16))
    p = ref.make_layer(whole, SEED, 1)
    x = jax.random.normal(jax.random.PRNGKey(6), (40, arch["hidden"]), jnp.float32)
    u = ref.rms_norm(x, p["norm2"], arch["rms_eps"])
    mm = ref.base._ops("f32")
    want, _, _ = ref.experts_and_shared(whole, p, u, mm)
    live = jnp.ones(40, bool)
    total = dec.shared_mlp(cfg, p, u)
    for first in (0, 4, 8, 12):
        share = dec.dataclasses.replace(cfg, experts_held=(first, 4))
        part = dict(p, experts_in=p["experts_in"][first:first + 4],
                    experts_out=p["experts_out"][first:first + 4])
        sel, gates, held = dec.route(share, part, u, live)
        routed, sizes = dec.routed_experts(share, part, u, sel, gates, held)
        total = total + routed
    assert rel(total, want) < 0.02


def catalog_row() -> dict:
    with open(CATALOG) as f:
        return next(r for r in map(json.loads, f) if r["name"] == "GLM-5.2")


def test_from_hf_gives_the_published_shapes():
    """(f) the catalog row's keys, whole: 78 layers, the published pattern."""
    hf = catalog_row()["config"]
    cfg = dec.DecoderConfig.from_hf(hf, max_positions=1024, prefill_chunk=512)
    assert (cfg.hidden, cfg.heads, cfg.q_rank, cfg.kv_rank) == (6144, 64, 2048, 512)
    assert (cfg.nope_dim, cfg.rope_dim, cfg.v_dim, cfg.latent_width) == (192, 64, 256, 576)
    assert (cfg.index_heads, cfg.index_dim, cfg.index_topk) == (32, 128, 2048)
    assert (cfg.experts, cfg.experts_per_token, cfg.expert_width, cfg.shared_width) == (
        256, 8, 2048, 2048)
    assert (cfg.dense_layers, cfg.dense_width, cfg.routed_scaling) == (3, 12288, 2.5)
    assert cfg.router_bias and not cfg.router_groups and not cfg.tied_head
    assert cfg.rope == dec.PlainRope(theta=8000000.0) and cfg.vocab_size == 154880
    assert dec.mla_scale(cfg) == 256 ** -0.5
    assert len(cfg.layer_types) == 78 and cfg.index_layers == 21
    assert cfg.indexer_types[:7] == ("full",) * 3 + ("shared",) * 3 + ("full",)
    shapes = dec.layer_shapes(cfg, "mla", "moe", "full")
    assert shapes["w_uq"] == (2048, 64 * 256) and shapes["w_ukv"] == (512, 64 * 448)
    assert shapes["w_iq"] == (2048, 32 * 128) and shapes["w_ik"] == (6144, 128)
    assert shapes["router_bias"] == (256,)
    # this chip's share: published layers 2-7, experts 0-15, an eighth of the vocabulary
    cut = dec.DecoderConfig.from_hf(
        hf, layers=6, first_layer=2, experts_held=(0, 16), vocab_held=(0, 19360),
        slots=8, prefill_chunk=512, max_positions=20480)
    assert cut.indexer_types == ("full", "shared", "shared", "shared", "full", "shared")
    assert cut.ffn_types == ("dense",) + ("moe",) * 5
    matrices = sum(
        int(np.prod(s)) for (kind, ffn), index in zip(cut.layers, cut.index_types)
        for s in dec.layer_shapes(cut, kind, ffn, index).values() if len(s) > 1)
    assert matrices + 2 * 19360 * 6144 == 4_689_756_160
    assert abs(dec.param_bytes(cut) - 9_379_512_320) < 1e6          # + the float32 vectors
    assert dec.cache_bytes(cut) == 9 * 20480 * (6 * 1152 + 2 * 256) == 1_368_391_680


@pytest.mark.parametrize("change, says", [
    ({"n_group": 8, "topk_group": 4}, "n_group"),
    ({"index_topk_pattern": [2048, 1024]}, "index_topk_pattern"),
    ({"indexer_types": ["full", "windowed"] + ["shared"] * 76}, "indexer_types"),
    ({"scoring_func": "softmax"}, "noaux_tc"),
    ({"norm_topk_prob": False}, "noaux_tc"),
    ({"rope_parameters": {"rope_theta": 8000000, "rope_type": "yarn", "factor": 4}}, "rope_type"),
    ({"rope_interleave": False}, "interleaved"),
    ({"q_lora_rank": None}, "q_lora_rank"),
    ({"mlp_layer_types": ["sparse"] * 78}, "mlp_layer_types"),
    ({"model_type": "glm_moe_dsa_next"}, "not written down"),
], ids=["groups", "topk-pattern", "indexer-kind", "scoring", "gates", "rope", "pairing",
        "full-rank-queries", "ffn-pattern", "model-type"])
def test_from_hf_refuses_what_is_not_written_down(change, says):
    with pytest.raises(ValueError, match=says):
        dec.DecoderConfig.from_hf({**catalog_row()["config"], **change},
                                  max_positions=1024, prefill_chunk=512)


def test_a_first_layer_that_shares_a_choice_is_refused():
    with pytest.raises(ValueError, match="'shared'"):
        dec.DecoderConfig.from_hf(catalog_row()["config"], layers=4, first_layer=3,
                                  max_positions=1024, prefill_chunk=512)
    with pytest.raises(ValueError, match="outside the published"):
        dec.DecoderConfig.from_hf(catalog_row()["config"], layers=4, first_layer=76)


def test_spans_and_counters_say_what_was_scored_and_kept(world):
    from pathway_tpu.internals import flight

    _, cfg, w = world
    t0 = flight._time.monotonic_ns()
    model = dec.AnswerModel(cfg, w)
    model.generate([np.arange(1, 41)], 3)
    spans = [(s[1], flight.args_of(s)) for s in flight.spans_between(t0, flight._time.monotonic_ns())]
    chunks = [a for name, a in spans if name == "answer.prefill"]
    steps = [a for name, a in spans if name == "answer.decode.step"]
    # 40 positions in chunks of 16: position t sees t + 1 rows and keeps min(t + 1, 8)
    assert [a["scored"] for a in chunks] == [136, 392, 292]
    assert [a["selected"] for a in chunks] == [100, 128, 64]
    assert [a["blocks"] for a in chunks] == [1, 2, 3]
    assert chunks[0]["kernel"] == "interpret" and chunks[0]["selection"] == "bisection"
    assert chunks[0]["attention"] == "masked-dense" and "selection" not in chunks[1]
    assert [(a["positions"], a["selected"]) for a in steps] == [(41, 8), (42, 8)]
    c = model.counters
    assert (c.indexed_positions_prefill, c.selected_positions_prefill) == (820, 292)
    assert (c.indexed_positions_decode, c.selected_positions_decode) == (83, 16)
    assert (c.attended_positions_prefill, c.attended_positions_decode) == (292, 16)
    assert c.index_rows == 2 * 42 and c.latent_rows == 5 * 42
    # a model without an indexer says and counts none of it
    t0 = flight._time.monotonic_ns()
    plain = dec.AnswerModel(dec.DecoderConfig.tiny_mla())
    plain.generate([[1, 2, 3]], 2)
    said = [flight.args_of(s) for s in flight.spans_between(t0, flight._time.monotonic_ns())
            if s[1] in ("answer.prefill", "answer.decode.step")]
    assert said and all("scored" not in a and "selected" not in a for a in said)
    assert plain.counters.index_rows == 0 and plain.counters.attended_positions_prefill == 6
