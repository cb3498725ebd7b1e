"""The hybrid answer decoder (models/decoder.py) against the plain float32
reference (benchmark/reference_decoder.py) at a tiny size on the CPU:
m m A m, 8 experts top-3, two shares of 4, a 64-row slice of 128 rows."""

import os
import sys
import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp

BENCH = os.path.join(os.path.dirname(__file__), "..", "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import reference_decoder as ref  # noqa: E402

from pathway_tpu.models import decoder as dec  # noqa: E402

SEED = 7
TINY = {
    "hidden_size": 32, "num_hidden_layers": 4,
    "layer_types": ["mamba", "mamba", "attention", "mamba", "mamba", "mamba"],
    "num_attention_heads": 4, "num_key_value_heads": 2, "attention_multiplier": 0.125,
    "mamba_n_heads": 8, "mamba_d_head": 8, "mamba_d_state": 16, "mamba_d_conv": 4,
    "mamba_n_groups": 1, "mamba_expand": 2, "mamba_chunk_size": 8,
    "num_local_experts": 4, "num_experts_per_tok": 3, "intermediate_size": 16,
    "shared_intermediate_size": 32, "vocab_size": 64, "embedding_multiplier": 1.5,
    "residual_multiplier": 0.22, "logits_scaling": 16, "rms_norm_eps": 1e-5,
    "position_embedding_type": "nope",
    # at hidden 32 the published N(0, 0.02) would leave every layer's output
    # far below the embedding's: the same products of width and deviation
    "init_std": 0.2,
    "published": {"num_hidden_layers": 6, "num_local_experts": 8, "vocab_size": 128},
    "held": {"experts": [0, 4], "vocab_rows": [0, 64]},
}
SERVING = dict(prefill_chunk=16, max_positions=64, slots=4)


def config_of(tiny: dict) -> dec.DecoderConfig:
    return dec.DecoderConfig.from_hf(
        {**tiny, **tiny["published"]}, layers=tiny["num_hidden_layers"],
        experts_held=tuple(tiny["held"]["experts"]),
        vocab_held=tuple(tiny["held"]["vocab_rows"]), **SERVING,
    )


def weights_of(arch: dict, seed: int = SEED) -> dict:
    """The reference's per-layer weights: the program takes them as they are."""
    layers = [ref.make_layer(arch, seed, l) for l in range(len(arch["layer_types"]))]
    return {"layers": layers, "embed": ref.make_embed(arch, seed),
            "final_norm": ref.final_norm(arch)}


@pytest.fixture(scope="module")
def world():
    arch = ref.arch_of(TINY)
    cfg = config_of(TINY)
    w = weights_of(arch)
    return arch, cfg, w, dict(w)


def spread(x) -> float:
    return float(np.max(x) - np.min(x))


def rel(got, want) -> float:
    return float(np.max(np.abs(np.asarray(got, np.float32) - np.asarray(want))) / spread(want))


def reference_logits(arch, w, gen, follow=True):
    """The reference's full forward over prompt + answer, teacher-forced
    on the program's ids, following its selections."""
    ids = np.concatenate([gen.prompt, gen.tokens[:-1]])
    routes = np.concatenate([gen.prompt_routes, gen.decode_routes], axis=1)
    out = ref.forward(arch, SEED, [ids], last=len(gen.tokens), layers=w,
                      routes=[routes] if follow else None, router_tol=0.05,
                      buckets=(32, 64))
    return out[0]


# bf16 operands against float32 read 0.002-0.006 of this tiny model's logit
# spread; a state that is not carried between chunks reads 0.04-0.08
TOL = 0.015


def test_shapes_and_counts_match_the_reference(world):
    arch, cfg, w, params = world
    for kind in ("mamba", "attention"):
        assert dec.layer_shapes(cfg, kind) == ref.layer_shapes(arch, kind)
    n = sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(params))
    assert n == ref.param_count(arch)
    assert dec.param_bytes(cfg) == sum(
        x.nbytes for x in jax.tree_util.tree_leaves(params))
    state = dec.empty_state(cfg)
    assert dec.cache_bytes(cfg) == sum(x.nbytes for x in jax.tree_util.tree_leaves(state))


@pytest.mark.parametrize("layer", [0, 2])
def test_mixer_matches_reference_layer_by_layer(world, layer):
    arch, cfg, w, _ = world
    p = w["layers"][layer]
    T = cfg.prefill_chunk
    u = jax.random.normal(jax.random.PRNGKey(layer), (T, cfg.hidden), jnp.float32)
    mm = ref._ops("f32")
    if arch["layer_types"][layer] == "mamba":
        tail = jnp.zeros((cfg.mamba_conv - 1, cfg.conv_width), jnp.bfloat16)
        ssm = jnp.zeros((cfg.mamba_heads, cfg.mamba_head_dim, cfg.mamba_state))
        got, _, state = dec.mamba_prefill(cfg, p, u, tail, ssm, 13)
        want, want_state = ref.mamba_mixer(arch, p, u, 13, mm)
        assert rel(state, want_state) < 0.02
    else:
        keys = jnp.zeros((cfg.max_positions, cfg.kv_heads, cfg.head_dim), jnp.bfloat16)
        got, keys, _ = dec.attention_prefill(cfg, p, u, keys, keys, 0, 13)
        want = ref.attention_mixer(arch, p, u, mm)
        assert not np.asarray(keys[13:], np.float32).any()   # padding wrote no key
    assert rel(got[:13], want[:13]) < 0.02


def test_expert_layer_matches_reference(world):
    arch, cfg, w, _ = world
    p = w["layers"][0]
    x = jax.random.normal(jax.random.PRNGKey(5), (24, cfg.hidden), jnp.float32)
    live = jnp.arange(24) < 20
    got, counted = dec.moe_block(cfg, p, x, live)
    u = ref.rms_norm(x, p["norm2"], arch["rms_eps"])
    want, sel, _ = ref.experts_and_shared(
        arch, p, u, ref._ops("f32"), follow=counted["sel"], tol=0.05)
    want = x + arch["residual_multiplier"] * want
    assert rel(got[:20], want[:20]) < 0.02
    sel = np.asarray(counted["sel"])[:20]
    held = (sel >= 0) & (sel < 4)
    assert int(counted["absent"]) == int((~held).sum())
    assert np.asarray(counted["counts"]).tolist() == [int((sel == e).sum()) for e in range(4)]


def test_two_shares_and_one_shared_mlp_add_up_to_the_uncut_layer():
    """Experts 0-3 on one chip and 4-7 on the other, the shared MLP
    counted once, give the whole layer's routed + shared sum."""
    whole = {**TINY, "num_local_experts": 8, "held": {"experts": [0, 8], "vocab_rows": [0, 64]}}
    arch = ref.arch_of(whole)
    p = ref.make_layer(arch, SEED, 0)
    x = jax.random.normal(jax.random.PRNGKey(9), (16, 32), jnp.float32)
    u = ref.rms_norm(x, p["norm2"], arch["rms_eps"])
    want, sel, _ = ref.experts_and_shared(arch, p, u, ref._ops("f32"))
    live = jnp.ones(16, bool)
    parts, shared = [], None
    for first in (0, 4):
        cfg = config_of({**TINY, "held": {"experts": [first, 4], "vocab_rows": [0, 64]}})
        share = {**p, "experts_in": p["experts_in"][first:first + 4],
                 "experts_out": p["experts_out"][first:first + 4]}
        s, gates, held = dec.route(cfg, share, u, live)
        routed, _ = dec.routed_experts(cfg, share, u, s, gates, held)
        parts.append(routed)
        shared = dec.shared_mlp(cfg, share, u)
        assert np.array_equal(np.sort(np.asarray(s)), np.sort(np.asarray(sel)))
    assert rel(parts[0] + parts[1] + shared, want) < 0.02
    # and each share alone is not the layer
    assert rel(parts[0] + shared, want) > 0.05


@pytest.mark.parametrize("lengths", [(5,), (16,), (8, 24), (37, 3, 16, 40)],
                         ids=["inside", "on-a-chunk", "on-a-mamba-chunk", "unequal-batch"])
def test_prefill_then_decode_equals_reference_full_forward(world, lengths):
    """Chunked prefill carrying conv tail, SSM state and keys/values,
    then decode through the cache, against one full forward: logits at
    every generated position, the final SSM state, and the routes."""
    arch, cfg, w, params = world
    model = dec.AnswerModel(cfg, params)
    rng = np.random.default_rng(sum(lengths))
    prompts = [rng.integers(1, 64, size=n) for n in lengths]
    made = model.generate(prompts, 6, keep=range(len(prompts)))
    for gen in made:
        want = reference_logits(arch, w, gen)
        assert gen.logits.shape == want["logits"].shape == (6, 64)
        assert rel(gen.logits, want["logits"]) < TOL
        assert want["wrong_routes"] == 0
        assert np.array_equal(want["routes"],
                              np.concatenate([gen.prompt_routes, gen.decode_routes], axis=1))
        assert np.array_equal(gen.tokens, gen.logits.argmax(axis=-1))


def test_broken_carry_is_seen(world):
    """The same comparison with the state dropped between chunks reads
    far above the tolerance: the tolerance tests something."""
    arch, cfg, w, params = world
    model = dec.AnswerModel(cfg, params)
    inner = model._prefill

    def forgetful(p, state, slot, ids, pos, n):
        return inner(p, jax.tree_util.tree_map(jnp.zeros_like, state), slot, ids, pos, n)

    model._prefill = forgetful
    prompt = np.random.default_rng(1).integers(1, 64, size=40)
    gen = model.generate([prompt], 6, keep=[0])[0]
    assert rel(gen.logits, reference_logits(arch, w, gen)["logits"]) > 2 * TOL


def test_sliced_logits_are_the_slice_of_the_whole(world):
    arch, cfg, w, params = world
    whole_cfg = config_of({**TINY, "vocab_size": 128,
                           "held": {"experts": [0, 4], "vocab_rows": [0, 128]}})
    table = (0.02 * jax.random.normal(jax.random.PRNGKey(1), (128, 32))).astype(jnp.bfloat16)
    whole = dec.AnswerModel(whole_cfg, {**params, "embed": table})
    part = dec.AnswerModel(cfg, {**params, "embed": table[:64]})
    prompt = np.random.default_rng(4).integers(1, 64, size=21)
    a = whole.generate([prompt], 1, keep=[0])[0]
    b = part.generate([prompt], 1, keep=[0])[0]
    assert np.array_equal(a.logits[:, :64], b.logits)
    assert b.tokens[0] == int(np.argmax(a.logits[0, :64]))


def test_released_slot_leaks_nothing(world):
    _, cfg, _, params = world
    model = dec.AnswerModel(cfg, params)
    rng = np.random.default_rng(5)
    probe = rng.integers(1, 64, size=19)
    fresh = model.generate([probe], 4, keep=[0])[0]
    for _ in range(2):   # fill every slot with other sequences, longer ones
        model.generate([rng.integers(1, 64, size=50) for _ in range(cfg.slots)], 8)
    again = model.generate([probe], 4, keep=[0])[0]
    assert np.array_equal(fresh.logits, again.logits)
    assert model.cache.in_use == 0


def test_ninth_sequence_waits_for_a_slot(world):
    _, cfg, _, _ = world
    cache = dec.StateCache(cfg)
    slots = [cache.acquire() for _ in range(cfg.slots)]
    assert sorted(slots) == list(range(cfg.slots)) and cache.in_use == cfg.slots
    got = []
    waiter = threading.Thread(target=lambda: got.append(cache.acquire()), daemon=True)
    waiter.start()
    waiter.join(timeout=0.3)
    assert waiter.is_alive() and not got
    cache.release(slots[2])
    waiter.join(timeout=10)
    assert not waiter.is_alive() and got == [slots[2]]
    with pytest.raises(ValueError):
        cache.release(cfg.slots)        # the scratch slot is never out


def test_counters_and_spans(world):
    from pathway_tpu.internals import flight

    _, cfg, _, params = world
    model = dec.AnswerModel(cfg, params)
    t0 = flight._time.monotonic_ns()
    rng = np.random.default_rng(6)
    model.generate([rng.integers(1, 64, size=n) for n in (20, 9, 33)], 5)
    c = model.counters
    assert (c.prefill_real, c.prefill_padded) == (62, 6 * 16 - 62)
    assert c.prompts == 3 and c.decode_steps == {3: 4}
    k, layers = cfg.experts_per_token, len(cfg.layer_types)
    assert c.held_selections + c.absent_selections == (62 + 3 * 4) * k * layers
    assert c.expert_tokens.sum() == c.held_selections
    names = [s[1] for s in flight.spans_between(t0, flight._time.monotonic_ns())]
    for name, n in (("answer.generate", 1), ("answer.prefill", 6), ("answer.decode", 1),
                    ("answer.decode.step", 4), ("answer.wait", 1), ("answer.d2h", 1),
                    ("cache.acquire", 3), ("cache.release", 3)):
        assert names.count(name) == n, name


def test_sites_are_registered():
    from pathway_tpu.internals.device import registered_sites

    sites = registered_sites()
    # one chip's share of granite-4.0-h-small: 10 layers, experts 0-35,
    # half the vocabulary
    cut = dec.DecoderConfig(experts_held=(0, 36), vocab_held=(0, 50176))
    flops, nbytes = sites["answer.prefill"].cost_model(cut)
    # the issue's arithmetic: 3.3 GFLOP a prompt token, 9.51 GB of weights
    assert abs(flops / cut.prefill_chunk - 3.3e9) < 0.1e9
    assert sites["answer.decode"].cost_model(cut, 1)[1] == nbytes == dec.param_bytes(cut)
    assert abs(nbytes - 9.51e9) < 0.02e9
    assert abs(dec.cache_bytes(cut) / 9 - 55e6) < 1e6


def test_generate_refuses_what_it_cannot_hold(world):
    _, cfg, _, params = world
    model = dec.AnswerModel(cfg, params)
    with pytest.raises(ValueError):
        model.generate([[1, 2, 70]], 2)          # id outside the held rows
    with pytest.raises(ValueError):
        model.generate([[1]] * (cfg.slots + 1), 2)
    with pytest.raises(ValueError):
        model.generate([[]], 2)
    long = model.generate([np.ones(200, np.int32)], 4)[0]   # cut to the cache's room
    assert len(long.prompt) == cfg.max_positions - 4


def test_answer_through_pw_run_is_what_direct_generation_returns():
    """/v2/answer through the gateway, the engine's memoized row-wise node
    and TPUChat returns what the model generates for the same prompt, and
    generates once a question (the retraction replays the stored answer)."""
    import socket
    import time

    import pathway_tpu as pw
    from pathway_tpu.xpacks.llm import prompts
    from pathway_tpu.xpacks.llm.llms import TPUChat
    from pathway_tpu.xpacks.llm.mocks import DeterministicMockEmbedder
    from pathway_tpu.xpacks.llm.question_answering import BaseRAGQuestionAnswerer, RAGClient
    from pathway_tpu.xpacks.llm.vector_store import VectorStoreServer

    docs = pw.debug.table_from_markdown(
        """
        data | meta
        pathway is a streaming framework | a.txt
        """
    ).select(
        data=pw.this.data,
        _metadata=pw.apply_with_type(
            lambda p: pw.Json({"path": p, "modified_at": 1, "seen_at": 2}), pw.Json,
            pw.this.meta),
    )
    chat = TPUChat(dec.AnswerModel(dec.DecoderConfig.tiny(), seed=11), max_new_tokens=5)
    store = VectorStoreServer(docs, embedder=DeterministicMockEmbedder(dimension=8))
    rag = BaseRAGQuestionAnswerer(llm=chat, indexer=store, search_topk=1)
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    rag.build_server(host="127.0.0.1", port=port)
    threading.Thread(target=pw.run, daemon=True).start()
    deadline = time.monotonic() + 60
    while True:
        try:
            socket.create_connection(("127.0.0.1", port), timeout=1).close()
            break
        except OSError:
            assert time.monotonic() < deadline, "server did not come up"
            time.sleep(0.05)
    question = "what is pathway"
    out = RAGClient(host="127.0.0.1", port=port).answer(question)
    time.sleep(0.5)     # the retraction of the answered query commits
    assert chat.model.counters.prompts == 1
    prompt = prompts.prompt_qa.func(question, [{"text": "pathway is a streaming framework"}])
    direct = chat.model.generate(chat.tokenize([prompt]), 5)[0]
    assert out["response"] == chat.detokenize(direct.tokens)
    assert len(direct.tokens) == 5


def test_device_plan_counts_the_answer_model():
    """One chip's share of the published model beside the index: weights
    and cache are in the static HBM plan, and the whole model is refused."""
    from pathway_tpu.analysis.device_plan import analyze_device_plan

    cut = dec.DecoderConfig(experts_held=(0, 36), vocab_held=(0, 50176))
    old = os.environ.get("PATHWAY_DEVICE_HBM_BYTES")
    os.environ["PATHWAY_DEVICE_HBM_BYTES"] = str(16 * 10**9)
    try:
        without = analyze_device_plan()
        held = analyze_device_plan(answer=cut)
        assert held.hbm["answer_param_bytes"] == dec.param_bytes(cut)
        assert held.hbm["answer_cache_bytes"] == dec.cache_bytes(cut)
        assert held.hbm["footprint_bytes"] - without.hbm["footprint_bytes"] == \
            dec.param_bytes(cut) + dec.cache_bytes(cut)
        assert not [d for d in held.diagnostics if d.code == "device.hbm.over_budget"]
        whole = analyze_device_plan(answer=dec.DecoderConfig(layer_types=cut.layer_types * 4))
        assert [d for d in whole.diagnostics if d.code == "device.hbm.over_budget"]
    finally:
        if old is None:
            del os.environ["PATHWAY_DEVICE_HBM_BYTES"]
        else:
            os.environ["PATHWAY_DEVICE_HBM_BYTES"] = old
