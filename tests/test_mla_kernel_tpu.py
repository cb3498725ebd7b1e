"""The latent-attention prefill kernel at DeepSeek-V2's published widths
(and, masked to an indexer's choice, at GLM-5.2's),
compiled for a described TPU v5e by the chip's own compiler (no chip is
attached and nothing runs): what Pallas' interpreter cannot refuse — a
block that is not whole tiles, more vector memory than a kernel may use —
and what only the compiled program shows: that the latent slab reaches the
kernel as it lies in memory, and that no block of float32 scores is an
array of the program any more.

One file, its topology described inside a fixture: only the worker that
is given this file loads the TPU's library."""

import json
import os

import pytest

import jax
import jax.numpy as jnp

from pathway_tpu.models import decoder as dec

CONFIG = os.path.join(os.path.dirname(__file__), "..", "benchmark", "configs", "DeepSeek-V2.json")


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")      # or the compiler logs under /tmp
        try:
            topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler here, or its library is taken
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def cfg():
    with open(CONFIG) as f:
        hf = json.load(f)
    return dec.DecoderConfig.from_hf(
        {**hf, **{k: hf["published"][k] for k in hf["reduced"]}},
        layers=hf["num_hidden_layers"], experts_held=tuple(hf["held"]["experts"]),
        vocab_held=tuple(hf["held"]["vocab_rows"]), **hf["serving"])


def test_one_layers_prefill_compiles_for_the_chip_with_the_slab_in_place(one_chip, cfg, monkeypatch):
    monkeypatch.setattr(dec, "mla_lowering", lambda: "mosaic")   # the backend here is the CPU
    T, H = cfg.prefill_chunk, cfg.heads

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(tuple(dims), dtype, sharding=one_chip)

    p = {name: shape(dims, jnp.float32 if len(dims) == 1 else jnp.bfloat16)
         for name, dims in dec.layer_shapes(cfg, dec.MLA, dec.DENSE).items()}
    slab = shape((cfg.slots + 1, cfg.max_positions, cfg.latent_width), jnp.bfloat16)
    i32 = shape((), jnp.int32)
    compiled = jax.jit(
        lambda p, u, latent, slot, pos, n: dec.mla_prefill(cfg, p, u, latent, slot, pos, n),
        donate_argnums=2,
    ).lower(p, shape((T, cfg.hidden), jnp.float32), slab, i32, i32, i32).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert "mla_prefill_attention" in text
    # the scores of a block, every head's, are no array of the program
    assert f"f32[{H},{T},{T}]" not in text
    # the slab is written in place and read where it lies: nothing the size
    # of it is made beside it (a copy into another order would be)
    slab_bytes = (cfg.slots + 1) * cfg.max_positions * cfg.latent_width * 2
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= slab_bytes
    assert memory.temp_size_in_bytes < slab_bytes


def test_a_layer_that_owns_an_indexer_compiles_for_the_chip_at_glms_widths(one_chip, monkeypatch):
    """GLM-5.2's cut: the same kernel at 64 heads of 192 + 64 | 256 with
    the queries' chosen rows as one more input, after the indexer's scores
    and the selection in plain XLA. Both slabs (latent rows, index keys)
    are written in place; the index heads' scores exist a block of cached
    keys at a time, never over the whole context."""
    monkeypatch.setattr(dec, "mla_lowering", lambda: "mosaic")
    with open(os.path.join(os.path.dirname(CONFIG), "GLM-5.2.json")) as f:
        hf = json.load(f)
    cfg = dec.DecoderConfig.from_hf(
        {**hf, **{k: hf["published"][k] for k in hf["reduced"]}},
        layers=hf["num_hidden_layers"], experts_held=tuple(hf["held"]["experts"]),
        vocab_held=tuple(hf["held"]["vocab_rows"]), **hf["serving"])
    T, J, P = cfg.prefill_chunk, cfg.index_heads, cfg.max_positions

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(tuple(dims), dtype, sharding=one_chip)

    p = {name: shape(dims, jnp.float32 if len(dims) == 1 else jnp.bfloat16)
         for name, dims in dec.layer_shapes(cfg, dec.MLA, dec.DENSE, dec.FULL).items()}
    latent = shape((cfg.slots + 1, P, cfg.latent_width), jnp.bfloat16)
    keys = shape((cfg.slots + 1, P, cfg.index_dim), jnp.bfloat16)
    i32 = shape((), jnp.int32)
    compiled = jax.jit(
        lambda p, u, latent, keys, slot, pos, n: dec.mla_prefill(
            cfg, p, u, latent, slot, pos, n, index=keys),
        donate_argnums=(2, 3),
    ).lower(p, shape((T, cfg.hidden), jnp.float32), latent, keys, i32, i32, i32).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert "mla_prefill_attention" in text
    assert f"f32[{T},{J},{P}]" not in text and f"f32[{cfg.heads},{T},{T}]" not in text
    slabs = (cfg.slots + 1) * P * (cfg.latent_width + cfg.index_dim) * 2
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= slabs
    assert memory.temp_size_in_bytes < 0.5 * slabs


def test_a_chunk_that_is_not_whole_lanes_is_refused_by_name_on_a_tpu(monkeypatch):
    """``tiny_mla``'s chunk of 16 runs under the interpreter only: where
    Mosaic would lower the kernel the refusal names ``prefill_chunk``
    before the compiler's own message about a block shape can."""
    monkeypatch.setattr(dec, "mla_lowering", lambda: "mosaic")
    toy = dec.DecoderConfig.tiny_mla()
    T, H = toy.prefill_chunk, toy.heads
    with pytest.raises(ValueError, match=r"prefill_chunk % 128"):
        jax.eval_shape(
            lambda q_nope, q_rope, latent, w: dec.mla_attend(toy, q_nope, q_rope, latent, w, 0, 0),
            jax.ShapeDtypeStruct((H, T, toy.nope_dim), jnp.bfloat16),
            jax.ShapeDtypeStruct((H, T, toy.rope_dim), jnp.bfloat16),
            jax.ShapeDtypeStruct((toy.slots + 1, toy.max_positions, toy.latent_width), jnp.bfloat16),
            jax.ShapeDtypeStruct((toy.kv_rank, H * (toy.nope_dim + toy.v_dim)), jnp.bfloat16))
