"""Causal decoder: the answer model of the RAG plane.

Three model families are written down here, as one stack of blocks ``x += r
* mixer(norm(x)); x += r * ffn(norm(x))`` whose layers each name a mixer
kind and a feed-forward kind:

* ``granitemoehybrid`` (IBM Granite 4.0-H): the mixer is a Mamba-2
  state-space layer or NoPE grouped-query attention by ``layer_types``;
  every feed-forward is routed experts (the k best router logits,
  softmax over those) beside an always-on shared MLP; a tied head; four
  scalar multipliers (embedding, residual, attention, logits).
* ``deepseek_v2`` (DeepSeek-V2): every mixer is multi-head latent
  attention (``mla``: low-rank queries, one jointly compressed key/value
  latent of ``kv_rank`` values and one decoupled rotary key of
  ``rope_dim`` values a position, YaRN frequencies); the first
  ``dense_layers`` feed-forwards are one dense MLP, the rest routed
  experts under a group-limited router (softmax over all experts, the
  best ``router_top_groups`` of ``router_groups`` groups, the k best of
  those, gates not renormalised, times ``routed_scaling``) beside the
  shared experts; an untied head; every multiplier 1.
* ``glm_moe_dsa`` (GLM-5.2): DeepSeek's latent attention under plain
  rotary frequencies, **over a chosen subset of the cache**: a layer
  whose ``indexer_types`` entry is ``full`` owns an indexer (``index_heads``
  small query heads, one cached key of ``index_dim`` values a position)
  that scores every cached position for every query and keeps the
  ``index_topk`` best; the ``shared`` layers after it own none and attend
  its choice. The router's third kind: sigmoid scores, the k best of
  score + a learned bias, gates the chosen scores renormalised times
  ``routed_scaling``.

Everything a deployment divides over chips is told to this module, never
assumed: which layers (``layer_types``), which experts
(``experts_held``) and which rows of the vocabulary (``vocab_held``)
live here. The router stays as wide as published and picks
``experts_per_token`` of all experts; this chip computes its own
experts' part and leaves out what the absent ones would add (on one chip
the layer runs without its exchange).

Two jitted programs serve every request:

* ``prefill(params, state, slot, ids, pos, n)``: one fixed chunk of
  ``prefill_chunk`` tokens of one sequence, carrying the convolution
  tail, the SSM state and the keys/values from chunk to chunk through
  the slot, so one executable serves every prompt length (a padded
  position has step size 0, routes nowhere and writes no key);
* ``decode(params, state, slots, ids, pos)``: one token for each of a
  batch of live slots.

The layers are unrolled, each with its own weight arrays: under
``lax.scan`` over stacked weights every step copied the layer's slice
before it multiplied by it (137 MB of ``in_proj``, 680 MB of experts, a
layer a call: a decode step read its weights twice), so the compile time
grows with depth instead. Matrix operands are bfloat16 with
float32 accumulation; the residual stream, every norm, softmax, gate and
the SSM state are float32.

Latent attention has two paths over one cache row ``[c_kv after its
norm | k_r after rotation]``: prefill expands keys and values from the
latent rows through ``w_ukv``, a block of keys at a time (compute-bound),
inside one Pallas kernel a layer that keeps a block's scores in the chip's
vector memory (Mosaic on a TPU, Pallas' interpreter elsewhere); decode
absorbs ``w_ukv``'s key half into the query and its value half into the
output and attends over the latent rows themselves (bandwidth-bound).
Neither holds scores over more than one block of keys. Under an indexer
both paths compute every visible block and mask it to the query's chosen
rows (the choice is a mask over the slot's positions: the ``index_topk``
best index scores of a row, found by bisection on the scores' bits).

``StateCache`` holds the kinds of per-sequence state side by side,
by slot: constant-size (convolution tail, SSM state) for the Mamba
layers, growing keys/values for the attention layers, growing latent rows
for the MLA layers and, beside them, the indexer's key rows for the
layers that own an indexer. ``AnswerModel`` is the host-facing object (prompt ids
in, generated ids out) the chat UDF wraps.
"""

from __future__ import annotations

import dataclasses
import functools
import math
import operator
import threading
from typing import Sequence

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from pathway_tpu.internals import device as _devsup
from pathway_tpu.internals import flight as _flight
from pathway_tpu.internals.device import (
    PLANE as _DEVICE,
    device_site,
    nbytes_of,
    place_compile_cache,
)

place_compile_cache()

MAMBA, ATTENTION, MLA = "mamba", "attention", "mla"      # mixer kinds
MOE, DENSE = "moe", "dense"                              # feed-forward kinds
DECODE_BUCKETS = (1, 2, 4, 8)


@dataclasses.dataclass(frozen=True)
class YarnRope:
    """``rope_scaling`` of type ``yarn`` and ``rope_theta``, as published."""

    theta: float = 10000.0
    factor: float = 40.0
    original_positions: int = 4096
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 0.707
    mscale_all_dim: float = 0.707


@dataclasses.dataclass(frozen=True)
class PlainRope:
    """``rope_parameters`` of type ``default``: ``rope_theta`` and nothing else."""

    theta: float = 10000.0


FULL, SHARED = "full", "shared"                          # indexer kinds


@dataclasses.dataclass(frozen=True)
class DecoderConfig:
    """Published sizes of one of the three families written down here
    (``granitemoehybrid``, ``deepseek_v2``, ``glm_moe_dsa``; the defaults
    are Granite 4.0-H-small's), the share of them held here, and the serving sizes
    (chunk, positions, slots)."""

    hidden: int = 4096
    layer_types: tuple = (MAMBA,) * 5 + (ATTENTION,) + (MAMBA,) * 4
    vocab_size: int = 100352
    vocab_held: tuple = (0, 100352)      # (first row, rows) of the table(s)
    heads: int = 32
    kv_heads: int = 8
    attention_multiplier: float = 1.0 / 128
    mamba_heads: int = 128
    mamba_head_dim: int = 64
    mamba_state: int = 128
    mamba_groups: int = 1
    mamba_conv: int = 4
    mamba_chunk: int = 256
    experts: int = 72
    experts_per_token: int = 10
    experts_held: tuple = (0, 72)        # (first expert, experts) held here
    expert_width: int = 768
    shared_width: int = 1536
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    logits_scaling: float = 16.0
    rms_eps: float = 1e-5
    prefill_chunk: int = 512
    max_positions: int = 4096
    slots: int = 8
    # what deepseek_v2 adds: the head, the leading dense layers, the router
    tied_head: bool = True
    dense_layers: int = 0                # leading layers whose feed-forward is one MLP
    dense_width: int = 0
    router_groups: int = 0               # 0: the k best logits, softmax over those
    router_top_groups: int = 0
    routed_scaling: float = 1.0
    # latent attention (``mla`` layers)
    q_rank: int = 0
    kv_rank: int = 0
    nope_dim: int = 0
    rope_dim: int = 0
    v_dim: int = 0
    rope: YarnRope | PlainRope | None = None
    # latent rows a block of the decode path's attention reads: a serving
    # constant, which only tests lower (to cross several blocks at toy lengths)
    decode_block: int = 2048
    # what glm_moe_dsa adds: the router's third kind (sigmoid scores, chosen
    # by score + a learned bias, gates renormalised) and the indexer
    router_bias: bool = False
    index_heads: int = 0
    index_dim: int = 0
    index_topk: int = 0                  # cached rows a query keeps
    indexer_types: tuple = ()            # a layer: "full" owns an indexer, "shared" reuses one

    def __post_init__(self):
        kinds = set(self.layer_types)
        if not kinds <= {MAMBA, ATTENTION, MLA}:
            raise ValueError(f"layer_types {sorted(kinds)}: only mamba, attention, mla")
        if self.mamba_groups != 1:
            raise ValueError("only mamba_n_groups == 1 is written down here")
        if MAMBA in kinds and self.prefill_chunk % self.mamba_chunk:
            raise ValueError("prefill_chunk must be whole Mamba chunks")
        if self.max_positions % self.prefill_chunk:
            raise ValueError("max_positions must be whole prefill chunks")
        if self.heads % self.kv_heads:
            raise ValueError("heads must be a multiple of kv_heads")
        if not 1 <= self.slots <= DECODE_BUCKETS[-1]:
            raise ValueError(f"slots must be 1..{DECODE_BUCKETS[-1]} (the decode buckets)")
        first, held = self.experts_held
        if first < 0 or held < 1 or first + held > self.experts:
            raise ValueError(f"experts_held {self.experts_held} outside 0..{self.experts}")
        first, rows = self.vocab_held
        if first < 0 or rows < 1 or first + rows > self.vocab_size:
            raise ValueError(f"vocab_held {self.vocab_held} outside 0..{self.vocab_size}")
        if not 0 <= self.dense_layers < len(self.layer_types):
            raise ValueError("dense_layers must leave an expert layer")
        if self.router_groups and (
                self.experts % self.router_groups
                or not 1 <= self.router_top_groups <= self.router_groups):
            raise ValueError("router groups must divide the experts, top groups be among them")
        if MLA in kinds:
            if self.rope is None or min(
                    self.q_rank, self.kv_rank, self.nope_dim, self.rope_dim, self.v_dim) < 1:
                raise ValueError("mla layers need q_rank, kv_rank, nope/rope/v dims and rope")
            if self.rope_dim % 2 or self.max_positions % self.decode_rows:
                raise ValueError("rope_dim must be even, max_positions whole decode blocks")
        if self.router_bias and self.router_groups:
            raise ValueError("the bias-corrected router is written down without groups")
        if self.indexer_types:
            if kinds != {MLA} or len(self.indexer_types) != len(self.layer_types) \
                    or not set(self.indexer_types) <= {FULL, SHARED}:
                raise ValueError("indexer_types: 'full' or 'shared' for each of the mla layers")
            if self.indexer_types[0] != FULL:
                raise ValueError("the first layer held here must own its indexer ('full')")
            if min(self.index_heads, self.index_topk) < 1 or self.index_dim < self.rope_dim:
                raise ValueError("an indexer needs index_heads, index_topk, index_dim >= rope_dim")
            if self.max_positions % 8:
                raise ValueError("under an indexer max_positions must be whole bytes of row bits")

    # derived widths
    @property
    def head_dim(self) -> int:
        return self.hidden // self.heads

    @property
    def mamba_inner(self) -> int:
        return self.mamba_heads * self.mamba_head_dim

    @property
    def conv_width(self) -> int:
        return self.mamba_inner + 2 * self.mamba_groups * self.mamba_state

    @property
    def in_proj_width(self) -> int:
        return self.mamba_inner + self.conv_width + self.mamba_heads

    @property
    def latent_width(self) -> int:
        """One cache row of an MLA layer: ``[c_kv | k_r]``."""
        return self.kv_rank + self.rope_dim

    @property
    def decode_rows(self) -> int:
        return min(self.decode_block, self.max_positions)

    @property
    def ffn_types(self) -> tuple:
        n = len(self.layer_types)
        return (DENSE,) * self.dense_layers + (MOE,) * (n - self.dense_layers)

    @property
    def layers(self) -> tuple:
        """(mixer kind, feed-forward kind) a layer."""
        return tuple(zip(self.layer_types, self.ffn_types))

    @property
    def expert_layers(self) -> int:
        return len(self.layer_types) - self.dense_layers

    @property
    def index_types(self) -> tuple:
        """``indexer_types``, or None a layer where no layer has an indexer."""
        return self.indexer_types or (None,) * len(self.layer_types)

    @property
    def index_layers(self) -> int:
        """Layers that own an indexer (and its key rows in the cache)."""
        return sum(kind == FULL for kind in self.indexer_types)

    @property
    def index_spans(self) -> tuple:
        """For each layer that owns an indexer, the layers that attend its
        choice: itself and those that share it."""
        owners = [i for i, kind in enumerate(self.indexer_types) if kind == FULL]
        return tuple(b - a for a, b in zip(owners, owners[1:] + [len(self.indexer_types)]))

    @classmethod
    def from_hf(cls, hf: dict, *, layers: int | None = None, first_layer: int = 0,
                experts_held: tuple | None = None,
                vocab_held: tuple | None = None, **serving) -> "DecoderConfig":
        """From a ``config.json`` of ``model_type`` ``granitemoehybrid``
        (also when the key is missing), ``deepseek_v2`` or ``glm_moe_dsa``;
        any other is refused. ``layers`` keeps that many of the published
        layers from ``first_layer`` on; ``experts_held`` and ``vocab_held``
        give this chip's share (default: everything)."""
        model_type = hf.get("model_type", "granitemoehybrid")
        if model_type not in _FROM_HF:
            raise ValueError(
                f"model_type {model_type!r} is not written down here (has: {sorted(_FROM_HF)})")
        layers = layers or hf["num_hidden_layers"] - first_layer
        if first_layer < 0 or layers < 1 or first_layer + layers > hf["num_hidden_layers"]:
            raise ValueError(
                f"layers {first_layer}..{first_layer + layers} outside the published "
                f"{hf['num_hidden_layers']}")
        fields, experts_key = _FROM_HF[model_type](hf, first_layer, layers)
        return cls(
            hidden=hf["hidden_size"], vocab_size=hf["vocab_size"],
            vocab_held=tuple(vocab_held or (0, hf["vocab_size"])),
            heads=hf["num_attention_heads"], kv_heads=hf["num_key_value_heads"],
            experts=hf[experts_key], experts_per_token=hf["num_experts_per_tok"],
            experts_held=tuple(experts_held or (0, hf[experts_key])),
            rms_eps=hf["rms_norm_eps"], **fields, **serving,
        )

    @classmethod
    def tiny(cls) -> "DecoderConfig":
        """Test geometry: every mechanism, toy widths (m m A m, 8 experts
        top-3, one share of 4, a 64-row slice of 128 rows)."""
        return cls(
            hidden=32, layer_types=(MAMBA, MAMBA, ATTENTION, MAMBA),
            vocab_size=128, vocab_held=(0, 64), heads=4, kv_heads=2,
            attention_multiplier=0.125, mamba_heads=8, mamba_head_dim=8,
            mamba_state=16, mamba_conv=4, mamba_chunk=8, experts=8,
            experts_per_token=3, experts_held=(0, 4), expert_width=16,
            shared_width=32, prefill_chunk=16, max_positions=64, slots=4,
        )

    @classmethod
    def tiny_mla(cls) -> "DecoderConfig":
        """``tiny``'s DeepSeek-shaped sibling: a dense layer then two
        expert layers, all latent attention (4 heads of 8 + 4, latent 16 +
        4), 16 experts in 4 groups, the best 2 groups, top-3, one share of
        4 experts (a whole group), a 64-row slice, an untied head."""
        return cls(
            hidden=32, layer_types=(MLA,) * 3, vocab_size=128, vocab_held=(0, 64),
            heads=4, kv_heads=4, experts=16, experts_per_token=3, experts_held=(0, 4),
            expert_width=16, shared_width=32, embedding_multiplier=1.0,
            residual_multiplier=1.0, logits_scaling=1.0, rms_eps=1e-6,
            prefill_chunk=16, max_positions=64, slots=4, tied_head=False,
            dense_layers=1, dense_width=48, router_groups=4, router_top_groups=2,
            routed_scaling=4.0, q_rank=24, kv_rank=16, nope_dim=8, rope_dim=4, v_dim=8,
            rope=YarnRope(factor=4.0, original_positions=16, beta_fast=4.0),
            decode_block=16,
        )

    @classmethod
    def tiny_dsa(cls) -> "DecoderConfig":
        """``tiny_mla``'s GLM-shaped sibling: five latent-attention layers
        under plain rotary frequencies whose indexers go ``full`` (the
        dense layer) ``shared shared full shared``, 3 index heads of 8
        that keep 8 cached rows a query, 16 experts top-3 under the
        sigmoid bias-corrected router, one share of 4."""
        return dataclasses.replace(
            cls.tiny_mla(), layer_types=(MLA,) * 5, rms_eps=1e-5, router_groups=0,
            router_top_groups=0, routed_scaling=2.5, rope=PlainRope(theta=10000.0),
            router_bias=True, index_heads=3, index_dim=8, index_topk=8,
            indexer_types=(FULL, SHARED, SHARED, FULL, SHARED),
        )


def _granite_fields(hf: dict, first: int, layers: int) -> tuple[dict, str]:
    if hf.get("position_embedding_type", "nope") != "nope":
        raise ValueError("only position_embedding_type 'nope' is written down here")
    if hf["mamba_expand"] * hf["hidden_size"] != hf["mamba_n_heads"] * hf["mamba_d_head"]:
        raise ValueError("mamba_expand x hidden_size != mamba_n_heads x mamba_d_head")
    return {
        "layer_types": tuple(hf["layer_types"])[first:first + layers],
        "attention_multiplier": hf["attention_multiplier"],
        "mamba_heads": hf["mamba_n_heads"], "mamba_head_dim": hf["mamba_d_head"],
        "mamba_state": hf["mamba_d_state"], "mamba_groups": hf["mamba_n_groups"],
        "mamba_conv": hf["mamba_d_conv"], "mamba_chunk": hf["mamba_chunk_size"],
        "expert_width": hf["intermediate_size"],
        "shared_width": hf["shared_intermediate_size"],
        "embedding_multiplier": hf["embedding_multiplier"],
        "residual_multiplier": hf["residual_multiplier"],
        "logits_scaling": hf["logits_scaling"],
    }, "num_local_experts"


def _mla_fields(hf: dict, first: int, layers: int) -> dict:
    """What the two latent-attention families share."""
    return {
        "layer_types": (MLA,) * layers,
        "tied_head": bool(hf.get("tie_word_embeddings", False)),
        "dense_layers": min(max(hf["first_k_dense_replace"] - first, 0), layers),
        "dense_width": hf["intermediate_size"],
        "expert_width": hf["moe_intermediate_size"],
        "shared_width": hf["n_shared_experts"] * hf["moe_intermediate_size"],
        "routed_scaling": float(hf["routed_scaling_factor"]),
        "q_rank": hf["q_lora_rank"], "kv_rank": hf["kv_lora_rank"],
        "nope_dim": hf["qk_nope_head_dim"], "rope_dim": hf["qk_rope_head_dim"],
        "v_dim": hf["v_head_dim"],
        "embedding_multiplier": 1.0, "residual_multiplier": 1.0, "logits_scaling": 1.0,
        "attention_multiplier": 0.0,     # MLA's scale comes from its dims (and YaRN)
    }


def _deepseek_v2_fields(hf: dict, first: int, layers: int) -> tuple[dict, str]:
    scaling = hf.get("rope_scaling") or {}
    if scaling.get("type") != "yarn":
        raise ValueError("deepseek_v2: only rope_scaling of type 'yarn' is written down here")
    if (hf.get("topk_method"), hf.get("scoring_func"), hf.get("norm_topk_prob")) != (
            "group_limited_greedy", "softmax", False) or hf.get("moe_layer_freq", 1) != 1:
        raise ValueError(
            "deepseek_v2: only the group_limited_greedy softmax router with gates not "
            "renormalised, on every layer past the dense ones, is written down here")
    if not hf.get("q_lora_rank"):
        raise ValueError("deepseek_v2: only low-rank queries (q_lora_rank) are written down here")
    return {
        **_mla_fields(hf, first, layers),
        "router_groups": hf["n_group"], "router_top_groups": hf["topk_group"],
        "rope": YarnRope(
            theta=float(hf["rope_theta"]), factor=float(scaling["factor"]),
            original_positions=int(scaling["original_max_position_embeddings"]),
            beta_fast=float(scaling["beta_fast"]), beta_slow=float(scaling["beta_slow"]),
            mscale=float(scaling["mscale"]), mscale_all_dim=float(scaling["mscale_all_dim"])),
    }, "n_routed_experts"


def _glm_moe_dsa_fields(hf: dict, first: int, layers: int) -> tuple[dict, str]:
    """What is written down of ``glm_moe_dsa``; everything else is refused.
    The draft module (``num_nextn_predict_layers``) is no layer of the main
    pass and is not loaded."""
    rope = hf.get("rope_parameters") or {}
    if rope.get("rope_type") != "default" or "rope_theta" not in rope:
        raise ValueError("glm_moe_dsa: only rope_parameters of rope_type 'default' are written down here")
    if not (hf.get("rope_interleave") and hf.get("indexer_rope_interleave")):
        raise ValueError("glm_moe_dsa: only interleaved rotary pairs (2i, 2i + 1) are written down here")
    if (hf.get("topk_method"), hf.get("scoring_func"), hf.get("norm_topk_prob")) != (
            "noaux_tc", "sigmoid", True) or hf.get("moe_layer_freq", 1) != 1:
        raise ValueError(
            "glm_moe_dsa: only the sigmoid bias-corrected (noaux_tc) router with "
            "renormalised gates, on every layer past the dense ones, is written down here")
    if (hf.get("n_group", 1), hf.get("topk_group", 1)) != (1, 1):
        raise ValueError("glm_moe_dsa: only n_group == topk_group == 1 (no group limit) is written down here")
    if hf.get("index_topk_pattern") is not None:
        raise ValueError("glm_moe_dsa: only index_topk_pattern null (one index_topk) is written down here")
    if not hf.get("q_lora_rank"):
        raise ValueError("glm_moe_dsa: only low-rank queries (q_lora_rank) are written down here")
    if hf.get("attention_bias"):
        raise ValueError("glm_moe_dsa: attention_bias is not written down here")
    kinds = tuple(hf["indexer_types"])[first:first + layers]
    if not set(kinds) <= {FULL, SHARED}:
        raise ValueError(f"glm_moe_dsa: indexer_types {sorted(set(kinds))}: only 'full' and 'shared'")
    if kinds[0] != FULL:
        raise ValueError(
            "glm_moe_dsa: the first layer held here is 'shared': its choice comes from a "
            "layer that is not here")
    fields = _mla_fields(hf, first, layers)
    ffn = tuple(hf["mlp_layer_types"])[first:first + layers]
    want = ("dense",) * fields["dense_layers"] + ("sparse",) * (layers - fields["dense_layers"])
    if ffn != want:
        raise ValueError(
            "glm_moe_dsa: mlp_layer_types other than first_k_dense_replace dense layers, "
            "then sparse ones, are not written down here")
    return {
        **fields, "rope": PlainRope(theta=float(rope["rope_theta"])), "router_bias": True,
        "indexer_types": kinds, "index_heads": hf["index_n_heads"],
        "index_dim": hf["index_head_dim"], "index_topk": hf["index_topk"],
    }, "n_routed_experts"


_FROM_HF = {"granitemoehybrid": _granite_fields, "deepseek_v2": _deepseek_v2_fields,
            "glm_moe_dsa": _glm_moe_dsa_fields}


# -- parameters ------------------------------------------------------------------


def layer_shapes(cfg: DecoderConfig, kind: str, ffn: str = MOE,
                 index: str | None = None) -> dict[str, tuple]:
    """Leaf name -> shape of one layer of mixer ``kind`` and feed-forward
    ``ffn``; ``index`` ``"full"``: the layer owns an indexer (a ``shared``
    layer has no leaf of its own for it)."""
    h, held = cfg.hidden, cfg.experts_held[1]
    block = {"norm1": (h,), "norm2": (h,)}
    if ffn == MOE:
        block.update({
            "router": (h, cfg.experts),
            "shared_in": (h, 2 * cfg.shared_width),
            "shared_out": (cfg.shared_width, h),
            "experts_in": (held, h, 2 * cfg.expert_width),
            "experts_out": (held, cfg.expert_width, h),
        })
        if cfg.router_bias:
            block["router_bias"] = (cfg.experts,)
    else:
        block.update({"mlp_in": (h, 2 * cfg.dense_width), "mlp_out": (cfg.dense_width, h)})
    if kind == MAMBA:
        block.update({
            "in_proj": (h, cfg.in_proj_width),
            "conv_w": (cfg.mamba_conv, cfg.conv_width),
            "conv_b": (cfg.conv_width,),
            "dt_bias": (cfg.mamba_heads,), "A_log": (cfg.mamba_heads,),
            "D": (cfg.mamba_heads,),
            "mixer_norm": (cfg.mamba_inner,),
            "out_proj": (cfg.mamba_inner, h),
        })
    elif kind == ATTENTION:
        qd, kvd = cfg.heads * cfg.head_dim, cfg.kv_heads * cfg.head_dim
        block.update({"wq": (h, qd), "wk": (h, kvd), "wv": (h, kvd), "wo": (qd, h)})
    else:
        block.update({
            "w_dq": (h, cfg.q_rank), "q_norm": (cfg.q_rank,),
            "w_uq": (cfg.q_rank, cfg.heads * (cfg.nope_dim + cfg.rope_dim)),
            "w_dkv": (h, cfg.latent_width), "kv_norm": (cfg.kv_rank,),
            # a head's columns are [k_nope | v], as the published kv_b_proj's rows
            "w_ukv": (cfg.kv_rank, cfg.heads * (cfg.nope_dim + cfg.v_dim)),
            "wo": (cfg.heads * cfg.v_dim, h),
        })
        if index == FULL:
            block.update({
                "w_iq": (cfg.q_rank, cfg.index_heads * cfg.index_dim),
                "w_ik": (h, cfg.index_dim),
                "ik_norm": (cfg.index_dim,), "ik_bias": (cfg.index_dim,),   # a LayerNorm's
                "w_iw": (h, cfg.index_heads),
            })
    return block


_NORM_LEAVES = frozenset(("norm1", "norm2", "mixer_norm", "q_norm", "kv_norm", "ik_norm"))
_BIAS_LEAVES = frozenset(("router_bias", "ik_bias"))
_F32_LEAVES = _NORM_LEAVES | _BIAS_LEAVES | frozenset(
    ("conv_w", "conv_b", "dt_bias", "A_log", "D"))
ROUTER_BIAS_STD = 0.01      # the correction bias of random weights: N(0, 0.01) (assumed)


def init_layer(cfg: DecoderConfig, kind: str, key, ffn: str = MOE,
               index: str | None = None) -> dict:
    """One layer's weights from its key: matrices N(0, 0.02) in bfloat16;
    norm scales 1 + N(0, 0.02); the router's correction bias N(0, 0.01) and
    the index keys' LayerNorm bias N(0, 0.02); Mamba's own conventions for the rest
    (``A_log`` = log U(1, 16), ``dt_bias`` = softplus^-1 of a step size
    log-uniform in [1e-3, 1e-1], ``D`` = 1, convolution U(-1/2, 1/2) with
    bias, which is Conv1d's default at fan-in 4)."""
    out = {}
    for i, (name, shape) in enumerate(sorted(layer_shapes(cfg, kind, ffn, index).items())):
        k = jax.random.fold_in(key, i)
        if name in _NORM_LEAVES:
            leaf = 1.0 + 0.02 * jax.random.normal(k, shape, jnp.float32)
        elif name in _BIAS_LEAVES:
            std = ROUTER_BIAS_STD if name == "router_bias" else 0.02
            leaf = std * jax.random.normal(k, shape, jnp.float32)
        elif name == "A_log":
            leaf = jnp.log(jax.random.uniform(k, shape, jnp.float32, 1.0, 16.0))
        elif name == "dt_bias":
            dt = jnp.exp(jax.random.uniform(k, shape, jnp.float32, np.log(1e-3), np.log(1e-1)))
            leaf = dt + jnp.log(-jnp.expm1(-dt))
        elif name == "D":
            leaf = jnp.ones(shape, jnp.float32)
        elif name in ("conv_w", "conv_b"):
            leaf = jax.random.uniform(k, shape, jnp.float32, -0.5, 0.5)
        else:
            leaf = (0.02 * jax.random.normal(k, shape, jnp.float32)).astype(jnp.bfloat16)
        out[name] = leaf
    return out


def init_params(cfg: DecoderConfig, seed: int = 0) -> dict:
    """Random parameters, each layer from its own key (a reference can
    then make one layer at a time): ``{"embed", "final_norm", "layers":
    [one tree a layer]}``, and ``"head"`` where the head is not tied."""
    root = jax.random.PRNGKey(seed)
    kinds = [(*layer, index) for layer, index in zip(cfg.layers, cfg.index_types)]
    make = {
        layer: jax.jit(functools.partial(init_layer, cfg, layer[0], ffn=layer[1], index=layer[2]))
        for layer in set(kinds)
    }
    rows = cfg.vocab_held[1]

    def table(stream):
        k = jax.random.fold_in(root, stream)
        return (0.02 * jax.random.normal(k, (rows, cfg.hidden), jnp.float32)).astype(jnp.bfloat16)

    params = {
        "embed": table(1_000_000),
        "final_norm": jnp.ones((cfg.hidden,), jnp.float32),
        "layers": [
            make[layer](jax.random.fold_in(root, i)) for i, layer in enumerate(kinds)
        ],
    }
    if not cfg.tied_head:
        params["head"] = table(1_000_001)
    return params


@functools.lru_cache(maxsize=None)
def param_bytes(cfg: DecoderConfig) -> float:
    """HBM bytes of the held parameters (bfloat16 matrices, float32 vectors)."""
    tables = 1 if cfg.tied_head else 2
    total = tables * 2.0 * cfg.vocab_held[1] * cfg.hidden + 4.0 * cfg.hidden
    for (kind, ffn), index in zip(cfg.layers, cfg.index_types):
        for name, shape in layer_shapes(cfg, kind, ffn, index).items():
            total += (4.0 if name in _F32_LEAVES else 2.0) * float(np.prod(shape))
    return total


def cache_bytes(cfg: DecoderConfig) -> float:
    """HBM bytes of the state cache: ``slots`` + 1 (the scratch slot padded
    decode rows write to) of convolution tail (bfloat16) and SSM state
    (float32) a Mamba layer, keys and values (bfloat16) an attention
    layer, latent rows (bfloat16) an MLA layer and, where it owns an
    indexer, its key rows (bfloat16) beside them."""
    per_kind = {
        MAMBA: 2.0 * (cfg.mamba_conv - 1) * cfg.conv_width
        + 4.0 * cfg.mamba_heads * cfg.mamba_head_dim * cfg.mamba_state,
        ATTENTION: 2 * 2.0 * cfg.max_positions * cfg.kv_heads * cfg.head_dim,
        MLA: 2.0 * cfg.max_positions * cfg.latent_width,
    }
    index_rows = 2.0 * cfg.max_positions * cfg.index_dim * cfg.index_layers
    return (cfg.slots + 1) * (sum(per_kind[kind] for kind in cfg.layer_types) + index_rows)


# -- layers ------------------------------------------------------------------------

_f32 = jnp.float32
_bf16 = jnp.bfloat16


def _mm(a, w):
    """bfloat16 operands, float32 result."""
    return jnp.dot(a.astype(_bf16), w, preferred_element_type=_f32)


def rms_norm(x, scale, eps: float):
    x = x.astype(_f32)
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale


def glu(a, width: int):
    return jax.nn.silu(a[..., :width]) * a[..., width:]


def route(cfg: DecoderConfig, p: dict, u, live):
    """Router over ALL experts: (selected ids [T, k], gates [T, k] float32,
    held [T, k]: the selection is one of this chip's experts and the row
    is live). Without router groups: the k best logits, gates their
    softmax. With them (group-limited greedy): scores are the softmax over
    all experts, a group's score its best expert's, only the
    ``router_top_groups`` best groups' experts stand, the k best of those
    are selected, gates their scores times ``routed_scaling``. With a
    bias (``noaux_tc``): scores are sigmoids, the k best of score + bias
    are selected (the bias chooses and does not weigh), gates the selected
    scores over their sum, times ``routed_scaling``."""
    logits = _mm(u, p["router"])
    if cfg.router_bias:
        scores = jax.nn.sigmoid(logits)
        _, sel = jax.lax.top_k(scores + p["router_bias"], cfg.experts_per_token)
        top = jnp.take_along_axis(scores, sel, axis=-1)
        gates = cfg.routed_scaling * top / jnp.sum(top, axis=-1, keepdims=True)
    elif cfg.router_groups:
        scores = jax.nn.softmax(logits, axis=-1)
        T, G = scores.shape[0], cfg.router_groups
        _, best = jax.lax.top_k(
            jnp.max(scores.reshape(T, G, -1), axis=-1), cfg.router_top_groups)
        stands = jnp.any(best[:, :, None] == jnp.arange(G)[None, None, :], axis=1)
        stands = jnp.repeat(stands, cfg.experts // G, axis=1)
        top, sel = jax.lax.top_k(jnp.where(stands, scores, 0.0), cfg.experts_per_token)
        gates = cfg.routed_scaling * top
    else:
        top, sel = jax.lax.top_k(logits, cfg.experts_per_token)
        gates = jax.nn.softmax(top, axis=-1)
    first, n_held = cfg.experts_held
    held = (sel >= first) & (sel < first + n_held) & live[:, None]
    return sel, gates, held


def routed_experts(cfg: DecoderConfig, p: dict, u, sel, gates, held):
    """This chip's experts' part of the routed sum, as grouped products
    over ragged token groups: the (token, selection) pairs sorted by
    expert, one ``ragged_dot`` in and one out, unsorted and weighted.
    Returns (sum [T, hidden] float32, tokens per held expert [E_held])."""
    T, k = sel.shape
    first, n_held = cfg.experts_held
    flat = jnp.where(held, sel - first, n_held).reshape(-1)
    order = jnp.argsort(flat, stable=True)
    sizes = jnp.sum(
        flat[:, None] == jnp.arange(n_held, dtype=flat.dtype)[None, :], axis=0,
        dtype=jnp.int32,
    )
    rows = u.astype(_bf16)[order // k]
    a = jax.lax.ragged_dot(rows, p["experts_in"], sizes, preferred_element_type=_f32)
    mid = glu(a, cfg.expert_width).astype(_bf16)
    y = jax.lax.ragged_dot(mid, p["experts_out"], sizes, preferred_element_type=_f32)
    # rows past the last group belong to no expert held here
    y = jnp.where((jnp.arange(T * k) < jnp.sum(sizes))[:, None], y, 0.0)
    back = jnp.argsort(order)
    y = y[back].reshape(T, k, -1)
    weight = jnp.where(held, gates, 0.0)
    return jnp.einsum("tk,tkh->th", weight, y), sizes


def shared_mlp(cfg: DecoderConfig, p: dict, u):
    return _mm(glu(_mm(u, p["shared_in"]), cfg.shared_width), p["shared_out"])


def dense_block(cfg: DecoderConfig, p: dict, x):
    """x + r * mlp(norm2(x)): the feed-forward of a leading dense layer."""
    u = rms_norm(x, p["norm2"], cfg.rms_eps)
    return x + cfg.residual_multiplier * _mm(
        glu(_mm(u, p["mlp_in"]), cfg.dense_width), p["mlp_out"])


def moe_block(cfg: DecoderConfig, p: dict, x, live):
    """x + r * (routed(u) + shared(u)), u = norm2(x); and what the layer
    counted: selections [T, k], tokens per held expert, selections of
    live rows that fell on absent experts."""
    u = rms_norm(x, p["norm2"], cfg.rms_eps)
    sel, gates, held = route(cfg, p, u, live)
    routed, sizes = routed_experts(cfg, p, u, sel, gates, held)
    x = x + cfg.residual_multiplier * (routed + shared_mlp(cfg, p, u))
    absent = jnp.sum(live[:, None] & ~held, dtype=jnp.int32)
    return x, {"sel": sel.astype(jnp.int32), "counts": sizes, "absent": absent}


def _split_proj(cfg: DecoderConfig, proj):
    """[z | xBC | dt] of the input projection; xBC is rounded to the
    activation dtype here, so the convolution sees the same values
    whether they come from this chunk or from the carried tail."""
    di, cw = cfg.mamba_inner, cfg.conv_width
    return proj[..., :di], proj[..., di:di + cw].astype(_bf16), proj[..., di + cw:]


def _split_xbc(cfg: DecoderConfig, xbc):
    di, n = cfg.mamba_inner, cfg.mamba_state
    lead = xbc.shape[:-1]
    x = xbc[..., :di].reshape(*lead, cfg.mamba_heads, cfg.mamba_head_dim)
    return x, xbc[..., di:di + n], xbc[..., di + n:]


def _gated_out(cfg: DecoderConfig, p: dict, y, z):
    y = rms_norm(y * jax.nn.silu(z), p["mixer_norm"], cfg.rms_eps)
    return _mm(y, p["out_proj"])


def mamba_prefill(cfg: DecoderConfig, p: dict, u, tail, ssm, n):
    """Mamba-2 mixer over one chunk of one sequence, the recurrence
    computed chunk-wise (``mamba_chunk`` positions at a time). ``u``
    [T, hidden]; ``tail`` [conv-1, conv_width] bfloat16, the last inputs
    of the convolution; ``ssm`` [heads, head_dim, state] float32; ``n``
    real positions. Returns (out [T, hidden], tail, ssm)."""
    T, H, Q = u.shape[0], cfg.mamba_heads, cfg.mamba_chunk
    K = cfg.mamba_conv
    z, xbc, dt = _split_proj(cfg, _mm(u, p["in_proj"]))
    ext = jnp.concatenate([tail, xbc], axis=0).astype(_f32)      # [K-1+T, C]
    conv = p["conv_b"] + sum(p["conv_w"][j] * ext[j:j + T] for j in range(K))
    new_tail = jax.lax.dynamic_slice_in_dim(ext, n, K - 1, axis=0).astype(_bf16)
    x, B, C = _split_xbc(cfg, jax.nn.silu(conv))
    real = jnp.arange(T) < n
    dt = jnp.where(real[:, None], jax.nn.softplus(dt + p["dt_bias"]), 0.0)
    A = -jnp.exp(p["A_log"])
    nc = T // Q
    a = (dt * A).reshape(nc, Q, H)
    cum = jnp.cumsum(a, axis=1)                                     # [c, Q, H]
    dtc, xc = dt.reshape(nc, Q, H), x.reshape(nc, Q, H, -1)
    Bc, Cc = B.reshape(nc, Q, -1), C.reshape(nc, Q, -1)
    # inside a chunk: position i reads j <= i through exp(sum of a over (j, i])
    lower = jnp.tril(jnp.ones((Q, Q), bool))[None, :, :, None]
    seg = jnp.where(lower, cum[:, :, None, :] - cum[:, None, :, :], -jnp.inf)
    w = jnp.exp(seg) * jnp.einsum("cin,cjn->cij", Cc, Bc)[..., None] * dtc[:, None]
    y = jnp.einsum("cijh,cjhp->cihp", w, xc)
    # what each chunk leaves behind, and what it receives
    to_end = jnp.exp(cum[:, -1:, :] - cum) * dtc                    # [c, Q, H]
    left = jnp.einsum("cjh,cjhp,cjn->chpn", to_end, xc, Bc)
    chunk_decay = jnp.exp(cum[:, -1, :])                            # [c, H]
    received = []
    for c in range(nc):
        received.append(ssm)
        ssm = chunk_decay[c][:, None, None] * ssm + left[c]
    received = jnp.stack(received)                                  # [c, H, P, N]
    y = y + jnp.einsum("cin,chpn->cihp", Cc, received) * jnp.exp(cum)[..., None]
    y = (y + p["D"][:, None] * xc).reshape(T, -1)
    return _gated_out(cfg, p, y, z), new_tail, ssm


def mamba_decode(cfg: DecoderConfig, p: dict, u, tail, ssm):
    """One recurrence step for a batch: ``u`` [B, hidden], ``tail``
    [B, conv-1, conv_width], ``ssm`` [B, heads, head_dim, state]."""
    z, xbc, dt = _split_proj(cfg, _mm(u, p["in_proj"]))
    ext = jnp.concatenate([tail, xbc[:, None]], axis=1)             # [B, K, C]
    conv = p["conv_b"] + jnp.einsum("kc,bkc->bc", p["conv_w"], ext.astype(_f32))
    x, B, C = _split_xbc(cfg, jax.nn.silu(conv))
    dt = jax.nn.softplus(dt + p["dt_bias"])                         # [B, H]
    decay = jnp.exp(dt * -jnp.exp(p["A_log"]))
    ssm = (
        decay[:, :, None, None] * ssm
        + (dt[:, :, None] * x)[..., None] * B[:, None, None, :]
    )
    y = jnp.sum(ssm * C[:, None, None, :], axis=-1) + p["D"][:, None] * x
    return _gated_out(cfg, p, y.reshape(y.shape[0], -1), z), ext[:, 1:], ssm


def _attend(cfg: DecoderConfig, q, k, v, visible):
    """q [.., T, heads, d] against k, v [.., P, kv_heads, d]; ``visible``
    [.., T, P]. Scores scaled by ``attention_multiplier``, softmax in
    float32, no positional encoding."""
    g = cfg.heads // cfg.kv_heads
    q = q.reshape(*q.shape[:-2], cfg.kv_heads, g, cfg.head_dim)
    s = jnp.einsum("...tkgd,...pkd->...kgtp", q, k, preferred_element_type=_f32)
    s = jnp.where(visible[..., None, None, :, :], s * cfg.attention_multiplier,
                  jnp.finfo(_f32).min)
    w = jax.nn.softmax(s, axis=-1).astype(_bf16)
    out = jnp.einsum("...kgtp,...pkd->...tkgd", w, v, preferred_element_type=_f32)
    return out.reshape(*out.shape[:-3], cfg.heads * cfg.head_dim)


def _qkv(cfg: DecoderConfig, p: dict, u):
    lead = u.shape[:-1]
    q = _mm(u, p["wq"]).astype(_bf16).reshape(*lead, cfg.heads, cfg.head_dim)
    k = _mm(u, p["wk"]).astype(_bf16).reshape(*lead, cfg.kv_heads, cfg.head_dim)
    v = _mm(u, p["wv"]).astype(_bf16).reshape(*lead, cfg.kv_heads, cfg.head_dim)
    return q, k, v


def attention_prefill(cfg: DecoderConfig, p: dict, u, keys, values, pos, n):
    """One chunk at positions ``pos``..: its keys/values go into the
    slot's slab (``keys``, ``values`` [positions, kv_heads, d]; a padded
    position writes nothing), its queries read the slab causally."""
    T, P = u.shape[0], keys.shape[0]
    q, k, v = _qkv(cfg, p, u)
    real = (jnp.arange(T) < n)[:, None, None]
    old_k = jax.lax.dynamic_slice_in_dim(keys, pos, T, axis=0)
    old_v = jax.lax.dynamic_slice_in_dim(values, pos, T, axis=0)
    keys = jax.lax.dynamic_update_slice_in_dim(keys, jnp.where(real, k, old_k), pos, 0)
    values = jax.lax.dynamic_update_slice_in_dim(values, jnp.where(real, v, old_v), pos, 0)
    visible = jnp.arange(P)[None, :] <= (pos + jnp.arange(T))[:, None]
    return _mm(_attend(cfg, q, keys, values, visible), p["wo"]), keys, values


def attention_decode(cfg: DecoderConfig, p: dict, u, keys, values, pos):
    """One token a sequence: ``keys``, ``values`` [B, positions, kv, d],
    ``pos`` [B] the token's position."""
    q, k, v = _qkv(cfg, p, u)
    rows = jnp.arange(u.shape[0])
    keys = keys.at[rows, pos].set(k)
    values = values.at[rows, pos].set(v)
    visible = (jnp.arange(keys.shape[1])[None, :] <= pos[:, None])[:, None, :]
    out = _attend(cfg, q[:, None], keys, values, visible)[:, 0]
    return _mm(out, p["wo"]), keys, values


# -- latent attention ---------------------------------------------------------------


def yarn_inv_freq(cfg: DecoderConfig) -> np.ndarray:
    """The ``rope_dim`` / 2 rotary frequencies (float64). Plain:
    theta^(-2i / rope_dim). Under YaRN: pairs that turn more than
    ``beta_fast`` times over the original positions keep their
    frequency, those that turn less than ``beta_slow`` times are divided
    by ``factor``, a linear ramp between."""
    r, d = cfg.rope, cfg.rope_dim
    freq = r.theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    if isinstance(r, PlainRope):
        return freq

    def dim_of(turns):        # the dim at which a pair makes ``turns`` turns
        return d * np.log(r.original_positions / (turns * 2 * np.pi)) / (2 * np.log(r.theta))

    low = max(np.floor(dim_of(r.beta_fast)), 0)
    high = min(np.ceil(dim_of(r.beta_slow)), d - 1)
    ramp = np.clip((np.arange(d // 2) - low) / max(high - low, 1e-3), 0, 1)
    return freq / r.factor * ramp + freq * (1 - ramp)


def _yarn_mscale(factor: float, m: float) -> float:
    return 0.1 * m * np.log(factor) + 1.0 if factor > 1 else 1.0


def mla_scale(cfg: DecoderConfig) -> float:
    """Softmax scale: (nope + rope)^-1/2, under YaRN times mscale(factor, mscale_all_dim)^2."""
    plain = (cfg.nope_dim + cfg.rope_dim) ** -0.5
    if isinstance(cfg.rope, PlainRope):
        return float(plain)
    return float(plain * _yarn_mscale(cfg.rope.factor, cfg.rope.mscale_all_dim) ** 2)


def _rotate(cfg: DecoderConfig, x, positions):
    """Rotary embedding of ``x`` [.., rope_dim] at ``positions`` (shaped as
    x's leading dims, or broadcastable to them): the published code's
    pairing (dims 2i and 2i + 1 turn together; the result holds the first
    of every pair, then the second), under YaRN cos and sin scaled by
    mscale(factor, mscale) / mscale(factor, mscale_all_dim)."""
    r = cfg.rope
    angles = positions[..., None].astype(_f32) * jnp.asarray(yarn_inv_freq(cfg), _f32)
    if isinstance(r, PlainRope):
        cos, sin = jnp.cos(angles), jnp.sin(angles)
    else:
        m = _yarn_mscale(r.factor, r.mscale) / _yarn_mscale(r.factor, r.mscale_all_dim)
        cos, sin = jnp.cos(angles) * m, jnp.sin(angles) * m
    a, b = x[..., 0::2].astype(_f32), x[..., 1::2].astype(_f32)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _mla_project(cfg: DecoderConfig, p: dict, u, positions):
    """(q_nope [.., heads, nope], q_rope [.., heads, rope] rotated, cache
    rows [.., kv_rank + rope] = [c_kv after its norm | k_r rotated]), all
    bfloat16; and the normed query latent c_q [.., q_rank] float32, which
    an indexer's queries are made from."""
    lead = u.shape[:-1]
    c_q = rms_norm(_mm(u, p["w_dq"]), p["q_norm"], cfg.rms_eps)
    q = _mm(c_q, p["w_uq"]).reshape(*lead, cfg.heads, cfg.nope_dim + cfg.rope_dim)
    q_rope = _rotate(cfg, q[..., cfg.nope_dim:], positions[..., None])
    down = _mm(u, p["w_dkv"])
    c_kv = rms_norm(down[..., :cfg.kv_rank], p["kv_norm"], cfg.rms_eps)
    k_r = _rotate(cfg, down[..., cfg.kv_rank:], positions)
    rows = jnp.concatenate([c_kv, k_r], axis=-1).astype(_bf16)
    return q[..., :cfg.nope_dim].astype(_bf16), q_rope.astype(_bf16), rows, c_q


# -- the indexer: which cached rows a query attends ---------------------------------

INDEX_NORM_EPS = 1e-6       # the index keys' LayerNorm (assumed: no key of the config)


def index_project(cfg: DecoderConfig, p: dict, u, c_q, positions):
    """The indexer's side of one layer's inputs: (q_I [.., index_heads,
    index_dim] bfloat16, the heads' weights w [.., index_heads] float32,
    k_I [.., index_dim] bfloat16: one key a position, the row the cache
    keeps). The first ``rope_dim`` dims of q_I and k_I are rotated, with
    the attention's frequencies and pairing."""
    lead, J, D, r = u.shape[:-1], cfg.index_heads, cfg.index_dim, cfg.rope_dim
    q = _mm(c_q, p["w_iq"]).reshape(*lead, J, D)
    q = jnp.concatenate([_rotate(cfg, q[..., :r], positions[..., None]), q[..., r:]], axis=-1)
    k = _mm(u, p["w_ik"])
    k = k - jnp.mean(k, axis=-1, keepdims=True)
    k = k * jax.lax.rsqrt(jnp.mean(jnp.square(k), axis=-1, keepdims=True) + INDEX_NORM_EPS)
    k = k * p["ik_norm"] + p["ik_bias"]
    k = jnp.concatenate([_rotate(cfg, k[..., :r], positions), k[..., r:]], axis=-1)
    w = _mm(u, p["w_iw"]) * float(J ** -0.5 * D ** -0.5)
    return q.astype(_bf16), w, k.astype(_bf16)


def _index_block(q, w, keys):
    """Index scores of one block of cached keys: sum over the index heads
    of w x relu(q . k). ``q`` [.., J, D], ``w`` [.., J], ``keys`` [.., R,
    D] -> [.., R] float32. The heads' scores [.., J, R] are the largest
    array the indexer makes."""
    s = jnp.einsum("...jd,...pd->...jp", q, keys, preferred_element_type=_f32)
    return jnp.sum(w[..., None] * jnp.maximum(s, 0.0), axis=-2)


def select_rows(scores, visible, k: int):
    """The ``k`` best visible entries of each row of ``scores`` [rows, P]
    float32 (all of them where a row sees no more than ``k``), the lowest
    position first among equal scores: a mask [rows, P]. No sort: the k-th
    largest value is found bit by bit on the scores' ordered bit
    patterns, 32 counting passes, and the last equal position to take by
    as many passes as P has bits."""
    R, P = scores.shape
    bits = jax.lax.bitcast_convert_type(scores, jnp.int32)
    # as unsigned integers in the scores' order; 0 is below every score's
    order = jax.lax.bitcast_convert_type(
        jnp.where(bits < 0, ~bits, bits | jnp.int32(-2 ** 31)), jnp.uint32)
    order = jnp.where(visible, order, jnp.uint32(0))

    def value_bit(i, kth):
        probe = kth | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        enough = jnp.sum(order >= probe[:, None], axis=1, dtype=jnp.int32) >= k
        return jnp.where(enough, probe, kth)

    kth = jax.lax.fori_loop(0, 32, value_bit, jnp.zeros((R,), jnp.uint32))
    above = order > kth[:, None]
    equal = (order == kth[:, None]) & visible
    room = k - jnp.sum(above, axis=1, dtype=jnp.int32)      # equal ones still to take
    at = jnp.arange(P, dtype=jnp.int32)[None, :]
    n_bits = int(P).bit_length()

    def position_bit(i, end):
        probe = end | (jnp.int32(1) << (n_bits - 1 - i))
        fits = jnp.sum(equal & (at < probe[:, None]), axis=1, dtype=jnp.int32) <= room
        return jnp.where(fits, probe, end)

    end = jax.lax.fori_loop(0, n_bits, position_bit, jnp.zeros((R,), jnp.int32))
    return (above | (equal & (at < end[:, None]))) & visible


def pack_rows(chosen):
    """A choice of rows [.., P] (not 0: chosen; NumPy or a device array)
    as bits [.., P / 8] uint8: bit k of byte j is row k * P / 8 + j, so
    that on the chip a byte is made of eight whole slices of lanes and no
    value changes its lane: one loop fusion (``numpy.packbits``' order
    gathers eight neighbours, and compiles to two transposing copies of
    the 42 MB choice at the published sizes)."""
    width = chosen.shape[-1] // 8
    return functools.reduce(operator.or_, (
        (chosen[..., k * width:(k + 1) * width] != 0).astype("uint8") << k for k in range(8)))


def unpack_rows(bits: np.ndarray) -> np.ndarray:
    """``pack_rows`` undone on the host: [.., P / 8] uint8 -> [.., P] bool."""
    return np.concatenate([(bits >> k) & 1 for k in range(8)], axis=-1).astype(bool)


_MASKED = -1e30     # a masked score: far below any real one, and exp() of it is 0


def _softmax_step(carry, s, visible, weigh, sparse: bool = False):
    """One block of an attention computed a block of keys at a time:
    ``carry`` = (running max [..], sum [..], weighted values [.., d]);
    ``s`` [.., keys] float32 scores; ``weigh(p)`` the block's values
    weighted by p [.., keys] (bfloat16)."""
    m, l, acc = carry
    s = jnp.where(visible, s, _MASKED)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1))
    p = jnp.exp(s - m_new[..., None])
    if sparse:      # a row may have seen nothing so far: its m_new is the mask's own
        p = jnp.where(visible, p, 0.0)
    fade = jnp.exp(m - m_new)
    return (m_new, l * fade + jnp.sum(p, axis=-1),
            acc * fade[..., None] + weigh(p.astype(_bf16)))


MLA_HEAD_GROUP = 8      # heads a grid step of the prefill attention kernel


def mla_lowering() -> str:
    """How ``mla_attend``'s kernel is lowered here: by Mosaic on a TPU,
    by Pallas' interpreter on any other backend."""
    return "mosaic" if jax.default_backend() == "tpu" else "interpret"


def _mla_attend_kernel(at_ref, q_nope_ref, q_rope_ref, rows_ref, w_ref, *refs,
                       scale: float, rk: int, nope: int):
    """One grid step (head group g, cached block b): the block's keys and
    values expanded from its latent rows through the group's rows of
    ``w_ukv`` transposed, the scores, the running-softmax step
    (``_softmax_step``'s, statement for statement) and the weighted
    values, a head at a time. The cached block arrives as the slab holds
    it, positions along the lanes (``rows_ref`` [1, kv_rank + rope,
    keys]), so keys and values are made transposed ([nope + v, keys]) and
    the scores are [T, keys] with no transposition in between. Running
    max, sum and weighted values stay in vector memory from block to
    block; the group's last block writes the output. Under an indexer one
    more input comes before the output: the block's columns of the
    queries' chosen rows (``chosen_ref`` [T, keys] int32, not 0: chosen);
    a query attends the chosen of its visible rows, and a block may hold
    none of them."""
    chosen_ref, out_ref, m_ref, l_ref, acc_ref = refs if len(refs) == 5 else (None, *refs)
    b, T = pl.program_id(1), rows_ref.shape[2]
    pos = at_ref[1]
    width = w_ref.shape[0] // q_nope_ref.shape[0]       # nope + v, one head's rows

    @pl.when(b == 0)
    def _start():
        m_ref[...] = jnp.full(m_ref.shape, _MASKED, _f32)
        l_ref[...] = jnp.zeros(l_ref.shape, _f32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, _f32)

    rows = rows_ref[0]
    c_kv, k_r = rows[:rk], rows[rk:]                     # [kv_rank, keys], [rope, keys]
    query_at = pos + jax.lax.broadcasted_iota(jnp.int32, (T, T), 0)
    key_at = b * T + jax.lax.broadcasted_iota(jnp.int32, (T, T), 1)
    visible = key_at <= query_at
    if chosen_ref is not None:
        visible = visible & (chosen_ref[...] != 0)
    for h in range(q_nope_ref.shape[0]):
        kv = jnp.dot(w_ref[h * width:(h + 1) * width], c_kv,
                     preferred_element_type=_f32).astype(_bf16)         # [nope + v, keys]
        s = jnp.dot(q_nope_ref[h], kv[:nope], preferred_element_type=_f32)
        s = s + jnp.dot(q_rope_ref[h], k_r, preferred_element_type=_f32)
        s = jnp.where(visible, s * scale, _MASKED)
        m = m_ref[h]
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        if chosen_ref is not None:      # a row with nothing chosen so far: m_new is the mask's
            p = jnp.where(visible, p, 0.0)
        fade = jnp.exp(m - m_new)
        m_ref[h] = m_new
        l_ref[h] = l_ref[h] * fade + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[h] = acc_ref[h] * fade + jax.lax.dot_general(
            p.astype(_bf16), kv[nope:], (((1,), (1,)), ((), ())),
            preferred_element_type=_f32)

    @pl.when(b == pl.num_programs(1) - 1)
    def _finish():
        out_ref[...] = (acc_ref[...] / l_ref[...]).astype(out_ref.dtype)


def mla_attend(cfg: DecoderConfig, q_nope, q_rope, latent, w_ukv, slot, pos, chosen=None):
    """Causal attention of one chunk's queries (``q_nope`` [heads, T,
    nope], ``q_rope`` [heads, T, rope] rotated, at positions ``pos``..)
    over ``slot``'s cached rows up to the chunk's own, a chunk-sized block
    of rows at a time: one Pallas kernel over (head group, cached block)
    whose scores never leave the chip's vector memory. ``slot`` reaches it
    as a prefetched scalar and the blocks it goes over (``pos // T + 1``)
    as the grid's dynamic bound, so the context shapes nothing: one
    executable. The slab is handed over with its positions last: the
    order a TPU keeps an array in whose rows are not whole lanes (kv_rank
    + rope = 576), so no copy is made of it. ``chosen`` [T, positions]
    int32 (an indexer's choice, not 0: chosen) masks each query to its
    chosen rows: every visible block is still computed. Returns [heads,
    T, v] bfloat16."""
    H, T, nope = q_nope.shape
    rk, v = cfg.kv_rank, cfg.v_dim
    lowering = mla_lowering()
    if lowering == "mosaic" and T % 128 and T != latent.shape[1]:
        # a cached block is [kv_rank + rope, T] with T along the lanes
        raise ValueError(
            f"prefill_chunk % 128 must be 0 for latent attention on a TPU, not {T}: "
            "the kernel reads a chunk of cached positions as whole lanes")
    G = math.gcd(H, MLA_HEAD_GROUP)
    masks = () if chosen is None else (chosen,)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(H // G, pos // T + 1),
        in_specs=[
            pl.BlockSpec((G, T, nope), lambda g, b, at: (g, 0, 0)),
            pl.BlockSpec((G, T, cfg.rope_dim), lambda g, b, at: (g, 0, 0)),
            pl.BlockSpec((1, cfg.latent_width, T), lambda g, b, at: (at[0], 0, b)),
            pl.BlockSpec((G * (nope + v), rk), lambda g, b, at: (g, 0)),
        ] + [pl.BlockSpec((T, T), lambda g, b, at: (0, b)) for _ in masks],
        out_specs=pl.BlockSpec((G, T, v), lambda g, b, at: (g, 0, 0)),
        scratch_shapes=[pltpu.VMEM((G, T, 1), _f32), pltpu.VMEM((G, T, 1), _f32),
                        pltpu.VMEM((G, T, v), _f32)],
    )
    return pl.pallas_call(
        functools.partial(_mla_attend_kernel, scale=mla_scale(cfg), rk=rk, nope=nope),
        out_shape=jax.ShapeDtypeStruct((H, T, v), _bf16),
        grid_spec=grid_spec,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=64 * 1024 * 1024),
        interpret=lowering == "interpret",
        name="mla_prefill_attention",
    )(jnp.stack([slot, pos]).astype(jnp.int32), q_nope, q_rope,
      jnp.swapaxes(latent, 1, 2), w_ukv.T, *masks)


def index_prefill(cfg: DecoderConfig, p: dict, u, c_q, keys, slot, pos, n):
    """One chunk through a layer's indexer: its keys go into ``keys``
    [slots + 1, positions, index_dim] at ``slot`` (a padded position
    writes nothing); its queries score the slot's cached keys up to the
    chunk's own, a chunk-sized block at a time, and each keeps its
    ``index_topk`` best visible rows. Returns (chosen [T, positions] int32,
    1 chosen; the index scores [T, positions] float32, 0 past the chunk's
    last block; keys)."""
    T, P = u.shape[0], keys.shape[1]
    at = pos + jnp.arange(T)
    q, w, k = index_project(cfg, p, u, c_q, at)
    old = jax.lax.dynamic_slice(keys, (slot, pos, 0), (1, T, cfg.index_dim))
    real = (jnp.arange(T) < n)[None, :, None]
    keys = jax.lax.dynamic_update_slice(keys, jnp.where(real, k[None], old), (slot, pos, 0))

    def block(b, scores):
        cached = jax.lax.dynamic_slice(keys, (slot, b * T, 0), (1, T, cfg.index_dim))[0]
        return jax.lax.dynamic_update_slice(scores, _index_block(q, w, cached), (0, b * T))

    scores = jax.lax.fori_loop(0, pos // T + 1, block, jnp.zeros((T, P), _f32))
    visible = jnp.arange(P)[None, :] <= at[:, None]
    return select_rows(scores, visible, cfg.index_topk).astype(jnp.int32), scores, keys


def index_decode(cfg: DecoderConfig, p: dict, u, c_q, keys, slots, pos):
    """One token a sequence through a layer's indexer, ``decode_rows``
    cached keys at a time up to the batch's longest context. Returns
    (chosen [B, positions] bool, the index scores [B, positions], keys)."""
    B, P, R = u.shape[0], keys.shape[1], cfg.decode_rows
    q, w, k = index_project(cfg, p, u, c_q, pos)
    for i in range(B):
        keys = jax.lax.dynamic_update_slice(keys, k[i][None, None], (slots[i], pos[i], 0))

    def block(b, scores):
        cached = jax.vmap(lambda s: jax.lax.dynamic_slice(
            keys, (s, b * R, 0), (1, R, cfg.index_dim))[0])(slots)          # [B, R, D]
        return jax.lax.dynamic_update_slice(scores, _index_block(q, w, cached), (0, b * R))

    scores = jax.lax.fori_loop(0, jnp.max(pos) // R + 1, block, jnp.zeros((B, P), _f32))
    visible = jnp.arange(P)[None, :] <= pos[:, None]
    return select_rows(scores, visible, cfg.index_topk), scores, keys


def mla_prefill(cfg: DecoderConfig, p: dict, u, latent, slot, pos, n, *,
                index=None, chosen=None):
    """Latent attention over one chunk at positions ``pos``.., the
    expanded path: the chunk's cache rows go into ``latent`` [slots + 1,
    positions, kv_rank + rope] at ``slot`` (a padded position writes
    nothing); then ``mla_attend`` expands keys and values from the cached
    rows through ``w_ukv`` and the chunk's queries attend to them
    causally. Returns (out [T, hidden], latent). Under an indexer: a
    layer that owns one (``index``: its key slab) chooses each query's
    rows first (``index_prefill``), a layer that shares one is handed the
    choice (``chosen``); the queries attend the chosen rows, and the
    result ends with what the indexer made: (out, latent, (chosen, index
    scores or None, index keys or None))."""
    T, H = u.shape[0], cfg.heads
    q_nope, q_rope, rows, c_q = _mla_project(cfg, p, u, pos + jnp.arange(T))
    old = jax.lax.dynamic_slice(latent, (slot, pos, 0), (1, T, cfg.latent_width))
    real = (jnp.arange(T) < n)[None, :, None]
    latent = jax.lax.dynamic_update_slice(
        latent, jnp.where(real, rows[None], old), (slot, pos, 0))
    scores = None
    if index is not None:
        chosen, scores, index = index_prefill(cfg, p, u, c_q, index, slot, pos, n)
    mixed = mla_attend(cfg, jnp.transpose(q_nope, (1, 0, 2)), jnp.transpose(q_rope, (1, 0, 2)),
                       latent, p["w_ukv"], slot, pos, chosen)
    out = jnp.einsum("htd,hdo->to", mixed, p["wo"].reshape(H, cfg.v_dim, -1),
                     preferred_element_type=_f32)
    if chosen is None:
        return out, latent
    return out, latent, (chosen, scores, index)


def mla_decode(cfg: DecoderConfig, p: dict, u, latent, slots, pos, *,
               index=None, chosen=None):
    """One token a sequence, the absorbed path: ``w_ukv``'s key half goes
    into the query (``q~ = q_nope W_UK^T``, kv_rank wide) and its value
    half onto the output, so the scores and the weighted sum run over the
    latent rows themselves, ``decode_rows`` of them at a time up to the
    batch's longest context. ``u`` [B, hidden]; ``latent`` the whole
    slab; ``slots``, ``pos`` [B]. Returns (out [B, hidden], latent).
    Under an indexer, as ``mla_prefill``: ``chosen`` [B, positions] bool
    masks each block, and the result ends with (chosen, index scores or
    None, index keys or None)."""
    B, H, rk, R = u.shape[0], cfg.heads, cfg.kv_rank, cfg.decode_rows
    q_nope, q_rope, rows, c_q = _mla_project(cfg, p, u, pos)
    for i in range(B):      # a row at a time: a scatter of B rows copied the whole slab
        latent = jax.lax.dynamic_update_slice(
            latent, rows[i][None, None], (slots[i], pos[i], 0))
    w = p["w_ukv"].reshape(rk, H, cfg.nope_dim + cfg.v_dim)
    q_abs = jnp.einsum("bhd,chd->bhc", q_nope, w[..., :cfg.nope_dim],
                       preferred_element_type=_f32).astype(_bf16)
    scale = mla_scale(cfg)
    scores = None
    if index is not None:
        chosen, scores, index = index_decode(cfg, p, u, c_q, index, slots, pos)

    def block(b, carry):
        rows_b = jax.vmap(lambda s: jax.lax.dynamic_slice(
            latent, (s, b * R, 0), (1, R, cfg.latent_width))[0])(slots)      # [B, R, row]
        s = jnp.einsum("bhc,bpc->bhp", q_abs, rows_b[..., :rk], preferred_element_type=_f32)
        s = s + jnp.einsum("bhr,bpr->bhp", q_rope, rows_b[..., rk:],
                           preferred_element_type=_f32)
        visible = (b * R + jnp.arange(R))[None, :] <= pos[:, None]
        if chosen is not None:
            visible = visible & jax.lax.dynamic_slice_in_dim(chosen, b * R, R, axis=1)
        return _softmax_step(
            carry, s * scale, visible[:, None, :],
            lambda w_: jnp.einsum("bhp,bpc->bhc", w_, rows_b[..., :rk],
                                  preferred_element_type=_f32),
            sparse=chosen is not None)

    start = (jnp.full((B, H), _MASKED, _f32), jnp.zeros((B, H), _f32),
             jnp.zeros((B, H, rk), _f32))
    _, l, acc = jax.lax.fori_loop(0, jnp.max(pos) // R + 1, block, start)
    mixed = (acc / l[..., None]).astype(_bf16)
    out = jnp.einsum("bhc,chd->bhd", mixed, w[..., cfg.nope_dim:],
                     preferred_element_type=_f32)
    out = _mm(out.reshape(B, H * cfg.v_dim), p["wo"])
    if chosen is None:
        return out, latent
    return out, latent, (chosen, scores, index)


def _logits(cfg: DecoderConfig, params: dict, x):
    """Final norm, the head over the held rows (the embedding table
    where it is tied), ``logits_scaling``."""
    x = rms_norm(x, params["final_norm"], cfg.rms_eps)
    table = params["embed"] if cfg.tied_head else params["head"]
    return jnp.dot(x.astype(_bf16), table.T,
                   preferred_element_type=_f32) / cfg.logits_scaling


def _embed(cfg: DecoderConfig, params: dict, ids):
    return cfg.embedding_multiplier * params["embed"][ids].astype(_f32)


# -- the two programs ---------------------------------------------------------------


def _stacked(counted: list) -> dict:
    """Per-layer counts -> one tree with a leading layer axis."""
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *counted)


def prefill_chunk(cfg: DecoderConfig, params: dict, state: list, slot, ids, pos, n):
    """One chunk of one sequence through every layer. ``state``: the
    cache's arrays, a dict a layer, ``[slots + 1, ...]`` each. Returns
    (state, greedy next id, logits [held rows] at the chunk's last real
    position, counts, a row an expert layer: ``sel`` [expert layers, T,
    k], ``counts`` [expert layers, held experts], ``absent`` [expert
    layers]); and, under an indexer, what the layers that own one chose:
    ``{"chosen": [index layers, T, positions / 8] uint8, each query's
    chosen rows as bits (``pack_rows``: 2.6 MB a chunk at the published
    sizes; the index scores, 84 MB, are not returned), "kept": [index
    layers] int32, the rows the real queries kept, counted on the mask
    the attention is handed}``."""
    x = _embed(cfg, params, ids)
    live = jnp.arange(ids.shape[0]) < n
    r = cfg.residual_multiplier
    new_state, counted, indexed, chosen = [], [], [], None
    for (kind, ffn), index, p, s in zip(cfg.layers, cfg.index_types, params["layers"], state):
        u = rms_norm(x, p["norm1"], cfg.rms_eps)
        if index is not None:   # a choice travels from the layer that made it to those that share it
            out, latent, (chosen, scores, keys) = mla_prefill(
                cfg, p, u, s["latent"], slot, pos, n, index=s.get("index"), chosen=chosen)
            if index == FULL:
                indexed.append({"chosen": pack_rows(chosen),
                                "kept": jnp.sum(jnp.where(live[:, None], chosen, 0))})
        elif kind == MLA:     # reads and writes its slot's rows inside the slab
            out, latent = mla_prefill(cfg, p, u, s["latent"], slot, pos, n)
        else:
            mine = {nm: jax.lax.dynamic_index_in_dim(a, slot, 0, keepdims=False)
                    for nm, a in s.items()}
            if kind == MAMBA:
                out, tail, ssm = mamba_prefill(cfg, p, u, mine["tail"], mine["ssm"], n)
                mine = {"tail": tail, "ssm": ssm}
            else:
                out, keys, values = attention_prefill(
                    cfg, p, u, mine["keys"], mine["values"], pos, n)
                mine = {"keys": keys, "values": values}
        if ffn == MOE:
            x, counts = moe_block(cfg, p, x + r * out, live)
            counted.append(counts)
        else:
            x = dense_block(cfg, p, x + r * out)
        if index == FULL:
            new_state.append({"latent": latent, "index": keys})
        else:
            new_state.append({"latent": latent} if kind == MLA else {
                nm: jax.lax.dynamic_update_index_in_dim(s[nm], new, slot, 0)
                for nm, new in mine.items()})
    last = jax.lax.dynamic_index_in_dim(x, n - 1, 0, keepdims=False)
    logits = _logits(cfg, params, last)
    out = (new_state, jnp.argmax(logits).astype(jnp.int32), logits, _stacked(counted))
    return out + (_stacked(indexed),) if indexed else out


def decode_step(cfg: DecoderConfig, params: dict, state: list, slots, ids, pos, live):
    """One token for each row: ``slots``, ``ids``, ``pos``, ``live`` [B]
    (a padded row is not live: it reads and writes the scratch slot and
    routes nowhere). Returns (state, next ids [B] (greedy), logits
    [B, held rows], counts as ``prefill_chunk`` with T = B and, under an
    indexer, ``{"scores": [index layers, B, positions] float32, "chosen":
    the same shape, bool, "kept": [index layers] int32 over the live
    rows}``)."""
    x = _embed(cfg, params, ids)
    r = cfg.residual_multiplier
    new_state, counted, indexed, chosen = [], [], [], None
    for (kind, ffn), index, p, s in zip(cfg.layers, cfg.index_types, params["layers"], state):
        u = rms_norm(x, p["norm1"], cfg.rms_eps)
        if index is not None:
            out, latent, (chosen, scores, keys) = mla_decode(
                cfg, p, u, s["latent"], slots, pos, index=s.get("index"), chosen=chosen)
            if index == FULL:
                indexed.append({"scores": scores, "chosen": chosen, "kept": jnp.sum(
                    chosen & live[:, None], dtype=jnp.int32)})
        elif kind == MLA:
            out, latent = mla_decode(cfg, p, u, s["latent"], slots, pos)
        elif kind == MAMBA:
            out, tail, ssm = mamba_decode(cfg, p, u, s["tail"][slots], s["ssm"][slots])
            mine = {"tail": tail, "ssm": ssm}
        else:
            out, keys, values = attention_decode(
                cfg, p, u, s["keys"][slots], s["values"][slots], pos)
            mine = {"keys": keys, "values": values}
        if ffn == MOE:
            x, counts = moe_block(cfg, p, x + r * out, live)
            counted.append(counts)
        else:
            x = dense_block(cfg, p, x + r * out)
        if index == FULL:
            new_state.append({"latent": latent, "index": keys})
        else:
            new_state.append({"latent": latent} if kind == MLA else {
                nm: s[nm].at[slots].set(new) for nm, new in mine.items()})
    logits = _logits(cfg, params, x)
    out = (new_state, jnp.argmax(logits, axis=-1).astype(jnp.int32), logits, _stacked(counted))
    return out + (_stacked(indexed),) if indexed else out


def empty_state(cfg: DecoderConfig) -> list:
    """Zeroed cache arrays: a dict a layer, each leaf ``[slots + 1, ...]``
    (the last slot is the scratch slot)."""
    S = cfg.slots + 1
    out = []
    for kind, index in zip(cfg.layer_types, cfg.index_types):
        if kind == MAMBA:
            out.append({
                "tail": jnp.zeros((S, cfg.mamba_conv - 1, cfg.conv_width), _bf16),
                "ssm": jnp.zeros(
                    (S, cfg.mamba_heads, cfg.mamba_head_dim, cfg.mamba_state), _f32),
            })
        elif kind == ATTENTION:
            shape = (S, cfg.max_positions, cfg.kv_heads, cfg.head_dim)
            out.append({"keys": jnp.zeros(shape, _bf16), "values": jnp.zeros(shape, _bf16)})
        else:
            out.append({"latent": jnp.zeros((S, cfg.max_positions, cfg.latent_width), _bf16)})
            if index == FULL:   # the indexer's keys, a row a position beside the latent row
                out[-1]["index"] = jnp.zeros((S, cfg.max_positions, cfg.index_dim), _bf16)
    return out


def _zero_slot(state: list, slot):
    return jax.tree_util.tree_map(
        lambda a: jax.lax.dynamic_update_index_in_dim(
            a, jnp.zeros(a.shape[1:], a.dtype), slot, 0),
        state,
    )


# -- cost models (device sites) -------------------------------------------------------


def _layer_matrix_params(cfg: DecoderConfig, kind: str, experts: float,
                         ffn: str = MOE, index: str | None = None) -> float:
    """Matrix parameters one token multiplies through in one layer, with
    ``experts`` routed experts a token where the layer has them."""
    shapes = layer_shapes(cfg, kind, ffn, index)
    dense = sum(
        float(np.prod(s)) for name, s in shapes.items()
        if len(s) == 2 and name != "conv_w"
    )
    if ffn != MOE:
        return dense
    per_expert = float(np.prod(shapes["experts_in"][1:]) + np.prod(shapes["experts_out"][1:]))
    return dense + experts * per_expert


@functools.lru_cache(maxsize=None)
def flops_per_token(cfg: DecoderConfig) -> float:
    """Forward FLOPs of one token through the held share: two a matrix
    parameter, the routed experts at this chip's expected share of the
    ``experts_per_token`` selections; the head, and attention and the
    indexer's scores over the context, are not included."""
    share = cfg.experts_per_token * cfg.experts_held[1] / cfg.experts
    return 2.0 * sum(_layer_matrix_params(cfg, k, share, f, i)
                     for (k, f), i in zip(cfg.layers, cfg.index_types))


def prefill_cost_model(cfg: DecoderConfig) -> tuple[float, float]:
    """(flops, bytes) of one prefill chunk: the chunk's tokens through
    every matrix, one read of the held parameters."""
    return cfg.prefill_chunk * flops_per_token(cfg), param_bytes(cfg)


def decode_cost_model(cfg: DecoderConfig, batch: int) -> tuple[float, float]:
    """(flops, bytes) of one decode step: bandwidth-bound, one read of
    the held parameters at the most."""
    head = 2.0 * cfg.vocab_held[1] * cfg.hidden
    return batch * (flops_per_token(cfg) + head), param_bytes(cfg)


device_site(
    "answer.prefill",
    cost_model=prefill_cost_model,
    dtypes=("int32", "bfloat16", "float32"),
    where="pathway_tpu/models/decoder.py:AnswerModel._prefill_prompt",
    donates=("state",),
    description="one fixed chunk of one prompt through the decoder, "
                "state carried through the cache slot",
)

device_site(
    "answer.decode",
    cost_model=decode_cost_model,
    dtypes=("int32", "bfloat16", "float32"),
    where="pathway_tpu/models/decoder.py:AnswerModel._generate",
    donates=("state",),
    description="one token for each live slot (batch buckets 1, 2, 4, 8)",
)


# -- the state cache -----------------------------------------------------------------


class StateCache:
    """Per-sequence state of every kind, by slot: convolution tail
    and SSM state for each Mamba layer (constant in length), keys/values
    for each attention layer, latent rows for each MLA layer and the index
    keys of each layer that owns an indexer (growing, up to
    ``max_positions``). ``acquire``
    zeroes a slot and hands it out; with every slot out it waits.
    ``release`` gives it back. ``state`` is the device arrays (the jitted
    programs donate and return them); ``scratch`` is the extra slot the
    padded rows of a decode batch use."""

    def __init__(self, cfg: DecoderConfig):
        self.cfg = cfg
        self.state = empty_state(cfg)
        self.scratch = cfg.slots
        self._free = list(range(cfg.slots))
        self._cond = threading.Condition()
        self._zero = jax.jit(_zero_slot, donate_argnums=0)

    @property
    def in_use(self) -> int:
        return self.cfg.slots - len(self._free)

    def acquire(self) -> int:
        with _flight.span("cache.acquire") as sp:
            with self._cond:
                while not self._free:
                    self._cond.wait()
                slot = self._free.pop(0)
                self.state = self._zero(self.state, np.int32(slot))
                sp.args["slot"] = slot
                sp.args["in_use"] = self.in_use
        return slot

    def release(self, slot: int) -> None:
        with _flight.span("cache.release", slot=slot) as sp:
            with self._cond:
                if slot in self._free or not 0 <= slot < self.cfg.slots:
                    raise ValueError(f"slot {slot} is not out")
                self._free.append(slot)
                sp.args["in_use"] = self.in_use
                self._cond.notify()


# -- the host-facing model -------------------------------------------------------------


@dataclasses.dataclass
class Generation:
    """What one prompt produced. ``logits`` [new tokens, held rows]
    float32 and the expert selections (``prompt_routes`` [layers, prompt
    tokens, k], ``decode_routes`` [layers, new tokens - 1, k], a row an
    expert layer), ``ssm`` (the slot's final SSM state, a device array a
    Mamba layer) and ``latent`` (the slot's cache rows, a device array
    [max_positions, kv_rank + rope] an MLA layer, then [max_positions,
    index_dim] a layer that owns an indexer; the sequence's are the
    first prompt + new tokens - 1) only for the rows ``generate`` was
    asked to keep. Under an indexer such a row also keeps ``indexed``:
    what its dispatches returned of the indexers' work, on the device
    as ``ssm`` and ``latent`` are (the reply does not wait for it);
    ``choices()`` fetches it."""

    prompt: np.ndarray
    tokens: np.ndarray
    logits: np.ndarray | None = None
    prompt_routes: np.ndarray | None = None
    decode_routes: np.ndarray | None = None
    ssm: list | None = None
    latent: list | None = None
    indexed: dict | None = None

    def choices(self) -> tuple[np.ndarray, np.ndarray | None]:
        """(every query's chosen rows as bits [index layers, prompt + new
        tokens - 1, max_positions / 8] uint8, ``unpack_rows`` undoes them;
        the index scores of the decode steps' queries [index layers, new
        tokens - 1, max_positions] float32: a prefill chunk's would be 84
        MB at the published sizes and are not kept)."""
        got = jax.device_get(self.indexed)
        row = got["row"]
        chosen = [bits[:, :n] for bits, n in zip(got["chunks"], got["real"])]
        chosen += [pack_rows(step["chosen"][:, row:row + 1]) for step in got["steps"]]
        scores = [step["scores"][:, row:row + 1] for step in got["steps"]]
        return np.concatenate(chosen, axis=1), np.concatenate(scores, axis=1) if scores else None


def _pairs(before: int, n: int, keep: int = 0) -> int:
    """(query, cached position) pairs of ``n`` queries after ``before``
    positions, each over its own context or at most ``keep`` rows of it."""
    contexts = before + 1 + np.arange(n, dtype=np.int64)
    return int((np.minimum(contexts, keep) if keep else contexts).sum())


class Counters:
    """What the model counted since it was made; plain numbers a reader
    may copy at any time."""

    def __init__(self, cfg: DecoderConfig):
        # a row an expert layer
        self.expert_tokens = np.zeros((cfg.expert_layers, cfg.experts_held[1]), np.int64)
        self.held_selections = 0
        self.absent_selections = 0
        self.prefill_real = 0
        self.prefill_padded = 0
        self.prompts = 0
        self.decode_steps: dict[int, int] = {}      # live rows -> steps
        self.decode_experts_touched = 0             # sum over steps and layers
        # (query, cached position) pairs one attention layer went over
        self.attended_positions_prefill = 0
        self.attended_positions_decode = 0
        self.latent_rows = 0                        # cache rows written, MLA layers summed
        # under an indexer: pairs one layer that owns it scored (the
        # host's count of the visible ones), pairs one layer attended after
        # the choice (what attended_positions counts too: the rows of the
        # mask, COUNTED ON THE DEVICE and fetched with a call's routing
        # counts, the mean over the layers), index key rows written (the
        # owning layers summed)
        self.indexed_positions_prefill = 0
        self.indexed_positions_decode = 0
        self.selected_positions_prefill = 0
        self.selected_positions_decode = 0
        self.index_rows = 0

    def add_routing(self, counts: np.ndarray, absent: np.ndarray) -> None:
        self.expert_tokens += counts
        self.held_selections += int(counts.sum())
        self.absent_selections += int(absent.sum())


class AnswerModel:
    """Prompt ids in, greedy continuations out: ``generate`` prefills a
    call's prompts one after another in fixed chunks, then decodes them
    together in lock-step, one dispatch a token, and waits once."""

    device_sites = ("answer.prefill", "answer.decode")

    def __init__(self, cfg: DecoderConfig, params: dict | None = None, *, seed: int = 0):
        self.cfg = cfg
        self.params = params if params is not None else init_params(cfg, seed)
        self.cache = StateCache(cfg)
        self.counters = Counters(cfg)
        self._lock = threading.Lock()

        def answer_prefill(params, state, slot, ids, pos, n):
            return prefill_chunk(cfg, params, state, slot, ids, pos, n)

        def answer_decode(params, state, slots, ids, pos, live):
            return decode_step(cfg, params, state, slots, ids, pos, live)

        def slot_latent(state, slot):     # the latent rows, then the index keys
            return [jax.lax.dynamic_index_in_dim(s[name], slot, 0, keepdims=False)
                    for name in ("latent", "index") for s in state if name in s]

        self._prefill = jax.jit(answer_prefill, donate_argnums=1)
        self._decode = jax.jit(answer_decode, donate_argnums=1)
        self._slot_latent = jax.jit(slot_latent)
        self._mla_layers = sum(kind == MLA for kind in cfg.layer_types)
        self._seen: set = set()

    def _dispatch(self, site: str, fn, bucket, *args, first_says=None, **span_args):
        """One supervised device call that takes and returns the cache's
        arrays; its ring span (with ``first_says`` too on the site's first
        dispatch at this bucket), and the armed plane's record."""
        first = (site, bucket) not in self._seen
        if first:
            self._seen.add((site, bucket))
            _DEVICE.note_recompile(site)
            span_args.update(first_says or {})
        dev = _DEVICE.begin(site, first=first, **span_args)
        try:
            state, *out = _devsup.supervised_dispatch(
                site, lambda: fn(self.params, self.cache.state, *args))
        except BaseException:
            _DEVICE.end(dev, None, block=False)
            raise
        self.cache.state = state
        flops, nbytes = (
            prefill_cost_model(self.cfg) if site == "answer.prefill"
            else decode_cost_model(self.cfg, bucket)
        )
        _DEVICE.end(dev, out[0], flops=flops, bytes_accessed=nbytes,
                    transfer_bytes=nbytes_of(*args))
        return out

    def _prefill_prompt(self, slot: int, ids: np.ndarray, kept: bool = False):
        """Every chunk of one prompt; (first generated id, logits at the
        prompt's last position, [(real positions, counts, rows the
        indexers' queries kept or None) a chunk], and of a ``kept`` row
        under an indexer every chunk's choice of rows as bits, still on
        the device)."""
        T, keep = self.cfg.prefill_chunk, self.cfg.index_topk
        out, counted, packed = None, [], []
        for at in range(0, len(ids), T):
            n = min(T, len(ids) - at)
            chunk = np.zeros(T, np.int32)
            chunk[:n] = ids[at:at + n]
            # position at + i sees at + i + 1 cached positions, and attends
            # them all or, under an indexer, the index_topk it is to keep of
            # them: what the host can say when it queues the chunk (the
            # counters hold what the device kept)
            seen, attended = _pairs(at, n), _pairs(at, n, keep)
            # latent attention: the cached-row blocks the chunk goes over, a
            # layer, and on the site's first dispatch what lowered its kernel
            latent_attention = {
                "blocks": at // T + 1, "first_says": {"kernel": mla_lowering()},
            } if self._mla_layers else {}
            if keep:    # pairs a layer that owns an indexer scores, pairs a layer is to attend
                latent_attention.update(scored=seen, selected=attended)
                latent_attention["first_says"].update(
                    selection="bisection", attention="masked-dense")
            out = self._dispatch(
                "answer.prefill", self._prefill, T,
                np.int32(slot), chunk, np.int32(at), np.int32(n),
                chunk=at // T, real=n, padded=T - n, context=at + n, **latent_attention,
            )
            counted.append((n, out[2], out[3]["kept"] if keep else None))
            self.counters.prefill_real += n
            self.counters.prefill_padded += T - n
            if keep:
                self.counters.indexed_positions_prefill += seen
                if kept:    # a reference held: the other rows' bits are freed as they come
                    packed.append(out[3]["chosen"])
            else:
                self.counters.attended_positions_prefill += attended
        return out[0], out[1], counted, packed

    def generate(self, prompts: Sequence[Sequence[int]], max_new_tokens: int,
                 keep: Sequence[int] = ()) -> list[Generation]:
        """Greedy continuations of ``prompts`` (token ids of the held
        vocabulary rows), ``max_new_tokens`` each. At most ``slots``
        prompts a call. The rows named in ``keep`` also return their
        logits, expert selections, final state and, under an indexer,
        every query's choice of rows (``Generation.choices``): the same
        two programs run for them as for every other row."""
        cfg = self.cfg
        if not 1 <= len(prompts) <= cfg.slots:
            raise ValueError(f"a call takes 1..{cfg.slots} prompts, got {len(prompts)}")
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be at least 1")
        room = cfg.max_positions - max_new_tokens
        prompts = [np.asarray(p, np.int32)[:room] for p in prompts]
        if any(len(p) == 0 for p in prompts):
            raise ValueError("an empty prompt")
        rows_held = cfg.vocab_held[1]
        if any(int(p.max()) >= rows_held or int(p.min()) < 0 for p in prompts):
            raise ValueError(f"a prompt id outside the held vocabulary rows 0..{rows_held}")
        with self._lock, _flight.span(
            "answer.generate", rows=len(prompts),
            prompt_tokens=int(sum(len(p) for p in prompts)),
            new_tokens=max_new_tokens * len(prompts),
        ):
            slots = [self.cache.acquire() for _ in prompts]
            try:
                return self._generate(prompts, slots, max_new_tokens, sorted(set(keep)))
            finally:
                for slot in slots:
                    self.cache.release(slot)

    def _generate(self, prompts, slots, max_new_tokens, keep):
        rows = len(prompts)
        topk = self.cfg.index_topk
        prefilled, indexed = [], {}
        for r, (slot, ids) in enumerate(zip(slots, prompts)):
            *made, packed = self._prefill_prompt(slot, ids, r in keep)
            prefilled.append(made)
            if packed:
                indexed[r] = {"row": r, "real": [n for n, *_ in made[2]],
                              "chunks": packed, "steps": []}
        self.counters.prompts += rows
        # lock-step decode: the ids stay on the device from step to step
        bucket = next(b for b in DECODE_BUCKETS if b >= rows)
        pad = bucket - rows
        slot_ids = np.asarray(slots + [self.cache.scratch] * pad, np.int32)
        live = np.asarray([True] * rows + [False] * pad)
        lengths = np.asarray([len(p) for p in prompts] + [0] * pad, np.int32)
        ids = jnp.stack([p[0] for p in prefilled] + [jnp.int32(0)] * pad)

        def counts_of(counts, kept):    # a dispatch's routing counts and, under an indexer, rows kept
            return {"counts": counts["counts"], "absent": counts["absent"],
                    **({"kept": kept} if topk else {})}

        fetch = {
            "tokens": [ids],
            # every dispatch's counts: the prefill chunks', then the steps'
            "counts": [counts_of(c, kept) for p in prefilled for _, c, kept in p[2]],
            "kept": {r: {"logits": [prefilled[r][1]],
                         "prompt_routes": [c["sel"] for _, c, _ in prefilled[r][2]],
                         "decode_routes": []} for r in keep},
        }
        chunks = len(fetch["counts"])
        prompt_tokens = int(lengths[:rows].sum())
        seen = 0
        with _flight.span("answer.decode", batch=rows, bucket=bucket,
                          steps=max_new_tokens - 1):
            for step in range(max_new_tokens - 1):
                # step t's token of a sequence of n prompt tokens sees n + t + 1
                # positions, and attends them all or the index_topk it is to keep
                positions = prompt_tokens + rows * (step + 1)
                seen += positions
                ids, logits, counts, *index = self._dispatch(
                    "answer.decode", self._decode, bucket,
                    slot_ids, ids, lengths + step, live,
                    span="answer.decode.step", batch=rows, positions=positions,
                    **({"selected": int(np.minimum(lengths[:rows] + step + 1, topk).sum())}
                       if topk else {}),
                )
                fetch["tokens"].append(ids)
                fetch["counts"].append(counts_of(counts, index[0]["kept"] if topk else None))
                for r in keep:
                    fetch["kept"][r]["logits"].append(logits[r])
                    fetch["kept"][r]["decode_routes"].append(counts["sel"][:, r])
                for r in indexed:
                    indexed[r]["steps"].append(
                        {"scores": index[0]["scores"], "chosen": index[0]["chosen"]})
        steps = max_new_tokens - 1
        self.counters.decode_steps[rows] = self.counters.decode_steps.get(rows, 0) + steps
        self.counters.latent_rows += self._mla_layers * (prompt_tokens + rows * steps)
        if topk:
            self.counters.indexed_positions_decode += seen
            self.counters.index_rows += self.cfg.index_layers * (prompt_tokens + rows * steps)
        else:
            self.counters.attended_positions_decode += seen
        final_ssm = {
            r: [s["ssm"][slots[r]] for s in self.cache.state if "ssm" in s]
            for r in keep
        }
        final_latent = {
            r: self._slot_latent(self.cache.state, np.int32(slots[r])) for r in keep
        } if self._mla_layers else {}
        with _flight.span("answer.wait"):
            # every copy queues behind the last step before anything waits
            leaves = jax.tree_util.tree_leaves(fetch)
            for leaf in leaves:
                leaf.copy_to_host_async()
            jax.block_until_ready(leaves)
        with _flight.span("answer.d2h") as sp:
            got = jax.device_get(fetch)
            sp.args["bytes"] = int(sum(x.nbytes for x in jax.tree_util.tree_leaves(got)))
        for c in got["counts"]:
            self.counters.add_routing(c["counts"], c["absent"])
        for c in got["counts"][chunks:]:
            self.counters.decode_experts_touched += int((c["counts"] > 0).sum())
        if topk:    # the rows the device kept, a layer: each owner's over the layers that attend it
            spans = np.asarray(self.cfg.index_spans, np.int64)
            in_prefill, in_decode = (
                int(sum(c["kept"] @ spans for c in part)) // int(spans.sum())
                for part in (got["counts"][:chunks], got["counts"][chunks:]))
            self.counters.selected_positions_prefill += in_prefill
            self.counters.attended_positions_prefill += in_prefill
            self.counters.selected_positions_decode += in_decode
            self.counters.attended_positions_decode += in_decode
        tokens = np.stack(got["tokens"], axis=1)                    # [bucket, new]
        out = [Generation(prompt=prompts[r], tokens=tokens[r]) for r in range(rows)]
        for r, kept in got["kept"].items():
            gen = out[r]
            gen.ssm = final_ssm[r]
            gen.latent = final_latent.get(r)
            gen.indexed = indexed.get(r)
            gen.logits = np.stack(kept["logits"])
            gen.prompt_routes = np.concatenate(
                [sel[:, :n] for sel, (n, *_) in zip(kept["prompt_routes"], prefilled[r][2])],
                axis=1)
            gen.decode_routes = np.stack(kept["decode_routes"], axis=1) \
                if kept["decode_routes"] else gen.prompt_routes[:, :0]
        return out
