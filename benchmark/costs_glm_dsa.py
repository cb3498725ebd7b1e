"""Operations and bytes of the answer model ``glm_moe_dsa``, from shapes
and from what the program counted (held selections, real positions,
scored and selected positions, expert touches). Written here from the
configuration's sizes (``reference_glm_dsa.arch_of``), not taken from the
program, so the count does not move when the program does.

The WORK is counted, whatever implements it. Attention: a (query,
selected cached position) pair of one layer costs the scores and the
weighted values over expanded keys and values, heads x (nope + rope + v) x
2 FLOPs in prefill (65,536 at the published widths), and in decode's
absorbed form heads x ((kv_rank + rope) + kv_rank) x 2; a program that
computes the visible pairs it then masks does more and is credited with
the selected ones. The indexer: a (query, visible cached position) pair of
a layer that owns one costs index_heads x index_dim x 2 FLOPs (8,192). A
cached latent row is (kv_rank + rope) x 2 B (1,152), an index key
index_dim x 2 B (256). Left out, each under 1%: norms, softmax, the
rotation, gates, the selection's comparisons (no FLOP of the MXU's)."""

# the latent attention's and an expert's parameters, the held share of a
# token's selections and the head's FLOPs are DeepSeek-V2's counts at these sizes
from costs_deepseek_v2 import (  # noqa: F401
    expected_held_selections, expert_params, head_flops, mla_params)
from reference_glm_dsa import DENSE, FULL, MOE


def indexer_params(a: dict) -> float:
    """Matrix parameters of one indexer: W_IQ, W_IK, W_Iw."""
    return (a["q_rank"] * a["index_heads"] * a["index_dim"] + a["hidden"] * a["index_dim"]
            + a["hidden"] * a["index_heads"])


def dense_matrix_params(a: dict, ffn: str, index: str) -> float:
    """Matrix parameters every position of a layer multiplies through: the
    attention's projections, the indexer's where the layer owns one, and
    the dense MLP or the router and the shared expert."""
    h = a["hidden"]
    own = mla_params(a) + (indexer_params(a) if index == FULL else 0.0)
    if ffn == DENSE:
        return own + 3.0 * h * a["dense_width"]
    return own + h * a["experts"] + 3.0 * h * a["shared_width"]


def _layers(a: dict):
    return list(zip(a["ffn_types"], a["index_types"]))


def expert_layers(a: dict) -> int:
    return sum(ffn == MOE for ffn in a["ffn_types"])


def index_layers(a: dict) -> int:
    return sum(index == FULL for index in a["index_types"])


def held_matrix_params(a: dict) -> float:
    """Every matrix parameter this chip holds: both tables, the layers'
    matrices with the held experts."""
    total = 2.0 * a["vocab_rows"] * a["hidden"]
    for ffn, index in _layers(a):
        total += dense_matrix_params(a, ffn, index)
        if ffn == MOE:
            total += a["experts_held"][1] * expert_params(a)
    return total


def vector_params(a: dict) -> float:
    """Float32 vectors: two norm scales a layer and the two low-rank
    norms', the router's bias an expert layer, the index keys' norm scale
    and bias a layer that owns an indexer, the final norm."""
    return (len(a["ffn_types"]) * (2.0 * a["hidden"] + a["q_rank"] + a["kv_rank"])
            + expert_layers(a) * a["experts"] + index_layers(a) * 2.0 * a["index_dim"]
            + a["hidden"])


def held_param_bytes(a: dict) -> float:
    """bfloat16 matrices, float32 vectors."""
    return 2.0 * held_matrix_params(a) + 4.0 * vector_params(a)


def dense_flops_per_token(a: dict) -> float:
    return 2.0 * sum(dense_matrix_params(a, ffn, index) for ffn, index in _layers(a))


def attention_flops_per_pair(a: dict) -> float:
    """One query over one SELECTED cached position, every layer, as prefill
    computes it: scores over nope + rope dims and the weighted values,
    every head."""
    return len(a["ffn_types"]) * 2.0 * a["heads"] * (a["nope_dim"] + a["rope_dim"] + a["v_dim"])


def absorbed_flops_per_pair(a: dict) -> float:
    """The same pair in decode's absorbed form: scores over the latent row,
    the weighted latent values."""
    return len(a["ffn_types"]) * 2.0 * a["heads"] * (2 * a["kv_rank"] + a["rope_dim"])


def index_flops_per_pair(a: dict) -> float:
    """One query's index score of one VISIBLE cached position, every layer
    that owns an indexer."""
    return index_layers(a) * 2.0 * a["index_heads"] * a["index_dim"]


def token_flops(a: dict, held_selections: float) -> float:
    """One real token through the held layers' matrices: the dense ones and
    its ``held_selections`` routed experts an expert layer. Attention, the
    indexer's scores and the head are counted apart."""
    return dense_flops_per_token(a) + 2.0 * expert_layers(a) * held_selections * expert_params(a)


def prefill_chunk_flops(a: dict, chunk: int, real: float, held_selections: float,
                        scored: float, selected: float) -> float:
    """One dispatched chunk: every one of its ``chunk`` positions goes
    through the dense matrices (padding is computed), the ``real`` ones
    through their held experts; the indexers score the ``scored`` (query,
    visible position) pairs, attention goes over the ``selected`` pairs;
    one position through the head."""
    return (
        chunk * dense_flops_per_token(a)
        + real * 2.0 * expert_layers(a) * held_selections * expert_params(a)
        + scored * index_flops_per_pair(a) + selected * attention_flops_per_pair(a)
        + head_flops(a)
    )


def latent_bytes_per_position(a: dict) -> float:
    """One position's latent cache rows, every layer (bfloat16)."""
    return len(a["ffn_types"]) * 2.0 * (a["kv_rank"] + a["rope_dim"])


def index_bytes_per_position(a: dict) -> float:
    """One position's index keys, every layer that owns an indexer (bfloat16)."""
    return index_layers(a) * 2.0 * a["index_dim"]


def decode_step_bytes(a: dict, experts_touched: float, positions: float,
                      selected: float) -> float:
    """What one decode step must move: every dense matrix (the absorbed
    ``w_ukv`` and the indexers' among them) and vector and the head's rows
    once, the ``experts_touched`` (summed over layers) routed experts once
    each, the index keys of the batch's live contexts (``positions``:
    their sum) and the latent rows of the ``selected`` positions."""
    dense = 2.0 * sum(dense_matrix_params(a, ffn, index) for ffn, index in _layers(a))
    head = 2.0 * a["vocab_rows"] * a["hidden"]
    return (
        dense + 4.0 * vector_params(a) + head + experts_touched * 2.0 * expert_params(a)
        + positions * index_bytes_per_position(a) + selected * latent_bytes_per_position(a)
    )
