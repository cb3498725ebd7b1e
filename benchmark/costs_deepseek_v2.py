"""Operations and bytes of the answer model ``deepseek_v2``, from shapes
and from what the program counted (held selections, real positions,
attended positions, expert touches). Written here from the
configuration's sizes (``reference_deepseek_v2.arch_of``), not taken from
the program, so the count does not move when the program does.

Attention is counted as the reference computes it: a (query, cached
position) pair of one layer costs the scores and the weighted values over
expanded keys and values, heads x (nope + rope + v) x 2 FLOPs; what a
path adds to that (prefill expanding earlier rows again for every chunk,
decode's products over the wider latent rows) is not counted. Left out,
each under 1%: norms, softmax, the rotation, gates."""

from reference_deepseek_v2 import DENSE, MOE


def mla_params(a: dict) -> float:
    """Matrix parameters of one layer's latent attention."""
    h, H = a["hidden"], a["heads"]
    return (
        h * a["q_rank"] + a["q_rank"] * H * (a["nope_dim"] + a["rope_dim"])
        + h * (a["kv_rank"] + a["rope_dim"]) + a["kv_rank"] * H * (a["nope_dim"] + a["v_dim"])
        + H * a["v_dim"] * h
    )


def expert_params(a: dict) -> float:
    """Matrix parameters of ONE routed expert."""
    return 3.0 * a["hidden"] * a["expert_width"]


def dense_matrix_params(a: dict, ffn: str) -> float:
    """Matrix parameters every position of a layer multiplies through: the
    attention's projections, and the dense MLP or the router and the
    shared experts."""
    h = a["hidden"]
    if ffn == DENSE:
        return mla_params(a) + 3.0 * h * a["dense_width"]
    return mla_params(a) + h * a["experts"] + 3.0 * h * a["shared_width"]


def expert_layers(a: dict) -> int:
    return sum(ffn == MOE for ffn in a["ffn_types"])


def held_matrix_params(a: dict) -> float:
    """Every matrix parameter this chip holds: both tables, the layers'
    matrices with the held experts."""
    total = 2.0 * a["vocab_rows"] * a["hidden"]
    for ffn in a["ffn_types"]:
        total += dense_matrix_params(a, ffn)
        if ffn == MOE:
            total += a["experts_held"][1] * expert_params(a)
    return total


def vector_params(a: dict) -> float:
    """Norm scales (float32): two a layer, the two low-rank norms, the final one."""
    return len(a["ffn_types"]) * (2.0 * a["hidden"] + a["q_rank"] + a["kv_rank"]) + a["hidden"]


def held_param_bytes(a: dict) -> float:
    """bfloat16 matrices, float32 vectors."""
    return 2.0 * held_matrix_params(a) + 4.0 * vector_params(a)


def expected_held_selections(a: dict) -> float:
    """Of a token's selections, how many fall on this chip's experts under
    uniform routing."""
    return a["experts_per_token"] * a["experts_held"][1] / a["experts"]


def dense_flops_per_token(a: dict) -> float:
    return 2.0 * sum(dense_matrix_params(a, ffn) for ffn in a["ffn_types"])


def attention_flops_per_pair(a: dict) -> float:
    """One query over one cached position, every layer: scores over nope +
    rope dims and the weighted values, every head."""
    return len(a["ffn_types"]) * 2.0 * a["heads"] * (a["nope_dim"] + a["rope_dim"] + a["v_dim"])


def head_flops(a: dict) -> float:
    """One position's logits over the held rows."""
    return 2.0 * a["vocab_rows"] * a["hidden"]


def token_flops(a: dict, held_selections: float, context: float) -> float:
    """One real token through the held layers: the dense matrices, its
    ``held_selections`` routed experts an expert layer, and attention over
    ``context`` cached positions. The head is counted apart."""
    return (
        dense_flops_per_token(a)
        + 2.0 * expert_layers(a) * held_selections * expert_params(a)
        + context * attention_flops_per_pair(a)
    )


def prefill_chunk_flops(a: dict, chunk: int, real: float, held_selections: float,
                        attended: float) -> float:
    """One dispatched chunk: every one of its ``chunk`` positions goes
    through the dense matrices (padding is computed), the ``real`` ones
    through their held experts and over the ``attended`` (query, cached
    position) pairs of their real contexts, and one position through the
    head."""
    return (
        chunk * dense_flops_per_token(a)
        + real * 2.0 * expert_layers(a) * held_selections * expert_params(a)
        + attended * attention_flops_per_pair(a)
        + head_flops(a)
    )


def latent_bytes_per_position(a: dict) -> float:
    """One position's cache rows, every layer (bfloat16)."""
    return len(a["ffn_types"]) * 2.0 * (a["kv_rank"] + a["rope_dim"])


def decode_step_bytes(a: dict, experts_touched: float, positions: float) -> float:
    """What one decode step must move: every dense matrix (the absorbed
    ``w_ukv`` among them) and vector and the head's rows once, the
    ``experts_touched`` (summed over layers) routed experts once each, and
    the latent rows of the batch's live contexts (``positions``: their
    sum)."""
    dense = 2.0 * sum(dense_matrix_params(a, ffn) for ffn in a["ffn_types"])
    head = 2.0 * a["vocab_rows"] * a["hidden"]
    return (
        dense + 4.0 * vector_params(a) + head + experts_touched * 2.0 * expert_params(a)
        + positions * latent_bytes_per_position(a)
    )
