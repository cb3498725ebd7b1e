"""Operations and bytes of the answer model, from shapes and from what
the program counted (expert touches, real positions). Written here from
the configuration's sizes (``reference_decoder.arch_of``), not taken from
the program, so the count does not move when the program does.

Left out, each under 2% at these shapes: attention's scores and weighted
values (4 x context x 4096 FLOPs a token in the one attention layer), the
recurrence (about 4.2 MFLOP a token a Mamba layer), norms, gates, the
convolution."""

from reference_decoder import F32_LEAVES, MAMBA, layer_shapes


def _prod(shape) -> float:
    out = 1.0
    for n in shape:
        out *= n
    return out


def expert_params(a: dict) -> float:
    """Matrix parameters of ONE routed expert."""
    return a["hidden"] * 2.0 * a["expert_width"] + a["expert_width"] * a["hidden"]


def dense_matrix_params(a: dict, kind: str) -> float:
    """Matrix parameters every token of a layer multiplies through: the
    mixer's projections, the router, the shared MLP."""
    return sum(
        _prod(s) for name, s in layer_shapes(a, kind).items()
        if len(s) == 2 and name != "conv_w"
    )


def held_params(a: dict) -> float:
    """Every parameter this chip holds."""
    total = a["vocab_rows"] * a["hidden"] + a["hidden"]
    for kind in a["layer_types"]:
        total += sum(_prod(s) for s in layer_shapes(a, kind).values())
    return total


def held_param_bytes(a: dict) -> float:
    """bfloat16 matrices, float32 vectors."""
    total = 2.0 * a["vocab_rows"] * a["hidden"] + 4.0 * a["hidden"]
    for kind in a["layer_types"]:
        for name, s in layer_shapes(a, kind).items():
            total += (4.0 if name in F32_LEAVES else 2.0) * _prod(s)
    return total


def expected_held_selections(a: dict) -> float:
    """Of a token's ``experts_per_token`` selections, how many fall on
    this chip's experts under uniform routing."""
    return a["experts_per_token"] * a["experts_held"][1] / a["experts"]


def dense_flops_per_token(a: dict) -> float:
    return 2.0 * sum(dense_matrix_params(a, kind) for kind in a["layer_types"])


def flops_per_token(a: dict, held_selections: float | None = None) -> float:
    """Forward FLOPs of one token through the held layers, with
    ``held_selections`` routed experts a token a layer (default: the
    expected share). The head is counted apart."""
    if held_selections is None:
        held_selections = expected_held_selections(a)
    return dense_flops_per_token(a) + \
        2.0 * len(a["layer_types"]) * held_selections * expert_params(a)


def head_flops(a: dict) -> float:
    """One position's logits over the held rows."""
    return 2.0 * a["vocab_rows"] * a["hidden"]


def prefill_chunk_flops(a: dict, chunk: int, real: float, held_selections: float) -> float:
    """One dispatched chunk: every one of its ``chunk`` positions goes
    through the dense matrices (padding is computed), the ``real`` ones
    through their held experts, and one position through the head."""
    return (
        chunk * dense_flops_per_token(a)
        + real * 2.0 * len(a["layer_types"]) * held_selections * expert_params(a)
        + head_flops(a)
    )


def state_bytes_per_sequence(a: dict) -> float:
    """One sequence's constant-size state: the SSM state (float32) and the
    convolution tail (bfloat16) of every Mamba layer."""
    mamba = sum(k == MAMBA for k in a["layer_types"])
    return mamba * (
        4.0 * a["mamba_heads"] * a["mamba_head_dim"] * a["mamba_state"]
        + 2.0 * (a["mamba_conv"] - 1) * a["conv_width"]
    )


def kv_bytes_per_position(a: dict) -> float:
    attn = sum(k != MAMBA for k in a["layer_types"])
    return attn * 2 * 2.0 * a["kv_heads"] * a["head_dim"]


def decode_step_bytes(a: dict, batch: float, experts_touched: float,
                      positions: float) -> float:
    """What one decode step must move: every dense matrix and vector and
    the head's rows once, the ``experts_touched`` (summed over layers)
    routed experts once each, each sequence's state in and out, and its
    keys/values up to ``positions``."""
    dense = 0.0
    for kind in a["layer_types"]:
        for name, s in layer_shapes(a, kind).items():
            if not name.startswith("experts_"):
                dense += (4.0 if name in F32_LEAVES else 2.0) * _prod(s)
    head = 2.0 * a["vocab_rows"] * a["hidden"]
    return (
        dense + head + experts_touched * 2.0 * expert_params(a)
        + batch * (2.0 * state_bytes_per_sequence(a) + positions * kv_bytes_per_position(a))
    )
