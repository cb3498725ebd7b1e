"""Operations and bytes, from shapes alone. Copied from the program
(``models/encoder.py forward_flops_per_token``, ``ops/topk.py
topk_scan_cost``) so that the count does not move when the program does."""


def encoder_flops_per_token(arch: dict, seq_len: int) -> float:
    """Forward FLOPs of one token in a sequence of ``seq_len``: per layer
    QKV + output projections 8h^2, the MLP pair 4hm, attention scores and
    weighted values 4Lh. Embeddings, layer norms and pooling are O(h)."""
    h, m = arch["hidden_size"], arch["intermediate_size"]
    return arch["num_hidden_layers"] * (8.0 * h * h + 4.0 * h * m + 4.0 * seq_len * h)


def encoder_flops(arch: dict, rows: int, seq_len: int) -> float:
    """One dispatch of ``rows`` x ``seq_len`` (padded or real) tokens."""
    return encoder_flops_per_token(arch, seq_len) * rows * seq_len


def encoder_param_bytes(arch: dict) -> float:
    h, m = arch["hidden_size"], arch["intermediate_size"]
    return 4.0 * (
        arch["vocab_size"] * h + arch["max_position_embeddings"] * h
        + arch["num_hidden_layers"] * (4.0 * h * h + 2.0 * h * m)
    )


def scan_flops(q: int, capacity: int, d: int) -> float:
    return 2.0 * q * capacity * d


def scan_bytes(capacity: int, d: int) -> float:
    """What one scan must read: every row (float32), its validity byte
    and its squared norm."""
    return capacity * (4.0 * d + 5.0)


def real_token_flops(arch: dict, batches) -> float:
    """Forward FLOPs of the real (unpadded) tokens of tapped encoder
    batches ``(t, phase, rows, longest, real_tokens)``, each token costed
    at its batch's mean real length."""
    return sum(
        encoder_flops_per_token(arch, real / max(rows, 1)) * real
        for _, _, rows, _, real in batches
    )
