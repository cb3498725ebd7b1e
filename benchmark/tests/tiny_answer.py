"""The answer cell shrunk to what a CPU test holds: the same files,
generator and checks; a 4-layer m m A m decoder of 8 experts top-3 (4
held) at toy widths over the WordPiece vocabulary's rows, a toy
retriever, a toy index. For tests only."""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

from tiny import tiny_cell  # noqa: E402

CELL = "granite-4.0-h-small.answer-steady"

# the logits of a model this small differ from the float32 reference's by a
# few thousandths of their spread (bfloat16 operands); the cell's own limits
# are set at the published widths, so the tiny cell brings its own, set the
# same way: above the sound run's reading, below every broken run's
TINY_LIMITS = {
    "logit_gap": 0.02, "token_gap": 0.02, "router_gap": 0.02, "state_gap": 0.02,
}
TINY_TOLERANCES = {"token": 0.02, "router": 0.02}


def tiny_answer_cell():
    cell = tiny_cell(CELL)
    c = cell.config
    c["retriever"].update(
        hidden_size=64, num_hidden_layers=2, num_attention_heads=4,
        intermediate_size=128, max_position_embeddings=64)
    c.update(
        hidden_size=64, num_hidden_layers=4,
        layer_types=["mamba", "mamba", "attention", "mamba"],
        num_attention_heads=4, num_key_value_heads=2, attention_multiplier=0.25,
        mamba_n_heads=8, mamba_d_head=16, mamba_d_state=16, mamba_chunk_size=16,
        num_local_experts=4, num_experts_per_tok=3, intermediate_size=32,
        shared_intermediate_size=64, embedding_multiplier=1.5, init_std=0.15,
        encoder_batch_size=32,
    )
    c["published"].update(num_hidden_layers=4, num_local_experts=8)
    c["held"].update(layers=[0, 4], experts=[0, 4])
    c["serving"].update(prefill_chunk=32, max_positions=512, slots=8)
    t = cell.traffic
    t.update(setup_docs=64, setup_commit_docs=32, rate_per_s=4.0, warm_rows=2,
             warm_answers=2, check_answers=3, clients=8, new_tokens=6, k=3)
    t["doc_words"].update(scale=20, cap=60)
    cell.limits["limits"].update(TINY_LIMITS)
    cell.limits["tolerances"].update(TINY_TOLERANCES)
    return cell
