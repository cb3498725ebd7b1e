"""The long-context answer cell shrunk to what a CPU test holds: the same
files, generator and checks; three latent-attention layers (dense,
experts, experts) of 16 experts in 4 groups top-3 (4 held) at toy widths
over the held vocabulary rows, a toy retriever, a toy index. For tests
only."""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

from tiny import tiny_cell  # noqa: E402

CELL = "DeepSeek-V2.answer-long"

# as tiny_answer.py: the cell's own limits are set at the published widths,
# so the tiny cell brings its own, set the same way: above the sound run's
# reading (logit_gap 0.0104, router_gap 0.0028, state_gap 0.0120 on seed 5),
# below the broken run's (0.66, 0.70, 1.0) and the control's
TINY_LIMITS = {
    "logit_gap": 0.03, "token_gap": 0.06, "router_gap": 0.03, "state_gap": 0.03,
}
TINY_TOLERANCES = {"token": 0.06, "router": 0.03}


def tiny_answer_long_cell():
    cell = tiny_cell(CELL)
    c = cell.config
    c["retriever"].update(
        hidden_size=64, num_hidden_layers=2, num_attention_heads=4,
        intermediate_size=128, max_position_embeddings=64)
    c.update(
        hidden_size=64, num_hidden_layers=3, num_attention_heads=4, num_key_value_heads=4,
        q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, intermediate_size=96, moe_intermediate_size=32,
        n_routed_experts=4, n_group=4, topk_group=2, num_experts_per_tok=3,
        routed_scaling_factor=4, init_std=0.15, encoder_batch_size=32,
    )
    c["rope_scaling"].update(factor=4, original_max_position_embeddings=64, beta_fast=8)
    c["published"].update(num_hidden_layers=3, n_routed_experts=16)
    c["held"].update(layers=[0, 3], experts=[0, 4])
    c["serving"].update(prefill_chunk=32, max_positions=512, slots=8, decode_block=128)
    t = cell.traffic
    t.update(setup_docs=64, setup_commit_docs=32, rate_per_s=4.0, warm_rows=2,
             warm_answers=2, check_answers=3, clients=8, new_tokens=6, k=4)
    t["doc_words"].update(scale=20, cap=60)
    cell.limits["limits"].update(TINY_LIMITS)
    cell.limits["tolerances"].update(TINY_TOLERANCES)
    return cell
