"""The sparse-attention answer cell shrunk to what a CPU test holds: the
same files, generator and checks; five latent-attention layers (published
layers 2-6 of a toy 8: dense, experts x 4; indexers full shared shared
full shared) whose indexers keep 64 cached rows a query, 16 experts top-3
under the sigmoid bias-corrected router (4 held) at toy widths over the
held vocabulary rows, a toy retriever, a toy index. For tests only."""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

from tiny import tiny_cell  # noqa: E402

CELL = "GLM-5.2.answer-sparse"
PATTERN = ["full", "shared", "shared", "full", "shared"]

# as tiny_answer_long.py: the cell's own limits are set at the published
# widths, so the tiny cell brings its own, set the same way: above the sound
# run's readings (logit_gap 0.0094, router_gap 0.0070, state_gap 0.0195,
# index_gap 0.0186 on seed 5), below the broken runs' (0.29-0.96, 0.76-0.98,
# 0.92-1.45, 0.62-1.25) and the control's
TINY_LIMITS = {
    "logit_gap": 0.03, "token_gap": 0.06, "router_gap": 0.03, "state_gap": 0.06,
    "index_gap": 0.06,
}
TINY_TOLERANCES = {"token": 0.06, "router": 0.03, "index": 0.06}


def tiny_answer_sparse_cell():
    cell = tiny_cell(CELL)
    c = cell.config
    c["retriever"].update(
        hidden_size=64, num_hidden_layers=2, num_attention_heads=4,
        intermediate_size=128, max_position_embeddings=64)
    c.update(
        hidden_size=64, num_hidden_layers=5, num_attention_heads=4, num_key_value_heads=4,
        q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, intermediate_size=96, moe_intermediate_size=32,
        n_routed_experts=4, num_experts_per_tok=3, first_k_dense_replace=1,
        index_n_heads=4, index_head_dim=16, index_topk=64,
        indexer_types=PATTERN, mlp_layer_types=["dense"] + ["sparse"] * 4,
        init_std=0.15, encoder_batch_size=32,
    )
    c["published"].update(
        num_hidden_layers=8, first_k_dense_replace=3, n_routed_experts=16,
        indexer_types=["full", "full"] + PATTERN + ["shared"],
        mlp_layer_types=["dense"] * 3 + ["sparse"] * 5)
    c["held"].update(layers=[2, 5], experts=[0, 4])
    c["serving"].update(prefill_chunk=32, max_positions=512, slots=8, decode_block=128)
    t = cell.traffic
    t.update(setup_docs=64, setup_commit_docs=32, rate_per_s=4.0, warm_rows=2,
             warm_answers=2, check_answers=3, clients=8, new_tokens=6, k=4)
    t["doc_words"].update(scale=20, cap=60)
    cell.limits["limits"].update(TINY_LIMITS)
    cell.limits["tolerances"].update(TINY_TOLERANCES)
    return cell
