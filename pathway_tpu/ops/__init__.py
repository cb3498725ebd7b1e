"""pathway_tpu.ops — TPU dense kernels for the framework's hot paths.

The reference implements its retrieval hot loop in native Rust
(/root/reference/src/external_integration/brute_force_knn_integration.rs:22-237
— ndarray matmul + k_smallest on CPU). Here the same role is played by
XLA: padded HBM-resident vector shards, fused matmul + top-k scoring on
the MXU, and mergeable partial top-k results for mesh-sharded indexes
(SURVEY §5 long-context mapping).
"""

from pathway_tpu.ops.topk import masked_topk, merge_topk, tree_merge_topk
from pathway_tpu.ops.knn import KnnShard, Metric

__all__ = [
    "KnnShard",
    "Metric",
    "masked_topk",
    "merge_topk",
    "tree_merge_topk",
]
