"""The whole answer step's share of the chip's peak bf16 FLOP/s over the
traced stretch: real prompt positions and generated tokens through the
held share, attention at their real contexts, plus the questions'
embeddings and the scans."""

from mla_reduce import mla_answer_step_mfu as read  # noqa: F401
