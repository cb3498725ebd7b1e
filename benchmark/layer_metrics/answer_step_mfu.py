"""The whole answer step's share of the chip's peak bf16 FLOP/s over the
traced stretch: real prompt positions and generated tokens through the
held share (at the measured share of selections), plus the questions'
embeddings and the scans."""

from answer_reduce import answer_step_mfu as read  # noqa: F401
