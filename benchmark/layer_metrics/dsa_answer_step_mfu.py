"""The whole answer step's share of peak FLOP/s over the traced stretch:
real prompt and generated tokens through the held share, the indexers'
scores at their real contexts, attention over the selected rows, the
questions' embeddings and the scans."""

from dsa_reduce import dsa_answer_step_mfu as read  # noqa: F401
