"""Tokens of the dearest held expert over the mean of the held experts, by
layer, worst layer, over the window (the model's counter)."""

from answer_reduce import expert_load_max_over_mean as read  # noqa: F401
