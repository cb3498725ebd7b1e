"""Prompts the chat generated for in the window over answers asked (the
tap at AnswerModel.generate). 1.0: the retraction replays the answer."""

from answer_reduce import generations_per_answer as read  # noqa: F401
