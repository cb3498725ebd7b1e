"""Seconds of the ring's ``answer.generate`` inside a request's dispatch
leg over that leg: median over the stretch's requests."""

from answer_reduce import generate_share_of_request as read  # noqa: F401
