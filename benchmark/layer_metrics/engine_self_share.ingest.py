"""The engine's own share of the traced stretch: seconds inside the
ring's ``engine.step`` spans that no ``encoder.encode`` or ``index.*``
span beneath them covers (parse and split UDFs, operators, statistics),
over the stretch."""

from ring_reduce import engine_self_share as read  # noqa: F401
