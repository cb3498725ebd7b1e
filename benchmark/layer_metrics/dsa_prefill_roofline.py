"""The prefill executable's share of its compute roofline under sparse
latent attention: runs x FLOPs of the stretch's mean dispatched chunk
(every position through the projections, the indexers' where a layer owns
one, the dense MLP or the router and the shared expert; real positions
through their held selections; the indexers' scores over the visible
pairs, 8,192 FLOP a pair a layer that owns one; attention over the
SELECTED pairs, 65,536 a pair a layer) over peak, against traced
seconds."""

from dsa_reduce import dsa_prefill_roofline as read  # noqa: F401
