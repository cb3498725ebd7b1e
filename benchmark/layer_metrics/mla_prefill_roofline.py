"""The prefill executable's share of its compute roofline under latent
attention: runs x FLOPs of the stretch's mean dispatched chunk (every
position through the projections, the dense MLP or the router and shared
experts; real positions through their held selections and over the
scores and values of their real contexts) over peak, against traced
seconds."""

from mla_reduce import mla_prefill_roofline as read  # noqa: F401
