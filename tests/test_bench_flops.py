"""Pin the bench's FLOP model against XLA's own cost analysis.

The MFU numbers in bench.py are only auditable if the analytic
forward_flops_per_token formula tracks what the compiled executable
actually computes. XLA's cost_analysis() reports the compiled HLO's flop
count; the analytic model must agree within a tolerance that covers the
bits the model deliberately omits (embeddings, layernorms, masking) and
XLA's own fusion accounting quirks.
"""

from __future__ import annotations

import numpy as np
import pytest


def test_flops_model_matches_xla_cost_analysis(monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    import jax
    import jax.numpy as jnp

    from pathway_tpu.models.encoder import (
        EncoderConfig,
        SentenceEncoder,
        forward_flops_per_token,
    )

    cfg = EncoderConfig.tiny()
    enc = SentenceEncoder(cfg, batch_size=8)
    n, L = 8, 64
    ids = jnp.zeros((n, L), jnp.int32)
    mask = jnp.ones((n, L), jnp.int32)
    compiled = (
        jax.jit(lambda i, m: enc._forward(enc.params, i, m))
        .lower(ids, mask)
        .compile()
    )
    cost = compiled.cost_analysis()
    xla_flops = float(cost.get("flops", 0.0))
    assert xla_flops > 0, "cost_analysis returned no flops"
    model = forward_flops_per_token(cfg, L) * n * L
    # the analytic model counts matmul cores only; XLA adds elementwise
    # ops and may fold masking — agree within 25%
    assert model == pytest.approx(xla_flops, rel=0.25), (
        model,
        xla_flops,
    )


def test_flops_model_scales_with_geometry():
    from pathway_tpu.models.encoder import (
        EncoderConfig,
        forward_flops_per_token,
    )

    small = forward_flops_per_token(EncoderConfig.bge_small(), 128)
    base = forward_flops_per_token(EncoderConfig.bge_base(), 128)
    # bge-base doubles hidden and mlp: projection terms 4x, attention 2x
    assert 3.0 < base / small < 4.5
    # longer sequences only grow the attention term
    longer = forward_flops_per_token(EncoderConfig.bge_small(), 512)
    assert small < longer < 1.5 * small
