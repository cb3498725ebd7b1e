"""Model smoke tests (tiny geometry, CPU backend)."""

import numpy as np

from pathway_tpu.models import (
    CrossEncoder,
    EncoderConfig,
    HashTokenizer,
    SentenceEncoder,
)


def test_hash_tokenizer_deterministic():
    tok = HashTokenizer(vocab_size=1000)
    ids1, mask1 = tok(["hello world", "a much longer sentence with morewordsthanusual"])
    ids2, _ = tok(["hello world", "a much longer sentence with morewordsthanusual"])
    np.testing.assert_array_equal(ids1, ids2)
    assert mask1[0].sum() == 4  # CLS hello world SEP
    assert (ids1 < 1000).all() and (ids1 >= 0).all()


def test_sentence_encoder_shapes_and_norm():
    enc = SentenceEncoder(EncoderConfig.tiny(), batch_size=16)
    out = enc.encode(["short", "a somewhat longer text here", "third"])
    assert out.shape == (3, 64)
    np.testing.assert_allclose(np.linalg.norm(out, axis=-1), 1.0, rtol=1e-4)
    # deterministic across calls and batch-size-independent
    again = enc.encode(["a somewhat longer text here"])
    np.testing.assert_allclose(out[1], again[0], atol=2e-2)


def test_sentence_encoder_empty():
    enc = SentenceEncoder(EncoderConfig.tiny())
    assert enc.encode([]).shape == (0, 64)


def test_cross_encoder_scores():
    ce = CrossEncoder(EncoderConfig.tiny(), batch_size=8)
    scores = ce.score([("query", "relevant doc"), ("query", "other doc text")])
    assert scores.shape == (2,)
    assert np.isfinite(scores).all()
    again = ce.score([("query", "relevant doc")])
    np.testing.assert_allclose(scores[0], again[0], atol=2e-2)


def test_reference_forward_matches_flax_module_in_float32():
    """The plain-jnp float32 oracle (what chip_smoke.py compares the bf16
    device path against) must itself equal the Flax module when both run
    in float32: ragged masks, a one-token row, padding past every row.
    Tolerance: float32 rounding of two orderings of the same sums."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from pathway_tpu.models.encoder import (
        TransformerEncoder,
        reference_forward,
    )

    cfg = dataclasses.replace(EncoderConfig.tiny(), dtype=jnp.float32)
    model = TransformerEncoder(cfg)
    rng = np.random.default_rng(0)
    ids = rng.integers(3, cfg.vocab_size, size=(5, 40)).astype(np.int32)
    lengths = np.array([38, 7, 23, 1, 33])
    mask = (np.arange(40)[None, :] < lengths[:, None]).astype(np.int32)
    params = jax.jit(model.init)(
        jax.random.PRNGKey(1), ids[:1, :8], mask[:1, :8]
    )["params"]
    got = np.asarray(model.apply({"params": params}, ids, mask))
    want = np.asarray(reference_forward(params, cfg, ids, mask))
    np.testing.assert_allclose(got, want, atol=2e-6)
