"""``SentenceEncoder.encode`` orders a call's rows by length and cuts them
into dispatches from the closed set ``internals/device.py`` enumerates: a
row's embedding is what it is alone and what the one-slab padding gave,
a short call is the one dispatch it was, and a heavy-tailed call's
shapes are members of the set, compiled at their first sighting only."""

import dataclasses

import numpy as np
import pytest

import jax.monitoring
import jax.numpy as jnp

from pathway_tpu.analysis.device_plan import WorkloadSpec, analyze_device_plan
from pathway_tpu.internals import device
from pathway_tpu.internals.device import (
    PLANE,
    batch_bucket,
    encoder_bucket,
    encoder_call_groups,
    encoder_call_shapes,
    encoder_group_shapes,
    seq_bucket,
)
from pathway_tpu.internals.monitoring import ProberStats
from pathway_tpu.models import encoder as encoder_module
from pathway_tpu.models.encoder import EncoderConfig, SentenceEncoder, pad_batch

TINY_F32 = dataclasses.replace(EncoderConfig.tiny(), dtype=jnp.float32)


def _recorded(enc) -> list:
    """(rows, width) of every forward the encoder dispatches from now on
    (the instance's jitted callables, bound at a bucket's first sighting:
    what a harness taps)."""
    seen = []

    def tapped(fn):
        def call(params, ids, second):
            seen.append(tuple(ids.shape))
            return fn(params, ids, second)

        return call

    enc._forward = tapped(enc._forward)
    enc._forward_compact = tapped(enc._forward_compact)
    return seen


def _texts(lengths) -> list:
    """One vocabulary word a token: ``n`` tokens with [CLS] and [SEP];
    fewer than two is the empty string (its two tokens alone)."""
    return [" ".join(["word"] * max(int(n) - 2, 0)) for n in lengths]


def _one_slab(enc, texts) -> np.ndarray:
    """What the encoder did before calls were cut: batches of
    ``batch_size`` in arrival order, every row as wide as the call's
    longest."""
    ids, mask = enc.tokenizer(texts)
    out = []
    for at in range(0, len(texts), enc.batch_size):
        ids_p, mask_p, n = pad_batch(
            ids[at:at + enc.batch_size], mask[at:at + enc.batch_size],
            enc.config.max_len, enc.batch_size,
        )
        out.append(np.asarray(enc.model.apply({"params": enc.params}, ids_p, mask_p))[:n])
    return np.concatenate(out)


@pytest.fixture(scope="module")
def alone():
    """Each distinct text encoded in a call of its own."""
    enc = SentenceEncoder(TINY_F32, batch_size=32)
    by_text = {}

    def embed(text):
        if text not in by_text:
            by_text[text] = enc.encode([text])[0]
        return by_text[text]

    return embed


@pytest.mark.parametrize("rows", [1, 7, 256, 300])
def test_rows_are_what_they_are_alone_and_in_one_slab(rows, alone):
    rng = np.random.default_rng(rows)
    enc = SentenceEncoder(TINY_F32, batch_size=32)
    # 1 to max_len tokens, both ends held, duplicates, an empty string
    lengths = rng.integers(1, enc.config.max_len + 1, size=rows)
    lengths[rng.integers(rows)] = enc.config.max_len
    texts = _texts(lengths)
    texts[rng.integers(rows)] = ""
    if rows > 1:
        texts[-1] = texts[0]
    seen = _recorded(enc)
    got = enc.encode(texts)
    assert got.shape == (rows, enc.config.hidden) and got.dtype == np.float32
    np.testing.assert_allclose(got, np.stack([alone(t) for t in texts]), atol=1e-5)
    np.testing.assert_allclose(got, _one_slab(enc, texts), atol=1e-5)
    extents = np.sort(enc.tokenizer(texts)[1].sum(axis=1))[::-1]
    plan = encoder_call_groups(extents, enc.batch_size, enc.config.max_len)
    assert seen == [(r, w) for _, _, r, w in plan]
    if rows > 32:
        assert set(seen) <= set(encoder_group_shapes(32, enc.config.max_len))


# the published batch_size and max_len, the body a toy
WIDE_TOY = EncoderConfig(
    vocab_size=512, hidden=32, layers=1, heads=2, mlp=64, max_len=512,
    dtype=jnp.float32,
)


LADDER = (32, 64, 128, 256, 512)


@pytest.mark.parametrize(
    "rows, tokens, shape",
    [
        (1, 5, (8, 32)), (8, 16, (8, 32)), (3, 17, (8, 32)), (64, 64, (64, 64)),
        (60, 30, (64, 32)), (20, 43, (32, 64)), (5, 300, (8, 512)), (3, 100, (8, 128)),
    ],
)
def test_a_short_call_is_one_dispatch_on_the_ladder(rows, tokens, shape):
    """Every question call among them (1 to 64 rows of 11 to 43 tokens):
    the pow2 bucket of its rows, the ladder's rung of its longest."""
    enc = SentenceEncoder(WIDE_TOY, batch_size=256)
    seen = _recorded(enc)
    enc.encode(_texts([tokens] * (rows - 1) + [max(tokens // 2, 1)]))
    assert seen == [shape]
    assert shape == (batch_bucket(rows, 8, 256), seq_bucket(tokens, 512))


@pytest.mark.parametrize(
    "cap, rungs",
    [(512, LADDER), (64, (32, 64)), (384, (32, 64, 128, 256, 384)), (16, (16,))],
)
def test_one_ladder_of_widths_for_every_dispatch(cap, rungs):
    """``seq_bucket`` is the narrowest rung that holds the row; the
    groups' widths are the same rungs, and ``pad_batch`` pads to them."""
    for tokens in range(0, cap + 40):
        want = next((w for w in rungs if tokens <= w), cap)
        assert seq_bucket(tokens, cap) == want, tokens
    assert tuple(w for _, w in encoder_group_shapes(256, cap)) == rungs
    ids = np.ones((3, min(cap, 33)), np.int32)
    assert pad_batch(ids, ids, cap, 256)[0].shape == (8, seq_bucket(ids.shape[1], cap))


def test_the_enumerated_set_is_small_and_the_doctor_reads_it(monkeypatch):
    shapes = encoder_group_shapes(256, 512)
    assert shapes == ((64, 32), (64, 64), (32, 128), (16, 256), (8, 512))
    for cap, max_len in [(256, 512), (256, 64), (8, 64), (32, 384), (1024, 8192)]:
        members = encoder_group_shapes(cap, max_len)
        assert len(members) <= 12 and len(set(members)) == len(members)
        assert members[-1][1] == max_len
        for rows, width in members:  # each is a bucket of pad_batch's own
            assert batch_bucket(rows, 8, cap) == rows
            assert seq_bucket(width, max_len) == width
    # a call that one member holds whole: pad_batch's shape; a longer
    # one: members no wider than the one its longest row opens
    assert encoder_call_shapes(64, 40, 256, 512) == {(64, 64)}
    assert encoder_call_shapes(65, 40, 256, 512) == set(shapes[:2])
    assert encoder_call_shapes(300, 100, 256, 512) == set(shapes[:3])
    # the encoder cuts with, and the Device Doctor lists through, the very
    # functions of internals/device.py: no copy that could drift
    assert encoder_module.encoder_call_groups is device.encoder_call_groups
    assert encoder_module._seq_bucket is device.seq_bucket
    asked = []

    def listed(*args):
        asked.append(args)
        return encoder_call_shapes(*args)

    monkeypatch.setattr(device, "encoder_call_shapes", listed)
    spec = WorkloadSpec(ingest_batches=((8, 40), (256, 60)), batch_cap=256)
    report = analyze_device_plan(workload=spec, config=EncoderConfig.tiny())
    assert asked == [(8, 40, 256, 64), (256, 60, 256, 64)]
    assert report.predictions["encoder.forward"]["buckets"] == {
        encoder_bucket(rows, width, True)
        for rows, width in {(8, 64), *encoder_group_shapes(256, 64)}
    }


class _CompileRequests:
    """JAX's own count of lowerings handed to the backend, while open."""

    def __enter__(self):
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def _on(self, event, _secs, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self._on)


@pytest.mark.parametrize("tokens", [12, 40, 200])
def test_no_executable_is_keyed_by_a_real_row_count(tokens):
    """Calls of 1 to 8 rows at one width: one compile request (the
    8-row bucket's forward), not eight (a slice a row count)."""
    enc = SentenceEncoder(WIDE_TOY, batch_size=256)
    enc.encode(_texts([tokens] * 9))  # a 16-row call: all but that forward
    with _CompileRequests() as compiles:
        for rows in range(1, 9):
            out = enc.encode(_texts([tokens] * rows))
            assert out.shape == (rows, WIDE_TOY.hidden)
    assert compiles.n == 1
    assert len(enc._compiled) == 2


def test_heavy_tailed_calls_stay_inside_the_set_and_compile_once_a_member():
    """64 calls of 256 rows from the ingest cells' length law."""
    enc = SentenceEncoder(WIDE_TOY, batch_size=256)
    members = encoder_group_shapes(256, 512)
    assert len(members) <= 12
    rng = np.random.default_rng(2402)
    words = np.minimum(510, (14 * (1.0 + rng.pareto(1.2, size=(64, 256)))).astype(int))
    seen = _recorded(enc)
    enc.encode(_texts([8]))  # everything but the forwards compiles here
    seen.clear()
    sighted, late, shares, first_seen_at = set(), [], [], {}
    with _CompileRequests() as compiles:
        for k, call in enumerate(words + 2):
            before, at = compiles.n, len(seen)
            enc.encode(_texts(call))
            new = set(seen[at:]) - sighted
            if compiles.n - before > len(new):
                late.append((sorted(new), compiles.n - before))
            for shape in new:
                first_seen_at[shape] = k
            sighted |= new
            padded = sum(r * w for r, w in seen[at:])
            shares.append(padded / (256 * 512))
    assert sighted == set(members)
    assert max(first_seen_at.values()) <= 1  # a set-up's first two calls meet them all
    assert not late, late  # no executable keyed by a call's row count
    assert max(shares) <= 0.25, max(shares)
    assert len(enc._compiled) == len(sighted) + 1  # and the warm call's


def test_encoder_bucket_cache_notes_recompiles():
    """A shape's first dispatch is one ``encoder.forward`` recompile on
    the armed plane; a shape met again is none."""
    enc = SentenceEncoder(EncoderConfig.tiny())
    texts = _texts([11, 10, 9, 8, 9])
    stats = ProberStats()
    PLANE.disarm()
    PLANE.arm(None, stats)
    try:
        enc.encode(texts)      # fresh (batch, seq) bucket
        enc.encode(texts)      # cached: no new note
        enc.encode(texts * 4)  # larger batch bucket
    finally:
        PLANE.disarm()
    assert stats.device_recompiles.get("encoder.forward") == 2
