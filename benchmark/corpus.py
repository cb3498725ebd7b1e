"""Texts from the seed: whole WordPiece-vocabulary words (one token each),
so a text of n words is n + 2 tokens. Sizes and arrivals come from the
mix's own ``shape_seed`` and are the same set in every run; ``--seed``
chooses the words and the order, so no seed changes the amount of work."""

from __future__ import annotations

import functools

import numpy as np

import reference


@functools.lru_cache(maxsize=None)
def _vocab() -> np.ndarray:
    with open(reference.VOCAB_FILE, encoding="utf-8") as f:
        picked = [w for w in (line.strip() for line in f) if w.isalpha() and len(w) > 2]
    return np.asarray(picked[:20000], dtype=object)


def words() -> list[str]:
    return list(_vocab())


def _rng(seed: int, stream: int):
    return np.random.default_rng([stream, seed & 0xFFFFFFFF, seed >> 32])


def doc_lengths(n: int, spec: dict, shape_seed: int, seed: int) -> np.ndarray:
    """Word counts ``min(cap, scale * (1 + Pareto(alpha)))``: the same
    multiset for every seed, shuffled by the seed within groups of
    ``shuffle_within`` (the encoder's batch), so that every batch keeps its
    own mix of lengths and no seed changes a dispatched shape."""
    rng = np.random.default_rng(shape_seed)
    lengths = np.minimum(
        spec["cap"], (spec["scale"] * (1.0 + rng.pareto(spec["alpha"], size=n))).astype(int)
    )
    group = int(spec.get("shuffle_within", n))
    order = _rng(seed, 11)
    for at in range(0, n, group):
        order.shuffle(lengths[at:at + group])
    return lengths


def texts(lengths: np.ndarray, seed: int, stream: int, suffix: str = "") -> list[str]:
    vocab = _vocab()
    picks = _rng(seed, stream).integers(0, len(vocab), size=int(lengths.sum()))
    out, at = [], 0
    for n in lengths:
        out.append(" ".join(vocab[picks[at:at + n]]) + suffix)
        at += int(n)
    return out


def arrivals(n: int, seconds: float, shape_seed: int) -> np.ndarray:
    """Due times of ``n`` Poisson arrivals inside ``seconds``: ``n``
    exponential gaps from the mix's ``shape_seed``, scaled to fill the
    window. The same schedule for every ``--seed``: at 4/5 of capacity the
    order of the gaps (where the bursts fall) moves a tail far more than
    anything the system does, so the seed chooses the words, not the bursts."""
    gaps = np.random.default_rng(shape_seed + 1).exponential(1.0, size=n)
    due = np.cumsum(gaps)
    return due * (seconds * n / (n + 1.0)) / due[-1]
