"""Payload generators: the benchmark's own copies, so that a change to the
program's data code cannot move the yardstick.

- :func:`news_day` follows ``TopicNews`` (topic-structured token sentences,
  ``benchmarks/common.py``) and ``hashed_features`` (hashed unigram and
  bigram counts, l2-normalised rows; ``repro.data.synthetic``), drawn in
  bulk per topic rather than sentence by sentence.
- :func:`video` follows ``repro.data.synthetic.video``: SumMe-like frame
  descriptors, a random walk per scene, nonnegative unit-norm rows.

Everything is host numpy float32, as a client's payload arrives.
"""

from __future__ import annotations

import numpy as np


def rng_for(*words: int) -> np.random.Generator:
    """A generator seeded from whole numbers of any size and sign."""
    return np.random.default_rng([int(w) % (1 << 63) for w in words])


def news_tokens(rng: np.random.Generator, n: int, vocab: int = 2048,
                n_topics: int = 10, sent_len: int = 18) -> np.ndarray:
    """(n, sent_len) int64 sentences, each drawn from its topic's Dirichlet
    word distribution (TopicNews)."""
    topics = rng.dirichlet(np.full(vocab, 0.03), size=n_topics)
    weights = rng.dirichlet(np.ones(n_topics) * 0.5)
    assign = rng.choice(n_topics, size=n, p=weights)
    docs = np.empty((n, sent_len), np.int64)
    for t in range(n_topics):
        rows = np.flatnonzero(assign == t)
        if rows.size:
            cdf = np.cumsum(topics[t])
            u = rng.random((rows.size, sent_len)) * cdf[-1]
            docs[rows] = np.minimum(np.searchsorted(cdf, u, side="right"),
                                    vocab - 1)
    return docs


def hashed_features(tokens: np.ndarray, n_features: int = 1024,
                    ngram: int = 2) -> np.ndarray:
    """Hashed 1..ngram-gram counts, l2-normalised rows: (n, F) float32."""
    n, L = tokens.shape
    t = tokens.astype(np.int64)
    cols, rows = [], []
    for g in range(1, ngram + 1):
        h = np.zeros((n, L - g + 1), np.int64)
        for j in range(g):
            h = h * 1_000_003 + t[:, j: L - g + 1 + j]
        h = (h ^ (h >> 13)) * 0x9E3779B1
        cols.append((np.abs(h) % n_features).ravel())
        rows.append(np.repeat(np.arange(n), L - g + 1))
    flat = np.concatenate(rows) * n_features + np.concatenate(cols)
    W = np.bincount(flat, minlength=n * n_features).astype(np.float32)
    W = W.reshape(n, n_features)
    W /= np.maximum(np.linalg.norm(W, axis=1, keepdims=True), 1e-9)
    return W


def news_day(seed: int, day: int, n: int, n_features: int = 1024) -> np.ndarray:
    """One day of news: (n, n_features) float32 hashed-bigram sentences."""
    return hashed_features(news_tokens(rng_for(seed, 1, day), n), n_features)


def video(seed: int, vid: int, n_frames: int, n_features: int = 256,
          walk_sigma: float = 0.02) -> np.ndarray:
    """One SumMe-like video: (n_frames, n_features) float32, unit rows."""
    rng = rng_for(seed, 2, vid)
    n_scenes = max(3, n_frames // 400)
    cuts = np.sort(rng.choice(np.arange(1, n_frames), n_scenes - 1,
                              replace=False))
    bounds = np.concatenate([[0], cuts, [n_frames]])
    X = np.zeros((n_frames, n_features), np.float32)
    for s in range(n_scenes):
        lo, hi = bounds[s], bounds[s + 1]
        center = np.abs(rng.normal(0, 1, n_features))
        steps = rng.normal(0, walk_sigma, (hi - lo, n_features)).cumsum(axis=0)
        X[lo:hi] = np.abs(center[None, :] + steps)
    X /= np.maximum(np.linalg.norm(X, axis=1, keepdims=True), 1e-9)
    return X
