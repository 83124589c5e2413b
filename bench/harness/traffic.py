"""Training batches made on the host from ``--seed``.

A numpy copy of the program's synthetic stream (``repro.data.synthetic``):
a Zipf-distributed start token per row, then a Markov chain that follows
a fixed permutation of the vocabulary and, with probability 0.1, jumps to
a uniform random token.  For an encoder, 15% of the positions are
masked (input id ``vocab - 1``) and are the loss's targets.  Each data
parallel shard of a step draws from its own generator, seeded by
``(seed, step, shard)``, so a step's global batch is the same whatever
order it is made in.

Warmup batches additionally carry the vocabulary: warmup step ``k``
holds share ``k % cover_steps`` of every id (in a seeded order), each at
a seeded position that is a loss target left unmasked, as BERT's "keep
the token" share of its targets is.  After a short warmup the frozen
second moment would otherwise hold exact zeros in the embedding rows of
ids no warmup batch held as an input, and next to zeros in elements
whose gradient was small the one time their id was seen; such an
element moves by ``m / eps``, or near it, once the compression stage
starts.  Seen in several warmup steps, an element's second moment is
small only if every one of its gradients was.
"""
from __future__ import annotations

from typing import Dict

import numpy as np

_PERM_SEED = 1234      # the fixed transition permutation, as in the program
_COVER_TAG = 7         # generator tag of the vocabulary cover
_NOISE = 0.1           # chance that a token jumps to a uniform random one
_MASK = 0.15           # share of positions masked for the MLM loss


def _rng(seed: int, *tags: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) % 2**64, *tags])


class Stream:
    """The batches of one cell: ``batch(step)`` for the compressed steps,
    ``warmup_batch(step)`` for the warmup steps."""

    def __init__(self, vocab: int, batch: int, seq: int, shards: int,
                 seed: int, cover_steps: int = 0):
        if batch % shards:
            raise ValueError(f"batch {batch} does not split over {shards} "
                             "shards")
        self.vocab, self.rows, self.seq = vocab, batch, seq
        self.shards, self.seed = shards, seed
        self.cover_steps = cover_steps
        self.perm = np.random.default_rng(_PERM_SEED).permutation(vocab)
        p = 1.0 / (np.arange(vocab) + 2.0)
        self.start_cdf = np.cumsum(p / p.sum())

    def _shard(self, step: int, shard: int, tag: int) -> Dict[str, np.ndarray]:
        rng = _rng(self.seed, tag, step, shard)
        b, s, v = self.rows // self.shards, self.seq, self.vocab
        start = np.minimum(np.searchsorted(self.start_cdf, rng.random(b)),
                           v - 1)
        noise = rng.random((b, s)) < _NOISE
        jump = rng.integers(0, v, (b, s))
        toks = np.empty((b, s), np.int64)
        tok = start
        for i in range(s):
            tok = np.where(noise[:, i], jump[:, i], self.perm[tok])
            toks[:, i] = tok
        mask = rng.random((b, s)) < _MASK
        return {"tokens": np.where(mask, v - 1, toks).astype(np.int32),
                "labels": toks.astype(np.int32),
                "loss_mask": mask.astype(np.float32)}

    def _global(self, step: int, tag: int) -> Dict[str, np.ndarray]:
        parts = [self._shard(step, r, tag) for r in range(self.shards)]
        return {k: np.concatenate([p[k] for p in parts]) for k in parts[0]}

    def batch(self, step: int) -> Dict[str, np.ndarray]:
        """The batch of compressed step ``step`` (0-based)."""
        return self._global(step, 0)

    def warmup_batch(self, step: int) -> Dict[str, np.ndarray]:
        """The batch of warmup step ``step``: the stream, with share
        ``step % cover_steps`` of the vocabulary (in a seeded order) written
        over seeded positions as unmasked loss targets."""
        out = self._global(step, 1)
        if not self.cover_steps:
            return out
        order = _rng(self.seed, _COVER_TAG).permutation(self.vocab)
        share = np.array_split(order, self.cover_steps)[
            step % self.cover_steps]
        n = self.rows * self.seq
        if share.size > n:
            raise ValueError(f"{share.size} cover ids do not fit a batch of "
                             f"{n} tokens: raise cover_steps")
        pos = _rng(self.seed, _COVER_TAG, step).choice(n, share.size,
                                                       replace=False)
        for k in ("tokens", "labels"):
            flat = out[k].reshape(-1)
            flat[pos] = share
        out["loss_mask"].reshape(-1)[pos] = 1.0
        return out
