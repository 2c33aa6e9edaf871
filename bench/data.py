"""Traffic generator: owners' records, the owner schedule and the keys,
all drawn from the run's seed.

Copied in spirit from `repro.data.pipeline` (synthetic owner shards, a
cursor per owner, the uniform schedule that stands for the paper's
rate-1 Poisson clocks), with one change: record j of owner i is made
from (seed, i, j) when it is used, so set-up never materialises every
owner's shard.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np

SCHEDULE_STREAM = 1 << 32       # seed-sequence words that never name an owner
KEY_STREAM = (1 << 32) + 1


def record(seed: int, owner: int, j: int, seq: int, vocab: int) -> np.ndarray:
    """Tokens of record j of `owner`: (seq,) int32."""
    rng = np.random.default_rng([seed, owner, j])
    return rng.integers(0, vocab, size=seq, dtype=np.int32)


def round_key(seed: int, dispatch: int) -> np.ndarray:
    """Raw threefry key (2,) uint32 of one dispatch."""
    return np.random.SeedSequence([seed, KEY_STREAM, dispatch]
                                  ).generate_state(2, np.uint32)


class Traffic:
    """Host side of a cell's traffic: per-owner record cursors and the
    schedule stream of one seed."""

    def __init__(self, seed: int, n_owners: int, records: List[int],
                 seq: int, batch: int, vocab: int):
        self.seed = seed
        self.n_owners = n_owners
        self.records = records
        self.seq, self.batch, self.vocab = seq, batch, vocab
        self.cursor = [0] * n_owners
        self.rng = np.random.default_rng([seed, SCHEDULE_STREAM])

    def schedule(self, k: int) -> np.ndarray:
        """Uniform owner sequence of k rounds."""
        return self.rng.integers(0, self.n_owners, size=k).astype(np.int32)

    def check_schedule(self, k: int, first: int) -> np.ndarray:
        """The first dispatch: `first` distinct owners, then k - first
        rounds drawn uniformly from the other owners, so the rows of the
        first rounds are not touched again within the dispatch."""
        first_owners = self.rng.choice(self.n_owners, size=first,
                                       replace=False)
        rest = np.setdiff1d(np.arange(self.n_owners), first_owners)
        tail = rest[self.rng.integers(0, rest.size, size=k - first)]
        return np.concatenate([first_owners, tail]).astype(np.int32)

    def batches_for(self, owner_seq: np.ndarray) -> Dict[str, np.ndarray]:
        """(K, batch, seq) tokens and labels: round k holds the next
        `batch` records of owner_seq[k], each owner's cursor wrapping at
        its record count."""
        toks = np.empty((len(owner_seq), self.batch, self.seq), np.int32)
        for k, i in enumerate(owner_seq):
            i = int(i)
            n = self.records[i]
            for b in range(self.batch):
                toks[k, b] = record(self.seed, i, (self.cursor[i] + b) % n,
                                    self.seq, self.vocab)
            self.cursor[i] = (self.cursor[i] + self.batch) % n
        return {"tokens": toks, "labels": np.roll(toks, -1, axis=-1)}
