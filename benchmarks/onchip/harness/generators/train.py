"""Training traffic: one seeded batch of token ids per step, every row
different. Parameters: ``seq_len``, ``micro_batch_per_shard``,
``accumulation``, ``warmup_s`` (continuous steps before the window opens)."""

from __future__ import annotations

import numpy as np

KIND = "train"


class Plan:
    def __init__(self, traffic: dict, vocab: int, rows: int, seed: int):
        self.seq_len = int(traffic["seq_len"])
        self.micro = int(traffic["micro_batch_per_shard"])
        self.accum = int(traffic.get("accumulation", 1))
        self.warmup_s = float(traffic.get("warmup_s", 5.0))
        self.shape = (self.accum, rows, self.seq_len)
        self.vocab, self.seed = vocab, seed

    @property
    def tokens_per_step(self) -> int:
        return int(np.prod(self.shape))

    def batch(self, step: int) -> np.ndarray:
        rng = np.random.default_rng([self.seed, 7, int(step)])
        return rng.integers(0, self.vocab, self.shape, dtype=np.int32)


def plan(traffic: dict, vocab: int, seed: int, seconds: float, rows: int = 1) -> Plan:
    """``rows``: sequences per micro-batch over all data shards, as the
    program's configuration makes it."""
    return Plan(traffic, vocab, rows, seed)
