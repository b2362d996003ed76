"""Closed-loop serving traffic: ``clients`` callers, each sending its next
request when its last completes, so every slot stays full. The file fixes one
list of ``requests`` shapes (prompt and output lengths at the quantiles of
their distributions, laid out once by ``order_seed``); ``--seed`` makes the
token ids and nothing else (any reordering moves the rate by more than two
runs of one order differ: PERF.md, PR 23); clients take shapes from the list
in turn, round and round. Set-up submits the first
``clients`` requests and opens the window once all of them decode."""

from __future__ import annotations

from .dists import quantile_values, rng_for

KIND = "serve"
LOOP = "closed"


class Plan:
    def __init__(self, traffic: dict, vocab: int, seed: int, seconds: float):
        n = int(traffic["requests"])
        order = rng_for(int(traffic.get("order_seed", 0)), 3)
        rng = rng_for(seed, 3)
        self.clients = int(traffic["clients"])
        self.prompt_lens = order.permutation(quantile_values(traffic["prompt_tokens"], n))
        self.output_lens = order.permutation(quantile_values(traffic["output_tokens"], n))
        self._rng, self._vocab, self._i = rng, vocab, 0
        self.lead_in_s = float(traffic.get("lead_in_s", 0.0))
        self.drain_s = 0.0

    def next_request(self) -> tuple[list, int]:
        i = self._i % len(self.prompt_lens)
        self._i += 1
        p = int(self.prompt_lens[i])
        return self._rng.integers(0, self._vocab, p).tolist(), int(self.output_lens[i])

    def warm_shapes(self) -> list[tuple[int, int]]:
        return sorted({(int(p), 2) for p in self.prompt_lens})


def plan(traffic: dict, vocab: int, seed: int, seconds: float, **_) -> Plan:
    return Plan(traffic, vocab, seed, seconds)
