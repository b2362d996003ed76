"""Open-loop serving traffic: requests fall due on a schedule whatever the
server does.

The file fixes the whole schedule: ``rate_per_s`` x window requests whose
prompt lengths, output lengths and gaps are the values at the quantiles
(i + 1/2)/n of their distributions, laid out in an order drawn once from the
file's ``order_seed``, with the gaps scaled to sum to the window. ``--seed``
makes the token ids (and, in the runner, the weights) and nothing else: every
seed offers the same requests at the same instants.

That is narrower than "the same multiset in another order", and measured to be
needed (PERF.md, PR 23): time to first token here is mostly queueing for the
one prefill lane, and on the chip a free shuffle moved its tail by 25 % from
seed to seed, a rotation of the schedule by 8 %, and letting only neighbouring
quantiles trade places still by 5 %, against 1 % between two runs of one
order. ``lead_in_s`` seconds of the schedule's own tail run before the window
(unmeasured), so that it opens on a server already at work.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from .dists import quantile_values, rng_for

KIND = "serve"
LOOP = "open"


@dataclass
class Request:
    due_s: float          # relative to the window's start; negative in the lead-in
    prompt: list
    max_new_tokens: int
    measured: bool


class Plan:
    def __init__(self, traffic: dict, vocab: int, seed: int, seconds: float):
        n = max(int(round(float(traffic["rate_per_s"]) * seconds)), 1)
        order, rng = rng_for(int(traffic.get("order_seed", 0)), 1), rng_for(seed, 1)
        lay = lambda key: order.permutation(quantile_values(traffic[key], n))  # noqa: E731
        prompts, outputs, gaps = lay("prompt_tokens"), lay("output_tokens"), lay("gaps")
        gaps = gaps * (seconds / gaps.sum())
        due = np.cumsum(gaps)
        self.lead_in_s = float(traffic.get("lead_in_s", 0.0))
        self.drain_s = float(traffic.get("drain_s", 30.0))
        lead, t, i = [], 0.0, n - 1                # the schedule's tail, walked backwards from 0
        while self.lead_in_s and t - gaps[(i + 1) % n] >= -self.lead_in_s and len(lead) < n:
            t -= gaps[(i + 1) % n]
            lead.append((t, int(prompts[i]), int(outputs[i])))
            i = (i - 1) % n
        mk = lambda d, p, o, m: Request(float(d), rng.integers(0, vocab, int(p)).tolist(), int(o), m)  # noqa: E731
        self.requests = [mk(d, p, o, False) for d, p, o in reversed(lead)] + \
                        [mk(d, p, o, True) for d, p, o in zip(due, prompts, outputs)]

    def warm_shapes(self) -> list[tuple[int, int]]:
        """(prompt length, output length) pairs that together touch every
        compiled shape the requests will: one per distinct prompt length (the
        runner keeps one per padded length)."""
        return sorted({(len(r.prompt), 2) for r in self.requests})


def plan(traffic: dict, vocab: int, seed: int, seconds: float, **_) -> Plan:
    return Plan(traffic, vocab, seed, seconds)
