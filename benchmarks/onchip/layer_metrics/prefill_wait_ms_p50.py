"""Batcher: median time a request of the window spent holding a slot until its first prefill chunk began
(the fleet's ``prefill_wait`` child span of the request, from the engine's stamps)."""

import statistics

from harness import program_trace


def read(run, name):
    ms = program_trace.request_stage_ms(run, "prefill_wait")
    return statistics.median(ms) if ms else None
