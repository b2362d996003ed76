"""Batcher: median time a request of the window spent from its first prefill chunk to its first token
(the fleet's ``prefill`` child span of the request, from the engine's stamps)."""

import statistics

from harness import program_trace


def read(run, name):
    ms = program_trace.request_stage_ms(run, "prefill")
    return statistics.median(ms) if ms else None
