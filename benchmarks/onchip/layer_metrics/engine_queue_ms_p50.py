"""Batcher: median time a request of the window spent submitted to the engine until it took a slot
(the fleet's ``engine_queue`` child span of the request, from the engine's stamps)."""

import statistics

from harness import program_trace


def read(run, name):
    ms = program_trace.request_stage_ms(run, "engine_queue")
    return statistics.median(ms) if ms else None
