"""Model step: share of the first chip's busy time in ops under the
``lightning`` scope (``generate._lightning_block``: the lightning-attention
mixer of the decode and prefill programs, from its input norm to its output
projection)."""

from harness import program_trace


def read(run, name):
    return program_trace.scope_share_pct(run, "lightning")
