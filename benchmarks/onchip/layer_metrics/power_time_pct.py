"""Model step: share of the first chip's busy time in ops under the ``power``
scope (``generate._power_block``: the power-retention mixer of the decode and
prefill programs, from its input norm to its output projection), over the whole
trace as every ``*_time_pct`` reader takes it (in a closed-loop cell that is
the fill's end, the lead-in and the window's first seconds)."""

from harness import program_trace


def read(run, name):
    return program_trace.scope_share_pct(run, "power")
