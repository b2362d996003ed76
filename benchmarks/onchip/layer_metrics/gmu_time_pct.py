"""Model step: share of the first chip's busy time in ops under the
``gmu`` scope (``generate._gmu_block``: a gated memory unit's two projections and its
gate over the carried memory),
over the whole trace as every ``*_time_pct`` reader takes it. In a closed-loop
cell with a long fill that is mostly the fill (decode dispatches with the rows
admitted so far, a prefill chunk between every two), and in
``phi-4-mini-flash.serve-reason32`` all of it: the device's side of that trace
ends before the window opens (``counts_phi4flash.traced_decode``)."""

from harness import program_trace


def read(run, name):
    return program_trace.scope_share_pct(run, "gmu")
