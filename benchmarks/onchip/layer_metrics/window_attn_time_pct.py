"""Model step: share of the first chip's busy time in ops under the
``window_attn`` scope (``generate._diff_attend``: the two contractions of the
windowed differential-attention layers, over the pool's ring of one window in
a decode step and over a block's window of a staged prompt in a chunk),
over the whole trace as every ``*_time_pct`` reader takes it. In a closed-loop
cell with a long fill that is mostly the fill (decode dispatches with the rows
admitted so far, a prefill chunk between every two), and in
``phi-4-mini-flash.serve-reason32`` all of it: the device's side of that trace
ends before the window opens (``counts_phi4flash.traced_decode``)."""

from harness import program_trace


def read(run, name):
    return program_trace.scope_share_pct(run, "window_attn")
