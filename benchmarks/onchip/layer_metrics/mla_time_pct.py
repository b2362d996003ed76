"""Model step: share of the first chip's busy time in ops under the ``mla``
scope (``generate._mla_block``'s mixer: the norm, the projections, the latent's
write, the expansion or the absorption, the attention and the output
projection, of the decode and prefill programs)."""

from harness import program_trace


def read(run, name):
    return program_trace.scope_share_pct(run, "mla")
