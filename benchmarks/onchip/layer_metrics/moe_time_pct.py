"""Model step: share of the first chip's busy time in ops under the ``moe``
scope (``generate._mlp_block`` of a mixture: the norm, the router, the held
experts' contraction and the shared expert, of the decode and prefill
programs)."""

from harness import program_trace


def read(run, name):
    return program_trace.scope_share_pct(run, "moe")
