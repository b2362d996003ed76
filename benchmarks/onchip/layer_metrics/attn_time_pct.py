"""Model step: share of the first chip's busy time in ops under the
``decode_attn`` scope of the decode and prefill programs (``generate._decode_block``)."""

from harness import program_trace


def read(run, name):
    return program_trace.scope_share_pct(run, "decode_attn")
