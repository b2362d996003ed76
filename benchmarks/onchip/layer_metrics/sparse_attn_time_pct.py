"""Model step: share of the first chip's busy time in ops under the
``sparse_attn`` scope (``generate._sparse_attn_block``: the block-sparse
attention mixer of the decode and prefill programs, from its input norm to its
output projection: indexer, gather, attention, the writes of keys, values and
compressed keys)."""

from harness import program_trace


def read(run, name):
    return program_trace.scope_share_pct(run, "sparse_attn")
