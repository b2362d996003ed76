"""Model step: share of the first chip's busy time in ops under the
``cast_weights`` scope (``transformer.cast_layer_stack``: the float32 layer
stack cast to the compute dtype inside every dispatch)."""

from harness import program_trace


def read(run, name):
    return program_trace.scope_share_pct(run, "cast_weights")
