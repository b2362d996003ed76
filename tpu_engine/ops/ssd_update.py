"""The decode step of a recurrent state, in place: one Pallas TPU kernel that
passes over a layer's state once.

A Mamba-2 layer's decode step, and a lightning-attention layer's (the same
recurrence with B = k, C = q per head), is ``generate._ssd_step``:
``h' = h * exp(dt A) + (dt x) (x) B`` and ``y = sum_n h' C``, all float32 and
bound by reading and writing ``h`` ([B, H, P, N]: 134 MB a layer of
granite-4.0-h-small's 32 slots). XLA lowers that to two fusions: one writes
``h'`` into the carried stack, the other reads ``h`` AGAIN and recomputes
``h'`` for ``y``. Here a program holds a block of rows x heads of ``h`` in the
chip's fast memory, forms ``h'`` and ``y`` from that one copy and writes ``h'``
back to the block it came from.

The state operand is the serving pool's leaf as stored, the WHOLE stack
``[L, B, H, P, N]``, aliased to the output. The layer index is prefetched to
scalar memory and the block index maps pick that layer's blocks: nothing is
sliced out of the stack or pasted back into it (each would be a pass of its
own), and the other layers' blocks are never visited.

Which whole kind runs which update (``layer_state.LAYER_KINDS``): ``ssm``
(Mamba-2) and ``lightning`` this kernel, through ``generate._ssd_step_at``;
``power`` (gated power retention: a state per kv-head that several query heads
read through an expansion formed in the kernel) ``ops.power_update``;
``mamba1`` XLA's step (its state is ``[N, I]``, no ``[P, N]`` tile).

One device's state only. Every caller that would shard a recurrent state
(mesh-sharded serving, ``disagg``, ``spec_pool``) refuses the stack by name
before it gets here (``transformer.refuse_recurrent``), so the kernel carries no
partitioning rule.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# The state one program holds. The chip's fast memory has it four times (in and
# out, two copies in flight each), which at 2 MiB stays inside the compiler's
# default allowance; from 1 MiB up the size moves nothing on a v5e, where the
# kernel takes what a bare copy through the same pipeline takes
# (benchmarks/ssd_update_probe.py; PERF.md, PR 39).
_BLOCK_BYTES = 2 << 20

# Off the TPU the kernel can only be interpreted, and XLA's form of the step is
# merely slower there, not wrong: the caller keeps ``generate._ssd_step`` unless
# a test asks for the interpreter here.
INTERPRET_OFF_TPU = False


def on_tpu() -> bool:
    return jax.devices()[0].platform == "tpu"


def engages(state) -> bool:
    """Whether :func:`ssd_update` runs for this stacked leaf ``[L, B, H, P, N]``
    on this process's devices: a float32 state whose ``[P, N]`` tiles are whole
    registers (N a multiple of 128, P of 8), on a TPU (or interpreted, where a
    test asked). Anything else keeps the XLA step. Decided from what the trace
    sees; no option selects it."""
    return (state.ndim == 5 and state.dtype == jnp.float32
            and state.shape[4] % 128 == 0 and state.shape[3] % 8 == 0
            and (on_tpu() or INTERPRET_OFF_TPU))


def block_of(B: int, H: int, P: int, N: int, block_bytes: int = _BLOCK_BYTES) -> tuple[int, int]:
    """(rows, heads) of the state a program holds: the most heads of one row
    that fit ``block_bytes`` (a multiple of 8 that divides H, or all H; the
    fewest such where none fits) and, where all of a row's heads fit, as many
    rows (a divisor of B) as still do."""
    tile = P * N * 4
    choices = [h for h in range(8, H, 8) if H % h == 0] + [H]
    heads = max((h for h in choices if h * tile <= block_bytes), default=choices[0])
    if heads < H:
        return 1, heads
    return max(b for b in range(1, B + 1) if B % b == 0 and (b == 1 or b * H * tile <= block_bytes)), H


def _kernel(at_ref, decay_ref, dtx_ref, b_ref, c_ref, h_ref, h_out_ref, y_ref):
    del at_ref  # the index maps read it
    # [rows, heads, P, N]; decay [rows, heads, 1], dtx [rows, heads, P],
    # b and c [rows, heads | 1, N]
    h = h_ref[0] * decay_ref[...][..., None] + dtx_ref[...][..., None] * b_ref[...][:, :, None, :]
    h_out_ref[0] = h
    y_ref[...] = jnp.sum(h * c_ref[...][:, :, None, :], axis=-1)


def ssd_update(x, dt, A, Bm, Cm, state, layer, *, block_bytes: int = _BLOCK_BYTES):
    """One recurrence step of layer ``layer`` of ``state``, in place.

    x [B, H, P]; dt [B, H] float32, 0 for a row that must keep its state bit
    for bit (``h * 1 + 0``); A [H]; Bm, Cm [B, N] (shared by the heads:
    Mamba-2) or [B, H, N] (per head: lightning attention); state
    [L, B, H, P, N] float32, the whole stack (donate it: it is aliased to the
    output); ``layer`` scalar int32. What ``generate._ssd_step`` computes on
    ``state[layer]``: the same float32 products, and the sum over N in the
    kernel's order. Returns (y [B, H, P] float32, state).

    ``block_bytes`` is the state a program holds (:func:`block_of`), for the
    probe and the tests to vary. Off the TPU (:func:`engages` says when a
    caller gets here) the kernel is interpreted."""
    _, B, H, P, N = state.shape
    f32 = jnp.float32
    rows, heads = block_of(B, H, P, N, block_bytes)
    decay = jnp.exp(dt * A)[:, :, None]                            # [B, H, 1]
    dtx = dt[:, :, None] * x.astype(f32)                           # [B, H, P]
    per_head = Bm.ndim == 3
    Bm, Cm = (a.astype(f32).reshape(B, -1, N) for a in (Bm, Cm))   # [B, H | 1, N]

    def small(n_heads, width):  # a block of an operand that has no [P, N] tile
        return pl.BlockSpec((rows, n_heads, width),
                            lambda i, j, at: (i, j, 0) if n_heads > 1 else (i, 0, 0))

    bc_spec = small(heads if per_head else 1, N)
    h_spec = pl.BlockSpec((1, rows, heads, P, N), lambda i, j, at: (at[0], i, j, 0, 0))
    state, y = pl.pallas_call(
        _kernel,
        name="ssd_update",  # the kernel's name in a profile
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B // rows, H // heads),
            in_specs=[small(heads, 1), small(heads, P), bc_spec, bc_spec, h_spec],
            out_specs=[h_spec, small(heads, P)],
        ),
        out_shape=[jax.ShapeDtypeStruct(state.shape, f32), jax.ShapeDtypeStruct((B, H, P), f32)],
        input_output_aliases={5: 0},  # the stack, counted after the prefetched index
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel")),
        interpret=not on_tpu(),
    )(jnp.asarray(layer, jnp.int32).reshape(1), decay, dtx, Bm, Cm, state)
    return y, state
