"""The decode step of a gated power-retention state, in place: one Pallas TPU
kernel that passes over a layer's state once.

A power-retention layer's decode step is ``generate._power_step``: per slot and
kv-head ``S' = gamma S + v (x) phi(k) / HD``, ``z' = gamma z + phi(k) / HD``,
and for each of the kv-head's G query heads ``num = S' phi(q)``, ``den = z' .
phi(q)`` — all float32 and bound by reading and writing ``S`` ([HD, W]: 4.7 MB
a slot and kv-head at HD = 128, W = 9 216; 38 MB a slot and layer). XLA's form
materialises ``phi(q)`` and ``phi(k)`` and passes over the state twice. Here a
program holds ONE slot's one kv-head: it expands ``phi(k)`` and the G
``phi(q)`` in the chip's fast memory — from the 128-wide rows laid out twice
by the caller, each value of a tile repeated ``tile`` times and each tile
repeated ``tile`` times, so that a pair of tiles' ``tile^2`` products are one
elementwise product of two aligned 256-lane slices — then walks the state
eight value rows at a time: forms ``S'`` from the one copy it read, writes it
back to the block it came from and adds its G products into accumulators that
stay in registers. All G query heads are served by that one pass.

The state operands are the serving pool's leaves as stored, the WHOLE stacks
``[L, B, KV, HD, W]`` and ``[L, B, KV, W]``, aliased to the outputs; the layer
index is prefetched to scalar memory and the block index maps pick that
layer's blocks: nothing is sliced out of a stack or pasted back into it. The
normaliser's block is a slot's ``[KV, W]`` (whole register tiles), revisited by
the slot's KV programs, each of which rewrites its own row.

One device's state only: every caller that would shard a recurrent state
refuses the stack by name before it gets here
(``transformer.refuse_recurrent``).

It engages (:func:`engages`) for a float32 stack whose value rows are whole
sublane groups (HD a multiple of 8) and whose pairs of tiles are whole lane
groups (``tile^2`` a multiple of 128), on a TPU; anywhere else the caller keeps
``generate._power_step``, the XLA statement of the same step. Decided from what
the trace sees; no option selects it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tpu_engine import layer_state

# Fast memory a program may use: the state's block four times (in and out, two
# copies in flight each: 19 MB at HD = 128, W = 9 216), the expansions and the
# accumulators (2 MB). The compiler's default allowance is 16 MiB.
_VMEM_LIMIT_BYTES = 48 << 20

# Off the TPU the kernel can only be interpreted, and XLA's form of the step is
# merely slower there, not wrong: the caller keeps ``generate._power_step``
# unless a test asks for the interpreter here.
INTERPRET_OFF_TPU = False

_SUBLANES = 8


def on_tpu() -> bool:
    return jax.devices()[0].platform == "tpu"


def engages(state, tile: int) -> bool:
    """Whether :func:`power_update` runs for this stacked leaf ``[L, B, KV, HD,
    W]`` on this process's devices (see the module docstring)."""
    return (state.ndim == 5 and state.dtype == jnp.float32
            and state.shape[3] % _SUBLANES == 0 and (tile * tile) % 128 == 0
            and state.shape[3] % tile == 0
            and (on_tpu() or INTERPRET_OFF_TPU))


def laid_out_twice(y, tile: int):
    """``y`` [..., HD] float32 -> (R, T), each [..., (HD / tile) x tile^2]:
    within tile a's ``tile^2`` values ``R[i tile + j] = y_a[i]`` and ``T[i tile
    + j] = y_a[j]``, so that the products of tiles a and b are ``R_a * T_b``."""
    tiles = y.astype(jnp.float32).reshape(*y.shape[:-1], y.shape[-1] // tile, tile)
    flat = lambda a: a.reshape(*y.shape[:-1], -1)  # noqa: E731
    return flat(jnp.repeat(tiles, tile, axis=-1)), flat(jnp.tile(tiles, tile))


def _kernel(at_ref, g_ref, v_ref, kr_ref, kt_ref, qr_ref, qt_ref, s_ref, z_ref,
            s_out, z_out, num_ref, den_ref, fk_scr, fq_scr, acc_scr, *, pairs, width, G, HD):
    del at_ref  # the index maps read it
    head = pl.program_id(1)
    g = g_ref[0, 0]                                                # [1, 1]
    row = pl.ds(head, 1)
    # phi(k) and the G phi(q), a pair of tiles at a time; the normaliser's row
    # advanced and read on the way. Each expansion is kept broadcast over a
    # register's eight sublanes, as the walk over the state wants it.
    den = jnp.zeros((G, width), jnp.float32)
    for p, (a, b) in enumerate(pairs):
        c = 1.0 if a == b else 2.0 ** 0.5
        lanes = slice(p * width, (p + 1) * width)
        of = lambda ref, t: ref[0, 0, :, t * width:(t + 1) * width]  # noqa: E731
        fk = of(kr_ref, a) * (of(kt_ref, b) * c)                   # [1, width]
        fq = of(qr_ref, a) * (of(qt_ref, b) * c)                   # [G, width]
        z = z_ref[0, 0, row, lanes] * g + fk
        z_out[0, 0, row, lanes] = z
        den = den + fq * z
        fk_scr[:, lanes] = jnp.broadcast_to(fk, (_SUBLANES, width))
        for i in range(G):
            fq_scr[i, :, lanes] = jnp.broadcast_to(fq[i:i + 1], (_SUBLANES, width))
    den_ref[0, 0] = jnp.sum(den, axis=-1, keepdims=True)

    def value_rows(r, carry):
        rows = pl.ds(pl.multiple_of(r * _SUBLANES, _SUBLANES), _SUBLANES)
        vb = v_ref[0, 0, rows, :]                                  # [8, 1]
        acc = [jnp.zeros((_SUBLANES, 128), jnp.float32)] * G
        for lo in range(0, len(pairs) * width, 128):
            lanes = slice(lo, lo + 128)
            s = s_ref[0, 0, 0, rows, lanes] * g + vb * fk_scr[:, lanes]
            s_out[0, 0, 0, rows, lanes] = s
            acc = [acc[i] + s * fq_scr[i, :, lanes] for i in range(G)]
        for i in range(G):
            acc_scr[i, rows, :] = acc[i]
        return carry

    lax.fori_loop(0, HD // _SUBLANES, value_rows, 0)
    num_ref[0, 0] = jnp.sum(acc_scr[...], axis=-1)                 # [G, HD]


def power_update(q, k, v, log_g, w, state, norm, layer, *, tile: int):
    """One recurrence step of layer ``layer`` of ``state`` / ``norm``, in place.

    q [B, KV, G, HD]; k, v [B, KV, HD]; ``log_g`` [B, KV] float32, the gate's
    log, and ``w`` [B, KV], what the token adds with (1 / HD) — both 0 for a
    row that must keep its state bit for bit (``S * 1 + 0``); state [L, B, KV,
    HD, W] and norm [L, B, KV, W] float32, the whole stacks (donate them: they
    are aliased to the outputs); ``layer`` scalar int32. What
    ``generate._power_step`` computes on ``state[layer]``, ``norm[layer]``: the
    same float32 products, the sums over W in the kernel's order. Returns
    (num [B, KV, G, HD], den [B, KV, G], state, norm).

    Off the TPU (:func:`engages` says when a caller gets here) the kernel is
    interpreted."""
    _, B, KV, HD, W = state.shape
    G = q.shape[2]
    f32 = jnp.float32
    pairs, width = layer_state.power_tile_pairs(HD, tile), tile * tile
    assert W == len(pairs) * width, (W, HD, tile)
    W2 = (HD // tile) * width
    g = jnp.exp(log_g.astype(f32))[:, :, None, None]               # [B, KV, 1, 1]
    vcol = v.astype(f32)[..., None]                                # [B, KV, HD, 1]
    kr, kt = laid_out_twice(k, tile)
    kr, kt = kr[:, :, None, :], (kt * w.astype(f32)[..., None])[:, :, None, :]   # [B, KV, 1, W2]
    qr, qt = laid_out_twice(q, tile)                               # [B, KV, G, W2]

    def small(rows, cols):  # a slot's one kv-head of an operand beside the state
        return pl.BlockSpec((1, 1, rows, cols), lambda b, h, at: (b, h, 0, 0))

    s_spec = pl.BlockSpec((1, 1, 1, HD, W), lambda b, h, at: (at[0], b, h, 0, 0))
    z_spec = pl.BlockSpec((1, 1, KV, W), lambda b, h, at: (at[0], b, 0, 0))
    state, norm, num, den = pl.pallas_call(
        functools.partial(_kernel, pairs=pairs, width=width, G=G, HD=HD),
        name="power_update",  # the kernel's name in a profile
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, KV),
            in_specs=[small(1, 1), small(HD, 1), small(1, W2), small(1, W2),
                      small(G, W2), small(G, W2), s_spec, z_spec],
            out_specs=[s_spec, z_spec, small(G, HD), small(G, 1)],
            scratch_shapes=[pltpu.VMEM((_SUBLANES, W), f32), pltpu.VMEM((G, _SUBLANES, W), f32),
                            pltpu.VMEM((G, HD, 128), f32)],
        ),
        out_shape=[jax.ShapeDtypeStruct(state.shape, f32), jax.ShapeDtypeStruct(norm.shape, f32),
                   jax.ShapeDtypeStruct((B, KV, G, HD), f32), jax.ShapeDtypeStruct((B, KV, G, 1), f32)],
        input_output_aliases={7: 0, 8: 1},  # the stacks, counted after the prefetched index
        compiler_params=pltpu.CompilerParams(
            # a slot's KV programs revisit its normaliser block in turn
            dimension_semantics=("parallel", "arbitrary"), vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=not on_tpu(),
    )(jnp.asarray(layer, jnp.int32).reshape(1), g, vcol, kr, kt, qr, qt, state, norm)
    return num, den[..., 0], state, norm
