"""The one-pass decode update of a recurrent state (``tpu_engine/ops/ssd_update``)
against the plain statement of the step, ``generate._ssd_step``.

The kernel is interpreted here, at shapes of a few ``[8,128]`` / ``[16,128]``
tiles. Both sides are float32 and form the same products; only the order of
the one N-term sum for ``y`` may differ, so ``y`` is held to a few float32
roundings of the sum's terms and the state to one rounding of an entry (XLA on
the CPU contracts the multiply and the add). What the kernel must not change
it must leave bit for bit: a row with ``dt = 0``, every other layer.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from tpu_engine.generate import _ssd_step, _ssd_step_at
from tpu_engine.ops import ssd_update as su

F32 = jnp.float32
L, B, H, N = 3, 4, 16, 128


@pytest.fixture(autouse=True)
def interpreted(monkeypatch):
    """These are the CPU's tests: the kernel is interpreted where a test does
    not say otherwise."""
    monkeypatch.setattr(su, "INTERPRET_OFF_TPU", True)


def _inputs(form: str, P: int, N: int = N, dtype=F32):
    """(x, dt, A, Bm, Cm, state): ``form`` is "mamba2" (B, C shared by the
    heads, dt a softplus) or "lightning" (B = k, C = q per head, dt in {0, 1});
    row 1 does not decode (dt = 0)."""
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    bc = (B, H, N) if form == "lightning" else (B, N)
    x = jax.random.normal(ks[0], (B, H, P), jnp.bfloat16)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, H), F32))
    if form == "lightning":
        dt = jnp.ones((B, H), F32)
    dt = dt.at[1].set(0.0)
    A = -jnp.exp(jax.random.normal(ks[2], (H,), F32))
    Bm = jax.random.normal(ks[3], bc, jnp.bfloat16)
    Cm = jax.random.normal(ks[4], bc, jnp.bfloat16)
    state = jax.random.normal(ks[5], (L, B, H, P, N), F32).astype(dtype)
    return x, dt, A, Bm, Cm, state


def _close(y, h, y_ref, h_ref):
    # y sums 128 terms of size up to ~|h||C|: a few roundings of the largest
    assert float(jnp.max(jnp.abs(y - y_ref))) <= 64 * np.finfo(np.float32).eps * float(jnp.max(jnp.abs(y_ref)))
    assert float(jnp.max(jnp.abs(h - h_ref))) <= 2 * np.finfo(np.float32).eps * float(jnp.max(jnp.abs(h_ref)))


# block_bytes -> (rows, heads) at [4, 16, P, 128]: half a row's heads, a row, two rows, all rows
BLOCKS = {8: {32 << 10: (1, 8), 64 << 10: (1, 16), 128 << 10: (2, 16), 1 << 20: (4, 16)},
          16: {64 << 10: (1, 8), 128 << 10: (1, 16), 256 << 10: (2, 16), 1 << 20: (4, 16)}}


@pytest.mark.parametrize("form", ["mamba2", "lightning"])
@pytest.mark.parametrize("P,block_bytes", [(P, bb) for P, by in BLOCKS.items() for bb in by])
def test_the_kernel_is_the_xla_step_on_its_layer_and_nothing_else(form, P, block_bytes):
    """Both forms, at more than one block and grid: ``y`` and the layer's new
    state to float32 round-off; the row with ``dt = 0`` and every other layer
    of the stack bit for bit what they were."""
    assert su.block_of(B, H, P, N, block_bytes) == BLOCKS[P][block_bytes]
    x, dt, A, Bm, Cm, state = _inputs(form, P)
    y_ref, h_ref = _ssd_step(x, dt, A, Bm, Cm, state[1])
    y, new = su.ssd_update(x, dt, A, Bm, Cm, state, jnp.int32(1), block_bytes=block_bytes)
    _close(y, new[1], y_ref, h_ref)
    assert np.array_equal(np.asarray(new[1, 1]), np.asarray(state[1, 1]))  # dt = 0: h * 1 + 0
    assert np.array_equal(np.asarray(new[0]), np.asarray(state[0]))
    assert np.array_equal(np.asarray(new[2]), np.asarray(state[2]))
    assert not np.array_equal(np.asarray(new[1, 0]), np.asarray(state[1, 0]))


@pytest.mark.parametrize("form", ["mamba2", "lightning"])
def test_the_stack_as_a_donated_scan_carry_two_tokens_deep(form):
    """As ``scan_layers`` holds it: the stack is the carry of a ``lax.scan``
    over the layers under ``jit``, donated, each layer updating its own blocks
    through ``generate._ssd_step_at``; two tokens deep it equals the unrolled
    XLA steps."""
    P = 16
    x, dt, A, Bm, Cm, state = _inputs(form, P)
    xs = jnp.stack([x, -x, x * 0.5])                              # a different input a layer

    def token(state, scale):
        def layer(state, at):
            y, state = _ssd_step_at(xs[at] * scale, dt, A, Bm, Cm, state, at)
            return state, y
        return lax.scan(layer, state, jnp.arange(L, dtype=jnp.int32))

    @jax.jit
    def two_tokens(state):
        state, y1 = token(state, 1.0)
        state, y2 = token(state, -2.0)
        return y1, y2, state

    assert "pallas_call" in str(jax.make_jaxpr(two_tokens)(state))
    want_y, h = [], [state[i] for i in range(L)]
    for scale in (1.0, -2.0):
        ys = []
        for i in range(L):
            y, h[i] = _ssd_step((xs[i] * scale).astype(xs.dtype), dt, A, Bm, Cm, h[i])
            ys.append(y)
        want_y.append(jnp.stack(ys))
    y1, y2, new = jax.jit(two_tokens, donate_argnums=(0,))(state + 0.0)
    _close(y1, new, want_y[0], jnp.stack(h))
    _close(y2, new, want_y[1], jnp.stack(h))
    assert np.array_equal(np.asarray(new[:, 1]), np.asarray(state[:, 1]))  # the row that does not decode


@pytest.mark.parametrize("what", ["bf16_leaf", "n_not_128s", "p_not_8s", "off_tpu_unasked"])
def test_what_the_kernel_cannot_tile_takes_the_xla_step(what, monkeypatch):
    """A leaf in another dtype, a ``[P,N]`` tile that is no whole register, a
    process off the TPU that did not ask for the interpreter: the trace keeps
    ``_ssd_step`` on the layer's slice, and the result is its result."""
    if what == "off_tpu_unasked":
        monkeypatch.setattr(su, "INTERPRET_OFF_TPU", False)
    x, dt, A, Bm, Cm, state = _inputs("mamba2", P=12 if what == "p_not_8s" else 16,
                                      N=64 if what == "n_not_128s" else N,
                                      dtype=jnp.bfloat16 if what == "bf16_leaf" else F32)
    assert not su.engages(state)
    step = lambda state: _ssd_step_at(x, dt, A, Bm, Cm, state, jnp.int32(2))  # noqa: E731
    assert "pallas_call" not in str(jax.make_jaxpr(step)(state))
    y, new = step(state)
    y_ref, h_ref = _ssd_step(x, dt, A, Bm, Cm, state[2].astype(F32))
    assert new.dtype == state.dtype and np.array_equal(np.asarray(y), np.asarray(y_ref))
    assert np.array_equal(np.asarray(new[2]), np.asarray(h_ref.astype(state.dtype)))
    assert np.array_equal(np.asarray(new[:2]), np.asarray(state[:2]))


def test_a_float32_stack_of_whole_tiles_engages_only_where_asked_off_the_tpu(monkeypatch):
    state = jax.ShapeDtypeStruct((9, 32, 128, 64, 128), F32)
    assert su.engages(state)
    monkeypatch.setattr(su, "INTERPRET_OFF_TPU", False)
    assert not su.engages(state)
    monkeypatch.setattr(su, "on_tpu", lambda: True)
    assert su.engages(state)
    assert not su.engages(jax.ShapeDtypeStruct((32, 128, 64, 128), F32))  # one layer's slice is not the stack


@pytest.mark.parametrize("shape,block_bytes,want", [
    # the three recurrent cells' leaves at the default: 2 MiB of one row
    ((32, 64, 64, 128), None, (1, 64)),      # granite-4.0-h-micro: all 64 heads of a row
    ((32, 128, 64, 128), None, (1, 64)),     # granite-4.0-h-small: half of a row's 128
    ((16, 32, 128, 128), None, (1, 32)),     # minicpm-sala's lightning layers: all 32
    ((32, 64, 64, 128), 8 << 20, (4, 64)),   # room for more: whole rows, a divisor of B
    ((6, 64, 64, 128), 8 << 20, (3, 64)),
    ((32, 64, 64, 128), 1 << 20, (1, 32)),
    ((32, 24, 64, 128), 1 << 20, (1, 24)),   # heads in eights that divide H, or all of them
    ((32, 24, 64, 128), 1 << 19, (1, 8)),
    ((32, 12, 64, 128), 1 << 10, (1, 12)),   # nothing fits: the fewest that may be held
    ((32, 64, 64, 128), 1 << 10, (1, 8)),
])
def test_the_block_a_program_holds_follows_the_shapes(shape, block_bytes, want):
    got = su.block_of(*shape) if block_bytes is None else su.block_of(*shape, block_bytes)
    assert got == want
    rows, heads = got
    assert shape[0] % rows == 0 and shape[1] % heads == 0 and (heads % 8 == 0 or heads == shape[1])
