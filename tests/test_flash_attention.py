"""Pallas flash attention: forward + backward vs XLA reference (interpret
mode on the CPU test mesh exercises the real kernel logic). The
compile-heavy parity tests are marked slow (excluded from the fast core
run, pytest -m "not slow"); tiling and dispatch rules run in it."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_engine.ops.flash_attention import mha
from tpu_engine.ops._flash_pallas import FlashUnsupported, _pick_block, flash_mha


def _rand_qkv(key, B=2, S=128, H=4, KV=4, D=64, dtype=jnp.float32):
    kq, kk, kv = jax.random.split(key, 3)
    return (
        jax.random.normal(kq, (B, S, H, D), dtype),
        jax.random.normal(kk, (B, S, KV, D), dtype),
        jax.random.normal(kv, (B, S, KV, D), dtype),
    )


def test_block_picker():
    """Tiles are 128-multiples or the whole sequence: Mosaic refuses a
    (1, 1, 64) lse block of a longer array (seen on the chip, PR 21)."""
    assert _pick_block(4096) == 1024
    assert _pick_block(1024) == 512
    assert _pick_block(256) == 128
    assert _pick_block(128) == 128  # one tile spanning the sequence
    assert _pick_block(64) == 64    # likewise
    assert _pick_block(192) == 0
    assert _pick_block(100) == 0


@pytest.mark.slow
@pytest.mark.parametrize("S", [64, 128, 256])
def test_flash_forward_matches_xla(S):
    q, k, v = _rand_qkv(jax.random.PRNGKey(0), S=S)
    ref = mha(q, k, v, force_xla=True)
    out = flash_mha(q, k, v, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


@pytest.mark.slow
def test_flash_gqa_forward():
    q, k, v = _rand_qkv(jax.random.PRNGKey(1), H=8, KV=2)
    ref = mha(q, k, v, force_xla=True)
    out = flash_mha(q, k, v, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


# S=2048 exercises the backward's bb=min(block, 512) re-tiling (block=1024)
# and the >2-block DMA-clamp index maps; smaller B/H keep interpret mode fast.
@pytest.mark.slow
@pytest.mark.parametrize("S,B,H", [(128, 2, 4), (512, 2, 4), (2048, 1, 2)])
def test_flash_backward_matches_xla(S, B, H):
    q, k, v = _rand_qkv(jax.random.PRNGKey(2), S=S, B=B, H=H, KV=H)

    def loss_flash(q, k, v):
        return jnp.sum(flash_mha(q, k, v, interpret=True) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(mha(q, k, v, force_xla=True) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-4, rtol=5e-4)


@pytest.mark.slow
def test_flash_backward_bf16():
    """bf16 is the training dtype: gradients must come back bf16 and agree
    with the XLA path at bf16 tolerances."""
    q, k, v = _rand_qkv(jax.random.PRNGKey(3), S=128, dtype=jnp.bfloat16)

    def loss_flash(q, k, v):
        return jnp.sum(flash_mha(q, k, v, interpret=True).astype(jnp.float32) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(mha(q, k, v, force_xla=True).astype(jnp.float32) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        assert a.dtype == jnp.bfloat16
        np.testing.assert_allclose(
            np.asarray(a, dtype=np.float32), np.asarray(b, dtype=np.float32),
            atol=0.15, rtol=0.1)


def test_unsupported_shape_raises_and_nothing_substitutes_xla():
    q, k, v = _rand_qkv(jax.random.PRNGKey(3), S=100)
    with pytest.raises(FlashUnsupported, match="seq_len=100"):
        flash_mha(q, k, v, interpret=True)
    # The entry point no longer turns that into XLA attention.
    with pytest.raises(FlashUnsupported):
        mha(q, k, v, interpret=True)


def _flash_cfg(**kw):
    from tpu_engine.mesh_runtime import MeshConfig
    from tpu_engine.sharding import ShardingStage, TPUTrainConfig

    base = dict(
        model_name="gpt-tiny", sharding_stage=ShardingStage.FULL_PARTITIONING,
        mesh=MeshConfig(data=2, fsdp=2, model=2), micro_batch_size=2,
        seq_len=128, precision="fp32", attention_impl="flash",
    )
    base.update(kw)
    return TPUTrainConfig(**base)


def test_explicit_flash_on_untileable_seq_len_is_a_build_error():
    """A resolved "flash" the kernel would decline is an error naming the
    shape — at build, not a silent switch to XLA attention at trace."""
    from tpu_engine.train import build_train_program

    with pytest.raises(ValueError, match="seq_len=100"):
        build_train_program(_flash_cfg(seq_len=100))


def test_explicit_flash_with_heads_not_dividing_model_axis_is_a_build_error():
    from tpu_engine.mesh_runtime import MeshConfig
    from tpu_engine.models import transformer as tfm
    from tpu_engine.train import build_train_program

    gqa = tfm.MODEL_CONFIGS["gpt-tiny"].with_(n_kv_heads=1)  # 1 kv head, model=2
    with pytest.raises(ValueError, match="'model' mesh axis"):
        build_train_program(_flash_cfg(), model_cfg=gqa)
    # The dispatch itself refuses too (an explicit request that bypassed
    # the build-time resolution), instead of returning XLA attention.
    from tpu_engine.mesh_runtime import build_mesh

    mesh = build_mesh(MeshConfig(data=4, model=2))
    q, k, v = _rand_qkv(jax.random.PRNGKey(8), B=4, H=4, KV=1)
    with pytest.raises(ValueError, match="'model' mesh axis"):
        tfm._attention(q, k, v, "flash", mesh=mesh)


def test_auto_reports_what_the_compiled_step_holds():
    """"auto" off-TPU is XLA attention, and the program says so; an
    explicit "flash" on the CPU mesh is the kernel in interpret mode — the
    lowered step holds pallas calls, not an XLA stand-in."""
    from tpu_engine.train import build_train_program

    auto = build_train_program(_flash_cfg(attention_impl="auto"))
    assert auto.model_config.attention_impl == "xla"
    flash = build_train_program(_flash_cfg())
    assert flash.model_config.attention_impl == "flash"

    def lowered(prog):
        state = jax.eval_shape(prog.init, jax.random.PRNGKey(0))
        batch = jax.ShapeDtypeStruct(prog.global_batch_shape(), jnp.int32)
        return prog.step.lower(state, batch).as_text()

    # The kernel runs in a manual (shard_map) region; XLA attention has none.
    assert "sdy.manual_computation" in lowered(flash)
    assert "sdy.manual_computation" not in lowered(auto)


@pytest.mark.slow
def test_flash_under_jit_bf16():
    q, k, v = _rand_qkv(jax.random.PRNGKey(4), S=128, dtype=jnp.bfloat16)
    out = jax.jit(lambda q, k, v: flash_mha(q, k, v, interpret=True))(q, k, v)
    ref = mha(q, k, v, force_xla=True)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=3e-2, rtol=3e-2
    )


# ---------------------------------------------------------------------------
# Sliding-window attention
# ---------------------------------------------------------------------------


@pytest.mark.slow
@pytest.mark.parametrize("S,W", [(128, 32), (128, 64), (256, 100), (256, 65)])
def test_flash_window_forward_matches_xla(S, W):
    """Windowed flash vs the XLA mask, incl. non-block-aligned windows."""
    q, k, v = _rand_qkv(jax.random.PRNGKey(4), S=S)
    ref = mha(q, k, v, force_xla=True, window=W)
    out = flash_mha(q, k, v, interpret=True, window=W)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


@pytest.mark.slow
def test_flash_window_ge_seq_is_plain_causal():
    q, k, v = _rand_qkv(jax.random.PRNGKey(5), S=128)
    full = flash_mha(q, k, v, interpret=True)
    windowed = flash_mha(q, k, v, interpret=True, window=128)
    np.testing.assert_allclose(np.asarray(windowed), np.asarray(full), atol=0, rtol=0)


@pytest.mark.slow
@pytest.mark.parametrize("S,W", [(128, 32), (256, 100)])
def test_flash_window_backward_matches_xla(S, W):
    q, k, v = _rand_qkv(jax.random.PRNGKey(6), S=S)

    def loss_flash(q, k, v):
        return jnp.sum(flash_mha(q, k, v, interpret=True, window=W) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(mha(q, k, v, force_xla=True, window=W) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-4, rtol=5e-4)


def test_xla_window_mask_semantics():
    """Each query sees exactly the trailing W keys (inclusive of itself)."""
    S, W = 8, 3
    q = jnp.zeros((1, S, 1, 64), jnp.float32)
    # v rows are one-hot position markers; uniform scores => output averages
    # exactly the visible rows.
    k = jnp.zeros((1, S, 1, 64), jnp.float32)
    v = jnp.eye(S, 64)[None, :, None, :]
    out = mha(q, k, v, force_xla=True, window=W)[0, :, 0, :]
    for t in range(S):
        lo = max(0, t - W + 1)
        expect = np.zeros(64)
        expect[lo:t + 1] = 1.0 / (t - lo + 1)
        np.testing.assert_allclose(np.asarray(out[t]), expect, atol=1e-6)


def test_window_validation():
    q, k, v = _rand_qkv(jax.random.PRNGKey(7), S=64)
    with pytest.raises(ValueError, match="causal"):
        mha(q, k, v, causal=False, window=16)
    with pytest.raises(ValueError, match=">= 0"):
        mha(q, k, v, force_xla=True, window=-1)


def test_window_narrows_inner_grid():
    """The windowed kernels shrink the grid itself — O(S·W) programs, not
    O(S²) programs with skipped bodies."""
    from tpu_engine.ops._flash_pallas import _n_kv_blocks, _n_q_blocks

    # mistral-7b shapes: S=32768, block 512 (bwd), W=4096
    assert _n_kv_blocks(64, 512, 4096) == 9   # vs 64 unwindowed
    assert _n_q_blocks(64, 512, 4096) == 9
    # window inside one block
    assert _n_kv_blocks(8, 64, 1) == 1
    assert _n_kv_blocks(8, 64, 64) == 2
    # no window: full inner dim
    assert _n_kv_blocks(8, 64, 0) == 8 and _n_q_blocks(8, 64, 0) == 8


@pytest.mark.slow
def test_flash_under_shard_map_matches_xla_on_mesh():
    """Mosaic calls cannot be GSPMD-partitioned: on a multi-device mesh the
    train program wraps the flash kernel in shard_map (batch over
    data/fsdp, heads over model). The full sharded train step must match
    the XLA-attention step bit-for-bit-close."""
    import jax

    from tpu_engine.mesh_runtime import MeshConfig
    from tpu_engine.sharding import ShardingStage, TPUTrainConfig
    from tpu_engine.train import build_train_program

    def step_loss(impl):
        cfg = TPUTrainConfig(
            model_name="gpt-tiny",
            sharding_stage=ShardingStage.FULL_PARTITIONING,
            mesh=MeshConfig(data=2, fsdp=2, model=2),
            micro_batch_size=2, seq_len=128, precision="fp32",
            attention_impl=impl, activation_checkpointing=True,
        )
        prog = build_train_program(cfg)
        state = prog.init(jax.random.PRNGKey(0))
        state, m = prog.step(state, prog.synthetic_batch(0))
        return float(m["loss"]), float(m["grad_norm"])

    flash = step_loss("flash")
    xla = step_loss("xla")
    assert flash == pytest.approx(xla, rel=1e-5)
