"""AQT-style int8 quantized training (``tpu_engine/quant_train.py``):
quantizer numerics (round-trip bound, stochastic-rounding unbiasedness),
einsum/gradient correctness of the custom_vjp primitive, CPU loss parity
of the end-to-end quantized train step vs the full-precision path,
composition with the ZeRO++ comm compression, and the config interaction
matrix that rejects unsupported combos with actionable errors."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_engine import quant_train as qt
from tpu_engine.mesh_runtime import MeshConfig, MeshRuntime
from tpu_engine.sharding import Precision, ShardingStage, TPUTrainConfig
from tpu_engine.train import build_train_program


# ---------------------------------------------------------------------------
# Quantizer numerics
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "shape, axes",
    [((8, 33), (0,)), ((8, 33), (1,)), ((4, 6, 10), (2,)), ((4, 6, 10), (1, 2))],
)
def test_channel_roundtrip_error_bound(shape, axes):
    """absmax/127 per-channel scales ⇒ round-trip error ≤ half a
    quantization step of the element's own channel scale."""
    x = jax.random.normal(jax.random.PRNGKey(0), shape) * 3.0
    codes, scales = qt.channel_quantize(x, axes)
    assert codes.dtype == jnp.int8 and codes.shape == shape
    # keepdims scales: size 1 exactly on the contraction axes.
    assert all(
        scales.shape[d] == (1 if d in axes else shape[d])
        for d in range(len(shape))
    )
    deq = codes.astype(jnp.float32) * scales
    err = np.abs(np.asarray(deq - x))
    bound = np.broadcast_to(np.asarray(scales) / 2 + 1e-6, shape)
    assert np.all(err <= bound)


def test_channel_roundtrip_exact_on_grid():
    x = jnp.arange(-127, 128, dtype=jnp.float32).reshape(1, 255) * 0.25
    codes, scales = qt.channel_quantize(x, (1,))
    np.testing.assert_allclose(
        np.asarray(codes.astype(jnp.float32) * scales), np.asarray(x),
        rtol=1e-6,
    )


def test_stochastic_rounding_unbiased():
    """Mean dequantized value over many independent draws converges to the
    input (nearest rounding would sit a deterministic fraction of a step
    off). Exercises the explicit-key path; the in-training path derives
    its key from the operand data instead."""
    x = jnp.full((1, 64), 0.3)
    deqs = []
    for i in range(300):
        codes, scales = qt.channel_quantize(x, (1,), key=jax.random.PRNGKey(i))
        deqs.append(codes.astype(jnp.float32) * scales)
    mean = float(jnp.mean(jnp.stack(deqs)))
    step = 0.3 / 127
    assert abs(mean - 0.3) < step / 5, (mean, step)


def test_data_derived_key_decorrelates():
    """The data-derived stochastic rounding is deterministic for the same
    operand and decorrelated across different operands — the property the
    scanned-layer backward relies on (same trace, different data)."""
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 64))
    c1, _ = qt.channel_quantize(x, (1,), stochastic=True)
    c2, _ = qt.channel_quantize(x, (1,), stochastic=True)
    np.testing.assert_array_equal(np.asarray(c1), np.asarray(c2))
    c3, _ = qt.channel_quantize(x * 1.0001, (1,), stochastic=True)
    assert np.any(np.asarray(c1) != np.asarray(c3))


# ---------------------------------------------------------------------------
# int8_einsum: forward accuracy + custom_vjp gradients
# ---------------------------------------------------------------------------

SPECS = [
    ("bsi,io->bso", (2, 8, 16), (16, 32)),     # projections
    ("ebcd,edf->ebcf", (3, 2, 8, 16), (3, 16, 32)),  # MoE gate/up
    ("ebcf,efd->ebcd", (3, 2, 8, 32), (3, 32, 16)),  # MoE down
]


@pytest.mark.parametrize("spec, lshape, rshape", SPECS)
def test_int8_einsum_forward_accuracy(spec, lshape, rshape):
    lhs = jax.random.normal(jax.random.PRNGKey(0), lshape)
    rhs = jax.random.normal(jax.random.PRNGKey(1), rshape)
    out = qt.int8_einsum(spec, lhs, rhs)
    ref = jnp.einsum(spec, lhs, rhs)
    assert out.shape == ref.shape
    rel = float(jnp.max(jnp.abs(out - ref)) / jnp.max(jnp.abs(ref)))
    assert rel < 0.02, rel


@pytest.mark.parametrize("spec, lshape, rshape", SPECS)
def test_int8_einsum_gradients_track_full_precision(spec, lshape, rshape):
    """The straight-through backward's gradients stay aligned with the
    exact full-precision gradients (cosine similarity): the transpose
    specs are derived correctly and the stochastic backward quantization
    is a small perturbation, not a direction change."""
    lhs = jax.random.normal(jax.random.PRNGKey(2), lshape)
    rhs = jax.random.normal(jax.random.PRNGKey(3), rshape)

    def loss(fn):
        return jax.grad(
            lambda a, b: jnp.sum(fn(spec, a, b) ** 2), argnums=(0, 1)
        )(lhs, rhs)

    (ga, gb), (fa, fb) = loss(qt.int8_einsum), loss(jnp.einsum)
    for g, f in ((ga, fa), (gb, fb)):
        g, f = np.asarray(g).ravel(), np.asarray(f).ravel()
        cos = g @ f / (np.linalg.norm(g) * np.linalg.norm(f))
        assert cos > 0.999, cos


def test_int8_einsum_under_jit_and_dtype():
    h = jax.random.normal(jax.random.PRNGKey(0), (2, 4, 8), jnp.bfloat16)
    w = jax.random.normal(jax.random.PRNGKey(1), (8, 6), jnp.bfloat16)
    out = jax.jit(lambda a, b: qt.int8_einsum("bsi,io->bso", a, b))(h, w)
    assert out.dtype == jnp.bfloat16 and out.shape == (2, 4, 6)
    g = jax.jit(jax.grad(
        lambda a: jnp.sum(qt.int8_einsum("bsi,io->bso", a, w)
                          .astype(jnp.float32))
    ))(h)
    assert g.dtype == h.dtype and g.shape == h.shape


def test_transpose_specs():
    assert qt._transpose_specs("bsi,io->bso") == ("bso,io->bsi", "bsi,bso->io")
    assert qt._transpose_specs("ebcd,edf->ebcf") == (
        "ebcf,edf->ebcd", "ebcd,ebcf->edf",
    )
    assert qt._contraction_axes("ebcd,edf->ebcf") == ((3,), (1,))


# ---------------------------------------------------------------------------
# End-to-end loss parity (CPU, single device)
# ---------------------------------------------------------------------------


def _cfg(**kw) -> TPUTrainConfig:
    base = dict(
        model_name="gpt-tiny",
        sharding_stage=ShardingStage.DISABLED,
        mesh=MeshConfig(data=8),
        micro_batch_size=2,
        seq_len=32,
        precision=Precision.FP32,
        param_dtype=Precision.FP32,
        # Sub-chaotic lr: parity measures per-step quantization error,
        # not trajectory divergence.
        learning_rate=1e-3,
        warmup_steps=2,
        total_steps=100,
        activation_checkpointing=False,
    )
    base.update(kw)
    return TPUTrainConfig(**base)


def _run(prog, n, seed=0):
    state = prog.init(jax.random.PRNGKey(prog.config.seed))
    batch = prog.synthetic_batch(seed)  # fixed batch → loss must drop
    losses = []
    for _ in range(n):
        state, metrics = prog.step(state, batch)
        losses.append(float(metrics["loss"]))
    return state, losses


@pytest.fixture(scope="module")
def parity_runs():
    runs = {}
    for quant in ("none", "int8"):
        prog = build_train_program(_cfg(quant_training=quant))
        runs[quant] = _run(prog, 9)[1]
    return runs


def test_loss_parity_8_steps(parity_runs):
    """int8 quantized training tracks the fp32 path: same seed, same
    batch, |Δloss| ≤ 0.01 at every one of ≥8 steps — and both actually
    train (the acceptance bar of ISSUE 2)."""
    base, q = parity_runs["none"], parity_runs["int8"]
    assert len(base) >= 8
    assert base[-1] < base[0] and q[-1] < q[0]
    for b, c in zip(base, q):
        assert abs(b - c) <= 0.01, (base, q)


def test_quantized_step_changes_logits(parity_runs):
    """The quantized path is actually active, not a silent no-op: the two
    trajectories must differ at some step (quantization error is small
    but nonzero)."""
    base, q = parity_runs["none"], parity_runs["int8"]
    assert any(b != c for b, c in zip(base, q)), (base, q)


def test_parity_moe_model():
    """MoE expert einsums ride the hook too — parity on moe-tiny."""
    runs = {}
    for quant in ("none", "int8"):
        prog = build_train_program(
            _cfg(model_name="moe-tiny", quant_training=quant)
        )
        runs[quant] = _run(prog, 8)[1]
    base, q = runs["none"], runs["int8"]
    assert base[-1] < base[0] and q[-1] < q[0]
    for b, c in zip(base, q):
        assert abs(b - c) <= 0.05, (base, q)


def test_targets_subset_only_quantizes_selected():
    """quant_train_targets=('mlp',) still trains and still perturbs the
    trajectory (the MLP hook is live even with attn excluded)."""
    prog = build_train_program(
        _cfg(quant_training="int8", quant_train_targets=("mlp",))
    )
    assert prog.model_config.quant_train_targets == ("mlp",)
    _, losses = _run(prog, 6)
    assert losses[-1] < losses[0]


def test_composes_with_comm_compression():
    """Wire quantization (ZeRO++ qwZ) and MXU quantization are orthogonal
    and compose: the int8 einsum is plain jnp inside the full-manual
    shard_map region. Loss must still track the uncompressed bf16 path."""
    kw = dict(
        sharding_stage=ShardingStage.FULL_PARTITIONING,
        mesh=MeshConfig(data=4, fsdp=2, dcn_data=2),
        gradient_accumulation_steps=2,
        comm_quant_weights=True,
        comm_quant_grads=True,
        comm_quant_block_size=64,
    )
    runtime_kw = dict(slice_assignments=[0, 0, 0, 0, 1, 1, 1, 1])
    runs = {}
    for quant in ("none", "int8"):
        cfg = _cfg(quant_training=quant, **kw)
        prog = build_train_program(
            cfg, runtime=MeshRuntime(cfg.mesh, **runtime_kw)
        )
        runs[quant] = _run(prog, 6)[1]
    base, q = runs["none"], runs["int8"]
    assert base[-1] < base[0] and q[-1] < q[0]
    for b, c in zip(base, q):
        assert abs(b - c) <= 0.02, (base, q)


def test_gpipe_pipeline_composes():
    """Autodiff differentiates through the custom_vjp inside the gpipe
    stage scan; 'auto' must resolve AWAY from 1f1b under quantization."""
    cfg = _cfg(
        model_name="gpt-tiny",
        sharding_stage=ShardingStage.FULL_PARTITIONING,
        mesh=MeshConfig(data=2, pipe=2, fsdp=2),
        gradient_accumulation_steps=4,  # would auto-pick 1f1b unquantized
        quant_training="int8",
        pipeline_schedule="auto",
    )
    prog = build_train_program(cfg)
    assert prog.pipeline_schedule == "gpipe"
    _, losses = _run(prog, 6)
    assert losses[-1] < losses[0], losses


# ---------------------------------------------------------------------------
# Config interaction matrix
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "kw, match",
    [
        (dict(quant_training="int8", lora_rank=4), "LoRA"),
        (dict(quant_training="int8", pipeline_schedule="1f1b"), "1f1b"),
        (dict(quant_training="int8", moe_impl="ragged"), "ragged"),
        (dict(quant_training="int8", quant_train_targets=()), "no-op"),
        (dict(quant_train_targets=("attn", "bogus")), "unknown quant_train_targets"),
    ],
)
def test_config_rejections(kw, match):
    base = dict(model_name="gpt-tiny", seq_len=32, mesh=MeshConfig(data=8))
    base.update(kw)
    with pytest.raises(ValueError, match=match):
        TPUTrainConfig(**base)


def test_comm_flags_compose_at_config_level():
    """The PR-1 interaction matrix: every comm_quant_* mechanism composes
    with quant_training (wire vs MXU — orthogonal)."""
    cfg = TPUTrainConfig(
        model_name="gpt-tiny", seq_len=32,
        sharding_stage=ShardingStage.FULL_PARTITIONING,
        mesh=MeshConfig(data=2, fsdp=4),
        quant_training="int8",
        comm_quant_weights=True, comm_secondary_weights=True,
        comm_quant_grads=True,
    )
    assert cfg.quant_training == "int8" and cfg.comm_quant_weights


def test_ragged_without_moe_target_composes():
    cfg = TPUTrainConfig(
        model_name="moe-tiny", seq_len=32, mesh=MeshConfig(data=8),
        quant_training="int8", moe_impl="ragged",
        quant_train_targets=("attn", "mlp"),
    )
    assert cfg.moe_impl == "ragged"


def test_ragged_model_preset_rejected_at_build():
    """cfg.moe_impl=None + a model preset carrying ragged must still be
    rejected — at build, on the RESOLVED model config."""
    from tpu_engine.models import transformer as tfm

    cfg = _cfg(model_name="moe-tiny", quant_training="int8")
    ragged_model = tfm.MODEL_CONFIGS["moe-tiny"].with_(moe_impl="ragged")
    with pytest.raises(ValueError, match="ragged"):
        build_train_program(cfg, model_cfg=ragged_model)


def test_off_by_default():
    cfg = TPUTrainConfig(model_name="gpt-tiny", mesh=MeshConfig(data=8))
    assert cfg.quant_training == "none"
    assert qt.enabled(cfg) is False
    prog = build_train_program(cfg)
    assert prog.model_config.quant_training == "none"


# ---------------------------------------------------------------------------
# Plan / API surface
# ---------------------------------------------------------------------------


def test_training_plan():
    off = qt.training_plan(_cfg())
    assert off["enabled"] is False and off["mode"] == "none"
    on = qt.training_plan(_cfg(quant_training="int8",
                               quant_train_targets=("attn", "mlp")))
    assert on["enabled"] is True
    assert on["targets"] == ["attn", "mlp"]
    assert "mfu_note" in on and "roofline" in on["mfu_note"]


def test_launcher_plan_includes_quant_training():
    from tpu_engine.launcher import TPULauncher

    plan = TPULauncher().generate_plan(_cfg(quant_training="int8"))
    assert plan["quant_training"]["enabled"] is True
    assert plan["quant_training"]["mode"] == "int8"
    off = TPULauncher().generate_plan(_cfg())
    assert off["quant_training"]["enabled"] is False


def test_http_launch_request_fields():
    """The launch API accepts the new knobs, maps them onto the config,
    and surfaces validator failures as a 422, not a job-thread crash."""
    from backend.http import ApiError
    from backend.routers.training import TrainingLaunchRequest, _to_config

    req = TrainingLaunchRequest(
        model_name="gpt-tiny", seq_len=32, mesh=MeshConfig(data=8),
        sharding_stage=0,
        quant_training="int8", quant_train_targets=["attn", "mlp"],
    )
    cfg = _to_config(req)
    assert cfg.quant_training == "int8"
    assert cfg.quant_train_targets == ("attn", "mlp")

    bad = TrainingLaunchRequest(
        model_name="gpt-tiny", seq_len=32, mesh=MeshConfig(data=8),
        sharding_stage=0, quant_training="int8", lora_rank=4,
    )
    with pytest.raises(ApiError):
        _to_config(bad)
