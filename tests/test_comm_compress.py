"""ZeRO++ comm compression (``tpu_engine/comm_compress.py``): quantize
round-trip bounds, the compressed train step's loss parity with the fp32
GSPMD path, int8 actually on the wire (compiled-HLO byte accounting), hpZ
store consistency, and the config validators that keep impossible combos
from reaching the SPMD partitioner (which aborts, not raises, on them)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_engine import comm_compress as cc
from tpu_engine.mesh_runtime import MeshConfig, MeshRuntime
from tpu_engine.sharding import (
    OffloadDevice, Precision, ShardingStage, TPUTrainConfig,
)
from tpu_engine.train import build_train_program


# ---------------------------------------------------------------------------
# Quantization numerics
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("block", [16, 64, 256])
def test_roundtrip_error_bound(block):
    """Per-block absmax/127 scales ⇒ round-trip error ≤ half a quantization
    step of the block's own scale — checked per block, not globally (the
    global bound would be weaker than what blocking buys)."""
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 3 * block + 7)) * 5.0
    codes, scales = cc.blockwise_quantize(x, block)
    nb = -(-x.shape[-1] // block)
    assert codes.shape == (4, nb * block) and codes.dtype == jnp.int8
    assert scales.shape == (4, nb) and scales.dtype == jnp.float32
    y = cc.blockwise_dequantize(codes, scales, block, last=x.shape[-1])
    err = np.abs(np.asarray(y - x))
    # err[i, j] ≤ scale_of_block(j)/2  (+eps for the division rounding)
    per_elem_bound = np.repeat(np.asarray(scales), block, axis=-1)[
        :, : x.shape[-1]
    ]
    assert np.all(err <= per_elem_bound / 2 + 1e-6)


def test_roundtrip_exact_on_grid():
    """Values already on the int8 grid survive exactly (scale = absmax/127,
    codes hit integers)."""
    x = jnp.arange(-127, 128, dtype=jnp.float32).reshape(1, 255) * 0.5
    codes, scales = cc.blockwise_quantize(x, 255)
    y = cc.blockwise_dequantize(codes, scales, 255, last=255)
    np.testing.assert_allclose(np.asarray(y), np.asarray(x), rtol=1e-6)


def test_stochastic_rounding_unbiased():
    """floor(v + u) with u~U[0,1) is unbiased: the mean dequantized value
    over many keys converges to the input (nearest rounding would sit a
    deterministic fraction of a step off)."""
    x = jnp.full((1, 64), 0.3)
    deqs = []
    for i in range(300):
        codes, scales = cc.blockwise_quantize(
            x, 64, key=jax.random.PRNGKey(i)
        )
        deqs.append(cc.blockwise_dequantize(codes, scales, 64, last=64))
    mean = float(jnp.mean(jnp.stack(deqs)))
    step = 0.3 / 127  # one quantization step
    assert abs(mean - 0.3) < step / 5, (mean, step)


def test_slice_groups():
    intra, cross = cc.data_slice_groups(4, 2)
    assert intra == [[0, 1], [2, 3]]
    assert cross == [[0, 2], [1, 3]]
    intra1, cross1 = cc.data_slice_groups(4, 4)
    assert intra1 == [[0], [1], [2], [3]]
    assert cross1 == [[0, 1, 2, 3]]
    with pytest.raises(ValueError, match="divisible"):
        cc.data_slice_groups(4, 3)


# ---------------------------------------------------------------------------
# Compressed training: parity + wire bytes (shared compiled programs)
# ---------------------------------------------------------------------------


def _cfg(**kw) -> TPUTrainConfig:
    base = dict(
        model_name="gpt-tiny",
        sharding_stage=ShardingStage.FULL_PARTITIONING,
        mesh=MeshConfig(data=4, fsdp=2, dcn_data=2),
        micro_batch_size=2,
        gradient_accumulation_steps=2,
        seq_len=32,
        precision=Precision.FP32,
        param_dtype=Precision.FP32,
        learning_rate=1e-2,
        warmup_steps=2,
        total_steps=100,
        comm_quant_block_size=64,
    )
    base.update(kw)
    return TPUTrainConfig(**base)


def _hybrid_runtime(cfg) -> MeshRuntime:
    # Two simulated slices over the 8 virtual CPU devices: data indices
    # {0,1} on slice 0, {2,3} on slice 1 (the mesh lays whole slices as
    # outer data blocks).
    return MeshRuntime(cfg.mesh, slice_assignments=[0, 0, 0, 0, 1, 1, 1, 1])


def _run(prog, n, seed=0):
    state = prog.init(jax.random.PRNGKey(prog.config.seed))
    batch = prog.synthetic_batch(seed)  # fixed batch → loss must drop
    losses = []
    for _ in range(n):
        state, metrics = prog.step(state, batch)
        losses.append(float(metrics["loss"]))
    return state, losses


@pytest.fixture(scope="module")
def baseline_run():
    cfg = _cfg()
    prog = build_train_program(cfg, runtime=_hybrid_runtime(cfg))
    state, losses = _run(prog, 6)
    return prog, state, losses


@pytest.fixture(scope="module")
def compressed_run():
    cfg = _cfg(comm_quant_weights=True, comm_secondary_weights=True,
               comm_quant_grads=True)
    prog = build_train_program(cfg, runtime=_hybrid_runtime(cfg))
    state, losses = _run(prog, 6)
    return prog, state, losses


def test_loss_parity(baseline_run, compressed_run):
    """qwZ+hpZ+qgZ training tracks the fp32-comm GSPMD path: same batch,
    same init, |Δloss| within tolerance at every step — and both actually
    train (loss drops)."""
    _, _, base = baseline_run
    _, _, comp = compressed_run
    assert base[-1] < base[0] and comp[-1] < comp[0]
    for b, c in zip(base, comp):
        assert abs(b - c) < 0.05, (base, comp)


def test_qwz_only_loss_parity(baseline_run):
    """qwZ alone (no secondary store, no grad quant) also tracks fp32."""
    cfg = _cfg(comm_quant_weights=True)
    prog = build_train_program(cfg, runtime=_hybrid_runtime(cfg))
    state, losses = _run(prog, 4)
    assert "hpz" not in state
    _, _, base = baseline_run
    for b, c in zip(base, losses):
        assert abs(b - c) < 0.05


def _abstract_step_args(prog, state):
    batch = jax.ShapeDtypeStruct(prog.global_batch_shape(), jnp.int32)
    return jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), state), batch


def test_int8_on_wire_and_cross_slice_reduction(baseline_run, compressed_run):
    """The compressed step must gather int8 across slices (the wire dtype IS
    the operand dtype — a dequant below the gather would move fp32), and
    ring-model byte accounting must show the ≥3x cross-slice reduction the
    subsystem exists for.

    Read from the step as LOWERED, before XLA's passes: the shard_map's
    collectives stand there with their operands and groups, once each and on
    the layer-stacked arrays, whatever the CPU compiler later combines into
    tuples or leaves inside a loop body (the text of the optimised module,
    which this test used to parse, counts a loop's collective once for all
    its trips). The baseline's gradient all-reduce exists only after GSPMD's
    partitioner has run, so it is priced by the same ring model from the
    parameters' shapes: fp32, every device's fsdp shard, over the data axis."""
    base_prog, base_state, _ = baseline_run
    comp_prog, comp_state, _ = compressed_run
    mesh = dict(comp_prog.mesh.shape)
    slice_of = cc.slice_of_partition(mesh, comp_prog.config.mesh.dcn_data)
    assert slice_of == [0, 0, 0, 0, 1, 1, 1, 1]

    lowered = comp_prog.step.lower(*_abstract_step_args(comp_prog, comp_state))
    comp_stats = cc.collective_stats(
        lowered.compiler_ir(dialect="hlo").as_hlo_text(), slice_of)
    crossing = [o for o in comp_stats["collectives"]
                if o["cross_slice"] and o["payload_bytes"] > 4]  # not the loss's scalar psum
    assert crossing and {o["op"] for o in crossing} == {"all-gather"}
    # int8 codes and their fp32 per-block scales, nothing else, and the codes
    # are 64/4 = 16 times the scales' bytes (comm_quant_block_size=64).
    assert {o["dtype"] for o in crossing} == {"s8", "f32"}
    by_dtype = {d: sum(o["payload_bytes"] for o in crossing if o["dtype"] == d) for d in ("s8", "f32")}
    assert by_dtype["s8"] == 16 * by_dtype["f32"]

    n_params = sum(a.size for a in jax.tree.leaves(base_state["params"]))
    g = mesh["data"]
    base_cross = 2 * (g - 1) / g * 4 * n_params / mesh["fsdp"]
    reduction = base_cross / comp_stats["cross_slice_bytes"]
    assert reduction >= 3.0, (base_cross, comp_stats["cross_slice_bytes"])
    # Total wire volume must shrink too, not just move intra-slice: the
    # compiled modules, tuple results counted (a loop body once on both sides).
    compiled = [cc.collective_stats(
        prog.step.lower(*_abstract_step_args(prog, state)).compile().as_text(), slice_of)
        for prog, state in ((base_prog, base_state), (comp_prog, comp_state))]
    assert compiled[0]["cross_slice_bytes"] > compiled[1]["cross_slice_bytes"] > 0
    assert compiled[1]["total_wire_bytes"] < compiled[0]["total_wire_bytes"]


@pytest.mark.parametrize("result, payload", [
    ("f32[4,32]{1,0}", 512),
    ("(f32[4,32,64]{2,1,0}, f32[4,32,64]{2,1,0}, /*index=2*/f32[64]{0})", 2 * 32768 + 256),
    ("s8[2,2,32,128]{3,2,1,0}", 16384),
], ids=["array", "combined-tuple", "int8"])
def test_collective_stats_counts_every_member_of_a_tuple_result(result, payload):
    """XLA combines the gradient all-reduces into one with a tuple result;
    it moved all of the members' bytes, not none."""
    line = (f"  %all-reduce.259 = {result} all-reduce(%bitcast.3, %bitcast.6), channel_id=2, "
            "replica_groups=[2,4]<=[8], use_global_device_ids=true, to_apply=%add.clone")
    stats = cc.collective_stats(line, [0, 0, 0, 0, 1, 1, 1, 1])
    (op,) = stats["collectives"]
    assert op["payload_bytes"] == payload and op["group_size"] == 4 and not op["cross_slice"]
    assert stats["total_wire_bytes"] == int(payload * 2 * 3 / 4) and stats["cross_slice_bytes"] == 0
    crossing = cc.collective_stats(line.replace("[2,4]<=[8]", "[4,2]<=[2,4]T(1,0)"), [0, 0, 0, 0, 1, 1, 1, 1])
    assert crossing["collectives"][0]["group_size"] == 2 and crossing["cross_slice_bytes"] == payload


def test_hpz_store_consistency(compressed_run):
    """The secondary store is exactly blockwise_quantize of the primary
    partition's local shards (refresh ran after the last update), and its
    leaves are int8 codes + fp32 scales sharded like the params."""
    prog, state, _ = compressed_run
    assert "hpz" in state
    block = prog.config.comm_quant_block_size
    codes_tree = state["hpz"]["codes"]
    q_codes = codes_tree["layers"]["q"]["kernel"]
    assert q_codes.dtype == jnp.int8
    # Verify one leaf end-to-end: quantizing the current param shard
    # reproduces the stored codes.
    w = state["params"]["layers"]["q"]["kernel"]
    expect_codes, expect_scales = cc.blockwise_quantize(
        jnp.asarray(w), block
    )
    np.testing.assert_array_equal(
        np.asarray(q_codes), np.asarray(expect_codes)
    )
    np.testing.assert_allclose(
        np.asarray(state["hpz"]["scales"]["layers"]["q"]["kernel"]),
        np.asarray(expect_scales), rtol=1e-6,
    )
    # Norm scales are not quantized — pruned (None) in the secondary store.
    assert codes_tree["final_norm"]["scale"] is None


def test_compressed_on_plain_fsdp_mesh():
    """No dcn axis (single slice): qwZ still works — the data-axis grad
    reduction degenerates to a plain psum and loss still drops."""
    cfg = _cfg(mesh=MeshConfig(data=2, fsdp=4), comm_quant_weights=True)
    prog = build_train_program(cfg)
    _, losses = _run(prog, 6)
    assert losses[-1] < losses[0] * 0.9, losses


# ---------------------------------------------------------------------------
# Config/build-time rejections
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "kw, match",
    [
        (dict(comm_secondary_weights=True), "requires comm_quant_weights"),
        (dict(comm_quant_weights=True,
              sharding_stage=ShardingStage.GRADIENT_PARTITIONING),
         "sharding_stage=3"),
        (dict(comm_quant_grads=True, pipeline_schedule="1f1b"), "1f1b"),
        (dict(comm_quant_weights=True,
              grad_allreduce_dtype=Precision.BF16), "redundant"),
        (dict(comm_quant_weights=True, lora_rank=4), "LoRA"),
        (dict(comm_quant_weights=True,
              param_offload=OffloadDevice.HOST), "param_offload"),
        (dict(comm_quant_weights=True, mesh=MeshConfig(data=2, fsdp=2,
                                                       model=2)), "model=1"),
        (dict(comm_quant_weights=True, attention_impl="flash"), "flash"),
    ],
)
def test_config_rejections(kw, match):
    base = dict(
        model_name="gpt-tiny", sharding_stage=ShardingStage.FULL_PARTITIONING,
        mesh=MeshConfig(data=2, fsdp=4), seq_len=32,
    )
    base.update(kw)
    with pytest.raises(ValueError, match=match):
        TPUTrainConfig(**base)


def test_disk_offload_rejection(tmp_path):
    with pytest.raises(ValueError, match="disk"):
        _cfg(comm_quant_weights=True,
             optimizer_offload=OffloadDevice.DISK,
             optimizer_spill_dir=str(tmp_path))


def test_moe_rejected_at_build():
    cfg = TPUTrainConfig(
        model_name="moe-tiny",
        sharding_stage=ShardingStage.FULL_PARTITIONING,
        mesh=MeshConfig(data=2, fsdp=4), seq_len=32,
        comm_quant_weights=True,
    )
    with pytest.raises(ValueError, match="MoE"):
        build_train_program(cfg)


# ---------------------------------------------------------------------------
# Plan / API surface
# ---------------------------------------------------------------------------


def test_compression_plan():
    from tpu_engine.comm import compression_plan

    off = compression_plan(_cfg())
    assert off["enabled"] is False
    on = compression_plan(
        _cfg(comm_quant_weights=True, comm_quant_grads=True,
             comm_quant_block_size=256)
    )
    assert on["enabled"] is True
    assert on["block_size"] == 256
    # int8 + fp32/256 scales vs fp32 ⇒ 4 / (1 + 4/256) ≈ 3.94x
    assert 3.9 < on["weight_gather_volume_factor"] < 4.0
    assert 3.9 < on["cross_slice_grad_volume_factor"] < 4.0


def test_launcher_plan_includes_compression():
    from tpu_engine.launcher import TPULauncher

    plan = TPULauncher().generate_plan(_cfg(comm_quant_weights=True))
    assert plan["comm_compression"]["quant_weight_gather"] is True


def test_http_launch_request_fields():
    """The launch API accepts the new knobs and surfaces validator
    failures as a 422, not a job-thread crash."""
    from backend.http import ApiError
    from backend.routers.training import TrainingLaunchRequest, _to_config

    req = TrainingLaunchRequest(
        model_name="gpt-tiny", seq_len=32,
        mesh=MeshConfig(data=2, fsdp=4),
        comm_quant_weights=True, comm_quant_grads=True,
        comm_quant_block_size=128,
    )
    cfg = _to_config(req)
    assert cfg.comm_quant_weights and cfg.comm_quant_grads
    assert cfg.comm_quant_block_size == 128

    bad = TrainingLaunchRequest(
        model_name="gpt-tiny", seq_len=32,
        mesh=MeshConfig(data=2, fsdp=4),
        comm_secondary_weights=True,  # hpZ without qwZ
    )
    with pytest.raises(ApiError):
        _to_config(bad)
