"""Sharding stages: ZeRO semantics → PartitionSpecs, configs, presets."""

import pytest
from jax.sharding import PartitionSpec as P

from tpu_engine.mesh_runtime import MeshConfig
from tpu_engine.models import transformer as tfm
from tpu_engine.sharding import (
    OffloadDevice,
    ShardingStage,
    TPUTrainConfig,
    grad_pspecs,
    logical_to_mesh_axes,
    opt_state_pspecs,
    param_pspecs,
    presets,
)

LG_2D = ("embed", "heads")  # e.g. an attention projection


def test_tp_axes_always_sharded():
    for fsdp in (False, True):
        spec = logical_to_mesh_axes(LG_2D, shard_fsdp=fsdp)
        assert spec[-1] == "model" or (len(spec) > 1 and spec[1] == "model")


def test_stage_semantics_on_representative_param():
    logical = {"w": LG_2D}
    # Stage 0: params/grads/opt all replicated on fsdp (TP still applies).
    assert param_pspecs(logical, ShardingStage.DISABLED)["w"] == P(None, "model")
    assert grad_pspecs(logical, ShardingStage.DISABLED)["w"] == P(None, "model")
    assert opt_state_pspecs(logical, ShardingStage.DISABLED)["w"] == P(None, "model")
    # Stage 1: only optimizer state is fsdp-sharded.
    assert param_pspecs(logical, ShardingStage.OPTIMIZER_STATE)["w"] == P(None, "model")
    assert grad_pspecs(logical, ShardingStage.OPTIMIZER_STATE)["w"] == P(None, "model")
    assert opt_state_pspecs(logical, ShardingStage.OPTIMIZER_STATE)["w"] == P("fsdp", "model")
    # Stage 2: + gradients reduce-scattered.
    assert grad_pspecs(logical, ShardingStage.GRADIENT_PARTITIONING)["w"] == P("fsdp", "model")
    assert param_pspecs(logical, ShardingStage.GRADIENT_PARTITIONING)["w"] == P(None, "model")
    # Stage 3: full FSDP.
    assert param_pspecs(logical, ShardingStage.FULL_PARTITIONING)["w"] == P("fsdp", "model")


def test_norm_scales_replicate_without_fsdp():
    spec = logical_to_mesh_axes(("embed",), shard_fsdp=False)
    assert spec == P()


def test_model_logical_tree_matches_param_tree():
    import jax

    cfg = tfm.MODEL_CONFIGS["gpt-tiny"]
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    logical = tfm.logical_axes(cfg)
    jax.tree.map(
        lambda p, lg: None if len(p.shape) == len(lg) else pytest.fail(f"{p.shape} vs {lg}"),
        params,
        logical,
        is_leaf=lambda x: isinstance(x, tuple) and all(isinstance(s, (str, type(None))) for s in x),
    )


def test_effective_batch_math():
    cfg = TPUTrainConfig(
        micro_batch_size=2,
        gradient_accumulation_steps=16,
        mesh=MeshConfig(data=1, fsdp=4),
    )
    # micro × accum × dp-world — reference deepspeed_launcher.py:323-328.
    assert cfg.effective_batch_size == 2 * 16 * 4
    # data=-1 resolves against the visible 8-device mesh (data=8, fsdp=1).
    inferred = TPUTrainConfig(micro_batch_size=8, gradient_accumulation_steps=1)
    assert inferred.effective_batch_size == 8 * 8


def test_presets_cover_reference_scales():
    p = presets()
    assert {"125m", "7b", "13b", "70b"} <= set(p)
    # Effective batch sizes match the reference's presets
    # (deepspeed_launcher.py:369-407: 128 / 256 / 1024); mesh shapes are
    # re-tuned for v5e HBM and AOT-verified (benchmarks/RESULTS.md,
    # "7B projection").
    assert p["7b"].effective_batch_size == 128
    assert p["13b"].effective_batch_size == 256
    assert p["70b"].effective_batch_size == 1024
    assert p["70b"].mesh.data * p["70b"].mesh.fsdp == 256  # v5e-256 slice
    assert all(c.sharding_stage == ShardingStage.FULL_PARTITIONING
               for n, c in p.items() if n != "125m")
    # Offload knobs on the big presets are REAL engine behavior now —
    # params stream from pinned host memory (tests/test_offload.py).
    assert p["13b"].param_offload == OffloadDevice.HOST
    assert p["70b"].param_offload == OffloadDevice.HOST


def test_param_count_roughly_right():
    assert 120e6 < tfm.param_count(tfm.MODEL_CONFIGS["gpt-125m"]) < 180e6
    assert 6.0e9 < tfm.param_count(tfm.MODEL_CONFIGS["llama-7b"]) < 7.5e9
    assert 60e9 < tfm.param_count(tfm.MODEL_CONFIGS["llama-70b"]) < 75e9


def test_per_stage_per_device_memory_shrinks():
    """The stage enum produces genuinely different per-device memory — the
    measurable ZeRO semantics, not a forwarded config string (SURVEY §7
    hard part (a))."""
    import jax

    from tpu_engine.train import build_train_program

    def device0_bytes(tree):
        return sum(
            leaf.addressable_shards[0].data.nbytes
            for leaf in jax.tree.leaves(tree)
            if hasattr(leaf, "addressable_shards")
        )

    stats = {}
    for stage in (ShardingStage.DISABLED, ShardingStage.OPTIMIZER_STATE,
                  ShardingStage.FULL_PARTITIONING):
        cfg = TPUTrainConfig(
            model_name="gpt-tiny", sharding_stage=stage,
            mesh=MeshConfig(data=2, fsdp=4), micro_batch_size=1, seq_len=32,
            precision="fp32", activation_checkpointing=False,
        )
        prog = build_train_program(cfg)
        state = prog.init(jax.random.PRNGKey(0))
        stats[stage] = (
            device0_bytes(state["params"]),
            device0_bytes(state["opt_state"]),
        )
    p0, o0 = stats[ShardingStage.DISABLED]
    p1, o1 = stats[ShardingStage.OPTIMIZER_STATE]
    p3, o3 = stats[ShardingStage.FULL_PARTITIONING]
    # Stage 1: optimizer state shards over fsdp=4; params stay replicated.
    assert p1 == p0
    assert o1 < o0 * 0.5
    # Stage 3: params shard too.
    assert p3 < p1 * 0.5
    assert o3 <= o1
