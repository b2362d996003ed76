"""Sharding engine: ZeRO-stage semantics as JAX sharding layouts.

The reference expresses its parallelism as DeepSpeed JSON config generation
(``ai_engine/deepspeed_launcher.py:114-240``); the stages
(``ZeROStage``, ``deepspeed_launcher.py:22-26``) are opaque knobs handed to an
external engine. Here each stage is a concrete, materially different sharding
layout that XLA compiles to ICI collectives:

====== ============================ ============================ ==========================
stage  params                       gradients                    optimizer state
====== ============================ ============================ ==========================
0      replicated                   all-reduced (replicated)     replicated
1      replicated                   all-reduced (replicated)     sharded over ``fsdp``
2      replicated                   reduce-scattered to shards   sharded over ``fsdp``
3      sharded over ``fsdp``        reduce-scattered to shards   sharded over ``fsdp``
====== ============================ ============================ ==========================

Tensor parallelism (absent in the reference — docstring-only claim at
``deepspeed_launcher.py:8``) is real here: the ``model`` mesh axis shards
attention heads / MLP hidden / vocab, independent of the ZeRO stage.

Mechanism: models annotate every parameter with *logical axis names*
(MaxText/t5x style); :func:`logical_to_mesh_axes` maps logical axes to mesh
axes given the stage, and the launcher applies the resulting
``NamedSharding``s via ``jit``'s in/out shardings plus
``with_sharding_constraint`` on gradients.

CPU offload (reference ``deepspeed_launcher.py:29-33,197-212``) maps to JAX
host memory kinds: optimizer state can live in ``pinned_host`` memory and is
streamed to device inside the update. NVMe offload maps to the disk tier
(``optimizer_offload="disk"`` + ``optimizer_spill_dir``): fp32 masters and
Adam moments in memory-mapped spill files, a fused host AdamW with
fadvise-driven slab prefetch (``tpu_engine/disk_offload.py``).
"""

from __future__ import annotations

from enum import Enum, IntEnum
from typing import Any, Literal, Optional

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from pydantic import BaseModel, Field, model_validator

from tpu_engine.mesh_runtime import MeshConfig


class ShardingStage(IntEnum):
    """Mirrors reference ``ZeROStage`` (``deepspeed_launcher.py:22-26``)."""

    DISABLED = 0
    OPTIMIZER_STATE = 1
    GRADIENT_PARTITIONING = 2
    FULL_PARTITIONING = 3


class OffloadDevice(str, Enum):
    """Mirrors reference ``OffloadDevice`` (``deepspeed_launcher.py:29-33``).

    ``disk`` is the NVMe tier's TPU-VM port: optimizer state (fp32
    masters + Adam moments) lives in memory-mapped files under
    ``optimizer_spill_dir``, the device holds compute-dtype params only,
    and a fused host AdamW streams slabs with fadvise-driven prefetch
    (``tpu_engine/disk_offload.py``). Valid for ``optimizer_offload``
    only — params cannot spill to disk (they are read every step).
    """

    NONE = "none"
    HOST = "host"  # pinned host memory (the TPU analogue of CPU offload)
    DISK = "disk"  # memory-mapped spill files (the NVMe-offload analogue)


class Precision(str, Enum):
    BF16 = "bf16"  # TPU-native default (reference defaults to fp16; see SURVEY §5 quirks)
    FP32 = "fp32"
    FP16 = "fp16"  # accepted for parity; on TPU bf16 is strictly better


_DTYPES = {"bf16": jax.numpy.bfloat16, "fp32": jax.numpy.float32, "fp16": jax.numpy.float16}


def dtype_of(p: Precision):
    return _DTYPES[p.value]


# ---------------------------------------------------------------------------
# Logical-axis → mesh-axis mapping
# ---------------------------------------------------------------------------

# Logical axis names used by models in tpu_engine.models:
#   "embed"    — the d_model dimension
#   "vocab"    — vocabulary dimension
#   "heads"    — attention-head dimension (q heads)
#   "kv_heads" — attention kv-head dimension
#   "head_dim" — per-head feature dimension
#   "mlp"      — MLP hidden dimension
#   "expert"   — MoE expert dimension (expert parallelism)
#   "layers"   — stacked-layer dimension (scan over layers)
#   None       — never sharded

# Tensor-parallel placement: which logical axes ride the "model" mesh axis.
# "expert" is listed FIRST: for MoE tensors ([..., expert, embed, mlp]) the
# expert dimension claims the model axis (expert parallelism) and the mlp
# dimension stays local — a PartitionSpec may not reuse a mesh axis.
_TP_AXES = {"expert": "model", "vocab": "model", "heads": "model",
            "kv_heads": "model", "mlp": "model"}

# FSDP placement: which logical axes ride the "fsdp" mesh axis (only at
# stage 3 for params; always for optimizer state at stage >= 1).
_FSDP_AXES = {"embed": "fsdp"}

# Pipeline placement: the stacked-layer dimension is sharded over the "pipe"
# mesh axis (contiguous blocks of n_layers/pipe layers per stage). With
# pipe == 1 this is a no-op; with pipe > 1 the train program switches to the
# pipelined schedule (tpu_engine/parallel/pipeline.py). Applies at every
# ZeRO stage — pipeline parallelism is orthogonal to param/grad/opt sharding.
_PIPE_AXES = {"layers": "pipe"}


def logical_to_mesh_axes(
    logical: tuple[Optional[str], ...],
    *,
    shard_fsdp: bool,
    shard_tp: bool = True,
) -> P:
    """Map a tuple of logical axis names to a PartitionSpec.

    Each mesh axis is assigned at most once per spec; among TP candidates in
    the same tensor, the axis earlier in ``_TP_AXES``'s priority order wins
    (e.g. "expert" over "mlp" for MoE expert kernels).
    """
    priority = {name: i for i, name in enumerate(_TP_AXES)}
    tp_winner: Optional[str] = None
    if shard_tp:
        candidates = [ax for ax in logical if ax in _TP_AXES]
        if candidates:
            tp_winner = min(candidates, key=lambda a: priority[a])
    out: list[Optional[str]] = []
    used: set[str] = set()
    for ax in logical:
        mesh_ax: Optional[str] = None
        if ax is not None:
            if ax in _PIPE_AXES and _PIPE_AXES[ax] not in used:
                mesh_ax = _PIPE_AXES[ax]
            elif ax == tp_winner and _TP_AXES[ax] not in used:
                mesh_ax = _TP_AXES[ax]
            elif shard_fsdp and ax in _FSDP_AXES and _FSDP_AXES[ax] not in used:
                mesh_ax = _FSDP_AXES[ax]
        if mesh_ax is not None:
            used.add(mesh_ax)
        out.append(mesh_ax)
    # Trim trailing Nones for canonical specs.
    while out and out[-1] is None:
        out.pop()
    return P(*out)


def param_pspecs(logical_tree: Any, stage: ShardingStage) -> Any:
    """PartitionSpecs for model parameters under a sharding stage."""
    shard_fsdp = stage >= ShardingStage.FULL_PARTITIONING
    return jax.tree.map(
        lambda lg: logical_to_mesh_axes(lg, shard_fsdp=shard_fsdp),
        logical_tree,
        is_leaf=lambda x: isinstance(x, tuple),
    )


def grad_pspecs(logical_tree: Any, stage: ShardingStage) -> Any:
    """PartitionSpecs for gradients: stage >= 2 reduce-scatters to shards."""
    shard_fsdp = stage >= ShardingStage.GRADIENT_PARTITIONING
    return jax.tree.map(
        lambda lg: logical_to_mesh_axes(lg, shard_fsdp=shard_fsdp),
        logical_tree,
        is_leaf=lambda x: isinstance(x, tuple),
    )


def opt_state_pspecs(logical_tree: Any, stage: ShardingStage) -> Any:
    """PartitionSpecs for optimizer-state leaves shaped like params: stage >= 1 shards."""
    shard_fsdp = stage >= ShardingStage.OPTIMIZER_STATE
    return jax.tree.map(
        lambda lg: logical_to_mesh_axes(lg, shard_fsdp=shard_fsdp),
        logical_tree,
        is_leaf=lambda x: isinstance(x, tuple),
    )


def named_shardings(
    mesh: Mesh,
    pspec_tree: Any,
    memory_kind: Optional[str] = None,
) -> Any:
    """Materialise a PartitionSpec tree into NamedShardings on ``mesh``."""

    def mk(spec: P) -> NamedSharding:
        return NamedSharding(mesh, spec, memory_kind=memory_kind)

    return jax.tree.map(mk, pspec_tree, is_leaf=lambda x: isinstance(x, P))


_HOST_KIND_CACHE: dict[str, bool] = {}


def host_memory_kind_available(mesh: Mesh) -> bool:
    """True when the backend supports pinned-host placement.

    Probed by actually placing a scalar (cached per platform): TPU supports
    it, and so does the CPU test backend — its ``memory_spaces`` attribute
    is absent, so introspection under-reports; probing keeps the offload
    paths exercised by the 8-virtual-device CPU test mesh rather than
    silently skipped off-TPU.
    """
    dev = mesh.devices.flat[0]
    key = getattr(dev, "platform", "unknown")
    if key == "tpu":
        # Every TPU runtime supports pinned_host — and AOT topology
        # devices (compile-only, no data placement possible) must not be
        # probed at all.
        return True
    hit = _HOST_KIND_CACHE.get(key)
    if hit is not None:
        return hit
    try:
        from jax.sharding import SingleDeviceSharding

        x = jax.device_put(
            jax.numpy.zeros((1,)),
            SingleDeviceSharding(dev, memory_kind="pinned_host"),
        )
        x.block_until_ready()
        ok = True
    except Exception:
        ok = False
    _HOST_KIND_CACHE[key] = ok
    return ok


# ---------------------------------------------------------------------------
# Training configuration (reference DeepSpeedConfig analogue)
# ---------------------------------------------------------------------------


class TPUTrainConfig(BaseModel):
    """Mirrors reference ``DeepSpeedConfig`` (``deepspeed_launcher.py:35-87``)
    field-for-field where meaningful, re-based to TPU semantics.

    Differences, deliberate:
    - ``num_gpus``/``num_nodes`` become a :class:`MeshConfig` — world size is
      the mesh, not a flag pair;
    - ``fp16`` + dynamic loss scaling become bf16 (no loss scaling needed);
    - comm bucket knobs become XLA-level toggles (async collectives are on by
      default in XLA; there is nothing to hand-tune here);
    - sequence length is a real field (the reference has none — SURVEY §5).
    """

    model_name: str = Field(default="gpt-125m", description="model preset or identifier")
    sharding_stage: ShardingStage = Field(default=ShardingStage.FULL_PARTITIONING)
    mesh: MeshConfig = Field(default_factory=MeshConfig)

    # Batch geometry (reference :43-44 micro-batch / accumulation).
    micro_batch_size: int = Field(default=1, ge=1)
    gradient_accumulation_steps: int = Field(default=1, ge=1)
    seq_len: int = Field(default=2048, ge=1)

    # Precision (reference :49-58 fp16/bf16 blocks).
    precision: Precision = Precision.BF16
    param_dtype: Precision = Precision.FP32  # master params
    grad_allreduce_dtype: Optional[Precision] = None  # reference communication_data_type :60
    # Adam first-moment dtype (None = master dtype). BF16 halves the mu
    # buffer (~2 GB/1B params) — the TPU analogue of DeepSpeed's reduced-
    # precision optimizer states; nu always stays at the master dtype.
    moment_dtype: Optional[Precision] = None

    # Optimizer / schedule (reference :145-164 AdamW + WarmupDecayLR).
    # "adamw" matches the reference; "adafactor" stores factored second
    # moments (O(in+out) per kernel instead of O(in·out) — the classic
    # TPU-era memory saver); "lion" keeps a single bf16-friendly momentum.
    optimizer: Literal["adamw", "adafactor", "lion"] = "adamw"
    # LR schedule shape; all warm up over warmup_steps first.
    lr_schedule: Literal["cosine", "linear", "constant", "rsqrt"] = "cosine"
    # Decay norm scales / embeddings too? Standard LLM practice is to decay
    # only the ≥2-D matmul kernels (the default); True matches the
    # reference's blanket AdamW weight_decay.
    decay_all_params: bool = False
    learning_rate: float = Field(default=3e-4, gt=0)
    min_lr: float = Field(default=3e-5, ge=0)
    warmup_steps: int = Field(default=100, ge=0)
    total_steps: int = Field(default=10_000, ge=1)
    weight_decay: float = Field(default=0.1, ge=0)
    beta1: float = Field(default=0.9, gt=0, lt=1)
    beta2: float = Field(default=0.95, gt=0, lt=1)
    grad_clip_norm: float = Field(default=1.0, gt=0)

    # Offload (reference :39-40,197-212).
    optimizer_offload: OffloadDevice = OffloadDevice.NONE
    param_offload: OffloadDevice = OffloadDevice.NONE
    # Disk tier only: where the optimizer spill files live (reference
    # ``nvme_path``, ``deepspeed_launcher.py:200``). Required when
    # optimizer_offload == disk; persists across restarts (warm
    # re-attach of exact Adam moments).
    optimizer_spill_dir: Optional[str] = None

    # Collective-communication tuning (reference overlap_comm /
    # bucket-size knobs, ``deepspeed_launcher.py:133-142`` → XLA flags;
    # see tpu_engine/comm.py). Applied by the worker CLI before the XLA
    # backend initialises.
    async_collectives: bool = True
    latency_hiding_scheduler: bool = True
    xla_extra_flags: str = ""

    # ZeRO++-style communication compression (arXiv:2306.10209; see
    # tpu_engine/comm_compress.py). Three composable mechanisms that cut
    # collective bytes on the slowest link of a hybrid ICI/DCN mesh:
    # qwZ — the ZeRO-3 weight all-gather moves block-quantized int8 codes
    # plus per-block fp32 scales instead of full-width values (~3.9x fewer
    # bytes at block 256). hpZ — steady-state gathers read a pre-quantized
    # secondary int8 replica refreshed once per optimizer step (requires
    # qwZ). qgZ — the cross-slice (dcn_data) gradient reduction goes
    # hierarchical: fp32 psum within each slice over ICI, int8 partials
    # with stochastic rounding across slices over DCN. Requires stage-3
    # sharding and a (data, fsdp)-only mesh; see _validate_comm_compression.
    comm_quant_weights: bool = False
    comm_secondary_weights: bool = False
    comm_quant_grads: bool = False
    # Quantization block length along each tensor's last axis; per-block
    # fp32 scale overhead is 4/block_size bytes per element.
    comm_quant_block_size: int = Field(default=256, ge=8)

    # AQT-style MXU int8 quantized training (tpu_engine/quant_train.py):
    # "int8" routes the targeted training matmuls (Q/K/V/O projections,
    # dense MLP, MoE expert einsums) through a channel-scaled int8 dot
    # with int32 accumulation and stochastically-rounded backward
    # operands — master weights/optimizer state stay full precision.
    # Orthogonal to, and composable with, the comm_quant_* wire
    # compression above (that quantizes collectives; this quantizes
    # compute). See _validate_quant_training for the rejected combos.
    quant_training: Literal["none", "int8"] = "none"
    # Which matmul groups ride the quantized dot: "attn" (Q/K/V/O),
    # "mlp" (dense MLP), "moe" (per-expert einsums). Router, dispatch/
    # combine, embed and unembed always stay full precision.
    quant_train_targets: tuple[str, ...] = ("attn", "mlp", "moe")

    # Attention implementation: "auto" = flash kernel on TPU, XLA elsewhere;
    # a >1 sequence mesh axis switches to ring attention unless "ulysses"
    # (all-to-all sequence parallelism) is requested explicitly.
    attention_impl: Literal["auto", "xla", "flash", "ring", "ulysses"] = Field(
        default="auto", description="auto | xla | flash | ring | ulysses"
    )
    # Sliding-window attention override: None = the model preset's own
    # window (e.g. mistral-7b → 4096); 0 = force full causal; N = window N.
    sliding_window: Optional[int] = Field(default=None, ge=0)
    # MoE dispatch override (MoE models only): None = the model's own
    # setting (dense). "dense" = capacity-factor dense dispatch (expert-
    # parallel shardable); "ragged" = sort + lax.ragged_dot, no token
    # dropping, wins at long sequence (measured crossover in
    # benchmarks/RESULTS.md §MoE, pre-ledger; single-shard experts only).
    moe_impl: Optional[Literal["dense", "ragged"]] = None

    # LoRA fine-tuning: when lora_rank is set, only rank-sized adapters on
    # lora_targets train (tpu_engine/lora.py); the base model is frozen —
    # gradients, optimizer state, and checkpoints are adapter-sized.
    lora_rank: Optional[int] = Field(default=None, ge=1)
    lora_alpha: float = Field(default=16.0, gt=0)
    lora_targets: tuple[str, ...] = ("q", "k", "v", "o")
    # Frozen base weights to adapt: a local HF checkpoint directory
    # (LlamaForCausalLM format). None = deterministic random init from seed
    # (tests/benchmarks only — the supervisor warns).
    lora_base_hf_checkpoint: Optional[str] = None

    # Activation checkpointing (reference :64-67,215-223) → jax.remat.
    activation_checkpointing: bool = True
    remat_policy: str = Field(
        default="nothing_saveable",
        description="jax.checkpoint policy name: nothing_saveable | dots_saveable | "
        "dots_with_no_batch_dims_saveable | everything_saveable | save_attn_out | "
        "save_qkv_attn_out",
    )
    # Disk-tier overlap (ZeRO-Offload "delayed parameter update"): the
    # device computes step N+1's forward/backward WHILE the host AdamW
    # walk applies step N — gradients are one step stale (computed on
    # params missing the in-flight update), the documented DPU tradeoff.
    # Step time approaches max(device, host) instead of their sum where
    # the walk's gradient device_gets do not queue behind the next step's
    # execution. Not measured on the current machine — measure before
    # enabling; the serial walk's built-in one-leaf-ahead gradient
    # prefetch overlaps transfer and compute either way. The supervisor
    # flushes the in-flight
    # walk before checkpoints/eval, so saved states are always
    # step-consistent. Requires optimizer_offload='disk'.
    disk_update_overlap: bool = False
    # Cross-entropy computed this many sequence positions at a time, so the
    # fp32 [B, S, vocab] logits tensor is never fully materialised. None =
    # single unchunked unembed+softmax. Must divide seq_len.
    loss_chunk_size: Optional[int] = Field(default=None, ge=1)
    # PaLM-style logit-normaliser penalty coef·mean(log Z²) — the standard
    # bf16 stabiliser; 0 disables. Training loss only (eval stays pure CE).
    z_loss_coef: float = Field(default=0.0, ge=0)

    # Pipeline schedule (pipe axis > 1): "gpipe" = forward all microbatches
    # then autodiff's reverse pipeline (activation residency O(M + P) stage
    # buffers); "1f1b" = interleaved one-forward-one-backward with manual
    # per-stage vjp — activation residency O(P) ring slots per stage, the
    # schedule that lets microbatch counts grow without activation blowup
    # (tpu_engine/parallel/pipeline_1f1b.py); "zb" = zero-bubble variant of
    # 1f1b that splits the backward into B (input-cotangent) and W (weight
    # gradient) phases and retires deferred W in the warmup/drain lanes
    # 1f1b burns as masked compute — same O(P) residency plus a bounded
    # P-1-entry stash, strictly less bubble compute per step
    # (tpu_engine/parallel/pipeline_zb.py). "auto" (default) picks zb
    # exactly where the O(P)-residency schedules win — microbatch count
    # above the stage count, so the residency bound frees real memory and
    # the warmup/drain overhead is amortised — and gpipe otherwise
    # (measured: benchmarks/RESULTS.md §Pipeline; resolution in
    # resolve_pipeline_schedule below, shared by train/launcher/HBM
    # admission). zb and 1f1b share one interaction matrix: both reject
    # comm compression, quant_training, reduced grad_allreduce_dtype and
    # loss_chunk_size when explicit, and "auto" degrades to gpipe.
    pipeline_schedule: Literal["auto", "gpipe", "1f1b", "zb"] = "auto"

    # Elasticity (reference :78,226-238): TPU slices are fixed-shape, so
    # elasticity means re-launch at a new mesh shape + resume from checkpoint.
    elastic_resume: bool = True
    # Admissible device-count bounds (reference elasticity min/max GPUs,
    # ``deepspeed_launcher.py:229-233``). When ``elastic_min_devices`` is
    # set and the configured mesh does not fit the visible devices at
    # launch/resume, the supervisor auto-selects the largest admissible
    # shape via ``mesh_runtime.derive_elastic_mesh`` and cross-mesh-restores
    # from checkpoint. None = exact-fit only (mismatch is an error).
    elastic_min_devices: Optional[int] = Field(default=None, ge=1)
    elastic_max_devices: Optional[int] = Field(default=None, ge=1)
    # Admissible EFFECTIVE-batch bounds (reference elasticity min/max batch
    # sizes, ``deepspeed_launcher.py:226-233`` — the second half of its
    # elasticity declaration). An elastic mesh resize preserves the
    # declared effective batch by rescaling gradient_accumulation_steps
    # (ceil — never a silent shrink); these bounds then gate ADMISSION of
    # the achieved batch: outside them, the resume fails rather than
    # training at a batch the job never declared. None = preserve-only.
    elastic_min_batch_size: Optional[int] = Field(default=None, ge=1)
    elastic_max_batch_size: Optional[int] = Field(default=None, ge=1)
    # The effective batch the job DECLARES (authoritative across process
    # restarts). None = derived from this config at job construction —
    # correct in-process, but a ``data=-1`` mesh resumed in a NEW process
    # on a shrunken slice cannot reconstruct the launch-time world from
    # the config alone (the -1 would re-resolve against the smaller
    # world and silently bless the shrink); set this field explicitly for
    # cross-process elasticity with -1 meshes.
    elastic_target_batch_size: Optional[int] = Field(default=None, ge=1)

    # Checkpointing.
    checkpoint_dir: Optional[str] = None
    checkpoint_interval_steps: int = Field(default=500, ge=1)
    max_checkpoints_to_keep: int = Field(default=3, ge=1)

    # Data / misc.
    dataset_path: Optional[str] = Field(
        default=None,
        description="flat binary token file (tpu_engine.data); None = synthetic",
    )
    dataset_dtype: Literal["uint16", "int32"] = "uint16"
    # Held-out evaluation: every eval_interval_steps, average the eval loss
    # over eval_batches batches from eval_dataset_path (or held-out
    # synthetic data). None = no evaluation.
    eval_interval_steps: Optional[int] = Field(default=None, ge=1)
    eval_batches: int = Field(default=4, ge=1)
    eval_dataset_path: Optional[str] = None
    seed: int = 0
    log_every_steps: int = Field(default=100, ge=1)  # reference steps_per_print :128
    # Structured metrics log: one JSON line per logged train step / eval run
    # (the reference's only logging is bare print()s in a stub —
    # ``spot_resiliency.py:22,35``; SURVEY.md §5 "no structured logging").
    metrics_log_path: Optional[str] = None

    @model_validator(mode="after")
    def _validate_elastic_bounds(self) -> "TPUTrainConfig":
        if (
            self.elastic_min_devices is not None
            and self.elastic_max_devices is not None
            and self.elastic_max_devices < self.elastic_min_devices
        ):
            raise ValueError(
                f"elastic_max_devices={self.elastic_max_devices} < "
                f"elastic_min_devices={self.elastic_min_devices}"
            )
        if self.elastic_max_devices is not None and self.elastic_min_devices is None:
            raise ValueError(
                "elastic_max_devices requires elastic_min_devices (the bounds "
                "are one declaration: 'this job may run between X and Y chips')"
            )
        if (
            self.elastic_min_batch_size is not None
            and self.elastic_max_batch_size is not None
            and self.elastic_max_batch_size < self.elastic_min_batch_size
        ):
            raise ValueError(
                f"elastic_max_batch_size={self.elastic_max_batch_size} < "
                f"elastic_min_batch_size={self.elastic_min_batch_size}"
            )
        return self

    @model_validator(mode="after")
    def _validate_grad_allreduce_dtype(self) -> "TPUTrainConfig":
        """Reduced-precision gradient communication rides the compute-dtype
        cotangent chain (see ``train.py``), so the comm dtype must be fp32
        or exactly the compute precision — fail fast on e.g. fp16 comm with
        bf16 compute rather than silently reducing in the wrong dtype."""
        if self.grad_allreduce_dtype not in (None, Precision.FP32) and (
            self.grad_allreduce_dtype != self.precision
        ):
            raise ValueError(
                f"grad_allreduce_dtype={self.grad_allreduce_dtype.value!r} must "
                f"be 'fp32' or match precision={self.precision.value!r}"
            )
        return self

    @model_validator(mode="after")
    def _validate_comm_compression(self) -> "TPUTrainConfig":
        """Comm compression replaces the GSPMD gather/reduce collectives
        with explicit ones inside a full-manual shard_map over (data,
        fsdp) — combinations that cannot ride that region fail at config
        time. (A partial-auto region with a real-extent auto axis aborts
        the SPMD partitioner outright, so these are hard rejections, not
        degradations.)"""
        compressing = (
            self.comm_quant_weights
            or self.comm_secondary_weights
            or self.comm_quant_grads
        )
        if not compressing:
            return self
        if self.comm_secondary_weights and not self.comm_quant_weights:
            raise ValueError(
                "comm_secondary_weights (hpZ) requires comm_quant_weights "
                "(qwZ): the secondary replica IS the quantized gather source"
            )
        if self.sharding_stage != ShardingStage.FULL_PARTITIONING:
            raise ValueError(
                "comm compression requires sharding_stage=3 (the quantized "
                "all-gather replaces the ZeRO-3 fsdp weight gather; stages "
                "0-2 keep params replicated and gather nothing)"
            )
        if self.pipeline_schedule in ("1f1b", "zb"):
            raise ValueError(
                f"comm compression with pipeline_schedule="
                f"{self.pipeline_schedule!r} is not supported (the manual "
                "per-stage vjp owns the grad collectives)"
            )
        if self.grad_allreduce_dtype not in (None, Precision.FP32):
            raise ValueError(
                "comm compression with reduced-precision "
                f"grad_allreduce_dtype={self.grad_allreduce_dtype.value!r} "
                "is redundant and unsupported — qgZ already quantizes the "
                "cross-slice reduction"
            )
        if self.lora_rank is not None:
            raise ValueError(
                "comm compression with LoRA is unsupported (adapter grads "
                "are rank-sized; there is nothing worth compressing)"
            )
        if self.param_offload != OffloadDevice.NONE:
            raise ValueError(
                "comm compression with param_offload is unsupported (the "
                "compressed gather sources device-resident shards)"
            )
        if self.optimizer_offload == OffloadDevice.DISK:
            raise ValueError(
                "comm compression with optimizer_offload='disk' is "
                "unsupported (the disk tier drives its own grad path)"
            )
        for ax in ("pipe", "sequence", "model"):
            if getattr(self.mesh, ax) > 1:
                raise ValueError(
                    f"comm compression requires mesh.{ax}=1: the quantized "
                    "collectives run in a full-manual shard_map over "
                    "(data, fsdp) only"
                )
        if self.attention_impl in ("flash", "ring", "ulysses"):
            raise ValueError(
                f"comm compression with attention_impl="
                f"{self.attention_impl!r} is unsupported (kernel attention "
                "is a shard_map region and cannot nest inside the "
                "compression region) — use 'auto' or 'xla'"
            )
        return self

    @model_validator(mode="after")
    def _validate_quant_training(self) -> "TPUTrainConfig":
        """MXU int8 quantized training interaction matrix.

        COMPOSES with the ZeRO++ comm_quant_* flags (they quantize the
        *wire*, this quantizes the *compute*; the int8 einsum is plain
        jnp inside the compression region's loss_fn) and with optimizer/
        param offload and the disk tier (orthogonal to where state
        lives). REJECTED combos fail here with the reason:
        """
        from tpu_engine.quant_train import QUANT_TARGET_GROUPS

        bad = set(self.quant_train_targets) - set(QUANT_TARGET_GROUPS)
        if bad:
            raise ValueError(
                f"unknown quant_train_targets {sorted(bad)}; valid groups: "
                f"{list(QUANT_TARGET_GROUPS)}"
            )
        if self.quant_training == "none":
            return self
        if not self.quant_train_targets:
            raise ValueError(
                "quant_training='int8' with empty quant_train_targets is a "
                "no-op; set targets or quant_training='none'"
            )
        if self.lora_rank is not None:
            raise ValueError(
                "quant_training='int8' with LoRA is unsupported: the "
                "rank-sized adapter matmuls bypass the quantized hook and "
                "stochastic-rounding noise on the frozen base would leak "
                "into merge-time semantics — fine-tune in bf16"
            )
        if self.pipeline_schedule in ("1f1b", "zb"):
            raise ValueError(
                f"quant_training='int8' with pipeline_schedule="
                f"{self.pipeline_schedule!r} is unsupported (the manual "
                "per-stage vjp bypasses the quantized primitive's custom "
                "backward); use 'gpipe' or 'auto' (auto falls back to "
                "gpipe under quantization)"
            )
        if self.moe_impl == "ragged" and "moe" in self.quant_train_targets:
            raise ValueError(
                "quant_training='int8' with moe_impl='ragged' is "
                "unsupported (lax.ragged_dot takes no per-channel scales); "
                "use moe_impl='dense' or drop 'moe' from quant_train_targets"
            )
        return self

    @model_validator(mode="after")
    def _validate_disk_offload(self) -> "TPUTrainConfig":
        """The disk tier is a host-side fused AdamW over memmap slabs —
        combinations that cannot ride that path fail at config time."""
        if self.optimizer_offload != OffloadDevice.DISK:
            if self.optimizer_spill_dir is not None:
                raise ValueError(
                    "optimizer_spill_dir only applies with "
                    "optimizer_offload='disk'"
                )
            if self.disk_update_overlap:
                raise ValueError(
                    "disk_update_overlap only applies with "
                    "optimizer_offload='disk'"
                )
            if self.param_offload == OffloadDevice.DISK:
                raise ValueError(
                    "param_offload='disk' is not supported: params are read "
                    "every forward pass — spill optimizer state instead "
                    "(optimizer_offload='disk')"
                )
            return self
        if self.optimizer_spill_dir is None:
            raise ValueError(
                "optimizer_offload='disk' requires optimizer_spill_dir "
                "(the reference's nvme_path)"
            )
        if self.optimizer != "adamw":
            raise ValueError(
                "optimizer_offload='disk' supports optimizer='adamw' only "
                "(the host update implements the AdamW chain)"
            )
        if self.moment_dtype is not None:
            raise ValueError(
                "moment_dtype targets device/host memory; disk-tier moments "
                "live in fp32 spill files — drop moment_dtype"
            )
        if self.param_offload != OffloadDevice.NONE:
            raise ValueError(
                "optimizer_offload='disk' with param_offload is not "
                "supported (the disk tier already keeps only compute-dtype "
                "params on device)"
            )
        if self.lora_rank is not None:
            raise ValueError(
                "optimizer_offload='disk' with LoRA is pointless (adapter "
                "state is rank-sized) and unsupported"
            )
        return self

    @property
    def effective_batch_size(self) -> int:
        """micro × accum × data-parallel world (reference ``deepspeed_launcher.py:323-328``).

        Computed against the *data-parallel* extent (data × fsdp axes), the
        honest analogue of ``num_gpus × num_nodes`` — and unlike the
        reference's elasticity block (``:229-233``) it cannot drop a factor.
        ``data = -1`` is resolved against the visible device count when the
        mesh fits; otherwise -1 is conservatively treated as 1.
        """
        data = self.mesh.data
        if data == -1:
            try:
                import jax

                data = self.mesh.resolved_shape(jax.device_count())[0]
            except Exception:
                data = 1
        dp = data * self.mesh.fsdp
        return self.micro_batch_size * self.gradient_accumulation_steps * dp

    def compute_dtype(self):
        return dtype_of(self.precision)

    def master_dtype(self):
        return dtype_of(self.param_dtype)


def resolve_pipeline_schedule(cfg: TPUTrainConfig) -> str:
    """Resolve ``pipeline_schedule="auto"`` to a concrete schedule.

    One resolver shared by the train-step builder, the launcher plan and
    HBM admission (``hbm_estimate``), so "what will this config actually
    run" has a single answer. Measured A/B in benchmarks/RESULTS.md
    §Pipeline: at M <= P microbatches the O(P)-residency schedules bound
    the same memory as GPipe while their masked warmup/drain lanes burn
    compute, so gpipe wins; at M > P GPipe's O(M) saved stage buffers
    grow past the ring — on memory-bound configs GPipe simply OOMs where
    1f1b/zb keep scaling. Of the two manual-vjp schedules zb strictly
    dominates 1f1b — same O(P) residency (plus a bounded P-1-entry
    stash), 2(P-1) F-units less bubble compute per stage per step — so
    auto picks zb; 1f1b stays selectable explicitly.

    Features the manual-vjp schedules do not support (chunked exit loss,
    quant_training's custom backward, reduced-dtype grad collectives)
    degrade auto to gpipe, whose plain autodiff handles them all.
    """
    if cfg.pipeline_schedule != "auto":
        return cfg.pipeline_schedule
    unsupported_manual = (
        bool(cfg.loss_chunk_size)
        or cfg.quant_training != "none"
        or (
            cfg.grad_allreduce_dtype is not None
            and cfg.grad_allreduce_dtype != Precision.FP32
        )
    )
    if (
        cfg.mesh.pipe > 1
        and cfg.gradient_accumulation_steps > cfg.mesh.pipe
        and not unsupported_manual
    ):
        return "zb"
    return "gpipe"


def presets() -> dict[str, TPUTrainConfig]:
    """Named configuration registry.

    Parity with reference ``DeepSpeedLauncher.presets`` (``deepspeed_launcher.py:369-407``:
    7b / 13b / 70b), plus the 125m smoke config from BASELINE.json configs[0].
    Batch geometry matches the reference presets; fp16 → bf16 (TPU-native).
    """
    return {
        "125m": TPUTrainConfig(
            model_name="gpt-125m",
            sharding_stage=ShardingStage.DISABLED,
            mesh=MeshConfig(data=-1),
            micro_batch_size=8,
            gradient_accumulation_steps=1,
            seq_len=1024,
            learning_rate=6e-4,
            activation_checkpointing=False,
        ),
        "1b": TPUTrainConfig(
            model_name="llama-1b",
            sharding_stage=ShardingStage.FULL_PARTITIONING,
            mesh=MeshConfig(data=1, fsdp=8),
            micro_batch_size=4,
            gradient_accumulation_steps=4,
            seq_len=2048,
            learning_rate=3e-4,
        ),
        # The 7b/13b/70b batch geometry mirrors the reference's presets
        # (``deepspeed_launcher.py:369-407``), but the mesh shapes are
        # re-tuned for 16-GiB v5e chips and AOT-VERIFIED to fit: the XLA
        # compiler's own memory analysis for each preset's target slice is
        # recorded in benchmarks/RESULTS.md ("7B projection"). The
        # reference never validated its GPU counts anywhere.
        "7b": TPUTrainConfig(
            model_name="llama-7b",
            sharding_stage=ShardingStage.FULL_PARTITIONING,
            mesh=MeshConfig(data=1, fsdp=8),  # v5e-8: 12.7 GiB/chip peak
            micro_batch_size=2,
            gradient_accumulation_steps=8,  # eff. batch 128, as reference
            seq_len=4096,
            learning_rate=3e-4,
            optimizer_offload=OffloadDevice.HOST,
        ),
        "13b": TPUTrainConfig(
            model_name="llama-13b",
            sharding_stage=ShardingStage.FULL_PARTITIONING,
            mesh=MeshConfig(data=1, fsdp=16),  # v5e-16: 13.1 GiB/chip peak
            micro_batch_size=1,
            gradient_accumulation_steps=16,  # eff. batch 256, as reference
            seq_len=4096,
            learning_rate=2e-4,
            optimizer_offload=OffloadDevice.HOST,
            param_offload=OffloadDevice.HOST,
            loss_chunk_size=1024,
        ),
        "70b": TPUTrainConfig(
            model_name="llama-70b",
            sharding_stage=ShardingStage.FULL_PARTITIONING,
            mesh=MeshConfig(data=2, fsdp=128),  # v5e-256: 12.3 GiB/chip peak
            micro_batch_size=1,
            gradient_accumulation_steps=4,  # eff. batch 1024, as reference
            seq_len=4096,
            learning_rate=1.5e-4,
            optimizer_offload=OffloadDevice.HOST,
            param_offload=OffloadDevice.HOST,
            loss_chunk_size=1024,
            remat_policy="nothing_saveable",
        ),
        "8x7b": TPUTrainConfig(  # Mixtral-style MoE: experts over "model" (EP)
            model_name="moe-8x7b",
            sharding_stage=ShardingStage.FULL_PARTITIONING,
            # v5e-64 (8x8): 12.57 GiB/device AOT-verified (round 5,
            # benchmarks/RESULTS.md §MoE). The earlier fsdp=4 32-chip
            # shape compiled 4.7 GiB OVER budget — exactly the
            # never-validated-preset failure this repo criticises the
            # reference for, caught by the same sweep that sizes the
            # dense presets.
            mesh=MeshConfig(data=1, fsdp=8, model=8),
            micro_batch_size=1,
            # fsdp doubled 4 -> 8 for the fit; accumulation halves so the
            # effective batch stays 64 (micro 1 x accum 8 x dp 8) — the
            # memory fix must not silently change training semantics.
            gradient_accumulation_steps=8,
            seq_len=4096,
            learning_rate=2e-4,
            optimizer_offload=OffloadDevice.HOST,
            loss_chunk_size=1024,
        ),
    }
