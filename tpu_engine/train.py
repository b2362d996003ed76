"""Sharded training program: optimizer, loss, and the pjit train step.

This is the in-process engine that replaces the reference's subprocess
launch of an external DeepSpeed script (``ai_engine/deepspeed_launcher.py:354``
— fire-and-forget ``Popen``). The engine *owns* the step function:

- AdamW + warmup-cosine schedule with floor (reference config blocks
  ``deepspeed_launcher.py:145-164`` — ``WarmupDecayLR`` + AdamW);
- gradient accumulation via ``lax.scan`` (reference
  ``gradient_accumulation_steps``, ``:44``);
- global-norm gradient clipping (reference ``gradient_clipping``, ``:46``);
- bf16 compute with fp32 master params — no loss scaling needed on TPU
  (the reference needs fp16 dynamic loss scaling, ``:176-183``);
- activation checkpointing via ``jax.checkpoint`` (reference ``:215-223``);
- ZeRO-stage sharding applied through NamedShardings from
  ``tpu_engine.sharding`` — gradients are reduce-scattered (stage ≥ 2) by
  constraining their sharding, optimizer state sharded (stage ≥ 1), params
  sharded (stage 3); XLA emits the all-gathers/reduce-scatters over ICI.
"""

from __future__ import annotations

import os
from dataclasses import dataclass
from functools import partial
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from tpu_engine import comm_compress
from tpu_engine.mesh_runtime import BATCH_AXES, MeshRuntime
from tpu_engine.models import transformer as tfm
from tpu_engine.sharding import (
    OffloadDevice,
    ShardingStage,
    TPUTrainConfig,
    dtype_of,
    grad_pspecs,
    host_memory_kind_available,
    named_shardings,
    opt_state_pspecs,
    param_pspecs,
    resolve_pipeline_schedule,
)


def make_schedule(cfg: TPUTrainConfig) -> optax.Schedule:
    """Warmup + the configured decay shape (reference WarmupDecayLR,
    ``:145-153``, generalised: cosine | linear | constant | rsqrt)."""
    warmup = max(cfg.warmup_steps, 1)
    decay_steps = max(cfg.total_steps, cfg.warmup_steps + 1)
    if cfg.lr_schedule == "cosine":
        return optax.warmup_cosine_decay_schedule(
            init_value=0.0, peak_value=cfg.learning_rate, warmup_steps=warmup,
            decay_steps=decay_steps, end_value=cfg.min_lr,
        )
    warm = optax.linear_schedule(0.0, cfg.learning_rate, warmup)
    if cfg.lr_schedule == "linear":
        tail = optax.linear_schedule(
            cfg.learning_rate, cfg.min_lr, max(decay_steps - warmup, 1)
        )
    elif cfg.lr_schedule == "constant":
        tail = optax.constant_schedule(cfg.learning_rate)
    else:  # rsqrt: lr · sqrt(warmup / step) past warmup, floored at min_lr
        def tail(step):
            lr = cfg.learning_rate * jnp.sqrt(warmup / jnp.maximum(step + warmup, 1))
            return jnp.maximum(lr, cfg.min_lr)
    return optax.join_schedules([warm, tail], boundaries=[warmup])


def accumulate_grads(grad_fn, reduce_grads, params_g, params_like, batch,
                     grad_sh):
    """Gradient accumulation over ``batch`` [accum, B, S]: the masked-SFT
    global-denominator scan shared by the in-memory train step and the
    disk-tier grad step — ONE definition so the two paths' objectives
    cannot silently diverge. Returns (summed loss, summed fp32 grads)."""
    accum = batch.shape[0]
    # Batch-wide valid-target count (masked SFT targets excluded): each
    # microbatch contributes raw sums / this denominator, so the summed
    # loss and grads realise the global mean.
    denom = jnp.maximum(
        jnp.sum((batch[:, :, 1:] >= 0).astype(jnp.float32)), 1.0
    )

    def accum_body(carry, tokens):
        loss_acc, grad_acc = carry
        loss, grads = grad_fn(params_g, tokens, True, denom=denom,
                              aux_weight=1.0 / accum)
        # Stage >= 2: the constraint to fsdp shards makes XLA
        # reduce-scatter instead of all-reduce (ZeRO-2 semantics);
        # reduce_grads routes the collective through the configured
        # comm dtype, accumulation stays fp32.
        grads = reduce_grads(grads)
        grad_acc = jax.tree.map(jnp.add, grad_acc, grads)
        return (loss_acc + loss, grad_acc), None

    zero_grads = jax.tree.map(
        lambda p: jnp.zeros(p.shape, jnp.float32), params_like
    )
    zero_grads = jax.lax.with_sharding_constraint(zero_grads, grad_sh)
    (loss, grad_sum), _ = jax.lax.scan(
        accum_body, (jnp.zeros((), jnp.float32), zero_grads), batch
    )
    return loss, grad_sum


def kernel_decay_mask(params: Any) -> Any:
    """Path-based weight-decay mask: matmul kernels and LoRA adapter
    factors decay; norm scales and embeddings do not. ndim alone cannot
    distinguish them — the stacked layout makes per-layer norm scales
    [L, D]. ONE definition, shared by the optax chain and the disk-tier
    host AdamW (their masks must never drift)."""
    return jax.tree_util.tree_map_with_path(
        lambda path, _: getattr(path[-1], "key", None) in ("kernel", "A", "B"),
        params,
    )


def make_optimizer(cfg: TPUTrainConfig) -> tuple[optax.GradientTransformation, optax.Schedule]:
    """The configured optimizer (AdamW matches the reference's block,
    ``:156-164``; Adafactor/Lion are the TPU-era memory-efficient options).

    The learning rate is deliberately NOT baked into the transformation: the
    train step applies ``-lr`` itself, where ``lr = schedule(step) × lr_scale``
    and ``lr_scale`` lives in the train state. That lets the supervisor cut
    the LR after a divergence rollback (mechanising the reference's
    "reduce learning rate" remediation strings, ``loss_monitor.py:131-136``)
    without recompiling the step function.

    Weight decay applies only to ≥2-D kernels unless ``decay_all_params``
    (norm scales and embeddings are conventionally undecayed).
    """
    schedule = make_schedule(cfg)
    mu_dtype = dtype_of(cfg.moment_dtype) if cfg.moment_dtype is not None else None
    if cfg.optimizer == "adafactor":
        if cfg.moment_dtype is not None:
            raise ValueError(
                "moment_dtype is not supported with optimizer='adafactor' "
                "(factored statistics have no dtype knob)"
            )
        # Honor an explicitly-set beta2 as the factored-RMS decay rate;
        # otherwise keep Adafactor's conventional 0.8 (Adam's 0.95 default
        # is not a sensible factored decay).
        decay_rate = cfg.beta2 if "beta2" in cfg.model_fields_set else 0.8
        scaler = optax.scale_by_factored_rms(decay_rate=decay_rate)
    elif cfg.optimizer == "lion":
        scaler = optax.scale_by_lion(
            b1=cfg.beta1, b2=cfg.beta2, mu_dtype=mu_dtype
        )
    else:
        scaler = optax.scale_by_adam(
            b1=cfg.beta1, b2=cfg.beta2, eps=1e-8, mu_dtype=mu_dtype
        )
    decay = optax.add_decayed_weights(
        cfg.weight_decay,
        mask=None if cfg.decay_all_params else kernel_decay_mask,
    )
    tx = optax.chain(
        optax.clip_by_global_norm(cfg.grad_clip_norm), scaler, decay
    )
    return tx, schedule


def _ce_sums(
    logits: jax.Array, tokens: jax.Array
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Raw next-token CE sums: (Σ log-likelihood, Σ logZ², valid count).

    Positions whose *target* token is negative are excluded (the in-band
    SFT masking convention — see ``decode_masked_tokens``). Returning sums
    lets the caller choose the normaliser — per-call mean (``lm_loss``) or
    a global valid-target count across gradient-accumulation microbatches
    (the train/eval steps), which keeps the objective the documented
    global mean rather than a mean of per-microbatch means.
    """
    targets = tokens[:, 1:]
    valid = (targets >= 0).astype(jnp.float32)
    logits = logits[:, :-1, :].astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)  # [B, S-1]
    logp = logits - logz[..., None]
    ll = jnp.take_along_axis(
        logp, jnp.maximum(targets, 0)[..., None], axis=-1
    ).squeeze(-1)
    return (
        jnp.sum(ll * valid),
        jnp.sum(jnp.square(logz) * valid),
        jnp.sum(valid),
    )


def lm_loss(
    logits: jax.Array, tokens: jax.Array, z_loss_coef: float = 0.0
) -> jax.Array:
    """Next-token cross-entropy in fp32. logits [B,S,V], tokens [B,S]:
    the mean over this call's valid targets (masked targets excluded).

    ``z_loss_coef > 0`` adds the PaLM-style logit-normaliser penalty
    ``coef·mean(log Z²)``, keeping softmax logits from drifting — the
    standard bf16-training stabiliser.
    """
    ll_sum, z_sum, n_valid = _ce_sums(logits, tokens)
    denom = jnp.maximum(n_valid, 1.0)
    loss = -ll_sum / denom
    if z_loss_coef:
        loss = loss + z_loss_coef * z_sum / denom
    return loss


def decode_masked_tokens(raw: jax.Array) -> tuple[jax.Array, jax.Array]:
    """In-band SFT loss masking: a position stored as ``-(token+1)`` is a
    real context token whose *prediction* must not be trained on (prompt
    tokens, padding). Returns (clean tokens for the forward pass, loss-view
    tokens where masked positions are ``-1`` so both loss paths skip them
    as targets). A no-op (identity, empty mask) for ordinary streams."""
    masked = raw < 0
    clean = jnp.where(masked, -raw - 1, raw)
    return clean, jnp.where(masked, -1, raw)


def _chunked_ce_sums(
    params: Any,
    hidden: jax.Array,
    tokens: jax.Array,
    model_cfg: tfm.ModelConfig,
    chunk: int,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Raw CE sums (see :func:`_ce_sums`) computed ``chunk`` sequence
    positions at a time, so the full fp32 [B, S, V] logits tensor is never
    materialised (at 1B scale that buffer plus its softmax temp is ~4 GB of
    HBM — often the difference between fitting a config and not). The chunk
    body is wrapped in ``jax.checkpoint`` so the backward pass recomputes
    each chunk's logits instead of keeping them alive.
    """
    B, S, D = hidden.shape
    n_chunks = S // chunk
    h = hidden.reshape(B, n_chunks, chunk, D).swapaxes(0, 1)  # [n, B, chunk, D]
    # Target for position i is tokens[i+1]; the final position has none.
    tgt = jnp.concatenate(
        [tokens[:, 1:], jnp.full((B, 1), -1, tokens.dtype)], axis=1
    ).reshape(B, n_chunks, chunk).swapaxes(0, 1)

    def body(acc, xs):
        hc, tc = xs
        logits = tfm.unembed(params, hc, model_cfg)
        logz = jax.nn.logsumexp(logits, axis=-1)
        logp = logits - logz[..., None]
        mask = tc >= 0
        ll = jnp.take_along_axis(
            logp, jnp.maximum(tc, 0)[..., None].astype(jnp.int32), axis=-1
        ).squeeze(-1)
        ll_sum, z_sum, n_sum = acc
        return (
            ll_sum + jnp.sum(ll * mask),
            z_sum + jnp.sum(jnp.square(logz) * mask),
            n_sum + jnp.sum(mask.astype(jnp.float32)),
        ), None

    (ll_total, z_total, n_total), _ = jax.lax.scan(
        jax.checkpoint(body),
        (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32),
         jnp.zeros((), jnp.float32)),
        (h, tgt),
    )
    return ll_total, z_total, n_total


def chunked_lm_loss(
    params: Any,
    hidden: jax.Array,
    tokens: jax.Array,
    model_cfg: tfm.ModelConfig,
    chunk: int,
    z_loss_coef: float = 0.0,
) -> jax.Array:
    """Chunked next-token cross-entropy — numerically identical to
    ``lm_loss(unembed(params, hidden), tokens)`` (masked targets excluded
    from the mean), with the flash-memory profile of
    :func:`_chunked_ce_sums`."""
    ll_total, z_total, n_total = _chunked_ce_sums(
        params, hidden, tokens, model_cfg, chunk
    )
    denom = jnp.maximum(n_total, 1.0)
    loss = -ll_total / denom
    if z_loss_coef:
        loss = loss + z_loss_coef * z_total / denom
    return loss


@dataclass
class TrainProgram:
    """A compiled, sharded training program bound to a mesh.

    ``init()`` creates the (sharded) train state; ``step(state, batch)`` runs
    one optimizer step over ``gradient_accumulation_steps`` microbatches.
    ``batch`` has shape [accum, global_micro_batch, seq_len] int32.
    """

    config: TPUTrainConfig
    model_config: tfm.ModelConfig
    runtime: MeshRuntime
    state_shardings: Any
    batch_sharding: NamedSharding
    init: Callable[[jax.Array], Any]
    step: Callable[[Any, jax.Array], tuple[Any, dict[str, jax.Array]]]
    # Held-out loss (no optimizer update, no MoE aux term): (state, batch) → scalar.
    eval_step: Optional[Callable[[Any, jax.Array], jax.Array]] = None
    # LoRA only: the frozen base weights and a jitted adapter→full-params
    # merge (for generation/export). None for full-parameter training.
    base_params: Any = None
    merged_params: Optional[Callable[[Any], Any]] = None
    # The RESOLVED pipeline schedule ("gpipe" | "1f1b") — config "auto"
    # is decided at build time (see build_train_program's selection rule).
    pipeline_schedule: str = "gpipe"
    # Disk-tier only: the live DiskAdamW spill store (spill_bytes(),
    # step_on_disk, masters() for export). None on in-memory programs.
    disk_store: Any = None
    # Disk-tier overlap only: joins the in-flight host walk and returns a
    # step-consistent state (params include every applied update). The
    # supervisor calls this before checkpoint saves and eval; no-op
    # (returns its argument) when nothing is in flight. None elsewhere.
    flush: Optional[Callable[[Any], Any]] = None

    @property
    def mesh(self) -> Mesh:
        return self.runtime.mesh

    def global_batch_shape(self) -> tuple[int, int, int]:
        dp = self.runtime.data_parallel_size()
        return (
            self.config.gradient_accumulation_steps,
            self.config.micro_batch_size * dp,
            self.config.seq_len,
        )

    def synthetic_batch(self, seed: int = 0) -> jax.Array:
        """Deterministic synthetic token batch (for smoke tests and benches)."""
        shape = self.global_batch_shape()
        rng = jax.random.PRNGKey(seed)
        host = jax.random.randint(rng, shape, 0, self.model_config.vocab_size, jnp.int32)
        return jax.device_put(host, self.batch_sharding)


def _flash_obstacle(
    seq_len: int, model_cfg: tfm.ModelConfig, model_axis: int
) -> Optional[str]:
    """Why the Pallas flash kernel cannot run this job (None = it can)."""
    from tpu_engine.ops._flash_pallas import tiling_obstacle

    obstacle = tiling_obstacle(seq_len)
    if obstacle is not None:
        return obstacle
    if model_cfg.n_heads % model_axis or model_cfg.n_kv_heads % model_axis:
        return (
            f"{model_cfg.n_heads} q heads / {model_cfg.n_kv_heads} kv heads do "
            f"not divide the 'model' mesh axis ({model_axis}) — sharding them "
            "unevenly would change the per-shard GQA ratio"
        )
    return None


def build_train_program(
    cfg: TPUTrainConfig,
    model_cfg: Optional[tfm.ModelConfig] = None,
    runtime: Optional[MeshRuntime] = None,
    base_params: Optional[Any] = None,
) -> TrainProgram:
    """Assemble the sharded train program for ``cfg`` on ``runtime``'s mesh.

    ``base_params`` only applies to LoRA runs (``cfg.lora_rank`` set): the
    frozen base model weights to adapt — e.g. an imported HF checkpoint
    (``tpu_engine.models.convert.from_hf_llama``). Default: deterministic
    init from ``cfg.seed``.
    """
    if model_cfg is None:
        model_cfg = tfm.MODEL_CONFIGS[cfg.model_name]
    # A hybrid (Mamba-2 + attention) stack is served only: the backward of
    # the chunked scan and packed documents are not written.
    tfm.refuse_beyond_kv(model_cfg, "training (build_train_program)")
    tfm.refuse_hybrid_mixture(model_cfg, "training (build_train_program)")
    if runtime is None:
        runtime = MeshRuntime(cfg.mesh)
    mesh = runtime.mesh
    # Attention implementation, resolved ONCE, here, from facts known now
    # (platform of the mesh's devices, seq_len, heads vs the model axis).
    # What the plan and describe() report is what the compiled step holds:
    # - a >1 'sequence' axis forces sequence-parallel attention (GSPMD alone
    #   would all-gather the sequence dim): ring by default, or the
    #   all-to-all Ulysses formulation when requested explicitly;
    # - "auto" → the Pallas flash kernel on a TPU mesh for shapes it can
    #   run, XLA attention otherwise;
    # - explicit "xla" / "flash" / "ring" / "ulysses" is honoured, and an
    #   explicit "flash" the kernel cannot run is an error, never XLA.
    on_tpu = mesh.devices.flat[0].platform == "tpu"
    if runtime.axis_sizes["sequence"] > 1:
        impl = "ulysses" if cfg.attention_impl == "ulysses" else "ring"
    else:
        obstacle = _flash_obstacle(
            cfg.seq_len, model_cfg, runtime.axis_sizes["model"]
        )
        if cfg.attention_impl == "auto":
            impl = "flash" if on_tpu and obstacle is None else "xla"
        else:
            impl = cfg.attention_impl
        if impl == "flash" and obstacle is not None:
            raise ValueError(f"attention_impl='flash' cannot run: {obstacle}")
    # Flash under pipeline parallelism: the stage vmap runs with
    # spmd_axis_name="pipe" (tpu_engine/parallel/pipeline.py), whose
    # shard_map batching rule threads the pipe axis into the kernel's
    # in/out specs — the round-2 "cannot nest inside the pipeline's vmap"
    # restriction is gone.
    if model_cfg.attention_impl != impl:
        model_cfg = model_cfg.with_(attention_impl=impl)
    if cfg.sliding_window is not None and model_cfg.sliding_window != cfg.sliding_window:
        model_cfg = model_cfg.with_(sliding_window=cfg.sliding_window)
    if cfg.moe_impl is not None:
        if not model_cfg.is_moe:
            # Checked BEFORE the no-op short-circuit: moe_impl='dense'
            # on a dense model must error like 'ragged' does, not be
            # silently swallowed because it matches the default.
            raise ValueError(
                f"moe_impl={cfg.moe_impl!r} set on the dense model "
                f"{model_cfg.name!r} (no experts to dispatch)"
            )
        if model_cfg.moe_impl != cfg.moe_impl:
            model_cfg = model_cfg.with_(moe_impl=cfg.moe_impl)
    # MXU int8 quantized training (tpu_engine/quant_train.py): resolve the
    # config knobs onto the model config exactly like attention_impl —
    # every parallelism layout's loss path reads model_cfg, so the
    # quantized-dot hook reaches plain GSPMD, comm-compressed shard_map,
    # gpipe pipeline, disk tier and offload builds alike.
    if (
        model_cfg.quant_training != cfg.quant_training
        or model_cfg.quant_train_targets != tuple(cfg.quant_train_targets)
    ):
        model_cfg = model_cfg.with_(
            quant_training=cfg.quant_training,
            quant_train_targets=tuple(cfg.quant_train_targets),
        )
    if (
        model_cfg.quant_training == "int8"
        and model_cfg.is_moe
        and model_cfg.moe_impl == "ragged"
        and "moe" in model_cfg.quant_train_targets
    ):
        # Config validation sees cfg.moe_impl=None when the MODEL preset
        # carries ragged — re-check on the resolved model config.
        raise ValueError(
            "quant_training='int8' cannot quantize ragged MoE "
            "(lax.ragged_dot takes no per-channel scales); use "
            "moe_impl='dense' or drop 'moe' from quant_train_targets"
        )
    # Reject window × sequence-parallel here, at build time, rather than
    # letting the job fail at first-step trace deep inside _attention.
    if model_cfg.sliding_window and impl in ("ring", "ulysses"):
        raise ValueError(
            f"sliding_window={model_cfg.sliding_window} is not supported with "
            f"attention_impl={impl!r} (a windowed model has no use for "
            "full-sequence context parallelism); use a mesh without a "
            "sequence axis, or set sliding_window=0"
        )
    # Ragged MoE × expert parallelism: lax.ragged_dot is a primitive GSPMD
    # cannot partition over the expert dim — sharded experts must keep the
    # dense-dispatch einsum path. Reject at build, not at first trace.
    if (
        model_cfg.is_moe
        and model_cfg.moe_impl == "ragged"
        and mesh.shape.get("model", 1) > 1
    ):
        raise ValueError(
            "moe_impl='ragged' does not support expert parallelism "
            "(ragged_dot cannot shard over the expert dim); use "
            "moe_impl='dense' on meshes with a model axis"
        )
    # Mesh is threaded into the forward pass for sequence-parallel attention
    # (shard_map over the 'sequence' axis) and for the flash kernel: its
    # devices decide compiled vs interpret mode, and on multi-device meshes
    # the kernel runs under shard_map (Mosaic calls cannot be
    # GSPMD-partitioned — see transformer._attention).
    attn_mesh = mesh if impl in ("ring", "ulysses", "flash") else None
    seq_size = runtime.axis_sizes["sequence"]
    if impl == "ulysses":
        local_heads = model_cfg.n_heads // runtime.axis_sizes["model"]
        if local_heads % seq_size != 0:
            raise ValueError(
                f"attention_impl='ulysses' needs the per-device head count "
                f"({model_cfg.n_heads} heads / model axis "
                f"{runtime.axis_sizes['model']} = {local_heads}) divisible by "
                f"the sequence axis size {seq_size}"
            )
    # ZeRO++-style comm compression (tpu_engine/comm_compress.py): the
    # grad path moves into a full-manual shard_map whose collectives are
    # explicit int8 gathers/reductions. Config validators reject most bad
    # combos; the runtime-shaped ones (resolved attention kernel, actual
    # mesh axis extents) must be re-checked here — reaching the SPMD
    # partitioner with a nested/partial-auto manual region aborts the
    # process rather than raising.
    compress = comm_compress.enabled(cfg)
    if compress:
        comm_compress.validate_runtime(cfg, runtime, model_cfg, attn_mesh=attn_mesh)

    stage = cfg.sharding_stage
    compute_dtype = cfg.compute_dtype()
    master_dtype = cfg.master_dtype()

    # Pipeline parallelism: a >1 'pipe' axis switches the step to the GPipe
    # schedule (tpu_engine/parallel/pipeline.py); the gradient-accumulation
    # microbatches become the pipeline stream.
    pipe_size = runtime.axis_sizes["pipe"]
    if pipe_size > 1 and model_cfg.n_layers % pipe_size != 0:
        raise ValueError(
            f"model n_layers={model_cfg.n_layers} must be divisible by the "
            f"pipe axis size {pipe_size}"
        )
    # Schedule auto-selection lives in sharding.resolve_pipeline_schedule
    # (one resolver shared with the launcher plan and HBM admission):
    # auto → zb at M > P when the manual-vjp schedules support the config
    # (no chunked exit loss, no quant_training custom backward, no
    # reduced-dtype grad collectives), gpipe otherwise. Measured A/B in
    # benchmarks/RESULTS.md §Pipeline.
    pipe_schedule = resolve_pipeline_schedule(cfg)
    if cfg.loss_chunk_size and cfg.seq_len % cfg.loss_chunk_size != 0:
        raise ValueError(
            f"loss_chunk_size={cfg.loss_chunk_size} must divide seq_len={cfg.seq_len}"
        )
    tfm.resolve_remat_policy(cfg.remat_policy)  # fail fast on typos
    if (
        cfg.remat_policy == "offload_dots"
        and mesh.devices.flat[0].platform != "tpu"
    ):
        raise ValueError(
            "remat_policy='offload_dots' requires TPU (the CPU SPMD "
            "partitioner cannot compile the policy's host-placement "
            "annotations)"
        )

    use_lora = cfg.lora_rank is not None
    if use_lora:
        from tpu_engine import lora as lora_mod

        lora_targets = lora_mod.validate_targets(model_cfg, cfg.lora_targets)
        if pipe_size > 1:
            raise ValueError("LoRA is not supported with pipeline parallelism")

    # Host-offloaded params (reference ZeRO-3 param CPU offload,
    # ``deepspeed_launcher.py:204-212``): the master params live in pinned
    # host memory; the forward/backward streams one layer at a time to
    # device inside the remat-wrapped scan body (weight residency stays
    # O(one layer) in both passes), and the optimizer update's param shards
    # transit device memory before the new params return to host via the
    # step's out-shardings. Fail fast on unsupported combinations rather
    # than silently ignoring the knob.
    offload_params = cfg.param_offload == OffloadDevice.HOST
    if offload_params and use_lora:
        raise ValueError(
            "param_offload is not supported with LoRA (the trainable "
            "adapters are rank-sized; offloading them saves nothing and the "
            "frozen base is better streamed via its own placement)"
        )
    if offload_params and pipe_size > 1:
        raise ValueError(
            "param_offload is not supported with pipeline parallelism "
            "(pipeline stages re-enter their layer block per microbatch; "
            "host-streaming weights per stage visit would thrash PCIe)"
        )
    if offload_params and not host_memory_kind_available(mesh):
        raise ValueError(
            "param_offload=host requires a backend with pinned_host memory "
            "support (TPU, or the JAX CPU backend)"
        )

    # Disk-tier optimizer offload (the NVMe analogue): the jitted step
    # computes + clips gradients only; masters and Adam moments live in
    # memmap spill files and a fused host AdamW applies the update
    # (tpu_engine/disk_offload.py). Config-level combos are validated by
    # TPUTrainConfig; runtime-shaped ones here.
    disk_tier = cfg.optimizer_offload == OffloadDevice.DISK
    if disk_tier and pipe_size > 1:
        raise ValueError(
            "optimizer_offload='disk' with pipeline parallelism is not "
            "supported (the host update walks the flat gradient tree)"
        )
    if (
        disk_tier
        and jax.process_count() > 1
        and cfg.sharding_stage < ShardingStage.FULL_PARTITIONING
    ):
        # Multi-host spill updates each process's ADDRESSABLE master
        # shards from the grad shards at the SAME indices — which holds
        # at ZeRO-3 (grad and param pspecs coincide). Below it, grads may
        # be reduce-scattered while params stay replicated (stage 2), and
        # per-shard pairing breaks.
        raise ValueError(
            "optimizer_offload='disk' across processes requires "
            "sharding_stage=3 (param and gradient shards must coincide "
            "per host)"
        )

    logical = tfm.logical_axes(model_cfg)

    # The *trainable* parameter space: the full model, or (LoRA) only the
    # rank-sized adapter tree — grads/optimizer state/checkpoints follow it.
    train_logical = lora_mod.lora_logical_axes(logical, lora_targets) if use_lora else logical
    p_pspecs = param_pspecs(train_logical, stage)
    g_pspecs = grad_pspecs(train_logical, stage)
    o_pspecs = opt_state_pspecs(train_logical, stage)

    param_sh = named_shardings(
        mesh, p_pspecs, memory_kind="pinned_host" if offload_params else None
    )
    # Full-model sharding: for LoRA this differs from the trainable tree's
    # (frozen base + merged exports); otherwise it IS the trainable one.
    full_param_sh = (
        named_shardings(mesh, param_pspecs(logical, stage)) if use_lora else param_sh
    )

    # The per-layer slice sharding: the stacked spec minus its leading layer
    # dimension. Used by the offload streaming hook and by the in-body
    # sharding anchor below.
    def _slice_spec(spec: P) -> P:
        parts = tuple(spec)
        return P(*parts[1:]) if parts else P()

    # Anchor each layer's sliced weights (and, through the constraint's
    # transpose, their cotangents) to their canonical shardings inside the
    # scan body. GSPMD sharding propagation through the remat-wrapped
    # backward loses the weight layout once manual (shard_map) regions —
    # the Pallas flash kernel — interrupt propagation, and the partitioner
    # then fully rematerialises (all-gathers) per-layer weights that should
    # stay sharded. One explicit constraint per slice removes the ambiguity
    # at zero cost when the layout already matches.
    # Under comm compression the loss runs inside a full-manual shard_map
    # region, where with_sharding_constraint is illegal (there is no GSPMD
    # propagation to anchor) — the explicit gathers pin every layout.
    layer_constraint = None
    if mesh.size > 1 and not compress:
        _full_layer_pspecs = (
            param_pspecs(logical, stage)["layers"] if use_lora
            else p_pspecs["layers"]
        )
        _layer_anchor_sh = named_shardings(
            mesh,
            jax.tree.map(
                _slice_spec, _full_layer_pspecs, is_leaf=lambda x: isinstance(x, P)
            ),
        )

        def layer_constraint(layer):
            return jax.lax.with_sharding_constraint(layer, _layer_anchor_sh)

    layer_stream = None
    if offload_params:
        # Per-layer pinned_host→device transfer + compute cast, applied
        # inside the scan body (tfm.remat_scan_body).
        layer_slice_sh = named_shardings(
            mesh,
            jax.tree.map(
                _slice_spec, p_pspecs["layers"], is_leaf=lambda x: isinstance(x, P)
            ),
            memory_kind="device",
        )

        def layer_stream(layer):
            moved = jax.tree.map(jax.device_put, layer, layer_slice_sh)
            return jax.tree.map(
                lambda a: a.astype(compute_dtype)
                if jnp.issubdtype(a.dtype, jnp.floating)
                else a,
                moved,
            )

        # Non-layer params (embeddings, final norm, head — O(vocab·d), a
        # sliver of the total) get an explicit on-device view per loss call:
        # XLA requires operands of one op to share a memory space, and
        # jnp.take/einsum consume these directly. Their cotangents still
        # accumulate in device space (device_put's transpose does not bounce
        # them through host).
        _nonlayer_dev_sh = {
            k: named_shardings(mesh, v, memory_kind="device")
            for k, v in p_pspecs.items()
            if k != "layers"
        }

        def _device_view(params):
            out = dict(params)
            for k, sh in _nonlayer_dev_sh.items():
                out[k] = jax.tree.map(jax.device_put, params[k], sh)
            return out
    else:
        def _device_view(params):
            return params

    if use_lora:
        if base_params is None:
            base_params = jax.jit(
                lambda rng: tfm.init_params(rng, model_cfg, dtype=master_dtype),
                out_shardings=full_param_sh,
            )(jax.random.PRNGKey(cfg.seed))
        else:
            base_params = jax.device_put(base_params, full_param_sh)

    # Optimizer-state offload: pinned host memory (reference CPU offload,
    # ``deepspeed_launcher.py:197-203``).
    opt_memory_kind = None
    if cfg.optimizer_offload == OffloadDevice.HOST:
        if not host_memory_kind_available(mesh):
            raise ValueError(
                "optimizer_offload=host requires a backend with pinned_host "
                "memory support (TPU, or the JAX CPU backend)"
            )
        opt_memory_kind = "pinned_host"
    opt_leaf_sh = named_shardings(mesh, o_pspecs, memory_kind=opt_memory_kind)
    grad_sh = named_shardings(mesh, g_pspecs)
    replicated = NamedSharding(mesh, P())

    tx, schedule = make_optimizer(cfg)

    def init_fn(rng: jax.Array) -> dict[str, Any]:
        if use_lora:
            params = lora_mod.init_lora_params(
                rng, model_cfg, cfg.lora_rank, lora_targets, dtype=master_dtype
            )
        else:
            params = tfm.init_params(rng, model_cfg, dtype=master_dtype)
        opt_state = tx.init(params)
        return {
            "params": params,
            "opt_state": opt_state,
            "step": jnp.zeros((), jnp.int32),
            "lr_scale": jnp.ones((), jnp.float32),
        }

    # Optimizer-state sharding tree: leaves shaped like params take the
    # opt pspecs; everything else (counts, schedule state, Adafactor's
    # factored row/col statistics — param-pathed but differently shaped)
    # replicates.
    def _opt_state_shardings(opt_state_shape, param_shapes) -> Any:
        flat_param_sh = {id_path: sh for id_path, sh in _path_leaves(opt_leaf_sh)}
        flat_param_shape = {
            id_path: leaf.shape for id_path, leaf in _path_leaves(param_shapes)
        }

        def assign(path, leaf):
            # Leaves inside the opt state that mirror a param (mu/nu) carry
            # the param's path as a suffix; match on path AND shape (a
            # factored statistic shares the path but not the shape).
            for p_path, sh in flat_param_sh.items():
                if _path_endswith(path, p_path):
                    if getattr(leaf, "shape", None) == flat_param_shape.get(p_path):
                        return sh
                    return replicated
            return replicated

        return _tree_map_with_path(assign, opt_state_shape)

    state_shape = jax.eval_shape(init_fn, jax.random.PRNGKey(0))
    state_shardings = {
        "params": param_sh,
        "opt_state": _opt_state_shardings(state_shape["opt_state"], state_shape["params"]),
        "step": replicated,
        "lr_scale": replicated,
    }

    opt_sh_tree = state_shardings["opt_state"]

    def _device_kinds(sh_tree):
        """The same sharding specs with the default (device) memory kind."""
        return jax.tree.map(
            lambda sh: NamedSharding(mesh, sh.spec),
            sh_tree,
            is_leaf=lambda x: isinstance(x, NamedSharding),
        )

    # Initialise with device memory kinds, then place offloaded subtrees in
    # pinned host memory with a one-time device_put outside jit: mixed-kind
    # out-shardings on constant outputs trip the SPMD partitioner's
    # placement-annotation handling (observed on the CPU backend), and init
    # runs once — the transfer is free relative to compile.
    has_host_kinds = offload_params or opt_memory_kind is not None
    if has_host_kinds:
        _jit_init = jax.jit(init_fn, out_shardings=_device_kinds(state_shardings))

        def jit_init(rng):
            return jax.device_put(_jit_init(rng), state_shardings)
    else:
        jit_init = jax.jit(init_fn, out_shardings=state_shardings)

    seq_ax = "sequence" if runtime.axis_sizes["sequence"] > 1 else None
    batch_sharding = NamedSharding(mesh, P(None, BATCH_AXES, seq_ax))

    def loss_fn(params, raw_tokens, include_aux: bool = True, lora_params=None,
                denom=None, aux_weight: float = 1.0):
        """Masked LM loss for one microbatch.

        ``denom=None`` → this microbatch's own valid-target mean. With a
        ``denom`` (the batch-wide valid count), returns raw sums divided by
        it, so summing over microbatches yields the *global* valid-target
        mean — not a mean of per-microbatch means, which would up-weight
        tokens in sparsely-supervised (heavily masked) microbatches.
        ``aux_weight`` scales the MoE router term (1/accum when summing).
        """
        # In-band SFT masking: -(t+1) positions are context-only (no loss).
        tokens, loss_tokens = decode_masked_tokens(raw_tokens)
        params = _device_view(params)  # no-op unless param_offload
        hidden, aux = tfm.forward_hidden_and_aux(
            params,
            tokens,
            model_cfg,
            compute_dtype=compute_dtype,
            remat=cfg.activation_checkpointing,
            remat_policy=cfg.remat_policy,
            mesh=attn_mesh,
            lora=lora_params,
            lora_scale=(cfg.lora_alpha / cfg.lora_rank) if use_lora else 1.0,
            layer_stream=layer_stream,
            layer_constraint=layer_constraint,
        )
        with jax.named_scope("loss"):
            # include_aux gates the training-only regularisers (MoE aux, z-loss)
            # so eval_step reports pure cross-entropy.
            z_coef = cfg.z_loss_coef if include_aux else 0.0
            if cfg.loss_chunk_size:
                ll_sum, z_sum, n_valid = _chunked_ce_sums(
                    params, hidden, loss_tokens, model_cfg, cfg.loss_chunk_size
                )
            else:
                ll_sum, z_sum, n_valid = _ce_sums(
                    tfm.unembed(params, hidden, model_cfg), loss_tokens
                )
            d = jnp.maximum(n_valid, 1.0) if denom is None else denom
            loss = -ll_sum / d
            if z_coef:
                loss = loss + z_coef * z_sum / d
            if model_cfg.is_moe and include_aux:
                loss = loss + aux_weight * model_cfg.router_aux_coef * aux
        return loss

    if use_lora:
        # Trainable space = adapters, applied activation-side inside each
        # projection (h@A@B — never a full ΔW, so cotangents stay
        # rank-sized). The frozen base enters the compiled step as captured
        # constants.
        def train_loss_fn(adapter_params, tokens, include_aux: bool = True,
                          denom=None, aux_weight: float = 1.0):
            return loss_fn(base_params, tokens, include_aux,
                           lora_params=adapter_params, denom=denom,
                           aux_weight=aux_weight)
    else:
        train_loss_fn = loss_fn

    grad_fn = jax.value_and_grad(train_loss_fn)

    # Compressed gradient path: one full-manual shard_map per microbatch.
    # Inside it ``train_loss_fn`` sees locally-sharded tokens and the
    # gathered (dequantized) params, and its raw-sums/global-denom form
    # makes the per-device losses sum to exactly the GSPMD objective.
    compression = None
    if compress:
        compression = comm_compress.build(
            mesh=mesh,
            loss_fn=train_loss_fn,
            pspecs=p_pspecs,
            abs_params=state_shape["params"],
            grad_sh=grad_sh,
            data_size=runtime.axis_sizes["data"],
            fsdp_size=runtime.axis_sizes["fsdp"],
            dcn_data=cfg.mesh.dcn_data,
            quant_weights=cfg.comm_quant_weights,
            secondary_weights=cfg.comm_secondary_weights,
            quant_grads=cfg.comm_quant_grads,
            block_size=cfg.comm_quant_block_size,
            dtype=compute_dtype,
        )
        if compression.refresh is not None:
            # hpZ: the secondary int8 store rides the train state so the
            # steady-state step never re-quantizes (and restores resume
            # with a consistent replica via init/refresh).
            hpz_sh = jax.tree.map(
                lambda spec: NamedSharding(mesh, spec),
                compression.hpz_pspecs,
                is_leaf=lambda x: isinstance(x, P),
            )
            state_shardings = {**state_shardings, "hpz": hpz_sh}
            _base_init = init_fn

            def init_fn(rng: jax.Array) -> dict[str, Any]:
                state = _base_init(rng)
                state["hpz"] = compression.refresh(state["params"])
                return state

            # compress excludes every host-memory-kind combo, so the
            # simple jit path is always the one being replaced here.
            jit_init = jax.jit(init_fn, out_shardings=state_shardings)

    # ---- pipelined loss (pipe axis > 1): one forward over all microbatches,
    # streamed through the stages; autodiff gives the reverse pipeline. ----
    if pipe_size > 1:
        from tpu_engine.parallel.pipeline import pipeline_apply, stage_layer_stack

        def _staged_spec(spec: P) -> P:
            parts = tuple(spec)
            return P(parts[0] if parts else None, None, *parts[1:])

        staged_sh = named_shardings(
            mesh,
            jax.tree.map(_staged_spec, p_pspecs["layers"], is_leaf=lambda x: isinstance(x, P)),
        )
        buf_sh = NamedSharding(mesh, P("pipe", BATCH_AXES, seq_ax))

        def _pipe_prologue(raw_batch):
            """Shared GPipe/1F1B front half: in-band SFT mask decode,
            positions, staged (cast, pipe-sharded) layer stack, and the
            batch-wide valid-target denominator — ONE place so the two
            schedules' objectives cannot silently diverge. Returns
            (batch, loss_batch, positions, staged_builder, denom)."""
            batch, loss_batch = decode_masked_tokens(raw_batch)
            B, S = batch.shape[1], batch.shape[2]
            positions = jnp.broadcast_to(
                jnp.arange(S, dtype=jnp.int32)[None, :], (B, S)
            )

            def staged_of(p):
                staged = stage_layer_stack(
                    tfm.cast_layer_stack(p, compute_dtype), pipe_size,
                    model_cfg.n_layers,
                )
                return jax.lax.with_sharding_constraint(staged, staged_sh)

            denom = jnp.maximum(
                jnp.sum((loss_batch[:, :, 1:] >= 0).astype(jnp.float32)), 1.0
            )
            return batch, loss_batch, positions, staged_of, denom

        def pipe_loss_fn(params, raw_batch, include_aux: bool = True):
            batch, loss_batch, positions, staged_of, denom = _pipe_prologue(
                raw_batch
            )
            # positions also feed learned absolute embeddings (gpt2 family).
            x_mb = tfm.embed_tokens(params, batch, compute_dtype,
                                    positions=positions,
                                    cfg=model_cfg)  # [M, B, S, D]
            staged = staged_of(params)
            outputs, aux_mean = pipeline_apply(
                staged,
                x_mb,
                model_cfg,
                positions=positions,
                mesh=attn_mesh,
                remat=cfg.activation_checkpointing,
                remat_policy=cfg.remat_policy,
                buf_sharding=buf_sh,
                layer_constraint=layer_constraint,
            )

            z_coef = cfg.z_loss_coef if include_aux else 0.0

            def loss_body(acc, xs):
                out, toks = xs
                if cfg.loss_chunk_size:
                    ll, zz, _ = _chunked_ce_sums(
                        params, out, toks, model_cfg, cfg.loss_chunk_size
                    )
                else:
                    ll, zz, _ = _ce_sums(tfm.unembed(params, out, model_cfg), toks)
                return acc + (-ll + z_coef * zz), None

            body = jax.checkpoint(loss_body) if cfg.activation_checkpointing else loss_body
            loss_sum, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), (outputs, loss_batch))
            loss = loss_sum / denom
            if model_cfg.is_moe and include_aux:
                loss = loss + model_cfg.router_aux_coef * aux_mean
            return loss

        pipe_grad_fn = jax.value_and_grad(pipe_loss_fn)

        if pipe_schedule in ("1f1b", "zb"):
            # Manual per-stage-vjp schedules: O(P) in-flight stage inputs
            # instead of GPipe-by-autodiff's O(M + P) saved boundary
            # buffers. "1f1b" interleaves one forward and one combined
            # backward per tick (tpu_engine/parallel/pipeline_1f1b.py);
            # "zb" additionally splits the drain backwards into B/W phases
            # and retires deferred weight gradients in lanes 1f1b burns as
            # masked bubble compute (tpu_engine/parallel/pipeline_zb.py).
            # Both take the same arguments and return the same gradient
            # pieces — the schedules are pure reorderings of the same
            # per-stage vjps. Gradients are assembled manually — no
            # jax.grad above this.
            if cfg.loss_chunk_size:
                raise ValueError(
                    f"loss_chunk_size is not supported with "
                    f"pipeline_schedule={pipe_schedule!r} (the exit loss "
                    "runs inside the schedule's scan)"
                )
            from tpu_engine.parallel.pipeline_1f1b import pipeline_1f1b_grads
            from tpu_engine.parallel.pipeline_zb import pipeline_zb_grads

            schedule_grads = (
                pipeline_zb_grads if pipe_schedule == "zb"
                else pipeline_1f1b_grads
            )

            def pipe_grad_fn(params, raw_batch):  # noqa: F811 — manual-vjp override
                batch, loss_batch, positions, staged_of, denom = (
                    _pipe_prologue(raw_batch)
                )
                accum = batch.shape[0]
                x_mb, embed_vjp = jax.vjp(
                    lambda p: tfm.embed_tokens(
                        p, batch, compute_dtype, positions=positions,
                        cfg=model_cfg,
                    ),
                    params,
                )
                staged = staged_of(params)
                z_coef = cfg.z_loss_coef
                outer_sub = {k: v for k, v in params.items() if k != "layers"}

                def exit_scalar(outer, y, toks):
                    ll, zz, _ = _ce_sums(tfm.unembed(outer, y, model_cfg), toks)
                    return (-ll + z_coef * zz) / denom

                def exit_fn(y, toks):
                    val, vjp = jax.vjp(
                        lambda o, yy: exit_scalar(o, yy, toks), outer_sub, y
                    )
                    d_outer, dy = vjp(jnp.ones((), jnp.float32))
                    return val, dy, d_outer

                outer_zero = jax.tree.map(
                    lambda a: jnp.zeros(a.shape, jnp.float32), outer_sub
                )
                aux_cot = (
                    model_cfg.router_aux_coef / (model_cfg.n_layers * accum)
                    if model_cfg.is_moe else 0.0
                )
                loss_sum, aux_sum, dstaged, d_outer, dx_mb = schedule_grads(
                    staged, x_mb, loss_batch, model_cfg,
                    positions=positions, exit_fn=exit_fn,
                    outer_grad_zero=outer_zero, mesh=attn_mesh,
                    remat=cfg.activation_checkpointing,
                    remat_policy=cfg.remat_policy,
                    buf_sharding=buf_sh, aux_cotangent=aux_cot,
                    layer_constraint=layer_constraint,
                )
                # Assemble the full gradient tree: embedding cotangent from
                # dx_mb, stage grads reshaped back to the [L, ...] stack
                # (the bf16 cast's vjp is the cast back), and the exit-side
                # outer grads (final norm, head, tied embedding).
                (grads,) = embed_vjp(dx_mb)
                grads = jax.tree.map(lambda a: a.astype(jnp.float32), grads)
                L = model_cfg.n_layers
                d_layers = jax.tree.map(
                    lambda a: a.reshape((L,) + a.shape[2:]), dstaged
                )
                grads["layers"] = jax.tree.map(
                    lambda a, b: a + b, grads["layers"], d_layers
                )
                for k, v in d_outer.items():
                    grads[k] = jax.tree.map(
                        lambda a, b: a + b.astype(jnp.float32), grads[k], v
                    )
                loss = loss_sum
                if model_cfg.is_moe:
                    loss = loss + model_cfg.router_aux_coef * aux_sum / (
                        model_cfg.n_layers * accum
                    )
                return loss, grads

    # Gradient collective dtype (reference ``communication_data_type``,
    # ``deepspeed_launcher.py:60-62,167-169``). A post-hoc cast cannot move
    # the collective's dtype — XLA inserts the grad reduction inside the
    # backward pass, upstream of anything applied to ``grad_fn``'s result.
    # The mechanism that works (and is what DeepSpeed's fp16-grads mode
    # actually does) is differentiating with respect to the *compute-dtype*
    # params: the whole cotangent chain, including the reduction point,
    # then carries the comm dtype; the upcast to fp32 happens once, after
    # the sharding constraint, for accumulation and the master update.
    # Config validation guarantees comm dtype == compute dtype (or fp32).
    comm_dtype = (
        dtype_of(cfg.grad_allreduce_dtype)
        if cfg.grad_allreduce_dtype is not None
        else None
    )
    reduced_comm = comm_dtype is not None and comm_dtype != jnp.float32
    if reduced_comm and pipe_size > 1 and pipe_schedule in ("1f1b", "zb"):
        raise ValueError(
            f"grad_allreduce_dtype with pipeline_schedule="
            f"{pipe_schedule!r} is not supported: the manual-vjp schedule "
            "accumulates gradients in fp32 inside its scan, so the "
            "reduced-dtype collective the option exists for would never "
            "materialise (use 'gpipe', or drop grad_allreduce_dtype)"
        )
    if reduced_comm and offload_params:
        raise ValueError(
            "grad_allreduce_dtype with param_offload=host is not supported: "
            "offloaded layers already stream in the compute dtype, and the "
            "host-resident master tree cannot be re-cast in device code"
        )

    def _cast_for_grad(params):
        if not reduced_comm:
            return params
        return jax.tree.map(
            lambda p: p.astype(comm_dtype)
            if jnp.issubdtype(p.dtype, jnp.floating)
            else p,
            params,
        )

    def _reduce_grads(grads):
        # Grads arrive in the comm dtype (reduced_comm) or fp32; the
        # constraint pins where XLA materialises the reduce-scatter /
        # all-reduce (stage >= 2: sharded — ZeRO-2 semantics).
        grads = jax.lax.with_sharding_constraint(grads, grad_sh)
        return jax.tree.map(lambda g: g.astype(jnp.float32), grads)

    def train_step(state, batch):
        params = state["params"]
        params_g = _cast_for_grad(params)

        # named_scope: metadata for profiles only, nothing computed changes.
        with jax.named_scope("forward_backward"):
            if pipe_size > 1:
                loss, grads = pipe_grad_fn(params_g, batch)
                grads = _reduce_grads(grads)
            elif compression is not None:
                # Step-deterministic key for qgZ's stochastic rounding (and
                # restart-reproducible: derived from seed + step, not a
                # threaded RNG state).
                qkey = jax.random.fold_in(
                    jax.random.PRNGKey(cfg.seed), state["step"]
                )
                loss, grads = compression.accumulate(
                    params_g, state.get("hpz"), batch, qkey
                )
            else:
                loss, grads = accumulate_grads(
                    grad_fn, _reduce_grads, params_g, params, batch, grad_sh
                )
        with jax.named_scope("optimizer"):
            grad_norm = optax.global_norm(grads)

            # Offloaded subtrees stream through device memory for the update
            # math (the per-device transient is the 1/N shard — reference
            # "streamed to device inside the update", ``deepspeed_launcher.py:
            # 197-203``) and are placed back in pinned host memory explicitly,
            # so the step's out-shardings see already-host-resident values.
            opt_in = state["opt_state"]
            if opt_memory_kind is not None:
                opt_in = jax.tree.map(jax.device_put, opt_in, _device_kinds(opt_sh_tree))
            params_upd = params
            if offload_params:
                params_upd = jax.tree.map(jax.device_put, params, _device_kinds(param_sh))

            lr = schedule(state["step"]).astype(jnp.float32) * state["lr_scale"]
            updates, new_opt_state = tx.update(grads, opt_in, params_upd)
            updates = jax.tree.map(lambda u: (-lr * u).astype(u.dtype), updates)
            new_params = optax.apply_updates(params_upd, updates)
        new_state = {
            "params": new_params,
            "opt_state": new_opt_state,
            "step": state["step"] + 1,
            "lr_scale": state["lr_scale"],
        }
        if compression is not None and compression.refresh is not None:
            # hpZ refresh: re-quantize the secondary store from the
            # just-updated primary partition, once per optimizer step.
            new_state["hpz"] = compression.refresh(new_params)
        metrics = {
            "loss": loss,
            "grad_norm": grad_norm,
            "learning_rate": lr,
            "step": new_state["step"],
        }
        return new_state, metrics

    # Host-kind out-shardings are the production (TPU) path: the updated
    # offloaded subtrees materialise straight into pinned host memory. The
    # CPU backend's SPMD partitioner cannot compile placement-annotated
    # outputs (RET_CHECK on the annotation it puts on replicated scalars)
    # and silently drops in-body host placements — so off-TPU the step
    # computes with device-kind outputs and the offloaded subtrees are
    # re-placed on host with a device_put *outside* jit. Semantically
    # identical; the CPU path exists so the 8-virtual-device test mesh can
    # exercise offloaded configs at all.
    if has_host_kinds and not on_tpu:
        _jit_step = jax.jit(
            train_step,
            in_shardings=(state_shardings, batch_sharding),
            out_shardings=None,
        )

        def jit_step(state, batch):
            new_state, metrics = _jit_step(state, batch)
            return jax.device_put(new_state, state_shardings), metrics
    else:
        jit_step = jax.jit(
            train_step,
            in_shardings=(state_shardings, batch_sharding),
            out_shardings=(state_shardings, None),
            donate_argnums=(0,),
        )

    def eval_step(state, batch):
        """Held-out loss over one [accum, B, S] batch — pure cross-entropy
        (no MoE aux term, so exp(loss) is an honest perplexity), no update."""
        params = state["params"]
        if pipe_size > 1:
            return pipe_loss_fn(params, batch, include_aux=False)

        denom = jnp.maximum(
            jnp.sum((batch[:, :, 1:] >= 0).astype(jnp.float32)), 1.0
        )

        def body(acc, tokens):
            return acc + train_loss_fn(params, tokens, include_aux=False,
                                       denom=denom), None

        loss_sum, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), batch)
        return loss_sum

    jit_eval = jax.jit(
        eval_step, in_shardings=(state_shardings, batch_sharding), out_shardings=None
    )

    merged_fn = None
    if use_lora:
        # Merged tree in the compute dtype: generation casts to it anyway,
        # and at bf16 the one-off merged copy is half the master-dtype size.
        merged_fn = jax.jit(
            lambda adapters: jax.tree.map(
                lambda a: a.astype(compute_dtype),
                lora_mod.merge_lora(
                    base_params, adapters, cfg.lora_alpha, cfg.lora_rank
                ),
            ),
            out_shardings=full_param_sh,
        )

    if disk_tier:
        return _assemble_disk_tier(
            cfg, model_cfg, runtime, mesh, schedule, grad_fn,
            _cast_for_grad, _reduce_grads, eval_step,
            param_sh=param_sh, grad_sh=grad_sh, replicated=replicated,
            batch_sharding=batch_sharding,
            compute_dtype=compute_dtype, master_dtype=master_dtype,
            pipe_schedule=pipe_schedule,
        )

    return TrainProgram(
        config=cfg,
        model_config=model_cfg,
        runtime=runtime,
        state_shardings=state_shardings,
        batch_sharding=batch_sharding,
        init=jit_init,
        step=jit_step,
        eval_step=jit_eval,
        base_params=base_params if use_lora else None,
        merged_params=merged_fn,
        pipeline_schedule=pipe_schedule,
    )


def _assemble_disk_tier(
    cfg, model_cfg, runtime, mesh, schedule, grad_fn,
    _cast_for_grad, _reduce_grads, eval_step, *,
    param_sh, grad_sh, replicated, batch_sharding,
    compute_dtype, master_dtype, pipe_schedule,
) -> TrainProgram:
    """Disk-tier (NVMe-analogue) program: device = forward/backward/clip
    on compute-dtype params; host = fused AdamW over memmap spill slabs
    (``tpu_engine/disk_offload.py``). The train state carries NO
    optimizer state and the params at COMPUTE dtype — HBM holds exactly
    what the forward pass reads.

    Rollback/restore semantics: the spill persists its applied-step
    count; when the incoming state's step disagrees (supervisor rollback,
    a restart that restored an older checkpoint, or a fresh run reusing
    a spill dir), masters reseed from the restored params with the Adam
    moments ZEROED and the bias-correction counter reset — exactly the
    behavior of loading a checkpoint without optimizer state. Where a
    master still rounds to the incoming compute-dtype value it is kept
    at full precision (see ``reseed_masters`` ``cast_dtype``).
    """
    import numpy as np

    from tpu_engine import disk_offload as dsk

    state_shardings = {
        "params": param_sh,
        "step": replicated,
        "lr_scale": replicated,
    }
    flat_param_sh = dsk.flatten_with_paths(param_sh)

    def _to_compute(params):
        return jax.tree.map(
            lambda a: a.astype(compute_dtype)
            if jnp.issubdtype(a.dtype, jnp.floating) else a,
            params,
        )

    def _decay_mask(params):
        if cfg.decay_all_params:
            return jax.tree.map(lambda _: True, params)
        return kernel_decay_mask(params)

    # Each process spills under its own subdirectory — slab files hold
    # only the shards ITS devices own (single-process runs keep the flat
    # directory, so existing spills still re-attach).
    spill_dir = cfg.optimizer_spill_dir
    if jax.process_count() > 1:
        spill_dir = os.path.join(spill_dir, f"proc{jax.process_index()}")
        if jax.process_index() == 0 and os.path.isdir(cfg.optimizer_spill_dir):
            # A dir previously used single-process holds FLAT slab files
            # this multi-host run will never touch — clean them (proc 0
            # only; they are stale for this layout either way).
            for f in os.listdir(cfg.optimizer_spill_dir):
                if f.endswith(".f32") or f == "disk_adamw.json":
                    try:
                        os.remove(os.path.join(cfg.optimizer_spill_dir, f))
                    except OSError:
                        pass

    def _all_hosts(flag: bool) -> bool:
        """Cross-process consensus on a local boolean (True only when
        EVERY process reports True). Attach/reseed decisions must agree
        cluster-wide: a host that attaches warm moments while another
        reseeds fresh would stitch a global tree from divergent
        trajectories — silently."""
        if jax.process_count() == 1:
            return flag
        from jax.experimental import multihost_utils

        flags = multihost_utils.process_allgather(
            np.asarray([bool(flag)])
        )
        return bool(np.all(flags))
    store = dsk.DiskAdamW(
        spill_dir, b1=cfg.beta1, b2=cfg.beta2, eps=1e-8,
        weight_decay=cfg.weight_decay,
    )

    _abs_params = jax.eval_shape(
        lambda r: tfm.init_params(r, model_cfg, dtype=master_dtype),
        jax.random.PRNGKey(0),
    )
    _abs_flat = dsk.flatten_with_paths(_abs_params)
    _flat_mask_by_leaf = dsk.flatten_with_paths(_decay_mask(_abs_params))

    # ---- shard-granular slab layout (multi-host / multi-device) ----------
    # Slabs are keyed per unique addressable shard of each leaf:
    # ``path`` when one full-leaf shard (replicated or single device —
    # backward-compatible with existing spills), ``path@a-b_c-d…``
    # otherwise. AdamW is elementwise, so every shard updates
    # independently; no cross-shard (or cross-host) communication exists
    # in the walk at all.

    def _suffix(shape, index) -> str:
        if not index or all(
            (s.start in (None, 0)) and (s.stop in (None, dim))
            for s, dim in zip(index, shape)
        ):
            return ""
        return "@" + "_".join(
            f"{0 if s.start is None else s.start}-"
            f"{dim if s.stop is None else s.stop}"
            for s, dim in zip(index, shape)
        )

    def _index_shape(shape, index):
        if not index:
            return tuple(shape)
        return tuple(
            (dim if s.stop is None else s.stop)
            - (0 if s.start is None else s.start)
            for s, dim in zip(index, shape)
        )

    # key → (leaf path, suffix, index slices, [devices holding the shard])
    _key_info: dict[str, tuple[str, str, tuple, list]] = {}
    for _path, _abs in _abs_flat.items():
        _shape = tuple(_abs.shape)
        _by_sig: dict[str, tuple] = {}
        for _dev, _idx in flat_param_sh[_path] \
                .addressable_devices_indices_map(_shape).items():
            _sig = _suffix(_shape, _idx)
            if _sig in _by_sig:
                _by_sig[_sig][1].append(_dev)
            else:
                _by_sig[_sig] = (_idx, [_dev])
        for _sig, (_idx, _devs) in sorted(_by_sig.items()):
            _key_info[_path + _sig] = (_path, _sig, tuple(_idx), _devs)

    _flat_shapes = {
        key: _index_shape(tuple(_abs_flat[path].shape), idx)
        for key, (path, _, idx, _) in _key_info.items()
    }
    _flat_mask = {
        key: _flat_mask_by_leaf[path]
        for key, (path, _, _, _) in _key_info.items()
    }
    _leaf_shapes = {p: tuple(a.shape) for p, a in _abs_flat.items()}

    def _shard_host(arr, path: str, sig: str, idx: tuple) -> np.ndarray:
        """The block of ``arr`` matching a slab key's index signature, as
        a host fp32 array. Prefers a matching addressable shard (no
        cross-device traffic); when the array's own sharding differs from
        the slab layout (e.g. stage-2 grads are fsdp-sharded while the
        params the slabs mirror are replicated), a single process falls
        back to materialising the leaf and slicing — cross-process that
        mismatch is rejected at build time."""
        shape = tuple(arr.shape)
        for s in arr.addressable_shards:
            if _suffix(shape, s.index) == sig:
                return np.asarray(jax.device_get(s.data), np.float32)
        if jax.process_count() == 1:
            return np.asarray(jax.device_get(arr), np.float32)[
                tuple(idx) if idx else ()
            ]
        raise ValueError(
            f"leaf {path}: no addressable shard matches slab key suffix "
            f"{sig!r} (sharding changed under the spill?)"
        )

    def _leaf_fetcher(params):
        """key → fp32 host block, ONE shard at a time — the full fp32
        tree must never be host-resident at once (the tier targets models
        where it cannot be)."""
        flat = dsk.flatten_with_paths(params)

        def fetch(key):
            path, sig, idx, _ = _key_info[key]
            return _shard_host(flat[path], path, sig, idx)

        return fetch

    def _grad_fetchers(grads):
        """key → deferred host fetch of the matching gradient shard (the
        walk's prefetch thread calls these one ahead of the update)."""
        flat = dsk.flatten_with_paths(grads)
        return {
            key: (lambda a=flat[path], p=path, s=sig, i=idx:
                  _shard_host(a, p, s, i))
            for key, (path, sig, idx, _) in _key_info.items()
        }

    def _make_uploader():
        return dsk.AsyncShardUploader(
            {key: (path, devs) for key, (path, _, _, devs) in _key_info.items()},
            _leaf_shapes, flat_param_sh, compute_dtype,
        )

    def _ensure_store(params) -> bool:
        """Attach if a clean matching spill exists ON EVERY HOST
        (shape-only check — no device fetch); otherwise ALL hosts seed a
        fresh spill from ``params`` (one host's lost/torn spill forces a
        cluster-wide reseed — mixed warm/fresh moments would silently
        diverge the stitched global state)."""
        attached = bool(store.slabs) or store.try_attach(_flat_shapes, _flat_mask)
        if _all_hosts(attached):
            return True
        return store.initialize(_leaf_fetcher(params), _flat_mask,
                                shapes=_flat_shapes, force_fresh=True)

    def _params_from_masters():
        # Shard-at-a-time through the SAME uploader the update walk uses
        # (one implementation of the block-stitch): copy one master slab,
        # cast, device_put to the shard's devices, assemble global arrays.
        up = _make_uploader()
        try:
            for key, slab in store.slabs.items():
                up.emit(key, slab.master)
        finally:
            up.close()
        return dsk.unflatten_like(_abs_params, up.result())

    def disk_init(rng):
        def pure(r):
            return {
                "params": _to_compute(
                    tfm.init_params(r, model_cfg, dtype=master_dtype)
                ),
                "step": jnp.zeros((), jnp.int32),
                "lr_scale": jnp.ones((), jnp.float32),
            }

        if isinstance(rng, jax.core.Tracer):
            # eval_shape path (the supervisor derives state shapes by
            # tracing init) — no host I/O under a tracer.
            return pure(rng)
        if _all_hosts(
            bool(store.slabs) or store.try_attach(_flat_shapes, _flat_mask)
        ):
            # A matching clean spill exists on EVERY host: its masters
            # are the truth (warm restart) — no throwaway random init,
            # no D2H fetch.
            params = _params_from_masters()
        else:
            masters = jax.jit(
                lambda r: tfm.init_params(r, model_cfg, dtype=master_dtype),
                out_shardings=param_sh,
            )(rng)
            # force_fresh: a host that COULD attach must still reseed
            # when any peer cannot (cluster-wide agreement).
            store.initialize(_leaf_fetcher(masters), _flat_mask,
                             shapes=_flat_shapes, force_fresh=True)
            params = jax.jit(
                _to_compute, donate_argnums=(0,), out_shardings=param_sh
            )(masters)
        _verified_step[0] = None  # init/attach: first step re-checks
        return {
            "params": params,
            "step": jax.device_put(jnp.zeros((), jnp.int32), replicated),
            "lr_scale": jax.device_put(jnp.ones((), jnp.float32), replicated),
        }

    def grad_step(state, batch):
        params_g = _cast_for_grad(state["params"])
        loss, grads = accumulate_grads(
            grad_fn, _reduce_grads, params_g, state["params"], batch, grad_sh
        )
        grad_norm = optax.global_norm(grads)
        # optax.clip_by_global_norm semantics: scale = min(1, clip/norm).
        scale = jnp.minimum(
            1.0, cfg.grad_clip_norm / jnp.maximum(grad_norm, 1e-12)
        )
        grads = jax.tree.map(lambda g: g * scale, grads)
        lr = schedule(state["step"]).astype(jnp.float32) * state["lr_scale"]
        metrics = {
            "loss": loss,
            "grad_norm": grad_norm,
            "learning_rate": lr,
            "step": state["step"] + 1,
        }
        return grads, metrics

    jit_grad = jax.jit(
        grad_step,
        in_shardings=(state_shardings, batch_sharding),
        out_shardings=(grad_sh, None),
    )

    # Delayed-parameter-update overlap (``disk_update_overlap``): the one
    # in-flight host walk. Only the engine thread touches this.
    pending: list[Any] = [None]

    # Discontinuity-consensus cache: the ``_all_hosts`` call below is a
    # blocking cross-host collective, and running it EVERY step would
    # serialise each disk step behind the slowest host (it used to).
    # Continuity only changes at attach/init, checkpoint restore, or
    # rollback — all of which surface as an incoming step that does NOT
    # continue the last step this process applied or verified, so the
    # steady state skips the collective entirely after the first agreeing
    # step. The cache is deterministic (every host sees the same
    # ``state.step`` sequence and the same walk outcomes), so all hosts
    # take the same skip/check branch and the collective stays aligned.
    _verified_step = [None]
    store.consensus_checks = 0  # observability: actual collective runs

    def _check_discontinuity(state, t):
        # ONE discontinuity check covering every path — lazy attach,
        # warm init-attach, in-process rollback, restored checkpoint at
        # a different step: the spill's applied-step must be exactly the
        # incoming state's step, else the state's weights are the truth
        # and the trajectory restarts from them (masters reseeded,
        # moments zeroed, bias-correction counter reset — the LR
        # schedule keeps the state's step).
        if _verified_step[0] == t - 1:
            return  # steady state: this process applied step t-1 itself
        store.consensus_checks += 1
        needs = store.step_on_disk is not None and store.step_on_disk != t - 1
        if not _all_hosts(not needs):
            # Any ONE host's discontinuity reseeds every host — moments
            # must restart together or the stitched state mixes Adam
            # bias-correction counters. cast_dtype: where a master still
            # rounds to exactly the incoming (compute-dtype-truncated)
            # value, keep the fp32 master — a reseed from a state that
            # never diverged (warm re-attach without a restored step
            # counter) must not shave master precision to bf16.
            store.reseed_masters(
                _leaf_fetcher(state["params"]), step=t - 1,
                cast_dtype=compute_dtype,
            )
        _verified_step[0] = t - 1

    def disk_step(state, batch):
        grads, metrics = jit_grad(state, batch)
        t = int(state["step"]) + 1
        if not store.slabs:
            _ensure_store(state["params"])  # restored-without-init path
            _verified_step[0] = None  # fresh attach: re-establish consensus
        _check_discontinuity(state, t)
        uploader = _make_uploader()
        try:
            store.update(
                _grad_fetchers(grads),
                float(metrics["learning_rate"]), t, uploader.emit,
            )
        finally:
            uploader.close()  # never leak the worker on an update failure
        new_params = dsk.unflatten_like(state["params"], uploader.result())
        _verified_step[0] = t  # this process applied t: continuity holds
        new_state = {
            "params": new_params,
            "step": metrics["step"],
            "lr_scale": state["lr_scale"],
        }
        return new_state, metrics

    def disk_step_overlap(state, batch):
        """Delayed parameter update (ZeRO-Offload DPU analogue): dispatch
        this step's forward/backward on the CURRENT (one-walk-stale)
        params, join the PREVIOUS step's host walk, then hand this step's
        gradients to a fresh background walk and return. Device compute
        for step N+1 and the host AdamW for step N run concurrently —
        step time approaches max(device, host) instead of their sum.
        Tradeoff (documented on the config field): gradients are computed
        on params missing the in-flight update — one step of staleness,
        pinned exactly by ``test_disk_offload.py::test_overlap_semantics``.
        """
        # Async dispatch: the device starts on this step's grads NOW and
        # crunches while the host joins the previous walk below.
        grads, metrics = jit_grad(state, batch)
        t = int(state["step"]) + 1
        if not store.slabs:
            _ensure_store(state["params"])
            _verified_step[0] = None  # fresh attach: re-establish consensus
        prev = pending[0]
        pending[0] = None
        prev_leaves = None
        if prev is not None:
            if prev.step == int(state["step"]):
                prev_leaves = prev.join()       # host walk N ∥ device grads N+1
            else:
                # The incoming state is NOT the continuation of the
                # in-flight walk (supervisor rollback / restored
                # checkpoint): the walk's trajectory is abandoned.
                prev.discard()
        _check_discontinuity(state, t)
        # float(lr) blocks until jit_grad is done — by now the previous
        # walk has already been joined, so nothing serialises behind it.
        pending[0] = dsk.WalkInFlight(
            store, _grad_fetchers(grads),
            float(metrics["learning_rate"]), t, _make_uploader(),
        )
        # The in-flight walk will apply t (a failure raises at the next
        # join and aborts the run — there is no silent-miss path).
        _verified_step[0] = t
        params = state["params"] if prev_leaves is None else \
            dsk.unflatten_like(state["params"], prev_leaves)
        new_state = {
            "params": params,   # stale by exactly the in-flight walk
            "step": metrics["step"],
            "lr_scale": state["lr_scale"],
        }
        return new_state, metrics

    def disk_flush(state):
        """Join the in-flight walk and return a step-consistent state
        (its ``step`` already counts the walk's update; only the params
        were lagging). No-op when nothing is in flight."""
        walk = pending[0]
        if walk is None:
            return state
        pending[0] = None
        if walk.step != int(state["step"]):
            walk.discard()  # flushing a state the walk does not continue
            return state
        leaves = walk.join()
        return {
            **state,
            "params": dsk.unflatten_like(state["params"], leaves),
        }

    jit_eval = jax.jit(
        eval_step,
        in_shardings=(state_shardings, batch_sharding),
        out_shardings=None,
    )

    return TrainProgram(
        config=cfg,
        model_config=model_cfg,
        runtime=runtime,
        state_shardings=state_shardings,
        batch_sharding=batch_sharding,
        init=disk_init,
        step=disk_step_overlap if cfg.disk_update_overlap else disk_step,
        eval_step=jit_eval,
        pipeline_schedule=pipe_schedule,
        disk_store=store,
        flush=disk_flush if cfg.disk_update_overlap else None,
    )


# ---------------------------------------------------------------------------
# Pytree path helpers (match optimizer-state leaves to their param shardings)
# ---------------------------------------------------------------------------


def _path_leaves(tree):
    flat, _ = jax.tree_util.tree_flatten_with_path(
        tree, is_leaf=lambda x: isinstance(x, NamedSharding)
    )
    return [(tuple(_key_str(k) for k in path), leaf) for path, leaf in flat]


def _key_str(k) -> str:
    if hasattr(k, "key"):
        return str(k.key)
    if hasattr(k, "name"):
        return str(k.name)
    if hasattr(k, "idx"):
        return str(k.idx)
    return str(k)


def _path_endswith(path: tuple[str, ...], suffix: tuple[str, ...]) -> bool:
    return len(path) >= len(suffix) and path[-len(suffix):] == suffix


def _tree_map_with_path(fn, tree):
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    leaves = [fn(tuple(_key_str(k) for k in path), leaf) for path, leaf in flat]
    return jax.tree_util.tree_unflatten(treedef, leaves)
