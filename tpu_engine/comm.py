"""Collective-communication tuning surface.

The reference's comm tuning is DeepSpeed JSON knobs — ``overlap_comm``,
``allgather_bucket_size``, ``reduce_bucket_size``, ``reduce_scatter``
(``ai_engine/deepspeed_launcher.py:133-142``) — that shape how NCCL
overlaps and buckets collectives. On TPU the collectives are emitted by
XLA from sharding annotations, so the equivalent surface is TPU *compiler
flags*: async collectives let communication overlap compute, and the
latency-hiding scheduler reorders the program to hide it (SURVEY.md §2.4:
"bucket-size analogs → XLA latency-hiding/async-collective flags").

Delivery (established on a v5e host, jax/jaxlib 0.9.0 + libtpu 0.0.34):
the ``xla_tpu_*`` options belong to libtpu, which takes them from
``LIBTPU_INIT_ARGS`` and exits on a spelling it does not know. jaxlib's own
``XLA_FLAGS`` parser does not know them and hard-aborts the process
("Unknown flags in XLA_FLAGS"), so they must never go there.
``xla_extra_flags`` is the operator's verbatim ``XLA_FLAGS`` addition.

Both variables are read once, when the backend initialises — the worker
CLI applies them first thing; library users call :func:`apply_comm_flags`
before touching jax. A job started in a process whose backend is already
up runs WITHOUT them, and :func:`comm_flags_status` (in the launch plan
and in ``describe()``) says so instead of echoing the config's ``True``.
"""

from __future__ import annotations

import logging
import os
from typing import Any

from tpu_engine.sharding import TPUTrainConfig

log = logging.getLogger(__name__)

# All five accepted by libtpu 0.0.34 through LIBTPU_INIT_ARGS (PR 21 chip
# run); a spelling the runtime rejects is removed from these tuples.
_ASYNC_COLLECTIVE_FLAGS = (
    "--xla_tpu_enable_async_collective_fusion=true",
    "--xla_tpu_enable_async_collective_fusion_fuse_all_gather=true",
    "--xla_tpu_overlap_compute_collective_tc=true",
)
_LATENCY_HIDING_FLAGS = (
    "--xla_tpu_enable_latency_hiding_scheduler=true",
    "--xla_latency_hiding_scheduler_rerun=1",
)


def _requested(cfg: TPUTrainConfig) -> dict[str, list[str]]:
    """``cfg``'s comm-tuning flags, grouped by the variable that delivers
    them."""
    tpu: list[str] = []
    if cfg.async_collectives:
        tpu.extend(_ASYNC_COLLECTIVE_FLAGS)
    if cfg.latency_hiding_scheduler:
        tpu.extend(_LATENCY_HIDING_FLAGS)
    return {
        "LIBTPU_INIT_ARGS": tpu,
        "XLA_FLAGS": cfg.xla_extra_flags.split(),
    }


def _missing(var: str, flags: list[str]) -> list[str]:
    """Flags of ``flags`` whose NAME is absent from ``$var``. Compared by
    name: an operator's explicit --foo=false must not be overridden by
    appending our --foo=true (the later value would win)."""
    present = {t.split("=", 1)[0] for t in os.environ.get(var, "").split()}
    return [f for f in flags if f.split("=", 1)[0] not in present]


def compression_plan(cfg: TPUTrainConfig) -> dict:
    """The comm-compression surface of ``cfg`` as a plan/launch-report
    dict (tpu_engine/comm_compress.py): which ZeRO++ mechanisms are on,
    the block size, and the analytic per-element wire reduction each one
    buys (int8 codes + fp32/block scales vs. fp32 full-width). Purely
    declarative — the mechanisms themselves are wired in train.py."""
    from tpu_engine import comm_compress

    plan: dict = {
        "enabled": comm_compress.enabled(cfg),
        "quant_weight_gather": cfg.comm_quant_weights,
        "secondary_weight_partition": cfg.comm_secondary_weights,
        "quant_grad_reduce": cfg.comm_quant_grads,
        "block_size": cfg.comm_quant_block_size,
    }
    if plan["enabled"]:
        factors = comm_compress.expected_volume_factors(
            cfg.comm_quant_block_size
        )
        if cfg.comm_quant_weights:
            plan["weight_gather_volume_factor"] = round(
                factors["weight_gather"], 3
            )
        if cfg.comm_quant_grads:
            plan["cross_slice_grad_volume_factor"] = round(
                factors["grad_cross_slice"], 3
            )
    return plan


def _backend_initialized() -> bool:
    from jax._src import xla_bridge

    return xla_bridge.backends_are_initialized()


def apply_comm_flags(cfg: TPUTrainConfig) -> None:
    """Deliver ``cfg``'s comm flags through the environment (idempotent):
    TPU compiler options to ``LIBTPU_INIT_ARGS`` — read only by libtpu, so
    harmless on a host without a TPU — and ``xla_extra_flags`` to
    ``XLA_FLAGS``. Only before the backend initialises; afterwards the
    environment is left alone, a warning is logged, and
    :func:`comm_flags_status` reports the flags as not in force. Never
    initialises the backend itself (multi-host rendezvous comes after).
    """
    late = _backend_initialized()
    for var, flags in _requested(cfg).items():
        missing = _missing(var, flags)
        if not missing:
            continue
        if late:
            log.warning(
                "backend already initialised — comm flags %s cannot take "
                "effect in this process; export %s before importing jax or "
                "start through the worker CLI", missing, var,
            )
            continue
        os.environ[var] = " ".join([os.environ.get(var, ""), *missing]).strip()


def comm_flags_status(cfg: TPUTrainConfig) -> dict[str, Any]:
    """Whether ``cfg``'s comm flags are in force in THIS process — what the
    compiled program was built with, not what the config asks for.

    ``apply_comm_flags`` never edits the environment once the backend is
    up, so a flag found in its variable was there when the backend read it.
    The TPU options additionally need a TPU backend to mean anything.
    Initialises the backend (callers are past that point: the launch plan
    and ``describe()``).
    """
    import jax

    requested = _requested(cfg)
    flags = [f for fl in requested.values() for f in fl]
    not_delivered = {
        var: miss for var, fl in requested.items() if (miss := _missing(var, fl))
    }
    backend = jax.default_backend()
    if not flags:
        in_force, reason = True, "no comm flags requested"
    elif not_delivered:
        in_force = False
        reason = "; ".join(
            f"{miss} not in {var} when the backend initialised"
            for var, miss in not_delivered.items()
        )
    elif requested["LIBTPU_INIT_ARGS"] and backend != "tpu":
        in_force = False
        reason = f"backend is {backend!r}: the TPU compiler options do not apply"
    else:
        in_force, reason = True, "delivered before the backend initialised"
    return {"requested": flags, "in_force": in_force, "reason": reason}
