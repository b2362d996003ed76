"""AOT-compile plumbing: the precompile worker's seam, and the tpu_aot tests'.

One canonical way to build the sharded train program against a described
TPU topology (libtpu compile-only — no chips needed) so the per-site
boilerplate (topology → MeshRuntime → build_train_program → eval_shape →
lower) doesn't drift across ``compile_index._default_precompile``,
``benchmarks/comm_overlap.py`` and tests/.
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp


class TopologyUnavailable(Exception):
    """No libtpu / described-topology support in this environment.

    Tests catch THIS (and only this) to skip — so a real lowering or
    config regression still fails instead of silently skipping."""


def topology(topo_name: str):
    """Resolve a described TPU topology, or raise :class:`TopologyUnavailable`."""
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(topo_name, platform="tpu")
    except Exception as e:
        raise TopologyUnavailable(f"{topo_name}: {e}") from e


def build_program(
    model: str,
    mesh_axes: dict[str, int],
    micro: int = 1,
    accum: int = 1,
    seq: int = 4096,
    overrides: Optional[dict[str, Any]] = None,
    devices=None,
):
    """The sharded train program for ``model`` on ``mesh_axes``.

    ``devices``: topology or runtime devices (defaults to the current
    backend's). ``overrides`` may carry any extra ``TPUTrainConfig``
    fields, plus ``sharding_stage``.
    """
    from tpu_engine.mesh_runtime import MeshConfig, MeshRuntime
    from tpu_engine.sharding import ShardingStage, TPUTrainConfig
    from tpu_engine.train import build_train_program

    overrides = dict(overrides or {})
    stage = overrides.pop("sharding_stage", ShardingStage.FULL_PARTITIONING)
    cfg = TPUTrainConfig(
        model_name=model,
        sharding_stage=stage,
        mesh=MeshConfig(**mesh_axes),
        micro_batch_size=micro,
        gradient_accumulation_steps=accum,
        seq_len=seq,
        **overrides,
    )
    runtime = MeshRuntime(cfg.mesh, devices=devices) if devices is not None else None
    return build_train_program(cfg, runtime=runtime)


def aot_lowered(
    model: str,
    topo_name: str,
    mesh_axes: dict[str, int],
    micro: int = 1,
    accum: int = 1,
    seq: int = 4096,
    overrides: Optional[dict[str, Any]] = None,
):
    """Lower the train step against a described TPU topology.

    Returns the ``Lowered`` step — call ``.compile()`` (optionally with
    ``compiler_options``) to get memory/cost analyses and HLO text.
    Raises :class:`TopologyUnavailable` when no libtpu is available —
    tests catch exactly that for their skip, so build/lowering failures
    still fail loudly.
    """
    topo = topology(topo_name)
    prog = build_program(model, mesh_axes, micro, accum, seq, overrides,
                         devices=topo.devices)
    state_shape = jax.eval_shape(prog.init, jax.random.PRNGKey(0))
    batch = jax.ShapeDtypeStruct(prog.global_batch_shape(), jnp.int32)
    return prog.step.lower(state_shape, batch)
