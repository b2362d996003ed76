"""Disk-tier optimizer offload: what it buys in HBM, measured.

Compiles the REAL step programs (``MODEL`` below) on the local TPU
backend and reports the per-device memory XLA allocated:

- in-memory AdamW (`build_train_program` default): the donated train
  state carries fp32 masters + mu + nu (12 bytes/param) through every
  step;
- disk tier (`optimizer_offload="disk"`): the device state is bf16
  params only (2 bytes/param); the jitted program is forward/backward/
  clip, and masters+moments live in memmap spill files
  (``tpu_engine/disk_offload.py``).

Run: ``python benchmarks/disk_offload_fit.py`` (needs the local chip;
step math parity with the in-memory path is pinned by
``tests/test_disk_offload.py``). Wall-clock per step is reported for
the disk tier: the host update fetches the full fp32 gradient tree
each step — that transfer (PCIe + NVMe) is the documented price of the
tier, paid for models whose optimizer state cannot fit anywhere else.
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import json
import tempfile
import time

import jax
import jax.numpy as jnp

GIB = 2**30
# gpt-125m keeps the gradient fetch small; the device-state shrink is
# byte-arithmetic (12 -> 2 bytes/param) and model-size-independent.
MODEL = "gpt-125m"


def main() -> None:
    from tpu_engine.mesh_runtime import MeshConfig
    from tpu_engine.sharding import OffloadDevice, Precision, TPUTrainConfig
    from tpu_engine.train import build_train_program

    if jax.devices()[0].platform != "tpu":
        print(json.dumps({"skipped": "needs a local TPU"}))
        return

    # micro_batch 8: enough device compute per step that the DPU overlap
    # regime is visible — with micro=1 the host walk dominates and hiding
    # the device step is marginal by construction. DPU's win is bounded
    # by (host+device)/max(host, device); the bench reports both sides so
    # the ratio is interpretable.
    base = dict(
        model_name=MODEL, mesh=MeshConfig(), micro_batch_size=8,
        gradient_accumulation_steps=1, seq_len=2048,
        precision=Precision.BF16, total_steps=10, warmup_steps=2,
        activation_checkpointing=True,
    )

    out = {}
    for mode in ("in_memory", "disk", "disk_overlap"):
        kw = dict(base)
        spill = None
        if mode.startswith("disk"):
            spill = tempfile.mkdtemp(prefix="spill_")
            kw.update(optimizer_offload=OffloadDevice.DISK,
                      optimizer_spill_dir=spill,
                      disk_update_overlap=mode == "disk_overlap")
        prog = build_train_program(TPUTrainConfig(**kw))
        state = prog.init(jax.random.PRNGKey(0))
        batch = prog.synthetic_batch(0)
        # Warm compile + one step so the report reflects the steady state.
        t0 = time.time()
        state, _ = prog.step(state, batch)
        jax.block_until_ready(state["params"])
        warm_s = time.time() - t0
        # Steady state over several steps; the overlap mode's walks drain
        # in the background, so the flush at the end charges the final
        # in-flight walk to the measured window (pipeline fill + drain
        # both inside the timing — honest steady-state amortisation).
        n_meas = 2
        t0 = time.time()
        for _ in range(n_meas):
            state, metrics = prog.step(state, batch)
        if prog.flush is not None:
            state = prog.flush(state)
        jax.block_until_ready(state["params"])
        step_s = (time.time() - t0) / n_meas

        state_gib = sum(
            leaf.size * leaf.dtype.itemsize
            for leaf in jax.tree.leaves(state)
        ) / GIB
        row = {
            "mode": mode, "model": MODEL,
            "device_state_gib": round(state_gib, 2),
            "warm_step_s": round(warm_s, 2),
            "loss": round(float(metrics["loss"]), 3),
        }
        if mode.startswith("disk"):
            # The host update's device_get ends the step's device work,
            # so this wall time covers the whole step.
            row["step_wall_s"] = round(step_s, 2)
            row["spill_gib_on_disk"] = round(
                prog.disk_store.spill_bytes() / GIB, 2
            )
        out[mode] = row
        print(json.dumps(row), flush=True)
    print(json.dumps({
        "metric": "disk_tier_device_state_shrink",
        "in_memory_gib": out["in_memory"]["device_state_gib"],
        "disk_gib": out["disk"]["device_state_gib"],
        "shrink": round(
            out["in_memory"]["device_state_gib"]
            / max(out["disk"]["device_state_gib"], 1e-9), 2
        ),
    }))
    print(json.dumps({
        "metric": "disk_tier_overlap_speedup",
        "serial_step_s": out["disk"]["step_wall_s"],
        "overlap_step_s": out["disk_overlap"]["step_wall_s"],
        "speedup": round(
            out["disk"]["step_wall_s"]
            / max(out["disk_overlap"]["step_wall_s"], 1e-9), 2
        ),
    }))


if __name__ == "__main__":
    main()
