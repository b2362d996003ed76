"""Comm-overlap A/B: prove the ``comm.py`` knobs change the TPU schedule.

The reference's comm tuning is DeepSpeed's ``overlap_comm``/bucket knobs
(``ai_engine/deepspeed_launcher.py:133-142``); ours is XLA's async-collective
fusion + latency-hiding scheduler (``tpu_engine/comm.py:29-37``). Round-2
VERDICT item 2: nothing *measured* that those flags do anything. This
benchmark AOT-compiles the llama-7b FSDP train step for a described v5e:4x4
(16-chip) topology three times — flags ON, flags OFF, and compiler default —
via per-compile ``compiler_options`` (no env mutation, no backend restart)
and reports, per variant:

- per-kind collective counts, split async (``*-start``/``*-done`` pairs)
  vs blocking;
- scheduled overlap distance: how many scheduled instructions sit between
  each async start and its matching done (the compute XLA placed under the
  in-flight collective — the direct analogue of NCCL overlap);
- per-device memory (overlap's cost: in-flight buffers live longer).

Run: ``python benchmarks/comm_overlap.py [--model llama-7b --topo v5e:4x4]``
Prints one JSON line per variant.

Wall-clock A/B needs a real multi-chip slice (the flags are TPU-only — the
CPU dry-run mesh neither accepts ``xla_tpu_*`` options nor shares the TPU
scheduler), so scheduled-placement + memory deltas are the strongest
single-host evidence available.
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import argparse
import json
import re
import time

COMM_ON = {
    "xla_tpu_enable_async_collective_fusion": "true",
    "xla_tpu_enable_async_collective_fusion_fuse_all_gather": "true",
    "xla_tpu_overlap_compute_collective_tc": "true",
    "xla_tpu_enable_latency_hiding_scheduler": "true",
    "xla_latency_hiding_scheduler_rerun": "1",
}
COMM_OFF = {
    "xla_tpu_enable_async_collective_fusion": "false",
    "xla_tpu_enable_async_collective_fusion_fuse_all_gather": "false",
    "xla_tpu_overlap_compute_collective_tc": "false",
    "xla_tpu_enable_latency_hiding_scheduler": "false",
}

_KINDS = ("all-gather", "all-reduce", "reduce-scatter", "collective-permute",
          "all-to-all")


def overlap_stats(hlo_text: str) -> dict:
    """Counts + scheduled start→done distances for every collective kind.

    Works on the post-scheduling ``compiled.as_text()``: within each
    computation, instructions appear in execution order, so the line count
    between ``X-start`` and its ``X-done`` approximates how much work XLA
    scheduled under the in-flight collective.
    """
    async_by_kind: dict[str, int] = {k: 0 for k in _KINDS}
    blocking_by_kind: dict[str, int] = {k: 0 for k in _KINDS}
    starts: dict[str, int] = {}
    distances: list[int] = []
    # TPU async-collective *fusion* spells overlap as custom-call pairs
    # (AsyncCollectiveStart → fusion computation → AsyncCollectiveDone)
    # rather than HLO -start/-done ops. The Done consumes a fusion, not the
    # Start, so name-matching is impossible from text — pair FIFO in
    # schedule order (starts and dones appear in execution order within a
    # scheduled computation), which is exact when pairs don't interleave
    # and a close approximation when they do.
    cc_pairs = 0
    cc_open: list[int] = []
    cc_distances: list[int] = []
    for i, line in enumerate(hlo_text.splitlines()):
        if 'custom_call_target="AsyncCollectiveStart"' in line:
            cc_open.append(i)
            continue
        if 'custom_call_target="AsyncCollectiveDone"' in line:
            cc_pairs += 1
            if cc_open:
                cc_distances.append(i - cc_open.pop(0))
            continue
        op = re.search(
            r"= [^=]*?\b((?:%s)(?:-start|-done)?)\(" % "|".join(_KINDS), line
        )
        if op is None:
            continue
        name = op.group(1)
        kind = next(k for k in _KINDS if name.startswith(k))
        if name.endswith("-start"):
            async_by_kind[kind] += 1
            m = re.search(r"%(\S+) =", line)
            if m:
                starts[m.group(1)] = i
        elif name.endswith("-done"):
            m = re.search(r"-done\(%?([^),]+)", line)
            if m and m.group(1) in starts:
                distances.append(i - starts[m.group(1)])
        else:
            blocking_by_kind[kind] += 1
    # Headline distances pool BOTH overlap spellings: HLO -start/-done ops
    # and the async-fusion custom-call pairs.
    pooled = distances + cc_distances
    return {
        "async_fusion_pairs": cc_pairs,
        "async_fusion_distance_mean": (
            round(sum(cc_distances) / len(cc_distances), 1)
            if cc_distances else 0.0
        ),
        "async_total": sum(async_by_kind.values()),
        "blocking_total": sum(blocking_by_kind.values()),
        "async_by_kind": {k: v for k, v in async_by_kind.items() if v},
        "blocking_by_kind": {k: v for k, v in blocking_by_kind.items() if v},
        "overlap_distance_mean": (
            round(sum(pooled) / len(pooled), 1) if pooled else 0.0
        ),
        "overlap_distance_p90": (
            sorted(pooled)[int(0.9 * (len(pooled) - 1))] if pooled else 0
        ),
        "overlap_distance_max": max(pooled) if pooled else 0,
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="llama-7b")
    ap.add_argument("--topo", default="v5e:4x4")
    ap.add_argument("--fsdp", type=int, default=16)
    ap.add_argument("--data", type=int, default=1)
    ap.add_argument("--seq", type=int, default=4096)
    args = ap.parse_args()

    from tpu_engine.aot import aot_lowered

    lowered = aot_lowered(
        args.model, args.topo, dict(data=args.data, fsdp=args.fsdp),
        seq=args.seq, overrides={"attention_impl": "flash"},
    )

    for variant, opts in (("comm_on", COMM_ON), ("comm_off", COMM_OFF),
                          ("compiler_default", None)):
        t0 = time.time()
        comp = (lowered.compile(compiler_options=opts) if opts
                else lowered.compile())
        ma = comp.memory_analysis()
        rec = {
            "variant": variant,
            "model": args.model,
            "topology": args.topo,
            "compile_s": round(time.time() - t0, 1),
            **overlap_stats(comp.as_text()),
            "device_args_gib": round(ma.argument_size_in_bytes / 2**30, 3),
            "device_temp_gib": round(ma.temp_size_in_bytes / 2**30, 3),
        }
        print(json.dumps(rec))


if __name__ == "__main__":
    main()
