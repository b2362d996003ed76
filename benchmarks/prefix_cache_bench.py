"""Prefix-cache TTFT benefit, measured on the real chip.

A 1024-token system prompt is prefilled once; later requests sharing it
paste the cached KV lanes and ingest only their suffix. TTFT for the
warm request should drop by roughly the shared chunks' cost: each skipped
chunk saves its forward time or its dispatch round-trip, whichever is
larger on the machine.

Run: ``python benchmarks/prefix_cache_bench.py``.
"""

from __future__ import annotations

import json
import os
import sys
import time

import jax
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    from tpu_engine.models import transformer as tfm
    from tpu_engine.serving import ContinuousBatcher

    cfg = tfm.MODEL_CONFIGS["gpt-125m"]
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    system = rng.integers(1, cfg.vocab_size, 1024).tolist()
    suffixes = [rng.integers(1, cfg.vocab_size, 24).tolist() for _ in range(3)]

    srv = ContinuousBatcher(params, cfg, max_slots=4, max_len=2048,
                            chunk_steps=8, prefill_chunk=256,
                            prefix_cache_tokens=4096)

    def run_one(prompt):
        rid = srv.submit(prompt, max_new_tokens=8)
        t_end = time.time() + 600
        while time.time() < t_end:
            srv.step()
            if srv.result(rid)["status"] == "done":
                return srv.result(rid)["ttft_ms"]
        raise TimeoutError

    # Warmup compiles (prefill chunks at the measured cache shape, paste,
    # decode) on an UNSHARED same-length prompt, so the cold row measures
    # dispatches, not XLA compiles.
    run_one(rng.integers(1, cfg.vocab_size, 1048).tolist())

    # Steady-state timings are the min of 3 runs after a discarded
    # compile-paying first run, so one slow dispatch does not drown the
    # signal. Cold runs
    # use DISTINCT unshared prompts (an identical re-run would hit).
    cold = min(
        run_one(rng.integers(1, cfg.vocab_size, 1048).tolist())
        for _ in range(3)
    )
    run_one(system + suffixes[0])                # creates the system entry
    first_warm = run_one(system + suffixes[1])   # pays the paste compile
    warm = min(run_one(system + suffixes[2]) for _ in range(3))
    # Token-granular reuse (round 5): a prompt diverging MID-chunk from
    # the stored prefix — shares 1000 of its 1024 tokens — reuses
    # floor(1000/64)=960 tokens of KV; the old boundary-keyed lookup
    # reused ZERO here. Every timed run uses a FRESH divergence (distinct
    # token at position 1000), because a repeated identical prompt would
    # hit its OWN full boundary entry from the previous run and measure
    # resubmit reuse instead of the genuine 960-token partial hit.
    def misaligned(i: int) -> list[int]:
        return (system[:1000] + [(system[1000] + 1 + i) % cfg.vocab_size]
                + rng.integers(1, cfg.vocab_size, 24).tolist())

    run_one(misaligned(0))                       # pays this shape's compiles
    partial = min(run_one(misaligned(1 + k)) for k in range(3))
    # And the identical-resubmit case (chunk-aligned prompt), the classic
    # shared-system-prompt dedupe the old lookup could never hit.
    aligned = system[:1024]
    run_one(list(aligned))                       # pays this bucket's compiles
    resub = min(run_one(list(aligned)) for _ in range(3))
    st = srv.stats()["prefix_cache"]
    print(json.dumps({
        "metric": "prefix_cache_ttft",
        "device": str(jax.devices()[0].device_kind),
        "system_tokens": 1024, "prefill_chunk": 256,
        "cold_ttft_ms": round(cold, 1),
        "first_warm_ttft_ms": round(first_warm, 1),
        "steady_warm_ttft_ms": round(warm, 1),
        "steady_speedup": round(cold / warm, 2),
        "partial_hit_ttft_ms": round(partial, 1),
        "partial_hit_speedup": round(cold / partial, 2),
        "aligned_resubmit_ttft_ms": round(resub, 1),
        "aligned_resubmit_speedup": round(cold / resub, 2),
        "cache": st,
    }))


if __name__ == "__main__":
    main()
