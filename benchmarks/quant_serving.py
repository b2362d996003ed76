"""int8 serving, measured on the real chip.

Two measurements (run: ``python benchmarks/quant_serving.py [7b|1b]``):

1. **llama-7b actually SERVES on one v5e chip** (int8 weights + int8 KV
   pool — the config ``benchmarks/serving_fit.py`` proves at 12.5 GiB).
   The quantized tree is built leaf-by-leaf ON the device (a full bf16
   7B tree plus its int8 copy would not fit during conversion), then a
   stock :class:`ContinuousBatcher` serves a full-slot batch and the
   decode throughput is measured. bf16 cannot run this at all: weights
   alone (12.6 GiB) leave no room for a pool or temporaries.

2. **llama-1b bf16 vs int8 chunked-decode A/B** — decode re-reads every
   weight per token, so weight-only int8 halves the dominant HBM
   traffic. Both modes run the same batcher, same prompts, same chunk;
   the per-dispatch host overhead is constant across modes, so the
   per-dispatch time DELTA isolates the on-chip difference.
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import json
import time

import jax
import jax.numpy as jnp
import numpy as np

GIB = 2**30


class _QuantSite:
    """Sentinel marking a kernel the builder should quantize on arrival."""

    def __init__(self, sds):
        self.sds = sds


def _leafwise_quantized_params(cfg, dtype=jnp.bfloat16, quantize=True):
    """Random serving weights built one leaf at a time on the device,
    quantizing each projection kernel as it lands — peak HBM stays
    (int8 tree so far) + one bf16 leaf + quant temps, never
    bf16-tree + int8-tree (a 7B tree cannot afford both)."""
    from tpu_engine.models import transformer as tfm
    from tpu_engine.quant import _walk, quantize_weight

    shapes = jax.eval_shape(
        lambda k: tfm.init_params(k, cfg, dtype=dtype), jax.random.PRNGKey(0)
    )
    key_box = [jax.random.PRNGKey(7)]
    quant = jax.jit(quantize_weight)

    def fill(sds):
        key_box[0], sub = jax.random.split(key_box[0])
        return jax.jit(
            lambda k: (jax.random.normal(k, sds.shape, jnp.float32)
                       * 0.02).astype(sds.dtype)
        )(sub)

    def build(leaf):
        if isinstance(leaf, _QuantSite):
            w = fill(leaf.sds)
            qw = quant(w)
            jax.block_until_ready(qw.q)
            w.delete()
            return qw
        return fill(leaf)

    marked = _walk(shapes, _QuantSite) if quantize else shapes
    return jax.tree.map(
        build, marked, is_leaf=lambda x: isinstance(x, _QuantSite)
    )


def _drain(srv, rids, timeout=1200):
    t_end = time.time() + timeout
    while time.time() < t_end:
        srv.step()
        if all(srv.result(r)["status"] == "done" for r in rids):
            return True
    return False


def serve_7b_one_chip() -> None:
    from tpu_engine.models import transformer as tfm
    from tpu_engine.serving import ContinuousBatcher

    cfg = tfm.MODEL_CONFIGS["llama-7b"]
    t0 = time.time()
    params = _leafwise_quantized_params(cfg)
    build_s = time.time() - t0
    srv = ContinuousBatcher(params, cfg, max_slots=8, max_len=1024,
                            chunk_steps=16, prefill_chunk=256,
                            kv_quant=True)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, cfg.vocab_size, 64).tolist() for _ in range(8)]

    # Warmup round: compiles prefill + decode chunk.
    rids = [srv.submit(p, max_new_tokens=16) for p in prompts]
    assert _drain(srv, rids), "warmup did not finish"

    n_new = 96
    rids = [srv.submit(p, max_new_tokens=n_new) for p in prompts]
    t0 = time.time()
    assert _drain(srv, rids), "timed decode did not finish"
    dt = time.time() - t0
    toks = 8 * n_new
    print(json.dumps({
        "metric": "llama7b_int8_serving_one_chip",
        "device": str(jax.devices()[0].device_kind),
        "slots": 8, "max_len": 1024, "chunk_steps": 16,
        "weights": "int8", "kv_pool": "int8",
        "param_build_s": round(build_s, 1),
        "tokens": toks, "wall_s": round(dt, 2),
        "tok_per_s": round(toks / dt, 1),
        "note": "bf16 weights alone (12.6 GiB) cannot serve on this chip",
    }))


def ab_1b() -> None:
    from tpu_engine.models import transformer as tfm
    from tpu_engine.serving import ContinuousBatcher

    cfg = tfm.MODEL_CONFIGS["llama-1b"]
    rng = np.random.default_rng(1)
    prompts = [rng.integers(1, cfg.vocab_size, 64).tolist() for _ in range(8)]
    chunk = 64
    K = 6  # timed pure-decode dispatches
    out = {}
    for mode in ("bf16", "int8"):
        params = _leafwise_quantized_params(cfg, quantize=(mode == "int8"))
        srv = ContinuousBatcher(params, cfg, max_slots=8, max_len=2048,
                                chunk_steps=chunk, prefill_chunk=256,
                                kv_quant=(mode == "int8"))
        # Submit long-running requests; settle until every slot is mid-
        # generation (prefills done, compiles warm) so each subsequent
        # step() is exactly ONE full-occupancy decode dispatch. The
        # budget covers every settle-phase chunk plus the timed window
        # with slack — a slot finishing mid-window would silently
        # deflate the denominator's real token count.
        settle = len(prompts) + 3
        rids = [srv.submit(p, max_new_tokens=(settle + K + 2) * chunk)
                for p in prompts]
        for _ in range(settle):
            srv.step()
        assert srv.stats()["active_slots"] == 8
        assert srv.stats()["prefilling"] == 0
        t0 = time.time()
        for _ in range(K):
            srv.step()
        dt = time.time() - t0
        st = srv.stats()
        assert st["active_slots"] == 8 and st["queued"] == 0, (
            "a slot finished inside the timed window — tok/s would be "
            f"overcounted: {st}"
        )
        out[mode] = dict(
            tok_per_s=round(8 * chunk * K / dt, 1),
            ms_per_dispatch=round(1e3 * dt / K, 1),
        )
        jax.tree.map(
            lambda a: a.delete() if hasattr(a, "delete") else None, params
        )
        del srv, params, rids
    delta = out["bf16"]["ms_per_dispatch"] - out["int8"]["ms_per_dispatch"]
    print(json.dumps({
        "metric": "llama1b_serving_decode_ab",
        "device": str(jax.devices()[0].device_kind),
        "slots": 8, "chunk_steps": chunk, "timed_dispatches": K,
        "bf16": out["bf16"], "int8": out["int8"],
        "speedup": round(out["int8"]["tok_per_s"] / out["bf16"]["tok_per_s"], 2),
        "on_chip_ms_saved_per_dispatch": round(delta, 1),
        "note": "full-occupancy decode dispatches only; the constant "
                "dispatch overhead cancels in the per-dispatch delta",
    }))


if __name__ == "__main__":
    which = sys.argv[1] if len(sys.argv) > 1 else "1b"
    if which == "7b":
        serve_7b_one_chip()
    else:
        ab_1b()
