"""Flash-attention kernel microbenchmark (real chip).

The round-3 roofline put the flash kernels at 16.2% of the headline step,
VPU-bound on the softmax chain (RESULTS.md:171-174 names it the next
lever). This times the kernel in isolation — fwd and fwd+bwd — at the
headline shapes, so kernel changes get an honest before/after.

Run: ``python benchmarks/flash_microbench.py`` (prints one JSON line per
shape/mode).
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import json
import time


def main() -> None:
    import sys

    import jax
    import jax.numpy as jnp

    from tpu_engine.ops import _flash_pallas
    from tpu_engine.ops.flash_attention import mha

    # --bwd-block N: sweep the backward tile cap (see _flash_bwd).
    if "--bwd-block" in sys.argv:
        cap = int(sys.argv[sys.argv.index("--bwd-block") + 1])
        _flash_pallas._BWD_BLOCK_CAP = cap
        print(json.dumps({"bwd_block_cap": cap}))

    shapes = [
        # (tag, BH, S, D, window)  — BH = batch × heads after GQA expand
        ("llama7b_seq4096", 32, 4096, 128, 0),
        ("llama7b_seq8192", 32, 8192, 128, 0),
        ("mistral_win4096_seq8192", 32, 8192, 128, 4096),
    ]
    rng = jax.random.PRNGKey(0)
    for idx, (tag, BH, S, D, window) in enumerate(shapes):
        # Deterministic per-shape seed (hash() is salted per interpreter —
        # the before/after runs this file exists for must see identical data).
        ks = jax.random.split(jax.random.fold_in(rng, idx), 3)
        q = jax.random.normal(ks[0], (1, S, BH, D), jnp.bfloat16)
        k = jax.random.normal(ks[1], (1, S, BH, D), jnp.bfloat16)
        v = jax.random.normal(ks[2], (1, S, BH, D), jnp.bfloat16)

        # The kernels take ~ms, the same order as one dispatch, so the
        # iteration loop lives INSIDE the jit — a scan whose carry chains
        # each iteration's output into the next input (data dependence
        # defeats CSE; the Pallas call is opaque to DCE). One dispatch
        # runs N kernels; reading the returned scalar waits for them.
        N = 32

        def fwd_loop(q, k, v):
            def body(qq, _):
                return mha(qq, k, v, window=window), None
            out, _ = jax.lax.scan(body, q, None, length=N)
            return out[0, 0, 0, 0]

        def loss(q, k, v):
            return jnp.sum(mha(q, k, v, window=window).astype(jnp.float32) ** 2)

        def fwdbwd_loop(q, k, v):
            def body(qq, _):
                dq, _, _ = jax.grad(loss, argnums=(0, 1, 2))(qq, k, v)
                return dq.astype(qq.dtype), None
            out, _ = jax.lax.scan(body, q, None, length=N)
            return out[0, 0, 0, 0]

        for mode, f in (("fwd", fwd_loop), ("fwd_bwd", fwdbwd_loop)):
            fn = jax.jit(f)
            float(fn(q, k, v))  # compile + one sync
            reps = 3
            t0 = time.perf_counter()
            for _ in range(reps):
                s = fn(q, k, v)
            float(s)
            ms = (time.perf_counter() - t0) / (reps * N) * 1e3
            # Causal attention FLOPs: 2·S·S·D per (bh) for qk, same for pv,
            # halved by causality; windowed further reduced.
            ctx = min(S, window) if window else S
            approx = BH * (2 * 2 * S * ctx * D) * (0.5 if not window else 1.0)
            if mode == "fwd_bwd":
                approx *= 3.5  # bwd ≈ 2.5x fwd for flash
            print(json.dumps({
                "shape": tag, "mode": mode, "bh": BH, "seq": S,
                "window": window, "ms": round(ms, 3),
                "approx_tflops": round(approx / ms / 1e9, 1),
            }))


if __name__ == "__main__":
    main()
