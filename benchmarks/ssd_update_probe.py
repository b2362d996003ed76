"""The decode update of a recurrent state, alone on the chip.

Times ``ops.ssd_update.ssd_update`` (the one-pass, in-place kernel) and the
plain XLA step it replaces (``generate._ssd_step`` on the layer's slice,
written back into the stack) at the three recurrent cells' shapes, each as the
serving program holds them: the whole stack ``[L, B, H, P, N]`` float32 the
donated carry of a ``lax.scan`` over the layers under ``jit``, a different
input a layer. One JSON line a reading: microseconds a layer-step, the share of
819 GB/s that is (the layer's state in and out once, plus the step's inputs and
its output: the least the mathematics needs), and the block a program holds.

``--block-bytes`` tries other block sizes; ``--ceiling`` adds what bounds the
kernel from below on this chip: the same pipeline with the arithmetic taken
out (a bare copy of the layer's blocks through the chip's fast memory, in
place), and one DMA of a layer's state from HBM to HBM.

Run on the chip: ``python benchmarks/ssd_update_probe.py --ceiling``.
Refuses to time anything off the TPU.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

HBM_BYTES_PER_S = 819e9  # TPU v5e (Google Cloud documentation, "TPU v5e")
# cell: (layers of the kind, slots, heads, P, N, B and C per head)
SHAPES = {
    "granite-4.0-h-micro.serve-chat-burst": (18, 32, 64, 64, 128, False),
    "granite-4.0-h-small.serve-batch32": (9, 32, 128, 64, 128, False),
    "minicpm-sala.serve-longdoc": (9, 16, 32, 128, 128, True),
}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--cells", default=",".join(SHAPES))
    ap.add_argument("--block-bytes", default="", help="other block sizes to try, comma separated")
    ap.add_argument("--ceiling", action="store_true")
    ap.add_argument("--seed", type=int, default=2147485001)
    ap.add_argument("--seconds", type=float, default=0.5, help="timed window a reading")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from tpu_engine.ops import ssd_update as su

    generate = sys.modules["tpu_engine.generate"]  # the package's ``generate`` is the function
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"ssd_update_probe times the chip; this process runs on {dev.platform!r}")
    f32, bf16 = jnp.float32, jnp.bfloat16

    def xla_step(x, dt, A, Bm, Cm, state, at):
        y, h = generate._ssd_step(x, dt, A, Bm, Cm, generate.layer_slice(state, at))
        return y, lax.dynamic_update_index_in_dim(state, h, at, 0)

    def walk(step):
        """All layers once, the stack carried as ``scan_layers`` carries it."""
        def run(x, dt, A, Bm, Cm, state):
            def layer(state, xs):
                at, x, dt, Bm, Cm = xs
                y, state = step(x, dt, A, Bm, Cm, state, at)
                return state, y
            state, y = lax.scan(layer, state, (jnp.arange(x.shape[0], dtype=jnp.int32), x, dt, Bm, Cm))
            return y, state
        return jax.jit(run, donate_argnums=(5,))

    def us_a_layer_step(fn, small, state):
        for _ in range(2):
            y, state = fn(*small, state)
        jax.block_until_ready(state)
        calls, t0 = 0, time.perf_counter()
        while (took := time.perf_counter() - t0) < args.seconds:
            for _ in range(5):
                y, state = fn(*small, state)
            jax.block_until_ready(state)
            calls += 5
        return 1e6 * took / (calls * small[0].shape[0]), y, state

    def copy_body(at_ref, decay_ref, dtx_ref, b_ref, c_ref, h_ref, h_out_ref, y_ref):
        h_out_ref[0] = h_ref[0]
        y_ref[...] = dtx_ref[...]

    def dma_body(src, dst, sem):
        copy = pltpu.make_async_copy(src, dst, sem)
        copy.start()
        copy.wait()

    for cell in args.cells.split(","):
        L, B, H, P, N, per_head = SHAPES[cell]
        ks = jax.random.split(jax.random.PRNGKey(args.seed % (2 ** 31)), 6)
        bc = (L, B, H, N) if per_head else (L, B, N)
        small = (jax.random.normal(ks[0], (L, B, H, P), bf16),
                 jax.nn.softplus(jax.random.normal(ks[1], (L, B, H), f32)),
                 -jnp.exp(jax.random.normal(ks[2], (H,), f32)),
                 jax.random.normal(ks[3], bc, bf16), jax.random.normal(ks[4], bc, bf16))
        state = jax.random.normal(ks[5], (L, B, H, P, N), f32)
        layer_bytes = B * H * P * N * 4
        need = 2 * layer_bytes + B * H * P * (2 + 4) + B * H * 4 + 2 * int(np.prod(bc[1:])) * 2

        def line(what, us, moved=need, **more):
            print(json.dumps({"cell": cell, "state": [L, B, H, P, N], "what": what, "us_a_layer_step": round(us, 1),
                              "pct_of_819_gb_s": round(100 * moved / HBM_BYTES_PER_S / (us * 1e-6), 1),
                              **more, "device": dev.device_kind}), flush=True)

        # one step of one layer from one state, both ways, before anything is timed
        y_x, s_x = jax.jit(xla_step)(*(a[1] for a in small[:2]), small[2], small[3][1], small[4][1], state, 1)
        y_k, s_k = jax.jit(su.ssd_update)(*(a[1] for a in small[:2]), small[2], small[3][1], small[4][1], state, 1)
        same = {"max_dy": float(jnp.max(jnp.abs(y_x - y_k))), "max_dh": float(jnp.max(jnp.abs(s_x - s_k))),
                "y_max": float(jnp.max(jnp.abs(y_x)))}
        del s_x, s_k

        us, _, state = us_a_layer_step(walk(xla_step), small, state)
        line("_ssd_step (XLA)", us)
        sizes = [None] + [int(b) for b in args.block_bytes.split(",") if b]
        for block_bytes in sizes:
            kw = {} if block_bytes is None else {"block_bytes": block_bytes}
            rows, heads = su.block_of(B, H, P, N, **kw)
            step = lambda *a, kw=kw: su.ssd_update(*a, **kw)  # noqa: E731
            held = {"block_rows_heads": [rows, heads], "block_bytes": rows * heads * P * N * 4}
            try:
                us, _, state = us_a_layer_step(walk(step), small, state)
            except jax.errors.JaxRuntimeError as e:  # four copies of the block past the fast memory's allowance
                print(json.dumps({"cell": cell, "what": "ssd_update (kernel)", **held,
                                  "refused": str(e).split("\n")[0][-200:]}), flush=True)
                continue
            line("ssd_update (kernel)", us, **held, **(same if block_bytes is None else {}))
        if args.ceiling:
            kernel, su._kernel = su._kernel, copy_body
            try:
                us, _, state = us_a_layer_step(walk(su.ssd_update), small, state)
            finally:
                su._kernel = kernel
            line("the kernel's pipeline, copying only", us, block_rows_heads=list(su.block_of(B, H, P, N)))
            dma = jax.jit(lambda a: pl.pallas_call(
                dma_body, name="hbm_to_hbm", out_shape=jax.ShapeDtypeStruct(a.shape, a.dtype),
                in_specs=[pl.BlockSpec(memory_space=pl.ANY)], out_specs=pl.BlockSpec(memory_space=pl.ANY),
                scratch_shapes=[pltpu.SemaphoreType.DMA(())])(a))
            one = state[0]
            jax.block_until_ready(dma(one))
            calls, t0 = 0, time.perf_counter()
            while (took := time.perf_counter() - t0) < args.seconds:
                outs = [dma(one) for _ in range(20)]
                jax.block_until_ready(outs)
                calls += 20
            line("one DMA of a layer's state, HBM to HBM", 1e6 * took / calls, moved=2 * layer_bytes)
            del one, outs
        del state, small


if __name__ == "__main__":
    main()
