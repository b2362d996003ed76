"""A decode step's read of the ``attn`` kind's keys and values, alone on the chip.

Times one layer-step of the two forms of ``generate._decode_block``'s
``kv_write`` + ``decode_attn`` at the four serving cells' shapes: the kernel
``ops.lane_decode`` over the pool as it is stored (``[L, slots, lanes, KV x
HD]``: the blocks of 512 lanes an active slot's length covers, read where they
lie) and the XLA form the parent ran (``[L, slots, lanes, KV, HD]``: the layer
sliced out of the carried pool, two contractions over every lane of every
slot). Each as the serving program holds it: the whole stack the donated carry
of a ``lax.scan`` over 8 token-steps of a ``lax.scan`` over the layers under
``jit`` (64 layer-steps a dispatch, as ``jit_decode_chunk`` of Mistral-7B's 8
layers; the one- and two-layer cells are timed with 8 layers too, so that a
dispatch is long enough to time), every step writing one row a slot and layer
first. Over live slots {1, 2, 4, all} and lengths {256, 1 024, 2 048} (the
length a live slot ENDS the dispatch at): one JSON line a reading,
microseconds a layer-step and the share of 819 GB/s that the LIVE bytes are
(the keys and values of the live slots' lengths: the least the mathematics
needs; the XLA form is held to the same count, which is why it reads so low).

``--ceiling`` adds what bounds the kernel from below on this chip: the same
pipeline with the arithmetic taken out (the blocks still copied into fast
memory, the grid still walked).

``--split`` times the kernel's call alone instead (no write, no XLA form; one
layer's pool, 32 calls a dispatch as a scan, each with queries of its own), at
the shapes above AND the differential caller's (``SPLIT_SHAPES``:
phi-4-mini-flash.serve-reason32's ONE shared cache, 32 slots x 12 288 lanes x
ten column groups of 128, and a window layer's ring of 512 lanes), with every
slot idle, at one block a slot, at the contexts the cell's window holds and
with every slot full; and an idle call over half the lanes, which under a
grid of a step for every block the leaf holds had half the grid steps and
nothing else less (under the grid bounded by the blocks in use, PR 49's, it
reads what the whole leaf's reads). From these it solves what a call is made
of: microseconds a block the leaf holds and no slot uses, a block, and a live
slot over an idle one (its reset, division, write-back, query fetch and
whatever of its first block's copy nothing hides).

Run on the chip: ``python benchmarks/decode_attn_probe.py --ceiling`` or
``--split``. Refuses to time anything off the TPU.
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
LAYERS, STEPS = 8, 8
# cells of one shape: (slots, lanes, kv-heads, head size, queries a kv-head)
SHAPES = {
    "mistral-7b.serve-chat, mixtral-8x7b.serve-batch": (16, 2048, 8, 128, 4),
    "granite-4.0-h-small.serve-batch32": (32, 2048, 8, 128, 4),
    "granite-4.0-h-micro.serve-chat-burst": (32, 2048, 8, 64, 4),
}
# ``--split`` only (eight layers of such a pool do not fit the chip): (slots,
# lanes, column groups of 128, query rows a group, the contexts of the cell's window)
SPLIT_SHAPES = {
    "phi-4-mini-flash.serve-reason32, the shared cache": (32, 12288, 10, 4, (5700, 7000)),
    "phi-4-mini-flash.serve-reason32, a window layer's ring": (32, 512, 10, 4, (512, 512)),
}
CALLS = 32  # calls a dispatch of ``--split``


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--cells", default="", help="substrings of the shapes' names, comma separated (default: all)")
    ap.add_argument("--live", default="1,2,4,all")
    ap.add_argument("--lengths", default="256,1024,2048")
    ap.add_argument("--ceiling", action="store_true")
    ap.add_argument("--split", action="store_true", help="the kernel's call alone: idle, one block a slot, the "
                    "window's contexts, full; and what a block, a block no slot uses and a slot cost")
    ap.add_argument("--seed", type=int, default=2147485003)
    ap.add_argument("--seconds", type=float, default=0.4, help="timed window a reading")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    from tpu_engine.models.transformer import ModelConfig
    from tpu_engine.ops import lane_decode

    generate = sys.modules["tpu_engine.generate"]  # the package's ``generate`` is the function
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"decode_attn_probe times the chip; this process runs on {dev.platform!r}")
    bf16, f32 = jnp.bfloat16, jnp.float32

    def xla_read(q, k5, v5, at, lengths, active, cfg):
        """The parent's ``decode_attn``: [L, B, M, KV, HD] leaves."""
        B, M = k5.shape[1:3]
        kc, vc = generate.layer_slice(k5, at), generate.layer_slice(v5, at)
        qg = q.reshape(B, cfg.n_kv_heads, -1, cfg.head_dim)
        s = jnp.einsum("bkgd,bmkd->bkgm", qg, kc, preferred_element_type=f32) * cfg.head_dim ** -0.5
        mask = jnp.arange(M, dtype=jnp.int32)[None, :] <= lengths[:, None]
        p = jax.nn.softmax(jnp.where(mask[:, None, None, :], s, -1e30), axis=-1).astype(bf16)
        return jnp.einsum("bkgm,bmkd->bkgd", p, vc).reshape(B, -1)

    def kernel_read(q, k4, v4, at, lengths, active, cfg):
        a = lane_decode.lane_decode(generate._grouped_queries(q, cfg), k4, v4, at,
                                    jnp.where(active, lengths + 1, 0), scale=cfg.head_dim ** -0.5, name="attn_decode")
        return generate._grouped_outputs(a, cfg).astype(bf16)

    def walk(read, cfg):
        """STEPS token-steps of all layers, the stacks carried as ``decode_chunk`` carries the pool."""
        def run(q, new, k, v, lengths, active):
            rows = jnp.arange(k.shape[1])

            def token(carry, _):
                k, v, lengths = carry

                def layer(kv, xs):
                    k, v = kv
                    at, q, new = xs
                    row = new.reshape(new.shape[:1] + k.shape[3:])
                    k, v = k.at[at, rows, lengths].set(row), v.at[at, rows, lengths].set(row)
                    return (k, v), read(q, k, v, at, lengths, active, cfg)

                (k, v), out = lax.scan(layer, (k, v), (jnp.arange(LAYERS, dtype=jnp.int32), q, new))
                return (k, v, lengths + active.astype(jnp.int32)), out

            (k, v, _), outs = lax.scan(token, (k, v, lengths), None, length=STEPS)
            return outs, k, v
        return jax.jit(run, donate_argnums=(2, 3))

    def us_a_layer_step(fn, small, k, v, lengths, active):
        for _ in range(2):
            out, k, v = fn(*small, k, v, lengths, active)
        jax.block_until_ready(out)
        calls, t0 = 0, time.perf_counter()
        while (took := time.perf_counter() - t0) < args.seconds:
            for _ in range(3):
                out, k, v = fn(*small, k, v, lengths, active)
            jax.block_until_ready(out)
            calls += 3
        return 1e6 * took / (calls * LAYERS * STEPS), k, v

    def pipeline_only(*refs, **_):
        o_ref, acc_ref = refs[-4], refs[-1]          # ... q, k, v, o, m, l, acc
        o_ref[0] = jnp.zeros_like(acc_ref)

    def traced_with(body, fn):
        """``fn``, whose first call (the one that traces it) sees ``body`` as the kernel's."""
        def call(*a):
            kernel, lane_decode._kernel = lane_decode._kernel, body
            try:
                return fn(*a)
            finally:
                lane_decode._kernel = kernel
        return call

    def chosen(name):
        return not args.cells or any(c in name for c in args.cells.split(","))

    def split(name, B, M, P, R, window):
        """The call alone at four fills of one layer's pool, and the three costs they imply."""
        ks = jax.random.split(jax.random.PRNGKey(args.seed % (2 ** 31)), 3)
        q = jax.random.normal(ks[0], (CALLS, B, P, R, lane_decode.COLUMNS), bf16)
        k = jax.random.normal(ks[1], (1, B, M, P * lane_decode.COLUMNS), bf16)
        v = jax.random.normal(ks[2], (1, B, M, P * lane_decode.COLUMNS), bf16)

        @jax.jit
        def calls(q, k, v, visible):
            # every call derives its tables from lengths of its own, as a layer of the model does
            return lax.map(lambda xs: lane_decode.lane_decode(xs[0], k, v, 0, xs[1], scale=0.09, name="diff_decode"),
                           (q, visible))

        def us_a_call(k, v, visible):
            visible = jnp.asarray(np.broadcast_to(visible, (CALLS, B)))   # an operand: nothing hoists the tables
            for _ in range(2):
                out = calls(q, k, v, visible)
            jax.block_until_ready(out)
            n, t0 = 0, time.perf_counter()
            while (took := time.perf_counter() - t0) < args.seconds:
                for _ in range(3):
                    out = calls(q, k, v, visible)
                jax.block_until_ready(out)
                n += 3
            return 1e6 * took / (n * CALLS)

        typical = np.linspace(*window, B).astype(np.int32)
        fills = {"idle": np.zeros(B, np.int32), "one block a slot": np.full(B, lane_decode.LANES, np.int32),
                 "the window's contexts": typical, "full": np.full(B, M, np.int32)}
        G, us_of = B * (M // lane_decode.LANES), {}
        walked = lambda visible: int((-(-visible // lane_decode.LANES)).sum())  # noqa: E731
        for what, visible in fills.items():
            us_of[what] = us = us_a_call(k, v, visible)
            live_bytes = int(visible.sum()) * P * lane_decode.COLUMNS * 2 * 2
            print(json.dumps({"cells": name, "what": "split", "fill": what, "blocks_walked": walked(visible),
                              "blocks_held": G, "us_a_call": round(us, 2),
                              "pct_of_819_gb_s_live_bytes": round(100 * live_bytes / HBM_BYTES_PER_S / (us * 1e-6), 1),
                              "device": dev.device_kind}), flush=True)
        if G < 2 * B:
            return      # one block a slot: the three live fills are one, and nothing can be told apart
        # An idle call over half the lanes holds half the blocks and nothing else less: what a block that no slot
        # uses costs (a grid step, where the grid has one for it). A block over that: full against one block a
        # slot. A live slot over an idle one: the rest of one block a slot. What is left of an idle call is its
        # launch, its tables and whatever an idle slot costs.
        half = us_a_call(k[:, :, :M // 2], v[:, :, :M // 2], fills["idle"])
        step = (us_of["idle"] - half) / (G / 2)
        block = step + (us_of["full"] - us_of["one block a slot"]) / (G - B)
        slot = (us_of["one block a slot"] - us_of["idle"]) / B - (block - step)
        parts = {"blocks": walked(typical) * block, "blocks_not_in_use": (G - walked(typical)) * step,
                 "slots": B * slot, "rest_of_an_idle_call": us_of["idle"] - G * step}
        print(json.dumps({"cells": name, "what": "split, solved", "us_a_block": round(block, 3),
                          "us_a_held_block_no_slot_uses": round(step, 3),
                          "us_a_live_slot_over_an_idle_one": round(slot, 3),
                          "idle_call_of_half_the_lanes_us": round(half, 2),
                          "at_the_windows_contexts_us": {k_: round(x, 1) for k_, x in parts.items()},
                          "sum_less_measured_us": round(sum(parts.values()) - us_of["the window's contexts"], 1),
                          "device": dev.device_kind}), flush=True)

    if args.split:
        per_group = lambda KV, HD, G: (KV // max(128 // HD, 1), max(128 // HD, 1) * G)  # noqa: E731
        shapes = {**{n: (B, M, *per_group(KV, HD, G), (M // 2, M // 2)) for n, (B, M, KV, HD, G) in SHAPES.items()},
                  **SPLIT_SHAPES}
        for name, shape in shapes.items():
            if chosen(name):
                split(name, *shape)
        return

    for name, (B, M, KV, HD, G) in SHAPES.items():
        if not chosen(name):
            continue
        cfg = ModelConfig(name="probe", vocab_size=8, d_model=KV * G * HD, n_layers=LAYERS, n_heads=KV * G,
                          n_kv_heads=KV, d_ff=8)
        ks = jax.random.split(jax.random.PRNGKey(args.seed % (2 ** 31)), 4)
        small = (jax.random.normal(ks[0], (LAYERS, B, KV * G, HD), bf16),
                 jax.random.normal(ks[1], (LAYERS, B, KV * HD), bf16))
        k4 = jax.random.normal(ks[2], (LAYERS, B, M, KV * HD), bf16)
        v4 = jax.random.normal(ks[3], (LAYERS, B, M, KV * HD), bf16)
        assert generate.lane_walk_engages(k4, 1, cfg), "the kernel does not engage at this shape"
        by_kernel, by_xla = walk(kernel_read, cfg), walk(xla_read, cfg)
        by_pipeline = traced_with(pipeline_only, walk(kernel_read, cfg))

        # one layer-step from one pool, both ways, before anything is timed
        lengths = jnp.asarray(np.linspace(0, M - 1, B).astype(np.int32))
        active = jnp.arange(B) % 3 != 1
        got = kernel_read(small[0][1], k4, v4, 1, lengths, active, cfg)
        want = xla_read(small[0][1], k4.reshape(LAYERS, B, M, KV, HD), v4.reshape(LAYERS, B, M, KV, HD), 1,
                        lengths, active, cfg)
        live_rows = np.asarray(active)
        same = {"max_diff_live": float(jnp.max(jnp.abs(got.astype(f32) - want.astype(f32))[live_rows])),
                "max_abs_idle": float(jnp.max(jnp.abs(got.astype(f32))[~live_rows])),
                "max_abs": float(jnp.max(jnp.abs(want.astype(f32))))}
        print(json.dumps({"cells": name, "pool": [LAYERS, B, M, KV * HD], "agreement": same}), flush=True)

        for live in args.live.split(","):
            n_live = B if live == "all" else int(live)
            for end in (int(x) for x in args.lengths.split(",")):
                active = jnp.arange(B) < n_live
                # a live slot ends the dispatch having written lane ``end - 1``
                lengths = jnp.where(active, end - STEPS, 0).astype(jnp.int32)
                live_bytes = n_live * (end - (STEPS - 1) / 2) * KV * HD * 2 * 2   # keys and values, mean over the steps

                def line(what, us, **more):
                    print(json.dumps({"cells": name, "what": what, "live_slots": n_live, "length": end,
                                      "us_a_layer_step": round(us, 1),
                                      "pct_of_819_gb_s_live_bytes": round(100 * live_bytes / HBM_BYTES_PER_S / (us * 1e-6), 1),
                                      **more, "device": dev.device_kind}), flush=True)

                us, k4, v4 = us_a_layer_step(by_kernel, small, k4, v4, lengths, active)
                line("lane_decode (kernel), [L,B,M,KVxHD]", us)
                if args.ceiling:
                    us, k4, v4 = us_a_layer_step(by_pipeline, small, k4, v4, lengths, active)
                    line("the kernel's pipeline, copying only", us)
                k5, v5 = k4.reshape(LAYERS, B, M, KV, HD), v4.reshape(LAYERS, B, M, KV, HD)
                del k4, v4
                us, k5, v5 = us_a_layer_step(by_xla, small, k5, v5, lengths, active)
                line("slice + two contractions (XLA, the parent's), [L,B,M,KV,HD]", us)
                k4, v4 = k5.reshape(LAYERS, B, M, KV * HD), v5.reshape(LAYERS, B, M, KV * HD)
                del k5, v5
        del k4, v4, small


if __name__ == "__main__":
    main()
