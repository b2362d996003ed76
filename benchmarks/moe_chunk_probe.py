"""A chunk's held experts, alone on the chip: masked, grouped, and ``ragged_dot``.

Times one mixture layer's routed experts (router included, shared expert left
out) for a prefill chunk at the three mixture cells' shapes, three ways, each
as the serving program runs it: a ``lax.scan`` over the layers of the stacked
weights under ``jit``, the activations carried from layer to layer.

- ``masked``: ``generate._moe_mlp_decode`` as a walk that declines runs it, the
  contraction of every held expert for every token.
- ``grouped``: the same function handed the whole stacks (``_experts_grouped``,
  the kernels of ``ops/expert_gmm.py`` over the routed pairs' row tiles),
  engaged by hand whatever ``experts_grouped_engages`` says for the shape.
- ``ragged_dot``: ``lax.ragged_dot`` over the same pairs sorted by expert in a
  buffer of as many rows, on the layer's slice of the stack (what the trial of
  PR 40 ran, less the absent experts' pairs).

One JSON line a reading: ms a layer-chunk and the share of the bf16 peak that
the ROUTED pairs' FLOPs (held pairs x 6 D F) are of it, with what
``experts_grouped_engages`` decides for the shape. The three forms' outputs of
one layer are compared before anything is timed. ``--parts`` adds the grouped
form cut short after the router, the layout, the gather and the kernels, at the
shapes it engages for: the differences are what each stage costs;
``--tile-rows`` tries other row tiles than ``expert_gmm.ROWS``.

Run on the chip: ``python benchmarks/moe_chunk_probe.py --parts``. Refuses to
time anything off the TPU.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

PEAK_BF16_FLOPS = 197e12  # TPU v5e (Google Cloud documentation, "TPU v5e")
# cell: (layers timed, chunk rows, D, F, experts held, of, a token, router)
SHAPES = {
    "kimi-vl-a3b.serve-longctx32": (12, 2048, 2048, 1408, 16, 64, 6, "sigmoid"),
    "mixtral-8x7b.serve-batch": (2, 256, 4096, 14336, 8, 8, 2, "softmax"),
    "granite-4.0-h-small.serve-batch32": (4, 256, 4096, 768, 36, 72, 10, "softmax"),
}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--cells", default=",".join(SHAPES))
    ap.add_argument("--rows", default="", help="other chunk lengths to try at every cell's widths, comma separated")
    ap.add_argument("--parts", action="store_true")
    ap.add_argument("--tile-rows", default="", help="other row tiles than expert_gmm.ROWS to try, comma separated")
    ap.add_argument("--seed", type=int, default=2147485045)
    ap.add_argument("--seconds", type=float, default=0.5, help="timed window a reading")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from jax import lax

    from tpu_engine.models import transformer as tfm
    from tpu_engine.ops import expert_gmm

    generate = sys.modules["tpu_engine.generate"]  # the package's ``generate`` is the function
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"moe_chunk_probe times the chip; this process runs on {dev.platform!r}")
    bf16 = jnp.bfloat16

    def sliced(stacks, at):
        return jax.tree.map(lambda a: generate.layer_slice(a, at), stacks)

    def masked(h, stacks, at, cfg, stop=None):
        return generate._moe_mlp_decode(h, sliced(stacks, at), cfg, jnp.ones(h.shape[:2], bool))[0]

    def grouped(h, stacks, at, cfg, stop=None):
        if stop is None:  # the program's own path, engaged by hand
            lp = {**sliced(stacks, at), "experts_in_stack": (stacks, at)}
            return generate._moe_mlp_decode(h, lp, cfg, jnp.ones(h.shape[:2], bool))[0]
        router = {k: v for k, v in stacks.items() if k.startswith("router")}
        top_idx, top_vals = generate._route(h, sliced(router, at), cfg)
        # the same steps as ``_experts_grouped``, cut short: every stage's result feeds the sum
        B, T, D = h.shape
        K, held = cfg.top_k, cfg.n_experts_held
        some = (jnp.sum(top_vals) + jnp.sum(top_idx)).astype(h.dtype)
        if stop == "router":
            return h * some
        local = top_idx - cfg.experts_first
        ours = (local >= 0) & (local < held)
        layout = expert_gmm.pair_layout(jnp.where(ours, local, held).reshape(-1).astype(jnp.int32), held,
                                        expert_gmm.n_tiles(B * T * min(K, held), held))
        if stop == "layout":
            return h * (some + sum(jnp.sum(a) for a in layout).astype(h.dtype))
        xs = expert_gmm.take(h.reshape(B * T, D), layout.src // K)
        if stop == "gather":
            return h * some + xs[:B * T].reshape(h.shape) * jnp.sum(layout.pos).astype(h.dtype)
        ys = expert_gmm.experts(xs, *(stacks[n]["kernel"] for n in ("gate", "up", "down")), at, layout)
        return h * some + ys[:B * T].reshape(h.shape) * jnp.sum(layout.pos).astype(h.dtype)

    def ragged(h, stacks, at, cfg, stop=None):
        lp = sliced(stacks, at)
        top_idx, top_vals = generate._route(h, lp, cfg)
        B, T, D = h.shape
        K, held = cfg.top_k, cfg.n_experts_held
        local = (top_idx - cfg.experts_first).reshape(-1)
        ours = (local >= 0) & (local < held)
        key = jnp.where(ours, local, held).astype(jnp.int32)
        rows = expert_gmm.n_tiles(B * T * min(K, held), held) * expert_gmm.ROWS
        order = jnp.argsort(key)
        order = jnp.pad(order, (0, max(rows - order.shape[0], 0)))[:rows]
        sizes = jnp.sum(key[:, None] == jnp.arange(held), axis=0, dtype=jnp.int32)
        tok = order // K
        xs = jnp.take(h.reshape(B * T, D), tok, axis=0)
        g = lax.ragged_dot(xs, lp["gate"]["kernel"], sizes, preferred_element_type=h.dtype)
        u = lax.ragged_dot(xs, lp["up"]["kernel"], sizes, preferred_element_type=h.dtype)
        y = lax.ragged_dot(jax.nn.silu(g) * u, lp["down"]["kernel"], sizes, preferred_element_type=jnp.float32)
        w = jnp.where(jnp.arange(rows) < jnp.sum(sizes), top_vals.reshape(-1)[order], 0.0)
        # rows past the groups' end hold whatever the kernel left: select, do not multiply
        y = jnp.where((jnp.arange(rows) < jnp.sum(sizes))[:, None], y * w[:, None], 0.0)
        return jax.ops.segment_sum(y, tok, num_segments=B * T).reshape(h.shape).astype(h.dtype)

    def walk(form, cfg, stop=None):
        """All layers once, the activations carried (and kept O(1)) from layer to layer."""
        def run(h, stacks):
            def layer(x, at):
                y = form(x, stacks, at, cfg, stop)
                return (x + y.astype(x.dtype)) * 0.5 ** 0.5, None
            return lax.scan(layer, h, jnp.arange(stacks["router"]["kernel"].shape[0], dtype=jnp.int32))[0]
        return jax.jit(run)

    def ms_a_layer(fn, h, stacks):
        for _ in range(2):
            out = fn(h, stacks)
        jax.block_until_ready(out)
        calls, t0 = 0, time.perf_counter()
        while (took := time.perf_counter() - t0) < args.seconds:
            for _ in range(3):
                out = fn(h, stacks)
            jax.block_until_ready(out)
            calls += 3
        return 1e3 * took / (calls * stacks["router"]["kernel"].shape[0])

    for cell in args.cells.split(","):
        L, chunk, D, F, held, of, K, scoring = SHAPES[cell]
        cfg = dataclasses.replace(tfm.MODEL_CONFIGS["moe-tiny"], d_model=D, d_ff=F, n_experts=of, top_k=K,
                                  experts_held=held if held < of else 0, router_scoring=scoring,
                                  routed_scale=2.446 if scoring == "sigmoid" else 1.0)
        ks = jax.random.split(jax.random.PRNGKey(args.seed % (2 ** 31)), 6)
        draw = lambda k, shape, std=0.02: (jax.random.normal(k, shape, jnp.float32) * std).astype(bf16)  # noqa: E731
        stacks = {"router": {"kernel": draw(ks[0], (L, D, of))},
                  "gate": {"kernel": draw(ks[1], (L, held, D, F))}, "up": {"kernel": draw(ks[2], (L, held, D, F))},
                  "down": {"kernel": draw(ks[3], (L, held, F, D))}}
        if scoring == "sigmoid":
            stacks["router_bias"] = jax.random.normal(ks[4], (L, of), jnp.float32) * 0.02
        for rows in [chunk] + [int(r) for r in args.rows.split(",") if r]:
            h = jax.random.normal(ks[5], (1, rows, D), jnp.float32).astype(bf16)
            lp1 = jax.tree.map(lambda a: a[1], stacks)
            top_idx = generate._route(h, lp1, cfg)[0]
            pairs = int(jnp.sum((top_idx >= 0) & (top_idx < held)))
            one = {name: jax.jit(lambda h, s, form=form: form(h, s, jnp.int32(1), cfg))(h, stacks)
                   for name, form in (("masked", masked), ("grouped", grouped), ("ragged_dot", ragged))}
            scale = float(jnp.max(jnp.abs(one["masked"].astype(jnp.float32))))
            same = {f"max_d_{name}": float(jnp.max(jnp.abs(one[name].astype(jnp.float32)
                                                            - one["masked"].astype(jnp.float32))))
                    for name in ("grouped", "ragged_dot")}
            engages = generate.experts_grouped_engages(rows, cfg, stacks["gate"]["kernel"])

            def line(what, ms, **more):
                print(json.dumps({"cell": cell, "rows": rows, "held_of": [held, of], "top_k": K, "D_F": [D, F],
                                  "held_pairs": pairs, "what": what, "ms_a_layer_chunk": round(ms, 3),
                                  "routed_pct_of_bf16_peak": round(100 * pairs * 6 * D * F / PEAK_BF16_FLOPS
                                                                   / (ms * 1e-3), 1),
                                  "engages": engages, **more, "device": dev.device_kind}), flush=True)

            line("masked", ms_a_layer(walk(masked, cfg), h, stacks), out_max=scale, **same)
            line("grouped", ms_a_layer(walk(grouped, cfg), h, stacks), tile_rows=expert_gmm.ROWS,
                 tiles_bound=expert_gmm.n_tiles(rows * min(K, held), held))
            default = expert_gmm.ROWS
            for tile_rows in (int(r) for r in args.tile_rows.split(",") if r and int(r) != default):
                expert_gmm.ROWS = tile_rows  # read where the form is traced
                line("grouped", ms_a_layer(walk(grouped, cfg), h, stacks), tile_rows=tile_rows,
                     tiles_bound=expert_gmm.n_tiles(rows * min(K, held), held))
                expert_gmm.ROWS = default
            line("ragged_dot", ms_a_layer(walk(ragged, cfg), h, stacks))
            if args.parts and engages:
                for stop in ("router", "layout", "gather", "kernels"):
                    line(f"grouped, cut after the {stop}", ms_a_layer(walk(grouped, cfg, stop), h, stacks))


if __name__ == "__main__":
    main()
