"""The prefill chunk kernel of a block-sparse attention layer, alone on the chip.

Two readings that the serving benchmark does not give (``PERF.md`` §6 PR 32):

``--time``: ``sparse_chunk_attend`` at the long-document cell's shapes (one
row, 2 kv-heads x 16 heads of 128, a 2 048-query chunk at the end of a 10 240-
and a 32 768-lane staging row, blocks of 64) in milliseconds a call and in
TFLOP/s of the key tiles it visited, for a chunk below ``dense_len`` (every
block up to a query's own) and one past it (block 0, the 32 local blocks, 31
others drawn at random: what random weights choose), checked against the
dense masked softmax on the way.

``--union``: how alike the queries of a tile choose. MiniCPM-SALA's
configuration of the cell, weights drawn from ``--seed`` as the cell draws
them, a random 32 768-token prompt ingested chunk by chunk; for every sparse
layer and chunk, the key tiles the kernel visited / the key tiles up to each
query tile's last position, and the same by blocks (blocks some query of the
tile chose / blocks up to the tile's last position).

Run on the chip: ``python benchmarks/sparse_chunk_probe.py --time --union``
(one JSON line a reading). Refuses to time anything off the TPU.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from functools import partial

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "benchmarks", "onchip"))

KV, G, HD, BLOCK, T = 2, 16, 128, 64, 2048
TOPK, LOCAL = 64, 32


def _chosen(rng, positions, n_blocks, dense):
    """[1,KV,T,n_blocks] bool: what a chunk's queries attend."""
    import numpy as np

    own = (positions // BLOCK)[0]                                   # [T]
    blocks = np.arange(n_blocks)
    if dense:
        return np.broadcast_to(blocks <= own[:, None], (1, KV, T, n_blocks)).copy()
    forced = (blocks < 1) | (blocks > own[:, None] - LOCAL)
    score = np.where(forced, 2.0, rng.random((KV, T, n_blocks)))
    score = np.where(blocks <= own[:, None], score, -1.0)
    kth = np.sort(score, axis=-1)[..., -TOPK][..., None]
    return ((score >= kth) & (score >= 0))[None]


def time_kernel(args):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    from tpu_engine.ops import sparse_block_attention as sba

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"a time comes only from the chip; this process runs on {dev.platform!r}")
    rng = np.random.default_rng(args.seed)
    N = 8
    for lanes in (10240, 32768):
        ks = jax.random.split(jax.random.PRNGKey(args.seed), 3)
        qg = jax.random.normal(ks[0], (1, T, KV, G, HD), jnp.bfloat16)
        k_pool = jax.random.normal(ks[1], (3, 1, lanes, KV * HD), jnp.bfloat16)
        v_pool = jax.random.normal(ks[2], (3, 1, lanes, KV * HD), jnp.bfloat16)
        positions = np.arange(lanes - T, lanes, dtype=np.int32)[None]
        for dense in (True, False):
            chosen = jnp.asarray(_chosen(rng, positions, lanes // BLOCK, dense))
            want = None
            for key_lanes, heads in [(int(a), int(b)) for a, b in (v.split("x") for v in args.variants.split(","))]:
                sba._KEY_LANES, sba._STEP_HEADS = key_lanes, heads
                attend = partial(sba.sparse_chunk_attend, block=BLOCK, scale=HD ** -0.5)

                @jax.jit
                def loop(qg, k_pool, v_pool, chosen, pos):
                    def body(q, _):
                        return attend(q, k_pool, v_pool, chosen, 1, pos), None
                    return lax.scan(body, qg, None, length=N)[0]

                pos = jnp.asarray(positions)
                try:
                    one = jax.jit(attend)(qg, k_pool, v_pool, chosen, 1, pos)
                    loop(qg, k_pool, v_pool, chosen, pos).block_until_ready()
                except Exception as e:  # what the chip's compiler refuses
                    print(json.dumps({"lanes": lanes, "dense": dense, "key_lanes": key_lanes, "heads": heads,
                                      "error": f"{type(e).__name__}: {str(e)[:300]}"}), flush=True)
                    continue
                t0 = time.perf_counter()
                loop(qg, k_pool, v_pool, chosen, pos).block_until_ready()
                ms = (time.perf_counter() - t0) / N * 1e3
                if want is None:
                    want = _dense_masked(qg, k_pool[1], v_pool[1], chosen, pos)
                tq, nb = sba.chunk_geometry(T, lanes // BLOCK, BLOCK)
                _, count = sba.tile_visits(chosen, tq, nb)
                flops = 4.0 * G * tq * nb * BLOCK * HD * float(count.sum())
                needed = 4.0 * G * HD * BLOCK * float(chosen.sum())  # chosen blocks only, as counts_sala counts
                print(json.dumps({
                    "reading": "sparse_chunk_attn", "device_kind": dev.device_kind, "lanes": lanes, "dense": dense,
                    "key_lanes": key_lanes, "heads_a_step": heads, "ms": ms, "visited_tflops": flops / ms / 1e9,
                    "needed_tflops": needed / ms / 1e9, "key_tiles_visited": int(count.sum()),
                    "max_abs_diff_vs_dense_masked": float(jnp.abs(one.astype(jnp.float32) - want).max()),
                }), flush=True)


def _dense_masked(qg, k, v, chosen, pos):
    """The oracle, 128 queries at a time: float32 out of the same bf16 inputs."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    S = k.shape[1]
    k, v = k.reshape(1, S, KV, HD), v.reshape(1, S, KV, HD)

    @jax.jit
    def run(qg, chosen, pos):
        def block(xs):
            q, c, p = xs                                            # [1,128,KV,G,HD], [1,KV,128,nb], [1,128]
            s = jnp.einsum("btkgd,bmkd->bkgtm", q, k, preferred_element_type=jnp.float32) * HD ** -0.5
            keep = jnp.repeat(c, BLOCK, axis=-1) & (jnp.arange(S) <= p[:, None, :, None])
            w = jax.nn.softmax(jnp.where(keep[:, :, None], s, -1e30), axis=-1).astype(q.dtype)
            return jnp.einsum("bkgtm,bmkd->btkgd", w, v, preferred_element_type=jnp.float32)
        n = T // 128
        out = lax.map(block, (jnp.moveaxis(qg.reshape(1, n, 128, KV, G, HD), 1, 0),
                              jnp.moveaxis(chosen.reshape(1, KV, n, 128, -1), 2, 0),
                              jnp.moveaxis(pos.reshape(1, n, 128), 1, 0)))
        return jnp.moveaxis(out, 0, 1).reshape(1, T, KV, G, HD)

    return run(qg, chosen, pos)


def union_share(args):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from harness import manifest, program
    from tpu_engine import serving
    from tpu_engine.generate import init_cache
    from tpu_engine.models import transformer as tfm
    from tpu_engine.ops import sparse_block_attention as sba

    man = manifest.load_manifest()
    cell = manifest.load_cell(man, "minicpm-sala.serve-longdoc")
    cfg = cell["config"] if jax.devices()[0].platform == "tpu" else {**cell["config"], **cell["config"]["rehearsal"]}
    mc = program.model_config(cfg, cell["config_entry"]["name"])
    chunk, lanes = cfg["program"]["prefill_chunk"], args.prompt
    dtype = jnp.bfloat16
    params = tfm.served_format(tfm.init_params(jax.random.PRNGKey(args.seed), mc, dtype=dtype), dtype)
    seen: list[dict] = []
    attend = sba.sparse_chunk_attend

    def note(first, tiles, tiles_visible, blocks, blocks_visible):
        seen.append({"first": int(first), "tiles": int(tiles), "tiles_visible": int(tiles_visible),
                     "blocks": int(blocks), "blocks_visible": int(blocks_visible)})

    def recording(qg, k_pool, v_pool, chosen, layer, positions, *, block, **kw):
        n_blocks = chosen.shape[-1]
        tq, nb = sba.chunk_geometry(qg.shape[1], n_blocks, block)
        _, count = sba.tile_visits(chosen, tq, nb)
        last = positions[:, tq - 1::tq]                             # [B, nq]: each query tile's last position
        union = chosen.reshape(*chosen.shape[:2], -1, tq, n_blocks).any(3)
        kv = chosen.shape[1]
        jax.debug.callback(note, positions[0, 0], count.sum(), kv * (last // (nb * block) + 1).sum(),
                           union.sum(), kv * (last // block + 1).sum(), ordered=True)
        return attend(qg, k_pool, v_pool, chosen, layer, positions, block=block, **kw)

    sba.sparse_chunk_attend = recording
    fn = jax.jit(partial(serving._prefill_forward, cfg=mc, compute_dtype=dtype), donate_argnums=(2,))
    toks = np.random.default_rng(args.seed).integers(0, mc.vocab_size, (1, lanes)).astype(np.int32)
    c1 = init_cache(mc, 1, lanes, dtype=dtype)
    for t0 in range(0, lanes, chunk):
        _, c1 = fn(params, jnp.asarray(toks[:, t0:t0 + chunk]), c1, jnp.int32(chunk - 1), jnp.int32(chunk))
    jax.effects_barrier()
    sba.sparse_chunk_attend = attend
    tq, nb = sba.chunk_geometry(chunk, lanes // mc.sparse_block_size, mc.sparse_block_size)
    by_chunk: dict[int, list[dict]] = {}
    for s in seen:
        by_chunk.setdefault(s["first"], []).append(s)
    share = lambda rows, a, b: sum(r[a] for r in rows) / sum(r[b] for r in rows)  # noqa: E731
    for first, rows in sorted(by_chunk.items()):
        print(json.dumps({"reading": "union_share", "chunk_first_position": first, "layers": len(rows),
                          "key_tiles_visited_of_visible": share(rows, "tiles", "tiles_visible"),
                          "blocks_chosen_by_some_query_of_visible": share(rows, "blocks", "blocks_visible")}),
              flush=True)
    past = [s for s in seen if s["first"] >= mc.sparse_dense_len]
    print(json.dumps({"reading": "union_share", "device_kind": jax.devices()[0].device_kind, "seed": args.seed,
                      "prompt": lanes, "query_tile": tq, "key_tile_lanes": nb * mc.sparse_block_size,
                      "chunks_past_dense_len": len(past) // max(1, len(by_chunk[0])),
                      "key_tiles_visited_of_visible": share(past, "tiles", "tiles_visible") if past else None,
                      "blocks_chosen_by_some_query_of_visible":
                          share(past, "blocks", "blocks_visible") if past else None}), flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--time", action="store_true")
    ap.add_argument("--union", action="store_true")
    ap.add_argument("--seed", type=int, default=2147485001)
    ap.add_argument("--prompt", type=int, default=32768)
    ap.add_argument("--variants", default="256x4", help="key lanes x heads a step, comma separated")
    args = ap.parse_args()
    if args.time:
        time_kernel(args)
    if args.union:
        union_share(args)


if __name__ == "__main__":
    main()
