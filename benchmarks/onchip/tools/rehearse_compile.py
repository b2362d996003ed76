"""Compile-only rehearsal: each configuration's step programs at real size for
a described ``v5e:2x2`` (no chip attached, no chip time), with the bytes the
compiler says each device needs. Decides depth before a chip run does.

    JAX_PLATFORMS=cpu python3 benchmarks/onchip/tools/rehearse_compile.py [config ...] [--layers N]

A compile that passes is not a chip run and is never reported as one.
"""

import argparse
import json
import os
import sys
from functools import partial

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))
sys.path.insert(0, HERE)

GIB = 2**30


def mem(compiled) -> dict:
    m = compiled.memory_analysis()
    return {k: round(getattr(m, k + "_size_in_bytes") / GIB, 3)
            for k in ("argument", "output", "temp", "alias", "generated_code")}


def train(cell, topo):
    import jax
    import jax.numpy as jnp

    from harness import program, train_runner
    from tpu_engine.mesh_runtime import MeshRuntime
    from tpu_engine.train import build_train_program

    program.model_config(cell["config"], cell["config_entry"]["name"])
    tcfg = train_runner._train_config(cell, 0, on_tpu=True)
    n = cell["cell"]["chips"]
    prog = build_train_program(tcfg, runtime=MeshRuntime(tcfg.mesh, devices=topo.devices[:n]))
    state = jax.eval_shape(prog.init, jax.random.PRNGKey(0))
    batch = jax.ShapeDtypeStruct(prog.global_batch_shape(), jnp.int32)
    compiled = prog.step.lower(state, batch).compile()
    text = compiled.as_text()
    return {"step": mem(compiled), "tpu_custom_calls": text.count("tpu_custom_call"),
            "all-gather": text.count("all-gather"), "reduce-scatter": text.count("reduce-scatter")}


def serve(cell, topo):
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from harness import program
    from tpu_engine import serving
    from tpu_engine.generate import init_cache
    from tpu_engine.models import transformer as tfm

    mc = program.model_config(cell["config"], cell["config_entry"]["name"])
    p = cell["config"]["program"]
    one = SingleDeviceSharding(topo.devices[0])
    put = lambda t: jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one), t)  # noqa: E731
    # a replica is built at the dtype it computes in and holds its weights once (``build_replica_engine``)
    params = put(jax.eval_shape(lambda k: tfm.init_params(k, mc, dtype=jnp.bfloat16), jax.random.PRNGKey(0)))
    cache = put(jax.eval_shape(lambda: serving.init_slot_cache(
        mc, p["max_slots"], p["max_len"], jnp.bfloat16, prefill_chunk=p["prefill_chunk"])))
    B = p["max_slots"]
    vec = lambda dt: jax.ShapeDtypeStruct((B,), dt, sharding=one)  # noqa: E731
    key = put(jax.eval_shape(lambda: jax.random.PRNGKey(0)))
    dec = jax.jit(partial(serving.decode_chunk, cfg=mc, n_steps=p["decode_chunk_steps"],
                          compute_dtype=jnp.bfloat16), donate_argnums=(2,))
    out = {"decode_chunk": mem(dec.lower(params, vec(jnp.int32), cache, vec(jnp.bool_), vec(jnp.float32),
                                         vec(jnp.int32), vec(jnp.int32), key).compile())}
    c1 = put(jax.eval_shape(lambda: init_cache(mc, 1, p["max_len"], dtype=jnp.bfloat16)))
    toks = jax.ShapeDtypeStruct((1, p["prefill_chunk"]), jnp.int32, sharding=one)
    row = jax.ShapeDtypeStruct((), jnp.int32, sharding=one)
    pre = jax.jit(partial(serving._prefill_forward, cfg=mc, compute_dtype=jnp.bfloat16), donate_argnums=(2,))
    out["prefill_chunk"] = mem(pre.lower(params, toks, c1, row).compile())
    return out


def main():
    from jax.experimental import topologies

    from harness import manifest

    ap = argparse.ArgumentParser()
    ap.add_argument("workloads", nargs="*")
    ap.add_argument("--layers", type=int)
    args = ap.parse_args()
    man = manifest.load_manifest()
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    for w in man["workloads"]:
        if args.workloads and w["name"] not in args.workloads:
            continue
        cell = manifest.load_cell(man, w["name"])
        if args.layers:
            cell["config"]["num_hidden_layers"] = args.layers
        fn = train if cell["config"]["role"] == "train" else serve
        try:
            res = fn(cell, topo)
        except Exception as e:  # what the chip's compiler would raise
            res = {"error": f"{type(e).__name__}: {str(e)[:400]}"}
        print(json.dumps({"workload": w["name"], "layers": cell["config"]["num_hidden_layers"], **res}), flush=True)


if __name__ == "__main__":
    main()
