"""The prefill chunk kernel of a block-sparse attention layer
(``ops.sparse_block_attention.sparse_chunk_attend``, reached through
``generate._sparse_prefill``) against a plain ``jnp`` oracle: every lane's
score for every query, masked to what the query attends — the lanes up to its
position of its chosen blocks, of every block below ``sparse_dense_len`` —
and one softmax over the row. That oracle was the program's own prefill until
the kernel replaced it.

Tiny widths (2 kv-heads x 2 heads of 16, blocks of 16 lanes, the 4 best of
them, 2 local, windows of 8 keys every 4), float32 on the CPU, the kernel
interpreted. Both sides are float32 and differ in the order of their sums (a
running softmax over key tiles against one over the row): the cases measured
2.1e-7 to 7.2e-7 where outputs spread 0.22, and ``TOL`` = 3e-6 leaves four
times that. The control attends, for every query, the whole union of its tile,
and must miss ``TOL`` by far.
"""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from tpu_engine.models import transformer as tfm  # noqa: E402
from tpu_engine.ops import sparse_block_attention as sba  # noqa: E402

sba.INTERPRET_OFF_TPU = True  # these are the CPU's tests: both kernels are interpreted

generate = sys.modules["tpu_engine.generate"]  # the package's ``generate`` is the function
TOL = 3e-6
KV, G, HD, BLOCK = 2, 2, 16, 16
F32 = jnp.float32


def _config(dense_len=64, topk=4):
    return tfm.ModelConfig(
        name="chunk-kernel", arch="llama", vocab_size=64, d_model=KV * G * HD, n_layers=1, n_heads=KV * G,
        n_kv_heads=KV, d_ff=32, max_seq_len=1024, norm_eps=1e-6, layer_types=("sparse_attention",),
        sparse_block_size=BLOCK, sparse_topk=topk, sparse_init_blocks=1, sparse_local_blocks=2,
        sparse_kernel_size=8, sparse_kernel_stride=4, sparse_dense_len=dense_len)


def _draw(seed, B, T, lanes, layers, starts):
    """Random queries, a pool of ``layers`` layers and the compressed keys its
    windows imply; row b's chunk stands at positions starts[b] .. + T - 1."""
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(seed), 3)
    qg = jax.random.normal(kq, (B, T, KV, G, HD), F32)
    k_pool = jax.random.normal(kk, (layers, B, lanes, KV * HD), F32)
    v_pool = jax.random.normal(kv, (layers, B, lanes, KV * HD), F32)
    windows = jnp.stack([k_pool[:, :, 4 * m:4 * m + 8].mean(2) for m in range((lanes - 8) // 4 + 1)], 2)
    ck_pool = jnp.pad(windows, ((0, 0), (0, 0), (0, lanes // 4 - windows.shape[2]), (0, 0)))
    positions = jnp.asarray(starts, jnp.int32)[:, None] + jnp.arange(T, dtype=jnp.int32)
    return qg, k_pool, v_pool, ck_pool, positions


def _chosen(qg, ck_pool, at, positions, n_blocks, cfg):
    """[B,KV,T,n_blocks]: the blocks each query attends, by the program's own indexer."""
    B = qg.shape[0]
    ids = generate._select_blocks(qg, ck_pool[at].reshape(B, -1, KV, HD), positions, n_blocks, cfg)
    chosen = jnp.any(ids[..., None] == jnp.arange(n_blocks), axis=-2)
    chosen |= (positions < cfg.sparse_dense_len)[:, None, :, None]
    return chosen & (jnp.arange(n_blocks) <= (positions // BLOCK)[:, None, :, None])


def _oracle(qg, k_pool, v_pool, at, positions, chosen, cfg):
    """Dense and masked: every lane's score, one softmax over the row."""
    B, S = qg.shape[0], k_pool.shape[2]
    k = k_pool[at].reshape(B, S, KV, HD)
    v = v_pool[at].reshape(B, S, KV, HD)
    s = jnp.einsum("btkgd,bmkd->bkgtm", qg, k, preferred_element_type=F32) * tfm.attention_scale(cfg)
    keep = jnp.repeat(chosen, BLOCK, axis=-1) & (jnp.arange(S) <= positions[:, None, :, None])
    p = jax.nn.softmax(jnp.where(keep[:, :, None], s, -1e30), axis=-1).astype(qg.dtype)
    return jnp.einsum("bkgtm,bmkd->btkgd", p, v)


# name: (rows' first positions, T, lanes, dense_len, topk, layers, at, lanes a key tile)
CASES = {
    "one_row_two_whole_tiles_past_dense_len": ([256], 256, 512, 64, 4, 1, 0, 256),
    "two_rows_at_different_positions": ([128, 40], 256, 512, 64, 4, 1, 0, 64),
    "T_is_no_multiple_of_the_tile": ([200], 200, 512, 64, 4, 1, 0, 32),
    "T_smaller_than_a_tile_and_no_multiple_of_8": ([100], 21, 256, 64, 4, 1, 0, 32),
    "straddles_dense_len": ([0], 256, 256, 100, 4, 1, 0, 32),
    "wholly_below_dense_len": ([16], 160, 512, 8192, 4, 1, 0, 64),
    "wholly_past_dense_len_key_tiles_of_one_block": ([300], 136, 512, 64, 4, 1, 0, 16),
    "fewer_blocks_than_topk": ([24], 24, 48, 16, 8, 1, 0, 256),
    "two_rows_layer_2_of_a_pool_of_3": ([64, 150], 144, 320, 64, 4, 3, 2, 32),
}


@pytest.mark.parametrize("case", CASES)
def test_a_chunk_through_the_kernel_equals_the_dense_masked_oracle(case, monkeypatch):
    starts, T, lanes, dense_len, topk, layers, at, key_lanes = CASES[case]
    monkeypatch.setattr(sba, "_KEY_LANES", key_lanes)
    cfg = _config(dense_len, topk)
    qg, k_pool, v_pool, ck_pool, positions = _draw(len(case), len(starts), T, lanes, layers, starts)
    got = jax.jit(generate._sparse_prefill, static_argnums=6)(
        qg, k_pool, v_pool, ck_pool, jnp.int32(at), positions, cfg)
    chosen = _chosen(qg, ck_pool, at, positions, lanes // BLOCK, cfg)
    want = _oracle(qg, k_pool, v_pool, at, positions, chosen, cfg)
    assert got.shape == want.shape == qg.shape
    assert float(jnp.abs(got - want).max()) < TOL
    # the case is what its name says
    past = np.asarray(positions) >= dense_len
    assert {"straddles_dense_len": past.any() and not past.all(), "wholly_below_dense_len": not past.any()}.get(
        case, past.any())


def test_control_a_query_that_attends_its_tiles_whole_union_moves_the_output(monkeypatch):
    """The mask is per query. A kernel that let every query attend what ANY
    query of its tile chose (fast, and wrong) is this: the same kernel given
    the tile's union as every query's choice. It must miss by far (measured
    1.6 where outputs spread 0.22)."""
    monkeypatch.setattr(sba, "_KEY_LANES", 32)
    cfg = _config()
    qg, k_pool, v_pool, ck_pool, positions = _draw(3, 1, 256, 512, 1, [256])
    chosen = _chosen(qg, ck_pool, 0, positions, 32, cfg)
    want = _oracle(qg, k_pool, v_pool, 0, positions, chosen, cfg)
    attend = lambda c: sba.sparse_chunk_attend(  # noqa: E731
        qg, k_pool, v_pool, c, 0, positions, block=BLOCK, scale=tfm.attention_scale(cfg), interpret=True)
    assert float(jnp.abs(attend(chosen) - want).max()) < TOL
    tq, _ = sba.chunk_geometry(256, 32, BLOCK)
    union = jnp.repeat(chosen.reshape(1, KV, 256 // tq, tq, 32).any(3), tq, axis=2)
    assert int(union.sum()) > 2 * int(chosen.sum())  # random weights: a tile's queries choose apart
    assert float(jnp.abs(attend(union) - want).max()) > 0.1 > 1e4 * TOL


@pytest.mark.parametrize("seed", range(6))
def test_a_tiles_visits_hold_every_chosen_block_and_nothing_past_the_tile(seed):
    """Property, over random chosen maps with the causal bound: the list a
    program walks (i) holds every key tile in which some query of the tile
    chose some block, once, ascending; (ii) holds no other: none in which no
    query chose a block, so none that begins past the tile's last position."""
    rng = np.random.default_rng(seed)
    B, T, n_blocks = 2, 96, 48
    tq, nb = (8, 16, 32)[seed % 3], (1, 2, 4)[seed % 3]
    positions = rng.integers(0, n_blocks * BLOCK - T, (B, 1)) + np.arange(T)
    own = positions // BLOCK
    chosen = rng.random((B, KV, T, n_blocks)) < (0.02, 0.2)[seed % 2]
    chosen |= np.arange(n_blocks) == own[:, None, :, None]          # a query's own block
    chosen &= np.arange(n_blocks) <= own[:, None, :, None]          # none past it
    tiles, count = (np.asarray(a) for a in sba.tile_visits(jnp.asarray(chosen), tq, nb))
    assert tiles.shape == (B, KV, T // tq, n_blocks // nb) and count.shape == tiles.shape[:3]
    for b, g, i in np.ndindex(*count.shape):
        walked = tiles[b, g, i, :count[b, g, i]].tolist()
        mine = chosen[b, g, i * tq:(i + 1) * tq]                    # [tq, n_blocks]
        wanted = sorted({int(blk) // nb for blk in np.nonzero(mine.any(0))[0]})
        assert walked == wanted
        last = positions[b, (i + 1) * tq - 1]
        assert all(tile * nb * BLOCK <= last for tile in walked)
        assert sorted(tiles[b, g, i].tolist()) == list(range(n_blocks // nb))  # the rest: a permutation's tail


def test_the_chunk_kernel_has_its_own_name_in_a_profile():
    """``layer_metrics/sparse_block_attn_roofline`` sums every kernel whose
    name holds the decode kernel's; the chunk kernel's must not."""
    cfg = _config()
    qg, k_pool, v_pool, ck_pool, positions = _draw(0, 1, 32, 64, 1, [32])
    text = jax.jit(generate._sparse_prefill, static_argnums=6).lower(
        qg, k_pool, v_pool, ck_pool, jnp.int32(0), positions, cfg).as_text(debug_info=True)
    assert "sparse_chunk_attn" in text and "sparse_block_attn" not in text
    assert "sparse_attend" in text and "sparse_index" in text


@pytest.fixture(scope="module")
def one_chip():
    """A described v5e chip (the TPU's compiler, no chip attached); made inside
    the fixture so that only the worker given this file loads the library."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def test_both_kernels_compile_for_the_chip_at_the_long_document_cells_widths(one_chip):
    """What interpret mode cannot show: Mosaic takes the kernels at the real
    widths (one staging row of 10 240 lanes, a 2 048-query chunk, 2 kv-heads x
    16 heads of 128, blocks of 64; decode: 16 rows of 34 816 lanes, 64 blocks a
    row). A compile, not a run."""
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)  # noqa: E731
    bf16, i32 = jnp.bfloat16, jnp.int32
    chunk = jax.jit(lambda q, k, v, c, at, pos: sba.sparse_chunk_attend(
        q, k, v, c, at, pos, block=64, scale=128 ** -0.5))
    text = chunk.lower(sds((1, 2048, 2, 16, 128), bf16), sds((3, 1, 10240, 256), bf16), sds((3, 1, 10240, 256), bf16),
                       sds((1, 2, 2048, 160), jnp.bool_), sds((), i32), sds((1, 2048), i32)).compile().as_text()
    assert "sparse_chunk_attn" in text and "tpu_custom_call" in text
    step = jax.jit(lambda q, k, v, ids, at, pos: sba.sparse_block_attend(
        q, k, v, ids, at, pos, block=64, scale=128 ** -0.5))
    text = step.lower(sds((16, 2, 16, 128), bf16), sds((3, 16, 34816, 256), bf16), sds((3, 16, 34816, 256), bf16),
                      sds((16, 2, 64), i32), sds((), i32), sds((16,), i32)).compile().as_text()
    assert "sparse_block_attn" in text and "tpu_custom_call" in text


@pytest.mark.parametrize("cell,L,B,H,P,N,per_head", [
    ("granite-4.0-h-micro.serve-chat-burst", 18, 32, 64, 64, 128, False),
    ("granite-4.0-h-small.serve-batch32", 9, 32, 128, 64, 128, False),
    ("minicpm-sala.serve-longdoc", 9, 16, 32, 128, 128, True),
])
def test_the_recurrent_update_compiles_in_place_at_the_cells_shapes(one_chip, monkeypatch, cell, L, B, H, P, N, per_head):
    """The one-pass decode update (``ops.ssd_update``, kept here with the other
    compiles for the chip: one file, one worker, one load of the library) at
    the three recurrent cells' shapes, the stack the donated carry of a scan
    over the layers as ``scan_layers`` holds it: Mosaic takes the kernel, the
    whole stack is aliased to the output, and the program's own temporaries
    stay far under ONE layer's state, so no copy of the stack or of a slice of
    it is made. A compile, not a run."""
    from jax import lax

    from tpu_engine.ops import ssd_update

    monkeypatch.setattr(ssd_update, "on_tpu", lambda: True)  # the described chip: this process's devices are the CPU's
    sds = lambda shape, dt=F32: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)  # noqa: E731
    bf16, bc = jnp.bfloat16, ((L, B, H, N) if per_head else (L, B, N))

    def walk(x, dt, A, Bm, Cm, state):
        def layer(state, xs):
            at, x, dt, Bm, Cm = xs
            y, state = generate._ssd_step_at(x, dt, A, Bm, Cm, state, at)
            return state, y

        return lax.scan(layer, state, (jnp.arange(L, dtype=jnp.int32), x, dt, Bm, Cm))

    compiled = jax.jit(walk, donate_argnums=(5,)).lower(
        sds((L, B, H, P), bf16), sds((L, B, H)), sds((H,)), sds(bc, bf16), sds(bc, bf16),
        sds((L, B, H, P, N))).compile()
    text, memory = compiled.as_text(), compiled.memory_analysis()
    assert "ssd_update" in text and "tpu_custom_call" in text
    assert memory.alias_size_in_bytes == L * B * H * P * N * 4
    assert memory.temp_size_in_bytes < B * H * P * N * 4 // 8


def test_the_latent_pool_is_written_in_place_at_the_long_context_cells_shapes(one_chip, monkeypatch):
    """A latent-attention (MLA) decode chunk at ``kimi-vl-a3b.serve-longctx32``'s
    widths and pool (32 slots x 10 240 lanes; three of its thirteen layers, to
    keep the compile short), kept here with the other compiles for the chip.
    The pool's rows are 640 wide (576 padded to the chip's tile columns): the
    donated pool is aliased to the output and the program's temporaries stay
    under HALF A LAYER's latent, so no layer of the pool is copied or laid out
    anew. With rows of 576 the chip lays the lanes out as the minor dimension
    and the same program transposes the whole pool there and back every
    dispatch (PERF.md §6 PR 40). Mosaic takes the decode kernel
    (``ops.mla_decode``) at these widths and the program holds it. A compile,
    not a run."""
    import json
    from functools import partial

    from tpu_engine import layer_state, serving
    from tpu_engine.ops import mla_decode

    monkeypatch.setattr(mla_decode, "on_tpu", lambda: True)  # the described chip: this process's devices are the CPU's

    bench = os.path.join(ROOT, "benchmarks", "onchip")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    from families import deepseek_v3

    with open(os.path.join(bench, "configs", "kimi-vl-a3b-1chip-serve.json")) as f:
        config = json.load(f)
    mc = deepseek_v3.model_config({**config, "num_hidden_layers": 3}, "kimi-3-layers")
    assert layer_state.latent_row_width(mc) == 640 and mc.latent_width == 576
    put = lambda t: jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), t)  # noqa: E731
    bf16, B = jnp.bfloat16, 32
    params = put(jax.eval_shape(lambda k: tfm.init_params(k, mc, dtype=bf16), jax.random.PRNGKey(0)))
    pool = put(jax.eval_shape(lambda: serving.init_slot_cache(mc, B, 10240, bf16, prefill_chunk=2048)))
    vec = lambda dt: jax.ShapeDtypeStruct((B,), dt, sharding=one_chip)  # noqa: E731
    key = put(jax.eval_shape(lambda: jax.random.PRNGKey(0)))
    dec = jax.jit(partial(serving.decode_chunk, cfg=mc, n_steps=2, compute_dtype=bf16), donate_argnums=(2,))
    compiled = dec.lower(params, vec(jnp.int32), pool, vec(jnp.bool_), vec(jnp.float32), vec(jnp.int32),
                         vec(jnp.int32), key).compile()
    text, memory = compiled.as_text(), compiled.memory_analysis()
    assert "mla_decode" in text and "tpu_custom_call" in text
    layer = B * 10240 * 640 * 2
    assert memory.alias_size_in_bytes >= 3 * layer
    assert memory.temp_size_in_bytes < layer // 2
    # and a 2 048-token chunk against a staging row of 8 192 lanes holds the flash-style chunk kernel
    from tpu_engine.generate import init_cache

    row = put(jax.eval_shape(lambda: init_cache(mc, 1, 8192, dtype=bf16)))
    pre = jax.jit(partial(serving._prefill_forward, cfg=mc, compute_dtype=bf16), donate_argnums=(2,))
    text = pre.lower(params, jax.ShapeDtypeStruct((1, 2048), jnp.int32, sharding=one_chip), row,
                     jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)).compile().as_text()
    assert "mla_chunk_attn" in text


def test_the_differential_decode_kernel_compiles_and_the_shared_cache_is_never_copied(one_chip, monkeypatch):
    """``ops.lane_decode`` at Phi-4-mini-flash-reasoning's widths (20 kv-heads of
    64 = ten pairs of 128 values a lane; 32 slots of 12 288 lanes for the ONE
    full layer, a ring of 512 for the window layers), inside the decode program
    of a stack cut to one period of each decoder (6 layers: the pattern's four
    loops): Mosaic takes the kernel for the full layer, the cross layers and the
    ring alike; the pool is aliased to the output; and the program's temporaries
    stay far under ONE copy of the shared cache's keys (XLA's own contractions
    want the lanes minor and transpose both leaves, 1 GB each, on every step
    and for every reader: PERF.md, PR 43). A compile, not a run."""
    from functools import partial

    from tpu_engine import serving
    from tpu_engine.ops import lane_decode

    monkeypatch.setattr(lane_decode, "on_tpu", lambda: True)  # the described chip: this process's devices are the CPU's
    types = ("mamba1", "diff_window_attention", "mamba1", "diff_attention", "gmu", "diff_cross_attention")
    mc = tfm.ModelConfig(name="phi-6-layers", vocab_size=8192, d_model=2560, n_layers=6, n_heads=40, n_kv_heads=20,
                         d_ff=10240, layer_types=types, sliding_window=512, mamba1_inner=5120, mamba1_state=16,
                         layer_norm=True, attn_bias=True, rope=False, tie_head=True)
    put = lambda t: jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), t)  # noqa: E731
    bf16, B = jnp.bfloat16, 32
    params = put(jax.eval_shape(lambda k: tfm.init_params(k, mc, dtype=bf16), jax.random.PRNGKey(0)))
    pool = put(jax.eval_shape(lambda: serving.init_slot_cache(mc, B, 12288, bf16, prefill_chunk=2048)))
    assert pool.layers["full_attn"]["k"].shape == (1, B, 12288, 1280) and pool.layers["window_attn"]["k"].shape == (1, B, 512, 1280)
    vec = lambda dt: jax.ShapeDtypeStruct((B,), dt, sharding=one_chip)  # noqa: E731
    key = put(jax.eval_shape(lambda: jax.random.PRNGKey(0)))
    dec = jax.jit(partial(serving.decode_chunk, cfg=mc, n_steps=2, compute_dtype=bf16), donate_argnums=(2,))
    compiled = dec.lower(params, vec(jnp.int32), pool, vec(jnp.bool_), vec(jnp.float32), vec(jnp.int32),
                         vec(jnp.int32), key).compile()
    text, memory = compiled.as_text(), compiled.memory_analysis()
    assert text.count("diff_decode") >= 3 and "tpu_custom_call" in text
    keys = B * 12288 * 1280 * 2
    assert memory.alias_size_in_bytes >= 2 * keys
    assert memory.temp_size_in_bytes < keys // 4


@pytest.mark.parametrize("shape", ["mistral-7b", "granite-4.0-h-micro"])
def test_the_attn_decode_kernel_compiles_and_no_layer_of_the_pool_is_copied(one_chip, monkeypatch, shape):
    """``ops.lane_decode`` under the ``attn`` kind at the serving cells' widths
    — Mistral-7B's (8 kv-heads of 128: a lane's row 1 024 values, one head a
    column group; 16 slots of 2 048 lanes) and granite-4.0-h-micro's attention
    layers' (8 kv-heads of 64: 512 values, two heads a group; 32 slots) — inside
    the decode program of a two-layer stack: Mosaic takes the kernel, the pool
    is aliased to the output, and nothing in the program has the shape of a
    layer's keys but the step's own in-place writes — no copy, transposition or
    slice of ``[slots, lanes, KV x HD]`` (XLA's contractions copy both leaves'
    layer out of the carried pool on every layer-step: PERF.md, PR 44). A
    compile, not a run."""
    import re
    from functools import partial

    from tpu_engine import serving
    from tpu_engine.ops import lane_decode

    monkeypatch.setattr(lane_decode, "on_tpu", lambda: True)  # the described chip: this process's devices are the CPU's
    B, HD, d_model, d_ff = (16, 128, 4096, 14336) if shape == "mistral-7b" else (32, 64, 2048, 8192)
    mc = tfm.ModelConfig(name=shape + "-2-layers", vocab_size=8192, d_model=d_model, n_layers=2, n_heads=32,
                         n_kv_heads=8, d_ff=d_ff, sliding_window=4096 if shape == "mistral-7b" else 0)
    put = lambda t: jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), t)  # noqa: E731
    bf16 = jnp.bfloat16
    params = put(jax.eval_shape(lambda k: tfm.init_params(k, mc, dtype=bf16), jax.random.PRNGKey(0)))
    pool = put(jax.eval_shape(lambda: serving.init_slot_cache(mc, B, 2048, bf16, prefill_chunk=512)))
    assert pool.layers["attn"]["k"].shape == (2, B, 2048, 8 * HD) and not pool.ring
    vec = lambda dt: jax.ShapeDtypeStruct((B,), dt, sharding=one_chip)  # noqa: E731
    key = put(jax.eval_shape(lambda: jax.random.PRNGKey(0)))
    dec = jax.jit(partial(serving.decode_chunk, cfg=mc, n_steps=2, compute_dtype=bf16), donate_argnums=(2,))
    compiled = dec.lower(params, vec(jnp.int32), pool, vec(jnp.bool_), vec(jnp.float32), vec(jnp.int32),
                         vec(jnp.int32), key).compile()
    text, memory = compiled.as_text(), compiled.memory_analysis()
    assert "attn_decode" in text and "tpu_custom_call" in text
    layer = B * 2048 * 8 * HD * 2
    assert memory.alias_size_in_bytes >= 4 * layer
    assert memory.temp_size_in_bytes < layer // 2
    a_layer = re.compile(rf"= bf16\[(1,)?{B},2048,({8 * HD}|8,{HD})\]\S* (copy|transpose|slice|dynamic-slice|fusion)\(")
    assert not [line for line in text.splitlines() if a_layer.search(line)]


def test_the_grouped_expert_kernels_compile_and_no_experts_weights_are_copied(one_chip, monkeypatch):
    """``ops.expert_gmm`` inside a walk of a three-layer stack at the
    long-context cell's widths (D 2 048, F 1 408, 16 of 64 experts held, 6 a
    token, a chunk of 2 048): Mosaic takes both kernels with an expert's whole
    ``[2048, 1408]`` block resident (twice in flight, gate and up: 23 MB of the
    chip's fast memory), their operands are the stacks AS THEY ARE HANDED IN
    (``[3, 16, ...]``: nothing in the program has the shape of one layer's
    experts, so no slice of the stack is copied for a custom call), and each
    carries its scope. A compile, not a run."""
    import re

    from tpu_engine.ops import expert_gmm

    generate = sys.modules["tpu_engine.generate"]
    monkeypatch.setattr(expert_gmm, "on_tpu", lambda: True)  # the described chip: this process's devices are the CPU's
    L, E, D, F, T = 3, 16, 2048, 1408, 2048
    mc = dataclasses.replace(tfm.MODEL_CONFIGS["moe-tiny"], d_model=D, d_ff=F, n_experts=64, top_k=6,
                             experts_held=E, router_scoring="sigmoid", routed_scale=2.446)
    bf16 = jnp.bfloat16
    sds = lambda shape, dt=bf16: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)  # noqa: E731
    stacks = {"router": {"kernel": sds((L, D, 64))}, "router_bias": sds((L, 64), jnp.float32),
              "gate": {"kernel": sds((L, E, D, F))}, "up": {"kernel": sds((L, E, D, F))},
              "down": {"kernel": sds((L, E, F, D))}}
    assert generate.experts_grouped_engages(T, mc, stacks["gate"]["kernel"])

    def walk(h, stacks):
        def layer(x, at):
            lp = jax.tree.map(lambda a: generate.layer_slice(a, at), stacks)
            lp["experts_in_stack"] = (stacks, at)
            with jax.named_scope("moe"):
                y, _ = generate._moe_mlp_decode(x, lp, mc, jnp.ones(x.shape[:2], bool))
            return x + y, None
        return jax.lax.scan(layer, h, jnp.arange(L, dtype=jnp.int32))[0]

    compiled = jax.jit(walk).lower(sds((1, T, D)), stacks).compile()
    text = compiled.as_text()
    calls = [line for line in text.splitlines() if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 2
    for line, name in zip(sorted(calls, key=lambda ln: "expert_down" in ln), ("expert_gate_up", "expert_down")):
        assert f"moe/moe_experts/{name}" in line
        assert f"bf16[{L},{E}," in line  # the whole stack is the operand
    one_layer = re.compile(rf"= bf16\[(1,)?{E},({D},{F}|{F},{D})\]")
    assert not [line for line in text.splitlines() if one_layer.search(line)]
    assert f"bf16[{expert_gmm.n_tiles(T * 6, E) * expert_gmm.ROWS},{D}]" in text  # the buffer any routing fits


def test_the_power_retention_update_compiles_in_place_at_the_long_document_cells_shapes(one_chip, monkeypatch):
    """The one-pass decode update of a power-retention state (``ops.power_update``,
    kept here with the other compiles for the chip) at
    ``brumby-14b.serve-longdoc12``'s shapes (8 layers, 12 slots, 8 kv-heads of
    128 values, 5 query heads a state, 9 216 coordinates): Mosaic takes the
    kernel, both stacks are aliased to the outputs, and nothing but the
    kernel's small operands is allocated: no copy of the 3.65 GB pool. A
    compile, not a run."""
    from functools import partial

    from tpu_engine.ops import power_update

    monkeypatch.setattr(power_update, "on_tpu", lambda: True)  # the described chip: this process's devices are the CPU's
    L, B, KV, G, HD, W = 8, 12, 8, 5, 128, 9216
    sds = lambda shape, dt=F32: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)  # noqa: E731
    bf16 = jnp.bfloat16
    compiled = jax.jit(partial(power_update.power_update, tile=tfm.POWER_TILE), donate_argnums=(5, 6)).lower(
        sds((B, KV, G, HD), bf16), sds((B, KV, HD), bf16), sds((B, KV, HD), bf16),
        sds((B, KV)), sds((B, KV)), sds((L, B, KV, HD, W)), sds((L, B, KV, W)), sds((), jnp.int32)).compile()
    text, memory = compiled.as_text(), compiled.memory_analysis()
    assert "power_update" in text and "tpu_custom_call" in text
    assert memory.alias_size_in_bytes >= L * B * KV * W * (HD + 1) * 4
    assert memory.temp_size_in_bytes < 64 << 20
