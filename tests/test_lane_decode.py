"""A decode step's read of the ``attn`` kind's keys and values through the
lane-walking kernel (``tpu_engine/ops/lane_decode``) against the plain statement
of what it computes: ``generate._decode_block``'s two XLA contractions.

The kernel is interpreted here (its ``INTERPRET_OFF_TPU`` switch), at the
smallest sizes it engages at: blocks of 512 lanes, column groups of 128
values. Both sides contract bfloat16 operands into float32; they differ in the
order of the softmax's sum (blocks of 512 lanes against one pass) and in where
the probabilities are rounded to bfloat16, so outputs are held to ``TOL`` =
0.02 of the largest value (bfloat16 has 8 bits: 0.4 % a rounding; the int8
cache's tests use the same bound). What the kernel must not touch it leaves bit
for bit: the leaves, and an idle slot's row is exactly zeros.
"""

import dataclasses
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks", "onchip")
for p in (ROOT, BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)

from tpu_engine import layer_state, serving  # noqa: E402
from tpu_engine.generate import (  # noqa: E402
    _decode_block, _grouped_outputs, _grouped_queries, forward_with_cache, init_cache, lane_walk_engages, layer_slice)
from tpu_engine.hbm_estimate import estimate_serving_hbm  # noqa: E402
from tpu_engine.models import transformer as tfm  # noqa: E402
from tpu_engine.ops import lane_decode  # noqa: E402
from tpu_engine.serving import ContinuousBatcher, init_slot_cache  # noqa: E402

BF16, F32 = jnp.bfloat16, jnp.float32
TOL = 0.02
M = 1024  # two blocks of lanes


def _cfg(HD: int, G: int, KV: int = 2, **kw) -> tfm.ModelConfig:
    H = KV * G
    return tfm.ModelConfig(name=f"hd{HD}g{G}", vocab_size=128, d_model=H * HD, n_layers=2, n_heads=H,
                           n_kv_heads=KV, d_ff=64, **kw)


def _layer(cfg, seed=0):
    params = tfm.init_params(jax.random.PRNGKey(seed), cfg, dtype=F32)
    return jax.tree.map(lambda a: a.astype(BF16), params["layers"])


# (a) the kernel against the XLA contractions, one layer of a stacked leaf -----------------------

# a slot's length before the step (it sees length + 1 lanes); -1: the slot does not decode
LENGTHS = (-1, 0, 510, -1, 511, 512, M - 1, -1)   # idle slots at the front, in the middle and at the end


@pytest.mark.parametrize("at", [0, 1], ids=["first-layer", "last-layer"])
@pytest.mark.parametrize("G", [1, 4])
@pytest.mark.parametrize("HD", [128, 64])
def test_the_kernel_equals_the_xla_contractions(monkeypatch, HD, G, at):
    """``_decode_block`` with the walk's ``visible`` (the kernel, interpreted)
    against the same block without it (XLA's contractions under the position
    mask): rows seeing 1, 511, 512, 513 lanes and the pool's last; the layer
    index first and last; idle slots, whose attention is exactly zeros; the
    stacks the same bit for bit, written in the step's lanes and nowhere else."""
    cfg = _cfg(HD, G)
    KV, B = cfg.n_kv_heads, len(LENGTHS)
    stack = _layer(cfg)
    lp = jax.tree.map(lambda a: a[at], stack)
    rng = np.random.default_rng([HD, G, at])
    k_arr, v_arr = (jnp.asarray(rng.normal(size=(2, B, M, KV * HD)), BF16) for _ in range(2))
    x = jnp.asarray(rng.normal(size=(B, 1, cfg.d_model)), BF16)
    active = jnp.asarray([n >= 0 for n in LENGTHS])
    lengths = jnp.asarray([max(n, 0) for n in LENGTHS], jnp.int32)
    positions = lengths[:, None]
    slot_pos = jnp.broadcast_to(jnp.arange(M, dtype=jnp.int32)[None, :], (B, M))
    visible = jnp.where(active, lengths + 1, 0)

    def write(arr, rows):
        return arr.at[at, jnp.arange(B), lengths].set(rows[:, 0].astype(arr.dtype))

    def block(**walk):
        return _decode_block(x, lp, k_arr, v_arr, write, slot_pos, positions, cfg,
                             read=lambda a: layer_slice(a, at), at=jnp.int32(at), **walk)

    assert not lane_walk_engages(k_arr, 1, cfg)      # off the TPU XLA's contractions stay
    want = block(visible=visible)                    # ... whatever the walk hands
    monkeypatch.setattr(lane_decode, "INTERPRET_OFF_TPU", True)
    assert lane_walk_engages(k_arr, 1, cfg)
    assert "pallas_call" in str(jax.make_jaxpr(lambda: block(visible=visible)[0])())
    assert "pallas_call" not in str(jax.make_jaxpr(lambda: block()[0])())   # a walk that hands no lengths
    got = block(visible=visible)
    for g, w, before in zip(got[1:3], want[1:3], (k_arr, v_arr)):           # the stacks: the same writes
        assert np.array_equal(np.asarray(g, np.float32), np.asarray(w, np.float32))
        untouched = np.ones((2, B, M), bool)
        untouched[at, np.arange(B), np.asarray(lengths)] = False
        assert np.array_equal(np.asarray(g, np.float32)[untouched], np.asarray(before, np.float32)[untouched])
    g, w = np.asarray(got[0], np.float32), np.asarray(want[0], np.float32)
    live = np.asarray(active)
    assert np.max(np.abs(g[live] - w[live])) < TOL * np.max(np.abs(w[live]))
    assert np.isfinite(g).all()
    # the kernel alone: an idle slot's rows are zeros, a live slot's are not
    q = jnp.asarray(rng.normal(size=(B, cfg.n_heads, HD)), BF16)
    a = _grouped_outputs(lane_decode.lane_decode(_grouped_queries(q, cfg), got[1], got[2], at, visible,
                                                 scale=HD ** -0.5, name="attn_decode"), cfg)
    a = np.asarray(a)
    assert a.shape == (B, cfg.n_heads * HD) and (a[~live] == 0).all() and (np.abs(a[live]).max(axis=1) > 0).all()


def test_the_walk_is_the_live_blocks_back_to_back_and_the_tail_names_the_last():
    """The tables the index maps read: slot 2's two blocks, slot 4's two, slot
    6's one, back to back; an idle slot owns no step; every entry past the five
    in use (the grid stops there) names the last of them again; an empty pool
    names slot 0's block 0 throughout and counts no block."""
    visible = jnp.asarray([0, 0, 513, 0, 1024, 0, 1, 0], jnp.int32)
    slot_of, block_of, blocks = (np.asarray(a).tolist() for a in lane_decode.walk_tables(visible, 16))
    assert blocks == 5
    assert slot_of == [2, 2, 4, 4, 6] + [6] * 11 and block_of == [0, 1, 0, 1, 0] + [0] * 11
    slot_of, block_of, blocks = (np.asarray(a).tolist() for a in lane_decode.walk_tables(jnp.asarray([1024, 1024]), 4))
    assert blocks == 4 and slot_of == [0, 0, 1, 1] and block_of == [0, 1, 0, 1]       # no tail at all
    slot_of, block_of, blocks = (np.asarray(a).tolist() for a in lane_decode.walk_tables(jnp.zeros((4,), jnp.int32), 8))
    assert blocks == 0 and slot_of == [0] * 8 and block_of == [0] * 8


# what each slot sees (0: idle) of a pool of ``lanes``; the walk is one sequence over all of them
WALKS = {
    "every-slot-live-unequal": (1536, (1, 1536, 700, 512, 1025, 90)),
    "idle-slots-first": (1024, (0, 0, 0, 600, 1024, 3)),
    "idle-slots-last": (1024, (1024, 5, 513, 0, 0, 0)),
    "idle-slots-interleaved": (1024, (0, 1000, 0, 0, 17, 0, 1024, 0)),
    "all-idle": (1024, (0, 0, 0, 0)),
    "one-live-slot-last": (1024, (0, 0, 0, 777)),
    "a-ring-one-block-a-slot": (512, (512, 1, 512, 300, 0, 512)),
    "slots-at-a-blocks-edge": (1536, (512, 1024, 1536, 511, 513, 1023)),
}


def _two_dimensional_walk(q, keys, values, layer, visible, *, scale):
    """The kernel as it was before the one walk (PR 44's): a program a slot,
    ``lanes // 512`` grid steps each, a step past the slot's length naming its
    last block again. The same arithmetic a block in the same order within a
    slot, so the one walk must give its bits; interpreted."""
    from jax import lax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from tpu_engine.ops.mla_decode import _fold, _reset

    LANES = lane_decode.LANES
    _, B, S, _ = keys.shape
    P, R, W = q.shape[1:]
    rows = -(-R // 16) * 16
    q = jnp.pad(q, ((0, 0), (0, 0), (0, rows - R), (0, 0)))
    visible = jnp.clip(visible.astype(jnp.int32), 0, S)

    def kernel(at_ref, n_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref):
        b, j = pl.program_id(0), pl.program_id(1)
        n = n_ref[b]

        @pl.when(j == 0)
        def _():
            _reset(m_ref, l_ref, acc_ref)

        @pl.when(j * LANES < n)
        def _():
            for i in range(P):
                cols = slice(i * W, (i + 1) * W)
                s = lax.dot_general(q_ref[0, i], k_ref[0, 0, :, cols], (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32) * scale
                lane = j * LANES + lax.broadcasted_iota(jnp.int32, s.shape, 1)
                _fold(jnp.where(lane < n, s, -1e30), v_ref[0, 0, :, cols], m_ref.at[i], l_ref.at[i], acc_ref.at[i])

        @pl.when(j == pl.num_programs(1) - 1)
        def _():
            o_ref[0] = jnp.where(n > 0, acc_ref[...] / l_ref[...], 0.0)

    def rows_map(b, j, at, n):
        return (at[0], b, jnp.minimum(j, jnp.maximum(n[b] - 1, 0) // LANES), 0)

    def own(b, j, *_):
        return (b, 0, 0, 0)

    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(B, S // LANES),
            in_specs=[pl.BlockSpec((1, P, rows, W), own), pl.BlockSpec((1, 1, LANES, P * W), rows_map),
                      pl.BlockSpec((1, 1, LANES, P * W), rows_map)],
            out_specs=pl.BlockSpec((1, P, rows, W), own),
            scratch_shapes=[pltpu.VMEM((P, rows, 1), F32), pltpu.VMEM((P, rows, 1), F32),
                            pltpu.VMEM((P, rows, W), F32)]),
        out_shape=jax.ShapeDtypeStruct((B, P, rows, W), F32), interpret=True,
    )(jnp.asarray(layer, jnp.int32).reshape(1), visible, q, keys, values)[:, :, :R]


def _contractions(q, keys, values, layer, visible, *, scale):
    """The plain statement: scores over every lane, masked past ``visible``, one softmax, per column group."""
    B, P, R, W = q.shape
    k, v = (a[layer].reshape(B, -1, P, W) for a in (keys, values))
    s = jnp.einsum("bprw,bmpw->bprm", q, k, preferred_element_type=F32) * scale
    mask = jnp.arange(k.shape[1])[None, :] < visible[:, None]
    p = jax.nn.softmax(jnp.where(mask[:, None, None, :], s, -1e30), axis=-1).astype(BF16)
    return jnp.einsum("bprm,bmpw->bprw", p, v, preferred_element_type=F32)


@pytest.mark.parametrize("heads", ["one-head-of-128", "two-heads-of-64"])
@pytest.mark.parametrize("walk", list(WALKS))
def test_the_one_walk_over_all_slots(monkeypatch, walk, heads):
    """The kernel alone over pools whose slots differ in every way the walk
    can: against XLA's contractions (to ``TOL``), an idle slot's rows exactly
    zeros, and BIT FOR BIT what the two-dimensional walk before it gave (the
    order of a slot's blocks is the same, and a slot never sees another's).
    Two heads of 64 lie side by side in a column group, each row zero outside
    its own head's columns (``_grouped_queries``)."""
    monkeypatch.setattr(lane_decode, "INTERPRET_OFF_TPU", True)
    lanes, visible = WALKS[walk]
    cfg = _cfg(128, 2) if heads == "one-head-of-128" else _cfg(64, 2, KV=4)
    B, width = len(visible), cfg.n_kv_heads * cfg.head_dim
    rng = np.random.default_rng([len(walk), lanes, width])
    keys, values = (jnp.asarray(rng.normal(size=(2, B, lanes, width)), BF16) for _ in range(2))
    q = _grouped_queries(jnp.asarray(rng.normal(size=(B, cfg.n_heads, cfg.head_dim)), BF16), cfg)
    visible = jnp.asarray(visible, jnp.int32)
    assert lane_decode.engages(keys)
    scale = cfg.head_dim ** -0.5
    got = np.asarray(lane_decode.lane_decode(q, keys, values, 1, visible, scale=scale, name="attn_decode"))
    live = np.asarray(visible) > 0
    assert got.shape == q.shape and np.isfinite(got).all() and (got[~live] == 0).all()
    before = np.asarray(_two_dimensional_walk(q, keys, values, 1, visible, scale=scale))
    assert np.array_equal(got, before)
    if live.any():
        want = np.asarray(_contractions(q, keys, values, 1, visible, scale=scale))
        own_head = np.asarray(q != 0)[live]                 # a row's own head's columns; the rest is not read
        assert np.max(np.abs(got[live] - want[live])[own_head]) < TOL * np.max(np.abs(want[live][own_head]))
        assert (np.abs(got[live]).max(axis=(1, 2, 3)) > 0).all()


# (b) the batcher end to end ---------------------------------------------------------------------


def _mistral_tiny():
    return tfm.ModelConfig(name="mistral-tiny", vocab_size=256, d_model=256, n_layers=2, n_heads=2, n_kv_heads=1,
                           d_ff=128, sliding_window=4096)


def _granite_micro_tiny():
    from families import granitemoehybrid as family

    with open(os.path.join(BENCH, "configs", "granite-4.0-h-micro-1chip-serve.json")) as f:
        cfg = json.load(f)
    cfg = {**cfg, **cfg["rehearsal"], "hidden_size": 256, "num_attention_heads": 4, "num_key_value_heads": 2,
           "mamba_n_heads": 8, "mamba_d_head": 64}
    return family.model_config(cfg, "granite-micro-tiny")


def _serve(mc, params, prompts, new_tokens, **kw):
    srv = ContinuousBatcher(params, mc, max_slots=3, max_len=M, compute_dtype=BF16, prefill_pad_to=16,
                            chunk_steps=2, prefill_chunk=64 if mc.is_hybrid else 256, **kw)
    rids = [srv.submit(p, max_new_tokens=n) for p, n in zip(prompts[:3], new_tokens[:3])]
    late, steps = None, 0
    while any(srv.result(r)["status"] != "done" for r in rids + ([late] if late is not None else [])):
        srv.step()
        steps += 1
        if late is None and srv.result(rids[1])["status"] == "done":   # a slot finished: another is admitted mid-run
            late = srv.submit(prompts[3], max_new_tokens=new_tokens[3])
        assert steps < 400
    assert late is not None
    return [srv.result(r)["tokens"] for r in rids + [late]], srv.stats()


@pytest.mark.parametrize("shape", ["mistral", "granite-micro"])
def test_the_batcher_serves_the_same_tokens_through_the_kernel(monkeypatch, shape):
    """Greedy requests through ``ContinuousBatcher``, the kernel interpreted
    against XLA's path: the same tokens for a Mistral-shaped stack (one kv-head
    of 128 a column group, a window that never binds) and a granite-micro-shaped
    one (Mamba-2 mixers beside attention, two kv-heads of 64 a group), with
    prompts on both sides of a block's edge, a slot finishing while two decode
    and another admitted into it mid-run. And the two counters: 0 off the
    kernel, with it the blocks each active slot's length covers, every step and
    layer, against slots x lanes."""
    mc = _mistral_tiny() if shape == "mistral" else _granite_micro_tiny()
    params = tfm.init_params(jax.random.PRNGKey(3), mc, dtype=F32)
    rng = np.random.default_rng(11)
    prompts = [rng.integers(1, mc.vocab_size, n).tolist() for n in (500, 9, 520, 30)]
    new_tokens = (20, 5, 12, 8)
    want, stats = _serve(mc, params, prompts, new_tokens)
    assert stats["decode_attn_lanes_read_total"] == 0 and stats["decode_attn_lanes_pool_total"] == 0
    monkeypatch.setattr(lane_decode, "INTERPRET_OFF_TPU", True)
    got, stats = _serve(mc, params, prompts, new_tokens)
    assert got == want and [len(t) for t in got] == list(new_tokens)
    layers = layer_state.layer_counts(mc)["attn"]
    steps = stats["decode_tokens_computed_total"]              # a step of one active slot
    read, pool = stats["decode_attn_lanes_read_total"], stats["decode_attn_lanes_pool_total"]
    assert pool % (layers * 3 * M) == 0 and pool > 0
    # every slot-step fetched one block or two, and the two long prompts crossed into their second
    assert layers * 512 * steps < read < layers * 1024 * steps
    assert read < pool


def test_the_counters_add_up_for_one_request(monkeypatch):
    """One request, prompt 5, 7 new tokens, 2 steps a dispatch, 2 layers: the
    first token comes from the prefill, three dispatches bring the other six;
    every step sees under 512 lanes, so reads one block a layer, of a pool of 3
    slots x 1 024 lanes."""
    monkeypatch.setattr(lane_decode, "INTERPRET_OFF_TPU", True)
    mc = _mistral_tiny()
    srv = ContinuousBatcher(tfm.init_params(jax.random.PRNGKey(0), mc, dtype=F32), mc, max_slots=3, max_len=M,
                            compute_dtype=BF16, prefill_pad_to=16, chunk_steps=2)
    rid = srv.submit([5, 4, 3, 2, 1], max_new_tokens=7)
    while srv.result(rid)["status"] != "done":
        srv.step()
    stats = srv.stats()
    assert stats["decode_attn_lanes_read_total"] == 3 * 2 * 2 * 512
    assert stats["decode_attn_lanes_pool_total"] == 3 * 2 * 2 * 3 * M
    # the one walk of a call: one block of the one live slot, and a grid of that one step (the leaf holds 3 x 2)
    assert stats["decode_attn_blocks_walked_total"] == 3 * 2 * 2 * 1
    assert stats["decode_attn_grid_steps_total"] == 3 * 2 * 2 * 1
    assert srv._attn_blocks_walked([]) == (0, 2 * 2)      # a call with no block still takes a step, which does nothing
    assert srv._attn_blocks_walked([512, 513]) == (2 * (1 + 2 + 2 + 2), 2 * (1 + 2 + 2 + 2))
    assert srv._attn_lanes_read([512]) == 2 * (512 + 1024) and srv._attn_lanes_read([M, 2 * M]) == 2 * 4 * M


def test_the_walks_counters_for_three_requests_are_a_hand_count(monkeypatch):
    """Three requests at once, 2 steps a dispatch, 2 layers, 3 slots x 1 024
    lanes. A request's first token comes from its prefill and the rest from
    whole dispatches, whoever decodes beside it, and a slot that does not decode
    owns no block, so the blocks walked are each request's own: prompt 5, 5
    tokens: 4 steps seeing 6..9 lanes, a block each; prompt 510, 5 tokens: 4
    steps seeing 511, 512, 513, 514 lanes: 1 + 1 + 2 + 2; prompt 600, 4 tokens:
    3 steps and the one that overshoots, two blocks each. A call's grid is its
    blocks (no call here is empty), and the blocks are PR 44's lanes read."""
    monkeypatch.setattr(lane_decode, "INTERPRET_OFF_TPU", True)
    mc = _mistral_tiny()
    srv = ContinuousBatcher(tfm.init_params(jax.random.PRNGKey(0), mc, dtype=F32), mc, max_slots=3, max_len=M,
                            compute_dtype=BF16, prefill_pad_to=16, chunk_steps=2, prefill_chunk=256)
    rng = np.random.default_rng(5)
    rids = [srv.submit(rng.integers(1, mc.vocab_size, n).tolist(), max_new_tokens=m)
            for n, m in ((5, 5), (510, 5), (600, 4))]
    for _ in range(50):
        srv.step()
        if all(srv.result(r)["status"] == "done" for r in rids):
            break
    stats = srv.stats()
    assert stats["decode_attn_blocks_walked_total"] == 2 * ((1 + 1 + 1 + 1) + (1 + 1 + 2 + 2) + (2 + 2 + 2 + 2))
    assert stats["decode_attn_grid_steps_total"] == stats["decode_attn_blocks_walked_total"]
    assert stats["decode_attn_blocks_walked_total"] * 512 == stats["decode_attn_lanes_read_total"]
    assert stats["decode_attn_lanes_read_total"] < stats["decode_attn_lanes_pool_total"]


# (c) what `engages` declines, and that each declined case is still the reference ----------------


def _decode_logits(mc, params, cache, active=None):
    B = cache.lengths.shape[0]
    toks = jnp.arange(1, B + 1, dtype=jnp.int32)
    active = jnp.ones((B,), bool) if active is None else active
    fn = lambda c: serving.decode_step(params, toks, c, active, mc, BF16)  # noqa: E731
    return str(jax.make_jaxpr(fn)(cache)), np.asarray(jax.jit(fn)(cache)[0])


def _filled(mc, cache, seed=0):
    """``cache`` with random keys and values and rows of different lengths."""
    rng = np.random.default_rng(seed)
    layers = jax.tree.map(lambda a: jnp.asarray(rng.normal(size=a.shape) * (20 if a.dtype == jnp.int8 else 1),
                                                a.dtype) if a.dtype != F32 else jnp.asarray(
                                                    rng.uniform(0.01, 0.02, size=a.shape), F32), cache.layers)
    lengths = jnp.asarray([3, 17, 100, 60][:cache.lengths.shape[0]], jnp.int32)
    pos = cache.pos
    if pos is not None:
        pos = jnp.where(jnp.arange(pos.shape[1])[None, :] < lengths[:, None], jnp.arange(pos.shape[1])[None, :], -1)
    return dataclasses.replace(cache, layers=layers, lengths=lengths, pos=pos)


@pytest.mark.parametrize("case", ["ring", "int8", "mesh", "part-block", "narrow-head", "window-binds"])
def test_a_declined_pool_keeps_the_xla_contractions(monkeypatch, case):
    """With the interpreter on, a ring pool, an int8 pool, a pool over a mesh,
    lanes that are not whole blocks, heads that do not fill a column group and a
    window shorter than the pool's lanes all keep XLA's path (no kernel in the
    step's jaxpr, no layer counted) and give the logits they give without it."""
    mc = _mistral_tiny()
    lanes, kw = 512, {}
    if case == "ring":
        mc = mc.with_(sliding_window=128)
    elif case == "int8":
        kw = {"kv_quant": True}
    elif case == "part-block":
        lanes = 500
    elif case == "narrow-head":
        mc = mc.with_(n_heads=8, n_kv_heads=8)      # heads of 32: eight would fill 256, not 128
        mc = mc.with_(n_heads=3, n_kv_heads=3, d_model=288)   # heads of 96
    elif case == "window-binds":
        mc = mc.with_(sliding_window=300)
    params = tfm.served_format(tfm.init_params(jax.random.PRNGKey(1), mc, dtype=F32), BF16)
    cache = init_slot_cache(mc, 4, lanes, BF16, prefill_chunk=lanes if case == "window-binds" else 64, **kw)
    if case == "mesh":
        cache = dataclasses.replace(cache, sharded=True)
    cache = _filled(mc, cache)
    assert cache.ring == (case == "ring")
    _, want = _decode_logits(mc, params, cache)
    monkeypatch.setattr(lane_decode, "INTERPRET_OFF_TPU", True)
    assert serving.lane_walk_layers(mc, cache) == 0
    text, got = _decode_logits(mc, params, cache)
    assert "pallas_call" not in text and np.array_equal(got, want)


def test_a_plain_pool_engages_and_a_verify_pass_does_not(monkeypatch):
    """The control of the cases above: the same pool with none of their
    properties takes the kernel (and agrees with XLA's path); a verify pass
    (T > 1) over it does not, nor does ``generate()``'s lockstep cache."""
    mc = _mistral_tiny()
    params = tfm.served_format(tfm.init_params(jax.random.PRNGKey(1), mc, dtype=F32), BF16)
    cache = _filled(mc, init_slot_cache(mc, 4, 512, BF16, prefill_chunk=64))
    active = jnp.asarray([True, False, True, True])
    _, want = _decode_logits(mc, params, cache, active)
    chain = jnp.arange(8, dtype=jnp.int32).reshape(4, 2)
    verify = lambda c: serving.decode_verify(params, chain, c, jnp.ones((4,), bool), mc, BF16)  # noqa: E731
    want_verify = np.asarray(jax.jit(verify)(cache)[0])
    monkeypatch.setattr(lane_decode, "INTERPRET_OFF_TPU", True)
    assert serving.lane_walk_layers(mc, cache) == 2
    assert not lane_walk_engages(cache.layers["attn"]["k"], 2, mc)
    text, got = _decode_logits(mc, params, cache, active)
    live = np.asarray(active)
    assert "pallas_call" in text and np.max(np.abs(got[live] - want[live])) < TOL * np.max(np.abs(want[live]))
    assert np.isfinite(got).all()
    assert "pallas_call" not in str(jax.make_jaxpr(verify)(cache))
    assert np.array_equal(np.asarray(jax.jit(verify)(cache)[0]), want_verify)
    c1 = init_cache(mc, 2, 512, dtype=BF16)
    step = lambda c: forward_with_cache(params, jnp.ones((2, 1), jnp.int32), c, mc, BF16)  # noqa: E731
    assert "pallas_call" not in str(jax.make_jaxpr(step)(c1))


# (d) the leaf's format through the cache manager -------------------------------------------------


def test_the_attn_leaf_keeps_its_kv_heads_side_by_side():
    """``[L, rows, lanes, KV x HD]`` through allocation, insert, reset, a
    prefix's slice and paste, the shardings over two model devices (whole
    kv-heads a shard; an odd head count replicates) and the estimator, whose
    bytes are what the pool's leaves hold."""
    from jax.sharding import PartitionSpec as P

    from tpu_engine.mesh_runtime import MeshConfig, build_mesh

    mc = _cfg(64, 2, KV=2)                                     # KV x HD = 128
    pool = init_slot_cache(mc, 4, 64, BF16)
    assert {n: a.shape for n, a in pool.layers["attn"].items()} == {"k": (2, 4, 64, 128), "v": (2, 4, 64, 128)}
    quant = init_slot_cache(mc, 4, 64, BF16, kv_quant=True)     # int8 keeps codes by kv-head beside their scales
    assert quant.layers["attn"]["k"].shape == (2, 4, 64, 2, 64) and quant.layers["attn"]["k_scale"].shape == (2, 4, 64, 2, 1)
    rng = np.random.default_rng(2)
    c1 = init_cache(mc, 1, 32, dtype=F32)
    c1 = dataclasses.replace(c1, layers=jax.tree.map(lambda a: jnp.asarray(rng.normal(size=a.shape), a.dtype), c1.layers))
    got = serving._insert_prefill(pool, c1, jnp.int32(2), jnp.int32(9), False)
    assert np.array_equal(np.asarray(got.layers["attn"]["k"][:, 2, :32], np.float32),
                          np.asarray(c1.layers["attn"]["k"][:, 0].astype(BF16), np.float32))
    assert not np.asarray(got.layers["attn"]["k"][:, [0, 1, 3]], np.float32).any() and got.lengths.tolist() == [0, 0, 9, 0]
    freed = serving._reset_slot(got, jnp.int32(2))              # a positional kind needs nothing but its length
    assert freed.lengths.tolist() == [0] * 4
    assert np.array_equal(np.asarray(freed.layers["attn"]["v"], np.float32), np.asarray(got.layers["attn"]["v"], np.float32))
    entry = serving._slice_prefix(c1, 16)
    assert entry.layers["attn"]["k"].shape == (2, 1, 16, 128)
    pasted = serving._paste_prefix(init_cache(mc, 1, 32, dtype=F32), entry, jnp.int32(12), 16)
    assert np.array_equal(np.asarray(pasted.layers["attn"]["k"][:, :, :16]), np.asarray(c1.layers["attn"]["k"][:, :, :16]))
    assert not np.asarray(pasted.layers["attn"]["k"][:, :, 16:]).any() and int(pasted.length) == 12
    mesh = build_mesh(MeshConfig(fsdp=4, model=2))
    sh = layer_state.cache_shardings(mesh, mc, pool)
    assert sh.layers["attn"]["k"].spec == P(None, None, None, "model")
    placed = jax.device_put(pool, sh)
    assert placed.layers["attn"]["k"].addressable_shards[0].data.shape == (2, 4, 64, 64)   # one whole kv-head
    odd = _cfg(64, 2, KV=3)
    assert layer_state.cache_shardings(mesh, odd, init_slot_cache(odd, 4, 64, BF16)).layers["attn"]["k"].is_fully_replicated
    assert layer_state.cache_shardings(mesh, mc, quant).layers["attn"]["k"].spec == P(None, None, None, "model", None)
    leaves = sum(a.nbytes for a in pool.layers["attn"].values())
    assert layer_state.state_bytes(mc, 4, 64, BF16) == {"attn": leaves}
    assert layer_state.state_bytes(mc, 4, 64, BF16, tp=2) == {"attn": leaves / 2}
    assert layer_state.state_bytes(odd, 4, 64, BF16, tp=2) == {"attn": leaves * 3 / 2}     # replicated
    # the estimator prices the same table: Mistral-7B's 32 layers of 16 slots x 2 048 lanes x 8 heads of 128, k and v
    assert estimate_serving_hbm("mistral-7b", 16, 2048).kv_pool_gib == 2 * 32 * 16 * 2048 * 8 * 128 * 2 / 2 ** 30 == 4.0
    assert estimate_serving_hbm("mistral-7b", 16, 2048, tensor_parallel=2).kv_pool_gib == 2.0
    assert estimate_serving_hbm("mistral-7b", 16, 2048, tensor_parallel=3).kv_pool_gib == 4.0   # 8 heads !% 3
