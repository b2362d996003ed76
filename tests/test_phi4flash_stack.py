"""A decoder-hybrid-decoder stack (Phi-4-mini-flash-reasoning's recipe) against
its plain float32 reference (``benchmarks/onchip/reference/phi4flash.py``: a
sequential scan, whole score matrices, every layer at every position, no cache):
Mamba-1 mixers beside differential attention under a window, ONE full-attention
cache that the cross-attention layers read and do not own, gated memory units
that read one Mamba layer's scan output, a ring of one window beside full lanes
in one pool, and a prefill that runs the cross-decoder at a prompt's last
position only.

Tiny widths (the configuration's rehearsal size: 12 layers = 3 x (mamba1,
window) + mamba1 + full + 2 x (gmu, cross), a window of 16, state 4), seeded
weights, on the CPU. Logits have a spread of 0.17. In float32 both sides differ
in the order of their sums only: 40 decoded positions measured 3.3e-7, and
``TOL`` = 5e-6 leaves fifteen times that; in bfloat16 (weights, activations,
keys, values and the carried memory rounded; states float32) the same positions
measured 0.0058, ``TOL_BF16`` = 0.04. The controls must miss ``TOL`` by far: a
window one position too short or too long measured 0.29 both ways.
"""

import dataclasses
import json
import os
import sys
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks", "onchip")
for p in (ROOT, BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)

from families import phi4flash as family  # noqa: E402
from reference import phi4flash as ref  # noqa: E402

from tpu_engine import layer_state, serving  # noqa: E402
from tpu_engine.generate import forward_with_cache, init_cache  # noqa: E402
from tpu_engine.models import transformer as tfm  # noqa: E402

generate = sys.modules["tpu_engine.generate"]  # the package's ``generate`` is the function

TOL, TOL_BF16 = 5e-6, 0.04
SEED = 5
# Prefill chunks of 24 are longer than the window (16); prompts pad to 8.
CHUNK, PAD = 24, 8
LANES = 160
F32, BF16 = jnp.float32, jnp.bfloat16
WINDOW = 16


@pytest.fixture(scope="module")
def tiny():
    """(configuration dict at its rehearsal size, ModelConfig, program params,
    reference params): one seed drawn twice, by the program and by the
    reference, each by its own code."""
    with open(os.path.join(BENCH, "configs", "phi-4-mini-flash-1chip-serve.json")) as f:
        cfg = json.load(f)
    cfg = {**cfg, **cfg["rehearsal"]}
    assert cfg["sliding_window"] == WINDOW
    mc = family.model_config(cfg, "phi4flash-tiny")
    return cfg, mc, tfm.init_params(jax.random.PRNGKey(SEED), mc), ref.init_params(cfg, SEED)


def _tokens(n, stream=0):
    return np.random.default_rng([SEED, stream]).integers(0, 512, n).astype(np.int32)


def _reference(tiny, tokens):
    cfg, _, _, rparams = tiny
    to = 16 if len(tokens) <= ref.Q_BLOCK else ref.Q_BLOCK
    padded = np.zeros(-(-len(tokens) // to) * to, np.int32)
    padded[:len(tokens)] = tokens
    return np.asarray(ref.forward_logits(rparams, padded, cfg))


def _prefill(params, mc, prompt, dtype=F32, lanes=LANES, split=True):
    """The batcher's ingestion: the prompt zero-padded to PAD, one CHUNK a call,
    every chunk but the last through ``serving._prefill_ingest`` (``split``;
    else every chunk through the program that walks the cross-decoder too).
    Returns (logits row of the last real token, the single-row cache)."""
    n = len(prompt)
    padded = -(-n // PAD) * PAD
    toks = np.zeros((1, padded), np.int32)
    toks[0, :n] = prompt
    c1 = init_cache(mc, 1, lanes, dtype=dtype)
    last_fn = jax.jit(partial(serving._prefill_forward, cfg=mc, compute_dtype=dtype))
    ingest = jax.jit(partial(serving._prefill_ingest, cfg=mc, compute_dtype=dtype))
    last = None
    for t0 in range(0, padded, CHUNK):
        t1 = min(t0 + CHUNK, padded)
        row = min(max(n - 1 - t0, 0), t1 - t0 - 1)
        n_valid = jnp.int32(min(max(n - t0, 0), t1 - t0))
        chunk = jnp.asarray(toks[:, t0:t1])
        if t0 <= n - 1 < t1 or not split:
            out, c1 = last_fn(params, chunk, c1, jnp.int32(row), n_valid)
            if t0 <= n - 1 < t1:
                last = out
        else:
            c1 = ingest(params, chunk, c1, n_valid)
    return last, c1


def _pool(mc, slots=3, lanes=LANES, dtype=F32):
    return serving.init_slot_cache(mc, slots, lanes, dtype, prefill_chunk=CHUNK)


def _insert(pool, c1, slot, n):
    return serving._insert_prefill(pool, c1, jnp.int32(slot), jnp.int32(n), False)


def _decode_logits(params, mc, pool, slot, feed, active=None, dtype=F32):
    B = pool.lengths.shape[0]
    act = np.zeros(B, bool) if active is None else np.array(active)
    act[slot] = True
    step = jax.jit(partial(serving.decode_step, cfg=mc, compute_dtype=dtype))
    out = []
    for tok in feed:
        toks = np.zeros(B, np.int32)
        toks[slot] = tok
        lg, pool = step(params, jnp.asarray(toks), pool, jnp.asarray(act))
        out.append(lg[slot])
    return jnp.stack(out), pool


def _with_stack_leaf(params, kind, path, fn):
    """``params`` with ``fn`` applied to ``params["layers"][kind][path...]``."""
    stack = dict(params["layers"][kind])
    if len(path) == 1:
        stack[path[0]] = fn(stack[path[0]])
    else:
        stack[path[0]] = {**stack[path[0]], path[1]: fn(stack[path[0]][path[1]])}
    return {**params, "layers": {**params["layers"], kind: stack}}


# (a) chunked prefill, insert, decode against the reference's whole forward -----


def test_forward_with_cache_over_a_whole_prompt_equals_the_reference(tiny):
    cfg, mc, params, _ = tiny
    toks = _tokens(150)  # past one query block of the reference, nine windows
    logits, cache = forward_with_cache(params, jnp.asarray(toks)[None], init_cache(mc, 1, LANES, dtype=F32),
                                       mc, compute_dtype=F32)
    assert np.abs(np.asarray(logits[0]) - _reference(tiny, toks)[:150]).max() < TOL
    assert int(cache.length) == 150
    # window and full kinds alike hold whole lanes in a staged cache; the kinds that read own nothing
    assert cache.layers["window_attn"]["k"].shape == (3, 1, LANES, 32)
    assert cache.layers["full_attn"]["k"].shape == (1, 1, LANES, 32)
    assert cache.layers["cross_attn"] == {} and cache.layers["gmu"] == {}


# prompts past the window and past one chunk; a prompt shorter than the window; one that ends a chunk
@pytest.mark.parametrize("n_prompt, dtype", [(45, "float32"), (100, "float32"), (10, "float32"), (48, "float32"),
                                             (100, "bfloat16")])
def test_chunked_prefill_insert_and_40_decode_steps_equal_the_reference(tiny, n_prompt, dtype):
    cfg, mc, params, _ = tiny
    dtype, tol = (F32, TOL) if dtype == "float32" else (BF16, TOL_BF16)
    params = tfm.served_format(params, dtype)
    toks = _tokens(n_prompt + 41, 1)
    last, c1 = _prefill(params, mc, toks[:n_prompt], dtype)
    pool = _insert(_pool(mc, dtype=dtype), c1, 1, n_prompt)
    logits, pool = _decode_logits(params, mc, pool, 1, toks[n_prompt:n_prompt + 40], dtype=dtype)
    want = _reference(tiny, toks)
    assert np.abs(np.asarray(last, np.float32) - want[n_prompt - 1]).max() < tol          # prefill's own row
    assert np.abs(np.asarray(logits, np.float32) - want[n_prompt:n_prompt + 40]).max() < tol   # through ring, shared cache, state
    assert int(pool.lengths[1]) == n_prompt + 40 and int(pool.lengths[0]) == 0
    # two lane counts in one pool: a ring of one window beside the full kind's lanes
    assert pool.layers["window_attn"]["k"].shape == (3, 3, WINDOW, 32)
    assert pool.layers["full_attn"]["k"].shape == (1, 3, LANES, 32)
    assert pool.layers["mamba1"]["state"].shape == (4, 3, 4, 128) and pool.layers["mamba1"]["state"].dtype == F32


def test_40_steps_through_decode_chunk_leave_the_state_the_reference_implies(tiny):
    cfg, mc, params, _ = tiny
    prompt = _tokens(52, 2)
    last, c1 = _prefill(params, mc, prompt)
    pool = _insert(_pool(mc), c1, 2, len(prompt))
    first = int(jnp.argmax(last))
    chunk = jax.jit(partial(serving.decode_chunk, cfg=mc, n_steps=8, compute_dtype=F32))
    active = jnp.asarray([False, False, True])
    zeros = jnp.zeros(3, jnp.int32)
    generated, tok = [first], first
    for _ in range(5):
        out, pool = chunk(params, jnp.asarray([0, 0, tok], jnp.int32), pool, active,
                          jnp.zeros(3, F32), zeros, zeros, jax.random.PRNGKey(0))
        generated += np.asarray(out[2]).tolist()
        tok = generated[-1]
    seq = np.concatenate([prompt, np.asarray(generated, np.int32)])
    logits, _ = _decode_logits(params, mc, pool, 2, [tok])
    want = _reference(tiny, seq)
    assert np.abs(np.asarray(logits[0]) - want[len(prompt) + 40]).max() < TOL
    rows = want[len(prompt) - 1:len(prompt) + 40]
    assert (rows[np.arange(41), generated] >= rows.max(-1) - TOL).all()


# (b) the ring ------------------------------------------------------------------


def test_a_ring_of_one_window_equals_full_lanes_masked_after_it_wraps(tiny):
    """The pool's window layers keep 16 lanes; the same layers at full lanes,
    masked by the window, give the same logits over 40 steps (the ring wraps
    twice and more), and a lane holds the position ``ring_positions`` says."""
    cfg, mc, params, _ = tiny
    toks = _tokens(100, 6)
    _, c1 = _prefill(params, mc, toks[:60])
    ring = _insert(_pool(mc), c1, 0, 60)
    flat = dataclasses.replace(ring, layers=layer_state.init_layers(mc, 3, LANES, F32))
    flat = _insert(flat, c1, 0, 60)
    assert flat.layers["window_attn"]["k"].shape[2] == LANES and ring.layers["window_attn"]["k"].shape[2] == WINDOW
    got, ring = _decode_logits(params, mc, ring, 0, toks[60:100])
    want, flat = _decode_logits(params, mc, flat, 0, toks[60:100])
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < TOL
    held = np.asarray(layer_state.ring_positions(WINDOW, jnp.asarray([100])))[0]
    assert sorted(held.tolist()) == list(range(84, 100)) and all(p % WINDOW == m for m, p in enumerate(held))
    for m, p in enumerate(held):  # the ring's lane m is the flat cache's lane p
        assert np.abs(np.asarray(ring.layers["window_attn"]["k"][:, 0, m])
                      - np.asarray(flat.layers["window_attn"]["k"][:, 0, p])).max() < TOL
    assert np.asarray(layer_state.ring_positions(WINDOW, jnp.asarray([5]))).tolist() == [[0, 1, 2, 3, 4] + [-1] * 11]


@pytest.mark.parametrize("window_seen, at_least", [(WINDOW - 1, 0.05), (WINDOW + 1, 0.05)])
def test_control_a_window_off_by_one_moves_the_logits(tiny, window_seen, at_least):
    cfg, mc, params, _ = tiny
    toks = _tokens(90, 7)
    wrong = mc.with_(sliding_window=window_seen)
    _, c1 = _prefill(params, wrong, toks[:50])
    flat = serving.SlotCache(layers=layer_state.init_layers(mc, 3, LANES, F32), lengths=jnp.zeros((3,), jnp.int32))
    logits, _ = _decode_logits(params, wrong, _insert(flat, c1, 1, 50), 1, toks[50:90])
    gap = np.abs(np.asarray(logits) - _reference(tiny, toks)[50:90]).max()
    assert gap > at_least > 10 * TOL, gap


# (c) the kinds that own nothing ---------------------------------------------------


def test_cross_layers_read_the_one_full_cache_and_own_none(tiny):
    cfg, mc, params, _ = tiny
    pool = _pool(mc)
    assert pool.layers["cross_attn"] == {} and pool.layers["gmu"] == {}
    priced = layer_state.state_bytes(mc, 3, LANES, F32, ring_lanes=WINDOW)
    assert priced["cross_attn"] == 0 and priced["gmu"] == 0
    assert priced["full_attn"] == 1 * 3 * LANES * 32 * 2 * 4          # ONE layer's keys and values, once
    assert priced["window_attn"] == 3 * 3 * WINDOW * 32 * 2 * 4
    assert sum(priced.values()) == sum(a.nbytes for a in jax.tree.leaves(pool.layers))
    assert layer_state.split_bytes(priced)[1] == pool.recurrent_state_bytes == priced["mamba1"]
    # the cross layers' logits follow the full layer's leaves: spoil one lane they can see
    toks = _tokens(70, 8)
    _, c1 = _prefill(params, mc, toks[:50])
    pool = _insert(pool, c1, 0, 50)
    spoiled = dataclasses.replace(pool, layers={**pool.layers, "full_attn": {
        **pool.layers["full_attn"], "v": pool.layers["full_attn"]["v"].at[0, 0, 3].add(1.0)}})
    got, _ = _decode_logits(params, mc, spoiled, 0, toks[50:52])
    assert np.abs(np.asarray(got) - _reference(tiny, toks)[50:52]).max() > 1e-3


# (d) the memory is the scan output before the gate -------------------------------


def test_the_gated_memory_units_read_the_last_mamba_layers_scan_output_before_its_gate(tiny, monkeypatch):
    cfg, mc, params, _ = tiny
    I = mc.mamba1_inner
    seen = []
    orig = generate._gmu_block

    def recording(x, lp, mem, valid, cfg, tally=None):
        jax.debug.callback(lambda m: seen.append(np.asarray(m)), mem, ordered=True)
        return orig(x, lp, mem, valid, cfg, tally)

    monkeypatch.setattr(generate, "_gmu_block", recording)
    toks = jnp.asarray(_tokens(40, 9))[None]

    def run(p):
        seen.clear()
        logits, _ = forward_with_cache(p, toks, init_cache(mc, 1, 48, dtype=F32), mc, compute_dtype=F32)
        jax.effects_barrier()
        return np.asarray(logits), [m.copy() for m in seen]

    base_logits, base_mem = run(params)
    assert len(base_mem) == 2 and all(m.shape == (1, 40, I) for m in base_mem)
    assert (base_mem[0] == base_mem[1]).all()
    # the z path (the gate) of the LAST mamba1 layer: the memory does not move, the logits do
    gate_path = lambda w: w.at[-1, :, I:].multiply(1.5)  # noqa: E731
    logits, mem = run(_with_stack_leaf(params, "mamba1", ("in_proj", "kernel"), gate_path))
    assert (mem[0] == base_mem[0]).all() and np.abs(logits - base_logits).max() > 1e-4
    # its x path moves the memory; an EARLIER mamba1 layer's gate moves it too (through x)
    logits, mem = run(_with_stack_leaf(params, "mamba1", ("in_proj", "kernel"), lambda w: w.at[-1, :, :I].multiply(1.5)))
    assert np.abs(mem[0] - base_mem[0]).max() > 1e-4
    # control: the reference with m taken AFTER the gate is another model
    want = _reference(tiny, np.asarray(toks[0]))
    assert np.abs(base_logits[0] - want[:40]).max() < TOL


# (e) the two prefill programs -------------------------------------------------------


def test_last_position_only_prefill_equals_the_full_walk(tiny):
    cfg, mc, params, _ = tiny
    toks = _tokens(72, 10)
    full_last, full_c1 = _prefill(params, mc, toks[:70], split=False)
    last, c1 = _prefill(params, mc, toks[:70])
    assert np.abs(np.asarray(last) - np.asarray(full_last)).max() < TOL
    for a, b in zip(jax.tree.leaves(c1.layers), jax.tree.leaves(full_c1.layers)):
        assert np.abs(np.asarray(a) - np.asarray(b)).max() < TOL
    # one call: logits at a row of the chunk, the cross-decoder at that row alone
    cache = init_cache(mc, 1, 80, dtype=F32)
    every, _ = forward_with_cache(params, jnp.asarray(toks)[None], cache, mc, compute_dtype=F32)
    one, _ = forward_with_cache(params, jnp.asarray(toks)[None], cache, mc, compute_dtype=F32,
                                logits_row=jnp.int32(37))
    assert one.shape == (1, 1, 512) and np.abs(np.asarray(one[0, 0]) - np.asarray(every[0, 37])).max() < TOL
    none, ingested = forward_with_cache(params, jnp.asarray(toks)[None], cache, mc, compute_dtype=F32, ingest_only=True)
    assert none is None and int(ingested.length) == 72
    with pytest.raises(ValueError, match="no cross-decoder"):
        plain = tfm.MODEL_CONFIGS["gpt-tiny"]
        forward_with_cache(tfm.init_params(jax.random.PRNGKey(0), plain), jnp.zeros((1, 4), jnp.int32),
                           init_cache(plain, 1, 8, dtype=F32), plain, compute_dtype=F32, ingest_only=True)


# (f) the chunk scan is the step repeated ----------------------------------------------


def test_the_chunk_scan_equals_the_step_by_step_update():
    rng = np.random.default_rng(3)
    B, T, I, N = 2, 37, 24, 4
    x, Bm, Cm = (jnp.asarray(rng.normal(size=s), F32) for s in ((B, T, I), (B, T, N), (B, T, N)))
    dt = jnp.asarray(rng.uniform(0.001, 0.5, (B, T, I)), F32).at[1, 30:].set(0.0)   # row 1 ends at 30
    A = -jnp.asarray(rng.uniform(1, 16, (N, I)), F32)
    h0 = jnp.asarray(rng.normal(size=(B, N, I)), F32)
    y, h = generate._mamba1_scan(x, dt, A, Bm, Cm, h0)
    hs, ys = h0, []
    for t in range(T):
        yt, hs = generate._mamba1_step(x[:, t], dt[:, t], Bm[:, t], Cm[:, t], A, hs)
        ys.append(yt)
    assert np.abs(np.asarray(y) - np.asarray(jnp.stack(ys, 1))).max() < 1e-5
    assert np.abs(np.asarray(h) - np.asarray(hs)).max() < 1e-5
    _, h30 = generate._mamba1_scan(x[:, :30], dt[:, :30], A, Bm[:, :30], Cm[:, :30], h0)
    assert np.abs(np.asarray(h[1]) - np.asarray(h30[1])).max() < 1e-6   # dt = 0: what followed left the state alone
    _, kept = generate._mamba1_step(x[:, 0], jnp.zeros_like(dt[:, 0]), Bm[:, 0], Cm[:, 0], A, h0)
    assert (np.asarray(kept) == np.asarray(h0)).all()                   # exactly


# (g) through the batcher ----------------------------------------------------------------


def test_the_batcher_serves_it_insert_reset_inactive_rows_and_reused_slots(tiny):
    """Five requests through two slots: the second slot's first request is the
    longest, so a shorter one reuses its slot; prompts past the window and past
    a chunk; a one-chunk prompt. Every served token is the reference's own best."""
    cfg, mc, params, _ = tiny
    engine = serving.ContinuousBatcher(params, mc, max_slots=2, max_len=LANES, compute_dtype=F32,
                                       prefill_pad_to=PAD, prefill_chunk=CHUNK, chunk_steps=4)
    shapes = [(30, 12), (100, 30), (20, 9), (60, 10), (49, 14)]
    prompts = [_tokens(n, 20 + i).tolist() for i, (n, _) in enumerate(shapes)]
    rids = [engine.submit(p, max_new_tokens=m) for p, (_, m) in zip(prompts, shapes)]
    for _ in range(400):
        engine.step()
        if all(engine.result(r)["status"] == "done" for r in rids):
            break
    for rid, prompt, (n, m) in zip(rids, prompts, shapes):
        out = engine.result(rid)
        assert out["status"] == "done" and len(out["tokens"]) == m
        rows = _reference(tiny, np.asarray(prompt + out["tokens"], np.int32))[n - 1:n - 1 + m]
        assert (rows[np.arange(m), out["tokens"]] >= rows.max(-1) - TOL).all(), rid
    st = engine.stats()
    chunks = sum(-(-(-(-n // PAD) * PAD) // CHUNK) for n, _ in shapes)
    assert st["prefill_tokens_computed_total"] == sum(-(-n // PAD) * PAD for n, _ in shapes)
    assert st["prefill_positions_cross_decoder_total"] == len(shapes) < chunks
    assert st["state_inserts_total"] == 5 and st["state_resets_total"] == 5
    assert st["shared_kv_bytes"] == 1 * 2 * LANES * 32 * 2 * 4 and st["window_kv_bytes"] == 3 * 2 * WINDOW * 32 * 2 * 4
    assert st["recurrent_state_bytes"] == 4 * 2 * (4 * 128 * 4 + 3 * 128 * 4) > 0
    assert float(jnp.abs(engine._cache.layers["mamba1"]["state"]).max()) == 0.0 and int(engine._cache.lengths.max()) == 0


# (h) the estimate ---------------------------------------------------------------------------


def test_the_estimate_prices_lanes_per_kind(tiny):
    from tpu_engine import hbm_estimate
    from tpu_engine.sharding import Precision

    cfg, mc, _, _ = tiny
    tfm.MODEL_CONFIGS[mc.name] = mc
    try:
        est = hbm_estimate.estimate_serving_hbm(mc.name, max_slots=4, max_len=256, prefill_chunk=32,
                                                compute_dtype=Precision.BF16)
    finally:
        del tfm.MODEL_CONFIGS[mc.name]
    pool = serving.init_slot_cache(mc, 4, 256, BF16, prefill_chunk=32)
    priced = layer_state.state_bytes(mc, 4, 256, BF16, ring_lanes=WINDOW)
    positional, whole = layer_state.split_bytes(priced)
    assert positional + whole == sum(a.nbytes for a in jax.tree.leaves(pool.layers))
    assert est.kv_pool_gib == round(positional / 2**30, 4) and any("window rings: 3 window layers" in n for n in est.notes)
    assert tfm.param_count(mc) == sum(a.size for a in jax.tree.leaves(tfm.init_params(jax.random.PRNGKey(0), mc)))


def test_the_published_size_is_3852_6_million_parameters_and_fits_one_chip():
    with open(os.path.join(BENCH, "configs", "phi-4-mini-flash-1chip-serve.json")) as f:
        cfg = json.load(f)
    mc = family.model_config(cfg, "phi-4-mini-flash-1chip-serve")
    assert round(tfm.param_count(mc) / 1e6, 1) == 3852.6
    assert mc.layer_periods() == ((("mamba1", "window_attn"), (0, 0), 8), (("mamba1",), (8,), 1),
                                  (("full_attn",), (0,), 1), (("gmu", "cross_attn"), (0, 0), 7))
    assert mc.cross_decoder_start == 17
    p = cfg["program"]
    by_kind = layer_state.state_bytes(mc, p["max_slots"], p["max_len"], BF16, ring_lanes=mc.sliding_window)
    assert by_kind["full_attn"] == 32 * 12288 * 5120 and by_kind["window_attn"] == 8 * 32 * 512 * 5120
    assert by_kind["mamba1"] == 9 * 32 * (16 * 5120 * 4 + 3 * 5120 * 2)
    assert 10.4e9 < 2 * tfm.param_count(mc) + sum(by_kind.values()) < 10.6e9


# (i) what is refused, by name ---------------------------------------------------------------------


def test_what_assumes_keys_and_values_alone_is_refused_by_name(tiny):
    from tpu_engine import disagg

    cfg, mc, params, _ = tiny
    refused = tfm.RecurrentLayersUnsupported
    with pytest.raises(refused, match="4 of its 12 layers are recurrent .mamba1."):
        serving.ContinuousBatcher(params, mc, max_slots=2, max_len=64, prefix_cache_tokens=64)
    with pytest.raises(refused, match="kv_quant"):
        serving.ContinuousBatcher(params, mc, max_slots=2, max_len=64, kv_quant=True)
    with pytest.raises(refused, match="mesh-sharded serving"):
        serving.ContinuousBatcher(params, mc, max_slots=2, max_len=64, mesh=jax.sharding.Mesh(np.array(jax.devices()[:1]), ("model",)))
    with pytest.raises(refused, match="speculative serving"):
        serving.ContinuousBatcher(params, mc, max_slots=2, max_len=64, draft_params=params, draft_cfg=mc)
    with pytest.raises(refused, match="speculative decoding"):
        generate.speculative_generate(params, params, jnp.zeros((1, 4), jnp.int32), mc, mc, 4)
    with pytest.raises(refused, match="KV handoff wire"):
        disagg.extract_slot_kv(_pool(mc), 0, 4, cfg=mc, prompt=[1], emitted=[2])
    with pytest.raises(refused, match="training"):
        tfm.refuse_beyond_kv(mc, "training (build_train_program)")
    with pytest.raises(NotImplementedError, match="window_attn layers' lanes are a ring"):
        layer_state.slice_lanes(_pool(mc).layers, 8)
    # what a pattern cannot be
    for bad, why in ((dict(layer_types=("gmu",) + mc.layer_types[1:]), "reads the scan output of a 'mamba1' layer"),
                     (dict(sliding_window=0), "a sliding window exactly where"),
                     (dict(n_heads=6, n_kv_heads=4), "pairs its heads"),
                     (dict(layer_types=tuple(t for t in mc.layer_types if t != "diff_attention") + ("mamba1",)),
                      "reads the keys and values of a 'diff_attention' layer")):
        with pytest.raises(ValueError, match=why):
            tfm.check_hybrid(mc.with_(**bad))


def test_training_refuses_the_stack_where_it_builds_its_program(tiny):
    from tpu_engine import train as train_mod

    _, mc, _, _ = tiny
    tfm.MODEL_CONFIGS[mc.name] = mc
    try:
        with pytest.raises(tfm.RecurrentLayersUnsupported, match="training"):
            train_mod.build_train_program(train_mod.TPUTrainConfig(model_name=mc.name, seq_len=32))
    finally:
        del tfm.MODEL_CONFIGS[mc.name]


# (j) the reference's two blocks against published code ---------------------------------------------


def _torch():
    return pytest.importorskip("torch"), pytest.importorskip("transformers")


def test_the_references_mamba_mixer_equals_transformers_mamba_mixer(tiny):
    torch, _ = _torch()
    from transformers.models.mamba.configuration_mamba import MambaConfig
    from transformers.models.mamba.modeling_mamba import MambaMixer

    cfg = tiny[0]
    d = ref._dims(cfg)
    w = jax.jit(lambda s, i: ref.mixer_weights(cfg, s, "mamba", i))(jnp.uint32(SEED), jnp.int32(1))
    h = jnp.asarray(np.random.default_rng(1).normal(size=(40, d["D"])), F32)
    with jax.default_matmul_precision("highest"):
        want, _ = ref.mamba_mixer(h, w, cfg)
    mixer = MambaMixer(MambaConfig(hidden_size=d["D"], state_size=d["N"], conv_kernel=d["K"], expand=2,
                                   time_step_rank=d["R"], use_conv_bias=True, use_bias=False, hidden_act="silu"),
                       layer_idx=0).eval()
    t = lambda a: torch.tensor(np.asarray(a, np.float32))  # noqa: E731
    with torch.no_grad():
        mixer.in_proj.weight.copy_(t(w["in_proj"]).T)
        mixer.conv1d.weight.copy_(t(w["conv"]).T[:, None, :])
        mixer.conv1d.bias.copy_(t(w["conv_bias"]))
        mixer.x_proj.weight.copy_(t(w["x_proj"]).T)
        mixer.dt_proj.weight.copy_(t(w["dt_proj"]).T)
        mixer.dt_proj.bias.copy_(t(w["dt_bias"]))
        mixer.A_log.copy_(t(w["A_log"]))
        mixer.D.copy_(t(w["D"]))
        mixer.out_proj.weight.copy_(t(w["out_proj"]).T)
        got = mixer.slow_forward(t(h)[None])[0].numpy()
    assert np.abs(got - np.asarray(want)).max() < 1e-6


@pytest.mark.parametrize("window", [0, WINDOW])
def test_the_references_differential_attention_equals_diffllama_attention(tiny, window):
    """``DiffLlamaAttention`` (eager, identity rotary) pairs head j with head
    j + H/2 and kv-head a with a + KV/2; the recipe pairs neighbours (2j, 2j+1):
    the same function with the heads permuted."""
    torch, _ = _torch()
    from transformers.models.diffllama.configuration_diffllama import DiffLlamaConfig
    from transformers.models.diffllama.modeling_diffllama import DiffLlamaAttention

    cfg = tiny[0]
    d = ref._dims(cfg)
    D, H, KV, HD, S, layer = d["D"], d["H"], d["KV"], d["HD"], 40, 5
    w = jax.jit(lambda s, i: ref.mixer_weights(cfg, s, "sliding_attention", i))(jnp.uint32(SEED), jnp.int32(2))
    h = jnp.asarray(np.random.default_rng(2).normal(size=(S, D)), F32)
    with jax.default_matmul_precision("highest"):
        k, v = ref.keys_values(h, w, cfg)
        want = ref.diff_attention(h, w, k, v, cfg, jnp.float32(layer), window)
    attn = DiffLlamaAttention(DiffLlamaConfig(hidden_size=D, num_attention_heads=H, num_key_value_heads=KV, head_dim=HD,
                                              attention_bias=True, rms_norm_eps=ref.SUB_NORM_EPS, attention_dropout=0.0),
                              layer_idx=layer).eval()
    t = lambda a: torch.tensor(np.asarray(a, np.float32))  # noqa: E731

    def heads(n):  # theirs <- ours: first halves of the pairs, then second halves
        order = list(range(0, n, 2)) + list(range(1, n, 2))
        return np.concatenate([np.arange(HD) + HD * j for j in order])

    with torch.no_grad():
        for name, n in (("q", H), ("k", KV), ("v", KV)):
            proj = getattr(attn, name + "_proj")
            proj.weight.copy_(t(np.asarray(w[name])[:, heads(n)]).T)
            proj.bias.copy_(t(np.asarray(w[name + "_bias"])[heads(n)]))
        attn.o_proj.weight.copy_(t(w["o"]).T)
        attn.o_proj.bias.copy_(t(w["o_bias"]))
        for i, name in enumerate(("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2")):
            getattr(attn, name).copy_(t(w["lambdas"][i]))
        q_pos, k_pos = np.arange(S)[:, None], np.arange(S)[None, :]
        seen = (k_pos <= q_pos) & ((k_pos > q_pos - window) if window else True)
        mask = torch.tensor(np.where(seen, 0.0, -1e30).astype(np.float32))[None, None]
        rotary = (torch.ones(1, S, HD), torch.zeros(1, S, HD))
        got = attn(t(h)[None], rotary, attention_mask=mask)[0][0].numpy()
    assert np.abs(got - np.asarray(want)).max() < 1e-6


# the decode kernel against the XLA contractions it replaces on the chip ------------------------


@pytest.mark.parametrize("window", [0, 512])
def test_the_decode_kernel_equals_the_xla_contractions(monkeypatch, window):
    """``ops.lane_decode`` interpreted, at the smallest sizes it engages at
    (blocks of 512 lanes, pairs of 128 values): rows of different lengths (one
    shorter than a block, one inside the second, one full; under the window the
    pool's ring of 512 lanes, wrapped and not), layer 1 of 2."""
    from tpu_engine.ops import lane_decode

    mc = tfm.ModelConfig(name="k", vocab_size=64, d_model=512, n_layers=4, n_heads=8, n_kv_heads=4, d_ff=64,
                         layer_types=("mamba1", "diff_window_attention", "mamba1", "diff_attention"),
                         sliding_window=512, mamba1_inner=64, mamba1_state=4, layer_norm=True, attn_bias=True,
                         rope=False, tie_head=True)
    rng = np.random.default_rng(4)
    M = 512 if window else 1024
    B, W = 3, mc.n_kv_heads * mc.head_dim
    k_arr, v_arr = (jnp.asarray(rng.normal(size=(2, B, M, W)), BF16) for _ in range(2))
    q = jnp.asarray(rng.normal(size=(B, 1, mc.n_heads * mc.head_dim)), BF16)
    lp = {"lambdas": jnp.asarray(rng.normal(size=(4, 64)) * 0.1, F32), "lambda_init": jnp.float32(0.5),
          "sub_norm": {"scale": jnp.ones((128,), BF16)}}
    positions = jnp.asarray([[99], [700], [M - 1 if not window else 2000]], jnp.int32)
    assert not lane_decode.engages(k_arr)   # off the TPU the XLA contractions stay
    want = generate._diff_attention(q, k_arr, v_arr, jnp.int32(1), positions, lp, mc, window, "full_attn")
    monkeypatch.setattr(lane_decode, "INTERPRET_OFF_TPU", True)
    assert lane_decode.engages(k_arr) and not lane_decode.engages(k_arr[:, :, :500])
    got = generate._diff_attention(q, k_arr, v_arr, jnp.int32(1), positions, lp, mc, window, "full_attn")
    assert np.abs(np.asarray(got, np.float32) - np.asarray(want, np.float32)).max() < 0.02


def test_the_walks_counters_count_the_shared_caches_readers_and_the_rings(monkeypatch):
    """Three requests through a stack whose reads engage the kernel (interpreted):
    one window layer's ring of 512 lanes, ONE full-attention cache of 1 024 that
    its own layer and the cross-attention layer after it read. A decode step is
    three calls: the ring's (every slot shows it a block, a freed one its lane
    0) and the shared cache's two (a row past 512 lanes walks two blocks, a freed
    slot one). By hand, from the rows each dispatch decoded: a request's own
    blocks whoever decodes beside it, and a block a call for every slot that does
    not decode; a call's grid is bounded by its blocks, so it took as many steps."""
    from tpu_engine.ops import lane_decode

    monkeypatch.setattr(lane_decode, "INTERPRET_OFF_TPU", True)
    mc = tfm.ModelConfig(name="k", vocab_size=64, d_model=512, n_layers=6, n_heads=8, n_kv_heads=4, d_ff=64,
                         layer_types=("mamba1", "diff_window_attention", "mamba1", "diff_attention", "gmu",
                                      "diff_cross_attention"),
                         sliding_window=512, mamba1_inner=64, mamba1_state=4, layer_norm=True, attn_bias=True,
                         rope=False, tie_head=True)
    engine = serving.ContinuousBatcher(tfm.init_params(jax.random.PRNGKey(2), mc), mc, max_slots=3, max_len=1024,
                                       compute_dtype=BF16, prefill_pad_to=8, prefill_chunk=512, chunk_steps=2)
    assert sorted(serving.lane_walks(mc, engine._cache)) == [(1, 512, 1), (2, 1024, 1)]
    shapes = [(20, 5), (510, 5), (600, 4)]          # (prompt, new tokens): the first comes from the prefill
    rids = [engine.submit(_tokens(n, 40 + i).tolist(), max_new_tokens=m) for i, (n, m) in enumerate(shapes)]
    steps, idle_slot_steps = 0, 0
    while any(engine.result(r)["status"] != "done" for r in rids):
        before = engine.stats()["decode_tokens_computed_total"]
        engine.step()
        computed = engine.stats()["decode_tokens_computed_total"] - before      # 2 steps x the rows that decoded
        steps += 2 * (computed > 0)
        idle_slot_steps += (2 * 3 - computed) * (computed > 0)
        assert steps < 100
    st = engine.stats()
    assert st["decode_attn_lanes_read_total"] == 0          # the ``attn`` kind's counters: the stack has none
    assert 0 < st["decode_attn_grid_steps_total"] == st["decode_attn_blocks_walked_total"]
    assert st["decode_attn_grid_steps_total"] < steps * (1 * 3 * 1 + 2 * 3 * 2)     # the blocks the leaves hold
    own = sum(1 + 2 * -(-(n + 1 + i) // 512)                # the ring's block and the shared cache's, twice
              for n, m in shapes for i in range(-(-(m - 1) // 2) * 2))
    assert own == 4 * 3 + (3 + 3 + 5 + 5) + 4 * 5
    assert st["decode_attn_blocks_walked_total"] == own + 3 * idle_slot_steps
