"""A stack whose every mixer is a gated power-retention layer of degree 2 — a
float32 state per kv-head that five (here: two or three) query heads share, a
normaliser carried beside it, NO lane at all — against its plain float32
reference (``benchmarks/onchip/reference/brumby.py``: the attention form, every
position's weights over every earlier one, no state, no expansion, no chunks).

Tiny widths (the configuration's rehearsal size: heads of 64 in tiles of 16, so
the tiled square keeps 10 tile pairs, 2 560 coordinates), seeded weights, float32
compute on the CPU. Logits have a spread of 0.16; both sides are float32 and
differ in the order of their sums (a state queried through the expansion
against explicit weights): whole prompts measured 3.3e-7 and ``TOL`` = 5e-6
absolute leaves ten times that. The controls must miss ``TOL`` by far, and each
does by the amount its case states. The gate's half-lives are drawn for 64 ..
16 384 tokens, so a late position still reads its prompt's first chunk: test
(b) measures that share before it trusts the comparison.
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

from families import brumby as family  # noqa: E402
from harness import counts_brumby  # noqa: E402
from reference import brumby as ref  # noqa: E402

from tpu_engine import layer_state, serving  # noqa: E402
from tpu_engine.generate import forward_with_cache, init_cache  # noqa: E402
from tpu_engine.hbm_estimate import estimate_serving_hbm  # noqa: E402
from tpu_engine.models import transformer as tfm  # noqa: E402
from tpu_engine.ops import power_update  # noqa: E402
from tpu_engine.sharding import Precision  # noqa: E402

TOL = 5e-6
SEED = 5
generate = sys.modules["tpu_engine.generate"]  # the package's ``generate`` is the function
CHUNK, PAD = 48, 8
LANES = 192  # positions a row may reach (the rotation): no leaf has a lane axis
F32 = jnp.float32


def _config():
    with open(os.path.join(BENCH, "configs", "brumby-14b-1chip-serve.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def tiny():
    """(configuration dict at its rehearsal size, ModelConfig, program params,
    reference params): one seed drawn twice, by the program and by the
    reference, each by its own code."""
    cfg = _config()
    cfg = {**cfg, **cfg["rehearsal"]}
    mc = family.model_config(cfg, "brumby-tiny")
    return cfg, mc, tfm.init_params(jax.random.PRNGKey(SEED), mc), ref.init_params(cfg, SEED)


def _tokens(n, stream=0):
    return np.random.default_rng([SEED, stream]).integers(0, 512, n).astype(np.int32)


def _reference(tiny, tokens):
    cfg, _, _, rparams = tiny
    return np.asarray(ref.forward_logits(rparams, np.asarray(tokens, np.int32), cfg))


def _prefill(params, mc, prompt, spoil=None):
    """The batcher's ingestion: the prompt zero-padded to PAD, one CHUNK a call
    through ``serving._prefill_forward`` with the chunk's real length. Returns
    (logits row of the last real token, the single-row cache). ``spoil(c1)``
    runs between chunks (the controls)."""
    n = len(prompt)
    padded = -(-n // PAD) * PAD
    toks = np.zeros((1, padded), np.int32)
    toks[0, :n] = prompt
    c1 = init_cache(mc, 1, LANES, dtype=F32)
    fn = jax.jit(partial(serving._prefill_forward, cfg=mc, compute_dtype=F32))
    last = None
    for t0 in range(0, padded, CHUNK):
        t1 = min(t0 + CHUNK, padded)
        row = min(max(n - 1 - t0, 0), t1 - t0 - 1)
        if spoil is not None and t0:
            c1 = spoil(c1)
        out, c1 = fn(params, jnp.asarray(toks[:, t0:t1]), c1, jnp.int32(row),
                     jnp.int32(min(max(n - t0, 0), t1 - t0)))
        if t0 <= n - 1 < t1:
            last = out
    return last, c1


def _pool(mc, slots=3):
    return serving.init_slot_cache(mc, slots, LANES, F32, prefill_chunk=CHUNK)


def _insert(pool, c1, slot, n):
    return serving._insert_prefill(pool, c1, jnp.int32(slot), jnp.int32(n), False)


def _decode_logits(params, mc, pool, slot, feed, active=None, each_step=None):
    """Teacher-forced decode of ``feed`` in ``slot`` through ``decode_step``;
    other rows are not active unless ``active`` says so. ``each_step(pool)``
    runs after every step (the bfloat16 control)."""
    B = pool.lengths.shape[0]
    act = np.zeros(B, bool) if active is None else np.array(active)
    act[slot] = True
    step = jax.jit(partial(serving.decode_step, cfg=mc, compute_dtype=F32))
    out = []
    for tok in feed:
        toks = np.zeros(B, np.int32)
        toks[slot] = tok
        lg, pool = step(params, jnp.asarray(toks), pool, jnp.asarray(act))
        if each_step is not None:
            pool = each_step(pool)
        out.append(lg[slot])
    return jnp.stack(out), pool


def _map_state(cache, fn):
    return dataclasses.replace(cache, layers={"power": jax.tree.map(fn, cache.layers["power"])})


def _serve_then_decode(tiny, n_prompt, n_decode, spoil=None, each_step=None):
    """Worst absolute logit gap to the reference over the prompt's last row and
    ``n_decode`` teacher-forced decode steps of one request."""
    _, mc, params, _ = tiny
    toks = _tokens(n_prompt + n_decode, 3)
    want = _reference(tiny, toks)
    last, c1 = _prefill(params, mc, toks[:n_prompt], spoil)
    pool = _insert(_pool(mc), c1, 1, n_prompt)
    if each_step is not None:
        pool = each_step(pool)
    got, _ = _decode_logits(params, mc, pool, 1, toks[n_prompt:], each_step=each_step)
    gap = np.abs(np.asarray(got) - want[n_prompt:]).max()
    return max(gap, np.abs(np.asarray(last) - want[n_prompt - 1]).max())


# (a) the cached forward over a whole prompt ---------------------------------


@pytest.mark.parametrize("n, sub_chunk", [(50, 256), (150, 256), (150, 16)])
def test_forward_with_cache_over_a_whole_prompt_equals_the_reference(tiny, n, sub_chunk):
    """No cache behind it and one call: the chunked form alone (one chunk, or
    sub-chunks of 16 whose state carries what came before) against the
    reference's explicit weights."""
    _, mc, params, _ = tiny
    mc = mc.with_(ssm_chunk=sub_chunk)
    toks = _tokens(n, 1)
    logits, cache = forward_with_cache(params, jnp.asarray(toks)[None], init_cache(mc, 1, LANES, dtype=F32),
                                       mc, compute_dtype=F32)
    assert np.abs(np.asarray(logits[0]) - _reference(tiny, toks)).max() < TOL
    assert cache.max_len == 0 and int(cache.length) == n  # no lane; the length still counts positions


# (b) chunked prefill, insert, decode ----------------------------------------


def test_the_first_chunk_still_carries_a_tenth_of_a_late_positions_normaliser(tiny):
    """What makes (b)'s comparison a test of the CARRIED state: at the last
    decoded position the reference's weights over the prompt's first chunk are
    more than a tenth of all its weights, for the median (layer, head)."""
    cfg, _, _, rparams = tiny
    toks = _tokens(166, 3)
    d = ref._dims(cfg)
    with jax.default_matmul_precision("highest"):
        x = rparams["embed"][jnp.asarray(toks)]
        shares = []
        for i in range(cfg["num_hidden_layers"]):
            w = ref.draw_layer(cfg, rparams["seed"], jnp.int32(i))
            u = ref.rms_norm(x, d["eps"])
            S, H, KV, HD = len(toks), d["H"], d["KV"], d["HD"]
            q = ref.rope(ref.rms_norm((u @ w["q"]).reshape(S, H, HD), d["eps"]), cfg["rope_theta"])[-1]
            k = ref.rope(ref.rms_norm((u @ w["k"]).reshape(S, KV, HD), d["eps"]), cfg["rope_theta"])
            cum = jnp.cumsum(jax.nn.log_sigmoid(u @ w["g_proj"] + w["g_bias"]), axis=0)
            wts = jnp.square(jnp.einsum("kgd,skd->kgs", q.reshape(KV, H // KV, HD), k)) \
                * jnp.exp(cum[-1] - cum).T[:, None, :]
            shares.append(np.asarray(jnp.sum(wts[..., :CHUNK], -1) / jnp.sum(wts, -1)).ravel())
            x = ref._layer(x, rparams["seed"], jnp.int32(i), ref._freeze(cfg))
    assert np.median(np.concatenate(shares)) > 0.10


def test_chunked_prefill_insert_and_22_decode_steps_equal_the_reference(tiny):
    """A prompt of 144 in three chunks of 48 (the state handed from chunk to
    chunk in the staging row), inserted into slot 1 of a pool of 3, then 22
    decode steps through ``decode_step``: logits, not tokens."""
    assert _serve_then_decode(tiny, 144, 22) < TOL


@pytest.mark.parametrize("control, at_least", [("state_zeroed_at_a_chunk_boundary", 1e-2),
                                               ("state_kept_in_bfloat16", 1e-4)])
def test_control_what_harms_the_carried_state_misses_the_tolerance(tiny, control, at_least):
    """The same comparison must FAIL, by far, when the staged state is zeroed
    between the second and third chunk (measured 0.12), and when the state is
    rounded to bfloat16 wherever it rests (after each chunk and each step:
    measured 1.5e-3): ``TOL`` sees both."""
    if control == "state_zeroed_at_a_chunk_boundary":
        calls = []

        def spoil(c1):
            calls.append(1)
            return _map_state(c1, jnp.zeros_like) if len(calls) == 2 else c1

        gap = _serve_then_decode(tiny, 144, 22, spoil=spoil)
    else:
        round_trip = lambda c: _map_state(c, lambda a: a.astype(jnp.bfloat16).astype(F32))  # noqa: E731
        gap = _serve_then_decode(tiny, 144, 22, spoil=round_trip, each_step=round_trip)
    assert gap > at_least > 10 * TOL


# (c) the expansion and the sharing of a state -------------------------------


@pytest.mark.parametrize("head_dim", [128, 64, 32, 16])
def test_the_expansions_inner_product_is_the_squared_dot_product(head_dim):
    mc = tfm.ModelConfig(name="phi", head_dim_override=head_dim)
    tile = tfm.POWER_TILE
    n = head_dim // tile
    assert mc.power_state_width == n * (n + 1) // 2 * tile * tile
    assert head_dim != 128 or mc.power_state_width == 9216
    y, z = jax.random.normal(jax.random.PRNGKey(0), (2, 7, head_dim), jnp.float64 if jax.config.x64_enabled else F32)
    got = jnp.sum(generate.power_expand(y, mc) * generate.power_expand(z, mc), -1)
    want = jnp.square(jnp.sum(y * z, -1))
    # float32 sums of W products that cancel: held to the terms' own scale, |y|^2 |z|^2
    scale = np.asarray(jnp.sum(y * y, -1) * jnp.sum(z * z, -1))
    assert (np.abs(np.asarray(got) - np.asarray(want)) <= 2e-6 * scale).all()
    # what the kernel is handed: the row laid out twice, a pair of tiles one product of two slices
    r, t = power_update.laid_out_twice(y, tile)
    w = tile * tile
    blocks = [r[..., a * w:(a + 1) * w] * (t[..., b * w:(b + 1) * w] * (1.0 if a == b else 2.0 ** 0.5))
              for a, b in mc.power_tile_pairs]
    assert np.array_equal(np.asarray(jnp.concatenate(blocks, -1)), np.asarray(generate.power_expand(y, mc)))


def _step_operands(mc, B, seed=0):
    KV, G, HD, W = mc.n_kv_heads, mc.n_heads // mc.n_kv_heads, mc.head_dim, mc.power_state_width
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    q, k, v = (jax.random.normal(ks[0], (B, KV, G, HD)), jax.random.normal(ks[1], (B, KV, HD)),
               jax.random.normal(ks[2], (B, KV, HD)))
    log_g = -jax.random.uniform(ks[3], (B, KV))
    w = jnp.full((B, KV), 1.0 / HD)
    state = jax.random.normal(ks[4], (2, B, KV, HD, W))
    norm = jax.random.uniform(ks[5], (2, B, KV, W))
    return q, k, v, log_g, w, state, norm


def test_the_query_heads_of_a_kv_head_read_one_state_as_separate_passes_would(tiny):
    """One step for G query heads on a kv-head's state against G steps of one
    query head each: the same numerators and normalisers, and the same state
    bit for bit."""
    mc = tiny[1].with_(n_heads=6)  # G = 3
    q, k, v, log_g, w, state, norm = _step_operands(mc, 2)
    num, den, h, z = generate._power_step(q, k, v, log_g, w, state[0], norm[0], mc)
    for i in range(3):
        n1, d1, h1, z1 = generate._power_step(q[:, :, i:i + 1], k, v, log_g, w, state[0], norm[0], mc)
        # (a contraction of another shape sums in another order)
        assert np.allclose(np.asarray(n1[:, :, 0]), np.asarray(num[:, :, i]), rtol=1e-5, atol=1e-4)
        assert np.allclose(np.asarray(d1[:, :, 0]), np.asarray(den[:, :, i]), rtol=1e-5, atol=1e-4)
        assert np.array_equal(np.asarray(h1), np.asarray(h)) and np.array_equal(np.asarray(z1), np.asarray(z))


# (d) the T = 1 kernel -------------------------------------------------------


@pytest.mark.parametrize("head_dim, heads, kv", [(32, 6, 2), (128, 5, 1), (16, 4, 4)])
@pytest.mark.parametrize("layer", [0, 1])
def test_the_kernel_interpreted_equals_the_xla_step(head_dim, heads, kv, layer, monkeypatch):
    """``ops.power_update`` (interpreted) against ``generate._power_step`` on
    layer ``layer`` of a stack of two: numerator, normaliser, the layer's state
    and normaliser; the other layer untouched; a row with ``log_g = w = 0``
    keeps its state bit for bit."""
    monkeypatch.setattr(power_update, "INTERPRET_OFF_TPU", True)
    tile = tfm.POWER_TILE
    mc = tfm.ModelConfig(name="k", n_heads=heads, n_kv_heads=kv, head_dim_override=head_dim)
    q, k, v, log_g, w, state, norm = _step_operands(mc, 3, seed=layer)
    log_g, w = log_g.at[1].set(0.0), w.at[1].set(0.0)
    assert power_update.engages(state, tile)
    num, den, s1, z1 = jax.jit(partial(power_update.power_update, tile=tile), donate_argnums=())(
        q, k, v, log_g, w, state, norm, jnp.int32(layer))
    n0, d0, h0, z0 = generate._power_step(q, k, v, log_g, w, state[layer], norm[layer], mc)
    for got, want in ((num, n0), (den, d0), (s1[layer], h0), (z1[layer], z0)):
        assert np.allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-4 * float(jnp.abs(want).max()))
    assert np.allclose(np.asarray(s1[layer]), np.asarray(h0), rtol=0, atol=1e-5)
    other = 1 - layer
    assert np.array_equal(np.asarray(s1[other]), np.asarray(state[other]))
    assert np.array_equal(np.asarray(z1[other]), np.asarray(norm[other]))
    assert np.array_equal(np.asarray(s1[layer, 1]), np.asarray(state[layer, 1]))
    assert np.array_equal(np.asarray(z1[layer, 1]), np.asarray(norm[layer, 1]))


@pytest.mark.parametrize("case, engages", [("whole_tiles", True), ("tile_pairs_of_64_lanes", False),
                                           ("bfloat16_state", False), ("off_the_tpu", False)])
def test_the_kernel_engages_from_what_the_trace_sees(case, engages, monkeypatch):
    monkeypatch.setattr(power_update, "INTERPRET_OFF_TPU", case != "off_the_tpu")
    tile = 8 if case == "tile_pairs_of_64_lanes" else 16
    state = jax.ShapeDtypeStruct((2, 3, 2, 32, 3 * 256), jnp.bfloat16 if case == "bfloat16_state" else F32)
    assert power_update.engages(state, tile) == engages


# (e) slots ------------------------------------------------------------------


def test_a_slot_reset_then_reused_reads_as_a_fresh_one(tiny):
    """Slot 1 serves a long request, is reset (its state zeroed: no length
    hides it) and takes a short one: the short request's logits are those of a
    pool that never held the first."""
    _, mc, params, _ = tiny
    first, second = _tokens(120, 4), _tokens(40, 5)
    feed = _tokens(6, 6)
    _, c_first = _prefill(params, mc, first)
    _, c_second = _prefill(params, mc, second)
    used = _insert(_pool(mc), c_first, 1, len(first))
    _, used = _decode_logits(params, mc, used, 1, feed)
    used = serving._reset_slot(used, 1)
    assert all(not np.asarray(a[:, 1]).any() for a in used.layers["power"].values())
    got, _ = _decode_logits(params, mc, _insert(used, c_second, 1, len(second)), 1, feed)
    want, _ = _decode_logits(params, mc, _insert(_pool(mc), c_second, 1, len(second)), 1, feed)
    assert np.array_equal(np.asarray(got), np.asarray(want))


def test_12_staggered_rows_equal_12_lockstep_ones(tiny):
    """Twelve requests of one prompt: admitted together and decoded in
    lockstep, or admitted one decode step apart (a row that is not yet active
    keeps its state, and the active ones do not see it): every row's logits at
    its own k-th step agree."""
    _, mc, params, _ = tiny
    n, steps, B = 56, 4, 12
    prompts = [_tokens(n, 30 + b) for b in range(B)]
    feeds = np.stack([_tokens(steps, 50 + b) for b in range(B)])
    cs = [_prefill(params, mc, p)[1] for p in prompts]
    step = jax.jit(partial(serving.decode_step, cfg=mc, compute_dtype=F32))
    lock = _pool(mc, B)
    for b in range(B):
        lock = _insert(lock, cs[b], b, n)
    want = []
    for t in range(steps):
        lg, lock = step(params, jnp.asarray(feeds[:, t]), lock, jnp.ones(B, bool))
        want.append(np.asarray(lg))
    stag, got = _pool(mc, B), np.zeros((steps, B, 512), np.float32)
    for tick in range(B + steps - 1):
        if tick < B:
            stag = _insert(stag, cs[tick], tick, n)
        k = tick - np.arange(B)  # row b is at its k-th step
        active = (k >= 0) & (k < steps)
        toks = np.where(active, feeds[np.arange(B), np.clip(k, 0, steps - 1)], 0)
        lg, stag = step(params, jnp.asarray(toks.astype(np.int32)), stag, jnp.asarray(active))
        for b in np.nonzero(active)[0]:
            got[k[b], b] = np.asarray(lg[b])
    assert np.abs(got - np.stack(want)).max() < 1e-6


# (f) the engine --------------------------------------------------------------


def test_the_engine_serves_what_the_reference_would_and_counts_it(tiny, monkeypatch):
    """``ContinuousBatcher`` end to end on a stack that keeps no lane (admit,
    chunked prefill with the bucket's padding, insert, decode chunks that
    overshoot, reset, reuse of both slots): every served token is the
    reference's best on the request's own history, and the counters say what
    happened."""
    _, mc, params, _ = tiny
    prompts = [_tokens(n, 10 + i).tolist() for i, n in enumerate((100, 45, 70, 90))]
    wants = [12, 30, 7, 15]
    engine = serving.ContinuousBatcher(params, mc, max_slots=2, max_len=LANES, compute_dtype=F32,
                                       prefill_chunk=CHUNK, prefill_pad_to=PAD, chunk_steps=4)
    assert engine._cache.n_lanes == 0 and engine._cache.recurrent and not engine._cache.ring
    dispatches, decode = [], engine._decode
    engine._decode = lambda *a: dispatches.append(1) or decode(*a)
    ids = [engine.submit(p, max_new_tokens=w) for p, w in zip(prompts, wants)]
    for _ in range(400):
        engine.step()
        if all(engine.result(i)["status"] == "done" for i in ids):
            break
    for i, p in zip(ids, prompts):
        served = engine.result(i)["tokens"]
        rows = _reference(tiny, np.asarray(p + served))[len(p) - 1:len(p) - 1 + len(served)]
        assert (rows.max(-1) - rows[np.arange(len(served)), served]).max() < 1e-5
    st = engine.stats()
    leaves = engine._cache.layers["power"]
    assert st["state_inserts_total"] == 4 and st["state_resets_total"] == 4
    assert st["recurrent_state_bytes"] == leaves["state"].nbytes + leaves["norm"].nbytes > 0
    assert st["power_layer_steps_total"] == len(dispatches) * 4 * mc.n_layers
    assert st["recurrent_updates_in_place_total"] == 0  # off the TPU and not interpreted: the XLA step
    assert st["decode_attn_lanes_read_total"] == st["decode_attn_lanes_pool_total"] == 0


def test_the_engine_updates_the_state_in_place_where_the_kernel_engages(tiny, monkeypatch):
    """The tiny model (a tile pair is 256 lanes, a head 64 value rows), served
    twice: with the one-pass kernel interpreted and with the XLA step. The same
    tokens; a slot that never decodes keeps the state planted in it bit for
    bit; ``recurrent_updates_in_place_total`` counts dispatches x the chunk's
    steps x the layers with the kernel, 0 without."""
    _, mc, params, _ = tiny
    prompts = [_tokens(n, 20 + i).tolist() for i, n in enumerate((70, 9))]

    def serve(interpret):
        monkeypatch.setattr(power_update, "INTERPRET_OFF_TPU", interpret)
        engine = serving.ContinuousBatcher(params, mc, max_slots=3, max_len=LANES, compute_dtype=F32,
                                           prefill_chunk=CHUNK, prefill_pad_to=PAD, chunk_steps=4)
        planted = jax.tree.map(lambda a: jax.random.normal(jax.random.PRNGKey(1), a[:, 2].shape),
                               engine._cache.layers["power"])
        engine._cache = dataclasses.replace(engine._cache, layers={"power": jax.tree.map(
            lambda a, p: a.at[:, 2].set(p), engine._cache.layers["power"], planted)})
        dispatches, decode = [], engine._decode
        engine._decode = lambda *a: dispatches.append(1) or decode(*a)
        ids = [engine.submit(p, max_new_tokens=w) for p, w in zip(prompts, (11, 6))]
        for _ in range(100):
            engine.step()
            if all(engine.result(i)["status"] == "done" for i in ids):
                break
        kept = jax.tree.map(lambda a, p: np.array_equal(np.asarray(a[:, 2]), np.asarray(p)),
                            engine._cache.layers["power"], planted)
        return ([engine.result(i)["tokens"] for i in ids], kept,
                engine.stats()["recurrent_updates_in_place_total"], len(dispatches))

    tokens, kept, in_place, dispatches = serve(True)
    assert [len(t) for t in tokens] == [11, 6] and dispatches >= 3
    assert in_place == dispatches * 4 * mc.n_layers and all(kept.values())
    xla_tokens, xla_kept, xla_in_place, _ = serve(False)
    assert xla_tokens == tokens and xla_in_place == 0 and all(xla_kept.values())


# (g) the table, the estimate, the refusals ----------------------------------


def test_param_count_and_the_serving_estimate_price_what_is_allocated(tiny):
    _, mc, params, _ = tiny
    assert tfm.param_count(mc) == sum(a.size for a in jax.tree.leaves(params))
    axes = tfm.logical_axes(mc)
    is_axes = lambda x: isinstance(x, tuple)  # noqa: E731
    assert jax.tree.structure(jax.tree.map(lambda a: 0, params)) \
        == jax.tree.structure(jax.tree.map(lambda a: 0, axes, is_leaf=is_axes))
    served = tfm.served_format(params, jnp.bfloat16)
    assert served["layers"]["power"]["g_bias"].dtype == F32  # the gate's bias sets every half-life
    assert served["layers"]["power"]["g_proj"]["kernel"].dtype == jnp.bfloat16


def test_the_estimate_at_the_published_widths_is_the_arithmetic(monkeypatch):
    """8 layers and 12 slots at the published widths, shapes only: the state
    term 3.65 GB and the weights 8.40 GB, each within 1 %; the counts module
    and the table agree on a layer's state to the byte."""
    monkeypatch.setattr(tfm, "MODEL_CONFIGS", dict(tfm.MODEL_CONFIGS))
    cfg = _config()
    mc = family.model_config(cfg, "brumby-14b-1chip-serve")
    tfm.MODEL_CONFIGS[mc.name] = mc
    p = cfg["program"]
    est = estimate_serving_hbm(mc.name, p["max_slots"], p["max_len"], prefill_chunk=p["prefill_chunk"],
                               compute_dtype=Precision.BF16)
    gib = 2 ** 30
    assert est.recurrent_state_gib * gib == pytest.approx(3.65e9, rel=0.01)
    assert est.params_gib * gib == pytest.approx(8.40e9, rel=0.01)
    assert est.kv_pool_gib == 0.0 and any("8 power-retention layers x 12 slots" in n for n in est.notes)
    by_kind = layer_state.state_bytes(mc, 12, p["max_len"], jnp.bfloat16)
    assert by_kind == {"power": 8 * counts_brumby.power_state_bytes(cfg, 12)}
    assert by_kind["power"] / (8 * 12) == 8 * 9216 * 129 * 4  # <= 9 216 x 129 float32 a kv-head, layer and slot
    pool = jax.eval_shape(lambda: serving.init_slot_cache(mc, 12, p["max_len"], jnp.bfloat16,
                                                          prefill_chunk=p["prefill_chunk"]))
    assert pool.recurrent_state_bytes == by_kind["power"] and pool.n_lanes == 0
    assert counts_brumby.weight_bytes_per_decode_step(cfg) == pytest.approx(
        2 * (tfm.param_count(mc) - 151936 * 5120), rel=1e-4)  # all but the table, which a step looks rows up in


def _refusals(mc, params):
    from tpu_engine import disagg, spec_pool
    from tpu_engine.generate import speculative_generate
    from tpu_engine.mesh_runtime import build_mesh
    from tpu_engine.serving_fleet import ServingFleet, ServingReplicaSpec, build_replica_engine
    from tpu_engine.sharding import MeshConfig, TPUTrainConfig

    eng = partial(serving.ContinuousBatcher, params, mc, max_slots=2, max_len=64, compute_dtype=F32)
    spec = ServingReplicaSpec(model_name=mc.name, max_slots=2, max_len=64)
    draft = tfm.MODEL_CONFIGS["gpt-tiny"]
    return {
        "prefix_cache": lambda: eng(prefix_cache_tokens=64),
        "hold_kv": lambda: eng().submit([1, 2, 3], hold_kv=True),
        "submit_prefilled": lambda: eng().submit_prefilled(None),
        "extract_slot_kv": lambda: disagg.extract_slot_kv(None, 0, 1, cfg=mc, prompt=[1], emitted=[]),
        "disagg_fleet": lambda: disagg.DisaggServingFleet(None, spec, spec),
        "host_kv_tier": lambda: ServingFleet(None, spec, prefix_plane=object()),
        "speculative_engine": lambda: eng(draft_params={}, draft_cfg=draft),
        "speculative_fleet": lambda: spec_pool.SpecServingFleet(None, spec, spec),
        "decode_verify": lambda: serving.decode_verify(params, jnp.zeros((2, 3), jnp.int32), None, None, mc),
        "speculative_generate": lambda: speculative_generate(params, params, jnp.zeros((1, 4), jnp.int32),
                                                             mc, mc, 4),
        "int8_kv_pool": lambda: serving.init_slot_cache(mc, 2, 64, kv_quant=True),
        "int8_kv_cache": lambda: init_cache(mc, 1, 64, kv_quant=True),
        "mesh_sharded_pool": lambda: eng(mesh=build_mesh(MeshConfig(model=2))),
        "tensor_parallel": lambda: build_replica_engine(spec.model_copy(update={"tensor_parallel": 2})),
        "training": lambda: __import__("tpu_engine.train", fromlist=["x"]).build_train_program(
            TPUTrainConfig(model_name=mc.name, precision=Precision.FP32), model_cfg=mc),
        "cacheless_forward": lambda: tfm.forward(params, jnp.zeros((1, 8), jnp.int32), mc),
    }


@pytest.mark.parametrize("feature", ["prefix_cache", "hold_kv", "submit_prefilled", "extract_slot_kv",
                                     "disagg_fleet", "host_kv_tier", "speculative_engine", "speculative_fleet",
                                     "decode_verify", "speculative_generate", "int8_kv_pool", "int8_kv_cache",
                                     "mesh_sharded_pool", "tensor_parallel", "training", "cacheless_forward"])
def test_what_assumes_keys_and_values_refuses_the_stack_by_name(tiny, feature):
    _, mc, params, _ = tiny
    tfm.MODEL_CONFIGS[mc.name] = mc
    try:
        with pytest.raises(tfm.RecurrentLayersUnsupported, match="power") as err:
            _refusals(mc, params)[feature]()
    finally:
        del tfm.MODEL_CONFIGS[mc.name]
    assert mc.name in str(err.value) and err.value.feature


@pytest.mark.parametrize("bad, why", [
    (dict(layer_types=("power_retention", "attention")), "stack of their own"),
    (dict(n_experts=4), "stack of their own"),
    (dict(head_dim_override=24), "whole tiles"),
    (dict(n_heads=5), "multiple of n_kv_heads"),
    (dict(sliding_window=16), "sliding window"),
])
def test_a_pattern_the_program_cannot_run_is_refused_where_it_is_built(tiny, bad, why):
    with pytest.raises(ValueError, match=why):
        tfm.check_hybrid(tiny[1].with_(**bad))


@pytest.mark.parametrize("key, value, why", [
    ("sliding_window", 4096, "sliding window"), ("use_sliding_window", True, "sliding window"),
    ("attention_bias", True, "attention_bias"), ("tie_word_embeddings", True, "tied head"),
    ("rope_scaling", {"type": "yarn", "factor": 4.0}, "rope_scaling"), ("power_degree", 3, "power_degree"),
    ("power_tile", 8, "power_tile"), ("power_norm_eps", 1e-5, "power_norm_eps"),
    ("hidden_act", "gelu", "hidden_act"), ("gate_half_life_tokens", [1, 2], "gate_half_life_tokens"),
    ("layer_types", ["power_retention", "full_attention"], "layer_types"),
])
def test_the_family_refuses_what_the_recipe_cannot_represent(tiny, key, value, why):
    with pytest.raises(ValueError, match=why):
        family.model_config({**tiny[0], key: value}, "refused")
