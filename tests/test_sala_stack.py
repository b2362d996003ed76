"""A stack of block-sparse attention layers beside lightning (linear) attention
layers — an indexer cache of compressed keys beside keys and values, a
recurrent per-slot state beside both — against its plain float32 reference
(``benchmarks/onchip/reference/minicpm_sala.py``: the recurrence token by
token, a plain ``top_k`` for every position, no cache).

Tiny widths (the configuration's rehearsal size: windows of 8 keys every 4,
blocks of 16, the 4 best of them, 2 local, dense below 64), seeded weights,
float32 compute on the CPU. Logits have a spread of 0.04 and the reference's two
best lie 0.007 apart (median); both sides are float32 and differ in the order
of their sums (the chunked lightning scan against the token-by-token
recurrence, a gather of chosen blocks against a dense masked softmax): 30
decoded positions measured 9e-8, and ``TOL`` = 1e-6 absolute leaves ten times
that. Every block id the program selects is compared with the reference's, as
a set per position, kv-head and layer. The controls must miss ``TOL`` by far,
and each does by the amount its case states: a lightning state dropped at a
chunk boundary measured 0.073, the prompt's padding fed as real tokens 0.036,
one compressed key dropped 8.5e-4, three blocks attended for four 1.8e-3.
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

from families import minicpm_sala as family  # noqa: E402
from reference import minicpm_sala as ref  # noqa: E402

from tpu_engine import layer_state, serving  # noqa: E402
from tpu_engine.generate import forward_with_cache, init_cache  # noqa: E402
from tpu_engine.models import transformer as tfm  # noqa: E402
from tpu_engine.ops import sparse_block_attention, ssd_update  # noqa: E402

sparse_block_attention.INTERPRET_OFF_TPU = True  # these are the CPU's tests: the decode kernel is interpreted

TOL = 1e-6
SEED = 5
generate = sys.modules["tpu_engine.generate"]  # the package's ``generate`` is the function
# Prefill chunks of 24 split the 8-key windows (20..27) and the 16-lane blocks
# (16..31); prompts pad to 8.
CHUNK, PAD = 24, 8
LANES = 160
F32 = jnp.float32


@pytest.fixture(scope="module")
def tiny():
    """(configuration dict at its rehearsal size, ModelConfig, program params,
    reference params): one seed drawn twice, by the program and by the
    reference, each by its own code."""
    with open(os.path.join(BENCH, "configs", "minicpm-sala-1chip-serve.json")) as f:
        cfg = json.load(f)
    cfg = {**cfg, **cfg["rehearsal"]}
    mc = family.model_config(cfg, "sala-tiny")
    return cfg, mc, tfm.init_params(jax.random.PRNGKey(SEED), mc), ref.init_params(cfg, SEED)


def _tokens(n, stream=0):
    return np.random.default_rng([SEED, stream]).integers(0, 512, n).astype(np.int32)


class Chosen:
    """Records every call of the program's indexer (``generate._select_blocks``)
    while it is installed: ``ids[layer][position][kv-head]`` is the set of
    block ids selected (a position's n-th appearance is the n-th sparse layer's:
    the stack is walked in order)."""

    def __init__(self, monkeypatch):
        self.ids: list[dict] = []
        orig = generate._select_blocks

        def recording(qg, ck, positions, n_blocks, cfg):
            ids = orig(qg, ck, positions, n_blocks, cfg)
            jax.debug.callback(self._note, positions, ids, ordered=True)
            return ids

        monkeypatch.setattr(generate, "_select_blocks", recording)

    def _note(self, positions, ids):
        positions, ids = np.asarray(positions), np.asarray(ids)   # [B,T], [B,KV,T,topk]
        for b in range(positions.shape[0]):
            seen = set()
            for t, pos in enumerate(positions[b].tolist()):
                if pos in seen:
                    continue  # a query block's padding repeats its last query
                seen.add(pos)
                layer = sum(1 for per in self.ids if (b, pos) in per)
                if layer == len(self.ids):
                    self.ids.append({})
                self.ids[layer][(b, pos)] = [frozenset(ids[b, g, t].tolist()) for g in range(ids.shape[1])]

    def check(self, want, row, positions, dense_len):
        """The program's selections for ``positions`` of ``row`` equal the
        reference's ``want`` (a list over sparse layers of [S, KV, topk])."""
        compared = 0
        for layer, per in enumerate(self.ids):
            for pos in positions:
                if pos < dense_len or (row, pos) not in per:
                    continue
                for g, got in enumerate(per[(row, pos)]):
                    assert got == frozenset(np.asarray(want[layer][pos, g]).tolist()), (layer, pos, g)
                    compared += 1
        return compared


def _prefill(params, mc, prompt, spoil=None, lanes=LANES):
    """The batcher's ingestion: the prompt zero-padded to PAD, one CHUNK a call
    through ``serving._prefill_forward`` with the chunk's real length. Returns
    (logits row of the last real token, the single-row cache). ``spoil(c1)``
    runs between chunks (the controls)."""
    n = len(prompt)
    padded = -(-n // PAD) * PAD
    toks = np.zeros((1, padded), np.int32)
    toks[0, :n] = prompt
    c1 = init_cache(mc, 1, lanes, dtype=F32)
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


def _pool(mc, slots=3, lanes=LANES):
    return serving.init_slot_cache(mc, slots, lanes, F32, prefill_chunk=CHUNK)


def _insert(pool, c1, slot, n):
    return serving._insert_prefill(pool, c1, jnp.int32(slot), jnp.int32(n), False)


def _decode_logits(params, mc, pool, slot, feed, active=None):
    """Teacher-forced decode of ``feed`` in ``slot`` through ``decode_step``;
    other rows are not active unless ``active`` says so."""
    B = pool.lengths.shape[0]
    act = np.zeros(B, bool) if active is None else np.array(active)
    act[slot] = True
    step = jax.jit(partial(serving.decode_step, cfg=mc, compute_dtype=F32))
    out = []
    for tok in feed:
        toks = np.zeros(B, np.int32)
        toks[slot] = tok
        lg, pool = step(params, jnp.asarray(toks), pool, jnp.asarray(act))
        out.append(lg[slot])
    return jnp.stack(out), pool


def _reference(tiny, tokens):
    """(logits [S, V], each sparse layer's block ids [S, KV, topk]) of the
    reference on ``tokens`` padded to whole blocks, and past its query block
    to whole query blocks (causal: padding never reaches a real row)."""
    cfg, _, _, rparams = tiny
    to = 16 if len(tokens) <= ref.Q_BLOCK else ref.Q_BLOCK
    padded = np.zeros(-(-len(tokens) // to) * to, np.int32)
    padded[:len(tokens)] = tokens
    logits, chosen = ref.forward_logits(rparams, padded, cfg)
    return np.asarray(logits), [np.asarray(c) for c in chosen]


def _with_leaf(cache, kind, leaf, fn):
    return dataclasses.replace(cache, layers={**cache.layers, kind: {
        **cache.layers[kind], leaf: fn(cache.layers[kind][leaf])}})


# (a) the cached forward over a whole prompt ---------------------------------


# every position dense; 86 positions that select, two query blocks; the same with the lightning scan
# in sub-chunks of 16 (nine whole and one of 6 positions, padded)
@pytest.mark.parametrize("n, sub_chunk", [(50, 256), (150, 256), (150, 16)])
def test_forward_with_cache_over_a_whole_prompt_equals_the_reference(tiny, n, sub_chunk, monkeypatch):
    cfg, mc, params, _ = tiny
    mc = mc.with_(ssm_chunk=sub_chunk)
    toks = _tokens(n)
    chosen = Chosen(monkeypatch)
    logits, cache = forward_with_cache(params, jnp.asarray(toks)[None], init_cache(mc, 1, LANES, dtype=F32),
                                       mc, compute_dtype=F32)
    jax.effects_barrier()
    want, want_ids = _reference(tiny, toks)
    assert np.abs(np.asarray(logits[0]) - want[:n]).max() < TOL
    assert chosen.check(want_ids, 0, range(n), mc.sparse_dense_len) == 2 * 2 * max(n - 64, 0)
    assert int(cache.length) == n
    assert cache.layers["sparse_attn"]["ck"].shape == (2, 1, LANES // 4, 32)


# (b) chunked prefill with pad positions, insert, >= 40 decode steps ---------


@pytest.mark.parametrize("n_prompt", [45, 100])  # decode crosses dense_len = 64; every decoded position selects
def test_chunked_prefill_insert_and_44_decode_steps_equal_the_reference(tiny, n_prompt, monkeypatch):
    cfg, mc, params, _ = tiny
    toks = _tokens(n_prompt + 45, 1)
    chosen = Chosen(monkeypatch)
    last, c1 = _prefill(params, mc, toks[:n_prompt])
    pool = _insert(_pool(mc), c1, 1, n_prompt)
    logits, pool = _decode_logits(params, mc, pool, 1, toks[n_prompt:n_prompt + 44])
    jax.effects_barrier()
    want, want_ids = _reference(tiny, toks)
    assert np.abs(np.asarray(last) - want[n_prompt - 1]).max() < TOL                       # prefill's own row
    assert np.abs(np.asarray(logits) - want[n_prompt:n_prompt + 44]).max() < TOL           # 44 decode steps
    assert int(pool.lengths[1]) == n_prompt + 44 and int(pool.lengths[0]) == 0
    # prefill ran on row 0 of its own cache, decode on row 1 of the pool
    selecting = [p for p in range(n_prompt + 44) if p >= 64]
    assert chosen.check(want_ids, 0, range(n_prompt), 64) + chosen.check(want_ids, 1, range(n_prompt, n_prompt + 44), 64) \
        == 2 * 2 * len(selecting)
    # every window the decode completed has its compressed key, and only those
    ck = np.asarray(pool.layers["sparse_attn"]["ck"][:, 1])
    written = (n_prompt + 44 - 8) // 4 + 1
    assert (np.abs(ck[:, :written]).max(-1) > 0).all() and (ck[:, written:] == 0).all()


def test_40_steps_through_decode_chunk_leave_the_state_the_reference_implies(tiny):
    """Greedy through ``decode_chunk`` (5 dispatches of 8, tokens fed back
    inside the scan), then one more step's logits against the reference on the
    prompt and what was generated."""
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
    want, _ = _reference(tiny, seq)
    assert np.abs(np.asarray(logits[0]) - want[len(prompt) + 40]).max() < TOL
    # and every token it fed itself was the reference's own best (to rounding)
    rows = want[len(prompt) - 1:len(prompt) + 40]
    assert (rows[np.arange(41), generated] >= rows.max(-1) - TOL).all()


# (c) a reused slot, an inactive neighbour -------------------------------------


@pytest.mark.parametrize("scenario", ["reused_after_a_longer_request", "inactive_neighbour"])
def test_a_slots_state_is_its_own_requests(tiny, scenario):
    cfg, mc, params, _ = tiny
    a, b = _tokens(110, 3), _tokens(100, 4)       # a: the longer, earlier request
    pool = _pool(mc)
    _, c1a = _prefill(params, mc, a[:90])
    pool = _insert(pool, c1a, 0, 90)
    if scenario == "reused_after_a_longer_request":
        _, pool = _decode_logits(params, mc, pool, 0, a[90:110])
        pool = serving._reset_slot(pool, 0)
        assert float(jnp.abs(pool.layers["lightning"]["state"][:, 0]).max()) == 0.0
    slot = 1 if scenario == "inactive_neighbour" else 0
    _, c1b = _prefill(params, mc, b[:70])
    pool = _insert(pool, c1b, slot, 70)
    if scenario == "inactive_neighbour":
        row0 = lambda pool: [np.asarray(pool.layers["lightning"]["state"][:, 0])] + [  # noqa: E731
            np.asarray(pool.layers["sparse_attn"][leaf][:, 0, :90 // (4 if leaf == "ck" else 1)]) for leaf in ("k", "v", "ck")]
        before = row0(pool)
    logits, pool = _decode_logits(params, mc, pool, slot, b[70:100])
    want, _ = _reference(tiny, b)
    assert np.abs(np.asarray(logits) - want[70:100]).max() < TOL
    if scenario == "inactive_neighbour":
        assert all((now == was).all() for now, was in zip(row0(pool), before)) and int(pool.lengths[0]) == 90
        logits_a, _ = _decode_logits(params, mc, pool, 0, a[90:110])
        assert np.abs(np.asarray(logits_a) - _reference(tiny, a)[0][90:110]).max() < TOL


# (d) the controls: what is left out must fail the same comparison -------------


@pytest.mark.parametrize("omitted, at_least", [
    ("lightning_state", 0.03),      # a state dropped at a chunk boundary (measured 0.073)
    ("compressed_key", 3e-4),       # one window's compressed key dropped: another block is chosen (8.5e-4)
    ("topk_minus_one", 6e-4),       # 3 blocks attended where the model attends 4 (1.8e-3)
    ("pad_positions_enter_the_state", 0.015),   # what n_valid is there to stop (0.036)
])
def test_control_an_omission_moves_the_logits(tiny, omitted, at_least):
    cfg, mc, params, _ = tiny
    toks = _tokens(100 + 31, 1)
    run = mc
    if omitted == "pad_positions_enter_the_state":
        padded = np.zeros((1, 104), np.int32)
        padded[0, :100] = toks[:100]
        _, c1 = forward_with_cache(params, jnp.asarray(padded), init_cache(mc, 1, LANES, dtype=F32), mc,
                                   compute_dtype=F32)
    elif omitted == "lightning_state":
        _, c1 = _prefill(params, mc, toks[:100],
                         spoil=lambda c1: _with_leaf(c1, "lightning", "state", jnp.zeros_like))
    else:
        _, c1 = _prefill(params, mc, toks[:100])
    pool = _insert(_pool(mc), c1, 1, 100)
    if omitted == "compressed_key":
        pool = _with_leaf(pool, "sparse_attn", "ck", lambda ck: ck.at[:, 1, 5].set(0.0))
    if omitted == "topk_minus_one":
        run = mc.with_(sparse_topk=3)
    logits, _ = _decode_logits(params, run, pool, 1, toks[100:130])
    gap = np.abs(np.asarray(logits) - _reference(tiny, toks)[0][100:130]).max()
    assert gap > at_least > 100 * TOL, gap


def test_control_every_query_attending_its_tiles_union_moves_the_logits(tiny, monkeypatch):
    """The chunk kernel skips by tile and masks by query. A kernel that is fast
    because every query attends whatever SOME query of its tile chose is the
    same kernel handed the tile's union as each query's choice: the logits of
    the positions that select must move by far more than ``TOL`` (measured
    2.2e-3; a position below ``dense_len`` attends everything either way)."""
    cfg, mc, params, _ = tiny
    toks = _tokens(150)
    attend = sparse_block_attention.sparse_chunk_attend

    def union_for_all(qg, k_pool, v_pool, chosen, layer, positions, *, block, **kw):
        B, KV, T, n_blocks = chosen.shape
        tq, _ = sparse_block_attention.chunk_geometry(T, n_blocks, block)
        tiles = jnp.pad(chosen, ((0, 0), (0, 0), (0, -T % tq), (0, 0))).reshape(B, KV, -1, tq, n_blocks)
        union = jnp.repeat(tiles.any(3), tq, axis=2)[:, :, :T]
        return attend(qg, k_pool, v_pool, union, layer, positions, block=block, **kw)

    monkeypatch.setattr(sparse_block_attention, "sparse_chunk_attend", union_for_all)
    logits, _ = forward_with_cache(params, jnp.asarray(toks)[None], init_cache(mc, 1, LANES, dtype=F32), mc,
                                   compute_dtype=F32)
    gap = np.abs(np.asarray(logits[0]) - _reference(tiny, toks)[0][:150]).max(-1)
    assert gap[:64].max() < TOL and gap[64:].max() > 6e-4 > 100 * TOL, (gap[:64].max(), gap[64:].max())


# (e) the engine, end to end -----------------------------------------------------


def test_the_engine_serves_what_the_reference_would(tiny):
    """``ContinuousBatcher`` end to end (admit, chunked prefill with the
    bucket's padding, insert, decode chunks that overshoot, reset, reuse of
    both slots): every served token is the reference's best on the request's
    own history, and the counters say what happened."""
    cfg, mc, params, rparams = tiny
    prompts = [_tokens(n, 10 + i).tolist() for i, n in enumerate((100, 45, 70, 90))]
    wants = [12, 30, 7, 15]
    # the staging row is whole prefill chunks, and a sparse layer's cache whole blocks
    engine = serving.ContinuousBatcher(params, mc, max_slots=2, max_len=LANES, compute_dtype=F32,
                                       prefill_chunk=2 * CHUNK, prefill_pad_to=PAD, chunk_steps=4)
    assert engine.prefill_chunk == 2 * CHUNK
    ids = [engine.submit(p, max_new_tokens=w) for p, w in zip(prompts, wants)]
    for _ in range(400):
        engine.step()
        if all(engine.result(i)["status"] == "done" for i in ids):
            break
    for i, p in zip(ids, prompts):
        served = engine.result(i)["tokens"]
        rows = _reference(tiny, np.asarray(p + served))[0][len(p) - 1:len(p) - 1 + len(served)]
        assert (rows.max(-1) - rows[np.arange(len(served)), served]).max() < 1e-5
    st = engine.stats()
    assert st["state_inserts_total"] == 4 and st["state_resets_total"] == 4
    assert st["recurrent_state_bytes"] == engine._cache.layers["lightning"]["state"].nbytes > 0
    # request 2 (45 + 30) crosses dense_len = 64 while it decodes; 100, 70 and 90 are past it throughout
    computed, sparse = st["decode_tokens_computed_total"], st["decode_tokens_sparse_total"]
    assert 0 < computed - sparse <= 64 - 45 + 4 and sparse >= 11 + 6 + 14 + (45 + 29 - 64)


def test_the_engine_updates_the_lightning_state_in_place_where_the_kernel_engages(tiny, monkeypatch):
    """The tiny model with lightning heads of 128 (a state of whole register
    tiles), served twice by ``ContinuousBatcher``: with the one-pass kernel
    (``ops.ssd_update``) interpreted, and with the XLA step. The same tokens;
    a slot that never decodes keeps the state planted in it bit for bit; and
    ``recurrent_updates_in_place_total`` counts dispatches x the chunk's steps
    x the lightning layers with the kernel, 0 without."""
    mc = family.model_config({**tiny[0], "lightning_head_dim": 128}, "sala-tiny-e128")
    params = tfm.init_params(jax.random.PRNGKey(SEED), mc)
    prompts = [_tokens(n, 20 + i).tolist() for i, n in enumerate((70, 9))]
    n_lightning = layer_state.layer_counts(mc)["lightning"]
    planted = jax.random.normal(jax.random.PRNGKey(1), (n_lightning, mc.lightning_heads, 128, 128))

    def serve(interpret):
        monkeypatch.setattr(ssd_update, "INTERPRET_OFF_TPU", interpret)
        engine = serving.ContinuousBatcher(params, mc, max_slots=3, max_len=LANES, compute_dtype=F32,
                                           prefill_chunk=2 * CHUNK, prefill_pad_to=PAD, chunk_steps=4)
        state = engine._cache.layers["lightning"]["state"]
        engine._cache = dataclasses.replace(engine._cache, layers={
            **engine._cache.layers, "lightning": {"state": state.at[:, 2].set(planted)}})
        dispatches, decode = [], engine._decode
        engine._decode = lambda *a: dispatches.append(1) or decode(*a)
        ids = [engine.submit(p, max_new_tokens=w) for p, w in zip(prompts, (11, 6))]
        for _ in range(100):
            engine.step()
            if all(engine.result(i)["status"] == "done" for i in ids):
                break
        return ([engine.result(i)["tokens"] for i in ids],
                np.asarray(engine._cache.layers["lightning"]["state"][:, 2]),
                engine.stats()["recurrent_updates_in_place_total"], len(dispatches))

    tokens, kept, in_place, dispatches = serve(True)
    assert [len(t) for t in tokens] == [11, 6] and dispatches >= 3
    assert in_place == dispatches * 4 * n_lightning
    assert np.array_equal(kept, np.asarray(planted))
    xla_tokens, xla_kept, xla_in_place, _ = serve(False)
    assert xla_tokens == tokens and xla_in_place == 0 and np.array_equal(xla_kept, np.asarray(planted))


@pytest.mark.parametrize("stack", ["sparse_and_lightning", "attention_only"])
def test_stats_count_the_prompt_positions_ingested_and_those_that_select(tiny, stack):
    """``prefill_tokens_computed_total`` counts every position a prefill chunk
    computed (the bucket's padding among them), ``prefill_tokens_sparse_total``
    those at or past ``sparse_dense_len`` of a stack that has the kind."""
    cfg, mc, params, _ = tiny
    if stack == "attention_only":
        mc = tfm.ModelConfig(name="plain-tiny", arch="llama", vocab_size=512, d_model=32, n_layers=2, n_heads=4,
                             n_kv_heads=2, d_ff=64, max_seq_len=256, norm_eps=1e-6)
        params = tfm.init_params(jax.random.PRNGKey(SEED), mc)
    engine = serving.ContinuousBatcher(params, mc, max_slots=2, max_len=LANES, compute_dtype=F32,
                                       prefill_chunk=2 * CHUNK, prefill_pad_to=PAD, chunk_steps=4)
    st = engine.stats()
    assert st["prefill_tokens_computed_total"] == st["prefill_tokens_sparse_total"] == 0
    # 45 pads to 48, one chunk, all under dense_len = 64; 100 pads to 104: chunks at 0, 48 and 96
    ids = [engine.submit(_tokens(n, 20 + n).tolist(), max_new_tokens=2) for n in (45, 100)]
    for _ in range(50):
        engine.step()
        if all(engine.result(i)["status"] == "done" for i in ids):
            break
    st = engine.stats()
    assert st["prefill_tokens_computed_total"] == 48 + 104
    assert st["prefill_tokens_sparse_total"] == (104 - 64 if stack == "sparse_and_lightning" else 0)


# (f) all four kinds in one stack --------------------------------------------------


def test_a_stack_of_all_four_kinds_walks_scan_layers():
    mc = tfm.ModelConfig(
        name="four-kinds", arch="llama", vocab_size=512, d_model=32, n_layers=6, n_heads=4, n_kv_heads=2,
        d_ff=64, max_seq_len=256, norm_eps=1e-6,
        layer_types=("attention", "mamba", "lightning", "sparse_attention", "sparse_attention", "mamba"),
        ssm_heads=4, ssm_head_dim=16, ssm_state=8, ssm_chunk=16,
        lightning_heads=4, lightning_head_dim=8,
        sparse_kernel_size=8, sparse_kernel_stride=4, sparse_block_size=16, sparse_topk=4,
        sparse_init_blocks=1, sparse_local_blocks=2, sparse_dense_len=64, rope=False)
    assert mc.layer_runs() == (("attn", 0, 1), ("ssm", 0, 1), ("lightning", 0, 1), ("sparse_attn", 0, 2), ("ssm", 1, 1))
    params = tfm.init_params(jax.random.PRNGKey(0), mc)
    assert set(params["layers"]) == {"attn", "ssm", "lightning", "sparse_attn"}
    assert tfm.param_count(mc) == sum(a.size for a in jax.tree.leaves(params))
    toks = jnp.asarray(_tokens(100, 9))[None]
    whole, cache = forward_with_cache(params, toks, init_cache(mc, 1, 128, dtype=F32), mc, compute_dtype=F32)
    assert set(cache.layers) == {"attn", "ssm", "lightning", "sparse_attn"} and bool(jnp.isfinite(whole).all())
    # the same tokens through chunks of 24, then one at a time: one walk, whatever the chunking
    c = init_cache(mc, 1, 128, dtype=F32)
    rows = []
    for t0, t1 in [(0, 24), (24, 48), (48, 72)] + [(t, t + 1) for t in range(72, 100)]:
        lg, c = forward_with_cache(params, toks[:, t0:t1], c, mc, compute_dtype=F32)
        rows.append(lg)
    assert np.abs(np.asarray(jnp.concatenate(rows, axis=1) - whole)).max() < TOL
    pool = jax.eval_shape(lambda: serving.init_slot_cache(mc, 3, 128, F32, prefill_chunk=32))
    vec = lambda dt: jax.ShapeDtypeStruct((3,), dt)  # noqa: E731
    jaxpr = jax.make_jaxpr(partial(serving.decode_step, cfg=mc, compute_dtype=F32))(
        params, vec(jnp.int32), pool, vec(jnp.bool_))
    assert sum(e.primitive.name == "scan" for e in jaxpr.jaxpr.eqns) == 5  # one loop a run


# (g) what is priced is what is allocated ------------------------------------


def test_param_count_and_the_serving_estimate_price_what_is_allocated(tiny):
    from tpu_engine.hbm_estimate import estimate_serving_hbm

    _, mc, params, _ = tiny
    assert tfm.param_count(mc) == sum(a.size for a in jax.tree.leaves(params))
    axes = tfm.logical_axes(mc)
    assert jax.tree.structure(jax.tree.map(lambda a: 0, params)) == jax.tree.structure(
        jax.tree.map(lambda a: 0, axes, is_leaf=lambda a: isinstance(a, tuple)))
    tfm.MODEL_CONFIGS[mc.name] = mc
    try:
        slots, lanes = 1024, 4096
        pool = jax.eval_shape(lambda: serving.init_slot_cache(mc, slots, lanes, jnp.bfloat16, prefill_chunk=64))
        est = estimate_serving_hbm(mc.name, slots, lanes, prefill_chunk=64)
    finally:
        del tfm.MODEL_CONFIGS[mc.name]
    nbytes = lambda arrs: sum(a.size * a.dtype.itemsize for a in arrs)  # noqa: E731
    by_kind = layer_state.state_bytes(mc, slots, lanes, jnp.bfloat16)
    assert by_kind == {kind: nbytes(leaves.values()) for kind, leaves in pool.layers.items()}
    sp = pool.layers["sparse_attn"]
    assert sp["k"].shape == (2, slots, lanes, 32) and sp["ck"].shape == (2, slots, lanes // 4, 32)
    assert est.kv_pool_gib == pytest.approx(by_kind["sparse_attn"] / 2**30, abs=1e-4)
    assert est.recurrent_state_gib == pytest.approx(by_kind["lightning"] / 2**30, abs=1e-4)
    assert pool.layers["lightning"]["state"].dtype == jnp.float32 and sp["ck"].dtype == jnp.bfloat16
    assert est.device_total_gib >= est.params_gib + est.kv_pool_gib + est.recurrent_state_gib


def test_int8_weights_quantise_both_kinds_projections(tiny):
    """``--control 1`` of the benchmark means something only if the new
    kernels are quantised too."""
    from tpu_engine.quant import QuantWeight, quantize_params

    _, mc, params, _ = tiny
    q = quantize_params(params)
    for kind in ("sparse_attn", "lightning"):
        for name in ("q", "k", "v", "o_gate", "o", "gate", "up", "down"):
            assert isinstance(q["layers"][kind][name]["kernel"], QuantWeight), (kind, name)
    assert q["layers"]["lightning"]["decay"].dtype == jnp.float32
    served = tfm.served_format(params, jnp.bfloat16)
    assert served["layers"]["lightning"]["decay"].dtype == jnp.float32
    assert served["layers"]["lightning"]["q"]["kernel"].dtype == jnp.bfloat16
    toks = _tokens(96, 7)
    lg, _ = forward_with_cache(q, jnp.asarray(toks)[None], init_cache(mc, 1, 96, dtype=F32), mc, compute_dtype=F32)
    gap = np.abs(np.asarray(lg[0]) - _reference(tiny, toks)[0][:96]).max()
    assert 50 * TOL < gap < 0.02  # it runs, and it is the lower precision


def test_the_int8_replica_is_built_leaf_by_leaf_with_the_whole_draws_values(tiny, monkeypatch):
    """The float32 tree of the cell's 3.93 B parameters is 15.7 GB: the int8
    build draws a kernel, makes its codes and lets the float32 go, and what it
    holds is ``quantize_params(init_params(key, cfg))`` bit for bit."""
    from tpu_engine.quant import quantize_params
    from tpu_engine.serving_fleet import ServingReplicaSpec, build_replica_engine

    _, mc, params, _ = tiny
    monkeypatch.setitem(tfm.MODEL_CONFIGS, mc.name, mc)
    engine = build_replica_engine(ServingReplicaSpec(
        model_name=mc.name, seed=SEED, max_slots=2, max_len=64, prefill_chunk=32, decode_chunk_steps=4,
        weight_quant="int8"))
    got = jax.tree_util.tree_leaves_with_path(engine.params)
    want = jax.tree_util.tree_leaves_with_path(tfm.served_format(quantize_params(params), jnp.bfloat16))
    assert [p for p, _ in got] == [p for p, _ in want]
    for (path, x), (_, y) in zip(got, want):
        assert x.dtype == y.dtype and np.array_equal(np.asarray(x), np.asarray(y)), jax.tree_util.keystr(path)


def test_off_the_tpu_the_decode_kernel_is_refused_unless_asked_for(monkeypatch):
    assert sparse_block_attention.interpret_here() is True  # this module asked, at its top
    monkeypatch.setattr(sparse_block_attention, "INTERPRET_OFF_TPU", False)
    with pytest.raises(RuntimeError, match="INTERPRET_OFF_TPU"):
        sparse_block_attention.interpret_here()


# (h) what assumes keys and values refuses the stack by name -------------------


def _refusals(mc, params):
    from tpu_engine import disagg, spec_pool
    from tpu_engine.generate import speculative_generate
    from tpu_engine.mesh_runtime import build_mesh
    from tpu_engine.serving_fleet import ServingFleet, ServingReplicaSpec, build_replica_engine
    from tpu_engine.sharding import MeshConfig, Precision, TPUTrainConfig

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
        with pytest.raises(tfm.RecurrentLayersUnsupported, match="lightning") as err:
            _refusals(mc, params)[feature]()
    finally:
        del tfm.MODEL_CONFIGS[mc.name]
    assert mc.name in str(err.value) and err.value.feature


@pytest.mark.parametrize("bad, why", [
    (dict(layer_types=("lightning", "sparse_attention")), "n_layers"),
    (dict(layer_types=("lightning", "sparse_attention", "linear", "lightning")), "n_layers"),
    (dict(sliding_window=16), "sliding window"),
    (dict(lightning_heads=0), "lightning_heads"),
    (dict(lightning_head_dim=7), "even"),
    (dict(layer_indices=(9, 10, 15, 40)), "published index"),
    (dict(layer_indices=(9, 10, 15)), "published index"),
    (dict(published_layers=1, layer_indices=(0, 0, 0, 0)), "depth >= 2"),
    (dict(sparse_kernel_size=6), "multiples"),
    (dict(sparse_block_size=18), "multiples"),
    (dict(sparse_topk=2), "sparse_topk"),
    (dict(sparse_dense_len=48), "sparse_dense_len"),
])
def test_a_pattern_the_program_cannot_run_is_refused_where_it_is_built(tiny, bad, why):
    _, mc, _, _ = tiny
    with pytest.raises(ValueError, match=why):
        tfm.init_params(jax.random.PRNGKey(0), mc.with_(**bad))


def test_a_cache_that_holds_no_whole_blocks_is_refused(tiny):
    _, mc, params, _ = tiny
    with pytest.raises(ValueError, match="whole blocks"):
        forward_with_cache(params, jnp.zeros((1, 8), jnp.int32), init_cache(mc, 1, 72, dtype=F32), mc,
                           compute_dtype=F32)


def test_the_decay_follows_the_published_index_not_the_kept_depth(tiny):
    _, mc, params, _ = tiny
    rates = np.asarray(params["layers"]["lightning"]["decay"])
    assert mc.published_indices("lightning") == (10, 15) and mc.published_layers == 32
    h = np.arange(1, 5)
    for row, at in zip(rates, (10, 15)):
        assert np.allclose(row, 2.0 ** (-8 * h / 4) * (1 - at / 31 + 1e-5), rtol=1e-6)
    moved = tfm.lightning_decay_rates(mc.with_(layer_indices=(0, 1, 2, 3)))
    assert not np.allclose(np.asarray(moved), rates)
    assert mc.residual_scale == pytest.approx(1.4 / 32 ** 0.5)
