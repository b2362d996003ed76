"""A hybrid stack (Mamba-2 layers beside attention layers, a recurrent per-slot
state beside the KV pool) against its plain float32 reference
(``benchmarks/onchip/reference/granitemoehybrid.py``: the recurrence token by
token, no chunking, no cache).

Tiny widths, seeded weights, float32 compute on the CPU. The tolerance on
logits is ``TOL`` = 5e-8 absolute where logits have a spread of 1.7e-3: both
sides are float32 and differ only in the order of their sums (the chunked scan
against the token-by-token recurrence measured 3e-9, so this leaves fifteen
times that). The controls must miss it twenty times over: an SSM state dropped
at a chunk boundary measured 2.6e-6 (the tiny model's state is a small part of
a logit: most of the history it would carry has decayed or sits in the skip
path), dropped convolution inputs 2.8e-4, the prompt's padding fed as real
tokens more.
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

from families import granitemoehybrid as family  # noqa: E402
from reference import granitemoehybrid as ref  # noqa: E402

from tpu_engine import serving  # noqa: E402
from tpu_engine.generate import forward_with_cache, init_cache  # noqa: E402
from tpu_engine.models import transformer as tfm  # noqa: E402
from tpu_engine.ops import ssd_update  # noqa: E402

TOL = 5e-8
SEED = 5
CHUNK, PAD = 32, 16  # prefill chunk = the tiny model's ssm_chunk; prompts pad to 16
F32 = jnp.float32


@pytest.fixture(scope="module")
def tiny():
    """(configuration dict at its rehearsal size, ModelConfig, program params,
    reference params): one seed drawn twice, by the program and by the
    reference, each by its own code."""
    with open(os.path.join(BENCH, "configs", "granite-4.0-h-micro-1chip-serve.json")) as f:
        cfg = json.load(f)
    cfg = {**cfg, **cfg["rehearsal"]}
    mc = family.model_config(cfg, "hybrid-tiny")
    return cfg, mc, tfm.init_params(jax.random.PRNGKey(SEED), mc), ref.init_params(cfg, SEED)


def _tokens(n, stream=0):
    return np.random.default_rng([SEED, stream]).integers(0, 512, n).astype(np.int32)


def _prefill(params, mc, prompt, spoil=None):
    """The batcher's ingestion: the prompt zero-padded to PAD, one CHUNK a call
    through ``serving._prefill_forward`` with the chunk's real length. Returns
    (logits row of the last real token, the single-row cache). ``spoil(c1)``
    runs between chunks (the controls)."""
    n = len(prompt)
    padded = -(-n // PAD) * PAD
    toks = np.zeros((1, padded), np.int32)
    toks[0, :n] = prompt
    c1 = init_cache(mc, 1, -(-padded // CHUNK) * CHUNK, dtype=F32)
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


def _pool(mc, slots=3, lanes=128):
    return serving.init_slot_cache(mc, slots, lanes, F32, prefill_chunk=CHUNK)


def _insert(pool, c1, slot, n):
    return serving._insert_prefill(pool, c1, jnp.int32(slot), jnp.int32(n), False)


def _decode_logits(params, mc, pool, slot, feed, active=None):
    """Teacher-forced decode of ``feed`` in ``slot`` through ``decode_step``;
    other rows are not active unless ``active`` says so. Returns (logits
    [len(feed), V], pool)."""
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


def _reference_rows(tiny, tokens, first, count):
    cfg, _, _, rparams = tiny
    return np.asarray(ref.forward_logits(rparams, tokens, cfg))[first:first + count]


# (a) the cached forward over a whole prompt ---------------------------------


@pytest.mark.parametrize("n", [23, 100])  # inside one SSD chunk; four chunks, the last padded
def test_forward_with_cache_over_a_whole_prompt_equals_the_reference(tiny, n):
    cfg, mc, params, _ = tiny
    toks = _tokens(n)
    logits, cache = forward_with_cache(params, jnp.asarray(toks)[None], init_cache(mc, 1, 128, dtype=F32),
                                       mc, compute_dtype=F32)
    assert np.abs(np.asarray(logits[0]) - _reference_rows(tiny, toks, 0, n)).max() < TOL
    assert cache.layers["attn"]["k"].shape[0] == mc.n_attn_layers == 1
    assert cache.layers["ssm"]["ssm"].shape[0] == mc.n_ssm_layers == 3


# (b) chunked prefill with pad positions, insert, >= 40 decode steps ---------


@pytest.mark.parametrize("n_prompt", [70, 45])  # 70 pads to 80: chunks of 32, 32, 16 with 6 real
def test_chunked_prefill_insert_and_40_decode_steps_equal_the_reference(tiny, n_prompt):
    cfg, mc, params, _ = tiny
    toks = _tokens(n_prompt + 41, 1)
    prompt, feed = toks[:n_prompt], toks[n_prompt - 1:n_prompt + 40]
    last, c1 = _prefill(params, mc, prompt)
    pool = _insert(_pool(mc), c1, 1, n_prompt)
    # decode_step's input is the row's last token, whose keys/state are not yet
    # in the pool: the insert holds the prompt, so feed starts after it.
    logits, pool = _decode_logits(params, mc, pool, 1, feed[1:])
    want = _reference_rows(tiny, toks, n_prompt - 1, 41)
    assert np.abs(np.asarray(last) - want[0]).max() < TOL          # prefill's own row
    assert np.abs(np.asarray(logits) - want[1:]).max() < TOL       # 40 decode steps
    assert int(pool.lengths[1]) == n_prompt + 40 and int(pool.lengths[0]) == 0


def test_40_steps_through_decode_chunk_leave_the_state_the_reference_implies(tiny):
    """Greedy through ``decode_chunk`` (5 dispatches of 8, tokens fed back
    inside the scan), then one more step's logits against the reference on the
    prompt and what was generated."""
    cfg, mc, params, _ = tiny
    prompt = _tokens(45, 2)
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
    want = _reference_rows(tiny, seq, len(prompt) - 1, 42)
    assert np.abs(np.asarray(logits[0]) - want[41]).max() < TOL
    # and every token it fed itself was the reference's own best (to rounding)
    assert (want[np.arange(41), generated] >= want[:41].max(-1) - TOL).all()


# (c) a reused slot, an inactive neighbour, a row that finishes mid-chunk ------


@pytest.mark.parametrize("scenario", ["reused_after_a_longer_request", "inactive_neighbour",
                                      "finished_mid_chunk_then_reused"])
def test_a_slots_recurrent_state_is_its_own_requests(tiny, scenario):
    cfg, mc, params, _ = tiny
    a, b = _tokens(90, 3), _tokens(60, 4)       # a: the longer, earlier request
    pool = _pool(mc)
    _, c1a = _prefill(params, mc, a[:70])
    pool = _insert(pool, c1a, 0, 70)
    if scenario == "reused_after_a_longer_request":
        _, pool = _decode_logits(params, mc, pool, 0, a[70:90])
        pool = serving._reset_slot(pool, 0)
        rec = pool.layers["ssm"]
        assert float(jnp.abs(rec["ssm"][:, 0]).max()) == 0.0 and float(jnp.abs(rec["conv"][:, 0]).max()) == 0.0
        assert float(jnp.abs(rec["ssm"][:, 1]).max()) == 0.0  # nothing leaked to a neighbour either
    elif scenario == "finished_mid_chunk_then_reused":
        # The request in slot 0 "finishes" after 3 of a chunk's 8 steps: the
        # device runs all 8 (static shapes), so the slot's state overshoots;
        # the host resets the slot and the next insert overwrites all of it.
        chunk = jax.jit(partial(serving.decode_chunk, cfg=mc, n_steps=8, compute_dtype=F32))
        z = jnp.zeros(3, jnp.int32)
        _, pool = chunk(params, jnp.asarray([int(a[70]), 0, 0], jnp.int32), pool,
                        jnp.asarray([True, False, False]), jnp.zeros(3, F32), z, z, jax.random.PRNGKey(0))
        pool = serving._reset_slot(pool, 0)
    slot = 1 if scenario == "inactive_neighbour" else 0
    _, c1b = _prefill(params, mc, b[:30])
    pool = _insert(pool, c1b, slot, 30)
    if scenario == "inactive_neighbour":
        # slot 0 holds request a and is NOT active while b decodes beside it:
        # its state must not move, and when it resumes its logits are right.
        row0 = lambda pool: (np.asarray(pool.layers["ssm"]["ssm"][:, 0]), np.asarray(pool.layers["ssm"]["conv"][:, 0]),  # noqa: E731
                             np.asarray(pool.layers["attn"]["k"][:, 0, :70]))
        before = row0(pool)
    logits, pool = _decode_logits(params, mc, pool, slot, b[30:60])
    assert np.abs(np.asarray(logits) - _reference_rows(tiny, b, 30, 30)).max() < TOL
    if scenario == "inactive_neighbour":
        assert all((now == was).all() for now, was in zip(row0(pool), before)) and int(pool.lengths[0]) == 70
        logits_a, _ = _decode_logits(params, mc, pool, 0, a[70:90])
        assert np.abs(np.asarray(logits_a) - _reference_rows(tiny, a, 70, 20)).max() < TOL


# (d) the controls: a dropped state must fail the same comparison --------------


@pytest.mark.parametrize("dropped", ["ssm", "conv", "pad_positions_enter_the_state"])
def test_control_a_state_dropped_at_a_chunk_boundary_fails(tiny, dropped):
    cfg, mc, params, _ = tiny
    toks = _tokens(70 + 11, 1)
    if dropped == "pad_positions_enter_the_state":
        # the prompt's padding fed as if real: what n_valid is there to stop
        padded = np.zeros((1, 80), np.int32)
        padded[0, :70] = toks[:70]
        _, c1 = forward_with_cache(params, jnp.asarray(padded), init_cache(mc, 1, 96, dtype=F32), mc,
                                   compute_dtype=F32)
    else:
        zero = lambda c1: dataclasses.replace(c1, layers={**c1.layers, "ssm": {  # noqa: E731
            **c1.layers["ssm"], dropped: jnp.zeros_like(c1.layers["ssm"][dropped])}})
        _, c1 = _prefill(params, mc, toks[:70], spoil=zero)
    pool = _insert(_pool(mc), c1, 1, 70)
    logits, _ = _decode_logits(params, mc, pool, 1, toks[70:80])
    assert np.abs(np.asarray(logits) - _reference_rows(tiny, toks, 70, 10)).max() > 20 * TOL


def test_the_engine_passes_each_chunks_real_length(tiny):
    """``ContinuousBatcher`` end to end (admit, chunked prefill with the
    bucket's padding, insert, decode chunks that overshoot, reset, reuse of
    both slots): every served token is the reference's best on the request's
    own history. The embedding is shrunk on both sides so that the layers, not
    the tied table, decide a token, and a state gone wrong moves it; with the
    chunk's real length withheld the same run fails."""
    cfg, mc, params, rparams = tiny
    shrink = lambda p: {**p, "embed": {"embedding": p["embed"]["embedding"] * 0.02}}  # noqa: E731
    params, rparams = shrink(params), shrink(rparams)
    prompts = [_tokens(n, 10 + i).tolist() for i, n in enumerate((70, 45, 9, 33))]
    wants = [12, 21, 7, 15]

    def worst_gap(engine):
        ids = [engine.submit(p, max_new_tokens=w) for p, w in zip(prompts, wants)]
        for _ in range(300):
            engine.step()
            if all(engine.result(i)["status"] == "done" for i in ids):
                break
        worst = 0.0
        for i, p in zip(ids, prompts):
            served = engine.result(i)["tokens"]
            rows = np.asarray(ref.forward_logits(rparams, np.asarray(p + served), cfg))[len(p) - 1:-1]
            worst = max(worst, float((rows.max(-1) - rows[np.arange(len(served)), served]).max()))
        return worst

    make = lambda: serving.ContinuousBatcher(  # noqa: E731
        params, mc, max_slots=2, max_len=128, compute_dtype=F32, prefill_chunk=CHUNK,
        prefill_pad_to=PAD, chunk_steps=4)
    engine = make()
    assert worst_gap(engine) < 1e-7
    st = engine.stats()
    assert st["state_inserts_total"] == 4 and st["state_resets_total"] == 4
    assert st["recurrent_state_bytes"] == sum(a.nbytes for a in engine._cache.layers["ssm"].values()) > 0

    blind = make()
    fn = blind._prefill_fn
    blind._prefill_fn = lambda p, chunk, c1, row, n_valid: fn(p, chunk, c1, row)
    assert worst_gap(blind) > 1e-5


def test_the_engine_decodes_in_place_where_the_kernel_engages(tiny, monkeypatch):
    """The tiny model with a state of whole register tiles (``[16,128]`` a
    head), served twice by ``ContinuousBatcher``: with the one-pass kernel
    (``ops.ssd_update``) interpreted, and with the XLA step. The same tokens;
    a slot that never decodes keeps the state planted in it bit for bit; and
    ``recurrent_updates_in_place_total`` counts dispatches x the chunk's steps
    x the Mamba-2 layers with the kernel, 0 without."""
    mc = family.model_config({**tiny[0], "mamba_d_state": 128}, "hybrid-tiny-n128")
    params = tfm.init_params(jax.random.PRNGKey(SEED), mc)
    params = {**params, "embed": {"embedding": params["embed"]["embedding"] * 0.02}}
    prompts = [_tokens(n, 20 + i).tolist() for i, n in enumerate((40, 9))]
    planted = jax.random.normal(jax.random.PRNGKey(1), (mc.n_ssm_layers, mc.ssm_heads, mc.ssm_head_dim, 128))

    def serve(interpret):
        monkeypatch.setattr(ssd_update, "INTERPRET_OFF_TPU", interpret)
        engine = serving.ContinuousBatcher(params, mc, max_slots=3, max_len=128, compute_dtype=F32,
                                           prefill_chunk=CHUNK, prefill_pad_to=PAD, chunk_steps=4)
        ssm = engine._cache.layers["ssm"]
        engine._cache = dataclasses.replace(engine._cache, layers={
            **engine._cache.layers, "ssm": {**ssm, "ssm": ssm["ssm"].at[:, 2].set(planted)}})
        dispatches, decode = [], engine._decode
        engine._decode = lambda *a: dispatches.append(1) or decode(*a)
        ids = [engine.submit(p, max_new_tokens=w) for p, w in zip(prompts, (11, 6))]
        for _ in range(100):
            engine.step()
            if all(engine.result(i)["status"] == "done" for i in ids):
                break
        return ([engine.result(i)["tokens"] for i in ids], np.asarray(engine._cache.layers["ssm"]["ssm"][:, 2]),
                engine.stats()["recurrent_updates_in_place_total"], len(dispatches))

    tokens, kept, in_place, dispatches = serve(True)
    assert [len(t) for t in tokens] == [11, 6] and dispatches >= 3
    assert in_place == dispatches * 4 * mc.n_ssm_layers
    assert np.array_equal(kept, np.asarray(planted))
    xla_tokens, xla_kept, xla_in_place, _ = serve(False)
    assert xla_tokens == tokens and xla_in_place == 0 and np.array_equal(xla_kept, np.asarray(planted))


# (e) one kind of layer is the parent's single scan ----------------------------


@pytest.mark.parametrize("program", ["decode_step", "prefill"])
def test_a_pattern_of_one_kind_lowers_to_the_single_scan(program):
    plain = tfm.MODEL_CONFIGS["gpt-tiny"].with_(sliding_window=0)
    patterned = plain.with_(layer_types=("attention",) * plain.n_layers)
    assert not patterned.is_hybrid and patterned.n_attn_layers == plain.n_layers

    def lowered(mc):
        params = jax.eval_shape(lambda k: tfm.init_params(k, mc, dtype=jnp.bfloat16), jax.random.PRNGKey(0))
        if program == "decode_step":
            pool = jax.eval_shape(lambda: serving.init_slot_cache(mc, 4, 64, jnp.bfloat16, prefill_chunk=32))
            vec = lambda dt: jax.ShapeDtypeStruct((4,), dt)  # noqa: E731
            return jax.make_jaxpr(partial(serving.decode_step, cfg=mc))(params, vec(jnp.int32), pool, vec(jnp.bool_))
        c1 = jax.eval_shape(lambda: init_cache(mc, 1, 64))
        return jax.make_jaxpr(partial(serving._prefill_forward, cfg=mc, compute_dtype=jnp.bfloat16))(
            params, jax.ShapeDtypeStruct((1, 32), jnp.int32), c1, jax.ShapeDtypeStruct((), jnp.int32))

    a, b = lowered(plain), lowered(patterned)
    assert str(a) == str(b)
    assert sum(e.primitive.name == "scan" for e in a.jaxpr.eqns) == 1  # the one layer scan


def test_a_hybrid_is_scanned_by_runs_not_unrolled(tiny):
    _, mc, params, _ = tiny
    assert mc.layer_runs() == (("ssm", 0, 2), ("attn", 0, 1), ("ssm", 2, 1))
    pool = jax.eval_shape(lambda: _pool(mc))
    vec = lambda dt: jax.ShapeDtypeStruct((3,), dt)  # noqa: E731
    jaxpr = jax.make_jaxpr(partial(serving.decode_step, cfg=mc, compute_dtype=F32))(
        params, vec(jnp.int32), pool, vec(jnp.bool_))
    assert sum(e.primitive.name == "scan" for e in jaxpr.jaxpr.eqns) == 3  # one loop a run


# (f) what is priced is what is allocated ------------------------------------


def test_param_count_and_the_serving_estimate_price_what_is_allocated(tiny):
    from tpu_engine.hbm_estimate import estimate_serving_hbm

    _, mc, params, _ = tiny
    assert tfm.param_count(mc) == sum(a.size for a in jax.tree.leaves(params))
    axes = tfm.logical_axes(mc)
    assert jax.tree.structure(jax.tree.map(lambda a: 0, params)) == jax.tree.structure(
        jax.tree.map(lambda a: 0, axes, is_leaf=lambda a: isinstance(a, tuple)))
    # Shapes only: a pool big enough for GiB rounded to 1e-4 to mean something.
    tfm.MODEL_CONFIGS[mc.name] = mc
    try:
        slots, lanes = 4096, 4096
        pool = jax.eval_shape(lambda: serving.init_slot_cache(mc, slots, lanes, jnp.bfloat16, prefill_chunk=CHUNK))
        est = estimate_serving_hbm(mc.name, slots, lanes, prefill_chunk=CHUNK)
    finally:
        del tfm.MODEL_CONFIGS[mc.name]
    nbytes = lambda *arrs: sum(a.size * a.dtype.itemsize for a in arrs) / 2**30  # noqa: E731
    kv, rec = pool.layers["attn"], pool.layers["ssm"]
    assert est.kv_pool_gib == pytest.approx(nbytes(kv["k"], kv["v"]), abs=1e-4)
    assert est.recurrent_state_gib == pytest.approx(nbytes(rec["ssm"], rec["conv"]), abs=1e-4)
    assert est.recurrent_state_gib > 0.09 and rec["ssm"].dtype == jnp.float32 and rec["conv"].dtype == jnp.bfloat16
    assert est.device_total_gib >= est.params_gib + est.kv_pool_gib + est.recurrent_state_gib


def test_int8_weights_quantise_the_mixers_projections(tiny):
    """``--control 1`` of the benchmark means something only if the new
    kernels are quantised too."""
    from tpu_engine.quant import QuantWeight, quantize_params

    _, mc, params, _ = tiny
    q = quantize_params(params)
    for kind, names in (("ssm", ("in_proj", "out_proj", "gate", "up", "down")),
                        ("attn", ("q", "k", "v", "o", "gate", "up", "down"))):
        for name in names:
            assert isinstance(q["layers"][kind][name]["kernel"], QuantWeight), (kind, name)
    assert q["layers"]["ssm"]["A_log"].dtype == jnp.float32
    toks = _tokens(40, 7)
    lg, _ = forward_with_cache(q, jnp.asarray(toks)[None], init_cache(mc, 1, 64, dtype=F32), mc, compute_dtype=F32)
    gap = np.abs(np.asarray(lg[0]) - _reference_rows(tiny, toks, 0, 40)).max()
    assert 1e-5 < gap < 0.01  # it runs, and it is the lower precision


# (g) what assumes keys and values refuses a recurrent stack by name -----------


def _refusals(mc, params):
    from tpu_engine import disagg, spec_pool
    from tpu_engine.generate import speculative_generate
    from tpu_engine.serving_fleet import ServingFleet, ServingReplicaSpec
    from tpu_engine.sharding import Precision, TPUTrainConfig

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
        "training": lambda: __import__("tpu_engine.train", fromlist=["x"]).build_train_program(
            TPUTrainConfig(model_name=mc.name, precision=Precision.FP32), model_cfg=mc),
        "cacheless_forward": lambda: tfm.forward(params, jnp.zeros((1, 8), jnp.int32), mc),
    }


@pytest.mark.parametrize("feature", ["prefix_cache", "hold_kv", "submit_prefilled", "extract_slot_kv",
                                     "disagg_fleet", "host_kv_tier", "speculative_engine", "speculative_fleet",
                                     "decode_verify", "speculative_generate", "int8_kv_pool", "int8_kv_cache",
                                     "training", "cacheless_forward"])
def test_what_assumes_keys_and_values_refuses_recurrent_layers_by_name(tiny, feature):
    _, mc, params, _ = tiny
    tfm.MODEL_CONFIGS[mc.name] = mc
    try:
        with pytest.raises(tfm.RecurrentLayersUnsupported, match="recurrent") as err:
            _refusals(mc, params)[feature]()
    finally:
        del tfm.MODEL_CONFIGS[mc.name]
    assert mc.name in str(err.value) and err.value.feature


@pytest.mark.parametrize("bad, why", [
    (dict(layer_types=("mamba", "attention")), "n_layers"),
    (dict(layer_types=("mamba", "attention", "linear", "mamba")), "n_layers"),
    (dict(sliding_window=16), "sliding window"),
    (dict(n_experts=4, top_k=5), "top_k"),  # a mixture is admitted since PR 35; more experts a token than the router has is not
    (dict(ssm_groups=2), "group"),
    (dict(ssm_state=0), "ssm_heads"),
])
def test_a_pattern_the_program_cannot_run_is_refused_where_it_is_built(tiny, bad, why):
    _, mc, _, _ = tiny
    with pytest.raises(ValueError, match=why):
        tfm.init_params(jax.random.PRNGKey(0), mc.with_(**bad))


def test_multipliers_no_rotation_and_a_tied_head_without_a_new_arch():
    """The scalar fields alone, on an attention-only stack: the cached forward
    agrees with the cache-less one (which training uses), there is no
    ``lm_head``, and each multiplier does what its name says."""
    base = tfm.MODEL_CONFIGS["gpt-tiny"].with_(sliding_window=0)
    mc = base.with_(embed_scale=12.0, residual_scale=0.22, logits_divisor=8.0, attn_scale=1 / 64,
                    rope=False, tie_head=True)
    params = tfm.init_params(jax.random.PRNGKey(1), mc)
    assert "lm_head" not in params and tfm.param_count(mc) == sum(a.size for a in jax.tree.leaves(params))
    toks = jnp.asarray(_tokens(24, 8))[None]
    full = tfm.forward(params, toks, mc, compute_dtype=F32)
    cached, _ = forward_with_cache(params, toks, init_cache(mc, 1, 32, dtype=F32), mc, compute_dtype=F32)
    assert np.abs(np.asarray(full - cached)).max() < 1e-6  # logits of order 1 here
    undivided = tfm.forward(params, toks, mc.with_(logits_divisor=1.0), compute_dtype=F32)
    assert np.allclose(np.asarray(undivided) / 8.0, np.asarray(full), atol=1e-7)
    assert np.abs(np.asarray(tfm.forward(params, toks, mc.with_(residual_scale=1.0), compute_dtype=F32) - full)).max() > 1e-3
