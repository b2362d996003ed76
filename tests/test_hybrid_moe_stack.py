"""A hybrid stack whose block after every mixer is a mixture of experts of
which this tree holds a stated share, plus a shared expert
(granite-4.0-h-small's recipe), against its plain float32 reference
(``benchmarks/onchip/reference/granitemoehybrid_moe.py``: the router over all
experts, the held experts one at a time, no cache, no batching).

Tiny widths (the configuration file's rehearsal size: 8 experts, 3 a token, 4
held, expert width 32, shared width 48, both kinds of mixer), seeded weights,
logits compared, never sampled tokens. Tolerances, each with its reason:

- ``TOL`` = 5e-8 absolute, float32 against float32, where logits have a spread
  of 8e-4: the two sides differ only in the order of their sums (measured
  1.4e-9 over 100 positions; this leaves thirty times that). A position whose
  k-th and (k+1)-th router logits lie within ``TIE`` = 1e-6 of each other in
  some layer is left out (rounding may pick the other expert there, and top-k
  is not continuous); the tests say how many were, and none is at these seeds.
- ``BF16_RMS`` = 0.0107 of the logits' spread, for the root-mean-square
  error of 41 rows of logits (chunked prefill, insert, 40 decode steps): a
  bfloat16 tree under bfloat16 compute measured 0.0085 to 0.0088 over three
  token streams, the int8 control (codes of the float32 draw, bfloat16
  compute, as ``--control 1`` runs) 0.0129 to 0.0142, so the control misses
  what bfloat16 meets with a fifth of room on either side. The two lie this
  close by nature: a per-channel int8 code is 1/254 of its column's largest
  weight, a bfloat16 weight 1/512 of itself. The largest single error swings
  twice as much from stream to stream and is not compared.
- Through ``ContinuousBatcher.step`` itself only served tokens come out, so
  there each served token's reference logit is held against the reference's
  best: 1e-7 in float32, 1e-5 in bfloat16 (measured 2e-7: at these widths the
  best token leads by more than bfloat16 moves it).
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
from jax import lax

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks", "onchip")
for p in (ROOT, BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)

from families import granitemoehybrid_moe as family  # noqa: E402
from reference import granitemoehybrid_moe as ref  # noqa: E402

from tpu_engine import serving  # noqa: E402
from tpu_engine.generate import _mlp_block, _moe_mlp_decode, forward_with_cache, init_cache  # noqa: E402
from tpu_engine.models import transformer as tfm  # noqa: E402

TOL, TIE, BF16_RMS = 5e-8, 1e-6, 0.0107
SEED = 5
CHUNK, PAD = 32, 16
F32, BF16 = jnp.float32, jnp.bfloat16


def _file_config():
    with open(os.path.join(BENCH, "configs", "granite-4.0-h-small-1chip-serve.json")) as f:
        cfg = json.load(f)
    return {**cfg, **cfg["rehearsal"]}


def _share(cfg, first, count):
    return {**cfg, "first_local_expert": first, "num_local_experts": count}


@pytest.fixture(scope="module")
def tiny():
    """(configuration dict at its rehearsal size, ModelConfig, program params,
    reference params): one seed drawn twice, by the program and by the
    reference, each by its own code. Experts 0-3 of 8 are held."""
    cfg = _file_config()
    mc = family.model_config(cfg, "hybrid-moe-tiny")
    assert (mc.n_experts, mc.top_k, mc.experts_first, mc.n_experts_held, mc.shared_d_ff, mc.d_ff) == (8, 3, 0, 4, 48, 32)
    assert set(mc.layer_types) == {"mamba", "attention"}
    return cfg, mc, tfm.init_params(jax.random.PRNGKey(SEED), mc), ref.init_params(cfg, SEED)


def _tokens(n, stream=0):
    return np.random.default_rng([SEED, stream]).integers(0, 512, n).astype(np.int32)


def _reference(tiny, tokens):
    """(logits [S, V], decided [S]: no layer's routing is a near-tie there)."""
    cfg, _, _, rparams = tiny
    logits, margin = ref.forward_logits(rparams, tokens, cfg)
    return np.asarray(logits), np.asarray(margin) >= TIE


def _serve(params, mc, dtype, prompts, wants, **kw):
    """The prompts through ``ContinuousBatcher.step`` itself; (served tokens
    per request, the engine)."""
    engine = serving.ContinuousBatcher(params, mc, max_slots=2, max_len=128, compute_dtype=dtype,
                                       prefill_chunk=CHUNK, prefill_pad_to=PAD, chunk_steps=4, **kw)
    ids = [engine.submit(p, max_new_tokens=w) for p, w in zip(prompts, wants)]
    for _ in range(400):
        engine.step()
        if all(engine.result(i)["status"] == "done" for i in ids):
            break
    return [engine.result(i)["tokens"] for i in ids], engine


PROMPTS, WANTS = (70, 45, 9, 33), (12, 21, 7, 15)


def _gaps(tiny, rparams, prompts, served):
    """How far each served token's reference logit lies under the reference's
    best, at the decided positions, and how many positions were left out."""
    cfg = tiny[0]
    gaps, left_out = [], 0
    for p, s in zip(prompts, served):
        logits, margin = ref.forward_logits(rparams, np.asarray(p + s), cfg)
        rows = np.asarray(logits)[len(p) - 1:-1]
        decided = np.asarray(margin)[len(p) - 1:-1] >= TIE
        left_out += int((~decided).sum())
        gaps += (rows.max(-1) - rows[np.arange(len(s)), s])[decided].tolist()
    return np.asarray(gaps), left_out


# (a) chunked prefill then decode through the batcher, against the full forward --


@pytest.mark.parametrize("n", [23, 100])
def test_forward_with_cache_over_a_whole_prompt_equals_the_reference(tiny, n):
    _, mc, params, _ = tiny
    toks = _tokens(n)
    logits, cache = forward_with_cache(params, jnp.asarray(toks)[None], init_cache(mc, 1, 128, dtype=F32),
                                       mc, compute_dtype=F32)
    want, decided = _reference(tiny, toks)
    assert decided.all()
    assert np.abs(np.asarray(logits[0]) - want).max() < TOL
    assert cache.layers["ssm"]["ssm"].shape[0] == 3 and cache.layers["attn"]["k"].shape[0] == 1


def _cached_logits(params, mc, dtype, toks, n_prompt):
    """The batcher's own steps by hand, so that logits come out: the prompt
    zero-padded to PAD and ingested one CHUNK a call through
    ``serving._prefill_forward`` with the chunk's real length, inserted into
    slot 1 of a pool of 3, then ``toks[n_prompt:]`` teacher-forced through
    ``decode_step``. Returns the logits rows of positions ``n_prompt - 1`` on."""
    params = tfm.served_format(params, dtype)
    padded = -(-n_prompt // PAD) * PAD
    t = np.zeros((1, padded), np.int32)
    t[0, :n_prompt] = toks[:n_prompt]
    c1 = init_cache(mc, 1, -(-padded // CHUNK) * CHUNK, dtype=dtype)
    fn = jax.jit(partial(serving._prefill_forward, cfg=mc, compute_dtype=dtype))
    for t0 in range(0, padded, CHUNK):
        t1 = min(t0 + CHUNK, padded)
        out, c1 = fn(params, jnp.asarray(t[:, t0:t1]), c1, jnp.int32(min(max(n_prompt - 1 - t0, 0), t1 - t0 - 1)),
                     jnp.int32(min(max(n_prompt - t0, 0), t1 - t0)))
        if t0 <= n_prompt - 1 < t1:
            rows = [out]
    pool = serving.init_slot_cache(mc, 3, 128, dtype, prefill_chunk=CHUNK)
    pool = serving._insert_prefill(pool, c1, jnp.int32(1), jnp.int32(n_prompt), False)
    step = jax.jit(partial(serving.decode_step, cfg=mc, compute_dtype=dtype))
    for tok in toks[n_prompt:]:
        lg, pool = step(params, jnp.asarray([0, int(tok), 0], jnp.int32), pool, jnp.asarray([False, True, False]))
        rows.append(lg[1])
    return np.asarray(jnp.stack(rows), np.float32)


def _rms_error(tiny, params, dtype, stream=1, n_prompt=70):
    """Root-mean-square error of 41 rows of cached logits, as a share of the
    reference logits' spread (every position decided)."""
    toks = _tokens(n_prompt + 40, stream)
    want, decided = _reference(tiny, toks)
    assert decided.all()
    want = want[n_prompt - 1:]
    got = _cached_logits(params, tiny[1], dtype, toks, n_prompt)
    return float(np.sqrt(np.mean(np.square(got - want))) / want.std()), float(np.abs(got - want).max())


@pytest.mark.parametrize("n_prompt", [70, 45])  # 70 pads to 80: chunks of 32, 32, 16 with 6 real
def test_chunked_prefill_insert_and_40_decode_steps_equal_the_reference(tiny, n_prompt):
    _, worst = _rms_error(tiny, tiny[2], F32, n_prompt=n_prompt)
    assert worst < TOL


def test_bfloat16_meets_its_tolerance(tiny):
    rms, _ = _rms_error(tiny, tiny[2], BF16)
    assert 0.004 < rms < BF16_RMS


@pytest.mark.parametrize("dtype,limit", [(F32, 1e-7), (BF16, 1e-5)], ids=["float32", "bfloat16"])
def test_the_batcher_serves_what_the_reference_ranks_best(tiny, dtype, limit):
    """``ContinuousBatcher`` end to end (admit, chunked prefill with the
    bucket's padding, insert, decode chunks that overshoot, reset, reuse of
    both slots): every served token is the reference's best on the request's
    own history, to the dtype's tolerance. The embedding is shrunk on both
    sides so that the layers, not the tied table, decide a token."""
    _, mc, params, rparams = tiny
    shrink = lambda p: {**p, "embed": {"embedding": p["embed"]["embedding"] * 0.02}}  # noqa: E731
    params, rparams = shrink(params), shrink(rparams)
    prompts = [_tokens(n, 10 + i).tolist() for i, n in enumerate(PROMPTS)]
    served, engine = _serve(params, mc, dtype, prompts, WANTS)
    assert [len(s) for s in served] == list(WANTS)
    gaps, left_out = _gaps(tiny, rparams, prompts, served)
    assert left_out == 0 and len(gaps) == sum(WANTS)
    assert gaps.max() < limit


# (b) the shares add up ------------------------------------------------------


def _layer_input(tiny, n=64):
    """A layer's normed input [n, D] and the first Mamba-2 layer's weights
    drawn for the shares [0,4), [4,8) and for all eight experts."""
    cfg = tiny[0]
    h = jax.random.normal(jax.random.PRNGKey(3), (n, cfg["hidden_size"]), F32)
    h = h * lax.rsqrt(jnp.mean(jnp.square(h), -1, keepdims=True))
    shares = {k: _share(cfg, *k) for k in ((0, 4), (4, 4), (0, 8))}
    return h, {k: (c, ref.draw_layer(c, SEED, "ssm", 0)) for k, c in shares.items()}


def test_the_shares_routed_parts_and_the_shared_expert_once_are_the_uncut_layer(tiny):
    h, drawn = _layer_input(tiny)
    with jax.default_matmul_precision("highest"):
        parts = [ref.routed_part(h, w, c)[0] for c, w in (drawn[0, 4], drawn[4, 4])]
        uncut_cfg, uncut_w = drawn[0, 8]
        uncut, _ = ref.mixture(h, uncut_w, uncut_cfg)
        both = parts[0] + parts[1] + ref.shared_part(h, uncut_w)
    # float32 sums in another order: 36 terms of 1e-3 each
    np.testing.assert_allclose(np.asarray(both), np.asarray(uncut), atol=2e-9, rtol=0)
    assert float(jnp.abs(parts[0]).max()) > 1e-4 and float(jnp.abs(parts[1]).max()) > 1e-4


@pytest.mark.parametrize("share", [(0, 8), (0, 4), (4, 4)], ids=["all-held", "first-half", "second-half"])
def test_the_programs_own_block_is_the_references_for_the_share_it_is_told(tiny, share):
    """``generate._mlp_block`` on one layer of the program's own draw for a
    share: x + r * (held experts' part + shared expert), the reference's."""
    cfg = _share(tiny[0], *share)
    mc = family.model_config(cfg, "share")
    assert (mc.experts_first, mc.n_experts_held, mc.n_experts) == (share[0], share[1], 8)
    params = tfm.init_params(jax.random.PRNGKey(SEED), mc)
    lp = jax.tree.map(lambda a: a[0], params["layers"]["ssm"])
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 32, 64), F32)
    with jax.default_matmul_precision("highest"):
        got = _mlp_block(x, lp, mc)
        w = ref.draw_layer(cfg, SEED, "ssm", 0)
        flat = x.reshape(-1, 64)
        y, _ = ref.mixture(ref.rms_norm(flat, ref.ONE, cfg["rms_norm_eps"]), w, cfg)
        want = (flat + cfg["residual_multiplier"] * y).reshape(x.shape)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6, rtol=0)  # x is O(1): float32 rounding


# (c) the router keeps its width and its top-k whatever is held ---------------


def test_the_router_keeps_its_width_and_a_token_with_no_held_expert_gets_the_shared_expert_alone(tiny):
    cfg, mc, params, _ = tiny
    lp = jax.tree.map(lambda a: a[0], params["layers"]["ssm"])
    assert lp["router"]["kernel"].shape == (64, 8) and lp["gate"]["kernel"].shape == (4, 64, 32)
    h = jax.random.normal(jax.random.PRNGKey(6), (1, 400, 64), F32)
    idx, gates, _ = ref.route(h[0], lp["router"]["kernel"], mc.top_k)
    assert idx.shape == (400, 3)
    np.testing.assert_allclose(np.asarray(gates.sum(-1)), 1.0, atol=1e-6)
    none_held = np.asarray((idx >= 4).all(-1))
    assert 5 < none_held.sum() < 100  # C(4,3) / C(8,3) = 7 % of tokens
    out, counts = _moe_mlp_decode(h, lp, mc, jnp.ones((1, 400), bool))
    shared = ref.shared_part(h[0], {k: lp[k]["kernel"] for k in ("shared_gate", "shared_up", "shared_down")})
    np.testing.assert_array_equal(np.asarray(out[0])[none_held], np.asarray(shared)[none_held])
    assert not np.array_equal(np.asarray(out[0])[~none_held], np.asarray(shared)[~none_held])
    # the counts are the hand count of the router's choices
    held = np.asarray(idx < 4)
    assert counts.tolist() == [400 * 3, int(held.sum()), len(set(np.asarray(idx)[held].tolist())),
                               400 * 4]  # the masked form's rows: every real position x the 4 held


# (d) an expert's weights depend on (seed, layer, expert index) only ----------


def test_a_share_holds_the_uncut_draws_experts_bit_for_bit(tiny):
    cfg = tiny[0]
    key = jax.random.PRNGKey(SEED)
    uncut = tfm.init_params(key, family.model_config(_share(cfg, 0, 8), "uncut"))
    upper = tfm.init_params(key, family.model_config(_share(cfg, 4, 4), "upper"))
    for kind in ("ssm", "attn"):
        for name in ("gate", "up", "down"):
            whole, part = uncut["layers"][kind][name]["kernel"], upper["layers"][kind][name]["kernel"]
            assert part.shape[1] == 4 and whole.shape[1] == 8
            np.testing.assert_array_equal(np.asarray(whole[:, 4:]), np.asarray(part))
            assert not np.array_equal(np.asarray(whole[:, :4]), np.asarray(part))
        for name in ("router", "shared_gate", "shared_down"):  # replicated on every share
            np.testing.assert_array_equal(np.asarray(uncut["layers"][kind][name]["kernel"]),
                                          np.asarray(upper["layers"][kind][name]["kernel"]))
    # and the reference draws the same numbers by its own code (to the last
    # bit: its scale multiplies in a program of its own, the program's inside the draw's)
    w = ref.draw_layer(_share(cfg, 4, 4), SEED, "ssm", 2)
    for name in ("gate", "router"):
        np.testing.assert_allclose(np.asarray(w[name]), np.asarray(upper["layers"]["ssm"][name]["kernel"][2]),
                                   rtol=2e-7, atol=0)


# (e) the int8 control walks the new leaves and can fail ----------------------


def test_int8_weights_quantise_the_experts_and_the_shared_expert_and_miss_bfloat16s_tolerance(tiny):
    from tpu_engine.quant import QuantWeight, quantize_params

    _, mc, _, _ = tiny
    q = quantize_params(tfm.init_params(jax.random.PRNGKey(SEED), mc, deferred=True))
    q = tfm.draw_deferred(q)
    for kind in ("ssm", "attn"):
        for name in ("gate", "up", "down", "shared_gate", "shared_up", "shared_down"):
            assert isinstance(q["layers"][kind][name]["kernel"], QuantWeight), (kind, name)
        assert not isinstance(q["layers"][kind]["router"]["kernel"], QuantWeight)
    assert q["layers"]["ssm"]["gate"]["kernel"].q.shape == (3, 4, 64, 32)
    rms, _ = _rms_error(tiny, q, BF16)
    assert BF16_RMS < rms < 2 * BF16_RMS  # outside bfloat16's tolerance, inside int8's own


# (f) what is counted is what is allocated; the counters count the router's choices --


def test_param_count_the_estimate_and_weight_bytes_price_the_held_share(tiny):
    from tpu_engine.hbm_estimate import estimate_serving_hbm

    _, mc, params, _ = tiny
    n = sum(a.size for a in jax.tree.leaves(params))
    assert tfm.param_count(mc) == n
    uncut = family.model_config(_share(tiny[0], 0, 8), "uncut")
    assert tfm.param_count(uncut) - n == 4 * 4 * 3 * 64 * 32  # four more experts in each of four layers
    axes = tfm.logical_axes(mc)
    assert jax.tree.structure(jax.tree.map(lambda a: 0, params)) == jax.tree.structure(
        jax.tree.map(lambda a: 0, axes, is_leaf=lambda a: isinstance(a, tuple)))
    engine = serving.ContinuousBatcher(params, mc, max_slots=2, max_len=64, compute_dtype=BF16,
                                       prefill_chunk=CHUNK, prefill_pad_to=PAD)
    st = engine.stats()
    assert sum(st["weight_bytes"].values()) == sum(a.nbytes for a in jax.tree.leaves(engine.params))
    small = 3 * 3 * 8 * 4  # A_log, dt_bias, D of three Mamba-2 layers stay float32
    assert st["weight_bytes"] == {"float32": small, "bfloat16": 2 * (n - small // 4)}
    assert st["held_experts"] == 4 and st["shared_expert_bytes"] == 4 * 3 * 64 * 48 * 2
    tfm.MODEL_CONFIGS[mc.name] = mc
    try:
        est = estimate_serving_hbm(mc.name, 2, 64, prefill_chunk=CHUNK)
    finally:
        del tfm.MODEL_CONFIGS[mc.name]
    assert est.params_gib == pytest.approx(2 * n / 2**30, abs=1e-4)


def test_the_counters_count_what_a_hand_count_of_the_routers_choices_gives(tiny):
    """One request through the batcher in float32; the reference's own router
    on the request's history says how many assignments fell on held experts
    and how many held experts each layer-step hit."""
    cfg, mc, params, rparams = tiny
    prompt, want = _tokens(40, 21).tolist(), 9
    (served,), engine = _serve(params, mc, F32, [prompt], [want])
    st = engine.stats()
    # prefill: 40 real positions (48 computed: the bucket's padding is not counted), 4 layers, 2 chunks
    assert st["moe_prefill_layer_steps_total"] == 2 * 4
    assert st["moe_prefill_assignments_total"] == 40 * 3 * 4
    # decode: the first token comes from the prefill's logits; 8 more take 2 dispatches of 4 steps
    assert st["moe_decode_layer_steps_total"] == 2 * 4 * 4
    assert st["moe_decode_assignments_total"] == 8 * 3 * 4

    history = np.asarray(prompt + served)
    x = cfg["embedding_multiplier"] * rparams["embed"]["embedding"][history]
    held_at, hit = np.zeros(len(history), int), {"prefill": 0, "decode": 0}
    seen = {"attn": 0, "ssm": 0}
    with jax.default_matmul_precision("highest"):
        for t in cfg["layer_types"]:
            kind = "ssm" if t == "mamba" else "attn"
            w = ref.draw_layer(cfg, SEED, kind, seen[kind])
            seen[kind] += 1
            mixed = (ref.attention_mixer if kind == "attn" else ref.mamba_mixer)(
                ref.rms_norm(x, ref.ONE, cfg["rms_norm_eps"]), w, ref._mixers(cfg))
            mid = x + cfg["residual_multiplier"] * mixed
            idx, _, margin = ref.route(ref.rms_norm(mid, ref.ONE, cfg["rms_norm_eps"]), w["router"], 3)
            assert float(margin.min()) >= TIE
            idx = np.asarray(idx)
            held_at += (idx < 4).sum(-1)
            hit["prefill"] += sum(len(set(idx[a:b][idx[a:b] < 4].tolist())) for a, b in ((0, 32), (32, 40)))
            hit["decode"] += sum(len(set(idx[p][idx[p] < 4].tolist())) for p in range(40, 48))
            x, _ = ref.layer(x, w, kind, cfg)
    assert st["moe_prefill_assignments_held_total"] == held_at[:40].sum()
    assert st["moe_decode_assignments_held_total"] == held_at[40:48].sum()
    assert st["moe_prefill_experts_hit_total"] == hit["prefill"]
    assert st["moe_decode_experts_hit_total"] == hit["decode"]
    # a model without experts reports none of this
    plain = serving.ContinuousBatcher(tfm.init_params(jax.random.PRNGKey(0), tfm.MODEL_CONFIGS["gpt-tiny"]),
                                      tfm.MODEL_CONFIGS["gpt-tiny"], max_slots=2, max_len=64)
    assert not [k for k in plain.stats() if k.startswith("moe_") or k == "held_experts"]
    assert plain._cache.moe_counts is None


# (g) Mixtral's mixture is the parent's -----------------------------------------


def _parent_moe_mlp_decode(h, layer_params, cfg):
    """``generate._moe_mlp_decode`` as PR 33 had it: every expert's output
    ``[B, T, E, D]`` written, then combined with the gates."""
    E, K = cfg.n_experts, cfg.top_k
    router_logits = jnp.einsum("btd,de->bte", h, layer_params["router"]["kernel"],
                               preferred_element_type=jnp.float32)
    probs = jax.nn.softmax(router_logits, axis=-1)
    kern = lambda name: layer_params[name]["kernel"]  # noqa: E731
    gate = jnp.einsum("btd,edf->btef", h, kern("gate"))
    up = jnp.einsum("btd,edf->btef", h, kern("up"))
    expert_out = jnp.einsum("btef,efd->bted", jax.nn.silu(gate) * up, kern("down"))
    top_vals, top_idx = lax.top_k(probs, K)
    top_vals = top_vals / jnp.maximum(jnp.sum(top_vals, -1, keepdims=True), 1e-9)
    weights = jnp.zeros_like(probs).at[
        jnp.arange(probs.shape[0])[:, None, None], jnp.arange(probs.shape[1])[None, :, None], top_idx,
    ].set(top_vals)
    return jnp.einsum("bte,bted->btd", weights.astype(h.dtype), expert_out), weights


def test_mixtrals_mixture_through_the_new_block_is_the_parents():
    """``moe-tiny`` (4 experts, 2 a token, all held, no shared expert): the
    gates are the parent's BIT FOR BIT (the same softmax, top-k and
    renormalisation; a one-hot sum where the parent scattered), and the output
    is the parent's to float32 rounding: the issue asks for no ``[B, T, E, D]``
    and for a contraction over expert and width together, which sums the same
    products in another order, so the output cannot be bit-equal."""
    mc = tfm.MODEL_CONFIGS["moe-tiny"]
    assert (mc.n_experts, mc.n_experts_held, mc.shared_d_ff, mc.top_k) == (4, 4, 0, 2)
    params = tfm.init_params(jax.random.PRNGKey(2), mc)
    lp = jax.tree.map(lambda a: a[1], params["layers"])
    h = jax.random.normal(jax.random.PRNGKey(9), (3, 17, mc.d_model), F32)
    want, weights = _parent_moe_mlp_decode(h, lp, mc)
    got, counts = _moe_mlp_decode(h, lp, mc, jnp.ones((3, 17), bool))
    vals, idx = lax.top_k(weights, 2)
    assert counts.tolist() == [3 * 17 * 2, 3 * 17 * 2, 4, 3 * 17 * 4]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-8, rtol=0)  # outputs are O(1e-3)
    # the gates, through the new code's own expression
    chosen = idx[..., None] == jnp.arange(4)
    np.testing.assert_array_equal(np.asarray(jnp.sum(jnp.where(chosen, vals[..., None], 0.0), axis=2)),
                                  np.asarray(weights))
    # and the uniform stack's cached walk still equals prefill-then-decode of itself
    toks = jnp.asarray(_tokens(24, 30) % mc.vocab_size)[None]
    whole, cache = forward_with_cache(params, toks, init_cache(mc, 1, 32, dtype=F32), mc, compute_dtype=F32)
    assert cache.moe_counts.tolist() == [24 * 2 * 2, 24 * 2 * 2, cache.moe_counts.tolist()[2], 24 * 4 * 2]
    part, c = forward_with_cache(params, toks[:, :16], init_cache(mc, 1, 32, dtype=F32), mc, compute_dtype=F32)
    rest, _ = forward_with_cache(params, toks[:, 16:], c, mc, compute_dtype=F32)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([part, rest], 1)), np.asarray(whole), atol=2e-6)


# (h) what this stack cannot do is refused by name ------------------------------


def test_a_hybrid_mixture_with_a_window_or_a_share_outside_a_hybrid_is_refused(tiny):
    _, mc, _, _ = tiny
    key = jax.random.PRNGKey(0)
    with pytest.raises(ValueError, match="sliding window"):
        tfm.init_params(key, mc.with_(sliding_window=16))
    with pytest.raises(ValueError, match="top_k"):
        tfm.init_params(key, mc.with_(top_k=9))
    with pytest.raises(ValueError, match="not among the router's n_experts=8"):
        tfm.init_params(key, mc.with_(experts_first=6))
    for field in (dict(experts_held=2), dict(shared_d_ff=64), dict(experts_first=1)):
        with pytest.raises(ValueError, match="a hybrid mixture's"):
            tfm.init_params(key, tfm.MODEL_CONFIGS["moe-tiny"].with_(**field))
        with pytest.raises(ValueError, match="a hybrid mixture's"):
            serving.init_slot_cache(tfm.MODEL_CONFIGS["moe-tiny"].with_(**field), 2, 64)


def _refusals(mc, params):
    from tpu_engine.mesh_runtime import build_mesh
    from tpu_engine.sharding import MeshConfig, Precision, TPUTrainConfig

    eng = partial(serving.ContinuousBatcher, params, mc, max_slots=2, max_len=64, compute_dtype=F32)
    train = lambda **kw: __import__("tpu_engine.train", fromlist=["x"]).build_train_program(  # noqa: E731
        TPUTrainConfig(model_name=mc.name, precision=Precision.FP32, **kw), model_cfg=mc)
    return {
        "tensor_parallel": lambda: eng(mesh=build_mesh(MeshConfig(model=2))),
        "prefix_cache": lambda: eng(prefix_cache_tokens=64),
        "hold_kv": lambda: eng().submit([1, 2, 3], hold_kv=True),
        "submit_prefilled": lambda: eng().submit_prefilled(None),
        "speculative_engine": lambda: eng(draft_params={}, draft_cfg=tfm.MODEL_CONFIGS["gpt-tiny"]),
        "int8_kv_pool": lambda: serving.init_slot_cache(mc, 2, 64, kv_quant=True),
        "training": train,
        "lora": lambda: train(lora_rank=4),
    }


@pytest.mark.parametrize("feature", ["tensor_parallel", "prefix_cache", "hold_kv", "submit_prefilled",
                                     "speculative_engine", "int8_kv_pool", "training", "lora"])
def test_what_the_micro_refuses_this_stack_refuses_by_the_same_name(tiny, feature):
    """Nothing new was needed: ``refuse_recurrent`` is reached first."""
    _, mc, params, _ = tiny
    with pytest.raises(tfm.RecurrentLayersUnsupported, match="recurrent") as err:
        _refusals(mc, params)[feature]()
    assert mc.name in str(err.value)


@pytest.mark.parametrize("feature", ["training", "cacheless_forward"])
def test_a_mixture_after_mixers_that_keep_no_whole_state_is_still_served_only(feature):
    """Block-sparse attention keeps keys and values, so ``refuse_recurrent``
    lets it pass; a mixture after its mixers is refused by its own name."""
    from tpu_engine.sharding import Precision, TPUTrainConfig
    from tpu_engine.train import build_train_program

    mc = tfm.ModelConfig(name="sparse-moe", vocab_size=512, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
                         d_ff=32, layer_types=("sparse_attention",) * 2, n_experts=4, top_k=2, shared_d_ff=48,
                         sparse_kernel_size=8, sparse_kernel_stride=4, sparse_block_size=8, sparse_topk=4,
                         sparse_init_blocks=1, sparse_local_blocks=2, sparse_dense_len=32)
    params = tfm.init_params(jax.random.PRNGKey(0), mc)
    assert params["layers"]["sparse_attn"]["gate"]["kernel"].shape == (2, 4, 64, 32)
    assert tfm.param_count(mc) == sum(a.size for a in jax.tree.leaves(params))
    with pytest.raises(NotImplementedError, match="served only"):
        if feature == "training":
            build_train_program(TPUTrainConfig(model_name=mc.name, precision=Precision.FP32), model_cfg=mc)
        else:
            tfm.forward(params, jnp.zeros((1, 8), jnp.int32), mc)


def test_every_committed_models_config_is_its_parents_field_for_field():
    """The new fields are absent at their defaults: a registered model's
    ``ModelConfig`` differs from the parent's in nothing it had."""
    new = {"experts_first": 0, "experts_held": 0, "shared_d_ff": 0}
    for name, mc in tfm.MODEL_CONFIGS.items():
        got = dataclasses.asdict(mc)
        assert {k: got[k] for k in new} == new, name
        assert mc.n_experts_held == mc.n_experts
