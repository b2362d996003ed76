"""A latent-attention (MLA) stack of the DeepSeek-V3 recipe — one leading dense
layer, then layers whose block is a mixture chosen by a sigmoid router with a
selection bias, of which this tree holds a stated share, plus shared experts
(Kimi-VL-A3B-Instruct's language decoder) — against its plain float32
reference (``benchmarks/onchip/reference/deepseek_v3.py``: the expanded form
only, no cache, no absorption, no batching), and the reference against the
published modelling code (``transformers``' ``DeepseekV3ForCausalLM``).

Tiny widths (the configuration file's rehearsal size: hidden 64, 4 heads of
16 + 8, latent 32, values 16, 8 experts, 3 a token, 4 held, expert width 32,
two shared experts, a dense layer of 96 and two mixture layers), seeded
weights, logits compared, never sampled tokens. Tolerances, each with its
reason:

- ``TOL`` = 2e-6 absolute, float32 against float32, where logits have a spread
  of 0.08: the two sides differ in the order of their sums and, for a decoded
  position, in the absorbed form's association ``(q W_k^T) c`` where the
  reference computes ``q (c W_k)`` (measured 1.8e-7 over 120 positions). A
  position whose k-th and (k+1)-th ``score + bias`` lie within ``TIE`` = 1e-6
  of each other in some layer is left out; none is at these seeds.
- ``BF16_RMS`` = 0.0062 of the logits' spread, for the root-mean-square error
  of 41 rows of logits (chunked prefill, insert, 40 absorbed decode steps): a
  bfloat16 tree under bfloat16 compute measured 0.0048-0.0052 over three token
  streams, the int8 control (codes of the float32 draw, bfloat16 compute, as
  ``--control 1`` runs) 0.0075-0.0077: the control misses what bfloat16 meets
  with a fifth of room on either side.
- Through ``ContinuousBatcher.step`` itself only served tokens come out, so
  there each served token's reference logit is held against the reference's
  best: 1e-6 in float32, 2e-3 in bfloat16 (the logits' spread is 0.08 here and
  the best token leads by little).
"""

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

from families import deepseek_v3 as family  # noqa: E402
from reference import deepseek_v3 as ref  # noqa: E402

from tpu_engine import layer_state, serving  # noqa: E402
from tpu_engine.generate import _mlp_block, _route, forward_with_cache, init_cache  # noqa: E402
from tpu_engine.models import transformer as tfm  # noqa: E402

TOL, TIE, BF16_RMS = 2e-6, 1e-6, 0.0062
SEED = 5
CHUNK, PAD = 32, 16
F32, BF16 = jnp.float32, jnp.bfloat16


def _file_config():
    with open(os.path.join(BENCH, "configs", "kimi-vl-a3b-1chip-serve.json")) as f:
        cfg = json.load(f)
    return {**cfg, **cfg["rehearsal"]}


def _share(cfg, first, count):
    return {**cfg, "first_local_expert": first, "n_routed_experts": count}


@pytest.fixture(scope="module")
def tiny():
    """(configuration dict at its rehearsal size, ModelConfig, program params,
    reference params): one seed drawn twice, by the program and by the
    reference, each by its own code. Experts 0-3 of 8 are held."""
    cfg = _file_config()
    mc = family.model_config(cfg, "mla-tiny")
    assert (mc.n_experts, mc.top_k, mc.experts_first, mc.n_experts_held, mc.shared_d_ff, mc.d_ff,
            mc.dense_d_ff) == (8, 3, 0, 4, 64, 32, 96)
    assert (mc.kv_latent_dim, mc.qk_nope_dim, mc.qk_rope_dim, mc.v_head_dim, mc.head_dim) == (32, 16, 8, 16, 24)
    assert (mc.router_scoring, mc.routed_scale, mc.router_bias_std) == ("sigmoid", 2.446, 0.02)
    return cfg, mc, tfm.init_params(jax.random.PRNGKey(SEED), mc), ref.init_params(cfg, SEED)


def _tokens(n, stream=0):
    return np.random.default_rng([SEED, stream]).integers(0, 512, n).astype(np.int32)


def _reference(tiny, tokens):
    cfg, _, _, rparams = tiny
    logits, margin = ref.forward_logits(rparams, tokens, cfg)
    return np.asarray(logits), np.asarray(margin) >= TIE


# (a) prefill by chunks then decode through the latent cache, against the full forward --


@pytest.mark.parametrize("n", [23, 100])
def test_forward_with_cache_over_a_whole_prompt_equals_the_reference(tiny, n):
    _, mc, params, _ = tiny
    toks = _tokens(n)
    logits, cache = forward_with_cache(params, jnp.asarray(toks)[None], init_cache(mc, 1, 128, dtype=F32),
                                       mc, compute_dtype=F32)
    want, decided = _reference(tiny, toks)
    assert decided.all()
    assert np.abs(np.asarray(logits[0]) - want).max() < TOL
    # one latent leaf a kind, a row of latent | rotated key | zeros to 128
    assert {k: {n: a.shape for n, a in v.items()} for k, v in cache.layers.items()} == {
        "mla_dense": {"latent": (1, 1, 128, 128)}, "mla": {"latent": (2, 1, 128, 128)}}
    row = np.asarray(cache.layers["mla"]["latent"][0, 0, :n])
    assert np.abs(row[:, :40]).min() > 0 and not row[:, 40:].any()


def _cached_logits(params, mc, dtype, toks, n_prompt):
    """The batcher's own steps by hand, so that logits come out: the prompt
    zero-padded to PAD and ingested one CHUNK a call through
    ``serving._prefill_forward`` (the expanded path), inserted into slot 1 of a
    pool of 3, then ``toks[n_prompt:]`` teacher-forced through ``decode_step``
    (the absorbed path). Returns the logits rows of positions ``n_prompt - 1`` on."""
    params = tfm.served_format(params, dtype)
    padded = -(-n_prompt // PAD) * PAD
    t = np.zeros((1, padded), np.int32)
    t[0, :n_prompt] = toks[:n_prompt]
    c1 = init_cache(mc, 1, -(-padded // CHUNK) * CHUNK, dtype=dtype)
    fn = jax.jit(partial(serving._prefill_forward, cfg=mc, compute_dtype=dtype))
    for t0 in range(0, padded, CHUNK):
        t1 = min(t0 + CHUNK, padded)
        out, c1 = fn(params, jnp.asarray(t[:, t0:t1]), c1, jnp.int32(min(max(n_prompt - 1 - t0, 0), t1 - t0 - 1)))
        if t0 <= n_prompt - 1 < t1:
            rows = [out]
    pool = serving.init_slot_cache(mc, 3, 128, dtype, prefill_chunk=CHUNK)
    pool = serving._insert_prefill(pool, c1, jnp.int32(1), jnp.int32(n_prompt), False)
    step = jax.jit(partial(serving.decode_step, cfg=mc, compute_dtype=dtype))
    for tok in toks[n_prompt:]:
        lg, pool = step(params, jnp.asarray([0, int(tok), 0], jnp.int32), pool, jnp.asarray([False, True, False]))
        rows.append(lg[1])
    assert pool.lengths.tolist() == [0, len(toks), 0]  # the rows that are not active stand still
    return np.asarray(jnp.stack(rows), np.float32)


def _rms_error(tiny, params, dtype, stream=1, n_prompt=70):
    toks = _tokens(n_prompt + 40, stream)
    want, decided = _reference(tiny, toks)
    assert decided.all()
    want = want[n_prompt - 1:]
    got = _cached_logits(params, tiny[1], dtype, toks, n_prompt)
    return float(np.sqrt(np.mean(np.square(got - want))) / want.std()), float(np.abs(got - want).max())


@pytest.mark.parametrize("n_prompt", [70, 45])  # 70 pads to 80: chunks of 32, 32, 16 with 6 real
def test_chunked_prefill_insert_and_40_absorbed_decode_steps_equal_the_reference(tiny, n_prompt):
    _, worst = _rms_error(tiny, tiny[2], F32, n_prompt=n_prompt)
    assert worst < TOL


def test_bfloat16_meets_its_tolerance_and_the_int8_control_misses_it(tiny):
    from tpu_engine.quant import QuantWeight, quantize_params

    _, mc, params, _ = tiny
    rms, _ = _rms_error(tiny, params, BF16)
    assert 0.003 < rms < BF16_RMS
    q = tfm.draw_deferred(quantize_params(tfm.init_params(jax.random.PRNGKey(SEED), mc, deferred=True)))
    for kind in ("mla", "mla_dense"):
        for name in ("q", "kv_a", "kv_b", "o", "gate", "up", "down"):
            assert isinstance(q["layers"][kind][name]["kernel"], QuantWeight), (kind, name)
    assert not isinstance(q["layers"]["mla"]["router"]["kernel"], QuantWeight)
    assert q["layers"]["mla"]["router_bias"].dtype == F32
    rms, _ = _rms_error(tiny, q, BF16)
    assert BF16_RMS < rms < 2 * BF16_RMS


# (b) the absorbed path equals the expanded path on one cache -----------------


def test_the_absorbed_path_equals_the_expanded_path_on_one_cache(tiny):
    """Position 50's logits from ONE cache of 50 positions, twice: as a decode
    step (T = 1: absorbed) and as the first row of a chunk of two (T = 2:
    expanded through ``kv_b``)."""
    _, mc, params, _ = tiny
    toks = jnp.asarray(_tokens(52, 3))[None]
    _, cache = forward_with_cache(params, toks[:, :50], init_cache(mc, 1, 64, dtype=F32), mc, compute_dtype=F32)
    absorbed, _ = forward_with_cache(params, toks[:, 50:51], cache, mc, compute_dtype=F32)
    expanded, _ = forward_with_cache(params, toks[:, 50:52], cache, mc, compute_dtype=F32)
    np.testing.assert_allclose(np.asarray(absorbed[0, 0]), np.asarray(expanded[0, 0]), atol=TOL, rtol=0)
    assert float(jnp.abs(absorbed).max()) > 0.01


# (c) the shares add up ---------------------------------------------------------


def _layer_input(tiny, n=64):
    cfg = tiny[0]
    h = jax.random.normal(jax.random.PRNGKey(3), (n, cfg["hidden_size"]), F32)
    return h * lax.rsqrt(jnp.mean(jnp.square(h), -1, keepdims=True))


def test_the_four_shares_routed_parts_and_the_shared_experts_once_are_the_uncut_layer(tiny):
    cfg, h = tiny[0], _layer_input(tiny)
    with jax.default_matmul_precision("highest"):
        parts = []
        for first in (0, 2, 4, 6):
            c = _share(cfg, first, 2)
            parts.append(ref.routed_part(h, ref.draw_layer(c, SEED, "mla", 1), c)[0])
        uncut_cfg = _share(cfg, 0, 8)
        w = ref.draw_layer(uncut_cfg, SEED, "mla", 1)
        uncut = ref.routed_part(h, w, uncut_cfg)[0] + ref.shared_part(h, w)
        total = sum(parts) + ref.shared_part(h, w)
    np.testing.assert_allclose(np.asarray(total), np.asarray(uncut), atol=5e-9, rtol=0)  # sums in another order
    assert all(float(jnp.abs(p).max()) > 1e-4 for p in parts)


@pytest.mark.parametrize("share", [(0, 8), (0, 2), (2, 2), (4, 2), (6, 2)],
                         ids=["all-held", "share-0", "share-1", "share-2", "share-3"])
def test_the_programs_own_block_is_the_references_for_the_share_it_is_told(tiny, share):
    """``generate._mlp_block`` on one mixture layer of the program's own draw
    for a share: x + held experts' part + shared experts, the reference's."""
    cfg = _share(tiny[0], *share)
    mc = family.model_config(cfg, "share")
    assert (mc.experts_first, mc.n_experts_held, mc.n_experts) == (share[0], share[1], 8)
    params = tfm.init_params(jax.random.PRNGKey(SEED), mc)
    lp = jax.tree.map(lambda a: a[1], params["layers"]["mla"])
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 32, 64), F32)
    with jax.default_matmul_precision("highest"):
        got = _mlp_block(x, lp, mc)
        w = ref.draw_layer(cfg, SEED, "mla", 1)
        flat = x.reshape(-1, 64)
        hn = ref.rms_norm(flat, ref.ONE, cfg["rms_norm_eps"])
        want = (flat + ref.routed_part(hn, w, cfg)[0] + ref.shared_part(hn, w)).reshape(x.shape)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6, rtol=0)  # x is O(1): float32 rounding


def test_a_share_holds_the_uncut_draws_experts_bit_for_bit(tiny):
    cfg = tiny[0]
    key = jax.random.PRNGKey(SEED)
    uncut = tfm.init_params(key, family.model_config(_share(cfg, 0, 8), "uncut"))
    upper = tfm.init_params(key, family.model_config(_share(cfg, 4, 4), "upper"))
    for name in ("gate", "up", "down"):
        whole, part = uncut["layers"]["mla"][name]["kernel"], upper["layers"]["mla"][name]["kernel"]
        assert part.shape[1] == 4 and whole.shape[1] == 8
        np.testing.assert_array_equal(np.asarray(whole[:, 4:]), np.asarray(part))
    for name in ("router", "shared_gate", "shared_down", "kv_b"):  # replicated on every share
        np.testing.assert_array_equal(np.asarray(uncut["layers"]["mla"][name]["kernel"]),
                                      np.asarray(upper["layers"]["mla"][name]["kernel"]))
    np.testing.assert_array_equal(np.asarray(uncut["layers"]["mla"]["router_bias"]),
                                  np.asarray(upper["layers"]["mla"]["router_bias"]))
    # and the reference draws the same numbers by its own code
    w = ref.draw_layer(_share(cfg, 4, 4), SEED, "mla", 1)
    for name, leaf in (("gate", "gate"), ("router", "router"), ("kv_a", "kv_a"), ("o", "o")):
        np.testing.assert_allclose(np.asarray(w[name]), np.asarray(upper["layers"]["mla"][leaf]["kernel"][1]),
                                   rtol=2e-7, atol=0)
    np.testing.assert_allclose(np.asarray(w["router_bias"]), np.asarray(upper["layers"]["mla"]["router_bias"][1]),
                               rtol=2e-7, atol=0)
    wd = ref.draw_layer(cfg, SEED, "mla_dense", 0)
    np.testing.assert_allclose(np.asarray(wd["down"]), np.asarray(upper["layers"]["mla_dense"]["down"]["kernel"][0]),
                               rtol=2e-7, atol=0)


# (d) the bias moves the choice and not the gates -------------------------------


def test_the_bias_moves_the_choice_and_not_the_gates_which_sum_to_the_scale(tiny):
    cfg, mc, params, _ = tiny
    lp = jax.tree.map(lambda a: a[0], params["layers"]["mla"])
    assert float(jnp.abs(lp["router_bias"]).min()) > 0 and lp["router_bias"].dtype == F32
    h = jax.random.normal(jax.random.PRNGKey(6), (1, 400, 64), F32)
    w = {"router": lp["router"]["kernel"], "router_bias": lp["router_bias"]}
    idx, gates, _ = ref.route(h[0], w, cfg)
    np.testing.assert_allclose(np.asarray(gates.sum(-1)), 2.446, rtol=1e-6)
    idx0, gates0, _ = ref.route(h[0], {**w, "router_bias": jnp.zeros(8)}, cfg)
    moved = np.asarray(jnp.sort(idx, -1) != jnp.sort(idx0, -1)).any(-1)
    assert 40 < moved.sum() < 360  # the bias decides the choice for a good share of the tokens
    # ... and never the gates: sigma at the chosen experts, renormalised, scaled
    sigma = np.asarray(jax.nn.sigmoid(h[0] @ w["router"]))
    at = np.take_along_axis(sigma, np.asarray(idx), -1)
    np.testing.assert_allclose(np.asarray(gates), 2.446 * at / at.sum(-1, keepdims=True), rtol=1e-6)
    with_bias = np.take_along_axis(sigma + np.asarray(w["router_bias"]), np.asarray(idx), -1)
    assert np.abs(np.asarray(gates) - 2.446 * with_bias / with_bias.sum(-1, keepdims=True)).max() > 1e-3
    # the program's router is the reference's
    pidx, pgates = _route(h, lp, mc)
    order, porder = np.argsort(np.asarray(idx), -1), np.argsort(np.asarray(pidx[0]), -1)
    np.testing.assert_array_equal(np.take_along_axis(np.asarray(idx), order, -1),
                                  np.take_along_axis(np.asarray(pidx[0]), porder, -1))
    np.testing.assert_allclose(np.take_along_axis(np.asarray(gates), order, -1),
                               np.take_along_axis(np.asarray(pgates[0]), porder, -1), rtol=1e-6)
    # a softmax router has no bias leaf and no scale
    soft = tfm.init_params(jax.random.PRNGKey(0), mc.with_(router_scoring="softmax", routed_scale=1.0))
    assert "router_bias" not in soft["layers"]["mla"]


# (e) the leading dense layer is dense and the rest are mixtures -----------------


def test_the_leading_layer_is_dense_and_the_rest_are_mixtures(tiny):
    cfg, mc, params, _ = tiny
    assert mc.layer_types == ("mla_dense", "mla", "mla") and mc.n_mixture_layers == 2
    assert mc.layer_runs() == (("mla_dense", 0, 1), ("mla", 0, 2))
    dense, mix = params["layers"]["mla_dense"], params["layers"]["mla"]
    assert dense["gate"]["kernel"].shape == (1, 64, 96) and "router" not in dense and "shared_gate" not in dense
    assert mix["gate"]["kernel"].shape == (2, 4, 64, 32) and mix["router"]["kernel"].shape == (2, 64, 8)
    assert mix["shared_gate"]["kernel"].shape == (2, 64, 64) and mix["router_bias"].shape == (2, 8)
    n = sum(a.size for a in jax.tree.leaves(params))
    assert tfm.param_count(mc) == n
    axes = tfm.logical_axes(mc)
    assert jax.tree.structure(jax.tree.map(lambda a: 0, params)) == jax.tree.structure(
        jax.tree.map(lambda a: 0, axes, is_leaf=lambda a: isinstance(a, tuple)))
    # a walk counts the two mixture layers' assignments, not three layers'
    toks = jnp.asarray(_tokens(24, 30))[None]
    _, cache = forward_with_cache(params, toks, init_cache(mc, 1, 32, dtype=F32), mc, compute_dtype=F32)
    assert cache.moe_counts.tolist()[0] == 24 * 3 * 2
    # the dense layer's block is the reference's dense SwiGLU
    lp = jax.tree.map(lambda a: a[0], dense)
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 16, 64), F32)
    w = ref.draw_layer(cfg, SEED, "mla_dense", 0)
    with jax.default_matmul_precision("highest"):
        got = _mlp_block(x, lp, mc, dense=True)
        want = x[0] + ref.swiglu(ref.rms_norm(x[0], ref.ONE, 1e-5), w["gate"], w["up"], w["down"])
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want), atol=1e-6, rtol=0)


# (f) through ContinuousBatcher ----------------------------------------------------


def _serve(params, mc, dtype, prompts, wants, **kw):
    engine = serving.ContinuousBatcher(params, mc, max_slots=2, max_len=128, compute_dtype=dtype,
                                       prefill_chunk=CHUNK, prefill_pad_to=PAD, chunk_steps=4, **kw)
    ids = [engine.submit(p, max_new_tokens=w) for p, w in zip(prompts, wants)]
    for _ in range(400):
        engine.step()
        if all(engine.result(i)["status"] == "done" for i in ids):
            break
    return [engine.result(i)["tokens"] for i in ids], engine


def _gaps(tiny, rparams, prompts, served):
    cfg = tiny[0]
    gaps, left_out = [], 0
    for p, s in zip(prompts, served):
        logits, margin = ref.forward_logits(rparams, np.asarray(p + s), cfg)
        rows = np.asarray(logits)[len(p) - 1:-1]
        decided = np.asarray(margin)[len(p) - 1:-1] >= TIE
        left_out += int((~decided).sum())
        gaps += (rows.max(-1) - rows[np.arange(len(s)), s])[decided].tolist()
    return np.asarray(gaps), left_out


PROMPTS, WANTS = (70, 45, 9, 33), (12, 21, 7, 15)  # a slot is reused by a shorter request after a longer one


@pytest.mark.parametrize("dtype,limit", [(F32, 1e-6), (BF16, 2e-3)], ids=["float32", "bfloat16"])
def test_the_batcher_serves_what_the_reference_ranks_best(tiny, dtype, limit):
    """``ContinuousBatcher`` end to end (admit, chunked prefill with the
    bucket's padding, insert, absorbed decode chunks that overshoot, reset,
    reuse of both slots after longer requests, a row that stands empty while
    the last request finishes): every served token is the reference's best on
    the request's own history, to the dtype's tolerance."""
    _, mc, params, rparams = tiny
    prompts = [_tokens(n, 10 + i).tolist() for i, n in enumerate(PROMPTS)]
    served, engine = _serve(params, mc, dtype, prompts, WANTS)
    assert [len(s) for s in served] == list(WANTS)
    gaps, left_out = _gaps(tiny, rparams, prompts, served)
    assert left_out == 0 and len(gaps) == sum(WANTS)
    assert gaps.max() < limit
    st = engine.stats()
    assert st["latent_cache_bytes"] == 3 * 2 * 128 * 128 * jnp.dtype(dtype).itemsize
    assert st["recurrent_state_bytes"] == 0 and st["state_resets_total"] == 0
    assert st["held_experts"] == 4 and st["moe_decode_layer_steps_total"] % (2 * 4) == 0
    assert st["moe_prefill_assignments_total"] > 0 and st["moe_decode_assignments_held_total"] > 0


def test_a_prompt_prefix_is_sliced_out_of_the_latent_lanes_and_pasted_back(tiny):
    """The latent kind is positional: the table's ``slice_lanes`` /
    ``paste_lanes`` serve the prompt-prefix cache with no code of its own."""
    _, mc, params, rparams = tiny
    shared = _tokens(64, 40).tolist()
    prompts = [shared + _tokens(9, 41).tolist(), shared + _tokens(13, 42).tolist()]
    served, engine = _serve(params, mc, F32, prompts, (6, 6), prefix_cache_tokens=128)
    assert engine.stats()["prefix_cache"]["hits"] >= 1
    gaps, _ = _gaps(tiny, rparams, prompts, served)
    assert gaps.max() < 1e-6


# (g) what is priced is what is allocated ------------------------------------------


def test_the_estimate_prices_the_latent_pool_the_table_allocates(tiny):
    from tpu_engine.hbm_estimate import estimate_serving_hbm

    _, mc, params, _ = tiny
    by_kind = layer_state.state_bytes(mc, 4, 128, BF16)
    pool = serving.init_slot_cache(mc, 4, 128, BF16, prefill_chunk=CHUNK)
    assert by_kind == {"mla_dense": 1 * 4 * 128 * 128 * 2, "mla": 2 * 4 * 128 * 128 * 2}
    assert sum(by_kind.values()) == sum(a.nbytes for a in jax.tree.leaves(pool.layers)) \
        == layer_state.latent_bytes(pool.layers)
    assert layer_state.split_bytes(by_kind) == (sum(by_kind.values()), 0)  # positional, nothing whole
    tfm.MODEL_CONFIGS[mc.name] = mc
    try:
        est = estimate_serving_hbm(mc.name, 4, 128, prefill_chunk=CHUNK)
    finally:
        del tfm.MODEL_CONFIGS[mc.name]
    # HBMEstimate rounds to 1e-4 GiB: its stated error
    assert est.kv_pool_gib == pytest.approx(sum(by_kind.values()) / 2**30, abs=1e-4)
    assert est.recurrent_state_gib == 0.0
    n = sum(a.size for a in jax.tree.leaves(params))
    assert est.params_gib == pytest.approx(2 * n / 2**30, abs=1e-4)
    # the full configuration: 13 layers x 32 slots x 10240 lanes x 640 values x 2 B
    with open(os.path.join(BENCH, "configs", "kimi-vl-a3b-1chip-serve.json")) as f:
        full = family.model_config(json.load(f), "kimi-full")
    assert sum(layer_state.state_bytes(full, 32, 10240, BF16).values()) == 13 * 32 * 10240 * 640 * 2
    assert tfm.param_count(full) == pytest.approx(2.789e9, rel=2e-3)  # ISSUE 40's own arithmetic


# (h) each refusal, by name ------------------------------------------------------------


def _refusals(mc, params):
    from tpu_engine import disagg, spec_pool
    from tpu_engine.generate import speculative_generate
    from tpu_engine.serving_fleet import ServingFleet, ServingReplicaSpec
    from tpu_engine.sharding import Precision, TPUTrainConfig

    eng = partial(serving.ContinuousBatcher, params, mc, max_slots=2, max_len=64, compute_dtype=F32)
    spec = ServingReplicaSpec(model_name=mc.name, max_slots=2, max_len=64)
    draft = tfm.MODEL_CONFIGS["gpt-tiny"]
    return {
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
        "training": lambda: __import__("tpu_engine.train", fromlist=["x"]).build_train_program(
            TPUTrainConfig(model_name=mc.name, precision=Precision.FP32), model_cfg=mc),
        "cacheless_forward": lambda: tfm.forward(params, jnp.zeros((1, 8), jnp.int32), mc),
    }


@pytest.mark.parametrize("feature", ["hold_kv", "submit_prefilled", "extract_slot_kv", "disagg_fleet",
                                     "host_kv_tier", "speculative_engine", "speculative_fleet", "decode_verify",
                                     "speculative_generate", "training", "cacheless_forward"])
def test_what_carries_keys_and_values_only_refuses_a_latent_cache_by_name(tiny, feature):
    _, mc, params, _ = tiny
    tfm.MODEL_CONFIGS[mc.name] = mc
    try:
        with pytest.raises(tfm.LatentCacheUnsupported, match="latent") as err:
            _refusals(mc, params)[feature]()
    finally:
        del tfm.MODEL_CONFIGS[mc.name]
    assert mc.name in str(err.value) and err.value.feature


@pytest.mark.parametrize("make", [lambda mc: serving.init_slot_cache(mc, 2, 64, kv_quant=True),
                                  lambda mc: init_cache(mc, 1, 64, kv_quant=True)], ids=["pool", "cache"])
def test_an_int8_latent_is_refused_by_name(tiny, make):
    with pytest.raises(NotImplementedError, match=r"no int8 latent \(kv_quant\)"):
        make(tiny[1])


@pytest.mark.parametrize("bad, why", [
    (dict(qk_rope_dim=7, head_dim_override=23), "even"),
    (dict(head_dim_override=32), "head_dim = qk_nope_dim"),
    (dict(dense_d_ff=0), "dense_d_ff"),
    (dict(router_scoring="tanh"), "router_scoring"),
    (dict(sliding_window=16), "sliding window"),
])
def test_a_latent_stack_that_cannot_be_is_refused_where_it_is_built(tiny, bad, why):
    with pytest.raises(ValueError, match=why):
        tfm.init_params(jax.random.PRNGKey(0), tiny[1].with_(**bad))


def test_a_sigmoid_router_outside_a_hybrid_mixture_is_refused():
    for field in (dict(router_scoring="sigmoid"), dict(routed_scale=2.0)):
        with pytest.raises(ValueError, match="a hybrid mixture's"):
            tfm.init_params(jax.random.PRNGKey(0), tfm.MODEL_CONFIGS["moe-tiny"].with_(**field))


def test_every_committed_models_config_keeps_the_new_fields_at_their_defaults():
    new = {"kv_latent_dim": 0, "qk_nope_dim": 0, "qk_rope_dim": 0, "v_head_dim": 0, "dense_d_ff": 0,
           "router_scoring": "softmax", "routed_scale": 1.0, "router_bias_std": 0.0}
    for name, mc in tfm.MODEL_CONFIGS.items():
        assert {k: getattr(mc, k) for k in new} == new, name
        assert mc.n_mixture_layers == (mc.n_layers if mc.is_moe else 0)


# (i) the reference is the published modelling code's -------------------------------------


def test_the_references_logits_are_transformers_deepseek_v3s_on_the_same_weights(tiny):
    """All eight experts held, the tiny size: ``DeepseekV3ForCausalLM`` (its
    default ``rope_interleave``) loaded with the reference's own draw, the
    rotary columns of ``W_q`` and ``W_kva`` permuted as the configuration's
    ``assumed.rotary_pairing`` says (published column 2i <- i, 2i + 1 <- half
    + i). Ties the reference to the published code, not to the program."""
    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")
    if not hasattr(transformers, "DeepseekV3ForCausalLM"):
        pytest.skip("this transformers has no DeepseekV3ForCausalLM")
    cfg = _share(tiny[0], 0, 8)
    H, N, R, C = cfg["num_attention_heads"], cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["kv_lora_rank"]
    hf = transformers.DeepseekV3ForCausalLM(transformers.DeepseekV3Config(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"], intermediate_size=cfg["intermediate_size"],
        moe_intermediate_size=cfg["moe_intermediate_size"], num_hidden_layers=cfg["num_hidden_layers"],
        num_attention_heads=H, num_key_value_heads=H, n_shared_experts=cfg["n_shared_experts"],
        n_routed_experts=8, routed_scaling_factor=cfg["routed_scaling_factor"], kv_lora_rank=C, q_lora_rank=None,
        qk_rope_head_dim=R, v_head_dim=cfg["v_head_dim"], qk_nope_head_dim=N, n_group=1, topk_group=1,
        num_experts_per_tok=cfg["num_experts_per_tok"], first_k_dense_replace=cfg["first_k_dense_replace"],
        norm_topk_prob=True, hidden_act="silu", max_position_embeddings=256, rms_norm_eps=cfg["rms_norm_eps"],
        rope_theta=cfg["rope_theta"], rope_scaling=None, attention_bias=False, tie_word_embeddings=False,
        attn_implementation="eager")).eval()
    assert hf.config.rope_interleave
    interleave = np.empty(R, int)
    interleave[0::2], interleave[1::2] = np.arange(R // 2), R // 2 + np.arange(R // 2)

    def lin(kernel):  # [in, out] -> nn.Linear's [out, in]
        return torch.tensor(np.asarray(kernel).T.copy())

    rparams = ref.init_params(cfg, SEED)
    sd = {"model.embed_tokens.weight": torch.tensor(np.asarray(rparams["embed"]["embedding"])),
          "lm_head.weight": lin(rparams["lm_head"]["kernel"]), "model.norm.weight": torch.ones(cfg["hidden_size"])}
    for li, (kind, i) in enumerate(ref.kinds(cfg)):
        w = {k: np.asarray(v) for k, v in ref.draw_layer(cfg, SEED, kind, i).items()}
        q = w["q"].reshape(-1, H, N + R)
        q = np.concatenate([q[..., :N], q[..., N:][..., interleave]], -1).reshape(-1, H * (N + R))
        kva = np.concatenate([w["kv_a"][:, :C], w["kv_a"][:, C:][:, interleave]], -1)
        pre = f"model.layers.{li}."
        sd.update({pre + "self_attn.q_proj.weight": lin(q), pre + "self_attn.kv_a_proj_with_mqa.weight": lin(kva),
                   pre + "self_attn.kv_a_layernorm.weight": torch.ones(C),
                   pre + "self_attn.kv_b_proj.weight": lin(w["kv_b"]), pre + "self_attn.o_proj.weight": lin(w["o"]),
                   pre + "input_layernorm.weight": torch.ones(cfg["hidden_size"]),
                   pre + "post_attention_layernorm.weight": torch.ones(cfg["hidden_size"])})
        if kind == "mla_dense":
            sd.update({pre + f"mlp.{n}_proj.weight": lin(w[n]) for n in ("gate", "up", "down")})
            continue
        sd.update({pre + "mlp.gate.weight": lin(w["router"]),
                   pre + "mlp.gate.e_score_correction_bias": torch.tensor(w["router_bias"])})
        sd.update({pre + f"mlp.shared_experts.{n}_proj.weight": lin(w["shared_" + n]) for n in ("gate", "up", "down")})
        for e in range(8):
            sd.update({pre + f"mlp.experts.{e}.{n}_proj.weight": lin(w[n][e]) for n in ("gate", "up", "down")})
    missing, unexpected = hf.load_state_dict(sd, strict=False)
    assert not unexpected and all("rotary" in k or "inv_freq" in k for k in missing), (missing, unexpected)
    toks = _tokens(48, 50)
    with torch.no_grad():
        theirs = hf(torch.tensor(toks[None].astype(np.int64))).logits[0].numpy()
    ours, margin = ref.forward_logits(rparams, toks, cfg)
    assert float(np.asarray(margin).min()) >= TIE
    assert np.abs(theirs).max() > 0.05
    np.testing.assert_allclose(np.asarray(ours), theirs, atol=2e-6, rtol=0)  # float32 both, other orders of sums


# (j) the decode kernel is the two contractions it replaces ---------------------------------


@pytest.mark.parametrize("dtype,tol", [(F32, 2e-6), (BF16, 2e-2)], ids=["float32", "bfloat16"])
def test_the_decode_kernel_is_the_masked_softmax_over_the_visible_lanes(dtype, tol):
    """``ops.mla_decode`` (interpreted) against plain ``jnp``: for every slot a
    softmax over its first ``visible`` lanes of layer 1's rows, times those
    rows. Slots stand at a block's first lane, its last, past it, at the row's
    end, and at lane 0 (a slot that is not active). In bfloat16 the oracle
    rounds the normalised probabilities and the kernel the unnormalised ones:
    outputs of spread 1 agree to a bfloat16 digit."""
    from tpu_engine.ops import mla_decode

    B, H, S, W = 6, 4, 1536, 128
    q = jax.random.normal(jax.random.PRNGKey(1), (B, H, W), F32).astype(dtype)
    pool = jax.random.normal(jax.random.PRNGKey(2), (3, B, S, W), F32).astype(dtype)
    visible = jnp.asarray([1, 512, 513, 1024, 1536, 700], jnp.int32)
    got = mla_decode.mla_decode(q, pool, jnp.int32(1), visible, scale=0.2)
    assert got.dtype == dtype and got.shape == (B, H, W)
    s = jnp.einsum("bhw,bmw->bhm", q.astype(F32), pool[1].astype(F32)) * 0.2
    s = jnp.where(jnp.arange(S)[None, None] < visible[:, None, None], s, -1e30)
    want = jnp.einsum("bhm,bmw->bhw", jax.nn.softmax(s, -1), pool[1].astype(F32))
    assert float(jnp.abs(want).max()) > 0.5
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want), atol=tol, rtol=0)
    # a block past a slot's last visible one is never computed: poison there
    # changes nothing (inside that last block a lane past the position is
    # masked, as in XLA's form: its probability is exactly 0)
    poisoned = pool.at[1, 0, 512:].set(jnp.nan).at[1, 5, 1024:].set(jnp.nan)
    again = mla_decode.mla_decode(q, poisoned, jnp.int32(1), visible, scale=0.2)
    np.testing.assert_array_equal(np.asarray(again, np.float32), np.asarray(got, np.float32))


def test_decode_through_the_kernel_equals_decode_through_xlas_contractions(tiny, monkeypatch):
    """The same pool and tokens through ``decode_step`` twice: XLA's two
    contractions over every lane (what every other test here runs), and the
    kernel, interpreted, which engages for a pool of whole 512-lane blocks."""
    from tpu_engine.ops import mla_decode

    _, mc, params, _ = tiny
    toks = _tokens(60, 7)
    pool = serving.init_slot_cache(mc, 2, 512, F32, prefill_chunk=CHUNK)
    _, c1 = forward_with_cache(params, jnp.asarray(toks[:40])[None], init_cache(mc, 1, 64, dtype=F32), mc,
                               compute_dtype=F32)
    pool = serving._insert_prefill(pool, c1, jnp.int32(1), jnp.int32(40), False)
    assert not mla_decode.engages(pool.layers["mla"]["latent"])  # off the TPU, unasked: XLA's

    def run(pool):
        rows = []
        for tok in toks[40:]:
            lg, pool = serving.decode_step(params, jnp.asarray([0, int(tok)], jnp.int32), pool,
                                           jnp.asarray([False, True]), mc, F32)
            rows.append(lg[1])
        return np.asarray(jnp.stack(rows))

    plain = run(pool)
    monkeypatch.setattr(mla_decode, "INTERPRET_OFF_TPU", True)
    assert mla_decode.engages(pool.layers["mla"]["latent"])
    np.testing.assert_allclose(run(pool), plain, atol=TOL, rtol=0)


@pytest.mark.parametrize("dtype,tol", [(F32, 2e-6), (BF16, 2e-2)], ids=["float32", "bfloat16"])
def test_the_chunk_kernel_is_causal_attention_from_the_chunks_first_position(dtype, tol):
    """``ops.mla_decode.mla_chunk_attend`` (interpreted) against plain ``jnp``:
    two rows whose chunks of 1 024 queries start at positions 0 and 512 of rows
    of 2 048 lanes; a query attends the lanes up to its own position. Keys 24
    wide, values 16: the two widths differ, as the layer's do."""
    from tpu_engine.ops import mla_decode

    B, H, T, M, qk, vd = 2, 3, 1024, 2048, 24, 16
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (B, H, T, qk), F32).astype(dtype)
    k = jax.random.normal(ks[1], (B, H, M, qk), F32).astype(dtype)
    v = jax.random.normal(ks[2], (B, H, M, vd), F32).astype(dtype)
    first = jnp.asarray([0, 512], jnp.int32)
    got = mla_decode.mla_chunk_attend(q, k, v, first, scale=0.3)
    assert got.dtype == dtype and got.shape == (B, H, T, vd)
    s = jnp.einsum("bhtd,bhmd->bhtm", q.astype(F32), k.astype(F32)) * 0.3
    seen = jnp.arange(M)[None, None, None, :] <= (first[:, None, None, None] + jnp.arange(T)[None, None, :, None])
    want = jnp.einsum("bhtm,bhmv->bhtv", jax.nn.softmax(jnp.where(seen, s, -1e30), -1), v.astype(F32))
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want), atol=tol, rtol=0)
    # a block past a tile's last position is never computed
    again = mla_decode.mla_chunk_attend(q, k.at[0, :, 1024:].set(jnp.nan).at[1, :, 1536:].set(jnp.nan), v, first,
                                        scale=0.3)
    np.testing.assert_array_equal(np.asarray(again, np.float32), np.asarray(got, np.float32))


def test_a_chunk_through_the_kernel_equals_a_chunk_through_xlas_form(tiny, monkeypatch):
    """Two chunks of 512 tokens into one staging row of 1 024 lanes, logits
    compared: XLA's expanded attention, then the chunk kernel, interpreted."""
    from tpu_engine.ops import mla_decode

    _, mc, params, _ = tiny
    toks = jnp.asarray(np.random.default_rng(8).integers(0, 512, 1024).astype(np.int32))[None]
    assert not mla_decode.chunk_engages(512, 1024)

    def run():
        cache = init_cache(mc.with_(max_seq_len=1024), 1, 1024, dtype=F32)
        a, cache = forward_with_cache(params, toks[:, :512], cache, mc, compute_dtype=F32)
        b, _ = forward_with_cache(params, toks[:, 512:], cache, mc, compute_dtype=F32)
        return np.asarray(jnp.concatenate([a, b], 1)[0])

    plain = run()
    monkeypatch.setattr(mla_decode, "INTERPRET_OFF_TPU", True)
    assert mla_decode.chunk_engages(512, 1024)
    np.testing.assert_allclose(run(), plain, atol=TOL, rtol=0)
