"""A long chunk's held experts over the routed pairs only
(``generate._experts_grouped``, the kernels of ``ops/expert_gmm.py``) against
the masked contraction that computes every held expert for every token, which
stays in the tree as every short walk's form and is the reference here.

CPU, the kernels interpreted (``expert_gmm.INTERPRET_OFF_TPU``), widths of one
128-column tile, float32: the two forms sum the same products in another order,
so outputs agree to float32 rounding (``TOL`` = 2e-6 of the outputs' largest;
measured 4e-7 of it) at every REAL position, and the router's three counts are equal;
``rows_computed`` is each form's own and is held to a hand count.
"""

import dataclasses
import json
import os
import re
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

from harness import program  # noqa: E402

from tpu_engine import serving  # noqa: E402
from tpu_engine.generate import init_cache  # noqa: E402
from tpu_engine.models import transformer as tfm  # noqa: E402
from tpu_engine.ops import expert_gmm  # noqa: E402
from tpu_engine.quant import quantize_weight  # noqa: E402

generate = sys.modules["tpu_engine.generate"]  # the package's ``generate`` is the function

TOL = 2e-6
D = F = 128
F32, BF16 = jnp.float32, jnp.bfloat16


@pytest.fixture
def interpreted(monkeypatch):
    monkeypatch.setattr(expert_gmm, "INTERPRET_OFF_TPU", True)


def _cfg(n_experts, held, first, top_k, scoring):
    return dataclasses.replace(
        tfm.MODEL_CONFIGS["moe-tiny"], d_model=D, d_ff=F, n_experts=n_experts, top_k=top_k,
        experts_first=first, experts_held=held if held < n_experts else 0, router_scoring=scoring,
        routed_scale=2.446 if scoring == "sigmoid" else 1.0)


def _stacks(cfg, seed=3, layers=2, router=None):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    held = cfg.n_experts_held
    out = {"router": {"kernel": jax.random.normal(ks[0], (layers, D, cfg.n_experts)) * 0.5},
           "gate": {"kernel": jax.random.normal(ks[1], (layers, held, D, F)) * 0.1},
           "up": {"kernel": jax.random.normal(ks[2], (layers, held, D, F)) * 0.1},
           "down": {"kernel": jax.random.normal(ks[3], (layers, held, F, D)) * 0.1}}
    if router is not None:
        out["router"]["kernel"] = router
    if cfg.router_scoring == "sigmoid":
        out["router_bias"] = jax.random.normal(ks[4], (layers, cfg.n_experts)) * 0.02
    return out


def _both(cfg, stacks, h, valid, at=1):
    """(masked, its counts, grouped, its counts, the router's experts) of layer
    ``at``; the grouped form is handed the stacks by hand, as ``scan_layers``
    does for a walk of 1 024 rows or more (these chunks are shorter: the
    kernels are interpreted here)."""
    lp = jax.tree.map(lambda a: a[at], stacks)
    masked, c_masked = generate._moe_mlp_decode(h, lp, cfg, valid)
    grouped, c_grouped = jax.jit(lambda h, stacks, valid: generate._moe_mlp_decode(
        h, {**lp, "experts_in_stack": (stacks, jnp.int32(at))}, cfg, valid))(h, stacks, valid)
    return (np.asarray(masked), c_masked.tolist(), np.asarray(grouped), c_grouped.tolist(),
            np.asarray(generate._route(h, lp, cfg)[0]))


def _hand_rows(idx, valid, cfg):
    """Rows of the tiles the real positions' held pairs fill, a group at a time."""
    local = idx[np.asarray(valid)] - cfg.experts_first
    return sum(-(-int((local == e).sum()) // expert_gmm.ROWS) * expert_gmm.ROWS for e in range(cfg.n_experts_held))


CASES = {
    # name: (n_experts, held, first, top_k, router, B, T, real positions a row)
    "4-of-8-top2-softmax": (8, 4, 0, 2, "softmax", 1, 512, (512,)),
    "4-of-8-top2-sigmoid": (8, 4, 4, 2, "sigmoid", 2, 300, (300, 300)),
    "16-of-64-top6-sigmoid": (64, 16, 0, 6, "sigmoid", 1, 640, (640,)),
    "16-of-64-top6-softmax-second-share": (64, 16, 16, 6, "softmax", 2, 320, (320, 320)),
    "a-partly-valid-chunk": (64, 16, 32, 6, "sigmoid", 2, 384, (384, 131)),
    "held-is-all": (8, 8, 0, 2, "softmax", 1, 512, (512,)),
}


@pytest.mark.parametrize("case", CASES)
def test_grouped_equals_masked_at_every_real_position_and_counts_what_it_ran(interpreted, case):
    n, held, first, K, scoring, B, T, real = CASES[case]
    cfg = _cfg(n, held, first, K, scoring)
    stacks = _stacks(cfg)
    h = jax.random.normal(jax.random.PRNGKey(11), (B, T, D), F32)
    valid = jnp.arange(T)[None, :] < jnp.asarray(real)[:, None]
    masked, c_masked, grouped, c_grouped, idx = _both(cfg, stacks, h, valid)
    v = np.asarray(valid)
    assert np.abs(masked[v]).max() > 0.05
    np.testing.assert_allclose(grouped[v], masked[v], atol=TOL * np.abs(masked[v]).max(), rtol=0)
    assert c_grouped[:3] == c_masked[:3] and c_masked[0] == K * sum(real)
    assert c_masked[3] == cfg.n_experts_held * sum(real)  # every real position x every held expert
    assert c_grouped[3] == _hand_rows(idx, v, cfg)
    assert c_masked[1] <= c_grouped[3] <= c_masked[1] + cfg.n_experts_held * (expert_gmm.ROWS - 1)
    if held < n:
        none_held = ((idx < first) | (idx >= first + held)).all(-1) & v
        assert none_held.sum() > 0  # a token none of whose experts is held adds exactly nothing
        assert not grouped[none_held].any() and not masked[none_held].any()
    # a position that is not real is not computed (the masked form computes it; nothing reads either)
    assert not grouped[~v].any()


def test_every_pair_on_one_held_expert_is_one_group_of_every_real_position(interpreted):
    """The routing the buffer is sized for: each token's two choices are expert 5
    (held) and expert 1 (absent), so ONE group holds a pair of every token."""
    cfg = _cfg(8, 4, 4, 2, "softmax")
    router = jnp.zeros((2, D, 8)).at[:, 0, 5].set(9.0).at[:, 0, 1].set(5.0)
    stacks = _stacks(cfg, router=router)
    T = 700
    h = jax.random.normal(jax.random.PRNGKey(12), (1, T, D), F32)
    h = h.at[..., 0].set(1.0 + jnp.abs(h[..., 0]))  # the router reads a positive first column
    masked, c_masked, grouped, c_grouped, idx = _both(cfg, stacks, h, jnp.ones((1, T), bool))
    assert sorted(set(idx.reshape(-1).tolist())) == [1, 5]
    assert c_grouped[:3] == c_masked[:3] == [2 * T, T, 1]
    assert c_grouped[3] == 768 and c_masked[3] == 4 * T  # six tiles of 128 for 700 pairs; 2 800 masked
    np.testing.assert_allclose(grouped, masked, atol=TOL * np.abs(masked).max(), rtol=0)


def test_no_held_pair_at_all_computes_no_tile_and_adds_nothing(interpreted):
    cfg = _cfg(8, 4, 4, 2, "softmax")
    router = jnp.zeros((2, D, 8)).at[:, 0, 0].set(9.0).at[:, 0, 1].set(5.0)
    stacks = _stacks(cfg, router=router)
    h = jnp.abs(jax.random.normal(jax.random.PRNGKey(13), (1, 512, D), F32)) + 0.5
    masked, c_masked, grouped, c_grouped, _ = _both(cfg, stacks, h, jnp.ones((1, 512), bool))
    assert c_grouped == [1024, 0, 0, 0] and c_masked == [1024, 0, 0, 4 * 512]
    assert not grouped.any() and not masked.any()  # and no NaN from a buffer no tile wrote


def test_bfloat16_agrees_to_bfloat16_rounding(interpreted):
    """The served precision: bfloat16 operands, float32 accumulation, each
    pair's gate folded in float32 (the masked form folds it in bfloat16 before
    the down contraction): the difference is a rounding of the outputs."""
    cfg = _cfg(64, 16, 0, 6, "sigmoid")
    stacks = jax.tree.map(lambda a: a.astype(BF16) if a.ndim == 4 or a.shape[-1] == 64 and a.ndim == 3 else a,
                          _stacks(cfg))
    h = jax.random.normal(jax.random.PRNGKey(14), (1, 512, D), F32).astype(BF16)
    masked, c_masked, grouped, c_grouped, _ = _both(cfg, stacks, h, jnp.ones((1, 512), bool))
    assert c_grouped[:3] == c_masked[:3]
    scale = np.abs(masked.astype(np.float32)).max()
    assert np.abs(grouped.astype(np.float32) - masked.astype(np.float32)).max() < 2 ** -7 * scale


# ``experts_grouped_engages``: decided from shapes, types and where the stacks lie


def _gate(held, d, f, dtype=BF16, layers=12):
    return jax.ShapeDtypeStruct((layers, held, d, f), dtype)


ENGAGES = {
    # name: (rows, n_experts, held, top_k, gate's stacked leaf, sharded, what is expected)
    "longctx32-chunk-2048": (2048, 64, 16, 6, _gate(16, 2048, 1408), False, True),
    "longctx32-decode-32-rows": (32, 64, 16, 6, _gate(16, 2048, 1408), False, False),
    "one-row": (1, 64, 16, 6, _gate(16, 2048, 1408), False, False),
    "mixtral-chunk-256": (256, 8, 8, 2, _gate(8, 4096, 14336), False, False),
    "mixtral-decode-16-rows": (16, 8, 8, 2, _gate(8, 4096, 14336), False, False),
    "granite-small-chunk-256": (256, 72, 36, 10, _gate(36, 4096, 768), False, False),
    "granite-small-decode-32-rows": (32, 72, 36, 10, _gate(36, 4096, 768), False, False),
    "a-replica-over-a-mesh": (2048, 64, 16, 6, _gate(16, 2048, 1408), True, False),
    "widths-that-are-not-whole-tiles": (2048, 64, 16, 6, _gate(16, 2048, 1400), False, False),
    "a-routing-too-dense-to-save-rows": (2048, 4, 4, 2, _gate(4, 2048, 1408), False, False),
    "mixtral-chunk-2048": (2048, 8, 8, 2, _gate(8, 4096, 14336), False, True),
    "granite-small-chunk-2048": (2048, 72, 36, 10, _gate(36, 4096, 768), False, True),
    "longctx32s-widths-chunk-1024": (1024, 64, 16, 6, _gate(16, 2048, 1408), False, True),
    "longctx32s-widths-chunk-512": (512, 64, 16, 6, _gate(16, 2048, 1408), False, False),
}


@pytest.mark.parametrize("case", ENGAGES)
def test_engages_is_decided_from_rows_routing_widths_type_and_placement(interpreted, case):
    rows, n, held, K, gate, sharded, want = ENGAGES[case]
    cfg = dataclasses.replace(_cfg(n, held, 0, K, "softmax"), d_model=gate.shape[2], d_ff=gate.shape[3])
    assert generate.experts_grouped_engages(rows, cfg, gate, sharded) is want


def test_engages_declines_int8_experts_and_a_process_off_the_tpu(interpreted, monkeypatch):
    cfg = _cfg(64, 16, 0, 6, "sigmoid")
    gate = jnp.zeros((2, 16, D, F), F32)
    assert generate.experts_grouped_engages(2048, cfg, gate)
    assert not generate.experts_grouped_engages(2048, cfg, quantize_weight(gate))
    monkeypatch.setattr(expert_gmm, "INTERPRET_OFF_TPU", False)
    assert not generate.experts_grouped_engages(2048, cfg, gate)  # this process runs on the CPU


# the programs: which of them hold which form


def _model_config(cfg, name):
    """The configuration's ``ModelConfig`` by its family's mapping
    (``harness.program``, which also registers it under ``name``: taken out
    again, other tests read the registry)."""
    try:
        return program.model_config(cfg, name)
    finally:
        tfm.MODEL_CONFIGS.pop(name, None)


def _file_config(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def _programs(name):
    """{program: StableHLO text} of a serving configuration at its REAL size,
    lowered from shapes (nothing is allocated or compiled)."""
    cfg = _file_config(name)
    mc, p = _model_config(cfg, name), cfg["program"]
    sds = lambda t: jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), t)  # noqa: E731
    params = sds(jax.eval_shape(lambda k: tfm.init_params(k, mc, dtype=BF16), jax.random.PRNGKey(0)))
    pool = sds(jax.eval_shape(lambda: serving.init_slot_cache(mc, p["max_slots"], p["max_len"], BF16,
                                                              prefill_chunk=p["prefill_chunk"])))
    vec = lambda dt: jax.ShapeDtypeStruct((p["max_slots"],), dt)  # noqa: E731
    key = sds(jax.eval_shape(lambda: jax.random.PRNGKey(0)))
    decode = jax.jit(partial(serving.decode_chunk, cfg=mc, n_steps=p["decode_chunk_steps"], compute_dtype=BF16))
    c1 = sds(jax.eval_shape(lambda: init_cache(mc, 1, p["max_len"], dtype=BF16)))
    toks, row = jax.ShapeDtypeStruct((1, p["prefill_chunk"]), jnp.int32), jax.ShapeDtypeStruct((), jnp.int32)
    prefill = jax.jit(partial(serving._prefill_forward, cfg=mc, compute_dtype=BF16))
    n_valid = (row,) if mc.is_hybrid and pool.recurrent else ()
    return mc, p, {
        "decode_chunk": decode.lower(params, vec(jnp.int32), pool, vec(jnp.bool_), vec(jnp.float32),
                                     vec(jnp.int32), vec(jnp.int32), key).as_text(),
        "prefill_chunk": prefill.lower(params, toks, c1, row, *n_valid).as_text()}


def _masked_einsums(text, bt, held, d, f):
    """How often the masked form's contractions appear: (gate and up, which
    give ``[B, T, held, F]``; down, which takes it), ``bt`` = ``"BxT"``."""
    up = len(re.findall(rf"stablehlo\.dot_general[^\n]*-> tensor<{bt}x{held}x{f}xbf16>", text))
    down = len(re.findall(rf"stablehlo\.dot_general[^\n]*tensor<{bt}x{held}x{f}xbf16>, "
                          rf"tensor<{held}x{f}x{d}xbf16>", text))
    return up, down


def _pair_sorts(text, pairs):
    """Sorts of ``pairs`` integers with a payload (the layout's; the type follows the comparator's region)."""
    return text.count(f"}}) : (tensor<{pairs}xi32>, tensor<{pairs}xi32>) -> (tensor<{pairs}xi32>")


DECLINING = {"mixtral-8x7b-1chip-serve": ("decode_chunk", "prefill_chunk"),
             "granite-4.0-h-small-1chip-serve": ("decode_chunk", "prefill_chunk"),
             "kimi-vl-a3b-1chip-serve": ("decode_chunk",)}


@pytest.mark.parametrize("name", DECLINING)
def test_a_program_that_declines_holds_the_masked_contraction_and_nothing_of_the_grouped_form(interpreted, name):
    mc, p, texts = _programs(name)
    for prog in DECLINING[name]:
        rows = p["max_slots"] if prog == "decode_chunk" else p["prefill_chunk"]
        bt = f"{rows}x1" if prog == "decode_chunk" else f"1x{rows}"
        up, down = _masked_einsums(texts[prog], bt, mc.n_experts_held, mc.d_model, mc.d_ff)
        assert up == 2 * down >= 2, (prog, up, down)  # gate, up and down in every loop of the walk
        assert "expert_gate_up" not in texts[prog] and "expert_down" not in texts[prog]
        assert "ragged_dot" not in texts[prog]
        assert _pair_sorts(texts[prog], rows * mc.top_k) == 0


def test_longctx32s_prefill_chunk_holds_the_grouped_form_and_not_the_masked_one(interpreted):
    mc, p, texts = _programs("kimi-vl-a3b-1chip-serve")
    text = texts["prefill_chunk"]
    assert _masked_einsums(text, f"1x{p['prefill_chunk']}", 16, 2048, 1408) == (0, 0)
    assert _pair_sorts(text, 2048 * 6) == 2  # the layout's two stable sorts of the chunk's pairs
    tiles = expert_gmm.n_tiles(2048 * 6, 16)
    assert tiles == 112 and f"tensor<{tiles * 128}x2048xbf16>" in text  # the buffer any routing fits


@pytest.mark.parametrize("name", ["mistral-7b-1chip-serve", "granite-4.0-h-micro-1chip-serve",
                                  "minicpm-sala-1chip-serve", "phi-4-mini-flash-1chip-serve"])
def test_a_stack_without_experts_is_handed_no_stack_and_counts_nothing(name):
    """(Their programs' StableHLO text is the parent's: PERF.md §6 PR 45 has the hashes.)"""
    mc = _model_config(_file_config(name), name)
    assert not mc.is_moe and generate.init_moe_counts(mc) is None


# through ``ContinuousBatcher``


def _serve(engine, prompts, wants):
    ids = [engine.submit(p, max_new_tokens=w) for p, w in zip(prompts, wants)]
    for _ in range(400):
        engine.step()
        if all(engine.result(i)["status"] == "done" for i in ids):
            break
    return [engine.result(i)["tokens"] for i in ids]


def test_stats_on_longctx32s_rehearsal_configuration_count_the_masked_forms_rows():
    """The rehearsal widths (64, 32) are no whole tiles, so every program keeps
    the masked contraction: rows = positions x held experts x mixture layers (a
    stack with no recurrent layer counts the bucket's padding, 40 -> 48, as it
    does for the router's assignments)."""
    cfg = _file_config("kimi-vl-a3b-1chip-serve")
    cfg = {**cfg, **cfg["rehearsal"]}
    mc = _model_config(cfg, "kimi-rehearsal")
    params = tfm.init_params(jax.random.PRNGKey(1), mc)
    engine = serving.ContinuousBatcher(params, mc, max_slots=2, max_len=128, compute_dtype=F32,
                                       prefill_chunk=32, prefill_pad_to=16, chunk_steps=4)
    prompt = np.random.default_rng(5).integers(0, 512, 40).tolist()
    _serve(engine, [prompt], [9])
    st = engine.stats()
    held, layers = mc.n_experts_held, mc.n_mixture_layers
    assert st["moe_prefill_rows_computed_total"] == 48 * held * layers
    assert st["moe_decode_rows_computed_total"] == 8 * held * layers
    assert st["moe_prefill_grouped_chunks_total"] == 0
    assert st["moe_prefill_rows_computed_total"] * mc.top_k == st["moe_prefill_assignments_total"] * held
    plain = serving.ContinuousBatcher(tfm.init_params(jax.random.PRNGKey(0), tfm.MODEL_CONFIGS["gpt-tiny"]),
                                      tfm.MODEL_CONFIGS["gpt-tiny"], max_slots=2, max_len=64)
    assert not [k for k in plain.stats() if k.startswith("moe_")]


def test_the_batcher_runs_a_long_chunk_grouped_and_serves_the_masked_forms_tokens(monkeypatch):
    """A uniform mixture (8 experts, 2 a token, all held) at one tile's widths:
    a prompt of 1 100 pads to 1 152 = a chunk of 1 024, which engages, and one of
    128, which declines; every decode step declines."""
    mc = dataclasses.replace(tfm.MODEL_CONFIGS["moe-tiny"], d_model=D, d_ff=F, n_experts=8, max_seq_len=2048)
    params = tfm.init_params(jax.random.PRNGKey(4), mc)
    prompt = np.random.default_rng(6).integers(0, 512, 1100).tolist()

    def run():
        engine = serving.ContinuousBatcher(params, mc, max_slots=2, max_len=1280, compute_dtype=F32,
                                           prefill_chunk=1024, prefill_pad_to=128, chunk_steps=4)
        return _serve(engine, [prompt], [6])[0], engine.stats()

    masked_tokens, masked = run()
    monkeypatch.setattr(expert_gmm, "INTERPRET_OFF_TPU", True)
    tokens, st = run()
    assert tokens == masked_tokens and len(tokens) == 6
    assert (masked["moe_prefill_grouped_chunks_total"], st["moe_prefill_grouped_chunks_total"]) == (0, 1)
    for key in ("assignments", "assignments_held", "experts_hit"):
        assert st[f"moe_prefill_{key}_total"] == masked[f"moe_prefill_{key}_total"]
    assert st["moe_prefill_assignments_held_total"] == 1152 * 2 * 2  # the bucket's padding is counted here
    assert masked["moe_prefill_rows_computed_total"] == 1152 * 8 * 2
    # the chunk of 1 024 ran tiles (2 layers x (2 048 pairs + at most 8 x 127 of padding)), the chunk of 128
    # every held expert at every position
    grouped_rows = st["moe_prefill_rows_computed_total"] - 128 * 8 * 2
    assert 2 * 2048 <= grouped_rows <= 2 * (2048 + 8 * 127) and grouped_rows % 128 == 0
    assert st["moe_decode_rows_computed_total"] == masked["moe_decode_rows_computed_total"] > 0
