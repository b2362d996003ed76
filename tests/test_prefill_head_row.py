"""A prefill chunk runs the final norm and the output head at the ONE row its
caller reads (``forward_with_cache(logits_row=)``, ``serving._prefill_forward``),
for every stack the tree serves, not only the one with a cross-decoder.

Row ``r`` of ``unembed(x)`` is ``unembed(x[r])``: the same products, so the
one-row call equals row ``r`` of the full call to float32 rounding (bit for bit
in float32 with an untied head, where the CPU sums both in one order), and the
cache is the full walk's leaf for leaf. The lowering checks are made at the
benchmark's REAL sizes from shapes alone: nothing is allocated or compiled.
"""

import dataclasses
import math
import re
import sys
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests.test_moe_grouped_chunk import _file_config, _model_config
from tpu_engine import layer_state, serving
from tpu_engine.generate import forward_with_cache, init_cache
from tpu_engine.models import transformer as tfm
from tpu_engine.ops import sparse_block_attention

F32, BF16 = jnp.float32, jnp.bfloat16

# name -> (the benchmark configuration whose family it is, fields replaced at its rehearsal size)
STACKS = {
    "dense-gqa": ("mistral-7b-1chip-serve", {}),
    "dense-gqa-ring": ("mistral-7b-1chip-serve", {"sliding_window": 16}),
    "mixtral-mixture": ("mixtral-8x7b-1chip-serve", {}),
    "granite-hybrid": ("granite-4.0-h-micro-1chip-serve", {}),
    "hybrid-mixture": ("granite-4.0-h-small-1chip-serve", {}),
    "sala": ("minicpm-sala-1chip-serve", {}),
    "mla": ("kimi-vl-a3b-1chip-serve", {}),
    "power-retention": ("brumby-14b-1chip-serve", {}),
    "phi4flash": ("phi-4-mini-flash-1chip-serve", {}),
}


@pytest.fixture(autouse=True)
def interpreted(monkeypatch):
    """A block-sparse chunk is a Pallas kernel that refuses to run off the TPU
    unless interpreted; no other stack's CPU path is a kernel."""
    monkeypatch.setattr(sparse_block_attention, "INTERPRET_OFF_TPU", True)


def _tiny(stack, dtype):
    """(ModelConfig, served params, the rehearsal's program sizes) of ``stack``."""
    name, replaced = STACKS[stack]
    cfg = _file_config(name)
    cfg = {**cfg, **cfg["rehearsal"]}
    mc = dataclasses.replace(_model_config(cfg, stack + "-tiny"), **replaced)
    params = tfm.served_format(tfm.init_params(jax.random.PRNGKey(7), mc), dtype)
    return mc, params, cfg["program"]


def _tokens(n, vocab, stream=0):
    return np.asarray(jax.random.randint(jax.random.PRNGKey(100 + stream), (n,), 0, vocab), np.int32)


def _parent_prefill_forward(params, toks, cache, row_idx, n_valid=None, *, cfg, compute_dtype):
    """``serving._prefill_forward`` as it stood before: a stack without a
    cross-decoder formed all T rows of logits and then took one."""
    if cfg.cross_decoder_start is not None:
        logits, cache = forward_with_cache(params, toks, cache, cfg, compute_dtype=compute_dtype,
                                           n_valid=n_valid, logits_row=row_idx)
        return logits[0, 0], cache
    logits, cache = forward_with_cache(params, toks, cache, cfg, compute_dtype=compute_dtype, n_valid=n_valid)
    return logits[0, row_idx], cache


# the one row against the full call ----------------------------------------------------


@pytest.mark.parametrize("dtype", [F32, BF16], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("stack", STACKS)
def test_the_one_row_is_the_full_calls_row_and_the_cache_is_the_full_walks(stack, dtype):
    """A prompt's second chunk, the bucket's padding behind its last real
    token: ``logits_row=r`` gives ``[B, 1, V]`` equal to row ``r`` of the full
    call, and the same cache."""
    mc, params, p = _tiny(stack, dtype)
    T = p["prefill_chunk"]
    n_valid = T - 5
    toks = jnp.asarray(_tokens(2 * T, mc.vocab_size))[None]
    c0 = init_cache(mc, 1, p["max_len"], dtype=dtype, max_chunk=T)
    assert c0.ring == (stack == "dense-gqa-ring")
    full = jax.jit(partial(forward_with_cache, cfg=mc, compute_dtype=dtype))
    one = jax.jit(lambda params, toks, cache, n, r: forward_with_cache(
        params, toks, cache, mc, compute_dtype=dtype, n_valid=n, logits_row=r))
    _, c1 = full(params, toks[:, :T], c0, n_valid=jnp.int32(T))
    want, c_full = full(params, toks[:, T:], c1, n_valid=jnp.int32(n_valid))
    for r in (n_valid - 1, 0):
        got, c_one = one(params, toks[:, T:], c1, jnp.int32(n_valid), jnp.int32(r))
        assert got.shape == (1, 1, mc.vocab_size) and got.dtype == jnp.float32
        got, row = np.asarray(got[0, 0]), np.asarray(want[0, r])
        # Bit for bit where the same contraction runs on one row of the same matrix; the
        # CPU sums a transposed (tied) table's one-row product in another order than its
        # matrix one, likewise bfloat16 operands, and a cross-decoder at one row
        # contracts other shapes from the shared layer on: float32 rounding (read 3e-7).
        exact = dtype == F32 and not mc.tied_head and mc.cross_decoder_start is None
        assert np.abs(got - row).max() <= (0.0 if exact else 1e-6) * np.abs(row).max()
        same = jax.tree.map(lambda a, b: bool((np.asarray(a) == np.asarray(b)).all()), c_one, c_full)
        assert all(jax.tree.leaves(same)), same


def test_a_stack_without_a_cross_decoder_still_refuses_a_tail_row():
    """``scan_layers`` keeps its refusal: ``forward_with_cache`` does not pass
    the row on for such a stack, it cuts ``x`` itself."""
    generate = sys.modules["tpu_engine.generate"]
    mc, params, p = _tiny("dense-gqa", F32)
    cache = init_cache(mc, 1, p["max_len"], dtype=F32)
    x = jnp.zeros((1, 4, mc.d_model), F32)
    pos = jnp.arange(4, dtype=jnp.int32)
    with pytest.raises(ValueError, match="no cross-decoder"):
        generate.scan_layers(x, params["layers"], mc, cache, lambda arr, rows, at: arr, pos, pos[None],
                             jnp.ones((1, 4), bool), tail_row=jnp.int32(1))


# through ``ContinuousBatcher`` ------------------------------------------------------------


def _serve(params, mc, p, prompts, new):
    engine = serving.ContinuousBatcher(params, mc, max_slots=2, max_len=p["max_len"], compute_dtype=F32,
                                       prefill_chunk=p["prefill_chunk"], prefill_pad_to=p["prefill_chunk"],
                                       chunk_steps=4)
    ids = [engine.submit(q, max_new_tokens=new) for q in prompts]
    for _ in range(400):
        engine.step()
        if all(engine.result(i)["status"] == "done" for i in ids):
            break
    return [engine.result(i)["tokens"] for i in ids], engine


@pytest.mark.parametrize("stack", STACKS)
def test_the_batcher_serves_the_parents_tokens_and_counts_one_head_row_a_chunk(stack, monkeypatch):
    """Multi-chunk prompts (two end in their bucket's padding) through two
    slots: the greedy tokens are those of an engine whose prefill program forms
    every row's logits and takes one, and the head ran for one row a chunk."""
    mc, params, p = _tiny(stack, F32)
    C = p["prefill_chunk"]
    lengths = (2 * C + C // 2 - 3, C + 7, 3 * C)
    prompts = [_tokens(n, mc.vocab_size, 1 + i).tolist() for i, n in enumerate(lengths)]
    got, engine = _serve(params, mc, p, prompts, 6)
    monkeypatch.setattr(serving, "_prefill_forward", _parent_prefill_forward)
    want, _ = _serve(params, mc, p, prompts, 6)
    assert got == want and all(len(t) == 6 for t in got)
    st = engine.stats()
    chunks = sum(-(-n // C) for n in lengths)
    assert st["prefill_tokens_computed_total"] == chunks * C
    # a stack with a cross-decoder forms logits, at that one position, in a prompt's last chunk only
    cross = mc.cross_decoder_start is not None
    assert st["prefill_head_rows_total"] == (len(prompts) if cross else chunks)
    assert st["prefill_positions_cross_decoder_total"] == (len(prompts) if cross else 0)


# the programs at their real sizes ---------------------------------------------------------


def _prefill_programs(name, forward=None):
    """(ModelConfig, program sizes, {program: StableHLO text}) of a serving
    configuration's prefill programs at its REAL size, lowered from shapes."""
    cfg = _file_config(name)
    mc, p = _model_config(cfg, name), cfg["program"]
    sds = lambda t: jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), t)  # noqa: E731
    params = sds(jax.eval_shape(lambda k: tfm.init_params(k, mc, dtype=BF16), jax.random.PRNGKey(0)))
    c1 = sds(jax.eval_shape(lambda: init_cache(mc, 1, p["max_len"], dtype=BF16)))
    toks, row = jax.ShapeDtypeStruct((1, p["prefill_chunk"]), jnp.int32), jax.ShapeDtypeStruct((), jnp.int32)
    n_valid = (row,) if layer_state.keeps_whole_state(c1.layers) else ()  # as the batcher passes it
    prefill = jax.jit(partial(forward or serving._prefill_forward, cfg=mc, compute_dtype=BF16))
    texts = {"prefill_chunk": prefill.lower(params, toks, c1, row, *n_valid).as_text()}
    if mc.cross_decoder_start is not None:
        ingest = jax.jit(partial(serving._prefill_ingest, cfg=mc, compute_dtype=BF16))
        texts["prefill_ingest"] = ingest.lower(params, toks, c1, *n_valid).as_text()
    return mc, p, texts


def _tensors_of(text, elements):
    """The tensor shapes the text names that hold ``elements`` elements."""
    shapes = {tuple(int(d) for d in dims[:-1].split("x")) for dims in re.findall(r"tensor<((?:\d+x)+)[a-z]", text)}
    return {s for s in shapes if math.prod(s) == elements}


@pytest.mark.parametrize("name", ["minicpm-sala-1chip-serve", "kimi-vl-a3b-1chip-serve", "brumby-14b-1chip-serve"])
def test_a_long_chunks_program_holds_no_tensor_of_chunk_by_vocabulary(name):
    """The three configurations that ingest prompts in chunks of 2 048: the
    ``prefill_chunk`` program forms logits ``[1, 1, V]`` and nothing of
    ``T x V`` elements but the head's own weights where D = T (Kimi-VL's
    table); the parent's form of the same program held ``[1, T, V]``."""
    mc, p, texts = _prefill_programs(name)
    T, V = p["prefill_chunk"], mc.vocab_size
    assert T == 2048
    weights = {(V, mc.d_model), (mc.d_model, V)}
    text = texts["prefill_chunk"]
    assert _tensors_of(text, T * V) <= weights
    assert f"-> tensor<1x1x{V}xf32>" in text
    _, _, parent = _prefill_programs(name, _parent_prefill_forward)
    assert (1, T, V) in _tensors_of(parent["prefill_chunk"], T * V)


def test_phi4flashs_two_prefill_programs_are_the_parents():
    """The stack was on this path already: its ``prefill_chunk`` program lowers
    to the text of the parent's form of ``_prefill_forward`` (the module's name
    apart), and its ``prefill_ingest`` program, which ``_prefill_forward`` has
    no part in, still forms no logits."""
    name = "phi-4-mini-flash-1chip-serve"
    mc, _, texts = _prefill_programs(name)
    _, _, parent = _prefill_programs(name, _parent_prefill_forward)
    assert texts["prefill_chunk"] == parent["prefill_chunk"].replace("_parent_prefill_forward", "_prefill_forward")
    assert f"x{mc.vocab_size}xf32>" in texts["prefill_chunk"]
    assert f"x{mc.vocab_size}xf32>" not in texts["prefill_ingest"]
