"""KV-cache decode correctness: prefill+decode logits must match the
training forward pass position-for-position (dense models), plus sampling
and MoE-decode behavior."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_engine.generate import (
    forward_with_cache,
    generate,
    init_cache,
    sample_token,
)
from tpu_engine.models import transformer as tfm


def _setup(name="gpt-tiny", seed=0, B=2, S=16):
    cfg = tfm.MODEL_CONFIGS[name]
    params = tfm.init_params(jax.random.PRNGKey(seed), cfg)
    tokens = jax.random.randint(
        jax.random.PRNGKey(seed + 1), (B, S), 0, cfg.vocab_size, jnp.int32
    )
    return cfg, params, tokens


def test_prefill_then_decode_matches_forward():
    cfg, params, tokens = _setup()
    B, S = tokens.shape
    full = tfm.forward(params, tokens, cfg, compute_dtype=jnp.float32)

    prefill_len = 5
    cache = init_cache(cfg, B, S, dtype=jnp.float32)
    logits, cache = forward_with_cache(
        params, tokens[:, :prefill_len], cache, cfg, compute_dtype=jnp.float32
    )
    np.testing.assert_allclose(
        np.asarray(logits), np.asarray(full[:, :prefill_len]), atol=2e-4, rtol=2e-4
    )
    # Teacher-forced single-token decode for the remaining positions.
    for t in range(prefill_len, S):
        logits, cache = forward_with_cache(
            params, tokens[:, t : t + 1], cache, cfg, compute_dtype=jnp.float32
        )
        np.testing.assert_allclose(
            np.asarray(logits[:, 0]), np.asarray(full[:, t]), atol=2e-4, rtol=2e-4
        )
    assert int(cache.length) == S


def test_decode_gqa_model():
    # A GQA variant (KV heads < heads): decode contracts the grouped query
    # heads (KV-major, head h on KV head h // G) against the cache as stored.
    cfg, params, tokens = _setup()
    cfg = cfg.with_(n_kv_heads=cfg.n_heads // 2)
    params = tfm.init_params(jax.random.PRNGKey(3), cfg)
    full = tfm.forward(params, tokens, cfg, compute_dtype=jnp.float32)
    cache = init_cache(cfg, tokens.shape[0], tokens.shape[1], dtype=jnp.float32)
    logits, _ = forward_with_cache(
        params, tokens, cache, cfg, compute_dtype=jnp.float32
    )
    np.testing.assert_allclose(
        np.asarray(logits), np.asarray(full), atol=2e-4, rtol=2e-4
    )


def test_greedy_generate_shape_and_determinism():
    cfg, params, tokens = _setup(S=8)
    out1 = generate(params, tokens, cfg, max_new_tokens=6, compute_dtype=jnp.float32)
    out2 = generate(params, tokens, cfg, max_new_tokens=6, compute_dtype=jnp.float32)
    assert out1.shape == (2, 8 + 6)
    assert out1.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))
    np.testing.assert_array_equal(np.asarray(out1[:, :8]), np.asarray(tokens))
    assert int(jnp.min(out1)) >= 0 and int(jnp.max(out1)) < cfg.vocab_size


def test_greedy_matches_stepwise_argmax():
    # generate() must reproduce manual argmax teacher-forcing on its own output.
    cfg, params, tokens = _setup(B=1, S=4)
    out = generate(params, tokens, cfg, max_new_tokens=3, compute_dtype=jnp.float32)
    seq = out
    for t in range(4, 7):
        logits = tfm.forward(params, seq[:, :t], cfg, compute_dtype=jnp.float32)
        expect = jnp.argmax(logits[:, -1], axis=-1)
        np.testing.assert_array_equal(np.asarray(seq[:, t]), np.asarray(expect))


def test_sampling_reproducible_and_temperature():
    cfg, params, tokens = _setup(S=8)
    rng = jax.random.PRNGKey(42)
    a = generate(params, tokens, cfg, max_new_tokens=5, rng=rng,
                 temperature=1.0, top_k=50, compute_dtype=jnp.float32)
    b = generate(params, tokens, cfg, max_new_tokens=5, rng=rng,
                 temperature=1.0, top_k=50, compute_dtype=jnp.float32)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_sample_token_greedy_vs_topk():
    logits = jnp.array([[0.0, 5.0, 1.0, -2.0]])
    assert int(sample_token(logits, jax.random.PRNGKey(0))[0]) == 1
    # top_k=1 sampling always picks the argmax regardless of temperature.
    t = sample_token(logits, jax.random.PRNGKey(7), temperature=2.0, top_k=1)
    assert int(t[0]) == 1


def test_moe_decode_runs_and_is_finite():
    cfg, params, tokens = _setup(name="moe-tiny")
    out = generate(params, tokens, cfg, max_new_tokens=4, compute_dtype=jnp.float32)
    assert out.shape == (2, 16 + 4)
    cache = init_cache(cfg, 2, 16, dtype=jnp.float32)
    logits, _ = forward_with_cache(params, tokens, cache, cfg, compute_dtype=jnp.float32)
    assert bool(jnp.all(jnp.isfinite(logits)))


def test_top_p_filters_tail():
    # One dominant token (~97% mass): top_p=0.5 must always pick it.
    logits = jnp.array([[8.0, 4.0, 3.0, 2.0]])
    for seed in range(20):
        t = sample_token(
            logits, jax.random.PRNGKey(seed), temperature=1.0, top_p=0.5
        )
        assert int(t[0]) == 0
    # top_p=1.0 keeps the full distribution: other tokens appear.
    seen = {
        int(sample_token(logits, jax.random.PRNGKey(s), temperature=2.0, top_p=1.0)[0])
        for s in range(200)
    }
    assert len(seen) > 1


def test_sampling_param_sweep_does_not_recompile():
    from tpu_engine.generate import _generate_jit

    cfg, params, tokens = _setup(S=8)
    base = _generate_jit._cache_size()
    generate(params, tokens, cfg, max_new_tokens=3, temperature=0.7,
             top_p=0.9, compute_dtype=jnp.float32)
    after_first = _generate_jit._cache_size()
    generate(params, tokens, cfg, max_new_tokens=3, temperature=1.3,
             top_p=0.5, compute_dtype=jnp.float32)
    generate(params, tokens, cfg, max_new_tokens=3, temperature=0.2,
             top_p=0.95, compute_dtype=jnp.float32)
    assert _generate_jit._cache_size() == after_first > base


def test_sliding_window_decode_matches_forward():
    """Windowed decode must match the windowed training forward position-
    for-position — seq 24 > window 6, so old keys really drop out."""
    cfg, params, tokens = _setup(S=24)
    cfg = cfg.with_(sliding_window=6)
    B, S = tokens.shape
    full = tfm.forward(params, tokens, cfg, compute_dtype=jnp.float32)

    cache = init_cache(cfg, B, S, dtype=jnp.float32)
    logits, cache = forward_with_cache(
        params, tokens[:, :4], cache, cfg, compute_dtype=jnp.float32
    )
    np.testing.assert_allclose(
        np.asarray(logits), np.asarray(full[:, :4]), atol=2e-4, rtol=2e-4
    )
    for t in range(4, S):
        logits, cache = forward_with_cache(
            params, tokens[:, t : t + 1], cache, cfg, compute_dtype=jnp.float32
        )
        np.testing.assert_allclose(
            np.asarray(logits[:, 0]), np.asarray(full[:, t]), atol=2e-4, rtol=2e-4
        )


def test_rolling_cache_matches_forward():
    """Ring-buffer cache: a windowed model decodes with O(window) cache
    slots; logits must still match the full training forward even after
    the buffer has wrapped several times."""
    cfg, params, tokens = _setup(S=40)
    cfg = cfg.with_(sliding_window=6)
    B, S = tokens.shape
    full = tfm.forward(params, tokens, cfg, compute_dtype=jnp.float32)

    prefill = 4
    cache = init_cache(cfg, B, S, dtype=jnp.float32, max_chunk=prefill)
    assert cache.max_len == 6 + prefill - 1  # O(window), not O(seq)
    logits, cache = forward_with_cache(
        params, tokens[:, :prefill], cache, cfg, compute_dtype=jnp.float32
    )
    np.testing.assert_allclose(
        np.asarray(logits), np.asarray(full[:, :prefill]), atol=2e-4, rtol=2e-4
    )
    for t in range(prefill, S):  # wraps the 9-slot buffer 4+ times
        logits, cache = forward_with_cache(
            params, tokens[:, t : t + 1], cache, cfg, compute_dtype=jnp.float32
        )
        np.testing.assert_allclose(
            np.asarray(logits[:, 0]), np.asarray(full[:, t]), atol=2e-4, rtol=2e-4,
            err_msg=f"position {t}",
        )


def test_rolling_cache_rejects_oversized_chunk():
    cfg, params, tokens = _setup(S=32)
    cfg = cfg.with_(sliding_window=8)
    cache = init_cache(cfg, 2, 32, dtype=jnp.float32, max_chunk=4)  # 11 slots
    with pytest.raises(ValueError, match="cache slots"):
        forward_with_cache(params, tokens[:, :8], cache, cfg,
                           compute_dtype=jnp.float32)


def test_windowed_generate_end_to_end():
    """generate() on a windowed model allocates an O(window) cache and
    produces identical tokens to a full-size-cache run."""
    cfg, params, tokens = _setup(S=8)
    wcfg = cfg.with_(sliding_window=5)
    out = generate(params, tokens, wcfg, max_new_tokens=20,
                   compute_dtype=jnp.float32)
    assert out.shape == (2, 28)
    # Reference: same model, cache big enough to never wrap.
    cache = init_cache(wcfg, 2, 28, dtype=jnp.float32)
    toks = tokens
    logits, cache = forward_with_cache(params, toks, cache, wcfg,
                                       compute_dtype=jnp.float32)
    nxt = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)
    for _ in range(20):
        toks = jnp.concatenate([toks, nxt[:, None]], axis=1)
        logits, cache = forward_with_cache(params, nxt[:, None], cache, wcfg,
                                           compute_dtype=jnp.float32)
        nxt = jnp.argmax(logits[:, 0], -1).astype(jnp.int32)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(toks))


def test_windowed_generate_short_run():
    """Short generations on windowed models (max_new_tokens < window-1)
    allocate a full-size (non-ring) cache and must not trip the ring guard."""
    cfg, params, tokens = _setup(S=8)
    out = generate(params, tokens, cfg.with_(sliding_window=5),
                   max_new_tokens=2, compute_dtype=jnp.float32)
    assert out.shape == (2, 10)


def test_ring_decode_requires_full_window():
    """T=1 decode on a ring cache with fewer slots than the window must
    raise, not silently drop in-window keys."""
    from tpu_engine.generate import KVCache

    cfg, params, tokens = _setup(S=8)
    cfg = cfg.with_(sliding_window=8)
    small = init_cache(cfg, 2, 4, dtype=jnp.float32)
    small = KVCache(layers=small.layers, pos=small.pos, length=small.length,
                    ring=True)  # force ring with M=4 < window=8
    with pytest.raises(ValueError, match="cache slots"):
        forward_with_cache(params, tokens[:, :1], small, cfg,
                           compute_dtype=jnp.float32)


# ---------------------------------------------------------------------------
# Speculative decoding
# ---------------------------------------------------------------------------


def test_speculative_matches_greedy():
    """Speculative decode must equal plain greedy decoding of the target
    exactly — with a perfect draft (same model) and an adversarial one
    (different random init, frequent rejections)."""
    from tpu_engine.generate import speculative_generate

    cfg, params, _ = _setup()
    draft = tfm.init_params(jax.random.PRNGKey(9), cfg)
    prompt = jnp.asarray([[3, 1, 4, 1, 5]], jnp.int32)
    ref = generate(params, prompt, cfg, max_new_tokens=24,
                   compute_dtype=jnp.float32)

    same, rounds = speculative_generate(params, params, prompt, cfg, cfg, 24,
                                        gamma=4, compute_dtype=jnp.float32,
                                        return_stats=True)
    np.testing.assert_array_equal(np.asarray(same), np.asarray(ref))
    # A perfect draft (same model) must accept all gamma proposals every
    # round: 24 tokens / (gamma+1) per round = 5 rounds. More means the
    # draft cache has holes (e.g. its own last proposal never ingested).
    assert rounds == 5, rounds

    diff = speculative_generate(params, draft, prompt, cfg, cfg, 24,
                                gamma=3, compute_dtype=jnp.float32)
    np.testing.assert_array_equal(np.asarray(diff), np.asarray(ref))


def test_speculative_windowed_ring_cache():
    """Speculative rewind composes with the sliding-window ring cache."""
    from tpu_engine.generate import speculative_generate

    cfg, params, _ = _setup()
    wcfg = cfg.with_(sliding_window=6)
    draft = tfm.init_params(jax.random.PRNGKey(9), wcfg)
    prompt = jnp.asarray([[3, 1, 4, 1, 5]], jnp.int32)
    ref = generate(params, prompt, wcfg, max_new_tokens=24,
                   compute_dtype=jnp.float32)
    spec = speculative_generate(params, draft, prompt, wcfg, wcfg, 24,
                                gamma=3, compute_dtype=jnp.float32)
    np.testing.assert_array_equal(np.asarray(spec), np.asarray(ref))


def test_speculative_validation():
    from tpu_engine.generate import speculative_generate

    cfg, params, tokens = _setup()
    with pytest.raises(ValueError, match="batch size 1"):
        speculative_generate(params, params, tokens, cfg, cfg, 4)
    with pytest.raises(ValueError, match="gamma"):
        speculative_generate(params, params, tokens[:1], cfg, cfg, 4, gamma=0)


def test_gpt2_decode_matches_forward():
    """GPT-2 decode (learned positions at embed, biases, LayerNorm) must
    match the training forward position-for-position."""
    cfg, params, tokens = _setup(name="gpt2-tiny")
    B, S = tokens.shape
    full = tfm.forward(params, tokens, cfg, compute_dtype=jnp.float32)
    cache = init_cache(cfg, B, S, dtype=jnp.float32)
    logits, cache = forward_with_cache(params, tokens[:, :5], cache, cfg,
                                       compute_dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(full[:, :5]),
                               atol=2e-4, rtol=2e-4)
    for t in range(5, S):
        logits, cache = forward_with_cache(params, tokens[:, t:t+1], cache, cfg,
                                           compute_dtype=jnp.float32)
        np.testing.assert_allclose(np.asarray(logits[:, 0]), np.asarray(full[:, t]),
                                   atol=2e-4, rtol=2e-4)


def test_gemma_decode_matches_forward():
    """Gemma decode (sqrt(d)-scaled embeddings, zero-centred RMSNorm,
    GeGLU, decoupled head_dim, MQA, tied head) must match the training
    forward position-for-position."""
    cfg, params, tokens = _setup(name="gemma-tiny")
    B, S = tokens.shape
    full = tfm.forward(params, tokens, cfg, compute_dtype=jnp.float32)
    cache = init_cache(cfg, B, S, dtype=jnp.float32)
    logits, cache = forward_with_cache(params, tokens[:, :5], cache, cfg,
                                       compute_dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(full[:, :5]),
                               atol=2e-4, rtol=2e-4)
    for t in range(5, 9):
        logits, cache = forward_with_cache(params, tokens[:, t:t+1], cache, cfg,
                                           compute_dtype=jnp.float32)
        np.testing.assert_allclose(np.asarray(logits[:, 0]), np.asarray(full[:, t]),
                                   atol=2e-4, rtol=2e-4)


def test_gpt2_position_table_bounds():
    """Out-of-table positions must raise, not silently clamp."""
    cfg, params, _ = _setup(name="gpt2-tiny")
    long_cfg = cfg.with_(max_seq_len=8)
    params8 = tfm.init_params(jax.random.PRNGKey(0), long_cfg)
    toks = jnp.zeros((1, 16), jnp.int32)
    with pytest.raises(ValueError, match="position table"):
        tfm.forward(params8, toks, long_cfg, compute_dtype=jnp.float32)
    with pytest.raises(ValueError, match="position table"):
        generate(params8, toks[:, :4], long_cfg, max_new_tokens=8,
                 compute_dtype=jnp.float32)


def test_int8_kv_cache_close_to_full_precision():
    """Quantised (int8 + per-(position, head) scales) cache: logits within
    ~1% of the full-precision cache, half the storage."""
    cfg, params, tokens = _setup()
    B, S = tokens.shape
    c_full = init_cache(cfg, B, S, dtype=jnp.float32)
    c_q = init_cache(cfg, B, S, dtype=jnp.float32, kv_quant=True)
    kv = c_q.layers["attn"]
    assert kv["k"].dtype == jnp.int8 and c_q.quantized
    assert kv["k_scale"].shape == kv["k"].shape[:-1] + (1,)
    l_full, _ = forward_with_cache(params, tokens, c_full, cfg, jnp.float32)
    l_q, _ = forward_with_cache(params, tokens, c_q, cfg, jnp.float32)
    scale = float(jnp.max(jnp.abs(l_full)))
    assert float(jnp.max(jnp.abs(l_full - l_q))) < 0.02 * scale


def test_int8_kv_cache_greedy_generation_matches():
    cfg, params, _ = _setup()
    prompt = jnp.asarray([[1, 2, 3, 4, 5, 6, 7, 8]], jnp.int32)
    full = generate(params, prompt, cfg, max_new_tokens=10,
                    compute_dtype=jnp.float32)
    q = generate(params, prompt, cfg, max_new_tokens=10,
                 compute_dtype=jnp.float32, kv_quant=True)
    # Random-init logit gaps dwarf the ~1% quantisation error, so greedy
    # decode must agree exactly here.
    assert np.array_equal(np.asarray(full), np.asarray(q))


def test_int8_kv_cache_windowed_ring():
    """Quantised cache composes with the sliding-window ring buffer: the
    scale rows wrap with the code rows."""
    cfg, params, _ = _setup()
    cfgw = cfg.with_(sliding_window=6)
    prompt = jnp.asarray([[3, 1, 4, 1, 5, 9, 2, 6]], jnp.int32)
    full = generate(params, prompt, cfgw, max_new_tokens=12,
                    compute_dtype=jnp.float32)
    q = generate(params, prompt, cfgw, max_new_tokens=12,
                 compute_dtype=jnp.float32, kv_quant=True)
    assert np.asarray(q).shape == np.asarray(full).shape
    assert (np.asarray(q) == np.asarray(full)).mean() > 0.9


# Compile-heavy module: excluded from the fast core run (pytest -m "not slow").
pytestmark = pytest.mark.slow


def test_qwen_decode_matches_forward():
    """Qwen3 decode (per-head qk-norm before RoPE, decoupled head_dim, GQA)
    must match the training forward position-for-position."""
    cfg, params, tokens = _setup(name="qwen-tiny")
    B, S = tokens.shape
    full = tfm.forward(params, tokens, cfg, compute_dtype=jnp.float32)
    cache = init_cache(cfg, B, S, dtype=jnp.float32)
    logits, cache = forward_with_cache(params, tokens[:, :5], cache, cfg,
                                       compute_dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(full[:, :5]),
                               atol=2e-4, rtol=2e-4)
    for t in range(5, 9):
        logits, cache = forward_with_cache(params, tokens[:, t:t+1], cache, cfg,
                                           compute_dtype=jnp.float32)
        np.testing.assert_allclose(np.asarray(logits[:, 0]), np.asarray(full[:, t]),
                                   atol=2e-4, rtol=2e-4)
