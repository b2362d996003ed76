"""Batcher: share of the decode tokens the engine computed that it threw away
(a dispatch computes ``chunk_steps`` tokens for every active slot; what runs past
a finished request is discarded), from ``ContinuousBatcher.stats()``'s
``decode_tokens_computed_total`` and ``decode_tokens_emitted_total`` over the
engine's life, warm-up included."""


def read(run, name):
    st = run.get("engine_stats") or {}
    computed = st.get("decode_tokens_computed_total")
    if not computed:
        return None
    return 100.0 * (1.0 - st["decode_tokens_emitted_total"] / computed)
