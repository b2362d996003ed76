"""Model step: share of the decode token-steps the engine computed whose
context was past the sparse layers' ``dense_len`` (they chose their blocks and
read nothing else of the row), from ``ContinuousBatcher.stats()``'s
``decode_tokens_sparse_total`` over ``decode_tokens_computed_total``, over the
engine's life, warm-up included."""


def read(run, name):
    st = run.get("engine_stats") or {}
    computed = st.get("decode_tokens_computed_total")
    if not computed or "decode_tokens_sparse_total" not in st:
        return None
    return 100.0 * st["decode_tokens_sparse_total"] / computed
