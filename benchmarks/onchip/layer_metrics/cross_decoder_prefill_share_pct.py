"""Batcher: share of the prompt positions the engine's prefill chunks computed
that ran the cross-decoder (the layers after the one full-attention layer), from
``ContinuousBatcher.stats()``'s ``prefill_positions_cross_decoder_total`` over
``prefill_tokens_computed_total`` (every position a prefill chunk computed, the
bucket's padding among them), over the engine's life, warm-up included. Logits
are wanted at a prompt's last position only, so one position a prompt is the
least; 100 would say every position of every chunk walked the whole stack."""


def read(run, name):
    st = run.get("engine_stats") or {}
    total = st.get("prefill_tokens_computed_total")
    if not total or "prefill_positions_cross_decoder_total" not in st:
        return None
    return 100.0 * st["prefill_positions_cross_decoder_total"] / total
