"""Model step: the FLOPs the expanded attention of the latent-attention (MLA)
layers needs for the traced prefill chunks — per layer and chunk the visible
lanes through W_kvb once, then scores and weighted values over the causal
triangle (``counts_mla_moe.mla_chunk_flops``; the chunk's place in its prompt
and its tokens from the ``tpu_engine.batcher.prefill`` annotations) — over peak
bf16 FLOP/s, against the traced device time of the PREFILL program's ops under
the scopes ``mla_expand`` and ``mla_attend``. Bound by FLOPs: a 2 048-token
chunk's attention is hundreds of FLOPs a byte of the row it reads. A program
that expands or scores lanes past the chunk's own (its staging row's padding)
reads low here."""

from harness import counts_mla_moe, counts_sala, program_trace
from harness.peaks import peaks


def read(run, name):
    parsed, cfg = program_trace.of_run(run), run["cell"]["config"]
    if not parsed or run["device"]["platform"] != "tpu" or not counts_mla_moe.is_mla_moe(cfg):
        return None
    took = [counts_sala.seconds_under(run, "prefill_chunk", scope) for scope in ("mla_expand", "mla_attend")]
    chunks = counts_sala.prefill_chunks(parsed)
    if None in took or not sum(took) or not chunks:
        return None
    size = run["cell"]["config"]["program"]["prefill_chunk"]
    need = sum(counts_mla_moe.mla_chunk_flops(cfg, index * size, tokens) for index, tokens in chunks)
    return 100.0 * counts_mla_moe.n_layers(cfg) * need / peaks(run["device"]["kind"])["flops_bf16"] / sum(took)
