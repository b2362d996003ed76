"""Model step: the bytes the absorbed decode of the latent-attention (MLA)
layers must move — per layer-step the live rows' latent at their real lengths,
once (the window's decoding rows' context: ``counts_sala.decoding_context``),
and W_kvb — over peak HBM bytes/s, against the traced device time of the
DECODE program's ops under the scopes ``mla_absorb`` and ``mla_attend``.
Layer-steps of the traced window: runs of ``jit_decode_chunk`` x the chunk's
steps x the layers. Bound by bytes: a step's FLOPs over the latent (32 rows x
16 heads x 2 x 576 a lane) take a sixth of its read's time at the peaks. The
kernel ``mla_decode`` (``tpu_engine/ops/mla_decode.py``) runs under
``mla_attend``, so this is its roofline share with the absorption's two small
contractions beside it. A program that reads every lane of every slot, or the
latent once for the scores and again for the output (XLA's two contractions,
which the kernel replaced on the chip), reads low here: that is the number's
purpose."""

from harness import counts_hybrid, counts_mla_moe, counts_sala
from harness.peaks import peaks


def read(run, name):
    tr, cfg = run.get("trace"), run["cell"]["config"]
    if not tr or run["device"]["platform"] != "tpu" or not counts_mla_moe.is_mla_moe(cfg):
        return None
    took = [counts_sala.seconds_under(run, "decode_chunk", scope) for scope in ("mla_absorb", "mla_attend")]
    steps = len(counts_hybrid.decode_chunk_runs(tr)) * run["decode_chunk_steps"]
    context = counts_sala.decoding_context(run)
    if None in took or not sum(took) or not steps or not context:
        return None
    need = steps * counts_mla_moe.n_layers(cfg) * counts_mla_moe.absorbed_decode_bytes(cfg, context)
    return 100.0 * need / peaks(run["device"]["kind"])["hbm_bytes_per_s"] / sum(took)
