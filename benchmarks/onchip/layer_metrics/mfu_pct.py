"""Train program: model FLOP/s utilisation — the benchmark's exact causal-and-
window FLOP count per token (``harness/counts.py``; recomputation not counted)
times tokens/s/chip over the chip's published bf16 peak."""

from harness.peaks import peaks


def read(run, name):
    if run["device"]["platform"] != "tpu" or not run.get("rate_chip"):
        return None
    return 100.0 * run["flops_per_token"] * run["rate_chip"] / peaks(run["device"]["kind"])["flops_bf16"]
