"""Kernels: the least time the chip could take for the attention the traced
steps needed — the larger of FLOPs over peak bf16 FLOP/s and bytes over peak
HBM bytes/s, causal-and-window exact, the remat forward not counted — over
the time the flash kernels took."""

from harness import counts
from harness.peaks import peaks


def read(run, name):
    tr = run.get("trace")
    if not tr or not tr["kernel_seconds"] or run["device"]["platform"] != "tpu":
        return None
    cfg = run["cell"]["config"]
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or cfg["hidden_size"] // heads
    window = cfg.get("sliding_window") or 0
    pk = peaks(run["device"]["kind"])
    rows, seq = run["rows_per_chip"], run["seq_len"]
    need = max(counts.flash_train_flops(seq, window, heads, hd, rows) / pk["flops_bf16"],
               counts.flash_train_bytes(seq, heads, kv, hd, rows) / pk["hbm_bytes_per_s"])
    steps = max((len(v) for v in tr["module_runs"].values()), default=0)
    took = sum(tr["kernel_seconds"].values())
    if not steps or not took:
        return None
    return 100.0 * steps * cfg["num_hidden_layers"] * need / took
