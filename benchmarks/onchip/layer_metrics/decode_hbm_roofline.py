"""Model step: bytes one decode step must read — every weight once in the
serving dtype (for experts, those the live slots are expected to route to),
keys and values at the slots' real lengths — over peak HBM bytes/s, against
the traced device time of one decode step: the median run of the program that
takes most device time in the trace, which in a serving cell is the decode
chunk (the trace calls it ``jit__unknown``: it is jitted from a partial), over
the chunk's steps. Contexts stay under the window in today's cells, so the sum
of contexts stands for the slots' lengths."""

import statistics

from harness import counts
from harness.peaks import peaks


def read(run, name):
    tr = run.get("trace")
    if not tr or run["device"]["platform"] != "tpu":
        return None
    if not tr["module_runs"] or not run.get("occupancy"):
        return None
    decode = max(tr["module_runs"].values(), key=sum)
    step_s = statistics.median(decode) / run["decode_chunk_steps"]
    cfg = run["cell"]["config"]
    live = statistics.fmean(run["occupancy"])
    hit = counts.expected_experts_hit(cfg.get("num_local_experts") or 0,
                                      cfg.get("num_experts_per_tok") or 0, live)
    ctx = statistics.fmean(run["dispatch_context"])
    need = counts.weight_bytes_per_decode_step(cfg, hit) + counts.kv_bytes_per_decode_step(cfg, [ctx])
    return 100.0 * need / peaks(run["device"]["kind"])["hbm_bytes_per_s"] / step_s
