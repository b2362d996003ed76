"""Model step: the serving cells' share of the whole step's peak, as ``mfu_pct``
is training's, and the bound of every kernel's claim. The bytes one decode step
must move (what the configuration's counts module, found through
``harness/counts_for.py``, says its ``decode_step`` needs: the weights once in
the serving dtype, for a mixture the experts some row chose, keys, values,
latent or chosen blocks at the rows' real lengths, a recurrent state in and out
for every slot) over peak HBM bytes/s, against the traced device time of one
decode step: the median run of the decode program over the chunk's steps.
Which runs, which rows and which count of experts is the module's own
``decode_step``; a configuration no module knows reads nothing."""

from harness.counts_for import counts_for
from harness.peaks import peaks


def read(run, name):
    if not run.get("trace") or run["device"]["platform"] != "tpu":
        return None
    family = counts_for(run["cell"]["config"])
    step = family.decode_step(run) if family else None
    if not step:
        return None
    need, step_s = step
    return 100.0 * need / peaks(run["device"]["kind"])["hbm_bytes_per_s"] / step_s
