"""Model step: the bytes the decode reads of the ONE full-attention cache must
move — per reader (the full-attention layer and every cross-attention layer)
and step the keys and values of the rows that decode at their real lengths,
once — over peak HBM bytes/s, against the traced device time of the DECODE
program's ops under the scopes ``full_attn`` and ``cross_attn`` (the two
contractions of each reader). Every traced run of the decode program is held
against the rows IT decoded at the lengths THEY had, which the program says on
the annotation of the phase that waits for the run
(``counts_phi4flash.traced_decode``): this cell's device trace ends inside the
fill, so its runs decode 1 to 30 rows and not the window's 32, and a count at
the window's contexts may not be held against them. Bound by bytes: a step's
FLOPs over a lane (40 zero-padded query heads x 2 x 128 a reader) take a fifth
of its read's time at the peaks. A program that reads every lane of every slot,
whatever the rows' lengths (XLA's two contractions over the whole leaf), reads
low here by the pool's empty share: that is the number's purpose. Prints its
ingredients: the traced runs, and of the first and the last of them the rows,
their mean length, the device milliseconds of one reader's step and the run's
own share."""

from harness import counts_phi4flash as counts
from harness import program_threads
from harness.peaks import peaks


def read(run, name):
    cfg = run["cell"]["config"]
    if not run.get("trace") or run["device"]["platform"] != "tpu" or not counts.knows(cfg):
        return None
    steps, readers = run["decode_chunk_steps"], counts.shared_readers(cfg)
    bw = peaks(run["device"]["kind"])["hbm_bytes_per_s"]
    runs = [(steps * readers * counts.shared_kv_decode_bytes(cfg, counts.lanes_read(r, steps)),
             r["by_scope"].get("full_attn", 0.0) + r["by_scope"].get("cross_attn", 0.0), r)
            for r in counts.traced_decode(run)]
    runs = [x for x in runs if x[0] and x[1]]
    if not runs:
        return None
    program_threads.say(name, traced_decode_runs=len(runs), **{
        f"{which}_run": {"rows": r["rows"], "context_per_row": r["context"] / r["rows"], "run_ms": 1e3 * r["s"],
                         "reader_step_ms": 1e3 * took / steps / readers, "share_pct": 100.0 * need / bw / took}
        for which, (need, took, r) in (("first", runs[0]), ("last", runs[-1]))})
    return 100.0 * sum(need for need, *_ in runs) / bw / sum(took for _, took, _ in runs)
