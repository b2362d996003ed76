"""Batcher: mean host milliseconds of a dispatch the chip did not work
through. Per iteration of the engine loop that holds a ``batcher.device``
phase: the iteration's length (its ``tpu_engine.batcher.other`` annotation,
begin to begin) minus the first chip's busy time inside it; the mean over
the traced window. What PERF.md worked out by hand as "iteration length minus
program medians". Prints the median, the 90th percentile, the split by
whether a prefill chunk rode in the dispatch (``stage``'s ``with_prefill=``)
and the median length of such an iteration (the loop's cycle, to hold
against ``scheduler_pass_busy_pct``'s ``pass_period_ms_p50``)."""

import statistics

from harness import program_threads, stats


def read(run, name):
    tr = program_threads.of_run(run)
    if not tr or tr["loop"] != "batcher":
        return None
    host = program_threads.dispatch_host_ms(tr)
    if not host:
        return None
    split = {"0": [], "1": []}
    for it, ms in zip(program_threads.dispatches(tr), host):
        carried = next((args.get("with_prefill") for _, _, phase, args in it["phases"] if phase == "stage"), None)
        if carried in split:
            split[carried].append(ms)
    cycle = [(it["t1"] - it["t0"]) / 1e6 for it in program_threads.dispatches(tr)]
    program_threads.say(name, dispatches=len(host), p50=statistics.median(host),
                        p90=stats.percentile(host, 90), cycle_ms_p50=statistics.median(cycle),
                        mean_decode_only=statistics.fmean(split["0"]) if split["0"] else None,
                        mean_with_prefill=statistics.fmean(split["1"]) if split["1"] else None,
                        n_with_prefill=len(split["1"]))
    return statistics.fmean(host)
