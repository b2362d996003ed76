"""The loop's blocking read: per ``tpu_engine.<loop>.device`` annotation, its
end minus max(its start, the end of the last program run on the first chip's
``XLA Modules`` line that ended inside it), 90th percentile in ms: how long
after the chip had finished the host got its answer. The chip is idle all
that while, and ``idle_named_pct`` books it to ``device``. Prints the median,
the mean and the share of reads later than 5 ms."""

import statistics

from harness import program_threads, stats


def read(run, name):
    tr = program_threads.of_run(run)
    lags = program_threads.read_lags_ms(tr) if tr else []
    if not lags:
        return None
    program_threads.say(name, reads=len(lags), p50=statistics.median(lags), mean=statistics.fmean(lags),
                        later_than_5ms_pct=100.0 * sum(1 for x in lags if x > 5.0) / len(lags))
    return stats.percentile(lags, 90)
