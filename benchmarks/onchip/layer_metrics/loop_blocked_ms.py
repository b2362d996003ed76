"""The loop that feeds the chip: mean milliseconds of a dispatching iteration
its thread spent OFF the CPU inside host-only phases (batcher: ``handoff``,
``admit``, ``first_token``, ``stage``, ``emit``, ``other``; supervisor: every
phase but ``device``), from the ``blocked_us=`` arguments of the phase clock's
annotations (wall minus ``time.thread_time``): waiting for the interpreter
lock, a mutex or a sleep where it meant to compute. The mean over the traced
window, unclamped, because the thread's CPU clock may tick coarsely (10 ms on
the benchmark's machine: one iteration's value is then a 100 Hz sample, not a
reading, and may be negative). The CPU ticks of disjoint phases add up to the
ticks of their union, so the number of phases summed adds no error of its
own; what the mean is worth is its standard error over the window's
dispatches, printed beside it with the median and the 90th percentile."""

import statistics

from harness import program_threads, stats


def read(run, name):
    tr = program_threads.of_run(run)
    blocked = program_threads.blocked_ms(tr) if tr else []
    if not blocked:
        return None
    sem = statistics.stdev(blocked) / len(blocked) ** 0.5 if len(blocked) > 1 else None
    program_threads.say(name, iterations=len(blocked), sem=sem, p50=statistics.median(blocked),
                        p90=stats.percentile(blocked, 90))
    return statistics.fmean(blocked)
