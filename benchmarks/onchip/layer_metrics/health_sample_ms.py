"""Supervisor loop: median host milliseconds of the live fleet sample an
iteration takes under the chip's step (the ``tpu_engine.supervisor.
health_sample`` annotations, PR 37), which ``describe()["health_sample_ms"]``
reports from the host clock."""

import statistics

from harness import program_threads

SAMPLE = program_threads.LOOP_PREFIX + "supervisor.health_sample"


def read(run, name):
    tr = program_threads.of_run(run)
    samples = program_threads.span_ms(tr, SAMPLE) if tr else []
    return statistics.median(samples) if samples else None
