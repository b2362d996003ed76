"""Control plane beside the loop: summed seconds of the scheduler's passes
(``tpu_ctl.scheduler.pass``, one ``FleetScheduler.poll()``) over the traced
window: the duty of the pump that runs beside every job it admitted. Prints
the passes' count, median length and period (median distance of their
starts) and the same for ``tpu_ctl.manager.fleet_status``."""

import statistics

from harness import program_threads

PASS, SAMPLE = "tpu_ctl.scheduler.pass", "tpu_ctl.manager.fleet_status"


def read(run, name):
    tr = program_threads.of_run(run)
    spent = program_threads.span_seconds(tr, PASS) if tr else None
    if spent is None:
        return None
    lo, hi = tr["window"]
    passes, samples = program_threads.span_ms(tr, PASS), program_threads.span_ms(tr, SAMPLE)
    program_threads.say(name, passes=len(passes), pass_ms_p50=statistics.median(passes),
                        pass_period_ms_p50=program_threads.span_period_ms(tr, PASS),
                        fleet_status_calls=len(samples),
                        fleet_status_ms_p50=statistics.median(samples) if samples else None)
    return 100.0 * spent / ((hi - lo) / 1e9)
