"""Of the first chip's idle time in the traced window, the share that lies
inside a phase the program itself named (``tpu_engine.<loop>.<phase>``
annotations of the supervisor loop or the batcher's step) other than
``other``, the loop's un-attributed remainder: what a reader of the trace can
account for without guessing."""

from harness import program_trace


def read(run, name):
    tr = program_trace.of_run(run)
    if not tr or not tr["annotations"] or not tr["idle"]["idle_s"]:
        return None
    idle = tr["idle"]
    named = sum(s for phase, s in idle["by_phase"].items() if not phase.endswith(".other"))
    return 100.0 * named / idle["idle_s"]
