"""Serving fleet: mean host milliseconds an engine step's iteration spends in
phase ``idle``, the wait the replica's driver (``ServingReplicaJob._run``,
``serve_forever``) takes between two steps through ``ContinuousBatcher.
idle_wait``, from the ``tpu_engine.batcher.idle`` annotations of the traced
window; 0 where the program names its waits and took none. Prints how many
were entered with work pending (``prefilling=`` / ``queued=`` not 0)."""

from harness import program_threads, program_trace


def read(run, name):
    tr = program_threads.of_run(run)
    if not tr or tr["loop"] != "batcher":
        return None
    waits = [args for it in tr["iterations"] for _, _, phase, args in it["phases"] if phase == "idle"]
    program_threads.say(name, waits=len(waits), iterations=len(tr["iterations"]),
                        with_work=sum(1 for a in waits if a.get("prefilling", "0") != "0" or a.get("queued", "0") != "0"))
    return program_trace.phase_ms_per_step(run, "batcher.idle") or 0.0
