"""Control plane beside the loop: of the first chip's idle seconds in the
traced window, the share during which a ``tpu_ctl.*`` span (the scheduler's
pass, ``TPUManager.get_fleet_status``, the serving fleet's tick / route /
result) was open on a host line other than the loop's. Prints the split by
span name (nested spans both count)."""

from harness import program_threads


def read(run, name):
    tr = program_threads.of_run(run)
    if not tr or not any(s[2].startswith(program_threads.CTL_PREFIX) for s in tr["spans"]):
        return None
    idle = program_threads.idle_beside(tr)
    if not idle["idle_s"]:
        return None
    program_threads.say(name, **idle)
    return 100.0 * idle["beside_s"] / idle["idle_s"]
