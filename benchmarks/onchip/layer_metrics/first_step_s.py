"""Worker start-up: seconds from the launcher (or replica) call to the first
completed step or dispatch, by the harness clock."""


def read(run, name):
    return run.get("first_step_s")
