"""Serving fleet: median time a request spent between the fleet's ``enqueue``
and its ``route`` to the replica (flight-recorder request spans)."""

import statistics


def read(run, name):
    waits = run.get("route_wait_ms")
    return statistics.median(waits) if waits else None
