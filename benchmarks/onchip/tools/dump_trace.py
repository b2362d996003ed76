"""Print what a ``.xplane.pb`` holds: planes, lines, the commonest event names
and the stat keys of an event — to look at one trace by hand before trusting
the reducer.   python3 benchmarks/onchip/tools/dump_trace.py <file.xplane.pb>"""

import collections
import sys

from jax.profiler import ProfileData

data = ProfileData.from_file(sys.argv[1])
for plane in data.planes:
    print(f"PLANE {plane.name!r}")
    for line in plane.lines:
        events = list(line.events)
        names = collections.Counter(e.name for e in events)
        dur = sum(e.duration_ns for e in events) / 1e9
        print(f"  LINE {line.name!r}: {len(events)} events, {dur:.4f} s")
        for name, n in names.most_common(int(sys.argv[2]) if len(sys.argv) > 2 else 8):
            print(f"     {n:6d} x {name[:140]}")
        if events:
            try:
                print("     stats of first event:", [(k, str(v)[:80]) for k, v in events[0].stats][:12])
            except Exception as e:  # noqa: BLE001
                print("     stats unreadable:", e)
