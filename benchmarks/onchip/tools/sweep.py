"""One run of a cell with numbers of its traffic file replaced: the sweep that
found the chat cell's knee (PERF.md) ran ``rate_per_s`` at 0.6 to 1.4. Not a
benchmark run: ``run.py`` itself reads only the committed files.

    python3 benchmarks/onchip/tools/sweep.py rate_per_s=1.2 --workload mistral-7b.serve-chat --seed 213 --seconds 45
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import run  # noqa: E402

if __name__ == "__main__":
    sets = [a for a in sys.argv[1:] if "=" in a and not a.startswith("-")]
    rest = [a for a in sys.argv[1:] if a not in sets]
    update = {k: json.loads(v) for k, v in (a.split("=", 1) for a in sets)}
    sys.exit(run.main(rest, traffic_update=update))
