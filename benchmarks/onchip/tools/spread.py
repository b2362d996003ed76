"""Spread of each metric over two sets of runs of one cell, as the bounds are
set from it: the distance between the first and third quartile
(``statistics.quantiles(n=4)``) as a share of the median, the wider of the two
sets; and whether the second set's median moved against the first's.

    python3 benchmarks/onchip/tools/spread.py <dir with setA.*.out and setB.*.out>
"""

import glob
import json
import os
import statistics
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from harness.stats import iqr_share  # noqa: E402


def last_json(path):
    lines = [ln for ln in open(path) if ln.startswith("{")]
    return json.loads(lines[-1]) if lines else None


def main(d):
    sets = {}
    for tag in ("setA", "setB"):
        runs = [last_json(p) for p in sorted(glob.glob(f"{d}/{tag}.*.out"))]
        sets[tag] = [r for r in runs if r]
    names = sorted({k for rs in sets.values() for r in rs for k in r["metrics"]})
    print("correct:", {t: [r["correct"] for r in rs] for t, rs in sets.items()})
    for n in names:
        row = {}
        for tag, rs in sets.items():
            vals = [r["metrics"][n]["value"] for r in rs if n in r["metrics"]]
            if n == "setup_s":
                vals = vals[1:] if tag == "setA" else vals  # a set's first run may compile
            if len(vals) >= 2:
                row[tag] = {"median": statistics.median(vals), "spread": iqr_share(vals),
                            "min": min(vals), "max": max(vals), "n": len(vals)}
        if len(row) == 2:
            a, b = row["setA"], row["setB"]
            wider = max(a["spread"], b["spread"])
            print(f"{n}: medians {a['median']:.6g} / {b['median']:.6g} (shift {(b['median'] - a['median']) / a['median']:+.3%}); "
                  f"spreads {a['spread']:.3%} / {b['spread']:.3%}; 5x wider = {5 * wider:.3%}; "
                  f"range A {a['min']:.6g}..{a['max']:.6g} B {b['min']:.6g}..{b['max']:.6g}")
        else:
            print(n, row)


if __name__ == "__main__":
    main(sys.argv[1])
