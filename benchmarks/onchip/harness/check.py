"""The comparison that decides ``correct``. Every number compared is printed
beside its limit; the limits are in the configuration file (``check``) and
PERF.md says what each was set from."""

from __future__ import annotations

import statistics


def worst_leaf_gap(prog: dict, ref: dict) -> tuple[float, str]:
    """Largest gap between the program's norm of a leaf and the reference's,
    against the reference's norm of that leaf or of the median leaf, whichever
    is larger (some leaves are all but zero). A leaf stacked over layers counts
    once per layer."""
    flat = [n for norms in ref.values() for n in norms]
    floor = statistics.median(flat)
    worst, where = 0.0, ""
    for name, ref_norms in ref.items():
        if name not in prog or len(prog[name]) != len(ref_norms):
            return float("inf"), f"{name}: missing in the program's state"
        for i, (p, r) in enumerate(zip(prog[name], ref_norms)):
            gap = abs(p - r) / max(r, floor)
            if gap > worst:
                worst, where = gap, f"{name}[{i}]"
    return worst, where


def compare_training(prog: dict, ref: dict, limits: dict) -> tuple[bool, list[dict]]:
    rows = []
    for i, (lp, lr) in enumerate(zip(prog["loss"], ref["loss"])):
        rows.append({"number": f"loss_gap.step{i + 1}", "value": abs(lp - lr) / abs(lr),
                     "limit": limits["loss_gap"], "program": lp, "reference": lr})
    if len(prog["loss"]) != len(ref["loss"]):
        rows.append({"number": "loss_steps", "value": float("inf"), "limit": 0.0})
    g, gw = worst_leaf_gap(prog["grad"], ref["grad"])
    rows.append({"number": "grad_norm_gap", "value": g, "limit": limits["grad_norm_gap"], "leaf": gw})
    d, dw = worst_leaf_gap(prog["dparam"], ref["dparam"])
    rows.append({"number": "dparam_norm_gap", "value": d, "limit": limits["dparam_norm_gap"], "leaf": dw})
    for r in rows:
        r["ok"] = bool(r["value"] <= r["limit"])
    return all(r["ok"] for r in rows), rows


def served_gaps(ref_logits, served) -> list[float]:
    """For each served token, how far its reference logit lies below the
    reference's best at that position (0 when the reference agrees)."""
    import numpy as np

    lg = np.asarray(ref_logits, np.float32)
    tok = np.asarray(served)
    return (lg.max(axis=-1) - lg[np.arange(len(tok)), tok]).tolist()


def compare_serving(gaps: list[float], limits: dict, failed: int, short: int) -> tuple[bool, list[dict]]:
    """``gaps``: for every served token compared, how far it lies below the
    reference's best. Their mean is steady from seed to seed and is what a
    lower precision moves; the widest swings by its nature and is held against
    a wrong token."""
    import statistics

    n = len(gaps)
    rows = [
        {"number": "served_logit_gap_mean", "value": statistics.fmean(gaps) if n else float("inf"),
         "limit": limits["served_logit_gap_mean"], "tokens_compared": n},
        {"number": "served_logit_gap_max", "value": max(gaps) if n else float("inf"),
         "limit": limits["served_logit_gap_max"], "tokens_compared": n},
        {"number": "requests_failed", "value": float(failed), "limit": 0.0},
        {"number": "requests_short_of_their_tokens", "value": float(short), "limit": 0.0},
    ]
    for r in rows:
        r["ok"] = bool(r["value"] <= r["limit"])
    return all(r["ok"] for r in rows), rows
