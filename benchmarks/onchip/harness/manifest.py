"""BENCHMARK.json and the files it names. Nothing here knows a cell's name:
a cell is found by the ``--workload`` argument, its configuration and traffic
by the names the cell gives, a per-layer metric's reader by the metric's name,
a configuration's family by ``family_of`` (the ``family`` its file states,
else the ``model_type`` it publishes) and its reference by the file's
``reference``.
"""

from __future__ import annotations

import importlib.util
import json
import os
import re

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(BENCH_DIR))
NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
TRAFFIC_SUFFIXES = (".json", ".jsonl", ".toml", ".txt", ".csv")
WIDTH_RE = re.compile(r"(_dim|_rank|hidden_size|intermediate_size|per_tok)$")
FAMILY_RE = re.compile(r"^[A-Za-z0-9_\-]+$")


def names_a_width(key: str) -> bool:
    """A key ``reduced`` may never name: depth and scale are cut, widths are not."""
    return bool(WIDTH_RE.search(key))


def family_of(config: dict) -> str:
    """The family of a configuration file, which names ``families/<it>.py``:
    the file's ``family`` where it has the key, else the ``model_type`` it
    publishes. ``family`` is a key of the benchmark's, beside ``reference``,
    ``role``, ``program``, ``check`` and ``rehearsal``: a second recipe under a
    ``model_type`` the benchmark already has states it, and the published key
    stays as published."""
    family = str(config.get("family", config.get("model_type")))
    if not FAMILY_RE.match(family):
        raise ValueError(f"family {family!r} is not a plain module name (letters, digits, _ and -)")
    return family


def load_manifest(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(manifest: dict, workload: str, root: str = ROOT) -> dict:
    """Everything one run needs: the cell, its configuration file, its traffic
    file, and the names of the metrics it has to report."""
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; known: {sorted(cells)}")
    cell = cells[workload]
    cfg_entry = {c["name"]: c for c in manifest["configs"]}[cell["config"]]
    return {
        "cell": cell,
        "config_entry": cfg_entry,
        "config": _read_json(os.path.join(root, cfg_entry["file"])),
        "traffic": _read_json(os.path.join(BENCH_DIR, "traffic", cell["traffic"] + ".json")),
        "end_to_end": metrics_of(manifest, "end_to_end", workload),
        "per_layer": metrics_of(manifest, "per_layer", workload),
    }


def metrics_of(manifest: dict, section: str, workload: str) -> list[dict]:
    """The metrics of ``section`` this cell reports: those that list it under
    ``workloads``; a per-layer metric without the key belongs to every cell
    that reports the end-to-end metric it moves."""
    e2e = [m for m in manifest["end_to_end"]
           if "workloads" not in m or workload in m["workloads"]]
    if section == "end_to_end":
        return e2e
    moved = {m["name"] for m in e2e}
    return [m for m in manifest["per_layer"]
            if (workload in m["workloads"] if "workloads" in m else m["moves"] in moved)]


def reader_path(name: str) -> str | None:
    """``layer_metrics/<name>.py``, or the file of the name without its last
    dotted suffix (``device_idle_pct.tpot`` is read by ``device_idle_pct.py``:
    a suffix names the end-to-end metric the entry moves, ``.train`` / ``.ttft``
    / ``.tpot`` / ``.rate``, or the one cell that reads the entry)."""
    d = os.path.join(BENCH_DIR, "layer_metrics")
    for cand in (name, name.rsplit(".", 1)[0]):
        p = os.path.join(d, cand + ".py")
        if os.path.isfile(p):
            return p
    return None


def load_reader(name: str):
    path = reader_path(name)
    if path is None:
        raise FileNotFoundError(f"no reader for per-layer metric {name!r} under layer_metrics/")
    spec = importlib.util.spec_from_file_location("layer_metric_" + re.sub(r"\W", "_", name), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def module_path(package_dir: str, name: str) -> str:
    return os.path.join(BENCH_DIR, package_dir, name + ".py")


def load_by_name(package_dir: str, name: str):
    """A module found by name in a directory of the benchmark (a generator, a
    reference, a family)."""
    path = module_path(package_dir, name)
    if not os.path.isfile(path):
        raise FileNotFoundError(f"{path} does not exist")
    pkg = package_dir.replace("/", ".")
    return importlib.import_module(f"{pkg}.{name}")


def lint(manifest: dict, root: str = ROOT) -> list[str]:
    """What the driver would refuse before a run, as far as it can be told
    here. Returns the complaints; empty is clean."""
    bad: list[str] = []
    if set(manifest) != {"command", "paths", "run_seconds", "configs", "workloads",
                         "end_to_end", "per_layer"}:
        bad.append(f"keys: {sorted(manifest)}")
    names = lambda sec: [x["name"] for x in manifest[sec]]  # noqa: E731
    for sec in ("configs", "workloads", "end_to_end", "per_layer"):
        for n in names(sec):
            if not NAME_RE.match(n):
                bad.append(f"{sec}: name {n!r}")
        if len(set(names(sec))) != len(names(sec)):
            bad.append(f"{sec}: duplicate names")
    if set(names("end_to_end")) & set(names("per_layer")):
        bad.append("a metric is both end-to-end and per-layer")
    paths = manifest["paths"]
    under = lambda p: any(p == d or p.startswith(d + "/") for d in paths)  # noqa: E731
    for word in manifest["command"]:
        if ("/" in word or word.endswith(".py")) and not under(word):
            bad.append(f"command names {word!r} outside paths")
    if not isinstance(manifest["run_seconds"], int) or not 1 <= manifest["run_seconds"] <= 51:
        bad.append("run_seconds")
    cfgs = {c["name"]: c for c in manifest["configs"]}
    used = set()
    for c in manifest["configs"]:
        if set(c) != {"name", "source", "file", "reduced", "why"}:
            bad.append(f"config {c['name']}: keys {sorted(c)}")
        if not under(c["file"]) or not os.path.isfile(os.path.join(root, c["file"])):
            bad.append(f"config {c['name']}: file {c['file']}")
        else:
            try:
                family = family_of(_read_json(os.path.join(root, c["file"])))
            except ValueError as e:
                bad.append(f"config {c['name']}: {e}")
            else:
                path = module_path("families", family)
                if not os.path.isfile(path):
                    bad.append(f"config {c['name']}: family {family!r}: {path} does not exist")
        for k in c["reduced"]:
            if not NAME_RE.match(k) or names_a_width(k):
                bad.append(f"config {c['name']}: reduced names a width: {k}")
    if len({c["file"] for c in manifest["configs"]}) != len(cfgs):
        bad.append("two configurations share a file")
    cells = manifest["workloads"]
    pairs = set()
    for w in cells:
        if set(w) != {"name", "config", "traffic", "chips", "why"}:
            bad.append(f"cell {w['name']}: keys {sorted(w)}")
        if w["config"] not in cfgs:
            bad.append(f"cell {w['name']}: config {w['config']}")
        used.add(w["config"])
        if not NAME_RE.match(w["traffic"]) or not any(
                os.path.isfile(os.path.join(BENCH_DIR, "traffic", w["traffic"] + s))
                for s in TRAFFIC_SUFFIXES):
            bad.append(f"cell {w['name']}: traffic {w['traffic']}")
        if w["chips"] not in (1, 4):
            bad.append(f"cell {w['name']}: chips")
        if not 1 <= len(w["why"]) <= 200 or "\n" in w["why"] or "\t" in w["why"]:
            bad.append(f"cell {w['name']}: why")
        if (w["config"], w["traffic"]) in pairs:
            bad.append(f"cell {w['name']}: pair repeated")
        pairs.add((w["config"], w["traffic"]))
    if set(cfgs) - used:
        bad.append(f"configurations no cell uses: {sorted(set(cfgs) - used)}")
    four = sum(1 for w in cells if w["chips"] == 4)
    if four > max(1, len(cells) // 4):
        bad.append(f"{four} of {len(cells)} cells ask four chips")
    cell_names = {w["name"] for w in cells}
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    if "setup_s" not in e2e:
        bad.append("no setup_s")
    for m in manifest["end_to_end"]:
        if set(m) - {"workloads"} != {"name", "unit", "better", "bound", "source"}:
            bad.append(f"metric {m['name']}: keys {sorted(m)}")
        if not 0 < m["bound"] <= 0.1:
            bad.append(f"metric {m['name']}: bound {m['bound']}")
        if m["source"] not in ("host_clock", "device_trace"):
            bad.append(f"metric {m['name']}: source {m['source']}")
    for m in manifest["per_layer"]:
        if set(m) - {"workloads"} != {"name", "unit", "better", "source", "layer", "moves"}:
            bad.append(f"metric {m['name']}: keys {sorted(m)}")
        if m["source"] not in ("device_trace", "program_span", "program_counter", "host_clock"):
            bad.append(f"metric {m['name']}: source {m['source']}")
        if m["moves"] not in e2e:
            bad.append(f"metric {m['name']}: moves {m['moves']}")
        if reader_path(m["name"]) is None:
            bad.append(f"metric {m['name']}: no reader file")
        if not 1 <= len(m["layer"]) <= 200 or "\n" in m["layer"]:
            bad.append(f"metric {m['name']}: layer")
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if not UNIT_RE.match(m["unit"]) or m["better"] not in ("lower", "higher"):
            bad.append(f"metric {m['name']}: unit or better")
        for w in m.get("workloads", []):
            if w not in cell_names:
                bad.append(f"metric {m['name']}: workload {w}")
    for w in cells:
        mine = metrics_of(manifest, "end_to_end", w["name"])
        if len([m for m in mine if m["name"] != "setup_s"]) < 1 or "setup_s" not in {m["name"] for m in mine}:
            bad.append(f"cell {w['name']}: needs setup_s and one more end-to-end metric")
        moved = {m["name"] for m in mine}
        layer = metrics_of(manifest, "per_layer", w["name"])
        if not layer:
            bad.append(f"cell {w['name']}: no per-layer metric")
        for m in layer:
            if m["moves"] not in moved:
                bad.append(f"metric {m['name']} moves {m['moves']}, which cell {w['name']} does not report")
    return bad
