"""The door from a configuration to its counts module, and the rule of form of
``BENCHMARK.json``'s per-layer entries (PR 42): an entry is one reader x one
end-to-end metric moved; an entry without a ``workloads`` list belongs to every
cell that reports that metric, today's and a later PR's, so its reader may know
no family and no cell."""

import json
import os
import re

import pytest

from harness import counts_for, manifest

MAN = manifest.load_manifest()
MOVED_SUFFIX = {"train_tokens_per_s_chip": "train", "ttft_p90_ms": "ttft", "tpot_p90_ms": "tpot",
                "serve_tokens_per_s": "rate"}


def _config(entry: dict) -> dict:
    with open(os.path.join(manifest.ROOT, entry["file"])) as f:
        return json.load(f)


@pytest.mark.parametrize("entry", MAN["configs"], ids=lambda e: e["name"])
def test_exactly_one_counts_module_knows_a_committed_configuration(entry):
    full = _config(entry)
    for cfg in (full, {**full, **full["rehearsal"]}):
        knowing = [m.__name__ for m in counts_for.modules() if m.knows(cfg)]
        assert len(knowing) == 1, knowing
        assert counts_for.counts_for(cfg).__name__ == knowing[0]


def test_the_door_names_no_module_and_refuses_two_that_know(monkeypatch):
    found = {m.__name__.rsplit(".", 1)[1] for m in counts_for.modules()}
    on_disk = {f[:-3] for f in os.listdir(os.path.join(manifest.BENCH_DIR, "harness"))
               if re.fullmatch(r"counts\w*\.py", f)}
    assert found == on_disk - {"counts_for"} and "counts" in found
    assert all(callable(m.knows) and callable(m.decode_step) for m in counts_for.modules())
    assert counts_for.counts_for({}) is None and counts_for.counts_for({"hidden_size": 64}) is None
    first, second = counts_for.modules()[:2]
    monkeypatch.setattr(first, "knows", lambda cfg: True)
    monkeypatch.setattr(second, "knows", lambda cfg: True)
    with pytest.raises(ValueError, match="exclusive"):
        counts_for.counts_for({})


def test_a_mixture_module_states_the_expert_readers_names():
    """What ``expert_decode_roofline``, ``expert_prefill_roofline`` and
    ``expert_tokens_per_step`` take from the module the door returns; a module
    without a mixture has none of them and those readers read nothing."""
    names = ("n_mixture_layers", "expert_bytes", "assignment_flops", "per_layer_step",
             "held_assignments_per_token", "expert_tokens_per_step")
    with_mixture = [m for m in counts_for.modules() if hasattr(m, "n_mixture_layers")]
    assert len(with_mixture) == 2
    assert counts_for.mixture_counts_for({}) is None
    for m in with_mixture:
        assert all(callable(getattr(m, n)) for n in names), m.__name__


def test_the_rule_of_form_of_the_per_layer_entries():
    entries = MAN["per_layer"]
    assert len(entries) <= 128, f"{len(entries)} per-layer entries, {128 - len(entries)} free: the form allows 128"
    seen = set()
    for m in (m for m in entries if "workloads" not in m):
        with open(manifest.reader_path(m["name"])) as f:
            source = f.read()
        assert not re.search(r"^\s*(from|import)\s.*\bcounts", source, re.M), \
            f"{m['name']}: no list, yet its reader imports a family's counts"
        assert '["config"]' not in source and "'config'" not in source, \
            f"{m['name']}: no list, yet its reader reads the configuration"
        key = (manifest.reader_path(m["name"]), m["moves"])
        assert key not in seen, f"{m['name']}: a second entry without a list for one reader and one metric moved"
        seen.add(key)


def test_a_suffix_names_the_metric_moved_or_the_one_cell_that_reads_the_entry():
    """``.train`` / ``.ttft`` / ``.tpot`` / ``.rate`` on an entry of several
    cells or of every cell; an entry that one cell alone lists keeps the name it
    was accepted under. ``supervisor_phase_ms``'s suffix is its reader's argument."""
    for m in MAN["per_layer"]:
        base, _, suffix = m["name"].rpartition(".")
        if not base or base == "supervisor_phase_ms":
            continue
        if len(m.get("workloads", ())) != 1:
            assert suffix == MOVED_SUFFIX[m["moves"]], m["name"]
        elif suffix in MOVED_SUFFIX.values():
            assert suffix == MOVED_SUFFIX[m["moves"]], m["name"]


@pytest.mark.parametrize("cell", [w["name"] for w in MAN["workloads"]])
def test_every_cell_reports_one_share_of_the_whole_step(cell):
    """Beside the kernels' rooflines, the bound of their claims: ``mfu_pct`` in a
    training cell, ``decode_step_hbm_roofline`` in a serving cell, moving an
    end-to-end metric the cell reports."""
    mine = manifest.metrics_of(MAN, "per_layer", cell)
    whole = [m["name"] for m in mine if m["name"].split(".")[0] in ("mfu_pct", "decode_step_hbm_roofline")]
    assert len(whole) == 1, whole
