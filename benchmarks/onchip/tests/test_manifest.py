"""The manifest lint, and that a new configuration, traffic mix, per-layer
metric and cell are files and list entries only."""

import copy
import json
import os
import shutil

import pytest

from harness import common, counts, manifest, peaks


@pytest.fixture()
def man():
    return manifest.load_manifest()


def test_the_committed_manifest_is_clean(man):
    assert manifest.lint(man) == []
    assert man["paths"] == ["benchmarks/onchip"]
    assert sum(w["chips"] == 4 for w in man["workloads"]) <= max(1, len(man["workloads"]) // 4)
    assert len(json.dumps(man)) < 64 * 1024


@pytest.mark.parametrize("breakage", [
    lambda m: m["workloads"][0].update(name="has space"),
    lambda m: m["end_to_end"][0].update(unit="tokens per second"),
    lambda m: m["per_layer"][0].update(moves="no_such_metric"),
    lambda m: m["per_layer"].append({**m["per_layer"][2], "name": "train_only.x",
                                     "workloads": [m["workloads"][1]["name"]]}),
    lambda m: [w.update(chips=4) for w in m["workloads"][:2]],
    lambda m: m["workloads"][0].update(traffic="no-such-traffic"),
    lambda m: m["configs"][0].update(reduced=["hidden_size"]),
    lambda m: m["end_to_end"][0].update(bound=0.5),
    lambda m: m["per_layer"][0].update(why="not a key"),
])
def test_lint_refuses(man, breakage):
    m = copy.deepcopy(man)
    breakage(m)
    assert manifest.lint(m) != []


def test_every_cell_loads_and_every_reader_exists(man):
    for w in man["workloads"]:
        cell = manifest.load_cell(man, w["name"])
        # A cell's widths are whatever its file publishes; what is cut is never a width.
        assert cell["config"]["reduced"] == cell["config_entry"]["reduced"]
        assert not [k for k in cell["config"]["reduced"] if manifest.names_a_width(k)]
        assert {m["name"] for m in cell["end_to_end"]} >= {"setup_s"}
        for m in cell["per_layer"]:
            assert callable(manifest.load_reader(m["name"]))
        assert manifest.load_by_name("harness/generators", cell["traffic"]["generator"]).KIND in ("train", "serve")
        assert hasattr(manifest.load_by_name("reference", cell["config"]["reference"]), "init_params")
        assert callable(manifest.load_by_name("families", manifest.family_of(cell["config"])).model_config)


def test_a_dummy_cell_is_files_and_entries_only(man, tmp_path, monkeypatch):
    bench = tmp_path / "benchmarks" / "onchip"
    for d in ("configs", "traffic", "layer_metrics", "families"):
        shutil.copytree(os.path.join(manifest.BENCH_DIR, d), bench / d)
    cfg = json.load(open(bench / "configs" / "mistral-7b-1chip-train.json"))
    cfg["num_hidden_layers"] = 1
    json.dump(cfg, open(bench / "configs" / "dummy-config.json", "w"))
    tr = json.load(open(bench / "traffic" / "train-8k.json"))
    tr["seq_len"] = 2048
    json.dump(tr, open(bench / "traffic" / "dummy-traffic.json", "w"))
    (bench / "layer_metrics" / "dummy_metric.py").write_text(
        "def read(run, name):\n    return run.get('dummy')\n")
    m = copy.deepcopy(man)
    m["configs"].append({"name": "dummy-config", "source": "https://example.org/x",
                         "file": "benchmarks/onchip/configs/dummy-config.json",
                         "reduced": ["num_hidden_layers"], "why": "a later PR's configuration"})
    m["workloads"] += [
        {"name": f"dummy.cell{i}", "config": "dummy-config", "traffic": t, "chips": 1, "why": "a later PR's cell"}
        for i, t in enumerate(["dummy-traffic", "train-8k", "chat-open", "batch-closed"])]
    for e in m["end_to_end"]:
        if e["name"] == "train_tokens_per_s_chip":
            e["workloads"].append("dummy.cell0")
    m["per_layer"].append({"name": "dummy_metric.train", "unit": "ms", "better": "lower",
                           "source": "program_span", "layer": "a new layer",
                           "moves": "train_tokens_per_s_chip", "workloads": ["dummy.cell0"]})
    json.dump(m, open(tmp_path / "BENCHMARK.json", "w"))
    monkeypatch.setattr(manifest, "BENCH_DIR", str(bench))
    complaints = manifest.lint(m, root=str(tmp_path))
    assert [c for c in complaints if "dummy.cell0" in c or "dummy_metric" in c or "dummy-config" in c] == []
    cell = manifest.load_cell(manifest.load_manifest(str(tmp_path)), "dummy.cell0", root=str(tmp_path))
    assert cell["config"]["num_hidden_layers"] == 1 and cell["traffic"]["seq_len"] == 2048
    names = [x["name"] for x in cell["per_layer"]]
    assert "dummy_metric.train" in names and "mfu_pct" not in names  # listed metrics only name their own cells
    got = common.read_layer_metrics([x for x in cell["per_layer"] if x["name"] == "dummy_metric.train"],
                                    {"dummy": 3.5})
    assert got == {"dummy_metric.train": {"value": 3.5, "unit": "ms"}}
    assert common.read_layer_metrics([{"name": "dummy_metric.train", "unit": "ms"}], {}) == {}


def test_exact_causal_and_window_counts():
    assert counts.mean_visible_keys(8192, 4096) == pytest.approx(3072.25)
    assert counts.mean_visible_keys(4096, 4096) == pytest.approx(2048.5)
    assert counts.visible_keys_total(8, 0) == 36 and counts.visible_keys_total(8, 3) == 6 + 5 * 3
    cfg = manifest.load_cell(manifest.load_manifest(), "mistral-7b.train-8k")["config"]
    n = counts.matmul_params_per_token(cfg)
    assert n == 2 * (2 * 4096 * 4096 + 2 * 4096 * 1024 + 3 * 4096 * 14336) + 4096 * 32000
    exact = counts.train_flops_per_token(cfg, 8192)
    programs = 6.0 * n + 12.0 * 2 * 4096 * 4096  # tpu_engine's count: min(S, W) keys for every query
    assert exact < programs and exact == pytest.approx(6.0 * n + 12.0 * 2 * 4096 * 3072.25)
    assert counts.expected_experts_hit(8, 2, 16) == pytest.approx(8 * (1 - 0.75 ** 16))


def test_unknown_device_kind_is_an_error():
    assert peaks.peaks("TPU v5 lite")["flops_bf16"] == 197e12
    with pytest.raises(KeyError):
        peaks.peaks("TPU v9")


def test_decode_roofline_reader_takes_the_program_with_most_device_time(man):
    read = manifest.load_reader("decode_step_hbm_roofline.tpot")
    cell = manifest.load_cell(man, "mistral-7b.serve-chat")
    run = {"trace": {"module_runs": {"jit__unknown(1)": [0.30] * 40, "jit__unknown(2)": [0.05] * 10,
                                      "jit_convert_element_type(3)": [1e-4] * 90}},
           "device": {"platform": "tpu", "kind": "TPU v5 lite"}, "occupancy": [3, 4], "decode_chunk_steps": 8,
           "cell": cell, "dispatch_context": [2400]}
    share = read(run, "decode_step_hbm_roofline.tpot")
    assert 5 < share < 30          # ~3.8 GB of bf16 weights and KV over 819 GB/s against 37.5 ms a step
    assert read({**run, "trace": None}, "x") is None
