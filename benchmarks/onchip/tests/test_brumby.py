"""What the Brumby configuration brought to the benchmark, on the CPU: the
family's mapping and its refusals, the configuration against the catalog's row
(depth alone cut), the traffic file, the count functions by hand, the three
readers the new cell brings and the one it shares with the benchmark's older
cells on a synthetic trace whose numbers are known exactly, and the new cell
driven end to end through ``run.py``'s runner at its rehearsal size."""

import copy
import json
import os

import pytest

from families import brumby as family
from harness import counts_brumby as counts
from harness import manifest, program
from tests.test_harness_drive import _numbers, _run
from tests.test_program_trace import _bytes, _op

CELL = "brumby-14b.serve-longdoc12"
CONFIG = "brumby-14b-1chip-serve"
NEW_READERS = ("power_time_pct.longdoc12", "power_update_roofline.longdoc12", "power_scan_roofline.longdoc12")
SHARED_READERS = ("decode_step_hbm_roofline.rate",)  # the one entry a metric moved now lists the cell (PR 42's rule)


def _config():
    with open(os.path.join(manifest.BENCH_DIR, "configs", CONFIG + ".json")) as f:
        return json.load(f)


# -- the family and the files ---------------------------------------------------


def test_the_family_maps_the_published_keys():
    cfg = _config()
    assert manifest.family_of(cfg) == "brumby" and cfg["reference"] == "brumby"
    mc = program.model_config(cfg, CONFIG)
    assert (mc.d_model, mc.n_layers, mc.n_heads, mc.n_kv_heads, mc.head_dim, mc.d_ff, mc.vocab_size) == \
        (5120, 8, 40, 8, 128, 17408, 151936)
    assert (mc.rope_theta, mc.norm_eps, mc.max_seq_len, mc.sliding_window) == (1e6, 1e-6, 32768, 0)
    assert (mc.arch, mc.tied_head, mc.is_moe, mc.rope) == ("llama", False, False, True)
    assert mc.layer_types == ("power_retention",) * 8
    assert (cfg["power_degree"], cfg["power_tile"], cfg["power_norm_eps"]) == (2, 16, 1e-6)  # constants of the program
    assert mc.power_state_width == 9216 and len(mc.power_tile_pairs) == 36
    small = program.model_config({**cfg, **cfg["rehearsal"]}, CONFIG)
    # the rehearsal's tiled square keeps several tile pairs
    assert (small.head_dim, len(small.power_tile_pairs), small.power_state_width) == (64, 10, 2560)


def test_the_configuration_holds_the_catalogs_row_and_cuts_depth_alone():
    cfg = _config()
    assert cfg["reduced"] == ["num_hidden_layers"] and cfg["published"]["num_hidden_layers"] == 40
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.isfile(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == "Brumby-14B-Base")
        assert {k for k, v in row["config"].items() if k not in cfg or cfg[k] != v} == {"num_hidden_layers"}
        assert cfg["source"] == row["source_url"]
    assert set(cfg["assumed"]) >= {"power_degree", "gate", "gate_bias", "normaliser", "scale",
                                   "qk_norm_and_rotation", "state_dtype", "layout", "init"}
    assert "five chips" in cfg["deployment"] and "tensor_parallel=1" in cfg["deployment"]
    assert cfg["program"] == dict(max_slots=12, max_len=28672, tensor_parallel=1, compute_dtype="BF16",
                                  prefill_chunk=2048, decode_chunk_steps=8, prefix_cache_tokens=0, kv_quant=False)
    entry = next(c for c in manifest.load_manifest()["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == ["num_hidden_layers"] and entry["source"] == cfg["source"]


def test_the_traffic_file_is_the_issues():
    cell = manifest.load_cell(manifest.load_manifest(), CELL)
    t = cell["traffic"]
    assert (t["generator"], t["clients"], t["requests"], t["order_seed"], t["lead_in_s"], t["trace_s"]) == \
        ("closed", 12, 48, 0, 6.0, 6.0)
    assert t["prompt_tokens"] == {"dist": "uniform", "min": 8192, "max": 24576, "round_to": 2048}
    assert t["output_tokens"] == {"dist": "uniform", "min": 1024, "max": 4096, "round_to": 1}
    assert cell["cell"]["chips"] == 1 and {m["name"] for m in cell["end_to_end"]} == {"serve_tokens_per_s", "setup_s"}
    assert set(NEW_READERS + SHARED_READERS) < {m["name"] for m in cell["per_layer"]}
    from harness.generators import closed

    plan = closed.plan(t, 1000, 1, 50)
    assert sorted(set(plan.prompt_lens.tolist())) == list(range(8192, 24577, 2048))
    assert int(plan.prompt_lens.max() + plan.output_lens.max()) <= cell["config"]["program"]["max_len"]
    small, traffic = {**cell["config"], **cell["config"]["rehearsal"]}, {**t, **t["rehearsal"]}
    assert traffic["prompt_tokens"]["min"] > small["program"]["prefill_chunk"]  # a prompt is several chunks


# -- the counts, by hand ---------------------------------------------------------------


def test_counts_of_the_configuration_by_hand():
    cfg = _config()
    assert counts.knows(cfg) and counts.knows({**cfg, **cfg["rehearsal"]}) and not counts.knows({"hidden_size": 64})
    state = 8 * 9216 * 129 * 4
    assert counts.power_state_bytes(cfg, 1) == state == 38_043_648
    io = (40 + 16) * 128 * 2 + 8 * 4 + 40 * 128 * 4
    assert counts.power_update_bytes(cfg, 12) == 2 * 12 * state + 12 * io
    assert counts.power_chunk_flops(cfg, 2048) == 2 * 2048 * 48 * 9216 * 129 and 0.233e12 < counts.power_chunk_flops(cfg, 2048) < 0.234e12
    assert counts.power_chunk_bytes(cfg, 2048) == 2048 * io + 2 * state
    layer = 2 * 5120 * 5120 + 2 * 5120 * 1024 + 5120 * 8 + 3 * 5120 * 17408
    weights = 2 * (8 * layer + 5120 * 151936)
    assert counts.weight_bytes_per_decode_step(cfg) == weights and 6.84e9 < weights < 6.85e9
    step = counts.decode_step_bytes(cfg, 12)
    assert step == weights + 8 * 2 * 12 * state and 14.1e9 < step < 14.2e9
    assert 0.51 < 8 * 2 * 12 * state / step < 0.52  # the state's share of a step


# -- the readers on a synthetic trace ------------------------------------------------------


def _traced_run(monkeypatch, tmp_path, with_names=True):
    """A run of the new cell with a synthetic trace: 2 decode chunks of 8 steps
    (200 ms each) and 2 prefill chunks of 2 048 tokens; in the decode program
    2 x 100 ms under ``power_update`` and 2 x 10 ms of ``power_qkvg``; in the
    prefill program 2 x 20 ms of ``power_scan`` and 2 x 5 ms of
    ``power_out_proj``; 500 ms busy. ``with_names=False``: the same device time
    from a program that has none of this PR's names."""
    from harness import program_trace, trace_reduce

    dec = "jit(decode_chunk)/while/body/while/body/"
    pre = "jit(prefill_chunk)/while/body/"
    names = {
        _op("fusion.1"): dec + "power/power_update/power_update/pallas_call:",
        _op("fusion.2"): dec + "power/power_qkvg/dot_general:",
        _op("fusion.3"): pre + "power/power_scan/while/body/dot_general:",
        _op("fusion.4"): pre + "power/power_out_proj/dot_general:",
        _op("fusion.9"): dec + "mlp/dot_general:",
    }
    if not with_names:
        names = {k: "jit(_unknown)/while/body/dot_general:" for k in names}
    ops = []
    for start in (0, 200):
        ops += [(_op("fusion.1"), start, 100), (_op("fusion.2"), start + 100, 10), (_op("fusion.9"), start + 110, 90)]
    for start in (400, 450):
        ops += [(_op("fusion.3"), start, 20), (_op("fusion.4"), start + 20, 5), (_op("fusion.9"), start + 25, 25)]
    mods = [("jit_decode_chunk(1)", 0, 200), ("jit_decode_chunk(1)", 200, 200),
            ("jit_prefill_chunk(2)", 400, 50), ("jit_prefill_chunk(2)", 450, 50)]
    if not with_names:
        mods = [("jit__unknown(1)", s, d) for _, s, d in mods]
    pf = lambda s, i: ("tpu_engine.batcher.prefill", s, 5,  # noqa: E731
                       {"rid": 1, "slot": 0, "chunk": i, **({"tokens": 2048} if with_names else {})})
    # a third chunk on the host's side alone: the device's side of the trace ended before it ran
    host = [pf(395, 0), pf(446, 1), pf(497, 2), ("tpu_engine.batcher.other", 0, 500)]
    path = tmp_path / "trace" / f"{CELL}.seed1.trace1" / "plugins" / "profile" / "t" / "x.xplane.pb"
    path.parent.mkdir(parents=True)
    path.write_bytes(_bytes({"/device:TPU:0": {"XLA Ops": ops, "XLA Modules": mods}, "/host:CPU": {"engine": host}},
                            tf_ops=names))
    monkeypatch.setattr(program_trace, "find_xplane", lambda cell: str(path))
    program_trace.load.cache_clear()
    cell = manifest.load_cell(manifest.load_manifest(), CELL)
    return {"cell": cell, "trace": trace_reduce.reduce(str(path), 1), "slots": 12, "decode_chunk_steps": 8,
            "device": {"platform": "tpu", "kind": "TPU v5 lite"}, "dispatch_ends": [100.0, 100.2],
            "dispatch_context": [200_000, 200_096], "occupancy": [12, 12], "dispatch_tokens": [96, 96],
            "engine_stats": {}}


def test_the_new_readers_on_a_synthetic_trace(monkeypatch, tmp_path):
    run = _traced_run(monkeypatch, tmp_path)
    cfg = run["cell"]["config"]
    read = lambda name: manifest.load_reader(name)(run, name)  # noqa: E731
    bw, peak = 819e9, 197e12
    assert read("power_time_pct.longdoc12") == pytest.approx(100 * (200 + 20 + 40 + 10) / 500)
    assert read("power_update_roofline.longdoc12") == pytest.approx(
        100 * 16 * 8 * counts.power_update_bytes(cfg, 12) / bw / 0.200)
    chunk = max(counts.power_chunk_flops(cfg, 2048) / peak, counts.power_chunk_bytes(cfg, 2048) / bw)
    assert chunk == counts.power_chunk_flops(cfg, 2048) / peak  # bound by the arithmetic, not by the bytes
    assert read("power_scan_roofline.longdoc12") == pytest.approx(100 * 8 * 2 * chunk / 0.040)
    assert read("decode_step_hbm_roofline.rate") == pytest.approx(100 * counts.decode_step_bytes(cfg, 12) / bw / 0.025)
    for name in NEW_READERS + SHARED_READERS:
        assert 0 < read(name)  # a synthetic trace: its times are made up, its arithmetic is not


def test_on_a_program_without_the_names_the_new_readers_return_nothing(monkeypatch, tmp_path):
    """The driver lays these files over the parent's checkout for its traced
    runs: no ``power`` scope there, and another family's configuration in the
    other cells."""
    run = _traced_run(monkeypatch, tmp_path, with_names=False)
    for name in NEW_READERS:
        assert manifest.load_reader(name)(run, name) is None, name
    named = _traced_run(monkeypatch, tmp_path / "b")
    untraced, other = {**named, "trace": None}, copy.deepcopy(named)
    other["cell"]["config"] = manifest.load_cell(manifest.load_manifest(),
                                                 "granite-4.0-h-micro.serve-chat-burst")["config"]
    for name in NEW_READERS:
        assert manifest.load_reader(name)(untraced, name) is None, name
    for name in ("power_update_roofline.longdoc12", "power_scan_roofline.longdoc12"):
        assert manifest.load_reader(name)(other, name) is None, name


# -- the cell, driven -------------------------------------------------------------


def test_the_new_cell_is_driven_to_correct(monkeypatch):
    res = _run(monkeypatch, CELL, seed=2147484005, seconds=3.0)
    assert res["correct"] is True, res
    assert res["metrics"] == {} and res["failed"] == 0 and res["attempted"] >= 6
    n = _numbers(res)
    assert n["served_logit_gap_max"]["tokens_compared"] >= 16 and n["programs_lowered_in_window"]["value"] == 0


def test_a_token_altered_where_it_is_produced_is_not_correct(monkeypatch):
    real_install = program.BatcherShim.install

    def install(shim):
        real_install(shim)
        shim.tamper = lambda tok: (tok + 1) % 512

    monkeypatch.setattr(program.BatcherShim, "install", install)
    res = _run(monkeypatch, CELL, seed=5, seconds=3.0)
    assert res["correct"] is False and not _numbers(res)["served_logit_gap_max"]["ok"]


def test_the_control_runs_the_stack_in_int8(monkeypatch):
    """``--control 1`` serves the stack with ``weight_quant=int8`` through the
    fleet (``quant.py`` walks ``q`` / ``k`` / ``v`` / ``o`` and the MLP inside
    the kind's stack; the gate's projection stays in the serving dtype). That
    the control comes out NOT correct is shown on the chip (PERF.md); here it
    must run, serve every token asked for, and compare some."""
    low = _run(monkeypatch, CELL, seed=9, seconds=2.0, control=1)
    assert low["failed"] == 0 and _numbers(low)["requests_short_of_their_tokens"]["ok"]
    assert _numbers(low)["served_logit_gap_max"]["tokens_compared"] >= 16
