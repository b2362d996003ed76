"""What the Phi-4-mini-flash configuration brought to the benchmark, on the CPU:
the family's mapping, its derived pattern and its refusals, the configuration
against the catalog's row (nothing cut), the count functions by hand, the seven
readers the new cell brings and the two it shares with the benchmark's older
cells on a synthetic trace whose numbers are known exactly,
and the new cell driven end to end through ``run.py``'s runner at its rehearsal
size."""

import copy
import json
import os

import pytest

from families import phi4flash as family
from harness import counts_phi4flash as counts
from harness import manifest, program
from tests.test_harness_drive import _numbers, _run
from tests.test_program_trace import _bytes, _op

CELL = "phi-4-mini-flash.serve-reason32"
CONFIG = "phi-4-mini-flash-1chip-serve"
NEW_READERS = ("shared_kv_decode_roofline.reason32", "window_attn_time_pct.reason32", "mamba1_time_pct.reason32",
               "mamba1_update_roofline.reason32", "mamba1_scan_roofline.reason32", "gmu_time_pct.reason32",
               "cross_decoder_prefill_share_pct.reason32")
# readers the benchmark had, whose one entry a metric moved now lists the cell (PR 42's rule)
SHARED_READERS = ("decode_step_hbm_roofline.rate", "attn_time_pct.rate")


def _config():
    with open(os.path.join(manifest.BENCH_DIR, "configs", CONFIG + ".json")) as f:
        return json.load(f)


# -- the family ----------------------------------------------------------------


def test_the_family_maps_the_published_keys_and_derives_the_pattern():
    cfg = _config()
    assert manifest.family_of(cfg) == "phi4flash" and cfg["reference"] == "phi4flash"
    mc = program.model_config(cfg, CONFIG)
    assert (mc.d_model, mc.n_layers, mc.n_heads, mc.n_kv_heads, mc.head_dim, mc.d_ff, mc.vocab_size) == \
        (2560, 32, 40, 20, 64, 10240, 200064)
    assert (mc.sliding_window, mc.norm_eps, mc.max_seq_len) == (512, 1e-5, 262144)
    assert (mc.mamba1_inner, mc.mamba1_state, mc.mamba1_rank, mc.ssm_conv) == (5120, 16, 160, 4)
    assert (mc.arch, mc.layer_norm, mc.attn_bias, mc.rope, mc.tied_head, mc.is_moe) == \
        ("llama", True, True, False, True, False)
    types = family.derived_layer_types(32, 2)
    assert cfg["layer_types"] == types and len(types) == 32
    assert [i for i, t in enumerate(types) if t == "mamba"] == list(range(0, 17, 2))
    assert [i for i, t in enumerate(types) if t == "sliding_attention"] == list(range(1, 16, 2))
    assert types.index("full_attention") == 17 and types.count("full_attention") == 1
    assert [i for i, t in enumerate(types) if t == "gmu"] == list(range(18, 32, 2))
    assert [i for i, t in enumerate(types) if t == "cross_attention"] == list(range(19, 32, 2))
    assert mc.layer_types == tuple(family.KINDS[t] for t in types) and mc.cross_decoder_start == 17
    small = program.model_config({**cfg, **cfg["rehearsal"]}, CONFIG)
    assert small.n_layers == 12 and small.cross_decoder_start == 7 and small.sliding_window == 16


@pytest.mark.parametrize("change, says", [
    ({"mb_per_layer": 1}, "mb_per_layer=1"),
    ({"mb_per_layer": 4}, "mb_per_layer=4"),
    ({"num_hidden_layers": 30}, "multiple of 4"),
    ({"num_hidden_layers": 4}, "multiple of 4"),
    ({"layer_types": ["mamba"] * 32}, "layer_types must state the pattern"),
    ({"num_hidden_layers": 16}, "layer_types must state the pattern"),
    ({"tie_word_embeddings": False}, "untied head"),
    ({"mlp_bias": True}, "mlp_bias"),
    ({"lm_head_bias": True}, "lm_head_bias"),
    ({"hidden_act": "gelu"}, "hidden_act"),
    ({"resid_pdrop": 0.1}, "dropout"),
    ({"sliding_window": None}, "sliding_window"),
    ({"num_attention_heads": 48}, "whole number of its 48 heads"),
    ({"num_key_value_heads": 5}, "pairs its heads"),
])
def test_the_family_refuses_what_the_recipe_cannot_represent(change, says):
    with pytest.raises(ValueError, match=says):
        program.model_config({**_config(), **change}, CONFIG)


def test_the_configuration_holds_the_catalogs_row_and_cuts_nothing():
    cfg = _config()
    assert cfg["reduced"] == [] and cfg["num_hidden_layers"] == cfg["published"]["num_hidden_layers"] == 32
    published = dict(embd_pdrop=0, hidden_act="silu", hidden_size=2560, intermediate_size=10240, layer_norm_eps=1e-05,
                     max_position_embeddings=262144, mb_per_layer=2, model_type="phi4flash", num_attention_heads=40,
                     num_hidden_layers=32, num_key_value_heads=20, resid_pdrop=0, sliding_window=512,
                     tie_word_embeddings=True, mlp_bias=False, lm_head_bias=False, vocab_size=200064)
    assert {k: cfg[k] for k in published} == published
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.isfile(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == "Phi-4-mini-flash-reasoning")
        assert {k: v for k, v in row["config"].items() if cfg.get(k) != v} == {}
        assert cfg["source"] == row["source_url"]
    assert cfg["assumed_sizes"] == dict(mamba_d_state=16, mamba_d_conv=4, mamba_expand=2, mamba_dt_rank=160)
    assert set(cfg["assumed"]) >= {"mamba", "layer_types", "attention", "shared_state", "dtypes", "init"}
    assert "nothing is cut" in cfg["deployment"] and "tensor_parallel=1" in cfg["deployment"]
    assert cfg["program"] == dict(max_slots=32, max_len=12288, tensor_parallel=1, compute_dtype="BF16",
                                  prefill_chunk=2048, decode_chunk_steps=8, prefix_cache_tokens=0, kv_quant=False)
    small = {**cfg, **cfg["rehearsal"]}
    assert small["num_hidden_layers"] >= 8 and small["layer_types"] == family.derived_layer_types(12, 2)
    assert {"gmu", "cross_attention", "mamba", "sliding_attention", "full_attention"} == set(small["layer_types"])
    entry = next(c for c in manifest.load_manifest()["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == [] and entry["source"] == cfg["source"]


def test_the_traffic_file_is_the_issues():
    cell = manifest.load_cell(manifest.load_manifest(), CELL)
    t = cell["traffic"]
    assert (t["generator"], t["clients"], t["requests"], t["order_seed"], t["lead_in_s"], t["trace_s"]) == \
        ("closed", 32, 128, 0, 6.0, 6.0)
    assert t["prompt_tokens"] == {"dist": "uniform", "min": 2048, "max": 8192, "round_to": 2048}
    assert t["output_tokens"] == {"dist": "uniform", "min": 1024, "max": 4096, "round_to": 1}
    assert cell["cell"]["chips"] == 1 and {m["name"] for m in cell["end_to_end"]} == {"serve_tokens_per_s", "setup_s"}
    assert set(NEW_READERS + SHARED_READERS) < {m["name"] for m in cell["per_layer"]}
    assert not any(m["name"].endswith(".reason32") and m["name"].split(".")[0] in {n.split(".")[0] for n in SHARED_READERS}
                   for m in cell["per_layer"])
    from harness.generators import closed

    plan = closed.plan(t, 1000, 1, 50)
    assert sorted(set(plan.prompt_lens.tolist())) == [2048, 4096, 6144, 8192]
    assert int(plan.prompt_lens.max() + plan.output_lens.max()) <= cell["config"]["program"]["max_len"]
    # the rehearsal's prompts are longer than its window and than one of its chunks
    small, traffic = {**cell["config"], **cell["config"]["rehearsal"]}, {**t, **t["rehearsal"]}
    assert traffic["prompt_tokens"]["min"] > small["sliding_window"]
    assert traffic["prompt_tokens"]["max"] > small["program"]["prefill_chunk"]


# -- the counts, by hand ---------------------------------------------------------------


def test_counts_of_the_configuration_by_hand():
    cfg = _config()
    assert counts.knows(cfg) and counts.knows({**cfg, **cfg["rehearsal"]}) and not counts.knows({"hidden_size": 64})
    assert counts.kv_row_bytes(cfg) == 2 * 1280 * 2 == 5120
    assert counts.shared_readers(cfg) == 8
    assert (counts.n_layers(cfg, "mamba"), counts.n_layers(cfg, "sliding_attention")) == (9, 8)
    assert counts.shared_kv_decode_bytes(cfg, 224_000) == 224_000 * 5120
    # a row shorter than the window reads all of itself, a longer one a window
    assert counts.window_decode_bytes(cfg, 32, 224_000) == 32 * 512 * 5120
    assert counts.window_decode_bytes(cfg, 32, 3_200) == 3_200 * 5120
    assert counts.mamba1_state_bytes(cfg, 32) == 32 * 16 * 5120 * 4
    assert counts.mamba1_update_bytes(cfg, 32) == 2 * 32 * 16 * 5120 * 4 + 32 * (5120 * 10 + 2 * 16 * 2)
    assert counts.mamba1_chunk_bytes(cfg, 2048) == 2048 * (5120 * 10 + 64) + 2 * 16 * 5120 * 4
    mamba = 2560 * 10240 + 5 * 5120 + 5120 * 192 + 160 * 5120 + 5120 * 2560
    weights = 2 * (9 * mamba + 9 * (2 * 2560 * 2560 + 2 * 2560 * 1280) + 7 * 2 * 2560 * 2560 + 7 * 2 * 2560 * 5120
                   + 32 * 3 * 2560 * 10240 + 2560 * 200064)
    assert counts.weight_bytes_per_decode_step(cfg) == weights and 7.69e9 < weights < 7.71e9
    step = counts.decode_step_bytes(cfg, 32, 32, 224_000)
    assert step == weights + 8 * 224_000 * 5120 + 8 * 32 * 512 * 5120 + 9 * 2 * (32 * 16 * 5120 * 4 + 32 * 3 * 5120 * 2)
    assert 17.7e9 < step < 17.8e9


# -- the readers on a synthetic trace ------------------------------------------------------

STATS = {"prefill_tokens_computed_total": 409_600, "prefill_positions_cross_decoder_total": 80}


def _traced_run(monkeypatch, tmp_path, with_names=True):
    """A run of the new cell with a synthetic trace: a decode chunk early in
    the fill (40 ms: ONE row of 6 149 lanes decodes, 30 ms of it under
    ``cross_attn``), then 2 decode chunks of 8 steps with 31 rows (200 ms each),
    each inside the ``batcher.device`` annotation that says what it decoded, and
    2 prefill chunks of 2 048 tokens; in the decode program
    2 x 60 ms under ``full_attn`` + ``cross_attn``, 2 x 8 ms of ``window_attn``,
    2 x 10 ms of ``mamba1_update``, 2 x 6 ms of ``gmu``; in the prefill program
    30 ms of ``mamba1_scan``, 5 ms of ``mamba1_in_proj`` and 9 ms of
    ``window_attn``; 300 ms busy. ``with_names=False``: the same device time
    from a program that has none of this PR's names or counters."""
    from harness import counts_sala, program_trace, trace_reduce

    dec = "jit(decode_chunk)/while/body/while/body/"
    pre = "jit(prefill_ingest)/while/body/"
    names = {
        _op("fusion.1"): dec + "full_attn_layer/decode_attn/full_attn/diff_decode:",
        _op("fusion.2"): dec + "cross_attn_layer/decode_attn/cross_attn/diff_decode:",
        _op("fusion.3"): dec + "window_attn_layer/decode_attn/window_attn/diff_decode:",
        _op("fusion.4"): dec + "mamba1/mamba1_update/mul:",
        _op("fusion.5"): dec + "gmu/dot_general:",
        _op("fusion.6"): pre + "mamba1/mamba1_scan/while/body/mul:",
        _op("fusion.7"): pre + "mamba1/mamba1_in_proj/dot_general:",
        _op("fusion.8"): pre + "window_attn_layer/decode_attn/window_attn/dot_general:",
        _op("fusion.9"): dec + "mlp/dot_general:",
    }
    if not with_names:
        names = {k: "jit(_unknown)/while/body/dot_general:" for k in names}
    ops = [(_op("fusion.2"), -100, 30), (_op("fusion.9"), -70, 10)]   # the fill's decode run
    for start in (0, 200):
        ops += [(_op("fusion.1"), start, 10), (_op("fusion.2"), start + 10, 50), (_op("fusion.3"), start + 60, 8),
                (_op("fusion.4"), start + 68, 10), (_op("fusion.5"), start + 78, 6), (_op("fusion.9"), start + 84, 44)]
    ops += [(_op("fusion.6"), 400, 30), (_op("fusion.7"), 430, 5), (_op("fusion.8"), 435, 9)]
    mods = [("jit_decode_chunk(1)", -100, 40), ("jit_decode_chunk(1)", 0, 200), ("jit_decode_chunk(1)", 200, 200),
            ("jit_prefill_ingest(2)", 400, 30), ("jit_prefill_ingest(2)", 430, 14)]
    if not with_names:
        mods = [("jit__unknown(1)", s, d) for _, s, d in mods]
    pf = lambda s, i: ("tpu_engine.batcher.prefill", s, 5,  # noqa: E731
                       {"rid": 1, "slot": 0, "chunk": i, **({"tokens": 2048} if with_names else {})})
    said = (lambda rows, context: {"rows": rows, "context": context}) if with_names else (lambda *_: {})
    # a third chunk on the host's side alone: the device's side of the trace ended before it ran
    host = [pf(390, 0), pf(396, 1), pf(446, 2), ("tpu_engine.batcher.other", 0, 450),
            ("tpu_engine.batcher.device", -95, 40, said(1, 6_149)), ("tpu_engine.batcher.device", 5, 200, said(31, 217_000)),
            ("tpu_engine.batcher.device", 210, 195, said(31, 217_248))]
    path = tmp_path / "trace" / f"{CELL}.seed1.trace1" / "plugins" / "profile" / "t" / "x.xplane.pb"
    path.parent.mkdir(parents=True)
    path.write_bytes(_bytes({"/device:TPU:0": {"XLA Ops": ops, "XLA Modules": mods}, "/host:CPU": {"engine": host}},
                            tf_ops=names))
    monkeypatch.setattr(program_trace, "find_xplane", lambda cell: str(path))
    program_trace.load.cache_clear()
    counts_sala._seconds_under.cache_clear()
    counts._traced_decode.cache_clear()
    cell = manifest.load_cell(manifest.load_manifest(), CELL)
    # the window's own contexts (a third longer: the trace ended before it) are none of the traced runs'
    return {"cell": cell, "trace": trace_reduce.reduce(str(path), 1), "slots": 32, "decode_chunk_steps": 8,
            "device": {"platform": "tpu", "kind": "TPU v5 lite"}, "dispatch_ends": [100.0, 100.3, 140.0],
            "dispatch_context": [220_000, 228_000, 300_000], "occupancy": [32, 32, 32],
            "dispatch_tokens": [248, 248, 256], "engine_stats": dict(STATS) if with_names else {}}


def test_the_new_readers_on_a_synthetic_trace(monkeypatch, tmp_path):
    run = _traced_run(monkeypatch, tmp_path)
    cfg = run["cell"]["config"]
    read = lambda name: manifest.load_reader(name)(run, name)  # noqa: E731
    bw = 819e9
    # every traced run against the rows IT decoded at the lengths THEY had (a row grows a lane a step: + 3.5 a row)
    said = [(1, 6_149), (31, 217_000), (31, 217_248)]
    lanes = [context + rows * 3.5 for rows, context in said]
    traced = counts.traced_decode(run)
    assert [(r["rows"], r["context"]) for r in traced] == said and [r["s"] for r in traced] == pytest.approx([0.04, 0.2, 0.2])
    assert traced[0]["by_scope"]["cross_attn"] == pytest.approx(0.030) and "full_attn" not in traced[0]["by_scope"]
    assert read("shared_kv_decode_roofline.reason32") == pytest.approx(100 * 8 * 8 * sum(lanes) * 5120 / bw / 0.150)
    assert read("window_attn_time_pct.reason32") == pytest.approx(100 * (16 + 9) / 340)
    assert read("mamba1_time_pct.reason32") == pytest.approx(100 * (20 + 30 + 5) / 340)
    assert read("gmu_time_pct.reason32") == pytest.approx(100 * 12 / 340)
    assert read("attn_time_pct.rate") == pytest.approx(100 * (30 + 120 + 16 + 9) / 340)
    assert read("mamba1_update_roofline.reason32") == pytest.approx(
        100 * 24 * 9 * counts.mamba1_update_bytes(cfg, 32) / bw / 0.020)
    assert read("mamba1_scan_roofline.reason32") == pytest.approx(
        100 * 9 * 2 * counts.mamba1_chunk_bytes(cfg, 2048) / bw / 0.030)
    assert read("cross_decoder_prefill_share_pct.reason32") == pytest.approx(100 * 80 / 409_600)
    assert read("decode_step_hbm_roofline.rate") == pytest.approx(
        100 * sum(counts.decode_step_bytes(cfg, 32, rows, n) for (rows, _), n in zip(said, lanes)) / bw / (0.440 / 8))
    for name in NEW_READERS + SHARED_READERS:
        assert 0 < read(name)  # a synthetic trace: its times are made up, its arithmetic is not


def test_on_a_program_without_the_names_or_the_counters_the_new_readers_return_nothing(monkeypatch, tmp_path):
    """The driver lays these files over the parent's checkout for its traced
    runs: no ``mamba1`` scope and no such counter there, and another family's
    configuration in the other cells."""
    run = _traced_run(monkeypatch, tmp_path, with_names=False)
    for name in NEW_READERS:
        assert manifest.load_reader(name)(run, name) is None, name
    named = _traced_run(monkeypatch, tmp_path / "b")
    untraced, other = {**named, "trace": None}, copy.deepcopy(named)
    other["cell"]["config"] = manifest.load_cell(manifest.load_manifest(),
                                                 "granite-4.0-h-micro.serve-chat-burst")["config"]
    for name in NEW_READERS:
        if not name.startswith("cross_decoder"):
            assert manifest.load_reader(name)(untraced, name) is None, name
    for name in ("shared_kv_decode_roofline.reason32", "mamba1_update_roofline.reason32", "mamba1_scan_roofline.reason32"):
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
    fleet (``quant.py`` walks ``in_proj`` / ``out_proj`` / ``x_proj`` /
    ``dt_proj``, ``q`` / ``k`` / ``v`` / ``o`` and the MLPs inside each kind's
    stack). That the control comes out NOT correct is shown on the chip
    (PERF.md); here it must run, serve every token asked for, and compare some."""
    low = _run(monkeypatch, CELL, seed=9, seconds=2.0, control=1)
    assert low["failed"] == 0 and _numbers(low)["requests_short_of_their_tokens"]["ok"]
    assert _numbers(low)["served_logit_gap_max"]["tokens_compared"] >= 16
