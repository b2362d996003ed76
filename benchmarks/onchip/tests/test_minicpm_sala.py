"""What the ``minicpm_sala`` configuration brought to the benchmark, on the CPU:
the family against the catalog's row, the count functions against hand
arithmetic at the cell's sizes, the readers on a synthetic trace whose numbers
are known exactly, and the configuration driven end to end through the
serving runner at its rehearsal size, as the cell ``BENCHMARK.json`` names.

    cd benchmarks/onchip && JAX_PLATFORMS=cpu python -m pytest tests/test_minicpm_sala.py -q"""

import copy
import json
import os

import pytest

from harness import counts_sala, manifest
from tests.test_harness_drive import _numbers, _run
from tests.test_program_trace import _bytes, _op

CELL = "minicpm-sala.serve-longdoc"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def _config():
    with open(os.path.join(manifest.BENCH_DIR, "configs", "minicpm-sala-1chip-serve.json")) as f:
        return json.load(f)


def _cell():
    return manifest.load_cell(manifest.load_manifest(), CELL)


def _drive(monkeypatch, **kw):
    """The cell through ``run.py`` at the rehearsal sizes its two files carry:
    four clients whose prompts are one or two 64-token chunks and whose answers
    outlast the ingestion of all four (4 tokens a dispatch), every decoded
    position past dense_len = 64."""
    return _run(monkeypatch, CELL, **kw)


# The family's own readers keep the cell's suffix; the whole step's share is the
# one reader of every serving family (PR 42), named by the metric it moves.
NEW = ["lightning_time_pct.longdoc", "sparse_attn_time_pct.longdoc", "lightning_update_roofline.longdoc",
       "lightning_scan_roofline.longdoc", "sparse_decode_roofline.longdoc", "sparse_prefill_roofline.longdoc",
       "decode_step_hbm_roofline.rate", "sparse_block_attn_roofline.longdoc"]


# -- the family and the file ------------------------------------------------------


def test_the_family_maps_every_published_width():
    from families import minicpm_sala

    mc = minicpm_sala.model_config(_config(), "m")
    assert (mc.d_model, mc.n_heads, mc.n_kv_heads, mc.head_dim, mc.d_ff, mc.vocab_size) == \
        (4096, 32, 2, 128, 16384, 73448)
    assert (mc.lightning_heads, mc.lightning_head_dim, mc.ssm_chunk) == (32, 128, 256)
    assert (mc.sparse_kernel_size, mc.sparse_kernel_stride, mc.sparse_block_size, mc.sparse_topk,
            mc.sparse_init_blocks, mc.sparse_local_blocks, mc.sparse_dense_len) == (32, 16, 64, 64, 1, 32, 8192)
    assert (mc.embed_scale, mc.logits_divisor) == (12.0, 16.0)
    assert mc.residual_scale == pytest.approx(1.4 / 32 ** 0.5)  # the PUBLISHED depth, whatever is kept
    assert mc.n_layers == 12 and mc.layer_indices == tuple(range(9, 21)) and mc.published_layers == 32
    assert mc.layer_runs() == (("sparse_attn", 0, 1), ("lightning", 0, 6), ("sparse_attn", 1, 2), ("lightning", 6, 3))
    assert mc.published_indices("lightning") == (10, 11, 12, 13, 14, 15, 18, 19, 20)
    assert not mc.tied_head and mc.arch == "llama" and mc.sliding_window == 0 and mc.max_seq_len == 524288


@pytest.mark.parametrize("change, says", [
    ({"tie_word_embeddings": True}, "tied"),
    ({"attention_bias": True}, "bias"),
    ({"hidden_act": "gelu"}, "silu"),
    ({"qk_norm": False}, "qk_norm"),
    ({"attn_use_rope": True}, "attn_use_rope"),
    ({"use_output_gate": False}, "use_output_gate"),
    ({"lightning_nkv": 8}, "lightning_nkv"),
    ({"lightning_scale": "1/d"}, "lightning_scale"),
    ({"mup_denominator": 12}, "mup_denominator"),
    ({"num_hidden_layers": 11}, "mixer_types"),
    ({"mixer_types": ["minicpm4"] * 11 + ["mamba"]}, "mixer_types"),
    ({"kept_layers": list(range(12))[:11]}, "kept_layers"),
    ({"kept_layers": list(range(21, 33))}, "kept_layers"),
])
def test_the_family_refuses_what_the_recipe_cannot_represent(change, says):
    from families import minicpm_sala

    with pytest.raises(ValueError, match=says):
        minicpm_sala.model_config({**_config(), **change}, "m")


@pytest.mark.skipif(not os.path.isfile(CATALOG), reason="the catalog is not on this machine")
def test_the_configuration_holds_the_catalogs_row_but_for_the_cut():
    """Every published key at its published value but the two in ``reduced``;
    the kept layers are a contiguous run of the published pattern."""
    cfg = _config()
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "MiniCPM-SALA")
    assert cfg["source"] == row["source_url"]
    assert cfg["reduced"] == ["num_hidden_layers", "mixer_types"]
    assert not any(manifest.names_a_width(k) for k in cfg["reduced"])
    for key, value in row["config"].items():
        if key not in cfg["reduced"]:
            assert cfg[key] == value, key
    assert cfg["num_hidden_layers"] == 12 and cfg["kept_layers"] == list(range(9, 21))
    assert cfg["mixer_types"] == [row["config"]["mixer_types"][i] for i in cfg["kept_layers"]]
    assert cfg["mixer_types"].count("minicpm4") * 3 == cfg["mixer_types"].count("lightning-attn")  # 1 : 3
    assert cfg["published"]["num_hidden_layers"] == row["config"]["num_hidden_layers"] == 32
    assert set(cfg["assumed"]) >= {"sparse_config", "topk_counts_forced", "dense_or_sparse", "activations",
                                   "output_norm", "slopes", "dtypes", "init"}
    assert "9-20" in cfg["deployment"] and "three" in cfg["deployment"]
    assert cfg["program"] == {"max_slots": 16, "max_len": 34816, "tensor_parallel": 1, "compute_dtype": "BF16",
                              "prefill_chunk": 2048, "decode_chunk_steps": 2, "prefix_cache_tokens": 0,
                              "kv_quant": False}
    assert cfg["check"]["sample_requests"] == 4


def test_the_cell_is_the_issues_traffic_and_the_lane_leaves_the_slots_full():
    """ISSUE 31's traffic as it gives it, through the generator that is there;
    and why the replica decodes 2 token-steps a dispatch: the runner opens a
    closed-loop window when every slot decodes and none ingests, and the
    batcher ingests one chunk a dispatch, so the chunks that 16 decoding rows
    ask for per dispatch have to stay under one."""
    cell = _cell()
    t = cell["traffic"]
    assert (t["generator"], t["clients"], t["requests"], t["order_seed"]) == ("closed", 16, 64, 0)
    assert t["prompt_tokens"] == {"dist": "uniform", "min": 10240, "max": 32768, "round_to": 2048}
    assert t["output_tokens"] == {"dist": "uniform", "min": 256, "max": 1024, "round_to": 1}
    assert [m["name"] for m in cell["end_to_end"]] == ["serve_tokens_per_s", "setup_s"]
    assert {m["name"] for m in cell["per_layer"]} >= set(NEW + ["sparse_decode_share_pct.longdoc"])
    from harness.generators import closed

    plan = closed.Plan(t, 73448, 1, 50.0)
    program = cell["config"]["program"]
    assert len({int(p) for p in plan.prompt_lens}) == 12 and all(int(p) % program["prefill_chunk"] == 0 for p in plan.prompt_lens)
    assert max(plan.prompt_lens) + max(plan.output_lens) <= program["max_len"]
    chunks = sum(int(p) // program["prefill_chunk"] for p in plan.prompt_lens) / len(plan.prompt_lens)
    dispatches = sum(int(o) for o in plan.output_lens) / len(plan.output_lens) / program["decode_chunk_steps"]
    assert 0.4 < t["clients"] * chunks / dispatches < 0.65   # 2.1 at 8 steps a dispatch: the window never opens


# -- counts ----------------------------------------------------------------------


def test_counts_of_the_configuration_by_hand():
    cfg = _config()
    state = 32 * 128 * 128 * 4                                   # one row's lightning state, float32
    assert counts_sala.lightning_state_bytes(cfg, 16) == 16 * state == 33554432
    assert counts_sala.lightning_update_bytes(cfg, 16) == 2 * 16 * state + 16 * 4096 * (3 * 2 + 4)
    assert counts_sala.lightning_chunk_flops(cfg, 2048) == 4 * 2048 * 32 * 128 * 128
    assert counts_sala.lightning_chunk_bytes(cfg, 2048) == 2048 * 4096 * (3 * 2 + 4) + 2 * state
    # a query past dense_len: 63 whole blocks and its own up to itself; below it: everything so far
    assert counts_sala.chosen_lanes(cfg, 20000) == 63 * 64 + 20000 % 64 + 1
    assert counts_sala.chosen_lanes(cfg, 8191) == 8192 and counts_sala.chosen_lanes(cfg, 8192) == 63 * 64 + 1
    assert counts_sala.windows_seen(cfg, 30) == 0 and counts_sala.windows_seen(cfg, 31) == 1
    assert counts_sala.windows_seen(cfg, 20000) == (20000 - 31) // 16 + 1
    blocks = 2 * 64 * 64 * 2 * 128 * 2                           # k and v, 64 blocks of 64 lanes, 2 kv-heads, bf16
    assert counts_sala.chosen_block_bytes(cfg, 1) == blocks == 4194304
    assert counts_sala.sparse_decode_bytes(cfg, 10, 200000) == 200000 / 16 * 512 + 10 * blocks
    per_lane = 2 * 32 * 128
    want = sum(per_lane * ((t - 31) // 16 + 1) + 2 * per_lane * (63 * 64 + t % 64 + 1) for t in range(16384, 16386))
    assert counts_sala.sparse_prefill_flops(cfg, 16384, 2) == want
    assert counts_sala.sparse_prefill_flops(cfg, 0, 3) == 2 * per_lane * (1 + 2 + 3)
    sparse = 3 * 4096 * 4096 + 2 * 4096 * 256 + 3 * 4096 * 16384
    lightning = 5 * 4096 * 4096 + 3 * 4096 * 16384
    assert (sparse, lightning) == (253755392, 285212672)        # the issue's 253.8 M and 285.2 M
    weights = 2 * (3 * sparse + 9 * lightning + 4096 * 73448)
    assert counts_sala.weight_bytes_per_decode_step(cfg) == weights and 7.25e9 < weights < 7.27e9
    assert counts_sala.decode_step_bytes(cfg, 16, 10, 200000) == \
        weights + 3 * counts_sala.sparse_decode_bytes(cfg, 10, 200000) + 9 * 2 * 16 * state
    # what the cell's ``why`` says: the chosen blocks are a small part of the row
    assert counts_sala.chosen_block_bytes(cfg, 1) / (2 * 20000 * 512) < 0.21


# -- the readers, on a trace whose numbers are known -----------------------------------


def _traced_run(monkeypatch, tmp_path, with_names=True):
    """A run of the new cell with a synthetic trace: 2 decode chunks of 8 steps
    (50 ms each), 2 prefill chunks of 2048 tokens (chunk 4 and 5 of a prompt),
    under the new scopes the times below, 170 ms busy. ``with_names=False``:
    the same device time from a program that has none of this PR's names."""
    from harness import program_trace, trace_reduce

    dec = "jit(decode_chunk)/while/body/while/body/"
    pre = "jit(prefill_chunk)/while/body/"
    kernel = "%sparse_block_attn.1 = bf16[16,2,16,128]{3,2,1,0} custom-call(%a)"
    names = {
        _op("fusion.1"): dec + "lightning/lightning_update/mul:",
        _op("fusion.2"): dec + "lightning/lightning_qkv/dot_general:",
        _op("fusion.3"): dec + "sparse_attn/sparse_index/dot_general:",
        kernel: dec + "sparse_attn/sparse_attend/pallas_call:",
        _op("fusion.5"): pre + "lightning/lightning_scan/dot_general:",
        _op("fusion.6"): pre + "sparse_attn/sparse_index/dot_general:",
        _op("fusion.7"): pre + "sparse_attn/sparse_attend/dot_general:",
        _op("fusion.8"): pre + "mlp/dot_general:",
    }
    if not with_names:
        names = {k: "jit(_unknown)/while/body/dot_general:" for k in names}
    ops = [(_op("fusion.1"), 0, 18), (_op("fusion.2"), 18, 12), (_op("fusion.3"), 30, 6), (kernel, 36, 4),
           (_op("fusion.1"), 50, 18), (_op("fusion.2"), 68, 12), (_op("fusion.3"), 80, 6), (kernel, 86, 4),
           (_op("fusion.5"), 100, 10), (_op("fusion.6"), 110, 5), (_op("fusion.7"), 115, 35), (_op("fusion.8"), 150, 40)]
    mods = [("jit_decode_chunk(1)", 0, 50), ("jit_decode_chunk(1)", 50, 50), ("jit_prefill_chunk(2)", 100, 90)]
    if not with_names:
        mods = [("jit__unknown(1)", s, d) for _, s, d in mods]
    pf = lambda s, c: ("tpu_engine.batcher.prefill", s, 5, {"rid": 1, "slot": 0, "chunk": c, **({"tokens": 2048} if with_names else {})})  # noqa: E731
    host = [pf(95, 4), pf(140, 5), ("tpu_engine.batcher.other", 0, 200)]
    path = tmp_path / "trace" / f"{CELL}.seed1.trace1" / "plugins" / "profile" / "t" / "x.xplane.pb"
    path.parent.mkdir(parents=True)
    path.write_bytes(_bytes({"/device:TPU:0": {"XLA Ops": ops, "XLA Modules": mods}, "/host:CPU": {"engine": host}},
                            tf_ops=names))
    monkeypatch.setattr(program_trace, "find_xplane", lambda cell: str(path))
    program_trace.load.cache_clear()
    counts_sala._seconds_under.cache_clear()
    # 15.5 slots held, 7 of them decoding (7 rows x 8 steps a dispatch), 20 000 tokens of context a held row
    return {"cell": _cell(), "trace": trace_reduce.reduce(str(path), 1), "slots": 16, "decode_chunk_steps": 8,
            "device": {"platform": "tpu", "kind": "TPU v5 lite"}, "dispatch_context": [300000, 320000],
            "occupancy": [15, 16], "dispatch_tokens": [56, 56], "engine_stats": {"decode_tokens_computed_total": 4000,
                                                   "decode_tokens_sparse_total": 4000,
                                                   "decode_tokens_emitted_total": 3980}}


def test_the_new_readers_on_a_synthetic_trace(monkeypatch, tmp_path):
    run = _traced_run(monkeypatch, tmp_path)
    cfg = run["cell"]["config"]
    def read(name, suffix=".longdoc"):
        return manifest.load_reader(name + suffix)(run, name + suffix)

    busy = 170
    assert read("lightning_time_pct") == pytest.approx(100 * (2 * 30 + 10) / busy)
    assert read("sparse_attn_time_pct") == pytest.approx(100 * (2 * 10 + 40) / busy)
    bw, fl = 819e9, 197e12
    steps = 2 * 8
    assert read("lightning_update_roofline") == pytest.approx(
        100 * steps * 9 * counts_sala.lightning_update_bytes(cfg, 16) / bw / 0.036)
    scan = max(counts_sala.lightning_chunk_flops(cfg, 2048) / fl, counts_sala.lightning_chunk_bytes(cfg, 2048) / bw)
    assert read("lightning_scan_roofline") == pytest.approx(100 * 9 * 2 * scan / 0.010)
    # the rows that DECODE, not the slots held, and their share of the held rows' contexts
    assert counts_sala.decoding_rows(run) == 7 and counts_sala.decoding_context(run) == pytest.approx(140000)
    # decode seconds under sparse_attn: the indexer and the kernel of the DECODE program only
    assert read("sparse_decode_roofline") == pytest.approx(
        100 * steps * 3 * counts_sala.sparse_decode_bytes(cfg, 7, 140000) / bw / 0.020)
    flops = counts_sala.sparse_prefill_flops(cfg, 4 * 2048, 2048) + counts_sala.sparse_prefill_flops(cfg, 5 * 2048, 2048)
    assert read("sparse_prefill_roofline") == pytest.approx(100 * 3 * flops / fl / 0.040)
    assert read("decode_step_hbm_roofline", ".rate") == pytest.approx(
        100 * counts_sala.decode_step_bytes(cfg, 16, 7, 140000) / bw / (0.050 / 8))
    assert read("sparse_block_attn_roofline") == pytest.approx(
        100 * steps * 3 * counts_sala.chosen_block_bytes(cfg, 7) / bw / 0.008)
    assert read("sparse_decode_share_pct") == 100.0
    assert read("decode_overshoot_pct", ".rate") == pytest.approx(0.5)
    assert read("slot_occupancy_pct", ".rate") == pytest.approx(100 * 15.5 / 16)


def test_on_a_program_without_the_names_the_new_readers_return_nothing(monkeypatch, tmp_path):
    """The driver lays these files over the parent's checkout for its traced
    runs: no ``lightning`` or ``sparse_attn`` scope and no kernel there, no
    ``decode_tokens_sparse_total`` in its counters, and other configurations
    in the other cells."""
    run = _traced_run(monkeypatch, tmp_path, with_names=False)
    run["engine_stats"].pop("decode_tokens_sparse_total")
    for name in NEW + ["sparse_decode_share_pct.longdoc"]:
        assert manifest.load_reader(name)(run, name) is None, name
    named = _traced_run(monkeypatch, tmp_path / "b")
    untraced = {**named, "trace": None}
    granite = copy.deepcopy(named)
    granite["cell"]["config"] = {k: v for k, v in granite["cell"]["config"].items()
                                 if k not in ("lightning_nh", "sparse_config")}
    for name in NEW:
        assert manifest.load_reader(name)(untraced, name) is None, name
    for name in NEW[2:]:
        assert manifest.load_reader(name)(granite, name) is None, name


# -- the cell, driven -------------------------------------------------------------


def test_the_configuration_is_driven_to_correct(monkeypatch):
    res = _drive(monkeypatch, seed=5, seconds=3.0)
    assert res["correct"] is True, res
    assert res["metrics"] == {} and res["failed"] == 0 and res["attempted"] >= 6
    n = _numbers(res)
    assert n["served_logit_gap_max"]["tokens_compared"] >= 16 and n["programs_lowered_in_window"]["value"] == 0


def test_a_token_altered_where_it_is_produced_is_not_correct(monkeypatch):
    from harness import program

    real_install = program.BatcherShim.install

    def install(shim):
        real_install(shim)
        shim.tamper = lambda tok: (tok + 1) % 512

    monkeypatch.setattr(program.BatcherShim, "install", install)
    res = _drive(monkeypatch, seed=5, seconds=3.0)
    assert res["correct"] is False and not _numbers(res)["served_logit_gap_max"]["ok"]


def test_the_control_runs_both_kinds_in_int8(monkeypatch):
    """``--control 1`` serves the stack with ``weight_quant=int8`` through the
    fleet (``quant.py`` walks both kinds of layer; the build draws and
    quantises leaf by leaf). That the control comes out NOT correct at
    the cell's size is shown on the chip (PERF.md), and that int8 kernels are
    the lower precision against the float32 reference in
    ``tests/test_sala_stack.py``; at a size a test can hold both runs serve
    the reference's own best token nearly everywhere, so here it must run."""
    low = _drive(monkeypatch, seed=9, seconds=2.0, control=1)
    assert low["failed"] == 0 and low["attempted"] >= 4
    n = _numbers(low)
    assert n["requests_short_of_their_tokens"]["ok"] and n["programs_lowered_in_window"]["value"] == 0
    assert n["served_logit_gap_mean"]["tokens_compared"] >= 16
