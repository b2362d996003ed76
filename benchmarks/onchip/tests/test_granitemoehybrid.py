"""What the ``granitemoehybrid`` configuration brought to the benchmark, on the
CPU: the family's refusals, the count functions and the four readers on a
synthetic trace whose numbers are known exactly, and the new cell driven end to
end through ``run.py``'s runner at its rehearsal size."""

import copy
import json
import os

import pytest

from harness import counts_hybrid, manifest
from tests.test_harness_drive import _numbers, _run
from tests.test_program_trace import _bytes, _op

CELL = "granite-4.0-h-micro.serve-chat-burst"


def _config():
    with open(os.path.join(manifest.BENCH_DIR, "configs", "granite-4.0-h-micro-1chip-serve.json")) as f:
        return json.load(f)


# -- the family ----------------------------------------------------------------


def test_the_family_maps_every_published_width():
    from families import granitemoehybrid

    mc = granitemoehybrid.model_config(_config(), "g")
    assert (mc.d_model, mc.n_heads, mc.n_kv_heads, mc.head_dim, mc.d_ff, mc.vocab_size) == \
        (2048, 32, 8, 64, 8192, 100352)
    assert (mc.ssm_heads, mc.ssm_head_dim, mc.ssm_state, mc.ssm_conv, mc.ssm_chunk, mc.ssm_groups) == \
        (64, 64, 128, 4, 256, 1)
    assert (mc.embed_scale, mc.residual_scale, mc.attn_scale, mc.logits_divisor) == (12.0, 0.22, 1 / 64, 8.0)
    assert mc.n_layers == 20 and mc.n_ssm_layers == 18 and mc.n_attn_layers == 2
    assert mc.layer_runs() == (("ssm", 0, 5), ("attn", 0, 1), ("ssm", 5, 9), ("attn", 1, 1), ("ssm", 14, 4))
    assert mc.tied_head and not mc.rope and mc.arch == "llama" and mc.sliding_window == 0


@pytest.mark.parametrize("change, says", [
    ({"num_local_experts": 8}, "experts"),
    ({"tie_word_embeddings": False}, "untied"),
    ({"position_embedding_type": "rope"}, "nope"),
    ({"mamba_n_groups": 8}, "group"),
    ({"num_hidden_layers": 19}, "layer_types"),
    ({"layer_types": ["mamba"] * 19 + ["linear"]}, "layer_types"),
    ({"attention_bias": True}, "bias"),
    ({"mamba_d_head": 32}, "mamba_expand"),
    ({"shared_intermediate_size": 4096}, "shared_intermediate_size"),
])
def test_the_family_refuses_what_the_recipe_cannot_represent(change, says):
    from families import granitemoehybrid

    with pytest.raises(ValueError, match=says):
        granitemoehybrid.model_config({**_config(), **change}, "g")


def test_the_configuration_holds_the_catalogs_row_but_for_the_cut():
    """Every published key at its published value; ``reduced`` names the depth
    and the pattern and no width; the pattern is the first two periods."""
    cfg = _config()
    assert cfg["reduced"] == ["num_hidden_layers", "layer_types"]
    assert not any(manifest.names_a_width(k) for k in cfg["reduced"])
    period = ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
    assert cfg["layer_types"] == period * 2 and cfg["num_hidden_layers"] == 20
    assert cfg["published"]["num_hidden_layers"] == 40
    published = dict(hidden_size=2048, intermediate_size=8192, shared_intermediate_size=8192, num_attention_heads=32,
                     num_key_value_heads=8, vocab_size=100352, mamba_n_heads=64, mamba_d_head=64, mamba_d_state=128,
                     mamba_d_conv=4, mamba_expand=2, mamba_chunk_size=256, mamba_n_groups=1, embedding_multiplier=12,
                     residual_multiplier=0.22, attention_multiplier=0.015625, logits_scaling=8,
                     max_position_embeddings=131072, rms_norm_eps=1e-05, tie_word_embeddings=True,
                     position_embedding_type="nope", num_local_experts=0, model_type="granitemoehybrid")
    assert {k: cfg[k] for k in published} == published
    assert cfg["program"]["max_slots"] == 32 and cfg["program"]["prefill_chunk"] == cfg["mamba_chunk_size"]


# -- counts ----------------------------------------------------------------------


def test_counts_of_the_configuration_by_hand():
    cfg = _config()
    state = 64 * 64 * 128 * 4
    assert counts_hybrid.ssm_state_bytes(cfg, 32) == 32 * state
    # update: state in and out, x|B|C in bf16, dt in and y out in float32
    assert counts_hybrid.ssm_update_bytes(cfg, 32) == 2 * 32 * state + 32 * (4352 * 2 + 64 * 4) + 32 * 4096 * 4
    pairs = 256 * 257 // 2
    assert counts_hybrid.ssd_chunk_flops(cfg, 256) == 2 * pairs * 128 + 2 * pairs * 4096 + 4 * 256 * 4096 * 128
    assert counts_hybrid.ssd_chunk_bytes(cfg, 256) == 256 * (4352 * 2 + 64 * 4 + 4096 * 4) + 2 * state
    attn = 2048 * 2048 + 2 * 2048 * 512 + 2048 * 2048
    ssm = 2048 * (4096 + 4352 + 64) + 5 * 4352 + 4096 * 2048
    mlp = 3 * 2048 * 8192
    assert counts_hybrid.weight_bytes_per_decode_step(cfg) == 2 * (2 * attn + 18 * ssm + 20 * mlp + 2048 * 100352)
    assert counts_hybrid.kv_bytes_per_decode_step(cfg, 1000) == 2 * 2 * 8 * 64 * 2 * 1000
    assert counts_hybrid.recurrent_bytes_per_decode_step(cfg, 32) == 2 * 18 * (32 * state + 32 * 3 * 4352 * 2)
    # what the cell's ``why`` says: state and mixer weights are most of a step's bytes
    total = (counts_hybrid.weight_bytes_per_decode_step(cfg) + counts_hybrid.recurrent_bytes_per_decode_step(cfg, 32))
    mixers = 2 * 18 * ssm + counts_hybrid.recurrent_bytes_per_decode_step(cfg, 32)
    assert 0.5 < mixers / total < 0.65


# -- the readers, on a trace whose numbers are known ---------------------------------


def _traced_run(monkeypatch, tmp_path, with_names=True):
    """A run of the new cell with a synthetic trace: 2 decode chunks of 8 steps
    (40 ms each), 3 prefill chunks (256, 256, 64 tokens), 72 ms under
    ``ssm_update``, 9 ms under ``ssm_scan``, 150 ms busy. ``with_names=False``:
    the same device time from a program that has none of this PR's names."""
    from harness import program_trace

    dec = "jit(decode_chunk)/while/body/"
    pre = "jit(prefill_chunk)/"
    names = {
        _op("fusion.1"): dec + "while/body/ssm/ssm_update/mul:",
        _op("fusion.2"): dec + "while/body/ssm/ssm_in_proj/dot_general:",
        _op("fusion.3"): dec + "while/body/attn/decode_attn/dot_general:",
        _op("fusion.4"): pre + "while/body/ssm/ssm_scan/dot_general:",
        _op("convert.5"): dec + "cast_weights/convert_element_type:",
    }
    if not with_names:
        names = {k: "jit(_unknown)/while/body/dot_general:" for k in names}
    ops = [(_op("fusion.1"), 0, 36), (_op("fusion.2"), 36, 20), (_op("fusion.3"), 56, 4), (_op("convert.5"), 60, 20),
           (_op("fusion.1"), 100, 36), (_op("fusion.4"), 136, 9), (_op("fusion.2"), 145, 25)]
    mods = [("jit_decode_chunk(1)", 0, 40), ("jit_decode_chunk(1)", 100, 40), ("jit_prefill_chunk(2)", 140, 30)]
    if not with_names:
        mods = [("jit__unknown(1)", s, d) for _, s, d in mods]
    pf = lambda s, t: ("tpu_engine.batcher.prefill", s, 5, {"rid": 1, "slot": 0, "chunk": 0, **({"tokens": t} if with_names else {})})  # noqa: E731
    host = [pf(80, 256), pf(90, 256), pf(130, 64), ("tpu_engine.batcher.other", 0, 170)]
    path = tmp_path / "trace" / f"{CELL}.seed1.trace1" / "plugins" / "profile" / "t" / "x.xplane.pb"
    path.parent.mkdir(parents=True)
    path.write_bytes(_bytes({"/device:TPU:0": {"XLA Ops": ops, "XLA Modules": mods}, "/host:CPU": {"engine": host}},
                            tf_ops=names))
    monkeypatch.setattr(program_trace, "find_xplane", lambda cell: str(path))
    program_trace.load.cache_clear()
    from harness import trace_reduce

    cell = manifest.load_cell(manifest.load_manifest(), CELL)
    return {"cell": cell, "trace": trace_reduce.reduce(str(path), 1), "slots": 32, "decode_chunk_steps": 8,
            "device": {"platform": "tpu", "kind": "TPU v5 lite"}, "dispatch_context": [9000, 11000],
            "occupancy": [20, 22]}


def test_the_new_readers_on_a_synthetic_trace(monkeypatch, tmp_path):
    run = _traced_run(monkeypatch, tmp_path)
    cfg = run["cell"]["config"]
    read = lambda name: manifest.load_reader(name)(run, name)  # noqa: E731
    assert read("ssm_time_pct.burst") == pytest.approx(100 * (72 + 45 + 9) / 150)
    assert read("attn_time_pct.tpot") == pytest.approx(100 * 4 / 150)
    bw, fl = 819e9, 197e12
    steps = 2 * 8
    assert read("ssm_update_roofline.burst") == pytest.approx(
        100 * steps * 18 * counts_hybrid.ssm_update_bytes(cfg, 32) / bw / 0.072)
    need = sum(max(counts_hybrid.ssd_chunk_flops(cfg, t) / fl, counts_hybrid.ssd_chunk_bytes(cfg, t) / bw)
               for t in (256, 256, 64))
    assert read("ssm_scan_roofline.burst") == pytest.approx(100 * 18 * need / 0.009)
    step_bytes = (counts_hybrid.weight_bytes_per_decode_step(cfg) + counts_hybrid.kv_bytes_per_decode_step(cfg, 10000)
                  + counts_hybrid.recurrent_bytes_per_decode_step(cfg, 32))
    assert read("decode_step_hbm_roofline.tpot") == pytest.approx(100 * step_bytes / bw / (0.040 / 8))
    for name in ("ssm_update_roofline.burst", "ssm_scan_roofline.burst"):
        assert 0 < read(name)  # a synthetic trace: its times are made up, its arithmetic is not


def test_on_a_program_without_the_names_the_new_readers_return_nothing(monkeypatch, tmp_path):
    """The driver lays these files over the parent's checkout for its traced
    runs: no ``ssm`` scope, no ``tokens=``, no ``jit_decode_chunk`` there, and
    a Llama configuration in the other cells (here the file without its
    ``mamba_*`` keys, which no counts module knows: ``decode_step_hbm_roofline``
    reads nothing of it, and reads a Llama file by ``counts.decode_step``)."""
    run = _traced_run(monkeypatch, tmp_path, with_names=False)
    for name in ("ssm_time_pct.burst", "ssm_update_roofline.burst", "ssm_scan_roofline.burst",
                 "decode_step_hbm_roofline.tpot"):
        assert manifest.load_reader(name)(run, name) is None
    untraced = {**run, "trace": None}
    llama = copy.deepcopy(_traced_run(monkeypatch, tmp_path / "b"))
    llama["cell"]["config"] = {k: v for k, v in llama["cell"]["config"].items() if not k.startswith("mamba_")}
    for name in ("ssm_update_roofline.burst", "ssm_scan_roofline.burst", "decode_step_hbm_roofline.tpot"):
        assert manifest.load_reader(name)(untraced, name) is None
        assert manifest.load_reader(name)(llama, name) is None


# -- the cell, driven -------------------------------------------------------------


def test_the_new_cell_is_driven_to_correct(monkeypatch):
    res = _run(monkeypatch, CELL, seed=5, seconds=3.0)
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
    res = _run(monkeypatch, CELL, seed=5, seconds=3.0)
    assert res["correct"] is False and not _numbers(res)["served_logit_gap_max"]["ok"]


def test_the_control_runs_the_mixers_in_int8(monkeypatch):
    """``--control 1`` serves the hybrid with ``weight_quant=int8`` through the
    fleet (``quant.py`` walks both kinds of layer). At a size a test can hold,
    a tied table answers each token with itself and no rounding moves a token,
    so that the control comes out NOT correct is shown on the chip (PERF.md);
    here it must run, and read no better than the sound run."""
    sound = _run(monkeypatch, CELL, seed=9, seconds=2.0)
    low = _run(monkeypatch, CELL, seed=9, seconds=2.0, control=1)
    assert sound["correct"] is True
    assert low["failed"] == 0 and _numbers(low)["requests_short_of_their_tokens"]["ok"]
    assert _numbers(low)["served_logit_gap_mean"]["value"] >= _numbers(sound)["served_logit_gap_mean"]["value"]
