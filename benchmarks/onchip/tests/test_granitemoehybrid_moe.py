"""What the granite-4.0-h-small configuration brought to the benchmark, on the
CPU: the family's mapping and refusals, the configuration against the catalog's
row, the reference against the program at rehearsal size, the count functions
and the five new readers on a synthetic trace whose numbers are known exactly,
and the new cell driven end to end through ``run.py``'s runner at its rehearsal
size."""

import copy
import json
import os

import pytest

from harness import counts_hybrid, counts_hybrid_moe, manifest, program
from tests.test_harness_drive import _numbers, _run
from tests.test_program_trace import _bytes, _op

CELL = "granite-4.0-h-small.serve-batch32"
CONFIG = "granite-4.0-h-small-1chip-serve"
NEW_READERS = ("moe_time_pct.rate", "expert_decode_roofline.rate", "expert_prefill_roofline.rate",
               "decode_step_hbm_roofline.rate", "expert_tokens_per_step.rate")


def _config():
    with open(os.path.join(manifest.BENCH_DIR, "configs", CONFIG + ".json")) as f:
        return json.load(f)


# -- the family ----------------------------------------------------------------


def test_the_file_states_its_family_and_the_real_family_gives_the_real_mixture():
    """The file publishes the micro's ``model_type`` and states ``family``: the
    seam loads ``families/granitemoehybrid_moe.py``, which maps every width of
    the mixers as ``granitemoehybrid.fields`` does and the block after them as
    a mixture: the router over the 72 published experts, 10 a token, experts
    0-35 held, a shared expert of 1536."""
    from families import granitemoehybrid

    cfg = _config()
    assert cfg["model_type"] == "granitemoehybrid" and manifest.family_of(cfg) == "granitemoehybrid_moe"
    mc = program.model_config(cfg, CONFIG)
    assert (mc.d_model, mc.n_heads, mc.n_kv_heads, mc.head_dim, mc.d_ff, mc.vocab_size) == \
        (4096, 32, 8, 128, 768, 100352)
    assert (mc.ssm_heads, mc.ssm_head_dim, mc.ssm_state, mc.ssm_conv, mc.ssm_chunk, mc.ssm_groups) == \
        (128, 64, 128, 4, 256, 1)
    assert (mc.embed_scale, mc.residual_scale, mc.attn_scale, mc.logits_divisor) == (12.0, 0.22, 1 / 128, 16.0)
    assert (mc.n_experts, mc.top_k, mc.experts_first, mc.experts_held, mc.n_experts_held, mc.shared_d_ff) == \
        (72, 10, 0, 36, 36, 1536)
    assert mc.n_layers == 10 and mc.n_ssm_layers == 9 and mc.n_attn_layers == 1
    assert mc.layer_runs() == (("ssm", 0, 5), ("attn", 0, 1), ("ssm", 5, 4))
    import dataclasses

    fields = granitemoehybrid.fields(cfg, CONFIG)
    assert {k: dataclasses.asdict(mc)[k] for k in fields} == fields
    # the micro's own family still refuses this file, with the message it had
    with pytest.raises(ValueError, match="experts are not this recipe"):
        granitemoehybrid.model_config(cfg, "g")
    # every expert held: the uncut layer, and the fields for a share stay at their defaults
    uncut = program.model_config({**cfg, "num_local_experts": 72}, CONFIG)
    assert (uncut.experts_first, uncut.experts_held, uncut.n_experts_held) == (0, 0, 72)
    upper = program.model_config({**cfg, "first_local_expert": 36}, CONFIG)
    assert (upper.experts_first, upper.n_experts_held) == (36, 36)


@pytest.mark.parametrize("change, says", [
    ({"published": {"num_hidden_layers": 40}}, "published.num_local_experts is missing"),
    ({"num_local_experts": 0}, "no whole share"),
    ({"num_local_experts": 30}, "no whole share"),
    ({"first_local_expert": 18}, "does not start a share"),
    ({"first_local_expert": 72}, "does not start a share"),
    ({"num_experts_per_tok": 73}, "num_experts_per_tok=73"),
    ({"num_experts_per_tok": 0}, "num_experts_per_tok=0"),
    ({"tie_word_embeddings": False}, "untied"),
    ({"mamba_n_groups": 8}, "group"),
])
def test_the_family_refuses_what_the_recipe_cannot_represent(change, says):
    with pytest.raises(ValueError, match=says):
        program.model_config({**_config(), **change}, CONFIG)


def test_the_configuration_holds_the_catalogs_row_but_for_the_cut():
    """Every published key at its published value; ``reduced`` names the depth,
    the pattern and the experts held, and no width; the pattern is the first
    whole period; the file states 40 layers, 72 experts and the deployment."""
    cfg = _config()
    assert cfg["reduced"] == ["num_hidden_layers", "layer_types", "num_local_experts"]
    assert not any(manifest.names_a_width(k) for k in cfg["reduced"])
    assert cfg["layer_types"] == ["mamba"] * 5 + ["attention"] + ["mamba"] * 4 and cfg["num_hidden_layers"] == 10
    assert cfg["published"]["num_hidden_layers"] == 40 and cfg["published"]["num_local_experts"] == 72
    assert cfg["num_local_experts"] == 36 and cfg.get("first_local_expert", 0) == 0
    published = dict(hidden_size=4096, intermediate_size=768, shared_intermediate_size=1536, num_attention_heads=32,
                     num_key_value_heads=8, vocab_size=100352, mamba_n_heads=128, mamba_d_head=64, mamba_d_state=128,
                     mamba_d_conv=4, mamba_expand=2, mamba_chunk_size=256, mamba_n_groups=1, embedding_multiplier=12,
                     residual_multiplier=0.22, attention_multiplier=0.0078125, logits_scaling=16,
                     max_position_embeddings=131072, rms_norm_eps=1e-05, tie_word_embeddings=True,
                     position_embedding_type="nope", num_experts_per_tok=10, model_type="granitemoehybrid")
    assert {k: cfg[k] for k in published} == published
    assert "2 chips" in cfg["deployment"] and "expert_width" in cfg["assumed"]
    assert cfg["program"]["max_slots"] == 32 and cfg["program"]["prefill_chunk"] == cfg["mamba_chunk_size"]
    small = {**cfg, **cfg["rehearsal"]}
    assert small["published"]["num_local_experts"] == 8 and small["num_local_experts"] == 4
    assert small["shared_intermediate_size"] != small["intermediate_size"] and small["num_experts_per_tok"] == 3


def test_the_traffic_file_is_the_issues():
    cell = manifest.load_cell(manifest.load_manifest(), CELL)
    t = cell["traffic"]
    assert (t["generator"], t["clients"], t["requests"], t["order_seed"], t["lead_in_s"], t["trace_s"]) == \
        ("closed", 32, 128, 0, 6.0, 6.0)
    assert t["prompt_tokens"] == {"dist": "uniform", "min": 128, "max": 512, "round_to": 1}
    assert t["output_tokens"] == {"dist": "uniform", "min": 256, "max": 1024, "round_to": 1}
    assert cell["cell"]["chips"] == 1 and {m["name"] for m in cell["end_to_end"]} == {"serve_tokens_per_s", "setup_s"}
    assert set(NEW_READERS) < {m["name"] for m in cell["per_layer"]}


# -- the reference against the program ---------------------------------------------


def test_the_reference_and_the_program_agree_at_rehearsal_size():
    """One seed drawn twice, by the program and by the reference, each by its
    own code; float32 on both sides, so they differ in the order of sums only
    (measured 1.4e-9 where logits spread 8e-4)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from reference import granitemoehybrid_moe as ref
    from tpu_engine.generate import forward_with_cache, init_cache
    from tpu_engine.models import transformer as tfm

    cfg = {**_config(), **_config()["rehearsal"]}
    mc = program.model_config(cfg, CONFIG)
    toks = np.random.default_rng(3).integers(0, cfg["vocab_size"], 90).astype(np.int32)
    got, cache = forward_with_cache(tfm.init_params(jax.random.PRNGKey(7), mc), jnp.asarray(toks)[None],
                                    init_cache(mc, 1, 96, dtype=jnp.float32), mc, compute_dtype=jnp.float32)
    want, margin = ref.forward_logits(ref.init_params(cfg, 7), toks, cfg)
    assert float(margin.min()) > 1e-6
    assert np.abs(np.asarray(got[0]) - np.asarray(want)).max() < 5e-8
    assert cache.moe_counts.tolist()[0] == 90 * 3 * 4
    lg, served_margin = ref.served_logits(ref.init_params(cfg, 7), toks[:60].tolist(), toks[60:].tolist(), cfg)
    np.testing.assert_allclose(np.asarray(lg), np.asarray(want)[59:89], atol=5e-8)
    np.testing.assert_allclose(np.asarray(served_margin), np.asarray(margin)[59:89], atol=1e-6)  # differences of float32 logits


# -- counts ----------------------------------------------------------------------


def test_counts_of_the_configuration_by_hand():
    cfg = _config()
    assert counts_hybrid_moe.is_mixture(cfg) and counts_hybrid_moe.n_layers(cfg) == 10
    assert counts_hybrid_moe.expert_weights(cfg) == 3 * 4096 * 768 == 9_437_184
    assert counts_hybrid_moe.expert_bytes(cfg) == 2 * 9_437_184
    assert counts_hybrid_moe.assignment_flops(cfg) == 6 * 4096 * 768
    assert counts_hybrid_moe.mixture_fixed_bytes(cfg) == 2 * (4096 * 72 + 3 * 4096 * 1536)
    attn = 4096 * 4096 + 2 * 4096 * 1024 + 4096 * 4096
    ssm = 4096 * (8192 + 8448 + 128) + 5 * 8448 + 8192 * 4096
    assert counts_hybrid_moe.mixer_and_head_bytes(cfg) == 2 * (attn + 9 * ssm + 4096 * 100352)
    state = 32 * 128 * 64 * 128 * 4
    assert counts_hybrid_moe.decode_step_bytes(cfg, 32, 20000, 35.5) == (
        2 * (attn + 9 * ssm + 4096 * 100352) + 10 * (2 * (4096 * 72 + 3 * 4096 * 1536) + 35.5 * 2 * 9_437_184)
        + 2 * 1 * 8 * 128 * 2 * 20000 + 2 * 9 * (state + 32 * 3 * 8448 * 2))
    # what the cell's ``why`` says: the experts are over half of a decode step's bytes
    experts = 10 * 36 * counts_hybrid_moe.expert_bytes(cfg)
    assert 0.5 < experts / counts_hybrid_moe.decode_step_bytes(cfg, 32, 20000, 36) < 0.6
    stats = {"held_experts": 36, "moe_decode_layer_steps_total": 800, "moe_decode_assignments_total": 800 * 320,
             "moe_decode_assignments_held_total": 800 * 158, "moe_decode_experts_hit_total": 800 * 35,
             "moe_prefill_layer_steps_total": 0}
    assert counts_hybrid_moe.per_layer_step(stats, "decode", "experts_hit") == 35
    assert counts_hybrid_moe.per_layer_step(stats, "prefill", "experts_hit") is None
    assert counts_hybrid_moe.held_assignments_per_token(stats, "decode", 10) == pytest.approx(10 * 158 / 320)
    assert counts_hybrid_moe.expert_tokens_per_step(stats, 31.0, 10) == pytest.approx(31.0 * 10 * 158 / 320 / 36)
    assert counts_hybrid_moe.expert_tokens_per_step({}, 31.0, 10) is None
    assert counts_hybrid_moe.expert_tokens_per_step(stats, None, 10) is None
    micro = json.load(open(os.path.join(manifest.BENCH_DIR, "configs", "granite-4.0-h-micro-1chip-serve.json")))
    assert not counts_hybrid_moe.is_mixture(micro)


# -- the readers, on a trace whose numbers are known ---------------------------------

STATS = {"held_experts": 36,
         "moe_decode_layer_steps_total": 1000, "moe_decode_assignments_total": 320_000,
         "moe_decode_assignments_held_total": 160_000, "moe_decode_experts_hit_total": 35_500,
         "moe_prefill_layer_steps_total": 100, "moe_prefill_assignments_total": 200_000,
         "moe_prefill_assignments_held_total": 98_000, "moe_prefill_experts_hit_total": 3_600}


def _traced_run(monkeypatch, tmp_path, with_names=True):
    """A run of the new cell with a synthetic trace: 2 decode chunks of 8 steps
    (40 ms each), 3 prefill chunks (256, 256, 64 tokens); under ``moe`` 20 ms
    of router, 2 x 30 ms of the decode program's experts, 12 ms of the prefill
    program's experts, 6 ms of shared expert; 72 ms of ``ssm_update``; 190 ms
    busy. ``with_names=False``: the same device time from a program that has
    none of this PR's names."""
    from harness import program_trace, trace_reduce

    dec = "jit(decode_chunk)/while/body/while/body/"
    pre = "jit(prefill_chunk)/while/body/"
    names = {
        _op("fusion.1"): dec + "ssm/ssm_update/mul:",
        _op("fusion.2"): dec + "moe/moe_router/dot_general:",
        _op("fusion.3"): dec + "moe/moe_experts/dot_general:",
        _op("fusion.4"): pre + "moe/moe_experts/dot_general:",
        _op("fusion.5"): dec + "moe/moe_shared/dot_general:",
    }
    if not with_names:
        names = {k: "jit(_unknown)/while/body/dot_general:" for k in names}
    ops = [(_op("fusion.1"), 0, 36), (_op("fusion.2"), 36, 10), (_op("fusion.3"), 46, 30), (_op("fusion.5"), 76, 6),
           (_op("fusion.1"), 100, 36), (_op("fusion.2"), 136, 10), (_op("fusion.3"), 146, 30),
           (_op("fusion.4"), 180, 12), (_op("fusion.1"), 192, 20)]
    mods = [("jit_decode_chunk(1)", 0, 82), ("jit_decode_chunk(1)", 100, 78), ("jit_prefill_chunk(2)", 180, 32)]
    if not with_names:
        mods = [("jit__unknown(1)", s, d) for _, s, d in mods]
    pf = lambda s, t: ("tpu_engine.batcher.prefill", s, 5, {"rid": 1, "slot": 0, "chunk": 0, **({"tokens": t} if with_names else {})})  # noqa: E731
    host = [pf(80, 256), pf(90, 256), pf(178, 64), ("tpu_engine.batcher.other", 0, 220)]
    path = tmp_path / "trace" / f"{CELL}.seed1.trace1" / "plugins" / "profile" / "t" / "x.xplane.pb"
    path.parent.mkdir(parents=True)
    path.write_bytes(_bytes({"/device:TPU:0": {"XLA Ops": ops, "XLA Modules": mods}, "/host:CPU": {"engine": host}},
                            tf_ops=names))
    monkeypatch.setattr(program_trace, "find_xplane", lambda cell: str(path))
    program_trace.load.cache_clear()
    from harness import counts_sala

    counts_sala._seconds_under.cache_clear()
    cell = manifest.load_cell(manifest.load_manifest(), CELL)
    return {"cell": cell, "trace": trace_reduce.reduce(str(path), 1), "slots": 32, "decode_chunk_steps": 8,
            "device": {"platform": "tpu", "kind": "TPU v5 lite"}, "dispatch_context": [19000, 21000],
            "occupancy": [32, 32], "dispatch_tokens": [250, 246], "engine_stats": dict(STATS) if with_names else {}}


def test_the_new_readers_on_a_synthetic_trace(monkeypatch, tmp_path):
    run = _traced_run(monkeypatch, tmp_path)
    cfg = run["cell"]["config"]
    read = lambda name: manifest.load_reader(name)(run, name)  # noqa: E731
    bw, fl = 819e9, 197e12
    assert read("moe_time_pct.rate") == pytest.approx(100 * (20 + 60 + 12 + 6) / 190)
    assert read("ssm_time_pct.batch32") == pytest.approx(100 * 92 / 190)
    expert = 2 * 3 * 4096 * 768
    assert read("expert_decode_roofline.rate") == pytest.approx(100 * 2 * 8 * 10 * 35.5 * expert / bw / 0.060)
    held = 10 * 98_000 / 200_000
    need = sum(max(t * held * 6 * 4096 * 768 / fl, 36 * expert / bw) for t in (256, 256, 64))
    assert read("expert_prefill_roofline.rate") == pytest.approx(100 * 10 * need / 0.012)
    step = counts_hybrid_moe.decode_step_bytes(cfg, 32, 20000, 35.5)
    assert read("decode_step_hbm_roofline.rate") == pytest.approx(100 * step / bw / (0.080 / 8))
    assert read("expert_tokens_per_step.rate") == pytest.approx(31.0 * 5.0 / 36)  # 248 tokens a dispatch of 8
    assert read("ssm_update_roofline.batch32") == pytest.approx(
        100 * 2 * 8 * 9 * counts_hybrid.ssm_update_bytes(cfg, 32) / bw / 0.092)
    for name in NEW_READERS:
        assert 0 < read(name)  # a synthetic trace: its times are made up, its arithmetic is not


def test_on_a_program_without_the_names_or_the_counters_the_new_readers_return_nothing(monkeypatch, tmp_path):
    """The driver lays these files over the parent's checkout for its traced
    runs: no ``moe`` scope, no ``moe_*`` counter there, and another family's
    configuration in the other cells: the experts' readers read nothing of a
    family without a mixture, and the whole step's share is then that family's
    own (``counts_hybrid.decode_step``), not this one's."""
    run = _traced_run(monkeypatch, tmp_path, with_names=False)
    for name in NEW_READERS:
        assert manifest.load_reader(name)(run, name) is None, name
    named = _traced_run(monkeypatch, tmp_path / "b")
    untraced = {**named, "trace": None}
    micro = copy.deepcopy(named)
    micro["cell"]["config"] = manifest.load_cell(manifest.load_manifest(),
                                                 "granite-4.0-h-micro.serve-chat-burst")["config"]
    for name in NEW_READERS[1:4]:
        assert manifest.load_reader(name)(untraced, name) is None, name
    for name in (NEW_READERS[1], NEW_READERS[2], NEW_READERS[4]):
        assert manifest.load_reader(name)(micro, name) is None, name
    bytes_of_micro = (counts_hybrid.weight_bytes_per_decode_step(micro["cell"]["config"])
                      + counts_hybrid.kv_bytes_per_decode_step(micro["cell"]["config"], 20000)
                      + counts_hybrid.recurrent_bytes_per_decode_step(micro["cell"]["config"], 32))
    assert manifest.load_reader(NEW_READERS[3])(micro, NEW_READERS[3]) == pytest.approx(
        100 * bytes_of_micro / 819e9 / (0.080 / 8))
    assert manifest.load_reader(NEW_READERS[0])(untraced, NEW_READERS[0]) is None


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


def test_the_control_runs_the_mixture_in_int8(monkeypatch):
    """``--control 1`` serves the stack with ``weight_quant=int8`` through the
    fleet (``quant.py`` walks the experts and the shared expert inside each
    kind's stack; ``tests/test_hybrid_moe_stack.py`` holds its logits outside
    bfloat16's tolerance). At a size a test can hold, a tied table answers each
    token with itself and no rounding moves a served token, so that the control
    comes out NOT correct is shown on the chip (PERF.md); here it must run, and
    read no better than the sound run."""
    sound = _run(monkeypatch, CELL, seed=9, seconds=2.0)
    low = _run(monkeypatch, CELL, seed=9, seconds=2.0, control=1)
    assert sound["correct"] is True
    assert low["failed"] == 0 and _numbers(low)["requests_short_of_their_tokens"]["ok"]
    assert _numbers(low)["served_logit_gap_mean"]["value"] >= _numbers(sound)["served_logit_gap_mean"]["value"]
