"""What the kimi-vl-a3b configuration brought to the benchmark, on the CPU: the
family's mapping and refusals, the configuration against the catalog's row, the
reference against the program at rehearsal size, the count functions and the
seven readers of its mechanisms on a synthetic trace whose numbers are known exactly, and the
new cell driven end to end through ``run.py``'s runner at its rehearsal size."""

import copy
import json
import os

import pytest

from harness import counts_mla_moe, manifest, program
from tests.test_harness_drive import _numbers, _run
from tests.test_program_trace import _bytes, _op

CELL = "kimi-vl-a3b.serve-longctx32"
CONFIG = "kimi-vl-a3b-1chip-serve"
# The family's own three, and the four it shares with every family whose counts
# module states the same names (PR 42: ``harness/counts_for.py``).
NEW_READERS = ("mla_time_pct.longctx32", "mla_decode_roofline.longctx32", "mla_prefill_roofline.longctx32",
               "decode_step_hbm_roofline.rate", "expert_decode_roofline.rate",
               "expert_prefill_roofline.rate", "expert_tokens_per_step.rate")


def _config():
    with open(os.path.join(manifest.BENCH_DIR, "configs", CONFIG + ".json")) as f:
        return json.load(f)


# -- the family ----------------------------------------------------------------


def test_the_file_states_its_family_and_the_family_maps_the_published_keys():
    cfg = _config()
    assert "model_type" not in cfg and manifest.family_of(cfg) == "deepseek_v3"
    mc = program.model_config(cfg, CONFIG)
    assert (mc.d_model, mc.n_heads, mc.n_kv_heads, mc.head_dim, mc.vocab_size, mc.max_seq_len) == \
        (2048, 16, 16, 192, 163840, 131072)
    assert (mc.kv_latent_dim, mc.qk_nope_dim, mc.qk_rope_dim, mc.v_head_dim, mc.latent_width) == (512, 128, 64, 128, 576)
    assert (mc.d_ff, mc.dense_d_ff, mc.shared_d_ff) == (1408, 11264, 2816)
    assert (mc.n_experts, mc.top_k, mc.experts_first, mc.experts_held, mc.n_experts_held) == (64, 6, 0, 16, 16)
    assert (mc.router_scoring, mc.routed_scale, mc.router_bias_std) == ("sigmoid", 2.446, 0.02)
    assert (mc.rope_theta, mc.norm_eps, mc.tied_head, mc.arch) == (800000.0, 1e-5, False, "llama")
    assert mc.n_layers == 13 and mc.published_layers == 27 and mc.n_mixture_layers == 12
    assert mc.layer_runs() == (("mla_dense", 0, 1), ("mla", 0, 12))
    # every expert held: the uncut layer, and the fields for a share stay at their defaults
    uncut = program.model_config({**cfg, "n_routed_experts": 64}, CONFIG)
    assert (uncut.experts_first, uncut.experts_held, uncut.n_experts_held) == (0, 0, 64)
    second = program.model_config({**cfg, "first_local_expert": 16}, CONFIG)
    assert (second.experts_first, second.n_experts_held) == (16, 16)


@pytest.mark.parametrize("change, says", [
    ({"q_lora_rank": 1536}, "query latent"),
    ({"n_group": 8, "topk_group": 4}, "group-limited"),
    ({"n_routed_experts": 24}, "no whole share"),
    ({"n_routed_experts": 0}, "no whole share"),
    ({"first_local_expert": 8}, "does not start a share"),
    ({"first_local_expert": 64}, "does not start a share"),
    ({"published": {"num_hidden_layers": 27}}, "published.n_routed_experts is missing"),
    ({"num_experts_per_tok": 65}, "num_experts_per_tok=65"),
    ({"scoring_func": "softmax"}, "scoring_func"),
    ({"topk_method": "greedy"}, "topk_method"),
    ({"rope_scaling": {"type": "yarn", "factor": 40}}, "rope_scaling"),
    ({"num_key_value_heads": 4}, "num_key_value_heads"),
    ({"tie_word_embeddings": True}, "tie_word_embeddings"),
])
def test_the_family_refuses_what_the_recipe_cannot_represent(change, says):
    with pytest.raises(ValueError, match=says):
        program.model_config({**_config(), **change}, CONFIG)


def test_the_configuration_holds_the_catalogs_row_but_for_the_cut():
    """Every number of the catalog's ``config`` under the same key; ``reduced``
    names the depth and the experts held, and no width; the file states 27
    layers, 64 experts, the deployment and what it assumed."""
    cfg = _config()
    assert cfg["reduced"] == ["num_hidden_layers", "n_routed_experts"]
    assert not any(manifest.names_a_width(k) for k in cfg["reduced"])
    published = dict(vocab_size=163840, max_position_embeddings=131072, hidden_size=2048, intermediate_size=11264,
                     moe_intermediate_size=1408, num_attention_heads=16, n_shared_experts=2, ep_size=1,
                     routed_scaling_factor=2.446, kv_lora_rank=512, q_lora_rank=None, qk_rope_head_dim=64,
                     v_head_dim=128, qk_nope_head_dim=128, topk_method="noaux_tc", n_group=1, topk_group=1,
                     num_experts_per_tok=6, moe_layer_freq=1, first_k_dense_replace=1, norm_topk_prob=True,
                     scoring_func="sigmoid", seq_aux=True, num_key_value_heads=16, hidden_act="silu",
                     rms_norm_eps=1e-05, rope_theta=800000, rope_scaling=None, attention_bias=False,
                     tie_word_embeddings=False)
    assert {k: cfg[k] for k in published} == published
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"]) == (13, 16)
    assert cfg["published"] == {"num_hidden_layers": 27, "n_routed_experts": 64}
    assert cfg["source"].endswith("moonshotai/Kimi-VL-A3B-Instruct/blob/main/config.json")
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.isfile(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == "Kimi-VL-A3B-Instruct")
        assert {k: v for k, v in row["config"].items() if cfg[k] != v} == \
            {"num_hidden_layers": 27, "n_routed_experts": 64}
        assert cfg["source"] == row["source_url"]
    assert "4 chips" in cfg["deployment"] and set(cfg["assumed"]) >= {
        "family", "tower", "init", "router_bias", "rotary_pairing", "state_dtypes", "routing"}
    assert cfg["program"] == dict(max_slots=32, max_len=10240, tensor_parallel=1, compute_dtype="BF16",
                                  prefill_chunk=2048, decode_chunk_steps=8, prefix_cache_tokens=0, kv_quant=False)
    small = {**cfg, **cfg["rehearsal"]}
    assert small["published"]["n_routed_experts"] == 8 and small["n_routed_experts"] == 4
    assert small["num_hidden_layers"] == 3 and small["num_experts_per_tok"] == 3


def test_the_traffic_file_is_the_issues():
    cell = manifest.load_cell(manifest.load_manifest(), CELL)
    t = cell["traffic"]
    assert (t["generator"], t["clients"], t["requests"], t["order_seed"], t["lead_in_s"], t["trace_s"]) == \
        ("closed", 32, 128, 0, 6.0, 6.0)
    assert t["prompt_tokens"] == {"dist": "uniform", "min": 2048, "max": 8192, "round_to": 2048}
    assert t["output_tokens"] == {"dist": "uniform", "min": 512, "max": 1536, "round_to": 1}
    assert cell["cell"]["chips"] == 1 and {m["name"] for m in cell["end_to_end"]} == {"serve_tokens_per_s", "setup_s"}
    assert set(NEW_READERS) < {m["name"] for m in cell["per_layer"]}
    # four lengths, every chunk full, and nothing past the pool's lanes
    from harness.generators import closed

    plan = closed.plan(t, 1000, 1, 50)
    assert sorted(set(plan.prompt_lens.tolist())) == [2048, 4096, 6144, 8192]
    assert int(plan.prompt_lens.max() + plan.output_lens.max()) <= cell["config"]["program"]["max_len"]


# -- the reference against the program ---------------------------------------------


def test_the_reference_and_the_program_agree_at_rehearsal_size():
    """One seed drawn twice, by the program and by the reference, each by its
    own code; float32 on both sides (measured 1.8e-7 where logits spread 0.08)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from reference import deepseek_v3 as ref
    from tpu_engine.generate import forward_with_cache, init_cache
    from tpu_engine.models import transformer as tfm

    cfg = {**_config(), **_config()["rehearsal"]}
    mc = program.model_config(cfg, CONFIG)
    toks = np.random.default_rng(3).integers(0, cfg["vocab_size"], 90).astype(np.int32)
    got, cache = forward_with_cache(tfm.init_params(jax.random.PRNGKey(7), mc), jnp.asarray(toks)[None],
                                    init_cache(mc, 1, 96, dtype=jnp.float32), mc, compute_dtype=jnp.float32)
    want, margin = ref.forward_logits(ref.init_params(cfg, 7), toks, cfg)
    assert float(margin.min()) > 1e-6
    assert np.abs(np.asarray(got[0]) - np.asarray(want)).max() < 2e-6
    assert cache.moe_counts.tolist()[0] == 90 * 3 * 2  # two mixture layers of three
    lg, served_margin = ref.served_logits(ref.init_params(cfg, 7), toks[:60].tolist(), toks[60:].tolist(), cfg)
    np.testing.assert_allclose(np.asarray(lg), np.asarray(want)[59:89], atol=2e-6)
    np.testing.assert_allclose(np.asarray(served_margin), np.asarray(margin)[59:89], atol=1e-6)


# -- counts ----------------------------------------------------------------------


def test_counts_of_the_configuration_by_hand():
    cfg = _config()
    c = counts_mla_moe
    assert c.is_mla_moe(cfg) and c.n_layers(cfg) == 13 and c.n_mixture_layers(cfg) == 12
    assert c.latent_row_bytes(cfg) == 1152
    assert c.expansion_weights(cfg) == 512 * 16 * 256 == 2_097_152
    mla = 2048 * 16 * 192 + 2048 * 576 + 2_097_152 + 16 * 128 * 2048
    assert c.mla_weights(cfg) == mla == 13_762_560                  # ISSUE 40: 13.76 M
    assert c.expert_weights(cfg) == 3 * 2048 * 1408 == 8_650_752    # 8.65 M
    assert c.assignment_flops(cfg) == 6 * 2048 * 1408
    fixed = 2048 * 64 + 64 + 3 * 2048 * 2816
    assert c.mixture_fixed_weights(cfg) == fixed
    assert c.absorbed_decode_bytes(cfg, 180_000) == 180_000 * 1152 + 2 * 2_097_152
    weights = 13 * mla + 3 * 2048 * 11264 + 12 * fixed + 2048 * 163840
    assert c.decode_step_bytes(cfg, 180_000, 11.5) == 2 * weights + 12 * 11.5 * 2 * 8_650_752 + 13 * 180_000 * 1152
    # what the cell's ``why`` says: with every lane of every slot read, the latent is half a step's bytes
    full = c.decode_step_bytes(cfg, 32 * 10240, 16)
    assert 0.47 < 13 * 32 * 10240 * 1152 / full < 0.53 and 9.5e9 < full < 10.1e9   # ISSUE 40: about 9.8 GB
    # a chunk: the visible lanes through W_kvb, then the causal triangle
    assert c.mla_chunk_flops(cfg, 2048, 2048) == \
        2 * 4096 * 2_097_152 + 2 * (2048 * 2048 + 2048 * 2049 / 2) * 16 * (192 + 128)
    whole = c.chunk_flops(cfg, 0, 2048, 1.5)
    routed = 12 * 2048 * 1.5 * 6 * 2048 * 1408
    assert 0.15 < routed / whole < 0.35
    stats = {"held_experts": 16, "moe_decode_layer_steps_total": 960, "moe_decode_assignments_total": 960 * 186,
             "moe_decode_assignments_held_total": 960 * 46, "moe_decode_experts_hit_total": 960 * 15}
    assert c.per_layer_step(stats, "decode", "experts_hit") == 15
    assert c.held_assignments_per_token(stats, "decode", 6) == pytest.approx(6 * 46 / 186)
    assert c.expert_tokens_per_step(stats, 31.0, 6) == pytest.approx(31.0 * 6 * 46 / 186 / 16)
    small = json.load(open(os.path.join(manifest.BENCH_DIR, "configs", "granite-4.0-h-small-1chip-serve.json")))
    assert not c.is_mla_moe(small)


# -- the readers, on a trace whose numbers are known ---------------------------------

STATS = {"held_experts": 16,
         "moe_decode_layer_steps_total": 1200, "moe_decode_assignments_total": 230_400,
         "moe_decode_assignments_held_total": 57_600, "moe_decode_experts_hit_total": 18_000,
         "moe_prefill_layer_steps_total": 120, "moe_prefill_assignments_total": 1_474_560,
         "moe_prefill_assignments_held_total": 368_640, "moe_prefill_experts_hit_total": 1_920}


def _traced_run(monkeypatch, tmp_path, with_names=True):
    """A run of the new cell with a synthetic trace: 2 decode chunks of 8 steps
    (120 ms each) and 2 prefill chunks (chunk 0 and chunk 2 of their prompts,
    2 048 tokens each); in the decode program 10 ms of ``mla_latent``, 2 x 8 ms
    of ``mla_absorb``, 2 x 50 ms of ``mla_attend`` and 2 x 20 ms of
    ``moe_experts``; in the prefill program 6 ms of ``mla_expand``, 30 ms of
    ``mla_attend`` and 14 ms of ``moe_experts``; 8 ms of router; 250 ms busy.
    ``with_names=False``: the same device time from a program that has none of
    this PR's names."""
    from harness import counts_sala, program_trace, trace_reduce

    dec = "jit(decode_chunk)/while/body/while/body/"
    pre = "jit(prefill_chunk)/while/body/"
    names = {
        _op("fusion.1"): dec + "mla/mla_latent/scatter:",
        _op("fusion.2"): dec + "mla/mla_absorb/dot_general:",
        _op("fusion.3"): dec + "mla/mla_attend/dot_general:",
        _op("fusion.4"): dec + "moe/moe_experts/dot_general:",
        _op("fusion.5"): pre + "mla/mla_expand/dot_general:",
        _op("fusion.6"): pre + "mla/mla_attend/dot_general:",
        _op("fusion.7"): pre + "moe/moe_experts/ragged_dot:",
        _op("fusion.8"): dec + "moe/moe_router/dot_general:",
    }
    if not with_names:
        names = {k: "jit(_unknown)/while/body/dot_general:" for k in names}
    ops = [(_op("fusion.1"), 0, 10), (_op("fusion.2"), 10, 8), (_op("fusion.3"), 18, 50), (_op("fusion.4"), 68, 20),
           (_op("fusion.8"), 88, 8),
           (_op("fusion.2"), 120, 8), (_op("fusion.3"), 128, 50), (_op("fusion.4"), 178, 20),
           (_op("fusion.5"), 240, 6), (_op("fusion.6"), 246, 30), (_op("fusion.7"), 276, 14),
           (_op("fusion.1"), 290, 26)]
    mods = [("jit_decode_chunk(1)", 0, 120), ("jit_decode_chunk(1)", 120, 120), ("jit_prefill_chunk(2)", 240, 76)]
    if not with_names:
        mods = [("jit__unknown(1)", s, d) for _, s, d in mods]
    pf = lambda s, i: ("tpu_engine.batcher.prefill", s, 5,  # noqa: E731
                       {"rid": 1, "slot": 0, "chunk": i, **({"tokens": 2048} if with_names else {})})
    host = [pf(230, 0), pf(236, 2), ("tpu_engine.batcher.other", 0, 320)]
    path = tmp_path / "trace" / f"{CELL}.seed1.trace1" / "plugins" / "profile" / "t" / "x.xplane.pb"
    path.parent.mkdir(parents=True)
    path.write_bytes(_bytes({"/device:TPU:0": {"XLA Ops": ops, "XLA Modules": mods}, "/host:CPU": {"engine": host}},
                            tf_ops=names))
    monkeypatch.setattr(program_trace, "find_xplane", lambda cell: str(path))
    program_trace.load.cache_clear()
    counts_sala._seconds_under.cache_clear()
    cell = manifest.load_cell(manifest.load_manifest(), CELL)
    return {"cell": cell, "trace": trace_reduce.reduce(str(path), 1), "slots": 32, "decode_chunk_steps": 8,
            "device": {"platform": "tpu", "kind": "TPU v5 lite"}, "dispatch_context": [190_000, 194_000],
            "occupancy": [32, 32], "dispatch_tokens": [248, 248], "engine_stats": dict(STATS) if with_names else {}}


def test_the_new_readers_on_a_synthetic_trace(monkeypatch, tmp_path):
    run = _traced_run(monkeypatch, tmp_path)
    cfg = run["cell"]["config"]
    read = lambda name: manifest.load_reader(name)(run, name)  # noqa: E731
    bw, fl = 819e9, 197e12
    assert read("mla_time_pct.longctx32") == pytest.approx(100 * (36 + 16 + 100 + 6 + 30) / 250)
    assert read("moe_time_pct.rate") == pytest.approx(100 * (40 + 14 + 8) / 250)
    context = 31.0 * 384_000 / 64  # 31 of the 32 held rows decode, at the held rows' average context
    need = 2 * 8 * 13 * counts_mla_moe.absorbed_decode_bytes(cfg, context)
    assert read("mla_decode_roofline.longctx32") == pytest.approx(100 * need / bw / 0.116)
    flops = counts_mla_moe.mla_chunk_flops(cfg, 0, 2048) + counts_mla_moe.mla_chunk_flops(cfg, 4096, 2048)
    assert read("mla_prefill_roofline.longctx32") == pytest.approx(100 * 13 * flops / fl / 0.036)
    step = counts_mla_moe.decode_step_bytes(cfg, context, 15.0)
    assert read("decode_step_hbm_roofline.rate") == pytest.approx(100 * step / bw / (0.120 / 8))
    expert = 2 * 3 * 2048 * 1408
    assert read("expert_decode_roofline.rate") == pytest.approx(100 * 2 * 8 * 12 * 15.0 * expert / bw / 0.040)
    held = 6 * 368_640 / 1_474_560
    one = max(2048 * held * 6 * 2048 * 1408 / fl, 16 * expert / bw)
    assert read("expert_prefill_roofline.rate") == pytest.approx(100 * 12 * 2 * one / 0.014)
    assert read("expert_tokens_per_step.rate") == pytest.approx(31.0 * 1.5 / 16)
    for name in NEW_READERS:
        assert 0 < read(name)  # a synthetic trace: its times are made up, its arithmetic is not


def test_on_a_program_without_the_names_or_the_counters_the_new_readers_return_nothing(monkeypatch, tmp_path):
    """The driver lays these files over the parent's checkout for its traced
    runs: no ``mla`` scope, no ``moe_*`` counter there, and another family's
    configuration in the other cells. The family's own readers read nothing of
    another family; the four shared ones read nothing of a configuration that
    no counts module knows, and the experts' three nothing of a family without
    a mixture."""
    run = _traced_run(monkeypatch, tmp_path, with_names=False)
    for name in NEW_READERS:
        assert manifest.load_reader(name)(run, name) is None, name
    named = _traced_run(monkeypatch, tmp_path / "b")
    untraced = {**named, "trace": None}
    other, unknown, dense = copy.deepcopy(named), copy.deepcopy(named), copy.deepcopy(named)
    other["cell"]["config"] = manifest.load_cell(manifest.load_manifest(),
                                                 "granite-4.0-h-small.serve-batch32")["config"]
    unknown["cell"]["config"] = {"hidden_size": 2048}
    dense["cell"]["config"] = manifest.load_cell(manifest.load_manifest(),
                                                 "granite-4.0-h-micro.serve-chat-burst")["config"]
    for name in NEW_READERS[1:6]:
        assert manifest.load_reader(name)(untraced, name) is None, name
    for name in NEW_READERS[1:3]:
        assert manifest.load_reader(name)(other, name) is None, name
    for name in NEW_READERS[3:]:
        assert manifest.load_reader(name)(unknown, name) is None, name
    for name in NEW_READERS[4:]:
        assert manifest.load_reader(name)(dense, name) is None, name
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


def test_the_control_runs_the_stack_in_int8(monkeypatch):
    """``--control 1`` serves the stack with ``weight_quant=int8`` through the
    fleet (``quant.py`` walks ``kv_a`` / ``kv_b``, the experts and the shared
    experts inside each kind's stack; ``tests/test_mla_stack.py`` holds its
    logits outside bfloat16's tolerance). That the control comes out NOT
    correct is shown on the chip (PERF.md); at a size a test can hold the two
    runs finish other requests in their two seconds and a few dozen served
    tokens' gaps say nothing of a precision, so here it must run, serve every
    token asked for, and compare some."""
    low = _run(monkeypatch, CELL, seed=9, seconds=2.0, control=1)
    assert low["failed"] == 0 and _numbers(low)["requests_short_of_their_tokens"]["ok"]
    assert _numbers(low)["served_logit_gap_max"]["tokens_compared"] >= 16
    assert _numbers(low)["served_logit_gap_mean"]["value"] < 1e-3
