"""The committed benchmark, held by tier-1 (CPU, under a second): a product PR
that renames a ``ModelConfig`` field, or a benchmark PR whose files do not
load, is caught here and not on the chip.

- ``BENCHMARK.json`` lints clean (``harness.manifest.lint``: what the driver
  would refuse before a run, as far as it can be told here);
- every configuration's family and reference load by the names its file gives;
- ``program.model_config`` builds each configuration at its rehearsal size.
"""

import json
import os
import re
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks", "onchip")
for p in (ROOT, BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)

from harness import manifest, program  # noqa: E402

MANIFEST = manifest.load_manifest()
CONFIGS = [c["name"] for c in MANIFEST["configs"]]


def _config(name: str) -> dict:
    entry = {c["name"]: c for c in MANIFEST["configs"]}[name]
    with open(os.path.join(ROOT, entry["file"])) as f:
        return json.load(f)


def test_the_committed_manifest_lints_clean():
    assert manifest.lint(MANIFEST) == []


def test_the_rules_of_form_that_lint_does_not_hold():
    """``harness.manifest.lint`` is the benchmark's file and checks a cell's
    ``why`` alone. The driver holds every line of prose to the same rule (PR 27
    was refused once over a configuration's ``why`` of 204 characters), and the
    file, each ``reduced`` list and each file name under ``paths`` to limits of
    their own."""
    lines = [(f"command word {i}", word) for i, word in enumerate(MANIFEST["command"])]
    for section, keys in (("configs", ("why", "source")), ("workloads", ("why",)), ("per_layer", ("layer",))):
        lines += [(f"{section} {entry['name']}: {key}", entry[key]) for entry in MANIFEST[section] for key in keys]
    for what, text in lines:
        assert 1 <= len(text) <= 200 and text.isprintable() and text.isascii(), (what, len(text))
    for c in MANIFEST["configs"]:
        assert len(c["reduced"]) <= 16, c["name"]
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    for path in MANIFEST["paths"]:
        for folder, _, files in os.walk(os.path.join(ROOT, path)):
            for name in files:
                rel = os.path.relpath(os.path.join(folder, name), ROOT)
                assert "__pycache__" in rel or re.fullmatch(r"[A-Za-z0-9_.\-/]+", rel), rel


def test_every_cell_loads_its_files_and_every_metric_has_a_reader():
    for w in MANIFEST["workloads"]:
        cell = manifest.load_cell(MANIFEST, w["name"])
        assert cell["end_to_end"] and cell["per_layer"], w["name"]
        for m in cell["per_layer"]:
            assert manifest.reader_path(m["name"]), m["name"]


@pytest.mark.parametrize("name", CONFIGS)
def test_a_configurations_family_and_reference_load(name):
    config = _config(name)
    # the family the file states, else the model_type it publishes: with
    # ``family`` stated, ``model_type`` names another recipe's file
    family = manifest.load_by_name("families", manifest.family_of(config))
    reference = manifest.load_by_name("reference", config["reference"])
    assert callable(family.model_config)
    assert callable(reference.init_params) and callable(reference.served_logits)


@pytest.mark.parametrize("name", CONFIGS)
def test_a_configuration_builds_at_its_rehearsal_size(name):
    from tpu_engine.models import transformer as tfm

    config = _config(name)
    small = {**config, **config["rehearsal"]}
    try:
        mc = program.model_config(small, name)
        assert tfm.MODEL_CONFIGS[name] is mc and mc.name == name
        assert mc.n_layers == small["num_hidden_layers"] and mc.d_model == small["hidden_size"]
        assert tfm.param_count(mc) > 0
        full = program.model_config(config, name)  # the published widths build too (no array is made)
        assert full.d_model == config["hidden_size"] and full.vocab_size == config["vocab_size"]
        for held in {"num_local_experts", "n_routed_experts"} & set(config["reduced"]):
            # One chip's share of the experts (under the key the family
            # publishes them by): the router keeps the published width, the
            # tree holds what the file keeps, at both sizes.
            for sized, cfg in ((full, config), (mc, small)):
                assert sized.n_experts == cfg["published"][held] > cfg[held]
                assert sized.n_experts_held == cfg[held]
                assert sized.top_k == cfg["num_experts_per_tok"] <= sized.n_experts
        if "kv_lora_rank" in config:
            # Latent attention: the family lays the pattern out from
            # ``first_k_dense_replace`` (the file has no ``layer_types`` key).
            dense = small["first_k_dense_replace"]
            assert mc.layer_types == ("mla_dense",) * dense + ("mla",) * (mc.n_layers - dense)
            assert full.latent_width == config["kv_lora_rank"] + config["qk_rope_head_dim"]
        elif "layer_types" not in config and "mixer_types" not in config:
            # A configuration without a layer pattern is untouched by the fields a
            # pattern brought (PR 27): each stays at its default, so its programs
            # are the ones it had.
            plain = tfm.ModelConfig()
            for field in ("layer_types", "ssm_heads", "ssm_head_dim", "ssm_state", "ssm_groups", "ssm_conv",
                          "ssm_chunk", "embed_scale", "residual_scale", "logits_divisor", "attn_scale", "rope",
                          "tie_head"):
                assert getattr(full, field) == getattr(plain, field), field
            assert not full.is_hybrid and full.n_attn_layers == full.n_layers
    finally:
        tfm.MODEL_CONFIGS.pop(name, None)
