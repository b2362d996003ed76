"""The seam takes a configuration's family from a file: ``program.model_config``
loads ``families/<model_type>.py`` and knows no key of any family.

- the three configurations the benchmark has become, at their full and their
  rehearsal sizes, the ``ModelConfig`` the parent's inline recipe built, in
  every field the parent set (literals here); a field the program gains later,
  with a default, is not this test's business and does not fail it;
- a ``model_type`` with no family file is a ``FileNotFoundError`` naming the
  path, and a complaint of the lint;
- a new family is files and entries only: a temporary copy of the benchmark
  gets a family, a configuration (tied head, no ``rope_theta``, a
  ``layer_types`` list), a reference and a cell, no file it had is touched,
  and the benchmark's own command drives the cell to ``correct: true``.
"""

import copy
import dataclasses
import filecmp
import json
import os
import shutil
import subprocess
import sys

import pytest

from harness import manifest, program

FULL = dict(vocab_size=32000, d_model=4096, n_heads=32, n_kv_heads=8, d_ff=14336, max_seq_len=32768)
TINY = dict(vocab_size=512, d_model=64, n_heads=4, n_kv_heads=2, d_ff=128, max_seq_len=256, n_layers=2)
# What the recipe relies on besides the published keys: the program's architecture, and a head size that follows
# from the widths. A default of the program's that no family sets (capacity_factor, moe_impl, ...) is not held.
MISTRAL = dict(arch="llama", norm_eps=1e-05, head_dim_override=0, rope_theta=10000.0, sliding_window=4096,
               n_experts=0, top_k=2)
MIXTRAL = dict(arch="llama", norm_eps=1e-05, head_dim_override=0, rope_theta=1000000.0, sliding_window=0, top_k=2)
PARENT = {  # the fields the parent's harness/program.py::model_config set or relied on (PR 25's tree)
    ("mistral-7b-1chip-train", "full"): {**FULL, **MISTRAL, "n_layers": 2},
    ("mistral-7b-1chip-train", "rehearsal"): {**TINY, **MISTRAL},
    ("mistral-7b-1chip-serve", "full"): {**FULL, **MISTRAL, "n_layers": 8},
    ("mistral-7b-1chip-serve", "rehearsal"): {**TINY, **MISTRAL},
    ("mixtral-8x7b-1chip-serve", "full"): {**FULL, **MIXTRAL, "n_layers": 1, "n_experts": 8},
    ("mixtral-8x7b-1chip-serve", "rehearsal"): {**TINY, **MIXTRAL, "n_experts": 4},
}


def _config(name):
    entry = {c["name"]: c for c in manifest.load_manifest()["configs"]}[name]
    with open(os.path.join(manifest.ROOT, entry["file"])) as f:
        return json.load(f)


@pytest.mark.parametrize("grown", [False, True], ids=["today", "with-a-later-field"])
@pytest.mark.parametrize("name,size", sorted(PARENT))
def test_the_model_config_holds_every_field_the_parent_set(name, size, grown, monkeypatch):
    """Only the fields the parent set are compared: a ``model_config`` PR that
    gives the program's ``ModelConfig`` a field with a default (``grown``: the
    drawn row's ``layer_types`` and a multiplier) leaves these cases passing."""
    from tpu_engine.models import transformer as tfm

    if grown:
        later = dataclasses.make_dataclass(
            "ModelConfig", [("layer_types", tuple, ()), ("residual_multiplier", float, 1.0)],
            bases=(tfm.ModelConfig,), frozen=True)
        monkeypatch.setattr(tfm, "ModelConfig", later)
    monkeypatch.setattr(tfm, "MODEL_CONFIGS", dict(tfm.MODEL_CONFIGS))
    config = _config(name)
    if size == "rehearsal":
        config = {**config, **config["rehearsal"]}
    mc = program.model_config(config, name)
    want = {"name": name, **PARENT[name, size]}
    got = dataclasses.asdict(mc)
    assert {k: got[k] for k in want} == want
    assert tfm.MODEL_CONFIGS[name] is mc
    if grown:
        assert got["layer_types"] == () and got["residual_multiplier"] == 1.0


def test_a_family_refuses_what_its_recipe_cannot_represent():
    config = _config("mistral-7b-1chip-serve")
    with pytest.raises(ValueError, match="tied head"):
        program.model_config({**config, "tie_word_embeddings": True}, "x")
    with pytest.raises(ValueError, match="mixtral"):
        program.model_config({**config, "num_local_experts": 8}, "x")
    with pytest.raises(KeyError):
        program.model_config({k: v for k, v in config.items() if k != "rope_theta"}, "x")


def test_a_model_type_without_a_family_file_names_the_path_it_looked_for():
    with pytest.raises(FileNotFoundError) as e:
        program.model_config({**_config("mistral-7b-1chip-serve"), "model_type": "granitemoehybrid"}, "x")
    assert os.path.join(manifest.BENCH_DIR, "families", "granitemoehybrid.py") in str(e.value)


def test_lint_refuses_a_model_type_without_a_family_file(tmp_path):
    man = copy.deepcopy(manifest.load_manifest())
    entry = man["configs"][0]
    config = _config(entry["name"])
    path = tmp_path / entry["file"]
    path.parent.mkdir(parents=True)
    for c in man["configs"]:
        shutil.copy(os.path.join(manifest.ROOT, c["file"]), tmp_path / c["file"])
    assert manifest.lint(man, root=str(tmp_path)) == []
    path.write_text(json.dumps({**config, "model_type": "granitemoehybrid"}))
    assert [c for c in manifest.lint(man, root=str(tmp_path)) if "granitemoehybrid" in c and "families/" in c]


# ----------------------------------------------------------------------------
# A new family is files and entries only, driven through the benchmark's command
# ----------------------------------------------------------------------------

FAMILY = '''"""A later PR's family: learned positions, so no rope_theta; a tied head."""


def model_config(config, name):
    from tpu_engine.models import transformer as tfm

    if not config["tie_word_embeddings"] or set(config["layer_types"]) != {"attention"}:
        raise ValueError("not this family's recipe")
    if len(config["layer_types"]) != config["num_hidden_layers"]:
        raise ValueError("layer_types does not list every layer")
    return tfm.ModelConfig(
        name=name, arch="gpt2", vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        n_layers=config["num_hidden_layers"], n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_attention_heads"], d_ff=config["intermediate_size"],
        max_seq_len=config["max_position_embeddings"], norm_eps=float(config["layer_norm_epsilon"]))
'''

# The test is of the seam, not of a model: the program's own float32 forward stands in.
REFERENCE = '''import jax
import jax.numpy as jnp
import numpy as np


def _mc(cfg):
    from tpu_engine.models import transformer as tfm

    return next(mc for mc in tfm.MODEL_CONFIGS.values()
                if mc.arch == "gpt2" and mc.d_model == cfg["hidden_size"] and mc.vocab_size == cfg["vocab_size"])


def init_params(cfg, seed):
    from tpu_engine.models import transformer as tfm

    return tfm.init_params(jax.random.PRNGKey(seed), _mc(cfg))


def served_logits(params, prompt, served, cfg, length=None, rows=None):
    from tpu_engine.models import transformer as tfm

    toks = jnp.asarray([list(prompt) + list(served)], jnp.int32)
    with jax.default_matmul_precision("highest"):
        lg = tfm.forward(params, toks, _mc(cfg), compute_dtype=jnp.float32)[0]
    lg = lg[len(prompt) - 1:len(prompt) - 1 + len(served)]
    return lg, np.full(len(served), np.inf)
'''


def _files(top):
    return sorted(os.path.relpath(os.path.join(d, f), top) for d, _, fs in os.walk(top) for f in fs)


def test_a_new_family_is_files_and_entries_only_and_the_command_drives_it(tmp_path):
    bench = tmp_path / "benchmarks" / "onchip"
    skip = shutil.ignore_patterns("__pycache__", ".cache", "out", "archive_check", "tests", ".pytest_cache")
    shutil.copytree(manifest.BENCH_DIR, bench, ignore=skip)
    had = _files(bench)
    man = manifest.load_manifest()

    serve = _config("mistral-7b-1chip-serve")
    tiny = dict(serve["rehearsal"], layer_types=["attention"] * 2, intermediate_size=256)
    config = {
        "model_type": "dummy-tied", "hidden_size": 768, "intermediate_size": 3072, "num_attention_heads": 12,
        "num_hidden_layers": 12, "layer_types": ["attention"] * 12, "vocab_size": 50257,
        "max_position_embeddings": 1024, "layer_norm_epsilon": 1e-05, "tie_word_embeddings": True,
        "reduced": [], "reference": "dummy_tied", "role": "serve", "program": serve["program"],
        "check": serve["check"], "rehearsal": tiny,
    }
    tiny.pop("num_key_value_heads")  # the family reads none: every head has its own keys and values
    assert "rope_theta" not in config and "num_key_value_heads" not in config
    (bench / "families" / "dummy-tied.py").write_text(FAMILY)
    (bench / "reference" / "dummy_tied.py").write_text(REFERENCE)
    (bench / "configs" / "dummy-tied-1chip-serve.json").write_text(json.dumps(config))
    new = copy.deepcopy(man)
    new["configs"].append({"name": "dummy-tied-1chip-serve", "source": "https://example.org/dummy-tied",
                           "file": "benchmarks/onchip/configs/dummy-tied-1chip-serve.json", "reduced": [],
                           "why": "a later PR's family: tied head, learned positions, a layer_types list"})
    cell = {"name": "dummy-tied.serve-chat", "config": "dummy-tied-1chip-serve", "traffic": "chat-open",
            "chips": 1, "why": "a later PR's cell on traffic the benchmark has"}
    new["workloads"].append(cell)
    for m in new["end_to_end"] + new["per_layer"]:
        if "mistral-7b.serve-chat" in m.get("workloads", []):
            m["workloads"].append(cell["name"])
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(new))

    # Nothing the benchmark had is touched, and what the manifest had is still there, entry for entry.
    assert [f for f in had if not filecmp.cmp(bench / f, os.path.join(manifest.BENCH_DIR, f), shallow=False)] == []
    assert sorted(set(_files(bench)) - set(had)) == [
        "configs/dummy-tied-1chip-serve.json", "families/dummy-tied.py", "reference/dummy_tied.py"]
    for sec in ("configs", "workloads", "end_to_end", "per_layer"):
        kept = [{k: v for k, v in x.items() if k != "workloads"} for x in new[sec][:len(man[sec])]]
        assert kept == [{k: v for k, v in x.items() if k != "workloads"} for x in man[sec]]

    env = {**os.environ, "ONCHIP_REHEARSAL": "1", "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
           "PYTHONPATH": manifest.ROOT}  # tpu_engine, the system under test, is not copied
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    out = subprocess.run(
        [sys.executable, str(bench / "run.py"), "--workload", cell["name"], "--seed", "2147483999",
         "--seconds", "3", "--trace", "0"], cwd=tmp_path, env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    result = json.loads(out.stdout.strip().splitlines()[-1])
    assert result["correct"] is True, result
    assert result["failed"] == 0 and result["attempted"] >= 6
    compared = {r["number"]: r for r in result["rehearsal"]["compared"]}
    assert compared["served_logit_gap_max"]["tokens_compared"] >= 20

    lint = subprocess.run(
        [sys.executable, "-c", "import sys; sys.path.insert(0, sys.argv[1]); from harness import manifest; "
         "print(manifest.lint(manifest.load_manifest()))", str(bench)],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=120)
    assert lint.stdout.strip() == "[]", lint.stdout + lint.stderr[-2000:]
