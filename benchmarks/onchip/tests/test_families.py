"""The seam takes a configuration's family from a file: ``program.model_config``
loads ``families/<family>.py`` and knows no key of any family.

- the configurations the benchmark has become, at their full and their
  rehearsal sizes, the ``ModelConfig`` the parent's recipe built, in every
  field the parent set (literals here); a field the program gains later, with
  a default, is not this test's business and does not fail it;
- a configuration's family is the ``family`` its file states, else the
  ``model_type`` it publishes (``manifest.family_of``), for ``program`` and for
  the lint alike; a family with no file is a ``FileNotFoundError`` naming the
  path, and a complaint of the lint that names it too;
- a new family is files and entries only: a temporary copy of the benchmark
  gets a family, a configuration (tied head, no ``rope_theta``, a
  ``layer_types`` list), a reference and a cell, no file it had is touched,
  and the benchmark's own command drives the cell to ``correct: true``;
- so is a second recipe under a ``model_type`` the benchmark has: a file that
  publishes ``granitemoehybrid`` with experts and a shared expert of another
  width states ``family``, and its family builds on ``granitemoehybrid.fields``.
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
GRANITE = dict(arch="llama", norm_eps=1e-05, ssm_groups=1, ssm_conv=4, embed_scale=12.0, residual_scale=0.22,
               logits_divisor=8.0, attn_scale=0.015625, rope=False, tie_head=True)
PERIOD = ("mamba",) * 5 + ("attention",) + ("mamba",) * 4
PARENT = {  # the fields the parent's recipe set or relied on (PR 25's harness/program.py; PR 32's granitemoehybrid.py)
    ("granite-4.0-h-micro-1chip-serve", "full"): {
        **GRANITE, "vocab_size": 100352, "d_model": 2048, "n_layers": 20, "n_heads": 32, "n_kv_heads": 8,
        "d_ff": 8192, "max_seq_len": 131072, "layer_types": PERIOD * 2, "ssm_heads": 64, "ssm_head_dim": 64,
        "ssm_state": 128, "ssm_chunk": 256},
    ("granite-4.0-h-micro-1chip-serve", "rehearsal"): {
        **GRANITE, "vocab_size": 512, "d_model": 64, "n_layers": 4, "n_heads": 4, "n_kv_heads": 2, "d_ff": 128,
        "max_seq_len": 256, "layer_types": ("mamba", "mamba", "attention", "mamba"), "ssm_heads": 8,
        "ssm_head_dim": 16, "ssm_state": 64, "ssm_chunk": 32},
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
    gives the program's ``ModelConfig`` a field with a default (``grown``: which
    experts a layer holds and a shared expert's width, as granite-4.0-h-small's
    PR may grow it) leaves these cases passing."""
    from tpu_engine.models import transformer as tfm

    if grown:
        later = dataclasses.make_dataclass(
            "ModelConfig", [("experts_held", tuple, ()), ("shared_d_ff", int, 0)],
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
        assert got["experts_held"] == () and got["shared_d_ff"] == 0


def test_a_family_refuses_what_its_recipe_cannot_represent():
    config = _config("mistral-7b-1chip-serve")
    with pytest.raises(ValueError, match="tied head"):
        program.model_config({**config, "tie_word_embeddings": True}, "x")
    with pytest.raises(ValueError, match="mixtral"):
        program.model_config({**config, "num_local_experts": 8}, "x")
    with pytest.raises(KeyError):
        program.model_config({k: v for k, v in config.items() if k != "rope_theta"}, "x")


NO_FAMILY = "no-catalog-holds-this-family"


@pytest.mark.parametrize("key", ["model_type", "family"])
def test_a_family_without_a_file_names_the_path_it_looked_for(key):
    with pytest.raises(FileNotFoundError) as e:
        program.model_config({**_config("mistral-7b-1chip-serve"), key: NO_FAMILY}, "x")
    assert os.path.join(manifest.BENCH_DIR, "families", NO_FAMILY + ".py") in str(e.value)


def test_the_family_a_file_states_is_taken_before_the_model_type_it_publishes():
    config = _config("mixtral-8x7b-1chip-serve")
    assert manifest.family_of(config) == "mixtral" and "family" not in config
    stated = {**config, "model_type": "mistral", "family": "mixtral"}
    assert manifest.family_of(stated) == "mixtral"
    assert program.model_config(stated, "x") == program.model_config(config, "x")  # experts and all: mixtral.py built it
    with pytest.raises(ValueError, match="mixtral"):
        program.model_config({**config, "model_type": "mistral"}, "x")  # without the key: mistral.py, which refuses experts
    for name in ("../harness/program", "families.mistral", ""):
        with pytest.raises(ValueError, match="plain module name"):
            program.model_config({**config, "family": name}, "x")


def test_a_committed_configuration_states_a_family_only_where_its_model_type_cannot_name_one():
    """``family`` is a key a file MAY state (PR 33), and only two kinds of file
    do: a second recipe under a ``model_type`` that another committed file
    already takes as its family (granite-4.0-h-small beside -micro, PR 35), and
    a file that publishes no ``model_type`` (kimi-vl-a3b, PR 40: the language
    decoder of a checkpoint whose ``model_type`` is the wrapper's). Every other
    file's family is the ``model_type`` it publishes."""
    configs = {e["name"]: _config(e["name"]) for e in manifest.load_manifest()["configs"]}
    taken = {c["model_type"] for c in configs.values() if "family" not in c}
    for name, config in configs.items():
        if "family" not in config:
            assert manifest.family_of(config) == config["model_type"], name
        else:
            assert config.get("model_type") != config["family"], name
            assert "model_type" not in config or config["model_type"] in taken, name
            assert manifest.family_of(config) == config["family"], name


def _lint_with(tmp_path, **keys):
    """The committed manifest linted against copies of its configuration files,
    the first of them with ``keys`` replaced."""
    man = copy.deepcopy(manifest.load_manifest())
    for c in man["configs"]:
        (tmp_path / c["file"]).parent.mkdir(parents=True, exist_ok=True)
        shutil.copy(os.path.join(manifest.ROOT, c["file"]), tmp_path / c["file"])
    entry = man["configs"][0]
    (tmp_path / entry["file"]).write_text(json.dumps({**_config(entry["name"]), **keys}))
    return manifest.lint(man, root=str(tmp_path))


def test_lint_takes_the_family_a_file_states_and_refuses_one_without_a_file(tmp_path):
    assert _lint_with(tmp_path) == []
    assert _lint_with(tmp_path, model_type="granitemoehybrid", family="mistral") == []
    path = os.path.join(manifest.BENCH_DIR, "families", NO_FAMILY + ".py")
    for keys in ({"model_type": NO_FAMILY}, {"family": NO_FAMILY}):
        assert [c for c in _lint_with(tmp_path, **keys) if path in c and "does not exist" in c], keys
    assert [c for c in _lint_with(tmp_path, family="../harness/program") if "plain module name" in c]


def test_granitemoehybrid_fields_leave_the_block_after_the_mixer_to_the_caller():
    """``fields()`` is the recipe but for the MLP: it never reads the three keys
    that say what the MLP is, and ``model_config`` is ``fields()`` behind the two
    refusals it had, with the messages it had."""
    from families import granitemoehybrid
    from tpu_engine.models import transformer as tfm

    read = set()

    class Watched(dict):
        def __getitem__(self, key):
            read.add(key)
            return super().__getitem__(key)

        def get(self, key, default=None):
            read.add(key)
            return super().get(key, default)

    config = _config("granite-4.0-h-micro-1chip-serve")
    watched = Watched(config)
    mc = granitemoehybrid.model_config(config, "g")
    assert tfm.ModelConfig(**granitemoehybrid.fields(watched, "g")) == mc
    assert "intermediate_size" in read
    assert not read & {"shared_intermediate_size", "num_local_experts", "num_experts_per_tok"}
    small = {**config, "num_local_experts": 72, "num_experts_per_tok": 10, "intermediate_size": 768,
             "shared_intermediate_size": 1536}
    assert granitemoehybrid.fields(small, "g") == {**granitemoehybrid.fields(config, "g"), "d_ff": 768}
    with pytest.raises(ValueError, match=r"num_local_experts=72: experts are not this recipe \(granite-4.0-h-small"):
        granitemoehybrid.model_config(small, "g")
    with pytest.raises(ValueError, match="shared_intermediate_size differs from intermediate_size: one dense MLP"):
        granitemoehybrid.model_config({**small, "num_local_experts": 0}, "g")


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


def _drive_a_copy(tmp_path, files, entry, cell, like):
    """A temporary copy of the benchmark gets ``files`` (path under the
    benchmark -> text), a configuration ``entry`` and a ``cell`` that reports
    what cell ``like`` reports; nothing the benchmark had is touched, the copy
    lints clean and its own ``run.py`` drives the cell. Returns the run's line."""
    bench = tmp_path / "benchmarks" / "onchip"
    skip = shutil.ignore_patterns("__pycache__", ".cache", "out", "archive_check", "tests", ".pytest_cache")
    shutil.copytree(manifest.BENCH_DIR, bench, ignore=skip)
    had = _files(bench)
    man = manifest.load_manifest()
    for rel, text in files.items():
        (bench / rel).write_text(text)
    new = copy.deepcopy(man)
    new["configs"].append(entry)
    new["workloads"].append(cell)
    for m in new["end_to_end"] + new["per_layer"]:
        if like in m.get("workloads", []):
            m["workloads"].append(cell["name"])
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(new))

    # Nothing the benchmark had is touched, and what the manifest had is still there, entry for entry.
    assert [f for f in had if not filecmp.cmp(bench / f, os.path.join(manifest.BENCH_DIR, f), shallow=False)] == []
    assert sorted(set(_files(bench)) - set(had)) == sorted(files)
    for sec in ("configs", "workloads", "end_to_end", "per_layer"):
        kept = [{k: v for k, v in x.items() if k != "workloads"} for x in new[sec][:len(man[sec])]]
        assert kept == [{k: v for k, v in x.items() if k != "workloads"} for x in man[sec]]

    env = {**os.environ, "ONCHIP_REHEARSAL": "1", "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
           "PYTHONPATH": manifest.ROOT}  # tpu_engine, the system under test, is not copied
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    lint = subprocess.run(
        [sys.executable, "-c", "import sys; sys.path.insert(0, sys.argv[1]); from harness import manifest; "
         "print(manifest.lint(manifest.load_manifest()))", str(bench)],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=120)
    assert lint.stdout.strip() == "[]", lint.stdout + lint.stderr[-2000:]
    out = subprocess.run(
        [sys.executable, str(bench / "run.py"), "--workload", cell["name"], "--seed", "2147483999",
         "--seconds", "3", "--trace", "0"], cwd=tmp_path, env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_a_new_family_is_files_and_entries_only_and_the_command_drives_it(tmp_path):
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
    result = _drive_a_copy(
        tmp_path,
        {"families/dummy-tied.py": FAMILY, "reference/dummy_tied.py": REFERENCE,
         "configs/dummy-tied-1chip-serve.json": json.dumps(config)},
        {"name": "dummy-tied-1chip-serve", "source": "https://example.org/dummy-tied",
         "file": "benchmarks/onchip/configs/dummy-tied-1chip-serve.json", "reduced": [],
         "why": "a later PR's family: tied head, learned positions, a layer_types list"},
        {"name": "dummy-tied.serve-chat", "config": "dummy-tied-1chip-serve", "traffic": "chat-open",
         "chips": 1, "why": "a later PR's cell on traffic the benchmark has"},
        like="mistral-7b.serve-chat")
    assert result["correct"] is True, result
    assert result["failed"] == 0 and result["attempted"] >= 6
    compared = {r["number"]: r for r in result["rehearsal"]["compared"]}
    assert compared["served_logit_gap_max"]["tokens_compared"] >= 20


# ----------------------------------------------------------------------------
# A second recipe under a model_type the benchmark has: the file states its family
# ----------------------------------------------------------------------------

# What is shown is the lookup, the lint and the reuse of ``fields()``, not a mixture of experts: the program runs
# none inside a hybrid stack yet (ROADMAP M1), so the fixture maps the file onto the stack it does run, the hybrid
# with ONE dense MLP of an expert's width, and its reference is the committed hybrid reference told the same.
SECOND_FAMILY = '''"""A later PR's second recipe under ``granitemoehybrid``: the mixers, the pattern, the head and
the multipliers are ``granitemoehybrid.fields``; the block after the mixer is this file's."""

from . import granitemoehybrid


def model_config(config, name):
    from tpu_engine.models import transformer as tfm

    if not config["num_local_experts"] or config["shared_intermediate_size"] == config["intermediate_size"]:
        raise ValueError("experts and a shared expert of another width are this recipe")
    return tfm.ModelConfig(**granitemoehybrid.fields(config, name))
'''

SECOND_REFERENCE = '''from reference import granitemoehybrid as dense


def _dense(cfg):
    return {**cfg, "num_local_experts": 0}


def init_params(cfg, seed):
    return dense.init_params(_dense(cfg), seed)


def served_logits(params, prompt, served, cfg, **kw):
    return dense.served_logits(params, prompt, served, _dense(cfg), **kw)
'''


def test_a_second_recipe_under_a_model_type_the_benchmark_has_is_files_and_entries_only(tmp_path):
    micro = _config("granite-4.0-h-micro-1chip-serve")
    config = {**micro, "num_local_experts": 72, "num_experts_per_tok": 10, "intermediate_size": 768,
              "shared_intermediate_size": 1536, "family": "granite-second-recipe",
              "reference": "granite_second_recipe",
              "rehearsal": {**micro["rehearsal"], "shared_intermediate_size": 256}}
    assert config["model_type"] == "granitemoehybrid" == micro["model_type"]

    # Without the key the file is the committed family's, which refuses it with the message it had.
    unstated = {k: v for k, v in config.items() if k != "family"}
    for sized in (unstated, {**unstated, **unstated["rehearsal"]}):
        with pytest.raises(ValueError, match="num_local_experts=72: experts are not this recipe"):
            program.model_config(sized, "x")

    result = _drive_a_copy(
        tmp_path,
        {"families/granite-second-recipe.py": SECOND_FAMILY, "reference/granite_second_recipe.py": SECOND_REFERENCE,
         "configs/granite-second-recipe-1chip-serve.json": json.dumps(config)},
        {"name": "granite-second-recipe-1chip-serve", "source": "https://example.org/granite-second-recipe",
         "file": "benchmarks/onchip/configs/granite-second-recipe-1chip-serve.json",
         "reduced": ["num_hidden_layers", "layer_types"],
         "why": "a later PR's second recipe under model_type granitemoehybrid: experts, a shared expert"},
        {"name": "granite-second-recipe.serve-chat-burst", "config": "granite-second-recipe-1chip-serve",
         "traffic": "chat-burst", "chips": 1, "why": "a later PR's cell on traffic the benchmark has"},
        like="granite-4.0-h-micro.serve-chat-burst")
    assert result["correct"] is True, result
    assert result["failed"] == 0 and result["attempted"] >= 6
    compared = {r["number"]: r for r in result["rehearsal"]["compared"]}
    assert compared["served_logit_gap_max"]["tokens_compared"] >= 16
