"""The harness driven end to end on the CPU rehearsal (tiny sizes, no device
metric), skipping only its look for a chip:

- a sound run of a training and of a serving cell comes out ``correct``;
- the control — the program's own int8 path, ``quant_training=int8`` for
  training and ``weight_quant=int8`` for serving (``--control 1``) — comes out
  not correct at a size a test can hold, where the sound run of the same seed
  comes out correct;
- the timed path broken underneath (a step that returns its parameters
  unchanged; a token altered where it is produced; a request cut short) makes
  ``correct`` come out false.

The limits used here are the rehearsal's own (``rehearsal.check`` in each
configuration file): the tiny model's numbers are not the cell's.
"""

import argparse
import time

import pytest

from harness import manifest, program


def _args(seed, seconds, control=0):
    return argparse.Namespace(seed=seed, seconds=seconds, trace=0, control=control)


# Wide enough for int8 weights to move a served token (at the rehearsal's 64 they do not).
MID = {"hidden_size": 256, "intermediate_size": 512, "num_attention_heads": 8,
       "num_key_value_heads": 2, "vocab_size": 2048}


def _run(monkeypatch, workload, seed, seconds, control=0, sizes=None):
    monkeypatch.setenv("ONCHIP_REHEARSAL", "1")
    cell = manifest.load_cell(manifest.load_manifest(), workload)
    if sizes:
        cell["config"]["rehearsal"].update(sizes)
        cell["config"]["rehearsal"]["check"]["sample_requests"] = 8
    gen = manifest.load_by_name("harness/generators", cell["traffic"]["generator"])
    runner = manifest.load_by_name("harness", gen.KIND + "_runner")
    return runner.run(cell, _args(seed, seconds, control), time.perf_counter())


def _numbers(result):
    return {r["number"]: r for r in result["rehearsal"]["compared"]}


def test_training_cell_sound_run_is_correct_and_reports_no_device_metric(monkeypatch):
    res = _run(monkeypatch, "mistral-7b.train-8k", seed=5, seconds=1.5)
    assert res["correct"] is True, res
    assert res["metrics"] == {} and res["device"]["platform"] == "cpu"
    assert res["attempted"] >= 5 and res["failed"] == 0
    n = _numbers(res)
    assert n["programs_lowered_in_window"]["value"] == 0
    assert n["loss_gap.step1"]["value"] < 1e-5  # same seeded weights on both sides


def test_training_control_int8_is_not_correct(monkeypatch):
    res = _run(monkeypatch, "mistral-7b.train-8k", seed=11, seconds=1.5, control=1)
    assert res["correct"] is False
    assert not _numbers(res)["grad_norm_gap"]["ok"]


def test_a_step_that_returns_its_parameters_unchanged_is_not_correct(monkeypatch):
    import jax
    import jax.numpy as jnp

    from tpu_engine import supervisor

    real_build = supervisor.build_train_program

    def broken_build(*a, **kw):
        prog = real_build(*a, **kw)
        real_step = prog.step

        def step(state, batch):
            kept = jax.tree.map(jnp.copy, state["params"])
            new_state, metrics = real_step(state, batch)
            return {**new_state, "params": kept}, metrics

        object.__setattr__(prog, "step", step)
        return prog

    monkeypatch.setattr(supervisor, "build_train_program", broken_build)
    res = _run(monkeypatch, "mistral-7b.train-8k", seed=5, seconds=1.0)
    assert res["correct"] is False
    n = _numbers(res)
    assert n["dparam_norm_gap"]["value"] > 0.9 and not n["dparam_norm_gap"]["ok"]


def test_serving_cell_sound_run_is_correct(monkeypatch):
    res = _run(monkeypatch, "mistral-7b.serve-chat", seed=5, seconds=4.0)
    assert res["correct"] is True, res
    assert res["metrics"] == {} and res["failed"] == 0 and res["attempted"] >= 8
    assert _numbers(res)["served_logit_gap_max"]["tokens_compared"] >= 20
    # every number compared sits beside its limit under the line's last key
    assert list(res)[-1] == "compared" and set(res["compared"]) == set(_numbers(res))
    assert res["compared"]["served_logit_gap_mean"] == {"value": _numbers(res)["served_logit_gap_mean"]["value"],
                                                        "limit": 0.001}


def test_moe_closed_loop_cell_runs_and_every_slot_is_used(monkeypatch):
    res = _run(monkeypatch, "mixtral-8x7b.serve-batch", seed=7, seconds=3.0)
    assert res["correct"] is True, res
    assert res["attempted"] >= 8 and res["failed"] == 0


@pytest.mark.parametrize("workload,seed,seconds,layers", [
    ("mistral-7b.serve-chat", 5, 4.0, 4), ("mixtral-8x7b.serve-batch", 23, 3.0, 1)])
def test_serving_control_int8_weights_is_not_correct(monkeypatch, workload, seed, seconds, layers):
    sizes = {**MID, "num_hidden_layers": layers}
    sound = _run(monkeypatch, workload, seed, seconds, sizes=sizes)
    assert sound["correct"] is True, sound
    low = _run(monkeypatch, workload, seed, seconds, control=1, sizes=sizes)
    assert low["correct"] is False
    n = _numbers(low)
    assert not (n["served_logit_gap_mean"]["ok"] and n["served_logit_gap_max"]["ok"])
    assert n["requests_failed"]["ok"] and n["requests_short_of_their_tokens"]["ok"]


@pytest.mark.parametrize("fault", ["token_altered", "request_cut_short"])
def test_a_broken_serving_path_is_not_correct(monkeypatch, fault):
    real_install = program.BatcherShim.install

    def install(shim):
        real_install(shim)
        if fault == "token_altered":
            shim.tamper = lambda tok: (tok + 1) % 512  # where tokens are produced
        else:
            from tpu_engine.serving import ContinuousBatcher

            patched = ContinuousBatcher._emit

            def emit(engine, req, slot, tok):
                if len(req.tokens) + 2 == req.max_new_tokens and req.max_new_tokens > 4:
                    req.max_new_tokens -= 1  # the engine stops one token early
                return patched(engine, req, slot, tok)

            ContinuousBatcher._emit = emit

    monkeypatch.setattr(program.BatcherShim, "install", install)
    res = _run(monkeypatch, "mistral-7b.serve-chat", seed=5, seconds=3.0)
    assert res["correct"] is False
    n = _numbers(res)
    bad = "served_logit_gap_max" if fault == "token_altered" else "requests_short_of_their_tokens"
    assert not n[bad]["ok"]
