"""Operations and bytes a hybrid (Mamba-2 + attention) stack needs, from
shapes alone: what the readers of the ``ssm_*`` rooflines and
``decode_step_hbm_roofline`` divide by a peak. ``harness/counts.py`` counts
Llama layers; a stack with two kinds of layer, a tied head and a recurrent
state is counted here.

``cfg`` is a configuration file's dict (Hugging Face keys:
``mamba_n_heads`` H, ``mamba_d_head`` P, ``mamba_d_state`` N, ``mamba_d_conv``
K, one group). The SSM state is float32 and every other tensor the serving
dtype (``itemsize``), as the configuration's ``assumed.state_dtypes`` says.
"""

from __future__ import annotations

import statistics

STATE_ITEMSIZE = 4


def knows(cfg: dict) -> bool:
    """Mamba-2 beside attention layers with one dense MLP after every mixer (a
    mixture after the mixers is ``counts_hybrid_moe``'s)."""
    return "mamba_n_heads" in cfg and not cfg.get("num_local_experts")


def _dims(cfg: dict):
    D, F, V = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    H, P, N, K = cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_d_state"], cfg["mamba_d_conv"]
    kinds = cfg["layer_types"]
    return D, F, V, H, P, N, K, sum(k == "mamba" for k in kinds), sum(k == "attention" for k in kinds)


def ssm_state_bytes(cfg: dict, slots: int) -> float:
    """One Mamba-2 layer's SSM state of ``slots`` rows."""
    _, _, _, H, P, N, _, _, _ = _dims(cfg)
    return float(slots * H * P * N * STATE_ITEMSIZE)


def ssm_update_bytes(cfg: dict, slots: int, itemsize: int = 2) -> float:
    """Bytes one layer's decode update must move for the ``slots`` rows the
    program computes (all of the pool: static shapes): the state read and
    written, its inputs x, B, C (serving dtype) and dt (float32), and y out
    (float32, as the gate takes it)."""
    _, _, _, H, P, N, _, _, _ = _dims(cfg)
    inputs = slots * ((H * P + 2 * N) * itemsize + H * 4)
    return 2.0 * ssm_state_bytes(cfg, slots) + inputs + slots * H * P * 4


def ssd_chunk_flops(cfg: dict, tokens: int) -> float:
    """One layer's chunked scan over ``tokens`` positions of one row: the
    causal pairs of C B^T and of (L o C B^T)(dt x), the entering state's
    share of y, and the state leaving. 2 FLOPs a multiply-add."""
    _, _, _, H, P, N, _, _, _ = _dims(cfg)
    pairs = tokens * (tokens + 1) // 2
    return 2.0 * pairs * N + 2.0 * pairs * H * P + 4.0 * tokens * H * P * N


def ssd_chunk_bytes(cfg: dict, tokens: int, itemsize: int = 2) -> float:
    """What that scan cannot avoid moving: x, B, C in (serving dtype), dt in
    and y out (float32), the row's state in and out."""
    _, _, _, H, P, N, _, _, _ = _dims(cfg)
    return tokens * ((H * P + 2 * N) * itemsize + H * 4 + H * P * 4) + 2.0 * ssm_state_bytes(cfg, 1)


def weight_bytes_per_decode_step(cfg: dict, itemsize: int = 2) -> float:
    """Every weight once in the serving dtype: the attention layers'
    projections, the Mamba-2 layers' (in_proj over z | x | B | C | dt, the
    convolution, out_proj), an MLP after each, and the tied head (the
    embedding table, read whole as the head; the lookup reads B rows of it)."""
    D, F, V, H, P, N, K, Ls, La = _dims(cfg)
    AH, KV = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    HD = cfg.get("head_dim") or D // AH
    inner, conv = H * P, H * P + 2 * N
    attn = D * AH * HD + 2 * D * KV * HD + AH * HD * D
    ssm = D * (inner + conv + H) + (K + 1) * conv + inner * D
    return float(itemsize * (La * attn + Ls * ssm + (La + Ls) * 3 * D * F + D * V))


def kv_bytes_per_decode_step(cfg: dict, context_tokens: float, itemsize: int = 2) -> float:
    """Keys and values of the ATTENTION layers at the live slots' real lengths
    (``context_tokens``: their sum)."""
    D, _, _, _, _, _, _, _, La = _dims(cfg)
    AH, KV = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    HD = cfg.get("head_dim") or D // AH
    return 2.0 * La * KV * HD * itemsize * context_tokens


def recurrent_bytes_per_decode_step(cfg: dict, slots: int, itemsize: int = 2) -> float:
    """The recurrent state of every Mamba-2 layer in and out, SSM and
    convolution, for the ``slots`` rows computed."""
    _, _, _, H, P, N, K, Ls, _ = _dims(cfg)
    conv = slots * (K - 1) * (H * P + 2 * N) * itemsize
    return 2.0 * Ls * (ssm_state_bytes(cfg, slots) + conv)


def decode_chunk_runs(trace: dict) -> list:
    """Device seconds of each traced run of the decode program
    (``jit_decode_chunk``), from ``trace_reduce``'s ``module_runs``; empty
    where the program has no such name."""
    return next((v for k, v in trace["module_runs"].items() if "decode_chunk" in k), [])


def decode_chunk_step_s(run: dict) -> float | None:
    """Traced device seconds of one decode step: the median run of
    ``jit_decode_chunk`` over the chunk's steps."""
    runs = decode_chunk_runs(run["trace"])
    return statistics.median(runs) / run["decode_chunk_steps"] if runs else None


def decode_step(run: dict) -> tuple[float, float] | None:
    """(bytes one decode step must move, traced seconds of one step) of a
    traced serving run, for ``decode_step_hbm_roofline``: the weights of both
    kinds of layer once in the serving dtype, the tied head, keys and values of
    the attention layers at the slots' real lengths, and the recurrent state of
    every Mamba-2 layer in and out for every slot (the program computes all of
    the pool)."""
    cfg, step_s = run["cell"]["config"], decode_chunk_step_s(run)
    if not step_s or not run.get("dispatch_context"):
        return None
    need = (weight_bytes_per_decode_step(cfg)
            + kv_bytes_per_decode_step(cfg, statistics.fmean(run["dispatch_context"]))
            + recurrent_bytes_per_decode_step(cfg, run["slots"]))
    return need, step_s
