"""Operations and bytes the algorithm needs, from shapes alone.

Taken over from ``tpu_engine/models/transformer.py::train_flops_per_token``
(6·N for the matmuls plus an attention term), which charges ``min(S, W)`` keys
to every query as if nothing were causal. Here the keys are counted exactly:
query i (0-based) of a causal layer with window W sees ``min(i + 1, W)`` keys,
so the mean over a sequence of S is below both S and W. Recomputation (remat)
is never counted. ``cfg`` is a configuration file's dict (Hugging Face keys).
"""

from __future__ import annotations

import statistics


def knows(cfg: dict) -> bool:
    """The Llama recipe (Mistral's, Mixtral's): every layer the same grouped-query
    attention before a dense or routed SwiGLU. A file that states a pattern of
    layers (``layer_types``, ``mixer_types``) or a latent (``kv_lora_rank``) is
    another module's (``counts_for``: exactly one module knows a configuration)."""
    return all(k in cfg for k in ("hidden_size", "intermediate_size", "vocab_size", "num_attention_heads",
                                  "num_key_value_heads", "num_hidden_layers")) \
        and not any(k in cfg for k in ("layer_types", "mixer_types", "kv_lora_rank"))


def visible_keys_total(seq: int, window: int) -> int:
    """Sum over queries 0..seq-1 of the keys each sees (causal, window)."""
    if not window or window >= seq:
        return seq * (seq + 1) // 2
    return window * (window + 1) // 2 + (seq - window) * window


def mean_visible_keys(seq: int, window: int) -> float:
    return visible_keys_total(seq, window) / seq


def _dims(cfg):
    D, F, V = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    H, KV = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    HD = cfg.get("head_dim") or D // H
    return D, F, V, H, KV, HD, cfg["num_hidden_layers"]


def matmul_params_per_token(cfg: dict) -> int:
    """Weights a token is multiplied by: attention projections, the experts it
    is routed to (all of a dense MLP), the router, the head. The embedding
    lookup is not a matmul."""
    D, F, V, H, KV, HD, L = _dims(cfg)
    E = cfg.get("num_local_experts") or 0
    mlp = 3 * D * F * (cfg["num_experts_per_tok"] if E else 1)
    return L * (D * H * HD + 2 * D * KV * HD + H * HD * D + mlp + D * E) + D * V


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward + backward: 6 per matmul weight, and for attention 2 matmuls
    (scores, values) x 2 FLOPs x 3 (forward, two backward) per visible key per
    head dimension."""
    D, F, V, H, KV, HD, L = _dims(cfg)
    attn = 12.0 * L * H * HD * mean_visible_keys(seq, cfg.get("sliding_window") or 0)
    return 6.0 * matmul_params_per_token(cfg) + attn


def flash_train_flops(seq: int, window: int, heads: int, head_dim: int, batch: int = 1) -> float:
    """Forward (2 matmuls) plus backward (4: dV, dP, dQ, dK, with the scores
    recomputed inside the kernel counted as needed work: 5) per visible pair.
    The remat forward that the train step runs again is NOT counted."""
    return (2 + 5) * 2.0 * batch * heads * head_dim * visible_keys_total(seq, window)


def flash_train_bytes(seq: int, heads: int, kv_heads: int, head_dim: int, batch: int = 1,
                      itemsize: int = 2) -> float:
    """HBM traffic the kernels cannot avoid: forward reads q,k,v and writes o;
    backward reads q,k,v,o,do and writes dq,dk,dv."""
    q = batch * seq * heads * head_dim * itemsize
    kv = batch * seq * kv_heads * head_dim * itemsize
    return (2 * q + 2 * kv) + (4 * q + 4 * kv)


def weight_bytes_per_decode_step(cfg: dict, experts_hit_per_layer: float | None = None,
                                 itemsize: int = 2) -> float:
    """Bytes of weights one decode step must read once, in the serving dtype:
    attention projections, the experts actually routed to (``experts_hit``,
    at most all of them; a dense MLP counts as one), router, final head."""
    D, F, V, H, KV, HD, L = _dims(cfg)
    E = cfg.get("num_local_experts") or 0
    hit = 1.0 if not E else min(float(E), experts_hit_per_layer if experts_hit_per_layer is not None else float(E))
    per_layer = D * H * HD + 2 * D * KV * HD + H * HD * D + 3 * D * F * hit + D * E
    return itemsize * (L * per_layer + D * V)


def kv_bytes_per_decode_step(cfg: dict, context_lengths, itemsize: int = 2) -> float:
    """Keys and values of every live slot at its real length (window-capped)."""
    D, F, V, H, KV, HD, L = _dims(cfg)
    W = cfg.get("sliding_window") or 0
    toks = sum(min(c, W) if W else c for c in context_lengths)
    return 2.0 * L * KV * HD * itemsize * toks


def expected_experts_hit(n_experts: int, top_k: int, tokens: int) -> float:
    """Expected number of distinct experts touched when ``tokens`` tokens each
    pick ``top_k`` distinct experts uniformly (seeded random weights route
    near-uniformly)."""
    if not n_experts:
        return 1.0
    return n_experts * (1.0 - (1.0 - top_k / n_experts) ** tokens)


def decode_step(run: dict) -> tuple[float, float] | None:
    """(bytes one decode step must read, traced seconds of one step) of a
    traced serving run, for ``decode_step_hbm_roofline``: every weight once in
    the serving dtype (for experts, those the live slots are expected to route
    to), keys and values at the slots' real lengths; the step is the median run
    of the program that takes most device time in the trace, which in a serving
    cell is the decode chunk (the trace calls it ``jit__unknown``: it is jitted
    from a partial), over the chunk's steps. Contexts stay under the window in
    today's cells, so the sum of contexts stands for the slots' lengths."""
    tr = run["trace"]
    if not tr["module_runs"] or not run.get("occupancy"):
        return None
    decode = max(tr["module_runs"].values(), key=sum)
    step_s = statistics.median(decode) / run["decode_chunk_steps"]
    cfg = run["cell"]["config"]
    live = statistics.fmean(run["occupancy"])
    hit = expected_experts_hit(cfg.get("num_local_experts") or 0, cfg.get("num_experts_per_tok") or 0, live)
    ctx = statistics.fmean(run["dispatch_context"])
    return weight_bytes_per_decode_step(cfg, hit) + kv_bytes_per_decode_step(cfg, [ctx]), step_s
