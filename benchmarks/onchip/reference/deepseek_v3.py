"""Plain float32 reference for the DeepSeek-V3 recipe as Kimi-VL-A3B-Instruct's
language decoder publishes it (``text_config``: ``model_type: deepseek_v3``):
latent attention (MLA) in its EXPANDED form only, a sigmoid router with a
selection bias, routed and shared SwiGLU experts, leading dense layers, an
untied head. It follows the published modelling code (transformers 4.57.6,
``models/deepseek_v3/modeling_deepseek_v3.py``: ``DeepseekV3Attention``,
``DeepseekV3TopkRouter``, ``DeepseekV3MoE``), and a tier-1 test holds its
logits to ``DeepseekV3ForCausalLM``'s on the same weights.

With ``x`` the residual stream, ``h = RMSNorm(x)``, H heads:

- MLA (``q_lora_rank`` null): ``q = h W_q`` -> per head ``(q_n [nope], q_r
  [rope])``; ``(c_raw [latent], k_raw [rope]) = h W_kva``; ``c =
  RMSNorm(c_raw)`` (epsilon 1e-6, the published class's default); ``q_r <-
  RoPE(q_r)``, ``k_r = RoPE(k_raw)``, one ``k_r`` for all heads; ``(k_n [nope], v [v_head])_head = c W_kvb``; ``s = (q_n . k_n
  + q_r . k_r) / sqrt(nope + rope)``, causal softmax, ``o = sum p v``, ``x <- x
  + concat(o) W_o``. No cache, no absorption: every position's keys and values
  are expanded and attended.
- Layers ``0 .. first_k_dense_replace - 1``: ``x <- x + SwiGLU(h)`` of width
  ``intermediate_size``.
- The rest: ``sigma = sigmoid(h W_r)`` (float32); the ``num_experts_per_tok``
  experts ``e`` are ``top_k(sigma + b)`` with ``b`` the per-expert selection
  bias (``noaux_tc``; one group, so group limiting is the identity); gates
  ``g_k = routed_scaling_factor * sigma[e_k] / sum_j sigma[e_j]`` (``b`` is in
  the choice and NOT in the gates); ``y = sum_k g_k SwiGLU_{e_k}(h) +
  SwiGLU_shared(h)`` (widths ``moe_intermediate_size`` and ``n_shared_experts``
  times that); ``x <- x + y``.
- Final RMSNorm, untied head.

DEPARTURES from the published model, each stated in the configuration file:
the vision tower and projector are left out (the decoder is fed token ids);
the rotary pairing is half-split (first half, second half: ``mistral.rope``)
where the published code pairs neighbours (``rope_interleave``) — the same
function of ``W_q``'s and ``W_kva``'s rotary columns permuted, and the weights
are seeded; and THE SHARE: a file that is one chip's share of a deployment
whose chips share each layer's experts holds ``n_routed_experts`` of the
``published.n_routed_experts``, from ``first_local_expert`` on. The router
scores all the published experts and keeps k as published; of the sum over k
only the terms whose expert is held are computed (a token none of whose experts
is held gets the shared experts alone), and that partial result goes on to the
next layer: what the program computes, and what this file computes when given
the same share. Given every expert it is the uncut published layer.

``margin`` of :func:`served_logits` is, per position, the least gap between
the k-th and the (k+1)-th of ``sigma + b`` over the expert layers.

Weights are drawn here, from the seed, by the recipe the configuration states
under ``assumed.init`` (which the program follows too): every kernel's layer i
from ``split(key, n)[i]``, expert e of it from ``split(that, E)[e]``, so that
one layer is drawn as the walk reaches it and dropped after it, and the share
16-31 would hold exactly the uncut model's experts 16-31. The bias ``b`` is
drawn normal(``router_bias_std``): NOT zero, so that a bias left out, or put in
the gates, shows in every comparison. It imports nothing of ``tpu_engine``.

``cfg`` everywhere is the benchmark's configuration file as a dict (Hugging
Face key names).
"""

from __future__ import annotations

import json
import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .mistral import Q_BLOCK, rms_norm, rope

STD = 0.02
N_KEYS = 16          # the program's split of the seed's key: [0] the table, fold_in([0], 1) the head
STACK_KEYS = 7       # split(fold_in(key, 103 | 104), 7): q, kv_a, kv_b, o, gate, up, down
STACK_FOLD = {"mla": 103, "mla_dense": 104}
ONE = np.float32(1.0)  # every norm scale is one
# The published code builds the latent's norm as ``DeepseekV3RMSNorm(kv_lora_rank)``:
# the class's default epsilon, not the configuration's ``rms_norm_eps``.
LATENT_NORM_EPS = 1e-6


def held(cfg: dict) -> tuple[int, int, int]:
    """(published experts, first held, how many held)."""
    return (cfg["published"]["n_routed_experts"], cfg.get("first_local_expert", 0),
            cfg["n_routed_experts"])


def n_dense(cfg: dict) -> int:
    return min(cfg["first_k_dense_replace"], cfg["num_hidden_layers"])


def kinds(cfg: dict) -> list[tuple[str, int]]:
    """(kind, index within its kind's stack) of every layer, in order."""
    k = n_dense(cfg)
    return [("mla_dense", i) for i in range(k)] + [("mla", i) for i in range(cfg["num_hidden_layers"] - k)]


def draw_layer(cfg: dict, seed, kind: str, i) -> dict:
    """Layer ``i`` of its kind's stack as float32 leaves; norm scales are
    ones. ``seed`` and ``i`` are arguments under ``jit``, never constants."""
    D, H = cfg["hidden_size"], cfg["num_attention_heads"]
    C, N, R, V = cfg["kv_lora_rank"], cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    n = n_dense(cfg) if kind == "mla_dense" else cfg["num_hidden_layers"] - n_dense(cfg)
    res = STD / math.sqrt(2 * cfg["published"]["num_hidden_layers"])
    ks = jax.random.split(jax.random.fold_in(jax.random.PRNGKey(seed), STACK_FOLD[kind]), STACK_KEYS)

    def layer_key(key):
        return jax.random.split(key, n)[i]

    def kernel(key, shape, std):
        return jax.random.normal(layer_key(key), shape, jnp.float32) * std

    w = {"q": kernel(ks[0], (D, H * (N + R)), STD), "kv_a": kernel(ks[1], (D, C + R), STD),
         "kv_b": kernel(ks[2], (C, H * (N + V)), STD), "o": kernel(ks[3], (H * V, D), res)}
    g, u, dn = ks[4], ks[5], ks[6]
    if kind == "mla_dense":
        F = cfg["intermediate_size"]
        w.update(gate=kernel(g, (D, F), STD), up=kernel(u, (D, F), STD), down=kernel(dn, (F, D), res))
        return w
    E, first, n_held = held(cfg)
    F, S = cfg["moe_intermediate_size"], cfg["moe_intermediate_size"] * cfg["n_shared_experts"]

    def experts(key, shape, std):
        eks = jax.random.split(layer_key(key), E)[first:first + n_held]
        return jax.vmap(lambda k: jax.random.normal(k, shape, jnp.float32))(eks) * std

    w.update(
        router=kernel(jax.random.fold_in(g, 1), (D, E), STD),
        router_bias=kernel(jax.random.fold_in(g, 3), (E,), cfg["router_bias_std"]),
        gate=experts(g, (D, F), STD), up=experts(u, (D, F), STD), down=experts(dn, (F, D), res),
        shared_gate=kernel(jax.random.fold_in(g, 2), (D, S), STD),
        shared_up=kernel(jax.random.fold_in(u, 2), (D, S), STD),
        shared_down=kernel(jax.random.fold_in(dn, 2), (S, D), res))
    return w


def init_params(cfg: dict, seed: int) -> dict:
    """What is held for the whole walk: the table, the untied head, the final
    norm, and the seed the layers are drawn from as the walk reaches them."""
    V, D = cfg["vocab_size"], cfg["hidden_size"]

    @jax.jit
    def tables(sd):
        k0 = jax.random.split(jax.random.PRNGKey(sd), N_KEYS)[0]
        return (jax.random.normal(k0, (V, D), jnp.float32) * STD,
                jax.random.normal(jax.random.fold_in(k0, 1), (D, V), jnp.float32) * STD)

    table, head = tables(jnp.uint32(seed))
    return {"embed": {"embedding": table}, "lm_head": {"kernel": head},
            "final_norm": {"scale": jnp.ones((D,), jnp.float32)}, "seed": jnp.uint32(seed)}


# ----------------------------------------------------------------------------
# Forward
# ----------------------------------------------------------------------------


def swiglu(h, gate, up, down):
    return (jax.nn.silu(h @ gate) * (h @ up)) @ down


def attention(q, k, v):
    """q, k [S, H, qk], v [S, H, vd] -> [S, H * vd]: causal softmax attention,
    a head and a block of query rows at a time."""
    S, H, qk = q.shape
    qb = min(Q_BLOCK, S)
    assert S % qb == 0, (S, qb)
    j = jnp.arange(S)[None, :]

    def head(args):
        q_h, k_h, v_h = args                                      # [S, qk], [S, qk], [S, vd]

        def block(xs):
            q_blk, start = xs
            s = jnp.einsum("qd,kd->qk", q_blk, k_h, preferred_element_type=jnp.float32) / math.sqrt(qk)
            s = jnp.where(j <= (start + jnp.arange(qb))[:, None], s, -1e30)
            return jax.nn.softmax(s, axis=-1) @ v_h

        return lax.map(block, (q_h.reshape(S // qb, qb, qk), jnp.arange(S // qb) * qb)).reshape(S, -1)

    out = lax.map(head, (q.transpose(1, 0, 2), k.transpose(1, 0, 2), v.transpose(1, 0, 2)))  # [H, S, vd]
    return out.transpose(1, 0, 2).reshape(S, -1)


def mla_mixer(h, w, cfg):
    """h [S, D] (normed) -> [S, D]: latent attention, expanded."""
    S = h.shape[0]
    H, C = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    N, R, V = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    theta = cfg["rope_theta"]
    q = (h @ w["q"]).reshape(S, H, N + R)
    q = jnp.concatenate([q[..., :N], rope(q[..., N:], theta)], axis=-1)
    kva = h @ w["kv_a"]
    c = rms_norm(kva[:, :C], ONE, LATENT_NORM_EPS)
    k_r = rope(kva[:, None, C:], theta)                           # [S, 1, R]: one for all heads
    kv = (c @ w["kv_b"]).reshape(S, H, N + V)
    k = jnp.concatenate([kv[..., :N], jnp.broadcast_to(k_r, (S, H, R))], axis=-1)
    return attention(q, k, kv[..., N:]) @ w["o"]


def route(h, w, cfg):
    """h [S, D] -> (experts [S, k], gates [S, k], margin [S]): the published
    ``DeepseekV3TopkRouter`` with one group."""
    k = cfg["num_experts_per_tok"]
    scores = jax.nn.sigmoid(jnp.einsum("sd,de->se", h, w["router"], preferred_element_type=jnp.float32))
    for_choice = scores + w["router_bias"]                        # the bias is in the choice ...
    if k < scores.shape[-1]:
        ranked, idx = lax.top_k(for_choice, k + 1)
        margin = ranked[:, k - 1] - ranked[:, k]
    else:
        _, idx = lax.top_k(for_choice, k)
        margin = jnp.full(scores.shape[:1], jnp.inf, jnp.float32)
    idx = idx[:, :k]
    gates = jnp.take_along_axis(scores, idx, axis=-1)             # ... and not in the gates
    gates = gates / (jnp.sum(gates, axis=-1, keepdims=True) + 1e-20) * cfg["routed_scaling_factor"]
    return idx, gates, margin


def routed_part(h, w, cfg):
    """The held experts' terms of the mixture: (sum [S, D], margin [S]).
    ``w["gate"]`` / ``["up"]`` / ``["down"]`` hold the experts ``first ..
    first + n - 1`` of :func:`held`; one expert at a time."""
    _, first, n = held(cfg)
    idx, gates, margin = route(h, w, cfg)

    def add(y, xs):
        e, g, u, dn = xs
        mine = jnp.sum(jnp.where(idx == e, gates, 0.0), axis=-1)  # this expert's gate, 0 where not chosen
        return y + mine[:, None] * swiglu(h, g, u, dn), None

    y, _ = lax.scan(add, jnp.zeros_like(h), (first + jnp.arange(n), w["gate"], w["up"], w["down"]))
    return y, margin


def shared_part(h, w):
    return swiglu(h, w["shared_gate"], w["shared_up"], w["shared_down"])


def layer(x, w, kind: str, cfg: dict):
    """One layer on x [S, D]: (x, margin [S])."""
    eps = cfg["rms_norm_eps"]
    x = x + mla_mixer(rms_norm(x, ONE, eps), w, cfg)
    h = rms_norm(x, ONE, eps)
    if kind == "mla_dense":
        return x + swiglu(h, w["gate"], w["up"], w["down"]), jnp.full(x.shape[:1], jnp.inf, jnp.float32)
    y, margin = routed_part(h, w, cfg)
    return x + y + shared_part(h, w), margin


@partial(jax.jit, static_argnames=("kind", "cfg_key"))
def _layer(x, seed, i, kind, cfg_key):
    """The layer with its weights drawn here and dropped on return."""
    cfg = _thaw(cfg_key)
    return layer(x, draw_layer(cfg, seed, kind, i), kind, cfg)


def hidden_states(params, tokens, cfg):
    """tokens [S] -> (final hidden [S, D] before the final norm, margin [S]:
    the least over the layers)."""
    key = _freeze(cfg)
    x = params["embed"]["embedding"][jnp.asarray(tokens, jnp.int32)]
    margin = jnp.full((len(tokens),), jnp.inf, jnp.float32)
    for kind, i in kinds(cfg):
        x, m = _layer(x, params["seed"], jnp.int32(i), kind, key)
        margin = jnp.minimum(margin, m)
    return x, margin


def logits_rows(params, hidden, cfg):
    h = rms_norm(hidden, params["final_norm"]["scale"], cfg["rms_norm_eps"])
    return jnp.einsum("sd,dv->sv", h, params["lm_head"]["kernel"], preferred_element_type=jnp.float32)


def forward_logits(params, tokens, cfg):
    """tokens [S] -> (logits [S, V], margin [S]): the whole forward pass, for the tests."""
    with jax.default_matmul_precision("highest"):
        hid, margin = hidden_states(params, tokens, cfg)
        return logits_rows(params, hid, cfg), margin


def _freeze(cfg: dict) -> str:
    """The keys the forward pass reads, as a string (a static argument of jit)."""
    keep = ("hidden_size", "intermediate_size", "moe_intermediate_size", "vocab_size", "num_attention_heads",
            "num_hidden_layers", "n_routed_experts", "n_shared_experts", "num_experts_per_tok",
            "first_k_dense_replace", "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
            "routed_scaling_factor", "rms_norm_eps", "rope_theta", "router_bias_std")
    return json.dumps({**{k: cfg[k] for k in keep}, "first_local_expert": cfg.get("first_local_expert", 0),
                       "published": {k: cfg["published"][k] for k in ("n_routed_experts", "num_hidden_layers")}},
                      sort_keys=True)


_thaw = json.loads


@partial(jax.jit, static_argnames=("rows", "cfg_key"))
def _served_rows(params, hidden, n_prompt, rows, cfg_key):
    return logits_rows(params, lax.dynamic_slice_in_dim(hidden, n_prompt - 1, rows, 0), _thaw(cfg_key))


def served_logits(params, prompt, served, cfg, length=None, rows=None):
    """(logits [n_served, V], margin [n_served]) at the positions that produced
    ``served`` when the model is fed ``prompt + served`` once, whole: the
    runners' interface (``mistral.served_logits``). Padded on the right to
    ``length`` (causal: padding never reaches a served row)."""
    toks = np.asarray(list(prompt) + list(served), np.int32)
    rows = rows or -(-len(served) // 128) * 128
    length = max(length or 0, -(-(len(prompt) - 1 + rows) // Q_BLOCK) * Q_BLOCK)
    toks = np.pad(toks, (0, length - len(toks)))
    with jax.default_matmul_precision("highest"):
        hid, margin = hidden_states(params, toks, cfg)
        tables = {k: params[k] for k in ("lm_head", "final_norm")}
        lg = _served_rows(tables, hid, jnp.int32(len(prompt)), rows, _freeze(cfg))
    at = len(prompt) - 1
    return lg[:len(served)], margin[at:at + len(served)]
