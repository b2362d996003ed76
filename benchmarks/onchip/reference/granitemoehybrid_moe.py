"""Plain float32 reference for the ``granitemoehybrid`` recipe WITH experts
(granite-4.0-h-small): the Mamba-2 and attention mixers of
``granitemoehybrid.py``, and after either, in place of one dense MLP, a
mixture of experts with a shared expert beside it.

With ``x`` the residual stream, E the published number of experts and k
``num_experts_per_tok``:

- ``h = RMSNorm(x)``; ``r = h W_r`` (E logits, no bias); ``(v, e) = top_k(r)``;
  ``g = softmax(v)`` over the k kept logits;
- ``y = sum_j g_j W_down[e_j] (silu(h W_gate[e_j]) * (h W_up[e_j]))
  + W_sd (silu(h W_sg) * (h W_su))``, the shared expert of its own width;
- ``x <- x + residual_multiplier * y``. No capacity, no dropped token.

THE SHARE. A configuration that is one chip's share of a deployment whose
chips share each layer's experts holds ``num_local_experts`` of the
``published.num_local_experts``, from ``first_local_expert`` on. The router
scores all E and keeps k as published; of the sum over j only the terms whose
expert is held are computed (a token none of whose experts is held gets the
shared expert alone), and that partial result goes on to the next layer: what
the program computes, and what this file computes when given the same share.
Given every expert (``num_local_experts`` = the published count) it is the
uncut published layer.

``margin`` of :func:`served_logits` is, per position, the least gap between
the k-th and the (k+1)-th router logit over the layers: where it is all but
zero, which expert a token gets tenth is decided by the rounding of whatever
precision computes the logits.

Weights are drawn here, from the seed, by the recipe the configuration states
under ``assumed.init`` (which the program follows too): every kernel's layer
i from ``split(key, n)[i]``, expert e of it from ``split(that, E)[e]``, so
that ONE LAYER IS DRAWN AS THE WALK REACHES IT and dropped after it (the
share's ten layers in float32 are 18 GB; one is 1.8 GB). ``init_params``
returns the table, the final norm and the seed. The walk is a Python loop
over the layers, one program per kind; experts are summed one at a time.
It imports nothing of ``tpu_engine``.

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

from . import granitemoehybrid as dense
from .granitemoehybrid import attention_mixer, logits_rows, mamba_mixer, rms_norm
from .mistral import Q_BLOCK

STD = 0.02
N_KEYS = 16
# Index of each drawn leaf's key in the 16-way split of the seed's key (the
# program's ``_init_hybrid_params``); the block after the mixer takes three
# keys from ``MLP_KEYS[kind]`` on: gate, up, down of the experts, the router
# from ``fold_in(gate's, 1)``, the shared expert from ``fold_in(each, 2)``.
MIXER_KEYS = {"attn": {"q": 1, "k": 2, "v": 3, "o": 4},
              "ssm": {"in_proj": 8, "conv": 9, "A_log": 10, "dt_bias": 11, "out_proj": 12}}
MLP_KEYS = {"attn": 5, "ssm": 13}
ONE = np.float32(1.0)  # every norm scale is one


def held(cfg: dict) -> tuple[int, int, int]:
    """(published experts, first held, how many held)."""
    return (cfg["published"]["num_local_experts"], cfg.get("first_local_expert", 0),
            cfg["num_local_experts"])


def _mixers(cfg: dict) -> dict:
    """``cfg`` as the dense reference's mixers read it (they look at no expert key)."""
    return {**cfg, "num_local_experts": 0}


def _dims(cfg: dict) -> dict:
    return dense._dims(_mixers(cfg))


def draw_layer(cfg: dict, seed, kind: str, i) -> dict:
    """Layer ``i`` of its kind's stack (``attn`` | ``ssm``) as float32 leaves,
    in the dense reference's layout; norm scales are ones. ``seed`` and ``i``
    are arguments under ``jit``, never constants."""
    d = _dims(cfg)
    D, F, n = d["D"], d["F"], d["La" if kind == "attn" else "Ls"]
    E, first, n_held = held(cfg)
    S = cfg["shared_intermediate_size"]
    res = STD / math.sqrt(2 * d["L"])
    keys = jax.random.split(jax.random.PRNGKey(seed), N_KEYS)

    def layer_key(key):
        return jax.random.split(key, n)[i]

    def kernel(key, shape, std):
        return {"kernel": jax.random.normal(layer_key(key), shape, jnp.float32) * std}

    def experts(key, shape, std):
        ks = jax.random.split(layer_key(key), E)[first:first + n_held]
        return jax.vmap(lambda k: jax.random.normal(k, shape, jnp.float32))(ks) * std

    mk = MIXER_KEYS[kind]
    if kind == "attn":
        w = {"q": kernel(keys[mk["q"]], (D, d["H"] * d["HD"]), STD),
             "k": kernel(keys[mk["k"]], (D, d["KV"] * d["HD"]), STD),
             "v": kernel(keys[mk["v"]], (D, d["KV"] * d["HD"]), STD),
             "o": kernel(keys[mk["o"]], (d["H"] * d["HD"], D), res)}
    else:
        # The small recurrence leaves are the dense recipe's, drawn for the
        # whole stack (a few thousand numbers) and sliced.
        small = lambda name: lax.dynamic_index_in_dim(  # noqa: E731
            dense.init_leaf(_mixers(cfg), seed, ("layers", "ssm", *name)), i, 0, keepdims=False)
        w = {"in_proj": kernel(keys[mk["in_proj"]], (D, d["I"] + d["C"] + d["SH"]), STD),
             "conv": {"kernel": small(("conv", "kernel")), "bias": jnp.zeros((d["C"],), jnp.float32)},
             "A_log": small(("A_log",)), "dt_bias": small(("dt_bias",)),
             "D": jnp.ones((d["SH"],), jnp.float32),
             "gate_norm": {"scale": jnp.ones((d["I"],), jnp.float32)},
             "out_proj": kernel(keys[mk["out_proj"]], (d["I"], D), res)}
    g, u, dn = keys[MLP_KEYS[kind]], keys[MLP_KEYS[kind] + 1], keys[MLP_KEYS[kind] + 2]
    w.update(
        router=kernel(jax.random.fold_in(g, 1), (D, E), STD)["kernel"],
        gate=experts(g, (D, F), STD), up=experts(u, (D, F), STD), down=experts(dn, (F, D), res),
        shared_gate=kernel(jax.random.fold_in(g, 2), (D, S), STD)["kernel"],
        shared_up=kernel(jax.random.fold_in(u, 2), (D, S), STD)["kernel"],
        shared_down=kernel(jax.random.fold_in(dn, 2), (S, D), res)["kernel"])
    return w


def init_params(cfg: dict, seed: int) -> dict:
    """What is held for the whole walk: the tied table (drawn
    ``embedding_multiplier`` times smaller than 0.02), the final norm, and the
    seed the layers are drawn from as the walk reaches them."""
    d = _dims(cfg)

    @jax.jit
    def table(sd):
        k0 = jax.random.split(jax.random.PRNGKey(sd), N_KEYS)[0]
        return jax.random.normal(k0, (d["V"], d["D"]), jnp.float32) * (STD / cfg["embedding_multiplier"])

    return {"embed": {"embedding": table(jnp.uint32(seed))},
            "final_norm": {"scale": jnp.ones((d["D"],), jnp.float32)}, "seed": jnp.uint32(seed)}


# ----------------------------------------------------------------------------
# Forward
# ----------------------------------------------------------------------------


def swiglu(h, gate, up, down):
    return (jax.nn.silu(h @ gate) * (h @ up)) @ down


def route(h, router, k: int):
    """h [S, D] -> (experts [S, k], gates [S, k] summing to 1, margin [S])."""
    logits = jnp.einsum("sd,de->se", h, router, preferred_element_type=jnp.float32)
    if k < logits.shape[-1]:
        ranked, idx = lax.top_k(logits, k + 1)
        margin = ranked[:, k - 1] - ranked[:, k]
    else:
        ranked, idx = lax.top_k(logits, k)
        margin = jnp.full(logits.shape[:1], jnp.inf, jnp.float32)
    return idx[:, :k], jax.nn.softmax(ranked[:, :k], axis=-1), margin


def routed_part(h, w, cfg):
    """The held experts' terms of the mixture: (sum [S, D], margin [S]).
    ``w["gate"]`` / ``["up"]`` / ``["down"]`` hold the experts ``first ..
    first + n - 1`` of :func:`held`; one expert at a time."""
    _, first, n = held(cfg)
    idx, gates, margin = route(h, w["router"], cfg["num_experts_per_tok"])

    def add(y, xs):
        e, g, u, dn = xs
        mine = jnp.sum(jnp.where(idx == e, gates, 0.0), axis=-1)  # this expert's gate, 0 where not chosen
        return y + mine[:, None] * swiglu(h, g, u, dn), None

    y, _ = lax.scan(add, jnp.zeros_like(h), (first + jnp.arange(n), w["gate"], w["up"], w["down"]))
    return y, margin


def shared_part(h, w):
    return swiglu(h, w["shared_gate"], w["shared_up"], w["shared_down"])


def mixture(h, w, cfg):
    """h [S, D] (normed) -> (the block's output before the residual multiplier, margin)."""
    y, margin = routed_part(h, w, cfg)
    return y + shared_part(h, w), margin


def layer(x, w, kind: str, cfg: dict):
    """One layer on x [S, D]: (x, margin [S])."""
    eps, r = cfg["rms_norm_eps"], cfg["residual_multiplier"]
    u = rms_norm(x, ONE, eps)
    mixed = attention_mixer(u, w, _mixers(cfg)) if kind == "attn" else mamba_mixer(u, w, _mixers(cfg))
    x = x + r * mixed
    y, margin = mixture(rms_norm(x, ONE, eps), w, cfg)
    return x + r * y, margin


@partial(jax.jit, static_argnames=("kind", "cfg_key"))
def _layer(x, seed, i, kind, cfg_key):
    """The layer with its weights drawn here and dropped on return."""
    cfg = _thaw(cfg_key)
    return layer(x, draw_layer(cfg, seed, kind, i), kind, cfg)


def hidden_states(params, tokens, cfg):
    """tokens [S] -> (final hidden [S, D] before the final norm, margin [S]:
    the least over the layers)."""
    key = _freeze(cfg)
    x = cfg["embedding_multiplier"] * params["embed"]["embedding"][jnp.asarray(tokens, jnp.int32)]
    margin = jnp.full((len(tokens),), jnp.inf, jnp.float32)
    seen = {"attn": 0, "ssm": 0}
    for t in cfg["layer_types"]:
        kind = "ssm" if t == "mamba" else "attn"
        x, m = _layer(x, params["seed"], jnp.int32(seen[kind]), kind, key)
        margin = jnp.minimum(margin, m)
        seen[kind] += 1
    return x, margin


def forward_logits(params, tokens, cfg):
    """tokens [S] -> (logits [S, V], margin [S]): the whole forward pass, for the tests."""
    with jax.default_matmul_precision("highest"):
        hid, margin = hidden_states(params, tokens, cfg)
        return logits_rows(params, hid, _mixers(cfg)), margin


def _freeze(cfg: dict) -> str:
    """The keys the forward pass reads, as a string (a static argument of jit)."""
    keep = ("hidden_size", "intermediate_size", "shared_intermediate_size", "vocab_size",
            "num_attention_heads", "num_key_value_heads", "num_hidden_layers", "layer_types",
            "num_local_experts", "num_experts_per_tok", "mamba_n_heads", "mamba_d_head", "mamba_d_state",
            "mamba_d_conv", "mamba_expand", "mamba_n_groups", "rms_norm_eps", "embedding_multiplier",
            "residual_multiplier", "attention_multiplier", "logits_scaling")
    return json.dumps({**{k: cfg[k] for k in keep}, "first_local_expert": cfg.get("first_local_expert", 0),
                       "published": {"num_local_experts": cfg["published"]["num_local_experts"]}},
                      sort_keys=True)


_thaw = json.loads


@partial(jax.jit, static_argnames=("rows", "cfg_key"))
def _served_rows(params, hidden, n_prompt, rows, cfg_key):
    return logits_rows(params, lax.dynamic_slice_in_dim(hidden, n_prompt - 1, rows, 0), _thaw(cfg_key))


def served_logits(params, prompt, served, cfg, length=None, rows=None):
    """(logits [n_served, V], margin [n_served]) at the positions that produced
    ``served`` when the model is fed ``prompt + served`` once, whole: the
    runners' interface (``mistral.served_logits``). Padded on the right to
    ``length`` (causal, and the recurrence runs forward: padding never reaches
    a served row)."""
    toks = np.asarray(list(prompt) + list(served), np.int32)
    rows = rows or -(-len(served) // 128) * 128
    length = max(length or 0, -(-(len(prompt) - 1 + rows) // Q_BLOCK) * Q_BLOCK)
    toks = np.pad(toks, (0, length - len(toks)))
    with jax.default_matmul_precision("highest"):
        hid, margin = hidden_states(params, toks, cfg)
        tables = {k: params[k] for k in ("embed", "final_norm")}
        lg = _served_rows(tables, hid, jnp.int32(len(prompt)), rows, _freeze(cfg))
    at = len(prompt) - 1
    return lg[:len(served)], margin[at:at + len(served)]
