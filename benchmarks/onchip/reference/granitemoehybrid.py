"""Plain float32 reference for the ``granitemoehybrid`` recipe with no experts
(granite-4.0-h-micro): Mamba-2 layers beside grouped-query attention layers
in one stack, a dense SwiGLU MLP after either, no positional encoding, a tied
head and four scalar multipliers.

With D the hidden size, H heads of P (``mamba_n_heads`` x ``mamba_d_head`` =
``mamba_expand`` x D), one group, state N, K convolution taps:

- ``x0 = embedding_multiplier * E[token]``; each layer ``x += residual_multiplier
  * Mixer(RMSNorm(x))`` then ``x += residual_multiplier * MLP(RMSNorm(x))``,
  ``MLP(h) = (silu(h Wg) * (h Wu)) Wd``; ``logits = RMSNorm(x_L) E^T /
  logits_scaling``.
- attention mixer: no rotation, scores scaled by ``attention_multiplier`` (not
  1/sqrt(head)), causal, full.
- Mamba-2 mixer on ``u_t = RMSNorm(x)_t``: ``[z_t | xBC_t | dt_t] = u_t W_in``;
  ``xBC_t <- silu(sum_{k<K} w[k] * xBC_{t-K+1+k} + b)`` (depthwise, causal, zeros
  before the sequence); split ``x_t`` [H,P], ``B_t`` [N], ``C_t`` [N];
  ``dt_t = softplus(dt_t + dt_bias)``, ``A = -exp(A_log)``; from ``h_0 = 0``:
  ``h_t = exp(dt_t A) h_{t-1} + dt_t x_t (x) B_t``, ``y_t = h_t C_t + D x_t``;
  ``g_t = y_t * silu(z_t)``, ``o_t = g_t / sqrt(mean(g_t^2) + eps) * w_norm``
  over the whole inner width; output ``o_t W_out``.

The recurrence runs TOKEN BY TOKEN (``lax.scan`` over time): no chunked form,
no cache, no batching. Straight ``jax.numpy`` at
``jax.default_matmul_precision("highest")``. It imports nothing of
``tpu_engine`` and takes nothing the program has made: the weights are drawn
here, from the seed, by the recipe the configuration states under
``assumed.init`` (which the program follows too).

Departures from the published description: layers are scanned by runs of like
layers and attention runs in blocks of query rows (``mistral.attention``), both
only to fit; the published checkpoint fuses ``Wg | Wu`` into one
``input_linear`` (the same mathematics).

``cfg`` everywhere is the benchmark's configuration file as a dict (Hugging
Face key names).
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from .mistral import Q_BLOCK, attention, rms_norm

STD = 0.02
N_KEYS = 16


def _dims(cfg: dict) -> dict:
    D, H = cfg["hidden_size"], cfg["num_attention_heads"]
    SH, P, N = cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_d_state"]
    assert SH * P == cfg["mamba_expand"] * D, "mamba_n_heads x mamba_d_head != mamba_expand x hidden_size"
    assert cfg["mamba_n_groups"] == 1 and not cfg.get("num_local_experts")
    kinds = cfg["layer_types"]
    assert len(kinds) == cfg["num_hidden_layers"]
    return dict(D=D, F=cfg["intermediate_size"], V=cfg["vocab_size"], H=H, KV=cfg["num_key_value_heads"],
                HD=D // H, SH=SH, P=P, N=N, K=cfg["mamba_d_conv"], I=SH * P, C=SH * P + 2 * N,
                L=len(kinds), Ls=sum(k == "mamba" for k in kinds), La=sum(k == "attention" for k in kinds))


def runs(cfg: dict) -> list[tuple[str, int, int]]:
    """The pattern as runs of like layers: (kind, first index within the
    kind's stack, count)."""
    out: list[list] = []
    seen = {"attn": 0, "ssm": 0}
    for t in cfg["layer_types"]:
        kind = "ssm" if t == "mamba" else "attn"
        if out and out[-1][0] == kind:
            out[-1][2] += 1
        else:
            out.append([kind, seen[kind], 1])
        seen[kind] += 1
    return [tuple(r) for r in out]


# ----------------------------------------------------------------------------
# Weights from the seed
# ----------------------------------------------------------------------------


def leaf_specs(cfg: dict) -> dict[tuple[str, ...], tuple]:
    """path -> (how it is drawn, index of its key in the 16-way split, shape,
    scale). ``normal``: N(0, scale); ``ones`` / ``zeros``; ``conv``:
    U(+-1/sqrt(taps)); ``a_log``: log U(1, 16); ``dt_bias``: softplus^-1 of a
    step log-uniform in [1e-3, 1e-1]."""
    d = _dims(cfg)
    D, F, V, La, Ls = d["D"], d["F"], d["V"], d["La"], d["Ls"]
    res = STD / math.sqrt(2 * d["L"])
    mlp = lambda n, k: {  # noqa: E731
        ("mlp_norm", "scale"): ("ones", -1, (n, D), 1.0),
        ("gate", "kernel"): ("normal", k, (n, D, F), STD),
        ("up", "kernel"): ("normal", k + 1, (n, D, F), STD),
        ("down", "kernel"): ("normal", k + 2, (n, F, D), res),
    }
    attn = {
        ("attn_norm", "scale"): ("ones", -1, (La, D), 1.0),
        ("q", "kernel"): ("normal", 1, (La, D, d["H"] * d["HD"]), STD),
        ("k", "kernel"): ("normal", 2, (La, D, d["KV"] * d["HD"]), STD),
        ("v", "kernel"): ("normal", 3, (La, D, d["KV"] * d["HD"]), STD),
        ("o", "kernel"): ("normal", 4, (La, d["H"] * d["HD"], D), res),
        **mlp(La, 5),
    }
    ssm = {
        ("ssm_norm", "scale"): ("ones", -1, (Ls, D), 1.0),
        ("in_proj", "kernel"): ("normal", 8, (Ls, D, d["I"] + d["C"] + d["SH"]), STD),
        ("conv", "kernel"): ("conv", 9, (Ls, d["K"], d["C"]), 1.0 / math.sqrt(d["K"])),
        ("conv", "bias"): ("zeros", -1, (Ls, d["C"]), 0.0),
        ("A_log",): ("a_log", 10, (Ls, d["SH"]), 0.0),
        ("dt_bias",): ("dt_bias", 11, (Ls, d["SH"]), 0.0),
        ("D",): ("ones", -1, (Ls, d["SH"]), 1.0),
        ("gate_norm", "scale"): ("ones", -1, (Ls, d["I"]), 1.0),
        ("out_proj", "kernel"): ("normal", 12, (Ls, d["I"], D), res),
        **mlp(Ls, 13),
    }
    # The table is drawn embedding_multiplier times smaller, so that x0 has the
    # 0.02 every other family's has (assumed.init says why).
    specs = {("embed", "embedding"): ("normal", 0, (V, D), STD / cfg["embedding_multiplier"]),
             ("final_norm", "scale"): ("ones", -1, (D,), 1.0)}
    specs.update({("layers", "attn", *p): s for p, s in attn.items()})
    specs.update({("layers", "ssm", *p): s for p, s in ssm.items()})
    return specs


def init_leaf(cfg: dict, seed, path: tuple[str, ...]) -> jax.Array:
    """One float32 leaf. Inside ``jit`` the seed is an argument, never a
    constant (a program that holds its seed compiles anew for every seed)."""
    how, idx, shape, scale = leaf_specs(cfg)[path]
    if how in ("ones", "zeros"):
        return jnp.full(shape, 1.0 if how == "ones" else 0.0, jnp.float32)
    key = jax.random.split(jax.random.PRNGKey(seed), N_KEYS)[idx]
    if how == "normal":
        return jax.random.normal(key, shape, jnp.float32) * scale
    if how == "conv":
        return jax.random.uniform(key, shape, jnp.float32, -scale, scale)
    if how == "a_log":
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))
    dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32, jnp.log(1e-3), jnp.log(1e-1)))
    return dt + jnp.log(-jnp.expm1(-dt))


def init_params(cfg: dict, seed: int) -> dict:
    """The whole tree, one jitted call per leaf."""
    out: dict = {}
    for path in leaf_specs(cfg):
        node = out
        for k in path[:-1]:
            node = node.setdefault(k, {})
        node[path[-1]] = jax.jit(lambda sd, path=path: init_leaf(cfg, sd, path))(jnp.uint32(seed))
    return out


# ----------------------------------------------------------------------------
# Forward
# ----------------------------------------------------------------------------


def mlp(h, lp):
    return (jax.nn.silu(h @ lp["gate"]["kernel"]) * (h @ lp["up"]["kernel"])) @ lp["down"]["kernel"]


def attention_mixer(u, lp, cfg):
    d = _dims(cfg)
    S = u.shape[0]
    q = (u @ lp["q"]["kernel"]).reshape(S, d["H"], d["HD"])
    k = (u @ lp["k"]["kernel"]).reshape(S, d["KV"], d["HD"])
    v = (u @ lp["v"]["kernel"]).reshape(S, d["KV"], d["HD"])
    # ``attention`` divides scores by sqrt(head); the recipe multiplies them by
    # attention_multiplier instead, so q carries the ratio of the two.
    q = q * (cfg["attention_multiplier"] * math.sqrt(d["HD"]))
    return attention(q, k, v, 0) @ lp["o"]["kernel"]


def mamba_mixer(u, lp, cfg, h0=None):
    """u [S, D] -> [S, D]; the recurrence one token at a time from ``h0``
    (zeros). ``h0`` is there for the tests' controls only."""
    d = _dims(cfg)
    S = u.shape[0]
    SH, P, N, K, I, C = d["SH"], d["P"], d["N"], d["K"], d["I"], d["C"]
    zxbcdt = u @ lp["in_proj"]["kernel"]
    z, xbc, dt = zxbcdt[:, :I], zxbcdt[:, I:I + C], zxbcdt[:, I + C:]
    padded = jnp.concatenate([jnp.zeros((K - 1, C), xbc.dtype), xbc], axis=0)
    conv = lp["conv"]["bias"] + sum(padded[k:k + S] * lp["conv"]["kernel"][k] for k in range(K))
    xbc = jax.nn.silu(conv)
    x = xbc[:, :I].reshape(S, SH, P)
    Bm, Cm = xbc[:, I:I + N], xbc[:, I + N:]
    dt = jax.nn.softplus(dt + lp["dt_bias"])                       # [S, SH]
    A = -jnp.exp(lp["A_log"])                                       # [SH]

    def step(h, t):
        x_t, b_t, c_t, dt_t = t
        h = jnp.exp(dt_t * A)[:, None, None] * h + (dt_t[:, None] * x_t)[:, :, None] * b_t[None, None, :]
        return h, jnp.sum(h * c_t[None, None, :], axis=-1)          # [SH, P]

    h0 = jnp.zeros((SH, P, N), jnp.float32) if h0 is None else h0
    _, y = lax.scan(step, h0, (x, Bm, Cm, dt))
    y = y + lp["D"][None, :, None] * x
    g = y.reshape(S, I) * jax.nn.silu(z)
    o = g * lax.rsqrt(jnp.mean(jnp.square(g), -1, keepdims=True) + cfg["rms_norm_eps"])
    return (o * lp["gate_norm"]["scale"]) @ lp["out_proj"]["kernel"]


def hidden_states(params, tokens, cfg):
    """tokens [S] -> final hidden [S, D] (before the final norm)."""
    eps, r = cfg["rms_norm_eps"], cfg["residual_multiplier"]
    x = cfg["embedding_multiplier"] * params["embed"]["embedding"][tokens]

    @jax.checkpoint
    def attn_layer(x, lp):
        x = x + r * attention_mixer(rms_norm(x, lp["attn_norm"]["scale"], eps), lp, cfg)
        return x + r * mlp(rms_norm(x, lp["mlp_norm"]["scale"], eps), lp), None

    @jax.checkpoint
    def ssm_layer(x, lp):
        x = x + r * mamba_mixer(rms_norm(x, lp["ssm_norm"]["scale"], eps), lp, cfg)
        return x + r * mlp(rms_norm(x, lp["mlp_norm"]["scale"], eps), lp), None

    for kind, first, count in runs(cfg):
        stack = jax.tree.map(lambda a: a[first:first + count], params["layers"][kind])
        x, _ = lax.scan(attn_layer if kind == "attn" else ssm_layer, x, stack)
    return x


def logits_rows(params, hidden, cfg):
    h = rms_norm(hidden, params["final_norm"]["scale"], cfg["rms_norm_eps"])
    return jnp.einsum("sd,vd->sv", h, params["embed"]["embedding"],
                      preferred_element_type=jnp.float32) / cfg["logits_scaling"]


def forward_logits(params, tokens, cfg):
    """tokens [S] -> logits [S, V]: the whole forward pass, for the tests."""
    with jax.default_matmul_precision("highest"):
        return logits_rows(params, hidden_states(params, jnp.asarray(tokens, jnp.int32), cfg), cfg)


@partial(jax.jit, static_argnames=("rows", "cfg_key"))
def _served_logits(params, tokens, n_prompt, rows, cfg_key):
    cfg = {k: (list(v) if isinstance(v, tuple) else v) for k, v in cfg_key}
    hid = hidden_states(params, tokens, cfg)
    return logits_rows(params, lax.dynamic_slice_in_dim(hid, n_prompt - 1, rows, 0), cfg)


def served_logits(params, prompt, served, cfg, length=None, rows=None):
    """(logits [n_served, V], margin [n_served]) at the positions that produced
    ``served`` when the model is fed ``prompt + served`` once, whole: the
    runners' interface (``mistral.served_logits``). Padded on the right to
    ``length`` (causal, and the recurrence runs forward: padding never reaches
    a served row); nothing is routed, so every margin is inf."""
    import numpy as np

    toks = np.asarray(list(prompt) + list(served), np.int32)
    rows = rows or -(-len(served) // 128) * 128
    length = max(length or 0, -(-(len(prompt) - 1 + rows) // Q_BLOCK) * Q_BLOCK)
    toks = np.pad(toks, (0, length - len(toks)))
    cfg_key = tuple(sorted((k, tuple(v) if isinstance(v, list) else v) for k, v in cfg.items()
                           if isinstance(v, (int, float, str, type(None), list))
                           and not (isinstance(v, list) and k != "layer_types")))
    with jax.default_matmul_precision("highest"):
        lg = _served_logits(params, jnp.asarray(toks), jnp.int32(len(prompt)), rows, cfg_key)
    return lg[:len(served)], jnp.full((len(served),), jnp.inf, jnp.float32)
