"""Plain float32 reference for the ``brumby`` recipe (Brumby-14B-Base: a dense
decoder whose every mixer is a gated power-retention layer of degree 2,
arXiv:2507.04239 "Scaling Context Requires Rethinking Attention"), in the
ATTENTION form of the layer: explicit weights of every earlier position, no
state, no expansion of keys, no chunks, no cache.

With D the hidden size, H query heads and KV key-value heads of HD, G = H / KV,
``u = RMSNorm(x)`` (eps ``rms_norm_eps``, scale 1 at init), every layer is ``x
+= Mixer(u)`` then ``x += (silu(u' Wg) * (u' Wu)) Wd`` with ``u' = RMSNorm(x)``;
``logits = RMSNorm(x_L) W_head``. The mixer, for position t:

- ``q_t = u_t Wq`` as H heads, ``k_t = u_t Wk`` and ``v_t = u_t Wv`` as KV
  heads, ``g_t = u_t Wgate + b_gate`` (one gate a key-value head; no other bias);
- ``q^_{t,i} = rot_t(RMSNorm_HD(q_{t,i}))``, ``k^_{t,h}`` alike (per-head norm,
  scale 1; half-split rotation at ``rope_theta``, as the ``mistral`` reference);
- ``G_{t,h} = sum_{r <= t} log sigmoid(g_{r,h})``;
- for query head i of group h = i // G and s <= t: ``w_{t,s,i} = exp(G_{t,h} -
  G_{s,h}) (q^_{t,i} . k^_{s,h} / sqrt(HD))^2``;
- ``o_{t,i} = sum_s w_{t,s,i} v_{s,h} / (sum_s w_{t,s,i} + eps)``; output
  ``concat_i(o_{t,i}) Wo``.

Straight ``jax.numpy`` at ``jax.default_matmul_precision("highest")``. It
imports nothing of ``tpu_engine`` and takes nothing the program has made: the
weights are drawn here, from the seed, by the recipe the configuration states
under ``assumed.init`` (which the program follows too). What the catalog's
``config`` does not hold (the degree, the gate and its bias, the normaliser,
the scale) is read from the file's ``assumed`` keys listed in :func:`_dims`.

Departures, only to fit: eight layers in float32 are 10.6 GB beside a table and
a head of 3.1 GB each, so the walk DRAWS EACH LAYER'S WEIGHTS AS IT REACHES THE
LAYER and holds one at a time (``init_params`` returns the table, the head and
the seed); the weights ``w`` are formed in blocks of ``Q_BLOCK`` query
positions against every key (dense, masked) and the MLP runs in blocks of
``ROW_BLOCK`` rows.

``cfg`` everywhere is the benchmark's configuration file as a dict (Hugging
Face key names).
"""

from __future__ import annotations

import json
import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

STD = 0.02
Q_BLOCK = 128     # query positions per block of weights
ROW_BLOCK = 2048  # rows per MLP block
FOLD = 110        # the layers' keys: split(fold_in(PRNGKey(seed), FOLD), 9)
# Index of each drawn leaf's key in that split; layer i of n is drawn from
# split(key, n)[i] alone.
LEAF_KEYS = ("q", "k", "v", "g_proj", "o", "gate", "up", "down", "g_bias")


def _dims(cfg: dict) -> dict:
    assert cfg["power_degree"] == 2, "the reference squares the dot product"
    return dict(D=cfg["hidden_size"], F=cfg["intermediate_size"], V=cfg["vocab_size"],
                H=cfg["num_attention_heads"], KV=cfg["num_key_value_heads"], HD=cfg["head_dim"],
                L=cfg["num_hidden_layers"], eps=cfg["rms_norm_eps"], norm_eps=cfg["power_norm_eps"],
                half_life=tuple(cfg["gate_half_life_tokens"]))


def layer_shapes(cfg: dict) -> dict[str, tuple]:
    """name -> (shape, std) of one layer's drawn kernels."""
    d = _dims(cfg)
    D, F, H, KV, HD = d["D"], d["F"], d["H"], d["KV"], d["HD"]
    res = STD / math.sqrt(2 * d["L"])
    return {"q": ((D, H * HD), STD), "k": ((D, KV * HD), STD), "v": ((D, KV * HD), STD),
            "g_proj": ((D, KV), STD), "o": ((H * HD, D), res),
            "gate": ((D, F), STD), "up": ((D, F), STD), "down": ((F, D), res)}


def draw_layer(cfg: dict, seed, i) -> dict:
    """Layer ``i`` as float32 kernels and the gate's bias ``logit(2^(-1/tau))``,
    ``tau`` log-uniform over ``gate_half_life_tokens`` per kv-head; norm scales
    are ones and are not stored. ``seed`` and ``i`` are arguments under ``jit``,
    never constants."""
    d = _dims(cfg)
    keys = jax.random.split(jax.random.fold_in(jax.random.PRNGKey(seed), FOLD), len(LEAF_KEYS))
    out = {}
    for name, (shape, std) in layer_shapes(cfg).items():
        key = jax.random.split(keys[LEAF_KEYS.index(name)], d["L"])[i]
        out[name] = jax.random.normal(key, shape, jnp.float32) * std
    lo, hi = (math.log(t) for t in d["half_life"])
    tau = jnp.exp(jax.random.uniform(jax.random.split(keys[LEAF_KEYS.index("g_bias")], d["L"])[i],
                                     (d["KV"],), jnp.float32, lo, hi))
    out["g_bias"] = -jnp.log(jnp.expm1(math.log(2.0) / tau))     # logit(2^(-1/tau))
    return out


def init_params(cfg: dict, seed: int) -> dict:
    """What is held for the whole walk: the table, the untied head, and the
    seed the layers are drawn from as the walk reaches them."""
    d = _dims(cfg)

    @jax.jit
    def top(sd):
        k0 = jax.random.split(jax.random.PRNGKey(sd), 16)[0]
        return {"embed": jax.random.normal(k0, (d["V"], d["D"]), jnp.float32) * STD,
                "head": jax.random.normal(jax.random.fold_in(k0, 1), (d["D"], d["V"]), jnp.float32) * STD}

    return {**top(jnp.uint32(seed)), "seed": jnp.uint32(seed)}


# ----------------------------------------------------------------------------
# Forward
# ----------------------------------------------------------------------------


def rms_norm(x, eps):
    """RMSNorm with the unit scale every norm of the recipe is drawn with."""
    return x * lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps)


def rope(x, theta):
    """x [S, heads, hd] at positions 0..S-1; rotates (first half, second half)."""
    S, _, hd = x.shape
    half = hd // 2
    inv = jnp.exp(-jnp.arange(half, dtype=jnp.float32) * (math.log(theta) / half))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def retention_mixer(u, w, cfg):
    """u [S, D] -> [S, D]: every position's weights over every earlier one."""
    d = _dims(cfg)
    S, H, KV, HD = u.shape[0], d["H"], d["KV"], d["HD"]
    q = rope(rms_norm((u @ w["q"]).reshape(S, H, HD), d["eps"]), cfg["rope_theta"]).reshape(S, KV, H // KV, HD)
    k = rope(rms_norm((u @ w["k"]).reshape(S, KV, HD), d["eps"]), cfg["rope_theta"])
    v = (u @ w["v"]).reshape(S, KV, HD)
    cum = jnp.cumsum(jax.nn.log_sigmoid(u @ w["g_proj"] + w["g_bias"]), axis=0)        # [S, KV]
    qb = math.gcd(S, Q_BLOCK)
    at = jnp.arange(S)

    def rows(start):
        pos = start + jnp.arange(qb)
        q_b = lax.dynamic_slice_in_dim(q, start, qb, 0)
        g_b = lax.dynamic_slice_in_dim(cum, start, qb, 0)
        dot = jnp.einsum("tkgd,skd->kgts", q_b, k) / math.sqrt(HD)
        seen = at[None, :] <= pos[:, None]                                              # [qb, S]
        decay = jnp.exp(jnp.where(seen[None], g_b.T[:, :, None] - cum.T[:, None, :], -jnp.inf))   # [KV, qb, S]
        wts = jnp.square(dot) * decay[:, None]
        num = jnp.einsum("kgts,skd->tkgd", wts, v)
        den = jnp.moveaxis(jnp.sum(wts, axis=-1), 2, 0)                                 # [qb, KV, G]
        return (num / (den[..., None] + d["norm_eps"])).reshape(qb, H * HD)

    o = lax.map(rows, jnp.arange(S // qb) * qb)
    return o.reshape(S, H * HD) @ w["o"]


def mlp(h, w):
    """Rows in blocks of at most ``ROW_BLOCK``, only so that [S, F] in float32
    is never whole."""
    S = h.shape[0]
    rows = math.gcd(S, ROW_BLOCK)
    one = lambda hb: (jax.nn.silu(hb @ w["gate"]) * (hb @ w["up"])) @ w["down"]  # noqa: E731
    return lax.map(one, h.reshape(S // rows, rows, -1)).reshape(S, -1)


@partial(jax.jit, static_argnames=("cfg_key",))
def _layer(x, seed, i, cfg_key):
    """One layer on x [S, D], its weights drawn here and dropped on return."""
    cfg = _thaw(cfg_key)
    w = draw_layer(cfg, seed, i)
    eps = cfg["rms_norm_eps"]
    x = x + retention_mixer(rms_norm(x, eps), w, cfg)
    return x + mlp(rms_norm(x, eps), w)


def hidden_states(params, tokens, cfg):
    """tokens [S] -> final hidden [S, D] (before the final norm): a Python walk
    over the layers, one program for all of them."""
    key = _freeze(cfg)
    x = params["embed"][jnp.asarray(tokens, jnp.int32)]
    for i in range(cfg["num_hidden_layers"]):
        x = _layer(x, params["seed"], jnp.int32(i), key)
    return x


@partial(jax.jit, static_argnames=("rows", "cfg_key"))
def _logits_rows(head, hidden, n_prompt, rows, cfg_key):
    cfg = _thaw(cfg_key)
    h = rms_norm(lax.dynamic_slice_in_dim(hidden, n_prompt - 1, rows, 0), cfg["rms_norm_eps"])
    return h @ head


def forward_logits(params, tokens, cfg):
    """tokens [S] -> logits [S, V]: the whole forward pass, for the tests."""
    with jax.default_matmul_precision("highest"):
        hid = hidden_states(params, tokens, cfg)
        return _logits_rows(params["head"], hid, jnp.int32(1), len(tokens), _freeze(cfg))


def _freeze(cfg: dict) -> str:
    """The keys the forward pass reads, as a string (a static argument of jit)."""
    keep = ("hidden_size", "intermediate_size", "vocab_size", "num_attention_heads", "num_key_value_heads",
            "head_dim", "num_hidden_layers", "rms_norm_eps", "rope_theta", "power_degree", "power_norm_eps",
            "gate_half_life_tokens")
    return json.dumps({k: cfg[k] for k in keep}, sort_keys=True)


_thaw = json.loads


def served_logits(params, prompt, served, cfg, length=None, rows=None):
    """(logits [n_served, V], margin [n_served]) at the positions that produced
    ``served`` when the model is fed ``prompt + served`` once, whole: the
    runners' interface (``mistral.served_logits``). Padded on the right to
    ``length`` (causal: padding never reaches a served row). Nothing is routed:
    every margin is inf."""
    import numpy as np

    toks = np.asarray(list(prompt) + list(served), np.int32)
    rows = rows or -(-len(served) // 128) * 128
    length = max(length or 0, -(-(len(prompt) - 1 + rows) // Q_BLOCK) * Q_BLOCK)
    toks = np.pad(toks, (0, length - len(toks)))
    with jax.default_matmul_precision("highest"):
        hid = hidden_states(params, toks, cfg)
        lg = _logits_rows(params["head"], hid, jnp.int32(len(prompt)), rows, _freeze(cfg))
    return lg[:len(served)], jnp.full((len(served),), jnp.inf, jnp.float32)
