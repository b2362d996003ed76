"""Plain float32 reference for the ``phi4flash`` recipe (Phi-4-mini-flash-reasoning:
the decoder-hybrid-decoder "SambaY" stack of arXiv:2507.06607 with differential
attention): Mamba-1 mixers beside differential attention under a window, ONE
full-attention layer whose keys and values every later cross-attention layer
reads, gated memory units that read one Mamba layer's scan output, a dense
SwiGLU MLP after every mixer, LayerNorm with bias, a tied head, no positions.

With D the hidden size, L the depth and ``h = LayerNorm(x)`` (mean-subtracting,
scale 1 and bias 0 at init), every layer is ``x += Mixer_i(h)`` then ``x +=
(silu(h' Wg) * (h' Wu)) Wd`` with ``h' = LayerNorm(x)``; ``logits =
LayerNorm(x_L) E^T`` against the token table E. The mixer of layer i (0-based;
``layer_types`` states it, by the published modelling code's rule: Mamba where
``i % mb_per_layer == 0``, a window only on odd ``i < L/2``, the cross-decoder
from ``L/2 + 2``):

- ``mamba`` (i even, i <= L/2): ``(u, z) = h W_in``; ``u = silu(conv(u) + b)``
  (causal, depthwise, ``d_conv`` taps); ``(dt_r, B, C) = u W_x``; ``dt =
  softplus(dt_r W_dt + b_dt)``; from ``s_0 = 0``, per channel c and state n:
  ``s_t[c,n] = exp(dt_t[c] A[c,n]) s_{t-1}[c,n] + dt_t[c] B_t[n] u_t[c]`` with
  ``A = -exp(A_log)``; ``m_t[c] = sum_n C_t[n] s_t[c,n] + D[c] u_t[c]``; output
  ``(m * silu(z)) W_out``. The LAST such layer's ``m`` (before the gate) is the
  memory the gated memory units read.
- ``sliding_attention`` (i odd, i < L/2) and ``full_attention`` (i = L/2 + 1):
  ``q = h Wq + bq`` as H/2 pairs of heads ``(q1, q2)`` (heads 2j and 2j+1), ``k``
  and ``v`` alike as KV/2 pairs, G = H/KV query pairs a kv pair; ``V = v1 | v2``;
  ``a1 = softmax(q1 k1^T / sqrt(HD)) V``, ``a2`` alike from ``(q2, k2)``, causal,
  and under the window a query at p sees ``p - window < k <= p``; ``lambda =
  exp(l_q1 . l_k1) - exp(l_q2 . l_k2) + lambda_init``, ``lambda_init = 0.8 - 0.6
  exp(-0.3 i)``; ``o = RMSNorm(a1 - lambda a2) (1 - lambda_init)`` per pair (2 HD
  wide, scale 1, eps 1e-5); output ``concat(o) Wo + bo``. No rotation.
- ``gmu`` (i even, i >= L/2 + 2): ``(m * silu(h W_1)) W_2``, m the memory at the
  same positions.
- ``cross_attention`` (i odd, i >= L/2 + 3): ``q = h Wq + bq`` only; keys and
  values are the full-attention layer's; the same differential attention with
  the layer's own lambdas and ``lambda_init(i)``; ``Wo + bo``.

The scan runs TOKEN BY TOKEN (``lax.scan`` over time), scores are whole
matrices of the sequence, every layer runs at every position: no cache, no
ring, no skipped layer, no batching. Straight ``jax.numpy`` at
``jax.default_matmul_precision("highest")``. It imports nothing of
``tpu_engine`` and takes nothing the program has made: the weights are drawn
here, from the seed, by the recipe the configuration states under
``assumed.init`` (which the program follows too).

Departures from the published code, only to fit: the float32 tree is 15.4 GB, so
the walk DRAWS EACH LAYER'S WEIGHTS AS IT REACHES THE LAYER and holds one layer
at a time (``init_params`` returns the table and the seed); attention runs in
blocks of ``Q_BLOCK`` query rows against every key (dense, masked) and the MLP
in blocks of ``ROW_BLOCK`` rows; the fused ``Wqkv`` and ``W_1 = (Wg, Wu)`` are
drawn as their parts. ``assumed`` in the configuration file lists what the
catalog's ``config`` does not hold; ``mixer_weights`` / ``mamba_mixer`` /
``diff_attention`` are held to ``transformers``' ``MambaMixer`` and
``DiffLlamaAttention`` by the repository's tests.

``cfg`` everywhere is the benchmark's configuration file as a dict (Hugging
Face key names; the sizes the published config lacks under ``assumed_sizes``).
"""

from __future__ import annotations

import json
import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

STD = 0.02
LAMBDA_STD = 0.1
SUB_NORM_EPS = 1e-5
Q_BLOCK = 128     # query rows per attention block
ROW_BLOCK = 2048  # rows per MLP block
BIG = 1e30
# Each kind's keys: split(fold_in(PRNGKey(seed), FOLD[kind]), 16); a leaf's key
# at LEAF_KEY[leaf]; layer i of the kind's n is drawn from split(key, n)[i] alone.
FOLD = {"mamba": 105, "sliding_attention": 106, "full_attention": 107, "cross_attention": 108, "gmu": 109}
LEAF_KEY = {"q": 0, "in_proj": 0, "k": 1, "conv": 1, "v": 2, "x_proj": 2, "o": 3, "dt_proj": 3,
            "lambdas": 4, "dt": 4, "out_proj": 5, "gate": 6, "up": 7, "down": 8,
            "q_bias": 9, "k_bias": 10, "v_bias": 11, "o_bias": 12}


def _dims(cfg: dict) -> dict:
    D, H, KV = cfg["hidden_size"], cfg["num_attention_heads"], cfg["num_key_value_heads"]
    a = cfg["assumed_sizes"]
    kinds = list(cfg["layer_types"])
    assert len(kinds) == cfg["num_hidden_layers"] and not set(kinds) - set(FOLD), kinds
    return dict(D=D, F=cfg["intermediate_size"], V=cfg["vocab_size"], H=H, KV=KV, HD=D // H,
                I=a["mamba_expand"] * D, N=a["mamba_d_state"], K=a["mamba_d_conv"], R=a["mamba_dt_rank"],
                W=cfg["sliding_window"], kinds=kinds, L=len(kinds))


def mixer_weights(cfg: dict, seed, kind: str, i) -> dict:
    """Layer ``i`` (within its kind's stack) as float32 weights: the mixer's and
    the MLP's. Norm scales are ones and their biases zeros and are not stored.
    ``seed`` and ``i`` are arguments under ``jit``, never constants."""
    d = _dims(cfg)
    D, F, H, KV, HD, I, N, K, R = (d[k] for k in ("D", "F", "H", "KV", "HD", "I", "N", "K", "R"))
    n = d["kinds"].count(kind)
    keys = jax.random.split(jax.random.fold_in(jax.random.PRNGKey(seed), FOLD[kind]), 16)
    res = STD / math.sqrt(2 * d["L"])

    def key(name):
        return jax.random.split(keys[LEAF_KEY[name]], n)[i]

    def normal(name, shape, std=STD):
        return jax.random.normal(key(name), shape, jnp.float32) * std

    def uniform(name, shape, lo, hi):
        return jax.random.uniform(key(name), shape, jnp.float32, lo, hi)

    w = {"gate": normal("gate", (D, F)), "up": normal("up", (D, F)), "down": normal("down", (F, D), res)}
    if kind == "mamba":
        dt = jnp.exp(uniform("dt", (I,), math.log(1e-3), math.log(1e-1)))
        w.update(in_proj=normal("in_proj", (D, 2 * I)),
                 conv=uniform("conv", (K, I), -1.0 / math.sqrt(K), 1.0 / math.sqrt(K)), conv_bias=jnp.zeros((I,)),
                 x_proj=normal("x_proj", (I, R + 2 * N)),
                 dt_proj=uniform("dt_proj", (R, I), -1.0 / math.sqrt(R), 1.0 / math.sqrt(R)),
                 dt_bias=dt + jnp.log(-jnp.expm1(-dt)),                     # softplus^-1(dt)
                 A_log=jnp.broadcast_to(jnp.log(jnp.arange(1, N + 1, dtype=jnp.float32)), (I, N)),
                 D=jnp.ones((I,)), out_proj=normal("out_proj", (I, D), res))
    elif kind == "gmu":
        w.update(in_proj=normal("in_proj", (D, I)), out_proj=normal("out_proj", (I, D), res))
    else:
        w.update(q=normal("q", (D, H * HD)), q_bias=normal("q_bias", (H * HD,)),
                 o=normal("o", (H * HD, D), res), o_bias=normal("o_bias", (D,)),
                 lambdas=normal("lambdas", (4, HD), LAMBDA_STD))          # l_q1, l_k1, l_q2, l_k2
        if kind != "cross_attention":
            w.update(k=normal("k", (D, KV * HD)), k_bias=normal("k_bias", (KV * HD,)),
                     v=normal("v", (D, KV * HD)), v_bias=normal("v_bias", (KV * HD,)))
    return w


def init_params(cfg: dict, seed: int) -> dict:
    """What is held for the whole walk: the token table (which is the head too)
    and the seed the layers are drawn from as the walk reaches them."""
    d = _dims(cfg)

    @jax.jit
    def table(sd):
        k0 = jax.random.split(jax.random.PRNGKey(sd), 16)[0]
        return jax.random.normal(k0, (d["V"], d["D"]), jnp.float32) * STD

    return {"embed": table(jnp.uint32(seed)), "seed": jnp.uint32(seed)}


# ----------------------------------------------------------------------------
# Forward
# ----------------------------------------------------------------------------


def layer_norm(x, eps):
    """LayerNorm with the unit scale and zero bias every norm is drawn with."""
    mu = jnp.mean(x, -1, keepdims=True)
    return (x - mu) * lax.rsqrt(jnp.mean(jnp.square(x - mu), -1, keepdims=True) + eps)


def mamba_mixer(h, w, cfg):
    """h [S, D] -> (output [S, D], the scan output ``m`` [S, I] BEFORE the
    gate); the recurrence one token at a time from zeros."""
    d = _dims(cfg)
    S, I, N, K, R = h.shape[0], d["I"], d["N"], d["K"], d["R"]
    uz = h @ w["in_proj"]
    u, z = uz[:, :I], uz[:, I:]
    padded = jnp.concatenate([jnp.zeros((K - 1, I), u.dtype), u], axis=0)
    u = jax.nn.silu(sum(padded[k:k + S] * w["conv"][k] for k in range(K)) + w["conv_bias"])
    dbc = u @ w["x_proj"]
    dt = jax.nn.softplus(dbc[:, :R] @ w["dt_proj"] + w["dt_bias"])          # [S, I]
    Bm, Cm = dbc[:, R:R + N], dbc[:, R + N:]
    A = -jnp.exp(w["A_log"])                                                  # [I, N]

    def step(s, t):
        dt_t, u_t, b_t, c_t = t
        s = jnp.exp(dt_t[:, None] * A) * s + (dt_t * u_t)[:, None] * b_t[None, :]
        return s, s @ c_t

    _, y = lax.scan(step, jnp.zeros((I, N), jnp.float32), (dt, u, Bm, Cm))
    m = y + w["D"] * u
    return (m * jax.nn.silu(z)) @ w["out_proj"], m


def lambda_init(layer_index):
    return 0.8 - 0.6 * jnp.exp(-0.3 * layer_index)


def keys_values(h, w, cfg):
    """A layer's keys and values [S, KV x HD], as the cross-attention layers read them."""
    return h @ w["k"] + w["k_bias"], h @ w["v"] + w["v_bias"]


def diff_attention(h, w, k, v, cfg, layer_index, window: int):
    """h [S, D], keys and values k, v [S, KV x HD] (the layer's own, or the
    full-attention layer's for a cross layer) -> [S, D]. ``window`` 0: causal
    over every earlier key; else a query at p sees ``p - window < key <= p``.
    Scores are whole rows of the sequence, ``Q_BLOCK`` queries at a time."""
    d = _dims(cfg)
    S, H, KV, HD = h.shape[0], d["H"], d["KV"], d["HD"]
    G = H // KV                                                   # query pairs a kv pair
    q = (h @ w["q"] + w["q_bias"]).reshape(S, KV // 2, G, 2, HD)   # pair j = (kv pair j // G, j % G)
    k = k.reshape(S, KV // 2, 2, HD)
    v = v.reshape(S, KV // 2, 2 * HD)                              # V = v1 | v2
    lam = w["lambdas"]
    init = lambda_init(layer_index)
    lam_full = jnp.exp(jnp.sum(lam[0] * lam[1])) - jnp.exp(jnp.sum(lam[2] * lam[3])) + init
    qb = min(Q_BLOCK, S)
    assert S % qb == 0, (S, qb)
    lanes = jnp.arange(S)

    def rows(start):
        pos = start + jnp.arange(qb)
        q_b = lax.dynamic_slice_in_dim(q, start, qb, 0)
        keep = lanes[None, :] <= pos[:, None]
        if window:
            keep &= lanes[None, :] > pos[:, None] - window
        out = []
        for half in (0, 1):
            s = jnp.einsum("tkgd,mkd->kgtm", q_b[:, :, :, half], k[:, :, half]) / math.sqrt(HD)
            p = jax.nn.softmax(jnp.where(keep[None, None], s, -BIG), axis=-1)
            out.append(jnp.einsum("kgtm,mkd->tkgd", p, v))
        a = out[0] - lam_full * out[1]                               # [qb, KV/2, G, 2 HD]
        a = a * lax.rsqrt(jnp.mean(jnp.square(a), -1, keepdims=True) + SUB_NORM_EPS)
        return (a * (1.0 - init)).reshape(qb, H * HD)

    o = lax.map(rows, jnp.arange(S // qb) * qb).reshape(S, H * HD)
    return o @ w["o"] + w["o_bias"]


def mlp(h, w):
    """Rows in blocks of at most ``ROW_BLOCK``, only so that [S, F] in float32
    is never whole."""
    S = h.shape[0]
    rows = math.gcd(S, ROW_BLOCK)
    one = lambda hb: (jax.nn.silu(hb @ w["gate"]) * (hb @ w["up"])) @ w["down"]  # noqa: E731
    return lax.map(one, h.reshape(S // rows, rows, -1)).reshape(S, -1)


@partial(jax.jit, static_argnames=("kind", "cfg_key"))
def _layer(x, mem, k, v, seed, i, layer_index, kind, cfg_key):
    """One layer on x [S, D], its weights drawn here and dropped on return.
    ``mem`` [S, I] is the memory (the last Mamba layer's scan output before its
    gate), ``k``, ``v`` [S, KV x HD] the last full-attention layer's keys and
    values: a layer that makes one returns it, every other hands them on."""
    cfg = _thaw(cfg_key)
    w = mixer_weights(cfg, seed, kind, i)
    eps = cfg["layer_norm_eps"]
    h = layer_norm(x, eps)
    if kind == "mamba":
        mixed, mem = mamba_mixer(h, w, cfg)
    elif kind == "gmu":
        mixed = (mem * jax.nn.silu(h @ w["in_proj"])) @ w["out_proj"]
    elif kind == "cross_attention":
        mixed = diff_attention(h, w, k, v, cfg, layer_index, 0)
    else:
        own_k, own_v = keys_values(h, w, cfg)
        mixed = diff_attention(h, w, own_k, own_v, cfg, layer_index,
                               cfg["sliding_window"] if kind == "sliding_attention" else 0)
        if kind == "full_attention":
            k, v = own_k, own_v
    x = x + mixed
    return x + mlp(layer_norm(x, eps), w), mem, k, v


def hidden_states(params, tokens, cfg):
    """tokens [S] -> final hidden [S, D] (before the final norm): a Python walk
    over the layers, one program per kind."""
    d = _dims(cfg)
    key = _freeze(cfg)
    x = params["embed"][jnp.asarray(tokens, jnp.int32)]
    S = x.shape[0]
    mem = jnp.zeros((S, d["I"]), jnp.float32)
    k = v = jnp.zeros((S, d["KV"] * d["HD"]), jnp.float32)
    seen = dict.fromkeys(FOLD, 0)
    for index, kind in enumerate(d["kinds"]):
        x, mem, k, v = _layer(x, mem, k, v, params["seed"], jnp.int32(seen[kind]), jnp.float32(index), kind, key)
        seen[kind] += 1
    return x


@partial(jax.jit, static_argnames=("rows", "cfg_key"))
def _logits_rows(embed, hidden, n_prompt, rows, cfg_key):
    cfg = _thaw(cfg_key)
    h = layer_norm(lax.dynamic_slice_in_dim(hidden, n_prompt - 1, rows, 0), cfg["layer_norm_eps"])
    return h @ embed.T


def forward_logits(params, tokens, cfg):
    """tokens [S] -> logits [S, V]: the whole forward pass, for the tests."""
    with jax.default_matmul_precision("highest"):
        hid = hidden_states(params, tokens, cfg)
        return _logits_rows(params["embed"], hid, jnp.int32(1), len(tokens), _freeze(cfg))


def _freeze(cfg: dict) -> str:
    """The keys the forward pass reads, as a string (a static argument of jit)."""
    keep = ("hidden_size", "intermediate_size", "vocab_size", "num_attention_heads", "num_key_value_heads",
            "num_hidden_layers", "layer_types", "sliding_window", "layer_norm_eps", "assumed_sizes")
    return json.dumps({k: cfg[k] for k in keep}, sort_keys=True)


_thaw = json.loads


def served_logits(params, prompt, served, cfg, length=None, rows=None):
    """(logits [n_served, V], margin [n_served]) at the positions that produced
    ``served`` when the model is fed ``prompt + served`` once, whole: the
    runners' interface (``mistral.served_logits``). Padded on the right to
    ``length`` (causal, and the recurrence runs forward: padding never reaches
    a served row). No router decides anything here: every margin is inf."""
    import numpy as np

    toks = np.asarray(list(prompt) + list(served), np.int32)
    rows = rows or -(-len(served) // 128) * 128
    length = max(length or 0, -(-(len(prompt) - 1 + rows) // Q_BLOCK) * Q_BLOCK)
    toks = np.pad(toks, (0, length - len(toks)))
    with jax.default_matmul_precision("highest"):
        hid = hidden_states(params, toks, cfg)
        lg = _logits_rows(params["embed"], hid, jnp.int32(len(prompt)), rows, _freeze(cfg))
    return lg[:len(served)], jnp.full((len(served),), jnp.inf, jnp.float32)
