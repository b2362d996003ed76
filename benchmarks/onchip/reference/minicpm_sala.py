"""Plain float32 reference for the ``minicpm_sala`` recipe (MiniCPM-SALA):
block-sparse attention layers beside lightning (linear) attention layers in
one stack, a dense SwiGLU MLP after either, an untied head and the muP scalars.

With D the hidden size, ``u = RMSNorm(x)``, every softmax and state float32:

- ``x0 = scale_emb * E[token]``; each layer ``x += r * Mixer(RMSNorm(x))`` then
  ``x += r * MLP(RMSNorm(x))`` with ``r = scale_depth / sqrt(published depth)``
  (32, whatever depth the file keeps), ``MLP(h) = (silu(h Wg) * (h Wu)) Wd``;
  ``logits = RMSNorm(x_L) W_head / (D / dim_model_base)``.
- lightning mixer, head h = 1..H of the layer published at index l:
  ``q_t = rope(norm(u_t Wq))``, ``k_t = rope(norm(u_t Wk))`` (per-head RMSNorm,
  half-split rotation, theta ``rope_theta``), ``v_t = u_t Wv``;
  ``d = exp(-2^(-8h/H) (1 - l/(L-1) + 1e-5))``; from ``S_0 = 0``:
  ``S_t = d S_{t-1} + k_t^T v_t``, ``o_t = q_t S_t / sqrt(E)``;
  output ``(RMSNorm(o_t) * sigmoid(u_t Wgate)) Wo``, the norm over the whole
  inner width.
- sparse mixer, kv-head g with its G query heads, query at position t: q and k
  per-head normed, not rotated. ``t < dense_len``: causal attention over all
  j <= t. Else: compressed keys ``ck[m] = mean(k[stride m : stride m + size])``
  for every window that ends at or before t; ``p_h = softmax_m(q_h . ck[m] /
  sqrt(HD))``; ``s[m] = sum_{h in g} p_h[m]``; block b (lanes ``block b`` ..
  ``block b + block - 1``) scores the max of ``s[m]`` over the windows that
  overlap it; the first ``init_blocks`` blocks, the block that holds t and the
  ``window_size / block - 1`` before it are forced; the ``topk`` best blocks,
  forced ones counted, are attended: causal softmax of ``q_h . k_j / sqrt(HD)``
  over their lanes. Output ``(concat_h o_h * sigmoid(u_t Wgate)) Wo``.

The lightning recurrence runs TOKEN BY TOKEN (``lax.scan`` over time: no
chunked form); the selection is a plain ``top_k`` for every position; no cache,
no batching. Straight ``jax.numpy`` at ``jax.default_matmul_precision(
"highest")``. It imports nothing of ``tpu_engine`` and takes nothing the
program has made: the weights are drawn here, from the seed, by the recipe the
configuration states under ``assumed.init`` (which the program follows too).

Departures from the published description, only to fit: twelve layers in
float32 are 15.7 GB, so the walk DRAWS EACH LAYER'S WEIGHTS AS IT REACHES THE
LAYER and holds one layer at a time (``init_params`` returns the table, the
head and the seed); attention runs in blocks of ``Q_BLOCK`` query rows, dense
and masked by the selection, and the MLP in blocks of ``ROW_BLOCK`` rows. ``assumed`` in the configuration file lists what
the catalog's ``config`` does not hold.

``cfg`` everywhere is the benchmark's configuration file as a dict (Hugging
Face key names; the sparse sizes under ``sparse_config``).
"""

from __future__ import annotations

import json
import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

STD = 0.02
Q_BLOCK = 128  # query rows per attention block
ROW_BLOCK = 2048  # rows per MLP block
KINDS = {"minicpm4": "sparse", "lightning-attn": "lightning"}
FOLD = {"sparse": 101, "lightning": 102}  # each kind's keys: split(fold_in(PRNGKey(seed), FOLD), 8)
# Index of each drawn leaf's key in its kind's 8-way split; layer i of n is
# drawn from split(key, n)[i] alone.
LEAF_KEYS = ("q", "k", "v", "o_gate", "o", "gate", "up", "down")
BIG = 1e30


def _dims(cfg: dict) -> dict:
    D, H, KV = cfg["hidden_size"], cfg["num_attention_heads"], cfg["num_key_value_heads"]
    kinds = [KINDS[t] for t in cfg["mixer_types"]]
    assert len(kinds) == cfg["num_hidden_layers"]
    assert cfg["lightning_nkv"] == cfg["lightning_nh"] and cfg["qk_norm"] and not cfg["attn_use_rope"]
    return dict(D=D, F=cfg["intermediate_size"], V=cfg["vocab_size"], H=H, KV=KV, HD=cfg["head_dim"],
                LH=cfg["lightning_nh"], E=cfg["lightning_head_dim"], kinds=kinds, L=len(kinds))


def layer_shapes(cfg: dict, kind: str) -> dict[str, tuple]:
    """name -> (shape, std) of one layer's drawn kernels."""
    d = _dims(cfg)
    D, F = d["D"], d["F"]
    res = STD / math.sqrt(2 * d["L"])
    inner, kv = (d["H"] * d["HD"], d["KV"] * d["HD"]) if kind == "sparse" else (d["LH"] * d["E"],) * 2
    return {"q": ((D, inner), STD), "k": ((D, kv), STD), "v": ((D, kv), STD), "o_gate": ((D, inner), STD),
            "o": ((inner, D), res), "gate": ((D, F), STD), "up": ((D, F), STD), "down": ((F, D), res)}


def draw_layer(cfg: dict, seed, kind: str, i) -> dict:
    """Layer ``i`` (within its kind's stack) as float32 kernels; norm scales
    are ones and are not stored. ``seed`` and ``i`` are arguments under
    ``jit``, never constants."""
    n = _dims(cfg)["kinds"].count(kind)
    keys = jax.random.split(jax.random.fold_in(jax.random.PRNGKey(seed), FOLD[kind]), len(LEAF_KEYS))
    out = {}
    for j, name in enumerate(LEAF_KEYS):
        shape, std = layer_shapes(cfg, kind)[name]
        out[name] = jax.random.normal(jax.random.split(keys[j], n)[i], shape, jnp.float32) * std
    return out


def init_params(cfg: dict, seed: int) -> dict:
    """What is held for the whole walk: the table (drawn ``scale_emb`` times
    smaller than 0.02, so that x0 has 0.02), the head, and the seed the layers
    are drawn from as the walk reaches them."""
    d = _dims(cfg)

    @jax.jit
    def top(sd):
        k0 = jax.random.split(jax.random.PRNGKey(sd), 16)[0]
        return {"embed": jax.random.normal(k0, (d["V"], d["D"]), jnp.float32) * (STD / cfg["scale_emb"]),
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


def lightning_mixer(u, w, cfg, published_index):
    """u [S, D] -> [S, D]; the recurrence one token at a time from zeros.
    ``published_index``: where the model has this layer (a scalar)."""
    d = _dims(cfg)
    S, H, E, eps = u.shape[0], d["LH"], d["E"], cfg["rms_norm_eps"]
    q = rope(rms_norm((u @ w["q"]).reshape(S, H, E), eps), cfg["rope_theta"])
    k = rope(rms_norm((u @ w["k"]).reshape(S, H, E), eps), cfg["rope_theta"])
    v = (u @ w["v"]).reshape(S, H, E)
    slopes = 2.0 ** (-8.0 * jnp.arange(1, H + 1, dtype=jnp.float32) / H)
    decay = jnp.exp(-slopes * (1.0 - published_index / (cfg["published"]["num_hidden_layers"] - 1) + 1e-5))

    def step(s, t):
        q_t, k_t, v_t = t
        s = decay[:, None, None] * s + k_t[:, :, None] * v_t[:, None, :]
        return s, jnp.sum(q_t[:, :, None] * s, axis=1)              # [H, E]

    _, o = lax.scan(step, jnp.zeros((H, E, E), jnp.float32), (q, k, v))
    o = rms_norm(o.reshape(S, H * E) / math.sqrt(E), eps)
    return (o * jax.nn.sigmoid(u @ w["o_gate"])) @ w["o"]


def select_blocks(q, ck, pos, cfg, n_blocks: int):
    """Block ids [KV, T, topk] the queries ``q`` [T, KV, G, HD] at positions
    ``pos`` [T] attend, from the compressed keys ``ck`` [M, KV, HD] (row m
    ends at lane ``stride m + size - 1``)."""
    sc = cfg["sparse_config"]
    size, stride, block = sc["kernel_size"], sc["kernel_stride"], sc["block_size"]
    M, hd = ck.shape[0], q.shape[-1]
    s = jnp.einsum("tkgd,mkd->kgtm", q, ck) / math.sqrt(hd)
    seen = (stride * jnp.arange(M) + size - 1)[None, :] <= pos[:, None]          # [T, M]
    p = jnp.where(seen, jax.nn.softmax(jnp.where(seen, s, -BIG), axis=-1), 0.0)
    w = jnp.sum(p, axis=1)                                                        # [KV, T, M]
    # Block b scores the best window that overlaps it.
    first, last = stride * jnp.arange(M), stride * jnp.arange(M) + size - 1
    lo, hi = block * jnp.arange(n_blocks), block * jnp.arange(n_blocks) + block - 1
    overlap = (first[None, :] <= hi[:, None]) & (last[None, :] >= lo[:, None])    # [n_blocks, M]
    score = jnp.max(jnp.where(overlap[None, None], w[:, :, None, :], 0.0), axis=-1)   # [KV, T, n_blocks]
    b, own = jnp.arange(n_blocks)[None, :], (pos // block)[:, None]
    forced = (b < sc["init_blocks"]) | (b > own - sc["window_size"] // block)
    score = jnp.where(b <= own, jnp.where(forced, BIG, score), -BIG)
    return lax.top_k(score, min(sc["topk"], n_blocks))[1]


def sparse_mixer(u, w, cfg):
    """u [S, D] -> ([S, D], every position's chosen block ids [S, KV, topk]:
    what a position below ``dense_len`` would choose is in them too, though it
    attends everything)."""
    d = _dims(cfg)
    sc = cfg["sparse_config"]
    S, H, KV, HD, eps = u.shape[0], d["H"], d["KV"], d["HD"], cfg["rms_norm_eps"]
    size, stride, block = sc["kernel_size"], sc["kernel_stride"], sc["block_size"]
    q = rms_norm((u @ w["q"]).reshape(S, KV, H // KV, HD), eps)
    k = rms_norm((u @ w["k"]).reshape(S, KV, HD), eps)
    v = (u @ w["v"]).reshape(S, KV, HD)
    M = (S - size) // stride + 1
    ck = jnp.mean(k[(stride * jnp.arange(M))[:, None] + jnp.arange(size)], axis=1)   # [M, KV, HD]
    n_blocks, qb = S // block, min(Q_BLOCK, S)
    assert S % block == 0 and S % qb == 0, (S, block, qb)
    lanes = jnp.arange(S)

    def rows(start):
        pos = start + jnp.arange(qb)
        q_b = lax.dynamic_slice_in_dim(q, start, qb, 0)
        ids = select_blocks(q_b, ck, pos, cfg, n_blocks)                          # [KV, qb, topk]
        chosen = jnp.any(ids[..., None] == jnp.arange(n_blocks), axis=-2)         # [KV, qb, n_blocks]
        chosen |= (pos < sc["dense_len"])[None, :, None]
        keep = jnp.repeat(chosen, block, axis=-1) & (lanes[None, None, :] <= pos[None, :, None])
        s = jnp.einsum("tkgd,mkd->kgtm", q_b, k) / math.sqrt(HD)
        p = jax.nn.softmax(jnp.where(keep[:, None], s, -BIG), axis=-1)
        return jnp.einsum("kgtm,mkd->tkgd", p, v).reshape(qb, H * HD), ids.transpose(1, 0, 2)

    o, ids = lax.map(rows, jnp.arange(S // qb) * qb)
    y = (o.reshape(S, H * HD) * jax.nn.sigmoid(u @ w["o_gate"])) @ w["o"]
    return y, ids.reshape(S, KV, -1)


def mlp(h, w):
    """Rows in blocks of at most ``ROW_BLOCK``, only so that [S, F] in float32
    is never whole (2.3 GB three times over at the served length)."""
    S = h.shape[0]
    rows = math.gcd(S, ROW_BLOCK)
    one = lambda hb: (jax.nn.silu(hb @ w["gate"]) * (hb @ w["up"])) @ w["down"]  # noqa: E731
    return lax.map(one, h.reshape(S // rows, rows, -1)).reshape(S, -1)


def residual_scale(cfg: dict) -> float:
    return cfg["scale_depth"] / math.sqrt(cfg["published"]["num_hidden_layers"])


@partial(jax.jit, static_argnames=("kind", "cfg_key"))
def _layer(x, seed, i, published_index, kind, cfg_key):
    """One layer on x [S, D], its weights drawn here and dropped on return:
    (x, the sparse layer's chosen block ids or None)."""
    cfg = _thaw(cfg_key)
    w = draw_layer(cfg, seed, kind, i)
    eps, r = cfg["rms_norm_eps"], residual_scale(cfg)
    u = rms_norm(x, eps)
    mixed, ids = sparse_mixer(u, w, cfg) if kind == "sparse" \
        else (lightning_mixer(u, w, cfg, published_index), None)
    x = x + r * mixed
    return x + r * mlp(rms_norm(x, eps), w), ids


def hidden_states(params, tokens, cfg, with_ids=False):
    """tokens [S] -> final hidden [S, D] (before the final norm): a Python
    walk over the layers, one program per kind (``with_ids``: and the block
    ids of each sparse layer, in order)."""
    key = _freeze(cfg)
    x = cfg["scale_emb"] * params["embed"][jnp.asarray(tokens, jnp.int32)]
    seen, chosen = {"sparse": 0, "lightning": 0}, []
    for kind, at in zip(_dims(cfg)["kinds"], cfg["kept_layers"]):
        x, ids = _layer(x, params["seed"], jnp.int32(seen[kind]), jnp.float32(at), kind, key)
        seen[kind] += 1
        if ids is not None:
            chosen.append(ids)
    return (x, chosen) if with_ids else x


@partial(jax.jit, static_argnames=("rows", "cfg_key"))
def _logits_rows(head, hidden, n_prompt, rows, cfg_key):
    cfg = _thaw(cfg_key)
    h = rms_norm(lax.dynamic_slice_in_dim(hidden, n_prompt - 1, rows, 0), cfg["rms_norm_eps"])
    return (h @ head) / (cfg["hidden_size"] / cfg["dim_model_base"])


def forward_logits(params, tokens, cfg):
    """tokens [S] -> (logits [S, V], each sparse layer's chosen block ids
    [S, KV, topk]): the whole forward pass, for the tests."""
    with jax.default_matmul_precision("highest"):
        hid, chosen = hidden_states(params, tokens, cfg, with_ids=True)
        return _logits_rows(params["head"], hid, jnp.int32(1), len(tokens), _freeze(cfg)), chosen


def _freeze(cfg: dict) -> str:
    """The keys the forward pass reads, as a string (a static argument of jit)."""
    keep = ("hidden_size", "intermediate_size", "vocab_size", "num_attention_heads", "num_key_value_heads",
            "head_dim", "lightning_nh", "lightning_nkv", "lightning_head_dim", "mixer_types",
            "num_hidden_layers", "qk_norm", "attn_use_rope", "rms_norm_eps", "rope_theta", "scale_emb",
            "scale_depth", "dim_model_base", "sparse_config", "kept_layers")
    return json.dumps({**{k: cfg[k] for k in keep},
                       "published": {"num_hidden_layers": cfg["published"]["num_hidden_layers"]}}, sort_keys=True)


_thaw = json.loads


def served_logits(params, prompt, served, cfg, length=None, rows=None):
    """(logits [n_served, V], margin [n_served]) at the positions that produced
    ``served`` when the model is fed ``prompt + served`` once, whole: the
    runners' interface (``mistral.served_logits``). Padded on the right to
    ``length`` (causal, and the recurrence runs forward: padding never reaches
    a served row). Which blocks a position reads is decided by scores, not by
    a router with a margin to report: every margin is inf."""
    import numpy as np

    toks = np.asarray(list(prompt) + list(served), np.int32)
    rows = rows or -(-len(served) // 128) * 128
    length = max(length or 0, -(-(len(prompt) - 1 + rows) // Q_BLOCK) * Q_BLOCK)
    toks = np.pad(toks, (0, length - len(toks)))
    with jax.default_matmul_precision("highest"):
        hid = hidden_states(params, toks, cfg)
        lg = _logits_rows(params["head"], hid, jnp.int32(len(prompt)), rows, _freeze(cfg))
    return lg[:len(served)], jnp.full((len(served),), jnp.inf, jnp.float32)
