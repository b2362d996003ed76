"""Plain float32 reference for the Mistral-7B recipe (and, with experts, the
Mixtral one — see ``mixtral.py``): RMSNorm, rotary embeddings (half-split, the
Hugging Face layout), grouped-query causal attention with a sliding window,
SwiGLU, an untied head; next-token cross-entropy; AdamW with global-norm
clipping and linear warm-up.

Straight ``jax.numpy`` at ``jax.default_matmul_precision("highest")``: no
kernels, no cache, no batching tricks. It imports nothing of ``tpu_engine``
and takes nothing the program has made — the weights are drawn here, from the
seed, by the published recipe the program also follows (normal(0.02), output
projections scaled by 1/sqrt(2·layers)).

Departures from a textbook forward pass, each to make the real widths fit one
chip beside nothing else: attention runs one KV group and one block of query
rows at a time (``lax.map``), layers are scanned, and both are wrapped in
``jax.checkpoint`` so the backward pass recomputes instead of storing the
S x S scores.

``cfg`` everywhere is the benchmark's configuration file as a dict (Hugging
Face key names).
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

STD = 0.02
Q_BLOCK = 1024  # query rows per attention block


# ----------------------------------------------------------------------------
# Weights from the seed
# ----------------------------------------------------------------------------


def leaf_specs(cfg: dict) -> dict[tuple[str, ...], tuple[int, tuple[int, ...], float]]:
    """path -> (index of its key in the 9-way split, shape, std). Norm scales
    are ones and carry key index -1."""
    L, D, V = cfg["num_hidden_layers"], cfg["hidden_size"], cfg["vocab_size"]
    F = cfg["intermediate_size"]
    H, KV = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    HD = cfg.get("head_dim") or D // H
    E = cfg.get("num_local_experts") or 0
    res = STD / math.sqrt(2 * L)
    ex = (E,) if E else ()
    specs = {
        ("embed", "embedding"): (0, (V, D), STD),
        ("layers", "attn_norm", "scale"): (-1, (L, D), 1.0),
        ("layers", "q", "kernel"): (1, (L, D, H * HD), STD),
        ("layers", "k", "kernel"): (2, (L, D, KV * HD), STD),
        ("layers", "v", "kernel"): (3, (L, D, KV * HD), STD),
        ("layers", "o", "kernel"): (4, (L, H * HD, D), res),
        ("layers", "mlp_norm", "scale"): (-1, (L, D), 1.0),
        ("layers", "gate", "kernel"): (5, (L, *ex, D, F), STD),
        ("layers", "up", "kernel"): (6, (L, *ex, D, F), STD),
        ("layers", "down", "kernel"): (7, (L, *ex, F, D), res),
        ("final_norm", "scale"): (-1, (D,), 1.0),
        ("lm_head", "kernel"): (8, (D, V), STD),
    }
    if E:
        specs[("layers", "router", "kernel")] = (-2, (L, D, E), STD)
    return specs


def init_leaf(cfg: dict, seed, path: tuple[str, ...]) -> jax.Array:
    """One float32 leaf, drawn as the recipe says. Inside ``jit`` pass the seed
    as an argument, never as a constant: a program that holds its seed compiles
    anew for every seed and never hits the compile cache."""
    idx, shape, std = leaf_specs(cfg)[path]
    if idx == -1:
        return jnp.ones(shape, jnp.float32)
    keys = jax.random.split(jax.random.PRNGKey(seed), 9)
    key = jax.random.fold_in(keys[5], 1) if idx == -2 else keys[idx]
    return jax.random.normal(key, shape, jnp.float32) * std


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


def rms_norm(x, w, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * lax.rsqrt(jnp.mean(jnp.square(x32), -1, keepdims=True) + eps)
    return (y * w.astype(jnp.float32)).astype(x.dtype)


def rope(x, theta):
    """x [S, heads, hd] at positions 0..S-1; rotates (first half, second half)."""
    S, _, hd = x.shape
    half = hd // 2
    inv = jnp.exp(-jnp.arange(half, dtype=jnp.float32) * (math.log(theta) / half))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1).astype(x.dtype)


def attention(q, k, v, window: int):
    """q [S, H, hd], k/v [S, KV, hd] -> [S, H*hd]. Causal; a query at i sees
    keys j with i - window < j <= i (window 0: all j <= i)."""
    S, H, hd = q.shape
    KV = k.shape[1]
    G = H // KV
    qb = min(Q_BLOCK, S)
    assert S % qb == 0, (S, qb)
    nb = S // qb
    qg = q.reshape(nb, qb, KV, G, hd).transpose(2, 0, 1, 3, 4)  # [KV, nb, qb, G, hd]
    kg, vg = k.transpose(1, 0, 2), v.transpose(1, 0, 2)         # [KV, S, hd]
    j = jnp.arange(S)[None, :]

    @jax.checkpoint
    def block(q_blk, k_g, v_g, start):
        i = (start + jnp.arange(qb))[:, None]
        mask = j <= i
        if window:
            mask &= j > i - window
        s = jnp.einsum("qgd,kd->gqk", q_blk, k_g,
                       preferred_element_type=jnp.float32) / math.sqrt(hd)
        s = jnp.where(mask[None], s, -1e30)
        p = jax.nn.softmax(s, axis=-1).astype(q_blk.dtype)
        return jnp.einsum("gqk,kd->qgd", p, v_g)                 # [qb, G, hd]

    def group(args):
        q_g, k_g, v_g = args
        starts = jnp.arange(nb) * qb
        return lax.map(lambda a: block(a[0], k_g, v_g, a[1]), (q_g, starts))

    out = lax.map(group, (qg, kg, vg))                           # [KV, nb, qb, G, hd]
    return out.transpose(1, 2, 0, 3, 4).reshape(S, H * hd)


def dense_mlp(h, lp):
    """-> (output [S, D], margin [S]): a dense layer routes nothing, so every
    position is decided (margin inf)."""
    out = (jax.nn.silu(h @ lp["gate"]["kernel"]) * (h @ lp["up"]["kernel"])) @ lp["down"]["kernel"]
    return out, jnp.full(h.shape[:1], jnp.inf, jnp.float32)


def hidden_states(params, tokens, cfg, mlp=dense_mlp, with_margin=False):
    """tokens [S] -> final hidden [S, D] (before the final norm); with
    ``with_margin`` also the least routing margin of each position over the
    layers."""
    H, KV = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    HD = cfg.get("head_dim") or cfg["hidden_size"] // H
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    window = cfg.get("sliding_window") or 0
    S = tokens.shape[0]
    x = params["embed"]["embedding"][tokens]

    @jax.checkpoint
    def layer(x, lp):
        h = rms_norm(x, lp["attn_norm"]["scale"], eps)
        q = rope((h @ lp["q"]["kernel"]).reshape(S, H, HD), theta)
        k = rope((h @ lp["k"]["kernel"]).reshape(S, KV, HD), theta)
        v = (h @ lp["v"]["kernel"]).reshape(S, KV, HD)
        x = x + attention(q, k, v, window) @ lp["o"]["kernel"]
        y, margin = mlp(rms_norm(x, lp["mlp_norm"]["scale"], eps), lp)
        return x + y, margin

    x, margins = lax.scan(layer, x, params["layers"])
    return (x, jnp.min(margins, axis=0)) if with_margin else x


def logits_rows(params, hidden, cfg):
    h = rms_norm(hidden, params["final_norm"]["scale"], cfg["rms_norm_eps"])
    return jnp.einsum("sd,dv->sv", h, params["lm_head"]["kernel"].astype(h.dtype),
                      preferred_element_type=jnp.float32)


def loss(params, batch, cfg, mlp=dense_mlp):
    """batch [B, S] int32 -> mean next-token cross-entropy over B*(S-1) targets."""

    def row(tokens):
        lg = logits_rows(params, hidden_states(params, tokens, cfg, mlp), cfg)[:-1]
        logz = jax.nn.logsumexp(lg, axis=-1)
        return jnp.sum(logz - jnp.take_along_axis(lg, tokens[1:, None], 1)[:, 0])

    B, S = batch.shape
    return jnp.sum(lax.map(row, batch)) / (B * (S - 1))


# ----------------------------------------------------------------------------
# Serving: the gap by which each served token lies below the reference's best
# ----------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("rows", "cfg_key", "mlp"))
def _served_logits(params, tokens, n_prompt, rows, cfg_key, mlp):
    cfg = dict(cfg_key)
    hid, margin = hidden_states(params, tokens, cfg, mlp, with_margin=True)
    take = lambda a: lax.dynamic_slice_in_dim(a, n_prompt - 1, rows, 0)  # noqa: E731
    return logits_rows(params, take(hid), cfg), take(margin)   # row t predicts served[t]


def served_logits(params, prompt, served, cfg, mlp=dense_mlp, length=None, rows=None):
    """(logits [n_served, V], margin [n_served]) at the positions that produced
    ``served`` when the model is fed ``prompt + served`` once, whole. The
    tokens are padded on the right to ``length`` (causal: padding never reaches
    a served row) and ``rows`` rows are computed, so that one compiled program
    scores every request of a cell. ``margin`` is how decided the routing was
    at each position (inf for a dense model; see ``mixtral.moe_mlp``)."""
    import numpy as np

    toks = np.asarray(list(prompt) + list(served), np.int32)
    rows = rows or -(-len(served) // 128) * 128
    length = max(length or 0, -(-(len(prompt) - 1 + rows) // Q_BLOCK) * Q_BLOCK)
    toks = np.pad(toks, (0, length - len(toks)))
    cfg_key = tuple(sorted((k, v) for k, v in cfg.items()
                           if isinstance(v, (int, float, str, type(None)))))
    with jax.default_matmul_precision("highest"):
        lg, margin = _served_logits(params, jnp.asarray(toks), jnp.int32(len(prompt)), rows,
                                    cfg_key, mlp)
    return lg[:len(served)], margin[:len(served)]


# ----------------------------------------------------------------------------
# Training: three steps of AdamW
# ----------------------------------------------------------------------------


def _is_kernel(path) -> bool:
    return getattr(path[-1], "key", None) == "kernel"


def leaf_norms(tree) -> dict[str, list[float]]:
    """Norm of every leaf; a leaf stacked over layers gives one norm per layer."""
    out = {}
    for path, a in jax.tree_util.tree_flatten_with_path(tree)[0]:
        name = "/".join(str(getattr(p, "key", p)) for p in path)
        a = a.astype(jnp.float32)
        if name.startswith("layers/"):
            n = jnp.sqrt(jnp.sum(jnp.square(a), axis=tuple(range(1, a.ndim))))
        else:
            n = jnp.sqrt(jnp.sum(jnp.square(a)))[None]
        out[name] = n
    return out


def change_norms(cfg: dict, seed: int, path: tuple[str, ...], leaf) -> list[float]:
    """Norm of a parameter leaf's change from its seeded value (one norm per
    layer for a stacked leaf). The seeded value is drawn again here, inside the
    call, so no second copy of the weights is ever resident."""
    fn = jax.jit(lambda a, sd: leaf_norms({path[0]: {"x": a - init_leaf(cfg, sd, path)}})[path[0] + "/x"])
    return [float(x) for x in jax.device_get(fn(leaf, jnp.uint32(seed)))]


def path_keys(path) -> tuple[str, ...]:
    return tuple(str(getattr(k, "key", k)) for k in path)


def make_step(cfg, hyper, mlp=dense_mlp):
    """One AdamW step, jitted: (params, m, v, batch, t) -> (params, m, v, loss,
    norms of the clipped gradient's leaves). ``t`` is the step count so far."""
    b1, b2 = hyper["beta1"], hyper["beta2"]
    clip, wd = hyper["grad_clip_norm"], hyper["weight_decay"]

    @partial(jax.jit, donate_argnums=(0, 1, 2))
    def step(p, m, v, batch, t):
        l, g = jax.value_and_grad(loss)(p, batch, cfg, mlp)
        gn = jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in jax.tree.leaves(g)))
        g = jax.tree.map(lambda x: jnp.where(gn < clip, x, x / gn * clip), g)
        gnorms = leaf_norms(g)
        lr = hyper["learning_rate"] * t / hyper["warmup_steps"]
        tt = t + 1.0
        m = jax.tree.map(lambda a, x: b1 * a + (1 - b1) * x, m, g)
        v = jax.tree.map(lambda a, x: b2 * a + (1 - b2) * x * x, v, g)

        def upd(path, w, mm, vv):
            u = (mm / (1 - b1 ** tt)) / (jnp.sqrt(vv / (1 - b2 ** tt)) + 1e-8)
            if _is_kernel(path):
                u = u + wd * w
            return w - lr * u

        p = jax.tree_util.tree_map_with_path(upd, p, m, v)
        return p, m, v, l, gnorms

    return step


def train_steps(cfg, hyper, seed, batches, mlp=dense_mlp):
    """Run len(batches) AdamW steps from the seeded weights. Returns
    {"loss": [...], "grad": {leaf: [norms]}, "dparam": {leaf: [norms]}} where
    ``grad`` is the first step's gradient after clipping (what Adam is given)
    and ``dparam`` the parameters' change over all the steps.

    ``hyper``: learning_rate, warmup_steps, beta1, beta2, weight_decay,
    grad_clip_norm."""
    if len(batches) >= hyper["warmup_steps"]:
        raise ValueError("the reference covers linear warm-up only")

    import time

    t_start = time.perf_counter()
    split = {}
    with jax.default_matmul_precision("highest"):
        zeros = jax.jit(lambda t: jax.tree.map(jnp.zeros_like, t))

        step = make_step(cfg, hyper, mlp)
        p = init_params(cfg, seed)
        m, v = zeros(p), zeros(p)  # laid out as the parameters are
        split["init"] = time.perf_counter() - t_start
        losses, first = [], None
        for t, batch in enumerate(batches):
            p, m, v, l, gnorms = step(p, m, v, jnp.asarray(batch), jnp.float32(t))
            losses.append(float(l))
            if t == 0:
                first = {k: [float(x) for x in n] for k, n in jax.device_get(gnorms).items()}
            split[f"step{t + 1}"] = time.perf_counter() - t_start
        # The change from the seeded weights, which are drawn again leaf by leaf.
        dparam = {"/".join(path_keys(path)): change_norms(cfg, seed, path_keys(path), leaf)
                  for path, leaf in jax.tree_util.tree_flatten_with_path(p)[0]}
        split["dparam"] = time.perf_counter() - t_start
    return {"loss": losses, "grad": first, "dparam": dparam, "split_s": split}
