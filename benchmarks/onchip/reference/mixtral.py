"""Plain float32 reference for the Mixtral-8x7B recipe: the Mistral block of
``mistral.py`` with the feed-forward replaced by eight SwiGLU experts, of
which a router picks two per token (softmax over all experts, take the top
two, renormalise the two gates to sum to one — Mixtral's published routing).
Every expert is computed for every token and weighted; no capacity, no drops.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from . import mistral
from .mistral import (  # noqa: F401 — one interface for every reference family
    change_norms, init_leaf, init_params, leaf_norms, leaf_specs, path_keys,
)

TOP_K = 2


def moe_mlp(h, lp):
    """h [S, D] -> (output [S, D], margin [S]). ``margin`` is the gap between
    the router logit of the last expert taken and of the first one left out:
    where it is all but zero, which two experts a token gets is decided by the
    rounding of whatever precision computes the logits, and the layer's output
    jumps with it — top-k routing is not continuous there."""
    logits = jnp.einsum("sd,de->se", h, lp["router"]["kernel"].astype(h.dtype),
                        preferred_element_type=jnp.float32)
    ranked = lax.top_k(logits, TOP_K + 1)[0]
    margin = ranked[:, TOP_K - 1] - ranked[:, TOP_K]
    probs = jax.nn.softmax(logits, axis=-1)
    top, idx = lax.top_k(probs, TOP_K)
    top = top / jnp.sum(top, -1, keepdims=True)
    w = jnp.sum(jax.nn.one_hot(idx, probs.shape[-1], dtype=jnp.float32) * top[..., None], axis=1)

    def expert(args):
        g, u, d = args
        return (jax.nn.silu(h @ g) * (h @ u)) @ d                    # [S, D]

    outs = lax.map(expert, (lp["gate"]["kernel"], lp["up"]["kernel"], lp["down"]["kernel"]))
    return jnp.einsum("se,esd->sd", w.astype(h.dtype), outs), margin


def served_logits(params, prompt, served, cfg, length=None, rows=None):
    assert cfg["num_experts_per_tok"] == TOP_K, cfg["num_experts_per_tok"]
    return mistral.served_logits(params, prompt, served, cfg, mlp=moe_mlp,
                                 length=length, rows=rows)


def train_steps(cfg, hyper, seed, batches):
    return mistral.train_steps(cfg, hyper, seed, batches, mlp=moe_mlp)
