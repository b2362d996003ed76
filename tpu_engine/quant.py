"""Weight-only int8 quantization for serving.

The reference control plane launches DeepSpeed jobs with fp16/bf16
configs only (``deepspeed_launcher.py``: precision knobs, no inference
quantization — the reference has no inference path at all). Serving is
where quantization pays on TPU: decode is weight-HBM-bandwidth-bound
(every generated token re-reads every weight), so storing projection
kernels as int8 halves both the weight footprint and the per-token HBM
traffic — the same lever as the KV-cache int8 mode
(:func:`tpu_engine.generate.init_cache` ``kv_quant``), applied to the
other half of decode's working set. Together they serve llama-7b-class
models on a single 16 GiB v5e chip.

Scheme: symmetric per-output-channel absmax. A kernel ``[..., in, out]``
becomes int8 codes of the same shape plus an fp32 scale ``[..., 1, out]``
(the contracted dim reduced). Because the scale is constant along the
contraction, it applies AFTER the matmul — ``(h @ q) * scale`` — so the
int8→bf16 convert fuses into the dot's operand read (XLA producer
fusion) and HBM sees only the int8 bytes. int8 magnitudes ≤ 127 are
exact in bfloat16, so the cast loses nothing.

What quantizes: the per-layer projection kernels (q/k/v/o,
gate/up/down — incl. stacked MoE expert kernels and a mixture's shared
expert — or fc/proj for GPT-2-family) and the LM head. What stays in the
master dtype: embeddings (a lookup, and the tied head of gpt2/gemma — tied-head
models keep a full-precision head), norm scales/biases, projection
biases, the MoE router (fp32-critical and ~0.01% of bytes), and qk-norm
scales.

Training never sees :class:`QuantWeight` — this is a serving-side
transform applied to a trained (or snapshot) param tree.
"""

from __future__ import annotations

import json
import os
from dataclasses import dataclass
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from tpu_engine.layer_state import LAYER_KINDS


@jax.tree_util.register_dataclass
@dataclass
class QuantWeight:
    """An int8-quantized linear kernel (a pytree — crosses jit/scan
    boundaries; ``lax.scan`` over a stacked ``[L, ...]`` tree slices
    ``q`` and ``scale`` in lockstep).

    ``q``: int8 codes, the original kernel's shape ``[..., in, out]``.
    ``scale``: fp32, ``[..., 1, out]`` — per-output-channel absmax/127,
    constant along the contracted (input) dim so it can be applied to
    the matmul OUTPUT.
    """

    q: jax.Array
    scale: jax.Array

    @property
    def shape(self) -> tuple[int, ...]:
        return self.q.shape

    @property
    def ndim(self) -> int:
        return self.q.ndim


@partial(jax.jit, static_argnames="axis")
def quantize_weight(w: jax.Array, axis: int = -2) -> QuantWeight:
    """Symmetric int8 quantization with the absmax taken over ``axis``
    (the contracted dim — every kernel this module touches contracts its
    second-to-last dim). One program: beside a float32 leaf stand its codes
    and no float32 intermediate (op by op, a stacked expert leaf of a few GB
    stood three times over)."""
    w32 = w.astype(jnp.float32)
    scale = jnp.max(jnp.abs(w32), axis=axis, keepdims=True) / 127.0
    scale = jnp.maximum(scale, 1e-12)
    q = jnp.clip(jnp.round(w32 / scale), -127, 127).astype(jnp.int8)
    return QuantWeight(q=q, scale=scale)


def dequantize_weight(qw: QuantWeight, dtype=jnp.float32) -> jax.Array:
    return (qw.q.astype(jnp.float32) * qw.scale).astype(dtype)


# Per-layer projection names whose "kernel" quantizes. Covers the llama
# family (q/k/v/o/gate/up/down), GPT-2 (q/k/v/o/fc/proj), and MoE
# (stacked expert gate/up/down; the router stays fp32).
_QUANT_LAYER_KEYS = ("q", "k", "v", "o", "gate", "up", "down", "fc", "proj",
                     # a hybrid stack's Mamba-2 mixer projections
                     "in_proj", "out_proj",
                     # a Mamba-1 mixer's projections to its step, B and C
                     "x_proj", "dt_proj",
                     # the output gate of its lightning and sparse-attention layers
                     "o_gate",
                     # a latent-attention (MLA) layer's down- and up-projection
                     # of keys and values
                     "kv_a", "kv_b",
                     # the shared expert beside a hybrid's mixture (its routed
                     # experts are ``gate`` / ``up`` / ``down``, stacked)
                     "shared_gate", "shared_up", "shared_down")


def _walk(params: dict[str, Any], kernel_fn) -> dict[str, Any]:
    """Structural walk shared by the param transform and the
    pspec mirror: applies ``kernel_fn`` to every quantization site,
    preserving everything else (biases, norms, router, embeddings)."""
    def walk_stack(stack):
        layers = dict(stack)
        for name in _QUANT_LAYER_KEYS:
            sub = layers.get(name)
            if isinstance(sub, dict) and "kernel" in sub:
                new_sub = dict(sub)
                new_sub["kernel"] = kernel_fn(sub["kernel"])
                layers[name] = new_sub
        return layers

    out = dict(params)
    if "layers" in params:
        layers = params["layers"]
        # A hybrid stack keeps one stack per kind of layer.
        out["layers"] = ({kind: walk_stack(stack) for kind, stack in layers.items()}
                         if set(layers) <= set(LAYER_KINDS) else walk_stack(layers))
    if "lm_head" in params:
        head = dict(params["lm_head"])
        head["kernel"] = kernel_fn(head["kernel"])
        out["lm_head"] = head
    return out


def quantize_params(params: dict[str, Any]) -> dict[str, Any]:
    """Param tree → serving tree with projection kernels as
    :class:`QuantWeight`. Idempotent-hostile by design: quantizing an
    already-quantized tree raises (re-quantization would silently
    compound the error). A kernel still to be drawn
    (``init_params(deferred=True)``) is drawn here and quantized at once,
    so its float32 lives no longer than that."""

    def quant(kernel):
        if isinstance(kernel, QuantWeight):
            raise ValueError("params are already int8-quantized")
        return quantize_weight(kernel() if callable(kernel) else kernel)

    return _walk(params, quant)


def quantized_param_bytes(params: dict[str, Any]) -> int:
    """Total bytes of a (possibly quantized) param tree — int8 leaves
    count 1 byte, scales 4; the fit benchmarks' accounting helper."""
    return sum(
        leaf.size * leaf.dtype.itemsize
        for leaf in jax.tree.leaves(params)
    )


def quantize_pspecs(pspecs: dict[str, Any], qparams: dict[str, Any]) -> dict[str, Any]:
    """Mirror a PartitionSpec tree onto a quantized param tree: at each
    :class:`QuantWeight` site the kernel's spec applies to ``q``
    unchanged, and the scale inherits it with the contracted dim (which
    collapsed to size 1) unsharded. ``qparams`` supplies each site's
    rank (a spec may have trailing dims trimmed); both trees are walked
    in one paired traversal, so a site present in one but not the other
    fails loudly instead of misaligning.
    """

    def mirror(spec: P, site) -> QuantWeight:
        if not isinstance(site, QuantWeight):
            raise ValueError(
                "quantize_pspecs needs the QUANTIZED param tree to read "
                f"kernel ranks (found {type(site).__name__}); call "
                "quantize_params first"
            )
        axes = list(spec) + [None] * (site.ndim - len(spec))
        axes[-2] = None  # the contracted dim is size 1 in the scale
        return QuantWeight(q=spec, scale=P(*axes))

    out = dict(pspecs)
    if ("layers" in pspecs) != ("layers" in qparams):
        raise ValueError("pspec and param trees disagree on 'layers'")
    if "layers" in pspecs:
        layers = dict(pspecs["layers"])
        for name in _QUANT_LAYER_KEYS:
            spec_sub, par_sub = layers.get(name), qparams["layers"].get(name)
            has_spec = isinstance(spec_sub, dict) and "kernel" in spec_sub
            has_par = isinstance(par_sub, dict) and "kernel" in par_sub
            if has_spec != has_par:
                raise ValueError(f"pspec/param trees disagree on layers.{name}")
            if has_spec:
                new_sub = dict(spec_sub)
                new_sub["kernel"] = mirror(spec_sub["kernel"], par_sub["kernel"])
                layers[name] = new_sub
        out["layers"] = layers
    if ("lm_head" in pspecs) != ("lm_head" in qparams):
        raise ValueError("pspec and param trees disagree on 'lm_head'")
    if "lm_head" in pspecs:
        head = dict(pspecs["lm_head"])
        head["kernel"] = mirror(head["kernel"], qparams["lm_head"]["kernel"])
        out["lm_head"] = head
    return out


# ---------------------------------------------------------------------------
# Quantized serving snapshots: quantize once, serve many times
# ---------------------------------------------------------------------------

_MANIFEST = "quant_snapshot.json"


def save_quantized(qparams: dict[str, Any], out_dir: str,
                   model_config: Any = None) -> str:
    """Persist a quantized serving tree as one ``.npy`` per leaf plus a
    manifest. int8 codes dominate the bytes, so a llama-7b snapshot is
    ~7 GB instead of 13.5 (bf16) or 27 (fp32) — and
    :func:`load_quantized` mmaps + uploads it one leaf at a time, so a
    serving host never materialises the tree twice.

    The tree must contain at least one :class:`QuantWeight` (use
    :func:`quantize_params` first — persisting an unquantized tree here
    would silently lose the format's point and is probably a bug)."""
    os.makedirs(out_dir, exist_ok=True)
    if os.path.exists(os.path.join(out_dir, _MANIFEST)):
        # Leaf files are written in place; overwriting an existing
        # snapshot would leave a valid old manifest over mixed-step leaf
        # files if interrupted — and load_quantized would serve that
        # Frankenstein tree without error. Fresh directory per export.
        raise ValueError(
            f"'{out_dir}' already holds a snapshot; export to a fresh "
            "directory (a crashed overwrite would silently mix steps)"
        )
    manifest: dict[str, Any] = {"leaves": {}}
    if model_config is not None:
        import dataclasses as _dc

        # The frozen ModelConfig is all primitives — a self-describing
        # snapshot serves without the caller re-supplying the config.
        manifest["model_config"] = _dc.asdict(model_config)
    n_quant = 0

    _CHUNK_BYTES = 128 * 2**20

    def record(path: str, arr, kind: str) -> None:
        fname = path.replace("/", "__") + ".npy"
        fpath = os.path.join(out_dir, fname)
        shape = tuple(arr.shape)
        nbytes = int(np.prod(shape or (1,))) * jnp.dtype(arr.dtype).itemsize
        if nbytes > _CHUNK_BYTES and shape and shape[0] > 1:
            # Big stacked leaves (a 7B gate kernel is ~1.4 GB) fetch in
            # bounded slices along the leading dim: one giant device→host
            # transfer can stall remote runtimes, and the host never
            # needs more than a chunk resident. The memmap writes the
            # same .npy format np.save would.
            rows = max(1, shape[0] * _CHUNK_BYTES // nbytes)
            first = np.asarray(arr[:1])
            out = np.lib.format.open_memmap(
                fpath, mode="w+", dtype=first.dtype, shape=shape
            )
            out[:1] = first
            for i in range(1, shape[0], rows):
                out[i:i + rows] = np.asarray(arr[i:i + rows])
            out.flush()
            host_dtype = first.dtype
        else:
            host = np.asarray(arr)
            np.save(fpath, host)
            host_dtype = host.dtype
        manifest["leaves"][path] = {
            "file": fname, "kind": kind, "dtype": str(host_dtype),
            "shape": list(shape),
        }

    def walk(node, prefix: str) -> None:
        nonlocal n_quant
        if isinstance(node, QuantWeight):
            n_quant += 1
            record(prefix + ".q", node.q, "quant_q")
            record(prefix + ".scale", node.scale, "quant_scale")
        elif isinstance(node, dict):
            for k, v in node.items():
                walk(v, f"{prefix}/{k}" if prefix else k)
        else:
            record(prefix, node, "array")

    walk(qparams, "")
    if not n_quant:
        raise ValueError(
            "tree has no QuantWeight leaves — quantize_params first"
        )
    tmp = os.path.join(out_dir, _MANIFEST + ".tmp")
    with open(tmp, "w") as f:
        json.dump(manifest, f)
    os.replace(tmp, os.path.join(out_dir, _MANIFEST))
    return out_dir


def load_quantized_config(snapshot_dir: str) -> Optional[Any]:
    """The ModelConfig recorded by :func:`save_quantized`, or None for
    snapshots written without one."""
    with open(os.path.join(snapshot_dir, _MANIFEST)) as f:
        raw = json.load(f).get("model_config")
    if raw is None:
        return None
    from tpu_engine.models.transformer import ModelConfig

    return ModelConfig(**raw)


def load_quantized(snapshot_dir: str,
                   shardings: Optional[dict[str, Any]] = None) -> dict[str, Any]:
    """Rebuild a quantized serving tree from :func:`save_quantized`
    output. Each leaf is mmapped and uploaded before the next is touched
    (bounded host residency). ``shardings``: an optional tree of
    NamedShardings matching the QUANTIZED structure (build with
    ``quantize_pspecs`` + ``named_shardings``) for mesh-sharded serving;
    omitted leaves go to the default device."""
    with open(os.path.join(snapshot_dir, _MANIFEST)) as f:
        manifest = json.load(f)
    leaves = manifest["leaves"]

    def put(path: str, sh) -> jax.Array:
        meta = leaves[path]
        host = np.load(os.path.join(snapshot_dir, meta["file"]), mmap_mode="r")
        want = np.dtype(meta["dtype"])  # ml_dtypes names resolve via jax
        if host.dtype != want:
            # Extended dtypes (bfloat16) round-trip .npy as raw void
            # bytes — reinterpret, don't convert.
            host = host.view(want)
        return jax.device_put(host, sh) if sh is not None else jnp.asarray(host)

    # Group leaf paths back into the nested dict structure.
    tree: dict[str, Any] = {}
    quant_sites: dict[str, dict[str, str]] = {}
    for path, meta in leaves.items():
        if meta["kind"] in ("quant_q", "quant_scale"):
            site, field = path.rsplit(".", 1)
            quant_sites.setdefault(site, {})[field] = path

    def sharding_at(path: str):
        node = shardings
        if node is None:
            return None
        for part in path.split("/"):
            if not isinstance(node, dict) or part not in node:
                return None
            node = node[part]
        return node

    def insert(path: str, value) -> None:
        parts = path.split("/")
        node = tree
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = value

    for path, meta in leaves.items():
        if meta["kind"] != "array":
            continue
        insert(path, put(path, sharding_at(path)))
    for site, fields in quant_sites.items():
        sh = sharding_at(site)
        q_sh = sh.q if isinstance(sh, QuantWeight) else None
        s_sh = sh.scale if isinstance(sh, QuantWeight) else None
        insert(site, QuantWeight(
            q=put(fields["q"], q_sh), scale=put(fields["scale"], s_sh),
        ))
    return tree
