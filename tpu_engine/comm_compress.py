"""ZeRO++-style quantized & hierarchical collectives for the multislice path.

The GSPMD train step moves full-width values over every link: ZeRO-3 weight
all-gathers carry fp32/bf16 over the ``fsdp`` axis, and the data-parallel
gradient reduction carries fp32 across DCN when ``dcn_data > 1``. ZeRO++
(arXiv:2306.10209) cuts that volume ~4x with three composable mechanisms,
which map directly onto the TPU ICI-vs-DCN bandwidth asymmetry:

- **qwZ** (``comm_quant_weights``): block-quantized int8 weight all-gather.
  Each ZeRO-3 shard is quantized to int8 with per-block absmax scales
  BEFORE the gather, so the ``fsdp`` collective moves 1 byte/element plus
  a small scale sidecar; the full-width weights are reconstructed on every
  device AFTER the gather. Gradients flow to the primary fp32 partition via
  a straight-through estimator whose transpose is the exact ZeRO-3
  reduce-scatter (``psum_scatter`` over ``fsdp``).
- **hpZ** (``comm_secondary_weights``): a secondary int8 parameter replica
  (codes + scales), sharded like the primary partition and refreshed from
  it after each optimizer step. Steady-state forward/backward gathers read
  the pre-quantized secondary store — the quantize work leaves the
  per-microbatch hot path (it would otherwise run once per microbatch per
  remat pass), and in deployments where the primary partition lives in
  host memory or spans slices the gather source stays in device HBM on
  ICI. Gradients still target the primary partition (straight-through).
- **qgZ** (``comm_quant_grads``): hierarchical gradient reduction for
  hybrid meshes. Gradients are first psum-reduced in fp32 WITHIN each
  slice (ICI, cheap), then block-quantized int8 partials are exchanged
  ACROSS slices (DCN, the slow link) and dequantize-summed locally — the
  cross-slice wire carries 1 byte/element instead of 4. Quantization uses
  stochastic rounding so the error is zero-mean and does not bias the
  optimizer (the stateless alternative to error-feedback buffers, which
  would add a persistent fp32 residual per leaf).

Mechanism: the per-microbatch loss/grad computation runs inside ONE
full-manual ``shard_map`` over the whole mesh, so the collectives are
explicit ``jax.lax`` calls whose operand dtype *is* the wire dtype — XLA
cannot fuse a dequantize below an implicit GSPMD gather and silently move
fp32 (observed: constraint-based int8 resharding does exactly that).
Full-manual is also a hard requirement: partial-auto ``shard_map`` with a
real-extent auto axis aborts the SPMD partitioner on the collectives this
module emits, which is why compression requires pipe = sequence = model = 1
(enforced at config/build time — a partitioner abort kills the process).
"""

from __future__ import annotations

import math
import re
from dataclasses import dataclass
from functools import partial
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from tpu_engine.mesh_runtime import BATCH_AXES

# Leaf names whose (>=2-D, fsdp-sharded) tensors ride the quantized gather;
# everything else (norm scales, biases) gathers full-width — those leaves
# are a sliver of the bytes and the most quantization-sensitive.
_QUANT_LEAF_NAMES = ("kernel", "embedding")


# ---------------------------------------------------------------------------
# Blockwise int8 quantization (last-axis blocks, absmax/127 scales)
# ---------------------------------------------------------------------------


def _n_blocks(last: int, block: int) -> int:
    return -(-last // block)


def stochastic_round(y: jax.Array, key: jax.Array) -> jax.Array:
    """Unbiased stochastic rounding: ``floor(y + u)``, ``u ~ U[0,1)`` —
    ``E[result] == y``. The shared rounding helper for the quantized
    collectives here (qgZ) and the quantized training matmuls
    (tpu_engine/quant_train.py): zero-mean error needs no error-feedback
    state."""
    return jnp.floor(y + jax.random.uniform(key, y.shape))


def blockwise_quantize(
    x: jax.Array, block: int, key: Optional[jax.Array] = None
) -> tuple[jax.Array, jax.Array]:
    """Symmetric int8 quantization in blocks of ``block`` along the last
    axis. Returns ``(codes, scales)`` where ``codes`` is int8 with the last
    axis PADDED up to a whole number of blocks (``n_blocks * block``) and
    ``scales`` is fp32 with shape ``x.shape[:-1] + (n_blocks,)``.

    ``key`` switches round-to-nearest to stochastic rounding
    (``floor(v + u)``, ``u ~ U[0,1)``) — unbiased: ``E[deq] == x``.

    The padded-codes convention is deliberate: a shard gathered over a
    mesh axis concatenates per-shard block grids, and keeping each shard's
    grid whole means the gathered codes always reshape cleanly to
    ``(..., n_blocks, block)`` regardless of the shard extent.
    """
    last = x.shape[-1]
    nb = _n_blocks(last, block)
    pad = nb * block - last
    xf = x.astype(jnp.float32)
    if pad:
        xf = jnp.pad(xf, [(0, 0)] * (x.ndim - 1) + [(0, pad)])
    xb = xf.reshape(*x.shape[:-1], nb, block)
    absmax = jnp.max(jnp.abs(xb), axis=-1)
    scales = jnp.maximum(absmax, 1e-30) / 127.0
    y = xb / scales[..., None]
    if key is not None:
        y = stochastic_round(y, key)
    else:
        y = jnp.round(y)
    codes = jnp.clip(y, -127.0, 127.0).astype(jnp.int8)
    return codes.reshape(*x.shape[:-1], nb * block), scales


def blockwise_dequantize(
    codes: jax.Array, scales: jax.Array, block: int,
    last: Optional[int] = None, dtype=jnp.float32,
) -> jax.Array:
    """Inverse of :func:`blockwise_quantize`: padded int8 codes + fp32
    scales → float array, trimmed to ``last`` elements on the final axis
    (default: the codes' own padded extent)."""
    nb = codes.shape[-1] // block
    cb = codes.astype(jnp.float32).reshape(*codes.shape[:-1], nb, block)
    out = (cb * scales[..., None]).reshape(*codes.shape[:-1], nb * block)
    if last is not None and last != out.shape[-1]:
        out = out[..., :last]
    return out.astype(dtype)


def _dequantize_gathered(
    codes_g: jax.Array, scales_g: jax.Array, *, gather_dim: int, block: int,
    shard_last: int, global_last: int, dtype,
) -> jax.Array:
    """Dequantize codes that were tile-gathered along ``gather_dim``.

    When the gather dim IS the last axis, the gathered codes interleave
    per-shard padding (each shard contributed its own whole block grid):
    dequantize per segment, trim each segment to the shard's true extent,
    and re-merge. Any other gather dim leaves block grids untouched.
    """
    ndim = codes_g.ndim
    if gather_dim != ndim - 1:
        return blockwise_dequantize(
            codes_g, scales_g, block, last=global_last, dtype=dtype
        )
    n_shards = global_last // shard_last
    seg = codes_g.shape[-1] // n_shards  # per-shard padded extent
    full = blockwise_dequantize(codes_g, scales_g, block, dtype=dtype)
    full = full.reshape(*full.shape[:-1], n_shards, seg)[..., :shard_last]
    return full.reshape(*full.shape[:-2], n_shards * shard_last)


# ---------------------------------------------------------------------------
# Hybrid-mesh replica groups (data axis = dcn_data outer blocks of slices)
# ---------------------------------------------------------------------------


def data_slice_groups(
    data_size: int, dcn_data: int
) -> tuple[list[list[int]], list[list[int]]]:
    """(intra-slice, cross-slice) ``axis_index_groups`` over the data axis.

    The mesh lays whole slices as the outer blocks of the data axis
    (``mesh_runtime.build_mesh``), so data indices ``[s*k, (s+1)*k)`` share
    slice ``s`` (``k = data/dcn``). Intra groups reduce over ICI; cross
    groups connect the same intra-slice position across slices (DCN).
    """
    if data_size % dcn_data != 0:
        raise ValueError(
            f"data axis {data_size} not divisible by dcn_data={dcn_data}"
        )
    per = data_size // dcn_data
    intra = [list(range(s * per, (s + 1) * per)) for s in range(dcn_data)]
    cross = [[s * per + i for s in range(dcn_data)] for i in range(per)]
    return intra, cross


# ---------------------------------------------------------------------------
# Per-leaf metadata
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class LeafPlan:
    """How one parameter leaf moves through the compressed step."""

    fsdp_dim: Optional[int]  # index of "fsdp" in the leaf's PartitionSpec
    quantize: bool           # ride the int8 gather (qwZ/hpZ)
    global_last: int         # full extent of the leaf's final axis
    shard_last: int          # per-shard extent of the final axis


def _leaf_name(path) -> str:
    last = path[-1]
    return getattr(last, "key", getattr(last, "name", str(last)))


def build_leaf_plans(
    pspecs: Any, abs_params: Any, fsdp_size: int, quant_weights: bool
) -> Any:
    """A :class:`LeafPlan` tree aligned with the params tree."""

    def plan(path, spec, leaf):
        parts = tuple(spec)
        fsdp_dim = parts.index("fsdp") if "fsdp" in parts else None
        shape = tuple(leaf.shape)
        for d, ax in enumerate(parts):
            if ax is None:
                continue
            # fsdp is the only >1 manual axis params shard over here
            # (pipe/sequence/model are forced to 1); uneven shards would
            # make shard_map reject the spec with an opaque error.
            if ax == "fsdp" and shape[d] % fsdp_size != 0:
                raise ValueError(
                    f"comm compression: leaf {jax.tree_util.keystr(path)} "
                    f"dim {d} ({shape[d]}) is not divisible by the fsdp "
                    f"axis size {fsdp_size}"
                )
        shard_last = shape[-1]
        if fsdp_dim == len(shape) - 1:
            shard_last = shape[-1] // fsdp_size
        quantize = (
            quant_weights
            and fsdp_dim is not None
            and len(shape) >= 2
            and _leaf_name(path) in _QUANT_LEAF_NAMES
        )
        return LeafPlan(fsdp_dim, quantize, shape[-1], shard_last)

    flat_specs, treedef = jax.tree_util.tree_flatten_with_path(
        pspecs, is_leaf=lambda x: isinstance(x, P)
    )
    flat_abs = jax.tree_util.tree_leaves(abs_params)
    return jax.tree_util.tree_unflatten(
        treedef,
        [plan(p, s, a) for (p, s), a in zip(flat_specs, flat_abs)],
    )


# ---------------------------------------------------------------------------
# Gather primitives (inside the full-manual shard_map region)
# ---------------------------------------------------------------------------


def _qwz_gather(shard, plan: LeafPlan, block: int, dtype):
    """Quantize-gather-dequantize over ``fsdp`` with a straight-through
    backward: the cotangent of the full weight reduce-scatters back to the
    primary shard — exactly the ZeRO-3 gradient collective."""

    @jax.custom_vjp
    def gather(x):
        codes, scales = blockwise_quantize(x, block)
        codes_g = jax.lax.all_gather(
            codes, "fsdp", axis=plan.fsdp_dim, tiled=True
        )
        scales_g = jax.lax.all_gather(
            scales, "fsdp", axis=plan.fsdp_dim, tiled=True
        )
        return _dequantize_gathered(
            codes_g, scales_g, gather_dim=plan.fsdp_dim, block=block,
            shard_last=plan.shard_last, global_last=plan.global_last,
            dtype=dtype,
        )

    def fwd(x):
        return gather(x), None

    def bwd(_, ct):
        g = jax.lax.psum_scatter(
            ct.astype(jnp.float32), "fsdp",
            scatter_dimension=plan.fsdp_dim, tiled=True,
        )
        return (g,)

    gather.defvjp(fwd, bwd)
    return gather(shard)


def _hpz_gather(shard, codes, scales, plan: LeafPlan, block: int, dtype):
    """qwZ gather reading the pre-quantized SECONDARY store (hpZ): the
    forward never touches the primary shard (and never re-quantizes), but
    the straight-through backward still routes the cotangent to it. The
    int8 codes/scales are closed over, not primal inputs — they carry no
    gradient by construction."""
    codes = jax.lax.stop_gradient(codes)
    scales = jax.lax.stop_gradient(scales)

    @jax.custom_vjp
    def gather(x):
        codes_g = jax.lax.all_gather(
            codes, "fsdp", axis=plan.fsdp_dim, tiled=True
        )
        scales_g = jax.lax.all_gather(
            scales, "fsdp", axis=plan.fsdp_dim, tiled=True
        )
        return _dequantize_gathered(
            codes_g, scales_g, gather_dim=plan.fsdp_dim, block=block,
            shard_last=plan.shard_last, global_last=plan.global_last,
            dtype=dtype,
        )

    def fwd(x):
        return gather(x), None

    def bwd(_, ct):
        g = jax.lax.psum_scatter(
            ct.astype(jnp.float32), "fsdp",
            scatter_dimension=plan.fsdp_dim, tiled=True,
        )
        return (g,)

    gather.defvjp(fwd, bwd)
    return gather(shard)


def _fp_gather(shard, plan: LeafPlan):
    """Full-width gather over ``fsdp`` for non-quantized sharded leaves.
    Same custom_vjp structure as the quantized path so every leaf's
    backward collective is the explicit psum_scatter."""

    @jax.custom_vjp
    def gather(x):
        return jax.lax.all_gather(x, "fsdp", axis=plan.fsdp_dim, tiled=True)

    def fwd(x):
        return gather(x), None

    def bwd(_, ct):
        g = jax.lax.psum_scatter(
            ct.astype(jnp.float32), "fsdp",
            scatter_dimension=plan.fsdp_dim, tiled=True,
        )
        return (g,)

    gather.defvjp(fwd, bwd)
    return gather(shard)


# ---------------------------------------------------------------------------
# The compression context: compressed grad fn + hpZ refresh
# ---------------------------------------------------------------------------


@dataclass
class CommCompression:
    """Bound compressed-communication step pieces for one train program.

    ``accumulate(params, hpz, batch, key)`` replaces
    ``train.accumulate_grads`` (same contract: summed loss, summed fp32
    grads at the ZeRO-3 grad shardings). ``refresh(params)`` produces the
    hpZ secondary store (None when hpZ is off); ``hpz_pspecs`` its
    PartitionSpec tree for the state shardings.
    """

    quant_weights: bool
    secondary_weights: bool
    quant_grads: bool
    block_size: int
    accumulate: Callable[..., tuple[jax.Array, Any]]
    refresh: Optional[Callable[[Any], Any]]
    hpz_pspecs: Optional[dict[str, Any]]


def enabled(cfg) -> bool:
    """True when any comm-compression mechanism is on for ``cfg``."""
    return bool(
        cfg.comm_quant_weights
        or cfg.comm_secondary_weights
        or cfg.comm_quant_grads
    )


def validate_runtime(cfg, runtime, model_cfg, *, attn_mesh) -> None:
    """Runtime-shaped rejections the config validators cannot see.

    These MUST fail at build time: the full-manual shard_map region cannot
    contain a second manual region (the flash/ring/ulysses attention
    kernels) and cannot leave a real-extent axis in auto mode — the SPMD
    partitioner hard-aborts the process on that combination rather than
    raising.
    """
    sizes = runtime.axis_sizes
    for ax in ("pipe", "sequence", "model"):
        if sizes[ax] > 1:
            raise ValueError(
                f"comm compression requires a mesh with {ax}=1 (got "
                f"{sizes[ax]}): the quantized collectives run in a "
                "full-manual shard_map over (data, fsdp) only"
            )
    if attn_mesh is not None:
        raise ValueError(
            "comm compression requires attention_impl='xla' (the "
            "flash/ring/ulysses kernels are shard_map regions and cannot "
            "nest inside the compression region)"
        )
    if model_cfg.is_moe:
        raise ValueError(
            "comm compression does not support MoE models (the router aux "
            "loss is a batch mean whose per-shard decomposition differs "
            "from the global mean)"
        )


def build(
    *,
    mesh: Mesh,
    loss_fn: Callable[..., jax.Array],
    pspecs: Any,
    abs_params: Any,
    grad_sh: Any,
    data_size: int,
    fsdp_size: int,
    dcn_data: int,
    quant_weights: bool,
    secondary_weights: bool,
    quant_grads: bool,
    block_size: int,
    dtype=jnp.float32,
) -> CommCompression:
    """Assemble the compressed gradient path for one train program.

    ``loss_fn(params, tokens, include_aux, denom=..., aux_weight=...)`` is
    the per-microbatch loss; inside the manual region it sees locally-
    sharded tokens and FULL (gathered) params, and returns this device's
    loss contribution (sums over local rows / the global denom) — summing
    over devices reproduces the GSPMD objective exactly.
    """
    plans = build_leaf_plans(pspecs, abs_params, fsdp_size, quant_weights)
    intra_groups, cross_groups = data_slice_groups(data_size, dcn_data)
    block = block_size
    n_leaves = len(jax.tree_util.tree_leaves(abs_params))

    def gather_full(shard, codes, scales, plan):
        if plan.quantize and secondary_weights:
            return _hpz_gather(shard, codes, scales, plan, block, dtype)
        if plan.quantize:
            return _qwz_gather(shard, plan, block, dtype)
        if plan.fsdp_dim is not None:
            return _fp_gather(shard, plan)
        return shard  # replicated over fsdp; grads reduced post-hoc

    def reduce_grad(g, plan, key):
        # fsdp-sharded leaves arrive fsdp-reduced (the gathers' backward
        # psum_scatter); replicated leaves hold per-device partials.
        if plan.fsdp_dim is None and fsdp_size > 1:
            g = jax.lax.psum(g, "fsdp")
        if data_size == 1:
            return g
        if not quant_grads:
            return jax.lax.psum(g, "data")
        # qgZ: fp32 within the slice (ICI), int8 partials across slices
        # (DCN), dequantize-sum locally. With dcn_data == 1 there is no
        # cross-slice link to compress — plain fp32 psum (documented).
        if dcn_data > 1:
            if data_size > dcn_data:
                g = jax.lax.psum(g, "data", axis_index_groups=intra_groups)
            codes, scales = blockwise_quantize(g, block, key=key)
            codes_x = jax.lax.all_gather(
                codes, "data", axis_index_groups=cross_groups
            )
            scales_x = jax.lax.all_gather(
                scales, "data", axis_index_groups=cross_groups
            )
            parts = blockwise_dequantize(
                codes_x, scales_x, block, last=g.shape[-1]
            )
            return jnp.sum(parts, axis=0)
        return jax.lax.psum(g, "data")

    def body(shards, hpz, tokens, denom, key):
        codes_tree = hpz["codes"] if secondary_weights else plans
        scales_tree = hpz["scales"] if secondary_weights else plans

        def local_loss(shards_):
            full = jax.tree_util.tree_map(
                gather_full, shards_, codes_tree, scales_tree, plans,
                is_leaf=lambda x: isinstance(x, LeafPlan),
            ) if secondary_weights else jax.tree_util.tree_map(
                lambda s, p: gather_full(s, None, None, p), shards_, plans,
                is_leaf=lambda x: isinstance(x, LeafPlan),
            )
            return loss_fn(full, tokens, True, denom=denom)

        loss, grads = jax.value_and_grad(local_loss)(shards)
        leaves, treedef = jax.tree_util.tree_flatten(grads)
        plan_leaves = jax.tree_util.tree_leaves(
            plans, is_leaf=lambda x: isinstance(x, LeafPlan)
        )
        keys = jax.random.split(key, len(leaves))
        reduced = [
            reduce_grad(g, p, k)
            for g, p, k in zip(leaves, plan_leaves, keys)
        ]
        grads = jax.tree_util.tree_unflatten(treedef, reduced)
        return jax.lax.psum(loss, ("data", "fsdp")), grads

    spec_trees = _hpz_spec_trees(pspecs, plans) if secondary_weights else None
    hpz_in_spec = (
        {"codes": spec_trees["codes"], "scales": spec_trees["scales"]}
        if secondary_weights
        else P()  # placeholder leaf for the empty {} pytree
    )
    sm_grad = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(pspecs, hpz_in_spec, P(BATCH_AXES), P(), P()),
        out_specs=(P(), pspecs),
        check_vma=False,
    )

    def accumulate(params, hpz, batch, key):
        """Drop-in for ``train.accumulate_grads``: scan the microbatches
        through the compressed grad fn, summing loss and fp32 grads."""
        accum = batch.shape[0]
        denom = jnp.maximum(
            jnp.sum((batch[:, :, 1:] >= 0).astype(jnp.float32)), 1.0
        )
        if hpz is None:
            hpz = {}

        def accum_body(carry, xs):
            loss_acc, grad_acc = carry
            tokens, k = xs
            loss, grads = sm_grad(params, hpz, tokens, denom, k)
            grad_acc = jax.tree.map(
                lambda a, g: a + g.astype(jnp.float32), grad_acc, grads
            )
            return (loss_acc + loss, grad_acc), None

        zero_grads = jax.tree.map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params
        )
        zero_grads = jax.lax.with_sharding_constraint(zero_grads, grad_sh)
        keys = jax.random.split(key, accum)
        (loss, grad_sum), _ = jax.lax.scan(
            accum_body, (jnp.zeros((), jnp.float32), zero_grads),
            (batch, keys),
        )
        return loss, grad_sum

    refresh = None
    hpz_pspecs = None
    if secondary_weights:
        hpz_pspecs = spec_trees

        def refresh_body(shards):
            def q(s, plan):
                if not plan.quantize:
                    return None
                return blockwise_quantize(s, block)

            pairs = jax.tree_util.tree_map(
                q, shards, plans, is_leaf=lambda x: isinstance(x, LeafPlan)
            )
            codes = jax.tree_util.tree_map(
                lambda pr: pr[0], pairs,
                is_leaf=lambda x: isinstance(x, tuple),
            )
            scales = jax.tree_util.tree_map(
                lambda pr: pr[1], pairs,
                is_leaf=lambda x: isinstance(x, tuple),
            )
            return {"codes": codes, "scales": scales}

        sm_refresh = jax.shard_map(
            refresh_body,
            mesh=mesh,
            in_specs=(pspecs,),
            out_specs={"codes": spec_trees["codes"],
                       "scales": spec_trees["scales"]},
            check_vma=False,
        )

        def refresh(params):
            """Re-quantize the secondary int8 store from the (updated)
            primary partition — runs once per optimizer step."""
            return sm_refresh(params)

    return CommCompression(
        quant_weights=quant_weights,
        secondary_weights=secondary_weights,
        quant_grads=quant_grads,
        block_size=block_size,
        accumulate=accumulate,
        refresh=refresh,
        hpz_pspecs=hpz_pspecs,
    )


def _hpz_spec_trees(pspecs: Any, plans: Any) -> dict[str, Any]:
    """PartitionSpec trees for the hpZ store: quantized leaves keep their
    param spec (codes AND scales concatenate along the same mesh axes);
    non-quantized leaves are dropped (None — pruned from the pytree)."""

    def keep(spec, plan):
        return spec if plan.quantize else None

    specs = jax.tree_util.tree_map(
        keep, pspecs, plans,
        is_leaf=lambda x: isinstance(x, (P, LeafPlan)),
    )
    return {"codes": specs, "scales": specs}


# ---------------------------------------------------------------------------
# HLO collective accounting (benchmarks + tests)
# ---------------------------------------------------------------------------

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
}

_COLLECTIVE_RE = re.compile(
    r"=\s+(?:\([^)]*\)|(?P<dtype>\w+)\[(?P<shape>[\d,]*)\][^ ]*)\s+"
    r"(?P<op>all-gather|all-reduce|reduce-scatter|all-to-all|"
    r"collective-permute)(?:-start)?\("
)
_TUPLE_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_GROUPS_RE = re.compile(
    r"replica_groups=(?:\{(?P<explicit>[^}]*(?:\},\{[^}]*)*)\}\}|"
    r"\[(?P<iota_dims>[\d,]+)\]<=\[(?P<iota_reshape>[\d,]+)\]"
    r"(?:T\((?P<iota_perm>[\d,]+)\))?)"
)


def _parse_groups(line: str, n_devices: int) -> list[list[int]]:
    """Replica groups from an HLO instruction line — both the explicit
    ``{{0,1},{2,3}}`` form and the iota ``[2,4]<=[8]`` / ``T(...)`` form."""
    m = _GROUPS_RE.search(line)
    if not m:
        return [list(range(n_devices))]
    if m.group("explicit") is not None:
        raw = m.group("explicit")
        return [
            [int(x) for x in grp.split(",") if x.strip() != ""]
            for grp in raw.replace("{", "").split("},")
        ]
    import numpy as np

    dims = [int(x) for x in m.group("iota_dims").split(",")]
    reshape = [int(x) for x in m.group("iota_reshape").split(",")]
    ids = np.arange(int(np.prod(reshape))).reshape(reshape)
    if m.group("iota_perm"):
        ids = ids.transpose([int(x) for x in m.group("iota_perm").split(",")])
    ids = ids.reshape(-1, dims[-1]) if len(dims) > 1 else ids.reshape(1, -1)
    # v2 iota semantics: reshape the (possibly transposed) iota to `dims`;
    # the final dim indexes within a group.
    ids = ids.flatten().reshape(dims)
    return ids.reshape(-1, dims[-1]).tolist()


def _payload_bytes(line: str) -> int:
    """Total element bytes of the instruction's result: everything between
    ``=`` and the collective's name, so a tuple result (the all-reduces XLA
    combines into one) counts every member."""
    m = _COLLECTIVE_RE.search(line)
    head = line[m.start():m.start("op")] if m else line.split("(", 1)[0]
    total = 0
    for dtype, shape in _TUPLE_SHAPE_RE.findall(head):
        if dtype not in _DTYPE_BYTES:
            continue
        elems = 1
        for d in shape.split(","):
            if d.strip():
                elems *= int(d)
        total += elems * _DTYPE_BYTES[dtype]
    return total


def slice_of_partition(mesh_shape: dict[str, int], dcn_data: int) -> list[int]:
    """partition-id → slice-id for a hybrid mesh: the partition order is
    the row-major flattening of the mesh device array, whose outer data
    blocks are whole slices."""
    total = 1
    for v in mesh_shape.values():
        total *= v
    data = mesh_shape.get("data", 1)
    inner = total // data
    per_slice_data = data // dcn_data
    return [
        (p // inner) // per_slice_data if per_slice_data else 0
        for p in range(total)
    ]


def collective_stats(
    hlo_text: str, slice_of: Optional[list[int]] = None
) -> dict[str, Any]:
    """Wire-byte accounting over an HLO module's collectives.

    Uses the standard ring cost model per participant group of size g:
    all-gather / reduce-scatter / all-to-all move (g-1)/g of the payload,
    all-reduce 2(g-1)/g, collective-permute the full payload. A collective
    whose replica group spans devices on different slices (``slice_of``)
    is charged to ``cross_slice_bytes``; with no slice map everything is
    intra-slice.
    """
    n_devices = len(slice_of) if slice_of else 1
    ops = []
    total = 0.0
    cross = 0.0
    for line in hlo_text.splitlines():
        m = _COLLECTIVE_RE.search(line)
        if not m or "-done" in line.split("=", 1)[-1][:40]:
            continue
        op = m.group("op")
        payload = _payload_bytes(line)
        groups = _parse_groups(line, n_devices)
        g = max(len(grp) for grp in groups) if groups else 1
        if op == "all-reduce":
            wire = payload * 2 * (g - 1) / max(g, 1)
        elif op == "collective-permute":
            wire = float(payload)
        else:
            wire = payload * (g - 1) / max(g, 1)
        crossing = False
        if slice_of:
            for grp in groups:
                slices = {slice_of[d] for d in grp if d < len(slice_of)}
                if len(slices) > 1:
                    crossing = True
                    break
        total += wire
        if crossing:
            cross += wire
        ops.append({
            "op": op, "bytes": int(wire), "payload_bytes": payload,
            "group_size": g, "cross_slice": crossing,
            "dtype": m.group("dtype"),  # None for a tuple result
        })
    return {
        "total_wire_bytes": int(total),
        "cross_slice_bytes": int(cross),
        "collectives": ops,
    }


def expected_volume_factors(block_size: int) -> dict[str, float]:
    """Analytic per-element wire reduction: int8 codes + fp32 per-block
    scales versus fp32 full-width (the number the docs/plan report)."""
    f = 4.0 / (1.0 + 4.0 / block_size)
    return {
        "weight_gather": f,
        "grad_cross_slice": f,
    }
