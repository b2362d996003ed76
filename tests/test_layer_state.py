"""The seam of a slot's per-layer state (``tpu_engine/layer_state.py``): one
table of layer kinds, one tree ``{kind: {leaf: array}}`` in both caches, and
the cache manager, the estimator and the sharding reading the table.

- (a) what ``init_slot_cache`` allocates is the table's leaves, shapes and
  dtypes, and ``estimate_serving_hbm`` prices exactly those bytes;
- (b) ``estimate_serving_hbm`` for the benchmark's serving configurations and
  their variants returns the numbers of the commit before the table existed
  (PR 28's tree, literals here): admission rides on them;
- (c) insert and reset of a row, per kind: positional or whole;
- (d) a third kind is ONE ENTRY of the table: a toy whole kind added in a
  fixture is allocated, inserted, reset, sharded, priced and refused with no
  edit to ``serving.py``, ``hbm_estimate.py`` or ``disagg.py``;
- (e) a positional leaf may hold a row per ``stride`` lanes (a toy kind with a
  leaf of one row per 4 lanes beside a leaf of one per lane): ``n_lanes`` is
  the longest leaf's, and insert, slice and paste cut each leaf at its own
  stride.
"""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks", "onchip")
for p in (ROOT, BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)

from harness import manifest, program  # noqa: E402

from tpu_engine import layer_state, serving  # noqa: E402
from tpu_engine.generate import KVCache, init_cache, ring_lanes  # noqa: E402
from tpu_engine.hbm_estimate import estimate_serving_hbm  # noqa: E402
from tpu_engine.models import transformer as tfm  # noqa: E402
from tpu_engine.sharding import Precision  # noqa: E402

GIB = 2**30
BF16 = jnp.bfloat16


def _benchmark_config(name):
    entry = {c["name"]: c for c in manifest.load_manifest()["configs"]}[name]
    with open(os.path.join(manifest.ROOT, entry["file"])) as f:
        return json.load(f)


@pytest.fixture
def registry(monkeypatch):
    """``MODEL_CONFIGS`` as a copy this test may add to."""
    monkeypatch.setattr(tfm, "MODEL_CONFIGS", dict(tfm.MODEL_CONFIGS))
    return tfm.MODEL_CONFIGS


def _stack(case, registry):
    """(ModelConfig, kv_quant) of a named small stack."""
    tiny = tfm.MODEL_CONFIGS["gpt-tiny"]
    if case == "hybrid-tiny":
        cfg = _benchmark_config("granite-4.0-h-micro-1chip-serve")
        mc = program.model_config({**cfg, **cfg["rehearsal"]}, "hybrid-tiny")
    elif case == "power-tiny":  # every mixer a power-retention layer: a stack with NO positional kind
        cfg = _benchmark_config("brumby-14b-1chip-serve")
        mc = program.model_config({**cfg, **cfg["rehearsal"]}, "power-tiny")
    elif case == "windowed":
        mc = tiny.with_(name="windowed", sliding_window=64)
    else:
        mc = tiny.with_(name=case)
    registry[mc.name] = mc
    return mc, case == "kv_quant"


def _nbytes(a):
    return a.size * jnp.dtype(a.dtype).itemsize


def _split_nbytes(layers):
    positional = sum(_nbytes(a) for kind, leaves in layers.items() for a in leaves.values()
                     if layer_state.LAYER_KINDS[kind].positional)
    return positional, sum(_nbytes(a) for a in jax.tree.leaves(layers)) - positional


# (a) the allocation is the table's, and the estimator prices it -------------


@pytest.mark.parametrize("case", ["gpt-tiny", "windowed", "kv_quant", "hybrid-tiny", "power-tiny"])
def test_the_pool_is_the_tables_leaves_and_the_estimate_prices_its_bytes(case, registry):
    mc, kv_quant = _stack(case, registry)
    slots, max_len, chunk = 4096, 4096, 32  # shapes only: big enough for 1e-4 GiB to mean something
    pool = jax.eval_shape(lambda: serving.init_slot_cache(
        mc, slots, max_len, BF16, prefill_chunk=chunk, kv_quant=kv_quant))
    lanes = ring_lanes(mc, max_len, chunk)
    held = 0 if case == "power-tiny" else lanes  # a stack with no positional kind holds no lane
    assert (lanes < max_len) == pool.ring == (case == "windowed") and pool.n_lanes == held
    counts = layer_state.layer_counts(mc)
    assert counts == ({"ssm": mc.n_ssm_layers, "attn": mc.n_attn_layers} if case == "hybrid-tiny"
                      else {"power": mc.n_layers} if case == "power-tiny" else {"attn": mc.n_layers})
    table = layer_state.leaf_specs(mc, counts, lanes, BF16, kv_quant)
    assert set(pool.layers) == set(table)  # a kind the stack has not is absent
    for kind, leaves in table.items():
        assert set(pool.layers[kind]) == set(leaves)
        for name, leaf in leaves.items():
            a = pool.layers[kind][name]
            assert a.shape == (counts[kind], slots) + leaf.shape and a.dtype == jnp.dtype(leaf.dtype), (kind, name)
    whole_kinds = case in ("hybrid-tiny", "power-tiny")
    assert pool.quantized == kv_quant and pool.recurrent == whole_kinds
    # the same table allocates the one-row ingestion cache
    c1 = jax.eval_shape(lambda: init_cache(mc, 1, max_len, BF16, max_chunk=chunk, kv_quant=kv_quant))
    assert jax.tree.structure(c1.layers) == jax.tree.structure(pool.layers) and c1.max_len == held

    positional, whole = _split_nbytes(pool.layers)
    priced = layer_state.state_bytes(mc, slots, lanes, BF16, kv_quant)
    assert layer_state.split_bytes(priced) == (positional, whole) and pool.recurrent_state_bytes == whole
    est = estimate_serving_hbm(mc.name, slots, max_len, prefill_chunk=chunk, kv_quant=kv_quant)
    assert est.kv_pool_gib == round(positional / GIB, 4) and est.recurrent_state_gib == round(whole / GIB, 4)
    assert (whole > 0) == whole_kinds and (positional > 0) == (case != "power-tiny")


# (b) the estimate's numbers, pinned at the parent commit --------------------

M, X, G = "mistral-7b-1chip-serve", "mixtral-8x7b-1chip-serve", "granite-4.0-h-micro-1chip-serve"
# (params, working, logits, device_total, kv_pool, recurrent_state) GiB and the
# gang: estimate_serving_hbm at PR 28's tree, each configuration at its
# ``program`` sizes plus the named change.
PARENT = {
    "mistral": (M, {}, (3.7384, 0.0215, 0.0019, 4.7618, 1.0, 0.0, 1)),
    "mixtral": (X, {}, (3.1915, 0.0215, 0.0019, 3.3399, 0.125, 0.0, 1)),
    "granite": (G, {}, (3.1636, 0.0117, 0.012, 4.5763, 0.25, 1.139, 1)),
    "mistral-kv_quant": (M, dict(kv_quant=True), (3.7384, 0.0215, 0.0019, 4.2774, 0.5156, 0.0, 1)),
    "mistral-tp2": (M, dict(tensor_parallel=2), (1.8692, 0.0107, 0.001, 2.3809, 0.5, 0.0, 2)),
    "mistral-tp3-kv-heads-replicated": (M, dict(tensor_parallel=3), (1.2461, 0.0072, 0.0006, 2.2539, 1.0, 0.0, 3)),
    "mixtral-prefill-pool": (X, dict(pool_role="prefill", inflight_handoffs=4),
                             (3.1915, 0.043, 0.0005, 3.2662, 0.0312, 0.0, 1)),
    "mistral-prefix-cache": (M, dict(prefix_cache_tokens=4096), (3.7384, 0.0215, 0.0019, 4.8868, 1.125, 0.0, 1)),
    "mistral-prefix-cache-kv_quant-tp2": (M, dict(prefix_cache_tokens=4096, kv_quant=True, tensor_parallel=2),
                                          (1.8692, 0.0107, 0.001, 2.1709, 0.29, 0.0, 2)),
    "granite-tp2": (G, dict(tensor_parallel=2), (1.5818, 0.0059, 0.006, 2.8577, 0.125, 1.139, 2)),
    "granite-float32": (G, dict(compute_dtype=Precision.FP32), (6.3273, 0.0234, 0.012, 8.0157, 0.5, 1.153, 1)),
    "mistral-with-a-draft": (M, dict(draft_model_name="gpt-tiny"), (3.7384, 0.0215, 0.0019, 4.7777, 1.0, 0.0, 1)),
}


@pytest.mark.parametrize("case", sorted(PARENT))
def test_the_serving_estimate_is_the_parents_to_the_last_digit(case, registry):
    name, change, want = PARENT[case]
    config = _benchmark_config(name)
    program.model_config(config, name)
    p = config["program"]
    args = dict(tensor_parallel=p["tensor_parallel"], compute_dtype=Precision[p["compute_dtype"]],
                kv_quant=p["kv_quant"], prefill_chunk=p["prefill_chunk"],
                prefix_cache_tokens=p["prefix_cache_tokens"])
    est = estimate_serving_hbm(name, p["max_slots"], p["max_len"], **{**args, **change})
    got = (est.params_gib, est.working_gib, est.logits_gib, est.device_total_gib, est.kv_pool_gib,
           est.recurrent_state_gib, est.gang_devices)
    assert got == want
    assert any("recurrent state: 18 Mamba-2 layers" in n for n in est.notes) == (name == G)
    assert any("kv pool replicated" in n for n in est.notes) == ("replicated" in case)
    assert any("int8 codes" in n for n in est.notes) == bool(change.get("kv_quant"))


# (c) insert and reset of a row, per kind ------------------------------------


def _random_like(tree, seed):
    leaves, treedef = jax.tree.flatten(tree)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    fill = [jax.random.randint(k, a.shape, -100, 100).astype(a.dtype) if a.dtype == jnp.int8
            else (1.0 + jax.random.uniform(k, a.shape, jnp.float32)).astype(a.dtype) for k, a in zip(keys, leaves)]
    return jax.tree.unflatten(treedef, fill)


@pytest.mark.parametrize("case, kind", [("gpt-tiny", "attn"), ("kv_quant", "attn"), ("windowed", "attn"),
                                        ("hybrid-tiny", "attn"), ("hybrid-tiny", "ssm"), ("power-tiny", "power")])
def test_insert_then_reset_of_a_row(case, kind, registry):
    mc, kv_quant = _stack(case, registry)
    slots, chunk, slot, n = 3, 16, 1, 21
    max_len = 128 if case == "windowed" else 64  # windowed: 79 lanes, a ring
    empty = serving.init_slot_cache(mc, slots, max_len, jnp.float32, prefill_chunk=chunk, kv_quant=kv_quant)
    pool = serving.SlotCache(layers=_random_like(empty.layers, 1), lengths=jnp.asarray([5, 9, 7], jnp.int32),
                             pos=empty.pos, ring=empty.ring)
    # the ingestion cache: a ring's is lane-aligned with the pool, a flat one's is a bucket of the prompt
    c1 = init_cache(mc, 1, max_len if pool.ring else 32, jnp.float32,
                    max_chunk=chunk if pool.ring else None, kv_quant=kv_quant)
    c1 = KVCache(layers=_random_like(c1.layers, 2), pos=jnp.arange(c1.max_len, dtype=jnp.int32),
                 length=jnp.asarray(n, jnp.int32), ring=c1.ring)
    M = c1.max_len
    positional = layer_state.LAYER_KINDS[kind].positional
    assert positional == (kind == "attn") and (M > 0) == (case != "power-tiny")

    got = serving._insert_prefill(pool, c1, jnp.int32(slot), jnp.int32(n), pool.ring)
    assert got.lengths.tolist() == [5, n, 7]
    for name, a in got.layers[kind].items():
        was, new = np.asarray(pool.layers[kind][name]), np.asarray(a)
        others = [r for r in range(slots) if r != slot]
        assert (new[:, others] == was[:, others]).all(), name  # other rows untouched
        src = np.asarray(c1.layers[kind][name].astype(a.dtype))[:, 0]
        if positional:
            assert (new[:, slot, :M] == src).all() and (new[:, slot, M:] == was[:, slot, M:]).all(), name
        else:
            assert (new[:, slot] == src).all(), name  # the row's whole state is the inserted one
    if pool.ring:
        assert (np.asarray(got.pos[slot]) == np.arange(M)).all()

    freed = serving._reset_slot(got, slot)
    assert freed.lengths.tolist() == [5, 0, 7]  # a positional kind's row is hidden by its length
    for name, a in freed.layers[kind].items():
        was, new = np.asarray(got.layers[kind][name]), np.asarray(a)
        if positional:
            assert (new == was).all(), name
        else:
            assert (new[:, slot] == 0).all() and (np.delete(new, slot, 1) == np.delete(was, slot, 1)).all(), name
    if pool.ring:
        assert (np.asarray(freed.pos[slot]) == -1).all()


# (d) a third kind is one entry ----------------------------------------------

COUNTS = {"attn": 2, "toy": 3}


@pytest.fixture
def toy_kind(monkeypatch):
    """A WHOLE kind that keeps one float32 vector per row: one entry."""
    monkeypatch.setitem(layer_state.LAYER_KINDS, "toy", layer_state.LayerKind(
        positional=False,
        leaves=lambda cfg, lanes, dtype, kv_quant: {"vec": layer_state.Leaf((cfg.d_model,), jnp.float32)}))
    return tfm.MODEL_CONFIGS["gpt-tiny"]


def _toy_pool(cfg, slots=4, lanes=32):
    layers = layer_state.init_layers(cfg, slots, lanes, BF16, counts=COUNTS)
    return serving.SlotCache(layers=_random_like(layers, 3), lengths=jnp.zeros((slots,), jnp.int32))


@pytest.mark.parametrize("what", ["allocation", "insert_and_reset", "sharding", "price", "refusal"])
def test_a_third_kind_is_one_entry(what, toy_kind):
    cfg = toy_kind
    pool = _toy_pool(cfg)
    vec = pool.layers["toy"]["vec"]
    if what == "allocation":
        assert set(pool.layers) == {"attn", "toy"} and set(pool.layers["toy"]) == {"vec"}
        assert vec.shape == (3, 4, cfg.d_model) and vec.dtype == jnp.float32
        assert pool.layers["attn"]["k"].shape == (2, 4, 32, cfg.n_kv_heads * cfg.head_dim)
        assert pool.n_lanes == 32 and pool.recurrent and not pool.quantized
        assert pool.recurrent_state_bytes == vec.nbytes
    elif what == "insert_and_reset":
        c1 = KVCache(layers=_random_like(layer_state.init_layers(cfg, 1, 16, jnp.float32, counts=COUNTS), 4),
                     pos=jnp.arange(16, dtype=jnp.int32), length=jnp.asarray(9, jnp.int32))
        got = jax.jit(serving._insert_prefill, static_argnums=(4,))(pool, c1, jnp.int32(2), jnp.int32(9), False)
        assert (np.asarray(got.layers["toy"]["vec"][:, 2]) == np.asarray(c1.layers["toy"]["vec"][:, 0])).all()
        assert (np.asarray(got.layers["toy"]["vec"][:, :2]) == np.asarray(vec[:, :2])).all()
        want_k = np.asarray(c1.layers["attn"]["k"].astype(BF16)[:, 0])
        assert (np.asarray(got.layers["attn"]["k"][:, 2, :16]) == want_k).all() and got.lengths.tolist() == [0, 0, 9, 0]
        freed = jax.jit(serving._reset_slot)(got, 2)
        assert (np.asarray(freed.layers["toy"]["vec"][:, 2]) == 0).all()
        assert (np.asarray(freed.layers["toy"]["vec"][:, 3]) == np.asarray(vec[:, 3])).all()
        assert (np.asarray(freed.layers["attn"]["k"]) == np.asarray(got.layers["attn"]["k"])).all()
        # what has lanes can be sliced out and pasted; a whole kind has none to give
        assert set(layer_state.slice_lanes(got.layers, 8)) == {"attn"}
    elif what == "sharding":
        mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:4]).reshape(2, 2), ("fsdp", "model"))
        sh = layer_state.cache_shardings(mesh, cfg, pool)
        P = jax.sharding.PartitionSpec
        assert sh.layers["attn"]["k"].spec == P(None, None, None, "model")
        assert sh.layers["toy"]["vec"].spec == P() and sh.lengths.spec == P() and sh.pos is None
        placed = jax.device_put(pool, sh)
        assert placed.layers["toy"]["vec"].sharding.is_fully_replicated
        assert not placed.layers["attn"]["v"].sharding.is_fully_replicated
    elif what == "price":
        priced = layer_state.state_bytes(cfg, 4, 32, BF16, counts=COUNTS)
        assert priced == {"attn": sum(a.nbytes for a in pool.layers["attn"].values()), "toy": vec.nbytes}
        assert layer_state.split_bytes(priced) == (priced["attn"], vec.nbytes)
        halved = layer_state.state_bytes(cfg, 4, 32, BF16, tp=2, counts=COUNTS)
        assert halved == {"attn": priced["attn"] / 2, "toy": vec.nbytes}  # no model_dim: replicated
    else:
        assert layer_state.keeps_whole_state(COUNTS) and not layer_state.keeps_whole_state(["attn"])

        class Stack:  # a stack with the toy kind in its pattern, as layer_runs() would yield it
            name, n_layers, n_ssm_layers = "toy-stack", 5, 0

            def layer_runs(self):
                return (("attn", 0, 2), ("toy", 0, 3))

        with pytest.raises(tfm.RecurrentLayersUnsupported, match="the prompt-prefix cache"):
            tfm.refuse_recurrent(Stack(), "the prompt-prefix cache")
        tfm.refuse_recurrent(cfg, "the prompt-prefix cache")  # keys and values only: admitted


# (e) a positional leaf with a lane stride --------------------------------------

STRIDE = 4
STRIDED_COUNTS = {"strided": 2}


@pytest.fixture
def strided_kind(monkeypatch):
    """A POSITIONAL kind with a leaf of one row per lane and a leaf of one row
    per ``STRIDE`` lanes (as a sparse-attention layer's compressed keys)."""
    monkeypatch.setitem(layer_state.LAYER_KINDS, "strided", layer_state.LayerKind(
        positional=True,
        leaves=lambda cfg, lanes, dtype, kv_quant: {
            "coarse": layer_state.Leaf((lanes // STRIDE, 3), dtype),   # listed first: n_lanes must not take it
            "fine": layer_state.Leaf((lanes, 3), dtype)}))
    return tfm.MODEL_CONFIGS["gpt-tiny"]


@pytest.mark.parametrize("what", ["n_lanes", "insert", "slice", "paste", "price"])
def test_a_positional_leaf_with_a_lane_stride(what, strided_kind):
    cfg = strided_kind
    layers = _random_like(layer_state.init_layers(cfg, 3, 32, jnp.float32, counts=STRIDED_COUNTS), 5)
    fine, coarse = layers["strided"]["fine"], layers["strided"]["coarse"]
    assert fine.shape == (2, 3, 32, 3) and coarse.shape == (2, 3, 8, 3)
    if what == "n_lanes":
        assert layer_state.n_lanes(layers) == 32
        assert layer_state.lane_stride(layers, coarse) == STRIDE and layer_state.lane_stride(layers, fine) == 1
        assert not layer_state.keeps_whole_state(STRIDED_COUNTS) and layer_state.whole_state_bytes(layers) == 0
    elif what == "insert":
        row = _random_like(layer_state.init_layers(cfg, 1, 16, jnp.float32, counts=STRIDED_COUNTS), 6)
        pool = serving.SlotCache(layers=layers, lengths=jnp.zeros((3,), jnp.int32))
        c1 = KVCache(layers=row, pos=jnp.arange(16, dtype=jnp.int32), length=jnp.asarray(13, jnp.int32))
        got = jax.jit(serving._insert_prefill, static_argnums=(4,))(pool, c1, jnp.int32(1), jnp.int32(13), False)
        new = got.layers["strided"]
        assert (np.asarray(new["fine"][:, 1, :16]) == np.asarray(row["strided"]["fine"][:, 0])).all()
        assert (np.asarray(new["coarse"][:, 1, :4]) == np.asarray(row["strided"]["coarse"][:, 0])).all()
        assert (np.asarray(new["fine"][:, 1, 16:]) == np.asarray(fine[:, 1, 16:])).all()
        assert (np.asarray(new["coarse"][:, 1, 4:]) == np.asarray(coarse[:, 1, 4:])).all()
        assert (np.asarray(new["coarse"][:, [0, 2]]) == np.asarray(coarse[:, [0, 2]])).all()
        freed = serving._reset_slot(got, 1)  # positional: the length hides it, nothing is zeroed
        assert (np.asarray(freed.layers["strided"]["coarse"]) == np.asarray(new["coarse"])).all()
    elif what == "slice":
        cut = layer_state.slice_lanes(layers, 16)["strided"]
        assert cut["fine"].shape == (2, 3, 16, 3) and cut["coarse"].shape == (2, 3, 4, 3)
        assert (np.asarray(cut["coarse"]) == np.asarray(coarse[:, :, :4])).all()
        # lanes that do not fill a row of the coarse leaf give it none
        assert layer_state.slice_lanes(layers, 18)["strided"]["coarse"].shape[2] == 4
    elif what == "paste":
        src = _random_like(layer_state.init_layers(cfg, 3, 24, jnp.float32, counts=STRIDED_COUNTS), 7)
        got = layer_state.paste_lanes(layers, src, 16)["strided"]
        assert (np.asarray(got["fine"][:, :, :16]) == np.asarray(src["strided"]["fine"][:, :, :16])).all()
        assert (np.asarray(got["fine"][:, :, 16:]) == np.asarray(fine[:, :, 16:])).all()
        assert (np.asarray(got["coarse"][:, :, :4]) == np.asarray(src["strided"]["coarse"][:, :, :4])).all()
        assert (np.asarray(got["coarse"][:, :, 4:]) == np.asarray(coarse[:, :, 4:])).all()
    else:
        priced = layer_state.state_bytes(cfg, 3, 32, jnp.float32, counts=STRIDED_COUNTS)
        assert priced == {"strided": fine.nbytes + coarse.nbytes}
        assert layer_state.split_bytes(priced) == (fine.nbytes + coarse.nbytes, 0)


# (f) a ring leaf beside a strided leaf ------------------------------------------

RING = 8
RING_COUNTS = {"strided": 2, "ringed": 3}


@pytest.fixture
def ring_beside_strided(strided_kind, monkeypatch):
    """The strided kind and a RING kind in one tree: two leaves shorter than
    the lanes, one because a row of it stands for ``STRIDE`` lanes, the other
    because it holds the newest ``RING`` positions and wraps."""
    monkeypatch.setitem(layer_state.LAYER_KINDS, "ringed", layer_state.LayerKind(
        positional=True, ring=True,
        leaves=lambda cfg, lanes, dtype, kv_quant: {"k": layer_state.Leaf((lanes, 3), dtype)}))
    return strided_kind


@pytest.mark.parametrize("what", ["n_lanes", "insert", "insert_short", "slice", "price"])
def test_a_ring_leaf_beside_a_strided_leaf(what, ring_beside_strided):
    cfg = ring_beside_strided
    layers = _random_like(layer_state.init_layers(cfg, 3, 32, jnp.float32, counts=RING_COUNTS, ring_lanes=RING), 8)
    coarse, ring = layers["strided"]["coarse"], layers["ringed"]["k"]
    assert coarse.shape == (2, 3, 8, 3) and ring.shape == (3, 3, RING, 3)   # as short as each other
    if what == "n_lanes":
        assert layer_state.n_lanes(layers) == 32
        assert layer_state.lane_stride(layers, coarse) == STRIDE                 # a row per 4 lanes
        # the ring's leaf is as short, and no stride: the table says so, not the shape
        assert layer_state.LAYER_KINDS["ringed"].ring and not layer_state.LAYER_KINDS["strided"].ring
        assert layer_state.ring_bytes(layers) == ring.nbytes and layer_state.lane_bytes(layers, "strided") \
            == coarse.nbytes + layers["strided"]["fine"].nbytes
        assert not layer_state.keeps_whole_state(RING_COUNTS)
        # without ring_lanes a ring kind is as long as the others: the ring that never wraps
        assert layer_state.init_layers(cfg, 1, 32, jnp.float32, counts=RING_COUNTS)["ringed"]["k"].shape[2] == 32
    elif what in ("insert", "insert_short"):
        # a staged row of 24 lanes (flat: lane = position) of which 21 (or 5) are real
        n = 21 if what == "insert" else 5
        row = _random_like(layer_state.init_layers(cfg, 1, 24, jnp.float32, counts=RING_COUNTS), 9)
        pool = serving.SlotCache(layers=layers, lengths=jnp.zeros((3,), jnp.int32))
        c1 = KVCache(layers=row, pos=jnp.arange(24, dtype=jnp.int32), length=jnp.asarray(n, jnp.int32))
        got = jax.jit(serving._insert_prefill, static_argnums=(4,))(pool, c1, jnp.int32(2), jnp.int32(n), False)
        held = np.asarray(layer_state.ring_positions(RING, jnp.asarray(n)))
        assert sorted(p for p in held.tolist() if p >= 0) == list(range(max(n - RING, 0), n))
        for m, p in enumerate(held.tolist()):   # lane m holds position p: the newest with p % RING == m
            if p >= 0:
                assert p % RING == m
                assert (np.asarray(got.layers["ringed"]["k"][:, 2, m]) == np.asarray(row["ringed"]["k"][:, 0, p])).all()
        assert (np.asarray(got.layers["ringed"]["k"][:, :2]) == np.asarray(ring[:, :2])).all()
        # the strided leaf beside it is inserted at its stride, as ever
        assert (np.asarray(got.layers["strided"]["coarse"][:, 2, :6]) == np.asarray(row["strided"]["coarse"][:, 0])).all()
        assert (np.asarray(got.layers["strided"]["fine"][:, 2, :24]) == np.asarray(row["strided"]["fine"][:, 0])).all()
    elif what == "slice":
        with pytest.raises(NotImplementedError, match="ringed layers' lanes are a ring"):
            layer_state.slice_lanes(layers, 16)
        with pytest.raises(NotImplementedError, match="ringed layers' lanes are a ring"):
            layer_state.paste_lanes(layers, layers, 16)
    else:
        priced = layer_state.state_bytes(cfg, 3, 32, jnp.float32, counts=RING_COUNTS, ring_lanes=RING)
        assert priced["ringed"] == ring.nbytes == 3 * 3 * RING * 3 * 4
        assert layer_state.split_bytes(priced) == (sum(a.nbytes for a in jax.tree.leaves(layers)), 0)
