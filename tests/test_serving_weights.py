"""A serving engine holds its weights ONCE, in the dtype it computes in
(``transformer.served_format``), and its programs cast nothing.

- (a) the format: which leaves go to the compute dtype and which stay, over a
  dense, an MoE and a hybrid stack, an int8-quantised tree, a speculative
  draft and a mesh-sharded tree; idempotent; shardings kept; ``stats()``
  reports the held bytes by dtype;
- (b) the programs: the lowered decode-chunk, prefill-chunk and speculative
  programs hold no op under ``cast_weights`` and no float32 weight, and a
  cached walk handed a float32 stack under bf16 activations refuses it;
- (c) the answer did not change: an engine given a float32 tree serves bit for
  bit what an engine given the converted tree serves, and what the programs of
  the commit before this format served (literals recorded from that tree, on
  the CPU); ``build_replica_engine`` holds ``served_format(init_params(
  float32))`` leaf for leaf and its int8 build the codes and scales of the
  float32 draw;
- (d) the other side of the separation: training's forward still casts;
- (e) ``estimate_serving_hbm`` prices the bytes the engine holds.
"""

import hashlib
import json
import os
import re
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

from tpu_engine import serving  # noqa: E402
from tpu_engine.generate import forward_with_cache, generate, init_cache  # noqa: E402
from tpu_engine.hbm_estimate import estimate_serving_hbm  # noqa: E402
from tpu_engine.models import transformer as tfm  # noqa: E402
from tpu_engine.quant import QuantWeight, quantize_params  # noqa: E402
from tpu_engine.serving_fleet import ServingReplicaSpec, build_replica_engine  # noqa: E402

GIB = 2**30
BF16, F32 = jnp.bfloat16, jnp.float32
SEED = 3
FAMILIES = {"dense": "mistral-7b-1chip-serve", "moe": "mixtral-8x7b-1chip-serve",
            "hybrid": "granite-4.0-h-micro-1chip-serve"}


@pytest.fixture
def stack(request, monkeypatch):
    """(ModelConfig, float32 params) of a serving configuration's family at its
    rehearsal widths, registered under a name of this test's."""
    monkeypatch.setattr(tfm, "MODEL_CONFIGS", dict(tfm.MODEL_CONFIGS))
    name = FAMILIES[request.param]
    entry = {c["name"]: c for c in manifest.load_manifest()["configs"]}[name]
    with open(os.path.join(manifest.ROOT, entry["file"])) as f:
        cfg = json.load(f)
    mc = program.model_config({**cfg, **cfg["rehearsal"]}, f"{request.param}-weights-tiny")
    return mc, tfm.init_params(jax.random.PRNGKey(SEED), mc)


all_families = pytest.mark.parametrize("stack", list(FAMILIES), indirect=True)


def _engine(params, mc, **kw):
    kw = {"max_slots": 2, "max_len": 64, "prefill_chunk": 32, "chunk_steps": 4, **kw}
    return serving.ContinuousBatcher(params, mc, **kw)


def _spec(mc, **kw):
    return ServingReplicaSpec(model_name=mc.name, seed=SEED, max_slots=2, max_len=64,
                              prefill_chunk=32, decode_chunk_steps=4, **kw)


def _leaves(tree):
    return jax.tree_util.tree_leaves_with_path(tree)


def _assert_served(tree, dtype=BF16):
    """Every floating leaf is ``dtype`` but the recurrence leaves and the
    scales of a QuantWeight (whose codes are int8)."""
    seen = set()
    for path, a in _leaves(tree):
        in_quant = any(getattr(k, "name", None) in ("q", "scale") for k in path)
        if in_quant:
            assert a.dtype == (jnp.int8 if path[-1].name == "q" else F32), jax.tree_util.keystr(path)
        elif path[-1].key in tfm.SSM_FLOAT32_LEAVES:
            assert a.dtype == F32, jax.tree_util.keystr(path)
            seen.add(path[-1].key)
        else:
            assert a.dtype == dtype, jax.tree_util.keystr(path)
    return seen


def _assert_same_tree(a, b):
    la, lb = _leaves(a), _leaves(b)
    assert [p for p, _ in la] == [p for p, _ in lb]
    for (path, x), (_, y) in zip(la, lb):
        assert x.dtype == y.dtype, jax.tree_util.keystr(path)
        assert np.array_equal(np.asarray(x, np.float32), np.asarray(y, np.float32)), jax.tree_util.keystr(path)


def _held_bytes(tree):
    out = {}
    for a in jax.tree.leaves(tree):
        out[str(a.dtype)] = out.get(str(a.dtype), 0) + a.size * a.dtype.itemsize
    return out


# (a) the format --------------------------------------------------------------


@all_families
def test_an_engine_holds_the_served_format_and_counts_it(stack):
    mc, params = stack
    engine = _engine(params, mc)
    kept = _assert_served(engine.params)
    assert kept == (set(tfm.SSM_FLOAT32_LEAVES) if mc.is_hybrid else set())
    assert engine.params["embed"]["embedding"].dtype == BF16
    st = engine.stats()
    assert st["weight_bytes"] == _held_bytes(engine.params)
    assert sum(st["weight_bytes"].values()) == sum(a.nbytes for a in jax.tree.leaves(engine.params))
    assert ("float32" in st["weight_bytes"]) == mc.is_hybrid
    # idempotent: a converted tree comes back leaf for leaf, the same arrays
    again = tfm.served_format(engine.params, BF16)
    assert all(x is y for x, y in zip(jax.tree.leaves(again), jax.tree.leaves(engine.params)))
    # and the float32 tree a float32 engine is given is already its format
    assert all(x is y for x, y in zip(jax.tree.leaves(tfm.served_format(params, F32)), jax.tree.leaves(params)))


@all_families
def test_an_int8_tree_keeps_its_codes_and_scales(stack):
    mc, params = stack
    q = quantize_params(params)
    engine = _engine(q, mc)
    _assert_served(engine.params)
    sites = [(a, b) for a, b in zip(jax.tree.leaves(q, is_leaf=lambda x: isinstance(x, QuantWeight)),
                                    jax.tree.leaves(engine.params, is_leaf=lambda x: isinstance(x, QuantWeight)))
             if isinstance(a, QuantWeight)]
    assert sites and all(a.q is b.q and a.scale is b.scale for a, b in sites)
    st = engine.stats()["weight_bytes"]
    assert st == _held_bytes(engine.params) and st["int8"] > st["bfloat16"] > 0 and st["float32"] > 0
    ids = [engine.submit(p, max_new_tokens=4) for p in _prompts()]
    _drain(engine, ids)  # the programs take codes and scales as they are


def test_a_speculative_draft_is_held_in_the_format_too():
    mc = tfm.MODEL_CONFIGS["gpt-tiny"].with_(sliding_window=0)
    draft = mc.with_(name="gpt-tiny-draft", n_layers=1)
    engine = _engine(tfm.init_params(jax.random.PRNGKey(1), mc), mc,
                     draft_params=tfm.init_params(jax.random.PRNGKey(2), draft), draft_cfg=draft)
    _assert_served(engine.params)
    _assert_served(engine._draft_params)
    st = engine.stats()
    assert st["draft_weight_bytes"] == _held_bytes(engine._draft_params)
    assert set(st["weight_bytes"]) == set(st["draft_weight_bytes"]) == {"bfloat16"}
    text = engine._spec.lower(
        engine.params, engine._draft_params, jnp.zeros((2,), jnp.int32), engine._cache,
        engine._draft_cache, jnp.ones((2,), bool)).as_text(debug_info=True)
    _assert_no_cast(text, [engine.params, engine._draft_params])


def test_a_mesh_sharded_tree_keeps_its_shardings():
    from tpu_engine.mesh_runtime import MeshConfig, build_mesh
    from tpu_engine.sharding import ShardingStage, named_shardings, param_pspecs

    mc = tfm.MODEL_CONFIGS["gpt-tiny"].with_(sliding_window=0)
    mesh = build_mesh(MeshConfig(fsdp=2, model=4))
    shardings = named_shardings(mesh, param_pspecs(tfm.logical_axes(mc), ShardingStage.FULL_PARTITIONING))
    params = jax.device_put(tfm.init_params(jax.random.PRNGKey(1), mc), shardings)
    assert any(not a.sharding.is_fully_replicated for a in jax.tree.leaves(params))
    engine = _engine(params, mc, mesh=mesh)
    _assert_served(engine.params)
    for (path, a), want in zip(_leaves(engine.params), jax.tree.leaves(shardings)):
        assert a.sharding == want, jax.tree_util.keystr(path)
    assert engine.stats()["weight_bytes"] == {"bfloat16": 2 * tfm.param_count(mc)}  # whole, over all devices
    ids = [engine.submit([5, 6, 7, 8, 9], max_new_tokens=4)]
    _drain(engine, ids)
    assert len(engine.result(ids[0])["tokens"]) == 4


# (b) the programs cast nothing ------------------------------------------------


def _dims(shape):
    return "x".join(str(d) for d in shape)


def _assert_no_cast(text, trees):
    """No op under ``cast_weights``; no float32 program argument of a weight's
    shape; no convert of a weight (whole or one layer of its stack) out of
    float32."""
    assert "cast_weights" not in text
    shapes = set()
    for tree in trees:
        for path, a in _leaves(tree):
            if a.dtype == BF16:
                shapes.update((a.shape, a.shape[1:]))
    shapes.discard(())
    main = re.search(r"func\.func public @main\((.*?)\) ->", text, re.S).group(1)
    for shape in shapes:
        assert f"tensor<{_dims(shape)}xf32>" not in main, shape
        assert not re.search(rf"stablehlo\.convert[^\n]*tensor<{_dims(shape)}xf32>\) -> tensor<{_dims(shape)}xbf16>",
                             text), shape


@all_families
def test_the_serving_programs_hold_no_weight_cast(stack):
    mc, params = stack
    engine = _engine(params, mc)
    B = engine.max_slots
    decode = engine._decode.lower(
        engine.params, jnp.zeros((B,), jnp.int32), engine._cache, jnp.ones((B,), bool),
        jnp.zeros((B,), F32), jnp.zeros((B,), jnp.int32), jnp.zeros((B,), jnp.int32),
        engine._base_key).as_text(debug_info=True)
    prefill = engine._prefill_fn.lower(
        engine.params, jnp.zeros((1, 32), jnp.int32), init_cache(mc, 1, 32, dtype=BF16),
        jnp.int32(0), jnp.int32(32) if mc.is_hybrid else None).as_text(debug_info=True)
    for text in (decode, prefill):
        assert "decode_attn" in text or "ssm" in text  # the scopes ARE in this text
        _assert_no_cast(text, [engine.params])


def test_a_cached_walk_refuses_a_stack_that_is_not_in_the_format():
    mc = tfm.MODEL_CONFIGS["gpt-tiny"].with_(sliding_window=0)
    params = tfm.init_params(jax.random.PRNGKey(1), mc)
    toks = jnp.zeros((1, 8), jnp.int32)
    with pytest.raises(TypeError, match="served_format"):
        forward_with_cache(params, toks, init_cache(mc, 1, 16), mc)  # bf16 activations, float32 stack
    logits, _ = forward_with_cache(tfm.served_format(params), toks, init_cache(mc, 1, 16), mc)
    assert logits.dtype == F32
    forward_with_cache(params, toks, init_cache(mc, 1, 16, dtype=F32), mc, compute_dtype=F32)


def test_generate_converts_a_float32_tree_at_its_entry():
    mc = tfm.MODEL_CONFIGS["gpt-tiny"].with_(sliding_window=0)
    params = tfm.init_params(jax.random.PRNGKey(1), mc)
    prompt = jnp.asarray([[5, 6, 7, 8, 9]], jnp.int32)
    a = generate(params, prompt, mc, max_new_tokens=6)
    b = generate(tfm.served_format(params), prompt, mc, max_new_tokens=6)
    assert np.array_equal(np.asarray(a), np.asarray(b))


# (c) the answer did not change --------------------------------------------------

# Recorded on the CPU from the commit before this format (b04a573: float32
# weights, a cast inside every program), by the same calls as
# ``_served_values``: greedy tokens of two requests, and four logits
# [0, 1, 255, 511] of a prefill chunk's row and of a decode step's row.
PARENT = {
    # ``prefill_row`` of dense and moe re-recorded in PR 48, whose prefill chunk
    # contracts the head with its one row alone ([1, D] x [D, V]), which the CPU
    # sums in another order than that row of the [T, V] product: the same
    # products, float32 rounding. b04a573's were 0.20951591432094574,
    # -0.013773605227470398, -0.056673794984817505, -0.2290504276752472 (dense) and
    # PR 35's -0.0733594223856926 at [255] (moe): within 1.1e-7 relative of these.
    # Tokens, ``decode_row`` and all of hybrid are as they were.
    "dense": {
        "tokens": [[98, 403, 233, 79, 308, 374, 309, 443], [497, 376, 469, 472, 88, 409, 350, 224]],
        "prefill_row": [0.20951589941978455, -0.013773605227470398, -0.056673791259527206, -0.229050412774086],
        "decode_row": [0.07227375358343124, 0.3225421607494354, -0.12000519037246704, -0.05271732062101364],
    },
    # Re-recorded in PR 35, whose mixture contracts over expert and width
    # together with the gates folded into the activations, where b04a573 wrote
    # every expert's [B, T, E, D] and combined it: the same products, rounded
    # to bfloat16 at another point. The tokens are b04a573's; its logits were
    # 0.18816834688186646, -0.01070772111415863, -0.07360314577817917,
    # -0.23313620686531067 (prefill) and 0.05203641951084137, 0.31955811381340027,
    # -0.11569535732269287, -0.040707044303417206 (decode): within 3e-4 of these.
    "moe": {
        "tokens": [[98, 403, 233, 79, 308, 374, 309, 443], [497, 376, 380, 12, 92, 465, 314, 84]],
        "prefill_row": [0.1878596395254135, -0.010642990469932556, -0.0733594298362732, -0.23365341126918793],
        "decode_row": [0.05209147185087204, 0.31985390186309814, -0.11561896651983261, -0.040776386857032776],
    },
    "hybrid": {
        "tokens": [[145] * 8, [503] * 8],
        "prefill_row": [0.003517378121614456, -0.0011713255662471056, -0.0032348204404115677,
                        -0.0004444918013177812],
        "decode_row": [0.0016347579658031464, -0.0025002367328852415, -0.0019199522212147713,
                       0.0017130946507677436],
    },
}


# sha256 (first 16 hex digits) over every leaf's path, dtype and bytes, recorded
# from that commit too: (``init_params`` at float32, the same tree with every
# leaf but the recurrence's rounded to bfloat16 as its programs' casts did).
PARENT_TREES = {
    "dense": ("33a1f4b2e36cb6b3", "f1749b05f0b60b4d"),
    "moe": ("328ab26b080768ae", "9344a59c415914e8"),
    "hybrid": ("7ce3d24002e0117e", "c85321527b740476"),
}


def _sha(tree):
    h = hashlib.sha256()
    for path, a in _leaves(tree):
        h.update(jax.tree_util.keystr(path).encode())
        h.update(str(a.dtype).encode())
        h.update(np.asarray(a).tobytes())
    return h.hexdigest()[:16]


def _prompts():
    rng = np.random.default_rng(11)
    return [rng.integers(0, 512, n).tolist() for n in (9, 40)]


def _drain(engine, ids):
    for _ in range(200):
        engine.step()
        if all(engine.result(i)["status"] == "done" for i in ids):
            return
    raise AssertionError("requests did not finish")


def _served_values(engine, mc):
    """Tokens through the batcher, and logits through the module's programs
    on the engine's own tree."""
    prompts = _prompts()
    ids = [engine.submit(p, max_new_tokens=8) for p in prompts]
    _drain(engine, ids)
    chunk = np.zeros((1, 32), np.int32)
    chunk[0, :9] = prompts[0]
    row, c1 = jax.jit(lambda p, t, c: serving._prefill_forward(
        p, t, c, jnp.int32(8), jnp.int32(9), cfg=mc, compute_dtype=BF16))(
        engine.params, jnp.asarray(chunk), init_cache(mc, 1, 32, dtype=BF16))
    pool = serving.init_slot_cache(mc, 2, 64, BF16, prefill_chunk=32)
    pool = serving._insert_prefill(pool, c1, jnp.int32(1), jnp.int32(9), False)
    lg, _ = jax.jit(lambda p, t, c, a: serving.decode_step(p, t, c, a, mc, BF16))(
        engine.params, jnp.asarray([0, int(np.argmax(row))], jnp.int32), pool, jnp.asarray([False, True]))
    pick = [0, 1, 255, 511]
    return {"tokens": [engine.result(i)["tokens"] for i in ids],
            "prefill_row": [float(row[i]) for i in pick], "decode_row": [float(lg[1, i]) for i in pick],
            "rows": (np.asarray(row), np.asarray(lg))}


@pytest.mark.parametrize("stack", list(FAMILIES), indirect=True)
def test_the_served_answer_is_the_parents_bit_for_bit(stack, request):
    mc, params = stack
    family = request.node.callspec.params["stack"]
    given_f32 = _served_values(_engine(params, mc), mc)
    given_converted = _served_values(_engine(tfm.served_format(params), mc), mc)
    built = _served_values(build_replica_engine(_spec(mc)), mc)
    for got in (given_converted, built):
        assert got["tokens"] == given_f32["tokens"]
        for a, b in zip(got["rows"], given_f32["rows"]):
            assert np.array_equal(a, b)
    for key, want in PARENT[family].items():
        assert given_f32[key] == want, key


@all_families
def test_the_factory_never_makes_the_float32_tree_and_holds_its_rounding(stack, request):
    mc, params = stack
    engine = build_replica_engine(_spec(mc))
    _assert_same_tree(engine.params, tfm.served_format(params, BF16))
    # both draws are the parent's, byte for byte (training's float32 one too)
    assert (_sha(params), _sha(engine.params)) == PARENT_TREES[request.node.callspec.params["stack"]]
    _assert_same_tree(tfm.init_params(jax.random.PRNGKey(SEED), mc, dtype=BF16), tfm.served_format(params, BF16))
    assert engine._compute_dtype == BF16
    # the spec's dtype reaches the batcher: a float32 replica holds float32
    from tpu_engine.sharding import Precision
    f32 = build_replica_engine(_spec(mc, compute_dtype=Precision.FP32))
    assert f32._compute_dtype == F32 and set(f32.stats()["weight_bytes"]) == {"float32"}
    _assert_same_tree(f32.params, params)


@all_families
def test_the_int8_build_quantises_the_float32_draw(stack):
    mc, params = stack
    engine = build_replica_engine(_spec(mc, weight_quant="int8"))
    _assert_served(engine.params)
    _assert_same_tree(engine.params, tfm.served_format(quantize_params(params), BF16))


@all_families
def test_the_int8_build_never_holds_the_float32_tree(stack):
    """``init_params(deferred=True)`` leaves every drawn kernel and table as a
    call; drawn, the tree is the one drawn at once, bit for bit; and
    ``quantize_params`` turns such a call into codes where it meets it."""
    mc, params = stack
    lazy = tfm.init_params(jax.random.PRNGKey(SEED), mc, deferred=True)
    calls = [(p, a) for p, a in _leaves(lazy) if callable(a)]
    held = sum(a.nbytes for _, a in _leaves(lazy) if not callable(a))
    assert calls and held < 0.02 * sum(a.nbytes for a in jax.tree.leaves(params))
    _assert_same_tree(tfm.draw_deferred(lazy), params)
    q = quantize_params(lazy)
    sites = [a for a in jax.tree.leaves(q, is_leaf=lambda x: isinstance(x, QuantWeight)) if isinstance(a, QuantWeight)]
    assert len(sites) + sum(callable(a) for a in jax.tree.leaves(q)) == len(calls)


# (d) training keeps its master and its cast -------------------------------------


def test_the_training_forward_still_casts_its_float32_master():
    mc = tfm.MODEL_CONFIGS["gpt-tiny"]
    params = jax.eval_shape(lambda k: tfm.init_params(k, mc), jax.random.PRNGKey(0))
    text = jax.jit(lambda p, t: tfm.forward_hidden_and_aux(p, t, mc)).lower(
        params, jax.ShapeDtypeStruct((1, 16), jnp.int32)).as_text(debug_info=True)
    assert "cast_weights" in text
    gate = params["layers"]["gate"]["kernel"].shape
    assert re.search(rf"stablehlo\.convert[^\n]*tensor<{_dims(gate)}xf32>\) -> tensor<{_dims(gate)}xbf16>", text)


# (e) the estimate meets the allocation ----------------------------------------------


@all_families
def test_the_estimate_prices_the_weights_the_engine_holds(stack):
    mc, params = stack
    spec = _spec(mc)
    held = build_replica_engine(spec).stats()["weight_bytes"]
    # The estimate prices ``param_count`` at the serving dtype; the engine
    # holds exactly that, and the float32 recurrence leaves' other two bytes.
    recurrence = held.get("float32", 0)
    assert recurrence == 4 * 3 * mc.ssm_heads * mc.n_ssm_layers if mc.is_hybrid else recurrence == 0
    assert sum(held.values()) == 2 * tfm.param_count(mc) + recurrence // 2
    est = spec.estimate()
    assert est.params_gib == estimate_serving_hbm(mc.name, 2, 64, prefill_chunk=32).params_gib
    assert abs(est.params_gib * GIB - sum(held.values())) <= recurrence // 2 + 0.5e-4 * GIB  # its 4 decimals
    # what the float32 master of the commit before held beside its per-dispatch copy
    assert sum(a.nbytes for a in jax.tree.leaves(params)) >= 2 * sum(held.values()) - recurrence
