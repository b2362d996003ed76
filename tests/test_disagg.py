"""Disaggregated prefill/decode serving: KV handoff, pools, chaos.

Three tiers in one file:

- **Real-engine parity** — a gpt-tiny prefill engine hands its KV to a
  separate decode engine; the stitched stream must be token-for-token
  identical to running the whole request on one replica (fp wire), and
  within a one-token bound for the int8 wire. This is the measured
  int8-KV-on-a-real-engine result the ROADMAP asked for.
- **Wire/cache unit properties** — quantization round-trip bounds, lane
  bucketing, geometry/invariant validation, per-pool HBM admission.
- **Fleet machinery on stubs** — the :class:`DisaggServingFleet` phase
  machine over the real scheduler, including a chaos round trip that
  preempts the decode replica (through the ``faults.py`` seam) while it
  holds handed-off KV and asserts the request re-prefills and completes.
"""

import dataclasses
import threading
import time
import types

import numpy as np
import pytest

from tests.test_serving_fleet import StubTrainJob, mock_fleet_fn, wait_until
from tpu_engine.disagg import (
    DisaggServingFleet,
    KVHandoff,
    _np_quantize,
    extract_slot_kv,
    handoff_to_cache,
    rebucket_handoff,
)
from tpu_engine.faults import FaultInjector, FaultKind, FaultPlan, FaultSpec
from tpu_engine.hbm_estimate import estimate_serving_hbm
from tpu_engine.placement import plan_serving_pool
from tpu_engine.scheduler import FleetScheduler, SubmissionState
from tpu_engine.serving_fleet import (
    AutoscalerConfig,
    ReplicaAutoscaler,
    ServingFleet,
    ServingReplicaSpec,
)


@pytest.fixture
def sched_factory():
    created = []

    def make(**kw):
        jobs = []

        def factory(sub):
            job = StubTrainJob(sub)
            jobs.append(job)
            return job

        kw.setdefault("job_factory", factory)
        kw.setdefault("poll_interval_s", 0.01)
        kw.setdefault("grow_back_cooldown_s", 0.0)
        s = FleetScheduler(**kw)
        s._stub_jobs = jobs
        created.append(s)
        return s

    yield make
    for s in created:
        for j in getattr(s, "_stub_jobs", []):
            j.finish()
        s.shutdown()


def _one(autoscaler_n=1):
    return ReplicaAutoscaler(
        AutoscalerConfig(min_replicas=autoscaler_n, max_replicas=autoscaler_n)
    )


# ---------------------------------------------------------------------------
# Real-engine KV handoff parity (the measured result)
# ---------------------------------------------------------------------------

PROMPT = [11, 7, 23, 42, 5]
MAX_NEW = 8


def tiny_spec(**kw):
    base = dict(
        model_name="gpt-tiny", max_slots=2, max_len=96, prefill_chunk=16
    )
    base.update(kw)
    return ServingReplicaSpec(**base)


def drive(engine, rid, steps=400):
    for _ in range(steps):
        if engine.result(rid)["status"] == "done":
            break
        engine.step()
    out = engine.result(rid)
    assert out["status"] == "done", out
    return out


def extract(engine, rid, quantize=False, steps=50):
    engine.request_handoff(rid, quantize=quantize)
    for _ in range(steps):
        engine.step()
        h = engine.take_handoff(rid)
        if h is not None:
            return h
    raise AssertionError("engine never serviced the handoff order")


@pytest.fixture(scope="module")
def engines():
    """Shared gpt-tiny engines (same seed → identical weights): a prefill
    source, an fp decode destination, and a kv_quant decode destination."""
    from tpu_engine.serving_fleet import build_replica_engine

    return {
        "prefill": build_replica_engine(tiny_spec()),
        "decode": build_replica_engine(tiny_spec()),
        "decode_kvq": build_replica_engine(tiny_spec(kv_quant=True)),
    }


@pytest.fixture(scope="module")
def baseline_tokens(engines):
    """The whole request on one replica — the parity reference."""
    out = drive(
        engines["decode"], engines["decode"].submit(PROMPT, MAX_NEW)
    )
    assert len(out["tokens"]) == MAX_NEW
    return list(out["tokens"])


def test_fp_handoff_token_identical(engines, baseline_tokens):
    pre, dec = engines["prefill"], engines["decode"]
    out = drive(pre, pre.submit(PROMPT, max_new_tokens=1, hold_kv=True))
    assert len(out["tokens"]) == 1
    # The prefill pool's first token IS the TTFT token — and must agree
    # with the unified baseline before any handoff happens.
    assert out["tokens"][0] == baseline_tokens[0]
    assert pre.stats()["held_slots"] == 1

    h = extract(pre, out["id"])
    assert not h.quantized
    # Resident-KV invariant: every history token except the last emitted.
    assert h.length == len(PROMPT) + 1 - 1 == len(PROMPT)
    assert h.last_token == out["tokens"][0]
    assert pre.stats()["held_slots"] == 0
    assert pre.stats()["handoffs_out"] >= 1

    got = drive(dec, dec.submit_prefilled(h, max_new_tokens=MAX_NEW - 1))
    assert [out["tokens"][0], *got["tokens"]] == baseline_tokens
    assert dec.stats()["handoffs_in"] >= 1


def test_int8_wire_parity_within_bound(engines, baseline_tokens):
    pre, dec = engines["prefill"], engines["decode"]
    out = drive(pre, pre.submit(PROMPT, max_new_tokens=1, hold_kv=True))
    h = extract(pre, out["id"], quantize=True)
    assert h.quantized and h.dtype == "int8"
    assert h.k.dtype == np.int8 and h.k_scale.dtype == np.float32
    # One fp32 scale per (layer, lane, kv-head) — the kv_quant pool layout.
    assert h.k_scale.shape == (*h.k.shape[:-1], 1)
    # int8 codes + scales vs the fp32 wire: better than half the bytes.
    fp_bytes = 2 * h.k.size * 4
    assert h.wire_bytes() < 0.5 * fp_bytes

    got = drive(dec, dec.submit_prefilled(h, max_new_tokens=MAX_NEW - 1))
    stitched = [out["tokens"][0], *got["tokens"]]
    # Documented bound: absmax-per-head int8 KV may flip at most one
    # argmax over an 8-token greedy stream (empirically zero on gpt-tiny).
    mismatches = sum(a != b for a, b in zip(stitched, baseline_tokens))
    assert len(stitched) == len(baseline_tokens)
    assert mismatches <= 1


def test_int8_wire_into_kv_quant_pool(engines):
    """int8 codes ingest byte-for-byte into an int8 slot pool."""
    pre, dec = engines["prefill"], engines["decode_kvq"]
    out = drive(pre, pre.submit(PROMPT, max_new_tokens=1, hold_kv=True))
    h = extract(pre, out["id"], quantize=True)
    got = drive(dec, dec.submit_prefilled(h, max_new_tokens=4))
    assert len(got["tokens"]) == 4


def test_fp_wire_into_kv_quant_pool(engines):
    """fp wire → int8 pool: the insert quantizes host-side on ingestion."""
    pre, dec = engines["prefill"], engines["decode_kvq"]
    out = drive(pre, pre.submit(PROMPT, max_new_tokens=1, hold_kv=True))
    h = extract(pre, out["id"])
    assert not h.quantized
    got = drive(dec, dec.submit_prefilled(h, max_new_tokens=4))
    assert len(got["tokens"]) == 4


def test_quantized_pool_ships_codes_directly(engines):
    """Extraction from a kv_quant pool is always int8 — dequantizing on
    the wire would add error AND bytes — and int8 → fp ingestion works."""
    pre, dec = engines["decode_kvq"], engines["decode"]
    out = drive(pre, pre.submit(PROMPT, max_new_tokens=1, hold_kv=True))
    h = extract(pre, out["id"])  # quantize NOT requested
    assert h.quantized
    got = drive(dec, dec.submit_prefilled(h, max_new_tokens=4))
    assert len(got["tokens"]) == 4


def test_submit_prefilled_validates_wire(engines):
    pre, dec = engines["prefill"], engines["decode"]
    out = drive(pre, pre.submit(PROMPT, max_new_tokens=1, hold_kv=True))
    h = extract(pre, out["id"])
    with pytest.raises(ValueError, match="inconsistent"):
        dec.submit_prefilled(dataclasses.replace(h, length=h.length + 1))
    bad_geom = dataclasses.replace(h, head_dim=h.head_dim + 1)
    with pytest.raises(ValueError):
        dec.submit_prefilled(bad_geom)


# ---------------------------------------------------------------------------
# Wire/cache unit properties (no engine)
# ---------------------------------------------------------------------------


def _fake_handoff(L=2, T=5, KV=2, HD=4, quantized=False, seed=0):
    rng = np.random.default_rng(seed)
    k = rng.standard_normal((L, T, KV, HD)).astype(np.float32)
    v = rng.standard_normal((L, T, KV, HD)).astype(np.float32)
    kw = dict(
        prompt=[1, 2, 3, 4, 5], emitted=[9], length=T, n_layers=L,
        n_kv_heads=KV, head_dim=HD,
    )
    if quantized:
        qk, sk = _np_quantize(k)
        qv, sv = _np_quantize(v)
        return KVHandoff(dtype="int8", quantized=True, k=qk, v=qv,
                         k_scale=sk, v_scale=sv, **kw), k, v
    return KVHandoff(dtype="float32", quantized=False, k=k, v=v, **kw), k, v


def test_np_quantize_roundtrip_bound():
    rng = np.random.default_rng(3)
    a = (rng.standard_normal((4, 16)) * 10).astype(np.float32)
    q, scale = _np_quantize(a)
    assert q.dtype == np.int8 and scale.shape == (4, 1)
    # Symmetric absmax rounding: worst-case error is half a code step.
    assert np.all(np.abs(a - q.astype(np.float32) * scale)
                  <= scale / 2 + 1e-6)


def test_handoff_to_cache_buckets_and_pads():
    import jax.numpy as jnp

    h, k, _v = _fake_handoff()
    cache = handoff_to_cache(
        h, dtype=jnp.float32, kv_quant=False, chunk=4, max_lanes=16
    )
    # T=5 buckets up to the next chunk multiple (8), not max_lanes; a lane's
    # row holds its kv-heads side by side (2 x 4 on the wire -> 8).
    assert cache.layers["attn"]["k"].shape == (2, 1, 8, 2 * 4)
    assert int(cache.length) == 5 and not cache.ring
    np.testing.assert_allclose(np.asarray(cache.layers["attn"]["k"][:, 0, :5]), k.reshape(2, 5, 8), rtol=1e-6)
    assert np.all(np.asarray(cache.layers["attn"]["k"][:, 0, 5:]) == 0)  # padding lanes
    assert "k_scale" not in cache.layers["attn"]


def test_handoff_to_cache_quantizes_fp_wire_for_int8_pool():
    import jax.numpy as jnp

    h, k, _v = _fake_handoff()
    cache = handoff_to_cache(
        h, dtype=jnp.float32, kv_quant=True, chunk=8, max_lanes=8
    )
    assert cache.layers["attn"]["k"].dtype == jnp.int8
    assert "k_scale" in cache.layers["attn"]
    deq = (np.asarray(cache.layers["attn"]["k"][:, 0, :5], dtype=np.float32)
           * np.asarray(cache.layers["attn"]["k_scale"][:, 0, :5]))
    assert np.max(np.abs(deq - k)) <= np.max(np.abs(k)) / 127 + 1e-6


def test_handoff_to_cache_dequantizes_int8_wire_for_fp_pool():
    import jax.numpy as jnp

    h, k, _v = _fake_handoff(quantized=True)
    cache = handoff_to_cache(
        h, dtype=jnp.float32, kv_quant=False, chunk=8, max_lanes=8
    )
    assert cache.layers["attn"]["k"].dtype == jnp.float32
    got = np.asarray(cache.layers["attn"]["k"][:, 0, :5]).reshape(k.shape)
    assert np.max(np.abs(got - k)) <= np.max(np.abs(k)) / 127 + 1e-6


def test_handoff_to_cache_rejects_overlong_payload():
    import jax.numpy as jnp

    h, _k, _v = _fake_handoff(T=5)
    with pytest.raises(ValueError, match="exceeds destination pool lanes"):
        handoff_to_cache(h, dtype=jnp.float32, kv_quant=False,
                         chunk=4, max_lanes=4)


def _quant_bound(a):
    return np.max(np.abs(a)) / 127 + 1e-6


def test_rebucket_fp_wire_fp_pool_unequal_geometry():
    # chunk 4/16 lanes → chunk 7/21 lanes: values survive exactly.
    h, k, v = _fake_handoff(T=5)
    out = rebucket_handoff(h, chunk=7, max_lanes=21, kv_quant=False)
    assert out.dtype == "float32" and not out.quantized
    assert out.length == h.length
    assert (out.prompt, out.emitted) == (h.prompt, h.emitted)
    np.testing.assert_allclose(out.k, k, rtol=1e-6)
    np.testing.assert_allclose(out.v, v, rtol=1e-6)


def test_rebucket_fp_wire_int8_pool_unequal_geometry():
    # An fp wire landing on a kv_quant pool ships the pool's own codes.
    h, k, v = _fake_handoff(T=6)
    out = rebucket_handoff(h, chunk=4, max_lanes=12, kv_quant=True)
    assert out.quantized and out.k.dtype == np.int8
    assert out.k_scale is not None
    deq_k = out.k.astype(np.float32) * out.k_scale
    deq_v = out.v.astype(np.float32) * out.v_scale
    assert np.max(np.abs(deq_k - k)) <= _quant_bound(k)
    assert np.max(np.abs(deq_v - v)) <= _quant_bound(v)


def test_rebucket_int8_wire_fp_pool_unequal_geometry():
    # int8 wire dequantizes into an fp pool within the one-step bound.
    h, k, v = _fake_handoff(T=5, quantized=True)
    out = rebucket_handoff(h, chunk=3, max_lanes=9, kv_quant=False)
    assert out.dtype == "float32" and not out.quantized
    assert np.max(np.abs(out.k - k)) <= _quant_bound(k)
    assert np.max(np.abs(out.v - v)) <= _quant_bound(v)


def test_rebucket_int8_wire_int8_pool_unequal_geometry():
    # Codes ship straight through the staging cache: byte-identical.
    h, k, v = _fake_handoff(T=5, quantized=True)
    out = rebucket_handoff(h, chunk=8, max_lanes=24, kv_quant=True)
    assert out.quantized and out.k.dtype == np.int8
    np.testing.assert_array_equal(out.k, h.k)
    np.testing.assert_array_equal(out.v, h.v)
    np.testing.assert_allclose(out.k_scale, h.k_scale, rtol=1e-6)
    assert np.max(np.abs(out.k.astype(np.float32) * out.k_scale - k)) \
        <= _quant_bound(k)
    assert np.max(np.abs(out.v.astype(np.float32) * out.v_scale - v)) \
        <= _quant_bound(v)


def test_rebucket_rejects_overlong_payload():
    h, _k, _v = _fake_handoff(T=5)
    with pytest.raises(ValueError, match="exceeds destination pool lanes"):
        rebucket_handoff(h, chunk=4, max_lanes=4, kv_quant=False)


def test_extract_rejects_ring_pools():
    cache = types.SimpleNamespace(ring=True)
    with pytest.raises(ValueError, match="ring"):
        extract_slot_kv(cache, 0, 4, cfg=None, prompt=[1], emitted=[])


def test_kvhandoff_last_token_and_wire_bytes():
    h, _k, _v = _fake_handoff()
    assert h.last_token == 9  # last emitted
    assert h.wire_bytes() == h.k.nbytes + h.v.nbytes
    hq, _k, _v = _fake_handoff(quantized=True)
    assert hq.wire_bytes() == (hq.k.nbytes + hq.v.nbytes
                               + hq.k_scale.nbytes + hq.v_scale.nbytes)
    no_emit = dataclasses.replace(h, emitted=[])
    assert no_emit.last_token == 5  # falls back to the prompt tail


# ---------------------------------------------------------------------------
# Per-pool HBM admission
# ---------------------------------------------------------------------------


def test_prefill_pool_estimate_sizes_kv_to_inflight():
    kw = dict(max_slots=64, max_len=2048)
    uni = estimate_serving_hbm("gpt-125m", **kw)
    pre = estimate_serving_hbm(
        "gpt-125m", pool_role="prefill", inflight_handoffs=4, **kw
    )
    dec = estimate_serving_hbm("gpt-125m", pool_role="decode", **kw)
    # Prefill KV shrinks to the handoff window; decode pays the full pool.
    # abs tolerance: the estimator rounds the reported plane to 4 decimals.
    assert pre.kv_pool_gib == pytest.approx(
        uni.kv_pool_gib * 4 / 64, abs=1e-4
    )
    assert dec.kv_pool_gib == uni.kv_pool_gib
    assert dec.device_total_gib == uni.device_total_gib
    assert "in-flight handoff" in " / ".join(pre.notes)


@pytest.mark.parametrize("slots,inflight", [(8, 2), (16, 16), (4, 32)])
def test_prefill_pool_kv_scaling_property(slots, inflight):
    uni = estimate_serving_hbm("gpt-tiny", max_slots=slots, max_len=256)
    pre = estimate_serving_hbm(
        "gpt-tiny", max_slots=slots, max_len=256,
        pool_role="prefill", inflight_handoffs=inflight,
    )
    eff = min(slots, inflight)
    assert pre.kv_pool_gib == pytest.approx(
        uni.kv_pool_gib * eff / slots, abs=1e-4
    )


def test_estimate_rejects_bad_pool_role():
    with pytest.raises(ValueError, match="pool_role"):
        estimate_serving_hbm("gpt-tiny", 4, 128, pool_role="bogus")


def test_disagg_decode_pool_oversubscription_queues(sched_factory):
    """The decode pool's KV plane is gated per-pool: a decode spec that
    exceeds per-device headroom queues with a structured reason while the
    (handoff-window-sized) prefill pool of the SAME shape admits."""
    big = dict(model_name="gpt-125m", max_slots=64, max_len=8192)
    assert ServingReplicaSpec(**big).estimate().device_total_gib > 9.6
    s = sched_factory(max_concurrent_jobs=4, fleet_fn=mock_fleet_fn)
    fleet = DisaggServingFleet(
        s,
        ServingReplicaSpec(**big, inflight_handoffs=4),
        ServingReplicaSpec(**big),
        prefill_autoscaler=_one(), decode_autoscaler=_one(),
        engine_factory=DisaggStubEngine,
    )
    fleet.start()
    assert wait_until(lambda: len(fleet.prefill.running_replicas()) == 1)
    time.sleep(0.15)
    (dec_sub,) = fleet.decode._replicas.values()
    assert dec_sub.state == SubmissionState.QUEUED
    assert "have that headroom" in dec_sub.last_skip_reason
    (pre_sub,) = fleet.prefill._replicas.values()
    assert pre_sub.estimate.kv_pool_gib < dec_sub.estimate.kv_pool_gib
    fleet.stop()


# ---------------------------------------------------------------------------
# DisaggServingFleet on stub engines (phase machine + chaos)
# ---------------------------------------------------------------------------


class _FakeHandoff:
    """Wire payload stand-in carrying only what the fleet plane reads."""

    def __init__(self, prompt, emitted):
        self.prompt = list(prompt)
        self.emitted = list(emitted)
        self.length = len(self.prompt) + len(self.emitted) - 1
        self.quantized = False

    def wire_bytes(self):
        return 64 * self.length


class DisaggStubEngine:
    """StubEngine plus the disaggregated surface: hold_kv, handoff
    extraction orders, and wire ingestion. Tokens are a deterministic
    function of history length, so a re-prefilled request reproduces the
    same stream — mirroring the real engine's greedy determinism."""

    def __init__(self, spec):
        self.slots = int(spec.max_slots)
        self._reqs = {}
        self._seq = 0
        self._handoffs = {}
        self.handoffs_out = 0
        self.handoffs_in = 0
        self._lock = threading.Lock()

    def submit(self, prompt, max_new_tokens=64, temperature=0.0,
               hold_kv=False):
        with self._lock:
            self._seq += 1
            self._reqs[self._seq] = {
                "prompt": list(prompt), "need": int(max_new_tokens),
                "tokens": [], "first_at": None, "hold_kv": bool(hold_kv),
            }
            return self._seq

    def submit_prefilled(self, handoff, max_new_tokens=64, temperature=0.0):
        history = list(handoff.prompt) + list(handoff.emitted)
        if handoff.length != len(history) - 1:
            raise ValueError("wire payload is inconsistent")
        with self._lock:
            self._seq += 1
            self.handoffs_in += 1
            self._reqs[self._seq] = {
                "prompt": history, "need": int(max_new_tokens),
                "tokens": [], "first_at": time.time(), "hold_kv": False,
            }
            return self._seq

    def step(self):
        out = 0
        with self._lock:
            for r in self._reqs.values():
                if len(r["tokens"]) < r["need"]:
                    r["tokens"].append(len(r["prompt"]) + len(r["tokens"]))
                    if r["first_at"] is None:
                        r["first_at"] = time.time()
                    out += 1
        return out

    def result(self, rid):
        with self._lock:
            r = self._reqs[rid]
            done = len(r["tokens"]) >= r["need"]
            return {
                "status": "done" if done else "running",
                "tokens": list(r["tokens"]),
                "first_token_at": r["first_at"],
            }

    def request_handoff(self, rid, quantize=False):
        with self._lock:
            r = self._reqs[rid]
            if not r["hold_kv"]:
                raise ValueError(f"request {rid} was not submitted hold_kv")
            self._handoffs[rid] = _FakeHandoff(r["prompt"], r["tokens"])

    def take_handoff(self, rid):
        with self._lock:
            h = self._handoffs.pop(rid, None)
            if h is not None:
                self.handoffs_out += 1
            return h

    def stats(self):
        with self._lock:
            active = sum(
                1 for r in self._reqs.values()
                if len(r["tokens"]) < r["need"]
            )
            held = len(self._handoffs)
        return {
            "slots": self.slots, "active_slots": active, "prefilling": 0,
            "queued": 0, "tokens_per_sec_recent": 100.0,
            "held_slots": held, "queued_handoffs": 0,
            "handoffs_out": self.handoffs_out,
            "handoffs_in": self.handoffs_in,
        }


def make_disagg(sched, **kw):
    kw.setdefault("prefill_autoscaler", _one())
    kw.setdefault("decode_autoscaler", _one())
    kw.setdefault("engine_factory", DisaggStubEngine)
    spec = dict(model_name="gpt-tiny", max_slots=4, max_len=128)
    return DisaggServingFleet(
        sched,
        ServingReplicaSpec(**spec, inflight_handoffs=2),
        ServingReplicaSpec(**spec),
        **kw,
    )


def _pools_up(fleet):
    return (len(fleet.prefill.running_replicas()) == 1
            and len(fleet.decode.running_replicas()) == 1)


def test_disagg_fleet_stitches_prefill_and_decode(sched_factory):
    s = sched_factory(max_concurrent_jobs=4, fleet_fn=mock_fleet_fn)
    fleet = make_disagg(s)
    fleet.start()
    assert wait_until(lambda: _pools_up(fleet))
    fids = [fleet.submit_request([i, i + 1, i + 2], max_new_tokens=5)
            for i in range(3)]
    outs = [fleet.wait(f, timeout=10.0) for f in fids]
    for out in outs:
        assert out["status"] == "done"
        # One token off the prefill logits + the decode pool's remainder.
        assert len(out["tokens"]) == 5
        assert out["prefill_replica"] is not None
        assert out["decode_replica"] is not None
        assert out["prefill_replica"] != out["decode_replica"]
        assert out.get("ttft_ms") is not None
    st = fleet.status()
    assert st["completed_total"] == 3 and st["failed_total"] == 0
    assert st["tokens_total"] == 15
    assert st["handoffs_total"] == 3
    assert st["handoff_bytes_total"] > 0
    assert st["reprefills_total"] == 0
    assert st["ttft_p50_ms"] is not None and st["ttft_p99_ms"] is not None
    fleet.stop()


def test_disagg_fleet_single_token_skips_decode(sched_factory):
    """max_new_tokens=1 is satisfied entirely by the prefill pool."""
    s = sched_factory(max_concurrent_jobs=4, fleet_fn=mock_fleet_fn)
    fleet = make_disagg(s)
    fleet.start()
    assert wait_until(lambda: _pools_up(fleet))
    out = fleet.wait(
        fleet.submit_request([5, 6, 7], max_new_tokens=1), timeout=10.0
    )
    assert out["status"] == "done" and len(out["tokens"]) == 1
    assert out["decode_replica"] is None
    fleet.stop()


def test_chaos_decode_preemption_reprefills_and_completes(sched_factory):
    """A decode replica holding handed-off KV dies through the faults.py
    preemption seam; the fleet re-prefills the request from scratch on
    the re-admitted replica and completes it."""
    inj = FaultInjector(FaultPlan(specs=[
        FaultSpec(kind=FaultKind.PREEMPTION_SIGNAL, at_step=1)
    ]))
    inj.arm()
    s = sched_factory(max_concurrent_jobs=4, fleet_fn=mock_fleet_fn)
    fleet = make_disagg(s, decode_fault_injector=inj)
    fleet.start()
    assert wait_until(lambda: _pools_up(fleet))
    # Enough decode tokens that the replica is mid-request when the fault
    # fires (the injector's step counter is the replica's token counter).
    fid = fleet.submit_request([1, 2, 3], max_new_tokens=32)
    out = fleet.wait(fid, timeout=20.0)
    assert out["status"] == "done"
    assert len(out["tokens"]) == 32
    assert out["redispatches"] >= 1
    assert fleet.reprefills_total >= 1
    assert inj.counters.get("preemption-signal") == 1
    (dec_sub,) = fleet.decode._replicas.values()
    assert dec_sub.preemptions >= 1
    assert dec_sub.attempts >= 2  # re-admitted after the preempt
    fleet.stop()


def test_requeue_gives_up_after_max_redispatch(sched_factory):
    s = sched_factory(max_concurrent_jobs=4, fleet_fn=mock_fleet_fn)
    fleet = make_disagg(s, max_redispatch=2)
    fleet.start()
    assert wait_until(lambda: _pools_up(fleet))
    fid = fleet.submit_request([1, 2], max_new_tokens=4)
    with fleet._lock:
        r = fleet._requests[fid]
        for _ in range(3):
            fleet._requeue_locked(fid, r, "test-forced")
    out = fleet.result(fid)
    assert out["status"] == "failed"
    assert "re-dispatches" in fleet._requests[fid]["error"]
    assert fleet.failed_total == 1
    fleet.stop()


# ---------------------------------------------------------------------------
# Fleet TTFT + autoscaler TTFT SLO (satellite)
# ---------------------------------------------------------------------------


def test_serving_fleet_status_reports_ttft(sched_factory):
    s = sched_factory(max_concurrent_jobs=2, fleet_fn=mock_fleet_fn)
    fleet = ServingFleet(
        s, ServingReplicaSpec(model_name="gpt-tiny", max_slots=4, max_len=128),
        autoscaler=_one(), engine_factory=DisaggStubEngine,
    )
    fleet.start()
    assert wait_until(lambda: len(fleet.running_replicas()) == 1)
    rids = [fleet.submit_request([1, 2], max_new_tokens=3) for _ in range(4)]
    assert all(
        wait_until(lambda r=r: fleet.result(r)["status"] == "done")
        for r in rids
    )
    st = fleet.status()
    assert st["ttft_p50_ms"] is not None and st["ttft_p50_ms"] >= 0
    assert st["ttft_p99_ms"] >= st["ttft_p50_ms"]
    pct = fleet.ttft_percentiles()
    assert pct["p50"] == st["ttft_p50_ms"]
    fleet.stop()


def test_autoscaler_ttft_slo_breach_scales_up():
    a = ReplicaAutoscaler(AutoscalerConfig(
        min_replicas=1, max_replicas=4, ttft_slo_ms=200.0,
    ))
    # End-to-end p99 is healthy; only TTFT is breached.
    assert a.observe(0.0, queue_depth=0.0, p99_ms=100.0, n_replicas=2,
                     ttft_p99_ms=900.0) == 3
    assert "TTFT SLO" in a.last_reason


def test_autoscaler_ignores_ttft_without_slo():
    a = ReplicaAutoscaler(AutoscalerConfig(min_replicas=1, max_replicas=4))
    assert a.observe(0.0, queue_depth=0.0, p99_ms=100.0, n_replicas=2,
                     ttft_p99_ms=9000.0) == 2


# ---------------------------------------------------------------------------
# Planner: per-pool layout choice
# ---------------------------------------------------------------------------


def test_plan_serving_pool_prefill_ranks_by_latency():
    plans = plan_serving_pool(
        "gpt-125m", "prefill", 4, hbm_free_gib=24.0, max_len=2048,
        inflight_handoffs=4,
    )
    feas = [p for p in plans if p.feasible]
    assert feas and feas[0].role == "prefill"
    # Slots pinned to the handoff window, not the candidate slot grid.
    assert all(p.max_slots == 4 for p in plans)
    assert all(
        feas[0].predicted_prefill_s <= p.predicted_prefill_s for p in feas
    )
    # More tensor parallelism lowers single-prompt latency on this model.
    assert feas[0].tensor_parallel > 1
    assert feas[0].label.startswith("prefill·tp")


def test_plan_serving_pool_decode_ranks_by_throughput():
    plans = plan_serving_pool(
        "gpt-125m", "decode", 4, hbm_free_gib=24.0, max_len=2048
    )
    feas = [p for p in plans if p.feasible]
    assert feas and all(
        feas[0].predicted_decode_tok_s >= p.predicted_decode_tok_s
        for p in feas
    )
    assert feas[0].predicted_decode_tok_s > 0


def test_plan_serving_pool_infeasible_carries_reason():
    plans = plan_serving_pool(
        "gpt-125m", "decode", 4, hbm_free_gib=0.05, max_len=2048
    )
    assert plans and all(not p.feasible for p in plans)
    assert all("free" in p.skip_reason for p in plans)


def test_plan_serving_pool_edges():
    assert plan_serving_pool("no-such-model", "decode", 4) == []
    with pytest.raises(ValueError):
        plan_serving_pool("gpt-tiny", "unified", 4)
    # Deterministic: same inputs, same ranking.
    a = plan_serving_pool("gpt-125m", "decode", 8, max_len=1024)
    b = plan_serving_pool("gpt-125m", "decode", 8, max_len=1024)
    assert [p.label for p in a] == [p.label for p in b]


def test_disagg_ab_sim_gates_and_layouts():
    """The A/B the bench gates on: disagg wins p99 TTFT at equal chips
    without giving up throughput, and both layouts are planner-chosen."""
    from benchmarks.serving_fleet_sim import run_disagg_ab

    ab = run_disagg_ab(seed=0)
    assert ab["gates_pass"], ab["gates"]
    assert ab["disagg"]["ttft_p99_ms"] < ab["symmetric"]["ttft_p99_ms"]
    lay = ab["layouts"]
    assert lay["disagg_prefill"].startswith("prefill·")
    assert lay["disagg_decode"].startswith("decode·")
    assert lay["symmetric"].startswith("decode·")
    assert lay["prefill_speedup"] > 1.0
