"""``harness/program_trace`` and the readers over it, on traces whose numbers
are known exactly (XSpace text protos in the layout of a v5e trace, as
``test_trace_reduce.py`` writes them, here with event arguments), and every
reader this PR adds on a hand-made ``run``."""

import time

import pytest

from harness import manifest, program_trace

MS = 1_000_000_000  # picoseconds per millisecond


def _xspace(planes, tf_ops=None) -> str:
    """planes: {plane: {line: [(name, start_ms, dur_ms[, {arg: value}]), ...]}};
    ``tf_ops``: {event name: scope path}, kept where a v5e trace keeps it — in
    the ``tf_op`` stat of the op's event metadata."""
    tf_ops = tf_ops or {}
    out = []
    for pname, lines in planes.items():
        names = sorted({ev[0] for evs in lines.values() for ev in evs})
        ids = {n: i + 1 for i, n in enumerate(names)}
        args = sorted({k for evs in lines.values() for ev in evs for k in (ev[3] if len(ev) > 3 else {})}
                      | ({"tf_op"} if tf_ops else set()))
        arg_ids = {k: i + 1 for i, k in enumerate(args)}
        body = [f'name: "{pname}"']
        for lname, evs in lines.items():
            ev_txt = []
            for ev in evs:
                n, s, d = ev[:3]
                stats = " ".join(f'stats {{ metadata_id: {arg_ids[k]} str_value: "{v}" }}'
                                 for k, v in (ev[3] if len(ev) > 3 else {}).items())
                ev_txt.append(f"events {{ metadata_id: {ids[n]} offset_ps: {int(s * MS)} "
                              f"duration_ps: {int(d * MS)} {stats} }}")
            body.append(f'lines {{ name: "{lname}" timestamp_ns: 0 {" ".join(ev_txt)} }}')
        for n, i in ids.items():
            scope = f'stats {{ metadata_id: {arg_ids["tf_op"]} str_value: "{tf_ops[n]}" }}' if n in tf_ops else ""
            body.append(f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" {scope} }} }}')
        for k, i in arg_ids.items():
            body.append(f'stat_metadata {{ key: {i} value {{ id: {i} name: "{k}" }} }}')
        out.append("planes { " + " ".join(body) + " }")
    return "\n".join(out)


def _data(planes):
    from jax.profiler import ProfileData

    return ProfileData.from_text_proto(_xspace(planes))


def _bytes(planes, tf_ops=None) -> bytes:
    from jax.profiler import ProfileData

    return ProfileData.text_proto_to_serialized_xspace(_xspace(planes, tf_ops))


def _op(name):
    return f"%{name} = bf16[16,4096]{{1,0}} fusion(%p), kind=kLoop"


def test_idle_time_is_split_by_the_innermost_program_phase():
    # busy 0-40, 70-100, 110-150, 180-200: gaps of 30, 10 and 30 ms
    ops = [("fusion.1", 0, 40), ("fusion.1", 70, 30), ("fusion.2", 110, 40), ("fusion.1", 180, 20)]
    data = _data({
        "/device:TPU:0": {"XLA Ops": ops},
        "/host:CPU": {"supervisor": [
            ("tpu_engine.supervisor.device", 0, 45, {"step": 7}),
            ("tpu_engine.supervisor.health", 45, 20, {"step": 7}),      # 40-70: 5 device, 20 health, 5 nothing
            ("onchip.supervisor.step", 0, 200),                          # the harness's span names nothing here
            ("tpu_engine.supervisor.monitor", 98, 62, {"step": 7}),     # outer: covers gaps 100-110 and 150-160
            ("tpu_engine.supervisor.checkpoint", 104, 4, {"step": 7}),  # nested inside it: 104-108
        ]},
    })
    ann = program_trace.annotations(data)
    assert [a[2] for a in ann] == ["supervisor.device", "supervisor.health", "supervisor.monitor",
                                   "supervisor.checkpoint"]
    assert ann[0][3] == {"step": "7"}
    idle = program_trace.idle_by_phase(data)
    assert idle["idle_s"] == pytest.approx(0.070)
    assert idle["by_phase"] == {
        "supervisor.health": pytest.approx(0.020),
        "supervisor.monitor": pytest.approx(0.006 + 0.010),  # 100-104 and 108-110, then 150-160
        "supervisor.device": pytest.approx(0.005),
        "supervisor.checkpoint": pytest.approx(0.004),       # the inner span wins where both cover
    }
    assert idle["unnamed_s"] == pytest.approx(0.005 + 0.020)  # 65-70 and 160-180
    assert list(idle["by_phase"])[0] == "supervisor.health"   # largest first


def test_a_trace_without_program_names_reads_as_nothing():
    parsed = program_trace.read(_bytes(
        {"/device:TPU:0": {"XLA Ops": [("fusion.1", 0, 40), ("fusion.1", 70, 30)]},
         "/host:CPU": {"main": [("onchip.batcher.step", 0, 100)]}},
        tf_ops={"fusion.1": "jit(_unknown)/while/body/dot_general:"}))  # a parent's program: no scope on the path
    assert parsed["annotations"] == [] and parsed["idle"]["by_phase"] == {}
    assert parsed["idle"]["unnamed_s"] == pytest.approx(parsed["idle"]["idle_s"]) == pytest.approx(0.030)
    assert set(parsed["scopes"]["by_scope"]) == {"_unknown"} and parsed["scopes"]["busy_s"] == pytest.approx(0.070)
    run = {"trace": {"busy_s": 0.07}, "cell": {"cell": {"name": "no-such-cell"}}}
    for name in ("idle_named_pct.train", "attn_time_pct.tpot", "attn_time_pct.rate"):
        assert manifest.load_reader(name)(run, name) is None  # no trace of that cell in this checkout


def test_device_seconds_by_named_scope_from_the_event_metadata():
    dec = "jit(decode_chunk)/while/body/while/body/"
    tf_ops = {
        _op("fusion.1"): dec + "attn/decode_attn/dot_general:",
        _op("fusion.2"): dec + "attn/kv_write/scatter:",
        _op("fusion.3"): dec + "moe_experts/dot_general:",
        _op("convert.4"): "jit(decode_chunk)/while/body/cast_weights/convert_element_type:",
        _op("fusion.5"): "jit(train_step)/forward_backward/transpose(jvp(while))/body/transpose(jvp(attn))/dot_general:",
    }
    ops = [(_op("fusion.1"), 0, 30), (_op("fusion.2"), 30, 10), (_op("fusion.3"), 40, 40),
           (_op("convert.4"), 80, 15), (_op("fusion.5"), 95, 5),
           ("%while.7 = (s32[]) while(%t), condition=%c, body=%b", 0, 80),  # a container: its body's ops count
           ("%copy.9 = bf16[8]{0} copy(%p)", 100, 10)]                      # no tf_op: unscoped
    raw = _bytes({"/device:TPU:0": {"XLA Ops": ops}, "/host:CPU": {"main": [(_op("fusion.1"), 0, 1)]}}, tf_ops)
    scopes = program_trace.op_scopes(raw)
    assert scopes[_op("fusion.1")] == dec + "attn/decode_attn/dot_general:" and len(scopes) == 5
    sc = program_trace.read(raw)["scopes"]
    assert sc["busy_s"] == pytest.approx(0.110)
    assert sc["by_scope"]["decode_attn"] == pytest.approx(0.030)
    assert sc["by_scope"]["attn"] == pytest.approx(0.045)         # 30 + 10 + the backward's 5
    assert sc["by_scope"]["cast_weights"] == pytest.approx(0.015)
    assert sc["by_scope"]["decode_chunk"] == pytest.approx(0.095)  # the program is on the path too
    assert sc["by_scope"]["forward_backward"] == pytest.approx(0.005)
    assert sc["unscoped_s"] == pytest.approx(0.010)
    assert not {"while", "body", "main", "jvp", "transpose"} & set(sc["by_scope"])


def test_trace_readers_read_the_cells_newest_traced_run(tmp_path, monkeypatch):
    monkeypatch.setattr(program_trace, "BENCH_DIR", str(tmp_path))
    program_trace.load.cache_clear()
    tf_ops = {_op("fusion.1"): "jit(decode_chunk)/attn/decode_attn/mul:",
              _op("convert.2"): "jit(decode_chunk)/cast_weights/convert_element_type:",
              _op("fusion.3"): "jit(decode_chunk)/mlp/dot_general:"}
    for seed, attn_ms in ((1, 10), (2, 30)):  # the newer run (seed 2) is the one read
        d = tmp_path / "out" / "trace" / f"cellA.seed{seed}.trace1" / "plugins" / "profile" / "t"
        d.mkdir(parents=True)
        ops = [(_op("fusion.1"), 0, attn_ms), (_op("convert.2"), attn_ms, 10), (_op("fusion.3"), 60, 40)]
        host = [("tpu_engine.batcher.other", 0, 59),  # the iteration: what its phases leave is ``other``
                ("tpu_engine.batcher.emit", attn_ms + 10, 15), ("tpu_engine.batcher.idle", 58, 1),
                ("tpu_engine.batcher.other", 60, 40), ("tpu_engine.batcher.emit", 90, 5)]  # a second step, chip busy
        (d / "host.xplane.pb").write_bytes(
            _bytes({"/device:TPU:0": {"XLA Ops": ops}, "/host:CPU": {"engine": host}}, tf_ops))
        time.sleep(0.02)
    run = {"trace": {"busy_s": 0.08}, "cell": {"cell": {"name": "cellA"}}}
    busy = 30 + 10 + 40
    assert manifest.load_reader("attn_time_pct.tpot")(run, "attn_time_pct.tpot") == pytest.approx(100 * 30 / busy)
    assert manifest.load_reader("attn_time_pct.rate")(run, "x") == pytest.approx(100 * 30 / busy)
    # idle 40-60 ms: 15 in emit (40-55), 1 in idle (58-59), 3 in the loop's ``other``, 1 under no name
    assert manifest.load_reader("idle_named_pct.rate")(run, "x") == pytest.approx(100 * 16 / 20)
    assert manifest.load_reader("idle_named_pct.rate")({**run, "trace": None}, "x") is None  # an untraced run
    # a phase's mean over the window's steps: 15 ms in one step and 5 in the other
    assert manifest.load_reader("batcher_emit_ms.batch")(run, "x") == pytest.approx((15 + 5) / 2)
    assert manifest.load_reader("batcher_emit_ms.batch")({**run, "trace": None}, "x") is None
    for name in ("batcher_admit_ms.batch", "batcher_stage_ms.batch", "batcher_prefill_ms.tpot"):
        assert manifest.load_reader(name)(run, name) is None  # no such annotation in this trace
    program_trace.load.cache_clear()


PHASES = {p: {"mean_ms": v + 1, "p50_ms": v, "p95_ms": v + 2} for p, v in
          {"data": 0.2, "dispatch": 1.1, "device": 311.0, "health": 24.5, "anomaly": 0.3, "monitor": 3.5,
           "checkpoint": 0.1, "other": 0.4}.items()}


def test_supervisor_readers_on_a_hand_made_run():
    run = {"profile": {"total": {"p50_ms": 341.1, "mean_ms": 343.0}, "phases": PHASES}}
    assert manifest.load_reader("step_time_program_ms")(run, "step_time_program_ms") == 341.1
    for phase in ("health", "anomaly", "monitor", "checkpoint"):
        name = f"supervisor_phase_ms.{phase}"
        assert manifest.load_reader(name)(run, name) == PHASES[phase]["p50_ms"]
    # the accepted reader becomes true: every phase but the device read
    host = manifest.load_reader("supervisor_host_ms_per_step")(run, "supervisor_host_ms_per_step")
    assert host == pytest.approx(0.2 + 1.1 + 24.5 + 0.3 + 3.5 + 0.1 + 0.4)
    # a parent's program reports the four old phases and nothing else
    old = {"profile": {"total": {"p50_ms": 312.0}, "phases": {p: PHASES[p] for p in ("data", "dispatch", "device", "other")}}}
    assert manifest.load_reader("supervisor_phase_ms.health")(old, "supervisor_phase_ms.health") is None
    assert manifest.load_reader("step_time_program_ms")({"profile": None}, "x") is None


def test_overshoot_reader_on_a_hand_made_run():
    stats = {"slots": 16, "decode_tokens_computed_total": 4000, "decode_tokens_emitted_total": 3700}
    for name in ("decode_overshoot_pct.tpot", "decode_overshoot_pct.rate"):
        assert manifest.load_reader(name)({"engine_stats": stats}, name) == pytest.approx(7.5)
        assert manifest.load_reader(name)({"engine_stats": {"slots": 16}}, name) is None  # a parent's stats
        assert manifest.load_reader(name)({"profile": {}}, name) is None                  # a training run


def test_request_stage_readers_take_the_requests_the_harness_counted():
    from tpu_engine import tracing

    rec = tracing.FlightRecorder()
    tracing.set_recorder(rec)
    try:
        now_wall = time.time()
        # four requests: one of the lead-in, then the three the harness measured
        for submitted, queue, wait, prefill in ((-5.0, 0.1, 0.1, 0.1), (1.0, 0.5, 1.0, 0.25), (3.0, 1.5, 3.0, 0.75),
                                               (10.05, 1.0, 2.0, 0.5)):
            root = rec.start_span("request", kind="serving", t0=now_wall + submitted - 0.001)
            t = now_wall + submitted
            for stage, dur in (("engine_queue", queue), ("prefill_wait", wait), ("prefill", prefill), ("decode", 2.0)):
                rec.record_span(stage, kind="serving", trace_id=root.trace_id, parent=root, t0=t, t1=t + dur)
                t += dur
            root.end(t1=t)
        run = {"loop": "open", "ttft_ms": [1750.0, 5250.0, 3500.0]}  # one entry a measured request
        for name, want in (("engine_queue_ms_p50.chat", 1000.0), ("prefill_wait_ms_p50.ttft", 2000.0),
                           ("prefill_ms_p50.ttft", 500.0)):
            assert manifest.load_reader(name)(run, name) == pytest.approx(want, abs=1.0)
        assert program_trace.request_stage_ms(run, "decode") == pytest.approx([2000.0] * 3, abs=1.0)
        assert manifest.load_reader("prefill_ms_p50.ttft")({"loop": "open", "ttft_ms": []}, "x") is None
        assert manifest.load_reader("prefill_ms_p50.ttft")({**run, "loop": "closed"}, "x") is None
        tracing.set_recorder(tracing.FlightRecorder())  # a parent's program records no such span
        assert manifest.load_reader("engine_queue_ms_p50.chat")(run, "x") is None
    finally:
        tracing.set_recorder(None)
