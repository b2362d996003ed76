"""``harness/program_threads`` and the seven readers over it, on a hand-built
trace whose numbers are known exactly: an engine loop on one host line, the
scheduler's pump and a client on two others."""

import time

import pytest
from test_program_trace import _bytes, _data, _op

from harness import manifest, program_threads, program_trace

# The chip: a decode program 10-60 (the read of it returns at 72), one 110-150
# (read at 151), one 210-230 that ended before its read began at 240.
OPS = [(_op("fusion.1"), 10, 50), (_op("fusion.1"), 110, 40), (_op("fusion.1"), 210, 20)]
MODULES = [("jit_decode_chunk", 10, 50), ("jit_decode_chunk", 110, 40), ("jit_decode_chunk", 210, 20)]
ENGINE = [
    ("tpu_engine.batcher.other", 0, 100, {"blocked_us": 70000}),
    ("tpu_engine.batcher.stage", 2, 8, {"with_prefill": 0, "blocked_us": 5000}),
    ("tpu_engine.batcher.device", 10, 62, {"blocked_us": 61000}),
    ("tpu_engine.batcher.emit", 72, 8, {"blocked_us": 1000}),
    ("tpu_ctl.fleet.result", 85, 10, {"thread": "engine"}),  # on the loop's own line: not beside
    ("tpu_engine.batcher.other", 100, 100, {"blocked_us": 60000}),
    ("tpu_engine.batcher.prefill", 101, 4, {"tokens": 128, "blocked_us": 2000}),
    ("tpu_engine.batcher.stage", 105, 5, {"with_prefill": 1, "blocked_us": 0}),
    ("tpu_engine.batcher.device", 110, 41, {"blocked_us": 40000}),
    ("tpu_engine.batcher.idle", 160, 10, {"prefilling": 1, "queued": 0, "blocked_us": 10000}),
    ("tpu_engine.batcher.other", 200, 50, {"blocked_us": 12000}),
    ("tpu_engine.batcher.stage", 205, 5, {"with_prefill": 0, "blocked_us": 0}),
    ("tpu_engine.batcher.device", 240, 5, {"blocked_us": 4000}),
    ("tpu_engine.batcher.other", 250, 10, {"blocked_us": 9000}),  # an empty step: no dispatch
    ("tpu_engine.batcher.idle", 251, 9, {"prefilling": 0, "queued": 0, "blocked_us": 9000}),
]
PUMP = [("tpu_ctl.scheduler.pass", 60, 30, {"thread": "fleet-scheduler", "queued": 0, "running": 1}),
        ("tpu_ctl.manager.fleet_status", 62, 26, {"thread": "fleet-scheduler"}),
        ("tpu_ctl.scheduler.pass", 190, 30, {"thread": "fleet-scheduler", "queued": 0, "running": 1})]
CLIENT = [("tpu_ctl.fleet.result", 152, 4, {"thread": "MainThread", "fid": "req_1"})]
PLANES = {"/device:TPU:0": {"XLA Ops": OPS, "XLA Modules": MODULES},
          "/host:CPU": {"engine": ENGINE, "pump": PUMP, "client": CLIENT}}


def test_the_loops_line_its_iterations_and_what_ran_beside_it():
    tr = program_threads.read(_data(PLANES))
    assert tr["loop"] == "batcher" and len(tr["iterations"]) == 4
    assert [len(it["phases"]) for it in tr["iterations"]] == [3, 4, 2, 1]
    assert len(program_threads.dispatches(tr)) == 3
    beside = {(name, round(a / 1e6)) for a, _, name, _, other_line in tr["spans"] if other_line}
    assert beside == {("tpu_ctl.scheduler.pass", 60), ("tpu_ctl.manager.fleet_status", 62),
                      ("tpu_ctl.scheduler.pass", 190), ("tpu_ctl.fleet.result", 152)}
    # the engine thread's own fleet.result is a span, but not beside the loop
    assert [s[4] for s in tr["spans"] if round(s[0] / 1e6) == 85] == [False]
    # an iteration's length less the chip's busy time inside it
    assert program_threads.dispatch_host_ms(tr) == pytest.approx([100 - 50, 100 - 40, 50 - 20])
    # the iteration's blocked seconds less those of prefill, device and idle
    assert program_threads.blocked_ms(tr) == pytest.approx([70 - 61, 60 - 2 - 40 - 10, 12 - 4])
    # 72 - 60, 151 - 150, and 0 where the program had ended before the read began
    assert program_threads.read_lags_ms(tr) == pytest.approx([12, 1, 0])
    # idle 60-110 and 150-210 (the window is first op to last): the pump covers 60-90 and 190-210, the
    # client 152-156; the engine's own span (85-95) adds nothing
    idle = program_threads.idle_beside(tr)
    assert idle["idle_s"] == pytest.approx(0.110) and idle["beside_s"] == pytest.approx(0.054)
    assert idle["by_span"] == pytest.approx({"tpu_ctl.scheduler.pass": 0.050, "tpu_ctl.manager.fleet_status": 0.026,
                                             "tpu_ctl.fleet.result": 0.004})
    # inside the device's window, 10-230
    assert program_threads.span_seconds(tr, "tpu_ctl.scheduler.pass") == pytest.approx(0.060)
    assert program_threads.span_period_ms(tr, "tpu_ctl.scheduler.pass") == pytest.approx(130)
    assert program_threads.span_seconds(tr, "tpu_ctl.no.such") is None


NEW = ("dispatch_host_ms.rate", "loop_blocked_ms.rate", "read_lag_ms_p90.rate", "idle_beside_ctl_pct.rate",
       "scheduler_pass_busy_pct.burst", "batcher_idle_ms.rate", "health_sample_ms.train")


def _traced(tmp_path, monkeypatch, cell, planes):
    monkeypatch.setattr(program_trace, "BENCH_DIR", str(tmp_path))
    program_trace.load.cache_clear()
    program_threads.load.cache_clear()
    d = tmp_path / "out" / "trace" / f"{cell}.seed1.trace1" / "plugins" / "profile" / "t"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(_bytes(planes))
    time.sleep(0.02)
    return {"trace": {"busy_s": 0.11, "window_s": 0.22}, "cell": {"cell": {"name": cell}}}


def test_the_readers_on_the_hand_built_trace(tmp_path, monkeypatch, capsys):
    run = _traced(tmp_path, monkeypatch, "cellT", PLANES)
    read = lambda name: manifest.load_reader(name)(run, name)  # noqa: E731
    assert read("dispatch_host_ms.rate") == pytest.approx((50 + 60 + 30) / 3)
    said = capsys.readouterr().out
    assert '"mean_with_prefill": 60.0' in said  # the split by what the dispatch carried
    assert '"cycle_ms_p50": 100.0' in said  # iterations of 100, 100 and 50 ms
    assert read("loop_blocked_ms.rate") == pytest.approx((9 + 8 + 8) / 3)
    assert '"sem": ' in capsys.readouterr().out  # the mean's standard error over the window's dispatches
    assert read("read_lag_ms_p90.rate") == pytest.approx(12, abs=2.3)  # of 0, 1 and 12
    assert read("idle_beside_ctl_pct.rate") == pytest.approx(100 * 54 / 110)
    assert read("scheduler_pass_busy_pct.burst") == pytest.approx(100 * 60 / 220)
    assert '"pass_period_ms_p50": 130.0' in capsys.readouterr().out
    assert read("batcher_idle_ms.rate") == pytest.approx((10 + 9) / 4)  # over every iteration of the window
    assert '"with_work": 1' in capsys.readouterr().out
    assert read("health_sample_ms.train") is None  # a batcher's trace holds no such annotation
    for name in NEW:
        assert manifest.load_reader(name)({**run, "trace": None}, name) is None  # an untraced run


def test_a_supervisors_trace_and_a_program_that_took_no_wait(tmp_path, monkeypatch):
    loop = [("tpu_engine.supervisor.other", 0, 100, {"blocked_us": 90000}),
            ("tpu_engine.supervisor.dispatch", 1, 4, {"step": 3, "blocked_us": 1000}),
            ("tpu_engine.supervisor.device", 5, 85, {"step": 3, "blocked_us": 60000}),
            ("tpu_engine.supervisor.health_sample", 6, 25, {"step": 3}),
            ("tpu_ctl.manager.fleet_status", 7, 23, {"thread": "train-job"}),
            ("tpu_engine.supervisor.monitor", 90, 8, {"step": 3, "blocked_us": 6000})]
    planes = {"/device:TPU:0": {"XLA Ops": [(_op("fusion.1"), 5, 75)], "XLA Modules": [("jit_train_step", 5, 75)]},
              "/host:CPU": {"job": loop, "pump": [("tpu_ctl.scheduler.pass", 70, 25, {"thread": "fleet-scheduler"})]}}
    run = _traced(tmp_path, monkeypatch, "cellS", planes)
    read = lambda name: manifest.load_reader(name)(run, name)  # noqa: E731
    assert read("health_sample_ms.train") == pytest.approx(25)
    assert read("loop_blocked_ms.train") == pytest.approx(90 - 60)  # every phase but device, ``other`` among them
    assert read("read_lag_ms_p90.train") == pytest.approx(10)  # the program ended at 80, the read returned at 90
    assert read("dispatch_host_ms.tpot") is None and read("batcher_idle_ms.tpot") is None  # no batcher here
    assert read("scheduler_pass_busy_pct.train") == pytest.approx(100 * 10 / 75)  # 70-80 of the window 5-80


def test_a_parents_trace_without_the_new_names_reads_none(tmp_path, monkeypatch):
    """The parent's program has the phases and the health sample, but no
    ``blocked_us`` on them and no ``tpu_ctl.*`` span."""
    bare = lambda evs: [ev[:3] + ({k: v for k, v in ev[3].items() if k != "blocked_us"},)  # noqa: E731
                        for ev in evs if not ev[0].startswith("tpu_ctl.")]
    planes = {"/device:TPU:0": PLANES["/device:TPU:0"],
              "/host:CPU": {"engine": bare(ENGINE) + [("tpu_engine.supervisor.health_sample", 6, 25, {"step": 3})]}}
    assert program_threads.read(_data(planes)) is None
    run = _traced(tmp_path, monkeypatch, "cellP", planes)
    for name in NEW:
        assert manifest.load_reader(name)(run, name) is None, name
    assert manifest.load_reader("idle_named_pct.rate")(run, "x") is not None  # the readers it had still read
    program_trace.load.cache_clear()
    program_threads.load.cache_clear()
