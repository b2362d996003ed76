"""The trace reducer on traces whose numbers are known exactly: XSpace text
protos written here, in the layout a v5e trace has (``/device:TPU:n`` planes
with ``XLA Ops`` and ``XLA Modules`` lines, HLO instructions as event names,
harness spans on ``/host:CPU``)."""

import pytest

from harness import trace_reduce

MS = 1_000_000_000  # picoseconds per millisecond


def _xspace(planes) -> str:
    """planes: {plane: {line: [(name, start_ms, dur_ms), ...]}}"""
    out = []
    for pname, lines in planes.items():
        names = sorted({n for evs in lines.values() for n, _, _ in evs})
        ids = {n: i + 1 for i, n in enumerate(names)}
        body = [f'name: "{pname}"']
        for lname, evs in lines.items():
            ev_txt = " ".join(
                f"events {{ metadata_id: {ids[n]} offset_ps: {int(s * MS)} duration_ps: {int(d * MS)} }}"
                for n, s, d in evs)
            body.append(f'lines {{ name: "{lname}" timestamp_ns: 0 {ev_txt} }}')
        for n, i in ids.items():
            body.append(f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}')
        out.append("planes { " + " ".join(body) + " }")
    return "\n".join(out)


def _reduce(planes, chips=1):
    from jax.profiler import ProfileData

    return trace_reduce.reduce_data(ProfileData.from_text_proto(_xspace(planes)), chips)


def test_known_idle_kernel_and_collective_time():
    ops = [("fusion.1", 0, 40), ("flash_fwd custom-call tpu_custom_call", 40, 20),
           ("all-gather-done.3", 60, 10),            # exposed: the core waits in it
           ("fusion.2", 100, 50),                    # 30 ms idle before it
           ("flash_fwd custom-call tpu_custom_call", 150, 20), ("reduce-scatter.9", 170, 5),
           ("fusion.1", 190, 10)]                    # 15 ms idle before it
    red = _reduce({
        "/device:TPU:0": {"XLA Ops": ops,
                          "XLA Modules": [("jit_train_step(1)", 0, 70), ("jit_train_step(1)", 100, 100)]},
        "/host:CPU": {"main": [("onchip.data_fn", 72, 20), ("onchip.client.wait", 176, 10),
                               ("something else", 0, 200)]},
    })
    assert red["window_s"] == pytest.approx(0.200)
    assert red["busy_s"] == pytest.approx(0.155)
    assert 1 - red["busy_s"] / red["window_s"] == pytest.approx(0.225)
    assert sum(red["kernel_seconds"].values()) == pytest.approx(0.040)
    # time the core's serial op line spends inside a collective is exposed time; a
    # four-chip cell's reader sums it from the per-op seconds
    assert sum(v for k, v in red["op_seconds"].items()
               if k.startswith(("all-gather", "reduce-scatter"))) == pytest.approx(0.015)
    assert red["module_gaps_s"] == pytest.approx([0.030])
    assert red["breakdown"]["device_ops"][0][0] == "fusion.1" or red["breakdown"]["device_ops"][0][1] == pytest.approx(0.050)
    gaps = red["breakdown"]["idle_gaps"]
    assert gaps[0] == ["data_fn", pytest.approx(0.030)] and gaps[1] == ["client.wait", pytest.approx(0.015)]
    assert len(red["breakdown"]["device_ops"]) <= 10 and len(gaps) <= 10


def test_an_idle_gap_takes_the_programs_innermost_phase_before_a_harness_span():
    """``tpu_engine.<loop>.<phase>`` annotations nest inside the harness's
    ``onchip.<loop>.step`` and inside the loop's own ``other``, which spans the
    whole iteration; the main thread's ``onchip.client.wait`` is shorter than
    either and merely concurrent."""
    ops = [("fusion.1", 0, 40), ("fusion.1", 70, 30),      # 30 ms idle, middle at 55
           ("fusion.1", 120, 20),                          # 20 ms idle, middle at 110
           ("fusion.1", 150, 10)]                          # 10 ms idle, middle at 145
    red = _reduce({
        "/device:TPU:0": {"XLA Ops": ops},
        "/host:CPU": {"engine": [("onchip.batcher.step", 35, 100), ("tpu_engine.batcher.other", 36, 98),
                                 ("tpu_engine.batcher.admit", 45, 25)],
                      "main": [("onchip.client.wait", 50, 10), ("onchip.client.wait", 141, 8)]},
    })
    assert red["breakdown"]["idle_gaps"] == [["batcher.admit", pytest.approx(0.030)],
                                             ["batcher.other", pytest.approx(0.020)],
                                             ["client.wait", pytest.approx(0.010)]]


def test_busy_is_averaged_over_the_chips_used():
    mk = lambda busy: {"XLA Ops": [("fusion.1", 0, busy), ("fusion.1", 90, 10)]}  # noqa: E731
    red = _reduce({"/device:TPU:0": mk(50), "/device:TPU:1": mk(30), "/device:TPU:2": mk(90),
                   "/device:TPU:3": mk(70)}, chips=4)
    assert red["chips_traced"] == 4 and red["window_s"] == pytest.approx(0.100)
    assert red["busy_s"] == pytest.approx((60 + 40 + 100 + 80) / 4 / 1000)


def test_a_trace_in_which_nothing_ran_on_the_device_is_refused():
    with pytest.raises(SystemExit):
        _reduce({"/host:CPU": {"main": [("onchip.data_fn", 0, 5)]}})
