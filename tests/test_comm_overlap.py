"""The ``comm.py`` knobs must observably change the compiled TPU schedule.

Round-2 VERDICT item 2: the async-collective / latency-hiding flag surface
(``tpu_engine/comm.py:29-37``) had no measurement behind it. This test AOT
compiles one and the same lowered train step twice — knobs ON vs OFF, via
per-compile ``compiler_options`` — and asserts the knobs do real work:
overlap (scheduled start→done distance) expands by at least 2x and the
async-collective fusion pairs appear only in the ON build. The methodology
lives in ``benchmarks/comm_overlap.py``.

A smaller model than the benchmark's 7B keeps the two compiles test-sized.
"""

from __future__ import annotations

import pytest

from tpu_engine.aot import aot_lowered
from benchmarks.comm_overlap import COMM_OFF, COMM_ON, overlap_stats

pytestmark = [pytest.mark.slow, pytest.mark.tpu_aot]


def test_comm_knobs_change_schedule():
    from tpu_engine.aot import TopologyUnavailable

    try:
        lowered = aot_lowered(
            "llama-1b", "v5e:2x4", dict(data=1, fsdp=8), seq=2048,
            overrides={"attention_impl": "flash"},
        )
    except TopologyUnavailable as e:  # only missing libtpu skips
        pytest.skip(f"TPU AOT topology unavailable: {e}")

    on = overlap_stats(lowered.compile(compiler_options=COMM_ON).as_text())
    off = overlap_stats(lowered.compile(compiler_options=COMM_OFF).as_text())

    # There are collectives to overlap in the first place (ZeRO-3 gathers).
    assert on["async_total"] + on["async_fusion_pairs"] + on["blocking_total"] > 0
    # The OFF build must not carry async-collective fusion pairs...
    assert off["async_fusion_pairs"] == 0
    # ...and the ON build must overlap at least twice as far as OFF.
    assert on["overlap_distance_mean"] >= 2 * max(off["overlap_distance_mean"], 1), (
        on,
        off,
    )
