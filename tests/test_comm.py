"""Comm-tuning surface: which variable delivers which flag, safe
application, and truthful in-force reporting."""

import logging

import jax
import pytest

import tpu_engine.comm as comm
from tpu_engine.comm import apply_comm_flags, comm_flags_status
from tpu_engine.sharding import TPUTrainConfig


@pytest.fixture(autouse=True)
def _clean_env(monkeypatch):
    monkeypatch.delenv("LIBTPU_INIT_ARGS", raising=False)
    monkeypatch.setenv("XLA_FLAGS", "--xla_force_host_platform_device_count=8")


def test_tpu_options_ride_libtpu_init_args_never_xla_flags(monkeypatch):
    """jaxlib's XLA_FLAGS parser aborts the process on libtpu's xla_tpu_*
    options (seen on the chip, PR 21); libtpu takes them from
    LIBTPU_INIT_ARGS. xla_extra_flags is the operator's XLA_FLAGS addition."""
    import os

    monkeypatch.setattr(comm, "_backend_initialized", lambda: False)
    apply_comm_flags(TPUTrainConfig(xla_extra_flags="--xla_foo=1"))
    libtpu, xla = os.environ["LIBTPU_INIT_ARGS"], os.environ["XLA_FLAGS"]
    assert "--xla_tpu_enable_async_collective_fusion=true" in libtpu
    assert "--xla_tpu_enable_latency_hiding_scheduler=true" in libtpu
    assert "--xla_latency_hiding_scheduler_rerun=1" in libtpu
    assert "xla_tpu" not in xla and "latency_hiding" not in xla
    assert xla.split()[-1] == "--xla_foo=1" and "--xla_foo" not in libtpu
    # Idempotent: a second apply adds nothing.
    apply_comm_flags(TPUTrainConfig(xla_extra_flags="--xla_foo=1"))
    assert os.environ["LIBTPU_INIT_ARGS"] == libtpu
    assert os.environ["XLA_FLAGS"] == xla


def test_flags_toggle_off(monkeypatch):
    import os

    monkeypatch.setattr(comm, "_backend_initialized", lambda: False)
    cfg = TPUTrainConfig(async_collectives=False, latency_hiding_scheduler=False)
    apply_comm_flags(cfg)
    assert "LIBTPU_INIT_ARGS" not in os.environ
    st = comm_flags_status(cfg)
    assert st["requested"] == [] and st["in_force"]


def test_apply_leaves_a_live_backend_alone_and_says_so(caplog):
    """Once the backend is up the variables have been read: apply must not
    edit them (that would make later status reads lie), and must warn."""
    import os

    jax.devices()  # ensure initialised
    before = dict(os.environ)
    with caplog.at_level(logging.WARNING, logger="tpu_engine.comm"):
        apply_comm_flags(TPUTrainConfig(xla_extra_flags="--xla_never_applied=1"))
    assert os.environ.get("XLA_FLAGS") == before.get("XLA_FLAGS")
    assert "LIBTPU_INIT_ARGS" not in os.environ
    assert any("already initialised" in r.message for r in caplog.records)
    st = comm_flags_status(TPUTrainConfig())
    assert not st["in_force"] and "LIBTPU_INIT_ARGS" in st["reason"]
    assert "--xla_tpu_overlap_compute_collective_tc=true" in st["requested"]


def test_apply_respects_operator_value(monkeypatch):
    # Operator's explicit --flag=false must not be overridden by our =true.
    import os

    monkeypatch.setattr(comm, "_backend_initialized", lambda: False)
    monkeypatch.setenv(
        "LIBTPU_INIT_ARGS", "--xla_tpu_enable_latency_hiding_scheduler=false"
    )
    apply_comm_flags(TPUTrainConfig(async_collectives=False))
    flags = os.environ["LIBTPU_INIT_ARGS"]
    assert flags.count("--xla_tpu_enable_latency_hiding_scheduler") == 1
    assert "--xla_tpu_enable_latency_hiding_scheduler=false" in flags
    # But genuinely-new flags were appended.
    assert "--xla_latency_hiding_scheduler_rerun=1" in flags


def test_delivered_tpu_options_need_a_tpu_backend(monkeypatch):
    """Delivered in time, but this process runs on CPU: the TPU compiler
    options are still not in force."""
    monkeypatch.setattr(comm, "_backend_initialized", lambda: False)
    cfg = TPUTrainConfig()
    apply_comm_flags(cfg)
    st = comm_flags_status(cfg)
    assert not st["in_force"] and "'cpu'" in st["reason"]


def test_job_reports_undelivered_flags_as_not_in_force():
    """A job started in a long-lived process (backend already up) runs
    without its default-on flags — its plan and describe() must say so
    rather than echo the config's True."""
    from tpu_engine import TPULauncher
    from tpu_engine.mesh_runtime import MeshConfig
    from tpu_engine.sharding import Precision, ShardingStage

    cfg = TPUTrainConfig(
        model_name="gpt-tiny", sharding_stage=ShardingStage.DISABLED,
        mesh=MeshConfig(data=8), micro_batch_size=1, seq_len=16,
        precision=Precision.FP32, activation_checkpointing=False,
    )
    assert cfg.async_collectives and cfg.latency_hiding_scheduler
    launcher = TPULauncher()
    res = launcher.launch(cfg, max_steps=1, block=True)
    assert res.plan["comm_flags"]["in_force"] is False
    desc = launcher.get_job(res.job_id).describe()
    assert desc["status"] == "completed", desc["error"]
    assert desc["comm_flags"]["in_force"] is False
    assert desc["comm_flags"]["requested"]
    assert desc["attention_impl"] == "xla"  # auto, off-TPU
