"""Supervised in-process training jobs: launch, monitor, rollback, resume.

The reference launches training as a fire-and-forget subprocess — stdout
piped and dropped, only the pid kept, no tracking after launch
(``ai_engine/deepspeed_launcher.py:354-362``; SURVEY.md §5 "no failure
detector for a running job"). Here the training task is an in-process thread
the supervisor actually owns:

- every step's metrics feed the :class:`~tpu_engine.loss_monitor.LossSpikeMonitor`
  directly (no HTTP hop for the local case — SURVEY.md §3.3);
- a critical divergence/spike alert triggers halt → restore last *stable*
  checkpoint → cut LR → continue (mechanising the remediation strings at
  reference ``loss_monitor.py:131-136,167-172``);
- periodic async Orbax saves; a checkpoint is marked stable only after a
  healthy margin of steps passes with no critical alert;
- preemption (metadata, SIGTERM, or the simulation seam) triggers a
  synchronous emergency save (``tpu_engine/preemption.py``);
- on restart, a job with the same checkpoint directory auto-resumes from the
  newest loadable checkpoint (corrupt ones are quarantined) — MTTR is
  bounded by restore + one warm compile (persistent XLA compilation cache).
"""

from __future__ import annotations

import logging
import math
import tempfile
import threading
import time
import traceback
from collections import deque
from enum import Enum
from typing import Any, Callable, Iterator, Optional, Sequence

import jax

from tpu_engine import tracing
from tpu_engine.checkpoint import TrainCheckpointManager, abstract_state_like
from tpu_engine.loss_monitor import (
    AlertSeverity,
    LossSpikeMonitor,
    MonitorConfig,
    TrainingMetrics,
)
from tpu_engine import telemetry
from tpu_engine.preemption import PreemptionWatcher
from tpu_engine.profiler import StepProfiler, pipeline_tick_account
from tpu_engine.sharding import TPUTrainConfig
from tpu_engine.train import TrainProgram, build_train_program

log = logging.getLogger(__name__)

# Phases of the training loop's body, on its phase clock (StepProfiler):
# ``describe()["profile"]["phases"]`` and the ``tpu_engine.supervisor.<phase>``
# trace annotations carry these names.
SUPERVISOR_PHASES = (
    "data",        # data_fn / synthetic batch
    "dispatch",    # trace-cache hit + async enqueue of the jitted step
    "device",      # the live fleet sample, then the blocking jax.device_get(metrics):
                   # device execution lands here, the sample runs under it
    "health",      # fault seams + the verdict on that sample (_unhealthy_mesh_devices)
    "anomaly",     # step-time detector, attribution, hetero tracker and consult
    "monitor",     # monitor.ingest, _log_metrics, alert handling
    "checkpoint",  # eval, periodic save, _advance_stable
)


def _perplexity(loss: float) -> float:
    """exp(loss), clamped so a divergence spike can't overflow to inf."""
    return math.exp(min(loss, 30.0))


class JobStatus(str, Enum):
    PENDING = "pending"
    COMPILING = "compiling"
    RUNNING = "running"
    COMPLETED = "completed"
    FAILED = "failed"
    STOPPED = "stopped"
    PREEMPTED = "preempted"


class TrainingJob:
    """One supervised training run (thread-owned)."""

    def __init__(
        self,
        job_id: str,
        config: TPUTrainConfig,
        program: Optional[TrainProgram] = None,
        data_fn: Optional[Callable[[int], jax.Array]] = None,
        monitor_config: Optional[MonitorConfig] = None,
        max_steps: Optional[int] = None,
        auto_rollback: bool = True,
        lr_cut_on_rollback: float = 0.5,
        max_rollbacks: int = 3,
        stable_margin_steps: int = 50,
        watch_preemption: bool = False,
        install_signal_handlers: bool = False,
        simulate_preemption_check: Optional[Callable[[], bool]] = None,
        devices: Optional[Sequence[jax.Device]] = None,
        fault_injector: Optional[Any] = None,
        fleet_fn: Optional[Callable[[], Any]] = None,
        self_heal: Optional[bool] = None,
        health_check_interval_steps: int = 1,
        emergency_save_retries: int = 3,
        emergency_save_backoff_s: float = 0.05,
        trace_id: Optional[str] = None,
        anomaly_detection: bool = True,
        anomaly_detector: Optional[tracing.StepTimeAnomalyDetector] = None,
        anomaly_trace_session: Optional[Any] = None,
        anomaly_trace_dir: Optional[str] = None,
        hetero_detection: bool = True,
        hetero_rebalancer: Optional[Any] = None,
        hetero_check_interval_steps: int = 25,
        hetero_dry_run: bool = True,
    ):
        self.job_id = job_id
        self.config = config
        self.program = program
        self.data_fn = data_fn
        self.monitor = LossSpikeMonitor(job_id=job_id, config=monitor_config)
        self.max_steps = max_steps if max_steps is not None else config.total_steps
        self.auto_rollback = auto_rollback
        self.lr_cut_on_rollback = lr_cut_on_rollback
        self.max_rollbacks = max_rollbacks
        self.stable_margin_steps = stable_margin_steps

        # Device pinning / elastic seam: None = all visible devices. A job
        # resumed on a different-sized slice records the auto-selected
        # shape in ``elastic_mesh`` (None = ran at the configured mesh).
        self._devices = list(devices) if devices is not None else None
        self.elastic_mesh: Optional[dict[str, int]] = None
        # The effective batch this job DECLARES — captured NOW, before any
        # elastic resize can shrink the world that a ``data=-1`` mesh
        # resolves against. ``elastic_target_batch_size`` overrides for
        # cross-process resumes where construction already happens on the
        # shrunken slice (the -1 re-resolution hazard; see the config
        # field's docstring).
        self._declared_batch = (
            config.elastic_target_batch_size
            if config.elastic_target_batch_size is not None
            else config.effective_batch_size
        )

        # Self-healing / fault-injection seams. A private injector wins;
        # otherwise the process-active one (tpu_engine.faults.get_active)
        # is consulted per step. fleet_fn gives the loop a live health view
        # (the scheduler wires TPUManager.get_fleet_status here); self_heal
        # defaults to the config's elastic_resume — a job that declared
        # elasticity wants to survive chip loss, one that didn't should
        # fail loudly as before.
        self.fault_injector = fault_injector
        self.fleet_fn = fleet_fn
        self.self_heal = self_heal if self_heal is not None else bool(config.elastic_resume)
        self.health_check_interval_steps = max(1, int(health_check_interval_steps))
        self.emergency_save_retries = emergency_save_retries
        self.emergency_save_backoff_s = emergency_save_backoff_s
        #: None | detected | saving | saved | save-failed — the recovery
        #: state machine position, surfaced via describe()/HTTP.
        self.recovery_state: Optional[str] = None
        self.recovery_events: list[dict[str, Any]] = []
        self.unhealthy_devices: list[int] = []
        # The fleet samples taken under the step (_sample_fleet): how many,
        # and each one's host seconds over the phase clock's window.
        self.health_samples_total = 0
        self._health_sample_s: deque[float] = deque()

        # Flight-recorder identity: the scheduler passes its submission's
        # trace so every attempt chains under one lifecycle root; a
        # standalone job gets its own trace lazily when the loop starts.
        self.trace_id = trace_id
        # Step-time anomaly attribution (Poplar-style: per-step wall time
        # is the health signal). The detector flags outliers against a
        # sliding baseline; the recorder attributes each to the span/event
        # overlapping that step's wall window. A sustained regression can
        # auto-start a bounded XPlane capture via anomaly_trace_session
        # (any object with TraceSession's start(log_dir, duration_s)).
        self._anomaly = anomaly_detector or (
            tracing.StepTimeAnomalyDetector(series_labels={"job": job_id})
            if anomaly_detection
            else None
        )
        self._anomaly_trace_session = anomaly_trace_session
        self._anomaly_trace_dir = anomaly_trace_dir
        self._auto_trace_started = False
        self._prev_step_end_ts: Optional[float] = None
        self.anomalies_total = 0
        self.last_anomaly: Optional[dict[str, Any]] = None
        # Heterogeneity plane (tpu_engine/hetero.py): per-host throughput
        # EMA + hysteresis-guarded rebalance of the data split. Dry-run by
        # default — the detector and audit trail run everywhere, but the
        # live row reassignment is opt-in per job.
        self.hetero_detection = hetero_detection
        self._hetero = hetero_rebalancer
        self.hetero_check_interval_steps = max(1, int(hetero_check_interval_steps))
        self.hetero_dry_run = hetero_dry_run
        self.hetero_rebalances_total = 0
        self._last_slow_proc: Optional[int] = None

        self.status = JobStatus.PENDING
        self.error: Optional[str] = None
        self.rollback_count = 0
        self.resumed_from_step: Optional[int] = None
        self.resumed_via_reshard: Optional[dict] = None
        #: What the built program holds: the resolved attention kernel and
        #: the comm-flag delivery found at build time
        #: (tpu_engine.comm.comm_flags_status). None before the build.
        self.attention_impl: Optional[str] = None
        self.comm_flags: Optional[dict[str, Any]] = None
        self._topology_written = False
        self.preemption_reason: Optional[str] = None
        self.started_at: Optional[float] = None
        self.finished_at: Optional[float] = None
        self.last_step_time_s: Optional[float] = None
        self.tokens_per_sec: Optional[float] = None
        self.current_step: int = 0
        self.profiler: Optional[StepProfiler] = None
        self._dataset: Any = None
        self._eval_dataset: Any = None
        self._eval_data_fn: Optional[Callable[[int], jax.Array]] = None
        self._eval_source: Optional[str] = None  # "file" | "synthetic"
        # (step, eval_loss) pairs, newest last; bounded (reference's unbounded
        # metric lists were a leak — SURVEY.md §3.3).
        self.eval_history: list[tuple[int, float]] = []
        self._max_eval_history = 1000
        # LoRA sampling: (step, merged params) — repeated /generate calls at
        # the same step reuse the merge instead of re-materialising it.
        self._merged_cache: Optional[tuple[int, Any]] = None
        self._metrics_file = None  # JSONL sink (config.metrics_log_path)

        self._state: Any = None
        self._state_lock = threading.Lock()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._last_critical_step = -1
        self._pending_stable: list[int] = []

        self.ckpt: Optional[TrainCheckpointManager] = None
        if config.checkpoint_dir:
            self.ckpt = TrainCheckpointManager(
                config.checkpoint_dir,
                max_to_keep=config.max_checkpoints_to_keep,
                save_interval_steps=1,
                fault_injector=fault_injector,
            )

        self.watcher: Optional[PreemptionWatcher] = None
        if watch_preemption:
            kwargs: dict[str, Any] = {}
            if simulate_preemption_check is not None:
                # Test seam: poll the injected check fast instead of GCE metadata.
                kwargs = {
                    "metadata_check": simulate_preemption_check,
                    "check_interval_s": 0.05,
                }
            self.watcher = PreemptionWatcher(
                on_preemption=self._on_preemption,
                install_signal_handlers=install_signal_handlers,
                **kwargs,
            )

    # -- lifecycle -----------------------------------------------------------

    def start(self) -> None:
        self._thread = threading.Thread(target=self._run, daemon=True, name=f"job-{self.job_id}")
        self._thread.start()

    def stop(self, timeout: float = 30.0) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=timeout)

    def join(self, timeout: Optional[float] = None) -> None:
        if self._thread is not None:
            self._thread.join(timeout=timeout)

    @property
    def is_alive(self) -> bool:
        return self._thread is not None and self._thread.is_alive()

    def release_device_state(self) -> None:
        """Give the chips back: drop the train state, the compiled program
        (and the constants it captured) and the merged-LoRA cache. For an
        attempt that is over — a terminal job being deleted, or a preempted
        attempt whose successor resumes from the checkpoint. ``describe()``
        keeps working; sampling and export raise "no initialized state".
        Until this runs a finished job's params and optimizer state stay
        resident (they serve ``generate``/``export``), and on a full-width
        job that alone keeps the next one from being admitted."""
        # A terminal status is set just before the thread's own clean-up
        # (closing datasets, waiting out an async save) — let that finish.
        self.join(timeout=30.0)
        if self.is_alive:
            raise RuntimeError(
                f"job '{self.job_id}' is still running; stop it first"
            )
        with self._state_lock:
            self._state = None
            self._merged_cache = None
            self.program = None
        self.data_fn = None
        self._eval_data_fn = None

    # -- preemption ----------------------------------------------------------

    def _on_preemption(self, reason: str) -> None:
        """Emergency path: flag stop; the train loop does the synchronous save."""
        log.warning("job %s: preemption (%s) — emergency checkpoint", self.job_id, reason)
        self.preemption_reason = reason
        tracing.get_recorder().event(
            "preemption",
            kind="preempt_drain",
            trace_id=self.trace_id,
            attrs={"reason": reason, "step": self.current_step},
        )
        self._stop.set()

    # -- self-healing ---------------------------------------------------------

    def _injector(self):
        if self.fault_injector is not None:
            return self.fault_injector
        from tpu_engine import faults

        return faults.get_active()

    def _record_recovery(self, kind: str, step: int, detail: str = "") -> None:
        self.recovery_events.append(
            {"kind": kind, "step": step, "detail": detail, "timestamp": time.time()}
        )
        del self.recovery_events[:-100]
        tracing.get_recorder().event(
            f"recovery:{kind}",
            kind="recovery",
            trace_id=self.trace_id,
            attrs={"job_id": self.job_id, "step": step, "detail": detail},
        )
        inj = self._injector()
        if inj is not None:
            inj.record(f"recovery:{kind}", step=step, detail=f"job {self.job_id}: {detail}")

    def _health_poll_due(self, step: int) -> bool:
        """Whether the iteration that reaches ``step`` judges fleet health."""
        return (
            self.self_heal
            and self.preemption_reason is None
            and step % self.health_check_interval_steps == 0
        )

    def _sample_fleet(self, it: int) -> Any:
        """The live fleet view for iteration ``it``'s health verdict, or None
        where ``fleet_fn`` raises. The loop calls it between the step's
        dispatch and the blocking read of its metrics, so its host time
        (``describe()["health_sample_ms"]``) runs under the chip's step
        instead of after it; the annotation shows where it ran in a trace."""
        with jax.profiler.TraceAnnotation("tpu_engine.supervisor.health_sample", step=it):
            t0 = time.perf_counter()
            try:
                return self.fleet_fn()
            except Exception:
                return None
            finally:
                self._health_sample_s.append(time.perf_counter() - t0)
                self.health_samples_total += 1

    def _unhealthy_mesh_devices(self, fleet: Any) -> list[int]:
        """Fleet device indices that are CRITICAL *and* inside this job's
        mesh: the verdict on ``fleet`` (:meth:`_sample_fleet`'s view, None
        for no view) and on the injector's chip overlay as it stands now.
        This job's own HBM footprint and duty cycle never read as a
        failure, mid-step or between steps: the fleet view does not
        classify load on chips that carry this control plane's job claims
        (``TPUDevice.carries_own_load``).

        Latency: the view was taken when the step was dispatched, about one
        step's device time before it is judged here. A chip that is CRITICAL
        at dispatch, and every injected fault (the overlay is read here,
        after ``observe_step``), starts the self-heal at that same step; a
        chip that turns CRITICAL while the step runs is seen by the next
        iteration's sample and heals one step later, never more."""
        prog = self.program
        if prog is None:
            return []
        mesh_ids = {int(d.id) for d in prog.runtime.mesh.devices.flat}
        try:
            all_devs = list(jax.devices())
        except Exception:
            all_devs = []

        def in_mesh(fleet_index: int) -> bool:
            return (
                0 <= fleet_index < len(all_devs)
                and int(all_devs[fleet_index].id) in mesh_ids
            )

        bad: set[int] = set()
        inj = self._injector()
        if inj is not None:
            from tpu_engine.faults import FaultKind

            for idx, kind in inj.chip_overlay().items():
                if kind is FaultKind.CHIP_UNHEALTHY and in_mesh(idx):
                    bad.add(idx)
        if fleet is not None:
            from tpu_engine.tpu_manager import TPUHealthStatus

            for dev in fleet.devices:
                if dev.health_status == TPUHealthStatus.CRITICAL and in_mesh(dev.index):
                    bad.add(dev.index)
        return sorted(bad)

    def _begin_self_heal(self, step: int, bad: list[int]) -> None:
        """Detect → (loop exit) → emergency save → PREEMPTED → scheduler
        requeues and re-admits on the healthy remainder (elastic shrink)."""
        self.unhealthy_devices = bad
        self.recovery_state = "detected"
        self._record_recovery("detected", step, f"unhealthy mesh device(s) {bad}")
        log.warning(
            "job %s: unhealthy device(s) %s in live mesh at step %d — "
            "self-healing: emergency save then elastic requeue",
            self.job_id, bad, step,
        )
        # Riding the preemption path gives us the whole proven machinery:
        # synchronous save, PREEMPTED status, scheduler requeue-with-seq.
        self.preemption_reason = f"self-heal: unhealthy device(s) {bad}"
        self._stop.set()

    def _note_saved_topology(self) -> None:
        """Best-effort: record the live mesh factorization next to the
        checkpoints (once per attempt) so a future resume on a different
        mesh knows it must route through the reshard plane."""
        if self._topology_written or self.ckpt is None or self.program is None:
            return
        try:
            from tpu_engine import reshard

            reshard.write_topology(
                self.ckpt.directory,
                reshard.mesh_topology(self.program.runtime.mesh),
                extra={"job_id": self.job_id},
            )
            self._topology_written = True
        except Exception:  # noqa: BLE001 — manifest is advisory, never fatal
            pass

    def _final_save(self, step: int) -> bool:
        """Final/emergency checkpoint with bounded retry; never raises.

        On persistent I/O failure the step is quarantined (partial write)
        and the job falls back to the last good periodic checkpoint on
        resume — progress loss is bounded by checkpoint_interval_steps
        instead of the whole run."""
        if self.recovery_state == "detected":
            self.recovery_state = "saving"
        ok = self.ckpt.save_with_retry(
            step,
            self._state,
            retries=self.emergency_save_retries,
            backoff_base_s=self.emergency_save_backoff_s,
            on_attempt=lambda attempt, err: self._record_recovery(
                "save-retry", step, f"attempt {attempt}: {err}"
            ),
        )
        if ok:
            self._note_saved_topology()
        if self.recovery_state is not None:
            self.recovery_state = "saved" if ok else "save-failed"
            self._record_recovery(
                self.recovery_state, step,
                "emergency checkpoint persisted" if ok
                else "emergency save failed after retries — step quarantined",
            )
        return ok

    # -- training loop -------------------------------------------------------

    def _elastic_config(self) -> TPUTrainConfig:
        """The config to build with: when the declared elastic bounds allow
        and the configured mesh does not fit the visible devices, swap in
        the largest admissible mesh (reference elasticity min/max bounds,
        ``deepspeed_launcher.py:226-238``). Cross-mesh restore then loads
        the checkpoint onto the new shardings as usual."""
        cfg = self.config
        devices = list(self._devices) if self._devices is not None else list(jax.devices())
        n_visible = len(devices)
        if not (cfg.elastic_resume and cfg.elastic_min_devices is not None):
            cfg.mesh.resolved_shape(n_visible)  # exact fit or raise
            return cfg
        from tpu_engine.mesh_runtime import derive_elastic_mesh

        # Bounds declared → they govern UNCONDITIONALLY: even a mesh that
        # "fits" (data=-1 absorbs anything) must land inside
        # [min_devices, max_devices], so always derive, then compare.
        new_mesh = derive_elastic_mesh(
            cfg.mesh, n_visible, cfg.elastic_min_devices,
            cfg.elastic_max_devices,
        )
        # derive_elastic_mesh returns explicit axis sizes (no -1).
        n_use = (new_mesh.data * new_mesh.fsdp * new_mesh.pipe
                 * new_mesh.sequence * new_mesh.model)
        if n_use < n_visible:
            # The derived mesh is smaller than the visible world
            # (max_devices cap, or divisibility): pair it with a concrete
            # device subset — a mesh must cover its runtime's devices
            # exactly. Auto-subset is SINGLE-CONTROLLER only: in a
            # multi-process run, jax.devices()[:n] spans host 0's chips and
            # would strand the other hosts mid-collective; cross-host
            # shrink means relaunching with fewer processes (the JobSet
            # respawns at the new world size and THIS path then sees a
            # single consistent process world again).
            if jax.process_count() > 1:
                raise ValueError(
                    f"elastic bounds admit {n_use} of {n_visible} visible "
                    "devices, but auto-subset cannot span a multi-process "
                    "world — relaunch with fewer processes instead"
                )
            self._devices = devices[:n_use]
        try:
            same = cfg.mesh.resolved_shape(n_visible) == new_mesh.resolved_shape(n_use)
        except ValueError:
            same = False
        if same:
            return cfg
        self.elastic_mesh = new_mesh.model_dump()
        log.warning(
            "job %s: configured mesh %s vs %d visible device(s); elastic "
            "bounds [%s, %s] admit %s on %d device(s) — relaunching at that "
            "shape",
            self.job_id, cfg.mesh.model_dump(), n_visible,
            cfg.elastic_min_devices, cfg.elastic_max_devices,
            self.elastic_mesh, n_use,
        )
        update: dict = {"mesh": new_mesh}
        # Preserve the DECLARED effective batch across the resize
        # (reference min/max-batch elasticity semantics,
        # ``deepspeed_launcher.py:226-233``; round-4 verdict gap 2): a mesh
        # shrink halves the data-parallel extent — without rescaling,
        # optimizer dynamics silently change. Ceil so the batch never
        # silently SHRINKS; the declared batch bounds then gate admission.
        # The target comes from ``_declared_batch`` (captured at job
        # construction, or the explicit ``elastic_target_batch_size``) —
        # NOT re-derived here, where a ``data=-1`` mesh would re-resolve
        # against the already-shrunken world and bless the shrink.
        target = self._declared_batch
        new_dp = new_mesh.data * new_mesh.fsdp
        new_accum = max(1, -(-target // (cfg.micro_batch_size * new_dp)))
        achieved = cfg.micro_batch_size * new_accum * new_dp
        if new_accum != cfg.gradient_accumulation_steps:
            update["gradient_accumulation_steps"] = new_accum
        if achieved != target or new_accum != cfg.gradient_accumulation_steps:
            # Growth is as loud as shrink: dp beyond target/micro with
            # accum already 1 GROWS the batch — say so (bounds, if
            # declared, gate it below).
            log.warning(
                "job %s: effective batch across elastic resize: declared "
                "%d, achieved %d on dp=%d (accum %d -> %d)",
                self.job_id, target, achieved, new_dp,
                cfg.gradient_accumulation_steps, new_accum,
            )
        lo, hi = cfg.elastic_min_batch_size, cfg.elastic_max_batch_size
        if (lo is not None and achieved < lo) or (
            hi is not None and achieved > hi
        ):
            raise ValueError(
                f"no admissible effective batch: the elastic mesh "
                f"{self.elastic_mesh} achieves batch {achieved} "
                f"(micro {cfg.micro_batch_size} x accum {new_accum} x "
                f"dp {new_dp}), outside declared bounds [{lo}, {hi}]"
            )
        return cfg.model_copy(update=update)

    def _build_program(self):
        """Build the train program; for LoRA, load the frozen base weights
        from the configured HF checkpoint directory."""
        cfg = self._elastic_config()
        # Comm-tuning flags: in the worker CLI these were applied before the
        # backend initialised; in a long-lived server this warns that the
        # per-job knobs cannot take effect, and describe() reports them as
        # not in force (never a silent no-op).
        from tpu_engine.comm import apply_comm_flags, comm_flags_status

        apply_comm_flags(cfg)
        self.comm_flags = comm_flags_status(cfg)
        if cfg.lora_rank and cfg.lora_base_hf_checkpoint:
            from transformers import AutoModelForCausalLM

            from tpu_engine.models.convert import config_from_hf, from_hf

            hf_model = AutoModelForCausalLM.from_pretrained(cfg.lora_base_hf_checkpoint)
            model_cfg = config_from_hf(hf_model.config)
            base = from_hf(hf_model.state_dict(), model_cfg)
            del hf_model
            log.info(
                "job %s: LoRA base loaded from %s (%s)",
                self.job_id, cfg.lora_base_hf_checkpoint, model_cfg.name,
            )
            return build_train_program(
                cfg, model_cfg=model_cfg, base_params=base,
                runtime=self._runtime_for(cfg),
            )
        if cfg.lora_rank:
            log.warning(
                "job %s: lora_rank set without lora_base_hf_checkpoint — "
                "adapting a randomly initialised base model (only meaningful "
                "for tests and benchmarks)", self.job_id,
            )
        return build_train_program(cfg, runtime=self._runtime_for(cfg))

    def _runtime_for(self, cfg: TPUTrainConfig):
        """A pinned-device MeshRuntime when this job was given an explicit
        device subset; None lets build_train_program use all visible."""
        if self._devices is None:
            return None
        from tpu_engine.mesh_runtime import MeshRuntime

        return MeshRuntime(cfg.mesh, devices=self._devices)

    def _abstract_state(self):
        prog = self.program
        state_shape = jax.eval_shape(lambda: prog.init(jax.random.PRNGKey(self.config.seed)))
        return abstract_state_like(prog.state_shardings, state_shape)

    def _note_compile_outcome(self, compile_s: float) -> Optional[bool]:
        """Classify this attempt's compile as warm (persistent-cache hit)
        or cold, and record the outcome into the fleet compile index.

        The classification is a cheap wall-clock heuristic against the
        index's measured cold-compile EMA: a layout the index already calls
        warm stays a hit unless the measured wall time blew far past the
        cold reference (cache evicted under us); a layout the index has
        never seen is a hit only when the compile came in at a small
        fraction of the cold reference (another process warmed the shared
        cache dir). Returns None (and records nothing) when keying fails —
        the index must never break the compile path.
        """
        try:
            from tpu_engine import compile_index as compile_index_mod

            idx = compile_index_mod.get_index()
            mesh = self.elastic_mesh or self.config.mesh
            gang = (
                len(self._devices) if self._devices
                else jax.device_count()
            )
            label = compile_index_mod.label_for_config(
                self.config, mesh=mesh, gang=gang
            )
            key = compile_index_mod.index_key(label, self.config)
            prior_warm = idx.is_warm(key)
            cold_ref = idx.expected_cold_s(key)
            if prior_warm:
                cache_hit = cold_ref is None or compile_s <= max(
                    0.5 * cold_ref, 1.0
                )
            else:
                cache_hit = (
                    cold_ref is not None and compile_s < 0.33 * cold_ref
                )
            idx.record(
                key, compile_s, cache_hit,
                label=label, model=self.config.model_name,
            )
            return cache_hit
        except Exception:
            log.debug("compile index record failed", exc_info=True)
            return None

    def _run(self) -> None:
        self.started_at = time.time()
        rec = tracing.get_recorder()
        if self.trace_id is None:
            self.trace_id = rec.new_trace_id()
        if self.ckpt is not None:
            self.ckpt.trace_id = self.trace_id
        attempt_span = rec.start_span(
            f"attempt:{self.job_id}",
            kind="attempt",
            trace_id=self.trace_id,
            parent=rec.trace_root(self.trace_id),
            attrs={"job_id": self.job_id},
        )
        # Measured per-step wall total for this attempt — annotated onto the
        # attempt span at close; the goodput ledger uses it as the cap on
        # how much attempt time may count productive (untraced gaps fall to
        # idle/unknown, not goodput).
        attempt_step_s = 0.0
        try:
            self.status = JobStatus.COMPILING
            # Warm-start compiles across restarts: a preempted job that
            # resumes pays a cache hit, not a cold compile (the MTTR bound
            # this module's docstring promises; SURVEY.md §7 hard part c).
            from tpu_engine.compile_cache import enable_compilation_cache

            with rec.start_span(
                "compile", kind="compile", trace_id=self.trace_id,
                parent=attempt_span,
            ) as compile_span:
                t_compile0 = time.time()
                enable_compilation_cache()
                if self.program is None:
                    self.program = self._build_program()
                compile_s = max(time.time() - t_compile0, 0.0)
                # Warm/cold classification feeds the fleet compile index
                # (scheduler admission + grow-back read it) and lets the
                # goodput ledger split `compile` into warm vs cold time.
                cache_hit = self._note_compile_outcome(compile_s)
                compile_span.annotate(
                    cache_hit=cache_hit, compile_s=round(compile_s, 6),
                )
            prog = self.program
            self.attention_impl = prog.model_config.attention_impl

            # Per-chip attribution: claim this job's chips in the fleet view
            # (reference per-GPU process table, ``gpu_manager.py:174-184``)
            # as soon as the mesh exists — the compile/restore/init window
            # holds the chips too, and shows as status "compiling".
            # Released in the outer finally. The same ids scope the derived
            # duty-cycle telemetry below.
            local_device_ids = [
                int(d.id)
                for d in prog.runtime.mesh.devices.flat
                if d.process_index == jax.process_index()
            ]
            telemetry.register_job_devices(
                self.job_id, local_device_ids, jax.process_index(),
                lambda: self.status.value,
            )

            # Resume if checkpoints exist (auto-resume; MTTR path). When the
            # saved topology manifest disagrees with the live mesh, route
            # through the reshard plane so any planner-feasible factorization
            # is a valid resume target (parity-gated; PR 18).
            start_step = 0
            if self.ckpt is not None and self.ckpt.latest_step() is not None:
                from tpu_engine import reshard

                saved_topo = reshard.read_topology(self.ckpt.directory)
                live_topo = reshard.mesh_topology(prog.runtime.mesh)
                resharded = (
                    saved_topo is not None
                    and not reshard.same_topology(saved_topo, live_topo)
                )
                if resharded:
                    step, state, report = reshard.restore_resharded(
                        self.ckpt,
                        self._abstract_state(),
                        saved_topology=saved_topo,
                        target_topology=live_topo,
                    )
                    self.resumed_via_reshard = report
                else:
                    step, state = self.ckpt.restore(self._abstract_state())
                if state is not None:
                    self._state = state
                    start_step = int(step)
                    self.resumed_from_step = start_step
                    rec.event(
                        "resume",
                        kind="supervisor",
                        trace_id=self.trace_id,
                        parent=attempt_span,
                        attrs={"from_step": start_step, "resharded": resharded},
                    )
                    log.info(
                        "job %s: resumed from checkpoint step %d%s",
                        self.job_id, start_step,
                        " (resharded across topologies)" if resharded else "",
                    )
            if self._state is None:
                self._state = prog.init(jax.random.PRNGKey(self.config.seed))

            if self.watcher is not None:
                self.watcher.start()

            # Input pipeline: explicit data_fn > config dataset file > synthetic.
            if self.data_fn is None and self.config.dataset_path:
                from tpu_engine import native
                from tpu_engine.data import TokenFileDataset, make_data_fn

                self._dataset = TokenFileDataset(
                    self.config.dataset_path,
                    seq_len=self.config.seq_len,
                    dtype=self.config.dataset_dtype,
                )
                self.data_fn = make_data_fn(prog, self._dataset, seed=self.config.seed)
                log.info(
                    "job %s: dataset %s (%d sequences, reader: %s)",
                    self.job_id, self.config.dataset_path,
                    self._dataset.num_sequences, native.status(),
                )

            # Held-out eval source: dedicated file > held-out synthetic seeds.
            if self.config.eval_interval_steps:
                if self.config.eval_dataset_path:
                    from tpu_engine.data import TokenFileDataset, make_eval_data_fn

                    self._eval_dataset = TokenFileDataset(
                        self.config.eval_dataset_path,
                        seq_len=self.config.seq_len,
                        dtype=self.config.dataset_dtype,
                    )
                    # Fixed held-out batches: call index i always reads the
                    # same sequences, so the eval curve is comparable.
                    self._eval_data_fn = make_eval_data_fn(prog, self._eval_dataset)
                    self._eval_source = "file"
                else:
                    # Synthetic fallback: a seed space disjoint from training
                    # steps (which seed by step index < total_steps).
                    self._eval_data_fn = lambda i: prog.synthetic_batch(
                        seed=1_000_000_007 + i
                    )
                    self._eval_source = "synthetic"
                    if self.data_fn is not None:
                        log.warning(
                            "job %s: eval_interval_steps set with real training "
                            "data but no eval_dataset_path — eval uses synthetic "
                            "random tokens (loss ≈ ln(vocab), not a held-out "
                            "metric)", self.job_id,
                        )

            if self.config.metrics_log_path:
                try:
                    self._metrics_file = open(self.config.metrics_log_path, "a")
                except OSError:  # metrics are best-effort; never fail the job
                    log.exception(
                        "job %s: cannot open metrics log %s — continuing without",
                        self.job_id, self.config.metrics_log_path,
                    )
                if self.resumed_from_step is not None:
                    self._log_metrics(kind="resume", step=start_step)

            self.status = JobStatus.RUNNING
            tokens_per_batch = 1
            for d in prog.global_batch_shape():
                tokens_per_batch *= d
            from tpu_engine.models import transformer as tfm

            prof = self.profiler = StepProfiler(
                loop="supervisor", phases=SUPERVISOR_PHASES,
                tokens_per_step=tokens_per_batch,
                flops_per_token=tfm.train_flops_per_token(prog.model_config, self.config.seq_len),
                n_devices=prog.runtime.n_devices,
                pipeline_account=pipeline_tick_account(
                    prog.pipeline_schedule,
                    prog.runtime.axis_sizes["pipe"],
                    self.config.gradient_accumulation_steps,
                ),
            )
            self._health_sample_s = deque(maxlen=prof.window)
            if self._hetero is None and self.hetero_detection:
                from tpu_engine import hetero as hetero_mod

                _, gm_h, _ = prog.global_batch_shape()
                n_proc = max(jax.process_count(), 1)
                # Multi-process: every rank consults at the same step (the
                # modulo check below), solves from rank 0's broadcast
                # estimates, and cools down in steps — so all ranks derive
                # the identical plan and the row windows never overlap or
                # gap (agreement enforced, not a caller convention).
                self._hetero = hetero_mod.HeteroRebalancer(
                    hetero_mod.ThroughputTracker(n_proc),
                    gm_h,
                    dry_run=self.hetero_dry_run,
                    trace_id=self.trace_id,
                    agree_fn=(
                        hetero_mod.broadcast_agree_fn() if n_proc > 1 else None
                    ),
                    cooldown_steps=(
                        4 * self.hetero_check_interval_steps
                        if n_proc > 1 else None
                    ),
                )
            if self._hetero is not None:
                from tpu_engine import hetero as hetero_mod

                hetero_mod.set_active(self._hetero)
            step = start_step
            closed_step = None  # the step number the last closed iteration reached
            while step < self.max_steps and not self._stop.is_set():
                # Every iteration counts once, when it closes (the last one
                # at the loop's exit).
                attempt_step_s += prof.begin_step() or 0.0
                it = step  # this iteration's id on every phase annotation
                with prof.phase("data", step=it):
                    batch = (
                        self.data_fn(step) if self.data_fn is not None
                        else prog.synthetic_batch(step)
                    )
                with prof.phase("dispatch", step=it), self._state_lock:
                    self._state, metrics = prog.step(self._state, batch)
                with prof.phase("device", step=it):
                    # The chip runs the step from here to the read's return:
                    # the fleet sample this iteration's health verdict wants
                    # is taken under it, on the iterations that poll (the
                    # step the read will report is it + 1).
                    fleet = None
                    if self.fleet_fn is not None and self._health_poll_due(it + 1):
                        fleet = self._sample_fleet(it)
                    host = {k: float(v) for k, v in jax.device_get(metrics).items()}
                # Step time is the WHOLE iteration, begin to begin — what an
                # operator's tokens/s must be over — so it is the previous
                # iteration's; an attempt's first iteration has only its own
                # time up to here.
                last = prof.last_step()
                phases_s, dt = last or prof.open_step()
                self.last_step_time_s = dt
                self.tokens_per_sec = tokens_per_batch / dt if dt > 0 else None
                # Feed the fleet's derived duty-cycle source: device-phase
                # time (the blocking device→host read absorbs the step's
                # device execution) over step wall time.
                telemetry.observe_step(
                    phases_s.get("device", 0.0), dt, device_ids=local_device_ids,
                )
                step = int(host["step"])
                self.current_step = step
                # The step whose time ``dt`` is: the anomaly record names it.
                dt_step = closed_step if last is not None else step
                closed_step = step

                # Fault-injection seams + self-healing health check.
                with prof.phase("health", step=it):
                    inj = self._injector()
                    if inj is not None:
                        inj.observe_step(step)
                        slow_spec = inj.take_host_slow(step)
                        slow = float(slow_spec.slow_s) if slow_spec is not None else 0.0
                        if slow > 0:
                            # Host-slow is a *reported* stall (step time +
                            # throughput degrade) — never an actual sleep, so
                            # chaos runs stay deterministic and fast.
                            self.last_step_time_s = dt + slow
                            dt_step = step  # the stall is reported at this step
                            self.tokens_per_sec = tokens_per_batch / self.last_step_time_s
                            rec.event(
                                "host-slow",
                                kind="fault",
                                trace_id=self.trace_id,
                                parent=attempt_span,
                                attrs={"step": step, "penalty_s": slow},
                            )
                            if self._hetero is not None:
                                # Attribute the stall to the host the spec
                                # names (fleet device index → owning process).
                                n_proc = self._hetero.tracker.n_processes
                                dev_per_proc = max(
                                    prog.runtime.n_devices // n_proc, 1
                                )
                                proc = (
                                    slow_spec.device_index // dev_per_proc
                                    if slow_spec.device_index is not None
                                    else None
                                )
                                self._last_slow_proc = proc
                                self._hetero.tracker.note_host_slow(proc, slow, dt)
                        if inj.preempt_due(step):
                            # Synchronous injection (not via the watcher thread):
                            # the step that triggers is the step that saves.
                            self._on_preemption("fault-injected:preemption-signal")
                    if self._health_poll_due(step):
                        bad = self._unhealthy_mesh_devices(fleet)
                        if bad:
                            self._begin_self_heal(step, bad)

                # Step-time anomaly attribution: flag against the sliding
                # baseline, then attribute to whatever span/event overlaps
                # the observed iteration's wall window through now (so it
                # covers inter-step work like a checkpoint save). The
                # host-slow event above lands BEFORE this check, so an
                # injected stall is both the anomaly and its cause.
                with prof.phase("anomaly", step=it):
                    if self._anomaly is not None:
                        now_ts = time.time()
                        observed = (
                            self.last_step_time_s
                            if self.last_step_time_s is not None
                            else dt
                        )
                        anom = self._anomaly.observe(dt_step, observed)
                        if anom is not None:
                            # From the begin of the iteration observed
                            # (the one before this, once one has closed) or
                            # the last check, whichever is earlier.
                            w0 = now_ts - observed - prof.open_step()[1]
                            if self._prev_step_end_ts is not None:
                                w0 = min(w0, self._prev_step_end_ts)
                            cause = rec.attribute(self.trace_id, w0, now_ts)
                            anom["cause"] = cause
                            self.anomalies_total += 1
                            self.last_anomaly = dict(anom)
                            if self._hetero is not None:
                                # Sustained host-slow attribution seeds the
                                # throughput tracker even when no injector
                                # reported a penalty (real-fleet path).
                                self._hetero.tracker.note_attribution(
                                    cause, anom, self._last_slow_proc
                                )
                            rec.record_anomaly(
                                cause,
                                trace_id=self.trace_id,
                                attrs={
                                    "job_id": self.job_id,
                                    "step": anom["step"],
                                    "duration_s": anom["duration_s"],
                                    "baseline_s": anom["baseline_s"],
                                    "sustained": anom["sustained"],
                                },
                            )
                            if (
                                anom["sustained"]
                                and self._anomaly_trace_session is not None
                                and not self._auto_trace_started
                            ):
                                # Opt-in: one bounded XPlane capture per job on
                                # sustained regression (never a retry storm).
                                self._auto_trace_started = True
                                try:
                                    log_dir = self._anomaly_trace_dir or (
                                        tempfile.mkdtemp(
                                            prefix=f"anomtrace_{self.job_id}_"
                                        )
                                    )
                                    self._anomaly_trace_session.start(
                                        log_dir, duration_s=30.0
                                    )
                                    rec.event(
                                        "auto_trace_started",
                                        kind="supervisor",
                                        trace_id=self.trace_id,
                                        attrs={"log_dir": log_dir, "step": step},
                                    )
                                except Exception as e:
                                    rec.event(
                                        "auto_trace_unavailable",
                                        kind="supervisor",
                                        trace_id=self.trace_id,
                                        attrs={"error": str(e)},
                                    )
                        self._prev_step_end_ts = now_ts

                    # Heterogeneity plane: every step feeds the throughput EMA
                    # (decay-to-1 heals transient stalls); every
                    # hetero_check_interval_steps the rebalancer is consulted.
                    # A live (non-dry-run) plan moves the data split through
                    # data_fn.reassign — the declared global batch is preserved
                    # exactly (validated again at the data layer).
                    if self._hetero is not None:
                        self._hetero.tracker.observe_step(
                            self.last_step_time_s if self.last_step_time_s else dt
                        )
                        consult = step % self.hetero_check_interval_steps == 0
                        if not consult and jax.process_count() <= 1:
                            # Out-of-band consult requested by the scheduler's
                            # rebalance-over-shrink path. Honored between
                            # modulo boundaries only single-process —
                            # multi-process ranks must all consult at the same
                            # step, so there the request simply rides the next
                            # periodic consult.
                            consult = self._hetero.consult_pending()
                        if consult:
                            h_plan = self._hetero.maybe_rebalance(step)
                            if h_plan is not None and not h_plan.dry_run:
                                reassign_fn = getattr(self.data_fn, "reassign", None)
                                if reassign_fn is None:
                                    # No seam to move rows through (synthetic
                                    # batches): roll the plan back so the
                                    # gauges never report a split that is not
                                    # actually feeding the mesh.
                                    self._hetero.revert(h_plan)
                                else:
                                    try:
                                        reassign_fn(h_plan.assignment)
                                        self.hetero_rebalances_total += 1
                                        rec.event(
                                            "hetero_reassign",
                                            kind="hetero",
                                            trace_id=self.trace_id,
                                            parent=attempt_span,
                                            attrs={
                                                "step": step,
                                                "assignment": list(h_plan.assignment),
                                            },
                                        )
                                    except ValueError as e:
                                        self._hetero.revert(h_plan)
                                        rec.event(
                                            "hetero_reassign_rejected",
                                            kind="hetero",
                                            trace_id=self.trace_id,
                                            attrs={"step": step, "error": str(e)},
                                        )

                with prof.phase("monitor", step=it):
                    alerts = self.monitor.ingest(
                        TrainingMetrics(
                            step=step,
                            loss=host["loss"],
                            learning_rate=host["learning_rate"],
                            gradient_norm=host["grad_norm"],
                            throughput_tokens_per_sec=self.tokens_per_sec,
                        )
                    )

                    if step % self.config.log_every_steps == 0:
                        self._log_metrics(
                            kind="train", step=step, loss=host["loss"],
                            learning_rate=host["learning_rate"],
                            grad_norm=host["grad_norm"],
                            tokens_per_sec=self.tokens_per_sec,
                        )

                    critical = [a for a in alerts if a.severity == AlertSeverity.CRITICAL]
                    if critical:
                        self._last_critical_step = step
                        if self.auto_rollback and self.ckpt is not None:
                            rolled = self._rollback(before_step=step)
                            if rolled is not None:
                                step = rolled
                                continue
                            if any(a.alert_type == "divergence" for a in critical):
                                raise RuntimeError(
                                    f"diverged at step {step} with no stable checkpoint to roll back to"
                                )
                        elif any(a.alert_type == "divergence" for a in critical):
                            raise RuntimeError(f"training diverged at step {step}")

                with prof.phase("checkpoint", step=it):
                    # Held-out evaluation.
                    if (
                        self.config.eval_interval_steps
                        and step % self.config.eval_interval_steps == 0
                    ):
                        self._run_eval(step)

                    # Periodic checkpoint + stable-pointer advancement.
                    if self.ckpt is not None:
                        if step % self.config.checkpoint_interval_steps == 0:
                            with self._state_lock:  # disk-overlap: saved params
                                self._flush_state()  # must include every update
                            self.ckpt.save(step, self._state, metrics={"loss": host["loss"]})
                            self._note_saved_topology()
                            self._pending_stable.append(step)
                        self._advance_stable(step)
            # The last iteration has no next begin to close it.
            attempt_step_s += prof.end_step() or 0.0

            # Final save + status.
            if self.ckpt is not None and self._state is not None:
                with self._state_lock:
                    self._flush_state()
                save_kind = (
                    "emergency_save"
                    if (
                        self.preemption_reason is not None
                        or self.recovery_state is not None
                    )
                    else "final_save"
                )
                with rec.start_span(
                    save_kind, kind=save_kind, trace_id=self.trace_id,
                    parent=attempt_span, attrs={"step": step},
                ) as save_span:
                    ok = self._final_save(step)
                    save_span.annotate(ok=ok)
                if ok:
                    self._advance_stable(step)
            if self.preemption_reason is not None:
                self.status = JobStatus.PREEMPTED
            elif self._stop.is_set() and step < self.max_steps:
                self.status = JobStatus.STOPPED
            else:
                self.status = JobStatus.COMPLETED
        except Exception as e:  # noqa: BLE001 — job boundary
            self.error = f"{type(e).__name__}: {e}"
            log.error("job %s failed:\n%s", self.job_id, traceback.format_exc())
            self.status = JobStatus.FAILED
        finally:
            self.finished_at = time.time()
            if self.profiler is not None:  # an iteration a failure left open
                attempt_step_s += self.profiler.end_step() or 0.0
            if attempt_span.t1 is None:
                attempt_span.end(
                    status=self.status.value,
                    step=self.current_step,
                    step_s=round(attempt_step_s, 6),
                    preemption_reason=self.preemption_reason,
                    error=self.error,
                    resumed_from_step=self.resumed_from_step,
                    anomalies=self.anomalies_total,
                )
            telemetry.unregister_job_devices(self.job_id)
            # Release the process-wide hetero plane only if this job owns it
            # (a newer job may already have installed its own rebalancer).
            if self._hetero is not None:
                from tpu_engine import hetero as hetero_mod

                if hetero_mod.get_active() is self._hetero:
                    hetero_mod.clear_active()
            # Stop a sharded-read prefetch thread with the job (make_data_fn
            # attaches close when it owns a stream).
            close_fn = getattr(self.data_fn, "close", None)
            if callable(close_fn):
                try:
                    close_fn()
                except Exception:
                    pass
            for ds in (self._dataset, self._eval_dataset):
                if ds is not None:
                    try:
                        ds.close()
                    except Exception:
                        pass
            if self._metrics_file is not None:
                try:
                    self._metrics_file.close()
                except Exception:
                    pass
            if self.watcher is not None:
                self.watcher.stop()
            if self.ckpt is not None:
                try:
                    self.ckpt.wait_until_finished()
                except Exception:
                    pass

    def run_eval_now(self) -> dict[str, float]:
        """On-demand held-out evaluation at the current step (requires
        ``eval_interval_steps`` so an eval data source exists). Returns
        {step, loss, perplexity} and records it in the history."""
        if self.program is None or self._state is None:
            raise RuntimeError(
                "job has not started its train loop yet (or failed during "
                "compile) — retry once it is running"
            )
        if self._eval_data_fn is None:
            raise RuntimeError(
                "job has no eval data source (set eval_interval_steps)"
            )
        try:
            step, loss = self._run_eval()
        except Exception as e:  # e.g. file-backed source closed after finish
            raise RuntimeError(f"eval failed: {type(e).__name__}: {e}")
        return {"step": step, "loss": loss, "perplexity": _perplexity(loss)}

    def _flush_state(self) -> None:
        """Disk-overlap jobs: fold the in-flight host walk into ``_state``
        so params match the step label (checkpoints, eval, and snapshots
        must never see the one-walk-stale tree). Caller holds
        ``_state_lock``. No-op for every other program kind."""
        prog = self.program
        if prog is not None and prog.flush is not None and self._state is not None:
            self._state = prog.flush(self._state)

    def _run_eval(self, step: Optional[int] = None) -> tuple[int, float]:
        """Average ``eval_batches`` held-out losses; record in history.

        ``step=None`` (the on-demand path) reads the current step under the
        state lock, so the recorded step matches the state evaluated even
        while training advances. Returns ``(step, loss)`` — callers must
        not re-read shared history, which concurrent evals/rollbacks mutate.
        """
        prog = self.program
        with self._state_lock:
            if step is None:
                step = self.current_step
            self._flush_state()  # disk-overlap: eval the step's real params
            # Dispatch all eval steps before the single host sync, so device
            # execution of batch k overlaps dispatch of batch k+1.
            device_losses = [
                prog.eval_step(self._state, self._eval_data_fn(i))
                for i in range(self.config.eval_batches)
            ]
        loss = float(sum(jax.device_get(device_losses))) / self.config.eval_batches
        self.eval_history.append((step, loss))
        del self.eval_history[: -self._max_eval_history]
        self._log_metrics(kind="eval", step=step, loss=loss, perplexity=_perplexity(loss))
        log.info(
            "job %s: eval @ step %d — loss %.4f ppl %.2f",
            self.job_id, step, loss, _perplexity(loss),
        )
        return step, loss

    def _log_metrics(self, **fields) -> None:
        """One JSON line to the job's metrics log (no-op when unconfigured)."""
        if self._metrics_file is None:
            return
        import json

        try:
            fields["job_id"] = self.job_id
            # Timeline disambiguation: after a divergence rollback the same
            # step numbers are re-logged; group by (step, rollback) to pick
            # the live timeline.
            fields["rollback"] = self.rollback_count
            fields["ts"] = time.time()
            self._metrics_file.write(json.dumps(fields) + "\n")
            self._metrics_file.flush()
        except Exception:  # a full disk must not kill training
            log.exception("job %s: metrics log write failed", self.job_id)

    def _advance_stable(self, current_step: int) -> None:
        """Mark saved steps stable once a healthy margin has passed them."""
        still_pending: list[int] = []
        for s in self._pending_stable:
            if self._last_critical_step >= s:
                continue  # anomaly at/after this save — never stable
            if current_step >= s + self.stable_margin_steps or current_step >= self.max_steps:
                self.ckpt.mark_stable(s)
            else:
                still_pending.append(s)
        self._pending_stable = still_pending

    def _rollback(self, before_step: int) -> Optional[int]:
        """Restore last stable checkpoint and cut LR; returns restored step."""
        if self.rollback_count >= self.max_rollbacks:
            log.error("job %s: max rollbacks (%d) reached", self.job_id, self.max_rollbacks)
            return None
        self.ckpt.wait_until_finished()
        step, state = self.ckpt.restore_stable(self._abstract_state(), before_step=before_step)
        if state is None:
            return None
        # Purge post-anomaly checkpoints: a crash-restart must not auto-resume
        # into the diverged timeline (latest-step restore would prefer them).
        self.ckpt.delete_after(int(step))
        self._pending_stable = [s for s in self._pending_stable if s <= int(step)]
        # Evals from the abandoned timeline would collide with re-reached steps.
        self.eval_history = [(s, l) for s, l in self.eval_history if s <= int(step)]
        # New timeline: the old anomaly step must not veto fresh post-rollback
        # checkpoints from ever being marked stable.
        self._last_critical_step = -1
        new_scale = jax.device_get(state["lr_scale"]) * self.lr_cut_on_rollback
        state["lr_scale"] = jax.device_put(
            jax.numpy.asarray(new_scale, jax.numpy.float32),
            self.program.state_shardings["lr_scale"],
        )
        with self._state_lock:
            self._state = state
        self.rollback_count += 1
        self.monitor.reset()
        self._log_metrics(kind="rollback", step=int(step), anomaly_step=before_step)
        log.warning(
            "job %s: rolled back to stable step %d (rollback #%d, lr_scale=%.4f)",
            self.job_id, step, self.rollback_count, float(new_scale),
        )
        return int(step)

    # -- sampling ------------------------------------------------------------

    def generate_sample(
        self,
        prompt_tokens: list[list[int]],
        max_new_tokens: int = 32,
        temperature: float = 0.0,
        top_k: Optional[int] = None,
        top_p: Optional[float] = None,
        seed: int = 0,
        kv_quant: bool = False,
    ) -> list[list[int]]:
        """Sample continuations from the job's *current* weights.

        Safe while training runs — but only because the lock is held across
        the generate *dispatch*: the train step is jitted with donated state
        (``donate_argnums=(0,)``), so a params reference grabbed under the
        lock would be deleted the moment the training thread dispatches its
        next step. Once generate is enqueued the runtime holds its own
        buffer references and the lock can drop; ``device_get`` then waits
        outside it. Returns prompt + continuation token ids per row.
        """
        import jax.numpy as jnp

        from tpu_engine.generate import generate

        if self.program is None or self._state is None:
            raise RuntimeError("job has no initialized state to sample from")
        lens = {len(p) for p in prompt_tokens}
        if len(lens) != 1 or 0 in lens:
            raise ValueError("prompt rows must be non-empty and equal-length")
        vocab = self.program.model_config.vocab_size
        if any(t < 0 or t >= vocab for row in prompt_tokens for t in row):
            raise ValueError(f"prompt token id out of range [0, {vocab})")
        prompt = jnp.asarray(prompt_tokens, jnp.int32)
        with self._state_lock:
            params = self._full_params_locked()
            out = generate(
                params,
                prompt,
                self.program.model_config,
                max_new_tokens=max_new_tokens,
                rng=jax.random.PRNGKey(seed),
                temperature=temperature,
                top_k=top_k,
                top_p=top_p,
                compute_dtype=self.program.config.compute_dtype(),
                kv_quant=kv_quant,
            )
        return [[int(t) for t in row] for row in jax.device_get(out)]

    def generate_samples_ragged(
        self,
        prompt_rows: list[list[int]],
        max_new_tokens: int = 32,
        temperature: float = 0.0,
        top_k: Optional[int] = None,
        top_p: Optional[float] = None,
        seed: int = 0,
        kv_quant: bool = False,
    ) -> list[list[int]]:
        """Sample continuations for rows of *different* lengths — each row
        decodes separately (no padding mask exists), but every dispatch
        happens under one state-lock hold, so all rows sample one
        consistent weight snapshot even while training runs."""
        import jax.numpy as jnp

        from tpu_engine.generate import generate

        if self.program is None or self._state is None:
            raise RuntimeError("job has no initialized state to sample from")
        vocab = self.program.model_config.vocab_size
        for row in prompt_rows:
            if not row:
                raise ValueError("prompt rows must be non-empty")
            if any(t < 0 or t >= vocab for t in row):
                raise ValueError(f"prompt token id out of range [0, {vocab})")
        # One consistent weight snapshot for every row; the per-row decode
        # loop runs with the state lock RELEASED, so a long ragged
        # generation never stalls the training thread (_params_snapshot
        # owns its buffers — donation cannot invalidate them).
        params = self._params_snapshot()
        outs = []
        for i, ids in enumerate(prompt_rows):
            outs.append(
                generate(
                    params,
                    jnp.asarray([ids], jnp.int32),
                    self.program.model_config,
                    max_new_tokens=max_new_tokens,
                    rng=jax.random.PRNGKey(seed + i),
                    temperature=temperature,
                    top_k=top_k,
                    top_p=top_p,
                    compute_dtype=self.program.config.compute_dtype(),
                    kv_quant=kv_quant,
                )
            )
        return [[int(t) for t in jax.device_get(o)[0]] for o in outs]

    def speculative_sample(
        self,
        prompt_tokens: list[int],
        draft_hf_checkpoint: str,
        max_new_tokens: int = 32,
        gamma: int = 4,
    ) -> tuple[list[int], int]:
        """Greedy speculative decoding from the job's current weights with a
        small draft model loaded from a local HF checkpoint directory
        (cached per path). Returns (prompt+continuation ids, verification
        rounds — i.e. target forward passes taken).
        """
        import jax.numpy as jnp

        from tpu_engine.generate import speculative_generate

        if self.program is None or self._state is None:
            raise RuntimeError("job has no initialized state to sample from")
        if not prompt_tokens:
            raise ValueError("prompt must be non-empty")
        model_cfg = self.program.model_config
        vocab = model_cfg.vocab_size
        if any(t < 0 or t >= vocab for t in prompt_tokens):
            raise ValueError(f"prompt token id out of range [0, {vocab})")
        draft_params, draft_cfg = _load_draft(
            draft_hf_checkpoint, self.program.config.compute_dtype()
        )
        if draft_cfg.vocab_size != model_cfg.vocab_size:
            raise ValueError(
                f"draft vocab ({draft_cfg.vocab_size}) != target vocab "
                f"({model_cfg.vocab_size}); speculative verification needs a "
                "shared tokenizer"
            )
        prompt = jnp.asarray([prompt_tokens], jnp.int32)
        # Snapshot once; the draft/verify rounds run outside the state lock
        # (a speculative decode is many dispatches — holding the lock across
        # them stalled training; round-1 review finding).
        params = self._params_snapshot()
        out, rounds = speculative_generate(
            params, draft_params, prompt, model_cfg, draft_cfg,
            max_new_tokens=max_new_tokens, gamma=gamma,
            compute_dtype=self.program.config.compute_dtype(),
            return_stats=True,
        )
        return [int(t) for t in jax.device_get(out)[0]], rounds

    def _full_params_locked(self):
        """Full model params for the current step (caller holds _state_lock):
        the trainable tree itself, or (LoRA) base+adapters merged — cached
        per step so repeated sampling/export reuses the merge."""
        params = self._state["params"]
        if self.program.merged_params is None:
            return params
        if self._merged_cache is not None and self._merged_cache[0] == self.current_step:
            return self._merged_cache[1]
        params = self.program.merged_params(params)
        self._merged_cache = (self.current_step, params)
        return params

    def _params_snapshot(self):
        """A decode-safe snapshot of the current full params.

        Taken under the state lock, returned with the lock RELEASED: the
        train step donates the live param buffers, so a multi-dispatch
        decode loop (ragged rows, speculative rounds) must not keep
        references into the live tree once training can advance. The
        merged LoRA tree already owns fresh buffers; host-offloaded params
        are placed on device (generation computes on device either way);
        the plain dense tree is copied — one extra params-sized allocation
        for the duration of the generation, in exchange for never stalling
        the train loop on a long decode (the round-1 review's finding)."""
        import jax.numpy as jnp

        from jax.sharding import NamedSharding

        from tpu_engine.sharding import OffloadDevice

        with self._state_lock:
            self._flush_state()  # disk-overlap: serve the step's real params
            params = self._full_params_locked()
            if self.program.merged_params is not None:
                return params
            if self.program.config.param_offload == OffloadDevice.HOST:
                # Stream + cast to the compute dtype in one compiled call:
                # generation computes in it anyway, and the device-resident
                # snapshot costs half the fp32 master — relevant because an
                # offloaded job's training footprint may be tuned close to
                # the HBM limit and training continues while we decode.
                dev_sh = jax.tree.map(
                    lambda sh: NamedSharding(self.program.mesh, sh.spec),
                    self.program.state_shardings["params"],
                    is_leaf=lambda x: isinstance(x, NamedSharding),
                )
                compute_dtype = self.program.config.compute_dtype()
                cast = jax.jit(
                    lambda t: jax.tree.map(
                        lambda a: a.astype(compute_dtype)
                        if jnp.issubdtype(a.dtype, jnp.floating)
                        else a,
                        t,
                    ),
                    out_shardings=dev_sh,
                )
                return cast(params)
            return jax.tree.map(jnp.copy, params)

    def export_hf_checkpoint(self, out_dir: str) -> tuple[str, int]:
        """Write the job's current weights (LoRA: base+adapters merged) as a
        loadable HF LlamaForCausalLM checkpoint directory.

        Returns ``(out_dir, step)`` where ``step`` is the training step the
        exported weights belong to (captured under the state lock — the job
        may advance while the conversion writes).
        """
        from tpu_engine.models.convert import save_hf_checkpoint

        if self.program is None or self._state is None:
            raise RuntimeError("job has no initialized state to export")
        with self._state_lock:
            step = self.current_step
            params = self._full_params_locked()
            if self.program.merged_params is None:
                # Dense path: no dispatched merge holds buffer references,
                # and the next train step DONATES these exact buffers —
                # host-copy before releasing the lock.
                params = jax.device_get(params)
        return save_hf_checkpoint(params, self.program.model_config, out_dir), step

    def export_quantized_snapshot(self, out_dir: str) -> tuple[str, int]:
        """Quantize the job's current weights (weight-only int8,
        ``tpu_engine/quant.py``) and persist them as a self-describing
        serving snapshot — quantize once, serve many times
        (``/serving/start {"snapshot_dir": ...}`` or
        ``quant.load_quantized``). Returns ``(out_dir, step)``."""
        from tpu_engine.quant import quantize_params, save_quantized

        if self.program is None or self._state is None:
            raise RuntimeError("job has no initialized state to export")
        # _params_snapshot takes the state lock itself (and returns
        # donation-safe buffers); the step is read after — a running job
        # may be off by the in-flight step, same as the generate path.
        params = self._params_snapshot()
        step = self.current_step
        qparams = quantize_params(params)
        return save_quantized(
            qparams, out_dir, model_config=self.program.model_config
        ), step

    # -- views ---------------------------------------------------------------

    def describe(self) -> dict[str, Any]:
        spill = None
        store = getattr(self.program, "disk_store", None) if self.program else None
        if store is not None:
            try:
                spill = store.spill_bytes()
            except RuntimeError:
                # The train thread may be repopulating the slab dict
                # (attach/reseed) — a transient miss, not an error.
                spill = None
        return {
            "job_id": self.job_id,
            "status": self.status.value,
            "error": self.error,
            "model_name": self.config.model_name,
            "sharding_stage": int(self.config.sharding_stage),
            # What the compiled program holds (None until it is built).
            "attention_impl": self.attention_impl,
            "comm_flags": self.comm_flags,
            "max_steps": self.max_steps,
            "current_step": self.current_step,
            "rollback_count": self.rollback_count,
            "resumed_from_step": self.resumed_from_step,
            "resumed_via_reshard": self.resumed_via_reshard,
            "elastic_mesh": self.elastic_mesh,
            "preemption_reason": self.preemption_reason,
            "recovery_state": self.recovery_state,
            "recovery_events": list(self.recovery_events),
            "unhealthy_devices": list(self.unhealthy_devices),
            "trace_id": self.trace_id,
            "anomalies_total": self.anomalies_total,
            "last_anomaly": self.last_anomaly,
            "hetero": self._hetero.stats() if self._hetero is not None else None,
            "hetero_rebalances_total": self.hetero_rebalances_total,
            "started_at": self.started_at,
            "finished_at": self.finished_at,
            "last_step_time_s": self.last_step_time_s,
            "tokens_per_sec": self.tokens_per_sec,
            "monitor": self.monitor.get_summary(),
            "profile": self.profiler.summary() if self.profiler is not None else None,
            "health_samples_total": self.health_samples_total,
            "health_sample_ms": StepProfiler._stats(list(self._health_sample_s)),
            "eval": self.eval_summary(),
            "disk_spill_bytes": spill,
        }

    def eval_summary(self) -> Optional[dict[str, Any]]:
        if not self.eval_history:
            return None
        step, loss = self.eval_history[-1]
        return {
            "source": self._eval_source,
            "latest_step": step,
            "latest_loss": loss,
            "latest_perplexity": _perplexity(loss),
            "history": [{"step": s, "loss": l} for s, l in self.eval_history],
        }


# -- speculative-draft cache -------------------------------------------------

_draft_cache: dict[tuple[str, int, str], tuple] = {}
_DRAFT_CACHE_MAX = 4


def _load_draft(path: str, compute_dtype):
    """Load (and cache) a draft model from a local HF checkpoint directory
    for speculative decoding. Cached per (path, mtime, dtype) — a re-export
    to the same directory refreshes the draft; the cache is tiny because
    drafts are meant to be small."""
    import os

    import jax.numpy as jnp

    if not os.path.isdir(path):
        raise ValueError(
            f"draft_hf_checkpoint {path!r} is not a local directory "
            "(hub repo ids are not fetched)"
        )
    key = (path, os.stat(path).st_mtime_ns, jnp.dtype(compute_dtype).name)
    hit = _draft_cache.get(key)
    if hit is not None:
        return hit
    from transformers import AutoModelForCausalLM

    from tpu_engine.models.convert import config_from_hf, from_hf

    hf_model = AutoModelForCausalLM.from_pretrained(path, local_files_only=True)
    cfg = config_from_hf(hf_model.config)
    params = from_hf(hf_model.state_dict(), cfg, dtype=compute_dtype)
    del hf_model
    if len(_draft_cache) >= _DRAFT_CACHE_MAX:
        _draft_cache.pop(next(iter(_draft_cache)))
    _draft_cache[key] = (params, cfg)
    return params, cfg
