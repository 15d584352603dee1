"""Training engine: the TPU-native ``DeepSpeedEngine``.

Re-design of the reference engine (``runtime/engine.py:182`` —
``DeepSpeedEngine.forward/backward/step`` :1838/:1977/:2176, optimizer
configuration :1272, ZeRO wiring :1532) for the XLA compilation model:

* forward/backward/step collapse into ONE jitted, donated train-step
  function; gradient accumulation is a ``lax.scan`` over micro-batches
  (the GAS boundary of engine.py:1960 becomes a scan carry), so a whole
  optimizer step is a single device dispatch.
* ZeRO stages are sharding specs (see ``parallel/zero.py``); the grad
  hooks / bucketing machinery of stage_1_and_2.py & stage3.py is replaced
  by the XLA SPMD partitioner.  Through stage 2 the specs on state and
  gradients are enough; at stage 3 the engine also hands the loss a
  ``Placement`` (gather each parameter per use, keep activations split
  over the batch), because specs on the parameters alone leave the
  partitioner free to move activations instead.  When each collective
  runs relative to the compute around it is XLA's to schedule.
* fp16 overflow handling (CheckOverflow, dynamic loss scaler) runs inside
  the step with ``jnp.where`` — no host sync, no global state.

Public API mirrors the reference:

    engine = deepspeed_tpu.initialize(loss_fn=..., params=..., config=...)
    metrics = engine.train_batch(batch)       # one full optimizer step
    engine.save_checkpoint(dir); engine.load_checkpoint(dir)
"""

from __future__ import annotations

import dataclasses
import time
from typing import (Any, Callable, Dict, List, NamedTuple, Optional,
                    Tuple)

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from ..comm.comms_logging import comms_logger
from ..comm.mesh import DATA_AXIS, FSDP_AXIS, MeshTopology
from ..comm.collectives import init_distributed
from ..config.config import Config, ConfigError, load_config
from ..parallel.zero import ZeroPolicy
from ..parallel import sharding as shd
from ..telemetry import (AnomalyConfig, AnomalyMonitor, DeviceTelemetry,
                         MetricsRegistry, ProfilerCapture, SpanTracer,
                         default_training_detectors)
from ..utils.logging import log_dist, logger
from ..utils.timer import ThroughputTimer
from .loss_scaler import LossScaler, LossScaleState, all_finite
from .lr_schedules import build_schedule, constant
from .optimizers import Optimizer, build_optimizer
from .runtime_utils import clip_by_global_norm, global_norm, param_count

PRECISION_DTYPE = {"fp32": jnp.float32, "fp16": jnp.float16, "bf16": jnp.bfloat16}


class TrainState(NamedTuple):
    """Everything that persists across steps — a single donated pytree."""
    step: jnp.ndarray          # i32 scalar (optimizer steps taken)
    master: Any                # fp32 master params (sharded per ZeRO stage)
    opt_state: Any             # optimizer moments (sharded like master)
    loss_scale: LossScaleState
    skipped: jnp.ndarray       # i32 count of overflow-skipped steps


class OnebitCommState(NamedTuple):
    """Optimizer-state wrapper for 1-bit compressed communication: the
    base optimizer's state plus the per-shard error-feedback buffers
    (stacked over the reduce axes — each shard owns its slice)."""
    base: Any
    comm_err: Any


class _StagedBatch(dict):
    """Marker: this batch is already device-placed (and, when staged with
    accumulate=True and gas>1, reshaped to [gas, micro, ...])."""

    accumulate: bool = True


jax.tree_util.register_pytree_node(
    _StagedBatch,
    lambda d: (tuple(d[k] for k in sorted(d)), tuple(sorted(d))),
    lambda keys, vals: _StagedBatch(zip(keys, vals)))


class Engine:
    """TPU-native training engine (reference: DeepSpeedEngine engine.py:182)."""

    def __init__(self,
                 loss_fn: Callable,
                 params: Any,
                 config: Config,
                 topology: Optional[MeshTopology] = None,
                 param_axes: Any = None,
                 sharding_rules: Optional[Dict] = None,
                 eval_fn: Optional[Callable] = None,
                 monitor=None,
                 model: Any = None):
        """``loss_fn(params, batch, rng) -> loss`` or ``(loss, aux_dict)``.

        ``params`` is a pytree of arrays (any dtype; cast to fp32 master).
        ``param_axes`` is an optional matching pytree of logical-axis tuples
        for TP sharding; absent axes mean replicate-under-TP, fsdp-by-shape.
        """
        self.config = config
        init_distributed()
        hpz = config.zero_optimization.zero_hpz_partition_size
        mics = config.zero_optimization.mics_shard_size
        mesh_cfg = config.mesh
        if mics > 0 and hpz > 1:
            raise ConfigError(
                "mics_shard_size and zero_hpz_partition_size both bound "
                "the shard group; set only one")

        def fold_fsdp(mc, group: int, knob: str):
            """Shrink the fsdp axis to ``group`` and fold the remaining
            degree into data replicas (copy — the user's config object
            stays as written)."""
            if mc.fsdp <= 0:
                raise ConfigError(
                    f"{knob} requires an explicit mesh.fsdp size "
                    "(the full shard degree being bounded)")
            if mc.fsdp % group:
                raise ConfigError(f"{knob}={group} must divide "
                                  f"mesh.fsdp={mc.fsdp}")
            outer = mc.fsdp // group
            return dataclasses.replace(
                mc, fsdp=group,
                data=mc.data * outer if mc.data > 0 else mc.data)

        if mics > 0:
            if topology is not None:
                raise ConfigError(
                    "mics_shard_size remaps the mesh and cannot be "
                    "combined with a pre-built topology; pass mesh "
                    "config instead")
            # MiCS (reference: runtime/zero/mics.py:64): shard over a
            # sub-group of mics_shard_size instead of the full DP world —
            # params, masters AND optimizer state live within the group,
            # replicated across groups (unlike hpZ, which keeps masters
            # world-sharded).  Mesh formulation: fsdp shrinks to the
            # group size, the remaining degree folds into data replicas;
            # XLA's grad psum over data+fsdp IS the hierarchical
            # reduce-scatter-then-all-reduce of mics.py:254.
            # Exception: with offload_optimizer=cpu, masters/moments
            # world-shard over data x fsdp anyway (host-DRAM
            # minimization, zero.py master_spec) — the MiCS bound
            # applies to the DEVICE collectives (compute-param gathers),
            # which stay within the group either way.
            mesh_cfg = fold_fsdp(mesh_cfg, mics, "mics_shard_size")
        if topology is None and hpz > 1 and mesh_cfg.fsdp > hpz:
            # hpZ: the gather axis shrinks to the secondary-partition size
            # (intra-slice) and the rest of the requested fsdp degree folds
            # into data; masters still shard over data x fsdp (zero.py).
            mesh_cfg = fold_fsdp(mesh_cfg, hpz, "zero_hpz_partition_size")
        self.topology = topology or MeshTopology.build(mesh_cfg)
        self.loss_fn = loss_fn
        self.eval_fn = eval_fn

        # batch-size triangulation (reference: runtime/config.py:802-884)
        self.train_batch_size, self.micro_batch_size, self.gas = \
            config.resolve_batch_sizes(self.topology.dp_world_size)

        # precision policy
        self.precision = config.precision
        self.compute_dtype = PRECISION_DTYPE[self.precision]
        self.scaler = LossScaler.from_config(config.fp16)

        # ZeRO policy + shardings
        self.param_axes = (param_axes if param_axes is not None
                           else shd.infer_logical_axes(params))
        self.zero = ZeroPolicy.from_config(
            config.zero_optimization, self.topology, rules=sharding_rules)
        # ZeRO-Infinity: fp32 master + moments on NVMe, bf16 working copy
        # on device (reference: stage3.py:614 _configure_tensor_swapping)
        self._nvme = None
        off_opt = config.zero_optimization.offload_optimizer
        if off_opt.device == "nvme":
            from .zero_infinity import NVMeOptimizer
            self._nvme = NVMeOptimizer(
                off_opt.nvme_path, config.optimizer.type,
                config.optimizer.params, buffer_size=off_opt.buffer_size,
                aio_config=config.aio)
        # ZeRO-Infinity param streaming: offload_param=nvme + a
        # stacked-layer model => per-layer NVMe parameter streaming
        # (reference: partitioned_param_swapper.py:290 / stage3.py:614)
        self._model = model
        self._stream = None
        self._stream_params = (
            self._nvme is not None
            and config.zero_optimization.offload_param.device == "nvme"
            and model is not None and hasattr(model, "config")
            and isinstance(params, dict) and "blocks" in params)
        self._build_shardings(params)
        # ZeRO-3 over an fsdp axis that exists: the loss is told what stage
        # 3 means (parallel/zero.py placement) — parameters gathered per
        # use, activations split over the batch.  A loss that cannot take
        # it (a user's own, the pipeline's shard_map stages) runs as it
        # did: specs on the parameters, the rest left to the partitioner.
        self._zero3_gather = None
        if self.zero.gathers_per_use and hasattr(loss_fn, "with_placement"):
            self.loss_fn = loss_fn.with_placement(
                self.zero.placement(self.param_specs, self.use_specs))
            self._zero3_gather = self.zero.gather_bytes(
                self.param_specs, self.use_specs, self.param_shapes,
                jnp.dtype(self.compute_dtype).itemsize)
        self._qgz_axes = self._qgz_manual_axes()
        self._sparse_axes = self._sparse_manual_axes(params)
        # overlapped / quantized grad-sync collectives (comm/overlap.py;
        # ROADMAP item 1): explicit tile-decomposed reduce-scatter /
        # all-reduce (optionally on the qgZ int8/int4 wire) over the DP
        # axes.  qgZ proper (zero_quantized_gradients) and sparse
        # gradients keep precedence — they already own the manual
        # region; _manual_reduce_axes carries the PR-1 loud-degradation
        # contract for meshes that cannot host it.
        self._comm_axes: Tuple[str, ...] = ()
        ccfg = config.comm
        opt_name = config.optimizer.type.lower()
        onebit_opt = "onebit" in opt_name or "zeroone" in opt_name
        if (ccfg.overlap or ccfg.quantized_allreduce) \
                and not self._qgz_axes and not self._sparse_axes:
            if onebit_opt:
                # the documented precedence: a 1-bit optimizer's packed
                # sign+scale reduction with error feedback owns the
                # wire — silently replacing it with the comm path would
                # downgrade the compression the optimizer is built
                # around
                logger.warning(
                    "comm.overlap/comm.quantized_allreduce: a 1-bit "
                    "optimizer (%s) owns the gradient reduction; comm "
                    "settings ignored", config.optimizer.type)
            else:
                self._comm_axes = self._manual_reduce_axes(
                    "comm.overlap/comm.quantized_allreduce gradient sync")
        self._comm_wire: Optional[Dict[str, float]] = None

        # optimizer + schedule (reference: _configure_basic_optimizer :1322)
        opt_cfg = config.optimizer
        lr = opt_cfg.params.get("lr", 1e-3)
        if config.scheduler is not None:
            sched_params = dict(config.scheduler.params)
            if config.scheduler.type in ("WarmupCosineLR",):
                sched_params.setdefault("lr", lr)
            self.lr_schedule = build_schedule(config.scheduler.type, sched_params)
        else:
            self.lr_schedule = constant(lr)

        # 1-bit optimizers: route the DP gradient reduction through the
        # packed sign+scale collective with error feedback (reference:
        # compressed_allreduce nccl.py:16; up to 5x/32x comm reduction,
        # docs/_tutorials/onebit-adam.md:2)
        self._onebit_axes: Tuple[str, ...] = ()
        if ("onebit" in opt_cfg.type.lower()
                or "zeroone" in opt_cfg.type.lower()) \
                and self._nvme is None and not self._qgz_axes \
                and not self._sparse_axes \
                and not getattr(self, "offload_active", False):
            self._onebit_axes = self._manual_reduce_axes(
                "onebit compressed communication")
        self._onebit_freeze = 0
        if self._onebit_axes:
            # exact (uncompressed) reduction through the warmup, like the
            # reference's pre-freeze allreduce
            self._onebit_freeze = int(opt_cfg.params.get(
                "freeze_step", opt_cfg.params.get("var_freeze_step", 100)))
            self._onebit_b1 = float(
                opt_cfg.params.get("betas", (0.9, 0.999))[0])
            # the wire carries the compression now — the in-optimizer
            # momentum compression would compound the noise
            base_opt = build_optimizer(
                opt_cfg.type, self.lr_schedule,
                {**opt_cfg.params, "compress": False})
            W = int(np.prod([self.topology.axis_sizes[a]
                             for a in self._onebit_axes]))

            def ob_init(master, _base=base_opt, _w=W):
                return OnebitCommState(
                    base=_base.init(master),
                    comm_err=jax.tree.map(
                        lambda p: jnp.zeros((_w,) + p.shape, jnp.float32),
                        master))

            self.optimizer = Optimizer(ob_init, base_opt.update)
        else:
            self.optimizer: Optimizer = build_optimizer(
                opt_cfg.type, self.lr_schedule, opt_cfg.params)

        # state init (sharded via jit out_shardings → no host-side gather)
        state = self._init_state(params)
        # the replicated scalars start where the step returns them —
        # committed to the mesh.  Left as fresh uncommitted arrays they
        # change type after step 1 and the SECOND call re-traces and
        # recompiles the whole train step (52 s on the chip at GPT-2s)
        self.state = state._replace(**jax.device_put(
            dict(step=state.step, loss_scale=state.loss_scale,
                 skipped=state.skipped), self.repl))
        self.global_steps = 0
        self.global_samples = 0

        self.tput = ThroughputTimer(batch_size=self.train_batch_size)
        self._t_entry: Optional[float] = None   # last train_batch entry
        self._setup_telemetry()
        if monitor is None and (config.tensorboard.enabled
                                or config.csv_monitor.enabled
                                or config.wandb.enabled
                                or config.comet.enabled):
            # reference: MonitorMaster constructed by the engine
            # (engine.py:259) from the monitor sub-configs
            from ..monitor import MonitorMaster
            monitor = MonitorMaster(config)
            if not monitor.enabled:
                monitor = None
        self.monitor = monitor
        self._train_step_fn = None
        self._warmup_step_fn = None
        self._eval_step_fn = None
        self._nvme_step_fn = None
        self._setup_data_efficiency()

        log_dist(
            f"Engine: {param_count(params):,} params | precision={self.precision} "
            f"| zero_stage={self.zero.stage} | mesh={self.topology.axis_sizes} "
            f"| batch={self.train_batch_size} (micro={self.micro_batch_size} "
            f"x gas={self.gas} x dp={self.topology.dp_world_size})")

    # ------------------------------------------------------------------
    # telemetry (docs/OBSERVABILITY.md)
    # ------------------------------------------------------------------
    def _setup_telemetry(self) -> None:
        """Metrics registry + span tracer for the training step's host
        phases.  Everything is host-side floats — the step itself is one
        fused jit program, so the phases telemetry can see are the host
        work around it: data-efficiency pre-step, batch staging, the
        (async) dispatch, and the metrics fetch.  Serving metrics and
        these training counters share the registry/export machinery
        (telemetry/metrics.py), and :meth:`_finish_step` fans both
        through the same ``monitor/`` writers as the loss scalars."""
        tcfg = self.config.telemetry
        self.metrics = MetricsRegistry()
        self.tracer = SpanTracer(capacity=tcfg.trace_capacity,
                                 enabled=tcfg.trace)
        reg = self.metrics
        self._phase_ms = {
            k: reg.counter(f"training_{k}_ms_total",
                           f"cumulative host milliseconds in the {k} "
                           "phase of train_batch")
            for k in ("pre_step", "stage", "dispatch", "fetch")}
        self._c_steps = reg.counter("training_steps_total",
                                    "optimizer steps taken",
                                    int_valued=True)
        self._h_step_host = reg.histogram(
            "training_step_host_ms",
            (0.5, 1.0, 2.0, 5.0, 10.0, 20.0, 50.0, 100.0, 200.0, 500.0,
             1000.0, 2000.0, 5000.0, 10000.0, 60000.0),
            "host-side wall ms per train_batch call (dispatch is async: "
            "device time appears here only when something blocks)")
        self._h_step_interval = reg.histogram(
            "training_step_interval_ms",
            (1.0, 2.0, 5.0, 10.0, 20.0, 50.0, 100.0, 200.0, 500.0, 1000.0,
             2000.0, 5000.0, 10000.0, 60000.0, 600000.0),
            "wall ms between consecutive train_batch entries: one step's "
            "time once the device's queue holds the caller back (what "
            "tput, training_mfu and the flops profile divide by)")
        # compile observatory (docs/OBSERVABILITY.md "Device & compiler
        # telemetry"): always-on host counters — a train-step rebuild
        # after the first is a runtime retrace and warns loudly (the
        # dynamic complement of tpulint's static retrace-hazard rule)
        # overlapped/quantized grad-sync collectives (docs/SERVING.md
        # "Overlapped & quantized collectives"): static per-step wire
        # accounting for the comm.{overlap,quantized_allreduce} path —
        # quantized ops carry bits/8 of the exact bytes (asserted by
        # the reconciliation test)
        self._c_comm_ops = reg.counter(
            "training_comm_ops_total",
            "explicit grad-sync collectives dispatched "
            "(kind: exact|quant)", int_valued=True)
        self._c_comm_tiles = reg.counter(
            "training_comm_tiles_total",
            "tiles across dispatched grad-sync collectives",
            int_valued=True)
        self._c_comm_bytes = reg.counter(
            "training_comm_bytes_total",
            "modeled bytes on the wire for explicit grad-sync "
            "collectives (kind: exact|quant)")
        # eager-collective profiling (comm/comms_logging.py): configure
        # the module logger from config and mirror its op records into
        # this registry as training_comm_* counters, so comm time shows
        # up in Prometheus exposition and flight dumps instead of only
        # the ad-hoc log_summary() table
        clcfg = self.config.comms_logger
        if clcfg.enabled:
            comms_logger.configure(enabled=True, verbose=clcfg.verbose,
                                   prof_all=clcfg.prof_all,
                                   prof_ops=clcfg.prof_ops)
        comms_logger.attach_registry(reg)
        self._c_compiles = reg.counter(
            "training_compiles_total",
            "training step programs built (jit-cache fills)",
            int_valued=True)
        self._c_retraces = reg.counter(
            "training_compile_retraces_total",
            "re-builds of a program key this engine had already "
            "compiled (runtime retrace — each warns loudly)",
            int_valued=True)
        self._compiled_ever: set = set()
        # gated device telemetry (telemetry/device.py): per-program
        # cost_analysis + derived training_mfu / training_hbm_bw_util
        # gauges, divided by the throughput timer's step wall: the
        # interval between consecutive train_batch entries, which a
        # full device queue holds to the device's own step time (the
        # dispatch call is async and returns in 2 ms of a 464 ms step,
        # so neither it nor the host phase ms are a step's time) +
        # memory polling at the steps_per_print boundary.  config:
        # {"telemetry": {"device": true}}
        self.devtel = DeviceTelemetry(
            reg, "training",
            step_ms_fn=lambda: self.tput.total_elapsed_time * 1e3) \
            if tcfg.device else None
        # streaming anomaly detection (telemetry/anomaly.py): None when
        # off — the step path then contains no detector call and no
        # added clock read (the serving engine's zero-cost bar, shared)
        self._acfg = AnomalyConfig()
        self._anom = None
        self._anom_prev: Dict[str, float] = {}
        if tcfg.anomaly:
            self._anom = AnomalyMonitor(self._acfg, reg, "training")
            self._anom.watch_all(default_training_detectors(self._acfg))
        # deep-capture windows (telemetry/profiler.py): the training
        # engine's one profiler seam, same artifact layout as serving
        # (tools/tracemerge.py merges host phases + device trace)
        self._cap = None
        if tcfg.profile:
            self._cap = ProfilerCapture(tcfg.profile, tracer=self.tracer,
                                        max_captures=self._acfg.
                                        max_captures)
            if tcfg.profile_steps > 0:
                self._cap.arm(tcfg.profile_steps, "config")

    def anomaly_summary(self) -> Optional[Dict[str, Any]]:
        """JSON-able anomaly tally (total / by-signal / recent events +
        completed capture dirs); None while anomaly detection is off."""
        if self._anom is None:
            return None
        return {**self._anom.summary(), "captures": self.capture_dirs}

    @property
    def capture_dirs(self) -> List[str]:
        return [] if self._cap is None else list(self._cap.captures)

    def capture(self, steps: Optional[int] = None,
                reason: str = "manual",
                out_dir: Optional[str] = None) -> Optional[str]:
        """Arm an explicit deep-capture window over the next ``steps``
        train steps (jax.profiler device trace + host phase spans,
        merged by tools/tracemerge.py); returns the capture dir or
        None when a window is already armed/active."""
        if self._cap is None:
            if not out_dir:
                raise ValueError("no capture directory: pass out_dir= "
                                 "or set config telemetry.profile")
            self._cap = ProfilerCapture(out_dir, tracer=self.tracer,
                                        max_captures=self._acfg.
                                        max_captures)
        return self._cap.arm(steps or self._acfg.capture_steps, reason,
                             budgeted=False)

    def finish_capture(self) -> Optional[str]:
        """Close any ACTIVE capture window immediately with the steps
        it has (releases the process-wide jax profiler session and the
        force-enabled tracer) — call when training ends before a
        window armed for more steps ran out.  Returns the capture dir
        or None."""
        if self._cap is None or not self._cap.active:
            return None
        return self._cap.finish_now()

    def _feed_step_signals(self, interval_ms: Optional[float],
                           host_ms: float) -> None:
        """Per-step anomaly feed from timestamps already taken (no
        added clock reads); called only when the monitor exists."""
        anom, prev = self._anom, self._anom_prev
        step = self.global_steps
        fired = []
        if interval_ms is not None:
            fired.append(anom.observe("step_interval_ms", interval_ms,
                                      step))
        fired.append(anom.observe("step_host_ms", host_ms, step))
        retr = self._c_retraces.value()
        fired.append(anom.observe("retrace",
                                  retr - prev.get("retrace", 0), step))
        prev["retrace"] = retr
        for ev in fired:
            if ev is not None:
                logger.warning(
                    "training anomaly: %s observed=%.3f baseline=%.3f "
                    "score=%.1f (step %d)", ev.signal, ev.observed,
                    ev.baseline, ev.score, ev.step)
                if self._cap is not None:
                    self._cap.arm(self._acfg.capture_steps,
                                  f"anomaly_{ev.signal}", budgeted=True)

    def _note_compile(self, key: str) -> None:
        self._c_compiles.inc()
        if key in self._compiled_ever:
            self._c_retraces.inc()
            logger.warning(
                "training program %r RECOMPILED at runtime (retrace "
                "#%d) — something invalidated the step executable",
                key, int(self._c_retraces.value()))
        else:
            unrolled = getattr(self.loss_fn, "layers_unrolled", None)
            if unrolled is not None:
                # what the loss's forward does at this build, from its
                # layer plan (models/transformer.py layers_unrolled)
                self.metrics.gauge(
                    "training_layers_unrolled",
                    "layers of the layer scan the compiled train step "
                    "runs unrolled, outside any while loop; 0: the scan "
                    "is rolled").set(unrolled())
            if self._zero3_gather is not None and not self._compiled_ever:
                # static, from the specs: no device read
                leaves, nbytes = self._zero3_gather
                self.metrics.gauge(
                    "training_zero3_gather_bytes_per_step",
                    "ZeRO-3: bytes of compute-dtype parameters one chip "
                    "receives per step for ONE gather of every sharded "
                    "leaf per micro-batch; forward, backward and (under "
                    "remat) recomputation each make one").set(
                        nbytes * self.gas)
                log_dist(
                    f"ZeRO-3: {leaves} parameter leaves gathered per use "
                    f"over fsdp={self.topology.axis_sizes[FSDP_AXIS]}, "
                    f"{nbytes * self.gas:,} bytes a chip a pass a step "
                    f"({jnp.dtype(self.compute_dtype).name}); activations "
                    "stay split over the batch")
            self._compiled_ever.add(key)

    def metrics_snapshot(self) -> Dict[str, Any]:
        """JSON-able snapshot of the training metrics registry; see also
        ``engine.metrics.prometheus_text()`` and
        ``engine.metrics.write_jsonl(path)``."""
        return self.metrics.snapshot()

    # ------------------------------------------------------------------
    # sharding setup
    # ------------------------------------------------------------------
    def _build_shardings(self, params):
        topo = self.topology
        zero = self.zero
        self.param_shapes = jax.tree.map(lambda p: tuple(np.shape(p)),
                                         params)
        self.param_specs = zero.tree_param_specs(self.param_axes, params)
        self.use_specs = zero.tree_use_specs(self.param_axes, params)
        self.master_specs = zero.tree_master_specs(self.param_axes, params)
        self.grad_specs = zero.tree_grad_specs(self.param_axes, params)
        self.param_shardings = zero.tree_named(self.param_specs)
        self.master_shardings = zero.tree_named(self.master_specs)
        self.batch_sharding = topo.batch_sharding()
        self.repl = NamedSharding(topo.mesh, P())

        # ZeRO-Offload: master params + optimizer moments live in host DRAM
        # (memory_kind pinned_host); XLA streams them through the device at
        # step time.  The reference's analogous path is CPU optimizer state
        # + DeepSpeedCPUAdam (stage_1_and_2 cpu_offload, csrc/adam) — under
        # XLA the "CPU adam" is the compiler-scheduled host<->HBM transfer
        # around the same fused update.
        self.offload_active = False
        self._offload_validated = False
        if self._nvme is not None:
            # ZeRO-Infinity: the device-resident state is the bf16 working
            # copy in the *compute* layout (fp32 master + moments live on
            # NVMe, see runtime/zero_infinity.py); offload_param=cpu/nvme
            # additionally pins the working copy to host DRAM so HBM only
            # holds parameters transiently during the step.
            self.master_specs = self.param_specs
            self.master_shardings = self.param_shardings
            offp = self.config.zero_optimization.offload_param.device
            if self._stream_params:
                # per-layer NVMe param streaming: the working copy never
                # stages anywhere whole — layers stream through HBM
                # (param_stream.py); shardings stay plain device specs
                return
            if offp in ("cpu", "nvme"):
                if offp == "nvme":
                    logger.warning(
                        "offload_param.device=nvme without a stacked-"
                        "layer model: staging the full bf16 working copy "
                        "in host DRAM; pass model= (models.transformer) "
                        "to stream parameters per layer instead")
                if self._host_memory_supported():
                    multi = self.topology.mesh.size > 1
                    self.master_shardings = jax.tree.map(
                        lambda sh: sh if (multi and sh.is_fully_replicated)
                        else sh.with_memory_kind("pinned_host"),
                        self.master_shardings)
                    self.offload_active = True
                else:
                    logger.warning(
                        "offload_param requested but this backend has no "
                        "pinned_host memory space; ignoring")
            return
        zcfg = self.config.zero_optimization
        if (zcfg.offload_optimizer.device == "cpu"
                or zcfg.offload_param.device == "cpu"):
            # offload_param=cpu without NVMe state rides the same host-DRAM
            # master placement: compute params are cast from the
            # host-placed master each step, so the persistent fp32/param
            # footprint leaves HBM either way (reference:
            # offload_param/offload_optimizer offload_config.py)
            if "lamb" in self.config.optimizer.type.lower():
                # LAMB trust ratios need whole-tensor norms; the offload
                # update runs per-shard inside shard_map, which would
                # silently compute per-shard ratios.
                raise ConfigError(
                    "optimizer offload is not supported with LAMB: trust "
                    "ratios need whole-tensor parameter/update norms, but "
                    "the offloaded update runs per-shard inside shard_map "
                    "and would silently compute per-shard ratios. Use "
                    "adam/adamw/lion/adagrad/sgd with offload, or drop "
                    "offload_optimizer/offload_param for LAMB.")
            if self._host_memory_supported():
                # Per-leaf placement: only sharded leaves move to host DRAM.
                # Under multi-device SPMD, fully-replicated leaves (tiny
                # params the mesh can't divide) stay in HBM — the
                # partitioner cannot express a memory-space transfer of a
                # replicated value, and their footprint is negligible.  On
                # a single-chip mesh there is no partitioning, so
                # everything pins to host (the reference's 1-GPU
                # ZeRO-Offload headline case).
                multi = self.topology.mesh.size > 1
                self.master_shardings = jax.tree.map(
                    lambda sh: sh if (multi and sh.is_fully_replicated)
                    else sh.with_memory_kind("pinned_host"),
                    self.master_shardings)
                self.offload_active = True
            else:
                logger.warning(
                    "offload_optimizer.device=cpu requested but this "
                    "backend has no pinned_host memory space; ignoring")

    @staticmethod
    def _host_memory_supported() -> bool:
        try:
            jax.devices()[0].memory("pinned_host")
            return True
        except Exception:  # tpulint: disable=silent-except — capability probe
            return False

    def _opt_state_shardings(self, opt_state, master):
        """Optimizer moments mirror the master param sharding.

        Any opt-state subtree whose structure equals the master param tree
        (e.g. AdamState.m / .v) gets the master shardings; NamedTuple
        wrappers are recursed into; anything else replicates."""
        master_def = jax.tree.structure(master)

        def rec(node):
            if isinstance(node, OnebitCommState):
                err_sh = jax.tree.map(
                    lambda _: NamedSharding(
                        self.topology.mesh, P(self._onebit_axes)),
                    node.comm_err)
                return OnebitCommState(base=rec(node.base),
                                       comm_err=err_sh)
            if jax.tree.structure(node) == master_def:
                return self.master_shardings
            if isinstance(node, tuple) and hasattr(node, "_fields"):
                return type(node)(*[rec(f) for f in node])
            return jax.tree.map(lambda _: self.repl, node)

        return rec(opt_state)

    # ------------------------------------------------------------------
    # state init
    # ------------------------------------------------------------------
    def _init_state(self, params) -> TrainState:
        if self._nvme is not None:
            return self._init_state_nvme(params)

        def init_fn(p):
            master = jax.tree.map(lambda x: x.astype(jnp.float32), p)
            opt_state = self.optimizer.init(master)
            return master, opt_state

        # discover opt-state structure via eval_shape, then jit w/ device
        # shardings; host (pinned_host) placement happens *outside* jit via
        # device_put — out_shardings with host memory kinds trip the SPMD
        # partitioner on some backends when the value aliases an input.
        master_shape, opt_shape = jax.eval_shape(init_fn, params)
        device_master_sh = jax.tree.map(
            lambda sh: NamedSharding(self.topology.mesh, sh.spec),
            self.master_shardings)
        opt_shardings = self._opt_state_shardings(opt_shape, master_shape)
        device_opt_sh = jax.tree.map(
            lambda sh: NamedSharding(self.topology.mesh, sh.spec),
            opt_shardings)
        init_jit = jax.jit(init_fn, out_shardings=(device_master_sh,
                                                   device_opt_sh))
        master, opt_state = init_jit(params)
        if self.offload_active:
            try:
                master = jax.device_put(master, self.master_shardings)
                opt_state = jax.device_put(opt_state, opt_shardings)
            except Exception as e:
                logger.warning(
                    "optimizer offload unsupported for this mesh/layout "
                    "(%s); keeping optimizer state in device memory",
                    str(e).splitlines()[0][:120])
                self.offload_active = False
                self.master_shardings = device_master_sh
                opt_shardings = device_opt_sh
                # the first put may have committed master to host already
                master = jax.device_put(master, device_master_sh)
                opt_state = jax.device_put(opt_state, device_opt_sh)
        self.opt_shardings = opt_shardings
        return TrainState(
            step=jnp.zeros((), jnp.int32),
            master=master,
            opt_state=opt_state,
            loss_scale=self.scaler.init(),
            skipped=jnp.zeros((), jnp.int32))

    def _init_state_nvme(self, params) -> TrainState:
        """ZeRO-Infinity init: fp32 master + zero moments written straight
        to NVMe (never materialized in HBM); the device keeps only the
        bf16 working copy in the compute layout — or, with param
        streaming, only the RESIDENT (non-layer) leaves."""
        if self._stream_params:
            from .param_stream import StreamedInfinityTrainer
            self._stream = StreamedInfinityTrainer(self, self._model,
                                                   params)
            self.opt_shardings = ()
            return TrainState(
                step=jnp.zeros((), jnp.int32),
                master=self._stream.resident,
                opt_state=(),
                loss_scale=self.scaler.init(),
                skipped=jnp.zeros((), jnp.int32))
        dev_sh = jax.tree.map(
            lambda sh: NamedSharding(self.topology.mesh, sh.spec),
            self.master_shardings)
        cast = jax.jit(
            lambda p: jax.tree.map(
                lambda x: x.astype(self.compute_dtype), p),
            out_shardings=dev_sh)
        master = cast(params)
        if self.offload_active:
            try:
                master = jax.device_put(master, self.master_shardings)
            except Exception as e:
                logger.warning(
                    "param offload unsupported for this mesh/layout (%s); "
                    "keeping the working copy in device memory",
                    str(e).splitlines()[0][:120])
                self.offload_active = False
                self.master_shardings = dev_sh
        # multi-host: masters partition into per-process fragments along
        # the GRADIENT layout — the layout step grads arrive in, so every
        # process's update reads only addressable shards (reference:
        # per-rank swap, stage3.py:614)
        self._nvme_grad_sh = jax.tree.map(
            lambda sp: NamedSharding(self.topology.mesh, sp),
            self.grad_specs, is_leaf=lambda x: isinstance(x, P))
        self._nvme_reshard_fn = None
        self._nvme.initialize(params, shardings=self._nvme_grad_sh)
        self.opt_shardings = ()
        return TrainState(
            step=jnp.zeros((), jnp.int32),
            master=master,
            opt_state=(),
            loss_scale=self.scaler.init(),
            skipped=jnp.zeros((), jnp.int32))

    @property
    def state_shardings(self) -> TrainState:
        return TrainState(
            step=self.repl, master=self.master_shardings,
            opt_state=self.opt_shardings,
            loss_scale=LossScaleState(self.repl, self.repl, self.repl),
            skipped=self.repl)

    # ------------------------------------------------------------------
    # data-efficiency family (reference: engine.py:288,346-356 —
    # curriculum/random-LTD/PLD/MoQ hooks driven purely by the config)
    # ------------------------------------------------------------------
    def _setup_data_efficiency(self) -> None:
        cfg = self.config
        self.curriculum = None
        self.curriculum_sampler = None
        ccfg = cfg.curriculum_learning
        de = cfg.data_efficiency
        if de.enabled and de.data_sampling.enabled \
                and de.data_sampling.curriculum_learning.enabled:
            ccfg = de.data_sampling.curriculum_learning
        if ccfg.enabled:
            from .data_pipeline import (CurriculumDataSampler,
                                        CurriculumScheduler)

            def sched():
                return CurriculumScheduler({
                    "min_difficulty": ccfg.min_difficulty,
                    "max_difficulty": ccfg.max_difficulty,
                    "schedule_type": ccfg.schedule_type,
                    "schedule_config": ccfg.schedule_config})

            if ccfg.curriculum_type == "seqlen":
                # batch-shape curriculum: the engine truncates each batch
                # in _data_efficiency_pre_step
                self.curriculum = sched()
            else:
                # metric-indexed curriculum: any DataAnalyzer metric drives
                # *sampling order* (reference: data_sampler.py consuming
                # index files produced by data_analyzer.py) — consumed via
                # curriculum_dataloader()/curriculum_sampler
                if not ccfg.data_analyzer_path:
                    raise ConfigError(
                        f"curriculum_type={ccfg.curriculum_type!r}: a "
                        "metric curriculum needs data_analyzer_path "
                        "pointing at a DataAnalyzer save dir containing "
                        f"{ccfg.curriculum_type}/sample_to_metric.npy")
                try:
                    self.curriculum_sampler = CurriculumDataSampler\
                        .from_analyzer(
                            ccfg.data_analyzer_path, ccfg.curriculum_type,
                            sched(), self.train_batch_size, seed=cfg.seed)
                except FileNotFoundError as e:
                    raise ConfigError(
                        f"curriculum_type={ccfg.curriculum_type!r}: no "
                        f"analyzer index under "
                        f"{ccfg.data_analyzer_path!r} ({e}); run "
                        "runtime.data_analyzer.DataAnalyzer first") from e

        self.pld = None
        if cfg.progressive_layer_drop.enabled:
            if not getattr(self.loss_fn, "uses_pld", False):
                raise ConfigError(
                    "progressive_layer_drop: this loss_fn does not "
                    "consume _pld_theta — initialize with model=")
            from .progressive_layer_drop import ProgressiveLayerDrop
            self.pld = ProgressiveLayerDrop(
                cfg.progressive_layer_drop.theta,
                cfg.progressive_layer_drop.gamma)

        self._ltd_cfg = None
        self._ltd_sched = None
        self._ltd_keep = None
        rl = de.data_routing.random_ltd
        if de.enabled and de.data_routing.enabled and rl.enabled:
            if not hasattr(self.loss_fn, "with_ltd"):
                raise ConfigError(
                    "random_ltd: this loss_fn has no with_ltd hook — "
                    "initialize with model=")
            self._ltd_base_loss = self.loss_fn
            # max_value=0 means "the batch's seqlen" — resolved against
            # the first batch (the scheduler needs the real target or the
            # anneal overshoots at step 1 and silently disables LTD)
            self._ltd_cfg = rl

        self.moq = None
        qt = cfg.quantize_training
        if qt.enabled:
            from .quantize import Quantizer
            self.moq = Quantizer(
                q_start_bits=qt.start_bits, q_target_bits=qt.target_bits,
                q_period=qt.quantize_period, q_groups=qt.quantize_groups)
            self._moq_bits = None
            self._moq_eig0 = None
            self._eig = None
            if qt.eigenvalue.enabled:
                from .eigenvalue import Eigenvalue
                self._eig = Eigenvalue(max_iter=qt.eigenvalue.max_iter,
                                       tol=qt.eigenvalue.tol,
                                       stability=qt.eigenvalue.stability)

    def _data_efficiency_pre_step(self, batch, rng):
        """Apply the scheduled per-step transforms; returns the possibly
        modified batch (host-side, before sharding)."""
        step = self.global_steps
        if self.curriculum is not None:
            from .data_pipeline import truncate_to_difficulty
            batch = truncate_to_difficulty(
                batch, self.curriculum.get_difficulty(step + 1))
        if self._ltd_cfg is not None:
            from .data_pipeline import RandomLTDScheduler
            S = int(np.shape(batch["input_ids"])[1])
            max_t = min(self._ltd_cfg.max_value or S, S)
            if self._ltd_sched is None or self._ltd_sched.max != max_t:
                self._ltd_sched = RandomLTDScheduler(
                    total_layers=0,
                    start_tokens=min(self._ltd_cfg.min_value, max_t),
                    max_tokens=max_t,
                    schedule_steps=self._ltd_cfg.require_steps,
                    step_size=self._ltd_cfg.seq_per_step)
            keep = min(self._ltd_sched.kept_tokens(step), S)
            keep_eff = None if keep >= S else keep
            if keep_eff != self._ltd_keep:
                self._ltd_keep = keep_eff
                self.loss_fn = (self._ltd_base_loss if keep_eff is None
                                else self._ltd_base_loss.with_ltd(keep_eff))
                self._train_step_fn = self._warmup_step_fn = None
                self._eval_step_fn = None
                self._nvme_step_fn = None
        if self.pld is not None:
            # injected BEFORE the MoQ block: _measure_eigenvalue slices
            # this batch and traces the pld-consuming loss
            theta = self.pld.update_state(step)
            B = int(np.shape(batch["input_ids"])[0])
            batch = dict(batch)
            # per-row column: survives batch sharding / the gas reshape;
            # the loss reads element 0 of its local shard
            batch["_pld_theta"] = np.full((B,), theta, np.float32)
        if self.moq is not None:
            qt = self.config.quantize_training
            bits = self.moq.current_bits(step)
            boundary = (step > 0 and step % self.moq.period == 0
                        and bits > self.moq.target_bits)
            if self._eig is not None and boundary:
                # eigenvalue pacing (reference: eigenvalue-scheduled MoQ):
                # growing curvature postpones the next bit reduction
                eig = self._measure_eigenvalue(batch, rng)
                if self._moq_eig0 is None:
                    self._moq_eig0 = abs(eig)
                elif abs(eig) > 1.5 * self._moq_eig0:
                    self.moq.period *= 2
                    logger.info(
                        f"MoQ: |eigenvalue| grew {abs(eig):.3g} vs "
                        f"{self._moq_eig0:.3g}; quantize_period -> "
                        f"{self.moq.period}")
                    bits = self.moq.current_bits(step)
            if bits != self._moq_bits:
                self._moq_bits = bits
                self._train_step_fn = self._warmup_step_fn = None
                self._eval_step_fn = None
                self._nvme_step_fn = None
                if hasattr(self, "_compute_params_fn"):
                    del self._compute_params_fn
        return batch

    def curriculum_dataloader(self, data, **kwargs):
        """Build a :class:`~deepspeed_tpu.runtime.dataloader.DataLoader`
        whose sampling order follows the configured metric curriculum
        (reference: engine.deepspeed_io attaching DeepSpeedDataSampler).
        Only valid when a non-seqlen ``curriculum_type`` is configured."""
        if self.curriculum_sampler is None:
            raise ConfigError(
                "curriculum_dataloader() needs a metric curriculum "
                "(curriculum_learning with curriculum_type != 'seqlen' "
                "and data_analyzer_path set)")
        from .dataloader import DataLoader
        return DataLoader(data, self.train_batch_size,
                          sampler=self.curriculum_sampler, **kwargs)

    def _measure_eigenvalue(self, batch, rng) -> float:
        """Dominant Hessian eigenvalue of the micro-loss at the current
        params (host-driven power iteration; period boundaries only)."""
        micro = jax.tree.map(lambda x: np.asarray(x)[:self.micro_batch_size],
                             batch)
        cparams = self._compute_params(self.state.master)

        def scalar_loss(p):
            out = self.loss_fn(p, micro, rng)
            return out[0] if isinstance(out, tuple) else out

        eig, _ = self._eig.compute_eigenvalue(scalar_loss, cparams, rng)
        return float(eig)

    # ------------------------------------------------------------------
    # the train step
    # ------------------------------------------------------------------
    def _compute_params(self, master):
        """Cast fp32 master → compute dtype, re-shard to the compute-param
        layout.  For ZeRO 1/2 this makes XLA all-gather in the *compute*
        dtype (half the bytes of an fp32 gather) — the comm-pattern analog
        of all_gather_dp_groups of fp16 shards (stage_1_and_2.py:1823)."""
        offloaded = self.offload_active
        qwz = self.config.zero_optimization.zero_quantized_weights

        def cast(p, spec, msh):
            if offloaded and getattr(msh, "memory_kind", None) == "pinned_host":
                # host->HBM transfer first (jit-legal device_put), then cast
                p = jax.device_put(p, NamedSharding(
                    self.topology.mesh, msh.spec, memory_kind="device"))
            if qwz:
                q = self._qwz_gather(p, msh.spec, spec)
                if q is not None:
                    return q.astype(self.compute_dtype)
            c = p.astype(self.compute_dtype)
            return jax.lax.with_sharding_constraint(
                c, NamedSharding(self.topology.mesh, spec))
        with jax.named_scope("cast_params"):
            out = jax.tree.map(cast, master, self.param_specs,
                               self.master_shardings)
        if qwz and not getattr(self, "_qwz_applied", False) \
                and not getattr(self, "_qwz_noop_warned", False):
            # plain stage 3: compute and master layouts coincide, so the
            # only gathers are the per-use ones the loss states through
            # its Placement (zero.py), in the compute dtype, where this
            # explicit path does not reach; combine qwZ with hpZ or
            # offload for an actual quantized gather boundary
            self._qwz_noop_warned = True
            logger.warning(
                "zero_quantized_weights: no parameter has a "
                "master->compute gather boundary under this config; "
                "weight gathers stay full-precision (combine with "
                "zero_hpz_partition_size or offload, or use stage<=2)")
        bits = getattr(self, "_moq_bits", None)
        if bits is not None and bits <= 8:
            # MoQ: fake-quantize 2-D+ weights in the forward at the
            # scheduled bit width (reference: quantize_weight_in_forward)
            from ..compression.compress import weight_quantization
            g = self.config.quantize_training.quantize_groups
            out = jax.tree.map(
                lambda w: weight_quantization(w, bits=bits, groups=g)
                if hasattr(w, "ndim") and w.ndim >= 2 else w, out)
        return out

    def _qwz_gather(self, p, mspec, pspec):
        """qwZ: int8-quantized weight all-gather (ZeRO++; reference:
        CUDAQuantizer partition_parameters.py:753, zeropp.md — 2x less
        all-gather traffic).  Replaces the implicit XLA gather from the
        master layout to the compute layout with an explicit shard_map
        int8 gather over the extra (fsdp/data) axes.  Returns None when
        the leaf has no extra sharded axes (nothing to gather)."""
        def axes_of(entry):
            if entry is None:
                return ()
            return (entry,) if isinstance(entry, str) else tuple(entry)

        ndim = len(np.shape(p))
        ments = list(mspec) + [None] * (ndim - len(list(mspec)))
        pents = list(pspec) + [None] * (ndim - len(list(pspec)))
        extra = []
        for d in range(ndim):
            gather_axes = [a for a in axes_of(ments[d])
                           if a not in axes_of(pents[d])
                           and self.topology.axis_sizes.get(a, 1) > 1]
            if gather_axes:
                extra.append((d, gather_axes))
        if not extra:
            return None
        self._qwz_applied = True
        from ..ops.quant import quantized_all_gather

        def local(x):
            for d, axes in extra:
                # minor axis first: sharding (a, b) splits the dim
                # a-major, so reconstruct b-blocks inside each a-block
                for ax in reversed(axes):
                    x = quantized_all_gather(x, ax, bits=8, gather_dim=d)
            return x

        # check_vma can't statically prove the all_gather output is
        # replicated along the gathered axes
        return shard_map(local, mesh=self.topology.mesh,
                             in_specs=mspec, out_specs=pspec,
                             check_vma=False)(p)

    # ------------------------------------------------------------------
    # qgZ: quantized gradient reduction (ZeRO++ third leg)
    # ------------------------------------------------------------------
    def _qgz_manual_axes(self) -> Tuple[str, ...]:
        """Mesh axes whose gradient reduction runs through the explicit
        int8 collectives instead of XLA's implicit fp32 reduce.

        data always; fsdp only through stage 2 — at stage 3 the compute
        params are fsdp-sharded and the per-use gathers and gradient
        reduce-scatters the loss states (zero.py ``placement``) are
        sharding constraints over an AUTO fsdp axis, so fsdp-axis
        reductions, the replicated (persistent) leaves' among them,
        remain full-precision."""
        if not self.config.zero_optimization.zero_quantized_gradients:
            return ()
        return self._manual_reduce_axes("zero_quantized_gradients")

    def _sparse_manual_axes(self, params) -> Tuple[str, ...]:
        """Mesh axes for the sparse embedding-grad reduction
        (config.sparse_gradients; reference: sparse_gradients_enabled +
        engine.py sparse_allreduce_bucket)."""
        if not self.config.sparse_gradients:
            return ()
        if self.config.zero_optimization.zero_quantized_gradients:
            self._degrade("sparse_gradients + zero_quantized_gradients: "
                          "qgZ takes the manual reduction; "
                          "sparse_gradients is dropped")
            return ()
        # tied embeddings feed the unembed projection: the table's grad
        # is DENSE over the vocab and row-capacity truncation would
        # silently corrupt it.  Untied models carry a separate lm_head
        # leaf — absence means tied; warn-and-disable.
        from ..parallel.zero import _is_axes
        a_flat = jax.tree.leaves(self.param_axes, is_leaf=_is_axes)
        has_vocab_table = any(
            isinstance(a, tuple) and len(a) >= 2 and a[0] == "vocab"
            for a in a_flat)
        untied = isinstance(params, dict) and "lm_head" in params
        if has_vocab_table and not untied:
            self._degrade("sparse_gradients: model ties embeddings (no "
                          "lm_head leaf) — the vocab-table gradient is "
                          "dense; sparse_gradients is dropped")
            return ()
        return self._manual_reduce_axes("sparse_gradients")

    def _degrade(self, msg: str) -> None:
        """Unsupported feature combination: hard error unless the config
        opts into degradation (``allow_feature_degradation``) — silently
        weaker training is worse than a loud stop (the reference composes
        e.g. 1-bit with PP; we do not yet)."""
        if self.config.allow_feature_degradation:
            logger.warning(msg)
            return
        from ..config.config import ConfigError
        raise ConfigError(
            msg + " — set allow_feature_degradation=true to run anyway "
            "with the plain reduction")

    def _manual_reduce_axes(self, feature: str) -> Tuple[str, ...]:
        sizes = self.topology.axis_sizes
        if sizes.get("pipe", 1) > 1 or sizes.get("seq", 1) > 1:
            # both wrap the loss in their own shard_map (pipeline stages /
            # Ulysses all_to_all), which cannot nest inside the manual
            # region
            self._degrade(f"{feature} is not composable with pipeline "
                          "or sequence parallelism yet")
            return ()
        axes = []
        if sizes.get(DATA_AXIS, 1) > 1:
            axes.append(DATA_AXIS)
        if self.zero.stage <= 2 and sizes.get(FSDP_AXIS, 1) > 1:
            axes.append(FSDP_AXIS)
        if not axes:
            logger.warning(f"{feature}: no multi-device reduction axis "
                           "on this mesh; ignoring")
        return tuple(axes)

    @staticmethod
    def _restrict_spec(spec: P, manual: Tuple[str, ...]) -> P:
        """PartitionSpec with only the ``manual`` axes kept (the rest of
        the sharding stays with the auto axes of the partial shard_map)."""
        out = []
        for e in spec:
            if e is None:
                out.append(None)
                continue
            ax = (e,) if isinstance(e, str) else tuple(e)
            kept = tuple(a for a in ax if a in manual)
            out.append(kept[0] if len(kept) == 1 else (kept or None))
        while out and out[-1] is None:
            out.pop()
        return P(*out)

    def _build_qgz_grads(self, gas: int):
        """Per-microbatch gradient function with explicit quantized
        reduction (reference: qgZ — all_to_all_quant_reduce,
        runtime/comm/coalesced_collectives.py + quant_reduce.cu;
        docs/_tutorials/zeropp.md:12-17 4x comm-volume claim).

        Per grad leaf: axes appearing in its grad spec get an int8
        reduce-scatter onto the owner shard (dequant-reduce on arrival);
        axes the leaf replicates over get an int8 reduce-scatter +
        all-gather."""
        from ..ops.quant import (quantized_all_reduce,
                                 quantized_psum_scatter_dim)

        manual = self._qgz_axes

        def reduce_leaf(g, spec, axes, batch_tokens):
            ents = list(spec) + [None] * (g.ndim - len(list(spec)))
            seen = set()
            for d, e in enumerate(ents):
                if e is None:
                    continue
                ax = (e,) if isinstance(e, str) else tuple(e)
                # major -> minor: scatter in entry order lands each
                # (outer, inner) coordinate on its owner shard
                for a in ax:
                    if a in manual:
                        g = quantized_psum_scatter_dim(g, a, dim=d)
                        seen.add(a)
            for a in manual:
                if a not in seen:
                    g = quantized_all_reduce(g, a)
            return g

        return self._build_manual_grads(gas, manual, reduce_leaf)

    def _build_comm_grads(self, gas: int):
        """Per-microbatch gradients with tile-decomposed (T3, arxiv
        2401.16677) and optionally quantized (EQuARX, arxiv 2506.17615)
        explicit reduction over the DP axes — config ``comm:
        {overlap, tiles, quantized_allreduce}``.

        Per grad leaf: axes appearing in its grad spec get a tiled
        reduce-scatter onto the owner shard, axes the leaf replicates
        over get a tiled all-reduce.  Each tile's collective carries no
        dependency on the next tile (or the next microbatch's backward
        GEMMs), so XLA may co-schedule them; the default exact rung is
        bitwise-identical to the plain reduction (parity-tested), the
        quantized rung rides the qgZ int8/int4 wire."""
        from ..comm import overlap as ov

        manual = self._comm_axes
        ccfg = self.config.comm
        tiles = ccfg.tiles if ccfg.overlap else 1
        qbits = {None: None, "int8": 8, "int4": 4}[
            ccfg.quantized_allreduce]
        sizes = self.topology.axis_sizes

        def plan(spec, ndim):
            """(scatter ops [(axis, dim)...] in entry order, leftover
            all-reduce axes) for one leaf — the same major->minor walk
            the qgZ reduce_leaf does."""
            ents = list(spec) + [None] * (ndim - len(list(spec)))
            scat, seen = [], set()
            for d, e in enumerate(ents):
                if e is None:
                    continue
                ax = (e,) if isinstance(e, str) else tuple(e)
                for a in ax:
                    if a in manual:
                        scat.append((a, d))
                        seen.add(a)
            return scat, tuple(a for a in manual if a not in seen)

        def reduce_leaf(g, spec, axes, batch_tokens):
            scat, rest = plan(spec, g.ndim)
            for a, d in scat:
                g = ov.overlapped_reduce_scatter(
                    g, a, scatter_dim=d, tiles=tiles, quant_bits=qbits)
            for a in rest:
                g = ov.overlapped_all_reduce(g, a, tiles=tiles,
                                             quant_bits=qbits)
            return g

        # static wire accounting (host arithmetic mirroring reduce_leaf;
        # bumped once per train_batch in _finish_step): the shapes and
        # specs fully determine what one microbatch's grad sync moves
        isz = jnp.dtype(self.compute_dtype).itemsize
        wire = {"ops_exact": 0, "ops_quant": 0, "tiles": 0,
                "bytes_exact": 0.0, "bytes_quant": 0.0}
        s_flat = jax.tree.leaves(self.grad_specs,
                                 is_leaf=lambda x: isinstance(x, P))
        shp_flat = jax.tree.leaves(self.param_shapes,
                                   is_leaf=lambda x: isinstance(x, tuple))
        for spec, shp in zip(s_flat, shp_flat):
            scat, rest = plan(spec, len(shp))
            shape = list(shp)
            for a, d in scat:
                n = sizes[a]
                elems = int(np.prod(shape)) if shape else 1
                kind = "quant" if qbits else "exact"
                wire[f"bytes_{kind}"] += ov.wire_bytes(
                    "reduce_scatter", elems, isz, n, qbits)
                wire[f"ops_{kind}"] += 1
                td = ov._rs_tile_dim(tuple(shape), d, tiles)
                wire["tiles"] += (ov._resolve_tiles(shape[td], tiles)
                                  if td is not None else 1)
                shape[d] //= n
            for a in rest:
                n = sizes[a]
                elems = int(np.prod(shape)) if shape else 1
                kind = "quant" if (qbits and shape) else "exact"
                wire[f"bytes_{kind}"] += ov.wire_bytes(
                    "all_reduce", elems, isz, n,
                    qbits if shape else None)
                wire[f"ops_{kind}"] += 1
                wire["tiles"] += (ov._resolve_tiles(shape[0], tiles)
                                  if shape else 1)
        self._comm_wire = wire

        return self._build_manual_grads(gas, manual, reduce_leaf)

    def _build_sparse_grads(self, gas: int):
        """Per-microbatch gradients with SPARSE reduction of embedding
        grads (reference: runtime/sparse_tensor.py + engine.py:2518
        sparse_allreduce_bucket): vocab-leading leaves travel as
        (row ids, rows) over the DP axes — capacity one row per shard
        token, so the reduction is lossless for pure-lookup embeddings.
        NOTE: tied embeddings receive a DENSE unembed gradient; enable
        only for untied models (capacity would truncate by row mass)."""
        from .sparse_grads import is_sparse_leaf, sparse_psum

        manual = self._sparse_axes
        sizes = self.topology.axis_sizes

        def reduce_leaf(g, spec, axes, batch_tokens):
            ents = list(spec) + [None] * (g.ndim - len(list(spec)))
            seen = set()
            for d, e in enumerate(ents):
                if e is None:
                    continue
                ax = (e,) if isinstance(e, str) else tuple(e)
                for a in ax:
                    if a in manual:
                        g = jax.lax.psum_scatter(
                            g, a, scatter_dimension=d, tiled=True)
                        seen.add(a)
            rest = tuple(a for a in manual if a not in seen)
            if rest:
                if is_sparse_leaf(axes):
                    # a preceding psum_scatter (stage-2 fsdp grad layout)
                    # merged rows from every scattered peer into the
                    # local vocab slice — the lossless capacity is one
                    # row per token across ALL merged shards
                    merged = int(np.prod([sizes[a] for a in seen])) \
                        if seen else 1
                    g = sparse_psum(
                        g, rest,
                        capacity=min(g.shape[0], batch_tokens * merged))
                else:
                    g = jax.lax.psum(g, rest)
            return g

        return self._build_manual_grads(gas, manual, reduce_leaf)

    def _build_local_grads(self, gas: int):
        """UNREDUCED per-shard gradients, stacked on a leading reduce-axes
        dim — the front half of the 1-bit compressed-communication step
        (the actual packed reduce happens once per step on the
        accumulated gradient, see ``_onebit_reduce``)."""
        manual = self._onebit_axes

        def reduce_leaf(g, spec, axes, batch_tokens):
            return g[None]                       # stack; no collective

        return self._build_manual_grads(gas, manual, reduce_leaf,
                                        stacked=True)

    def _onebit_reduce(self, grads_stacked, err, m_prev, b1, denom):
        """The reference 1-bit step at the wire: each shard forms its
        LOCAL momentum ``b1*m + (1-b1)*g_local``, sends sign bits + one
        scale (error feedback local), and the mean of the per-shard
        reconstructions is the new global momentum
        (reference: OnebitAdam.step adam.py:198 + compressed_allreduce).

        Returns (pseudo_grads, new_err): feeding
        ``(m_hat - b1*m_prev)/(1-b1)`` to the uncompressed-momentum
        optimizer makes its ``m`` land exactly on ``m_hat``."""
        from ..ops.quant import onebit_all_reduce

        manual = self._onebit_axes
        mesh = self.topology.mesh
        spec_in = jax.tree.map(lambda _: P(manual), grads_stacked)
        rep = jax.tree.map(lambda _: P(), grads_stacked)

        def local(gs, es, ms):
            def one(g, e, m):
                m_loc = b1 * m + (1 - b1) * (g[0].astype(jnp.float32)
                                             / denom)
                return onebit_all_reduce(m_loc, manual, e[0])
            outs = jax.tree.map(one, gs, es, ms)
            m_hat = jax.tree.map(lambda o: o[0], outs,
                                 is_leaf=lambda x: isinstance(x, tuple))
            e_new = jax.tree.map(lambda o: o[1][None], outs,
                                 is_leaf=lambda x: isinstance(x, tuple))
            return m_hat, e_new

        m_hat, new_err = shard_map(
            local, mesh=mesh,
            in_specs=(spec_in, spec_in, rep),
            out_specs=(rep, spec_in),
            axis_names=set(manual),
            check_vma=False)(grads_stacked, err, m_prev)
        pseudo = jax.tree.map(lambda mh, m: (mh - b1 * m) / (1 - b1),
                              m_hat, m_prev)
        return pseudo, new_err

    def _build_manual_grads(self, gas: int, manual: Tuple[str, ...],
                            reduce_leaf, stacked: bool = False):
        """Shared scaffolding for explicitly-reduced gradient paths (qgZ,
        sparse, 1-bit): shard_map *manual* over the reduce axes and auto
        elsewhere (TP collectives stay compiler-placed)."""
        mesh = self.topology.mesh
        sizes = self.topology.axis_sizes
        nred = int(np.prod([sizes[a] for a in manual]))

        grad_specs = self.grad_specs
        p_in = jax.tree.map(lambda s: self._restrict_spec(s, manual),
                            self.param_specs,
                            is_leaf=lambda x: isinstance(x, P))
        if stacked:
            # leading dim = the reduce-axes product; no manual axes on
            # the unreduced leaf dims (every shard keeps its full local
            # gradient)
            g_out = jax.tree.map(lambda s: P(manual), grad_specs,
                                 is_leaf=lambda x: isinstance(x, P))
        else:
            g_out = jax.tree.map(
                lambda s: self._restrict_spec(s, manual),
                grad_specs, is_leaf=lambda x: isinstance(x, P))
        batch_spec = P(self._restrict_spec(
            P((DATA_AXIS, FSDP_AXIS)), manual)[0])

        def local(cparams, batch, rng, scale):
            idx = jnp.int32(0)
            for a in manual:
                idx = idx * sizes[a] + jax.lax.axis_index(a)
            rng = jax.random.fold_in(rng, idx)

            def scaled_loss(p):
                loss, aux = self._micro_loss(p, batch, rng)
                return loss * scale / gas, (loss, aux)

            (_, (loss, aux)), grads = jax.value_and_grad(
                scaled_loss, has_aux=True)(cparams)
            batch_tokens = int(jax.tree.leaves(batch)[0].size)
            g_flat, treedef = jax.tree.flatten(grads)
            s_flat = jax.tree.leaves(grad_specs,
                                     is_leaf=lambda x: isinstance(x, P))
            from ..parallel.zero import _is_axes
            a_flat = jax.tree.leaves(self.param_axes, is_leaf=_is_axes)
            # the three trees were flattened independently: a leaf-count
            # drift (e.g. bare None leaves in user param_axes, which
            # jax.tree.leaves drops) would silently mis-pair specs with
            # gradients and apply the wrong reduction
            if not (len(g_flat) == len(s_flat) == len(a_flat)):
                raise ValueError(
                    f"manual-reduction tree mismatch: {len(g_flat)} grads "
                    f"vs {len(s_flat)} specs vs {len(a_flat)} param_axes "
                    "leaves (param_axes must annotate every parameter "
                    "leaf)")
            grads = jax.tree.unflatten(treedef, [
                reduce_leaf(g, s, a, batch_tokens)
                for g, s, a in zip(g_flat, s_flat, a_flat)])
            if not stacked:
                # local losses are means over the local batch shard; the
                # global mean divides the reduced sums by the rank count
                grads = jax.tree.map(
                    lambda g: (g / nred).astype(g.dtype), grads)
            loss = jax.lax.psum(loss, manual) / nred
            aux = jax.tree.map(lambda a: jax.lax.psum(a, manual) / nred, aux)
            return loss, aux, grads

        def manual_grads(cparams, batch, rng, scale):
            mb_specs = jax.tree.map(lambda _: batch_spec, batch)
            return shard_map(
                local, mesh=mesh,
                in_specs=(p_in, mb_specs, P(), P()),
                out_specs=(P(), P(), g_out),
                axis_names=set(manual),     # auto everywhere else: TP/fsdp
                check_vma=False,            # shardings stay compiler-placed
            )(cparams, batch, rng, scale)

        return manual_grads

    def _offload_update(self, grads, opt_state, master, step, finite):
        """ZeRO-Offload optimizer step: fp32 master + moments live in host
        DRAM and the update executes as XLA host compute — the TPU analog
        of the reference's DeepSpeedCPUAdam path (stage_1_and_2.py
        cpu_offload + csrc/adam/cpu_adam_impl.cpp), with the
        compiler-scheduled grad HBM->host stream standing in for the
        hand-rolled async grad copy (async_accumulate_grad_in_cpu_via_gpu,
        stage_1_and_2.py:1190).

        Runs inside shard_map: under manual sharding every op carries a
        sharding, which the SPMD partitioner requires of memory-space
        transfer annotations (a *replicated* transfer is inexpressible —
        the reason replicated leaves stay in HBM, see _build_shardings)."""
        from jax.experimental.compute_on import compute_on

        opt_specs = jax.tree.map(lambda sh: sh.spec, self.opt_shardings)

        def host_flags(shardings):
            return jax.tree.map(
                lambda sh: getattr(sh, "memory_kind", None) == "pinned_host",
                shardings)

        m_host, o_host = (host_flags(self.master_shardings),
                          host_flags(self.opt_shardings))

        def put(tree, flags, space):
            # host-flagged leaves never move (host is both where they
            # arrive and where they belong); the rest transfer to `space`
            # — Host on entry for the update, Device on exit to restore.
            return jax.tree.map(
                lambda x, h: x if h else jax.device_put(x, space),
                tree, flags)

        def local(g, o, m, step, finite):
            g = jax.tree.map(
                lambda x: jax.device_put(x, jax.memory.Space.Host), g)
            o = put(o, o_host, jax.memory.Space.Host)
            m = put(m, m_host, jax.memory.Space.Host)
            step_h = jax.device_put(step, jax.memory.Space.Host)
            finite_h = jax.device_put(finite, jax.memory.Space.Host)
            with compute_on("device_host"), jax.named_scope("optimizer"):
                updates, new_o = self.optimizer.update(g, o, m, step_h)
                new_m = jax.tree.map(lambda p, u: p + u, m, updates)

                def sel(new, old):
                    return jax.tree.map(
                        lambda a, b: jnp.where(finite_h, a, b), new, old)
                new_m, new_o = sel(new_m, m), sel(new_o, o)
            # leaves that live in HBM go back before leaving the region
            new_m = put(new_m, m_host, jax.memory.Space.Device)
            new_o = put(new_o, o_host, jax.memory.Space.Device)
            return new_m, new_o

        return shard_map(
            local, mesh=self.topology.mesh,
            in_specs=(self.master_specs, opt_specs, self.master_specs,
                      P(), P()),
            out_specs=(self.master_specs, opt_specs),
        )(grads, opt_state, master, step, finite)

    def _micro_loss(self, cparams, batch, rng):
        out = self.loss_fn(cparams, batch, rng)
        if isinstance(out, tuple):
            loss, aux = out
        else:
            loss, aux = out, {}
        return loss, aux

    def _build_grad_pipeline(self, gas: int):
        """(cparams, batch, rng, scale) -> (loss, aux, fp32 grads in the
        ZeRO grad layout) — the shared front half of the device-resident
        and NVMe-offloaded train steps (gas scan = the IPG/bucketing
        analog, compiler-scheduled)."""
        qgz_grads = self._build_qgz_grads(gas) if self._qgz_axes else None
        if qgz_grads is None and self._sparse_axes:
            qgz_grads = self._build_sparse_grads(gas)
        if qgz_grads is None and self._comm_axes:
            qgz_grads = self._build_comm_grads(gas)
        stacked = bool(self._onebit_axes)
        if qgz_grads is None and stacked:
            qgz_grads = self._build_local_grads(gas)

        def grads_of_microbatch(cparams, batch, rng, scale):
            if qgz_grads is not None:
                return qgz_grads(cparams, batch, rng, scale)

            def scaled_loss(p):
                loss, aux = self._micro_loss(p, batch, rng)
                return loss * scale / gas, (loss, aux)
            (_, (loss, aux)), grads = jax.value_and_grad(
                scaled_loss, has_aux=True)(cparams)
            return loss, aux, grads

        if stacked:
            acc_specs = jax.tree.map(
                lambda _: P(self._onebit_axes), self.grad_specs,
                is_leaf=lambda x: isinstance(x, P))
        else:
            acc_specs = self.grad_specs

        def shard_grads(g):
            """fp32 gradients in the ZeRO grad layout."""
            with jax.named_scope("grad_accumulate"):
                g = jax.tree.map(lambda t: t.astype(jnp.float32), g)
                return jax.tree.map(
                    lambda t, spec: jax.lax.with_sharding_constraint(
                        t, NamedSharding(self.topology.mesh, spec)),
                    g, acc_specs)

        def pipeline(cparams, batch, rng, scale):
            if gas > 1:
                # batch leaves have leading [gas, ...]; scan accumulates
                # fp32 grads in the ZeRO grad layout (reduce-scattered for
                # stage>=2)
                def body(acc, xs):
                    mb, r = xs
                    loss, aux, g = grads_of_microbatch(cparams, mb, r, scale)
                    g = shard_grads(g)
                    acc_g, acc_loss = acc
                    with jax.named_scope("grad_accumulate"):
                        acc_g = jax.tree.map(jnp.add, acc_g, g)
                    return (acc_g, acc_loss + loss), aux

                W = int(np.prod([self.topology.axis_sizes[a]
                                 for a in self._onebit_axes])) \
                    if stacked else 1
                zero_g = jax.tree.map(
                    lambda p, spec: jax.lax.with_sharding_constraint(
                        jnp.zeros(((W,) if stacked else ())
                                  + tuple(np.shape(p)), jnp.float32),
                        NamedSharding(self.topology.mesh, spec)),
                    cparams, acc_specs)
                rngs = jax.random.split(rng, gas)
                (grads, loss_sum), aux = jax.lax.scan(
                    body, (zero_g, jnp.float32(0.0)), (batch, rngs))
                loss = loss_sum / gas
                aux = jax.tree.map(lambda a: a[-1], aux)
            else:
                loss, aux, grads = grads_of_microbatch(cparams, batch, rng,
                                                       scale)
                grads = shard_grads(grads)
            return loss, aux, grads

        return pipeline

    def _build_grad_epilogue(self):
        """Shared back half of both step builders: unscale (+ predivide,
        reference: prescale_gradients), overflow check, clip."""
        use_scaling = self.precision == "fp16"
        clip = self.config.gradient_clipping
        prescale = self.config.prescale_gradients
        predivide = self.config.gradient_predivide_factor

        def epilogue(grads, scale):
            with jax.named_scope("grad_epilogue"):
                denom = scale * (predivide if prescale else 1.0)
                grads = jax.tree.map(lambda g: g / denom, grads)
                finite = all_finite(grads) if use_scaling \
                    else jnp.asarray(True)
                grads, gnorm = clip_by_global_norm(grads, clip)
            return grads, finite, gnorm
        return epilogue

    def _build_train_step(self, onebit_compress: bool = True):
        gas = self.gas
        scaler = self.scaler
        use_scaling = self.precision == "fp16"
        offloaded = self.offload_active
        pipeline = self._build_grad_pipeline(gas)
        epilogue = self._build_grad_epilogue()

        onebit = bool(self._onebit_axes)
        opt_update = self.optimizer.update
        if onebit:
            # phase-aligned optimizer: the engine switches host-side on
            # global_steps, but the optimizer's own frozen flag counts
            # only APPLIED steps (state.step) — under fp16 overflow skips
            # the two drift apart.  Pin the optimizer to this compiled
            # step's phase instead of its step counter.
            opt_cfg = self.config.optimizer
            key = ("var_freeze_step" if "zeroone" in opt_cfg.type.lower()
                   else "freeze_step")
            phase_params = {**opt_cfg.params, "compress": False,
                            key: -1 if onebit_compress else (1 << 30)}
            from .optimizers import build_optimizer
            opt_update = build_optimizer(
                opt_cfg.type, self.lr_schedule, phase_params).update

        def train_step(state: TrainState, batch, rng):
            scale = state.loss_scale.scale if use_scaling else jnp.float32(1.0)
            cparams = self._compute_params(state.master)
            loss, aux, grads = pipeline(cparams, batch, rng, scale)
            opt_in = state.opt_state
            if onebit:
                # packed 1-bit momentum reduce with error feedback,
                # threaded through the opt state.  During warmup
                # (reference: exact allreduce until freeze_step) the
                # mean is exact and EF stays zero.
                err = opt_in.comm_err
                opt_in = opt_in.base
                if onebit_compress:
                    # loss-scale unscaling happens inside the reduce; the
                    # epilogue (called with scale=1) still applies the
                    # predivide factor exactly once
                    grads, new_err = self._onebit_reduce(
                        grads, err, opt_in.m, self._onebit_b1, scale)
                    grads, finite, gnorm = epilogue(grads,
                                                    jnp.float32(1.0))
                else:
                    grads = jax.tree.map(lambda g: g.mean(axis=0), grads)
                    new_err = err
                    grads, finite, gnorm = epilogue(grads, scale)
            else:
                grads, finite, gnorm = epilogue(grads, scale)

            # overflow → skip update (jnp.where keeps shapes static)
            def sel(new, old):
                return jax.tree.map(
                    lambda a, b: jnp.where(finite, a, b), new, old)

            # optimizer update on the (fsdp-sharded) master partition —
            # the local-adam-on-owned-shard of stage_1_and_2.py:1823.
            step_next = state.step + 1

            def update_master(grads, opt_state, master):
                with jax.named_scope("optimizer"):
                    updates, new_opt = opt_update(
                        grads, opt_state, master, step_next)
                    new_master = jax.tree.map(lambda p, u: p + u, master,
                                              updates)
                    return sel(new_master, master), sel(new_opt, opt_state)

            if offloaded:
                new_master, new_opt = self._offload_update(
                    grads, opt_in, state.master, step_next, finite)
            else:
                new_master, new_opt = update_master(
                    grads, opt_in, state.master)
            if onebit:
                new_opt = OnebitCommState(
                    base=new_opt, comm_err=sel(new_err, err))
            new_step = jnp.where(finite, step_next, state.step)
            new_scale_state = scaler.update(state.loss_scale, ~finite)
            new_skipped = state.skipped + jnp.where(finite, 0, 1)
            if offloaded:
                # mixed memory kinds make jit annotate every output's
                # placement; scalar outputs need an explicit (replicated)
                # sharding attached or the SPMD partitioner rejects the
                # annotation op (hlo->has_sharding() RET_CHECK).
                rep = lambda x: jax.lax.with_sharding_constraint(x, self.repl)
                new_step = rep(new_step)
                new_skipped = rep(new_skipped)
                new_scale_state = jax.tree.map(rep, new_scale_state)

            new_state = TrainState(
                step=new_step, master=new_master, opt_state=new_opt,
                loss_scale=new_scale_state,
                skipped=new_skipped)
            lr = self.lr_schedule(new_step.astype(jnp.float32))
            metrics = {
                "loss": loss.astype(jnp.float32),
                "grad_norm": gnorm,
                "lr": lr,
                "loss_scale": state.loss_scale.scale,
                "overflow": (~finite).astype(jnp.int32),
                **{f"aux/{k}": v for k, v in aux.items()},
            }
            return new_state, metrics

        state_sh = self.state_shardings
        return jax.jit(
            train_step,
            in_shardings=(state_sh, None, None),
            out_shardings=(state_sh, None),
            donate_argnums=() if offloaded else (0,))

    # ------------------------------------------------------------------
    # ZeRO-Infinity step (NVMe-backed optimizer state)
    # ------------------------------------------------------------------
    def _build_nvme_step(self):
        """Device half of the ZeRO-Infinity step: grads + overflow check +
        clip, returning the gradients for the host-side NVMe update
        (reference: stage3.py:2049 per-sub_group gather-step-swap loop;
        here the group loop lives in runtime/zero_infinity.py)."""
        gas = self.gas
        scaler = self.scaler
        use_scaling = self.precision == "fp16"
        pipeline = self._build_grad_pipeline(gas)
        epilogue = self._build_grad_epilogue()

        def nvme_step(state: TrainState, batch, rng):
            scale = state.loss_scale.scale if use_scaling else jnp.float32(1.0)
            cparams = self._compute_params(state.master)
            loss, aux, grads = pipeline(cparams, batch, rng, scale)
            grads, finite, gnorm = epilogue(grads, scale)
            new_scale_state = scaler.update(state.loss_scale, ~finite)
            metrics = {
                "loss": loss.astype(jnp.float32),
                "grad_norm": gnorm,
                "loss_scale": state.loss_scale.scale,
                "overflow": (~finite).astype(jnp.int32),
                **{f"aux/{k}": v for k, v in aux.items()},
            }
            return grads, finite, new_scale_state, metrics

        state_sh = self.state_shardings
        return jax.jit(nvme_step, in_shardings=(state_sh, None, None))

    def _train_batch_nvme(self, batch, rng) -> Dict[str, Any]:
        if self._stream is not None:
            # per-layer param streaming: the host loop IS the step
            metrics = self._stream.train_batch(batch, rng)
            return self._finish_step(batch, rng, metrics)
        if self._nvme_step_fn is None:
            self._nvme_step_fn = self._build_nvme_step()
        batch = self.shard_batch(batch)
        try:
            grads, finite, new_scale_state, metrics = \
                self._nvme_step_fn(self.state, batch, rng)
            finite_b = bool(np.asarray(finite))
        except jax.errors.JaxRuntimeError as e:
            if not self.offload_active or self._offload_validated:
                raise
            self._disable_offload(e)
            return self._train_batch_nvme(batch, rng)
        self._offload_validated = True

        step_next = int(np.asarray(self.state.step)) + 1
        lr = float(np.asarray(self.lr_schedule(np.float32(step_next))))
        if finite_b:
            flat_grads = jax.tree_util.tree_leaves(grads)
            new_master = self._nvme.step(flat_grads, lr, step_next)
            if self._nvme._multi:
                master = self._assemble_nvme_master(new_master)
            else:
                flat_sh = jax.tree_util.tree_leaves(
                    self.master_shardings,
                    is_leaf=lambda x: isinstance(x, jax.sharding.Sharding))
                dev_leaves = [
                    jax.device_put(m.astype(self.compute_dtype), sh)
                    for m, sh in zip(new_master, flat_sh)]
                master = jax.tree_util.tree_unflatten(
                    jax.tree_util.tree_structure(self.state.master),
                    dev_leaves)
            new_step = jnp.asarray(step_next, jnp.int32)
            skipped = self.state.skipped
        else:
            master = self.state.master
            new_step = self.state.step
            skipped = self.state.skipped + 1
        self.state = TrainState(
            step=new_step, master=master, opt_state=(),
            loss_scale=new_scale_state, skipped=skipped)
        metrics = dict(metrics)
        metrics["lr"] = jnp.float32(lr)
        return self._finish_step(batch, rng, metrics)

    def _assemble_nvme_master(self, frag_leaves):
        """Multi-host: build the device working copy from this process's
        updated master fragments — per-device buffers in the gradient
        layout, then one jitted reshard (XLA collectives over ICI) into
        the compute layout."""
        dt = self.compute_dtype
        flat_sh = jax.tree_util.tree_leaves(
            self._nvme_grad_sh,
            is_leaf=lambda x: isinstance(x, jax.sharding.Sharding))
        arrs = []
        for i, (frags, sh) in enumerate(zip(frag_leaves, flat_sh)):
            shape = self._nvme._leaf_meta[i][0]
            imap = sh.devices_indices_map(shape)
            fragmap = dict(zip(self._nvme._frags[i], frags))
            bufs = [jax.device_put(fragmap[tuple(imap[d])].astype(dt), d)
                    for d in sh.addressable_devices]
            arrs.append(jax.make_array_from_single_device_arrays(
                shape, sh, bufs))
        tree = jax.tree_util.tree_unflatten(
            jax.tree_util.tree_structure(self.state.master), arrs)
        if self._nvme_reshard_fn is None:
            self._nvme_reshard_fn = jax.jit(
                lambda t: t, out_shardings=self.master_shardings)
        return self._nvme_reshard_fn(tree)

    # ------------------------------------------------------------------
    # public API (reference: engine.train_batch / forward+backward+step)
    # ------------------------------------------------------------------
    def train_batch(self, batch, rng: Optional[jax.Array] = None) -> Dict[str, Any]:
        """Run one full optimizer step (forward+backward+step fused).

        ``batch``: pytree of arrays with leading dim ``gas * micro`` (host-
        local view is fine under multi-host; see ``shard_batch``); with
        gas>1, leaves are reshaped to [gas, micro, ...] for the scan.
        """
        if self._cap is not None and self._cap.armed:
            # an armed deep-capture window opens at the step boundary
            # (the one profiler seam — tpulint: profiler-capture)
            self._cap.begin(step=self.global_steps)
        # the step's phases are live spans (telemetry/tracer.py): each
        # cut ends one phase, begins the next, and returns the one
        # clock reading _phase_ms takes there anyway
        tr = self.tracer
        sid = self.global_steps + 1
        t0 = tr.phase("ds.train.pre_step", track="pre_step", step=sid)
        # one step's wall is the interval between consecutive entries
        # (no new clock read): the step itself is dispatched async
        last, self._t_entry = self._t_entry, t0
        interval_ms = None
        if last is not None:
            interval_ms = (t0 - last) * 1e3
            self._h_step_interval.observe(interval_ms)
            self.tput.record(t0 - last)
        if rng is None:
            rng = jax.random.PRNGKey(self.config.seed + self.global_steps)
        if self.curriculum or self.pld or self._ltd_cfg or self.moq:
            batch = self._data_efficiency_pre_step(batch, rng)
        if self._nvme is not None:
            # the NVMe-streamed step runs as many per-layer programs; its
            # phases are not the four this instrumentation names
            tr.phase_end()
            return self._train_batch_nvme(batch, rng)
        t1 = tr.phase("ds.train.stage", track="stage", step=sid)
        step_fn = self._pick_train_step()
        batch = self.shard_batch(batch)
        t2 = tr.phase("ds.train.dispatch", track="dispatch", step=sid)
        try:
            self.state, metrics = step_fn(self.state, batch, rng)
            if self.offload_active and not self._offload_validated:
                # dispatch is async: an unsupported host-compute path
                # surfaces at the first blocking fetch, which would land
                # OUTSIDE this try in the caller — force execution now so
                # the fallback can actually fire
                float(np.asarray(metrics["loss"]))
        except jax.errors.JaxRuntimeError as e:
            # only the *first* execution may fall back — a later failure is
            # a genuine runtime error, not a backend capability gap
            if not self.offload_active or self._offload_validated:
                tr.phase_end(failed=type(e).__name__)
                raise
            self._disable_offload(e)
            self._train_step_fn = self._warmup_step_fn = None
            step_fn = self._pick_train_step()
            self.state, metrics = step_fn(self.state, batch, rng)
        self._offload_validated = True
        t3 = tr.phase_end()
        if self.devtel is not None:
            # cost probe once per program (post-call: the donated state
            # was rebound to the step's output, same avals), then
            # attribute this dispatch's flops/bytes from the table
            pkey = ("train_step_warmup"
                    if step_fn is self._warmup_step_fn else "train_step")
            if pkey not in self.devtel.program_costs:
                self.devtel.probe_program(pkey, step_fn,
                                          (self.state, batch, rng))
            self.devtel.on_dispatch(pkey)
        self._phase_ms["pre_step"].inc((t1 - t0) * 1e3)
        self._phase_ms["stage"].inc((t2 - t1) * 1e3)
        self._phase_ms["dispatch"].inc((t3 - t2) * 1e3)
        self._h_step_host.observe((t3 - t0) * 1e3)
        if self._anom is not None:
            # detectors fed from the timestamps above — no added reads
            self._feed_step_signals(interval_ms, (t3 - t0) * 1e3)
        return self._finish_step(batch, rng, metrics)

    def _pick_train_step(self):
        """Standard jitted step, or — for 1-bit optimizers — the exact
        warmup step until ``freeze_step`` optimizer updates have run
        (reference: uncompressed allreduce during warmup, adam.py)."""
        if self._onebit_axes and self.global_steps < self._onebit_freeze:
            if self._warmup_step_fn is None:
                self._warmup_step_fn = self._build_train_step(
                    onebit_compress=False)
                self._note_compile("train_step_warmup")
            return self._warmup_step_fn
        if self._train_step_fn is None:
            self._train_step_fn = self._build_train_step()
            self._note_compile("train_step")
        return self._train_step_fn

    def _finish_step(self, batch, rng, metrics) -> Dict[str, Any]:
        self.global_steps += 1
        self.global_samples += self.train_batch_size
        self._c_steps.inc()
        if self._comm_wire is not None:
            # one bump per train_batch: the gas per-microbatch explicit
            # reductions of the comm grad path (static accounting —
            # host arithmetic mirroring _build_comm_grads' reduce plan)
            w = self._comm_wire
            gas = self.gas
            if w["ops_exact"]:
                self._c_comm_ops.inc(w["ops_exact"] * gas, kind="exact")
                self._c_comm_bytes.inc(w["bytes_exact"] * gas,
                                       kind="exact")
            if w["ops_quant"]:
                self._c_comm_ops.inc(w["ops_quant"] * gas, kind="quant")
                self._c_comm_bytes.inc(w["bytes_quant"] * gas,
                                       kind="quant")
            self._c_comm_tiles.inc(w["tiles"] * gas)
        if self._cap is not None and self._cap.active:
            self._cap.end_step(step=self.global_steps)
        # metrics stay on device — a host fetch every step would stall the
        # async dispatch pipeline; fetch once, and only when someone
        # actually looks
        self._last_metrics = metrics
        self._last_metrics_host = None
        fp_cfg = self.config.flops_profiler
        if fp_cfg.enabled and self.global_steps == fp_cfg.profile_step:
            self._write_flops_profile(batch, rng)
        need_host = (self.global_steps % self.config.steps_per_print == 0
                     or self.monitor is not None)
        if need_host:
            if self.devtel is not None and self.global_steps \
                    % self.config.steps_per_print == 0:
                # the steps_per_print boundary is the training loop's
                # phase boundary: refresh the memory gauges here (one
                # host call per device — NOT every step; a configured
                # monitor makes need_host true per step, so the poll
                # keeps its own cadence guard like publish below)
                self.devtel.poll_memory()
            t_f0 = self.tracer.phase("ds.train.fetch", track="fetch",
                                     step=self.global_steps)
            fetched = jax.device_get(metrics)        # ONE transfer
            t_f1 = self.tracer.phase_end()
            self._phase_ms["fetch"].inc((t_f1 - t_f0) * 1e3)
            self._last_metrics_host = fetched
            if self.global_steps % self.config.steps_per_print == 0:
                log_dist(
                    f"step={self.global_steps} loss={fetched['loss']:.4f} "
                    f"lr={fetched['lr']:.3e} "
                    f"gnorm={fetched['grad_norm']:.3f} "
                    f"tput={self.tput.avg_samples_per_sec():.1f} samples/s")
            if self.monitor is not None:
                self.monitor.write_scalars(self.global_steps, {
                    "Train/loss": float(fetched["loss"]),
                    "Train/lr": float(fetched["lr"]),
                    "Train/grad_norm": float(fetched["grad_norm"]),
                    "Train/loss_scale": float(fetched["loss_scale"]),
                })
                # registry fan-out rides the SAME writer pipeline as the
                # loss scalars (telemetry/metrics.py publish): per-phase
                # host-ms counters + step histogram land in CSV/TB/WandB
                # at the print cadence (every step would 5x the writer
                # volume for numbers that only move slowly)
                if self.global_steps % self.config.steps_per_print == 0:
                    self.metrics.publish(self.monitor, self.global_steps)
            metrics = fetched
        return metrics

    def eval_batch(self, batch, rng: Optional[jax.Array] = None):
        if self._stream is not None:
            return np.asarray(self._stream.eval_batch(
                batch, rng if rng is not None else jax.random.PRNGKey(0)))
        if self._eval_step_fn is None:
            fn = self.eval_fn or self.loss_fn
            # a pipelined 1F1B loss exposes a forward-only schedule for
            # evaluation (its primal otherwise pays full fwd+bwd cost)
            fn = getattr(fn, "eval_fn", fn)
            # PLD/random-LTD losses expose a hook-free eval variant (no
            # theta column in eval batches, no token dropping)
            fn = getattr(fn, "base_eval", None) or fn

            def eval_step(master, batch, rng):
                cparams = self._compute_params(master)
                out = fn(cparams, batch, rng)
                return out[0] if isinstance(out, tuple) else out

            self._eval_step_fn = jax.jit(
                eval_step, in_shardings=(self.master_shardings, None, None))
        if rng is None:
            rng = jax.random.PRNGKey(0)
        batch = self.shard_batch(batch, accumulate=False)
        try:
            out = np.asarray(self._eval_step_fn(self.state.master, batch, rng))
        except jax.errors.JaxRuntimeError as e:
            if not self.offload_active or self._offload_validated:
                raise
            self._disable_offload(e)
            return self.eval_batch(batch, rng)
        self._offload_validated = True
        return out

    def _write_flops_profile(self, batch, rng) -> None:
        """Engine flops-profiler hook (reference: engine.py:288,1850 —
        module-hook profiler; here: compiled-HLO cost analysis + the step
        wall time already measured, no extra execution)."""
        if self._stream is not None:
            logger.warning("flops_profiler: param-streamed steps run as "
                           "many per-layer programs; HLO cost analysis "
                           "of the monolithic step is unavailable")
            return
        from ..profiling import FlopsProfiler, analyze_fn

        stats = analyze_fn(self._train_step_fn or self._nvme_step_fn,
                           self.state, batch, rng)
        stats["params"] = float(param_count(self.state.master))
        # total_elapsed_time only counts intervals after tput.start_step
        counted = self.tput.global_step_count - self.tput.start_step
        if counted > 0 and self.tput.total_elapsed_time:
            stats["latency_s"] = self.tput.total_elapsed_time / counted
            if stats.get("flops"):
                stats["tflops_per_s"] = (
                    stats["flops"] / stats["latency_s"] / 1e12)
        report = FlopsProfiler.report(stats,
                                      batch_size=self.train_batch_size)
        log_dist("\n" + report)
        if self.config.flops_profiler.output_file:
            with open(self.config.flops_profiler.output_file, "w") as f:
                f.write(report + "\n")

    def _disable_offload(self, err: Exception) -> None:
        """Fall back to device-resident optimizer state.

        The pinned_host placement compiles on real TPU but some backends
        (notably multi-device CPU SPMD, used by the virtual test mesh)
        cannot partition memory-space transfer annotations at all; detect
        that at first compile and keep training instead of dying."""
        logger.warning(
            "optimizer offload unsupported on this backend (%s); "
            "falling back to device-resident optimizer state",
            str(err).splitlines()[0][:120])
        self.offload_active = False
        to_dev = lambda sh: NamedSharding(self.topology.mesh, sh.spec)
        self.master_shardings = jax.tree.map(to_dev, self.master_shardings)
        self.opt_shardings = jax.tree.map(to_dev, self.opt_shardings)
        self.state = TrainState(
            step=self.state.step,
            master=jax.device_put(self.state.master, self.master_shardings),
            opt_state=jax.device_put(self.state.opt_state, self.opt_shardings),
            loss_scale=self.state.loss_scale,
            skipped=self.state.skipped)
        # drop every jit compiled against the host-placed shardings
        self._train_step_fn = None
        self._warmup_step_fn = None
        self._eval_step_fn = None
        self._nvme_step_fn = None
        if hasattr(self, "_compute_params_fn"):
            del self._compute_params_fn

    def shard_batch(self, batch, accumulate: bool = True):
        """Device-put host batch with [B] → sharded over data axes; with
        gas>1 reshape leaves to [gas, micro_global, ...].

        Idempotent: an already-staged batch (e.g. from
        ``PrefetchingLoader``, which uploads batch N+1 during step N)
        passes through untouched — but only for the staging mode it was
        built with (train batches are gas-reshaped; eval ones are not)."""
        if isinstance(batch, _StagedBatch):
            if batch.accumulate != (accumulate and self.gas > 1):
                raise ValueError(
                    "batch was staged for "
                    f"{'training' if batch.accumulate else 'eval'} "
                    "(gas reshape mismatch); re-stage the host batch "
                    "instead of reusing the staged one")
            return batch
        gas = self.gas if accumulate else 1
        sp = self.topology.sp_size
        from ..comm.mesh import SEQ_AXIS

        pc = jax.process_count()
        data_shards = (self.topology.mesh.shape[DATA_AXIS]
                       * self.topology.mesh.shape[FSDP_AXIS])

        def put(x):
            x = np.asarray(x)
            b = x.shape[0]
            if b % gas or (b * pc) % (gas * data_shards):
                raise ValueError(
                    f"batch dim {b} (x {pc} processes) not divisible by "
                    f"gas={gas} x data shards {data_shards}; for a "
                    "partial tail batch use eval or drop_last=True")
            # dim after batch is the sequence: shard it over the seq axis
            seq_entry = (SEQ_AXIS,) if (sp > 1 and x.ndim >= 2) else ()
            if gas > 1:
                x = x.reshape((gas, x.shape[0] // gas) + x.shape[1:])
                spec = P(None, (DATA_AXIS, FSDP_AXIS), *seq_entry)
                batch_dim = 1
            else:
                spec = P((DATA_AXIS, FSDP_AXIS), *seq_entry)
                batch_dim = 0
            sharding = NamedSharding(self.topology.mesh, spec)
            if pc > 1:
                # x is this process's host-local slice (DataLoader yields
                # per-process batch shards; every other dim — notably the
                # sequence — is fully present locally).  Assemble the
                # global array with an explicit global_shape scaling ONLY
                # the batch dim: inference would scale every sharded dim
                # by its cross-process extent and silently double a
                # process-spanning SEQ_AXIS.
                gshape = list(x.shape)
                gshape[batch_dim] *= pc
                return jax.make_array_from_process_local_data(
                    sharding, x, tuple(gshape))
            return jax.device_put(x, sharding)

        out = jax.tree.map(put, batch)
        if isinstance(out, dict):
            out = _StagedBatch(out)
            out.accumulate = gas > 1
        return out

    # ------------------------------------------------------------------
    # introspection / params access
    # ------------------------------------------------------------------
    @property
    def compute_params(self):
        """Current params in compute dtype (jitted gather+cast, cached)."""
        if self._stream is not None:
            raise ConfigError(
                "compute_params is unavailable under param streaming "
                "(offload_param.device=nvme): the full compute tree "
                "never materializes — stream layers via "
                "engine._stream or load a checkpoint instead")
        if not hasattr(self, "_compute_params_fn"):
            self._compute_params_fn = jax.jit(
                self._compute_params, in_shardings=(self.master_shardings,))
        return self._compute_params_fn(self.state.master)

    def get_lr(self) -> float:
        # schedule position = optimizer steps actually applied (state.step
        # excludes overflow-skipped steps; global_steps would drift under fp16)
        return float(self.lr_schedule(
            np.asarray(self.state.step).astype(np.float32)))

    def get_global_grad_norm(self) -> Optional[float]:
        if getattr(self, "_last_metrics", None) is None:
            return None
        if self._last_metrics_host is None:
            # one transfer, cached until the next step overwrites it
            self._last_metrics_host = jax.device_get(self._last_metrics)
        return float(self._last_metrics_host["grad_norm"])

    # ------------------------------------------------------------------
    # checkpointing (delegates to deepspeed_tpu.checkpoint)
    # ------------------------------------------------------------------
    def save_checkpoint(self, save_dir: str, tag: Optional[str] = None,
                        client_state: Optional[Dict] = None):
        from ..checkpoint.engine import save_checkpoint as _save
        if self.config.checkpoint.async_save and self._nvme is None \
                and jax.process_count() == 1:
            # Nebula-style background persistence: snapshot shards to
            # host now, write files on a worker thread.  Multi-host runs
            # save synchronously: save_tree's cross-host barriers are
            # device collectives that must not race the main thread's
            # training collectives (divergent issue order deadlocks).
            from ..checkpoint.engine import (AsyncCheckpointSaver,
                                             save_checkpoint_async)
            if not hasattr(self, "_async_saver"):
                self._async_saver = AsyncCheckpointSaver()
            return save_checkpoint_async(
                self, self._async_saver, save_dir, tag=tag,
                client_state=client_state or {})
        if self._nvme is None:
            return _save(self, save_dir, tag=tag,
                         client_state=client_state or {})
        # ZeRO-Infinity: checkpoint the *fp32* NVMe state, not the bf16
        # working copy, so resume (on any config) is lossless — the same
        # fragment format as every other run.  Lazy leaves stream one
        # swap group at a time through host RAM (state may exceed DRAM).
        from .optimizers import AdamState
        source = self._stream if self._stream is not None else self._nvme
        master, m, v = source.state_trees(lazy=True)
        saved = self.state
        self.state = TrainState(
            step=saved.step, master=master,
            opt_state=AdamState(m=m, v=v),
            loss_scale=saved.loss_scale, skipped=saved.skipped)
        try:
            return _save(self, save_dir, tag=tag,
                         client_state=client_state or {})
        finally:
            self.state = saved

    def wait_checkpoint(self) -> None:
        """Join an in-flight async checkpoint save (no-op otherwise);
        re-raises a failed save's error."""
        if hasattr(self, "_async_saver"):
            self._async_saver.wait()

    def load_checkpoint(self, load_dir: str, tag: Optional[str] = None):
        from ..checkpoint.engine import load_checkpoint as _load
        self.wait_checkpoint()        # never read a half-written save
        if self._nvme is None:
            return _load(self, load_dir, tag=tag)
        return self._load_checkpoint_nvme(load_dir, tag)

    def _load_checkpoint_nvme(self, load_dir: str, tag: Optional[str]):
        """Load a fragment checkpoint into the NVMe state store: fp32
        master + moments go to NVMe files, the device gets a fresh bf16
        working copy.  Checkpoints from non-Infinity runs load too (same
        master/AdamState key layout)."""
        import os

        from ..checkpoint.engine import LATEST, load_tree_host
        from .optimizers import AdamState
        if tag is None:
            latest = os.path.join(load_dir, LATEST)
            if not os.path.exists(latest):
                raise FileNotFoundError(f"No {LATEST} file in {load_dir}")
            with open(latest) as f:
                tag = f.read().strip()
        ckpt_dir = os.path.join(load_dir, tag)

        f32 = lambda tree: jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(np.shape(x), np.float32), tree)
        scalar = lambda dt: jax.ShapeDtypeStruct((), dt)
        master_tpl = (self._stream.master_template()
                      if self._stream is not None
                      else f32(self.state.master))
        template = TrainState(
            step=scalar(np.int32),
            master=master_tpl,
            opt_state=AdamState(m=master_tpl, v=master_tpl),
            loss_scale=LossScaleState(scalar(np.float32), scalar(np.int32),
                                      scalar(np.int32)),
            skipped=scalar(np.int32))
        host, meta = load_tree_host(template, ckpt_dir)
        if self._stream is not None:
            self._stream.restore(host.master, host.opt_state.m,
                                 host.opt_state.v)
            master = self._stream.resident
        else:
            self._nvme.restore(host.master, host.opt_state.m,
                               host.opt_state.v)
            flat = jax.tree_util.tree_leaves(host.master)
            flat_sh = jax.tree_util.tree_leaves(
                self.master_shardings,
                is_leaf=lambda x: isinstance(x, jax.sharding.Sharding))
            dev_leaves = [jax.device_put(m.astype(self.compute_dtype), sh)
                          for m, sh in zip(flat, flat_sh)]
            master = jax.tree_util.tree_unflatten(
                jax.tree_util.tree_structure(self.state.master), dev_leaves)
        self.state = TrainState(
            step=jnp.asarray(host.step, jnp.int32),
            master=master, opt_state=(),
            loss_scale=LossScaleState(
                *[jnp.asarray(x) for x in host.loss_scale]),
            skipped=jnp.asarray(host.skipped, jnp.int32))
        self.global_steps = int(meta.get("global_steps", 0))
        self.global_samples = int(meta.get("global_samples", 0))
        log_dist(f"loaded checkpoint {ckpt_dir} into NVMe state "
                 f"(step {self.global_steps})")
        return ckpt_dir, meta.get("client_state", {})


def initialize(loss_fn: Callable = None,
               params: Any = None,
               config: Any = None,
               topology: Optional[MeshTopology] = None,
               param_axes: Any = None,
               sharding_rules: Optional[Dict] = None,
               model: Any = None,
               **kwargs) -> Engine:
    """Build an :class:`Engine` (reference: deepspeed.initialize
    deepspeed/__init__.py:69).

    Either pass ``loss_fn`` + ``params`` directly, or a ``model`` object
    exposing ``.loss_fn``, ``.params`` (and optionally ``.param_axes``,
    ``.sharding_rules``) — the models in ``deepspeed_tpu.models`` do.
    """
    cfg = load_config(config)
    if (cfg.mesh.expert > 1 and model is not None
            and getattr(getattr(model, "config", None), "moe_dispatch",
                        None) == "ragged"):
        # ragged_dot contracts against expert-sharded weights: GSPMD
        # would all-gather every expert's weights per layer
        raise ConfigError(
            "moe_dispatch='ragged' (dropless grouped GEMM) does not "
            "compose with expert parallelism; use the scatter dispatch "
            "on expert meshes")
    de_routing = cfg.data_efficiency.enabled \
        and cfg.data_efficiency.data_routing.enabled \
        and cfg.data_efficiency.data_routing.random_ltd.enabled
    if (cfg.progressive_layer_drop.enabled or de_routing) \
            and loss_fn is None:
        # PLD / random-LTD modify the transformer forward — they need
        # the model path (reference wires them by module surgery,
        # engine.py:346-356; here the loss is rebuilt with the hooks)
        if model is None or not hasattr(model, "config"):
            raise ConfigError(
                "progressive_layer_drop / random_ltd need model= with a "
                "TransformerConfig (the loss must expose the layer stack)")
        if de_routing and model.config.position == "alibi":
            # LTD gathers a token subset; the ALiBi bias uses compressed
            # key indices and would silently distort distances (rope
            # threads original positions; the alibi wrapper cannot)
            raise ConfigError(
                "random_ltd does not compose with position='alibi' "
                "(the distance bias would see gathered, not original, "
                "token positions)")
        if max(cfg.mesh.pipe, cfg.pipeline.stages) > 1 \
                or max(cfg.mesh.seq, cfg.sequence_parallel.size) > 1:
            raise ConfigError(
                "progressive_layer_drop / random_ltd are not composable "
                "with pipeline or sequence parallelism yet")
        from ..models import layers as _L
        from ..models.transformer import lm_loss_fn

        attn = getattr(model, "attention_fn", None) or _L.causal_attention
        loss_fn = lm_loss_fn(model.config, attn,
                             pld=cfg.progressive_layer_drop.enabled)
    if model is not None:
        params = params if params is not None else model.params
        param_axes = param_axes if param_axes is not None else getattr(
            model, "param_axes", None)
        sharding_rules = sharding_rules or getattr(model, "sharding_rules", None)
        # sequence parallelism: swap the model's attention for the
        # Ulysses/ring wrapper over this run's mesh
        seq_size = max(cfg.mesh.seq, cfg.sequence_parallel.size)
        pipe_size = max(cfg.mesh.pipe, cfg.pipeline.stages)
        is_alibi = getattr(getattr(model, "config", None),
                           "position", None) == "alibi"
        # seq parallel WITHOUT pipeline: swap attention in the plain loss.
        # With pipeline, make_pipelined_loss_fn composes seq itself.
        if loss_fn is None and seq_size > 1 and pipe_size == 1 \
                and hasattr(model, "config"):
            from ..parallel.sequence import make_attention
            from ..models.transformer import lm_loss_fn

            topology = topology or MeshTopology.build(cfg.mesh)
            kw = {}
            if is_alibi:
                # bypass the model's plain ALiBi wrapper: the bias must
                # be built INSIDE the Ulysses shard_map with this
                # shard's global head offset
                kw["alibi_heads"] = model.config.num_heads
                kw["alibi_scale"] = model.config.attn_scale
            else:
                base = getattr(model, "attention_fn", None)
                if base is not None:
                    kw["base_attention"] = base
            attn = make_attention(topology, cfg.sequence_parallel.mode,
                                  **kw)
            loss_fn = lm_loss_fn(model.config, attn)
        # pipeline parallelism (gpipe/1f1b) over the pipe axis; seq > 1
        # composes via per-shard Ulysses inside the pipeline shard_map
        if loss_fn is None and pipe_size > 1 and hasattr(model, "config"):
            if seq_size > 1 and cfg.sequence_parallel.mode != "ulysses":
                raise NotImplementedError(
                    f"sequence_parallel.mode="
                    f"{cfg.sequence_parallel.mode!r} is not composable "
                    "with pipeline parallelism (only 'ulysses' is)")
            from ..parallel.pipeline import make_pipelined_loss_fn

            topology = topology or MeshTopology.build(cfg.mesh)
            M = cfg.pipeline.num_microbatches or pipe_size
            kw = {"schedule": cfg.pipeline.schedule}
            model_attn = getattr(model, "attention_fn", None)
            if model_attn is not None:
                kw["attention_fn"] = model_attn
            loss_fn = make_pipelined_loss_fn(model.config, topology, M, **kw)
        loss_fn = loss_fn or model.loss_fn
    if loss_fn is None or params is None:
        raise ValueError("initialize() needs loss_fn+params or model=")
    return Engine(loss_fn=loss_fn, params=params, config=cfg,
                  topology=topology, param_axes=param_axes,
                  sharding_rules=sharding_rules, model=model, **kwargs)
