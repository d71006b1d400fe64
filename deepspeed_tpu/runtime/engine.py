"""Training engine.

Capability parity with the reference's ``DeepSpeedEngine``
(``runtime/engine.py:175`` — forward :1761 / backward :1902 / step :2100,
gradient accumulation, allreduce, mixed precision, checkpoint save/load,
monitor + timer integration), redesigned TPU-first:

* The fwd/bwd/step trio and all of ZeRO's hook machinery compile into ONE
  jitted, donated ``train_step`` containing a ``lax.scan`` over gradient-
  accumulation microbatches, gradient sharding constraints (ZeRO), global-
  norm clipping, loss scaling, and the fused optimizer update. XLA inserts
  and overlaps every collective the reference issues by hand.
* DeepSpeed's imperative micro-batch API (``forward``/``backward``/``step``
  per microbatch with ``is_gradient_accumulation_boundary``) is preserved as
  a compatibility path that accumulates gradient shards across jitted calls
  and applies the same update at the boundary.
* ZeRO stages 0-3 are placement policies from ``parallel/zero.py`` — there
  is no separate optimizer wrapper class per stage (reference
  stage_1_and_2.py / stage3.py / bf16_optimizer.py / fused_optimizer.py all
  collapse here).

Mixed precision follows the BF16_Optimizer design (reference
runtime/bf16_optimizer.py:30): fp32 master params live in the (ZeRO-sharded)
param tree; compute casts to bf16/fp16 at the loss-fn boundary. fp16 adds
dynamic loss scaling (runtime/fp16/loss_scaler.py parity in
``runtime/loss_scaler.py``).
"""

from __future__ import annotations

import os
import threading
import time
from functools import partial
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from ..config import Config
from ..parallel.mesh import Topology
from ..parallel.zero import ZeroShardingRules
from ..profiling import collectives as coll
from ..profiling.trace import annotate
from ..utils.logging import log_dist, logger
from ..utils.timer import SynchronizedWallClockTimer, ThroughputTimer
from . import loss_scaler as ls
from .checkpoint import CheckpointEngine, consolidate_full_state, validate_tag_consistency
from .lr_schedules import Schedule, build_schedule, constant_lr
from .optimizers import Transform, as_transform, build_optimizer

LossFn = Callable[..., Any]  # (params, batch, rng) -> loss | (loss, aux)


def _normalize_loss_fn(loss_fn: LossFn) -> Callable[[Any, Any, Any], Tuple[Any, Dict[str, Any]]]:
    def wrapped(params, batch, rng):
        out = loss_fn(params, batch, rng)
        if isinstance(out, tuple):
            loss, aux = out
            if not isinstance(aux, dict):
                aux = {"aux": aux}
        else:
            loss, aux = out, {}
        return loss, aux

    return wrapped


def _cast_tree(tree: Any, dtype) -> Any:
    def cast(x):
        if hasattr(x, "dtype") and jnp.issubdtype(x.dtype, jnp.floating):
            return x.astype(dtype)
        return x

    return jax.tree_util.tree_map(cast, tree)


def _jit_cache_size(fn: Any) -> int:
    """Compiled-entry count of a jitted callable (0 when unbuilt or the
    running JAX hides the counter)."""
    if fn is None:
        return 0
    try:
        return int(fn._cache_size())
    except Exception:
        return 0


def _batch_abstract(batch: Any) -> Any:
    """ShapeDtypeStruct tree for AOT lowering: jax.Arrays keep their
    sharding, ShapeDtypeStructs pass through, host arrays lower with
    unspecified placement."""
    def leaf(x):
        if isinstance(x, jax.ShapeDtypeStruct):
            return x
        sharding = getattr(x, "sharding", None)
        x = np.asarray(x) if not hasattr(x, "shape") else x
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)

    return jax.tree_util.tree_map(leaf, batch)


def _batch_signature(batch: Any) -> tuple:
    """Hashable (shape, dtype) signature of a batch pytree — the part of
    the jit cache key a dataloader can change between steps."""
    return tuple(
        (tuple(getattr(x, "shape", ())), str(getattr(x, "dtype", type(x).__name__)))
        for x in jax.tree_util.tree_leaves(batch))


def host_memory_kind() -> str:
    """The host memory space name for offload shardings. Accelerator
    backends expose ``pinned_host``; the CPU backend (and some older
    runtimes) only ``unpinned_host`` — probing keeps ZeRO-Offload
    functional on both instead of silently disabling itself."""
    try:
        kinds = {m.kind for m in jax.devices()[0].addressable_memories()}
    except Exception:
        return "pinned_host"
    for kind in ("pinned_host", "unpinned_host"):
        if kind in kinds:
            return kind
    return "pinned_host"


def global_norm(tree: Any) -> jnp.ndarray:
    leaves = [jnp.sum(jnp.square(g.astype(jnp.float32))) for g in jax.tree_util.tree_leaves(tree)]
    if not leaves:
        return jnp.zeros([], jnp.float32)
    return jnp.sqrt(jnp.asarray(leaves).sum())


class TrainEngine:
    """The TPU-native DeepSpeedEngine."""

    def __init__(self, *,
                 loss_fn: LossFn,
                 params: Any,
                 config: Config,
                 topology: Optional[Topology] = None,
                 optimizer: Optional[Any] = None,
                 lr_scheduler: Optional[Any] = None,
                 tp_specs: Optional[Any] = None,
                 model: Optional[Any] = None,
                 donate: bool = True):
        self.config = config
        self.model = model
        # hpZ / MiCS factor the data-parallel dimension into data × zshard
        # (inner = fast-ICI slice); see parallel/mesh.py MESH_AXES.
        zero_inner = config.zero.zero_inner_size()
        self.topo = topology or Topology.build(config.mesh, zero_inner=zero_inner)
        if zero_inner > 1 and self.topo.zero_secondary_size == 1:
            logger.warning(
                f"hpz/mics inner partition size {zero_inner} requested but the "
                f"provided topology has no zshard axis — running without it")
        self._raw_loss_fn = loss_fn
        self.loss_fn = _normalize_loss_fn(loss_fn)
        self.tp_specs = tp_specs
        self._donate = donate

        # -- pipeline parallelism: GAS micro-batches flow through the
        # rotating-microbatch executor inside ONE loss call instead of the
        # outer accumulation scan (reference: PipelineEngine.train_batch,
        # runtime/pipe/engine.py:312, where GAS == in-flight micro-batches)
        self._pipelined = self.topo.pipe_parallel_size > 1
        if self._pipelined:
            if model is None or not hasattr(model, "pipeline_loss"):
                raise ValueError(
                    "mesh has pipe axis > 1 but the model does not expose "
                    "pipeline_loss(params, batch, rng, num_microbatches)")

            def pipe_loss(p, batch, rng):
                # read GAS at call time: resolve_batch_config (below) may
                # derive it from train_batch/micro_batch after this closure
                # is created
                return model.pipeline_loss(p, batch, rng,
                                           config.gradient_accumulation_steps)

            self.loss_fn = _normalize_loss_fn(pipe_loss)

        # -- batch arithmetic (reference config._configure_train_batch_size)
        config.resolve_batch_config(self.topo.data_parallel_size)
        log_dist(
            f"batch config: train_batch={config.train_batch_size} "
            f"micro_batch={config.train_micro_batch_size_per_gpu} "
            f"gas={config.gradient_accumulation_steps} dp={self.topo.data_parallel_size}"
        )

        # -- ZeRO placement rules
        self.zero_rules = ZeroShardingRules(self.topo, config.zero)
        param_shapes = jax.eval_shape(lambda p: p, params)
        self.param_shardings = self.zero_rules.param_shardings(param_shapes, tp_specs)
        self.grad_shardings = self.zero_rules.grad_shardings(param_shapes, tp_specs)
        # what the stage needs to move a chip a step (zero_plan), and what
        # train.step carries beside its number: static ints, made once
        self._zero_plan = self._make_zero_plan(param_shapes)
        self._collectives: Tuple[Any, ...] = ()   # warmup() fills it
        self._step_attrs: Dict[str, int] = self._make_step_attrs()

        # -- ZeRO++ (reference runtime/engine.py:836-845 keys):
        #   qwZ  — the stage-3 weight gather at the compute-cast boundary
        #          moves blockwise-int8 payloads (partition_parameters.py:679)
        #   hpZ  — compute copy sharded over the inner 'zshard' axes only, so
        #          per-layer all-gathers stay on fast ICI (:883)
        #   qgZ  — gradients reduced across the outer 'data' axis through the
        #          hierarchical quantized collective (comm/compressed.py)
        # The comm_compression block (docs/communication.md) makes both
        # quantized legs the DEFAULT above its mesh-size threshold; the
        # explicit zero_optimization knobs opt individual legs in below it.
        self._cc = config.comm_compression
        cc_on = self._cc.resolve_enabled(self.topo.data_parallel_size)
        self._qwz = ((bool(config.zero.zero_quantized_weights) or cc_on)
                     and config.zero.stage >= 3)
        self._qgz = (bool(config.zero.zero_quantized_gradients)
                     or (cc_on and config.zero.stage >= 2))
        self._hpz = self.zero_rules.hpz
        # manual shard_map axes of the facade-routed grad/weight paths:
        # the factored data-parallel dimension (outer 'data' = the slow
        # inter-slice hop, inner 'zshard' = fast ICI)
        self._dp_manual_axes = tuple(
            a for a in ("data", "zshard") if self.topo.axis_size(a) > 1)
        # T3-style staged block schedule (parallel/zero.py): models
        # exposing zero3_blocks get per-block eager collective issue
        # inside the fused step; "serial" keeps just-in-time issue (A/B).
        # Only when the engine trains the MODEL'S OWN loss: the staged
        # path computes loss from zero3_blocks' loss_tail, so silently
        # engaging it under a user-supplied loss_fn would optimize a
        # different objective than the one passed to initialize().
        self._staged_mode = None
        if (config.zero.stage >= 3 and not self._pipelined
                and self._dp_manual_axes
                and model is not None and hasattr(model, "zero3_blocks")
                and self._cc.overlap != "off"):
            if self._raw_loss_fn == getattr(model, "loss", None):
                self._staged_mode = self._cc.overlap
            else:
                logger.warning(
                    "staged ZeRO-3 overlap disabled: a custom loss_fn was "
                    "supplied, but the model's zero3_blocks defines its own "
                    "loss_tail — training proceeds on the (unstaged) facade "
                    "path with the custom loss")
        # quant-error stats only exist where a quantized facade path runs
        self._wants_quant_err = bool(
            self._cc.error_stats
            and (self._staged_mode is not None
                 or (self._qgz and self._dp_manual_axes)))
        self._secondary_shardings = None
        if self._hpz or (self._qwz and self.zero_rules.zero_size > 1):
            self._secondary_shardings = self.zero_rules.secondary_param_shardings(
                param_shapes, tp_specs)

        # master params: fp32 (BF16_Optimizer design); compute dtype applied in loss
        params = _cast_tree(params, jnp.float32)
        self.params = jax.device_put(params, self.param_shardings)

        # -- ZeRO-3 param offload (reference runtime/zero/stage3.py:558 +
        # partitioned_param_swapper.py): master param shards parked in
        # pinned host memory ("cpu") or on disk via the aio engine ("nvme")
        # between steps; uploaded around each step. The compute copy inside
        # the step is unchanged (bf16, per-layer gathers).
        self._param_offload_device = (config.zero.offload_param.device
                                      if config.zero.stage >= 3 else "none")
        self._param_host_shardings = None
        self._param_nvme_swapper = None
        if self._param_offload_device == "cpu":
            # pinned-host shardings gate only the 'cpu' mode — the nvme path
            # never uses them (it stages through the aio swapper)
            try:
                host_kind = host_memory_kind()
                self._param_host_shardings = jax.tree_util.tree_map(
                    lambda sh, x: (sh.with_memory_kind(host_kind)
                                   if getattr(x, "ndim", 0) >= 1 else sh),
                    self.param_shardings, self.params)
            except Exception as e:  # platform without host memory space
                logger.warning(f"param offload unavailable: {e}")
                self._param_offload_device = "none"
        if self._param_offload_device == "nvme":
            from .swap_tensor import OptimizerSwapper

            path = (config.zero.offload_param.nvme_path
                    or "/tmp/ds_tpu_param_swap")
            self._param_nvme_swapper = OptimizerSwapper(path)
        # actual parking happens after optimizer-state init below
        # (the optimizer init consumes the device-resident params)

        # -- optimizer + schedule
        base_lr = float(config.optimizer.params.get("lr", 1e-3))
        if lr_scheduler is not None and callable(lr_scheduler):
            self.lr_schedule: Schedule = lr_scheduler
        elif config.scheduler.type:
            self.lr_schedule = build_schedule(config.scheduler.type, config.scheduler.params, base_lr)
        else:
            self.lr_schedule = constant_lr(base_lr)
        if optimizer is not None:
            self.optimizer: Transform = as_transform(optimizer)
        else:
            self.optimizer = build_optimizer(config.optimizer.type, config.optimizer.params,
                                             lr_schedule=self.lr_schedule)

        opt_shape = jax.eval_shape(self.optimizer.init, params)
        self.opt_state_shardings = self.zero_rules.opt_state_shardings(
            opt_shape, param_shapes, tp_specs)

        # -- optimizer-state offload (ZeRO-Offload / Infinity parity:
        # reference runtime/zero/offload_config.py + swap_tensor stack).
        # "cpu": state parked in pinned host memory between steps; uploaded
        #        to device around each step (the reference's pinned-buffer
        #        copy engine analog).
        # "nvme": state lives on disk between steps via the native aio
        #        engine (csrc/aio), host RAM as staging.
        self._offload_device = config.zero.offload_optimizer.device
        self._opt_host_shardings = None
        self._nvme_swapper = None
        if self._offload_device in ("cpu", "nvme"):
            try:
                # scalars (step counters) stay in device memory — XLA's SPMD
                # partitioner rejects host placement on replicated scalars,
                # and there is nothing to save by offloading them
                host_kind = host_memory_kind()
                self._opt_host_shardings = jax.tree_util.tree_map(
                    lambda s, shape: (s.with_memory_kind(host_kind)
                                      if len(shape.shape) >= 1 else s),
                    self.opt_state_shardings, opt_shape)
            except Exception as e:  # platform without host memory space
                logger.warning(f"optimizer offload unavailable: {e}")
                self._offload_device = "none"
        if self._offload_device == "nvme":
            from .swap_tensor import OptimizerSwapper

            path = config.zero.offload_optimizer.nvme_path or "/tmp/ds_tpu_swap"
            self._nvme_swapper = OptimizerSwapper(path)

        self.opt_state = jax.jit(
            self.optimizer.init, out_shardings=self.opt_state_shardings
        )(self.params)
        # struct-only checkpoint template captured while everything is still
        # device-resident: load_checkpoint must not have to swap offloaded
        # state in from disk just to learn the tree structure
        self._params_struct = jax.eval_shape(lambda p: p, self.params)
        self._opt_struct = jax.eval_shape(lambda o: o, self.opt_state)
        if self._opt_host_shardings is not None:
            # park in host memory outside jit (memory-kind out_shardings on
            # scalar leaves trip the SPMD partitioner)
            self.opt_state = jax.device_put(self.opt_state,
                                            self._opt_host_shardings)
        if self._offload_device == "nvme":
            self._nvme_swapper.swap_out(self.opt_state)
            self.opt_state = None  # lives on disk between steps
        self._params_to_offload()

        # -- loss scaling state
        if config.fp16.enabled:
            if config.fp16.dynamic_loss_scale:
                self.scaler_state = ls.make_state(config.fp16.initial_scale_power, config.fp16.hysteresis)
            else:
                self.scaler_state = ls.static_state(config.fp16.loss_scale)
        else:
            self.scaler_state = ls.static_state(1.0)

        self.compute_dtype = config.compute_dtype
        self.global_steps = 0
        self.micro_steps = 0
        self.skipped_steps = 0  # via the lazy property below
        self.rng = jax.random.PRNGKey(config.train_seed)
        # commit the small carried states (scaler, rng) to the replicated
        # sharding they come back with after a step: uncommitted first-call
        # avals would miss the jit cache on step 2 and compile the whole
        # train step a SECOND time (trace-stability contract: one compile
        # per program — tests/test_perf_pipeline.py pins it)
        repl = self.topo.replicated()
        self.scaler_state = jax.device_put(self.scaler_state, repl)
        self.rng = jax.device_put(self.rng, repl)

        # -- bookkeeping / observability
        self.timers = SynchronizedWallClockTimer()
        self.tput = ThroughputTimer(batch_size=config.train_batch_size,
                                    steps_per_output=config.steps_per_print,
                                    monitor_memory=config.memory_breakdown)
        self.monitor = None
        if config.monitor.enabled:
            from ..monitor.monitor import MonitorMaster

            self.monitor = MonitorMaster(config.monitor)
        # unified telemetry: the monitor (when enabled) is one sink among
        # several; with telemetry AND monitor off, wants_step_records is
        # False and the step path keeps the seed's sync discipline exactly
        from ..telemetry import Telemetry

        self.telemetry = Telemetry(config.telemetry, monitor=self.monitor)
        if config.telemetry.enabled:
            from ..resilience import restart_count_from_env
            from ..telemetry import set_telemetry

            # share the pipeline with the comm facade / inference engines
            set_telemetry(self.telemetry)
            restart_count_from_env()
        if config.comms_logger.enabled or config.telemetry.enabled:
            # trace-time recording is free at steady state; telemetry needs
            # it on for the StepStats comm breakdown
            from ..comm.comm import configure_comms_logger

            configure_comms_logger(enabled=True,
                                   verbose=config.comms_logger.verbose)
        self._step_flops: Optional[float] = None  # per-step, from XLA cost analysis
        self._peak_flops: Optional[float] = None
        self._tokens_per_batch: Optional[int] = None
        self._comm_totals_prev: Dict[str, Dict[str, float]] = {}
        # the compiled step's own traffic as the comm ledger has it
        # (_step_comm): {op: {payload: runs a step}}, {op: (calls, bytes)}
        self._comm_booked: Optional[Dict[str, Dict[int, int]]] = None
        self._comm_totals: Dict[str, Tuple[int, int]] = {}
        self._closed = False
        self.ckpt_engine = CheckpointEngine(
            async_save=config.checkpoint.async_save,
            keep_last_n=config.checkpoint.keep_last_n,
            verify_checksums=config.checkpoint.verify_checksums)

        # -- fault tolerance (docs/fault_tolerance.md). When every knob is
        # off, _ft_active stays False and the step path performs exactly
        # the same host synchronizations as before — the guards' cost
        # exists only when a guard does.
        rcfg = config.resilience
        self._step_hooks: list = []
        self._nan_skip_traced = rcfg.divergence.nan_action == "skip"
        self._divergence = None
        if rcfg.divergence.wants_host_check:
            from ..resilience.divergence import DivergenceGuard

            self._divergence = DivergenceGuard(
                nan_action=rcfg.divergence.nan_action,
                spike_action=rcfg.divergence.spike_action,
                spike_factor=rcfg.divergence.spike_factor,
                window=rcfg.divergence.window,
                warmup_steps=rcfg.divergence.warmup_steps)
        self.preemption_guard = None
        self._stop_reason: Optional[str] = None
        self._dataloader = None  # bound loader whose position checkpoints carry
        self._rollback_streak = 0   # rollbacks without progress past...
        self._ft_high_step = 0      # ...this high-water step
        self._ckpt_save_dir = config.checkpoint.save_dir
        self._ft_active = (self._divergence is not None
                           or bool(self._ckpt_save_dir
                                   and config.checkpoint.save_interval > 0))
        if rcfg.chaos.enabled:
            from ..resilience.chaos import FaultInjector, install_fault_injector

            inj = install_fault_injector(FaultInjector(rcfg.chaos))
            self.register_step_hook(lambda _eng, step: inj.on_step(step))

        # compat micro-step accumulation state
        self._acc_grads: Optional[Any] = None
        self._acc_add_fn = None   # cached jitted accumulator (one trace)
        self._last_loss = None

        # optional traced transform applied to the compute-copy params
        # (compression QAT / pruning masks — compression/compress.py)
        self._param_transform: Optional[Callable[[Any], Any]] = None

        self._train_step_fn = None
        self._eval_step_fn = None
        self._micro_grad_fn = None
        self._apply_update_fn = None

        # -- async/compiled dispatch machinery (docs/performance.md)
        self._train_step_raw = None          # unjitted step body (scanned by train_steps)
        self._train_steps_fns: Dict[int, Any] = {}  # k -> jitted k-step scan
        self._train_step_aot = None          # AOT executable from warmup()
        self._warmup_thread: Optional[threading.Thread] = None
        self._loader_iter = None             # persistent iterator for train_steps(k)
        self._loader_iter_src = None
        self._steps_fallback_logged: set = set()
        # recompile guard: batch signatures seen per compiled program
        self._seen_batch_sigs: Dict[str, set] = {}
        self._recompile_warned = False
        # trace counters: the step bodies bump these at TRACE time (the
        # Python in a jitted function only runs while JAX (re)traces it),
        # so each count is one program construction — the honest
        # "compiles per program" number the trace-stability tests pin.
        # (pjit's _cache_size() over-counts: it keys fastpath entries on
        # argument committed-ness and can hold 2 entries for 1 executable.)
        from collections import Counter as _Counter

        self._trace_counts: Dict[str, int] = _Counter()
        # host-overhead ledger clocks
        self._last_call_end_t: Optional[float] = None
        self._data_wait_prev_s = 0.0
        from .compile_cache import place_compile_cache

        place_compile_cache(config.compile.cache_dir)

    # ==================================================================
    # properties (parity with engine.py:468-:869 accessors)
    @property
    def skipped_steps(self) -> int:
        """Steps dropped by the loss scaler. Resolved lazily: the per-step
        overflow flag stays a device scalar accumulated with an async add —
        fetching it eagerly would block the host on every step and
        serialize dispatch."""
        if self._skipped_dev is not None:
            self._skipped_base += int(jax.device_get(self._skipped_dev))
            self._skipped_dev = None
        return self._skipped_base

    @skipped_steps.setter
    def skipped_steps(self, value: int) -> None:
        self._skipped_base = int(value)
        self._skipped_dev = None

    def _note_skipped(self, skipped) -> None:
        s = jnp.asarray(skipped).astype(jnp.int32)
        self._skipped_dev = s if self._skipped_dev is None else self._skipped_dev + s

    @property
    def train_batch_size(self) -> int:
        return self.config.train_batch_size

    @property
    def train_micro_batch_size_per_gpu(self) -> int:
        return self.config.train_micro_batch_size_per_gpu

    @property
    def gradient_accumulation_steps(self) -> int:
        return self.config.gradient_accumulation_steps

    @property
    def zero_optimization_stage(self) -> int:
        return self.config.zero.stage

    @property
    def data_parallel_world_size(self) -> int:
        return self.topo.data_parallel_size

    @property
    def world_size(self) -> int:
        return self.topo.world_size

    @property
    def gradient_clipping(self) -> float:
        return self.config.gradient_clipping

    def get_lr(self) -> float:
        return float(self.lr_schedule(jnp.asarray(self.global_steps)))

    def get_loss_scale(self) -> float:
        return float(self.scaler_state.scale)

    def is_gradient_accumulation_boundary(self) -> bool:
        return (self.micro_steps + 1) % self.gradient_accumulation_steps == 0

    # ==================================================================
    # core jitted programs
    @jax.named_scope("zero_cast")   # ZeRO's placement point, by name
    def _compute_copy(self, params):
        """Compute-dtype copy of the fp32 master params with the ZeRO++
        transforms applied at this boundary: qwZ fake-quantizes through the
        facade's STE gather (comm/compressed.py — the int8 tensor carries
        the gather placement, so the cross-'data' all-gather moves
        1 byte/elt), hpZ re-shards onto the inner axes only (per-layer
        gathers stay on fast ICI). The facade shard_map paths use
        :meth:`_facade_compute_copy` instead, which keeps the sharded
        layout so the gather happens inside the metered region."""
        pc = _cast_tree(params, self.compute_dtype)
        if self._param_transform is not None:
            pc = self._param_transform(pc)
        if self._secondary_shardings is None:
            return pc
        from ..comm.compressed import QuantSpec, ste_quant_gather

        wq = QuantSpec(self._cc.weight_bits, self._cc.weight_block)

        # no 'data' hop (e.g. hpZ partition == dp): the re-shard moves
        # nothing across a slow link, so fake-quantizing it would pay the
        # bracket + error with no wire to save (intra-slice stays dense,
        # docs/communication.md)
        qwz_here = self._qwz and "data" in self._dp_manual_axes

        def leaf(x, sh):
            if not (hasattr(x, "dtype") and jnp.issubdtype(x.dtype, jnp.floating)):
                return x
            if qwz_here and x.size % wq.block == 0 and x.size >= 4096:
                return ste_quant_gather(x, sh, wq, self.compute_dtype)
            return jax.lax.with_sharding_constraint(x, sh)

        return jax.tree_util.tree_map(leaf, pc, self._secondary_shardings)

    def _loss_and_grads(self, params, batch, rng, scale):
        """One microbatch: grads of (scaled) loss wrt fp32 master params,
        computed in the compute dtype. Dispatch: the staged block schedule
        (T3 overlap) when the model exposes it, else the facade qgZ path
        when quantized gradients are on, else the plain GSPMD path."""
        if self._staged_mode is not None:
            return self._loss_and_grads_staged(params, batch, rng, scale)
        if self._qgz and self._dp_manual_axes:
            return self._loss_and_grads_qgz(params, batch, rng, scale)

        def scaled_loss(p):
            loss, aux = self.loss_fn(self._compute_copy(p), batch, rng)
            return loss.astype(jnp.float32) * scale, (loss, aux)

        grads, (loss, aux) = jax.grad(scaled_loss, has_aux=True)(params)
        return grads, loss, aux

    @staticmethod
    def _strip_spec_to_axes(spec: PartitionSpec, keep) -> PartitionSpec:
        """Project a PartitionSpec onto a subset of mesh axes (for partial-
        manual shard_map in_specs, which may only name manual axes)."""
        out = []
        for e in spec:
            if e is None:
                out.append(None)
            elif isinstance(e, tuple):
                kept = tuple(a for a in e if a in keep)
                out.append(kept[0] if len(kept) == 1 else (kept or None))
            else:
                out.append(e if e in keep else None)
        return PartitionSpec(*out)

    def _facade_compute_copy(self, params):
        """Compute-dtype copy for the facade shard_map paths: keeps the
        stage-3 SHARDED layout so the per-leaf (quantized) gather happens
        INSIDE the shard_map region where the facade can meter it. Under
        hpZ the secondary (inner-sharded) copy is used instead, with the
        STE fake-quant booking the one outer hop at the cast boundary —
        the facade then only issues the fast-ICI inner gathers.
        Returns (pc, pc_shardings)."""
        if self._hpz and self._secondary_shardings is not None:
            return self._compute_copy(params), self._secondary_shardings
        pc = _cast_tree(params, self.compute_dtype)
        if self._param_transform is not None:
            pc = self._param_transform(pc)
        pc = jax.lax.with_sharding_constraint(pc, self.param_shardings)
        return pc, self.param_shardings

    def _facade_axes(self):
        """(outer, outer_world, inner, inner_world) of the hierarchical
        comm layout: 'data' is the slow inter-slice hop, 'zshard' the
        fast-ICI intra-slice hop when the mesh factors it out. When the
        whole DP group is the inner slice (data=1, e.g. hpZ partition ==
        dp), there IS no slow hop: outer comes back None/world-1 so every
        quantized leg degrades to the dense fast-ICI path — the contract
        ("the intra-slice hop always reduces dense fp",
        docs/communication.md) must hold on degenerate meshes too."""
        axes = self._dp_manual_axes
        outer = "data" if "data" in axes else None
        inner = "zshard" if "zshard" in axes else None
        return (outer, self.topo.axis_size("data") if outer else 1,
                inner, self.topo.axis_size("zshard") if inner else 1)

    def _facade_qspecs(self):
        from ..comm.compressed import QuantSpec

        wq = (QuantSpec(self._cc.weight_bits, self._cc.weight_block)
              if self._qwz else None)
        gq = (QuantSpec(self._cc.grad_bits, self._cc.grad_block)
              if self._qgz else None)
        return wq, gq

    def _facade_prelude(self, params, batch):
        """Shared setup of the facade shard_map paths (qgZ + staged):
        axis layout, quant specs, sharded compute copy, stripped in/out
        specs. One site to change when the facade contract moves."""
        axes = self._dp_manual_axes
        outer, outer_world, inner, inner_world = self._facade_axes()
        wq, gq = self._facade_qspecs()
        pc, pc_shardings = self._facade_compute_copy(params)
        is_spec = lambda x: isinstance(x, PartitionSpec)  # noqa: E731
        pc_specs = jax.tree_util.tree_map(
            lambda sh: self._strip_spec_to_axes(sh.spec, set(axes)),
            pc_shardings)
        bspec = PartitionSpec(axes[0] if len(axes) == 1 else axes)
        batch_specs = jax.tree_util.tree_map(lambda _: bspec, batch)
        rep = PartitionSpec()
        rep_tree = jax.tree_util.tree_map(lambda _: rep, pc_specs,
                                          is_leaf=is_spec)
        return dict(axes=axes, outer=outer, outer_world=outer_world,
                    inner=inner, inner_world=inner_world, wq=wq, gq=gq,
                    pc=pc, pc_specs=pc_specs, batch_specs=batch_specs,
                    rep=rep, rep_tree=rep_tree, is_spec=is_spec)

    @staticmethod
    def _facade_err_scalar(stats, axes):
        """Replicated max quantization error: each rank's local max must
        be pmax-reduced over the manual axes before the out_spec declares
        it replicated — otherwise the host reads an arbitrary shard's
        value and a single-rank bound violation is invisible."""
        from ..comm import compressed as ccomm

        local = (jnp.max(jnp.stack(stats)) if stats
                 else jnp.zeros([], jnp.float32))
        return ccomm.pmax(local, axes)

    def _run_facade_spmd(self, spmd, env, batch, rng, scale, aux_spec):
        """jit-traceable shard_map wrapper shared by the facade paths:
        manual over the factored DP axes, replicated outputs, fp32 grad
        cast (the linear master->compute chain rule)."""
        grads_c, loss, aux = jax.shard_map(
            spmd, mesh=self.topo.mesh, axis_names=set(env["axes"]),
            in_specs=(env["pc_specs"], env["batch_specs"], env["rep"],
                      env["rep"]),
            out_specs=(env["rep_tree"], env["rep"], aux_spec),
            check_vma=False)(env["pc"], batch, rng, scale)
        grads = jax.tree_util.tree_map(lambda g: g.astype(jnp.float32),
                                       grads_c)
        return grads, loss, aux

    def _loss_and_grads_qgz(self, params, batch, rng, scale):
        """qgZ/qwZ through the compressed-collectives facade
        (docs/communication.md): the stage-3 weight fetch is a facade
        all-gather per sharded leaf — quantized across the outer 'data'
        hop when qwZ is on, dense across the fast-ICI 'zshard' hop — and
        the cross-replica gradient reduction is the hierarchical chunked
        mean (fp reduce-scatter inside the slice, int8/int4 exchange on
        the chunk across slices, fp all-gather back). Runs under
        shard_map with the factored data-parallel axes manual; model/seq
        axes stay on their GSPMD placement as before."""
        from ..comm import compressed as ccomm

        env = self._facade_prelude(params, batch)
        wants_err = self._wants_quant_err

        def spmd(pc, mb, rng, scale):
            stats = [] if wants_err else None
            pc_full = jax.tree_util.tree_map(
                lambda x, spec: ccomm.gather_param_leaf(
                    x, spec,
                    outer_axes=(env["outer"],) if env["outer"] else (),
                    qspec=env["wq"], stats=stats),
                pc, env["pc_specs"], is_leaf=env["is_spec"])

            def scaled_loss(p):
                loss, aux = self.loss_fn(p, mb, rng)
                return loss.astype(jnp.float32) * scale, (loss, aux)

            grads, (loss, aux) = jax.grad(scaled_loss, has_aux=True)(pc_full)
            grads = ccomm.tree_hierarchical_pmean(
                grads, outer_axis=env["outer"],
                outer_world=env["outer_world"], inner_axis=env["inner"],
                inner_world=env["inner_world"], qspec=env["gq"],
                stats=stats)
            loss = ccomm.pmean(loss, env["axes"])
            if wants_err:
                aux = dict(aux)
                aux["quant_rel_err"] = self._facade_err_scalar(
                    stats, env["axes"])
            return grads, loss, aux

        return self._run_facade_spmd(spmd, env, batch, rng, scale,
                                     aux_spec=env["rep"])

    def _loss_and_grads_staged(self, params, batch, rng, scale):
        """T3-style staged ZeRO-3 step (parallel/zero.py
        Zero3BlockSchedule): the model's sequential blocks run with
        per-block facade collectives — block i+1's weight all-gather
        issued before block i's forward, the backward re-gathers each
        block (2-gather schedule, bounded param residency) and defers
        the previous block's gradient reduce behind the current block's
        compute — so the compiler can hide the ZeRO-3 comm behind
        compute. Serial mode ("comm_compression.overlap": "serial")
        issues each collective just-in-time instead; both orders are
        bit-exact to each other (identical dataflow) and that is pinned
        by tests."""
        from ..comm import compressed as ccomm
        from ..parallel.zero import Zero3BlockSchedule

        env = self._facade_prelude(params, batch)
        # per-block spec subtrees: zero3_blocks is structural in params
        prog_struct = self.model.zero3_blocks(env["pc_specs"], None)
        block_specs = prog_struct.blocks
        overlapped = self._staged_mode == "staged"
        wants_err = self._wants_quant_err
        def spmd(pc, mb, rng, scale):
            stats = [] if wants_err else None
            prog = self.model.zero3_blocks(pc, mb, rng)

            def gather(i, blk):
                return jax.tree_util.tree_map(
                    lambda x, spec: ccomm.gather_param_leaf(
                        x, spec,
                        outer_axes=(env["outer"],) if env["outer"] else (),
                        qspec=env["wq"], stats=stats),
                    blk, block_specs[i], is_leaf=env["is_spec"])

            def reduce(i, g):
                return ccomm.tree_hierarchical_pmean(
                    g, outer_axis=env["outer"],
                    outer_world=env["outer_world"],
                    inner_axis=env["inner"],
                    inner_world=env["inner_world"], qspec=env["gq"],
                    stats=stats)

            sched = Zero3BlockSchedule(gather, reduce, overlapped=overlapped)
            loss, block_grads = sched.loss_and_grads(prog, scale)
            grads = prog.merge(block_grads)
            loss = ccomm.pmean(loss.astype(jnp.float32), env["axes"])
            aux = {}
            if wants_err:
                aux["quant_rel_err"] = self._facade_err_scalar(
                    stats, env["axes"])
            return grads, loss, aux

        aux_spec = {"quant_rel_err": env["rep"]} if wants_err else {}
        return self._run_facade_spmd(spmd, env, batch, rng, scale,
                                     aux_spec=aux_spec)

    def _build_train_step(self):
        cfg = self.config
        # pipelined: micro-batching happens inside pipeline_loss
        gas = 1 if self._pipelined else cfg.gradient_accumulation_steps
        clip = cfg.gradient_clipping
        fp16 = cfg.fp16.enabled
        dynamic = fp16 and cfg.fp16.dynamic_loss_scale
        optimizer = self.optimizer

        def train_step(params, opt_state, scaler_state, rng, batch):
            self._trace_counts["train_step"] += 1  # dslint: disable=trace-hygiene -- deliberate trace-time counter: bumps once per (re)trace, which IS the recompile telemetry
            scale = scaler_state.scale if fp16 else jnp.ones([], jnp.float32)

            wants_err = self._wants_quant_err

            def micro(carry, mb):
                acc, rng = carry
                rng, sub = jax.random.split(rng)
                grads, loss, _aux = self._loss_and_grads(params, mb, sub, scale)
                grads = jax.lax.with_sharding_constraint(grads, self.grad_shardings)
                acc_g, acc_loss = acc
                acc_g = jax.tree_util.tree_map(lambda a, g: a + g.astype(jnp.float32), acc_g, grads)
                err = _aux.get("quant_rel_err") if wants_err else None
                return ((acc_g, acc_loss + loss.astype(jnp.float32)), rng), err

            quant_err = None
            if gas > 1:
                # [global_batch, ...] -> [gas, global_batch/gas, ...]
                mb_batch = jax.tree_util.tree_map(
                    lambda x: x.reshape((gas, x.shape[0] // gas) + x.shape[1:]), batch)
                zero_acc = jax.tree_util.tree_map(
                    lambda s: jnp.zeros(s.shape, jnp.float32), jax.eval_shape(lambda p: p, params))
                zero_acc = jax.lax.with_sharding_constraint(zero_acc, self.grad_shardings)
                (carry, rng), errs = jax.lax.scan(
                    micro, ((zero_acc, jnp.zeros([], jnp.float32)), rng), mb_batch)
                grads, loss_sum = carry
                inv = 1.0 / gas
                grads = jax.tree_util.tree_map(lambda g: g * inv, grads)
                loss = loss_sum * inv
                if wants_err:
                    quant_err = jnp.max(errs)
            else:
                rng, sub = jax.random.split(rng)
                grads, loss, _aux = self._loss_and_grads(params, batch, sub, scale)
                grads = jax.lax.with_sharding_constraint(
                    jax.tree_util.tree_map(lambda g: g.astype(jnp.float32), grads),
                    self.grad_shardings)
                if wants_err:
                    quant_err = _aux["quant_rel_err"]

            new_params, new_opt, new_scaler, gnorm, skipped = self._update(
                params, opt_state, scaler_state, grads, scale,
                clip=clip, fp16=fp16, dynamic=dynamic, optimizer=optimizer,
                nan_skip=self._nan_skip_traced)
            metrics = {
                "loss": loss,
                "grad_norm": gnorm,
                "loss_scale": new_scaler.scale,
                "skipped": skipped,
            }
            if wants_err:
                # max local quantization round-trip rel error across this
                # step's facade collectives (docs/communication.md)
                metrics["quant_rel_err"] = quant_err
            return new_params, new_opt, new_scaler, rng, metrics

        self._train_step_raw = train_step  # dslint: disable=races -- warmup-join synchronization: _build_train_step runs on main or on the warmup thread, never concurrently (_ensure_train_step_fn joins a pending warmup first; warmup_async is called once from initialize)
        donate = (0, 1, 2) if self._donate else ()
        return jax.jit(train_step, donate_argnums=donate,
                       out_shardings=self._step_out_shardings())

    def _step_out_shardings(self):
        """Output shardings pinning the engine state to exactly the
        shardings it entered with. Left unspecified, GSPMD may hand the
        carried state back under an equivalent-but-unequal sharding
        representation, and the NEXT call's avals miss the jit cache —
        the whole step program compiles a second time (trace-stability
        contract, tests/test_perf_pipeline.py)."""
        repl = self.topo.replicated()
        scaler_sh = jax.tree_util.tree_map(lambda _: repl, self.scaler_state)
        metrics_sh = {"loss": repl, "grad_norm": repl, "loss_scale": repl,
                      "skipped": repl}
        if self._wants_quant_err:
            metrics_sh["quant_rel_err"] = repl
        return (self.param_shardings, self.opt_state_shardings, scaler_sh,
                repl, metrics_sh)

    def _ensure_train_step_fn(self):
        """The jitted single-step program, building it on first use. Joins
        a pending AOT warmup thread first so a warmup-compiled executable
        (and its persistent-cache entry) is never raced by a second
        compile of the same program."""
        if self._warmup_thread is not None:
            self._warmup_thread.join()
            self._warmup_thread = None
        if self._train_step_fn is None:
            self._train_step_fn = self._build_train_step()  # dslint: disable=races -- warmup-join synchronization: the join two lines up establishes happens-before with the warmup thread's write; no other writer exists
        return self._train_step_fn

    # ==================================================================
    # AOT warmup (docs/performance.md): compile the fused step during
    # initialize(), overlapped with the input pipeline's warm fill
    def warmup(self, batch: Any) -> bool:
        """AOT-compile the fused train step against ``batch`` — a real
        batch or a ``jax.ShapeDtypeStruct`` tree (see
        ``DataLoader.batch_struct``; no data movement needed). The
        compiled executable serves subsequent ``train_batch`` calls whose
        batch signature matches, and with the persistent compilation
        cache enabled the compile is also written to disk, so even a
        signature miss only pays a cache read. Returns False (warned,
        engine fully functional on the lazy-jit path) on any failure."""
        if self._offload_device != "none" or self._param_offload_device != "none":
            logger.warning("AOT warmup skipped: offload parks state between"
                           " steps (no stable arguments to lower against)")
            return False
        try:
            if self._train_step_fn is None:
                self._train_step_fn = self._build_train_step()
            struct = _batch_abstract(batch)
            lowered = self._train_step_fn.lower(
                self.params, self.opt_state, self.scaler_state, self.rng,
                struct)
            self._train_step_aot = lowered.compile()  # dslint: disable=races -- warmup-join synchronization: train_batch reaches its _train_step_aot read only after _ensure_train_step_fn joined this thread
            self._note_step_collectives()
            return True
        except Exception as e:  # noqa: BLE001 — warmup must never kill init
            logger.warning(f"AOT warmup failed (lazy jit path unaffected): {e}")
            return False

    def warmup_async(self, batch: Any) -> threading.Thread:
        """Run :meth:`warmup` in a background thread (XLA compilation
        releases the GIL), overlapping the compile with the caller's own
        warm-up work — e.g. the prefetch pipeline's first fills. The first
        ``train_batch``/``train_steps`` joins it."""
        t = threading.Thread(target=self.warmup, args=(batch,),
                             name="dst-aot-warmup", daemon=True)
        self._warmup_thread = t
        t.start()
        return t

    # ==================================================================
    # what the step moves (docs/observability.md "Program spans and device
    # scopes"): the stage's plan from shapes, the compiled step's
    # collectives from its text. Host-side bookkeeping, made once.
    def _make_zero_plan(self, param_shapes) -> Dict[str, int]:
        """See :meth:`zero_plan`."""
        n = self.topo.data_parallel_size
        stage = self.config.zero.stage
        zero_axes = set(self.zero_rules.zero_axes)

        def leaf(l, sh) -> Tuple[int, bool]:
            """(elements of one chip's part before ZeRO cuts it, whether
            the stage stores it cut): a leaf a tensor-parallel axis cuts is
            gathered and reduced over the data axis a part at a time."""
            axes = [a for e in sh.spec if e is not None
                    for a in (e if isinstance(e, tuple) else (e,))]
            other = int(np.prod([self.topo.axis_size(a) for a in axes
                                 if a not in zero_axes] or [1]))
            return (int(np.prod(l.shape)) // other,
                    any(a in zero_axes for a in axes))

        leaves = [leaf(l, sh) for l, sh in zip(
            jax.tree_util.tree_leaves(param_shapes),
            jax.tree_util.tree_leaves(self.param_shardings))
            if hasattr(l, "shape")]
        count = sum(c for c, _ in leaves)
        # gradients are cast to float32 where grad_shardings constrain them
        grad_bytes = count * 4
        compute_size = jnp.dtype(self.config.compute_dtype).itemsize
        share = lambda b: b * (n - 1) // n if n > 1 else 0
        if stage >= 3:
            # the compute copy of every leaf stored sharded, brought
            # together for the forward and once more for the backward
            param_bytes = sum(c for c, cut in leaves if cut) * compute_size
            gather, reduce = 2 * share(param_bytes), share(grad_bytes)
        elif stage >= 1:
            # gradient shards in, the updated float32 parameters out
            param_bytes = count * 4
            gather, reduce = share(param_bytes), share(grad_bytes)
        else:
            param_bytes = 0
            gather, reduce = 0, 2 * share(grad_bytes)
        return {"chips": n, "zero_stage": stage, "param_bytes": param_bytes,
                "grad_bytes": grad_bytes, "gather_bytes": gather,
                "reduce_bytes": reduce, "plan_bytes": gather + reduce}

    def zero_plan(self) -> Dict[str, int]:
        """What the configured ZeRO stage needs to move, a chip a step, over
        the data-parallel dimension of size n (``chips``), from shapes and
        shardings alone (docs/observability.md "What a stage moves"): stage
        0 the gradients all-reduced, ``2(n-1)/n x G``; stages 1 and 2 the
        gradients reduce-scattered, ``(n-1)/n x G``, and the updated
        parameters gathered, ``(n-1)/n x P``; stage 3 the compute copy
        gathered for the forward and once more for the backward,
        ``2(n-1)/n x P_compute``, and the gradients reduce-scattered,
        ``(n-1)/n x G``. ``G`` (``grad_bytes``) is the gradient tree in
        float32, the dtype it is constrained onto ``grad_shardings`` in;
        ``param_bytes`` is ``P`` in float32 or, at stage 3, the leaves the
        stage stores sharded in ``compute_dtype``; both count a chip's part
        of a leaf that another mesh axis cuts (1/tp of it under a ``model``
        axis of tp), as the compiled step's shapes do. ``plan_bytes`` =
        ``gather_bytes`` + ``reduce_bytes``. All ints; 0 on one chip."""
        return dict(self._zero_plan)

    def _make_step_attrs(self) -> Dict[str, int]:
        """``train.step``'s static attributes: the plan's bytes, and with a
        catalogue what the compiled step sends a chip a step by the same
        count (``Collective.sent_bytes``, not the comm ledger's
        ``wire_bytes``, which is a payload)."""
        attrs = {"plan_bytes": self._zero_plan["plan_bytes"]}
        if self._collectives:
            attrs["sent_bytes"] = sum(
                t["sent_bytes"] for t in coll.totals(self._collectives).values())
        return attrs

    def step_collectives(self) -> List[Any]:
        """The collectives of the compiled train step, one
        :class:`profiling.collectives.Collective` an instruction (kind,
        the name a device trace shows, bytes, replica group, asynchronous,
        in a loop, runs a step, ``op_name``), read once from the AOT
        program's optimized HLO at :meth:`warmup`. Empty without an AOT
        program (the lazy ``jit`` path, an offload engine) and on one
        chip. No profiler needed::

            for c in engine.step_collectives():
                print(c.kind, c.name, c.bytes, c.runs, c.op_name)
        """
        return list(self._collectives)

    def _note_step_collectives(self) -> None:
        """Catalogue the AOT step's collectives, say them on one line and
        put their totals on ``train.step``. Off the step's path: called by
        :meth:`warmup` once the program is compiled."""
        t0 = time.perf_counter()
        try:
            found = tuple(coll.catalogue(self._train_step_aot.as_text()))
        except Exception as e:  # noqa: BLE001 — bookkeeping never kills warmup
            logger.warning(f"train step collectives not catalogued: {e}")
            found = ()
        self._collectives = found  # dslint: disable=races -- warmup-join synchronization: written on the warmup thread before _ensure_train_step_fn's join; a whole tuple swapped in, never mutated
        self._step_attrs = self._make_step_attrs()  # dslint: disable=races -- same: one dict swapped in whole; train_batch reads either the plan's or the catalogue's
        if self._zero_plan["chips"] > 1:
            log_dist(coll.describe(list(found), self._zero_plan)
                     + f" (read in {time.perf_counter() - t0:.2f} s)")

    def _forget_aot(self) -> None:
        """Drop the AOT program and what was read off it: the step that
        runs from here on is another program."""
        self._train_step_aot = None
        self._collectives = ()
        self._step_attrs = self._make_step_attrs()
        if self._comm_booked is not None:
            # the next step books its own; the dropped program's one-time
            # records stay in the CommsLogger as history, not as a delta
            from ..comm.comm import get_comms_logger

            for op, entry in self._comm_entries(get_comms_logger())[0].items():
                prev = self._comm_totals_prev.setdefault(op, {})
                for k, v in entry.items():
                    prev[k] = prev.get(k, 0.0) + v
            self._comm_booked = None

    @jax.named_scope("optimizer")   # names clip + update in a device trace
    def _update(self, params, opt_state, scaler_state, grads, scale, *,
                clip, fp16, dynamic, optimizer, nan_skip=False):
        """Unscale, clip, step — shared by fused and compat paths.

        ``nan_skip`` (divergence.nan_action == "skip") reuses the fp16
        overflow machinery for full-precision runs: a non-finite gradient
        tree keeps the old params/opt state ON DEVICE — the NaN guard
        compiles into the step and costs zero extra host syncs."""
        cfg = self.config
        # two names inside ``optimizer`` (docs/observability.md): what a
        # trace books under the whole scope is then told apart
        with jax.named_scope("norm"):
            if fp16:
                grads = jax.tree_util.tree_map(lambda g: g / scale, grads)
                finite = ls.grads_finite(grads)
            elif nan_skip:
                finite = ls.grads_finite(grads)
            else:
                finite = jnp.asarray(True)
            gnorm = global_norm(grads)
            if clip > 0:
                factor = jnp.minimum(1.0, clip / (gnorm + 1e-6))
                grads = jax.tree_util.tree_map(lambda g: g * factor, grads)
        with jax.named_scope("update"):
            updates, new_opt = optimizer.update(grads, opt_state, params)
            new_params = jax.tree_util.tree_map(lambda p, u: p + u, params, updates)
            # overflow / injected NaN => keep old params/opt state
            # (reference: skipped step)
            if fp16 or nan_skip:
                new_params = jax.tree_util.tree_map(
                    lambda n, o: jnp.where(finite, n, o), new_params, params)
                new_opt = jax.tree_util.tree_map(
                    lambda n, o: jnp.where(finite, n, o) if hasattr(n, "dtype") else n,
                    new_opt, opt_state)
            new_scaler = ls.update(
                scaler_state, finite, dynamic=dynamic,
                scale_window=cfg.fp16.loss_scale_window,
                min_scale=cfg.fp16.min_loss_scale,
                consecutive_hysteresis=cfg.fp16.consecutive_hysteresis,
                init_hysteresis=cfg.fp16.hysteresis)
        new_params = jax.lax.with_sharding_constraint(new_params, self.param_shardings)
        skipped = jnp.logical_not(finite)
        return new_params, new_opt, new_scaler, gnorm, skipped

    # ==================================================================
    # fused fast path
    def train_batch(self, batch: Any) -> Dict[str, Any]:
        """One full optimizer step over a global batch of
        ``train_batch_size`` samples (parity with PipelineEngine.train_batch
        semantics for the non-pipelined engine)."""
        with annotate("train.step", step=self.global_steps, k=1,
                      **self._step_attrs):
            return self._train_batch(batch)

    def _train_batch(self, batch: Any) -> Dict[str, Any]:
        """:meth:`train_batch` under its ``train.step`` span; the phases
        are spans of their own (docs/observability.md)."""
        with annotate("train.pre"):
            t_entry = time.perf_counter()
            for hook in self._step_hooks:
                hook(self, self.global_steps)
            fn = self._ensure_train_step_fn()
            self._note_batch_sig(batch)
            self.tput.start()
            if self._offload_device == "nvme":
                # disk -> host staging via the aio engine (reference
                # pipelined_optimizer_swapper), then host -> device
                self.opt_state = self._nvme_swapper.swap_in(self.opt_state_shardings)  # dslint: disable=races -- warmup-join synchronization: warmup only READS engine state, and train_batch joined it (via _ensure_train_step_fn above) before this write; offload engines additionally skip AOT warmup entirely
            elif self._offload_device == "cpu":
                # pinned host -> device upload (the reference offload engine's
                # per-step copy-in)
                self.opt_state = jax.device_put(self.opt_state, self.opt_state_shardings)
            self._params_to_device()
            if self.telemetry.wants_step_records and self._step_flops is None:
                # MFU numerator from HLO cost analysis of the lowered step,
                # measured BEFORE the donated call while the argument buffers
                # are alive (no XLA compile — see _measure_step_flops)
                self._measure_step_flops(batch)
        with annotate("train.dispatch"):
            out = None
            if self._train_step_aot is not None:
                # warmup's AOT executable: same program, dispatched without the
                # jit cache lookup. Any argument mismatch (new batch signature,
                # different sharding) falls back to the lazy jit path for good.
                try:
                    out = self._train_step_aot(
                        self.params, self.opt_state, self.scaler_state, self.rng,
                        batch)
                except Exception as e:  # noqa: BLE001 — aval check precedes execution
                    logger.warning(f"AOT train step no longer matches the inputs "
                                   f"({e}); using the jit path")
                    self._forget_aot()
            if out is None:
                out = fn(self.params, self.opt_state, self.scaler_state, self.rng,
                         batch)
        with annotate("train.post"):
            self.params, self.opt_state, self.scaler_state, self.rng, metrics = out  # dslint: disable=races -- warmup-join synchronization: the warmup thread's reads of params/opt_state/scaler/rng happen strictly before _ensure_train_step_fn's join at the top of train_batch; after the join, main is the only toucher
            self._params_to_offload()
            if self._offload_device == "nvme":
                self._nvme_swapper.swap_out(self.opt_state)
                self.opt_state = None
            elif self._offload_device == "cpu":
                self.opt_state = jax.device_put(self.opt_state, self._opt_host_shardings)
            # host ledger: everything from entry to here ran on the host while
            # the device was free to execute (dispatch is async) — the per-step
            # dispatch tax the async pipeline + train_steps(k) amortize
            t_dispatched = time.perf_counter()
            self.global_steps += 1
            self.micro_steps += self.gradient_accumulation_steps
            # sync_obj blocks the host until the step completes — honest per-step
            # timing, but it forbids dispatch-ahead pipelining. Only pay for it
            # when the user asked for timing (wall_clock_breakdown), when a
            # telemetry sink will fetch the metrics anyway (so the fetch lands
            # inside the timed region, not the untimed gap), or at the report
            # boundary. Telemetry off + monitor off => same sync points as seed.
            report_boundary = self.tput.will_report_next()
            want_stats = self.telemetry.wants_step_records
            sync = metrics["loss"] if (
                self.config.wall_clock_breakdown or want_stats
                or report_boundary) else None
            step_dt = self.tput.stop(sync_obj=sync, report_speed=True)
            host = None
            if want_stats:
                host = {"host_ms": (t_dispatched - t_entry) * 1e3,
                        "data_wait_ms": self._consume_data_wait_ms(),
                        "dispatch_gap_ms": ((t_entry - self._last_call_end_t) * 1e3
                                            if self._last_call_end_t is not None
                                            else None)}
            self._emit_step(metrics, wall_time_s=step_dt, log_step=report_boundary,
                            host=host)
            self._last_call_end_t = time.perf_counter()
            self._note_skipped(metrics["skipped"])
            self._last_loss = metrics["loss"]
            if self._ft_active or self.preemption_guard is not None:
                self._after_step(metrics)
            if self.config.memory_breakdown and report_boundary:
                # reference see_memory_usage at engine phase boundaries
                # (runtime/utils.py); boundary-only so it never adds a host
                # sync to the steady-state step
                from ..utils.memory import see_memory_usage

                see_memory_usage(f"step {self.global_steps}")
        return metrics

    # ==================================================================
    # compiled multi-step driver (docs/performance.md)
    def train_steps_eligible(self) -> Tuple[bool, Optional[str]]:
        """Whether ``train_steps`` may fuse k steps into one compiled
        program, with the blocking reason when it may not. Anything that
        must interleave HOST work between optimizer steps forces the
        per-step path."""
        if self._offload_device != "none" or self._param_offload_device != "none":
            return False, "zero-offload swaps state around every step"
        if self._step_hooks:
            return False, "per-step hooks registered"
        if self.preemption_guard is not None:
            return False, "preemption-latch polling needs per-step boundaries"
        if self._divergence is not None:
            return False, "host-side divergence guard fetches the loss each step"
        if self._pipelined:
            return False, "pipelined engine schedules micro-batches itself"
        return True, None

    def train_steps(self, batches: Union[int, Sequence[Any]]) -> Dict[str, Any]:
        """Run k optimizer steps as ONE jitted, donated ``lax.scan`` —
        dispatch cost amortized k×, zero host work between the inner
        steps. Bit-exact with k calls to :meth:`train_batch` (the scan
        body IS the single-step program).

        ``batches`` is a sequence of k equal-shaped global batches (e.g.
        pulled from a prefetching loader), or an int k to pull them from
        the bound dataloader (cycling epochs like ``RepeatingLoader``).

        When the engine is ineligible (:meth:`train_steps_eligible` —
        offload, per-step hooks, preemption polling, host divergence
        guards), falls back to per-step ``train_batch`` calls with the
        reason logged once. Returns the last step's metrics plus
        ``losses``, the per-step loss vector."""
        if isinstance(batches, int):
            k, batches = int(batches), None  # pulled below, path-dependent
        else:
            batches = list(batches)
            k = len(batches)
        if k <= 0:
            raise ValueError("train_steps: no batches")
        eligible, reason = self.train_steps_eligible()
        if not eligible or k == 1:
            if not eligible and reason not in self._steps_fallback_logged:
                self._steps_fallback_logged.add(reason)
                log_dist(f"train_steps: fused multi-step path ineligible "
                         f"({reason}); running {k} per-step train_batch calls")
            # pull lazily, one batch per step: the ineligible reasons are
            # exactly the ones that can checkpoint/rollback BETWEEN the
            # inner steps (preemption drain, divergence), and the loader
            # position those paths capture must reflect actual consumption,
            # not a k-batch read-ahead
            losses = []
            metrics: Dict[str, Any] = {}
            for i in range(k):
                if batches is not None:
                    b = batches[i]
                else:
                    pulled = self._pull_batches(1)
                    if not pulled:  # loader is empty
                        break
                    b = pulled[0]
                metrics = self.train_batch(b)
                losses.append(metrics["loss"])
            if not losses:
                raise ValueError("train_steps: no batches")
            out = dict(metrics)
            out["losses"] = jnp.stack([jnp.asarray(l) for l in losses])
            return out
        if batches is None:
            batches = self._pull_batches(k)
            k = len(batches)
            if k == 0:
                raise ValueError("train_steps: no batches")
            if k == 1:  # loader could only supply one batch
                metrics = self.train_batch(batches[0])
                out = dict(metrics)
                out["losses"] = jnp.stack([jnp.asarray(metrics["loss"])])
                return out

        with annotate("train.step", step=self.global_steps, k=k,
                      **self._step_attrs):
            return self._train_steps_fused(batches, k)

    def _train_steps_fused(self, batches: List[Any], k: int
                           ) -> Dict[str, Any]:
        """The fused path of :meth:`train_steps` under its one
        ``train.step`` span, in :meth:`_train_batch`'s phases."""
        with annotate("train.pre"):
            t_entry = time.perf_counter()
            gap_ms = ((t_entry - self._last_call_end_t) * 1e3
                      if self._last_call_end_t is not None else None)
            self._ensure_train_step_fn()  # also builds _train_step_raw
            fn = self._train_steps_fns.get(k)
            if fn is None:
                fn = self._build_train_steps(k)
                self._train_steps_fns[k] = fn
            # the k batches enter the program as a tuple and are stacked INTO
            # the scan's leading dim inside the compiled program — stacking on
            # the host side would pay one dispatch per leaf per block, exactly
            # the tax this driver exists to amortize
            batch_tuple = tuple(batches)
            self._note_batch_sig(batch_tuple, program=f"train_steps_{k}")
            want_stats = self.telemetry.wants_step_records
            if want_stats and self._step_flops is None:
                self._measure_step_flops(batches[0])
            prev_steps = self.global_steps
            self.tput.start()
        with annotate("train.dispatch"):
            self.params, self.opt_state, self.scaler_state, self.rng, ms = fn(
                self.params, self.opt_state, self.scaler_state, self.rng,
                batch_tuple)
        with annotate("train.post"):
            t_dispatched = time.perf_counter()
            self.global_steps += k
            self.micro_steps += k * self.gradient_accumulation_steps
            metrics = {"loss": ms["loss"][-1], "grad_norm": ms["grad_norm"][-1],
                       "loss_scale": ms["loss_scale"][-1],
                       "skipped": ms["skipped"][-1]}
            sync = metrics["loss"] if (self.config.wall_clock_breakdown
                                       or want_stats) else None
            block_dt = self.tput.stop(sync_obj=sync, report_speed=False)
            # keep the throughput aggregates honest: stop() booked one step of
            # batch_size; this block ran k of them
            self.tput.step_count = self.global_steps
            self.tput.total_samples += self.train_batch_size * (k - 1)
            host = None
            if want_stats:
                host = {"host_ms": (t_dispatched - t_entry) * 1e3,
                        "data_wait_ms": self._consume_data_wait_ms(),
                        "dispatch_gap_ms": gap_ms}
            self._emit_step(metrics, wall_time_s=block_dt, log_step=False,
                            host=host, n_steps=k)
            self._last_call_end_t = time.perf_counter()
            self._note_skipped(ms["skipped"].sum())
            self._last_loss = metrics["loss"]
            # periodic auto-save: a block can cross (or land on) a save
            # boundary; preemption/divergence never reach here (ineligible)
            iv = self.config.checkpoint.save_interval
            if (self._ckpt_save_dir and iv > 0
                    and self.global_steps // iv != prev_steps // iv):
                self.save_checkpoint(self._ckpt_save_dir)
        out = dict(metrics)
        out["losses"] = ms["loss"]
        return out

    def _build_train_steps(self, k: int):
        raw = self._train_step_raw

        def k_step(params, opt_state, scaler_state, rng, batch_tuple):
            self._trace_counts[f"train_steps_{k}"] += 1  # dslint: disable=trace-hygiene -- deliberate trace-time counter (recompile telemetry)
            stacked = jax.tree_util.tree_map(
                lambda *xs: jnp.stack(xs), *batch_tuple)

            def body(carry, mb):
                p, o, s, r = carry
                p, o, s, r, m = raw(p, o, s, r, mb)
                return (p, o, s, r), m

            (p, o, s, r), ms = jax.lax.scan(
                body, (params, opt_state, scaler_state, rng), stacked)
            return p, o, s, r, ms

        donate = (0, 1, 2) if self._donate else ()
        return jax.jit(k_step, donate_argnums=donate,
                       out_shardings=self._step_out_shardings())

    def _pull_batches(self, k: int) -> List[Any]:
        """k batches from the bound dataloader via a persistent iterator,
        advancing epochs like RepeatingLoader when one ends mid-pull."""
        src = self._dataloader
        if src is None:
            raise ValueError(
                "train_steps(k) needs a bound dataloader (bind_dataloader) "
                "or an explicit sequence of batches")
        if self._loader_iter is None or self._loader_iter_src is not src:
            self._loader_iter = iter(src)
            self._loader_iter_src = src
        out: List[Any] = []
        fresh_restarts = 0
        while len(out) < k:
            try:
                out.append(next(self._loader_iter))
                fresh_restarts = 0
            except StopIteration:
                if fresh_restarts:  # empty loader — don't spin forever
                    break
                fresh_restarts += 1
                if hasattr(src, "set_epoch"):
                    src.set_epoch(getattr(src, "epoch", 0) + 1)
                self._loader_iter = iter(src)
        return out

    # ==================================================================
    # trace accounting (docs/performance.md#recompile-guard)
    def trace_count(self, name: str = "train_step") -> int:
        """Times the named program body was traced (each trace constructs
        a new program and, modulo the compilation cache, a new XLA
        compile). 1 at steady state; >1 means shape/type churn retraced
        it. Names: ``train_step``, ``eval_step``, ``train_steps_<k>``."""
        return int(self._trace_counts.get(name, 0))

    def train_step_cache_size(self) -> int:
        """Entry count of the fused train step's pjit call cache. NOTE:
        fastpath entries key on argument committed-ness too, so this can
        exceed :meth:`trace_count` by one without any recompile; use
        trace_count for the one-compile-per-program contract."""
        return _jit_cache_size(self._train_step_fn)

    def eval_step_cache_size(self) -> int:
        return _jit_cache_size(self._eval_step_fn)

    def overlap_report(self, batch: Any, repeats: int = 3,
                       **kwargs) -> Dict[str, Any]:
        """Measured (not modeled) comm-overlap accounting for the staged
        ZeRO-3 schedule (profiling/overlap.py): drives this engine's
        block program eagerly with per-phase fenced timing, joins wire
        bytes from the CommsLogger ledger, and compares measured comm
        exposure against ``modeled_exposure`` under a calibrated
        bandwidth. Requires the staged path (model exposes
        ``zero3_blocks`` and the mesh factors a data-parallel axis);
        never touches the jitted step programs."""
        if self._staged_mode is None:
            raise ValueError(
                "overlap_report needs the staged ZeRO-3 path (stage 3, a "
                "zero3_blocks model, comm_compression.overlap != 'off' "
                "and a >1 data-parallel mesh axis)")
        from ..profiling.overlap import overlap_report

        return overlap_report(self, batch, repeats=repeats, **kwargs)

    def _note_batch_sig(self, batch: Any, program: str = "train_step") -> None:
        """Recompile guard: a batch signature (leaf shapes/dtypes) this
        program has not seen misses its jit cache and compiles a whole new
        XLA program. Count it (``train/recompiles``) and warn once with
        the remedy. Signatures are per program — the k-step driver and the
        single-step program legitimately see different shapes."""
        sig = _batch_signature(batch)
        seen = self._seen_batch_sigs.setdefault(program, set())
        if sig in seen:
            return
        first = not seen
        seen.add(sig)
        if first:
            return
        from ..telemetry.registry import get_registry

        get_registry().counter("train/recompiles").inc()
        if self.config.compile.warn_on_recompile and not self._recompile_warned:
            self._recompile_warned = True
            logger.warning(
                f"train step RETRACED: new batch signature {sig} missed the "
                f"jit cache (curriculum_fn changing seq length? ragged last "
                f"batch?). Every distinct shape compiles a new XLA program — "
                f"pad batches to a small fixed set of bucket shapes "
                f"(docs/performance.md#recompile-guard). Further recompiles "
                f"are counted in train/recompiles without this warning.")

    def _consume_data_wait_ms(self) -> Optional[float]:
        """Delta of the bound loader's cumulative data-wait ledger since
        the last step record (host time the consumer spent waiting for /
        producing batches)."""
        dl = self._dataloader
        cur = getattr(dl, "data_wait_s", None) if dl is not None else None
        if cur is None:
            return None
        d = float(cur) - self._data_wait_prev_s
        self._data_wait_prev_s = float(cur)
        return d * 1e3 if d >= 0 else None

    # ==================================================================
    # fault tolerance (docs/fault_tolerance.md)
    @property
    def should_stop(self) -> bool:
        """True once a preemption was handled (emergency checkpoint saved,
        telemetry flushed) or a guard halted the run — the training loop's
        drain signal."""
        return self._stop_reason is not None

    @property
    def stop_reason(self) -> Optional[str]:
        return self._stop_reason

    def attach_preemption_guard(self, guard: Optional[Any] = None):
        """Wire a PreemptionGuard into the step path: when its signal
        latches, the NEXT step boundary saves an emergency checkpoint
        (into ``checkpoint.save_dir``), flushes telemetry, and sets
        :attr:`should_stop`. Pass an entered guard, or None to construct
        one (caller still manages its context)."""
        if guard is None:
            from ..resilience.preemption import PreemptionGuard

            guard = PreemptionGuard()
        self.preemption_guard = guard
        return guard

    def bind_dataloader(self, loader: Any) -> None:
        """Checkpoints now carry this loader's position (epoch + batch
        index) in client_state, and load_checkpoint restores it — resume
        replays the exact remaining data order. Bind before iterating."""
        self._dataloader = loader
        self._loader_iter = None
        self._loader_iter_src = None
        self._data_wait_prev_s = float(getattr(loader, "data_wait_s", 0.0) or 0.0)

    def _after_step(self, metrics: Dict[str, Any]) -> None:
        """Step-boundary fault-tolerance checks. Never called when every
        knob is off (the zero-extra-host-syncs contract)."""
        step = self.global_steps
        if step > self._ft_high_step:
            # progress past the previous high-water step: any earlier
            # divergence was transient, the rollback did its job
            self._ft_high_step = step
            self._rollback_streak = 0
        if self._divergence is not None:
            # the one host sync the divergence guard costs, documented
            verdict = self._divergence.observe(step, float(metrics["loss"]))
            if verdict is not None:
                kind, action = verdict
                from ..telemetry.registry import get_registry

                get_registry().counter(f"resilience/divergence/{kind}").inc()
                if action == "halt":
                    from ..resilience.divergence import DivergenceError

                    self._stop_reason = f"divergence:{kind}"
                    raise DivergenceError(
                        f"{kind} divergence at step {step} (action=halt)")
                if action == "rollback":
                    self._rollback_streak += 1
                    limit = self.config.resilience.divergence.max_rollbacks
                    if self._rollback_streak > limit:
                        # bit-exact resume replays a deterministic fault
                        # identically — rolling back again would loop
                        # forever; escalate to halt
                        from ..resilience.divergence import DivergenceError

                        self._stop_reason = f"divergence:{kind}:rollback-loop"
                        raise DivergenceError(
                            f"{kind} divergence at step {step} persisted "
                            f"through {limit} rollbacks (deterministic "
                            f"fault?) — halting")
                    self._rollback(kind)
                    return  # don't checkpoint the rolled-back state twice
                # "warn": the guard already logged and counted
        if (self.preemption_guard is not None
                and self.preemption_guard.should_stop
                and self._stop_reason is None):
            self._emergency_checkpoint()
            self._stop_reason = "preempted"
            return
        if (self._ckpt_save_dir and self.config.checkpoint.save_interval > 0
                and step % self.config.checkpoint.save_interval == 0):
            self.save_checkpoint(self._ckpt_save_dir)

    def _rollback(self, kind: str) -> None:
        from ..resilience.counters import record_rollback
        from ..resilience.divergence import DivergenceError

        if not self._ckpt_save_dir:
            raise DivergenceError(
                f"{kind} divergence: rollback requested but "
                f"checkpoint.save_dir is not configured")
        bad_step = self.global_steps
        client = self.load_checkpoint(self._ckpt_save_dir, auto=True)
        if client is None:
            raise DivergenceError(
                f"{kind} divergence at step {bad_step}: no valid "
                f"checkpoint to roll back to")
        self._divergence.reset()
        record_rollback()
        logger.warning(f"divergence ({kind}) at step {bad_step}: rolled "
                       f"back to step {self.global_steps}")

    def _emergency_checkpoint(self) -> None:
        """Preemption drain: checkpoint (if a save_dir is configured) and
        flush every telemetry sink before the SIGKILL deadline."""
        from ..resilience.counters import record_emergency_save

        if self._ckpt_save_dir:
            self.save_checkpoint(self._ckpt_save_dir)
            record_emergency_save()
            log_dist(f"emergency checkpoint at step {self.global_steps} "
                     f"(preemption drain)")
        else:
            logger.warning("preempted with no checkpoint.save_dir — "
                           "draining without an emergency checkpoint")
        self.telemetry.close()

    def register_param_transform(self, fn: Optional[Callable[[Any], Any]]) -> None:
        """Install/replace a traced params transform applied at the
        compute-cast boundary (compression QAT, pruning masks). Invalidates
        compiled step functions — call sparingly (schedule boundaries)."""
        if self._warmup_thread is not None:
            # an in-flight AOT warmup would re-install a pre-transform
            # executable AFTER the reset below; let it land first
            self._warmup_thread.join()
            self._warmup_thread = None
        self._param_transform = fn
        self._train_step_fn = None
        self._train_step_raw = None
        self._train_steps_fns = {}
        self._forget_aot()
        self._micro_grad_fn = None
        self._acc_add_fn = None
        self._eval_step_fn = None

    def register_step_hook(self, fn: Callable[["TrainEngine", int], None]) -> None:
        """fn(engine, global_step) before each train_batch (compression
        schedule gating, reference scheduler.py analog)."""
        self._step_hooks.append(fn)
    def _params_to_device(self) -> None:
        if self._param_offload_device == "nvme":
            if self.params is None:
                self.params = self._param_nvme_swapper.swap_in(self.param_shardings)
        elif self._param_offload_device == "cpu":
            self.params = jax.device_put(self.params, self.param_shardings)

    def _params_to_offload(self) -> None:
        if self._param_offload_device == "nvme":
            self._param_nvme_swapper.swap_out(self.params)
            self.params = None
        elif self._param_offload_device == "cpu":
            self.params = jax.device_put(self.params, self._param_host_shardings)

    # ==================================================================
    # DeepSpeed-compatible micro-step path
    def forward(self, batch: Any) -> Any:
        """Compute loss for a microbatch (no grads). Provided for API parity;
        ``backward`` recomputes through ``jax.grad`` (forward+backward fuse
        on TPU, so the split exists only at the Python API level)."""
        self._reject_if_pipelined()
        self._params_to_device()
        # no phase timer here: forward() is an eval op in this engine
        # (backward() recomputes through jax.grad), and it is routinely
        # called for validation between optimizer steps — accumulating it
        # into the next step's phase times would corrupt wall_time_s and
        # trip false stalls
        loss, _aux = self._jitted_eval()(self.params, batch, self._next_rng())
        self._last_loss = loss
        return loss

    def backward(self, batch: Any) -> Any:
        """Accumulate gradient shards for one microbatch (parity with
        engine.backward engine.py:1902 + ZeRO IPG accumulation)."""
        self._reject_if_pipelined()
        self._params_to_device()
        self._note_batch_shape(batch, scale=self.gradient_accumulation_steps)
        if self._micro_grad_fn is None:
            self._micro_grad_fn = jax.jit(
                lambda p, b, r, s: self._loss_and_grads(p, b, r, s)[:2],
                out_shardings=(self.grad_shardings, None))
        scale = self.scaler_state.scale if self.config.fp16.enabled else jnp.ones([], jnp.float32)
        want_stats = self.telemetry.wants_step_records
        if want_stats:
            self.timers("compat/backward").start()
        grads, loss = self._micro_grad_fn(self.params, batch, self._next_rng(), scale)
        if want_stats:
            self.timers("compat/backward").stop(sync_obj=loss)
        if self._acc_grads is None:
            self._acc_grads = grads
        else:
            # cache the jitted accumulator: a fresh jax.jit(lambda ...)
            # per microbatch is a new wrapper with an empty trace cache,
            # i.e. one recompile per accumulation step (dslint
            # recompile-hazard)
            if self._acc_add_fn is None:
                self._acc_add_fn = jax.jit(
                    lambda a, g: jax.tree_util.tree_map(jnp.add, a, g),
                    donate_argnums=(0,))
            self._acc_grads = self._acc_add_fn(self._acc_grads, grads)
        self.micro_steps += 1
        self._last_loss = loss
        return loss

    def _reject_if_pipelined(self) -> None:
        if self._pipelined:
            # reference parity: PipelineEngine only supports train_batch()
            # (pipe/engine.py — forward/backward are schedule instructions,
            # not user API)
            raise RuntimeError("pipelined engine: use train_batch(), not "
                               "forward()/backward()/step()")

    def step(self) -> None:
        """Apply the update at a gradient-accumulation boundary (parity with
        engine.step engine.py:2100: no-op off-boundary)."""
        self._reject_if_pipelined()
        if self.micro_steps % self.gradient_accumulation_steps != 0:
            return
        if self._acc_grads is None:
            logger.warning("step() called with no accumulated gradients")
            return
        if self._apply_update_fn is None:
            optimizer = self.optimizer
            cfg = self.config

            def apply_update(params, opt_state, scaler_state, grads):
                inv = 1.0 / cfg.gradient_accumulation_steps
                grads = jax.tree_util.tree_map(lambda g: g * inv, grads)
                scale = scaler_state.scale if cfg.fp16.enabled else jnp.ones([], jnp.float32)
                return self._update(params, opt_state, scaler_state, grads, scale,
                                    clip=cfg.gradient_clipping, fp16=cfg.fp16.enabled,
                                    dynamic=cfg.fp16.enabled and cfg.fp16.dynamic_loss_scale,
                                    optimizer=optimizer,
                                    nan_skip=self._nan_skip_traced)

            donate = (0, 1, 2, 3) if self._donate else ()
            self._apply_update_fn = jax.jit(apply_update, donate_argnums=donate)

        self._params_to_device()
        want_stats = self.telemetry.wants_step_records
        if want_stats:
            self.timers("compat/optimizer").start()
        self.params, self.opt_state, self.scaler_state, gnorm, skipped = self._apply_update_fn(
            self.params, self.opt_state, self.scaler_state, self._acc_grads)
        if want_stats:
            self.timers("compat/optimizer").stop(sync_obj=gnorm)
        self._acc_grads = None
        self._params_to_offload()
        self.global_steps += 1
        # the compat fwd/bwd/step path drives global_steps without the
        # throughput timer; keep the two counters aligned so a later
        # train_batch's report boundary lands on steps_per_print multiples
        self.tput.step_count = self.global_steps
        self._note_skipped(skipped)
        phase_times = None
        wall = None
        if want_stats:
            # phase wall times accumulated since the last boundary. Only
            # backward/optimizer: forward() is an eval op here (see above),
            # so forward_s stays null on both engine paths
            phase_times = {}
            for phase in ("backward", "optimizer"):
                t = self.timers.timers.get(f"compat/{phase}")
                if t is not None and t.count:
                    phase_times[phase] = t.elapsed_total
                    t.reset()
            wall = sum(phase_times.values()) or None
        self._emit_step({"loss": self._last_loss, "grad_norm": gnorm,
                         "loss_scale": self.scaler_state.scale, "skipped": skipped},
                        wall_time_s=wall, phase_times=phase_times)
        if self._ft_active or self.preemption_guard is not None:
            # the compat path is an optimizer-step boundary too: divergence
            # guards, preemption drain and periodic auto-save all apply
            self._after_step({"loss": self._last_loss, "grad_norm": gnorm,
                              "skipped": skipped})

    # ==================================================================
    def eval_batch(self, batch: Any) -> Any:
        self._params_to_device()
        loss, aux = self._jitted_eval()(self.params, batch, self._next_rng())
        return loss

    def _jitted_eval(self):
        if self._eval_step_fn is None:
            def eval_step(params, batch, rng):
                self._trace_counts["eval_step"] += 1  # dslint: disable=trace-hygiene -- deliberate trace-time counter (recompile telemetry)
                return self.loss_fn(self._compute_copy(params), batch, rng)

            self._eval_step_fn = jax.jit(eval_step)
        return self._eval_step_fn

    def _next_rng(self):
        self.rng, sub = jax.random.split(self.rng)
        return sub

    def _emit_step(self, metrics: Dict[str, Any],
                   wall_time_s: Optional[float] = None,
                   log_step: Optional[bool] = None,
                   phase_times: Optional[Dict[str, float]] = None,
                   host: Optional[Dict[str, Optional[float]]] = None,
                   n_steps: int = 1) -> None:
        """Step-boundary observability: the human log line plus — when any
        telemetry sink is configured (JSONL/Prometheus/monitor) — one
        StepStats span record through the unified pipeline. Replaces the
        seed's ad-hoc ``_write_monitor``: MonitorMaster now receives its
        Train/* events as one telemetry sink among several."""
        # keyed off the throughput timer's boundary when the caller knows it
        # (train_batch) so the blocking float() fetches below never land
        # mid-window on an unsynced step; global_steps fallback for the
        # compat step() path
        if log_step is None:
            log_step = self.global_steps % self.config.steps_per_print == 0
        if log_step:
            log_dist(
                f"step={self.global_steps} loss={float(metrics['loss']):.4f} "
                f"lr={self.get_lr():.3e} grad_norm={float(metrics['grad_norm']):.3f}"
                + (f" loss_scale={float(metrics['loss_scale']):.0f}" if self.config.fp16.enabled else "")
            )
        if not self.telemetry.wants_step_records:
            return
        self.telemetry.record_step(
            self._build_step_stats(metrics, wall_time_s, phase_times,
                                   host=host, n_steps=n_steps))

    def _build_step_stats(self, metrics: Dict[str, Any],
                          wall_time_s: Optional[float],
                          phase_times: Optional[Dict[str, float]] = None,
                          host: Optional[Dict[str, Optional[float]]] = None,
                          n_steps: int = 1):
        from ..telemetry import StepStats

        dt = float(wall_time_s) if wall_time_s else 0.0
        tokens = self._count_batch_tokens() * n_steps
        comm, comm_s = self._comm_step_delta()
        if self.telemetry.enabled:
            from ..utils.memory import device_memory_stats, host_rss_gb

            memory = device_memory_stats()
            rss = host_rss_gb()
            if rss is not None:
                memory["host_rss_gb"] = rss
        else:  # monitor-only: reuse the report-boundary sample, if any
            memory = dict(self.tput.last_memory)
        mfu = 0.0
        if dt > 0 and self._step_flops and self._get_peak_flops():
            mfu = self._step_flops * n_steps / dt / self._get_peak_flops()
        host = host or {}
        # distributed-tracing join: when a tracer is installed, the step
        # lands as one "train/step" span and the record carries its ids
        # (telemetry/tracing.py). Off by default: one attribute check.
        trace_id = span_id = None
        from ..telemetry.tracing import get_tracer

        tracer = get_tracer()
        if tracer.enabled:
            from ..resilience.clock import get_clock

            t_end = get_clock().time()
            sp = tracer.span_complete(
                "train/step", t_end - dt, t_end, track="train",
                step=self.global_steps, n_steps=n_steps)
            trace_id, span_id = sp.trace_id, sp.span_id
        quant_err = None
        if metrics.get("quant_rel_err") is not None:
            # one extra host fetch, paid only when comm_compression.
            # error_stats is on (docs/communication.md#error-bounds)
            quant_err = float(metrics["quant_rel_err"])
            from ..telemetry.registry import get_registry

            get_registry().histogram("comm/quant_rel_err").observe(quant_err)
        return StepStats(
            step=self.global_steps,
            n_steps=n_steps,
            wall_time_s=dt,
            tokens_per_s=tokens / dt if dt > 0 else 0.0,
            samples_per_s=(self.train_batch_size * n_steps / dt
                           if dt > 0 else 0.0),
            host_ms=host.get("host_ms"),
            data_wait_ms=host.get("data_wait_ms"),
            dispatch_gap_ms=host.get("dispatch_gap_ms"),
            mfu=mfu,
            loss=float(metrics["loss"]) if metrics.get("loss") is not None else None,
            grad_norm=float(metrics["grad_norm"]) if metrics.get("grad_norm") is not None else None,
            loss_scale=float(metrics["loss_scale"]) if self.config.fp16.enabled else None,
            lr=self.get_lr(),
            skipped=bool(metrics["skipped"]) if metrics.get("skipped") is not None else None,
            forward_s=(phase_times or {}).get("forward"),
            backward_s=(phase_times or {}).get("backward"),
            optimizer_s=(phase_times or {}).get("optimizer"),
            comm_s=comm_s,
            comm=comm,
            quant_rel_err=quant_err,
            memory=memory,
            trace_id=trace_id,
            span_id=span_id,
        )

    def _count_batch_tokens(self) -> int:
        """Tokens per optimizer step: sequence models carry [batch, seq]
        input_ids; anything else counts samples (tokens == samples for
        non-sequence workloads)."""
        return (self._tokens_per_batch if self._tokens_per_batch is not None
                else self.config.train_batch_size)

    def _note_batch_shape(self, batch: Any, scale: int = 1) -> None:
        """Latch tokens-per-step from the first observed batch. ``scale``
        lifts a micro-batch (compat path) to the full accumulation step."""
        if self._tokens_per_batch is not None:
            return
        if isinstance(batch, dict) and "input_ids" in batch:
            self._tokens_per_batch = int(
                np.prod(batch["input_ids"].shape)) * scale
        else:
            self._tokens_per_batch = self.config.train_batch_size

    #: a catalogue's kinds under the comm facade's op names
    _COMM_OPS = {"all-gather": "all_gather", "reduce-scatter": "reduce_scatter",
                 "all-reduce": "all_reduce", "all-to-all": "all_to_all",
                 "collective-permute": "ppermute",
                 "collective-broadcast": "broadcast"}

    def _axis_of(self, members: Tuple[int, ...]) -> Optional[str]:
        """The one mesh axis a replica group runs along, None where it
        spans several (then no one axis replays it): a partition's id is
        its place in the mesh's devices, in order."""
        mesh = self.topo.mesh
        if not members or max(members) >= mesh.devices.size:
            return None     # not this mesh's partitions: nothing to replay
        at = np.unravel_index(list(members), mesh.devices.shape)
        along = [name for name, row in zip(mesh.axis_names, at)
                 if len(set(row.tolist())) > 1]
        return along[0] if len(along) == 1 else None

    def _book_step_comm(self, log) -> None:
        """Fold the compiled step's traffic once into ``_comm_booked``,
        {op: {payload bytes: runs a step}}, and ``_comm_totals``, {op:
        (calls, payload bytes) a step}; each (op, payload) is recorded with
        the CommsLogger ONCE, so that measure_comm_latencies can replay it
        (along its group's axis, where that is one axis) and log_summary
        shows one row a size; the exported comm/<op> counters get the whole
        first step."""
        from ..telemetry.registry import get_registry

        booked: Dict[str, Dict[int, int]] = {}
        where: Dict[Tuple[str, int], Tuple[int, Optional[str]]] = {}
        if self._collectives:
            for c in self._collectives:
                if c.bytes:
                    op = self._COMM_OPS[c.kind]
                    sizes = booked.setdefault(op, {})
                    sizes[c.bytes] = sizes.get(c.bytes, 0) + c.runs
                    where[op, c.bytes] = (c.group, self._axis_of(c.members))
        else:
            op = "reduce_scatter" if self.config.zero.stage >= 1 else "all_reduce"
            size = self._zero_plan["grad_bytes"]
            booked[op] = {size: 1}
            where[op, size] = (self.topo.data_parallel_size, "data")
        reg = get_registry()
        totals = {}
        for op, sizes in booked.items():
            for size in sizes:
                log.append(op, size, 0.0, *where[op, size])
            calls = sum(sizes.values())
            total = sum(b * n for b, n in sizes.items())
            reg.counter(f"comm/{op}/calls").inc(calls - len(sizes))
            reg.counter(f"comm/{op}/bytes").inc(total - sum(sizes))
            reg.counter(f"comm/{op}/wire_bytes").inc(total - sum(sizes))
            totals[op] = (calls, total)
        self._comm_booked, self._comm_totals = booked, totals

    def _step_comm(self) -> Tuple[Dict[str, Dict[str, float]],
                                  Dict[str, Dict[str, float]]]:
        """({op: what the CommsLogger holds of it from the one-time
        records}, {op: this step's entry}) for the traffic GSPMD places
        inside the compiled step, where the facade's wrappers cannot see
        it. With a catalogue of the compiled step
        (:meth:`step_collectives`) it is what the program holds: every
        kind, parameter gathers too, each run with its payload (``bytes``
        and ``wire_bytes`` both: the ledger's wire is what is left of a
        payload after compression, and nothing here is compressed; what a
        chip sends by the ring's count is ``train.step``'s ``sent_bytes``).
        Without one (the lazy jit path) the gradients' reduction is guessed
        from the grad shardings: replicated grads (stage 0) reduce with an
        all-reduce of the float32 tree (``zero_plan``'s ``grad_bytes``);
        sharded grads (stage >= 1) with a reduce-scatter. What the first
        step booked stands until the step is another program
        (:meth:`_forget_aot`); time_s comes from the backfilled records
        when available."""
        dp = self.topo.data_parallel_size
        if dp <= 1 or not self._zero_plan["grad_bytes"]:
            return {}, {}
        if self._qgz or self._staged_mode is not None:
            # the facade paths record their own (quantized, wire-accurate)
            # ledger entries at trace time — a dense booking on top would
            # double-count traffic that never happens
            return {}, {}
        from ..comm.comm import get_comms_logger

        log = get_comms_logger()
        if not log.enabled:
            return {}, {}
        if self._comm_booked is None:
            self._book_step_comm(log)
        else:
            # the one-time records fed the registry the first step; keep
            # the exported comm/<op> counters tracking the per-step traffic
            from ..telemetry.registry import get_registry

            reg = get_registry()
            for op, (calls, total) in self._comm_totals.items():
                reg.counter(f"comm/{op}/calls").inc(calls)
                reg.counter(f"comm/{op}/bytes").inc(total)
                reg.counter(f"comm/{op}/wire_bytes").inc(total)
        return self._comm_entries(log)

    def _comm_entries(self, log):
        """:meth:`_step_comm`'s two dicts of what is booked, as the
        CommsLogger's records read now."""
        once: Dict[str, Dict[str, float]] = {}
        step: Dict[str, Dict[str, float]] = {}
        for op, sizes in self._comm_booked.items():
            records = log.records.get(op, {})
            took = {size: max((records.get(size) or [0.0])[0], 0.0)
                    for size in sizes}
            calls, total = self._comm_totals[op]
            once[op] = {"count": float(len(sizes)), "bytes": float(sum(sizes)),
                        "wire_bytes": float(sum(sizes)),
                        "time_s": sum(took.values())}
            step[op] = {"count": float(calls), "bytes": float(total),
                        "wire_bytes": float(total),
                        "time_s": sum(took[b] * n for b, n in sizes.items())}
        return once, step

    def _comm_step_delta(self):
        """Per-step comm breakdown: delta of the CommsLogger's cumulative
        totals since the last emitted step. Counts/bytes are trace-time
        facts; time_s becomes real once measure_comm_latencies backfills."""
        from ..comm.comm import get_comms_logger

        # the step's own collectives run EVERY step, but their CommsLogger
        # records are one-time appends (so measure_comm_latencies can
        # replay them). Subtract those records from the cumulative stream
        # — including their possibly-backfilled durations — and re-inject
        # the entries per step below; otherwise the step after a backfill
        # would count the measured latency twice (once via the snapshot
        # jump, once via the merge).
        once, implied = self._step_comm()
        totals = get_comms_logger().snapshot_totals()
        for op, entry in once.items():
            if op in totals:
                cur = totals[op]
                for k in ("count", "bytes", "wire_bytes", "time_s"):
                    cur[k] = max(0.0, cur.get(k, 0.0) - entry.get(k, 0.0))
        delta: Dict[str, Dict[str, float]] = {}
        comm_s = 0.0
        for op, cur in totals.items():
            prev = self._comm_totals_prev.get(op, {})
            d = {k: cur[k] - prev.get(k, 0.0) for k in cur}
            if d["count"] <= 0 and d["time_s"]:
                # duration moved with no new records: that's a
                # measure_comm_latencies backfill rewriting history, not
                # traffic on this step — don't spike this step's comm_s
                d["time_s"] = 0.0
            if any(v for v in d.values()):
                delta[op] = d
                comm_s += d["time_s"]
        self._comm_totals_prev = totals
        for op, entry in implied.items():
            if op in delta:
                for k in entry:
                    delta[op][k] += entry[k]
            else:
                delta[op] = dict(entry)
            comm_s += entry["time_s"]
        return delta, (comm_s if comm_s > 0 else None)

    def _measure_step_flops(self, batch: Any) -> None:
        """One-time HLO cost analysis of the fused train step (the flops
        profiler's program counting applied to the real step). Analysis
        runs on the LOWERED module, not a compiled one — ``.compile()``
        here would XLA-compile the step a second time (the AOT executable
        does not populate the jit call cache), doubling time-to-first-step
        for large models. Pre-optimization flops differ negligibly for the
        matmul-dominated MFU numerator."""
        self._note_batch_shape(batch)
        try:
            cost = self._train_step_fn.lower(
                self.params, self.opt_state, self.scaler_state, self.rng,
                batch).cost_analysis()
            if isinstance(cost, list):  # some versions return [dict]
                cost = cost[0] if cost else {}
            f = (cost or {}).get("flops")
            self._step_flops = float(f) if f and f > 0 else 0.0
        except Exception as e:  # backend without cost analysis
            logger.debug(f"train-step cost analysis unavailable: {e}")
            self._step_flops = 0.0

    def _get_peak_flops(self) -> float:
        if self._peak_flops is None:
            from ..profiling.flops_profiler import mesh_peak_flops

            # the mesh this engine runs on — one process may hold a sub-mesh
            self._peak_flops = mesh_peak_flops(self.topo.world_size)
        return self._peak_flops

    def close(self) -> None:
        """Engine shutdown: flush + close every telemetry sink (including
        the MonitorMaster adapter — the TensorBoard writer buffers events
        and loses the run tail if never closed). Idempotent."""
        if self._closed:
            return
        self._closed = True
        if self._warmup_thread is not None:
            self._warmup_thread.join()
            self._warmup_thread = None
        self.telemetry.close()
        from ..telemetry import get_telemetry, set_telemetry

        if get_telemetry() is self.telemetry:
            set_telemetry(None)

    # ==================================================================
    # checkpointing (parity with engine.save_checkpoint engine.py:3010)
    def _materialized_params(self) -> Any:
        """Params for read-out (export/eval/state-dict): swapped in from
        disk under nvme offload WITHOUT mutating the engine's parked state."""
        if self._param_offload_device == "nvme" and self.params is None:
            return self._param_nvme_swapper.swap_in()
        return self.params

    def _state_dict(self) -> Dict[str, Any]:
        opt_state = self.opt_state
        if self._offload_device == "nvme" and opt_state is None:
            opt_state = self._nvme_swapper.swap_in()
        return {
            "params": self._materialized_params(),
            "opt_state": opt_state,
            "scaler": self.scaler_state,
            "step": jnp.asarray(self.global_steps, jnp.int32),
            "rng": self.rng,
        }

    def save_checkpoint(self, save_dir: str, tag: Optional[str] = None,
                        client_state: Optional[Dict[str, Any]] = None,
                        model_version: Optional[int] = None) -> str:
        tag = tag if tag is not None else f"global_step{self.global_steps}"
        validate_tag_consistency(str(tag), self.config.checkpoint.tag_validation)
        client = {**(client_state or {}),
                  "global_steps": self.global_steps,
                  "micro_steps": self.micro_steps,
                  "skipped_steps": self.skipped_steps}
        if self._dataloader is not None and hasattr(self._dataloader,
                                                    "state_dict"):
            # data-pipeline position rides along so resume replays the
            # exact remaining batch order (bit-exact resume contract)
            client["dataloader"] = self._dataloader.state_dict()
        return self.ckpt_engine.save(
            save_dir, str(tag), self._state_dict(),
            client_state=client,
            config_snapshot=self.config.raw,
            model_version=model_version)

    def load_checkpoint(self, load_dir: str, tag: Optional[str] = None,
                        load_optimizer_states: bool = True,
                        auto: bool = False) -> Optional[Dict[str, Any]]:
        """Restore engine state. ``tag=None`` picks the newest VALID tag
        (torn/uncommitted/corrupt tags are verified against their manifest
        and skipped — see runtime/checkpoint.py). ``auto=True`` is the
        resume-after-restart entry point: a missing/empty directory is a
        quiet no-op instead of a warning, so first boot and restart share
        one code path."""
        if auto and not os.path.isdir(load_dir):
            return None
        # struct-only template: never swaps offloaded state in from disk
        # just to learn the tree structure
        template = {
            "params": self._params_struct,
            "opt_state": self._opt_struct,
            "scaler": self.scaler_state,
            "step": jnp.asarray(self.global_steps, jnp.int32),
            "rng": self.rng,
        }
        result = self.ckpt_engine.load(load_dir, tag, template=template)
        if result is None:
            return None
        state = result["state"]
        repl = self.topo.replicated()
        self.params = jax.device_put(state["params"], self.param_shardings)
        self._params_to_offload()
        if load_optimizer_states:
            if self._offload_device == "nvme":
                self._nvme_swapper.swap_out(state["opt_state"])
                self.opt_state = None
            else:
                target = (self._opt_host_shardings
                          if self._opt_host_shardings is not None
                          else self.opt_state_shardings)
                self.opt_state = jax.device_put(state["opt_state"], target)
            self.scaler_state = jax.device_put(
                jax.tree_util.tree_map(jnp.asarray, state["scaler"]), repl)
        self.global_steps = int(state["step"])
        # keep the throughput timer's step counter aligned with
        # global_steps so the report boundary (will_report_next) stays on
        # steps_per_print multiples of the *global* step across resumes
        self.tput.step_count = self.global_steps
        self.rng = jax.device_put(jnp.asarray(state["rng"]), repl)
        client = result["meta"].get("client_state", {})
        self.micro_steps = int(client.get("micro_steps", self.global_steps * self.gradient_accumulation_steps))
        self.skipped_steps = int(client.get("skipped_steps", 0))
        if (self._dataloader is not None and "dataloader" in client
                and hasattr(self._dataloader, "load_state_dict")):
            self._dataloader.load_state_dict(client["dataloader"])
        return client

    def hot_swap_checkpoint(self, load_dir: str,
                            tag: Optional[str] = None,
                            warmup_batch: Optional[Any] = None
                            ) -> Optional[int]:
        """Weight-only swap for zero-downtime rollout (serving/rollout.py).

        Loads ONLY ``params`` from the checkpoint — optimizer state,
        loss-scaler, step counters, rng, and dataloader position are all
        left untouched, because the process keeps serving/training as the
        same logical worker; only the model weights flip. The checkpoint
        is manifest-verified exactly like :meth:`load_checkpoint` — a
        torn or corrupt tag raises instead of half-swapping, so the
        rollout controller's swap-failure path (re-open admission, retry
        or roll back) sees a clean error, never a franken-model.

        ``warmup_batch`` triggers :meth:`warmup_async` on the new weights
        so the first post-swap step does not eat a compile stall.

        Returns the checkpoint's ``model_version`` manifest field (None
        when the checkpoint predates version stamping).
        """
        template = {
            "params": self._params_struct,
            "opt_state": self._opt_struct,
            "scaler": self.scaler_state,
            "step": jnp.asarray(self.global_steps, jnp.int32),
            "rng": self.rng,
        }
        result = self.ckpt_engine.load(load_dir, tag, template=template)
        if result is None:
            raise ValueError(
                f"hot_swap_checkpoint: no valid checkpoint under "
                f"{load_dir!r} (tag={tag!r}) — refusing to swap")
        self.params = jax.device_put(result["state"]["params"],
                                     self.param_shardings)
        self._params_to_offload()
        if warmup_batch is not None:
            self.warmup_async(warmup_batch)
        version = result["meta"].get("model_version")
        return int(version) if version is not None else None

    def save_16bit_model(self, save_dir: str, filename: str = "model_fp16.npz") -> str:
        """Consolidated 16-bit export (reference engine.save_16bit_model
        engine.py:3492 + zero_to_fp32 consolidation)."""
        os.makedirs(save_dir, exist_ok=True)
        flat = consolidate_full_state(
            _cast_tree(self._materialized_params(), jnp.bfloat16))
        leaves, treedef = jax.tree_util.tree_flatten_with_path(flat)
        out = {jax.tree_util.keystr(k): np.asarray(v) for k, v in leaves}
        path = os.path.join(save_dir, filename)
        np.savez(path, **out)
        return path

    def get_fp32_state_dict(self) -> Any:
        return consolidate_full_state(self._materialized_params(),
                                      dtype=np.float32)
