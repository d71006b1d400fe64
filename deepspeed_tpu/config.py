"""JSON configuration system.

Capability parity with the reference's ``runtime/config.py`` (DeepSpeedConfig:
JSON -> typed config with batch-size arithmetic and per-subsystem sub-configs)
and ``runtime/config_utils.py`` (pydantic base supporting ``"auto"`` values).
Rebuilt on plain dataclasses — no pydantic dependency — and extended with a
TPU-native ``mesh`` section describing the device-mesh axes
(data / seq / pipe / model / expert) that replaces the reference's
process-group plumbing (``deepspeed/utils/groups.py``).

The batch invariant from the reference
(``train_batch_size == micro_batch_per_device * gradient_accumulation_steps *
data_parallel_world_size``) is resolved and validated in
:meth:`Config.resolve_batch_config`, mirroring ``runtime/config.py``'s
``_configure_train_batch_size``.
"""

from __future__ import annotations

import copy
import dataclasses
import json
import os
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Union

from .utils.logging import logger

AUTO = "auto"


class ConfigError(ValueError):
    pass


def _is_auto(v: Any) -> bool:
    return isinstance(v, str) and v.lower() == AUTO


def _take(d: Dict[str, Any], key: str, default: Any) -> Any:
    v = d.pop(key, default)
    return default if v is None else v


def _warn_unknown(d: Dict[str, Any], section: str) -> None:
    for k in d:
        logger.warning(f"Unknown config key '{k}' in section '{section}' — ignored")


@dataclass
class OptimizerConfig:
    """Mirrors the reference's ``optimizer`` block (runtime/config.py get_optimizer_*)."""

    type: str = "adamw"
    params: Dict[str, Any] = field(default_factory=dict)
    # Reference: "legacy_fusion" etc. are CUDA-specific; fused-by-construction under jit.

    @classmethod
    def from_dict(cls, d: Optional[Dict[str, Any]]) -> "OptimizerConfig":
        if not d:
            return cls()
        d = dict(d)
        out = cls(type=str(_take(d, "type", "adamw")).lower(), params=dict(_take(d, "params", {})))
        _warn_unknown(d, "optimizer")
        return out


@dataclass
class SchedulerConfig:
    """Mirrors the reference's ``scheduler`` block (runtime/lr_schedules.py)."""

    type: Optional[str] = None
    params: Dict[str, Any] = field(default_factory=dict)

    @classmethod
    def from_dict(cls, d: Optional[Dict[str, Any]]) -> "SchedulerConfig":
        if not d:
            return cls()
        d = dict(d)
        out = cls(type=_take(d, "type", None), params=dict(_take(d, "params", {})))
        _warn_unknown(d, "scheduler")
        return out


@dataclass
class FP16Config:
    """Mirrors reference ``fp16`` block incl. dynamic loss scaling knobs
    (runtime/fp16/loss_scaler.py:91 DynamicLossScaler)."""

    enabled: bool = False
    loss_scale: float = 0.0  # 0 => dynamic
    initial_scale_power: int = 16
    loss_scale_window: int = 1000
    hysteresis: int = 2
    min_loss_scale: float = 1.0
    consecutive_hysteresis: bool = False

    @property
    def dynamic_loss_scale(self) -> bool:
        return self.loss_scale == 0

    @classmethod
    def from_dict(cls, d: Optional[Dict[str, Any]]) -> "FP16Config":
        if not d:
            return cls()
        d = dict(d)
        out = cls(
            enabled=bool(_take(d, "enabled", False)),
            loss_scale=float(_take(d, "loss_scale", 0.0)),
            initial_scale_power=int(_take(d, "initial_scale_power", 16)),
            loss_scale_window=int(_take(d, "loss_scale_window", 1000)),
            hysteresis=int(_take(d, "hysteresis", 2)),
            min_loss_scale=float(_take(d, "min_loss_scale", 1.0)),
            consecutive_hysteresis=bool(_take(d, "consecutive_hysteresis", False)),
        )
        d.pop("auto_cast", None)  # torch-amp specific; casting is explicit in JAX
        d.pop("fp16_master_weights_and_grads", None)
        _warn_unknown(d, "fp16")
        return out


@dataclass
class BF16Config:
    enabled: bool = False

    @classmethod
    def from_dict(cls, d: Optional[Dict[str, Any]]) -> "BF16Config":
        if not d:
            return cls()
        d = dict(d)
        out = cls(enabled=bool(_take(d, "enabled", False)))
        d.pop("immediate_grad_update", None)
        _warn_unknown(d, "bf16")
        return out


@dataclass
class OffloadConfig:
    """Mirrors reference ``runtime/zero/offload_config.py`` (device: cpu|nvme)."""

    device: str = "none"  # none | cpu | nvme
    nvme_path: Optional[str] = None
    pin_memory: bool = True
    buffer_count: int = 4
    buffer_size: int = 100_000_000
    fast_init: bool = False
    ratio: float = 1.0

    @classmethod
    def from_dict(cls, d: Optional[Dict[str, Any]]) -> "OffloadConfig":
        if not d:
            return cls()
        d = dict(d)
        out = cls(
            device=str(_take(d, "device", "none")),
            nvme_path=_take(d, "nvme_path", None),
            pin_memory=bool(_take(d, "pin_memory", True)),
            buffer_count=int(_take(d, "buffer_count", 4)),
            buffer_size=int(_take(d, "buffer_size", 100_000_000)),
            fast_init=bool(_take(d, "fast_init", False)),
            ratio=float(_take(d, "ratio", 1.0)),
        )
        d.pop("max_in_cpu", None)
        _warn_unknown(d, "offload")
        return out

    @property
    def enabled(self) -> bool:
        return self.device not in ("none", None)


@dataclass
class ZeroConfig:
    """Mirrors reference ``runtime/zero/config.py`` DeepSpeedZeroConfig.

    On TPU the stages translate to sharding choices over the ``data`` mesh
    axis rather than hook machinery (SURVEY.md §2.2):
      stage 0 — replicated params/grads/opt state (plain DP, psum grads)
      stage 1 — optimizer states sharded (reduce-scatter grads, shard update,
                all-gather params)
      stage 2 — + gradients sharded (identical XLA program to stage 1; kept
                distinct for config parity)
      stage 3 — + parameters sharded (FSDP-style; XLA inserts all-gathers)
    """

    stage: int = 0
    # Communication/bucketing knobs (accepted for parity; XLA schedules
    # collectives, so these do not change the compiled program).
    allgather_partitions: bool = True
    allgather_bucket_size: int = 500_000_000
    overlap_comm: bool = True
    reduce_scatter: bool = True
    reduce_bucket_size: int = 500_000_000
    contiguous_gradients: bool = True
    offload_param: OffloadConfig = field(default_factory=OffloadConfig)
    offload_optimizer: OffloadConfig = field(default_factory=OffloadConfig)
    sub_group_size: int = 1_000_000_000
    # stage-3 partitioning thresholds: params smaller than this stay replicated
    stage3_param_persistence_threshold: int = 10_000
    stage3_max_live_parameters: int = 1_000_000_000
    stage3_max_reuse_distance: int = 1_000_000_000
    stage3_prefetch_bucket_size: int = 50_000_000
    stage3_gather_16bit_weights_on_model_save: bool = False
    # ZeRO++ style knobs
    zero_hpz_partition_size: int = 1
    zero_quantized_weights: bool = False
    zero_quantized_gradients: bool = False
    # MiCS
    mics_shard_size: int = -1
    mics_hierarchical_params_gather: bool = False
    round_robin_gradients: bool = False
    ignore_unused_parameters: bool = True

    def zero_inner_size(self) -> int:
        """Inner (zshard) factor of the data-parallel dimension: MiCS
        sub-group size takes precedence over the hpZ secondary partition
        (a MiCS run shards everything at that granularity already)."""
        if (self.mics_shard_size or 0) > 0:
            return int(self.mics_shard_size)
        if self.zero_hpz_partition_size > 1:
            return int(self.zero_hpz_partition_size)
        return 1

    @classmethod
    def from_dict(cls, d: Optional[Dict[str, Any]]) -> "ZeroConfig":
        if not d:
            return cls()
        d = dict(d)
        out = cls(
            stage=int(_take(d, "stage", 0)),
            allgather_partitions=bool(_take(d, "allgather_partitions", True)),
            allgather_bucket_size=int(float(_take(d, "allgather_bucket_size", 500_000_000))),
            overlap_comm=bool(_take(d, "overlap_comm", True)),
            reduce_scatter=bool(_take(d, "reduce_scatter", True)),
            reduce_bucket_size=int(float(_take(d, "reduce_bucket_size", 500_000_000))),
            contiguous_gradients=bool(_take(d, "contiguous_gradients", True)),
            offload_param=OffloadConfig.from_dict(_take(d, "offload_param", None)),
            offload_optimizer=OffloadConfig.from_dict(_take(d, "offload_optimizer", None)),
            sub_group_size=int(float(_take(d, "sub_group_size", 1_000_000_000))),
            stage3_param_persistence_threshold=int(float(_take(d, "stage3_param_persistence_threshold", 10_000))),
            stage3_max_live_parameters=int(float(_take(d, "stage3_max_live_parameters", 1_000_000_000))),
            stage3_max_reuse_distance=int(float(_take(d, "stage3_max_reuse_distance", 1_000_000_000))),
            stage3_prefetch_bucket_size=int(float(_take(d, "stage3_prefetch_bucket_size", 50_000_000))),
            stage3_gather_16bit_weights_on_model_save=bool(
                _take(d, "stage3_gather_16bit_weights_on_model_save", False)
            ),
            zero_hpz_partition_size=int(_take(d, "zero_hpz_partition_size", 1)),
            zero_quantized_weights=bool(_take(d, "zero_quantized_weights", False)),
            zero_quantized_gradients=bool(_take(d, "zero_quantized_gradients", False)),
            mics_shard_size=int(_take(d, "mics_shard_size", -1)),
            mics_hierarchical_params_gather=bool(_take(d, "mics_hierarchical_params_gather", False)),
            round_robin_gradients=bool(_take(d, "round_robin_gradients", False)),
            ignore_unused_parameters=bool(_take(d, "ignore_unused_parameters", True)),
        )
        if out.stage not in (0, 1, 2, 3):
            raise ConfigError(f"zero_optimization.stage must be 0..3, got {out.stage}")
        # Accepted-but-inert reference keys.
        for k in ("cpu_offload", "cpu_offload_params", "load_from_fp32_weights", "elastic_checkpoint",
                  "zero_quantized_nontrainable_weights", "memory_efficient_linear", "param_persistence_threshold",
                  "model_persistence_threshold", "max_live_parameters", "max_reuse_distance",
                  "prefetch_bucket_size", "gather_16bit_weights_on_model_save", "use_multi_rank_bucket_allreduce",
                  "legacy_stage1"):
            d.pop(k, None)
        _warn_unknown(d, "zero_optimization")
        return out


@dataclass
class MeshConfig:
    """TPU-native topology description (replaces reference groups.py).

    Axis sizes; -1 means "use all remaining devices". Axis order is outermost
    to innermost: (data, seq, pipe, expert, model). ``model`` is innermost so
    tensor-parallel collectives ride the fastest ICI links.
    """

    data: int = -1
    seq: int = 1
    pipe: int = 1
    expert: int = 1
    model: int = 1

    AXES = ("data", "seq", "pipe", "expert", "model")

    @classmethod
    def from_dict(cls, d: Optional[Dict[str, Any]]) -> "MeshConfig":
        if not d:
            return cls()
        d = dict(d)
        out = cls(
            data=int(_take(d, "data", -1)),
            seq=int(_take(d, "seq", 1)),
            pipe=int(_take(d, "pipe", 1)),
            expert=int(_take(d, "expert", 1)),
            model=int(_take(d, "model", 1)),
        )
        _warn_unknown(d, "mesh")
        return out

    def resolve(self, n_devices: int) -> Dict[str, int]:
        sizes = {a: getattr(self, a) for a in self.AXES}
        fixed = 1
        free_axes = [a for a, s in sizes.items() if s == -1]
        for a, s in sizes.items():
            if s != -1:
                fixed *= s
        if n_devices % fixed != 0:
            raise ConfigError(f"mesh axes {sizes} do not divide device count {n_devices}")
        rem = n_devices // fixed
        if not free_axes:
            if fixed != n_devices:
                raise ConfigError(f"mesh axes {sizes} product {fixed} != device count {n_devices}")
        elif len(free_axes) == 1:
            sizes[free_axes[0]] = rem
        else:
            # first free axis soaks up the remainder, rest get 1
            sizes[free_axes[0]] = rem
            for a in free_axes[1:]:
                sizes[a] = 1
        return sizes


@dataclass
class ActivationCheckpointingConfig:
    """Mirrors reference ``runtime/activation_checkpointing/config.py``.

    On TPU this maps to ``jax.checkpoint`` (remat) policies; partitioned
    activations map to remat + sharding constraints.
    """

    partition_activations: bool = False
    cpu_checkpointing: bool = False
    contiguous_memory_optimization: bool = False
    number_checkpoints: Optional[int] = None
    synchronize_checkpoint_boundary: bool = False
    profile: bool = False
    # TPU-native: which remat policy to use ("full", "dots", "nothing")
    policy: str = "full"

    @classmethod
    def from_dict(cls, d: Optional[Dict[str, Any]]) -> "ActivationCheckpointingConfig":
        if not d:
            return cls()
        d = dict(d)
        out = cls(
            partition_activations=bool(_take(d, "partition_activations", False)),
            cpu_checkpointing=bool(_take(d, "cpu_checkpointing", False)),
            contiguous_memory_optimization=bool(_take(d, "contiguous_memory_optimization", False)),
            number_checkpoints=_take(d, "number_checkpoints", None),
            synchronize_checkpoint_boundary=bool(_take(d, "synchronize_checkpoint_boundary", False)),
            profile=bool(_take(d, "profile", False)),
            policy=str(_take(d, "policy", "full")),
        )
        _warn_unknown(d, "activation_checkpointing")
        return out


@dataclass
class MonitorConfig:
    """Mirrors reference ``monitor/config.py`` (tensorboard/csv/wandb)."""

    tensorboard_enabled: bool = False
    tensorboard_output_path: str = ""
    tensorboard_job_name: str = "DeepSpeedTPUJob"
    csv_enabled: bool = False
    csv_output_path: str = ""
    csv_job_name: str = "DeepSpeedTPUJob"
    wandb_enabled: bool = False
    wandb_project: Optional[str] = None
    wandb_team: Optional[str] = None
    wandb_group: Optional[str] = None

    @classmethod
    def from_dict(cls, tb: Optional[Dict], csv: Optional[Dict], wandb: Optional[Dict]) -> "MonitorConfig":
        tb = dict(tb or {})
        csv = dict(csv or {})
        wandb = dict(wandb or {})
        return cls(
            tensorboard_enabled=bool(tb.get("enabled", False)),
            tensorboard_output_path=str(tb.get("output_path", "")),
            tensorboard_job_name=str(tb.get("job_name", "DeepSpeedTPUJob")),
            csv_enabled=bool(csv.get("enabled", False)),
            csv_output_path=str(csv.get("output_path", "")),
            csv_job_name=str(csv.get("job_name", "DeepSpeedTPUJob")),
            wandb_enabled=bool(wandb.get("enabled", False)),
            wandb_project=wandb.get("project"),
            wandb_team=wandb.get("team"),
            wandb_group=wandb.get("group"),
        )

    @property
    def enabled(self) -> bool:
        return self.tensorboard_enabled or self.csv_enabled or self.wandb_enabled


@dataclass
class TelemetryConfig:
    """Unified telemetry pipeline (``telemetry`` block — TPU-native, no
    reference analog; see docs/observability.md).

    When enabled, the train engine emits one StepStats JSONL record per
    optimizer step (wall time, tokens/s, MFU, comm breakdown, memory
    watermarks) and runs heartbeat/stall detection. Disabled (default),
    the engine adds zero extra per-step host synchronization.
    """

    enabled: bool = False
    output_dir: str = "telemetry"
    jsonl_path: Optional[str] = None       # default: <output_dir>/steps.jsonl
    prometheus_path: Optional[str] = None  # e.g. <output_dir>/metrics.prom
    flush_every: int = 1
    export_every: int = 10
    stall_detection: bool = True
    stall_factor: float = 3.0
    stall_window: int = 20
    stall_warmup_steps: int = 2
    heartbeat_path: Optional[str] = None
    # serving-request span records (docs/serving.md); None defaults to
    # <output_dir>/requests.jsonl, "" disables the sink
    requests_jsonl_path: Optional[str] = None
    # request-scoped distributed tracing + flight recorder
    # (telemetry/tracing.py, docs/observability.md). Off by default:
    # zero extra host syncs / clock reads on every hot path.
    tracing: bool = False
    trace_ring: int = 4096          # finished-span ring buffer size
    flight_capacity: int = 512      # flight-recorder ring size
    flight_dump_dir: Optional[str] = None  # auto-dump dir; None = in-memory

    @classmethod
    def from_dict(cls, d: Optional[Dict[str, Any]]) -> "TelemetryConfig":
        if not d:
            return cls()
        d = dict(d)
        out = cls(
            enabled=bool(_take(d, "enabled", False)),
            output_dir=str(_take(d, "output_dir", "telemetry")),
            jsonl_path=_take(d, "jsonl_path", None),
            prometheus_path=_take(d, "prometheus_path", None),
            flush_every=int(_take(d, "flush_every", 1)),
            export_every=int(_take(d, "export_every", 10)),
            stall_detection=bool(_take(d, "stall_detection", True)),
            stall_factor=float(_take(d, "stall_factor", 3.0)),
            stall_window=int(_take(d, "stall_window", 20)),
            stall_warmup_steps=int(_take(d, "stall_warmup_steps", 2)),
            heartbeat_path=_take(d, "heartbeat_path", None),
            requests_jsonl_path=_take(d, "requests_jsonl_path", None),
            tracing=bool(_take(d, "tracing", False)),
            trace_ring=int(_take(d, "trace_ring", 4096)),
            flight_capacity=int(_take(d, "flight_capacity", 512)),
            flight_dump_dir=_take(d, "flight_dump_dir", None),
        )
        if out.trace_ring < 1 or out.flight_capacity < 1:
            raise ConfigError(
                "telemetry.trace_ring and telemetry.flight_capacity must "
                f"be >= 1, got {out.trace_ring}/{out.flight_capacity}")
        if out.stall_factor <= 1.0:
            raise ConfigError(
                f"telemetry.stall_factor must exceed 1.0, got {out.stall_factor}")
        _warn_unknown(d, "telemetry")
        return out


@dataclass
class DataLoaderConfig:
    """The ``dataloader`` block: async input-pipeline knobs
    (docs/performance.md — TPU-native analog of the reference's
    pinned-memory staged loaders).

    ``prefetch_depth`` batches are collated + uploaded by a producer
    thread ahead of the training loop (0 = synchronous inline loading;
    2 = double buffering, the default). ``initialize()`` threads this
    into the :class:`~deepspeed_tpu.runtime.dataloader.DataLoader` it
    builds; checkpoints stay FT-safe — the loader position always
    reflects consumed batches, never producer read-ahead."""

    prefetch_depth: int = 2

    @classmethod
    def from_dict(cls, d: Optional[Dict[str, Any]]) -> "DataLoaderConfig":
        if not d:
            return cls()
        d = dict(d)
        out = cls(prefetch_depth=int(_take(d, "prefetch_depth", 2)))
        if out.prefetch_depth < 0:
            raise ConfigError(
                f"dataloader.prefetch_depth must be >= 0, got {out.prefetch_depth}")
        _warn_unknown(d, "dataloader")
        return out


@dataclass
class CompileConfig:
    """The ``compile`` block: XLA compilation-cache + warmup knobs
    (docs/performance.md).

    ``cache_dir`` enables JAX's persistent compilation cache there (time-
    to-first-step across process restarts drops to cache-deserialize
    time); a set ``JAX_COMPILATION_CACHE_DIR`` overrides it
    (runtime/compile_cache.py). ``aot_warmup`` makes ``initialize()`` AOT-compile the fused
    train step (``lower().compile()``) in a background thread, overlapped
    with the input pipeline's warm fill; the resulting executable serves
    the steady-state steps directly. ``warn_on_recompile`` logs (once)
    when a new batch shape misses the train-step jit cache — every new
    shape compiles a new program; the counter ``train/recompiles`` tracks
    it either way."""

    cache_dir: Optional[str] = None
    aot_warmup: bool = True
    warn_on_recompile: bool = True

    @classmethod
    def from_dict(cls, d: Optional[Dict[str, Any]]) -> "CompileConfig":
        if not d:
            return cls()
        d = dict(d)
        out = cls(
            cache_dir=_take(d, "cache_dir", None),
            aot_warmup=bool(_take(d, "aot_warmup", True)),
            warn_on_recompile=bool(_take(d, "warn_on_recompile", True)),
        )
        _warn_unknown(d, "compile")
        return out


@dataclass
class FlopsProfilerConfig:
    """Mirrors reference ``profiling/config.py``."""

    enabled: bool = False
    profile_step: int = 1
    module_depth: int = -1
    top_modules: int = 1
    detailed: bool = True
    output_file: Optional[str] = None

    @classmethod
    def from_dict(cls, d: Optional[Dict[str, Any]]) -> "FlopsProfilerConfig":
        if not d:
            return cls()
        d = dict(d)
        out = cls(
            enabled=bool(_take(d, "enabled", False)),
            profile_step=int(_take(d, "profile_step", 1)),
            module_depth=int(_take(d, "module_depth", -1)),
            top_modules=int(_take(d, "top_modules", 1)),
            detailed=bool(_take(d, "detailed", True)),
            output_file=_take(d, "output_file", None),
        )
        _warn_unknown(d, "flops_profiler")
        return out


@dataclass
class CommsLoggerConfig:
    """Mirrors reference ``comms_logger`` block (utils/comms_logging.py)."""

    enabled: bool = False
    verbose: bool = False
    prof_all: bool = True
    debug: bool = False
    prof_ops: List[str] = field(default_factory=list)

    @classmethod
    def from_dict(cls, d: Optional[Dict[str, Any]]) -> "CommsLoggerConfig":
        if not d:
            return cls()
        d = dict(d)
        out = cls(
            enabled=bool(_take(d, "enabled", False)),
            verbose=bool(_take(d, "verbose", False)),
            prof_all=bool(_take(d, "prof_all", True)),
            debug=bool(_take(d, "debug", False)),
            prof_ops=list(_take(d, "prof_ops", [])),
        )
        _warn_unknown(d, "comms_logger")
        return out


@dataclass
class CommCompressionConfig:
    """The ``comm_compression`` block: the compressed-collectives facade
    (comm/compressed.py, docs/communication.md) — quantized weight
    all-gather (qwZ), hierarchical quantized gradient reduce-scatter
    (qgZ) and the T3-style staged overlap schedule as the shipped ZeRO-3
    path on large meshes.

    ``enabled`` is tri-state: ``"auto"`` (default) turns compression on
    exactly when the ZeRO data-parallel group reaches
    ``mesh_size_threshold`` ranks — small meshes keep the dense path
    (the pack/unpack bracket only pays for itself across slow links,
    docs/communication.md); ``true``/
    ``false`` force it. The explicit ZeRO++ knobs
    (``zero_optimization.zero_quantized_weights`` / ``_gradients``)
    still opt individual legs in regardless of the threshold.

    ``grad_bits`` applies to the INTER-slice gradient hop only — the
    intra-slice (fast-ICI) hop always reduces dense fp (the ZeRO++
    hierarchical positioning). ``overlap`` picks the per-block issue
    order of the staged schedule for models exposing ``zero3_blocks``:
    ``"staged"`` prefetches the next block's gather and defers the
    previous block's reduce (T3), ``"serial"`` issues each collective
    immediately at its consumer, ``"off"`` disables the block schedule.
    ``error_stats`` adds traced quantization-error scalars to the step
    metrics (one extra host fetch per step when telemetry is on)."""

    enabled: Any = "auto"      # "auto" | True | False
    mesh_size_threshold: int = 16
    weight_bits: int = 8
    weight_block: int = 256
    grad_bits: int = 8
    grad_block: int = 256
    overlap: str = "staged"    # staged | serial | off
    error_stats: bool = False

    def resolve_enabled(self, dp_size: int) -> bool:
        if isinstance(self.enabled, bool):
            return self.enabled
        return dp_size >= self.mesh_size_threshold

    @classmethod
    def from_dict(cls, d: Optional[Dict[str, Any]]) -> "CommCompressionConfig":
        if not d:
            return cls()
        d = dict(d)
        enabled = _take(d, "enabled", "auto")
        if not isinstance(enabled, bool):
            if str(enabled).lower() != "auto":
                raise ConfigError(
                    f"comm_compression.enabled must be true/false/'auto', "
                    f"got {enabled!r}")
            enabled = "auto"
        out = cls(
            enabled=enabled,
            mesh_size_threshold=int(_take(d, "mesh_size_threshold", 16)),
            weight_bits=int(_take(d, "weight_bits", 8)),
            weight_block=int(_take(d, "weight_block", 256)),
            grad_bits=int(_take(d, "grad_bits", 8)),
            grad_block=int(_take(d, "grad_block", 256)),
            overlap=str(_take(d, "overlap", "staged")),
            error_stats=bool(_take(d, "error_stats", False)),
        )
        for name, bits in (("weight_bits", out.weight_bits),
                           ("grad_bits", out.grad_bits)):
            if bits not in (4, 8):
                raise ConfigError(
                    f"comm_compression.{name} must be 4 or 8, got {bits}")
        for name, block in (("weight_block", out.weight_block),
                            ("grad_block", out.grad_block)):
            if block <= 0 or block % 2:
                raise ConfigError(
                    f"comm_compression.{name} must be positive and even, "
                    f"got {block}")
        if out.overlap not in ("staged", "serial", "off"):
            raise ConfigError(
                f"comm_compression.overlap must be 'staged', 'serial' or "
                f"'off', got '{out.overlap}'")
        if out.mesh_size_threshold < 1:
            raise ConfigError(
                f"comm_compression.mesh_size_threshold must be >= 1, got "
                f"{out.mesh_size_threshold}")
        _warn_unknown(d, "comm_compression")
        return out


@dataclass
class PipelineConfig:
    """Pipeline execution knobs (reference: PipelineModule/PipelineEngine args)."""

    stages: int = 1
    partition_method: str = "parameters"  # uniform | parameters | type:regex
    activation_checkpoint_interval: int = 0
    pipe_schedule: str = "1f1b"  # 1f1b | gpipe

    @classmethod
    def from_dict(cls, d: Optional[Dict[str, Any]]) -> "PipelineConfig":
        if not d:
            return cls()
        d = dict(d)
        out = cls(
            stages=int(_take(d, "stages", 1)),
            partition_method=str(_take(d, "partition_method", "parameters")),
            activation_checkpoint_interval=int(_take(d, "activation_checkpoint_interval", 0)),
            pipe_schedule=str(_take(d, "pipe_schedule", "1f1b")).lower(),
        )
        _warn_unknown(d, "pipeline")
        return out


@dataclass
class CheckpointConfig:
    """Mirrors reference ``checkpoint`` block (tag validation, parallel
    write), extended with the fault-tolerance knobs
    (docs/fault_tolerance.md): a save dir the engine auto-saves to and
    rolls back from, auto-resume on startup, keep-last-N garbage
    collection, and manifest checksum verification on load."""

    tag_validation: str = "Warn"  # Ignore | Warn | Fail
    load_universal: bool = False
    use_node_local_storage: bool = False
    parallel_write_pipeline: bool = False
    async_save: bool = False
    save_dir: Optional[str] = None   # enables auto-save / rollback / emergency saves
    auto_resume: bool = False        # initialize() loads the newest valid tag
    save_interval: int = 0           # auto-save every N steps (0 = off)
    keep_last_n: int = 0             # GC old valid tags (0 = keep all)
    verify_checksums: bool = True    # manifest CRC verification on load

    @classmethod
    def from_dict(cls, d: Optional[Dict[str, Any]]) -> "CheckpointConfig":
        if not d:
            return cls()
        d = dict(d)
        out = cls(
            tag_validation=str(_take(d, "tag_validation", "Warn")).capitalize(),
            load_universal=bool(_take(d, "load_universal", False)),
            use_node_local_storage=bool(_take(d, "use_node_local_storage", False)),
            parallel_write_pipeline=bool(_take(d, "parallel_write", {}).get("pipeline_stage", False))
            if isinstance(d.get("parallel_write"), dict)
            else False,
            async_save=bool(_take(d, "async_save", False)),
            save_dir=_take(d, "save_dir", None),
            auto_resume=bool(_take(d, "auto_resume", False)),
            save_interval=int(_take(d, "save_interval", 0)),
            keep_last_n=int(_take(d, "keep_last_n", 0)),
            verify_checksums=bool(_take(d, "verify_checksums", True)),
        )
        d.pop("parallel_write", None)
        if out.save_interval < 0:
            raise ConfigError(f"checkpoint.save_interval must be >= 0, got {out.save_interval}")
        if out.keep_last_n < 0:
            raise ConfigError(f"checkpoint.keep_last_n must be >= 0, got {out.keep_last_n}")
        _warn_unknown(d, "checkpoint")
        return out


@dataclass
class DivergenceConfig:
    """Divergence guards in the engine step path (resilience/divergence.py).

    ``nan_action``: off | skip | rollback | halt — "skip" compiles the
    non-finite check into the train step (old params kept on-device, zero
    extra host syncs); rollback/halt fetch the loss each step.
    ``spike_action``: off | warn | rollback | halt — loss exceeding
    ``spike_factor`` x the rolling median of the last ``window`` finite
    losses (after ``warmup_steps``).
    """

    nan_action: str = "off"
    spike_action: str = "off"
    spike_factor: float = 10.0
    window: int = 20
    warmup_steps: int = 5
    # rollbacks that fail to progress past the previously-diverging step
    # escalate to halt after this many attempts (a deterministic NaN
    # replays bit-exactly — unbounded rollback would loop forever)
    max_rollbacks: int = 2

    @property
    def wants_host_check(self) -> bool:
        return self.nan_action in ("rollback", "halt") or self.spike_action != "off"

    @classmethod
    def from_dict(cls, d: Optional[Dict[str, Any]]) -> "DivergenceConfig":
        if not d:
            return cls()
        d = dict(d)
        out = cls(
            nan_action=str(_take(d, "nan_action", "off")).lower(),
            spike_action=str(_take(d, "spike_action", "off")).lower(),
            spike_factor=float(_take(d, "spike_factor", 10.0)),
            window=int(_take(d, "window", 20)),
            warmup_steps=int(_take(d, "warmup_steps", 5)),
            max_rollbacks=int(_take(d, "max_rollbacks", 2)),
        )
        if out.max_rollbacks < 1:
            raise ConfigError(
                f"divergence.max_rollbacks must be >= 1, got {out.max_rollbacks}")
        if out.nan_action not in ("off", "skip", "rollback", "halt"):
            raise ConfigError(f"divergence.nan_action must be off|skip|rollback|halt, got {out.nan_action!r}")
        if out.spike_action not in ("off", "warn", "rollback", "halt"):
            raise ConfigError(f"divergence.spike_action must be off|warn|rollback|halt, got {out.spike_action!r}")
        if out.spike_action != "off" and out.spike_factor <= 1.0:
            raise ConfigError(f"divergence.spike_factor must exceed 1.0, got {out.spike_factor}")
        _warn_unknown(d, "resilience.divergence")
        return out


@dataclass
class ChaosConfig:
    """Seeded fault injection (resilience/chaos.py FaultInjector). All
    ``*_at_save`` are 1-based save counts, ``*_at_step`` match the engine's
    ``global_steps`` at the start of a train_batch; -1 disables."""

    enabled: bool = False
    seed: int = 0
    crash_before_commit_at_save: int = -1
    crash_after_commit_at_save: int = -1
    corrupt_shard_at_save: int = -1
    sigterm_at_step: int = -1
    crash_at_step: int = -1
    exit_process: bool = False  # os._exit instead of raising InjectedFault
    exit_code: int = 113
    collective_fail_op: str = ""
    collective_fail_at_call: int = -1
    collective_delay_s: float = 0.0
    collective_delay_every: int = 0
    serving_tick_fail_at: int = -1
    serving_tick_fail_every: int = 0
    # kill serving replica #replica_die_index once its engine has run
    # replica_die_at_tick ticks (-1 disables; one-shot)
    replica_die_at_tick: int = -1
    replica_die_index: int = 0
    # kill serving cell #cell_die_index (whole failure domain) once any
    # of its replicas has run cell_die_at_tick ticks (-1 disables)
    cell_die_at_tick: int = -1
    cell_die_index: int = 0
    # delay every fleet autoscaler decision by this many (virtual)
    # seconds — models real controller observe/decide/boot lag
    autoscaler_lag_s: float = 0.0
    # rollout-targeted faults (serving/rollout.py): corrupt the next N
    # hot-swap weight loads (the swap must fall back to the old version,
    # the controller must retry/rollback — never strand the replica);
    # kill the replica being flipped on the Nth flip (1-based, one-shot,
    # -1 disables); stall every other engine tick of one model version
    # (the injected canary SLO regression auto-rollback is gated on)
    corrupt_swap_count: int = 0
    die_at_flip: int = -1
    degrade_version: int = -1
    # gray-failure faults (docs/fault_tolerance.md "Gray failures"):
    # every Nth serving KV import raises a recoverable fault (the
    # adoption falls back to a requeue; 0 disables). The per-replica
    # k x-slowdowns and stall bursts are runtime-armed on the injector
    # (degrade_replica / arm_stall_burst), not config keys — they name
    # replicas that only exist once the fleet is up.
    flaky_import_every: int = 0
    # global-KV-tier faults (docs/serving.md "Global KV tier"): every
    # Nth directory publish also injects one bogus residency entry (a
    # directory lie — routing must detect the miss and fall back);
    # every Nth prefix export corrupts the wire payload while keeping
    # the stamped checksum (the importer's verify() must catch it);
    # every Nth cold-tier put is dropped (host memory pressure — the
    # prefix degrades to re-prefill, never double-frees). 0 disables.
    stale_directory_every: int = 0
    corrupt_adopt_every: int = 0
    cold_pressure_every: int = 0

    @classmethod
    def from_dict(cls, d: Optional[Dict[str, Any]]) -> "ChaosConfig":
        if not d:
            return cls()
        d = dict(d)
        out = cls(
            enabled=bool(_take(d, "enabled", False)),
            seed=int(_take(d, "seed", 0)),
            crash_before_commit_at_save=int(_take(d, "crash_before_commit_at_save", -1)),
            crash_after_commit_at_save=int(_take(d, "crash_after_commit_at_save", -1)),
            corrupt_shard_at_save=int(_take(d, "corrupt_shard_at_save", -1)),
            sigterm_at_step=int(_take(d, "sigterm_at_step", -1)),
            crash_at_step=int(_take(d, "crash_at_step", -1)),
            exit_process=bool(_take(d, "exit_process", False)),
            exit_code=int(_take(d, "exit_code", 113)),
            collective_fail_op=str(_take(d, "collective_fail_op", "")),
            collective_fail_at_call=int(_take(d, "collective_fail_at_call", -1)),
            collective_delay_s=float(_take(d, "collective_delay_s", 0.0)),
            collective_delay_every=int(_take(d, "collective_delay_every", 0)),
            serving_tick_fail_at=int(_take(d, "serving_tick_fail_at", -1)),
            serving_tick_fail_every=int(_take(d, "serving_tick_fail_every", 0)),
            replica_die_at_tick=int(_take(d, "replica_die_at_tick", -1)),
            replica_die_index=int(_take(d, "replica_die_index", 0)),
            cell_die_at_tick=int(_take(d, "cell_die_at_tick", -1)),
            cell_die_index=int(_take(d, "cell_die_index", 0)),
            autoscaler_lag_s=float(_take(d, "autoscaler_lag_s", 0.0)),
            corrupt_swap_count=int(_take(d, "corrupt_swap_count", 0)),
            die_at_flip=int(_take(d, "die_at_flip", -1)),
            degrade_version=int(_take(d, "degrade_version", -1)),
            flaky_import_every=int(_take(d, "flaky_import_every", 0)),
            stale_directory_every=int(_take(d, "stale_directory_every", 0)),
            corrupt_adopt_every=int(_take(d, "corrupt_adopt_every", 0)),
            cold_pressure_every=int(_take(d, "cold_pressure_every", 0)),
        )
        if out.autoscaler_lag_s < 0:
            raise ConfigError(
                f"resilience.chaos.autoscaler_lag_s must be >= 0, got "
                f"{out.autoscaler_lag_s}")
        if out.corrupt_swap_count < 0:
            raise ConfigError(
                f"resilience.chaos.corrupt_swap_count must be >= 0, got "
                f"{out.corrupt_swap_count}")
        if out.flaky_import_every < 0:
            raise ConfigError(
                f"resilience.chaos.flaky_import_every must be >= 0, got "
                f"{out.flaky_import_every}")
        for knob in ("stale_directory_every", "corrupt_adopt_every",
                     "cold_pressure_every"):
            if getattr(out, knob) < 0:
                raise ConfigError(
                    f"resilience.chaos.{knob} must be >= 0, got "
                    f"{getattr(out, knob)}")
        _warn_unknown(d, "resilience.chaos")
        return out


@dataclass
class ResilienceConfig:
    """The ``resilience`` block: divergence guards + chaos injection
    (docs/fault_tolerance.md)."""

    divergence: DivergenceConfig = field(default_factory=DivergenceConfig)
    chaos: ChaosConfig = field(default_factory=ChaosConfig)

    @classmethod
    def from_dict(cls, d: Optional[Dict[str, Any]]) -> "ResilienceConfig":
        if not d:
            return cls()
        d = dict(d)
        out = cls(
            divergence=DivergenceConfig.from_dict(_take(d, "divergence", None)),
            chaos=ChaosConfig.from_dict(_take(d, "chaos", None)),
        )
        _warn_unknown(d, "resilience")
        return out


@dataclass
class FleetConfig:
    """The ``serving.fleet`` block: multi-replica router front-end
    (docs/serving.md).

    ``router`` picks the routing policy (``"least_loaded"`` or
    ``"prefix_affinity"`` — consistent hashing on the prompt's full-block
    prefix so repeat traffic lands on the replica holding its cached KV).
    ``disaggregated`` splits the fleet into ``prefill_replicas`` replicas
    that only compute prompt KV and hand pages off to the decode
    replicas. ``autoscale`` turns on the telemetry-driven controller; the
    sizing policy itself lives in
    :class:`deepspeed_tpu.elasticity.ServingElasticityConfig` (the
    ``min_replicas``..``sla_low`` knobs here are forwarded to it, so
    training and serving elasticity share one policy surface).
    ``failover`` re-queues a dead replica's in-flight requests onto the
    survivors via the bit-exact resume path; ``respawn`` additionally
    replaces dead replicas while the healthy count sits below
    ``min_replicas``."""

    replicas: int = 1
    router: str = "least_loaded"
    affinity_vnodes: int = 64
    affinity_spill_load: int = 0
    disaggregated: bool = False
    prefill_replicas: int = 1
    health_interval_s: float = 0.05
    failover: bool = True
    respawn: bool = True
    autoscale: bool = False
    autoscale_interval_s: float = 1.0
    min_replicas: int = 1
    max_replicas: int = 8
    scale_up_queue_per_replica: float = 8.0
    scale_down_queue_per_replica: float = 1.0
    kv_high: float = 0.85
    sla_low: float = 0.90
    sla_window: int = 64
    # route-retry discipline (resilience/retry.py RetryBudget): each
    # refused replica pick past the first consumes one unit from a
    # budget shared fleet-wide (and region-wide when the fleet belongs
    # to a ServingCell), with jittered exponential backoff between
    # attempts — a replica/cell that refuses forever is given up on
    # explicitly (REJECTED span) instead of being hammered in a tight
    # loop. 0 budget = first refusal already rejects.
    route_retry_budget: int = 256
    route_backoff_s: float = 0.02
    route_backoff_jitter: float = 0.5
    # gray-failure resilience plane (docs/fault_tolerance.md "Gray
    # failures"; serving/health.py) — all default OFF so the behavioral
    # pins (exact tick-count TTFT gates) are untouched unless opted in.
    # ``quarantine`` drains a replica whose continuous health score
    # breaches ``quarantine_threshold`` for ``quarantine_after``
    # consecutive monitor polls out of the NEW-work routing view (never
    # below ``min_replicas`` — the capacity floor), dwells
    # ``quarantine_dwell_s``, then probes it with live traffic and
    # re-admits after ``quarantine_readmit_polls`` clean polls (a
    # probation breach doubles the dwell — hysteresis against flap).
    # ``breakers`` arms per-replica routing circuit breakers
    # (closed -> open after ``breaker_failures`` consecutive failures,
    # half-open single probe after ``breaker_cooldown_s``).  ``hedge``
    # dispatches a backup leg for an interactive request once
    # ``hedge_ttft_fraction`` of its TTFT deadline has elapsed with no
    # first token — first token wins, the loser is cancelled with its
    # KV discarded, and the SLO ledger judges the request once.
    quarantine: bool = False
    quarantine_threshold: float = 0.5
    quarantine_after: int = 3
    quarantine_dwell_s: float = 8.0
    quarantine_readmit_polls: int = 3
    breakers: bool = False
    breaker_failures: int = 4
    breaker_cooldown_s: float = 5.0
    hedge: bool = False
    hedge_ttft_fraction: float = 0.6

    @classmethod
    def from_dict(cls, d: Optional[Dict[str, Any]]) -> "FleetConfig":
        if not d:
            return cls()
        d = dict(d)
        out = cls(
            replicas=int(_take(d, "replicas", 1)),
            router=str(_take(d, "router", "least_loaded")),
            affinity_vnodes=int(_take(d, "affinity_vnodes", 64)),
            affinity_spill_load=int(_take(d, "affinity_spill_load", 0)),
            disaggregated=bool(_take(d, "disaggregated", False)),
            prefill_replicas=int(_take(d, "prefill_replicas", 1)),
            health_interval_s=float(_take(d, "health_interval_s", 0.05)),
            failover=bool(_take(d, "failover", True)),
            respawn=bool(_take(d, "respawn", True)),
            autoscale=bool(_take(d, "autoscale", False)),
            autoscale_interval_s=float(_take(d, "autoscale_interval_s", 1.0)),
            min_replicas=int(_take(d, "min_replicas", 1)),
            max_replicas=int(_take(d, "max_replicas", 8)),
            scale_up_queue_per_replica=float(
                _take(d, "scale_up_queue_per_replica", 8.0)),
            scale_down_queue_per_replica=float(
                _take(d, "scale_down_queue_per_replica", 1.0)),
            kv_high=float(_take(d, "kv_high", 0.85)),
            sla_low=float(_take(d, "sla_low", 0.90)),
            sla_window=int(_take(d, "sla_window", 64)),
            route_retry_budget=int(_take(d, "route_retry_budget", 256)),
            route_backoff_s=float(_take(d, "route_backoff_s", 0.02)),
            route_backoff_jitter=float(
                _take(d, "route_backoff_jitter", 0.5)),
            quarantine=bool(_take(d, "quarantine", False)),
            quarantine_threshold=float(
                _take(d, "quarantine_threshold", 0.5)),
            quarantine_after=int(_take(d, "quarantine_after", 3)),
            quarantine_dwell_s=float(
                _take(d, "quarantine_dwell_s", 8.0)),
            quarantine_readmit_polls=int(
                _take(d, "quarantine_readmit_polls", 3)),
            breakers=bool(_take(d, "breakers", False)),
            breaker_failures=int(_take(d, "breaker_failures", 4)),
            breaker_cooldown_s=float(
                _take(d, "breaker_cooldown_s", 5.0)),
            hedge=bool(_take(d, "hedge", False)),
            hedge_ttft_fraction=float(
                _take(d, "hedge_ttft_fraction", 0.6)),
        )
        if out.route_retry_budget < 0:
            raise ConfigError(
                f"serving.fleet.route_retry_budget must be >= 0, got "
                f"{out.route_retry_budget}")
        if out.route_backoff_s < 0 or out.route_backoff_jitter < 0:
            raise ConfigError(
                "serving.fleet route_backoff_s and route_backoff_jitter "
                "must be >= 0")
        if out.router not in ("least_loaded", "prefix_affinity",
                              "residency"):
            raise ConfigError(
                f"serving.fleet.router must be 'least_loaded', "
                f"'prefix_affinity' or 'residency', got '{out.router}'")
        if out.replicas < 1:
            raise ConfigError(
                f"serving.fleet.replicas must be >= 1, got {out.replicas}")
        if out.disaggregated and out.prefill_replicas < 1:
            raise ConfigError(
                f"serving.fleet.prefill_replicas must be >= 1 in "
                f"disaggregated mode, got {out.prefill_replicas}")
        if not 1 <= out.min_replicas <= out.max_replicas:
            raise ConfigError(
                f"serving.fleet needs 1 <= min_replicas <= max_replicas, "
                f"got [{out.min_replicas}, {out.max_replicas}]")
        if out.scale_down_queue_per_replica > out.scale_up_queue_per_replica:
            # fail at parse, not as an ElasticityError inside every
            # monitor poll (the hysteresis band must be non-negative)
            raise ConfigError(
                "serving.fleet.scale_down_queue_per_replica must not "
                "exceed scale_up_queue_per_replica "
                f"({out.scale_down_queue_per_replica} > "
                f"{out.scale_up_queue_per_replica})")
        if out.sla_window < 1:
            raise ConfigError(
                f"serving.fleet.sla_window must be >= 1, got "
                f"{out.sla_window}")
        if not 0.0 < out.quarantine_threshold <= 1.0:
            raise ConfigError(
                f"serving.fleet.quarantine_threshold must be in (0, 1], "
                f"got {out.quarantine_threshold}")
        if out.quarantine_after < 1 or out.quarantine_readmit_polls < 1:
            raise ConfigError(
                "serving.fleet quarantine_after and "
                "quarantine_readmit_polls must be >= 1")
        if out.quarantine_dwell_s <= 0:
            raise ConfigError(
                f"serving.fleet.quarantine_dwell_s must be > 0, got "
                f"{out.quarantine_dwell_s}")
        if out.breaker_failures < 1 or out.breaker_cooldown_s <= 0:
            raise ConfigError(
                "serving.fleet breaker_failures must be >= 1 and "
                "breaker_cooldown_s > 0")
        if not 0.0 < out.hedge_ttft_fraction < 1.0:
            # 0 would hedge EVERY interactive request on submit; 1
            # would hedge only after the deadline is already blown
            raise ConfigError(
                f"serving.fleet.hedge_ttft_fraction must be in (0, 1), "
                f"got {out.hedge_ttft_fraction}")
        _warn_unknown(d, "serving.fleet")
        return out


@dataclass
class RegionConfig:
    """The ``serving.region`` block: the cell-based fleet-of-fleets
    front-end (docs/serving.md "Region & cells").

    ``cells`` fleets (each a :class:`FleetConfig`-shaped failure domain)
    sit behind one :class:`~deepspeed_tpu.serving.Region` that routes by
    a two-tier consistent hash: a ``cell_ring_vnodes``-point cell ring
    picks the failure domain from each cell's PUBLISHED load/health
    digest (queue depth, KV demand, in-SLA window — refreshed on the
    monitor cadence, never scanned per route), then the cell's own
    router picks the replica. ``cell_spill_load`` (0 = off) spills a
    request off an overloaded primary cell to the least-loaded
    reachable one (digest queue depth per healthy replica >= the
    threshold), mirroring the replica ring's spill valve one tier up.

    Brownout: when reachable demand exceeds ``brownout_queue_per_replica``
    queued requests per healthy reachable replica, the region sheds NEW
    work below a priority floor that climbs one tier per additional
    multiple of the threshold (the brownout ladder), always with a
    REJECTED span — explicit degradation, never silent drops.
    ``brownout_exit_ratio`` is the hysteresis: a floor level is left
    only once pressure falls below ``ratio`` x its entry threshold.

    ``rebalance_threshold`` (queued requests per replica above the
    reachable mean, 0 = off) lets a heal re-spread QUEUED work from
    cells that bore the partition onto the rejoined capacity.

    Telemetry plane (docs/observability.md "Region rollups"): every
    ``telemetry_rollup_every``-th digest refresh the region pulls each
    cell's telemetry digest delta (sketch merges + counter deltas +
    SLO verdicts) into its accumulator and SLO tracker. The ``slo_*``
    knobs parameterize the per-tenant SLO objective
    (:class:`~deepspeed_tpu.telemetry.slo.SLOObjective`): target in-SLA
    ratio over ``slo_window_s`` of virtual time, with fast/slow
    burn-rate alert windows and thresholds."""

    cells: int = 2
    cell_ring_vnodes: int = 32
    cell_spill_load: int = 0
    brownout_queue_per_replica: float = 8.0
    brownout_exit_ratio: float = 0.5
    rebalance_threshold: float = 4.0
    health_interval_s: float = 0.05
    telemetry_rollup_every: int = 1
    slo_target: float = 0.95
    slo_window_s: float = 240.0
    slo_fast_window_s: float = 300.0
    slo_slow_window_s: float = 3600.0
    slo_fast_burn: float = 14.4
    slo_slow_burn: float = 6.0
    slo_min_samples: int = 4

    @classmethod
    def from_dict(cls, d: Optional[Dict[str, Any]]) -> "RegionConfig":
        if not d:
            return cls()
        d = dict(d)
        out = cls(
            cells=int(_take(d, "cells", 2)),
            cell_ring_vnodes=int(_take(d, "cell_ring_vnodes", 32)),
            cell_spill_load=int(_take(d, "cell_spill_load", 0)),
            brownout_queue_per_replica=float(
                _take(d, "brownout_queue_per_replica", 8.0)),
            brownout_exit_ratio=float(
                _take(d, "brownout_exit_ratio", 0.5)),
            rebalance_threshold=float(
                _take(d, "rebalance_threshold", 4.0)),
            health_interval_s=float(_take(d, "health_interval_s", 0.05)),
            telemetry_rollup_every=int(
                _take(d, "telemetry_rollup_every", 1)),
            slo_target=float(_take(d, "slo_target", 0.95)),
            slo_window_s=float(_take(d, "slo_window_s", 240.0)),
            slo_fast_window_s=float(_take(d, "slo_fast_window_s", 300.0)),
            slo_slow_window_s=float(
                _take(d, "slo_slow_window_s", 3600.0)),
            slo_fast_burn=float(_take(d, "slo_fast_burn", 14.4)),
            slo_slow_burn=float(_take(d, "slo_slow_burn", 6.0)),
            slo_min_samples=int(_take(d, "slo_min_samples", 4)),
        )
        if out.cells < 1:
            raise ConfigError(
                f"serving.region.cells must be >= 1, got {out.cells}")
        if out.cell_ring_vnodes < 1:
            raise ConfigError(
                f"serving.region.cell_ring_vnodes must be >= 1, got "
                f"{out.cell_ring_vnodes}")
        if out.brownout_queue_per_replica <= 0:
            raise ConfigError(
                f"serving.region.brownout_queue_per_replica must be > 0, "
                f"got {out.brownout_queue_per_replica}")
        if not 0.0 <= out.brownout_exit_ratio <= 1.0:
            # exit above entry would re-enter the level it just left on
            # the very next poll (oscillation, not hysteresis)
            raise ConfigError(
                f"serving.region.brownout_exit_ratio must be in [0, 1], "
                f"got {out.brownout_exit_ratio}")
        if out.rebalance_threshold < 0:
            raise ConfigError(
                f"serving.region.rebalance_threshold must be >= 0, got "
                f"{out.rebalance_threshold}")
        if out.telemetry_rollup_every < 1:
            # 0 would divide-by-zero the poll's cadence modulo; a named
            # error at parse beats a ZeroDivisionError mid-rollup
            raise ConfigError(
                f"serving.region.telemetry_rollup_every must be >= 1, "
                f"got {out.telemetry_rollup_every}")
        _warn_unknown(d, "serving.region")
        return out


@dataclass
class RolloutConfig:
    """The ``serving.rollout`` block: zero-downtime model rollout
    (docs/serving.md "Rollout, canary, and migration").

    ``canary_fraction`` is the tenant-sticky traffic slice routed to the
    canary version while the controller observes it.  The canary is
    judged after ``canary_observe_ticks`` controller steps: if the
    canary's in-SLA ratio sits more than ``slo_regression_threshold``
    below the stable version's over at least ``min_canary_samples``
    retired requests, the rollout rolls back automatically; otherwise it
    promotes.  ``warmup_ticks`` is the post-swap AOT warmup countdown a
    flipped replica serves through before re-opening admission.
    ``swap_retry_limit`` bounds hot-swap retries per replica (a corrupt
    new-version checkpoint falls back to the old weights each time);
    ``max_flip_attempts`` bounds how often the controller re-targets a
    flip after the victim dies mid-flip — past either bound the rollout
    rolls back instead of wedging."""

    canary_fraction: float = 0.10
    canary_observe_ticks: int = 40
    slo_regression_threshold: float = 0.20
    min_canary_samples: int = 8
    warmup_ticks: int = 2
    swap_retry_limit: int = 2
    max_flip_attempts: int = 4

    @classmethod
    def from_dict(cls, d: Optional[Dict[str, Any]]) -> "RolloutConfig":
        if not d:
            return cls()
        d = dict(d)
        out = cls(
            canary_fraction=float(_take(d, "canary_fraction", 0.10)),
            canary_observe_ticks=int(_take(d, "canary_observe_ticks", 40)),
            slo_regression_threshold=float(
                _take(d, "slo_regression_threshold", 0.20)),
            min_canary_samples=int(_take(d, "min_canary_samples", 8)),
            warmup_ticks=int(_take(d, "warmup_ticks", 2)),
            swap_retry_limit=int(_take(d, "swap_retry_limit", 2)),
            max_flip_attempts=int(_take(d, "max_flip_attempts", 4)),
        )
        if not 0.0 < out.canary_fraction <= 1.0:
            raise ConfigError(
                f"serving.rollout.canary_fraction must be in (0, 1], got "
                f"{out.canary_fraction}")
        if out.canary_observe_ticks < 1:
            raise ConfigError(
                f"serving.rollout.canary_observe_ticks must be >= 1, got "
                f"{out.canary_observe_ticks}")
        if not 0.0 <= out.slo_regression_threshold <= 1.0:
            raise ConfigError(
                f"serving.rollout.slo_regression_threshold must be in "
                f"[0, 1], got {out.slo_regression_threshold}")
        if out.min_canary_samples < 1:
            raise ConfigError(
                f"serving.rollout.min_canary_samples must be >= 1, got "
                f"{out.min_canary_samples}")
        if out.warmup_ticks < 0:
            raise ConfigError(
                f"serving.rollout.warmup_ticks must be >= 0, got "
                f"{out.warmup_ticks}")
        if out.swap_retry_limit < 0:
            raise ConfigError(
                f"serving.rollout.swap_retry_limit must be >= 0, got "
                f"{out.swap_retry_limit}")
        if out.max_flip_attempts < 1:
            raise ConfigError(
                f"serving.rollout.max_flip_attempts must be >= 1, got "
                f"{out.max_flip_attempts}")
        _warn_unknown(d, "serving.rollout")
        return out


@dataclass
class KVTierConfig:
    """The ``serving.kv_tier`` block: the global KV tier
    (docs/serving.md "Global KV tier"). Default OFF — with
    ``enabled=False`` no directory, adoption pen, or cold tier is
    constructed and old traces/seeds replay bit-identically.

    ``publish_interval_s`` is the residency-publication cadence (each
    replica's driver snapshots its prefix-cache keys at most this often,
    piggybacked on the fleet's poll); ``directory_staleness_s`` bounds
    how old a directory entry may be before routing stops trusting it —
    it must be at least the publish interval, or every entry would
    expire before its holder could refresh it. ``adoption`` gates
    cross-replica prefix adoption (directory hit on another replica ->
    quantized pages on the wire); ``cold_tier``/``cold_capacity_pages``
    gate the host-memory spill store for evicted prefixes."""

    enabled: bool = False
    publish_interval_s: float = 1.0
    directory_staleness_s: float = 5.0
    adoption: bool = True
    cold_tier: bool = True
    cold_capacity_pages: int = 256

    @classmethod
    def from_dict(cls, d: Optional[Dict[str, Any]]) -> "KVTierConfig":
        if not d:
            return cls()
        d = dict(d)
        out = cls(
            enabled=bool(_take(d, "enabled", False)),
            publish_interval_s=float(_take(d, "publish_interval_s", 1.0)),
            directory_staleness_s=float(
                _take(d, "directory_staleness_s", 5.0)),
            adoption=bool(_take(d, "adoption", True)),
            cold_tier=bool(_take(d, "cold_tier", True)),
            cold_capacity_pages=int(_take(d, "cold_capacity_pages", 256)),
        )
        if out.publish_interval_s <= 0:
            raise ConfigError(
                f"serving.kv_tier.publish_interval_s must be > 0, got "
                f"{out.publish_interval_s}")
        if out.directory_staleness_s < out.publish_interval_s:
            raise ConfigError(
                f"serving.kv_tier.directory_staleness_s must be >= "
                f"publish_interval_s ({out.publish_interval_s}), got "
                f"{out.directory_staleness_s}")
        if out.cold_tier and out.cold_capacity_pages < 1:
            raise ConfigError(
                f"serving.kv_tier.cold_capacity_pages must be >= 1 when "
                f"the cold tier is enabled, got {out.cold_capacity_pages}")
        _warn_unknown(d, "serving.kv_tier")
        return out


@dataclass
class ServingConfig:
    """The ``serving`` block: knobs for the request front-end over the
    ragged engine (docs/serving.md).

    ``policy`` selects the admission/preemption policy (``"slo"`` —
    priority tiers + earliest-deadline-first + KV-pressure preemption —
    or ``"fcfs"``, the strict-arrival-order baseline).  ``max_queue``
    bounds the admission queue: submissions beyond it are REJECTED
    immediately (explicit backpressure).  ``reserve_output_blocks``
    charges admission for the whole remaining output, so an admitted
    request cannot exhaust the KV pool mid-decode; turning it off admits
    more aggressively and relies on mid-tick preemption to recover.
    ``tick_retry_limit`` is the per-request budget for re-queue-on-tick-
    fault before the request is failed.  ``stuck_tick_timeout_s`` arms
    the watchdog (0 disables it).

    ``speculative`` turns on prompt-lookup speculative decoding inside
    the serving tick (docs/serving.md "Speculative scheduling"): draft
    chains verify in the one static SplitFuse shape, greedy output stays
    token-identical, and drafting consumes only token-budget SLACK (an
    acceptance-rate-aware credit per priority class — EMA smoothing
    ``spec_ema`` — sizes chains, so drafting never starves prefill).
    Per request, drafting falls back to plain decode when its rolling
    acceptance EMA drops below ``spec_accept_floor`` after at least
    ``spec_floor_min_proposed`` proposed tokens. ``kv_quant`` declares
    the engines' KV-cache quantization mode; the serving layer validates
    it against each engine's own config (one knob, fleet-wide)."""

    max_queue: int = 256
    policy: str = "slo"
    kv_pressure: float = 0.90
    reject_expired: bool = True
    preemption: bool = True
    reserve_output_blocks: bool = True
    default_max_new_tokens: int = 128
    poll_interval_s: float = 0.002
    drain_timeout_s: float = 120.0
    stuck_tick_timeout_s: float = 30.0
    # after this many CONSECUTIVE stuck watchdog polls the engine marks
    # itself watchdog-unhealthy so the fleet monitor evacuates the
    # replica (0 = log-only, the pre-escalation behavior)
    stuck_tick_escalate_polls: int = 3
    tick_retry_limit: int = 1
    speculative: bool = False
    spec_lookahead: int = 4
    spec_ngram: int = 3
    spec_accept_floor: float = 0.25
    spec_floor_min_proposed: int = 16
    spec_ema: float = 0.25
    kv_quant: str = "none"
    # the model version the fleet starts serving (serving/rollout.py);
    # monotonically bumped by rollouts, never by config reload
    model_version: int = 0
    fleet: FleetConfig = field(default_factory=FleetConfig)
    region: RegionConfig = field(default_factory=RegionConfig)
    rollout: RolloutConfig = field(default_factory=RolloutConfig)
    kv_tier: KVTierConfig = field(default_factory=KVTierConfig)

    @classmethod
    def from_dict(cls, d: Optional[Dict[str, Any]]) -> "ServingConfig":
        if not d:
            return cls()
        d = dict(d)
        out = cls(
            fleet=FleetConfig.from_dict(_take(d, "fleet", None)),
            region=RegionConfig.from_dict(_take(d, "region", None)),
            rollout=RolloutConfig.from_dict(_take(d, "rollout", None)),
            kv_tier=KVTierConfig.from_dict(_take(d, "kv_tier", None)),
            max_queue=int(_take(d, "max_queue", 256)),
            policy=str(_take(d, "policy", "slo")),
            kv_pressure=float(_take(d, "kv_pressure", 0.90)),
            reject_expired=bool(_take(d, "reject_expired", True)),
            preemption=bool(_take(d, "preemption", True)),
            reserve_output_blocks=bool(_take(d, "reserve_output_blocks", True)),
            default_max_new_tokens=int(_take(d, "default_max_new_tokens", 128)),
            poll_interval_s=float(_take(d, "poll_interval_s", 0.002)),
            drain_timeout_s=float(_take(d, "drain_timeout_s", 120.0)),
            stuck_tick_timeout_s=float(_take(d, "stuck_tick_timeout_s", 30.0)),
            stuck_tick_escalate_polls=int(
                _take(d, "stuck_tick_escalate_polls", 3)),
            tick_retry_limit=int(_take(d, "tick_retry_limit", 1)),
            speculative=bool(_take(d, "speculative", False)),
            spec_lookahead=int(_take(d, "spec_lookahead", 4)),
            spec_ngram=int(_take(d, "spec_ngram", 3)),
            spec_accept_floor=float(_take(d, "spec_accept_floor", 0.25)),
            spec_floor_min_proposed=int(
                _take(d, "spec_floor_min_proposed", 16)),
            spec_ema=float(_take(d, "spec_ema", 0.25)),
            kv_quant=str(_take(d, "kv_quant", "none")),
            model_version=int(_take(d, "model_version", 0)),
        )
        if out.model_version < 0:
            raise ConfigError(
                f"serving.model_version must be >= 0, got "
                f"{out.model_version}")
        if out.policy not in ("slo", "fcfs"):
            raise ConfigError(
                f"serving.policy must be 'slo' or 'fcfs', got '{out.policy}'")
        if out.max_queue < 1:
            raise ConfigError(
                f"serving.max_queue must be >= 1, got {out.max_queue}")
        if not 0.0 <= out.kv_pressure <= 1.0:
            raise ConfigError(
                f"serving.kv_pressure must be in [0, 1], got {out.kv_pressure}")
        if out.tick_retry_limit < 0:
            raise ConfigError(
                f"serving.tick_retry_limit must be >= 0, got "
                f"{out.tick_retry_limit}")
        if out.stuck_tick_escalate_polls < 0:
            raise ConfigError(
                f"serving.stuck_tick_escalate_polls must be >= 0, got "
                f"{out.stuck_tick_escalate_polls}")
        if out.default_max_new_tokens < 1:
            raise ConfigError(
                f"serving.default_max_new_tokens must be >= 1, got "
                f"{out.default_max_new_tokens}")
        if out.spec_lookahead < 1 or out.spec_ngram < 1:
            raise ConfigError(
                f"serving.spec_lookahead/spec_ngram must be >= 1, got "
                f"{out.spec_lookahead}/{out.spec_ngram}")
        if not 0.0 <= out.spec_accept_floor <= 1.0:
            raise ConfigError(
                f"serving.spec_accept_floor must be in [0, 1], got "
                f"{out.spec_accept_floor}")
        if not 0.0 < out.spec_ema <= 1.0:
            raise ConfigError(
                f"serving.spec_ema must be in (0, 1], got {out.spec_ema}")
        if out.kv_quant not in ("none", "int8", "int4"):
            raise ConfigError(
                f"serving.kv_quant must be 'none', 'int8' or 'int4', got "
                f"'{out.kv_quant}'")
        _warn_unknown(d, "serving")
        return out


@dataclass
class DataEfficiencyConfig:
    """Curriculum learning / data sampling (reference runtime/data_pipeline)."""

    enabled: bool = False
    seed: int = 1234
    curriculum_enabled: bool = False
    curriculum_metrics: Dict[str, Any] = field(default_factory=dict)

    @classmethod
    def from_dict(cls, d: Optional[Dict[str, Any]]) -> "DataEfficiencyConfig":
        if not d:
            return cls()
        d = dict(d)
        cl = d.pop("data_sampling", {}) or {}
        cur = (cl.get("curriculum_learning") or {}) if isinstance(cl, dict) else {}
        out = cls(
            enabled=bool(_take(d, "enabled", False)),
            seed=int(_take(d, "seed", 1234)),
            curriculum_enabled=bool(cur.get("enabled", False)),
            curriculum_metrics=dict(cur.get("curriculum_metrics", {})),
        )
        d.pop("data_routing", None)
        _warn_unknown(d, "data_efficiency")
        return out


@dataclass
class Config:
    """Top-level typed config. Parity with reference ``DeepSpeedConfig``."""

    # batch arithmetic
    train_batch_size: Optional[int] = None
    train_micro_batch_size_per_gpu: Optional[int] = None
    gradient_accumulation_steps: Optional[int] = None

    steps_per_print: int = 10
    gradient_clipping: float = 0.0
    prescale_gradients: bool = False
    gradient_predivide_factor: float = 1.0
    communication_data_type: Optional[str] = None
    seq_parallel_communication_data_type: Optional[str] = None
    disable_allgather: bool = False
    dump_state: bool = False
    wall_clock_breakdown: bool = False
    memory_breakdown: bool = False
    sparse_gradients: bool = False
    train_seed: int = 42

    optimizer: OptimizerConfig = field(default_factory=OptimizerConfig)
    scheduler: SchedulerConfig = field(default_factory=SchedulerConfig)
    fp16: FP16Config = field(default_factory=FP16Config)
    bf16: BF16Config = field(default_factory=BF16Config)
    zero: ZeroConfig = field(default_factory=ZeroConfig)
    mesh: MeshConfig = field(default_factory=MeshConfig)
    activation_checkpointing: ActivationCheckpointingConfig = field(default_factory=ActivationCheckpointingConfig)
    monitor: MonitorConfig = field(default_factory=MonitorConfig)
    telemetry: TelemetryConfig = field(default_factory=TelemetryConfig)
    dataloader: DataLoaderConfig = field(default_factory=DataLoaderConfig)
    compile: CompileConfig = field(default_factory=CompileConfig)
    flops_profiler: FlopsProfilerConfig = field(default_factory=FlopsProfilerConfig)
    comms_logger: CommsLoggerConfig = field(default_factory=CommsLoggerConfig)
    comm_compression: CommCompressionConfig = field(default_factory=CommCompressionConfig)
    pipeline: PipelineConfig = field(default_factory=PipelineConfig)
    checkpoint: CheckpointConfig = field(default_factory=CheckpointConfig)
    resilience: ResilienceConfig = field(default_factory=ResilienceConfig)
    serving: ServingConfig = field(default_factory=ServingConfig)
    data_efficiency: DataEfficiencyConfig = field(default_factory=DataEfficiencyConfig)

    raw: Dict[str, Any] = field(default_factory=dict)

    # ------------------------------------------------------------------
    @classmethod
    def from_any(cls, config: Union[str, Dict[str, Any], "Config", None]) -> "Config":
        if config is None:
            return cls()
        if isinstance(config, Config):
            return config
        if isinstance(config, str):
            if not os.path.isfile(config):
                raise ConfigError(f"config file not found: {config}")
            with open(config) as f:
                config = json.load(f)
        if not isinstance(config, dict):
            raise ConfigError(f"config must be a dict or path, got {type(config)}")
        return cls.from_dict(config)

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "Config":
        raw = copy.deepcopy(d)
        d = copy.deepcopy(d)

        def intval(key, default=None):
            v = _take(d, key, default)
            if v is None or _is_auto(v):
                return None
            return int(v)

        cfg = cls(
            train_batch_size=intval("train_batch_size"),
            train_micro_batch_size_per_gpu=intval("train_micro_batch_size_per_gpu"),
            gradient_accumulation_steps=intval("gradient_accumulation_steps"),
            steps_per_print=int(_take(d, "steps_per_print", 10)),
            gradient_clipping=float(_take(d, "gradient_clipping", 0.0)),
            prescale_gradients=bool(_take(d, "prescale_gradients", False)),
            gradient_predivide_factor=float(_take(d, "gradient_predivide_factor", 1.0)),
            communication_data_type=_take(d, "communication_data_type", None),
            seq_parallel_communication_data_type=_take(d, "seq_parallel_communication_data_type", None),
            disable_allgather=bool(_take(d, "disable_allgather", False)),
            dump_state=bool(_take(d, "dump_state", False)),
            wall_clock_breakdown=bool(_take(d, "wall_clock_breakdown", False)),
            memory_breakdown=bool(_take(d, "memory_breakdown", False)),
            sparse_gradients=bool(_take(d, "sparse_gradients", False)),
            train_seed=int(_take(d, "seed", 42)),
            optimizer=OptimizerConfig.from_dict(_take(d, "optimizer", None)),
            scheduler=SchedulerConfig.from_dict(_take(d, "scheduler", None)),
            fp16=FP16Config.from_dict(_take(d, "fp16", None)),
            bf16=BF16Config.from_dict(_take(d, "bf16", None)),
            zero=ZeroConfig.from_dict(_take(d, "zero_optimization", None)),
            mesh=MeshConfig.from_dict(_take(d, "mesh", None)),
            activation_checkpointing=ActivationCheckpointingConfig.from_dict(_take(d, "activation_checkpointing", None)),
            monitor=MonitorConfig.from_dict(
                _take(d, "tensorboard", None), _take(d, "csv_monitor", None), _take(d, "wandb", None)
            ),
            telemetry=TelemetryConfig.from_dict(_take(d, "telemetry", None)),
            dataloader=DataLoaderConfig.from_dict(_take(d, "dataloader", None)),
            compile=CompileConfig.from_dict(_take(d, "compile", None)),
            flops_profiler=FlopsProfilerConfig.from_dict(_take(d, "flops_profiler", None)),
            comms_logger=CommsLoggerConfig.from_dict(_take(d, "comms_logger", None)),
            comm_compression=CommCompressionConfig.from_dict(_take(d, "comm_compression", None)),
            pipeline=PipelineConfig.from_dict(_take(d, "pipeline", None)),
            checkpoint=CheckpointConfig.from_dict(_take(d, "checkpoint", None)),
            resilience=ResilienceConfig.from_dict(_take(d, "resilience", None)),
            serving=ServingConfig.from_dict(_take(d, "serving", None)),
            data_efficiency=DataEfficiencyConfig.from_dict(_take(d, "data_efficiency", None)),
            raw=raw,
        )
        if cfg.fp16.enabled and cfg.bf16.enabled:
            raise ConfigError("fp16 and bf16 cannot both be enabled")
        # Accepted-but-unused reference top-level keys (features configured
        # elsewhere in this framework or CUDA-specific).
        for k in ("amp", "zero_allow_untested_optimizer", "zero_force_ds_cpu_optimizer",
                  "gradient_accumulation_dtype", "dataloader_drop_last", "data_types",
                  "compression_training", "autotuning", "elasticity", "nebula",
                  "curriculum_learning", "sparse_attention", "hybrid_engine"):
            d.pop(k, None)
        _warn_unknown(d, "<top-level>")
        return cfg

    # ------------------------------------------------------------------
    def resolve_batch_config(self, dp_world_size: int) -> None:
        """Resolve the train_batch = micro_batch * GAS * dp_world invariant.

        Mirrors reference ``runtime/config.py`` ``_configure_train_batch_size``:
        any two of the three determine the third; a single given value is
        completed with defaults; all three given are validated.
        """
        tb, mb, gas = self.train_batch_size, self.train_micro_batch_size_per_gpu, self.gradient_accumulation_steps
        if tb is not None and mb is not None and gas is not None:
            if tb != mb * gas * dp_world_size:
                raise ConfigError(
                    f"train_batch_size ({tb}) != micro_batch ({mb}) * gas ({gas}) * dp_world ({dp_world_size})"
                )
        elif tb is not None and mb is not None:
            if tb % (mb * dp_world_size) != 0:
                raise ConfigError(
                    f"train_batch_size ({tb}) not divisible by micro_batch ({mb}) * dp_world ({dp_world_size})"
                )
            gas = tb // (mb * dp_world_size)
        elif tb is not None and gas is not None:
            if tb % (gas * dp_world_size) != 0:
                raise ConfigError(
                    f"train_batch_size ({tb}) not divisible by gas ({gas}) * dp_world ({dp_world_size})"
                )
            mb = tb // (gas * dp_world_size)
        elif mb is not None and gas is not None:
            tb = mb * gas * dp_world_size
        elif tb is not None:
            gas = 1
            if tb % dp_world_size != 0:
                raise ConfigError(f"train_batch_size ({tb}) not divisible by dp world size ({dp_world_size})")
            mb = tb // dp_world_size
        elif mb is not None:
            gas = 1
            tb = mb * dp_world_size
        else:
            raise ConfigError(
                "At least one of train_batch_size / train_micro_batch_size_per_gpu /"
                " gradient_accumulation_steps must be set"
            )
        self.train_batch_size, self.train_micro_batch_size_per_gpu, self.gradient_accumulation_steps = tb, mb, gas

    # ------------------------------------------------------------------
    @property
    def compute_dtype(self):
        import jax.numpy as jnp

        if self.bf16.enabled:
            return jnp.bfloat16
        if self.fp16.enabled:
            return jnp.float16
        return jnp.float32

    def to_dict(self) -> Dict[str, Any]:
        def conv(obj):
            if dataclasses.is_dataclass(obj) and not isinstance(obj, type):
                return {f.name: conv(getattr(obj, f.name)) for f in dataclasses.fields(obj) if f.name != "raw"}
            return obj

        return conv(self)


def add_config_arguments(parser):
    """Parity with reference ``deepspeed.add_config_arguments`` (__init__.py:246)."""
    group = parser.add_argument_group("DeepSpeed-TPU", "DeepSpeed-TPU configurations")
    group.add_argument("--deepspeed", default=False, action="store_true",
                       help="Enable DeepSpeed-TPU (helper flag for compat)")
    group.add_argument("--deepspeed_config", default=None, type=str,
                       help="Path to the JSON config file")
    group.add_argument("--deepscale", default=False, action="store_true", help=argparse_suppress())
    group.add_argument("--local_rank", default=-1, type=int,
                       help="Local process rank (compat; unused on TPU)")
    return parser


def argparse_suppress():
    import argparse

    return argparse.SUPPRESS
