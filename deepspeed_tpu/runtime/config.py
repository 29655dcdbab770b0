"""Framework configuration.

TPU-native analog of the reference config system (deepspeed/runtime/config.py —
``DeepSpeedConfig`` with ~80 ``get_*`` extractors plus pydantic sub-models).  A single
JSON file or dict configures the whole engine; the batch-size triple
``train_batch_size = micro_batch * gradient_accumulation_steps * dp_world_size``
is reconciled exactly like the reference (runtime/config.py:837 ``_configure_train_batch_size``).

TPU-specific extension: the ``mesh`` section declaring the device-mesh axis sizes
(data/fsdp/tensor/sequence/expert/pipe) instead of the reference's implicit
world-size + mpu plumbing.
"""

import json
from typing import Any, Dict, List, Optional, Union

from .config_utils import ConfigModel, Field
from ..utils.logging import logger

TRAIN_BATCH_SIZE = "train_batch_size"
TRAIN_MICRO_BATCH_SIZE_PER_GPU = "train_micro_batch_size_per_gpu"
GRADIENT_ACCUMULATION_STEPS = "gradient_accumulation_steps"

# Reference-spelled keys read out of sections this schema deliberately models
# as ``Dict[str, Any]`` (curriculum schedules, compression_training): dslint's
# undeclared-config-key rule checks every string key read from a config dict
# against the union of all ConfigModel fields AND this registry, so a typo'd
# key is a lint error instead of a silent fall-through to the default.  Add a
# key here ONLY when it matches the reference DeepSpeed spelling.
DECLARED_EXTRA_KEYS = frozenset({
    # curriculum learning schedule dict (reference runtime/data_pipeline/config.py
    # + legacy get_curriculum_params spellings)
    "curriculum_type", "schedule_type", "schedule_config", "min_difficulty",
    "max_difficulty", "total_curriculum_step", "difficulty_step", "root_degree",
    "difficulty", "max_step",
    # compression_training sections (reference compression/config.py)
    "weight_quantization", "sparse_pruning", "row_pruning", "head_pruning",
    "channel_pruning", "different_groups", "shared_parameters",
    "layer_reduction", "keep_layers", "keep_number_layer", "teacher_layer",
    "module_name_prefix",
})


class FP16Config(ConfigModel):
    """Reference: deepspeed/runtime/fp16 config (runtime/config.py:125-180)."""
    enabled: bool = False
    auto_cast: bool = False
    loss_scale: float = Field(0.0, ge=0.0)  # 0 => dynamic
    initial_scale_power: int = Field(16, ge=0)
    loss_scale_window: int = Field(1000, ge=1)
    hysteresis: int = Field(2, ge=1)
    consecutive_hysteresis: bool = False
    min_loss_scale: float = Field(1.0, ge=0.0)


class BF16Config(ConfigModel):
    """Reference: bf16 section (runtime/config.py:162). TPU default-on happens in
    TrainingConfig.model_validate when neither fp16 nor fp32 is requested."""
    enabled: bool = True


class OffloadDeviceEnum:
    none = "none"
    cpu = "cpu"
    nvme = "nvme"


class OffloadParamConfig(ConfigModel):
    """Reference: DeepSpeedZeroOffloadParamConfig (runtime/zero/offload_config.py:24)."""
    device: str = Field("none", choices=("none", "cpu", "nvme"))
    nvme_path: Optional[str] = None
    buffer_count: int = Field(5, ge=1)
    buffer_size: int = Field(10**8, ge=1)
    max_in_cpu: int = Field(10**9, ge=0)
    pin_memory: bool = False


class OffloadOptimizerConfig(ConfigModel):
    """Reference: DeepSpeedZeroOffloadOptimizerConfig (runtime/zero/offload_config.py:52)."""
    device: str = Field("none", choices=("none", "cpu", "nvme"))
    nvme_path: Optional[str] = None
    buffer_count: int = Field(4, ge=1)
    pin_memory: bool = False
    fast_init: bool = False
    ratio: float = Field(1.0, ge=0.0, le=1.0)


class ZeroConfig(ConfigModel):
    """Reference: DeepSpeedZeroConfig (runtime/zero/config.py) — stages, buckets,
    ZeRO++ knobs (hpZ/qwZ/qgZ), offload sub-configs."""
    stage: int = Field(0, ge=0, le=3)
    contiguous_gradients: bool = True
    reduce_scatter: bool = True
    reduce_bucket_size: int = Field(int(5e8), ge=0)
    allgather_partitions: bool = True
    allgather_bucket_size: int = Field(int(5e8), ge=0)
    overlap_comm: Optional[bool] = None
    round_robin_gradients: bool = False
    offload_param: Optional[OffloadParamConfig] = None
    offload_optimizer: Optional[OffloadOptimizerConfig] = None
    sub_group_size: int = Field(int(1e9), ge=0)
    prefetch_bucket_size: int = Field(int(5e7), ge=0, deprecated_names=("stage3_prefetch_bucket_size", ))
    param_persistence_threshold: int = Field(int(1e5), ge=0, deprecated_names=("stage3_param_persistence_threshold", ))
    model_persistence_threshold: int = Field(int(1e14), ge=0, deprecated_names=("stage3_model_persistence_threshold", ))
    max_live_parameters: int = Field(int(1e9), ge=0, deprecated_names=("stage3_max_live_parameters", ))
    max_reuse_distance: int = Field(int(1e9), ge=0, deprecated_names=("stage3_max_reuse_distance", ))
    gather_16bit_weights_on_model_save: bool = Field(False,
                                                    deprecated_names=("stage3_gather_16bit_weights_on_model_save", ))
    ignore_unused_parameters: bool = True
    # ZeRO++ analogs (reference runtime/zero/config.py:264-280)
    zero_hpz_partition_size: int = Field(1, ge=1)
    zero_quantized_weights: bool = False
    zero_quantized_gradients: bool = False
    mics_shard_size: int = Field(-1, deprecated_names=("mics_shard_size_", ))
    mics_hierarchical_params_gather: bool = False
    elastic_checkpoint: bool = False

    def model_validate(self):
        if self.overlap_comm is None:
            # Reference defaults overlap_comm True for stage 3 (zero/config.py:308)
            object.__setattr__(self, "overlap_comm", self.stage == 3)


class ActivationCheckpointingConfig(ConfigModel):
    """Reference: runtime/activation_checkpointing config (runtime/config.py:440)."""
    partition_activations: bool = False
    contiguous_memory_optimization: bool = False
    cpu_checkpointing: bool = False
    number_checkpoints: Optional[int] = None
    synchronize_checkpoint_boundary: bool = False
    profile: bool = False
    # TPU-native: jax.checkpoint policy name applied to the layer scan.
    policy: str = Field("nothing_saveable",
                        choices=("everything_saveable", "nothing_saveable", "dots_saveable",
                                 "dots_with_no_batch_dims_saveable", "checkpoint_dots",
                                 "save_anything_except_these_names", "offload_dot",
                                 "offload_residuals"))


class OptimizerConfig(ConfigModel):
    allow_extra = True
    type: str = "adamw"
    params: Dict[str, Any] = Field(dict)


class SchedulerConfig(ConfigModel):
    allow_extra = True
    type: Optional[str] = None
    params: Dict[str, Any] = Field(dict)


class CommsLoggerConfig(ConfigModel):
    """Reference: DeepSpeedCommsConfig (comm/config.py)."""
    enabled: bool = False
    verbose: bool = False
    prof_all: bool = True
    debug: bool = False
    prof_ops: List[str] = Field(list)


class TensorBoardConfig(ConfigModel):
    enabled: bool = False
    output_path: str = ""
    job_name: str = "DeepSpeedTPUJobName"


class WandbConfig(ConfigModel):
    enabled: bool = False
    group: Optional[str] = None
    team: Optional[str] = None
    project: str = "deepspeed_tpu"


class CSVConfig(ConfigModel):
    enabled: bool = False
    output_path: str = ""
    job_name: str = "DeepSpeedTPUJobName"


class MonitorConfig(ConfigModel):
    """Reference: DeepSpeedMonitorConfig (monitor/config.py)."""
    tensorboard: TensorBoardConfig = Field(TensorBoardConfig)
    wandb: WandbConfig = Field(WandbConfig)
    csv_monitor: CSVConfig = Field(CSVConfig)


class FlopsProfilerConfig(ConfigModel):
    """Reference: DeepSpeedFlopsProfilerConfig (profiling/config.py)."""
    enabled: bool = False
    profile_step: int = Field(1, ge=0)
    module_depth: int = -1
    top_modules: int = Field(1, ge=1)
    detailed: bool = True
    output_file: Optional[str] = None


class TelemetryConfig(ConfigModel):
    """Unified telemetry (TPU-native; no single reference analog — subsumes the
    reference's wall_clock_breakdown timers + see_memory_usage + monitor event
    wiring into one per-step record stream, monitor/telemetry.py).

    ``enabled`` (or a non-None ``jsonl_path``) turns on per-step structured
    records: loss, grad-norm, lr, step wall-time, samples/sec, tokens/sec, MFU
    and HBM stats, fanned out to MonitorMaster and a rank-0 JSONL sink.

    ``profile_step_start``/``profile_step_stop`` open a ``jax.profiler`` trace
    window over those global steps (TensorBoard-readable files under
    ``profile_dir``), with StepTraceAnnotation on each step and TraceAnnotation
    around batch-prep and checkpoint IO.

    Cost: a per-step record needs the step's loss and wall-time, so enabling
    telemetry adds ONE host value-fetch (device sync) per train step — host
    work stops overlapping device execution, like ``wall_clock_breakdown``.
    Leave it off for maximum-throughput runs and sample with a profiler window
    instead.
    """
    enabled: bool = False
    jsonl_path: Optional[str] = None
    # flush the JSONL sink every N records (1 = after every record, the
    # pre-tracing behavior tests rely on; raise it for high-rate record
    # streams — per-request serving traces — so file flushes stay off the
    # serve loop; close() always flushes whatever is buffered)
    jsonl_flush_every: int = Field(1, ge=1)
    # -1 disables; [start, stop) in global steps, mirroring the reference's
    # flops_profiler profile_step single-shot trigger but as a window
    profile_step_start: int = Field(-1, ge=-1)
    profile_step_stop: int = Field(-1, ge=-1)
    profile_dir: str = "profiler_traces"
    # -1 disables; [start, stop) in SERVE-LOOP iterations (ISSUE 16): opens
    # one jax.profiler trace window per generate() call, bracketing serve
    # iterations the way profile_step_start/stop brackets train steps, with a
    # TraceAnnotation per serve phase while the window is open
    profile_serve_iteration_start: int = Field(-1, ge=-1)
    profile_serve_iteration_stop: int = Field(-1, ge=-1)
    # see_memory_usage(tag) at each steps_per_print boundary (also honors the
    # reference's top-level memory_breakdown key)
    memory_breakdown: bool = False
    # per-chip peak FLOPs override for MFU; None => detect from device_kind
    peak_flops_per_chip: Optional[float] = Field(None, gt=0.0)

    def model_validate(self):
        if self.jsonl_path is not None and not self.enabled:
            object.__setattr__(self, "enabled", True)
        if (self.profile_step_stop >= 0 and self.profile_step_start >= 0
                and self.profile_step_stop <= self.profile_step_start):
            raise ValueError(f"telemetry: profile_step_stop={self.profile_step_stop} must be "
                             f"> profile_step_start={self.profile_step_start}")
        if (self.profile_serve_iteration_stop >= 0
                and self.profile_serve_iteration_start >= 0
                and self.profile_serve_iteration_stop <= self.profile_serve_iteration_start):
            raise ValueError(
                f"telemetry: profile_serve_iteration_stop={self.profile_serve_iteration_stop} "
                f"must be > profile_serve_iteration_start={self.profile_serve_iteration_start}")


class MeshConfig(ConfigModel):
    """TPU-native: explicit device-mesh axis sizes.

    Replaces the reference's world-size + mpu + groups plumbing
    (deepspeed/utils/groups.py).  Any axis set to -1 absorbs the remaining
    devices (at most one axis may be -1; default: data).
    """
    data: int = -1
    fsdp: int = 1
    tensor: int = 1
    sequence: int = 1
    expert: int = 1
    pipe: int = 1
    # Axis order outer→inner; inner axes map to ICI-adjacent devices.
    axis_order: List[str] = Field(lambda: ["pipe", "data", "fsdp", "expert", "sequence", "tensor"])

    def model_validate(self):
        sizes = self.axis_sizes()
        wild = [a for a, s in sizes.items() if s == -1]
        if len(wild) > 1:
            raise ValueError(f"MeshConfig: at most one axis may be -1, got {wild}")
        for a, s in sizes.items():
            if s < 1 and s != -1:
                raise ValueError(f"MeshConfig.{a}={s} must be >=1 or -1")
        known = set(sizes)
        seen = set()
        for a in self.axis_order:
            if a not in known:
                raise ValueError(f"MeshConfig.axis_order: unknown axis {a!r}; valid axes: {sorted(known)}")
            if a in seen:
                raise ValueError(f"MeshConfig.axis_order: duplicate axis {a!r}")
            seen.add(a)

    def axis_sizes(self):
        return {a: getattr(self, a) for a in ("data", "fsdp", "tensor", "sequence", "expert", "pipe")}


class SparseAttentionConfig(ConfigModel):
    """Blocksparse attention section (reference runtime/config.py:286
    ``get_sparse_attention`` — mode + per-mode knobs).  ``build(num_heads)``
    resolves the matching SparsityConfig from ops/sparse_attention."""
    mode: str = Field("fixed", choices=("dense", "fixed", "variable", "bigbird", "bslongformer", "local"))
    block: int = Field(16, ge=8)  # must be a multiple of 8 (TPU sublane); see model_validate
    different_layout_per_head: bool = False
    # fixed / variable
    num_local_blocks: int = Field(4, ge=1)
    num_global_blocks: int = Field(1, ge=1)
    # None -> per-mode default: "unidirectional" for local (the causal Mistral
    # pattern is that class's own default), "bidirectional" elsewhere.
    attention: Optional[str] = Field(None, choices=(None, "unidirectional", "bidirectional"))
    horizontal_global_attention: bool = False
    num_different_global_patterns: int = Field(1, ge=1)
    # variable / bigbird; None -> per-mode default (bigbird: 1, variable: 0),
    # matching each reference class's own constructor default.
    num_random_blocks: Optional[int] = Field(None, ge=0)
    local_window_blocks: Optional[List[int]] = None
    global_block_indices: Optional[List[int]] = None
    global_block_end_indices: Optional[List[int]] = None
    # bigbird / bslongformer / local
    num_sliding_window_blocks: int = Field(3, ge=1)
    # seeds the random-block placement (variable / bigbird) so layouts are
    # reproducible AND rank-identical — every process derives the same layout
    # from config alone instead of the global `random` module state
    seed: int = Field(1234, ge=0)

    def model_validate(self):
        if self.block % 8 != 0:
            raise ValueError(
                f"sparse_attention.block={self.block} must be a multiple of 8 — the "
                f"Pallas kernel tiles on the TPU sublane; non-multiples silently hit "
                f"the O(S^2) dense fallback")

    def build(self, num_heads: int):
        from ..ops.sparse_attention import (BigBirdSparsityConfig, BSLongformerSparsityConfig,
                                            DenseSparsityConfig, FixedSparsityConfig,
                                            LocalSlidingWindowSparsityConfig,
                                            VariableSparsityConfig)
        attention = self.attention or ("unidirectional" if self.mode == "local" else "bidirectional")
        if self.mode == "dense":
            return DenseSparsityConfig(num_heads, self.block, self.different_layout_per_head)
        if self.mode == "fixed":
            return FixedSparsityConfig(
                num_heads, self.block, self.different_layout_per_head,
                self.num_local_blocks, self.num_global_blocks, attention,
                self.horizontal_global_attention, self.num_different_global_patterns)
        if self.mode == "variable":
            return VariableSparsityConfig(
                num_heads, self.block, self.different_layout_per_head,
                self.num_random_blocks or 0, self.local_window_blocks,
                self.global_block_indices, self.global_block_end_indices,
                attention, self.horizontal_global_attention, seed=self.seed)
        if self.mode == "bigbird":
            num_random = self.num_random_blocks if self.num_random_blocks is not None else 1
            return BigBirdSparsityConfig(
                num_heads, self.block, self.different_layout_per_head,
                num_random, self.num_sliding_window_blocks,
                self.num_global_blocks, attention, seed=self.seed)
        if self.mode == "bslongformer":
            return BSLongformerSparsityConfig(
                num_heads, self.block, self.different_layout_per_head,
                self.num_sliding_window_blocks, self.global_block_indices,
                self.global_block_end_indices, attention)
        return LocalSlidingWindowSparsityConfig(
            num_heads, self.block, self.num_sliding_window_blocks, attention)


class GradientCompressionConfig(ConfigModel):
    """1-bit style compressed gradient reduction (reference runtime/comm/nccl.py:51)."""
    enabled: bool = False
    freeze_step: int = Field(100, ge=0)


class CheckpointSectionConfig(ConfigModel):
    """Reference: the "checkpoint" section (runtime/config.py
    ``get_checkpoint_params``) plus engine selection — the reference picks the
    Nebula async engine vs torch from config in ``_configure_checkpointing``
    (runtime/engine.py:921).  ``checkpoint_engine`` here selects the plug-in
    built by runtime/checkpoint_engine.build_checkpoint_engine.

    Resilience knobs (runtime/checkpointing.py durability protocol):
    ``keep_last_n`` GCs tags beyond the newest N after each save (the newest
    VALID tag is never deleted); ``verify_integrity`` re-checks each leaf's
    CRC32 against the manifest at load; ``save_retries``/``retry_backoff_secs``
    bound the exponential-backoff retry loop around transient save OSErrors;
    ``save_on_preemption`` installs a SIGTERM handler that performs one final
    best-effort save (tag ``preempt_step<N>``, ``client_state.preempted``
    true) before the process dies."""
    allow_extra = True
    checkpoint_engine: str = Field("native", choices=("native", "torch", "async", "nebula"))
    async_max_queue: int = Field(64, ge=1)
    tag_validation: Optional[str] = Field(None, choices=(None, "Ignore", "Warn", "Fail",
                                                         "ignore", "warn", "fail"))
    use_node_local_storage: bool = False
    parallel_write: Optional[Dict[str, Any]] = None
    keep_last_n: Optional[int] = Field(None, ge=1)
    verify_integrity: bool = False
    save_retries: int = Field(2, ge=0)
    retry_backoff_secs: float = Field(0.5, ge=0.0)
    save_on_preemption: bool = False


class FaultToleranceConfig(ConfigModel):
    """Elastic training fault tolerance (runtime/heartbeat.py + the elastic
    agent's liveness monitor + comm/comm.py bounded collectives — the
    training-side analog of the reference's elastic agent supervision,
    ``DSElasticAgent`` in deepspeed/elasticity/elastic_agent.py, extended with
    hang detection the reference delegates to torch-elastic/NCCL timeouts).

    ``heartbeat`` arms per-rank liveness stamps: the engine writes
    ``step + wall-clock + last-entered-collective`` to
    ``<heartbeat_dir>/hb.rank<R>.json`` from its existing host-touch points
    (zero extra device syncs — dslint's host-sync rule scans heartbeat.py),
    throttled to one write per ``heartbeat_interval_s``.  The elastic agent
    exports ``DSTPU_HEARTBEAT_DIR`` to its workers, which arms stamping even
    when this section is absent — config here is for standalone runs that
    want the liveness file anyway.

    ``collective_timeout_s`` bounds host-level collectives (``comm.barrier``
    and anything routed through ``comm.bounded_collective``): instead of a
    silent distributed deadlock, a wedged collective raises
    ``CollectiveTimeoutError`` naming the collective, this rank, and the
    elapsed time — a fast, attributable failure the agent restarts from.
    ``init_retries``/``init_retry_backoff_s`` bound the exponential-backoff
    retry loop around transient process-group setup failures in
    ``comm.init_distributed`` (coordinator not yet listening at scale-up);
    ``deepspeed_tpu.initialize()`` applies them before process-group setup,
    and the agent-exported env (``DSTPU_INIT_RETRIES`` /
    ``DSTPU_INIT_RETRY_BACKOFF_S``) wins over both.
    """
    heartbeat: bool = False
    heartbeat_dir: Optional[str] = None
    heartbeat_interval_s: float = Field(1.0, ge=0.0)
    collective_timeout_s: Optional[float] = Field(None, gt=0.0)
    init_retries: int = Field(3, ge=0)
    init_retry_backoff_s: float = Field(0.5, ge=0.0)

    def model_validate(self):
        import os

        from .heartbeat import HEARTBEAT_DIR_ENV
        # the agent-exported env satisfies the requirement (it's the remedy
        # the error names): heartbeat=true under supervision must not turn
        # every worker into a restartable config error the agent respawns
        # until the budget burns
        if self.heartbeat and not self.heartbeat_dir and not os.environ.get(HEARTBEAT_DIR_ENV):
            raise ValueError("fault_tolerance.heartbeat=true needs heartbeat_dir "
                             "(or launch under the elastic agent, which exports "
                             "DSTPU_HEARTBEAT_DIR and overrides this section)")


class ServingResilienceConfig(ConfigModel):
    """Serving-side overload policy for the v2 ragged engine
    (inference/v2/admission.py — the serving analog of the training-side
    checkpoint/watchdog resilience knobs; no single reference section, this
    models FastGen/MII request rejection + flush as explicit policy).

    Admission: requests enter a bounded, priority-aware queue and are load-shed
    with a structured retryable/fatal reason BEFORE any KV allocation when
    ``max_queue_depth`` or ``shed_kv_utilization`` is crossed
    (``shed_kv_utilization=1.0`` disables pressure shedding: requests queue
    until the pool frees instead).  ``default_ttl_s`` gives every request a
    deadline (per-call ``generate(ttl_s=...)`` overrides); expired requests are
    evicted between steps — never mid-forward — with their blocks reclaimed.

    Scheduling: ``preemption`` lets a starved decode step reclaim KV blocks
    from the newest prefilling sequence (rolled back to a block boundary and
    requeued, at most ``max_preemptions`` times; once every candidate victim
    is exhausted the newest is evicted with status
    ``preempt_requeued_exhausted``).  ``stall_watchdog_steps`` bounds
    live-but-unschedulable loops: after that many steps without progress the
    engine raises ``ServingStalledError`` carrying a full state snapshot
    (strict mode) or fails the stuck requests and keeps serving the rest.
    """
    max_queue_depth: int = Field(0, ge=0)  # 0 => unbounded admission queue
    shed_kv_utilization: float = Field(1.0, gt=0.0, le=1.0)
    default_ttl_s: Optional[float] = Field(None, gt=0.0)
    max_live_seqs: int = Field(0, ge=0)  # 0 => bounded only by the scheduler
    preemption: bool = True
    max_preemptions: int = Field(2, ge=0)
    stall_watchdog_steps: int = Field(100, ge=1)


class ServingFastpathConfig(ConfigModel):
    """Serving hot-path policy for the v2 ragged engine
    (inference/v2/fastpath.py — no reference section; this models the
    orchestration-overhead levers FastGen gets from CUDA graphs + pinned
    ragged batch buffers, translated to XLA: persistent device-resident
    batch state, deferred host syncs, and fused decode slices).

    ``enabled`` turns the whole fast path off, falling back to the
    rebuild-and-upload-per-step reference loop (the equivalence oracle the
    fastpath tests diff against).  ``pipeline_depth=1`` defers the sampled-
    token fetch by one step so host-side scheduling of step N+1 overlaps
    device execution of step N (0 = fully synchronous); the pipeline
    disengages automatically whenever admission tickets are queued or any
    live sequence carries a deadline, so PR-4 eviction semantics are
    bit-exact.  ``fusion_min_steps`` is the smallest remaining-token window
    worth fusing into one on-device decode burst.  ``prewarm_buckets``
    bounds how many (batch, chunk, table) bucket programs ``generate()``
    AOT-compiles at intake so mid-wave recompiles stop stalling p95.

    The whole fast path applies unchanged under TP×DP meshes (ISSUE 15):
    the persistent batch buffers replicate over the engine's mesh
    (``NamedSharding(mesh, PartitionSpec())``) while params/KV keep their
    sharded specs, the delta scatter compiles as a sharded donated update,
    and prewarm lowers against sharded avals — no knob selects this; the
    engine's topology does.
    """
    enabled: bool = True
    pipeline_depth: int = Field(1, choices=(0, 1))
    fusion_min_steps: int = Field(2, ge=2)
    prewarm_buckets: int = Field(4, ge=0)


class ServingSpecDecodeConfig(ConfigModel):
    """Speculative decoding on the v2 engine's fused decode path (ISSUE 20 —
    inference/v2/spec_decode.py; the XLA translation of Leviathan et al.'s
    draft/verify with exact rejection sampling, applied per-sequence inside
    the Orca-style ragged batch).

    ``enabled`` arms the spec path: on every pure-decode fused window a
    drafter proposes ``k`` tokens per sequence, the target model verifies all
    of them in ONE batched forward over the paged KV pool, and on-device
    rejection sampling accepts the longest valid prefix plus one resampled
    token — between 1 and k+1 tokens per sequence per round, with the output
    distribution provably the target model's (token-identical to spec-off
    under greedy decode; distribution-identical under temperature/top-k/
    top-p sampling).  Off (the default) the engine is byte-identical to the
    pre-spec stack.

    ``drafter`` picks the proposal source: ``"ngram"`` is the zero-weight
    prompt-lookup drafter (longest-suffix n-gram match over the sequence's
    own token history — no second model, proposals cost pure host python);
    ``"model"`` uses a small draft model from the model zoo attached via
    ``InferenceEngineV2.attach_draft_model(...)`` (greedy-drafted against
    its own paged pool, replicated under the engine's mesh).

    ``k`` caps the draft length; the ADAPTIVE controller moves the live k
    through a small static ladder (1, 3, 7, 15, ... capped at ``k`` —
    verify widths k+1 stay powers of two) on an EWMA of the acceptance rate
    (``ewma_alpha``; raise above ``raise_threshold``, lower below
    ``lower_threshold``), so every verify program is one of a handful of
    prewarmable bucket shapes and a drifting acceptance rate can never
    recompile mid-serve.  At the k=1 floor the engine falls back to the
    plain fused burst (zero spec overhead, zero recompiles) and re-probes
    spec every ``probe_every`` fused rounds.  ``adaptive_k=False`` pins k.

    ``ngram_max``/``ngram_min`` bound the suffix-match length the n-gram
    drafter tries (longest first).
    """
    enabled: bool = False
    drafter: str = Field("ngram", choices=("ngram", "model"))
    k: int = Field(4, ge=1)
    adaptive_k: bool = True
    ewma_alpha: float = Field(0.3, gt=0.0, le=1.0)
    raise_threshold: float = Field(0.7, ge=0.0, le=1.0)
    lower_threshold: float = Field(0.3, ge=0.0, le=1.0)
    probe_every: int = Field(16, ge=1)
    ngram_max: int = Field(3, ge=1)
    ngram_min: int = Field(1, ge=1)


class ServingTracingConfig(ConfigModel):
    """Request-lifecycle tracing + SLO latency histograms for the v2 ragged
    engine (monitor/tracing.py wired through inference/v2 — no reference
    section; this models the per-request observability vLLM/Orca-class
    systems report: TTFT/TBT/e2e percentiles and per-request span chains).

    ``enabled`` turns on per-uid span recording (queue_wait → prefill →
    decode, requeue spans around preemptions, one terminal event matching the
    request's ``RequestResult`` status) and the TTFT/TBT/e2e histograms.
    Tracing consumes ONLY the engine's injectable clock at host-touch points
    (admission, wave boundaries, token materialization) and adds zero device
    syncs — the serving fast path's counter invariants hold with tracing on.
    ``trace_jsonl`` exports each completed trace as a ``kind: trace`` record
    through the attached telemetry collector's JSONL sink;
    ``chrome_trace_path`` additionally buffers Chrome-trace-event JSON
    (load in Perfetto / chrome://tracing) written by
    ``RequestTracer.write_chrome_trace()`` (the engine writes it at the end
    of each ``generate()`` call).

    The flight recorder — a bounded ring of the last
    ``flight_recorder_events`` engine events (dispatch/absorb/flush/burst/
    preempt/shed/admit/expire/stall) dumped into ``ServingStalledError``
    snapshots and ``health()`` — is ALWAYS on; the knob only sizes the ring.

    Histogram buckets are logarithmic: ``histogram_buckets_per_decade``
    buckets per decade starting at ``histogram_min_s`` seconds; quantiles
    return deterministic bucket representatives (relative error bounded by
    one bucket width), and same-shaped histograms merge exactly.
    """
    enabled: bool = False
    trace_jsonl: bool = True
    chrome_trace_path: Optional[str] = None
    flight_recorder_events: int = Field(256, ge=16)
    histogram_buckets_per_decade: int = Field(6, ge=1, le=100)
    histogram_min_s: float = Field(1e-5, gt=0.0)


class ServingPerfConfig(ConfigModel):
    """Serving performance observatory for the v2 ragged engine (ISSUE 16 —
    monitor/perf.py wired through inference/v2; the serving twin of the
    reference's training-only ``wall_clock_breakdown`` + flops profiler).

    ``enabled`` turns on the StepPhaseProfiler: per-iteration phase spans
    (admission_pump / scatter_upload / dispatch / absorb_patch / burst /
    flush / expire / other) charged by reading the engine's injectable clock
    at phase boundaries, accumulated into deterministic-quantile streaming
    histograms, exported as ``serving_phase_*`` metric families, Chrome-trace
    phase tracks and an every-``phase_budget_every``-iterations phase-budget
    flight-recorder line.  Off by default: phase marks READ the clock, and
    deadline/TTL semantics under an injected deterministic clock must not
    shift when the observatory is toggled — with it off, the engine performs
    zero additional clock reads, so tokens and ``ServeCounters`` are
    byte-identical either way (the perf-smoke lane proves it).

    The CompileLedger, the slot counters of ``ServeCounters`` and the serve
    loop's ``jax.profiler`` spans are ALWAYS on regardless of ``enabled`` —
    they add no clock reads and no device work, and the ledger is the single
    source of truth behind ``ServeCounters.compiles``.  (The roofline gauges
    from ``cost_analysis()`` and their three knobs went in ISSUE 24.)
    """
    enabled: bool = False
    # emit a phase-budget flight-recorder line every N serve iterations
    phase_budget_every: int = Field(50, ge=1)
    # phase-span histogram shape; min_s is two decades below the request
    # histograms' 1e-5 — phase spans are sub-iteration slivers
    histogram_buckets_per_decade: int = Field(6, ge=1, le=100)
    histogram_min_s: float = Field(1e-7, gt=0.0)


class ServingFaultToleranceConfig(ConfigModel):
    """Serving-side crash durability + supervised restart for the v2 ragged
    engine (inference/v2/journal.py + inference/v2/supervisor.py — the
    serving analog of the elastic training supervision in PR 7; no single
    reference section: the reference pairs its inference runtime with
    elastic checkpoint-backed recovery, but a serving-process crash there
    still loses every queued and in-flight request).

    ``enabled`` arms the durable request journal: one CRC-framed record per
    admitted request (uid, prompt, priority, TTL, budget, sampling key),
    batched emitted-token deltas appended at wave-boundary flushes (the host
    already holds those tokens — zero extra device syncs), and a terminal
    record mirroring each ``RequestResult``.  ``journal_path`` names the WAL
    file (the supervisor-exported ``DSTPU_SERVING_JOURNAL`` env arms it with
    no config changes, the same contract the elastic agent uses for
    heartbeats); ``fsync_every`` fsyncs the journal every N wave-boundary
    flushes (strict mode also writes + fsyncs admits and terminals
    eagerly).  0 is throughput mode: no fsync until close, but every
    record reaches OS pages at the NEXT wave boundary (the serve loop
    flushes each iteration; the serve call's exit always flushes), so a
    process crash loses at most one iteration's records — which recovery
    absorbs by re-serving from the surviving journaled prefix.

    ``heartbeat`` stamps a serve-iteration liveness file (phase ``serving``)
    through ``runtime/heartbeat.py`` — zero device syncs, same writer the
    training engine uses; ``ServingSupervisor`` arms it via env for its
    workers, and a stale stamp (``hang_timeout_s``, after
    ``startup_grace_s``) or a dead process both count as one failure.

    ``max_restarts`` within ``restart_window_s`` bounds the supervisor's
    restart budget; past it the supervisor degrades to drain-only mode —
    new admissions are shed with a structured retryable reason, recoverable
    journal work gets one final attempt, and anything still unfinished is
    finalized as ``failed`` directly in the journal.  Never a hang.
    """
    enabled: bool = False
    journal_path: Optional[str] = None
    fsync_every: int = Field(1, ge=0)
    heartbeat: bool = False
    heartbeat_dir: Optional[str] = None
    heartbeat_interval_s: float = Field(0.2, ge=0.0)
    max_restarts: int = Field(2, ge=0)
    restart_window_s: float = Field(300.0, gt=0.0)
    hang_timeout_s: float = Field(30.0, gt=0.0)
    startup_grace_s: float = Field(120.0, ge=0.0)
    poll_interval_s: float = Field(0.05, gt=0.0)

    def model_validate(self):
        import os

        from .heartbeat import HEARTBEAT_DIR_ENV, SERVING_JOURNAL_ENV
        # same remedy-is-the-env contract as FaultToleranceConfig: a worker
        # under ServingSupervisor gets both paths from the environment, so
        # enabling the section without explicit paths is only an error when
        # nothing supervises the process
        if self.enabled and not self.journal_path \
                and not os.environ.get(SERVING_JOURNAL_ENV):
            raise ValueError("serving_fault_tolerance.enabled=true needs "
                             "journal_path (or launch under ServingSupervisor, "
                             "which exports DSTPU_SERVING_JOURNAL and overrides "
                             "this section)")
        if self.heartbeat and not self.heartbeat_dir \
                and not os.environ.get(HEARTBEAT_DIR_ENV):
            raise ValueError("serving_fault_tolerance.heartbeat=true needs "
                             "heartbeat_dir (or launch under ServingSupervisor, "
                             "which exports DSTPU_HEARTBEAT_DIR)")


class ServingFleetConfig(ConfigModel):
    """Fleet front-end over N supervised serving replicas
    (inference/v2/router.py — the horizontal-scale layer over the
    single-engine stack: Orca/vLLM-class deployments put a health-gated
    router in front of replicated engines; no reference section, the
    reference delegates fleet routing to external serving infra).

    ``replicas`` sizes the fleet the router fronts.  Admission is
    least-loaded-healthy: the router scores each replica from its last
    ``health()`` snapshot (queue depth weighted by ``queue_weight``, KV
    utilization by ``kv_weight``) and steers AWAY from any replica whose
    ``CapacityForecaster`` predicts KV exhaustion within
    ``exhaustion_steer_steps`` serve steps — pressure-avoidance before the
    replica ever sheds.  A snapshot older than ``health_stale_s`` (per its
    ``generated_at`` stamp) marks the replica unhealthy: a frozen replica's
    last-good gauges must not keep attracting traffic (the hang-worker
    failure mode).

    ``affinity_blocks`` > 0 routes shared-header prompts by prefix
    affinity: the chained token-block hash (the PR-13 ``PrefixCache``
    keying) of the prompt's leading full blocks picks a stable home
    replica, so one header's PrefixCache tree stays hot on one replica
    instead of lukewarm on all of them.  0 disables affinity (pure
    least-loaded).

    A retryable per-replica shed is never surfaced to the caller while
    budget remains: the router re-routes it up to ``max_reroutes`` times
    with exponential backoff (``backoff_base_s`` doubling per attempt,
    capped at ``backoff_max_s``), honoring the shed's ``retry_after_s``
    hint when the admission door supplied one.

    Failover: each replica keeps its own journal under its own
    ``ServingSupervisor`` (restart budget per ``serving_fault_tolerance``);
    a replica that exhausts its budget is drained and its journaled
    in-flight work MIGRATES to a healthy replica — emitted prefixes are
    copied into the target's journal with their ORIGINAL wall-clock admit
    stamps, so ``serve_recovered`` continues them byte-identically on
    their original TTL clocks.  Zero lost requests.
    """
    enabled: bool = False
    replicas: int = Field(2, ge=1)
    health_stale_s: float = Field(5.0, gt=0.0)
    affinity_blocks: int = Field(1, ge=0)  # full prompt blocks hashed; 0 = off
    max_reroutes: int = Field(3, ge=0)
    backoff_base_s: float = Field(0.05, ge=0.0)
    backoff_max_s: float = Field(2.0, gt=0.0)
    exhaustion_steer_steps: float = Field(32.0, gt=0.0)
    queue_weight: float = Field(1.0, ge=0.0)
    kv_weight: float = Field(8.0, ge=0.0)
    namespace: str = "dstpu"


class ServingQosConfig(ConfigModel):
    """Multi-tenant QoS policy over the v2 serving plane
    (inference/v2/qos.py — the *policy* layer on the existing admission /
    preemption / prefix-cache *mechanisms*; no reference section, the
    reference's ragged engine is single-tenant and delegates isolation to
    external serving infra).

    Every request carries a ``tenant`` id and a service class
    (``interactive`` / ``batch`` / ``best_effort``).  With
    ``enabled=false`` (the default) the layer is inert: requests get the
    default tenant, dequeue order, prefix-cache keying and preemption
    victims are byte-identical to the policy-free engine.

    Front-door quotas (checked BEFORE any KV allocation, like every other
    shed): ``tenant_tokens_per_s`` rate-limits each tenant's admitted
    token volume through a token bucket of capacity
    ``tenant_token_burst`` (0 disables; burst defaults to one second of
    rate).  ``tenant_max_kv_blocks`` caps a tenant's RESIDENT KV blocks;
    a tenant at its cap is shed rather than allowed to starve its
    neighbors' pool.  Both produce a structured, retryable
    ``quota_exceeded`` shed whose ``retry_after_s`` is the exact bucket
    refill time (rate) or a pressure-scaled hint (KV), riding the
    FleetRouter's existing backoff path.  ``tenants`` maps tenant id to
    per-tenant overrides (``tokens_per_s`` / ``token_burst`` /
    ``max_kv_blocks``).

    Weighted-fair dequeue: the admission queue becomes per-class with
    deficit-round-robin on TOKEN cost — each visit grants a class
    ``drr_quantum_tokens * weight`` deficit, so interactive (weight 8 by
    default) drains ~8x the token volume of best-effort per round while
    best-effort still makes progress (starvation-free by construction).
    Priority ordering within a class is preserved.  The DRR state is pure
    arrival-sequence arithmetic — no clock reads — so dequeue order is
    FakeClock-deterministic and rerun-identical.

    ``preempt_over_quota`` steers KV-pressure preemption: victims are
    preferred over-quota-tenant first, then lower class, then the PR-4
    newest-prefill heuristic as the tie-break.

    Isolation: the tenant id is folded into the chained block-hash key,
    so cross-tenant prompts can NEVER share prefix blocks (closes the
    cross-tenant cache-timing side-channel); the default tenant keeps the
    legacy keying, so single-tenant sharing is unchanged.
    """
    enabled: bool = False
    default_class: str = Field("interactive",
                               choices=("interactive", "batch", "best_effort"))
    interactive_weight: int = Field(8, ge=1)
    batch_weight: int = Field(2, ge=1)
    best_effort_weight: int = Field(1, ge=1)
    drr_quantum_tokens: int = Field(64, ge=1)
    tenant_tokens_per_s: float = Field(0.0, ge=0.0)  # 0 => no rate quota
    tenant_token_burst: float = Field(0.0, ge=0.0)  # 0 => 1s of rate
    tenant_max_kv_blocks: int = Field(0, ge=0)  # 0 => no KV quota
    tenants: Dict[str, Any] = Field(dict)  # per-tenant quota overrides
    preempt_over_quota: bool = True


class KVObservabilityConfig(ConfigModel):
    """Block-level observability over the paged KV pool for the v2 ragged
    engine (inference/v2/kv_metrics.py — no reference section: the CUDA
    reference's monitor reports aggregate throughput and has no block-granular
    pool view; vLLM-class systems treat block bookkeeping as the substrate for
    prefix caching and eviction policy, which is exactly what this measures
    ahead of those ROADMAP items).

    ``enabled`` arms the block census (per-block owner/age/residency with
    utilization, fragmentation and block-age rollups), the
    ``PrefixObservatory`` (counterfactual prefix-cache win per serve pass:
    duplicate token-block hashes across live+admitted requests, prefill
    tokens sharing would have saved, would-be hit-rate), and the capacity
    forecaster (EWMA block alloc/free rates per iteration yielding a
    steps-to-exhaustion gauge next to the shed/preempt counters).  Everything
    reads host-side ints the allocator and ragged manager already own — ZERO
    device syncs (dslint's host-sync rule scans ``kv_metrics.py`` whole-file,
    and the kv-obs smoke proves byte-identical fastpath ``ServeCounters``
    observability on vs off).

    ``invariant_check`` re-verifies after every serve pass that the census's
    owned-block set exactly partitions against the allocator free list — the
    PR-4 double-free guard as a continuously-checked pool invariant
    (``CensusInvariantError`` names the offending uid/block).
    ``pressure_steps`` is the steps-to-exhaustion threshold below which a
    ``kv_pressure`` event lands in the flight recorder (edge-triggered:
    entered/cleared, not once per iteration); ``ewma_alpha`` smooths the
    forecaster's alloc/free rates.
    """
    enabled: bool = True
    invariant_check: bool = True
    ewma_alpha: float = Field(0.2, gt=0.0, le=1.0)
    pressure_steps: float = Field(64.0, gt=0.0)
    age_buckets_per_decade: int = Field(6, ge=1, le=100)


class ServingPrefixCacheConfig(ConfigModel):
    """Copy-on-write prefix caching over the paged KV pool for the v2 ragged
    engine (inference/v2/ragged_manager.py ``PrefixCache`` — the realized
    form of vLLM-style block-granular prefix reuse / SGLang RadixAttention,
    keyed on the same chained token-block hashes PR 12's
    ``PrefixObservatory`` measures the counterfactual with).

    ``enabled`` arms the tree: an admitted request whose leading FULL prompt
    blocks match live, fully-computed blocks maps them read-only (allocator
    refcount +1 per mapping; shared KV capacity counted once) and only
    prefills its divergent tail — cutting TTFT and prefill FLOPs by exactly
    the hit-rate the observatory predicts, at zero device cost when nothing
    shares (the fastpath ServeCounters are byte-identical on a no-sharing
    workload).

    ``cow`` allows the copy-on-write block copy for prompts cached to their
    LAST token: the final block's KV is duplicated into a private block so
    the one recomputed position (needed for first-token logits) never writes
    a shared block.  Off, such prompts simply recompute their final block.

    ``defer_shared_prefill`` lets the scheduler hold a prefill chunk for ONE
    step when a sequence already scheduled this step is computing the exact
    block it needs — same-wave duplicates of one header become a one-step
    delay plus a cache hit instead of duplicate prefill.
    """
    enabled: bool = True
    cow: bool = True
    defer_shared_prefill: bool = True


class OpsServerConfig(ConfigModel):
    """Pull-based ops endpoints (monitor/metrics.py + monitor/ops_server.py —
    the PULL counterpart of the reference's push-only ``monitor/`` backends:
    a Prometheus ``/metrics`` endpoint plus JSON ``/healthz``/``/statez``
    probes over everything PRs 1-8 measure).

    ``enabled`` starts a stdlib ``ThreadingHTTPServer`` on ``host:port``
    (``port=0`` = ephemeral; read it from the attach point's ``.ops.port``)
    serving ONLY host-side cached snapshots — the owning loop refreshes the
    cache at host-touch points it already pays for, throttled to one refresh
    per ``refresh_interval_s``, so a scrape can never trigger a device sync
    or race a mutating step (dslint's host-sync rule scans the whole ops
    plane).  The serving engine refreshes on its injectable clock; training
    refreshes at the telemetry record boundary.

    ``textfile_dir`` additionally publishes this process's registry as
    atomic per-rank files (``ops.rank<R>.json`` exact-merge snapshot +
    ``ops.rank<R>.prom`` rendered textfile).  The elastic agent and the
    ``ServingSupervisor`` export ``DSTPU_OPS_DIR`` to their workers (the
    heartbeat env contract) and merge the snapshots into one fleet-level
    endpoint whose counters stay monotone across worker restarts; the env
    wins over this field, so supervised workers need no config changes.
    """
    enabled: bool = False
    host: str = "127.0.0.1"
    port: int = Field(0, ge=0, le=65535)  # 0 => ephemeral
    refresh_interval_s: float = Field(0.25, ge=0.0)
    textfile_dir: Optional[str] = None
    namespace: str = "dstpu"


class NebulaConfig(ConfigModel):
    """Reference: top-level "nebula" section (nebula/config.py) — enabling it
    selects the async (background-writer) checkpoint engine."""
    allow_extra = True
    enabled: bool = False
    persistent_storage_path: Optional[str] = None
    persistent_time_interval: int = Field(100, ge=1)
    num_of_version_in_retention: int = Field(2, ge=1)
    enable_nebula_load: bool = True


class DataSamplingConfig(ConfigModel):
    """Reference: data_efficiency.data_sampling (runtime/data_pipeline/config.py:37)
    — the curriculum_learning sub-dict feeds CurriculumScheduler; the reference's
    multi-metric ``curriculum_metrics`` form is accepted, with the ``seqlen``
    metric driving batch truncation (the reference's default difficulty proxy)."""
    allow_extra = True
    enabled: bool = True
    num_workers: int = 0
    curriculum_learning: Dict[str, Any] = Field(dict)


class DataRoutingConfig(ConfigModel):
    """Reference: data_efficiency.data_routing (random-LTD; runtime/data_pipeline/
    config.py:77).  The library lives in runtime/data_pipeline/random_ltd.py;
    models opt in by wrapping their layer stack (initialize() warns loudly when
    the section is enabled, since an opaque loss_fn can't be rewritten)."""
    allow_extra = True
    enabled: bool = False
    random_ltd: Dict[str, Any] = Field(dict)


class DataEfficiencyConfig(ConfigModel):
    """Reference: DeepSpeedDataEfficiencyConfig (runtime/data_pipeline/config.py:12),
    activated through the engine's dataloader (engine.deepspeed_io:1686)."""
    allow_extra = True
    enabled: bool = False
    seed: int = Field(1234, ge=0)
    data_sampling: DataSamplingConfig = Field(DataSamplingConfig)
    data_routing: DataRoutingConfig = Field(DataRoutingConfig)

    def curriculum_dict(self) -> Optional[Dict[str, Any]]:
        """The CurriculumScheduler config when curriculum sampling is active,
        else None.  Accepts both the flat schedule form and the reference's
        ``curriculum_metrics: {seqlen: {...}}`` nesting."""
        cl = dict(self.data_sampling.curriculum_learning or {})
        if not (self.enabled and self.data_sampling.enabled and cl.pop("enabled", False)):
            return None
        metrics = cl.pop("curriculum_metrics", None)
        if metrics:
            name = "seqlen" if "seqlen" in metrics else next(iter(metrics))
            if len(metrics) > 1:
                logger.warning(f"data_efficiency curriculum_metrics: multiple metrics "
                               f"configured; using {name!r} for difficulty (seqlen truncation)")
            return dict(metrics[name])
        return cl or None


class TrainingConfig(ConfigModel):
    """Top-level config — analog of ``DeepSpeedConfig`` (runtime/config.py:687).

    Accepts the same key spellings as a DeepSpeed JSON config where the concept
    carries over.  Unknown top-level keys are accepted with a loud warning (so
    reference configs with not-yet-modeled sections still load); sub-models are
    strict and raise, matching the reference's per-section validation.
    """
    allow_extra = "warn"

    train_batch_size: Optional[int] = Field(None, ge=1)
    train_micro_batch_size_per_gpu: Optional[int] = Field(None, ge=1)
    gradient_accumulation_steps: Optional[int] = Field(None, ge=1)
    steps_per_print: int = Field(10, ge=1)
    gradient_clipping: float = Field(0.0, ge=0.0)
    prescale_gradients: bool = False
    gradient_predivide_factor: float = Field(1.0, gt=0.0)
    sparse_gradients: bool = False
    communication_data_type: Optional[str] = None
    seed: int = 1234

    optimizer: Optional[OptimizerConfig] = None
    scheduler: Optional[SchedulerConfig] = None
    fp16: FP16Config = Field(FP16Config)
    bf16: Optional[BF16Config] = None
    zero_optimization: ZeroConfig = Field(ZeroConfig)
    activation_checkpointing: ActivationCheckpointingConfig = Field(ActivationCheckpointingConfig)
    comms_logger: CommsLoggerConfig = Field(CommsLoggerConfig)
    monitor_config: Optional[MonitorConfig] = None
    tensorboard: TensorBoardConfig = Field(TensorBoardConfig)
    wandb: WandbConfig = Field(WandbConfig)
    csv_monitor: CSVConfig = Field(CSVConfig)
    flops_profiler: FlopsProfilerConfig = Field(FlopsProfilerConfig)
    telemetry: TelemetryConfig = Field(TelemetryConfig)
    mesh: MeshConfig = Field(MeshConfig)
    gradient_compression: GradientCompressionConfig = Field(GradientCompressionConfig)
    sparse_attention: Optional[SparseAttentionConfig] = None
    data_efficiency: DataEfficiencyConfig = Field(DataEfficiencyConfig)
    # legacy pre-data_efficiency curriculum section (reference runtime/config.py
    # ``get_curriculum_params`` — curriculum_type/min/max/schedule keys)
    curriculum_learning: Optional[Dict[str, Any]] = None
    checkpoint: CheckpointSectionConfig = Field(CheckpointSectionConfig)
    # training-side liveness + bounded collectives (heartbeat stamps, hang
    # conversion, process-group setup retries); the elastic agent's env
    # exports override/augment this section for supervised workers
    fault_tolerance: FaultToleranceConfig = Field(FaultToleranceConfig)
    nebula: NebulaConfig = Field(NebulaConfig)
    # serving-side resilience thresholds; consumed by inference/v2 (the
    # InferenceConfig carries the same section so a serving-only config and a
    # combined train+serve config spell it identically)
    serving_resilience: ServingResilienceConfig = Field(ServingResilienceConfig)
    # serving hot-path knobs (device-resident batch state, step pipelining,
    # adaptive decode fusion) — same dual-spelling contract as above
    serving_fastpath: ServingFastpathConfig = Field(ServingFastpathConfig)
    # speculative decoding on the fused decode path (draft/verify with exact
    # rejection sampling) — same dual-spelling contract as above
    serving_spec_decode: ServingSpecDecodeConfig = Field(ServingSpecDecodeConfig)
    # request-lifecycle tracing, SLO latency histograms, flight recorder —
    # same dual-spelling contract as above
    serving_tracing: ServingTracingConfig = Field(ServingTracingConfig)
    # serving crash durability (request journal) + supervised restart —
    # same dual-spelling contract as above
    serving_fault_tolerance: ServingFaultToleranceConfig = Field(ServingFaultToleranceConfig)
    # pull-based ops endpoints (/metrics Prometheus exposition + /healthz +
    # /statez) and per-rank metrics textfiles — same dual-spelling contract
    ops_server: OpsServerConfig = Field(OpsServerConfig)
    # block-level KV-pool observability (census + prefix-sharing opportunity
    # + capacity forecast) — same dual-spelling contract as above
    serving_kv_observability: KVObservabilityConfig = Field(KVObservabilityConfig)
    # copy-on-write prefix caching over the paged KV pool — same
    # dual-spelling contract as above
    serving_prefix_cache: ServingPrefixCacheConfig = Field(ServingPrefixCacheConfig)
    # serving performance observatory (phase attribution, compile ledger)
    # — same dual-spelling contract as above
    serving_perf: ServingPerfConfig = Field(ServingPerfConfig)
    # fleet front-end over N supervised replicas (health-gated routing,
    # prefix affinity, journaled failover migration) — same dual-spelling
    # contract as above
    serving_fleet: ServingFleetConfig = Field(ServingFleetConfig)
    # multi-tenant QoS (priority classes, per-tenant quotas, weighted-fair
    # dequeue, tenant-keyed prefix isolation) — same dual-spelling contract
    # as above
    serving_qos: ServingQosConfig = Field(ServingQosConfig)

    wall_clock_breakdown: bool = False
    memory_breakdown: bool = False
    # train-loop watchdog: abort after this many CONSECUTIVE bad steps — fp16
    # overflow-skips, or non-finite loss/grad-norm on bf16/fp32 (which have no
    # overflow-skip and would otherwise silently train on NaNs forever).
    # 0 disables; enabling adds one host value-fetch (device sync) per step
    # when telemetry/wall_clock_breakdown haven't already paid it.
    max_consecutive_skips: int = Field(0, ge=0)
    dump_state: bool = False
    checkpoint_tag_validation: str = Field("Warn", choices=("Ignore", "Warn", "Fail", "ignore", "warn", "fail"))
    load_universal_checkpoint: bool = False
    use_node_local_storage: bool = False
    elasticity: Optional[Dict[str, Any]] = None
    autotuning: Optional[Dict[str, Any]] = None  # parsed by autotuning.AutotuningConfig

    def model_validate(self):
        if self.fp16.enabled and self.bf16 is not None and self.bf16.enabled:
            raise ValueError("fp16 and bf16 cannot both be enabled")
        if self.bf16 is None:
            # TPU-first default: bf16 on unless fp16 explicitly requested.
            object.__setattr__(self, "bf16", BF16Config(enabled=not self.fp16.enabled))
        if self.checkpoint.tag_validation is not None:
            object.__setattr__(self, "checkpoint_tag_validation", self.checkpoint.tag_validation)
        if self.memory_breakdown and not self.telemetry.memory_breakdown:
            # the reference's top-level memory_breakdown key routes to the same
            # see_memory_usage cadence the telemetry section controls
            object.__setattr__(self.telemetry, "memory_breakdown", True)

    def checkpoint_engine_kind(self) -> str:
        """Engine plug-in selection (reference _configure_checkpointing,
        engine.py:921): the "nebula" section wins, else checkpoint.checkpoint_engine."""
        if self.nebula.enabled:
            return "async"
        return self.checkpoint.checkpoint_engine

    def effective_curriculum(self) -> Optional[Dict[str, Any]]:
        """Curriculum schedule dict from either the data_efficiency section or
        the legacy top-level curriculum_learning section; None when inactive."""
        cur = self.data_efficiency.curriculum_dict()
        if cur is not None:
            return cur
        legacy = dict(self.curriculum_learning or {})
        if legacy.pop("enabled", False):
            return legacy
        return None

    # --- batch-size triple reconciliation (reference runtime/config.py:837) ---
    def resolve_batch_sizes(self, dp_world_size: int):
        """Return (train_batch, micro_batch, gas), solving for any missing member of
        train_batch = micro_batch * gas * dp_world_size; raises on inconsistency."""
        tb, mb, gas = (self.train_batch_size, self.train_micro_batch_size_per_gpu,
                       self.gradient_accumulation_steps)
        if tb is not None and mb is not None and gas is not None:
            if tb != mb * gas * dp_world_size:
                raise ValueError(
                    f"train_batch_size={tb} != micro_batch({mb}) * gas({gas}) * dp_world({dp_world_size})")
        elif tb is not None and mb is not None:
            if tb % (mb * dp_world_size) != 0:
                raise ValueError(f"train_batch_size={tb} not divisible by micro_batch*dp={mb * dp_world_size}")
            gas = tb // (mb * dp_world_size)
        elif tb is not None and gas is not None:
            if tb % (gas * dp_world_size) != 0:
                raise ValueError(f"train_batch_size={tb} not divisible by gas*dp={gas * dp_world_size}")
            mb = tb // (gas * dp_world_size)
        elif mb is not None:
            gas = gas or 1
            tb = mb * gas * dp_world_size
        elif tb is not None:
            mb = tb // dp_world_size
            if mb == 0 or tb % dp_world_size != 0:
                raise ValueError(f"train_batch_size={tb} not divisible by dp_world_size={dp_world_size}")
            gas = 1
        else:
            raise ValueError("One of train_batch_size or train_micro_batch_size_per_gpu must be set")
        object.__setattr__(self, "train_batch_size", tb)
        object.__setattr__(self, "train_micro_batch_size_per_gpu", mb)
        object.__setattr__(self, "gradient_accumulation_steps", gas)
        return tb, mb, gas

    @property
    def precision_dtype(self):
        import jax.numpy as jnp
        if self.fp16.enabled:
            return jnp.float16
        if self.bf16 is not None and self.bf16.enabled:
            return jnp.bfloat16
        return jnp.float32


def load_config(config: Union[str, dict, TrainingConfig, None]) -> TrainingConfig:
    """Parse a config path / dict / model into a TrainingConfig.

    Analog of DeepSpeedConfig.__init__ (runtime/config.py:699) accepting either a
    JSON file path or an already-parsed dict.
    """
    if config is None:
        return TrainingConfig()
    if isinstance(config, TrainingConfig):
        return config
    if isinstance(config, str):
        with open(config, "r") as fh:
            config = json.load(fh)
    if not isinstance(config, dict):
        raise TypeError(f"config must be a path, dict, or TrainingConfig; got {type(config)}")
    known_zero_aliases = {"zero_allow_untested_optimizer", "zero_force_ds_cpu_optimizer"}
    config = {k: v for k, v in config.items() if k not in known_zero_aliases}
    return TrainingConfig(**config)
