"""Training engine.

TPU-native analog of ``DeepSpeedEngine`` (runtime/engine.py:179).  The reference
wraps a torch module and intercepts ``forward/backward/step`` with hooks; here the
engine owns a **pure jitted train step** ``(state, batch) -> (state, metrics)``
compiled once over the device mesh, with ZeRO expressed as sharding annotations
(see runtime/zero/sharding.py).  The imperative ``forward/backward/step`` calling
convention is kept as a thin micro-batch-accumulating shim so reference training
loops port over unchanged.

Precision model (reference BF16_Optimizer semantics, runtime/bf16_optimizer.py:30):
state holds ONE fp32 master copy of the params (sharded over dp from ZeRO-1 up);
the bf16/fp16 compute copy is cast inside the step and — at stage<3 — constrained
replicated so XLA gathers the half-size copy (the analog of allgathering updated
bit16 partitions after the sharded step, stage_1_and_2.py:1786).
"""

import json
import os
import time
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from ..compat import shard_map
from ..monitor.monitor import MonitorMaster
from ..monitor import compile_events, program_scopes
from ..monitor.telemetry import TelemetryCollector
from ..parallel.mesh import MeshTopology, set_topology
from ..utils.logging import log_dist, logger
from ..utils.memory import see_memory_usage
from ..utils.timer import ThroughputTimer
from . import lr_schedules, optimizers
from .checkpointing import (CheckpointError, _is_rank0, find_latest_valid_tag,
                            load_checkpoint_dir, save_checkpoint_with_retries,
                            sweep_retention, validate_checkpoint_tag)
from .heartbeat import OPS_DIR_ENV, build_heartbeat
from .grad_accum import accumulate_micro_grads
from .config import TrainingConfig, load_config
from .optimizers import (LossScaleState, clip_by_global_norm, global_grad_norm, has_overflow, init_loss_scale,
                         update_loss_scale)
from .zero.sharding import ShardingPlan, build_sharding_plan


class NonFiniteLossError(RuntimeError):
    """The train-loop watchdog tripped: ``max_consecutive_skips`` successive
    steps produced a non-finite loss/grad-norm (bf16/fp32) or overflow-skipped
    (fp16) — the run is diverged and further steps only burn accelerator time."""


class TrainState(NamedTuple):
    """The entire training state as one sharded pytree."""
    step: jnp.ndarray  # int32 global step (optimizer steps taken)
    params: Any  # fp32 master params
    opt_state: Any
    loss_scale: Optional[LossScaleState]
    rng: jnp.ndarray


class StepMetrics(NamedTuple):
    loss: jnp.ndarray
    grad_norm: jnp.ndarray
    lr: jnp.ndarray
    skipped: jnp.ndarray  # bool: fp16 overflow skipped the update
    loss_scale: jnp.ndarray


def _mesh_config_for(config: TrainingConfig):
    """Honor zero_hpz_partition_size (reference zero/config.py:264) when the
    user didn't lay out the mesh: at stage 3 with hpZ requested and mesh axes
    left at defaults, factor the devices into data x fsdp with
    fsdp = hpz_partition_size (the secondary/intra-slice shard group)."""
    mesh_cfg = config.mesh
    hpz = config.zero_optimization.zero_hpz_partition_size
    other_axes = int(np.prod([s for a, s in mesh_cfg.axis_sizes().items()
                              if a not in ("data", "fsdp") and s != -1]))
    if (config.zero_optimization.stage >= 3 and hpz > 1
            and mesh_cfg.fsdp == 1 and mesh_cfg.data == -1
            and jax.device_count() % (hpz * other_axes) == 0):
        from .config import MeshConfig
        sizes = mesh_cfg.axis_sizes()
        sizes["fsdp"] = hpz
        mesh_cfg = MeshConfig(**sizes, axis_order=list(mesh_cfg.axis_order))
    return mesh_cfg


class Engine:
    """Wraps a loss function + params with distributed training mechanics.

    loss_fn(params, batch, rng) -> loss  (params arrive in compute dtype)
    """

    @compile_events.engine_init
    def __init__(self,
                 loss_fn: Callable,
                 params: Any,
                 config: TrainingConfig,
                 topology: Optional[MeshTopology] = None,
                 dp_world_size: Optional[int] = None,
                 tp_rules=None,
                 param_init_fn: Optional[Callable] = None,
                 layer_fn: Optional[Callable] = None,
                 head_fn: Optional[Callable] = None,
                 stem_fn: Optional[Callable] = None,
                 ltd_state: Optional[dict] = None):
        self.config = config
        self._stem_fn = stem_fn
        # random-LTD ramp state ({"keep", "scheduler"}) — train_batch re-jits
        # the step when the scheduler moves the kept-token budget
        self._ltd_state = ltd_state
        self.loss_fn = loss_fn
        self.topology = topology or MeshTopology.build(_mesh_config_for(config))
        set_topology(self.topology)
        self.dp_world_size = dp_world_size or self.topology.get_data_parallel_world_size()
        (self.train_batch_size, self.micro_batch_size,
         self.gradient_accumulation_steps) = config.resolve_batch_sizes(self.dp_world_size)

        self.zero_stage = config.zero_optimization.stage
        self.plan: ShardingPlan = build_sharding_plan(config.zero_optimization, self.topology, tp_rules=tp_rules)

        # optimizer
        opt_cfg = config.optimizer
        opt_params = dict(opt_cfg.params) if opt_cfg else {}
        self.base_lr = float(opt_params.pop("lr", 1e-3))
        self.optimizer = optimizers.get_optimizer(opt_cfg.type if opt_cfg else "adamw", **opt_params)

        # 1-bit optimizers: comm-coupled, so the engine owns their shard_map step
        # (reference fp16/onebit/adam.py restricts to non-ZeRO dp; same here)
        self._onebit = getattr(self.optimizer, "onebit", None)
        self._onebit_world = 1
        if self._onebit is not None:
            pure = all(self.topology.axis_size(a) == 1
                       for a in ("tensor", "sequence", "expert", "pipe"))
            if self.zero_stage != 0 or not pure:
                raise ValueError("1-bit optimizers require ZeRO stage 0 and a pure "
                                 "data-parallel mesh (reference onebit/adam.py compat)")
            if config.fp16.enabled:
                raise ValueError("1-bit optimizers require bf16/fp32 compute (sign "
                                 "compression would launder fp16 overflow)")
            self._onebit_world = int(np.prod([self.topology.axis_size(a)
                                              for a in self.plan.shard_axes]))

        # lr schedule
        sched_cfg = config.scheduler
        self.lr_schedule = lr_schedules.build_lr_schedule(sched_cfg.type if sched_cfg else None,
                                                          dict(sched_cfg.params) if sched_cfg else {},
                                                          base_lr=self.base_lr)
        # host-float reads of the schedule (offload/NVMe steps, engine.lr,
        # telemetry) evaluate on the CPU backend — never an accelerator
        # round-trip in the train hot loop
        self._host_lr = lr_schedules.host_lr_fn(self.lr_schedule)
        self.lr_scheduler = lr_schedules.LRScheduler(self.lr_schedule)

        self.compute_dtype = config.precision_dtype
        self.fp16_enabled = config.fp16.enabled
        self.monitor = MonitorMaster(config)
        self.telemetry = TelemetryCollector(config.telemetry, monitor=self.monitor,
                                            batch_size=self.train_batch_size)
        self._last_telemetry_record = None
        self._setup_account_told = False  # the set-up account goes to telemetry once
        # per-rank liveness stamps for the elastic agent (runtime/heartbeat.py):
        # armed by the fault_tolerance config section OR the agent-exported
        # DSTPU_HEARTBEAT_DIR env; the NULL writer otherwise (no-op stamps)
        self.heartbeat = build_heartbeat(config.fault_tolerance)
        # unconditional: this engine's config OWNS the process default, so a
        # timeout from an earlier engine's config can never leak into a later
        # engine (None resets to unbounded, the historical behavior)
        from ..comm import comm as _dist
        _dist.set_default_collective_timeout(config.fault_tolerance.collective_timeout_s)
        # pull-based ops plane (ISSUE 11): rank 0 serves /metrics (Prometheus
        # text over the telemetry collector's cached records) + /healthz +
        # /statez; every rank publishes per-rank snapshot/textfiles when the
        # elastic agent exported DSTPU_OPS_DIR (or ops_server.textfile_dir is
        # set), which the agent merges into one fleet endpoint.  The cache
        # refreshes at the train-step telemetry boundary — host values only
        self._ops = None
        self._ops_cfg = config.ops_server
        self._ops_rank = int(os.environ.get("RANK", "0") or 0)
        ops_dir = os.environ.get(OPS_DIR_ENV) or self._ops_cfg.textfile_dir
        if self._ops_cfg.enabled or ops_dir:
            from ..monitor.ops_server import OpsPublisher
            from .config import OpsServerConfig
            cfg = self._ops_cfg
            if cfg.enabled and not self.telemetry._is_rank0:
                # one endpoint per job: ranks > 0 publish exchange files only
                # (the agent merges them); a per-rank listener would fight
                # over the configured port across processes
                cfg = OpsServerConfig(enabled=False, host=cfg.host,
                                      refresh_interval_s=cfg.refresh_interval_s,
                                      textfile_dir=cfg.textfile_dir,
                                      namespace=cfg.namespace)
            self._ops = OpsPublisher(
                cfg,
                generation=int(os.environ.get("DSTPU_ELASTIC_RESTART", "0") or 0),
                ops_dir=ops_dir, rank=self._ops_rank, owner="training engine")
        self.ops = self._ops.server if self._ops is not None else None
        self.throughput = ThroughputTimer(batch_size=self.train_batch_size)
        self.global_steps = 0
        self.global_samples = 0
        # per-process counter bases for the ops plane: load_checkpoint moves
        # them to the restored position so exported counters stay
        # this-process-only (see _populate_ops_registry)
        self._ops_steps_base = 0
        self._ops_samples_base = 0
        self._micro_batches: list = []
        self._compiled_step = None
        self._compiled_eval = None
        self._ckpt_engine = None  # built lazily from config (checkpoint/nebula)
        self._consecutive_bad_steps = 0  # NaN/overflow watchdog counter
        # preemption (SIGTERM) best-effort final save: armed on the first
        # save_checkpoint() when checkpoint.save_on_preemption is set
        self._preempt_save_dir: Optional[str] = None
        self._preempt_prev_handler = None
        self._preempt_registered = False
        self._in_preempt_save = False

        act_cfg = config.activation_checkpointing
        if act_cfg.cpu_checkpointing or act_cfg.policy != "nothing_saveable":
            # remat is owned by the MODEL under the functional contract (the
            # loss_fn closes over jax.checkpoint) — same loud requested-but-
            # engine-cannot-apply pattern as the hpZ/qwZ knobs
            log_dist(
                f"activation_checkpointing requests policy="
                f"{'cpu_checkpointing (host-offloaded inputs)' if act_cfg.cpu_checkpointing else act_cfg.policy}: "
                f"apply it in the model config (LlamaConfig.remat_policy="
                f"{'offload_inputs' if act_cfg.cpu_checkpointing else act_cfg.policy!r}, "
                f"or runtime.activation_checkpointing.offload_checkpoint for custom "
                f"stacks) — the engine cannot rewrite remat inside an opaque loss_fn. "
                f"NOTE: host-offload remat is a PER-DEVICE lever (single chip or "
                f"inside shard_map); multi-device GSPMD jit rejects the placement "
                f"annotation (activation_checkpointing.py composition status)",
                ranks=[0])
        off = config.zero_optimization.offload_optimizer
        self.offload_device = off.device if (off is not None and off.device != "none") else None
        off_p = config.zero_optimization.offload_param
        self._nvme_trainer = None
        if off_p is not None and off_p.device == "nvme":
            # ZeRO-Infinity param streaming from config alone (reference
            # partition_parameters.py:1479 + swapper wiring): the engine builds
            # the SwappedLayerTrainer when the caller supplies the layer
            # structure an opaque loss_fn hides.
            if layer_fn is None or head_fn is None:
                raise ValueError(
                    "offload_param: nvme streams one layer at a time, which needs the layer "
                    "structure the opaque loss_fn hides — pass layer_fn(params_l, x) -> x and "
                    "head_fn(head_params, x, labels) -> loss to initialize(), with "
                    "model_parameters = {'layers': stacked [L, ...] tree, ...head leaves} "
                    "(ZeRO-Infinity layer streaming, ref partition_parameters.py:1479)")
            if not (isinstance(params, dict) and "layers" in params):
                raise ValueError("offload_param: nvme expects model_parameters to be a dict "
                                 "with a stacked 'layers' subtree ([L, ...] leaves)")
            if self.gradient_accumulation_steps != 1 or self.dp_world_size != 1:
                raise ValueError(
                    f"offload_param: nvme streams layers on ONE process/device "
                    f"(gas={self.gradient_accumulation_steps}, dp={self.dp_world_size} "
                    f"requested) — set gradient_accumulation_steps=1 and a single-device "
                    f"topology; scale-out composes via the launcher, one trainer per host")
            self._init_nvme_trainer(params, off_p, layer_fn, head_fn)
            return
        abstract = any(isinstance(p, jax.ShapeDtypeStruct) for p in jax.tree_util.tree_leaves(params))
        if abstract and param_init_fn is None:
            raise ValueError("model_parameters is abstract (ShapeDtypeStruct leaves); "
                             "pass param_init_fn so the engine can materialize shards "
                             "(zero.Init semantics, ref partition_parameters.py:786)")
        if self.offload_device is not None:
            if abstract:
                # offload wants the master on HOST anyway — materialize on the
                # CPU backend so the full fp32 tree never touches HBM
                cpu = jax.local_devices(backend="cpu")[0]
                with jax.default_device(cpu):
                    params = param_init_fn()
            self._init_offload(params, off)
            self.state = None
        elif abstract:
            self.state = self._init_state_sharded(param_init_fn)
        else:
            self.state = self._init_state(params)
        n_params = sum(int(np.prod(getattr(p, "shape", ()) or ())) for p in jax.tree_util.tree_leaves(params))
        log_dist(
            f"Engine: zero_stage={self.zero_stage} dp_world={self.dp_world_size} "
            f"batch={self.train_batch_size} (micro={self.micro_batch_size} x gas="
            f"{self.gradient_accumulation_steps} x dp={self.dp_world_size}) "
            f"dtype={self.compute_dtype.__name__} params={n_params/1e6:.2f}M", ranks=[0])
        # first ops snapshot at attach: a scrape during the (possibly long)
        # jit-compile window before step 1 must see real zeroed families and
        # a populated /healthz, not the cache's empty defaults — the same
        # contract the serving engine's attach-time refresh keeps
        self._refresh_ops(force=True)

    # ------------------------------------------------------------------ init
    def _init_state(self, params) -> TrainState:
        """Materialize the sharded train state — the analog of zero.Init +
        initialize_optimizer_states (stage_1_and_2.py:653): every leaf lands on
        device already partitioned per the plan, so full replicas never exist."""

        def make_state(p):
            master = jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), p)
            opt_state = self._opt_init(master)
            ls = init_loss_scale(self.config.fp16) if self.fp16_enabled else None
            return TrainState(step=jnp.zeros((), jnp.int32),
                              params=master,
                              opt_state=opt_state,
                              loss_scale=ls,
                              rng=jax.random.PRNGKey(self.config.seed))

        shapes = jax.eval_shape(make_state, params)
        shardings = self._state_shardings(shapes)
        init_fn = jax.jit(make_state, out_shardings=shardings)
        return init_fn(params)

    def _init_state_sharded(self, param_init_fn: Callable) -> TrainState:
        """zero.Init path (ref partition_parameters.py:786): params are built
        INSIDE the jitted state constructor with sharded out_shardings, so every
        leaf is computed/stored already partitioned — no host or single-device
        full copy of a 7B model ever exists."""

        def make_state():
            p = param_init_fn()
            master = jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), p)
            opt_state = self._opt_init(master)
            ls = init_loss_scale(self.config.fp16) if self.fp16_enabled else None
            return TrainState(step=jnp.zeros((), jnp.int32),
                              params=master,
                              opt_state=opt_state,
                              loss_scale=ls,
                              rng=jax.random.PRNGKey(self.config.seed))

        shapes = jax.eval_shape(make_state)
        shardings = self._state_shardings(shapes)
        return jax.jit(make_state, out_shardings=shardings)()

    def _opt_init(self, master):
        if self._onebit is not None:
            return self._onebit.init(master, self._onebit_world)
        return self.optimizer.init(master)

    def _state_shardings(self, state_shapes: TrainState) -> TrainState:
        rep = NamedSharding(self.topology.mesh, PartitionSpec())
        opt = self.plan.opt_state_shardings(state_shapes.opt_state)
        if self._onebit is not None and self._onebit_world > 1:
            # error-feedback buffers are per-rank data: worker [world, npad]
            # sharded on dim 0, server [npad] sharded (each rank its slice)
            from .onebit import error_buffer_spec
            axes = self.plan.shard_axes
            ax = axes if len(axes) > 1 else axes[0]
            mesh = self.topology.mesh

            def fix(path, sharding):
                spec = error_buffer_spec(path, ax)
                return NamedSharding(mesh, spec) if spec is not None else sharding

            opt = jax.tree_util.tree_map_with_path(fix, opt)
        return TrainState(
            step=rep,
            params=self.plan.master_shardings(state_shapes.params),
            opt_state=opt,
            loss_scale=jax.tree_util.tree_map(lambda _: rep, state_shapes.loss_scale),
            rng=rep,
        )

    # ------------------------------------------------- optimizer offload path
    def _init_nvme_trainer(self, params, off_p, layer_fn, head_fn):
        """Config-reachable ZeRO-Infinity param path (reference reaches the
        AsyncPartitionedParameterSwapper from offload_param: nvme alone,
        partition_parameters.py:1479)."""
        import tempfile

        from .swap_tensor.partitioned_param_swapper import (AsyncPartitionedParameterSwapper,
                                                            SwappedLayerTrainer)
        opt_cfg = self.config.optimizer
        opt_type = (opt_cfg.type if opt_cfg else "adamw").lower()
        if opt_type not in ("adam", "adamw", "fusedadam", "fused_adam"):
            raise ValueError(f"offload_param: nvme steps layers with the host CPU-Adam "
                             f"(csrc/cpu_adam analog); optimizer '{opt_type}' is not supported")
        opt_params = dict(opt_cfg.params) if opt_cfg else {}
        path = off_p.nvme_path or tempfile.mkdtemp(prefix="dstpu_nvme_")
        swapper = AsyncPartitionedParameterSwapper(path, buffer_count=off_p.buffer_count)
        stacked = params["layers"]
        num_layers = int(np.shape(jax.tree_util.tree_leaves(stacked)[0])[0])
        # offload_optimizer: cpu + offload_param: nvme => moments pinned in host
        # RAM (one tier up), halving per-step disk traffic — the reference's
        # mixed ZeRO-Infinity placement (offload_config.py device per tier)
        off_o = self.config.zero_optimization.offload_optimizer
        opt_device = "cpu" if (off_o is not None and off_o.device == "cpu") else "nvme"
        stem_fn = getattr(self, "_stem_fn", None)
        trainer = SwappedLayerTrainer(layer_fn, num_layers, head_fn, swapper,
                                      lr=self.base_lr,
                                      betas=tuple(opt_params.get("betas", (0.9, 0.999))),
                                      eps=float(opt_params.get("eps", 1e-8)),
                                      weight_decay=float(opt_params.get("weight_decay", 0.0)),
                                      compute_dtype=self.compute_dtype,
                                      stem_fn=stem_fn,
                                      optimizer_device=opt_device,
                                      offload_activations=self.config.activation_checkpointing.cpu_checkpointing)
        # "stem" is reserved ONLY when a stem_fn claims it; without one it
        # stays in the head params (e.g. head_fn reading params["stem"])
        head_keys = ("layers", "stem") if stem_fn is not None else ("layers", )
        trainer.init_from_stacked(
            stacked,
            {k: v for k, v in params.items() if k not in head_keys},
            stem_params=params.get("stem") if stem_fn is not None else None)
        self._nvme_trainer = trainer
        self.state = None
        log_dist(f"Engine: ZeRO-Infinity NVMe param streaming — {num_layers} layers, "
                 f"buffer_count={off_p.buffer_count}, moments={opt_device}, path={path}", ranks=[0])

    def _init_offload(self, params, off_cfg):
        """ZeRO-Offload/Infinity analog (reference swap_tensor + cpu_adam): fp32
        master + Adam moments live on host (cpu) or disk (nvme); the device
        holds only the bf16 compute copy.  The jitted program computes grads;
        the C++ cpu_adam steps host buffers."""
        from .swap_tensor.optimizer_swapper import OffloadedAdamState
        if self.fp16_enabled:
            raise ValueError("optimizer offload requires bf16/fp32 (fp16 dynamic loss "
                             "scaling is not supported on the host-offload path)")
        opt_cfg = self.config.optimizer
        opt_type = (opt_cfg.type if opt_cfg else "adamw").lower()
        if opt_type not in ("adam", "adamw"):
            raise ValueError(f"optimizer offload supports adam/adamw, got '{opt_type}'")
        opt_params = dict(opt_cfg.params) if opt_cfg else {}
        from .checkpointing import _leaf_key
        flat, self._offload_treedef = jax.tree_util.tree_flatten_with_path(params)
        self._offload_keys = []
        self._offload_shapes = []
        flat_dict = {}
        for path, leaf in flat:
            key = _leaf_key(path)
            self._offload_keys.append(key)
            self._offload_shapes.append(np.shape(leaf))
            flat_dict[key] = np.asarray(leaf, np.float32).ravel()
        betas = tuple(opt_params.get("betas", (0.9, 0.999)))
        self._offload_state = OffloadedAdamState(
            flat_dict, device=self.offload_device,
            nvme_path=getattr(off_cfg, "nvme_path", None),
            lr=self.base_lr, betas=betas,
            eps=float(opt_params.get("eps", 1e-8)),
            weight_decay=float(opt_params.get("weight_decay", 0.0)))
        self._offload_push_fn = None  # built lazily, cached (jit identity + shardings)
        self._push_compute_params()
        self._offload_grad_fn = None
        self._host_rng = jax.random.PRNGKey(self.config.seed)

    def _push_compute_params(self):
        leaves = [jnp.asarray(self._offload_state.params[k].reshape(shape), self.compute_dtype)
                  for k, shape in zip(self._offload_keys, self._offload_shapes)]
        tree = jax.tree_util.tree_unflatten(self._offload_treedef, leaves)
        if self._offload_push_fn is None:
            shardings = self.plan.param_shardings(tree)

            def push_compute_params(p):  # named: the set-up account's row is not "<lambda>"
                return p

            self._offload_push_fn = jax.jit(push_compute_params, out_shardings=shardings)
        self._compute_params = self._offload_push_fn(tree)

    def _offload_train_batch(self, batch):
        gas = self.gradient_accumulation_steps
        if self._offload_grad_fn is None:
            loss_fn = self.loss_fn
            clip_norm = self.config.gradient_clipping

            def grad_step(params16, batch, rngs):
                # the scope names of train_step (the optimizer runs on the host)
                with jax.named_scope("forward_backward"):
                    grads, loss_sum = accumulate_micro_grads(loss_fn, params16, batch, rngs,
                                                             jnp.float32(1.0))
                    grads = jax.tree_util.tree_map(lambda g: g / gas, grads)
                with jax.named_scope("grad_norm_clip"):
                    norm = global_grad_norm(grads)
                    if clip_norm > 0:
                        grads, norm = clip_by_global_norm(grads, clip_norm, precomputed_norm=norm)
                return grads, loss_sum / gas, norm

            self._offload_grad_fn = jax.jit(grad_step)

        self._host_rng, step_rng = jax.random.split(self._host_rng)
        rngs = jax.random.split(step_rng, gas)
        grads, loss, norm = self._offload_grad_fn(self._compute_params, batch, rngs)
        grad_leaves = jax.tree_util.tree_leaves(grads)
        grads_np = {k: np.asarray(g, np.float32).ravel()  # dslint: disable=host-sync-in-hot-path  # ZeRO-Offload by design: grads must land on host for the CPU-Adam step
                    for k, g in zip(self._offload_keys, grad_leaves)}
        lr = self._host_lr(self.global_steps)
        self._offload_state.step(grads_np, lr=lr)
        self._push_compute_params()
        return StepMetrics(loss=loss, grad_norm=norm, lr=jnp.float32(lr),
                           skipped=jnp.zeros((), jnp.bool_), loss_scale=jnp.float32(1.0))

    # ------------------------------------------------------------- train step
    def _build_train_step(self):
        gas = self.gradient_accumulation_steps
        compute_dtype = self.compute_dtype
        plan = self.plan
        optimizer = self.optimizer
        loss_fn = self.loss_fn
        lr_schedule = self.lr_schedule
        fp16 = self.fp16_enabled
        fp16_cfg = self.config.fp16
        clip_norm = self.config.gradient_clipping
        zero_cfg = self.config.zero_optimization
        topo = self.topology
        # ZeRO++ paths need pure dp/fsdp sharding (replicated model axes) and an
        # actual dp world to save traffic on
        pure_dp = all(topo.axis_size(a) == 1 for a in ("tensor", "sequence", "expert", "pipe"))
        dp_world = 1
        for a in self.plan.shard_axes:
            dp_world *= topo.axis_size(a)
        qgz = (bool(zero_cfg.zero_quantized_gradients) and 1 <= self.zero_stage <= 2
               and pure_dp and dp_world > 1 and not fp16)
        qwz = bool(zero_cfg.zero_quantized_weights) and 1 <= self.zero_stage <= 2 and pure_dp and dp_world > 1
        # stage-3 ZeRO++ (hierarchical over data=slow / fsdp=fast; reference
        # partition_parameters.py:1171-1243 + coalesced_collectives.py:31):
        # requires both axes so the quantized hop ('data') is distinct from the
        # GSPMD per-layer gather axis ('fsdp' — the hpZ secondary partition)
        # fp16 is excluded: int4 quantization would launder grad inf/nan into
        # finite values before overflow detection, defeating loss-scale skips
        zpp3 = (self.zero_stage >= 3 and pure_dp and not fp16
                and self.plan.shard_axes == ("data", "fsdp")
                and topo.axis_size("data") > 1 and topo.axis_size("fsdp") > 1
                and bool(zero_cfg.zero_quantized_gradients
                         or zero_cfg.zero_quantized_weights))
        hpz = (zero_cfg.zero_hpz_partition_size > 1 and self.zero_stage >= 3
               and topo.axis_size("fsdp") > 1)
        if zero_cfg.zero_quantized_gradients and not (qgz or zpp3):
            log_dist("zero_quantized_gradients requested but inactive (needs bf16/fp32 "
                     "compute — not fp16 — and a pure dp/fsdp mesh with dp world > 1; "
                     "stage 3 additionally needs data>1 AND fsdp>1)", ranks=[0])
        if zero_cfg.zero_quantized_weights and not (qwz or zpp3):
            log_dist("zero_quantized_weights requested but inactive (needs pure dp/fsdp "
                     "mesh with dp world > 1; stage 3 additionally needs data>1 AND fsdp>1)", ranks=[0])
        if zero_cfg.zero_hpz_partition_size > 1 and not hpz:
            log_dist("zero_hpz_partition_size requested but inactive (needs stage 3 and "
                     "an fsdp mesh axis > 1)", ranks=[0])
        if hpz and zero_cfg.zero_hpz_partition_size != topo.axis_size("fsdp"):
            log_dist(f"hpZ secondary partition follows the fsdp mesh axis "
                     f"(size {topo.axis_size('fsdp')}), not zero_hpz_partition_size="
                     f"{zero_cfg.zero_hpz_partition_size}", ranks=[0])
        # Pallas fused optimizer step: single-device only (pallas_call under
        # GSPMD would replicate sharded leaves); multi-device runs the identical
        # delta-form math, which XLA shards per the plan.
        fused_step = optimizer.step_fn if (optimizer.step_fn is not None
                                           and self.topology.mesh.devices.size == 1) else None
        compute_shardings = None
        if self.zero_stage < 3:
            # Replicated over dp (keeping any tensor-parallel dims sharded): the
            # bit16-allgather analog.
            compute_shardings = self.plan.param_shardings(self.state.params)
        elif hpz:
            # hpZ secondary partition: compute copy sharded over fsdp only
            compute_shardings = self.plan.secondary_shardings(self.state.params)
        elif self.plan.persistence_threshold > 0:
            # stage 3: pin the compute copy to the plan's layout — big leaves
            # sharded (per-layer gathers ride the scan), persistent small
            # leaves REPLICATED (param_persistence_threshold semantics,
            # partition_parameters.py:1479).  threshold=0 leaves layout to
            # GSPMD entirely.
            compute_shardings = self.plan.param_shardings(self.state.params)

        def cast_for_compute(master):
            if qwz:
                from .zero.quantized import qwz_cast_gather
                return qwz_cast_gather(master, topo.mesh, plan.shard_axes, compute_dtype, plan=plan)
            p16 = jax.tree_util.tree_map(lambda x: x.astype(compute_dtype), master)
            if compute_shardings is not None:
                p16 = jax.tree_util.tree_map(jax.lax.with_sharding_constraint, p16, compute_shardings)
            return p16

        qgz_grad_fn = None
        if qgz:
            from .zero.quantized import make_qgz_grad_fn
            qgz_grad_fn = make_qgz_grad_fn(loss_fn, topo.mesh, plan.shard_axes, gas)
        zpp3_fn = None
        if zpp3:
            from .zero.quantized import make_zpp3_grad_fn
            zpp3_fn = make_zpp3_grad_fn(loss_fn, topo.mesh, plan, gas,
                                        qwz=bool(zero_cfg.zero_quantized_weights),
                                        qgz=bool(zero_cfg.zero_quantized_gradients),
                                        compute_dtype=compute_dtype)
        onebit_fn = None
        if self._onebit is not None and self._onebit_world > 1:
            onebit_fn = self._make_onebit_step()

        def train_step(state: TrainState, batch) -> Tuple[TrainState, StepMetrics]:
            rng, step_rng = jax.random.split(state.rng)
            scale = state.loss_scale.cur_scale if fp16 else jnp.float32(1.0)
            micro_rngs = jax.random.split(step_rng, gas)

            # the named scopes below are metadata on the operations (a trace
            # tells forward+backward, the norm and the update apart by them)
            # and change nothing that is compiled.  Forward and backward are
            # ONE scope: under value_and_grad XLA interleaves them
            if onebit_fn is not None:
                # 1-bit optimizer: grads + compressed momentum reduction +
                # update all inside one shard_map (comm is part of the step;
                # its phases are scoped inside the body)
                lr = lr_schedule(state.step)
                new_params, new_opt, loss_sum, norm = onebit_fn(
                    state.params, state.opt_state, batch, micro_rngs, lr)
                new_state = TrainState(step=state.step + 1, params=new_params,
                                       opt_state=new_opt, loss_scale=None, rng=rng)
                return new_state, StepMetrics(loss=loss_sum / gas, grad_norm=norm, lr=lr,
                                              skipped=jnp.zeros((), jnp.bool_),
                                              loss_scale=jnp.float32(1.0))

            with jax.named_scope("forward_backward"):
                if zpp3_fn is not None:
                    # stage-3 ZeRO++: int8 gather + int4 hierarchical grad reduction
                    # straight from/to the fp32 master layout
                    grads, loss_sum = zpp3_fn(state.params, batch, micro_rngs, scale)
                elif qgz_grad_fn is not None:
                    # qgZ: explicit int4-quantized dp gradient reduction (shard_map)
                    params16 = cast_for_compute(state.params)
                    grads, loss_sum = qgz_grad_fn(params16, batch, micro_rngs, scale)
                else:
                    params16 = cast_for_compute(state.params)
                    grads, loss_sum = accumulate_micro_grads(loss_fn, params16, batch, micro_rngs, scale)

                # average over micro-batches and unscale; dp reduction happens via
                # sharding propagation (data-sharded batch -> psum/reduce-scatter)
                grads = jax.tree_util.tree_map(lambda g: g / (gas * scale), grads)
                grads = plan.constrain_grads(grads)

            with jax.named_scope("grad_norm_clip"):
                norm = global_grad_norm(grads)
                if clip_norm > 0:
                    grads, norm = clip_by_global_norm(grads, clip_norm, precomputed_norm=norm)

            lr = lr_schedule(state.step)
            overflow = jnp.logical_or(has_overflow(grads), jnp.logical_not(jnp.isfinite(norm))) if fp16 \
                else jnp.zeros((), jnp.bool_)

            with jax.named_scope("optimizer"):
                if fused_step is not None:
                    new_params, new_opt = fused_step(grads, state.opt_state, state.params, lr)
                else:
                    updates, new_opt = optimizer.update(grads, state.opt_state, state.params, lr)
                    new_params = jax.tree_util.tree_map(lambda p, u: p + u, state.params, updates)

            # fp16 overflow: skip the update (reference step:1786 overflow path).
            # bf16/fp32 never overflows-skips — eliding the select keeps the old
            # params dead so the fused step's buffer aliasing holds.
            if fp16:
                def pick(new, old):
                    return jax.tree_util.tree_map(lambda a, b: jnp.where(overflow, b, a), new, old)

                new_params = pick(new_params, state.params)
                new_opt = pick(new_opt, state.opt_state)
            new_ls = update_loss_scale(state.loss_scale, overflow, fp16_cfg) if fp16 else None

            new_state = TrainState(step=state.step + jnp.where(overflow, 0, 1),
                                   params=new_params,
                                   opt_state=new_opt,
                                   loss_scale=new_ls,
                                   rng=rng)
            metrics = StepMetrics(loss=loss_sum / gas,
                                  grad_norm=norm,
                                  lr=lr,
                                  skipped=overflow,
                                  loss_scale=scale)
            return new_state, metrics

        shardings = self._state_shardings(jax.eval_shape(lambda s: s, self.state))
        return jax.jit(train_step,  # dslint: disable=donation-after-use  # call-site contract: train_batch reassigns self.state from the result in the same statement; FlopsProfiler only lower()s (never executes) the callable
                       in_shardings=(shardings, None),
                       out_shardings=(shardings, None),
                       donate_argnums=(0, ))

    def _make_onebit_step(self):
        """shard_map step for 1-bit optimizers: local grads -> local momentum
        update -> sign-compressed allreduce of the momentum -> param update
        (reference fp16/onebit/adam.py:14 + runtime/comm/nccl.py:51)."""
        spec = self._onebit
        axes = self.plan.shard_axes
        ax = axes if len(axes) > 1 else axes[0]
        world = self._onebit_world
        mesh = self.topology.mesh
        gas = self.gradient_accumulation_steps
        compute_dtype = self.compute_dtype
        loss_fn = self.loss_fn
        rep = PartitionSpec()

        from .onebit import error_buffer_spec

        def opt_spec(path, _):
            spec = error_buffer_spec(path, ax)
            return spec if spec is not None else rep

        clip_norm = self.config.gradient_clipping

        def body(master, opt_state, batch, micro_rngs, lr):
            with jax.named_scope("forward_backward"):
                params16 = jax.tree_util.tree_map(lambda x: x.astype(compute_dtype), master)
                grads, loss_sum = accumulate_micro_grads(loss_fn, params16, batch, micro_rngs,
                                                         jnp.float32(1.0))
                grads = jax.tree_util.tree_map(lambda g: g / gas, grads)
            # global norm from ONE scalar psum of squared local norms (no full
            # gradient allreduce — that would defeat the 1-bit compression):
            # normalized by world so it equals the exact global norm when rank
            # grads coincide (post-allreduce semantics); identical on every
            # rank, so the clip factor below is consistent
            with jax.named_scope("grad_norm_clip"):
                sq = global_grad_norm(grads) ** 2
                norm = jnp.sqrt(jax.lax.psum(sq, ax) / world)
                if clip_norm > 0:
                    # clip BEFORE the momentum update, like the fp16 optimizer path
                    grads, norm = clip_by_global_norm(grads, clip_norm, precomputed_norm=norm)
            with jax.named_scope("optimizer"):
                new_master, new_opt = spec.local_step(grads, opt_state, master, lr, ax, world)
            return new_master, new_opt, jax.lax.pmean(loss_sum, ax), norm

        def step(master, opt_state, batch, micro_rngs, lr):
            rep_tree = lambda t: jax.tree_util.tree_map(lambda _: rep, t)
            opt_specs = jax.tree_util.tree_map_with_path(opt_spec, opt_state)
            batch_specs = jax.tree_util.tree_map(lambda _: PartitionSpec(None, ax), batch)
            in_specs = (rep_tree(master), opt_specs, batch_specs, rep, rep)
            out_specs = (rep_tree(master), opt_specs, rep, rep)
            return shard_map(body, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                             check_vma=False)(master, opt_state, batch, micro_rngs, lr)

        return step

    @property
    def train_step_fn(self):
        if self._compiled_step is None:
            def seen(fn, args):
                # the first step's shapes, for program_scopes(); then the bare program
                program = compile_events.compile_later(fn, args)
                if program is not None:  # None: traced into another program, no step
                    self._compiled_step = fn
                    program_scopes.register(self, "train_step", program)
            self._compiled_step = program_scopes.FirstCall(self._build_train_step(), seen)
        return self._compiled_step

    def program_scopes(self, name: Optional[str] = None):
        """Which scope (``forward_backward``, ``grad_norm_clip``, ``optimizer``,
        and the model's own inside the first) each operation of the compiled
        train step belongs to: ``{"train_step": {instruction: (scope, ...)}}``,
        to lay over a device trace's ``jit_train_step`` events
        (``monitor/program_scopes.py``; the serving engine's twin).  Compiles
        the step again at its first call's shapes (a hit of JAX's caches) and
        parses its text, once: an operator's call, never a step's."""
        return program_scopes.tables(None if name is None else [name], owner=self)

    # ------------------------------------------------------------ public API
    def _shard_batch(self, batch):
        """Place a [gas, global_micro, ...] host batch with the global_micro dim
        sharded over the dp axes (DistributedSampler analog — each dp shard sees
        its slice; engine.deepspeed_io:1686).  NOT plan.shard_axes: ZeRO state
        may also partition over 'sequence' (seq_data_parallel composition), but
        the batch dim only spans data x fsdp."""
        dp_axes = self.topology.data_parallel_axes()
        axes = dp_axes if len(dp_axes) > 1 else dp_axes[0]
        sharding = NamedSharding(self.topology.mesh, PartitionSpec(None, axes))
        return jax.tree_util.tree_map(lambda x: jax.device_put(jnp.asarray(x), sharding), batch)

    # ------------------------------------------------------------ ops plane
    def ops_health(self) -> Dict[str, Any]:
        """The training engine's /healthz payload: host-owned progress and
        liveness state plus the newest telemetry record's headline numbers
        (all cached — reading this can never touch a device value)."""
        record = self._last_telemetry_record or {}
        return {
            "global_steps": self.global_steps,
            "global_samples": self.global_samples,
            "consecutive_bad_steps": self._consecutive_bad_steps,
            "heartbeat": bool(getattr(self.heartbeat, "enabled", False)),
            "rank": self._ops_rank,
            "loss": record.get("loss"),
            "step_time_ms": record.get("step_time_ms"),
            "samples_per_sec": record.get("samples_per_sec"),
            "tokens_per_sec": record.get("tokens_per_sec"),
            "mfu": record.get("mfu"),
        }

    def _refresh_ops(self, force: bool = False) -> None:
        """Refresh the cached ops snapshots at the train-step boundary
        (throttled to ``ops_server.refresh_interval_s``): registry from the
        engine's host counters + the telemetry caches, /healthz JSON, and the
        per-rank exchange files under the agent-exported ops dir.  A no-op
        when the ops plane is off.  A checkpoint rollback (load_checkpoint
        after the NaN watchdog) legally rewinds global_steps; the publisher
        exposes that as a standard Prometheus counter reset (OpsPublisher
        docstring) instead of raising into train_batch."""
        if self._ops is None:
            return
        self._ops.refresh(
            self._populate_ops_registry, now=time.monotonic(), force=force,
            healthz=lambda: json.dumps(self.ops_health()),
            statez=lambda: json.dumps(self._ops.registry.snapshot()))

    def _populate_ops_registry(self, reg) -> None:
        from ..monitor.metrics import populate_from_telemetry
        ns = reg.namespace
        # telemetry first, engine families second: both spell the
        # global-step/samples gauges, and after a checkpoint rollback the
        # collector's cached record is stale — the engine's live position
        # must win the overwrite
        populate_from_telemetry(reg, self.telemetry)
        # counters are THIS PROCESS's work (steps/samples since the last
        # checkpoint load): a resumed engine restarts them from zero so the
        # fleet aggregator's generation carry — which folds the previous
        # life's totals — never double-counts the resumed prefix.  The
        # absolute training position rides as a gauge.
        reg.set_counter(f"{ns}_train_steps_total",
                        self.global_steps - self._ops_steps_base,
                        help_text="optimizer steps run by this process")
        reg.set_counter(f"{ns}_train_samples_total",
                        self.global_samples - self._ops_samples_base,
                        help_text="samples consumed by this process")
        reg.set_gauge(f"{ns}_train_global_step", self.global_steps,
                      help_text="absolute training step (checkpoint position)")
        reg.set_gauge(f"{ns}_train_global_samples", self.global_samples,
                      help_text="absolute samples consumed (checkpoint position)")
        reg.set_gauge(f"{ns}_train_consecutive_bad_steps",
                      self._consecutive_bad_steps,
                      help_text="current NaN/overflow watchdog streak")

    def close_ops(self) -> None:
        """Shut the ops HTTP listener down (tests / clean teardown)."""
        if self._ops is not None:
            self._ops.close()

    def _tell_setup_account(self) -> None:
        """Once, after this process's first step: what the programs built up
        to here cost to trace, lower and compile or load (the process's
        set-up account, ``monitor/compile_events.py``), as one gauge record
        (``Train/Setup/trace_s`` ...).  Host floats the account already
        holds: nothing is read from the device, and nothing on a later step."""
        if self._setup_account_told:
            return
        self._setup_account_told = True
        self.telemetry.record_gauges(compile_events.ACCOUNT.totals(), step=self.global_steps,
                                     prefix="Train/Setup")

    def train_batch(self, batch):
        """Run one full optimizer step on a global macro-batch.

        ``batch``: pytree with leaves shaped [train_batch_size, ...] or
        [gas, micro*dp, ...]; reshaped/sharded automatically.
        """
        if self._nvme_trainer is not None:
            # ZeRO-Infinity layer streaming: one layer (+ its Adam state) on
            # device / in host buffers at a time; batch passes through whole
            self.telemetry.profile_step_boundary(self.global_steps)
            self.throughput.start()
            lr = self._host_lr(self.global_steps)
            t0 = time.perf_counter()
            with self.telemetry.step_annotation(self.global_steps):
                loss = self._nvme_trainer.train_step(batch, lr=lr)
            step_time = time.perf_counter() - t0
            metrics = StepMetrics(loss=jnp.float32(loss), grad_norm=jnp.float32(0.0),
                                  lr=jnp.float32(lr), skipped=jnp.asarray(False),
                                  loss_scale=jnp.float32(1.0))
            self.global_steps += 1
            self.global_samples += self.train_batch_size
            self.lr_scheduler.last_step = self.global_steps
            self.heartbeat.stamp(self.global_steps)
            if self.telemetry.enabled:
                # XLA cost analysis of the streamed layer loop is not one
                # program; MFU stays null on this path
                self.telemetry.set_flops_per_step(None)
                self._last_telemetry_record = self.telemetry.record_train_step(
                    step=self.global_steps, samples=self.global_samples,
                    loss=loss, grad_norm=0.0, lr=lr, step_time_s=step_time,
                    tokens=self._batch_tokens(batch, seq_dim=1))
            self._tell_setup_account()
            self._refresh_ops()
            self._watchdog_check(metrics, loss_val=loss)
            self._maybe_report(metrics)
            return metrics
        if self._ltd_state is not None:
            if self.global_steps == 1 and not self._ltd_state.get("engaged"):
                from ..utils.logging import logger
                logger.warning(
                    "data_routing.random_ltd is configured but the first traced step "
                    "never engaged token dropping — this loss_fn does not read "
                    "configured_ltd() (llama-family forwards with an rng do); "
                    "training proceeds WITHOUT random-LTD")
            new_keep = self._ltd_state["scheduler"].update_seq(self.global_steps)
            if new_keep != self._ltd_state["keep"]:
                # the kept-token count is a static shape in the traced program
                # (reference random-LTD pays the same via its seqlen buckets):
                # bump it and rebuild the jitted step at the new budget
                self._ltd_state["keep"] = new_keep
                self._compiled_step = None
                self._offload_grad_fn = None  # offload path re-traces at the new budget
        telemetry = self.telemetry.enabled
        if telemetry:
            self.telemetry.profile_step_boundary(self.global_steps)
        breakdown = self.config.wall_clock_breakdown
        timed = breakdown or telemetry
        t0 = time.perf_counter() if timed else 0.0
        with self.telemetry.annotation("batch_prep"):
            batch = self._ensure_gas_layout(batch)
            batch = self._shard_batch(batch)
        t1 = time.perf_counter() if timed else 0.0
        self.throughput.start()
        with self.telemetry.step_annotation(self.global_steps):
            if self.offload_device is not None:
                metrics = self._offload_train_batch(batch)
            else:
                self.state, metrics = self.train_step_fn(self.state, batch)
        loss_val = None
        t2 = 0.0
        if timed:
            # a value fetch is the only true sync; keep it off the fast path
            loss_val = float(metrics.loss)  # dslint: disable=host-sync-in-hot-path  # the step's ONE deliberate sync, opt-in via telemetry/wall_clock_breakdown (documented in TelemetryConfig)
            t2 = time.perf_counter()
        if breakdown:
            self._breakdown_acc = getattr(self, "_breakdown_acc", [0.0, 0.0, 0])
            self._breakdown_acc[0] += t1 - t0
            self._breakdown_acc[1] += t2 - t1
            self._breakdown_acc[2] += 1
            if (self.global_steps + 1) % self.config.steps_per_print == 0:
                bd, bs, n = self._breakdown_acc
                # the reference's fwd/bwd/step split is one fused XLA program
                # here — batch-prep vs compiled-step is the meaningful split
                log_dist(f"wall clock breakdown (avg over {n} steps): "
                         f"batch_prep={bd / n * 1e3:.2f}ms "
                         f"train_step={bs / n * 1e3:.2f}ms", ranks=[0])
                self._breakdown_acc = [0.0, 0.0, 0]
        self.global_steps += 1
        self.global_samples += self.train_batch_size
        self.lr_scheduler.last_step = self.global_steps
        # liveness stamp at the step's existing host-touch point: python-int
        # step + wall clock only, throttled inside the writer (zero syncs)
        self.heartbeat.stamp(self.global_steps)
        if telemetry:
            if self.telemetry.wants_flops():
                self.telemetry.set_flops_per_step(self._train_step_flops(batch))
            # the step already synced for loss_val above: fetch the remaining
            # scalars in ONE transfer instead of two more round-trips
            grad_norm_val, lr_val = map(float, jax.device_get((metrics.grad_norm, metrics.lr)))  # dslint: disable=host-sync-in-hot-path  # telemetry opt-in: single batched fetch after the loss sync
            self._last_telemetry_record = self.telemetry.record_train_step(
                step=self.global_steps, samples=self.global_samples,
                loss=loss_val, grad_norm=grad_norm_val,
                lr=lr_val, step_time_s=max(t2 - t1, 0.0) or None,
                tokens=self._batch_tokens(batch))
        if (self.config.telemetry.memory_breakdown
                and self.global_steps % self.config.steps_per_print == 0):
            # memory_breakdown stands alone: the reference's top-level key must
            # snapshot even when per-step telemetry records are off
            see_memory_usage(f"after train step {self.global_steps}")
        self._tell_setup_account()
        # ops-plane cache refresh (ISSUE 11): host-only, after the telemetry
        # record so a scrape sees THIS step; throttled; no-op when off
        self._refresh_ops()
        self._watchdog_check(metrics, loss_val=loss_val)
        self._maybe_report(metrics, loss=loss_val)
        return metrics

    def _train_step_flops(self, sharded_batch) -> Optional[float]:
        """One-time per-step FLOPs from the XLA cost analysis of the compiled
        train step (FlopsProfiler, fed the exact batch the step runs on — no
        re-layout); None on the offload paths (the step is not one jitted
        program there) or when cost analysis is unavailable."""
        if self.offload_device is not None or self._nvme_trainer is not None:
            return None
        try:
            from ..profiling.flops_profiler import FlopsProfiler
            return FlopsProfiler(self).profile_train_step(sharded_batch,
                                                          pre_sharded=True).flops
        except Exception as e:
            logger.warning(f"telemetry: train-step cost analysis failed ({e}); mfu stays null")
            return None

    def _batch_tokens(self, batch, seq_dim: int = 2) -> Optional[int]:
        """Global tokens this step: train_batch_size * seq_len, with seq_len
        read off the first integer-dtype leaf carrying a sequence dim —
        ``seq_dim=2`` for the gas layout ([gas, micro, seq, ...]), ``seq_dim=1``
        for raw [batch, seq, ...] batches (the NVMe streaming path, which never
        gas-reshapes).  None for sequence-free batches (telemetry then counts
        one token per sample)."""
        for leaf in jax.tree_util.tree_leaves(batch):
            shape = getattr(leaf, "shape", ())
            dt = getattr(leaf, "dtype", None)
            if len(shape) > seq_dim and dt is not None and jnp.issubdtype(dt, jnp.integer):
                return self.train_batch_size * int(shape[seq_dim])
        return None

    def _ensure_gas_layout(self, batch):
        gas = self.gradient_accumulation_steps

        def fix(x):
            x = np.asarray(x)
            if x.shape[0] == self.train_batch_size:
                return x.reshape(gas, self.train_batch_size // gas, *x.shape[1:])
            if x.ndim >= 2 and x.shape[0] == gas:
                return x
            raise ValueError(f"batch leading dim {x.shape[0]} matches neither train_batch_size="
                             f"{self.train_batch_size} nor gas={gas}")

        return jax.tree_util.tree_map(fix, batch)

    # torch-style 3-call shim (reference forward:1781 / backward:1922 / step:2120)
    def forward(self, micro_batch):
        self._micro_batches.append(micro_batch)
        return None

    def backward(self, loss=None):
        return None

    def step(self):
        gas = self.gradient_accumulation_steps
        if len(self._micro_batches) != gas:
            raise RuntimeError(f"engine.step() called after {len(self._micro_batches)} forward() calls; "
                               f"gradient_accumulation_steps={gas} micro-batches are required")
        stacked = jax.tree_util.tree_map(lambda *xs: np.stack(xs), *self._micro_batches)
        self._micro_batches = []
        return self.train_batch(stacked)

    def _nvme_guard(self, what: str):
        if self._nvme_trainer is not None:
            raise NotImplementedError(
                f"{what} is not available on the offload_param:nvme streaming path — state "
                f"lives in the swapper's NVMe files (persistent across runs at nvme_path); "
                f"use the trainer's forward() for inference, and point a new engine at the "
                f"same nvme_path to resume")

    def eval_batch(self, batch, rng=None):
        self._nvme_guard("eval_batch")
        if self._compiled_eval is None:
            compute_dtype = self.compute_dtype

            loss_fn = self.loss_fn
            if self._ltd_state is not None:
                # random-LTD is train-only (reference applies it via the
                # training forward rewrite): eval traces with the LTD scope
                # pinned empty so the full model is measured
                from ..models.transformer import scoped_random_ltd
                loss_fn = scoped_random_ltd(loss_fn, None)

            def eval_step(params, b, rng):
                p16 = jax.tree_util.tree_map(lambda x: x.astype(compute_dtype), params)
                out = loss_fn(p16, b, rng)
                return out[0] if isinstance(out, tuple) else out

            self._compiled_eval = jax.jit(eval_step)
        rng = rng if rng is not None else jax.random.PRNGKey(0)
        # batch dim spans the dp axes only — plan.shard_axes may also carry
        # 'sequence' (seq_data ZeRO composition), which never splits samples
        dp_axes = self.topology.data_parallel_axes()
        sharding = NamedSharding(self.topology.mesh,
                                 PartitionSpec(dp_axes if len(dp_axes) > 1 else dp_axes[0]))
        batch = jax.tree_util.tree_map(lambda x: jax.device_put(jnp.asarray(x), sharding), batch)
        params = self._compute_params if self.offload_device is not None else self.state.params
        if not self.telemetry.enabled:
            return self._compiled_eval(params, batch, rng)
        t0 = time.perf_counter()
        with self.telemetry.annotation("eval_batch"):
            loss = self._compiled_eval(params, batch, rng)
            loss_val = float(loss)  # dslint: disable=host-sync-in-hot-path  # telemetry opt-in: sync so the measured time covers execution
        self.telemetry.record_events([
            ("Eval/loss", loss_val, self.global_samples),
            ("Eval/batch_time_ms", (time.perf_counter() - t0) * 1e3, self.global_samples)])
        return loss

    # ----------------------------------------------------------- watchdog
    def _watchdog_check(self, metrics: StepMetrics, loss_val: Optional[float] = None):
        """NaN/Inf sentinel (``max_consecutive_skips`` config): fp16 runs count
        consecutive overflow-SKIPPED steps (the loss scaler absorbs isolated
        spikes, but an unbroken skip streak means the scale can't find footing);
        bf16/fp32 runs — which have no skip path — count consecutive non-finite
        losses/grad-norms.  One good step resets the streak; hitting the limit
        raises :class:`NonFiniteLossError` with a diagnostic instead of letting
        the run silently train on garbage until the job deadline."""
        limit = self.config.max_consecutive_skips
        if limit <= 0:
            return
        if self.fp16_enabled:
            bad = bool(metrics.skipped)
            grad_norm = None
        else:
            if loss_val is None:
                loss_val = float(metrics.loss)
            grad_norm = float(metrics.grad_norm)
            bad = not (np.isfinite(loss_val) and np.isfinite(grad_norm))
        if not bad:
            self._consecutive_bad_steps = 0
            return
        self._consecutive_bad_steps += 1
        self.telemetry.record_resilience(
            "watchdog_nonfinite", step=self.global_steps, samples=self.global_samples,
            consecutive=self._consecutive_bad_steps, limit=limit,
            loss=loss_val, grad_norm=grad_norm)
        if self._consecutive_bad_steps >= limit:
            kind = ("fp16 overflow-skipped" if self.fp16_enabled
                    else "non-finite loss/grad-norm")
            raise NonFiniteLossError(
                f"train-loop watchdog: {self._consecutive_bad_steps} consecutive "
                f"{kind} steps (max_consecutive_skips={limit}) at global step "
                f"{self.global_steps} — last loss={loss_val}, grad_norm={grad_norm}, "
                f"lr={float(metrics.lr):.3e}. The run has diverged: check the data "
                f"pipeline for corrupt batches, lower the lr, or resume from the "
                f"last checkpoint with load_checkpoint(fallback_to_valid=True)")

    # ----------------------------------------------------------- reporting
    def _maybe_report(self, metrics: StepMetrics, loss: Optional[float] = None):
        if self.global_steps % self.config.steps_per_print == 0:
            elapsed = self.throughput.stop()
            loss = float(metrics.loss) if loss is None else loss
            log_dist(
                f"step={self.global_steps} loss={loss:.4f} lr={float(metrics.lr):.3e} "
                f"grad_norm={float(metrics.grad_norm):.3f}"
                + (f" loss_scale={float(metrics.loss_scale):.0f}" if self.fp16_enabled else "")
                + (f" samples/sec={self.throughput.avg_samples_per_sec():.1f}" if elapsed else ""),
                ranks=[0])
            samples = self.global_samples
            events = [("Train/Samples/train_loss", loss, samples),
                      ("Train/Samples/lr", float(metrics.lr), samples),
                      ("Train/Samples/grad_norm", float(metrics.grad_norm), samples)]
            if self.fp16_enabled:
                events.append(("Train/Samples/loss_scale", float(metrics.loss_scale), samples))
            rec = self._last_telemetry_record
            if elapsed and (rec is None or rec.get("samples_per_sec") is None):
                # telemetry's per-step rate supersedes the running average
                events.append(("Train/Samples/samples_per_sec",
                               self.throughput.avg_samples_per_sec(), samples))
            if rec is not None:
                for key in ("step_time_ms", "samples_per_sec", "tokens_per_sec",
                            "tflops_per_sec", "mfu"):
                    if rec.get(key) is not None:
                        events.append((f"Train/Samples/{key}", float(rec[key]), samples))
                for key, value in (rec.get("hbm") or {}).items():
                    if value is not None:
                        events.append((f"Train/HBM/{key}", float(value), samples))
            if self.config.comms_logger.enabled:
                # comms-logger summary rides the same monitor event stream
                from ..utils.comms_logging import get_comms_logger
                events.extend(get_comms_logger().as_events(samples))
            self.monitor.write_events(events)

    @property
    def lr(self):
        return self._host_lr(self.global_steps)

    def get_global_grad_norm(self):
        return None  # populated per-step in metrics

    # --------------------------------------------------------- checkpointing
    def _validate_tag(self, tag: str):
        """Cross-process tag consistency (reference engine.py:3035
        ``_checkpoint_tag_validation``): every process must save under the
        same tag or loads will mix steps.  Single-process: a no-op beyond the
        mode plumbing; multi-process compares a tag hash via a host allreduce."""
        mode = self.config.checkpoint_tag_validation.lower()
        if mode == "ignore" or jax.process_count() <= 1:
            return
        import zlib
        from jax.experimental import multihost_utils
        # one CRC row PER PROCESS — a local reduce would be the identity
        crcs = multihost_utils.process_allgather(
            jnp.asarray([zlib.crc32(tag.encode())], jnp.uint32))
        if len(np.unique(np.asarray(crcs))) > 1:
            msg = f"checkpoint tag {tag!r} differs across processes"
            if mode == "fail":
                raise ValueError(msg)
            logger.warning(msg)

    @property
    def checkpoint_engine(self):
        """Config-selected persistence plug-in (reference _configure_checkpointing,
        engine.py:921: Nebula async vs torch).  Built lazily so engines that
        never checkpoint don't spawn the async writer thread."""
        if self._ckpt_engine is None:
            from .checkpoint_engine.checkpoint_engine import build_checkpoint_engine
            kind = self.config.checkpoint_engine_kind()
            self._ckpt_engine = build_checkpoint_engine(
                kind, max_queue=self.config.checkpoint.async_max_queue)
            if kind not in ("native", "torch"):
                log_dist(f"checkpoint engine: {kind} "
                         f"({type(self._ckpt_engine).__name__} — background writer; "
                         f"commit() at tag boundaries makes saves durable)", ranks=[0])
        return self._ckpt_engine

    def save_checkpoint(self, save_dir: str, tag: Optional[str] = None, client_state: Optional[dict] = None):
        self._nvme_guard("save_checkpoint")
        tag = tag or f"global_step{self.global_steps}"
        self._validate_tag(tag)
        client_state = dict(client_state or {})
        client_state.update({
            "global_steps": self.global_steps,
            "global_samples": self.global_samples,
            "lr_scheduler": self.lr_scheduler.state_dict(),
        })
        state = self.state if self.offload_device is None else self._offload_host_state()
        ck = self.config.checkpoint
        t0 = time.perf_counter()
        # phase-stamped so the agent's hang dump distinguishes "in checkpoint
        # IO" (expected to be slow) from "wedged in a collective"
        self.heartbeat.stamp(self.global_steps, phase="checkpoint_save", force=True)
        with self.telemetry.annotation("checkpoint_save"):
            save_checkpoint_with_retries(
                save_dir, tag, state, client_state, config=self.config,
                engine=self.checkpoint_engine,
                retries=ck.save_retries, backoff_secs=ck.retry_backoff_secs,
                on_retry=lambda attempt, exc: self.telemetry.record_resilience(
                    "save_retry", step=self.global_steps, samples=self.global_samples,
                    tag=tag, attempt=attempt, error=repr(exc)))
        self.telemetry.record_events([("Train/Checkpoint/save_time_ms",
                                       (time.perf_counter() - t0) * 1e3, self.global_samples)])
        if ck.keep_last_n and _is_rank0():
            sweep_retention(save_dir, ck.keep_last_n, verify_integrity=ck.verify_integrity)
        self._register_preemption_handler(save_dir)
        self.heartbeat.stamp(self.global_steps, force=True)
        return tag

    # ----------------------------------------------- preemption (SIGTERM) save
    def _register_preemption_handler(self, save_dir: str):
        """Arm the best-effort final save (``checkpoint.save_on_preemption``):
        on SIGTERM — the TPU-pod preemption notice — save one last checkpoint
        tagged ``preempt_step<N>`` with ``client_state.preempted`` set, then
        chain to whatever handler was installed before (so the default
        die-on-TERM still happens in production)."""
        self._preempt_save_dir = save_dir
        if self._preempt_registered or not self.config.checkpoint.save_on_preemption:
            return
        import signal
        import threading
        if threading.current_thread() is not threading.main_thread():
            return  # signal.signal only works from the main thread
        try:
            self._preempt_prev_handler = signal.signal(signal.SIGTERM, self._on_preemption)
            self._preempt_registered = True
            log_dist("checkpoint: save_on_preemption armed (SIGTERM -> final save)",
                     ranks=[0])
        except (ValueError, OSError) as exc:
            logger.warning(f"save_on_preemption: could not install SIGTERM handler ({exc})")

    def _on_preemption(self, signum=None, frame=None):
        import signal
        # dslint: disable-next-line=handler-holds-engine  # the PR-2 save_on_preemption contract IS "the handler drives the engine": CPython runs signal handlers on the main thread between bytecodes, so this never executes concurrently with a step, and a best-effort final save_checkpoint is the whole point
        if not self._in_preempt_save and self._preempt_save_dir is not None:
            self._in_preempt_save = True
            try:
                tag = f"preempt_step{self.global_steps}"
                logger.warning(f"SIGTERM: best-effort preemption save -> "
                               f"{self._preempt_save_dir}/{tag}")
                self.save_checkpoint(self._preempt_save_dir, tag=tag,
                                     client_state={"preempted": True})
                self.telemetry.record_resilience("preemption_save", step=self.global_steps,
                                                 samples=self.global_samples, tag=tag)
            except BaseException as exc:  # best-effort: never mask the signal
                logger.error(f"preemption save failed: {exc!r}")
            finally:
                self._in_preempt_save = False
        prev = self._preempt_prev_handler
        if callable(prev):
            prev(signum, frame)
        elif prev == signal.SIG_DFL and signum is not None:
            # restore the default disposition and re-deliver so the process
            # still dies the way the supervisor expects
            signal.signal(signum, signal.SIG_DFL)
            os.kill(os.getpid(), signum)

    def _offload_host_state(self):
        """Host-side state pytree with the SAME key layout as the on-device
        TrainState, so checkpoints and the universal converter are identical
        across offload modes."""
        unflatten = lambda arrs: jax.tree_util.tree_unflatten(
            self._offload_treedef,
            [a.reshape(shape) for a, shape in zip(arrs, self._offload_shapes)])
        sd = self._offload_state.state_dict()
        params = unflatten([self._offload_state.params[k] for k in self._offload_keys])
        m = unflatten([sd["m"][k] for k in self._offload_keys])
        v = unflatten([sd["v"][k] for k in self._offload_keys])
        return {"step": np.int32(sd["step"]), "params": params,
                "opt_state": {"step": np.int32(sd["step"]), "exp_avg": m, "exp_avg_sq": v}}

    def load_checkpoint(self, load_dir: str, tag: Optional[str] = None,
                        load_optimizer_states: bool = True, fallback_to_valid: bool = False):
        """Resume from ``load_dir``.  With ``fallback_to_valid`` a missing,
        incomplete, or corrupt target tag (per manifest sizes, plus CRC32s when
        ``checkpoint.verify_integrity`` is on) doesn't raise: the load walks
        prior tags — checkpoint-index order, newest first — to the newest one
        that validates (resume-from-latest-valid).

        When ``tag`` is None and the elastic agent pinned a consensus resume
        tag (``DSTPU_RESUME_TAG`` env), that pin wins over ``latest``: every
        rank of a restarted generation must resume from the SAME tag, not its
        own per-rank newest (which the failure may have left divergent).  The
        pin only applies when the pinned tag exists under ``load_dir`` — a
        load from a directory the consensus wasn't computed over (e.g. a
        pretrained base checkpoint) still gets its own ``latest``."""
        self._nvme_guard("load_checkpoint")
        t0 = time.perf_counter()
        self.heartbeat.stamp(self.global_steps, phase="checkpoint_load", force=True)
        with self.telemetry.annotation("checkpoint_load"):
            if self.config.load_universal_checkpoint:
                out = self._load_universal_checkpoint(load_dir, tag, load_optimizer_states)
            else:
                tag = self._resolve_load_tag(load_dir, tag, fallback_to_valid)
                if self.offload_device is not None:
                    out = self._load_checkpoint_offload(load_dir, tag, load_optimizer_states)
                else:
                    state, client_state = load_checkpoint_dir(
                        load_dir,
                        tag,
                        self.state,
                        self._state_shardings(jax.eval_shape(lambda s: s, self.state)),
                        load_optimizer_states=load_optimizer_states,
                        # _resolve_load_tag just validated this tag (CRCs per
                        # checkpoint.verify_integrity); don't pay it twice
                        validate=False)
                    self.state = state
                    self.global_steps = client_state.get("global_steps", 0)
                    self.global_samples = client_state.get("global_samples", 0)
                    # ops-plane counter base: the restored steps/samples were
                    # executed by a PREVIOUS process life (the fleet
                    # aggregator carries that life's totals), so this
                    # process's exported counters restart from zero here —
                    # without this, every supervised restart that resumes
                    # from a checkpoint double-counts the resumed work in
                    # the merged fleet endpoint
                    self._ops_steps_base = self.global_steps
                    self._ops_samples_base = self.global_samples
                    if "lr_scheduler" in client_state:
                        self.lr_scheduler.load_state_dict(client_state["lr_scheduler"])
                    out = (tag, client_state)
        self.telemetry.record_events([("Train/Checkpoint/load_time_ms",
                                       (time.perf_counter() - t0) * 1e3, self.global_samples)])
        # trailing marker: clears phase=checkpoint_load (whose 10x IO grace
        # would delay post-resume hang detection) but declares phase=resumed,
        # because the jit recompile between here and the first step can
        # outlast the heartbeat timeout — the agent grants 'resumed' stamps
        # the startup grace window instead of indicting a healthy restart
        self.heartbeat.stamp(self.global_steps, phase="resumed", force=True)
        return out

    def _resolve_load_tag(self, load_dir: str, tag: Optional[str],
                          fallback_to_valid: bool) -> str:
        """Pick the tag to load: the requested one (or the agent-pinned
        ``DSTPU_RESUME_TAG``, or ``latest``) when it validates; otherwise —
        only with ``fallback_to_valid`` — the newest prior tag that does."""
        from .checkpointing import get_latest_tag
        from .heartbeat import RESUME_DIR_ENV, RESUME_TAG_ENV
        pinned = None
        if tag is None:
            pinned = os.environ.get(RESUME_TAG_ENV) or None
            # the pin is scoped to the agent-supervised checkpoint dir: a
            # base/warm-start load from an unrelated directory must not have
            # its 'latest' hijacked.  Tag names are the generic
            # global_step<N>, so a tag-existence check alone can false-match
            # a foreign dir — when the agent also exported the dir it
            # computed consensus over, require load_dir to be under it
            if pinned is not None and os.path.isdir(os.path.join(load_dir, pinned)):
                resume_dir = os.environ.get(RESUME_DIR_ENV) or None
                if resume_dir is not None:
                    try:
                        inside = os.path.commonpath(
                            [os.path.realpath(load_dir), os.path.realpath(resume_dir)]
                        ) == os.path.realpath(resume_dir)
                    except ValueError:  # different drives / mixed abs-rel
                        inside = False
                    if not inside:
                        pinned = None
            else:
                pinned = None
            tag = pinned
        verify = self.config.checkpoint.verify_integrity
        requested, failure = tag, None
        try:
            requested = tag or get_latest_tag(load_dir)
            if requested is None:
                raise CheckpointError(
                    f"checkpoint dir {load_dir!r} has no 'latest' file and no tag was "
                    f"given — nothing to resume from")
            validate_checkpoint_tag(load_dir, requested, verify_integrity=verify)
            return requested
        except CheckpointError as exc:
            if pinned is not None:
                # never silently walk away from the agent's consensus pin:
                # falling back would resume this rank from a DIFFERENT tag
                # than its peers — the exact divergence the pin prevents.
                # Fail fast so the agent restarts and re-runs consensus
                # (enable its verify_checkpoint_integrity to also catch what
                # this rank's CRC pass caught).
                raise CheckpointError(
                    f"agent-pinned resume tag {pinned!r} failed validation on this "
                    f"rank ({exc}); refusing to fall back to a per-rank tag — all "
                    f"ranks must resume from the same checkpoint") from exc
            if not fallback_to_valid:
                raise
            failure = exc
        exclude = (requested, ) if requested else ()
        found = find_latest_valid_tag(load_dir, verify_integrity=verify, exclude=exclude)
        if found is None:
            raise CheckpointError(
                f"checkpoint dir {load_dir!r}: no valid checkpoint to fall back to "
                f"(requested tag {requested!r} failed: {failure})")
        logger.warning(f"checkpoint tag {requested!r} is unusable ({failure}); "
                       f"falling back to newest valid tag {found!r}")
        self.telemetry.record_resilience(
            "fallback_load", step=self.global_steps, samples=self.global_samples,
            requested=str(requested), fallback=found, reason=str(failure))
        return found

    def _load_checkpoint_offload(self, load_dir, tag, load_optimizer_states=True):
        from .checkpointing import get_latest_tag, read_metadata
        tag = tag or get_latest_tag(load_dir)
        ckpt_dir = os.path.join(load_dir, tag)
        meta = read_metadata(ckpt_dir)
        sd = {"m": {}, "v": {}, "step": 0}
        for m in meta["manifest"]:
            key = m["key"]
            path = os.path.join(ckpt_dir, key + ".npy")
            if key.startswith("params."):
                self._offload_state.params[key[len("params."):]][...] = np.load(path).ravel()
            elif key.startswith("opt_state.exp_avg_sq.") and load_optimizer_states:
                sd["v"][key[len("opt_state.exp_avg_sq."):]] = np.load(path).ravel()
            elif key.startswith("opt_state.exp_avg.") and load_optimizer_states:
                sd["m"][key[len("opt_state.exp_avg."):]] = np.load(path).ravel()
            elif key in ("step", "opt_state.step"):
                sd["step"] = int(np.load(path))
        if load_optimizer_states and sd["m"]:
            self._offload_state.load_state_dict(sd)
        self._push_compute_params()
        client_state = meta.get("client_state", {})
        self.global_steps = client_state.get("global_steps", 0)
        self.global_samples = client_state.get("global_samples", 0)
        if "lr_scheduler" in client_state:
            self.lr_scheduler.load_state_dict(client_state["lr_scheduler"])
        return tag, client_state

    def _load_universal_checkpoint(self, load_dir, tag, load_optimizer_states=True):
        """Resume from the universal atom format at ANY topology/optimizer —
        the reference's ``engine.load_universal_checkpoint`` (engine.py:813) +
        ``load_hp_checkpoint_state`` (checkpoint/universal_checkpoint.py:12),
        engaged by ``load_universal_checkpoint: true`` in config.

        ``load_dir`` may point directly at a ds_to_universal output (contains
        universal_metadata.json) or at a checkpoint root whose ``<tag>/``
        subdirectory holds one.  Param leaves rebuild from their fp32 atoms;
        optimizer leaves match atoms by the same suffix discovery used at
        conversion, so any optimizer whose state mirrors the param tree (adam,
        lion, lamb, sgd momentum) resumes — including into a DIFFERENT
        optimizer, where unmatched moments warn and keep their init values.
        Atoms saved with vocab padding stripped are zero-re-padded on dim 0
        (reference merge_tp_slices vocab fixups, ds_to_universal.py:156)."""
        from ..checkpoint.universal import PARAM_ATOM, load_universal
        from .checkpointing import _leaf_key, get_latest_tag
        udir = load_dir
        if not os.path.exists(os.path.join(udir, "universal_metadata.json")):
            tag = tag or get_latest_tag(load_dir)
            if tag is not None and os.path.exists(os.path.join(load_dir, tag, "universal_metadata.json")):
                udir = os.path.join(load_dir, tag)
            else:
                raise FileNotFoundError(
                    f"load_universal_checkpoint: no universal_metadata.json under {load_dir}"
                    + (f" or {load_dir}/{tag}" if tag else "") +
                    " — convert a checkpoint first (python -m deepspeed_tpu.checkpoint.universal)")
        data = load_universal(udir)
        atoms, passthrough = data["params"], data["passthrough"]
        stripped_to = data.get("strip_vocab_padding")
        by_len = sorted(atoms, key=len, reverse=True)

        def lookup(key: str):
            if key.startswith("params."):
                p = key[len("params."):]
                return atoms[p][PARAM_ATOM] if p in atoms else None
            if key.startswith("opt_state."):
                if not load_optimizer_states:
                    return None
                rest = key[len("opt_state."):]
                for p in by_len:
                    if rest.endswith("." + p):
                        got = atoms[p].get(rest[:-(len(p) + 1)])
                        if got is not None:
                            return got
                return passthrough.get(key)
            return passthrough.get(key)

        def fit(arr, cur, key):
            want = tuple(np.shape(cur))
            if tuple(arr.shape) != want:
                # re-pad ONLY atoms the converter recorded as vocab-stripped
                # (strip_vocab_padding in universal_metadata.json) — a bare
                # dim-0 mismatch (e.g. different layer count) must stay a hard
                # error, not silently zero-filled "layers"
                if (stripped_to is not None and arr.ndim == len(want) and arr.ndim >= 1
                        and arr.shape[0] == stripped_to and arr.shape[0] < want[0]
                        and tuple(arr.shape[1:]) == tuple(want[1:])):
                    pad = np.zeros((want[0] - arr.shape[0], ) + tuple(arr.shape[1:]), arr.dtype)
                    arr = np.concatenate([arr, pad], axis=0)
                    log_dist(f"universal load: re-padded {key} dim0 "
                             f"{arr.shape[0] - pad.shape[0]} -> {want[0]} (vocab padding)", ranks=[0])
                else:
                    raise ValueError(f"universal atom {key} shape {arr.shape} != model {want}")
            dtype = getattr(cur, "dtype", None)
            return arr.astype(dtype) if dtype is not None and arr.dtype != dtype else arr

        if self.offload_device is not None:
            # host-offloaded Adam: atoms land in the host buffers via the same
            # state_dict path the native offload resume uses.  load_state_dict
            # consumes EVERY key's m AND v, so unmatched moments must be filled
            # from the current state (not omitted — a partial dict KeyErrors)
            template = lambda shape: np.empty(shape, np.float32)
            cur = self._offload_state.state_dict() if load_optimizer_states else None
            any_moment = False
            sd = {"m": {}, "v": {}, "step": int(passthrough.get("opt_state.step", 0))}
            for key, shape in zip(self._offload_keys, self._offload_shapes):
                a = atoms.get(key)
                if a is None:
                    logger.warning(f"universal load: no atom for param {key}; keeping current")
                    a = {}
                else:
                    self._offload_state.params[key][...] = fit(a[PARAM_ATOM], template(shape),
                                                               key).ravel()
                if load_optimizer_states:
                    for atom_name, slot in (("exp_avg", "m"), ("exp_avg_sq", "v")):
                        if atom_name in a:
                            sd[slot][key] = fit(a[atom_name], template(shape), key).ravel()
                            any_moment = True
                        else:
                            sd[slot][key] = cur[slot][key]
                    extra = sorted(set(a) - {PARAM_ATOM, "exp_avg", "exp_avg_sq"})
                    if a and (extra or "exp_avg" not in a):
                        logger.warning(
                            f"universal load (offload): param {key} has atoms {sorted(a)} "
                            f"but the host-offload Adam consumes exp_avg/exp_avg_sq only — "
                            f"unmatched moments keep their current values")
            if load_optimizer_states and any_moment:
                self._offload_state.load_state_dict(sd)
            self._push_compute_params()
        else:
            shardings = self._state_shardings(jax.eval_shape(lambda s: s, self.state))
            leaves_with_path, treedef = jax.tree_util.tree_flatten_with_path(self.state)
            shard_leaves = jax.tree_util.tree_leaves(shardings)
            multi = jax.process_count() > 1
            new_leaves = []
            for (path, cur), sharding in zip(leaves_with_path, shard_leaves):
                key = _leaf_key(path)
                arr = lookup(key)
                if arr is None:
                    skip = (not load_optimizer_states) and key.split(".")[0] in ("opt_state", "loss_scale")
                    if not skip:
                        logger.warning(f"universal load: no atom/passthrough for {key}; "
                                       f"keeping current value")
                    new_leaves.append(cur)
                    continue
                arr = fit(np.asarray(arr), cur, key)
                if multi:
                    new_leaves.append(jax.make_array_from_callback(
                        tuple(arr.shape), sharding, lambda idx, a=arr: np.asarray(a[idx])))
                else:
                    new_leaves.append(jax.device_put(arr, sharding))
            self.state = jax.tree_util.tree_unflatten(treedef, new_leaves)
        client_state = data.get("client_state", {})
        self.global_steps = client_state.get("global_steps", 0)
        self.global_samples = client_state.get("global_samples", 0)
        if "lr_scheduler" in client_state:
            self.lr_scheduler.load_state_dict(client_state["lr_scheduler"])
        log_dist(f"loaded universal checkpoint from {udir} "
                 f"({len(atoms)} parameter atoms, step={self.global_steps})", ranks=[0])
        return tag, client_state

    # ------------------------------------------------------------- utilities
    def get_fp32_params(self):
        """Gather the full fp32 master params on host — the analog of
        zero_to_fp32 consolidation (deepspeed/utils/zero_to_fp32.py)."""
        if self.offload_device is not None:
            return self._offload_host_state()["params"]
        rep = NamedSharding(self.topology.mesh, PartitionSpec())

        def gather_fp32_params(p):
            return p

        gathered = jax.jit(gather_fp32_params,
                           out_shardings=jax.tree_util.tree_map(lambda _: rep, self.state.params))(
            self.state.params)
        return jax.tree_util.tree_map(np.asarray, gathered)

    def save_16bit_model(self, save_dir: str, filename: str = "model.safetensors"):
        """Consolidated 16-bit weights for deployment/HF export — the analog of
        ``_zero3_consolidated_16bit_state_dict`` + ``save_16bit_model``
        (reference engine.py:3479,3548): ZeRO-3 shards gather leaf-by-leaf
        (never the whole tree at once), cast to the compute dtype, and land in
        one safetensors file keyed by pytree path (the HF deployment format;
        bf16-native, unlike .npz)."""
        from safetensors.numpy import save_file
        from .checkpointing import _is_rank0, _leaf_key
        os.makedirs(save_dir, exist_ok=True)
        params = (self._offload_host_state()["params"] if self.offload_device is not None
                  else self.state.params)
        rep = NamedSharding(self.topology.mesh, PartitionSpec())
        ct = self.compute_dtype
        # cast BEFORE replicating: the gather then moves 2 bytes/param, not 4
        # (the reference gathers the bit16 copy for the same reason), which is
        # why this doesn't reuse checkpointing._gather_to_host (fp32 path)

        def gather_16bit_leaf(x):
            return x.astype(ct)

        gather16 = jax.jit(gather_16bit_leaf, out_shardings=rep)
        rank0 = _is_rank0()
        out = {}
        for keypath, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
            if isinstance(leaf, jax.Array) and len(leaf.sharding.device_set) > 1:
                leaf = gather16(leaf)  # collective: every rank participates
            if rank0:  # only the writer pays the D2H copy + host RAM
                out[_leaf_key(keypath)] = np.asarray(jnp.asarray(leaf, ct))
        out_path = os.path.join(save_dir, filename)
        if rank0:  # shared storage: exactly one writer
            save_file(out, out_path)
        log_dist(f"saved 16-bit model weights ({len(out)} leaves) -> {out_path}", ranks=[0])
        return out_path
