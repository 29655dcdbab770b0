"""The autotuner: memory-feasibility pruning + per-stage tuning spaces +
measured short runs.

Reference: deepspeed/autotuning/autotuner.py — ``tune:404`` walks ZeRO stages
0→3, prunes stages whose model-state memory cannot fit
(``get_instantiation_memory_required_per_gpu:882``), sweeps micro-batch sizes
within each stage's space (``tune_space:525``), and records/emits the best
config.  Differences by design:

* experiments run **in-process** — each candidate re-jits the train step
  (XLA recompile replaces the reference's per-experiment launcher sub-job,
  scheduler.py:33);
* the per-stage spaces tune TPU knobs (remat policy) instead of CUDA ones
  (allgather_bucket_size etc.), which XLA owns;
* memory math assumes bf16 params/grads + fp32 master/m/v (the engine's
  layout, runtime/engine.py), not fp16+fp32 apex conventions.
"""

import itertools
import json
import os
import time
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional

from ..utils.logging import logger
from .config import AutotuningConfig
from .tuner import BaseTuner, GridSearchTuner, ModelBasedTuner, RandomTuner

BYTES_PER_PARAM_BF16 = 2
BYTES_PER_PARAM_GRAD = 2          # grads kept in compute dtype
BYTES_PER_PARAM_OPT = 12          # fp32 master + m + v


@dataclass
class ModelInfo:
    """What the tuner needs to know about the model (reference
    ``model_info_profile_run:663`` measures this with a profile job)."""
    num_params: int
    activation_mem_per_mbs: int  # bytes of activations at micro-batch 1


def model_state_memory(num_params: int, stage: int, dp_size: int) -> int:
    """Per-chip model-state bytes under a given ZeRO stage (reference
    autotuner.py:882 ``get_instantiation_memory_required_per_gpu``)."""
    p, g, o = (num_params * BYTES_PER_PARAM_BF16, num_params * BYTES_PER_PARAM_GRAD,
               num_params * BYTES_PER_PARAM_OPT)
    d = max(1, dp_size)
    if stage == 0:
        return p + g + o
    if stage == 1:
        return p + g + o // d
    if stage == 2:
        return p + (g + o) // d
    return (p + g + o) // d


# Per-stage extra knobs (the reference's DEFAULT_TUNING_SPACE_ZERO_*,
# constants.py:116-185, retargeted to TPU knobs).
REMAT_POLICIES = ["dots_with_no_batch_dims_saveable", "nothing_saveable"]


def stage_tuning_space(stage: int, fast: bool = True) -> Dict[str, List[Any]]:
    """Fast mode (reference ``fast_enabled:386``) sweeps micro-batch only;
    full mode adds the remat policy and stage-3 ZeRO++ levers."""
    if fast:
        return {}
    space: Dict[str, List[Any]] = {"activation_checkpointing.policy": REMAT_POLICIES}
    if stage == 3:
        # ZeRO++ analogs are stage-3 levers (runtime/zero/quantized.py)
        space["zero_optimization.zero_quantized_weights"] = [False, True]
    return space


def _set_path(cfg: Dict[str, Any], dotted: str, value: Any) -> None:
    node = cfg
    keys = dotted.split(".")
    for k in keys[:-1]:
        node = node.setdefault(k, {})
    node[keys[-1]] = value


class Autotuner:
    """Searches (stage, micro-batch, knobs) and emits the best config.

    ``runner(exp_config) -> metrics`` executes one short experiment and
    returns ``{"throughput": samples/s, "latency": s, "flops": flops/s}`` or
    None on failure/OOM.  Tests stub it; production uses
    ``make_engine_runner`` below.
    """

    def __init__(self, model_info: ModelInfo, runner: Callable[[Dict[str, Any]], Optional[Dict[str, float]]],
                 user_config: Optional[Dict[str, Any]] = None, dp_size: int = 1,
                 device_memory: Optional[int] = None,
                 config: Optional[AutotuningConfig] = None):
        self.model_info = model_info
        self.runner = runner
        self.user_config = dict(user_config or {})
        self.dp_size = dp_size
        self.config = config or AutotuningConfig(
            **(self.user_config.get("autotuning") or {}))
        self.device_memory = (device_memory if device_memory is not None
                              else self.config.device_memory)
        if self.device_memory is None:
            from ..accelerator import get_accelerator
            self.device_memory = get_accelerator().total_memory()
            if not self.device_memory:
                raise ValueError(
                    "autotuning: this backend reports no device memory "
                    "(memory_stats() has no bytes_limit); set autotuning.device_memory")
        self.records: List[Dict[str, Any]] = []
        self.best_exp: Optional[Dict[str, Any]] = None
        self.best_metric: float = -float("inf")

    # ----------------------------------------------------------- search space
    def feasible_stages(self) -> List[int]:
        stages = self.config.zero_stages or [0, 1, 2, 3]
        act = self.model_info.activation_mem_per_mbs
        out = []
        for s in stages:
            need = model_state_memory(self.model_info.num_params, s, self.dp_size) + act
            if need <= self.device_memory:
                out.append(s)
            else:
                logger.info(f"autotuning: ZeRO-{s} infeasible "
                            f"(needs {need >> 20} MiB > {self.device_memory >> 20} MiB)")
        return out

    def max_micro_batch(self, stage: int) -> int:
        free = self.device_memory - model_state_memory(
            self.model_info.num_params, stage, self.dp_size)
        return max(0, free // max(1, self.model_info.activation_mem_per_mbs))

    def _user_gas(self) -> int:
        return int(self.user_config.get("gradient_accumulation_steps") or 1)

    def micro_batch_candidates(self, stage: int) -> List[int]:
        """Memory cap ∩ the user's global batch window: train_batch = mbs * gas
        * dp must land in [min_train_batch_size, max_train_batch_size]."""
        cap = self.max_micro_batch(stage)
        scale = self._user_gas() * max(1, self.dp_size)
        if self.config.max_train_batch_size:
            cap = min(cap, self.config.max_train_batch_size // scale)
        floor = -(-self.config.min_train_batch_size // scale)  # ceil div
        if self.config.micro_batch_sizes:
            return [m for m in self.config.micro_batch_sizes if floor <= m <= cap]
        out, m = [], 1
        while m <= cap:
            if m >= floor:
                out.append(m)
            m *= 2
        return out

    def experiments_for_stage(self, stage: int) -> List[Dict[str, Any]]:
        mbs_list = self.micro_batch_candidates(stage)
        if not mbs_list:
            return []
        space = stage_tuning_space(stage, fast=self.config.fast)
        keys = sorted(space)
        exps = []
        for mbs in mbs_list:
            for combo in itertools.product(*(space[k] for k in keys)):
                exp = json.loads(json.dumps(self.user_config))  # deep copy
                exp.pop("autotuning", None)
                _set_path(exp, "zero_optimization.stage", stage)
                exp["train_micro_batch_size_per_gpu"] = mbs
                # retune the batch triple: keep user gas, drop fixed total
                exp.pop("train_batch_size", None)
                for k, v in zip(keys, combo):
                    _set_path(exp, k, v)
                exps.append(exp)
        return exps

    # ------------------------------------------------------------------ tuning
    def _metric_of(self, metrics: Optional[Dict[str, float]]) -> Optional[float]:
        if metrics is None:
            return None
        name = self.config.metric
        val = metrics.get(name)
        if val is None:
            return None
        return -val if name == "latency" else val

    def _make_tuner(self, exps, run_fn) -> BaseTuner:
        cls = {"gridsearch": GridSearchTuner, "random": RandomTuner,
               "model_based": ModelBasedTuner}[self.config.tuner_type]
        return cls(exps, run_fn, early_stopping=self.config.tuner_early_stopping)

    def tune(self) -> Optional[Dict[str, Any]]:
        """Returns the best experiment config (or None if nothing ran)."""
        t0 = time.time()
        for stage in self.feasible_stages():
            exps = self.experiments_for_stage(stage)
            if not exps:
                continue
            logger.info(f"autotuning: ZeRO-{stage} space has {len(exps)} experiments")

            def run_fn(exp):
                metrics = self.runner(exp)
                rec = {"config": exp, "metrics": metrics, "stage": stage}
                self.records.append(rec)
                return self._metric_of(metrics)

            tuner = self._make_tuner(exps, run_fn)
            best_exp, best_metric = tuner.tune(num_trials=self.config.tuner_num_trials)
            if best_exp is not None and best_metric > self.best_metric:
                self.best_metric = best_metric
                self.best_exp = best_exp
        best_display = None
        if self.best_exp is not None:
            # latency is negated internally for max-comparison; report raw
            best_display = -self.best_metric if self.config.metric == "latency" else self.best_metric
        logger.info(f"autotuning: {len(self.records)} experiments in "
                    f"{time.time() - t0:.1f}s; best {self.config.metric} = {best_display}")
        return self.best_exp

    # ----------------------------------------------------------------- output
    def write_results(self) -> Optional[str]:
        """Write experiment records to exps_dir and the winning config to
        results_dir (reference autotuner.py:1055 ds_config_optimal.json);
        ``overwrite`` clears previous runs' records first."""
        import shutil
        for d in (self.config.exps_dir, self.config.results_dir):
            if self.config.overwrite and os.path.isdir(d):
                shutil.rmtree(d, ignore_errors=True)
            os.makedirs(d, exist_ok=True)
        with open(os.path.join(self.config.exps_dir, "experiments.jsonl"), "w") as fh:
            for rec in self.records:
                fh.write(json.dumps(rec) + "\n")
        if self.best_exp is None:
            return None
        path = os.path.join(self.config.results_dir, "dstpu_config_optimal.json")
        with open(path, "w") as fh:
            json.dump(self.best_exp, fh, indent=2)
        return path


def make_engine_runner(loss_fn, params, topology=None, example_batch_fn=None,
                       warmup_steps: int = 2, measure_steps: int = 3,
                       autotuning_config: Optional[AutotuningConfig] = None):
    """Build the default measured runner: construct an Engine per candidate
    config, run a few steps, report steady-state throughput/latency/flops.

    ``example_batch_fn(train_batch_size) -> batch`` supplies data.  When an
    ``autotuning_config`` is given, its start/end_profile_step define the
    warmup and measured windows (reference autotuner profile-step knobs).
    A value fetch (float(loss)) closes each measurement.
    """
    if autotuning_config is not None:
        warmup_steps = autotuning_config.start_profile_step
        measure_steps = autotuning_config.end_profile_step - autotuning_config.start_profile_step

    def runner(exp_config):
        from ..profiling.flops_profiler import FlopsProfiler
        from ..runtime.config import load_config
        from ..runtime.engine import Engine
        try:
            cfg = load_config(exp_config)
            engine = Engine(loss_fn=loss_fn, params=params, config=cfg, topology=topology)
            batch = example_batch_fn(engine.train_batch_size)
            for _ in range(max(1, warmup_steps)):
                metrics = engine.train_batch(batch)
            float(metrics.loss)  # sync before timing
            t0 = time.time()
            for _ in range(max(1, measure_steps)):
                metrics = engine.train_batch(batch)
            float(metrics.loss)  # sync on the dependent chain's tail
            dt = (time.time() - t0) / max(1, measure_steps)
            step_flops = FlopsProfiler(engine).profile_train_step(batch).flops
            samples = engine.train_batch_size
            return {"throughput": samples / dt, "latency": dt,
                    "flops": step_flops / dt}
        except Exception as e:  # OOM / invalid combo -> prune this point
            logger.warning(f"autotuning experiment failed: {e}")
            return None

    return runner
