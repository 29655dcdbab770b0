"""Inference configuration — analog of DeepSpeedInferenceConfig
(deepspeed/inference/config.py: DeepSpeedTPConfig:47, quantization/moe blocks).
"""

from typing import Any, Dict, Optional

import jax.numpy as jnp

from ..runtime.config import (KVObservabilityConfig, OpsServerConfig,
                              ServingFastpathConfig,
                              ServingFaultToleranceConfig,
                              ServingFleetConfig,
                              ServingPerfConfig,
                              ServingPrefixCacheConfig, ServingQosConfig,
                              ServingResilienceConfig,
                              ServingSpecDecodeConfig, ServingTracingConfig)
from ..runtime.config_utils import ConfigModel, Field

DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16, "float16": jnp.float16}


class TPConfig(ConfigModel):
    """Reference DeepSpeedTPConfig (inference/config.py:47)."""
    enabled: bool = True
    tp_size: int = Field(1, ge=1)


class QuantConfig(ConfigModel):
    """Weight-only quantization for serving (reference inference/quantization)."""
    enabled: bool = False
    bits: int = Field(8, choices=(4, 8))
    group_size: int = Field(2048, ge=8)


class InferenceConfig(ConfigModel):
    """Reference DeepSpeedInferenceConfig (inference/config.py)."""
    dtype: str = Field("bfloat16", choices=("float32", "bfloat16", "float16"))
    tensor_parallel: Optional[TPConfig] = None
    max_out_tokens: int = Field(1024, ge=1)
    min_out_tokens: int = Field(1, ge=1)
    max_seq_len: Optional[int] = None
    replace_with_kernel_inject: bool = False  # Pallas flash decode path
    quant: Optional[QuantConfig] = None
    # sampling defaults
    temperature: float = Field(1.0, ge=0.0)
    top_k: int = Field(0, ge=0)
    top_p: float = Field(1.0, gt=0.0, le=1.0)
    seed: int = 0
    # admission control / load shedding / preemption / stall watchdog for the
    # v2 ragged engine (runtime/config.py defines the section so train+serve
    # configs share one spelling)
    serving_resilience: ServingResilienceConfig = Field(ServingResilienceConfig)
    # serving hot-path policy (device-resident batch buffers, async step
    # pipelining, adaptive decode fusion) — inference/v2/fastpath.py
    serving_fastpath: ServingFastpathConfig = Field(ServingFastpathConfig)
    # speculative decoding on the fused decode path: draft/verify with exact
    # rejection sampling — inference/v2/spec_decode.py (section defined in
    # runtime/config.py so train+serve configs share one spelling)
    serving_spec_decode: ServingSpecDecodeConfig = Field(ServingSpecDecodeConfig)
    # request-lifecycle tracing + SLO latency histograms + flight recorder —
    # monitor/tracing.py wired through the v2 serving stack (same section
    # spelling as runtime/config.py so train+serve configs share it)
    serving_tracing: ServingTracingConfig = Field(ServingTracingConfig)
    # durable request journal + supervised restart / crash recovery —
    # inference/v2/journal.py + supervisor.py (same dual-spelling contract)
    serving_fault_tolerance: ServingFaultToleranceConfig = Field(ServingFaultToleranceConfig)
    # pull-based ops endpoints (/metrics + /healthz + /statez) and per-rank
    # metrics textfiles — monitor/ops_server.py (same dual-spelling contract)
    ops_server: OpsServerConfig = Field(OpsServerConfig)
    # block-level KV-pool observability: census + prefix-sharing opportunity
    # + capacity forecast — inference/v2/kv_metrics.py (section defined in
    # runtime/config.py so train+serve configs share one spelling)
    serving_kv_observability: KVObservabilityConfig = Field(KVObservabilityConfig)
    # copy-on-write prefix caching: shared-prefix requests map live computed
    # prompt blocks read-only and skip the duplicate prefill —
    # inference/v2/ragged_manager.py PrefixCache (section defined in
    # runtime/config.py so train+serve configs share one spelling)
    serving_prefix_cache: ServingPrefixCacheConfig = Field(ServingPrefixCacheConfig)
    # serving performance observatory: phase attribution + compile ledger
    # — monitor/perf.py wired through the v2 serve loop
    # (section defined in runtime/config.py so train+serve configs share one
    # spelling)
    serving_perf: ServingPerfConfig = Field(ServingPerfConfig)
    # fleet front-end over N supervised replicas: health-gated least-loaded
    # routing, prefix-affinity homing, shed backoff, journaled failover
    # migration — inference/v2/router.py (section defined in
    # runtime/config.py so train+serve configs share one spelling)
    serving_fleet: ServingFleetConfig = Field(ServingFleetConfig)
    # multi-tenant QoS: priority classes, per-tenant token-rate + KV-block
    # quotas, weighted-fair dequeue, tenant-keyed prefix isolation —
    # inference/v2/qos.py (section defined in runtime/config.py so
    # train+serve configs share one spelling)
    serving_qos: ServingQosConfig = Field(ServingQosConfig)

    def model_validate(self):
        if self.tensor_parallel is None:
            object.__setattr__(self, "tensor_parallel", TPConfig())
        if self.quant is None:
            object.__setattr__(self, "quant", QuantConfig())


def load_inference_config(config) -> InferenceConfig:
    if config is None:
        return InferenceConfig()
    if isinstance(config, InferenceConfig):
        return config
    return InferenceConfig(**dict(config))
