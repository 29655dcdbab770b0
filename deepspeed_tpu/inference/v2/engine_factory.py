"""v2 engine factory — build a ragged serving engine from a HF checkpoint.

Reference ``build_hf_engine`` (inference/v2/engine_factory.py:66): resolves the
model's policy by HF ``model_type`` and assembles InferenceEngineV2.  Supported:
llama, mistral (sliding window), mixtral (MoE), olmoe (64-expert top-8 MoE with
QK-norm; serving only, training not supported), afmoe (windowed and full attention
layers mixed, a sigmoid-routed MoE; serving only), bailing_hybrid (Kimi Delta Attention
and latent attention layers mixed; serving only), nemotron_h (layers that are a Mamba-2
mixer, ungated experts or NoPE attention alone; serving only), opt, falcon, phi, qwen2, gptj.
(BLOOM serves through the v1 engine — ALiBi needs the biased dense attention,
models/bloom.py.)
"""

from typing import Any, Dict, Optional

from ...utils.logging import log_dist
from .engine_v2 import InferenceEngineV2


def _registry():
    from ...models import (afmoe, bailing_hybrid, falcon, gptj, llama, mistral, mixtral, nemotron_h, olmoe, opt,
                           phi, qwen)
    return {
        "afmoe": (afmoe, afmoe.config_from_hf),
        "bailing_hybrid": (bailing_hybrid, bailing_hybrid.config_from_hf),
        "llama": (llama, llama.config_from_hf),
        "mistral": (mistral, mistral.config_from_hf),
        "mixtral": (mixtral, None),  # config built field-by-field below
        "nemotron_h": (nemotron_h, nemotron_h.config_from_hf),
        "olmoe": (olmoe, olmoe.config_from_hf),
        "opt": (opt, opt.config_from_hf),
        "falcon": (falcon, falcon.config_from_hf),
        "phi": (phi, phi.config_from_hf),
        "qwen2": (qwen, qwen.config_from_hf),
        "gptj": (gptj, gptj.config_from_hf),
    }


def _mixtral_config(hf_config):
    from ...models.mixtral import MixtralConfig
    return MixtralConfig(
        vocab_size=hf_config.vocab_size,
        hidden_size=hf_config.hidden_size,
        intermediate_size=hf_config.intermediate_size,
        num_layers=hf_config.num_hidden_layers,
        num_heads=hf_config.num_attention_heads,
        num_kv_heads=getattr(hf_config, "num_key_value_heads", hf_config.num_attention_heads),
        num_experts=getattr(hf_config, "num_local_experts", 8),
        top_k=getattr(hf_config, "num_experts_per_tok", 2),
        max_seq_len=getattr(hf_config, "max_position_embeddings", 4096),
        rope_theta=getattr(hf_config, "rope_theta", 1e6),
        rms_eps=getattr(hf_config, "rms_norm_eps", 1e-5),
    )


def build_engine(model_type: str, model_config, params, config: Optional[Dict] = None,
                 **engine_kwargs) -> InferenceEngineV2:
    """Assemble a v2 engine for a known model family with ready params."""
    reg = _registry()
    if model_type not in reg:
        raise ValueError(f"v2 serving supports {sorted(reg)}; got {model_type!r}")
    module, _ = reg[model_type]
    return InferenceEngineV2(module, model_config, params, config=config, **engine_kwargs)


def build_hf_engine(hf_model_or_path: Any, config: Optional[Dict] = None,
                    **engine_kwargs) -> InferenceEngineV2:
    """Reference build_hf_engine analog: accepts a transformers model instance
    (or a local path loadable by transformers) and converts its weights."""
    if isinstance(hf_model_or_path, str):
        import transformers
        hf_model = transformers.AutoModelForCausalLM.from_pretrained(hf_model_or_path)
    else:
        hf_model = hf_model_or_path
    hf_config = hf_model.config
    model_type = hf_config.model_type
    reg = _registry()
    if model_type not in reg:
        raise ValueError(f"v2 serving supports {sorted(reg)}; got {model_type!r}")
    module, conv = reg[model_type]
    model_config = conv(hf_config) if conv is not None else _mixtral_config(hf_config)
    params = module.from_hf_state_dict(model_config, hf_model.state_dict())
    log_dist(f"build_hf_engine: {model_type} params ready", ranks=[0])
    return InferenceEngineV2(module, model_config, params, config=config, **engine_kwargs)
