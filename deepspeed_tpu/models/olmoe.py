"""OLMoE causal LM (arXiv:2409.02060) — serving only.

A Mixtral-shaped decoder with three differences, all facts of the checkpoint:
64 small experts of which a token takes 8, router weights that are NOT
renormalised over the picked experts (``norm_topk_prob: false``), and QK-norm
(an RMSNorm with a learned gain over the whole projected width of q and of k,
before rotary).  ``OlmoeConfig`` states them; everything else delegates to
models/mixtral, whose paged forward is Llama's callables on the one driver.

Training is not supported: the training gate (``moe/sharded_moe.TopKGate``)
takes k of 1 or 2, and ``mixtral.forward`` has no QK-norm.
"""

import dataclasses

import jax.numpy as jnp
import numpy as np

from . import mixtral
from .mixtral import MixtralConfig


@dataclasses.dataclass(frozen=True)
class OlmoeConfig(MixtralConfig):
    vocab_size: int = 50304
    hidden_size: int = 2048
    intermediate_size: int = 1024  # the width of ONE expert
    num_layers: int = 16
    num_heads: int = 16
    num_kv_heads: int = 16
    num_experts: int = 64
    top_k: int = 8
    max_seq_len: int = 4096
    rope_theta: float = 10000.0
    norm_topk_prob: bool = False
    qk_norm: bool = True

    @staticmethod
    def olmoe_1b_7b():
        return OlmoeConfig()

    @staticmethod
    def tiny(vocab=256, hidden=64, layers=2, heads=4, kv_heads=4, experts=8, top_k=4, seq=64):
        return OlmoeConfig(vocab_size=vocab, hidden_size=hidden, intermediate_size=hidden // 2,
                           num_layers=layers, num_heads=heads, num_kv_heads=kv_heads,
                           num_experts=experts, top_k=top_k, max_seq_len=seq)


init_params = mixtral.init_params
init_paged_cache = mixtral.init_paged_cache
forward_paged = mixtral.forward_paged
moe_picks_per_token = mixtral.moe_picks_per_token
moe_expert_rows = mixtral.moe_expert_rows
tp_rules = mixtral.tp_rules
make_tp_rules = mixtral.make_tp_rules


def config_from_hf(hf_config) -> OlmoeConfig:
    """An ``OlmoeConfig`` from a transformers ``OlmoeConfig``."""
    if getattr(hf_config, "clip_qkv", None) is not None:
        raise ValueError(f"clip_qkv={hf_config.clip_qkv}: clipped projections are not implemented "
                         "(the published OLMoE-1B-7B checkpoints leave it null)")
    return OlmoeConfig(
        vocab_size=hf_config.vocab_size,
        hidden_size=hf_config.hidden_size,
        intermediate_size=hf_config.intermediate_size,
        num_layers=hf_config.num_hidden_layers,
        num_heads=hf_config.num_attention_heads,
        num_kv_heads=getattr(hf_config, "num_key_value_heads", hf_config.num_attention_heads),
        num_experts=hf_config.num_experts,
        top_k=hf_config.num_experts_per_tok,
        max_seq_len=getattr(hf_config, "max_position_embeddings", 4096),
        rope_theta=float(getattr(hf_config, "rope_theta", 10000.0)),
        rms_eps=getattr(hf_config, "rms_norm_eps", 1e-5),
        norm_topk_prob=bool(getattr(hf_config, "norm_topk_prob", False)),
    )


def from_hf_state_dict(config: OlmoeConfig, state_dict, dtype=jnp.float32):
    """A HF ``OlmoeForCausalLM`` state dict (``mlp.gate``, ``mlp.experts.{e}.
    gate_proj/up_proj/down_proj``, ``self_attn.q_norm/k_norm``) as our stacked
    pytree; torch Linear stores [out, in], ours is [in, out]."""
    from .transformer import hf_stack, hf_tensor
    L, E = config.num_layers, config.num_experts
    stack = lambda fmt, tr=True: hf_stack(state_dict, fmt, L, dtype, tr)

    def stack_expert(which):
        return jnp.asarray(np.stack([
            np.stack([hf_tensor(state_dict, f"model.layers.{i}.mlp.experts.{e}.{which}.weight").T
                      for e in range(E)]) for i in range(L)]), dtype)

    return {
        "embed": jnp.asarray(hf_tensor(state_dict, "model.embed_tokens.weight"), dtype),
        "layers": {
            "attn": {
                "wq": stack("model.layers.{}.self_attn.q_proj.weight"),
                "wk": stack("model.layers.{}.self_attn.k_proj.weight"),
                "wv": stack("model.layers.{}.self_attn.v_proj.weight"),
                "wo": stack("model.layers.{}.self_attn.o_proj.weight"),
                "q_norm": stack("model.layers.{}.self_attn.q_norm.weight", tr=False),
                "k_norm": stack("model.layers.{}.self_attn.k_norm.weight", tr=False),
            },
            "moe": {
                "gate": {"wg": stack("model.layers.{}.mlp.gate.weight")},
                "experts": {"w_gate": stack_expert("gate_proj"), "w_up": stack_expert("up_proj"),
                            "w_down": stack_expert("down_proj")},
            },
            "attn_norm": stack("model.layers.{}.input_layernorm.weight", tr=False),
            "mlp_norm": stack("model.layers.{}.post_attention_layernorm.weight", tr=False),
        },
        "final_norm": jnp.asarray(hf_tensor(state_dict, "model.norm.weight"), dtype),
        "lm_head": jnp.asarray(hf_tensor(state_dict, "lm_head.weight").T, dtype),
    }
