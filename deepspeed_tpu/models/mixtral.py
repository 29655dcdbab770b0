"""Mixtral-style MoE causal LM.

Parity: the reference serves mixtral via inference/v2/model_implementations/
mixtral and trains MoE via deepspeed/moe; BASELINE.md config ladder step 5 is
Mixtral-8x7B EP+Ulysses SP.  Llama backbone with the FFN replaced by a top-k
gated expert layer; aux losses summed across layers and added to the LM loss
(reference MoE aux-loss pattern, sharded_moe.py top2gating usage).

Serving runs the one paged driver (``transformer.paged_forward``) with
Llama's callables and the expert FFN (``moe/serving.py``: sparse dispatch over
a grouped matmul) in the dense FFN's place; ``models/olmoe.py`` is this module
under OLMoE's configuration.
"""

import dataclasses
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..moe.experts import init_swiglu_experts, swiglu_experts
from ..moe.sharded_moe import TopKGate, moe_layer
from ..parallel.mesh import EXPERT_AXIS
from .transformer import (attention_block, cross_entropy_loss, init_linear, rms_norm,
                          rotary_tables)


@dataclasses.dataclass(frozen=True)
class MixtralConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 14336
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 8
    num_experts: int = 8
    top_k: int = 2
    capacity_factor: float = 1.25
    aux_loss_coef: float = 0.02
    max_seq_len: int = 4096
    rope_theta: float = 1e6
    rms_eps: float = 1e-5
    remat: bool = True
    # facts of a checkpoint's architecture, not switches: whether the top-k
    # router weights are divided by their sum (Mixtral: yes; OLMoE: no), and
    # whether q and k pass an RMSNorm over their whole width before rotary
    # (OLMoE: yes).  Serving reads both; training stays Mixtral's.
    norm_topk_prob: bool = True
    qk_norm: bool = False

    @staticmethod
    def mixtral_8x7b():
        return MixtralConfig()

    @staticmethod
    def tiny(vocab=256, hidden=64, layers=2, heads=4, kv_heads=2, experts=4, seq=64):
        return MixtralConfig(vocab_size=vocab, hidden_size=hidden, intermediate_size=hidden * 2,
                             num_layers=layers, num_heads=heads, num_kv_heads=kv_heads,
                             num_experts=experts, max_seq_len=seq)


def init_params(config: MixtralConfig, key, dtype=jnp.float32):
    k_emb, k_layers, k_out = jax.random.split(key, 3)
    L, D = config.num_layers, config.hidden_size
    H, KV = config.num_heads, config.num_kv_heads
    head_dim = D // H
    lk = jax.random.split(k_layers, 6)

    def stack(key, in_dim, out_dim):
        keys = jax.random.split(key, L)
        return jnp.stack([init_linear(k, in_dim, out_dim, dtype=dtype) for k in keys])

    def stack_experts(key):
        keys = jax.random.split(key, L)
        per_layer = [init_swiglu_experts(k, config.num_experts, D, config.intermediate_size, dtype=dtype)
                     for k in keys]
        return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *per_layer)

    gate_keys = jax.random.split(lk[4], L)
    attn = {
        "wq": stack(lk[0], D, H * head_dim),
        "wk": stack(lk[1], D, KV * head_dim),
        "wv": stack(lk[2], D, KV * head_dim),
        "wo": stack(lk[3], H * head_dim, D),
    }
    if config.qk_norm:
        attn["q_norm"] = jnp.ones((L, H * head_dim), dtype)
        attn["k_norm"] = jnp.ones((L, KV * head_dim), dtype)
    return {
        "embed": jax.random.normal(k_emb, (config.vocab_size, D), dtype) * 0.02,
        "layers": {
            "attn": attn,
            "moe": {
                "gate": {"wg": jnp.stack([jax.random.normal(k, (D, config.num_experts), dtype) * 0.02
                                          for k in gate_keys])},
                "experts": stack_experts(lk[5]),
            },
            "attn_norm": jnp.ones((L, D), dtype),
            "mlp_norm": jnp.ones((L, D), dtype),
        },
        "final_norm": jnp.ones((D, ), dtype),
        "lm_head": init_linear(k_out, D, config.vocab_size, dtype=dtype),
    }


def forward(config: MixtralConfig, params, input_ids, attention_fn=None, train=True, topo=None):
    """-> (logits, total_aux_loss)."""
    cos, sin = rotary_tables(config.hidden_size // config.num_heads, config.max_seq_len, config.rope_theta)
    x = params["embed"][input_ids]
    gate = TopKGate(config.hidden_size, config.num_experts, k=config.top_k,
                    capacity_factor=config.capacity_factor,
                    eval_capacity_factor=config.capacity_factor)

    def layer(carry, layer_params):
        x, aux = carry
        attn_in = rms_norm(x, layer_params["attn_norm"], config.rms_eps)
        attn_out, _ = attention_block(layer_params["attn"], attn_in,
                                      n_heads=config.num_heads, n_kv_heads=config.num_kv_heads,
                                      cos=cos, sin=sin, causal=True, attention_fn=attention_fn)
        x = x + attn_out
        moe_in = rms_norm(x, layer_params["mlp_norm"], config.rms_eps)
        moe_out, l_aux = moe_layer(gate, layer_params["moe"], moe_in,
                                   expert_fn=swiglu_experts, train=train, topo=topo)
        return (x + moe_out, aux + l_aux), None

    if config.remat:
        layer = jax.checkpoint(layer)
    (x, aux), _ = jax.lax.scan(layer, (x, jnp.float32(0.0)), params["layers"])
    x = rms_norm(x, params["final_norm"], config.rms_eps)
    logits = x @ params["lm_head"].astype(x.dtype)
    return logits, aux


def make_loss_fn(config: MixtralConfig, attention_fn=None, topo=None) -> Callable:

    def loss_fn(params, batch, rng):
        logits, aux = forward(config, params, batch["input_ids"], attention_fn=attention_fn, topo=topo)
        lm = cross_entropy_loss(logits, batch["labels"])
        return lm + config.aux_loss_coef * aux, {"aux_loss": aux}

    return loss_fn


def from_hf_state_dict(config: MixtralConfig, state_dict, dtype=jnp.float32):
    """Convert a HF MixtralForCausalLM state dict (block_sparse_moe naming:
    w1=gate, w3=up, w2=down) to our stacked pytree."""
    from .transformer import hf_stack, hf_tensor
    t = lambda name: hf_tensor(state_dict, name)
    L, E = config.num_layers, config.num_experts
    stack = lambda fmt, tr=True: hf_stack(state_dict, fmt, L, dtype, tr)

    def stack_expert(which):
        return jnp.asarray(np.stack([
            np.stack([t(f"model.layers.{i}.block_sparse_moe.experts.{e}.{which}.weight").T
                      for e in range(E)]) for i in range(L)]), dtype)

    return {
        "embed": jnp.asarray(t("model.embed_tokens.weight"), dtype),
        "layers": {
            "attn": {
                "wq": stack("model.layers.{}.self_attn.q_proj.weight"),
                "wk": stack("model.layers.{}.self_attn.k_proj.weight"),
                "wv": stack("model.layers.{}.self_attn.v_proj.weight"),
                "wo": stack("model.layers.{}.self_attn.o_proj.weight"),
            },
            "moe": {
                "gate": {"wg": stack("model.layers.{}.block_sparse_moe.gate.weight")},
                "experts": {"w_gate": stack_expert("w1"), "w_up": stack_expert("w3"),
                            "w_down": stack_expert("w2")},
            },
            "attn_norm": stack("model.layers.{}.input_layernorm.weight", tr=False),
            "mlp_norm": stack("model.layers.{}.post_attention_layernorm.weight", tr=False),
        },
        "final_norm": jnp.asarray(t("model.norm.weight"), dtype),
        "lm_head": jnp.asarray(t("lm_head.weight").T, dtype),
    }


def _llama_view(config: MixtralConfig):
    from .llama import LlamaConfig
    return LlamaConfig(vocab_size=config.vocab_size, hidden_size=config.hidden_size,
                       intermediate_size=config.intermediate_size, num_layers=config.num_layers,
                       num_heads=config.num_heads, num_kv_heads=config.num_kv_heads,
                       max_seq_len=config.max_seq_len, rope_theta=config.rope_theta,
                       rms_eps=config.rms_eps)


def init_paged_cache(config: MixtralConfig, num_blocks: int, block_size: int, dtype=jnp.bfloat16):
    from . import llama
    return llama.init_paged_cache(_llama_view(config), num_blocks, block_size, dtype=dtype)


def tp_rules(path: str, shape) -> "int | None":
    """Tensor-parallel layout (reference v2 sharding helpers for mixtral:
    inference/v2/model_implementations/sharding/ + mixtral container): attention
    column/row split like llama; experts sharded on the intermediate dim
    (w1/w3 column, w2 row per expert); router gate replicated."""
    if path.endswith("attn.wq"):
        return 2  # [L, in, out] -> shard out (heads)
    if path.endswith(("attn.wk", "attn.wv")):
        # GQA kv projections replicate (transformer.kv_projection_shardable)
        from .transformer import kv_projection_shardable
        return 2 if kv_projection_shardable(shape) else None
    if path.endswith("attn.wo"):
        return 1
    if path.endswith(("experts.w_gate", "experts.w_up")):
        return 3  # [L, E, D, F] -> shard F
    if path.endswith("experts.w_down"):
        return 2  # [L, E, F, D] -> shard F
    if path == "lm_head":
        return 1  # vocab-parallel logits
    return None


def make_tp_rules(config: MixtralConfig):
    """v2 serving rules: GQA kv shards head-aligned (the v2 engine validates
    kv % tp == 0 first), MQA replicates (validate_model's make_tp_rules
    contract); static tp_rules keep GQA kv replicated for GSPMD layouts
    (transformer.kv_projection_shardable)."""
    kv = config.num_kv_heads

    def rules(path: str, shape) -> "int | None":
        if path.endswith(("attn.wk", "attn.wv")):
            return 2 if kv > 1 else None
        if path.endswith("attn.q_norm"):
            return 1  # [L, H * Dh]: a QK-norm gain follows its projection's heads
        if path.endswith("attn.k_norm"):
            return 1 if kv > 1 else None
        return tp_rules(path, shape)

    return rules


# --------------------------------------------------------- paged (ragged) serve
def moe_picks_per_token(config: MixtralConfig) -> int:
    """Expert rows one token routes through a forward pass: k in every layer
    (what ``ServeCounters.moe_routed_rows`` counts a live token as)."""
    return config.top_k * config.num_layers


def moe_expert_rows(config: MixtralConfig, slots: int) -> int:
    """Rows the expert FFNs of one forward pass over ``slots`` token slots run
    their grouped matmuls over (``ServeCounters.moe_expert_rows``)."""
    from ..moe.serving import expert_rows
    return expert_rows(slots, config.top_k) * config.num_layers


def whole_width_qk_norm(config: MixtralConfig, tp_axis: Optional[str]):
    """OLMoE's QK-norm, ``qk_norm(lp, q, k) -> (q, k)``: an RMSNorm with a
    learned gain over the WHOLE projected width (all heads together, not head
    by head), before rotary (``forward_paged`` hands it in as
    ``llama.paged_callables``'s ``on_heads``).  It acts on the local heads
    ``[b, s, heads, Dh]``; where they are a tensor-parallel shard of the
    width, the sum of squares is psum'd so the statistic is the full width's."""
    dh = config.hidden_size // config.num_heads

    def norm(x, gain, full_width):
        x32 = x.astype(jnp.float32)
        ss = jnp.sum(x32 * x32, axis=(-2, -1), keepdims=True)
        if tp_axis is not None and x.shape[-2] * dh != full_width:
            ss = jax.lax.psum(ss, tp_axis)
        x32 = x32 * jax.lax.rsqrt(ss / full_width + config.rms_eps)
        return (x32 * gain.astype(jnp.float32).reshape(x.shape[-2:])).astype(x.dtype)

    def qk_norm(lp, q, k):
        return (norm(q, lp["attn"]["q_norm"], config.num_heads * dh),
                norm(k, lp["attn"]["k_norm"], config.num_kv_heads * dh))

    return qk_norm


def forward_paged(config: MixtralConfig, params, tokens, n_tokens, start_pos, block_tables,
                  kv_cache, *, block_size: int, tp_axis: Optional[str] = None,
                  gather_logits: bool = True, live_token_bound: Optional[int] = None,
                  last_rows: bool = False):
    """Ragged chunked forward (reference inference/v2/model_implementations/
    mixtral): Llama's callables, compaction and all, with the dense SwiGLU of
    a layer replaced by the no-drop sparse top-k expert FFN (moe/serving.py)
    and, where the checkpoint has it, QK-norm before rotary.  Under
    ``tp_axis`` the experts are sharded on their width and ``finish`` psums
    the expert FFN's partial sum like a dense row-parallel FFN's."""
    from . import llama, transformer
    from ..moe.serving import sparse_moe_ffn

    # the driver scans the layers' leaves; the experts stay one stack, and each
    # layer is handed its index into it (moe/serving.py says why)
    layers = params["layers"]
    experts = layers["moe"]["experts"]
    moe = {"gate": layers["moe"]["gate"], "layer": jnp.arange(config.num_layers, dtype=jnp.int32)}

    def ffn(lp, h, live):
        out = sparse_moe_ffn({"gate": lp["moe"]["gate"], "experts": experts},
                             h.reshape(-1, h.shape[-1]), config.top_k, config.norm_topk_prob,
                             live.reshape(-1), layer=lp["moe"]["layer"])
        return out.reshape(h.shape)

    on_heads = None
    if config.qk_norm:
        qk_norm = whole_width_qk_norm(config, tp_axis)
        on_heads = lambda lp, q, k, v: (*qk_norm(lp, q, k), v)
    return transformer.paged_forward(
        {**layers, "moe": moe}, tokens, n_tokens, start_pos, block_tables, kv_cache,
        block_size=block_size, live_token_bound=live_token_bound, last_rows=last_rows,
        **llama.paged_callables(_llama_view(config), params, kv_cache["k"].dtype, tp_axis,
                                gather_logits, on_heads=on_heads, ffn=ffn))
