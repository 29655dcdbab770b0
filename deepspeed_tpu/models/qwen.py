"""Qwen2 causal LM (Qwen/Qwen2 family).

Parity: reference inference/v2/model_implementations/qwen.  Qwen2 is the
Llama architecture with BIASES on the Q/K/V projections (output projection
and MLP stay bias-free) — so everything delegates to models/llama with the
bias terms folded in by pre-adding them through a wrapped forward.

Implementation note: rather than forking llama's scan, the qkv biases are
threaded as extra per-layer params and applied via a custom block that calls
the same building blocks (transformer.attention_block has no bias slot, so
the block is written out here; the paged path is Llama's callables with
the three bias adds handed in).
"""

import dataclasses
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import llama, transformer
from .llama import LlamaConfig
from .transformer import (apply_rotary, count_params, cross_entropy_loss, rms_norm,
                          rotary_tables, sdpa, swiglu_mlp)


@dataclasses.dataclass(frozen=True)
class QwenConfig(LlamaConfig):

    @staticmethod
    def qwen2_7b():
        return QwenConfig(vocab_size=152064, hidden_size=3584, intermediate_size=18944,
                          num_layers=28, num_heads=28, num_kv_heads=4,
                          max_seq_len=32768, rope_theta=1000000.0)

    @staticmethod
    def tiny(vocab=256, hidden=64, layers=2, heads=4, kv_heads=2, seq=64):
        return QwenConfig(vocab_size=vocab, hidden_size=hidden, intermediate_size=hidden * 2,
                          num_layers=layers, num_heads=heads, num_kv_heads=kv_heads,
                          max_seq_len=seq)


def init_params(config: QwenConfig, key, dtype=jnp.float32):
    """Llama params + per-layer q/k/v biases."""
    params = llama.init_params(config, key, dtype)
    L = config.num_layers
    H, KV = config.num_heads, config.num_kv_heads
    Dh = config.hidden_size // H
    params["layers"]["attn"]["bq"] = jnp.zeros((L, H * Dh), dtype)
    params["layers"]["attn"]["bk"] = jnp.zeros((L, KV * Dh), dtype)
    params["layers"]["attn"]["bv"] = jnp.zeros((L, KV * Dh), dtype)
    return params


def num_params(config: QwenConfig) -> int:
    return count_params(lambda: init_params(config, jax.random.PRNGKey(0)))


def _block(config: QwenConfig, lp, x, cos, sin, attention_fn=None):
    b, s, D = x.shape
    H, KV = config.num_heads, config.num_kv_heads
    Dh = D // H
    a = lp["attn"]
    attn_in = rms_norm(x, lp["attn_norm"], config.rms_eps)
    q = (attn_in @ a["wq"].astype(x.dtype) + a["bq"].astype(x.dtype)).reshape(b, s, H, Dh)
    k = (attn_in @ a["wk"].astype(x.dtype) + a["bk"].astype(x.dtype)).reshape(b, s, KV, Dh)
    v = (attn_in @ a["wv"].astype(x.dtype) + a["bv"].astype(x.dtype)).reshape(b, s, KV, Dh)
    q = apply_rotary(q, cos, sin)
    k = apply_rotary(k, cos, sin)
    out = (attention_fn or sdpa)(q, k, v, causal=True)
    x = x + out.reshape(b, s, H * Dh) @ a["wo"].astype(x.dtype)
    mlp_in = rms_norm(x, lp["mlp_norm"], config.rms_eps)
    return x + swiglu_mlp(lp["mlp"], mlp_in)


def forward(config: QwenConfig, params, input_ids, attention_fn=None):
    Dh = config.hidden_size // config.num_heads
    cos, sin = rotary_tables(Dh, config.max_seq_len, config.rope_theta)
    x = params["embed"][input_ids]

    def body(h, lp):
        return _block(config, lp, h, cos, sin, attention_fn), None

    if config.remat:
        body = jax.checkpoint(body)
    x, _ = jax.lax.scan(body, x, params["layers"])
    x = rms_norm(x, params["final_norm"], config.rms_eps)
    head = params["embed"].T if config.tie_embeddings else params["lm_head"]
    return x @ head.astype(x.dtype)


def make_loss_fn(config: QwenConfig, attention_fn=None) -> Callable:
    def loss_fn(params, batch, rng=None):
        logits = forward(config, params, batch["input_ids"], attention_fn=attention_fn)
        return cross_entropy_loss(logits, batch["labels"])
    return loss_fn


causal_lm_batch = llama.causal_lm_batch
init_paged_cache = llama.init_paged_cache


def tp_rules(path: str, shape) -> "int | None":
    """Llama's column/row layout + qwen's qkv biases sharded with their
    column-parallel weights ([L, out] -> dim 1)."""
    if path.endswith("attn.bq"):
        return 1
    if path.endswith(("attn.bk", "attn.bv")):
        # kv biases must follow their weights: the static rules replicate GQA
        # kv projections (transformer.kv_projection_shardable — a bias's
        # [L, out] shape can't even distinguish GQA), so a sharded bias here
        # would hint the sub-head kv layout the weight rule exists to prevent;
        # make_tp_rules restores head-aligned sharding where config is known
        return None
    return llama.tp_rules(path, shape)


def make_tp_rules(config: QwenConfig):
    """v2 serving rules: GQA kv (weights AND their biases) shards
    head-aligned (the v2 engine validates kv % tp == 0 first), MQA
    replicates (validate_model's make_tp_rules contract); static tp_rules
    keep GQA kv replicated for GSPMD layouts
    (transformer.kv_projection_shardable)."""
    kv = config.num_kv_heads

    def rules(path: str, shape) -> "int | None":
        if path.endswith(("attn.wk", "attn.wv")):
            return 2 if kv > 1 else None
        if path.endswith(("attn.bk", "attn.bv")):
            return 1 if kv > 1 else None
        return tp_rules(path, shape)

    return rules


def _add_qkv_biases(lp, q, k, v):
    """Qwen's three biases on the projected local heads ``[b, s, heads, Dh]``
    (a column-parallel weight's bias rides its shard)."""
    add = lambda y, b: y + lp["attn"][b].astype(y.dtype).reshape(y.shape[-2:])
    return add(q, "bq"), add(k, "bk"), add(v, "bv")


def forward_paged(config: QwenConfig, params, tokens, n_tokens, start_pos, block_tables,
                  kv_cache, *, block_size: int, tp_axis: Optional[str] = None,
                  gather_logits: bool = True, live_token_bound: Optional[int] = None,
                  last_rows: bool = False):
    """Ragged chunked Qwen2 forward: Llama's callables with the qkv biases
    added before rotary (``transformer.paged_forward`` states the contract)."""
    return transformer.paged_forward(
        params["layers"], tokens, n_tokens, start_pos, block_tables, kv_cache,
        block_size=block_size, live_token_bound=live_token_bound, last_rows=last_rows,
        **llama.paged_callables(config, params, kv_cache["k"].dtype, tp_axis, gather_logits,
                                on_heads=_add_qkv_biases))


# ----------------------------------------------------------------- HF import
def config_from_hf(hf_config) -> QwenConfig:
    base = llama.config_from_hf(hf_config)
    return QwenConfig(**dataclasses.asdict(base))


def from_hf_state_dict(config: QwenConfig, state_dict, dtype=jnp.float32):
    """Qwen2ForCausalLM = llama layout + q/k/v biases."""
    params = llama.from_hf_state_dict(config, state_dict, dtype)

    from .transformer import hf_stack
    L = config.num_layers
    stack_bias = lambda fmt: hf_stack(state_dict, fmt, L, dtype, transpose=False)

    params["layers"]["attn"]["bq"] = stack_bias("model.layers.{}.self_attn.q_proj.bias")
    params["layers"]["attn"]["bk"] = stack_bias("model.layers.{}.self_attn.k_proj.bias")
    params["layers"]["attn"]["bv"] = stack_bias("model.layers.{}.self_attn.v_proj.bias")
    return params
