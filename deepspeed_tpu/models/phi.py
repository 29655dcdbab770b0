"""Phi causal LM (microsoft/phi-2 family).

Parity: reference inference/v2/model_implementations/phi.  Architecture:
parallel attention+MLP like Falcon but with biases everywhere, PARTIAL rotary
(only the first ``rotary_dim`` of each head rotates — phi-2's
partial_rotary_factor 0.4), GELU fc1/fc2 MLP, untied lm_head with bias.
"""

import dataclasses
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import transformer
from .transformer import (apply_rotary, causal_lm_batch, count_params,
                          cross_entropy_loss, init_paged_kv_pool, layer_norm,
                          rotary_tables, sdpa)


@dataclasses.dataclass(frozen=True)
class PhiConfig:
    vocab_size: int = 51200
    hidden_size: int = 2560
    ffn_dim: int = 10240
    num_layers: int = 32
    num_heads: int = 32
    max_seq_len: int = 2048
    partial_rotary_factor: float = 0.4
    ln_eps: float = 1e-5
    rope_theta: float = 10000.0
    remat: bool = True

    @property
    def rotary_dim(self) -> int:
        dh = self.hidden_size // self.num_heads
        # HF phi rounds the rotary slice to an even size
        return int(dh * self.partial_rotary_factor) // 2 * 2

    @staticmethod
    def phi_2():
        return PhiConfig()

    @staticmethod
    def tiny(vocab=256, hidden=64, layers=2, heads=4, seq=64):
        return PhiConfig(vocab_size=vocab, hidden_size=hidden, ffn_dim=hidden * 4,
                         num_layers=layers, num_heads=heads, max_seq_len=seq,
                         partial_rotary_factor=0.5)


def partial_rotary(x, cos, sin, rotary_dim: int, positions=None):
    """Rotate only the leading ``rotary_dim`` of the head dim; rest passes."""
    rot, rest = x[..., :rotary_dim], x[..., rotary_dim:]
    rot = apply_rotary(rot, cos, sin, positions)
    return jnp.concatenate([rot, rest], axis=-1)


def init_params(config: PhiConfig, key, dtype=jnp.float32):
    D, F, L = config.hidden_size, config.ffn_dim, config.num_layers
    ks = jax.random.split(key, 8)
    s = D ** -0.5

    def stack(k, shape):
        return jax.random.normal(k, (L, *shape), dtype) * s

    return {
        "embed": jax.random.normal(ks[0], (config.vocab_size, D), dtype) * 0.02,
        "layers": {
            "ln_w": jnp.ones((L, D), dtype), "ln_b": jnp.zeros((L, D), dtype),
            "wq": stack(ks[1], (D, D)), "bq": jnp.zeros((L, D), dtype),
            "wk": stack(ks[2], (D, D)), "bk": jnp.zeros((L, D), dtype),
            "wv": stack(ks[3], (D, D)), "bv": jnp.zeros((L, D), dtype),
            "wo": stack(ks[4], (D, D)), "bo": jnp.zeros((L, D), dtype),
            "fc1": stack(ks[5], (D, F)), "b_fc1": jnp.zeros((L, F), dtype),
            "fc2": stack(ks[6], (F, D)), "b_fc2": jnp.zeros((L, D), dtype),
        },
        "final_ln_w": jnp.ones((D,), dtype), "final_ln_b": jnp.zeros((D,), dtype),
        "lm_head": jax.random.normal(ks[7], (D, config.vocab_size), dtype) * s,
        "lm_head_b": jnp.zeros((config.vocab_size,), dtype),
    }


def num_params(config: PhiConfig) -> int:
    return count_params(lambda: init_params(config, jax.random.PRNGKey(0)))


def _qkv(config: PhiConfig, lp, x, cos, sin, positions=None):
    """The layer's one LayerNorm, the biased projections as heads ``[b, s,
    heads, Dh]`` (the local ones under TP) with partial rotary, and the normed
    ``h`` the parallel MLP reads too: ``(q, k, v, h)``."""
    Dh = config.hidden_size // config.num_heads  # TP-invariant
    h = layer_norm(x, lp["ln_w"], lp["ln_b"], config.ln_eps)
    q, k, v = ((h @ lp["w" + c].astype(x.dtype) + lp["b" + c].astype(x.dtype)).reshape(
        x.shape[:2] + (-1, Dh)) for c in "qkv")
    return (partial_rotary(q, cos, sin, config.rotary_dim, positions),
            partial_rotary(k, cos, sin, config.rotary_dim, positions), v, h)


def _block(config: PhiConfig, lp, x, cos, sin, attention_fn=None):
    q, k, v, h = _qkv(config, lp, x, cos, sin)
    attn = (attention_fn or sdpa)(q, k, v, causal=True)
    attn_out = attn.reshape(x.shape) @ lp["wo"].astype(x.dtype) + lp["bo"].astype(x.dtype)
    mlp = jax.nn.gelu(h @ lp["fc1"].astype(x.dtype) + lp["b_fc1"].astype(x.dtype),
                      approximate=True)
    mlp_out = mlp @ lp["fc2"].astype(x.dtype) + lp["b_fc2"].astype(x.dtype)
    return x + attn_out + mlp_out  # parallel residual


def forward(config: PhiConfig, params, input_ids, attention_fn=None):
    cos, sin = rotary_tables(config.rotary_dim, config.max_seq_len, config.rope_theta)
    x = params["embed"][input_ids]

    def body(h, lp):
        return _block(config, lp, h, cos, sin, attention_fn), None

    if config.remat:
        body = jax.checkpoint(body)
    x, _ = jax.lax.scan(body, x, params["layers"])
    x = layer_norm(x, params["final_ln_w"], params["final_ln_b"], config.ln_eps)
    return x @ params["lm_head"].astype(x.dtype) + params["lm_head_b"].astype(x.dtype)


def make_loss_fn(config: PhiConfig, attention_fn=None) -> Callable:
    def loss_fn(params, batch, rng=None):
        logits = forward(config, params, batch["input_ids"], attention_fn=attention_fn)
        return cross_entropy_loss(logits, batch["labels"])
    return loss_fn


# --------------------------------------------------------- paged (ragged) serve
def init_paged_cache(config: PhiConfig, num_blocks: int, block_size: int, dtype=jnp.bfloat16):
    return init_paged_kv_pool(config.num_layers, config.num_heads,
                              config.hidden_size // config.num_heads,
                              num_blocks, block_size, dtype)


def tp_rules(path: str, shape) -> "int | None":
    """v2 TP layout (reference inference/v2/model_implementations/sharding/
    used by the phi containers): qkv + fc1 column-parallel with their biases;
    wo/fc2 row-parallel with replicated biases (added once after the psum);
    untied lm_head vocab-parallel with its bias sharded alongside."""
    if path.endswith(("bo", "b_fc2")):
        return None  # row-parallel biases replicate (added once, post-psum)
    if path.endswith(("bq", "bk", "bv", "b_fc1")):
        return 1
    # bias checks precede weights: "b_fc1"/"b_fc2" suffix-match "fc1"/"fc2"
    if path.endswith(("wq", "fc1")):
        return 2
    if path.endswith(("wk", "wv")):
        from .transformer import kv_projection_shardable
        return 2 if kv_projection_shardable(shape) else None
    if path.endswith(("wo", "fc2")):
        return 1
    if path == "lm_head":
        return 1  # [D, V] vocab-parallel
    if path == "lm_head_b":
        return 0  # [V] sharded with its vocab slice
    return None


def forward_paged(config: PhiConfig, params, tokens, n_tokens, start_pos, block_tables,
                  kv_cache, *, block_size: int, tp_axis: Optional[str] = None,
                  gather_logits: bool = True, live_token_bound: Optional[int] = None,
                  last_rows: bool = False):
    """Ragged chunked Phi forward (``transformer.paged_forward`` states the
    contract): partial rotary feeds the paged kernel.

    ``tp_axis``: heads shard; the parallel residual's attn+mlp partials reduce
    in ONE psum with the replicated bo/b_fc2 added after it.  The untied
    lm_head is vocab-parallel: the local bias slice lands on local logits
    before the (optional) gather, so greedy decode can argmax the local shard
    (gather_logits=False) without moving O(V) over ICI."""
    cos, sin = rotary_tables(config.rotary_dim, config.max_seq_len, config.rope_theta)
    dtype = kv_cache["k"].dtype
    preduce = transformer.tp_psum(tp_axis)

    def finish(lp, x, h, attn, live):
        attn_out = attn.reshape(x.shape[:2] + (-1, )) @ lp["wo"].astype(x.dtype)
        mlp = jax.nn.gelu(h @ lp["fc1"].astype(x.dtype) + lp["b_fc1"].astype(x.dtype),
                          approximate=True)
        return x + preduce(attn_out + mlp @ lp["fc2"].astype(x.dtype)) \
            + lp["bo"].astype(x.dtype) + lp["b_fc2"].astype(x.dtype)

    def head(x):
        x = layer_norm(x, params["final_ln_w"], params["final_ln_b"], config.ln_eps)
        logits = x @ params["lm_head"].astype(x.dtype) + params["lm_head_b"].astype(x.dtype)
        if tp_axis is not None and gather_logits:
            logits = jax.lax.all_gather(logits, tp_axis, axis=-1, tiled=True)
        return logits

    return transformer.paged_forward(
        params["layers"], tokens, n_tokens, start_pos, block_tables, kv_cache,
        block_size=block_size, live_token_bound=live_token_bound, last_rows=last_rows,
        embed=lambda tokens, safe_pos: params["embed"][tokens].astype(dtype),
        qkv=lambda lp, x, safe_pos: _qkv(config, lp, x, cos, sin, safe_pos),
        finish=finish, head=head)


# ----------------------------------------------------------------- HF import
def config_from_hf(hf_config) -> PhiConfig:
    if getattr(hf_config, "qk_layernorm", False):
        raise NotImplementedError("qk_layernorm Phi variants are not supported")
    kv = getattr(hf_config, "num_key_value_heads", None)
    if kv is not None and kv != hf_config.num_attention_heads:
        raise NotImplementedError("GQA Phi variants (num_key_value_heads < "
                                  "num_attention_heads) are not supported")
    return PhiConfig(vocab_size=hf_config.vocab_size, hidden_size=hf_config.hidden_size,
                     ffn_dim=hf_config.intermediate_size,
                     num_layers=hf_config.num_hidden_layers,
                     num_heads=hf_config.num_attention_heads,
                     max_seq_len=hf_config.max_position_embeddings,
                     partial_rotary_factor=getattr(hf_config, "partial_rotary_factor", 0.4),
                     ln_eps=getattr(hf_config, "layer_norm_eps", 1e-5),
                     rope_theta=getattr(hf_config, "rope_theta", 10000.0))


def from_hf_state_dict(config: PhiConfig, state_dict, dtype=jnp.float32):
    """Convert a PhiForCausalLM state dict (biases everywhere, untied head)."""
    from .transformer import hf_stack, hf_tensor
    t = lambda name: hf_tensor(state_dict, name)
    L = config.num_layers
    pre = "model.layers.{}"
    stack = lambda fmt, transpose=True: hf_stack(state_dict, fmt, L, dtype, transpose)

    return {
        "embed": jnp.asarray(t("model.embed_tokens.weight"), dtype),
        "layers": {
            "ln_w": stack(pre + ".input_layernorm.weight", False),
            "ln_b": stack(pre + ".input_layernorm.bias", False),
            "wq": stack(pre + ".self_attn.q_proj.weight"),
            "bq": stack(pre + ".self_attn.q_proj.bias", False),
            "wk": stack(pre + ".self_attn.k_proj.weight"),
            "bk": stack(pre + ".self_attn.k_proj.bias", False),
            "wv": stack(pre + ".self_attn.v_proj.weight"),
            "bv": stack(pre + ".self_attn.v_proj.bias", False),
            "wo": stack(pre + ".self_attn.dense.weight"),
            "bo": stack(pre + ".self_attn.dense.bias", False),
            "fc1": stack(pre + ".mlp.fc1.weight"),
            "b_fc1": stack(pre + ".mlp.fc1.bias", False),
            "fc2": stack(pre + ".mlp.fc2.weight"),
            "b_fc2": stack(pre + ".mlp.fc2.bias", False),
        },
        "final_ln_w": jnp.asarray(t("model.final_layernorm.weight"), dtype),
        "final_ln_b": jnp.asarray(t("model.final_layernorm.bias"), dtype),
        "lm_head": jnp.asarray(t("lm_head.weight").T, dtype),
        "lm_head_b": jnp.asarray(t("lm_head.bias"), dtype),
    }
