"""Falcon causal LM (tiiuae/falcon family).

Parity: reference inference/v2/model_implementations/falcon.  Architecture vs
Llama: PARALLEL attention+MLP off one shared input LayerNorm
(x + attn(ln(x)) + mlp(ln(x))), multi-query attention (1 KV head on 7B; GQA
on 40B), rotary embeddings, GELU 4x MLP, no projection biases, tied unembed.
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
class FalconConfig:
    vocab_size: int = 65024
    hidden_size: int = 4544
    num_layers: int = 32
    num_heads: int = 71
    num_kv_heads: int = 1          # MQA on falcon-7b
    max_seq_len: int = 2048
    ln_eps: float = 1e-5
    rope_theta: float = 10000.0
    remat: bool = True

    @staticmethod
    def falcon_7b():
        return FalconConfig()

    @staticmethod
    def tiny(vocab=256, hidden=64, layers=2, heads=4, kv_heads=1, seq=64):
        return FalconConfig(vocab_size=vocab, hidden_size=hidden, num_layers=layers,
                            num_heads=heads, num_kv_heads=kv_heads, max_seq_len=seq)


def init_params(config: FalconConfig, key, dtype=jnp.float32):
    D, L, H, KV = config.hidden_size, config.num_layers, config.num_heads, config.num_kv_heads
    Dh = D // H
    ks = jax.random.split(key, 7)
    s = D ** -0.5

    def stack(k, shape):
        return jax.random.normal(k, (L, *shape), dtype) * s

    return {
        "embed": jax.random.normal(ks[0], (config.vocab_size, D), dtype) * 0.02,
        "layers": {
            "ln_w": jnp.ones((L, D), dtype), "ln_b": jnp.zeros((L, D), dtype),
            "wq": stack(ks[1], (D, H * Dh)), "wk": stack(ks[2], (D, KV * Dh)),
            "wv": stack(ks[3], (D, KV * Dh)), "wo": stack(ks[4], (H * Dh, D)),
            "fc1": stack(ks[5], (D, 4 * D)), "fc2": stack(ks[6], (4 * D, D)),
        },
        "final_ln_w": jnp.ones((D,), dtype), "final_ln_b": jnp.zeros((D,), dtype),
    }


def num_params(config: FalconConfig) -> int:
    return count_params(lambda: init_params(config, jax.random.PRNGKey(0)))


def _qkv(config: FalconConfig, lp, x, cos, sin, positions=None):
    """The layer's one LayerNorm, the projections as heads ``[b, s, heads,
    Dh]`` (the local ones under TP; MQA's one KV head) with rotary, and the
    normed ``h`` the parallel MLP reads too: ``(q, k, v, h)``."""
    Dh = config.hidden_size // config.num_heads  # TP-invariant
    h = layer_norm(x, lp["ln_w"], lp["ln_b"], config.ln_eps)
    q, k, v = ((h @ lp[w].astype(x.dtype)).reshape(x.shape[:2] + (-1, Dh))
               for w in ("wq", "wk", "wv"))
    return apply_rotary(q, cos, sin, positions), apply_rotary(k, cos, sin, positions), v, h


def _block(config: FalconConfig, lp, x, cos, sin, attention_fn=None):
    q, k, v, h = _qkv(config, lp, x, cos, sin)
    attn = (attention_fn or sdpa)(q, k, v, causal=True)
    attn_out = attn.reshape(x.shape) @ lp["wo"].astype(x.dtype)
    # HF Falcon's 'gelu' is the exact erf form, not tanh (phi's gelu_new IS tanh)
    mlp_out = jax.nn.gelu(h @ lp["fc1"].astype(x.dtype), approximate=False) @ lp["fc2"].astype(x.dtype)
    return x + attn_out + mlp_out  # parallel residual


def forward(config: FalconConfig, params, input_ids, attention_fn=None):
    Dh = config.hidden_size // config.num_heads
    cos, sin = rotary_tables(Dh, config.max_seq_len, config.rope_theta)
    x = params["embed"][input_ids]

    def body(h, lp):
        return _block(config, lp, h, cos, sin, attention_fn), None

    if config.remat:
        body = jax.checkpoint(body)
    x, _ = jax.lax.scan(body, x, params["layers"])
    x = layer_norm(x, params["final_ln_w"], params["final_ln_b"], config.ln_eps)
    return x @ params["embed"].T.astype(x.dtype)


def make_loss_fn(config: FalconConfig, attention_fn=None) -> Callable:
    def loss_fn(params, batch, rng=None):
        logits = forward(config, params, batch["input_ids"], attention_fn=attention_fn)
        return cross_entropy_loss(logits, batch["labels"])
    return loss_fn


# --------------------------------------------------------- paged (ragged) serve
def init_paged_cache(config: FalconConfig, num_blocks: int, block_size: int, dtype=jnp.bfloat16):
    return init_paged_kv_pool(config.num_layers, config.num_kv_heads,
                              config.hidden_size // config.num_heads,
                              num_blocks, block_size, dtype)


def make_tp_rules(config: FalconConfig):
    """v2 TP layout (reference inference/v2/model_implementations/sharding/
    used by the falcon containers): wq/fc1 column-parallel, wo/fc2
    row-parallel, norms/embed replicated.  MQA (num_kv_heads == 1, falcon-7b):
    wk/wv and the KV pool REPLICATE — every shard computes the same single KV
    head (the reference's KV-replication fallback in sharding/qkv.py); GQA
    40B-style (kv > 1) shards them when divisible."""
    kv = config.num_kv_heads

    def rules(path: str, shape) -> "int | None":
        if path.endswith(("wq", "fc1")):
            return 2
        if path.endswith(("wk", "wv")):
            return 2 if kv > 1 else None
        if path.endswith(("wo", "fc2")):
            return 1
        return None

    return rules


def forward_paged(config: FalconConfig, params, tokens, n_tokens, start_pos, block_tables,
                  kv_cache, *, block_size: int, tp_axis: Optional[str] = None,
                  gather_logits: bool = True, live_token_bound: Optional[int] = None,
                  last_rows: bool = False):
    """Ragged chunked Falcon forward (``transformer.paged_forward`` states the
    contract): the MQA KV pool (1 KV head) goes through the Pallas paged
    kernel's GQA head mapping.

    ``tp_axis``: q heads shard; MQA's single KV head (and its pool) replicates
    across shards — each computes the identical k/v, the GQA mapping folds all
    local q heads onto it.  The parallel-residual psum covers attn+mlp in ONE
    reduction (attn_out + mlp_out summed before the psum).  Tied unembed keeps
    full-vocab logits (gather_logits accepted for the engine's convention)."""
    Dh = config.hidden_size // config.num_heads  # TP-invariant
    cos, sin = rotary_tables(Dh, config.max_seq_len, config.rope_theta)
    dtype = kv_cache["k"].dtype
    preduce = transformer.tp_psum(tp_axis)

    def finish(lp, x, h, attn, live):
        attn_out = attn.reshape(x.shape[:2] + (-1, )) @ lp["wo"].astype(x.dtype)
        mlp_out = jax.nn.gelu(h @ lp["fc1"].astype(x.dtype),
                              approximate=False) @ lp["fc2"].astype(x.dtype)
        return x + preduce(attn_out + mlp_out)

    def head(x):
        x = layer_norm(x, params["final_ln_w"], params["final_ln_b"], config.ln_eps)
        return x @ params["embed"].T.astype(x.dtype)

    return transformer.paged_forward(
        params["layers"], tokens, n_tokens, start_pos, block_tables, kv_cache,
        block_size=block_size, live_token_bound=live_token_bound, last_rows=last_rows,
        embed=lambda tokens, safe_pos: params["embed"][tokens].astype(dtype),
        qkv=lambda lp, x, safe_pos: _qkv(config, lp, x, cos, sin, safe_pos),
        finish=finish, head=head)


# ----------------------------------------------------------------- HF import
def config_from_hf(hf_config) -> FalconConfig:
    if getattr(hf_config, "new_decoder_architecture", False):
        raise NotImplementedError(
            "new-decoder-architecture Falcon (40B/180B: ln_attn/ln_mlp split "
            "norms) is not supported by this importer")
    if getattr(hf_config, "alibi", False):
        raise NotImplementedError("alibi Falcon variants (falcon-rw) are not "
                                  "supported — this implementation is rotary")
    if getattr(hf_config, "bias", False):
        raise NotImplementedError("bias=True Falcon variants are not supported")
    if not getattr(hf_config, "parallel_attn", True):
        raise NotImplementedError("sequential-attention Falcon variants "
                                  "(parallel_attn=False) are not supported")
    # old decoder architecture: multi-query -> 1 kv head, else full MHA
    kv = 1 if getattr(hf_config, "multi_query", True) else hf_config.num_attention_heads
    return FalconConfig(vocab_size=hf_config.vocab_size, hidden_size=hf_config.hidden_size,
                        num_layers=hf_config.num_hidden_layers,
                        num_heads=hf_config.num_attention_heads, num_kv_heads=kv,
                        max_seq_len=getattr(hf_config, "max_position_embeddings", 2048),
                        ln_eps=getattr(hf_config, "layer_norm_epsilon", 1e-5),
                        rope_theta=getattr(hf_config, "rope_theta", 10000.0))


def from_hf_state_dict(config: FalconConfig, state_dict, dtype=jnp.float32):
    """Convert a FalconForCausalLM state dict.  HF stores one FUSED
    query_key_value projection [ (H + 2*KV) * Dh, D ] laid out q-then-k-then-v
    (multi-query: all H query slices first); split into our wq/wk/wv."""
    from .transformer import hf_stack, hf_tensor
    t = lambda name: hf_tensor(state_dict, name)
    H, KV = config.num_heads, config.num_kv_heads
    Dh = config.hidden_size // H
    L = config.num_layers
    pre = "transformer.h.{}"

    wq, wk, wv = [], [], []
    for i in range(L):
        qkv = t(f"transformer.h.{i}.self_attention.query_key_value.weight")  # [(H+2KV)Dh, D]
        if KV == 1:  # multi-query: [q x H, k, v]
            q, k, v = qkv[:H * Dh], qkv[H * Dh:(H + 1) * Dh], qkv[(H + 1) * Dh:]
        else:  # grouped: interleaved per-group [q x (H/KV), k, v]
            grp = H // KV
            blocks = qkv.reshape(KV, (grp + 2) * Dh, -1)
            q = blocks[:, :grp * Dh].reshape(H * Dh, -1)
            k = blocks[:, grp * Dh:(grp + 1) * Dh].reshape(KV * Dh, -1)
            v = blocks[:, (grp + 1) * Dh:].reshape(KV * Dh, -1)
        wq.append(q.T)
        wk.append(k.T)
        wv.append(v.T)

    stack = lambda fmt, transpose=True: hf_stack(state_dict, fmt, L, dtype, transpose)

    return {
        "embed": jnp.asarray(t("transformer.word_embeddings.weight"), dtype),
        "layers": {
            "ln_w": stack(pre + ".input_layernorm.weight", False),
            "ln_b": stack(pre + ".input_layernorm.bias", False),
            "wq": jnp.asarray(np.stack(wq), dtype),
            "wk": jnp.asarray(np.stack(wk), dtype),
            "wv": jnp.asarray(np.stack(wv), dtype),
            "wo": stack(pre + ".self_attention.dense.weight"),
            "fc1": stack(pre + ".mlp.dense_h_to_4h.weight"),
            "fc2": stack(pre + ".mlp.dense_4h_to_h.weight"),
        },
        "final_ln_w": jnp.asarray(t("transformer.ln_f.weight"), dtype),
        "final_ln_b": jnp.asarray(t("transformer.ln_f.bias"), dtype),
    }
