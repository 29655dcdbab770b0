"""OPT causal LM (facebook/opt family).

Parity: reference inference/v2/model_implementations/opt (container + policy
serving OPT with blocked flash).  Architecture vs Llama: learned positional
embeddings (with OPT's +2 offset quirk), pre-LayerNorm blocks with biases,
standard MHA (no GQA), ReLU fc1/fc2 MLP, tied unembedding.

Training forward is a scan over stacked layers (ZeRO-3-friendly like
models/llama.py); ``forward_paged`` serves ragged batches through the Pallas
paged kernel (ops/attention/paged.py).
"""

import dataclasses
import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import transformer
from .transformer import (causal_lm_batch, count_params, cross_entropy_loss,
                          init_paged_kv_pool, layer_norm, sdpa)

POS_OFFSET = 2  # OPT reserves the first two position slots (HF modeling_opt)


@dataclasses.dataclass(frozen=True)
class OPTConfig:
    vocab_size: int = 50272
    hidden_size: int = 768
    ffn_dim: int = 3072
    num_layers: int = 12
    num_heads: int = 12
    max_seq_len: int = 2048
    ln_eps: float = 1e-5
    remat: bool = True

    @staticmethod
    def opt_125m():
        return OPTConfig()

    @staticmethod
    def tiny(vocab=256, hidden=64, layers=2, heads=4, seq=64):
        return OPTConfig(vocab_size=vocab, hidden_size=hidden, ffn_dim=hidden * 4,
                         num_layers=layers, num_heads=heads, max_seq_len=seq)


def init_params(config: OPTConfig, key, dtype=jnp.float32):
    D, F, L = config.hidden_size, config.ffn_dim, config.num_layers
    ks = jax.random.split(key, 8)
    s = D ** -0.5

    def stack(k, shape):
        return jax.random.normal(k, (L, *shape), dtype) * s

    return {
        "embed": jax.random.normal(ks[0], (config.vocab_size, D), dtype) * 0.02,
        "pos_embed": jax.random.normal(ks[1], (config.max_seq_len + POS_OFFSET, D), dtype) * 0.02,
        "layers": {
            "ln1_w": jnp.ones((L, D), dtype), "ln1_b": jnp.zeros((L, D), dtype),
            "ln2_w": jnp.ones((L, D), dtype), "ln2_b": jnp.zeros((L, D), dtype),
            "wq": stack(ks[2], (D, D)), "wk": stack(ks[3], (D, D)),
            "wv": stack(ks[4], (D, D)), "wo": stack(ks[5], (D, D)),
            "bq": jnp.zeros((L, D), dtype), "bk": jnp.zeros((L, D), dtype),
            "bv": jnp.zeros((L, D), dtype), "bo": jnp.zeros((L, D), dtype),
            "fc1": stack(ks[6], (D, F)), "b_fc1": jnp.zeros((L, F), dtype),
            "fc2": stack(ks[7], (F, D)), "b_fc2": jnp.zeros((L, D), dtype),
        },
        "final_ln_w": jnp.ones((D,), dtype), "final_ln_b": jnp.zeros((D,), dtype),
    }


def num_params(config: OPTConfig) -> int:
    return count_params(lambda: init_params(config, jax.random.PRNGKey(0)))


def _qkv(config: OPTConfig, lp, x):
    """Pre-LayerNorm and the biased projections as heads ``[b, s, heads, Dh]``
    (the local ones under TP); positions are learned, nothing rotates."""
    Dh = config.hidden_size // config.num_heads  # TP-invariant head dim
    h = layer_norm(x, lp["ln1_w"], lp["ln1_b"], config.ln_eps)
    return tuple((h @ lp["w" + c].astype(x.dtype) + lp["b" + c].astype(x.dtype)).reshape(
        x.shape[:2] + (-1, Dh)) for c in "qkv")


def _block(config: OPTConfig, lp, x, attention_fn=None):
    attn = (attention_fn or sdpa)(*_qkv(config, lp, x), causal=True)
    x = x + attn.reshape(x.shape) @ lp["wo"].astype(x.dtype) + lp["bo"].astype(x.dtype)
    h = layer_norm(x, lp["ln2_w"], lp["ln2_b"], config.ln_eps)
    h = jax.nn.relu(h @ lp["fc1"].astype(x.dtype) + lp["b_fc1"].astype(x.dtype))
    return x + h @ lp["fc2"].astype(x.dtype) + lp["b_fc2"].astype(x.dtype)


def forward(config: OPTConfig, params, input_ids, attention_fn=None):
    s = input_ids.shape[1]
    x = params["embed"][input_ids]
    x = x + params["pos_embed"][POS_OFFSET:POS_OFFSET + s][None].astype(x.dtype)

    def body(h, lp):
        return _block(config, lp, h, attention_fn), None

    if config.remat:
        body = jax.checkpoint(body)
    x, _ = jax.lax.scan(body, x, params["layers"])
    x = layer_norm(x, params["final_ln_w"], params["final_ln_b"], config.ln_eps)
    return x @ params["embed"].T.astype(x.dtype)  # tied unembed


def make_loss_fn(config: OPTConfig, attention_fn=None) -> Callable:
    def loss_fn(params, batch, rng=None):
        logits = forward(config, params, batch["input_ids"], attention_fn=attention_fn)
        return cross_entropy_loss(logits, batch["labels"])
    return loss_fn


# --------------------------------------------------------- paged (ragged) serve
def init_paged_cache(config: OPTConfig, num_blocks: int, block_size: int, dtype=jnp.bfloat16):
    return init_paged_kv_pool(config.num_layers, config.num_heads,
                              config.hidden_size // config.num_heads,
                              num_blocks, block_size, dtype)


def tp_rules(path: str, shape) -> "int | None":
    """v2 TP layout (reference inference/v2/model_implementations/sharding/):
    qkv + fc1 column-parallel WITH their biases; wo/fc2 row-parallel with
    replicated biases (added once, after the psum); embeddings/norms replicated
    (tied unembed keeps full-vocab logits on every shard)."""
    if path.endswith(("bo", "b_fc2")):
        return None  # row-parallel biases replicate (added once, post-psum)
    if path.endswith(("bq", "bk", "bv", "b_fc1")):
        return 1  # [L, out] -> shard with the matching column weight
    # bias checks precede weights: "b_fc1"/"b_fc2" suffix-match "fc1"/"fc2"
    if path.endswith(("wq", "wk", "wv", "fc1")):
        return 2  # [L, in, out] -> shard out
    if path.endswith(("wo", "fc2")):
        return 1  # [L, in, out] -> shard in
    return None


def forward_paged(config: OPTConfig, params, tokens, n_tokens, start_pos, block_tables,
                  kv_cache, *, block_size: int, tp_axis: Optional[str] = None,
                  gather_logits: bool = True, live_token_bound: Optional[int] = None,
                  last_rows: bool = False):
    """Ragged chunked OPT forward (``transformer.paged_forward`` states the
    contract): learned positions, no rotary on K/Q.

    ``tp_axis``: row-parallel biases (bo, b_fc2) are replicated and added
    AFTER the psum so they count once.  The tied unembedding is replicated, so
    logits are always full-vocab (gather_logits is a no-op, accepted for the
    engine's uniform calling convention)."""
    dtype = kv_cache["k"].dtype
    preduce = transformer.tp_psum(tp_axis)

    def embed(tokens, safe_pos):
        x = params["embed"][tokens].astype(dtype)
        return x + params["pos_embed"][safe_pos + POS_OFFSET].astype(x.dtype)

    def finish(lp, x, kept, attn, live):
        x = x + preduce(attn.reshape(x.shape[:2] + (-1, )) @ lp["wo"].astype(x.dtype)) \
            + lp["bo"].astype(x.dtype)
        h = layer_norm(x, lp["ln2_w"], lp["ln2_b"], config.ln_eps)
        h = jax.nn.relu(h @ lp["fc1"].astype(x.dtype) + lp["b_fc1"].astype(x.dtype))
        return x + preduce(h @ lp["fc2"].astype(x.dtype)) + lp["b_fc2"].astype(x.dtype)

    def head(x):
        x = layer_norm(x, params["final_ln_w"], params["final_ln_b"], config.ln_eps)
        return x @ params["embed"].T.astype(x.dtype)

    return transformer.paged_forward(
        params["layers"], tokens, n_tokens, start_pos, block_tables, kv_cache,
        block_size=block_size, live_token_bound=live_token_bound, last_rows=last_rows,
        embed=embed, qkv=lambda lp, x, safe_pos: (*_qkv(config, lp, x), None), finish=finish,
        head=head)


# ----------------------------------------------------------------- HF import
def config_from_hf(hf_config) -> OPTConfig:
    if not getattr(hf_config, "do_layer_norm_before", True):
        raise NotImplementedError(
            "post-LN OPT variants (do_layer_norm_before=False, e.g. opt-350m) "
            "are not supported — this implementation is pre-LN")
    if getattr(hf_config, "word_embed_proj_dim", hf_config.hidden_size) != hf_config.hidden_size:
        raise NotImplementedError(
            "OPT variants with word_embed_proj_dim != hidden_size (project_in/out "
            "layers, e.g. opt-350m) are not supported")
    return OPTConfig(vocab_size=hf_config.vocab_size, hidden_size=hf_config.hidden_size,
                     ffn_dim=hf_config.ffn_dim, num_layers=hf_config.num_hidden_layers,
                     num_heads=hf_config.num_attention_heads,
                     max_seq_len=hf_config.max_position_embeddings)


def from_hf_state_dict(config: OPTConfig, state_dict, dtype=jnp.float32):
    """Convert an OPTForCausalLM state dict (module_inject/load_checkpoint.py
    analog).  HF's learned positional table already contains the +2 offset
    rows; torch Linear [out, in] transposes to our [in, out]."""
    from .transformer import hf_stack, hf_tensor
    t = lambda name: hf_tensor(state_dict, name)
    L = config.num_layers
    pre = "model.decoder.layers.{}"
    stack = lambda fmt, transpose=True: hf_stack(state_dict, fmt, L, dtype, transpose)

    return {
        "embed": jnp.asarray(t("model.decoder.embed_tokens.weight"), dtype),
        "pos_embed": jnp.asarray(t("model.decoder.embed_positions.weight"), dtype),
        "layers": {
            "ln1_w": stack(pre + ".self_attn_layer_norm.weight", False),
            "ln1_b": stack(pre + ".self_attn_layer_norm.bias", False),
            "ln2_w": stack(pre + ".final_layer_norm.weight", False),
            "ln2_b": stack(pre + ".final_layer_norm.bias", False),
            "wq": stack(pre + ".self_attn.q_proj.weight"),
            "wk": stack(pre + ".self_attn.k_proj.weight"),
            "wv": stack(pre + ".self_attn.v_proj.weight"),
            "wo": stack(pre + ".self_attn.out_proj.weight"),
            "bq": stack(pre + ".self_attn.q_proj.bias", False),
            "bk": stack(pre + ".self_attn.k_proj.bias", False),
            "bv": stack(pre + ".self_attn.v_proj.bias", False),
            "bo": stack(pre + ".self_attn.out_proj.bias", False),
            "fc1": stack(pre + ".fc1.weight"),
            "b_fc1": stack(pre + ".fc1.bias", False),
            "fc2": stack(pre + ".fc2.weight"),
            "b_fc2": stack(pre + ".fc2.bias", False),
        },
        "final_ln_w": jnp.asarray(t("model.decoder.final_layer_norm.weight"), dtype),
        "final_ln_b": jnp.asarray(t("model.decoder.final_layer_norm.bias"), dtype),
    }
