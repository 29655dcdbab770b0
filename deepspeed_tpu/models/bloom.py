"""BLOOM causal LM (bigscience/bloom family).

Parity: reference module_inject/containers/bloom.py + replace_policy BLOOM —
the reference serves BLOOM via kernel injection; here it's a first-class
family.  Architecture: embedding LayerNorm after the word embeddings, ALiBi
positional biases (no rotary/learned positions), per-head-interleaved fused
QKV with biases, sequential residuals, tanh-gelu 4x MLP with biases, tied
unembedding.

ALiBi: each head h adds slope_h * key_index to its attention scores — the
key-only form is softmax-equivalent to the relative-distance form (each query
row differs by a constant), which is exactly how HF builds the bias
(modeling_bloom.build_alibi_tensor).  Attention runs through a local
biased-sdpa in training (the generic attention_fn hook has no bias slot);
serving goes through ``forward_with_cache`` (v1) or ``forward_paged`` (v2
ragged serving — the paged kernel's ``alibi_slopes`` operand carries the
key-only bias, ops/attention/paged.py).
"""

import dataclasses
import math
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import transformer
from .transformer import causal_lm_batch, count_params, cross_entropy_loss, layer_norm


@dataclasses.dataclass(frozen=True)
class BloomConfig:
    vocab_size: int = 250880
    hidden_size: int = 4096
    num_layers: int = 30
    num_heads: int = 32
    max_seq_len: int = 2048
    ln_eps: float = 1e-5
    remat: bool = True

    @staticmethod
    def bloom_7b1():
        return BloomConfig()

    @staticmethod
    def tiny(vocab=256, hidden=64, layers=2, heads=4, seq=64):
        return BloomConfig(vocab_size=vocab, hidden_size=hidden, num_layers=layers,
                           num_heads=heads, max_seq_len=seq)


def alibi_slopes(num_heads: int) -> np.ndarray:
    """The ALiBi slope schedule (train-short-test-long paper; HF
    build_alibi_tensor): powers of 2^(-8/n) for the nearest power-of-two head
    count, interleaved extras for the rest."""
    def pow2_slopes(n):
        start = 2.0 ** (-(2.0 ** -(math.log2(n) - 3)))
        return [start * (start ** i) for i in range(n)]

    if math.log2(num_heads).is_integer():
        return np.asarray(pow2_slopes(num_heads), np.float32)
    closest = 2 ** math.floor(math.log2(num_heads))
    extra = pow2_slopes(2 * closest)[0::2][:num_heads - closest]
    return np.asarray(pow2_slopes(closest) + extra, np.float32)


def _biased_sdpa(q, k, v, slopes, kpos, causal_mask):
    """sdpa with per-head ALiBi key bias.  q/k/v [B, S(q/k), H, D];
    kpos [Sk] absolute key positions; causal_mask [Sq, Sk] bool."""
    d = q.shape[-1]
    scale = 1.0 / np.sqrt(d)
    qf = q.astype(jnp.float32)
    kf = k.astype(jnp.float32)
    scores = jnp.einsum("bqhd,bkhd->bhqk", qf, kf) * scale
    bias = slopes[None, :, None, None] * kpos[None, None, None, :].astype(jnp.float32)
    scores = scores + bias
    scores = jnp.where(causal_mask[None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def init_params(config: BloomConfig, key, dtype=jnp.float32):
    D, L, V = config.hidden_size, config.num_layers, config.vocab_size
    ks = jax.random.split(key, 5)
    s = D ** -0.5

    def stack(k, shape):
        return jax.random.normal(k, (L, *shape), dtype) * s

    return {
        "embed": jax.random.normal(ks[0], (V, D), dtype) * 0.02,
        "embed_ln_w": jnp.ones((D,), dtype), "embed_ln_b": jnp.zeros((D,), dtype),
        "layers": {
            "ln1_w": jnp.ones((L, D), dtype), "ln1_b": jnp.zeros((L, D), dtype),
            "ln2_w": jnp.ones((L, D), dtype), "ln2_b": jnp.zeros((L, D), dtype),
            # fused per-head-interleaved qkv: [D, 3D] with rows grouped (q,k,v)
            # per head (the HF layout, split in _qkv)
            "w_qkv": stack(ks[1], (D, 3 * D)), "b_qkv": jnp.zeros((L, 3 * D), dtype),
            "wo": stack(ks[2], (D, D)), "bo": jnp.zeros((L, D), dtype),
            "fc1": stack(ks[3], (D, 4 * D)), "b_fc1": jnp.zeros((L, 4 * D), dtype),
            "fc2": stack(ks[4], (4 * D, D)), "b_fc2": jnp.zeros((L, D), dtype),
        },
        "final_ln_w": jnp.ones((D,), dtype), "final_ln_b": jnp.zeros((D,), dtype),
    }


def num_params(config: BloomConfig) -> int:
    return count_params(lambda: init_params(config, jax.random.PRNGKey(0)))


def _qkv(config: BloomConfig, lp, x):
    """Pre-LayerNorm and the fused biased projection, ``[B, S, 3D]``
    per-head-interleaved, split into q/k/v ``[B, S, heads, Dh]`` each (the
    local heads under TP: the fused width shards on head boundaries)."""
    Dh = config.hidden_size // config.num_heads
    h = layer_norm(x, lp["ln1_w"], lp["ln1_b"], config.ln_eps)
    fused = (h @ lp["w_qkv"].astype(x.dtype) + lp["b_qkv"].astype(x.dtype)).reshape(
        x.shape[:2] + (-1, 3, Dh))
    return fused[..., 0, :], fused[..., 1, :], fused[..., 2, :]


def _block(config: BloomConfig, lp, x, slopes, kpos, causal_mask):
    q, k, v = _qkv(config, lp, x)
    attn = _biased_sdpa(q, k, v, slopes, kpos, causal_mask)
    x = x + attn.reshape(x.shape) @ lp["wo"].astype(x.dtype) + lp["bo"].astype(x.dtype)
    h = layer_norm(x, lp["ln2_w"], lp["ln2_b"], config.ln_eps)
    h = jax.nn.gelu(h @ lp["fc1"].astype(x.dtype) + lp["b_fc1"].astype(x.dtype),
                    approximate=True)
    return x + h @ lp["fc2"].astype(x.dtype) + lp["b_fc2"].astype(x.dtype)


def forward(config: BloomConfig, params, input_ids, attention_fn=None):
    del attention_fn  # ALiBi needs the biased local attention
    b, s = input_ids.shape
    slopes = jnp.asarray(alibi_slopes(config.num_heads))
    kpos = jnp.arange(s)
    causal_mask = kpos[None, :] <= kpos[:, None]
    x = params["embed"][input_ids]
    x = layer_norm(x, params["embed_ln_w"], params["embed_ln_b"], config.ln_eps)

    def body(h, lp):
        return _block(config, lp, h, slopes, kpos, causal_mask), None

    if config.remat:
        body = jax.checkpoint(body)
    x, _ = jax.lax.scan(body, x, params["layers"])
    x = layer_norm(x, params["final_ln_w"], params["final_ln_b"], config.ln_eps)
    return x @ params["embed"].T.astype(x.dtype)  # tied unembed


def make_loss_fn(config: BloomConfig, attention_fn=None) -> Callable:
    def loss_fn(params, batch, rng=None):
        logits = forward(config, params, batch["input_ids"])
        return cross_entropy_loss(logits, batch["labels"])
    return loss_fn


def tp_rules(path: str, shape) -> "int | None":
    """Fused qkv is per-HEAD interleaved, so column-sharding dim 2 splits on
    head boundaries exactly (heads/tp per shard); its bias rides along.
    wo/fc2 row-parallel with replicated biases."""
    if path.endswith(("bo", "b_fc2")):
        return None
    if path.endswith(("b_qkv", "b_fc1")):
        return 1
    if path.endswith(("w_qkv", "fc1")):
        return 2
    if path.endswith(("wo", "fc2")):
        return 1
    return None


# ------------------------------------------------------------------ inference
def init_cache(config: BloomConfig, batch: int, max_seq: Optional[int] = None,
               dtype=jnp.bfloat16):
    """Dense KV cache for v1 incremental decoding (llama.init_cache layout)."""
    S = max_seq or config.max_seq_len
    L, H = config.num_layers, config.num_heads
    Dh = config.hidden_size // H
    return {
        "k": jnp.zeros((L, batch, S, H, Dh), dtype),
        "v": jnp.zeros((L, batch, S, H, Dh), dtype),
        "len": jnp.zeros((), jnp.int32),
    }


def forward_with_cache(config: BloomConfig, params, input_ids, cache, attention_fn=None):
    """Incremental forward with ALiBi over absolute key positions."""
    del attention_fn
    b, s = input_ids.shape
    start = cache["len"]
    S_max = cache["k"].shape[2]
    slopes = jnp.asarray(alibi_slopes(config.num_heads))
    kpos = jnp.arange(S_max)
    qpos = start + jnp.arange(s)
    valid = kpos[None, :] < (start + s)
    causal_mask = jnp.logical_and(kpos[None, :] <= qpos[:, None], valid)
    x = params["embed"][input_ids].astype(cache["k"].dtype)
    x = layer_norm(x, params["embed_ln_w"], params["embed_ln_b"], config.ln_eps)

    def layer(x, inp):
        lp, kc, vc = inp
        q, k, v = _qkv(config, lp, x)
        kc = jax.lax.dynamic_update_slice_in_dim(kc, k, start, axis=1)
        vc = jax.lax.dynamic_update_slice_in_dim(vc, v, start, axis=1)
        attn = _biased_sdpa(q, kc, vc, slopes, kpos, causal_mask)
        x = x + attn.reshape(b, s, -1) @ lp["wo"].astype(x.dtype) + lp["bo"].astype(x.dtype)
        h = layer_norm(x, lp["ln2_w"], lp["ln2_b"], config.ln_eps)
        h = jax.nn.gelu(h @ lp["fc1"].astype(x.dtype) + lp["b_fc1"].astype(x.dtype),
                        approximate=True)
        x = x + h @ lp["fc2"].astype(x.dtype) + lp["b_fc2"].astype(x.dtype)
        return x, (kc, vc)

    x, (new_k, new_v) = jax.lax.scan(layer, x, (params["layers"], cache["k"], cache["v"]))
    x = layer_norm(x, params["final_ln_w"], params["final_ln_b"], config.ln_eps)
    logits = x @ params["embed"].T.astype(x.dtype)
    return logits, {"k": new_k, "v": new_v, "len": start + s}


def init_paged_cache(config: BloomConfig, num_blocks: int, block_size: int,
                     dtype=jnp.bfloat16):
    from .transformer import init_paged_kv_pool
    return init_paged_kv_pool(config.num_layers, config.num_heads,
                              config.hidden_size // config.num_heads,
                              num_blocks, block_size, dtype)


def forward_paged(config: BloomConfig, params, tokens, n_tokens, start_pos, block_tables,
                  kv_cache, *, block_size: int, tp_axis: Optional[str] = None,
                  gather_logits: bool = True, live_token_bound: Optional[int] = None,
                  last_rows: bool = False):
    """Ragged chunked BLOOM forward (``transformer.paged_forward`` states the
    contract): ALiBi rides the paged kernel's ``alibi_slopes`` operand
    (key-only form, absolute key index).  The reference's v2 zoo doesn't serve
    BLOOM at all; its v1 path injects ALiBi through the softmax op binding,
    ops/transformer/inference/op_binding/softmax.py.

    TP: fused per-head-interleaved qkv is column-sharded on head boundaries
    (tp_rules), so the local shard holds H/tp whole heads; each shard slices
    its own run of the slope schedule by mesh position.  The tied unembedding
    uses the replicated embedding, so logits come out full-vocab on every
    shard (gather_logits is a no-op)."""
    H = kv_cache["k"].shape[2]  # local heads
    slopes = jnp.asarray(alibi_slopes(config.num_heads))
    if tp_axis is not None and H < config.num_heads:
        first = jax.lax.axis_index(tp_axis).astype(jnp.int32) * H
        slopes = jax.lax.dynamic_slice(slopes, (first,), (H,))
    dtype = kv_cache["k"].dtype
    preduce = transformer.tp_psum(tp_axis)

    def embed(tokens, safe_pos):
        x = params["embed"][tokens].astype(dtype)
        return layer_norm(x, params["embed_ln_w"], params["embed_ln_b"], config.ln_eps)

    def finish(lp, x, kept, attn, live):
        x = x + preduce(attn.reshape(x.shape[:2] + (-1, )) @ lp["wo"].astype(x.dtype)) \
            + lp["bo"].astype(x.dtype)
        h = layer_norm(x, lp["ln2_w"], lp["ln2_b"], config.ln_eps)
        h = jax.nn.gelu(h @ lp["fc1"].astype(x.dtype) + lp["b_fc1"].astype(x.dtype),
                        approximate=True)
        return x + preduce(h @ lp["fc2"].astype(x.dtype)) + lp["b_fc2"].astype(x.dtype)

    def head(x):
        x = layer_norm(x, params["final_ln_w"], params["final_ln_b"], config.ln_eps)
        return x @ params["embed"].T.astype(x.dtype)

    return transformer.paged_forward(
        params["layers"], tokens, n_tokens, start_pos, block_tables, kv_cache,
        block_size=block_size, live_token_bound=live_token_bound, last_rows=last_rows,
        embed=embed, qkv=lambda lp, x, safe_pos: (*_qkv(config, lp, x), None), finish=finish,
        head=head, alibi_slopes=slopes)


# ----------------------------------------------------------------- HF import
def config_from_hf(hf_config) -> BloomConfig:
    return BloomConfig(vocab_size=hf_config.vocab_size, hidden_size=hf_config.hidden_size,
                       num_layers=hf_config.n_layer, num_heads=hf_config.n_head,
                       ln_eps=getattr(hf_config, "layer_norm_epsilon", 1e-5))


def from_hf_state_dict(config: BloomConfig, state_dict, dtype=jnp.float32):
    """Convert a BloomForCausalLM state dict.  The fused query_key_value keeps
    HF's per-head (q, k, v) interleaving — _qkv consumes it directly."""
    from .transformer import hf_stack, hf_tensor
    t = lambda name: hf_tensor(state_dict, name)
    L = config.num_layers
    pre = "transformer.h.{}"
    stack = lambda fmt, transpose=True: hf_stack(state_dict, fmt, L, dtype, transpose)

    return {
        "embed": jnp.asarray(t("transformer.word_embeddings.weight"), dtype),
        "embed_ln_w": jnp.asarray(t("transformer.word_embeddings_layernorm.weight"), dtype),
        "embed_ln_b": jnp.asarray(t("transformer.word_embeddings_layernorm.bias"), dtype),
        "layers": {
            "ln1_w": stack(pre + ".input_layernorm.weight", False),
            "ln1_b": stack(pre + ".input_layernorm.bias", False),
            "ln2_w": stack(pre + ".post_attention_layernorm.weight", False),
            "ln2_b": stack(pre + ".post_attention_layernorm.bias", False),
            "w_qkv": stack(pre + ".self_attention.query_key_value.weight"),
            "b_qkv": stack(pre + ".self_attention.query_key_value.bias", False),
            "wo": stack(pre + ".self_attention.dense.weight"),
            "bo": stack(pre + ".self_attention.dense.bias", False),
            "fc1": stack(pre + ".mlp.dense_h_to_4h.weight"),
            "b_fc1": stack(pre + ".mlp.dense_h_to_4h.bias", False),
            "fc2": stack(pre + ".mlp.dense_4h_to_h.weight"),
            "b_fc2": stack(pre + ".mlp.dense_4h_to_h.bias", False),
        },
        "final_ln_w": jnp.asarray(t("transformer.ln_f.weight"), dtype),
        "final_ln_b": jnp.asarray(t("transformer.ln_f.bias"), dtype),
    }
