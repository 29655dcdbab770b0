"""Mistral causal LM — Llama architecture + sliding-window attention.

Parity: reference inference/v2/model_implementations/mistral (the reference
serves Mistral with windowed blocked flash).  The backbone is byte-identical to
Llama, so everything delegates to models/llama with ``sliding_window`` threaded
through: training runs the default (flash) attention while the sequence fits
the window and masks the window inside sdpa past it; v2 serving passes it to
the Pallas paged kernel (ops/attention/paged.py window arg).
"""

import dataclasses
import functools
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import llama, transformer
from .llama import LlamaConfig
from .transformer import cross_entropy_loss, default_attention, sdpa


@dataclasses.dataclass(frozen=True)
class MistralConfig(LlamaConfig):
    sliding_window: Optional[int] = 4096

    @staticmethod
    def mistral_7b():
        return MistralConfig(vocab_size=32000, hidden_size=4096, intermediate_size=14336,
                             num_layers=32, num_heads=32, num_kv_heads=8,
                             max_seq_len=32768, rope_theta=10000.0, sliding_window=4096)

    @staticmethod
    def tiny(vocab=256, hidden=64, layers=2, heads=4, kv_heads=2, seq=64, window=16):
        return MistralConfig(vocab_size=vocab, hidden_size=hidden, intermediate_size=hidden * 2,
                             num_layers=layers, num_heads=heads, num_kv_heads=kv_heads,
                             max_seq_len=seq, sliding_window=window)


def dense_windowed_attention(window: int):
    """Sliding-window causal attention as one dense mask handed to ``sdpa`` —
    right for any length, and the plain reference the kernels are held to."""

    def attn(q, k, v, causal=True, mask=None, softmax_scale=None):
        sq, sk = q.shape[1], k.shape[1]
        qp = jnp.arange(sq)[:, None] + (sk - sq)
        kp = jnp.arange(sk)[None, :]
        wmask = (kp <= qp) & (kp > qp - window)
        if mask is not None:
            wmask = jnp.logical_and(mask, wmask[None, None])
        else:
            wmask = wmask[None, None]
        return sdpa(q, k, v, causal=False, mask=wmask, softmax_scale=softmax_scale)

    return attn


def windowed_attention(window: Optional[int]):
    """attention_fn for the training path.  While the keys fit the window
    (``sk <= window``) the window mask IS the causal mask, so the call goes to
    the backend's default attention with ``causal=True`` — the Pallas flash
    kernel on TPU — exactly as Llama does.  Only past the window is the dense
    mask built."""
    if window is None:
        return None
    dense = dense_windowed_attention(window)

    def attn(q, k, v, causal=True, mask=None, softmax_scale=None):
        if k.shape[1] <= window:
            return default_attention()(q, k, v, causal=True, mask=mask,
                                       softmax_scale=softmax_scale)
        return dense(q, k, v, mask=mask, softmax_scale=softmax_scale)

    return attn


init_params = llama.init_params
num_params = llama.num_params
flops_per_token = llama.flops_per_token
tp_rules = llama.tp_rules
make_tp_rules = llama.make_tp_rules
abstract_params = llama.abstract_params
from_hf_state_dict = llama.from_hf_state_dict
hf_streaming_loader = llama.hf_streaming_loader
init_cache = llama.init_cache
init_paged_cache = llama.init_paged_cache
causal_lm_batch = llama.causal_lm_batch


def forward(config: MistralConfig, params, input_ids, attention_fn=None):
    fn = attention_fn or windowed_attention(config.sliding_window)
    return llama.forward(config, params, input_ids, attention_fn=fn)


def make_loss_fn(config: MistralConfig, attention_fn=None) -> Callable:
    fn = attention_fn or windowed_attention(config.sliding_window)
    return llama.make_loss_fn(config, attention_fn=fn)


def forward_paged(config: MistralConfig, params, tokens, n_tokens, start_pos, block_tables,
                  kv_cache, *, block_size: int, tp_axis: Optional[str] = None,
                  gather_logits: bool = True, live_token_bound: Optional[int] = None,
                  last_rows: bool = False):
    """v2 ragged forward: Llama's callables, and the paged kernel applies the
    sliding window directly (reference mistral serving uses windowed blocked
    flash)."""
    return transformer.paged_forward(
        params["layers"], tokens, n_tokens, start_pos, block_tables, kv_cache,
        block_size=block_size, live_token_bound=live_token_bound, last_rows=last_rows,
        window=config.sliding_window,
        **llama.paged_callables(config, params, kv_cache["k"].dtype, tp_axis, gather_logits))


def config_from_hf(hf_config) -> MistralConfig:
    base = llama.config_from_hf(hf_config)
    return MistralConfig(**dataclasses.asdict(base),
                         sliding_window=getattr(hf_config, "sliding_window", None))
