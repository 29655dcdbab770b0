"""GPT-J causal LM (EleutherAI/gpt-j-6B family).

Parity: reference module_inject/containers/gptj.py + replace_policy GPTJ
(module_inject/replace_policy.py) — the reference serves GPT-J through kernel
injection; here it's a first-class family.  Architecture: PARALLEL
attention+MLP off one shared LayerNorm (like Falcon), partial rotary with
GPT-J's INTERLEAVED convention (rotate_every_two — not the half-split used by
Llama/NeoX), no attention biases, biased fc_in/fc_out MLP with gelu_new,
untied lm_head WITH bias.
"""

import dataclasses
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import transformer
from .transformer import (causal_lm_batch, count_params, cross_entropy_loss,
                          init_paged_kv_pool, layer_norm, sdpa)


@dataclasses.dataclass(frozen=True)
class GPTJConfig:
    vocab_size: int = 50400
    hidden_size: int = 4096
    ffn_dim: int = 16384
    num_layers: int = 28
    num_heads: int = 16
    rotary_dim: int = 64
    max_seq_len: int = 2048
    ln_eps: float = 1e-5
    remat: bool = True

    @staticmethod
    def gptj_6b():
        return GPTJConfig()

    @staticmethod
    def tiny(vocab=256, hidden=64, layers=2, heads=4, seq=64, rotary_dim=8):
        return GPTJConfig(vocab_size=vocab, hidden_size=hidden, ffn_dim=hidden * 4,
                          num_layers=layers, num_heads=heads, rotary_dim=rotary_dim,
                          max_seq_len=seq)


def interleaved_rotary_tables(rotary_dim: int, max_seq: int, base: float = 10000.0):
    """GPT-J's sincos tables with duplicate-interleave: each frequency's value
    repeats at dims (2i, 2i+1) — pairs rotate together (HF modeling_gptj
    ``create_sinusoidal_positions`` + ``duplicate_interleave``)."""
    inv_freq = 1.0 / (base ** (np.arange(0, rotary_dim, 2) / rotary_dim))
    freqs = np.einsum("i,j->ij", np.arange(max_seq), inv_freq)
    return (jnp.asarray(np.repeat(np.cos(freqs), 2, axis=1), jnp.float32),
            jnp.asarray(np.repeat(np.sin(freqs), 2, axis=1), jnp.float32))


def _rotate_every_two(x):
    x1, x2 = x[..., ::2], x[..., 1::2]
    return jnp.stack([-x2, x1], axis=-1).reshape(x.shape)


def apply_rotary_interleaved(x, cos, sin, positions=None):
    """x [B, S, H, rotary_dim]; GPT-J pairwise rotation."""
    if positions is None:
        s = x.shape[1]
        c, sn = cos[:s][None, :, None, :], sin[:s][None, :, None, :]
    else:
        c, sn = cos[positions][:, :, None, :], sin[positions][:, :, None, :]
    c, sn = c.astype(x.dtype), sn.astype(x.dtype)
    return x * c + _rotate_every_two(x) * sn


def init_params(config: GPTJConfig, key, dtype=jnp.float32):
    D, F, L, V = config.hidden_size, config.ffn_dim, config.num_layers, config.vocab_size
    ks = jax.random.split(key, 8)
    s = D ** -0.5

    def stack(k, shape):
        return jax.random.normal(k, (L, *shape), dtype) * s

    return {
        "embed": jax.random.normal(ks[0], (V, D), dtype) * 0.02,
        "layers": {
            "ln_w": jnp.ones((L, D), dtype), "ln_b": jnp.zeros((L, D), dtype),
            "wq": stack(ks[1], (D, D)), "wk": stack(ks[2], (D, D)),
            "wv": stack(ks[3], (D, D)), "wo": stack(ks[4], (D, D)),
            "fc_in": stack(ks[5], (D, F)), "b_fc_in": jnp.zeros((L, F), dtype),
            "fc_out": stack(ks[6], (F, D)), "b_fc_out": jnp.zeros((L, D), dtype),
        },
        "final_ln_w": jnp.ones((D,), dtype), "final_ln_b": jnp.zeros((D,), dtype),
        "lm_head": jax.random.normal(ks[7], (D, V), dtype) * s,
        "lm_head_b": jnp.zeros((V,), dtype),
    }


def num_params(config: GPTJConfig) -> int:
    return count_params(lambda: init_params(config, jax.random.PRNGKey(0)))


def _qkv(config: GPTJConfig, lp, x, cos, sin, positions=None):
    """The layer's one LayerNorm, the projections as heads ``[b, s, heads,
    Dh]`` (the local ones under TP) with the leading ``rotary_dim`` of q and k
    rotated pairwise, and the normed ``h`` the parallel MLP reads too:
    ``(q, k, v, h)``."""
    rd, Dh = config.rotary_dim, config.hidden_size // config.num_heads  # TP-invariant
    h = layer_norm(x, lp["ln_w"], lp["ln_b"], config.ln_eps)
    q, k, v = ((h @ lp[w].astype(x.dtype)).reshape(x.shape[:2] + (-1, Dh))
               for w in ("wq", "wk", "wv"))
    q, k = (jnp.concatenate([apply_rotary_interleaved(y[..., :rd], cos, sin, positions),
                             y[..., rd:]], axis=-1) for y in (q, k))
    return q, k, v, h


def _block(config: GPTJConfig, lp, x, cos, sin, attention_fn=None):
    q, k, v, h = _qkv(config, lp, x, cos, sin)
    attn = (attention_fn or sdpa)(q, k, v, causal=True)
    attn_out = attn.reshape(x.shape) @ lp["wo"].astype(x.dtype)
    mlp = jax.nn.gelu(h @ lp["fc_in"].astype(x.dtype) + lp["b_fc_in"].astype(x.dtype),
                      approximate=True)
    mlp_out = mlp @ lp["fc_out"].astype(x.dtype) + lp["b_fc_out"].astype(x.dtype)
    return x + attn_out + mlp_out  # parallel residual


def forward(config: GPTJConfig, params, input_ids, attention_fn=None):
    cos, sin = interleaved_rotary_tables(config.rotary_dim, config.max_seq_len)
    x = params["embed"][input_ids]

    def body(h, lp):
        return _block(config, lp, h, cos, sin, attention_fn), None

    if config.remat:
        body = jax.checkpoint(body)
    x, _ = jax.lax.scan(body, x, params["layers"])
    x = layer_norm(x, params["final_ln_w"], params["final_ln_b"], config.ln_eps)
    return x @ params["lm_head"].astype(x.dtype) + params["lm_head_b"].astype(x.dtype)


def make_loss_fn(config: GPTJConfig, attention_fn=None) -> Callable:
    def loss_fn(params, batch, rng=None):
        logits = forward(config, params, batch["input_ids"], attention_fn=attention_fn)
        return cross_entropy_loss(logits, batch["labels"])
    return loss_fn


def tp_rules(path: str, shape) -> "int | None":
    """Column: qkv + fc_in (+ its bias); row: wo/fc_out (bias replicated,
    added once post-psum); vocab-parallel lm_head + bias."""
    if path.endswith("b_fc_out"):
        return None
    if path.endswith("b_fc_in"):
        return 1
    if path.endswith(("wq", "wk", "wv", "fc_in")):
        return 2
    if path.endswith(("wo", "fc_out")):
        return 1
    if path == "lm_head":
        return 1
    if path == "lm_head_b":
        return 0
    return None


# --------------------------------------------------------- paged (ragged) serve
def init_paged_cache(config: GPTJConfig, num_blocks: int, block_size: int, dtype=jnp.bfloat16):
    return init_paged_kv_pool(config.num_layers, config.num_heads,
                              config.hidden_size // config.num_heads,
                              num_blocks, block_size, dtype)


def forward_paged(config: GPTJConfig, params, tokens, n_tokens, start_pos, block_tables,
                  kv_cache, *, block_size: int, tp_axis: Optional[str] = None,
                  gather_logits: bool = True, live_token_bound: Optional[int] = None,
                  last_rows: bool = False):
    """Ragged chunked GPT-J forward (``transformer.paged_forward`` states the
    contract): interleaved partial rotary feeds the paged kernel; the parallel
    residual reduces attn+mlp in one psum under TP; vocab-parallel biased head
    like phi."""
    cos, sin = interleaved_rotary_tables(config.rotary_dim, config.max_seq_len)
    dtype = kv_cache["k"].dtype
    preduce = transformer.tp_psum(tp_axis)

    def finish(lp, x, h, attn, live):
        attn_out = attn.reshape(x.shape[:2] + (-1, )) @ lp["wo"].astype(x.dtype)
        mlp = jax.nn.gelu(h @ lp["fc_in"].astype(x.dtype) + lp["b_fc_in"].astype(x.dtype),
                          approximate=True)
        return x + preduce(attn_out + mlp @ lp["fc_out"].astype(x.dtype)) \
            + lp["b_fc_out"].astype(x.dtype)

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
def config_from_hf(hf_config) -> GPTJConfig:
    return GPTJConfig(vocab_size=hf_config.vocab_size, hidden_size=hf_config.n_embd,
                      ffn_dim=hf_config.n_inner or 4 * hf_config.n_embd,
                      num_layers=hf_config.n_layer, num_heads=hf_config.n_head,
                      rotary_dim=hf_config.rotary_dim or hf_config.n_embd // hf_config.n_head,
                      max_seq_len=hf_config.n_positions,
                      ln_eps=getattr(hf_config, "layer_norm_epsilon", 1e-5))


def from_hf_state_dict(config: GPTJConfig, state_dict, dtype=jnp.float32):
    """Convert a GPTJForCausalLM state dict (no attention biases; biased
    fc_in/fc_out and lm_head; torch Linear [out, in] -> ours [in, out])."""
    from .transformer import hf_stack, hf_tensor
    t = lambda name: hf_tensor(state_dict, name)
    L = config.num_layers
    pre = "transformer.h.{}"
    stack = lambda fmt, transpose=True: hf_stack(state_dict, fmt, L, dtype, transpose)

    return {
        "embed": jnp.asarray(t("transformer.wte.weight"), dtype),
        "layers": {
            "ln_w": stack(pre + ".ln_1.weight", False),
            "ln_b": stack(pre + ".ln_1.bias", False),
            "wq": stack(pre + ".attn.q_proj.weight"),
            "wk": stack(pre + ".attn.k_proj.weight"),
            "wv": stack(pre + ".attn.v_proj.weight"),
            "wo": stack(pre + ".attn.out_proj.weight"),
            "fc_in": stack(pre + ".mlp.fc_in.weight"),
            "b_fc_in": stack(pre + ".mlp.fc_in.bias", False),
            "fc_out": stack(pre + ".mlp.fc_out.weight"),
            "b_fc_out": stack(pre + ".mlp.fc_out.bias", False),
        },
        "final_ln_w": jnp.asarray(t("transformer.ln_f.weight"), dtype),
        "final_ln_b": jnp.asarray(t("transformer.ln_f.bias"), dtype),
        "lm_head": jnp.asarray(t("lm_head.weight").T, dtype),
        "lm_head_b": jnp.asarray(t("lm_head.bias"), dtype),
    }
