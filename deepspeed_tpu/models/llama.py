"""Llama-family causal LM — the flagship training model.

Parity target: the reference trains Llama-2 via HF + ZeRO-3 (BASELINE.md config
ladder) and serves it via inference/v2/model_implementations/llama_v2.  This is
a TPU-first implementation: stacked-layer params swept by ``lax.scan`` (one
compiled block; per-layer ZeRO-3 gather), per-layer ``jax.checkpoint`` remat,
bf16 compute with fp32 reductions, rotary + GQA attention.
"""

import dataclasses
import functools
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import transformer
from .transformer import (apply_rotary, attention_block, cross_entropy_loss, init_linear,
                          kv_projection_shardable, rms_norm, rotary_tables, sdpa, swiglu_mlp)


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 32
    max_seq_len: int = 4096
    rope_theta: float = 10000.0
    rms_eps: float = 1e-5
    tie_embeddings: bool = False
    remat: bool = True
    # dots_with_no_batch_dims_saveable keeps per-layer matmul outputs (cheap to
    # store, expensive to recompute) and recomputes the rest — measured ~1.5x
    # faster than nothing_saveable at 438M/seq2048 on v5e (53% vs 35% MFU)
    remat_policy: Optional[str] = "dots_with_no_batch_dims_saveable"

    @staticmethod
    def llama2_7b():
        return LlamaConfig()

    @staticmethod
    def tiny(vocab=256, hidden=64, layers=2, heads=4, kv_heads=2, seq=64):
        return LlamaConfig(vocab_size=vocab, hidden_size=hidden, intermediate_size=hidden * 2,
                           num_layers=layers, num_heads=heads, num_kv_heads=kv_heads, max_seq_len=seq)


def init_params(config: LlamaConfig, key, dtype=jnp.float32):
    """Params pytree: per-layer leaves STACKED on dim 0 (num_layers) for scan."""
    k_emb, k_layers, k_out = jax.random.split(key, 3)
    L, D, F = config.num_layers, config.hidden_size, config.intermediate_size
    H, KV = config.num_heads, config.num_kv_heads
    head_dim = D // H
    lk = jax.random.split(k_layers, 7)

    def stack(key, in_dim, out_dim):
        keys = jax.random.split(key, L)
        return jnp.stack([init_linear(k, in_dim, out_dim, dtype=dtype) for k in keys])

    params = {
        "embed": jax.random.normal(k_emb, (config.vocab_size, D), dtype) * 0.02,
        "layers": {
            "attn": {
                "wq": stack(lk[0], D, H * head_dim),
                "wk": stack(lk[1], D, KV * head_dim),
                "wv": stack(lk[2], D, KV * head_dim),
                "wo": stack(lk[3], H * head_dim, D),
            },
            "mlp": {
                "w_gate": stack(lk[4], D, F),
                "w_up": stack(lk[5], D, F),
                "w_down": stack(lk[6], F, D),
            },
            "attn_norm": jnp.ones((L, D), dtype),
            "mlp_norm": jnp.ones((L, D), dtype),
        },
        "final_norm": jnp.ones((D, ), dtype),
    }
    if not config.tie_embeddings:
        params["lm_head"] = init_linear(k_out, D, config.vocab_size, dtype=dtype)
    return params


def _layer_fn(config: LlamaConfig, cos, sin, attention_fn=None):
    from ..runtime.activation_checkpointing import checkpoint_name

    def layer(x, layer_params, positions=None):
        attn_in = rms_norm(x, layer_params["attn_norm"], config.rms_eps)
        attn_out, _ = attention_block(layer_params["attn"], attn_in,
                                      n_heads=config.num_heads, n_kv_heads=config.num_kv_heads,
                                      cos=cos, sin=sin, causal=True, attention_fn=attention_fn,
                                      positions=positions)
        # residual-stream names: identity unless an offload/naming remat policy
        # targets them (runtime/activation_checkpointing.py RESIDUAL_NAMES)
        x = checkpoint_name(x + attn_out, "attn_resid")
        mlp_in = rms_norm(x, layer_params["mlp_norm"], config.rms_eps)
        x = checkpoint_name(x + swiglu_mlp(layer_params["mlp"], mlp_in), "mlp_resid")
        return x, None

    return layer


def forward(config: LlamaConfig, params, input_ids, attention_fn=None, rng=None):
    """input_ids [B, S] -> logits [B, S, V].  When an engine-scoped random-LTD
    state is configured (initialize() with data_efficiency.data_routing) and an
    ``rng`` is provided, middle layers process a random token subset
    (transformer.random_ltd_scan)."""
    from .transformer import configured_ltd, random_ltd_scan
    cos, sin = rotary_tables(config.hidden_size // config.num_heads, config.max_seq_len, config.rope_theta)
    x = params["embed"][input_ids]  # keep embed dtype (engine casts params)
    layer = _layer_fn(config, cos, sin, attention_fn)
    if config.remat:
        if config.remat_policy in ("offload_inputs", "cpu_checkpointing"):
            # real host-offloaded checkpointing (the policy-based offload
            # silently degrades to recompute — activation_checkpointing.py)
            from ..runtime.activation_checkpointing import offload_checkpoint
            layer = offload_checkpoint(layer)
        else:
            from ..runtime.activation_checkpointing import resolve_policy
            layer = jax.checkpoint(layer, policy=resolve_policy(config.remat_policy))
    ltd = configured_ltd()
    if ltd is not None and rng is not None:
        x = random_ltd_scan(layer, x, params["layers"], rng, int(ltd["keep"]))
    else:
        x, _ = jax.lax.scan(layer, x, params["layers"])
    x = rms_norm(x, params["final_norm"], config.rms_eps)
    head = params["embed"].T if config.tie_embeddings else params["lm_head"]
    return x @ head.astype(x.dtype)


def make_loss_fn(config: LlamaConfig, attention_fn=None) -> Callable:
    """loss_fn(params, batch, rng) for the engine; batch: {input_ids, labels}
    (labels = input_ids shifted; -100 = ignore)."""

    def loss_fn(params, batch, rng):
        logits = forward(config, params, batch["input_ids"], attention_fn=attention_fn,
                         rng=rng)
        return cross_entropy_loss(logits, batch["labels"])

    return loss_fn


def causal_lm_batch(input_ids: np.ndarray):
    """Build {input_ids, labels} with next-token labels from raw token rows."""
    labels = np.full_like(input_ids, -100)
    labels[:, :-1] = input_ids[:, 1:]
    return {"input_ids": input_ids, "labels": labels}


def tp_rules(path: str, shape) -> "int | None":
    """Tensor-parallel sharding rules — the Megatron-style column/row-parallel
    layout the reference receives via the external mpu (deepspeed/__init__.py:95)
    and that AutoTP autodetects for inference (module_inject/auto_tp.py:188).

    Column-parallel (shard output dim): wq/wk/wv, w_gate/w_up, lm_head.
    Row-parallel (shard input dim): wo, w_down.  Stacked layer leaves carry a
    leading L dim, so dims shift by one.
    """
    if path.endswith(("attn.wq", "mlp.w_gate", "mlp.w_up")):
        return 2  # [L, in, out] -> shard out
    if path.endswith(("attn.wk", "attn.wv")):
        # GQA/MQA kv projections replicate (see kv_projection_shardable)
        return 2 if kv_projection_shardable(shape) else None
    if path.endswith(("attn.wo", "mlp.w_down")):
        return 1  # [L, in, out] -> shard in
    if path == "lm_head":
        return 1  # [D, V] -> vocab-parallel logits
    return None


def make_tp_rules(config: LlamaConfig):
    """Config-aware v2 serving rules (inference/v2/tp.resolve_rules prefers
    these over the static ``tp_rules``): GQA kv projections shard
    head-aligned here — the v2 engine validates ``num_kv_heads % tp == 0``
    before sharding — while MQA (one kv head) REPLICATES, honoring
    validate_model's make_tp_rules escape hatch (same contract as falcon).
    The static rules keep GQA kv replicated instead: GSPMD auto layouts can
    be asked for sub-head kv shards (tp > kv_heads), which is both the wrong
    layout and an XLA miscompile (transformer.kv_projection_shardable)."""
    kv = config.num_kv_heads

    def rules(path: str, shape) -> "int | None":
        if path.endswith(("attn.wk", "attn.wv")):
            return 2 if kv > 1 else None
        return tp_rules(path, shape)

    return rules

def num_params(config: LlamaConfig) -> int:
    D, F, L, V = config.hidden_size, config.intermediate_size, config.num_layers, config.vocab_size
    H, KV = config.num_heads, config.num_kv_heads
    head_dim = D // H
    per_layer = (D * (H * head_dim) + 2 * D * (KV * head_dim) + (H * head_dim) * D
                 + D * F * 2 + F * D + 2 * D)
    total = V * D + L * per_layer + D
    if not config.tie_embeddings:
        total += D * V
    return total


def flops_per_token(config: LlamaConfig, seq_len: int) -> float:
    """Approximate training FLOPs/token (6N + attention terms) for MFU accounting."""
    n = num_params(config)
    attn = 12 * config.num_layers * config.hidden_size * seq_len  # qk+av fwd+bwd
    return 6.0 * n + attn


# ------------------------------------------------------------------ inference
def init_cache(config: LlamaConfig, batch: int, max_seq: Optional[int] = None, dtype=jnp.bfloat16):
    """Dense KV cache pytree for incremental decoding: stacked per-layer
    [L, B, S_max, KV, Dh] k/v buffers (the v1-engine analog of the reference's
    inference_context workspace, csrc/transformer/inference/includes)."""
    S = max_seq or config.max_seq_len
    L, KV = config.num_layers, config.num_kv_heads
    Dh = config.hidden_size // config.num_heads
    return {
        "k": jnp.zeros((L, batch, S, KV, Dh), dtype),
        "v": jnp.zeros((L, batch, S, KV, Dh), dtype),
        "len": jnp.zeros((), jnp.int32),
    }


def forward_with_cache(config: LlamaConfig, params, input_ids, cache, attention_fn=None):
    """Incremental forward: consumes/extends the KV cache.

    input_ids [B, S] (prompt at prefill, 1 token at decode); returns
    (logits [B, S, V], new_cache).
    """
    cos, sin = rotary_tables(config.hidden_size // config.num_heads, config.max_seq_len, config.rope_theta)
    b, s = input_ids.shape
    start = cache["len"]
    positions = start + jnp.arange(s)[None, :].repeat(b, axis=0)
    x = params["embed"][input_ids].astype(cache["k"].dtype)

    def layer(x, inp):
        lp, kc, vc = inp
        attn_in = rms_norm(x, lp["attn_norm"], config.rms_eps)
        attn_out, new_kv = attention_block(lp["attn"], attn_in,
                                           n_heads=config.num_heads, n_kv_heads=config.num_kv_heads,
                                           cos=cos, sin=sin, causal=True, attention_fn=attention_fn,
                                           positions=positions, kv_cache=(kc, vc, start))
        x = x + attn_out
        mlp_in = rms_norm(x, lp["mlp_norm"], config.rms_eps)
        x = x + swiglu_mlp(lp["mlp"], mlp_in)
        return x, (new_kv[0], new_kv[1])

    x, (new_k, new_v) = jax.lax.scan(layer, x, (params["layers"], cache["k"], cache["v"]))
    x = rms_norm(x, params["final_norm"], config.rms_eps)
    head = params["embed"].T if config.tie_embeddings else params["lm_head"]
    logits = x @ head.astype(x.dtype)
    return logits, {"k": new_k, "v": new_v, "len": start + s}


def from_hf_state_dict(config: LlamaConfig, state_dict, dtype=jnp.float32):
    """Convert a HuggingFace LlamaForCausalLM state dict to our params pytree
    (the checkpoint-loading analog of module_inject/load_checkpoint.py).

    torch Linear stores [out, in]; ours is [in, out] — transposed here.
    """
    from .transformer import hf_stack, hf_tensor
    t = lambda name: hf_tensor(state_dict, name)
    L = config.num_layers
    stack = lambda fmt, transpose=True: hf_stack(state_dict, fmt, L, dtype, transpose)

    params = {
        "embed": jnp.asarray(t("model.embed_tokens.weight"), dtype),
        "layers": {
            "attn": {
                "wq": stack("model.layers.{}.self_attn.q_proj.weight"),
                "wk": stack("model.layers.{}.self_attn.k_proj.weight"),
                "wv": stack("model.layers.{}.self_attn.v_proj.weight"),
                "wo": stack("model.layers.{}.self_attn.o_proj.weight"),
            },
            "mlp": {
                "w_gate": stack("model.layers.{}.mlp.gate_proj.weight"),
                "w_up": stack("model.layers.{}.mlp.up_proj.weight"),
                "w_down": stack("model.layers.{}.mlp.down_proj.weight"),
            },
            "attn_norm": stack("model.layers.{}.input_layernorm.weight", transpose=False),
            "mlp_norm": stack("model.layers.{}.post_attention_layernorm.weight", transpose=False),
        },
        "final_norm": jnp.asarray(t("model.norm.weight"), dtype),
    }
    if not config.tie_embeddings:
        key = "lm_head.weight" if "lm_head.weight" in state_dict else "model.embed_tokens.weight"
        params["lm_head"] = jnp.asarray(t(key).T, dtype)
    return params


def abstract_params(config: LlamaConfig, dtype=jnp.float32):
    """Meta-device skeleton (zero bytes): the OnDevice/zero.Init abstract half
    (ref utils/init_on_device.py:12)."""
    return jax.eval_shape(lambda: init_params(config, jax.random.PRNGKey(0), dtype=dtype))


def hf_streaming_loader(config: LlamaConfig, get_tensor: Callable[[str], Any]):
    """Build a ``get_leaf`` for zero.Init.materialize_from_loader that streams a
    HuggingFace Llama checkpoint **one layer-tensor at a time** — the analog of
    shard-by-shard checkpoint loading into ZeRO-3 (module_inject/load_checkpoint.py).

    ``get_tensor(hf_name) -> array-like`` (e.g. a safetensors lazy handle or a
    torch state_dict lookup).  Stacked per-layer leaves are returned as slice
    callbacks, so a device owning layers [a:b) of wq only ever pulls those
    layers' tensors; peak host memory is O(one layer tensor), not O(leaf).
    """

    def t(name):
        w = get_tensor(name)
        w = w.float().numpy() if hasattr(w, "float") else np.asarray(w, dtype=np.float32)
        return w

    fmt = {
        "layers.attn.wq": ("model.layers.{}.self_attn.q_proj.weight", True),
        "layers.attn.wk": ("model.layers.{}.self_attn.k_proj.weight", True),
        "layers.attn.wv": ("model.layers.{}.self_attn.v_proj.weight", True),
        "layers.attn.wo": ("model.layers.{}.self_attn.o_proj.weight", True),
        "layers.mlp.w_gate": ("model.layers.{}.mlp.gate_proj.weight", True),
        "layers.mlp.w_up": ("model.layers.{}.mlp.up_proj.weight", True),
        "layers.mlp.w_down": ("model.layers.{}.mlp.down_proj.weight", True),
        "layers.attn_norm": ("model.layers.{}.input_layernorm.weight", False),
        "layers.mlp_norm": ("model.layers.{}.post_attention_layernorm.weight", False),
    }

    def get_leaf(path, leaf):
        if path == "embed":
            return t("model.embed_tokens.weight")
        if path == "final_norm":
            return t("model.norm.weight")
        if path == "lm_head":
            name = "lm_head.weight" if _has(get_tensor, "lm_head.weight") else "model.embed_tokens.weight"
            return t(name).T
        name_fmt, transpose = fmt[path]

        def slice_cb(idx):
            layers = range(*idx[0].indices(config.num_layers))
            parts = []
            for i in layers:
                w = t(name_fmt.format(i))
                if transpose:
                    w = w.T
                parts.append(w[idx[1:]] if len(idx) > 1 else w)
            return np.stack(parts)

        return slice_cb

    return get_leaf


def _has(get_tensor, name) -> bool:
    try:
        return get_tensor(name) is not None
    except Exception:
        return False


def config_from_hf(hf_config) -> LlamaConfig:
    """Build a LlamaConfig from a transformers LlamaConfig/MistralConfig."""
    return LlamaConfig(
        vocab_size=hf_config.vocab_size,
        hidden_size=hf_config.hidden_size,
        intermediate_size=hf_config.intermediate_size,
        num_layers=hf_config.num_hidden_layers,
        num_heads=hf_config.num_attention_heads,
        num_kv_heads=getattr(hf_config, "num_key_value_heads", hf_config.num_attention_heads),
        max_seq_len=getattr(hf_config, "max_position_embeddings", 4096),
        rope_theta=getattr(hf_config, "rope_theta", 10000.0),
        rms_eps=getattr(hf_config, "rms_norm_eps", 1e-5),
        tie_embeddings=getattr(hf_config, "tie_word_embeddings", False),
    )


# --------------------------------------------------------- paged (ragged) serve
def init_paged_cache(config: LlamaConfig, num_blocks: int, block_size: int, dtype=jnp.bfloat16):
    """The paged KV pool of ``transformer.init_paged_kv_pool`` at this config's
    layers, KV heads and head width."""
    return transformer.init_paged_kv_pool(config.num_layers, config.num_kv_heads,
                                          config.hidden_size // config.num_heads,
                                          num_blocks, block_size, dtype)


def paged_callables(config, params, dtype, tp_axis: Optional[str], gather_logits: bool, *,
                    on_heads: Optional[Callable] = None, ffn: Optional[Callable] = None):
    """What is Llama's own of the ragged forward: ``embed``, ``qkv``, ``finish``
    and ``head`` for ``transformer.paged_forward``, as its keywords.  A family
    that is this decoder but for one sub-layer (mistral, qwen, mixtral, olmoe)
    takes them and hands in what differs; both hooks are facts of an
    architecture, never user options.  ``on_heads(lp, q, k, v) -> (q, k, v)``
    acts on the projected local heads ``[b, s, heads, Dh]`` before they are
    rotated (Qwen's biases, OLMoE's QK-norm).  ``ffn(lp, h, live)`` stands in
    for the dense SwiGLU (the MoE families): ``h`` is the normed ``[b, s, D]``,
    the result the row-parallel partial that ``finish`` psums."""
    Dh = config.hidden_size // config.num_heads  # true head dim: TP-invariant
    cos, sin = rotary_tables(Dh, config.max_seq_len, config.rope_theta)
    preduce = transformer.tp_psum(tp_axis)

    def embed(tokens, safe_pos):
        return params["embed"][tokens].astype(dtype)

    def qkv(lp, x, safe_pos):
        h = rms_norm(x, lp["attn_norm"], config.rms_eps)
        q, k, v = ((h @ lp["attn"][w].astype(x.dtype)).reshape(x.shape[:2] + (-1, Dh))
                   for w in ("wq", "wk", "wv"))
        if on_heads is not None:
            q, k, v = on_heads(lp, q, k, v)
        return apply_rotary(q, cos, sin, safe_pos), apply_rotary(k, cos, sin, safe_pos), v, None

    def finish(lp, x, kept, attn, live):
        x = x + preduce(attn.reshape(x.shape[:2] + (-1, )) @ lp["attn"]["wo"].astype(x.dtype))
        h = rms_norm(x, lp["mlp_norm"], config.rms_eps)
        return x + preduce(swiglu_mlp(lp["mlp"], h) if ffn is None else ffn(lp, h, live))

    def head(x):
        x = rms_norm(x, params["final_norm"], config.rms_eps)
        w = params["embed"].T if config.tie_embeddings else params["lm_head"]
        logits = x @ w.astype(x.dtype)
        if tp_axis is not None and gather_logits and not config.tie_embeddings:
            # lm_head is vocab-parallel (tp_rules: lm_head dim 1): gather shards.
            # Greedy decode skips this (gather_logits=False) and argmaxes the
            # vocab-local shard instead: O(1) scalars over ICI per token, not O(V).
            logits = jax.lax.all_gather(logits, tp_axis, axis=-1, tiled=True)
        return logits

    return {"embed": embed, "qkv": qkv, "finish": finish, "head": head}


def forward_paged(config: LlamaConfig, params, tokens, n_tokens, start_pos, block_tables,
                  kv_cache, *, block_size: int, tp_axis: Optional[str] = None,
                  gather_logits: bool = True, live_token_bound: Optional[int] = None,
                  last_rows: bool = False):
    """The v2 ragged forward (``transformer.paged_forward`` states the contract;
    reference inference/v2/model_implementations/llama_v2): rotary GQA, dense
    SwiGLU, vocab-parallel untied head."""
    return transformer.paged_forward(
        params["layers"], tokens, n_tokens, start_pos, block_tables, kv_cache,
        block_size=block_size, live_token_bound=live_token_bound, last_rows=last_rows,
        **paged_callables(config, params, kv_cache["k"].dtype, tp_axis, gather_logits))
