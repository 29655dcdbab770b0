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

from .transformer import (apply_rotary, attention_block, cross_entropy_loss,
                          flat_chunk_indices, flat_slots, init_linear,
                          kv_projection_shardable, paged_chunk_indices, rms_norm,
                          rotary_tables, sdpa, swiglu_mlp)


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
    """Paged KV pool (reference inference/v2/ragged blocked KV layout):
    [L, num_blocks, KV, block_size, Dh] — heads-major so the Pallas paged
    kernel's trailing (block_size, Dh) tile satisfies TPU tiling.  The last
    block of each layer is reserved as a trash target for padded-token writes.

    One stacked array per K and V, layer axis first: ``forward_paged`` writes
    and reads it where it lies (block b of layer l is row ``l * num_blocks +
    b`` of the free ``[L * num_blocks, KV, block_size, Dh]`` view), and the
    engine's copy-on-write, its TP spec (heads on axis 2) and the benchmark's
    pool-shape reader rest on this layout."""
    L, KV = config.num_layers, config.num_kv_heads
    Dh = config.hidden_size // config.num_heads
    return {
        "k": jnp.zeros((L, num_blocks, KV, block_size, Dh), dtype),
        "v": jnp.zeros((L, num_blocks, KV, block_size, Dh), dtype),
    }


def forward_paged(config: LlamaConfig, params, tokens, n_tokens, start_pos, block_tables,
                  kv_cache, *, block_size: int, window: Optional[int] = None,
                  tp_axis: Optional[str] = None, gather_logits: bool = True,
                  live_token_bound: Optional[int] = None,
                  ffn: Optional[Callable] = None, qk_norm: Optional[Callable] = None):
    """Ragged chunked forward over the paged KV pool (FastGen model-forward
    analog, inference/v2/model_implementations/llama_v2 + blocked flash).

    tokens [N, T] (right-padded chunks), n_tokens [N] valid counts,
    start_pos [N] absolute start of this chunk, block_tables [N, MAXB]
    (padded entries point at the trash block).  ``window`` enables Mistral-style
    sliding-window attention.  Returns (logits [N, T, V], new kv_cache).

    ``kv_cache`` is ``{"k", "v"}`` of ``[L, NB, KV, bs, Dh]`` in and out.  The
    layer scan CARRIES both pools whole beside the activations (its ``xs`` is
    the layers' parameters and the layer's index); each layer scatters this
    step's rows (live tokens x KV x Dh; a dead slot's into the layer's trash
    block) into the carried stack in place and hands the paged kernel the
    stack as one pool of ``L * NB`` blocks, with the block table offset by the
    layer's first row ``l * NB``: the kernel knows nothing of layers.  No
    layer is ever cut out of the pool or stacked back, so a jitted caller
    that donates ``kv_cache`` (or carries it through a loop of its own, as the
    fused burst does) runs with the one pool it was given and no copy of it.

    ``live_token_bound``: the caller's promise that ``sum(n_tokens)`` never
    passes it (the serving engine hands its scheduler's ``token_budget``).
    Where the bucket holds more slots than that (``flat_slots``, from the
    static shapes: a mixed SplitFuse step of one 225-token chunk beside 31
    decode rows is ``[32, 256]`` = 8,192 slots for 256 live tokens), the chunk
    is compacted onto one flat axis of S slots and everything that is per
    token (embedding, norms, the Q/K/V/O projections, rotary, the KV write,
    SwiGLU, the final norm and the output head) runs over ``[S, ...]``.  Only
    attention sees the padded layout: ``q`` is scattered into a zero
    ``[N, T, H, Dh]`` for the paged kernel and its output gathered back.  The
    logits come back as ``[N, T, V]`` all the same, zero wherever no live
    token sits.  Without the bound, or where the bucket fits it (decode
    ``[N, 1]``, a burst body, a spec verify), every slot of the bucket is
    computed and the trace is the padded one.

    Attention runs in the Pallas paged kernel (ops/attention/paged.py) on TPU —
    only live blocks are read via scalar-prefetched table indices; off-TPU the
    identical-math dense-gather fallback runs.

    ``tp_axis``: when called inside shard_map with params column/row-sharded per
    tp_rules and the KV pool sharded on its head dim, names the mesh axis to
    psum row-parallel partial outputs over (the TPU analog of the reference's
    v2 sharding helpers, inference/v2/model_implementations/sharding/qkv.py +
    attn.py + mlp.py + unembed.py).  Head counts are derived from the (local)
    param shapes, so the same code serves single-chip and TP-sharded.

    Two seams let a family that is this decoder but for one sub-layer run this
    body and not a copy of it (models/mixtral.py, models/olmoe.py); both are
    facts of an architecture, handed in by the family's module, never user
    options.  ``ffn(layer_params, h, live)`` stands in for the dense SwiGLU:
    ``h`` is the normed ``[b, s, D]`` the per-token layers run over (padded or
    compacted), ``live`` the ``[b, s]`` mask of slots that hold a token, the
    result the row-parallel partial this body psums.  ``qk_norm(layer_params,
    q, k)`` acts on the projected queries ``[b, s, H, Dh]`` and keys
    ``[b, s, KV, Dh]`` (the local heads) before they are rotated.
    """
    from ..ops.attention.paged import paged_attention

    n, t = tokens.shape
    cos, sin = rotary_tables(config.hidden_size // config.num_heads, config.max_seq_len, config.rope_theta)
    num_blocks = kv_cache["k"].shape[1]
    slots = flat_slots(n, t, live_token_bound)
    if slots is None:
        # the padded bucket as it is: the per-token layers see [N, T]
        b, tchunk = n, t
        safe_pos, live, lengths, blk, off = paged_chunk_indices(
            tokens, n_tokens, start_pos, block_tables, num_blocks, block_size)
        to_padded = from_padded = lambda a: a
    else:
        # the live tokens on one flat axis: the per-token layers see [1, S]
        b, tchunk = 1, slots
        row, col, live, safe_pos, blk, off = (a[None] for a in flat_chunk_indices(
            n_tokens, start_pos, block_tables, num_blocks, block_size, slots))
        lengths = start_pos + n_tokens
        tokens = tokens[row, col]
        drop_row = jnp.where(live, row, n)[0]  # out of bounds: a dead slot lands nowhere

        def to_padded(a):  # [1, S, ...] -> [N, T, ...], zero wherever no live token sits
            return jnp.zeros((n, t) + a.shape[2:], a.dtype).at[drop_row, col[0]].set(
                a[0], mode="drop")

        def from_padded(a):  # [N, T, ...] -> [1, S, ...]; a dead slot's value is never used
            return a[row, col]

    x = params["embed"][tokens].astype(kv_cache["k"].dtype)
    Dh = config.hidden_size // config.num_heads  # true head dim: TP-invariant
    H = params["layers"]["attn"]["wq"].shape[-1] // Dh   # local (per-shard) heads
    KV = params["layers"]["attn"]["wk"].shape[-1] // Dh
    scale = 1.0 / np.sqrt(Dh)
    head_idx = jnp.arange(KV)[None, None, :]
    preduce = (lambda y: jax.lax.psum(y, tp_axis)) if tp_axis else (lambda y: y)

    def layer(carry, inp):
        x, kpool, vpool = carry  # the pools whole: [L*NB, KV, bs, Dh]
        lp, l = inp
        attn_in = rms_norm(x, lp["attn_norm"], config.rms_eps)
        q = (attn_in @ lp["attn"]["wq"].astype(x.dtype)).reshape(b, tchunk, H, Dh)
        k = (attn_in @ lp["attn"]["wk"].astype(x.dtype)).reshape(b, tchunk, KV, Dh)
        v = (attn_in @ lp["attn"]["wv"].astype(x.dtype)).reshape(b, tchunk, KV, Dh)
        if qk_norm is not None:
            q, k = qk_norm(lp, q, k)
        q = apply_rotary(q, cos, sin, safe_pos)
        k = apply_rotary(k, cos, sin, safe_pos)
        # this step's rows, in place: pool[l*NB + blk, h, off] = k[n, t, h].  One
        # index per (token, head): a token's heads written as one window
        # (.at[row, :, off]) makes the compiler relayout the pool, two copies a pass
        first = l * num_blocks  # the layer's first row of the flat stack
        row = (first + blk)[:, :, None]
        kpool = kpool.at[row, head_idx, off[:, :, None]].set(k)
        vpool = vpool.at[row, head_idx, off[:, :, None]].set(v)
        # the kernel takes the flat stack as it would one layer's pool (a Pallas
        # operand is materialised, so kpool[l] would be a copy): the table is offset
        out = from_padded(paged_attention(
            to_padded(q), kpool, vpool, block_tables + first, lengths, start_pos, n_tokens,
            block_size=block_size, softmax_scale=scale, window=window))
        x = x + preduce(out.reshape(b, tchunk, H * Dh) @ lp["attn"]["wo"].astype(x.dtype))
        mlp_in = rms_norm(x, lp["mlp_norm"], config.rms_eps)
        x = x + preduce(swiglu_mlp(lp["mlp"], mlp_in) if ffn is None else ffn(lp, mlp_in, live))
        return (x, kpool, vpool), None

    # The pool is carried, never sliced (xs) and restacked (ys): a scan's ys is
    # a new [L, ...] array that cannot alias a donated argument still being
    # read, which cost a slice, an update and a copy of the whole pool a pass.
    pool_shape = kv_cache["k"].shape
    flat = (-1, ) + pool_shape[2:]
    (x, new_k, new_v), _ = jax.lax.scan(
        layer, (x, kv_cache["k"].reshape(flat), kv_cache["v"].reshape(flat)),
        (params["layers"], jnp.arange(pool_shape[0], dtype=jnp.int32)))
    new_k, new_v = new_k.reshape(pool_shape), new_v.reshape(pool_shape)
    x = rms_norm(x, params["final_norm"], config.rms_eps)
    head = params["embed"].T if config.tie_embeddings else params["lm_head"]
    logits = x @ head.astype(x.dtype)
    if tp_axis is not None and gather_logits and not config.tie_embeddings:
        # lm_head is vocab-parallel (tp_rules: lm_head dim 1): gather shards.
        # Greedy decode skips this (gather_logits=False) and argmaxes the
        # vocab-local shard instead — O(1) scalars over ICI per token, not O(V).
        logits = jax.lax.all_gather(logits, tp_axis, axis=-1, tiled=True)
    return to_padded(logits), {"k": new_k, "v": new_v}
