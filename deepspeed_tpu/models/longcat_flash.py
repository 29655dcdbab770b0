"""LongCat-Flash causal LM (``model_type: longcat_flash``; the language model of
``meituan-longcat/LongCat-Flash-Omni``, HF ``modeling_longcat_flash.py``) — serving only.

DeepSeek-V2's latent attention over a latent pool and one chip's share of the
experts (``deepseek_v2.mla_qkv`` / ``mla_out`` and ``moe/serving.py``, imported,
not copied) in a layer no other family here has.  One of the ``num_layers``
layers is TWO sublayers and ONE expert layer (``N`` = RMSNorm, ``h`` the stream):

    a0 = h  + MLA_0(N(h))                    u = N(a0)
    s  = MoE(u)                              # the shortcut: computed here, added at the layer's end
    b0 = a0 + FFN_0(u)                       # dense SwiGLU
    a1 = b0 + MLA_1(N(b0))
    h' = a1 + FFN_1(N(a1)) + s

- **A hand-on inside a period.**  A layer is a period ``(sublayer 0, sublayer
  1)`` of :func:`transformer.paged_forward` (one scan step; each sublayer an
  attention layer with its own weights and its own row of the pool: ``2 x
  num_layers`` rows).  Sublayer 0's ``finish`` computes ``s`` and hands it,
  with the layer's pick tallies, to sublayer 1's, which adds it
  (``paged_forward(hand_on=True)``); it never crosses a layer.
- **Identity experts.**  The router is ``n_routed_experts + zero_expert_num``
  wide (512 + 256): softmax over all, the top ``moe_topk`` of ``score +
  e_score_correction_bias``, the picked scores without the bias and NOT
  renormalised, times ``routed_scaling_factor``.  Outputs past the real
  experts are experts that return their input: such a pick adds ``w u``
  (``moe/serving.py sparse_moe_ffn(identity_experts=)``).  No shared expert, no
  leading dense layer, no group limit.
- **Two LoRA scales** (``mla_scale_q_lora``, ``mla_scale_kv_lora``): ``q``
  (both parts) times ``sqrt(hidden / q_lora_rank)`` and the normed ``c_kv``
  times ``sqrt(hidden / kv_lora_rank)``; ``k_pe`` is not scaled.  Folded where
  they cost nothing: the first into the softmax scale, the second into the
  latent norm's gain in float32 (so the cached latent is the scaled one, and
  its value columns with it, as the source's ``k_pass`` feeds both).
- **Pick tallies.**  How many picks fall on identity experts and on held ones
  varies a token by construction and only the device knows: every pass adds
  its layers' counts to ``kv_cache[transformer.TALLY]`` (int32 ``[3]``, carried
  with the pool), which the engine reads once a wave into
  ``ServeCounters.moe_identity_picks`` / ``moe_held_picks`` /
  ``moe_overflow_windows`` (the trips an expert layer ran beyond its first
  because a pass held more picks than the window: ``moe/serving.py``).

Rotary is plain (``rope_theta`` 1e7, no scaling) over interleaved pairs.
Embedding and head are untied.  Training and tensor parallelism are not
implemented (``tp_axis`` raises, as DeepSeek-V2's); the Omni model's audio and
vision encoders and its codec decoder are not built.
"""

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import transformer
from .deepseek_v2 import latent_width, mla_out, mla_qkv
from .transformer import TALLY, init_linear, rms_norm, swiglu_mlp


@dataclasses.dataclass(frozen=True)
class LongcatFlashConfig:
    vocab_size: int = 131072
    hidden_size: int = 6144
    ffn_hidden_size: int = 12288  # a sublayer's dense FFN
    expert_ffn_hidden_size: int = 2048  # ONE routed expert
    num_layers: int = 28  # layers of two sublayers: 2 x num_layers attentions and pool rows
    num_heads: int = 64
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    n_routed_experts: int = 512  # the router's REAL experts (its width is these + the identity ones)
    # experts whose weights are here: None = all; fewer = this chip's share of an
    # expert-parallel deployment (experts 0..n-1).  Only ``init_params`` reads
    # either count: the forward reads the parameters' shapes.
    num_local_experts: Optional[int] = None
    zero_expert_num: int = 256  # identity experts: router outputs past the real ones
    moe_topk: int = 12
    routed_scaling_factor: float = 6.0
    max_seq_len: int = 131072
    rope_theta: float = 10000000.0
    rms_eps: float = 1e-5

    @staticmethod
    def longcat_flash():
        return LongcatFlashConfig()

    @staticmethod
    def tiny(vocab=256, hidden=128, layers=2, heads=8, experts=16, local_experts=None,
             zero_experts=8, topk=4, seq=512):
        return LongcatFlashConfig(
            vocab_size=vocab, hidden_size=hidden, ffn_hidden_size=hidden * 2,
            expert_ffn_hidden_size=hidden // 2, num_layers=layers, num_heads=heads,
            q_lora_rank=48, kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=16,
            v_head_dim=16, n_routed_experts=experts, num_local_experts=local_experts,
            zero_expert_num=zero_experts, moe_topk=topk, max_seq_len=seq)


def lora_scales(config: LongcatFlashConfig):
    """``(q, kv)``: what ``q`` and the normed ``c_kv`` are multiplied by."""
    return ((config.hidden_size / config.q_lora_rank) ** 0.5,
            (config.hidden_size / config.kv_lora_rank) ** 0.5)


def softmax_scale(config: LongcatFlashConfig) -> float:
    """``(qk_nope + qk_rope)^-0.5`` times the q scale, which multiplies both parts of ``q``."""
    return (config.qk_nope_head_dim + config.qk_rope_head_dim) ** -0.5 * lora_scales(config)[0]


def rotary_inv_freq(config: LongcatFlashConfig) -> np.ndarray:
    dim = config.qk_rope_head_dim
    return (1.0 / config.rope_theta ** (np.arange(0, dim, 2) / dim)).astype(np.float32)


def init_params(config: LongcatFlashConfig, key, dtype=jnp.float32):
    """``{"embed", "layers": {"sub0", "sub1", "moe"}, "final_norm", "lm_head"}``:
    every leaf of ``layers`` is a stack ``[num_layers, ...]``; a sublayer is
    ``attn_norm``, ``attn`` (DeepSeek-V2's seven), ``mlp_norm``, ``mlp`` (the
    dense SwiGLU); ``moe`` the layer's one expert layer: the router ``wg`` ``[D,
    real + identity]`` at unit logit scale with its selection ``bias``, experts
    ``[num_layers, num_local_experts, ...]``."""
    D, H, L = config.hidden_size, config.num_heads, config.num_layers
    qk = config.qk_nope_head_dim + config.qk_rope_head_dim
    kv_out = config.qk_nope_head_dim + config.v_head_dim
    held = config.num_local_experts or config.n_routed_experts
    wide = config.n_routed_experts + config.zero_expert_num
    k_emb, k0, k1, k_moe, k_out = jax.random.split(key, 5)

    def stack(key, *shape):
        """[L, ..., fan_in, fan_out] at 1/sqrt(fan_in)"""
        return jax.random.normal(key, (L, ) + shape, dtype) * float(shape[-2]) ** -0.5

    def ffn(key, width, *lead):
        ks = jax.random.split(key, 3)
        return {"w_gate": stack(ks[0], *lead, D, width), "w_up": stack(ks[1], *lead, D, width),
                "w_down": stack(ks[2], *lead, width, D)}

    def sublayer(key):
        ks = jax.random.split(key, 6)
        return {"attn_norm": jnp.ones((L, D), dtype), "mlp_norm": jnp.ones((L, D), dtype),
                "attn": {"wq_a": stack(ks[0], D, config.q_lora_rank),
                         "q_norm": jnp.ones((L, config.q_lora_rank), dtype),
                         "wq_b": stack(ks[1], config.q_lora_rank, H * qk),
                         "wkv_a": stack(ks[2], D, config.kv_lora_rank + config.qk_rope_head_dim),
                         "kv_norm": jnp.ones((L, config.kv_lora_rank), dtype),
                         "wkv_b": stack(ks[3], config.kv_lora_rank, H * kv_out),
                         "wo": stack(ks[4], H * config.v_head_dim, D)},
                "mlp": ffn(ks[5], config.ffn_hidden_size)}

    km = jax.random.split(k_moe, 2)
    return {
        "embed": jax.random.normal(k_emb, (config.vocab_size, D), dtype) * 0.02,
        "layers": {"sub0": sublayer(k0), "sub1": sublayer(k1),
                   "moe": {"gate": {"wg": stack(km[0], D, wide),
                                    "bias": jnp.zeros((L, wide), dtype)},
                           "experts": ffn(km[1], config.expert_ffn_hidden_size, held)}},
        "final_norm": jnp.ones((D, ), dtype),
        "lm_head": init_linear(k_out, D, config.vocab_size, dtype=dtype),
    }


# --------------------------------------------------------- paged (ragged) serve
def init_paged_cache(config: LongcatFlashConfig, num_blocks: int, block_size: int,
                     dtype=jnp.bfloat16):
    """The latent pool, ONE leaf ``[2 x num_layers, NB, 1, bs, latent_width]``: a
    token's scaled ``[c_kv | k_pe]`` once a SUBLAYER (row ``2 l`` is layer
    ``l``'s first attention, ``2 l + 1`` its second), blocks on axis 1 as every
    family's pool; and beside it the pick tallies (``transformer.TALLY``: int32
    ``[identity, held, trips of the held picks' window beyond the first]``,
    running sums that wrap around; no pool leaf)."""
    return {"latent": jnp.zeros((2 * config.num_layers, num_blocks, 1, block_size,
                                 latent_width(config)), dtype),
            TALLY: jnp.zeros((3, ), jnp.int32)}


def moe_picks_per_token(config: LongcatFlashConfig) -> int:
    """Picks one token makes through a forward pass: ``moe_topk`` in every
    layer's one expert layer, whatever kind each pick is."""
    return config.moe_topk * config.num_layers


def moe_expert_rows(config: LongcatFlashConfig, slots: int) -> int:
    """Rows the expert layers' grouped matmuls of one pass over ``slots`` token
    slots run over: the window the held picks are compacted into (identity
    picks and picks held elsewhere never become rows), the first trip's; the
    trips beyond it are tallied on the device (``moe_overflow_windows``)."""
    from ..moe.serving import expert_rows
    held = config.num_local_experts or config.n_routed_experts
    return expert_rows(slots, config.moe_topk, held,
                       config.n_routed_experts + config.zero_expert_num) * config.num_layers


def paged_value_dim(config: LongcatFlashConfig) -> int:
    """The value's width inside the cached latent (``paged_forward``'s ``value_dim``)."""
    return config.kv_lora_rank


def pick_tallies(config: LongcatFlashConfig) -> tuple:
    """The ``ServeCounters`` fields that ``kv_cache[TALLY]``'s entries are, in order."""
    return "moe_identity_picks", "moe_held_picks", "moe_overflow_windows"


def forward_paged(config: LongcatFlashConfig, params, tokens, n_tokens, start_pos, block_tables,
                  kv_cache, *, block_size: int, tp_axis: Optional[str] = None,
                  gather_logits: bool = True, live_token_bound: Optional[int] = None,
                  last_rows: bool = False):
    """Ragged chunked forward (``transformer.paged_forward`` states the
    contract): a layer is the period (sublayer 0, sublayer 1), each absorbed MLA
    over its own row of the latent pool and a dense SwiGLU; sublayer 0 computes
    the shortcut expert layer and hands it to sublayer 1, which adds it."""
    from ..moe.serving import expert_rows, sparse_moe_ffn, window_trips
    if tp_axis is not None:
        raise NotImplementedError("longcat_flash: tensor-parallel serving is not implemented "
                                  "(the deployment it is cut for is expert-parallel)")
    kv_cache = dict(kv_cache)
    tally = kv_cache.pop(TALLY)
    dtype = kv_cache["latent"].dtype
    width = kv_cache["latent"].shape[-1]
    inv_freq, kv_scale = rotary_inv_freq(config), lora_scales(config)[1]

    layers = params["layers"]
    experts = layers["moe"]["experts"]  # one stack; each layer is handed its index
    depth = experts["w_gate"].shape[0]
    moe = {"gate": layers["moe"]["gate"], "layer": jnp.arange(depth, dtype=jnp.int32)}

    def embed(tokens, safe_pos):
        return params["embed"][tokens].astype(dtype)

    def qkv(lp, x, safe_pos):
        h = rms_norm(x, lp["attn_norm"], config.rms_eps)
        # the kv scale in the latent norm's gain, in float32 (3.46 is no bfloat16)
        a = {**lp["attn"], "kv_norm": lp["attn"]["kv_norm"].astype(jnp.float32) * kv_scale}
        q, latent, _ = mla_qkv(config, a, h, safe_pos, inv_freq, 1.0, width)
        return q, latent, None

    def finish(lp, x, kept, attn, live, handed):
        x = x + mla_out(config, lp["attn"], attn)
        u = rms_norm(x, lp["mlp_norm"], config.rms_eps)
        if "moe" not in lp:  # sublayer 1: the layer ends, the shortcut joins the stream
            shortcut, picks = handed
            return x + swiglu_mlp(lp["mlp"], u) + shortcut, picks
        with jax.named_scope("scmoe_shortcut"):
            shortcut, picks = sparse_moe_ffn(
                {"gate": lp["moe"]["gate"], "experts": experts}, u.reshape(-1, u.shape[-1]),
                config.moe_topk, False, live.reshape(-1), layer=lp["moe"]["layer"],
                scaling=config.routed_scaling_factor, identity_experts=config.zero_expert_num)
            # the trips the layer ran beyond its first: more held picks than a window's rows
            # (added here: sparse_moe_ffn's tally is the pair tests/chipbench compares whole)
            window = expert_rows(live.size, config.moe_topk, experts["w_gate"].shape[1],
                                 lp["moe"]["gate"]["wg"].shape[-1])
            picks = jnp.append(picks, jnp.maximum(window_trips(picks[1], window) - 1, 0))
        return x + swiglu_mlp(lp["mlp"], u), (shortcut.reshape(u.shape), picks)

    def head(x):
        return rms_norm(x, params["final_norm"], config.rms_eps) @ params["lm_head"].astype(dtype)

    logits, cache, (picks, ) = transformer.paged_forward(
        ({**layers["sub0"], "moe": moe}, layers["sub1"]), tokens, n_tokens, start_pos,
        block_tables, kv_cache, block_size=block_size, live_token_bound=live_token_bound,
        last_rows=last_rows, embed=embed, qkv=qkv, finish=finish, head=head,
        softmax_scale=softmax_scale(config), value_dim=config.kv_lora_rank, hand_on=True)
    cache[TALLY] = tally + jnp.sum(picks, axis=0)  # [layers, 3] of this pass; int32 wraps around
    return logits, cache


def config_from_hf(hf_config) -> LongcatFlashConfig:
    """A ``LongcatFlashConfig`` from a transformers ``LongcatFlashConfig``."""
    if getattr(hf_config, "rope_scaling", None) is not None \
            or getattr(hf_config, "router_bias", False) \
            or getattr(hf_config, "zero_expert_type", "identity") != "identity":
        raise ValueError("longcat_flash: plain rotary, a router without a bias in its logits and "
                         "identity zero-experts are implemented (got rope_scaling "
                         f"{hf_config.rope_scaling}, router_bias "
                         f"{getattr(hf_config, 'router_bias', False)}, zero_expert_type "
                         f"{getattr(hf_config, 'zero_expert_type', 'identity')})")
    if not (getattr(hf_config, "mla_scale_q_lora", True)
            and getattr(hf_config, "mla_scale_kv_lora", True)) or hf_config.q_lora_rank is None:
        raise ValueError("longcat_flash: a low-rank q and both LoRA scales are what the "
                         "installed modeling code applies; a config that switches one off has "
                         "no reader")
    return LongcatFlashConfig(
        vocab_size=hf_config.vocab_size, hidden_size=hf_config.hidden_size,
        ffn_hidden_size=hf_config.ffn_hidden_size,
        expert_ffn_hidden_size=hf_config.expert_ffn_hidden_size, num_layers=hf_config.num_layers,
        num_heads=hf_config.num_attention_heads, q_lora_rank=hf_config.q_lora_rank,
        kv_lora_rank=hf_config.kv_lora_rank, qk_nope_head_dim=hf_config.qk_nope_head_dim,
        qk_rope_head_dim=hf_config.qk_rope_head_dim, v_head_dim=hf_config.v_head_dim,
        n_routed_experts=hf_config.n_routed_experts,
        zero_expert_num=hf_config.zero_expert_num or 0, moe_topk=hf_config.moe_topk,
        routed_scaling_factor=float(hf_config.routed_scaling_factor),
        max_seq_len=hf_config.max_position_embeddings, rope_theta=float(hf_config.rope_theta),
        rms_eps=hf_config.rms_norm_eps)
