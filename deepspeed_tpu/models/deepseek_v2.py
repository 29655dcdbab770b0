"""DeepSeek-V2 causal LM (arXiv:2405.04434; HF ``modeling_deepseek.py``) — serving only.

Three things no other family here has, each done in the shared code and handed
over from this module as data:

- **Latent attention (MLA).**  A token caches ONE vector ``[c_kv | k_pe]``
  (``kv_lora_rank`` + ``qk_rope_head_dim`` = 576 values, padded to a lane
  tile, 640), never K and V apart and never expanded to heads.  Queries arrive
  at the paged kernel *absorbed*: ``q_lat = q_nope W_kvb[k]^T`` beside the
  rotated ``q_pe``, so a head's score over a cached token is one 576-wide dot
  product with that vector and all 128 heads read the same one (multi-query
  attention with the q group stacked into the kernel's rows); the weighted sum
  runs over the vector's first 512 columns (``value_dim``: the kernel reads a
  block once) and goes through ``W_kvb[v]`` and ``W_o`` afterwards.  Chunks
  and decode steps take the same path.
- **A layer pattern.**  ``first_k_dense`` dense layers, then the expert layers:
  two stacks of parameters (``dense_layers``, ``layers``) handed to
  ``transformer.paged_forward`` as a list, which scans each with the pool and
  the layer's index carried through.
- **The chip's share of the experts.**  The router is ``[D, num_experts]``;
  the expert leaves hold ``num_local_experts`` of them (all, or what one chip
  of an expert-parallel deployment holds: experts 0..n-1, whole groups).
  ``moe/serving.py`` reads both off the shapes, routes over all (group-limited
  greedy, ``routed_scaling_factor``, no renormalisation), computes its own
  experts' part and adds the shared expert.  On one chip there is no exchange.

Rotary is YaRN-scaled (``rope_scaling``) over DeepSeek's interleaved pairs
``(2i, 2i + 1)``; with ``mscale == mscale_all_dim`` the tables are unscaled
and the softmax scale carries ``m^2`` (``softmax_scale``).  Angles are computed
from the positions (a table of 163,840 rows would sit in every program).

Training and tensor parallelism are not implemented for this family.
"""

import dataclasses
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import transformer
from .transformer import init_linear, rms_norm, swiglu_mlp

LANE = 128  # the pool's last axis is whole lane tiles: 576 is held as 640


@dataclasses.dataclass(frozen=True)
class DeepseekV2Config:
    vocab_size: int = 102400
    hidden_size: int = 5120
    intermediate_size: int = 12288  # the dense layers' FFN
    moe_intermediate_size: int = 1536  # ONE routed expert
    num_layers: int = 60
    first_k_dense: int = 1
    num_heads: int = 128
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    num_experts: int = 160  # the router's width
    # experts whose weights are here: None = all; fewer = this chip's share of
    # an expert-parallel deployment (whole groups, from expert 0).  Only
    # ``init_params`` reads it: the forward reads the parameters' shapes.
    num_local_experts: Optional[int] = None
    n_shared_experts: int = 2
    top_k: int = 6
    n_group: int = 8
    topk_group: int = 3
    routed_scaling_factor: float = 16.0
    norm_topk_prob: bool = False
    max_seq_len: int = 163840
    rope_theta: float = 10000.0
    # HF ``rope_scaling`` (type yarn) as sorted items, or None for plain rotary
    rope_scaling: Optional[tuple] = (
        ("beta_fast", 32), ("beta_slow", 1), ("factor", 40), ("mscale", 0.707),
        ("mscale_all_dim", 0.707), ("original_max_position_embeddings", 4096), ("type", "yarn"))
    rms_eps: float = 1e-6

    def __post_init__(self):
        if isinstance(self.rope_scaling, dict):  # a config.json's own form
            object.__setattr__(self, "rope_scaling", tuple(sorted(self.rope_scaling.items())))

    @staticmethod
    def deepseek_v2():
        return DeepseekV2Config()

    @staticmethod
    def tiny(vocab=256, hidden=128, layers=3, heads=8, experts=16, local_experts=None, seq=512,
             original_seq=32):
        return DeepseekV2Config(
            vocab_size=vocab, hidden_size=hidden, intermediate_size=hidden * 2,
            moe_intermediate_size=hidden // 2, num_layers=layers, first_k_dense=1,
            num_heads=heads, q_lora_rank=48, kv_lora_rank=32, qk_nope_head_dim=16,
            qk_rope_head_dim=16, v_head_dim=16, num_experts=experts,
            num_local_experts=local_experts, n_shared_experts=2, top_k=4, n_group=4,
            topk_group=2, max_seq_len=seq,
            rope_scaling={"type": "yarn", "factor": seq // original_seq, "beta_fast": 32,
                          "beta_slow": 1, "mscale": 0.707, "mscale_all_dim": 0.707,
                          "original_max_position_embeddings": original_seq})


def latent_width(config: DeepseekV2Config) -> int:
    """Values a cached token holds, a layer: ``[c_kv | k_pe]`` in whole lanes."""
    return -(-(config.kv_lora_rank + config.qk_rope_head_dim) // LANE) * LANE


def yarn_mscale(scale: float, mscale: float) -> float:
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def rotary_inv_freq(config: DeepseekV2Config) -> np.ndarray:
    """``[qk_rope_head_dim / 2]`` inverse frequencies: plain rotary's, or
    YaRN's blend of them with the same divided by ``factor`` (dimensions that
    turn more than ``beta_fast`` times over the original context keep their
    frequency, those under ``beta_slow`` are interpolated, a linear ramp
    between), as HF ``DeepseekV2YarnRotaryEmbedding``."""
    dim, base = config.qk_rope_head_dim, config.rope_theta
    plain = 1.0 / base ** (np.arange(0, dim, 2) / dim)  # host arithmetic; float32 at the return
    if config.rope_scaling is None:
        return plain.astype(np.float32)
    yarn = dict(config.rope_scaling)
    if yarn["type"] != "yarn":
        raise ValueError(f"rope_scaling type {yarn['type']!r} is not implemented (yarn is)")
    original = yarn["original_max_position_embeddings"]

    def correction_dim(rotations):
        return dim * math.log(original / (rotations * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(correction_dim(yarn["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(yarn["beta_slow"])), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / ((high if high != low else high + 0.001) - low),
                   0, 1)
    return (plain / yarn["factor"] * ramp + plain * (1 - ramp)).astype(np.float32)


def rotary_table_scale(config: DeepseekV2Config) -> float:
    """What YaRN multiplies cos and sin by: 1 where ``mscale == mscale_all_dim``."""
    if config.rope_scaling is None:
        return 1.0
    yarn = dict(config.rope_scaling)
    return (yarn_mscale(yarn["factor"], yarn.get("mscale", 1))
            / yarn_mscale(yarn["factor"], yarn.get("mscale_all_dim", 0)))


def softmax_scale(config: DeepseekV2Config) -> float:
    """``(qk_nope + qk_rope)^-0.5 * m^2``, ``m = 0.1 * mscale_all_dim * ln(factor) + 1``."""
    scale = (config.qk_nope_head_dim + config.qk_rope_head_dim) ** -0.5
    yarn = dict(config.rope_scaling or ())
    if yarn.get("mscale_all_dim"):
        scale *= yarn_mscale(yarn["factor"], yarn["mscale_all_dim"]) ** 2
    return scale


def rotate_pairs(x, positions, inv_freq, table_scale: float = 1.0):
    """x [b, s, heads, d] with pairs ``(2i, 2i + 1)`` rotated by ``positions *
    inv_freq[i]`` (DeepSeek's interleaved convention), angles in float32."""
    angle = positions.astype(jnp.float32)[..., None, None] * jnp.asarray(inv_freq)
    cos, sin = jnp.cos(angle) * table_scale, jnp.sin(angle) * table_scale
    pairs = x.astype(jnp.float32).reshape(x.shape[:-1] + (-1, 2))
    even, odd = pairs[..., 0], pairs[..., 1]
    out = jnp.stack([even * cos - odd * sin, odd * cos + even * sin], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def mla_qkv(config, a, h, safe_pos, inv_freq, table_scale: float, width: int):
    """Absorbed latent attention's projections of one layer, for any family
    whose config names the MLA sizes as :class:`DeepseekV2Config` does
    (``num_heads``, ``kv_lora_rank``, ``qk_nope_head_dim``, ``qk_rope_head_dim``,
    ``v_head_dim``, ``rms_eps``).  Borrowed by ``glm_moe_dsa.py`` (which reads
    ``c_q`` again for its indexer) and by ``longcat_flash.py`` (twice a layer;
    its two LoRA scales ride in the softmax scale and in the ``kv_norm`` gain it
    hands over, so nothing here knows of them).  ``a`` the layer's attention weights, ``h``
    ``[b, s, D]`` the normed input.  Returns ``(q, latent, c_q)``: ``q`` ``[b, s,
    H, width]`` = ``[q_nope W_kvb[k]^T | rotated q_pe]`` in whole lanes, a head's
    query against the cached vector itself; ``latent`` ``[b, s, 1, width]`` =
    ``[c_kv | rotated k_pe]``, the token's row of the pool; ``c_q`` ``[b, s,
    q_lora_rank]``, the normed low-rank query (an indexer projects it again)."""
    H, rank, rope = config.num_heads, config.kv_lora_rank, config.qk_rope_head_dim
    nope, dv = config.qk_nope_head_dim, config.v_head_dim
    dtype = h.dtype
    if "wq_a" in a:
        c_q = rms_norm(h @ a["wq_a"].astype(dtype), a["q_norm"], config.rms_eps)
        q = (c_q @ a["wq_b"].astype(dtype)).reshape(h.shape[:2] + (H, nope + rope))
    else:  # a full-rank query (``q_lora_rank`` null: bailing_hybrid): ``c_q`` is None
        c_q, q = None, (h @ a["wq"].astype(dtype)).reshape(h.shape[:2] + (H, nope + rope))
    kv = h @ a["wkv_a"].astype(dtype)
    c_kv = rms_norm(kv[..., :rank], a["kv_norm"], config.rms_eps)
    q_pe = rotate_pairs(q[..., nope:], safe_pos, inv_freq, table_scale)
    k_pe = rotate_pairs(kv[..., None, rank:], safe_pos, inv_freq, table_scale)[..., 0, :]
    with jax.named_scope("mla_absorb"):
        # q_nope W_kvb[k]^T: a head's query against the latent itself
        w_k = a["wkv_b"].astype(dtype).reshape(rank, H, nope + dv)[..., :nope]
        q_lat = jnp.einsum("bshd,chd->bshc", q[..., :nope], w_k)
    to_lanes = lambda a: jnp.pad(a, [(0, 0)] * (a.ndim - 1) + [(0, width - rank - rope)])
    latent = to_lanes(jnp.concatenate([c_kv, k_pe], axis=-1))[:, :, None, :]
    return to_lanes(jnp.concatenate([q_lat, q_pe], axis=-1)), latent, c_q


def mla_out(config, a, attn, gate=None):
    """What a layer's attention adds to the residual stream: ``attn`` ``[b, s,
    H, kv_lora_rank]``, the kernel's weighted sums of latents, out of the latent
    through ``W_kvb[v]`` and through ``W_o``; ``gate`` ``[b, s, H]`` float32
    (bailing_hybrid's head-wise output gate; None: none) scales each head's
    output by its sigmoid between the two."""
    H, rank = config.num_heads, config.kv_lora_rank
    nope, dv = config.qk_nope_head_dim, config.v_head_dim
    dtype = attn.dtype
    with jax.named_scope("mla_absorb"):
        w_v = a["wkv_b"].astype(dtype).reshape(rank, H, nope + dv)[..., nope:]
        heads = jnp.einsum("bshc,chd->bshd", attn, w_v)
    if gate is not None:
        with jax.named_scope("attn_gate"):
            heads = (heads.astype(jnp.float32) * jax.nn.sigmoid(gate)[..., None]).astype(dtype)
    return heads.reshape(attn.shape[:2] + (H * dv, )) @ a["wo"].astype(dtype)


def mla_finish(config, lp, x, attn, live, experts, **routing):
    """The rest of a layer after the kernel, for a family of MLA layers over a
    dense stack and an expert stack: the attention's output into the residual
    stream, the norm, and the layer's FFN: the dense SwiGLU where ``lp`` holds
    no ``moe``, else ``moe/serving.py sparse_moe_ffn`` over the stack
    ``experts`` at the layer's index with the family's ``routing`` keywords
    (``config.top_k``, ``config.norm_topk_prob`` and the shared expert in ``lp``)."""
    from ..moe.serving import sparse_moe_ffn
    x = x + mla_out(config, lp["attn"], attn)
    h = rms_norm(x, lp["mlp_norm"], config.rms_eps)
    if "moe" not in lp:
        return x + swiglu_mlp(lp["mlp"], h)
    out = sparse_moe_ffn({"gate": lp["moe"]["gate"], "shared": lp["moe"]["shared"],
                          "experts": experts},
                         h.reshape(-1, h.shape[-1]), config.top_k, config.norm_topk_prob,
                         live.reshape(-1), layer=lp["moe"]["layer"], **routing)
    return x + out.reshape(h.shape)


def init_params(config: DeepseekV2Config, key, dtype=jnp.float32):
    """``{"embed", "dense_layers": [first_k_dense, ...], "layers": [the expert
    layers, ...], "final_norm", "lm_head"}``: two stacks, attention alike in
    both.  The router is drawn at unit logit scale so that routing is not
    uniform; experts ``[L, num_local_experts, ...]``."""
    D, H = config.hidden_size, config.num_heads
    qk = config.qk_nope_head_dim + config.qk_rope_head_dim
    kv_out = config.qk_nope_head_dim + config.v_head_dim
    held = config.num_local_experts or config.num_experts
    Fe, Fs = config.moe_intermediate_size, config.moe_intermediate_size * config.n_shared_experts
    k_emb, k_dense, k_moe, k_out = jax.random.split(key, 4)

    def stack(key, depth, *shape):
        """[depth, ..., fan_in, fan_out] at 1/sqrt(fan_in)"""
        return jax.random.normal(key, (depth, ) + shape, dtype) * float(shape[-2]) ** -0.5

    def attention(key, depth):
        ks = jax.random.split(key, 5)
        return {"wq_a": stack(ks[0], depth, D, config.q_lora_rank),
                "q_norm": jnp.ones((depth, config.q_lora_rank), dtype),
                "wq_b": stack(ks[1], depth, config.q_lora_rank, H * qk),
                "wkv_a": stack(ks[2], depth, D, config.kv_lora_rank + config.qk_rope_head_dim),
                "kv_norm": jnp.ones((depth, config.kv_lora_rank), dtype),
                "wkv_b": stack(ks[3], depth, config.kv_lora_rank, H * kv_out),
                "wo": stack(ks[4], depth, H * config.v_head_dim, D)}

    def ffn(key, depth, width, *lead):
        ks = jax.random.split(key, 3)
        return {"w_gate": stack(ks[0], depth, *lead, D, width),
                "w_up": stack(ks[1], depth, *lead, D, width),
                "w_down": stack(ks[2], depth, *lead, width, D)}

    def norms(depth):
        return {"attn_norm": jnp.ones((depth, D), dtype), "mlp_norm": jnp.ones((depth, D), dtype)}

    n_dense, n_moe = config.first_k_dense, config.num_layers - config.first_k_dense
    kd, km = jax.random.split(k_dense), jax.random.split(k_moe, 4)
    return {
        "embed": jax.random.normal(k_emb, (config.vocab_size, D), dtype) * 0.02,
        "dense_layers": {"attn": attention(kd[0], n_dense),
                         "mlp": ffn(kd[1], n_dense, config.intermediate_size), **norms(n_dense)},
        "layers": {"attn": attention(km[0], n_moe),
                   "moe": {"gate": {"wg": stack(km[1], n_moe, D, config.num_experts)},
                           "experts": ffn(km[2], n_moe, Fe, held),
                           "shared": ffn(km[3], n_moe, Fs)},
                   **norms(n_moe)},
        "final_norm": jnp.ones((D, ), dtype),
        "lm_head": init_linear(k_out, D, config.vocab_size, dtype=dtype),
    }


# --------------------------------------------------------- paged (ragged) serve
def init_paged_cache(config: DeepseekV2Config, num_blocks: int, block_size: int,
                     dtype=jnp.bfloat16):
    """The latent pool: ONE leaf ``[L, NB, 1, bs, latent_width]``, a token's
    ``[c_kv | k_pe]`` (and zeros to the lane tile) once a layer.  Blocks on
    axis 1 and one "KV head" on axis 2, as every family's pool, so the
    engine's copy-on-write and ``paged_forward``'s write find it as it is."""
    return {"latent": jnp.zeros((config.num_layers, num_blocks, 1, block_size,
                                 latent_width(config)), dtype)}


def moe_picks_per_token(config: DeepseekV2Config) -> int:
    """Picks one token makes through a forward pass: k in every expert layer,
    whether or not the picked expert is held here."""
    return config.top_k * (config.num_layers - config.first_k_dense)


def paged_value_dim(config: DeepseekV2Config) -> int:
    """The value's width inside the one cached vector (``paged_forward``'s
    ``value_dim``; ``transformer.paged_step_slots`` reads it for the counters)."""
    return config.kv_lora_rank


def moe_expert_rows(config: DeepseekV2Config, slots: int) -> int:
    """Rows the expert layers' grouped matmuls of one pass over ``slots`` token
    slots run over: on a share the window its held picks are compacted into
    (``moe/serving.py expert_rows``), the first trip's."""
    from ..moe.serving import expert_rows
    held = config.num_local_experts or config.num_experts
    return expert_rows(slots, config.top_k, held, config.num_experts) \
        * (config.num_layers - config.first_k_dense)


def forward_paged(config: DeepseekV2Config, params, tokens, n_tokens, start_pos, block_tables,
                  kv_cache, *, block_size: int, tp_axis: Optional[str] = None,
                  gather_logits: bool = True, live_token_bound: Optional[int] = None,
                  last_rows: bool = False):
    """Ragged chunked forward (``transformer.paged_forward`` states the
    contract): absorbed MLA over the latent pool, a dense stack and an expert
    stack, the expert FFN of ``moe/serving.py`` over the experts held here."""
    if tp_axis is not None:
        raise NotImplementedError("deepseek_v2: tensor-parallel serving is not implemented "
                                  "(the deployment it is cut for is expert-parallel)")
    dtype = kv_cache["latent"].dtype
    width = kv_cache["latent"].shape[-1]
    inv_freq, table_scale = rotary_inv_freq(config), rotary_table_scale(config)

    moe_layers = params["layers"]
    experts = moe_layers["moe"]["experts"]  # one stack; each layer is handed its index
    n_moe = experts["w_gate"].shape[0]
    moe = {"gate": moe_layers["moe"]["gate"], "shared": moe_layers["moe"]["shared"],
           "layer": jnp.arange(n_moe, dtype=jnp.int32)}

    def embed(tokens, safe_pos):
        return params["embed"][tokens].astype(dtype)

    def qkv(lp, x, safe_pos):
        h = rms_norm(x, lp["attn_norm"], config.rms_eps)
        q, latent, _ = mla_qkv(config, lp["attn"], h, safe_pos, inv_freq, table_scale, width)
        return q, latent, None

    def finish(lp, x, kept, attn, live):
        return mla_finish(config, lp, x, attn, live, experts, n_group=config.n_group,
                          topk_group=config.topk_group, scaling=config.routed_scaling_factor)

    def head(x):
        return rms_norm(x, params["final_norm"], config.rms_eps) @ params["lm_head"].astype(dtype)

    return transformer.paged_forward(
        [params["dense_layers"], {**moe_layers, "moe": moe}], tokens, n_tokens, start_pos,
        block_tables, kv_cache, block_size=block_size, live_token_bound=live_token_bound,
        last_rows=last_rows, embed=embed, qkv=qkv, finish=finish, head=head,
        softmax_scale=softmax_scale(config), value_dim=config.kv_lora_rank)


def config_from_hf(hf_config) -> DeepseekV2Config:
    """A ``DeepseekV2Config`` from a transformers ``DeepseekV2Config``."""
    if getattr(hf_config, "topk_method", "group_limited_greedy") != "group_limited_greedy" \
            or getattr(hf_config, "scoring_func", "softmax") != "softmax":
        raise ValueError("deepseek_v2: only softmax scoring with group_limited_greedy top-k is "
                         f"implemented (got {hf_config.scoring_func}, {hf_config.topk_method})")
    if getattr(hf_config, "moe_layer_freq", 1) != 1 or hf_config.q_lora_rank is None:
        raise ValueError("deepseek_v2: every layer after the dense ones is an expert layer and q "
                         "is low-rank (DeepSeek-V2); the Lite variant's full-rank q is not implemented")
    return DeepseekV2Config(
        vocab_size=hf_config.vocab_size, hidden_size=hf_config.hidden_size,
        intermediate_size=hf_config.intermediate_size,
        moe_intermediate_size=hf_config.moe_intermediate_size,
        num_layers=hf_config.num_hidden_layers, first_k_dense=hf_config.first_k_dense_replace,
        num_heads=hf_config.num_attention_heads, q_lora_rank=hf_config.q_lora_rank,
        kv_lora_rank=hf_config.kv_lora_rank, qk_nope_head_dim=hf_config.qk_nope_head_dim,
        qk_rope_head_dim=hf_config.qk_rope_head_dim, v_head_dim=hf_config.v_head_dim,
        num_experts=hf_config.n_routed_experts, n_shared_experts=hf_config.n_shared_experts,
        top_k=hf_config.num_experts_per_tok, n_group=hf_config.n_group,
        topk_group=hf_config.topk_group,
        routed_scaling_factor=float(hf_config.routed_scaling_factor),
        norm_topk_prob=bool(hf_config.norm_topk_prob),
        max_seq_len=hf_config.max_position_embeddings, rope_theta=float(hf_config.rope_theta),
        rope_scaling=hf_config.rope_scaling, rms_eps=hf_config.rms_norm_eps)
