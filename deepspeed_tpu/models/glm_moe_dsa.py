"""GLM-5 causal LM (``model_type: glm_moe_dsa``; the ``config.json`` of
``zai-org/GLM-5``; its attention is DeepSeek-V3.2-Exp's ``inference/model.py``
``MLA`` + ``Indexer``) — serving only.

DeepSeek-V2's shape (absorbed latent attention over a latent pool, a leading
dense stack and an expert stack, one chip's share of the experts: all of it
``deepseek_v2.py``'s and ``moe/serving.py``'s, imported, not copied) with one
thing no other family here has:

- **Attention over a learned selection of the cache** (DeepSeek sparse
  attention).  Beside the latent ``[c_kv | k_pe]`` a token caches ONE *index
  key* ``k^I`` (``index_head_dim`` = 128 values, a LayerNorm of ``h W^I_k`` with
  its first ``qk_rope_head_dim`` values rotated) in a second pool leaf of its
  own width, which every layer writes and the attention kernel never reads.  A
  query token's ``index_n_heads`` = 32 light index queries ``q^I_j = c_q
  W^I_q,j`` (from the same normed low-rank query ``c_q`` the attention's heads
  come from) score every cached token of its sequence, ``I[t, s] = sum_j w[t, j]
  relu(q^I_j[t] . k^I[s])`` with ``w = (h W^I_w) J^-1/2 Di^-1/2`` in float32, and
  the token attends the ``index_topk`` = 2,048 positions ``s <= t`` of largest
  score alone (all of its past under 2,048: plain MLA).  This module gives
  :func:`transformer.paged_forward` the projections (``Selection.indexer``);
  the scores over the paged index-key leaf, the exact top-k and the kernel that
  attends the selection are ``ops/attention/dsa.py`` and ``paged.py``.

Also: ``v_head_dim`` (256) is not ``qk_nope_head_dim`` (192), rotary is plain
(theta 1e6, no scaling) over interleaved pairs, and the router is sigmoid with
a stored selection bias, renormalised over the picks (+1e-20) and scaled by
``routed_scaling_factor`` (``moe/serving.py route(scoring="sigmoid", bias=,
norm_eps=)``), one group.

NOT here: training, tensor parallelism (``tp_axis`` raises, as DeepSeek-V2's),
and the multi-token-prediction layer (``num_nextn_predict_layers``: it drafts
tokens for speculative decoding; a checkpoint's is not loaded).  The
published stack's Hadamard rotation of ``q^I`` and ``k^I`` (orthogonal: the
scores are the same) and their fp8 rounding are left out: bfloat16 throughout.
Speculative decoding with this engine's own drafters works as for any pool:
index keys live in the blocks that ``rollback_blocks`` rolls back.
"""

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from . import transformer
from .deepseek_v2 import (latent_width, mla_finish, mla_qkv, rotary_inv_freq, rotate_pairs,
                          softmax_scale)
from .transformer import init_linear, rms_norm

# the pool leaf that holds a token's index key: written, scored, never attended
# (``transformer.paged_step_slots`` reads this name off the module)
PAGED_SELECT_LEAF = "index_keys"
INDEX_NORM_EPS = 1e-6  # the indexer's LayerNorm (``model.py`` ``LayerNorm(dim, eps=1e-6)``)
ROUTE_NORM_EPS = 1e-20  # added to the picked scores' sum where they are renormalised


@dataclasses.dataclass(frozen=True)
class GlmMoeDsaConfig:
    vocab_size: int = 154880
    hidden_size: int = 6144
    intermediate_size: int = 12288  # the dense layers' FFN
    moe_intermediate_size: int = 2048  # ONE routed expert, and the shared one
    num_layers: int = 78
    first_k_dense: int = 3
    num_heads: int = 64
    q_lora_rank: int = 2048
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 192
    qk_rope_head_dim: int = 64
    v_head_dim: int = 256
    index_n_heads: int = 32
    index_head_dim: int = 128
    index_topk: int = 2048
    num_experts: int = 256  # the router's width
    # experts whose weights are here: None = all; fewer = this chip's share of an
    # expert-parallel deployment (from expert 0).  Only ``init_params`` reads it.
    num_local_experts: Optional[int] = None
    n_shared_experts: int = 1
    top_k: int = 8
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    max_seq_len: int = 202752
    # HF ``rope_parameters`` as sorted items (a config.json's own dict is taken too)
    rope_parameters: tuple = (("rope_theta", 1000000), ("rope_type", "default"))
    rms_eps: float = 1e-5
    rope_scaling = None  # plain rotary: what ``deepseek_v2.rotary_inv_freq`` / ``softmax_scale`` ask

    def __post_init__(self):
        if isinstance(self.rope_parameters, dict):
            object.__setattr__(self, "rope_parameters", tuple(sorted(self.rope_parameters.items())))
        if dict(self.rope_parameters).get("rope_type", "default") != "default":
            raise ValueError("glm_moe_dsa: plain rotary (rope_type default) is implemented, got "
                             f"{dict(self.rope_parameters)}")

    @property
    def rope_theta(self) -> float:
        return float(dict(self.rope_parameters)["rope_theta"])

    @staticmethod
    def glm_5():
        return GlmMoeDsaConfig()

    @staticmethod
    def tiny(vocab=256, hidden=128, layers=3, heads=16, experts=16, local_experts=None, seq=1024,
             index_topk=16, index_heads=4):
        return GlmMoeDsaConfig(
            vocab_size=vocab, hidden_size=hidden, intermediate_size=hidden * 2,
            moe_intermediate_size=hidden // 2, num_layers=layers, first_k_dense=1,
            num_heads=heads, q_lora_rank=48, kv_lora_rank=32, qk_nope_head_dim=24,
            qk_rope_head_dim=16, v_head_dim=32, index_n_heads=index_heads, index_head_dim=32,
            index_topk=index_topk, num_experts=experts, num_local_experts=local_experts,
            n_shared_experts=1, top_k=4, max_seq_len=seq)


def init_params(config: GlmMoeDsaConfig, key, dtype=jnp.float32):
    """``{"embed", "dense_layers", "layers", "final_norm", "lm_head"}`` as
    DeepSeek-V2's two stacks, each layer with an ``indexer`` (``wq`` from the
    low-rank query, ``wk`` and ``weights`` from the hidden state, the key's
    LayerNorm) and each expert layer's gate with its selection ``bias``."""
    D, H = config.hidden_size, config.num_heads
    qk = config.qk_nope_head_dim + config.qk_rope_head_dim
    kv_out = config.qk_nope_head_dim + config.v_head_dim
    J, Di = config.index_n_heads, config.index_head_dim
    held = config.num_local_experts or config.num_experts
    Fe, Fs = config.moe_intermediate_size, config.moe_intermediate_size * config.n_shared_experts
    k_emb, k_dense, k_moe, k_out = jax.random.split(key, 4)

    def stack(key, depth, *shape):
        """[depth, ..., fan_in, fan_out] at 1/sqrt(fan_in)"""
        return jax.random.normal(key, (depth, ) + shape, dtype) * float(shape[-2]) ** -0.5

    def attention(key, depth):
        ks = jax.random.split(key, 8)
        return {"attn": {"wq_a": stack(ks[0], depth, D, config.q_lora_rank),
                         "q_norm": jnp.ones((depth, config.q_lora_rank), dtype),
                         "wq_b": stack(ks[1], depth, config.q_lora_rank, H * qk),
                         "wkv_a": stack(ks[2], depth, D,
                                        config.kv_lora_rank + config.qk_rope_head_dim),
                         "kv_norm": jnp.ones((depth, config.kv_lora_rank), dtype),
                         "wkv_b": stack(ks[3], depth, config.kv_lora_rank, H * kv_out),
                         "wo": stack(ks[4], depth, H * config.v_head_dim, D)},
                "indexer": {"wq": stack(ks[5], depth, config.q_lora_rank, J * Di),
                            "wk": stack(ks[6], depth, D, Di),
                            "k_norm": jnp.ones((depth, Di), dtype),
                            "k_norm_bias": jnp.zeros((depth, Di), dtype),
                            "weights": stack(ks[7], depth, D, J)},
                "attn_norm": jnp.ones((depth, D), dtype), "mlp_norm": jnp.ones((depth, D), dtype)}

    def ffn(key, depth, width, *lead):
        ks = jax.random.split(key, 3)
        return {"w_gate": stack(ks[0], depth, *lead, D, width),
                "w_up": stack(ks[1], depth, *lead, D, width),
                "w_down": stack(ks[2], depth, *lead, width, D)}

    n_dense, n_moe = config.first_k_dense, config.num_layers - config.first_k_dense
    kd, km = jax.random.split(k_dense), jax.random.split(k_moe, 4)
    return {
        "embed": jax.random.normal(k_emb, (config.vocab_size, D), dtype) * 0.02,
        "dense_layers": {**attention(kd[0], n_dense),
                         "mlp": ffn(kd[1], n_dense, config.intermediate_size)},
        "layers": {**attention(km[0], n_moe),
                   "moe": {"gate": {"wg": stack(km[1], n_moe, D, config.num_experts),
                                    "bias": jnp.zeros((n_moe, config.num_experts), dtype)},
                           "experts": ffn(km[2], n_moe, Fe, held),
                           "shared": ffn(km[3], n_moe, Fs)}},
        "final_norm": jnp.ones((D, ), dtype),
        "lm_head": init_linear(k_out, D, config.vocab_size, dtype=dtype),
    }


# --------------------------------------------------------- paged (ragged) serve
def init_paged_cache(config: GlmMoeDsaConfig, num_blocks: int, block_size: int,
                     dtype=jnp.bfloat16):
    """Two leaves of unlike widths, blocks on axis 1 and one "KV head" on axis
    2 as every family's pool: the latent ``[L, NB, 1, bs, latent_width]`` that
    the kernel attends, and the index keys ``[L, NB, 1, bs, index_head_dim]``
    that the indexer scores (``PAGED_SELECT_LEAF``).  Copy-on-write, prefix
    blocks and a rollback move blocks, so they move both."""
    return {"latent": jnp.zeros((config.num_layers, num_blocks, 1, block_size,
                                 latent_width(config)), dtype),
            PAGED_SELECT_LEAF: jnp.zeros((config.num_layers, num_blocks, 1, block_size,
                                          config.index_head_dim), dtype)}


def moe_picks_per_token(config: GlmMoeDsaConfig) -> int:
    """Picks one token makes through a forward pass: k in every expert layer,
    whether or not the picked expert is held here."""
    return config.top_k * (config.num_layers - config.first_k_dense)


def moe_expert_rows(config: GlmMoeDsaConfig, slots: int) -> int:
    """Rows the expert layers' grouped matmuls of one pass over ``slots`` token slots run
    over: on a share the window its held picks are compacted into, the first trip's."""
    from ..moe.serving import expert_rows
    held = config.num_local_experts or config.num_experts
    return expert_rows(slots, config.top_k, held, config.num_experts) \
        * (config.num_layers - config.first_k_dense)


def paged_value_dim(config: GlmMoeDsaConfig) -> int:
    """The value's width inside the cached latent (``paged_forward``'s ``value_dim``)."""
    return config.kv_lora_rank


def selected_keys(config: GlmMoeDsaConfig) -> tuple:
    """``(index_topk, attention layers)`` for the engine's ``dsa_*`` counters:
    every layer attends a selection of that many cached tokens at most."""
    return config.index_topk, config.num_layers


def forward_paged(config: GlmMoeDsaConfig, params, tokens, n_tokens, start_pos, block_tables,
                  kv_cache, *, block_size: int, tp_axis: Optional[str] = None,
                  gather_logits: bool = True, live_token_bound: Optional[int] = None,
                  last_rows: bool = False):
    """Ragged chunked forward (``transformer.paged_forward`` states the
    contract): DeepSeek-V2's absorbed MLA over the latent leaf, the indexer's
    projections for the selection, a dense stack and an expert stack."""
    if tp_axis is not None:
        raise NotImplementedError("glm_moe_dsa: tensor-parallel serving is not implemented "
                                  "(the deployment it is cut for is expert-parallel)")
    rope, J, Di = config.qk_rope_head_dim, config.index_n_heads, config.index_head_dim
    dtype = kv_cache["latent"].dtype
    width = kv_cache["latent"].shape[-1]
    inv_freq = rotary_inv_freq(config)

    moe_layers = params["layers"]
    experts = moe_layers["moe"]["experts"]  # one stack; each layer is handed its index
    n_moe = experts["w_gate"].shape[0]
    moe = {"gate": moe_layers["moe"]["gate"], "shared": moe_layers["moe"]["shared"],
           "layer": jnp.arange(n_moe, dtype=jnp.int32)}

    def embed(tokens, safe_pos):
        return params["embed"][tokens].astype(dtype)

    def rotate_first(x, safe_pos):
        """The indexer's rotary part is the FIRST ``qk_rope_head_dim`` values of a head."""
        return jnp.concatenate([rotate_pairs(x[..., :rope], safe_pos, inv_freq), x[..., rope:]],
                               axis=-1)

    def qkv(lp, x, safe_pos):
        h = rms_norm(x, lp["attn_norm"], config.rms_eps)
        q, latent, c_q = mla_qkv(config, lp["attn"], h, safe_pos, inv_freq, 1.0, width)
        with jax.named_scope("dsa_index"):
            ix = lp["indexer"]
            q_i = rotate_first((c_q @ ix["wq"].astype(dtype)).reshape(x.shape[:2] + (J, Di)),
                               safe_pos)
            k_i = (h @ ix["wk"].astype(dtype)).astype(jnp.float32)
            k_i = k_i - jnp.mean(k_i, axis=-1, keepdims=True)
            k_i = k_i * jax.lax.rsqrt(jnp.mean(k_i * k_i, axis=-1, keepdims=True) + INDEX_NORM_EPS)
            k_i = (k_i * ix["k_norm"].astype(jnp.float32)
                   + ix["k_norm_bias"].astype(jnp.float32)).astype(dtype)
            k_i = rotate_first(k_i[:, :, None, :], safe_pos)
            w = jnp.dot(h, ix["weights"].astype(dtype), preferred_element_type=jnp.float32) \
                * (J ** -0.5 * Di ** -0.5)
        # the rows in the order of the pool's leaves: index_keys, latent
        return q, k_i, latent, (q_i, w)

    def finish(lp, x, kept, attn, live):
        return mla_finish(config, lp, x, attn, live, experts, n_group=1, topk_group=1,
                          scaling=config.routed_scaling_factor, scoring="sigmoid",
                          norm_eps=ROUTE_NORM_EPS)

    def head(x):
        return rms_norm(x, params["final_norm"], config.rms_eps) @ params["lm_head"].astype(dtype)

    return transformer.paged_forward(
        [params["dense_layers"], {**moe_layers, "moe": moe}], tokens, n_tokens, start_pos,
        block_tables, kv_cache, block_size=block_size, live_token_bound=live_token_bound,
        last_rows=last_rows, embed=embed, qkv=qkv, finish=finish, head=head,
        softmax_scale=softmax_scale(config), value_dim=config.kv_lora_rank,
        selection=transformer.Selection(PAGED_SELECT_LEAF, lambda lp, kept: kept,
                                        config.index_topk))


def config_from_hf(hf_config) -> GlmMoeDsaConfig:
    """A ``GlmMoeDsaConfig`` from a transformers config of ``model_type``
    ``glm_moe_dsa`` (``num_nextn_predict_layers`` is read past: the module is not loaded)."""
    if getattr(hf_config, "scoring_func", "sigmoid") != "sigmoid" \
            or getattr(hf_config, "topk_method", "noaux_tc") != "noaux_tc" \
            or getattr(hf_config, "n_group", 1) != 1 or getattr(hf_config, "topk_group", 1) != 1:
        raise ValueError("glm_moe_dsa: only sigmoid scoring with noaux_tc top-k over one group is "
                         f"implemented (got {hf_config.scoring_func}, {hf_config.topk_method}, "
                         f"n_group {hf_config.n_group})")
    rope = getattr(hf_config, "rope_parameters", None) or {}
    if rope.get("rope_type", "default") != "default" or not hf_config.rope_interleave \
            or not hf_config.indexer_rope_interleave:
        raise ValueError("glm_moe_dsa: plain rotary over interleaved pairs is implemented "
                         f"(got rope_parameters {rope}, rope_interleave "
                         f"{hf_config.rope_interleave}, indexer_rope_interleave "
                         f"{hf_config.indexer_rope_interleave})")
    return GlmMoeDsaConfig(
        vocab_size=hf_config.vocab_size, hidden_size=hf_config.hidden_size,
        intermediate_size=hf_config.intermediate_size,
        moe_intermediate_size=hf_config.moe_intermediate_size,
        num_layers=hf_config.num_hidden_layers, first_k_dense=hf_config.first_k_dense_replace,
        num_heads=hf_config.num_attention_heads, q_lora_rank=hf_config.q_lora_rank,
        kv_lora_rank=hf_config.kv_lora_rank, qk_nope_head_dim=hf_config.qk_nope_head_dim,
        qk_rope_head_dim=hf_config.qk_rope_head_dim, v_head_dim=hf_config.v_head_dim,
        index_n_heads=hf_config.index_n_heads, index_head_dim=hf_config.index_head_dim,
        index_topk=hf_config.index_topk, num_experts=hf_config.n_routed_experts,
        n_shared_experts=hf_config.n_shared_experts, top_k=hf_config.num_experts_per_tok,
        routed_scaling_factor=float(hf_config.routed_scaling_factor),
        norm_topk_prob=bool(hf_config.norm_topk_prob),
        max_seq_len=hf_config.max_position_embeddings,
        rope_parameters={"rope_theta": rope.get("rope_theta", 1000000), "rope_type": "default"},
        rms_eps=hf_config.rms_norm_eps)
