"""LFM2 mixture-of-experts causal LM (LiquidAI LFM2-24B-A2B ``config.json``,
``model_type: lfm2_moe``; HF ``modeling_lfm2.py`` for the two operators and the
block) — serving only.

A hybrid: ``layer_types`` names each layer's token mixer, and three in four are
not attention.  One block is ``h = x + Op(rms(x))``, ``y = h + FFN(rms(h))``:

- **Gated short convolution** (``conv``): ``[B | C | X] = u W_in``, ``z = B *
  X``, a depth-wise causal filter of ``conv_L_cache`` taps over ``z`` along the
  sequence, ``Op = (C * conv) W_out``.  What a sequence must remember is the
  last ``conv_L_cache - 1`` values of ``z``: a FIXED state a sequence, whatever
  its length.  It lives beside the paged pool (``kv_cache[STATE]``, one leaf,
  one slot a live sequence); ``transformer.paged_forward`` states the contract
  of such layers (``mix``, ``filtered``: the filter local to a sequence, its
  products and sum in float32; the carried leaves) and nothing here knows
  where a step's tokens lie.
- **Attention** (``full_attention``): GQA with an RMSNorm over each head of q
  and of k before rotate-half rotary, no window, over the paged pool.  Heads
  are 64 wide, half a lane tile: ``pack`` KV heads share one 128-wide row of
  the pool (``[L_attn, NB, KV / pack, bs, pack * Dh]``: every byte a value, no
  relayout, the writer and the kernel as they are).  A q head is laid into the
  columns of its own KV head, zeros elsewhere, so its score over a row is its
  score over that head to the bit; of the kernel's output it keeps those
  columns.
- **FFN**: the ``num_dense_layers`` leading layers a dense SwiGLU, every other
  layer ``num_experts`` SwiGLU experts under a float32 router that scores by
  SIGMOID, picks the top-k of ``score + expert_bias`` (the stored bias chooses
  and never weighs) and renormalises the picked scores by ``sum + 1e-6``
  (``moe/serving.py``).  No shared expert.

Parameters are laid out as they are scanned (``layer_segments``): runs of
layers that repeat a pattern are one scan whose body is the pattern, each
position of it a stack ``[repeats, ...]``; the experts are one stack over all
expert layers that a layer indexes, never sliced.

Training and tensor parallelism are not implemented for this family.
"""

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import transformer
from .transformer import STATE, STATE_MIXER, rms_norm, swiglu_mlp

LANE = 128
ROUTER_NORM_EPS = 1e-6  # in the picked scores' sum (Lfm2MoeSparseMoeBlock)
PATTERN = ("conv", "conv", "full_attention", "conv")


@dataclasses.dataclass(frozen=True)
class Lfm2Config:
    vocab_size: int = 65536
    hidden_size: int = 2048
    intermediate_size: int = 11776  # the dense layers' FFN
    moe_intermediate_size: int = 1536  # ONE expert
    num_layers: int = 40
    num_dense_layers: int = 2
    # one of "conv" / "full_attention" a layer; the first ``num_layers`` entries are used
    layer_types: tuple = PATTERN * 10
    num_heads: int = 32
    num_kv_heads: int = 8
    num_experts: int = 64
    top_k: int = 4
    norm_topk_prob: bool = True
    use_expert_bias: bool = True
    routed_scaling_factor: float = 1.0
    conv_L_cache: int = 3
    conv_bias: bool = False
    max_seq_len: int = 128000
    rope_theta: float = 1000000.0
    # HF ``rope_parameters`` (``{"rope_theta", "rope_type": "default"}``), where a config.json has it
    rope_parameters: Optional[tuple] = None
    norm_eps: float = 1e-5
    tie_embeddings: bool = True

    def __post_init__(self):
        object.__setattr__(self, "layer_types", tuple(self.layer_types)[:self.num_layers])
        if isinstance(self.rope_parameters, dict):
            rope = dict(self.rope_parameters)
            if rope.get("rope_type", "default") != "default":
                raise ValueError(f"lfm2: rope_type {rope['rope_type']!r} is not implemented")
            object.__setattr__(self, "rope_theta", float(rope["rope_theta"]))
            object.__setattr__(self, "rope_parameters", tuple(sorted(rope.items())))
        unknown = set(self.layer_types) - {"conv", "full_attention"}
        if unknown or len(self.layer_types) != self.num_layers:
            raise ValueError(f"lfm2: {self.num_layers} layers need as many layer_types of conv / "
                             f"full_attention (got {len(self.layer_types)}, unknown {unknown})")
        if self.conv_bias:
            raise NotImplementedError("lfm2: conv_bias is not implemented (published: false)")

    @staticmethod
    def lfm2_24b_a2b():
        return Lfm2Config()

    @staticmethod
    def tiny(vocab=256, hidden=64, layers=9, dense_layers=1, heads=4, kv_heads=2, experts=8,
             top_k=4, layer_types=("conv", ) + ("full_attention", "conv", "conv", "conv") * 2,
             seq=512):
        return Lfm2Config(vocab_size=vocab, hidden_size=hidden, intermediate_size=hidden * 2,
                          moe_intermediate_size=hidden // 2, num_layers=layers,
                          num_dense_layers=dense_layers, layer_types=layer_types, num_heads=heads,
                          num_kv_heads=kv_heads, num_experts=experts, top_k=top_k, max_seq_len=seq)


def head_dim(config: Lfm2Config) -> int:
    return config.hidden_size // config.num_heads


def kv_pack(config: Lfm2Config) -> int:
    """KV heads that share one row of the pool: as many as fit a lane tile and
    divide the KV heads (2 at the published 8 heads of 64; 1 for heads of 128)."""
    return max(g for g in range(1, config.num_kv_heads + 1)
               if config.num_kv_heads % g == 0 and (g == 1 or g * head_dim(config) <= LANE))


def layer_segments(config: Lfm2Config):
    """``[(start, period, repeats)]``: the layers as runs that repeat a pattern
    of ``period`` layers ``repeats`` times, greedily the longest run from each
    start (a run must repeat at least twice; a layer that starts none is a run
    of one).  A layer's kind is its mixer and whether its FFN is dense, so a
    run never crosses from the dense layers into the expert layers.  Published:
    ``[(0, 1, 2), (2, 4, 9), (38, 1, 1), (39, 1, 1)]``."""
    return transformer.repeating_runs(
        [(kind, i < config.num_dense_layers) for i, kind in enumerate(config.layer_types)])


def init_params(config: Lfm2Config, key, dtype=jnp.float32):
    """``{"embed", "segments": [one tuple of per-position stacks a run of
    :func:`layer_segments`], "experts": [expert layers, E, ...], "final_norm"}``
    (and ``"lm_head"`` where the embedding is not tied).  Projections, experts
    and router at 1/sqrt(fan_in) (router logits of unit scale: sigmoid scores
    that differ), the filter's taps at 1/sqrt(taps), the expert bias normal(0,
    0.64 / num_experts): about half the gap between a token's fourth and fifth
    score at 64 experts, enough to change picks without unbalancing the loads."""
    D, dh = config.hidden_size, head_dim(config)
    n_moe = config.num_layers - config.num_dense_layers
    k_emb, k_layers, k_experts, k_head = jax.random.split(key, 4)

    def stack(key, depth, *shape):
        return jax.random.normal(key, (depth, ) + shape, dtype) * float(shape[-2]) ** -0.5

    def ffn(key, depth, width, *lead):
        ks = jax.random.split(key, 3)
        return {"w_gate": stack(ks[0], depth, *lead, D, width),
                "w_up": stack(ks[1], depth, *lead, D, width),
                "w_down": stack(ks[2], depth, *lead, width, D)}

    def position(key, depth, kind, dense):
        """One position of a run's pattern: ``depth`` layers of one kind, stacked."""
        ks = jax.random.split(key, 8)
        lp = {"op_norm": jnp.ones((depth, D), dtype), "ffn_norm": jnp.ones((depth, D), dtype)}
        if kind == "conv":
            lp[STATE_MIXER] = {"w_in": stack(ks[0], depth, D, 3 * D),
                               "filter": jax.random.normal(ks[1], (depth, config.conv_L_cache, D),
                                                           dtype) * config.conv_L_cache ** -0.5,
                               "w_out": stack(ks[2], depth, D, D)}
        else:
            lp["attn"] = {"wq": stack(ks[0], depth, D, config.num_heads * dh),
                          "wk": stack(ks[1], depth, D, config.num_kv_heads * dh),
                          "wv": stack(ks[2], depth, D, config.num_kv_heads * dh),
                          "wo": stack(ks[3], depth, config.num_heads * dh, D),
                          "q_norm": jnp.ones((depth, dh), dtype),
                          "k_norm": jnp.ones((depth, dh), dtype)}
        if dense:
            lp["mlp"] = ffn(ks[4], depth, config.intermediate_size)
        else:
            lp["moe"] = {"gate": {"wg": stack(ks[5], depth, D, config.num_experts)}}
            if config.use_expert_bias:
                lp["moe"]["gate"]["bias"] = jax.random.normal(
                    ks[6], (depth, config.num_experts), dtype) * (0.64 / config.num_experts)
        return lp

    segments = []
    for start, period, repeats in layer_segments(config):
        keys = jax.random.split(jax.random.fold_in(k_layers, start), period)
        segments.append(tuple(
            position(keys[j], repeats, config.layer_types[start + j],
                     start + j < config.num_dense_layers) for j in range(period)))
    params = {"embed": jax.random.normal(k_emb, (config.vocab_size, D), dtype) * 0.02,
              "segments": segments,
              "experts": ffn(k_experts, n_moe, config.moe_intermediate_size, config.num_experts),
              "final_norm": jnp.ones((D, ), dtype)}
    if not config.tie_embeddings:
        params["lm_head"] = transformer.init_linear(k_head, D, config.vocab_size, dtype=dtype)
    return params


# --------------------------------------------------------- paged (ragged) serve
def init_paged_cache(config: Lfm2Config, num_blocks: int, block_size: int, dtype=jnp.bfloat16,
                     state_slots: int = 32):
    """The KV pool of the ATTENTION layers alone, ``pack`` heads a row in whole
    lanes, and under ``STATE`` the conv layers' state ``[L_conv, state_slots +
    1, conv_L_cache - 1, D]``: a live sequence's slot holds its last values of
    ``z`` a conv layer; the last slot takes a dead row's writes."""
    kinds = config.layer_types
    pack = kv_pack(config)
    cache = transformer.init_paged_kv_pool(
        kinds.count("full_attention"), config.num_kv_heads // pack, pack * head_dim(config),
        num_blocks, block_size, dtype)
    cache[STATE] = jnp.zeros((kinds.count("conv"), state_slots + 1, config.conv_L_cache - 1,
                              config.hidden_size), dtype)
    return cache


def state_bytes_per_seq(config: Lfm2Config, value_bytes: int = 2) -> int:
    """What one live sequence holds outside the paged pool, whatever its
    length: ``conv_L_cache - 1`` values of ``z`` a conv layer (57,344 B at 7
    conv layers of 2048 in bfloat16).  The engine reads a family's state off
    this function's presence."""
    return (config.layer_types.count("conv") * (config.conv_L_cache - 1) * config.hidden_size
            * value_bytes)


def moe_picks_per_token(config: Lfm2Config) -> int:
    return config.top_k * (config.num_layers - config.num_dense_layers)


def moe_expert_rows(config: Lfm2Config, slots: int) -> int:
    from ..moe.serving import expert_rows
    return expert_rows(slots, config.top_k) * (config.num_layers - config.num_dense_layers)


def rotate_half(x, positions, inv_freq):
    """x [b, s, heads, d]: pairs ``(i, i + d/2)`` rotated by ``positions *
    inv_freq[i]``, angles in float32 (a table of 128,000 rows would sit in
    every program)."""
    angle = positions.astype(jnp.float32)[..., None, None] * jnp.asarray(inv_freq)
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).astype(x.dtype)


def forward_paged(config: Lfm2Config, params, tokens, n_tokens, start_pos, block_tables,
                  kv_cache, *, block_size: int, tp_axis: Optional[str] = None,
                  gather_logits: bool = True, live_token_bound: Optional[int] = None,
                  last_rows: bool = False):
    """Ragged chunked forward (``transformer.paged_forward`` states the
    contract): the conv layers through ``mix`` and their sequences' state, the
    attention layers over the packed pool, dense and expert FFNs."""
    from ..moe.serving import sparse_moe_ffn
    if tp_axis is not None:
        raise NotImplementedError("lfm2: tensor-parallel serving is not implemented")
    D, H, KV, dh = config.hidden_size, config.num_heads, config.num_kv_heads, head_dim(config)
    pack = kv_pack(config)
    dtype = kv_cache["k"].dtype
    inv_freq = (config.rope_theta ** -(np.arange(0, dh, 2, dtype=np.float32) / dh))
    # q head i reads KV head i // (H / KV), which lies in columns (that % pack) * dh of its row
    own = np.eye(pack, dtype=np.float32)[(np.arange(H) // (H // KV)) % pack]  # [H, pack]
    experts = params["experts"]

    def ffn(lp, h, live):
        if "mlp" in lp:
            return swiglu_mlp(lp["mlp"], h)
        out = sparse_moe_ffn({"gate": lp["moe"]["gate"], "experts": experts},
                             h.reshape(-1, D), config.top_k, config.norm_topk_prob,
                             live.reshape(-1), layer=lp["moe"]["layer"],
                             scaling=config.routed_scaling_factor, scoring="sigmoid",
                             norm_eps=ROUTER_NORM_EPS)
        return out.reshape(h.shape)

    def block_ffn(lp, x, live):
        return x + ffn(lp, rms_norm(x, lp["ffn_norm"], config.norm_eps), live)

    def embed(tokens, safe_pos):
        return params["embed"][tokens].astype(dtype)

    def mix(lp, x, filtered, live, kept, places):
        m = lp[STATE_MIXER]
        u = rms_norm(x, lp["op_norm"], config.norm_eps)
        with jax.named_scope("conv_mixer"):
            b, c, xs = jnp.split(u @ m["w_in"].astype(dtype), 3, axis=-1)
            z = b * xs
            conv, last = filtered(z, kept, m["filter"])  # float32; the state's one leaf is this shift's
            x = x + (c * conv).astype(dtype) @ m["w_out"].astype(dtype)
        return block_ffn(lp, x, live), last

    def qkv(lp, x, safe_pos):
        a = lp["attn"]
        u = rms_norm(x, lp["op_norm"], config.norm_eps)
        q, k, v = ((u @ a[w].astype(dtype)).reshape(x.shape[:2] + (-1, dh))
                   for w in ("wq", "wk", "wv"))
        q = rotate_half(rms_norm(q, a["q_norm"], config.norm_eps), safe_pos, inv_freq)
        k = rotate_half(rms_norm(k, a["k_norm"], config.norm_eps), safe_pos, inv_freq)
        # pack KV heads a row; q into its own head's columns of the row, zeros elsewhere
        q = (q[..., None, :] * jnp.asarray(own, dtype)[:, :, None]).reshape(
            x.shape[:2] + (H, pack * dh))
        rows = x.shape[:2] + (KV // pack, pack * dh)
        return q, k.reshape(rows), v.reshape(rows), None

    def finish(lp, x, kept, attn, live):
        # of a row's pack * dh output columns a q head keeps its own KV head's
        heads = jnp.einsum("bshpd,hp->bshd", attn.reshape(x.shape[:2] + (H, pack, dh)),
                           jnp.asarray(own, dtype))
        x = x + heads.reshape(x.shape[:2] + (H * dh, )) @ lp["attn"]["wo"].astype(dtype)
        return block_ffn(lp, x, live)

    def head(x):
        w = params["embed"].T if config.tie_embeddings else params["lm_head"]
        return rms_norm(x, params["final_norm"], config.norm_eps) @ w.astype(dtype)

    # each expert layer is handed its index into the one stack of experts
    layers, moe_at = [], 0
    for (start, period, repeats), segment in zip(layer_segments(config), params["segments"]):
        positions = []
        for lp in segment:
            if "moe" in lp:
                lp = {**lp, "moe": {**lp["moe"], "layer": moe_at + jnp.arange(
                    0, repeats * period, period, dtype=jnp.int32)}}
                moe_at += 1
            positions.append(lp)
        moe_at += (repeats - 1) * sum("moe" in lp for lp in segment)
        layers.append(tuple(positions))
    return transformer.paged_forward(
        layers, tokens, n_tokens, start_pos, block_tables, kv_cache, block_size=block_size,
        live_token_bound=live_token_bound, last_rows=last_rows, embed=embed, qkv=qkv, finish=finish,
        head=head, mix=mix, softmax_scale=dh ** -0.5)
