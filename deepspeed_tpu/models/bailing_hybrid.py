"""Ling-3.0 causal LM (``Ling-3.0-flash`` ``config.json``, ``model_type:
bailing_hybrid``; Kimi Linear, arXiv:2510.26692, and FLA's ``fla/ops/kda`` for
Kimi Delta Attention and its gate, DeepSeek-V2 for the latent attention,
DeepSeek-V3 for the router) — serving only.

A hybrid: layer ``i`` is latent attention (MLA) where ``(i + 1) %
layer_group_size == 0`` and Kimi Delta Attention (KDA) otherwise (published: five
KDA, one MLA).  One block is ``a = x + Mixer(rms(x))``, ``y = a + FFN(rms(a))``,
every ``rms`` a plain gain; an untied head; no biases.

- **KDA** (the layer's parameters hold ``STATE_MIXER``; ``H`` heads of ``dh`` for
  keys and values alike): ``[q | k | v] = u [W_q | W_k | W_v]``; a depth-wise
  causal filter of ``short_conv_kernel_size`` taps without a bias over all ``3 H
  dh`` columns, then SiLU; ``q`` and ``k`` l2-normalised a head, ``q`` times
  ``dh^-1/2``; ``beta = sigmoid(u W_beta)`` one a head; the decay a head a
  CHANNEL: ``f = u W_f`` (full rank), ``g = kda_lower_bound x sigmoid(exp(A_log)
  (f + dt_bias))`` with ``A_log`` one a head and ``dt_bias`` one a channel, so
  ``kda_lower_bound < g < 0``; then the recurrence of
  ``ops/linear_attention/kda.py`` (a head's memory one matrix ``S`` ``[dh, dh]``),

      S <- diag(exp(g_t)) S;  d = beta_t (v_t - S^T k_t);  S <- S + k_t d^T;  o_t = S^T q_t

  ``rms`` over each head's values with a gain, times ``sigmoid(u W_g)`` with
  ONE gate a head, through ``W_o``.  What a sequence remembers a layer,
  whatever its length: ``S`` of every head in float32 (2 MB at 32 heads of
  128 x 128) and the last ``taps - 1`` rows of ``[q | k | v]`` before the
  filter.  Both are leaves of ``kv_cache[STATE]``, one slot a live sequence,
  beside the pool; ``transformer.paged_forward`` (which states the contract)
  hands ``mix`` the shift's rows BY VALUE, with the filter over them local to a
  sequence (``filtered``: no shifted copy of the columns), and the matrices BY REFERENCE
  (``STATE_BY_REFERENCE``: ``kda_step`` and ``kda_scan`` index the rows' slots
  of the carried leaf themselves).  Nothing here computes a slot.
- **MLA** (``q_lora_rank`` null: a full-rank query): DeepSeek-V2's absorbed
  latent attention over ONE pool leaf (``deepseek_v2.mla_qkv`` / ``mla_out``),
  rotary over DeepSeek's interleaved pairs, the heads' outputs times
  ``sigmoid(u W_g)``, one gate a head as above.  The pool's rows are counted over
  the MLA layers alone.
- **FFN**: a dense SwiGLU in the first ``first_k_dense`` layers; after them
  ``num_experts`` SwiGLU experts under a float32 sigmoid router with a selection
  bias and DeepSeek-V3's group-limited choice (``moe/serving.py route``: a
  group's score is the sum of its two best biased scores), the picked scores
  renormalised and times ``routed_scaling_factor``, plus one shared expert added
  whole.  ``num_local_experts`` of the experts' weights may be here (this chip's
  share of an expert-parallel deployment); only ``init_params`` reads that
  count, the forward reads the shapes.

Parameters are laid out as they are scanned (``layer_segments``, as
``models/qwen3_next.py``); the experts are one stack over the expert layers.
Refused (``__post_init__``), not guessed: a clamp on an expert's SwiGLU
(``expert_swiglu_limit_list`` non-zero), a low-rank decay projection
(``use_kda_lora``), an unbounded gate (``kda_safe_gate`` false), ``use_mla_nope``,
``value_norm``, ``up_proj_norm``, ``use_nGPT``, ``scale_router_input``, a
``q_lora_rank``, a ``rope_scaling``, biases, the multi-token-prediction layer.
Training and tensor parallelism are not implemented.
"""

import dataclasses
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import transformer
from .deepseek_v2 import latent_width, mla_out, mla_qkv
from .qwen3_next import DECAY_RATES, l2norm
from .transformer import STATE, STATE_MIXER, rms_norm, swiglu_mlp

KDA, MLA = "kda", "mla"
GATE_SLOPES = (0.8, 1.25)  # ``init_params``: exp(A_log) of a layer's first and last head
ROUTER_BIAS = 0.03         # ``init_params``: the deviation of the router's selection bias


@dataclasses.dataclass(frozen=True)
class BailingHybridConfig:
    vocab_size: int = 157184
    hidden_size: int = 2560
    num_layers: int = 42
    layer_group_size: int = 6  # every layer_group_size-th layer is MLA, the others KDA
    first_k_dense: int = 2
    num_heads: int = 32
    num_kv_heads: int = 32
    head_dim: int = 128
    kda_kv_heads: int = 0  # num_kv_heads_for_linear_attn: 0 = as many as query heads
    short_conv_kernel_size: int = 4
    kda_lower_bound: float = -5.0
    kda_safe_gate: bool = True
    use_kda_lora: bool = False
    linear_silu: bool = True
    use_qk_norm: bool = True
    group_norm_size: int = 1
    gate_granularity: str = "head_wise"
    q_lora_rank: Optional[int] = None
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 6e6
    rope_interleave: bool = True
    rope_scaling: Optional[dict] = None
    intermediate_size: int = 6144
    moe_intermediate_size: int = 768
    shared_intermediate_size: int = 768
    num_shared_experts: int = 1
    # the router's width; None: n_group x num_local_experts (the deployment this family is cut
    # for holds one group a chip)
    num_experts: Optional[int] = None
    # experts whose weights are here: None = all; fewer = this chip's share of an
    # expert-parallel deployment, from expert 0.  Only ``init_params`` reads it.
    num_local_experts: Optional[int] = None
    top_k: int = 8
    n_group: int = 8
    topk_group: int = 4
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    score_function: str = "sigmoid"
    topk_method: str = "noaux_tc"
    router_bias: bool = True
    scale_router_input: bool = False
    expert_swiglu_limits: tuple = ()
    shared_swiglu_limits: tuple = ()
    use_mla_nope: bool = False
    value_norm: bool = False
    up_proj_norm: bool = False
    use_ngpt: bool = False
    use_bias: bool = False
    use_qkv_bias: bool = False
    tie_embeddings: bool = False
    hidden_act: str = "silu"
    max_seq_len: int = 262144
    rms_eps: float = 1e-6

    def __post_init__(self):
        from ..ops.linear_attention.kda import LOWER_BOUND
        for name in ("expert_swiglu_limits", "shared_swiglu_limits"):
            object.__setattr__(self, name, tuple(getattr(self, name) or ())[:self.num_layers])
        if self.num_experts is None:
            object.__setattr__(self, "num_experts", 512 if self.num_local_experts is None
                               else self.n_group * self.num_local_experts)
        for what, wrong in (
                ("a clamp on an expert's SwiGLU (a non-zero expert_swiglu_limit_list or "
                 "share_expert_swiglu_limit_list entry)",
                 any(self.expert_swiglu_limits) or any(self.shared_swiglu_limits)),
                ("use_kda_lora (a low-rank decay projection)", self.use_kda_lora),
                (f"kda_safe_gate false or kda_lower_bound {self.kda_lower_bound} under {LOWER_BOUND}",
                 not self.kda_safe_gate or not LOWER_BOUND <= self.kda_lower_bound < 0),
                ("linear_silu false", not self.linear_silu), ("use_qk_norm false", not self.use_qk_norm),
                (f"group_norm_size {self.group_norm_size}", self.group_norm_size != 1),
                (f"gated_attention_proj_granularity_type {self.gate_granularity!r}",
                 self.gate_granularity != "head_wise"),
                (f"num_kv_heads_for_linear_attn {self.kda_kv_heads}",
                 self.kda_kv_heads not in (0, self.num_heads)),
                (f"num_key_value_heads {self.num_kv_heads}", self.num_kv_heads != self.num_heads),
                ("a q_lora_rank", self.q_lora_rank is not None),
                ("a rope_scaling", self.rope_scaling is not None),
                ("rope_interleave false", not self.rope_interleave),
                ("use_mla_nope", self.use_mla_nope), ("value_norm", self.value_norm),
                ("up_proj_norm", self.up_proj_norm), ("use_nGPT", self.use_ngpt),
                ("scale_router_input", self.scale_router_input),
                ("use_bias", self.use_bias), ("use_qkv_bias", self.use_qkv_bias),
                ("a tied head", self.tie_embeddings),
                (f"hidden_act {self.hidden_act!r}", self.hidden_act != "silu"),
                (f"score_function {self.score_function!r} / topk_method {self.topk_method!r} / "
                 f"moe_router_enable_expert_bias {self.router_bias}",
                 (self.score_function, self.topk_method, self.router_bias)
                 != ("sigmoid", "noaux_tc", True)),
                (f"num_shared_experts {self.num_shared_experts}", self.num_shared_experts != 1),
                (f"{self.num_experts} experts in {self.n_group} groups",
                 self.num_experts % self.n_group != 0)):
            if wrong:
                raise NotImplementedError(f"bailing_hybrid: {what} is not implemented")

    @property
    def layer_types(self):
        """``(KDA | MLA)`` a layer, from ``layer_group_size``."""
        return tuple(MLA if (i + 1) % self.layer_group_size == 0 else KDA
                     for i in range(self.num_layers))

    @staticmethod
    def ling_3_0_flash():
        return BailingHybridConfig()

    @staticmethod
    def tiny(vocab=256, hidden=64, layers=6, group=3, first_k_dense=1, heads=4, head_dim=16,
             experts=16, local_experts=None, n_group=4, topk_group=2, top_k=4, seq=512):
        return BailingHybridConfig(
            vocab_size=vocab, hidden_size=hidden, num_layers=layers, layer_group_size=group,
            first_k_dense=first_k_dense, num_heads=heads, num_kv_heads=heads, head_dim=head_dim,
            kv_lora_rank=2 * head_dim, qk_nope_head_dim=head_dim, qk_rope_head_dim=head_dim // 2,
            v_head_dim=head_dim, rope_theta=1e4, intermediate_size=2 * hidden,
            moe_intermediate_size=hidden // 2, shared_intermediate_size=hidden // 2,
            num_experts=experts, num_local_experts=local_experts, n_group=n_group,
            topk_group=topk_group, top_k=top_k, max_seq_len=seq)


def layer_kinds(config: BailingHybridConfig):
    """``[(KDA | MLA, dense FFN?)]`` a layer."""
    return [(kind, i < config.first_k_dense) for i, kind in enumerate(config.layer_types)]


def layer_segments(config: BailingHybridConfig):
    """``[(start, period, repeats)]``: the layers as runs that repeat a pattern
    (``transformer.repeating_runs``).  Twelve layers: ``[(0, 1, 2), (2, 1, 3), (5, 1, 1), (6,
    1, 5), (11, 1, 1)]``; published: ``[(0, 1, 2), (2, 6, 6), (38, 1, 3),
    (41, 1, 1)]``, six periods of (KDA x 3, MLA, KDA x 2) in one scan."""
    return transformer.repeating_runs(layer_kinds(config))


def init_params(config: BailingHybridConfig, key, dtype=jnp.float32):
    """``{"embed", "segments": [one tuple of per-position stacks a run of
    :func:`layer_segments`], "experts": [expert layers, held, ...], "final_norm",
    "lm_head"}``.  Projections, experts and router at 1/sqrt(fan_in), the filter's
    taps at 1/sqrt(taps), gains at one, the router's selection bias normal(0,
    ``ROUTER_BIAS``) in float32 (it chooses, and the loads do not follow the seed), a
    routed ``W_down`` over ``top_k``.  THE DECAY: ``dt_bias`` is the logit of ``rate /
    -kda_lower_bound`` for rates log-spaced over a head's CHANNELS between
    ``DECAY_RATES`` and ``exp(A_log)`` log-spaced over a layer's HEADS between
    ``GATE_SLOPES``: at ``f = 0`` a channel's decay a token runs from about 0.999 to 0.9
    (a trained model's heads remember over tens to thousands of tokens), none at the
    bound, and every token's differs (``f`` is of unit scale)."""
    d, h, dh = config.hidden_size, config.num_heads, config.head_dim
    qk = config.qk_nope_head_dim + config.qk_rope_head_dim
    held = config.num_local_experts or config.num_experts
    taps, kinds = config.short_conv_kernel_size, layer_kinds(config)
    k_emb, k_head, k_layers, k_experts = jax.random.split(key, 4)

    def stack(key, *shape):
        return jax.random.normal(key, shape, dtype) * float(shape[-2]) ** -0.5

    def ffn(key, width, *lead):
        ks = jax.random.split(key, 3)
        return {"w_gate": stack(ks[0], *lead, d, width), "w_up": stack(ks[1], *lead, d, width),
                "w_down": stack(ks[2], *lead, width, d)}

    def position(key, depth, kind, dense):
        ks = jax.random.split(key, 10)
        lp = {"op_norm": jnp.ones((depth, d), dtype), "ffn_norm": jnp.ones((depth, d), dtype)}
        if kind == KDA:
            rates = np.exp(np.linspace(math.log(DECAY_RATES[0]), math.log(DECAY_RATES[1]), dh))
            lp[STATE_MIXER] = {
                "w_qkv": stack(ks[0], depth, d, 3 * h * dh),
                "filter": jax.random.normal(ks[1], (depth, taps, 3 * h * dh), dtype) * float(taps) ** -0.5,
                "w_beta": stack(ks[2], depth, d, h), "w_f": stack(ks[3], depth, d, h * dh),
                "A_log": jnp.broadcast_to(jnp.asarray(np.linspace(
                    math.log(GATE_SLOPES[0]), math.log(GATE_SLOPES[1]), h), dtype), (depth, h)),
                "dt_bias": jnp.broadcast_to(jnp.asarray(np.tile(
                    np.log(rates / (-config.kda_lower_bound - rates)), h), dtype), (depth, h * dh)),
                "norm": jnp.ones((depth, dh), dtype), "w_gate": stack(ks[4], depth, d, h),
                "w_out": stack(ks[5], depth, h * dh, d)}
        else:
            lp["attn"] = {"wq": stack(ks[0], depth, d, h * qk),
                          "wkv_a": stack(ks[1], depth, d, config.kv_lora_rank + config.qk_rope_head_dim),
                          "kv_norm": jnp.ones((depth, config.kv_lora_rank), dtype),
                          "wkv_b": stack(ks[2], depth, config.kv_lora_rank,
                                         h * (config.qk_nope_head_dim + config.v_head_dim)),
                          "w_gate": stack(ks[4], depth, d, h),
                          "wo": stack(ks[5], depth, h * config.v_head_dim, d)}
        if dense:
            lp["mlp"] = ffn(ks[6], config.intermediate_size, depth)
        else:
            lp["moe"] = {"gate": {"wg": stack(ks[6], depth, d, config.num_experts),
                                  "bias": jax.random.normal(ks[7], (depth, config.num_experts),
                                                            jnp.float32) * ROUTER_BIAS},
                         "shared": ffn(ks[8], config.shared_intermediate_size, depth)}
        return lp

    segments = []
    for start, period, repeats in layer_segments(config):
        keys = jax.random.split(jax.random.fold_in(k_layers, start), period)
        segments.append(tuple(position(keys[j], repeats, *kinds[start + j]) for j in range(period)))
    experts = ffn(k_experts, config.moe_intermediate_size, config.num_layers - config.first_k_dense,
                  held)
    experts["w_down"] = experts["w_down"] / config.top_k
    return {"embed": jax.random.normal(k_emb, (config.vocab_size, d), dtype) * 0.02,
            "segments": segments, "experts": experts, "final_norm": jnp.ones((d, ), dtype),
            "lm_head": stack(k_head, d, config.vocab_size)}


# --------------------------------------------------------- paged (ragged) serve
# Which leaves of ``kv_cache[STATE]`` ``paged_forward`` hands ``mix`` by reference: what the
# kernels of ``ops/linear_attention/kda.py`` take whole, with the rows' slots.
STATE_BY_REFERENCE = {"conv": False, "recurrent": True}


def init_paged_cache(config: BailingHybridConfig, num_blocks: int, block_size: int,
                     dtype=jnp.bfloat16, state_slots: int = 32):
    """The latent pool of the MLA layers alone, ONE leaf ``[L_mla, NB, 1, bs,
    latent_width]`` (a token's ``[c_kv | k_pe]`` in whole lanes, as
    ``deepseek_v2``'s), and, under ``STATE``, the KDA layers' two leaves,
    ``state_slots`` slots and a trash slot each: ``conv`` ``[L_kda, slots + 1,
    taps - 1, 3 H dh]`` in the pool's dtype (the last rows of ``[q | k | v]``
    before the filter) and ``recurrent`` ``[L_kda, slots + 1, H, dh, dh]`` in
    FLOAT32 whatever the pool's dtype."""
    kinds = config.layer_types
    h, dh = config.num_heads, config.head_dim
    kda, slots = kinds.count(KDA), state_slots + 1
    return {"latent": jnp.zeros((kinds.count(MLA), num_blocks, 1, block_size,
                                 latent_width(config)), dtype),
            STATE: {"conv": jnp.zeros((kda, slots, config.short_conv_kernel_size - 1, 3 * h * dh),
                                      dtype),
                    "recurrent": jnp.zeros((kda, slots, h, dh, dh), jnp.float32)}}


def paged_value_dim(config: BailingHybridConfig) -> int:
    """The value's width inside the one cached vector (``deepseek_v2.paged_value_dim``)."""
    return config.kv_lora_rank


def state_bytes_per_seq(config: BailingHybridConfig, value_bytes: int = 2) -> int:
    """What one live sequence holds outside the paged pool, whatever its length:
    a KDA layer's float32 matrix a head and ``taps - 1`` rows of the filter's
    input (2,097,152 + 73,728 B published; 21.7 MB at 10 such layers).  The
    engine reads a family's state off this function."""
    h, dh = config.num_heads, config.head_dim
    a_layer = (config.short_conv_kernel_size - 1) * 3 * h * dh * value_bytes + h * dh * dh * 4
    return config.layer_types.count(KDA) * a_layer


def state_scan(config: BailingHybridConfig):
    """``(chunks(n, t, flat, walked), positions a chunk, layers, trips(walked))``
    for the serving counters, as ``granite_moe_hybrid.state_scan``: the KDA
    layers' scans share ``ssd.py``'s layouts, and a row of one token leaves the
    walk for the update."""
    from ..ops.linear_attention.ssd import CHUNK, scan_chunks, walk_trips
    layers = config.layer_types.count(KDA)
    return ((lambda n, t, flat, walked: scan_chunks(n, t, flat, walked) * layers), CHUNK, layers,
            walk_trips)


def moe_picks_per_token(config: BailingHybridConfig) -> int:
    return config.top_k * (config.num_layers - config.first_k_dense)


def moe_expert_rows(config: BailingHybridConfig, slots: int) -> int:
    """Rows the expert layers' grouped matmuls of one pass over ``slots`` token slots run
    over: on a share the window its held picks are compacted into, the first trip's."""
    from ..moe.serving import expert_rows
    held = config.num_local_experts or config.num_experts
    return expert_rows(slots, config.top_k, held, config.num_experts) \
        * (config.num_layers - config.first_k_dense)


def scanned_layers(config: BailingHybridConfig, params):
    """``params["segments"]`` as ``transformer.paged_forward`` takes ``layers``: an
    expert layer with its index into the one stack of experts."""
    layers = []
    for (start, period, repeats), segment in zip(layer_segments(config), params["segments"]):
        layers.append(tuple(
            {**lp, "moe": {**lp["moe"], "layer": start + j - config.first_k_dense + jnp.arange(
                0, repeats * period, period, dtype=jnp.int32)}} if "moe" in lp else lp
            for j, lp in enumerate(segment)))
    return layers


def forward_paged(config: BailingHybridConfig, params, tokens, n_tokens, start_pos, block_tables,
                  kv_cache, *, block_size: int, tp_axis: Optional[str] = None,
                  gather_logits: bool = True, live_token_bound: Optional[int] = None,
                  last_rows: bool = False):
    """Ragged chunked forward (``transformer.paged_forward`` states the
    contract): the KDA layers through ``mix`` and their sequences' carried
    leaves, the MLA layers over the latent pool, the dense or expert FFN."""
    from ..moe.serving import sparse_moe_ffn
    from ..ops.linear_attention import kda_chunks, kda_step
    if tp_axis is not None:
        raise NotImplementedError("bailing_hybrid: tensor-parallel serving is not implemented")
    D, H, dh, eps = config.hidden_size, config.num_heads, config.head_dim, config.rms_eps
    dtype = kv_cache["latent"].dtype
    width = kv_cache["latent"].shape[-1]
    rope = config.qk_rope_head_dim
    inv_freq = (1.0 / config.rope_theta ** (np.arange(0, rope, 2) / rope)).astype(np.float32)  # host arithmetic
    experts = params["experts"]

    def block_ffn(lp, x, live):
        h = rms_norm(x, lp["ffn_norm"], eps)
        if "moe" not in lp:
            return x + swiglu_mlp(lp["mlp"], h)
        moe = lp["moe"]
        out = sparse_moe_ffn({"gate": moe["gate"], "experts": experts, "shared": moe["shared"]},
                             h.reshape(-1, D), config.top_k, config.norm_topk_prob, live.reshape(-1),
                             layer=moe["layer"], n_group=config.n_group,
                             topk_group=config.topk_group, scaling=config.routed_scaling_factor,
                             scoring="sigmoid")
        return x + out.reshape(x.shape)

    def embed(tokens, safe_pos):
        return params["embed"][tokens].astype(dtype)

    def head_gate(u, w):  # one gate a head, float32: its sigmoid scales the head's output
        return jnp.dot(u, w.astype(dtype), preferred_element_type=jnp.float32)

    def mix(lp, x, filtered, live, carried, places):
        m = lp[STATE_MIXER]
        u = rms_norm(x, lp["op_norm"], eps)
        lead = x.shape[:2]
        with jax.named_scope("kda_mixer"):
            mixed = u @ m["w_qkv"].astype(dtype)
            with jax.named_scope("kda_state"):
                conv, last = filtered(mixed, carried["conv"], m["filter"])
            conv = jax.nn.silu(conv)
            q, k, v = (conv[..., i * H * dh:(i + 1) * H * dh].reshape(lead + (H, dh)) for i in range(3))
            q, k, v = (l2norm(q) * dh ** -0.5).astype(dtype), l2norm(k).astype(dtype), v.astype(dtype)
            with jax.named_scope("kda_gate"):
                beta = jax.nn.sigmoid(head_gate(u, m["w_beta"]))
                f = head_gate(u, m["w_f"]) + m["dt_bias"].astype(jnp.float32)
                g = config.kda_lower_bound * jax.nn.sigmoid(
                    jnp.exp(m["A_log"].astype(jnp.float32))[:, None] * f.reshape(lead + (H, dh)))
            ref = carried["recurrent"]  # a ``StateRef``: the kernels' state arguments, and the trash slot
            if places.row is None and x.shape[1] == 1:  # a decode row, a burst's step
                with jax.named_scope("kda_update"), jax.named_scope("kda_state"):
                    o, state = kda_step(q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0], ref.leaf,
                                        ref.at, ref.begins)
                o = o[:, None]
            else:  # a pass of chunks: the scan for the rows of several tokens, the update for the rest
                o, state = kda_chunks(q, k, v, g, beta, *ref, *places)
            o = rms_norm(o.astype(jnp.float32), m["norm"], eps)  # a head's values, then its gate
            o = (o * jax.nn.sigmoid(head_gate(u, m["w_gate"]))[..., None]).astype(dtype)
            x = x + o.reshape(lead + (H * dh, )) @ m["w_out"].astype(dtype)
        return block_ffn(lp, x, live), {"conv": last, "recurrent": state}

    def qkv(lp, x, safe_pos):
        u = rms_norm(x, lp["op_norm"], eps)
        q, latent, _ = mla_qkv(config, lp["attn"], u, safe_pos, inv_freq, 1.0, width)
        return q, latent, u

    def finish(lp, x, u, attn, live):
        x = x + mla_out(config, lp["attn"], attn, head_gate(u, lp["attn"]["w_gate"]))
        return block_ffn(lp, x, live)

    def head(x):
        return rms_norm(x, params["final_norm"], eps) @ params["lm_head"].astype(dtype)

    return transformer.paged_forward(
        scanned_layers(config, params), tokens, n_tokens, start_pos, block_tables, kv_cache,
        block_size=block_size, live_token_bound=live_token_bound, last_rows=last_rows, embed=embed,
        qkv=qkv, finish=finish, head=head, mix=mix, by_reference=STATE_BY_REFERENCE,
        softmax_scale=(config.qk_nope_head_dim + config.qk_rope_head_dim) ** -0.5,
        value_dim=config.kv_lora_rank)


def config_from_hf(hf_config) -> BailingHybridConfig:
    """A ``BailingHybridConfig`` from a transformers config of ``model_type``
    ``bailing_hybrid`` (``num_nextn_predict_layers`` is read past: the module is
    not loaded); what the family does not implement raises in ``__post_init__``."""
    get = lambda name, default=None: getattr(hf_config, name, default)
    return BailingHybridConfig(
        vocab_size=hf_config.vocab_size, hidden_size=hf_config.hidden_size,
        num_layers=hf_config.num_hidden_layers, layer_group_size=hf_config.layer_group_size,
        first_k_dense=hf_config.first_k_dense_replace, num_heads=hf_config.num_attention_heads,
        num_kv_heads=hf_config.num_key_value_heads, head_dim=hf_config.head_dim,
        kda_kv_heads=get("num_kv_heads_for_linear_attn", 0),
        short_conv_kernel_size=hf_config.short_conv_kernel_size,
        kda_lower_bound=float(hf_config.kda_lower_bound), kda_safe_gate=bool(hf_config.kda_safe_gate),
        use_kda_lora=bool(get("use_kda_lora", False)) or not get("no_kda_lora", True),
        linear_silu=bool(get("linear_silu", True)), use_qk_norm=bool(get("use_qk_norm", True)),
        group_norm_size=get("group_norm_size", 1),
        gate_granularity=get("gated_attention_proj_granularity_type", "head_wise"),
        q_lora_rank=get("q_lora_rank"), kv_lora_rank=hf_config.kv_lora_rank,
        qk_nope_head_dim=hf_config.qk_nope_head_dim, qk_rope_head_dim=hf_config.qk_rope_head_dim,
        v_head_dim=hf_config.v_head_dim, rope_theta=float(hf_config.rope_theta),
        rope_interleave=bool(get("rope_interleave", True)), rope_scaling=get("rope_scaling"),
        intermediate_size=hf_config.intermediate_size,
        moe_intermediate_size=hf_config.moe_intermediate_size,
        shared_intermediate_size=hf_config.moe_shared_expert_intermediate_size,
        num_shared_experts=hf_config.num_shared_experts, num_experts=hf_config.num_experts,
        top_k=hf_config.num_experts_per_tok, n_group=hf_config.n_group,
        topk_group=hf_config.topk_group, routed_scaling_factor=float(hf_config.routed_scaling_factor),
        norm_topk_prob=bool(hf_config.norm_topk_prob),
        score_function=get("score_function", "sigmoid"), topk_method=get("topk_method", "noaux_tc"),
        router_bias=bool(get("moe_router_enable_expert_bias", True)),
        scale_router_input=bool(get("scale_router_input", False)),
        expert_swiglu_limits=tuple(get("expert_swiglu_limit_list") or ()),
        shared_swiglu_limits=tuple(get("share_expert_swiglu_limit_list") or ()),
        use_mla_nope=bool(get("use_mla_nope", False)), value_norm=bool(get("value_norm", False)),
        up_proj_norm=bool(get("up_proj_norm", False)), use_ngpt=bool(get("use_nGPT", False)),
        use_bias=bool(get("use_bias", False)), use_qkv_bias=bool(get("use_qkv_bias", False)),
        tie_embeddings=bool(get("tie_word_embeddings", False)), hidden_act=get("hidden_act", "silu"),
        max_seq_len=hf_config.max_position_embeddings, rms_eps=hf_config.rms_norm_eps)
