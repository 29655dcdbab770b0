"""Qwen3-Next causal LM (Qwen3-Next-80B-A3B ``config.json``, ``model_type:
qwen3_next``; HF ``modeling_qwen3_next.py`` for every layer) — serving only.

A hybrid: layer ``i`` is a gated softmax attention where ``(i + 1) %
full_attention_interval == 0`` and a **Gated DeltaNet** otherwise, three in
four.  One block is ``h = x + Mixer(rms(x))``, ``y = h + MoE(rms(h))``, every
``rms`` with the gain ``1 + w``.

- **Gated DeltaNet** (the layer's parameters hold ``STATE_MIXER``): ``[q | k |
  v | z] = u W_qkvz``, ``[b | a] = u W_ba``; a depth-wise causal filter of
  ``linear_conv_kernel_dim`` taps and a SiLU over ``[q | k | v]``; q and k
  l2-normalised a head; then the gated delta rule over the sequence, whose
  memory is ONE MATRIX ``[dk, dv]`` A VALUE HEAD (``ops/linear_attention``: a
  chunked scan for a step's chunk, a one-token update for a decode row and a
  burst's step); the output RMS-normed a head, times ``silu(z)``, through
  ``W_out``.  What a sequence remembers a layer, whatever its length: that
  matrix of every head in float32 (2 MB at 32 heads of 128 x 128) and the last
  ``taps - 1`` rows of ``[q | k | v]`` before the filter.  Both are leaves of
  ``kv_cache[STATE]``, one slot a live sequence, beside the paged pool;
  ``transformer.paged_forward`` (which states the contract) hands ``mix`` the
  rows' carried leaves, the filter over the shift local to a sequence
  (``filtered``: no shifted copy of the columns) and where the sequences
  lie, and writes back what ``mix`` returns.  Nothing here knows of slots.
- **Gated attention**: ``W_q`` gives a head its query and, beside it, a gate as
  wide; an RMSNorm over each head of q and of k, rotate-half rotary over the
  first ``partial_rotary_factor`` of the head's dimensions, GQA over the paged
  pool (heads of 256: a pool row two lane tiles wide), the kernel's output
  times ``sigmoid(gate)`` before ``W_o``.
- **FFN**: every layer ``num_experts`` SwiGLU experts under a float32 softmax
  router, top-k renormalised, plus a shared expert scaled by ``sigmoid(x w_g)``,
  one gate a token (``moe/serving.py``).  ``num_local_experts`` of the experts'
  weights may be here (this chip's share of an expert-parallel deployment);
  only ``init_params`` reads that count, the forward reads the shapes.

The multi-token-prediction module of the released checkpoint is not here:
serving does not run it.  Parameters are laid out as they are scanned
(``layer_segments``, as ``models/lfm2.py``): a run of layers that repeats a
pattern is one scan whose body is the pattern; the experts are one stack over
all layers.  Training and tensor parallelism are not implemented.
"""

import dataclasses
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import transformer
from .lfm2 import rotate_half
from .transformer import STATE, STATE_MIXER, rms_norm

L2_EPS = 1e-6  # inside l2norm's root (FLA's)
DECAY_RATES = (7e-4, 7e-2)  # ``init_params``: exp(A_log) of a layer's first and last value head


@dataclasses.dataclass(frozen=True)
class Qwen3NextConfig:
    vocab_size: int = 151936
    hidden_size: int = 2048
    num_layers: int = 48
    full_attention_interval: int = 4
    num_heads: int = 16
    num_kv_heads: int = 2
    head_dim: int = 256
    partial_rotary_factor: float = 0.25
    rope_theta: float = 10000000.0
    rope_scaling: Optional[dict] = None
    linear_num_key_heads: int = 16
    linear_num_value_heads: int = 32
    linear_key_head_dim: int = 128
    linear_value_head_dim: int = 128
    linear_conv_kernel_dim: int = 4
    num_experts: int = 512  # the router's width
    # experts whose weights are here: None = all; fewer = this chip's share of an
    # expert-parallel deployment, from expert 0.  Only ``init_params`` reads it.
    num_local_experts: Optional[int] = None
    top_k: int = 10
    norm_topk_prob: bool = True
    moe_intermediate_size: int = 512
    shared_expert_intermediate_size: int = 512
    decoder_sparse_step: int = 1
    mlp_only_layers: tuple = ()
    hidden_act: str = "silu"
    use_sliding_window: bool = False
    tie_embeddings: bool = False
    max_seq_len: int = 262144
    norm_eps: float = 1e-6

    def __post_init__(self):
        object.__setattr__(self, "mlp_only_layers", tuple(self.mlp_only_layers))
        for what, wrong in (("mlp_only_layers", self.mlp_only_layers),
                            ("decoder_sparse_step != 1", self.decoder_sparse_step != 1),
                            ("rope_scaling", self.rope_scaling), ("tie_word_embeddings", self.tie_embeddings),
                            ("use_sliding_window", self.use_sliding_window),
                            (f"hidden_act {self.hidden_act!r}", self.hidden_act != "silu")):
            if wrong:
                raise NotImplementedError(f"qwen3_next: {what} is not implemented (published: every "
                                          f"layer sparse, plain rotary, an untied head, no window, silu)")

    @property
    def layer_types(self):
        return tuple("full_attention" if (i + 1) % self.full_attention_interval == 0
                     else "linear_attention" for i in range(self.num_layers))

    @staticmethod
    def qwen3_next_80b_a3b():
        return Qwen3NextConfig()

    @staticmethod
    def tiny(vocab=256, hidden=64, layers=8, heads=4, kv_heads=2, head_dim=16, key_heads=2,
             value_heads=4, linear_dim=8, experts=8, local_experts=None, top_k=4, seq=512):
        return Qwen3NextConfig(
            vocab_size=vocab, hidden_size=hidden, num_layers=layers, num_heads=heads,
            num_kv_heads=kv_heads, head_dim=head_dim, linear_num_key_heads=key_heads,
            linear_num_value_heads=value_heads, linear_key_head_dim=linear_dim,
            linear_value_head_dim=linear_dim, num_experts=experts, num_local_experts=local_experts,
            top_k=top_k, moe_intermediate_size=hidden // 2,
            shared_expert_intermediate_size=hidden // 2, max_seq_len=seq)


def gdn_widths(config: Qwen3NextConfig):
    """(key columns, value columns) of a Gated DeltaNet layer; the filter runs
    over ``2 x key + value`` columns."""
    return (config.linear_num_key_heads * config.linear_key_head_dim,
            config.linear_num_value_heads * config.linear_value_head_dim)


def layer_segments(config: Qwen3NextConfig):
    """``[(start, period, repeats)]``: the layers as runs that repeat a pattern
    (``transformer.repeating_runs``).  Published: ``[(0, 4, 12)]``."""
    return transformer.repeating_runs(list(config.layer_types))


def init_params(config: Qwen3NextConfig, key, dtype=jnp.float32):
    """``{"embed", "lm_head", "segments": [one tuple of per-position stacks a run
    of :func:`layer_segments`], "experts": [layers, held, ...], "final_norm"}``.
    Projections, experts, router and the shared expert's gate at 1/sqrt(fan_in),
    the filter's taps at 1/sqrt(taps), ``1 + w`` gains at zero and the
    DeltaNet's output gain at one.  ``exp(A_log)`` log-spaced over a layer's
    value heads between ``DECAY_RATES`` with ``dt_bias`` 1: a head's decay a
    token between about 0.999 and 0.9, as a trained model's (HF's own draw
    forgets everything at every token)."""
    d, dh = config.hidden_size, config.head_dim
    h, kv, hv = config.num_heads, config.num_kv_heads, config.linear_num_value_heads
    key_dim, value_dim = gdn_widths(config)
    taps = config.linear_conv_kernel_dim
    held = config.num_local_experts or config.num_experts
    k_emb, k_head, k_layers, k_experts = jax.random.split(key, 4)

    def stack(key, *shape):
        return jax.random.normal(key, shape, dtype) * float(shape[-2]) ** -0.5

    def ffn(key, width, *lead):
        ks = jax.random.split(key, 3)
        return {"w_gate": stack(ks[0], *lead, d, width), "w_up": stack(ks[1], *lead, d, width),
                "w_down": stack(ks[2], *lead, width, d)}

    def position(key, depth, kind):
        ks = jax.random.split(key, 10)
        lp = {"op_norm": jnp.zeros((depth, d), dtype), "ffn_norm": jnp.zeros((depth, d), dtype)}
        if kind == "linear_attention":
            rates = np.exp(np.linspace(math.log(DECAY_RATES[0]), math.log(DECAY_RATES[1]), hv))
            lp[STATE_MIXER] = {
                "w_qkvz": stack(ks[0], depth, d, 2 * key_dim + 2 * value_dim),
                "w_ba": stack(ks[1], depth, d, 2 * hv),
                "filter": jax.random.normal(ks[2], (depth, taps, 2 * key_dim + value_dim), dtype)
                * float(taps) ** -0.5,
                "A_log": jnp.broadcast_to(jnp.asarray(np.log(rates), dtype), (depth, hv)),
                "dt_bias": jnp.ones((depth, hv), dtype),
                "norm": jnp.ones((depth, config.linear_value_head_dim), dtype),
                "w_out": stack(ks[3], depth, value_dim, d)}
        else:
            lp["attn"] = {"wq": stack(ks[0], depth, d, h * 2 * dh), "wk": stack(ks[1], depth, d, kv * dh),
                          "wv": stack(ks[2], depth, d, kv * dh), "wo": stack(ks[3], depth, h * dh, d),
                          "q_norm": jnp.zeros((depth, dh), dtype),
                          "k_norm": jnp.zeros((depth, dh), dtype)}
        lp["moe"] = {"gate": {"wg": stack(ks[4], depth, d, config.num_experts)},
                     "shared": ffn(ks[5], config.shared_expert_intermediate_size, depth),
                     "shared_gate": stack(ks[6], depth, d, 1)}
        return lp

    segments = []
    for start, period, repeats in layer_segments(config):
        keys = jax.random.split(jax.random.fold_in(k_layers, start), period)
        segments.append(tuple(position(keys[j], repeats, config.layer_types[start + j])
                              for j in range(period)))
    return {"embed": jax.random.normal(k_emb, (config.vocab_size, d), dtype) * 0.02,
            "segments": segments,
            "experts": ffn(k_experts, config.moe_intermediate_size, config.num_layers, held),
            "final_norm": jnp.zeros((d, ), dtype),
            "lm_head": transformer.init_linear(k_head, d, config.vocab_size, dtype=dtype)}


# --------------------------------------------------------- paged (ragged) serve
def init_paged_cache(config: Qwen3NextConfig, num_blocks: int, block_size: int,
                     dtype=jnp.bfloat16, state_slots: int = 32):
    """The KV pool of the ATTENTION layers alone and, under ``STATE``, the Gated
    DeltaNet layers' two leaves, ``state_slots`` slots and a trash slot each:
    ``conv`` ``[L_gdn, slots + 1, taps - 1, 2 x key + value]`` in the pool's
    dtype (the last rows of ``[q | k | v]`` before the filter) and ``recurrent``
    ``[L_gdn, slots + 1, Hv, dk, dv]`` in FLOAT32 whatever the pool's dtype
    (HF's and FLA's kernels keep the matrix so)."""
    kinds = config.layer_types
    key_dim, value_dim = gdn_widths(config)
    cache = transformer.init_paged_kv_pool(kinds.count("full_attention"), config.num_kv_heads,
                                           config.head_dim, num_blocks, block_size, dtype)
    gdn, slots = kinds.count("linear_attention"), state_slots + 1
    cache[STATE] = {
        "conv": jnp.zeros((gdn, slots, config.linear_conv_kernel_dim - 1, 2 * key_dim + value_dim),
                          dtype),
        "recurrent": jnp.zeros((gdn, slots, config.linear_num_value_heads,
                                config.linear_key_head_dim, config.linear_value_head_dim),
                               jnp.float32)}
    return cache


def state_bytes_per_seq(config: Qwen3NextConfig, value_bytes: int = 2) -> int:
    """What one live sequence holds outside the paged pool, whatever its
    length: a Gated DeltaNet layer's float32 matrix a value head and ``taps -
    1`` rows of the filter's input (2,097,152 + 49,152 B published; 19.3 MB at
    9 such layers).  The engine reads a family's state off this function."""
    key_dim, value_dim = gdn_widths(config)
    a_layer = ((config.linear_conv_kernel_dim - 1) * (2 * key_dim + value_dim) * value_bytes
               + config.linear_num_value_heads * config.linear_key_head_dim
               * config.linear_value_head_dim * 4)
    return config.layer_types.count("linear_attention") * a_layer


def state_scan(config: Qwen3NextConfig):
    """``(chunks(n, t, flat), positions a chunk, layers)`` for the serving
    counters: the chunks the Gated DeltaNet layers' scans walk in one forward
    pass over a ``[n, t]`` bucket (``flat``: its compacted slots), how many
    positions a chunk holds, and how many layers scan."""
    from ..ops.linear_attention import CHUNK, scan_chunks
    layers = config.layer_types.count("linear_attention")
    return (lambda n, t, flat=None: scan_chunks(n, t, flat) * layers), CHUNK, layers


def moe_picks_per_token(config: Qwen3NextConfig) -> int:
    return config.top_k * config.num_layers


def moe_expert_rows(config: Qwen3NextConfig, slots: int) -> int:
    """Rows the expert layers' grouped matmuls of one pass over ``slots`` token slots run
    over: on a share the window its held picks are compacted into, the first trip's."""
    from ..moe.serving import expert_rows
    held = config.num_local_experts or config.num_experts
    return expert_rows(slots, config.top_k, held, config.num_experts) * config.num_layers


def l2norm(x):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def forward_paged(config: Qwen3NextConfig, params, tokens, n_tokens, start_pos, block_tables,
                  kv_cache, *, block_size: int, tp_axis: Optional[str] = None,
                  gather_logits: bool = True, live_token_bound: Optional[int] = None,
                  last_rows: bool = False):
    """Ragged chunked forward (``transformer.paged_forward`` states the
    contract): the Gated DeltaNet layers through ``mix`` and their sequences'
    carried leaves, the gated attention layers over the pool, the expert FFN."""
    from ..moe.serving import sparse_moe_ffn
    from ..ops.linear_attention import gated_delta_scan, gated_delta_step
    if tp_axis is not None:
        raise NotImplementedError("qwen3_next: tensor-parallel serving is not implemented")
    D, H, KV, dh = config.hidden_size, config.num_heads, config.num_kv_heads, config.head_dim
    hk, hv = config.linear_num_key_heads, config.linear_num_value_heads
    dk, dv = config.linear_key_head_dim, config.linear_value_head_dim
    key_dim, value_dim = gdn_widths(config)
    eps = config.norm_eps
    dtype = kv_cache["k"].dtype
    rotated = int(dh * config.partial_rotary_factor)
    inv_freq = (config.rope_theta ** -(np.arange(0, rotated, 2, dtype=np.float32) / rotated))
    experts = params["experts"]

    def norm(x, w):  # Qwen3NextRMSNorm: the gain is 1 + w
        return rms_norm(x, 1.0 + w.astype(jnp.float32), eps)

    def block_ffn(lp, x, live):
        moe = lp["moe"]
        out = sparse_moe_ffn({"gate": moe["gate"], "experts": experts, "shared": moe["shared"],
                              "shared_gate": moe["shared_gate"]},
                             norm(x, lp["ffn_norm"]).reshape(-1, D), config.top_k,
                             config.norm_topk_prob, live.reshape(-1), layer=moe["layer"])
        return x + out.reshape(x.shape)

    def embed(tokens, safe_pos):
        return params["embed"][tokens].astype(dtype)

    def mix(lp, x, filtered, live, carried, places):
        m = lp[STATE_MIXER]
        u = norm(x, lp["op_norm"])
        lead = x.shape[:2]
        with jax.named_scope("gdn_mixer"):
            qkvz = u @ m["w_qkvz"].astype(dtype)
            mixed, z = qkvz[..., :2 * key_dim + value_dim], qkvz[..., 2 * key_dim + value_dim:]
            b, a = jnp.split(jnp.dot(u, m["w_ba"].astype(dtype),
                                     preferred_element_type=jnp.float32), 2, axis=-1)
            with jax.named_scope("gdn_state"):
                conv, last = filtered(mixed, carried["conv"], m["filter"])
            conv = jax.nn.silu(conv)
            q = (l2norm(conv[..., :key_dim].reshape(lead + (hk, dk))) * dk ** -0.5).astype(dtype)
            k = l2norm(conv[..., key_dim:2 * key_dim].reshape(lead + (hk, dk))).astype(dtype)
            v = conv[..., 2 * key_dim:].reshape(lead + (hv, dv)).astype(dtype)
            beta = jax.nn.sigmoid(b)
            g = -jnp.exp(m["A_log"].astype(jnp.float32)) * jax.nn.softplus(
                a + m["dt_bias"].astype(jnp.float32))
            with jax.named_scope("gdn_scan"):
                if places.row is None and x.shape[1] == 1:  # a decode row, a burst's step
                    with jax.named_scope("gdn_state"):
                        o, state = gated_delta_step(
                            jnp.repeat(q[:, 0], hv // hk, axis=1), jnp.repeat(k[:, 0], hv // hk, axis=1),
                            v[:, 0], g[:, 0], beta[:, 0], carried["recurrent"])
                    o = o[:, None]
                else:
                    o, state = gated_delta_scan(q, k, v, g, beta, carried["recurrent"],
                                                places.n_tokens, places.row, places.col)
            # Qwen3NextRMSNormGated: a plain gain over each head, then the gate
            o = o.astype(jnp.float32)
            o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps) * m["norm"].astype(
                jnp.float32)
            o = (o * jax.nn.silu(z.reshape(lead + (hv, dv)).astype(jnp.float32))).astype(dtype)
            x = x + o.reshape(lead + (value_dim, )) @ m["w_out"].astype(dtype)
        return block_ffn(lp, x, live), {"conv": last, "recurrent": state}

    def qkv(lp, x, safe_pos):
        a = lp["attn"]
        u = norm(x, lp["op_norm"])
        lead = x.shape[:2]
        q_gate = (u @ a["wq"].astype(dtype)).reshape(lead + (H, 2 * dh))  # a head's query, then its gate
        q, gate = q_gate[..., :dh], q_gate[..., dh:]
        k = (u @ a["wk"].astype(dtype)).reshape(lead + (KV, dh))
        v = (u @ a["wv"].astype(dtype)).reshape(lead + (KV, dh))

        def rotary(x, w):  # the first ``rotated`` dimensions of each normed head turn, the others pass
            x = norm(x, w)
            return jnp.concatenate([rotate_half(x[..., :rotated], safe_pos, inv_freq),
                                    x[..., rotated:]], axis=-1)

        return rotary(q, a["q_norm"]), rotary(k, a["k_norm"]), v, gate

    def finish(lp, x, gate, attn, live):
        with jax.named_scope("attn_gate"):
            attn = (attn.astype(jnp.float32) * jax.nn.sigmoid(gate.astype(jnp.float32))).astype(dtype)
        x = x + attn.reshape(x.shape[:2] + (H * dh, )) @ lp["attn"]["wo"].astype(dtype)
        return block_ffn(lp, x, live)

    def head(x):
        return norm(x, params["final_norm"]) @ params["lm_head"].astype(dtype)

    # every layer is handed its index into the one stack of experts
    return transformer.paged_forward(
        transformer.layers_of_one_expert_stack(layer_segments(config), params["segments"]), tokens,
        n_tokens, start_pos, block_tables, kv_cache, block_size=block_size,
        live_token_bound=live_token_bound, last_rows=last_rows, embed=embed, qkv=qkv, finish=finish,
        head=head, mix=mix, softmax_scale=dh ** -0.5)
