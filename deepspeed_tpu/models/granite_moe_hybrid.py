"""Granite 4.0-H causal LM (``granite-4.0-h-small`` ``config.json``, ``model_type:
granitemoehybrid``; HF ``modeling_granitemoehybrid.py`` for every layer) —
serving only.

A hybrid: ``layer_types`` names each layer ``mamba`` or ``attention`` (published:
a period of ten with the attention layer sixth).  With ``r`` the
``residual_multiplier``, one block is ``a = x + r Mixer(rms(x))``, ``y = a + r
(MoE(rms(a)) + Shared(rms(a)))``, every ``rms`` a plain gain ``w``; the
embedding is multiplied by ``embedding_multiplier``, the logits (a head tied to
the embedding) divided by ``logits_scaling``.

- **Mamba-2** (the layer's parameters hold ``STATE_MIXER``): ``[z | xBC | dt] =
  u W_in`` (``I | I + 2 Ns | H`` columns, ``I = mamba_expand x hidden = H x P``);
  a depth-wise causal filter of ``mamba_d_conv`` taps with a bias and a SiLU
  over ``xBC`` = ``[x | B | C]``; ``dt = softplus(dt + dt_bias)``, ``A =
  -exp(A_log)``; then the state-space duality recurrence over the sequence,
  whose memory is ONE MATRIX ``[P, Ns]`` A HEAD with B and C shared by every
  head (``ops/linear_attention/ssd.py``: a one-token update for a decode row and
  a burst's step; for a pass of chunks ``ssd_chunks``, which hands that same
  update the pass's rows of one token and walks the rest in a chunked scan whose
  layout is sized by the pass's tokens); the output times
  ``silu(z)`` INSIDE one RMS norm over all ``I`` columns, through ``W_out``.
  What a sequence remembers a layer, whatever its length: that matrix of every
  head in float32 (4 MB at 128 heads of 64 x 128) and the last ``taps - 1``
  rows of ``xBC`` before the filter.  Both are leaves of ``kv_cache[STATE]``,
  one slot a live sequence, beside the paged pool; ``transformer.paged_forward``
  (which states the contract) hands ``mix`` the shift's rows BY VALUE, with the
  filter over them local to a sequence (``filtered``: no shifted copy of the
  columns) and where the sequences lie, and writes back what
  ``mix`` returns for them; the matrices go BY REFERENCE (``STATE_BY_REFERENCE``:
  ``ssd_update`` and ``ssd_scan`` index the rows' slots of the carried leaf
  themselves, so 4 MB a row a layer is read where it lies once and written there
  once).  Nothing here computes a slot.
- **Attention**: GQA over the paged pool with NO positions at all
  (``position_embedding_type: "nope"``), scores times ``attention_multiplier``
  (published 1/128 at heads of 128: not one over the root).
- **FFN**: every layer ``num_experts`` SwiGLU experts under a float32 router,
  the top-k logits softmaxed among themselves (= the renormalised top-k of the
  full softmax: ``moe/serving.py``), plus a shared MLP added whole.
  ``num_local_experts`` of the experts' weights may be here (this chip's share
  of an expert-parallel deployment); only ``init_params`` reads that count, the
  forward reads the shapes.  (The PUBLISHED ``num_local_experts`` is the
  router's width: ``num_experts`` here.)

Parameters are laid out as they are scanned (``layer_segments``, as
``models/qwen3_next.py``); the experts are one stack over all layers.  Training,
tensor parallelism, rotary positions, projection biases and more than one B/C
group are not implemented.
"""

import dataclasses
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import transformer
from .qwen3_next import DECAY_RATES  # ``init_params``: exp(A_log) of a layer's first and last head
from .transformer import STATE, STATE_MIXER, rms_norm


@dataclasses.dataclass(frozen=True)
class GraniteMoeHybridConfig:
    vocab_size: int = 100352
    hidden_size: int = 4096
    num_layers: int = 40
    layer_types: Optional[tuple] = None  # None: a period of ten, the sixth layer attention
    num_heads: int = 32
    num_kv_heads: int = 8
    mamba_n_heads: int = 128
    mamba_d_head: int = 64
    mamba_d_state: int = 128
    mamba_n_groups: int = 1
    mamba_expand: int = 2
    mamba_d_conv: int = 4
    mamba_conv_bias: bool = True
    mamba_proj_bias: bool = False
    mamba_chunk_size: int = 256  # the training kernel's block; the serving scan picks its own
    num_experts: int = 72  # the router's width
    # experts whose weights are here: None = all; fewer = this chip's share of an
    # expert-parallel deployment, from expert 0.  Only ``init_params`` reads it.
    num_local_experts: Optional[int] = None
    top_k: int = 10
    intermediate_size: int = 768  # one expert's width
    shared_intermediate_size: int = 1536
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    attention_multiplier: float = 0.0078125
    logits_scaling: float = 16.0
    position_embedding_type: str = "nope"
    attention_bias: bool = False
    tie_embeddings: bool = True
    hidden_act: str = "silu"
    normalization_function: str = "rmsnorm"
    max_seq_len: int = 131072
    norm_eps: float = 1e-5

    def __post_init__(self):
        kinds = self.layer_types or tuple(
            "attention" if i % 10 == 5 else "mamba" for i in range(self.num_layers))
        object.__setattr__(self, "layer_types", tuple(kinds)[:self.num_layers])
        inner = self.mamba_n_heads * self.mamba_d_head
        for what, wrong in (
                (f"position_embedding_type {self.position_embedding_type!r}",
                 self.position_embedding_type != "nope"),
                ("attention_bias", self.attention_bias), ("mamba_proj_bias", self.mamba_proj_bias),
                ("mamba_n_groups != 1", self.mamba_n_groups != 1),
                ("an untied head", not self.tie_embeddings),
                (f"hidden_act {self.hidden_act!r}", self.hidden_act != "silu"),
                (f"normalization_function {self.normalization_function!r}",
                 self.normalization_function != "rmsnorm"),
                (f"mamba_n_heads x mamba_d_head = {inner} != mamba_expand x hidden_size",
                 inner != self.mamba_expand * self.hidden_size),
                (f"layer_types {set(self.layer_types) - {'mamba', 'attention'}}",
                 set(self.layer_types) - {"mamba", "attention"} or len(self.layer_types)
                 != self.num_layers)):
            if wrong:
                raise NotImplementedError(
                    f"granite_moe_hybrid: {what} is not implemented (published: no positions, no "
                    f"biases in the projections, one B/C group, a tied head, silu, rmsnorm)")

    @property
    def head_dim(self):
        return self.hidden_size // self.num_heads

    @staticmethod
    def granite_4_0_h_small():
        return GraniteMoeHybridConfig()

    @staticmethod
    def tiny(vocab=256, hidden=64, layers=10, heads=4, kv_heads=2, mamba_heads=8, d_state=16,
             experts=8, local_experts=None, top_k=4, seq=512):
        return GraniteMoeHybridConfig(
            vocab_size=vocab, hidden_size=hidden, num_layers=layers, num_heads=heads,
            num_kv_heads=kv_heads, mamba_n_heads=mamba_heads, mamba_d_head=2 * hidden // mamba_heads,
            mamba_d_state=d_state, num_experts=experts, num_local_experts=local_experts, top_k=top_k,
            intermediate_size=hidden // 2, shared_intermediate_size=hidden // 2,
            attention_multiplier=float(hidden // heads) ** -1.0, max_seq_len=seq)


def ssm_widths(config: GraniteMoeHybridConfig):
    """(inner columns ``I``, the filter's columns ``I + 2 Ns``, ``W_in``'s columns)."""
    inner = config.mamba_n_heads * config.mamba_d_head
    conv = inner + 2 * config.mamba_n_groups * config.mamba_d_state
    return inner, conv, inner + conv + config.mamba_n_heads


def layer_segments(config: GraniteMoeHybridConfig):
    """``[(start, period, repeats)]``: the layers as runs that repeat a pattern
    (``transformer.repeating_runs``).  Published: ``[(0, 10, 4)]``; one period
    alone: mamba x 5, attention, mamba x 4 = ``[(0, 1, 5), (5, 1, 1), (6, 1, 4)]``."""
    return transformer.repeating_runs(list(config.layer_types))


def init_params(config: GraniteMoeHybridConfig, key, dtype=jnp.float32):
    """``{"embed", "segments": [one tuple of per-position stacks a run of
    :func:`layer_segments`], "experts": [layers, held, ...], "final_norm"}``.
    Projections, experts and router at 1/sqrt(fan_in), the filter's taps at
    1/sqrt(taps) with a small bias, gains and ``D`` at one, the embedding at 0.02 over
    ``embedding_multiplier`` (at 0.02 a tied head makes the last input token the
    argmax by far).  ``exp(A_log)``
    log-spaced over a layer's heads between ``DECAY_RATES`` with ``dt_bias`` 1: a
    head's decay a token between about 0.999 and 0.9, as a trained model's
    (HF's own draw, ``A = 1..H``, forgets everything at every token)."""
    d, dh = config.hidden_size, config.head_dim
    h, kv, hm = config.num_heads, config.num_kv_heads, config.mamba_n_heads
    inner, conv, projected = ssm_widths(config)
    taps = config.mamba_d_conv
    held = config.num_local_experts or config.num_experts
    k_emb, k_layers, k_experts = jax.random.split(key, 3)

    def stack(key, *shape):
        return jax.random.normal(key, shape, dtype) * float(shape[-2]) ** -0.5

    def ffn(key, width, *lead):
        ks = jax.random.split(key, 3)
        return {"w_gate": stack(ks[0], *lead, d, width), "w_up": stack(ks[1], *lead, d, width),
                "w_down": stack(ks[2], *lead, width, d)}

    def position(key, depth, kind):
        ks = jax.random.split(key, 8)
        lp = {"op_norm": jnp.ones((depth, d), dtype), "ffn_norm": jnp.ones((depth, d), dtype)}
        if kind == "mamba":
            rates = np.exp(np.linspace(math.log(DECAY_RATES[0]), math.log(DECAY_RATES[1]), hm))
            lp[STATE_MIXER] = {
                "w_in": stack(ks[0], depth, d, projected),
                "filter": jax.random.normal(ks[1], (depth, taps, conv), dtype) * float(taps) ** -0.5,
                "conv_bias": jax.random.normal(ks[2], (depth, conv), dtype) * 0.1,
                "A_log": jnp.broadcast_to(jnp.asarray(np.log(rates), dtype), (depth, hm)),
                "dt_bias": jnp.ones((depth, hm), dtype), "D": jnp.ones((depth, hm), dtype),
                "norm": jnp.ones((depth, inner), dtype), "w_out": stack(ks[3], depth, inner, d)}
        else:
            lp["attn"] = {"wq": stack(ks[0], depth, d, h * dh), "wk": stack(ks[1], depth, d, kv * dh),
                          "wv": stack(ks[2], depth, d, kv * dh), "wo": stack(ks[3], depth, h * dh, d)}
        lp["moe"] = {"gate": {"wg": stack(ks[4], depth, d, config.num_experts)},
                     "shared": ffn(ks[5], config.shared_intermediate_size, depth)}
        return lp

    segments = []
    for start, period, repeats in layer_segments(config):
        keys = jax.random.split(jax.random.fold_in(k_layers, start), period)
        segments.append(tuple(position(keys[j], repeats, config.layer_types[start + j])
                              for j in range(period)))
    return {"embed": jax.random.normal(k_emb, (config.vocab_size, d), dtype)
            * (0.02 / config.embedding_multiplier),
            "segments": segments,
            "experts": ffn(k_experts, config.intermediate_size, config.num_layers, held),
            "final_norm": jnp.ones((d, ), dtype)}


# --------------------------------------------------------- paged (ragged) serve
# Which leaves of ``kv_cache[STATE]`` ``paged_forward`` hands ``mix`` by reference: what the
# kernels of ``ops/linear_attention/ssd.py`` take whole, with the rows' slots.
STATE_BY_REFERENCE = {"conv": False, "ssm": True}


def init_paged_cache(config: GraniteMoeHybridConfig, num_blocks: int, block_size: int,
                     dtype=jnp.bfloat16, state_slots: int = 32):
    """The KV pool of the ATTENTION layers alone and, under ``STATE``, the
    Mamba-2 layers' two leaves, ``state_slots`` slots and a trash slot each:
    ``conv`` ``[L_mamba, slots + 1, taps - 1, I + 2 Ns]`` in the pool's dtype
    (the last rows of ``xBC`` before the filter) and ``ssm`` ``[L_mamba, slots +
    1, H, P, Ns]`` in FLOAT32 whatever the pool's dtype (HF's cache and
    ``mamba_ssm``'s kernels keep the matrices so)."""
    kinds = config.layer_types
    _, conv, _ = ssm_widths(config)
    cache = transformer.init_paged_kv_pool(kinds.count("attention"), config.num_kv_heads,
                                           config.head_dim, num_blocks, block_size, dtype)
    mamba, slots = kinds.count("mamba"), state_slots + 1
    cache[STATE] = {
        "conv": jnp.zeros((mamba, slots, config.mamba_d_conv - 1, conv), dtype),
        "ssm": jnp.zeros((mamba, slots, config.mamba_n_heads, config.mamba_d_head,
                          config.mamba_d_state), jnp.float32)}
    return cache


def state_bytes_per_seq(config: GraniteMoeHybridConfig, value_bytes: int = 2) -> int:
    """What one live sequence holds outside the paged pool, whatever its
    length: a Mamba-2 layer's float32 matrix a head and ``taps - 1`` rows of the
    filter's input (4,194,304 + 50,688 B published; 38.2 MB at 9 such layers).
    The engine reads a family's state off this function."""
    _, conv, _ = ssm_widths(config)
    a_layer = ((config.mamba_d_conv - 1) * conv * value_bytes
               + config.mamba_n_heads * config.mamba_d_head * config.mamba_d_state * 4)
    return config.layer_types.count("mamba") * a_layer


def state_scan(config: GraniteMoeHybridConfig):
    """``(chunks(n, t, flat, walked), positions a chunk, layers, trips(walked))``
    for the serving counters: the chunks of the layouts the Mamba-2 layers' scans
    are given in one forward pass over a ``[n, t]`` bucket (``flat``: its
    compacted slots; ``walked``: its rows of more than one token), how many
    positions a chunk holds, how many layers scan, and the trips a compacted
    walk of ``walked`` rows takes.  The fourth entry says that a row of one token
    leaves the walk for the update kernel: the scan's live positions are the
    tokens of the rows it walked."""
    from ..ops.linear_attention.ssd import CHUNK, scan_chunks, walk_trips
    layers = config.layer_types.count("mamba")
    return ((lambda n, t, flat, walked: scan_chunks(n, t, flat, walked) * layers), CHUNK, layers,
            walk_trips)


def moe_picks_per_token(config: GraniteMoeHybridConfig) -> int:
    return config.top_k * config.num_layers


def moe_expert_rows(config: GraniteMoeHybridConfig, slots: int) -> int:
    """Rows the expert layers' grouped matmuls of one pass over ``slots`` token slots run
    over: on a share the window its held picks are compacted into, the first trip's."""
    from ..moe.serving import expert_rows
    held = config.num_local_experts or config.num_experts
    return expert_rows(slots, config.top_k, held, config.num_experts) * config.num_layers


def forward_paged(config: GraniteMoeHybridConfig, params, tokens, n_tokens, start_pos,
                  block_tables, kv_cache, *, block_size: int, tp_axis: Optional[str] = None,
                  gather_logits: bool = True, live_token_bound: Optional[int] = None,
                  last_rows: bool = False):
    """Ragged chunked forward (``transformer.paged_forward`` states the
    contract): the Mamba-2 layers through ``mix`` and their sequences' carried
    leaves, the attention layers over the pool, the expert FFN."""
    from ..moe.serving import sparse_moe_ffn
    from ..ops.linear_attention import ssd_chunks, ssd_update
    if tp_axis is not None:
        raise NotImplementedError("granite_moe_hybrid: tensor-parallel serving is not implemented")
    D, H, KV, dh = config.hidden_size, config.num_heads, config.num_kv_heads, config.head_dim
    hm, p, ns = config.mamba_n_heads, config.mamba_d_head, config.mamba_d_state
    inner, conv_dim, _ = ssm_widths(config)
    eps, residual = config.norm_eps, config.residual_multiplier
    dtype = kv_cache["k"].dtype
    experts = params["experts"]

    def branch(x, out):  # every branch joins the stream times the residual multiplier
        return x + (residual * out.astype(jnp.float32)).astype(x.dtype)

    def block_ffn(lp, x, live):
        moe = lp["moe"]
        out = sparse_moe_ffn({"gate": moe["gate"], "experts": experts, "shared": moe["shared"]},
                             rms_norm(x, lp["ffn_norm"], eps).reshape(-1, D), config.top_k, True,
                             live.reshape(-1), layer=moe["layer"])
        return branch(x, out.reshape(x.shape))

    def embed(tokens, safe_pos):
        return (params["embed"][tokens].astype(jnp.float32)
                * config.embedding_multiplier).astype(dtype)

    def mix(lp, x, filtered, live, carried, places):
        m = lp[STATE_MIXER]
        u = rms_norm(x, lp["op_norm"], eps)
        lead = x.shape[:2]
        with jax.named_scope("ssm_mixer"):
            projected = u @ m["w_in"].astype(dtype)
            z, xbc = projected[..., :inner], projected[..., inner:inner + conv_dim]
            dt = projected[..., inner + conv_dim:].astype(jnp.float32)
            with jax.named_scope("ssm_state"):
                xbc, last = filtered(xbc, carried["conv"], m["filter"], m["conv_bias"])
            xbc = jax.nn.silu(xbc).astype(dtype)
            xs = xbc[..., :inner].reshape(lead + (hm, p))
            b, c = xbc[..., inner:inner + ns], xbc[..., inner + ns:]
            dt = jax.nn.softplus(dt + m["dt_bias"].astype(jnp.float32))
            a = -jnp.exp(m["A_log"].astype(jnp.float32))
            ref = carried["ssm"]  # a ``StateRef``: the kernels' state arguments, and the trash slot
            if places.row is None and x.shape[1] == 1:  # a decode row, a burst's step
                with jax.named_scope("ssm_update"), jax.named_scope("ssm_state"):
                    y, state = ssd_update(xs[:, 0], dt[:, 0], a, b[:, 0], c[:, 0], m["D"], ref.leaf,
                                          ref.at, ref.begins)
                y = y[:, None]
            else:  # a pass of chunks: the scan for the rows of several tokens, the update for the rest
                y, state = ssd_chunks(xs, dt, a, b, c, m["D"], *ref, *places)
            # GraniteMoeHybridRMSNormGated: the gate INSIDE the norm, one group over all columns
            y = y.reshape(lead + (inner, )).astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
            y = rms_norm(y, m["norm"], eps).astype(dtype)
            x = branch(x, y @ m["w_out"].astype(dtype))
        return block_ffn(lp, x, live), {"conv": last, "ssm": state}

    def qkv(lp, x, safe_pos):  # no positions: the keys are cached as they are projected
        a = lp["attn"]
        u = rms_norm(x, lp["op_norm"], eps)
        lead = x.shape[:2]
        return ((u @ a["wq"].astype(dtype)).reshape(lead + (H, dh)),
                (u @ a["wk"].astype(dtype)).reshape(lead + (KV, dh)),
                (u @ a["wv"].astype(dtype)).reshape(lead + (KV, dh)), None)

    def finish(lp, x, kept, attn, live):
        x = branch(x, attn.reshape(x.shape[:2] + (H * dh, )) @ lp["attn"]["wo"].astype(dtype))
        return block_ffn(lp, x, live)

    def head(x):  # tied: the logits over this chip's rows of the embedding
        x = rms_norm(x, params["final_norm"], eps)
        logits = jax.lax.dot_general(x, params["embed"].astype(dtype),
                                     (((x.ndim - 1, ), (1, )), ((), ())))
        return (logits.astype(jnp.float32) / config.logits_scaling).astype(logits.dtype)

    return transformer.paged_forward(
        transformer.layers_of_one_expert_stack(layer_segments(config), params["segments"]), tokens,
        n_tokens, start_pos, block_tables, kv_cache, block_size=block_size,
        live_token_bound=live_token_bound, last_rows=last_rows, embed=embed, qkv=qkv, finish=finish,
        head=head, mix=mix, by_reference=STATE_BY_REFERENCE,
        softmax_scale=config.attention_multiplier)
