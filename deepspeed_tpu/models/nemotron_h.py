"""Nemotron-H causal LM (``NVIDIA-Nemotron-3-Nano-30B-A3B`` ``config.json``,
``model_type: nemotron_h``) — serving only.

A hybrid whose layer is ONE PART ALONE: ``hybrid_override_pattern`` names layer
``i`` ``M`` (a Mamba-2 mixer), ``E`` (a mixture of experts) or ``*`` (attention),
and the layer is ``x + Part_i(rms(x))`` with one norm (a plain gain) and that one
part: no mixer-then-FFN block.  (``-``, a dense MLP, does not occur in the
published pattern and raises.)  The embedding as stored, a final norm, an UNTIED
head, no bias in any projection.  Published: 23 ``M``, 23 ``E``, 6 ``*``.

- **M, Mamba-2** (``H`` heads of ``P``, a state of ``N``, ``G`` = ``n_groups`` B/C
  groups; ``I = H P``, NOT ``expand x hidden``: ``expand`` is published and
  unused): ``[z | xBC | dt] = u W_in`` (``I | I + 2 G N | H`` columns); a
  depth-wise causal filter of ``conv_kernel`` taps with a bias over ``xBC``,
  then SiLU; ``xBC = [x (H, P) | B (G, N) | C (G, N)]``; ``dt = softplus(dt +
  dt_bias)`` (no clamp), ``A = -exp(A_log)``; for head ``h`` with ``g = h // (H /
  G)``

      S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_{g,t}^T;    y_t = S_t C_{g,t} + D x_t

  (``ops/linear_attention/ssd.py``, which takes B and C a group); then ``y *
  silu(z)`` and an RMS norm OVER EACH GROUP of ``I / G`` columns with a gain
  ``[I]`` (the gate first, the norm after), through ``W_out``.  What a sequence
  keeps a layer, whatever its length: ``H`` matrices ``[P, N]`` in float32 (2 MB
  at 64 heads of 64 x 128) BY REFERENCE (``STATE_BY_REFERENCE``: ``ssd_update``
  and ``ssd_scan`` index the rows' slots of the carried leaf themselves) and the
  last ``taps - 1`` rows of ``xBC`` before the filter BY VALUE, through
  ``filtered`` (``transformer.paged_forward`` states the contract).
- ***, attention**: GQA over the paged pool with ``head_dim`` published (128: NOT
  ``hidden / heads``), scores over ``sqrt(head_dim)``, causal, NO positions at all
  (``rope_theta`` and ``partial_rotary_factor`` are published and unused).
- **E, experts**: ``s = sigmoid(u W_r)`` in float32 over ``n_routed_experts``; the
  picks the top ``num_experts_per_tok`` of ``s + bias``; the weights the picked
  ``s`` without the bias over their sum + 1e-20, times ``routed_scaling_factor``
  (``moe/serving.py route``); an expert is UNGATED, ``W_down relu(W_up u)^2``,
  which ``moe/serving.py`` is told by the leaves holding no ``w_gate``; one shared
  expert of the same form added whole.  ``held_experts`` of the experts' weights
  may be here (this chip's share of an expert-parallel deployment, from expert
  0); only ``init_params`` reads that count, the forward reads the shapes.

Such a layer touches neither cache (``transformer.PART_ALONE``): the state's
leaves are ``[M layers, slots + 1, ...]``, the pool's ``[* layers, NB, ...]`` and
an ``E`` layer has a row in neither.  The counts the engine asks are by kind of
layer too: ``state_scan`` and ``state_bytes_per_seq`` over the ``M`` layers,
``moe_picks_per_token`` and ``moe_expert_rows`` over the ``E`` layers.  The held
picks of a pass are tallied on the device (``transformer.TALLY``, handed along
the period's chain: ``pick_tallies``).

Parameters are laid out as they are scanned (``layer_segments``); the experts of
all ``E`` layers are one stack ``[E layers, held, ...]``.  Training, tensor
parallelism, a ``-`` layer, a ``dt`` clamp (``time_step_limit``), rotary
positions and projection biases are not implemented.
"""

import dataclasses
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import transformer
from .qwen3_next import DECAY_RATES  # ``init_params``: exp(A_log) of a layer's first and last head
from .transformer import PART_ALONE, STATE, STATE_MIXER, TALLY, rms_norm

PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"  # published, 52 layers


@dataclasses.dataclass(frozen=True)
class NemotronHConfig:
    vocab_size: int = 131072
    hidden_size: int = 2688
    num_layers: int = 52
    hybrid_override_pattern: str = PATTERN  # the first ``num_layers`` characters are run
    num_heads: int = 32
    num_kv_heads: int = 2
    head_dim: int = 128
    mamba_num_heads: int = 64
    mamba_head_dim: int = 64
    ssm_state_size: int = 128
    n_groups: int = 8
    expand: int = 2  # published, unused: the inner width is heads x head_dim
    conv_kernel: int = 4
    use_conv_bias: bool = True
    mamba_proj_bias: bool = False
    mamba_hidden_act: str = "silu"
    chunk_size: int = 128  # the training kernel's block; the serving scan picks its own
    # the router's width (``n_routed_experts`` as PUBLISHED).  The forward reads it off the router's
    # shape; ``moe_expert_rows`` (a counter's) reads it here
    num_experts: int = 128
    # experts whose weights are here: None = all; fewer = this chip's share of an
    # expert-parallel deployment, from expert 0.  Only ``init_params`` reads it.
    held_experts: Optional[int] = None
    top_k: int = 6
    moe_intermediate_size: int = 1856
    shared_intermediate_size: int = 3712
    n_shared_experts: int = 1
    n_group: int = 1
    topk_group: int = 1
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    mlp_hidden_act: str = "relu2"
    mlp_bias: bool = False
    attention_bias: bool = False
    use_bias: bool = False
    tie_embeddings: bool = False
    max_seq_len: int = 262144
    norm_eps: float = 1e-5  # ``layer_norm_epsilon``

    def __post_init__(self):
        kinds = self.hybrid_override_pattern[:self.num_layers]
        for what, wrong in (
                (f"hybrid_override_pattern {kinds!r}: {self.num_layers} layers of M, E and * "
                 f"(a '-' dense layer is not implemented)",
                 len(kinds) != self.num_layers or set(kinds) - set("ME*")),
                ("a projection bias", self.mamba_proj_bias or self.mlp_bias or self.attention_bias
                 or self.use_bias),
                ("a filter without its bias (use_conv_bias false)", not self.use_conv_bias),
                (f"{self.held_experts} held experts of a router over {self.num_experts}",
                 self.held_experts is not None and self.num_experts % self.held_experts),
                ("a tied head", self.tie_embeddings),
                (f"mamba_hidden_act {self.mamba_hidden_act!r}", self.mamba_hidden_act != "silu"),
                (f"mlp_hidden_act {self.mlp_hidden_act!r}", self.mlp_hidden_act != "relu2"),
                ("a group-limited router", (self.n_group, self.topk_group) != (1, 1)),
                ("more than one shared expert", self.n_shared_experts != 1),
                ("norm_topk_prob false", not self.norm_topk_prob),
                (f"{self.mamba_num_heads} Mamba heads in {self.n_groups} B/C groups",
                 self.mamba_num_heads % self.n_groups)):
            if wrong:
                raise NotImplementedError(
                    f"nemotron_h: {what} is not implemented (published: M / E / * layers, no "
                    f"biases but the filter's, an untied head, silu and relu2, one router group, one "
                    f"shared expert)")

    @property
    def kinds(self) -> str:
        return self.hybrid_override_pattern[:self.num_layers]

    @staticmethod
    def nemotron_3_nano_30b_a3b():
        return NemotronHConfig()

    @staticmethod
    def tiny(vocab=256, hidden=64, layers=14, heads=4, kv_heads=2, mamba_heads=8, groups=4,
             d_state=16, experts=8, held_experts=None, top_k=3, seq=512, pattern=PATTERN):
        return NemotronHConfig(
            vocab_size=vocab, hidden_size=hidden, num_layers=layers, hybrid_override_pattern=pattern,
            num_heads=heads, num_kv_heads=kv_heads, head_dim=2 * hidden // heads,
            mamba_num_heads=mamba_heads, mamba_head_dim=16, ssm_state_size=d_state, n_groups=groups,
            num_experts=experts, held_experts=held_experts, top_k=top_k,
            moe_intermediate_size=hidden // 2, shared_intermediate_size=hidden, max_seq_len=seq)


def ssm_widths(config: NemotronHConfig):
    """(inner columns ``I = H P``, the filter's columns ``I + 2 G N``, ``W_in``'s columns)."""
    inner = config.mamba_num_heads * config.mamba_head_dim
    conv = inner + 2 * config.n_groups * config.ssm_state_size
    return inner, conv, inner + conv + config.mamba_num_heads


def layer_segments(config: NemotronHConfig):
    """``[(start, period, repeats)]``: the layers as runs that repeat a pattern
    (``transformer.repeating_runs``).  Two published periods, ``MEMEM*E`` twice:
    ``[(0, 7, 2)]``."""
    return transformer.repeating_runs(list(config.kinds))


def init_params(config: NemotronHConfig, key, dtype=jnp.float32):
    """``{"embed", "head", "segments": [one tuple of per-position stacks a run of
    :func:`layer_segments`], "experts": [E layers, held, ...], "final_norm"}``; a
    position's stack holds ``norm`` and ONE of ``STATE_MIXER`` (M), ``attn`` (*),
    ``PART_ALONE`` (E: the router with its bias and the shared expert).
    Projections, experts and router at 1/sqrt(fan_in), the filter's taps at
    1/sqrt(taps) with a bias about -0.65 (where SiLU's output is centred: else every
    sequence's state converges on one common matrix), every ``w_down`` with its rows' mean
    taken off (``relu^2`` is positive: else it adds one vector to every token), gains and
    ``D`` at one, the router's bias normal(0, 0.01), the embedding at 0.02 and a head of its
    own draw (why each: ``chipbench/references/nemotron_h.py init_params``).  ``exp(A_log)``
    log-spaced over a layer's heads between ``DECAY_RATES`` with ``dt_bias`` 1: a
    head's decay a token between about 0.999 and 0.9, as a trained model's."""
    d, dh = config.hidden_size, config.head_dim
    h, kv, hm = config.num_heads, config.num_kv_heads, config.mamba_num_heads
    inner, conv, projected = ssm_widths(config)
    taps = config.conv_kernel
    held = config.held_experts or config.num_experts
    k_emb, k_head, k_layers, k_experts = jax.random.split(key, 4)

    def stack(key, *shape):
        return jax.random.normal(key, shape, dtype) * float(shape[-2]) ** -0.5

    def ffn(key, width, *lead):  # ungated: two matrices an expert, both ``[width, hidden]``
        ks = jax.random.split(key, 2)
        up = jax.random.normal(ks[0], (*lead, width, d), dtype) * float(d) ** -0.5  # [F, D]: as w_down lies
        down = stack(ks[1], *lead, width, d)
        return {"w_up": up, "w_down": down - jnp.mean(down, axis=-2, keepdims=True)}

    def position(key, depth, kind):
        ks = jax.random.split(key, 6)
        lp = {"norm": jnp.ones((depth, d), dtype)}
        if kind == "M":
            rates = np.exp(np.linspace(math.log(DECAY_RATES[0]), math.log(DECAY_RATES[1]), hm))
            lp[STATE_MIXER] = {
                "w_in": stack(ks[0], depth, d, projected),
                "filter": jax.random.normal(ks[1], (depth, taps, conv), dtype) * float(taps) ** -0.5,
                "conv_bias": jax.random.normal(ks[2], (depth, conv), dtype) * 0.1 - 0.65,
                "A_log": jnp.broadcast_to(jnp.asarray(np.log(rates), dtype), (depth, hm)),
                "dt_bias": jnp.ones((depth, hm), dtype), "D": jnp.ones((depth, hm), dtype),
                "norm": jnp.ones((depth, inner), dtype), "w_out": stack(ks[3], depth, inner, d)}
        elif kind == "*":
            lp["attn"] = {"wq": stack(ks[0], depth, d, h * dh), "wk": stack(ks[1], depth, d, kv * dh),
                          "wv": stack(ks[2], depth, d, kv * dh), "wo": stack(ks[3], depth, h * dh, d)}
        else:
            lp[PART_ALONE] = {
                "gate": {"wg": stack(ks[4], depth, d, config.num_experts),
                         "bias": jax.random.normal(ks[0], (depth, config.num_experts), dtype) * 0.01},
                "shared": ffn(ks[5], config.shared_intermediate_size, depth)}
        return lp

    segments = []
    for start, period, repeats in layer_segments(config):
        keys = jax.random.split(jax.random.fold_in(k_layers, start), period)
        segments.append(tuple(position(keys[j], repeats, config.kinds[start + j])
                              for j in range(period)))
    experts = ffn(k_experts, config.moe_intermediate_size, config.kinds.count("E"), held)
    experts["w_down"] = experts["w_down"] / config.top_k
    return {"embed": jax.random.normal(k_emb, (config.vocab_size, d), dtype) * 0.02,
            "head": stack(k_head, d, config.vocab_size), "segments": segments,
            "experts": experts, "final_norm": jnp.ones((d, ), dtype)}


# --------------------------------------------------------- paged (ragged) serve
# Which leaves of ``kv_cache[STATE]`` ``paged_forward`` hands ``mix`` by reference: what the
# kernels of ``ops/linear_attention/ssd.py`` take whole, with the rows' slots.
STATE_BY_REFERENCE = {"conv": False, "ssm": True}


def init_paged_cache(config: NemotronHConfig, num_blocks: int, block_size: int,
                     dtype=jnp.bfloat16, state_slots: int = 64):
    """The KV pool of the ``*`` layers alone, under ``STATE`` the ``M`` layers'
    two leaves (``state_slots`` slots and a trash slot each: ``conv`` ``[L_M, slots
    + 1, taps - 1, I + 2 G N]`` in the pool's dtype, ``ssm`` ``[L_M, slots + 1, H,
    P, N]`` in FLOAT32 whatever the pool's dtype) and, under ``TALLY``, the running
    pick tallies (int32 ``[3]``: :func:`pick_tallies`).  An ``E`` layer has a row
    in none of them."""
    kinds = config.kinds
    _, conv, _ = ssm_widths(config)
    cache = transformer.init_paged_kv_pool(kinds.count("*"), config.num_kv_heads,
                                           config.head_dim, num_blocks, block_size, dtype)
    mamba, slots = kinds.count("M"), state_slots + 1
    cache[STATE] = {
        "conv": jnp.zeros((mamba, slots, config.conv_kernel - 1, conv), dtype),
        "ssm": jnp.zeros((mamba, slots, config.mamba_num_heads, config.mamba_head_dim,
                          config.ssm_state_size), jnp.float32)}
    cache[TALLY] = jnp.zeros((3, ), jnp.int32)
    return cache


def state_bytes_per_seq(config: NemotronHConfig, value_bytes: int = 2) -> int:
    """What one live sequence holds outside the paged pool, whatever its length:
    an ``M`` layer's float32 matrix a head and ``taps - 1`` rows of the filter's
    input (2,097,152 + 36,864 B published; 12,804,096 B at 6 such layers).  The
    engine reads a family's state off this function."""
    _, conv, _ = ssm_widths(config)
    a_layer = ((config.conv_kernel - 1) * conv * value_bytes
               + config.mamba_num_heads * config.mamba_head_dim * config.ssm_state_size * 4)
    return config.kinds.count("M") * a_layer


def state_scan(config: NemotronHConfig):
    """``(chunks(n, t, flat, walked), positions a chunk, layers, trips(walked))``
    for the serving counters, as ``granite_moe_hybrid.state_scan``: the layers
    that scan are the ``M`` layers."""
    from ..ops.linear_attention.ssd import CHUNK, scan_chunks, walk_trips
    layers = config.kinds.count("M")
    return ((lambda n, t, flat, walked: scan_chunks(n, t, flat, walked) * layers), CHUNK, layers,
            walk_trips)


def moe_picks_per_token(config: NemotronHConfig) -> int:
    return config.top_k * config.kinds.count("E")


def moe_expert_rows(config: NemotronHConfig, slots: int) -> int:
    """Rows the ``E`` layers' grouped matmuls of one pass over ``slots`` token slots run
    over: on a share the window its held picks are compacted into, the first trip's."""
    from ..moe.serving import expert_rows
    held = config.held_experts or config.num_experts
    return expert_rows(slots, config.top_k, held, config.num_experts) * config.kinds.count("E")


def pick_tallies(config: NemotronHConfig):
    """The ``ServeCounters`` fields that ``kv_cache[TALLY]``'s entries are, in order: the
    picks on experts held here, the trips beyond a layer's first, and the held experts a
    layer-pass's live picks named (each one's two matrices are read for it)."""
    return "moe_held_picks", "moe_overflow_windows", "moe_experts_hit"


def layers_by_kind(config: NemotronHConfig, segments):
    """``segments`` with every ``E`` position handed its layers' indices into the one
    expert stack (``lp[PART_ALONE]["layer"]`` ``[repeats]``: the ``E`` layers before
    the position, then one period's further each repeat): the list
    ``paged_forward`` takes as ``layers``."""
    kinds, out = config.kinds, []
    for (start, period, repeats), segment in zip(layer_segments(config), segments):
        a_period = kinds[start:start + period].count("E")
        out.append(tuple(
            lp if PART_ALONE not in lp else {**lp, PART_ALONE: {**lp[PART_ALONE], "layer": (
                kinds[:start + j].count("E")
                + jnp.arange(0, repeats * a_period, a_period, dtype=jnp.int32))}}
            for j, lp in enumerate(segment)))
    return out


def forward_paged(config: NemotronHConfig, params, tokens, n_tokens, start_pos,
                  block_tables, kv_cache, *, block_size: int, tp_axis: Optional[str] = None,
                  gather_logits: bool = True, live_token_bound: Optional[int] = None,
                  last_rows: bool = False):
    """Ragged chunked forward (``transformer.paged_forward`` states the
    contract): the ``M`` layers through ``mix`` and their sequences' carried
    leaves, the ``*`` layers over the pool, the ``E`` layers through ``alone``."""
    from ..moe.serving import expert_rows, sparse_moe_ffn, window_trips
    from ..ops.linear_attention import ssd_chunks, ssd_update
    if tp_axis is not None:
        raise NotImplementedError("nemotron_h: tensor-parallel serving is not implemented")
    D, H, KV, dh = config.hidden_size, config.num_heads, config.num_kv_heads, config.head_dim
    hm, p, ns, g = (config.mamba_num_heads, config.mamba_head_dim, config.ssm_state_size,
                    config.n_groups)
    inner, conv_dim, _ = ssm_widths(config)
    eps = config.norm_eps
    kv_cache = dict(kv_cache)
    tally = kv_cache.pop(TALLY)
    dtype = kv_cache["k"].dtype
    experts = params["experts"]

    def embed(tokens, safe_pos):
        return params["embed"][tokens].astype(dtype)

    def mix(lp, x, filtered, live, carried, places):
        m = lp[STATE_MIXER]
        u = rms_norm(x, lp["norm"], eps)
        lead = x.shape[:2]
        with jax.named_scope("ssm_mixer"):
            projected = u @ m["w_in"].astype(dtype)
            z, xbc = projected[..., :inner], projected[..., inner:inner + conv_dim]
            dt = projected[..., inner + conv_dim:].astype(jnp.float32)
            with jax.named_scope("ssm_state"):
                xbc, last = filtered(xbc, carried["conv"], m["filter"], m["conv_bias"])
            xbc = jax.nn.silu(xbc).astype(dtype)
            xs = xbc[..., :inner].reshape(lead + (hm, p))
            b = xbc[..., inner:inner + g * ns].reshape(lead + (g, ns))
            c = xbc[..., inner + g * ns:].reshape(lead + (g, ns))
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
            # the gate first, then the norm OVER EACH GROUP of I / G columns
            y = y.reshape(lead + (inner, )).astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
            y = rms_norm(y.reshape(lead + (g, inner // g)), m["norm"].reshape(g, inner // g), eps)
            y = y.reshape(lead + (inner, )).astype(dtype)
            x = x + y @ m["w_out"].astype(dtype)
        return x, {"conv": last, "ssm": state}

    def qkv(lp, x, safe_pos):  # no positions: the keys are cached as they are projected
        a = lp["attn"]
        u = rms_norm(x, lp["norm"], eps)
        lead = x.shape[:2]
        return ((u @ a["wq"].astype(dtype)).reshape(lead + (H, dh)),
                (u @ a["wk"].astype(dtype)).reshape(lead + (KV, dh)),
                (u @ a["wv"].astype(dtype)).reshape(lead + (KV, dh)), None)

    def finish(lp, x, kept, attn, live, handed):
        return x + attn.reshape(x.shape[:2] + (H * dh, )) @ lp["attn"]["wo"].astype(dtype), handed

    def alone(lp, x, live, handed):
        """An ``E`` layer whole; its norm and its residual under the experts' own scopes."""
        moe = lp[PART_ALONE]
        with jax.named_scope("moe_route"):
            u = rms_norm(x, lp["norm"], eps).reshape(-1, D)
        out, picks = sparse_moe_ffn(
            {"gate": moe["gate"], "experts": experts, "shared": moe["shared"]}, u, config.top_k, True,
            live.reshape(-1), layer=moe["layer"], scaling=config.routed_scaling_factor,
            scoring="sigmoid", norm_eps=1e-20, tally=("held", "experts_hit"))
        with jax.named_scope("moe_expert_ffn"):
            x = x + out.reshape(x.shape)
            # the held picks, and the trips the layer ran beyond its first
            window = expert_rows(live.size, config.top_k, experts["w_up"].shape[1],
                                 moe["gate"]["wg"].shape[-1])
            held, hit = picks
            picks = jnp.stack([held, jnp.maximum(window_trips(held, window) - 1, 0), hit])
        return x, picks if handed is None else handed + picks

    def head(x):  # untied: this chip's columns of the head
        return rms_norm(x, params["final_norm"], eps) @ params["head"].astype(dtype)

    logits, cache, left = transformer.paged_forward(
        layers_by_kind(config, params["segments"]), tokens, n_tokens, start_pos, block_tables,
        kv_cache, block_size=block_size, live_token_bound=live_token_bound, last_rows=last_rows,
        embed=embed, qkv=qkv, finish=finish, head=head, mix=mix, alone=alone, hand_on=True,
        by_reference=STATE_BY_REFERENCE)
    # what each period's chain ended with: [periods, 3] a stack, none where it held no E layer
    cache[TALLY] = tally + sum(jnp.sum(picks, axis=0) for picks in left if picks is not None)
    return logits, cache


def config_from_hf(hf_config) -> NemotronHConfig:
    """A ``NemotronHConfig`` from a transformers config of ``model_type``
    ``nemotron_h``; what the family does not implement raises in ``__post_init__``
    (a ``time_step_limit`` other than ``(0, inf)`` here)."""
    get = lambda name, default=None: getattr(hf_config, name, default)
    low, high = get("time_step_limit") or (0.0, float("inf"))
    if low > 0.0 or high != float("inf"):
        raise NotImplementedError(f"nemotron_h: a dt clamp (time_step_limit {low, high}) is not implemented")
    return NemotronHConfig(
        vocab_size=hf_config.vocab_size, hidden_size=hf_config.hidden_size,
        num_layers=hf_config.num_hidden_layers,
        hybrid_override_pattern=hf_config.hybrid_override_pattern,
        num_heads=hf_config.num_attention_heads, num_kv_heads=hf_config.num_key_value_heads,
        head_dim=get("head_dim") or hf_config.hidden_size // hf_config.num_attention_heads,
        mamba_num_heads=hf_config.mamba_num_heads, mamba_head_dim=hf_config.mamba_head_dim,
        ssm_state_size=hf_config.ssm_state_size, n_groups=hf_config.n_groups,
        expand=get("expand", 2), conv_kernel=hf_config.conv_kernel,
        use_conv_bias=bool(get("use_conv_bias", True)), mamba_proj_bias=bool(get("mamba_proj_bias", False)),
        mamba_hidden_act=get("mamba_hidden_act", "silu"), chunk_size=get("chunk_size", 128),
        num_experts=hf_config.n_routed_experts, top_k=hf_config.num_experts_per_tok,
        moe_intermediate_size=hf_config.moe_intermediate_size,
        shared_intermediate_size=hf_config.moe_shared_expert_intermediate_size,
        n_shared_experts=get("n_shared_experts", 1), n_group=get("n_group", 1),
        topk_group=get("topk_group", 1), norm_topk_prob=bool(get("norm_topk_prob", True)),
        routed_scaling_factor=float(get("routed_scaling_factor", 1.0)),
        mlp_hidden_act=get("mlp_hidden_act", "relu2"), mlp_bias=bool(get("mlp_bias", False)),
        attention_bias=bool(get("attention_bias", False)), use_bias=bool(get("use_bias", False)),
        tie_embeddings=bool(get("tie_word_embeddings", False)),
        max_seq_len=hf_config.max_position_embeddings, norm_eps=get("layer_norm_epsilon", 1e-5))
