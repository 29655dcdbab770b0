"""Arcee AFMoE causal LM (Trinity-Large-Preview's ``config.json``, ``model_type:
afmoe``; HF ``modeling_afmoe.py`` for every layer) — serving only.

A decoder whose ATTENTION LAYERS ARE OF TWO KINDS: ``layer_types`` names each
layer ``sliding_attention`` or ``full_attention`` (published: every fourth full).
With ``N*`` RMS norms of a plain gain, one block is a SANDWICH: ``h = h +
N_post_attn(Attn(N_in(h)))``, ``h = h + N_post_mlp(FFN(N_pre_mlp(h)))``: a norm on
each sublayer's OUTPUT before it joins the stream.

- **Attention**: ``q``, ``k``, ``v`` and a gate ``g`` as wide as ``q`` from the
  normed stream, no biases; an RMS norm over each head of ``q`` and of ``k``
  with learned gains; GQA over the paged pool; the kernel's output times
  ``sigmoid(g)`` before ``W_o``.  A ``sliding_attention`` layer turns ``q`` and
  ``k`` by rotate-half rotary over the whole head and a token attends the
  ``sliding_window`` newest positions, itself among them; a ``full_attention``
  layer has NO positions and attends every earlier token.  Which kind a layer is
  is its place in the scanned period: :func:`forward_paged` hands
  ``transformer.paged_forward`` one window a layer of the period (None: full)
  and a windowed layer its rotary frequencies in ``lp`` (``qkv`` tells the kind
  by what ``lp`` holds, as every family's callables do).
- **FFN**: the first ``num_dense_layers`` layers a SwiGLU of ``intermediate_size``;
  every other layer ``num_experts`` SwiGLU experts of ``moe_intermediate_size``
  under a float32 sigmoid router whose picks are the top-k of ``score +
  expert_bias``, weighted by the picked scores without the bias over their sum
  (``route_norm``, + 1e-20) times ``route_scale``, plus one shared expert added
  whole (``moe/serving.py``).  ``num_local_experts`` of the experts' weights may
  be here (this chip's share of an expert-parallel deployment); only
  ``init_params`` reads that count, the forward reads the shapes.
- The embedding is multiplied by ``sqrt(hidden_size)`` (``mup_enabled``); the
  head is untied.

Parameters are laid out as they are scanned (``layer_segments``, as
``models/qwen3_next.py``): a run of layers that repeats a pattern of kinds is a
tuple of one stack a place of the pattern; the experts of all expert layers are
one stack.  Training and tensor parallelism are not implemented.
"""

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import transformer
from .lfm2 import rotate_half
from .transformer import rms_norm, swiglu_mlp

SLIDING, FULL = "sliding_attention", "full_attention"
ROUTE_NORM_EPS = 1e-20  # added to the picked scores' sum where they are renormalised


@dataclasses.dataclass(frozen=True)
class AfmoeConfig:
    vocab_size: int = 200192
    hidden_size: int = 3072
    num_layers: int = 60
    num_dense_layers: int = 6
    layer_types: Optional[tuple] = None  # None: every ``global_attn_every_n_layers``-th layer full
    global_attn_every_n_layers: int = 4
    sliding_window: int = 4096
    num_heads: int = 48
    num_kv_heads: int = 8
    head_dim: int = 128
    intermediate_size: int = 12288  # a dense layer's FFN
    moe_intermediate_size: int = 3072  # one expert's width, and the shared expert's
    num_experts: int = 256  # the router's width
    # experts whose weights are here: None = all; fewer = this chip's share of an
    # expert-parallel deployment, from expert 0.  Only ``init_params`` reads it.
    num_local_experts: Optional[int] = None
    top_k: int = 4
    num_shared_experts: int = 1
    score_func: str = "sigmoid"
    route_norm: bool = True
    route_scale: float = 2.448
    n_group: int = 1
    topk_group: int = 1
    rope_theta: float = 10000.0
    rope_scaling: Optional[dict] = None
    mup_enabled: bool = True
    hidden_act: str = "silu"
    tie_embeddings: bool = False
    max_seq_len: int = 262144
    norm_eps: float = 1e-5

    def __post_init__(self):
        every = self.global_attn_every_n_layers
        kinds = self.layer_types or tuple(FULL if (i + 1) % every == 0 else SLIDING
                                          for i in range(self.num_layers))
        object.__setattr__(self, "layer_types", tuple(kinds)[:self.num_layers])
        for what, wrong in (
                ("rope_scaling", self.rope_scaling), ("tie_word_embeddings", self.tie_embeddings),
                (f"score_func {self.score_func!r}", self.score_func != "sigmoid"),
                (f"hidden_act {self.hidden_act!r}", self.hidden_act != "silu"),
                ("expert groups", (self.n_group, self.topk_group) != (1, 1)),
                (f"layer_types {set(self.layer_types) - {SLIDING, FULL}}",
                 set(self.layer_types) - {SLIDING, FULL} or len(self.layer_types) != self.num_layers)):
            if wrong:
                raise NotImplementedError(
                    f"afmoe: {what} is not implemented (published: plain rotary, an untied head, "
                    f"a sigmoid router over one group, silu)")

    @staticmethod
    def trinity_large_preview():
        return AfmoeConfig()

    @staticmethod
    def tiny(vocab=256, hidden=64, layers=9, dense_layers=1, heads=4, kv_heads=2, head_dim=16,
             window=16, experts=8, local_experts=None, top_k=2, seq=512):
        return AfmoeConfig(
            vocab_size=vocab, hidden_size=hidden, num_layers=layers, num_dense_layers=dense_layers,
            sliding_window=window, num_heads=heads, num_kv_heads=kv_heads, head_dim=head_dim,
            intermediate_size=2 * hidden, moe_intermediate_size=hidden // 2, num_experts=experts,
            num_local_experts=local_experts, top_k=top_k, max_seq_len=seq)


def attention_windows(config: AfmoeConfig) -> tuple:
    """One window a layer: ``sliding_window`` keys for a windowed layer, None for
    one that attends its whole past (the serving counters read a family's layer
    kinds off this function)."""
    return tuple(config.sliding_window if kind == SLIDING else None for kind in config.layer_types)


def layer_segments(config: AfmoeConfig):
    """``[(start, period, repeats)]``: the layers as runs that repeat a pattern of
    kinds (``transformer.repeating_runs``), a kind being the attention's and the
    FFN's.  Published: three dense runs, then ``[(6, 4, 13), (58, 1, 1), (59, 1,
    1)]``: thirteen periods of (sliding, full, sliding, sliding)."""
    return transformer.repeating_runs([
        (kind, i < config.num_dense_layers) for i, kind in enumerate(config.layer_types)])


def rotary_inv_freq(config: AfmoeConfig) -> np.ndarray:
    dh = config.head_dim
    return config.rope_theta ** -(np.arange(0, dh, 2, dtype=np.float32) / dh)


def init_params(config: AfmoeConfig, key, dtype=jnp.float32):
    """``{"embed", "lm_head", "segments": [one tuple of per-place stacks a run of
    :func:`layer_segments`], "experts": [expert layers, held, ...], "final_norm"}``.
    Projections, experts and router at 1/sqrt(fan_in), every gain at one, a
    selection bias of normal(0, 0.64 / E) in float32 (nonzero: it chooses)."""
    d, dh, h, kv = config.hidden_size, config.head_dim, config.num_heads, config.num_kv_heads
    held = config.num_local_experts or config.num_experts
    n_moe = config.num_layers - config.num_dense_layers
    k_emb, k_head, k_layers, k_experts = jax.random.split(key, 4)

    def stack(key, *shape):
        return jax.random.normal(key, shape, dtype) * float(shape[-2]) ** -0.5

    def ffn(key, width, *lead):
        ks = jax.random.split(key, 3)
        return {"w_gate": stack(ks[0], *lead, d, width), "w_up": stack(ks[1], *lead, d, width),
                "w_down": stack(ks[2], *lead, width, d)}

    def place(key, depth, dense):
        ks = jax.random.split(key, 8)
        lp = {name: jnp.ones((depth, d), dtype)
              for name in ("in_norm", "post_attn_norm", "pre_mlp_norm", "post_mlp_norm")}
        lp["attn"] = {"wq": stack(ks[0], depth, d, h * dh), "wk": stack(ks[1], depth, d, kv * dh),
                      "wv": stack(ks[2], depth, d, kv * dh), "w_gate": stack(ks[3], depth, d, h * dh),
                      "wo": stack(ks[4], depth, h * dh, d),
                      "q_norm": jnp.ones((depth, dh), dtype), "k_norm": jnp.ones((depth, dh), dtype)}
        if dense:
            lp["mlp"] = ffn(ks[5], config.intermediate_size, depth)
        else:
            lp["moe"] = {"gate": {"wg": stack(ks[5], depth, d, config.num_experts),
                                  "bias": jax.random.normal(ks[6], (depth, config.num_experts),
                                                            jnp.float32) * (0.64 / config.num_experts)},
                         "shared": ffn(ks[7], config.moe_intermediate_size * config.num_shared_experts,
                                       depth)}
        return lp

    segments = []
    for start, period, repeats in layer_segments(config):
        keys = jax.random.split(jax.random.fold_in(k_layers, start), period)
        segments.append(tuple(place(keys[j], repeats, start + j < config.num_dense_layers)
                              for j in range(period)))
    return {"embed": jax.random.normal(k_emb, (config.vocab_size, d), dtype) * 0.02,
            "segments": segments,
            "experts": ffn(k_experts, config.moe_intermediate_size, n_moe, held),
            "final_norm": jnp.ones((d, ), dtype),
            "lm_head": transformer.init_linear(k_head, d, config.vocab_size, dtype=dtype)}


def _embed(config: AfmoeConfig, params, tokens, dtype):
    x = params["embed"][tokens]
    if config.mup_enabled:  # muP: the embedding times the root of the width
        x = x.astype(jnp.float32) * float(config.hidden_size) ** 0.5
    return x.astype(dtype)


def _qkv(config: AfmoeConfig, lp, x, positions):
    """The layer's projections: ``(q, k, v, gate)``, q and k normed a head and, in
    a layer that holds rotary frequencies (a windowed one), turned by ``positions``."""
    a, dtype, lead = lp["attn"], x.dtype, x.shape[:2]
    H, KV, dh = config.num_heads, config.num_kv_heads, config.head_dim
    u = rms_norm(x, lp["in_norm"], config.norm_eps)
    q = rms_norm((u @ a["wq"].astype(dtype)).reshape(lead + (H, dh)), a["q_norm"], config.norm_eps)
    k = rms_norm((u @ a["wk"].astype(dtype)).reshape(lead + (KV, dh)), a["k_norm"], config.norm_eps)
    v = (u @ a["wv"].astype(dtype)).reshape(lead + (KV, dh))
    if "inv_freq" in lp:
        q, k = rotate_half(q, positions, lp["inv_freq"]), rotate_half(k, positions, lp["inv_freq"])
    return q, k, v, u @ a["w_gate"].astype(dtype)


def _finish(config: AfmoeConfig, lp, x, gate, attn, live, experts):
    """The rest of a layer after the kernel: the gate, ``W_o``, the norm on the
    attention's output, then the FFN between its two norms."""
    from ..moe.serving import sparse_moe_ffn
    dtype, eps = x.dtype, config.norm_eps
    with jax.named_scope("attn_gate"):
        attn = (attn.reshape(gate.shape).astype(jnp.float32)
                * jax.nn.sigmoid(gate.astype(jnp.float32))).astype(dtype)
    x = x + rms_norm(attn @ lp["attn"]["wo"].astype(dtype), lp["post_attn_norm"], eps)
    h = rms_norm(x, lp["pre_mlp_norm"], eps)
    if "moe" in lp:
        moe = lp["moe"]
        out = sparse_moe_ffn({"gate": moe["gate"], "experts": experts, "shared": moe["shared"]},
                             h.reshape(-1, h.shape[-1]), config.top_k, config.route_norm,
                             None if live is None else live.reshape(-1), layer=moe["layer"],
                             scaling=config.route_scale, scoring=config.score_func,
                             norm_eps=ROUTE_NORM_EPS).reshape(h.shape)
    else:
        out = swiglu_mlp(lp["mlp"], h)
    return x + rms_norm(out, lp["post_mlp_norm"], eps)


def scanned_layers(config: AfmoeConfig, params):
    """``(layers, windows)`` as ``transformer.paged_forward`` takes them: each run
    of :func:`layer_segments` a tuple of its places' stacks, a windowed layer with
    its rotary frequencies and an expert layer with its index into the one stack
    of experts; beside it the run's tuple of one window a place."""
    inv_freq, of_layer = jnp.asarray(rotary_inv_freq(config)), attention_windows(config)
    layers, windows = [], []
    for (start, period, repeats), segment in zip(layer_segments(config), params["segments"]):
        run, here = [], []
        for j, lp in enumerate(segment):
            window = of_layer[start + j]
            if window is not None:
                lp = {**lp, "inv_freq": jnp.broadcast_to(inv_freq, (repeats, ) + inv_freq.shape)}
            if "moe" in lp:
                first = start + j - config.num_dense_layers
                lp = {**lp, "moe": {**lp["moe"], "layer": first + jnp.arange(
                    0, repeats * period, period, dtype=jnp.int32)}}
            run.append(lp)
            here.append(window)
        layers.append(tuple(run))
        windows.append(tuple(here))
    return layers, windows


def forward(config: AfmoeConfig, params, tokens):
    """The plain forward over whole sequences, no cache: tokens ``[B, S]`` ->
    logits ``[B, S, V]`` in the parameters' dtype, every layer taken from its stack
    in the order the layers are numbered."""
    dtype = params["final_norm"].dtype
    s = tokens.shape[1]
    positions = jnp.broadcast_to(jnp.arange(s), tokens.shape)
    x = _embed(config, params, tokens, dtype)
    layers, windows = scanned_layers(config, params)
    for run, here in zip(layers, windows):
        for i in range(jax.tree_util.tree_leaves(run[0])[0].shape[0]):
            for stack, window in zip(run, here):
                lp = jax.tree_util.tree_map(lambda a, i=i: a[i], stack)
                q, k, v, gate = _qkv(config, lp, x, positions)
                seen = None if window is None else (
                    jnp.arange(s)[None, :] > jnp.arange(s)[:, None] - window)[None, None]
                attn = transformer.sdpa(q, k, v, causal=True, mask=seen)
                x = _finish(config, lp, x, gate, attn, None, params["experts"])
    return rms_norm(x, params["final_norm"], config.norm_eps) @ params["lm_head"].astype(dtype)


# --------------------------------------------------------- paged (ragged) serve
def init_paged_cache(config: AfmoeConfig, num_blocks: int, block_size: int, dtype=jnp.bfloat16):
    """One pool for every layer, windowed or full: a block table a sequence, so a
    windowed layer keeps the blocks behind its window too (the kernel does not walk
    them; ``ServeCounters.kv_blocks_behind_window`` counts them)."""
    return transformer.init_paged_kv_pool(config.num_layers, config.num_kv_heads, config.head_dim,
                                          num_blocks, block_size, dtype)


def moe_picks_per_token(config: AfmoeConfig) -> int:
    return config.top_k * (config.num_layers - config.num_dense_layers)


def moe_expert_rows(config: AfmoeConfig, slots: int) -> int:
    """Rows the expert layers' grouped matmuls of one pass over ``slots`` token slots run
    over: on a share the window its held picks are compacted into, the first trip's."""
    from ..moe.serving import expert_rows
    held = config.num_local_experts or config.num_experts
    return expert_rows(slots, config.top_k, held, config.num_experts) \
        * (config.num_layers - config.num_dense_layers)


def forward_paged(config: AfmoeConfig, params, tokens, n_tokens, start_pos, block_tables, kv_cache,
                  *, block_size: int, tp_axis: Optional[str] = None, gather_logits: bool = True,
                  live_token_bound: Optional[int] = None, last_rows: bool = False):
    """Ragged chunked forward (``transformer.paged_forward`` states the contract):
    gated attention over the paged pool, a window a layer of the period, the dense
    or the expert FFN between sandwich norms."""
    if tp_axis is not None:
        raise NotImplementedError("afmoe: tensor-parallel serving is not implemented (the "
                                  "deployment it is cut for is expert-parallel)")
    dtype = kv_cache["k"].dtype
    layers, windows = scanned_layers(config, params)

    def embed(tokens, safe_pos):
        return _embed(config, params, tokens, dtype)

    def qkv(lp, x, safe_pos):
        return _qkv(config, lp, x, safe_pos)

    def finish(lp, x, gate, attn, live):
        return _finish(config, lp, x, gate, attn, live, params["experts"])

    def head(x):
        return rms_norm(x, params["final_norm"], config.norm_eps) @ params["lm_head"].astype(dtype)

    return transformer.paged_forward(
        layers, tokens, n_tokens, start_pos, block_tables, kv_cache, block_size=block_size,
        live_token_bound=live_token_bound, last_rows=last_rows, embed=embed, qkv=qkv, finish=finish,
        head=head, window=windows)


# ------------------------------------------------------------- HF checkpoints
def config_from_hf(hf_config) -> AfmoeConfig:
    """An ``AfmoeConfig`` from a transformers ``AfmoeConfig``."""
    return AfmoeConfig(
        vocab_size=hf_config.vocab_size, hidden_size=hf_config.hidden_size,
        num_layers=hf_config.num_hidden_layers, num_dense_layers=hf_config.num_dense_layers,
        layer_types=tuple(hf_config.layer_types),
        global_attn_every_n_layers=hf_config.global_attn_every_n_layers,
        sliding_window=hf_config.sliding_window, num_heads=hf_config.num_attention_heads,
        num_kv_heads=hf_config.num_key_value_heads, head_dim=hf_config.head_dim,
        intermediate_size=hf_config.intermediate_size,
        moe_intermediate_size=hf_config.moe_intermediate_size, num_experts=hf_config.num_experts,
        top_k=hf_config.num_experts_per_tok, num_shared_experts=hf_config.num_shared_experts,
        score_func=hf_config.score_func, route_norm=bool(hf_config.route_norm),
        route_scale=float(hf_config.route_scale), n_group=getattr(hf_config, "n_group", 1),
        topk_group=getattr(hf_config, "topk_group", 1), rope_theta=float(hf_config.rope_theta),
        rope_scaling=hf_config.rope_scaling, mup_enabled=bool(hf_config.mup_enabled),
        hidden_act=hf_config.hidden_act, tie_embeddings=bool(hf_config.tie_word_embeddings),
        max_seq_len=hf_config.max_position_embeddings, norm_eps=hf_config.rms_norm_eps)


# our leaf of a layer's ``lp`` -> the name under ``model.layers.{i}.`` (torch Linear is [out, in])
HF_NAMES = {
    ("in_norm", ): "input_layernorm.weight",
    ("post_attn_norm", ): "post_attention_layernorm.weight",
    ("pre_mlp_norm", ): "pre_mlp_layernorm.weight",
    ("post_mlp_norm", ): "post_mlp_layernorm.weight",
    ("attn", "wq"): "self_attn.q_proj.weight", ("attn", "wk"): "self_attn.k_proj.weight",
    ("attn", "wv"): "self_attn.v_proj.weight", ("attn", "wo"): "self_attn.o_proj.weight",
    ("attn", "w_gate"): "self_attn.gate_proj.weight",
    ("attn", "q_norm"): "self_attn.q_norm.weight", ("attn", "k_norm"): "self_attn.k_norm.weight",
    ("mlp", "w_gate"): "mlp.gate_proj.weight", ("mlp", "w_up"): "mlp.up_proj.weight",
    ("mlp", "w_down"): "mlp.down_proj.weight",
    ("moe", "gate", "wg"): "mlp.router.gate.weight", ("moe", "gate", "bias"): "mlp.expert_bias",
    ("moe", "shared", "w_gate"): "mlp.shared_experts.gate_proj.weight",
    ("moe", "shared", "w_up"): "mlp.shared_experts.up_proj.weight",
    ("moe", "shared", "w_down"): "mlp.shared_experts.down_proj.weight",
}
HF_EXPERT_NAMES = {"w_gate": "gate_proj", "w_up": "up_proj", "w_down": "down_proj"}


def from_hf_state_dict(config: AfmoeConfig, state_dict, dtype=jnp.float32):
    """A HF ``AfmoeForCausalLM`` state dict as the pytree :func:`init_params`
    makes; the experts held are ``0 .. num_local_experts - 1``."""
    from .transformer import hf_tensor
    held = config.num_local_experts or config.num_experts

    def leaf(path, layer):
        w = hf_tensor(state_dict, f"model.layers.{layer}.{HF_NAMES[path]}")
        return w.T if w.ndim == 2 else w

    segments = []
    for start, period, repeats in layer_segments(config):
        run = []
        for j in range(period):
            at = range(start + j, start + repeats * period, period)
            dense = start + j < config.num_dense_layers
            lp = {}
            for path in HF_NAMES:
                if path[0] == ("moe" if dense else "mlp"):
                    continue
                node = lp
                for name in path[:-1]:
                    node = node.setdefault(name, {})
                node[path[-1]] = jnp.asarray(np.stack([leaf(path, i) for i in at]),
                                             jnp.float32 if path[-1] == "bias" else dtype)
            run.append(lp)
        segments.append(tuple(run))
    experts = {ours: jnp.asarray(np.stack([np.stack([hf_tensor(
        state_dict, f"model.layers.{i}.mlp.experts.{e}.{theirs}.weight").T for e in range(held)])
        for i in range(config.num_dense_layers, config.num_layers)]), dtype)
        for ours, theirs in HF_EXPERT_NAMES.items()}
    return {"embed": jnp.asarray(hf_tensor(state_dict, "model.embed_tokens.weight"), dtype),
            "segments": segments, "experts": experts,
            "final_norm": jnp.asarray(hf_tensor(state_dict, "model.norm.weight"), dtype),
            "lm_head": jnp.asarray(hf_tensor(state_dict, "lm_head.weight").T, dtype)}
