"""Plain reference of DeepSeek-V2 (arXiv:2405.04434, the model's ``config.json``
and HF ``modeling_deepseek.py``), as ONE CHIP'S SHARE of a deployment in which
``EP_CHIPS`` = 4 chips share each layer: a pre-norm decoder of RMSNorm,
multi-head latent attention (MLA) under a causal mask, one leading dense
SwiGLU layer and then expert layers.  One layer, x ``[S, D]``:

    n1   = RMSNorm(x)
    c_q  = RMSNorm_q(n1 W_qa)                                  (q_lora_rank)
    [q_nope | q_pe]_h = (c_q W_qb)_h                           (128 + 64 a head)
    [c_kv | k_pe] = n1 W_kva ;  c_kv = RMSNorm_kv(c_kv)        (512 + 64, ONE k_pe for all heads)
    [k_nope | v]_h = (c_kv W_kvb)_h                            (128 + 128 a head)
    q_pe, k_pe rotated by YaRN-scaled rotary over the pairs (2i, 2i + 1)
    score_h = (q_nope_h . k_nope_h + q_pe_h . k_pe) * (128 + 64)^-0.5 * m^2
    h    = x + W_o concat_h( softmax_causal(score_h) v_h )
    n2   = RMSNorm(h)
    dense layer :  y = h + W_down( silu(W_gate n2) * (W_up n2) )
    expert layer:  s = softmax(W_g n2) over ALL ``EP_CHIPS x held`` experts, float32;
                   the experts lie in ``n_group`` equal groups, a group scores its
                   largest s, the ``topk_group`` best groups are kept and the top
                   ``num_experts_per_tok`` taken among their experts; weights
                   ``s_i * routed_scaling_factor``, never renormalised
                   (``norm_topk_prob`` false);
                   y = h + sum_{i picked AND held here} w_i E_i(n2) + Shared(n2)

``m = 0.1 * mscale_all_dim * ln(factor) + 1`` (1.2608 as published); with
``mscale == mscale_all_dim`` YaRN leaves cos and sin unscaled.

**The share.**  The configuration's ``n_routed_experts`` is the number of
experts whose weights are HERE (40 of the published 160): this chip is chip 0 of
four, holds experts 0..39 (groups 0 and 1 of 8), routes over all 160, adds its
own experts' part and the shared expert (which every chip computes for its own
tokens), and leaves out what the other three chips' experts would add.  That
partial sum goes on to the next layer.  ``vocab_size`` is this chip's quarter of
the vocabulary (embedding and head rows).  Attention is whole on every chip.
``layer_parts`` returns the routed part of any chip's share apart from the
shared expert's, so a test can add the four up to the uncut layer.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision, one
sequence at a time: NOT absorbed (``k_nope`` and ``v`` are expanded for every
head), no cache, no kernel, no sorting and no dispatch (every held expert is
computed for every token and combined through a ``[S, E]`` matrix of weights).
So that an 8k-token prompt fits beside 10 GB of weights, attention runs over
blocks of heads and, inside, blocks of queries; that changes no number's
meaning.  Departures from the published model: none in the mathematics.

Nothing here comes from ``deepspeed_tpu``: sizes come from the configuration
file's published keys, weights from the seed.  ``init_params`` lays the weights
out as the pytree the program's ``models/deepseek_v2.py`` takes (two stacks of
layers, ``dense_layers`` and ``layers``, experts on a second axis) because that
layout is the program's input interface; the same arrays go to both.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

EP_CHIPS = 4    # chips that share each layer in the deployment this file is one chip of
Q_BLOCK = 512   # queries per attention block
HEAD_BLOCK = 16  # heads per attention block: 16 x 512 x 9216 keys x 4 B = 0.3 GB of scores


def router_width(sizes) -> int:
    """Experts the router scores: the held count times the chips of the deployment."""
    return EP_CHIPS * sizes["n_routed_experts"]


def init_params(sizes, key, dtype=jnp.float32):
    """Random weights from ``key``: normal(0, 1/sqrt(fan_in)) projections,
    experts AND router (router logits of unit scale, so that routing is not
    uniform by accident), a normal(0, 0.02) embedding, unit norm gains.  A
    routed expert's ``W_down`` is drawn at that scale over
    ``routed_scaling_factor``, so that a pick weighs ``s_i`` (about 0.02, the
    picked probability of unit-scale logits over 160 experts) and not 16 times
    it: the factor is in a trained checkpoint because its probabilities and
    its experts' outputs are small, and a random draw has to be given that.
    Routing is discrete: a bfloat16 engine and this float32 reference break a
    near-tie between two experts, or between two groups, differently, and the
    token's later layers then route differently too.  The comparison pools six
    rows, so one such row is the whole reading, and how far it can move is the
    routed part's share of the residual stream: drawn at the full scale six
    pooled rows read 0.023-0.285 over 15 seeds, at a quarter 0.019-0.054 over
    27 seeds and then 0.104 at one of the driver's, at an eighth that seed
    reads 0.044 and at this scale 0.028 (chip runs, PR 31).  Call it under
    ``jax.jit``."""
    d, h = sizes["hidden_size"], sizes["num_attention_heads"]
    qk = sizes["qk_nope_head_dim"] + sizes["qk_rope_head_dim"]
    kv_out = sizes["qk_nope_head_dim"] + sizes["v_head_dim"]
    rank, q_rank = sizes["kv_lora_rank"], sizes["q_lora_rank"]
    n_dense = sizes["first_k_dense_replace"]
    n_moe = sizes["num_hidden_layers"] - n_dense
    held, fe = sizes["n_routed_experts"], sizes["moe_intermediate_size"]
    fs = fe * sizes["n_shared_experts"]
    keys = iter(jax.random.split(key, 32))

    def linear(*shape):
        """[..., fan_in, fan_out]"""
        return jax.random.normal(next(keys), shape, dtype) * float(shape[-2]) ** -0.5

    def attention(depth):
        return {"wq_a": linear(depth, d, q_rank), "q_norm": jnp.ones((depth, q_rank), dtype),
                "wq_b": linear(depth, q_rank, h * qk),
                "wkv_a": linear(depth, d, rank + sizes["qk_rope_head_dim"]),
                "kv_norm": jnp.ones((depth, rank), dtype),
                "wkv_b": linear(depth, rank, h * kv_out),
                "wo": linear(depth, h * sizes["v_head_dim"], d)}

    def ffn(width, *lead, out_scale=1.0):
        return {"w_gate": linear(*lead, d, width), "w_up": linear(*lead, d, width),
                "w_down": linear(*lead, width, d) * out_scale}

    def norms(depth):
        return {"attn_norm": jnp.ones((depth, d), dtype), "mlp_norm": jnp.ones((depth, d), dtype)}

    return {
        "embed": jax.random.normal(next(keys), (sizes["vocab_size"], d), dtype) * 0.02,
        "dense_layers": {"attn": attention(n_dense),
                         "mlp": ffn(sizes["intermediate_size"], n_dense), **norms(n_dense)},
        "layers": {"attn": attention(n_moe),
                   "moe": {"gate": {"wg": linear(n_moe, d, router_width(sizes))},
                           "experts": ffn(fe, n_moe, held,
                                          out_scale=1.0 / sizes["routed_scaling_factor"]),
                           "shared": ffn(fs, n_moe)},
                   **norms(n_moe)},
        "final_norm": jnp.ones((d,), dtype),
        "lm_head": linear(d, sizes["vocab_size"]),
    }


def round_to(x, fmt):
    """``x`` rounded to the float format ``fmt`` (exponent and mantissa bits)
    under a per-tensor power-of-two scale that puts its largest magnitude at
    the format's largest value, as fp8 is used in practice; the type stays.
    ``lax.reduce_precision`` and not a pair of casts: the TPU compiler drops a
    cast down and up again as excess precision."""
    top = jnp.max(jnp.abs(x)).astype(jnp.float32)
    scale = jnp.exp2(jnp.ceil(jnp.log2(jnp.maximum(top, 1e-30) / fmt["largest"]))).astype(x.dtype)
    return jax.lax.reduce_precision(x / scale, fmt["exponent_bits"], fmt["mantissa_bits"]) * scale


def round_weights_to(params, fmt):
    """The precision control for serving: every weight tensor rounded to
    ``fmt`` (fp8 below bfloat16).  Norm gains are ones and stay ones."""
    return jax.tree_util.tree_map(lambda w: round_to(w, fmt), params)


def f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


def rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain


def swiglu(x, w):
    w = f32(w)
    return (jax.nn.silu(x @ w["w_gate"]) * (x @ w["w_up"])) @ w["w_down"]


# ------------------------------------------------------------------- rotary
def yarn_mscale(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(dim: int, theta: float, scaling) -> np.ndarray:
    """Inverse frequencies of the ``dim / 2`` rotary pairs.  YaRN: a pair that
    turns more than ``beta_fast`` times over the original context keeps its
    frequency, one that turns fewer than ``beta_slow`` times has it divided by
    ``factor``, and between the two pair indices the blend is linear."""
    plain = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    if not scaling:
        return plain.astype(np.float32)
    assert scaling["type"] == "yarn", scaling

    def pair_that_turns(times):
        return dim * math.log(scaling["original_max_position_embeddings"]
                              / (times * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(pair_that_turns(scaling["beta_fast"])), 0)
    high = min(math.ceil(pair_that_turns(scaling["beta_slow"])), dim - 1)
    if high == low:
        high += 0.001
    interpolated = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    return (plain * (1 - interpolated) + plain / scaling["factor"] * interpolated).astype(np.float32)


def rotary(x, positions, inv_freq, table_scale):
    """x [S, heads, d]: the pair (x[2i], x[2i + 1]) rotates by positions * inv_freq[i]."""
    angle = positions.astype(jnp.float32)[:, None, None] * jnp.asarray(inv_freq)[None, None, :]
    cos, sin = jnp.cos(angle) * table_scale, jnp.sin(angle) * table_scale
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin], axis=-1).reshape(x.shape)


# ---------------------------------------------------------------- attention
def causal_attention(q, k, v, scale, q_block=Q_BLOCK):
    """q/k [S, h, dk], v [S, h, dv]: query i sees keys j <= i, head by head."""
    s, h, dk = q.shape
    q_block = min(q_block, s)
    pad = (-s) % q_block
    qb = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(-1, q_block, h, dk)
    q_pos = jnp.arange(s + pad).reshape(-1, q_block)
    k_pos = jnp.arange(s)

    def block(args):
        qs, pos = args
        scores = jnp.einsum("qhd,shd->hqs", qs, k) * scale
        seen = k_pos[None, :] <= pos[:, None]
        probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), axis=-1)
        return jnp.einsum("hqs,shd->qhd", probs, v)

    return jax.lax.map(block, (qb, q_pos)).reshape(-1, h, v.shape[-1])[:s]


def mla(sizes, a, n1, positions):
    """Multi-head latent attention of one layer, EXPANDED: every head gets its
    own ``k_nope`` and ``v`` from the latent.  Heads in blocks, so that q, k, v
    and the scores of a long prompt stay small; a block's part of ``W_o`` is
    applied at once and the parts are summed."""
    h, rank = sizes["num_attention_heads"], sizes["kv_lora_rank"]
    nope, rope, dv = sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"], sizes["v_head_dim"]
    eps, scaling = sizes["rms_norm_eps"], sizes.get("rope_scaling")
    inv_freq = yarn_inv_freq(rope, float(sizes["rope_theta"]), scaling)
    scale, table_scale = (nope + rope) ** -0.5, 1.0
    if scaling:
        table_scale = (yarn_mscale(scaling["factor"], scaling.get("mscale", 1))
                       / yarn_mscale(scaling["factor"], scaling.get("mscale_all_dim", 0)))
        if scaling.get("mscale_all_dim"):
            scale *= yarn_mscale(scaling["factor"], scaling["mscale_all_dim"]) ** 2

    c_q = rms_norm(n1 @ a["wq_a"].astype(jnp.float32), a["q_norm"].astype(jnp.float32), eps)
    kv = n1 @ a["wkv_a"].astype(jnp.float32)
    c_kv = rms_norm(kv[:, :rank], a["kv_norm"].astype(jnp.float32), eps)
    k_pe = rotary(kv[:, None, rank:], positions, inv_freq, table_scale)  # [S, 1, rope]: all heads'

    hb = math.gcd(h, HEAD_BLOCK)
    blocks = (a["wq_b"].reshape(-1, h // hb, hb, nope + rope).swapaxes(0, 1),
              a["wkv_b"].reshape(rank, h // hb, hb, nope + dv).swapaxes(0, 1),
              a["wo"].reshape(h // hb, hb * dv, -1))

    def heads(out, w):
        wq_b, wkv_b, wo = f32(w)
        q = jnp.einsum("sr,rhd->shd", c_q, wq_b)
        k_v = jnp.einsum("sr,rhd->shd", c_kv, wkv_b)
        q = jnp.concatenate([q[..., :nope], rotary(q[..., nope:], positions, inv_freq,
                                                   table_scale)], axis=-1)
        k = jnp.concatenate([k_v[..., :nope], jnp.broadcast_to(k_pe, (k_pe.shape[0], hb, rope))],
                            axis=-1)
        o = causal_attention(q, k, k_v[..., nope:], scale)
        return out + o.reshape(o.shape[0], hb * dv) @ wo, None

    out, _ = jax.lax.scan(heads, jnp.zeros_like(n1), blocks)
    return out


# ------------------------------------------------------------ expert layer
def router(sizes, n2, wg):
    """n2 [S, D] -> combine [S, E]: the group-limited top-k of the softmax over
    all E experts, times the scaling factor, zero elsewhere."""
    probs = jax.nn.softmax(n2 @ wg, axis=-1)
    s, e = probs.shape
    groups = sizes["n_group"]
    group_score = probs.reshape(s, groups, e // groups).max(axis=-1)
    _, best = jax.lax.top_k(group_score, sizes["topk_group"])
    kept = jnp.zeros((s, groups), bool).at[jnp.arange(s)[:, None], best].set(True)
    allowed = jnp.where(jnp.repeat(kept, e // groups, axis=1), probs, 0.0)
    top_p, top_idx = jax.lax.top_k(allowed, sizes["num_experts_per_tok"])
    if sizes["norm_topk_prob"]:
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    else:
        top_p = top_p * sizes["routed_scaling_factor"]
    return jnp.zeros_like(probs).at[jnp.arange(s)[:, None], top_idx].set(top_p)


def experts_ffn(n2, combine, w, layer):
    """Every expert of layer ``layer`` of the stack ``w`` (leaves [L, E, ...])
    over every token, one at a time, each output weighted by the token's
    ``combine`` column and summed.  An expert's three matrices are taken from
    the stack as they are needed: a layer's 40 experts are 1.9 GB."""

    def one(acc, inp):
        e, weight = inp
        out = swiglu(n2, {name: m[layer, e] for name, m in w.items()})
        return acc + weight.astype(jnp.float32)[:, None] * out, None

    acc, _ = jax.lax.scan(one, jnp.zeros_like(n2),
                          (jnp.arange(w["w_gate"].shape[1]), combine.T))
    return acc


def layer_parts(sizes, moe, n2, chip: int = 0, layer=None):
    """(routed, shared) of one expert layer over n2 [S, D] for the chip that
    holds experts ``chip * held ... (chip + 1) * held - 1`` (``moe["experts"]``
    are those ``held`` experts, or with ``layer`` the whole stack of them):
    the sum over the held experts a token picked, and the shared expert's
    output, which is the same on every chip."""
    experts = moe["experts"]
    if layer is None:
        experts, layer = jax.tree_util.tree_map(lambda m: m[None], experts), 0
    combine = router(sizes, n2, moe["gate"]["wg"].astype(jnp.float32))
    held = experts["w_gate"].shape[1]
    routed = experts_ffn(n2, combine[:, chip * held:(chip + 1) * held], experts, layer)
    return routed, swiglu(n2, moe["shared"])


def hidden_states(sizes, params, ids):
    """ids [S] -> the final normed hidden states [S, D], float32."""
    eps = sizes["rms_norm_eps"]
    positions = jnp.arange(ids.shape[0])
    x = params["embed"].astype(jnp.float32)[ids]
    moe_layers = dict(params["layers"])
    moe = dict(moe_layers.pop("moe"))
    experts = moe.pop("experts")  # stays one stack: a layer takes its experts one by one

    def attend(x, w):
        n1 = rms_norm(x, w["attn_norm"].astype(jnp.float32), eps)
        x = x + mla(sizes, w["attn"], n1, positions)
        return x, rms_norm(x, w["mlp_norm"].astype(jnp.float32), eps)

    def dense_layer(x, w):
        x, n2 = attend(x, w)
        return x + swiglu(n2, w["mlp"]), None

    def expert_layer(x, inp):
        w, gate_and_shared, l = inp
        x, n2 = attend(x, w)
        return x + sum(layer_parts(sizes, {**gate_and_shared, "experts": experts}, n2,
                                   layer=l)), None

    x, _ = jax.lax.scan(dense_layer, x, params["dense_layers"])
    x, _ = jax.lax.scan(expert_layer, x,
                        (moe_layers, moe, jnp.arange(experts["w_gate"].shape[0])))
    return rms_norm(x, params["final_norm"].astype(jnp.float32), eps)


@functools.partial(jax.jit, static_argnums=(0,))
def _logits_rows(sizes_items, params, ids, rows):
    with jax.default_matmul_precision("highest"):
        x = hidden_states(_thawed(sizes_items), params, ids)
        return x[rows] @ params["lm_head"].astype(jnp.float32)


def logits_rows(sizes, params, ids, rows):
    """Logits [len(rows), V] of one sequence ``ids`` [S] at positions ``rows``.
    The mask is causal, so tokens padded on after the last row change nothing."""
    return _logits_rows(_static(sizes), params, jnp.asarray(ids, jnp.int32),
                        jnp.asarray(rows, jnp.int32))


def _static(sizes):
    """The sizes as something hashable; a nested group (``rope_scaling``) too."""
    return tuple(sorted((k, _static(v) if isinstance(v, dict) else v) for k, v in sizes.items()
                        if isinstance(v, (int, float, str, dict)) or v is None))


def _thawed(items):
    return {k: _thawed(v) if isinstance(v, tuple) else v for k, v in items}
