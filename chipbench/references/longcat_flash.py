"""Plain reference of LongCat-Flash's language model (the ``config.json`` of
``meituan-longcat/LongCat-Flash-Omni``; HF ``modeling_longcat_flash.py``), as ONE
CHIP'S SHARE of a deployment in which ``EP_CHIPS`` = 32 chips share each layer.
A pre-norm decoder whose layer is TWO sublayers and ONE expert layer.  One
layer, ``h`` ``[S, D]`` the residual stream, ``N`` = RMSNorm with gain:

    a0 = h  + MLA_0(N(h))                    u = N(a0)
    s  = MoE(u)                              # the shortcut: computed here, added at the layer's end
    b0 = a0 + FFN_0(u)                       # dense SwiGLU, width ffn_hidden_size
    a1 = b0 + MLA_1(N(b0))
    h' = a1 + FFN_1(N(a1)) + s

    MLA_i(x):  c_q = N(x W_qa);  [q_nope | q_pe]_h = (c_q W_qb)_h         (128 + 64 a head)
               [c_kv | k_pe] = x W_kva;  c_kv = N(c_kv)                   (512 + 64, ONE k_pe)
               q_nope, q_pe times sqrt(hidden / q_lora_rank) = 2;  c_kv times
               sqrt(hidden / kv_lora_rank) = sqrt(12);  k_pe NOT scaled
               [k_nope | v]_h = (c_kv W_kvb)_h                            (128 + 128 a head)
               q_pe, k_pe rotated over the pairs (2i, 2i + 1), theta 1e7, no scaling
               score_h = (q_nope_h . k_nope_h + q_pe_h . k_pe) * (128 + 64)^-0.5, causal
               out = W_o concat_h( softmax(score_h) v_h )
    MoE(u):    p = softmax(u W_r) in float32 over ALL ``n_real + zero_expert_num`` outputs
               picks = top ``moe_topk`` of p + e_score_correction_bias
               w_i = p_i * routed_scaling_factor        (no bias, NOT renormalised)
               expert i < n_real: SwiGLU E_i of width expert_ffn_hidden_size
               expert i >= n_real: the identity (``zero_expert_type`` identity): adds w_i u
               s = sum over picks of w_i E_i(u)

Each sublayer's attention has its own weights and its own keys and values (a
served model: its own cache row).  Embedding and head untied; a final ``N``.

**The share.**  The configuration's ``n_routed_experts`` is the number of
experts whose weights are HERE (16 of the published 512): this chip is chip 0 of
32, holds experts 0..15, routes over all 512 + 256 outputs, adds its own
experts' part and the identity picks' part (which every chip computes for its
own tokens: an identity pick is never dispatched), and leaves out what the other
31 chips' experts would add.  That partial sum is ``s``.  ``vocab_size`` is this
chip's eighth of the vocabulary.  Attention and the dense FFNs are whole on
every chip.  ``layer_parts`` returns the held part of any chip's share apart
from the identity part, so a test can add the 32 up to the uncut layer.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision, one
sequence at a time: NOT absorbed (``k_nope`` and ``v`` expanded for every head),
no cache, no kernel, no sorting and no dispatch (a loop over the held experts,
each computed for every token and combined through a ``[S, E]`` matrix of
weights).  Attention runs over blocks of heads and of queries so that it stays
small beside 10 GB of weights; that changes no number's meaning.  Departures
from the published model: none in the mathematics (HF's rotary leaves q_pe and
k_pe de-interleaved, the same permutation on both, so every score is the same).

Nothing here comes from ``deepspeed_tpu``: sizes come from the configuration
file's published keys, weights from the seed.  ``init_params`` lays the weights
out as the pytree the program's ``models/longcat_flash.py`` takes (``layers``:
``sub0``, ``sub1``, ``moe``, every leaf a stack ``[num_layers, ...]``) because
that layout is the program's input interface; the same arrays go to both.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

EP_CHIPS = 32   # chips that share each layer in the deployment this file is one chip of
Q_BLOCK = 512   # queries per attention block
HEAD_BLOCK = 16  # heads per attention block
BIAS_SCALE = 0.64  # the selection bias is this over the router's width, times a normal


def router_width(sizes) -> int:
    """Outputs the router scores: the held experts times the chips of the
    deployment, and the identity experts, which no chip holds."""
    return EP_CHIPS * sizes["n_routed_experts"] + sizes["zero_expert_num"]


def init_params(sizes, key, dtype=jnp.float32):
    """Random weights from ``key``: normal(0, 1/sqrt(fan_in)) projections,
    experts AND router (router logits of unit scale, so that routing is not
    uniform by accident), a normal(0, 0.02) embedding, unit norm gains.  Three
    departures from a plain draw.  ``W_qb`` is drawn over the q scale and
    ``W_kvb`` over the kv scale (:func:`lora_scales`), so that q, k_nope and v
    come out at unit scale AFTER the model's two multiplications, as DeepSeek-V2's
    do without them: a checkpoint was trained with the scales in place, and a
    plain draw under them gives scores of standard deviation 5.8 (2 x sqrt(12) x
    sqrt(128) / sqrt(192)), an attention that is all but an argmax over the
    sequence, which a bfloat16 engine and this float32 reference break
    differently (sound chip runs read ``logit_rel_rms`` 0.37: PERF.md, PR 49).
    Program and reference still multiply as the model does, so a scale left out
    or put on ``k_pe`` shows.  The selection bias is ``BIAS_SCALE /
    width`` times a normal (a zero bias would leave ``score + bias`` untested;
    a large one would make the experts' loads, and the rate, follow the seed:
    LFM2's lesson).  A routed expert's ``W_down`` is drawn over
    ``routed_scaling_factor x moe_topk``: a pick weighs about 0.05 (six times a
    top probability of unit-scale logits over 768), and routing is discrete, so
    a bfloat16 engine and this float32 reference break a near-tie differently;
    on a compared row that must not decide ``correct`` (DeepSeek-V2's lesson).
    An identity pick's ``w u`` is that small already.  Call it under ``jax.jit``."""
    d, h, depth = sizes["hidden_size"], sizes["num_attention_heads"], sizes["num_layers"]
    qk = sizes["qk_nope_head_dim"] + sizes["qk_rope_head_dim"]
    kv_out = sizes["qk_nope_head_dim"] + sizes["v_head_dim"]
    rank, q_rank = sizes["kv_lora_rank"], sizes["q_lora_rank"]
    held, wide = sizes["n_routed_experts"], router_width(sizes)
    q_scale, kv_scale = lora_scales(sizes)
    keys = iter(jax.random.split(key, 32))

    def linear(*shape):
        """[depth, ..., fan_in, fan_out]"""
        return jax.random.normal(next(keys), (depth, ) + shape, dtype) * float(shape[-2]) ** -0.5

    def ffn(width, *lead, out_scale=1.0):
        return {"w_gate": linear(*lead, d, width), "w_up": linear(*lead, d, width),
                "w_down": linear(*lead, width, d) * out_scale}

    def sublayer():
        return {"attn_norm": jnp.ones((depth, d), dtype), "mlp_norm": jnp.ones((depth, d), dtype),
                "attn": {"wq_a": linear(d, q_rank), "q_norm": jnp.ones((depth, q_rank), dtype),
                         "wq_b": linear(q_rank, h * qk) / q_scale,
                         "wkv_a": linear(d, rank + sizes["qk_rope_head_dim"]),
                         "kv_norm": jnp.ones((depth, rank), dtype),
                         "wkv_b": linear(rank, h * kv_out) / kv_scale,
                         "wo": linear(h * sizes["v_head_dim"], d)},
                "mlp": ffn(sizes["ffn_hidden_size"])}

    embed = jax.random.normal(next(keys), (sizes["vocab_size"], d), dtype) * 0.02
    bias = jax.random.normal(next(keys), (depth, wide), dtype) * (BIAS_SCALE / wide)
    out_scale = 1.0 / (sizes["routed_scaling_factor"] * sizes["moe_topk"])
    return {
        "embed": embed,
        "layers": {"sub0": sublayer(), "sub1": sublayer(),
                   "moe": {"gate": {"wg": linear(d, wide), "bias": bias},
                           "experts": ffn(sizes["expert_ffn_hidden_size"], held,
                                          out_scale=out_scale)}},
        "final_norm": jnp.ones((d, ), dtype),
        "lm_head": jax.random.normal(next(keys), (d, sizes["vocab_size"]), dtype) * d ** -0.5,
    }


def lora_scales(sizes):
    """``(q, kv)``: what q (both parts) and the normed c_kv are multiplied by.
    config.json gives the two switches, modeling_longcat_flash.py the values."""
    d = sizes["hidden_size"]
    return ((d / sizes["q_lora_rank"]) ** 0.5 if sizes.get("mla_scale_q_lora", True) else 1.0,
            (d / sizes["kv_lora_rank"]) ** 0.5 if sizes.get("mla_scale_kv_lora", True) else 1.0)


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


def rotary(x, positions, inv_freq):
    """x [S, heads, d]: the pair (x[2i], x[2i + 1]) rotates by positions * inv_freq[i]."""
    angle = positions.astype(jnp.float32)[:, None, None] * jnp.asarray(inv_freq)[None, None, :]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
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
    """Latent attention of one sublayer, EXPANDED: every head gets its own
    ``k_nope`` and ``v`` from the (scaled) latent.  Heads in blocks; a block's
    part of ``W_o`` is applied at once and the parts are summed."""
    h, rank = sizes["num_attention_heads"], sizes["kv_lora_rank"]
    nope, rope, dv = sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"], sizes["v_head_dim"]
    eps = sizes["rms_norm_eps"]
    inv_freq = (float(sizes["rope_theta"]) ** (-np.arange(0, rope, 2, dtype=np.float64) / rope)
                ).astype(np.float32)
    q_scale, kv_scale = lora_scales(sizes)

    c_q = rms_norm(n1 @ a["wq_a"].astype(jnp.float32), a["q_norm"].astype(jnp.float32), eps)
    kv = n1 @ a["wkv_a"].astype(jnp.float32)
    c_kv = rms_norm(kv[:, :rank], a["kv_norm"].astype(jnp.float32), eps) * kv_scale
    k_pe = rotary(kv[:, None, rank:], positions, inv_freq)  # [S, 1, rope]: all heads', unscaled

    hb = math.gcd(h, HEAD_BLOCK)
    blocks = (a["wq_b"].reshape(-1, h // hb, hb, nope + rope).swapaxes(0, 1),
              a["wkv_b"].reshape(rank, h // hb, hb, nope + dv).swapaxes(0, 1),
              a["wo"].reshape(h // hb, hb * dv, -1))

    def heads(out, w):
        wq_b, wkv_b, wo = f32(w)
        q = jnp.einsum("sr,rhd->shd", c_q, wq_b) * q_scale  # both parts
        k_v = jnp.einsum("sr,rhd->shd", c_kv, wkv_b)
        q = jnp.concatenate([q[..., :nope], rotary(q[..., nope:], positions, inv_freq)], axis=-1)
        k = jnp.concatenate([k_v[..., :nope], jnp.broadcast_to(k_pe, (k_pe.shape[0], hb, rope))],
                            axis=-1)
        o = causal_attention(q, k, k_v[..., nope:], (nope + rope) ** -0.5)
        return out + o.reshape(o.shape[0], hb * dv) @ wo, None

    out, _ = jax.lax.scan(heads, jnp.zeros_like(n1), blocks)
    return out


# ------------------------------------------------------------ expert layer
def router(sizes, u, gate):
    """u [S, D] -> (combine [S, W], picks [S, k]): the picked outputs' softmax
    probabilities times the scaling factor, zero elsewhere, and which they are.
    The bias chooses and never weighs; nothing is renormalised."""
    probs = jax.nn.softmax(u @ gate["wg"].astype(jnp.float32), axis=-1)
    _, picks = jax.lax.top_k(probs + gate["bias"].astype(jnp.float32), sizes["moe_topk"])
    rows = jnp.arange(probs.shape[0])[:, None]
    weights = probs[rows, picks] * sizes["routed_scaling_factor"]
    return jnp.zeros_like(probs).at[rows, picks].set(weights), picks


def experts_ffn(u, combine, w, layer):
    """Every expert of layer ``layer`` of the stack ``w`` (leaves [L, E, ...])
    over every token, one at a time, each output weighted by the token's
    ``combine`` column and summed."""

    def one(acc, inp):
        e, weight = inp
        out = swiglu(u, {name: m[layer, e] for name, m in w.items()})
        return acc + weight[:, None] * out, None

    acc, _ = jax.lax.scan(one, jnp.zeros_like(u), (jnp.arange(w["w_gate"].shape[1]), combine.T))
    return acc


def layer_parts(sizes, moe, u, chip: int = 0, layer=None):
    """(held, identity, counts) of one expert layer over u [S, D] for the chip
    that holds experts ``chip * held ... (chip + 1) * held - 1``
    (``moe["experts"]`` are those ``held`` experts, or with ``layer`` the whole
    stack of them): the sum over the held experts a token picked; the identity
    picks' ``w u``, which is the same on every chip (a token's own chip
    computes it, once); and int32 ``[2]``, the picks on identity experts and on
    this chip's experts.  The real experts are the router's outputs before the
    last ``zero_expert_num``."""
    experts = moe["experts"]
    if layer is None:
        experts, layer = jax.tree_util.tree_map(lambda m: m[None], experts), 0
    combine, picks = router(sizes, u, moe["gate"])
    held = experts["w_gate"].shape[1]
    real = combine.shape[-1] - sizes["zero_expert_num"]
    routed = experts_ffn(u, combine[:, chip * held:(chip + 1) * held], experts, layer)
    identity = jnp.sum(combine[:, real:], axis=-1, keepdims=True) * u
    counts = jnp.stack([jnp.sum(picks >= real), jnp.sum(picks // held == chip)]).astype(jnp.int32)
    return routed, identity, counts


def hidden_states(sizes, params, ids):
    """ids [S] -> (the final normed hidden states [S, D] float32, int32 [2]: the
    sequence's picks on identity experts and on held experts, all layers)."""
    eps = sizes["rms_norm_eps"]
    positions = jnp.arange(ids.shape[0])
    x = params["embed"].astype(jnp.float32)[ids]
    layers = params["layers"]
    experts = layers["moe"]["experts"]  # stays one stack: a layer takes its experts one by one
    norm = lambda x, gain: rms_norm(x, gain.astype(jnp.float32), eps)

    def layer(h, inp):
        w0, w1, gate, l = inp
        a0 = h + mla(sizes, w0["attn"], norm(h, w0["attn_norm"]), positions)
        u = norm(a0, w0["mlp_norm"])
        held, identity, counts = layer_parts(sizes, {"gate": gate, "experts": experts}, u, layer=l)
        b0 = a0 + swiglu(u, w0["mlp"])
        a1 = b0 + mla(sizes, w1["attn"], norm(b0, w1["attn_norm"]), positions)
        return a1 + swiglu(norm(a1, w1["mlp_norm"]), w1["mlp"]) + (held + identity), counts

    x, counts = jax.lax.scan(layer, x, (layers["sub0"], layers["sub1"], layers["moe"]["gate"],
                                        jnp.arange(experts["w_gate"].shape[0])))
    return rms_norm(x, params["final_norm"].astype(jnp.float32), eps), jnp.sum(counts, axis=0)


@functools.partial(jax.jit, static_argnums=(0,))
def _logits_rows(sizes_items, params, ids, rows):
    with jax.default_matmul_precision("highest"):
        x, _ = hidden_states(dict(sizes_items), params, ids)
        return x[rows] @ params["lm_head"].astype(jnp.float32)


def logits_rows(sizes, params, ids, rows):
    """Logits [len(rows), V] of one sequence ``ids`` [S] at positions ``rows``.
    The mask is causal, so tokens padded on after the last row change nothing."""
    return _logits_rows(_static(sizes), params, jnp.asarray(ids, jnp.int32),
                        jnp.asarray(rows, jnp.int32))


def _static(sizes):
    """The sizes as something hashable."""
    return tuple(sorted((k, v) for k, v in sizes.items()
                        if isinstance(v, (int, float, str)) or v is None))
