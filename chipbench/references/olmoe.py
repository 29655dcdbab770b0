"""Plain reference of the OLMoE family (OLMoE-1B-7B, arXiv:2409.02060, and the
model's ``config.json``): a pre-norm decoder of RMSNorm, rotary multi-head
attention with QK-norm under a causal mask, and a mixture of SwiGLU experts
under a top-k router whose weights are not renormalised.  One layer:

    n1 = RMSNorm(x)
    q  = rope(heads(RMSNorm_q(Wq n1)));  k = rope(heads(RMSNorm_k(Wk n1)));  v = heads(Wv n1)
    h  = x + Wo Attn(q, k, v)
    n2 = RMSNorm(h)
    p  = softmax(Wg n2)                 over ALL experts, float32
    S  = the top-k indices of p;  weights = p[S], divided by their sum only
         where ``norm_topk_prob`` is true (OLMoE publishes false)
    y  = h + sum_{e in S} p[e] W_down_e( silu(W_gate_e n2) * (W_up_e n2) )

``RMSNorm_q`` / ``RMSNorm_k`` are QK-norm: an RMSNorm with a learned gain over
the WHOLE projected width (all heads together), before the split into heads
and before rotary.  It is in the paper and the model's code, not in
``config.json``: the configuration file lists it under ``assumed``.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision, one
sequence at a time, no kernel, no cache, no batching, no sorting and no
dispatch: EVERY expert is computed for every token and the outputs are
combined with a ``[S, E]`` matrix that holds ``p`` at a token's picks and zero
elsewhere.  Long contexts go through attention in blocks of queries so the
score matrix stays small.  Departures from the published model: none in the
mathematics; rotary uses the half-split ("rotate half") layout of the
published checkpoints; ``clip_qkv`` is null in the source and not implemented.

Nothing here comes from ``deepspeed_tpu``: sizes come from the configuration
file's published keys, weights from the seed.  ``init_params`` lays the weights
out as the pytree the program's ``models/olmoe.py`` takes (per-layer leaves
stacked on a leading layer axis, experts on a second) because that layout is
the program's input interface; the same arrays go to the program and to this
reference.
"""

import functools

import jax
import jax.numpy as jnp

Q_BLOCK = 512  # queries per attention block: 16 heads x 512 x 3072 keys x 4 B = 0.1 GB


def head_dim(sizes) -> int:
    return int(sizes.get("head_dim") or sizes["hidden_size"] // sizes["num_attention_heads"])


def init_params(sizes, key, dtype=jnp.float32):
    """Random weights from ``key`` (``jax.random.PRNGKey(seed)``):
    normal(0, 1/sqrt(fan_in)) projections and experts AND router, a
    normal(0, 0.02) embedding, unit norm and QK-norm gains.  The router is
    drawn at the projections' scale so that its logits are of unit scale: the
    top-k weights then sum to a third or more and the expert path carries real
    weight in the logits (a router drawn at 0.02 would give every expert 1/E
    and make the layer nearly invisible to the comparison).  Call it under
    ``jax.jit`` with the key as an argument (one program for every seed)."""
    d, f, dh = sizes["hidden_size"], sizes["intermediate_size"], head_dim(sizes)
    h, kv, n_layers = sizes["num_attention_heads"], sizes["num_key_value_heads"], \
        sizes["num_hidden_layers"]
    e = sizes["num_experts"]
    keys = jax.random.split(key, 10)

    def linear(key, *shape):
        """[..., fan_in, fan_out]"""
        return jax.random.normal(key, shape, dtype) * float(shape[-2]) ** -0.5

    return {
        "embed": jax.random.normal(keys[0], (sizes["vocab_size"], d), dtype) * 0.02,
        "layers": {
            "attn": {"wq": linear(keys[1], n_layers, d, h * dh),
                     "wk": linear(keys[2], n_layers, d, kv * dh),
                     "wv": linear(keys[3], n_layers, d, kv * dh),
                     "wo": linear(keys[4], n_layers, h * dh, d),
                     "q_norm": jnp.ones((n_layers, h * dh), dtype),
                     "k_norm": jnp.ones((n_layers, kv * dh), dtype)},
            "moe": {"gate": {"wg": linear(keys[5], n_layers, d, e)},
                    "experts": {"w_gate": linear(keys[6], n_layers, e, d, f),
                                "w_up": linear(keys[7], n_layers, e, d, f),
                                "w_down": linear(keys[8], n_layers, e, f, d)}},
            "attn_norm": jnp.ones((n_layers, d), dtype),
            "mlp_norm": jnp.ones((n_layers, d), dtype),
        },
        "final_norm": jnp.ones((d,), dtype),
        "lm_head": linear(keys[9], d, sizes["vocab_size"]),
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


def rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain


def rotary(x, positions, theta):
    """x [S, heads, Dh]; pairs (i, i + Dh/2) rotate by positions * theta^(-2i/Dh)."""
    half = x.shape[-1] // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def attention(q, k, v, q_block=Q_BLOCK):
    """q [S, H, Dh], k/v [S, KV, Dh]: query i sees keys j <= i; each group of
    H/KV query heads shares one KV head (OLMoE: H = KV, groups of one)."""
    s, h, dh = q.shape
    kv = k.shape[1]
    q_block = min(q_block, s)
    pad = (-s) % q_block
    qg = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(-1, q_block, kv, h // kv, dh)
    q_pos = jnp.arange(s + pad).reshape(-1, q_block)
    k_pos = jnp.arange(s)

    def block(args):
        qb, pos = args
        scores = jnp.einsum("qkgd,skd->kgqs", qb, k) * dh ** -0.5
        seen = k_pos[None, :] <= pos[:, None]
        probs = jax.nn.softmax(jnp.where(seen[None, None], scores, -jnp.inf), axis=-1)
        return jnp.einsum("kgqs,skd->qkgd", probs, v)

    out = jax.lax.map(block, (qg, q_pos))
    return out.reshape(-1, h, dh)[:s]


def router(n2, wg, top_k: int, renormalise: bool):
    """n2 [S, D] -> combine [S, E]: the softmax over all experts, kept at each
    token's ``top_k`` largest and zero elsewhere; never renormalised unless
    the source says so."""
    probs = jax.nn.softmax(n2 @ wg, axis=-1)
    top_p, top_idx = jax.lax.top_k(probs, top_k)
    if renormalise:
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    return jnp.zeros_like(probs).at[jnp.arange(probs.shape[0])[:, None], top_idx].set(top_p)


def experts_ffn(n2, combine, w):
    """Every expert over every token, one expert at a time, each output
    weighted by the token's ``combine`` column and summed."""

    def one(acc, inp):
        w_gate, w_up, w_down, weight = (a.astype(jnp.float32) for a in inp)
        out = (jax.nn.silu(n2 @ w_gate) * (n2 @ w_up)) @ w_down
        return acc + weight[:, None] * out, None

    acc, _ = jax.lax.scan(one, jnp.zeros_like(n2),
                          (w["w_gate"], w["w_up"], w["w_down"], combine.T))
    return acc


def hidden_states(sizes, params, ids):
    """ids [S] -> the final normed hidden states [S, D], float32."""
    h, kv, dh = sizes["num_attention_heads"], sizes["num_key_value_heads"], head_dim(sizes)
    eps, theta = sizes["rms_norm_eps"], float(sizes["rope_theta"])
    top_k, renormalise = sizes["num_experts_per_tok"], bool(sizes["norm_topk_prob"])
    positions = jnp.arange(ids.shape[0])
    x = params["embed"].astype(jnp.float32)[ids]

    def f32(tree):
        return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)

    def layer(x, w):
        attn = f32(w["attn"])
        a = rms_norm(x, f32(w["attn_norm"]), eps)
        q = rms_norm(a @ attn["wq"], attn["q_norm"], eps)  # over the whole width, before the split
        k = rms_norm(a @ attn["wk"], attn["k_norm"], eps)
        q = rotary(q.reshape(-1, h, dh), positions, theta)
        k = rotary(k.reshape(-1, kv, dh), positions, theta)
        v = (a @ attn["wv"]).reshape(-1, kv, dh)
        x = x + attention(q, k, v).reshape(-1, h * dh) @ attn["wo"]
        m = rms_norm(x, f32(w["mlp_norm"]), eps)
        combine = router(m, f32(w["moe"]["gate"]["wg"]), top_k, renormalise)
        # the experts are cast one at a time inside experts_ffn, not a layer's 64 at once
        return x + experts_ffn(m, combine, w["moe"]["experts"]), None

    x, _ = jax.lax.scan(layer, x, params["layers"])
    return rms_norm(x, params["final_norm"].astype(jnp.float32), eps)


@functools.partial(jax.jit, static_argnums=(0,))
def _logits_rows(sizes_items, params, ids, rows):
    with jax.default_matmul_precision("highest"):
        x = hidden_states(dict(sizes_items), params, ids)
        return x[rows] @ params["lm_head"].astype(jnp.float32)


def logits_rows(sizes, params, ids, rows):
    """Logits [len(rows), V] of one sequence ``ids`` [S] at positions ``rows``.
    The mask is causal, so tokens padded on after the last row change nothing."""
    return _logits_rows(_static(sizes), params, jnp.asarray(ids, jnp.int32),
                        jnp.asarray(rows, jnp.int32))


def _static(sizes):
    return tuple(sorted((k, v) for k, v in sizes.items()
                        if isinstance(v, (int, float)) or v is None))
