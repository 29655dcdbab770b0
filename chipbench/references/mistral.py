"""Plain reference of the Mistral family: a decoder of RMSNorm, rotary
grouped-query attention under a causal sliding-window mask, and SwiGLU.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision, one
sequence at a time, no kernel, no cache, no batching; long contexts go through
attention in blocks of queries so that the score matrix stays small.  It follows
the published description (Mistral 7B, arXiv:2310.06825, and the model's
``config.json``).  Departures: none in the mathematics; the rotary embedding
uses the half-split ("rotate half") layout of the published checkpoints.

Nothing here comes from ``deepspeed_tpu``: sizes come from the configuration
file's published keys, weights from the seed.  ``init_params`` lays the weights
out as the pytree the program's llama-family modules take (per-layer leaves
stacked on a leading layer axis) because that layout is the program's input
interface; the same arrays go to the program and to this reference.
"""

import functools

import jax
import jax.numpy as jnp

Q_BLOCK = 512  # queries per attention block: 32 heads x 512 x 5120 keys x 4 B = 0.34 GB


def head_dim(sizes) -> int:
    return int(sizes.get("head_dim") or sizes["hidden_size"] // sizes["num_attention_heads"])


def init_params(sizes, key, dtype=jnp.float32):
    """Random weights from ``key`` (``jax.random.PRNGKey(seed)``):
    normal(0, 1/sqrt(fan_in)) projections, a normal(0, 0.02) embedding, unit
    norm gains.  Call it under ``jax.jit`` with the key as an argument: the
    weights are then drawn on the device, in ``dtype``, by one program that is
    the same for every seed (a seed closed over would be a constant of the
    program, and every seed would compile its own)."""
    d, f, dh = sizes["hidden_size"], sizes["intermediate_size"], head_dim(sizes)
    h, kv, n_layers = sizes["num_attention_heads"], sizes["num_key_value_heads"], \
        sizes["num_hidden_layers"]
    keys = jax.random.split(key, 9)

    def linear(key, fan_in, fan_out, stacked=True):
        shape = (n_layers, fan_in, fan_out) if stacked else (fan_in, fan_out)
        return jax.random.normal(key, shape, dtype) * float(fan_in) ** -0.5

    return {
        "embed": jax.random.normal(keys[0], (sizes["vocab_size"], d), dtype) * 0.02,
        "layers": {
            "attn": {"wq": linear(keys[1], d, h * dh), "wk": linear(keys[2], d, kv * dh),
                     "wv": linear(keys[3], d, kv * dh), "wo": linear(keys[4], h * dh, d)},
            "mlp": {"w_gate": linear(keys[5], d, f), "w_up": linear(keys[6], d, f),
                    "w_down": linear(keys[7], f, d)},
            "attn_norm": jnp.ones((n_layers, d), dtype),
            "mlp_norm": jnp.ones((n_layers, d), dtype),
        },
        "final_norm": jnp.ones((d,), dtype),
        "lm_head": linear(keys[8], d, sizes["vocab_size"], stacked=False),
    }


FP8_E4M3 = {"exponent_bits": 4, "mantissa_bits": 3, "largest": 240.0}


def round_to(x, fmt):
    """``x`` rounded to the float format ``fmt`` (exponent and mantissa bits)
    under a per-tensor power-of-two scale that puts its largest magnitude at
    the format's largest value (240 for e4m3 with an exponent kept for
    infinity, as ``reduce_precision`` has it), as fp8 is used in practice; the
    type stays.
    ``lax.reduce_precision`` and not a pair of casts: the TPU compiler drops a
    cast down and up again as excess precision, and the control then computes
    exactly what the reference does (my chip run, PR 23)."""
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


def attention(q, k, v, window, q_block=Q_BLOCK):
    """q [S, H, Dh], k/v [S, KV, Dh]: query i sees keys j with
    i - window < j <= i; each group of H/KV query heads shares one KV head."""
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
        if window is not None:
            seen &= k_pos[None, :] > pos[:, None] - window
        probs = jax.nn.softmax(jnp.where(seen[None, None], scores, -jnp.inf), axis=-1)
        return jnp.einsum("kgqs,skd->qkgd", probs, v)

    out = jax.lax.map(block, (qg, q_pos))
    return out.reshape(-1, h, dh)[:s]


def matmul_in(fmt):
    """``a @ w`` with both operands first rounded to ``fmt``: how the
    precision control computes its projections.  The rounding is passed
    straight through in the backward pass.  ``None`` is the reference itself:
    a plain float32 product."""
    if fmt is None:
        return jnp.matmul

    def rounded(x):
        return x + jax.lax.stop_gradient(round_to(x, fmt) - x)

    return lambda a, w: rounded(a) @ rounded(w)


def hidden_states(sizes, params, ids, remat=False, matmul=jnp.matmul):
    """ids [S] -> the final normed hidden states [S, D], float32."""
    h, kv, dh = sizes["num_attention_heads"], sizes["num_key_value_heads"], head_dim(sizes)
    eps, theta = sizes["rms_norm_eps"], sizes["rope_theta"]
    window = sizes.get("sliding_window")
    positions = jnp.arange(ids.shape[0])
    x = params["embed"].astype(jnp.float32)[ids]

    def layer(x, w):
        w = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), w)
        a = rms_norm(x, w["attn_norm"], eps)
        q = rotary(matmul(a, w["attn"]["wq"]).reshape(-1, h, dh), positions, theta)
        k = rotary(matmul(a, w["attn"]["wk"]).reshape(-1, kv, dh), positions, theta)
        v = matmul(a, w["attn"]["wv"]).reshape(-1, kv, dh)
        x = x + matmul(attention(q, k, v, window).reshape(-1, h * dh), w["attn"]["wo"])
        m = rms_norm(x, w["mlp_norm"], eps)
        gated = jax.nn.silu(matmul(m, w["mlp"]["w_gate"])) * matmul(m, w["mlp"]["w_up"])
        return x + matmul(gated, w["mlp"]["w_down"]), None

    # remat changes what is kept for the backward pass, not what is computed
    x, _ = jax.lax.scan(jax.checkpoint(layer) if remat else layer, x, params["layers"])
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


def loss_fn(sizes, params, ids, matmul=jnp.matmul):
    """Mean next-token cross entropy over a batch ids [B, S]: position t
    predicts token t + 1, the last position predicts nothing."""
    def one(seq):
        x = hidden_states(sizes, params, seq, remat=True, matmul=matmul)
        logits = matmul(x[:-1], params["lm_head"].astype(jnp.float32))
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, seq[1:, None], axis=-1)[:, 0]
        return jnp.sum(logz - gold)

    return jnp.sum(jax.vmap(one)(ids)) / (ids.shape[0] * (ids.shape[1] - 1))


def loss_and_grads(sizes, params, ids, matmul_format=None):
    """The loss and its gradient at ``params``.  Jit it (with whatever
    placement the caller gives the arguments).  ``matmul_format`` makes it the
    precision control (see ``matmul_in``)."""
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(
            lambda p: loss_fn(sizes, p, ids, matmul_in(matmul_format)))(params)


def global_norm(grads):
    return jnp.sqrt(sum(jnp.sum(g.astype(jnp.float32) ** 2)
                        for g in jax.tree_util.tree_leaves(grads)))


def not_descended_share(grads, update_signs):
    """Share of the gradient's mass (sum of |g|) on which an update does not
    go down the gradient: it climbs, or it stays.  ``update_signs`` is the
    sign of each element's first step, which a descent step makes the opposite
    of g's wherever g is not zero."""
    pairs = zip(jax.tree_util.tree_leaves(grads), jax.tree_util.tree_leaves(update_signs))
    wrong = mass = 0.0
    for g, s in pairs:
        g = g.astype(jnp.float32)
        wrong += jnp.sum(jnp.abs(g) * (g * s.astype(jnp.float32) >= 0))
        mass += jnp.sum(jnp.abs(g))
    return wrong / mass


def _static(sizes):
    return tuple(sorted((k, v) for k, v in sizes.items()
                        if isinstance(v, (int, float)) or v is None))
