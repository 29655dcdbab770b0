"""Plain reference of LFM2-24B-A2B (LiquidAI's ``config.json``, ``model_type:
lfm2_moe``; HF ``transformers/models/lfm2/modeling_lfm2.py`` is the written
source of the two operators and the block, and the tests hold this file to it):
a pre-norm decoder whose ``layer_types`` name each layer's token mixer, a gated
short convolution in three layers of four and attention in the fourth, with a
dense SwiGLU in the ``num_dense_layers`` leading layers and a mixture of SwiGLU
experts in every other.  One layer, ``x`` ``[S, D]`` of one sequence:

    u  = RMSNorm_op(x)
    conv layer:  [B | C | X] = u W_in                 (D -> 3D, split in that order)
                 z   = B * X
                 c_t = w[0] z_{t-2} + w[1] z_{t-1} + w[2] z_t      (depth-wise, one 3-tap
                       filter a channel, no bias, z zero before the first token)
                 h   = x + (C * c) W_out
    attention:   q = RMSNorm_q(heads(u W_q)), k = RMSNorm_k(heads(u W_k))   (a gain of
                 head_dim over EACH head, 32 q heads and 8 KV heads of 64), v = heads(u W_v)
                 rotate-half rotary over all 64 dimensions, theta 1e6
                 h = x + W_o softmax_causal(q k^T / 8) v             (GQA, no window)
    n  = RMSNorm_ffn(h)
    dense layer:  y = h + W_2( silu(W_1 n) * (W_3 n) )               (width 11776)
    expert layer: s = sigmoid(n W_g), float32, over all 64 experts
                  picks = top-4 of s + b     (b: the stored ``expert_bias``; it chooses,
                                              it never weighs)
                  w = s[picks] / (sum s[picks] + 1e-6) * routed_scaling_factor
                  y = h + sum_i w_i E_i(n),  E_i a SwiGLU of width 1536; no shared expert

and RMSNorm_emb over the last layer's output, then the head (the embedding,
tied).

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision, one
sequence at a time: no cache and no state (the convolution runs over the whole
sequence), no kernel, no batching, no sorting and no dispatch: EVERY expert is
computed for every token and combined through a ``[S, E]`` matrix that holds
``w`` at a token's picks and zero elsewhere.  Attention runs in blocks of
queries so that the cell's longest prompt fits.

Departures from the published description, each also under the configuration
file's ``assumed``: (1) the sparse block (sigmoid, bias on the choice alone,
the 1e-6, the factor after the renormalisation) is ``lfm2_moe``'s as its
author knew it: the installed ``transformers`` has ``lfm2`` and no
``lfm2_moe``; (2) ``tie_word_embeddings`` true (Lfm2's default; the catalog row
has no key); (3) the dense width is taken as given (the row has no
``block_auto_adjust_ff_dim``).  None else in the mathematics.

Nothing here comes from ``deepspeed_tpu``: sizes come from the configuration
file's published keys, weights from the seed.  ``init_params`` lays the weights
out as the pytree the program's ``models/lfm2.py`` takes, because that layout is
the program's input interface; the same arrays go to both.  The layout follows
the layers as they are scanned (``segments``): a run of layers that repeats a
pattern is a tuple of one stack ``[repeats, ...]`` a position of the pattern;
the experts of all expert layers are one stack ``[expert layers, E, ...]``.
"""

import functools

import jax
import jax.numpy as jnp

Q_BLOCK = 512  # queries per attention block: 32 heads x 512 x 3072 keys x 4 B = 0.2 GB
ROUTER_EPS = 1e-6


def head_dim(sizes) -> int:
    return int(sizes.get("head_dim") or sizes["hidden_size"] // sizes["num_attention_heads"])


def layer_kinds(sizes):
    """``[(mixer, dense)]`` of the layers that are run: the first
    ``num_hidden_layers`` of ``layer_types``, the leading ``num_dense_layers``
    of them with a dense FFN."""
    kinds = list(sizes["layer_types"])[:sizes["num_hidden_layers"]]
    assert len(kinds) == sizes["num_hidden_layers"] and set(kinds) <= {"conv", "full_attention"}
    return [(kind, i < sizes["num_dense_layers"]) for i, kind in enumerate(kinds)]


def segments(sizes):
    """``[(start, period, repeats)]``: from each start the longest run of
    layers that repeats a pattern of ``period`` kinds at least twice, else one
    layer alone.  Published (40 layers): two dense conv layers, nine times
    (attention, conv, conv, conv), then attention and conv apart."""
    kinds = layer_kinds(sizes)
    out, at = [], 0
    while at < len(kinds):
        best = (1, 1)
        for period in range(1, (len(kinds) - at) // 2 + 1):
            repeats = 1
            while kinds[at + repeats * period:at + (repeats + 1) * period] == kinds[at:at + period]:
                repeats += 1
            if repeats > 1 and period * repeats > best[0] * best[1]:
                best = (period, repeats)
        out.append((at, ) + best)
        at += best[0] * best[1]
    return out


def init_params(sizes, key, dtype=jnp.float32):
    """Random weights from ``key`` (``jax.random.PRNGKey(seed)``):
    normal(0, 1/sqrt(fan_in)) projections, experts and router (router logits
    of unit scale, so the sigmoid scores differ), filter taps normal(0,
    1/sqrt(3)), a normal(0, 0.02) embedding (tied head), unit norm gains, and
    an ``expert_bias`` of normal(0, 0.64 / num_experts) (0.01 at 64 experts):
    about half the gap between a token's fourth and fifth score, so it changes
    the picks of three tokens in ten a layer (an unused bias would be
    untested) and leaves the experts' loads within a quarter of each other, as
    a bias that balances loads does in a deployment.  At 0.1 the loads
    differed ten to one, by seed: a decode step of 32 rows read 37-41 of the
    64 experts where balanced routing reads 55-56, and the cell's rate spread
    by 1.8% over seeds (the configuration file has the readings).  A routed expert's ``W_down`` is drawn at that scale over
    ``num_experts_per_tok``: routing is discrete, a bfloat16 engine and this
    float32 reference break a near-tie between a token's fourth and fifth
    expert differently (about one token-layer in ten), and with renormalised
    weights of a quarter each and experts of the mixers' own scale such a tie
    moved a row's logits by a sixth; the configuration file's ``assumed.
    weights`` has the readings.  Call it under ``jax.jit`` with the key as
    an argument."""
    d, dh = sizes["hidden_size"], head_dim(sizes)
    h, kv, e = sizes["num_attention_heads"], sizes["num_key_value_heads"], sizes["num_experts"]
    taps = sizes["conv_L_cache"]
    kinds = layer_kinds(sizes)
    n_moe = sum(not dense for _, dense in kinds)
    k_emb, k_layers, k_experts = jax.random.split(key, 3)

    def linear(key, *shape):
        """[..., fan_in, fan_out]"""
        return jax.random.normal(key, shape, dtype) * float(shape[-2]) ** -0.5

    def ffn(key, width, *lead):
        ks = jax.random.split(key, 3)
        return {"w_gate": linear(ks[0], *lead, d, width), "w_up": linear(ks[1], *lead, d, width),
                "w_down": linear(ks[2], *lead, width, d)}

    def position(key, depth, mixer, dense):
        ks = jax.random.split(key, 7)
        lp = {"op_norm": jnp.ones((depth, d), dtype), "ffn_norm": jnp.ones((depth, d), dtype)}
        if mixer == "conv":
            lp["mixer"] = {"w_in": linear(ks[0], depth, d, 3 * d),
                           "filter": jax.random.normal(ks[1], (depth, taps, d), dtype)
                           * float(taps) ** -0.5,
                           "w_out": linear(ks[2], depth, d, d)}
        else:
            lp["attn"] = {"wq": linear(ks[0], depth, d, h * dh), "wk": linear(ks[1], depth, d, kv * dh),
                          "wv": linear(ks[2], depth, d, kv * dh), "wo": linear(ks[3], depth, h * dh, d),
                          "q_norm": jnp.ones((depth, dh), dtype),
                          "k_norm": jnp.ones((depth, dh), dtype)}
        if dense:
            lp["mlp"] = ffn(ks[4], sizes["intermediate_size"], depth)
        else:
            lp["moe"] = {"gate": {"wg": linear(ks[5], depth, d, e)}}
            if sizes["use_expert_bias"]:
                lp["moe"]["gate"]["bias"] = jax.random.normal(ks[6], (depth, e), dtype) * (0.64 / e)
        return lp

    runs = []
    for start, period, repeats in segments(sizes):
        keys = jax.random.split(jax.random.fold_in(k_layers, start), period)
        runs.append(tuple(position(keys[j], repeats, *kinds[start + j]) for j in range(period)))
    experts = ffn(k_experts, sizes["moe_intermediate_size"], n_moe, e)
    experts["w_down"] = experts["w_down"] / sizes["num_experts_per_tok"]
    return {"embed": jax.random.normal(k_emb, (sizes["vocab_size"], d), dtype) * 0.02,
            "segments": runs, "experts": experts, "final_norm": jnp.ones((d, ), dtype)}


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


def short_conv(u, w):
    """The gated short convolution over one whole sequence, u ``[S, D]``."""
    w = f32(w)
    b, c, xs = jnp.split(u @ w["w_in"], 3, axis=-1)
    z = b * xs
    taps = w["filter"].shape[0]
    padded = jnp.pad(z, ((taps - 1, 0), (0, 0)))  # z is zero before the first token
    conv = sum(w["filter"][j] * padded[j:j + z.shape[0]] for j in range(taps))
    return (c * conv) @ w["w_out"]


def rotary(x, positions, theta):
    """x [S, heads, Dh]; pairs (i, i + Dh/2) rotate by positions * theta^(-2i/Dh)."""
    half = x.shape[-1] // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def causal_attention(q, k, v, q_block=Q_BLOCK):
    """q [S, H, Dh], k/v [S, KV, Dh]: query i sees keys j <= i; each group of
    H/KV query heads shares one KV head."""
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

    return jax.lax.map(block, (qg, q_pos)).reshape(-1, h, dh)[:s]


def attention(sizes, u, w, positions):
    """The attention operator over one whole sequence, u ``[S, D]``."""
    h, kv, dh = sizes["num_attention_heads"], sizes["num_key_value_heads"], head_dim(sizes)
    eps, theta = sizes["norm_eps"], float(sizes["rope_parameters"]["rope_theta"])
    w = f32(w)
    q = rms_norm((u @ w["wq"]).reshape(-1, h, dh), w["q_norm"], eps)  # a gain over each head
    k = rms_norm((u @ w["wk"]).reshape(-1, kv, dh), w["k_norm"], eps)
    v = (u @ w["wv"]).reshape(-1, kv, dh)
    out = causal_attention(rotary(q, positions, theta), rotary(k, positions, theta), v)
    return out.reshape(-1, h * dh) @ w["wo"]


def router(sizes, n, gate):
    """n [S, D] -> combine [S, E]: the renormalised, scaled sigmoid scores at
    each token's picks (the top-k of score + bias) and zero elsewhere."""
    scores = jax.nn.sigmoid(n @ gate["wg"].astype(jnp.float32))
    chosen_by = scores + gate["bias"].astype(jnp.float32) if "bias" in gate else scores
    _, picks = jax.lax.top_k(chosen_by, sizes["num_experts_per_tok"])
    w = jnp.take_along_axis(scores, picks, axis=-1)
    if sizes["norm_topk_prob"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + ROUTER_EPS)
    w = w * sizes["routed_scaling_factor"]
    return jnp.zeros_like(scores).at[jnp.arange(scores.shape[0])[:, None], picks].set(w)


def experts_ffn(n, combine, experts, layer):
    """Every expert of layer ``layer`` of the stack (leaves [L, E, ...]) over
    every token, one at a time, each output weighted by the token's
    ``combine`` column and summed."""

    def one(acc, inp):
        e, weight = inp
        out = swiglu(n, {name: m[layer, e] for name, m in experts.items()})
        return acc + weight[:, None] * out, None

    acc, _ = jax.lax.scan(one, jnp.zeros_like(n),
                          (jnp.arange(experts["w_gate"].shape[1]), combine.T))
    return acc


def block(sizes, x, w, positions, experts, moe_layer):
    """One layer: the operator its parameters name, then its FFN."""
    eps = sizes["norm_eps"]
    u = rms_norm(x, w["op_norm"].astype(jnp.float32), eps)
    x = x + (short_conv(u, w["mixer"]) if "mixer" in w else attention(sizes, u, w["attn"], positions))
    n = rms_norm(x, w["ffn_norm"].astype(jnp.float32), eps)
    if "mlp" in w:
        return x + swiglu(n, w["mlp"])
    return x + experts_ffn(n, router(sizes, n, w["moe"]["gate"]), experts, moe_layer)


def hidden_states(sizes, params, ids):
    """ids [S] -> the final normed hidden states [S, D], float32.  The layers
    are taken from their stacks in the order they are numbered."""
    positions = jnp.arange(ids.shape[0])
    x = params["embed"][ids].astype(jnp.float32)
    moe_layer = 0
    for (start, period, repeats), run in zip(segments(sizes), params["segments"]):
        for i in range(repeats):
            for stack in run:
                w = jax.tree_util.tree_map(lambda a, i=i: a[i], stack)
                x = block(sizes, x, w, positions, params["experts"], moe_layer)
                moe_layer += "moe" in w
    return rms_norm(x, params["final_norm"].astype(jnp.float32), sizes["norm_eps"])


@functools.partial(jax.jit, static_argnums=(0,))
def _logits_rows(sizes_items, params, ids, rows):
    with jax.default_matmul_precision("highest"):
        x = hidden_states(_thawed(sizes_items), params, ids)
        return x[rows] @ params["embed"].astype(jnp.float32).T  # the head is the embedding


def logits_rows(sizes, params, ids, rows):
    """Logits [len(rows), V] of one sequence ``ids`` [S] at positions ``rows``.
    The mask and the filter are causal, so tokens padded on after the last row
    change nothing."""
    return _logits_rows(_static(sizes), params, jnp.asarray(ids, jnp.int32),
                        jnp.asarray(rows, jnp.int32))


def _static(sizes):
    """The sizes as something hashable; nested groups and lists too."""
    def freeze(v):
        if isinstance(v, dict):
            return ("dict", tuple(sorted((k, freeze(x)) for k, x in v.items())))
        if isinstance(v, (list, tuple)):
            return ("list", tuple(freeze(x) for x in v))
        return v
    return tuple(sorted((k, freeze(v)) for k, v in sizes.items()))


def _thawed(items):
    def thaw(v):
        if isinstance(v, tuple) and len(v) == 2 and v[0] == "dict":
            return {k: thaw(x) for k, x in v[1]}
        if isinstance(v, tuple) and len(v) == 2 and v[0] == "list":
            return [thaw(x) for x in v[1]]
        return v
    return {k: thaw(v) for k, v in items}
