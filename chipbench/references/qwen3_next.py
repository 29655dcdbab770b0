"""Plain reference of Qwen3-Next-80B-A3B-Instruct (the model's ``config.json``,
``model_type: qwen3_next``; HF ``transformers/models/qwen3_next/
modeling_qwen3_next.py`` is the written source of every layer, and the tests
hold this file to it), as ONE CHIP'S SHARE of a deployment in which
``EP_CHIPS`` = 4 chips share each layer.  A pre-norm decoder; layer ``i`` is a
gated softmax attention where ``(i + 1) % full_attention_interval == 0`` and a
Gated DeltaNet otherwise; every layer's FFN is a mixture of experts with a
gated shared expert.  One layer, ``x`` ``[S, D]`` of one sequence:

    u = rms(x; w) = x / sqrt(mean(x^2) + eps) * (1 + w)        (the gain is stored about zero)
    Gated DeltaNet:
        [q | k | v | z] = u W_qkvz      (Hk x dk | Hk x dk | Hv x dv | Hv x dv columns)
        [b | a] = u W_ba                (Hv | Hv)
        c = silu(conv([q | k | v]))     (depth-wise causal filter of 4 taps, no bias, the
                                         columns zero before the first token)
        q = l2norm(q_c) / sqrt(dk), k = l2norm(k_c)  a head (eps 1e-6 inside the root);
        key head j serves value heads 2j and 2j + 1 (repeat_interleave)
        beta = sigmoid(b);  alpha = exp(-exp(A_log) * softplus(a + dt_bias))  a value head
        for each value head, S [dk, dv] zero before the first token, token by token:
            S <- alpha_t S;  d = beta_t (v_t - S^T k_t);  S <- S + k_t d^T;  o_t = S^T q_t
        h = x + W_out concat_heads( o_t / sqrt(mean(o_t^2) + eps) * w_norm * silu(z_t) )
    gated attention:
        [q | gate]_h = (u W_q)_h (dh + dh a head), k = heads(u W_k), v = heads(u W_v)
        q = rms_dh(q), k = rms_dh(k) a head; rotate-half rotary over the first
        ``partial_rotary_factor x dh`` dimensions; causal softmax(q k^T / sqrt(dh)) v, GQA
        h = x + W_o (attn * sigmoid(gate))
    n = rms(h)
    p = softmax(n W_r) over ALL ``EP_CHIPS x held`` experts, float32; the top
        ``num_experts_per_tok``; w = p[picks] / sum(p[picks])  (``norm_topk_prob``)
    y = h + sum_{i picked AND held here} w_i E_i(n) + sigmoid(n w_g) * Shared(n)

then the final ``rms`` and the head (untied).

**The share.**  ``num_experts`` in the configuration is the number of experts
whose weights are HERE (128 of the published 512): this chip is chip 0 of four,
holds experts 0..127, routes over all 512, adds its own experts' part and the
gated shared expert (which every chip computes whole for its own tokens) and
leaves out what the other three chips' experts would add.  That partial sum
goes on to the next layer.  ``vocab_size`` is this chip's quarter of the
vocabulary (embedding and head rows).  Attention and the Gated DeltaNet are
whole on every chip.  ``layer_parts`` returns the routed part of any chip's
share apart from the shared expert's, so a test can add the four up to the
uncut layer.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision, one
sequence at a time: the delta rule TOKEN BY TOKEN (a ``lax.scan`` over the
positions; no chunks, no triangular solve: it shares no algebra with the
program's chunked scan), the filter over the whole sequence, attention a full
masked softmax in blocks of queries, every held expert computed for every token
and combined through an ``[S, E]`` matrix of weights.  No cache, no state, no
kernel, no sorting.

Departures from ``modeling_qwen3_next.py``, each also under the configuration
file's ``assumed``: (1) the columns of ``W_qkvz`` and ``W_ba`` are drawn in the
order written above, all q, all k, all v, all z (``b``, then ``a``); HF stores
them grouped by key head (``[q_j | k_j | v_2j v_2j+1 | z_2j z_2j+1]`` for key
head j): a permutation of columns, which the test applies; (2) the
multi-token-prediction module that the model's description names is left out:
``config.json`` has no key for it and serving does not run it; (3) ``A_log``
and ``dt_bias`` are drawn so that a head's decay a token lies between about
0.9 and 0.999 (``init_params``).  None else in the mathematics.

Nothing here comes from ``deepspeed_tpu``: sizes come from the configuration
file's published keys, weights from the seed.  ``init_params`` lays the weights
out as the pytree the program's ``models/qwen3_next.py`` takes, because that
layout is the program's input interface; the same arrays go to both: a run of
layers that repeats a pattern is a tuple of one stack ``[repeats, ...]`` a
position of the pattern (``segments``); the experts of all layers are one stack
``[layers, held, ...]``.
"""

import functools
import math

import jax
import jax.numpy as jnp

EP_CHIPS = 4    # chips that share each layer in the deployment this file is one chip of
Q_BLOCK = 256   # queries per attention block: 16 heads x 256 x 17,408 keys x 4 B = 0.29 GB
L2_EPS = 1e-6   # inside l2norm's root (FLA's, which HF follows)
DECAY_RATES = (7e-4, 7e-2)  # exp(A_log) of the first and the last value head, log-spaced between


def router_width(sizes) -> int:
    """Experts the router scores: the held count times the chips of the deployment."""
    return EP_CHIPS * sizes["num_experts"]


def layer_kinds(sizes):
    """``["linear_attention" | "full_attention"]`` a layer: HF's default
    ``layer_types`` from ``full_attention_interval``."""
    every = sizes["full_attention_interval"]
    return ["full_attention" if (i + 1) % every == 0 else "linear_attention"
            for i in range(sizes["num_hidden_layers"])]


def segments(sizes):
    """``[(start, period, repeats)]``: from each start the longest run of
    layers that repeats a pattern of ``period`` kinds at least twice, else one
    layer alone.  Twelve layers: ``[(0, 4, 3)]``."""
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


def gdn_widths(sizes):
    """(Hk, Hv, dk, dv, key columns, value columns) of a Gated DeltaNet layer."""
    hk, hv = sizes["linear_num_key_heads"], sizes["linear_num_value_heads"]
    dk, dv = sizes["linear_key_head_dim"], sizes["linear_value_head_dim"]
    return hk, hv, dk, dv, hk * dk, hv * dv


def init_params(sizes, key, dtype=jnp.float32):
    """Random weights from ``key`` (``jax.random.PRNGKey(seed)``):
    normal(0, 1/sqrt(fan_in)) projections, experts, router and the shared
    expert's gate (logits of unit scale, so that routing is not uniform and
    the gate differs by token), filter taps normal(0, 1/sqrt(4)), a normal(0,
    0.02) embedding, an untied head, gains at their neutral value (zero for
    the ``1 + w`` norms, one for the DeltaNet's output norm).

    **The decay.**  HF's initialisation (``A_log = log(U(0, 16))``, ``dt_bias
    = 1``) gives ``alpha = exp(-A * 1.3)``, under 1e-3 for most heads: a state
    that forgets everything at every token, so a fault in the carried state
    would not reach the logits.  A trained model's heads remember over tens to
    thousands of tokens.  Here ``exp(A_log)`` is log-spaced over a layer's
    value heads from 7e-4 to 7e-2 and ``dt_bias`` is 1 (``softplus(a + 1)`` is
    about 1.4 over unit-scale ``a``): a head's typical decay a token runs from
    0.999 to 0.9, and every token's differs.

    A routed expert's ``W_down`` is drawn at its scale over
    ``num_experts_per_tok``: routing is discrete, a bfloat16 engine and this
    float32 reference break a near-tie between a token's tenth and eleventh
    expert differently, and the routed part's share of the residual stream is
    how far one such tie moves a row's logits (PERF.md section 6, PRs 31 and
    33).  Call it under ``jax.jit`` with the key as an argument."""
    d, e = sizes["hidden_size"], router_width(sizes)
    h, kv, dh = sizes["num_attention_heads"], sizes["num_key_value_heads"], sizes["head_dim"]
    hk, hv, dk, dv, key_dim, value_dim = gdn_widths(sizes)
    taps = sizes["linear_conv_kernel_dim"]
    kinds = layer_kinds(sizes)
    k_emb, k_head, k_layers, k_experts = jax.random.split(key, 4)

    def linear(key, *shape):
        """[..., fan_in, fan_out]"""
        return jax.random.normal(key, shape, dtype) * float(shape[-2]) ** -0.5

    def ffn(key, width, *lead):
        ks = jax.random.split(key, 3)
        return {"w_gate": linear(ks[0], *lead, d, width), "w_up": linear(ks[1], *lead, d, width),
                "w_down": linear(ks[2], *lead, width, d)}

    def position(key, depth, kind):
        ks = jax.random.split(key, 10)
        lp = {"op_norm": jnp.zeros((depth, d), dtype), "ffn_norm": jnp.zeros((depth, d), dtype)}
        if kind == "linear_attention":
            rates = jnp.exp(jnp.linspace(math.log(DECAY_RATES[0]), math.log(DECAY_RATES[1]), hv))
            lp["mixer"] = {"w_qkvz": linear(ks[0], depth, d, 2 * key_dim + 2 * value_dim),
                           "w_ba": linear(ks[1], depth, d, 2 * hv),
                           "filter": jax.random.normal(ks[2], (depth, taps, 2 * key_dim + value_dim),
                                                       dtype) * float(taps) ** -0.5,
                           "A_log": jnp.broadcast_to(jnp.log(rates), (depth, hv)).astype(dtype),
                           "dt_bias": jnp.ones((depth, hv), dtype),
                           "norm": jnp.ones((depth, dv), dtype),
                           "w_out": linear(ks[3], depth, value_dim, d)}
        else:
            lp["attn"] = {"wq": linear(ks[0], depth, d, h * 2 * dh), "wk": linear(ks[1], depth, d, kv * dh),
                          "wv": linear(ks[2], depth, d, kv * dh), "wo": linear(ks[3], depth, h * dh, d),
                          "q_norm": jnp.zeros((depth, dh), dtype),
                          "k_norm": jnp.zeros((depth, dh), dtype)}
        lp["moe"] = {"gate": {"wg": linear(ks[4], depth, d, e)},
                     "shared": ffn(ks[5], sizes["shared_expert_intermediate_size"], depth),
                     "shared_gate": linear(ks[6], depth, d, 1)}
        return lp

    runs = []
    for start, period, repeats in segments(sizes):
        keys = jax.random.split(jax.random.fold_in(k_layers, start), period)
        runs.append(tuple(position(keys[j], repeats, kinds[start + j]) for j in range(period)))
    experts = ffn(k_experts, sizes["moe_intermediate_size"], len(kinds), sizes["num_experts"])
    experts["w_down"] = experts["w_down"] / sizes["num_experts_per_tok"]
    return {"embed": jax.random.normal(k_emb, (sizes["vocab_size"], d), dtype) * 0.02,
            "segments": runs, "experts": experts, "final_norm": jnp.zeros((d, ), dtype),
            "lm_head": linear(k_head, d, sizes["vocab_size"])}


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
    ``fmt`` (fp8 below bfloat16).  Neutral gains (zeros, ones) stay."""
    return jax.tree_util.tree_map(lambda w: round_to(w, fmt), params)


def f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


def rms_norm(x, gain, eps):
    """Qwen3NextRMSNorm: the gain is ``1 + w``."""
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * (1.0 + gain)


def swiglu(x, w):
    w = f32(w)
    return (jax.nn.silu(x @ w["w_gate"]) * (x @ w["w_up"])) @ w["w_down"]


# ----------------------------------------------------------- Gated DeltaNet
def delta_rule(q, k, v, alpha, beta, state=None):
    """The gated delta rule token by token.  q, k ``[S, H, dk]``, v ``[S, H,
    dv]``, alpha, beta ``[S, H]``; ``state`` ``[H, dk, dv]`` (zeros where
    None).  Returns (o ``[S, H, dv]``, the state after the last token)."""
    if state is None:
        state = jnp.zeros((q.shape[1], q.shape[2], v.shape[2]), jnp.float32)

    def token(s, inp):
        q_t, k_t, v_t, a_t, b_t = inp
        s = s * a_t[:, None, None]
        d = b_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", s, k_t))
        s = s + k_t[:, :, None] * d[:, None, :]
        return s, jnp.einsum("hkv,hk->hv", s, q_t)

    state, out = jax.lax.scan(token, state, (q, k, v, alpha, beta))
    return out, state


def l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def gated_delta_net(sizes, u, w):
    """The Gated DeltaNet operator over one whole sequence, u ``[S, D]``."""
    hk, hv, dk, dv, key_dim, value_dim = gdn_widths(sizes)
    w = f32(w)
    qkvz = u @ w["w_qkvz"]
    mixed, z = qkvz[:, :2 * key_dim + value_dim], qkvz[:, 2 * key_dim + value_dim:]
    b, a = jnp.split(u @ w["w_ba"], 2, axis=-1)
    taps = w["filter"].shape[0]
    padded = jnp.pad(mixed, ((taps - 1, 0), (0, 0)))  # zero before the first token
    conv = jax.nn.silu(sum(w["filter"][j] * padded[j:j + mixed.shape[0]] for j in range(taps)))
    q = conv[:, :key_dim].reshape(-1, hk, dk)
    k = conv[:, key_dim:2 * key_dim].reshape(-1, hk, dk)
    v = conv[:, 2 * key_dim:].reshape(-1, hv, dv)
    q = jnp.repeat(l2norm(q) * dk ** -0.5, hv // hk, axis=1)  # key head j -> value heads 2j, 2j + 1
    k = jnp.repeat(l2norm(k), hv // hk, axis=1)
    beta = jax.nn.sigmoid(b)
    alpha = jnp.exp(-jnp.exp(w["A_log"]) * jax.nn.softplus(a + w["dt_bias"]))
    o, _ = delta_rule(q, k, v, alpha, beta)
    # Qwen3NextRMSNormGated: a plain gain (no 1 +) over each head, then the gate
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + sizes["rms_norm_eps"]) * w["norm"]
    o = o * jax.nn.silu(z.reshape(-1, hv, dv))
    return o.reshape(-1, value_dim) @ w["w_out"]


# ---------------------------------------------------------------- attention
def rotary(x, positions, theta, rotated):
    """x [S, heads, dh]: the first ``rotated`` dimensions rotate-half (pairs
    ``(i, i + rotated / 2)`` by ``positions * theta^(-2i / rotated)``), the
    others pass."""
    half = rotated // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    x1, x2, rest = x[..., :half], x[..., half:rotated], x[..., rotated:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], axis=-1)


def causal_attention(q, k, v, q_block=Q_BLOCK):
    """q [S, H, dh], k/v [S, KV, dh]: query i sees keys j <= i; each group of
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


def gated_attention(sizes, u, w, positions):
    """The gated attention operator over one whole sequence, u ``[S, D]``."""
    h, kv, dh = sizes["num_attention_heads"], sizes["num_key_value_heads"], sizes["head_dim"]
    eps, theta = sizes["rms_norm_eps"], float(sizes["rope_theta"])
    rotated = int(dh * sizes["partial_rotary_factor"])
    w = f32(w)
    q_gate = (u @ w["wq"]).reshape(-1, h, 2 * dh)  # a head's query, then its gate
    q, gate = q_gate[..., :dh], q_gate[..., dh:]
    q = rms_norm(q, w["q_norm"], eps)
    k = rms_norm((u @ w["wk"]).reshape(-1, kv, dh), w["k_norm"], eps)
    v = (u @ w["wv"]).reshape(-1, kv, dh)
    out = causal_attention(rotary(q, positions, theta, rotated), rotary(k, positions, theta, rotated), v)
    return (out * jax.nn.sigmoid(gate)).reshape(-1, h * dh) @ w["wo"]


# ------------------------------------------------------------ expert layer
def router(sizes, n, wg):
    """n [S, D] -> combine [S, E]: the softmax over all E experts at each
    token's top-k, renormalised where ``norm_topk_prob``, zero elsewhere."""
    probs = jax.nn.softmax(n @ wg, axis=-1)
    top_p, top_idx = jax.lax.top_k(probs, sizes["num_experts_per_tok"])
    if sizes["norm_topk_prob"]:
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    return jnp.zeros_like(probs).at[jnp.arange(probs.shape[0])[:, None], top_idx].set(top_p)


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


def layer_parts(sizes, moe, n, chip: int = 0, layer=None):
    """(routed, shared) of one expert layer over n [S, D] for the chip that
    holds experts ``chip * held ... (chip + 1) * held - 1`` (``moe["experts"]``
    are those ``held`` experts, or with ``layer`` the whole stack of them):
    the sum over the held experts a token picked, and the gated shared
    expert's output, which is the same on every chip."""
    experts = moe["experts"]
    if layer is None:
        experts, layer = jax.tree_util.tree_map(lambda m: m[None], experts), 0
    combine = router(sizes, n, moe["gate"]["wg"].astype(jnp.float32))
    held = experts["w_gate"].shape[1]
    routed = experts_ffn(n, combine[:, chip * held:(chip + 1) * held], experts, layer)
    gate = jax.nn.sigmoid(n @ moe["shared_gate"].astype(jnp.float32))  # [S, 1]: one a token
    return routed, gate * swiglu(n, moe["shared"])


# ---------------------------------------------------------------- the model
def block(sizes, x, w, positions, experts, layer):
    """One layer: the mixer its parameters name, then its expert FFN."""
    eps = sizes["rms_norm_eps"]
    u = rms_norm(x, w["op_norm"].astype(jnp.float32), eps)
    x = x + (gated_delta_net(sizes, u, w["mixer"]) if "mixer" in w
             else gated_attention(sizes, u, w["attn"], positions))
    n = rms_norm(x, w["ffn_norm"].astype(jnp.float32), eps)
    return x + sum(layer_parts(sizes, {**w["moe"], "experts": experts}, n, layer=layer))


def hidden_states(sizes, params, ids):
    """ids [S] -> the final normed hidden states [S, D], float32.  The layers
    are taken from their stacks in the order they are numbered."""
    positions = jnp.arange(ids.shape[0])
    x = params["embed"][ids].astype(jnp.float32)
    layer = 0
    for (start, period, repeats), run in zip(segments(sizes), params["segments"]):
        for i in range(repeats):
            for stack in run:
                w = jax.tree_util.tree_map(lambda a, i=i: a[i], stack)
                x = block(sizes, x, w, positions, params["experts"], layer)
                layer += 1
    return rms_norm(x, params["final_norm"].astype(jnp.float32), sizes["rms_norm_eps"])


@functools.partial(jax.jit, static_argnums=(0,))
def _logits_rows(sizes_items, params, ids, rows):
    with jax.default_matmul_precision("highest"):
        x = hidden_states(_thawed(sizes_items), params, ids)
        return x[rows] @ params["lm_head"].astype(jnp.float32)


def logits_rows(sizes, params, ids, rows):
    """Logits [len(rows), V] of one sequence ``ids`` [S] at positions ``rows``.
    The mask, the filter and the recurrence are causal, so tokens padded on
    after the last row change nothing."""
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
