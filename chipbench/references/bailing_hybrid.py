"""Plain reference of Ling-3.0-flash's language model (the model's ``config.json``,
``model_type: bailing_hybrid``; Kimi Linear, arXiv:2510.26692, and FLA's
``fla/ops/kda`` for Kimi Delta Attention and its gate, DeepSeek-V2 for the latent
attention, DeepSeek-V3 for the ``noaux_tc`` router), as ONE CHIP'S SHARE of a
deployment in which ``n_group`` = 8 chips share each layer, one group of the
router's experts a chip.  A pre-norm decoder; layer ``i`` is latent attention
(MLA) where ``(i + 1) % layer_group_size == 0`` and Kimi Delta Attention (KDA)
otherwise; the FFN of layer ``i < first_k_dense_replace`` is a dense SwiGLU, of
every later layer a mixture of experts with one shared expert.  One layer, ``x``
``[S, D]`` of one sequence:

    u = rms(x; w) = x / sqrt(mean(x^2) + eps) * w                   (a plain gain)
    KDA (H heads of dh for keys and values alike):
        [q | k | v] = u [W_q | W_k | W_v]                           (3 x H dh columns)
        c = silu(conv([q | k | v]))   (depth-wise causal filter of 4 taps, no bias, the
                                       columns zero before the first token)
        q = l2norm(q_c) / sqrt(dh), k = l2norm(k_c)  a head (eps 1e-6 inside the root)
        beta = sigmoid(u W_beta)                                    one a head
        g = kda_lower_bound * sigmoid(exp(A_log) * (u W_f + dt_bias))
            (A_log one a HEAD, dt_bias one a CHANNEL; W_f D -> H dh, full rank)
        alpha = exp(g)                                              a head a CHANNEL of the key
        for each head, S [dh, dh] zero before the first token, token by token:
            S <- diag(alpha_t) S;  d = beta_t (v_t - S^T k_t);  S <- S + k_t d^T;  o_t = S^T q_t
        h = x + W_o concat_heads( o_t / sqrt(mean(o_t^2) + eps) * w_norm * sigmoid(u W_g)_head )
    MLA (H heads; q_lora_rank null):
        q = heads(u W_q)  (nope + rope a head);  [c | k_r] = u W_kva;  c <- rms(c; w_kv)
        [k_n | v] = heads(c W_kvb);  rotary over interleaved pairs (2i, 2i + 1) of q's and
        k_r's ``qk_rope_head_dim`` (k_r one for all heads), theta ``rope_theta``, no scaling
        causal softmax([q_n | q_r] [k_n | k_r]^T / sqrt(nope + rope)) v
        h = x + W_o concat_heads( attn_head * sigmoid(u W_g)_head )
    n = rms(h)
    dense:    y = h + SwiGLU(n)
    mixture:  s = sigmoid(float32(n W_r)) over ALL ``n_group x held`` experts
        choice = s + bias;  the experts lie in ``n_group`` runs, a group's score is the sum of
        its two largest ``choice``, the ``topk_group`` best groups stay, the top
        ``num_experts_per_tok`` of ``choice`` among their experts are picked
        w = s[picks] / sum(s[picks]) * routed_scaling_factor        (``norm_topk_prob``)
        y = h + sum_{i picked AND held here} w_i E_i(n) + Shared(n)

then the final ``rms`` and the head (untied).

**The share.**  ``num_experts`` in the configuration is the number of experts
whose weights are HERE (64 of the published 512): this chip is chip 0 of eight,
holds group 0 whole (experts 0..63), routes over all 512 in 8 groups, adds its own
experts' part and the shared expert (which every chip computes whole for its own
tokens) and leaves out what the other seven chips' experts would add.  That
partial sum goes on to the next layer.  ``vocab_size`` is this chip's eighth of
the vocabulary (embedding and head rows).  Both mixers are whole on every chip.
``layer_parts`` returns the routed part of any chip's share apart from the
shared expert's, so a test can add the eight up to the uncut layer.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision, one
sequence at a time: the delta rule TOKEN BY TOKEN (a ``lax.scan`` over the
positions; no chunks, no triangular solve: it shares no algebra with the program's
chunked scan), the filter over the whole sequence, attention a full masked softmax
in blocks of queries with K and V materialised a head (not absorbed), every held
expert computed for every token and combined through an ``[S, E]`` matrix of
weights.  No cache, no state, no kernel, no sorting.

Departures from the source, each also under the configuration file's ``assumed``:
(1) the modeling code is not public here: the layer rule is read from
``layer_group_size``, the gate's bounded form from ``kda_safe_gate`` /
``kda_lower_bound`` and FLA's ``kda`` gate, the head-wise output gate of BOTH
mixers from ``gated_attention_proj_granularity_type`` and the published parameter
count; (2) an expert another group's bias would carry in is masked with -inf, not
with 0 (DeepSeek-V3's own inference code; HF's port fills 0, the same picks
wherever a kept group holds ``num_experts_per_tok`` positive choices); (3) the
multi-token-prediction layer is left out; (4) ``expert_swiglu_limit_list`` /
``share_expert_swiglu_limit_list`` must be zero in every layer run (the clamp's
form in the last eight published layers is stated nowhere: refused); (5) ``A_log``,
``dt_bias`` and the router's bias are drawn as ``init_params`` says.

Nothing here comes from ``deepspeed_tpu``: sizes come from the configuration
file's published keys, weights from the seed.  ``init_params`` lays the weights
out as the pytree the program's ``models/bailing_hybrid.py`` takes, because that
layout is the program's input interface; the same arrays go to both: a run of
layers that repeats a pattern is a tuple of one stack ``[repeats, ...]`` a
position of the pattern (``segments``); the experts of all expert layers are one
stack ``[expert layers, held, ...]``.
"""

import functools
import math

import jax
import jax.numpy as jnp

Q_BLOCK = 128     # queries an attention block: 32 heads x 128 x 32,768 keys x 4 B = 0.54 GB of scores
ROW_BLOCK = 2048  # rows a block of the per-token layers
HEAD_BLOCK = 8    # KDA heads at a time: [32,768, 3 x 8 x 128] float32 = 0.4 GB where all 32 would be 1.6 GB
L2_EPS = 1e-6     # inside l2norm's root (FLA's)
DECAY_RATES = (7e-4, 7e-2)  # -g at f = 0 of a head's first and last channel, log-spaced between
GATE_SLOPES = (0.8, 1.25)   # exp(A_log) of a layer's first and last head, log-spaced between
ROUTER_BIAS = 0.03          # the deviation of the router's selection bias
KDA, MLA = "kda", "mla"


def router_width(sizes) -> int:
    """Experts the router scores: the held count times the chips of the deployment,
    which holds one group a chip."""
    return sizes["n_group"] * sizes["num_experts"]


def layer_kinds(sizes):
    """``[(KDA | MLA, dense FFN?)]`` a layer, from ``layer_group_size`` and
    ``first_k_dense_replace``."""
    return [(MLA if (i + 1) % sizes["layer_group_size"] == 0 else KDA,
             i < sizes["first_k_dense_replace"]) for i in range(sizes["num_hidden_layers"])]


def segments(sizes):
    """``[(start, period, repeats)]``: from each start the longest run of layers
    that repeats a pattern of ``period`` kinds at least twice, else one layer alone."""
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


def check(sizes):
    """What this file does not state raises: see the module's departures."""
    layers = sizes["num_hidden_layers"]
    limits = list(sizes["expert_swiglu_limit_list"][:layers]) + list(
        sizes["share_expert_swiglu_limit_list"][:layers])
    refused = [name for name, wrong in (
        ("a non-zero swiglu limit in a layer that is run", any(limits)),
        ("use_kda_lora", sizes["use_kda_lora"] or not sizes["no_kda_lora"]),
        ("kda_safe_gate false", not sizes["kda_safe_gate"]),
        ("q_lora_rank", sizes["q_lora_rank"] is not None),
        ("rope_scaling", sizes["rope_scaling"] is not None),
        ("rope_interleave false", not sizes["rope_interleave"]),
        ("use_mla_nope", sizes["use_mla_nope"]), ("value_norm", sizes["value_norm"]),
        ("up_proj_norm", sizes["up_proj_norm"]), ("use_nGPT", sizes["use_nGPT"]),
        ("scale_router_input", sizes["scale_router_input"]),
        ("use_bias", sizes["use_bias"] or sizes["use_qkv_bias"]),
        ("tie_word_embeddings", sizes["tie_word_embeddings"]),
        ("a router that is not sigmoid / noaux_tc with a bias",
         (sizes["score_function"], sizes["topk_method"], sizes["moe_router_enable_expert_bias"])
         != ("sigmoid", "noaux_tc", True))) if wrong]
    if refused:
        raise NotImplementedError(f"references/bailing_hybrid: not stated here: {refused}")


def init_params(sizes, key, dtype=jnp.float32):
    """Random weights from ``key`` (``jax.random.PRNGKey(seed)``): normal(0,
    1/sqrt(fan_in)) projections, gates, experts and router (logits of unit scale,
    so that routing is not uniform and the gates differ by token), filter taps
    normal(0, 1/sqrt(4)), a normal(0, 0.02) embedding, an untied head, gains at one.

    **The decay.**  ``dt_bias`` is the logit of ``rate / -kda_lower_bound`` for rates
    log-spaced over a head's 128 CHANNELS from 7e-4 to 7e-2, and ``exp(A_log)`` is
    log-spaced over a layer's HEADS from 0.8 to 1.25: at ``f = 0`` a channel's decay
    a token runs from about 0.999 to 0.9 (a trained model's heads remember over tens
    to thousands of tokens; a draw at the bound forgets everything in two tokens, and
    a fault in the carried state would not reach the logits), no channel sits at the
    bound, and every token's differs (``f = u W_f`` is of unit scale).

    The router's bias is a float32 buffer, normal(0, ``ROUTER_BIAS``): nonzero so that
    it chooses (at 0.03 beside sigmoid scores of spread 0.2 two tokens in three pick
    another set of experts for it, and the groups kept move with it), small so that the
    loads do not follow the seed (LFM2's lesson: at 0.1 this chip's group drew 10.7% to
    12.5% of the picks by seed and the wave's time followed: my chip runs, PR 58).  A routed expert's ``W_down`` is drawn at its scale over
    ``num_experts_per_tok``: routing is discrete, a bfloat16 engine and this float32
    reference break a near-tie between a token's eighth and ninth expert
    differently, and the routed part's share of the residual stream is how far one
    such tie moves a row's logits.  Call it under ``jax.jit`` with the key as an
    argument."""
    check(sizes)
    d, e = sizes["hidden_size"], router_width(sizes)
    h, dh = sizes["num_attention_heads"], sizes["head_dim"]
    rank, nope, rope = sizes["kv_lora_rank"], sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"]
    taps, bound = sizes["short_conv_kernel_size"], -float(sizes["kda_lower_bound"])
    kinds = layer_kinds(sizes)
    k_emb, k_head, k_layers, k_experts = jax.random.split(key, 4)

    def linear(key, *shape):
        """[..., fan_in, fan_out]"""
        return jax.random.normal(key, shape, dtype) * float(shape[-2]) ** -0.5

    def ffn(key, width, *lead):
        ks = jax.random.split(key, 3)
        return {"w_gate": linear(ks[0], *lead, d, width), "w_up": linear(ks[1], *lead, d, width),
                "w_down": linear(ks[2], *lead, width, d)}

    def place(key, depth, kind, dense):
        ks = jax.random.split(key, 10)
        lp = {"op_norm": jnp.ones((depth, d), dtype), "ffn_norm": jnp.ones((depth, d), dtype)}
        if kind == KDA:
            rates = jnp.exp(jnp.linspace(math.log(DECAY_RATES[0]), math.log(DECAY_RATES[1]), dh))
            slopes = jnp.linspace(math.log(GATE_SLOPES[0]), math.log(GATE_SLOPES[1]), h)
            lp["mixer"] = {
                "w_qkv": linear(ks[0], depth, d, 3 * h * dh),
                "filter": jax.random.normal(ks[1], (depth, taps, 3 * h * dh), dtype) * float(taps) ** -0.5,
                "w_beta": linear(ks[2], depth, d, h), "w_f": linear(ks[3], depth, d, h * dh),
                "A_log": jnp.broadcast_to(slopes, (depth, h)).astype(dtype),
                "dt_bias": jnp.broadcast_to(jnp.tile(jnp.log(rates / (bound - rates)), h),
                                            (depth, h * dh)).astype(dtype),
                "norm": jnp.ones((depth, dh), dtype), "w_gate": linear(ks[4], depth, d, h),
                "w_out": linear(ks[5], depth, h * dh, d)}
        else:
            lp["attn"] = {"wq": linear(ks[0], depth, d, h * (nope + rope)),
                          "wkv_a": linear(ks[1], depth, d, rank + rope),
                          "kv_norm": jnp.ones((depth, rank), dtype),
                          "wkv_b": linear(ks[2], depth, rank, h * (nope + sizes["v_head_dim"])),
                          "w_gate": linear(ks[4], depth, d, h),
                          "wo": linear(ks[5], depth, h * sizes["v_head_dim"], d)}
        if dense:
            lp["mlp"] = ffn(ks[6], sizes["intermediate_size"], depth)
        else:
            lp["moe"] = {"gate": {"wg": linear(ks[6], depth, d, e),
                                  "bias": jax.random.normal(ks[7], (depth, e), jnp.float32) * ROUTER_BIAS},
                         "shared": ffn(ks[8], sizes["moe_shared_expert_intermediate_size"]
                                       * sizes["num_shared_experts"], depth)}
        return lp

    runs = []
    for start, period, repeats in segments(sizes):
        keys = jax.random.split(jax.random.fold_in(k_layers, start), period)
        runs.append(tuple(place(keys[j], repeats, *kinds[start + j]) for j in range(period)))
    n_moe = sum(not dense for _, dense in kinds)
    experts = ffn(k_experts, sizes["moe_intermediate_size"], n_moe, sizes["num_experts"])
    experts["w_down"] = experts["w_down"] / sizes["num_experts_per_tok"]
    return {"embed": jax.random.normal(k_emb, (sizes["vocab_size"], d), dtype) * 0.02,
            "segments": runs, "experts": experts, "final_norm": jnp.ones((d, ), dtype),
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
    ``fmt`` (fp8 below bfloat16).  Neutral gains (ones) stay."""
    return jax.tree_util.tree_map(lambda w: round_to(w, fmt), params)


def f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


def rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain.astype(jnp.float32)


def swiglu(x, w):
    w = f32(w)
    return (jax.nn.silu(x @ w["w_gate"]) * (x @ w["w_up"])) @ w["w_down"]


def by_rows(fn, *xs, block=ROW_BLOCK):
    """``fn`` over blocks of the rows of ``xs`` (each ``[S, ...]``), the results
    laid under one another: the arithmetic is a row's own, the blocks are memory's."""
    s = xs[0].shape[0]
    block = min(block, s)
    pad = (-s) % block
    cut = [jnp.pad(x, ((0, pad), ) + ((0, 0), ) * (x.ndim - 1)).reshape((-1, block) + x.shape[1:])
           for x in xs]
    out = jax.lax.map(lambda args: fn(*args), tuple(cut))
    return out.reshape((-1, ) + out.shape[2:])[:s]


# ------------------------------------------------------- Kimi Delta Attention
def delta_rule(q, k, v, alpha, beta, state=None):
    """The delta rule with a decay a channel, token by token.  q, k, alpha ``[S,
    H, dk]``, v ``[S, H, dv]``, beta ``[S, H]``; ``state`` ``[H, dk, dv]`` (zeros
    where None).  Returns (o ``[S, H, dv]``, the state after the last token)."""
    if state is None:
        state = jnp.zeros((q.shape[1], q.shape[2], v.shape[2]), jnp.float32)

    def token(s, inp):
        q_t, k_t, v_t, a_t, b_t = inp
        s = s * a_t[:, :, None]
        d = b_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", s, k_t))
        s = s + k_t[:, :, None] * d[:, None, :]
        return s, jnp.einsum("hkv,hk->hv", s, q_t)

    state, out = jax.lax.scan(token, state, (q, k, v, alpha, beta))
    return out, state


def l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def kda_gate(sizes, u, w):
    """``g`` ``[S, H, dh]``, the log of a token's decay a head a channel: FLA's
    ``kda`` gate with a ``lower_bound`` (``kda_safe_gate``)."""
    h, dh = sizes["num_attention_heads"], sizes["head_dim"]
    f = (u @ w["w_f"] + w["dt_bias"]).reshape(-1, h, dh)
    return sizes["kda_lower_bound"] * jax.nn.sigmoid(jnp.exp(w["A_log"])[None, :, None] * f)


def kimi_delta_attention(sizes, u, w):
    """The KDA operator over one whole sequence, u ``[S, D]`` (the normed stream),
    ``HEAD_BLOCK`` heads at a time: every step before ``W_o`` is a head's own (the
    projections' columns, the depth-wise filter, the norms, the gate, the recurrence),
    so the blocks are memory's and ``W_o`` sums them."""
    h, dh = sizes["num_attention_heads"], sizes["head_dim"]
    w = f32(w)
    block = min(HEAD_BLOCK, h)
    taps = w["filter"].shape[0]
    s = u.shape[0]

    def heads(first):
        def mine(m, part=0, each=dh):  # the block's columns (rows, for W_o) of one part of a matrix
            return jax.lax.dynamic_slice_in_dim(m, part * h * each + first * each, block * each,
                                                axis=m.ndim - 1)

        def filtered(part):  # q, k or v of the block: projected, filtered, SiLU
            mixed = jnp.pad(u @ mine(w["w_qkv"], part), ((taps - 1, 0), (0, 0)))  # zero before token 0
            kernel = mine(w["filter"], part)
            return jax.nn.silu(sum(kernel[j] * mixed[j:j + s] for j in range(taps))).reshape(s, block, dh)

        q, k, v = filtered(0), filtered(1), filtered(2)
        beta = jax.nn.sigmoid(u @ mine(w["w_beta"], each=1))
        g = kda_gate({**sizes, "num_attention_heads": block}, u, {
            "w_f": mine(w["w_f"]), "dt_bias": mine(w["dt_bias"]), "A_log": mine(w["A_log"], each=1)})
        o, _ = delta_rule(l2norm(q) * dh ** -0.5, l2norm(k), v, jnp.exp(g), beta)
        o = rms_norm(o, w["norm"], sizes["rms_norm_eps"])  # over a head's values (group_norm_size 1)
        o = o * jax.nn.sigmoid(u @ mine(w["w_gate"], each=1))[:, :, None]  # ONE gate a head
        return o.reshape(s, block * dh) @ jax.lax.dynamic_slice_in_dim(w["w_out"], first * dh, block * dh, 0)

    return jnp.sum(jax.lax.map(heads, jnp.arange(0, h, block)), axis=0)


# ------------------------------------------------------------ latent attention
def rotary_pairs(x, positions, theta):
    """x [S, heads, d]: the pairs ``(2i, 2i + 1)`` turned by ``positions x
    theta^(-2i/d)`` (DeepSeek's interleaved convention, ``rope_interleave``)."""
    d = x.shape[-1]
    angle = positions[:, None, None] * theta ** -(jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    pairs = x.reshape(x.shape[:-1] + (d // 2, 2))
    even, odd = pairs[..., 0], pairs[..., 1]
    return jnp.stack([even * jnp.cos(angle) - odd * jnp.sin(angle),
                      odd * jnp.cos(angle) + even * jnp.sin(angle)], axis=-1).reshape(x.shape)


def latent_attention(sizes, u, w):
    """The MLA operator over one whole sequence, u ``[S, D]``: keys and values a
    head materialised from the latent (nothing absorbed), every query against all
    S keys under the causal mask, a block of queries at a time."""
    h, rank = sizes["num_attention_heads"], sizes["kv_lora_rank"]
    nope, rope, dv = sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"], sizes["v_head_dim"]
    theta = float(sizes["rope_theta"])
    w = f32(w)
    s = u.shape[0]
    pos = jnp.arange(s, dtype=jnp.float32)
    kva = by_rows(lambda r: r @ w["wkv_a"], u)
    c = rms_norm(kva[:, :rank], w["kv_norm"], sizes["rms_norm_eps"])
    k_r = rotary_pairs(kva[:, None, rank:], pos, theta)  # [S, 1, rope]: one for all heads
    kv = by_rows(lambda r: r @ w["wkv_b"], c).reshape(s, h, nope + dv)
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(k_r, (s, h, rope))], axis=-1)
    v = kv[..., nope:]

    def block(rows, at):
        q = (rows @ w["wq"]).reshape(-1, h, nope + rope)
        q = jnp.concatenate([q[..., :nope], rotary_pairs(q[..., nope:], at, theta)], axis=-1)
        scores = jnp.einsum("qhd,shd->hqs", q, k) / math.sqrt(nope + rope)
        seen = pos[None, :] <= at[:, None]
        probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), axis=-1)
        out = jnp.einsum("hqs,shd->qhd", probs, v)
        out = out * jax.nn.sigmoid(rows @ w["w_gate"])[:, :, None]  # ONE gate a head
        return out.reshape(-1, h * dv) @ w["wo"]

    return by_rows(block, u, pos, block=Q_BLOCK)


# ------------------------------------------------------------ expert layer
def router(sizes, n, gate):
    """n [S, D] -> combine [S, E]: DeepSeek-V3's group-limited choice under sigmoid
    scores and a selection bias, by brute force.  The weights of each token's
    picks (the picked scores WITHOUT the bias over their sum, times
    ``routed_scaling_factor``), zero elsewhere."""
    groups, kept, k = sizes["n_group"], sizes["topk_group"], sizes["num_experts_per_tok"]
    scores = jax.nn.sigmoid(n @ gate["wg"].astype(jnp.float32))
    choice = scores + gate["bias"].astype(jnp.float32)
    by_group = choice.reshape(choice.shape[0], groups, -1)
    group_score = jnp.sum(jnp.sort(by_group, axis=-1)[..., -2:], axis=-1)  # its two largest
    _, best = jax.lax.top_k(group_score, kept)
    stays = jnp.zeros(group_score.shape, bool).at[jnp.arange(choice.shape[0])[:, None], best].set(True)
    allowed = jnp.where(jnp.repeat(stays, by_group.shape[-1], axis=1), choice, -jnp.inf)
    _, top_idx = jax.lax.top_k(allowed, k)
    top = jnp.take_along_axis(scores, top_idx, axis=-1)
    if sizes["norm_topk_prob"]:
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    top = top * sizes["routed_scaling_factor"]
    return jnp.zeros_like(scores).at[jnp.arange(scores.shape[0])[:, None], top_idx].set(top)


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
    the weighted sum over the held experts a token picked, and the shared
    expert's output, which is the same on every chip."""
    experts = moe["experts"]
    if layer is None:
        experts, layer = jax.tree_util.tree_map(lambda m: m[None], experts), 0
    combine = router(sizes, n, moe["gate"])
    held = experts["w_gate"].shape[1]
    routed = experts_ffn(n, combine[:, chip * held:(chip + 1) * held], experts, layer)
    return routed, swiglu(n, moe["shared"])


# ---------------------------------------------------------------- the model
def block(sizes, x, w, experts, layer):
    """One layer: the mixer its parameters name, then its FFN."""
    eps = sizes["rms_norm_eps"]
    u = rms_norm(x, w["op_norm"], eps)
    x = x + (kimi_delta_attention(sizes, u, w["mixer"]) if "mixer" in w
             else latent_attention(sizes, u, w["attn"]))

    def ffn(rows):
        n = rms_norm(rows, w["ffn_norm"], eps)
        return swiglu(n, w["mlp"]) if "mlp" in w else sum(
            layer_parts(sizes, {**w["moe"], "experts": experts}, n, layer=layer))

    return x + by_rows(ffn, x)


def embedded(sizes, table, ids):
    return table[ids].astype(jnp.float32)


def stream(sizes, params, ids, embed=embedded, block=block):
    """ids [S] -> the residual stream after the last layer [S, D], float32.  The
    layers are taken from their stacks in the order they are numbered.  (``embed``
    and ``block``: the same two functions compiled one at a time, :func:`logits_rows`.)"""
    check(sizes)
    x = embed(sizes, params["embed"], ids)
    layer = 0
    for (start, period, repeats), run in zip(segments(sizes), params["segments"]):
        for i in range(repeats):
            for stack in run:
                w = jax.tree_util.tree_map(lambda a, i=i: a[i], stack)
                x = block(sizes, x, w, params["experts"], layer - sizes["first_k_dense_replace"])
                layer += 1
    return x


def hidden_states(sizes, params, ids):
    """ids [S] -> the final normed hidden states [S, D], float32."""
    return rms_norm(stream(sizes, params, ids), params["final_norm"], sizes["rms_norm_eps"])


@functools.partial(jax.jit, static_argnums=(0, ))
def _embedded(sizes_items, table, ids):
    return embedded(_thawed(sizes_items), table, ids)


@functools.partial(jax.jit, static_argnums=(0, ))
def _block(sizes_items, x, w, experts, layer):
    with jax.default_matmul_precision("highest"):
        return block(_thawed(sizes_items), x, w, experts, layer)


@functools.partial(jax.jit, static_argnums=(0, ))
def _head_rows(sizes_items, x, gain, head, rows):
    with jax.default_matmul_precision("highest"):
        return rms_norm(x[rows], gain, _thawed(sizes_items)["rms_norm_eps"]) @ head.astype(jnp.float32)


def logits_rows(sizes, params, ids, rows):
    """Logits [len(rows), V] of one sequence ``ids`` [S] at positions ``rows``:
    ``hidden_states(...)[rows] @ W_head``.  The mask, the filter and the recurrence
    are causal, so tokens padded on after the last row change nothing.  Each layer
    is a program of its own here (one a kind of layer and a length), so that what
    is live at once is one layer's float32 weights and temporaries beside the 9.9
    GB of bfloat16 weights the engine leaves; the final norm is a row's own and is
    taken over the rows asked for."""
    items = _static(sizes)
    x = stream(sizes, params, jnp.asarray(ids, jnp.int32),
               embed=lambda _, table, ids: _embedded(items, table, ids),
               block=lambda _, x, w, experts, layer: _block(items, x, w, experts,
                                                            jnp.asarray(layer, jnp.int32)))
    return _head_rows(items, x, params["final_norm"], params["lm_head"], jnp.asarray(rows, jnp.int32))


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
