"""Plain reference of NVIDIA-Nemotron-3-Nano-30B-A3B's language model (the
model's ``config.json``, ``model_type: nemotron_h``; the modeling code is remote
code and not on this machine, so the layers are as ISSUE 62 states them from the
catalog row, and the Mamba-2 mixer with groups and its grouped gated norm are held
by a test to ``transformers``' ``Zamba2MambaMixer`` / ``Zamba2RMSNormGated``, the
same mathematics), as ONE CHIP'S SHARE of a deployment in which ``EP_CHIPS`` = 2
chips share each layer.  A pre-norm decoder in which A LAYER IS ONE PART ALONE:
``hybrid_override_pattern[i]`` names layer ``i`` ``M`` (Mamba-2), ``E`` (experts)
or ``*`` (attention), and with ``u = rms(x; w) = x / sqrt(mean(x^2) + eps) * w``
(a plain gain, eps ``layer_norm_epsilon``), ``x`` ``[S, D]`` of one sequence:

    M:  [z | xBC | dt] = u W_in     (I | I + 2 G N | H columns; I = H P, NOT expand x D)
        xBC = silu(conv(xBC) + bias)   (depth-wise causal filter of ``conv_kernel`` taps,
                                        the columns zero before the first token)
        [x (H, P) | B (G, N) | C (G, N)] = xBC
        dt = softplus(dt + dt_bias)  (no clamp);   A = -exp(A_log)      a head
        for head h with g = h // (H / G), S [P, N] zero before the first token:
            S <- exp(dt_t A) S + dt_t x_t B_{g,t}^T;    y_t = S C_{g,t} + D x_t
        y = x + W_out ( rms_{each group of I / G columns}(y * silu(z)) * w_norm )
            (the gate first, the norm after: ``norm_before_gate`` false)
    *:  q = heads(u W_q) (H_q x head_dim), k, v = heads(u W_k), heads(u W_v) (KV x head_dim)
        y = x + W_o softmax_causal(q k^T / sqrt(head_dim)) v     (GQA; NO positions at all)
    E:  s = sigmoid(float32(u W_r)) over ALL ``EP_CHIPS x held`` experts
        picks = top ``num_experts_per_tok`` of s + bias  (``n_group`` 1: no group limit)
        w = s[picks] / (sum(s[picks]) + 1e-20) * routed_scaling_factor
        y = x + sum_{i picked AND held here} w_i E_i(u) + Shared(u)
            E_i(u) = W_down_i relu(W_up_i u)^2  (UNGATED: two matrices, both stored
            [width, hidden]); Shared the same form

then the final ``rms`` and an UNTIED head; the embedding as stored; no bias in any
projection.

**The share.**  ``n_routed_experts`` in the configuration is the number of experts
whose weights are HERE (64 of the published 128): this chip is chip 0 of two, holds
experts 0..63, routes over all 128, adds its own experts' part and the shared
expert (which every chip computes whole for its own tokens) and leaves out what the
other chip's experts would add.  That partial sum goes on to the next layer.
``vocab_size`` is this chip's half of the vocabulary (rows of the embedding, columns
of the head).  The mixers are whole on every chip.  ``layer_parts`` returns the
routed part of any chip's share apart from the shared expert's, so a test can add
the two up to the uncut layer.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision, one
sequence at a time: the recurrence TOKEN BY TOKEN (a ``lax.scan`` over the
positions; no chunks: it shares no algebra with the program's chunked scan), the
filter over the whole sequence, attention a full masked softmax in blocks of
queries, every held expert computed for every token and combined through an ``[S,
E]`` matrix of weights.  No cache, no state, no kernel, no sorting.

Departures and readings, each also under the configuration file's ``assumed``:
(1) NO ROTARY in the attention layers (Nemotron-H's attention applies none;
``rope_theta`` and ``partial_rotary_factor`` are published and unused): this
issue's reading; (2) ``I = H P`` and ``expand`` unused; (3) the grouped norm after
the gate, over groups of ``I / G``; (4) no ``dt`` clamp (``time_step_limit`` is not
among the keys: HF's default ``(0, inf)``; ``time_step_min`` / ``_max`` / ``_floor``
are the initialiser's); (5) the ``+ 1e-20`` in the weights' sum; (6)
``intermediate_size`` is the absent ``-`` layer's and is read nowhere; (7)
``A_log``, ``dt_bias`` and the router's bias are drawn as ``init_params`` says.

Nothing here comes from ``deepspeed_tpu``: sizes come from the configuration
file's published keys, weights from the seed.  ``init_params`` lays the weights
out as the pytree the program's ``models/nemotron_h.py`` takes, because that layout
is the program's input interface; the same arrays go to both: a run of layers that
repeats a pattern is a tuple of one stack ``[repeats, ...]`` a position of the
pattern (``segments``), a position holding ``norm`` and ONE of ``mixer`` (M),
``attn`` (*), ``alone`` (E: router and shared expert); the experts of all ``E``
layers are one stack ``[E layers, held, ...]``.
"""

import functools
import math

import jax
import jax.numpy as jnp

EP_CHIPS = 2    # chips that share each layer in the deployment this file is one chip of
Q_BLOCK = 512   # queries per attention block
DECAY_RATES = (7e-4, 7e-2)  # exp(A_log) of the first and the last head, log-spaced between
ROUTER_BIAS_SCALE = 0.01    # the selection bias's: what parts a token's sixth score from its seventh
FILTER_BIAS_MEAN = -0.65    # the filter's bias is drawn about it: SiLU's output over unit-scale input is then centred


def router_width(sizes) -> int:
    """Experts the router scores: the held count times the chips of the deployment."""
    return EP_CHIPS * sizes["n_routed_experts"]


def layer_kinds(sizes) -> str:
    """``M`` / ``E`` / ``*`` a layer: the first ``num_hidden_layers`` characters of
    the published ``hybrid_override_pattern``."""
    kinds = sizes["hybrid_override_pattern"][:sizes["num_hidden_layers"]]
    assert len(kinds) == sizes["num_hidden_layers"] and not set(kinds) - set("ME*"), kinds
    return kinds


def segments(sizes):
    """``[(start, period, repeats)]``: from each start the longest run of layers
    that repeats a pattern of ``period`` kinds at least twice, else one layer
    alone.  Two published periods (``MEMEM*E`` twice): ``[(0, 7, 2)]``."""
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


def ssm_widths(sizes):
    """(H, P, N, G, inner columns I = H P, the filter's columns I + 2 G N)."""
    h, p, n, g = (sizes["mamba_num_heads"], sizes["mamba_head_dim"], sizes["ssm_state_size"],
                  sizes["n_groups"])
    assert h % g == 0
    return h, p, n, g, h * p, h * p + 2 * g * n


def init_params(sizes, key, dtype=jnp.float32):
    """Random weights from ``key`` (``jax.random.PRNGKey(seed)``): normal(0,
    1/sqrt(fan_in)) projections, experts, router and head (logits of unit scale, so
    that routing is not uniform), filter taps normal(0, 1/sqrt(taps)) with a bias
    normal(``FILTER_BIAS_MEAN``, 0.1), gains and ``D`` at one, the embedding at
    normal(0, 0.02) (the head is a draw of its own: nothing meets itself there), the
    router's selection bias normal(0, ``ROUTER_BIAS_SCALE``), every ``W_down`` centred
    over its hidden units.

    **What every token shares decides the rate, so the draw takes it out of the stream.**
    A decode step's time is the expert matrices it reads: over six seeds ``serve_tok_s``
    followed the count of held experts a layer-pass's picks named, line for line (my chip
    runs, PR 62).  That count is 61 of 64 where 64 rows route apart, and falls, by another
    amount a seed, with whatever the routers see in EVERY token.  Three things put one
    constant vector into every token of a random network, and a trained one has none of
    them; each is taken out where it arises.  (i) **The router's bias**, at ISSUE 62's
    normal(0, 0.1), CHOSE the picks, since the top scores of 128 sigmoids all lie near 1:
    a step named 16 of the 64 held experts a layer, this chip's share of the picks ran 45%
    to 62% with the seed and the rate spread 6.1%.  A token's sixth and seventh scores lie
    about 0.01 apart, so at 0.01 the bias turns about half the tokens' last pick and
    chooses none (LFM2's and Ling-3.0's lesson, PERF.md section 6).  (ii) **The filter's
    bias**, below.  (iii) **An ungated expert's hidden units are all positive**:
    ``relu(z)^2`` over unit-scale ``z`` has mean 1/2, so ``W_down`` adds half the SUM of
    its rows to every token, a sixth of the shared expert's output by power; ``W_down``
    (shared and routed) is drawn with its rows' sum taken off (``w - mean over the hidden
    units``), which changes a 3,712-row matrix by 1 part in 3,712 of its power.

    **The filter's bias** is drawn about ``FILTER_BIAS_MEAN`` = -0.65, where the SiLU
    of a unit-scale input has mean zero.  About zero, x, B and C all have a mean of
    0.2, every sequence's state converges on ONE common matrix ``mean(x) mean(B)^T``
    that grows with the tokens and each ``M`` layer adds one constant vector to every
    token of every sequence: with (i) at 0.03 a step named 24-27 of the 64 held experts a
    layer and the rate spread 6.0%; with the filter centred about 41 and 2.3%, still over
    what the driver admits; (iii) is what was left (readings: the configuration's
    ``correct.limits_from``).

    **The decay.**  ``exp(A_log)`` is log-spaced over a layer's heads from 7e-4 to
    7e-2 and ``dt_bias`` is 1 (``softplus(dt + 1)`` is about 1.4 over unit-scale
    ``dt``): a head's typical decay a token runs from 0.999 to 0.9, as a trained
    model's heads remember over tens to thousands of tokens (HF's own draw, ``A =
    1..H``, forgets everything at every token, and a fault of the carried state
    would not reach the logits).

    A routed expert's ``W_down`` is drawn at its scale over ``num_experts_per_tok``:
    routing is discrete, a bfloat16 engine and this float32 reference break a
    near-tie between a token's sixth and seventh expert differently, and the routed
    part's share of the stream is how far one such tie moves a row's logits
    (PERF.md section 6, PRs 31 and 33).  Call it under ``jax.jit`` with the key as an
    argument."""
    d, e, dh = sizes["hidden_size"], router_width(sizes), sizes["head_dim"]
    h, kv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    hm, _, _, _, inner, conv = ssm_widths(sizes)
    taps = sizes["conv_kernel"]
    kinds = layer_kinds(sizes)
    k_emb, k_head, k_layers, k_experts = jax.random.split(key, 4)

    def linear(key, *shape):
        """[..., fan_in, fan_out]"""
        return jax.random.normal(key, shape, dtype) * float(shape[-2]) ** -0.5

    def ffn(key, width, *lead):
        ks = jax.random.split(key, 2)
        up = jax.random.normal(ks[0], (*lead, width, d), dtype) * float(d) ** -0.5  # [F, D]: as w_down lies
        down = linear(ks[1], *lead, width, d)
        return {"w_up": up, "w_down": down - jnp.mean(down, axis=-2, keepdims=True)}  # (iii)

    def position(key, depth, kind):
        ks = jax.random.split(key, 6)
        lp = {"norm": jnp.ones((depth, d), dtype)}
        if kind == "M":
            rates = jnp.exp(jnp.linspace(math.log(DECAY_RATES[0]), math.log(DECAY_RATES[1]), hm))
            lp["mixer"] = {"w_in": linear(ks[0], depth, d, inner + conv + hm),
                           "filter": jax.random.normal(ks[1], (depth, taps, conv), dtype)
                           * float(taps) ** -0.5,
                           "conv_bias": jax.random.normal(ks[2], (depth, conv), dtype) * 0.1
                           + FILTER_BIAS_MEAN,
                           "A_log": jnp.broadcast_to(jnp.log(rates), (depth, hm)).astype(dtype),
                           "dt_bias": jnp.ones((depth, hm), dtype), "D": jnp.ones((depth, hm), dtype),
                           "norm": jnp.ones((depth, inner), dtype),
                           "w_out": linear(ks[3], depth, inner, d)}
        elif kind == "*":
            lp["attn"] = {"wq": linear(ks[0], depth, d, h * dh), "wk": linear(ks[1], depth, d, kv * dh),
                          "wv": linear(ks[2], depth, d, kv * dh), "wo": linear(ks[3], depth, h * dh, d)}
        else:
            lp["alone"] = {"gate": {"wg": linear(ks[4], depth, d, e),
                                    "bias": jax.random.normal(ks[0], (depth, e), dtype)
                                    * ROUTER_BIAS_SCALE},
                           "shared": ffn(ks[5], sizes["moe_shared_expert_intermediate_size"], depth)}
        return lp

    runs = []
    for start, period, repeats in segments(sizes):
        keys = jax.random.split(jax.random.fold_in(k_layers, start), period)
        runs.append(tuple(position(keys[j], repeats, kinds[start + j]) for j in range(period)))
    experts = ffn(k_experts, sizes["moe_intermediate_size"], kinds.count("E"), sizes["n_routed_experts"])
    experts["w_down"] = experts["w_down"] / sizes["num_experts_per_tok"]
    return {"embed": jax.random.normal(k_emb, (sizes["vocab_size"], d), dtype) * 0.02,
            "head": linear(k_head, d, sizes["vocab_size"]), "segments": runs, "experts": experts,
            "final_norm": jnp.ones((d, ), dtype)}


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
    """A plain gain."""
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain


def gated_group_norm(y, z, gain, groups, eps):
    """The gate first, then an RMS norm over each of ``groups`` runs of the columns
    (``norm_before_gate`` false), and one gain over all of them."""
    gated = (y * jax.nn.silu(z)).reshape(y.shape[0], groups, -1)
    return rms_norm(gated, 1.0, eps).reshape(y.shape) * gain


def relu2_mlp(x, w):
    """UNGATED: ``W_down relu(W_up x)^2``; ``w_up`` is stored ``[F, D]`` like ``w_down``."""
    w = f32(w)
    return jnp.square(jax.nn.relu(x @ w["w_up"].T)) @ w["w_down"]


# ------------------------------------------------------------------- Mamba-2
def selective_scan(x, dt, a, b, c, d, state=None):
    """The recurrence token by token.  x ``[S, H, P]``, dt ``[S, H]`` (after its
    softplus), a, d ``[H]``, b, c ``[S, G, N]``: head ``h`` reads group ``h // (H /
    G)``; ``state`` ``[H, P, N]`` (zeros where None).  Returns (y ``[S, H, P]``, the
    state after the last token)."""
    heads, groups = x.shape[1], b.shape[1]
    if state is None:
        state = jnp.zeros((heads, x.shape[2], b.shape[2]), jnp.float32)
    group_of = jnp.arange(heads) // (heads // groups)

    def token(s, inp):
        x_t, dt_t, b_t, c_t = inp
        s = (s * jnp.exp(dt_t * a)[:, None, None]
             + (dt_t[:, None] * x_t)[:, :, None] * b_t[group_of][:, None, :])
        return s, jnp.einsum("hpn,hn->hp", s, c_t[group_of]) + d[:, None] * x_t

    state, y = jax.lax.scan(token, state, (x, dt, b, c))
    return y, state


def mamba2(sizes, u, w):
    """The Mamba-2 operator over one whole sequence, u ``[S, D]``."""
    hm, p, n, g, inner, conv = ssm_widths(sizes)
    w = f32(w)
    projected = u @ w["w_in"]
    z, xbc, dt = projected[:, :inner], projected[:, inner:inner + conv], projected[:, inner + conv:]
    taps = w["filter"].shape[0]
    padded = jnp.pad(xbc, ((taps - 1, 0), (0, 0)))  # zero before the first token
    xbc = jax.nn.silu(sum(w["filter"][j] * padded[j:j + xbc.shape[0]] for j in range(taps))
                      + w["conv_bias"])
    x = xbc[:, :inner].reshape(-1, hm, p)
    b = xbc[:, inner:inner + g * n].reshape(-1, g, n)
    c = xbc[:, inner + g * n:].reshape(-1, g, n)
    dt = jax.nn.softplus(dt + w["dt_bias"])  # no clamp
    y, _ = selective_scan(x, dt, -jnp.exp(w["A_log"]), b, c, w["D"])
    return gated_group_norm(y.reshape(-1, inner), z, w["norm"], g,
                            sizes["layer_norm_epsilon"]) @ w["w_out"]


# ---------------------------------------------------------------- attention
def causal_attention(q, k, v, scale, q_block=Q_BLOCK):
    """q [S, H, dh], k/v [S, KV, dh]: query i sees keys j <= i; each group of
    H/KV query heads shares one KV head; scores times ``scale``."""
    s, h, dh = q.shape
    kv = k.shape[1]
    q_block = min(q_block, s)
    pad = (-s) % q_block
    qg = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(-1, q_block, kv, h // kv, dh)
    q_pos = jnp.arange(s + pad).reshape(-1, q_block)
    k_pos = jnp.arange(s)

    def block(args):
        qb, pos = args
        scores = jnp.einsum("qkgd,skd->kgqs", qb, k) * scale
        seen = k_pos[None, :] <= pos[:, None]
        probs = jax.nn.softmax(jnp.where(seen[None, None], scores, -jnp.inf), axis=-1)
        return jnp.einsum("kgqs,skd->qkgd", probs, v)

    return jax.lax.map(block, (qg, q_pos)).reshape(-1, h, dh)[:s]


def attention(sizes, u, w):
    """The attention operator over one whole sequence, u ``[S, D]``: no positions."""
    h, kv, dh = sizes["num_attention_heads"], sizes["num_key_value_heads"], sizes["head_dim"]
    w = f32(w)
    out = causal_attention((u @ w["wq"]).reshape(-1, h, dh), (u @ w["wk"]).reshape(-1, kv, dh),
                           (u @ w["wv"]).reshape(-1, kv, dh), dh ** -0.5)
    return out.reshape(-1, h * dh) @ w["wo"]


# ------------------------------------------------------------ expert layer
def router(sizes, u, gate):
    """u [S, D] -> combine [S, E]: at each token's picks (the top-k of the sigmoid
    scores PLUS the bias) the scores WITHOUT the bias over their sum + 1e-20, times
    ``routed_scaling_factor``; zero elsewhere."""
    gate = f32(gate)
    scores = jax.nn.sigmoid(u @ gate["wg"])
    _, picks = jax.lax.top_k(scores + gate["bias"], sizes["num_experts_per_tok"])
    picked = jnp.take_along_axis(scores, picks, axis=-1)
    weights = picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20) \
        * sizes["routed_scaling_factor"]
    return jnp.zeros_like(scores).at[jnp.arange(scores.shape[0])[:, None], picks].set(weights)


def experts_ffn(u, combine, experts, layer):
    """Every expert of layer ``layer`` of the stack (leaves [L, E, ...]) over
    every token, one at a time, each output weighted by the token's
    ``combine`` column and summed."""

    def one(acc, inp):
        e, weight = inp
        out = relu2_mlp(u, {name: m[layer, e] for name, m in experts.items()})
        return acc + weight[:, None] * out, None

    acc, _ = jax.lax.scan(one, jnp.zeros_like(u),
                          (jnp.arange(experts["w_up"].shape[1]), combine.T))
    return acc


def layer_parts(sizes, moe, u, chip: int = 0, layer=None):
    """(routed, shared) of one expert layer over u [S, D] for the chip that
    holds experts ``chip * held ... (chip + 1) * held - 1`` (``moe["experts"]``
    are those ``held`` experts, or with ``layer`` the whole stack of them):
    the sum over the held experts a token picked, and the shared expert's output,
    which is the same on every chip."""
    experts = moe["experts"]
    if layer is None:
        experts, layer = jax.tree_util.tree_map(lambda m: m[None], experts), 0
    combine = router(sizes, u, moe["gate"])
    held = experts["w_up"].shape[1]
    routed = experts_ffn(u, combine[:, chip * held:(chip + 1) * held], experts, layer)
    return routed, relu2_mlp(u, moe["shared"])


# ---------------------------------------------------------------- the model
def layer(sizes, x, w, experts, expert_layer):
    """One layer: its one norm, the ONE part its parameters hold, the residual."""
    u = rms_norm(x, w["norm"].astype(jnp.float32), sizes["layer_norm_epsilon"])
    if "mixer" in w:
        return x + mamba2(sizes, u, w["mixer"])
    if "attn" in w:
        return x + attention(sizes, u, w["attn"])
    return x + sum(layer_parts(sizes, {**w["alone"], "experts": experts}, u, layer=expert_layer))


def hidden_states(sizes, params, ids):
    """ids [S] -> the final normed hidden states [S, D], float32.  The layers are
    taken from their stacks in the order they are numbered; an ``E`` layer's experts
    are its row of the one stack, counted over the ``E`` layers."""
    x = params["embed"][ids].astype(jnp.float32)
    expert_layer = 0
    for (start, period, repeats), run in zip(segments(sizes), params["segments"]):
        for i in range(repeats):
            for stack in run:
                w = jax.tree_util.tree_map(lambda a, i=i: a[i], stack)
                x = layer(sizes, x, w, params["experts"], expert_layer)
                expert_layer += "alone" in w
    return rms_norm(x, params["final_norm"].astype(jnp.float32), sizes["layer_norm_epsilon"])


@functools.partial(jax.jit, static_argnums=(0,))
def _logits_rows(sizes_items, params, ids, rows):
    with jax.default_matmul_precision("highest"):
        sizes = _thawed(sizes_items)
        x = hidden_states(sizes, params, ids)
        return x[rows] @ params["head"].astype(jnp.float32)


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
