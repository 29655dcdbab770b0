"""Plain reference of Arcee's Trinity-Large-Preview (the model's ``config.json``,
``model_type: afmoe``; HF ``transformers/models/afmoe/modeling_afmoe.py`` is the
written source of every layer), as ONE CHIP'S SHARE of a deployment in which
``EP_CHIPS`` = 8 chips share each layer.  A decoder whose attention layers are of
two kinds (``layer_types``: ``sliding_attention`` or ``full_attention``), with a
norm on each sublayer's input AND on its output (a sandwich), a leading run of
dense layers and then expert layers.  ``N*`` are RMS norms ``x / sqrt(mean(x^2) +
eps) * w``.  One layer ``i``, ``h`` ``[S, D]`` of one sequence:

    u = N_in(h)
    q = heads(u W_q), k = heads(u W_k), v = heads(u W_v), g = u W_gate   (no biases)
    q, k <- RMS norm over each head's 128 values, learned gains
    sliding_attention: q, k <- rotate-half rotary over the whole head (theta 10000);
        token t attends positions s with t - sliding_window < s <= t
    full_attention:    NO positions; token t attends every s <= t
    a = ( softmax(q k^T / sqrt(128)) v * sigmoid(g) ) W_o                  (GQA)
    h = h + N_post_attn(a)
    n = N_pre_mlp(h)
    i < num_dense_layers:  m = W_down (silu(n W_gate) * n W_up)            (width 12288)
    else:  s = sigmoid(float32(n W_r)) over ALL ``EP_CHIPS x held`` experts
           picks = the top ``num_experts_per_tok`` of s + expert_bias
           w = s[picks] / (sum s[picks] + 1e-20) * route_scale             (the bias never weighs)
           m = Shared(n) + sum_{i picked AND held here} w_i E_i(n)         (SwiGLUs of width 3072)
    h = h + N_post_mlp(m)

with ``h_0 = embed[ids] * sqrt(hidden_size)`` (``mup_enabled``) and ``logits =
N_f(h_L) W_head`` (untied).

**The share.**  ``num_experts`` in the configuration is the number of experts whose
weights are HERE (32 of the published 256): this chip is chip 0 of eight, holds
experts 0..31, routes over all 256, adds its own experts' part and the shared
expert (which every chip computes whole for its own tokens) and leaves out what
the other chips' experts would add.  That partial sum goes through ``N_post_mlp``
and on to the next layer.  ``vocab_size`` is this chip's eighth of the vocabulary
(rows of the embedding, columns of the head).  Attention is whole on every chip.
``layer_parts`` returns the routed part of any chip's share apart from the shared
expert's, so a test can add the eight up to the uncut layer.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision, one
sequence at a time: attention a full masked softmax over ALL S keys, the mask of
the layer's kind stated as ``[queries, S]`` comparisons of positions, computed a
block of queries at a time (``Q_BLOCK``) so that a 32,768-token sequence fits
beside the engine; the per-token layers a block of rows at a time (``ROW_BLOCK``);
every held expert computed for every token and combined through an ``[S, E]``
matrix of weights.  No cache, no window of keys cut out, no kernel, no sorting.

Departures from ``modeling_afmoe.py``, each also under the configuration file's
``assumed``: none in the mathematics as the issue's statement of it has it; the
installed ``transformers`` (4.57) has no ``afmoe``, so no test holds this file to
HF's code: the statement above is what it is held to.

Nothing here comes from ``deepspeed_tpu``: sizes come from the configuration
file's published keys, weights from the seed.  ``init_params`` lays the weights
out as the pytree the program's ``models/afmoe.py`` takes, because that layout is
the program's input interface; the same arrays go to both: a run of layers that
repeats a pattern of kinds is a tuple of one stack ``[repeats, ...]`` a place of
the pattern (``segments``); the experts of all expert layers are one stack
``[expert layers, held, ...]``.
"""

import functools
import math

import jax
import jax.numpy as jnp

EP_CHIPS = 8      # chips that share each layer in the deployment this file is one chip of
Q_BLOCK = 64      # queries an attention block: 48 heads x 64 x 32,768 keys x 4 B = 0.4 GB of scores
ROW_BLOCK = 2048  # rows a block of the per-token layers: [2048, 12288] float32 = 0.1 GB
ROUTE_NORM_EPS = 1e-20
SLIDING = "sliding_attention"
# the deviation of a layer's scores q . k / sqrt(128) under ``init_params``: the q and k gains
# are its root each (unit gains: 1.0, an attention that is an average of thousands of values)
SCORE_DEVIATION = 3.0


def router_width(sizes) -> int:
    """Experts the router scores: the held count times the chips of the deployment."""
    return EP_CHIPS * sizes["num_experts"]


def layer_kinds(sizes):
    """``[(layer type, dense?)]`` a layer: the first ``num_hidden_layers`` of the
    published ``layer_types``, the first ``num_dense_layers`` of them dense."""
    return [(kind, i < sizes["num_dense_layers"])
            for i, kind in enumerate(sizes["layer_types"][:sizes["num_hidden_layers"]])]


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


def init_params(sizes, key, dtype=jnp.float32):
    """Random weights from ``key`` (``jax.random.PRNGKey(seed)``): normal(0,
    1/sqrt(fan_in)) projections, experts and router (logits of unit scale, so that
    routing is not uniform), an embedding at normal(0, 0.02) (times sqrt(3072) = 55
    the stream begins at about one, what each sandwiched sublayer adds), the four
    norms' gains and the final norm's at one.

    **The q and k gains.**  With QK-norm and unit gains a head's scores over
    random weights have deviation one: the softmax over thousands of keys is near
    uniform, its output an average that the sandwich norm blows up to unit size
    whatever it was, and a fault that loses or adds keys moves a direction that is
    noise to begin with.  A trained model's gains make its heads choose.  Here both
    gains are ``sqrt(SCORE_DEVIATION)``: scores of deviation 3, a token's attention
    concentrated on a handful of keys that lie anywhere in its past, so that a full
    layer given the window, or a windowed layer given none, attends other tokens.

    ``expert_bias`` is a float32 buffer, normal(0, 0.64 / E) (LFM2's and GLM-5's
    lesson: nonzero so that it chooses, small so that the loads do not follow the
    seed).  A routed expert's ``W_down`` is drawn at its scale over
    ``num_experts_per_tok``: routing is discrete, a bfloat16 engine and this
    float32 reference break a near-tie between a token's fourth and fifth expert
    differently, and the routed part's share of the layer's sum is how far one
    such tie moves a row (PERF.md section 6, PRs 31 and 33).  Call it under
    ``jax.jit`` with the key as an argument."""
    d, dh = sizes["hidden_size"], sizes["head_dim"]
    h, kv, e = sizes["num_attention_heads"], sizes["num_key_value_heads"], router_width(sizes)
    kinds = layer_kinds(sizes)
    k_emb, k_head, k_layers, k_experts = jax.random.split(key, 4)

    def linear(key, *shape):
        """[..., fan_in, fan_out]"""
        return jax.random.normal(key, shape, dtype) * float(shape[-2]) ** -0.5

    def ffn(key, width, *lead):
        ks = jax.random.split(key, 3)
        return {"w_gate": linear(ks[0], *lead, d, width), "w_up": linear(ks[1], *lead, d, width),
                "w_down": linear(ks[2], *lead, width, d)}

    def place(key, depth, dense):
        ks = jax.random.split(key, 8)
        lp = {name: jnp.ones((depth, d), dtype)
              for name in ("in_norm", "post_attn_norm", "pre_mlp_norm", "post_mlp_norm")}
        gain = jnp.full((depth, dh), math.sqrt(SCORE_DEVIATION), dtype)
        lp["attn"] = {"wq": linear(ks[0], depth, d, h * dh), "wk": linear(ks[1], depth, d, kv * dh),
                      "wv": linear(ks[2], depth, d, kv * dh), "w_gate": linear(ks[3], depth, d, h * dh),
                      "wo": linear(ks[4], depth, h * dh, d), "q_norm": gain, "k_norm": gain}
        if dense:
            lp["mlp"] = ffn(ks[5], sizes["intermediate_size"], depth)
        else:
            lp["moe"] = {"gate": {"wg": linear(ks[5], depth, d, e),
                                  "bias": jax.random.normal(ks[6], (depth, e), jnp.float32) * (0.64 / e)},
                         "shared": ffn(ks[7], sizes["moe_intermediate_size"]
                                       * sizes["num_shared_experts"], depth)}
        return lp

    runs = []
    for start, period, repeats in segments(sizes):
        keys = jax.random.split(jax.random.fold_in(k_layers, start), period)
        runs.append(tuple(place(keys[j], repeats, kinds[start + j][1]) for j in range(period)))
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
    ``fmt`` (fp8 below bfloat16)."""
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


# ---------------------------------------------------------------- attention
def rotary(x, positions, theta):
    """x [S, heads, dh]: the pairs ``(i, i + dh/2)`` turned by ``positions x
    theta^(-2i/dh)`` (HF ``rotate_half``), over the whole head."""
    half = x.shape[-1] // 2
    angle = positions[:, None, None] * theta ** -(jnp.arange(half, dtype=jnp.float32) / half)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * jnp.cos(angle) - x2 * jnp.sin(angle),
                            x2 * jnp.cos(angle) + x1 * jnp.sin(angle)], axis=-1)


def attention(sizes, u, w, window, turned: bool):
    """The attention operator of one layer over one whole sequence, u ``[S, D]``
    (the normed stream): every query against ALL S keys under the mask of the
    layer's kind (``window``: how many of the newest positions a token attends,
    None for all; ``turned``: rotary on q and k), a block of queries at a time."""
    h, kv, dh = sizes["num_attention_heads"], sizes["num_key_value_heads"], sizes["head_dim"]
    eps, theta = sizes["rms_norm_eps"], float(sizes["rope_theta"])
    w = f32(w)
    s = u.shape[0]
    k_pos = jnp.arange(s, dtype=jnp.float32)
    k = rms_norm(by_rows(lambda r: r @ w["wk"], u).reshape(s, kv, dh), w["k_norm"], eps)
    v = by_rows(lambda r: r @ w["wv"], u).reshape(s, kv, dh)
    if turned:
        k = rotary(k, k_pos, theta)

    def block(rows, pos):
        q = rms_norm((rows @ w["wq"]).reshape(-1, kv, h // kv, dh), w["q_norm"], eps)
        if turned:
            q = rotary(q.reshape(-1, h, dh), pos, theta).reshape(q.shape)
        scores = jnp.einsum("qkgd,skd->kgqs", q, k) / math.sqrt(dh)
        seen = k_pos[None, :] <= pos[:, None]
        if window is not None:  # the ``window`` newest positions, the token's own among them
            seen = seen & (k_pos[None, :] > pos[:, None] - window)
        probs = jax.nn.softmax(jnp.where(seen[None, None], scores, -jnp.inf), axis=-1)
        out = jnp.einsum("kgqs,skd->qkgd", probs, v).reshape(-1, h * dh)
        return (out * jax.nn.sigmoid(rows @ w["w_gate"])) @ w["wo"]

    return by_rows(block, u, k_pos, block=Q_BLOCK)


# ------------------------------------------------------------ expert layer
def router(sizes, n, gate):
    """n [S, D] -> combine [S, E]: the weights of each token's picks (the top-k of
    score + bias; the picked scores WITHOUT the bias over their sum, times
    ``route_scale``), zero elsewhere."""
    scores = jax.nn.sigmoid(n @ gate["wg"].astype(jnp.float32))
    _, top_idx = jax.lax.top_k(scores + gate["bias"].astype(jnp.float32), sizes["num_experts_per_tok"])
    top = jnp.take_along_axis(scores, top_idx, axis=-1)
    if sizes["route_norm"]:
        top = top / (jnp.sum(top, axis=-1, keepdims=True) + ROUTE_NORM_EPS)
    top = top * sizes["route_scale"]
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
def block(sizes, x, w, experts, layer, sliding: bool):
    """One layer: attention of its kind (a ``sliding_attention`` layer has the
    window and the rotary, a ``full_attention`` layer neither) and its FFN, each
    between two norms."""
    eps = sizes["rms_norm_eps"]
    a = attention(sizes, rms_norm(x, w["in_norm"], eps), w["attn"],
                  sizes["sliding_window"] if sliding else None, sliding)
    x = x + rms_norm(a, w["post_attn_norm"], eps)

    def ffn(rows):
        n = rms_norm(rows, w["pre_mlp_norm"], eps)
        m = swiglu(n, w["mlp"]) if "mlp" in w else sum(
            layer_parts(sizes, {**w["moe"], "experts": experts}, n, layer=layer))
        return rms_norm(m, w["post_mlp_norm"], eps)

    return x + by_rows(ffn, x)


def embedded(sizes, table, ids):
    x = table[ids].astype(jnp.float32)
    return x * math.sqrt(sizes["hidden_size"]) if sizes["mup_enabled"] else x


def stream(sizes, params, ids, embed=embedded, block=block):
    """ids [S] -> the residual stream after the last layer [S, D], float32.  The
    layers are taken from their stacks in the order they are numbered.  (``embed``
    and ``block``: the same two functions compiled one at a time, :func:`logits_rows`.)"""
    x = embed(sizes, params["embed"], ids)
    kinds, layer = layer_kinds(sizes), 0
    for (start, period, repeats), run in zip(segments(sizes), params["segments"]):
        for i in range(repeats):
            for stack in run:
                w = jax.tree_util.tree_map(lambda a, i=i: a[i], stack)
                x = block(sizes, x, w, params["experts"], layer - sizes["num_dense_layers"],
                          kinds[layer][0] == SLIDING)
                layer += 1
    return x


def hidden_states(sizes, params, ids):
    """ids [S] -> the final normed hidden states [S, D], float32."""
    return rms_norm(stream(sizes, params, ids), params["final_norm"], sizes["rms_norm_eps"])


@functools.partial(jax.jit, static_argnums=(0, ))
def _embedded(sizes_items, table, ids):
    return embedded(_thawed(sizes_items), table, ids)


@functools.partial(jax.jit, static_argnums=(0, 5))
def _block(sizes_items, x, w, experts, layer, sliding):
    with jax.default_matmul_precision("highest"):
        return block(_thawed(sizes_items), x, w, experts, layer, sliding)


@functools.partial(jax.jit, static_argnums=(0, ))
def _head_rows(sizes_items, x, gain, head, rows):
    with jax.default_matmul_precision("highest"):
        return rms_norm(x[rows], gain, _thawed(sizes_items)["rms_norm_eps"]) @ head.astype(jnp.float32)


def logits_rows(sizes, params, ids, rows):
    """Logits [len(rows), V] of one sequence ``ids`` [S] at positions ``rows``:
    ``hidden_states(...)[rows] @ W_head``.  Both masks are causal, so tokens
    padded on after the last row change nothing.  Each layer is a program of its
    own here (one a kind of layer and a length), so that what is live at once is
    one layer's float32 weights and temporaries: a 32,768-token sequence then
    fits in the 5.8 GB the engine leaves (as ONE program the model asked for 7.8
    GB there: my chip run, PR 56); the final norm is a row's own and is taken
    over the rows asked for."""
    items = _static(sizes)
    x = stream(sizes, params, jnp.asarray(ids, jnp.int32),
               embed=lambda _, table, ids: _embedded(items, table, ids),
               block=lambda _, x, w, experts, layer, sliding: _block(
                   items, x, w, experts, jnp.asarray(layer, jnp.int32), sliding))
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
