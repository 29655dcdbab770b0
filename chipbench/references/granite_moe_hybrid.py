"""Plain reference of Granite 4.0-H Small (the model's ``config.json``,
``model_type: granitemoehybrid``; HF ``transformers/models/granitemoehybrid/
modeling_granitemoehybrid.py`` is the written source of every layer, its
``torch_forward`` the Mamba-2 layer's, and the tests hold this file to it), as
ONE CHIP'S SHARE of a deployment in which ``EP_CHIPS`` = 2 chips share each
layer.  A pre-norm decoder; ``layer_types`` names each layer ``mamba`` or
``attention``; every layer's FFN is a mixture of experts beside a shared MLP.
With ``r`` = ``residual_multiplier``, one layer, ``x`` ``[S, D]`` of one sequence:

    u = rms(x; w) = x / sqrt(mean(x^2) + eps) * w
    Mamba-2:
        [z | xBC | dt] = u W_in            (I | I + 2 Ns | H columns, I = H x P)
        xBC = silu(conv(xBC) + bias)       (depth-wise causal filter of 4 taps, the
                                            columns zero before the first token)
        [x | B | C] = xBC                  (I | Ns | Ns: B and C shared by all H heads)
        dt = softplus(dt + dt_bias);  A = -exp(A_log)        a head
        for each head, S [P, Ns] zero before the first token, token by token:
            S <- exp(dt_t A) S + dt_t x_t B_t^T;   y_t = S C_t + D x_t
        a = x + r W_out ( rms_I(y * silu(z)) * w_norm )      (the gate INSIDE the norm,
                                                              one group over all I columns)
    attention:
        q = heads(u W_q), k = heads(u W_k), v = heads(u W_v);  NO positions
        a = x + r W_o softmax_causal(q k^T * attention_multiplier) v        (GQA)
    n = rms(a)
    l = n W_r over ALL ``EP_CHIPS x held`` experts, float32; the top
        ``num_experts_per_tok`` logits; w = softmax over those alone
    y = a + r ( sum_{i picked AND held here} w_i E_i(n) + Shared(n) )
        E_i(n) = W_out_i (silu(g) * u'), [g | u'] = n W_in_i; Shared the same form

with ``h_0 = embed[ids] * embedding_multiplier``, and ``logits = rms(h_L)
embed^T / logits_scaling`` (the head is the embedding, tied).

**The share.**  ``num_local_experts`` in the configuration is the number of
experts whose weights are HERE (36 of the published 72, where the published key
is the router's width): this chip is chip 0 of two, holds experts 0..35, routes
over all 72, adds its own experts' part and the shared MLP (which every chip
computes whole for its own tokens) and leaves out what the other chip's experts
would add.  That partial sum goes on to the next layer.  ``vocab_size`` is this
chip's half of the vocabulary (rows of the tied embedding).  The mixers are
whole on every chip.  ``layer_parts`` returns the routed part of any chip's
share apart from the shared MLP's, so a test can add the two up to the uncut
layer.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision, one
sequence at a time: the recurrence TOKEN BY TOKEN (a ``lax.scan`` over the
positions; no chunks: it shares no algebra with the program's chunked scan),
the filter over the whole sequence, attention a full masked softmax in blocks
of queries, every held expert computed for every token and combined through an
``[S, E]`` matrix of weights.  No cache, no state, no kernel, no sorting.

Departures from ``modeling_granitemoehybrid.py``, each also under the
configuration file's ``assumed``: (1) an expert's ``W_in`` is drawn as two
matrices ``w_gate`` and ``w_up`` (HF stores ``[g | u']`` as one
``input_linear``: a concatenation, which the test applies); (2) ``A_log`` and
``dt_bias`` are drawn so that a head's decay a token lies between about 0.9 and
0.999 (``init_params``); (3) ``intermediate_size`` is read as one expert's
width (the catalog row's note; HF reads it so too).  None else in the mathematics.

Nothing here comes from ``deepspeed_tpu``: sizes come from the configuration
file's published keys, weights from the seed.  ``init_params`` lays the weights
out as the pytree the program's ``models/granite_moe_hybrid.py`` takes, because
that layout is the program's input interface; the same arrays go to both: a run
of layers that repeats a pattern is a tuple of one stack ``[repeats, ...]`` a
position of the pattern (``segments``); the experts of all layers are one stack
``[layers, held, ...]``.
"""

import functools
import math

import jax
import jax.numpy as jnp

EP_CHIPS = 2    # chips that share each layer in the deployment this file is one chip of
Q_BLOCK = 512   # queries per attention block: 32 heads x 512 x 3,072 keys x 4 B = 0.2 GB
DECAY_RATES = (7e-4, 7e-2)  # exp(A_log) of the first and the last head, log-spaced between


def router_width(sizes) -> int:
    """Experts the router scores: the held count times the chips of the deployment."""
    return EP_CHIPS * sizes["num_local_experts"]


def layer_kinds(sizes):
    """``["mamba" | "attention"]`` a layer: the first ``num_hidden_layers`` of
    the published ``layer_types``."""
    return list(sizes["layer_types"][:sizes["num_hidden_layers"]])


def segments(sizes):
    """``[(start, period, repeats)]``: from each start the longest run of
    layers that repeats a pattern of ``period`` kinds at least twice, else one
    layer alone.  One published period: ``[(0, 1, 5), (5, 1, 1), (6, 1, 4)]``."""
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
    """(H, P, Ns, inner columns I, the filter's columns I + 2 Ns)."""
    h, p, ns = sizes["mamba_n_heads"], sizes["mamba_d_head"], sizes["mamba_d_state"]
    assert sizes["mamba_n_groups"] == 1 and h * p == sizes["mamba_expand"] * sizes["hidden_size"]
    return h, p, ns, h * p, h * p + 2 * ns


def init_params(sizes, key, dtype=jnp.float32):
    """Random weights from ``key`` (``jax.random.PRNGKey(seed)``):
    normal(0, 1/sqrt(fan_in)) projections, experts and router (logits of unit
    scale, so that routing is not uniform), filter taps normal(0, 1/sqrt(4))
    with a normal(0, 0.1) bias, gains and ``D`` at one, and an embedding that is
    the head too at normal(0, 0.02 / ``embedding_multiplier``): the stream
    begins at 0.02, under the layers' 0.22 a branch.  (At 0.02 the multiplied
    embedding of the LAST INPUT TOKEN is a fifth of the final stream and meets
    itself in the tied head: that token's logit stood 15 deviations over every
    other, every generated token repeated its predecessor and the comparison of
    picks could tell nothing: my chip runs, PR 52.)

    **The decay.**  HF's initialisation (``A = 1, 2, ..., H``, ``dt_bias = 1``)
    gives ``exp(-A x 1.3)``, under 1e-3 for most of 128 heads: a state that
    forgets everything at every token, so a fault in the carried state would not
    reach the logits.  A trained model's heads remember over tens to thousands
    of tokens.  Here ``exp(A_log)`` is log-spaced over a layer's heads from 7e-4
    to 7e-2 and ``dt_bias`` is 1 (``softplus(dt + 1)`` is about 1.4 over
    unit-scale ``dt``): a head's typical decay a token runs from 0.999 to 0.9,
    and every token's differs.

    A routed expert's ``W_out`` (``w_down``) is drawn at its scale over
    ``num_experts_per_tok``: routing is discrete, a bfloat16 engine and this
    float32 reference break a near-tie between a token's tenth and eleventh
    expert differently, and the routed part's share of the residual stream is
    how far one such tie moves a row's logits (PERF.md section 6, PRs 31 and
    33).  Call it under ``jax.jit`` with the key as an argument."""
    d, e = sizes["hidden_size"], router_width(sizes)
    h, kv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    dh = d // h
    hm, _, _, inner, conv = ssm_widths(sizes)
    taps = sizes["mamba_d_conv"]
    kinds = layer_kinds(sizes)
    k_emb, k_layers, k_experts = jax.random.split(key, 3)

    def linear(key, *shape):
        """[..., fan_in, fan_out]"""
        return jax.random.normal(key, shape, dtype) * float(shape[-2]) ** -0.5

    def ffn(key, width, *lead):
        ks = jax.random.split(key, 3)
        return {"w_gate": linear(ks[0], *lead, d, width), "w_up": linear(ks[1], *lead, d, width),
                "w_down": linear(ks[2], *lead, width, d)}

    def position(key, depth, kind):
        ks = jax.random.split(key, 8)
        lp = {"op_norm": jnp.ones((depth, d), dtype), "ffn_norm": jnp.ones((depth, d), dtype)}
        if kind == "mamba":
            rates = jnp.exp(jnp.linspace(math.log(DECAY_RATES[0]), math.log(DECAY_RATES[1]), hm))
            lp["mixer"] = {"w_in": linear(ks[0], depth, d, inner + conv + hm),
                           "filter": jax.random.normal(ks[1], (depth, taps, conv), dtype)
                           * float(taps) ** -0.5,
                           "conv_bias": jax.random.normal(ks[2], (depth, conv), dtype) * 0.1,
                           "A_log": jnp.broadcast_to(jnp.log(rates), (depth, hm)).astype(dtype),
                           "dt_bias": jnp.ones((depth, hm), dtype), "D": jnp.ones((depth, hm), dtype),
                           "norm": jnp.ones((depth, inner), dtype),
                           "w_out": linear(ks[3], depth, inner, d)}
        else:
            lp["attn"] = {"wq": linear(ks[0], depth, d, h * dh), "wk": linear(ks[1], depth, d, kv * dh),
                          "wv": linear(ks[2], depth, d, kv * dh), "wo": linear(ks[3], depth, h * dh, d)}
        lp["moe"] = {"gate": {"wg": linear(ks[4], depth, d, e)},
                     "shared": ffn(ks[5], sizes["shared_intermediate_size"], depth)}
        return lp

    runs = []
    for start, period, repeats in segments(sizes):
        keys = jax.random.split(jax.random.fold_in(k_layers, start), period)
        runs.append(tuple(position(keys[j], repeats, kinds[start + j]) for j in range(period)))
    experts = ffn(k_experts, sizes["intermediate_size"], len(kinds), sizes["num_local_experts"])
    experts["w_down"] = experts["w_down"] / sizes["num_experts_per_tok"]
    return {"embed": jax.random.normal(k_emb, (sizes["vocab_size"], d), dtype)
            * (0.02 / sizes["embedding_multiplier"]),
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
    ``fmt`` (fp8 below bfloat16).  Neutral gains (ones) stay."""
    return jax.tree_util.tree_map(lambda w: round_to(w, fmt), params)


def f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


def rms_norm(x, gain, eps):
    """GraniteMoeHybridRMSNorm: a plain gain."""
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain


def gated_norm(y, z, gain, eps):
    """GraniteMoeHybridRMSNormGated: the gate first, then ONE norm over all the columns."""
    return rms_norm(y * jax.nn.silu(z), gain, eps)


def swiglu(x, w):
    w = f32(w)
    return (jax.nn.silu(x @ w["w_gate"]) * (x @ w["w_up"])) @ w["w_down"]


# ------------------------------------------------------------------- Mamba-2
def selective_scan(x, dt, a, b, c, d, state=None):
    """The recurrence token by token.  x ``[S, H, P]``, dt ``[S, H]`` (after its
    softplus), a, d ``[H]``, b, c ``[S, Ns]``; ``state`` ``[H, P, Ns]`` (zeros
    where None).  Returns (y ``[S, H, P]``, the state after the last token)."""
    if state is None:
        state = jnp.zeros((x.shape[1], x.shape[2], b.shape[1]), jnp.float32)

    def token(s, inp):
        x_t, dt_t, b_t, c_t = inp
        s = s * jnp.exp(dt_t * a)[:, None, None] + (dt_t[:, None] * x_t)[:, :, None] * b_t
        return s, jnp.einsum("hpn,n->hp", s, c_t) + d[:, None] * x_t

    state, y = jax.lax.scan(token, state, (x, dt, b, c))
    return y, state


def mamba2(sizes, u, w):
    """The Mamba-2 operator over one whole sequence, u ``[S, D]``."""
    hm, p, ns, inner, conv = ssm_widths(sizes)
    w = f32(w)
    projected = u @ w["w_in"]
    z, xbc, dt = projected[:, :inner], projected[:, inner:inner + conv], projected[:, inner + conv:]
    taps = w["filter"].shape[0]
    padded = jnp.pad(xbc, ((taps - 1, 0), (0, 0)))  # zero before the first token
    xbc = jax.nn.silu(sum(w["filter"][j] * padded[j:j + xbc.shape[0]] for j in range(taps))
                      + w["conv_bias"])
    x, b, c = xbc[:, :inner].reshape(-1, hm, p), xbc[:, inner:inner + ns], xbc[:, inner + ns:]
    dt = jax.nn.softplus(dt + w["dt_bias"])  # HF clamps to (0, inf): nothing
    y, _ = selective_scan(x, dt, -jnp.exp(w["A_log"]), b, c, w["D"])
    return gated_norm(y.reshape(-1, inner), z, w["norm"], sizes["rms_norm_eps"]) @ w["w_out"]


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
    """The attention operator over one whole sequence, u ``[S, D]``: no
    positions (``position_embedding_type: "nope"``)."""
    h, kv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    dh = sizes["hidden_size"] // h
    w = f32(w)
    out = causal_attention((u @ w["wq"]).reshape(-1, h, dh), (u @ w["wk"]).reshape(-1, kv, dh),
                           (u @ w["wv"]).reshape(-1, kv, dh), sizes["attention_multiplier"])
    return out.reshape(-1, h * dh) @ w["wo"]


# ------------------------------------------------------------ expert layer
def router(sizes, n, wg):
    """n [S, D] -> combine [S, E]: at each token's top-k LOGITS the softmax over
    those k alone, zero elsewhere (GraniteMoeHybridTopKGating)."""
    logits = n @ wg
    top, top_idx = jax.lax.top_k(logits, sizes["num_experts_per_tok"])
    gates = jax.nn.softmax(top, axis=-1)
    return jnp.zeros_like(logits).at[jnp.arange(logits.shape[0])[:, None], top_idx].set(gates)


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
    the sum over the held experts a token picked, and the shared MLP's output,
    which is the same on every chip."""
    experts = moe["experts"]
    if layer is None:
        experts, layer = jax.tree_util.tree_map(lambda m: m[None], experts), 0
    combine = router(sizes, n, moe["gate"]["wg"].astype(jnp.float32))
    held = experts["w_gate"].shape[1]
    routed = experts_ffn(n, combine[:, chip * held:(chip + 1) * held], experts, layer)
    return routed, swiglu(n, moe["shared"])


# ---------------------------------------------------------------- the model
def block(sizes, x, w, experts, layer):
    """One layer: the mixer its parameters name, then its expert FFN, each
    joined to the stream times ``residual_multiplier``."""
    eps, r = sizes["rms_norm_eps"], sizes["residual_multiplier"]
    u = rms_norm(x, w["op_norm"].astype(jnp.float32), eps)
    x = x + r * (mamba2(sizes, u, w["mixer"]) if "mixer" in w else attention(sizes, u, w["attn"]))
    n = rms_norm(x, w["ffn_norm"].astype(jnp.float32), eps)
    return x + r * sum(layer_parts(sizes, {**w["moe"], "experts": experts}, n, layer=layer))


def hidden_states(sizes, params, ids):
    """ids [S] -> the final normed hidden states [S, D], float32.  The layers
    are taken from their stacks in the order they are numbered."""
    x = params["embed"][ids].astype(jnp.float32) * sizes["embedding_multiplier"]
    layer = 0
    for (start, period, repeats), run in zip(segments(sizes), params["segments"]):
        for i in range(repeats):
            for stack in run:
                w = jax.tree_util.tree_map(lambda a, i=i: a[i], stack)
                x = block(sizes, x, w, params["experts"], layer)
                layer += 1
    return rms_norm(x, params["final_norm"].astype(jnp.float32), sizes["rms_norm_eps"])


@functools.partial(jax.jit, static_argnums=(0,))
def _logits_rows(sizes_items, params, ids, rows):
    with jax.default_matmul_precision("highest"):
        sizes = _thawed(sizes_items)
        x = hidden_states(sizes, params, ids)
        return x[rows] @ params["embed"].astype(jnp.float32).T / sizes["logits_scaling"]


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
