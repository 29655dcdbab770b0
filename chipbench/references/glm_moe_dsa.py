"""Plain reference of GLM-5's language model (``model_type: glm_moe_dsa``; the
``config.json`` of ``zai-org/GLM-5``, whose attention is DeepSeek-V3.2-Exp's
``inference/model.py`` ``MLA`` and ``Indexer``), as ONE CHIP'S SHARE of a
deployment in which ``EP_CHIPS`` = 16 chips share each layer: a pre-norm decoder
of RMSNorm (eps 1e-5), latent attention that attends only the cached tokens a
learned indexer picks, one leading dense SwiGLU layer and then expert layers.
One layer, input ``x_t`` at position ``t``, ``h_t = RMSNorm(x_t)``:

    c^q_t = RMSNorm(h_t W_qa)                                        (q_lora_rank 2048)
    [q^nope_{t,i} | q^pe_{t,i}] = c^q_t W_qb,i                       (192 | 64), heads i = 1..64
    [c'_t | k'_t] = h_t W_kva                                        (512 | 64)
    c^kv_t = RMSNorm(c'_t) ;  k^pe_t = rotate(k'_t) ;  q^pe rotated alike
    [k^nope_{s,i} | v_{s,i}] = c^kv_s W_kvb,i                        (192 | 256)
    score_{t,s,i} = (q^nope_{t,i} . k^nope_{s,i} + q^pe_{t,i} . k^pe_s) / sqrt(256)

    the indexer:
    q^I_{t,j} = c^q_t W^I_q,j                                        (128), j = 1..32
    k^I_s = LayerNorm(h_s W^I_k)                                     (128; gain and bias, eps 1e-6)
    in both, the FIRST 64 values are rotated, the other 64 are not
    w_{t,j} = (h_t W^I_w)_j x 32^-1/2 x 128^-1/2
    I_{t,s} = sum_j w_{t,j} relu(q^I_{t,j} . k^I_s)                  for s <= t
    S_t = the min(t + 1, 2048) positions s <= t of largest I_{t,s},
          equal scores broken towards the lower position

    p_{t,s,i} = softmax over s in S_t of score_{t,s,i}
    x_t += concat_i( sum_{s in S_t} p_{t,s,i} v_{s,i} ) W_o
    n = RMSNorm(x_t)
    dense layer :  x_t += W_down( silu(W_gate n) * (W_up n) )        (width 12288)
    expert layer:  s = sigmoid(n W_g) over ALL ``EP_CHIPS x held`` = 256 experts, float32;
                   the picks are the top 8 of s + b (b the stored selection bias: it
                   selects, it never weighs; one group);
                   weights s_pick / (sum of the picked s + 1e-20) x 2.5;
                   x_t += sum_{i picked AND held here} w_i E_i(n) + Shared(n)   (widths 2048)

Rotary is plain (theta 1e6, no scaling) over interleaved pairs ``(2m, 2m + 1)``
(``rope_interleave`` and ``indexer_rope_interleave`` true).  A token at a
position under 2,048 attends all of its past: plain MLA.  Final RMSNorm, untied
head.

**The share.**  The configuration's ``n_routed_experts`` is the number of
experts whose weights are HERE (16 of the published 256): this is chip 0 of
sixteen, holds experts 0..15, routes over all 256, adds its own experts' part
and the shared expert (which every chip computes for its own tokens) and leaves
out what the other fifteen chips' experts would add.  ``vocab_size`` is this
chip's slice of the vocabulary.  Attention and the indexer are whole on every
chip.  ``layer_parts`` returns the routed part of any chip's share apart from
the shared expert's, so a test can add the sixteen up to the uncut layer.

**Departures from the published model** (the configuration file lists them under
``assumed``): the multi-token-prediction layer is not built; the published
stack's Hadamard rotation of ``q^I`` and ``k^I`` is left out (orthogonal: the
scores are the same) and so is their fp8 rounding (this configuration is served
in bfloat16); the tie rule of ``S_t`` is ours to state (``torch.topk`` states
none).

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision, one
sequence at a time: NOT absorbed (``k^nope`` and ``v`` are expanded for every
head), no cache, no kernel, no sorting and no dispatch (every held expert is
computed for every token and combined through a ``[S, E]`` matrix of weights);
``S_t`` is ``lax.top_k`` (exact; of equal values the lower index first) over the
whole row of scores, kept as a mask ``[S, S]``.  So that a 16,416-token prompt
fits beside 9.45 GB of weights, every product runs over blocks of rows, the
attention over blocks of heads and, inside, blocks of queries, and the index
scores head by head; that changes no number's meaning.

Nothing here comes from ``deepspeed_tpu``: sizes come from the configuration
file's published keys, weights from the seed.  ``init_params`` lays the weights
out as the pytree the program's ``models/glm_moe_dsa.py`` takes because that
layout is the program's input interface; the same arrays go to both.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

EP_CHIPS = 16    # chips that share each layer in the deployment this file is one chip of
Q_BLOCK = 512    # queries per attention block, and per block of index scores
HEAD_BLOCK = 8   # heads per attention block: 8 x 512 x 17,408 keys x 4 B = 0.29 GB of scores
ROW_BLOCK = 2048  # rows per block of a projection or an FFN
INDEX_NORM_EPS = 1e-6
ROUTE_NORM_EPS = 1e-20
RELEVANCE_SPREAD = 2.0  # standard deviation of the attention score all heads share (``init_params``)
INDEX_NOISE = 0.3       # of an index query's own, beside the shared projection
BIAS_CHANNEL = 0.08     # every token's embedding in channel 0: four times a drawn value


def router_width(sizes) -> int:
    """Experts the router scores: the held count times the chips of the deployment."""
    return EP_CHIPS * sizes["n_routed_experts"]


def init_params(sizes, key, dtype=jnp.float32):
    """Random weights from ``key``: normal(0, 1/sqrt(fan_in)) projections,
    experts and router (router logits of unit scale: sigmoid scores spread over
    0.27-0.73), a normal(0, 0.02) embedding, unit norm gains, with the
    departures below, each of which gives a random draw something a trained
    checkpoint has by itself.

    **The indexer agrees with the attention it selects for.**  A trained
    indexer is fitted to the attention's own distribution, so the keys at its
    threshold are keys no head weighs much.  Drawn independently, the index
    scores of a random model have no such margin: a bfloat16 engine and this
    float32 reference then select sets that differ in a few positions of a
    hundred, those positions carry as much attention as any, an attention output
    moves by about the root of that share, and six pooled rows read 0.32 on
    sound runs (chip run, PR 45: no limit can part that from a fault).  So:

    - every head's rotary query is ``RELEVANCE_SPREAD x sqrt(qk / rope)`` times
      ONE shared projection ``U`` of the low-rank query plus the head's own
      unit-scale part: all heads share a *relevance* ``R(t, s) = rot(c_q U) .
      k^pe_s / sqrt(qk)`` of standard deviation ``RELEVANCE_SPREAD`` = 2 beside
      their own scores of about 1 (``W_qb``'s other columns at the plain scale);
    - the index queries' rotated values are ``U`` too (plus ``INDEX_NOISE`` of
      their own in every value) and the index key's rotated values are the
      attention's ``k'`` (the same columns of ``W_kva``; its other values drawn):
      every index head scores ``R`` and some noise;
    - the head weights read ONE input channel that is constant and positive
      (``W^I_w`` is zero but for row 0; ``embed[:, 0]`` = ``BIAS_CHANNEL`` for
      every token, and no layer writes channel 0: column 0 of ``W_o`` and of
      every ``W_down`` is zero), so ``w`` is positive whatever the token and
      ``I`` rises with ``R``.

    What that gives (the configuration file's ``assumed.weights`` has the
    readings): the selected 2,048 of 8-16k keys hold all but about a sixth of
    every head's softmax mass, so attending ALL keys (the selection switched
    off) moves an attention output by that sixth and attending another set by
    far more, while a key at the threshold weighs ``e^-4`` and less of a top
    one and a few of them swapped move nothing.

    Two more:

    - a routed expert's ``W_down`` over ``routed_scaling_factor x
      num_experts_per_tok``, so that a pick weighs about 0.016 of an expert's
      output and not 2.5 / 8 = 0.31: routing is discrete, a bfloat16 engine and
      this float32 reference break a near-tie between two experts differently,
      and the comparison pools six rows (DeepSeek-V2's lesson: its
      configuration file tells it);
    - the selection bias at ``0.64 / E`` times a normal (LFM2's lesson: a larger
      one makes the experts' loads, and with them the rate, follow the seed).

    Call it under ``jax.jit``."""
    d, h = sizes["hidden_size"], sizes["num_attention_heads"]
    nope, rope = sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"]
    kv_out = nope + sizes["v_head_dim"]
    rank, q_rank = sizes["kv_lora_rank"], sizes["q_lora_rank"]
    j, di = sizes["index_n_heads"], sizes["index_head_dim"]
    n_dense = sizes["first_k_dense_replace"]
    n_moe = sizes["num_hidden_layers"] - n_dense
    held, fe = sizes["n_routed_experts"], sizes["moe_intermediate_size"]
    fs = fe * sizes["n_shared_experts"]
    experts = router_width(sizes)
    keys = iter(jax.random.split(key, 64))

    def linear(*shape, scale=1.0, fan_in=None):
        """[..., fan_in, fan_out] (or ``fan_in`` named, where heads stand between the two)"""
        return jax.random.normal(next(keys), shape, dtype) * (scale * float(fan_in or shape[-2]) ** -0.5)

    def unwritten(w):
        """No layer writes the constant channel: column 0 of an output projection is zero."""
        return w.at[..., 0].set(0)

    def attention(depth):
        shared = linear(depth, q_rank, 1, rope, fan_in=q_rank)  # U: the relevance every head and the indexer share
        wq_rope = shared * (RELEVANCE_SPREAD * ((nope + rope) / rope) ** 0.5) \
            + linear(depth, q_rank, h, rope, fan_in=q_rank)
        wq_b = jnp.concatenate([linear(depth, q_rank, h, nope, fan_in=q_rank), wq_rope], axis=-1)
        wkv_a = linear(depth, d, rank + rope)
        index_q = jnp.concatenate([jnp.broadcast_to(shared, (depth, q_rank, j, rope)),
                                   jnp.zeros((depth, q_rank, j, di - rope), dtype)], axis=-1) \
            + linear(depth, q_rank, j, di, scale=INDEX_NOISE, fan_in=q_rank)
        index_k = jnp.concatenate([wkv_a[..., rank:], linear(depth, d, di - rope)], axis=-1)
        return {"attn": {"wq_a": linear(depth, d, q_rank), "q_norm": jnp.ones((depth, q_rank), dtype),
                         "wq_b": wq_b.reshape(depth, q_rank, h * (nope + rope)), "wkv_a": wkv_a,
                         "kv_norm": jnp.ones((depth, rank), dtype),
                         "wkv_b": linear(depth, rank, h * kv_out),
                         "wo": unwritten(linear(depth, h * sizes["v_head_dim"], d))},
                "indexer": {"wq": index_q.reshape(depth, q_rank, j * di), "wk": index_k,
                            "k_norm": jnp.ones((depth, di), dtype),
                            "k_norm_bias": jnp.zeros((depth, di), dtype),
                            "weights": jnp.zeros((depth, d, j), dtype).at[:, 0, :].set(1.0)},
                "attn_norm": jnp.ones((depth, d), dtype), "mlp_norm": jnp.ones((depth, d), dtype)}

    def ffn(width, *lead, out_scale=1.0):
        return {"w_gate": linear(*lead, d, width), "w_up": linear(*lead, d, width),
                "w_down": unwritten(linear(*lead, width, d, scale=out_scale))}

    routed_scale = 1.0 / (sizes["routed_scaling_factor"] * sizes["num_experts_per_tok"])
    embed = jax.random.normal(next(keys), (sizes["vocab_size"], d), dtype) * 0.02
    return {
        "embed": embed.at[:, 0].set(BIAS_CHANNEL),
        "dense_layers": {**attention(n_dense), "mlp": ffn(sizes["intermediate_size"], n_dense)},
        "layers": {**attention(n_moe),
                   "moe": {"gate": {"wg": linear(n_moe, d, experts),
                                    "bias": jax.random.normal(next(keys), (n_moe, experts), dtype)
                                    * (0.64 / experts)},
                           "experts": ffn(fe, n_moe, held, out_scale=routed_scale),
                           "shared": ffn(fs, n_moe)}},
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


def by_rows(fn, x, block=ROW_BLOCK):
    """``fn(x)`` for a ``fn`` that treats rows apart, a block of rows at a time."""
    s = x.shape[0]
    if s <= block:
        return fn(x)
    pad = (-s) % block
    out = jax.lax.map(fn, jnp.pad(x, ((0, pad), ) + ((0, 0), ) * (x.ndim - 1)).reshape(
        (-1, block) + x.shape[1:]))
    return out.reshape((-1, ) + out.shape[2:])[:s]


def rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain.astype(jnp.float32)


def layer_norm(x, gain, bias, eps):
    x = x - jnp.mean(x, axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
            * gain.astype(jnp.float32) + bias.astype(jnp.float32))


def swiglu(x, w):
    w = f32(w)
    return by_rows(lambda r: (jax.nn.silu(r @ w["w_gate"]) * (r @ w["w_up"])) @ w["w_down"], x)


# ------------------------------------------------------------------- rotary
def rotary(x, positions, theta: float):
    """x [S, heads, d]: the pair (x[2m], x[2m + 1]) rotates by positions * theta^(-2m / d)."""
    d = x.shape[-1]
    inv_freq = jnp.asarray((theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)).astype(np.float32))
    angle = positions.astype(jnp.float32)[:, None, None] * inv_freq[None, None, :]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin], axis=-1).reshape(x.shape)


def rotary_first(x, positions, theta: float, rope: int):
    """The indexer's heads: the FIRST ``rope`` values rotate, the others do not."""
    return jnp.concatenate([rotary(x[..., :rope], positions, theta), x[..., rope:]], axis=-1)


def theta_of(sizes) -> float:
    return float(sizes["rope_parameters"]["rope_theta"])


# ------------------------------------------------------------------ indexer
def index_keys(sizes, ix, n1, positions):
    """``k^I_s`` ``[S, index_head_dim]`` of one layer: what a server caches beside the latent."""
    wk = ix["wk"].astype(jnp.float32)
    k_i = layer_norm(by_rows(lambda r: r @ wk, n1), ix["k_norm"], ix["k_norm_bias"], INDEX_NORM_EPS)
    return rotary_first(k_i[:, None, :], positions, theta_of(sizes), sizes["qk_rope_head_dim"])[:, 0]


def index_scores(sizes, ix, k_i, n1, c_q, positions):
    """``I[t, s]`` ``[q, S]`` of one layer for the ``q`` query tokens whose
    normed input, low-rank query and positions are given, over the index keys
    ``k_i`` ``[S, Di]``; float32; ``s > t`` is not yet masked."""
    j, di = sizes["index_n_heads"], sizes["index_head_dim"]
    q_i = rotary_first((c_q @ ix["wq"].astype(jnp.float32)).reshape(-1, j, di), positions,
                       theta_of(sizes), sizes["qk_rope_head_dim"])
    w = (n1 @ ix["weights"].astype(jnp.float32)) * (j ** -0.5 * di ** -0.5)

    def head(acc, inp):  # head by head: [q, S] at a time, never [q, J, S]
        q_j, w_j = inp
        return acc + w_j[:, None] * jax.nn.relu(q_j @ k_i.T), None

    acc, _ = jax.lax.scan(head, jnp.zeros((q_i.shape[0], k_i.shape[0]), jnp.float32),
                          (q_i.swapaxes(0, 1), w.T))
    return acc


def selected(scores, q_pos, k: int):
    """bool ``[q, S]``: ``S_t`` of each row: the ``min(t + 1, k)`` positions ``s
    <= t`` of largest score, equal scores towards the lower position
    (``lax.top_k``: exact, of equal values the lower index first; ``-0.0`` and
    ``0.0`` are one score)."""
    q, s = scores.shape
    seen = jnp.arange(s)[None, :] <= q_pos[:, None]
    scores = jnp.where(seen, jnp.where(scores == 0, 0.0, scores), -jnp.inf)
    _, idx = jax.lax.top_k(scores, min(k, s))
    picked = jnp.zeros((q, s), bool).at[jnp.arange(q)[:, None], idx].set(True)
    return picked & seen


def selection(sizes, ix, n1, c_q, positions, select: bool = True):
    """``S_t`` for every position as one mask ``[S, S]``, a block of queries at a time."""
    s = n1.shape[0]
    if not select:
        return jnp.arange(s)[None, :] <= positions[:, None]
    block = min(Q_BLOCK, s)
    pad = (-s) % block
    rows = jnp.minimum(jnp.arange(s + pad), s - 1).reshape(-1, block)
    k_i = index_keys(sizes, ix, n1, positions)
    masks = jax.lax.map(
        lambda r: selected(index_scores(sizes, ix, k_i, n1[r], c_q[r], positions[r]),
                           positions[r], sizes["index_topk"]), rows)
    return masks.reshape(-1, s)[:s]


# ---------------------------------------------------------------- attention
def attend(q, k, v, scale, mask, q_block=Q_BLOCK):
    """q/k [S, h, dk], v [S, h, dv], mask [S, S]: query t sees the keys of its mask row."""
    s, h, dk = q.shape
    q_block = min(q_block, s)
    pad = (-s) % q_block
    qb = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(-1, q_block, h, dk)
    mb = jnp.pad(mask, ((0, pad), (0, 0)), constant_values=True).reshape(-1, q_block, s)

    def block(args):
        qs, seen = args
        scores = jnp.einsum("qhd,shd->hqs", qs, k) * scale
        probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), axis=-1)
        return jnp.einsum("hqs,shd->qhd", probs, v)

    return jax.lax.map(block, (qb, mb)).reshape(-1, h, v.shape[-1])[:s]


def mla(sizes, w, n1, positions, select: bool = True):
    """Latent attention of one layer over the indexer's selection, EXPANDED:
    every head gets its own ``k_nope`` and ``v`` from the latent.  Heads in
    blocks, so that q, k, v and the scores of a long prompt stay small; a
    block's part of ``W_o`` is applied at once and the parts are summed."""
    a = w["attn"]
    h, rank = sizes["num_attention_heads"], sizes["kv_lora_rank"]
    nope, rope, dv = sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"], sizes["v_head_dim"]
    eps, theta = sizes["rms_norm_eps"], theta_of(sizes)
    scale = (nope + rope) ** -0.5

    wq_a, wkv_a = a["wq_a"].astype(jnp.float32), a["wkv_a"].astype(jnp.float32)
    c_q = rms_norm(by_rows(lambda r: r @ wq_a, n1), a["q_norm"], eps)
    kv = by_rows(lambda r: r @ wkv_a, n1)
    c_kv = rms_norm(kv[:, :rank], a["kv_norm"], eps)
    k_pe = rotary(kv[:, None, rank:], positions, theta)  # [S, 1, rope]: all heads'
    mask = selection(sizes, w["indexer"], n1, c_q, positions, select)

    hb = math.gcd(h, HEAD_BLOCK)
    blocks = (a["wq_b"].reshape(-1, h // hb, hb, nope + rope).swapaxes(0, 1),
              a["wkv_b"].reshape(rank, h // hb, hb, nope + dv).swapaxes(0, 1),
              a["wo"].reshape(h // hb, hb * dv, -1))

    def heads(out, blk):
        wq_b, wkv_b, wo = f32(blk)
        q = jnp.einsum("sr,rhd->shd", c_q, wq_b)
        k_v = jnp.einsum("sr,rhd->shd", c_kv, wkv_b)
        q = jnp.concatenate([q[..., :nope], rotary(q[..., nope:], positions, theta)], axis=-1)
        k = jnp.concatenate([k_v[..., :nope], jnp.broadcast_to(k_pe, (k_pe.shape[0], hb, rope))],
                            axis=-1)
        o = attend(q, k, k_v[..., nope:], scale, mask)
        return out + o.reshape(o.shape[0], hb * dv) @ wo, None

    out, _ = jax.lax.scan(heads, jnp.zeros_like(n1), blocks)
    return out


# ------------------------------------------------------------ expert layer
def router(sizes, n2, gate):
    """n2 [S, D] -> combine [S, E]: sigmoid scores over all E experts, the top
    k of score + bias picked, the picked SCORES (without the bias) divided by
    their sum + 1e-20 and times the scaling factor, zero elsewhere."""
    scores = jax.nn.sigmoid(n2 @ gate["wg"].astype(jnp.float32))
    _, top_idx = jax.lax.top_k(scores + gate["bias"].astype(jnp.float32), sizes["num_experts_per_tok"])
    top_s = jnp.take_along_axis(scores, top_idx, axis=-1)
    if sizes["norm_topk_prob"]:
        top_s = top_s / (jnp.sum(top_s, axis=-1, keepdims=True) + ROUTE_NORM_EPS)
    top_s = top_s * sizes["routed_scaling_factor"]
    return jnp.zeros_like(scores).at[jnp.arange(scores.shape[0])[:, None], top_idx].set(top_s)


def experts_ffn(n2, combine, w, layer):
    """Every expert of layer ``layer`` of the stack ``w`` (leaves [L, E, ...])
    over every token, one at a time, each output weighted by the token's
    ``combine`` column and summed."""

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
    combine = router(sizes, n2, moe["gate"])
    held = experts["w_gate"].shape[1]
    routed = experts_ffn(n2, combine[:, chip * held:(chip + 1) * held], experts, layer)
    return routed, swiglu(n2, moe["shared"])


def hidden_states(sizes, params, ids, select: bool = True):
    """ids [S] -> the final normed hidden states [S, D], float32."""
    eps = sizes["rms_norm_eps"]
    positions = jnp.arange(ids.shape[0])
    x = params["embed"].astype(jnp.float32)[ids]
    moe_layers = dict(params["layers"])
    moe = dict(moe_layers.pop("moe"))
    experts = moe.pop("experts")  # stays one stack: a layer takes its experts one by one

    def attention(x, w):
        x = x + mla(sizes, w, rms_norm(x, w["attn_norm"], eps), positions, select)
        return x, rms_norm(x, w["mlp_norm"], eps)

    def dense_layer(x, w):
        x, n2 = attention(x, w)
        return x + swiglu(n2, w["mlp"]), None

    def expert_layer(x, inp):
        w, gate_and_shared, l = inp
        x, n2 = attention(x, w)
        return x + sum(layer_parts(sizes, {**gate_and_shared, "experts": experts}, n2,
                                   layer=l)), None

    x, _ = jax.lax.scan(dense_layer, x, params["dense_layers"])
    x, _ = jax.lax.scan(expert_layer, x,
                        (moe_layers, moe, jnp.arange(experts["w_gate"].shape[0])))
    return rms_norm(x, params["final_norm"], eps)


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
    """The sizes as something hashable; a nested group (``rope_parameters``) too."""
    return tuple(sorted((k, _static(v) if isinstance(v, dict) else v) for k, v in sizes.items()
                        if isinstance(v, (int, float, str, dict)) or v is None))


def _thawed(items):
    return {k: _thawed(v) if isinstance(v, tuple) else v for k, v in items}
