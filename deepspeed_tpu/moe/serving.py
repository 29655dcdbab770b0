"""Serving-time mixture-of-experts FFN: sparse dispatch, no capacity, no drop.

The reference's ragged ``moe_gather`` / ``moe_scatter`` around CUTLASS
``moe_gemm`` (inference/v2/kernels/ragged_ops, cutlass_ops/moe_gemm): every
token reaches its k experts and each expert multiplies only the rows routed
to it.  Shapes are static and follow from the slot count and the leaves' shapes
alone (:func:`expert_rows`).  Where the leaves hold every routed expert, ``S``
token slots give ``S x k`` routed rows, sorted by expert and handed to one
grouped matmul per projection; a dead slot's picks are given the expert id
``E``, past every group, so they sort to the tail, belong to no group and cost
no read of any expert's weights.

A pick is one of three kinds (:func:`sparse_moe_ffn`).  *Held*: the expert's
weights are here; the pick is a row of its group.  *Held elsewhere*: the router
is wider than the experts the leaves hold (one chip's share of an
expert-parallel layer); the pick reads no weight and adds nothing here.
*Identity* (LongCat-Flash's zero-computation experts): the router's last
``identity_experts`` outputs are experts that return their input; such a pick
keeps its weight, adds ``w x`` beside the routed sum on the token's own chip and
is never dispatched.  Two kinds read no weight; only one of them adds nothing.

**A share compacts its held picks first** (ISSUE 51).  On a share only the held
picks become rows at all: one sort lists them by expert, and the row gather,
the three grouped matmuls, the activation, the mask and the combine run over a
static window of :func:`expert_rows` rows (what uniform
routing sends here, with headroom), not over every pick.  Still no capacity and
no drop: a pass that holds more picks than a window's rows runs the window
again (a ``while_loop`` whose body is traced once).  The rows' outputs reach
their tokens through :func:`combine_rows`, which builds nothing ``[slots,
top_k, D]``.

**An expert is gated or not** (:func:`expert_ffn` and the shared expert, told by what the leaves
hold).  With a ``w_gate`` leaf an expert is SwiGLU, ``W_down (silu(W_gate x) *
W_up x)``, three grouped matmuls; without one it is UNGATED, ``W_down relu(W_up
x)^2`` (Nemotron-H's ``mlp_hidden_act: relu2``), two grouped matmuls with the
squared rectifier between them: for the routed experts all held, for a share's
compacted window of held picks, and for the shared expert alike.  No argument
and no engine knob says which: a parameter tree cannot hold the wrong form.
An ungated expert's ``w_up`` is ``[F, D]``, as its ``w_down`` is (a checkpoint's
``up_proj.weight``), and is multiplied transposed: at Nemotron-H's width of 1,856
(14.5 lane tiles) the chip lays an array ``[.., D, 1856]`` out with ``D`` minor,
and a kernel that takes it row-major has the WHOLE expert stack copied for it, 3.8
GB a step program (compile, PR 62); ``[.., 1856, D]`` lies as the kernel reads it.

The grouped matmul is the Pallas ``gmm`` of ``jax.experimental.pallas.ops.tpu.
megablox`` on TPU and ``jax.lax.ragged_dot``, the same mathematics in XLA,
elsewhere.  On the v5e at OLMoE's ``[rows, 64 groups, 2048 x 1024]`` the three
matmuls of an expert FFN took 1.28 ms (gmm, tiles 128 x 2048 x 1024) against
2.73 ms (``ragged_dot``) over 2,048 rows and 1.11 against 1.69 ms over 256,
where reading the 64 experts' weights once is 0.98 ms (PERF.md, PR 27).
"""

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..compat import CompilerParams
from ..ops import _pallas

ROW_TILE = 128          # rows a gmm program step multiplies: the MXU's height
HEADROOM = 1.25         # a share's window of held picks over what uniform routing sends it
K_TILE, N_TILE = 2048, 1024  # an expert's whole 2048 x 1024 matrix in one step
K_TILE_T, N_TILE_T = 1024, 2048  # a transposed matrix's: its ``[N, K]`` block, N whole (:func:`_even_tile`)
COMBINE_WIDTH = 2048    # columns a step of the combine kernel holds: 1.5 MB of blocks
TALLIES = ("identity", "held", "experts_hit")  # what :func:`sparse_moe_ffn` can count beside its sum


def expert_rows(slots: int, top_k: int, held: int = 1, routed: int = 1) -> int:
    """Rows the expert FFN's program computes a trip for ``slots`` token slots.
    Static, so the serving counters ask it too.

    Every routed expert held (``held == routed``): every pick is a row, ``slots
    x top_k``, rounded up to whole row tiles (to 16, a bf16 sublane pair, under
    one tile).  A share (the leaves hold ``held`` experts of a router ``routed``
    wide, identity outputs among them): the picks on held experts alone become
    rows, and the window they are compacted into is what uniform routing sends
    here times :data:`HEADROOM` in whole row tiles (a narrower one saves the
    chip nothing: the grouped matmul takes 128 rows a step either way), never
    more than every pick: of LongCat-Flash's 768 picks a decode step as one
    chip of 32 (16 of 768), 128 rows; of 12,288 a chunk pass, 384; of
    Qwen3-Next's 20,480 (128 of 512), 6,400.  A pass that holds more runs
    another trip (:func:`sparse_moe_ffn`)."""
    rows = slots * top_k
    tile = ROW_TILE if rows > ROW_TILE else 16
    every = -(-rows // tile) * tile
    if held >= routed:
        return every
    return min(every, -(-math.ceil(rows * held * HEADROOM / routed) // ROW_TILE) * ROW_TILE)


def window_trips(held_picks, rows: int):
    """Trips a share's expert FFN makes over ``held_picks`` picks, ``rows`` a window."""
    return -(-held_picks // rows)


def _even_tile(dim: int, most: int) -> int:
    """The whole dimension where it is at most ``most``, else its largest divisor in whole
    lane tiles under ``most`` (2,688 under 1,024: 896, three even steps), else ``most``."""
    if dim <= most:
        return dim
    return next((t for t in range(most - most % 128, 0, -128) if dim % t == 0), most)


def grouped_matmul(lhs, rhs, group_sizes, transposed: bool = False):
    """``lhs[rows of group g] @ rhs[g]``: lhs [M, K] sorted by group, rhs
    [G, K, N], group_sizes [G] -> [M, N].  Rows past the last group come back
    as whatever the kernel left there: the caller must not use them.
    ``transposed``: rhs is ``[G, N, K]`` and the product ``lhs @ rhs[g]^T`` (an
    ungated expert's ``w_up``); its tiles divide the matrix evenly."""
    if not _pallas.use_pallas():
        return jax.lax.ragged_dot(lhs, jnp.swapaxes(rhs, 1, 2) if transposed else rhs, group_sizes)
    from jax.experimental.pallas.ops.tpu.megablox import gmm
    (m, k), n = lhs.shape, rhs.shape[1 if transposed else -1]
    tiling = ((min(m, ROW_TILE), _even_tile(k, K_TILE_T), _even_tile(n, N_TILE_T)) if transposed
              else (min(m, ROW_TILE), min(k, K_TILE), min(n, N_TILE)))
    # positionally: the custom-vjp wrapper takes its static arguments by place
    return gmm(lhs, rhs, group_sizes, lhs.dtype, tiling, None, None, transposed, _pallas.INTERPRET)


def combine_rows(ys, token, weight, slots: int):
    """``out[s] = sum of weight[j] * ys[j] over the rows j with token[j] == s``,
    in float32: ys ``[R, D]`` in token order (``token`` ``[R]`` rises; a dead
    row's is ``slots`` and its weight 0), -> ``[slots, D]``.

    Rising tokens make a tile of 128 tokens' rows one run, so the sum is a
    grouped product over token tiles: the Pallas kernel ``moe_combine`` walks the
    (token tile, row tile) pairs whose rows meet, at most ``token tiles + row
    tiles - 1`` of them (a scalar-prefetched list), and adds ``W [128 tokens,
    128 rows] x ys [128 rows, D]`` a pair, ``W`` holding row j's weight at its
    token's line.  Nothing ``[slots, top_k, D]`` is built.  The weights stay
    exact: against bfloat16 rows ``W`` is split into three bfloat16 terms whose
    products with a row are exact in float32 (the sum's order alone differs from
    a multiply-and-add a pick); float32 rows are multiplied at the highest
    precision.  Off the TPU it is a segment sum."""
    if not _pallas.use_pallas():
        return jax.ops.segment_sum(weight[:, None] * ys.astype(jnp.float32), token,
                                   num_segments=slots, indices_are_sorted=True)
    return _combine_tiles(ys, token, weight, slots=slots, interpret=_pallas.INTERPRET)


def _combine_kernel(tile_ref, rows_ref, first_ref, adds_ref, token_ref, weight_ref, ys_ref, out_ref):
    del rows_ref  # the index maps' own
    p = pl.program_id(1)

    @pl.when(first_ref[p] == 1)
    def _begin():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(adds_ref[p] == 1)
    def _add():
        tokens, rows = out_ref.shape[0], ys_ref.shape[0]
        line = tile_ref[p] * tokens + jax.lax.broadcasted_iota(jnp.int32, (tokens, rows), 0)
        w = jnp.where(token_ref[0] == line, weight_ref[0], 0.0)  # [tokens, rows], a weight a column
        ys = ys_ref[...]
        if ys.dtype == jnp.float32:
            out_ref[...] += jnp.dot(w, ys, precision=jax.lax.Precision.HIGHEST,
                                    preferred_element_type=jnp.float32)
            return
        added = 0.0
        for _ in range(3):  # float32's 24 bits as three bfloat16 terms
            term = w.astype(jnp.bfloat16)
            added += jnp.dot(term, ys.astype(jnp.bfloat16), preferred_element_type=jnp.float32)
            w = w - term.astype(jnp.float32)
        out_ref[...] += added


# jitted for its trace cache alone (inlined where it is called)
@functools.partial(jax.jit, static_argnames=("slots", "interpret"), inline=True)
def _combine_tiles(ys, token, weight, *, slots, interpret):
    rows, width = ys.shape
    tt, rt = min(slots, ROW_TILE), min(rows, ROW_TILE)
    n_t, n_r = -(-slots // tt), rows // rt  # rows are whole tiles (expert_rows)
    wide = width if width % 128 else next(  # whole lanes, a divisor of the width
        w for w in range(min(width, COMBINE_WIDTH), 0, -128) if width % w == 0)
    # a token tile's run of rows [start, end) and the row tiles it meets (one, not added,
    # where the run is empty: its lines are begun and stay zero)
    bounds = jnp.sum(token[None, :] < (jnp.arange(n_t + 1, dtype=jnp.int32) * tt)[:, None], axis=1,
                     dtype=jnp.int32)
    start, end = bounds[:-1], bounds[1:]
    lo = jnp.minimum(start // rt, n_r - 1)
    met = jnp.where(end > start, (end - 1) // rt - lo + 1, 1)
    ends = jnp.cumsum(met)
    # pairs past the last one are the last one again: nothing fetched, begun or added for them
    p = jnp.arange(n_t + n_r - 1, dtype=jnp.int32)
    q = jnp.minimum(p, ends[-1] - 1)
    tile = jnp.sum(q[:, None] >= ends[None, :], axis=1, dtype=jnp.int32)
    before = (ends - met)[tile]
    first = ((q == before) & (p == q)).astype(jnp.int32)
    adds = ((end > start)[tile] & (p == q)).astype(jnp.int32)
    out = pl.pallas_call(
        _combine_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(width // wide, n_t + n_r - 1),
            in_specs=[pl.BlockSpec((1, 1, rt), lambda d, p, tile, row, first, adds: (row[p], 0, 0)),
                      pl.BlockSpec((1, 1, rt), lambda d, p, tile, row, first, adds: (row[p], 0, 0)),
                      pl.BlockSpec((rt, wide), lambda d, p, tile, row, first, adds: (row[p], d))],
            out_specs=pl.BlockSpec((tt, wide), lambda d, p, tile, row, first, adds: (tile[p], d))),
        out_shape=jax.ShapeDtypeStruct((n_t * tt, width), jnp.float32),
        compiler_params=CompilerParams(dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret, name="moe_combine",
    )(tile, lo[tile] + q - before, first, adds, token.reshape(n_r, 1, rt),
      weight.reshape(n_r, 1, rt), ys)
    return out[:slots]


def expert_ffn(xs, stacked, group_sizes):
    """The experts' FFN over rows ``xs`` sorted by group: ``stacked`` holds ``w_up``,
    ``w_down`` and, for gated experts, ``w_gate``, each ``[groups, ...]``.  Gated
    (``w_gate``, ``w_up`` ``[.., D, F]``): ``down(silu(gate) * up)``, three grouped
    matmuls.  Ungated (no ``w_gate`` leaf; ``w_up`` ``[.., F, D]``, multiplied
    transposed): ``down(relu(up)^2)``, two."""
    if "w_gate" in stacked:
        gate = grouped_matmul(xs, stacked["w_gate"], group_sizes)
        up = grouped_matmul(xs, stacked["w_up"], group_sizes)
        return grouped_matmul(jax.nn.silu(gate) * up, stacked["w_down"], group_sizes)
    up = grouped_matmul(xs, stacked["w_up"], group_sizes, transposed=True)
    return grouped_matmul(jnp.square(jax.nn.relu(up)), stacked["w_down"], group_sizes)


def _held_picks_ffn(stacked, x, weights, experts, held, layer, num_experts: int, rows: int):
    """The held picks' part of a share's expert FFN, ``rows`` of them a trip.

    ``experts`` / ``held`` ``[S, k]``: each pick's expert and whether it is a
    live slot's pick on an expert held here; the leaves ``stacked`` are ``[L x
    E, ...]``, E = ``num_experts``, and ``layer`` says which E groups to use.
    ONE stable sort of the picks by expert, every other pick behind the last
    expert, lays the held ones first with their tokens and weights beside them
    (a sort of 20,480 keys takes the chip 17-30 us where a scatter of them
    takes 95 and each gather of 6,400 scalars 43: PERF.md, PR 51); trip ``w``
    takes places ``w x rows ..`` of that list: rows already in their experts'
    order, which it multiplies, sorts by token and adds to their tokens
    (:func:`combine_rows`).  Routing is data: a pass may hold any number, so the
    trips are a ``while_loop`` (its body traced once), ``ceil(held / rows)`` of
    them: none where nothing is held, one in nearly every call, more where a
    prompt or a popular expert sends more picks here; a later trip's rows name
    only the experts the earlier ones did not finish, so no expert's weights
    are read twice but the one a window's edge cuts.  -> out [S, D] float32."""
    (slots, top_k), groups = experts.shape, stacked["w_up"].shape[0]
    picks = slots * top_k
    held = held.reshape(picks)
    listed = jax.lax.sort((jnp.where(held, experts.reshape(picks), num_experts),
                           jnp.arange(picks, dtype=jnp.int32) // top_k, weights.reshape(picks)),
                          num_keys=1)
    # whole windows: the last one's tail is no expert's
    listed = [jnp.pad(a, (0, -picks % rows), constant_values=fill)
              for a, fill in zip(listed, (num_experts, 0, 0.0))]
    trips = window_trips(jnp.sum(held, dtype=jnp.int32), rows)
    row = jnp.arange(rows, dtype=jnp.int32)

    def trip(carry):
        w, out = carry
        expert, token, weight = (jax.lax.dynamic_slice(a, (w * rows,), (rows,)) for a in listed)
        alive = expert < num_experts  # the rows past the held picks are no expert's
        sizes = jnp.sum(expert[:, None] == jnp.arange(num_experts)[None, :], axis=0,
                        dtype=jnp.int32)
        group_sizes = jax.lax.dynamic_update_slice(jnp.zeros((groups,), jnp.int32), sizes,
                                                   (layer * num_experts,))
        ys = expert_ffn(x[token], stacked, group_sizes)
        # rows past the last group are whatever the kernel left: zeroed; then all in token
        # order (the dead ones last), each with its weight, for the combine
        token, order, weight = jax.lax.sort(
            (jnp.where(alive, token, slots), row, jnp.where(alive, weight, 0.0)), num_keys=1)
        ys = jnp.where(alive[:, None], ys, 0)[order]
        return w + 1, out + combine_rows(ys, token, weight, slots)

    _, out = jax.lax.while_loop(lambda carry: carry[0] < trips, trip,
                                (jnp.zeros((), jnp.int32),
                                 jnp.zeros((slots, x.shape[-1]), jnp.float32)))
    return out


def route(wg, x, top_k: int, renormalise: bool, n_group: int = 1, topk_group: int = 1,
          scaling: float = 1.0, scoring: str = "softmax", bias=None, norm_eps: float = 0.0):
    """Router of a top-k MoE layer, in float32: the logits accumulate in
    float32, the softmax runs over ALL experts, ``lax.top_k`` picks, and the
    picked probabilities are divided by their sum only where the checkpoint
    says so (Mixtral: yes; OLMoE ``norm_topk_prob: false``: no).

    ``scoring="sigmoid"`` scores each expert by itself, ``sigmoid(logit)``
    (LFM2, DeepSeek-V3's kind).  ``bias`` ``[E]`` is a selection bias: the
    picks are the top-k of ``score + bias``, the weights the picked experts'
    scores WITHOUT it (it balances loads, it never weighs).  ``norm_eps`` is
    added to the picked scores' sum where they are renormalised.  Softmax, no
    bias and no epsilon (every family before) trace as they did.

    ``n_group`` > 1 is the group-limited greedy choice (DeepSeek-V2): the
    experts lie in ``n_group`` equal runs (one a device of the deployment), a
    group scores its best expert's probability, and the top-k is taken among
    the experts of the ``topk_group`` best groups alone.  ``scaling``
    multiplies the picked weights (``routed_scaling_factor``: 16 there, where
    nothing is renormalised).  One group and factor 1 (every other family)
    trace to the plain softmax top-k.

    ``n_group`` > 1 WITH a ``bias`` is DeepSeek-V3's choice (``noaux_tc``;
    Ling-3.0's ``bailing_hybrid``): groups and picks are chosen by the BIASED
    score, a group scores the sum of its two best, the top-k is taken among the
    experts of the ``topk_group`` best groups (an expert of another group can
    not be picked whatever the bias: it is masked with -inf, not with 0), and
    the weights are the picked experts' scores without the bias.
    x [S, D] -> (weights [S, k] float32, experts [S, k] int32)."""
    with jax.named_scope("moe_route"):
        logits = jnp.dot(x, wg.astype(x.dtype), preferred_element_type=jnp.float32)
        if scoring not in ("softmax", "sigmoid"):
            raise ValueError(f"route: scoring {scoring!r} is not implemented (softmax, sigmoid)")
        probs = jax.nn.softmax(logits, axis=-1) if scoring == "softmax" else jax.nn.sigmoid(logits)
        if n_group > 1 and bias is not None:  # DeepSeek-V3's: groups and picks by the biased score
            by_group = (probs + bias.astype(jnp.float32)).reshape(probs.shape[0], n_group, -1)
            _, best = jax.lax.top_k(jnp.sum(jax.lax.top_k(by_group, 2)[0], axis=-1), topk_group)
            kept = jnp.any(best[:, :, None] == jnp.arange(n_group)[None, None, :], axis=1)
            _, top_idx = jax.lax.top_k(
                jnp.where(kept[:, :, None], by_group, -jnp.inf).reshape(probs.shape), top_k)
            top_p = jnp.take_along_axis(probs, top_idx, axis=-1)
        else:
            if n_group > 1:
                by_group = probs.reshape(probs.shape[0], n_group, -1)
                _, best = jax.lax.top_k(jnp.max(by_group, axis=-1), topk_group)
                kept = jnp.any(best[:, :, None] == jnp.arange(n_group)[None, None, :], axis=1)
                probs = jnp.where(kept[:, :, None], by_group, 0.0).reshape(probs.shape)
            if bias is None:
                top_p, top_idx = jax.lax.top_k(probs, top_k)
            else:
                _, top_idx = jax.lax.top_k(probs + bias.astype(jnp.float32), top_k)
                top_p = jnp.take_along_axis(probs, top_idx, axis=-1)
        if renormalise:
            total = jnp.sum(top_p, axis=-1, keepdims=True)
            top_p = top_p / (total + norm_eps if norm_eps else total)
        if scaling != 1.0:
            top_p = top_p * scaling
    return top_p, top_idx.astype(jnp.int32)


def sparse_moe_ffn(moe_params, x, top_k: int, renormalise: bool,
                   live: Optional[jax.Array] = None, layer: Optional[jax.Array] = None,
                   n_group: int = 1, topk_group: int = 1, scaling: float = 1.0,
                   scoring: str = "softmax", norm_eps: float = 0.0,
                   identity_experts: Optional[int] = None, tally: Optional[Tuple[str, ...]] = None):
    """x [S, D] -> [S, D]: experts under top-k routing.

    ``moe_params``: ``{"gate": {"wg": [D, E]}, "experts": {"w_gate": [E, D, F],
    "w_up": [E, D, F], "w_down": [E, F, D]}}``; experts (and a shared expert)
    whose leaves hold no ``w_gate`` are ungated and their ``w_up`` is ``[E, F, D]``
    (:func:`expert_ffn`).  ``live`` [S] bool marks the
    slots that hold a token; a dead slot gets zeros.  Under tensor parallelism
    the experts are sharded on F and the result is a partial sum: the caller
    psums it, as it does a dense row-parallel FFN's.

    ``layer``: the experts' leaves are the whole stack ``[L, E, ...]`` and
    this is the index of the layer to use.  A scan over layers hands them over
    so and not sliced: a kernel's operand has to sit in memory, so a slice of
    the stack would be copied for it, 805 MB a layer at OLMoE's size, as much
    again as the kernels read.  The stack is one grouped matmul's ``L x E``
    groups instead, of which only the layer's hold rows; the kernel visits no
    empty group, and fetches the others' tiles straight from the stack.

    **The chip's share of the experts.**  Which experts are held is read off
    the shapes, as ``paged_forward`` reads its local heads: a router ``[D, E]``
    over expert leaves ``[.., H, ...]`` with H < E says that this chip holds
    experts 0..H-1 of a layer that other chips share (an expert-parallel
    deployment: DeepSeek-V2's 160 as 40 a chip).  The router runs over all E;
    a pick on an expert that is not here never becomes a row: the held picks
    are compacted into a window of ``expert_rows(S, k, H, E)`` rows (sorted by
    expert, whatever their number: a pass that holds more runs the window
    again over the rest, nothing is dropped) and the gather, the grouped
    matmuls and the combine run over that window.  Where H = E none of that is traced and the
    program is the one it was.
    The result is this chip's experts' part of the layer's sum (what the
    exchange of the deployment would gather from the other chips is theirs to
    add: nothing here stands in for them).

    **Identity experts** (``identity_experts`` = Z, not None: LongCat-Flash's
    ``zero_expert_num``).  The router's width is ``real + Z``: outputs
    ``real ..`` are experts that return their input.  The softmax, the
    selection bias and the factor run over all of them alike; a pick at or
    past ``real`` keeps its weight and adds ``w x`` in float32 beside the
    routed sum (scope ``moe_identity``), here, for this chip's own tokens: it
    is never dispatched and no row of a grouped matmul is its own.  A pick under ``real`` that is not held stays a pick
    held elsewhere and adds nothing.  The three kinds of pick in one layer:
    held, held elsewhere, identity.  The call then returns ``(out, tally)``:
    ``tally`` int32 ``[2]``, the live slots' picks on identity experts and on
    held experts (which kind a pick is only the device knows; a family sums
    them over its layers for ``ServeCounters.moe_identity_picks`` /
    ``moe_held_picks``).  With neither ``identity_experts`` nor ``tally`` (every
    family but the two named here) nothing of this is traced and the result is
    ``out`` alone.

    **The tally** (``tally``: names out of :data:`TALLIES`; left out, ``("identity",
    "held")`` with identity experts and nothing without).  The call returns ``(out,
    counts)``, ``counts`` int32 ``[len(tally)]`` in the order named, over the live
    slots: ``identity`` and ``held`` as above; ``experts_hit`` the held experts that
    some pick names, each once: the expert matrices the grouped matmuls of this call
    must read, whatever the routing's skew (``ServeCounters.moe_experts_hit``).  A
    share that has no identity experts and counts its picks passes ``tally`` alone
    (Nemotron-H: ``("held", "experts_hit")``).  The counts are part of the step
    program whether or not a run is traced: two sums, and for ``experts_hit`` one
    comparison of ``[slots, top_k]`` picks with the held experts' numbers.

    ``moe_params["shared"]`` (``{"w_gate": [D, Fs], "w_up", "w_down"}``; ungated
    without ``w_gate``), where a family has it, is the expert every token takes: a dense FFN added to
    the routed part.  ``n_group``, ``topk_group``, ``scaling``, ``scoring`` and
    ``norm_eps`` are :func:`route`'s, and so is ``moe_params["gate"]["bias"]``
    (``[E]``), the selection bias of a family that stores one.
    ``moe_params["shared_gate"]`` (``[D, 1]``), where a family has it, scales the
    shared expert's output by ``sigmoid(x w_g)``, one gate a token (Qwen3-Next)."""
    ex = moe_params["experts"]
    if layer is None:
        ex, layer = jax.tree_util.tree_map(lambda w: w[None], ex), 0
    num_layers, num_experts = ex["w_up"].shape[:2]
    groups = num_layers * num_experts
    stacked = {name: w.reshape((groups,) + w.shape[2:]).astype(x.dtype) for name, w in ex.items()}
    slots = x.shape[0]
    picks, routed = slots * top_k, moe_params["gate"]["wg"].shape[-1]
    rows = expert_rows(slots, top_k, num_experts, routed)
    # one group and no factor: the four arguments route always took, which is
    # what tests/chipbench/test_reference_olmoe.py's stand-in for it accepts
    # (a benchmark test: not this module's to edit); the program is the same
    grouped = () if (n_group, scaling) == (1, 1.0) else (n_group, topk_group, scaling)
    scored = {} if scoring == "softmax" and "bias" not in moe_params["gate"] else {
        "scoring": scoring, "bias": moe_params["gate"].get("bias"), "norm_eps": norm_eps}
    weights, experts = route(moe_params["gate"]["wg"], x, top_k, renormalise, *grouped, **scored)
    with jax.named_scope("moe_expert_ffn"):
        if num_experts < routed:  # a share: the held picks alone become rows, a window a trip
            held = experts < num_experts
            if live is not None:
                held = held & live[:, None]
            out = _held_picks_ffn(stacked, x, weights, experts, held, layer, num_experts, rows)
        else:
            group = layer * num_experts + experts
            if live is not None:
                group = jnp.where(live[:, None], group, groups)
            # row s * k + p is token s's p-th pick; the rows that fill the last tile are dead
            flat = jnp.full((rows,), groups, jnp.int32).at[:picks].set(group.reshape(picks))
            order = jnp.argsort(flat)  # stable: rows sorted by expert, dead rows last
            group_sizes = jnp.zeros((groups,), jnp.int32).at[flat].add(1, mode="drop")
            ys = expert_ffn(x[jnp.minimum(order // top_k, slots - 1)], stacked, group_sizes)
            # rows past the last group are no expert's: whatever sits there is dropped
            ys = jnp.where((flat[order] < groups)[:, None], ys, 0)
            picked = ys[jnp.argsort(order)[:picks]].reshape(slots, top_k, -1)  # back in token order
            out = jnp.einsum("sk,skd->sd", weights, picked.astype(jnp.float32))
    if "shared" in moe_params:
        shared = {name: w.astype(x.dtype) for name, w in moe_params["shared"].items()}
        with jax.named_scope("moe_shared_expert"):
            if "w_gate" in shared:
                hidden = jax.nn.silu(x @ shared["w_gate"]) * (x @ shared["w_up"])
            else:  # ungated, as the routed experts beside it: ``w_up`` ``[F, D]``
                hidden = jnp.square(jax.nn.relu(x @ shared["w_up"].T))
            added = (hidden @ shared["w_down"]).astype(jnp.float32)
            if "shared_gate" in moe_params:
                with jax.named_scope("moe_shared_gate"):
                    added = added * jax.nn.sigmoid(jnp.dot(
                        x, moe_params["shared_gate"].astype(x.dtype),
                        preferred_element_type=jnp.float32))
            out = out + added
    if tally is None and identity_experts is not None:
        tally = ("identity", "held")
    if tally:
        with jax.named_scope("moe_identity"):
            alive = jnp.ones((slots, 1), bool) if live is None else live[:, None]
            counts = {}
            if identity_experts is not None:
                identity = (experts >= moe_params["gate"]["wg"].shape[-1] - identity_experts) & alive
                # an identity expert returns its input: its picks' weights times x, on this chip
                out = out + jnp.sum(jnp.where(identity, weights, 0.0), axis=-1,
                                    keepdims=True) * x.astype(jnp.float32)
                counts["identity"] = jnp.sum(identity, dtype=jnp.int32)
            counts["held"] = jnp.sum((experts < num_experts) & alive, dtype=jnp.int32)
            if "experts_hit" in tally:
                named = jnp.where(alive, experts, routed)[:, :, None] == jnp.arange(num_experts)
                counts["experts_hit"] = jnp.sum(jnp.any(named, axis=(0, 1)), dtype=jnp.int32)
        return out.astype(x.dtype), jnp.stack([counts[name] for name in tally])
    return out.astype(x.dtype)
