"""Serving-time mixture-of-experts FFN: sparse dispatch, no capacity, no drop.

The reference's ragged ``moe_gather`` / ``moe_scatter`` around CUTLASS
``moe_gemm`` (inference/v2/kernels/ragged_ops, cutlass_ops/moe_gemm): every
token reaches its k experts and each expert multiplies only the rows routed
to it.  Shapes are static and follow from the slot count alone: ``S`` token
slots give ``S x k`` routed rows (:func:`expert_rows`), sorted by expert and
handed to one grouped matmul per projection.  A dead slot's picks are given
the expert id ``E``, past every group, so they sort to the tail, belong to no
group and cost no read of any expert's weights.

A pick is one of three kinds (:func:`sparse_moe_ffn`).  *Held*: the expert's
weights are here; the pick is a row of its group.  *Held elsewhere*: the router
is wider than the experts the leaves hold (one chip's share of an
expert-parallel layer); the pick gets the dead group id, reads no weight and
adds nothing here.  *Identity* (LongCat-Flash's zero-computation experts): the
router's last ``identity_experts`` outputs are experts that return their input;
such a pick keeps its weight, adds ``w x`` beside the routed sum on the token's
own chip, is never dispatched, and gets the dead group id too.  Two kinds read
no weight; only one of them adds nothing.

The grouped matmul is the Pallas ``gmm`` of ``jax.experimental.pallas.ops.tpu.
megablox`` on TPU and ``jax.lax.ragged_dot``, the same mathematics in XLA,
elsewhere.  On the v5e at OLMoE's ``[rows, 64 groups, 2048 x 1024]`` the three
matmuls of an expert FFN took 1.28 ms (gmm, tiles 128 x 2048 x 1024) against
2.73 ms (``ragged_dot``) over 2,048 rows and 1.11 against 1.69 ms over 256,
where reading the 64 experts' weights once is 0.98 ms (PERF.md, PR 27).
"""

from typing import Optional

import jax
import jax.numpy as jnp

from ..ops import _pallas

ROW_TILE = 128          # rows a gmm program step multiplies: the MXU's height
K_TILE, N_TILE = 2048, 1024  # an expert's whole 2048 x 1024 matrix in one step


def expert_rows(slots: int, top_k: int) -> int:
    """Rows the expert FFN's program computes for ``slots`` token slots:
    ``slots x top_k``, rounded up to whole row tiles (to 16, a bf16 sublane
    pair, under one tile).  Static, so the serving counters ask it too.  Every
    pick is a row, whatever its kind: picks on experts held elsewhere and on
    identity experts are among the dead rows behind the last group (gathered,
    sorted and combined, multiplied by nothing) until a later PR compacts them:
    of LongCat-Flash's 12 rows a token as one chip of 32, 4 are identity picks
    and 7.75 are held elsewhere under uniform routing, 0.25 a held expert's."""
    tile = ROW_TILE if slots * top_k > ROW_TILE else 16
    return -(-slots * top_k // tile) * tile


def grouped_matmul(lhs, rhs, group_sizes):
    """``lhs[rows of group g] @ rhs[g]``: lhs [M, K] sorted by group, rhs
    [G, K, N], group_sizes [G] -> [M, N].  Rows past the last group come back
    as whatever the kernel left there: the caller must not use them."""
    if not _pallas.use_pallas():
        return jax.lax.ragged_dot(lhs, rhs, group_sizes)
    from jax.experimental.pallas.ops.tpu.megablox import gmm
    (m, k), n = lhs.shape, rhs.shape[-1]
    tiling = (min(m, ROW_TILE), min(k, K_TILE), min(n, N_TILE))
    # positionally: the custom-vjp wrapper takes its static arguments by place
    return gmm(lhs, rhs, group_sizes, lhs.dtype, tiling, None, None, False, _pallas.INTERPRET)


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
    x [S, D] -> (weights [S, k] float32, experts [S, k] int32)."""
    with jax.named_scope("moe_route"):
        logits = jnp.dot(x, wg.astype(x.dtype), preferred_element_type=jnp.float32)
        if scoring not in ("softmax", "sigmoid"):
            raise ValueError(f"route: scoring {scoring!r} is not implemented (softmax, sigmoid)")
        probs = jax.nn.softmax(logits, axis=-1) if scoring == "softmax" else jax.nn.sigmoid(logits)
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
                   identity_experts: Optional[int] = None):
    """x [S, D] -> [S, D]: SwiGLU experts under top-k routing.

    ``moe_params``: ``{"gate": {"wg": [D, E]}, "experts": {"w_gate": [E, D, F],
    "w_up": [E, D, F], "w_down": [E, F, D]}}``.  ``live`` [S] bool marks the
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
    a pick on an expert that is not here gets the dead group id, as a dead
    slot's picks do, so it sorts to the tail, reads no weight and adds zero.
    The result is this chip's experts' part of the layer's sum (what the
    exchange of the deployment would gather from the other chips is theirs to
    add: nothing here stands in for them).

    **Identity experts** (``identity_experts`` = Z, not None: LongCat-Flash's
    ``zero_expert_num``).  The router's width is ``real + Z``: outputs
    ``real ..`` are experts that return their input.  The softmax, the
    selection bias and the factor run over all of them alike; a pick at or
    past ``real`` keeps its weight and adds ``w x`` in float32 beside the
    routed sum (scope ``moe_identity``), here, for this chip's own tokens: it
    is never dispatched, so it gets the dead group id and no row of a grouped
    matmul is its own.  A pick under ``real`` that is not held stays a pick
    held elsewhere and adds nothing.  The three kinds of pick in one layer:
    held, held elsewhere, identity.  The call then returns ``(out, tally)``:
    ``tally`` int32 ``[2]``, the live slots' picks on identity experts and on
    held experts (which kind a pick is only the device knows; a family sums
    them over its layers for ``ServeCounters.moe_identity_picks`` /
    ``moe_held_picks``).  With ``identity_experts`` None (every other family)
    nothing of this is traced and the result is ``out`` alone.

    ``moe_params["shared"]`` (``{"w_gate": [D, Fs], "w_up", "w_down"}``), where
    a family has it, is the expert every token takes: a dense SwiGLU added to
    the routed part.  ``n_group``, ``topk_group``, ``scaling``, ``scoring`` and
    ``norm_eps`` are :func:`route`'s, and so is ``moe_params["gate"]["bias"]``
    (``[E]``), the selection bias of a family that stores one.
    ``moe_params["shared_gate"]`` (``[D, 1]``), where a family has it, scales the
    shared expert's output by ``sigmoid(x w_g)``, one gate a token (Qwen3-Next)."""
    ex = moe_params["experts"]
    if layer is None:
        ex, layer = jax.tree_util.tree_map(lambda w: w[None], ex), 0
    num_layers, num_experts = ex["w_gate"].shape[:2]
    groups = num_layers * num_experts
    stacked = {name: w.reshape((groups,) + w.shape[2:]).astype(x.dtype) for name, w in ex.items()}
    slots = x.shape[0]
    picks, rows = slots * top_k, expert_rows(slots, top_k)
    # one group and no factor: the four arguments route always took, which is
    # what tests/chipbench/test_reference_olmoe.py's stand-in for it accepts
    # (a benchmark test: not this module's to edit); the program is the same
    grouped = () if (n_group, scaling) == (1, 1.0) else (n_group, topk_group, scaling)
    scored = {} if scoring == "softmax" and "bias" not in moe_params["gate"] else {
        "scoring": scoring, "bias": moe_params["gate"].get("bias"), "norm_eps": norm_eps}
    weights, experts = route(moe_params["gate"]["wg"], x, top_k, renormalise, *grouped, **scored)
    with jax.named_scope("moe_expert_ffn"):
        group = layer * num_experts + experts
        if num_experts < moe_params["gate"]["wg"].shape[-1]:  # a pick on an expert held elsewhere
            group = jnp.where(experts < num_experts, group, groups)
        if live is not None:
            group = jnp.where(live[:, None], group, groups)
        # row s * k + p is token s's p-th pick; the rows that fill the last tile are dead
        flat = jnp.full((rows,), groups, jnp.int32).at[:picks].set(group.reshape(picks))
        order = jnp.argsort(flat)  # stable: rows sorted by expert, dead rows last
        group_sizes = jnp.zeros((groups,), jnp.int32).at[flat].add(1, mode="drop")
        xs = x[jnp.minimum(order // top_k, slots - 1)]
        gate = grouped_matmul(xs, stacked["w_gate"], group_sizes)
        up = grouped_matmul(xs, stacked["w_up"], group_sizes)
        ys = grouped_matmul(jax.nn.silu(gate) * up, stacked["w_down"], group_sizes)
        # rows past the last group are no expert's: whatever sits there is dropped
        ys = jnp.where((flat[order] < groups)[:, None], ys, 0)
        picked = ys[jnp.argsort(order)[:picks]].reshape(slots, top_k, -1)  # back in token order
        out = jnp.einsum("sk,skd->sd", weights, picked.astype(jnp.float32))
    if "shared" in moe_params:
        shared = {name: w.astype(x.dtype) for name, w in moe_params["shared"].items()}
        with jax.named_scope("moe_shared_expert"):
            hidden = jax.nn.silu(x @ shared["w_gate"]) * (x @ shared["w_up"])
            added = (hidden @ shared["w_down"]).astype(jnp.float32)
            if "shared_gate" in moe_params:
                with jax.named_scope("moe_shared_gate"):
                    added = added * jax.nn.sigmoid(jnp.dot(
                        x, moe_params["shared_gate"].astype(x.dtype),
                        preferred_element_type=jnp.float32))
            out = out + added
    if identity_experts is not None:
        real = moe_params["gate"]["wg"].shape[-1] - identity_experts
        with jax.named_scope("moe_identity"):
            alive = jnp.ones((slots, 1), bool) if live is None else live[:, None]
            identity = (experts >= real) & alive
            # an identity expert returns its input: its picks' weights times x, on this chip
            out = out + jnp.sum(jnp.where(identity, weights, 0.0), axis=-1,
                                keepdims=True) * x.astype(jnp.float32)
            tally = jnp.stack([jnp.sum(identity, dtype=jnp.int32),
                               jnp.sum((experts < num_experts) & alive, dtype=jnp.int32)])
        return out.astype(x.dtype), tally
    return out.astype(x.dtype)
