"""Shapes and counts of a hybrid of Kimi Delta Attention and latent-attention
layers (Ling-3.0's ``bailing_hybrid``), from the published sizes, the leaf shapes
of the cache the engine holds and the engine's counters: what a sequence holds in
the state, and the least time the chip could take for the recurrence over the
tokens the scan was given and for the one-token update of the rows a step held.
Nothing here comes from ``deepspeed_tpu``.

The engine's cache tree has the latent pool's leaf ``[L_mla, NB, 1, bs, width]``
and two state leaves: ``conv`` ``[L_kda, slots + 1, taps - 1, 3 H dh]`` (rank 4,
the pool's dtype) and ``recurrent`` ``[L_kda, slots + 1, H, dh, dh]`` (rank 5 like
a pool leaf; told from one by its trailing ``(H, dh, dh)`` and the conv leaf's
leading ``(L_kda, slots + 1)``), float32.
"""

CONV_VALUE_BYTES = 2       # every serving configuration's cache is bfloat16; the entry hands no dtype
RECURRENT_VALUE_BYTES = 4  # the configuration file's ``assumed.recurrent_state``: float32


def widths(sizes):
    """(H, dh, the filter's columns ``3 H dh``)."""
    h, dh = sizes["num_attention_heads"], sizes["head_dim"]
    return h, dh, 3 * h * dh


def is_family(sizes) -> bool:
    return "kda_lower_bound" in sizes and "layer_group_size" in sizes


def state_leaves(sizes, pool_shapes):
    """``(conv leaf, recurrent leaf)`` among the cache's leaf shapes, or None."""
    if not is_family(sizes):
        return None
    h, dh, columns = widths(sizes)
    conv = [tuple(s) for s in pool_shapes or () if len(s) == 4 and s[3] == columns
            and s[2] == sizes["short_conv_kernel_size"] - 1]
    if len(conv) != 1:
        return None
    recurrent = conv[0][:2] + (h, dh, dh)
    return (conv[0], recurrent) if recurrent in {tuple(s) for s in pool_shapes} else None


def state_bytes_per_seq(sizes, pool_shapes):
    """{leaf: bytes one live sequence holds in it, whatever its length}."""
    leaves = state_leaves(sizes, pool_shapes)
    if leaves is None:
        return None
    (layers, _, kept, columns), (_, _, h, dk, dv) = leaves
    return {"conv": layers * kept * columns * CONV_VALUE_BYTES,
            "recurrent": layers * h * dk * dv * RECURRENT_VALUE_BYTES}


def scan_least_seconds(sizes, live_positions: int, peaks) -> dict:
    """The least time for the recurrence over ``live_positions`` token positions
    (tokens x layers: the engine's ``scan_live_positions``).

    operations a token a head (a multiply-add is two): every form of the delta
    rule, chunked or token by token, multiplies the head's state by ``k`` (``2 dh
    dh``), adds the outer product ``k d^T`` to it (``2 dh dh``) and multiplies it
    by ``q`` (``2 dh dh``); what a chunk's own tokens exchange (``A``, ``B``, the
    inverse), the decay and its exponentials are the implementation's and count
    for nothing here.

    bytes a token a layer: q, k, v in and o out (``H dh`` each) at 2 bytes, the
    decay's logarithm (``H dh``) and beta (``H``) at 4.  The carried matrices (``H
    x dh x dh x 4`` in and out a sequence a pass a layer) are left out: how many
    sequences a pass held is not among the counters, so the share is the smaller
    for it."""
    h, dh, _ = widths(sizes)
    operations = live_positions * h * 6 * dh * dh
    moved = live_positions * (4 * h * dh * 2 + h * dh * 4 + h * 4)
    compute_s = operations / peaks["bf16_flops_per_s"]
    memory_s = moved / peaks["hbm_bytes_per_s"]
    return {"seconds": max(compute_s, memory_s), "compute_s": compute_s, "memory_s": memory_s}


def update_least_seconds(sizes, row_updates: int, peaks) -> dict:
    """The least time for ``row_updates`` one-token updates (rows of one token x
    layers): a row's matrices ``H x dh x dh x 4`` bytes read once and written once.
    Its operations (``7 dh dh`` a head, on the vector unit) are a hundredth of that
    time at the matrix unit's peak and are not counted."""
    h, dh, _ = widths(sizes)
    moved = row_updates * 2 * h * dh * dh * RECURRENT_VALUE_BYTES
    return {"seconds": moved / peaks["hbm_bytes_per_s"], "moved_bytes": moved}
