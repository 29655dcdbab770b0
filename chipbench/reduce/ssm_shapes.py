"""Shapes and counts of a hybrid of Mamba-2 and attention layers (Granite
4.0-H), from the published sizes, the leaf shapes of the cache the engine holds
and the engine's counters: what a sequence holds in the state, which results of
a device trace are certainly a Mamba-2 layer's, and the least time the chip
could take for the recurrence over the tokens the scan was given and for the
one-token update of the rows a decode step held.  Nothing here comes from
``deepspeed_tpu``.

The engine's cache tree has the paged pool's leaves ``[L_attn, NB, KV, bs,
dh]`` and two state leaves: ``conv`` ``[L_mamba, slots + 1, taps - 1, I + 2
Ns]`` (rank 4, the pool's dtype) and ``ssm`` ``[L_mamba, slots + 1, H, P, Ns]``
(rank 5 like a pool leaf; told from one by its trailing ``(H, P, Ns)`` and the
conv leaf's leading ``(L_mamba, slots + 1)``), float32.
"""

CONV_VALUE_BYTES = 2  # every serving configuration's cache is bfloat16; the entry hands no dtype
SSM_VALUE_BYTES = 4   # the configuration file's ``assumed.ssm_state``: float32


def widths(sizes):
    """(H, P, Ns, inner columns I, the filter's columns, ``W_in``'s columns)."""
    h, p, ns = sizes["mamba_n_heads"], sizes["mamba_d_head"], sizes["mamba_d_state"]
    conv = h * p + 2 * sizes["mamba_n_groups"] * ns
    return h, p, ns, h * p, conv, h * p + conv + h


def is_family(sizes) -> bool:
    return "mamba_n_heads" in sizes and "mamba_d_state" in sizes


def state_leaves(sizes, pool_shapes):
    """``(conv leaf, ssm leaf)`` among the cache's leaf shapes, or None."""
    if not is_family(sizes):
        return None
    h, p, ns, _, conv_columns, _ = widths(sizes)
    conv = [tuple(s) for s in pool_shapes or () if len(s) == 4 and s[3] == conv_columns
            and s[2] == sizes["mamba_d_conv"] - 1]
    if len(conv) != 1:
        return None
    ssm = conv[0][:2] + (h, p, ns)
    return (conv[0], ssm) if ssm in {tuple(s) for s in pool_shapes} else None


def state_bytes_per_seq(sizes, pool_shapes):
    """{leaf: bytes one live sequence holds in it, whatever its length}."""
    leaves = state_leaves(sizes, pool_shapes)
    if leaves is None:
        return None
    (layers, _, kept, columns), (_, _, h, p, ns) = leaves
    return {"conv": layers * kept * columns * CONV_VALUE_BYTES,
            "ssm": layers * h * p * ns * SSM_VALUE_BYTES}


def is_mixer_result(dims, sizes) -> bool:
    """A result that is certainly a Mamba-2 layer's: ``[.., z | xBC | dt]`` (``u
    W_in``), ``[.., xBC]`` (the filter and its SiLU, the rows of the shift), or
    anything that ends in ``(H, P, Ns)`` (the carried matrices).  NOT among
    them, because nothing tells them from the step's other per-token
    operations: the output projection ``[slots, hidden]``, and what the gate,
    the norm and the scan's layout do over ``[.., I]`` or ``[H, .., P]``."""
    h, p, ns, _, conv, projected = widths(sizes)
    dims = tuple(dims)
    return bool(dims) and (dims[-1] in (conv, projected) or dims[-3:] == (h, p, ns))


def is_whole_state(dims, ssm_leaf) -> bool:
    """A result that is the whole SSM state: as held, or with its layers and
    slots on one axis, as the layer scan carries it."""
    layers, slots = ssm_leaf[:2]
    return tuple(dims) in (tuple(ssm_leaf), (layers * slots, ) + tuple(ssm_leaf[2:]))


def is_state_move(dims, ssm_leaf) -> bool:
    """A result that is the whole SSM state or a batch's rows of it ``[n, H, P,
    Ns]`` for ``n`` up to the slots: the slot read, the write back."""
    dims, slots = tuple(dims), ssm_leaf[1]
    return is_whole_state(dims, ssm_leaf) or (
        len(dims) == 4 and dims[1:] == tuple(ssm_leaf[2:]) and 0 < dims[0] < slots)


def scan_least_seconds(sizes, live_positions: int, peaks) -> dict:
    """The least time for the recurrence over ``live_positions`` token
    positions (tokens x layers: the engine's ``scan_live_positions``).

    operations a token a head (a multiply-add is two): every form of the
    recurrence, chunked or token by token, multiplies the head's state by ``C``
    (``2 P Ns``) and adds an update of ``x B^T`` to it (``2 P Ns``); what a
    chunk's own tokens exchange (``C B^T``, the mask, ``2 C P`` a token), the
    decays and ``D x`` are the implementation's and count for nothing here.

    bytes a token a layer: x in and y out (``I`` each) and B and C in (``Ns``
    each) at 2 bytes, dt (``H``) at 4.  The carried matrices (``H x P x Ns x 4``
    in and out a sequence a pass a layer) are left out: how many sequences a
    pass held is not among the counters, so the share is the smaller for it."""
    h, p, ns, inner, _, _ = widths(sizes)
    operations = live_positions * h * 4 * p * ns
    moved = live_positions * (2 * inner * 2 + 2 * ns * 2 + h * 4)
    compute_s = operations / peaks["bf16_flops_per_s"]
    memory_s = moved / peaks["hbm_bytes_per_s"]
    return {"seconds": max(compute_s, memory_s), "compute_s": compute_s, "memory_s": memory_s}


def update_least_seconds(sizes, row_updates: int, peaks) -> dict:
    """The least time for ``row_updates`` one-token updates (live decode rows x
    layers): a row's matrices ``H x P x Ns x 4`` bytes read once and written
    once.  Its operations (``5 P Ns`` a head) are a thousandth of that time."""
    h, p, ns, _, _, _ = widths(sizes)
    moved = row_updates * 2 * h * p * ns * SSM_VALUE_BYTES
    return {"seconds": moved / peaks["hbm_bytes_per_s"], "moved_bytes": moved}
