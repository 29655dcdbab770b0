"""Shapes and counts of a hybrid of Gated DeltaNet and attention layers
(Qwen3-Next), from the published sizes, the leaf shapes of the cache the engine
holds and the engine's scan counters: what a sequence holds in the state, which
results of a device trace are certainly a Gated DeltaNet layer's, and the least
time the chip could take for the gated delta rule over the tokens the scan was
given.  Nothing here comes from ``deepspeed_tpu``.

The engine's cache tree has the paged pool's leaves ``[L_attn, NB, KV, bs,
dh]`` and two state leaves: ``conv`` ``[L_gdn, slots + 1, taps - 1, 2 x key +
value]`` (rank 4, the pool's dtype) and ``recurrent`` ``[L_gdn, slots + 1, Hv,
dk, dv]`` (rank 5 like a pool leaf; told from one by its trailing ``(Hv, dk,
dv)`` and the conv leaf's leading ``(L_gdn, slots + 1)``), float32.
"""

CHUNK = 64  # positions of one chunk of the scan (the published algorithm's, FLA's and HF's)
CONV_VALUE_BYTES = 2       # every serving configuration's cache is bfloat16; the entry hands no dtype
RECURRENT_VALUE_BYTES = 4  # the configuration file's ``assumed.recurrent_state``: float32


def widths(sizes):
    """(Hk, Hv, dk, dv, columns of [q | k | v], columns of [q | k | v | z])."""
    hk, hv = sizes["linear_num_key_heads"], sizes["linear_num_value_heads"]
    dk, dv = sizes["linear_key_head_dim"], sizes["linear_value_head_dim"]
    return hk, hv, dk, dv, 2 * hk * dk + hv * dv, 2 * hk * dk + 2 * hv * dv


def is_family(sizes) -> bool:
    return "linear_num_value_heads" in sizes and "linear_conv_kernel_dim" in sizes


def state_leaves(sizes, pool_shapes):
    """``(conv leaf, recurrent leaf)`` among the cache's leaf shapes, or None."""
    if not is_family(sizes):
        return None
    _, hv, dk, dv, mixed, _ = widths(sizes)
    conv = [tuple(s) for s in pool_shapes or () if len(s) == 4 and s[3] == mixed
            and s[2] == sizes["linear_conv_kernel_dim"] - 1]
    if len(conv) != 1:
        return None
    recurrent = conv[0][:2] + (hv, dk, dv)
    return (conv[0], recurrent) if recurrent in {tuple(s) for s in pool_shapes} else None


def state_bytes_per_seq(sizes, pool_shapes):
    """{leaf: bytes one live sequence holds in it, whatever its length}."""
    leaves = state_leaves(sizes, pool_shapes)
    if leaves is None:
        return None
    (layers, _, kept, mixed), (_, _, hv, dk, dv) = leaves
    return {"conv": layers * kept * mixed * CONV_VALUE_BYTES,
            "recurrent": layers * hv * dk * dv * RECURRENT_VALUE_BYTES}


def is_mixer_result(dims, sizes) -> bool:
    """A result that is certainly a Gated DeltaNet layer's: ``[.., q | k | v |
    z]`` (``u W_qkvz``), ``[.., q | k | v]`` (the filter and its SiLU, the rows
    of the shift), or anything that ends in ``(Hv, dk, dv)`` (the carried
    matrices).  NOT among them, because nothing tells them from the step's
    other per-token operations: the output projection ``[slots, hidden]``, and
    what the scan's layout and the output norm do over ``[.., Hv, dv]`` or
    ``[Hv, .., dv]`` (the attention's ``[.., H, dh]`` can be as wide)."""
    _, hv, dk, dv, mixed, projected = widths(sizes)
    dims = tuple(dims)
    return bool(dims) and (dims[-1] in (mixed, projected) or dims[-3:] == (hv, dk, dv))


def scan_least_seconds(sizes, live_positions: int, peaks) -> dict:
    """The least time for the gated delta rule over ``live_positions`` token
    positions (tokens x layers: the engine's ``scan_live_positions``), in
    chunks of ``CHUNK``, a value head at a time, as the published chunked
    algorithm needs them:

    operations a chunk a head (a multiply-add is two): ``K K^T`` and ``Q K^T``
    ``2 C^2 dk`` each; the unit-lower-triangular solve against ``[beta K | beta
    V]`` ``C^2 (dk + dv)``; ``W S``, ``Q S`` and ``K^T V'`` ``2 C dk dv`` each;
    the triangular ``(Gamma * Q K^T) V'`` ``C^2 dv``.  (An inverse by repeated
    squaring, a padded chunk or a pass per mantissa slice are the
    implementation's and count for nothing here.)

    bytes a token a layer: q and k in (``Hk x dk`` each), v in and o out (``Hv
    x dv`` each) at 2 bytes, g and beta (``Hv`` each) at 4.  The carried
    matrices (``Hv x dk x dv x 4`` in and out a sequence a pass a layer) are
    left out: how many sequences a pass held is not among the counters, so the
    share is the smaller for it."""
    hk, hv, dk, dv, _, _ = widths(sizes)
    c = CHUNK
    a_chunk = 2 * (2 * c * c * dk) + c * c * (dk + dv) + 3 * (2 * c * dk * dv) + c * c * dv
    operations = live_positions / c * hv * a_chunk
    moved = live_positions * (2 * hk * dk * 2 + 2 * hv * dv * 2 + 2 * hv * 4)
    compute_s = operations / peaks["bf16_flops_per_s"]
    memory_s = moved / peaks["hbm_bytes_per_s"]
    return {"seconds": max(compute_s, memory_s), "compute_s": compute_s, "memory_s": memory_s}
