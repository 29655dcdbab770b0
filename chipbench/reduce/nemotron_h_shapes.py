"""Shapes and counts of a hybrid whose layer is ONE part alone (Nemotron-H: a
Mamba-2 mixer with ``n_groups`` B/C groups, ungated relu^2 experts, or
attention), from the published sizes, the leaf shapes of the cache the engine
holds and the engine's counters: what a sequence holds in the state, the least
time the chip could take for the recurrence over the tokens the scan was given,
for the one-token update of the rows a decode step held, and for the two grouped
matmuls of the held picks.  Nothing here comes from ``deepspeed_tpu``.

The engine's cache tree has the paged pool's leaves ``[L_attention, NB, KV, bs,
dh]`` and two state leaves over the ``M`` layers alone: ``conv`` ``[L_M, slots
+ 1, taps - 1, I + 2 G N]`` (rank 4, the pool's dtype) and ``ssm`` ``[L_M, slots
+ 1, H, P, N]`` (rank 5 like a pool leaf; told from one by its trailing ``(H, P,
N)`` and the conv leaf's leading ``(L_M, slots + 1)``), float32.  An ``E`` layer
has a row in none of them.
"""

CONV_VALUE_BYTES = 2  # every serving configuration's cache is bfloat16; the entry hands no dtype
SSM_VALUE_BYTES = 4   # the configuration file's ``assumed.ssm_state``: float32
KEYS = {"mamba_num_heads", "mamba_head_dim", "ssm_state_size", "n_groups", "hybrid_override_pattern",
        "moe_intermediate_size"}


def is_family(sizes) -> bool:
    return KEYS <= set(sizes)


def widths(sizes):
    """(H, P, N, G, inner columns I = H P, the filter's columns I + 2 G N)."""
    h, p, n, g = (sizes["mamba_num_heads"], sizes["mamba_head_dim"], sizes["ssm_state_size"],
                  sizes["n_groups"])
    return h, p, n, g, h * p, h * p + 2 * g * n


def layers(sizes, kind: str) -> int:
    """Layers of ``kind`` (``M``, ``E`` or ``*``) among the ``num_hidden_layers`` run."""
    return sizes["hybrid_override_pattern"][:sizes["num_hidden_layers"]].count(kind)


def state_leaves(sizes, pool_shapes):
    """``(conv leaf, ssm leaf)`` among the cache's leaf shapes, or None."""
    if not is_family(sizes):
        return None
    h, p, n, _, _, conv_columns = widths(sizes)
    conv = [tuple(s) for s in pool_shapes or () if len(s) == 4 and s[3] == conv_columns
            and s[2] == sizes["conv_kernel"] - 1]
    if len(conv) != 1:
        return None
    ssm = conv[0][:2] + (h, p, n)
    return (conv[0], ssm) if ssm in {tuple(s) for s in pool_shapes} else None


def state_bytes_per_seq(sizes, pool_shapes):
    """{leaf: bytes one live sequence holds in it, whatever its length}."""
    leaves = state_leaves(sizes, pool_shapes)
    if leaves is None:
        return None
    (count, _, kept, columns), (_, _, h, p, n) = leaves
    return {"conv": count * kept * columns * CONV_VALUE_BYTES, "ssm": count * h * p * n * SSM_VALUE_BYTES}


def scan_least_seconds(sizes, live_positions: int, peaks) -> dict:
    """The least time for the recurrence over ``live_positions`` token positions
    (tokens x ``M`` layers: the engine's ``scan_live_positions``).

    operations a token a head (a multiply-add is two): every form of the
    recurrence multiplies the head's state by its group's ``C`` (``2 P N``) and
    adds an update of ``x B^T`` to it (``2 P N``); what a chunk's own tokens
    exchange, the decays and ``D x`` are the implementation's and count for
    nothing here.

    bytes a token a layer: x in and y out (``I`` each) and B and C in (``G N``
    each: EVERY group's, 4,096 B at 8 groups of 128 where one group's are 512) at
    2 bytes, dt (``H``) at 4.  The carried matrices are left out, as in
    ``ssm_shapes.py``: the share is the smaller for it."""
    h, p, n, g, inner, _ = widths(sizes)
    operations = live_positions * h * 4 * p * n
    moved = live_positions * (2 * inner * 2 + 2 * g * n * 2 + h * 4)
    compute_s = operations / peaks["bf16_flops_per_s"]
    memory_s = moved / peaks["hbm_bytes_per_s"]
    return {"seconds": max(compute_s, memory_s), "compute_s": compute_s, "memory_s": memory_s}


def update_least_seconds(sizes, row_updates: int, peaks) -> dict:
    """The least time for ``row_updates`` one-token updates (live one-token rows x
    ``M`` layers): a row's matrices ``H x P x N x 4`` bytes read once and written
    once, and its groups' B and C (``2 G N`` at 2 bytes) read.  Its operations
    (``5 P N`` a head) are a thousandth of that time."""
    h, p, n, g, _, _ = widths(sizes)
    moved = row_updates * (2 * h * p * n * SSM_VALUE_BYTES + 2 * g * n * 2)
    return {"seconds": moved / peaks["hbm_bytes_per_s"], "moved_bytes": moved}


# ------------------------------------------------------------- ungated experts
def matrix_bytes(sizes, dtype_bytes: int = 2) -> int:
    """One of an expert's TWO matrices (``W_up``, ``W_down``: no gate)."""
    return sizes["hidden_size"] * sizes["moe_intermediate_size"] * dtype_bytes


def expert_ffn_flops(sizes, held_rows: int) -> int:
    """rows x 4 x hidden x width: 2 x hidden x width for each of the two matmuls a held row passes."""
    return held_rows * 4 * sizes["hidden_size"] * sizes["moe_intermediate_size"]


def expert_ffn_least_seconds(sizes, held_rows: int, experts_hit: int, peaks, dtype_bytes: int = 2) -> dict:
    """Least time the chip could take for grouped matmuls that multiply
    ``held_rows`` rows (every ``E`` layer and pass counted) whose picks named
    ``experts_hit`` held experts (summed over layers and passes): the larger of the
    operations at the peak rate and the bytes at the memory's.  An ungated expert
    FFN is TWO calls over the same rows (up, down), each with a matrix of its own
    an expert, read at least once where a row names the expert.  Bytes: those
    matrices and each held row in and out once at the hidden width.  A floor (a
    call reads an expert's matrix once a row tile its rows span), so no reading
    passes 100%."""
    compute = expert_ffn_flops(sizes, held_rows) / peaks["bf16_flops_per_s"]
    reads = 2 * experts_hit
    traffic = reads * matrix_bytes(sizes, dtype_bytes) + held_rows * 2 * sizes["hidden_size"] * dtype_bytes
    memory = traffic / peaks["hbm_bytes_per_s"]
    return {"seconds": max(compute, memory), "compute_s": compute, "memory_s": memory, "matrix_reads": reads}
