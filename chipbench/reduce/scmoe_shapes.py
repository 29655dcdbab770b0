"""Operations and bytes of a shortcut expert layer whose router may pick
identity experts, served as one chip's share of an expert-parallel layer
(LongCat-Flash), counted from shapes and the program's own pick tallies.

An expert is SwiGLU: three matrices of ``hidden_size x expert_ffn_hidden_size``.
Of a token's ``moe_topk`` picks in a layer only those on experts HELD here
multiply anything: a pick on an identity expert adds ``w u`` and a pick on an
expert held elsewhere adds nothing, and neither is a row of a grouped matmul
that a matrix is read for.  Which kind a pick is only the device knows
(``ServeCounters.moe_held_picks`` / ``moe_identity_picks``, tallied by the
program), so the counts here start from the true held rows."""

from chipbench.reduce import moe_shapes


def groups(sizes) -> int:
    """Groups of the stacked grouped matmul: layers x held experts."""
    return sizes["num_layers"] * sizes["n_routed_experts"]


def router_widths(sizes, chips=(1, 2, 4, 8, 16, 32, 64)):
    """Widths the router may have: the held experts times the chips of some
    deployment (not among the published keys), and the identity experts."""
    return [sizes["n_routed_experts"] * c + sizes["zero_expert_num"] for c in chips]


def picks(sizes, live_tokens: int) -> int:
    """Every pick of ``live_tokens`` tokens, whatever its kind."""
    return live_tokens * sizes["moe_topk"] * sizes["num_layers"]


def matrix_bytes(sizes, dtype_bytes: int = 2) -> int:
    """One of an expert's three matrices."""
    return sizes["hidden_size"] * sizes["expert_ffn_hidden_size"] * dtype_bytes


def expert_ffn_flops(sizes, held_rows: int) -> int:
    """2 x hidden x width for each of the three matmuls a held row passes."""
    return held_rows * 3 * 2 * sizes["hidden_size"] * sizes["expert_ffn_hidden_size"]


def expert_ffn_least_seconds(sizes, held_rows: int, call_capacities, peaks,
                             dtype_bytes: int = 2) -> dict:
    """Least time the chip could take for grouped matmuls that multiply
    ``held_rows`` rows (every layer counted) in calls of ``call_capacities``
    rows each: the larger of the operations at the peak rate and the bytes at
    the memory's.  Bytes: the fewest expert matrices the calls can have read,
    and each held row in and out once at the hidden width.  An expert FFN is
    three calls over the same rows (gate, up, down), each with matrices of its
    own, so the calls are taken in threes: one call of every three holds all
    ``held_rows``, laid into the widest first, a call of r held rows reading
    ``min(held experts, r)`` (``moe_shapes.fewest_matrix_reads``), and the two
    others read as many.  How the held rows really sat in the calls is not
    recorded, and any true laying reads more: a decode step's 16 held rows a
    layer touch about 10 of 16 experts in EVERY call, where this count lays a
    wave's into a few chunk calls.  A floor, so no reading passes 100%; far
    under it where most calls hold a few rows."""
    compute = expert_ffn_flops(sizes, held_rows) / peaks["bf16_flops_per_s"]
    one_of_three = sorted(call_capacities, reverse=True)[::3]
    reads = 3 * moe_shapes.fewest_matrix_reads(one_of_three, held_rows,
                                               sizes["n_routed_experts"])
    traffic = reads * matrix_bytes(sizes, dtype_bytes) \
        + held_rows * 2 * sizes["hidden_size"] * dtype_bytes
    memory = traffic / peaks["hbm_bytes_per_s"]
    return {"seconds": max(compute, memory), "compute_s": compute, "memory_s": memory,
            "matrix_reads": reads}
