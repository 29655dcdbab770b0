"""Operations and bytes of a mixture-of-experts FFN, counted from shapes and
routed-row counts alone (``shapes.py`` counts a dense decoder and refuses
expert keys; this file is the sparse configuration's own count).

An expert is SwiGLU: three matrices of ``hidden x width``.  A routed row is one
(token, picked expert) pair of one layer; a call is one grouped matmul (three
to an expert FFN: gate, up, down), over the rows of one pass and layer."""


def matrix_bytes(sizes, dtype_bytes: int = 2) -> int:
    """One of an expert's three matrices."""
    return sizes["hidden_size"] * sizes["intermediate_size"] * dtype_bytes


def expert_ffn_flops(sizes, routed_rows: int) -> int:
    """2 x hidden x width for each of the three matmuls a routed row passes."""
    return routed_rows * 3 * 2 * sizes["hidden_size"] * sizes["intermediate_size"]


def fewest_matrix_reads(capacities, rows: int, experts: int) -> int:
    """The fewest expert matrices that calls holding at most ``capacities[c]``
    rows must read to multiply ``rows`` rows in all, where a call of r rows
    reads ``min(experts, r)`` (each row of a pass and layer may sit with
    another expert until all are in use, and a matrix in use is read once).
    How the rows sat in the calls is not recorded, so they are laid where they
    cost least: into the widest calls first, since a call's rows beyond
    ``experts`` read nothing more.  Any other way of laying them reads more."""
    reads = 0
    for capacity in sorted(capacities, reverse=True):
        if rows <= 0:
            break
        took = min(capacity, rows)
        reads += min(experts, took)
        rows -= took
    return reads


def expert_ffn_least_seconds(sizes, routed_rows: int, call_capacities, peaks,
                             dtype_bytes: int = 2) -> dict:
    """Least time the chip could take for expert FFNs that route
    ``routed_rows`` rows (every layer counted) through grouped-matmul calls of
    ``call_capacities`` rows each: the larger of the time the operations need
    at the peak rate and the time the bytes need at the memory's.  Bytes: the
    fewest matrix reads (each FFN is three calls over the same rows, so the
    calls hold ``3 x routed_rows``), and each routed row read and written once
    at the hidden width (the gate and up products can stay on the chip).  The
    larger of two sums is at most the sum, call by call, of the larger."""
    compute = expert_ffn_flops(sizes, routed_rows) / peaks["bf16_flops_per_s"]
    reads = fewest_matrix_reads(call_capacities, 3 * routed_rows, sizes["num_experts"])
    traffic = reads * matrix_bytes(sizes, dtype_bytes) \
        + routed_rows * 2 * sizes["hidden_size"] * dtype_bytes
    memory = traffic / peaks["hbm_bytes_per_s"]
    return {"seconds": max(compute, memory), "compute_s": compute, "memory_s": memory,
            "matrix_reads": reads}
