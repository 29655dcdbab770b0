"""The index-score kernel's share of its roofline: the least time the chip
could take for ``sum_j w_j relu(q_j . k_s)`` over the causal (query token, key)
pairs of the traced wave (``chipbench/reduce/dsa_shapes.py``, from the engine's
``dsa_causal_keys``) over the device time of the events named
``dsa_index_scores``.  The kernel scores whole steps of blocks up to a tile's
last position, so it reads under 100 by construction."""

from chipbench.readers.dsa_indexer_share import KERNEL
from chipbench.reduce import dsa_shapes


def read(run):
    counters = getattr(run, "counters", None) or {}
    if (run.kind != "serve" or run.trace is None or not dsa_shapes.is_family(run.sizes)
            or not counters.get("dsa_causal_keys")):
        return None
    spent = run.trace.kernel_seconds(KERNEL)
    if spent <= 0:
        return None
    a_pass = counters.get("live_tokens", 0) / max(getattr(run, "forwards", 0), 1)
    least = dsa_shapes.index_least_seconds(run.sizes, counters["dsa_causal_keys"], a_pass, run.peaks)
    bound = max((k for k in least if k != "seconds"), key=least.get)
    return 100.0 * least["seconds"] / spent, {"kernel_s": round(spent, 4), "mostly": bound,
                                              **{k: round(v, 5) for k, v in least.items()}}
