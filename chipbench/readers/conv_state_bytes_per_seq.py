"""Bytes one live sequence holds in the conv layers' state, whatever its
length, from the leaf shapes of ``engine.kv`` (the state is the one rank-4
leaf ``[L_conv, slots + 1, k, D]``: ``chipbench/reduce/lfm2_shapes.py``).  A
program that holds no such leaf gives nothing."""

from chipbench.reduce import lfm2_shapes


def read(run):
    if run.kind != "serve" or "conv_L_cache" not in run.sizes:
        return None
    leaf = lfm2_shapes.state_leaf(getattr(run, "pool_shapes", ()))
    if leaf is None:
        return None
    return float(lfm2_shapes.state_bytes_per_seq([leaf])), {"state_leaf": list(leaf),
                                                            "slots": leaf[1] - 1}
