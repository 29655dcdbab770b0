"""Bytes one live sequence holds in the Kimi Delta Attention layers' state,
whatever its length, from the leaf shapes of ``engine.kv`` and the published sizes
(``chipbench/reduce/kda_shapes.py``: the shift's leaf in the cache's dtype, the
matrices' in float32).  A program that holds no such leaves gives nothing."""

from chipbench.reduce import kda_shapes


def read(run):
    if run.kind != "serve":
        return None
    by_leaf = kda_shapes.state_bytes_per_seq(run.sizes, getattr(run, "pool_shapes", ()))
    if by_leaf is None:
        return None
    return float(sum(by_leaf.values())), dict(by_leaf)
