"""Bytes one live sequence holds in the ``M`` layers' state of a hybrid whose
layer is one part alone (Nemotron-H), whatever its length, from the leaf shapes
of ``engine.kv`` and the published sizes (``chipbench/reduce/
nemotron_h_shapes.py``: the shift's leaf ``I + 2 G N`` columns wide in the
cache's dtype, the matrices' in float32; the leaves count the ``M`` layers
alone).  A program that holds no such leaves gives nothing."""

from chipbench.reduce import nemotron_h_shapes as shapes


def read(run):
    if run.kind != "serve":
        return None
    by_leaf = shapes.state_bytes_per_seq(run.sizes, getattr(run, "pool_shapes", ()))
    if by_leaf is None:
        return None
    return float(sum(by_leaf.values())), dict(by_leaf)
