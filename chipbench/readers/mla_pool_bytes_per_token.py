"""Bytes one cached token takes in one layer of the pool the engine holds,
from the leaf shapes of ``engine.kv`` (``[L, NB, KV, bs, width]``: KV x width
values a token a layer for every distinct leaf).  The entry keeps the shapes
as a set, so two leaves of one shape (a K and a V pool) would count once: the
metric is for a pool whose leaves differ or are one, which a latent pool is.
Values are 2 bytes: every serving configuration's pool is bfloat16 (``engine.
config.dtype``), and the entry hands the readers no dtype."""

POOL_VALUE_BYTES = 2


def read(run):
    if run.kind != "serve" or "kv_lora_rank" not in run.sizes:
        return None
    shapes = [s for s in getattr(run, "pool_shapes", ()) if len(s) == 5]
    if not shapes:
        return None
    values = sum(kv * width for _, _, kv, _, width in shapes)
    return float(values * POOL_VALUE_BYTES), {"pool_leaves": [list(s) for s in shapes]}
