"""The share of the device's busy time spent moving the KV pool: self time of
the operations whose printed result has the pool's shape (slices, updates and
whole copies of it), over the traced wave's busy time.  The paged-attention
kernel reads the pool and is not counted.

The entry hands over the pool's leaves as the program holds them
(``run.pool_shapes``, layer axis first).  An operation is pool-shaped when its
result's dimensions end in a leaf's dimensions after the layer axis,
``[.., NB, KV, bs, Dh]``, or in their flattening ``[.., NB*KV*bs, Dh]``.  The
trace prints a result as ``bf16[16,368,8,128,128]``, the ledger's breakdown as
``_bf16_16_368_8_128_128_``: both are read."""

import math
import re

from chipbench.reduce import xplane

KERNEL = "paged_attention"
KINDS = ("dynamic-update-slice", "dynamic-slice", "copy")  # by what an operation's name says it does
NOT_A_NAME = re.compile(r"[^A-Za-z0-9.\-]")


def pool_endings(pool_shapes):
    """The endings ``_368_8_128_128_`` and ``_376832_128_`` of each leaf."""
    endings = set()
    for shape in pool_shapes:
        dims = tuple(shape[1:])
        if len(dims) < 2:
            continue
        for ending in (dims, (math.prod(dims[:-1]), dims[-1])):
            endings.add("_" + "_".join(str(d) for d in ending) + "_")
    return tuple(sorted(endings))


def pool_shaped(name: str, endings) -> bool:
    return KERNEL not in name and NOT_A_NAME.sub("_", name).endswith(endings)


def read(run):
    if run.kind != "serve" or run.trace is None:
        return None
    endings = pool_endings(getattr(run, "pool_shapes", ()))
    if not endings or run.trace.busy_s <= 0:
        return None
    names, by_kind = set(), dict.fromkeys(KINDS + ("other",), 0)
    for device in run.trace.devices.values():
        for name, ns in xplane.time_by_name(device["ops"]):
            if pool_shaped(name, endings):
                names.add(name)
                by_kind[next((k for k in KINDS if k in name), "other")] += ns
    chips = len(run.trace.devices)
    seconds = sum(by_kind.values()) / 1e9 / chips
    return 100.0 * seconds / run.trace.busy_s, {
        "moved_s": round(seconds, 4), "busy_s": round(run.trace.busy_s, 4), "operations": len(names),
        **{f"{kind}_s": round(ns / 1e9 / chips, 4) for kind, ns in by_kind.items()}}
