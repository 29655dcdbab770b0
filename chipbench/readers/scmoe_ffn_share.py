"""The share of the device's busy time spent in the shortcut expert layer of a
model whose router is wider than its experts twice over: by the experts other
chips hold and by identity experts (LongCat-Flash: ``n_routed_experts`` is the
held count, the router ``chips x held + zero_expert_num`` wide, the expert
width ``expert_ffn_hidden_size``, the layers ``num_layers``).  Operations are
found program by program as ``moe_ffn_share`` finds them (its docstring), under
this configuration's keys:

- ``grouped_matmul`` and ``sort``: as there;
- ``group_metadata``: INTEGER vectors as long as the stack's groups
  (``num_layers`` x held experts), one more, or groups + row tiles - 1
  (integer, because 4 x 16 groups are as many as a decode bucket's 64 rows:
  a projection fused with its norm's statistics is named by its first
  output, ``(f32[64], ...``, 0.32 s of a wave that is no part of this layer);
- ``router``: results ``[slots, W]`` with W the held count times a power of
  two, plus the identity experts (768 = 32 x 16 + 256 here): logits, softmax,
  the biased scores, the masks of an identity pick;
- ``dispatch``: results of R rows by nothing, one, the hidden size or
  ``expert_ffn_hidden_size``.

Left out, because nothing tells them from other operations: the identity add
and the weighted sum over a token's picks, whose results are ``[slots,
hidden]`` like every dense per-token operation's, and the ``[slots, k]`` picks.
A configuration without ``zero_expert_num`` among its sizes gives nothing."""

from chipbench.readers import moe_ffn_share
from chipbench.reduce import scmoe_shapes

KEYS = {"n_routed_experts", "zero_expert_num", "expert_ffn_hidden_size", "num_layers"}


def kind_of(name: str, rows: int, sizes):
    if moe_ffn_share.is_grouped_matmul(name):
        return "grouped_matmul"
    if name.startswith("%sort"):
        return "sort"
    dtype, dims = moe_ffn_share.result_shape(name)
    if not dims:
        return None
    groups, tiles = scmoe_shapes.groups(sizes), max(1, rows // 128)
    if len(dims) == 1 and dtype[0] in "su" and dims[0] in (groups, groups + 1, groups + tiles - 1):
        return "group_metadata"
    if len(dims) == 2 and dims[1] in scmoe_shapes.router_widths(sizes):
        return "router"
    if dims[0] == rows and dims[1:] in ((), (1,), (sizes["hidden_size"],),
                                        (sizes["expert_ffn_hidden_size"],)):
        return "dispatch"
    return None


def operations(run):
    """``[(program, operation, ns, kind)]`` of the shortcut expert layer."""
    found = []
    for device in run.trace.devices.values():
        for program, ops in moe_ffn_share.by_program(device).items():
            rows = {moe_ffn_share.result_shape(n)[1][0] for n, _ in ops
                    if moe_ffn_share.is_grouped_matmul(n)}
            if len(rows) != 1:
                continue
            for name, ns in ops:
                kind = kind_of(name, min(rows), run.sizes)
                if kind:
                    found.append((program, name, ns, kind))
    return found


def read(run):
    if run.kind != "serve" or getattr(run, "trace", None) is None or run.trace.busy_s <= 0:
        return None
    if not KEYS <= set(run.sizes):
        return None
    by_kind = {}
    for _, _, ns, kind in operations(run):
        by_kind[kind] = by_kind.get(kind, 0) + ns
    if not by_kind.get("grouped_matmul"):
        return None
    chips = len(run.trace.devices)
    seconds = sum(by_kind.values()) / 1e9 / chips
    return 100.0 * seconds / run.trace.busy_s, {
        "ffn_s": round(seconds, 4), "busy_s": round(run.trace.busy_s, 4),
        **{f"{kind}_s": round(ns / 1e9 / chips, 4) for kind, ns in sorted(by_kind.items())}}
