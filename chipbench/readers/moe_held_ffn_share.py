"""The share of the device's busy time spent in the FFN of the experts HELD
here, where a chip holds its share of an expert-parallel layer (DeepSeek-V2:
``n_routed_experts`` is the held count, the router is wider, the dense width
``intermediate_size`` is not an expert's).  Operations are found program by
program as ``moe_ffn_share`` finds them (its docstring), under this
configuration's keys:

- ``grouped_matmul`` and ``sort``: as there;
- ``group_metadata``: vectors as long as the stack's groups (expert layers x
  held experts), one more, or groups + row tiles - 1;
- ``router``: results ``[slots, W]`` or ``[slots, n_group, W / n_group]`` with
  W a power-of-two multiple of the held count (the deployment's chips are not
  among the published keys; 160 = 4 x 40 here);
- ``dispatch``: results of R rows by nothing, one, the hidden size or
  ``moe_intermediate_size``.

The shared expert is a dense matmul over ``[slots, hidden]`` like any other and
cannot be told apart: it is not in this share."""

from chipbench.readers import moe_ffn_share

CHIPS = (1, 2, 4, 8, 16, 32)


def kind_of(name: str, rows: int, groups: int, sizes):
    if moe_ffn_share.is_grouped_matmul(name):
        return "grouped_matmul"
    if name.startswith("%sort"):
        return "sort"
    _, dims = moe_ffn_share.result_shape(name)
    if not dims:
        return None
    held, n_group = sizes["n_routed_experts"], sizes["n_group"]
    tiles = max(1, rows // 128)
    if len(dims) == 1 and dims[0] in (groups, groups + 1, groups + tiles - 1):
        return "group_metadata"
    widths = [held * c for c in CHIPS]
    if len(dims) == 2 and dims[1] in widths:
        return "router"
    if len(dims) == 3 and dims[1] == n_group and dims[1] * dims[2] in widths:
        return "router"
    if dims[0] == rows and dims[1:] in ((), (1,), (sizes["hidden_size"],),
                                        (sizes["moe_intermediate_size"],)):
        return "dispatch"
    return None


def operations(run):
    """``[(program, operation, ns, kind)]`` of the held experts' FFN."""
    sizes = run.sizes
    groups = (sizes["num_hidden_layers"] - sizes["first_k_dense_replace"]) * sizes["n_routed_experts"]
    found = []
    for device in run.trace.devices.values():
        for program, ops in moe_ffn_share.by_program(device).items():
            rows = {moe_ffn_share.result_shape(n)[1][0] for n, _ in ops
                    if moe_ffn_share.is_grouped_matmul(n)}
            if len(rows) != 1:
                continue
            for name, ns in ops:
                kind = kind_of(name, min(rows), groups, sizes)
                if kind:
                    found.append((program, name, ns, kind))
    return found


def read(run):
    if run.kind != "serve" or run.trace is None or run.trace.busy_s <= 0:
        return None
    if not {"n_routed_experts", "moe_intermediate_size", "first_k_dense_replace"} <= set(run.sizes):
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
