"""The share of the device's busy time spent moving the Mamba-2 layers' SSM
state OUTSIDE the two kernels: operations whose result is the whole state or a
batch's rows of it ``[n, H, P, Ns]`` (the slot read before a layer's scan or
update, the write back after it), over the traced wave's busy time.  A step
that gathers the rows out of their slots, updates them and scatters them back
as separate passes over memory, or that copies the state whole, shows here as
time and as operations; the whole-state ones are counted apart."""

from chipbench.readers import moe_ffn_share, ssm_mixer_share
from chipbench.reduce import ssm_shapes


def read(run):
    if run.kind != "serve" or run.trace is None or run.trace.busy_s <= 0:
        return None
    leaves = ssm_shapes.state_leaves(run.sizes, getattr(run, "pool_shapes", ()))
    if leaves is None:
        return None
    shape = lambda name: moe_ffn_share.result_shape(name)[1]
    moves = [(name, ns) for name, ns, kind in ssm_mixer_share.operations(run)
             if kind == "state" and ssm_shapes.is_state_move(shape(name), leaves[1])]
    if not moves:
        return None
    chips = len(run.trace.devices)
    whole = [ns for name, ns in moves if ssm_shapes.is_whole_state(shape(name), leaves[1])]
    seconds = sum(ns for _, ns in moves) / 1e9 / chips
    return 100.0 * seconds / run.trace.busy_s, {
        "moved_s": round(seconds, 4), "busy_s": round(run.trace.busy_s, 4),
        "whole_state_s": round(sum(whole) / 1e9 / chips, 4),
        "whole_state_operations": len(whole) // chips, "row_operations": (len(moves) - len(whole)) // chips}
