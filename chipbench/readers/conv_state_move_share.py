"""The share of the device's busy time spent moving the conv layers' state:
operations whose result is the whole state or a batch's rows of it (the slot
read, the rows beside a step's new value, the write back), over the traced
wave's busy time.  It is to this cache what ``pool.moved_share`` was meant to
be to the pool: a step that copies the state whole, or holds it twice, shows
here as time and as operations whose result is the whole state."""

from chipbench.readers import conv_mixer_share, moe_ffn_share
from chipbench.reduce import lfm2_shapes


def read(run):
    if run.kind != "serve" or run.trace is None or run.trace.busy_s <= 0:
        return None
    leaf = lfm2_shapes.state_leaf(getattr(run, "pool_shapes", ()))
    moves = [(name, ns) for name, ns, kind in conv_mixer_share.operations(run) if kind == "state"]
    if leaf is None or not moves:
        return None
    chips = len(run.trace.devices)
    whole = [ns for name, ns in moves
             if lfm2_shapes.is_whole_state(moe_ffn_share.result_shape(name)[1], leaf)]
    seconds = sum(ns for _, ns in moves) / 1e9 / chips
    return 100.0 * seconds / run.trace.busy_s, {
        "moved_s": round(seconds, 4), "busy_s": round(run.trace.busy_s, 4),
        "whole_state_s": round(sum(whole) / 1e9 / chips, 4),
        "whole_state_operations": len(whole) // chips, "row_operations": (len(moves) - len(whole)) // chips}
